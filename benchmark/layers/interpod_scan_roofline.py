"""Scan kernel, inter-pod terms: the least HBM traffic the traced scans
needed with their terms (benchmark/roofline_interpod.py, from the
configuration's shapes) at the chip's peak bandwidth, over the scan
programs' device time. Nothing to read where the recorded scans carry
no terms' shapes."""

from benchmark import roofline, roofline_interpod
from benchmark.layers import _scan


def read(run):
    t = _scan.device_s(run)
    nbytes = roofline_interpod.total_bytes(run.window.scans)
    if t is None or not nbytes:
        return None
    return roofline.roofline_pct(nbytes, t, run.peaks["hbm_bytes_per_s"])

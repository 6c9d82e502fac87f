"""The Pallas launch (jaxe/fastscan.py fast_scan, host side): the program's
plan_upload and kernel_launch spans inside operations, per capacity query
traced. A run with neither span (the XLA route) or with no query (a churn
cycle) reads nothing."""

from benchmark import spans as S
from benchmark.layers import _ops


def read(run):
    queries = len(S.named(run.spans, "bench:query"))
    launch = _ops.in_operations(run.spans, "plan_upload", "kernel_launch")
    if not queries or not launch:
        return None
    return sum(s["dur"] for s in launch) / 1e3 / queries

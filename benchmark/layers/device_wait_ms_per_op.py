"""Device: the host's waits on the device inside operations, per operation:
the program's device_wait spans (a result fetch) and overlay_save (a
what-if's read of the carry's per-batch lanes)."""

from benchmark.layers import _ops


def read(run):
    n = _ops.count(run.spans)
    waits = _ops.in_operations(run.spans, "device_wait", "overlay_save")
    if not n or not waits:
        return None
    return sum(s["dur"] for s in waits) / 1e3 / n

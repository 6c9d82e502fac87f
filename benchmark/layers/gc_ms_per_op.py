"""Python garbage collector: the collections inside the program's root
spans (their gc_ms, from the recorder's gc.callbacks hook), per operation.
Collections in the client's draw and between calls are not in it;
gc_pause_pct counts those too."""

from benchmark.layers import _ops


def read(run):
    n = _ops.count(run.spans)
    ms = [_ops.arg(r, "gc_ms") for r in _ops.roots(run.spans)]
    ms = [v for v in ms if v is not None]
    if not n or not ms:
        return None
    return sum(ms) / n

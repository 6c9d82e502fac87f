"""Stream fold-back (stream/runtime.py): the program's stream_foldback
spans, each cycle's binds applied to the host picture and its journal
drained, per cycle."""

from benchmark.layers import _ops


def read(run):
    cycles = sum(1 for s in run.spans if s["name"] == "bench:schedule")
    folds = _ops.in_operations(run.spans, "stream_foldback")
    if not cycles or not folds:
        return None
    return sum(s["dur"] for s in folds) / 1e3 / cycles

"""Entry (simulator.run_simulation, stream.StreamSession): the time inside
the harness's operation spans that no leaf span of the program covers, per
operation (benchmark/layers/_ops.py). What the program's spans leave
unexplained; it reads a program with few spans too."""

from benchmark.layers import _ops


def read(run):
    n = _ops.count(run.spans)
    if not n:
        return None
    leaves = _ops.leaves(run.spans)
    us = sum(op["dur"] - _ops.covered_us(leaves, op)
             for op in _ops.operations(run.spans))
    return us / 1e3 / n

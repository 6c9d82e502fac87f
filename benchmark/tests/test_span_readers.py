"""The readers of the program's host spans (unnamed_ms_per_op,
device_wait_ms_per_op, foldback_ms, gc_ms_per_op) on hand-built span
lists: a capacity query and a churn cycle as the program spans them, and
the same operations as a program with only its first few spans (the
parent's shape), where each returns nothing but unnamed_ms_per_op."""

import pytest

from benchmark import run as R
from benchmark.layers import _ops
from benchmark.traffic import Window

NAMES = ("unnamed_ms_per_op", "device_wait_ms_per_op", "foldback_ms",
         "gc_ms_per_op")


def span(name, ts, dur, **args):
    s = {"ph": "X", "name": name, "ts": float(ts), "dur": float(dur)}
    if args:
        s["args"] = args
    return s


def query(t0):
    """One capacity query of 1,000 us: 110 us of it under no leaf."""
    return [span("bench:query", t0, 1000),
            span("simulate", t0 + 10, 980, cpu_ms=0.9, gc_ms=0.3,
                 gc_collections=2),
            span("feed", t0 + 10, 10, pods=1000),
            span("backend_schedule", t0 + 30, 900),
            span("compile_cluster", t0 + 30, 400, pods=1000, nodes=5000),
            span("engine_config", t0 + 440, 20),
            span("plan_fast", t0 + 460, 100, pods=1000, nodes=5000),
            span("device_dispatch", t0 + 560, 120),
            span("kernel_launch", t0 + 560, 40, pods=1000, chunks=2),
            span("device_wait", t0 + 600, 80),
            span("decode_placements", t0 + 690, 200),
            span("status", t0 + 940, 40, pods=1000)]


def cycle(t0):
    """One churn cycle of 1,000 us and its what-if of 200 us: 35 us of
    them under no leaf."""
    return [span("bench:ingest", t0, 100),
            span("stream_ingest", t0 + 5, 90, events=1000, cpu_ms=0.1,
                 gc_ms=0.0, gc_collections=0),
            span("bench:schedule", t0 + 100, 900),
            span("stream_cycle", t0 + 105, 890, cpu_ms=0.5, gc_ms=0.2,
                 gc_collections=1),
            span("stream_route", t0 + 105, 50, pods=1000, restage=""),
            span("commit_gather", t0 + 155, 20, rows=900, cells=900),
            span("stream_commit", t0 + 175, 10, rows=900, cells=900),
            span("stream_upload", t0 + 185, 15, bytes=4096),
            span("device_dispatch", t0 + 200, 400),
            span("scan_launch", t0 + 200, 10),
            span("device_wait", t0 + 210, 390),
            span("decode_placements", t0 + 600, 200),
            span("stream_foldback", t0 + 800, 190, pods=1000),
            span("bench:overlay", t0 + 1000, 200),
            span("overlay_query", t0 + 1005, 190, pods=64, cpu_ms=0.1,
                 gc_ms=0.5, gc_collections=1),
            span("overlay_route", t0 + 1005, 20, pods=64, fallback=""),
            span("overlay_save", t0 + 1025, 30),
            span("commit_gather", t0 + 1055, 10, rows=0, cells=0),
            span("stream_commit", t0 + 1065, 10, rows=0, cells=0),
            span("stream_upload", t0 + 1075, 5, bytes=512),
            span("overlay_scan", t0 + 1080, 20),
            span("device_wait", t0 + 1100, 40),
            span("rollback_gather", t0 + 1140, 10, rows=64, cells=64),
            span("overlay_rollback", t0 + 1150, 10, rows=64),
            span("overlay_decode", t0 + 1160, 35)]


PARENT_NAMES = {"bench:query", "bench:ingest", "bench:schedule",
                "bench:overlay", "backend_schedule", "compile_cluster",
                "device_dispatch", "decode_placements", "stream_commit",
                "overlay_scan", "overlay_rollback", "overlay_decode"}


def parent(spans):
    """The same operations as the parent program spans them: no roots, no
    leaf at the phase boundaries, no args beyond the ones it had."""
    return [{k: v for k, v in s.items() if k != "args"} for s in spans
            if s["name"] in PARENT_NAMES]


def run_of(spans):
    return R.Run(Window(), 1.0, {"seconds": 0.0, "count": 0}, {}, None,
                 spans)


def read(spans):
    run = run_of(spans)
    return {n: R.reader("layers", n)(run) for n in NAMES}


CAPACITY = query(0) + query(2000)
CHURN = cycle(0) + cycle(5000)


def test_capacity_query():
    got = read(CAPACITY)
    # before the root and feed, after feed, staging and the wait,
    # before status and after it
    assert got["unnamed_ms_per_op"] == pytest.approx(
        (10 + 10 + 10 + 10 + 50 + 20) / 1e3)
    assert got["device_wait_ms_per_op"] == pytest.approx(80 / 1e3)
    assert got["foldback_ms"] is None
    assert got["gc_ms_per_op"] == pytest.approx(0.3)


def test_churn_cycle_and_what_if():
    got = read(CHURN)
    # the roots' edges: ingest 5 + 5, cycle 5 + 10, what-if 5 + 5
    assert got["unnamed_ms_per_op"] == pytest.approx(35 / 1e3)
    assert got["device_wait_ms_per_op"] == pytest.approx((390 + 30 + 40)
                                                         / 1e3)
    assert got["foldback_ms"] == pytest.approx(190 / 1e3)
    assert got["gc_ms_per_op"] == pytest.approx(0.0 + 0.2 + 0.5)


@pytest.mark.parametrize("spans,unnamed_us", [
    # capacity: leaves compile_cluster 400, device_dispatch 120, decode 200
    (CAPACITY, 1000 - 400 - 120 - 200),
    # churn: ingest 100 unnamed; schedule 900 less commit 10, dispatch
    # 400, decode 200; overlay 200 less commit 10, scan 20, rollback 10,
    # decode 35
    (CHURN, 100 + (900 - 610) + (200 - 75)),
], ids=["capacity", "churn"])
def test_parent_shape(spans, unnamed_us):
    got = read(parent(spans))
    assert got["unnamed_ms_per_op"] == pytest.approx(unnamed_us / 1e3)
    for name in NAMES[1:]:
        assert got[name] is None, name


def test_no_operations_reads_nothing():
    assert all(v is None for v in read([]).values())
    warm = [s for s in cycle(0) if not s["name"].startswith("bench:")]
    assert all(v is None for v in read(warm).values())


def test_leaves_and_roots():
    spans = query(0)
    names = {s["name"] for s in _ops.leaves(spans)}
    assert names == {"feed", "compile_cluster", "engine_config",
                     "plan_fast", "kernel_launch", "device_wait",
                     "decode_placements", "status"}
    assert [r["name"] for r in _ops.roots(spans)] == ["simulate"]
    # a root inside another counts once
    nested = spans + [span("overlay_query", 100, 10, gc_ms=5.0)]
    assert [r["name"] for r in _ops.roots(nested)] == ["simulate"]


def test_rounding_keeps_a_child_inside_its_parent():
    """ts and dur are rounded apart: a child may end a thousandth of a
    microsecond past its parent and still be nested."""
    spans = [span("bench:query", 0, 200), span("simulate", 0, 100),
             span("status", 60.0004, 40.0004)]
    assert [s["name"] for s in _ops.leaves(spans)] == ["status"]

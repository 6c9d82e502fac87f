"""Distributed-tracing goldens (ISSUE 20).

The propagation contract under test: one TraceContext born at a serve
request's admission (or a stream cycle's ingest) reaches every phase it
causes — including ACROSS the WAL-shipping socket, where rec/ckpt frames
carry the originating cycle's trace id and the follower's replay spans
link back via Chrome flow events. A merged multi-process trace must load
in Perfetto as ONE connected graph, `tools/trace_lint.py` must pass on
every artifact we export, and tracing must be invisible to the decisions
themselves (placement-hash chain byte-identical tracing-on vs -off).
"""

import itertools
import json

import numpy as np
import pytest

from tpusim.api.snapshot import ClusterSnapshot, make_node, make_pod, \
    synthetic_cluster
from tpusim.chaos import ChaosClock, DeviceFaultPlan
from tpusim.framework.metrics import register
from tpusim.jaxe.backend import install_chaos, uninstall_chaos
from tpusim.obs import recorder as flight
from tpusim.obs import tracectx
from tpusim.serve import ScenarioFleet, WhatIfRequest
from tpusim.simulator import run_stream_simulation
from tpusim.stream import ChurnLoadGen, StreamPersistence, StreamSession


def _load_tool(name):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(__file__), os.pardir,
                           "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def traced():
    """A deterministic-id FlightRecorder installed for the test body."""
    register().reset()
    counter = itertools.count(1)
    tracectx.set_id_source(lambda: f"{next(counter):016x}")
    rec = flight.install(flight.FlightRecorder(process_name="test"))
    try:
        yield rec
    finally:
        flight.uninstall()
        tracectx.set_id_source(None)


def _scenario(seed, num_nodes=4, num_pods=3):
    rng = np.random.RandomState(seed)
    nodes = [make_node(f"t{seed}-n{i}",
                       milli_cpu=int(rng.choice([2000, 4000, 8000])),
                       memory=int(rng.choice([4, 8])) * 1024 ** 3)
             for i in range(num_nodes)]
    pods = [make_pod(f"t{seed}-p{i}",
                     milli_cpu=int(rng.randint(100, 1500)),
                     memory=int(rng.randint(2 ** 20, 2 ** 30)))
            for i in range(num_pods)]
    return ClusterSnapshot(nodes=nodes), pods


def _warm_twin(num_nodes=8, cycles=3, seed=11):
    session = StreamSession(synthetic_cluster(num_nodes))
    gen = ChurnLoadGen(synthetic_cluster(num_nodes), seed=seed, arrivals=8,
                       evict_fraction=0.25)
    for c in range(cycles):
        session.apply_events(gen.events(c))
        gen.note_bound(session.schedule(gen.batch()))
    return session


def _events(rec, name, ph=None):
    return [e for e in rec.events if e.get("name") == name
            and (ph is None or e.get("ph") == ph)]


def _flow_pairs(rec, cat):
    s = [e for e in rec.events if e.get("ph") == "s" and e.get("cat") == cat]
    f = [e for e in rec.events if e.get("ph") == "f" and e.get("cat") == cat]
    return s, f


# ---------------------------------------------------------------------------
# serve request lifecycles: overlay-hit / staged-fallback / degraded
# ---------------------------------------------------------------------------


class TestServeTraces:
    def test_overlay_hit_path_is_one_connected_trace(self, traced):
        fleet = ScenarioFleet(bucket_size=4, flush_after_s=60.0)
        fleet.attach_stream(_warm_twin(), ref="live")
        _, pods = _scenario(41, num_nodes=8)
        fut = fleet.submit(WhatIfRequest(pods=pods, snapshot_ref="live"))
        fleet.drain()
        assert fut.result().ok
        [ov] = _events(traced, "serve:overlay", ph="X")
        assert ov["args"]["path"] == "resident"
        trace_id = ov["args"]["trace_id"]
        # admission and the overlay answer share the request's context
        assert any(e.get("args", {}).get("trace_id") == trace_id
                   for e in _events(traced, "serve:admit"))
        # the queue hand-off is a paired flow on the SAME context
        s, f = _flow_pairs(traced, "host")
        enq = [e for e in s if e["name"] == "serve:enqueue"]
        assert enq and enq[0]["id"] == f"{trace_id}:q"
        assert {e["id"] for e in s} == {e["id"] for e in f}

    def test_staged_fallback_keeps_the_request_context(self, traced):
        session = _warm_twin(seed=12)
        fleet = ScenarioFleet(bucket_size=1, flush_after_s=60.0)
        fleet.attach_stream(session, ref="live")
        session.force_restage("trace_fallback_test")
        _, pods = _scenario(42, num_nodes=8)
        fut = fleet.submit(WhatIfRequest(pods=pods, snapshot_ref="live"))
        fleet.drain()
        assert fut.result().ok
        [ov] = _events(traced, "serve:overlay", ph="X")
        assert ov["args"]["path"] == "fallback"
        trace_id = ov["args"]["trace_id"]
        # the staged pipeline that answered instead carries the context
        assert any(e.get("args", {}).get("trace_id") == trace_id
                   for e in _events(traced, "serve:stage"))
        assert any(e.get("args", {}).get("trace_id") == trace_id
                   for e in _events(traced, "serve:decode"))

    def test_degraded_breaker_path_is_stamped(self, traced):
        snap, pods = _scenario(43)
        install_chaos(DeviceFaultPlan(
            faults={i: "exception" for i in range(1000)},
            failure_threshold=1, cooldown=1_000_000))
        try:
            fleet = ScenarioFleet(bucket_size=2, clock=ChaosClock())
            responses = fleet.run([WhatIfRequest(pods=pods, snapshot=snap)
                                   for _ in range(2)])
        finally:
            uninstall_chaos()
        assert all(r.ok and r.degraded == "breaker_open"
                   for r in responses)
        degraded = _events(traced, "serve_degraded:breaker_open")
        assert degraded
        # the degraded instants fire under the bucket lead's context
        assert all(e["args"].get("trace_id") for e in degraded)

    def test_serve_trace_exports_lint_clean(self, traced):
        fleet = ScenarioFleet(bucket_size=2, flush_after_s=60.0)
        snap, pods = _scenario(44)
        fleet.run([WhatIfRequest(pods=pods, snapshot=snap)
                   for _ in range(3)])
        lint = _load_tool("trace_lint")
        doc = json.loads(traced.to_chrome_json())
        assert lint.lint_trace(doc) == []


# ---------------------------------------------------------------------------
# WAL-shipping propagation: leader cycle -> socket frame -> follower apply
# ---------------------------------------------------------------------------


def _drive(session, gen, cycles, start=0):
    for cycle in range(start, cycles):
        session.apply_events(gen.events(cycle))
        gen.note_bound(session.schedule(gen.batch()))


def _wait_caught_up(shipper, timeout=15.0):
    import time

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if shipper.drain(timeout=1.0):
            return True
    return False


class TestWalFlowGolden:
    def test_leader_follower_flow_graph_connects(self, traced, tmp_path):
        from tpusim.stream.replicate import FollowerTwin, WalShipper

        follower = FollowerTwin(synthetic_cluster(8))
        leader = StreamSession(synthetic_cluster(8))
        persist = StreamPersistence(str(tmp_path), checkpoint_every=2)
        shipper = WalShipper(persist, follower.address)
        leader.attach_persistence(persist)
        gen = ChurnLoadGen(synthetic_cluster(8), seed=5, arrivals=8,
                           evict_fraction=0.25)
        try:
            _drive(leader, gen, 5)
            assert _wait_caught_up(shipper)
            assert follower.diverged is None
            resend = [dict(fr) for fr in shipper._frames[:3]]
            applied_before = follower.applied_seq
            f_before = len(_flow_pairs(traced, "wal")[1])
            shipper.close()
            # reconnect-with-resume: a resuming sender replays already-
            # acked frames; the dedup guard must swallow them WITHOUT
            # emitting a second flow `f` (no doubled arrows, no orphans)
            import socket
            import time

            from tpusim.stream.replicate import _read_frame, _send_frame

            def hello_handshake(deadline_s=30.0):
                # the follower accepts serially, so on a suite-loaded
                # host one 5s window can transiently miss the hello —
                # retry with a fresh connection (closing ours EOFs any
                # abandoned attempt and unblocks the accept loop)
                deadline = time.monotonic() + deadline_s
                while True:
                    c = socket.create_connection(follower.address,
                                                 timeout=5.0)
                    r = c.makefile("rb")
                    try:
                        hl = _read_frame(r)
                        if hl is not None:
                            return c, r, hl
                    except OSError:
                        pass
                    c.close()
                    if time.monotonic() > deadline:
                        raise AssertionError("follower never sent hello")

            sock, reader, hello = hello_handshake()
            try:
                assert hello["t"] == "hello"
                assert hello["next"] == applied_before + 1
                assert "clk" in hello   # the clock-alignment handshake
                for fr in resend:
                    _send_frame(sock, fr)
                # a gap frame makes the follower drop the connection —
                # the deterministic barrier that the resends were seen
                _send_frame(sock, {"t": "rec", "seq": applied_before + 10,
                                   "rec": {"k": "ev", "c": 0}, "ofs": 0})
                while _read_frame(reader) is not None:
                    pass
            finally:
                sock.close()
            assert follower.applied_seq == applied_before
            assert len(_flow_pairs(traced, "wal")[1]) == f_before
        finally:
            shipper.close()
            persist.close()
            follower.stop()

        # every shipped frame's flow start met exactly one finish: the
        # socket hop did not lose or duplicate a single context
        s, f = _flow_pairs(traced, "wal")
        assert s, "no wal:ship flows were emitted"
        s_ids = [e["id"] for e in s]
        f_ids = [e["id"] for e in f]
        assert sorted(s_ids) == sorted(set(s_ids)), "duplicated flow start"
        assert sorted(f_ids) == sorted(set(f_ids)), "duplicated flow end"
        assert set(s_ids) == set(f_ids)
        # the flow's two endpoints carry the SAME trace id — the leader
        # cycle's context crossed the socket intact
        f_by_id = {e["id"]: e for e in f}
        for ev in s:
            assert ev["args"]["trace_id"] == \
                f_by_id[ev["id"]]["args"]["trace_id"], ev["id"]
        # follower replay spans exist, stamped with leader trace ids
        applies = _events(traced, "replicate:apply")
        leader_ids = {e["args"]["trace_id"] for e in s}
        stamped = [e for e in applies
                   if e.get("args", {}).get("trace_id")]
        assert stamped
        assert {e["args"]["trace_id"] for e in stamped} <= leader_ids
        # both frame kinds crossed with context (checkpoint_every=2)
        frames = {e["args"].get("frame") for e in stamped}
        assert "rec" in frames and "ckpt" in frames
        # the hello handshake pinned the trace_merge clock anchors
        for anchor in ("hello_tx_us", "peer_clk_us", "peer_clk_rx_us"):
            assert anchor in traced.anchors, anchor
        # and the whole artifact is Perfetto-valid
        lint = _load_tool("trace_lint")
        assert lint.lint_trace(json.loads(traced.to_chrome_json())) == []


# ---------------------------------------------------------------------------
# zero-interference: tracing must not move a single placement
# ---------------------------------------------------------------------------


def test_tracing_on_vs_off_chain_is_byte_identical():
    cfg = dict(num_nodes=8, cycles=4, arrivals=8, evict_fraction=0.25,
               seed=3)
    register().reset()
    off = run_stream_simulation(**cfg)
    register().reset()
    flight.install(flight.FlightRecorder(process_name="ab"))
    try:
        on = run_stream_simulation(**cfg)
    finally:
        flight.uninstall()
    assert off["fold_chain"] and on["fold_chain"] == off["fold_chain"]
    assert on["scheduled"] == off["scheduled"]


def test_traced_stream_run_lints_clean_with_exemplars(traced, tmp_path):
    run_stream_simulation(num_nodes=4, cycles=4, arrivals=3, seed=2)
    doc = json.loads(traced.to_chrome_json())
    lint = _load_tool("trace_lint")
    assert lint.lint_trace(doc) == []
    # the session records each loop cycle's ingest once, as its own
    # root, on the trace of the cycle it feeds
    ingests = [e for e in doc["traceEvents"]
               if e.get("name") == "stream_ingest"]
    cycles = [e for e in doc["traceEvents"]
              if e.get("name") == "stream_cycle"]
    assert len(ingests) == len(cycles) == 4
    assert all(e["args"]["events"] >= 0 and "gc_ms" in e["args"]
               for e in ingests)
    assert [e["args"]["trace_id"] for e in ingests] == \
        [e["args"]["trace_id"] for e in cycles]
    # the latency exemplars the run stamped resolve back into the trace
    exposition = register().expose()
    assert 'trace_id="' in exposition, "no exemplars on the exposition"
    assert lint.lint_exemplars(doc, exposition) == []


# ---------------------------------------------------------------------------
# tools/trace_lint.py bites on broken artifacts
# ---------------------------------------------------------------------------


class TestTraceLintBites:
    def test_flags_dangling_flow_and_bad_phase(self):
        lint = _load_tool("trace_lint")
        doc = {"traceEvents": [
            {"name": "x", "ph": "s", "cat": "wal", "id": "7", "ts": 1.0,
             "pid": 1, "tid": 1},
            {"name": "y", "ph": "Z", "ts": 2.0, "pid": 1, "tid": 1},
            {"name": "z", "ph": "f", "cat": "wal", "id": "9", "ts": 3.0,
             "pid": 1, "tid": 1},  # no bp, no matching s
        ]}
        problems = lint.lint_trace(doc)
        assert any("unknown phase" in p for p in problems)
        assert any("without any" in p and "finish" in p for p in problems)
        assert any("without a" in p and "start" in p for p in problems)
        assert any("bp=e" in p for p in problems)

    def test_flags_backwards_clock_beyond_slack(self):
        lint = _load_tool("trace_lint")
        doc = {"traceEvents": [
            {"name": "a", "ph": "i", "s": "g", "ts": 9_000_000.0,
             "pid": 1, "tid": 1},
            {"name": "b", "ph": "i", "s": "g", "ts": 1.0,
             "pid": 1, "tid": 1},
        ]}
        assert any("jumps back" in p for p in lint.lint_trace(doc))
        # same jitter within the slack is tolerated (thread hand-off)
        doc["traceEvents"][1]["ts"] = 9_000_000.0 - 100.0
        assert lint.lint_trace(doc) == []

    def test_flags_unresolved_exemplar(self):
        lint = _load_tool("trace_lint")
        doc = {"traceEvents": [
            {"name": "a", "ph": "i", "s": "g", "ts": 1.0, "pid": 1,
             "tid": 1, "args": {"trace_id": "aa11"}}]}
        text = ('m_bucket{le="+Inf"} 4 # {trace_id="aa11"} 7.0\n'
                'm_bucket{le="+Inf"} 9 # {trace_id="dead"} 1.0\n')
        problems = lint.lint_exemplars(doc, text)
        assert problems == [
            "exemplar trace_id dead on the metrics exposition resolves "
            "to no event in the trace"]


# ---------------------------------------------------------------------------
# tools/trace_merge.py: clock alignment + pid remap
# ---------------------------------------------------------------------------


class TestTraceMerge:
    def _leader(self):
        return {
            "displayTimeUnit": "ms",
            "traceEvents": [
                {"name": "process_name", "ph": "M", "ts": 0, "pid": 9,
                 "tid": 0, "args": {"name": "tpusim-stream"}},
                {"name": "cycle", "ph": "X", "ts": 1500.0, "dur": 10.0,
                 "pid": 9, "tid": 2},
                {"name": "wal:ship", "ph": "s", "cat": "wal", "id": "1",
                 "ts": 1505.0, "pid": 9, "tid": 1},
            ],
            "otherData": {"process_name": "tpusim-stream",
                          "anchors": {"peer_clk_us": 500.0,
                                      "peer_clk_rx_us": 1500.0}},
        }

    def _follower(self):
        return {
            "displayTimeUnit": "ms",
            "traceEvents": [
                {"name": "process_name", "ph": "M", "ts": 0, "pid": 9,
                 "tid": 0, "args": {"name": "tpusim-follow"}},
                {"name": "replicate:apply", "ph": "X", "ts": 600.0,
                 "dur": 5.0, "pid": 9, "tid": 1},
                {"name": "wal:ship", "ph": "f", "cat": "wal", "id": "1",
                 "bp": "e", "ts": 604.0, "pid": 9, "tid": 1},
            ],
            "otherData": {"process_name": "tpusim-follow",
                          "anchors": {"hello_tx_us": 500.0}},
        }

    def test_merge_shifts_follower_into_leader_domain(self):
        merge = _load_tool("trace_merge")
        merged = merge.merge([self._leader(), self._follower()])
        assert merged["otherData"]["shifts_us"] == [0.0, 1000.0]
        by_name = {}
        for ev in merged["traceEvents"]:
            by_name.setdefault(ev["name"], []).append(ev)
        # both processes kept distinct pids despite the os-pid collision
        assert {e["pid"] for e in merged["traceEvents"]} == {1, 2}
        # the follower's apply span landed in the leader's clock domain
        [apply_ev] = by_name["replicate:apply"]
        assert apply_ev["ts"] == 1600.0
        # the flow endpoints pair up in the merged doc — and the whole
        # thing still lints clean
        s, f = [e for e in by_name["wal:ship"] if e["ph"] == "s"], \
            [e for e in by_name["wal:ship"] if e["ph"] == "f"]
        assert s[0]["id"] == f[0]["id"]
        lint = _load_tool("trace_lint")
        assert lint.lint_trace(merged) == []

    def test_merge_without_anchors_is_unshifted(self):
        merge = _load_tool("trace_merge")
        follower = self._follower()
        follower["otherData"]["anchors"] = {}
        merged = merge.merge([self._leader(), follower])
        assert merged["otherData"]["shifts_us"] == [0.0, 0.0]

"""Host spans of one operation: a capacity query (`run_simulation` on the
jax backend, XLA and Pallas routes), a stream cycle (sync and pipelined),
a live what-if and an ingest each open one root span that carries a trace
id, CPU time and collector time, and a leaf span at every phase boundary
beneath it, with counts as args. With the recorder off nothing of this
runs: no event, no gc hook, no profiler metadata."""

import gc
import itertools

import pytest

from tpusim.api.snapshot import ClusterSnapshot, make_node, make_pod, \
    synthetic_cluster
from tpusim.framework.metrics import register
from tpusim.framework.store import DELETED
from tpusim.obs import recorder as flight
from tpusim.obs import tracectx
from tpusim.simulator import run_simulation
from tpusim.stream import StreamSession

ROOT_ARGS = ("cpu_ms", "gc_ms", "gc_collections")


@pytest.fixture
def rec():
    """A recorder with counting trace ids, installed for the test body."""
    register().reset()
    ids = itertools.count(1)
    tracectx.set_id_source(lambda: f"{next(ids):016x}")
    r = flight.install(flight.FlightRecorder(process_name="test"))
    try:
        yield r
    finally:
        flight.uninstall()
        tracectx.set_id_source(None)


def _pods(prefix, n, milli_cpu=100):
    return [make_pod(f"{prefix}-{i}", milli_cpu=milli_cpu, memory=2**20)
            for i in range(n)]


def _session(cycles=2):
    """A twin past its cold-start restage, so the next cycle rides the
    resident stream path."""
    session = StreamSession(synthetic_cluster(8))
    for c in range(cycles):
        session.schedule(_pods(f"warm{c}", 6))
    return session


def _simulate():
    nodes = [make_node(f"n{i}", milli_cpu=4000, memory=2**33)
             for i in range(4)]
    status = run_simulation(_pods("q", 6) + _pods("big", 1, 8000),
                            ClusterSnapshot(nodes=nodes), backend="jax")
    assert len(status.successful_pods) == 6 and len(status.failed_pods) == 1


def _cycle(session):
    placements = session.schedule(_pods("c", 5))
    assert sum(1 for p in placements if p.node_name) == 5


def _pipelined(session):
    session.schedule_pipelined(_pods("p0", 5))
    session.schedule_pipelined(_pods("p1", 5))
    assert len(session.flush()) == 5


def _overlay(session):
    assert len(session.overlay_query(_pods("w", 3))) == 3


def _ingest(session):
    bound = [p for p in session.inc._pods.values() if p.spec.node_name][:2]
    session.apply_events([(DELETED, p) for p in bound])


# path -> (drive, root, leaves beneath the root)
PATHS = {
    "simulate_xla": (_simulate, "simulate", {
        "feed", "compile_cluster", "engine_config", "batch_upload",
        "scan_launch", "device_wait", "decode_placements", "status"}),
    "simulate_pallas": (_simulate, "simulate", {
        "feed", "compile_cluster", "engine_config", "plan_fast",
        "plan_upload", "kernel_launch", "device_wait", "decode_placements",
        "status"}),
    "stream_cycle": (_cycle, "stream_cycle", {
        "stream_route", "commit_gather", "stream_commit", "stream_upload",
        "scan_launch", "device_wait", "decode_placements",
        "stream_foldback"}),
    "pipelined": (_pipelined, "stream_cycle", {
        "stream_route", "commit_gather", "stream_commit", "stream_upload",
        "scan_launch"}),
    "overlay_query": (_overlay, "overlay_query", {
        "overlay_route", "overlay_save", "commit_gather", "stream_commit",
        "stream_upload", "overlay_scan", "device_wait", "rollback_gather",
        "overlay_rollback", "overlay_decode"}),
    "stream_ingest": (_ingest, "stream_ingest", set()),
}


def _inside(s, outer):
    return (s is not outer and s["ts"] >= outer["ts"]
            and s["ts"] + s["dur"] <= outer["ts"] + outer["dur"])


def _spans(rec):
    return [e for e in rec.events if e["ph"] == "X"]


def _traced(rec, path, monkeypatch):
    drive, root, leaves = PATHS[path]
    if path == "simulate_pallas":
        monkeypatch.setenv("TPUSIM_FAST", "1")
        monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    session = None if drive is _simulate else _session()
    rec.events.clear()
    drive(*(() if session is None else (session,)))
    spans = _spans(rec)
    roots = [s for s in spans if s["name"] == root]
    return spans, roots, leaves


@pytest.mark.parametrize("path", sorted(PATHS))
def test_root_and_leaf_spans_share_one_trace(rec, path, monkeypatch):
    spans, roots, leaves = _traced(rec, path, monkeypatch)
    assert roots, f"no {PATHS[path][1]} root"
    root = roots[0]
    for key in ROOT_ARGS:
        assert root["args"][key] >= 0, key
    under = [s for s in spans if _inside(s, root)]
    assert leaves <= {s["name"] for s in under}
    # one trace id stamps the root and every span inside it
    assert {s["args"]["trace_id"] for s in under + [root]} == \
        {root["args"]["trace_id"]}
    # roots do not nest
    assert not any(s["name"] in ("simulate", "stream_cycle",
                                 "overlay_query", "stream_ingest")
                   for s in under)


@pytest.mark.parametrize("path", ["simulate_xla", "simulate_pallas"])
def test_nothing_nests_inside_compile_cluster(rec, path, monkeypatch):
    spans, _, _ = _traced(rec, path, monkeypatch)
    [cc] = [s for s in spans if s["name"] == "compile_cluster"]
    assert not [s for s in spans if _inside(s, cc)]


@pytest.mark.parametrize("path", ["simulate_xla", "simulate_pallas"])
def test_compile_cluster_counts_classes_and_matcher_calls(rec, path,
                                                          monkeypatch):
    spans, _, _ = _traced(rec, path, monkeypatch)
    [cc] = [s for s in spans if s["name"] == "compile_cluster"]
    # four alike nodes are one class; one signature in each of the six
    # tables is one matcher call each
    assert cc["args"]["node_classes"] == 1
    assert cc["args"]["row_evals"] == 6
    assert cc["args"]["nodes"] == 4
    # staging_ms_per_query reads the span's self time
    assert not [s for s in spans if _inside(s, cc)]


def _simulate_antiaffinity():
    """Pods one to a node under required hostname anti-affinity, beside a
    zone-keyed one, with one bound pod."""
    def aff(key):
        return {"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "labelSelector": {"matchLabels": {"app": "a"}},
                "topologyKey": key}]}}

    nodes = [make_node(f"n{i}", milli_cpu=4000, memory=2**33,
                       labels={"zone": f"z{i % 2}"}) for i in range(4)]
    bound = [make_pod("b0", milli_cpu=100, memory=2**20, labels={"app": "a"},
                      affinity=aff("kubernetes.io/hostname"),
                      node_name="n0", phase="Running")]
    pods = [make_pod(f"q{i}", milli_cpu=100, memory=2**20,
                     labels={"app": "a"},
                     affinity=aff("kubernetes.io/hostname" if i else "zone"))
            for i in range(4)]
    status = run_simulation(pods, ClusterSnapshot(nodes=nodes, pods=bound),
                            backend="jax")
    assert len(status.successful_pods) == 3 and len(status.failed_pods) == 1


@pytest.mark.parametrize("path", ["simulate_xla", "simulate_pallas"])
def test_interpod_stage_nests_in_compile_cluster(rec, path, monkeypatch):
    """Inter-pod staging has a span of its own inside compile_cluster,
    and the Pallas plan says which domains its kernel carries."""
    if path == "simulate_pallas":
        monkeypatch.setenv("TPUSIM_FAST", "1")
        monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    _simulate_antiaffinity()
    spans = _spans(rec)
    [cc] = [s for s in spans if s["name"] == "compile_cluster"]
    [ip] = [s for s in spans if s["name"] == "interpod_stage"]
    assert _inside(ip, cc)
    # two keys, the hostname's four domains and the bucket, one term
    # signature (namespaces and selector; the key is not part of it), and
    # two groups: the terms' keys tell the pods apart
    assert ip["args"] == {**ip["args"], "topo_keys": 2, "hostname_keys": 1,
                          "topo_doms": 5, "terms": 1, "groups": 2}
    plan = [s for s in spans if s["name"] == "plan_fast"]
    if path == "simulate_pallas":
        # the zones and the bucket stay; the hostname terms read presence
        assert plan[0]["args"]["interpod_doms"] == 3
        assert plan[0]["args"]["hostname_terms"] == 1
    else:
        assert not plan


def test_simulate_count_args(rec):
    _simulate()
    by = {s["name"]: s["args"] for s in _spans(rec)}
    assert by["simulate"]["pods"] == 7 and by["simulate"]["nodes"] == 4
    assert by["feed"]["pods"] == 7
    assert by["status"]["pods"] == 7
    assert by["batch_upload"]["pods"] == 7


def test_pallas_count_args(rec, monkeypatch):
    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    _simulate()
    by = {s["name"]: s["args"] for s in _spans(rec)}
    assert by["plan_fast"] == {**by["plan_fast"], "pods": 7, "nodes": 4}
    assert by["plan_upload"]["nodes"] >= 4
    assert by["plan_upload"]["bytes"] > 0
    assert by["kernel_launch"]["pods"] == 7
    assert by["kernel_launch"]["chunks"] >= 1


def test_stream_cycle_count_args(rec):
    session = _session()
    _ingest(session)
    rec.events.clear()
    _cycle(session)
    by = {s["name"]: s["args"] for s in _spans(rec)}
    assert by["stream_cycle"]["pods"] == 5
    assert by["stream_route"]["pods"] == 5
    assert by["stream_route"]["restage"] == ""
    # the two evictions' nodes, and the presence cells they touched
    assert 1 <= by["commit_gather"]["rows"] <= 2
    assert by["commit_gather"]["cells"] >= 0
    assert by["stream_upload"]["bytes"] > 0
    assert by["stream_foldback"]["pods"] == 5


@pytest.mark.parametrize("path", ["simulate_pallas", "simulate_xla",
                                  "stream_cycle"])
def test_decode_counts_the_binds_that_share_the_pending_spec(rec, path,
                                                             monkeypatch):
    """Every placed pod's bound copy shares its pending pod's spec parts:
    6 of the query's 7 pods fit, and all 5 of the cycle's."""
    spans, _, _ = _traced(rec, path, monkeypatch)
    decode = [s for s in spans if s["name"] == "decode_placements"]
    assert [s["args"]["shared_binds"] for s in decode] == [
        5 if path == "stream_cycle" else 6]


def test_overlay_and_ingest_count_args(rec):
    session = _session()
    rec.events.clear()
    _ingest(session)
    _overlay(session)
    by = {s["name"]: s["args"] for s in _spans(rec)}
    assert by["stream_ingest"]["events"] == 2
    assert by["overlay_query"]["pods"] == 3
    assert by["overlay_route"] == {**by["overlay_route"], "pods": 3,
                                   "fallback": ""}
    assert 1 <= by["rollback_gather"]["rows"] <= 3
    # the evictions are committed early, then rolled back with the query
    assert 1 <= by["commit_gather"]["rows"] <= 2


def test_pipelined_decode_keeps_the_dispatching_cycle_trace(rec):
    session = _session()
    rec.events.clear()
    _pipelined(session)
    spans = _spans(rec)
    roots = [s for s in spans if s["name"] == "stream_cycle"]
    assert len(roots) == 2 and all(r["args"]["pipelined"] for r in roots)
    first, second = (r["args"]["trace_id"] for r in roots)
    assert first != second
    # cycle 0 is decoded inside cycle 1's call, under cycle 0's trace
    decodes = [s for s in spans if s["name"] == "stream_decode"]
    assert [d["args"]["trace_id"] for d in decodes] == [first, second]
    folds = [s for s in spans if s["name"] == "stream_foldback"]
    assert [f["args"]["pods"] for f in folds] == [5, 5]
    assert [f["args"]["trace_id"] for f in folds] == [first, second]
    assert _inside(decodes[0], roots[1])


def test_a_root_joins_an_active_trace(rec):
    session = _session()
    ctx = tracectx.start()
    with tracectx.activate(ctx):
        _ingest(session)
        _overlay(session)
    ids = {s["args"]["trace_id"] for s in _spans(rec)
           if s["name"] in ("stream_ingest", "overlay_query")}
    assert ids == {ctx.trace_id}


def test_root_counts_the_collections_inside_it(rec):
    with flight.root("stream_cycle"):
        gc.collect()
        with flight.root("overlay_query") as inner:
            assert inner is flight.NOOP_SPAN
    [root] = _spans(rec)
    assert root["args"]["gc_collections"] >= 1
    assert root["args"]["gc_ms"] > 0
    assert root["args"]["cpu_ms"] >= 0


def test_profiled_scopes_carry_the_recorder_clock(rec, monkeypatch):
    seen = []
    monkeypatch.setattr(flight, "_annotation_cls",
                        lambda name, **kw: seen.append((name, kw)))
    before = rec.now_us()
    flight.profiled("tpusim:x")
    (name, kw), = seen
    assert name == "tpusim:x"
    assert before <= kw["rec_us"] <= rec.now_us()


class TestRecorderOff:
    def test_no_event_no_hook_no_metadata(self, monkeypatch):
        assert flight.get_recorder() is None
        idle = flight.FlightRecorder()
        assert flight.root("simulate") is flight.NOOP_SPAN
        _simulate()
        session = _session()
        _ingest(session)
        _cycle(session)
        _overlay(session)
        assert not idle.events
        assert flight._GC not in gc.callbacks
        assert tracectx.current() is None
        seen = []
        monkeypatch.setattr(flight, "_annotation_cls",
                            lambda name, **kw: seen.append((name, kw)))
        flight.profiled("tpusim:x")
        assert seen == [("tpusim:x", {})]

    def test_install_adds_the_gc_hook_once_and_uninstall_removes_it(self):
        flight.install(flight.FlightRecorder())
        flight.install(flight.FlightRecorder())
        try:
            assert gc.callbacks.count(flight._GC) == 1
        finally:
            flight.uninstall()
        assert flight._GC not in gc.callbacks

"""The anti-affinity cell's parts at a size the CPU holds: the inter-pod
reference against the program's host engine, its control, the roofline's
bytes and readers, and the cell's traffic end to end, sound and with the
kernel's budget forced down."""

import random

import numpy as np
import pytest

from benchmark import cluster as C
from benchmark import reference_interpod as RI
from benchmark import roofline, roofline_interpod
from benchmark import traffic as T
from benchmark.tests import tiny

SEED = 2**31 + 123


def antiaffinity(nodes: int = 64, bound: int = 20) -> dict:
    cfg = tiny.load("benchmark/configs/sched-perf-antiaffinity-5000.json")
    cfg["nodes"]["count"] = nodes
    cfg["bound_pods"] = bound
    return cfg


def traffic(pods: int = 100) -> dict:
    tr = tiny.load("benchmark/traffic/capacity-interpod.json")
    tr["backlog_pods"] = pods
    return tr


def driver(cfg=None, tr=None, seed=SEED):
    cfg = cfg or antiaffinity()
    return T.driver(C.generate(cfg, seed), tr or traffic(), seed)


# -- the reference against the host engine ----------------------------------

def engine_answers(snapshot, pods, hard_weight=10):
    from tpusim.backends import ReferenceBackend

    placed = ReferenceBackend(
        hard_pod_affinity_symmetric_weight=hard_weight).schedule(
            pods, snapshot)
    return T.fit_answers(placed)


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 2**40 + 3])
def test_cell_reference_matches_the_host_engine(seed):
    """The cell's own queries, more pods than nodes: every pod past the
    free nodes gets the inter-pod FitError text."""
    from benchmark.drivers import capacity_interpod as D

    drv = driver(seed=seed)
    cl, backlog = drv.query(0)
    names = cl.nodes.names
    snap = C.snapshot(cl)
    snap.pods = D.pod_objects(cl.config, cl.bound, "init",
                              [names[i] for i in cl.bound_node])
    pods = D.pod_objects(cl.config, backlog, "measure")
    want = engine_answers(snap, pods[::-1])
    got = drv.reference_answers(0)
    assert RI.R.mismatches(got, want) == 0
    failed = [v for v in want.values() if v.startswith("!")]
    assert len(failed) == 100 - (64 - 20)
    assert all("64 node(s) didn't match pod affinity/anti-affinity" in v
               and "didn't satisfy existing pods anti-affinity rules" in v
               for v in failed)


def _random_case(seed: int):
    """Small seeded clusters with zones, nodes without some labels, and
    pods of several kinds with required affinity and anti-affinity on the
    hostname and on the zone. Affinity terms name only kinds that are
    never pending (anchors), and one pending pod whose term matches itself
    (the first pod of its group): no affinity term scores."""
    from tpusim.api.snapshot import ClusterSnapshot, make_node, make_pod

    rng = random.Random(seed)
    n = rng.randint(12, 30)
    labels = []
    nodes = []
    for i in range(n):
        node = make_node(f"n{i}", milli_cpu=rng.choice([1000, 2000]),
                         memory=4 * 1024**3, pods=rng.choice([2, 4, 110]),
                         labels={"zone": f"z{i % 3}"} if i % 5 else None)
        if i % 11 == 3:
            del node.metadata.labels[RI.HOSTNAME]
        nodes.append(node)
        labels.append(dict(node.metadata.labels))
    menu = [
        ("web", "anti", "web", RI.HOSTNAME),
        ("cache", "aff", "anchor", RI.HOSTNAME),
        ("db", "anti", "db", "zone"),
        ("near", "aff", "zanchor", "zone"),
        ("batch", None, None, None)]
    anchors = [("anchor", None, None, None), ("zanchor", None, None, None)]
    kinds, kind_of = [], {}

    def kind(entry, ns):
        key = (entry, ns)
        if key not in kind_of:
            app, rule, target, tkey = entry
            t = (RI.term([], {"app": target}, tkey),) if rule else ()
            kind_of[key] = len(kinds)
            kinds.append(RI.Kind(ns, (("app", app),),
                                 affinity=t if rule == "aff" else (),
                                 anti=t if rule == "anti" else ()))
        return kind_of[key]

    def obj(name, entry, ns, **kw):
        app, rule, target, tkey = entry
        aff = None
        if rule:
            side = "podAffinity" if rule == "aff" else "podAntiAffinity"
            aff = {side: {"requiredDuringSchedulingIgnoredDuringExecution": [
                {"labelSelector": {"matchLabels": {"app": target}},
                 "topologyKey": tkey}]}}
        return make_pod(name, milli_cpu=rng.choice([100, 300]),
                        memory=2**27, namespace=ns, labels={"app": app},
                        affinity=aff, **kw)

    placed, placed_kind = [], []
    for j in range(n // 3):
        entry = rng.choice([menu[0], menu[2], menu[4]] + anchors)
        placed.append(obj(f"e{j}", entry, "a", node_name=f"n{rng.randrange(n)}",
                          phase="Running"))
        placed_kind.append(kind(entry, "a"))
    pods, pod_kind = [], []
    solo = rng.randrange(2 * n)
    for j in range(2 * n):
        entry = (rng.choice(menu) if j != solo
                 else ("solo", "aff", "solo", "zone"))
        ns = rng.choice(["a", "b"])
        pods.append(obj(f"p{j}", entry, ns))
        pod_kind.append(kind(entry, ns))
    return (ClusterSnapshot(nodes=nodes, pods=placed), pods, labels, kinds,
            placed_kind, pod_kind)


def _reference(snapshot, pods, labels, kinds, placed_kind, pod_kind,
               tie="rr"):
    from tpusim.engine.resources import NodeInfo, get_resource_request

    def req(p):
        r = get_resource_request(p)
        return r.milli_cpu, r.memory

    alloc = []
    for nd in snapshot.nodes:
        ni = NodeInfo()
        ni.set_node(nd)
        alloc.append(ni.allocatable_resource)
    s = RI.Scheduler(
        [a.milli_cpu for a in alloc], [a.memory for a in alloc],
        [a.allowed_pod_number for a in alloc], np.zeros(len(alloc), bool),
        labels, kinds, tie=tie)
    index = {nd.name: i for i, nd in enumerate(snapshot.nodes)}
    for p, k in zip(snapshot.pods, placed_kind):
        c, m = req(p)
        s.place(index[p.spec.node_name], c, m, k)
    cm = np.array([req(p) for p in pods], np.int64)
    ch, err = s.schedule(cm[:, 0], cm[:, 1], np.zeros(len(pods), bool),
                         pod_kind)
    return RI.R.answers([p.name for p in pods],
                        [nd.name for nd in snapshot.nodes], ch, err)


@pytest.mark.parametrize("seed", range(8))
def test_reference_matches_the_host_engine_on_random_terms(seed):
    case = _random_case(seed)
    want = engine_answers(case[0], case[1])
    got = _reference(*case)
    assert RI.R.mismatches(got, want) == 0
    assert any(v.startswith("!") for v in want.values())


def test_reference_refuses_what_it_does_not_score():
    k = RI.Kind("a", (("app", "x"),),
                affinity=(RI.term([], {"app": "x"}, "zone"),))
    s = RI.Scheduler([1000] * 2, [2**30] * 2, [4] * 2, [False] * 2,
                     [{"zone": "z"}] * 2, [k])
    s.schedule([100], [2**20], [False], [0])      # the first pod: fine
    with pytest.raises(ValueError, match="would score"):
        s.schedule([100], [2**20], [False], [0])
    with pytest.raises(ValueError):
        RI.term([], {"app": "x"}, "")


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_fails_the_cell_check(seed):
    from benchmark.control import control_reading

    cfg, tr = antiaffinity(), traffic()
    sound = control_reading(cfg, tr, seed, 4, tie="rr")
    control = control_reading(cfg, tr, seed, 4)
    assert sound["wrong_answers"] == 0 and sound["fast_fallbacks"] == 0
    assert control["wrong_answers"] > 0


# -- bytes and readers --------------------------------------------------------

def test_interpod_scan_bytes_from_shapes():
    from benchmark.drivers.capacity_interpod import scan_shape

    cfg = antiaffinity(5000, 1000)
    shape = scan_shape(cfg, C.make_nodes(cfg))
    # one group (both namespaces are in the term), one term: its own bit
    # and the group's bit against it; no domain cells off the hostname
    assert shape == {"groups": 1, "match_bits": 2, "dom_cells": 0}
    b = roofline_interpod.scan_bytes(1000, 5000, **shape)
    assert b == roofline.scan_bytes(1000, 5000) + 1000 * 8 + 4 * 5000
    assert roofline_interpod.total_bytes(
        [{"pods": 1000, "nodes": 5000}]) == 0


def test_readers_read_nothing_without_the_terms():
    from benchmark import byname

    class Run:
        spans = [{"name": "bench:query", "ph": "X", "ts": 0, "dur": 10},
                 {"name": "compile_cluster", "ph": "X", "ts": 1, "dur": 5}]
        reduced = None
        window = T.Window(scans=[{"pods": 10, "nodes": 10}])

    assert byname.load("layers", "interpod_staging_ms").read(Run) is None
    assert byname.load("layers", "interpod_scan_roofline").read(Run) is None
    Run.spans = Run.spans + [
        {"name": "interpod_stage", "ph": "X", "ts": 2, "dur": 3}]
    assert byname.load("layers", "interpod_staging_ms").read(Run) == \
        pytest.approx(0.003)


# -- the cell end to end ------------------------------------------------------

def _run(drv, seconds=1.0):
    drv.setup()
    w = drv.window(seconds)
    drv.release()
    return w, drv.check()


def test_cell_rehearsal():
    w, check = _run(driver())
    queries = len(w.latencies["query"])
    assert queries >= 1 and w.decided == 100 * queries
    assert check.correct, check.numbers()
    assert check.numbers()["fast_fallbacks"]["value"] == 0


def test_cell_rehearsal_on_the_kernel(monkeypatch):
    """The kernel's route, in the interpreter: no fallback, every answer
    the reference's."""
    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    tr = traffic(64)
    tr["warmup_queries"] = 1
    w, check = _run(driver(antiaffinity(64, 20), tr), seconds=0.0)
    assert check.correct, check.numbers()
    assert check.compared == 64


def test_fallback_in_the_window_fails_the_check(monkeypatch):
    drv = driver()
    drv.setup()
    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    monkeypatch.setenv("TPUSIM_FAST_MAX_TOPO_DOMS", "0")
    drv.window(0.5)
    drv.release()
    check = drv.check()
    assert check.numbers()["fast_fallbacks"]["value"] > 0
    assert check.numbers()["wrong_answers"]["value"] == 0
    assert not check.correct


def test_fallback_in_the_warmup_stops_the_run(monkeypatch):
    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    monkeypatch.setenv("TPUSIM_FAST_MAX_TOPO_DOMS", "0")
    with pytest.raises(RuntimeError, match="left the Pallas kernel"):
        driver().setup()


def test_init_pods_one_to_a_node():
    drv = driver(antiaffinity(64, 60))
    for q in range(3):
        cl, _ = drv.query(q)
        assert len(set(cl.bound_node.tolist())) == 60
    assert not np.array_equal(drv.query(0)[0].bound_node,
                              drv.query(1)[0].bound_node)


def test_configuration_is_the_source_template():
    cfg = antiaffinity(5000, 1000)
    base = tiny.load("benchmark/configs/sched-perf-basic-5000.json")
    assert cfg["nodes"] == base["nodes"] and cfg["pods"] == base["pods"]
    assert cfg["pod_template"]["anti_affinity"] == [
        {"match_labels": {"color": "green"},
         "topology_key": "kubernetes.io/hostname",
         "namespaces": ["sched-1", "sched-0"]}]
    assert cfg["reduced"] == []

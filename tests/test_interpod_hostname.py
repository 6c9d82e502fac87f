"""Hostname-keyed inter-pod terms on the Pallas kernel's node-wise presence.

A kubernetes.io/hostname key whose values are one per node leaves the
kernel's domain axis: its own terms read the node's matched presence, the
existing pods' terms on it read the presence carry at the node, and it
has no presence_dom rows. TPUSIM_FAST_MAX_TOPO_DOMS then bounds only the
other keys' domains. Seeded clusters above the old 64-domain budget, with
hostname- and zone-keyed terms on both sides, run through the kernel (in
the interpreter here), the XLA scan and the host engine, placement for
placement and FitError text for FitError text.
"""

import random

import numpy as np
import pytest

pytest.importorskip("jax")

from tpusim.jaxe import ensure_x64  # noqa: E402

ensure_x64()

from tpusim.api.snapshot import ClusterSnapshot, make_node, make_pod  # noqa: E402
from tpusim.api.types import LABEL_HOSTNAME  # noqa: E402
from tpusim.jaxe.fastscan import plan_fast  # noqa: E402
from tpusim.jaxe.kernels import (  # noqa: E402
    carry_init,
    config_for,
    pod_columns_to_device,
    schedule_scan,
    statics_to_device,
)
from tpusim.jaxe.state import NUM_FIXED_BITS, compile_cluster  # noqa: E402

REQ = "requiredDuringSchedulingIgnoredDuringExecution"
PREF = "preferredDuringSchedulingIgnoredDuringExecution"


def term(app, key):
    return {"labelSelector": {"matchLabels": {"app": app}},
            "topologyKey": key}


# pending pod kinds: (labels, affinity)
PENDING = [
    # one per node: required anti-affinity to its own kind, by hostname
    ({"app": "web"}, {"podAntiAffinity": {REQ: [term("web", LABEL_HOSTNAME)]}}),
    # beside a web pod: required affinity by hostname
    ({"app": "cache"}, {"podAffinity": {REQ: [term("web", LABEL_HOSTNAME)]}}),
    # one per zone
    ({"app": "db"}, {"podAntiAffinity": {REQ: [term("db", "zone")]}}),
    ({"app": "batch"}, None),
    # scored only: away from web by hostname, toward cache by zone
    ({"app": "batch"}, {
        "podAntiAffinity": {PREF: [{"weight": 10, "podAffinityTerm":
                                    term("web", LABEL_HOSTNAME)}]},
        "podAffinity": {PREF: [{"weight": 5, "podAffinityTerm":
                                term("cache", "zone")}]}}),
    # beside a pod that never exists: fits nowhere, with the FitError of
    # every node that passes the earlier predicates
    ({"app": "orphan"}, {"podAffinity": {REQ: [term("ghost",
                                                    LABEL_HOSTNAME)]}}),
]
# bound pod kinds: web as above, and guards whose required anti-affinity
# keeps batch pods off their node and db pods out of their zone
BOUND = [
    PENDING[0],
    ({"app": "guard"}, {"podAntiAffinity": {REQ: [term("batch",
                                                       LABEL_HOSTNAME)]}}),
    ({"app": "zguard"}, {"podAntiAffinity": {REQ: [term("db", "zone")]}}),
]


def cluster(seed: int, n_nodes: int, n_pods: int, shared_hostname=False):
    rng = random.Random(seed)
    nodes = []
    for i in range(n_nodes):
        labels = {"zone": f"z{i % 3}"} if i % 7 else {}
        node = make_node(f"n{i}", milli_cpu=rng.choice([1000, 2000, 4000]),
                         memory=rng.choice([2, 4]) * 1024**3,
                         pods=rng.choice([3, 8, 110]), labels=labels)
        if i % 23 == 5:
            # a node without the hostname label matches no hostname term
            del node.metadata.labels[LABEL_HOSTNAME]
        nodes.append(node)
    if shared_hostname:
        # two nodes with one hostname value: one domain of two nodes
        nodes[1].metadata.labels[LABEL_HOSTNAME] = nodes[2].name
    bound = []
    for i in range(n_nodes // 4):
        labels, aff = rng.choice(BOUND)
        bound.append(make_pod(f"e{i}", milli_cpu=100, memory=2**26,
                              node_name=f"n{rng.randrange(n_nodes)}",
                              phase="Running", labels=labels, affinity=aff))
    pods = []
    for i in range(n_pods):
        labels, aff = rng.choice(PENDING)
        pods.append(make_pod(f"p{i}", milli_cpu=rng.randrange(1, 6) * 100,
                             memory=rng.randrange(1, 6) * 2**27,
                             labels=labels, affinity=aff))
    return ClusterSnapshot(nodes=nodes, pods=bound), pods


def _outcomes(placements):
    return [(p.pod.name, p.node_name, p.message) for p in placements]


def _kernel_xla_engine(monkeypatch, snapshot, pods):
    """The kernel's arrays against the XLA scan's, and the kernel's
    placements (through JaxBackend, decoded) against the host engine's.
    Returns the plan and the kernel's placements."""
    from tpusim.backends import ReferenceBackend
    from tpusim.jaxe import fastscan
    from tpusim.jaxe.backend import JaxBackend

    compiled, cols = compile_cluster(snapshot, list(pods))
    config = config_for([compiled], most_requested=False,
                        num_reason_bits=NUM_FIXED_BITS
                        + len(compiled.scalar_names))
    plan, why = plan_fast(config, compiled, cols)
    assert plan is not None, why
    _, choices, counts, adv = schedule_scan(
        config, carry_init(compiled), statics_to_device(compiled),
        pod_columns_to_device(cols))

    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    runs = []
    real = fastscan.fast_scan

    def recorded(p, **kw):
        out = real(p, chunk=64, **kw)
        runs.append(out)
        return out

    monkeypatch.setattr(fastscan, "fast_scan", recorded)
    got = JaxBackend().schedule(list(pods), snapshot)
    assert len(runs) == 1, "the Pallas kernel did not run"
    f_choices, f_counts, f_adv = runs[0]
    assert np.array_equal(f_choices, np.asarray(choices))
    assert np.array_equal(f_counts, np.asarray(counts))
    assert np.array_equal(f_adv, np.asarray(adv))
    want = ReferenceBackend().schedule(list(pods), snapshot)
    assert _outcomes(got) == _outcomes(want)
    return plan, got


@pytest.mark.parametrize("seed,n_nodes,n_pods", [
    (1, 80, 100), (2**31 + 5, 131, 80), (77, 200, 64)])
def test_hostname_terms_match_xla_and_host_engine(monkeypatch, seed,
                                                  n_nodes, n_pods):
    snapshot, pods = cluster(seed, n_nodes, n_pods)
    plan, got = _kernel_xla_engine(monkeypatch, snapshot, pods)
    # hostname domains left the axis: only the zones (and bucket 0) stay
    assert plan.topo_nodewise.count(True) == 1
    assert plan.n_topo_doms_ip == 4
    assert plan.hostname_terms >= 3
    placed = [p for p in got if p.node_name]
    failed = [p for p in got if not p.node_name]
    assert placed and failed
    assert any("affinity" in p.message for p in failed)


def test_shared_hostname_value_keeps_its_domains(monkeypatch):
    """Two nodes with one hostname value are one domain for the existing
    pods' terms (NodesHaveSameTopologyKey): the key stays on the domain
    axis, and the kernel still matches both oracles."""
    snapshot, pods = cluster(9, 12, 24, shared_hostname=True)
    plan, _ = _kernel_xla_engine(monkeypatch, snapshot, pods)
    assert plan.topo_nodewise == (False, False)
    assert plan.presence_dom is not None


def _antiaffinity_cluster(n_nodes: int, bound: int, pending: int,
                          zone_doms: int = 0):
    """scheduler_perf SchedulingPodAntiAffinity's shape: nodes labelled
    with their hostname, pods of one template whose required
    anti-affinity (color=green, hostname, both namespaces) keeps them one
    to a node; bound pods one to a node."""
    aff = {"podAntiAffinity": {REQ: [{
        "labelSelector": {"matchLabels": {"color": "green"}},
        "topologyKey": LABEL_HOSTNAME,
        "namespaces": ["sched-1", "sched-0"]}]}}
    labels = {"color": "green", "name": "test"}
    nodes = [make_node(f"node-{i}", milli_cpu=4000, memory=32 * 1024**3,
                       labels={"zone": f"z{i % zone_doms}"}
                       if zone_doms else None)
             for i in range(n_nodes)]
    placed = [make_pod(f"init-{j}", milli_cpu=100, memory=500 * 2**20,
                       namespace="sched-0", labels=labels, affinity=aff,
                       node_name=f"node-{(7 * j) % n_nodes}",
                       phase="Running") for j in range(bound)]
    pods = [make_pod(f"measure-{j}", milli_cpu=100, memory=500 * 2**20,
                     namespace="sched-1", labels=labels, affinity=aff)
            for j in range(pending)]
    return ClusterSnapshot(nodes=nodes, pods=placed), pods


def _plan(snapshot, pods):
    compiled, cols = compile_cluster(snapshot, pods)
    config = config_for([compiled], most_requested=False,
                        num_reason_bits=NUM_FIXED_BITS
                        + len(compiled.scalar_names))
    return compiled, plan_fast(config, compiled, cols)


def test_plan_takes_the_5000_node_antiaffinity_shape():
    """The benchmark cell's shape: 5,001 hostname domains, none of them
    on the kernel's axis; one merged group; no presence_dom at all."""
    snapshot, pods = _antiaffinity_cluster(5000, 1000, 1000)
    compiled, (plan, why) = _plan(snapshot, pods)
    assert compiled.n_topo_doms == 5001
    assert plan is not None, why
    assert plan.n_topo_doms_ip == 1
    assert plan.topo_nodewise == (True,)
    assert plan.presence_dom is None
    assert plan.hostname_terms == 1


def test_plan_signature_does_not_follow_the_bound_pods():
    """Bound pods on other nodes stage the same kernel variant."""
    from tpusim.jaxe.backend import plan_signature

    sigs = set()
    for shift in (0, 3):
        snapshot, pods = _antiaffinity_cluster(300, 60, 50)
        for j, p in enumerate(snapshot.pods):
            p.spec.node_name = f"node-{(11 * j + shift) % 300}"
        sigs.add(plan_signature(_plan(snapshot, pods)[1][0]))
    assert len(sigs) == 1


def test_budget_counts_only_other_domains(monkeypatch):
    """TPUSIM_FAST_MAX_TOPO_DOMS bounds the non-hostname domains, and the
    refusal names the key that crossed it."""
    snapshot, pods = _antiaffinity_cluster(200, 10, 10, zone_doms=70)
    for p in pods[:3]:
        p.spec.affinity.pod_anti_affinity.required[0].topology_key = "zone"
    _, (plan, why) = _plan(snapshot, pods)
    assert plan is None
    assert "71 topology domains exceed" in why and "'zone'" in why
    monkeypatch.setenv("TPUSIM_FAST_MAX_TOPO_DOMS", "71")
    _, (plan, why) = _plan(snapshot, pods)
    assert plan is not None, why
    assert plan.n_topo_doms_ip == 71


def test_cluster_without_interpod_terms_keeps_its_signature():
    """No inter-pod term: the plan and its kernel variant key are the
    ones the fast path staged before hostname keys left the domain axis."""
    from tpusim.jaxe.backend import plan_signature

    nodes = [make_node(f"n{i}") for i in range(300)]
    pods = [make_pod(f"p{i}", milli_cpu=100, memory=500 * 2**20)
            for i in range(40)]
    _, (plan, why) = _plan(ClusterSnapshot(nodes=nodes), pods)
    assert plan is not None, why
    assert not plan.has_interpod and plan.topo_nodewise == ()
    assert plan.presence_dom is None and plan.topo_rows is None
    assert plan_signature(plan) == (384, False, 0, 0, 0, False, False,
                                    False, False)

"""Gang admission (tpusim/gang): all-or-nothing pod-group scheduling.

Coverage: annotation schema and feed planning; host-oracle vs device-kernel
packing parity (bit-exact choices, the AUTO seam's contract); all-or-nothing
semantics on both the jax group driver and the reference orchestrator
(zero binds + ONE shared FitError on rejection, min-available partial
admission); gang-free workloads identical to the pre-gang paths on every
route; preemption gang release; chaos node_delete rollback of every member
(the no-partial-gang-bound invariant).
"""

import numpy as np
import pytest

from tpusim.api.snapshot import ClusterSnapshot, make_node, make_pod
from tpusim.framework.metrics import SchedulerMetrics
from tpusim.framework.metrics import register as register_metrics
from tpusim.gang.group import (
    GANG_MIN_AVAILABLE_ANNOTATION,
    GANG_NAME_ANNOTATION,
    PodGroup,
    gang_min_available,
    gang_name,
    has_gangs,
    mark_gang,
    split_feed,
)
from tpusim.simulator import run_simulation

jax = pytest.importorskip("jax")


def _cluster(num_nodes=6, milli_cpu=4000, racks=True, zones=False):
    nodes = []
    for i in range(num_nodes):
        labels = {}
        if racks:
            labels["topology.kubernetes.io/rack"] = f"rack-{i // 2}"
        if zones:
            labels["failure-domain.beta.kubernetes.io/region"] = "r1"
            labels["failure-domain.beta.kubernetes.io/zone"] = f"z{i // 3}"
        nodes.append(make_node(f"node-{i}", milli_cpu=milli_cpu,
                               labels=labels))
    return ClusterSnapshot(nodes=nodes, pods=[])


def _gang(name, size, milli_cpu=1000, min_available=0):
    return [mark_gang(make_pod(f"{name}-{i}", milli_cpu=milli_cpu),
                      name, min_available=min_available)
            for i in range(size)]


def _assignments(st):
    return ({p.metadata.name: p.spec.node_name for p in st.successful_pods},
            {p.metadata.name for p in st.failed_pods})


# ---------------------------------------------------------------------------
# annotations + feed planning
# ---------------------------------------------------------------------------


def test_annotation_roundtrip():
    pod = mark_gang(make_pod("a"), "train", min_available=2)
    assert pod.metadata.annotations[GANG_NAME_ANNOTATION] == "train"
    assert pod.metadata.annotations[GANG_MIN_AVAILABLE_ANNOTATION] == "2"
    assert gang_name(pod) == "train"
    assert gang_min_available(pod) == 2
    assert gang_name(make_pod("b")) == ""
    assert gang_min_available(make_pod("b")) == 0
    assert has_gangs([make_pod("b"), pod])
    assert not has_gangs([make_pod("b")])


def test_min_available_defaults_and_clamps():
    assert PodGroup("g", _gang("g", 4)).min_available == 4
    assert PodGroup("g", _gang("g", 4, min_available=2)).min_available == 2
    # a declared floor above the group size clamps to the size
    assert PodGroup("g", _gang("g", 3, min_available=9)).min_available == 3


def test_split_feed_pulls_gang_forward():
    solos = [make_pod(f"s{i}") for i in range(3)]
    g = _gang("g", 3)
    feed = [solos[0], g[0], solos[1], g[1], solos[2], g[2]]
    segs = split_feed(feed)
    # decision point at the FIRST member's position: [s0] [gang] [s1 s2]
    assert [s.group.name if s.group else None for s in segs] == \
        [None, "g", None]
    assert [p.metadata.name for p in segs[0].pods] == ["s0"]
    assert [p.metadata.name for p in segs[1].group.pods] == \
        ["g-0", "g-1", "g-2"]
    assert [p.metadata.name for p in segs[2].pods] == ["s1", "s2"]


# ---------------------------------------------------------------------------
# oracle vs kernel parity (the AUTO seam's bit-exactness contract)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,n,seed", [(2, 3, 0), (4, 8, 1), (7, 16, 2),
                                      (12, 5, 3)])
def test_gang_select_oracle_kernel_parity(m, n, seed):
    import jax.numpy as jnp

    from tpusim.gang.oracle import select_oracle
    from tpusim.jaxe import ensure_x64
    from tpusim.jaxe.kernels import GangIn, gang_select

    ensure_x64()
    rng = np.random.RandomState(seed)
    feasible = rng.rand(m, n) > 0.3
    score = rng.randint(0, 10_000, size=(m, n)).astype(np.int64)
    req_cpu = rng.randint(0, 2000, size=m).astype(np.int64)
    req_mem = rng.randint(0, 2**30, size=m).astype(np.int64)
    zeros = np.zeros(m, dtype=np.int64)
    zero_request = rng.rand(m) > 0.8
    alloc_cpu = np.full(n, 4000, dtype=np.int64)
    alloc_mem = np.full(n, 2**34, dtype=np.int64)
    alloc_zero = np.zeros(n, dtype=np.int64)
    allowed = np.full(n, 8, dtype=np.int64)
    used_cpu = rng.randint(0, 2000, size=n).astype(np.int64)
    used_zero = np.zeros(n, dtype=np.int64)
    pod_count = rng.randint(0, 4, size=n).astype(np.int64)
    zone_dom = rng.randint(0, 3, size=n).astype(np.int32)
    rack_dom = rng.randint(0, 4, size=n).astype(np.int32)

    host = select_oracle(
        feasible, score, req_cpu, req_mem, zeros, zeros, zero_request,
        alloc_cpu, alloc_mem, alloc_zero, alloc_zero, allowed,
        used_cpu, used_zero, used_zero, used_zero, pod_count,
        zone_dom, rack_dom, 3, 4)
    gi = GangIn(
        alloc_cpu=jnp.asarray(alloc_cpu), alloc_mem=jnp.asarray(alloc_mem),
        alloc_gpu=jnp.asarray(alloc_zero), alloc_eph=jnp.asarray(alloc_zero),
        allowed_pods=jnp.asarray(allowed), used_cpu=jnp.asarray(used_cpu),
        used_mem=jnp.asarray(used_zero), used_gpu=jnp.asarray(used_zero),
        used_eph=jnp.asarray(used_zero), pod_count=jnp.asarray(pod_count),
        zone_dom=jnp.asarray(zone_dom), rack_dom=jnp.asarray(rack_dom))
    device = [int(c) for c in np.asarray(gang_select(
        jnp.asarray(feasible), jnp.asarray(score), jnp.asarray(req_cpu),
        jnp.asarray(req_mem), jnp.asarray(zeros), jnp.asarray(zeros),
        jnp.asarray(zero_request), gi, n_zone=3, n_rack=4))]
    assert host == device


def test_gang_auto_seam_verifies_then_trusts(monkeypatch):
    from tpusim.jaxe.trust import GANG

    monkeypatch.delenv("TPUSIM_GANG_KERNEL", raising=False)
    st = run_simulation([*_gang("g", 4)], _cluster(), backend="jax")
    assert len(st.successful_pods) == 4
    assert GANG.verified, "first gang must verify its sig"
    assert not GANG.disabled


def test_gang_kernel_env_force_host(monkeypatch):
    monkeypatch.setenv("TPUSIM_GANG_KERNEL", "0")
    st = run_simulation([*_gang("g", 4)], _cluster(), backend="jax")
    assert len(st.successful_pods) == 4


# ---------------------------------------------------------------------------
# all-or-nothing semantics, both routes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["reference", "jax"])
def test_all_or_nothing_zero_binds_on_reject(backend):
    # 8 members x 3900m on 6 x 4000m nodes: at most 6 fit, gang needs 8
    st = run_simulation(_gang("big", 8, milli_cpu=3900), _cluster(),
                        backend=backend)
    assert len(st.successful_pods) == 0
    assert len(st.failed_pods) == 8
    msgs = {p.status.conditions[-1].message for p in st.failed_pods}
    assert len(msgs) == 1, "a rejected gang shares ONE FitError message"
    assert 'pod group "big"' in next(iter(msgs))


@pytest.mark.parametrize("backend", ["reference", "jax"])
def test_min_available_partial_admission(backend):
    # 8 members, min-available 4: 6 fit -> admitted, overflow individually
    # unschedulable
    st = run_simulation(_gang("part", 8, milli_cpu=3900, min_available=4),
                        _cluster(), backend=backend)
    assert len(st.successful_pods) == 6
    assert len(st.failed_pods) == 2
    for p in st.failed_pods:
        assert "admitted at 6/8" in p.status.conditions[-1].message


@pytest.mark.parametrize("backend", ["reference", "jax"])
def test_mixed_feed_gangs_and_solos(backend):
    solos = [make_pod(f"s{i}", milli_cpu=100) for i in range(3)]
    feed = [solos[0]] + _gang("g", 4) + solos[1:] \
        + _gang("big", 8, milli_cpu=3900)
    st = run_simulation(feed, _cluster(), backend=backend)
    ok = {p.metadata.name for p in st.successful_pods}
    fail = {p.metadata.name for p in st.failed_pods}
    assert {"s0", "s1", "s2", "g-0", "g-1", "g-2", "g-3"} <= ok
    assert fail == {f"big-{i}" for i in range(8)}


def test_rank_aware_packing_prefers_mate_domains():
    # plenty of room everywhere: the gang should pile into one rack's
    # nodes rather than spraying by per-pod score alone
    st = run_simulation(_gang("g", 4, milli_cpu=500),
                        _cluster(num_nodes=8, racks=True, zones=True),
                        backend="jax")
    assert len(st.successful_pods) == 4
    racks = {int(p.spec.node_name.split("-")[1]) // 2
             for p in st.successful_pods}
    assert len(racks) <= 2, f"gang sprayed across racks: {sorted(racks)}"


def test_gang_metrics_counted():
    m = register_metrics()
    admitted0 = m.gang_admitted.value
    rejected0 = dict(m.gang_rejected.values)
    run_simulation(_gang("g", 4) + _gang("big", 8, milli_cpu=3900),
                   _cluster(), backend="reference")
    assert m.gang_admitted.value == admitted0 + 1
    assert m.gang_rejected.values.get("min_available", 0) == \
        rejected0.get("min_available", 0) + 1


# ---------------------------------------------------------------------------
# gang-free identity: the ONLY routing trigger is the annotation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["reference", "jax"])
def test_gang_free_placements_deterministic(backend):
    pods = [make_pod(f"p{i}", milli_cpu=300) for i in range(12)]
    st1 = run_simulation([p.copy() for p in pods], _cluster(),
                         backend=backend)
    st2 = run_simulation([p.copy() for p in pods], _cluster(),
                         backend=backend)
    assert _assignments(st1) == _assignments(st2)
    assert len(st1.successful_pods) == 12


def test_gang_free_stream_chain_unchanged():
    from tpusim.simulator import run_stream_simulation

    a = run_stream_simulation(num_nodes=8, cycles=4, arrivals=6, seed=5)
    b = run_stream_simulation(num_nodes=8, cycles=4, arrivals=6, seed=5,
                              gang_size=0, gang_count=0)
    assert a["placement_chain"] == b["placement_chain"]
    assert "gang" not in a["paths"]


def test_stream_gang_cycles_verify():
    from tpusim.simulator import run_stream_simulation

    out = run_stream_simulation(num_nodes=12, cycles=4, arrivals=6,
                                gang_size=3, gang_count=1, verify=True,
                                seed=2)
    assert out["verified"], out
    assert out["paths"].get("gang") == 4
    assert out["load"]["gangs"] == 4


def test_stream_pipelined_gang_matches_sync():
    from tpusim.simulator import run_stream_simulation

    kw = dict(num_nodes=12, cycles=4, arrivals=6, gang_size=3, gang_count=1,
              seed=2)
    sync = run_stream_simulation(**kw)
    piped = run_stream_simulation(pipeline=True, **kw)
    assert sync["placement_chain"] == piped["placement_chain"]


# ---------------------------------------------------------------------------
# preemption interplay: one member preempted releases the gang
# ---------------------------------------------------------------------------


def test_preemption_releases_whole_gang():
    snap = _cluster(num_nodes=2, milli_cpu=4000)
    gang = _gang("lowprio", 2, milli_cpu=3000)
    for p in gang:
        p.spec.priority = 0
    high = make_pod("vip", milli_cpu=3500)
    high.spec.priority = 100
    # podspec order is reversed into a LIFO feed: listing the vip FIRST
    # schedules it LAST, after both gang members hold a node each
    st = run_simulation([high] + gang, snap, backend="reference",
                        enable_pod_priority=True)
    ok = {p.metadata.name for p in st.successful_pods}
    assert "vip" in ok
    preempted = {p.metadata.name for p in st.preempted_pods}
    bound_gang = {n for n in ok if n.startswith("lowprio")}
    # no partial gang: either both members survive or both are out
    assert len(bound_gang) in (0, 2), (ok, preempted)
    assert preempted, "the vip must have preempted at least one member"


# ---------------------------------------------------------------------------
# chaos: node_delete mid-gang rolls back every member
# ---------------------------------------------------------------------------


def test_node_delete_releases_gang():
    from tpusim.chaos import ChurnEvent, FaultPlan

    rollbacks0 = register_metrics().gang_partial_rollback.value
    snap = _cluster(num_nodes=3, milli_cpu=4000)
    gang = _gang("g", 3, milli_cpu=3000)
    # the gang binds one member per node on the first attempt; deleting
    # node-0 at the next boundary must release ALL three members, and the
    # retried gang (3 x 3000m on 2 x 4000m survivors) cannot re-admit
    plan = FaultPlan(churn=[ChurnEvent(at=1, action="node_delete",
                                       target="node-0")],
                     max_retries=2)
    st = run_simulation(gang, snap, backend="reference", chaos_plan=plan)
    assert st.chaos_violations == []
    bound = [p for p in st.successful_pods if gang_name(p) == "g"]
    assert bound == [], [p.metadata.name for p in bound]
    assert register_metrics().gang_partial_rollback.value > rollbacks0


def test_gang_metrics_families_registered():
    m = SchedulerMetrics()
    names = {metric.name for metric in m._all()}
    assert {"tpusim_gang_admitted_total", "tpusim_gang_rejected_total",
            "tpusim_gang_partial_rollback_total",
            "tpusim_gang_size"} <= names

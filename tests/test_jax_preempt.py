"""Differential tests: preemption on the jax backend (host-device hybrid,
tpusim/jaxe/preempt.py) vs the reference ClusterCapacity run.

Reference semantics under test: scheduler.go:449-455 (preempt on FitError) +
core/generic_scheduler.go:205-1000 (Preempt/selectNodesForPreemption/
selectVictimsOnNode/pickOneNodeForPreemption)."""

import random

from tpusim.api.snapshot import ClusterSnapshot, make_node, make_pod
from tpusim.simulator import run_simulation


def prio_pod(name, priority, milli_cpu=500, node_name="", labels=None,
             memory=0):
    p = make_pod(name, milli_cpu=milli_cpu, node_name=node_name, labels=labels,
                 memory=memory)
    p.spec.priority = priority
    if node_name:
        p.status.phase = "Running"
    return p


def status_sig(status):
    return {
        "success": [(p.name, p.spec.node_name) for p in status.successful_pods],
        "failed": [(p.name, p.status.conditions[-1].message if p.status.conditions else "")
                   for p in status.failed_pods],
        "preempted": sorted(p.name for p in status.preempted_pods),
        "stop": status.stop_reason,
    }


def assert_preempt_parity(pods, snapshot, provider="DefaultProvider"):
    ref = run_simulation(list(pods), snapshot, provider=provider,
                         backend="reference", enable_pod_priority=True)
    jax_status = run_simulation(list(pods), snapshot, provider=provider,
                                backend="jax", enable_pod_priority=True)
    assert status_sig(jax_status) == status_sig(ref)
    return jax_status


def test_jax_preemption_evicts_lower_priority_victim():
    node = make_node("n1", milli_cpu=1000, memory=16 * 1024**3)
    victim = prio_pod("victim", 1, milli_cpu=800, node_name="n1")
    high = prio_pod("high", 10, milli_cpu=800)
    snap = ClusterSnapshot(nodes=[node], pods=[victim])
    status = assert_preempt_parity([high], snap)
    assert [p.name for p in status.preempted_pods] == ["victim"]
    assert [p.name for p in status.successful_pods] == ["high"]


def test_jax_no_preemption_among_equal_priorities():
    node = make_node("n1", milli_cpu=1000, memory=16 * 1024**3)
    peer = prio_pod("peer", 10, milli_cpu=800, node_name="n1")
    pod = prio_pod("pod", 10, milli_cpu=800)
    snap = ClusterSnapshot(nodes=[node], pods=[peer])
    status = assert_preempt_parity([pod], snap)
    assert not status.preempted_pods
    assert [p.name for p in status.failed_pods] == ["pod"]


def test_jax_preemption_mid_batch_redispatch():
    """A preemption in the middle of the feed forces a re-dispatch; decisions
    before the preemptor must be kept, decisions after recomputed."""
    nodes = [make_node(f"n{i}", milli_cpu=2000, memory=16 * 1024**3)
             for i in range(3)]
    victims = [prio_pod(f"v{i}", 0, milli_cpu=1800, node_name=f"n{i}")
               for i in range(3)]
    # feed is LIFO: list order [first-fed last ... last-fed first]; build in
    # podspec order so 'small' pods schedule first, then the preemptor fires
    pods = [
        prio_pod("post", 0, milli_cpu=150),
        prio_pod("preemptor", 5, milli_cpu=1900),
        prio_pod("small-b", 0, milli_cpu=100),
        prio_pod("small-a", 0, milli_cpu=100),
    ]
    snap = ClusterSnapshot(nodes=nodes, pods=victims)
    status = assert_preempt_parity(pods, snap)
    assert len(status.preempted_pods) == 1
    assert any(p.name == "preemptor" for p in status.successful_pods)


def test_jax_preemption_cascade():
    """Several preemptors in one batch: each success invalidates later
    decisions, exercising repeated re-dispatch + bucket padding."""
    nodes = [make_node(f"n{i}", milli_cpu=1000, memory=16 * 1024**3)
             for i in range(4)]
    victims = [prio_pod(f"v{i}", i % 3, milli_cpu=900, node_name=f"n{i}")
               for i in range(4)]
    pods = [prio_pod(f"h{i}", 8, milli_cpu=900) for i in range(6)]
    snap = ClusterSnapshot(nodes=nodes, pods=victims)
    status = assert_preempt_parity(pods, snap)
    assert len(status.preempted_pods) == 4
    assert len(status.successful_pods) == 4
    assert len(status.failed_pods) == 2


def test_jax_preemption_respects_unresolvable_nodes():
    """Nodes failing on taints/selector are excluded from preemption
    (nodesWherePreemptionMightHelp, generic_scheduler.go:1050-1080)."""
    tainted = make_node("tainted", milli_cpu=4000, memory=16 * 1024**3,
                        taints=[{"key": "k", "value": "v",
                                 "effect": "NoSchedule"}])
    normal = make_node("normal", milli_cpu=1000, memory=16 * 1024**3)
    victim_t = prio_pod("vt", 0, milli_cpu=100, node_name="tainted")
    victim_n = prio_pod("vn", 0, milli_cpu=900, node_name="normal")
    pod = prio_pod("pod", 9, milli_cpu=900)
    snap = ClusterSnapshot(nodes=[tainted, normal], pods=[victim_t, victim_n])
    status = assert_preempt_parity([pod], snap)
    assert [p.name for p in status.preempted_pods] == ["vn"]
    assert status.successful_pods[0].spec.node_name == "normal"


def test_jax_preemption_random_differential():
    rng = random.Random(7)
    for trial in range(3):
        n_nodes = 6
        nodes = [make_node(f"n{i}", milli_cpu=rng.choice([1000, 2000, 3000]),
                           memory=16 * 1024**3) for i in range(n_nodes)]
        placed = []
        for i in range(10):
            placed.append(prio_pod(
                f"placed-{trial}-{i}", rng.randint(0, 5),
                milli_cpu=rng.choice([200, 500, 900]),
                node_name=f"n{rng.randrange(n_nodes)}"))
        pods = [prio_pod(f"new-{trial}-{i}", rng.randint(0, 10),
                         milli_cpu=rng.choice([300, 800, 1500, 2500]))
                for i in range(18)]
        snap = ClusterSnapshot(nodes=nodes, pods=placed)
        assert_preempt_parity(pods, snap)


def test_jax_preemption_no_nodes():
    pod = prio_pod("pod", 5, milli_cpu=100)
    snap = ClusterSnapshot(nodes=[], pods=[])
    status = assert_preempt_parity([pod], snap)
    assert [p.name for p in status.failed_pods] == ["pod"]


def test_jax_preemption_empty_feed():
    snap = ClusterSnapshot(nodes=[make_node("n1", milli_cpu=1000)], pods=[])
    status = assert_preempt_parity([], snap)
    assert status.stop_reason


def test_jax_preemption_chunk_sizing_invariant(monkeypatch):
    """The chunked dispatch loop: the carry flows across chunks, so ANY
    chunk sizing must produce the outcome of a single full dispatch
    (including the pow2 bucket padding after preemptions)."""
    import numpy as np

    rng = np.random.RandomState(11)
    nodes = [make_node(f"n{i}", milli_cpu=2000, memory=16 * 1024**3)
             for i in range(12)]
    placed = [prio_pod(f"placed-{i}", i % 3, milli_cpu=700,
                       node_name=f"n{i % 12}") for i in range(18)]
    pods = [prio_pod(f"new-{i}", int(rng.randint(0, 10)),
                     milli_cpu=int(rng.choice([400, 900, 1600])))
            for i in range(40)]
    snap = ClusterSnapshot(nodes=nodes, pods=placed)

    def run(chunk0, chunk_max):
        monkeypatch.setenv("TPUSIM_PREEMPT_CHUNK0", str(chunk0))
        monkeypatch.setenv("TPUSIM_PREEMPT_CHUNK_MAX", str(chunk_max))
        # fresh copies per run: the orchestrator seams mutate fed pods in
        # place (conditions, nominated node names)
        return run_simulation([p.copy() for p in pods], snap, backend="jax",
                              enable_pod_priority=True)

    small = run(8, 16)
    single = run(1 << 20, 1 << 20)
    assert status_sig(small) == status_sig(single)
    assert sorted(p.name for p in small.preempted_pods) == \
        sorted(p.name for p in single.preempted_pods)
    # the workload must actually exercise the preemption arm
    assert small.preempted_pods


def _node_mesh_or_skip():
    import jax
    import pytest

    from tpusim.jaxe.sharding import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return make_mesh(8, snap=1)


def test_jax_preemption_node_sharded_mesh_matches_single_device():
    """The hybrid with the node axis sharded over the 8-way mesh (speculation
    chunks dispatch under `with mesh`, the carry re-arm after every preemption
    lands back on the mesh) must stay byte-identical to the single-device
    hybrid on a priority-banded saturated workload — including the device
    victim-selection arm, whose kernel runs unsharded off the host victim
    table. 10 nodes over 8 shards also exercises the node-axis padding."""
    import bench
    from tpusim.jaxe.preempt import run_with_preemption

    mesh = _node_mesh_or_skip()
    snap, pods = bench.zipf_workload(400, 10, priorities=True, seed=41)
    base = run_with_preemption([p.copy() for p in pods], snap)
    assert base.preempted_pods, "workload drifted: nothing preempted"
    sharded = run_with_preemption([p.copy() for p in pods], snap, mesh=mesh)
    assert status_sig(sharded) == status_sig(base)
    assert len(sharded.preempted_pods) == len(base.preempted_pods)


def test_jax_preemption_mesh_host_arm_parity(monkeypatch):
    """TPUSIM_PREEMPT_DEVICE=0 forces host victim selection; under the mesh
    the outcome must still match the single-device run with the device kernel
    on — the victim arm and the scan sharding are independent axes."""
    import bench
    from tpusim.jaxe.preempt import run_with_preemption

    mesh = _node_mesh_or_skip()
    snap, pods = bench.zipf_workload(400, 10, priorities=True, seed=43)
    monkeypatch.delenv("TPUSIM_PREEMPT_DEVICE", raising=False)
    base = run_with_preemption([p.copy() for p in pods], snap)
    assert base.preempted_pods
    monkeypatch.setenv("TPUSIM_PREEMPT_DEVICE", "0")
    sharded = run_with_preemption([p.copy() for p in pods], snap, mesh=mesh)
    assert status_sig(sharded) == status_sig(base)


def test_preempt_fast_path_engages_and_matches(monkeypatch):
    """Round-5: the preemption hybrid drives its speculation chunks through
    the Pallas kernel (interpreter here), re-arming the carry from
    refresh_dynamic after each preemption — placements byte-identical to
    the XLA hybrid at equal preemption counts."""
    import bench
    from tpusim.jaxe import fastscan
    from tpusim.jaxe.preempt import run_with_preemption

    snap, pods = bench.zipf_workload(600, 40, priorities=True, seed=17)

    monkeypatch.delenv("TPUSIM_FAST", raising=False)
    base = run_with_preemption(pods, snap)

    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    calls = []
    real = fastscan.fast_scan

    def wrapped(*a, **kw):
        calls.append(kw.get("carry_in") is not None)
        return real(*a, **kw)

    monkeypatch.setattr(fastscan, "fast_scan", wrapped)
    fast = run_with_preemption(pods, snap)

    assert calls, "fast path did not engage"
    assert any(calls), "no chunk ran with an explicit carry (re-arm path)"

    def outcome(st):
        return ({p.metadata.name: p.spec.node_name
                 for p in st.successful_pods},
                sorted(p.metadata.name for p in st.failed_pods),
                sorted(p.metadata.name for p in st.preempted_pods))

    assert outcome(fast) == outcome(base)


def test_preempt_fast_verify_once_small_chunk0(monkeypatch):
    """A chunk0 below trust.PIN_MIN_PODS must verify ONLY the first
    chunk (later chunks run on a chained carry that no from-scratch replay
    matches) and must not spuriously disable the fast path."""
    import bench
    from tpusim.jaxe import backend
    from tpusim.jaxe.preempt import run_with_preemption
    from tpusim.jaxe.trust import FAST

    snap, pods = bench.zipf_workload(400, 30, priorities=True, seed=23)
    monkeypatch.delenv("TPUSIM_FAST", raising=False)
    base = run_with_preemption(pods, snap)

    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    monkeypatch.setenv("TPUSIM_PREEMPT_CHUNK0", "32")  # < PIN_MIN_PODS
    # AUTO mode off-TPU never engages; force the gate while keeping
    # auto-mode verification on
    monkeypatch.setattr(FAST, "mode", lambda: (True, True))
    verifies = []
    real_check = backend.FastRoute.check

    def counting_check(*a, **kw):
        verifies.append(1)
        return real_check(*a, **kw)

    monkeypatch.setattr(backend.FastRoute, "check", counting_check)
    fast = run_with_preemption(pods, snap)
    assert len(verifies) == 1
    assert FAST.disabled is False
    assert {p.metadata.name: p.spec.node_name
            for p in fast.successful_pods} \
        == {p.metadata.name: p.spec.node_name for p in base.successful_pods}


def test_preempt_fast_path_with_interpod(monkeypatch):
    """Preemption + inter-pod anti-affinity together on the fast path: the
    post-victim re-arm must rebuild BOTH the presence and presence_dom
    carries (rearm_carry's interpod branch) and stay outcome-identical to
    the XLA hybrid."""
    import random

    from tpusim.api.snapshot import ClusterSnapshot, make_node, make_pod
    from tpusim.jaxe import fastscan
    from tpusim.jaxe.preempt import run_with_preemption

    rng = random.Random(31)
    nodes = [make_node(f"n{i}", milli_cpu=2000, memory=8 * 1024**3,
                       labels={"zone": f"z{i % 3}"}) for i in range(12)]
    low = []
    for i in range(20):
        p = make_pod(f"low{i}", milli_cpu=800, memory=2**28,
                     labels={"app": "lo"})
        p.spec.node_name = f"n{i % 12}"
        p.spec.priority = 0
        low.append(p)
    pods = []
    for i in range(60):
        kw = {"labels": {"app": f"a{rng.randrange(2)}"}}
        if rng.random() < 0.3:
            kw["affinity"] = {"podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    {"labelSelector":
                     {"matchLabels": {"app": kw["labels"]["app"]}},
                     "topologyKey": "zone"}]}}
        p = make_pod(f"p{i}", milli_cpu=rng.choice([400, 800]),
                     memory=2**28, **kw)
        p.spec.priority = int(rng.choice([0, 500, 1000]))
        pods.append(p)
    snap = ClusterSnapshot(nodes=nodes, pods=low)

    def outcome(st):
        return ({p.metadata.name: p.spec.node_name
                 for p in st.successful_pods},
                sorted(p.metadata.name for p in st.failed_pods),
                sorted(p.metadata.name for p in st.preempted_pods))

    monkeypatch.delenv("TPUSIM_FAST", raising=False)
    base = run_with_preemption(pods, snap)
    assert base.preempted_pods  # the shape must actually preempt

    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    calls = []
    real = fastscan.fast_scan

    def wrapped(*a, **kw):
        calls.append(kw.get("carry_in") is not None)
        return real(*a, **kw)

    monkeypatch.setattr(fastscan, "fast_scan", wrapped)
    fast = run_with_preemption(pods, snap)
    assert calls and all(calls)
    assert outcome(fast) == outcome(base)

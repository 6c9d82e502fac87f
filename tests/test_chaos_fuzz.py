"""Fault-fuzz differential campaigns: seeded adversarial plans against the
full simulator, asserting the end-state invariants (no pod lost, no
double-bind, no bind to a deleted node, fabric reconvergence) and
host/device parity under identical device-fault plans.

Two lanes: a fixed fast seed matrix that rides tier-1 (-m 'not slow'),
and a wider sweep marked slow. Everything here is deterministic — a
failing seed reproduces byte-identically with
``random_plan(seed, ...)`` + the printed summary.
"""

import pytest

from tpusim.api.snapshot import make_pod, synthetic_cluster
from tpusim.chaos import DeviceFaultPlan, FaultPlan, random_plan
from tpusim.framework.metrics import register as register_metrics
from tpusim.simulator import run_simulation

pytestmark = pytest.mark.chaos_fuzz


def _workload(num_nodes=4, num_pods=8):
    snap = synthetic_cluster(num_nodes)
    pods = [make_pod(f"p{i}", milli_cpu=400, memory=1024**3)
            for i in range(num_pods)]
    return snap, pods


def _run_seeded(seed, num_nodes=4, num_pods=8, **plan_kw):
    snap, pods = _workload(num_nodes, num_pods)
    plan = random_plan(seed, [n.name for n in snap.nodes],
                       [p.key() for p in pods], attempts=num_pods, **plan_kw)
    status = run_simulation(pods, snap, backend="reference", chaos_plan=plan)
    return plan, status


def _assert_clean(seed, plan, status):
    assert status.chaos_violations == [], (
        f"seed {seed}: invariant violation(s) {status.chaos_violations} "
        f"under plan {plan.to_json()} summary {status.chaos_summary}")
    # conservation: every fed pod is accounted for exactly once
    summary = status.chaos_summary
    placed = {p.key() for p in status.successful_pods}
    failed = {p.key() for p in status.failed_pods}
    assert not placed & failed, f"seed {seed}: pods both placed and failed"
    assert summary["violations"] == []


# ---------------------------------------------------------------------------
# fast matrix (tier-1): churn + fabric faults on the reference orchestrator
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2, 7, 13, 42])
def test_fuzz_churn_invariants(seed):
    plan, status = _run_seeded(seed)
    _assert_clean(seed, plan, status)


def test_fuzz_replay_is_deterministic():
    plan_a, status_a = _run_seeded(42)
    plan_b, status_b = _run_seeded(42)
    assert plan_a == plan_b
    assert status_a.chaos_summary == status_b.chaos_summary
    assert [(p.key(), p.spec.node_name) for p in status_a.successful_pods] \
        == [(p.key(), p.spec.node_name) for p in status_b.successful_pods]


def test_fuzz_all_nodes_killable():
    # keep_nodes=0: plans may delete/cordon every node; pods must end
    # parked (unschedulable), never lost
    for seed in (3, 11):
        snap, pods = _workload(num_nodes=3, num_pods=5)
        plan = random_plan(seed, [n.name for n in snap.nodes],
                           [p.key() for p in pods], attempts=5, keep_nodes=0)
        status = run_simulation(pods, snap, backend="reference",
                                chaos_plan=plan)
        _assert_clean(seed, plan, status)


# ---------------------------------------------------------------------------
# fast matrix (tier-1): device faults — breaker + host/device parity
# ---------------------------------------------------------------------------


def _device_plan(faults, threshold=2, cooldown=1):
    return FaultPlan(seed=0, device=DeviceFaultPlan(
        faults=faults, failure_threshold=threshold, cooldown=cooldown))


@pytest.mark.parametrize("faults", [
    {0: "exception"},
    {0: "corrupt_invalid"},
    {0: "corrupt_silent"},
    {0: "exception", 1: "exception"},          # trips the breaker open
])
def test_fuzz_device_faults_host_parity(faults):
    """A faulted device run must emit byte-identical placements to the
    clean host run — the breaker + verify="all" contract."""
    snap, pods = _workload(num_nodes=3, num_pods=6)
    expected = run_simulation(pods, snap, backend="reference")
    status = run_simulation(pods, snap, backend="jax",
                            chaos_plan=_device_plan(faults))
    assert status.chaos_violations == []
    assert sorted((p.key(), p.spec.node_name)
                  for p in status.successful_pods) \
        == sorted((p.key(), p.spec.node_name)
                  for p in expected.successful_pods)
    assert {p.key() for p in status.failed_pods} \
        == {p.key() for p in expected.failed_pods}


def test_fuzz_breaker_cycle_visible_in_counters():
    """The full open -> half_open -> close sequence must surface both in
    the returned transition audit and the tpusim_breaker_* counters."""
    reg = register_metrics()
    before = dict(reg.breaker_transitions.values)
    snap, pods = _workload(num_nodes=3, num_pods=6)
    # threshold 2 trips on dispatches 0+1; the run makes only one dispatch,
    # so drive the cycle through the backend directly
    from tpusim.jaxe.backend import JaxBackend, install_chaos, uninstall_chaos

    breaker = install_chaos(DeviceFaultPlan(
        faults={0: "exception", 1: "exception"},
        failure_threshold=2, cooldown=1))
    try:
        backend = JaxBackend()
        for _ in range(4):
            placements = backend.schedule(pods, snap)
            assert all(p.node_name or p.reason == "Unschedulable"
                       for p in placements)
    finally:
        uninstall_chaos()
    assert [t for t, _ in breaker.transitions] \
        == ["open", "half_open", "close"]
    after = reg.breaker_transitions.values
    for transition in ("open", "half_open", "close"):
        assert after.get(transition, 0) == before.get(transition, 0) + 1, \
            f"tpusim_breaker_transitions_total[{transition}] did not move"
    assert reg.breaker_state.value == 0.0  # ends closed


def test_fuzz_device_plan_summary_reaches_status():
    snap, pods = _workload(num_nodes=3, num_pods=6)
    status = run_simulation(pods, snap, backend="jax",
                            chaos_plan=_device_plan({0: "exception"},
                                                    threshold=1))
    transitions = [t for t, _ in status.chaos_summary["breaker_transitions"]]
    assert transitions == ["open"]


# ---------------------------------------------------------------------------
# fast matrix (tier-1): gang workloads under churn — the no-partial-gang
# invariant (ISSUE 15: node_delete mid-gang must roll back every member)
# ---------------------------------------------------------------------------


def _gang_workload(num_nodes=6, num_solos=4, gang_size=4):
    from tpusim.api.snapshot import ClusterSnapshot, make_node
    from tpusim.gang.group import mark_gang

    nodes = [make_node(f"node-{i}", milli_cpu=4000,
                       labels={"topology.kubernetes.io/rack":
                               f"rack-{i // 2}"})
             for i in range(num_nodes)]
    snap = ClusterSnapshot(nodes=nodes, pods=[])
    pods = [make_pod(f"s{i}", milli_cpu=200) for i in range(num_solos)]
    pods += [mark_gang(make_pod(f"g-{j}", milli_cpu=800), "g")
             for j in range(gang_size)]
    return snap, pods


@pytest.mark.parametrize("seed", [0, 1, 2, 7, 13, 42])
def test_fuzz_gang_churn_invariants(seed):
    snap, pods = _gang_workload()
    plan = random_plan(seed, [n.name for n in snap.nodes],
                       [p.key() for p in pods], attempts=len(pods) + 4)
    status = run_simulation(pods, snap, backend="reference", chaos_plan=plan)
    _assert_clean(seed, plan, status)
    # all-or-nothing survives churn: the audit above includes the
    # partial-gang invariant, but assert it end-state here too
    bound = [p for p in status.successful_pods
             if p.metadata.name.startswith("g-")]
    assert len(bound) in (0, 4), (
        f"seed {seed}: partial gang survived: "
        f"{sorted(p.metadata.name for p in bound)}")


# ---------------------------------------------------------------------------
# fast matrix (tier-1): seeded shard axis (ISSUE 16) — the node-sharded
# backend route under the same adversarial churn plans
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("faults", [
    {0: "exception"},
    {0: "corrupt_silent"},
    {1: "corrupt_invalid"},
])
def test_fuzz_device_faults_sharded_axis(monkeypatch, faults):
    """Seeded device-fault plans against the node-SHARDED backend route
    (churn sections are host-bound, so this is the lane that reaches the
    mesh): TPUSIM_SHARDS=2 under injected faults must (a) still emit
    byte-identical placements to the clean host run, and (b) never let
    the injected corruption spuriously disable the shard route — the
    shard verify seam runs BEFORE the chaos corruption point, so only a
    REAL cross-shard divergence may trip it."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    from tpusim.jaxe import trust

    snap, pods = _workload(num_nodes=3, num_pods=6)
    expected = run_simulation(pods, snap, backend="reference")
    monkeypatch.setenv("TPUSIM_SHARDS", "2")  # 3 nodes: uneven pad to 4
    trust.reset_all()
    status = run_simulation(pods, snap, backend="jax",
                            chaos_plan=_device_plan(faults))
    assert status.chaos_violations == []
    assert not trust.SHARD.disabled, \
        "injected device fault tripped the shard verify seam"
    if "exception" not in faults.values():
        # corrupt faults let the dispatch complete: the sharded route ran
        # and pinned its signature before the corruption was injected
        assert trust.SHARD.verified, \
            "corrupt fault kept the sharded route from pinning"
    assert sorted((p.key(), p.spec.node_name)
                  for p in status.successful_pods) \
        == sorted((p.key(), p.spec.node_name)
                  for p in expected.successful_pods)
    assert {p.key() for p in status.failed_pods} \
        == {p.key() for p in expected.failed_pods}


# ---------------------------------------------------------------------------
# wide sweep (slow lane): more seeds, bigger shapes, device faults mixed in
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("seed", list(range(20)))
def test_fuzz_sweep_churn(seed):
    plan, status = _run_seeded(seed, num_nodes=6, num_pods=12)
    _assert_clean(seed, plan, status)


@pytest.mark.slow
@pytest.mark.parametrize("seed", list(range(8)))
def test_fuzz_sweep_kill_leader(seed, tmp_path_factory):
    """Seeded kill-the-leader campaigns (ISSUE 18): every crash point of
    every seed must promote the standby to the crash-free chain with a
    clean failover audit."""
    import os

    from tpusim.chaos.engine import audit_failover
    from tpusim.chaos.plan import kill_leader_campaign
    from tpusim.simulator import run_replicated_stream, run_stream_simulation
    from tpusim.stream.persist import StreamPersistence, read_wal

    kw = dict(num_nodes=16, cycles=10, arrivals=16, evict_fraction=0.25,
              node_flap_every=4, seed=seed)
    base_dir = tmp_path_factory.mktemp(f"kl-base-{seed}")
    base = run_stream_simulation(**kw, checkpoint_dir=str(base_dir),
                                 checkpoint_every=3)
    for plan in kill_leader_campaign(seed=seed, cycles=10):
        d = tmp_path_factory.mktemp(
            f"kl-{seed}-{plan.churn[0].target}")
        out = run_replicated_stream(**kw, checkpoint_dir=str(d),
                                    checkpoint_every=3, chaos_plan=plan)
        assert out["promoted"], f"seed {seed} {plan.churn[0].target}"
        assert out["promotion_violations"] == []
        assert out["fold_chain"] == base["fold_chain"], (
            f"seed {seed} point {plan.churn[0].target}: promoted chain "
            "diverged from the crash-free run")
        records, torn = read_wal(os.path.join(str(d),
                                              StreamPersistence.WAL))
        assert torn == [] and audit_failover(records) == []


@pytest.mark.slow
@pytest.mark.parametrize("seed", [5, 17, 23])
def test_fuzz_sweep_device(seed):
    snap, pods = _workload(num_nodes=4, num_pods=8)
    plan = random_plan(seed, [], [], attempts=1, device_dispatches=3)
    assert plan.host_sections_empty() or not plan.churn
    expected = run_simulation(pods, snap, backend="reference")
    status = run_simulation(
        pods, snap, backend="jax",
        chaos_plan=FaultPlan(seed=seed, device=plan.device))
    assert status.chaos_violations == []
    assert sorted((p.key(), p.spec.node_name)
                  for p in status.successful_pods) \
        == sorted((p.key(), p.spec.node_name)
                  for p in expected.successful_pods)

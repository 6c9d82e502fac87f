"""Cross-feature randomized differential fuzz: every device-engine feature
mixed in one workload must still place byte-identically to the reference
engine (placements AND failure messages). This is the BASELINE.json
"placement-parity" metric as a property test; the narrower per-feature
differentials live in test_jax_parity.py / test_jax_groups.py /
test_jax_policy.py / test_jax_preempt.py."""

import random

import pytest


@pytest.fixture(autouse=True)
def _bounded_compile_state():
    """Extended campaigns (TPUSIM_FUZZ_SEEDS=100+) compile hundreds of
    distinct programs per axis; letting them accumulate across axes in one
    process eventually segfaults XLA:CPU's native compiler (observed at
    ~200+ cached executables). Clearing jax's compilation caches between
    axes bounds the in-process state — each axis then behaves like its own
    fresh process, which runs clean at 100 seeds. Default quick runs keep
    their warm caches (the clear would force later test modules to
    recompile shared engine programs for no safety benefit)."""
    import os

    try:
        extended = int(os.environ.get("TPUSIM_FUZZ_SEEDS", "0")) > 25
    except ValueError:
        extended = False
    if extended:
        import jax

        jax.clear_caches()
    yield

from tpusim.api.snapshot import ClusterSnapshot, make_node, make_pod
from tpusim.api.types import ContainerImage, Service
from tpusim.engine.policy import (
    LabelsPresenceArg,
    Policy,
    PredicateArgument,
    PredicatePolicy,
    PriorityPolicy,
)
from tpusim.simulator import run_simulation

PROVIDERS = ["DefaultProvider", "ClusterAutoscalerProvider",
             "TalkintDataProvider"]
MB = 1024 * 1024


def random_cluster(rng: random.Random):
    n_nodes = rng.randint(8, 14)
    nodes = []
    for i in range(n_nodes):
        labels = {"zone": f"z{rng.randrange(3)}",
                  "cores": str(rng.choice([4, 16, 64]))}
        if rng.random() < 0.5:
            labels["disktype"] = rng.choice(["ssd", "hdd"])
        taints = None
        if rng.random() < 0.2:
            taints = [{"key": "team", "value": rng.choice(["a", "b"]),
                       "effect": rng.choice(["NoSchedule",
                                             "PreferNoSchedule"])}]
        node = make_node(
            f"n{i}", milli_cpu=rng.choice([2000, 4000, 8000]),
            memory=rng.choice([8, 16]) * 1024**3,
            pods=rng.choice([10, 110]),
            labels=labels, taints=taints,
            unschedulable=rng.random() < 0.05,
            ready=rng.random() > 0.05)
        if rng.random() < 0.4:
            node.status.images = [ContainerImage(
                names=[f"img-{rng.randrange(3)}:v1"],
                size_bytes=rng.choice([50, 300, 900]) * MB)]
        nodes.append(node)

    services = []
    for s in range(rng.randint(0, 2)):
        services.append(Service.from_obj({
            "metadata": {"name": f"svc{s}", "namespace": "default"},
            "spec": {"selector": {"app": f"app{s}"}}}))

    placed = []
    for i in range(rng.randint(0, 10)):
        labels = {"app": f"app{rng.randrange(3)}"} if rng.random() < 0.7 else None
        p = make_pod(f"placed-{i}", milli_cpu=rng.choice([100, 500, 1200]),
                     memory=rng.choice([128, 512]) * MB,
                     node_name=f"n{rng.randrange(n_nodes)}", phase="Running",
                     labels=labels)
        placed.append(p)
    return ClusterSnapshot(nodes=nodes, pods=placed, services=services)


def random_pods(rng: random.Random, count: int):
    pods = []
    for i in range(count):
        kwargs = {}
        labels = {}
        if rng.random() < 0.5:
            labels["app"] = f"app{rng.randrange(3)}"
        if rng.random() < 0.3:
            kwargs["node_selector"] = {"disktype": rng.choice(["ssd", "hdd"])}
        if rng.random() < 0.3:
            kwargs["tolerations"] = [{"key": "team", "operator": "Equal",
                                      "value": rng.choice(["a", "b"]),
                                      "effect": "NoSchedule"}]
        if rng.random() < 0.25:
            # the full NodeSelectorRequirement operator set, incl. the
            # numeric comparisons (Gt/Lt) and existence checks
            expr = rng.choice([
                {"key": "zone", "operator": rng.choice(["In", "NotIn"]),
                 "values": [f"z{rng.randrange(3)}"]},
                {"key": "cores", "operator": rng.choice(["Gt", "Lt"]),
                 "values": [str(rng.choice([8, 32]))]},
                {"key": "disktype",
                 "operator": rng.choice(["Exists", "DoesNotExist"])},
            ])
            kwargs["affinity"] = {"nodeAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": {
                    "nodeSelectorTerms": [{"matchExpressions": [expr]}]},
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {"weight": rng.randint(1, 50),
                     "preference": {"matchExpressions": [
                         {"key": "disktype", "operator": "Exists"}]}}]}}
        elif rng.random() < 0.15:
            kwargs["affinity"] = {"podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    {"labelSelector": {"matchLabels":
                                       {"app": f"app{rng.randrange(3)}"}},
                     "topologyKey": "kubernetes.io/hostname"}]}}
        p = make_pod(f"pod-{i}", milli_cpu=rng.choice([100, 400, 900, 2500]),
                     memory=rng.choice([64, 256, 1024, 4096]) * MB,
                     labels=labels or None, **kwargs)
        if rng.random() < 0.2:
            from tpusim.api.types import ContainerPort

            p.spec.containers[0].ports = [ContainerPort.from_obj(
                {"containerPort": 8080,
                 "hostPort": rng.choice([8080, 9090])})]
        if rng.random() < 0.3:
            p.spec.containers[0].image = f"img-{rng.randrange(3)}:v1"
        pods.append(p)
    return pods


def sig(status):
    return ([(p.name, p.spec.node_name) for p in status.successful_pods],
            [(p.name, p.status.conditions[-1].message if p.status.conditions
              else "") for p in status.failed_pods],
            sorted(p.name for p in status.preempted_pods))


def _bound_compile_state(seed: int) -> None:
    """Every 40 seeds, clear jax's compilation caches mid-axis: XLA:CPU's
    native compiler segfaults once ~200+ cached executables accumulate in
    one process, and a 150-seed single axis gets there on its own (observed
    in round 4) — the autouse between-axes clear is not enough for long
    campaigns."""
    if seed and seed % 40 == 0:
        import jax

        jax.clear_caches()


def test_fuzz_provider_parity():
    for seed in range(_fuzz_seeds(6)):
        _bound_compile_state(seed)
        rng = random.Random(1000 + seed)
        snapshot = random_cluster(rng)
        pods = random_pods(rng, rng.randint(20, 30))
        provider = rng.choice(PROVIDERS)
        ref = run_simulation(list(pods), snapshot, provider=provider,
                             backend="reference")
        jx = run_simulation(list(pods), snapshot, provider=provider,
                            backend="jax")
        assert sig(jx) == sig(ref), f"seed {seed} provider {provider}"


def random_policy(rng: random.Random) -> Policy:
    """One random 1.10-surface policy mixing builtin predicates/priorities
    with the custom-argument residue classes (label presence, Service
    Affinity segments, ServiceAntiAffinity spreading, count-mode)."""
    pred_pool = ["GeneralPredicates", "PodFitsResources",
                 "PodToleratesNodeTaints", "MatchNodeSelector",
                 "CheckNodeMemoryPressure", "CheckNodeDiskPressure",
                 "MatchInterPodAffinity", "PodFitsHostPorts", "HostName",
                 "CheckNodeUnschedulable", "PodToleratesNodeNoExecuteTaints",
                 "PodFitsPorts"]
    prio_pool = ["LeastRequestedPriority", "MostRequestedPriority",
                 "BalancedResourceAllocation", "NodeAffinityPriority",
                 "TaintTolerationPriority", "SelectorSpreadPriority",
                 "InterPodAffinityPriority", "ImageLocalityPriority"]
    preds = [PredicatePolicy(name=n) for n in
             rng.sample(pred_pool, rng.randint(2, 5))]
    if rng.random() < 0.6:
        preds.append(PredicatePolicy(
            name="NeedsDisk", argument=PredicateArgument(
                labels_presence=LabelsPresenceArg(
                    labels=["disktype"],
                    presence=rng.random() < 0.7))))
    if rng.random() < 0.3:
        # a second label predicate: with alwaysCheckAllPredicates below,
        # several failing label predicates duplicate one reason string —
        # the kernel's count-mode histogram must match the host's
        # multiplicities (VERDICT r3 item 8)
        preds.append(PredicatePolicy(
            name="WantsZone", argument=PredicateArgument(
                labels_presence=LabelsPresenceArg(
                    labels=["zone"], presence=rng.random() < 0.7))))
    if rng.random() < 0.5:
        from tpusim.engine.policy import ServiceAffinityArg

        preds.append(PredicatePolicy(
            name="StickToZone", argument=PredicateArgument(
                service_affinity=ServiceAffinityArg(
                    labels=[rng.choice(["zone", "disktype"])]))))
        if rng.random() < 0.4:
            # a SECOND ServiceAffinity entry: each evaluates its own
            # label segment against the shared first-pod lock
            preds.append(PredicatePolicy(
                name="StickToDisk", argument=PredicateArgument(
                    service_affinity=ServiceAffinityArg(
                        labels=["disktype"]))))
    prios = [PriorityPolicy(name=n, weight=rng.randint(1, 5)) for n in
             rng.sample(prio_pool, rng.randint(1, 4))]
    if rng.random() < 0.5:
        from tpusim.engine.policy import (
            PriorityArgument,
            ServiceAntiAffinityArg,
        )

        prios.append(PriorityPolicy(
            name="SpreadByZone", weight=rng.randint(1, 4),
            argument=PriorityArgument(
                service_anti_affinity=ServiceAntiAffinityArg(
                    label="zone"))))
    return Policy(predicates=preds, priorities=prios,
                  always_check_all_predicates=rng.random() < 0.4)


def test_fuzz_policy_parity():
    for seed in range(_fuzz_seeds(4)):
        _bound_compile_state(seed)
        rng = random.Random(2000 + seed)
        snapshot = random_cluster(rng)
        pods = random_pods(rng, rng.randint(15, 25))
        policy = random_policy(rng)
        ref = run_simulation(list(pods), snapshot, backend="reference",
                             policy=policy)
        jx = run_simulation(list(pods), snapshot, backend="jax",
                            policy=policy)
        assert sig(jx) == sig(ref), f"seed {seed}"


def test_fuzz_policy_parity_fast(monkeypatch):
    """The policy fuzz axis under TPUSIM_FAST=1 interpreter mode (ISSUE 4
    acceptance): random residue-heavy policies run through the Pallas
    kernel byte-identical to the host reference, with the kernel actually
    engaging and ZERO fast-path fallbacks — every compilable policy must be
    fast-path eligible now. Each seed bakes a distinct PolicySpec into its
    own kernel variant, so seeds are few (interpreter traces are slow);
    TPUSIM_FUZZ_SEEDS widens the campaign."""
    from tpusim.framework.metrics import register
    from tpusim.jaxe import fastscan

    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    runs = []
    real_fast_scan = fastscan.fast_scan
    monkeypatch.setattr(
        fastscan, "fast_scan",
        lambda plan, **kw: runs.append(1) or real_fast_scan(plan, **kw))
    fallback = register().fast_fallback
    before = dict(fallback.values)
    for seed in range(_fuzz_seeds(2)):
        _bound_compile_state(seed)
        rng = random.Random(2000 + seed)  # same stream as the XLA-axis test
        snapshot = random_cluster(rng)
        pods = random_pods(rng, rng.randint(15, 25))
        policy = random_policy(rng)
        ref = run_simulation(list(pods), snapshot, backend="reference",
                             policy=policy)
        jx = run_simulation(list(pods), snapshot, backend="jax",
                            policy=policy)
        assert sig(jx) == sig(ref), f"seed {seed}"
    assert runs, "pallas fast path did not engage"
    assert fallback.values == before, \
        f"fast-path fallbacks during the policy axis: {fallback.values}"


def test_compat_policy_matrix_fast_parity(monkeypatch):
    """Every versioned compat policy end-to-end through the Pallas kernel
    (interpreter mode): byte-identical placements AND failure messages vs
    the reference engine, zero fallbacks (the ROADMAP item-4 done
    condition, end-to-end leg — the planning-only leg is tier-1 in
    test_jax_policy.py)."""
    import json
    import os as _os

    from tpusim.engine.policy import decode_policy
    from tpusim.framework.metrics import register
    from tpusim.jaxe import fastscan
    from test_jax_policy import compat_cluster, compat_workload

    fixture = _os.path.join(_os.path.dirname(__file__),
                            "compat_policies.json")
    with open(fixture) as f:
        compat = json.load(f)
    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    runs = []
    real_fast_scan = fastscan.fast_scan
    monkeypatch.setattr(
        fastscan, "fast_scan",
        lambda plan, **kw: runs.append(1) or real_fast_scan(plan, **kw))
    fallback = register().fast_fallback
    before = dict(fallback.values)
    for version in sorted(compat):
        policy = decode_policy(compat[version])
        snapshot = compat_cluster()
        pods = compat_workload()
        engaged = len(runs)
        ref = run_simulation(list(pods), snapshot, backend="reference",
                             policy=policy)
        jx = run_simulation(list(pods), snapshot, backend="jax",
                            policy=policy)
        assert sig(jx) == sig(ref), f"policy {version}"
        assert len(runs) > engaged, \
            f"policy {version}: pallas fast path did not engage"
    assert fallback.values == before, \
        f"fast-path fallbacks during the compat matrix: {fallback.values}"


def test_fuzz_preemption_parity():
    for seed in range(_fuzz_seeds(3)):
        _bound_compile_state(seed)
        rng = random.Random(3000 + seed)
        snapshot = random_cluster(rng)
        for p in snapshot.pods:
            p.spec.priority = rng.randint(0, 5)
        pods = random_pods(rng, rng.randint(15, 20))
        for p in pods:
            p.spec.priority = rng.randint(0, 10)
        ref = run_simulation(list(pods), snapshot, backend="reference",
                             enable_pod_priority=True)
        jx = run_simulation(list(pods), snapshot, backend="jax",
                            enable_pod_priority=True)
        assert sig(jx) == sig(ref), f"seed {seed}"


def test_fuzz_preemption_banded_saturated_parity():
    """Priority-banded SATURATED workloads: every seed drives preemption
    chains through the arithmetic-reprieve dispatch seam, so the device
    victim-selection kernel (jaxe/kernels.preempt_select) is exercised
    against the host oracle across node counts, victim shapes (incl.
    zero-request pods — the kernel's static zero_req variant) and band
    overlaps. Half the seeds clear the kernel's per-variant trust so the
    first-use verification path re-runs; a nonzero `fallback` count means
    the kernel DISAGREED with the host pipeline — a hard failure here, not
    a fallback to tolerate. The occasional host-ports pod flips the whole
    run to the general class, covering the class-dispatch seam itself."""
    from tpusim.api.types import ContainerPort
    from tpusim.jaxe.trust import VICTIM
    from tpusim.jaxe.preempt import (
        PREEMPT_CLASS_STATS,
        reset_preempt_class_stats,
    )

    reset_preempt_class_stats()
    for seed in range(_fuzz_seeds(4)):
        _bound_compile_state(seed)
        rng = random.Random(7000 + seed)
        if seed % 2 == 0:
            VICTIM.verified.clear()
        n_nodes = rng.randint(3, 8)
        nodes = [make_node(f"n{i}", milli_cpu=rng.choice([1000, 2000, 4000]),
                           memory=rng.choice([2, 4, 8]) * 1024 * MB,
                           pods=rng.choice([5, 110]),
                           labels={"zone": f"z{i % 3}"})
                 for i in range(n_nodes)]
        placed = []
        for i in range(rng.randint(n_nodes, 3 * n_nodes)):
            zero = rng.random() < 0.15
            p = make_pod(f"placed-{i}",
                         milli_cpu=0 if zero else rng.choice([200, 700, 1500]),
                         memory=0 if zero else rng.choice([0, 128, 512]) * MB,
                         node_name=f"n{rng.randrange(n_nodes)}",
                         phase="Running")
            p.spec.priority = rng.choice([0, 0, 1, 2, 4])
            placed.append(p)
        # the class flags are workload-wide: ONE ports pod anywhere demotes
        # the whole run to the general class, so ports seeds are explicit
        # (otherwise ~every seed would carry one and the kernel never runs)
        with_ports = seed % 3 == 2
        pods = []
        for i in range(rng.randint(15, 25)):
            zero = rng.random() < 0.15
            p = make_pod(f"pod-{i}",
                         milli_cpu=0 if zero else rng.choice([300, 800, 1800]),
                         memory=0 if zero else rng.choice([0, 256, 1024]) * MB)
            p.spec.priority = rng.choice([0, 1, 3, 5, 5, 9])
            if with_ports and rng.random() < 0.3:
                p.spec.containers[0].ports = [ContainerPort.from_obj(
                    {"containerPort": 8080, "hostPort": 8080})]
            pods.append(p)
        snapshot = ClusterSnapshot(nodes=nodes, pods=placed)
        ref = run_simulation(list(pods), snapshot, backend="reference",
                             enable_pod_priority=True)
        jx = run_simulation(list(pods), snapshot, backend="jax",
                            enable_pod_priority=True)
        assert sig(jx) == sig(ref), f"seed {seed}"
        if seed % 2 == 1:
            # node-sharded mesh leg: the same banded workload with the
            # speculation chunks dispatched over the 8-way virtual mesh
            import jax

            from tpusim.jaxe.preempt import run_with_preemption
            from tpusim.jaxe.sharding import make_mesh

            if len(jax.devices()) >= 8:
                ms = run_with_preemption([p.copy() for p in pods], snapshot,
                                         mesh=make_mesh(8, snap=1))
                assert sig(ms) == sig(ref), f"seed {seed} (mesh)"
    assert PREEMPT_CLASS_STATS.get("fallback", 0) == 0, PREEMPT_CLASS_STATS
    assert (PREEMPT_CLASS_STATS.get("device", 0)
            + PREEMPT_CLASS_STATS.get("device_verified", 0)) > 0, \
        dict(PREEMPT_CLASS_STATS)


def _fuzz_seeds(default: int) -> int:
    """TPUSIM_FUZZ_SEEDS scales the committed quick sweeps into extended
    campaigns (COVERAGE.md 'verification campaign')."""
    import os

    try:
        return max(int(os.environ.get("TPUSIM_FUZZ_SEEDS", default)), 1)
    except ValueError:
        return default


def random_volume_cluster(rng: random.Random):
    """random_cluster + zone-labeled PVs, bound/unbound PVCs, and scalar
    (extended) node resources — the round-3 feature axes."""
    from tpusim.api.quantity import parse_quantity
    from tpusim.api.snapshot import make_pv, make_pvc

    snapshot = random_cluster(rng)
    ZONE = "failure-domain.beta.kubernetes.io/zone"
    for i, node in enumerate(snapshot.nodes):
        node.metadata.labels[ZONE] = f"vz{i % 2}"
    pvs, pvcs = [], []
    for v in range(rng.randint(1, 5)):
        src = rng.choice([
            {"gcePersistentDisk": {"pdName": f"disk-{v % 3}"}},
            {"awsElasticBlockStore": {"volumeID": f"ebs-{v % 3}"}},
        ])
        pvs.append(make_pv(f"pv-{v}", labels={ZONE: f"vz{v % 2}"}, source=src))
        # ~1 in 4 claims stays UNBOUND: a pod referencing it fails host-side
        # ("unbound PersistentVolumeClaims") and forces the device's
        # documented unresolvable-claim fallback — both paths must agree
        bound = rng.random() >= 0.25
        pvcs.append(make_pvc(f"claim-{v}",
                             volume_name=f"pv-{v}" if bound else ""))
    snapshot.pvs, snapshot.pvcs = pvs, pvcs
    # scalar resources on a node slice
    for node in snapshot.nodes:
        if rng.random() < 0.5:
            node.status.allocatable["example.com/widget"] = \
                parse_quantity(str(rng.randint(1, 4)))
    return snapshot


def random_volume_pods(rng: random.Random, count: int, n_claims: int):
    from tpusim.api.quantity import parse_quantity
    from tpusim.api.snapshot import make_pod_volume
    from tpusim.api.types import Volume

    pods = random_pods(rng, count)
    for p in pods:
        roll = rng.random()
        if roll < 0.3 and n_claims:
            p.spec.volumes = [Volume.from_obj(make_pod_volume(
                "v", pvc=f"claim-{rng.randrange(n_claims)}"))]
        elif roll < 0.45:
            p.spec.volumes = [Volume.from_obj(make_pod_volume(
                "d", source={"gcePersistentDisk":
                             {"pdName": f"disk-{rng.randrange(3)}"}}))]
        if rng.random() < 0.3:
            p.spec.containers[0].requests["example.com/widget"] = \
                parse_quantity(str(rng.randint(1, 2)))
    return pods


def test_fuzz_volume_scalar_parity():
    """Round-3 axes: PVC/zone/disk-conflict volumes + scalar resources,
    reference vs device engine, fresh AND incremental compiles."""
    from tpusim.jaxe.delta import IncrementalCluster

    for seed in range(_fuzz_seeds(4)):
        _bound_compile_state(seed)
        rng = random.Random(4000 + seed)
        snapshot = random_volume_cluster(rng)
        pods = random_volume_pods(rng, rng.randint(12, 20),
                                  len(snapshot.pvcs))
        ref = run_simulation(list(pods), snapshot, backend="reference")
        jx = run_simulation(list(pods), snapshot, backend="jax")
        assert sig(jx) == sig(ref), f"seed {seed}"
        # incremental path: seed an empty cluster, stream everything as events
        inc = IncrementalCluster(ClusterSnapshot(
            nodes=snapshot.nodes, pvs=snapshot.pvs, pvcs=snapshot.pvcs))
        from tpusim.framework.store import ADDED

        for placed in snapshot.pods:
            inc.apply(ADDED, placed)
        for svc in snapshot.services:
            inc.apply(ADDED, svc)
        from tpusim.backends import ReferenceBackend, placement_hash
        from tpusim.jaxe.backend import JaxBackend

        feed = list(reversed(pods))
        incr = inc.schedule(list(feed))
        fresh = JaxBackend().schedule(list(feed), inc.to_snapshot())
        host = ReferenceBackend().schedule(list(feed), inc.to_snapshot())
        assert placement_hash(incr) == placement_hash(fresh), f"seed {seed}"
        assert placement_hash(incr) == placement_hash(host), f"seed {seed}"

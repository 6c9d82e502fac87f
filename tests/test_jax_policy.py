"""Differential tests: scheduler policies compiled onto the jax backend
(tpusim/jaxe/policyc.py) vs the reference engine's CreateFromConfig assembly.

Reference semantics: factory.go CreateFromConfig:933-1000, plugins.go
RegisterCustomFitPredicate:197-240 / RegisterCustomPriorityFunction:302-348,
api/types.go:52-117 (Policy schema)."""

import pytest

from tpusim.api.snapshot import ClusterSnapshot, make_node, make_pod
from tpusim.engine.policy import (
    LabelPreferenceArg,
    LabelsPresenceArg,
    Policy,
    PredicateArgument,
    PredicatePolicy,
    PriorityArgument,
    PriorityPolicy,
    ServiceAffinityArg,
)
from tpusim.jaxe.policyc import compile_policy
from tpusim.simulator import run_simulation


def sig(status):
    return ([(p.name, p.spec.node_name) for p in status.successful_pods],
            [(p.name, p.status.conditions[-1].message if p.status.conditions
              else "") for p in status.failed_pods])


def assert_policy_parity(pods, snapshot, policy):
    ref = run_simulation(list(pods), snapshot, backend="reference",
                         policy=policy)
    jx = run_simulation(list(pods), snapshot, backend="jax", policy=policy)
    assert sig(jx) == sig(ref)
    return jx


def mixed_cluster():
    nodes = []
    for i in range(6):
        labels = {"zone": f"z{i % 2}"}
        if i % 2 == 0:
            labels["disktype"] = "ssd"
        taints = None
        if i == 5:
            taints = [{"key": "k", "value": "v", "effect": "NoSchedule"}]
        nodes.append(make_node(f"n{i}", milli_cpu=[2000, 4000, 8000][i % 3],
                               memory=16 * 1024**3, labels=labels,
                               taints=taints))
    return ClusterSnapshot(nodes=nodes)


def workload(k=12):
    pods = []
    for i in range(k):
        sel = {"disktype": "ssd"} if i % 4 == 0 else None
        pods.append(make_pod(f"p{i}", milli_cpu=[300, 900, 1800][i % 3],
                             memory=(256 + 128 * (i % 5)) * 2**20,
                             node_selector=sel))
    return pods


def test_policy_node_label_predicate_on_device():
    """The VERDICT done-criterion: a NodeLabel predicate + weighted
    priorities policy runs on device and matches the reference."""
    policy = Policy(
        predicates=[
            PredicatePolicy(name="PodFitsResources"),
            PredicatePolicy(name="RequireSSD", argument=PredicateArgument(
                labels_presence=LabelsPresenceArg(labels=["disktype"],
                                                  presence=True))),
        ],
        priorities=[
            PriorityPolicy(name="LeastRequestedPriority", weight=3),
            PriorityPolicy(name="BalancedResourceAllocation", weight=1),
        ])
    cp = compile_policy(policy)
    assert not cp.unsupported and cp.spec.label_rows == ("tail:0",)
    status = assert_policy_parity(workload(), mixed_cluster(), policy)
    # only ssd-labelled nodes (n0/n2/n4) may host pods
    assert status.successful_pods
    assert all(p.spec.node_name in ("n0", "n2", "n4")
               for p in status.successful_pods)


def test_policy_label_presence_absent_and_ordering_slot():
    # registered under the canonical ordering name → the ordering-slot stage
    policy = Policy(
        predicates=[
            PredicatePolicy(name="CheckNodeLabelPresence",
                            argument=PredicateArgument(
                                labels_presence=LabelsPresenceArg(
                                    labels=["disktype"], presence=False))),
            PredicatePolicy(name="PodToleratesNodeTaints"),
        ],
        priorities=[PriorityPolicy(name="TaintTolerationPriority", weight=2)])
    cp = compile_policy(policy)
    assert cp.spec.label_rows == ("CheckNodeLabelPresence",)
    status = assert_policy_parity(workload(), mixed_cluster(), policy)
    assert all(p.spec.node_name in ("n1", "n3")  # n5 is tainted
               for p in status.successful_pods)


def test_policy_label_pred_under_standard_name_keeps_slot_order():
    """A label-presence custom registered under ANY standard ordering name
    evaluates at that name's slot: here 'HostName' precedes taints, so a
    tainted node missing the label reports the label reason, not taints."""
    policy = Policy(
        predicates=[
            PredicatePolicy(name="HostName", argument=PredicateArgument(
                labels_presence=LabelsPresenceArg(labels=["disktype"],
                                                  presence=True))),
            PredicatePolicy(name="PodToleratesNodeTaints"),
        ],
        priorities=[])
    cp = compile_policy(policy)
    assert cp.spec.label_rows == ("HostName",)
    # the only node fails BOTH the label predicate and taints: the reported
    # reason must come from the earlier (HostName) slot
    node = make_node("n", milli_cpu=8000,
                     taints=[{"key": "k", "value": "v",
                              "effect": "NoSchedule"}])
    status = assert_policy_parity([make_pod("p", milli_cpu=100)],
                                  ClusterSnapshot(nodes=[node]), policy)
    msg = status.failed_pods[0].status.conditions[-1].message
    assert "1 node(s) didn't have the requested labels" in msg
    assert "taint" not in msg


def test_policy_label_preference_priority():
    policy = Policy(
        predicates=[PredicatePolicy(name="GeneralPredicates")],
        priorities=[
            PriorityPolicy(name="PreferSSD", weight=5,
                           argument=PriorityArgument(
                               label_preference=LabelPreferenceArg(
                                   label="disktype", presence=True))),
            PriorityPolicy(name="LeastRequestedPriority", weight=1),
        ])
    cp = compile_policy(policy)
    assert cp.spec.has_label_prio and not cp.unsupported
    status = assert_policy_parity(workload(8), mixed_cluster(), policy)
    # weight-5 label preference dominates: everything lands on ssd nodes
    assert all(p.spec.node_name in ("n0", "n2", "n4")
               for p in status.successful_pods)


def test_policy_most_requested_weights():
    policy = Policy(
        predicates=[PredicatePolicy(name="GeneralPredicates"),
                    PredicatePolicy(name="PodToleratesNodeTaints")],
        priorities=[PriorityPolicy(name="MostRequestedPriority", weight=2),
                    PriorityPolicy(name="NodeAffinityPriority", weight=1)])
    assert_policy_parity(workload(), mixed_cluster(), policy)


def test_policy_empty_priorities_all_tie():
    policy = Policy(predicates=[PredicatePolicy(name="PodFitsResources")],
                    priorities=[])
    assert_policy_parity(workload(), mixed_cluster(), policy)


def test_policy_mandatory_only_predicates():
    # predicates=[] → only the mandatory CheckNodeCondition runs
    policy = Policy(predicates=[], priorities=[
        PriorityPolicy(name="LeastRequestedPriority", weight=1)])
    bad = make_node("down", milli_cpu=8000, ready=False)
    snap = ClusterSnapshot(nodes=[*mixed_cluster().nodes, bad])
    status = assert_policy_parity(workload(6), snap, policy)
    assert all(p.spec.node_name != "down" for p in status.successful_pods)


def test_policy_subset_failure_reasons():
    # with only PodFitsResources enabled, an unmatchable selector pod still
    # schedules (MatchNodeSelector is off) and an oversized pod reports only
    # resource reasons
    policy = Policy(predicates=[PredicatePolicy(name="PodFitsResources")],
                    priorities=[])
    pods = [make_pod("huge", milli_cpu=64000),
            make_pod("sel", milli_cpu=10,
                     node_selector={"no-such-label": "x"})]
    status = assert_policy_parity(pods, mixed_cluster(), policy)
    assert [p.name for p in status.successful_pods] == ["sel"]
    msg = status.failed_pods[0].status.conditions[-1].message
    assert "Insufficient cpu" in msg and "selector" not in msg


def test_policy_unknown_names_raise_like_host():
    with pytest.raises(KeyError, match="Predicate type not found for Bogus"):
        compile_policy(Policy(predicates=[PredicatePolicy(name="Bogus")]))
    with pytest.raises(KeyError, match="Priority type not found for Bogus"):
        compile_policy(Policy(priorities=[
            PriorityPolicy(name="Bogus", weight=1)]))


def test_policy_host_bound_features_fall_back():
    from tpusim.engine.policy import ExtenderConfig

    policy = Policy(
        predicates=[PredicatePolicy(name="PodFitsResources")],
        priorities=[],
        extender_configs=[ExtenderConfig(url_prefix="http://x",
                                         filter_verb="filter")])
    cp = compile_policy(policy)
    assert cp.unsupported
    # (no run here: a transportless extender would attempt real HTTP with 5s
    # timeouts per pod on BOTH backends; the routing itself is covered by
    # run_simulation's compiled_policy.unsupported arm + the what-if test)


def test_policy_hard_weight_override_compiles():
    policy = Policy(predicates=None, priorities=None,
                    hard_pod_affinity_symmetric_weight=50)
    cp = compile_policy(policy)
    assert cp.hard_weight == 50 and cp.spec.pred_keys is None
    assert_policy_parity(workload(6), mixed_cluster(), policy)


def test_policy_duplicate_name_last_wins():
    policy = Policy(
        predicates=[PredicatePolicy(name="PodFitsResources")],
        priorities=[PriorityPolicy(name="LeastRequestedPriority", weight=1),
                    PriorityPolicy(name="LeastRequestedPriority", weight=7)])
    cp = compile_policy(policy)
    assert cp.spec.w_least == 7
    assert_policy_parity(workload(6), mixed_cluster(), policy)


def test_policy_image_locality_on_device():
    """ImageLocalityPriority compiles to a static (pod-image-set, node)
    table (image_locality.go thresholds) and matches the host engine."""
    from tpusim.api.types import ContainerImage

    mb = 1024 * 1024
    nodes = []
    for i in range(4):
        node = make_node(f"n{i}", milli_cpu=4000)
        if i % 2 == 0:
            node.status.images = [
                ContainerImage(names=[f"registry/app:v1"],
                               size_bytes=600 * mb),
                ContainerImage(names=["registry/sidecar:v2"],
                               size_bytes=120 * mb)]
        nodes.append(node)
    snap = ClusterSnapshot(nodes=nodes)
    pods = []
    for i in range(6):
        p = make_pod(f"p{i}", milli_cpu=300)
        p.spec.containers[0].image = "registry/app:v1"
        pods.append(p)
    policy = Policy(
        predicates=[PredicatePolicy(name="PodFitsResources")],
        priorities=[PriorityPolicy(name="ImageLocalityPriority", weight=4)])
    cp = compile_policy(policy)
    assert not cp.unsupported and cp.spec.w_image == 4
    status = assert_policy_parity(pods, snap, policy)
    # the image-bearing nodes win every placement
    assert all(p.spec.node_name in ("n0", "n2")
               for p in status.successful_pods)


def test_policy_always_check_all_on_device():
    """alwaysCheckAllPredicates: a node failing several predicates reports
    every reason (podFitsOnNode keeps evaluating past the first failure)."""
    policy = Policy(
        predicates=[PredicatePolicy(name="PodFitsResources"),
                    PredicatePolicy(name="PodToleratesNodeTaints")],
        priorities=[],
        always_check_all_predicates=True)
    cp = compile_policy(policy)
    assert not cp.unsupported and cp.spec.always_check_all
    node = make_node("n", milli_cpu=100,
                     taints=[{"key": "k", "value": "v",
                              "effect": "NoSchedule"}])
    status = assert_policy_parity([make_pod("p", milli_cpu=500)],
                                  ClusterSnapshot(nodes=[node]), policy)
    msg = status.failed_pods[0].status.conditions[-1].message
    assert "Insufficient cpu" in msg and "taints" in msg


def test_policy_always_check_all_duplicate_reasons_on_device():
    """Shapes where the host emits one reason string SEVERAL times per node
    (VERDICT r3 item 8): the kernel's count-mode histogram reproduces the
    multiplicities natively — no fallback, byte-identical messages."""
    aca = dict(always_check_all_predicates=True)

    # (a) several label-presence predicates sharing one reason string
    two_labels = Policy(predicates=[
        PredicatePolicy(name="LblA", argument=PredicateArgument(
            labels_presence=LabelsPresenceArg(labels=["x"], presence=True))),
        PredicatePolicy(name="LblB", argument=PredicateArgument(
            labels_presence=LabelsPresenceArg(labels=["y"], presence=True))),
    ], priorities=[], **aca)
    assert not compile_policy(two_labels).unsupported
    # n0 misses both labels (2 occurrences), n1 misses one (1 occurrence)
    nodes = [make_node("n0"), make_node("n1", labels={"x": "1"})]
    status = assert_policy_parity([make_pod("p", milli_cpu=100)],
                                  ClusterSnapshot(nodes=nodes), two_labels)
    msg = status.failed_pods[0].status.conditions[-1].message
    assert "3 node(s) didn't have the requested labels" in msg

    # (b) GeneralPredicates plus an individually-named part
    umbrella_plus_part = Policy(predicates=[
        PredicatePolicy(name="GeneralPredicates"),
        PredicatePolicy(name="PodFitsResources")], priorities=[], **aca)
    assert not compile_policy(umbrella_plus_part).unsupported
    status = assert_policy_parity(
        [make_pod("p", milli_cpu=500)],
        ClusterSnapshot(nodes=[make_node("tiny", milli_cpu=100)]),
        umbrella_plus_part)
    msg = status.failed_pods[0].status.conditions[-1].message
    assert "2 Insufficient cpu" in msg

    # (c) CheckNodeUnschedulable beside the mandatory condition check
    unsched = Policy(predicates=[
        PredicatePolicy(name="CheckNodeUnschedulable"),
        PredicatePolicy(name="PodFitsResources")], priorities=[], **aca)
    assert not compile_policy(unsched).unsupported
    status = assert_policy_parity(
        [make_pod("p", milli_cpu=100)],
        ClusterSnapshot(nodes=[make_node("cordoned", unschedulable=True)]),
        unsched)
    msg = status.failed_pods[0].status.conditions[-1].message
    assert "2 node(s) were unschedulable" in msg


def test_policy_no_execute_taints_on_device():
    """PodToleratesNodeNoExecuteTaints: NoExecute taints filter, NoSchedule
    taints do not (the policy-registered narrow variant)."""
    policy = Policy(
        predicates=[PredicatePolicy(name="PodFitsResources"),
                    PredicatePolicy(name="PodToleratesNodeNoExecuteTaints")],
        priorities=[PriorityPolicy(name="LeastRequestedPriority", weight=1)])
    cp = compile_policy(policy)
    assert not cp.unsupported
    nodes = [
        make_node("evict", milli_cpu=8000,
                  taints=[{"key": "k", "value": "v", "effect": "NoExecute"}]),
        make_node("soft", milli_cpu=2000,
                  taints=[{"key": "k", "value": "v", "effect": "NoSchedule"}]),
    ]
    pods = [make_pod(f"p{i}", milli_cpu=400) for i in range(3)]
    tol = [{"key": "k", "operator": "Equal", "value": "v",
            "effect": "NoExecute"}]
    pods.append(make_pod("tolerant", milli_cpu=400, tolerations=tol))
    status = assert_policy_parity(pods, ClusterSnapshot(nodes=nodes), policy)
    by_name = {p.name: p.spec.node_name for p in status.successful_pods}
    # intolerant pods avoid the NoExecute node but CAN land on the
    # NoSchedule node (the narrow variant ignores NoSchedule)
    assert by_name["p0"] == "soft" and by_name["p1"] == "soft"
    assert by_name["tolerant"] == "evict"
    # always-check-all plus BOTH taint predicates: a NoExecute taint fails
    # both (2 occurrences of the shared string), NoSchedule only the broad
    # one (1 occurrence) — count mode keeps this on device
    both = Policy(predicates=[
        PredicatePolicy(name="PodToleratesNodeTaints"),
        PredicatePolicy(name="PodToleratesNodeNoExecuteTaints"),
        PredicatePolicy(name="PodFitsResources")],
        priorities=[], always_check_all_predicates=True)
    assert not compile_policy(both).unsupported
    status = assert_policy_parity(
        [make_pod("p", milli_cpu=100)],
        ClusterSnapshot(nodes=nodes), both)
    msg = status.failed_pods[0].status.conditions[-1].message
    assert "3 node(s) had taints that the pod didn't tolerate" in msg


def _saa_world(rng_seed=0):
    from tpusim.api.types import Service

    import random as _random
    rng = _random.Random(rng_seed)
    nodes = []
    for i in range(8):
        labels = {}
        if i < 6:
            labels["rack"] = f"r{i % 3}"
        nodes.append(make_node(f"n{i}", milli_cpu=4000, labels=labels or None))
    svc = Service.from_obj({"metadata": {"name": "db", "namespace": "default"},
                            "spec": {"selector": {"app": "db"}}})
    svc2 = Service.from_obj({"metadata": {"name": "db2",
                                          "namespace": "default"},
                             "spec": {"selector": {"tier": "data"}}})
    placed = [make_pod(f"seed-{i}", milli_cpu=100,
                       node_name=f"n{rng.randrange(6)}", phase="Running",
                       labels={"app": "db"}) for i in range(4)]
    pods = [make_pod(f"p{i}", milli_cpu=300,
                     labels={"app": "db"} if i % 2 == 0 else
                     {"tier": "data"}) for i in range(10)]
    return ClusterSnapshot(nodes=nodes, pods=placed,
                           services=[svc, svc2]), pods


def test_policy_service_anti_affinity_on_device():
    """ServiceAntiAffinity compiles: first-matching-service selectors are
    static, so spreading over the policy label's node groups runs on device
    and matches the host map/reduce exactly."""
    from tpusim.engine.policy import ServiceAntiAffinityArg

    policy = Policy(
        predicates=[PredicatePolicy(name="PodFitsResources")],
        priorities=[
            PriorityPolicy(name="SpreadByRack", weight=3,
                           argument=PriorityArgument(
                               service_anti_affinity=ServiceAntiAffinityArg(
                                   label="rack"))),
            PriorityPolicy(name="LeastRequestedPriority", weight=1),
        ])
    cp = compile_policy(policy)
    assert not cp.unsupported and cp.spec.saa_weights == (3,)
    snap, pods = _saa_world()
    status = assert_policy_parity(pods, snap, policy)
    # the dominating spread weight keeps db pods on labeled racks
    assert status.successful_pods
    placed_nodes = {p.spec.node_name for p in status.successful_pods}
    assert placed_nodes <= {f"n{i}" for i in range(6)}


def test_policy_service_anti_affinity_no_services():
    """Without any matching service the host still scores labeled nodes 10
    and unlabeled 0 — reproduced on device with zero-count tables."""
    from tpusim.engine.policy import ServiceAntiAffinityArg

    policy = Policy(
        predicates=[PredicatePolicy(name="PodFitsResources")],
        priorities=[PriorityPolicy(name="Spread", weight=2,
                                   argument=PriorityArgument(
                                       service_anti_affinity=
                                       ServiceAntiAffinityArg(label="rack")))])
    nodes = [make_node("labeled", milli_cpu=1000, labels={"rack": "r0"}),
             make_node("bare", milli_cpu=8000)]
    pods = [make_pod(f"p{i}", milli_cpu=100) for i in range(3)]
    status = assert_policy_parity(pods, ClusterSnapshot(nodes=nodes), policy)
    # labeled node wins despite less capacity (score 10*2 vs 0)
    assert all(p.spec.node_name == "labeled" for p in status.successful_pods)


def _sa_policy(labels=("zone",), name="ByZone", extra_preds=(),
               prios=()):
    from tpusim.engine.policy import ServiceAffinityArg

    return Policy(
        predicates=[PredicatePolicy(name=name, argument=PredicateArgument(
            service_affinity=ServiceAffinityArg(labels=list(labels)))),
            PredicatePolicy(name="PodFitsResources"),
            *[PredicatePolicy(name=n) for n in extra_preds]],
        priorities=[PriorityPolicy(name=n, weight=w) for n, w in prios])


def _sa_world(seed_node="n1", seed=True, seed_node_known=True):
    from tpusim.api.types import Service

    nodes = [make_node("n1", milli_cpu=9000, labels={"zone": "z1"}),
             make_node("n2", milli_cpu=9000, labels={"zone": "z2"}),
             make_node("n3", milli_cpu=9000)]  # no zone label
    svc = Service.from_obj({"metadata": {"name": "db", "namespace": "default"},
                            "spec": {"selector": {"app": "db"}}})
    placed = []
    if seed:
        placed = [make_pod("seed", milli_cpu=100,
                           node_name=seed_node if seed_node_known else "ghost",
                           phase="Running", labels={"app": "db"})]
    return ClusterSnapshot(nodes=nodes, pods=placed, services=[svc])


def test_policy_service_affinity_seeded_lock():
    """A placed first-service pod statically pins every later service pod to
    its node's zone value."""
    policy = _sa_policy()
    cp = compile_policy(policy)
    assert not cp.unsupported and cp.spec.sa_enabled
    pods = [make_pod(f"p{i}", milli_cpu=200, labels={"app": "db"})
            for i in range(4)]
    pods.append(make_pod("free", milli_cpu=200))  # no service: unconstrained
    status = assert_policy_parity(pods, _sa_world(), policy)
    by = {p.name: p.spec.node_name for p in status.successful_pods}
    assert all(by[f"p{i}"] == "n1" for i in range(4))
    assert "free" in by


def test_policy_service_affinity_fed_first_locks_at_bind():
    """No seeded service pod: the FIRST FED service pod's bind locks the sig;
    later service pods must follow its zone."""
    policy = _sa_policy()
    snap = _sa_world(seed=False)
    pods = [make_pod(f"p{i}", milli_cpu=200, labels={"app": "db"})
            for i in range(5)]
    status = assert_policy_parity(pods, snap, policy)
    placed = [p.spec.node_name for p in status.successful_pods]
    assert len(status.successful_pods) == 5
    # all service pods share the first pod's zone (zone of n1/n2, or the
    # unlabeled n3 where no zone pin applies)
    zones = {"n1": "z1", "n2": "z2", "n3": None}
    first_zone = zones[placed[0]]
    if first_zone is not None:
        assert all(zones[n] == first_zone or zones[n] is None for n in placed)


def test_policy_service_affinity_unknown_seed_node_never_pins():
    """A seeded first pod on an unknowable node stays service_pods[0]
    forever, so nothing ever pins (predicates.py: node_getter -> None)."""
    policy = _sa_policy()
    snap = _sa_world(seed_node_known=False)
    pods = [make_pod(f"p{i}", milli_cpu=200, labels={"app": "db"})
            for i in range(4)]
    status = assert_policy_parity(pods, snap, policy)
    assert len(status.successful_pods) == 4


def test_policy_service_affinity_own_selector_pins():
    """The pod's own nodeSelector resolves the label without any lock."""
    policy = _sa_policy()
    snap = _sa_world(seed=False)
    pods = [make_pod("pinned", milli_cpu=200, labels={"app": "db"},
                     node_selector={"zone": "z2"})]
    status = assert_policy_parity(pods, snap, policy)
    assert status.successful_pods[0].spec.node_name == "n2"


def test_policy_service_affinity_failed_first_is_skipped():
    """A failed service pod never enters the scheduler cache (the plugin pod
    lister, factory.go:166), so the first SUCCESSFUL matcher's bind defines
    the pin for everyone after it. run_simulation reverses the list (LIFO
    feed), so `huge` goes LAST here to be scheduled FIRST."""
    policy = _sa_policy()
    snap = _sa_world(seed=False)
    huge = make_pod("first", milli_cpu=90_000, labels={"app": "db"})
    pods = [make_pod(f"p{i}", milli_cpu=200, labels={"app": "db"})
            for i in range(3)] + [huge]
    status = assert_policy_parity(pods, snap, policy)
    assert [p.name for p in status.failed_pods] == ["first"]
    assert len(status.successful_pods) == 3
    # the first successful matcher locked its zone; followers share it
    # (or sit on the zone-less n3, which no zone pin constrains)
    zones = {"n1": "z1", "n2": "z2", "n3": None}
    placed = [p.spec.node_name for p in status.successful_pods]
    locked = zones[placed[0]]
    if locked is not None:
        assert all(zones[n] in (locked, None) for n in placed[1:])


def test_policy_service_affinity_tail_order_vs_label_customs():
    """Tail customs run in alphabetical NAME order on the host: an SA named
    'AaaZone' fails a node BEFORE a label custom named 'ZzzDisk', and the
    reverse for 'ZzzZone'/'AaaDisk' — reason strings must match either way."""
    for sa_name, lbl_name in (("AaaZone", "ZzzDisk"), ("ZzzZone", "AaaDisk")):
        from tpusim.engine.policy import ServiceAffinityArg

        policy = Policy(predicates=[
            PredicatePolicy(name=sa_name, argument=PredicateArgument(
                service_affinity=ServiceAffinityArg(labels=["zone"]))),
            PredicatePolicy(name=lbl_name, argument=PredicateArgument(
                labels_presence=LabelsPresenceArg(labels=["disktype"],
                                                  presence=True))),
        ], priorities=[])
        # one node failing BOTH: no disktype label AND wrong zone vs the
        # seeded lock (seed on n1/z1, candidate pinned pod wants z1)
        snap = _sa_world()  # n2 is z2 + no disktype -> fails both customs
        pods = [make_pod("p", milli_cpu=200, labels={"app": "db"},
                         node_selector={"zone": "z2"})]
        # nodeSelector pins z2 via MatchNodeSelector? not enabled; the SA own
        # pin (zone=z2) conflicts with every candidate except n2, which
        # fails the label custom -> everything fails, reasons must agree
        assert_policy_parity(pods, snap, policy)


def test_policy_service_affinity_locked_node_lacks_label():
    """Lock on an unlabeled node pins nothing for that label."""
    policy = _sa_policy()
    snap = _sa_world(seed_node="n3")  # seed on the zone-less node
    pods = [make_pod(f"p{i}", milli_cpu=200, labels={"app": "db"})
            for i in range(4)]
    status = assert_policy_parity(pods, snap, policy)
    assert len(status.successful_pods) == 4
    # unpinned: pods spread freely (round-robin over all 3 nodes)
    assert {p.spec.node_name for p in status.successful_pods} == \
        {"n1", "n2", "n3"}


def test_policy_service_affinity_multiple_entries_on_device():
    """Two ServiceAffinity predicates in one policy: each entry evaluates
    its own label segment as a separate stage against the shared
    first-matching-pod lock (VERDICT r3 item 8 — previously a fallback)."""
    from tpusim.api.types import Service
    from tpusim.engine.policy import ServiceAffinityArg

    policy = Policy(predicates=[
        PredicatePolicy(name="SA-One", argument=PredicateArgument(
            service_affinity=ServiceAffinityArg(labels=["zone"]))),
        PredicatePolicy(name="SA-Two", argument=PredicateArgument(
            service_affinity=ServiceAffinityArg(labels=["rack"]))),
        PredicatePolicy(name="PodFitsResources"),
    ], priorities=[PriorityPolicy(name="LeastRequestedPriority", weight=1)])
    cp = compile_policy(policy)
    assert not cp.unsupported
    assert cp.spec.sa_segs == (1, 1) and len(cp.spec.sa_slots) == 2
    assert cp.sa_entries == (("zone",), ("rack",))

    # zone AND rack must both follow the first db pod's node (n1: z1/r1);
    # n2 shares the zone but not the rack, n3 shares neither
    nodes = [
        make_node("n1", milli_cpu=9000, labels={"zone": "z1", "rack": "r1"}),
        make_node("n2", milli_cpu=9000, labels={"zone": "z1", "rack": "r2"}),
        make_node("n3", milli_cpu=9000, labels={"zone": "z2", "rack": "r3"}),
    ]
    svc = Service.from_obj({"metadata": {"name": "db",
                                         "namespace": "default"},
                            "spec": {"selector": {"app": "db"}}})
    seed = make_pod("seed", milli_cpu=100, node_name="n1", phase="Running",
                    labels={"app": "db"})
    snap = ClusterSnapshot(nodes=nodes, pods=[seed], services=[svc])
    pods = [make_pod(f"db{i}", milli_cpu=200, labels={"app": "db"})
            for i in range(3)]
    status = assert_policy_parity(pods, snap, policy)
    # both entries constrain: every db pod lands on the seed's node
    assert all(p.spec.node_name == "n1" for p in status.successful_pods)

    # differential: zone-only would have allowed n2 — prove rack bites
    zone_only = Policy(predicates=[
        PredicatePolicy(name="SA-One", argument=PredicateArgument(
            service_affinity=ServiceAffinityArg(labels=["zone"]))),
        PredicatePolicy(name="PodFitsResources"),
    ], priorities=[PriorityPolicy(name="LeastRequestedPriority", weight=1)])
    status2 = assert_policy_parity(pods, snap, zone_only)
    assert {p.spec.node_name
            for p in status2.successful_pods} == {"n1", "n2"}


def test_policy_service_affinity_with_equivalence_cache():
    """A bind that establishes the first-pod lock changes SA verdicts on
    EVERY node, so the equivalence cache must invalidate the SA predicate
    cluster-wide (factory.go's CheckServiceAffinity invalidation) — cached
    pre-lock verdicts must not leak to equivalence-class siblings."""
    from tpusim.api.types import OwnerReference
    from tpusim.simulator import ClusterCapacity, SchedulerServerConfig

    policy = _sa_policy()
    snap = _sa_world(seed=False)

    def replica(name):
        p = make_pod(name, milli_cpu=200, labels={"app": "db"})
        p.metadata.owner_references = [OwnerReference(
            kind="ReplicaSet", name="rs", uid="rs-uid", controller=True)]
        return p

    pods = [replica(f"r{i}") for i in range(4)]
    runs = []
    for ecache in (False, True):
        cc = ClusterCapacity(
            SchedulerServerConfig(policy=policy,
                                  enable_equivalence_cache=ecache),
            new_pods=list(pods), scheduled_pods=[], nodes=snap.nodes,
            services=snap.services)
        cc.run()
        runs.append(sorted((p.name, p.spec.node_name)
                           for p in cc.status.successful_pods))
        # once the first replica locked a zone, no sibling may sit in the
        # other zone
        zones = {"n1": "z1", "n2": "z2", "n3": None}
        placed_zones = {zones[n] for _, n in runs[-1]} - {None}
        assert len(placed_zones) <= 1, (ecache, runs[-1])
    assert runs[0] == runs[1]


def test_policy_unsupported_routes_end_to_end():
    """run_simulation's host-bound-policy reroute arm, end to end: an
    extender policy (the last host-bound feature) runs the reference
    orchestrator under backend='jax' and matches backend='reference'.
    A prioritize-only extender keeps the run schedulable — prioritize
    transport errors are ignored (generic_scheduler.go:649-653) — so no
    live extender server is needed."""
    from tpusim.engine.policy import ExtenderConfig

    policy = Policy(predicates=[
        PredicatePolicy(name="PodFitsResources"),
    ], priorities=[PriorityPolicy(name="LeastRequestedPriority", weight=1)],
        extender_configs=[ExtenderConfig(url_prefix="http://no-such-host",
                                         prioritize_verb="prioritize",
                                         weight=2)])
    assert compile_policy(policy).unsupported
    pods = [make_pod(f"p{i}", milli_cpu=400, labels={"app": "db"})
            for i in range(5)]
    snap = _sa_world()
    ref = run_simulation(list(pods), snap, backend="reference", policy=policy)
    jx = run_simulation(list(pods), snap, backend="jax", policy=policy)
    assert sig(jx) == sig(ref)
    assert jx.successful_pods


def test_policy_legacy_aliases_compile_and_match():
    """1.0 backward-compat names (compatibility_test.go '1.0' stanza):
    ServiceSpreadingPriority shares SelectorSpread's device path
    (service-derived signatures only) — naming BOTH spread priorities sums
    their weights like two host instances. The PodFitsPorts predicate alias
    evaluates at the host's custom tail slot — the device re-emits the
    port-conflict stage at that tail position (PolicySpec.ports_slots)."""
    from tpusim.api.types import Service

    snapshot = mixed_cluster()
    snapshot.services = [Service.from_obj(
        {"metadata": {"name": "web", "namespace": "default"},
         "spec": {"selector": {"app": "web"}}})]
    pods = workload()
    for i, p in enumerate(pods):
        if i % 2 == 0:
            p.metadata.labels["app"] = "web"
    policy = Policy(
        predicates=[PredicatePolicy(name="PodFitsHostPorts"),
                    PredicatePolicy(name="PodFitsResources"),
                    PredicatePolicy(name="MatchNodeSelector")],
        priorities=[PriorityPolicy(name="ServiceSpreadingPriority", weight=2),
                    PriorityPolicy(name="SelectorSpreadPriority", weight=3)])
    cp = compile_policy(policy)
    assert not cp.unsupported
    assert cp.spec.w_spread == 5  # summed, like two host instances
    assert_policy_parity(pods, snapshot, policy)

    # the 1.0 predicate alias compiles: the port stage re-runs at its
    # alphabetical tail slot, after every fixed-ordering predicate
    legacy = Policy(
        predicates=[PredicatePolicy(name="PodFitsPorts"),
                    PredicatePolicy(name="PodFitsResources"),
                    PredicatePolicy(name="MatchNodeSelector")],
        priorities=[PriorityPolicy(name="ServiceSpreadingPriority", weight=2)])
    cp = compile_policy(legacy)
    assert not cp.unsupported and cp.spec.ports_slots == ("tail:0",)
    assert_policy_parity(pods, snapshot, legacy)


def test_policy_ports_alias_tail_slot_reason_ordering():
    """The alias's OBSERVABLE difference from PodFitsHostPorts: it
    short-circuits AFTER the fixed ordering. A node failing both resources
    and a port conflict reports the port reason under the fixed-slot name
    (PodFitsHostPorts runs before PodFitsResources in the ordering,
    predicates.go:130-136) but the RESOURCE reason under the tail alias —
    byte-matched against the reference on both shapes."""
    from test_jax_groups import port_pod

    nodes = [make_node("tiny", milli_cpu=300)]
    # occupy the port AND most of the cpu
    seed = port_pod("seed", 7070, milli_cpu=200, node_name="tiny",
                    phase="Running")
    snap = ClusterSnapshot(nodes=nodes, pods=[seed])
    contender = port_pod("p", 7070, milli_cpu=200)

    def msg_for(pred_name):
        policy = Policy(
            predicates=[PredicatePolicy(name=pred_name),
                        PredicatePolicy(name="PodFitsResources")],
            priorities=[PriorityPolicy(name="LeastRequestedPriority",
                                       weight=1)])
        status = assert_policy_parity([contender.copy()], snap, policy)
        return status.failed_pods[0].status.conditions[-1].message

    assert "free ports" in msg_for("PodFitsHostPorts")   # fixed slot first
    assert "Insufficient cpu" in msg_for("PodFitsPorts")  # alias at tail


def test_policy_ports_alias_actually_filters():
    """Regression guard for the tail emission itself: when the port
    conflict is the pod ONLY obstacle, the alias must still veto the
    node - a silently-skipped tail stage would schedule the pod and be
    invisible to the ordering test above (both backends would report the
    earlier resource failure either way)."""
    from test_jax_groups import port_pod

    nodes = [make_node("roomy", milli_cpu=8000)]
    seed = port_pod("seed", 7070, milli_cpu=100, node_name="roomy",
                    phase="Running")
    snap = ClusterSnapshot(nodes=nodes, pods=[seed])
    policy = Policy(
        predicates=[PredicatePolicy(name="PodFitsPorts"),
                    PredicatePolicy(name="PodFitsResources")],
        priorities=[PriorityPolicy(name="LeastRequestedPriority", weight=1)])
    assert not compile_policy(policy).unsupported
    status = assert_policy_parity([port_pod("p", 7070, milli_cpu=100)],
                                  snap, policy)
    [failed] = status.failed_pods
    assert "free ports" in failed.status.conditions[-1].message


def test_policy_custom_arg_under_alias_name_keeps_its_own_key():
    """Review regression: a labelsPresence custom named 'PodFitsPorts' must
    register under ITS OWN name (plugins.go registers customs by the policy
    name; alias resolution only applies to the no-argument lookup), not be
    silently collapsed into PodFitsHostPorts."""
    policy = Policy(
        predicates=[
            PredicatePolicy(name="PodFitsPorts", argument=PredicateArgument(
                labels_presence=LabelsPresenceArg(labels=["disktype"],
                                                  presence=True))),
            PredicatePolicy(name="PodFitsHostPorts"),
        ],
        priorities=[PriorityPolicy(name="LeastRequestedPriority", weight=1)])
    cp = compile_policy(policy)
    # the custom keeps its own (tail-slot) entry and the builtin survives
    assert cp.label_rows, "custom label predicate was dropped"
    status = assert_policy_parity(workload(), mixed_cluster(), policy)
    # presence=True on 'disktype': only the ssd-labeled nodes qualify
    assert all(p.spec.node_name in ("n0", "n2", "n4")
               for p in status.successful_pods)


# ---------------------------------------------------------------------------
# ISSUE 4 — policy residue closed: every non-extender compat policy is
# Pallas fast-path eligible, and the fast route is byte-identical. The
# cheap legs (planning-only eligibility, fallback observability) run in
# tier-1; the full end-to-end interpreter matrix is the slow sweep in
# test_fuzz_differential.py.
# ---------------------------------------------------------------------------

import json
import os

COMPAT_FIXTURE = os.path.join(os.path.dirname(__file__),
                              "compat_policies.json")
with open(COMPAT_FIXTURE) as _f:
    COMPAT_POLICIES = json.load(_f)


def compat_cluster():
    """A cluster exercising every residue feature the compat policies use:
    region/zone/foo/bar labels (ServiceAffinity + presence rows + NodeLabel
    priority), a service + labeled running pods (spreading / SAA / SA
    first-pod locks), and node images (ImageLocality)."""
    from tpusim.api.types import ContainerImage, Service

    MB = 1024 * 1024
    nodes = []
    for i in range(9):
        labels = {"region": f"r{i % 2}", "zone": f"z{i % 3}"}
        if i % 3 != 2:
            labels["foo"] = "x"
        if i % 2 == 0:
            labels["bar"] = "y"
        node = make_node(f"n{i}", milli_cpu=[2000, 4000, 8000][i % 3],
                         memory=16 * 1024**3, labels=labels)
        if i % 2 == 1:
            node.status.images = [ContainerImage(names=[f"img-{i % 3}:v1"],
                                                 size_bytes=400 * MB)]
        nodes.append(node)
    services = [Service.from_obj({
        "metadata": {"name": "svc0", "namespace": "default"},
        "spec": {"selector": {"app": "app0"}}})]
    placed = [make_pod(f"placed-{i}", milli_cpu=200, memory=128 * MB,
                       node_name=f"n{i % 9}", phase="Running",
                       labels={"app": f"app{i % 2}"}) for i in range(4)]
    return ClusterSnapshot(nodes=nodes, pods=placed, services=services)


def compat_workload(k=70):
    MB = 1024 * 1024
    pods = []
    for i in range(k):
        kw = {}
        if i % 5 == 0:
            kw["node_selector"] = {"region": f"r{i % 2}"}
        p = make_pod(f"pod-{i}", milli_cpu=[100, 400, 900][i % 3],
                     memory=[64, 256][i % 2] * MB,
                     labels={"app": f"app{i % 2}"} if i % 3 else None, **kw)
        if i % 4 == 0:
            p.spec.containers[0].image = f"img-{i % 3}:v1"
        pods.append(p)
    return pods


def _compat_plan(version, snapshot, pods):
    """Mirror the backend's planning flow for one compat policy; returns
    (plan, why) from plan_fast without running any kernel."""
    from dataclasses import replace as _dc_replace

    from tpusim.engine.policy import decode_policy
    from tpusim.engine.predicates import (
        POD_TOLERATES_NODE_NO_EXECUTE_TAINTS_PRED,
    )
    from tpusim.jaxe.fastscan import plan_fast
    from tpusim.jaxe.kernels import config_for
    from tpusim.jaxe.policyc import build_policy_tables
    from tpusim.jaxe.state import NUM_FIXED_BITS, compile_cluster

    cp = compile_policy(decode_policy(COMPAT_POLICIES[version]))
    assert not cp.unsupported, cp.unsupported
    need_noexec = (cp.spec.pred_keys is not None
                   and POD_TOLERATES_NODE_NO_EXECUTE_TAINTS_PRED
                   in cp.spec.pred_keys)
    need_saa = bool(cp.spec.saa_weights) or cp.spec.sa_enabled
    compiled, cols = compile_cluster(snapshot, pods, need_noexec=need_noexec,
                                     need_saa=need_saa)
    assert not compiled.unsupported
    config = config_for(
        [compiled], most_requested=False,
        num_reason_bits=NUM_FIXED_BITS + len(compiled.scalar_names))
    config = _dc_replace(config, policy=cp.spec)
    ptabs = build_policy_tables(cp, snapshot, pods, compiled, cols)
    if cp.saa_entries:
        config = _dc_replace(config, n_saa_doms=ptabs.n_saa_doms)
    return plan_fast(config, compiled, cols, ptabs=ptabs)


def test_compat_policies_all_fast_path_eligible():
    """The ROADMAP item-4 done condition, planning leg: plan_fast returns a
    plan (no `policy:` blocker) for EVERY non-extender policy in
    compat_policies.json. Planning is host-only — no kernel compiles — so
    the whole matrix fits in tier-1."""
    snapshot = compat_cluster()
    pods = compat_workload()
    for version in sorted(COMPAT_POLICIES):
        plan, why = _compat_plan(version, snapshot, pods)
        assert plan is not None, f"policy {version} ineligible: {why}"
        assert plan.policy is not None


def test_compat_policy_fast_parity_smoke(monkeypatch):
    """One end-to-end residue policy (1.1: ServiceAffinity + SAA + label
    presence rows + NodeLabel priority) through the Pallas kernel in
    interpreter mode: byte-identical to the reference engine, with the
    kernel actually engaging and zero fast-path fallbacks recorded."""
    from tpusim.engine.policy import decode_policy
    from tpusim.framework.metrics import register
    from tpusim.jaxe import fastscan

    snapshot = compat_cluster()
    pods = compat_workload()
    policy = decode_policy(COMPAT_POLICIES["1.1"])
    ref = run_simulation(list(pods), snapshot, backend="reference",
                         policy=policy)
    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    runs = []
    real_fast_scan = fastscan.fast_scan
    monkeypatch.setattr(
        fastscan, "fast_scan",
        lambda plan, **kw: runs.append(1) or real_fast_scan(plan, **kw))
    fallback = register().fast_fallback
    before = dict(fallback.values)
    jx = run_simulation(list(pods), snapshot, backend="jax", policy=policy)
    assert runs, "pallas fast path did not engage"
    assert fallback.values == before, \
        f"unexpected fast-path fallbacks: {fallback.values}"
    assert sig(jx) == sig(ref)


def test_fast_fallback_counter_classifies_blockers(monkeypatch):
    """The observability satellite: a plan_fast rejection lands in
    tpusim_fast_fallback_total under a low-cardinality blocker class, and
    the flight recorder gets a fallback: instant."""
    from tpusim.engine.policy import decode_policy
    from tpusim.framework.metrics import register
    from tpusim.jaxe.backend import _fast_fallback_key

    # key classification covers every plan_fast reason family
    assert _fast_fallback_key(
        "3 ServiceAffinity lock segments exceed the fast-path budget "
        "(16; TPUSIM_FAST_MAX_SA_SEGS)") == "sa_segs_budget"
    assert _fast_fallback_key("NoExecute taint table not compiled") \
        == "tables_not_compiled"
    assert _fast_fallback_key("something new") == "other"

    # end-to-end: choke the SA budget so a residue policy falls back, and
    # assert the counter moved under the classified key
    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    monkeypatch.setenv("TPUSIM_FAST_MAX_SA_SEGS", "0")
    fallback = register().fast_fallback
    before = fallback.get("sa_segs_budget")
    policy = decode_policy(COMPAT_POLICIES["1.1"])
    snapshot = compat_cluster()
    pods = compat_workload(12)
    ref = run_simulation(list(pods), snapshot, backend="reference",
                         policy=policy)
    jx = run_simulation(list(pods), snapshot, backend="jax", policy=policy)
    assert sig(jx) == sig(ref)  # the XLA fallback stays byte-identical
    assert fallback.get("sa_segs_budget") >= before + 1

"""The Bind and Update seams copy only what they write.

`bind_pod` and `mark_unschedulable` build their result with
`Pod.bound_copy`: a new Pod, ObjectMeta, PodSpec and PodStatus, sharing the
pending pod's containers, init containers, affinity, tolerations, volumes
and node selector, which nothing writes once a pod is built. The answers
equal the deep copy's (`Pod.copy`, kept inline below as the oracle) for
every pod shape; writes to what the seams own do not cross between the two
pods; and the seams of a capacity query, a stream cycle with evictions and
a what-if, and the reference backend leave every pending pod as it was.
"""

import copy
import gc

import pytest

from tpusim.api.snapshot import ClusterSnapshot, make_node, make_pod, \
    make_pod_volume, synthetic_cluster
from tpusim.api.types import LABEL_HOSTNAME, OwnerReference, Pod, \
    PodCondition
from tpusim.backends import ReferenceBackend, bind_pod, mark_unschedulable
from tpusim.framework.store import DELETED
from tpusim.simulator import run_simulation
from tpusim.stream import StreamSession

REQ = "requiredDuringSchedulingIgnoredDuringExecution"
ANTI = {"podAntiAffinity": {REQ: [
    {"labelSelector": {"matchLabels": {"color": "green"}},
     "topologyKey": LABEL_HOSTNAME, "namespaces": ["sched-1", "sched-0"]}]}}


def _anti_affinity(name="p"):
    """scheduler_perf's pod-with-pod-anti-affinity template, as the
    anti-affinity benchmark cell builds it."""
    req = {"cpu": "100m", "memory": "500Mi"}
    return Pod.from_obj({
        "metadata": {"name": name, "namespace": "sched-1",
                     "uid": f"sched-1/{name}",
                     "labels": {"color": "green", "name": "test"}},
        "spec": {"containers": [{"name": "pause",
                                 "ports": [{"containerPort": 80}],
                                 "resources": {"requests": req,
                                               "limits": dict(req)}}],
                 "affinity": ANTI},
        "status": {}})


def _init_containers():
    p = make_pod("init", milli_cpu=100, memory=2**20)
    p.spec.init_containers = copy.deepcopy(p.spec.containers)
    return p


def _priority():
    p = make_pod("prio", milli_cpu=100)
    p.spec.priority = 1000
    return p


def _owner_references():
    p = make_pod("owned", milli_cpu=100)
    p.metadata.owner_references = [OwnerReference(
        api_version="apps/v1", kind="ReplicaSet", name="rs", uid="rs-1",
        controller=True)]
    return p


def _requeued():
    """A pod that already carries an Unschedulable condition."""
    p = make_pod("requeued", milli_cpu=100)
    p.status.phase = "Pending"
    p.status.conditions = [PodCondition(type="PodScheduled", status="False",
                                        reason="Unschedulable", message="x")]
    p.status.reason = "Unschedulable"
    return p


def _gang():
    p = make_pod("gang", milli_cpu=100, labels={"app": "g"})
    p.metadata.annotations = {"pod-group.tpusim.io/name": "g",
                              "pod-group.tpusim.io/min-available": "2"}
    return p


SHAPES = {
    "basic": lambda: make_pod("basic", milli_cpu=100, memory=500 * 2**20),
    "anti_affinity": _anti_affinity,
    "tolerations": lambda: make_pod("tol", milli_cpu=100, tolerations=[
        {"key": "dedicated", "operator": "Equal", "value": "infra",
         "effect": "NoSchedule"}]),
    "init_containers": _init_containers,
    "volumes": lambda: make_pod("vol", milli_cpu=100, volumes=[
        make_pod_volume("data", pvc="claim-0"),
        make_pod_volume("disk", source={"gcePersistentDisk":
                                        {"pdName": "pd-0"}})]),
    "node_selector": lambda: make_pod("sel", milli_cpu=100,
                                      node_selector={"zone": "z1"}),
    "priority": _priority,
    "owner_references": _owner_references,
    "gang": _gang,
    "requeued": _requeued,
}


def _bind_deep(pod, node_name):
    """The seam as it was: a deep copy, then the writes."""
    bound = pod.copy()
    bound.spec.node_name = node_name
    bound.status.phase = "Running"
    return bound


def _mark_deep(pod, message):
    failed = pod.copy()
    failed.status.phase = "Pending"
    failed.status.conditions.append(PodCondition(
        type="PodScheduled", status="False", reason="Unschedulable",
        message=message))
    failed.status.reason = "Unschedulable"
    return failed


SEAMS = {
    "bind": (lambda p: bind_pod(p, "n7"), lambda p: _bind_deep(p, "n7")),
    "unschedulable": (lambda p: mark_unschedulable(p, "0/3 nodes"),
                      lambda p: _mark_deep(p, "0/3 nodes")),
}


def _write_everything_the_seams_own(pod, tag):
    pod.spec.node_name = f"node-{tag}"
    pod.spec.priority = 7
    pod.status.phase = f"phase-{tag}"
    pod.status.conditions.append(PodCondition(type=tag))
    pod.status.reason = tag
    pod.status.message = tag
    pod.status.nominated_node_name = tag
    pod.metadata.labels[tag] = tag
    pod.metadata.annotations[tag] = tag
    pod.metadata.owner_references.append(OwnerReference(name=tag))
    for ref in pod.metadata.owner_references:
        ref.uid = tag


@pytest.mark.parametrize("seam", sorted(SEAMS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_result_equals_the_deep_copy_seam(shape, seam):
    shared, deep = SEAMS[seam]
    pod = SHAPES[shape]()
    got, want = shared(pod), deep(SHAPES[shape]())
    assert got.to_obj() == want.to_obj()
    assert got == want


@pytest.mark.parametrize("seam", sorted(SEAMS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_writes_to_what_the_seam_owns_stay_on_their_pod(shape, seam):
    shared, _ = SEAMS[seam]
    pending = SHAPES[shape]()
    before = pending.to_obj()
    result = shared(pending)
    after = result.to_obj()
    _write_everything_the_seams_own(result, "result")
    assert pending.to_obj() == before
    _write_everything_the_seams_own(pending, "pending")
    fresh = shared(SHAPES[shape]())
    _write_everything_the_seams_own(fresh, "result")
    assert result.to_obj() == fresh.to_obj() != after


@pytest.mark.parametrize("seam", sorted(SEAMS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_spec_parts_nothing_writes_are_shared(shape, seam):
    shared, _ = SEAMS[seam]
    pending = SHAPES[shape]()
    result = shared(pending)
    for part in ("containers", "init_containers", "affinity", "tolerations",
                 "volumes", "node_selector"):
        assert getattr(result.spec, part) is getattr(pending.spec, part)
    for own in ("metadata", "spec", "status"):
        assert getattr(result, own) is not getattr(pending, own)
    meta, pmeta = result.metadata, pending.metadata
    assert meta.labels is not pmeta.labels
    assert meta.annotations is not pmeta.annotations
    assert meta.owner_references is not pmeta.owner_references
    assert all(r is not q for r, q in zip(meta.owner_references,
                                          pmeta.owner_references))
    assert result.status.conditions is not pending.status.conditions


def _tracked_objects(make):
    """gc-tracked objects that `make()` leaves alive, the collector off."""
    gc.disable()
    try:
        gc.collect()
        before = len(gc.get_objects())
        kept = make()
        grown = len(gc.get_objects()) - before
        del kept
    finally:
        gc.enable()
    return grown


def test_a_bind_of_the_anti_affinity_template_makes_few_objects():
    pod = _anti_affinity()
    shared = _tracked_objects(lambda: bind_pod(pod, "n0"))
    deep = _tracked_objects(lambda: _bind_deep(pod, "n0"))
    assert shared <= 10 < deep


# --- the seams in a run: every pending pod is left as it was ---


def _snapshot_of(pods):
    """What a run could write in place: each pod's object form, and its
    shared spec parts copied whole."""
    return [(p.to_obj(), copy.deepcopy(p.spec.containers),
             copy.deepcopy(p.spec.affinity)) for p in pods]


def _assert_untouched(pods, taken):
    for p, (obj, containers, affinity) in zip(pods, taken):
        assert p.to_obj() == obj
        assert p.spec.containers == containers
        assert p.spec.affinity == affinity


def _hostname_cluster(n_nodes):
    nodes = [make_node(f"n{i}", milli_cpu=4000, memory=2**34,
                       labels={LABEL_HOSTNAME: f"n{i}"})
             for i in range(n_nodes)]
    bound = [_anti_affinity(f"init-{i}") for i in range(2)]
    for i, p in enumerate(bound):
        p.metadata.namespace = "sched-0"
        p.spec.node_name = f"n{3 * i}"
        p.status.phase = "Running"
    return ClusterSnapshot(nodes=nodes, pods=bound)


def test_a_capacity_query_leaves_pending_pods_untouched(monkeypatch):
    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    pods = [_anti_affinity(f"m-{i}") for i in range(8)]
    taken = _snapshot_of(pods)
    status = run_simulation(pods, _hostname_cluster(8), backend="jax")
    # two nodes hold an init pod: six of the eight fit, one to a node
    assert len(status.successful_pods) == 6
    assert len(status.failed_pods) == 2
    _assert_untouched(pods, taken)
    by_key = {p.key(): p for p in pods}
    for placed in status.successful_pods:
        assert placed.spec.containers is by_key[placed.key()].spec.containers


def _tolerating(name):
    return make_pod(name, milli_cpu=100, memory=2**20,
                    tolerations=[{"key": "k", "operator": "Exists"}])


def test_a_stream_cycle_and_a_what_if_leave_pending_pods_untouched():
    session = StreamSession(synthetic_cluster(8))
    batches = [[_tolerating(f"c{c}-{i}") for i in range(6)]
               for c in range(4)]
    taken = [_snapshot_of(b) for b in batches]
    placed = session.schedule(batches[0]) + session.schedule(batches[1])
    assert all(pl.node_name for pl in placed)
    # a resident cycle after evictions, then a live what-if on the twin
    session.apply_events([(DELETED, pl.pod) for pl in placed[:3]])
    assert all(pl.node_name for pl in session.schedule(batches[2]))
    assert len(session.overlay_query(batches[3])) == 6
    for batch, snap in zip(batches, taken):
        _assert_untouched(batch, snap)


def test_a_reference_backend_run_leaves_pending_pods_untouched():
    pods = [_anti_affinity(f"m-{i}") for i in range(8)]
    taken = _snapshot_of(pods)
    placements = ReferenceBackend().schedule(pods, _hostname_cluster(8))
    assert sum(1 for pl in placements if pl.node_name) == 6
    _assert_untouched(pods, taken)
    for pod, pl in zip(pods, placements):
        assert pl.pod.spec.affinity is pod.spec.affinity

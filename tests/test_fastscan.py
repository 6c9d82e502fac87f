"""Pallas fast-path parity: fast_scan == schedule_scan, bit for bit.

Runs the kernel in interpreter mode on CPU (auto-selected off-TPU), over
failure-heavy workloads exercising every eligible stage: node conditions,
unschedulable, resource exhaustion (cpu/mem/pods), hostname pins, selectors
incl. never-matching zones, NoSchedule taints + tolerations, best-effort
zero-request pods, preferred node affinity, PreferNoSchedule taint scoring,
seeded running pods in the initial carry, and both providers.
"""

import numpy as np
import pytest

pytest.importorskip("jax")

from tpusim.jaxe import ensure_x64  # noqa: E402

ensure_x64()

from tpusim.api.snapshot import ClusterSnapshot, make_node, make_pod  # noqa: E402
from tpusim.jaxe.fastscan import fast_scan, plan_fast  # noqa: E402
from tpusim.jaxe.kernels import (  # noqa: E402
    carry_init,
    config_for,
    pod_columns_to_device,
    schedule_scan,
    statics_to_device,
)
from tpusim.jaxe.state import NUM_FIXED_BITS, compile_cluster  # noqa: E402


def build(seed: int, num_nodes: int = 40, num_pods: int = 180):
    rng = np.random.RandomState(seed)
    nodes = []
    for i in range(num_nodes):
        taints = None
        if i % 3 == 0:
            taints = [{"key": "dedicated", "value": "batch",
                       "effect": "NoSchedule"}]
        if i % 5 == 1:
            taints = (taints or []) + [{"key": "soft", "value": "x",
                                        "effect": "PreferNoSchedule"}]
        nodes.append(make_node(
            f"n{i}", milli_cpu=int(rng.choice([500, 1000, 2000])),
            memory=int(rng.choice([1, 2, 4])) * 1024**3,
            pods=int(rng.choice([3, 8, 110])),
            labels={"zone": f"z{i % 3}"}, taints=taints,
            unschedulable=(i % 13 == 0), ready=(i % 17 != 3)))
    running = [make_pod(f"r{i}", milli_cpu=300, memory=2**28,
                        node_name=f"n{i % num_nodes}", phase="Running")
               for i in range(25)]
    pods = []
    for i in range(num_pods):
        kw = {}
        if i % 5 == 0:
            kw["tolerations"] = [{"key": "dedicated", "operator": "Equal",
                                  "value": "batch", "effect": "NoSchedule"}]
        if i % 4 == 0:
            kw["node_selector"] = {"zone": f"z{i % 4}"}  # z3 never matches
        if i % 9 == 0:
            kw["node_name"] = f"n{i % 50}"  # hostname pins, some dangling
        if i % 13 == 0:
            pods.append(make_pod(f"p{i}"))  # zero-request best-effort
            continue
        if i % 11 == 0:
            kw["affinity"] = {"nodeAffinity": {
                "preferredDuringSchedulingIgnoredDuringExecution": [
                    {"weight": 10, "preference": {"matchExpressions": [
                        {"key": "zone", "operator": "In",
                         "values": ["z1"]}]}}]}}
        pods.append(make_pod(
            f"p{i}", milli_cpu=int(rng.randint(1, 25)) * 100,
            memory=int(rng.randint(1, 24)) * 2**27, **kw))
    return ClusterSnapshot(nodes=nodes, pods=running), pods


@pytest.mark.parametrize("seed,most_requested", [(0, False), (1, True)])
def test_fast_scan_matches_xla_scan(seed, most_requested):
    snapshot, pods = build(seed)
    compiled, cols = compile_cluster(snapshot, pods)
    assert not compiled.unsupported
    config = config_for(
        [compiled], most_requested=most_requested,
        num_reason_bits=NUM_FIXED_BITS + len(compiled.scalar_names))
    plan, reason = plan_fast(config, compiled, cols)
    assert plan is not None, reason

    _, choices, counts, advanced = schedule_scan(
        config, carry_init(compiled), statics_to_device(compiled),
        pod_columns_to_device(cols))
    # chunk 64 exercises multiple kernel invocations + ghost-padded tails
    f_choices, f_counts, f_adv = fast_scan(plan, chunk=64)
    assert np.array_equal(f_choices, np.asarray(choices))
    assert np.array_equal(f_counts, np.asarray(counts))
    assert np.array_equal(f_adv, np.asarray(advanced))
    scheduled = int(np.sum(f_choices >= 0))
    assert 0 < scheduled < len(pods)  # both outcomes actually exercised


def test_backend_fast_path_matches_xla(monkeypatch):
    from tpusim.jaxe import fastscan
    from tpusim.jaxe.backend import JaxBackend

    snapshot, pods = build(3, num_nodes=20, num_pods=60)
    baseline = JaxBackend().schedule(pods, snapshot)
    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    # guard against the fast path silently not engaging (which would make
    # this comparison vacuous): count actual kernel runs
    runs = []
    real_fast_scan = fastscan.fast_scan
    monkeypatch.setattr(
        fastscan, "fast_scan",
        lambda plan, **kw: runs.append(1) or real_fast_scan(plan, **kw))
    fast = JaxBackend().schedule(pods, snapshot)
    assert runs, "pallas fast path did not engage"
    assert [(p.pod.metadata.name, p.pod.spec.node_name, p.message)
            for p in fast] == \
           [(p.pod.metadata.name, p.pod.spec.node_name, p.message)
            for p in baseline]


def _diff(snapshot, pods, most_requested=False):
    compiled, cols = compile_cluster(snapshot, pods)
    assert not compiled.unsupported
    config = config_for(
        [compiled], most_requested=most_requested,
        num_reason_bits=NUM_FIXED_BITS + len(compiled.scalar_names))
    plan, reason = plan_fast(config, compiled, cols)
    assert plan is not None, reason
    _, choices, counts, advanced = schedule_scan(
        config, carry_init(compiled), statics_to_device(compiled),
        pod_columns_to_device(cols))
    f_choices, f_counts, f_adv = fast_scan(plan, chunk=32)
    assert np.array_equal(f_choices, np.asarray(choices))
    assert np.array_equal(f_counts, np.asarray(counts))
    assert np.array_equal(f_adv, np.asarray(advanced))
    return f_choices


def test_gpu_pods_and_single_node():
    nodes = [make_node("n0", milli_cpu=2000, memory=2 * 1024**3, gpus=2)]
    pods = [make_pod(f"g{i}", milli_cpu=100, memory=2**20, gpus=1)
            for i in range(4)]
    choices = _diff(ClusterSnapshot(nodes=nodes), pods)
    # 2 GPUs: first two pods fit, the rest report Insufficient gpu
    assert (choices >= 0).tolist() == [True, True, False, False]


def test_all_infeasible_workload():
    nodes = [make_node(f"n{i}", milli_cpu=500, memory=2**28)
             for i in range(3)]
    pods = [make_pod(f"p{i}", milli_cpu=4000, memory=2**30)
            for i in range(5)]
    choices = _diff(ClusterSnapshot(nodes=nodes), pods)
    assert (choices == -1).all()


def test_empty_pod_batch():
    nodes = [make_node("n0")]
    compiled, cols = compile_cluster(ClusterSnapshot(nodes=nodes), [])
    config = config_for([compiled], most_requested=False,
                        num_reason_bits=NUM_FIXED_BITS)
    plan, reason = plan_fast(config, compiled, cols)
    assert plan is not None, reason
    choices, counts, adv = fast_scan(plan)
    assert choices.shape == (0,) and counts.shape == (0, NUM_FIXED_BITS)


def test_ineligible_workloads_report_reasons(monkeypatch):
    # interpod is fast-path-native since round 5; budget overruns still
    # report a reason (the topo-dom budget here, forced to 1), naming the
    # key that crossed it. A hostname key carries no domains, so the term
    # is zone-keyed.
    nodes = [make_node("n0", labels={"zone": "z0"})]
    pods = [make_pod("p0", milli_cpu=100, memory=2**20, labels={"app": "a"},
                     affinity={"podAffinity": {
                         "requiredDuringSchedulingIgnoredDuringExecution": [
                             {"labelSelector": {"matchLabels": {"app": "a"}},
                              "topologyKey": "zone"}]}})]
    compiled, cols = compile_cluster(ClusterSnapshot(nodes=nodes), pods)
    config = config_for([compiled], most_requested=False,
                        num_reason_bits=NUM_FIXED_BITS)
    plan, reason = plan_fast(config, compiled, cols)
    assert plan is not None
    monkeypatch.setenv("TPUSIM_FAST_MAX_TOPO_DOMS", "1")
    plan, reason = plan_fast(config, compiled, cols)
    assert plan is None
    assert "topology domains exceed" in reason
    assert "'zone'" in reason


def test_scalar_resources_eligible_and_exact():
    """Round-3 eligibility expansion: scalar (extended) resources run on the
    fast path with bit-identical placements and reason histograms."""
    nodes = [make_node(f"n{i}", milli_cpu=4000, memory=8 * 1024**3,
                       scalars={"example.com/widget": 4 - i % 3,
                                "example.com/gadget": 1000 * (1 + i % 2)})
             for i in range(12)]
    running = [make_pod(f"r{i}", milli_cpu=200, memory=2**26,
                        node_name=f"n{i}", phase="Running",
                        scalars={"example.com/widget": 1})
               for i in range(4)]
    pods = []
    for i in range(60):
        kw = {}
        if i % 2 == 0:
            kw["scalars"] = {"example.com/widget": 1 + i % 3}
        elif i % 5 == 0:
            kw["scalars"] = {"example.com/gadget": 700}
        pods.append(make_pod(f"p{i}", milli_cpu=300, memory=2**27, **kw))
    choices = _diff(ClusterSnapshot(nodes=nodes, pods=running), pods)
    assert 0 < int(np.sum(choices >= 0)) < len(pods)  # widget exhaustion hits


def test_scalar_reason_bits_match_reference_strings():
    """The scalar failure bit decodes to the exact reference reason string."""
    from tpusim.jaxe.backend import format_fit_error
    from tpusim.jaxe.state import reason_strings

    nodes = [make_node("n0", milli_cpu=4000, scalars={"example.com/widget": 1})]
    pods = [make_pod(f"p{i}", milli_cpu=100,
                     scalars={"example.com/widget": 1}) for i in range(3)]
    compiled, cols = compile_cluster(ClusterSnapshot(nodes=nodes), pods)
    config = config_for(
        [compiled], most_requested=False,
        num_reason_bits=NUM_FIXED_BITS + len(compiled.scalar_names))
    plan, reason = plan_fast(config, compiled, cols)
    assert plan is not None, reason
    f_choices, f_counts, _ = fast_scan(plan)
    assert (f_choices >= 0).tolist() == [True, False, False]
    msg = format_fit_error(1, f_counts[1], reason_strings(compiled.scalar_names))
    assert "Insufficient example.com/widget" in msg


def _outcomes(placements):
    return [(p.pod.metadata.name, p.pod.spec.node_name, p.message)
            for p in placements]


def test_auto_mode_env_gates(monkeypatch):
    """AUTO (env unset): default-on only on TPU, with verification requested
    until the first self-check passes; explicit 0/1 still win."""
    from tpusim.jaxe.trust import FAST

    monkeypatch.delenv("TPUSIM_FAST", raising=False)
    monkeypatch.delenv("TPUSIM_FAST_INTERPRET", raising=False)
    # this suite runs on the CPU backend: AUTO must stay off (the
    # interpreter is not a fast path)
    assert FAST.mode() == (False, True)
    monkeypatch.setenv("TPUSIM_FAST", "0")
    assert FAST.mode() == (False, False)
    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    assert FAST.mode() == (True, False)
    # a failed self-check pins the process off even in AUTO
    monkeypatch.delenv("TPUSIM_FAST", raising=False)
    FAST.disabled = True
    assert FAST.mode() == (False, False)


def _run_auto(monkeypatch, corrupt=None, boom=False, num_pods=120):
    """Drive JaxBackend through the AUTO fast path on CPU (interpreter) by
    forcing the gate open with verification on; returns (baseline, auto)."""
    from tpusim.jaxe import backend, fastscan
    from tpusim.jaxe.trust import FAST

    snapshot, pods = build(3, num_nodes=20, num_pods=num_pods)
    monkeypatch.delenv("TPUSIM_FAST", raising=False)
    baseline = backend.JaxBackend().schedule(pods, snapshot)

    monkeypatch.setattr(FAST, "mode", lambda: (True, True))
    real = fastscan.fast_scan
    calls = []

    def wrapped(plan, **kw):
        calls.append(1)
        if boom:
            raise RuntimeError(boom)
        choices, counts, adv = real(plan, **kw)
        if corrupt is not None:
            choices = np.array(choices, copy=True)
            choices[0] = corrupt(choices[0])
        return choices, counts, adv

    monkeypatch.setattr(fastscan, "fast_scan", wrapped)
    auto = backend.JaxBackend().schedule(pods, snapshot)
    return FAST, baseline, auto, calls


def test_auto_verification_passes_and_trusts(monkeypatch):
    fast, baseline, auto, calls = _run_auto(monkeypatch)
    assert calls, "pallas fast path did not engage"
    assert _outcomes(auto) == _outcomes(baseline)
    assert fast.verified
    assert fast.disabled is False


def test_auto_small_batch_skips_fast_path(monkeypatch):
    """An unverified batch below trust.PIN_MIN_PODS must not run the
    kernel at all: running it plus a full XLA replay would be strictly
    slower than plain XLA, and passing on tiny evidence must not pin
    process-wide trust either."""
    fast, baseline, auto, calls = _run_auto(monkeypatch, num_pods=20)
    assert not calls  # routed straight to the XLA scan
    assert _outcomes(auto) == _outcomes(baseline)
    assert not fast.verified
    assert fast.disabled is False


def test_auto_verification_mismatch_falls_back(monkeypatch):
    """A kernel that lowers but miscomputes must lose to the XLA scan: the
    guardrail discards the fast results and pins the process off."""
    fast, baseline, auto, _calls = _run_auto(
        monkeypatch, corrupt=lambda c: -1 if c >= 0 else 0)
    assert _outcomes(auto) == _outcomes(baseline)
    assert fast.disabled is True


def test_auto_fast_path_exception_propagates(monkeypatch):
    """A Mosaic rejection inside fast_scan reaches the caller: the batch
    is never quietly re-run on the XLA scan."""
    with pytest.raises(RuntimeError, match="mosaic said no"):
        _run_auto(monkeypatch, boom="mosaic said no")


def test_auto_fast_path_transient_reruns_on_xla(monkeypatch):
    """A one-off device OOM reruns that batch on the XLA scan, on the same
    device, and leaves the fast path on for the next batch (ADVICE r4)."""
    fast, baseline, auto, calls = _run_auto(
        monkeypatch, boom="RESOURCE_EXHAUSTED: out of memory allocating")
    assert calls
    assert _outcomes(auto) == _outcomes(baseline)
    assert fast.transient == 1
    assert fast.disabled is False


def test_too_many_scalar_kinds_fall_back():
    scal = {f"example.com/r{i}": 1 for i in range(8)}  # > 6-bit budget
    nodes = [make_node("n0", scalars=scal)]
    pods = [make_pod("p0", milli_cpu=100, scalars=scal)]
    compiled, cols = compile_cluster(ClusterSnapshot(nodes=nodes), pods)
    config = config_for(
        [compiled], most_requested=False,
        num_reason_bits=NUM_FIXED_BITS + len(compiled.scalar_names))
    plan, reason = plan_fast(config, compiled, cols)
    assert plan is None
    assert "reason-bit budget" in reason


# --------------------------------------------------------------------------
# pod-group features: host ports / disk conflicts / spreading / volume zones
# run on the fast path via the [Gpad, Npad] presence carry (round 4)
# --------------------------------------------------------------------------

from tpusim.api.snapshot import make_pod_volume, make_pv, make_pvc  # noqa: E402
from tpusim.api.types import (  # noqa: E402
    LABEL_ZONE_FAILURE_DOMAIN,
    ContainerPort,
    Service,
)


def _service(name, selector, namespace="default"):
    return Service.from_obj(
        {"metadata": {"name": name, "namespace": namespace},
         "spec": {"selector": selector}})


def _port_pod(name, port, **kw):
    p = make_pod(name, milli_cpu=100, **kw)
    p.spec.containers[0].ports = [ContainerPort.from_obj(
        {"containerPort": port, "hostPort": port})]
    return p


def test_host_ports_parity_and_exhaustion():
    nodes = [make_node(f"n{i}") for i in range(3)]
    pods = [_port_pod(f"p{i}", 8080) for i in range(6)] \
        + [_port_pod("other", 9090)]
    choices = _diff(ClusterSnapshot(nodes=nodes), pods)
    # one 8080 pod per node, then port-exhausted; 9090 still fits
    assert int((choices >= 0).sum()) == 4
    assert choices[-1] >= 0


def test_host_ports_seeded_presence():
    """Running pods' port occupancy must block from the very first pod."""
    nodes = [make_node(f"n{i}") for i in range(2)]
    seeded = _port_pod("seed", 8080, node_name="n0", phase="Running")
    pods = [_port_pod(f"p{i}", 8080) for i in range(2)]
    choices = _diff(ClusterSnapshot(nodes=nodes, pods=[seeded]), pods)
    assert int((choices >= 0).sum()) == 1  # only n1 is free


def test_disk_conflict_parity():
    # RBD (not GCE PD/EBS): NoDiskConflict covers it while the maxpd
    # volume-count predicates — still a fast-path fallback — do not
    nodes = [make_node(f"n{i}") for i in range(2)]
    vol = [make_pod_volume("v", {"rbd": {"monitors": ["a", "b"],
                                         "pool": "test", "image": "bar"}})]
    pods = [make_pod(f"p{i}", milli_cpu=100, volumes=vol) for i in range(4)]
    choices = _diff(ClusterSnapshot(nodes=nodes), pods)
    # the same RBD image cannot mount read-write on two pods per node
    assert int((choices >= 0).sum()) == 2


def test_selector_spread_parity_plain_and_zones():
    nodes = [make_node(f"n{i}", labels={
        LABEL_ZONE_FAILURE_DOMAIN: f"z{i % 2}"}) for i in range(4)]
    nodes.append(make_node("n-nozone"))
    existing = [make_pod(f"e{i}", node_name=f"n{i % 2}", phase="Running",
                         labels={"app": "api"}) for i in range(3)]
    snap = ClusterSnapshot(nodes=nodes, pods=existing,
                           services=[_service("api", {"app": "api"})])
    pods = [make_pod(f"p{i}", milli_cpu=10, labels={"app": "api"})
            for i in range(8)]
    choices = _diff(snap, pods)
    assert int((choices >= 0).sum()) == 8


def test_volume_zone_parity():
    nodes = [make_node(f"n{i}", labels={
        LABEL_ZONE_FAILURE_DOMAIN: f"z{i % 2}"}) for i in range(4)]
    pvs = [make_pv("pv-a", labels={LABEL_ZONE_FAILURE_DOMAIN: "z0"})]
    pvcs = [make_pvc("claim-a", volume_name="pv-a")]
    pods = [make_pod(f"p{i}", milli_cpu=10,
                     volumes=[make_pod_volume("v", pvc="claim-a")])
            for i in range(3)]
    snap = ClusterSnapshot(nodes=nodes, pvs=pvs, pvcs=pvcs)
    choices = _diff(snap, pods)
    # all pods pinned to z0 nodes (n0, n2) by the bound PV's zone label
    assert all(int(c) % 2 == 0 for c in choices if c >= 0)
    assert int((choices >= 0).sum()) == 3


def test_all_group_features_combined_parity():
    """Ports + spreading + disk conflicts + volume zones in ONE workload,
    byte-identical to the XLA scan (choices, counts, rr advancement)."""
    rng = np.random.RandomState(7)
    nodes = [make_node(f"n{i}", milli_cpu=2000, memory=4 * 1024**3,
                       labels={LABEL_ZONE_FAILURE_DOMAIN: f"z{i % 2}"})
             for i in range(8)]
    pvs = [make_pv("pv-z", labels={LABEL_ZONE_FAILURE_DOMAIN: "z1"})]
    pvcs = [make_pvc("claim-z", volume_name="pv-z")]
    existing = [make_pod(f"e{i}", node_name=f"n{i % 3}", phase="Running",
                         labels={"app": "web"}) for i in range(4)]
    svc = [_service("web", {"app": "web"})]
    vol = [make_pod_volume("d", {"rbd": {"monitors": ["m"],
                                         "pool": "p", "image": "x"}})]
    pods = []
    for i in range(40):
        kw = {}
        if i % 3 == 0:
            kw["labels"] = {"app": "web"}
        if i % 7 == 0:
            kw["volumes"] = [make_pod_volume("v", pvc="claim-z")]
        elif i % 5 == 0:
            kw["volumes"] = vol
        p = make_pod(f"p{i}", milli_cpu=int(rng.randint(1, 6)) * 100,
                     memory=int(rng.randint(1, 8)) * 2**26, **kw)
        if i % 4 == 0:
            p.spec.containers[0].ports = [ContainerPort.from_obj(
                {"containerPort": 80, "hostPort": 8000 + (i % 2)})]
        pods.append(p)
    snap = ClusterSnapshot(nodes=nodes, pods=existing, services=svc,
                           pvs=pvs, pvcs=pvcs)
    choices = _diff(snap, pods)
    assert 0 < int((choices >= 0).sum()) <= len(pods)


def test_fuzz_group_fast_path_parity():
    """Randomized mixed group workloads (ports + services/zones + RBD disk
    conflicts + PVC volume zones + plain pods) through plan_fast/fast_scan
    vs the XLA scan, bit-for-bit. TPUSIM_FUZZ_SEEDS scales the sweep."""
    import os
    import random

    seeds = max(int(os.environ.get("TPUSIM_FUZZ_SEEDS", "3")), 1)
    skipped = 0
    for seed in range(min(seeds, 25)):
        rng = random.Random(9000 + seed)
        n_nodes = rng.randint(4, 10)
        nodes = []
        for i in range(n_nodes):
            labels = {}
            if rng.random() < 0.7:
                labels[LABEL_ZONE_FAILURE_DOMAIN] = f"z{i % 3}"
            nodes.append(make_node(
                f"n{i}", milli_cpu=rng.choice([1000, 2000, 4000]),
                memory=rng.choice([2, 4, 8]) * 1024**3,
                pods=rng.choice([5, 20, 110]), labels=labels or None))
        pvs = [make_pv("pv-z", labels={LABEL_ZONE_FAILURE_DOMAIN: "z1"})]
        pvcs = [make_pvc("claim-z", volume_name="pv-z")]
        services = [_service("s0", {"app": "a0"}),
                    _service("s1", {"app": "a1"})]
        existing = [make_pod(f"e{i}", node_name=f"n{i % n_nodes}",
                             phase="Running",
                             labels={"app": f"a{i % 2}"})
                    for i in range(rng.randint(0, 5))]
        pods = []
        for i in range(rng.randint(15, 35)):
            kw = {}
            if rng.random() < 0.5:
                kw["labels"] = {"app": f"a{rng.randrange(3)}"}
            r = rng.random()
            if r < 0.15:
                kw["volumes"] = [make_pod_volume("v", pvc="claim-z")]
            elif r < 0.3:
                kw["volumes"] = [make_pod_volume(
                    "d", {"rbd": {"monitors": ["m"], "pool": "p",
                                  "image": f"img{rng.randrange(2)}"}})]
            elif r < 0.4:
                # MaxPD (fast-path-native since round 5): exercises the
                # used-volume union carry and the shared-volumeID disk
                # -conflict path; exhaustion of the per-type LIMIT is
                # pinned separately by test_maxpd_exhaustion_parity,
                # which forces KUBE_MAX_PD_VOLS low
                kw["volumes"] = [make_pod_volume(
                    "b", {"awsElasticBlockStore":
                          {"volumeID": f"ebs{rng.randrange(4)}"}})]
            p = make_pod(f"p{i}", milli_cpu=rng.randrange(1, 12) * 100,
                         memory=rng.randrange(1, 12) * 2**26, **kw)
            if rng.random() < 0.4:
                p.spec.containers[0].ports = [ContainerPort.from_obj(
                    {"containerPort": 80,
                     "hostPort": rng.choice([8080, 9090])})]
            pods.append(p)
        snap = ClusterSnapshot(nodes=nodes, pods=existing,
                               services=services, pvs=pvs, pvcs=pvcs)
        compiled, cols = compile_cluster(snap, pods)
        assert not compiled.unsupported, compiled.unsupported
        config = config_for(
            [compiled], most_requested=bool(rng.getrandbits(1)),
            num_reason_bits=NUM_FIXED_BITS + len(compiled.scalar_names))
        plan, reason = plan_fast(config, compiled, cols)
        if plan is None:
            # budget rejections are legitimate (e.g. many merged groups);
            # they must never be wrong-answer escapes, so count them
            skipped += 1
            continue
        _, choices, counts, advanced = schedule_scan(
            config, carry_init(compiled), statics_to_device(compiled),
            pod_columns_to_device(cols))
        f_choices, f_counts, f_adv = fast_scan(plan, chunk=16)
        assert np.array_equal(f_choices, np.asarray(choices)), f"seed {seed}"
        assert np.array_equal(f_counts, np.asarray(counts)), f"seed {seed}"
        assert np.array_equal(f_adv, np.asarray(advanced)), f"seed {seed}"
    # the sweep must mostly engage the fast path to mean anything
    assert skipped <= max(1, min(seeds, 25) // 3), \
        f"{skipped} of {min(seeds, 25)} seeds fell back"


def test_fast_path_over_incremental_compile(monkeypatch):
    """The event-log path hands a cached (CompiledCluster, PodColumns) into
    JaxBackend.schedule; the fast path must consume that incremental state
    (updated dynamic columns, presence) identically to a fresh compile."""
    from tpusim.framework.store import ADDED, DELETED
    from tpusim.jaxe import fastscan
    from tpusim.jaxe.delta import IncrementalCluster

    snap = ClusterSnapshot(
        nodes=[make_node(f"n{i}") for i in range(4)],
        services=[_service("web", {"app": "web"})])
    inc = IncrementalCluster(snap)
    inc.apply(ADDED, make_node("n4"))
    inc.apply(ADDED, make_pod("placed", milli_cpu=500, node_name="n0",
                              phase="Running", labels={"app": "web"}))
    gone = _port_pod("gone", 8080, node_name="n1", phase="Running")
    inc.apply(ADDED, gone)
    inc.apply(DELETED, gone)
    pods = [_port_pod(f"p{i}", 8080,
                      labels={"app": "web"} if i % 2 == 0 else None)
            for i in range(6)]

    baseline = inc.schedule([p.copy() for p in pods], fallback="error")
    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    runs = []
    real = fastscan.fast_scan
    monkeypatch.setattr(fastscan, "fast_scan",
                        lambda plan, **kw: runs.append(1) or real(plan, **kw))
    fast = inc.schedule([p.copy() for p in pods], fallback="error")
    assert runs, "fast path did not engage on the incremental compile"
    assert _outcomes(fast) == _outcomes(baseline)
    # port occupancy of the deleted pod must be gone: one 8080 pod per node
    assert sum(1 for p in fast if p.node_name) == 5


def test_group_budget_falls_back(monkeypatch):
    monkeypatch.setenv("TPUSIM_FAST_MAX_GROUPS", "2")
    nodes = [make_node("n0")]
    pods = [_port_pod(f"p{i}", 8000 + i) for i in range(4)]
    compiled, cols = compile_cluster(ClusterSnapshot(nodes=nodes), pods)
    config = config_for([compiled], most_requested=False,
                        num_reason_bits=NUM_FIXED_BITS)
    plan, reason = plan_fast(config, compiled, cols)
    assert plan is None
    assert "unrolled-loop budget" in reason


def test_failure_classification(monkeypatch):
    """ADVICE r4: transient runtime errors (device OOM etc) must not
    permanently disable the fast path — but three in a row do. A lowering
    or compile error is not classified at all: the caller re-raises it,
    even when it names an exhausted resource (a VMEM overflow)."""
    from tpusim.jaxe.trust import FAST

    oom = RuntimeError("RESOURCE_EXHAUSTED: out of memory while allocating")
    assert FAST.note_failure(oom)
    assert FAST.disabled is False
    assert FAST.note_failure(oom)
    assert FAST.disabled is False
    assert FAST.note_failure(oom)
    assert FAST.disabled is True

    FAST.reset()
    for msg in ("Mosaic failed to compile TPU kernel: unsupported block",
                "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem"):
        assert not FAST.note_failure(RuntimeError(msg))
    assert FAST.transient == 0
    assert FAST.disabled is False


def test_forced_mode_honors_disabled(monkeypatch):
    """ADVICE r4: a persistently failing kernel under TPUSIM_FAST=1 must not
    re-attempt (and re-upload the plan) on every batch."""
    from tpusim.jaxe.trust import FAST

    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    FAST.disabled = True
    assert FAST.mode() == (False, False)


def test_trust_is_per_kernel_signature(monkeypatch):
    """ADVICE r4 (medium): trust pinned by one kernel variant must not
    exempt a different variant — a workload with different feature flags
    or node padding re-verifies."""
    fast, baseline, auto, calls = _run_auto(monkeypatch)
    sigs = fast.verified
    assert len(sigs) == 1
    sig = next(iter(sigs))
    # same variant: no re-verification wanted; different npad: verify again
    assert fast.disabled is False
    other = (sig[0] + 128,) + sig[1:]
    assert other not in sigs


def test_fuzz_interpod_fast_path_parity():
    """Randomized inter-pod (anti)affinity workloads — required affinity /
    anti-affinity, preferred terms with signed weights, hostname and label
    topologies, pre-placed pods — through plan_fast/fast_scan vs the XLA
    scan, bit-for-bit (round 5). TPUSIM_FUZZ_SEEDS scales the sweep."""
    import os
    import random

    seeds = max(int(os.environ.get("TPUSIM_FUZZ_SEEDS", "3")), 1)
    skipped = 0
    for seed in range(min(seeds, 25)):
        rng = random.Random(7100 + seed)
        # kept small on purpose: every distinct group universe bakes its
        # own kernel variant (exist-side tables are compile-time
        # constants), and an interpreter-mode variant traces in ~1-2 min
        # at Gpad 16 — diversity comes from seeds, not per-seed size
        n_nodes = rng.randint(4, 8)
        nodes = []
        for i in range(n_nodes):
            labels = {"rack": f"r{i % rng.choice([2, 3])}"}
            if rng.random() < 0.8:
                labels["zone"] = f"z{i % 3}"
            nodes.append(make_node(
                f"n{i}", milli_cpu=rng.choice([2000, 4000, 8000]),
                memory=rng.choice([4, 8]) * 1024**3,
                labels=labels))
        apps = [f"a{j}" for j in range(2)]

        def term(required=True):
            t = {"labelSelector":
                 {"matchLabels": {"app": rng.choice(apps)}},
                 "topologyKey": rng.choice(
                     ["zone", "rack", "kubernetes.io/hostname"])}
            if required:
                return t
            return {"weight": rng.choice([-50, -1, 1, 10, 100]),
                    "podAffinityTerm": t}

        def affinity():
            aff = {}
            r = rng.random()
            if r < 0.3:
                aff["podAffinity"] = {
                    "requiredDuringSchedulingIgnoredDuringExecution":
                        [term() for _ in range(rng.randint(1, 2))]}
            elif r < 0.55:
                aff["podAntiAffinity"] = {
                    "requiredDuringSchedulingIgnoredDuringExecution":
                        [term() for _ in range(rng.randint(1, 2))]}
            elif r < 0.8:
                key = rng.choice(["podAffinity", "podAntiAffinity"])
                aff[key] = {
                    "preferredDuringSchedulingIgnoredDuringExecution":
                        [term(False) for _ in range(rng.randint(1, 2))]}
            return aff or None

        existing = []
        for i in range(rng.randint(0, 4)):
            kw = {"labels": {"app": rng.choice(apps)}}
            a = affinity()
            if a:
                kw["affinity"] = a
            existing.append(make_pod(
                f"e{i}", node_name=f"n{i % n_nodes}", phase="Running",
                milli_cpu=100, **kw))
        pods = []
        for i in range(rng.randint(10, 16)):
            kw = {"labels": {"app": rng.choice(apps)}}
            a = affinity()
            if a:
                kw["affinity"] = a
            pods.append(make_pod(
                f"p{i}", milli_cpu=rng.randrange(1, 8) * 100,
                memory=rng.randrange(1, 8) * 2**26, **kw))
        snap = ClusterSnapshot(nodes=nodes, pods=existing)
        compiled, cols = compile_cluster(snap, pods)
        assert not compiled.unsupported, compiled.unsupported
        config = config_for(
            [compiled], most_requested=bool(rng.getrandbits(1)),
            num_reason_bits=NUM_FIXED_BITS + len(compiled.scalar_names))
        assert config.has_interpod
        plan, reason = plan_fast(config, compiled, cols)
        if plan is None:
            skipped += 1
            continue
        _, choices, counts, advanced = schedule_scan(
            config, carry_init(compiled), statics_to_device(compiled),
            pod_columns_to_device(cols))
        f_choices, f_counts, f_adv = fast_scan(plan, chunk=16)
        assert np.array_equal(f_choices, np.asarray(choices)), f"seed {seed}"
        assert np.array_equal(f_counts, np.asarray(counts)), f"seed {seed}"
        assert np.array_equal(f_adv, np.asarray(advanced)), f"seed {seed}"
    assert skipped <= max(1, min(seeds, 25) // 2), \
        f"{skipped} of {min(seeds, 25)} seeds fell back"


def test_maxpd_exhaustion_parity(monkeypatch):
    """Max{EBS,GCE}VolumeCount on the fast path: per-node unique-volume
    unions ride the [Vpad, Npad] bit carry; limits exhaust (forced low via
    KUBE_MAX_PD_VOLS so BIT_MAX_VOLUME_COUNT actually fires) and
    placements + reason histograms stay bit-identical to the XLA scan
    (round 5)."""
    import random

    from tpusim.jaxe.state import BIT_MAX_VOLUME_COUNT

    monkeypatch.setenv("KUBE_MAX_PD_VOLS", "3")
    rng = random.Random(5)
    nodes = [make_node(f"n{i}", milli_cpu=64000, memory=256 * 1024**3,
                       pods=200) for i in range(6)]
    existing = [make_pod(
        f"e{i}", node_name=f"n{i % 6}", phase="Running", milli_cpu=100,
        volumes=[make_pod_volume(
            "v", {"awsElasticBlockStore": {"volumeID": f"ebs{i % 5}"}})])
        for i in range(8)]
    pods = []
    for i in range(120):
        vols = []
        r = rng.random()
        if r < 0.5:
            vols.append(make_pod_volume(
                "v", {"awsElasticBlockStore":
                      {"volumeID": f"ebs{rng.randrange(8)}"}}))
        elif r < 0.7:
            vols.append(make_pod_volume(
                "v", {"gcePersistentDisk": {"pdName":
                                            f"gce{rng.randrange(4)}"}}))
        pods.append(make_pod(f"p{i}", milli_cpu=100, memory=64 * 1024**2,
                             volumes=vols or None))
    snap = ClusterSnapshot(nodes=nodes, pods=existing)
    compiled, cols = compile_cluster(snap, pods)
    assert not compiled.unsupported
    config = config_for([compiled], most_requested=False,
                        num_reason_bits=NUM_FIXED_BITS
                        + len(compiled.scalar_names))
    assert config.has_maxpd
    plan, why = plan_fast(config, compiled, cols)
    assert plan is not None, why
    f_choices, f_counts, _ = fast_scan(plan, chunk=32)
    _, choices, counts, _ = schedule_scan(
        config, carry_init(compiled), statics_to_device(compiled),
        pod_columns_to_device(cols))
    assert 0 < int((np.asarray(choices) >= 0).sum()) < len(pods)
    # the exhaustion branch must actually fire, not just NoDiskConflict
    assert int(np.asarray(counts)[:, BIT_MAX_VOLUME_COUNT].sum()) > 0
    assert np.array_equal(f_choices, np.asarray(choices))
    w = f_counts.shape[1]
    assert np.array_equal(f_counts, np.asarray(counts)[:, :w])


def test_fuzz_policy_fast_path_parity():
    """Randomized statically-gateable policies (predicate subsets incl.
    individually-named GeneralPredicates parts, random priority weights)
    through plan_fast/fast_scan vs the XLA scan, bit-for-bit (round 5)."""
    import os
    import random
    from dataclasses import replace as dc_replace

    from tpusim.engine.policy import decode_policy
    from tpusim.jaxe.policyc import compile_policy

    seeds = max(int(os.environ.get("TPUSIM_FUZZ_SEEDS", "3")), 1)
    skipped = 0
    pred_pool = ["GeneralPredicates", "PodFitsResources", "HostName",
                 "MatchNodeSelector", "PodToleratesNodeTaints",
                 "CheckNodeMemoryPressure", "CheckNodeDiskPressure",
                 "NoDiskConflict", "MaxEBSVolumeCount"]
    prio_pool = ["LeastRequestedPriority", "MostRequestedPriority",
                 "BalancedResourceAllocation", "NodeAffinityPriority",
                 "TaintTolerationPriority", "NodePreferAvoidPodsPriority"]
    for seed in range(min(seeds, 25)):
        rng = random.Random(8200 + seed)
        preds = rng.sample(pred_pool, rng.randint(1, 5))
        prios = [{"name": n, "weight": rng.randint(1, 5)}
                 for n in rng.sample(prio_pool, rng.randint(1, 4))]
        policy = decode_policy({
            "kind": "Policy", "apiVersion": "v1",
            "predicates": [{"name": n} for n in preds],
            "priorities": prios,
        })
        cp = compile_policy(policy)
        assert not cp.unsupported
        nodes = [make_node(
            f"n{i}", milli_cpu=rng.choice([1000, 2000, 4000]),
            memory=rng.choice([2, 4, 8]) * 1024**3,
            labels={"zone": f"z{i % 3}"},
            taints=([{"key": "d", "value": "b", "effect": "NoSchedule"}]
                    if i % 3 == 0 else None))
            for i in range(rng.randint(4, 10))]
        pods = []
        for i in range(rng.randint(15, 30)):
            kw = {}
            if rng.random() < 0.3:
                kw["tolerations"] = [{"key": "d", "operator": "Equal",
                                      "value": "b",
                                      "effect": "NoSchedule"}]
            if rng.random() < 0.2:
                kw["node_selector"] = {"zone": f"z{rng.randrange(3)}"}
            pods.append(make_pod(
                f"p{i}", milli_cpu=rng.randrange(1, 12) * 100,
                memory=rng.randrange(1, 12) * 2**26, **kw))
        snap = ClusterSnapshot(nodes=nodes)
        compiled, cols = compile_cluster(snap, pods)
        assert not compiled.unsupported
        config = config_for(
            [compiled], most_requested=False,
            num_reason_bits=NUM_FIXED_BITS + len(compiled.scalar_names))
        config = dc_replace(config, policy=cp.spec)
        plan, reason = plan_fast(config, compiled, cols)
        if plan is None:
            skipped += 1
            continue
        _, choices, counts, advanced = schedule_scan(
            config, carry_init(compiled), statics_to_device(compiled),
            pod_columns_to_device(cols))
        f_choices, f_counts, f_adv = fast_scan(plan, chunk=16)
        assert np.array_equal(f_choices, np.asarray(choices)), \
            f"seed {seed} preds={preds} prios={prios}"
        assert np.array_equal(
            f_counts, np.asarray(counts)[:, :f_counts.shape[1]]), \
            f"seed {seed} preds={preds}"
        assert np.array_equal(f_adv, np.asarray(advanced)), f"seed {seed}"
    assert skipped <= max(1, min(seeds, 25) // 3), \
        f"{skipped} of {min(seeds, 25)} seeds fell back"


def test_every_group_feature_combined_parity():
    """The strongest single operand-ordering test: ports + services/spread
    + disk conflicts + volume zones + MaxPD + inter-pod anti-affinity ALL
    active in ONE kernel variant, bit-identical to the XLA scan."""
    import random

    from tpusim.api.snapshot import make_pv, make_pvc

    rng = random.Random(99)
    nodes = [make_node(
        f"n{i}", milli_cpu=16000, memory=64 * 1024**3, pods=60,
        labels={LABEL_ZONE_FAILURE_DOMAIN: f"z{i % 3}",
                "rack": f"r{i % 2}"}) for i in range(8)]
    svc = [_service("web", {"app": "a0"})]
    pvs = [make_pv("pv-z", labels={LABEL_ZONE_FAILURE_DOMAIN: "z1"})]
    pvcs = [make_pvc("claim-z", volume_name="pv-z")]
    existing = [make_pod(f"e{i}", node_name=f"n{i % 8}", phase="Running",
                         milli_cpu=100, labels={"app": f"a{i % 2}"})
                for i in range(6)]
    pods = []
    for i in range(40):
        kw = {"labels": {"app": f"a{rng.randrange(2)}"}}
        r = rng.random()
        if r < 0.2:
            kw["affinity"] = {"podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    {"labelSelector":
                     {"matchLabels": {"app": kw["labels"]["app"]}},
                     "topologyKey": "rack"}]}}
        elif r < 0.35:
            kw["volumes"] = [make_pod_volume("v", pvc="claim-z")]
        elif r < 0.5:
            kw["volumes"] = [make_pod_volume(
                "b", {"awsElasticBlockStore":
                      {"volumeID": f"ebs{rng.randrange(3)}"}})]
        p = make_pod(f"p{i}", milli_cpu=rng.randrange(1, 8) * 100,
                     memory=rng.randrange(1, 8) * 2**26, **kw)
        if rng.random() < 0.3:
            p.spec.containers[0].ports = [ContainerPort.from_obj(
                {"containerPort": 80,
                 "hostPort": rng.choice([8080, 9090])})]
        pods.append(p)
    snap = ClusterSnapshot(nodes=nodes, pods=existing, services=svc,
                           pvs=pvs, pvcs=pvcs)
    compiled, cols = compile_cluster(snap, pods)
    assert not compiled.unsupported
    config = config_for([compiled], most_requested=False,
                        num_reason_bits=NUM_FIXED_BITS
                        + len(compiled.scalar_names))
    for flag in ("has_ports", "has_services", "has_disk_conflict",
                 "has_vol_zone", "has_maxpd", "has_interpod"):
        assert getattr(config, flag), flag
    plan, why = plan_fast(config, compiled, cols)
    assert plan is not None, why
    f_choices, f_counts, f_adv = fast_scan(plan, chunk=16)
    _, choices, counts, advanced = schedule_scan(
        config, carry_init(compiled), statics_to_device(compiled),
        pod_columns_to_device(cols))
    assert 0 < int((np.asarray(choices) >= 0).sum()) < len(pods)
    assert np.array_equal(f_choices, np.asarray(choices))
    assert np.array_equal(f_counts,
                          np.asarray(counts)[:, :f_counts.shape[1]])
    assert np.array_equal(f_adv, np.asarray(advanced))


def _launch_groups_interpod():
    """Host ports, spreading and inter-pod anti-affinity on a node-wise
    (hostname) key and on a zone key: group rows, presence and the
    presence_dom carry all stream through the chunk program."""
    nodes = [make_node(f"n{i}", milli_cpu=4000, memory=8 * 1024**3,
                       labels={LABEL_ZONE_FAILURE_DOMAIN: f"z{i % 3}"})
             for i in range(10)]
    existing = [make_pod("e0", node_name="n0", phase="Running",
                         milli_cpu=100, labels={"app": "a"})]
    pods = []
    for i in range(40):
        app = "ab"[i % 2]
        kw = {"labels": {"app": app}}
        if i % 3 == 0:
            kw["affinity"] = {"podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [{
                    "labelSelector": {"matchLabels": {"app": app}},
                    "topologyKey": ("kubernetes.io/hostname" if i % 2
                                    else LABEL_ZONE_FAILURE_DOMAIN)}]}}
        p = make_pod(f"p{i}", milli_cpu=100 * (1 + i % 4),
                     memory=2**27, **kw)
        if i % 5 == 0:
            p.spec.containers[0].ports = [ContainerPort.from_obj(
                {"containerPort": 80, "hostPort": 8080})]
        pods.append(p)
    return ClusterSnapshot(nodes=nodes, pods=existing,
                           services=[_service("web", {"app": "a"})]), pods


def _launch_scalars():
    nodes = [make_node(f"n{i}", milli_cpu=4000, memory=8 * 1024**3,
                       scalars={"example.com/widget": 3 - i % 3})
             for i in range(9)]
    pods = [make_pod(f"p{i}", milli_cpu=300, memory=2**27,
                     scalars={"example.com/widget": 1 + i % 2} if i % 3
                     else None)
            for i in range(45)]
    return ClusterSnapshot(nodes=nodes), pods


def _host_carry(fc):
    """The same carry as numpy arrays, so that the next call uploads it."""
    from tpusim.jaxe.fastscan import FastCarry

    def host(a):
        return None if a is None else np.asarray(a)

    return FastCarry(rows=[host(a) for a in fc.rows], misc=host(fc.misc),
                     scal=host(fc.scal), pres=host(fc.pres),
                     pd=host(fc.pd), uv=host(fc.uv))


def _in_two(plan, cut, chunk, host=False, **kw):
    """Pods [0, cut) then [cut, P), the second resuming from the first's
    carry: (choices, counts, advanced) over all P, and the final carry."""
    a = fast_scan(plan, chunk=chunk, stop=cut, return_carry=True, **kw)
    b = fast_scan(plan, chunk=chunk, start=cut, return_carry=True,
                  carry_in=_host_carry(a[3]) if host else a[3], **kw)
    return tuple(np.concatenate(pair) for pair in zip(a[:3], b[:3])), b[3]


# (workload, how the span is launched); every case covers the whole batch
LAUNCH_CASES = {
    # 180 pods in chunks of 64: the last chunk is 52 real rows and ghosts
    "ragged_tail": (lambda: build(0),
                    lambda plan: fast_scan(plan, chunk=64)),
    # the second call starts at pod 37 from a carry uploaded from the host
    "unaligned_start": (lambda: build(0),
                        lambda plan: _in_two(plan, 37, 64, host=True)[0]),
    "chained_carry": (lambda: build(0), None),
    # a 10-pod span still runs one 64-row chunk, then the rest
    "fixed_chunk_short": (lambda: build(0),
                          lambda plan: _in_two(plan, 10, 64,
                                               fixed_chunk=True)[0]),
    "groups_interpod": (_launch_groups_interpod,
                        lambda plan: _in_two(plan, 21, 16)[0]),
    "scalars": (_launch_scalars, lambda plan: _in_two(plan, 21, 16)[0]),
}


@pytest.mark.parametrize("case", sorted(LAUNCH_CASES))
def test_chunk_launch_matches_xla_scan(case):
    """Each chunk is one upload of its packed pods and one compiled call
    that gathers the signature rows on the device; placements, reason
    counts and the rr advance match the XLA scan bit for bit however the
    span is cut into calls and chunks."""
    workload, launch = LAUNCH_CASES[case]
    snapshot, pods = workload()
    compiled, cols = compile_cluster(snapshot, pods)
    assert not compiled.unsupported
    config = config_for(
        [compiled], most_requested=False,
        num_reason_bits=NUM_FIXED_BITS + len(compiled.scalar_names))
    plan, reason = plan_fast(config, compiled, cols)
    assert plan is not None, reason
    if case == "groups_interpod":
        assert plan.num_groups and plan.has_interpod and plan.has_ports
        assert plan.presence_dom is not None and any(plan.topo_nodewise)
    if case == "scalars":
        assert plan.num_scalars == 1
    _, choices, counts, advanced = schedule_scan(
        config, carry_init(compiled), statics_to_device(compiled),
        pod_columns_to_device(cols))
    if launch is None:
        # two chained calls against one call over the whole span: the same
        # answers and the same carry out
        got, carry = _in_two(plan, 128, 64)
        whole = fast_scan(plan, chunk=64, return_carry=True)
        assert all(np.array_equal(x, y) for x, y in zip(got, whole[:3]))
        for x, y in zip(carry.rows + [carry.misc],
                        whole[3].rows + [whole[3].misc]):
            assert np.array_equal(np.asarray(x), np.asarray(y))
    else:
        got = launch(plan)
    assert np.array_equal(got[0], np.asarray(choices))
    assert np.array_equal(got[1], np.asarray(counts))
    assert np.array_equal(got[2], np.asarray(advanced))
    assert 0 < int((got[0] >= 0).sum()) < len(pods)


def test_signature_count_reuses_the_kernel_program(monkeypatch):
    """The signature tables reach only the gather program: two batches on
    the same nodes whose selector tables differ in row count (1 and 16)
    run one compiled kernel program, and both match the XLA scan."""
    from tpusim.jaxe import fastscan

    nodes = [make_node(f"n{i}", milli_cpu=2000, memory=4 * 1024**3,
                       labels={"zone": f"z{i % 3}"}) for i in range(12)]
    plain = [make_pod(f"a{i}", milli_cpu=300, memory=2**27)
             for i in range(16)]
    pinned = [make_pod(f"b{i}", milli_cpu=300, memory=2**27,
                       node_selector={"zone": f"z{i}"} if i else None)
              for i in range(16)]
    fastscan._build_call.cache_clear()
    programs = []
    build = fastscan._build_call

    def recorded(*args, **kwargs):
        programs.append(build(*args, **kwargs))
        return programs[-1]

    monkeypatch.setattr(fastscan, "_build_call", recorded)
    rows = []
    for pods in (plain, pinned):
        compiled, cols = compile_cluster(ClusterSnapshot(nodes=nodes), pods)
        config = config_for([compiled], most_requested=False,
                            num_reason_bits=NUM_FIXED_BITS)
        plan, reason = plan_fast(config, compiled, cols)
        assert plan is not None, reason
        rows.append(plan.selector_ok.shape[0])
        got = fast_scan(plan, chunk=16)
        _, choices, counts, advanced = schedule_scan(
            config, carry_init(compiled), statics_to_device(compiled),
            pod_columns_to_device(cols))
        assert np.array_equal(got[0], np.asarray(choices))
        assert np.array_equal(got[1], np.asarray(counts))
        assert np.array_equal(got[2], np.asarray(advanced))
    assert rows[0] < rows[1]
    assert programs[0] is programs[1]
    assert programs[0]._cache_size() == 1

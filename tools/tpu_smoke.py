#!/usr/bin/env python
"""Stage-0 all-variants TPU smoke: one tiny-shape batch per kernel-variant
class, each checked for placement-hash parity against the XLA scan in the
same process.

One chip call of this script certifies that every Pallas
kernel-variant class — base scan, MostRequested scoring, host-ports,
disk-conflict, selector-spreading, volume-zone, inter-pod affinity,
max-PD volume counts, and the policy-residue classes (label-presence
rows + NodeLabel preference, ServiceAffinity first-pod locks,
ImageLocality, NoExecute-taint predicate, alwaysCheckAllPredicates
count-mode) — actually lowers through Mosaic and agrees with
the XLA scan bit-for-bit, plus that the preemption victim-selection
kernel (jaxe/preempt.py) byte-matches the host oracle and that the
streaming runtime's scatter-committed fast path (tpusim/stream)
byte-matches a fresh-compile reference without retracing once warm —
plus that a fully traced replicated fleet (leader -> follower WAL
shipping + a serve batch) exports one lint-clean Perfetto flow graph
without moving a single placement. Shapes are tiny
(<=8 nodes, <=24 pods) so the whole sweep compiles and runs in well
under a minute on a healthy TPU; off-TPU the Pallas kernels auto-select
interpreter mode, so the same script validates on CPU (slower).

Each variant prints one line:

    SMOKE <variant>: OK hash=<sha256[:16]> scheduled=<n>/<total> (<s>s)

and the script ends with `SMOKE COMPLETE: <n> variants, platform=<p>`
(exit 0) or `SMOKE FAILED: ...` (exit 1). TPUSIM_SMOKE_VARIANTS=a,b
restricts the sweep (debugging a single variant class).
"""

import hashlib
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# The sharded variant needs a >= 2 device mesh; a CPU host exposes one
# device unless told otherwise, and the flag only takes effect before
# jax initializes. Real accelerator hosts enumerate hardware devices and
# ignore it. Mirrors tests/conftest.py (which forces 8 for the suite).
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=2")

import numpy as np  # noqa: E402

from tpusim.jaxe import ensure_x64  # noqa: E402

ensure_x64()

from tpusim.api.snapshot import (  # noqa: E402
    ClusterSnapshot,
    make_node,
    make_pod,
    make_pod_volume,
    make_pv,
    make_pvc,
)
from tpusim.api.types import (  # noqa: E402
    LABEL_ZONE_FAILURE_DOMAIN,
    ContainerImage,
    ContainerPort,
    Service,
)
from tpusim.jaxe.fastscan import fast_scan, plan_fast  # noqa: E402
from tpusim.jaxe.kernels import (  # noqa: E402
    carry_init,
    config_for,
    pod_columns_to_device,
    schedule_scan,
    statics_to_device,
)
from tpusim.jaxe.state import NUM_FIXED_BITS, compile_cluster  # noqa: E402


def _service(name, selector):
    return Service.from_obj({"metadata": {"name": name,
                                          "namespace": "default"},
                             "spec": {"selector": selector}})


def _port_pod(name, port, **kw):
    p = make_pod(name, milli_cpu=100, **kw)
    p.spec.containers[0].ports = [ContainerPort.from_obj(
        {"containerPort": port, "hostPort": port})]
    return p


# --- one tiny workload per kernel-variant class -------------------------


def _base():
    """Group-free scan: taints, selectors, pins, preferred node affinity."""
    nodes = [make_node(f"n{i}", milli_cpu=(500, 1000, 2000)[i % 3],
                       memory=(1 + i % 3) * 1024**3, pods=(4, 8, 110)[i % 3],
                       labels={"zone": f"z{i % 3}"},
                       taints=[{"key": "dedicated", "value": "batch",
                                "effect": "NoSchedule"}] if i % 3 == 0
                       else None,
                       unschedulable=(i == 5)) for i in range(8)]
    seeded = [make_pod(f"r{i}", milli_cpu=300, memory=2**28,
                       node_name=f"n{i}", phase="Running") for i in range(4)]
    pods = []
    for i in range(24):
        kw = {}
        if i % 5 == 0:
            kw["tolerations"] = [{"key": "dedicated", "operator": "Equal",
                                  "value": "batch", "effect": "NoSchedule"}]
        if i % 4 == 0:
            kw["node_selector"] = {"zone": f"z{i % 4}"}  # z3 never matches
        if i % 9 == 0:
            kw["node_name"] = f"n{i % 10}"  # pins, one dangling
        pods.append(make_pod(f"p{i}", milli_cpu=(1 + i % 6) * 200,
                             memory=(1 + i % 4) * 2**27, **kw))
    return ClusterSnapshot(nodes=nodes, pods=seeded), pods


def _ports():
    nodes = [make_node(f"n{i}") for i in range(3)]
    seeded = _port_pod("seed", 8080, node_name="n0", phase="Running")
    pods = [_port_pod(f"p{i}", 8080) for i in range(5)] \
        + [_port_pod("other", 9090)]
    return ClusterSnapshot(nodes=nodes, pods=[seeded]), pods


def _disk():
    nodes = [make_node(f"n{i}") for i in range(2)]
    vol = [make_pod_volume("v", {"rbd": {"monitors": ["a"], "pool": "p",
                                         "image": "img"}})]
    pods = [make_pod(f"p{i}", milli_cpu=100, volumes=vol) for i in range(4)]
    return ClusterSnapshot(nodes=nodes), pods


def _spread():
    nodes = [make_node(f"n{i}", labels={
        LABEL_ZONE_FAILURE_DOMAIN: f"z{i % 2}"}) for i in range(4)]
    existing = [make_pod(f"e{i}", node_name=f"n{i % 2}", phase="Running",
                         labels={"app": "api"}) for i in range(3)]
    snap = ClusterSnapshot(nodes=nodes, pods=existing,
                           services=[_service("api", {"app": "api"})])
    return snap, [make_pod(f"p{i}", milli_cpu=10, labels={"app": "api"})
                  for i in range(8)]


def _vol_zone():
    nodes = [make_node(f"n{i}", labels={
        LABEL_ZONE_FAILURE_DOMAIN: f"z{i % 2}"}) for i in range(4)]
    pvs = [make_pv("pv-a", labels={LABEL_ZONE_FAILURE_DOMAIN: "z0"})]
    pvcs = [make_pvc("claim-a", volume_name="pv-a")]
    pods = [make_pod(f"p{i}", milli_cpu=10,
                     volumes=[make_pod_volume("v", pvc="claim-a")])
            for i in range(3)]
    return ClusterSnapshot(nodes=nodes, pvs=pvs, pvcs=pvcs), pods


def _interpod():
    nodes = [make_node(f"n{i}", milli_cpu=4000, memory=8 * 1024**3,
                       labels={"zone": f"z{i % 2}", "rack": f"r{i % 3}"})
             for i in range(6)]
    existing = [make_pod(f"e{i}", node_name=f"n{i}", phase="Running",
                         milli_cpu=100, labels={"app": ("a0", "a1")[i % 2]})
                for i in range(3)]
    pods = []
    for i in range(12):
        aff = None
        if i % 3 == 0:
            aff = {"podAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    {"labelSelector": {"matchLabels": {"app": "a0"}},
                     "topologyKey": "zone"}]}}
        elif i % 3 == 1:
            aff = {"podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    {"labelSelector": {"matchLabels": {"app": "a1"}},
                     "topologyKey": "rack"}]}}
        pods.append(make_pod(f"p{i}", milli_cpu=200, memory=2**27,
                             labels={"app": ("a0", "a1")[i % 2]},
                             affinity=aff))
    return ClusterSnapshot(nodes=nodes, pods=existing), pods


def _maxpd():
    # KUBE_MAX_PD_VOLS forced low so the volume-count limit actually fires
    os.environ["KUBE_MAX_PD_VOLS"] = "2"
    nodes = [make_node(f"n{i}", milli_cpu=64000, memory=64 * 1024**3,
                       pods=100) for i in range(3)]
    existing = [make_pod(
        f"e{i}", node_name=f"n{i % 3}", phase="Running", milli_cpu=100,
        volumes=[make_pod_volume(
            "v", {"awsElasticBlockStore": {"volumeID": f"ebs{i}"}})])
        for i in range(3)]
    pods = [make_pod(
        f"p{i}", milli_cpu=100, memory=2**26,
        volumes=[make_pod_volume(
            "v", {"awsElasticBlockStore": {"volumeID": f"ebs{i % 5}"}})])
        for i in range(10)]
    return ClusterSnapshot(nodes=nodes, pods=existing), pods


# --- policy-residue variant classes (ISSUE 4): one tiny workload per
# residue family the fused scan absorbed; builders return a third element
# (the policy-as-data dict) and run_pallas_variant compiles it like the
# backend does -------------------------------------------------------------


def _pol(preds, prios, **extra):
    d = {"kind": "Policy", "apiVersion": "v1",
         "predicates": preds, "priorities": prios}
    d.update(extra)
    return d


def _residue_nodes(n=6):
    nodes = []
    for i in range(n):
        labels = {"region": f"r{i % 2}", "zone": f"z{i % 3}"}
        if i % 3 != 2:
            labels["foo"] = "x"
        if i % 2 == 0:
            labels["bar"] = "y"
        node = make_node(f"n{i}", milli_cpu=(800, 1600, 3200)[i % 3],
                         memory=(2 + i % 3) * 2**30, labels=labels)
        if i % 2 == 1:
            node.status.images = [ContainerImage(
                names=[f"img-{i % 3}:v1"], size_bytes=400 * 1024**2)]
        nodes.append(node)
    return nodes


def _pol_labels():
    """Label-presence predicate rows + NodeLabel preference."""
    pods = [make_pod(f"p{i}", milli_cpu=(1 + i % 4) * 150, memory=2**27)
            for i in range(10)]
    return ClusterSnapshot(nodes=_residue_nodes()), pods, _pol(
        [{"name": "PodFitsResources"},
         {"name": "TestLabelsPresence",
          "argument": {"labelsPresence": {"labels": ["foo"],
                                          "presence": True}}}],
        [{"name": "LeastRequestedPriority", "weight": 1},
         {"name": "TestLabelPreference", "weight": 2,
          "argument": {"labelPreference": {"label": "bar",
                                           "presence": True}}}])


def _pol_service_affinity():
    """ServiceAffinity region locks: one service pre-bound by a running
    pod, one binding its first-pod lock inside the scan."""
    nodes = _residue_nodes()
    placed = [make_pod("seed", milli_cpu=100, memory=2**26, node_name="n0",
                       phase="Running", labels={"app": "api"})]
    snap = ClusterSnapshot(nodes=nodes, pods=placed,
                           services=[_service("api", {"app": "api"}),
                                     _service("web", {"app": "web"})])
    pods = [make_pod(f"p{i}", milli_cpu=150, memory=2**26,
                     labels={"app": ("api", "web")[i % 2]})
            for i in range(8)]
    return snap, pods, _pol(
        [{"name": "PodFitsResources"},
         {"name": "TestServiceAffinity",
          "argument": {"serviceAffinity": {"labels": ["region"]}}}],
        [{"name": "LeastRequestedPriority", "weight": 1}])


def _pol_image():
    """ImageLocality via the signature-table streaming path."""
    pods = []
    for i in range(9):
        p = make_pod(f"p{i}", milli_cpu=150, memory=2**26)
        if i % 2 == 0:
            p.spec.containers[0].image = f"img-{i % 3}:v1"
        pods.append(p)
    return ClusterSnapshot(nodes=_residue_nodes()), pods, _pol(
        [{"name": "PodFitsResources"}],
        [{"name": "ImageLocalityPriority", "weight": 2},
         {"name": "LeastRequestedPriority", "weight": 1}])


def _pol_noexec():
    """NoExecute-only taint predicate (policy-registered variant)."""
    nodes = [make_node(f"n{i}", milli_cpu=(800, 1600, 3200)[i % 3],
                       memory=(2 + i % 3) * 2**30,
                       labels={"zone": f"z{i % 3}"},
                       taints=[{"key": "evict", "value": "now",
                                "effect": "NoExecute"}] if i % 3 == 0
                       else None) for i in range(6)]
    pods = []
    for i in range(8):
        kw = {}
        if i % 2 == 0:
            kw["tolerations"] = [{"key": "evict", "operator": "Equal",
                                  "value": "now", "effect": "NoExecute"}]
        pods.append(make_pod(f"p{i}", milli_cpu=150, memory=2**26, **kw))
    return ClusterSnapshot(nodes=nodes), pods, _pol(
        [{"name": "PodFitsResources"},
         {"name": "PodToleratesNodeNoExecuteTaints"}],
        [{"name": "LeastRequestedPriority", "weight": 1}])


def _pol_count_mode():
    """alwaysCheckAllPredicates: per-stage failure bits stay live past the
    first miss (pods failing resources AND the presence row)."""
    snap, pods, _ = _pol_labels()
    pods = pods + [make_pod(f"big{i}", milli_cpu=50_000, memory=2**27)
                   for i in range(3)]
    return snap, pods, _pol(
        [{"name": "PodFitsResources"},
         {"name": "TestLabelsPresence",
          "argument": {"labelsPresence": {"labels": ["foo"],
                                          "presence": True}}}],
        [{"name": "LeastRequestedPriority", "weight": 1}],
        alwaysCheckAllPredicates=True)


PALLAS_VARIANTS = [
    # (name, workload builder, most_requested)
    ("base", _base, False),
    ("most_requested", _base, True),
    ("ports", _ports, False),
    ("disk", _disk, False),
    ("spread", _spread, False),
    ("vol_zone", _vol_zone, False),
    ("interpod", _interpod, False),
    ("maxpd", _maxpd, False),
    ("pol_labels", _pol_labels, False),
    ("pol_service_affinity", _pol_service_affinity, False),
    ("pol_image", _pol_image, False),
    ("pol_noexec", _pol_noexec, False),
    ("pol_count_mode", _pol_count_mode, False),
]


def run_pallas_variant(name, build, most_requested):
    """Pallas fast path vs the XLA scan, bit-for-bit, on one tiny batch."""
    built = build()
    snapshot, pods = built[:2]
    policy = built[2] if len(built) > 2 else None
    cp = ptabs = None
    if policy is not None:
        from dataclasses import replace as _dc_replace

        from tpusim.engine.policy import decode_policy
        from tpusim.engine.predicates import (
            POD_TOLERATES_NODE_NO_EXECUTE_TAINTS_PRED,
        )
        from tpusim.jaxe.policyc import build_policy_tables, compile_policy

        cp = compile_policy(decode_policy(policy))
        assert not cp.unsupported, (name, cp.unsupported)
        need_noexec = (cp.spec.pred_keys is not None
                       and POD_TOLERATES_NODE_NO_EXECUTE_TAINTS_PRED
                       in cp.spec.pred_keys)
        need_saa = bool(cp.spec.saa_weights) or cp.spec.sa_enabled
        compiled, cols = compile_cluster(snapshot, pods,
                                         need_noexec=need_noexec,
                                         need_saa=need_saa)
    else:
        compiled, cols = compile_cluster(snapshot, pods)
    assert not compiled.unsupported, (name, compiled.unsupported)
    config = config_for(
        [compiled], most_requested=most_requested,
        num_reason_bits=NUM_FIXED_BITS + len(compiled.scalar_names))
    if cp is not None:
        config = _dc_replace(config, policy=cp.spec)
        ptabs = build_policy_tables(cp, snapshot, pods, compiled, cols)
        if cp.saa_entries:
            config = _dc_replace(config, n_saa_doms=ptabs.n_saa_doms)
    plan, reason = plan_fast(config, compiled, cols, ptabs=ptabs)
    if plan is None:
        raise AssertionError(f"variant {name} ineligible for the fast "
                             f"path: {reason}")
    if cp is not None:
        from tpusim.jaxe.kernels import _tree_to_device, statics_to_host

        statics = _tree_to_device(statics_to_host(compiled)._replace(
            label_ok=ptabs.label_ok, label_prio=ptabs.label_prio,
            image_score=ptabs.image_score, saa_dom=ptabs.saa_dom,
            sa_pin=ptabs.sa_pin, sa_val=ptabs.sa_val))
        carry = carry_init(compiled)._replace(sa_lock=ptabs.sa_lock_init)
    else:
        statics = statics_to_device(compiled)
        carry = carry_init(compiled)
    _, choices, counts, advanced = schedule_scan(
        config, carry, statics, pod_columns_to_device(cols))
    f_choices, f_counts, f_adv = fast_scan(plan, chunk=16)
    choices, counts = np.asarray(choices), np.asarray(counts)
    if not np.array_equal(f_choices, choices):
        raise AssertionError(f"variant {name}: choices diverge from the "
                             f"XLA scan")
    w = f_counts.shape[1]
    if not np.array_equal(f_counts, counts[:, :w]):
        raise AssertionError(f"variant {name}: reason histograms diverge")
    if not np.array_equal(f_adv, np.asarray(advanced)):
        raise AssertionError(f"variant {name}: rr advancement diverges")
    h = hashlib.sha256(
        choices.tobytes() + counts.tobytes()).hexdigest()[:16]
    return h, int((choices >= 0).sum()), len(pods)


def _preempt_workload():
    """Arithmetic-reprieve class: packed low-priority residents, banded
    incoming pods — only PodFitsResources can flip, so the device
    victim-selection kernel handles every preemption."""
    nodes = [make_node(f"n{i}", milli_cpu=2000, memory=4 * 1024**3)
             for i in range(4)]
    residents = []
    for i in range(4):
        p = make_pod(f"fill{i}", milli_cpu=1800, memory=2**28,
                     node_name=f"n{i}", phase="Running")
        p.spec.priority = 0
        residents.append(p)
    pods = []
    for i in range(8):
        p = make_pod(f"p{i}", milli_cpu=600, memory=2**26)
        p.spec.priority = (0, 500, 1000)[i % 3]
        pods.append(p)
    return ClusterSnapshot(nodes=nodes, pods=residents), pods


def run_preempt_variant():
    """Device victim-selection kernel vs the host oracle on a tiny banded
    batch: same placements, same victims, and the device arm actually
    fired (an all-host run must not certify the kernel)."""
    from tpusim.jaxe.preempt import (
        PREEMPT_CLASS_STATS,
        reset_preempt_class_stats,
        run_with_preemption,
    )

    def sig(status):
        return ([(p.name, p.spec.node_name)
                 for p in status.successful_pods],
                sorted(p.name for p in status.preempted_pods),
                [p.name for p in status.failed_pods])

    snapshot, pods = _preempt_workload()
    reset_preempt_class_stats()
    os.environ.pop("TPUSIM_PREEMPT_DEVICE", None)  # AUTO: verify-then-trust
    dev = run_with_preemption([p.copy() for p in pods], snapshot)
    paths = dict(PREEMPT_CLASS_STATS)
    if not dev.preempted_pods:
        raise AssertionError("preempt workload evicted nothing; the "
                             "victim kernel was never exercised")
    if not (paths.get("device") or paths.get("device_verified")):
        raise AssertionError(f"victim selection never took the device "
                             f"arm: {paths}")
    os.environ["TPUSIM_PREEMPT_DEVICE"] = "0"
    try:
        host = run_with_preemption([p.copy() for p in pods], snapshot)
    finally:
        os.environ.pop("TPUSIM_PREEMPT_DEVICE", None)
    if sig(dev) != sig(host):
        raise AssertionError("device victim selection diverges from the "
                             "host oracle")
    h = hashlib.sha256(repr(sig(dev)).encode()).hexdigest()[:16]
    return h, len(dev.preempted_pods), paths


def run_chaos_breaker_variant():
    """Dispatch circuit breaker under scripted device faults: two injected
    exceptions trip it OPEN, the cooldown denial flips it HALF_OPEN, and a
    verified probe CLOSES it again — while every emitted batch stays
    byte-identical to the host pipeline (verify="all"). Certifies that a
    flaky accelerator degrades and RECOVERS instead of being benched for
    the life of the process."""
    from tpusim.backends import placement_hash
    from tpusim.chaos import DeviceFaultPlan
    from tpusim.jaxe.backend import JaxBackend, install_chaos, uninstall_chaos

    snapshot, pods = _base()
    backend = JaxBackend()
    expected = placement_hash(backend.schedule(pods, snapshot))
    breaker = install_chaos(DeviceFaultPlan(
        faults={0: "exception", 1: "exception"},
        failure_threshold=2, cooldown=1))
    try:
        for _ in range(4):  # fault, fault->open, denied->half_open, probe
            got = placement_hash(backend.schedule(pods, snapshot))
            if got != expected:
                raise AssertionError(
                    "placements diverged from the clean run under chaos")
    finally:
        uninstall_chaos()
    transitions = [t for t, _ in breaker.transitions]
    if transitions != ["open", "half_open", "close"]:
        raise AssertionError(f"breaker cycle incomplete: {transitions}")
    return expected[:16], transitions


def run_serve_fleet_variant():
    """Scenario fleet (tpusim/serve) on one tiny bucket: serve-path
    placements must hash-match per-scenario run_what_if — including a
    ghost-padded partial bucket — and an exact warm repeat must dispatch
    without tracing a single fresh program."""
    from tpusim.backends import placement_hash
    from tpusim.jaxe.whatif import compile_count, run_what_if
    from tpusim.serve import ScenarioFleet, WhatIfRequest

    base = _base()[0]
    scenarios = [(base, [make_pod(f"f{s}-p{i}", milli_cpu=(1 + i % 4) * 200,
                                  memory=(1 + (s + i) % 3) * 2**27)
                         for i in range(6 + s)])
                 for s in range(3)]
    fleet = ScenarioFleet(bucket_size=2, flush_after_s=60.0)
    load = lambda: [WhatIfRequest(pods=pods, snapshot=snap,  # noqa: E731
                                  cache_key=f"smoke-{i}")
                    for i, (snap, pods) in enumerate(scenarios)]
    # 3 requests / bucket 2: one full bucket + one ghost-padded partial
    responses = fleet.run(load())
    hashes = []
    for resp, (snap, pods) in zip(responses, scenarios):
        if not resp.ok:
            raise AssertionError(f"serve request failed: {resp.error}")
        got = placement_hash(resp.result.placements)
        [single] = run_what_if([(snap, pods)])
        want = placement_hash(single.placements)
        if got != want:
            raise AssertionError(
                f"serve placements diverge from run_what_if "
                f"(ghosts={resp.bucket_ghosts}): {got[:16]} != {want[:16]}")
        hashes.append(got)
    before = compile_count()
    warm = fleet.run(load())
    traced = compile_count() - before
    if traced:
        raise AssertionError(f"warm repeat traced {traced} program(s); "
                             "the warm-executable cache is broken")
    if not all(r.compile_cache_hit for r in warm):
        raise AssertionError("warm responses missing the compile_cache_hit "
                             "stamp")
    h = hashlib.sha256("".join(hashes).encode()).hexdigest()[:16]
    return h, len(responses), dict(fleet.executor.stats)


def run_stream_churn_variant():
    """Streaming runtime (tpusim/stream) under seeded churn: every cycle's
    placements — scatter-committed device-resident fast path and classified
    restages alike — must byte-match a fresh-compile reference arm, and a
    second warm session over the same shapes must dispatch without tracing
    a single fresh scan or scatter program (the pow2-bucket zero-retrace
    contract)."""
    from tpusim.jaxe.kernels import apply_delta_donated, schedule_scan_donated
    from tpusim.simulator import run_stream_simulation

    def cache_sizes():
        try:
            return (schedule_scan_donated._cache_size(),
                    apply_delta_donated._cache_size())
        except AttributeError:  # private jit API moved: skip the check
            return None

    out = run_stream_simulation(num_nodes=16, cycles=10, arrivals=16,
                                evict_fraction=0.25, node_flap_every=4,
                                seed=7, verify=True)
    if not out["verified"]:
        raise AssertionError(
            f"stream placements diverge from the full-restage reference on "
            f"{out['mismatched_cycles']} of {out['cycles']} cycles")
    stream_cycles = out["paths"].get("stream_scan", 0)
    if not stream_cycles:
        raise AssertionError(
            f"churn never took the O(delta) stream path: {out['paths']}")
    if not out["commits"]:
        raise AssertionError("no scatter commits dispatched")
    before = cache_sizes()
    warm = run_stream_simulation(num_nodes=16, cycles=4, arrivals=16,
                                 evict_fraction=0.25, seed=8)
    traced = None
    if before is not None:
        after = cache_sizes()
        traced = (after[0] - before[0], after[1] - before[1])
        if any(traced):
            raise AssertionError(
                f"warm session retraced (scan +{traced[0]}, scatter "
                f"+{traced[1]}); pow2 bucketing is broken")
    if warm["paths"].get("stream_scan", 0) != warm["cycles"] - 1:
        raise AssertionError(
            f"warm session left the stream path: {warm['paths']}")
    h = out["placement_chain"][:16]
    return h, out["scheduled"], out["decisions"], stream_cycles, traced


def run_stream_policy_variant():
    """Stream v2 (compiled-policy residency + pipelined dispatch) stage-0:
    a policy-built streaming session under node label/taint churn must (a)
    byte-match the fresh-compile reference every cycle while classifying
    only the cold start as a restage — churn lands as the O(delta) statics
    scatter, not a re-stage; (b) emit an identical placement chain from the
    pipelined double-buffered path; (c) leave every donated program cache
    untouched on a warm re-run (scan, delta scatter AND the policy-aware
    statics scatter)."""
    from tpusim.engine.policy import decode_policy
    from tpusim.jaxe.kernels import (
        apply_delta_donated,
        apply_statics_delta_donated,
        schedule_scan_donated,
    )
    from tpusim.simulator import run_stream_simulation

    policy = decode_policy(_pol(
        [{"name": "PodFitsResources"},
         {"name": "MatchNodeSelector"},
         {"name": "PodToleratesNodeTaints"},
         {"name": "TestServiceAffinity",
          "argument": {"serviceAffinity": {"labels": ["region"]}}},
         {"name": "TestLabelsPresence",
          "argument": {"labelsPresence": {"labels": ["foo"],
                                          "presence": True}}}],
        [{"name": "LeastRequestedPriority", "weight": 1},
         {"name": "zone-spread", "weight": 2,
          "argument": {"serviceAntiAffinity": {"label": "zone"}}},
         {"name": "bar-pref", "weight": 1,
          "argument": {"labelPreference": {"label": "bar",
                                           "presence": True}}}]))

    def cache_sizes():
        try:
            return (schedule_scan_donated._cache_size(),
                    apply_delta_donated._cache_size(),
                    apply_statics_delta_donated._cache_size())
        except AttributeError:  # private jit API moved: skip the check
            return None

    def run(**kw):
        return run_stream_simulation(num_nodes=16, cycles=10, arrivals=16,
                                     evict_fraction=0.25, label_churn=2,
                                     taint_churn=1, seed=7, policy=policy,
                                     **kw)

    out = run(verify=True)
    if not out["verified"]:
        raise AssertionError(
            f"policy-stream placements diverge from the full-restage "
            f"reference on {out['mismatched_cycles']} of "
            f"{out['cycles']} cycles")
    if out["restages"] != {"cold_start": 1}:
        raise AssertionError(
            f"label/taint churn restaged beyond the cold start: "
            f"{out['restages']} (paths {out['paths']}) — policy-table "
            f"residency is broken")
    piped = run(pipeline=True)
    if piped["placement_chain"] != out["placement_chain"]:
        raise AssertionError(
            "pipelined placement chain diverges from synchronous "
            f"({piped['placement_chain'][:16]} != "
            f"{out['placement_chain'][:16]})")
    pipelined_cycles = piped["paths"].get("pipelined", 0)
    if not pipelined_cycles:
        raise AssertionError(
            f"pipeline never engaged the async path: {piped['paths']}")
    before = cache_sizes()
    warm = run(pipeline=True)
    traced = None
    if before is not None:
        after = cache_sizes()
        traced = tuple(a - b for a, b in zip(after, before))
        if any(traced):
            raise AssertionError(
                f"warm policy session retraced (scan +{traced[0]}, delta "
                f"+{traced[1]}, statics +{traced[2]}); residency or "
                f"bucketing is broken")
    if warm["placement_chain"] != out["placement_chain"]:
        raise AssertionError("warm re-run chain diverges")
    h = out["placement_chain"][:16]
    return h, out["scheduled"], out["decisions"], pipelined_cycles, traced


def run_stream_recover_variant():
    """Crash recovery (tpusim/stream/persist) stage-0: a WAL-journaled
    streaming session killed mid-run by a scripted process crash must (a)
    recover to a fold chain byte-identical to the uninterrupted run's, (b)
    classify the recovery restage exactly once as "recovered", with zero
    replay invariant violations, and (c) resume WITHOUT retracing a single
    scan or scatter program — the recovered device picture re-enters the
    same pow2-bucketed executables the crashed run compiled."""
    import shutil
    import tempfile

    from tpusim.chaos.engine import ProcessCrash
    from tpusim.chaos.plan import ChurnEvent, FaultPlan
    from tpusim.jaxe.kernels import apply_delta_donated, schedule_scan_donated
    from tpusim.simulator import run_stream_simulation

    def cache_sizes():
        try:
            return (schedule_scan_donated._cache_size(),
                    apply_delta_donated._cache_size())
        except AttributeError:  # private jit API moved: skip the check
            return None

    def run(ckdir, **kw):
        return run_stream_simulation(num_nodes=16, cycles=10, arrivals=16,
                                     evict_fraction=0.25, node_flap_every=4,
                                     seed=7, checkpoint_dir=ckdir,
                                     checkpoint_every=2, **kw)

    base_dir = tempfile.mkdtemp(prefix="tpusim-smoke-ck-")
    ck_dir = tempfile.mkdtemp(prefix="tpusim-smoke-ck-")
    try:
        base = run(base_dir)
        plan = FaultPlan(seed=7, churn=[
            ChurnEvent(at=6, action="process_crash", target="emit")])
        try:
            run(ck_dir, chaos_plan=plan)
            raise AssertionError("scripted process crash never fired")
        except ProcessCrash:
            pass
        before = cache_sizes()
        out = run(ck_dir, recover=True)
        traced = None
        if before is not None:
            after = cache_sizes()
            traced = (after[0] - before[0], after[1] - before[1])
            if any(traced):
                raise AssertionError(
                    f"recovery retraced (scan +{traced[0]}, scatter "
                    f"+{traced[1]}); the restored device picture missed "
                    f"the warm executables")
        if out["fold_chain"] != base["fold_chain"]:
            raise AssertionError(
                f"recovered fold chain diverges from the uninterrupted "
                f"run ({out['fold_chain'][:16]} != "
                f"{base['fold_chain'][:16]})")
        if out["recovery_violations"]:
            raise AssertionError(
                f"WAL replay invariant violations: "
                f"{out['recovery_violations']}")
        if out["restages"].get("recovered") != 1:
            raise AssertionError(
                f"recovery restage misclassified: {out['restages']} "
                f"(want exactly one 'recovered')")
        h = out["fold_chain"][:16]
        return h, out["resume_cycle"], out["wal_records"], traced
    finally:
        shutil.rmtree(base_dir, ignore_errors=True)
        shutil.rmtree(ck_dir, ignore_errors=True)


def run_standby_variant():
    """Hot standby + failover (tpusim/stream/replicate) stage-0: a leader
    shipping its WAL live to an in-process FollowerTwin, killed mid-run by
    a scripted crash, must (a) promote the standby to a fold chain
    byte-identical to the crash-free run's, (b) replay only the unshipped
    tail (the replication lag), not the journal, with zero promotion
    violations, and (c) resume on the promoted twin WITHOUT retracing a
    single scan or scatter program — the follower's replayed device
    picture re-enters the same pow2-bucketed executables the leader (and
    the warm-up baseline) compiled."""
    import shutil
    import tempfile

    from tpusim.chaos.plan import kill_leader_campaign
    from tpusim.jaxe.kernels import apply_delta_donated, schedule_scan_donated
    from tpusim.simulator import run_replicated_stream, run_stream_simulation
    from tpusim.stream import CRASH_POINTS

    def cache_sizes():
        try:
            return (schedule_scan_donated._cache_size(),
                    apply_delta_donated._cache_size())
        except AttributeError:  # private jit API moved: skip the check
            return None

    kw = dict(num_nodes=16, cycles=10, arrivals=16, evict_fraction=0.25,
              node_flap_every=4, seed=7, checkpoint_every=2)
    base_dir = tempfile.mkdtemp(prefix="tpusim-smoke-repl-")
    rep_dir = tempfile.mkdtemp(prefix="tpusim-smoke-repl-")
    try:
        base = run_stream_simulation(checkpoint_dir=base_dir, **kw)
        plan = kill_leader_campaign(seed=7, cycles=kw["cycles"])[
            CRASH_POINTS.index("emit")]
        before = cache_sizes()
        out = run_replicated_stream(checkpoint_dir=rep_dir,
                                    chaos_plan=plan, **kw)
        traced = None
        if before is not None:
            after = cache_sizes()
            traced = (after[0] - before[0], after[1] - before[1])
            if any(traced):
                raise AssertionError(
                    f"promotion retraced (scan +{traced[0]}, scatter "
                    f"+{traced[1]}); the standby's replayed device "
                    f"picture missed the warm executables")
        if not out["crashed"] or not out["promoted"]:
            raise AssertionError(
                f"kill-the-leader never promoted: {out['crash_detail']}")
        if out["fold_chain"] != base["fold_chain"]:
            raise AssertionError(
                f"promoted chain diverges from the crash-free run "
                f"({out['fold_chain'][:16]} != {base['fold_chain'][:16]})")
        if out["promotion_violations"]:
            raise AssertionError(
                f"promotion violations: {out['promotion_violations']}")
        if out["divergence"]:
            raise AssertionError(
                f"follower diverged during replication: "
                f"{out['divergence']}")
        if not out["replayed_records"] < out["wal_records"]:
            raise AssertionError(
                f"promotion replayed the whole journal "
                f"({out['replayed_records']}/{out['wal_records']} "
                f"records) — the warm twin bought nothing")
        h = out["fold_chain"][:16]
        return (h, out["rto_s"] * 1e3, out["replayed_records"],
                out["wal_records"], traced)
    finally:
        shutil.rmtree(base_dir, ignore_errors=True)
        shutil.rmtree(rep_dir, ignore_errors=True)


def run_live_whatif_variant():
    """Live-twin what-if overlay (ISSUE 19) stage-0: an overlay query on a
    churn-warm device-resident twin must (a) answer placement-hash
    identical to the staged run_what_if oracle over the same logical
    state, (b) trace ZERO fresh programs across warm-shape repeats — the
    overlay rides the stream's pow2-bucketed scan + scatter programs —
    and (c) leave the churn run's fold chain byte-unchanged when queries
    interleave with live cycles (the copy-on-write rollback contract)."""
    import numpy as np

    from tpusim.api.snapshot import make_pod, synthetic_cluster
    from tpusim.backends import placement_hash
    from tpusim.jaxe.whatif import compile_count, run_what_if
    from tpusim.simulator import run_stream_simulation
    from tpusim.stream import ChurnLoadGen, StreamSession

    session = StreamSession(synthetic_cluster(16))
    gen = ChurnLoadGen(synthetic_cluster(16), seed=7, arrivals=16,
                       evict_fraction=0.25)
    for c in range(4):
        session.apply_events(gen.events(c))
        gen.note_bound(session.schedule(gen.batch()))
    rng = np.random.RandomState(19)
    qpods = [make_pod(f"smoke-q{i}",
                      milli_cpu=int(rng.randint(100, 1500)),
                      memory=int(rng.randint(2 ** 20, 2 ** 30)))
             for i in range(6)]
    placements = session.overlay_query(qpods)
    if placements is None:
        raise AssertionError("overlay refused on a warm resident twin")
    [oracle] = run_what_if([(session.inc.to_snapshot(), qpods)])
    h = placement_hash(placements)
    if h != placement_hash(oracle.placements):
        raise AssertionError(
            f"overlay hash {h[:16]} != staged run_what_if "
            f"{placement_hash(oracle.placements)[:16]} on the same state")
    traced_before = compile_count()
    for k in (6, 5, 3):   # all land in already-traced pow2 buckets
        if session.overlay_query(qpods[:k]) is None:
            raise AssertionError(f"warm overlay refused at {k} pods")
    retraces = compile_count() - traced_before
    if retraces:
        raise AssertionError(
            f"warm overlay queries traced {retraces} fresh programs; "
            "pow2 bucket reuse is broken")
    kw = dict(num_nodes=16, cycles=8, arrivals=16, evict_fraction=0.25,
              node_flap_every=4, seed=7)
    base = run_stream_simulation(**kw)
    live = run_stream_simulation(**kw, whatif_every=2, whatif_pods=6)
    if live["fold_chain"] != base["fold_chain"]:
        raise AssertionError(
            "interleaved overlay queries changed the churn fold chain: "
            f"{live['fold_chain'][:16]} vs {base['fold_chain'][:16]}")
    ov = live["overlay"]
    if ov["answered"] != ov["queries"]:
        raise AssertionError(f"overlay fell back under churn: {ov}")
    return h[:16], ov["answered"], retraces


def run_analytics_variant():
    """Cluster analytics plane (tpusim/obs/analytics) stage-0: with the
    post-scan reduction riding every dispatch, (a) on-device aggregates
    must equal a host-side numpy recomputation bit-for-bit for every
    captured sample across the jax-backend one-shot, the streaming runtime
    (sync AND pipelined), and the serve fleet; (b) placement hashes /
    chains must be byte-identical to an analytics-off run — the reduction
    is a separate dispatch over the scan's final carry, never a change to
    the scan program; (c) a pure-churn stream session must still classify
    only the cold start as a restage."""
    from tpusim.backends import placement_hash
    from tpusim.jaxe.backend import JaxBackend
    from tpusim.obs import analytics
    from tpusim.serve import ScenarioFleet, WhatIfRequest
    from tpusim.simulator import run_stream_simulation

    def stream(**kw):
        return run_stream_simulation(num_nodes=16, cycles=6, arrivals=16,
                                     evict_fraction=0.25, seed=7, **kw)

    snapshot, pods = _base()
    off_hash = placement_hash(JaxBackend().schedule(
        [p.copy() for p in pods], snapshot))
    off_stream = stream()

    # keep_inputs host-copies each reduction's input columns at capture
    # time (the carry buffers are donated into the next cycle), enabling
    # the device-vs-numpy replay below
    log = analytics.install(analytics.ClusterAnalytics(
        keep_inputs=True, sample_interval_s=0.0))
    try:
        on_hash = placement_hash(JaxBackend().schedule(
            [p.copy() for p in pods], snapshot))
        on_stream = stream()
        piped = stream(pipeline=True)
        serve_pods = [make_pod(f"an-p{i}", milli_cpu=200 * (1 + i % 3),
                               memory=(1 + i % 2) * 2**27)
                      for i in range(5)]
        fleet = ScenarioFleet(bucket_size=2, flush_after_s=60.0)
        [resp] = fleet.run([WhatIfRequest(pods=serve_pods, snapshot=snapshot,
                                          cache_key="analytics-smoke")])
        if not resp.ok:
            raise AssertionError(f"serve request failed: {resp.error}")
        mismatches = log.verify_against_host()
        if mismatches:
            raise AssertionError(
                "device aggregates diverge from the numpy recomputation: "
                + "; ".join(mismatches[:3]))
        sources = {s.source for s in log.samples()}
        if not {"backend", "stream", "serve"} <= sources:
            raise AssertionError(f"missing capture sources: {sorted(sources)}")
        n_samples = len(log.samples())
    finally:
        analytics.uninstall()

    if on_hash != off_hash:
        raise AssertionError(
            f"backend placement hash moved with analytics on "
            f"({on_hash[:16]} != {off_hash[:16]})")
    if on_stream["placement_chain"] != off_stream["placement_chain"]:
        raise AssertionError("stream placement chain moved with analytics on")
    if piped["placement_chain"] != off_stream["placement_chain"]:
        raise AssertionError(
            "pipelined placement chain moved with analytics on")
    if on_stream["restages"] != {"cold_start": 1}:
        raise AssertionError(
            f"analytics run restaged beyond the cold start: "
            f"{on_stream['restages']}")
    return off_hash[:16], n_samples, sorted(sources)


def run_gang_variant():
    """Gang admission (tpusim/gang) stage-0: (a) the host oracle and the
    batched kernel route must produce byte-identical placements for the
    same gang feed (TPUSIM_GANG_KERNEL=0 vs =1); (b) all-or-nothing — an
    oversized gang binds ZERO members and every member carries the SAME
    FitError message; (c) a gang-free feed's placement hash is untouched
    by the group driver's presence (annotation is the only trigger)."""
    from tpusim.backends import Placement, placement_hash
    from tpusim.gang.group import mark_gang
    from tpusim.simulator import run_simulation

    def cluster():
        nodes = [make_node(f"gn{i}", milli_cpu=4000,
                           labels={"topology.kubernetes.io/rack":
                                   f"rack-{i // 2}"})
                 for i in range(6)]
        return ClusterSnapshot(nodes=nodes, pods=[])

    def feed(gang=True):
        pods = [make_pod(f"gs{i}", milli_cpu=200) for i in range(4)]
        if gang:
            pods += [mark_gang(make_pod(f"gg-{j}", milli_cpu=800), "gg")
                     for j in range(4)]
        return pods

    def run_route(kernel_env):
        prev = os.environ.get("TPUSIM_GANG_KERNEL")
        os.environ["TPUSIM_GANG_KERNEL"] = kernel_env
        try:
            from tpusim.jaxe import trust

            trust.reset_all()
            st = run_simulation(feed(), cluster(), backend="jax")
        finally:
            if prev is None:
                os.environ.pop("TPUSIM_GANG_KERNEL", None)
            else:
                os.environ["TPUSIM_GANG_KERNEL"] = prev
        return placement_hash(
            [Placement(pod=p, node_name=p.spec.node_name)
             for p in sorted(st.successful_pods,
                             key=lambda p: p.metadata.name)]
            + [Placement(pod=p, reason="Unschedulable")
               for p in sorted(st.failed_pods,
                               key=lambda p: p.metadata.name)])

    host_hash = run_route("0")
    kernel_hash = run_route("1")
    if host_hash != kernel_hash:
        raise AssertionError(
            f"gang kernel route diverges from the host oracle "
            f"({kernel_hash[:16]} != {host_hash[:16]})")

    # all-or-nothing: 8 x 3900m on 6 x 4000m nodes cannot all fit
    big = [mark_gang(make_pod(f"big-{j}", milli_cpu=3900), "big")
           for j in range(8)]
    st = run_simulation(big, cluster(), backend="jax")
    if st.successful_pods:
        raise AssertionError(
            f"rejected gang left {len(st.successful_pods)} members bound")
    msgs = {p.status.conditions[-1].message for p in st.failed_pods}
    if len(st.failed_pods) != 8 or len(msgs) != 1:
        raise AssertionError(
            f"expected 8 members sharing one FitError, got "
            f"{len(st.failed_pods)} members / {len(msgs)} messages")

    # gang-free identity across backends (the annotation is the trigger)
    ref = run_simulation(feed(gang=False), cluster(), backend="reference")
    jx = run_simulation(feed(gang=False), cluster(), backend="jax")
    ref_bind = sorted((p.metadata.name, p.spec.node_name)
                      for p in ref.successful_pods)
    jx_bind = sorted((p.metadata.name, p.spec.node_name)
                     for p in jx.successful_pods)
    if ref_bind != jx_bind:
        raise AssertionError("gang-free feed diverges between backends")
    return host_hash[:16], len(feed()), len(msgs)


def run_sharded_variant():
    """Node-sharded twin (ISSUE 16) stage-0: the TPUSIM_SHARDS=2 mesh
    route must (a) byte-match the single-device placement hash for the
    same seeded feed — the verify-then-trust seam pins the (shards,
    config) signature on the first batch; (b) serve a warm second batch
    from the already-compiled shard_map program without tracing a fresh
    one (zero-retrace across batches). Returns None (skip) on hosts
    exposing a single device."""
    import jax

    if len(jax.devices()) < 2:
        return None
    from tpusim.backends import Placement, placement_hash
    from tpusim.jaxe import trust
    from tpusim.jaxe.kernels import _SHARDED_SCAN_PROGRAMS
    from tpusim.simulator import run_simulation

    def cluster():
        nodes = [make_node(f"sn{i}", milli_cpu=(1500, 2500, 4000)[i % 3],
                           memory=(2 << 30) + (i % 4) * (1 << 30),
                           labels={"zone": f"z{i % 2}",
                                   "topology.kubernetes.io/rack":
                                   f"rack-{i // 4}"})
                 for i in range(14)]
        return ClusterSnapshot(nodes=nodes, pods=[])

    def feed(tag="a"):
        pods = [make_pod(f"sp-{tag}-{i}", milli_cpu=150 + 70 * (i % 9),
                         memory=(192 << 20) * (1 + i % 3))
                for i in range(28)]
        # oversized tail: FitError text must survive the shard merge
        pods += [make_pod(f"sp-{tag}-big{j}", milli_cpu=9000)
                 for j in range(2)]
        return pods

    def run(shards, tag="a", reset=True):
        prev = os.environ.get("TPUSIM_SHARDS")
        os.environ["TPUSIM_SHARDS"] = str(shards)
        try:
            if reset:
                trust.reset_all()
            st = run_simulation(feed(tag), cluster(), backend="jax")
        finally:
            if prev is None:
                os.environ.pop("TPUSIM_SHARDS", None)
            else:
                os.environ["TPUSIM_SHARDS"] = prev
        return placement_hash(
            [Placement(pod=p, node_name=p.spec.node_name)
             for p in sorted(st.successful_pods,
                             key=lambda p: p.metadata.name)]
            + [Placement(pod=p, reason="Unschedulable")
               for p in sorted(st.failed_pods,
                               key=lambda p: p.metadata.name)])

    base_hash = run(1)
    shard_hash = run(2)
    if shard_hash != base_hash:
        raise AssertionError(
            f"sharded route diverges from single-device "
            f"({shard_hash[:16]} != {base_hash[:16]})")
    if trust.SHARD.disabled or not trust.SHARD.verified:
        raise AssertionError(
            "sharded run never pinned a verified signature "
            f"(disabled={trust.SHARD.disabled})")

    # zero-retrace: a warm batch over the same shapes must reuse the
    # compiled shard_map program (the pinned sig skips re-verification)
    def program_traces():
        try:
            return sum(fn._cache_size()
                       for fn in _SHARDED_SCAN_PROGRAMS.values())
        except AttributeError:  # private jit API moved: skip the check
            return None

    before = program_traces()
    warm_hash = run(2, tag="b", reset=False)
    traced = None
    if before is not None:
        traced = program_traces() - before
        if traced:
            raise AssertionError(
                f"warm sharded batch retraced ({traced:+d} shard_map "
                "programs); the per-(config, mesh) cache is broken")
    warm_base = run(1, tag="b")
    if warm_hash != warm_base:
        raise AssertionError("warm sharded batch diverges from "
                             "single-device")
    return base_hash[:16], 2, traced


def run_traced_fleet_variant():
    """Fleet-wide distributed tracing (ISSUE 20) stage-0: a replicated
    leader -> follower stream run plus a traced serve batch, captured on
    one flight recorder, must (a) leave the fold chain byte-identical
    to an untraced run — the recorder is invisible to the decisions;
    (b) carry every WAL frame's context across the shipping socket:
    each flow start meets exactly one finish and the follower's replay
    spans are stamped with leader trace ids; (c) pin the hello-handshake
    clock anchors tools/trace_merge.py aligns multi-process captures
    on; and (d) export an artifact that tools/trace_lint.py certifies
    Perfetto-valid both as captured and after a trace_merge round-trip."""
    import importlib.util
    import json
    import shutil
    import tempfile

    from tpusim.obs import recorder as flight
    from tpusim.serve import ScenarioFleet, WhatIfRequest
    from tpusim.simulator import run_replicated_stream, \
        run_stream_simulation

    def load_tool(name):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(os.path.dirname(__file__), f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    kw = dict(num_nodes=16, cycles=8, arrivals=16, evict_fraction=0.25,
              seed=9, checkpoint_every=2)
    base_dir = tempfile.mkdtemp(prefix="tpusim-smoke-trace-")
    rep_dir = tempfile.mkdtemp(prefix="tpusim-smoke-trace-")
    outer = flight.get_recorder()
    flight.uninstall()
    try:
        base = run_stream_simulation(checkpoint_dir=base_dir, **kw)
        rec = flight.install(
            flight.FlightRecorder(process_name="tpusim-smoke-fleet"))
        out = run_replicated_stream(checkpoint_dir=rep_dir, **kw)
        if out["fold_chain"] != base["fold_chain"]:
            raise AssertionError(
                f"tracing moved the fold chain ({out['fold_chain'][:16]} "
                f"!= {base['fold_chain'][:16]}); the recorder must be "
                "invisible to the decisions")
        if out["divergence"]:
            raise AssertionError(
                f"follower diverged under tracing: {out['divergence']}")
        snap, pods = _base()
        fleet = ScenarioFleet(bucket_size=2, flush_after_s=60.0)
        responses = fleet.run([WhatIfRequest(pods=pods, snapshot=snap)
                               for _ in range(2)])
        if not all(r.ok for r in responses):
            raise AssertionError("traced serve batch failed")
        flight.uninstall()

        s = [e for e in rec.events
             if e.get("ph") == "s" and e.get("cat") == "wal"]
        f = [e for e in rec.events
             if e.get("ph") == "f" and e.get("cat") == "wal"]
        if not s or {e["id"] for e in s} != {e["id"] for e in f}:
            raise AssertionError(
                f"wal flow graph disconnected ({len(s)} starts, "
                f"{len(f)} finishes)")
        applies = [e for e in rec.events
                   if e.get("name") == "replicate:apply"
                   and e.get("args", {}).get("trace_id")]
        leader_ids = {e["args"]["trace_id"] for e in s}
        if not applies or \
                not {e["args"]["trace_id"] for e in applies} <= leader_ids:
            raise AssertionError(
                "follower replay spans lost the leader's trace context")
        admits = [e for e in rec.events if e.get("name") == "serve:admit"
                  and e.get("args", {}).get("trace_id")]
        if not admits:
            raise AssertionError("serve admissions were not stamped with "
                                 "a trace context")
        for anchor in ("hello_tx_us", "peer_clk_us", "peer_clk_rx_us"):
            if anchor not in rec.anchors:
                raise AssertionError(
                    f"clock anchor {anchor} missing; trace_merge cannot "
                    "align this capture")
        doc = json.loads(rec.to_chrome_json())
        lint = load_tool("trace_lint")
        problems = lint.lint_trace(doc)
        merged = load_tool("trace_merge").merge([doc])
        problems += [f"post-merge: {p}" for p in lint.lint_trace(merged)]
        if problems:
            raise AssertionError(f"trace lint found: {problems[:3]}")
        return (out["fold_chain"][:16], len(doc["traceEvents"]), len(s),
                len(applies))
    finally:
        flight.uninstall()
        if outer is not None:
            flight.install(outer)
        shutil.rmtree(base_dir, ignore_errors=True)
        shutil.rmtree(rep_dir, ignore_errors=True)


def _write_smoke_trace(recorder):
    """Persist the sweep's flight-recorder trace; never fail the smoke."""
    path = os.environ.get("TPUSIM_SMOKE_TRACE") or os.path.join(
        os.path.dirname(__file__), "..", "bench_results", "smoke_trace.json")
    try:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        recorder.write(path)
    except OSError as exc:
        print(f"SMOKE trace write failed: {exc}", flush=True)
        return
    print(f"SMOKE trace: {os.path.normpath(path)} "
          f"({len(recorder.events)} events)", flush=True)


def main() -> int:
    import jax

    from tpusim.obs import recorder as flight

    platform = jax.default_backend()
    only = [v for v in os.environ.get("TPUSIM_SMOKE_VARIANTS", "").split(",")
            if v]
    recorder = flight.install(flight.FlightRecorder())
    t0 = time.time()
    ran = 0
    try:
        for name, build, most in PALLAS_VARIANTS:
            if only and name not in only:
                continue
            t = time.time()
            vsp = flight.span("smoke_variant")
            vsp.set("variant", name)
            try:
                h, scheduled, total = run_pallas_variant(name, build, most)
            except Exception as exc:  # noqa: BLE001 — one line per failure
                vsp.set("parity", "FAILED")
                vsp.set("error", type(exc).__name__)
                vsp.end()
                print(f"SMOKE FAILED: {name}: {exc}", flush=True)
                return 1
            vsp.set("parity", "ok")
            vsp.set("hash", h)
            vsp.set("scheduled", f"{scheduled}/{total}")
            vsp.end()
            ran += 1
            print(f"SMOKE {name}: OK hash={h} scheduled={scheduled}/{total} "
                  f"({time.time() - t:.1f}s)", flush=True)
        if not only or "preempt_victim" in only:
            t = time.time()
            vsp = flight.span("smoke_variant")
            vsp.set("variant", "preempt_victim")
            try:
                h, n_victims, paths = run_preempt_variant()
            except Exception as exc:  # noqa: BLE001
                vsp.set("parity", "FAILED")
                vsp.set("error", type(exc).__name__)
                vsp.end()
                print(f"SMOKE FAILED: preempt_victim: {exc}", flush=True)
                return 1
            vsp.set("parity", "ok")
            vsp.set("hash", h)
            vsp.set("victims", n_victims)
            vsp.end()
            ran += 1
            print(f"SMOKE preempt_victim: OK hash={h} victims={n_victims} "
                  f"paths={paths} ({time.time() - t:.1f}s)", flush=True)
        if not only or "serve_fleet" in only:
            t = time.time()
            vsp = flight.span("smoke_variant")
            vsp.set("variant", "serve_fleet")
            try:
                h, n_req, stats = run_serve_fleet_variant()
            except Exception as exc:  # noqa: BLE001
                vsp.set("parity", "FAILED")
                vsp.set("error", type(exc).__name__)
                vsp.end()
                print(f"SMOKE FAILED: serve_fleet: {exc}", flush=True)
                return 1
            vsp.set("parity", "ok")
            vsp.set("hash", h)
            vsp.set("requests", n_req)
            vsp.end()
            ran += 1
            print(f"SMOKE serve_fleet: OK hash={h} requests={n_req} "
                  f"warm_hits={stats['warm_hits']} "
                  f"({time.time() - t:.1f}s)", flush=True)
        if not only or "chaos_breaker" in only:
            t = time.time()
            vsp = flight.span("smoke_variant")
            vsp.set("variant", "chaos_breaker")
            try:
                h, transitions = run_chaos_breaker_variant()
            except Exception as exc:  # noqa: BLE001
                vsp.set("parity", "FAILED")
                vsp.set("error", type(exc).__name__)
                vsp.end()
                print(f"SMOKE FAILED: chaos_breaker: {exc}", flush=True)
                return 1
            vsp.set("parity", "ok")
            vsp.set("hash", h)
            vsp.set("transitions", "->".join(transitions))
            vsp.end()
            ran += 1
            print(f"SMOKE chaos_breaker: OK hash={h} "
                  f"transitions={'->'.join(transitions)} "
                  f"({time.time() - t:.1f}s)", flush=True)
        if not only or "stream_churn" in only:
            t = time.time()
            vsp = flight.span("smoke_variant")
            vsp.set("variant", "stream_churn")
            try:
                h, scheduled, total, stream_cycles, traced = \
                    run_stream_churn_variant()
            except Exception as exc:  # noqa: BLE001
                vsp.set("parity", "FAILED")
                vsp.set("error", type(exc).__name__)
                vsp.end()
                print(f"SMOKE FAILED: stream_churn: {exc}", flush=True)
                return 1
            vsp.set("parity", "ok")
            vsp.set("hash", h)
            vsp.set("stream_cycles", stream_cycles)
            vsp.end()
            ran += 1
            retrace = ("skipped" if traced is None
                       else f"+{traced[0]}/+{traced[1]}")
            print(f"SMOKE stream_churn: OK hash={h} "
                  f"scheduled={scheduled}/{total} "
                  f"stream_cycles={stream_cycles} retrace={retrace} "
                  f"({time.time() - t:.1f}s)", flush=True)
        if not only or "stream_policy" in only:
            t = time.time()
            vsp = flight.span("smoke_variant")
            vsp.set("variant", "stream_policy")
            try:
                h, scheduled, total, pipelined_cycles, traced = \
                    run_stream_policy_variant()
            except Exception as exc:  # noqa: BLE001
                vsp.set("parity", "FAILED")
                vsp.set("error", type(exc).__name__)
                vsp.end()
                print(f"SMOKE FAILED: stream_policy: {exc}", flush=True)
                return 1
            vsp.set("parity", "ok")
            vsp.set("hash", h)
            vsp.set("pipelined_cycles", pipelined_cycles)
            vsp.end()
            ran += 1
            retrace = ("skipped" if traced is None
                       else f"+{traced[0]}/+{traced[1]}/+{traced[2]}")
            print(f"SMOKE stream_policy: OK hash={h} "
                  f"scheduled={scheduled}/{total} "
                  f"pipelined_cycles={pipelined_cycles} retrace={retrace} "
                  f"({time.time() - t:.1f}s)", flush=True)
        if not only or "stream_recover" in only:
            t = time.time()
            vsp = flight.span("smoke_variant")
            vsp.set("variant", "stream_recover")
            try:
                h, resume_cycle, wal_records, traced = \
                    run_stream_recover_variant()
            except Exception as exc:  # noqa: BLE001
                vsp.set("parity", "FAILED")
                vsp.set("error", type(exc).__name__)
                vsp.end()
                print(f"SMOKE FAILED: stream_recover: {exc}", flush=True)
                return 1
            vsp.set("parity", "ok")
            vsp.set("hash", h)
            vsp.set("resume_cycle", resume_cycle)
            vsp.end()
            ran += 1
            retrace = ("skipped" if traced is None
                       else f"+{traced[0]}/+{traced[1]}")
            print(f"SMOKE stream_recover: OK hash={h} "
                  f"resume_cycle={resume_cycle} wal_records={wal_records} "
                  f"retrace={retrace} ({time.time() - t:.1f}s)", flush=True)
        if not only or "standby" in only:
            t = time.time()
            vsp = flight.span("smoke_variant")
            vsp.set("variant", "standby")
            try:
                h, rto_ms, replayed, wal_records, traced = \
                    run_standby_variant()
            except Exception as exc:  # noqa: BLE001
                vsp.set("parity", "FAILED")
                vsp.set("error", type(exc).__name__)
                vsp.end()
                print(f"SMOKE FAILED: standby: {exc}", flush=True)
                return 1
            vsp.set("parity", "ok")
            vsp.set("hash", h)
            vsp.set("rto_ms", round(rto_ms, 2))
            vsp.end()
            ran += 1
            retrace = ("skipped" if traced is None
                       else f"+{traced[0]}/+{traced[1]}")
            print(f"SMOKE standby: OK hash={h} rto_ms={rto_ms:.1f} "
                  f"replayed={replayed}/{wal_records} retrace={retrace} "
                  f"({time.time() - t:.1f}s)", flush=True)
        if not only or "live_whatif" in only:
            t = time.time()
            vsp = flight.span("smoke_variant")
            vsp.set("variant", "live_whatif")
            try:
                h, answered, retraces = run_live_whatif_variant()
            except Exception as exc:  # noqa: BLE001
                vsp.set("parity", "FAILED")
                vsp.set("error", type(exc).__name__)
                vsp.end()
                print(f"SMOKE FAILED: live_whatif: {exc}", flush=True)
                return 1
            vsp.set("parity", "ok")
            vsp.set("hash", h)
            vsp.set("answered", answered)
            vsp.end()
            ran += 1
            print(f"SMOKE live_whatif: OK hash={h} answered={answered} "
                  f"retrace=+{retraces} ({time.time() - t:.1f}s)", flush=True)
        if not only or "analytics" in only:
            t = time.time()
            vsp = flight.span("smoke_variant")
            vsp.set("variant", "analytics")
            try:
                h, n_samples, sources = run_analytics_variant()
            except Exception as exc:  # noqa: BLE001
                vsp.set("parity", "FAILED")
                vsp.set("error", type(exc).__name__)
                vsp.end()
                print(f"SMOKE FAILED: analytics: {exc}", flush=True)
                return 1
            vsp.set("parity", "ok")
            vsp.set("hash", h)
            vsp.set("samples", n_samples)
            vsp.end()
            ran += 1
            print(f"SMOKE analytics: OK hash={h} samples={n_samples} "
                  f"sources={'+'.join(sources)} "
                  f"({time.time() - t:.1f}s)", flush=True)
        if not only or "gang" in only:
            t = time.time()
            vsp = flight.span("smoke_variant")
            vsp.set("variant", "gang")
            try:
                h, n_pods, n_msgs = run_gang_variant()
            except Exception as exc:  # noqa: BLE001
                vsp.set("parity", "FAILED")
                vsp.set("error", type(exc).__name__)
                vsp.end()
                print(f"SMOKE FAILED: gang: {exc}", flush=True)
                return 1
            vsp.set("parity", "ok")
            vsp.set("hash", h)
            vsp.end()
            ran += 1
            print(f"SMOKE gang: OK hash={h} pods={n_pods} "
                  f"shared_fit_msgs={n_msgs} "
                  f"({time.time() - t:.1f}s)", flush=True)
        if not only or "sharded" in only:
            t = time.time()
            vsp = flight.span("smoke_variant")
            vsp.set("variant", "sharded")
            try:
                out = run_sharded_variant()
            except Exception as exc:  # noqa: BLE001
                vsp.set("parity", "FAILED")
                vsp.set("error", type(exc).__name__)
                vsp.end()
                print(f"SMOKE FAILED: sharded: {exc}", flush=True)
                return 1
            if out is None:
                vsp.set("parity", "skipped")
                vsp.end()
                print("SMOKE sharded: SKIPPED (needs >= 2 devices)",
                      flush=True)
            else:
                h, n_shards, traced = out
                vsp.set("parity", "ok")
                vsp.set("hash", h)
                vsp.set("shards", n_shards)
                vsp.end()
                ran += 1
                retrace = "skipped" if traced is None else f"+{traced}"
                print(f"SMOKE sharded: OK hash={h} shards={n_shards} "
                      f"retrace={retrace} ({time.time() - t:.1f}s)",
                      flush=True)
        if not only or "traced_fleet" in only:
            t = time.time()
            vsp = flight.span("smoke_variant")
            vsp.set("variant", "traced_fleet")
            try:
                h, n_events, n_flows, n_applies = run_traced_fleet_variant()
            except Exception as exc:  # noqa: BLE001
                vsp.set("parity", "FAILED")
                vsp.set("error", type(exc).__name__)
                vsp.end()
                print(f"SMOKE FAILED: traced_fleet: {exc}", flush=True)
                return 1
            vsp.set("parity", "ok")
            vsp.set("hash", h)
            vsp.set("events", n_events)
            vsp.end()
            ran += 1
            print(f"SMOKE traced_fleet: OK hash={h} events={n_events} "
                  f"wal_flows={n_flows} replay_spans={n_applies} "
                  f"({time.time() - t:.1f}s)", flush=True)
    finally:
        flight.uninstall()
        _write_smoke_trace(recorder)
    print(f"SMOKE COMPLETE: {ran} variants, platform={platform} "
          f"({time.time() - t0:.1f}s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

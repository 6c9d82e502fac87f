"""JaxBackend: the SimulatorBackend implementation running on TPU/XLA.

Exactness contract: placements are
IDENTICAL to ReferenceBackend — verified by randomized differential tests —
across the full DefaultProvider feature set: resources/conditions/pressure,
taints/tolerations, node selectors, node affinity, hostname pins, scalar
resources, controller-avoid annotations, host ports,
services/selector-spreading, and inter-pod (anti)affinity (pod-group presence
state carried on device; state.GroupTables).

Compile-time fallbacks route to the reference backend (fallback="reference")
or raise (fallback="error"): pod-group budget overruns (merged groups >
TPUSIM_MAX_GROUPS, raw signatures > TPUSIM_MAX_RAW_GROUPS, matcher precompute
> TPUSIM_MAX_MATCH_WORK, presence bytes > TPUSIM_MAX_PRESENCE_BYTES — groups
merge by match profile first, so only behaviorally distinct classes count),
unresolvable PVC references on zone-constrained clusters (the reference's
NoVolumeZoneConflict *errors* host-side there), and the host-bound policy
shapes listed in jaxe/policyc.py (extenders only). Volume workloads run
natively on BOTH the fresh and incremental (event-log) paths.
"""

from __future__ import annotations

import logging
import os
from typing import List, Optional

import numpy as np

from tpusim.api.snapshot import ClusterSnapshot
from tpusim.api.types import Pod
from tpusim.backends import (
    Placement,
    ReferenceBackend,
    bind_pod,
    mark_unschedulable,
    placement_hash,
)
from tpusim.engine.generic_scheduler import NO_NODE_AVAILABLE_MSG
from tpusim.engine.providers import (
    CLUSTER_AUTOSCALER_PROVIDER,
    DEFAULT_PROVIDER,
    TD_PROVIDER,
)
from tpusim.jaxe import ensure_x64
from tpusim.jaxe.kernels import (
    EXPLAIN_SENTINEL,
    carry_init,
    config_for,
    explain_part_names,
    pod_columns_to_device,
    pod_columns_to_host,
    schedule_scan,
    schedule_scan_chunked,
    statics_to_device,
)
from tpusim.jaxe.state import NUM_FIXED_BITS, compile_cluster, reason_strings
from tpusim.obs import analytics
from tpusim.obs import provenance
from tpusim.obs import recorder as flight
from tpusim.obs import tracectx

log = logging.getLogger(__name__)


# process-wide fast-path auto-mode state: `disabled` flips the first time a
# self-verification chunk disagrees with the XLA scan (never re-enabled);
# `verified_sigs` holds the kernel signatures (the _build_call variant:
# shape pads + feature flags) whose first large-enough batch verified —
# each distinct Pallas/Mosaic kernel variant earns trust separately
# (ADVICE r4: a process whose first verified batch was group-free must not
# run the group-featured kernel unverified); `transient` counts consecutive
# runtime failures such as a one-off device OOM — that batch reruns on the
# XLA scan on the same device, and three in a row disable the path. Any
# other kernel failure (a lowering or compile error) raises: there is no
# quiet route around the device.
_FAST_AUTO = {"disabled": False, "verified_sigs": set(), "transient": 0}

_TRANSIENT_MARKERS = ("RESOURCE_EXHAUSTED", "out of memory", "OOM",
                      "UNAVAILABLE", "DEADLINE_EXCEEDED", "CANCELLED")
# a compile-time refusal can carry a runtime marker too (a VMEM or HBM
# overflow is RESOURCE_EXHAUSTED) and is never transient
_COMPILE_MARKERS = ("Mosaic", "compil", "lower", "vmem", "VMEM")
_MAX_TRANSIENT_FAILURES = 3


def reset_fast_auto() -> None:
    """Reset every process-wide fast-path/victim-kernel trust flag to its
    boot state. Test isolation ONLY: a test that trips the transient path or
    a verify failure would otherwise leak `disabled`/pinned-signature state
    into every later test in the process (ordering could then flip fast-path
    eligibility mid-session). Wired as an autouse fixture in
    tests/conftest.py; production code never calls it."""
    _FAST_AUTO["disabled"] = False
    _FAST_AUTO["verified_sigs"] = set()
    _FAST_AUTO["transient"] = 0
    _VICTIM_AUTO["disabled"] = False
    _VICTIM_AUTO["verified_sigs"] = set()
    _SHARD_AUTO["disabled"] = False
    _SHARD_AUTO["verified_sigs"] = set()
    from tpusim.gang.kernel import _GANG_AUTO  # lazy: gang imports backend
    _GANG_AUTO["disabled"] = False
    _GANG_AUTO["verified_sigs"] = set()
    # disarm any leftover chaos seam (breaker + injector) the same way
    uninstall_chaos()


# plan_fast ineligibility reasons, classified into low-cardinality counter
# keys (the raw strings embed counts/budgets and would explode the label
# space); ordered — first substring match wins
_FALLBACK_KEYS = (
    ("policy static tables unavailable", "policy_tables_missing"),
    ("not compiled", "tables_not_compiled"),
    ("ServiceAffinity lock segments", "sa_segs_budget"),
    ("ServiceAffinity entry labels", "sa_segs_budget"),
    ("ServiceAntiAffinity label domains", "saa_doms_budget"),
    ("ServiceAntiAffinity spread counts", "saa_int32"),
    ("negative", "negative_scores"),
    ("pod groups exceed", "groups_budget"),
    ("zone domains exceed", "zones_budget"),
    ("topology keys exceed", "interpod_budget"),
    ("topology domains exceed", "interpod_budget"),
    ("inter-pod terms exceed", "interpod_budget"),
    ("inter-pod priority counts", "interpod_int32"),
    ("non-integral preferred inter-pod", "interpod_weights"),
    ("MaxPD volume ids", "maxpd_budget"),
    ("scalar resource kinds", "reason_bits_budget"),
    ("priority weights exceed", "score_int32"),
    ("int32", "int32_overflow"),
    ("explain lanes", "explain"),
)


def _fast_fallback_key(why: str) -> str:
    for marker, key in _FALLBACK_KEYS:
        if marker in why:
            return key
    return "other"


def _note_fast_fallback(metrics, why: str) -> None:
    """Surface a plan_fast rejection as observability (ISSUE 4 satellite):
    a labeled counter keyed by blocker class plus a flight-recorder instant
    carrying the full reason string."""
    key = _fast_fallback_key(why)
    metrics.fast_fallback.inc(key)
    flight.note_fast_fallback(key, why)


def plan_signature(plan) -> tuple:
    """The kernel-variant key for AUTO-mode trust: mirrors the _build_call
    cache key's semantic axes (node padding, feature flags, scalar/group
    widths) — a Mosaic miscompile is per compiled variant, so verification
    of one variant must not exempt another."""
    sig = (plan.alloc_cpu.shape[1], plan.most_requested, plan.num_scalars,
           plan.num_groups, plan.n_zone_doms, plan.has_ports,
           plan.has_disk, plan.has_spread, plan.has_vol_zone)
    if plan.has_interpod:
        # the exist-side tables and hard weight are BAKED into the compiled
        # kernel (part of the _build_call cache key): same dims with
        # different constants is a different Mosaic program and must earn
        # trust separately
        sig += (plan.n_topo_keys, plan.topo_nodewise, plan.n_topo_doms_ip,
                plan.ta, plan.tb,
                plan.tp, plan.hard_weight, plan.exist_anti_key,
                plan.exist_anti_mask, plan.exist_anti_empty,
                plan.exist_pref_key, plan.exist_pref_w,
                plan.exist_aff_key, plan.exist_aff_mask)
    if plan.has_maxpd:
        # volume type triples and limits are baked into the kernel variant
        sig += (plan.n_vols, plan.vol_type3, plan.maxpd_limits,
                plan.maxpd_enabled)
    if plan.policy is not None:
        # the whole PolicySpec (hashable) is baked into the variant, plus
        # the policy-residue table dims/flags (PolConst rides the
        # _build_call cache key the same way)
        from tpusim.jaxe.fastscan import pol_const_of

        sig += (plan.policy, pol_const_of(plan))
    return sig


def _note_fast_failure(exc: Exception) -> bool:
    """Classify a fast-path failure. A transient runtime error (ADVICE r4)
    returns True: the caller reruns that batch on the XLA scan, and
    _MAX_TRANSIENT_FAILURES in a row disable the path for the process.
    Anything else returns False and the caller re-raises it."""
    msg = f"{type(exc).__name__}: {exc}"
    if (not any(m in msg for m in _TRANSIENT_MARKERS)
            or any(m in msg for m in _COMPILE_MARKERS)):
        return False
    _FAST_AUTO["transient"] += 1
    flight.note_auto_transition("discard_transient")
    if _FAST_AUTO["transient"] >= _MAX_TRANSIENT_FAILURES:
        _FAST_AUTO["disabled"] = True
        flight.note_auto_transition("discard_permanent")
        log.warning("pallas fast path: %d consecutive transient failures; "
                    "disabling it for this process", _FAST_AUTO["transient"])
    else:
        log.warning("pallas fast path: transient failure %d/%d (%s); this "
                    "batch reruns on the XLA scan", _FAST_AUTO["transient"],
                    _MAX_TRANSIENT_FAILURES, msg)
    return True


def _auto_verify_and_pin(config, compiled, cols, choices, counts,
                         sig: tuple, limit: int = None,
                         statics=None, carry=None) -> bool:
    """AUTO-mode guardrail (shared by run_batch and the what-if fast loop):
    replay the leading pods through the XLA scan and compare bit-for-bit.
    Returns True when the fast results may be used; on disagreement the
    fast path is disabled for the process. Trust is pinned per kernel
    signature, only on a batch of TPUSIM_FAST_VERIFY_MIN+ pods."""
    from tpusim.jaxe.fastscan import verify_against_xla

    m = min(int(os.environ.get("TPUSIM_FAST_VERIFY_PODS", 512)),
            len(np.asarray(cols.req_cpu)))
    if limit is not None:
        # the caller produced fewer rows than the full batch (the
        # preemption hybrid verifies on its first speculation chunk)
        m = min(m, limit)
    if not verify_against_xla(config, compiled, cols, choices, counts, m,
                              statics=statics, carry=carry):
        _FAST_AUTO["disabled"] = True
        flight.note_auto_transition("verify_fail", str(sig))
        log.warning("pallas fast path DISAGREES with the XLA scan on the "
                    "first %d pods; disabling it for this process and "
                    "re-running on the XLA scan", m)
        return False
    flight.note_auto_transition("verify_pass", str(sig))
    min_pin = int(os.environ.get("TPUSIM_FAST_VERIFY_MIN", 64))
    if m >= min_pin:
        _FAST_AUTO["verified_sigs"].add(sig)
        flight.note_auto_transition("pin", str(sig))
        log.info("pallas fast path self-verified on the first %d pods; "
                 "trusting kernel variant %s for this process", m, sig)
    else:
        log.info("pallas fast path verified on %d pods (< %d): keeping "
                 "per-batch verification on", m, min_pin)
    return True


def _fast_path_enabled() -> tuple[bool, bool]:
    """Returns (enabled, auto_mode).

    TPUSIM_FAST=1 forces the Pallas fused-scan fast path (jaxe.fastscan) on
    for eligible workloads (group-free, plus ports/disk-conflict/spreading/
    volume-zone group features within the fast-path budgets), =0 forces it
    off. Unset = AUTO: on
    TPU the fast path is default-ON with first-chunk self-verification —
    before trusting a kernel variant's first fast run, the backend re-runs
    the leading pods through the XLA scan and compares choices bit-for-bit,
    falling back (and disabling the fast path for the process) on any
    disagreement. Off-TPU the kernel would run in the Pallas interpreter —
    far slower than the XLA scan — so non-TPU backends require the explicit
    opt-in with TPUSIM_FAST_INTERPRET=1 (correctness runs).

    A process-wide `disabled` flag (a verify disagreement, or repeated
    transient failures) is honored in BOTH modes: a failing kernel is not
    re-attempted, and re-uploaded, on every batch (ADVICE r4)."""
    env = os.environ.get("TPUSIM_FAST")
    if env == "0":
        return False, False
    if _FAST_AUTO["disabled"]:
        return False, False
    if env == "1":
        if os.environ.get("TPUSIM_FAST_INTERPRET") == "1":
            return True, False
        import jax

        return jax.default_backend() == "tpu", False
    # AUTO (round-3 VERDICT item 2: default-on on TPU, kill-switch kept)
    import jax

    return jax.default_backend() == "tpu", True

# process-wide trust state for the device-side preemption victim-selection
# kernel (jaxe/preempt.py), mirroring _FAST_AUTO: `disabled` flips on the
# first device/host disagreement (never re-enabled); `verified_sigs` holds
# (candidate_bucket, victim_bucket, zero_req) kernel-variant signatures whose
# first device-selected preemption byte-matched the full host oracle
# (selectVictimsOnNode + pickOneNodeForPreemption on cloned NodeInfos) —
# pow2-bucketed shapes mean each compiled variant earns trust separately.
_VICTIM_AUTO = {"disabled": False, "verified_sigs": set()}


def victim_kernel_enabled() -> tuple[bool, bool]:
    """Returns (enabled, auto_mode) for the preemption victim-selection
    kernel.

    TPUSIM_PREEMPT_DEVICE=0 forces the host pipeline, =1 forces the device
    kernel WITHOUT first-use verification (benchmark/debug). Unset = AUTO:
    default-ON on every backend — the kernel is a jitted XLA scan (not
    Pallas), fast on CPU too — with first-preemption-per-variant
    verification against the host oracle; any disagreement disables the
    kernel for the process and the host result is used, so AUTO can never
    change behavior. The `disabled` flag is honored in both modes."""
    env = os.environ.get("TPUSIM_PREEMPT_DEVICE")
    if env == "0":
        return False, False
    if _VICTIM_AUTO["disabled"]:
        return False, False
    if env == "1":
        return True, False
    return True, True


# process-wide trust state for the node-sharded scan route (ISSUE 16),
# mirroring _FAST_AUTO: `disabled` flips the first time a sharded dispatch's
# choices/counts disagree with the single-device replay (never re-enabled);
# `verified_sigs` holds (shard_count, config) pairs whose first batch
# verified — a different shard count or engine config compiles a different
# collective program and earns trust separately.
_SHARD_AUTO = {"disabled": False, "verified_sigs": set()}


def _shard_count() -> int:
    """TPUSIM_SHARDS=k (k > 1) opts the XLA scan into the node-sharded
    shard_map route over a k-device mesh. Unset, 1, 0, or garbage selects
    the single-device route — k=1 must not even build a mesh, so those
    placement chains stay byte-identical to pre-shard builds. A k beyond
    the device count raises: the sharded route never quietly becomes the
    single-device one."""
    try:
        k = int(os.environ.get("TPUSIM_SHARDS", "1"))
    except ValueError:
        return 1
    if k <= 1:
        return 1
    import jax

    if len(jax.devices()) < k:
        raise ValueError(
            f"TPUSIM_SHARDS={k} needs {k} devices; this process has "
            f"{len(jax.devices())} ({jax.devices()[0].platform})")
    return k


def _dispatch_sharded(config, mesh, n_shards, statics, carry, xs,
                      use_chunks, scan_chunk, metrics):
    """One node-sharded dispatch: pad the node axis shard-even, place the
    trees per the mesh's node shardings, run the shard_map scan (chunked
    or single), and stamp the tpusim_shard_* telemetry. Returns
    (final_carry, choices, counts, sharded_statics) — the carry/statics
    come back padded + sharded for the analytics reduction to fold."""
    from dataclasses import replace as _dc_replace

    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpusim.jaxe.kernels import sharded_scan_fn
    from tpusim.jaxe.sharding import (
        node_shardings,
        pad_node_axis,
        stage_tree,
    )

    sconfig = _dc_replace(config, shard_axis="node")
    with flight.span("shard:stage") as ssp:
        st, ca, n_real = pad_node_axis(statics, carry, n_shards)
        st_sh, ca_sh = node_shardings(mesh)
        st = stage_tree(st, st_sh)
        ca = stage_tree(ca, ca_sh)
        if ssp:
            ssp.set("shards", n_shards)
            ssp.set("nodes", n_real)
    per = st.alloc_cpu.shape[0] // n_shards
    metrics.shard_count.set(n_shards)
    for s in range(n_shards):
        metrics.shard_node_occupancy.set(
            str(s), max(0, min(n_real - s * per, per)))
    # estimated collective payload: each pod step moves ~12 psum/pmax/pmin
    # scalars plus an n_shards-wide tie-count all_gather, 8 bytes each, on
    # every shard (the analytics/gang collectives are separate dispatches)
    n_pods = int(np.asarray(xs.req_cpu).shape[0])
    metrics.shard_collective_bytes.set(
        float(n_pods) * (12 + n_shards) * 8 * n_shards)
    rep = NamedSharding(mesh, P())
    with flight.span("shard:scan", "device") as sp:
        if use_chunks:
            final_carry, choices, counts, _ = schedule_scan_chunked(
                config, ca, st, xs, scan_chunk,
                scan_donated=sharded_scan_fn(sconfig, mesh, donate=True),
                put=lambda rows: stage_tree(rows, rep))
        else:
            final_carry, choices, counts, _ = sharded_scan_fn(
                sconfig, mesh)(ca, st, stage_tree(xs, rep))
        if sp:
            sp.set("shards", n_shards)
            sp.set("pods", n_pods)
    return final_carry, choices, counts, st


_MOST_REQUESTED_PROVIDERS = {CLUSTER_AUTOSCALER_PROVIDER, TD_PROVIDER}
_KNOWN_PROVIDERS = {DEFAULT_PROVIDER} | _MOST_REQUESTED_PROVIDERS


# process-wide chaos seam (ISSUE 3): when installed, every JaxBackend
# dispatch flows through the circuit breaker (closed → open on repeated
# device faults → half-open re-probe → closed) and the DeviceInjector
# scripts per-dispatch exceptions/corruptions. Upgrades the _FAST_AUTO
# three-strikes-and-permanently-out policy into a RECOVERING state
# machine: a flaky device degrades to the host pipeline and comes back,
# and under chaos no placement is ever emitted unverified (verify="all")
# or un-re-probed (half-open).
_CHAOS = {"injector": None, "breaker": None, "verify": "all"}


def install_chaos(device_plan):
    """Arm the device-fault layer of a chaos plan
    (tpusim.chaos.plan.DeviceFaultPlan). Returns the CircuitBreaker so
    callers can assert on its transition audit trail."""
    from tpusim.chaos.breaker import CircuitBreaker
    from tpusim.chaos.engine import DeviceInjector

    device_plan.validate()
    breaker = CircuitBreaker("device", device_plan.failure_threshold,
                             device_plan.cooldown)
    _CHAOS.update(injector=DeviceInjector(device_plan.faults),
                  breaker=breaker, verify=device_plan.verify)
    return breaker


def uninstall_chaos() -> None:
    _CHAOS.update(injector=None, breaker=None, verify="all")


def format_fit_error(num_nodes: int, counts: np.ndarray, strings: List[str]) -> str:
    """Byte-identical FitError.Error() (generic_scheduler.go:71-90)."""
    reason_strs = sorted(f"{int(c)} {strings[i]}"
                         for i, c in enumerate(counts) if c > 0)
    return (NO_NODE_AVAILABLE_MSG.format(num_nodes)
            + ": " + ", ".join(reason_strs) + ".")


def decode_placements(pods: List[Pod], choices: np.ndarray, counts: np.ndarray,
                      names: List[str], strings: List[str],
                      prebound: Optional[List[Placement]] = None
                      ) -> tuple[List[Placement], int]:
    """Device results -> Placement list (shared by JaxBackend and run_what_if).

    prebound: already-constructed Placements for the scheduled pods, in pod
    order — the pipelined fold-back (stream/runtime._fold_binds) binds each
    placed pod once to feed the host IncrementalCluster, and handing those
    objects in here avoids a second bind_pod copy per placement."""
    placements: List[Placement] = []
    bound_iter = iter(prebound) if prebound is not None else None
    scheduled = 0
    for j, pod in enumerate(pods):
        c = int(choices[j])
        if c >= 0:
            scheduled += 1
            placements.append(next(bound_iter) if bound_iter is not None
                              else Placement(pod=bind_pod(pod, names[c]),
                                             node_name=names[c]))
        else:
            msg = format_fit_error(len(names), counts[j], strings)
            placements.append(Placement(pod=mark_unschedulable(pod, msg),
                                        reason="Unschedulable", message=msg))
    return placements, scheduled


def shared_binds(pods: List[Pod], placements: List[Placement]) -> int:
    """Placed pods whose bound copy shares the pending pod's containers
    (`Pod.bound_copy`): the `decode_placements` span's `shared_binds`."""
    return sum(1 for pod, pl in zip(pods, placements)
               if pl.node_name and pl.pod.spec.containers is pod.spec.containers)


class JaxBackend:
    name = "jax"

    def __init__(self, provider: str = DEFAULT_PROVIDER, fallback: str = "reference",
                 hard_pod_affinity_symmetric_weight: int = 10,
                 policy=None, compiled_policy=None, extender_transport=None):
        """policy: an engine.policy.Policy compiled to static gating + weights
        (jaxe.policyc) — replaces the provider's predicate/priority sets like
        factory.go CreateFromConfig; host-bound policy features (extenders,
        ServiceAffinity, ...) route through the fallback. compiled_policy: a
        jaxe.policyc.CompiledPolicy for `policy`, if the caller already
        compiled it. extender_transport: the in-process extender seam handed
        to the reference fallback (policy extenders are host-bound)."""
        if provider not in _KNOWN_PROVIDERS:
            raise KeyError(f"plugin {provider!r} has not been registered")
        if fallback not in ("reference", "error"):
            raise ValueError("fallback must be 'reference' or 'error'")
        if not 1 <= hard_pod_affinity_symmetric_weight <= 100:
            # factory.go:1024-1026 — the host backend rejects this range in
            # _create_from_keys; the device backend must match
            raise ValueError("invalid hardPodAffinitySymmetricWeight: "
                             f"{hard_pod_affinity_symmetric_weight}, must be "
                             "in the range 1-100")
        self.provider = provider
        self.fallback = fallback
        self.hard_pod_affinity_symmetric_weight = hard_pod_affinity_symmetric_weight
        self.policy = policy
        self.extender_transport = extender_transport
        if policy is not None and compiled_policy is None:
            # compile (and validate) at build time, like CreateFromConfig
            from tpusim.jaxe.policyc import compile_policy

            compiled_policy = compile_policy(policy)
        self._compiled_policy = compiled_policy

    def _reference(self, pods: List[Pod],
                   snapshot: ClusterSnapshot) -> List[Placement]:
        placements = ReferenceBackend(
            provider=self.provider, policy=self.policy,
            extender_transport=self.extender_transport,
            hard_pod_affinity_symmetric_weight=self.hard_pod_affinity_symmetric_weight,
        ).schedule(pods, snapshot)
        # host-route decisions carry provenance too (failures-only: the
        # reference path computes no per-part score lanes); FitError text
        # is the host original by construction
        provenance.capture(placements, "reference")
        return placements

    def schedule(self, pods: List[Pod], snapshot: ClusterSnapshot,
                 precompiled=None) -> List[Placement]:
        """Device dispatch behind the chaos circuit breaker (when armed via
        install_chaos; a no-op wrapper otherwise). The contract under
        chaos: a denied or faulted dispatch routes the batch through the
        host pipeline (byte-identical placements), a half-open probe — and
        every dispatch under verify="all" — is host-verified before its
        placements are emitted, so a flaky device can never surface an
        unverified result, and a recovered device is re-trusted after one
        verified probe."""
        breaker = _CHAOS["breaker"]
        if breaker is None:
            return self._schedule_on_device(pods, snapshot, precompiled)
        if not pods:
            return []
        from tpusim.chaos.engine import DeviceFault

        if not breaker.allow():
            flight.note_route("breaker_fallback", len(pods))
            return self._reference(pods, snapshot)
        probing = breaker.probing
        try:
            placements = self._schedule_on_device(pods, snapshot, precompiled)
        except DeviceFault as exc:
            breaker.record_failure(f"{type(exc).__name__}: {exc}")
            flight.note_route("breaker_fallback", len(pods))
            return self._reference(pods, snapshot)
        if probing or _CHAOS["verify"] == "all":
            expected = self._reference(pods, snapshot)
            if placement_hash(placements) != placement_hash(expected):
                # silent corruption: in-range but wrong placements — only
                # the host parity digest catches it
                breaker.record_failure("device/host placement divergence")
                flight.note_route("breaker_fallback", len(pods))
                return expected
        breaker.record_success()
        return placements

    def _schedule_on_device(self, pods: List[Pod], snapshot: ClusterSnapshot,
                            precompiled=None) -> List[Placement]:
        """precompiled: an optional (CompiledCluster, PodColumns) pair for
        `pods` against `snapshot` — the incremental event-log path
        (jaxe.delta.IncrementalCluster.compile) hands its cached state in
        here instead of recompiling."""
        if not pods:
            return []
        if not snapshot.nodes:
            msg = "no nodes available to schedule pods"
            placements = [Placement(pod=mark_unschedulable(p, msg),
                                    reason="Unschedulable", message=msg)
                          for p in pods]
            provenance.capture(placements, "backend")
            return placements
        from time import perf_counter

        from tpusim.framework.metrics import register, since_in_microseconds

        metrics = register()

        cp = self._compiled_policy
        from tpusim.engine.predicates import (
            POD_TOLERATES_NODE_NO_EXECUTE_TAINTS_PRED,
        )

        need_noexec = (cp is not None and cp.spec.pred_keys is not None
                       and POD_TOLERATES_NODE_NO_EXECUTE_TAINTS_PRED
                       in cp.spec.pred_keys)
        need_saa = cp is not None and (bool(cp.spec.saa_weights)
                                       or cp.spec.sa_enabled)
        def _timed_compile():
            compile_start = perf_counter()
            with flight.span("compile_cluster") as csp:
                out = compile_cluster(snapshot, pods, need_noexec=need_noexec,
                                      need_saa=need_saa)
                if csp:
                    csp.set("pods", len(pods))
                    csp.set("nodes", len(snapshot.nodes))
                    csp.set("node_classes", out[0].node_classes)
                    csp.set("row_evals", out[0].row_evals)
            compile_us = since_in_microseconds(compile_start)
            metrics.backend_compile_latency.observe(compile_us)
            analytics.note_compile(
                "backend", f"nodes={len(snapshot.nodes)}", compile_us)
            return out

        compiled, cols = precompiled or _timed_compile()
        if (need_noexec and not compiled.has_noexec_table) \
                or (need_saa and not compiled.has_saa_table):
            # a precompiled (event-log/incremental) state built without the
            # policy-only tables: recompile fresh for this rare combination
            compiled, cols = _timed_compile()
        unsupported = list(compiled.unsupported)
        if cp is not None:
            unsupported.extend(cp.unsupported)
        if unsupported:
            detail = "; ".join(sorted(set(unsupported))[:5])
            if self.fallback == "error":
                raise NotImplementedError(
                    f"jax backend does not yet carry state for: {detail}")
            log.warning("jax backend falling back to reference for: %s", detail)
            flight.note_route("reference_fallback", len(pods))
            return self._reference(pods, snapshot)

        # the engine configuration and the route: everything between the
        # staging and the Pallas plan
        esp = flight.span("engine_config")
        hard_weight = self.hard_pod_affinity_symmetric_weight
        if cp is not None and cp.hard_weight is not None:
            hard_weight = cp.hard_weight
        num_bits = NUM_FIXED_BITS + len(compiled.scalar_names)
        config = config_for(
            [compiled],
            most_requested=self.provider in _MOST_REQUESTED_PROVIDERS,
            num_reason_bits=num_bits,
            hard_weight=hard_weight)
        ptabs = None
        if cp is not None:
            from dataclasses import replace as _dc_replace

            from tpusim.jaxe.policyc import build_policy_tables

            config = _dc_replace(config, policy=cp.spec)
            # one host-side table build feeds BOTH device routes: plan_fast
            # bakes these into the Pallas plan, the XLA branch grafts them
            # onto the trivial statics rows below (also fills cols.img_id /
            # cols.sa_self_id in place), so the two engines cannot drift on
            # their inputs
            ptabs = build_policy_tables(cp, snapshot, pods, compiled, cols)
            if cp.saa_entries:
                config = _dc_replace(config, n_saa_doms=ptabs.n_saa_doms)

        # decision provenance (ISSUE 13): an installed explain log that
        # asked for a score breakdown compiles the top-k lanes into the
        # scan. explain_k is a jit static, so provenance-off programs (the
        # default) are byte-identical to pre-provenance ones.
        explain_k = provenance.requested_top_k()
        if explain_k > 0:
            from dataclasses import replace as _explain_replace

            config = _explain_replace(
                config,
                explain_k=min(explain_k, len(compiled.statics.names)))

        ensure_x64()
        # fast-path decision BEFORE any device upload: when the Pallas plan
        # engages, the statics/carry/pod-column HBM transfers below would be
        # pure wasted latency on exactly the hot path the feature accelerates
        fplan = None
        fast_verify = False
        fast_sig = None
        fast_on, auto_mode = _fast_path_enabled()
        if fast_on and config.explain_k > 0:
            # the score-breakdown lanes are an XLA-scan feature: the Pallas
            # kernel carries failure provenance natively (reason counts) but
            # not per-part top-k scores (DEVIATIONS.md)
            fast_on = False
            _note_fast_fallback(
                metrics, "explain lanes (top-k score breakdown) route "
                "through the XLA scan")
        if fast_on and analytics.get() is not None:
            # the analytics reduction folds the scan's final carry, which
            # the Pallas kernel never materializes (it emits choices/counts
            # only) — same precedent as the explain lanes above
            fast_on = False
            _note_fast_fallback(
                metrics, "cluster analytics rides the XLA scan's final "
                "carry")
        if (fast_on and auto_mode and not _FAST_AUTO["verified_sigs"]
                and len(pods) < int(os.environ.get(
                    "TPUSIM_FAST_VERIFY_MIN", 64))):
            # no variant is trusted yet, so this small batch would be
            # deferred after planning anyway — skip the O(nodes+pods)
            # gcd reduction entirely (the pre-signature fast exit)
            fast_on = False
            flight.note_auto_transition("defer")
            log.info("pallas fast path deferred: %d pods is below "
                     "the self-verification threshold; using the "
                     "XLA scan", len(pods))
        if esp:
            esp.set("fast", bool(fast_on))
            esp.end()
        if fast_on:
            from tpusim.jaxe.fastscan import plan_fast

            with flight.span("plan_fast") as psp:
                fplan, why = plan_fast(config, compiled, cols, ptabs=ptabs)
                if psp:
                    psp.set("pods", len(pods))
                    psp.set("nodes", len(snapshot.nodes))
                    if fplan is not None and fplan.has_interpod:
                        psp.set("interpod_doms", fplan.n_topo_doms_ip)
                        psp.set("hostname_terms", fplan.hostname_terms)
            if fplan is None:
                _note_fast_fallback(metrics, why)
                log.info("pallas fast path ineligible (%s); using the "
                         "XLA scan", why)
            else:
                fast_sig = plan_signature(fplan)
                fast_verify = (auto_mode and fast_sig
                               not in _FAST_AUTO["verified_sigs"])
            if fplan is not None and fast_verify and len(pods) < int(
                    os.environ.get("TPUSIM_FAST_VERIFY_MIN", 64)):
                # AUTO mode, variant not yet trusted: a batch too small to
                # pin trust (< TPUSIM_FAST_VERIFY_MIN) would run the kernel
                # AND a full XLA replay — strictly slower than plain XLA.
                # Small batches gain nothing from the fast path anyway;
                # route them straight to the XLA scan.
                fplan = None
                fast_verify = False
                flight.note_auto_transition("defer")
                log.info("pallas fast path deferred: %d pods is below "
                         "the self-verification threshold; using the "
                         "XLA scan", len(pods))
        def _xla_statics():
            if cp is None:
                return statics_to_device(compiled)
            # overwrite the trivial custom-plugin rows with the policy's
            # per-node tables (ordering by the compiled node index); the
            # trivial PolicyTables shapes match statics_to_host exactly, so
            # the unconditional replace is byte-identical for policies
            # without the corresponding feature
            from tpusim.jaxe.kernels import _tree_to_device, statics_to_host

            return _tree_to_device(statics_to_host(compiled)._replace(
                label_ok=ptabs.label_ok, label_prio=ptabs.label_prio,
                image_score=ptabs.image_score, saa_dom=ptabs.saa_dom,
                sa_pin=ptabs.sa_pin, sa_val=ptabs.sa_val))

        def _xla_carry():
            carry = carry_init(compiled)
            if cp is not None and cp.spec.sa_enabled:
                carry = carry._replace(sa_lock=ptabs.sa_lock_init)
            return carry

        usp = (flight.span("batch_upload") if fplan is None
               else flight.NOOP_SPAN)
        statics = None if fplan is not None else _xla_statics()
        # Batches beyond TPUSIM_SCAN_CHUNK pods run through the
        # double-buffered chunked scan: pod columns stay host-side and stream
        # to HBM chunk by chunk, bit-identical to the single dispatch
        # (SURVEY.md §7 hard part 6 — 1M-pod batches).
        scan_chunk = int(os.environ.get("TPUSIM_SCAN_CHUNK", 131072))
        use_chunks = (fplan is None and config.explain_k == 0
                      and scan_chunk > 0 and len(pods) > scan_chunk)
        if fplan is None:
            carry = _xla_carry()
            xs = (pod_columns_to_host(cols) if use_chunks
                  else pod_columns_to_device(cols))
        if usp:
            usp.set("pods", len(pods))
            usp.end()
        # On TPU the per-pod filter→score→select→bind pipeline is one fused
        # device program, so the whole batch dispatch lands in the algorithm
        # histogram (the per-phase split of metrics.go has no device analog);
        # e2e additionally covers host-side result materialization.
        # chaos seam: scripted dispatch faults raise here (outside the fast
        # path's own try/except — an injected device death is not a Mosaic
        # lowering failure and must reach the circuit breaker, not flip
        # _FAST_AUTO); scripted corruptions apply to the results below
        _corrupt_kind = None
        if _CHAOS["injector"] is not None:
            _corrupt_kind = _CHAOS["injector"].begin_dispatch()
        dispatch_start = perf_counter()
        dsp = flight.span("device_dispatch", "device")

        def _discard_fast_path():
            # pay the uploads the fast path deferred and rebuild the
            # XLA-scan inputs (set via nonlocal) with a fresh dispatch
            # clock; whether the path stays disabled for the process is
            # the caller's call (_note_fast_failure / _auto_verify_and_pin)
            nonlocal fplan, statics, carry, use_chunks, xs, dispatch_start
            fplan = None
            statics = _xla_statics()
            carry = _xla_carry()
            use_chunks = (config.explain_k == 0
                          and scan_chunk > 0 and len(pods) > scan_chunk)
            xs = (pod_columns_to_host(cols) if use_chunks
                  else pod_columns_to_device(cols))
            dispatch_start = perf_counter()

        if fplan is not None:
            from tpusim.jaxe.fastscan import fast_scan

            try:
                with flight.profiled("tpusim:fast_scan"):
                    choices, counts, _adv = fast_scan(fplan)
            except Exception as exc:
                # a transient runtime error reruns this batch on the XLA
                # scan, on the same device; a lowering or compile error
                # propagates
                if not _note_fast_failure(exc):
                    raise
                _discard_fast_path()
            else:
                _FAST_AUTO["transient"] = 0
                if fast_verify and not _auto_verify_and_pin(
                        config, compiled, cols, choices, counts, fast_sig,
                        statics=_xla_statics(), carry=_xla_carry()):
                    # the kernel lowered but miscomputed: the guardrail
                    # already disabled it process-wide; rerun on XLA
                    _discard_fast_path()
                elif auto_mode and not fast_verify:
                    # already-pinned variant ran without re-verification
                    flight.note_auto_transition("trust", str(fast_sig))
        # node-sharded route decision (ISSUE 16): TPUSIM_SHARDS=k > 1 runs
        # the same fused scan as a shard_map over a k-device node mesh —
        # bit-identical placements via cross-shard collectives, so every
        # ineligibility is a classified fallback to the single-device scan,
        # never a behavior change
        n_shards = _shard_count()
        shard_mesh = None
        shard_statics = None
        if fplan is None and n_shards > 1 and not _SHARD_AUTO["disabled"]:
            from tpusim.jaxe.kernels import shard_route_eligible

            ok, why = shard_route_eligible(config)
            if not ok:
                metrics.shard_fallback.inc(why)
                flight.note_fast_fallback(
                    "shard_" + why,
                    f"TPUSIM_SHARDS={n_shards} batch routed single-device")
                log.info("sharded route ineligible (%s); using the "
                         "single-device scan", why)
            else:
                from tpusim.jaxe.sharding import make_mesh

                shard_mesh = make_mesh(n_shards, snap=1)
        explain_lanes = None
        final_carry = None  # bound-and-dropped unless analytics reads it
        if fplan is None:  # fast path off, ineligible, or discarded above
            with flight.span("scan_launch", "device"), \
                    flight.profiled("tpusim:schedule_scan"):
                if shard_mesh is not None:
                    (final_carry, choices, counts,
                     shard_statics) = _dispatch_sharded(
                         config, shard_mesh, n_shards, statics, carry,
                         xs, use_chunks, scan_chunk, metrics)
                elif use_chunks:
                    final_carry, choices, counts, _ = schedule_scan_chunked(
                        config, carry, statics, xs, scan_chunk)
                elif config.explain_k > 0:
                    (final_carry, choices, counts, _,
                     explain_lanes) = schedule_scan(config, carry,
                                                    statics, xs)
                else:
                    (final_carry, choices, counts,
                     _) = schedule_scan(config, carry, statics, xs)
        with flight.span("device_wait", "device"):
            choices = np.asarray(choices)
            counts = np.asarray(counts)
        if shard_mesh is not None:
            # verify-then-trust, the same seam as the fast path: the first
            # batch per (shard count, config) replays its leading pods
            # through the single-device scan bit-for-bit; a disagreement
            # disables the sharded route for the process and this batch
            # reruns single-device (TPUSIM_SHARD_VERIFY=0 skips, bench only)
            shard_sig = (n_shards, config)
            if os.environ.get("TPUSIM_SHARD_VERIFY") == "0":
                pass
            elif shard_sig in _SHARD_AUTO["verified_sigs"]:
                flight.note_auto_transition("shard_trust", str(n_shards))
            else:
                from tpusim.jaxe.fastscan import verify_against_xla

                if verify_against_xla(config, compiled, cols, choices,
                                      counts, statics=statics,
                                      carry=_xla_carry()):
                    _SHARD_AUTO["verified_sigs"].add(shard_sig)
                    flight.note_auto_transition("shard_pin", str(n_shards))
                else:
                    _SHARD_AUTO["disabled"] = True
                    metrics.shard_count.set(0)
                    flight.note_auto_transition("shard_verify_fail",
                                                str(n_shards))
                    log.warning(
                        "sharded scan DISAGREES with the single-device "
                        "scan on the leading pods (shards=%d); disabling "
                        "the sharded route for this process and re-running "
                        "single-device", n_shards)
                    shard_mesh = None
                    shard_statics = None
                    with flight.profiled("tpusim:schedule_scan"):
                        if use_chunks:
                            (final_carry, choices, counts,
                             _) = schedule_scan_chunked(
                                 config, _xla_carry(), statics, xs,
                                 scan_chunk)
                        else:
                            (final_carry, choices, counts,
                             _) = schedule_scan(config, _xla_carry(),
                                                statics, xs)
                    choices = np.asarray(choices)
                    counts = np.asarray(counts)
        if _CHAOS["injector"] is not None:
            if _corrupt_kind is not None:
                from tpusim.chaos.engine import DeviceInjector

                choices, counts = DeviceInjector.corrupt(_corrupt_kind,
                                                         choices, counts)
            # structural validation always runs under chaos: out-of-range
            # choices and NaN counts never reach decode_placements
            from tpusim.chaos.engine import DeviceOutputError

            n_nodes = len(compiled.statics.names)
            if choices.size and (int(choices.max()) >= n_nodes
                                 or int(choices.min()) < -1):
                raise DeviceOutputError(
                    f"device choice out of range [-1, {n_nodes})")
            if np.isnan(np.asarray(counts, dtype=np.float64)).any():
                raise DeviceOutputError("NaN in device reason counts")
        if fplan is not None:
            # the interpreter only engages on the explicit TPUSIM_FAST=1
            # opt-in (see _fast_path_enabled)
            route = ("fastscan_interpret"
                     if os.environ.get("TPUSIM_FAST") == "1"
                     and os.environ.get("TPUSIM_FAST_INTERPRET") == "1"
                     else "fastscan")
        elif shard_mesh is not None:
            route = "xla_sharded_chunked" if use_chunks else "xla_sharded"
        else:
            route = "xla_chunked" if use_chunks else "xla_scan"
        flight.note_route(route, len(pods))
        if dsp:
            dsp.set("route", route)
            dsp.set("pods", len(pods))
            if shard_mesh is not None:
                dsp.set("shards", n_shards)
            if fast_sig is not None:
                dsp.set("sig", str(fast_sig))
            dsp.end()
        # trace-id exemplar (ISSUE 20): a dispatch-latency spike on the
        # dashboard resolves to the exact device-dispatch trace
        _ctx = tracectx.current()
        metrics.backend_dispatch_latency.observe(
            since_in_microseconds(dispatch_start),
            exemplar=_ctx.trace_id if _ctx is not None else None)
        metrics.scheduling_algorithm_latency.observe(
            since_in_microseconds(dispatch_start))

        strings = reason_strings(compiled.scalar_names)
        with flight.span("decode_placements") as dec:
            placements, _ = decode_placements(pods, choices, counts,
                                              compiled.statics.names, strings)
            if dec:
                dec.set("shared_binds", shared_binds(pods, placements))
        prov = provenance.get_log()
        if prov is not None:
            topk = None
            if explain_lanes is not None:
                top_idx, top_scores, top_parts = explain_lanes
                p = len(pods)
                topk = {"idx": np.asarray(top_idx)[:p],
                        "scores": np.asarray(top_scores)[:p],
                        "parts": np.asarray(top_parts)[:p],
                        "names": compiled.statics.names,
                        "part_names": explain_part_names(config),
                        "sentinel": EXPLAIN_SENTINEL}
            prov.capture_batch(placements, "backend", topk=topk)
        if final_carry is not None:
            # one None-check inside; the reduction folds the POST-bind
            # carry this batch produced against the staged statics — on the
            # sharded route both trees are padded + node-sharded, so the
            # reduction runs the two-level cross-shard merge
            analytics.capture(
                shard_statics if shard_mesh is not None else statics,
                final_carry, len(compiled.statics.names), "backend",
                names=compiled.statics.names, mesh=shard_mesh)
        # e2e additionally covers host-side result materialization
        metrics.e2e_scheduling_latency.observe(
            since_in_microseconds(dispatch_start))
        return placements

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
from typing import List, NamedTuple, Optional

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
from tpusim.framework.metrics import register, since_in_microseconds
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
from tpusim.jaxe.sharding import shard_mesh, stage_node_sharded, stage_tree
from tpusim.jaxe.state import NUM_FIXED_BITS, compile_cluster, reason_strings
from tpusim.jaxe import trust
from tpusim.jaxe.trust import FAST, SHARD
from tpusim.obs import analytics
from tpusim.obs import provenance
from tpusim.obs import recorder as flight
from tpusim.obs import tracectx

log = logging.getLogger(__name__)


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


def _note_fast_fallback(why: str) -> None:
    """Surface a plan_fast rejection as observability: a labeled counter
    keyed by blocker class plus a flight-recorder instant carrying the full
    reason string."""
    key = _fast_fallback_key(why)
    register().fast_fallback.inc(key)
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


class FastRoute(NamedTuple):
    """The fast decision for one batch: the Pallas plan (None: the XLA
    scan), its kernel signature, whether AUTO must verify the kernel's
    results before they are used, and whether the route runs in AUTO."""
    plan: object = None
    sig: Optional[tuple] = None
    verify: bool = False
    auto: bool = False

    def check(self, config, compiled, cols, choices, counts, *,
              limit: Optional[int] = None, statics=None, carry=None) -> bool:
        """AUTO's check of the kernel's results: the leading pods (at most
        `limit`, where the caller ran fewer than the batch) replayed through
        the XLA scan bit for bit, then FAST's verdict. The variant pins only
        from trust.PIN_MIN_PODS replayed pods. statics/carry: the
        policy-grafted XLA trees, where the caller has them."""
        from tpusim.jaxe.fastscan import verify_against_xla

        m = min(trust.REPLAY_PODS, len(np.asarray(cols.req_cpu)),
                limit if limit is not None else trust.REPLAY_PODS)
        ok = verify_against_xla(config, compiled, cols, choices, counts, m,
                                statics=statics, carry=carry)
        return FAST.verdict(self.sig, ok, pin=m >= trust.PIN_MIN_PODS)


def fast_route(config, compiled, cols, n_pods: int, *, ptabs=None,
               placed_pods=None, refuse: Optional[str] = None,
               pinned_only: bool = False) -> FastRoute:
    """The fast decision every Pallas caller shares: FAST's mode, then
    plan_fast (in the `plan_fast` span) and the plan's signature; an
    ineligible plan is counted and logged as a fast_fallback.

    refuse: what this batch needs that only the XLA scan gives, counted as
    a fast_fallback without planning. pinned_only: the batch is too small
    to pin a variant, so in AUTO it runs the kernel only on a variant
    already pinned; with none pinned it is not even planned, and a dropped
    batch is noted as `defer`."""
    on, auto = FAST.mode()
    if not on:
        return FastRoute()
    if refuse is not None:
        _note_fast_fallback(refuse)
        return FastRoute(auto=auto)
    defer = auto and pinned_only
    if not (defer and not FAST.verified):
        from tpusim.jaxe.fastscan import plan_fast

        with flight.span("plan_fast") as psp:
            plan, why = plan_fast(config, compiled, cols,
                                  placed_pods=placed_pods, ptabs=ptabs)
            if psp:
                psp.set("pods", n_pods)
                psp.set("nodes", len(compiled.statics.names))
                if plan is not None and plan.has_interpod:
                    psp.set("interpod_doms", plan.n_topo_doms_ip)
                    psp.set("hostname_terms", plan.hostname_terms)
        if plan is None:
            _note_fast_fallback(why)
            log.info("pallas fast path ineligible (%s); using the XLA scan",
                     why)
            return FastRoute(auto=auto)
        sig = plan_signature(plan)
        route = FastRoute(plan, sig, auto and not FAST.trusted(sig), auto)
        if not (defer and route.verify):
            return route
    flight.note_auto_transition("defer")
    log.info("pallas fast path deferred: %d pods is below the "
             "self-verification threshold; using the XLA scan", n_pods)
    return FastRoute(auto=auto)


def _dispatch_sharded(config, mesh, statics, carry, xs, use_chunks,
                      scan_chunk):
    """One node-sharded dispatch: pad the node axis shard-even, place the
    trees per the mesh's node shardings, run the shard_map scan (chunked
    or single), and stamp the tpusim_shard_* telemetry. Returns
    (final_carry, choices, counts, sharded_statics) — the carry/statics
    come back padded + sharded for the analytics reduction to fold."""
    from dataclasses import replace as _dc_replace

    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpusim.jaxe.kernels import sharded_scan_fn

    sconfig = _dc_replace(config, shard_axis="node")
    n_shards = mesh.shape["node"]
    st, ca, _ = stage_node_sharded(mesh, statics, carry)
    # estimated collective payload: each pod step moves ~12 psum/pmax/pmin
    # scalars plus an n_shards-wide tie-count all_gather, 8 bytes each, on
    # every shard (the analytics/gang collectives are separate dispatches)
    n_pods = int(np.asarray(xs.req_cpu).shape[0])
    register().shard_collective_bytes.set(
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
# scripts per-dispatch exceptions/corruptions. Upgrades trust.FAST's
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

        n_pods = len(pods)

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
            flight.note_route("reference_fallback", n_pods)
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
        names = compiled.statics.names
        n_nodes = len(names)
        explain_k = provenance.requested_top_k()
        if explain_k > 0:
            from dataclasses import replace as _explain_replace

            config = _explain_replace(
                config, explain_k=min(explain_k, n_nodes))
        explain = config.explain_k > 0

        ensure_x64()
        if esp:
            esp.end()
        # fast-path decision BEFORE any device upload: when the Pallas plan
        # engages, the statics/carry/pod-column HBM transfers below would be
        # pure wasted latency on exactly the hot path the feature accelerates.
        # The score-breakdown lanes are an XLA-scan feature (the kernel
        # carries reason counts, not per-part top-k scores, DEVIATIONS.md),
        # and the analytics reduction folds the scan's final carry, which the
        # kernel never materializes. A batch below trust.PIN_MIN_PODS cannot
        # pin a variant, and running the kernel AND the XLA replay would be
        # strictly slower than plain XLA: it takes only a pinned variant.
        refuse = ("explain lanes (top-k score breakdown) route through the "
                  "XLA scan" if explain
                  else "cluster analytics rides the XLA scan's final carry"
                  if analytics.get() is not None else None)
        fast = fast_route(config, compiled, cols, n_pods, ptabs=ptabs,
                          refuse=refuse,
                          pinned_only=n_pods < trust.PIN_MIN_PODS)
        fplan, fast_sig, fast_verify, auto_mode = fast

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

        # Batches beyond TPUSIM_SCAN_CHUNK pods run through the
        # double-buffered chunked scan: pod columns stay host-side and stream
        # to HBM chunk by chunk, bit-identical to the single dispatch
        # (SURVEY.md §7 hard part 6 — 1M-pod batches).
        scan_chunk = int(os.environ.get("TPUSIM_SCAN_CHUNK", 131072))
        chunked = not explain and 0 < scan_chunk < n_pods

        def _xla_inputs():
            # (statics, carry, use_chunks, xs): the XLA scan's uploads
            return (_xla_statics(), _xla_carry(), chunked,
                    pod_columns_to_host(cols) if chunked
                    else pod_columns_to_device(cols))

        statics = carry = xs = None
        use_chunks = False
        if fplan is None:
            with flight.span("batch_upload") as usp:
                statics, carry, use_chunks, xs = _xla_inputs()
                if usp:
                    usp.set("pods", n_pods)
        # On TPU the per-pod filter→score→select→bind pipeline is one fused
        # device program, so the whole batch dispatch lands in the algorithm
        # histogram (the per-phase split of metrics.go has no device analog);
        # e2e additionally covers host-side result materialization.
        # chaos seam: scripted dispatch faults raise here (outside the fast
        # path's own try/except — an injected device death is not a Mosaic
        # lowering failure and must reach the circuit breaker, not count
        # against trust.FAST); scripted corruptions apply to the results
        # below
        injector = _CHAOS["injector"]
        _corrupt_kind = (None if injector is None
                         else injector.begin_dispatch())
        dispatch_start = perf_counter()
        dsp = flight.span("device_dispatch", "device")

        def _discard_fast_path():
            # pay the uploads the fast path deferred and rebuild the
            # XLA-scan inputs (set via nonlocal) with a fresh dispatch
            # clock; whether the path stays disabled for the process is
            # trust.FAST's call (note_failure / the verdict)
            nonlocal fplan, statics, carry, use_chunks, xs, dispatch_start
            fplan = None
            statics, carry, use_chunks, xs = _xla_inputs()
            dispatch_start = perf_counter()

        def _scan_single(carry):
            # the single-device XLA scan: (final carry, choices, counts,
            # explain lanes or None)
            if use_chunks:
                return schedule_scan_chunked(config, carry, statics, xs,
                                             scan_chunk)[:3] + (None,)
            out = schedule_scan(config, carry, statics, xs)
            return out[:3] + (out[4] if explain else None,)

        if fplan is not None:
            from tpusim.jaxe.fastscan import fast_scan

            try:
                with flight.profiled("tpusim:fast_scan"):
                    choices, counts, _adv = fast_scan(fplan)
            except Exception as exc:
                # a transient runtime error reruns this batch on the XLA
                # scan, on the same device; a lowering or compile error
                # propagates
                if not FAST.note_failure(exc):
                    raise
                _discard_fast_path()
            else:
                FAST.note_success()
                if fast_verify and not fast.check(
                        config, compiled, cols, choices, counts,
                        statics=_xla_statics(), carry=_xla_carry()):
                    # the kernel lowered but miscomputed: the verdict
                    # already disabled it process-wide; rerun on XLA
                    _discard_fast_path()
                elif auto_mode and not fast_verify:
                    # already-pinned variant ran without re-verification
                    FAST.note_reuse(str(fast_sig))
        # node-sharded route (TPUSIM_SHARDS=k > 1): the same fused scan as
        # a shard_map over a k-device node mesh
        mesh = (shard_mesh(config, "batch routed single-device")
                if fplan is None else None)
        shard_statics = None
        explain_lanes = None
        final_carry = None  # bound-and-dropped unless analytics reads it
        if fplan is None:  # fast path off, ineligible, or discarded above
            with flight.span("scan_launch", "device"), \
                    flight.profiled("tpusim:schedule_scan"):
                if mesh is not None:
                    (final_carry, choices, counts,
                     shard_statics) = _dispatch_sharded(
                         config, mesh, statics, carry, xs, use_chunks,
                         scan_chunk)
                else:
                    (final_carry, choices, counts,
                     explain_lanes) = _scan_single(carry)
        with flight.span("device_wait", "device"):
            choices = np.asarray(choices)
            counts = np.asarray(counts)
        if mesh is not None:
            # verify-then-trust (trust.SHARD): the first batch per (shard
            # count, config) replays its leading pods through the
            # single-device scan bit-for-bit; a disagreement disables the
            # sharded route for the process and this batch reruns
            # single-device
            n_shards = mesh.shape["node"]
            shard_sig = (n_shards, config)
            if SHARD.trusted(shard_sig):
                SHARD.note_reuse(str(n_shards))
            else:
                from tpusim.jaxe.fastscan import verify_against_xla

                same = verify_against_xla(
                    config, compiled, cols, choices, counts,
                    trust.REPLAY_PODS, statics=statics, carry=_xla_carry())
                if not SHARD.verdict(shard_sig, same, label=str(n_shards)):
                    metrics.shard_count.set(0)
                    mesh = None
                    shard_statics = None
                    with flight.profiled("tpusim:schedule_scan"):
                        final_carry, choices, counts, _ = _scan_single(
                            _xla_carry())
                    choices = np.asarray(choices)
                    counts = np.asarray(counts)
        if injector is not None:
            if _corrupt_kind is not None:
                from tpusim.chaos.engine import DeviceInjector

                choices, counts = DeviceInjector.corrupt(_corrupt_kind,
                                                         choices, counts)
            # structural validation always runs under chaos: out-of-range
            # choices and NaN counts never reach decode_placements
            from tpusim.chaos.engine import DeviceOutputError

            if choices.size and (int(choices.max()) >= n_nodes
                                 or int(choices.min()) < -1):
                raise DeviceOutputError(
                    f"device choice out of range [-1, {n_nodes})")
            if np.isnan(np.asarray(counts, dtype=np.float64)).any():
                raise DeviceOutputError("NaN in device reason counts")
        if fplan is not None:
            route = ("fastscan_interpret" if trust.fast_interpret()
                     else "fastscan")
        elif mesh is not None:
            route = "xla_sharded_chunked" if use_chunks else "xla_sharded"
        else:
            route = "xla_chunked" if use_chunks else "xla_scan"
        flight.note_route(route, n_pods)
        if dsp:
            dsp.set("route", route)
            dsp.set("pods", n_pods)
            if mesh is not None:
                dsp.set("shards", n_shards)
            if fast_sig is not None:
                dsp.set("sig", str(fast_sig))
            dsp.end()
        # trace-id exemplar (ISSUE 20): a dispatch-latency spike on the
        # dashboard resolves to the exact device-dispatch trace
        _ctx = tracectx.current()
        exemplar = _ctx.trace_id if _ctx is not None else None
        metrics.backend_dispatch_latency.observe(
            since_in_microseconds(dispatch_start), exemplar=exemplar)
        metrics.scheduling_algorithm_latency.observe(
            since_in_microseconds(dispatch_start))

        strings = reason_strings(compiled.scalar_names)
        with flight.span("decode_placements") as dec:
            placements, _ = decode_placements(pods, choices, counts, names,
                                              strings)
            if dec:
                dec.set("shared_binds", shared_binds(pods, placements))
        prov = provenance.get_log()
        if prov is not None:
            topk = None
            if explain_lanes is not None:
                top_idx, top_scores, top_parts = explain_lanes
                topk = {"idx": np.asarray(top_idx)[:n_pods],
                        "scores": np.asarray(top_scores)[:n_pods],
                        "parts": np.asarray(top_parts)[:n_pods],
                        "names": names,
                        "part_names": explain_part_names(config),
                        "sentinel": EXPLAIN_SENTINEL}
            prov.capture_batch(placements, "backend", topk=topk)
        if final_carry is not None:
            # one None-check inside; the reduction folds the POST-bind
            # carry this batch produced against the staged statics — on the
            # sharded route both trees are padded + node-sharded, so the
            # reduction runs the two-level cross-shard merge
            analytics.capture(
                shard_statics if mesh is not None else statics,
                final_carry, n_nodes, "backend",
                names=names, mesh=mesh)
        # e2e additionally covers host-side result materialization
        metrics.e2e_scheduling_latency.observe(
            since_in_microseconds(dispatch_start))
        return placements

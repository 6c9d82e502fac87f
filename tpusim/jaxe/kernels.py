"""Device kernels: the fused filter→score→select→bind pipeline.

Reference mapping:
  findNodesThatFit (generic_scheduler.go:289-377)  -> staged fail masks + reason bits
  PrioritizeNodes  (generic_scheduler.go:542-680)  -> vectorized scores + masked normalize
  selectHost       (generic_scheduler.go:183-198)  -> masked argmax + round-robin tie pick
  assume/bind      (scheduler.go:431-497)          -> scatter-add into the carry

Execution mode (SURVEY.md §7 step 5): schedule_scan — EXACT: one lax.scan
step per pod; pod t's bind is seen by pod t+1, identical to the Go loop.
(A "wavefront" approximate mode — K pods vmapped against a frozen snapshot
per wave — existed through round 4 and was removed: measured on the
BASELINE.md phase shape it was slower than the exact scan at every K on
CPU AND overestimated schedulable capacity by 8-75% under saturation,
because pods in a wave don't see each other's binds; see BASELINE.md
"wavefront verdict".)
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tpusim.engine.predicates import (
    CHECK_NODE_DISK_PRESSURE_PRED,
    CHECK_NODE_LABEL_PRESENCE_PRED,
    CHECK_NODE_MEMORY_PRESSURE_PRED,
    CHECK_NODE_UNSCHEDULABLE_PRED,
    CHECK_SERVICE_AFFINITY_PRED,
    CHECK_VOLUME_BINDING_PRED,
    DEFAULT_MAXPD_LIMITS,
    GENERAL_PRED,
    HOSTNAME_PRED,
    MATCH_INTERPOD_AFFINITY_PRED,
    MATCH_NODE_SELECTOR_PRED,
    MAX_AZURE_DISK_VOLUME_COUNT_PRED,
    MAX_EBS_VOLUME_COUNT_PRED,
    MAX_GCE_PD_VOLUME_COUNT_PRED,
    NO_DISK_CONFLICT_PRED,
    NO_VOLUME_ZONE_CONFLICT_PRED,
    POD_FITS_HOST_PORTS_PRED,
    POD_FITS_RESOURCES_PRED,
    POD_TOLERATES_NODE_NO_EXECUTE_TAINTS_PRED,
    POD_TOLERATES_NODE_TAINTS_PRED,
)
from tpusim.jaxe.packing import (
    GANG_RACK_SHIFT,
    GANG_SCORE_MASK,
    GANG_ZONE_SHIFT,
    TIE_BITS as _ANALYTICS_TIE_BITS,
    encode_gang_rank,
    encode_topk_keys,
)
from tpusim.jaxe.state import (
    BIT_AFFINITY_NOT_MATCH,
    BIT_AFFINITY_RULES,
    BIT_ANTI_AFFINITY_RULES,
    BIT_DISK_CONFLICT,
    BIT_DISK_PRESSURE,
    BIT_MAX_VOLUME_COUNT,
    BIT_VOLUME_ZONE_CONFLICT,
    BIT_EXISTING_ANTI_AFFINITY,
    BIT_HOSTNAME_MISMATCH,
    BIT_HOST_PORTS,
    BIT_INSUFFICIENT_CPU,
    BIT_INSUFFICIENT_EPHEMERAL,
    BIT_INSUFFICIENT_GPU,
    BIT_INSUFFICIENT_MEMORY,
    BIT_INSUFFICIENT_PODS,
    BIT_MEMORY_PRESSURE,
    BIT_NODE_LABEL_PRESENCE,
    BIT_NODE_SELECTOR_MISMATCH,
    BIT_NODE_UNSCHEDULABLE,
    BIT_SERVICE_AFFINITY,
    BIT_TAINTS_NOT_TOLERATED,
    NUM_FIXED_BITS,
    CompiledCluster,
    PodColumns,
)

MAX_PRIORITY = 10
AVOID_PODS_WEIGHT = 10000


class Carry(NamedTuple):
    used_cpu: jnp.ndarray      # [N] int64
    used_mem: jnp.ndarray
    used_gpu: jnp.ndarray
    used_eph: jnp.ndarray
    used_scalar: jnp.ndarray   # [N, S]
    nonzero_cpu: jnp.ndarray
    nonzero_mem: jnp.ndarray
    pod_count: jnp.ndarray
    presence: jnp.ndarray      # [G, N] int32 — pods per (group, node)
    presence_dom: jnp.ndarray  # [G, K, D] int32 — presence summed per topo domain
    used_vols: jnp.ndarray     # [N, V] bool — MaxPD volume ids mounted per node
    # ServiceAffinity (policy): per first-service-sig lock = node index of the
    # designated first matching pod once it binds; -1 = not yet locked,
    # -2 = permanently unpinned (the first pod's node is unknowable)
    sa_lock: jnp.ndarray       # [Fd] int32
    rr: jnp.ndarray            # scalar int64 — selectHost's lastNodeIndex


class Statics(NamedTuple):
    alloc_cpu: jnp.ndarray
    alloc_mem: jnp.ndarray
    alloc_gpu: jnp.ndarray
    alloc_eph: jnp.ndarray
    allowed_pods: jnp.ndarray
    alloc_scalar: jnp.ndarray
    cond_fail_bits: jnp.ndarray
    mem_pressure: jnp.ndarray
    disk_pressure: jnp.ndarray
    selector_ok: jnp.ndarray
    taint_ok: jnp.ndarray
    taint_ok_noexec: jnp.ndarray
    intolerable: jnp.ndarray
    affinity_count: jnp.ndarray
    avoid_score: jnp.ndarray
    host_ok: jnp.ndarray
    # pod-group tables (state.GroupTables; zero-size-semantics dummies when off)
    port_conflict: jnp.ndarray
    port_sig: jnp.ndarray
    disk_conflict: jnp.ndarray
    disk_sig: jnp.ndarray
    vol_mask: jnp.ndarray
    vol_type: jnp.ndarray
    zone_ok: jnp.ndarray
    ss_rows: jnp.ndarray
    ss_sig: jnp.ndarray
    saa_rows: jnp.ndarray
    saa_sig: jnp.ndarray
    term_match: jnp.ndarray
    zone_dom: jnp.ndarray
    topo_dom: jnp.ndarray
    aff_valid: jnp.ndarray
    aff_err: jnp.ndarray
    aff_empty: jnp.ndarray
    aff_term: jnp.ndarray
    aff_key: jnp.ndarray
    aff_hostname: jnp.ndarray
    aff_self: jnp.ndarray
    aff_unplaced: jnp.ndarray
    anti_valid: jnp.ndarray
    anti_err: jnp.ndarray
    anti_empty: jnp.ndarray
    anti_term: jnp.ndarray
    anti_key: jnp.ndarray
    anti_hostname: jnp.ndarray
    pref_w: jnp.ndarray
    pref_term: jnp.ndarray
    pref_key: jnp.ndarray
    # policy-configured custom plugin rows (trivial when no policy):
    #   label_ok   — [L, N] pass masks for the policy's label-presence
    #                predicates; PolicySpec.label_rows names each row's
    #                ordering slot (a custom registered under a standard
    #                PREDICATES_ORDERING name evaluates at that position in
    #                the host's _predicate_key_order; other names run after
    #                the fixed ordering, folded into one tail row)
    #   label_prio — pre-weighted sum of NodeLabel/LabelPreference priority
    #                rows (node_label.go; no normalize pass)
    #   image_score — [Si, N] ImageLocalityPriority map scores per interned
    #                pod-image-set signature (image_locality.go; static)
    label_ok: jnp.ndarray
    label_prio: jnp.ndarray
    image_score: jnp.ndarray
    #   saa_dom — [E, N] per-ServiceAntiAffinity-entry node label-value domain
    #             ids (0 = label absent), from jaxe.policyc
    #   ServiceAffinity predicates (policy): sa_val [La, N] interned node
    #   values per policy affinity label (0 = absent; label rows concatenate
    #   the entries' label lists, segmented by PolicySpec.sa_segs); sa_pin
    #   [Cs, La] the pod's own nodeSelector pins in the same value space
    #   (0 = label unpinned; a pinned value no node carries interns to a
    #   fresh id that matches nothing)
    saa_dom: jnp.ndarray
    sa_val: jnp.ndarray
    sa_pin: jnp.ndarray


class PodX(NamedTuple):
    """One pod's columns (scan xs slice)."""

    req_cpu: jnp.ndarray
    req_mem: jnp.ndarray
    req_gpu: jnp.ndarray
    req_eph: jnp.ndarray
    req_scalar: jnp.ndarray    # [S]
    nz_cpu: jnp.ndarray
    nz_mem: jnp.ndarray
    zero_request: jnp.ndarray
    best_effort: jnp.ndarray
    sel_id: jnp.ndarray
    tol_id: jnp.ndarray
    aff_id: jnp.ndarray
    avoid_id: jnp.ndarray
    host_id: jnp.ndarray
    group_id: jnp.ndarray
    img_id: jnp.ndarray
    # ServiceAffinity (policy): own-nodeSelector-pin signature
    sa_self_id: jnp.ndarray


@dataclass(frozen=True)
class PolicySpec:
    """Compile-time image of a scheduler Policy (api/types.go:52-77) for the
    device engine: which standard predicates run and each score component's
    weight. Built by jaxe.policyc.compile_policy; None on the provider paths
    (= provider defaults). Hashable so EngineConfig stays a valid jit static.

    pred_keys: frozenset of predicate names from PREDICATES_ORDERING that the
    policy enables (customs are carried via the has_label_* flags + Statics
    rows, not names). CheckNodeCondition runs regardless — it is mandatory
    (build_predicates unions mandatory_fit_predicates)."""

    pred_keys: frozenset
    w_least: int = 0
    w_most: int = 0
    w_balanced: int = 0
    w_node_aff: int = 0
    w_taint: int = 0
    w_avoid: int = 0           # NodePreferAvoidPodsPriority policy weight
    w_spread: int = 0
    w_interpod: int = 0
    w_image: int = 0           # ImageLocalityPriority (table-driven)
    # ServiceAntiAffinity custom priorities: one weight per entry, parallel
    # to the Statics.saa_dom rows (selector_spreading.go:176-280)
    saa_weights: tuple = ()
    # ServiceAffinity predicates (policy): one slot per entry — a canonical
    # PREDICATES_ORDERING name evaluates at that position; any other policy
    # name runs after the fixed ordering at its alphabetical tail position
    # ("tail:<k>"). sa_segs holds each entry's label count, segmenting the
    # concatenated Statics.sa_val label rows. sa_enabled gates the
    # first-matching-pod lock updates in the bind scatter.
    sa_enabled: bool = False
    sa_slots: tuple = ()
    sa_segs: tuple = ()
    # 1.0 PodFitsPorts alias: tail slots ("tail:<k>") where the
    # port-conflict stage runs again (the host evaluates registry keys
    # outside predicates.Ordering() at the alphabetical tail)
    ports_slots: tuple = ()
    # first-failure reason selection becomes collect-all-failures
    # (generic_scheduler.go alwaysCheckAllPredicates)
    always_check_all: bool = False
    # one entry per Statics.label_ok row: the PREDICATES_ORDERING name whose
    # slot the row evaluates at, or "tail:<k>" for its alphabetical position
    # after the fixed ordering
    label_rows: tuple = ()
    has_label_prio: bool = False


@dataclass(frozen=True)
class EngineConfig:
    """Static (compile-time) provider configuration."""

    most_requested: bool = False  # LeastRequested -> MostRequested swap (TD/autoscaler)
    num_reason_bits: int = NUM_FIXED_BITS
    # pod-group features — compiled in only when the workload needs them
    has_ports: bool = False
    has_services: bool = False
    has_interpod: bool = False
    has_disk_conflict: bool = False
    has_maxpd: bool = False
    has_vol_zone: bool = False
    maxpd_limits: tuple = DEFAULT_MAXPD_LIMITS  # (EBS, GCE PD, AzureDisk)
    hard_weight: int = 10         # HardPodAffinitySymmetricWeight
    n_topo_doms: int = 1          # segment counts (incl. the invalid-0 bucket)
    n_zone_doms: int = 1
    # lax.scan unroll factor for the exact sequential mode: semantically
    # identical, amortizes per-step dispatch overhead at the cost of compile
    # time; tune via TPUSIM_SCAN_UNROLL (backend reads the env)
    scan_unroll: int = 1
    # policy-as-data overrides (None = the named provider's defaults)
    policy: PolicySpec = None
    # segment count for the ServiceAntiAffinity label domains (incl. the
    # invalid-0 bucket); set by the backend from the compiled node labels
    n_saa_doms: int = 1
    # decision provenance (ISSUE 13): when > 0 the scan additionally emits,
    # per pod, the top-k candidate nodes by final score with each node's
    # per-priority score contributions (explain_part_names order). Static,
    # so explain_k=0 traces are byte-identical to pre-provenance programs —
    # zero cost when disabled.
    explain_k: int = 0
    # node-axis sharding (ISSUE 16): when set, the fused step runs inside
    # shard_map over a mesh axis of this name — every global node reduction
    # becomes a collective and selection merges across shards bit-identically
    # (integer arithmetic only, so the collective sums/maxes are exact and
    # order-independent). None (the default) emits NO collectives: the trace
    # is byte-identical to the single-device engine.
    shard_axis: str = None


# ---------------------------------------------------------------------------
# Axis registries: for each pytree field, a tuple naming every array axis.
# sharding.py pads/shards the "node" axis; whatif.py unifies every *other*
# named axis to a common cross-scenario size. PodX omits its leading pod axis.
# Adding a field to a NamedTuple requires only a matching entry here.
# ---------------------------------------------------------------------------

STATICS_AXES = dict(
    alloc_cpu=("node",), alloc_mem=("node",), alloc_gpu=("node",),
    alloc_eph=("node",), allowed_pods=("node",), alloc_scalar=("node", "scalar"),
    cond_fail_bits=("node",), mem_pressure=("node",), disk_pressure=("node",),
    selector_ok=("sig_sel", "node"), taint_ok=("sig_tol", "node"),
    taint_ok_noexec=("sig_tol", "node"), intolerable=("sig_tol", "node"), affinity_count=("sig_aff", "node"),
    avoid_score=("sig_avoid", "node"), host_ok=("sig_host", "node"),
    port_conflict=("port_sig", "port_sig"), port_sig=("group",),
    disk_conflict=("disk_sig", "disk_sig"), disk_sig=("group",),
    vol_mask=("group", "vol_id"), vol_type=("vol_id", "vol_filter"),
    zone_ok=("group", "node"),
    ss_rows=("spread_sig", "group"), ss_sig=("group",),
    saa_rows=("saa_sig", "group"), saa_sig=("group",),
    term_match=("term_sig", "group"),
    zone_dom=("node",), topo_dom=("topo_key", "node"),
    aff_valid=("group", "aff_term"), aff_err=("group",),
    aff_empty=("group", "aff_term"), aff_term=("group", "aff_term"),
    aff_key=("group", "aff_term"), aff_hostname=("group", "aff_term"),
    aff_self=("group", "aff_term"), aff_unplaced=("group", "aff_term"),
    anti_valid=("group", "anti_term"), anti_err=("group",),
    anti_empty=("group", "anti_term"), anti_term=("group", "anti_term"),
    anti_key=("group", "anti_term"), anti_hostname=("group", "anti_term"),
    pref_w=("group", "pref_term"), pref_term=("group", "pref_term"),
    pref_key=("group", "pref_term"),
    label_ok=("label_pred", "node"), label_prio=("node",),
    image_score=("sig_img", "node"), saa_dom=("saa_entry", "node"),
    sa_val=("sa_label", "node"),
    sa_pin=("sig_sa_self", "sa_label"),
)
CARRY_AXES = dict(
    used_cpu=("node",), used_mem=("node",), used_gpu=("node",), used_eph=("node",),
    used_scalar=("node", "scalar"), nonzero_cpu=("node",), nonzero_mem=("node",),
    pod_count=("node",), presence=("group", "node"),
    presence_dom=("group", "topo_key", "topo_dom"),
    used_vols=("node", "vol_id"), sa_lock=("saa_sig",), rr=(),
)
PODX_AXES = dict(
    req_cpu=(), req_mem=(), req_gpu=(), req_eph=(), req_scalar=("scalar",),
    nz_cpu=(), nz_mem=(), zero_request=(), best_effort=(), sel_id=(),
    tol_id=(), aff_id=(), avoid_id=(), host_id=(), group_id=(), img_id=(),
    sa_self_id=(),
)
# Node-axis pad fill per field (default 0). Exception: cond_fail_bits is
# special-cased in sharding._pad_node_tree with a lazily-built infeasible
# sentinel (1<<62 needs x64 enabled), so padded nodes can never be selected.
PAD_FILLS: dict = {}


def scan_unroll_from_env() -> int:
    import os

    try:
        return max(1, int(os.environ.get("TPUSIM_SCAN_UNROLL", "1")))
    except ValueError:
        return 1


def config_for(compiled_list, most_requested: bool, num_reason_bits: int,
               hard_weight: int = 10) -> EngineConfig:
    """Union EngineConfig across one or more CompiledClusters (the what-if
    batch shares one jitted program; zero-filled tables are no-ops)."""
    limits = [c.maxpd_limits for c in compiled_list if c.has_maxpd]
    return EngineConfig(
        most_requested=most_requested,
        num_reason_bits=num_reason_bits,
        has_ports=any(c.has_ports for c in compiled_list),
        has_services=any(c.has_services for c in compiled_list),
        has_interpod=any(c.has_interpod for c in compiled_list),
        has_disk_conflict=any(c.has_disk_conflict for c in compiled_list),
        has_maxpd=any(c.has_maxpd for c in compiled_list),
        has_vol_zone=any(c.has_vol_zone for c in compiled_list),
        maxpd_limits=limits[0] if limits else DEFAULT_MAXPD_LIMITS,
        hard_weight=hard_weight,
        n_topo_doms=max(c.n_topo_doms for c in compiled_list),
        n_zone_doms=max(c.n_zone_doms for c in compiled_list),
        scan_unroll=scan_unroll_from_env())


def statics_to_host(compiled: CompiledCluster) -> Statics:
    """Statics pytree over host numpy arrays (no device transfer)."""
    s, t, gt = compiled.statics, compiled.tables, compiled.groups
    return Statics(
        alloc_cpu=s.alloc_cpu, alloc_mem=s.alloc_mem,
        alloc_gpu=s.alloc_gpu, alloc_eph=s.alloc_eph,
        allowed_pods=s.allowed_pods, alloc_scalar=s.alloc_scalar,
        cond_fail_bits=s.cond_fail_bits, mem_pressure=s.mem_pressure,
        disk_pressure=s.disk_pressure,
        selector_ok=t.selector_ok, taint_ok=t.taint_ok,
        taint_ok_noexec=t.taint_ok_noexec, intolerable=t.intolerable, affinity_count=t.affinity_count,
        avoid_score=t.avoid_score, host_ok=t.host_ok,
        port_conflict=gt.port_conflict, port_sig=gt.port_sig,
        disk_conflict=gt.disk_conflict, disk_sig=gt.disk_sig,
        vol_mask=gt.vol_mask, vol_type=gt.vol_type, zone_ok=gt.zone_ok,
        ss_rows=gt.ss_rows, ss_sig=gt.ss_sig,
        saa_rows=gt.saa_rows, saa_sig=gt.saa_sig, term_match=gt.term_match,
        zone_dom=gt.zone_dom, topo_dom=gt.topo_dom,
        aff_valid=gt.aff_valid, aff_err=gt.aff_err, aff_empty=gt.aff_empty,
        aff_term=gt.aff_term, aff_key=gt.aff_key,
        aff_hostname=gt.aff_hostname, aff_self=gt.aff_self,
        aff_unplaced=gt.aff_unplaced,
        anti_valid=gt.anti_valid, anti_err=gt.anti_err,
        anti_empty=gt.anti_empty, anti_term=gt.anti_term,
        anti_key=gt.anti_key, anti_hostname=gt.anti_hostname,
        pref_w=gt.pref_w, pref_term=gt.pref_term, pref_key=gt.pref_key,
        # trivial policy rows; jaxe.policyc overwrites them via _replace
        label_ok=np.ones((1, len(s.alloc_cpu)), dtype=bool),
        label_prio=np.zeros(len(s.alloc_cpu), dtype=np.int64),
        image_score=np.zeros((1, len(s.alloc_cpu)), dtype=np.int64),
        saa_dom=np.zeros((1, len(s.alloc_cpu)), dtype=np.int32),
        sa_val=np.zeros((1, len(s.alloc_cpu)), dtype=np.int32),
        sa_pin=np.zeros((1, 1), dtype=np.int32))


def _presence_dom_init(presence: np.ndarray, topo_dom: np.ndarray,
                       n_doms: int) -> np.ndarray:
    """presence_dom[g, k, d] = sum of presence[g, n] over nodes in domain d."""
    g, _ = presence.shape
    k = topo_dom.shape[0]
    pd = np.zeros((g, k, n_doms), dtype=np.int32)
    for ki in range(k):
        np.add.at(pd[:, ki, :], (slice(None), topo_dom[ki]), presence)
    return pd


def carry_init_host(compiled: CompiledCluster) -> Carry:
    """Initial carry over host numpy arrays (no device transfer)."""
    d, gt = compiled.dynamic, compiled.groups
    return Carry(
        used_cpu=d.used_cpu, used_mem=d.used_mem, used_gpu=d.used_gpu,
        used_eph=d.used_eph, used_scalar=d.used_scalar,
        nonzero_cpu=d.nonzero_cpu, nonzero_mem=d.nonzero_mem,
        pod_count=d.pod_count,
        presence=gt.presence,
        presence_dom=_presence_dom_init(gt.presence, gt.topo_dom,
                                        compiled.n_topo_doms),
        used_vols=gt.used_vols_init,
        sa_lock=np.full(gt.saa_rows.shape[0], -1, dtype=np.int32),
        rr=np.int64(0))


def pod_columns_to_host(cols: PodColumns) -> PodX:
    """PodX pytree over host numpy arrays (no device transfer)."""
    return PodX(
        req_cpu=cols.req_cpu, req_mem=cols.req_mem, req_gpu=cols.req_gpu,
        req_eph=cols.req_eph, req_scalar=cols.req_scalar,
        nz_cpu=cols.nz_cpu, nz_mem=cols.nz_mem,
        zero_request=cols.zero_request, best_effort=cols.best_effort,
        sel_id=cols.sel_id, tol_id=cols.tol_id, aff_id=cols.aff_id,
        avoid_id=cols.avoid_id, host_id=cols.host_id, group_id=cols.group_id,
        img_id=cols.img_id, sa_self_id=cols.sa_self_id)


def _tree_to_device(tree):
    return type(tree)(*(jnp.asarray(a) for a in tree))


def statics_to_device(compiled: CompiledCluster) -> Statics:
    return _tree_to_device(statics_to_host(compiled))


def carry_init(compiled: CompiledCluster) -> Carry:
    return _tree_to_device(carry_init_host(compiled))


def pod_columns_to_device(cols: PodColumns) -> PodX:
    return _tree_to_device(pod_columns_to_host(cols))


def _ratio_score(requested, capacity, most: bool):
    """least_requested.go:41-52 / most_requested.go:44-55, elementwise."""
    valid = (capacity > 0) & (requested <= capacity)
    if most:
        return jnp.where(valid, (requested * MAX_PRIORITY) // jnp.maximum(capacity, 1), 0)
    return jnp.where(
        valid, ((capacity - requested) * MAX_PRIORITY) // jnp.maximum(capacity, 1), 0)


# --- exact 128-bit integer helpers (4x32-bit limbs held in uint64) ---------
# Score arithmetic must be EXACT, not float64: TPUs have no native f64 (XLA
# emulates it), and emulated divisions round differently from the host's IEEE
# f64, flipping scores at integer boundaries — observed as placement-hash
# divergence between the CPU and TPU runs of the same workload. Products like
# req_cpu*alloc_mem overflow int64 for large-memory nodes, so the balanced-
# allocation score runs on 128-bit limbs (DEVIATIONS.md #16).

_M32 = np.uint64(0xFFFFFFFF)


def _mul_limbs(a, b):
    """Exact 128-bit product of two nonnegative int64 arrays as 4x32-bit
    limbs (least-significant first), each limb stored in a uint64."""
    a = a.astype(jnp.uint64)
    b = b.astype(jnp.uint64)
    ah, al = a >> 32, a & _M32
    bh, bl = b >> 32, b & _M32
    ll = al * bl
    lh = al * bh
    hl = ah * bl
    hh = ah * bh
    l0 = ll & _M32
    c1 = (ll >> 32) + (lh & _M32) + (hl & _M32)
    l1 = c1 & _M32
    c2 = (c1 >> 32) + (lh >> 32) + (hl >> 32) + (hh & _M32)
    l2 = c2 & _M32
    l3 = (c2 >> 32) + (hh >> 32)  # < 2^32: the full product is < 2^126
    return (l0, l1, l2, l3)


def _scale_limbs(limbs, k: int):
    """limbs * k for a small Python int k (k <= 10); returns len+1 limbs."""
    k64 = np.uint64(k)
    out = []
    carry = jnp.zeros_like(limbs[0])
    for li in limbs:
        v = li * k64 + carry  # < 2^32 * 10 + carry: fits uint64 easily
        out.append(v & _M32)
        carry = v >> 32
    out.append(carry)
    return tuple(out)


def _ge_limbs(x, y):
    """x >= y, lexicographic over equal-length limb tuples (LSB first)."""
    ge = jnp.ones_like(x[0], dtype=bool)
    for xi, yi in zip(x, y):  # LSB -> MSB; the last differing limb decides
        ge = (xi > yi) | ((xi == yi) & ge)
    return ge


def _sub_limbs(x, y):
    """x - y over 4-limb values, requiring x >= y elementwise."""
    base = np.uint64(1) << np.uint64(32)
    out = []
    borrow = jnp.zeros_like(x[0])
    for xi, yi in zip(x, y):
        need = yi + borrow  # <= 2^32: no overflow
        under = xi < need
        out.append(jnp.where(under, xi + base - need, xi - need))
        borrow = under.astype(jnp.uint64)
    return tuple(out)


def _balanced_score(req_cpu, req_mem, alloc_cpu, alloc_mem):
    """balanced_resource_allocation.go:39-63 in exact rational arithmetic.

    score = floor(10 * (den - |rc*am - rm*ac|) / den), den = ac*am — the same
    quantity Go computes as int64((1-|cpuFrac-memFrac|)*10) in float64, here
    evaluated exactly on 128-bit limbs: score = #{t in 0..9 : 10*num <= t*den}
    (t*den >= 10*num  <=>  t/10 >= num/den  counts each score unit)."""
    p1 = _mul_limbs(req_cpu, alloc_mem)
    p2 = _mul_limbs(req_mem, alloc_cpu)
    swap = _ge_limbs(p1, p2)
    hi = tuple(jnp.where(swap, a, b) for a, b in zip(p1, p2))
    lo = tuple(jnp.where(swap, b, a) for a, b in zip(p1, p2))
    num10 = _scale_limbs(_sub_limbs(hi, lo), 10)
    den = _mul_limbs(alloc_cpu, alloc_mem)
    score = jnp.zeros(req_cpu.shape, dtype=jnp.int64)
    for t in range(10):
        score = score + _ge_limbs(_scale_limbs(den, t), num10).astype(jnp.int64)
    zero = ((alloc_cpu == 0) | (req_cpu >= alloc_cpu)
            | (alloc_mem == 0) | (req_mem >= alloc_mem))
    return jnp.where(zero, 0, score)


def _seg_rows(values, doms, num_segments: int):
    """Row-wise segment sums: [T, N] values × [T, N] domain ids -> [T, D]."""
    return jax.vmap(
        lambda v, d: jax.ops.segment_sum(v, d, num_segments=num_segments)
    )(values, doms)


def policy_weights(ps, most_requested: bool) -> tuple:
    """The score-component weight table (generic_scheduler.go:631-639),
    shared by the XLA scan and the Pallas fast kernel so the ps-None
    provider defaults (the most_requested swap, AVOID_PODS_WEIGHT) can
    never drift between the two engines: (least, most, balanced, node_aff,
    taint, avoid, spread, interpod)."""
    if ps is None:
        w_least, w_most = (0, 1) if most_requested else (1, 0)
        return (w_least, w_most, 1, 1, 1, AVOID_PODS_WEIGHT, 1, 1)
    return (ps.w_least, ps.w_most, ps.w_balanced, ps.w_node_aff,
            ps.w_taint, ps.w_avoid, ps.w_spread, ps.w_interpod)


# Any real node score is a small weighted sum of 0..10*weight components;
# masking infeasible nodes to -(1<<62) before the explain top_k leaves a
# comfortable decode threshold at -(1<<61): a top-k row scoring at or below
# it is padding from fewer-than-k feasible nodes, not a candidate.
EXPLAIN_SENTINEL = -(1 << 61)


def explain_part_names(config: EngineConfig) -> list:
    """Provider-priority names for the explain lanes' part columns, in the
    exact order _evaluate's score section appends them. Must mirror that
    section's static gating — tests/test_provenance.py locks the two
    together by summing parts back to the emitted top-k scores."""
    ps = config.policy
    (w_least, w_most, w_balanced, w_node_aff, w_taint, w_avoid, w_spread,
     w_interpod) = policy_weights(ps, config.most_requested)
    names = []
    if w_least:
        names.append("LeastRequestedPriority")
    if w_most:
        names.append("MostRequestedPriority")
    if w_balanced:
        names.append("BalancedResourceAllocation")
    if w_node_aff:
        names.append("NodeAffinityPriority")
    if w_taint:
        names.append("TaintTolerationPriority")
    if w_avoid:
        names.append("NodePreferAvoidPodsPriority")
    if ps is not None and ps.has_label_prio:
        names.append("NodeLabelPriority")
    if ps is not None and ps.w_image:
        names.append("ImageLocalityPriority")
    if ps is not None and ps.saa_weights:
        names.append("ServiceAntiAffinityPriority")
    if config.has_services and w_spread:
        names.append("SelectorSpreadPriority")
    if config.has_interpod and w_interpod:
        names.append("InterPodAffinityPriority")
    return names


# --- node-axis collectives (ISSUE 16) --------------------------------------
# Every cross-node reduction in _evaluate/_select funnels through these four
# helpers. With axis=None they are identity wrappers (the single-device trace
# is untouched); with a mesh axis name they append the matching collective.
# All reduced quantities are integers (or integer-valued f64 counts below
# 2^53), so psum/pmax/pmin across shards are exact and order-independent —
# the basis for the bit-identical cross-shard claim. int64 max/min reduce
# as int32 word pairs (_pmax_i64).

def _ax_sum(v, axis):
    return v if axis is None else jax.lax.psum(v, axis)


def _ax_max(v, axis):
    if axis is None:
        return v
    if v.dtype == jnp.int64:
        return _pmax_i64(v, axis)
    return jax.lax.pmax(v, axis)


def _ax_min(v, axis):
    if axis is None:
        return v
    if v.dtype == jnp.int64:
        return ~_pmax_i64(~v, axis)  # ~ reverses int64 order exactly
    return jax.lax.pmin(v, axis)


def _pmax_i64(v, axis):
    """Exact int64 pmax from two int32 pmaxes: a TPU lowers no 64-bit
    all-reduce but Sum. The signed high words go first; the low words,
    shifted into signed order, then compete only among the shards that
    hold the maximal high word."""
    hi = (v >> 32).astype(jnp.int32)
    lo = ((v & 0xFFFFFFFF) - (1 << 31)).astype(jnp.int32)
    hi_max = jax.lax.pmax(hi, axis)
    lo_max = jax.lax.pmax(
        jnp.where(hi == hi_max, lo, jnp.iinfo(jnp.int32).min), axis)
    return ((hi_max.astype(jnp.int64) << 32)
            + (lo_max.astype(jnp.int64) + (1 << 31)))


def _ax_any(v, axis):
    if axis is None:
        return v
    return jax.lax.pmax(v.astype(jnp.int32), axis) != 0


def _evaluate(config: EngineConfig, carry: Carry, st: Statics, x: PodX):
    """Filter + score one pod against the carried aggregates.

    Returns (feasible[N], reason_bits[N], score[N], n_feasible).

    With config.policy set, stages/components are statically gated to the
    policy's predicate set and priority weights (factory.go CreateFromConfig);
    stage order always follows PREDICATES_ORDERING so first-failure reason
    selection matches the host engine's short-circuit."""
    ps = config.policy
    en = ps.pred_keys if ps is not None else None
    ax = config.shard_axis

    def on(name):
        # None = the provider's default predicate set (the full pipeline)
        return en is None or name in en

    # ---- filter: staged fail masks in predicatesOrdering ----
    # CheckNodeCondition is mandatory (build_predicates always unions it in);
    # CheckNodeUnschedulable adds nothing on the device: the condition bits
    # already carry spec.unschedulable and fail first with the same reason
    fail_cond = st.cond_fail_bits != 0
    stages = [(fail_cond, st.cond_fail_bits)]
    if (ps is not None and ps.always_check_all and en is not None
            and CHECK_NODE_UNSCHEDULABLE_PRED in en):
        # with always-check-all, a registered CheckNodeUnschedulable emits
        # the unschedulable reason a SECOND time beyond the mandatory
        # condition check (both run; same string) — the count-mode histogram
        # below sums stage firings, so a duplicate stage reproduces the
        # host's doubled occurrence exactly
        unsched = (st.cond_fail_bits
                   & (jnp.int64(1) << BIT_NODE_UNSCHEDULABLE)) != 0
        stages.append((unsched, jnp.int64(1) << BIT_NODE_UNSCHEDULABLE))

    # policy label-presence predicates evaluate at the ordering slot of the
    # name they were registered under (the host's _predicate_key_order slots
    # any custom key whose name appears in PREDICATES_ORDERING); "" = tail
    label_at: dict = {}
    if ps is not None:
        for i, slot in enumerate(ps.label_rows):
            label_at.setdefault(slot, []).append(i)

    if ps is not None and ps.sa_slots:
        # ServiceAffinity predicates (predicates.py check_service_affinity),
        # shared prelude: the candidate node must match (a) the labels the
        # pod pins via its own nodeSelector and (b), for the remaining
        # entry labels, the values on the locked first-service-pod's node —
        # when a lock exists and the locked node carries the label. The
        # lock (a node index) is entry-independent (same first matching
        # pod); only the label segments differ per entry.
        _sa_f = st.saa_sig[x.group_id]
        _sa_lock = carry.sa_lock[_sa_f]
        _sa_li = jnp.maximum(_sa_lock, 0)
        _sa_pin = st.sa_pin[x.sa_self_id]                    # [La]
        _sa_unres = _sa_pin == 0
        _sa_own_l = _sa_unres[:, None] | (st.sa_val == _sa_pin[:, None])
        _sa_locked = st.sa_val[:, _sa_li]                    # [La]
        _sa_pinned = _sa_unres & (_sa_locked > 0)
        _sa_lock_l = (~_sa_pinned[:, None]
                      | (st.sa_val == _sa_locked[:, None]))  # [La, N]
        _sa_off = [0]
        for seg in ps.sa_segs:
            _sa_off.append(_sa_off[-1] + seg)

    def sa_fail(e):
        l0, l1 = _sa_off[e], _sa_off[e + 1]
        own_ok = jnp.all(_sa_own_l[l0:l1], axis=0)
        lock_ok = jnp.all(_sa_lock_l[l0:l1], axis=0)
        ok = own_ok & (lock_ok | (_sa_lock < 0))
        return ~ok

    def emit_label(slot_name):
        for i in label_at.get(slot_name, ()):
            stages.append((~st.label_ok[i],
                           jnp.int64(1) << BIT_NODE_LABEL_PRESENCE))
        if ps is not None:
            for e, slot in enumerate(ps.sa_slots):
                if slot == slot_name:
                    stages.append((sa_fail(e),
                                   jnp.int64(1) << BIT_SERVICE_AFFINITY))
            if slot_name in ps.ports_slots and config.has_ports:
                # the PodFitsPorts tail alias re-emits the port stage here
                # (port_bad is defined by the time tail slots run; a
                # port-free workload has nothing to re-check)
                stages.append((port_bad, jnp.int64(1) << BIT_HOST_PORTS))

    emit_label(CHECK_NODE_UNSCHEDULABLE_PRED)

    general_on = on(GENERAL_PRED)
    part_on = {name: en is not None and name in en
               for name in (HOSTNAME_PRED, POD_FITS_HOST_PORTS_PRED,
                            MATCH_NODE_SELECTOR_PRED, POD_FITS_RESOURCES_PRED)}

    if general_on or part_on[POD_FITS_RESOURCES_PRED]:
        insuff_pods = (carry.pod_count + 1) > st.allowed_pods
        check_res = ~x.zero_request
        insuff_cpu = check_res & (st.alloc_cpu < x.req_cpu + carry.used_cpu)
        insuff_mem = check_res & (st.alloc_mem < x.req_mem + carry.used_mem)
        insuff_gpu = check_res & (st.alloc_gpu < x.req_gpu + carry.used_gpu)
        insuff_eph = check_res & (st.alloc_eph < x.req_eph + carry.used_eph)
        insuff_scalar = check_res[..., None] & (
            st.alloc_scalar < x.req_scalar[None, :] + carry.used_scalar)
        fail_res = (insuff_pods | insuff_cpu | insuff_mem | insuff_gpu
                    | insuff_eph | jnp.any(insuff_scalar, axis=-1))
        bits_res = (
            insuff_pods.astype(jnp.int64) << BIT_INSUFFICIENT_PODS
            | insuff_cpu.astype(jnp.int64) << BIT_INSUFFICIENT_CPU
            | insuff_mem.astype(jnp.int64) << BIT_INSUFFICIENT_MEMORY
            | insuff_gpu.astype(jnp.int64) << BIT_INSUFFICIENT_GPU
            | insuff_eph.astype(jnp.int64) << BIT_INSUFFICIENT_EPHEMERAL)
        if st.alloc_scalar.shape[-1] > 0:
            scalar_bits = (insuff_scalar.astype(jnp.int64)
                           << (NUM_FIXED_BITS + jnp.arange(
                               st.alloc_scalar.shape[-1], dtype=jnp.int64)))
            bits_res = bits_res | jnp.sum(scalar_bits, axis=-1)
    if general_on or part_on[HOSTNAME_PRED]:
        host_bad = ~st.host_ok[x.host_id]
    if general_on or part_on[MATCH_NODE_SELECTOR_PRED]:
        sel_bad = ~st.selector_ok[x.sel_id]
    ports_alias_on = ps is not None and bool(ps.ports_slots)
    if config.has_ports and (general_on or part_on[POD_FITS_HOST_PORTS_PRED]
                             or ports_alias_on):
        # PodFitsHostPorts (predicates.go:1019-1039), part of GeneralPredicates:
        # a wanted port of my group conflicts with occupancy of any group
        # present; conflict is factored through interned port-set ids
        conflict_row = st.port_conflict[st.port_sig[x.group_id]][st.port_sig]
        port_bad = jnp.any(conflict_row[:, None] & (carry.presence > 0), axis=0)

    if general_on:
        fail_general = fail_res | host_bad | sel_bad
        bits_general = (
            bits_res
            | host_bad.astype(jnp.int64) << BIT_HOSTNAME_MISMATCH
            | sel_bad.astype(jnp.int64) << BIT_NODE_SELECTOR_MISMATCH)
        if config.has_ports:
            fail_general = fail_general | port_bad
            bits_general = bits_general | (
                port_bad.astype(jnp.int64) << BIT_HOST_PORTS)
        stages.append((fail_general, bits_general))
    emit_label(GENERAL_PRED)
    # individually-named parts run as separate short-circuit stages in the
    # ordering slots HostName → PodFitsHostPorts → MatchNodeSelector →
    # PodFitsResources (predicates.go:130-136)
    if part_on[HOSTNAME_PRED]:
        stages.append((host_bad, jnp.int64(1) << BIT_HOSTNAME_MISMATCH))
    emit_label(HOSTNAME_PRED)
    if part_on[POD_FITS_HOST_PORTS_PRED] and config.has_ports:
        stages.append((port_bad, jnp.int64(1) << BIT_HOST_PORTS))
    emit_label(POD_FITS_HOST_PORTS_PRED)
    if part_on[MATCH_NODE_SELECTOR_PRED]:
        stages.append((sel_bad, jnp.int64(1) << BIT_NODE_SELECTOR_MISMATCH))
    emit_label(MATCH_NODE_SELECTOR_PRED)
    if part_on[POD_FITS_RESOURCES_PRED]:
        stages.append((fail_res, bits_res))
    emit_label(POD_FITS_RESOURCES_PRED)

    if config.has_disk_conflict and on(NO_DISK_CONFLICT_PRED):
        # NoDiskConflict (predicates.go:266-276): my volume set conflicts with
        # the volume set of any group present on the node
        disk_row = st.disk_conflict[st.disk_sig[x.group_id]][st.disk_sig]
        fail_disk = jnp.any(disk_row[:, None] & (carry.presence > 0), axis=0)
        stages.append((fail_disk, jnp.int64(1) << BIT_DISK_CONFLICT))
    emit_label(NO_DISK_CONFLICT_PRED)

    if on(POD_TOLERATES_NODE_TAINTS_PRED):
        stages.append((~st.taint_ok[x.tol_id],
                       jnp.int64(1) << BIT_TAINTS_NOT_TOLERATED))
    emit_label(POD_TOLERATES_NODE_TAINTS_PRED)
    if en is not None and POD_TOLERATES_NODE_NO_EXECUTE_TAINTS_PRED in en:
        # policy-registered NoExecute-only variant (not in any provider set)
        stages.append((~st.taint_ok_noexec[x.tol_id],
                       jnp.int64(1) << BIT_TAINTS_NOT_TOLERATED))
    emit_label(POD_TOLERATES_NODE_NO_EXECUTE_TAINTS_PRED)
    emit_label(CHECK_NODE_LABEL_PRESENCE_PRED)
    emit_label(CHECK_SERVICE_AFFINITY_PRED)

    maxpd_on = (on(MAX_EBS_VOLUME_COUNT_PRED), on(MAX_GCE_PD_VOLUME_COUNT_PRED),
                on(MAX_AZURE_DISK_VOLUME_COUNT_PRED))
    if config.has_maxpd and any(maxpd_on):
        # Max{EBS,GCEPD,AzureDisk}VolumeCount (predicates.go:422-460): unique
        # relevant volume ids on the node incl. mine vs the per-type limit;
        # a pod adding no relevant volumes passes regardless. Disabled types
        # get an unreachable limit.
        mask_g = st.vol_mask[x.group_id]                       # [V]
        type_i = st.vol_type.astype(jnp.int32)                 # [V, 3]
        union_counts = (carry.used_vols | mask_g[None, :]).astype(jnp.int32) @ type_i
        my_counts = mask_g.astype(jnp.int32) @ type_i          # [3]
        limits = jnp.array(
            [lim if enabled else (1 << 30)
             for lim, enabled in zip(config.maxpd_limits, maxpd_on)],
            dtype=jnp.int32)
        fail_maxpd = jnp.any((my_counts[None, :] > 0)
                             & (union_counts > limits[None, :]), axis=1)
        stages.append((fail_maxpd, jnp.int64(1) << BIT_MAX_VOLUME_COUNT))
    emit_label(MAX_EBS_VOLUME_COUNT_PRED)
    emit_label(MAX_GCE_PD_VOLUME_COUNT_PRED)
    emit_label(MAX_AZURE_DISK_VOLUME_COUNT_PRED)
    emit_label(CHECK_VOLUME_BINDING_PRED)

    if config.has_vol_zone and on(NO_VOLUME_ZONE_CONFLICT_PRED):
        # NoVolumeZoneConflict (predicates.go:510-533): static per
        # (volume-set, node) — bound PV zone labels vs node zone labels
        stages.append((~st.zone_ok[x.group_id],
                       jnp.int64(1) << BIT_VOLUME_ZONE_CONFLICT))
    emit_label(NO_VOLUME_ZONE_CONFLICT_PRED)

    if on(CHECK_NODE_MEMORY_PRESSURE_PRED):
        stages.append((st.mem_pressure & x.best_effort,
                       jnp.int64(1) << BIT_MEMORY_PRESSURE))
    emit_label(CHECK_NODE_MEMORY_PRESSURE_PRED)
    if on(CHECK_NODE_DISK_PRESSURE_PRED):
        stages.append((st.disk_pressure, jnp.int64(1) << BIT_DISK_PRESSURE))
    emit_label(CHECK_NODE_DISK_PRESSURE_PRED)

    if config.has_interpod:
        # shared prelude for the MatchInterPodAffinity predicate and the
        # InterPodAffinityPriority score block
        g = x.group_id
        presence_f = carry.presence.astype(jnp.float64)
        pd_f = carry.presence_dom.astype(jnp.float64)
        k_count = st.topo_dom.shape[0]

    if config.has_interpod and on(MATCH_INTERPOD_AFFINITY_PRED):
        # MatchInterPodAffinity (predicates.go:1125-1450) — last in
        # predicatesOrdering. Group-space matching is precompiled; here only
        # presence/topology aggregation runs.

        # own required affinity terms (_satisfies_pods_affinity_anti_affinity)
        mcount = st.term_match[st.aff_term[g]].astype(jnp.float64) @ presence_f  # [Ta, N]
        dom_rows = st.topo_dom[st.aff_key[g]]                       # [Ta, N]
        valid_dom = dom_rows > 0
        dc_at = jnp.take_along_axis(
            _ax_sum(_seg_rows(mcount, dom_rows, config.n_topo_doms), ax),
            dom_rows, axis=1)
        is_host = st.aff_hostname[g][:, None]
        on_node = mcount > 0.5
        term_matches = jnp.where(is_host, valid_dom & on_node,
                                 valid_dom & (dc_at > 0.5))
        # hostname terms scan only this node's pods (predicates.go: topologyKey
        # == hostname restricts the search), so "matching pod exists" is
        # per-node there and global (incl. unplaced snapshot pods) otherwise
        exists = jnp.where(
            is_host, on_node,
            ((_ax_sum(jnp.sum(mcount, axis=1), ax) > 0.5)
             | st.aff_unplaced[g])[:, None])
        term_ok = term_matches | ((~exists) & st.aff_self[g][:, None])
        aff_fail = jnp.any(st.aff_valid[g][:, None] & ~term_ok,
                           axis=0) | st.aff_err[g]

        # own required anti-affinity terms
        bmcount = st.term_match[st.anti_term[g]].astype(jnp.float64) @ presence_f
        bdom_rows = st.topo_dom[st.anti_key[g]]
        bvalid = bdom_rows > 0
        bdc_at = jnp.take_along_axis(
            _ax_sum(_seg_rows(bmcount, bdom_rows, config.n_topo_doms), ax),
            bdom_rows, axis=1)
        b_is_host = st.anti_hostname[g][:, None]
        b_matches = jnp.where(b_is_host, bvalid & (bmcount > 0.5),
                              bvalid & (bdc_at > 0.5))
        anti_fail = jnp.any(st.anti_valid[g][:, None] & b_matches,
                            axis=0) | st.anti_err[g]

        # existing pods' anti-affinity vs me (symmetric check; runs first)
        w = st.anti_valid & st.term_match[st.anti_term, g]          # [G, Tb]
        grp_present = _ax_sum(jnp.sum(carry.presence, axis=1), ax) > 0  # [G]
        fail_all = jnp.any(w & st.anti_empty & grp_present[:, None])
        key_oh = jax.nn.one_hot(st.anti_key, k_count, dtype=jnp.float64)
        bad_dom = jnp.einsum("gtk,gt,gkd->kd", key_oh,
                             (w & ~st.anti_empty).astype(jnp.float64), pd_f)
        bad_at = jnp.take_along_axis(bad_dom, st.topo_dom, axis=1)  # [K, N]
        exist_fail = jnp.any((st.topo_dom > 0) & (bad_at > 0.5),
                             axis=0) | fail_all

        fail_interpod = exist_fail | aff_fail | anti_fail
        # two reasons per failure: the umbrella + the specific rule, in the
        # engine's check order (existing-anti, affinity, anti-affinity)
        interpod_bits = (jnp.int64(1) << BIT_AFFINITY_NOT_MATCH) | jnp.where(
            exist_fail, jnp.int64(1) << BIT_EXISTING_ANTI_AFFINITY,
            jnp.where(aff_fail, jnp.int64(1) << BIT_AFFINITY_RULES,
                      jnp.int64(1) << BIT_ANTI_AFFINITY_RULES))
        stages.append((fail_interpod, interpod_bits))
    emit_label(MATCH_INTERPOD_AFFINITY_PRED)
    # customs under non-ordering names run after the fixed ordering in the
    # host's ALPHABETICAL name order; policyc assigns each tail custom
    # (label-presence row or ServiceAffinity entry) its sorted position as
    # slot "tail:<k>"
    if ps is not None:
        tail_ks = sorted(
            int(s.split(":", 1)[1])
            for s in set(ps.label_rows) | set(ps.sa_slots)
            | set(ps.ports_slots)
            if s.startswith("tail:"))
        for k in tail_ks:
            emit_label(f"tail:{k}")

    fail_any = stages[0][0]
    for fail, _ in stages[1:]:
        fail_any = fail_any | fail
    feasible = ~fail_any
    reason_bits = jnp.int64(0)
    aca_counts = None
    if ps is not None and ps.always_check_all:
        # alwaysCheckAllPredicates: every failing stage contributes its
        # reasons (podFitsOnNode keeps evaluating past the first failure).
        # Sentinel-padded nodes (sharding/what-if node-axis padding: condition
        # bit 62, never decoded) must contribute NOTHING else, or phantom
        # nodes would inflate the reason histogram.
        is_pad = (st.cond_fail_bits & (jnp.int64(1) << 62)) != 0
        # count mode: the host can emit one reason STRING several times per
        # node (a duplicated stage pair, or several label predicates sharing
        # ERR_NODE_LABEL_PRESENCE_VIOLATED) — summing decoded stage firings
        # reproduces those multiplicities, which a bitmask OR cannot. Only
        # the cheap [S, N] stacks materialize here; the [S, N, bits] decode
        # is deferred to the caller's not-found cond branch (hoisting it
        # would run it on every step, bound or not — see the histogram
        # comment in make_step).
        fail_stack = jnp.stack([fail & ~is_pad for fail, _ in stages])
        bits_stack = jnp.stack([
            jnp.broadcast_to(bits, fail.shape) for fail, bits in stages])
        aca_counts = (fail_stack, bits_stack)
        # reason_bits stays zero in count mode: the scan step's consumer
        # reads aca_counts instead
    else:
        # short-circuit reason selection: first failing stage wins (padded
        # nodes fail at the cond stage, whose sentinel bit is never decoded)
        for fail, bits in reversed(stages):
            reason_bits = jnp.where(fail, bits, reason_bits)
    n_feasible = _ax_sum(jnp.sum(feasible), ax)

    # ---- score (weighted sum, generic_scheduler.go:631-639) ----
    (w_least, w_most, w_balanced, w_node_aff, w_taint, w_avoid, w_spread,
     w_interpod) = policy_weights(ps, config.most_requested)
    label_prio_on = ps is not None and ps.has_label_prio

    score = jnp.zeros_like(st.alloc_cpu)
    # explain lanes (ISSUE 13): each weighted component lands in `parts`
    # alongside its addition into score, in explain_part_names order. The
    # list stays empty when explain_k == 0 (static), so the disabled trace
    # is unchanged.
    explain = config.explain_k > 0
    parts: list = []

    def add(term):
        nonlocal score
        score = score + term
        if explain:
            parts.append(jnp.broadcast_to(term, score.shape))

    if w_least or w_most or w_balanced:
        total_cpu = x.nz_cpu + carry.nonzero_cpu
        total_mem = x.nz_mem + carry.nonzero_mem
    if w_least:
        # least_requested.go:41-52
        add(w_least * (
            (_ratio_score(total_cpu, st.alloc_cpu, False)
             + _ratio_score(total_mem, st.alloc_mem, False)) // 2))
    if w_most:
        # most_requested.go:44-55
        add(w_most * (
            (_ratio_score(total_cpu, st.alloc_cpu, True)
             + _ratio_score(total_mem, st.alloc_mem, True)) // 2))
    if w_balanced:
        add(w_balanced * _balanced_score(
            total_cpu, total_mem, st.alloc_cpu, st.alloc_mem))

    if w_node_aff:
        # NodeAffinityPriority: NormalizeReduce(10, False) over feasible nodes
        aff = st.affinity_count[x.aff_id]
        aff_max = _ax_max(jnp.max(jnp.where(feasible, aff, 0)), ax)
        aff_norm = jnp.where(
            aff_max > 0, MAX_PRIORITY * aff // jnp.maximum(aff_max, 1), 0)
        add(w_node_aff * aff_norm)

    if w_taint:
        # TaintTolerationPriority: NormalizeReduce(10, True) over feasible nodes
        intol = st.intolerable[x.tol_id]
        intol_max = _ax_max(jnp.max(jnp.where(feasible, intol, 0)), ax)
        taint_norm = jnp.where(
            intol_max > 0,
            MAX_PRIORITY - MAX_PRIORITY * intol // jnp.maximum(intol_max, 1),
            MAX_PRIORITY)
        add(w_taint * taint_norm)

    if w_avoid:
        add(st.avoid_score[x.avoid_id] * w_avoid)

    if label_prio_on:
        # NodeLabel/LabelPreference priorities: static pre-weighted rows
        add(st.label_prio)

    if ps is not None and ps.w_image:
        # ImageLocalityPriority (image_locality.go): static per
        # (pod-image-set, node) score row
        add(st.image_score[x.img_id] * ps.w_image)

    if ps is not None and ps.saa_weights:
        # ServiceAntiAffinity (selector_spreading.go:176-280): spread the
        # pods matching MY first service's selector across node groups
        # identified by the policy label. cnt counts such pods per node;
        # the reduce is over feasible nodes (the host maps over filtered
        # nodes only); unlabeled nodes score 0.
        # the f64 matmul is exact (counts are small integers, far below
        # 2^24); the normalize below is exact integer (DEVIATIONS.md #16)
        saa_cnt = (st.saa_rows[st.saa_sig[x.group_id]].astype(jnp.float64) @
                   carry.presence.astype(jnp.float64)).astype(jnp.int64)  # [N]
        saa_fcnt = jnp.where(feasible, saa_cnt, 0)
        saa_total = _ax_sum(jnp.sum(saa_fcnt), ax)
        # entries accumulate into ONE explain part (integer adds: regrouping
        # the per-entry additions into a single term is exact)
        saa_term = jnp.zeros_like(score)
        for e, w_saa in enumerate(ps.saa_weights):
            dom = st.saa_dom[e]
            labeled = dom > 0
            grp = _ax_sum(jax.ops.segment_sum(
                jnp.where(labeled, saa_fcnt, 0), dom,
                num_segments=config.n_saa_doms), ax).at[0].set(0)
            f_score = jnp.where(
                saa_total > 0,
                (MAX_PRIORITY * (saa_total - grp[dom]))
                // jnp.maximum(saa_total, 1),
                MAX_PRIORITY)
            saa_term = saa_term + jnp.where(labeled, f_score, 0) * w_saa
        add(saa_term)

    if config.has_services and w_spread:
        # SelectorSpreadPriority (selector_spreading.go:66-175): per-node count
        # of same-namespace pods matched by my services' selectors, then the
        # node/zone-blended normalize over feasible nodes
        # f64 matmul exact for small integer counts; normalize + zone blend
        # in exact integer arithmetic, one floor at the end — matching the
        # host's rational form of Go's nodeScore/3 + 2*zoneScore/3
        # (selector_spreading.go hardcodes zoneWeighting=2.0/3.0;
        # DEVIATIONS.md #16)
        cnt = (st.ss_rows[st.ss_sig[x.group_id]].astype(jnp.float64) @
               carry.presence.astype(jnp.float64)).astype(jnp.int64)  # [N]
        fcnt = jnp.where(feasible, cnt, 0)
        max_node = _ax_max(jnp.max(fcnt), ax)
        zdom = st.zone_dom
        zvalid = zdom > 0
        zcnt = _ax_sum(jax.ops.segment_sum(
            fcnt, zdom, num_segments=config.n_zone_doms), ax).at[0].set(0)
        have_zones = _ax_any(jnp.any(feasible & zvalid), ax)
        max_zone = jnp.max(zcnt)
        node_num = jnp.where(max_node > 0, max_node - cnt, 1)
        node_den = jnp.maximum(max_node, 1)
        zone_num = jnp.where(max_zone > 0, max_zone - zcnt[zdom], 1)
        zone_den = jnp.maximum(max_zone, 1)
        plain = (MAX_PRIORITY * node_num) // node_den
        blend = (MAX_PRIORITY
                 * (node_num * zone_den + 2 * zone_num * node_den)
                 ) // (3 * node_den * zone_den)
        add(jnp.where(have_zones & zvalid, blend, plain) * w_spread)

    if config.has_interpod and w_interpod:
        # InterPodAffinityPriority (interpod_affinity.go:118+): float64 counts
        # from (a) my preferred terms over existing pods, (b) existing pods'
        # preferred terms over me, (c) their required affinity × hard weight;
        # all contributions are integer-valued so summation order is exact
        p_w = st.pref_w[g]                                          # [Tp]
        pcount = st.term_match[st.pref_term[g]].astype(jnp.float64) @ presence_f  # [Tp, N]
        pdom = st.topo_dom[st.pref_key[g]]                          # [Tp, N]
        pdc_at = jnp.take_along_axis(
            _ax_sum(_seg_rows(pcount, pdom, config.n_topo_doms), ax),
            pdom, axis=1)
        counts = jnp.sum(p_w[:, None] * jnp.where(pdom > 0, pdc_at, 0.0), axis=0)

        wb = st.pref_w * st.term_match[st.pref_term, g]             # [G, Tp]
        wc = float(config.hard_weight) * (
            st.aff_valid & ~st.aff_empty
            & st.term_match[st.aff_term, g]).astype(jnp.float64)    # [G, Ta]
        key_oh_p = jax.nn.one_hot(st.pref_key, k_count, dtype=jnp.float64)
        key_oh_a = jax.nn.one_hot(st.aff_key, k_count, dtype=jnp.float64)
        wsum = (jnp.einsum("gtk,gt,gkd->kd", key_oh_p, wb, pd_f)
                + jnp.einsum("gtk,gt,gkd->kd", key_oh_a, wc, pd_f))  # [K, D]
        wsum_at = jnp.take_along_axis(wsum, st.topo_dom, axis=1)     # [K, N]
        counts = counts + jnp.sum(
            jnp.where(st.topo_dom > 0, wsum_at, 0.0), axis=0)

        # counts are integer-valued f64 sums (weights and hard_weight are
        # ints, well below 2^24: exact); the normalize is exact integer —
        # the numerator is nonnegative, so floor division equals Go's
        # toward-zero int() conversion (DEVIATIONS.md #16)
        counts_i = counts.astype(jnp.int64)
        big = jnp.int64(1) << 62
        maxc = jnp.maximum(
            _ax_max(jnp.max(jnp.where(feasible, counts_i, -big)), ax), 0)
        minc = jnp.minimum(
            _ax_min(jnp.min(jnp.where(feasible, counts_i, big)), ax), 0)
        rng = maxc - minc
        ip = jnp.where(rng > 0,
                       (MAX_PRIORITY * (counts_i - minc)) // jnp.maximum(rng, 1),
                       0)
        add(ip * w_interpod)

    return feasible, reason_bits, score, n_feasible, aca_counts, parts


def _select(feasible, score, n_feasible, rr, axis=None):
    """selectHost (generic_scheduler.go:183-198): stable-desc + round-robin
    among max-score ties; rr is consumed only when >1 node passed the filter
    (with one feasible node scheduleOne returns it directly, :176-180).

    With `axis` set (the shard_map route) each shard holds a contiguous
    block of the node axis and the same selection runs globally: the tie
    threshold is a pmax, the tie COUNT a psum, and each shard ranks its
    ties at a global offset (the all-gathered tie counts of earlier
    shards) — so `rank == k` fires on exactly one node cluster-wide, at
    the same position the single-device cumsum would pick. The winning
    shard publishes its global index through a pmin (losers contribute
    int32-max), making `choice` replicated and bit-identical to the
    unsharded route, round-robin tie-break included."""
    masked = jnp.where(feasible, score, jnp.int64(-1))
    max_score = _ax_max(jnp.max(masked), axis)
    tie = feasible & (masked == max_score)
    local_ties = jnp.sum(tie)
    ties = jnp.maximum(_ax_sum(local_ties, axis), 1)
    k = jnp.where(n_feasible > 1, rr % ties, 0)
    rank = jnp.cumsum(tie.astype(jnp.int64)) - 1
    if axis is not None:
        per_shard = jax.lax.all_gather(local_ties, axis)        # [S]
        me = jax.lax.axis_index(axis)
        rank = rank + jnp.sum(jnp.where(
            jnp.arange(per_shard.shape[0]) < me, per_shard, 0))
    pick = tie & (rank == k)
    choice = jnp.argmax(pick).astype(jnp.int32)
    if axis is not None:
        base = (jax.lax.axis_index(axis) * feasible.shape[0]).astype(jnp.int32)
        choice = _ax_min(jnp.where(jnp.any(pick), base + choice,
                                   jnp.iinfo(jnp.int32).max), axis)
    found = n_feasible > 0
    return jnp.where(found, choice, -1), found


def _reason_histogram(reason_bits, num_bits: int):
    bit_ids = jnp.arange(num_bits, dtype=jnp.int64)
    present = (reason_bits[:, None] >> bit_ids[None, :]) & 1
    return jnp.sum(present, axis=0).astype(jnp.int32)


def _aca_histogram(aca_counts, num_bits: int):
    """Count-mode histogram from _evaluate's (fail_stack, bits_stack):
    per-reason-string occurrence sums over ALL failing stages (pad-masked
    already), reproducing the host's duplicate-string multiplicities under
    alwaysCheckAllPredicates."""
    fail_stack, bits_stack = aca_counts
    bit_ids = jnp.arange(num_bits, dtype=jnp.int64)
    decoded = ((bits_stack[..., None] >> bit_ids) & 1) != 0   # [S, N, B]
    return jnp.sum(fail_stack[..., None] & decoded,
                   axis=(0, 1)).astype(jnp.int32)


def make_step(config: EngineConfig):
    """The exact sequential scan step: (carry, PodX) -> (carry', (choice, counts))."""

    def step(state: tuple, x: PodX):
        carry, st = state
        feasible, reason_bits, score, n_feasible, aca_counts, parts = \
            _evaluate(config, carry, st, x)
        choice, found = _select(feasible, score, n_feasible, carry.rr,
                                config.shard_axis)
        rr_next = carry.rr + jnp.where(n_feasible > 1, 1, 0)

        if config.shard_axis is None:
            bind = found
            idx = jnp.maximum(choice, 0)
        else:
            # sharded route: `choice` is a GLOBAL node index (replicated by
            # _select's pmin); only the owner shard scatters into its
            # node-sharded columns. Replicated fields (presence_dom, rr)
            # update identically on every shard further down.
            n_local = feasible.shape[0]
            base = (jax.lax.axis_index(config.shard_axis)
                    * n_local).astype(jnp.int32)
            local = choice - base
            bind = found & (local >= 0) & (local < n_local)
            idx = jnp.clip(local, 0, n_local - 1)
        gate = bind.astype(jnp.int64)
        gate32 = bind.astype(jnp.int32)
        if (config.has_ports or config.has_services or config.has_interpod
                or config.has_disk_conflict):
            presence = carry.presence.at[x.group_id, idx].add(gate32)
        else:
            presence = carry.presence
        if config.has_maxpd:
            row = jnp.where(bind,
                            carry.used_vols[idx] | st.vol_mask[x.group_id],
                            carry.used_vols[idx])
            used_vols = carry.used_vols.at[idx].set(row)
        else:
            used_vols = carry.used_vols
        if config.has_interpod:
            k_count = st.topo_dom.shape[0]
            dom_at = st.topo_dom[:, idx]                    # [K]
            if config.shard_axis is not None:
                # presence_dom is replicated: every shard applies the same
                # update, so the owner broadcasts its topo_dom column (the
                # psum has one nonzero contributor)
                dom_at = jax.lax.psum(jnp.where(bind, dom_at, 0),
                                      config.shard_axis)
            presence_dom = carry.presence_dom.at[
                x.group_id, jnp.arange(k_count), dom_at].add(
                    found.astype(jnp.int32))
        else:
            presence_dom = carry.presence_dom
        if config.policy is not None and config.policy.sa_enabled:
            # the first ASSIGNED pod matching a selector defines its pin (the
            # plugin pod lister is the scheduler cache, factory.go:166), and
            # assigned order == bind order here — so the first matching BIND
            # locks each still-unlocked sig to the chosen node
            match_f = st.saa_rows[:, x.group_id] & found      # [F]
            sa_lock = jnp.where((carry.sa_lock == -1) & match_f,
                                idx.astype(jnp.int32), carry.sa_lock)
        else:
            sa_lock = carry.sa_lock
        new_carry = Carry(
            used_cpu=carry.used_cpu.at[idx].add(gate * x.req_cpu),
            used_mem=carry.used_mem.at[idx].add(gate * x.req_mem),
            used_gpu=carry.used_gpu.at[idx].add(gate * x.req_gpu),
            used_eph=carry.used_eph.at[idx].add(gate * x.req_eph),
            used_scalar=carry.used_scalar.at[idx].add(gate * x.req_scalar),
            nonzero_cpu=carry.nonzero_cpu.at[idx].add(gate * x.nz_cpu),
            nonzero_mem=carry.nonzero_mem.at[idx].add(gate * x.nz_mem),
            pod_count=carry.pod_count.at[idx].add(gate),
            presence=presence, presence_dom=presence_dom,
            used_vols=used_vols, sa_lock=sa_lock,
            rr=rr_next)

        # the histogram lambdas must stay INSIDE the cond branch: hoisting
        # them out captures the decode as a cond operand and XLA then
        # computes the [N x bits] (or [S x N x bits]) sum every step, bound
        # or not (measured ~25% on the 20k x 2000 CPU scan)
        counts = jax.lax.cond(
            found,
            lambda: jnp.zeros(config.num_reason_bits, dtype=jnp.int32),
            (lambda: _aca_histogram(aca_counts, config.num_reason_bits))
            if aca_counts is not None else
            (lambda: _reason_histogram(reason_bits, config.num_reason_bits)))
        if config.shard_axis is not None:
            # per-shard histograms merge OUTSIDE the cond (found is
            # replicated, so every shard takes the same branch and the
            # psum stays uniform; a bound pod psums zeros)
            counts = jax.lax.psum(counts, config.shard_axis)
        # advanced: selectHost consumed the rr counter for this pod — lets the
        # preemption hybrid (jaxe/preempt.py) resume rr mid-batch on re-dispatch
        if config.explain_k > 0:
            # explain lanes: top-k candidates by final score with per-part
            # contributions; infeasible nodes masked far below any real
            # score so padding rows decode as EXPLAIN_SENTINEL
            k = min(config.explain_k, score.shape[0])
            masked_sc = jnp.where(feasible, score,
                                  jnp.asarray(2 * EXPLAIN_SENTINEL,
                                              dtype=score.dtype))
            top_scores, top_idx = jax.lax.top_k(masked_sc, k)
            if parts:
                parts_mat = jnp.stack(parts)              # [C, N]
                top_parts = parts_mat[:, top_idx].T       # [k, C]
            else:
                top_parts = jnp.zeros((k, 0), dtype=score.dtype)
            return (new_carry, st), (choice, counts, n_feasible > 1,
                                     top_idx, top_scores, top_parts)
        return (new_carry, st), (choice, counts, n_feasible > 1)

    return step


def _schedule_scan_impl(config: EngineConfig, carry: Carry, statics: Statics,
                        xs: PodX):
    step = make_step(config)
    if config.explain_k > 0:
        (final_carry, _), (choices, counts, advanced, top_idx, top_scores,
                           top_parts) = jax.lax.scan(
            step, (carry, statics), xs, unroll=config.scan_unroll)
        return (final_carry, choices, counts, advanced,
                (top_idx, top_scores, top_parts))
    (final_carry, _), (choices, counts, advanced) = jax.lax.scan(
        step, (carry, statics), xs, unroll=config.scan_unroll)
    return final_carry, choices, counts, advanced


# Exact sequential mode: scan the fused step over the pod axis.
schedule_scan = partial(jax.jit, static_argnames=("config",))(_schedule_scan_impl)


# Chunked-driver variant: the carry buffers are donated, so a host loop
# feeding pod chunks (carry, ch = scan(carry, chunk)) updates the [N]-sized
# state in place instead of churning fresh HBM allocations per chunk
# (SURVEY.md §7 hard part 6 — 1M-pod batches).
schedule_scan_donated = jax.jit(_schedule_scan_impl,
                                static_argnames=("config",),
                                donate_argnums=(1,))


# --------------------------------------------------------------------------
# Node-axis sharded route (ISSUE 16): the SAME fused step, wrapped in
# shard_map over a "node" mesh axis. Each shard owns a contiguous block of
# the (shard-even padded) node axis; per-step reductions and host selection
# merge through the collectives threaded above, so placements are
# bit-identical to the single-device scan — the backend's verify-then-trust
# seam (trust.SHARD) replays the first batch per signature to prove it.

def node_partition_specs(axis: str = "node"):
    """(Statics, Carry, PodX) PartitionSpec trees for the node-sharded route,
    derived from the axis registries: "node" axes map to the mesh axis,
    everything else (group tables, presence_dom, pod columns) replicates."""
    from jax.sharding import PartitionSpec as P

    def tree(cls, registry):
        return cls(*(P(*(axis if a == "node" else None
                         for a in registry[f])) for f in cls._fields))

    # PodX leaves carry a leading pod axis ahead of their registry axes;
    # every pod column is replicated, so P() covers them regardless of rank
    return (tree(Statics, STATICS_AXES), tree(Carry, CARRY_AXES),
            PodX(*(P() for _ in PodX._fields)))


def shard_route_eligible(config: EngineConfig):
    """(ok, reason) — static feature gates the sharded route cannot serve.
    ServiceAffinity reads node columns by a GLOBAL locked index (sa_val
    gathers cross shards) and explain lanes emit a per-node top-k that has
    no associative merge wired yet; both fall back, classified."""
    ps = config.policy
    if ps is not None and (ps.sa_enabled or ps.sa_slots):
        return False, "service_affinity"
    if config.explain_k > 0:
        return False, "explain_lanes"
    return True, ""


_SHARDED_SCAN_PROGRAMS: dict = {}


def sharded_scan_fn(config: EngineConfig, mesh, donate: bool = False):
    """The jitted shard_map program for the node-sharded fused scan,
    cached per (config, mesh, donate). `config.shard_axis` must name a
    mesh axis; inputs must be shard-even padded (sharding.pad_node_axis)
    and placed/placeable per `node_partition_specs`. Signature matches
    schedule_scan minus the leading config: fn(carry, statics, xs) ->
    (final_carry, choices, counts, advanced)."""
    if config.shard_axis is None:
        raise ValueError("sharded_scan_fn requires config.shard_axis")
    ok, why = shard_route_eligible(config)
    if not ok:
        raise ValueError(f"sharded route cannot serve this config: {why}")
    key = (config, mesh, donate)
    fn = _SHARDED_SCAN_PROGRAMS.get(key)
    if fn is None:
        from jax.sharding import PartitionSpec as P

        st_specs, ca_specs, xs_specs = node_partition_specs(config.shard_axis)
        sm = jax.shard_map(
            partial(_schedule_scan_impl, config), mesh=mesh,
            in_specs=(ca_specs, st_specs, xs_specs),
            # final carry keeps its node-sharded layout; choices/counts/
            # advanced are replicated by construction (pmin/psum merges)
            out_specs=(ca_specs, P(), P(), P()),
            check_vma=False)
        fn = jax.jit(sm, donate_argnums=(0,) if donate else ())
        _SHARDED_SCAN_PROGRAMS[key] = fn
    return fn


def schedule_scan_chunked(config: EngineConfig, carry: Carry, statics: Statics,
                          xs_host: PodX, chunk: int, progress=None,
                          scan_donated=None, put=None):
    """Exact sequential scan over a pod batch too large for one dispatch,
    with double-buffered transfers (SURVEY.md §7 hard part 6).

    `scan_donated` swaps the per-chunk program — the sharded route passes
    its shard_map fn (signature (carry, statics, xs), config already
    bound) — and `put` overrides the chunk upload (e.g. a device_put onto
    the mesh's replicated sharding). Defaults reproduce the single-device
    donated scan exactly.

    `xs_host` holds host-numpy pod columns; the full [P]-row PodX never lands
    in HBM at once. Per iteration the host loop (a) dispatches chunk t on the
    donated carry, (b) immediately enqueues the async upload of chunk t+1's
    columns, and (c) only then fetches chunk t-1's choices — so the one host
    sync per iteration overlaps with chunk t's device compute, and the upload
    rides the same overlap window instead of serializing with dispatch.
    Placements are bit-identical to the unchunked scan (the carry crosses
    chunk boundaries untouched; padding rows are infeasible no-ops).

    Returns (final_carry, choices[P], counts[P, bits], advanced[P]) with the
    result arrays as host numpy."""
    p = int(xs_host.req_cpu.shape[0])
    pad = (-p) % chunk
    if pad:
        xs_host = pad_infeasible_rows(xs_host, pad)
    num_chunks = (p + pad) // chunk

    def upload(ci):
        sl = slice(ci * chunk, (ci + 1) * chunk)
        rows = PodX(*(a[sl] for a in xs_host))
        return jax.device_put(rows) if put is None else put(rows)

    choice_parts, count_parts, adv_parts = [], [], []
    pending = None
    nxt = upload(0)
    for ci in range(num_chunks):
        xs_c = nxt
        if scan_donated is None:
            carry, ch, cnt, adv = schedule_scan_donated(config, carry,
                                                        statics, xs_c)
        else:
            carry, ch, cnt, adv = scan_donated(carry, statics, xs_c)
        if ci + 1 < num_chunks:
            nxt = upload(ci + 1)
        count_parts.append(cnt)
        adv_parts.append(adv)
        if pending is not None:
            choice_parts.append(np.asarray(pending))  # forces chunk ci-1
            if progress is not None:
                progress(ci, num_chunks, ci * chunk)
        pending = ch
    choice_parts.append(np.asarray(pending))
    if progress is not None:
        progress(num_chunks, num_chunks, p)
    choices = np.concatenate(choice_parts)[:p]
    counts = np.concatenate([np.asarray(c) for c in count_parts])[:p]
    advanced = np.concatenate([np.asarray(a) for a in adv_parts])[:p]
    return carry, choices, counts, advanced


def pad_infeasible_rows(xs, pad: int):
    """Append `pad` PodX rows that fail PodFitsResources on every node
    (req_cpu = 2^61 exceeds any allocatable): no carry mutation, no rr
    advance (n_feasible == 0 skips both), so shape padding is semantics-free.
    Host-numpy in, host-numpy out."""
    if pad <= 0:
        return xs

    def pad_field(name, arr):
        fill = (np.int64(1) << 61) if name == "req_cpu" else 0
        widths = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
        return np.pad(arr, widths, constant_values=fill)

    return PodX(*(pad_field(name, arr)
                  for name, arr in zip(PodX._fields, xs)))


# --------------------------------------------------------------------------
# Streaming delta commit (ISSUE 7): O(delta) scatter updates into the
# device-resident carry instead of a full carry_init + device_put restage.
#
# The host (jaxe.delta.IncrementalCluster) stays the source of truth: after
# folding a cycle's watch events it gathers the AUTHORITATIVE post-event
# values of every touched node row / presence cell, and the donated kernel
# scatter-`set`s them into the resident carry. Set-from-authoritative (not
# add-a-delta) makes the commit idempotent and self-healing — the device can
# never drift from the host columns on the fields it syncs.
#
# Per-batch lanes are re-armed here too: sa_lock resets to -1 and rr to 0,
# exactly what carry_init_host hands a fresh restage, so a stream cycle and
# a restage cycle run the scan from byte-identical carries. presence_dom and
# used_vols have no scatter path (their host mirrors live in the group
# tables, which rebuild on any structural event) — the stream layer
# (tpusim.stream) restages whenever a config with has_interpod/has_maxpd
# sees presence/volume churn, so their stale values are never read.
#
# Shapes are the caller's retrace contract: tpusim.stream pads node_idx /
# presence cells to pow2 buckets, so a warm steady-state churn rate reuses
# one compiled commit program (the zero-retrace warm cycle).
# --------------------------------------------------------------------------


class DeltaRows(NamedTuple):
    """Authoritative post-event dynamic values for `node_idx` rows, gathered
    from the host columns (DynamicInit dtypes: int64 throughout)."""

    used_cpu: jnp.ndarray      # [U]
    used_mem: jnp.ndarray      # [U]
    used_gpu: jnp.ndarray      # [U]
    used_eph: jnp.ndarray      # [U]
    used_scalar: jnp.ndarray   # [U, S]
    nonzero_cpu: jnp.ndarray   # [U]
    nonzero_mem: jnp.ndarray   # [U]
    pod_count: jnp.ndarray     # [U]


def _apply_delta_impl(carry: Carry, node_idx, rows: DeltaRows,
                      pres_gid, pres_nid, pres_val, sa_lock_init) -> Carry:
    # duplicate indices (bucket padding repeats a real row) are safe under
    # `set` scatter semantics only because every duplicate carries the same
    # authoritative value — any winner writes the same bytes
    return carry._replace(
        used_cpu=carry.used_cpu.at[node_idx].set(rows.used_cpu),
        used_mem=carry.used_mem.at[node_idx].set(rows.used_mem),
        used_gpu=carry.used_gpu.at[node_idx].set(rows.used_gpu),
        used_eph=carry.used_eph.at[node_idx].set(rows.used_eph),
        used_scalar=carry.used_scalar.at[node_idx].set(rows.used_scalar),
        nonzero_cpu=carry.nonzero_cpu.at[node_idx].set(rows.nonzero_cpu),
        nonzero_mem=carry.nonzero_mem.at[node_idx].set(rows.nonzero_mem),
        pod_count=carry.pod_count.at[node_idx].set(rows.pod_count),
        presence=carry.presence.at[pres_gid, pres_nid].set(pres_val),
        sa_lock=jnp.asarray(sa_lock_init, carry.sa_lock.dtype),
        rr=jnp.zeros_like(carry.rr))


# Donating the carry makes the commit a true in-place HBM update: the
# resident buffers are patched, not reallocated, mirroring
# schedule_scan_donated's chunk-loop contract above.
#
# sa_lock_init re-arms the ServiceAffinity segment-lock lanes exactly the way
# carry_init does on a restage: providers pass the all-unlocked fill(-1),
# compiled policies with ServiceAffinity pass policyc.sa_lock_init_rows
# recomputed from the live snapshot, so the resident plan sees the same
# first-assigned-pod pins a fresh restage would (ISSUE 9).
apply_delta_donated = jax.jit(_apply_delta_impl, donate_argnums=(0,))


def _overlay_restore_impl(carry: Carry, node_idx, rows: DeltaRows,
                          pres_gid, pres_nid, pres_val, sa_lock_save,
                          rr_save) -> Carry:
    # The rollback half of a what-if overlay (tpusim.stream overlay_query):
    # the same authoritative scatter as _apply_delta_impl over the nodes the
    # overlay scan BOUND, but the per-batch lanes restore the SAVED pre-mark
    # arrays instead of re-arming — sa_lock returns to the segment locks the
    # last real cycle left and rr to its pre-overlay cursor, so the
    # post-rollback carry is byte-identical to the pre-mark carry (modulo
    # churn the overlay early-committed, which the restored journal makes
    # the next real commit's idempotent no-op).
    return carry._replace(
        used_cpu=carry.used_cpu.at[node_idx].set(rows.used_cpu),
        used_mem=carry.used_mem.at[node_idx].set(rows.used_mem),
        used_gpu=carry.used_gpu.at[node_idx].set(rows.used_gpu),
        used_eph=carry.used_eph.at[node_idx].set(rows.used_eph),
        used_scalar=carry.used_scalar.at[node_idx].set(rows.used_scalar),
        nonzero_cpu=carry.nonzero_cpu.at[node_idx].set(rows.nonzero_cpu),
        nonzero_mem=carry.nonzero_mem.at[node_idx].set(rows.nonzero_mem),
        pod_count=carry.pod_count.at[node_idx].set(rows.pod_count),
        presence=carry.presence.at[pres_gid, pres_nid].set(pres_val),
        sa_lock=jnp.asarray(sa_lock_save, carry.sa_lock.dtype),
        rr=jnp.asarray(rr_save, carry.rr.dtype))


# Donation contract matches apply_delta_donated: the overlay scan's final
# carry is patched in place back to host truth. Shapes ride the same pow2
# bucketing, so warm overlay traffic reuses one compiled restore program.
overlay_restore_donated = jax.jit(_overlay_restore_impl, donate_argnums=(0,))


class StaticsDelta(NamedTuple):
    """Authoritative post-churn statics columns for `node_idx`, one column
    slice per table whose cells depend on node labels/taints. The leading
    (signature/policy-row) dims match the resident tables; the trailing dim
    is the padded churn-node bucket U."""

    selector_ok: jnp.ndarray       # [Ksel, U] bool
    taint_ok: jnp.ndarray          # [Ktol, U] bool
    taint_ok_noexec: jnp.ndarray   # [Ktol, U] bool
    intolerable: jnp.ndarray       # [Ktol, U] int32
    affinity_count: jnp.ndarray    # [Kaff, U] int64
    avoid_score: jnp.ndarray       # [Kav, U] int64
    host_ok: jnp.ndarray           # [Khost, U] bool
    label_ok: jnp.ndarray          # [L, U] bool
    label_prio: jnp.ndarray        # [U] int64
    image_score: jnp.ndarray       # [Si, U] int64
    saa_dom: jnp.ndarray           # [E, U] int32
    sa_val: jnp.ndarray            # [La, U] int32


def _apply_statics_delta_impl(statics: Statics, node_idx,
                              d: StaticsDelta) -> Statics:
    # Label/taint churn only moves per-(signature, node) and per-(policy-row,
    # node) cells; every other statics table is either node-structural
    # (alloc_*, cond_fail_bits — those churn classes restage via node_set /
    # scalar_set) or group-derived (rebuilt behind groups_dirty).
    return statics._replace(
        selector_ok=statics.selector_ok.at[:, node_idx].set(d.selector_ok),
        taint_ok=statics.taint_ok.at[:, node_idx].set(d.taint_ok),
        taint_ok_noexec=statics.taint_ok_noexec.at[:, node_idx].set(
            d.taint_ok_noexec),
        intolerable=statics.intolerable.at[:, node_idx].set(d.intolerable),
        affinity_count=statics.affinity_count.at[:, node_idx].set(
            d.affinity_count),
        avoid_score=statics.avoid_score.at[:, node_idx].set(d.avoid_score),
        host_ok=statics.host_ok.at[:, node_idx].set(d.host_ok),
        label_ok=statics.label_ok.at[:, node_idx].set(d.label_ok),
        label_prio=statics.label_prio.at[node_idx].set(d.label_prio),
        image_score=statics.image_score.at[:, node_idx].set(d.image_score),
        saa_dom=statics.saa_dom.at[:, node_idx].set(d.saa_dom),
        sa_val=statics.sa_val.at[:, node_idx].set(d.sa_val))


# Same donation contract as apply_delta_donated: the resident statics
# buffers are patched in HBM, not reallocated. XLA refcounts device buffers,
# so donating while a previously dispatched scan still reads the old statics
# is safe — the old buffers live until that computation retires.
apply_statics_delta_donated = jax.jit(_apply_statics_delta_impl,
                                      donate_argnums=(0,))


# --------------------------------------------------------------------------
# Device-side preemption victim selection — the arithmetic-reprieve class.
#
# Reference mapping (all in core/generic_scheduler.go):
#   selectVictimsOnNode (:583-665)    -> masked scan over priority-sorted
#                                        victim slots, one candidate node per
#                                        lane; the reprieve re-check reduces
#                                        to PodFitsResources' integer
#                                        arithmetic in this class (the host
#                                        mirror is GenericScheduler.
#                                        _make_arithmetic_reprieve)
#   pickOneNodeForPreemption (:739-831) -> five tie-break criteria as masked
#                                        reductions over the lane axis
#
# The host side (jaxe/preempt.py) computes the candidate lanes (static
# predicate mask + stripped-node resource fit) and the priority-sorted victim
# slots from its columnar pod table; the kernel runs the cumulative reprieve
# and the pick. Lane and slot axes are pow2-bucketed by the caller, bounding
# recompiles to O(log C · log V) variants.

PRIO_SUM_OFFSET = 1 << 31  # util.MAX_INT32 + 1 (pickOneNode criterion 4)


def _preempt_select_impl(zero_req: bool, lane_valid, node_idx,
                         alloc_cpu, alloc_mem, alloc_gpu, alloc_eph, allowed,
                         n_base, base_cpu, base_mem, base_gpu, base_eph,
                         v_prio, v_cpu, v_mem, v_gpu, v_eph, v_valid):
    """One failed pod against C candidate lanes × V victim slots.

    Per-lane inputs ([C], int64 unless noted): node_idx = global node index
    (insertion-order tie-breaks), alloc_* / allowed = node allocatables,
    n_base = resident pods AFTER stripping every lower-priority pod,
    base_* = stripped usage PLUS the incoming pod's request (the
    _make_arithmetic_reprieve state seed). Slot inputs ([C, V]): the lane's
    lower-priority pods sorted priority-desc (stable by NodeInfo.pods
    position); v_valid masks real slots. zero_req (static): the incoming
    pod requests nothing, so only the pod-count check applies
    (predicates.go:706-776 early-out).

    Returns (winner, empty_winner, victim[C, V] bool, num[C]):
    winner = node_idx picked by criteria 2-5 over lanes with victims
    (num_violating is uniformly 0 in this class — no PDBs), empty_winner =
    first-in-order lane with zero victims (criterion 1; its existence means
    the node fit without preempting anyone, i.e. a device/host scan
    disagreement the caller must resolve on the host). Both are the big
    sentinel when no lane qualifies."""

    def step(state, slot):
        n, cpu, mem, gpu, eph = state
        vp, vc, vm, vg, ve, valid = slot
        # state holds the incoming pod's request already; +2 = +victim +pod
        fits = n + 2 <= allowed
        if not zero_req:
            fits = fits & ((alloc_cpu >= cpu + vc)
                           & (alloc_mem >= mem + vm)
                           & (alloc_gpu >= gpu + vg)
                           & (alloc_eph >= eph + ve))
        reprieved = fits & valid
        state = (n + reprieved.astype(jnp.int64),
                 cpu + jnp.where(reprieved, vc, 0),
                 mem + jnp.where(reprieved, vm, 0),
                 gpu + jnp.where(reprieved, vg, 0),
                 eph + jnp.where(reprieved, ve, 0))
        return state, valid & ~fits

    state0 = (n_base, base_cpu, base_mem, base_gpu, base_eph)
    xs = (v_prio.T, v_cpu.T, v_mem.T, v_gpu.T, v_eph.T, v_valid.T)
    _, victim_cols = jax.lax.scan(step, state0, xs)
    victim = victim_cols.T  # [C, V]

    big = jnp.int64(1) << 62
    num = jnp.sum(victim, axis=1)
    empty = lane_valid & (num == 0)
    empty_winner = jnp.min(jnp.where(empty, node_idx, big))

    # criterion 3: lowest highest-victim priority — slots are priority-desc,
    # so the first masked slot per lane carries the lane's highest
    first = jnp.argmax(victim, axis=1)
    highest = jnp.take_along_axis(v_prio, first[:, None], axis=1)[:, 0]
    # criterion 4: smallest sum(priority + MAX_INT32 + 1) over victims
    psum = jnp.sum(jnp.where(victim, v_prio + PRIO_SUM_OFFSET, 0), axis=1)

    # staged min-filters (criteria 2 is a no-op: num_violating uniformly 0);
    # a single surviving lane passes every later filter unchanged, matching
    # the host's len(names) > 1 guards
    sel = lane_valid & (num > 0)
    sel = sel & (highest == jnp.min(jnp.where(sel, highest, big)))
    sel = sel & (psum == jnp.min(jnp.where(sel, psum, big)))
    sel = sel & (num == jnp.min(jnp.where(sel, num, big)))
    winner = jnp.min(jnp.where(sel, node_idx, big))  # criterion 5: first
    return winner, empty_winner, victim, num


preempt_select = partial(jax.jit, static_argnums=(0,))(_preempt_select_impl)


# --------------------------------------------------------------------------
# Cluster analytics reduction (ISSUE 14).
#
# A post-scan fold over the resident twin's per-node columns: the ten
# allocatable/requested arrays below are plain field references into a
# (Statics, Carry) pair, so building AnalyticsIn costs a tuple pack and the
# reduction is one extra O(N) dispatch per cycle that never touches the
# scheduling scan itself (placement hashes stay pinned by construction).
#
# The kernel is integer-only: sums, maxes, counts, and encoded top-k keys.
# Ratios (utilization, fragmentation) are derived at host decode time in
# tpusim/obs/analytics.py, whose numpy mirror recomputes these same integer
# ops so device-vs-host comparison is bit-exact, not within-epsilon.

ANALYTICS_RESOURCES = ("cpu", "memory", "gpu", "ephemeral", "pods")
ANALYTICS_UTIL_SCALE = 1_000_000  # utilization in ppm (integer floor-div)
# _ANALYTICS_TIE_BITS (the key layout) now lives in jaxe/packing.py and is
# re-exported above for the host mirror in obs/analytics.py


class AnalyticsIn(NamedTuple):
    """Per-node columns the analytics reduction folds ([N] each).

    Allocatables come from Statics, requested totals from the scan's final
    Carry; `analytics_in` builds one by reference (no copies, no tracing of
    the full trees — serve slices exactly these ten fields per entry)."""
    alloc_cpu: jnp.ndarray
    alloc_mem: jnp.ndarray
    alloc_gpu: jnp.ndarray
    alloc_eph: jnp.ndarray
    allowed_pods: jnp.ndarray
    used_cpu: jnp.ndarray
    used_mem: jnp.ndarray
    used_gpu: jnp.ndarray
    used_eph: jnp.ndarray
    pod_count: jnp.ndarray


class AnalyticsStats(NamedTuple):
    """Integer aggregates, resource axis ordered as ANALYTICS_RESOURCES.

    hot_keys / cold_keys encode `score * 2^32 + (2^32 - 1 - node_index)`
    (score = dominant cpu/mem utilization in ppm, clipped to [0, 1e6]);
    the index term makes every key unique, so lax.top_k and a host-side
    descending sort agree exactly. Nodes outside n_valid carry key -1 and
    are dropped at decode."""
    alloc: jnp.ndarray           # [R] int64 — allocatable totals
    used: jnp.ndarray            # [R] int64 — requested totals
    free_sum: jnp.ndarray        # [R] int64 — sum of per-node free (>= 0)
    free_max: jnp.ndarray        # [R] int64 — largest single free slot
    headroom_nodes: jnp.ndarray  # [R] int64 — nodes with free > 0
    feasible_nodes: jnp.ndarray  # int64 — free cpu AND mem AND pod slots
    valid_nodes: jnp.ndarray     # int64 — nodes inside n_valid
    hot_keys: jnp.ndarray        # [k] int64 — hottest-first encoded keys
    cold_keys: jnp.ndarray       # [k] int64 — coldest-first encoded keys


def analytics_in(statics, carry) -> AnalyticsIn:
    """The ten-column analytics view of a (Statics, Carry) pair."""
    return AnalyticsIn(
        alloc_cpu=statics.alloc_cpu, alloc_mem=statics.alloc_mem,
        alloc_gpu=statics.alloc_gpu, alloc_eph=statics.alloc_eph,
        allowed_pods=statics.allowed_pods,
        used_cpu=carry.used_cpu, used_mem=carry.used_mem,
        used_gpu=carry.used_gpu, used_eph=carry.used_eph,
        pod_count=carry.pod_count)


def _merged_top_k(keys, k: int, axis):
    """Descending top-k over (possibly node-sharded) packed keys. Sharded,
    each shard takes its local top-k and an all_gather + re-top-k merges —
    associative and exact because keys are unique (the index tiebreak), so
    any global top-k key is necessarily within its own shard's top-k."""
    if axis is None:
        vals, _ = jax.lax.top_k(keys, k)
        return vals
    local, _ = jax.lax.top_k(keys, min(k, keys.shape[0]))
    gathered = jax.lax.all_gather(local, axis).reshape(-1)
    vals, _ = jax.lax.top_k(gathered, k)
    return vals


def _analytics_reduce_impl(inp: AnalyticsIn, n_valid, *, k: int, axis=None):
    n = inp.alloc_cpu.shape[0]
    if axis is None:
        gidx = jnp.arange(n, dtype=jnp.int64)
    else:
        # inside shard_map `n` is the local block; keys carry GLOBAL node
        # indices so the merged top-k decodes identically to single-device
        gidx = (jax.lax.axis_index(axis).astype(jnp.int64) * n
                + jnp.arange(n, dtype=jnp.int64))
    mask = gidx < n_valid
    alloc = jnp.stack([inp.alloc_cpu.astype(jnp.int64),
                       inp.alloc_mem.astype(jnp.int64),
                       inp.alloc_gpu.astype(jnp.int64),
                       inp.alloc_eph.astype(jnp.int64),
                       inp.allowed_pods.astype(jnp.int64)])
    used = jnp.stack([inp.used_cpu.astype(jnp.int64),
                      inp.used_mem.astype(jnp.int64),
                      inp.used_gpu.astype(jnp.int64),
                      inp.used_eph.astype(jnp.int64),
                      inp.pod_count.astype(jnp.int64)])
    alloc = jnp.where(mask[None, :], alloc, 0)  # [R, N]
    used = jnp.where(mask[None, :], used, 0)
    free = jnp.maximum(alloc - used, 0)

    # dominant-share hotness in ppm; padded/invalid nodes encode key -1
    util = jnp.where(alloc[:2] > 0,
                     (used[:2] * ANALYTICS_UTIL_SCALE)
                     // jnp.maximum(alloc[:2], 1), 0)
    score = jnp.clip(jnp.maximum(util[0], util[1]),
                     0, ANALYTICS_UTIL_SCALE)
    hot = encode_topk_keys(score, gidx, mask)
    cold = encode_topk_keys(ANALYTICS_UTIL_SCALE - score, gidx, mask)
    hot_keys = _merged_top_k(hot, k, axis)
    cold_keys = _merged_top_k(cold, k, axis)

    return AnalyticsStats(
        alloc=_ax_sum(alloc.sum(axis=1), axis),
        used=_ax_sum(used.sum(axis=1), axis),
        free_sum=_ax_sum(free.sum(axis=1), axis),
        free_max=_ax_max(free.max(axis=1), axis),
        headroom_nodes=_ax_sum(
            (free > 0).sum(axis=1).astype(jnp.int64), axis),
        feasible_nodes=_ax_sum(((free[0] > 0) & (free[1] > 0)
                                & (free[4] > 0)).sum().astype(jnp.int64),
                               axis),
        valid_nodes=_ax_sum(mask.sum().astype(jnp.int64), axis),
        hot_keys=hot_keys,
        cold_keys=cold_keys)


analytics_reduce = partial(jax.jit, static_argnames=("k",))(
    _analytics_reduce_impl)


_ANALYTICS_SHARDED_PROGRAMS: dict = {}


def analytics_reduce_sharded(mesh, inp: AnalyticsIn, n_valid, *, k: int,
                             axis: str = "node"):
    """Two-level analytics reduction over a node-sharded AnalyticsIn: each
    shard folds its block (sums/maxes/counts + a local top-k of packed keys
    carrying GLOBAL node indices), then psum/pmax/all_gather-merge — the
    result is bit-identical to `analytics_reduce` on the unsharded columns,
    so obs/analytics.py's host mirror verifies it unchanged."""
    key = (mesh, k, axis)
    fn = _ANALYTICS_SHARDED_PROGRAMS.get(key)
    if fn is None:
        from jax.sharding import PartitionSpec as P

        sm = jax.shard_map(
            partial(_analytics_reduce_impl, k=k, axis=axis), mesh=mesh,
            in_specs=(AnalyticsIn(*(P(axis) for _ in AnalyticsIn._fields)),
                      P()),
            out_specs=AnalyticsStats(*(P() for _ in AnalyticsStats._fields)),
            check_vma=False)
        fn = jax.jit(sm)
        _ANALYTICS_SHARDED_PROGRAMS[key] = fn
    return fn(inp, n_valid)


# --------------------------------------------------------------------------
# Gang admission (ISSUE 15): member lanes + joint rank-aware packing.
#
# A gang decision is two programs. First, `gang_lanes` vmaps the fused
# scan's filter/score stage over the member rows against ONE frozen carry —
# every member sees the identical snapshot, so the lanes are a consistent
# (member, node) feasibility/score matrix, not a sequence of stale reads.
# Second, `gang_select` solves the joint placement: a fori_loop over members
# in feed order that packs each one onto the highest-ranked node, where the
# rank key prefers zone domains and then rack domains already holding
# placed mates and breaks ties by the scan's own score. Capacity is
# re-checked arithmetically as members stack (the same resource-arithmetic
# reprieve `_preempt_select_impl` applies to victims: cpu/mem/gpu/eph +
# pod count; presence-dependent predicates are frozen at lane time — a
# documented gang deviation). The host oracle in tpusim/gang/oracle.py
# mirrors this loop in numpy with identical int64 arithmetic, so
# device-vs-host choices are bit-exact, not within-epsilon.

# Rank-key layout (int64): zone-mate count, then rack-mate count, then the
# clipped scan score; -1 marks an infeasible/over-capacity node. First-
# occurrence argmax resolves ties identically in numpy and XLA. The
# encode (and the GANG_* constants re-exported above for gang/oracle.py)
# lives in jaxe/packing.py, shared with the numpy mirror.


class GangIn(NamedTuple):
    """Per-node columns the packing solve reads ([N] each)."""

    alloc_cpu: jnp.ndarray
    alloc_mem: jnp.ndarray
    alloc_gpu: jnp.ndarray
    alloc_eph: jnp.ndarray
    allowed_pods: jnp.ndarray
    used_cpu: jnp.ndarray
    used_mem: jnp.ndarray
    used_gpu: jnp.ndarray
    used_eph: jnp.ndarray
    pod_count: jnp.ndarray
    zone_dom: jnp.ndarray   # int32, 0 = no zone domain
    rack_dom: jnp.ndarray   # int32, 0 = no rack domain


def gang_columns(statics: Statics, carry: Carry, zone_dom, rack_dom) -> GangIn:
    """Pack a GangIn from an engine (Statics, Carry) pair plus the packing
    domain ids computed by the gang driver (plain field references)."""
    return GangIn(
        alloc_cpu=statics.alloc_cpu, alloc_mem=statics.alloc_mem,
        alloc_gpu=statics.alloc_gpu, alloc_eph=statics.alloc_eph,
        allowed_pods=statics.allowed_pods,
        used_cpu=carry.used_cpu, used_mem=carry.used_mem,
        used_gpu=carry.used_gpu, used_eph=carry.used_eph,
        pod_count=carry.pod_count,
        zone_dom=zone_dom, rack_dom=rack_dom)


def _gang_lanes_impl(config: EngineConfig, carry: Carry, statics: Statics,
                     xs: PodX):
    """(feasible[M, N], score[M, N]): the fused scan's filter/score stage for
    each member against the SAME carry. Only the two lanes the packing solve
    consumes are returned — reason decoding for a rejected gang is the
    driver's single shared FitError, not a per-member histogram."""

    def lanes(x: PodX):
        feasible, _bits, score, _n, _aca, _parts = _evaluate(
            config, carry, statics, x)
        return feasible, score

    return jax.vmap(lanes)(xs)


gang_lanes = partial(jax.jit, static_argnames=("config",))(_gang_lanes_impl)


_GANG_LANES_SHARDED_PROGRAMS: dict = {}


def gang_lanes_sharded(config: EngineConfig, mesh, carry: Carry,
                       statics: Statics, xs: PodX):
    """Cross-shard gang lanes (ISSUE 16 sub-problem b): the member vmap
    runs per shard over its node block (with config.shard_axis collectives
    globalizing the filter/score reductions), and the stitched out_specs
    all_gather the node axis — every host then holds the full (member,
    node) feasible/score matrix and ONE `gang_select` packer pass decides
    jointly, bit-identical to single-device lanes."""
    if config.shard_axis is None:
        raise ValueError("gang_lanes_sharded requires config.shard_axis")
    key = (config, mesh)
    fn = _GANG_LANES_SHARDED_PROGRAMS.get(key)
    if fn is None:
        from jax.sharding import PartitionSpec as P

        st_specs, ca_specs, xs_specs = node_partition_specs(config.shard_axis)
        sm = jax.shard_map(
            partial(_gang_lanes_impl, config), mesh=mesh,
            in_specs=(ca_specs, st_specs, xs_specs),
            out_specs=(P(None, config.shard_axis),
                       P(None, config.shard_axis)),
            check_vma=False)
        fn = jax.jit(sm)
        _GANG_LANES_SHARDED_PROGRAMS[key] = fn
    return fn(carry, statics, xs)


def _gang_select_impl(feasible, score, req_cpu, req_mem, req_gpu, req_eph,
                      zero_request, gi: GangIn, n_zone: int, n_rack: int):
    """Joint greedy packing over the (member, node) lanes. Returns
    choices[M] (node index or -1). Members are visited in feed order; each
    placement feeds the next member's domain bonuses and capacity stack."""
    m, n = feasible.shape
    del n  # shapes are static under jit; n documents the lane width

    def body(i, state):
        (gang_cpu, gang_mem, gang_gpu, gang_eph, gang_pods,
         zone_cnt, rack_cnt, choices) = state
        fits = (gi.pod_count + gang_pods + 1) <= gi.allowed_pods
        check = ~zero_request[i]
        fits &= ~check | (gi.alloc_cpu >= gi.used_cpu + gang_cpu + req_cpu[i])
        fits &= ~check | (gi.alloc_mem >= gi.used_mem + gang_mem + req_mem[i])
        fits &= ~check | (gi.alloc_gpu >= gi.used_gpu + gang_gpu + req_gpu[i])
        fits &= ~check | (gi.alloc_eph >= gi.used_eph + gang_eph + req_eph[i])
        ok = feasible[i] & fits
        zone_bonus = jnp.where(gi.zone_dom > 0, zone_cnt[gi.zone_dom], 0)
        rack_bonus = jnp.where(gi.rack_dom > 0, rack_cnt[gi.rack_dom], 0)
        rank = encode_gang_rank(zone_bonus, rack_bonus, score[i], ok)
        choice = jnp.argmax(rank).astype(jnp.int32)
        found = rank[choice] >= 0
        idx = jnp.maximum(choice, 0)
        gate = found.astype(jnp.int64)
        gate32 = found.astype(jnp.int32)
        # domain slot 0 is the "no domain" bucket: incrementing it is
        # harmless because the bonus reads above gate on dom > 0
        return (gang_cpu.at[idx].add(gate * req_cpu[i]),
                gang_mem.at[idx].add(gate * req_mem[i]),
                gang_gpu.at[idx].add(gate * req_gpu[i]),
                gang_eph.at[idx].add(gate * req_eph[i]),
                gang_pods.at[idx].add(gate),
                zone_cnt.at[gi.zone_dom[idx]].add(gate32),
                rack_cnt.at[gi.rack_dom[idx]].add(gate32),
                choices.at[i].set(jnp.where(found, choice, -1)))

    n_nodes = gi.alloc_cpu.shape[0]
    init = (jnp.zeros(n_nodes, dtype=jnp.int64),
            jnp.zeros(n_nodes, dtype=jnp.int64),
            jnp.zeros(n_nodes, dtype=jnp.int64),
            jnp.zeros(n_nodes, dtype=jnp.int64),
            jnp.zeros(n_nodes, dtype=jnp.int64),
            jnp.zeros(n_zone, dtype=jnp.int32),
            jnp.zeros(n_rack, dtype=jnp.int32),
            jnp.full(m, -1, dtype=jnp.int32))
    state = jax.lax.fori_loop(0, m, body, init)
    return state[-1]


gang_select = partial(jax.jit, static_argnames=("n_zone", "n_rack"))(
    _gang_select_impl)

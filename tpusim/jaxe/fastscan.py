"""Pallas TPU fast path for the exact sequential scan.

The production `schedule_scan` is a `lax.scan` whose per-pod step is one XLA
while-loop iteration; on TPU each iteration pays loop/dispatch overhead that
dwarfs the [N]-wide arithmetic (measured ~94us/pod at 10k nodes vs ~5us of
compute). This module runs the same step as a single Pallas kernel with a
grid over the pod axis: the carry ([N]-sized node state) lives in VMEM
output blocks that persist across grid steps, pod scalars and pregathered
signature-table rows stream in via the grid pipeline, and each step is pure
VPU work — no per-pod dispatch.

Semantics are IDENTICAL to the XLA path for eligible workloads (differential
tests drive both); ineligible workloads fall back to `schedule_scan`.

Eligibility (checked by `plan_fast`, reasons returned):
  * pod-group features run natively through a [Gpad, Npad] presence carry
    (round 4): host ports, NoDiskConflict, services/SelectorSpreadPriority
    (incl. the zone blend), and NoVolumeZoneConflict — per-pod group rows
    stream through SMEM and all group state is accessed via statically
    -unrolled loops over Gpad with (g == gid)-masked row ops (no dynamic
    indexing; Mosaic-safe). Bounded by TPUSIM_FAST_MAX_GROUPS (32) merged
    groups / TPUSIM_FAST_MAX_ZONES (16) zone domains, and the spread
    blend's int32 product bound;
  * inter-pod (anti)affinity runs natively (round 5): own terms via per
    -pod match rows + D scalar segment reductions over the presence carry,
    the existing-pods side via a [Gpad*Kd, Dpad] presence_dom carry with
    per-(group, term) constants baked into the kernel variant, and
    InterPodAffinityPriority in exact int32 — bounded by
    TPUSIM_FAST_MAX_TOPO_KEYS (4), _MAX_TOPO_DOMS (64), _MAX_TERMS (4)
    and an int32 weight-mass bound. A kubernetes.io/hostname key whose
    values are one per node is node-wise (nodewise_keys): its domains are
    nodes, so both sides read the presence carry at the node and the key
    has no presence_dom rows and no domains in D. _MAX_TOPO_DOMS counts
    only the other keys' domains;
  * MaxPD volume counts run natively (round 5): the [N, V] used-volume
    union as a [Vpad, Npad] bit carry with baked type triples/limits,
    bounded by TPUSIM_FAST_MAX_VOLS (32);
  * POLICIES compile into the kernel in full (rounds 5-6): the PolicySpec
    (predicate subset incl. individually-named GeneralPredicates parts,
    priority weights, per-type MaxPD enables, hard weight) is baked into
    the kernel variant like the interpod constants, and the round-6
    residue classes all run natively — label-presence predicate rows and
    the NoExecute-only taint table as static mask stages at their
    ordering slots, NodeLabel/LabelPreference priorities as a pre
    -weighted score row, ImageLocality through the signature-table
    streaming path, ServiceAntiAffinity via per-pod first-service rows
    over the presence carry, ServiceAffinity predicates via pin/value
    label rows plus first-matching-pod lock slots riding the misc carry
    lanes (bounded by TPUSIM_FAST_MAX_SA_SEGS, default 16), and
    alwaysCheckAllPredicates count-mode by keeping every stage's failure
    bits live through the full chain. Only extenders stay host-bound
    (they call out to HTTP processes — no device analog);
  * every resource quantity reduces exactly to int32: values are divided by
    the per-axis gcd (exact — fractions and fit comparisons are
    unit-invariant) and the reduced values must stay under 2^29, with the
    BalancedResourceAllocation product bound 10*max_cpu*max_mem < 2^31
    (Mosaic has no 64-bit integers, so the kernel is int32 throughout;
    DEVIATIONS.md #16's exactness contract is preserved because the reduced
    arithmetic never overflows);
  * scalar (extended) resources ARE eligible: each scalar axis gcd-reduces
    independently like cpu/mem (PodFitsResources treats every scalar as one
    more fit column, predicates.go:706-776), and its failure bit rides at
    NUM_FIXED_BITS+s — at most PAD_SENTINEL_BIT-NUM_FIXED_BITS (=6) scalar
    kinds fit the int32 reason word; more falls back to the XLA scan.

Reference mapping (same as kernels._evaluate for this subset):
  CheckNodeCondition/Unschedulable -> cond_fail_bits stage
  GeneralPredicates (resources, hostname, selector+affinity) -> stage 2
    (predicates.go:1059-1123, :659-776, :780-865)
  PodToleratesNodeTaints (predicates.go:1465-1493) -> stage 3
  CheckNodeMemory/DiskPressure (predicates.go:1502-1541) -> stages 4-5
  Least/MostRequested, BalancedResourceAllocation, NodeAffinity,
  TaintToleration normalizes, NodePreferAvoidPods -> int32 score sum
  selectHost round-robin tie-break (generic_scheduler.go:183-198) -> masked
    argmax + rank-k tie pick carried through the VMEM rr cell
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass
from functools import lru_cache
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from tpusim.api.types import LABEL_HOSTNAME
from tpusim.engine.predicates import (
    CHECK_NODE_DISK_PRESSURE_PRED,
    CHECK_NODE_LABEL_PRESENCE_PRED,
    CHECK_NODE_MEMORY_PRESSURE_PRED,
    CHECK_NODE_UNSCHEDULABLE_PRED,
    CHECK_SERVICE_AFFINITY_PRED,
    CHECK_VOLUME_BINDING_PRED,
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
from tpusim.jaxe.state import NUM_FIXED_BITS, CompiledCluster, PodColumns
from tpusim.jaxe.kernels import (
    AVOID_PODS_WEIGHT,
    MAX_PRIORITY,
    EngineConfig,
)
from tpusim.jaxe.state import (
    BIT_AFFINITY_NOT_MATCH,
    BIT_MAX_VOLUME_COUNT,
    BIT_AFFINITY_RULES,
    BIT_ANTI_AFFINITY_RULES,
    BIT_DISK_CONFLICT,
    BIT_DISK_PRESSURE,
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
    BIT_VOLUME_ZONE_CONFLICT,
)
from tpusim.obs import recorder as flight

INT_LIMIT = 1 << 29          # per-value bound after gcd reduction
GHOST_REQ = 1 << 30          # > any reduced allocatable: never feasible
PAD_SENTINEL_BIT = 30        # cond bit for padded nodes; >= last scalar bit
LANES = 128
SUBLANES = 8                 # scalar-axis row padding (TPU sublane tile)


@dataclass
class FastPlan:
    """int32 device-ready arrays; node axis padded to a multiple of 128."""

    num_nodes: int           # real nodes (pad rows follow)
    num_pods: int
    most_requested: bool
    num_scalars: int         # scalar-resource kinds (0 = no scalar args)
    # statics [1, Npad]
    alloc_cpu: np.ndarray
    alloc_mem: np.ndarray
    alloc_gpu: np.ndarray
    alloc_eph: np.ndarray
    allowed: np.ndarray
    cond_bits: np.ndarray
    mem_pressure: np.ndarray
    disk_pressure: np.ndarray
    # signature tables [S, Npad]
    selector_ok: np.ndarray
    taint_ok: np.ndarray
    intolerable: np.ndarray
    aff_count: np.ndarray
    avoid_score: np.ndarray
    host_ok: np.ndarray
    # initial carry [1, Npad]
    used_cpu: np.ndarray
    used_mem: np.ndarray
    used_gpu: np.ndarray
    used_eph: np.ndarray
    nonzero_cpu: np.ndarray
    nonzero_mem: np.ndarray
    pod_count: np.ndarray
    # pod columns [P]
    req_cpu: np.ndarray
    req_mem: np.ndarray
    req_gpu: np.ndarray
    req_eph: np.ndarray
    nz_cpu: np.ndarray
    nz_mem: np.ndarray
    zero_request: np.ndarray
    best_effort: np.ndarray
    sel_id: np.ndarray
    tol_id: np.ndarray
    aff_id: np.ndarray
    avoid_id: np.ndarray
    host_id: np.ndarray
    # scalar resources (present when num_scalars > 0)
    alloc_scalar: Optional[np.ndarray] = None   # [Srows, Npad]
    used_scalar: Optional[np.ndarray] = None    # [Srows, Npad] init carry
    req_scalar: Optional[np.ndarray] = None     # [P, S]; chunks pad to LANES
    # pod-group features (num_groups == 0 -> group-free kernel). Presence is
    # a [Gpad, Npad] int32 carry; per-pod group rows are SMEM-streamed
    # scalars read in a statically-unrolled loop over Gpad (no dynamic
    # indexing — Mosaic-safe), and the bind updates presence via
    # (g == gid) masked whole-row adds.
    num_groups: int = 0          # Gpad (sublane-padded merged group count)
    has_ports: bool = False
    has_disk: bool = False
    has_spread: bool = False
    has_vol_zone: bool = False
    presence: Optional[np.ndarray] = None    # [Gpad, Npad] int32 init carry
    gid: Optional[np.ndarray] = None         # [P] int32 merged group id
    port_row: Optional[np.ndarray] = None    # [P, Gpad] int32 0/1 conflicts
    disk_row: Optional[np.ndarray] = None    # [P, Gpad] int32 0/1 conflicts
    ss_row: Optional[np.ndarray] = None      # [P, Gpad] int32 0/1 spread set
    zone_ok_tbl: Optional[np.ndarray] = None  # [G, Npad] int32 0/1 by gid
    zone_onehot: Optional[np.ndarray] = None  # [Zpad, Npad] int32; row 0 =
    #                                           the unlabeled dom-0 sentinel
    n_zone_doms: int = 0         # Zpad (sublane-padded)
    # per-axis gcds the int32 reduction divided by — the preemption hybrid
    # re-arms its carry from refreshed ORIGINAL-unit aggregates by dividing
    # through these (exact when every placed pod's request was folded into
    # the gcd via plan_fast's placed_pods; rearm_carry verifies anyway)
    gcds: Tuple[int, int, int, int] = (1, 1, 1, 1)   # cpu, mem, gpu, eph
    scalar_gcds: Tuple[int, ...] = ()
    # statically-gateable policy (round 5): the PolicySpec itself (hashable)
    # is baked into the kernel variant — stage gating + score weights
    policy: Optional[object] = None
    # inter-pod (anti)affinity (round 5). Own required/preferred terms run
    # through per-pod match rows + domain segment sums recomputed from the
    # presence carry (dc_at == broadcast-back of the per-domain sums of
    # mcount — identical to the XLA path's _seg_rows + take_along_axis);
    # the existing-pods side (their anti-affinity / preferred terms vs ME)
    # rides a [Gpad*K, Dpad] presence_dom carry with the per-(group, term)
    # keys, weights, and validity masks baked into the kernel as static
    # constants, so per-pod operands reduce to pure match bits.
    has_interpod: bool = False
    n_topo_keys: int = 0           # K (rows of topo_dom and topo_rows)
    # per key: True for a node-wise key (kubernetes.io/hostname with a value
    # of its own on every labelled node): its domains are single nodes, so
    # its terms read the presence carry and it has no presence_dom rows
    topo_nodewise: Tuple[bool, ...] = ()
    n_topo_doms_ip: int = 0        # REAL domain count of the non-node-wise
    #                                keys incl. the invalid-0 bucket (the
    #                                unroll bound; presence_dom's lane axis
    #                                is padded to 128 separately)
    hostname_terms: int = 0        # terms on a node-wise key, over groups
    ta: int = 0                    # own required-affinity term slots
    tb: int = 0                    # own required-anti-affinity term slots
    tp: int = 0                    # own preferred term slots
    hard_weight: int = 10
    # [Kpad8, Npad] int32: dom ids, or for a node-wise key 1 where the node
    # has the label
    topo_rows: Optional[np.ndarray] = None
    # [Gpad*Kd, Dpad] int32 init over the Kd non-node-wise keys (None when
    # every key is node-wise)
    presence_dom: Optional[np.ndarray] = None
    ipod: Optional[np.ndarray] = None            # [P, Wip] per-pod packed row
    # static exist-side tables (baked into the kernel; part of its cache key)
    exist_anti_key: Tuple[int, ...] = ()     # [G*Tb] topo-key per term
    exist_anti_mask: Tuple[int, ...] = ()    # [G*Tb] valid & ~empty
    exist_anti_empty: Tuple[int, ...] = ()   # [G*Tb] valid & empty (fail_all)
    exist_pref_key: Tuple[int, ...] = ()     # [G*Tp]
    exist_pref_w: Tuple[int, ...] = ()       # [G*Tp] signed int weights
    exist_aff_key: Tuple[int, ...] = ()      # [G*Ta]
    exist_aff_mask: Tuple[int, ...] = ()     # [G*Ta] valid & ~empty
    # Max{EBS,GCEPD,AzureDisk}VolumeCount (round 5): the [N, V] per-node
    # used-volume union becomes a [Vpad8, Npad] 0/1 carry; per-pod volume
    # masks ride a [Gpad?, Vpad] group table gathered by group id (maxpd
    # needs no presence), and the per-volume type triples + per-type
    # limits are baked into the kernel variant.
    has_maxpd: bool = False
    maxpd_enabled: Tuple[bool, bool, bool] = (True, True, True)
    n_vols: int = 0                          # V real volume ids
    used_vols: Optional[np.ndarray] = None   # [Vpad8, Npad] init carry
    vol_tbl: Optional[np.ndarray] = None     # [G, Vpad] mask by group id
    vol_type3: Tuple[int, ...] = ()          # [V*3] type bits (EBS,GCE,AZ)
    maxpd_limits: Tuple[int, int, int] = (0, 0, 0)
    # full policy residue (round 6): label-presence mask rows, the pre
    # -weighted NodeLabel/LabelPreference priority row, the ImageLocality
    # signature table, the NoExecute-only taint table, ServiceAntiAffinity
    # label domains, and the ServiceAffinity pin/value/lock tables. All
    # int32, node axis padded to Npad; ServiceAffinity locks ride the misc
    # carry lanes 1..Fd (first-matching-pod node index, -1 unlocked, -2
    # permanently unpinned).
    label_tbl: Optional[np.ndarray] = None      # [Lpad8, Npad] 0/1 pass
    label_prio_row: Optional[np.ndarray] = None  # [1, Npad] pre-weighted
    image_tbl: Optional[np.ndarray] = None      # [Si, Npad] by img_id
    img_id: Optional[np.ndarray] = None         # [P] int32
    noexec_tbl: Optional[np.ndarray] = None     # [Ctol, Npad] by tol_id
    saa_row: Optional[np.ndarray] = None        # [P, Gpad] first-service row
    saa_dom_tbl: Optional[np.ndarray] = None    # [Epad8, Npad] label doms
    n_saa_doms: int = 0                         # unroll bound (incl. dom 0)
    sa_sig: Optional[np.ndarray] = None         # [P] first-service sig id
    sa_pin_row: Optional[np.ndarray] = None     # [P, La8] own selector pins
    sa_match_row: Optional[np.ndarray] = None   # [P, Fd8] bind match bits
    sa_val_tbl: Optional[np.ndarray] = None     # [Lapad8, Npad] label values
    sa_lock_init: Optional[np.ndarray] = None   # [Fd] int32 lock seeds
    sa_la: int = 0                              # real concatenated SA labels


@dataclass
class FastCarry:
    """Device/host carry state threaded through fast_scan calls: the seven
    [1, Npad] node rows, the rr misc row, and the optional scalar / group
    -presence rows. Arrays may be numpy (fresh/re-armed) or jax device
    arrays (chained from a previous call's carry_out)."""

    rows: list               # [used_c, used_m, used_g, used_e, nz_c, nz_m, pc]
    misc: object             # [1, LANES] int32; rr at [0, 0]
    scal: Optional[object] = None    # [Srows, Npad] int32
    pres: Optional[object] = None    # [Gpad, Npad] int32
    pd: Optional[object] = None      # [Gpad*Kd, Dpad] int32 (interpod)
    uv: Optional[object] = None      # [Vpad8, Npad] 0/1 int32 (maxpd)


def init_carry(plan: FastPlan, rr: int = 0) -> FastCarry:
    """The carry at the plan's initial cluster state."""
    misc = np.zeros((1, LANES), dtype=np.int32)
    misc[0, 0] = rr
    if plan.sa_lock_init is not None:
        # ServiceAffinity first-matching-pod locks ride misc lanes 1..Fd
        misc[0, 1:1 + len(plan.sa_lock_init)] = plan.sa_lock_init
    return FastCarry(
        rows=[plan.used_cpu, plan.used_mem, plan.used_gpu, plan.used_eph,
              plan.nonzero_cpu, plan.nonzero_mem, plan.pod_count],
        misc=misc,
        scal=plan.used_scalar if plan.num_scalars else None,
        pres=plan.presence if plan.num_groups else None,
        pd=plan.presence_dom,
        uv=plan.used_vols if plan.has_maxpd else None)


def rearm_carry(plan: FastPlan, compiled, rr: int) -> Optional[FastCarry]:
    """Rebuild the carry from a refreshed CompiledCluster's ORIGINAL-unit
    dynamic aggregates (IncrementalCluster.refresh_dynamic after preemption
    churn: binds streamed in as ADDED, victims as DELETED). Every value must
    divide exactly by the plan's per-axis gcd and stay inside the int32
    budget — guaranteed when plan_fast folded all placed pods' requests into
    the gcds, verified here regardless. Returns None when the refreshed
    state can't be expressed in plan units (caller re-plans or falls back).
    """
    if plan.sa_lock_init is not None:
        # ServiceAffinity locks are pod-assignment history the refreshed
        # cluster tables cannot reproduce; SA policies never reach the
        # preemption hybrid (policyc forces preemption_class "general"),
        # so this is a defensive fallback, not a hot path
        return None
    d = compiled.dynamic
    n = plan.num_nodes
    npad = plan.alloc_cpu.shape[1]

    def reduce_row(agg, g):
        a = np.asarray(agg, dtype=np.int64)
        if g > 1:
            if (a % g).any():
                return None
            a = a // g
        if a.size and int(a.max(initial=0)) >= INT_LIMIT:
            return None
        out = np.zeros((1, npad), dtype=np.int32)
        out[0, :n] = a.astype(np.int32)
        return out

    gc, gm, gg, ge = plan.gcds
    rows = [reduce_row(d.used_cpu, gc), reduce_row(d.used_mem, gm),
            reduce_row(d.used_gpu, gg), reduce_row(d.used_eph, ge),
            reduce_row(d.nonzero_cpu, gc), reduce_row(d.nonzero_mem, gm),
            reduce_row(d.pod_count, 1)]
    if any(r is None for r in rows):
        return None
    scal = None
    if plan.num_scalars:
        srows = plan.used_scalar.shape[0]
        scal = np.zeros((srows, npad), dtype=np.int32)
        us = np.asarray(d.used_scalar, dtype=np.int64)
        for si, g in enumerate(plan.scalar_gcds):
            col = us[:, si]
            if g > 1:
                if (col % g).any():
                    return None
                col = col // g
            if col.size and int(col.max(initial=0)) >= INT_LIMIT:
                return None
            scal[si, :n] = col.astype(np.int32)
    pres = pd = None
    if plan.num_groups:
        gt = compiled.groups
        if gt.presence.shape[0] > plan.num_groups:
            return None  # group universe grew: the plan's rows are stale
        pres = np.zeros((plan.num_groups, npad), dtype=np.int32)
        pres[:gt.presence.shape[0], :n] = gt.presence.astype(np.int32)
        if plan.has_interpod:
            if (gt.topo_dom.shape[0] != plan.n_topo_keys
                    or nodewise_keys(gt) != plan.topo_nodewise):
                return None  # topology-key universe changed
            if plan.presence_dom is not None:
                pd = embed_presence_dom(
                    gt.presence, domain_rows(gt.topo_dom, plan.topo_nodewise),
                    plan.n_topo_doms_ip, plan.num_groups,
                    plan.presence_dom.shape[1])
    uv = None
    if plan.has_maxpd:
        gt = compiled.groups
        if gt.vol_mask.shape[1] != plan.n_vols:
            return None  # volume-id universe changed
        # valid because refresh_dynamic only succeeds with CLEAN group
        # tables: a volume-carrying bind or victim dirties them and forces
        # the full recompile path instead
        uv = np.zeros_like(plan.used_vols)
        uv[:plan.n_vols, :plan.num_nodes] = \
            gt.used_vols_init.T.astype(np.int32)
    misc = np.zeros((1, LANES), dtype=np.int32)
    misc[0, 0] = rr
    return FastCarry(rows=rows, misc=misc, scal=scal, pres=pres, pd=pd,
                     uv=uv)


class IpLayout:
    """Static offsets into the per-pod packed interpod row (int32 lanes).

    Own-term data (my group's required affinity / anti-affinity / preferred
    terms): match bits vs every group, topo-key ids, and flag bits. Exist
    -side data (other groups' terms evaluated against ME): pure match bits
    — their keys, weights, and validity masks are compile-time constants
    baked into the kernel."""

    def __init__(self, ta: int, tb: int, tp: int, gpad: int):
        off = 0

        def take(n):
            nonlocal off
            at = off
            off += n
            return at

        self.aff_match = take(ta * gpad)    # [t*gpad+g]
        self.aff_key = take(ta)
        self.aff_valid = take(ta)
        self.aff_empty = take(ta)
        self.aff_host = take(ta)
        self.aff_self = take(ta)
        self.aff_unpl = take(ta)
        self.aff_err = take(1)
        self.anti_match = take(tb * gpad)
        self.anti_key = take(tb)
        self.anti_valid = take(tb)
        self.anti_host = take(tb)
        self.anti_err = take(1)
        self.pref_match = take(tp * gpad)
        self.pref_key = take(tp)
        self.pref_w = take(tp)              # signed int weights
        self.ex_anti = take(gpad * tb)      # [g*tb+t] term matches ME
        self.ex_pref = take(gpad * tp)
        self.ex_aff = take(gpad * ta)
        self.width = max(-(-off // LANES) * LANES, LANES)


def _gcd_reduce(arrays) -> Tuple[int, list]:
    """gcd over every value in `arrays`; returns (g, arrays // g)."""
    g = 0
    for a in arrays:
        for v in np.unique(np.asarray(a, dtype=np.int64)):
            g = math.gcd(g, int(v))
    if g <= 1:
        return max(g, 1), [np.asarray(a, dtype=np.int64) for a in arrays]
    return g, [np.asarray(a, dtype=np.int64) // g for a in arrays]


def nodewise_keys(gt) -> Tuple[bool, ...]:
    """Per topology key of the group tables: whether it is node-wise —
    kubernetes.io/hostname, with a value of its own on every node that has
    the label (ids are interned 1..V in first-seen order, so V labelled
    nodes hold V values). A domain of such a key is one node: its own terms
    read the node's matched presence, and existing pods' terms on it read
    the presence carry at the node (predicates.go
    NodesHaveSameTopologyKey), so it needs no domain axis. A hostname key
    whose values repeat keeps its domains."""
    out = []
    for k in range(gt.topo_dom.shape[0]):
        row = gt.topo_dom[k]
        key = gt.topo_keys[k] if k < len(gt.topo_keys) else None
        out.append(key == LABEL_HOSTNAME and row.size > 0
                   and int(row.max()) == int(np.count_nonzero(row)))
    return tuple(out)


def domain_rows(topo_dom, nodewise: Tuple[bool, ...]) -> np.ndarray:
    """The topo_dom rows of the keys that keep their domains."""
    return topo_dom[[k for k, nw in enumerate(nodewise) if not nw]]


def embed_presence_dom(presence, topo_dom, d_doms: int, gpad: int,
                      dpad: int) -> np.ndarray:
    """[G, K, D] presence_dom -> the kernel's [Gpad*K, Dpad] row-interleaved
    carry layout (row g*K + k), over the domain keys' topo_dom rows: ONE
    definition shared by plan_fast and rearm_carry so the embedding can
    never diverge between the initial plan and a post-preemption re-arm."""
    from tpusim.jaxe.kernels import _presence_dom_init

    pd3 = _presence_dom_init(presence, topo_dom, d_doms)
    g, k_keys, _ = pd3.shape
    out = np.zeros((gpad * k_keys, dpad), dtype=np.int32)
    out[:g * k_keys, :d_doms] = pd3.reshape(g * k_keys, d_doms)
    return out


def placed_pod_values(placed_pods, scalar_names) -> dict:
    """Per-pod request values of already-placed pods, by axis — folded into
    plan_fast's gcds so a preemption victim's deletion keeps every refreshed
    aggregate an exact multiple of the reduction unit (the staged round-5
    design: victim-adjusted sums then divide exactly)."""
    from tpusim.engine.resources import (
        get_nonzero_pod_request,
        get_resource_request,
    )

    vals = {"cpu": [], "mem": [], "gpu": [], "eph": [],
            "scalar": [[] for _ in scalar_names]}
    idx = {name: i for i, name in enumerate(scalar_names)}
    for pod in placed_pods:
        req = get_resource_request(pod)
        nz = get_nonzero_pod_request(pod)
        vals["cpu"] += [req.milli_cpu, nz.milli_cpu]
        vals["mem"] += [req.memory, nz.memory]
        vals["gpu"].append(req.nvidia_gpu)
        vals["eph"].append(req.ephemeral_storage)
        for name, v in (req.scalar or {}).items():
            if name in idx:
                vals["scalar"][idx[name]].append(v)
    return {"cpu": np.asarray(vals["cpu"], dtype=np.int64),
            "mem": np.asarray(vals["mem"], dtype=np.int64),
            "gpu": np.asarray(vals["gpu"], dtype=np.int64),
            "eph": np.asarray(vals["eph"], dtype=np.int64),
            "scalar": [np.asarray(col, dtype=np.int64)
                       for col in vals["scalar"]]}


def plan_fast(config: EngineConfig, compiled: CompiledCluster,
              cols: PodColumns, placed_pods=None, ptabs=None
              ) -> Tuple[Optional[FastPlan], str]:
    """Build the int32 plan, or (None, reason) when ineligible.

    placed_pods: pods already bound in the snapshot (preemption callers) —
    their per-pod request/nonzero values join the gcd reduction so victim
    deletions keep refreshed aggregates expressible in plan units.

    ptabs: policyc.PolicyTables with the host-built residue-class arrays
    (label rows, label priorities, image scores, ServiceAntiAffinity
    domains, ServiceAffinity pins/values/locks). Required whenever the
    policy uses any residue class; callers without them (the preemption
    hybrid compiles policy-free configs) simply stay on the XLA scan."""
    ps = config.policy
    pol_label = ps is not None and bool(ps.label_rows)
    pol_prio = ps is not None and ps.has_label_prio
    pol_image = ps is not None and bool(ps.w_image)
    pol_saa = ps is not None and bool(ps.saa_weights)
    pol_sa = ps is not None and (ps.sa_enabled or bool(ps.sa_slots))
    pol_noexec = (ps is not None and ps.pred_keys is not None
                  and POD_TOLERATES_NODE_NO_EXECUTE_TAINTS_PRED
                  in ps.pred_keys)
    pol_any = (pol_label or pol_prio or pol_image or pol_saa or pol_sa
               or pol_noexec)
    if pol_any:
        # every residue class compiles into the kernel (round 6) — the
        # remaining rejections are table availability and unroll budgets,
        # never the feature itself
        if ptabs is None:
            return None, ("policy static tables unavailable (caller did "
                          "not supply them)")
        if pol_noexec and not compiled.has_noexec_table:
            return None, "NoExecute taint table not compiled"
        if (pol_sa or pol_saa) and not compiled.has_saa_table:
            return None, "ServiceAffinity signature tables not compiled"
        if pol_sa:
            fd_real = int(compiled.groups.saa_rows.shape[0])
            la_real = int(sum(ps.sa_segs))
            max_sa = int(os.environ.get("TPUSIM_FAST_MAX_SA_SEGS", 16))
            # lock slots ride misc carry lanes 1..Fd (lane 0 is rr)
            if fd_real > min(max_sa, LANES - 1):
                return None, (f"{fd_real} ServiceAffinity lock segments "
                              f"exceed the fast-path budget "
                              f"({min(max_sa, LANES - 1)}; "
                              "TPUSIM_FAST_MAX_SA_SEGS)")
            if la_real > max_sa:
                return None, (f"{la_real} ServiceAffinity entry labels "
                              f"exceed the fast-path budget ({max_sa}; "
                              "TPUSIM_FAST_MAX_SA_SEGS)")
        if pol_saa:
            max_sz = int(os.environ.get("TPUSIM_FAST_MAX_ZONES", 16))
            if config.n_saa_doms > max_sz:
                return None, (f"{config.n_saa_doms} ServiceAntiAffinity "
                              f"label domains exceed the fast-path budget "
                              f"({max_sz}; TPUSIM_FAST_MAX_ZONES)")
    # maxpd carries a [N, V] per-node volume-id union — beyond the kernel's
    # presence model; every other pod-group feature (ports, disk conflicts,
    # spreading, volume zones, and — round 5 — inter-pod (anti)affinity)
    # runs via the [Gpad, Npad] presence carry (+ the [Gpad*K, Dpad]
    # presence_dom carry for interpod's existing-pods side) when the group
    # count fits the unrolled-loop budget
    if config.has_maxpd:
        n_vols_real = int(compiled.groups.vol_mask.shape[1])
        max_v = int(os.environ.get("TPUSIM_FAST_MAX_VOLS", 32))
        if n_vols_real > max_v:
            return None, (f"{n_vols_real} MaxPD volume ids exceed the "
                          f"fast-path budget ({max_v}; "
                          "TPUSIM_FAST_MAX_VOLS)")
    gt = compiled.groups
    group_bound = (config.has_ports or config.has_services
                   or config.has_disk_conflict or config.has_vol_zone
                   or config.has_interpod or config.has_maxpd or pol_saa)
    # presence is only read by ports/disk/spread/interpod/SAA; a vol-zone
    # -only workload streams per-pod zone rows (gathered by group id from an
    # HBM table) and needs neither the presence carry nor the unrolled
    # budget. SAA reads presence but (on service-less clusters) must not
    # force the bind to UPDATE it — the kernel's presence write mirrors the
    # XLA gate (ports|services|disk|interpod) separately.
    needs_presence = (config.has_ports or config.has_services
                      or config.has_disk_conflict or config.has_interpod
                      or pol_saa)
    num_g = int(gt.presence.shape[0]) if group_bound else 0
    if needs_presence:
        max_g = int(os.environ.get("TPUSIM_FAST_MAX_GROUPS", 32))
        if num_g > max_g:
            return None, (f"{num_g} pod groups exceed the fast-path "
                          f"unrolled-loop budget ({max_g}; "
                          "TPUSIM_FAST_MAX_GROUPS)")
        if config.has_services:
            max_z = int(os.environ.get("TPUSIM_FAST_MAX_ZONES", 16))
            if config.n_zone_doms > max_z:
                return None, (f"{config.n_zone_doms} zone domains exceed "
                              f"the fast-path budget ({max_z})")
    ip_dims = None
    if config.has_interpod:
        k_keys = int(gt.topo_dom.shape[0])
        nodewise = nodewise_keys(gt)
        # the kernel unrolls over the domains of the other keys only
        doms = {k: int(gt.topo_dom[k].max(initial=0)) + 1
                for k in range(k_keys) if not nodewise[k]}
        d_doms = max(doms.values(), default=1)
        ta = int(gt.aff_valid.shape[1])
        tb = int(gt.anti_valid.shape[1])
        tp = int(gt.pref_w.shape[1])
        max_k = int(os.environ.get("TPUSIM_FAST_MAX_TOPO_KEYS", 4))
        max_d = int(os.environ.get("TPUSIM_FAST_MAX_TOPO_DOMS", 64))
        max_t = int(os.environ.get("TPUSIM_FAST_MAX_TERMS", 4))
        if k_keys > max_k:
            return None, (f"{k_keys} topology keys exceed the fast-path "
                          f"budget ({max_k}; TPUSIM_FAST_MAX_TOPO_KEYS)")
        if d_doms > max_d:
            k_max = max(doms, key=doms.get, default=None)
            on_key = ("" if k_max is None else " on key %r" % (
                gt.topo_keys[k_max] if k_max < len(gt.topo_keys)
                else f"#{k_max}"))
            return None, (f"{d_doms} topology domains exceed the fast-path "
                          f"budget ({max_d}; TPUSIM_FAST_MAX_TOPO_DOMS)"
                          f"{on_key}")
        if max(ta, tb, tp) > max_t:
            return None, (f"{max(ta, tb, tp)} inter-pod terms exceed the "
                          f"fast-path budget ({max_t}; "
                          "TPUSIM_FAST_MAX_TERMS)")
        if not np.all(gt.pref_w == np.round(gt.pref_w)):
            return None, "non-integral preferred inter-pod weights"
        # InterPodAffinityPriority counts stay int32: bound |counts| by the
        # total weight mass times the largest possible pod population
        total_pods = int(gt.presence.sum()) + len(np.asarray(cols.req_cpu))
        w_own = int(np.abs(gt.pref_w).sum(axis=1).max(initial=0))
        w_exist = int(np.abs(gt.pref_w).sum()) + config.hard_weight * int(
            (gt.aff_valid & ~gt.aff_empty).sum())
        bound_counts = (w_own + w_exist) * max(total_pods, 1)
        w_ip_eff = 1 if ps is None else max(ps.w_interpod, 1)
        if MAX_PRIORITY * 2 * w_ip_eff * bound_counts >= (1 << 31):
            return None, ("inter-pod priority counts exceed int32 "
                          f"(weight mass {w_own + w_exist} x "
                          f"{total_pods} pods)")
        ip_dims = (k_keys, nodewise, d_doms, ta, tb, tp)
    n_scal = len(compiled.scalar_names)
    if NUM_FIXED_BITS + n_scal > PAD_SENTINEL_BIT:
        return None, (f"{n_scal} scalar resource kinds exceed the int32 "
                      f"reason-bit budget "
                      f"({PAD_SENTINEL_BIT - NUM_FIXED_BITS})")
    s, t, d = compiled.statics, compiled.tables, compiled.dynamic

    placed = (placed_pod_values(placed_pods, compiled.scalar_names)
              if placed_pods else None)

    def axis(key):
        # extra per-placed-pod values join the gcd but are discarded after
        # (only the gcd itself matters for them)
        return [placed[key]] if placed is not None else []

    g_cpu, (ac, rc, nzc, uc, nzuc, *_) = _gcd_reduce(
        [s.alloc_cpu, cols.req_cpu, cols.nz_cpu, d.used_cpu, d.nonzero_cpu]
        + axis("cpu"))
    g_mem, (am, rm, nzm, um, nzum, *_) = _gcd_reduce(
        [s.alloc_mem, cols.req_mem, cols.nz_mem, d.used_mem, d.nonzero_mem]
        + axis("mem"))
    g_gpu, (ag, rg, ug, *_) = _gcd_reduce(
        [s.alloc_gpu, cols.req_gpu, d.used_gpu] + axis("gpu"))
    g_eph, (ae, re_, ue, *_) = _gcd_reduce(
        [s.alloc_eph, cols.req_eph, d.used_eph] + axis("eph"))
    # each scalar axis reduces independently (fit comparisons never mix axes)
    scal_cols = []
    scal_gcds = []
    if n_scal:
        ascal = np.asarray(s.alloc_scalar, dtype=np.int64).reshape(-1, n_scal)
        rscal = np.asarray(cols.req_scalar, dtype=np.int64).reshape(-1, n_scal)
        uscal = np.asarray(d.used_scalar, dtype=np.int64).reshape(-1, n_scal)
        for si in range(n_scal):
            extra = [placed["scalar"][si]] if placed is not None else []
            g_s, (a_s, r_s, u_s, *_) = _gcd_reduce(
                [ascal[:, si], rscal[:, si], uscal[:, si]] + extra)
            scal_cols.append((a_s, r_s, u_s))
            scal_gcds.append(g_s)

    checks = [("cpu", (ac, rc, nzc, uc, nzuc)),
              ("memory", (am, rm, nzm, um, nzum)),
              ("gpu", (ag, rg, ug)), ("ephemeral", (ae, re_, ue))]
    checks += [(compiled.scalar_names[si], scal_cols[si])
               for si in range(n_scal)]
    for name, arrs in checks:
        for a in arrs:
            if a.size and int(a.max(initial=0)) >= INT_LIMIT:
                return None, f"{name} values exceed int32 after gcd reduction"
    # BalancedResourceAllocation products must fit int32 including the
    # nonzero totals (which can exceed allocatable; bounded by allowed_pods
    # extra defaulted requests per node)
    allowed_max = int(np.max(s.allowed_pods, initial=0))
    bound_c = int(ac.max(initial=0)) + allowed_max * int(
        max(nzc.max(initial=0), nzuc.max(initial=0), 0))
    bound_m = int(am.max(initial=0)) + allowed_max * int(
        max(nzm.max(initial=0), nzum.max(initial=0), 0))
    if 10 * bound_c * bound_m >= (1 << 31):
        return None, "balanced-allocation product exceeds int32"
    if ps is not None:
        # the weighted sum of 0..MAX_PRIORITY components must stay int32
        # (each component is bounded by MAX_PRIORITY after its normalize;
        # avoid rides its own table check below via the policy weight).
        # Residue-class score rows join the mass: label priorities and
        # image scores are pre-computed host-side, SAA contributes another
        # 0..MAX_PRIORITY component per entry.
        w_total = (ps.w_least + ps.w_most + ps.w_balanced + ps.w_node_aff
                   + ps.w_taint + ps.w_spread + ps.w_interpod
                   + sum(ps.saa_weights))
        pol_mass = 0
        if pol_prio:
            lp64 = np.asarray(ptabs.label_prio, dtype=np.int64)
            if lp64.size and int(lp64.min(initial=0)) < 0:
                # the kernel's argmax uses -1 as the infeasible sentinel
                # (matching the XLA _select); negative scores would
                # collide with it
                return None, ("negative label priority scores exceed the "
                              "fast-path score model")
            pol_mass += int(lp64.max(initial=0))
        if pol_image:
            im64 = np.asarray(ptabs.image_score, dtype=np.int64)
            if ps.w_image < 0 or (im64.size
                                  and int(im64.min(initial=0)) < 0):
                return None, ("negative image-locality scores exceed the "
                              "fast-path score model")
            pol_mass += ps.w_image * int(im64.max(initial=0))
        if pol_saa and ps.saa_weights and min(ps.saa_weights) < 0:
            return None, ("negative ServiceAntiAffinity weights exceed "
                          "the fast-path score model")
        if pol_saa:
            # the SAA normalize multiplies MAX_PRIORITY by the feasible
            # matched-pod total before the divide
            total_pods_saa = (int(gt.presence.sum())
                              + len(np.asarray(cols.req_cpu)))
            if MAX_PRIORITY * max(total_pods_saa, 1) >= (1 << 31):
                return None, ("ServiceAntiAffinity spread counts exceed "
                              f"int32 ({total_pods_saa} pods)")
        if w_total * MAX_PRIORITY + pol_mass >= (1 << 30):
            return None, "policy priority weights exceed the int32 budget"
        if ps.w_balanced and 10 * ps.w_balanced * bound_c * bound_m \
                >= (1 << 31):
            return None, "weighted balanced-allocation exceeds int32"
    w_avoid_eff = AVOID_PODS_WEIGHT if ps is None else ps.w_avoid
    for name, table in (("affinity", t.affinity_count),
                        ("intolerable", t.intolerable),
                        ("avoid", t.avoid_score)):
        if table.size and MAX_PRIORITY * int(np.max(np.abs(table))) * max(
                w_avoid_eff if name == "avoid" else 1, 1) >= (1 << 31):
            return None, f"{name} table exceeds int32"

    n = len(np.asarray(s.alloc_cpu))
    npad = -(-max(n, 1) // LANES) * LANES

    gpad = zpad = 0
    if needs_presence:
        gpad = max(-(-num_g // SUBLANES) * SUBLANES, SUBLANES)
    if group_bound:
        # SelectorSpreadPriority's zone blend multiplies per-node by
        # per-zone counts: bound both from the seeded presence plus the
        # worst case every remaining slot fills with matched pods, and
        # require the blend products to fit int32 (exactness contract)
        if config.has_services:
            col_tot = gt.presence.sum(axis=0).astype(np.int64)  # [N]
            allowed_pods_max = int(np.max(s.allowed_pods, initial=0))
            bound_node = int(col_tot.max(initial=0)) + allowed_pods_max
            zd = np.asarray(gt.zone_dom, dtype=np.int64)
            bound_zone = 1
            for dom in np.unique(zd):
                if dom == 0:
                    # the unlabeled dom-0 sentinel never enters a zone
                    # product (the kernel loops z >= 1; the XLA path zeroes
                    # zcnt[0]) — including it would spuriously reject
                    # zone-label-free clusters at scale
                    continue
                in_dom = zd == dom
                bound_zone = max(bound_zone,
                                 int(col_tot[in_dom].sum())
                                 + int(in_dom.sum()) * allowed_pods_max)
            w_spread_eff = 1 if ps is None else max(ps.w_spread, 1)
            if 3 * MAX_PRIORITY * w_spread_eff * bound_node * bound_zone \
                    >= (1 << 31):
                return None, ("spread zone-blend products exceed int32 "
                              f"(node bound {bound_node} x zone bound "
                              f"{bound_zone})")
            zpad = max(-(-config.n_zone_doms // SUBLANES) * SUBLANES,
                       SUBLANES)

    def node_row(a, fill=0):
        a = np.asarray(a, dtype=np.int64).astype(np.int32)
        out = np.full((1, npad), fill, dtype=np.int32)
        out[0, :n] = a
        return out

    def table_rows(a, fill=0):
        a = np.asarray(a)
        rows = max(a.shape[0], 1)
        out = np.full((rows, npad), fill, dtype=np.int32)
        if a.size:
            out[:a.shape[0], :n] = a.astype(np.int32)
        return out

    cond = node_row(np.asarray(s.cond_fail_bits, dtype=np.int64)
                    .astype(np.int32))
    cond[0, n:] = np.int32(1 << PAD_SENTINEL_BIT)

    def pods(a):
        return np.asarray(a, dtype=np.int64).astype(np.int32)

    alloc_scalar = used_scalar = req_scalar = None
    if n_scal:
        srows = -(-n_scal // SUBLANES) * SUBLANES
        alloc_scalar = np.zeros((srows, npad), dtype=np.int32)
        used_scalar = np.zeros((srows, npad), dtype=np.int32)
        p_count = rscal.shape[0]
        req_scalar = np.zeros((p_count, n_scal), dtype=np.int32)
        for si, (a_s, r_s, u_s) in enumerate(scal_cols):
            alloc_scalar[si, :n] = a_s.astype(np.int32)
            used_scalar[si, :n] = u_s.astype(np.int32)
            req_scalar[:, si] = r_s.astype(np.int32)

    presence = gid = port_row = disk_row = ss_row = None
    zone_ok_tbl = zone_onehot = None
    if group_bound:
        gid = pods(cols.group_id)
    if needs_presence:
        presence = np.zeros((gpad, npad), dtype=np.int32)
        presence[:num_g, :n] = gt.presence.astype(np.int32)

        def per_pod_grow(row_of_group):
            # row_of_group [G, G] -> per-pod [P, Gpad] int32 0/1
            out = np.zeros((len(gid), gpad), dtype=np.int32)
            out[:, :num_g] = row_of_group[gid]
            return out

        if config.has_ports:
            # conflict of MY port set vs each group's port set
            # (kernels._evaluate: port_conflict[port_sig[g]][port_sig])
            port_row = per_pod_grow(
                gt.port_conflict[gt.port_sig][:, gt.port_sig]
                .astype(np.int32))
        if config.has_disk_conflict:
            disk_row = per_pod_grow(
                gt.disk_conflict[gt.disk_sig][:, gt.disk_sig]
                .astype(np.int32))
        if config.has_services:
            ss_row = per_pod_grow(
                gt.ss_rows[gt.ss_sig].astype(np.int32))
            zone_onehot = np.zeros((zpad, npad), dtype=np.int32)
            zd = np.asarray(gt.zone_dom, dtype=np.int64)
            for z in range(config.n_zone_doms):
                zone_onehot[z, :n] = (zd == z).astype(np.int32)
    if config.has_vol_zone:
        zone_ok_tbl = table_rows(gt.zone_ok, fill=0)

    used_vols = vol_tbl = None
    n_vols = 0
    vol_type3 = ()
    mp_limits = (0, 0, 0)
    mp_enabled = (True, True, True)
    if config.has_maxpd and ps is not None and ps.pred_keys is not None:
        mp_enabled = (MAX_EBS_VOLUME_COUNT_PRED in ps.pred_keys,
                      MAX_GCE_PD_VOLUME_COUNT_PRED in ps.pred_keys,
                      MAX_AZURE_DISK_VOLUME_COUNT_PRED in ps.pred_keys)
    if config.has_maxpd and any(mp_enabled):
        n_vols = n_vols_real
        vpad8 = max(-(-n_vols // SUBLANES) * SUBLANES, SUBLANES)
        vpad_l = max(-(-n_vols // LANES) * LANES, LANES)
        used_vols = np.zeros((vpad8, npad), dtype=np.int32)
        used_vols[:n_vols, :n] = gt.used_vols_init.T.astype(np.int32)
        vol_tbl = np.zeros((max(num_g, 1), vpad_l), dtype=np.int32)
        vol_tbl[:num_g, :n_vols] = gt.vol_mask.astype(np.int32)
        vol_type3 = tuple(int(v) for v in
                          np.asarray(gt.vol_type, dtype=np.int64).flatten())
        mp_limits = tuple(int(x) for x in config.maxpd_limits)

    topo_rows = presence_dom = ip_tbl = None
    ip_static = {}
    k_keys = d_doms_real = ta = tb = tp = host_terms = 0
    nodewise = ()
    if config.has_interpod:
        k_keys, nodewise, d_doms, ta, tb, tp = ip_dims
        d_doms_real = d_doms
        kpad8 = max(-(-k_keys // SUBLANES) * SUBLANES, SUBLANES)
        dpad = max(-(-d_doms // LANES) * LANES, LANES)
        topo_rows = np.zeros((kpad8, npad), dtype=np.int32)
        topo_rows[:k_keys, :n] = np.where(
            np.array(nodewise, bool)[:, None], gt.topo_dom > 0,
            gt.topo_dom).astype(np.int32)
        # pad rows and pad nodes keep domain 0 ("label missing": never
        # matches, and pad nodes are infeasible everywhere anyway)
        if not all(nodewise):
            presence_dom = embed_presence_dom(
                gt.presence, domain_rows(gt.topo_dom, nodewise), d_doms,
                gpad, dpad)
        on_node = np.array(nodewise, bool)
        host_terms = int(
            (gt.aff_valid & ~gt.aff_empty & on_node[gt.aff_key]).sum()
            + (gt.anti_valid & ~gt.anti_empty & on_node[gt.anti_key]).sum()
            + ((gt.pref_w != 0) & on_node[gt.pref_key]).sum())
        # every per-pod interpod operand is a pure function of the pod's
        # GROUP, so the packed rows live in a [Gpad, Wip] table gathered by
        # group id per chunk on device — no O(P) host materialization
        L = IpLayout(ta, tb, tp, gpad)
        ip_tbl = np.zeros((gpad, L.width), dtype=np.int32)
        gi = np.arange(num_g)
        tm = gt.term_match.astype(np.int32)            # [Td, G]

        def put(offset, arr):
            a = np.asarray(arr).reshape(num_g, -1).astype(np.int32)
            ip_tbl[:num_g, offset:offset + a.shape[1]] = a

        def pad_groups(a3):
            # [G, T, G] match tensor -> [G, T, Gpad]
            out = np.zeros((num_g, a3.shape[1], gpad), np.int32)
            out[:, :, :num_g] = a3
            return out

        put(L.aff_match, pad_groups(tm[gt.aff_term[gi]]))
        put(L.aff_key, gt.aff_key[gi])
        put(L.aff_valid, gt.aff_valid[gi])
        put(L.aff_empty, gt.aff_empty[gi])
        put(L.aff_host, gt.aff_hostname[gi])
        put(L.aff_self, gt.aff_self[gi])
        put(L.aff_unpl, gt.aff_unplaced[gi])
        put(L.aff_err, gt.aff_err[gi])
        put(L.anti_match, pad_groups(tm[gt.anti_term[gi]]))
        put(L.anti_key, gt.anti_key[gi])
        put(L.anti_valid, gt.anti_valid[gi])
        put(L.anti_host, gt.anti_hostname[gi])
        put(L.anti_err, gt.anti_err[gi])
        put(L.pref_match, pad_groups(tm[gt.pref_term[gi]]))
        put(L.pref_key, gt.pref_key[gi])
        put(L.pref_w, np.round(gt.pref_w[gi]).astype(np.int64))
        # exist side: does group g2's term t match ME — transpose of the
        # same factored tables, padded on the OUTER group axis
        def exist_bits(term_ids, t_):
            # [G_me, Gpad * t_]: bit (g2, t) = term_match[term_ids[g2, t], me]
            a = tm[term_ids][:, :, gi]                 # [G, T, G_me]
            out = np.zeros((num_g, gpad, t_), np.int32)
            out[:, :num_g] = a.transpose(2, 0, 1)
            return out

        put(L.ex_anti, exist_bits(gt.anti_term, tb))
        put(L.ex_pref, exist_bits(gt.pref_term, tp))
        put(L.ex_aff, exist_bits(gt.aff_term, ta))

        def bake(a, t_, dtype=np.int64):
            out = np.zeros((gpad, t_), dtype=dtype)
            out[:num_g] = a
            return tuple(int(v) for v in out.flatten())

        ip_static = dict(
            exist_anti_key=bake(gt.anti_key, tb),
            exist_anti_mask=bake(gt.anti_valid & ~gt.anti_empty, tb),
            exist_anti_empty=bake(gt.anti_valid & gt.anti_empty, tb),
            exist_pref_key=bake(gt.pref_key, tp),
            exist_pref_w=bake(np.round(gt.pref_w).astype(np.int64), tp),
            exist_aff_key=bake(gt.aff_key, ta),
            exist_aff_mask=bake(gt.aff_valid & ~gt.aff_empty, ta),
        )

    label_tbl = label_prio_row = image_tbl = img_id_col = noexec_tbl = None
    saa_row = saa_dom_tbl = None
    sa_sig = sa_pin_row = sa_match_row = sa_val_tbl = sa_lock_init = None
    n_saa_doms_p = 0
    sa_la = 0
    if pol_any:
        gid_all = pods(cols.group_id)
        if pol_label:
            # whatif axis-unification may pad the shared table wider than
            # this scenario's policy needs — slice to the spec's own rows
            lr = np.asarray(ptabs.label_ok)[:len(ps.label_rows)]
            lpad8 = max(-(-lr.shape[0] // SUBLANES) * SUBLANES, SUBLANES)
            label_tbl = np.zeros((lpad8, npad), dtype=np.int32)
            label_tbl[:lr.shape[0], :n] = lr.astype(np.int32)
        if pol_prio:
            label_prio_row = node_row(ptabs.label_prio)
        if pol_image:
            image_tbl = table_rows(ptabs.image_score)
            img_id_col = pods(cols.img_id)
        if pol_noexec:
            noexec_tbl = table_rows(t.taint_ok_noexec)
        if pol_saa:
            n_saa_doms_p = int(config.n_saa_doms)
            saa_row = np.zeros((len(gid_all), gpad), dtype=np.int32)
            saa_row[:, :num_g] = \
                gt.saa_rows[gt.saa_sig[gid_all]].astype(np.int32)
            ne = len(ps.saa_weights)
            dom_rows_p = np.asarray(ptabs.saa_dom)[:ne]
            epad8 = max(-(-ne // SUBLANES) * SUBLANES, SUBLANES)
            saa_dom_tbl = np.zeros((epad8, npad), dtype=np.int32)
            saa_dom_tbl[:ne, :n] = dom_rows_p.astype(np.int32)
        if pol_sa:
            sa_la = int(sum(ps.sa_segs))
            fd_real = int(gt.saa_rows.shape[0])
            fd8 = max(-(-fd_real // SUBLANES) * SUBLANES, SUBLANES)
            la8 = max(-(-max(sa_la, 1) // SUBLANES) * SUBLANES, SUBLANES)
            sa_sig = pods(gt.saa_sig[gid_all])
            pin = np.asarray(ptabs.sa_pin)[pods(cols.sa_self_id)][:, :sa_la]
            sa_pin_row = np.zeros((len(gid_all), la8), dtype=np.int32)
            sa_pin_row[:, :sa_la] = pin.astype(np.int32)
            sa_match_row = np.zeros((len(gid_all), fd8), dtype=np.int32)
            sa_match_row[:, :fd_real] = \
                gt.saa_rows[:, gid_all].T.astype(np.int32)
            lapad8 = max(-(-max(sa_la, 1) // SUBLANES) * SUBLANES, SUBLANES)
            sa_val_tbl = np.zeros((lapad8, npad), dtype=np.int32)
            sa_val_tbl[:sa_la, :n] = \
                np.asarray(ptabs.sa_val)[:sa_la].astype(np.int32)
            sa_lock_init = np.asarray(ptabs.sa_lock_init,
                                      dtype=np.int32)[:fd_real]

    plan = FastPlan(
        num_nodes=n, num_pods=len(np.asarray(cols.req_cpu)),
        most_requested=config.most_requested, num_scalars=n_scal,
        alloc_scalar=alloc_scalar, used_scalar=used_scalar,
        req_scalar=req_scalar,
        alloc_cpu=node_row(ac), alloc_mem=node_row(am),
        alloc_gpu=node_row(ag), alloc_eph=node_row(ae),
        allowed=node_row(s.allowed_pods), cond_bits=cond,
        mem_pressure=node_row(np.asarray(s.mem_pressure, dtype=np.int64)),
        disk_pressure=node_row(np.asarray(s.disk_pressure, dtype=np.int64)),
        selector_ok=table_rows(t.selector_ok),
        taint_ok=table_rows(t.taint_ok),
        intolerable=table_rows(t.intolerable),
        aff_count=table_rows(t.affinity_count),
        avoid_score=table_rows(t.avoid_score),
        host_ok=table_rows(t.host_ok),
        used_cpu=node_row(uc), used_mem=node_row(um),
        used_gpu=node_row(ug), used_eph=node_row(ue),
        nonzero_cpu=node_row(nzuc), nonzero_mem=node_row(nzum),
        pod_count=node_row(d.pod_count),
        req_cpu=pods(rc), req_mem=pods(rm), req_gpu=pods(rg),
        req_eph=pods(re_),
        nz_cpu=pods(nzc), nz_mem=pods(nzm),
        zero_request=pods(np.asarray(cols.zero_request, dtype=np.int64)),
        best_effort=pods(np.asarray(cols.best_effort, dtype=np.int64)),
        sel_id=pods(cols.sel_id), tol_id=pods(cols.tol_id),
        aff_id=pods(cols.aff_id), avoid_id=pods(cols.avoid_id),
        host_id=pods(cols.host_id),
        num_groups=gpad, has_ports=config.has_ports,
        has_disk=config.has_disk_conflict,
        has_spread=config.has_services, has_vol_zone=config.has_vol_zone,
        presence=presence, gid=gid, port_row=port_row, disk_row=disk_row,
        ss_row=ss_row, zone_ok_tbl=zone_ok_tbl, zone_onehot=zone_onehot,
        n_zone_doms=zpad if config.has_services else 0,
        gcds=(g_cpu, g_mem, g_gpu, g_eph), scalar_gcds=tuple(scal_gcds),
        has_interpod=config.has_interpod, n_topo_keys=k_keys,
        topo_nodewise=nodewise, n_topo_doms_ip=d_doms_real,
        hostname_terms=host_terms, ta=ta, tb=tb, tp=tp,
        hard_weight=config.hard_weight, topo_rows=topo_rows,
        presence_dom=presence_dom, ipod=ip_tbl, **ip_static,
        has_maxpd=config.has_maxpd and any(mp_enabled),
        maxpd_enabled=mp_enabled, n_vols=n_vols, used_vols=used_vols,
        vol_tbl=vol_tbl, vol_type3=vol_type3, maxpd_limits=mp_limits,
        policy=ps,
        label_tbl=label_tbl, label_prio_row=label_prio_row,
        image_tbl=image_tbl, img_id=img_id_col, noexec_tbl=noexec_tbl,
        saa_row=saa_row, saa_dom_tbl=saa_dom_tbl, n_saa_doms=n_saa_doms_p,
        sa_sig=sa_sig, sa_pin_row=sa_pin_row, sa_match_row=sa_match_row,
        sa_val_tbl=sa_val_tbl, sa_lock_init=sa_lock_init, sa_la=sa_la,
    )
    return plan, ""


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class IpConst:
    """Compile-time interpod constants baked into one kernel variant (and
    therefore part of the _build_call cache key): dimensions plus the
    exist-side per-(group, term) key/weight/mask tables — per-pod operands
    then carry only match bits."""

    k_keys: int
    nodewise: Tuple[bool, ...]   # per key (FastPlan.topo_nodewise)
    kpad8: int              # sublane-padded rows of the static topo block
    d_doms: int             # REAL domain count of the domain keys (the
    #                         unroll bound)
    dpad: int               # lane-padded presence_dom width (0: none)
    ta: int
    tb: int
    tp: int
    hard_weight: int
    wip: int
    exist_anti_key: Tuple[int, ...]
    exist_anti_mask: Tuple[int, ...]
    exist_anti_empty: Tuple[int, ...]
    exist_pref_key: Tuple[int, ...]
    exist_pref_w: Tuple[int, ...]
    exist_aff_key: Tuple[int, ...]
    exist_aff_mask: Tuple[int, ...]

    @property
    def kd(self) -> int:
        """Keys that keep their domains: presence_dom's rows per group."""
        return self.nodewise.count(False)

    def dom_index(self, k: int) -> int:
        """Key k's row among the domain keys."""
        return self.nodewise[:k].count(False)


def ip_const_of(plan: FastPlan) -> Optional[IpConst]:
    if not plan.has_interpod:
        return None
    return IpConst(
        k_keys=plan.n_topo_keys, nodewise=plan.topo_nodewise,
        kpad8=plan.topo_rows.shape[0], d_doms=plan.n_topo_doms_ip,
        dpad=(0 if plan.presence_dom is None
              else plan.presence_dom.shape[1]),
        ta=plan.ta, tb=plan.tb, tp=plan.tp, hard_weight=plan.hard_weight,
        wip=plan.ipod.shape[1],
        exist_anti_key=plan.exist_anti_key,
        exist_anti_mask=plan.exist_anti_mask,
        exist_anti_empty=plan.exist_anti_empty,
        exist_pref_key=plan.exist_pref_key,
        exist_pref_w=plan.exist_pref_w,
        exist_aff_key=plan.exist_aff_key,
        exist_aff_mask=plan.exist_aff_mask)


@dataclass(frozen=True)
class MpConst:
    """Compile-time MaxPD constants baked into one kernel variant: volume
    count/padding, per-volume type triples, and per-type limits."""

    n_vols: int
    vpad8: int       # sublane-padded carry rows
    vpad_l: int      # lane-padded per-pod mask row width
    vol_type3: Tuple[int, ...]              # [V*3] (EBS, GCE, AzureDisk)
    limits: Tuple[int, int, int]
    enabled3: Tuple[bool, bool, bool] = (True, True, True)


def mp_const_of(plan: FastPlan) -> Optional[MpConst]:
    if not plan.has_maxpd:
        return None
    return MpConst(n_vols=plan.n_vols, vpad8=plan.used_vols.shape[0],
                   vpad_l=plan.vol_tbl.shape[1], vol_type3=plan.vol_type3,
                   limits=plan.maxpd_limits, enabled3=plan.maxpd_enabled)


@dataclass(frozen=True)
class PolConst:
    """Compile-time policy-residue dimensions baked into one kernel variant
    (round 6). The PolicySpec itself already rides the _build_call cache
    key; these are the cluster-dependent axis sizes the spec alone cannot
    name: label-row padding, ServiceAntiAffinity domain rows/unroll bound,
    and the ServiceAffinity label/lock-slot widths."""

    lpad8: int = 0        # label-presence mask rows (0 = no label input)
    epad8: int = 0        # ServiceAntiAffinity dom-row padding
    n_saa_doms: int = 0   # SAA label-domain unroll bound (incl. bucket 0)
    la: int = 0           # real concatenated ServiceAffinity entry labels
    la8: int = 0          # SMEM pin-row width
    fd: int = 0           # ServiceAffinity lock slots (misc lanes 1..fd)
    fd8: int = 0          # SMEM match-row width
    lapad8: int = 0       # sa_val table rows
    has_label: bool = False
    has_prio: bool = False
    has_image: bool = False
    has_noexec: bool = False
    has_saa: bool = False
    has_sa: bool = False


def pol_const_of(plan: FastPlan) -> Optional[PolConst]:
    if plan.policy is None:
        return None
    has_label = plan.label_tbl is not None
    has_prio = plan.label_prio_row is not None
    has_image = plan.image_tbl is not None
    has_noexec = plan.noexec_tbl is not None
    has_saa = plan.saa_dom_tbl is not None
    has_sa = plan.sa_val_tbl is not None
    if not (has_label or has_prio or has_image or has_noexec or has_saa
            or has_sa):
        return None
    return PolConst(
        lpad8=plan.label_tbl.shape[0] if has_label else 0,
        epad8=plan.saa_dom_tbl.shape[0] if has_saa else 0,
        n_saa_doms=plan.n_saa_doms,
        la=plan.sa_la,
        la8=plan.sa_pin_row.shape[1] if has_sa else 0,
        fd=len(plan.sa_lock_init) if has_sa else 0,
        fd8=plan.sa_match_row.shape[1] if has_sa else 0,
        lapad8=plan.sa_val_tbl.shape[0] if has_sa else 0,
        has_label=has_label, has_prio=has_prio, has_image=has_image,
        has_noexec=has_noexec, has_saa=has_saa, has_sa=has_sa)


def _make_kernel(most_requested: bool, num_bits: int, num_scalars: int,
                 group: int, gpad: int = 0, zpad: int = 0,
                 has_ports: bool = False, has_disk: bool = False,
                 has_spread: bool = False, has_vol_zone: bool = False,
                 ip: Optional[IpConst] = None,
                 mp: Optional[MpConst] = None,
                 pol: Optional[PolConst] = None, ps=None):
    """Kernel body for one grid step of `group` consecutive pods.

    Mosaic requires the sublane (second-to-last) block dim to be a multiple
    of 8 or the whole array axis, so per-pod operands stream in blocks of
    `group`=SUBLANES pods and the kernel statically unrolls the sequential
    per-pod step `group` times (carry reads re-load the output refs, so pod
    j sees pod j-1's bind). Binds are masked whole-row vector updates — a
    one-hot (1,Npad) `pick` row — rather than dynamic-lane scalar stores,
    which Mosaic does not lower.

    gpad > 0 enables the pod-group path: a [Gpad, Npad] presence carry, per
    -pod SMEM group rows (conflict/spread flags vs each group), and all
    group state access via statically-unrolled loops over Gpad with
    (g == gid)-masked row ops — no dynamic indexing anywhere."""
    group_bound = gpad > 0
    # the bind only UPDATES the presence carry when the XLA make_step does
    # (ports|services|disk|interpod); an SAA-only plan reads presence
    # frozen at its seeded state, exactly like the host path
    pres_update = has_ports or has_disk or has_spread or (ip is not None)

    # policy gating + weights (kernels._evaluate's on()/part_on and the
    # weighted-sum table, generic_scheduler.go:631-639) — all static, so
    # gated-off stages and zero-weight components generate no code
    en = None if ps is None else ps.pred_keys

    def on(name):
        return en is None or name in en

    def part(name):
        return en is not None and name in en

    from tpusim.jaxe.kernels import policy_weights

    (w_least, w_most, w_balanced, w_node_aff, w_taint, w_avoid, w_spread,
     w_interpod) = policy_weights(ps, most_requested)

    aca = ps is not None and ps.always_check_all

    def kernel(*refs):
        (rc_r, rm_r, rg_r, re_r, nzc_r, nzm_r, zr_r, be_r,
         sel_r, tol_r, intol_r, aff_r, av_r, host_r,
         acpu_r, amem_r, agpu_r, aeph_r, allowed_r, cond_r, mpr_r, dpr_r,
         iuc_r, ium_r, iug_r, iue_r, inzc_r, inzm_r, ipc_r,
         imisc_r) = refs[:30]
        at = 30
        if num_scalars:
            rs_r, ascal_r, ius_r = refs[at:at + 3]
            at += 3
        if has_vol_zone:
            # vol-zone only needs per-pod static rows, not the presence
            # carry (kernels.py skips presence updates for it too)
            vz_r = refs[at]
            at += 1
        if mp is not None:
            mvrow_r = refs[at]     # per-pod volume-mask rows [SUB, Vpad_l]
            iuv_r = refs[at + 1]   # used-vols init carry [Vpad8, Npad]
            at += 2
        if group_bound:
            gid_r = refs[at]
            at += 1
            if has_spread:
                zoh_r = refs[at]
                at += 1
            ipres_r = refs[at]
            at += 1
            if has_ports:
                prow_r = refs[at]
                at += 1
            if has_disk:
                drow_r = refs[at]
                at += 1
            if has_spread:
                ssrow_r = refs[at]
                at += 1
            if ip is not None:
                topo_r = refs[at]      # [Kpad8, Npad] static domain rows
                iprow_r = refs[at + 1]  # per-pod packed interpod rows
                at += 2
                if ip.kd:
                    ipd_r = refs[at]   # [Gpad*Kd, Dpad] presence_dom init
                    at += 1
        if pol is not None:
            if pol.has_label:
                ltbl_r = refs[at]      # [Lpad8, Npad] 0/1 pass masks
                at += 1
            if pol.has_prio:
                lprio_r = refs[at]     # [1, Npad] pre-weighted priorities
                at += 1
            if pol.has_image:
                img_r = refs[at]       # per-pod image score rows
                at += 1
            if pol.has_noexec:
                nx_r = refs[at]        # per-pod NoExecute tolerance rows
                at += 1
            if pol.has_saa:
                samrow_r = refs[at]      # SMEM [SUB, gpad] my-service row
                saadom_r = refs[at + 1]  # [Epad8, Npad] label domains
                at += 2
            if pol.has_sa:
                sasig_r = refs[at]        # SMEM [SUB, 1] first-service sig
                sapin_r = refs[at + 1]    # SMEM [SUB, la8] own pins
                samatch_r = refs[at + 2]  # SMEM [SUB, fd8] bind match bits
                saval_r = refs[at + 3]    # [Lapad8, Npad] label values
                at += 4
        (ouc_r, oum_r, oug_r, oue_r, onzc_r, onzm_r, opc_r, omisc_r,
         choice_r, counts_r, adv_r) = refs[at:at + 11]
        at += 11
        if num_scalars:
            ous_r = refs[at]
            at += 1
        if group_bound:
            opres_r = refs[at]
            at += 1
            if ip is not None and ip.kd:
                opd_r = refs[at]
                at += 1
        if mp is not None:
            ouv_r = refs[at]
        p = pl.program_id(0)

        @pl.when(p == 0)
        def _init():
            ouc_r[:] = iuc_r[:]
            oum_r[:] = ium_r[:]
            oug_r[:] = iug_r[:]
            oue_r[:] = iue_r[:]
            onzc_r[:] = inzc_r[:]
            onzm_r[:] = inzm_r[:]
            opc_r[:] = ipc_r[:]
            for lane in range(LANES):  # SMEM: scalar copies only
                omisc_r[0, lane] = imisc_r[0, lane]
            if num_scalars:
                ous_r[:] = ius_r[:]
            if group_bound:
                opres_r[:] = ipres_r[:]
                if ip is not None and ip.kd:
                    opd_r[:] = ipd_r[:]
            if mp is not None:
                ouv_r[:] = iuv_r[:]

        acpu = acpu_r[:]
        amem = amem_r[:]
        agpu = agpu_r[:]
        aeph = aeph_r[:]
        allowed = allowed_r[:]
        cond = cond_r[:]
        fail_cond = cond != 0
        mpr = mpr_r[:] != 0
        dpr_fail = dpr_r[:] != 0
        if num_scalars:
            asc = ascal_r[:]

        for j in range(group):
            rc = rc_r[j, 0]
            rm = rm_r[j, 0]
            rg = rg_r[j, 0]
            re = re_r[j, 0]
            nzc = nzc_r[j, 0]
            nzm = nzm_r[j, 0]
            check_res = zr_r[j, 0] == 0
            best_effort = be_r[j, 0] != 0
            rr = omisc_r[0, 0]

            used_c = ouc_r[:]
            used_m = oum_r[:]
            used_g = oug_r[:]
            used_e = oue_r[:]
            nz_c = onzc_r[:]
            nz_m = onzm_r[:]
            pc = opc_r[:]

            # ---- filter stages, predicatesOrdering (kernels._evaluate).
            # Stage gating mirrors _evaluate's on()/part_on(): a policy
            # (baked statically into this variant) enables GeneralPredicates
            # and/or its individually-named parts, each a separate stage at
            # its ordering slot; ps None = the provider's full pipeline ----
            general_on = on(GENERAL_PRED)
            need_res = general_on or part(POD_FITS_RESOURCES_PRED)
            need_host = general_on or part(HOSTNAME_PRED)
            need_sel = general_on or part(MATCH_NODE_SELECTOR_PRED)
            if need_res:
                insuff_pods = (pc + 1) > allowed
                insuff_cpu = check_res & (acpu < used_c + rc)
                insuff_mem = check_res & (amem < used_m + rm)
                insuff_gpu = check_res & (agpu < used_g + rg)
                insuff_eph = check_res & (aeph < used_e + re)
                fail_res = (insuff_pods | insuff_cpu | insuff_mem
                            | insuff_gpu | insuff_eph)
                bits_res = (
                    insuff_pods.astype(jnp.int32) << BIT_INSUFFICIENT_PODS
                    | insuff_cpu.astype(jnp.int32) << BIT_INSUFFICIENT_CPU
                    | insuff_mem.astype(jnp.int32)
                    << BIT_INSUFFICIENT_MEMORY
                    | insuff_gpu.astype(jnp.int32) << BIT_INSUFFICIENT_GPU
                    | insuff_eph.astype(jnp.int32)
                    << BIT_INSUFFICIENT_EPHEMERAL)
                if num_scalars:
                    us = ous_r[:]
                    for si in range(num_scalars):
                        ins = check_res & (asc[si:si + 1, :]
                                           < us[si:si + 1, :] + rs_r[j, si])
                        fail_res = fail_res | ins
                        bits_res = bits_res | (
                            ins.astype(jnp.int32)
                            << (NUM_FIXED_BITS + si))
            elif num_scalars:
                us = ous_r[:]
            if need_host:
                host_bad = host_r[j:j + 1, :] == 0
            if need_sel:
                sel_bad = sel_r[j:j + 1, :] == 0
            if general_on:
                fail_general = fail_res | host_bad | sel_bad
                bits_general = (
                    bits_res
                    | host_bad.astype(jnp.int32) << BIT_HOSTNAME_MISMATCH
                    | sel_bad.astype(jnp.int32)
                    << BIT_NODE_SELECTOR_MISMATCH)
            if group_bound:
                gid_s = gid_r[j, 0]
                pres_rows = [opres_r[g2:g2 + 1, :] for g2 in range(gpad)]
            if ip is not None:
                K, D, KD = ip.k_keys, ip.d_doms, ip.kd
                L = IpLayout(ip.ta, ip.tb, ip.tp, gpad)
                pd_rows = [opd_r[r:r + 1, :] for r in range(gpad * KD)]
                topo_k = [topo_r[k:k + 1, :] for k in range(K)]

                def ip_own_term(match_off, key_off, t):
                    """One own term: (mcount row, dc_at row, domsel row).
                    dc_at[n] == the XLA path's take-along of _seg_rows —
                    the per-domain sum of matched presence broadcast back
                    to nodes. Computed directly from mcount with D scalar
                    segment reductions (pd[g,k,d] is the domain-d sum of
                    presence[g], so Σ_g match·pd == Σ_{n∈d} mcount):
                    pad nodes carry domain 0 and zero presence, so they
                    never contaminate a real domain's sum. On a node-wise
                    key a domain is one node: dc_at is mcount, and domsel
                    is 1 where the node has the label."""
                    mcount = jnp.zeros_like(cond)
                    for g2 in range(gpad):
                        mcount = mcount + jnp.where(
                            iprow_r[j, match_off + t * gpad + g2] != 0,
                            pres_rows[g2], 0)
                    key_t = iprow_r[j, key_off + t]
                    domsel = jnp.zeros_like(cond)
                    for k in range(K):
                        domsel = jnp.where(key_t == k, topo_k[k], domsel)
                    if not KD:
                        return mcount, mcount, domsel
                    dc_at = jnp.zeros_like(cond)
                    for d in range(D):
                        in_d = domsel == d
                        seg_d = jnp.sum(jnp.where(in_d, mcount, 0),
                                        dtype=jnp.int32)
                        dc_at = dc_at + jnp.where(in_d, seg_d, 0)
                    for k in range(K):
                        if ip.nodewise[k]:
                            dc_at = jnp.where(key_t == k, mcount, dc_at)
                    return mcount, dc_at, domsel
            ports_alias_on = ps is not None and bool(ps.ports_slots)
            if has_ports and (general_on or part(POD_FITS_HOST_PORTS_PRED)
                              or ports_alias_on):
                # PodFitsHostPorts (predicates.go:1019-1039), part of
                # GeneralPredicates (or re-emitted at an alias tail slot):
                # my port set conflicts with the port set of any group
                # present on the node
                port_bad = fail_cond & False
                for g2 in range(gpad):
                    port_bad = port_bad | (
                        (prow_r[j, g2] != 0) & (pres_rows[g2] > 0))
                if general_on:
                    fail_general = fail_general | port_bad
                    bits_general = bits_general | (
                        port_bad.astype(jnp.int32) << BIT_HOST_PORTS)

            # ---- ServiceAffinity shared prelude (kernels._evaluate): the
            # lock is the entry-independent first-matching-pod node index
            # for MY first-service signature (-1 unlocked, -2 permanently
            # unpinned, >= 0 a node), read fresh from the misc carry lanes
            # so pod j sees pod j-1's bind ----
            if pol is not None and pol.has_sa:
                sasig = sasig_r[j, 0]
                sa_lock = jnp.int32(-1)
                for f in range(pol.fd):
                    sa_lock = jnp.where(sasig == f, omisc_r[0, 1 + f],
                                        sa_lock)
                sa_li = jnp.maximum(sa_lock, 0)
                idx_n = jax.lax.broadcasted_iota(jnp.int32, cond.shape, 1)
                sa_own_l = []
                sa_lock_l = []
                for l_ in range(pol.la):
                    val_l = saval_r[l_:l_ + 1, :]
                    pin_l = sapin_r[j, l_]
                    unres = pin_l == 0
                    # own: my pin (any value when unresolved); lock: the
                    # locked node's value, binding only when I'm unresolved
                    # and the locked node actually carries the label
                    sa_own_l.append(unres | (val_l == pin_l))
                    locked_v = jnp.sum(
                        jnp.where(idx_n == sa_li, val_l, 0),
                        dtype=jnp.int32)
                    pinned = unres & (locked_v > 0)
                    sa_lock_l.append(~pinned | (val_l == locked_v))
                sa_off = [0]
                for seg in ps.sa_segs:
                    sa_off.append(sa_off[-1] + seg)

                def sa_fail(e):
                    ok_own = fail_cond | True
                    ok_lock = fail_cond | True
                    for l_ in range(sa_off[e], sa_off[e + 1]):
                        ok_own = ok_own & sa_own_l[l_]
                        ok_lock = ok_lock & sa_lock_l[l_]
                    return ~(ok_own & (ok_lock | (sa_lock < 0)))

            # policy label-presence / ServiceAffinity / ports-alias stages
            # fire at the ordering slot they were registered under,
            # mirroring kernels._evaluate's emit_label
            stages = []
            label_at = {}
            if ps is not None:
                for i_l, slot in enumerate(ps.label_rows):
                    label_at.setdefault(slot, []).append(i_l)

            def emit_label(slot_name):
                if ps is None:
                    return
                for i_l in label_at.get(slot_name, ()):
                    stages.append(
                        (ltbl_r[i_l:i_l + 1, :] == 0,
                         jnp.int32(1) << BIT_NODE_LABEL_PRESENCE))
                for e, slot in enumerate(ps.sa_slots):
                    if slot == slot_name:
                        stages.append(
                            (sa_fail(e),
                             jnp.int32(1) << BIT_SERVICE_AFFINITY))
                if slot_name in ps.ports_slots and has_ports:
                    stages.append(
                        (port_bad, jnp.int32(1) << BIT_HOST_PORTS))

            # short-circuit reason selection: first failing stage wins in
            # predicatesOrdering (cond -> general -> hostname -> ports ->
            # selector -> resources -> NoDiskConflict -> taints ->
            # NoExecute -> MaxPD -> NoVolumeZoneConflict -> memory pressure
            # -> disk pressure -> interpod, matching kernels._evaluate
            # incl. policy part slots and every emit_label ordering slot)
            stages.append((fail_cond, cond))
            if aca and en is not None \
                    and CHECK_NODE_UNSCHEDULABLE_PRED in en:
                # count mode re-reports unschedulable as its own stage on
                # top of the condition stage (kernels._evaluate)
                stages.append(
                    ((cond & (jnp.int32(1) << BIT_NODE_UNSCHEDULABLE)) != 0,
                     jnp.int32(1) << BIT_NODE_UNSCHEDULABLE))
            emit_label(CHECK_NODE_UNSCHEDULABLE_PRED)
            if general_on:
                stages.append((fail_general, bits_general))
            emit_label(GENERAL_PRED)
            if part(HOSTNAME_PRED):
                stages.append(
                    (host_bad, jnp.int32(1) << BIT_HOSTNAME_MISMATCH))
            emit_label(HOSTNAME_PRED)
            if part(POD_FITS_HOST_PORTS_PRED) and has_ports:
                stages.append((port_bad, jnp.int32(1) << BIT_HOST_PORTS))
            emit_label(POD_FITS_HOST_PORTS_PRED)
            if part(MATCH_NODE_SELECTOR_PRED):
                stages.append(
                    (sel_bad, jnp.int32(1) << BIT_NODE_SELECTOR_MISMATCH))
            emit_label(MATCH_NODE_SELECTOR_PRED)
            if part(POD_FITS_RESOURCES_PRED):
                stages.append((fail_res, bits_res))
            emit_label(POD_FITS_RESOURCES_PRED)
            if has_disk and on(NO_DISK_CONFLICT_PRED):
                # NoDiskConflict (predicates.go:266-276): my volume set
                # conflicts with the volume set of any group present
                fail_disk = fail_cond & False
                for g2 in range(gpad):
                    fail_disk = fail_disk | (
                        (drow_r[j, g2] != 0) & (pres_rows[g2] > 0))
                stages.append(
                    (fail_disk, jnp.int32(1) << BIT_DISK_CONFLICT))
            emit_label(NO_DISK_CONFLICT_PRED)
            if on(POD_TOLERATES_NODE_TAINTS_PRED):
                fail_taint = tol_r[j:j + 1, :] == 0
                stages.append(
                    (fail_taint, jnp.int32(1) << BIT_TAINTS_NOT_TOLERATED))
            emit_label(POD_TOLERATES_NODE_TAINTS_PRED)
            if pol is not None and pol.has_noexec:
                # the NoExecute-only taint predicate shares the taint
                # reason bit (kernels._evaluate's noexec stage)
                stages.append(
                    (nx_r[j:j + 1, :] == 0,
                     jnp.int32(1) << BIT_TAINTS_NOT_TOLERATED))
            emit_label(POD_TOLERATES_NODE_NO_EXECUTE_TAINTS_PRED)
            emit_label(CHECK_NODE_LABEL_PRESENCE_PRED)
            emit_label(CHECK_SERVICE_AFFINITY_PRED)
            if mp is not None:
                # Max{EBS,GCEPD,AzureDisk}VolumeCount (predicates.go:422
                # -460): unique relevant volume ids on the node incl. mine
                # vs the per-type limit; a pod adding no relevant volumes
                # passes regardless. Type triples are static, so only
                # typed volumes generate code.
                uv_rows = [ouv_r[v:v + 1, :] for v in range(mp.n_vols)]
                fail_maxpd = fail_cond & False
                for t3 in range(3):
                    if not mp.enabled3[t3]:
                        continue  # policy-disabled type (XLA: limit 2^30)
                    typed = [v for v in range(mp.n_vols)
                             if mp.vol_type3[v * 3 + t3]]
                    if not typed:
                        continue
                    myc = jnp.int32(0)
                    cnt = jnp.zeros_like(cond)
                    for v in typed:
                        mb = mvrow_r[j, v] != 0
                        myc = myc + mb.astype(jnp.int32)
                        cnt = cnt + jnp.where(mb, 1, uv_rows[v])
                    fail_maxpd = fail_maxpd | (
                        (myc > 0) & (cnt > mp.limits[t3]))
                stages.append(
                    (fail_maxpd, jnp.int32(1) << BIT_MAX_VOLUME_COUNT))
            emit_label(MAX_EBS_VOLUME_COUNT_PRED)
            emit_label(MAX_GCE_PD_VOLUME_COUNT_PRED)
            emit_label(MAX_AZURE_DISK_VOLUME_COUNT_PRED)
            emit_label(CHECK_VOLUME_BINDING_PRED)
            if has_vol_zone and on(NO_VOLUME_ZONE_CONFLICT_PRED):
                # NoVolumeZoneConflict (predicates.go:510-533): static per
                # (volume-set, node) row, pregathered per pod
                fail_vz = vz_r[j:j + 1, :] == 0
                stages.append(
                    (fail_vz, jnp.int32(1) << BIT_VOLUME_ZONE_CONFLICT))
            emit_label(NO_VOLUME_ZONE_CONFLICT_PRED)
            if on(CHECK_NODE_MEMORY_PRESSURE_PRED):
                stages.append((mpr & best_effort,
                               jnp.int32(1) << BIT_MEMORY_PRESSURE))
            emit_label(CHECK_NODE_MEMORY_PRESSURE_PRED)
            if on(CHECK_NODE_DISK_PRESSURE_PRED):
                stages.append((dpr_fail,
                               jnp.int32(1) << BIT_DISK_PRESSURE))
            emit_label(CHECK_NODE_DISK_PRESSURE_PRED)
            if ip is not None and on(MATCH_INTERPOD_AFFINITY_PRED):
                # MatchInterPodAffinity (predicates.go:1125-1450) — last in
                # predicatesOrdering; mirrors kernels._evaluate's stage.
                # own required affinity terms
                aff_fail = fail_cond & False
                for t in range(ip.ta):
                    mcount, dc_at, domsel = ip_own_term(
                        L.aff_match, L.aff_key, t)
                    valid_t = iprow_r[j, L.aff_valid + t] != 0
                    host_t = iprow_r[j, L.aff_host + t] != 0
                    self_t = iprow_r[j, L.aff_self + t] != 0
                    unpl_t = iprow_r[j, L.aff_unpl + t] != 0
                    valid_dom = domsel > 0
                    # selects stay on int32: Mosaic selects no i1 vectors
                    term_matches = valid_dom & (
                        jnp.where(host_t, mcount, dc_at) > 0)
                    # hostname terms scan only this node's pods; otherwise
                    # "a matching pod exists" is global (incl. unplaced
                    # snapshot pods)
                    exists_any = (jnp.sum(mcount, dtype=jnp.int32) > 0) \
                        | unpl_t
                    exists = jnp.where(host_t, mcount,
                                       exists_any.astype(jnp.int32)) > 0
                    term_ok = term_matches | ((~exists) & self_t)
                    aff_fail = aff_fail | (valid_t & ~term_ok)
                aff_fail = aff_fail | (iprow_r[j, L.aff_err] != 0)
                # own required anti-affinity terms
                anti_fail = fail_cond & False
                for t in range(ip.tb):
                    bmcount, bdc_at, bdomsel = ip_own_term(
                        L.anti_match, L.anti_key, t)
                    valid_t = iprow_r[j, L.anti_valid + t] != 0
                    host_t = iprow_r[j, L.anti_host + t] != 0
                    bvalid_dom = bdomsel > 0
                    b_matches = bvalid_dom & (
                        jnp.where(host_t, bmcount, bdc_at) > 0)
                    anti_fail = anti_fail | (valid_t & b_matches)
                anti_fail = anti_fail | (iprow_r[j, L.anti_err] != 0)
                # existing pods' anti-affinity vs me (symmetric; runs first
                # in the reference's check order). Keys/masks are static:
                # only referenced (group, term) pairs generate any code.
                # Bk[k]: per domain of key k, or per node on a node-wise key
                Bk = [None] * K
                fail_all = jnp.int32(0)
                for g2 in range(gpad):
                    for t in range(ip.tb):
                        idx = g2 * ip.tb + t
                        if ip.exist_anti_mask[idx]:
                            k_gt = ip.exist_anti_key[idx]
                            mbit = iprow_r[j, L.ex_anti + idx] != 0
                            src = (pres_rows[g2] if ip.nodewise[k_gt] else
                                   pd_rows[g2 * KD + ip.dom_index(k_gt)])
                            Bk[k_gt] = (0 if Bk[k_gt] is None else Bk[k_gt]) \
                                + jnp.where(mbit, src, 0)
                        if ip.exist_anti_empty[idx]:
                            gp = jnp.sum(pres_rows[g2],
                                         dtype=jnp.int32) > 0
                            mbit = iprow_r[j, L.ex_anti + idx] != 0
                            fail_all = fail_all | (
                                mbit & gp).astype(jnp.int32)
                exist_fail = fail_cond & False
                for k in range(K):
                    if Bk[k] is None:
                        continue
                    if ip.nodewise[k]:
                        exist_fail = exist_fail | (
                            (topo_k[k] > 0) & (Bk[k] > 0))
                        continue
                    for d in range(1, D):
                        exist_fail = exist_fail | (
                            (topo_k[k] == d) & (Bk[k][0, d] > 0))
                exist_fail = exist_fail | (fail_all != 0)
                fail_interpod = exist_fail | aff_fail | anti_fail
                # two reasons per failure: the umbrella + the specific rule
                # in the engine's check order
                ip_bits = (jnp.int32(1) << BIT_AFFINITY_NOT_MATCH) | \
                    jnp.where(
                        exist_fail,
                        jnp.int32(1) << BIT_EXISTING_ANTI_AFFINITY,
                        jnp.where(aff_fail,
                                  jnp.int32(1) << BIT_AFFINITY_RULES,
                                  jnp.int32(1) << BIT_ANTI_AFFINITY_RULES))
                stages.append((fail_interpod, ip_bits))
            emit_label(MATCH_INTERPOD_AFFINITY_PRED)
            if ps is not None:
                # alphabetical-tail alias slots (predicate names the host
                # orders after the known pipeline)
                tail_ks = sorted(
                    int(s_.split(":", 1)[1])
                    for s_ in set(ps.label_rows) | set(ps.sa_slots)
                    | set(ps.ports_slots) if s_.startswith("tail:"))
                for tk in tail_ks:
                    emit_label(f"tail:{tk}")
            feasible = jnp.ones_like(fail_cond)
            reason = jnp.zeros_like(cond)
            if aca:
                # count mode: no short-circuit — every stage's failure
                # bits stay live for the histogram below
                for fail, _ in stages:
                    feasible = feasible & ~fail
            else:
                for fail, bits in reversed(stages):
                    feasible = feasible & ~fail
                    reason = jnp.where(fail, bits, reason)
            n_feasible = jnp.sum(feasible.astype(jnp.int32), dtype=jnp.int32)
            found = n_feasible > 0

            # ---- score (weighted sum, generic_scheduler.go:631-639;
            # int32 throughout — products bounded by plan_fast; weights
            # are compile-time statics, so zero-weight components generate
            # no code, exactly like kernels._evaluate's gating) ----
            score = jnp.zeros_like(cond)
            if w_least or w_most or w_balanced:
                total_c = nz_c + nzc
                total_m = nz_m + nzm

            def ratio(req, cap, most):
                valid = (cap > 0) & (req <= cap)
                if most:
                    expr = (req * MAX_PRIORITY) // jnp.maximum(cap, 1)
                else:
                    expr = ((cap - req) * MAX_PRIORITY) // jnp.maximum(cap, 1)
                return jnp.where(valid, expr, 0)

            if w_least:
                score = score + w_least * (
                    (ratio(total_c, acpu, False)
                     + ratio(total_m, amem, False)) // 2)
            if w_most:
                score = score + w_most * (
                    (ratio(total_c, acpu, True)
                     + ratio(total_m, amem, True)) // 2)
            if w_balanced:
                # balanced (exact rational, DEVIATIONS.md #16): products
                # fit int32
                num = jnp.abs(total_c * amem - total_m * acpu)
                den = acpu * amem
                bal = (MAX_PRIORITY * (den - num)) // jnp.maximum(den, 1)
                bal_zero = ((acpu == 0) | (total_c >= acpu)
                            | (amem == 0) | (total_m >= amem))
                score = score + w_balanced * jnp.where(bal_zero, 0, bal)
            if w_node_aff:
                # NodeAffinityPriority normalize over feasible nodes
                aff = aff_r[j:j + 1, :]
                aff_max = jnp.max(jnp.where(feasible, aff, 0))
                score = score + w_node_aff * jnp.where(
                    aff_max > 0,
                    MAX_PRIORITY * aff // jnp.maximum(aff_max, 1), 0)
            if w_taint:
                # TaintTolerationPriority reversed normalize
                intol = intol_r[j:j + 1, :]
                intol_max = jnp.max(jnp.where(feasible, intol, 0))
                score = score + w_taint * jnp.where(
                    intol_max > 0,
                    MAX_PRIORITY
                    - MAX_PRIORITY * intol // jnp.maximum(intol_max, 1),
                    MAX_PRIORITY)
            if w_avoid:
                score = score + av_r[j:j + 1, :] * w_avoid
            if pol is not None and pol.has_prio:
                # NodeLabel/LabelPreference priorities: static pre-weighted
                # per-node row (kernels._evaluate's st.label_prio)
                score = score + lprio_r[0:1, :]
            if pol is not None and pol.has_image:
                # ImageLocalityPriority: static per (image-set, node) score
                score = score + img_r[j:j + 1, :] * ps.w_image
            if pol is not None and pol.has_saa:
                # ServiceAntiAffinity (selector_spreading.go:176-280): per
                # -node count of pods in MY first service, normalized per
                # label domain; domain 0 = label missing (score stays 0)
                saa_cnt = jnp.zeros_like(score)
                for g2 in range(gpad):
                    saa_cnt = saa_cnt + jnp.where(
                        samrow_r[j, g2] != 0, pres_rows[g2], 0)
                saa_fcnt = jnp.where(feasible, saa_cnt, 0)
                saa_total = jnp.sum(saa_fcnt, dtype=jnp.int32)
                for e, w_saa in enumerate(ps.saa_weights):
                    dom_row = saadom_r[e:e + 1, :]
                    labeled = dom_row > 0
                    grp_at = jnp.zeros_like(score)
                    for d2 in range(1, pol.n_saa_doms):
                        in_d = dom_row == d2
                        seg_d = jnp.sum(jnp.where(in_d, saa_fcnt, 0),
                                        dtype=jnp.int32)
                        grp_at = grp_at + jnp.where(in_d, seg_d, 0)
                    f_sc = jnp.where(
                        saa_total > 0,
                        (MAX_PRIORITY * (saa_total - grp_at))
                        // jnp.maximum(saa_total, 1),
                        MAX_PRIORITY)
                    score = score + jnp.where(labeled, f_sc, 0) * w_saa
            if has_spread and w_spread:
                # SelectorSpreadPriority (selector_spreading.go:66-175):
                # per-node count of pods matched by my services' selectors
                # (groups flagged in my ss row), node/zone-blended exact
                # normalize — int32 products bounded by plan_fast's gate
                cnt = jnp.zeros_like(score)
                for g2 in range(gpad):
                    cnt = cnt + jnp.where(
                        ssrow_r[j, g2] != 0, pres_rows[g2], 0)
                fcnt = jnp.where(feasible, cnt, 0)
                max_node = jnp.max(fcnt)
                row0 = zoh_r[0:1, :]
                zvalid = row0 == 0  # rows 1.. are real zone domains
                zper = jnp.zeros_like(cnt)
                max_zone = jnp.int32(0)
                for z in range(1, zpad):
                    zrow = zoh_r[z:z + 1, :]
                    zc = jnp.sum(zrow * fcnt, dtype=jnp.int32)
                    zper = zper + zc * zrow
                    max_zone = jnp.maximum(max_zone, zc)
                have_zones = jnp.any(feasible & zvalid)
                node_num = jnp.where(max_node > 0, max_node - cnt, 1)
                node_den = jnp.maximum(max_node, 1)
                zone_num = jnp.where(max_zone > 0, max_zone - zper, 1)
                zone_den = jnp.maximum(max_zone, 1)
                plain = (MAX_PRIORITY * node_num) // node_den
                blend = (MAX_PRIORITY
                         * (node_num * zone_den + 2 * zone_num * node_den)
                         ) // (3 * node_den * zone_den)
                score = score + w_spread * jnp.where(
                    have_zones & zvalid, blend, plain)
            if ip is not None and w_interpod:
                # InterPodAffinityPriority (interpod_affinity.go:118+):
                # (a) my preferred terms over existing pods, (b) existing
                # pods' preferred terms over me, (c) their required
                # affinity x hard weight — int32 throughout (plan_fast
                # bounds the weight mass x pod population)
                counts_row = jnp.zeros_like(score)
                for t in range(ip.tp):
                    _, pdc_at, pdomsel = ip_own_term(
                        L.pref_match, L.pref_key, t)
                    w_t = iprow_r[j, L.pref_w + t]
                    counts_row = counts_row + jnp.where(
                        pdomsel > 0, pdc_at, 0) * w_t
                # Wk[k]: per domain of key k, or per node on a node-wise key
                Wk = [None] * K

                def add_w(k_gt, g2, mbit, w):
                    src = (pres_rows[g2] if ip.nodewise[k_gt] else
                           pd_rows[g2 * KD + ip.dom_index(k_gt)])
                    Wk[k_gt] = (0 if Wk[k_gt] is None else Wk[k_gt]) \
                        + jnp.where(mbit, src * w, 0)

                for g2 in range(gpad):
                    for t in range(ip.tp):
                        idx = g2 * ip.tp + t
                        w_s = ip.exist_pref_w[idx]
                        if w_s:
                            add_w(ip.exist_pref_key[idx], g2,
                                  iprow_r[j, L.ex_pref + idx] != 0, w_s)
                    for t in range(ip.ta):
                        idx = g2 * ip.ta + t
                        if ip.exist_aff_mask[idx]:
                            add_w(ip.exist_aff_key[idx], g2,
                                  iprow_r[j, L.ex_aff + idx] != 0,
                                  ip.hard_weight)
                for k in range(K):
                    if Wk[k] is None:
                        continue
                    if ip.nodewise[k]:
                        counts_row = counts_row + jnp.where(
                            topo_k[k] > 0, Wk[k], 0)
                        continue
                    for d in range(1, D):
                        counts_row = counts_row + jnp.where(
                            topo_k[k] == d, Wk[k][0, d], 0)
                big_i = jnp.int32(1 << 30)
                maxc = jnp.maximum(
                    jnp.max(jnp.where(feasible, counts_row, -big_i)), 0)
                minc = jnp.minimum(
                    jnp.min(jnp.where(feasible, counts_row, big_i)), 0)
                rng_i = maxc - minc
                score = score + w_interpod * jnp.where(
                    rng_i > 0,
                    (MAX_PRIORITY * (counts_row - minc))
                    // jnp.maximum(rng_i, 1),
                    0)

            # ---- selectHost: stable-desc argmax + round-robin tie pick ----
            masked = jnp.where(feasible, score, -1)
            max_score = jnp.max(masked)
            tie = feasible & (masked == max_score)
            ties = jnp.maximum(
                jnp.sum(tie.astype(jnp.int32), dtype=jnp.int32), 1)
            k = jnp.where(n_feasible > 1, rr % ties, 0)
            # the k-th tie (0-based) by binary search for the first lane
            # whose inclusive tie count exceeds k: Mosaic lowers no lane
            # prefix sum, and masked sums it does
            idx_row = jax.lax.broadcasted_iota(jnp.int32, tie.shape, 1)
            lo = jnp.int32(0)
            hi = jnp.int32(tie.shape[1] - 1)
            for _ in range((tie.shape[1] - 1).bit_length()):
                mid = (lo + hi) // 2
                left = jnp.sum(jnp.where(tie & (idx_row <= mid), 1, 0),
                               dtype=jnp.int32) > k
                hi = jnp.where(left, mid, hi)
                lo = jnp.where(left, lo, mid + 1)
            pick = tie & (idx_row == lo)
            choice = jnp.min(jnp.where(pick, idx_row, jnp.int32(1 << 30)))
            choice_r[j, 0] = jnp.where(found, choice, -1)
            adv_r[j, 0] = (n_feasible > 1).astype(jnp.int32)

            # ---- reason histogram (zeros when scheduled) ----
            if aca:
                # count mode: every failing stage contributes its decoded
                # reasons (the host keeps evaluating past the first
                # failure); pad nodes carry the int32 sentinel bit (the
                # XLA path's bit-62 analog) and must contribute nothing
                live = (cond & (jnp.int32(1) << PAD_SENTINEL_BIT)) == 0
                for b in range(num_bits):
                    tot_b = jnp.int32(0)
                    for fail, bits in stages:
                        tot_b = tot_b + jnp.sum(
                            jnp.where(fail & live, (bits >> b) & 1, 0),
                            dtype=jnp.int32)
                    counts_r[j, b] = jnp.where(found, 0, tot_b)
            else:
                fr = jnp.where(found, jnp.zeros_like(reason), reason)
                for b in range(num_bits):
                    counts_r[j, b] = jnp.sum((fr >> b) & 1, dtype=jnp.int32)

            # ---- bind: one-hot masked whole-row updates (pick is all-False
            # when nothing is feasible, so no `found` gate is needed) ----
            ouc_r[:] = jnp.where(pick, used_c + rc, used_c)
            oum_r[:] = jnp.where(pick, used_m + rm, used_m)
            oug_r[:] = jnp.where(pick, used_g + rg, used_g)
            oue_r[:] = jnp.where(pick, used_e + re, used_e)
            onzc_r[:] = jnp.where(pick, nz_c + nzc, nz_c)
            onzm_r[:] = jnp.where(pick, nz_m + nzm, nz_m)
            opc_r[:] = jnp.where(pick, pc + 1, pc)
            if num_scalars:
                for si in range(num_scalars):
                    ous_r[si:si + 1, :] = jnp.where(
                        pick, us[si:si + 1, :] + rs_r[j, si],
                        us[si:si + 1, :])
            if group_bound and pres_update:
                # presence[gid, choice] += 1 via (g == gid)-masked row adds
                pick_i = pick.astype(jnp.int32)
                for g2 in range(gpad):
                    opres_r[g2:g2 + 1, :] = jnp.where(
                        gid_s == g2, pres_rows[g2] + pick_i, pres_rows[g2])
            if mp is not None:
                for v in range(mp.n_vols):
                    mb = mvrow_r[j, v] != 0
                    ouv_r[v:v + 1, :] = jnp.where(
                        pick & mb, 1, uv_rows[v])
            if ip is not None and KD:
                # presence_dom[gid, k, dom_k(choice)] += 1: the chosen
                # node's domain id per key is a one-hot-extracted scalar
                # (pick is one-hot), then a lane-one-hot masked row add —
                # all-False pick (no feasible node) adds nothing. Node-wise
                # keys ride the presence update above.
                found_i = found.astype(jnp.int32)
                for k in range(K):
                    if ip.nodewise[k]:
                        continue
                    chosen_dom = jnp.sum(jnp.where(pick, topo_k[k], 0),
                                         dtype=jnp.int32)
                    ohrow = (jax.lax.broadcasted_iota(
                        jnp.int32, pd_rows[0].shape, 1)
                        == chosen_dom).astype(jnp.int32) * found_i
                    for g2 in range(gpad):
                        r = g2 * KD + ip.dom_index(k)
                        opd_r[r:r + 1, :] = jnp.where(
                            gid_s == g2, pd_rows[r] + ohrow, pd_rows[r])

            if pol is not None and pol.has_sa and ps.sa_enabled:
                # first matching bind locks each still-unlocked signature
                # to the chosen node (kernels.make_step's sa_lock scatter)
                for f in range(pol.fd):
                    lock_f = omisc_r[0, 1 + f]
                    omisc_r[0, 1 + f] = jnp.where(
                        (lock_f == -1) & (samatch_r[j, f] != 0) & found,
                        choice, lock_f)

            omisc_r[0, 0] = rr + (n_feasible > 1).astype(jnp.int32)

    return kernel


@lru_cache(maxsize=16)
def _build_call(npad: int, k: int, most_requested: bool, num_bits: int,
                num_scalars: int, srows: int, interpret: bool,
                gpad: int = 0, zpad: int = 0, has_ports: bool = False,
                has_disk: bool = False, has_spread: bool = False,
                has_vol_zone: bool = False, ip: Optional[IpConst] = None,
                mp: Optional[MpConst] = None,
                pol: Optional[PolConst] = None, ps=None):
    """jitted pallas_call for one (node-pad, chunk, scalar, group) shape.

    k must be a multiple of SUBLANES: Mosaic rejects blocks whose sublane
    dim is neither a multiple of 8 nor the whole axis, so per-pod operands
    move in (SUBLANES, …) blocks and the grid covers k/SUBLANES steps of
    SUBLANES statically-unrolled pods each."""
    assert k % SUBLANES == 0, k
    group_bound = gpad > 0
    kernel = _make_kernel(most_requested, num_bits, num_scalars, SUBLANES,
                          gpad, zpad, has_ports, has_disk, has_spread,
                          has_vol_zone, ip, mp, pol, ps)

    def smem_rows(width=1):
        return pl.BlockSpec((SUBLANES, width), lambda p: (p, 0),
                            memory_space=pltpu.SMEM)

    def row_per_pod(width=None):
        return pl.BlockSpec((SUBLANES, width or npad), lambda p: (p, 0),
                            memory_space=pltpu.VMEM)

    def const_row(width=None, rows=1):
        return pl.BlockSpec((rows, width or npad), lambda p: (0, 0),
                            memory_space=pltpu.VMEM)

    def misc_cell():
        return pl.BlockSpec((1, LANES), lambda p: (0, 0),
                            memory_space=pltpu.SMEM)

    scalar_in = ([smem_rows(LANES),              # req_scalar row per pod
                  const_row(rows=srows),         # alloc_scalar
                  const_row(rows=srows)]         # init used_scalar
                 if num_scalars else [])
    scalar_out = [const_row(rows=srows)] if num_scalars else []
    # group inputs (order mirrors the kernel's unpack): [zone rows], gid,
    # [zone onehot], presence init, [port rows], [disk rows], [spread rows]
    group_in = []
    group_out = []
    if has_vol_zone:
        group_in.append(row_per_pod())                 # zone_ok rows
    if mp is not None:
        group_in.append(smem_rows(mp.vpad_l))          # volume-mask rows
        group_in.append(const_row(rows=mp.vpad8))      # used-vols init
    if group_bound:
        group_in.append(smem_rows())                   # gid
        if has_spread:
            group_in.append(const_row(rows=zpad))      # zone onehot
        group_in.append(const_row(rows=gpad))          # presence init
        if has_ports:
            group_in.append(smem_rows(gpad))           # port conflict rows
        if has_disk:
            group_in.append(smem_rows(gpad))           # disk conflict rows
        if has_spread:
            group_in.append(smem_rows(gpad))           # spread-set rows
        if ip is not None:
            group_in.append(const_row(rows=ip.kpad8))  # static topo rows
            group_in.append(smem_rows(ip.wip))         # per-pod ip rows
            if ip.kd:
                group_in.append(const_row(ip.dpad,
                                          rows=gpad * ip.kd))  # pd init
        group_out.append(const_row(rows=gpad))         # presence out
        if ip is not None and ip.kd:
            group_out.append(const_row(ip.dpad, rows=gpad * ip.kd))
    if mp is not None:
        group_out.append(const_row(rows=mp.vpad8))     # used-vols out
    # policy-residue inputs (order mirrors the kernel's unpack); the
    # ServiceAffinity locks ride the existing misc carry — no new outputs
    pol_in = []
    if pol is not None:
        if pol.has_label:
            pol_in.append(const_row(rows=pol.lpad8))   # label masks
        if pol.has_prio:
            pol_in.append(const_row())                 # label priority row
        if pol.has_image:
            pol_in.append(row_per_pod())               # image score rows
        if pol.has_noexec:
            pol_in.append(row_per_pod())               # noexec taint rows
        if pol.has_saa:
            pol_in.append(smem_rows(gpad))             # my-service rows
            pol_in.append(const_row(rows=pol.epad8))   # saa label domains
        if pol.has_sa:
            pol_in.append(smem_rows())                 # first-service sig
            pol_in.append(smem_rows(pol.la8))          # own pin rows
            pol_in.append(smem_rows(pol.fd8))          # bind match rows
            pol_in.append(const_row(rows=pol.lapad8))  # sa label values
    grid_spec = pl.GridSpec(
        grid=(k // SUBLANES,),
        in_specs=(
            [smem_rows() for _ in range(8)]             # pod scalars
            + [row_per_pod() for _ in range(6)]         # pregathered rows
            + [const_row() for _ in range(8)]           # statics
            + [const_row() for _ in range(7)]           # init carry
            + [misc_cell()]                             # init misc (rr)
            + scalar_in
            + group_in
            + pol_in
        ),
        out_specs=(
            [const_row() for _ in range(7)]             # carry out
            + [misc_cell()]                             # misc out
            + [smem_rows(1), smem_rows(num_bits), smem_rows(1)]
            + scalar_out
            + group_out
        ),
    )
    i32 = jnp.int32
    out_shape = (
        [jax.ShapeDtypeStruct((1, npad), i32) for _ in range(7)]
        + [jax.ShapeDtypeStruct((1, LANES), i32)]
        + [jax.ShapeDtypeStruct((k, 1), i32),
           jax.ShapeDtypeStruct((k, num_bits), i32),
           jax.ShapeDtypeStruct((k, 1), i32)]
        + ([jax.ShapeDtypeStruct((srows, npad), i32)] if num_scalars else [])
        + ([jax.ShapeDtypeStruct((gpad, npad), i32)] if group_bound else [])
        + ([jax.ShapeDtypeStruct((gpad * ip.kd, ip.dpad), i32)]
           if ip is not None and ip.kd else [])
        + ([jax.ShapeDtypeStruct((mp.vpad8, npad), i32)]
           if mp is not None else [])
    )
    call = pl.pallas_call(kernel, grid_spec=grid_spec,
                          out_shape=out_shape, interpret=interpret)
    return jax.jit(call)


def verify_against_xla(config, compiled, cols, choices, counts,
                       max_pods: int = 512, statics=None,
                       carry=None) -> bool:
    """Replay the first max_pods pods through the XLA scan and compare the
    kernel's choices AND reason histograms bit-for-bit (the AUTO-mode
    guardrail shared by JaxBackend and the what-if fast loop). Histogram
    widths may differ when a what-if batch unifies scalar axes — the
    common prefix must match and the excess columns must be zero.

    statics/carry: device-tree overrides for policies whose host statics
    carry policy tables (label rows, image scores, ServiceAffinity state)
    that the bare compiled-cluster trees lack."""
    from tpusim.jaxe.kernels import (
        _tree_to_device,
        carry_init,
        pod_columns_to_host,
        schedule_scan,
        statics_to_device,
    )

    m = min(max_pods, len(np.asarray(cols.req_cpu)))
    xs_h = pod_columns_to_host(cols)
    xs_head = _tree_to_device(type(xs_h)(*(a[:m] for a in xs_h)))
    if statics is None:
        statics = statics_to_device(compiled)
    if carry is None:
        carry = carry_init(compiled)
    _, vch, vcnt, _ = schedule_scan(config, carry, statics, xs_head)
    vch = np.asarray(vch)
    vcnt = np.asarray(vcnt)
    fch = np.asarray(choices)[:m]
    fcnt = np.asarray(counts)[:m]
    if not np.array_equal(vch, fch):
        return False
    w = min(vcnt.shape[1], fcnt.shape[1])
    return (np.array_equal(vcnt[:, :w], fcnt[:, :w])
            and not vcnt[:, w:].any() and not fcnt[:, w:].any())


def fast_scan(plan: FastPlan, chunk: int = 0,
              interpret: Optional[bool] = None, progress=None,
              start: int = 0, stop: Optional[int] = None,
              carry_in: Optional[FastCarry] = None,
              return_carry: bool = False, fixed_chunk: bool = False):
    """Run pods [start, stop) of the plan; returns (choices, counts,
    advanced) over that span, plus the FastCarry out when return_carry.

    chunk: pods per kernel invocation (TPUSIM_FAST_CHUNK, default 512 — each
    chunk pregathers its signature rows as [chunk, Npad] int32 arrays, so the
    chunk size bounds that transient HBM footprint). interpret=None
    auto-selects interpreter mode off-TPU (tests run on CPU).

    carry_in: resume from an explicit carry (a previous call's carry_out or
    rearm_carry after preemption churn) instead of the plan's initial state.
    fixed_chunk: keep the kernel chunk at exactly `chunk` even when the span
    is shorter — the preemption hybrid's pow2 buckets then reuse one
    compiled kernel per bucket size instead of tracing per tail length
    (ghost padding rows are infeasible everywhere: no carry/rr effect).
    """
    # the plan's uploads, the kernel calls, then the wait for their outputs
    usp = flight.span("plan_upload", "device")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not chunk:
        try:
            chunk = int(os.environ.get("TPUSIM_FAST_CHUNK", 512))
        except ValueError:
            chunk = 512
    chunk = max(chunk, 1)
    p = plan.num_pods
    if stop is None:
        stop = p
    span = stop - start
    npad = plan.alloc_cpu.shape[1]
    num_bits = NUM_FIXED_BITS + plan.num_scalars
    srows = plan.alloc_scalar.shape[0] if plan.num_scalars else 0
    # round the chunk up to a SUBLANES multiple (Mosaic block granularity);
    # tail rows ride the existing GHOST_REQ padding (infeasible everywhere,
    # no carry/rr effect)
    k = -(-(chunk if fixed_chunk else min(chunk, max(span, 1)))
          // SUBLANES) * SUBLANES
    gpad = plan.num_groups
    ipc = ip_const_of(plan)
    mpc = mp_const_of(plan)
    polc = pol_const_of(plan)
    call = _build_call(npad, k, plan.most_requested, num_bits,
                       plan.num_scalars, srows, interpret,
                       gpad, plan.n_zone_doms, plan.has_ports,
                       plan.has_disk, plan.has_spread, plan.has_vol_zone,
                       ipc, mpc, polc, plan.policy)

    statics = [jnp.asarray(a) for a in (
        plan.alloc_cpu, plan.alloc_mem, plan.alloc_gpu, plan.alloc_eph,
        plan.allowed, plan.cond_bits, plan.mem_pressure, plan.disk_pressure)]
    tables = [jnp.asarray(a) for a in (
        plan.selector_ok, plan.taint_ok, plan.intolerable,
        plan.aff_count, plan.avoid_score, plan.host_ok)]
    if carry_in is None:
        carry_in = init_carry(plan)
    carry = [jnp.asarray(a) for a in carry_in.rows]
    misc = jnp.asarray(carry_in.misc)
    if plan.num_scalars:
        ascal = jnp.asarray(plan.alloc_scalar)
        scal_carry = jnp.asarray(carry_in.scal)
    if gpad:
        pres_carry = jnp.asarray(carry_in.pres)
        zone_oh = (jnp.asarray(plan.zone_onehot)
                   if plan.has_spread else None)
    pd_carry = None
    if ipc is not None:
        topo_dev = jnp.asarray(plan.topo_rows)
        ip_tbl_dev = jnp.asarray(plan.ipod)
        if ipc.kd:
            pd_carry = jnp.asarray(carry_in.pd)
    if mpc is not None:
        vol_tbl_dev = jnp.asarray(plan.vol_tbl)
        uv_carry = jnp.asarray(carry_in.uv)
    zone_tbl = (jnp.asarray(plan.zone_ok_tbl)
                if plan.has_vol_zone else None)
    if polc is not None:
        if polc.has_label:
            ltbl_dev = jnp.asarray(plan.label_tbl)
        if polc.has_prio:
            lprio_dev = jnp.asarray(plan.label_prio_row)
        if polc.has_image:
            img_tbl_dev = jnp.asarray(plan.image_tbl)
        if polc.has_noexec:
            nx_tbl_dev = jnp.asarray(plan.noexec_tbl)
        if polc.has_saa:
            saadom_dev = jnp.asarray(plan.saa_dom_tbl)
        if polc.has_sa:
            saval_dev = jnp.asarray(plan.sa_val_tbl)

    if usp:
        usp.set("nodes", npad)
        usp.set("bytes", sum(int(a.nbytes) for a in statics + tables
                             + carry + [misc]))
        usp.end()
    lsp = flight.span("kernel_launch", "device")

    def col(a, fill):
        out = np.full(k, fill, dtype=np.int32)
        out[:a.shape[0]] = a
        return out.reshape(k, 1)

    def grow(a, w=None):
        # per-pod [*, W] group rows for one chunk; ghost rows all-zero
        out = np.zeros((k, w or gpad), dtype=np.int32)
        out[:a.shape[0]] = a
        return out

    # Dispatch chunks ahead of fetching their per-pod outputs: the carry
    # chains device-to-device (out[:7] feed the next call unmaterialized),
    # so jax's async dispatch pipelines the chunk sequence — a synchronous
    # np.asarray per chunk would instead pay one full host<->device round
    # trip per chunk. The pipeline depth is
    # bounded: once more than SYNC_EVERY chunks are in flight, the OLDEST
    # chunk's outputs are materialized to host (freeing its device
    # buffers), so (a) retained HBM stays O(sync_every * chunk), not
    # O(num_pods), (b) the caller's progress/stall watchdog trails real
    # completion by at most sync_every chunks.
    # clamp to >= 1: 0 would silently disable the drain and retain every
    # chunk's output buffers on device for the whole run — O(num_pods) HBM,
    # contradicting the documented O(sync_every * chunk) bound (ADVICE r4)
    sync_every = max(1, int(os.environ.get("TPUSIM_FAST_SYNC_EVERY", "64")))
    results = []   # host triples (choices[n], counts[n,B], adv[n])
    pending = []   # FIFO of (choices_dev, counts_dev, adv_dev, n_real)

    def drain_one():
        och, ocnt, oadv, n_real = pending.pop(0)
        results.append((np.asarray(och)[:n_real, 0],
                        np.asarray(ocnt)[:n_real, :num_bits],
                        np.asarray(oadv)[:n_real, 0] != 0))

    num_chunks = -(-span // k) if span > 0 else 0
    for ci in range(num_chunks):
        sl = slice(start + ci * k, min(start + (ci + 1) * k, stop))
        # ghost padding: infeasible everywhere, no carry/rr effect
        scalars = [
            col(plan.req_cpu[sl], GHOST_REQ), col(plan.req_mem[sl], 0),
            col(plan.req_gpu[sl], 0), col(plan.req_eph[sl], 0),
            col(plan.nz_cpu[sl], 0), col(plan.nz_mem[sl], 0),
            col(plan.zero_request[sl], 0), col(plan.best_effort[sl], 0)]
        ids = [col(plan.sel_id[sl], 0), col(plan.tol_id[sl], 0),
               col(plan.aff_id[sl], 0), col(plan.avoid_id[sl], 0),
               col(plan.host_id[sl], 0)]
        # pregather the signature rows for this chunk (XLA gather, [k, Npad])
        sel_rows = tables[0][ids[0][:, 0]]
        tol_rows = tables[1][ids[1][:, 0]]
        intol_rows = tables[2][ids[1][:, 0]]
        aff_rows = tables[3][ids[2][:, 0]]
        av_rows = tables[4][ids[3][:, 0]]
        host_rows = tables[5][ids[4][:, 0]]
        args = ([jnp.asarray(a) for a in scalars]
                + [sel_rows, tol_rows, intol_rows, aff_rows, av_rows,
                   host_rows]
                + statics + carry + [misc])
        if plan.num_scalars:
            rs = np.zeros((k, LANES), dtype=np.int32)
            rs[:sl.stop - sl.start, :plan.num_scalars] = plan.req_scalar[sl]
            args += [jnp.asarray(rs), ascal, scal_carry]
        if gpad or plan.has_vol_zone or mpc is not None:
            gids = col(plan.gid[sl], 0)
        if plan.has_vol_zone:
            args.append(zone_tbl[gids[:, 0]])
        if mpc is not None:
            args.append(vol_tbl_dev[gids[:, 0]])
            args.append(uv_carry)
        if gpad:
            args.append(jnp.asarray(gids))
            if plan.has_spread:
                args.append(zone_oh)
            args.append(pres_carry)
            if plan.has_ports:
                args.append(jnp.asarray(grow(plan.port_row[sl])))
            if plan.has_disk:
                args.append(jnp.asarray(grow(plan.disk_row[sl])))
            if plan.has_spread:
                args.append(jnp.asarray(grow(plan.ss_row[sl])))
            if ipc is not None:
                args.append(topo_dev)
                # per-pod interpod rows: device gather from the per-group
                # table (pad rows gather row 0 of a zero-padded table;
                # ghost pods are infeasible everywhere regardless)
                args.append(ip_tbl_dev[gids[:, 0]])
                if ipc.kd:
                    args.append(pd_carry)
        if polc is not None:
            # residue-class policy operands (ghost pods gather row 0 /
            # all-zero rows; they are infeasible everywhere regardless)
            if polc.has_label:
                args.append(ltbl_dev)
            if polc.has_prio:
                args.append(lprio_dev)
            if polc.has_image:
                iid = col(plan.img_id[sl], 0)
                args.append(img_tbl_dev[iid[:, 0]])
            if polc.has_noexec:
                args.append(nx_tbl_dev[ids[1][:, 0]])
            if polc.has_saa:
                args.append(jnp.asarray(grow(plan.saa_row[sl])))
                args.append(saadom_dev)
            if polc.has_sa:
                args.append(jnp.asarray(col(plan.sa_sig[sl], 0)))
                args.append(jnp.asarray(grow(plan.sa_pin_row[sl],
                                             polc.la8)))
                args.append(jnp.asarray(grow(plan.sa_match_row[sl],
                                             polc.fd8)))
                args.append(saval_dev)
        # Mosaic has no 64-bit types, and under the backend's process-wide
        # x64 every Python scalar in the kernel body would trace as a weak
        # int64; the operands and results are int32 either way
        with jax.enable_x64(False):
            out = call(*args)
        carry = list(out[:7])
        misc = out[7]
        oat = 11
        if plan.num_scalars:
            scal_carry = out[oat]
            oat += 1
        if gpad:
            pres_carry = out[oat]
            oat += 1
        if pd_carry is not None:
            pd_carry = out[oat]
            oat += 1
        if mpc is not None:
            uv_carry = out[oat]
        pending.append((out[8], out[9], out[10], sl.stop - sl.start))
        if sync_every and len(pending) > sync_every:
            drain_one()
        if progress is not None:
            # dispatch-front progress; completion trails by <= sync_every
            progress(ci + 1, num_chunks, sl.stop)

    if lsp:
        lsp.set("pods", span)
        lsp.set("chunks", num_chunks)
        lsp.end()
    with flight.span("device_wait", "device"):
        while pending:
            drain_one()
        if not results:
            out3 = (np.zeros(0, np.int32),
                    np.zeros((0, num_bits), np.int32), np.zeros(0, bool))
        else:
            out3 = (np.concatenate([r[0] for r in results]),
                    np.concatenate([r[1] for r in results]),
                    np.concatenate([r[2] for r in results]))
    if not return_carry:
        return out3
    carry_out = FastCarry(
        rows=list(carry), misc=misc,
        scal=scal_carry if plan.num_scalars else None,
        pres=pres_carry if gpad else None,
        pd=pd_carry,
        uv=uv_carry if mpc is not None else None)
    return out3 + (carry_out,)

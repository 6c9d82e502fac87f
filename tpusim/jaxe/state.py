"""Columnar cluster state: SoA arrays + signature interning + static tables.

Reference mapping (SURVEY.md §7 step 2): NodeInfo's cached aggregates
(schedulercache/node_info.go:35-76) become per-node column vectors; the
symbolic pod features become interned signature ids with precompiled
[signature, node] tables (see tpusim/jaxe/__init__.py design note).
"""

from __future__ import annotations

from dataclasses import dataclass, field
import json
from operator import attrgetter, methodcaller
from typing import Dict, List, Optional, Tuple

import numpy as np

from tpusim.api.snapshot import ClusterSnapshot
from tpusim.api.types import (
    LABEL_HOSTNAME,
    TAINT_PREFER_NO_SCHEDULE,
    Node,
    Pod,
    find_matching_untolerated_taint,
    tolerations_tolerate_taint,
)
from tpusim.engine.predicates import (
    _ZONE_LABELS,
    DEFAULT_MAXPD_LIMITS,
    effective_maxpd_limits,
    get_namespaces_from_pod_affinity_term,
    get_pod_affinity_terms,
    get_pod_anti_affinity_terms,
    pod_matches_node_labels,
    pod_matches_term_namespace_and_selector,
)
from tpusim.engine.priorities import (
    PREFER_AVOID_PODS_ANNOTATION,
    calculate_node_affinity_priority_map,
    calculate_node_prefer_avoid_pods_priority_map,
    get_zone_key,
)
from tpusim.engine.resources import (
    NodeInfo,
    get_nonzero_pod_request,
    get_resource_request,
    is_pod_best_effort,
)
from tpusim.obs import recorder as flight

# ---------------------------------------------------------------------------
# failure reason bit layout (decoded back to error.go strings for the report)
# ---------------------------------------------------------------------------

BIT_NODE_NOT_READY = 0
BIT_NODE_OUT_OF_DISK = 1
BIT_NODE_NETWORK_UNAVAILABLE = 2
BIT_NODE_UNSCHEDULABLE = 3
BIT_INSUFFICIENT_PODS = 4
BIT_INSUFFICIENT_CPU = 5
BIT_INSUFFICIENT_MEMORY = 6
BIT_INSUFFICIENT_GPU = 7
BIT_INSUFFICIENT_EPHEMERAL = 8
BIT_HOSTNAME_MISMATCH = 9
BIT_NODE_SELECTOR_MISMATCH = 10
BIT_TAINTS_NOT_TOLERATED = 11
BIT_MEMORY_PRESSURE = 12
BIT_DISK_PRESSURE = 13
BIT_HOST_PORTS = 14
BIT_AFFINITY_NOT_MATCH = 15     # MatchInterPodAffinity umbrella reason
BIT_EXISTING_ANTI_AFFINITY = 16
BIT_AFFINITY_RULES = 17
BIT_ANTI_AFFINITY_RULES = 18
BIT_DISK_CONFLICT = 19          # NoDiskConflict (error.go ErrDiskConflict)
BIT_MAX_VOLUME_COUNT = 20       # MaxPDVolumeCount
BIT_VOLUME_ZONE_CONFLICT = 21   # NoVolumeZoneConflict
BIT_NODE_LABEL_PRESENCE = 22    # CheckNodeLabelPresence (policy-configured)
BIT_SERVICE_AFFINITY = 23       # CheckServiceAffinity (policy-configured)
NUM_FIXED_BITS = 24
# bits >= NUM_FIXED_BITS: Insufficient <scalar resource s>, per interned name

REASON_STRINGS = [
    "node(s) were not ready",
    "node(s) were out of disk space",
    "node(s) had unavailable network",
    "node(s) were unschedulable",
    "Insufficient pods",
    "Insufficient cpu",
    "Insufficient memory",
    "Insufficient alpha.kubernetes.io/nvidia-gpu",
    "Insufficient ephemeral-storage",
    "node(s) didn't match the requested hostname",
    "node(s) didn't match node selector",
    "node(s) had taints that the pod didn't tolerate",
    "node(s) had memory pressure",
    "node(s) had disk pressure",
    "node(s) didn't have free ports for the requested pod ports",
    "node(s) didn't match pod affinity/anti-affinity",
    "node(s) didn't satisfy existing pods anti-affinity rules",
    "node(s) didn't match pod affinity rules",
    "node(s) didn't match pod anti-affinity rules",
    "node(s) had no available disk",
    "node(s) exceed max volume count",
    "node(s) had no available volume zone",
    "node(s) didn't have the requested labels",
    "node(s) didn't match service affinity",
]

# Pod-group budgets (env-overridable). Groups are merged by match profile and
# every pairwise table is factored through interned matcher spaces, so the
# limits bound device memory / host precompute, not workload diversity:
#   MAX_GROUPS          — merged groups (presence rows)
#   MAX_RAW_GROUPS      — distinct raw signatures before merging
#   MAX_MATCH_WORK      — host matcher evaluations ((Td + Sd) * Graw)
#   MAX_PRESENCE_BYTES  — presence[G, N] carry size
MAX_GROUPS = 8192
MAX_RAW_GROUPS = 262_144
MAX_MATCH_WORK = 8_000_000
MAX_PRESENCE_BYTES = 1 << 30


def _group_budgets():
    import os

    def env_int(name: str, default: int) -> int:
        try:
            return int(os.environ.get(name, default))
        except ValueError:
            return default

    return (env_int("TPUSIM_MAX_GROUPS", MAX_GROUPS),
            env_int("TPUSIM_MAX_RAW_GROUPS", MAX_RAW_GROUPS),
            env_int("TPUSIM_MAX_MATCH_WORK", MAX_MATCH_WORK),
            env_int("TPUSIM_MAX_PRESENCE_BYTES", MAX_PRESENCE_BYTES))


_DICT_TAG = object()  # can never equal any JSON value


def _freeze(x):
    """Signature -> hashable canonical key. Same dedup power as the previous
    sorted-key json.dumps at a fraction of the cost (interning is the
    host-compile hot loop: 5 signatures per pod); at least as discriminating,
    which only ever splits a group, never merges one. Leaf types first —
    most signature nodes are strings."""
    t = type(x)
    if t is str or x is None:
        return x
    if t is int or t is bool or t is float:
        # type-tagged: Python cross-type equality (True == 1 == 1.0) would
        # otherwise merge keys json.dumps kept distinct ("true" vs "1")
        return (t.__name__, x)
    if t is dict:
        try:
            items = sorted(x.items())
        except TypeError:  # mixed-type keys: order by a stable stringification
            items = sorted(x.items(), key=lambda kv: (str(type(kv[0])),
                                                      str(kv[0])))
        # the sentinel keeps {} distinct from [] (and any dict distinct from
        # a list that happens to freeze to the same item tuple)
        return (_DICT_TAG,) + tuple((k, _freeze(v)) for k, v in items)
    if t is list or t is tuple:
        return tuple(_freeze(v) for v in x)
    if isinstance(x, (bool, int, float)):  # numeric subclasses
        return (type(x).__name__, x)
    if isinstance(x, str):
        return str(x)
    if isinstance(x, dict):
        return _freeze(dict(x))
    if isinstance(x, (list, tuple)):
        return tuple(_freeze(v) for v in x)
    return str(x)  # json default=str analog for exotic leaves


class Interner:
    """Canonical signature -> dense id."""

    def __init__(self):
        self._ids: Dict[object, int] = {}
        self.representatives: List[Pod] = []

    def intern(self, signature, representative) -> int:
        key = _freeze(signature)
        if key not in self._ids:
            self._ids[key] = len(self.representatives)
            self.representatives.append(representative)
        return self._ids[key]

    def __len__(self) -> int:
        return len(self.representatives)


@dataclass
class NodeStatics:
    """Per-node static columns (never mutated by binds)."""

    names: List[str]
    alloc_cpu: np.ndarray        # [N] int64, milli
    alloc_mem: np.ndarray        # [N] int64, bytes
    alloc_gpu: np.ndarray        # [N] int64
    alloc_eph: np.ndarray        # [N] int64
    allowed_pods: np.ndarray     # [N] int64
    alloc_scalar: np.ndarray     # [N, S] int64
    cond_fail_bits: np.ndarray   # [N] int64 (condition+unschedulable reason bits)
    mem_pressure: np.ndarray     # [N] bool
    disk_pressure: np.ndarray    # [N] bool


@dataclass
class SignatureTables:
    """[signature, node] static evaluation tables."""

    selector_ok: np.ndarray      # [Csel, N] bool — nodeSelector + required node affinity
    taint_ok: np.ndarray         # [Ctol, N] bool — NoSchedule/NoExecute taints tolerated
    taint_ok_noexec: np.ndarray  # [Ctol, N] bool — NoExecute-only variant (policy pred)
    intolerable: np.ndarray      # [Ctol, N] int64 — PreferNoSchedule intolerable count
    affinity_count: np.ndarray   # [Caff, N] int64 — preferred node-affinity weight sum
    avoid_score: np.ndarray      # [Cavoid, N] int64 — NodePreferAvoidPods (0 or 10)
    host_ok: np.ndarray          # [Chost, N] bool — spec.nodeName pin


@dataclass
class GroupTables:
    """Pod-group tables for the features whose state depends on which pods sit
    where: host ports (predicates.go:1019-1039), SelectorSpreadPriority
    (selector_spreading.go:66-175), and inter-pod (anti)affinity
    (predicates.go:1125-1450, interpod_affinity.go).

    A "group" is an interned (namespace, labels, pod-(anti)affinity, host-ports)
    pod signature over new + placed-existing pods, MERGED by match profile:
    raw signatures that every compiled matcher treats identically (same term
    matches, same service-selector matches, same port behavior, same actor
    terms) collapse into one group, so thousands of distinct label sets cost
    only as many groups as there are behaviorally distinct classes.

    The pairwise group tables are FACTORED through interned matcher spaces so
    nothing is O(G^2):
      term_match[Td, G]   — distinct (namespaces, selector) term signatures vs
                            groups; row 0 reserved all-False (invalid/padding)
      ss_rows[Sd, G]      — distinct (namespace, service-selector-set) spread
                            signatures vs groups; row 0 all-False
      port_conflict[Pp,Pp]— distinct sanitized host-port sets vs each other;
                            index 0 = "no ports"
    Per-group tensors then hold ids into those spaces (aff_term/anti_term/
    pref_term -> Td, ss_sig -> Sd, port_sig -> Pp).

    Topology domains: for each used topologyKey k, topo_dom[k, n] interns the
    node's label value, with 0 reserved for "label missing" (never matches,
    NodesHaveSameTopologyKey semantics). zone_dom likewise interns
    utilnode.GetZoneKey with 0 = no zone. Term tensors are padded on the term
    axis with valid=False rows."""

    group_of_pod: np.ndarray     # [P] int32 — new pods' group ids
    presence: np.ndarray         # [G, N] int32 — placed existing pods per group
    port_conflict: np.ndarray    # [Pp, Pp] bool — wanted ports of a hit ports of b
    port_sig: np.ndarray         # [G] int32 — group -> port-set id (0 = none)
    # volume predicates (device-native; see _compile_volumes)
    disk_conflict: np.ndarray    # [Dv, Dv] bool — volume-set a conflicts with b
    disk_sig: np.ndarray         # [G] int32 — group -> volume-set id (0 = none)
    vol_mask: np.ndarray         # [G, V] bool — MaxPD-relevant volume ids used
    vol_type: np.ndarray         # [V, 3] bool — id counts toward (EBS,GCE,Azure)
    zone_ok: np.ndarray          # [G, N] bool — NoVolumeZoneConflict passes
    used_vols_init: np.ndarray   # [N, V] bool — placed pods' volume ids per node
    ss_rows: np.ndarray          # [Sd, G] bool — b counts toward spread sig s
    ss_sig: np.ndarray           # [G] int32 — group -> its spread sig (0 = none)
    # ServiceAntiAffinity (policy): first-matching-service selector signatures
    # (getFirstServiceSelector is lister-order-first, and services are static
    # during a run, so "first" is a compile-time property)
    saa_rows: np.ndarray         # [Fd, G] bool — b counts toward first-sel f
    saa_sig: np.ndarray          # [G] int32 — group -> its first-sel sig (0 = none)
    term_match: np.ndarray       # [Td, G] bool — term t matches a pod of group b
    zone_dom: np.ndarray         # [N] int32
    topo_dom: np.ndarray         # [K, N] int32
    aff_valid: np.ndarray        # [G, Ta] bool — required pod-affinity terms
    aff_err: np.ndarray          # [G] bool — any term with empty topologyKey
    aff_empty: np.ndarray        # [G, Ta] bool — per-term empty topologyKey
    aff_term: np.ndarray         # [G, Ta] int32 (into Td)
    aff_key: np.ndarray          # [G, Ta] int32 (into K)
    aff_hostname: np.ndarray     # [G, Ta] bool — topologyKey == kubernetes.io/hostname
    aff_self: np.ndarray         # [G, Ta] bool — the pod matches its own term
    aff_unplaced: np.ndarray     # [G, Ta] bool — an unplaced snapshot pod matches
    anti_valid: np.ndarray       # [G, Tb] bool — required pod-anti-affinity terms
    anti_err: np.ndarray         # [G] bool
    anti_empty: np.ndarray       # [G, Tb] bool
    anti_term: np.ndarray        # [G, Tb] int32 (into Td)
    anti_key: np.ndarray         # [G, Tb] int32
    anti_hostname: np.ndarray    # [G, Tb] bool
    pref_w: np.ndarray           # [G, Tp] float64 — preferred terms, signed weight
    pref_term: np.ndarray        # [G, Tp] int32 (into Td)
    pref_key: np.ndarray         # [G, Tp] int32
    # (namespace, selector) per first-sel sig, index 0 = None; the backend's
    # ServiceAffinity first-POD analysis resolves locks against these
    saa_defs: list = field(default_factory=list)
    # the topologyKey of each topo_dom row
    topo_keys: list = field(default_factory=list)


@dataclass
class PodColumns:
    """Per-pod numeric columns + signature ids (the scan's xs)."""

    req_cpu: np.ndarray          # [P] int64 milli
    req_mem: np.ndarray          # [P] int64
    req_gpu: np.ndarray          # [P] int64
    req_eph: np.ndarray          # [P] int64
    req_scalar: np.ndarray       # [P, S] int64
    nz_cpu: np.ndarray           # [P] int64 (non-zero-default cpu, priorities only)
    nz_mem: np.ndarray           # [P] int64
    zero_request: np.ndarray     # [P] bool (PodFitsResources fast path)
    best_effort: np.ndarray      # [P] bool
    sel_id: np.ndarray           # [P] int32
    tol_id: np.ndarray           # [P] int32
    aff_id: np.ndarray           # [P] int32
    avoid_id: np.ndarray         # [P] int32
    host_id: np.ndarray          # [P] int32
    group_id: np.ndarray         # [P] int32 — pod-group id (GroupTables)
    # pod-image-set signature id (ImageLocalityPriority table; zeros unless a
    # policy enables the priority — jaxe.policyc fills it then)
    img_id: np.ndarray           # [P] int32
    # ServiceAffinity predicate column (policy-only; policyc fills it)
    sa_self_id: np.ndarray       # [P] int32 — own-nodeSelector-pin signature


@dataclass
class DynamicInit:
    """Mutable aggregates seeded from pre-scheduled snapshot pods
    (NodeInfo.AddPod accounting, node_info.go:318-398)."""

    used_cpu: np.ndarray         # [N] int64
    used_mem: np.ndarray
    used_gpu: np.ndarray
    used_eph: np.ndarray
    used_scalar: np.ndarray      # [N, S] int64
    nonzero_cpu: np.ndarray      # [N] int64
    nonzero_mem: np.ndarray
    pod_count: np.ndarray        # [N] int64


@dataclass
class CompiledCluster:
    statics: NodeStatics
    tables: SignatureTables
    groups: GroupTables
    dynamic: DynamicInit
    scalar_names: List[str]
    node_index: Dict[str, int]
    has_ports: bool = False
    has_services: bool = False
    has_interpod: bool = False
    has_disk_conflict: bool = False
    has_maxpd: bool = False
    has_vol_zone: bool = False
    # taint_ok_noexec / saa tables hold real rows (vs the dummies the
    # no-policy path ships); jaxe.backend recompiles when a policy needs them
    has_noexec_table: bool = False
    has_saa_table: bool = False
    maxpd_limits: tuple = DEFAULT_MAXPD_LIMITS   # (EBS, GCE PD, AzureDisk)
    n_topo_doms: int = 1         # segment count for topo_dom (incl. invalid 0)
    n_zone_doms: int = 1
    unsupported: List[str] = field(default_factory=list)  # features needing fallback
    # compile_cluster's staging counts (0 where the incremental path built
    # the state): distinct node static classes, and engine matcher calls
    # made across the signature tables
    node_classes: int = 0
    row_evals: int = 0


def _selector_signature(pod: Pod):
    aff = pod.spec.affinity
    na = aff.node_affinity.to_obj() if (aff and aff.node_affinity) else None
    return {"nodeSelector": pod.spec.node_selector,
            "required": (na or {}).get("requiredDuringSchedulingIgnoredDuringExecution")}


def _toleration_signature(pod: Pod):
    return {"tolerations": [t.to_obj() for t in pod.spec.tolerations]}


def _affinity_signature(pod: Pod):
    aff = pod.spec.affinity
    na = aff.node_affinity.to_obj() if (aff and aff.node_affinity) else None
    return {"preferred": (na or {}).get("preferredDuringSchedulingIgnoredDuringExecution")}


def _avoid_signature(pod: Pod):
    ref = pod.metadata.controller_ref()
    if ref is None or ref.kind not in ("ReplicationController", "ReplicaSet"):
        return None
    return {"kind": ref.kind, "uid": ref.uid}


def _host_signature(pod: Pod):
    return pod.spec.node_name or None


# ---------------------------------------------------------------------------
# pod-group compilation (host ports / selector spreading / inter-pod affinity)
# ---------------------------------------------------------------------------

_ANY_IP = "0.0.0.0"


def _sanitized_ports(pod: Pod) -> list:
    """Wanted (ip, protocol, port) triples, HostPortInfo-sanitized
    (util/utils.go:51-137: ip defaults 0.0.0.0, protocol TCP, port>0 only)."""
    out = set()
    for c in pod.spec.containers:
        for p in c.ports:
            if p.host_port > 0:
                out.add((p.host_ip or _ANY_IP, p.protocol or "TCP", p.host_port))
    return sorted(out)


def _ports_conflict(wants: list, occupied: list) -> bool:
    """check_conflict over a full pod pair: 0.0.0.0 wildcards either side."""
    for wip, wproto, wport in wants:
        for oip, oproto, oport in occupied:
            if (wport == oport and wproto == oproto
                    and (wip == _ANY_IP or oip == _ANY_IP or wip == oip)):
                return True
    return False


def _group_signature(pod: Pod):
    aff = pod.spec.affinity
    return {
        "ns": pod.namespace,
        "labels": pod.metadata.labels,
        "aff": aff.pod_affinity.to_obj() if (aff and aff.pod_affinity) else None,
        "anti": (aff.pod_anti_affinity.to_obj()
                 if (aff and aff.pod_anti_affinity) else None),
        "ports": _sanitized_ports(pod),
        # volumes drive NoDiskConflict/MaxPDVolumeCount/NoVolumeZoneConflict;
        # [] keeps volume-less pods in one signature class
        "vols": sorted(json.dumps(v.to_obj(), sort_keys=True)
                       for v in pod.spec.volumes),
    }


def _has_interpod_terms(pod: Pod) -> bool:
    a = pod.spec.affinity
    return a is not None and (a.pod_affinity is not None
                              or a.pod_anti_affinity is not None)


def _req_aff_terms(pod: Pod) -> list:
    a = pod.spec.affinity
    return get_pod_affinity_terms(a.pod_affinity) if a else []


def _req_anti_terms(pod: Pod) -> list:
    a = pod.spec.affinity
    return get_pod_anti_affinity_terms(a.pod_anti_affinity) if a else []


def _pref_terms(pod: Pod) -> list:
    """Signed (weight, term): preferred affinity positive, anti negative
    (interpod_affinity.go processWeightedTerms multipliers)."""
    a = pod.spec.affinity
    out = []
    if a and a.pod_affinity:
        out += [(wt.weight, wt.pod_affinity_term) for wt in a.pod_affinity.preferred]
    if a and a.pod_anti_affinity:
        out += [(-wt.weight, wt.pod_affinity_term)
                for wt in a.pod_anti_affinity.preferred]
    return out


class _VolumeFallback(Exception):
    """Raised during volume compilation when the workload needs host-side
    semantics (resolution errors the reference reports per pod) or exceeds a
    budget; routes the batch to the parity engine."""


_MAXPD_TYPES = ("EBS", "GCE", "AzureDisk")
MAX_VOLUME_IDS = 4096


def _compile_volumes(raw_reps: List[Pod], nodes: List[Node],
                     snapshot: ClusterSnapshot, max_work: int):
    """Device tables for NoDiskConflict / MaxPDVolumeCount /
    NoVolumeZoneConflict (predicates.go:266-276, 288-460, 510-533).

    Volume sets are interned per (namespace, volumes) signature; PVC->PV
    resolution happens here against the snapshot, so the device only carries
    a per-node used-volume-id matrix and static conflict/zone tables.
    Returns (vsig_raw[Graw], disk_conflict[Dv,Dv], vol_mask[Dv,V],
    vol_type[V,3], zone_rows[Dv,N], limits, flags)."""
    import os

    from tpusim.engine.predicates import (
        _VOLUME_FILTERS,
        is_volume_conflict,
        label_zones_to_set,
    )

    graw = len(raw_reps)
    n = len(nodes)
    pvcs = {pvc.key(): pvc for pvc in snapshot.pvcs}
    pvs = {pv.name: pv for pv in snapshot.pvs}
    node_constraints = [
        {k: v for k, v in node.metadata.labels.items() if k in _ZONE_LABELS}
        for node in nodes]
    any_zone_nodes = any(node_constraints)

    # --- volume-set signature interning over raw groups ---
    vsig_ids: Dict[str, int] = {"": 0}
    vsig_reps: List[Optional[Pod]] = [None]
    vsig_raw = np.zeros(graw, np.int32)
    for b, rep in enumerate(raw_reps):
        if not rep.spec.volumes:
            continue
        key = json.dumps([rep.namespace,
                          sorted(json.dumps(v.to_obj(), sort_keys=True)
                                 for v in rep.spec.volumes)])
        vid = vsig_ids.get(key)
        if vid is None:
            vid = len(vsig_reps)
            vsig_ids[key] = vid
            vsig_reps.append(rep)
        vsig_raw[b] = vid
    dv = len(vsig_reps)
    if dv * dv + dv * n > max_work:
        raise _VolumeFallback(
            f"volume-set precompute ({dv} sets, {n} nodes) exceeds the jax "
            f"backend work budget ({max_work})")

    # --- NoDiskConflict: pairwise conflicts between volume sets ---
    disk_conflict = np.zeros((dv, dv), dtype=bool)
    for a in range(1, dv):
        for b in range(1, dv):
            disk_conflict[a, b] = any(
                is_volume_conflict(v, vsig_reps[b])
                for v in vsig_reps[a].spec.volumes)
    has_disk = bool(disk_conflict.any())

    # --- MaxPDVolumeCount: per-set relevant volume ids (resolved via PVC->PV;
    # unresolvable claims count conservatively toward every filter type) ---
    vol_ids: Dict[tuple, int] = {}
    set_ids: List[List[int]] = [[] for _ in range(dv)]
    id_types: List[set] = []

    def intern_vol(key: tuple, types: set) -> int:
        vid = vol_ids.get(key)
        if vid is None:
            vid = len(id_types)
            vol_ids[key] = vid
            id_types.append(set())
        id_types[vid] |= types
        return vid

    for s in range(1, dv):
        rep = vsig_reps[s]
        for vol in rep.spec.volumes:
            direct = False
            for t, name in enumerate(_MAXPD_TYPES):
                vol_src, _, id_field, _ = _VOLUME_FILTERS[name]
                src = vol_src(vol)
                if src is not None:
                    set_ids[s].append(intern_vol(
                        (name, src.get(id_field, "")), {t}))
                    direct = True
                    break
            if direct:
                continue
            pvc_name = vol.pvc_name
            if pvc_name is None:
                continue
            if pvc_name == "":
                raise _VolumeFallback(
                    "a pod volume has a PersistentVolumeClaim with no name")
            pvc = pvcs.get(f"{rep.namespace}/{pvc_name}")
            pv = pvs.get(pvc.volume_name) if (pvc and pvc.volume_name) else None
            if pv is None:
                # missing PVC / unbound PVC / missing PV: conservative id
                # counted toward every type (predicates.go:379-410); the zone
                # predicate would error on these when zone constraints exist
                if any_zone_nodes:
                    raise _VolumeFallback(
                        f'unresolvable PersistentVolumeClaim "{pvc_name}" with '
                        "zone-constrained nodes (NoVolumeZoneConflict errors "
                        "host-side)")
                set_ids[s].append(intern_vol(
                    ("pvc", f"{rep.namespace}/{pvc_name}"), {0, 1, 2}))
                continue
            for t, name in enumerate(_MAXPD_TYPES):
                _, pv_src, id_field, _ = _VOLUME_FILTERS[name]
                src = pv_src(pv)
                if src is not None:
                    set_ids[s].append(intern_vol(
                        (name, src.get(id_field, "")), {t}))
                    break
    v_count = len(id_types)
    max_vol_ids = int(os.environ.get("TPUSIM_MAX_VOLUME_IDS", MAX_VOLUME_IDS))
    if v_count > max_vol_ids:
        raise _VolumeFallback(
            f"{v_count} distinct MaxPD volume ids exceed the jax backend "
            f"limit ({max_vol_ids})")
    v_dim = max(v_count, 1)
    vol_mask = np.zeros((dv, v_dim), dtype=bool)
    for s in range(dv):
        for vid in set_ids[s]:
            vol_mask[s, vid] = True
    vol_type = np.zeros((v_dim, 3), dtype=bool)
    for vid, types in enumerate(id_types):
        for t in types:
            vol_type[vid, t] = True
    has_maxpd = v_count > 0
    limits = effective_maxpd_limits()

    # --- NoVolumeZoneConflict: static (volume set, node) pass/fail ---
    zone_rows = np.ones((dv, n), dtype=bool)
    has_zone = False
    if any_zone_nodes:
        for s in range(1, dv):
            rep = vsig_reps[s]
            for vol in rep.spec.volumes:
                pvc_name = vol.pvc_name
                if not pvc_name:
                    continue
                pvc = pvcs[f"{rep.namespace}/{pvc_name}"]  # resolved above
                pv = pvs[pvc.volume_name]
                for k, v in pv.metadata.labels.items():
                    if k not in _ZONE_LABELS:
                        continue
                    try:
                        allowed = label_zones_to_set(v)
                    except ValueError:
                        continue  # unparsable label ignored
                    for i, constraints in enumerate(node_constraints):
                        if not constraints:
                            continue  # zone-label-less node passes trivially
                        # a constrained node missing the PV's label fails too
                        # (nodeConstraints[k] yields "" in the reference)
                        if constraints.get(k) not in allowed:
                            zone_rows[s, i] = False
                            has_zone = True
    return (vsig_raw, disk_conflict, vol_mask, vol_type, zone_rows, limits,
            has_disk, has_maxpd, has_zone)


def _trivial_groups(num_pods: int, n: int) -> "GroupTables":
    z = np.zeros
    return GroupTables(
        group_of_pod=z(num_pods, np.int32), presence=z((1, n), np.int32),
        port_conflict=z((1, 1), bool), port_sig=z(1, np.int32),
        disk_conflict=z((1, 1), bool), disk_sig=z(1, np.int32),
        vol_mask=z((1, 1), bool), vol_type=z((1, 3), bool),
        zone_ok=np.ones((1, n), bool), used_vols_init=z((n, 1), bool),
        ss_rows=z((1, 1), bool), ss_sig=z(1, np.int32),
        saa_rows=z((1, 1), bool), saa_sig=z(1, np.int32),
        term_match=z((1, 1), bool),
        zone_dom=z(n, np.int32), topo_dom=z((1, n), np.int32),
        aff_valid=z((1, 1), bool), aff_err=z(1, bool), aff_empty=z((1, 1), bool),
        aff_term=z((1, 1), np.int32), aff_key=z((1, 1), np.int32),
        aff_hostname=z((1, 1), bool), aff_self=z((1, 1), bool),
        aff_unplaced=z((1, 1), bool),
        anti_valid=z((1, 1), bool), anti_err=z(1, bool), anti_empty=z((1, 1), bool),
        anti_term=z((1, 1), np.int32), anti_key=z((1, 1), np.int32),
        anti_hostname=z((1, 1), bool),
        pref_w=z((1, 1), np.float64), pref_term=z((1, 1), np.int32),
        pref_key=z((1, 1), np.int32))


def _compile_groups(snapshot: ClusterSnapshot, pods: List[Pod],
                    nodes: List[Node], node_index: Dict[str, int],
                    need_saa: bool = False):
    """Build GroupTables + feature flags. Returns
    (tables, has_ports, has_services, has_interpod, n_topo_doms, n_zone_doms,
    unsupported, sig_to_gid, vol_meta) where sig_to_gid maps each raw
    canonical group signature key to its merged group id (used by the
    incremental path) and vol_meta = (has_disk_conflict, has_maxpd,
    has_vol_zone, maxpd_limits)."""
    n = len(nodes)
    no_vol_meta = (False, False, False, DEFAULT_MAXPD_LIMITS)
    placed = [p for p in snapshot.pods if p.spec.node_name in node_index]
    # pods with an unknown-but-set nodeName still count for "matching pod
    # exists"; nodeName-less (pending) pods are dropped by the reference's pod
    # lister (backends.py scheduled-pod filter) and must not count
    unplaced = [p for p in snapshot.pods
                if p.spec.node_name and p.spec.node_name not in node_index]

    has_ports = any(_sanitized_ports(p) for p in pods) \
        or any(_sanitized_ports(p) for p in placed)
    has_interpod = any(_has_interpod_terms(p) for p in pods) \
        or any(_has_interpod_terms(p) for p in placed)
    has_services = bool(snapshot.services)
    has_volumes = any(p.spec.volumes for p in pods) \
        or any(p.spec.volumes for p in placed)
    if not (has_ports or has_interpod or has_services or has_volumes):
        return (_trivial_groups(len(pods), n), False, False, False, 1, 1, [],
                {}, no_vol_meta)
    args = (snapshot, pods, nodes, node_index, need_saa, placed, unplaced,
            has_ports, has_interpod, has_services, has_volumes)
    if not has_interpod:
        return _group_tables(*args)
    # inter-pod staging: topology interning, presence, the term and match
    # tables (the whole group build, which the terms drive here)
    with flight.span("interpod_stage") as sp:
        out = _group_tables(*args)
        if sp:
            gt = out[0]
            sp.set("topo_keys", len(gt.topo_keys))
            sp.set("hostname_keys", gt.topo_keys.count(LABEL_HOSTNAME))
            sp.set("topo_doms", out[4])
            sp.set("terms", int(gt.term_match.shape[0]) - 1)
            sp.set("groups", int(gt.presence.shape[0]))
    return out


def _group_tables(snapshot: ClusterSnapshot, pods: List[Pod],
                  nodes: List[Node], node_index: Dict[str, int],
                  need_saa: bool, placed: List[Pod], unplaced: List[Pod],
                  has_ports: bool, has_interpod: bool, has_services: bool,
                  has_volumes: bool):
    """_compile_groups past its feature scan: the same return tuple."""
    n = len(nodes)
    no_vol_meta = (False, False, False, DEFAULT_MAXPD_LIMITS)
    max_groups, max_raw, max_work, max_presence = _group_budgets()

    def fallback(reason: str):
        return (_trivial_groups(len(pods), n), False, False, False, 1, 1,
                [reason], {}, no_vol_meta)

    # --- 1. raw signature interning ---
    gi = Interner()
    raw_of_pod = [gi.intern(_group_signature(p), p) for p in pods]
    placed_raw = [gi.intern(_group_signature(p), p) for p in placed]
    graw = len(gi)
    if graw > max_raw:
        return fallback(f"{graw} distinct raw pod groups exceed the jax "
                        f"backend limit ({max_raw})")
    raw_reps = gi.representatives
    raw_keys = list(gi._ids.keys())  # insertion-ordered: index == raw id

    # --- volume tables (NoDiskConflict / MaxPDVolumeCount / NoVolumeZone) ---
    if has_volumes:
        try:
            (vsig_raw, disk_conflict, vsig_mask, vol_type, zone_rows,
             maxpd_limits, has_disk, has_maxpd, has_zone) = _compile_volumes(
                 raw_reps, nodes, snapshot, max_work)
        except _VolumeFallback as exc:
            return fallback(str(exc))
    else:
        vsig_raw = np.zeros(graw, np.int32)
        disk_conflict = np.zeros((1, 1), bool)
        vsig_mask = np.zeros((1, 1), bool)
        vol_type = np.zeros((1, 3), bool)
        zone_rows = np.ones((1, n), bool)
        maxpd_limits = DEFAULT_MAXPD_LIMITS
        has_disk = has_maxpd = has_zone = False

    # --- 2. intern matcher spaces: terms, port sets, spread signatures ---
    # term signature = (resolved namespaces, selector): that pair fully
    # determines which pods a term matches (predicates.go
    # podMatchesTermNamespaceAndSelector)
    term_defs: List[tuple] = [None]  # index 0 reserved: matches nothing
    term_ids: Dict[str, int] = {}

    def intern_term(rep: Pod, term) -> int:
        namespaces = get_namespaces_from_pod_affinity_term(rep, term)
        sel = term.label_selector
        key = json.dumps([sorted(namespaces),
                          sel.to_obj() if sel is not None else None],
                         sort_keys=True)
        tid = term_ids.get(key)
        if tid is None:
            tid = len(term_defs)
            term_ids[key] = tid
            term_defs.append((namespaces, sel))
        return tid

    # raw per-group actor term lists: [(tid, topology_key, weight)] per kind
    aff_of: List[list] = []
    anti_of: List[list] = []
    pref_of: List[list] = []
    if has_interpod:
        for rep in raw_reps:
            aff_of.append([(intern_term(rep, t), t.topology_key)
                           for t in _req_aff_terms(rep)])
            anti_of.append([(intern_term(rep, t), t.topology_key)
                            for t in _req_anti_terms(rep)])
            pref_of.append([(intern_term(rep, t), t.topology_key, w)
                            for w, t in _pref_terms(rep)])
    else:
        aff_of = anti_of = pref_of = [[] for _ in raw_reps]
    td = len(term_defs)

    # spread signature = (namespace, selected service selectors); 0 = none
    spread_defs: List[tuple] = [None]
    spread_ids: Dict[str, int] = {}
    ss_sig_raw = np.zeros(graw, np.int32)
    if has_services and len(snapshot.services) * graw > max_work:
        # the service->group scan below is O(services * graw); budget it like
        # the matcher rows so a huge snapshot can't hang host compile
        return fallback(
            f"pod-group service scan ({len(snapshot.services)} services x "
            f"{graw} raw groups) exceeds the jax backend work budget "
            f"({max_work})")
    # ServiceAntiAffinity first-service signature rides the same scan: the
    # spread loop builds `sels` in lister order, so the FIRST matching
    # service's selector (priorities.ServiceAntiAffinity
    # ._first_service_selector) is sels[0]; policy-only (need_saa)
    saa_defs: List[tuple] = [None]
    saa_ids: Dict[str, int] = {}
    saa_sig_raw = np.zeros(graw, np.int32)
    if has_services:
        for b, rep in enumerate(raw_reps):
            sels = [dict(svc.selector) for svc in snapshot.services
                    if (svc.namespace == rep.namespace and svc.selector
                        and all(rep.metadata.labels.get(k) == v
                                for k, v in svc.selector.items()))]
            if not sels:
                continue
            key = json.dumps([rep.namespace,
                              sorted(json.dumps(s, sort_keys=True) for s in sels)])
            sid = spread_ids.get(key)
            if sid is None:
                sid = len(spread_defs)
                spread_ids[key] = sid
                spread_defs.append((rep.namespace, sels))
            ss_sig_raw[b] = sid
            if need_saa:
                fkey = json.dumps([rep.namespace,
                                   json.dumps(sels[0], sort_keys=True)])
                fid = saa_ids.get(fkey)
                if fid is None:
                    fid = len(saa_defs)
                    saa_ids[fkey] = fid
                    saa_defs.append((rep.namespace, sels[0]))
                saa_sig_raw[b] = fid
    sd = len(spread_defs)
    fd = len(saa_defs)

    if (td + sd + (fd - 1)) * graw > max_work:
        return fallback(
            f"pod-group matcher precompute ({td} terms + {sd} spread sigs + "
            f"{fd - 1} service-anti-affinity sigs x {graw} raw groups) "
            f"exceeds the jax backend work budget ({max_work})")

    # port-set interning; 0 = no ports
    port_defs: List[list] = [[]]
    port_ids: Dict[tuple, int] = {(): 0}
    port_sig_raw = np.zeros(graw, np.int32)
    if has_ports:
        for b, rep in enumerate(raw_reps):
            ports = tuple(_sanitized_ports(rep))
            pid = port_ids.get(ports)
            if pid is None:
                pid = len(port_defs)
                port_ids[ports] = pid
                port_defs.append(list(ports))
            port_sig_raw[b] = pid
    pp = len(port_defs)
    port_conflict = np.zeros((pp, pp), dtype=bool)
    for a in range(1, pp):
        for b in range(1, pp):
            port_conflict[a, b] = _ports_conflict(port_defs[a], port_defs[b])

    # --- 3. matcher rows over raw groups ---
    term_match_raw = np.zeros((td, graw), dtype=bool)
    unplaced_match = np.zeros(td, dtype=bool)
    for tid in range(1, td):
        namespaces, sel = term_defs[tid]
        for b, rep in enumerate(raw_reps):
            term_match_raw[tid, b] = pod_matches_term_namespace_and_selector(
                rep, namespaces, sel)
        unplaced_match[tid] = any(
            pod_matches_term_namespace_and_selector(u, namespaces, sel)
            for u in unplaced)

    ss_rows_raw = np.zeros((sd, graw), dtype=bool)
    for sid in range(1, sd):
        ns, sels = spread_defs[sid]
        for b, rep in enumerate(raw_reps):
            ss_rows_raw[sid, b] = rep.namespace == ns and any(
                all(rep.metadata.labels.get(k) == v for k, v in sel.items())
                for sel in sels)

    saa_rows_raw = np.zeros((fd, graw), dtype=bool)
    for fid in range(1, fd):
        ns, sel = saa_defs[fid]
        for b, rep in enumerate(raw_reps):
            saa_rows_raw[fid, b] = rep.namespace == ns and all(
                rep.metadata.labels.get(k) == v for k, v in sel.items())

    # --- 4. merge raw groups by match profile ---
    # two raw groups are indistinguishable when every matcher treats them the
    # same (same term/spread columns, same port set) AND they act identically
    # (same own terms with the same topology keys/weights, same spread sig)
    merged: Dict[tuple, int] = {}
    gid_of_raw = np.zeros(graw, np.int32)
    rep_raw_idx: List[int] = []
    for b in range(graw):
        profile = (term_match_raw[:, b].tobytes(), ss_rows_raw[:, b].tobytes(),
                   saa_rows_raw[:, b].tobytes(),
                   int(port_sig_raw[b]), int(ss_sig_raw[b]),
                   int(saa_sig_raw[b]), int(vsig_raw[b]),
                   tuple(aff_of[b]), tuple(anti_of[b]), tuple(pref_of[b]))
        gid = merged.get(profile)
        if gid is None:
            gid = len(rep_raw_idx)
            merged[profile] = gid
            rep_raw_idx.append(b)
        gid_of_raw[b] = gid
    g = len(rep_raw_idx)
    if g > max_groups:
        return fallback(f"{g} distinct pod groups exceed the jax backend "
                        f"limit ({max_groups})")
    if g * n * 4 > max_presence:
        return fallback(
            f"pod-group presence state ({g} groups x {n} nodes) exceeds the "
            f"jax backend memory budget ({max_presence} bytes)")
    sig_to_gid = {key: int(gid_of_raw[b]) for b, key in enumerate(raw_keys)}

    group_of_pod = gid_of_raw[np.array(raw_of_pod, dtype=np.int64)] \
        if raw_of_pod else np.zeros(0, np.int32)
    group_of_pod = group_of_pod.astype(np.int32)
    reps = [raw_reps[b] for b in rep_raw_idx]
    sel_cols = np.array(rep_raw_idx, dtype=np.int64)
    term_match = term_match_raw[:, sel_cols] if graw else term_match_raw
    ss_rows = ss_rows_raw[:, sel_cols] if graw else ss_rows_raw
    saa_rows = saa_rows_raw[:, sel_cols] if graw else saa_rows_raw
    port_sig = port_sig_raw[sel_cols].astype(np.int32)
    ss_sig = ss_sig_raw[sel_cols].astype(np.int32)
    saa_sig = saa_sig_raw[sel_cols].astype(np.int32)

    disk_sig = vsig_raw[sel_cols].astype(np.int32)
    vol_mask = vsig_mask[vsig_raw[sel_cols]]        # [G, V]
    zone_ok = zone_rows[vsig_raw[sel_cols]]         # [G, N]

    presence = np.zeros((g, n), dtype=np.int32)
    used_vols_init = np.zeros((n, vsig_mask.shape[1]), dtype=bool)
    for raw_id, p in zip(placed_raw, placed):
        i = node_index[p.spec.node_name]
        presence[gid_of_raw[raw_id], i] += 1
        if has_maxpd:
            used_vols_init[i] |= vsig_mask[vsig_raw[raw_id]]

    zone_dom = np.zeros(n, dtype=np.int32)
    n_zone_doms = 1
    if has_services:
        zvals: Dict[str, int] = {}
        for i, node in enumerate(nodes):
            z = get_zone_key(node)
            if z:
                zone_dom[i] = zvals.setdefault(z, len(zvals) + 1)
        n_zone_doms = len(zvals) + 1

    # --- 5. topology keys + per-group actor tensors over merged groups ---
    topo_keys: List[str] = []
    if has_interpod:
        seen_keys = set()
        for b in rep_raw_idx:
            for tid, key in aff_of[b] + anti_of[b]:
                if key and key not in seen_keys:
                    seen_keys.add(key)
                    topo_keys.append(key)
            for tid, key, w in pref_of[b]:
                if key and key not in seen_keys:
                    seen_keys.add(key)
                    topo_keys.append(key)
    k_count = max(len(topo_keys), 1)
    key_idx = {key: i for i, key in enumerate(topo_keys)}

    topo_dom = np.zeros((k_count, n), dtype=np.int32)
    n_topo_doms = 1
    for k, key in enumerate(topo_keys):
        vals: Dict[str, int] = {}
        for i, node in enumerate(nodes):
            v = node.metadata.labels.get(key)
            if v is not None:
                topo_dom[k, i] = vals.setdefault(v, len(vals) + 1)
        n_topo_doms = max(n_topo_doms, len(vals) + 1)

    ta = max([1] + [len(aff_of[b]) for b in rep_raw_idx])
    tb = max([1] + [len(anti_of[b]) for b in rep_raw_idx])
    tp = max([1] + [len(pref_of[b]) for b in rep_raw_idx])
    aff_valid = np.zeros((g, ta), bool)
    aff_err = np.zeros(g, bool)
    aff_empty = np.zeros((g, ta), bool)
    aff_term = np.zeros((g, ta), np.int32)
    aff_key = np.zeros((g, ta), np.int32)
    aff_hostname = np.zeros((g, ta), bool)
    aff_self = np.zeros((g, ta), bool)
    aff_unplaced = np.zeros((g, ta), bool)
    anti_valid = np.zeros((g, tb), bool)
    anti_err = np.zeros(g, bool)
    anti_empty = np.zeros((g, tb), bool)
    anti_term = np.zeros((g, tb), np.int32)
    anti_key = np.zeros((g, tb), np.int32)
    anti_hostname = np.zeros((g, tb), bool)
    pref_w = np.zeros((g, tp), np.float64)
    pref_term = np.zeros((g, tp), np.int32)
    pref_key = np.zeros((g, tp), np.int32)

    if has_interpod:
        for a, b in enumerate(rep_raw_idx):
            for t, (tid, key) in enumerate(aff_of[b]):
                aff_valid[a, t] = True
                aff_term[a, t] = tid
                if not key:
                    # _any_pod_matches_term raises -> whole predicate fails
                    aff_empty[a, t] = True
                    aff_err[a] = True
                else:
                    aff_key[a, t] = key_idx[key]
                    aff_hostname[a, t] = key == LABEL_HOSTNAME
                aff_self[a, t] = term_match[tid, a]
                aff_unplaced[a, t] = unplaced_match[tid]
            for t, (tid, key) in enumerate(anti_of[b]):
                anti_valid[a, t] = True
                anti_term[a, t] = tid
                if not key:
                    anti_empty[a, t] = True
                    anti_err[a] = True
                else:
                    anti_key[a, t] = key_idx[key]
                    anti_hostname[a, t] = key == LABEL_HOSTNAME
            for t, (tid, key, w) in enumerate(pref_of[b]):
                if not key:
                    continue  # NodesHaveSameTopologyKey("") is always False
                pref_w[a, t] = float(w)
                pref_term[a, t] = tid
                pref_key[a, t] = key_idx[key]

    tables = GroupTables(
        group_of_pod=group_of_pod, presence=presence,
        port_conflict=port_conflict, port_sig=port_sig,
        disk_conflict=disk_conflict, disk_sig=disk_sig,
        vol_mask=vol_mask, vol_type=vol_type, zone_ok=zone_ok,
        used_vols_init=used_vols_init,
        ss_rows=ss_rows, ss_sig=ss_sig,
        saa_rows=saa_rows, saa_sig=saa_sig, saa_defs=list(saa_defs),
        term_match=term_match,
        zone_dom=zone_dom, topo_dom=topo_dom,
        aff_valid=aff_valid, aff_err=aff_err, aff_empty=aff_empty,
        aff_term=aff_term, aff_key=aff_key, aff_hostname=aff_hostname,
        aff_self=aff_self, aff_unplaced=aff_unplaced,
        anti_valid=anti_valid, anti_err=anti_err, anti_empty=anti_empty,
        anti_term=anti_term, anti_key=anti_key, anti_hostname=anti_hostname,
        pref_w=pref_w, pref_term=pref_term, pref_key=pref_key,
        topo_keys=topo_keys)
    return (tables, has_ports, has_services, has_interpod,
            n_topo_doms, n_zone_doms, [], sig_to_gid,
            (has_disk, has_maxpd, has_zone, maxpd_limits))


def node_static_row(node: Node, ni: NodeInfo, scalar_idx: Dict[str, int],
                    s: int):
    """One node's static column values (shared with the incremental path):
    (cpu, mem, gpu, eph, pods, scalar_row[s], cond_bits, mem_p, disk_p)."""
    r = ni.allocatable_resource
    scalar_row = np.zeros(s, dtype=np.int64)
    for name, v in r.scalar.items():
        scalar_row[scalar_idx[name]] = v
    bits = 0
    for cond in node.status.conditions:
        if cond.type == "Ready" and cond.status != "True":
            bits |= 1 << BIT_NODE_NOT_READY
        elif cond.type == "OutOfDisk" and cond.status != "False":
            bits |= 1 << BIT_NODE_OUT_OF_DISK
        elif cond.type == "NetworkUnavailable" and cond.status != "False":
            bits |= 1 << BIT_NODE_NETWORK_UNAVAILABLE
    if node.spec.unschedulable:
        bits |= 1 << BIT_NODE_UNSCHEDULABLE
    return (r.milli_cpu, r.memory, r.nvidia_gpu, r.ephemeral_storage,
            r.allowed_pod_number, scalar_row, bits, ni.memory_pressure,
            ni.disk_pressure)


def signature_row_fns(nodes: List[Node], node_infos: List["NodeInfo"]):
    """Per-signature-table row evaluators: kind -> (fn(rep, node_idx), dtype).

    Shared by compile_cluster and the incremental delta path (delta.py), so
    both compute table cells with exactly the same engine matchers. The
    sel/tol/aff/avoid/host interner each table reads from is fixed:
    selector_ok<-sel, taint_ok+intolerable<-tol, affinity_count<-aff,
    avoid_score<-avoid, host_ok<-host."""

    def selector_fn(rep: Optional[Pod], i: int) -> bool:
        return pod_matches_node_labels(rep, nodes[i])

    def taint_ok_fn(rep: Pod, i: int) -> bool:
        return find_matching_untolerated_taint(
            node_infos[i].taints, rep.spec.tolerations,
            lambda t: t.effect in ("NoSchedule", "NoExecute")) is None

    def taint_ok_noexec_fn(rep: Pod, i: int) -> bool:
        # PodToleratesNodeNoExecuteTaints (policy-registered): NoExecute only
        return find_matching_untolerated_taint(
            node_infos[i].taints, rep.spec.tolerations,
            lambda t: t.effect == "NoExecute") is None

    def intolerable_fn(rep: Pod, i: int) -> int:
        tols = [t for t in rep.spec.tolerations
                if not t.effect or t.effect == TAINT_PREFER_NO_SCHEDULE]
        return sum(1 for taint in node_infos[i].taints
                   if taint.effect == TAINT_PREFER_NO_SCHEDULE
                   and not tolerations_tolerate_taint(tols, taint))

    def affinity_fn(rep: Pod, i: int) -> int:
        return calculate_node_affinity_priority_map(rep, None, node_infos[i]).score

    def avoid_fn(rep: Pod, i: int) -> int:
        return calculate_node_prefer_avoid_pods_priority_map(rep, None, node_infos[i]).score

    def host_fn(rep: Pod, i: int) -> bool:
        return (not rep.spec.node_name) or rep.spec.node_name == nodes[i].name

    return {
        "selector_ok": (selector_fn, bool),
        "taint_ok": (taint_ok_fn, bool),
        "taint_ok_noexec": (taint_ok_noexec_fn, bool),
        "intolerable": (intolerable_fn, np.int64),
        "affinity_count": (affinity_fn, np.int64),
        "avoid_score": (avoid_fn, np.int64),
        "host_ok": (host_fn, bool),
    }


_condition_fields = attrgetter("type", "status")
_milli_value = methodcaller("milli_value")
_taint_fields = attrgetter("key", "value", "effect")
_ABSENT = object()  # a label the node lacks: equal to no label value


def _static_key(node: Node) -> tuple:
    """What NodeInfo.set_node and node_static_row read of a node: equal keys
    give equal NodeInfo aggregates and static rows. A cpu quantity enters
    NodeInfo as milli_value() and every other as value(), which its
    milli_value() fixes (the ceiling to 1/1000 determines the ceiling to 1).
    The last element is the taint set, which the taint tables read alone."""
    status, spec = node.status, node.spec
    alloc = status.allocatable
    return (tuple(alloc), tuple(map(_milli_value, alloc.values())),
            tuple(map(_condition_fields, status.conditions)),
            spec.unschedulable,
            tuple(map(_taint_fields, spec.taints)))


def _classes(keys: list) -> Tuple[np.ndarray, List[int]]:
    """Dense class id of each key, numbered by first appearance, and the
    index of each class's first member."""
    ids: Dict[object, int] = {}
    class_of = np.array([ids.setdefault(k, len(ids)) for k in keys],
                        dtype=np.intp)
    return class_of, np.unique(class_of, return_index=True)[1].tolist()


def _label_keys_read(rep: Pod, kind: str) -> List[str]:
    """The node label keys that the selector_ok or affinity_count matcher
    reads for `rep`: NodeSelectorRequirement.matches and the nodeSelector
    loop look labels up by these keys and no others."""
    keys: List[str] = []
    aff = rep.spec.affinity
    na = aff.node_affinity if aff is not None else None
    if kind == "selector_ok":
        keys.extend(rep.spec.node_selector or ())
        terms = (na.required_terms or []) if na is not None else []
        for term in terms:
            keys.extend(e.key for e in term.match_expressions)
    elif na is not None:
        for term in na.preferred:
            if term.weight != 0:
                keys.extend(e.key for e in term.preference.match_expressions)
    return keys


class _HeadNodeInfos:
    """node_infos[i] for signature_row_fns, built only for the nodes that a
    matcher is called on: the static class's NodeInfo where node i heads its
    class, else one of node i's own (the matchers read node_info.node)."""

    def __init__(self, nodes: List[Node], infos: Dict[int, NodeInfo]):
        self._nodes = nodes
        self._infos = infos

    def __getitem__(self, i: int) -> NodeInfo:
        ni = self._infos.get(i)
        if ni is None:
            ni = self._infos[i] = NodeInfo()
            ni.set_node(self._nodes[i])
        return ni


def fill_pod_request_row(cols: PodColumns, j: int, pod: Pod, req,
                         scalar_idx: Dict[str, int]) -> None:
    """Fill one pod's numeric request columns (shared with delta.py so the
    incremental path can never drift from the fresh-compile semantics)."""
    cols.req_cpu[j] = req.milli_cpu
    cols.req_mem[j] = req.memory
    cols.req_gpu[j] = req.nvidia_gpu
    cols.req_eph[j] = req.ephemeral_storage
    for name, v in req.scalar.items():
        cols.req_scalar[j, scalar_idx[name]] = v
    cols.zero_request[j] = (req.milli_cpu == 0 and req.memory == 0
                            and req.nvidia_gpu == 0 and req.ephemeral_storage == 0
                            and not req.scalar)
    nz = get_nonzero_pod_request(pod)
    cols.nz_cpu[j] = nz.milli_cpu
    cols.nz_mem[j] = nz.memory
    cols.best_effort[j] = is_pod_best_effort(pod)


def compile_cluster(snapshot: ClusterSnapshot, pods: List[Pod],
                    need_noexec: bool = False, need_saa: bool = False
                    ) -> Tuple[CompiledCluster, PodColumns]:
    """Build columnar state for `pods` scheduled against `snapshot`.

    Static matching reuses the parity engine's own functions (semantics match
    by construction); only numeric aggregates stay dynamic. need_noexec:
    compute the PodToleratesNodeNoExecuteTaints table — only a policy can
    enable that predicate, so the default path skips the row work and ships
    an all-pass dummy of the right shape.

    Node-side work runs once per class of nodes that read alike, not once
    per node: one NodeInfo and one static row per static class
    (_static_key), and one matcher call per group of nodes that a
    signature's matcher cannot tell apart (the labels it looks up, the
    taints, the prefer-avoid annotation, the name), broadcast to the
    group. Nodes that all differ make one class each.
    """
    nodes = snapshot.nodes
    n = len(nodes)

    # node static classes: one NodeInfo per class, from its first node
    static_keys = [_static_key(node) for node in nodes]
    node_class, heads = _classes(static_keys)
    class_infos: List[NodeInfo] = []
    for i in heads:
        ni = NodeInfo()
        ni.set_node(nodes[i])
        class_infos.append(ni)
    pod_requests = [get_resource_request(pod) for pod in pods]
    existing_requests = [get_resource_request(pod) for pod in snapshot.pods]

    scalar_names: List[str] = []
    seen = set()

    def _note_scalars(names):
        for name in names:
            if name not in seen:
                seen.add(name)
                scalar_names.append(name)

    for req in pod_requests + existing_requests:
        _note_scalars(req.scalar)
    for ni in class_infos:  # class heads in node order: the same name order
        _note_scalars(ni.allocatable_resource.scalar)
    s = len(scalar_names)
    scalar_idx = {name: i for i, name in enumerate(scalar_names)}

    # --- node statics: one row per class, gathered to its nodes ---
    rows = [node_static_row(nodes[i], ni, scalar_idx, s)
            for i, ni in zip(heads, class_infos)]

    def column(k: int, dtype) -> np.ndarray:
        return np.array([row[k] for row in rows], dtype=dtype)[node_class]

    class_scalar = np.zeros((len(rows), s), dtype=np.int64)
    for c, row in enumerate(rows):
        class_scalar[c] = row[5]
    statics = NodeStatics(
        names=[nd.name for nd in nodes],
        alloc_cpu=column(0, np.int64), alloc_mem=column(1, np.int64),
        alloc_gpu=column(2, np.int64), alloc_eph=column(3, np.int64),
        allowed_pods=column(4, np.int64),
        alloc_scalar=class_scalar[node_class],
        cond_fail_bits=column(6, np.int64),
        mem_pressure=column(7, bool), disk_pressure=column(8, bool))

    # --- pod columns + signature interning ---
    p = len(pods)
    cols = PodColumns(
        req_cpu=np.zeros(p, dtype=np.int64), req_mem=np.zeros(p, dtype=np.int64),
        req_gpu=np.zeros(p, dtype=np.int64), req_eph=np.zeros(p, dtype=np.int64),
        req_scalar=np.zeros((p, s), dtype=np.int64),
        nz_cpu=np.zeros(p, dtype=np.int64), nz_mem=np.zeros(p, dtype=np.int64),
        zero_request=np.zeros(p, dtype=bool), best_effort=np.zeros(p, dtype=bool),
        sel_id=np.zeros(p, dtype=np.int32), tol_id=np.zeros(p, dtype=np.int32),
        aff_id=np.zeros(p, dtype=np.int32), avoid_id=np.zeros(p, dtype=np.int32),
        host_id=np.zeros(p, dtype=np.int32), group_id=np.zeros(p, dtype=np.int32),
        img_id=np.zeros(p, dtype=np.int32),
        sa_self_id=np.zeros(p, dtype=np.int32))

    sel_i, tol_i, aff_i, avoid_i, host_i = (Interner() for _ in range(5))
    unsupported: List[str] = []
    for j, pod in enumerate(pods):
        fill_pod_request_row(cols, j, pod, pod_requests[j], scalar_idx)
        cols.sel_id[j] = sel_i.intern(_selector_signature(pod), pod)
        cols.tol_id[j] = tol_i.intern(_toleration_signature(pod), pod)
        cols.aff_id[j] = aff_i.intern(_affinity_signature(pod), pod)
        cols.avoid_id[j] = avoid_i.intern(_avoid_signature(pod), pod)
        cols.host_id[j] = host_i.intern(_host_signature(pod), pod)

    node_index = {nd.name: i for i, nd in enumerate(nodes)}
    (groups, has_ports, has_services, has_interpod, n_topo_doms, n_zone_doms,
     group_unsupported, _, vol_meta) = _compile_groups(snapshot, pods, nodes,
                                                       node_index,
                                                       need_saa=need_saa)
    has_disk_conflict, has_maxpd, has_vol_zone, maxpd_limits = vol_meta
    unsupported.extend(group_unsupported)
    cols.group_id = groups.group_of_pod

    # --- static [signature, node] tables: one matcher call per group of
    # nodes that the matcher reads alike for the signature ---
    row_fns = signature_row_fns(
        nodes, _HeadNodeInfos(nodes, dict(zip(heads, class_infos))))
    groupings: Dict[tuple, Tuple[np.ndarray, List[int]]] = {}
    row_evals = 0

    def groups_of(rep: Pod, kind: str):
        """(group of each node, first node of each group) over what the
        kind's matcher reads of a node for `rep`; None where that is
        nothing that differs between nodes."""
        if kind in ("selector_ok", "affinity_count"):
            label_keys = tuple(_label_keys_read(rep, kind))
            if not label_keys:
                return None
            reads = ("labels", label_keys)
        elif kind == "avoid_score":
            if _avoid_signature(rep) is None:
                return None
            reads = ("avoid",)
        elif kind == "host_ok":
            if not rep.spec.node_name:
                return None
            reads = ("name",)
        else:
            reads = ("taints",)
        if reads not in groupings:
            if reads[0] == "labels":
                keys = [tuple([labels.get(k, _ABSENT) for k in label_keys])
                        for labels in (nd.metadata.labels for nd in nodes)]
            elif reads[0] == "avoid":
                keys = [nd.metadata.annotations.get(PREFER_AVOID_PODS_ANNOTATION)
                        for nd in nodes]
            elif reads[0] == "name":
                keys = [nd.name for nd in nodes]
            else:
                keys = [k[-1] for k in static_keys]
            groupings[reads] = _classes(keys)
        return groupings[reads]

    def table(interner: Interner, kind: str):
        nonlocal row_evals
        fn, dtype = row_fns[kind]
        t = np.zeros((max(len(interner), 1), n), dtype=dtype)
        if n == 0:
            return t
        for sig_id, rep in enumerate(interner.representatives):
            groups = groups_of(rep, kind)
            if groups is None:
                t[sig_id] = fn(rep, 0)
                row_evals += 1
                continue
            group_of, firsts = groups
            t[sig_id] = np.array([fn(rep, i) for i in firsts], dtype=dtype)[group_of]
            row_evals += len(firsts)
        return t

    tables = SignatureTables(
        selector_ok=table(sel_i, "selector_ok"),
        taint_ok=table(tol_i, "taint_ok"),
        taint_ok_noexec=(table(tol_i, "taint_ok_noexec") if need_noexec else
                         np.ones((max(len(tol_i), 1), n), dtype=bool)),
        intolerable=table(tol_i, "intolerable"),
        affinity_count=table(aff_i, "affinity_count"),
        avoid_score=table(avoid_i, "avoid_score"),
        host_ok=table(host_i, "host_ok"),
    )

    # --- dynamic aggregates from pre-scheduled pods ---
    dyn = DynamicInit(
        used_cpu=np.zeros(n, dtype=np.int64), used_mem=np.zeros(n, dtype=np.int64),
        used_gpu=np.zeros(n, dtype=np.int64), used_eph=np.zeros(n, dtype=np.int64),
        used_scalar=np.zeros((n, s), dtype=np.int64),
        nonzero_cpu=np.zeros(n, dtype=np.int64), nonzero_mem=np.zeros(n, dtype=np.int64),
        pod_count=np.zeros(n, dtype=np.int64))
    for k, existing in enumerate(snapshot.pods):
        i = node_index.get(existing.spec.node_name)
        if i is None:
            continue
        req = existing_requests[k]
        dyn.used_cpu[i] += req.milli_cpu
        dyn.used_mem[i] += req.memory
        dyn.used_gpu[i] += req.nvidia_gpu
        dyn.used_eph[i] += req.ephemeral_storage
        for name, v in req.scalar.items():
            dyn.used_scalar[i, scalar_idx[name]] += v
        nz = get_nonzero_pod_request(existing)
        dyn.nonzero_cpu[i] += nz.milli_cpu
        dyn.nonzero_mem[i] += nz.memory
        dyn.pod_count[i] += 1

    compiled = CompiledCluster(statics=statics, tables=tables, groups=groups,
                               dynamic=dyn, scalar_names=scalar_names,
                               node_index=node_index,
                               has_ports=has_ports, has_services=has_services,
                               has_interpod=has_interpod,
                               has_disk_conflict=has_disk_conflict,
                               has_maxpd=has_maxpd, has_vol_zone=has_vol_zone,
                               has_noexec_table=need_noexec,
                               has_saa_table=need_saa,
                               maxpd_limits=maxpd_limits,
                               n_topo_doms=n_topo_doms, n_zone_doms=n_zone_doms,
                               unsupported=unsupported,
                               node_classes=len(heads), row_evals=row_evals)
    return compiled, cols


def reason_strings(scalar_names: List[str]) -> List[str]:
    return REASON_STRINGS + [f"Insufficient {name}" for name in scalar_names]


def victim_order_columns(pods: List, node_index: dict):
    """Victim-ordering columns for device-side preemption (jaxe/preempt.py
    _VictimTable seed): one row per PLACED pod of `pods`, in list order.

    Row order is the parity-critical part: the host oracle's
    sort_by_priority_desc over NodeInfo.pods is a STABLE sort, and
    NodeInfo.pods is append-ordered (snapshot order, then bind order), so a
    table seeded in snapshot order and appended to on every bind reproduces
    the host's victim ordering with a stable (-priority, row) lexsort.

    Returns (node_i int32[R], prio int64[R], req int64[R, 4] —
    cpu/mem/gpu/eph in get_resource_request units — and the row-parallel
    list of pod objects). Pods without a known node are skipped (they can
    never be victims: victim selection only reads NodeInfo.pods)."""
    from tpusim.engine.resources import get_resource_request
    from tpusim.engine.util import get_pod_priority

    rows = [(node_index[p.spec.node_name], p) for p in pods
            if p.spec.node_name and p.spec.node_name in node_index]
    r = len(rows)
    node_i = np.zeros(r, dtype=np.int32)
    prio = np.zeros(r, dtype=np.int64)
    req = np.zeros((r, 4), dtype=np.int64)
    objs = []
    for k, (i, p) in enumerate(rows):
        node_i[k] = i
        prio[k] = get_pod_priority(p)
        pr = get_resource_request(p)
        req[k, 0] = pr.milli_cpu
        req[k, 1] = pr.memory
        req[k, 2] = pr.nvidia_gpu
        req[k, 3] = pr.ephemeral_storage
        objs.append(p)
    return node_i, prio, req, objs

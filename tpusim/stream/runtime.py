"""Streaming scheduler runtime: device-resident cluster state, O(delta)
scatter commits (ISSUE 7), compiled-policy residency and pipelined cycle
execution (ISSUE 9).

Every other execution path in this repo re-stages the full compiled cluster
(statics + dynamic carry) onto device per scheduling attempt, so churn-heavy
steady state pays O(cluster) host→HBM traffic per cycle — BASELINE.md's
r02→r05 warm-CPU slide (11,410 → 6,232 pods/s on an unchanged placement
hash) is that staging contention. The reference simulator never re-lists the
world per decision: its reflector→informer fabric mutates a persistent cache
in place. This module is the device-side analog:

  DeviceResidentCluster — the compiled arrays stay in HBM across decisions;
      watch-fabric deltas land as donated scatter updates
      (kernels.apply_delta_donated) gathered from the IncrementalCluster's
      journal, so a warm cycle's update cost is O(touched rows), not
      O(nodes).
  StreamSession — drives ingest → scatter-commit → schedule → fold-back.
      Binds from the fused scan update the resident carry directly (the
      scan's final carry IS the post-bind state — zero host round-trip);
      host fold-back journal entries are therefore discarded, not
      re-committed. Structural events the scatter path can't express (node
      churn, group-table invalidation, signature-memo eviction, scalar
      universe growth, watch 410-relists) fall back to a full restage,
      classified in tpusim_stream_restage_total{reason}.

Exactness contract (tested by the churn-parity fuzz): stream-path placements
are byte-identical (placement_hash) to scheduling every batch through the
full-restage path (JaxBackend.schedule on a fresh compile) over any event
sequence. The parity argument: the host IncrementalCluster stays the source
of truth; commits scatter-`set` AUTHORITATIVE host values (idempotent,
self-healing), the commit re-arms the per-batch lanes (sa_lock/rr) exactly
like carry_init_host — including the policy ServiceAffinity segment locks,
recomputed per commit from the live pod set the way a restage would — and
every field without a scatter path (presence_dom, used_vols, group tables)
only changes under events that force a restage. Statics columns gained a
scatter path in v2: label/taint-only node churn lands as
kernels.apply_statics_delta_donated over the churned columns (signature
rows re-gathered from the host memo, policy rows recomputed against the
RESIDENT interning), so a fixed plan signature rides out arbitrary
label/taint churn with zero restages; only a genuine plan change restages,
classified as policy_plan_change.

Pipelined execution (schedule_pipelined/poll_placed/flush) keeps the same
contract: dispatch cycle N's device program without blocking, decode cycle
N-1's placements while N runs, and fold N-1's binds back BEFORE the driver
draws N's events — the host picture evolves in exactly the synchronous
order, so emitted placements and placement_hash are byte-identical to the
synchronous path. Any off-stream condition (chaos installed, restage
reason, no nodes) drains the in-flight cycle and runs synchronously.

Chaos composition mirrors jaxe.backend.JaxBackend.schedule: host faults
(node flap, pod evict, watch drop) arrive as ordinary deltas; device faults
flow through the same circuit breaker + injector seam, and any chaos
intervention (fault, corruption, verify divergence, open breaker)
invalidates residency so the next cycle re-arms from host truth.
"""

from __future__ import annotations

import logging
from contextlib import nullcontext
from time import perf_counter
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from tpusim.api.snapshot import ClusterSnapshot
from tpusim.api.types import Pod, ResourceType
from tpusim.backends import (
    Placement,
    ReferenceBackend,
    bind_pod,
    mark_unschedulable,
    placement_hash,
)
from tpusim.engine.providers import DEFAULT_PROVIDER
from tpusim.framework.events import WatchExpiredError
from tpusim.framework.metrics import register, since_in_microseconds
from tpusim.framework.reflector import Reflector
from tpusim.framework.store import MODIFIED
from tpusim.gang.driver import schedule_with_gangs
from tpusim.gang.group import has_gangs
from tpusim.jaxe import backend as _backend
from tpusim.jaxe import ensure_x64
from tpusim.jaxe.delta import _SIG_KINDS, IncrementalCluster
from tpusim.jaxe.kernels import (
    DeltaRows,
    StaticsDelta,
    apply_delta_donated,
    apply_statics_delta_donated,
    carry_init_host,
    config_for,
    overlay_restore_donated,
    pad_infeasible_rows,
    pod_columns_to_host,
    schedule_scan_donated,
    statics_to_host,
)
from tpusim.jaxe.policyc import (
    build_policy_residency,
    build_policy_tables,
    compile_policy,
    policy_delta_columns,
    policy_plan_key,
    remap_policy_columns,
    sa_lock_init_rows,
)
from tpusim.jaxe.sharding import shard_mesh, stage_node_sharded, stage_tree
from tpusim.jaxe.state import NUM_FIXED_BITS, reason_strings
from tpusim.jaxe.trust import SHARD
from tpusim.obs import analytics
from tpusim.obs import provenance
from tpusim.obs import recorder as flight
from tpusim.obs import slo
from tpusim.obs import tracectx

log = logging.getLogger(__name__)

# Scatter-commit and pod-batch axes are padded up to pow2 buckets (floor 8):
# the warm steady state cycles through a handful of compiled programs instead
# of one per delta count — the zero-retrace contract kernels.py documents.
MIN_BUCKET = 8


def bucket_size(n: int) -> int:
    """Smallest pow2 >= n, floored at MIN_BUCKET."""
    return max(MIN_BUCKET, 1 << max(0, n - 1).bit_length())


def _pad_index(idx: np.ndarray, size: int) -> np.ndarray:
    """Pad an index vector to `size` by repeating its first entry (index 0
    when empty): duplicates are safe under the commit's `set` semantics
    because every duplicate carries the same authoritative value."""
    if len(idx) >= size:
        return idx
    fill = idx[0] if len(idx) else np.int32(0)
    return np.concatenate([idx, np.full(size - len(idx), fill, np.int32)])


class DeviceResidentCluster:
    """The device half of the stream runtime: compiled statics + carry held
    in HBM across decisions, plus the host-side metadata needed to prove a
    new batch can reuse them (resident signature-row interning, group batch
    keys, node/scalar shape)."""

    def __init__(self):
        self.compiled = None          # host CompiledCluster of the restage
        self.config = None            # EngineConfig (jit-static)
        self.statics = None           # device Statics
        self.carry = None             # device Carry — THE resident state
        self.sig_rows: Optional[Dict[str, Dict[object, int]]] = None
        self.plan_key = None          # policyc.policy_plan_key of the restage
        self.ptabs = None             # host PolicyTables of the restage
        self.pol_res = None           # policyc.PolicyResidency interning
        self.n_nodes = 0
        self.scalar_width = 0
        self.evictions_mark = 0       # inc.sig_evictions at adopt time
        self.commits = 0              # scatter commits since construction
        self.restages = 0

    @property
    def valid(self) -> bool:
        return self.carry is not None

    def invalidate(self) -> None:
        self.compiled = self.config = self.statics = self.carry = None
        self.sig_rows = None
        self.plan_key = self.ptabs = self.pol_res = None

    def adopt(self, inc: IncrementalCluster, compiled, config, statics,
              carry, plan_key=None, ptabs=None, pol_res=None) -> None:
        """Install a freshly restaged state as resident."""
        self.compiled = compiled
        self.config = config
        self.statics = statics
        self.carry = carry
        # resident signature-row order per kind: later batches' batch-local
        # ids are remapped through these dicts onto the resident table rows
        self.sig_rows = {kind: {key: row for row, key in enumerate(keys)}
                         for kind, keys in inc.last_batch_key_lists.items()}
        self.plan_key = plan_key
        self.ptabs = ptabs
        self.pol_res = pol_res
        self.n_nodes = len(compiled.statics.names)
        self.scalar_width = len(compiled.scalar_names)
        self.evictions_mark = inc.sig_evictions
        self.restages += 1

    def residency_miss(self, inc: IncrementalCluster,
                       plan_key=None) -> Optional[str]:
        """A structural reason the resident arrays cannot serve the next
        cycle, or None. Ordering matters for the classifier: node events
        also dirty the group tables, so the node-set check runs first; a
        plan-signature change outranks everything but a cold start (the
        resident policy tables serve the OLD plan, whatever else holds)."""
        if self.carry is None:
            return "cold_start"
        if plan_key != self.plan_key:
            return "policy_plan_change"
        if len(inc.nodes) != self.n_nodes:
            return "node_set"
        if inc._groups_dirty:
            return "groups_dirty"
        if len(inc._scalar_names) != self.scalar_width:
            return "scalar_set"
        return None

    def remap_signatures(self, inc: IncrementalCluster, cols,
                         key_lists: Dict[str, List]) -> Optional[str]:
        """Rewrite the batch's batch-local signature ids into resident table
        row ids in place. Returns None on success, or the restage reason for
        a signature the resident tables have no row for ("sig_evict" when
        the memo has evicted rows since the restage — the miss may be cache
        pressure, not novelty)."""
        luts = {}
        for kind, keys in key_lists.items():
            resident = self.sig_rows[kind]
            try:
                luts[kind] = np.fromiter((resident[k] for k in keys),
                                         dtype=np.int32, count=len(keys))
            except KeyError:
                return ("sig_evict"
                        if inc.sig_evictions > self.evictions_mark
                        else "new_signature")
        for kind, lut in luts.items():
            col = getattr(cols, kind)
            col[:] = lut[col]
        return None

    def commit(self, inc: IncrementalCluster, sa_lock_init) -> None:
        """Drain the IncrementalCluster's delta journal and scatter-commit
        the AUTHORITATIVE post-event values of every touched node row /
        presence cell into the resident carry (donated: the HBM buffers are
        patched in place). Always dispatches — even with an empty journal —
        because the commit also re-arms the per-batch lanes (sa_lock/rr) to
        the values a fresh restage would stage (`sa_lock_init`: all-unlocked
        for providers, the live first-matching-pod pins for ServiceAffinity
        policies), keeping stream and restage cycles byte-identical."""
        gsp = flight.span("commit_gather")
        nodes, cells = inc.drain_journal()
        dyn = inc._ensure_dyn()
        idx = np.fromiter(sorted(nodes), dtype=np.int32, count=len(nodes))
        idx = _pad_index(idx, bucket_size(max(len(idx), 1)))
        rows = DeltaRows(
            used_cpu=dyn.used_cpu[idx], used_mem=dyn.used_mem[idx],
            used_gpu=dyn.used_gpu[idx], used_eph=dyn.used_eph[idx],
            used_scalar=dyn.used_scalar[idx],
            nonzero_cpu=dyn.nonzero_cpu[idx],
            nonzero_mem=dyn.nonzero_mem[idx],
            pod_count=dyn.pod_count[idx])
        cell_list = sorted(cells)
        gid = np.fromiter((g for g, _ in cell_list), dtype=np.int32,
                          count=len(cell_list))
        nid = np.fromiter((n for _, n in cell_list), dtype=np.int32,
                          count=len(cell_list))
        size = bucket_size(max(len(gid), 1))
        gid, nid = _pad_index(gid, size), _pad_index(nid, size)
        if inc._presence is not None:
            val = inc._presence[gid, nid].astype(np.int32)
        else:
            # trivial [1, N] dummy presence: the padded (0, 0) cells are
            # untouched zeros on both sides
            val = np.zeros(size, np.int32)
        if gsp:
            gsp.set("rows", int(len(nodes)))
            gsp.set("cells", int(len(cells)))
            gsp.end()
        sp = flight.span("stream_commit", "device")
        self.carry = apply_delta_donated(self.carry, idx, rows, gid, nid, val,
                                         sa_lock_init)
        if sp:
            sp.set("rows", int(len(nodes)))
            sp.set("cells", int(len(cells)))
            sp.end()
        self.commits += 1


class _PendingCycle:
    """One in-flight (or sync-buffered) pipelined cycle: the donated scan's
    un-forced device outputs plus everything the deferred decode needs."""

    __slots__ = ("pods", "choices", "counts", "compiled", "t0",
                 "dispatched_at", "folded", "bound", "placements",
                 "wal_cycle", "trace")

    def __init__(self, pods, choices=None, counts=None, compiled=None,
                 t0=0.0, dispatched_at=0.0, placements=None,
                 wal_cycle=None):
        self.pods = pods
        self.choices = choices
        self.counts = counts
        self.compiled = compiled
        self.t0 = t0
        self.dispatched_at = dispatched_at
        self.folded = placements is not None
        self.bound: List[Placement] = []
        self.placements = placements
        # WAL cycle id when a persistence layer is attached; None for a
        # sync-buffered cycle (schedule() already journaled its commit)
        self.wal_cycle = wal_cycle
        # the dispatching cycle's trace context: the deferred decode and
        # fold-time bind journaling run under THIS cycle's trace, not the
        # overlapping cycle's (ISSUE 20)
        self.trace = tracectx.current()


class StreamSession:
    """Drives the streaming loop: ingest watch deltas → scatter-commit →
    schedule on the resident state → fold placements back.

    v2 scope (ISSUE 9): providers AND compiled policies — every policy the
    Pallas fused scan can express stays device-resident, keyed on its plan
    signature. Unsupported feature combinations (extenders, unsupported
    predicates) route whole batches through the reference backend,
    classified like every other fallback.
    """

    def __init__(self, snapshot: Optional[ClusterSnapshot] = None, *,
                 incremental: Optional[IncrementalCluster] = None,
                 provider: str = DEFAULT_PROVIDER,
                 hard_pod_affinity_symmetric_weight: int = 10,
                 always_restage: bool = False,
                 policy=None, compiled_policy=None):
        """always_restage: disable the O(delta) fast path — every cycle pays
        the full compile + device staging. The bench's restage-vs-stream
        comparison arm; placements are identical either way.
        policy/compiled_policy: a scheduler Policy compiled for residency
        (compile-time validation mirrors JaxBackend); swap mid-session via
        set_policy (a plan-signature change restages once)."""
        if provider not in _backend._KNOWN_PROVIDERS:
            raise KeyError(f"plugin {provider!r} has not been registered")
        if policy is not None and compiled_policy is None:
            compiled_policy = compile_policy(policy)
        ensure_x64()
        self.inc = (incremental if incremental is not None
                    else IncrementalCluster(snapshot))
        self.provider = provider
        self.hard_weight = hard_pod_affinity_symmetric_weight
        self.always_restage = always_restage
        self.policy = policy
        self.cp = compiled_policy
        self._plan_key = policy_plan_key(compiled_policy)
        self.device = DeviceResidentCluster()
        self.cycles = 0
        self.restage_counts: Dict[str, int] = {}
        self.path_counts: Dict[str, int] = {}
        self._forced: Optional[str] = None
        self._reflectors: List[Reflector] = []
        self._statics_patch = None    # (padded idx, StaticsDelta) or None
        self._pending: Optional[_PendingCycle] = None
        self._last_path: Optional[str] = None
        self._gang_jax = None         # lazy JaxBackend for gang cycles
        self.persist = None           # stream.persist.StreamPersistence
        # node-sharded residency (ISSUE 16 sub-problem c): set by the
        # restage when TPUSIM_SHARDS engages — the resident twin then lives
        # shard-even padded over the mesh's "node" axis, stream cycles run
        # the shard_map scan, and delta scatter-commits touch only the
        # owner shard's block (O(delta-per-shard))
        self._shard_mesh = None
        self._shard_layout: Optional[Dict[str, int]] = None
        # HBM residency accounting (ISSUE 14): polled at scrape/snapshot
        # time only; the weakref drops the source with the session
        analytics.register_hbm_source(
            "stream_twin", self.device,
            lambda dev: (analytics.tree_nbytes((dev.statics, dev.carry)),
                         1 if dev.valid else 0))

    def set_policy(self, policy=None, compiled_policy=None) -> None:
        """Swap the session's scheduling policy. The next cycle restages
        exactly once, classified policy_plan_change, unless the new plan
        signature matches the resident one."""
        if policy is not None and compiled_policy is None:
            compiled_policy = compile_policy(policy)
        self.policy = policy
        self.cp = compiled_policy
        self._plan_key = policy_plan_key(compiled_policy)

    # -- ingest -----------------------------------------------------------

    def apply(self, event_type: str, obj) -> None:
        self.inc.apply(event_type, obj)

    def apply_events(self, events) -> None:
        with flight.root("stream_ingest") as sp:
            if sp:
                events = list(events)
                sp.set("events", len(events))
            self.inc.apply_events(events)

    def ingest(self, watch_buffer) -> int:
        """Drain a WatchBuffer into the host picture. A torn stream (410
        Gone analog) forces a restage on the next cycle and re-raises so
        the caller can relist (or use watch()/sync(), which do)."""
        with flight.root("stream_ingest") as sp:
            try:
                n = self.inc.ingest(watch_buffer)
            except WatchExpiredError:
                self.force_restage("watch_expired")
                raise
            if sp:
                sp.set("events", n)
            return n

    def watch(self, client, resource: ResourceType, **kwargs) -> Reflector:
        """Attach a Reflector stream feeding this session; its 410-Gone
        recovery relists force a device restage (the synthetic diff may not
        be O(delta)-expressible)."""
        r = Reflector(client, resource, handler=self.inc.apply,
                      on_relist=lambda _n: self.force_restage("watch_expired"),
                      **kwargs)
        self._reflectors.append(r)
        return r

    def sync(self) -> int:
        """Drain every attached Reflector; returns events applied."""
        return sum(r.sync() for r in self._reflectors)

    def force_restage(self, reason: str) -> None:
        """Invalidate residency before the next cycle (first reason wins)."""
        if self._forced is None:
            self._forced = reason

    # -- persistence (stream.persist) -------------------------------------

    def attach_persistence(self, persistence) -> None:
        """Journal this session's committed deltas, batches, binds, and
        emissions through a StreamPersistence (WAL + checkpoints)."""
        persistence.attach(self)

    def _persist_suppressed(self):
        """Gate the WAL's watch-delta hook around fold-back binds: binds
        are journaled as bind records, not as synthetic MODIFIED events."""
        return (self.persist.suppress_events() if self.persist is not None
                else nullcontext())

    # -- the cycle --------------------------------------------------------

    def schedule(self, pods: List[Pod],
                 _routed=None) -> List[Placement]:
        """One decision cycle: route the batch through the resident fast
        path when residency holds, else a classified restage; fold scheduled
        placements back into the host picture (and, on the fast path, rely
        on the scan having already bound them on device). `_routed`: a
        (reason, cols) pair from a _route call this cycle already made
        (schedule_pipelined's off-stream degrade) — routing is not
        re-entrant across the forced latch and the column journal."""
        if not pods:
            return []
        # one trace context per decision cycle (ISSUE 20): every span the
        # cycle emits — and every WAL frame it ships — carries this id.
        # A context already active (serve front door, pipelined degrade)
        # is the parent; start() is None (and activate a no-op) unless a
        # flight recorder is installed.
        with tracectx.activate(tracectx.start(parent=tracectx.current())), \
                flight.root("stream_cycle") as sp:
            if sp:
                sp.set("pods", len(pods))
            return self._schedule_cycle(pods, _routed)

    def _schedule_cycle(self, pods: List[Pod],
                        _routed=None) -> List[Placement]:
        self.cycles += 1
        inc = self.inc
        t0 = perf_counter()
        cid = (self.persist.begin_cycle(pods)
               if self.persist is not None else None)
        if not inc.nodes:
            # final disposition like any other cycle: one path label plus
            # the latency observations (the accounting-identity contract)
            msg = "no nodes available to schedule pods"
            placements = [Placement(pod=mark_unschedulable(p, msg),
                                    reason="Unschedulable", message=msg)
                          for p in pods]
            self._note_path("no_nodes", len(pods))
            if cid is not None:
                self.persist.log_bind(cid, [])
                self.persist.log_emit(cid, placements)
            self._observe_cycle("no_nodes", t0)
            return placements
        if has_gangs(pods):
            return self._gang_cycle(pods, t0, cid)
        reason, cols = _routed if _routed is not None else self._route(pods)
        if reason is None:
            placements = self._stream_cycle(pods, cols)
        else:
            placements = self._restage_cycle(pods, reason)
        with flight.span("stream_foldback") as fsp:
            bound = [pl for pl in placements if pl.node_name]
            with self._persist_suppressed():
                for pl in bound:
                    inc.apply(MODIFIED, pl.pod)
            if self.device.valid:
                # the scan already applied these binds to the resident
                # carry with identical integer arithmetic — replaying the
                # fold-back journal next cycle would be a byte-for-byte
                # no-op
                inc.drain_journal()
            if fsp:
                fsp.set("pods", len(bound))
        if cid is not None:
            self.persist.log_bind(cid, bound)
            self.persist.log_emit(cid, placements)
        self._observe_cycle(self._last_path, t0)
        return placements

    def _gang_cycle(self, pods: List[Pod], t0: float,
                    cid) -> List[Placement]:
        """A gang decision is a multi-pod cycle solved through the group
        driver (tpusim/gang) against the live host picture: member lanes +
        rank-aware joint packing, committed all-or-nothing. The driver
        applies binds to `inc` directly, so the rows sit in the fold-back
        journal and the NEXT cycle's scatter-commit carries them onto the
        resident twin exactly like external churn — O(delta), residency
        stays valid, nothing restages. The WAL hook is suppressed around
        the driver (binds are journaled as bind records below, not as
        synthetic watch events)."""
        with self._persist_suppressed():
            placements = schedule_with_gangs(
                self._gang_backend(), self.inc, pods, source="stream-gang")
        bound = [pl for pl in placements if pl.node_name]
        self._note_path("gang", len(pods))
        if cid is not None:
            self.persist.log_bind(cid, bound)
            self.persist.log_emit(cid, placements)
        self._observe_cycle("gang", t0)
        return placements

    def _gang_backend(self):
        """Lazy JaxBackend for gang cycles: the group driver's per-pod
        segments and member lanes run through the batch backend, not the
        resident twin (a gang decision re-snapshots by design)."""
        if self._gang_jax is None:
            self._gang_jax = _backend.JaxBackend(
                provider=self.provider,
                hard_pod_affinity_symmetric_weight=self.hard_weight,
                policy=self.policy, compiled_policy=self.cp)
        return self._gang_jax

    def _route(self, pods: List[Pod]):
        """Decide stream-vs-restage for a batch: returns (None, cols) when
        the resident state can serve it, else (reason, cols-or-None).
        Consumes the forced-restage latch and the column journal (a restage
        rebuilds everything, so a lost patch is harmless)."""
        sp = flight.span("stream_route")
        inc = self.inc
        reason = self._forced
        self._forced = None
        if reason is None and self.always_restage:
            reason = "forced_restage"
        if reason is None:
            reason = self.device.residency_miss(inc, self._plan_key)
        cols = None
        if reason is None:
            cols, key_lists = inc._batch_columns(pods)
            if len(inc._scalar_names) != self.device.scalar_width:
                # the batch itself widened the scalar universe
                reason = "scalar_set"
            else:
                reason = self.device.remap_signatures(inc, cols, key_lists)
            if reason is None and not inc.assign_group_ids(cols, pods):
                reason = "group_shape"
            if reason is None and self.device.config.has_interpod \
                    and inc._journal_presence:
                # presence_dom has no scatter path: external presence churn
                # under inter-pod affinity must rebuild it host-side
                reason = "interpod_delta"
            if reason is None and self.cp is not None:
                # per-pod policy signature columns against the RESIDENT
                # interning (image multisets, ServiceAffinity pins)
                reason = remap_policy_columns(self.cp, self.device.pol_res,
                                              pods, cols)
            if reason is None:
                reason = self._prepare_statics_delta()
        if sp:
            sp.set("pods", len(pods))
            sp.set("restage", reason or "")
            sp.end()
        return reason, cols

    # -- paths ------------------------------------------------------------

    def _prepare_statics_delta(self) -> Optional[str]:
        """Turn the column journal (label/taint-only node churn) into a
        pending StaticsDelta scatter: authoritative post-churn columns for
        every churned node, gathered from the host signature-row memo
        (patched in place by _update_node, so it IS current) and recomputed
        against the RESIDENT policy interning. Returns a restage reason when
        the resident tables cannot express the new columns (evicted
        signature row with no representative, label value outside the
        resident domain space), else None with the patch staged for the
        next dispatch."""
        inc = self.inc
        dev = self.device
        touched = inc.drain_column_journal()
        if not touched:
            return None
        n = len(touched)
        idx = _pad_index(np.fromiter(sorted(touched), np.int32, count=n),
                         bucket_size(n))
        u = len(idx)
        cols: Dict[str, np.ndarray] = {}
        for col_kind, _fn, table_kinds in _SIG_KINDS:
            keys_by_row = sorted(dev.sig_rows[col_kind].items(),
                                 key=lambda kv: kv[1])
            for tk in table_kinds:
                if tk == "taint_ok_noexec" \
                        and not dev.compiled.has_noexec_table:
                    # the resident table is the all-pass dummy compile()
                    # stages when no pod tolerates NoExecute predicates
                    cols[tk] = np.ones((max(len(keys_by_row), 1), u),
                                       dtype=bool)
                    continue
                fn, dtype = inc._row_fns[tk]
                out = np.zeros((max(len(keys_by_row), 1), u), dtype=dtype)
                for sig_key, row in keys_by_row:
                    memo = inc._sig_rows.get((tk, sig_key))
                    if memo is not None:
                        out[row] = memo[idx]
                        continue
                    rep = inc._sig_reps.get(sig_key)
                    if rep is None:
                        return "sig_evict"
                    out[row] = np.fromiter((fn(rep, int(i)) for i in idx),
                                           dtype=dtype, count=u)
                cols[tk] = out
        st = dev.statics
        shapes = (st.label_ok.shape[0], st.image_score.shape[0],
                  st.saa_dom.shape[0], st.sa_val.shape[0])
        pol = policy_delta_columns(self.cp, dev.pol_res, dev.ptabs,
                                   inc.nodes, idx, shapes)
        if isinstance(pol, str):
            return pol
        label_ok, label_prio, image_score, saa_dom, sa_val = pol
        self._statics_patch = (idx, StaticsDelta(
            selector_ok=cols["selector_ok"], taint_ok=cols["taint_ok"],
            taint_ok_noexec=cols["taint_ok_noexec"],
            intolerable=cols["intolerable"],
            affinity_count=cols["affinity_count"],
            avoid_score=cols["avoid_score"], host_ok=cols["host_ok"],
            label_ok=label_ok, label_prio=label_prio,
            image_score=image_score, saa_dom=saa_dom, sa_val=sa_val))
        return None

    def _commit_sa_lock(self) -> np.ndarray:
        """The sa_lock re-arm values a restage would stage RIGHT NOW: the
        live first-matching-pod pins for ServiceAffinity policies (snapshot
        pod order — inc._pods preserves insertion order like the reference
        cache), all-unlocked otherwise."""
        dev = self.device
        if self.cp is not None and self.cp.spec.sa_enabled:
            return sa_lock_init_rows(dev.compiled.groups.saa_defs,
                                     list(self.inc._pods.values()),
                                     dev.compiled.node_index)
        return np.full(dev.compiled.groups.saa_rows.shape[0], -1,
                       dtype=np.int32)

    def _apply_statics_patch(self) -> None:
        """Scatter the pending label/taint-churn statics columns into the
        resident tables (donated in-place HBM patch)."""
        if self._statics_patch is None:
            return
        idx, delta = self._statics_patch
        self._statics_patch = None
        dev = self.device
        sp = flight.span("statics_commit", "device")
        dev.statics = apply_statics_delta_donated(dev.statics, idx, delta)
        if sp:
            sp.set("cols", int(len(idx)))
            sp.end()

    def _stream_cycle(self, pods: List[Pod], cols) -> List[Placement]:
        dev = self.device

        def dispatch():
            self._apply_statics_patch()
            dev.commit(self.inc, self._commit_sa_lock())
            carry, placements, intervened = self._dispatch(
                dev.config, dev.carry, dev.statics, self._upload(cols, pods),
                pods, dev.compiled)
            # the donated input buffer is gone either way; the scan's final
            # carry IS the post-bind resident state
            dev.carry = carry
            return placements, intervened

        return self._run_guarded(pods, "stream_scan", dispatch)

    def _restage_cycle(self, pods: List[Pod], reason: str) -> List[Placement]:
        inc = self.inc
        dev = self.device
        cp = self.cp
        dev.invalidate()
        inc.drain_journal()  # structural restage: indices may have shifted
        self._statics_patch = None
        from tpusim.engine.predicates import (
            POD_TOLERATES_NODE_NO_EXECUTE_TAINTS_PRED,
        )

        need_noexec = (cp is not None and cp.spec.pred_keys is not None
                       and POD_TOLERATES_NODE_NO_EXECUTE_TAINTS_PRED
                       in cp.spec.pred_keys)
        need_saa = cp is not None and (bool(cp.spec.saa_weights)
                                       or cp.spec.sa_enabled)
        t0 = perf_counter()
        with flight.span("compile_cluster") as csp:
            compiled, cols = inc.compile(pods, need_noexec=need_noexec,
                                         need_saa=need_saa)
            if csp:
                csp.set("pods", len(pods))
                csp.set("nodes", len(inc.nodes))
        compile_us = since_in_microseconds(t0)
        register().backend_compile_latency.observe(compile_us)
        analytics.note_compile(
            "stream_restage",
            f"plan={self._plan_key}/nodes={len(inc.nodes)}", compile_us)
        unsupported = list(compiled.unsupported)
        if cp is not None:
            unsupported.extend(cp.unsupported)
        if unsupported:
            detail = "; ".join(sorted(set(unsupported))[:5])
            log.warning("stream runtime falling back to reference for: %s",
                        detail)
            return self._host_cycle(pods, "reference_fallback")
        hard_weight = self.hard_weight
        if cp is not None and cp.hard_weight is not None:
            hard_weight = cp.hard_weight
        config = config_for(
            [compiled],
            most_requested=self.provider in _backend._MOST_REQUESTED_PROVIDERS,
            num_reason_bits=NUM_FIXED_BITS + len(compiled.scalar_names),
            hard_weight=hard_weight)
        statics_host = statics_to_host(compiled)
        carry_host = carry_init_host(compiled)
        ptabs = pol_res = None
        if cp is not None:
            # mirror JaxBackend._schedule_on_device's staging recipe: the
            # policy tables overwrite the trivial custom-plugin rows (shapes
            # match exactly, so the replace is byte-identical for policies
            # without the corresponding feature), and the residency capture
            # records the interning those tables were built with
            from dataclasses import replace as _dc_replace

            config = _dc_replace(config, policy=cp.spec)
            snapshot = inc.to_snapshot()
            ptabs = build_policy_tables(cp, snapshot, pods, compiled, cols)
            if cp.saa_entries:
                config = _dc_replace(config, n_saa_doms=ptabs.n_saa_doms)
            pol_res = build_policy_residency(cp, snapshot, pods, compiled,
                                             ptabs)
            statics_host = statics_host._replace(
                label_ok=ptabs.label_ok, label_prio=ptabs.label_prio,
                image_score=ptabs.image_score, saa_dom=ptabs.saa_dom,
                sa_pin=ptabs.sa_pin, sa_val=ptabs.sa_val)
            if cp.spec.sa_enabled:
                carry_host = carry_host._replace(sa_lock=ptabs.sa_lock_init)
        # the residency layout is re-decided here only: stream cycles
        # inherit whatever the twin was staged as
        self._shard_mesh = shard_mesh(config,
                                      "stream residency staying single-device")
        self._shard_layout = None
        statics, carry0 = self._stage_resident(statics_host, carry_host)
        p = len(pods)
        xs_host = pad_infeasible_rows(pod_columns_to_host(cols),
                                      bucket_size(p) - p)
        xs = self._stage_xs(xs_host)
        if self._shard_mesh is not None and not self._shard_verified(
                config, statics, carry0, xs,
                stage_tree(statics_host), stage_tree(carry_host),
                stage_tree(xs_host)):
            # first-use verify disagreed: residency drops back to the
            # single-device layout for the process (trust.SHARD disabled)
            statics, carry0 = self._stage_resident(statics_host, carry_host)
            xs = stage_tree(xs_host)

        def dispatch():
            carry, placements, intervened = self._dispatch(
                config, carry0, statics, xs, pods, compiled)
            if not intervened:
                dev.adopt(inc, compiled, config, statics, carry,
                          plan_key=self._plan_key, ptabs=ptabs,
                          pol_res=pol_res)
            return placements, intervened

        return self._run_guarded(pods, "restage_scan", dispatch, reason)

    def _run_guarded(self, pods: List[Pod], path: str,
                     dispatch: Callable[[], Tuple[List[Placement], bool]],
                     restage_reason: Optional[str] = None) -> List[Placement]:
        """The chaos seam, mirroring JaxBackend.schedule: breaker-denied or
        faulted dispatches route to the host pipeline, probes and
        verify="all" dispatches are host-verified before placements are
        emitted, and ANY intervention invalidates residency (the next cycle
        re-arms from host truth).

        Classification is deferred to here so each off-stream cycle carries
        exactly ONE label — its final disposition: a restage cycle that the
        breaker denies counts as breaker_open, not as its structural reason
        plus breaker_open."""
        breaker = _backend._CHAOS["breaker"]
        if breaker is None:
            placements, intervened = dispatch()
            if restage_reason is not None:
                self._classify(restage_reason)
            if intervened:
                self.device.invalidate()
            self._note_path(path, len(pods))
            return placements
        from tpusim.chaos.engine import DeviceFault

        if not breaker.allow():
            flight.note_route("breaker_fallback", len(pods))
            return self._host_cycle(pods, "breaker_open")
        probing = breaker.probing
        try:
            placements, intervened = dispatch()
        except DeviceFault as exc:
            breaker.record_failure(f"{type(exc).__name__}: {exc}")
            flight.note_route("breaker_fallback", len(pods))
            return self._host_cycle(pods, "device_fault")
        if probing or _backend._CHAOS["verify"] == "all":
            expected = self._reference(pods)
            if placement_hash(placements) != placement_hash(expected):
                breaker.record_failure("device/host placement divergence")
                flight.note_route("breaker_fallback", len(pods))
                self.device.invalidate()
                self._classify("verify_divergence")
                self._note_path("host", len(pods))
                return expected
        breaker.record_success()
        if restage_reason is not None:
            self._classify(restage_reason)
        if intervened:
            self.device.invalidate()
        self._note_path(path, len(pods))
        return placements

    def _stage_resident(self, statics_host, carry_host):
        """Stage the restage's host trees as the resident twin: default
        placement, or shard-even padded + node-sharded over the mesh."""
        mesh = self._shard_mesh
        if mesh is None:
            return stage_tree(statics_host), stage_tree(carry_host)
        statics, carry, self._shard_layout = stage_node_sharded(
            mesh, statics_host, carry_host)
        return statics, carry

    def _stage_xs(self, xs_host):
        """Pod columns are replicated on the sharded residency (every shard
        reduces every pod over its node block)."""
        mesh = self._shard_mesh
        if mesh is None:
            return stage_tree(xs_host)
        from jax.sharding import NamedSharding, PartitionSpec as P

        return stage_tree(xs_host, NamedSharding(mesh, P()))

    def _upload(self, cols, pods: List[Pod]):
        """The batch's pod columns, padded to their bucket, on the device."""
        with flight.span("stream_upload") as sp:
            p = len(pods)
            xs_host = pad_infeasible_rows(pod_columns_to_host(cols),
                                          bucket_size(p) - p)
            xs = self._stage_xs(xs_host)
            if sp:
                sp.set("bytes", sum(int(a.nbytes) for a in xs_host))
        return xs

    def _shard_verified(self, config, statics, carry, xs,
                        statics_1d, carry_1d, xs_1d) -> bool:
        """First-use verify for the sharded residency, the same seam as the
        backend route: run the restage batch through BOTH programs on the
        fresh restage trees and compare choices/counts bit-for-bit. A
        match pins (shards, config) in trust.SHARD (later restages and
        every stream cycle trust); a mismatch disables the sharded route
        process-wide and the caller re-stages single-device."""
        mesh = self._shard_mesh
        n_shards = mesh.shape["node"]
        sig = (n_shards, config)
        if SHARD.trusted(sig):
            return True
        from dataclasses import replace as _dc_replace

        from tpusim.jaxe.kernels import schedule_scan, sharded_scan_fn

        _, sch, scnt, _ = sharded_scan_fn(
            _dc_replace(config, shard_axis="node"), mesh)(carry, statics,
                                                          xs)
        _, ch, cnt, _ = schedule_scan(config, carry_1d, statics_1d, xs_1d)
        same = (np.array_equal(np.asarray(sch), np.asarray(ch))
                and np.array_equal(np.asarray(scnt), np.asarray(cnt)))
        if SHARD.verdict(sig, same, label=str(n_shards)):
            return True
        register().shard_count.set(0)
        self._shard_mesh = None
        self._shard_layout = None
        return False

    def _scan(self, config, carry, statics, xs):
        """The per-cycle donated scan: single-device, or the shard_map
        program when the twin is node-sharded (choices come back as GLOBAL
        node indices either way, bit-identical by the verify seam)."""
        mesh = self._shard_mesh
        if mesh is None:
            return schedule_scan_donated(config, carry, statics, xs)
        from dataclasses import replace as _dc_replace

        from tpusim.jaxe.kernels import sharded_scan_fn

        with flight.span("shard:scan", "device"):
            return sharded_scan_fn(_dc_replace(config, shard_axis="node"),
                                   mesh, donate=True)(carry, statics, xs)

    def _dispatch(self, config, carry, statics, xs, pods: List[Pod],
                  compiled) -> Tuple[object, List[Placement], bool]:
        """Run the donated scan under the chaos injector seam. Returns
        (final_carry, placements, intervened) — `intervened` flags a
        scripted corruption this dispatch (the emitted results may not
        match the device's true decisions, so residency must drop)."""
        metrics = register()
        injector = _backend._CHAOS["injector"]
        corrupt_kind = None
        if injector is not None:
            corrupt_kind = injector.begin_dispatch()  # may raise DeviceFault
        t0 = perf_counter()
        dsp = flight.span("device_dispatch", "device")
        with flight.span("scan_launch", "device"), \
                flight.profiled("tpusim:stream_scan"):
            final_carry, choices, counts, _adv = self._scan(
                config, carry, statics, xs)
        p = len(pods)
        with flight.span("device_wait", "device"):
            choices = np.asarray(choices)[:p]
            counts = np.asarray(counts)[:p]
        if injector is not None:
            if corrupt_kind is not None:
                from tpusim.chaos.engine import DeviceInjector

                choices, counts = DeviceInjector.corrupt(corrupt_kind,
                                                         choices, counts)
            from tpusim.chaos.engine import DeviceOutputError

            n_nodes = len(compiled.statics.names)
            if choices.size and (int(choices.max()) >= n_nodes
                                 or int(choices.min()) < -1):
                raise DeviceOutputError(
                    f"device choice out of range [-1, {n_nodes})")
            if np.isnan(np.asarray(counts, dtype=np.float64)).any():
                raise DeviceOutputError("NaN in device reason counts")
        if dsp:
            dsp.set("pods", p)
            dsp.end()
        metrics.backend_dispatch_latency.observe(since_in_microseconds(t0))
        metrics.scheduling_algorithm_latency.observe(
            since_in_microseconds(t0))
        strings = reason_strings(compiled.scalar_names)
        with flight.span("decode_placements") as dec:
            placements, _ = _backend.decode_placements(
                pods, choices, counts, compiled.statics.names, strings)
            if dec:
                dec.set("shared_binds",
                        _backend.shared_binds(pods, placements))
        # decision provenance (ISSUE 13): capture the decoded batch only —
        # no EngineConfig change, so residency/donation and the restage
        # classification are untouched (failure text is already the
        # byte-identical FitError rendering from decode_placements)
        provenance.capture(placements, "stream", cycle=self.cycles)
        # cluster analytics (ISSUE 14): one extra O(N) reduction dispatch
        # over columns the scan already owns — the scan program itself is
        # untouched, so placement hashes and restage classification are
        # pinned; one None-check when disabled
        analytics.capture(statics, final_carry,
                          len(compiled.statics.names), "stream",
                          cycle=self.cycles, names=compiled.statics.names,
                          mesh=self._shard_mesh)
        return final_carry, placements, corrupt_kind is not None

    def _host_cycle(self, pods: List[Pod], reason: str) -> List[Placement]:
        """Reference-backend cycle (chaos fallback or unsupported features):
        residency drops — the device never saw these binds."""
        self._classify(reason)
        self.device.invalidate()
        placements = self._reference(pods)
        self._note_path("host", len(pods))
        return placements

    def _reference(self, pods: List[Pod]) -> List[Placement]:
        placements = ReferenceBackend(
            provider=self.provider,
            hard_pod_affinity_symmetric_weight=self.hard_weight,
            policy=self.policy,
        ).schedule(pods, self.inc.to_snapshot())
        provenance.capture(placements, "stream_host", cycle=self.cycles)
        return placements

    # -- pipelined execution ----------------------------------------------

    def poll_placed(self) -> List[Placement]:
        """Block on the in-flight pipelined cycle's device choices (if
        any), fold its binds into the host picture, and return the
        placements that BOUND — the note_bound feed for a pipelined
        driver. MUST be called before the driver applies the next cycle's
        watch events, so the host picture evolves in the synchronous
        order. Full decode (fit errors, reason strings) stays deferred to
        the next schedule_pipelined/flush."""
        p = self._pending
        if p is None:
            return []
        if p.placements is not None:
            return [pl for pl in p.placements if pl.node_name]
        self._fold_binds(p)
        return p.bound

    def schedule_pipelined(self, pods: List[Pod]) -> Optional[List[Placement]]:
        """One pipelined decision cycle: dispatch THIS batch's device
        program without blocking on its result and return the PREVIOUS
        cycle's placements (None before any cycle completes). The decode
        of cycle N-1 overlaps cycle N's device execution. Emitted
        placements are byte-identical to schedule(): any off-stream
        condition (chaos seam armed, restage reason, no nodes) runs that
        cycle synchronously, buffered one cycle so emission order is
        preserved. Call flush() for the tail."""
        if not pods:
            return self.flush()
        with flight.root("stream_cycle") as sp:
            if sp:
                sp.set("pods", len(pods))
                sp.set("pipelined", True)
            return self._pipelined_cycle(pods)

    def _pipelined_cycle(self, pods: List[Pod]) -> Optional[List[Placement]]:
        prev_p, self._pending = self._pending, None
        if prev_p is not None and prev_p.placements is None:
            # defensive: a poll_placed-first driver has already folded
            self._fold_binds(prev_p)
        # the verify mode alone is inert (it only gates behavior once a
        # breaker is installed), so only live seams force the sync path
        chaos = (_backend._CHAOS["breaker"] is not None
                 or _backend._CHAOS["injector"] is not None)
        routed = None
        if not chaos and self.inc.nodes and not has_gangs(pods):
            # gang batches run off-stream: schedule() routes them through
            # the group driver's multi-pod cycle
            routed = self._route(pods)
        if routed is not None and routed[0] is None:
            self.cycles += 1
            with tracectx.activate(tracectx.start(
                    parent=tracectx.current())):
                t0 = perf_counter()
                cid = (self.persist.begin_cycle(pods)
                       if self.persist is not None else None)
                self._dispatch_async(pods, routed[1], t0, cid)
            register().stream_pipeline_depth.set(1.0)
            osp = flight.span("stream_overlap")
            prev = self._finalize(prev_p)
            if osp:
                osp.end()
            return prev
        # off-stream: drain the pipeline, then run this cycle through the
        # full synchronous path (chaos seam, restage classification)
        prev = self._finalize(prev_p)
        placements = self.schedule(pods, _routed=routed)
        self._pending = _PendingCycle(pods, placements=placements)
        register().stream_pipeline_depth.set(0.0)
        return prev

    def flush(self) -> List[Placement]:
        """Drain the in-flight (or sync-buffered) pipelined cycle and
        return its placements ([] when none): the tail of a pipelined run
        and the drain point for mid-run mode switches."""
        p, self._pending = self._pending, None
        out = self._finalize(p)
        register().stream_pipeline_depth.set(0.0)
        return out if out is not None else []

    def _fold_binds(self, p: _PendingCycle) -> None:
        """Synchronize on the pending cycle's choices and apply its binds
        to the host IncrementalCluster. The journal entries the fold-back
        creates are rolled back to the pre-fold mark: the scan already
        applied these binds to the resident carry with identical integer
        arithmetic (the same invariant the synchronous path relies on when
        it drains after _stream_cycle), and re-scattering them would both
        waste commit bandwidth and push the journal into bucket sizes the
        warmed jit cache has never traced. Interleaved watch deltas
        journaled BEFORE the fold sit inside the mark and survive."""
        if p.folded:
            return
        # the wait and the fold belong to the dispatching cycle's trace,
        # whichever later call drains them
        with tracectx.activate(p.trace):
            waited0 = perf_counter()
            with flight.span("device_wait", "device"):
                choices = np.asarray(p.choices)[:len(p.pods)]
            waited = perf_counter() - waited0
            elapsed = max(waited0 - p.dispatched_at + waited, 1e-9)
            register().stream_overlap_fraction.set(
                max(0.0, 1.0 - waited / elapsed))
            p.choices = choices
            names = p.compiled.statics.names
            fsp = flight.span("stream_foldback")
            mark = self.inc.journal_mark()
            with self._persist_suppressed():
                for pod, c in zip(p.pods, choices):
                    c = int(c)
                    if c >= 0:
                        bound = bind_pod(pod, names[c])
                        self.inc.apply(MODIFIED, bound)
                        p.bound.append(Placement(pod=bound,
                                                 node_name=names[c]))
            self.inc.journal_rollback(mark)
            if fsp:
                fsp.set("pods", len(p.bound))
                fsp.end()
            p.folded = True
            if self.persist is not None and p.wal_cycle is not None:
                # journaled at fold time: cycle N's binds land BEFORE cycle
                # N+1's watch events, the order the host picture mutates —
                # under cycle N's trace context (_fold_binds) so the shipped
                # frame links back to the dispatching cycle, not the
                # overlapping one
                self.persist.log_bind(p.wal_cycle, p.bound)

    def _finalize(self, p: Optional[_PendingCycle]
                  ) -> Optional[List[Placement]]:
        """Decode a pending cycle into its placement list (None for None):
        the deferred host half of a pipelined cycle, overlapping the next
        cycle's device execution when called from schedule_pipelined."""
        if p is None:
            return None
        if p.placements is not None:
            return p.placements
        with tracectx.activate(p.trace):
            self._fold_binds(p)
            counts = np.asarray(p.counts)[:len(p.pods)]
            strings = reason_strings(p.compiled.scalar_names)
            with flight.span("stream_decode"):
                placements, _ = _backend.decode_placements(
                    p.pods, p.choices, counts, p.compiled.statics.names,
                    strings, prebound=p.bound)
            p.placements = placements
            provenance.capture(placements, "stream",
                               cycle=p.wal_cycle if p.wal_cycle is not None
                               else self.cycles)
            self._note_path("pipelined", len(p.pods))
            if self.persist is not None and p.wal_cycle is not None:
                self.persist.log_emit(p.wal_cycle, placements)
            self._observe_cycle("pipelined", p.t0)
            return placements

    def _dispatch_async(self, pods: List[Pod], cols, t0: float,
                        wal_cycle: Optional[int] = None) -> None:
        """Commit pending deltas and launch the donated scan WITHOUT
        forcing its outputs — JAX's async dispatch returns futures, so the
        host is free to decode the previous cycle while the device runs.
        The scan's final carry is adopted immediately (a device-side
        future too)."""
        dev = self.device
        self._apply_statics_patch()
        dev.commit(self.inc, self._commit_sa_lock())
        xs = self._upload(cols, pods)
        dsp = flight.span("device_dispatch", "device")
        with flight.span("scan_launch", "device"), \
                flight.profiled("tpusim:stream_scan"):
            final_carry, choices, counts, _adv = self._scan(
                dev.config, dev.carry, dev.statics, xs)
        if dsp:
            dsp.set("pods", len(pods))
            dsp.end()
        dev.carry = final_carry
        # analytics rides the un-forced final carry: the reduction is
        # itself an async dispatch, so the pipeline's decode/device overlap
        # is preserved (nothing here blocks)
        analytics.capture(dev.statics, final_carry,
                          len(dev.compiled.statics.names), "stream",
                          cycle=self.cycles,
                          names=dev.compiled.statics.names,
                          mesh=self._shard_mesh)
        self._pending = _PendingCycle(pods, choices, counts, dev.compiled,
                                      t0, perf_counter(),
                                      wal_cycle=wal_cycle)

    # -- overlay what-if queries (ISSUE 19) -------------------------------

    def overlay_query(self, pods: List[Pod],
                      _path: str = "resident") -> Optional[List[Placement]]:
        """Answer a what-if query against the LIVE resident twin in
        O(scenario): fork the donated carry behind a journal mark,
        scatter-commit pending churn exactly like the next real cycle
        would (authoritative and idempotent — the restored journal makes
        that cycle's commit a byte-identical re-scatter), run the fused
        scan over the query batch, decode placements, and roll the carry
        back to host truth (kernels.overlay_restore_donated over the nodes
        the query bound, per-batch lanes restored from pre-mark copies).
        The query batch is never folded back: WAL, persistence, restage
        classification and the cycle chain are untouched, and placements
        are placement-hash-identical to staging inc.to_snapshot() plus the
        query through whatif.run_what_if (the stream-vs-restage parity
        contract applied to a batch that never binds).

        Returns None when the query cannot ride the resident twin — no
        residency, a restage-class change (novel scalar/signature/group/
        policy columns), gang semantics, or a chaos-seam intervention
        mid-query — and the caller (serve.ServeExecutor) falls back to
        the staged path. A restage reason discovered here is latched via
        force_restage so the next real cycle classifies it exactly as
        _route would have."""
        if not pods:
            return []
        with flight.root("overlay_query") as sp:
            if sp:
                sp.set("pods", len(pods))
            return self._overlay_query(pods, _path)

    def _overlay_query(self, pods: List[Pod],
                       _path: str) -> Optional[List[Placement]]:
        t0 = perf_counter()
        with flight.span("overlay_route") as rsp:
            routed = self._overlay_route(pods)
            if rsp:
                rsp.set("pods", len(pods))
                rsp.set("fallback", routed if isinstance(routed, str)
                        else "")
        if isinstance(routed, str):
            register().overlay_fallback.inc(routed)
            flight.note_route("overlay_fallback", len(pods))
            return None
        placements = self._overlay_dispatch(pods, routed)
        if placements is None:
            return None
        m = register()
        m.overlay_queries.inc(_path)
        ctx = tracectx.current()
        m.overlay_latency.observe(
            since_in_microseconds(t0),
            exemplar=ctx.trace_id if ctx is not None else None)
        return placements

    def _overlay_route(self, pods: List[Pod]):
        """The overlay twin of _route: prove the resident arrays can serve
        the query batch WITHOUT perturbing the live session. Returns the
        batch's remapped PodColumns on success, else the fallback reason.
        Stricter than _route — any condition a real cycle would restage
        over is a refusal here (the staged path answers instead), plus the
        configs whose carry fields have no rollback path."""
        inc = self.inc
        dev = self.device
        if self._pending is not None and self._pending.placements is None:
            # pipelined in-flight cycle: fold its binds into the host
            # picture first so the mark below brackets the same logical
            # state the resident carry already holds
            self._fold_binds(self._pending)
        if self._forced is not None or self.always_restage:
            return "forced_restage"
        if not inc.nodes:
            return "no_nodes"
        if has_gangs(pods):
            return "gang_semantics"
        breaker = _backend._CHAOS["breaker"]
        if breaker is not None and (breaker.probing
                                    or _backend._CHAOS["verify"] == "all"
                                    or not breaker.allow()):
            # probe/verify cycles carry a host-parity obligation the
            # overlay cannot discharge; an open breaker denies dispatch
            return "breaker_open"
        reason = dev.residency_miss(inc, self._plan_key)
        if reason is not None:
            return reason
        if dev.config.has_interpod or dev.config.has_maxpd:
            # presence_dom / used_vols have no overlay rollback path
            return "no_rollback_path"
        n_scalars = len(inc._scalar_names)
        cols, key_lists = inc._batch_columns(pods)
        if len(inc._scalar_names) != n_scalars:
            # the QUERY widened the scalar universe: un-note the synthetic
            # names (no live object references them — _note_scalar only
            # appends) so the live session keeps its resident width
            for name in inc._scalar_names[n_scalars:]:
                del inc._scalar_idx[name]
            del inc._scalar_names[n_scalars:]
            if inc._statics is not None:
                inc._statics.alloc_scalar = \
                    inc._statics.alloc_scalar[:, :n_scalars]
            if inc._dyn is not None:
                inc._dyn.used_scalar = inc._dyn.used_scalar[:, :n_scalars]
            return "scalar_set"
        reason = dev.remap_signatures(inc, cols, key_lists)
        if reason is not None:
            return reason
        if not inc.assign_group_ids(cols, pods):
            return "group_shape"
        if self.cp is not None:
            reason = remap_policy_columns(self.cp, dev.pol_res, pods, cols)
            if reason is not None:
                return reason
        reason = self._prepare_statics_delta()
        if reason is not None:
            # the column journal cannot land as a scatter: the next REAL
            # cycle must restage for it, classified exactly as _route
            # would have classified it
            self.force_restage(reason)
            return reason
        return cols

    def _overlay_dispatch(self, pods: List[Pod],
                          cols) -> Optional[List[Placement]]:
        """The mark → commit → scan → decode → rollback bracket. Pending
        churn is early-committed (the next real commit re-scatters the
        same authoritative rows, so the cycle chain is byte-unchanged);
        the per-batch lanes (sa_lock/rr) are saved host-side before the
        donation destroys them and restored verbatim on rollback, so with
        an empty journal the post-rollback carry is byte-identical to
        pre-mark. Chaos interventions (DeviceFault, scripted corruption)
        drop the overlay: journal rolled back, residency invalidated, None
        returned — the next real cycle re-arms from host truth."""
        inc = self.inc
        dev = self.device
        injector = _backend._CHAOS["injector"]
        breaker = _backend._CHAOS["breaker"]
        # reading the per-batch lanes waits for the carry's last writer
        with flight.span("overlay_save", "device"):
            mark = inc.journal_mark()
            rr_save = np.asarray(dev.carry.rr)
            sa_save = np.asarray(dev.carry.sa_lock)
        try:
            self._apply_statics_patch()
            dev.commit(inc, self._commit_sa_lock())
            corrupt_kind = (injector.begin_dispatch()
                            if injector is not None else None)
            p = len(pods)
            with flight.span("stream_upload") as usp:
                xs_host = pad_infeasible_rows(pod_columns_to_host(cols),
                                              bucket_size(p) - p)
                if usp:
                    usp.set("bytes", sum(int(a.nbytes) for a in xs_host))
            with flight.span("overlay_scan", "device"):
                final_carry, choices, counts, _adv = self._scan(
                    dev.config, dev.carry, dev.statics,
                    self._stage_xs(xs_host))
            dev.carry = final_carry
            with flight.span("device_wait", "device"):
                choices = np.asarray(choices)[:p]
                counts = np.asarray(counts)[:p]
        except Exception as exc:
            inc.journal_rollback(mark)
            dev.invalidate()
            from tpusim.chaos.engine import DeviceFault
            if isinstance(exc, DeviceFault):
                if breaker is not None:
                    breaker.record_failure(f"{type(exc).__name__}: {exc}")
                register().overlay_fallback.inc("device_fault")
                flight.note_route("overlay_fallback", len(pods))
                return None
            raise
        if corrupt_kind is not None:
            # the reported choices may not be the device's true decisions,
            # so the row-wise restore below cannot be trusted to cover
            # every bound node — drop residency instead (the next real
            # cycle restages from host truth, placements unchanged by the
            # restage-parity contract)
            inc.journal_rollback(mark)
            dev.invalidate()
            register().overlay_fallback.inc("corruption")
            flight.note_route("overlay_fallback", len(pods))
            return None
        self._overlay_rollback(cols, choices, mark, sa_save, rr_save)
        if breaker is not None:
            breaker.record_success()
        strings = reason_strings(dev.compiled.scalar_names)
        with flight.span("overlay_decode"):
            placements, _ = _backend.decode_placements(
                pods, choices, counts, dev.compiled.statics.names, strings)
        provenance.capture(placements, "overlay", cycle=self.cycles)
        return placements

    def _overlay_rollback(self, cols, choices: np.ndarray, mark,
                          sa_save: np.ndarray, rr_save: np.ndarray) -> None:
        """Scatter the query's bound rows back to host truth — the exact
        gather commit() performs, restricted to the nodes the query bound
        (the query never touched inc, so inc._dyn/_presence still hold the
        pre-query authoritative values) — and restore the journal mark.
        Rides the same pow2 buckets as commit, so warm query shapes reuse
        one compiled restore program."""
        inc = self.inc
        dev = self.device
        gsp = flight.span("rollback_gather")
        bound = sorted({int(c) for c in choices if int(c) >= 0})
        dyn = inc._ensure_dyn()
        idx = np.fromiter(bound, dtype=np.int32, count=len(bound))
        idx = _pad_index(idx, bucket_size(max(len(idx), 1)))
        rows = DeltaRows(
            used_cpu=dyn.used_cpu[idx], used_mem=dyn.used_mem[idx],
            used_gpu=dyn.used_gpu[idx], used_eph=dyn.used_eph[idx],
            used_scalar=dyn.used_scalar[idx],
            nonzero_cpu=dyn.nonzero_cpu[idx],
            nonzero_mem=dyn.nonzero_mem[idx],
            pod_count=dyn.pod_count[idx])
        cell_list = sorted({(int(cols.group_id[j]), int(c))
                            for j, c in enumerate(choices) if int(c) >= 0})
        gid = np.fromiter((g for g, _ in cell_list), dtype=np.int32,
                          count=len(cell_list))
        nid = np.fromiter((n for _, n in cell_list), dtype=np.int32,
                          count=len(cell_list))
        size = bucket_size(max(len(gid), 1))
        gid, nid = _pad_index(gid, size), _pad_index(nid, size)
        if inc._presence is not None:
            val = inc._presence[gid, nid].astype(np.int32)
        else:
            val = np.zeros(size, np.int32)
        if gsp:
            gsp.set("rows", len(bound))
            gsp.set("cells", len(cell_list))
            gsp.end()
        sp = flight.span("overlay_rollback", "device")
        dev.carry = overlay_restore_donated(dev.carry, idx, rows, gid, nid,
                                            val, sa_save, rr_save)
        if sp:
            sp.set("rows", int(len(bound)))
            sp.end()
        inc.journal_rollback(mark)

    # -- accounting -------------------------------------------------------

    def _classify(self, reason: str, detail: Optional[str] = None) -> None:
        self.restage_counts[reason] = self.restage_counts.get(reason, 0) + 1
        flight.note_stream_restage(reason, detail)

    def _note_path(self, path: str, pods: int) -> None:
        self.path_counts[path] = self.path_counts.get(path, 0) + 1
        self._last_path = path
        flight.note_stream_cycle(path, pods)

    def _observe_cycle(self, path: str, t0: float) -> None:
        """Per-cycle latency, twice: the legacy e2e histogram (unchanged
        semantics) and the per-path stream histogram (ISSUE 9). When a
        trace context is active the cycle's trace id rides the histograms
        as an exemplar (ISSUE 20): the slow-cycle spike on a dashboard
        resolves to the exact flight-recorder trace that produced it."""
        us = since_in_microseconds(t0)
        ctx = tracectx.current()
        ex = ctx.trace_id if ctx is not None else None
        m = register()
        m.e2e_scheduling_latency.observe(us, exemplar=ex)
        m.stream_cycle_latency.observe(path, us, exemplar=ex)
        slo.observe_cycle(path, us)

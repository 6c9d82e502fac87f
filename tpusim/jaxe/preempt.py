"""Preemption on the jax backend: a host-device hybrid.

Reference: the Preempt pipeline (core/generic_scheduler.go:205-1000) driven
from scheduleOne's error arm (scheduler.go:449-455). Victim selection is
inherently pod-identity-bound (remove lower-priority pods one by one, reprieve
in priority order, PDB-aware) — state the device deliberately does not carry
(the scan holds per-node aggregates + group presence, not per-pod rows). The
TPU-native split is therefore:

  device — the fused filter→score→select→bind scan schedules every pod that
           fits (tpusim/jaxe/kernels.py); a pod that fails leaves the carry
           untouched and does not advance the round-robin counter, so the
           decisions AFTER a failed pod stay valid.
  host   — only when a pod fails with the PodPriority gate on does the exact
           engine pipeline (GenericScheduler.preempt — the same code the
           reference backend runs) pick a node + victims against a host mirror
           of the cluster.

A successful preemption mutates state (victims deleted), which invalidates
the device's decisions for every later pod — so the scan must restart from
the failed pod. Restarts are made cheap two ways:

  1. **Chunked speculation.** The batch is compiled ONCE; the device scans
     adaptively growing power-of-two chunks (TPUSIM_PREEMPT_CHUNK0, doubling
     to TPUSIM_PREEMPT_CHUNK_MAX) instead of all remaining pods at once.
     Decisions after a preemption point are discarded, so a bounded chunk
     caps the wasted speculation at one chunk per preemption — previously a
     full O(remaining) re-scan (and an O(remaining) host recompile) per
     preemption made config-6-style saturated workloads quadratic. The
     chunk size resets after every preemption (preemptions cluster once the
     cluster saturates) and doubles while the stream stays clean, so
     preemption-free stretches approach single-dispatch throughput.
  2. **Dynamic-only re-arm.** The IncrementalCluster event path
     (tpusim/jaxe/delta.py) keeps columns in sync: binds stream in as ADDED
     events, victims as DELETED events. After a preemption the carry is
     rebuilt from `IncrementalCluster.refresh_dynamic` — a handful of array
     copies — and the compiled statics/tables/pod columns are reused as-is.
     Only structural churn (a victim or bound pod carrying volumes dirties
     the group tables) falls back to a full compile of the remaining feed.

Chunks are padded to power-of-two buckets with provably-infeasible rows
(req_cpu = 2^61 exceeds any allocatable), bounding XLA recompiles to
O(log chunk_max) per run; an infeasible row can never bind or advance the
rr counter, so padding is semantics-free.

A cheap host gate skips the preemption attempt entirely when no placed pod
has lower priority than the failed pod (selectVictimsOnNode can then never
produce a fitting node), so equal-priority workloads pay no host cost beyond
the mirror bookkeeping.
"""

from __future__ import annotations

import logging
import os
from collections import Counter
from typing import List

import numpy as np

from tpusim.api.snapshot import ClusterSnapshot
from tpusim.api.types import Pod, PodCondition, ResourceType
from tpusim.engine.generic_scheduler import (
    ERR_NO_NODES_AVAILABLE,
    FitError,
    SchedulingError,
)
from tpusim.engine.providers import DEFAULT_PROVIDER
from tpusim.engine.resources import get_resource_request, request_memo
from tpusim.engine.util import get_pod_priority
from tpusim.framework.report import Status
from tpusim.framework.store import ADDED
from tpusim.framework.store import DELETED as EV_DELETED
from tpusim.jaxe import ensure_x64
from tpusim.jaxe.backend import (
    _MOST_REQUESTED_PROVIDERS,
    _VICTIM_AUTO,
    format_fit_error,
    victim_kernel_enabled,
)
from tpusim.jaxe.delta import IncrementalCluster
from tpusim.jaxe.kernels import (
    PodX,
    carry_init,
    pad_infeasible_rows,
    config_for,
    pod_columns_to_host,
    preempt_select,
    schedule_scan,
    statics_to_device,
)
from tpusim.jaxe.policyc import classify_preemption_class
from tpusim.jaxe.state import NUM_FIXED_BITS, reason_strings, victim_order_columns
from tpusim.obs import recorder as flight

log = logging.getLogger(__name__)

# per-process counters for how each preemption's victim selection ran:
#   "device"          trusted kernel pick committed directly
#   "device_verified" kernel pick byte-checked against the full host oracle
#                     on its kernel variant's first use (host objects
#                     committed — AUTO mode can never change behavior)
#   "host"            host pipeline (general class, scalar/volume-gated pod,
#                     kernel disabled, or kernel declined the case)
#   "fallback"        kernel disagreed with the oracle: disabled for the
#                     process, host result used
# Read by tests and bench.py (stamped into the config-6 record); reset with
# reset_preempt_class_stats().
PREEMPT_CLASS_STATS: Counter = Counter()


def reset_preempt_class_stats() -> None:
    PREEMPT_CLASS_STATS.clear()


def _note_victim_path(path: str) -> None:
    """One preemption's victim-selection path: bumps the in-module Counter
    (read by tests/bench) and the tpusim_backend_victim_path_total metric
    family + recorder instant in one place."""
    PREEMPT_CLASS_STATS[path] += 1
    flight.note_victim_path(path)

def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


class _PreemptBound:
    """Vectorized necessary-fit bound over [priority, node] request aggregates
    — the device-engine analog of victim selection's masked aggregate search.

    selectVictimsOnNode (core/generic_scheduler.go:583-665) strips every
    lower-priority pod from a candidate node and runs podFitsOnNode;
    PodFitsResources (predicates.go:706-776) is in that predicate set, so a
    node where the pod's request still exceeds allocatable after removing ALL
    lower-priority usage can never yield a fitting victim set. This tracker
    keeps per-priority-band per-node aggregates of the same
    get_resource_request accounting NodeInfo uses, and evaluates that bound
    for every node in one numpy pass, so the host pipeline only clones and
    reprieves on the handful of nodes that can actually fit the pod.

    The bound checks pod count + cpu/mem/gpu/ephemeral exactly as
    pod_fits_resources does (including the all-zero-request early-out) and
    deliberately ignores scalar resources and meta.ignored_extended_resources
    — omitted checks only make the bound more permissive, so a pruned node is
    PROVABLY unfit and the filtered pipeline's outcome is identical."""

    def __init__(self, compiled, placed_pods: List[Pod]):
        st = compiled.statics
        self._node_index = dict(compiled.node_index)
        n = len(st.names)
        self._alloc = (st.alloc_cpu.copy(), st.alloc_mem.copy(),
                       st.alloc_gpu.copy(), st.alloc_eph.copy())
        self._allowed = st.allowed_pods.copy()
        # priority -> [cpu, mem, gpu, eph, count] per-node arrays
        self._bands: dict = {}
        self._n = n
        for pod in placed_pods:
            if pod.spec.node_name:
                self.update(pod, +1)

    def update(self, pod: Pod, sign: int) -> None:
        i = self._node_index.get(pod.spec.node_name)
        if i is None:
            return
        prio = get_pod_priority(pod)
        band = self._bands.get(prio)
        if band is None:
            band = [np.zeros(self._n, np.int64) for _ in range(5)]
            self._bands[prio] = band
        req = get_resource_request(pod)
        band[0][i] += sign * req.milli_cpu
        band[1][i] += sign * req.memory
        band[2][i] += sign * req.nvidia_gpu
        band[3][i] += sign * req.ephemeral_storage
        band[4][i] += sign

    def candidates(self, pod: Pod):
        """Set of node names where the stripped-node resource bound passes,
        or None when every node passes (skip filtering)."""
        pp = get_pod_priority(pod)
        remain = [np.zeros(self._n, np.int64) for _ in range(5)]
        for prio, band in self._bands.items():
            if prio >= pp:   # only lower-priority pods are strippable
                for acc, col in zip(remain, band):
                    acc += col
        req = get_resource_request(pod)
        ok = remain[4] + 1 <= self._allowed
        if (req.milli_cpu or req.memory or req.nvidia_gpu
                or req.ephemeral_storage or req.scalar):
            for k, want in enumerate((req.milli_cpu, req.memory,
                                      req.nvidia_gpu, req.ephemeral_storage)):
                ok &= want + remain[k] <= self._alloc[k]
        if ok.all():
            return None
        names = self._node_index
        mask = ok
        return {name for name, i in names.items() if mask[i]}


class _VictimTable:
    """Columnar mirror of every placed pod, maintained alongside the host
    cache so victim selection can run on device (kernels.preempt_select).

    Row order is the parity anchor: rows are appended in placement-event
    order (snapshot seeds via state.victim_order_columns, then every bind),
    and removals only clear the alive bit — so the per-node subsequence of
    alive rows equals NodeInfo.pods (append on add, order-preserving `del`
    on remove), and a stable sort by descending priority reproduces
    sort_by_priority_desc's victim ordering exactly."""

    def __init__(self, compiled, placed_pods: List[Pod]):
        self._node_index = dict(compiled.node_index)
        n = len(compiled.statics.names)
        node_i, prio, req, objs = victim_order_columns(placed_pods,
                                                       self._node_index)
        self.size = len(objs)
        cap = max(256, _next_pow2(self.size + 1))
        self.node_i = np.zeros(cap, np.int32)
        self.node_i[:self.size] = node_i
        self.prio = np.zeros(cap, np.int64)
        self.prio[:self.size] = prio
        self.req = np.zeros((cap, 4), np.int64)   # cpu/mem/gpu/eph
        self.req[:self.size] = req
        self.alive = np.zeros(cap, bool)
        self.alive[:self.size] = True
        self.objs: List = list(objs) + [None] * (cap - self.size)
        self._row = {p.key(): i for i, p in enumerate(objs)}
        # per-node totals over alive rows — the un-stripped NodeInfo
        # requested/pod-count aggregates
        self.tot = np.zeros((n, 4), np.int64)
        np.add.at(self.tot, node_i, req)
        self.tot_n = np.bincount(node_i, minlength=n).astype(np.int64)

    def _grow(self) -> None:
        cap = len(self.alive) * 2
        for name in ("node_i", "prio", "alive"):
            old = getattr(self, name)
            new = np.zeros(cap, old.dtype)
            new[:self.size] = old[:self.size]
            setattr(self, name, new)
        new_req = np.zeros((cap, 4), np.int64)
        new_req[:self.size] = self.req[:self.size]
        self.req = new_req
        self.objs.extend([None] * (cap - len(self.objs)))

    def add(self, pod: Pod) -> None:
        i = self._node_index.get(pod.spec.node_name)
        if i is None:
            return
        if self.size == len(self.alive):
            self._grow()
        r = self.size
        self.size = r + 1
        pr = get_resource_request(pod)
        self.node_i[r] = i
        self.prio[r] = get_pod_priority(pod)
        self.req[r] = (pr.milli_cpu, pr.memory, pr.nvidia_gpu,
                       pr.ephemeral_storage)
        self.alive[r] = True
        self.objs[r] = pod
        self._row[pod.key()] = r
        self.tot[i] += self.req[r]
        self.tot_n[i] += 1

    def remove(self, pod: Pod) -> None:
        r = self._row.pop(pod.key(), None)
        if r is None:
            return
        self.alive[r] = False
        self.objs[r] = None
        i = self.node_i[r]
        self.tot[i] -= self.req[r]
        self.tot_n[i] -= 1


def _device_select_victims(vtable: _VictimTable, compiled, cols, row,
                           pod: Pod):
    """One failed pod through the device victim-selection pipeline:
    candidate lanes (static-predicate mask + stripped-node resource fit, the
    exact complement of _UNRESOLVABLE given the arithmetic class), victim
    slots (priority-desc lower-priority residents per lane), then the
    preempt_select kernel for the reprieve scan + pickOneNode reductions.

    Returns (winner_node_index, victims_in_reprieve_order, kernel_sig) on a
    device pick, or (None, why, None) when the host arm must run (no
    candidates, or the kernel's result contradicts the scan's infeasibility
    verdict)."""
    st, tb = compiled.statics, compiled.tables
    n_nodes = len(st.names)
    pp = get_pod_priority(pod)
    preq = get_resource_request(pod)
    zero_req = (preq.milli_cpu == 0 and preq.memory == 0
                and preq.nvidia_gpu == 0 and preq.ephemeral_storage == 0
                and not preq.scalar)

    # static-predicate mask == nodes whose only failure can be resources:
    # in the arithmetic class every registered predicate is either node-
    # static (condition/unschedulable bits, hostname pin, selector+required
    # affinity, taints, pressure) or PodFitsResources, and a static failure
    # is _UNRESOLVABLE while a resource failure is not — so this mask IS
    # nodesWherePreemptionMightHelp ∩ {stripped-chain statics pass}
    ok = ((st.cond_fail_bits == 0)
          & tb.host_ok[cols.host_id[row]]
          & tb.selector_ok[cols.sel_id[row]]
          & tb.taint_ok[cols.tol_id[row]]
          & ~st.disk_pressure)
    if cols.best_effort[row]:
        ok = ok & ~st.mem_pressure

    # strip every lower-priority pod, then podFitsOnNode's resource half on
    # the stripped node (the _fits_sans_nominated gate of selectVictims)
    size = vtable.size
    lower = vtable.alive[:size] & (vtable.prio[:size] < pp)
    vrows = np.nonzero(lower)[0]
    node_of = vtable.node_i[:size]
    lower_sum = np.zeros((n_nodes, 4), np.int64)
    np.add.at(lower_sum, node_of[vrows], vtable.req[:size][vrows])
    lower_n = np.bincount(node_of[vrows], minlength=n_nodes)
    n_base = vtable.tot_n - lower_n
    used_base = vtable.tot - lower_sum
    fit = ok & (n_base + 1 <= st.allowed_pods)
    if not zero_req:
        fit = (fit
               & (used_base[:, 0] + preq.milli_cpu <= st.alloc_cpu)
               & (used_base[:, 1] + preq.memory <= st.alloc_mem)
               & (used_base[:, 2] + preq.nvidia_gpu <= st.alloc_gpu)
               & (used_base[:, 3] + preq.ephemeral_storage <= st.alloc_eph))
    cand = np.nonzero(fit)[0]
    c_real = int(cand.size)
    if c_real == 0:
        return None, "no stripped-fit candidate nodes", None

    # victims on candidate lanes, stable-sorted by descending priority
    # (row order within equal priority = NodeInfo.pods order)
    rows = vrows[fit[node_of[vrows]]]
    if rows.size == 0:
        # a candidate with zero strippable pods fits as-is — the scan said
        # it doesn't; surface through the host disagreement arm
        return None, "candidate fits without victims (scan disagreement)", None
    rows = rows[np.argsort(-vtable.prio[:size][rows], kind="stable")]
    lane_of_node = np.full(n_nodes, -1, np.int64)
    lane_of_node[cand] = np.arange(c_real)
    lane = lane_of_node[node_of[rows]]
    g = np.argsort(lane, kind="stable")   # group by lane, keep prio order
    rows_g, lane_g = rows[g], lane[g]
    counts = np.bincount(lane_g, minlength=c_real)
    if int(counts.min()) == 0:
        return None, "candidate fits without victims (scan disagreement)", None
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    pos_in = np.arange(rows_g.size) - starts[lane_g]
    v_real = int(counts.max())

    c_pad = _next_pow2(c_real)
    v_pad = _next_pow2(v_real)
    sig = (c_pad, v_pad, zero_req)

    def lane_arr(vals, base=0):
        out = np.full(c_pad, base, np.int64)
        out[:c_real] = vals
        return out

    lane_valid = np.zeros(c_pad, bool)
    lane_valid[:c_real] = True
    v_prio = np.zeros((c_pad, v_pad), np.int64)
    v_req = np.zeros((c_pad, v_pad, 4), np.int64)
    v_valid = np.zeros((c_pad, v_pad), bool)
    v_row = np.full((c_pad, v_pad), -1, np.int64)
    v_prio[lane_g, pos_in] = vtable.prio[:size][rows_g]
    v_req[lane_g, pos_in] = vtable.req[:size][rows_g]
    v_valid[lane_g, pos_in] = True
    v_row[lane_g, pos_in] = rows_g

    winner, empty_winner, victim_mask, _num = preempt_select(
        bool(zero_req), lane_valid, lane_arr(cand),
        lane_arr(st.alloc_cpu[cand]), lane_arr(st.alloc_mem[cand]),
        lane_arr(st.alloc_gpu[cand]), lane_arr(st.alloc_eph[cand]),
        lane_arr(st.allowed_pods[cand]),
        lane_arr(n_base[cand]),
        lane_arr(used_base[cand, 0] + preq.milli_cpu),
        lane_arr(used_base[cand, 1] + preq.memory),
        lane_arr(used_base[cand, 2] + preq.nvidia_gpu),
        lane_arr(used_base[cand, 3] + preq.ephemeral_storage),
        v_prio, v_req[:, :, 0], v_req[:, :, 1], v_req[:, :, 2],
        v_req[:, :, 3], v_valid)
    big = 1 << 62
    if int(empty_winner) < big:
        return None, "kernel found a no-victim candidate (scan disagreement)", None
    win = int(winner)
    if win >= big:
        # cannot happen for a lane that passed the stripped fit (the scan
        # terminates with a valid, possibly-empty victim set); treat any
        # occurrence as a disagreement and let the host arm decide
        return None, "kernel produced no winner", None
    lane_w = int(lane_of_node[win])
    mask = np.asarray(victim_mask)[lane_w]
    slot_rows = v_row[lane_w][mask & (v_row[lane_w] >= 0)]
    victims = [vtable.objs[int(r)] for r in slot_rows]
    return win, victims, sig


def _device_preempt(cc, vtable: _VictimTable, compiled, cols, row, pod: Pod,
                    bound, by_name, auto_mode: bool):
    """Run one preemption attempt's victim selection on device, with the
    AUTO-mode first-use verification against the full host oracle.

    Returns (status, payload):
      ("skip", why)                   kernel not applicable — run the host arm
      ("committed", (node, victims))  preemption committed through
                                      Simulator.commit_preemption
      ("nopreempt", message)          the verifying host oracle found no
                                      preemption; the None outcome was
                                      committed and `message` is the FitError
                                      text for the pod's condition
    """
    from time import perf_counter

    from tpusim.framework.metrics import since_in_microseconds

    metrics = cc.metrics
    start = perf_counter()
    win, payload, sig = _device_select_victims(vtable, compiled, cols, row,
                                               pod)
    if win is None:
        if "disagreement" in payload:
            log.error("device victim selection for pod %s: %s; deferring to "
                      "the host pipeline", pod.key(), payload)
        return "skip", payload
    name = compiled.statics.names[win]
    if auto_mode and sig not in _VICTIM_AUTO["verified_sigs"]:
        # first preemption on this kernel variant: run the FULL host
        # pipeline alongside and compare (node, ordered victim keys)
        node_infos = cc.refresh_node_info_snapshot()
        try:
            filtered, failed = cc.scheduler.find_nodes_that_fit(
                pod, cc.nodes, node_infos)
        except SchedulingError:
            return "skip", "host oracle errored during verification"
        if filtered:
            # scan-level disagreement — the host arm owns that safety net
            return "skip", "host found feasible nodes"
        fit_err = FitError(pod, len(cc.nodes), failed)
        cand = bound.candidates(pod) if bound is not None else None
        metrics.preemption_attempts.inc()
        host_node, host_victims, host_to_clear = cc.scheduler.preempt(
            pod, cc.nodes, node_infos, fit_err,
            candidate_filter=(cand.__contains__
                              if cand is not None else None))
        metrics.preemption_evaluation.observe(since_in_microseconds(start))
        agree = (host_node is not None and host_node.name == name
                 and [v.key() for v in host_victims]
                 == [v.key() for v in payload])
        if agree:
            _VICTIM_AUTO["verified_sigs"].add(sig)
            _note_victim_path("device_verified")
            log.info("preempt-victim kernel verified against the host "
                     "oracle (variant %s); trusting it for this process",
                     sig)
        else:
            _VICTIM_AUTO["disabled"] = True
            _note_victim_path("fallback")
            log.error(
                "preempt-victim kernel DISAGREES with the host oracle for "
                "pod %s (device: %s + %d victims; host: %s + %d victims); "
                "disabling it for this process and using the host result",
                pod.key(), name, len(payload),
                host_node.name if host_node is not None else None,
                len(host_victims))
        node, victims = cc.commit_preemption(pod, host_node, host_victims,
                                             host_to_clear)
        if node is None:
            return "nopreempt", fit_err.error()
        return "committed", (node, victims)
    # trusted: commit the kernel's pick through the same side-effect path
    # the host pipeline uses (store deletes, nominations, events)
    metrics.preemption_attempts.inc()
    to_clear = cc.scheduler._get_lower_priority_nominated_pods(pod, name)
    metrics.preemption_evaluation.observe(since_in_microseconds(start))
    _note_victim_path("device")
    node, victims = cc.commit_preemption(pod, by_name[name], payload,
                                         to_clear)
    return "committed", (node, victims)


def _mesh_place(mesh, carry, statics=None):
    """Place the hybrid's scan state on a ("snap", "node") mesh: node columns
    sharded over "node", pad rows permanently infeasible (sharding.py sentinel
    bit). statics=None is the post-preemption re-arm — a fresh carry padded to
    match the already-placed statics."""
    from tpusim.jaxe.sharding import (
        node_shardings,
        pad_carry_node_axis,
        pad_node_axis,
        stage_tree,
    )

    st_spec, ca_spec = node_shardings(mesh)
    if statics is None:
        carry = pad_carry_node_axis(carry, mesh.shape["node"])
    else:
        statics, carry, _ = pad_node_axis(statics, carry, mesh.shape["node"])
        statics = stage_tree(statics, st_spec)
    return statics, stage_tree(carry, ca_spec)


def run_with_preemption(pods: List[Pod], snapshot: ClusterSnapshot,
                        provider: str = DEFAULT_PROVIDER,
                        hard_pod_affinity_symmetric_weight: int = 10,
                        incremental: IncrementalCluster = None,
                        mesh=None) -> Status:
    """Run `pods` (podspec order; the LIFO feed reversal happens here, like
    the reference's store.go:223-233 queue) with the PodPriority gate on.
    Returns the final Status with successful/failed/preempted buckets matching
    the reference backend's ClusterCapacity run.

    incremental: an IncrementalCluster already equivalent to `snapshot` (e.g.
    from an event-log replay) — reused instead of compiling a fresh one.

    mesh: an optional ("snap", "node") jax.sharding.Mesh (sharding.make_mesh);
    the speculation chunks dispatch with node columns sharded over the "node"
    axis (pod rows replicated), and the carry re-arm after every preemption
    lands back on the mesh. Placements must stay byte-identical to the
    single-device hybrid — host arms (victim selection, binds, report) never
    see the mesh. Forces the XLA scan (the Pallas plan is single-device)."""
    # deferred import: simulator imports this module's sibling lazily too
    from tpusim.simulator import ClusterCapacity, SchedulerServerConfig

    def host_config():
        return SchedulerServerConfig(
            algorithm_provider=provider,
            hard_pod_affinity_symmetric_weight=hard_pod_affinity_symmetric_weight,
            enable_pod_priority=True)

    # the host mirror: the same orchestrator the reference backend runs, fed
    # manually — binds via the Bind seam, failures via the Update seam, and
    # preemption via the shared attempt_preemption arm
    cc = ClusterCapacity(host_config(), new_pods=[],
                         scheduled_pods=snapshot.pods, nodes=snapshot.nodes,
                         services=snapshot.services, pvs=snapshot.pvs,
                         pvcs=snapshot.pvcs,
                         storage_classes=snapshot.storage_classes)
    feed = list(reversed(pods))
    if not feed:
        cc.status.stop_reason = cc.STOP_REASONS["run"]
        cc.close()
        return cc.status
    if not snapshot.nodes:
        # generic_scheduler raises ERR_NO_NODES_AVAILABLE — the plain
        # SchedulingError arm, which never enters the preemption pipeline
        for pod in feed:
            cc.resource_store.add(ResourceType.PODS, pod)
            cc.update(pod, PodCondition(
                type="PodScheduled", status="False", reason="Unschedulable",
                message=str(ERR_NO_NODES_AVAILABLE)))
        cc.status.stop_reason = cc.STOP_REASONS["failed"]
        cc.close()
        return cc.status

    inc = incremental if incremental is not None else IncrementalCluster(snapshot)
    by_name = {n.name: n for n in cc.nodes}
    vtable = None         # _VictimTable, built at first compile
    run_class = None      # victim-selection class, logged once per run
    # priority histogram of placed pods — the preemption-possible gate
    placed_priorities: Counter = Counter(
        get_pod_priority(p) for p in snapshot.pods if p.spec.node_name)
    attempts: dict = {}   # pod key -> preemption attempts (budget 1, like
    #                       _schedule_one's preempt_budget)
    last_outcome = "run"
    metrics = cc.metrics
    first_compile = True
    rr_start = 0          # lastNodeIndex persists across the whole run
    #                       (generic_scheduler.go:97); restarts resume it
    pos = 0               # next unprocessed pod in `feed`

    chunk0 = max(1, int(os.environ.get("TPUSIM_PREEMPT_CHUNK0", "128")))
    chunk_max = max(chunk0,
                    int(os.environ.get("TPUSIM_PREEMPT_CHUNK_MAX", "8192")))

    # Pallas fast path for the speculation chunks (staged round-5 design):
    # the same kernel the plain batch path runs, driven with explicit
    # carry-in/out over pow2 buckets; after a preemption the carry re-arms
    # from refresh_dynamic's original-unit aggregates divided by the plan's
    # gcds (exact — placed pods' requests joined the gcd fold). Host arms
    # (victim selection, binds, report) are untouched: placements stay
    # byte-identical to the XLA hybrid, pinned by the differential suites.
    from tpusim.jaxe.backend import (
        _FAST_AUTO,
        _auto_verify_and_pin,
        _fast_path_enabled,
        _note_fast_failure,
        plan_signature,
    )
    from tpusim.jaxe.fastscan import fast_scan, init_carry, plan_fast, rearm_carry

    # placed-pod values for the gcd fold: initial snapshot placements plus
    # every pod bound during the run (appended at both bind arms below — a
    # superset is safe: the gcd over a superset still divides every victim
    # adjustment, so victims are never removed from this list either)
    placed_for_gcd = [p for p in snapshot.pods if p.spec.node_name]

    from time import perf_counter

    from tpusim.framework.metrics import since_in_microseconds

    import jax.numpy as jnp

    # pod specs are immutable for the duration of the run (only status and
    # node_name change), so request recomputation — hot in victim selection's
    # clone/strip/reprieve churn — is memoized for the whole hybrid loop
    with request_memo():
        while pos < len(feed):
            # (re)compile feed[pos:] against the current picture; reached once up
            # front and again only after structural churn (volume-carrying binds
            # or victims dirty the group tables — refresh_dynamic covers the rest)
            compile_start = perf_counter()
            with flight.span("compile_cluster") as csp:
                compiled, cols = inc.compile(feed[pos:])
                if csp:
                    csp.set("pods", len(feed) - pos)
            metrics.backend_compile_latency.observe(
                since_in_microseconds(compile_start))
            if compiled.unsupported:
                if not first_compile:
                    raise RuntimeError(
                        "jax preemption: compile fallback after binds were made "
                        f"({sorted(set(compiled.unsupported))[:3]})")
                log.warning("jax backend (preemption) falling back to reference "
                            "for: %s", "; ".join(sorted(set(compiled.unsupported))[:5]))
                ref = ClusterCapacity(host_config(), new_pods=pods,
                                      scheduled_pods=snapshot.pods,
                                      nodes=snapshot.nodes,
                                      services=snapshot.services, pvs=snapshot.pvs,
                                      pvcs=snapshot.pvcs,
                                      storage_classes=snapshot.storage_classes)
                ref.run()
                return ref.status
            if first_compile:
                # the bound only prunes nodes the resource-fit check would
                # reject; shipped providers carry it via GeneralPredicates
                # (which subsumes PodFitsResources, predicates.go:1059-1123),
                # policies may register PodFitsResources directly — a set
                # with neither skips pruning to stay outcome-identical
                preds = cc.scheduler.predicates
                bound = (_PreemptBound(compiled, snapshot.pods)
                         if "GeneralPredicates" in preds
                         or "PodFitsResources" in preds else None)
                vtable = _VictimTable(compiled, snapshot.pods)
            first_compile = False

            num_bits = NUM_FIXED_BITS + len(compiled.scalar_names)
            config = config_for(
                [compiled],
                most_requested=provider in _MOST_REQUESTED_PROVIDERS,
                num_reason_bits=num_bits,
                hard_weight=hard_pod_affinity_symmetric_weight)
            ensure_x64()
            # workload feature hints for the arithmetic reprieve fast path
            # (generic_scheduler._make_arithmetic_reprieve): compiled flags
            # cover new AND placed pods, so an absent feature's reprieve
            # predicate is constant-true for the whole run
            cc.scheduler.reprieve_feature_hints = {
                "has_ports": config.has_ports,
                "has_disk_conflict": config.has_disk_conflict,
                "has_maxpd": config.has_maxpd,
                "has_interpod": config.has_interpod,
            }
            # victim-selection class for this compile: the key/flag
            # classification (shared with policy compilation), cross-checked
            # against the scheduler's own reprieve-chain seam, plus the live
            # PDB gate (criterion 2 is only a no-op with no PDBs registered)
            vclass, vclass_why = classify_preemption_class(
                frozenset(cc.scheduler.predicates),
                cc.scheduler.reprieve_feature_hints,
                has_extenders=bool(cc.scheduler.extenders))
            if vclass == "arithmetic" and cc.scheduler.pdb_lister():
                vclass, vclass_why = ("general",
                                      "pod disruption budgets registered")
            if (vclass == "arithmetic"
                    and cc.scheduler.preemption_reprieve_class()
                    != "arithmetic"):
                vclass, vclass_why = ("general", "reprieve chain kept a "
                                      "pod-set-dependent predicate")
            if run_class != vclass:
                log.info("preemption victim-selection class: %s%s", vclass,
                         f" ({vclass_why})" if vclass_why else "")
                run_class = vclass
            strings = reason_strings(compiled.scalar_names)
            names = compiled.statics.names
            base = pos            # plan/column row i holds feed[base + i]

            # fast-path decision BEFORE the statics upload (same rule as
            # backend.schedule): when the kernel engages, the XLA-scan
            # inputs are never materialized
            fplan = fcarry = fsig = None
            fverify = False
            fast_on, auto_mode = _fast_path_enabled()
            if mesh is not None:
                fast_on = False  # Pallas plan is single-device; mesh -> XLA
            if fast_on:
                fplan, why = plan_fast(config, compiled, cols,
                                       placed_pods=placed_for_gcd)
                if fplan is None:
                    log.info("preemption fast path ineligible (%s); using "
                             "the XLA scan", why)
                else:
                    fsig = plan_signature(fplan)
                    if (auto_mode
                            and fsig not in _FAST_AUTO["verified_sigs"]
                            and not (pos == 0 and rr_start == 0)):
                        # verification replays from carry_init (rr=0): an
                        # unverified variant can only earn trust on the
                        # run's very first chunk — later compiles of an
                        # untrusted variant stay on the XLA scan
                        log.info("preemption fast path deferred: kernel "
                                 "variant unverified and the run is past "
                                 "its first chunk")
                        fplan = fsig = None
                    else:
                        fcarry = init_carry(fplan, rr=rr_start)
                        fverify = (auto_mode and fsig
                                   not in _FAST_AUTO["verified_sigs"])
            statics = xs_all = carry = None
            if fplan is None:
                statics = statics_to_device(compiled)
                xs_all = pod_columns_to_host(cols)
                carry = carry_init(compiled)._replace(rr=np.int64(rr_start))
                if mesh is not None:
                    statics, carry = _mesh_place(mesh, carry, statics)
            flight.note_route("fastscan" if fplan is not None else "xla_scan",
                              len(feed) - pos)
            chunk = chunk0

            while pos < len(feed):
                take = min(chunk, len(feed) - pos)
                off = pos - base
                dispatch_start = perf_counter()
                dsp = flight.span("device_dispatch", "device")
                # pow2 buckets bound recompiles to O(log chunk_max) on both
                # engines: arbitrary tail lengths after a preemption would
                # otherwise each trace a fresh program (infeasible pad rows
                # never bind or advance rr)
                bucket = _next_pow2(take)
                if fplan is not None:
                    try:
                        with flight.profiled("tpusim:fast_scan"):
                            choices, counts, advanced, fc_out = fast_scan(
                                fplan, chunk=bucket, start=off,
                                stop=off + take, carry_in=fcarry,
                                return_carry=True, fixed_chunk=True)
                    except Exception as exc:
                        # a transient runtime error: the outer loop
                        # recompiles feed[pos:] and re-decides the engine;
                        # a lowering or compile error propagates
                        if not _note_fast_failure(exc):
                            raise
                        if dsp:
                            dsp.set("error", type(exc).__name__)
                            dsp.end()
                        break
                    _FAST_AUTO["transient"] = 0
                    if fverify:
                        # ONCE, on the run's first chunk only (the plan
                        # gate guarantees pos==0, rr_start==0 there):
                        # verify_against_xla replays the LEADING pods from
                        # carry_init, which matches no later chunk's
                        # chained-carry state — comparing those would be
                        # pods-vs-different-pods
                        fverify = False
                        if not _auto_verify_and_pin(
                                config, compiled, cols, choices, counts,
                                fsig, limit=take):
                            if dsp:
                                dsp.end()
                            break
                    carry_out = fc_out
                else:
                    sl = PodX(*(a[off:off + take] for a in xs_all))
                    sl = pad_infeasible_rows(sl, bucket - take)
                    xs = PodX(*(jnp.asarray(a) for a in sl))
                    if mesh is not None:
                        import jax
                        from jax.sharding import NamedSharding, PartitionSpec
                        rep = NamedSharding(mesh, PartitionSpec())
                        xs = jax.tree.map(
                            lambda a: jax.device_put(a, rep), xs)
                        with mesh, flight.profiled("tpusim:schedule_scan"):
                            carry_out, choices, counts, advanced = \
                                schedule_scan(config, carry, statics, xs)
                    else:
                        with flight.profiled("tpusim:schedule_scan"):
                            carry_out, choices, counts, advanced = \
                                schedule_scan(config, carry, statics, xs)
                choices = np.asarray(choices)[:take]
                counts = np.asarray(counts)[:take]
                advanced = np.asarray(advanced)[:take]
                if dsp:
                    dsp.set("engine", "fastscan" if fplan is not None
                            else "xla_scan")
                    dsp.set("chunk", bucket)
                    dsp.set("take", take)
                    dsp.end()
                metrics.backend_dispatch_latency.observe(
                    since_in_microseconds(dispatch_start))
                metrics.scheduling_algorithm_latency.observe(
                    since_in_microseconds(dispatch_start))

                mutated = False
                for j in range(take):
                    pod = feed[pos + j]
                    cc.resource_store.add(ResourceType.PODS, pod)  # nextPod's add
                    c = int(choices[j])
                    if c >= 0:
                        cc.bind(pod, names[c])
                        placed, _ = cc.resource_store.get(ResourceType.PODS,
                                                          pod.key())
                        inc.apply(ADDED, placed)
                        placed_for_gcd.append(placed)
                        placed_priorities[get_pod_priority(placed)] += 1
                        if bound is not None:
                            bound.update(placed, +1)
                        vtable.add(placed)
                        last_outcome = "bound"
                        continue

                    # failure: the scan left the carry untouched, so later
                    # decisions stay valid unless a preemption below mutates state
                    pod_priority = get_pod_priority(pod)
                    can_preempt = (
                        cc.config.enable_pod_priority
                        and attempts.get(pod.key(), 0) < 1
                        and any(count > 0 and pri < pod_priority
                                for pri, count in placed_priorities.items()))
                    if not can_preempt:
                        cc.update(pod, PodCondition(
                            type="PodScheduled", status="False",
                            reason="Unschedulable",
                            message=format_fit_error(len(names), counts[j],
                                                     strings)))
                        last_outcome = "failed"
                        continue

                    rr_here = rr_start + int(np.sum(advanced[:j]))
                    # device arm: the arithmetic-reprieve class runs victim
                    # selection on device (kernels.preempt_select) — the pod
                    # is additionally gated on no scalar requests (victim
                    # scalar columns are not carried) and no volumes (keeps
                    # the candidate mask == nodesWherePreemptionMightHelp)
                    dev_status = None
                    dev_payload = None
                    if vclass == "arithmetic":
                        vk_on, vk_auto = victim_kernel_enabled()
                        preq_pod = get_resource_request(pod)
                        if (vk_on and not preq_pod.scalar
                                and not pod.spec.volumes):
                            dev_status, dev_payload = _device_preempt(
                                cc, vtable, compiled, cols, off + j, pod,
                                bound, by_name, vk_auto)
                            if dev_status == "skip":
                                dev_status = None
                    if dev_status == "committed":
                        node, victims = dev_payload
                    elif dev_status == "nopreempt":
                        cc.update(pod, PodCondition(
                            type="PodScheduled", status="False",
                            reason="Unschedulable", message=dev_payload))
                        last_outcome = "failed"
                        continue
                    else:
                        # host arm: per-node failure reasons (the device ships
                        # only the aggregate histogram), then the exact Preempt
                        # pipeline — both against the cache's generation-checked
                        # snapshot, like the host engine's g.cachedNodeInfoMap
                        node_infos = cc.refresh_node_info_snapshot()
                        try:
                            filtered, failed = cc.scheduler.find_nodes_that_fit(
                                pod, cc.nodes, node_infos)
                        except SchedulingError as exc:
                            cc.update(pod, PodCondition(
                                type="PodScheduled", status="False",
                                reason="Unschedulable", message=str(exc)))
                            last_outcome = "failed"
                            continue
                        if filtered:
                            # device said infeasible, host disagrees — a parity
                            # bug; keep the run coherent by trusting the host
                            log.error("device/host disagreement for pod %s: host "
                                      "found %d feasible nodes; using host placement",
                                      pod.key(), len(filtered))
                            cc.scheduler.last_node_index = rr_here
                            host = cc.scheduler.schedule(pod, cc.nodes, node_infos)
                            rr_start = cc.scheduler.last_node_index
                            cc.bind(pod, host)
                            placed, _ = cc.resource_store.get(ResourceType.PODS,
                                                              pod.key())
                            inc.apply(ADDED, placed)
                            placed_for_gcd.append(placed)
                            placed_priorities[get_pod_priority(placed)] += 1
                            if bound is not None:
                                bound.update(placed, +1)
                            vtable.add(placed)
                            last_outcome = "bound"
                            pos += j + 1
                            mutated = True
                            break
                        fit_err = FitError(pod, len(cc.nodes), failed)
                        cand = (bound.candidates(pod)
                                if bound is not None else None)
                        _note_victim_path("host")
                        node, victims = cc.attempt_preemption(
                            pod, fit_err,
                            candidate_filter=(cand.__contains__
                                              if cand is not None else None))
                        if node is None:
                            cc.update(pod, PodCondition(
                                type="PodScheduled", status="False",
                                reason="Unschedulable", message=fit_err.error()))
                            last_outcome = "failed"
                            continue
                    for victim in victims:
                        inc.apply(EV_DELETED, victim)
                        placed_priorities[get_pod_priority(victim)] -= 1
                        if bound is not None:
                            bound.update(victim, -1)
                        vtable.remove(victim)
                    attempts[pod.key()] = attempts.get(pod.key(), 0) + 1
                    # scheduleOne retries the nominated pod immediately
                    # (simulator _schedule_one preempt_budget arm); every later
                    # decision was computed against pre-preemption state
                    pos += j
                    rr_start = rr_here
                    mutated = True
                    break

                if not mutated:
                    pos += take
                    if fplan is not None:
                        fcarry = carry_out
                    else:
                        carry = carry_out
                    rr_start += int(np.sum(advanced))
                    chunk = min(chunk * 2, chunk_max)
                    continue
                if pos >= len(feed):
                    break
                # state changed: re-arm the carry from the incremental picture;
                # statics/tables/pod columns are reused when the group structure
                # is clean, else fall out to a full recompile of feed[pos:]
                refreshed = inc.refresh_dynamic(compiled)
                if refreshed is None:
                    break
                compiled = refreshed
                if fplan is not None:
                    # original-unit aggregates -> plan units via the stored
                    # gcds (exact by the placed-pod fold; verified anyway)
                    fcarry = rearm_carry(fplan, compiled, rr_start)
                    if fcarry is None:
                        log.info("preemption fast path: refreshed state "
                                 "not expressible in plan units; "
                                 "recompiling")
                        break
                else:
                    carry = carry_init(compiled)._replace(
                        rr=np.int64(rr_start))
                    if mesh is not None:
                        _, carry = _mesh_place(mesh, carry)
                chunk = chunk0

    if PREEMPT_CLASS_STATS:
        log.info("preemption victim-selection paths (process cumulative): %s",
                 dict(PREEMPT_CLASS_STATS))
    cc.status.stop_reason = cc.STOP_REASONS[last_outcome]
    cc.close()
    return cc.status

"""ClusterCapacity: the simulation orchestrator.

Reference: pkg/scheduler/simulator.go. The control-flow inversion documented in
SURVEY.md §1 is preserved in-process and synchronously: pods are pushed into
the store, store events drive the scheduler, and the engine calls back up
through the two injected seams — Bind (GetBinder) and Update
(PodConditionUpdater) (simulator.go:247-255) — so placements mutate only the
in-memory store. The LIFO pod feed (store.go:223-233) and stop-reason strings
are reproduced exactly.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from time import perf_counter
from typing import Callable, Dict, List, Optional

from tpusim.api.snapshot import ClusterSnapshot
from tpusim.api.types import Node, Pod, PodCondition, ResourceType
from tpusim.engine.cache import CacheError, SchedulerCache
from tpusim.engine.equivalence import EquivalenceCache
from tpusim.engine.generic_scheduler import FitError, GenericScheduler, SchedulingError
from tpusim.engine.queue import new_scheduling_queue
from tpusim.engine.util import PodBackoff
from tpusim.engine.policy import Policy
from tpusim.engine.providers import (
    DEFAULT_PROVIDER,
    PluginFactoryArgs,
    apply_feature_gates,
    create_from_config,
    create_from_provider,
    default_registry,
)
from tpusim.engine.resources import NodeInfo
from tpusim.framework.events import Recorder
from tpusim.framework.metrics import register as register_metrics, since_in_microseconds
from tpusim.framework.report import GeneralReview, Status, get_report
from tpusim.framework.store import ADDED, DELETED, MODIFIED, PodQueue, ResourceStore
from tpusim.framework.strategy import PredictiveStrategy
from tpusim.obs import recorder as flight
from tpusim.obs import tracectx

DEFAULT_SCHEDULER_NAME = "TD-Scheduler"  # options.go:49


@dataclass
class SchedulerServerConfig:
    """The slice of componentconfig.KubeSchedulerConfiguration the simulator
    reads (options.go:47-61), plus the two feature gates the engine consults:
    PodPriority (preemption; off by default like the reference's 1.10 gates,
    scheduler.go:210-213) and EnableEquivalenceClassCache (simulator.go:369)."""

    scheduler_name: str = DEFAULT_SCHEDULER_NAME
    algorithm_provider: str = DEFAULT_PROVIDER
    # AlgorithmSource.Policy analog (simulator.go:383-424): when set, the
    # scheduler is built from the policy instead of the named provider
    policy: Optional[Policy] = None
    extender_transport: Optional[object] = None  # in-process extender seam
    hard_pod_affinity_symmetric_weight: int = 10
    enable_pod_priority: bool = False
    enable_equivalence_cache: bool = False
    # VolumeScheduling feature gate (scheduler.go:175; off in the reference's
    # 1.10 defaults): enables CheckVolumeBinding + delayed-binding semantics
    enable_volume_scheduling: bool = False
    # registry-surgery gates (ApplyFeatureGates, defaults.go:181-205):
    # TaintNodesByCondition / ResourceLimitsPriorityFunction — both default
    # off in this k8s vintage; applied before provider/policy assembly
    feature_gates: Optional[Dict[str, bool]] = None


class ClusterCapacity:
    """Reference: simulator.go:63-342."""

    def __init__(self, config: SchedulerServerConfig, new_pods: List[Pod],
                 scheduled_pods: List[Pod], nodes: List[Node],
                 services: Optional[list] = None,
                 pvs: Optional[list] = None, pvcs: Optional[list] = None,
                 storage_classes: Optional[list] = None,
                 chaos: Optional[object] = None,
                 backoff_clock: Optional[Callable[[], float]] = None):
        self.config = config
        self.status = Status()
        self.report: Optional[GeneralReview] = None
        self.closed = False
        # chaos engine (tpusim.chaos.ChaosEngine): fires scripted churn at
        # pod-attempt boundaries and audits end-state invariants; its
        # deterministic clock drives the backoff below so retry order is a
        # pure function of the fault plan
        self.chaos = chaos.attach(self) if chaos is not None else None

        # --- store + queue + strategy + recorder (simulator.go:286-342) ---
        self.resource_store = ResourceStore()
        self.strategy = PredictiveStrategy(self.resource_store)
        self.pod_queue = PodQueue(new_pods)
        self.recorder = Recorder(10)

        # --- the scheduler cache, maintained by store event handlers exactly
        # like factory.go's informer handlers (factory.go:139-299); carries
        # the assumed-pod lifecycle + generation-based snapshots
        # (schedulercache/cache.go, engine/cache.py) ---
        self.cache = SchedulerCache()
        self._cached_node_infos: Dict[str, NodeInfo] = {}
        self.resource_store.register_event_handler(ResourceType.PODS, self._on_pod_event)
        self.resource_store.register_event_handler(ResourceType.NODES, self._on_node_event)

        # --- seed cluster state (simulator.go:315-322) ---
        for node in nodes:
            self.resource_store.add(ResourceType.NODES, node)
        for pod in scheduled_pods:
            self.resource_store.add(ResourceType.PODS, pod)
            self.status.scheduled_pods.append(pod)
        for svc in services or []:
            self.resource_store.add(ResourceType.SERVICES, svc)
        for pv in pvs or []:
            self.resource_store.add(ResourceType.PERSISTENT_VOLUMES, pv)
        for pvc in pvcs or []:
            self.resource_store.add(ResourceType.PERSISTENT_VOLUME_CLAIMS, pvc)
        self.nodes = nodes

        # --- volume binder over the seeded PV/PVC/StorageClass state
        # (simulator SchedulerConfigLocal wires PV/PVC informers,
        # simulator.go:355-366; the binder itself is factory.go:252-259) ---
        from tpusim.engine.volume import VolumeBinder

        self.volume_binder = VolumeBinder(
            self.resource_store.list(ResourceType.PERSISTENT_VOLUMES),
            self.resource_store.list(ResourceType.PERSISTENT_VOLUME_CLAIMS),
            storage_classes or [],
            enabled=config.enable_volume_scheduling)

        # --- build the engine with store-backed listers (SchedulerConfigLocal,
        # simulator.go:345-428: fake empty RC/RS/StatefulSet listers, simulated
        # pod/node/service listers) ---
        args = PluginFactoryArgs(
            # the plugin pod lister is the SCHEDULER CACHE, not the store
            # (factory.go:166 podLister: schedulerCache): assigned pods only,
            # in cache insertion order (seed order then bind order) — the
            # deterministic stand-in for Go's random map iteration
            pod_lister=lambda: [state.pod for state
                                in self.cache.pod_states.values()],
            service_lister=lambda: self.resource_store.list(ResourceType.SERVICES),
            node_info_getter=lambda name: self.node_info_map.get(name),
            pvc_getter=self.volume_binder.get_pvc,
            pv_getter=self.volume_binder.get_pv,
            storage_class_getter=self.volume_binder.get_class,
            volume_binder=self.volume_binder,
            volume_scheduling_enabled=config.enable_volume_scheduling,
            hard_pod_affinity_symmetric_weight=config.hard_pod_affinity_symmetric_weight,
        )
        # ServiceAffinity predicates (policy-registered, arbitrary names)
        # judge OTHER nodes by where service pods sit, so any pod add/delete
        # invalidates them on ALL nodes (factory.go's onPodAdd/Delete
        # invalidation set includes CheckServiceAffinity)
        self._service_affinity_pred_names = [
            pp.name for pp in (config.policy.predicates or [])
            if pp.argument is not None
            and pp.argument.service_affinity is not None
        ] if config.policy is not None else []
        self.scheduling_queue = new_scheduling_queue(config.enable_pod_priority)
        # MakeDefaultErrorFunc's backoff state; the clock is injectable
        # (chaos > explicit > wall monotonic) so backoff expiry is testable
        # and chaos replays are byte-stable
        if self.chaos is not None:
            backoff_clock = self.chaos.clock
        self.pod_backoff = (PodBackoff(clock=backoff_clock)
                            if backoff_clock is not None else PodBackoff())
        registry = None
        if config.feature_gates:
            # ApplyFeatureGates runs before provider/policy assembly, like
            # the scheduler app (defaults.go:181-205)
            registry = default_registry()
            apply_feature_gates(registry, config.feature_gates)
        if config.policy is not None:
            # AlgorithmSource.Policy path (simulator.go:383-424 →
            # factory.go CreateFromConfig)
            self.scheduler: GenericScheduler = create_from_config(
                config.policy, args, registry=registry,
                extender_transport=config.extender_transport)
        else:
            self.scheduler = create_from_provider(
                config.algorithm_provider, args, registry=registry)
        self.scheduler.scheduling_queue = self.scheduling_queue
        if config.enable_equivalence_cache:
            self.scheduler.equivalence_cache = EquivalenceCache(
                pvc_getter=self.volume_binder.get_pvc)
        # PDBs come from the fake informer in the reference (empty,
        # simulator.go:352-366) but can be injected for preemption studies
        self.pdbs: list = []
        self.scheduler.pdb_lister = lambda: list(self.pdbs)
        self.metrics = register_metrics()

    # --- cache event handlers ---

    @property
    def node_info_map(self) -> Dict[str, NodeInfo]:
        """The cache's live per-node view (schedulerCache.nodes)."""
        return self.cache.nodes

    def refresh_node_info_snapshot(self) -> Dict[str, NodeInfo]:
        """Expire overdue assumed pods, then refresh the generation-checked
        snapshot the algorithm runs against (generic_scheduler.go:129 →
        cache.go UpdateNodeNameToInfoMap:83-97)."""
        self.cache.cleanup_assumed_pods()
        return self.cache.update_node_name_to_info_map(self._cached_node_infos)

    def _on_pod_event(self, event: str, pod: Pod) -> None:
        if event in (ADDED, MODIFIED) and pod.spec.node_name:
            # a bound pod confirms its assumed entry; re-delivered Modified
            # events for an already-confirmed pod are ignored by the cache
            if self.cache.is_assumed_pod(pod) \
                    or pod.key() not in self.cache.pod_states:
                self.cache.add_pod(pod)
                self._invalidate_ecache_for_node(pod.spec.node_name)
            # factory.go:607-615 wires assigned-pod informer events to the
            # queue's affinity-triggered moves: a bound pod may make parked
            # pods with matching required pod-affinity terms schedulable
            queue = getattr(self, "scheduling_queue", None)
            if queue is not None:
                if event == ADDED:
                    queue.assigned_pod_added(pod)
                else:
                    queue.assigned_pod_updated(pod)
        elif event == DELETED and pod.key() in self.cache.pod_states:
            self.cache.remove_pod(pod)
            self._invalidate_ecache_for_node(pod.spec.node_name)
            # factory.go:624-631: a deleted pod may free anti-affinity or
            # resources anywhere — move everything back to active
            queue = getattr(self, "scheduling_queue", None)
            if queue is not None:
                queue.move_all_to_active_queue()

    def _invalidate_ecache_for_node(self, node_name: str) -> None:
        """The factory event handlers invalidate cached predicate results when
        a node's pod set changes (factory.go:596-631 + ecache hooks); the
        conservative whole-node invalidation keeps the cache correct. A
        ServiceAffinity verdict on EVERY node can change when a service pod
        binds or leaves anywhere, so those predicate keys invalidate
        cluster-wide (factory.go's CheckServiceAffinity invalidation)."""
        # handlers also fire during __init__ seeding, before the engine exists
        scheduler = getattr(self, "scheduler", None)
        if scheduler is not None and scheduler.equivalence_cache is not None:
            scheduler.equivalence_cache.invalidate_all_on_node(node_name)
            if self._service_affinity_pred_names:
                scheduler.equivalence_cache \
                    .invalidate_cached_predicate_item_of_all_nodes(
                        self._service_affinity_pred_names)

    def _on_node_event(self, event: str, node: Node) -> None:
        if event == DELETED:
            self.cache.remove_node(node)
        else:
            self.cache.add_node(node)
        self._invalidate_ecache_for_node(node.name)

    # --- the two seams (simulator.go:108-185) ---

    def bind(self, pod: Pod, node_name: str) -> None:
        """SEAM 1 — Bind intercept (simulator.go:108-145)."""
        stored, exists = self.resource_store.get(ResourceType.PODS, pod.key())
        if not exists:
            raise SchedulingError(f"Unable to bind, pod {pod.key()} not found")
        if self.chaos is not None and node_name in self.chaos.deleted_nodes:
            # churn fires only at attempt boundaries, so the algorithm can
            # never legitimately pick a node deleted before its snapshot:
            # reaching here means stale state leaked through a seam
            self.chaos.record_violation(
                f"bind of {pod.key()} to deleted node {node_name}")
        updated = stored.copy()
        updated.spec.node_name = node_name
        updated.status.phase = "Running"
        if self.chaos is not None:
            # a chaos retry can bind a pod that already parked as
            # unschedulable in an earlier attempt; the terminal buckets
            # must stay disjoint (the reference never retries, so only
            # the chaos arm can hit this)
            self.status.failed_pods = [
                p for p in self.status.failed_pods if p.key() != pod.key()]
        self.strategy.add(updated)  # -> store.update -> Modified -> cache AddPod
        self.scheduling_queue.delete(updated)
        self.pod_backoff.clear_pod_backoff(updated.key())
        self.status.successful_pods.append(updated)
        self.recorder.eventf(updated, "Normal", "Scheduled",
                             "Successfully assigned %s to %s", pod.name, node_name)
        self.recorder.drain_one()  # simulator.go:130-132

    def update(self, pod: Pod, condition: PodCondition) -> None:
        """SEAM 2 — unschedulable intercept (simulator.go:163-185)."""
        stop = (condition.type == "PodScheduled" and condition.status == "False"
                and condition.reason == "Unschedulable")
        if stop:
            pod.status.phase = "Pending"
            pod.status.conditions.append(condition)
            pod.status.reason = condition.reason
            # MakeDefaultErrorFunc (factory.go:1259-1341): record backoff and
            # park the pod in the unschedulable queue — its nominated-node
            # state stays visible to later pods' feasibility double-pass
            self.pod_backoff.get_backoff_time(pod.key())
            self.scheduling_queue.add_unschedulable_if_not_present(pod)
            self.status.failed_pods.append(pod)
            self.recorder.eventf(pod, "Warning", "FailedScheduling", condition.message)
            self.recorder.drain_one()

    # --- the loop (simulator.go:187-223 + scheduler.go:431-497) ---

    def _next_pod(self) -> Optional[Pod]:
        pod = self.pod_queue.pop()
        if pod is None:
            return None
        # scheduling_queue.Pop's receivedMoveRequest reset marks the start of
        # a scheduling cycle (scheduling_queue.go:295-312); the simulator
        # feeds from the LIFO pod queue instead of popping the scheduling
        # queue, so the reset is mirrored here — a move request then flips
        # parking to re-activation only when it arrived while THIS pod was
        # in flight (e.g. a preemption's victim deletions), like upstream
        if hasattr(self.scheduling_queue, "received_move_request"):
            self.scheduling_queue.received_move_request = False
        self.resource_store.add(ResourceType.PODS, pod)
        return pod

    def _schedule_one(self, pod: Pod, preempt_budget: int = 1) -> str:
        """Returns 'bound' or 'failed' — the seam whose deferred nextPod sets
        the stop-reason string when the queue drains (simulator.go:136, :171).

        With the PodPriority gate on, a FitError triggers the preemption
        pipeline (scheduler.go:449-455): victims are deleted from the store
        (mutating the cache through the DELETED event) and the pod retries —
        synchronously here, since the one-pod-in-flight feed would pop it right
        back anyway. Deviation from the reference, documented: the transient
        Unschedulable condition the Go scheduler sets before a successful
        preemption is not recorded in FailedPods."""
        sp = flight.span("pod_attempt")
        if not sp:
            return self._schedule_one_inner(pod, preempt_budget)
        sp.set("pod", pod.key())
        sp.set("preempt_budget", preempt_budget)
        try:
            outcome = self._schedule_one_inner(pod, preempt_budget)
            sp.set("outcome", outcome)
            return outcome
        finally:
            sp.end()

    def _schedule_one_inner(self, pod: Pod, preempt_budget: int) -> str:
        metrics = self.metrics
        e2e_start = algo_start = perf_counter()
        # the algorithm runs against the cache's generation-checked snapshot,
        # not the live view (generic_scheduler.go:129)
        node_infos = self.refresh_node_info_snapshot()
        try:
            with flight.span("schedule"):
                host = self.scheduler.schedule(pod, self.nodes, node_infos)
            metrics.scheduling_algorithm_latency.observe(
                since_in_microseconds(algo_start))
        except FitError as fit_err:
            if self.config.enable_pod_priority and preempt_budget > 0:
                node, _victims = self.attempt_preemption(pod, fit_err)
                if node is not None:
                    return self._schedule_one(pod, preempt_budget - 1)
            # scheduler.go:190-201 error arm -> PodConditionUpdater.Update
            self.update(pod, PodCondition(type="PodScheduled", status="False",
                                          reason="Unschedulable",
                                          message=fit_err.error()))
            return "failed"
        except SchedulingError as sched_err:
            self.update(pod, PodCondition(type="PodScheduled", status="False",
                                          reason="Unschedulable",
                                          message=str(sched_err)))
            return "failed"
        # assumeAndBindVolumes (scheduler.go:367-398): with the gate on, the
        # matched PVs are consumed before the pod binds
        if self.config.enable_volume_scheduling:
            self.volume_binder.assume_pod_volumes(pod, host)
            if self.scheduler.equivalence_cache is not None:
                # PV claimRef changes invalidate volume predicates everywhere,
                # like the factory's PV/PVC event hooks (factory.go
                # invalidatePredicatesForPv/Pvc)
                from tpusim.engine import predicates as preds

                self.scheduler.equivalence_cache \
                    .invalidate_cached_predicate_item_of_all_nodes([
                        preds.MAX_EBS_VOLUME_COUNT_PRED,
                        preds.MAX_GCE_PD_VOLUME_COUNT_PRED,
                        preds.MAX_AZURE_DISK_VOLUME_COUNT_PRED,
                        preds.NO_VOLUME_ZONE_CONFLICT_PRED,
                        preds.CHECK_VOLUME_BINDING_PRED,
                    ])
        # assume (scheduler.go:366-398 → cache.AssumePod): later pods see the
        # placement immediately; the synchronous Bind's store event confirms it
        assumed = pod.copy()
        assumed.spec.node_name = host
        try:
            with flight.span("assume"):
                self.cache.assume_pod(assumed)
        except CacheError as cache_err:
            # assume error arm (scheduler.go:377-380 → config.Error): the pod
            # is reported failed, the run continues — e.g. a fed pod whose
            # namespace/name collides with an already-cached pod
            self.update(pod, PodCondition(type="PodScheduled", status="False",
                                          reason="Unschedulable",
                                          message=str(cache_err)))
            return "failed"
        # binding latency + e2e (scheduler.go:425,492)
        binding_start = perf_counter()
        try:
            with flight.span("bind") as bsp:
                if bsp:
                    bsp.set("host", host)
                self.bind(pod, host)
        except SchedulingError:
            # bind error arm (scheduler.go:484-496): forget the assumed pod
            # so its resources are returned, then surface the error
            self.cache.forget_pod(assumed)
            raise
        self.cache.finish_binding(assumed)  # no-op once confirmed
        metrics.binding_latency.observe(since_in_microseconds(binding_start))
        metrics.e2e_scheduling_latency.observe(since_in_microseconds(e2e_start))
        return "bound"

    # --- gang admission (tpusim/gang): all-or-nothing group scheduling ---

    def _schedule_or_admit(self, pod: Pod) -> str:
        """Per-pod dispatch: a pod carrying a group annotation routes its
        whole gang through all-or-nothing admission; everything else takes
        the unchanged scheduleOne path."""
        from tpusim.gang.group import gang_name

        if gang_name(pod):
            return self._admit_gang(pod)
        return self._schedule_one(pod)

    def _gather_gang(self, pod: Pod):
        """Pull `pod`'s mates forward — from the LIFO feed and, on retries,
        from the scheduling queue — so the group decides as one unit at the
        first member's feed position."""
        from tpusim.gang.group import PodGroup, gang_name

        name = gang_name(pod)
        members = [pod]
        seen = {pod.key()}
        for mate in (self.pod_queue.take_matching(
                lambda p: gang_name(p) == name)
                + self.scheduling_queue.take_matching(
                    lambda p: gang_name(p) == name)):
            if mate.key() not in seen:
                seen.add(mate.key())
                members.append(mate)
        return PodGroup(name=name, pods=members)

    def _trial_member(self, pod: Pod) -> Optional[str]:
        """One member's trial: schedule + assume + bind (so the next member
        sees the placement), WITHOUT the unschedulable intercept — failure
        attribution belongs to the group decision, not the member. Returns
        the host or None."""
        node_infos = self.refresh_node_info_snapshot()
        try:
            host = self.scheduler.schedule(pod, self.nodes, node_infos)
        except SchedulingError:
            return None
        assumed = pod.copy()
        assumed.spec.node_name = host
        try:
            self.cache.assume_pod(assumed)
        except CacheError:
            return None
        try:
            self.bind(pod, host)
        except SchedulingError:
            self.cache.forget_pod(assumed)
            return None
        self.cache.finish_binding(assumed)
        return host

    def _admit_gang(self, pod: Pod) -> str:
        """All-or-nothing admission of `pod`'s group: gather the mates,
        trial-bind members sequentially (intra-gang binds visible), then
        either keep the binds (>= min-available placed) or roll every one
        back through the store — the cache sees the deletes — and park the
        whole gang with ONE shared FitError. Gang admission does not
        attempt preemption (documented in DEVIATIONS.md)."""
        from tpusim.gang.driver import gang_fit_message

        group = self._gather_gang(pod)
        m = self.metrics
        m.gang_size.observe(len(group.pods))
        bound: List[Pod] = []
        overflow: List[Pod] = []
        with flight.span("gang:admit") as sp:
            if sp:
                sp.set("group", group.name)
                sp.set("members", len(group.pods))
            for member in group.pods:
                _stored, exists = self.resource_store.get(
                    ResourceType.PODS, member.key())
                if not exists:
                    self.resource_store.add(ResourceType.PODS, member)
                if self.chaos is not None:
                    # mates pulled forward never went through _next_pod:
                    # they are fed HERE, so the no-pod-lost audit and the
                    # eviction re-feed mechanics cover them too
                    self.chaos.note_fed(member)
                if self._trial_member(member) is not None:
                    bound.append(member)
                else:
                    overflow.append(member)

        if len(bound) >= group.min_available:
            # admitted: the gang stands; overflow members failed
            # individually, not the gang
            keys = {p.key() for p in bound}
            self.status.failed_pods = [
                p for p in self.status.failed_pods if p.key() not in keys]
            for member in overflow:
                msg = (f"pod group \"{group.name}\" admitted at "
                       f"{len(bound)}/{len(group.pods)}; this member did "
                       f"not fit.")
                self.update(member, PodCondition(
                    type="PodScheduled", status="False",
                    reason="Unschedulable", message=msg))
            m.gang_admitted.inc()
            flight.note_gang("admit", {"group": group.name,
                                       "placed": len(bound),
                                       "members": len(group.pods)})
            return "bound"

        # rejected: roll back every trial bind so no partial gang survives
        msg = gang_fit_message(group, len(self.nodes), len(bound))
        for member in bound:
            current, exists = self.resource_store.get(
                ResourceType.PODS, member.key())
            if exists and current.spec.node_name:
                self.resource_store.delete(ResourceType.PODS, current)
            key = member.key()
            self.status.successful_pods = [
                p for p in self.status.successful_pods if p.key() != key]
            # the pristine pending member goes back to the store, exactly
            # like a pod that never trial-bound
            self.resource_store.add(ResourceType.PODS, member)
        if bound:
            m.gang_partial_rollback.inc()
            flight.note_gang("rollback", {"group": group.name,
                                          "unbound": len(bound)})
        m.gang_rejected.inc("min_available" if self.nodes else "no_nodes")
        flight.note_gang("reject", {"group": group.name,
                                    "placed": len(bound)})
        for member in group.pods:
            self.update(member, PodCondition(
                type="PodScheduled", status="False",
                reason="Unschedulable", message=msg))
        return "failed"

    def _release_gangs(self, names, preemptor: Pod, node) -> None:
        """A preempted member releases its whole gang: every still-bound
        mate is deleted from the store (the cache sees the deletes), moved
        to the preempted bucket, and the group's queued nominations are
        cleared so parked members re-attempt as a unit."""
        from tpusim.gang.group import gang_name

        m = self.metrics
        for mate in list(self.resource_store.list(ResourceType.PODS)):
            if gang_name(mate) not in names or not mate.spec.node_name:
                continue
            self.resource_store.delete(ResourceType.PODS, mate)
            key = mate.key()
            self.status.successful_pods = [
                p for p in self.status.successful_pods if p.key() != key]
            self.status.scheduled_pods = [
                p for p in self.status.scheduled_pods if p.key() != key]
            self.status.preempted_pods.append(mate)
            self.recorder.eventf(mate, "Normal", "Preempted",
                                 "gang released by %s on node %s",
                                 preemptor.name, node.name)
            m.gang_partial_rollback.inc()
        cleared = self.scheduling_queue.clear_nominations_for_gangs(names)
        for p in cleared:
            p.status.nominated_node_name = ""
        flight.note_gang("release", {"groups": sorted(names)})

    def attempt_preemption(self, pod: Pod, fit_err: FitError,
                           candidate_filter=None):
        """The preemption arm of scheduleOne (scheduler.go:449-455 → the full
        Preempt pipeline, core/generic_scheduler.go:205-262): pick a node +
        victims, delete the victims from the store (mutating the cache through
        the DELETED events), and nominate the pod. Returns (node, victims) —
        node is None when preemption found nothing. Shared by the host loop
        (_schedule_one retry) and the jax backend's host-device hybrid
        (tpusim/jaxe/preempt.py)."""
        metrics = self.metrics
        preemption_start = perf_counter()
        metrics.preemption_attempts.inc()
        psp = flight.span("preempt")
        try:
            # Preempt runs against the same cached snapshot the failed
            # Schedule used (g.cachedNodeInfoMap, generic_scheduler.go:205)
            node, victims, to_clear = self.scheduler.preempt(
                pod, self.nodes, self._cached_node_infos, fit_err,
                candidate_filter=candidate_filter)
        except SchedulingError:
            # a failed preemption attempt (e.g. extender error) is
            # logged-and-dropped in the reference (scheduler.go:
            # 449-451); the pod still gets its Unschedulable condition
            node, victims, to_clear = None, [], []
        if psp:
            psp.set("pod", pod.key())
            psp.set("node", node.name if node is not None else "")
            psp.set("victims", len(victims))
            psp.end()
        metrics.preemption_evaluation.observe(
            since_in_microseconds(preemption_start))
        return self.commit_preemption(pod, node, victims, to_clear)

    def commit_preemption(self, pod: Pod, node, victims, to_clear):
        """The side-effect half of attempt_preemption (preempt.go:45-75):
        clear losing nominations, nominate the pod, delete victims from the
        store (mutating the cache through the DELETED events), and emit the
        Preempted events. Split out so the jax backend's device-side victim
        selection (tpusim/jaxe/preempt.py) can commit a kernel-picked
        (node, victims) through the exact same store/status/event sequence
        the host pipeline uses."""
        metrics = self.metrics
        metrics.preemption_victims.set(len(victims))
        for p in to_clear:
            p.status.nominated_node_name = ""
        if node is None:
            return None, []
        pod.status.nominated_node_name = node.name
        for victim in victims:
            self.resource_store.delete(ResourceType.PODS, victim)
            self.status.preempted_pods.append(victim)
            # an evicted pod is no longer placed: drop it from the
            # success/pre-scheduled buckets so the report balances
            key = victim.key()
            self.status.successful_pods = [
                p for p in self.status.successful_pods if p.key() != key]
            self.status.scheduled_pods = [
                p for p in self.status.scheduled_pods if p.key() != key]
            self.recorder.eventf(victim, "Normal", "Preempted",
                                 "by %s on node %s", pod.name, node.name)
        from tpusim.gang.group import gang_name

        gang_names = {gang_name(v) for v in victims if gang_name(v)}
        if gang_names:
            # preempting one member releases the whole gang — an
            # all-or-nothing admission cannot survive partially
            self._release_gangs(gang_names, pod, node)
        return node, victims

    STOP_REASONS = {
        # Bind's deferred nextPod uses lowercase "fail", Update's uses "Fail"
        "run": "fail to get next pod: No pods left\n",      # simulator.go:204
        "bound": "fail to get next pod: No pods left\n",    # simulator.go:136
        "failed": "Fail to get next pod: No pods left\n",   # simulator.go:171
    }

    def run(self) -> None:
        """Reference: simulator.go:187-213 — feed one pod at a time until the
        queue drains; the stop-reason strings match the Go format verbatim."""
        if self.chaos is not None:
            return self._run_chaos()
        rec = flight.get_recorder()
        idle_since = rec.clock() if rec is not None else 0.0
        pod = self._next_pod()
        if pod is None:
            self.status.stop_reason = self.STOP_REASONS["run"]
            self.close()
            return
        while pod is not None:
            if rec is not None:
                # time the pod sat in the LIFO feed since the scheduler
                # last went idle (the reference's scheduling-queue wait)
                rec.add_span("queue_wait", "host", idle_since, rec.clock(),
                             {"pod": pod.key()})
            outcome = self._schedule_or_admit(pod)
            if rec is not None:
                idle_since = rec.clock()
            next_pod = self._next_pod()
            if next_pod is None:
                self.status.stop_reason = self.STOP_REASONS[outcome]
                self.close()
                return
            pod = next_pod

    def _run_chaos(self) -> None:
        """The chaos arm of run(): identical seams and scheduling path, but
        every attempt boundary fires due churn first, and after the LIFO
        feed drains, churn-reactivated pods (evicted-and-requeued, or
        parked pods a returning node released) get bounded re-attempts out
        of the scheduling queue — gated per pod by the plan's max_retries
        and by PodBackoff under the chaos clock. A global attempt budget
        guarantees termination for any plan."""
        chaos = self.chaos
        outcome = "run"
        max_at = max([ev.at for ev in chaos.plan.churn] +
                     [ev.at + ev.restore_after for ev in chaos.plan.churn],
                     default=0)
        budget = ((len(self.pod_queue) + len(self.status.scheduled_pods)
                   + len(chaos.plan.churn) + 8)
                  * (chaos.plan.max_retries + 2) + max_at + 64)
        spent = 0
        while spent < budget:
            spent += 1
            chaos.fire_boundary()
            pod = self._next_pod()
            if pod is not None:
                chaos.note_fed(pod)
                outcome = self._schedule_or_admit(pod)
                continue
            if chaos.has_pending_churn():
                # churn scheduled past the attempt horizon may still evict
                # and requeue; keep ticking boundaries until it lands
                continue
            retry = self.scheduling_queue.pop()
            if retry is None:
                break
            if chaos.allow_retry(retry):
                outcome = self._schedule_or_admit(retry)
        if spent >= budget:
            chaos.record_violation(
                f"attempt budget exhausted ({budget}): the run did not "
                "quiesce")
        chaos.flush()
        self.status.stop_reason = self.STOP_REASONS.get(
            outcome, self.STOP_REASONS["run"])
        self.close()

    def close(self) -> None:
        self.closed = True

    def get_report(self) -> GeneralReview:
        if self.report is None:
            self.report = get_report(self.status)
        return self.report


def new_cluster_capacity(config: SchedulerServerConfig, new_pods: List[Pod],
                         scheduled_pods: List[Pod], nodes: List[Node],
                         services: Optional[list] = None) -> ClusterCapacity:
    """Reference: scheduler.New (simulator.go:286-342)."""
    return ClusterCapacity(config, new_pods, scheduled_pods, nodes, services)


def auto_routes_to_host(num_pods: int, num_nodes: int,
                        enable_volume_scheduling: bool = False) -> bool:
    """The --backend auto routing rule (shared with the CLI's --v 5 note;
    callers size num_nodes AFTER any event-log fold, since node-adding
    logs count toward the threshold).

    Tiny workloads lose to device-dispatch latency, so batches below
    TPUSIM_AUTO_THRESHOLD pods x nodes stay on the host engine. The rule
    reads only the sizes and never initializes jax. Volume scheduling is
    host-bound and wins over everything."""
    if enable_volume_scheduling:
        return True
    threshold = int(os.environ.get("TPUSIM_AUTO_THRESHOLD", 100_000))
    return num_pods * max(num_nodes, 1) < threshold


def run_simulation(pods: List[Pod], snapshot: ClusterSnapshot,
                   provider: str = DEFAULT_PROVIDER, backend: str = "reference",
                   scheduler_name: str = DEFAULT_SCHEDULER_NAME,
                   enable_pod_priority: bool = False,
                   enable_volume_scheduling: bool = False,
                   policy: Optional[Policy] = None,
                   events: Optional[list] = None,
                   feature_gates: Optional[Dict[str, bool]] = None,
                   chaos_plan: Optional[object] = None) -> Status:
    """High-level entry: run `pods` (in podspec order; the LIFO feed reversal
    happens inside, matching the reference) against `snapshot` and return the
    final Status. backend='jax' routes the batch through the TPU engine and
    reconstructs the same Status/report shape.

    events: an optional [(ADDED|MODIFIED|DELETED, Pod|Node|Service), ...]
    watch-event log (framework.events.load_event_log) replayed on top of
    `snapshot` before scheduling — the reference's watch fabric
    (restclient.go:218-236 → informer cache mutations) as data. On the jax
    backend the replay drives the IncrementalCluster column caches
    (jaxe/delta.py), so compiled state is patched, not rebuilt.

    chaos_plan: an optional tpusim.chaos.FaultPlan. Churn and fabric
    sections drive the reference orchestrator (a jax-backend request with
    those sections reroutes host-side with a warning, like the other
    host-bound features); the device section arms the dispatch circuit
    breaker + fault injector around the jax backend. The returned Status
    gains `chaos_summary` (fired faults, retries) and `chaos_violations`
    (end-state invariant audit; empty = degraded gracefully)."""
    incremental = None
    if events:
        from tpusim.jaxe.delta import IncrementalCluster

        incremental = IncrementalCluster(snapshot)
        incremental.apply_events(events)
        folded = incremental.to_snapshot()
        # folded PV/PVC state includes applied PersistentVolume(Claim) events
        # (jaxe/delta.py); StorageClass objects are not watch-fabric events
        # and pass through from the seed snapshot
        snapshot = ClusterSnapshot(
            nodes=folded.nodes, pods=folded.pods, services=folded.services,
            pvs=folded.pvs, pvcs=folded.pvcs,
            storage_classes=snapshot.storage_classes)
    if backend == "auto":
        # sized AFTER the event-log fold above, so node-adding logs count
        backend = ("reference"
                   if auto_routes_to_host(len(pods), len(snapshot.nodes),
                                          enable_volume_scheduling)
                   else "jax")
    if feature_gates:
        # PodPriority / VolumeScheduling gate the same behavior as the
        # dedicated parameters (scheduler.go:175,210-213) for library
        # callers; the registry-surgery gates pass through to
        # apply_feature_gates
        feature_gates = dict(feature_gates)
        if feature_gates.pop("PodPriority", False):
            enable_pod_priority = True
        if feature_gates.pop("VolumeScheduling", False):
            enable_volume_scheduling = True
    if chaos_plan is not None:
        chaos_plan.validate()
        if backend == "jax" and not chaos_plan.host_sections_empty():
            # churn fires at per-pod attempt boundaries and fabric faults
            # hit watch streams — both exist only in the host orchestrator
            # (the jax batch path has neither); device faults alone stay
            # on the device path, absorbed by the circuit breaker
            import logging

            logging.getLogger(__name__).warning(
                "chaos churn/fabric sections are host-bound: running the "
                "reference orchestrator instead of the jax backend")
            backend = "reference"
    if feature_gates and any(feature_gates.get(g) for g in
                             ("TaintNodesByCondition",
                              "ResourceLimitsPriorityFunction")) \
            and backend == "jax":
        # registry surgery is host-registry-bound; the gated predicate/
        # priority sets have no compiled device shape (both gates default
        # off upstream, so the ungated device engine matches executed
        # reference behavior)
        import logging

        logging.getLogger(__name__).warning(
            "feature gates %s are host-bound: running the reference "
            "orchestrator instead of the jax backend",
            sorted(k for k, v in feature_gates.items() if v))
        backend = "reference"
    compiled_policy = None
    if policy is not None and backend == "jax":
        # compile (and validate) the policy for the device engine; the one
        # host-bound feature (extenders) routes to the reference
        # orchestrator, which has the full plugin registry and the
        # in-process extender seam
        import logging

        from tpusim.jaxe.policyc import compile_policy

        compiled_policy = compile_policy(policy)
        if compiled_policy.unsupported or enable_pod_priority:
            reason = ("preemption with a policy scheduler"
                      if not compiled_policy.unsupported else
                      "; ".join(sorted(set(compiled_policy.unsupported))[:5]))
            logging.getLogger(__name__).warning(
                "policy is host-bound (%s): running the reference "
                "orchestrator instead of the jax backend", reason)
            backend = "reference"
    if backend == "reference":
        chaos_engine = None
        if chaos_plan is not None:
            from tpusim.chaos import ChaosEngine

            chaos_engine = ChaosEngine(chaos_plan)
        cc = ClusterCapacity(
            SchedulerServerConfig(scheduler_name=scheduler_name,
                                  algorithm_provider=provider,
                                  policy=policy,
                                  enable_pod_priority=enable_pod_priority,
                                  enable_volume_scheduling=enable_volume_scheduling,
                                  feature_gates=feature_gates),
            new_pods=pods, scheduled_pods=snapshot.pods, nodes=snapshot.nodes,
            services=snapshot.services, pvs=snapshot.pvs, pvcs=snapshot.pvcs,
            storage_classes=snapshot.storage_classes, chaos=chaos_engine)
        cc.run()
        if chaos_engine is not None:
            from tpusim.chaos import check_invariants

            cc.status.chaos_violations = check_invariants(cc, chaos_engine)
            cc.status.chaos_summary = chaos_engine.summary()
        return cc.status
    if backend == "jax":
        # the root span of one query (a no-op without a recorder): its
        # trace id stamps every span below, and its args say how much of
        # it was the process on the CPU and how much the collector
        with flight.root("simulate") as ssp:
            if ssp:
                ssp.set("pods", len(pods))
                ssp.set("nodes", len(snapshot.nodes))
            from tpusim.backends import get_backend

            if enable_volume_scheduling:
                raise ValueError("--enable-volume-scheduling requires "
                                 "--backend reference (delayed PV binding "
                                 "is stateful host-side matching)")
            from tpusim.gang.group import has_gangs

            if enable_pod_priority:
                if has_gangs(pods):
                    # preemption interplay (gang release, nomination
                    # cleanup) lives in the host orchestrator's queue/store
                    # machinery; the device hybrid has no group-aware retry
                    # loop
                    import logging

                    logging.getLogger(__name__).warning(
                        "pod groups with PodPriority are host-bound: running "
                        "the reference orchestrator instead of the jax "
                        "backend")
                    return run_simulation(
                        pods, snapshot, provider=provider,
                        backend="reference", scheduler_name=scheduler_name,
                        enable_pod_priority=True,
                        policy=policy, events=events,
                        feature_gates=feature_gates, chaos_plan=chaos_plan)
                # host-device hybrid: device scan schedules, the exact host
                # Preempt pipeline fires on failures (jaxe/preempt.py)
                from tpusim.jaxe.preempt import run_with_preemption

                return run_with_preemption(pods, snapshot, provider=provider,
                                           incremental=incremental)
            with flight.span("feed") as fsp:
                jax_backend = get_backend("jax", provider=provider,
                                          policy=policy,
                                          compiled_policy=compiled_policy)
                # the LIFO queue pops the last element first
                feed = list(reversed(pods))
                gangs = has_gangs(feed)
                if fsp:
                    fsp.set("pods", len(feed))
            precompiled = (incremental.compile(feed)
                           if incremental is not None and feed
                           and snapshot.nodes and not gangs else None)
            breaker = None
            if chaos_plan is not None and not chaos_plan.device.empty():
                from tpusim.jaxe.backend import install_chaos

                breaker = install_chaos(chaos_plan.device)
            try:
                with flight.span("backend_schedule") as bsp:
                    if bsp:
                        bsp.set("backend", "jax")
                        bsp.set("pods", len(feed))
                    if gangs:
                        # gang feeds route through schedule_with_gangs:
                        # ungrouped runs use the unchanged per-pod path
                        # against the live incremental cluster, gangs are
                        # admitted all-or-nothing
                        from tpusim.gang.driver import schedule_with_gangs
                        from tpusim.jaxe.delta import IncrementalCluster

                        inc = incremental or IncrementalCluster(snapshot)
                        placements = schedule_with_gangs(jax_backend, inc,
                                                         feed)
                    else:
                        placements = jax_backend.schedule(
                            feed, snapshot, precompiled=precompiled)
            finally:
                if breaker is not None:
                    from tpusim.jaxe.backend import uninstall_chaos

                    uninstall_chaos()
            with flight.span("status") as stsp:
                status = Status(scheduled_pods=list(snapshot.pods))
                for placement in placements:
                    if placement.scheduled:
                        status.successful_pods.append(placement.pod)
                    else:
                        status.failed_pods.append(placement.pod)
                last_failed = placements and not placements[-1].scheduled
                status.stop_reason = (
                    "Fail to get next pod: No pods left\n" if last_failed
                    else "fail to get next pod: No pods left\n")
                if stsp:
                    stsp.set("pods", len(placements))
            if breaker is not None:
                status.chaos_summary = {
                    "breaker_transitions": list(breaker.transitions)}
                status.chaos_violations = []
            return status
    raise ValueError(f"unknown backend {backend!r}")


def run_stream_simulation(snapshot: Optional[ClusterSnapshot] = None, *,
                          num_nodes: int = 64, cycles: int = 50,
                          arrivals: int = 32, evict_fraction: float = 0.25,
                          node_flap_every: int = 0,
                          label_churn: int = 0, taint_churn: int = 0,
                          gang_size: int = 0, gang_count: int = 0,
                          seed: int = 0,
                          provider: str = DEFAULT_PROVIDER,
                          policy=None, pipeline: bool = False,
                          always_restage: bool = False, verify: bool = False,
                          chaos_plan: Optional[object] = None,
                          checkpoint_dir: Optional[str] = None,
                          checkpoint_every: int = 0,
                          fsync_every: int = 0,
                          replicate_to: Optional[tuple] = None,
                          recover: bool = False,
                          whatif_every: int = 0,
                          whatif_pods: int = 4) -> dict:
    """Drive a StreamSession through seeded churn (tpusim.stream.ChurnLoadGen)
    and return a summary dict — the `tpusim stream` CLI, the bench's configs
    9/10, and the smoke variants all sit on this loop.

    Unlike run_simulation (one batch, one decision), this is the steady-state
    shape the streaming runtime exists for: per cycle, watch events fold into
    the host picture, the delta scatter-commits onto the device-resident
    carry, and a fresh arrival batch schedules against it — O(delta) per warm
    cycle instead of O(cluster).

    always_restage: disable the fast path (the restage-comparison arm).
    policy: an engine.policy.Policy compiled for device residency (ISSUE 9);
        synthetic clusters get their node labels seeded from the churn
        universe so every label value interns at cold start — pure
        label/taint churn then rides the statics scatter with zero restages.
    pipeline: overlap host decode of cycle N-1 with cycle N's device
        execution (StreamSession.schedule_pipelined); placements and the
        placement chain are byte-identical to the synchronous path.
    label_churn / taint_churn: per-cycle label rewrites / taint toggles fed
        through the load generator (the scatter-absorbable churn class).
    gang_size / gang_count: per-cycle pod-group arrivals (tpusim/gang):
        each cycle carrying gangs runs as a multi-pod gang cycle —
        all-or-nothing admission with rank-aware packing; fold-back stays
        O(delta) through the journal's next-cycle scatter-commit.
    verify: additionally run every cycle through a fresh-compile
        JaxBackend.schedule and assert byte-identical placement hashes
        (pipelined cycles compare when their placements emerge, one cycle
        later).
    chaos_plan: device-fault and process_crash sections only — churn/fabric
        faults are what the load generator already produces, event-shaped.
        A process_crash event arms the WAL writer (requires
        checkpoint_dir) and the run dies with chaos.engine.ProcessCrash at
        the targeted record; a follow-up call with recover=True and the
        SAME workload arguments resumes it.
    checkpoint_dir / checkpoint_every: journal every cycle to a WAL and
        checkpoint the host+device picture every that-many emitted cycles
        (stream.persist); 0 = genesis checkpoint only.
    recover: load checkpoint_dir, replay the WAL tail, fast-forward the
        load generator over the committed prefix, and run the REMAINING
        cycles. The summary's fold_chain is then byte-identical to an
        uninterrupted run's.
    fsync_every: fsync the WAL file every N appends (stream.persist's
        durability dial; the mode is stamped into checkpoint manifests).
    replicate_to: (host, port) of a listening FollowerTwin — attach a
        WalShipper to the journal (requires checkpoint_dir) and drain it
        before returning; the summary grows replication_{drained,
        acked_seq, lag_at_close} (ISSUE 18).
    whatif_every: every N cycles, answer a live what-if query against the
        device-resident twin via StreamSession.overlay_query — a
        copy-on-write overlay (mark -> scatter scenario pods -> scan ->
        roll back) that leaves the carry byte-identical, so the run's
        fold_chain is unchanged by the queries (ISSUE 19). The summary
        grows an ``overlay`` block: queries/answered/fallbacks and query
        latency percentiles. 0 disables.
    whatif_pods: scenario pods per live query (drawn from a dedicated
        rng stream, deterministic per seed, never entering the churn
        picture).
    """
    from tpusim.api.snapshot import synthetic_cluster
    from tpusim.backends import Placement, bind_pod, get_backend, \
        placement_hash
    from tpusim.jaxe.delta import IncrementalCluster
    from tpusim.stream import ChurnLoadGen, StreamSession
    from tpusim.stream.loadgen import DEFAULT_LABEL_UNIVERSE
    from tpusim.stream.persist import (
        StreamPersistence,
        chain_fold,
        recover_stream_session,
    )

    if snapshot is None:
        snapshot = synthetic_cluster(num_nodes)
        if policy is not None or label_churn or taint_churn:
            # seed every churn-universe value across the synthetic nodes so
            # the cold-start compile interns the full label domain — churn
            # then never needs a new domain id (a staged-shape property)
            for i, node in enumerate(snapshot.nodes):
                node.metadata.labels.update(
                    {k: vals[i % len(vals)]
                     for k, vals in DEFAULT_LABEL_UNIVERSE.items()})
    breaker = None
    crash_events = []
    if chaos_plan is not None:
        chaos_plan.validate()
        if not chaos_plan.host_sections_empty():
            raise ValueError(
                "run_stream_simulation takes device fault and process_crash "
                "sections only: churn/fabric faults arrive through the load "
                "generator as watch events")
        crash_events = chaos_plan.crash_events()
        if crash_events and checkpoint_dir is None:
            raise ValueError(
                "process_crash faults fire from the WAL writer: pass "
                "checkpoint_dir (--checkpoint-dir)")
        if not chaos_plan.device.empty():
            from tpusim.jaxe.backend import install_chaos

            breaker = install_chaos(chaos_plan.device)
    if recover and checkpoint_dir is None:
        raise ValueError("recover=True needs checkpoint_dir")
    if replicate_to is not None and checkpoint_dir is None:
        raise ValueError("replicate_to ships the WAL: pass checkpoint_dir "
                         "(--checkpoint-dir)")
    if replicate_to is not None and recover:
        raise ValueError("replicate_to cannot resume a recovery replay; "
                         "recover first, then re-attach the shipper")
    if recover and verify:
        raise ValueError(
            "verify and recover are mutually exclusive: the verify arm "
            "replays the reference picture from cycle 0")
    persist = report = shipper = None
    start_cycle = 0
    if recover:
        session, report, persist = recover_stream_session(
            checkpoint_dir, provider=provider, policy=policy,
            always_restage=always_restage,
            checkpoint_every=checkpoint_every)
        start_cycle = report.resume_cycle
    else:
        session = StreamSession(snapshot, provider=provider, policy=policy,
                                always_restage=always_restage)
        if checkpoint_dir is not None:
            persist = StreamPersistence(checkpoint_dir,
                                        checkpoint_every=checkpoint_every,
                                        fsync_every=fsync_every)
            if replicate_to is not None:
                # hook the journal BEFORE attach so the genesis
                # checkpoint manifest is the first shipped frame
                from tpusim.stream.replicate import WalShipper

                shipper = WalShipper(persist, tuple(replicate_to))
            session.attach_persistence(persist)
    if crash_events and persist is not None:
        ev = crash_events[0]
        persist.arm_crash(ev.at, ev.target)
    gen = ChurnLoadGen(snapshot, seed=seed, arrivals=arrivals,
                       evict_fraction=evict_fraction,
                       node_flap_every=node_flap_every,
                       label_churn=label_churn, taint_churn=taint_churn,
                       gang_size=gang_size, gang_count=gang_count)
    skip_events = 0
    if recover:
        # deterministic fast-forward: the generator draws NO rng in batch()
        # or note_bound(), so replaying events()/batch() for the committed
        # prefix — with binds fed back from the WAL — leaves the rng and
        # the bound population exactly where the crashed run had them
        for c in range(start_cycle):
            gen.events(c)
            by_key = {p.key(): p for p in gen.batch()}
            gen.note_bound([
                Placement(pod=bind_pod(by_key[k], node), node_name=node)
                for k, node in report.bound_by_cycle.get(c, [])
                if k in by_key])
        # a crash mid-events left a partially-applied cycle: the replayed
        # picture already holds its first events_applied deltas
        skip_events = report.events_applied.get(start_cycle, 0)
    ref_inc = ref_backend = ref_gen = None
    if verify:
        ref_inc = IncrementalCluster(snapshot)
        ref_backend = get_backend("jax", provider=provider, policy=policy)
        ref_gen = ChurnLoadGen(snapshot, seed=seed, arrivals=arrivals,
                               evict_fraction=evict_fraction,
                               node_flap_every=node_flap_every,
                               label_churn=label_churn,
                               taint_churn=taint_churn,
                               gang_size=gang_size, gang_count=gang_count)
    import hashlib

    chain = hashlib.sha256()
    # the resumable fold over per-cycle placement hashes (persist.chain's
    # twin): seeded from the recovered prefix, so a recovered run's final
    # fold is comparable byte-for-byte with an uninterrupted run's
    fold_chain = report.chain if recover else ""
    latencies: List[float] = []
    expected_hashes: List[str] = []   # verify arm FIFO (pipeline lags 1)
    scheduled = decisions = mismatches = 0

    def account(placements) -> None:
        nonlocal decisions, scheduled, mismatches, fold_chain
        decisions += len(placements)
        scheduled += sum(1 for p in placements if p.node_name)
        h = placement_hash(placements)
        chain.update(h.encode())
        fold_chain = chain_fold(fold_chain, h)
        if persist is None:
            # with persistence attached log_emit publishes the WAL chain
            # head instead; don't fight it with the in-memory fold
            register_metrics().stream_chain_head.set_info(
                head=fold_chain, cycle=str(session.cycles))
        if verify and expected_hashes.pop(0) != h:
            mismatches += 1

    # live what-if arm (ISSUE 19): a dedicated rng stream so the query
    # pods never perturb the churn draw, and per-query latency tracking
    from numpy.random import RandomState as _RandomState
    whatif_rng = _RandomState(seed + 9173) if whatif_every else None
    whatif_lat: List[float] = []
    whatif_stats = {"queries": 0, "answered": 0, "fallbacks": 0}

    def live_query(cycle: int) -> None:
        from tpusim.api.snapshot import make_pod

        qpods = [make_pod(f"whatif-c{cycle}-p{i}",
                          milli_cpu=int(whatif_rng.randint(100, 1500)),
                          memory=int(whatif_rng.randint(2 ** 20, 2 ** 30)))
                 for i in range(whatif_pods)]
        whatif_stats["queries"] += 1
        tq = perf_counter()
        answered = session.overlay_query(qpods)
        if answered is None:
            whatif_stats["fallbacks"] += 1
        else:
            whatif_stats["answered"] += 1
            whatif_lat.append(perf_counter() - tq)

    t_start = perf_counter()
    clean_exit = False
    try:
        for cycle in range(start_cycle, cycles):
            if pipeline:
                # fold cycle N-1's binds BEFORE drawing cycle N's events:
                # the host picture evolves in exactly the synchronous order
                gen.note_bound(session.poll_placed())
            evs = gen.events(cycle)
            if skip_events:
                evs = evs[skip_events:]
                skip_events = 0
            # one trace context per loop cycle: the session's
            # stream_ingest span AND the scheduler's own cycle context (a
            # child — same trace id) share one causal story, so the
            # exported graph connects ingest → scatter-commit → scan →
            # fold → emit. start() is None (and everything below a no-op)
            # unless a flight recorder is installed.
            with tracectx.activate(tracectx.start()):
                session.apply_events(evs)
                batch = gen.batch()
                t0 = perf_counter()
                if pipeline:
                    prev = session.schedule_pipelined(batch)
                else:
                    prev = session.schedule(batch)
            latencies.append(perf_counter() - t0)
            if verify:
                # the reference pictures advance at dispatch time (their
                # state matches the session's host picture NOW); the
                # comparison happens whenever the placements emerge
                ref_inc.apply_events(ref_gen.events(cycle))
                ref_batch = ref_gen.batch()
                from tpusim.gang.group import has_gangs as _has_gangs

                if _has_gangs(ref_batch):
                    # the group driver applies its binds to ref_inc
                    # internally — folding them again would double-apply
                    from tpusim.gang.driver import schedule_with_gangs

                    expected = schedule_with_gangs(ref_backend, ref_inc,
                                                   ref_batch)
                else:
                    expected = ref_backend.schedule(ref_batch,
                                                    ref_inc.to_snapshot())
                    for pl in expected:
                        if pl.node_name:
                            ref_inc.apply(MODIFIED, pl.pod)
                ref_gen.note_bound(expected)
                expected_hashes.append(placement_hash(expected))
            if pipeline:
                if prev is not None:
                    account(prev)
            else:
                gen.note_bound(prev)
                account(prev)
            if whatif_every and (cycle + 1) % whatif_every == 0:
                # interleave a live read with the churn: the overlay
                # rolls back to a byte-identical carry, so fold_chain is
                # provably unchanged vs the query-free run
                live_query(cycle)
        if pipeline:
            tail = session.flush()
            if tail:
                account(tail)
        clean_exit = True
    finally:
        if shipper is not None:
            # a graceful end waits for the follower's cumulative ack; a
            # ProcessCrash propagating through here deliberately does NOT
            # (drain=False is the death model — the unshipped tail lives
            # only in the durable WAL)
            shipper.close(drain=clean_exit, timeout=30.0)
        if persist is not None:
            persist.close()
        if breaker is not None:
            from tpusim.jaxe.backend import uninstall_chaos

            uninstall_chaos()
    elapsed = perf_counter() - t_start
    latencies.sort()

    def pct(q: float) -> float:
        i = min(len(latencies) - 1, int(round(q * (len(latencies) - 1))))
        return latencies[i] if latencies else 0.0

    out = {
        "cycles": cycles, "nodes": len(session.inc.nodes),
        "decisions": decisions, "scheduled": scheduled,
        "unschedulable": decisions - scheduled,
        "elapsed_s": elapsed,
        "decisions_per_s": decisions / elapsed if elapsed > 0 else 0.0,
        "p50_cycle_ms": pct(0.5) * 1e3, "p99_cycle_ms": pct(0.99) * 1e3,
        "paths": dict(session.path_counts),
        "restages": dict(session.restage_counts),
        "commits": session.device.commits,
        "placement_chain": chain.hexdigest(),
        "fold_chain": fold_chain,
        "load": dict(gen.stats),
    }
    if whatif_every:
        whatif_lat.sort()

        def qpct(q: float) -> float:
            if not whatif_lat:
                return 0.0
            i = min(len(whatif_lat) - 1,
                    int(round(q * (len(whatif_lat) - 1))))
            return whatif_lat[i]

        out["overlay"] = {
            **whatif_stats,
            "p50_query_ms": qpct(0.5) * 1e3,
            "p99_query_ms": qpct(0.99) * 1e3,
        }
    if verify:
        out["verified"] = mismatches == 0
        out["mismatched_cycles"] = mismatches
    if breaker is not None:
        out["breaker_transitions"] = list(breaker.transitions)
    if persist is not None:
        out["wal_records"] = persist.wal_records
        out["checkpoints"] = persist.checkpoints
        out["wal_chain"] = persist.chain
    if shipper is not None:
        out["replication_acked_seq"] = shipper.acked_seq
        out["replication_acked_chain"] = shipper.acked_chain
        out["replication_lag_at_close"] = shipper.lag_records()
    if recover:
        out["recovered"] = True
        out["resume_cycle"] = start_cycle
        out["replay_ms"] = report.replay_s * 1e3
        out["recomputed_cycles"] = list(report.recomputed)
        out["recovery_violations"] = list(report.violations)
    # cluster analytics (ISSUE 14): fold the run's fleet-state snapshot
    # into the report when the plane is armed (one None-check otherwise)
    from tpusim.obs import analytics as _analytics

    alog = _analytics.get()
    if alog is not None:
        alog.flush()
        out["analytics"] = alog.snapshot()
    return out


def run_replicated_stream(snapshot: Optional[ClusterSnapshot] = None, *,
                          num_nodes: int = 64, cycles: int = 50,
                          arrivals: int = 32, evict_fraction: float = 0.25,
                          node_flap_every: int = 0,
                          label_churn: int = 0, taint_churn: int = 0,
                          gang_size: int = 0, gang_count: int = 0,
                          seed: int = 0,
                          provider: str = DEFAULT_PROVIDER,
                          policy=None, pipeline: bool = False,
                          always_restage: bool = False,
                          chaos_plan: Optional[object] = None,
                          checkpoint_dir: Optional[str] = None,
                          checkpoint_every: int = 1,
                          fsync_every: int = 0,
                          drain_timeout: float = 30.0) -> dict:
    """Drive a LEADER StreamSession with a live FollowerTwin attached over
    the WAL-shipping socket protocol (stream.replicate, ISSUE 18).

    Without a chaos plan this is a replicated steady-state run: the
    summary reports the follower's chain head next to the leader's (they
    must be byte-identical after a drain) plus the shipping lag the run
    sustained.

    With a process_crash plan (chaos.plan.kill_leader_campaign) the
    leader dies at the targeted WAL record; a FailoverController detects
    the death, promotes the follower (byte-identical chain head is the
    promotion invariant — replaying ONLY the unshipped WAL tail), and
    the churn load generator resumes from the WAL position on the
    promoted twin for the remaining cycles. The summary's fold_chain is
    then byte-identical to an uninterrupted run's, and rto_s measures
    death-detection to promoted end-to-end.
    """
    from tpusim.api.snapshot import synthetic_cluster
    from tpusim.backends import Placement, bind_pod
    from tpusim.chaos.engine import ProcessCrash
    from tpusim.stream import ChurnLoadGen, StreamPersistence, StreamSession
    from tpusim.stream.loadgen import DEFAULT_LABEL_UNIVERSE
    from tpusim.stream.replicate import (
        FailoverController,
        FollowerTwin,
        WalShipper,
    )

    if checkpoint_dir is None:
        raise ValueError("run_replicated_stream needs checkpoint_dir: the "
                         "WAL is the replication substrate")

    def make_snap():
        # fresh object graphs per consumer: the leader, the follower, and
        # each load generator must never share mutable node/pod objects
        if snapshot is not None:
            return snapshot
        snap = synthetic_cluster(num_nodes)
        if policy is not None or label_churn or taint_churn:
            for i, node in enumerate(snap.nodes):
                node.metadata.labels.update(
                    {k: vals[i % len(vals)]
                     for k, vals in DEFAULT_LABEL_UNIVERSE.items()})
        return snap

    crash_events = []
    if chaos_plan is not None:
        chaos_plan.validate()
        if not chaos_plan.host_sections_empty() \
                or not chaos_plan.device.empty():
            raise ValueError(
                "run_replicated_stream takes process_crash sections only "
                "(kill-the-leader campaigns); churn arrives through the "
                "load generator and device faults through the breaker arm")
        crash_events = chaos_plan.crash_events()

    follower = FollowerTwin(make_snap(), provider=provider, policy=policy,
                            always_restage=always_restage)
    leader = StreamSession(make_snap(), provider=provider, policy=policy,
                           always_restage=always_restage)
    persist = StreamPersistence(checkpoint_dir,
                                checkpoint_every=checkpoint_every,
                                fsync_every=fsync_every)
    shipper = WalShipper(persist, follower.address)
    leader.attach_persistence(persist)
    if crash_events:
        ev = crash_events[0]
        persist.arm_crash(ev.at, ev.target)

    gen = ChurnLoadGen(make_snap(), seed=seed, arrivals=arrivals,
                       evict_fraction=evict_fraction,
                       node_flap_every=node_flap_every,
                       label_churn=label_churn, taint_churn=taint_churn,
                       gang_size=gang_size, gang_count=gang_count)

    latencies: List[float] = []
    crashed: Optional[str] = None
    lag_at_crash = 0
    leader_alive = [True]

    def run_cycles(session, g, start: int, skip_events: int) -> None:
        skip = skip_events
        for cycle in range(start, cycles):
            if pipeline:
                g.note_bound(session.poll_placed())
            evs = g.events(cycle)
            if skip:
                evs = evs[skip:]
                skip = 0
            session.apply_events(evs)
            batch = g.batch()
            t0 = perf_counter()
            prev = (session.schedule_pipelined(batch) if pipeline
                    else session.schedule(batch))
            latencies.append(perf_counter() - t0)
            if not pipeline:
                g.note_bound(prev)
        if pipeline:
            session.flush()

    t_start = perf_counter()
    try:
        run_cycles(leader, gen, 0, 0)
    except ProcessCrash as exc:
        crashed = str(exc)
        leader_alive[0] = False
        lag_at_crash = shipper.lag_records()
        # leader death: nothing drains — the wire keeps only what it
        # already carried, the durable WAL keeps everything
        shipper.close(drain=False)
        persist.close()

    out: dict = {
        "cycles": cycles, "pipeline": pipeline,
        "crashed": crashed is not None, "crash_detail": crashed,
        "promoted": False, "divergence": None,
    }
    if crashed is None:
        # steady-state shipping backlog: records appended but not yet
        # acked the instant the producer stops (drain clears it, so
        # sample before)
        lag_at_loop_end = shipper.lag_records()
        drained = shipper.drain(drain_timeout)
        shipper.close(drain=False)
        out.update({
            "drained": drained,
            "lag_at_loop_end": lag_at_loop_end,
            "fold_chain": persist.chain,
            "follower_chain": follower.chain,
            "follower_chain_matches": follower.chain == persist.chain,
            "wal_records": persist.wal_records,
            "checkpoints": persist.checkpoints,
            "decisions": persist.decisions,
            "scheduled": persist.scheduled,
            "applied_records": follower.wal_records_applied,
            "divergence": follower.diverged,
            "restages": dict(leader.restage_counts),
            "follower_restages": dict(follower.session.restage_counts),
        })
        follower.stop()
        final_persist = persist
    else:
        controller = FailoverController(
            lambda: leader_alive[0], [follower], checkpoint_dir,
            interval_s=0.005, misses=2,
            checkpoint_every=checkpoint_every, fsync_every=fsync_every,
            leader_was_alive=True)
        promoted, preport = controller.run(timeout=30.0)
        resume_cycle = preport.resume_cycle
        # resume the churn load generator from the WAL position: batch()
        # and note_bound() draw no rng, so replaying the committed prefix
        # with binds fed back from the replicated/replayed bind maps
        # leaves the rng and the bound population exactly where the dead
        # leader had them (the recover_stream_session fast-forward)
        gen2 = ChurnLoadGen(make_snap(), seed=seed, arrivals=arrivals,
                            evict_fraction=evict_fraction,
                            node_flap_every=node_flap_every,
                            label_churn=label_churn,
                            taint_churn=taint_churn,
                            gang_size=gang_size, gang_count=gang_count)
        for c in range(resume_cycle):
            gen2.events(c)
            by_key = {p.key(): p for p in gen2.batch()}
            gen2.note_bound([
                Placement(pod=bind_pod(by_key[k], node), node_name=node)
                for k, node in promoted.bound_by_cycle.get(c, [])
                if k in by_key])
        skip_events = promoted.events_applied.get(resume_cycle, 0)
        run_cycles(promoted.session, gen2, resume_cycle, skip_events)
        final_persist = promoted.persist
        final_persist.close()
        out.update({
            "promoted": True,
            "rto_s": preport.rto_s,
            "resume_cycle": resume_cycle,
            "replayed_records": preport.tail_records,
            "applied_records": preport.applied_records,
            "recomputed_cycles": list(preport.recomputed),
            "settled_live_cycles": list(preport.settled_live),
            "promotion_violations": list(preport.violations),
            "lag_at_crash": lag_at_crash,
            "fold_chain": final_persist.chain,
            "wal_records": final_persist.wal_records,
            "checkpoints": final_persist.checkpoints,
            "decisions": final_persist.decisions,
            "scheduled": final_persist.scheduled,
            "divergence": promoted.diverged,
            "restages": dict(leader.restage_counts),
            "follower_restages": dict(promoted.session.restage_counts),
        })
    elapsed = perf_counter() - t_start
    latencies.sort()
    out["elapsed_s"] = elapsed
    out["nodes"] = num_nodes
    out["p50_cycle_ms"] = (latencies[len(latencies) // 2] * 1e3
                           if latencies else 0.0)
    return out

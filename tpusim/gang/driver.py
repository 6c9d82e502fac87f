"""Gang admission driver: host-oracle group pass over the fused scan's lanes.

`schedule_with_gangs` is the one entry point every route (batch simulator,
stream runtime, verify oracle) calls when a feed carries gang annotations.
It splits the feed into ungrouped runs — scheduled through the UNCHANGED
per-pod path, so gang-free prefixes place identically to today — and
complete gangs, each admitted all-or-nothing by `admit_gang`:

  1. compile the member batch against the live IncrementalCluster and run
     the fused scan's feasibility/score lanes for every member against the
     SAME snapshot (`gang_lanes`: a vmap over the per-pod evaluate stage);
  2. solve joint placement (`gang_choices`: rank-aware greedy packing that
     pulls members toward zone/rack domains already holding mates, with an
     arithmetic capacity re-check as members stack — the device kernel runs
     behind the AUTO verify-then-trust seam against the numpy oracle);
  3. if at least `min-available` members placed, commit every bind
     atomically through the store fabric (journal-marked: a partial apply
     failure rolls the journal back); otherwise reject the WHOLE gang with
     one shared FitError and zero binds.

Gangs whose members use features the compiled state cannot carry fall back
to the backend's sequential path for the trial (intra-batch binds visible,
reference semantics), then the same all-or-nothing commit-or-reject gate.
"""

from __future__ import annotations

import logging
from typing import Dict, List, Tuple

import numpy as np

from tpusim.api.types import Pod
from tpusim.backends import Placement, bind_pod, mark_unschedulable
from tpusim.framework import metrics as _metrics
from tpusim.framework.store import MODIFIED
from tpusim.gang.group import PodGroup, split_feed
from tpusim.gang.kernel import gang_choices
from tpusim.gang.oracle import packing_domains
from tpusim.obs import provenance
from tpusim.obs import recorder as flight

log = logging.getLogger("tpusim.gang")


def gang_fit_message(group: PodGroup, num_nodes: int, placed: int) -> str:
    """The single FitError message shared by every member of a rejected
    gang: the group identity and the shortfall, not a per-member reason
    histogram (the decision is joint, so the attribution is too)."""
    return (f"0/{num_nodes} nodes are available: pod group "
            f"\"{group.name}\" requires {group.min_available}/"
            f"{len(group.pods)} members, only {placed} fit jointly.")


def _reject(group: PodGroup, num_nodes: int, placed: int,
            reason: str) -> List[Placement]:
    msg = gang_fit_message(group, num_nodes, placed)
    m = _metrics.register()
    m.gang_rejected.inc(reason)
    flight.note_gang("reject", {"group": group.name, "reason": reason,
                                "placed": placed})
    return [Placement(pod=mark_unschedulable(p, msg),
                      reason="Unschedulable", message=msg)
            for p in group.pods]


def admit_gang(backend, inc, group: PodGroup) -> List[Placement]:
    """All-or-nothing admission of one gang against the live incremental
    cluster. On admit the binds are applied to `inc` (journal-marked);
    on reject nothing is applied."""
    m = _metrics.register()
    m.gang_size.observe(len(group.pods))
    members = group.pods
    num_nodes = len(inc.nodes)
    if num_nodes == 0:
        return _reject(group, 0, 0, "no_nodes")

    with flight.span("gang:admit") as sp:
        if sp:
            sp.set("group", group.name)
            sp.set("members", len(members))
        compiled, cols = inc.compile(members)
        if compiled.unsupported:
            choices, node_names = _sequential_trial(
                backend, inc, members, compiled, cols)
        else:
            choices, node_names = _joint_solve(
                backend, inc, members, compiled, cols)

    placed = sum(1 for c in choices if c >= 0)
    if placed < group.min_available:
        return _reject(group, num_nodes, placed, "min_available")

    # commit: every placed member binds atomically through the store
    # fabric; a failure mid-loop rolls the journal back so no partial
    # gang survives in the event stream
    mark = inc.journal_mark()
    placements: List[Placement] = []
    try:
        for pod, c in zip(members, choices):
            if c >= 0:
                bound = bind_pod(pod, node_names[c])
                inc.apply(MODIFIED, bound)
                placements.append(Placement(pod=bound,
                                            node_name=node_names[c]))
            else:
                # admitted at min-available: the overflow members failed
                # individually, not the gang
                msg = (f"pod group \"{group.name}\" admitted at "
                       f"{placed}/{len(members)}; this member did not fit.")
                placements.append(Placement(
                    pod=mark_unschedulable(pod, msg),
                    reason="Unschedulable", message=msg))
    except Exception:
        inc.journal_rollback(mark)
        m.gang_partial_rollback.inc()
        flight.note_gang("rollback", {"group": group.name})
        raise
    inc.journal_release()
    m.gang_admitted.inc()
    flight.note_gang("admit", {"group": group.name, "placed": placed,
                               "members": len(members)})
    return placements


def _joint_solve(backend, inc, members: List[Pod], compiled, cols
                 ) -> Tuple[List[int], List[str]]:
    """Member lanes + joint packing. Returns (choices, node name order)."""
    from tpusim.jaxe import ensure_x64
    from tpusim.jaxe.backend import _MOST_REQUESTED_PROVIDERS
    from tpusim.jaxe.kernels import (
        carry_init,
        carry_init_host,
        config_for,
        gang_lanes,
        pod_columns_to_device,
        pod_columns_to_host,
        statics_to_device,
        statics_to_host,
    )
    from tpusim.jaxe.state import NUM_FIXED_BITS

    ensure_x64()
    config = config_for(
        [compiled],
        most_requested=getattr(backend, "provider",
                               None) in _MOST_REQUESTED_PROVIDERS,
        num_reason_bits=NUM_FIXED_BITS + len(compiled.scalar_names),
        hard_weight=getattr(backend,
                            "hard_pod_affinity_symmetric_weight", 10))
    statics = statics_to_device(compiled)
    carry = carry_init(compiled)
    xs = pod_columns_to_device(cols)
    n_nodes = len(compiled.statics.names)
    lanes = None
    from tpusim.jaxe.backend import _SHARD_AUTO, _shard_count

    n_shards = _shard_count()
    if n_shards > 1 and not _SHARD_AUTO["disabled"]:
        # cross-shard gang lanes (ISSUE 16 sub-problem b): per-member
        # filter/score runs per node shard with collective reductions,
        # the stitched output re-gathers the full (member, node) matrix
        # — padded columns come back all-infeasible, so slicing to the
        # real node count feeds gang_choices byte-identical inputs
        from dataclasses import replace as _dc_replace

        from tpusim.jaxe.kernels import gang_lanes_sharded
        from tpusim.jaxe.sharding import make_mesh, shard_for_mesh

        mesh = make_mesh(n_shards, snap=1)
        st, ca, xs_r = shard_for_mesh(mesh, statics, carry, xs)
        with flight.span("shard:gang_lanes", "device") as sp:
            lanes = gang_lanes_sharded(
                _dc_replace(config, shard_axis="node"), mesh, ca, st,
                xs_r)
            if sp:
                sp.set("shards", n_shards)
                sp.set("members", len(members))
    if lanes is None:
        lanes = gang_lanes(config, carry, statics, xs)
    feasible = np.asarray(lanes[0])[:, :n_nodes]
    score = np.asarray(lanes[1])[:, :n_nodes]

    names = list(compiled.statics.names)
    by_name = {n.metadata.name: n for n in inc.nodes}
    zone_dom, rack_dom, n_zone, n_rack = packing_domains(
        [by_name[name] for name in names])

    hs = statics_to_host(compiled)
    hc = carry_init_host(compiled)
    hx = pod_columns_to_host(cols)
    choices = gang_choices(
        feasible, score,
        np.asarray(hx.req_cpu), np.asarray(hx.req_mem),
        np.asarray(hx.req_gpu), np.asarray(hx.req_eph),
        np.asarray(hx.zero_request),
        np.asarray(hs.alloc_cpu), np.asarray(hs.alloc_mem),
        np.asarray(hs.alloc_gpu), np.asarray(hs.alloc_eph),
        np.asarray(hs.allowed_pods),
        np.asarray(hc.used_cpu), np.asarray(hc.used_mem),
        np.asarray(hc.used_gpu), np.asarray(hc.used_eph),
        np.asarray(hc.pod_count),
        zone_dom, rack_dom, n_zone, n_rack)
    return choices, names


def _sequential_trial(backend, inc, members: List[Pod], compiled, cols
                      ) -> Tuple[List[int], List[str]]:
    """Fallback for gangs carrying features the compiled state cannot hold:
    a sequential trial through the backend (which itself falls back to
    reference semantics for the unsupported features; intra-batch binds are
    visible pod-to-pod on both engines). Nothing is committed here — the
    caller applies the all-or-nothing gate over the resulting choices."""
    log.warning("gang trial via sequential fallback for: %s",
                "; ".join(sorted(set(compiled.unsupported))[:5]))
    names = list(compiled.statics.names)
    index = {name: i for i, name in enumerate(names)}
    trial = backend.schedule(members, inc.to_snapshot(),
                             precompiled=(compiled, cols))
    return [index.get(pl.node_name, -1) if pl.scheduled else -1
            for pl in trial], names


def schedule_with_gangs(backend, inc, pods: List[Pod],
                        source: str = "gang") -> List[Placement]:
    """Schedule a feed that (may) carry gang annotations: ungrouped runs go
    through the backend's unchanged per-pod path; each gang is admitted
    all-or-nothing by `admit_gang`. Binds are applied to `inc` as decisions
    land, so later segments see earlier placements. Placements come back in
    the original feed order."""
    by_key: Dict[Tuple[str, str], Placement] = {}
    gang_placements: List[Placement] = []
    for seg in split_feed(pods):
        if seg.pods is not None:
            snapshot = inc.to_snapshot()
            precompiled = inc.compile(seg.pods) if inc.nodes else None
            pls = backend.schedule(seg.pods, snapshot,
                                   precompiled=precompiled)
            for pl in pls:
                if pl.scheduled:
                    inc.apply(MODIFIED, pl.pod)
        else:
            pls = admit_gang(backend, inc, seg.group)
            gang_placements.extend(pls)
        for pl in pls:
            key = (pl.pod.metadata.namespace, pl.pod.metadata.name)
            by_key[key] = pl
    if gang_placements:
        provenance.capture(gang_placements, source)
    return [by_key[(p.metadata.namespace, p.metadata.name)] for p in pods]

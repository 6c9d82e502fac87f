"""Multi-snapshot what-if: batch independent cluster scenarios over the mesh.

BASELINE.json config 5 ("Multi-tenant what-if: 50 concurrent cluster snapshots
× 20k pods each, batched over TPU"). The reference has no analog — each run is
one process over one snapshot; what-if studies mean re-running the binary
(SURVEY.md §5 checkpoint note). Here scenarios are compiled to a common array
shape, stacked on a leading snapshot axis, and dispatched as ONE device
program: vmap over the snapshot axis, with the axis sharded over the mesh's
"snap" dimension (zero cross-snapshot communication — the dp analog) and node
columns over "node" (ICI collectives inserted by GSPMD).

Shape unification:
  * node axis — padded to the common max (and the mesh's node-shard multiple)
    with sentinel-infeasible nodes (sharding.pad_node_axis).
  * signature tables — padded on the signature axis with unreferenced rows.
  * scalar-resource columns — padded to the widest scenario; a scenario's
    reason-bit space stays its own (unused high bits never fire).
  * pod axis — padded with ghost pods whose CPU request exceeds any node
    (infeasible everywhere: no bind scatter, no round-robin advance), dropped
    on decode.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpusim.api.snapshot import ClusterSnapshot
from tpusim.api.types import Pod
from tpusim.backends import Placement
from tpusim.jaxe import ensure_x64
from tpusim.jaxe.backend import (
    _KNOWN_PROVIDERS,
    _MOST_REQUESTED_PROVIDERS,
    decode_placements,
    fast_route,
)
from tpusim.jaxe.kernels import (
    CARRY_AXES,
    PODX_AXES,
    STATICS_AXES,
    Carry,
    PodX,
    Statics,
    carry_init_host,
    config_for,
    _schedule_scan_impl,
    _tree_to_device,
    pod_columns_to_host,
    statics_to_host,
)
from tpusim.jaxe.sharding import (
    mesh_kind,
    pad_node_axis,
    scenario_shardings,
    scenario_specs,
    snap_shardings,
    stage_tree,
)
from tpusim.jaxe.state import NUM_FIXED_BITS, compile_cluster, reason_strings
from tpusim.jaxe.trust import FAST

log = logging.getLogger(__name__)

GHOST_CPU = np.int64(1) << 61  # larger than any allocatable: never feasible

# Trace-time compile tally: the increments below run while jax TRACES a
# program (cache miss), not when a cached executable re-runs — so the delta
# across two calls says whether the second paid a compile. The serve
# executor's warm-cache stamps and the bench config-8 `compile_cache_hit`
# field are both read off this counter.
_COMPILE_COUNTS = {"batched": 0, "scenario_sharded": 0}


def compile_count() -> int:
    """Total what-if program traces this process (see _COMPILE_COUNTS)."""
    return sum(_COMPILE_COUNTS.values())


@partial(jax.jit, static_argnames=("config",))
def _batched(config, carries, statics_b, xs_b):
    """vmap of the exact scan over the scenario axis, jitted at module level
    so jax's compile cache persists across run_what_if invocations: repeated
    what-if studies with matching shapes+config skip the (minutes-long on
    TPU) XLA compile that dominates a cold call (BASELINE.md config 5)."""
    _COMPILE_COUNTS["batched"] += 1

    def one(carry, st, xs):
        _, choices, counts, _adv = _schedule_scan_impl(config, carry, st, xs)
        return choices, counts

    return jax.vmap(one)(carries, statics_b, xs_b)


# (config, mesh) -> jitted shard_map program. jax's jit cache would dedupe
# the executables anyway; this dict also dedupes the shard_map/closure
# CONSTRUCTION and gives the serve executor a stable identity to key its
# warm-cache bookkeeping on.
_SCENARIO_PROGRAMS: dict = {}


def _scenario_program(config, mesh):
    """The manual shard_map route: scenarios partitioned over the mesh's
    "scenario" axis, node columns whole per shard (make_scenario_mesh).
    Cross-scenario communication is impossible by construction — each shard
    runs the vmap-of-scan on its own scenario slice. check_vma=False because
    out_specs carry no replicated axes to prove."""
    fn = _SCENARIO_PROGRAMS.get((config, mesh))
    if fn is None:

        ca_spec, st_spec, xs_spec = scenario_specs()

        def local(carries, statics_b, xs_b):
            _COMPILE_COUNTS["scenario_sharded"] += 1

            def one(carry, st, xs):
                _, choices, counts, _adv = _schedule_scan_impl(
                    config, carry, st, xs)
                return choices, counts

            return jax.vmap(one)(carries, statics_b, xs_b)

        from jax.sharding import PartitionSpec as P

        fn = jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=(ca_spec, st_spec, xs_spec),
            out_specs=(P("scenario"), P("scenario")), check_vma=False))
        _SCENARIO_PROGRAMS[(config, mesh)] = fn
    return fn


@dataclass
class WhatIfResult:
    """Per-scenario outcome."""

    placements: List[Placement]
    scheduled: int
    unschedulable: int

    @property
    def total(self) -> int:
        return self.scheduled + self.unschedulable


def _pad_axis(a: np.ndarray, axis: int, target: int, fill=0) -> np.ndarray:
    pad = target - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return np.pad(a, widths, constant_values=fill)


def _axis_targets(host_trees) -> dict:
    """Max size per named (non-node) axis across scenarios, derived from the
    kernels axis registries — new state fields unify automatically."""
    targets: dict = {}
    for statics, carry, xs in host_trees:
        trees = [(statics, STATICS_AXES, 0), (carry, CARRY_AXES, 0),
                 (xs, PODX_AXES, 1)]
        for tree, axes_map, offset in trees:
            for name, arr in tree._asdict().items():
                for i, axis in enumerate(axes_map[name]):
                    if axis == "node":
                        continue
                    size = np.asarray(arr).shape[i + offset]
                    targets[axis] = max(targets.get(axis, 0), size)
    return targets


def _unify_tree(tree, axes_map, targets: dict, axis_offset: int = 0):
    fields = {}
    for name, arr in tree._asdict().items():
        arr = np.asarray(arr)
        for i, axis in enumerate(axes_map[name]):
            if axis == "node":
                continue
            arr = _pad_axis(arr, i + axis_offset, targets[axis])
        fields[name] = arr
    return fields


def _unify(statics: Statics, carry: Carry, xs: PodX, targets: dict,
           p_max: int) -> Tuple[Statics, Carry, PodX]:
    """Pad signature / scalar / pod axes to the common shape (host-side)."""
    st_fields = _unify_tree(statics, STATICS_AXES, targets)
    ca_fields = _unify_tree(carry, CARRY_AXES, targets)

    p = np.asarray(xs.req_cpu).shape[0]
    fields = _unify_tree(xs, PODX_AXES, targets, axis_offset=1)
    fields = {k: _pad_axis(v, 0, p_max) for k, v in fields.items()}
    if p_max > p:
        # ghost pods: infeasible everywhere, never advance rr or bind
        fields["req_cpu"] = fields["req_cpu"].copy()
        fields["req_cpu"][p:] = GHOST_CPU
        fields["zero_request"] = fields["zero_request"].copy()
        fields["zero_request"][p:] = False
    # stays on host: the single device upload happens after scenario stacking
    return Statics(**st_fields), Carry(**ca_fields), PodX(**fields)


def _policy_prep(policy, hard_pod_affinity_symmetric_weight: int):
    """Compile the batch-wide policy once: (cp, need_noexec, need_saa,
    hard_weight). Shared by run_what_if and the serve executor (which keys
    its warm-executable cache on cp.spec — the what-if analog of the fast
    path's plan_signature)."""
    cp = None
    if policy is not None:
        from tpusim.jaxe.policyc import compile_policy

        cp = compile_policy(policy)
        if cp.unsupported:
            detail = "; ".join(sorted(set(cp.unsupported))[:5])
            raise NotImplementedError(
                "what-if batching requires a jax-compilable policy; "
                f"host-bound: {detail}")
        if cp.hard_weight is not None:
            hard_pod_affinity_symmetric_weight = cp.hard_weight
    from tpusim.engine.predicates import (
        POD_TOLERATES_NODE_NO_EXECUTE_TAINTS_PRED,
    )

    need_noexec = (cp is not None and cp.spec.pred_keys is not None
                   and POD_TOLERATES_NODE_NO_EXECUTE_TAINTS_PRED
                   in cp.spec.pred_keys)
    need_saa = cp is not None and (bool(cp.spec.saa_weights)
                                   or cp.spec.sa_enabled)
    return cp, need_noexec, need_saa, hard_pod_affinity_symmetric_weight


@dataclass
class StagedScenario:
    """One scenario compiled to host trees, ready to batch (run_what_if) or
    bucket (tpusim.serve): the unit the serve snapshot cache stores."""

    compiled: object
    cols: object
    statics: Statics
    carry: Carry
    xs: PodX
    ptabs: object
    n_saa_doms: int


def _stage_scenario(snapshot: ClusterSnapshot, pods: List[Pod], cp,
                    need_noexec: bool, need_saa: bool) -> StagedScenario:
    """Host-stage one (snapshot, pods) scenario: compile_cluster + policy
    tables + host trees. Raises ValueError for a zero-node snapshot (there
    is no node axis to pad onto) and NotImplementedError for scenarios the
    device engine can't express."""
    if not snapshot.nodes:
        raise ValueError(
            "what-if scenario has a zero-node snapshot: nothing can "
            "schedule; run scenarios against at least one node")
    compiled, cols = compile_cluster(snapshot, pods, need_noexec=need_noexec,
                                     need_saa=need_saa)
    if compiled.unsupported:
        detail = "; ".join(sorted(set(compiled.unsupported))[:5])
        raise NotImplementedError(
            "what-if batching requires jax-compilable scenarios; "
            f"unsupported: {detail} (run this scenario on the reference "
            "backend instead)")
    host_statics = statics_to_host(compiled)
    host_carry = carry_init_host(compiled)
    ptabs = None
    n_saa_doms = 1
    if cp is not None:
        # one build per scenario feeds the vmap statics AND the fast
        # loop's plan (the trivial PolicyTables shapes match
        # statics_to_host / carry_init_host, so unconditional replace
        # is byte-identical for features the policy lacks)
        from tpusim.jaxe.policyc import build_policy_tables

        ptabs = build_policy_tables(cp, snapshot, pods, compiled, cols)
        host_statics = host_statics._replace(
            label_ok=ptabs.label_ok, label_prio=ptabs.label_prio,
            image_score=ptabs.image_score, saa_dom=ptabs.saa_dom,
            sa_pin=ptabs.sa_pin, sa_val=ptabs.sa_val)
        host_carry = host_carry._replace(sa_lock=ptabs.sa_lock_init)
        n_saa_doms = ptabs.n_saa_doms
    return StagedScenario(compiled=compiled, cols=cols, statics=host_statics,
                          carry=host_carry, xs=pod_columns_to_host(cols),
                          ptabs=ptabs, n_saa_doms=n_saa_doms)


def batch_config(compiled_list, provider: str, cp, hard_weight: int,
                 n_saa_doms: int, num_scalars: Optional[int] = None):
    """EngineConfig for a batch of compiled scenarios. num_scalars widens
    the reason-bit space beyond the batch's own max (the serve executor
    pins it to the shape class's scalar budget so every bucket of a class
    traces one program; unused high bits never fire)."""
    s_max = max(len(c.scalar_names) for c in compiled_list)
    if num_scalars is not None:
        s_max = max(s_max, num_scalars)
    config = config_for(
        compiled_list,
        most_requested=provider in _MOST_REQUESTED_PROVIDERS,
        num_reason_bits=NUM_FIXED_BITS + s_max,
        hard_weight=hard_weight)
    if cp is not None:
        from dataclasses import replace as _dc_replace

        config = _dc_replace(config, policy=cp.spec, n_saa_doms=n_saa_doms)
    return config


def _prepare_host_batch(scenarios, provider: str,
                        hard_pod_affinity_symmetric_weight: int, policy):
    """Compile the batch on host numpy (shape unification is deferred:
    `_unify_batch` pads the returned host_trees for the vmap program — the
    Pallas fast loop consumes the per-scenario compiled state directly and
    must not pay for padding it would throw away).

    Returns (config, host_trees, compiled_list, ptabs_list) — ptabs_list
    holds each scenario's PolicyTables (None without a policy) for the fast
    loop's planner. Raises ValueError for input shapes that cannot batch
    (empty scenario list, zero-node snapshots) — clear host-side errors
    instead of a failure inside jit.
    """
    if provider not in _KNOWN_PROVIDERS:
        raise KeyError(f"plugin {provider!r} has not been registered")
    if not scenarios:
        raise ValueError(
            "run_what_if needs at least one (snapshot, pods) scenario")
    cp, need_noexec, need_saa, hard_weight = _policy_prep(
        policy, hard_pod_affinity_symmetric_weight)
    ensure_x64()

    staged: List[StagedScenario] = []
    for i, (snapshot, pods) in enumerate(scenarios):
        try:
            staged.append(_stage_scenario(snapshot, pods, cp,
                                          need_noexec, need_saa))
        except ValueError as exc:
            raise ValueError(f"scenario {i}: {exc}") from None

    host_trees = [(s.statics, s.carry, s.xs) for s in staged]
    compiled_list = [(s.compiled, s.cols) for s in staged]
    ptabs_list = [s.ptabs for s in staged]
    config = batch_config(
        [s.compiled for s in staged], provider, cp, hard_weight,
        n_saa_doms=max(s.n_saa_doms for s in staged))
    return config, host_trees, compiled_list, ptabs_list


def _unify_batch(host_trees, n_snap_shards: int, n_node_shards: int):
    """Shape-unify + pad the compiled host trees for the batched vmap
    program; returns per_scenario (carry, statics, xs) tuples padded to
    the snap-shard multiple."""
    targets = _axis_targets(host_trees)
    p_max = max(np.asarray(xs.req_cpu).shape[0] for _, _, xs in host_trees)
    n_max = max(s.alloc_cpu.shape[0] for s, _, _ in host_trees)
    # one pad target: max nodes rounded up to the node-shard multiple
    n_target = -(-n_max // n_node_shards) * n_node_shards

    per_scenario = []
    for statics, carry, xs in host_trees:
        statics, carry, xs = _unify(statics, carry, xs, targets, p_max)
        statics, carry, _ = pad_node_axis(statics, carry, n_target)
        per_scenario.append((carry, statics, xs))

    # pad the scenario axis to the snap-shard multiple with replicas
    while len(per_scenario) % n_snap_shards != 0:
        per_scenario.append(per_scenario[0])
    return per_scenario


def _stack_host(per_scenario):
    """Stacked host-numpy trees (carries, statics_b, xs_b)."""
    stack = lambda trees: jax.tree.map(  # noqa: E731
        lambda *a: np.stack([np.asarray(x) for x in a]), *trees)
    return (stack([t[0] for t in per_scenario]),
            stack([t[1] for t in per_scenario]),
            stack([t[2] for t in per_scenario]))


def decode_one(pods: List[Pod], compiled, choices, counts) -> WhatIfResult:
    """Decode one scenario's device outputs back to placements (shared with
    the serve executor, whose buckets decode only their REAL entries — ghost
    scenarios and pod-axis padding never reach here)."""
    placements, scheduled = decode_placements(
        pods, choices, counts, compiled.statics.names,
        reason_strings(compiled.scalar_names))
    return WhatIfResult(placements=placements, scheduled=scheduled,
                        unschedulable=len(pods) - scheduled)


def _decode_batch(scenarios, compiled_list, choices_b,
                  counts_b) -> List[WhatIfResult]:
    # the batch may be longer than the scenario list (scenario-axis padding
    # replicas); iterate the real scenarios only
    return [decode_one(scenarios[b][1], compiled_list[b][0], choices_b[b],
                       counts_b[b])
            for b in range(len(scenarios))]


def _try_fast_loop(scenarios, config, compiled_list, ptabs_list, host_trees):
    """Run every scenario through the Pallas fast path sequentially;
    returns the decoded results, or None to fall back to the batched vmap
    program (ineligible scenario, fast path off/disabled, a transient
    runtime failure, or a failed AUTO self-verification). A kernel that
    fails to lower or compile raises."""
    from tpusim.jaxe.fastscan import fast_scan

    routes = []
    for b, (compiled, cols) in enumerate(compiled_list):
        route = fast_route(config, compiled, cols, len(cols.req_cpu),
                           ptabs=ptabs_list[b])
        if route.plan is None:
            return None
        routes.append(route)
    choices_list = []
    counts_list = []
    for b, route in enumerate(routes):
        try:
            choices, counts, _adv = fast_scan(route.plan)
        except Exception as exc:
            if not FAST.note_failure(exc):
                raise
            return None
        FAST.note_success()
        if route.auto and not FAST.trusted(route.sig):
            # every scenario verifies until its kernel variant is pinned —
            # a small scenario 0 passing trivially must not exempt the rest
            # of the batch (a variant pins only from trust.PIN_MIN_PODS
            # pods); the replay runs on the same policy-grafted statics and
            # carry the batched vmap program would use for this scenario
            compiled, cols = compiled_list[b]
            hs, hc, _ = host_trees[b]
            if not route.check(config, compiled, cols, choices, counts,
                               statics=_tree_to_device(hs), carry=hc):
                return None
        choices_list.append(choices)
        counts_list.append(counts)
    return _decode_batch(scenarios, compiled_list, choices_list, counts_list)


def run_what_if(scenarios: Sequence[Tuple[ClusterSnapshot, List[Pod]]],
                provider: str = "DefaultProvider",
                mesh: Optional[object] = None,
                hard_pod_affinity_symmetric_weight: int = 10,
                policy=None) -> List[WhatIfResult]:
    """Run independent (snapshot, pods) scenarios as one batched device
    program. Pods are fed in podspec order (callers wanting reference LIFO
    parity pass the reversed list, as run_simulation does).

    mesh: an optional jax.sharding.Mesh; None runs single-device. A
    ("snap", "node") mesh (sharding.make_mesh) runs the GSPMD route: the
    scenario axis sharded over "snap", node columns over "node" with XLA
    collectives. A ("scenario", "node") mesh (sharding.make_scenario_mesh)
    runs the manual shard_map route: scenarios partitioned with node columns
    whole per shard — the serving shape, where scenario throughput is the
    axis that matters. Any other axis names raise ValueError. The scenario
    count need not divide the scenario/snap axis — the batch is padded with
    a replica of the first scenario and the padding dropped on decode.

    policy: an engine.policy.Policy applied to EVERY scenario (one jitted
    program serves the batch, so the policy is batch-wide); host-bound policy
    features raise — what-if has no per-scenario host fallback.

    Raises ValueError for inputs that cannot batch — empty scenario list,
    zero-node snapshots, unknown mesh axes — before anything reaches jit.
    """
    # mesh validation runs BEFORE any host prep or staging: axis names via
    # mesh_kind, then device membership — a mesh built over devices this
    # process can't see used to surface as an opaque device_put failure
    # after the whole batch was already unified and stacked
    kind = mesh_kind(mesh) if mesh is not None else None
    if mesh is not None:
        visible = set(jax.devices())
        missing = [d for d in mesh.devices.flat if d not in visible]
        if missing:
            raise ValueError(
                f"what-if mesh spans {len(missing)} device(s) not visible "
                f"to this process (e.g. {missing[0]}); rebuild the mesh "
                "from jax.devices()")
    n_snap_shards = 1 if mesh is None else (
        mesh.shape["snap"] if kind == "snap" else mesh.shape["scenario"])
    # the shard_map route keeps node columns whole per shard: no node pad
    n_node_shards = mesh.shape["node"] if kind == "snap" else 1
    config, host_trees, compiled_list, ptabs_list = _prepare_host_batch(
        scenarios, provider, hard_pod_affinity_symmetric_weight, policy)

    if mesh is None:
        # Pallas fast loop: per-scenario kernels instead of the single
        # vmap(S)xscan(P) program, whose XLA compile alone costs ~2min at
        # the 50x20k BASELINE config-5 shape. Engages only when EVERY
        # scenario is fast-eligible and the fast path is on for this
        # process (AUTO on TPU, sharing the backend's self-verification
        # state); anything else keeps the batched program. Runs BEFORE the
        # shape unification below, which the fast loop never needs.
        fast = _try_fast_loop(scenarios, config, compiled_list, ptabs_list,
                              host_trees)
        if fast is not None:
            return fast

    per_scenario = _unify_batch(host_trees, n_snap_shards, n_node_shards)
    host_carries, host_statics, host_xs = _stack_host(per_scenario)
    if mesh is not None:
        # sharded upload straight from host numpy — materializing on the
        # default device first would double the transfer and peak memory
        if kind == "snap":
            st_spec, ca_spec, xs_spec = snap_shardings(mesh)
        else:
            ca_spec, st_spec, xs_spec = scenario_shardings(mesh)
        xs_b = stage_tree(host_xs, xs_spec)
        carries = stage_tree(host_carries, ca_spec)
        statics_b = stage_tree(host_statics, st_spec)
    else:
        carries, statics_b, xs_b = (stage_tree(host_carries),
                                    stage_tree(host_statics),
                                    stage_tree(host_xs))

    if kind == "scenario":
        choices_b, counts_b = _scenario_program(config, mesh)(
            carries, statics_b, xs_b)
        choices_b = np.asarray(choices_b)
    elif kind == "snap":
        with mesh:
            choices_b, counts_b = _batched(config, carries, statics_b, xs_b)
            choices_b = np.asarray(choices_b)
    else:
        choices_b, counts_b = _batched(config, carries, statics_b, xs_b)
        choices_b = np.asarray(choices_b)
    counts_b = np.asarray(counts_b)
    return _decode_batch(scenarios, compiled_list, choices_b, counts_b)


def run_what_if_multihost(scenarios: Sequence[Tuple[ClusterSnapshot, List[Pod]]],
                          provider: str = "DefaultProvider",
                          hard_pod_affinity_symmetric_weight: int = 10,
                          policy=None) -> List[WhatIfResult]:
    """Multi-process what-if: one global batched program over every
    participating host's devices (the DCN analog — SURVEY.md §5
    "distributed communication backend").

    EVERY process (after `jax.distributed.initialize`) calls this with an
    IDENTICAL, deterministically-built scenario list. The global
    ("snap", "node") mesh puts one snap shard per process (scenarios are
    data-parallel across hosts; node columns shard across each host's local
    devices), array shards are placed via `jax.make_array_from_callback`
    (host data is replicated, placement is distributed), and the results
    are replicated back so every process decodes the full batch.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from tpusim.jaxe.sharding import make_mesh

    nproc = jax.process_count()
    n_node = jax.local_device_count()
    config, host_trees, compiled_list, _ptabs_list = _prepare_host_batch(
        scenarios, provider, hard_pod_affinity_symmetric_weight, policy)
    per_scenario = _unify_batch(host_trees, n_snap_shards=nproc,
                                n_node_shards=n_node)

    # jax.devices() orders process 0's devices first, then process 1's, ...
    # so reshaping to (nproc, n_node) gives each process its own snap row
    mesh = make_mesh(nproc * n_node, snap=nproc)
    st_spec, ca_spec, xs_spec = snap_shardings(mesh)
    host_carries, host_statics, host_xs = _stack_host(per_scenario)

    def _global(full, sharding):
        return jax.make_array_from_callback(
            full.shape, sharding, lambda idx: full[idx])

    carries = jax.tree.map(_global, host_carries, ca_spec)
    statics_b = jax.tree.map(_global, host_statics, st_spec)
    xs_b = jax.tree.map(lambda a: _global(a, xs_spec), host_xs)

    replicate = jax.jit(lambda x: x,
                         out_shardings=NamedSharding(mesh, PartitionSpec()))
    with mesh:
        choices_b, counts_b = _batched(config, carries, statics_b, xs_b)
        # fully replicated -> every shard addressable on every process
        choices_b = np.asarray(replicate(choices_b))
        counts_b = np.asarray(replicate(counts_b))
    return _decode_batch(scenarios, compiled_list, choices_b, counts_b)

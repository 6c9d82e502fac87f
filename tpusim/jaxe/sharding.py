"""Multi-chip execution: shard the node axis (and the snapshot axis) over a
device mesh.

Design (SURVEY.md §5 "distributed communication backend"): the reference's
scaling axes are pods × nodes (16 goroutines per pod scan) and independent
cluster snapshots (the multi-tenant what-if). On TPU these map to:

  "node" mesh axis — node-column arrays ([N] carries, [sig, N] tables) are
      sharded over ICI; per-step reductions (max score, tie counts, cumsum
      ranks) become XLA collectives inserted by GSPMD — nothing hand-rolled.
  "snap" mesh axis — the 50-snapshot what-if (BASELINE.json config 5) is
      embarrassingly parallel: snapshots are batched on a leading axis and
      sharded across the mesh; zero cross-snapshot communication.

Single-host multi-chip and multi-host (ICI+DCN) use the same code path: a
jax.sharding.Mesh over jax.devices() — on multi-host, `jax.distributed` brings
up the fleet and the Mesh spans hosts, with XLA routing collectives over
ICI/DCN (this replaces the reference's in-process watch-event fabric; there is
no NCCL/MPI analog to port, SURVEY.md §2 note).

Axis placement is derived from the kernels.STATICS_AXES / CARRY_AXES
registries, so new state fields inherit padding + sharding automatically.
"""

from __future__ import annotations

import logging
import os
import re
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpusim.framework.metrics import register
from tpusim.jaxe.kernels import (
    CARRY_AXES,
    PAD_FILLS,
    PODX_AXES,
    STATICS_AXES,
    Carry,
    PodX,
    Statics,
    shard_route_eligible,
)
from tpusim.jaxe.trust import SHARD
from tpusim.obs import recorder as flight

log = logging.getLogger(__name__)


def _infeasible_sentinel():
    # computed lazily: jnp.int64 truncates to int32 before ensure_x64() runs
    return jnp.int64(1) << 62


def stage_tree(tree, sharding=None):
    """Stage a host-numpy pytree onto device — THE device_put shape shared
    by every staging site (mesh placement here, the what-if batcher, the
    preemption hybrid's re-arm, the serve executor, and the stream runtime's
    restage path):

      sharding=None         -> default-device commit (jnp.asarray per leaf)
      a single Sharding     -> that placement applied to every leaf
      a pytree of shardings -> leafwise jax.device_put (tree must match)
    """
    if sharding is None:
        return jax.tree.map(jnp.asarray, tree)
    if isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(lambda a: jax.device_put(a, sharding), tree)
    return jax.tree.map(jax.device_put, tree, sharding)


def _valid_shard_counts(n: int) -> list:
    """Divisors of n: the shard counts a leading mesh axis can take."""
    return [d for d in range(1, n + 1) if n % d == 0]


def _factoring_error(n: int, first_axis: str, first: int,
                     second_axis: str) -> ValueError:
    """Non-factoring mesh request: name BOTH axes the grid would have had
    and list the shard counts that do divide the device count — the error
    used to name only one axis and leave the caller to factor by hand."""
    return ValueError(
        f"{n} devices do not factor into a ({first_axis!r}, {second_axis!r}) "
        f"mesh with {first_axis}={first} ({second_axis} would not get a "
        f"whole number of devices); valid {first_axis} counts for "
        f"{n} devices: {_valid_shard_counts(n)}")


def make_mesh(n_devices: Optional[int] = None, snap: int = 1,
              devices: Optional[list] = None) -> Mesh:
    """A ("snap", "node") mesh over the first n_devices devices."""
    if devices is None:
        devices = jax.devices()
    devices = devices[: (n_devices or len(devices))]
    n = len(devices)
    if snap < 1 or n % snap != 0:
        raise _factoring_error(n, "snap", snap, "node")
    grid = np.array(devices).reshape(snap, n // snap)
    return Mesh(grid, ("snap", "node"))


def shard_count() -> int:
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
    if k > 1 and len(jax.devices()) < k:
        raise ValueError(
            f"TPUSIM_SHARDS={k} needs {k} devices; this process has "
            f"{len(jax.devices())} ({jax.devices()[0].platform})")
    return max(k, 1)


def shard_mesh(config, detail: str) -> Optional[Mesh]:
    """The node-sharded route's decision: a shard_count()-device node mesh
    when that count is above one, the route is not disabled by a failed
    verification (trust.SHARD) and the config is eligible. An ineligible
    config is a classified fallback to the single-device scan (the
    `shard_fallback` counter and a `fallback:shard_*` instant carrying
    `detail`), never a behaviour change: the sharded scan is bit-identical."""
    n = shard_count()
    if n <= 1 or not SHARD.mode()[0]:
        return None
    ok, why = shard_route_eligible(config)
    if not ok:
        register().shard_fallback.inc(why)
        flight.note_fast_fallback("shard_" + why,
                                  f"TPUSIM_SHARDS={n} {detail}")
        log.info("TPUSIM_SHARDS=%d %s (%s)", n, detail, why)
        return None
    return make_mesh(n, snap=1)


def make_scenario_mesh(n_devices: Optional[int] = None,
                       scenario: Optional[int] = None,
                       devices: Optional[list] = None) -> Mesh:
    """A ("scenario", "node") mesh for the shard_map what-if route: the
    scenario axis is partitioned MANUALLY (whatif._scenario_sharded), with
    node columns kept whole inside each shard — the per-step node reductions
    (argmax, tie counts, rank cumsum) stay shard-local instead of becoming
    collectives. `scenario` defaults to every visible device (node dim 1);
    a node dim > 1 replicates the manual program across node rows."""
    if devices is None:
        devices = jax.devices()
    devices = devices[: (n_devices or len(devices))]
    n = len(devices)
    scenario = scenario or n
    if scenario < 1 or n % scenario != 0:
        raise _factoring_error(n, "scenario", scenario, "node")
    grid = np.array(devices).reshape(scenario, n // scenario)
    return Mesh(grid, ("scenario", "node"))


def mesh_kind(mesh: Mesh) -> str:
    """Which what-if route a mesh selects: "snap" (GSPMD vmap: snapshot axis
    over "snap", node columns over "node") or "scenario" (manual shard_map
    over "scenario", node columns whole per shard). Anything else is a
    caller error surfaced here instead of as a KeyError inside dispatch."""
    names = tuple(mesh.axis_names)
    if names == ("snap", "node"):
        return "snap"
    if names == ("scenario", "node"):
        return "scenario"
    raise ValueError(
        f"what-if mesh has axes {names!r}; want ('snap', 'node') "
        "(make_mesh: GSPMD-sharded vmap) or ('scenario', 'node') "
        "(make_scenario_mesh: manual shard_map over scenarios)")


def match_partition_rules(rules: Sequence[Tuple[str, P]],
                          fields: Sequence[str],
                          prefix: str = "") -> Dict[str, P]:
    """Regex-rule PartitionSpec assignment (SNIPPETS [1] idiom): each field
    is matched as "prefix/name" against the rules in order; the first hit
    assigns its PartitionSpec, no hit means replicated (P()). Keeps the
    sharding story declarative as state trees grow fields."""
    out: Dict[str, P] = {}
    for name in fields:
        path = f"{prefix}/{name}" if prefix else name
        for pattern, spec in rules:
            if re.search(pattern, path):
                out[name] = spec
                break
        else:
            out[name] = P()
    return out


# The stacked what-if batch: every statics/carry/xs leaf gains a leading
# scenario axis in _stack_host, so every tree matches its prefix rule; the
# replicated default only catches future scalar/config leaves.
SCENARIO_BATCH_RULES: Tuple[Tuple[str, P], ...] = (
    (r"^statics/", P("scenario")),
    (r"^carry/", P("scenario")),
    (r"^xs/", P("scenario")),
)


def scenario_specs() -> Tuple[Carry, Statics, PodX]:
    """PartitionSpec trees (carry, statics, xs) for the shard_map what-if
    program, derived from the axis registries via the regex rules."""
    ca = match_partition_rules(SCENARIO_BATCH_RULES, CARRY_AXES, "carry")
    st = match_partition_rules(SCENARIO_BATCH_RULES, STATICS_AXES, "statics")
    xs = match_partition_rules(SCENARIO_BATCH_RULES, PODX_AXES, "xs")
    return Carry(**ca), Statics(**st), PodX(**xs)


def scenario_shardings(mesh: Mesh) -> Tuple[Carry, Statics, PodX]:
    """NamedSharding trees matching scenario_specs, for placing the stacked
    host batch so the shard_map program starts without a reshard."""
    ca, st, xs = scenario_specs()
    named = lambda tree: type(tree)(  # noqa: E731
        **{k: NamedSharding(mesh, v) for k, v in tree._asdict().items()})
    return named(ca), named(st), named(xs)


def _pad_to(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _pad_node_tree(tree, axes_map, pad: int):
    fields = {}
    for name, arr in tree._asdict().items():
        spec = axes_map[name]
        if "node" not in spec:
            fields[name] = arr
            continue
        # stay on host for numpy inputs (the what-if path pads before upload)
        xp = np if isinstance(arr, np.ndarray) else jnp
        ax = spec.index("node")
        widths = [(0, 0)] * arr.ndim
        widths[ax] = (0, pad)
        if name == "cond_fail_bits":
            sentinel = (np.int64(1) << 62) if xp is np else _infeasible_sentinel()
            fields[name] = xp.concatenate(
                [arr, xp.full(pad, sentinel, dtype=xp.int64)])
        else:
            fields[name] = xp.pad(arr, widths,
                                  constant_values=PAD_FILLS.get(name, 0))
    return type(tree)(**fields)


def pad_node_axis(statics: Statics, carry: Carry, n_shards: int
                  ) -> Tuple[Statics, Carry, int]:
    """Pad the node axis so it divides the mesh.

    Padded nodes are made permanently infeasible through a sentinel condition
    bit (bit 62): feasibility tests cond_fail_bits != 0, while the reason
    histogram only decodes bits [0, num_reason_bits), so the sentinel never
    shows up in failure messages and the padded nodes can never be selected.
    Returns the padded arrays plus the real node count."""
    n = statics.alloc_cpu.shape[0]
    padded = _pad_to(n, n_shards)
    pad = padded - n
    if pad == 0:
        return statics, carry, n
    return (_pad_node_tree(statics, STATICS_AXES, pad),
            _pad_node_tree(carry, CARRY_AXES, pad), n)


def pad_carry_node_axis(carry: Carry, n_shards: int) -> Carry:
    """Pad ONLY the carry's node axis to the mesh multiple (the preemption
    hybrid's re-arm path: statics were padded and placed at compile time and
    are reused; the fresh carry must match their padded node extent)."""
    name = next(n for n, spec in CARRY_AXES.items() if "node" in spec)
    ax = CARRY_AXES[name].index("node")
    n = getattr(carry, name).shape[ax]
    pad = _pad_to(n, n_shards) - n
    return carry if pad == 0 else _pad_node_tree(carry, CARRY_AXES, pad)


def _sharding_tree(tree_cls, axes_map, mesh: Mesh, leading: Optional[str] = None):
    fields = {}
    for name, spec in axes_map.items():
        parts = ([leading] if leading is not None else []) + [
            "node" if a == "node" else None for a in spec]
        fields[name] = NamedSharding(mesh, P(*parts))
    return tree_cls(**fields)


def node_shardings(mesh: Mesh) -> Tuple[Statics, Carry]:
    """NamedShardings for statics/carry pytrees: node axis sharded, signature
    and scalar axes replicated."""
    return (_sharding_tree(Statics, STATICS_AXES, mesh),
            _sharding_tree(Carry, CARRY_AXES, mesh))


def stage_node_sharded(mesh: Mesh, statics, carry):
    """The node-sharded route's staging (`shard:stage` span): pad the node
    axis shard-even, place statics and carry per the mesh's node
    shardings, and stamp the tpusim_shard_* layout gauges. Returns
    (statics, carry, layout)."""
    n_shards = mesh.shape["node"]
    with flight.span("shard:stage") as ssp:
        statics, carry, n_real = pad_node_axis(statics, carry, n_shards)
        st_sh, ca_sh = node_shardings(mesh)
        statics, carry = stage_tree(statics, st_sh), stage_tree(carry, ca_sh)
        if ssp:
            ssp.set("shards", n_shards)
            ssp.set("nodes", n_real)
    per = statics.alloc_cpu.shape[0] // n_shards
    m = register()
    m.shard_count.set(n_shards)
    for s in range(n_shards):
        m.shard_node_occupancy.set(str(s),
                                   max(0, min(n_real - s * per, per)))
    return statics, carry, {"shards": n_shards, "nodes": n_real,
                            "nodes_per_shard": per}


def shard_for_mesh(mesh: Mesh, statics: Statics, carry: Carry, xs: PodX
                   ) -> Tuple[Statics, Carry, PodX]:
    """Place arrays: node columns sharded over the "node" axis, pod columns
    replicated (every shard sees every pod; the per-pod work is the reduction
    over its node shard)."""
    n_node_shards = mesh.shape["node"]
    statics, carry, _ = pad_node_axis(statics, carry, n_node_shards)
    st_spec, ca_spec = node_shardings(mesh)
    statics = stage_tree(statics, st_spec)
    carry = stage_tree(carry, ca_spec)
    xs = stage_tree(xs, NamedSharding(mesh, P()))
    return statics, carry, xs


def snap_shardings(mesh: Mesh) -> Tuple[Statics, Carry, object]:
    """Shardings for the multi-snapshot what-if: leading snapshot axis sharded
    over "snap", node axis over "node"."""
    statics = _sharding_tree(Statics, STATICS_AXES, mesh, leading="snap")
    carry = _sharding_tree(Carry, CARRY_AXES, mesh, leading="snap")
    xs_sharding = NamedSharding(mesh, P("snap"))
    return statics, carry, xs_sharding

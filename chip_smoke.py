"""Bring-up check: the scheduler's device path on one TPU, end to end.

    python chip_smoke.py            # one chip: capacity + stream phases
    python chip_smoke.py --chips 4  # four chips: the node-sharded batch only

One process owns the chip(s); everything is generated from --seed. It exits
1, printing no result, when JAX finds no TPU: there is no CPU fallback.

Capacity: 150,000 Zipf-sized pods onto 5,000 heterogeneous nodes (110 pods
per node at most: the upstream Kubernetes scalability envelope, "Considerations
for large clusters") through run_simulation(backend="jax"), once on the
chunked XLA scan (TPUSIM_FAST=0) and once on the compiled Pallas kernel
(TPUSIM_FAST=1). Both full-batch placement hashes must agree, and the first
200 pods must agree with ReferenceBackend, the host engine.

Stream: run_stream_simulation on the same cluster, 10 cycles of 1,000
arrivals with 25% evictions, every cycle verified against a fresh compile,
and a live what-if overlay query every second cycle.

--chips 4 runs the capacity batch on the one-chip XLA route, then twice on
the node-sharded route (TPUSIM_SHARDS=4); the three hashes must agree.

Every phase prints one JSON line (wall and compile time, routes, hash, pods
scheduled, peak device bytes) and fails on any fallback the flight recorder
or the fallback counter saw. The last line is the device record:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

NODES = 5_000
PODS = 150_000
REFERENCE_PODS = 200
STREAM = dict(cycles=10, arrivals=1_000, evict_fraction=0.25,
              whatif_every=2, whatif_pods=64)

# route and AUTO-transition names that mean the device path was avoided or
# distrusted (recorder instants "route:<name>" and "auto:<name>")
BAD_EVENTS = {"route:reference_fallback", "route:breaker_fallback",
              "route:fastscan_interpret", "route:overlay_fallback",
              "auto:verify_fail", "auto:shard_verify_fail"}

_COMPILE = {"seconds": 0.0, "count": 0}


def _on_duration(event: str, seconds: float, **_) -> None:
    if event.startswith("/jax/core/compile/"):
        _COMPILE["seconds"] += seconds
        if event.endswith("backend_compile_duration"):
            _COMPILE["count"] += 1


def require_tpu():
    """The devices, or exit 1 when JAX finds no TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "this check runs on the chip only", file=sys.stderr)
        sys.exit(1)
    return devices


class Phase:
    """One phase: wall clock, compile time, a fresh flight recorder and
    metrics window; fail() raises on any fallback either one saw."""

    def __init__(self, name: str, route: str = None):
        self.name = name
        self.route = route  # the one batch route the phase must take
        self.info: dict = {}

    def __enter__(self):
        from tpusim.framework.metrics import register
        from tpusim.obs import recorder as flight

        register().reset()
        self.rec = flight.install(flight.FlightRecorder(
            process_name=f"chip_smoke:{self.name}"))
        self.compile0 = dict(_COMPILE)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *_):
        from tpusim.framework.metrics import register
        from tpusim.obs import recorder as flight

        wall = time.perf_counter() - self.t0
        flight.uninstall()
        if exc_type is not None:
            return False
        names = [ev.get("name", "") for ev in self.rec.events]
        routes = sorted({n[len("route:"):] for n in names
                         if n.startswith("route:")})
        bad = sorted({n for n in names if n in BAD_EVENTS
                      or n.startswith("auto:discard")
                      or n.startswith("fallback:")})
        fast_fallbacks = register().fast_fallback.values
        line = {"phase": self.name, "wall_s": wall,
                "compile_s": _COMPILE["seconds"] - self.compile0["seconds"],
                "compiles": _COMPILE["count"] - self.compile0["count"],
                "routes": routes, **self.info,
                "peak_bytes_in_use": _peak_bytes()}
        print(json.dumps(line), flush=True)
        if self.route is not None and routes != [self.route]:
            bad.append(f"routes {routes}, expected [{self.route}]")
        if bad or any(fast_fallbacks.values()):
            raise SystemExit(f"chip_smoke: phase {self.name} left the "
                             f"device path: {bad} {dict(fast_fallbacks)}")
        return False


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def status_hash(status) -> str:
    """Digest of a run_simulation Status: every pod with its node, in the
    order the scheduler decided them."""
    h = hashlib.sha256()
    for p in status.successful_pods:
        h.update(f"{p.metadata.name}\x00{p.spec.node_name}\n".encode())
    for p in status.failed_pods:
        h.update(f"{p.metadata.name}\x00\n".encode())
    return h.hexdigest()[:16]


def capacity(name: str, snapshot, pods, env: dict, route: str):
    """run_simulation(backend="jax") on the whole batch under `env`, which
    must take `route` and no other; returns the Status."""
    from tpusim.simulator import run_simulation

    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        with Phase(name, route) as ph:
            status = run_simulation(list(pods), snapshot, backend="jax")
            ph.info = {"pods": len(pods), "nodes": len(snapshot.nodes),
                       "scheduled": len(status.successful_pods),
                       "hash": status_hash(status)}
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    if not status.successful_pods:
        raise SystemExit(f"chip_smoke: {name} scheduled nothing")
    return status


def reference_prefix(snapshot, pods, status, n: int) -> None:
    """The first n decisions agree with ReferenceBackend, the host engine
    (run_simulation decides the pod list last-first)."""
    from tpusim.backends import ReferenceBackend

    feed = list(reversed(pods))[:n]
    want = ReferenceBackend().schedule(feed, snapshot)
    got = {p.metadata.name: p.spec.node_name
           for p in status.successful_pods}
    bad = [p.pod.metadata.name for p in want
           if (p.node_name or "") != got.get(p.pod.metadata.name, "")]
    print(json.dumps({"phase": "reference_prefix", "pods": n,
                      "mismatches": len(bad)}), flush=True)
    if bad:
        raise SystemExit(f"chip_smoke: {len(bad)} of the first {n} pods "
                         f"differ from the host engine, e.g. {bad[:5]}")


def stream(snapshot, seed: int) -> None:
    from tpusim.simulator import run_stream_simulation

    with Phase("stream") as ph:
        out = run_stream_simulation(snapshot, seed=seed, verify=True,
                                    **STREAM)
        ph.info = {"cycles": out["cycles"], "decisions": out["decisions"],
                   "scheduled": out["scheduled"],
                   "verified": out["verified"],
                   "mismatched_cycles": out["mismatched_cycles"],
                   "paths": out["paths"], "overlay": out["overlay"],
                   "hash": out["placement_chain"][:16]}
    ov = out["overlay"]
    if not out["verified"] or out["cycles"] != STREAM["cycles"]:
        raise SystemExit(f"chip_smoke: stream verify failed on "
                         f"{out['mismatched_cycles']} cycle(s)")
    if ov["fallbacks"] or ov["answered"] != ov["queries"] \
            or not ov["queries"]:
        raise SystemExit(f"chip_smoke: overlay queries not all answered "
                         f"on the twin: {ov}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = require_tpu()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    import importlib.metadata

    import jax
    import jax.monitoring

    import tpusim.jaxe  # noqa: F401  (places the compile cache)
    from tpusim.api.snapshot import zipf_workload

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(json.dumps({
        "device_kind": devices[0].device_kind, "devices": len(devices),
        "jax": jax.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
        "compile_cache": jax.config.jax_compilation_cache_dir}), flush=True)

    t0 = time.perf_counter()
    snapshot, pods = zipf_workload(PODS, NODES, seed=args.seed)
    print(json.dumps({"phase": "generate", "wall_s": time.perf_counter() - t0,
                      "pods": len(pods), "nodes": len(snapshot.nodes)}),
          flush=True)

    xla = capacity("capacity_xla", snapshot, pods, {"TPUSIM_FAST": "0"},
                   "xla_chunked")
    if args.chips == 4:
        hashes = {status_hash(xla)}
        for run in (1, 2):
            hashes.add(status_hash(capacity(
                f"capacity_sharded_{run}", snapshot, pods,
                {"TPUSIM_FAST": "0", "TPUSIM_SHARDS": "4"},
                "xla_sharded_chunked")))
        if len(hashes) != 1:
            raise SystemExit(f"chip_smoke: sharded and one-chip placements "
                             f"differ: {sorted(hashes)}")
    else:
        reference_prefix(snapshot, pods, xla, REFERENCE_PODS)
        fast = capacity("capacity_pallas", snapshot, pods,
                        {"TPUSIM_FAST": "1"}, "fastscan")
        if status_hash(fast) != status_hash(xla):
            raise SystemExit("chip_smoke: the Pallas kernel and the XLA "
                             "scan placed the batch differently")
        stream(snapshot, args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

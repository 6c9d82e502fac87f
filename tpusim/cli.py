"""Command-line entry.

Reference: cmd/app/server.go + cmd/app/options/options.go. Flag surface kept
(--kubeconfig --podspec --algorithmprovider), extended per BASELINE.json with
--backend, plus snapshot sources replacing the live-cluster
List (this environment has no kube apiserver): --snapshot / --nodes / --pods /
--synthetic-nodes.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

from tpusim.api.podspec import expand_simulation_pods, load_simulation_pods
from tpusim.api.snapshot import (
    ClusterSnapshot,
    load_nodes_checkpoint,
    load_pods_checkpoint,
    synthetic_cluster,
)
from tpusim.framework.report import (
    cluster_capacity_review_print,
    get_report,
    spec_print,
)
from tpusim.simulator import run_simulation


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="tpusim",
        description="Cluster-capacity schedule simulation on a TPU-native engine")
    # reference flags (options.go:67-71)
    parser.add_argument("--kubeconfig", default="",
                        help="Path to kubeconfig for a live-cluster snapshot "
                             "(Running pods across all namespaces + all nodes, "
                             "server.go:104-118); CC_INCLUSTER=1 uses the "
                             "in-cluster service-account config instead")
    parser.add_argument("--podspec", default="",
                        help="YAML/JSON file with [{name, pod, num}] entries")
    parser.add_argument("--algorithmprovider", default="DefaultProvider",
                        help="DefaultProvider | ClusterAutoscalerProvider | "
                             "TalkintDataProvider")
    # AlgorithmSource.Policy analog (simulator.go:383-424): policy from a
    # serialized file, or from a ConfigMap object saved as JSON/YAML
    parser.add_argument("--scheduler-policy-file", default="",
                        help="schedulerapi/v1 Policy file (kind: Policy) "
                             "overriding the algorithm provider")
    parser.add_argument("--scheduler-policy-configmap-file", default="",
                        help="ConfigMap object (JSON/YAML) carrying the policy "
                             "under data['policy.cfg']")
    parser.add_argument("--scheduler-policy-configmap", default="",
                        help="Name of a ConfigMap to fetch the policy from the "
                             "live cluster API (simulator.go:402-415); needs "
                             "--kubeconfig or CC_INCLUSTER")
    parser.add_argument("--scheduler-policy-configmap-namespace",
                        default="kube-system",
                        help="Namespace of --scheduler-policy-configmap")
    parser.add_argument("--namespace", default="default",
                        help="Namespace stamped onto simulated pods")
    # new flags (BASELINE.json)
    parser.add_argument("--backend", default="auto",
                        choices=["auto", "reference", "jax"],
                        help="Scheduling engine: jax (TPU batched), reference "
                             "(pure-Python parity loop), or auto (default — "
                             "workloads under TPUSIM_AUTO_THRESHOLD pods x "
                             "nodes [100k] run on the host engine, avoiding "
                             "device-dispatch latency on tiny runs; larger "
                             "ones use the jax engine)")
    # snapshot sources
    parser.add_argument("--snapshot", default="",
                        help="Combined ClusterSnapshot JSON ({nodes, pods, services})")
    parser.add_argument("--nodes", default="", help="nodes.json checkpoint")
    parser.add_argument("--pods", default="", help="pods.json checkpoint (Running pods)")
    parser.add_argument("--synthetic-nodes", type=int, default=0,
                        help="Generate N homogeneous synthetic nodes")
    parser.add_argument("--synthetic-milli-cpu", type=int, default=4000)
    parser.add_argument("--synthetic-memory", type=int, default=16 * 1024**3)
    parser.add_argument("--event-log", default="",
                        help="Watch-event log (JSON lines, the WatchBuffer "
                             "wire frames: {type: Added|Modified|Deleted, "
                             "object: {kind: Pod|Node|Service, ...}}) "
                             "replayed on top of the snapshot before "
                             "scheduling; on the jax backend the replay "
                             "drives incremental column-cache updates")
    parser.add_argument("--what-if", default="",
                        help="Manifest JSON [{snapshot, podspec}, ...]: run "
                             "all scenarios as ONE batched device program "
                             "(jax backend; snapshot axis shardable over a "
                             "mesh). Ignores --podspec/--snapshot.")
    parser.add_argument("--mesh", default="",
                        help="What-if device mesh 'SNAPxNODE' (e.g. 2x4): "
                             "scenarios data-parallel over SNAP devices, "
                             "node columns sharded over NODE devices with "
                             "GSPMD collectives (jaxe/sharding.py). Needs "
                             "SNAP*NODE visible jax devices; default "
                             "single-device.")
    parser.add_argument("--chaos-plan", default="",
                        help="Fault-plan JSON (tpusim.chaos schema: churn/"
                             "fabric/device sections) injected into the run; "
                             "the summary line reports invariant violations "
                             "and a non-empty audit exits 1")
    parser.add_argument("--chaos-seed", type=int, default=None,
                        help="Generate a seeded adversarial fault plan "
                             "against the loaded workload instead of (or "
                             "overriding the seed of) --chaos-plan; "
                             "deterministic per seed")
    parser.add_argument("--enable-pod-priority", action="store_true",
                        help="Enable the PodPriority feature gate (preemption). "
                             "On the jax backend this runs the host-device "
                             "hybrid: device scan + exact host Preempt pipeline")
    parser.add_argument("--enable-volume-scheduling", action="store_true",
                        help="Enable the VolumeScheduling feature gate "
                             "(CheckVolumeBinding + delayed PV binding); "
                             "reference backend only")
    parser.add_argument("--feature-gates", default="",
                        help="Comma-separated key=bool feature gates "
                             "(kube --feature-gates format): "
                             "TaintNodesByCondition, "
                             "ResourceLimitsPriorityFunction (registry "
                             "surgery, defaults.go:181-205), plus "
                             "PodPriority / VolumeScheduling as aliases "
                             "for the dedicated flags")
    parser.add_argument("--platform", default=os.environ.get("TPUSIM_PLATFORM", ""),
                        help="Pin the jax platform (e.g. cpu); default: "
                             "JAX's own choice (TPUSIM_PLATFORM env)")
    parser.add_argument("--print-requirements", action="store_true",
                        help="Also print per-pod requirement spec")
    parser.add_argument("--quiet", action="store_true",
                        help="Only print the summary counts and timing")
    parser.add_argument("--v", type=int, default=0, dest="verbosity",
                        help="Log verbosity (glog analog). >=2 surfaces the "
                             "tpusim.* loggers on stderr (slow-schedule "
                             "traces, backend routing); >=5 enables DEBUG "
                             "plus the per-node score dump: every priority's "
                             "score per node and the post-extender aggregate "
                             "(generic_scheduler.go:618-622,670-674)")
    parser.add_argument("--trace-out", default="",
                        help="Write the flight-recorder timeline after the "
                             "run: Chrome trace_event JSON (Perfetto-"
                             "loadable) by default, or a raw span stream "
                             "with a .jsonl extension")
    parser.add_argument("--metrics-out", default="",
                        help="Write the scheduler metrics registry in "
                             "Prometheus text exposition format after the "
                             "run")
    add_explain_flags(parser)
    parser.add_argument("--analytics-out", default="",
                        help="Append cluster-analytics samples (reduced "
                             "on-device from the final scan carry) to this "
                             "JSONL file")
    return parser


def add_explain_flags(parser: argparse.ArgumentParser) -> None:
    """The decision-provenance flag pair, shared by the one-shot, serve,
    and stream entrypoints."""
    parser.add_argument("--explain-out", default="",
                        help="Append decision-provenance records (one JSON "
                             "object per pod decision: why placed / why "
                             "not, with failure text byte-identical to the "
                             "host FitError) to this JSONL file; query it "
                             "with `tpusim explain FILE`")
    parser.add_argument("--explain-top-k", type=int, default=0,
                        help="Also record the top-K candidate nodes per "
                             "placed pod with each one's per-priority score "
                             "breakdown (jax backend one-shot runs; routes "
                             "through the XLA scan). 0 = failures-only "
                             "provenance")


def add_obs_flags(parser: argparse.ArgumentParser) -> None:
    """The live-telemetry flag pair, shared by serve and stream."""
    parser.add_argument("--listen", default="",
                        help="Serve the live telemetry plane on HOST:PORT "
                             "(also ':PORT' or 'PORT'): GET /metrics "
                             "(Prometheus/OpenMetrics text), /healthz "
                             "(JSON liveness; 503 while the dispatch "
                             "breaker is open), /debug/provenance (recent "
                             "decision records)")
    parser.add_argument("--slo-target-ms", type=float, default=0.0,
                        help="Arm the per-cycle latency SLO at this target: "
                             "publishes tpusim_slo_cycles_total{verdict} "
                             "and tpusim_slo_burn_rate, and drops "
                             "slo:burn_start/_end instants on the flight "
                             "recorder at burn-rate crossings (0: off)")
    parser.add_argument("--analytics-out", default="",
                        help="Append cluster-analytics samples (one JSON "
                             "object per cycle/dispatch: per-resource "
                             "utilization/fragmentation, feasible-node "
                             "count, top-k hot/cold nodes, reduced "
                             "on-device) to this JSONL file")


def _arm_observability(args):
    """Install the provenance log, SLO tracker, and telemetry endpoint the
    flags ask for; returns a teardown callable (flushes --explain-out)."""
    from tpusim.obs import analytics, provenance, slo

    server = None
    listen = getattr(args, "listen", "")
    explain_out = getattr(args, "explain_out", "")
    explain_top_k = max(0, getattr(args, "explain_top_k", 0))
    slo_target_ms = getattr(args, "slo_target_ms", 0.0)
    analytics_out = getattr(args, "analytics_out", "")
    # --listen without --explain-out still arms an in-memory ring so
    # /debug/provenance serves the recent decisions
    if explain_out or explain_top_k or listen:
        provenance.install(provenance.ProvenanceLog(
            top_k=explain_top_k, path=explain_out or None))
    # likewise --listen alone arms the analytics ring so /analytics (and
    # `tpusim top` against this endpoint) serves live samples
    if analytics_out or listen:
        analytics.install(analytics.ClusterAnalytics(
            path=analytics_out or None))
    if slo_target_ms and slo_target_ms > 0:
        slo.install(slo.SloTracker(slo_target_ms * 1000.0))
    if listen:
        from tpusim.obs.server import start_server

        server = start_server(listen)
        host, port = server.address
        print(f"telemetry: listening on http://{host}:{port} "
              "(/metrics /healthz /debug/provenance /analytics)",
              file=sys.stderr)

    def teardown() -> None:
        if provenance.get_log() is not None:
            provenance.uninstall()   # close() flushes --explain-out
        if analytics.get() is not None:
            # pin the final sample into the tpusim_cluster_* gauges so a
            # post-teardown --metrics-out dump carries it
            analytics.refresh_gauges()
            analytics.uninstall()    # close() flushes --analytics-out
        if slo.get_tracker() is not None:
            slo.uninstall()
        if server is not None:
            server.stop()

    return teardown


def load_snapshot(args) -> ClusterSnapshot:
    if args.kubeconfig or os.environ.get("CC_INCLUSTER"):
        if args.snapshot or args.nodes or args.pods or args.synthetic_nodes:
            raise ValueError(
                "--kubeconfig/CC_INCLUSTER conflicts with "
                "--snapshot/--nodes/--pods/--synthetic-nodes; pick one "
                "snapshot source")
        # the reference's only real network I/O: the initial checkpoint
        # (server.go:75-118); its Namespace field is never flag-bound, so the
        # pod list always spans all namespaces — --namespace here only stamps
        # the simulated pods
        from tpusim.api.kubeclient import snapshot_from_cluster

        return snapshot_from_cluster(kubeconfig=args.kubeconfig)
    if args.snapshot:
        return ClusterSnapshot.load(args.snapshot)
    snapshot = ClusterSnapshot()
    if args.nodes:
        snapshot.nodes = load_nodes_checkpoint(args.nodes)
    elif args.synthetic_nodes:
        snapshot.nodes = synthetic_cluster(
            args.synthetic_nodes, milli_cpu=args.synthetic_milli_cpu,
            memory=args.synthetic_memory).nodes
    if args.pods:
        snapshot.pods = load_pods_checkpoint(args.pods)
    return snapshot


def load_policy_from_args(args):
    """(policy | None, error string | None) from the three policy sources:
    serialized Policy file, ConfigMap-object file, or a live ConfigMap fetched
    from the cluster API (simulator.go:383-424)."""
    live_name = getattr(args, "scheduler_policy_configmap", "")
    if not (args.scheduler_policy_file or args.scheduler_policy_configmap_file
            or live_name):
        return None, None
    from tpusim.engine.policy import (
        PolicyError,
        load_policy_configmap_file,
        load_policy_file,
        policy_from_configmap,
    )
    try:
        if args.scheduler_policy_file:
            policy = load_policy_file(args.scheduler_policy_file)
        elif args.scheduler_policy_configmap_file:
            policy = load_policy_configmap_file(
                args.scheduler_policy_configmap_file)
        else:
            # live source: ConfigMaps(ns).Get(name) through the kube client
            # (simulator.go:402-406)
            if not (args.kubeconfig or os.environ.get("CC_INCLUSTER")):
                return None, ("--scheduler-policy-configmap needs a cluster "
                              "connection (--kubeconfig or CC_INCLUSTER)")
            from tpusim.api.kubeclient import (
                KubeClient,
                in_cluster_config,
                load_kubeconfig,
            )
            config = (load_kubeconfig(args.kubeconfig) if args.kubeconfig
                      else in_cluster_config())
            try:
                client = KubeClient(config)
            finally:
                config.cleanup()
            ns = args.scheduler_policy_configmap_namespace
            try:
                obj = client.get_configmap(ns, live_name)
            except OSError as exc:
                return None, (f"couldn't get policy config map "
                              f"{ns}/{live_name}: {exc}")
            policy = policy_from_configmap(obj)
    except (OSError, PolicyError) as exc:
        return None, f"invalid scheduler policy: {exc}"
    return policy, None


def run_what_if_cli(args) -> int:
    """Batched multi-snapshot mode (BASELINE.json config 5)."""
    import json

    from tpusim.jaxe.whatif import run_what_if

    if args.verbosity >= 5:
        print("note: the per-node score dump (--v 5) is produced by the "
              "host engine; --what-if always runs the batched device "
              "program and emits no dump.", file=sys.stderr)

    try:
        with open(args.what_if) as f:
            manifest = json.load(f)
        if not isinstance(manifest, list) or not manifest:
            raise ValueError("manifest must be a non-empty JSON list")
        scenarios = []
        for entry in manifest:
            snapshot = ClusterSnapshot.load(entry["snapshot"])
            sim_pods = load_simulation_pods(entry["podspec"])
            pods = expand_simulation_pods(sim_pods, namespace=args.namespace)
            # match run_simulation's LIFO feed order
            scenarios.append((snapshot, list(reversed(pods))))
    except (OSError, KeyError, TypeError, ValueError) as exc:
        print(f"error: invalid what-if manifest: {exc}", file=sys.stderr)
        return 2

    policy, policy_err = load_policy_from_args(args)
    if policy_err:
        print(f"error: {policy_err}", file=sys.stderr)
        return 2

    mesh = None
    if args.mesh:
        import jax

        from tpusim.jaxe.sharding import make_mesh

        try:
            snap_s, _, node_s = args.mesh.lower().partition("x")
            snap, node = int(snap_s), int(node_s)
            if snap < 1 or node < 1:
                raise ValueError
        except ValueError:
            print(f"error: --mesh {args.mesh!r}: want 'SNAPxNODE', e.g. 2x4",
                  file=sys.stderr)
            return 2
        have = len(jax.devices())
        if snap * node > have:
            print(f"error: --mesh {args.mesh} needs {snap * node} devices, "
                  f"{have} visible", file=sys.stderr)
            return 2
        mesh = make_mesh(snap * node, snap=snap)

    start = time.perf_counter()
    try:
        results = run_what_if(scenarios, provider=args.algorithmprovider,
                              policy=policy, mesh=mesh)
    except (KeyError, ValueError, NotImplementedError) as exc:
        # KeyError: unknown provider/plugin name; ValueError incl. PolicyError
        # from compile_policy's validation — same contract as the single-run
        # path's build-time error arm
        print(f"error: {exc}", file=sys.stderr)
        return 2
    elapsed = time.perf_counter() - start
    total = sum(r.total for r in results)
    for i, result in enumerate(results):
        print(f"scenario {i}: {result.scheduled} scheduled, "
              f"{result.unschedulable} unschedulable")
    rate = total / elapsed if elapsed > 0 else 0.0
    print(f"\n{len(results)} scenarios, {total} pods in one batched dispatch "
          f"[{elapsed:.3f}s, {rate:.0f} pods/s]")
    return 0


def build_serve_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="tpusim serve",
        description="Scenario fleet: run the what-if capacity service over "
                    "a snapshot and drive it with a synthetic request load "
                    "(tpusim/serve; in-process — no network listener)")
    parser.add_argument("--snapshot", default="",
                        help="Combined ClusterSnapshot JSON ({nodes, pods})")
    parser.add_argument("--nodes", default="", help="nodes.json checkpoint")
    parser.add_argument("--synthetic-nodes", type=int, default=0,
                        help="Generate N homogeneous synthetic nodes")
    parser.add_argument("--synthetic-milli-cpu", type=int, default=4000)
    parser.add_argument("--synthetic-memory", type=int, default=16 * 1024**3)
    parser.add_argument("--podspec", required=True,
                        help="YAML/JSON [{name, pod, num}] entries: the pod "
                             "pool the load generator draws request "
                             "workloads from")
    parser.add_argument("--algorithmprovider", default="DefaultProvider")
    parser.add_argument("--scheduler-policy-file", default="",
                        help="schedulerapi/v1 Policy file applied to every "
                             "request")
    parser.add_argument("--namespace", default="default")
    parser.add_argument("--requests", type=int, default=32,
                        help="Synthetic what-if requests to generate")
    parser.add_argument("--seed", type=int, default=0,
                        help="Load-generator seed (request sizes)")
    parser.add_argument("--bucket-size", type=int, default=4,
                        help="Scenarios per dispatched device program")
    parser.add_argument("--flush-after-ms", type=float, default=50.0,
                        help="Deadline before a partial bucket dispatches "
                             "ghost-padded")
    parser.add_argument("--deadline-ms", type=float, default=0.0,
                        help="Fleet-wide request deadline: a request older "
                             "than this at staging or bucket time is "
                             "rejected REJECT_DEADLINE instead of run "
                             "(0: no deadline)")
    parser.add_argument("--chaos-plan", default="",
                        help="Fault-plan JSON, device section only: scripted "
                             "dispatch faults behind the serve retry + "
                             "circuit-breaker + host-fallback path")
    parser.add_argument("--max-queue", type=int, default=256,
                        help="Admission queue bound (backpressure)")
    parser.add_argument("--warm-repeats", type=int, default=1,
                        help="Extra passes over the same request set: repeat "
                             "traffic must ride the warm-executable and "
                             "device-batch caches")
    parser.add_argument("--mesh", default="",
                        help="Scenario mesh 'SCENARIOxNODE' (e.g. 8x1) or "
                             "just 'SCENARIO': shard each bucket over the "
                             "mesh's scenario axis with shard_map "
                             "(make_scenario_mesh); bucket size must divide "
                             "over it")
    parser.add_argument("--attach-stream", action="store_true",
                        help="Live-twin serving (ISSUE 19): hold the cluster "
                             "device-resident in a StreamSession, warm it "
                             "with a few churn cycles, and answer requests "
                             "through copy-on-write overlay queries on the "
                             "resident carry — zero per-request staging; "
                             "the staged pipeline stays armed as fallback")
    parser.add_argument("--stream-cycles", type=int, default=4,
                        help="Churn warm-up cycles for --attach-stream")
    parser.add_argument("--stream-arrivals", type=int, default=16,
                        help="Arrivals per --attach-stream warm-up cycle")
    parser.add_argument("--platform",
                        default=os.environ.get("TPUSIM_PLATFORM", ""))
    parser.add_argument("--quiet", action="store_true",
                        help="Only print the summary lines")
    parser.add_argument("--metrics-out", default="",
                        help="Write the tpusim_serve_* metric families "
                             "(Prometheus text format) after the run")
    parser.add_argument("--trace-out", default="",
                        help="Write the serve: span timeline (Chrome trace "
                             "JSON, or .jsonl for raw spans)")
    add_obs_flags(parser)
    add_explain_flags(parser)
    return parser


def _percentile(sorted_vals, q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def serve_cli(argv) -> int:
    """`tpusim serve`: stand up a ScenarioFleet and load-generate against it."""
    import random

    args = build_serve_parser().parse_args(argv)
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    # snapshot source (load_snapshot's flag subset; no live cluster, no
    # running-pods checkpoint — the fleet schedules synthetic pods only)
    try:
        if args.snapshot:
            snapshot = ClusterSnapshot.load(args.snapshot)
        elif args.nodes:
            snapshot = ClusterSnapshot(nodes=load_nodes_checkpoint(args.nodes))
        elif args.synthetic_nodes:
            snapshot = synthetic_cluster(
                args.synthetic_nodes, milli_cpu=args.synthetic_milli_cpu,
                memory=args.synthetic_memory)
        else:
            print("error: no cluster nodes; pass --snapshot, --nodes, or "
                  "--synthetic-nodes", file=sys.stderr)
            return 2
        sim_pods = load_simulation_pods(args.podspec)
    except (OSError, ValueError) as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2
    pool = expand_simulation_pods(sim_pods, namespace=args.namespace)
    if not pool:
        print("error: podspec expands to zero pods", file=sys.stderr)
        return 2

    policy = None
    if args.scheduler_policy_file:
        from tpusim.engine.policy import PolicyError, load_policy_file

        try:
            policy = load_policy_file(args.scheduler_policy_file)
        except (OSError, PolicyError) as exc:
            print(f"error: invalid scheduler policy: {exc}", file=sys.stderr)
            return 2

    mesh = None
    if args.mesh:
        import jax

        from tpusim.jaxe.sharding import make_scenario_mesh

        try:
            scen_s, _, node_s = args.mesh.lower().partition("x")
            scen, node = int(scen_s), int(node_s or 1)
            if scen < 1 or node < 1:
                raise ValueError
        except ValueError:
            print(f"error: --mesh {args.mesh!r}: want 'SCENARIOxNODE' "
                  "(e.g. 8x1) or 'SCENARIO'", file=sys.stderr)
            return 2
        have = len(jax.devices())
        if scen * node > have:
            print(f"error: --mesh {args.mesh} needs {scen * node} devices, "
                  f"{have} visible", file=sys.stderr)
            return 2
        mesh = make_scenario_mesh(scen * node, scenario=scen)

    breaker = None
    if args.chaos_plan:
        from tpusim.chaos import load_plan
        from tpusim.chaos.plan import PlanError
        from tpusim.jaxe.backend import install_chaos

        try:
            chaos_plan = load_plan(args.chaos_plan)
        except (OSError, PlanError, ValueError) as exc:
            print(f"error: --chaos-plan: {exc}", file=sys.stderr)
            return 2
        breaker = install_chaos(chaos_plan.device)

    recorder = None
    if args.trace_out:
        from tpusim.obs import recorder as flight

        recorder = flight.install(
            flight.FlightRecorder(process_name="tpusim-serve"))

    from tpusim.serve import ScenarioFleet, WhatIfRequest

    try:
        fleet = ScenarioFleet(provider=args.algorithmprovider,
                              bucket_size=args.bucket_size,
                              flush_after_s=args.flush_after_ms / 1000.0,
                              max_queue=args.max_queue, mesh=mesh,
                              deadline_s=(args.deadline_ms / 1000.0
                                          if args.deadline_ms > 0 else None))
    except (KeyError, ValueError) as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2
    ref = "base"
    if args.attach_stream:
        # live-twin serving: the fleet answers against a device-resident
        # StreamSession's carry via overlay queries instead of staging a
        # fresh device picture per request (ISSUE 19). Fresh object
        # graphs per consumer — the twin and the churn generator must
        # never share mutable nodes with each other or the pod pool.
        from tpusim.stream import ChurnLoadGen, StreamSession

        twin_snap = ClusterSnapshot.from_obj(snapshot.to_obj())
        session = StreamSession(twin_snap, provider=args.algorithmprovider,
                                policy=policy)
        sgen = ChurnLoadGen(ClusterSnapshot.from_obj(snapshot.to_obj()),
                            seed=args.seed, arrivals=args.stream_arrivals,
                            evict_fraction=0.25)
        for c in range(max(1, args.stream_cycles)):
            session.apply_events(sgen.events(c))
            sgen.note_bound(session.schedule(sgen.batch()))
        fleet.attach_stream(session, ref="live")
        ref = "live"
        if not args.quiet:
            print(f"live twin: {max(1, args.stream_cycles)} warm-up churn "
                  f"cycles over {len(twin_snap.nodes)} nodes; overlay path "
                  "armed (staged fallback behind it)", file=sys.stderr)
    else:
        fleet.register_snapshot("base", snapshot)

    # the load: random-size what-if queries drawn from the pod pool, each
    # cache-keyed so warm repeats exercise the staged + device-batch caches
    rng = random.Random(args.seed)
    sizes = [rng.randint(1, len(pool)) for _ in range(args.requests)]
    make_load = lambda: [  # noqa: E731
        WhatIfRequest(pods=pool[:n], snapshot_ref=ref, policy=policy,
                      cache_key=f"load-{i}-{n}")
        for i, n in enumerate(sizes)]

    obs_teardown = _arm_observability(args)
    fleet.start()
    try:
        passes = []  # (label, elapsed, responses)
        for rep in range(1 + max(0, args.warm_repeats)):
            label = "cold" if rep == 0 else f"warm {rep}"
            start = time.perf_counter()
            futures = [fleet.submit(r) for r in make_load()]
            responses = [f.result(timeout=600) for f in futures]
            passes.append((label, time.perf_counter() - start, responses))
    finally:
        fleet.stop()
        obs_teardown()
        if breaker is not None:
            from tpusim.jaxe.backend import uninstall_chaos

            uninstall_chaos()

    stats = fleet.executor.stats
    exit_code = 0
    for label, elapsed, responses in passes:
        ok = [r for r in responses if r.ok]
        rejected = [r for r in responses if r.rejected is not None]
        errors = [r for r in responses if r.error and r.rejected is None]
        lat = sorted(r.latency_s for r in ok)
        rate = len(responses) / elapsed if elapsed > 0 else 0.0
        hits = sum(1 for r in ok if r.compile_cache_hit)
        degraded = sum(1 for r in ok if r.degraded)
        print(f"{label}: {len(ok)}/{len(responses)} ok "
              f"({len(rejected)} rejected, {len(errors)} failed"
              + (f", {degraded} degraded" if degraded else "") + "), "
              f"{rate:.1f} scenarios/s, latency p50/p90/max "
              f"{_percentile(lat, 0.5) * 1e3:.1f}/"
              f"{_percentile(lat, 0.9) * 1e3:.1f}/"
              f"{(lat[-1] if lat else 0.0) * 1e3:.1f} ms, "
              f"compile_cache_hit {hits}/{len(ok)}")
        if not args.quiet:
            for r in rejected[:5]:
                print(f"  rejected {r.request_id}: [{r.rejected}] {r.error}",
                      file=sys.stderr)
            for r in errors[:5]:
                print(f"  failed {r.request_id}: {r.error}", file=sys.stderr)
        if errors:
            exit_code = 1
    print(f"fleet: {stats['dispatches']} dispatches "
          f"({stats['warm_hits']} warm, {stats['device_batch_hits']} "
          f"device-resident), {stats['traces']} program traces, "
          f"{stats['staged_hits']} staged-cache hits"
          + (f", mesh {mesh.shape['scenario']}x{mesh.shape['node']}"
             if mesh is not None else ""))
    if args.attach_stream:
        print(f"overlay: {stats['overlay_hits']} served from the resident "
              f"twin, {stats['overlay_fallbacks']} staged fallbacks")

    if recorder is not None:
        from tpusim.obs import recorder as flight

        flight.uninstall()
        try:
            recorder.write(args.trace_out)
        except OSError as exc:
            print(f"error: failed to write trace: {exc}", file=sys.stderr)
            return 2
        if not args.quiet:
            print(f"trace: {args.trace_out} ({len(recorder.events)} events)",
                  file=sys.stderr)
    if args.metrics_out:
        try:
            _write_metrics(args.metrics_out)
        except OSError as exc:
            print(f"error: failed to write metrics: {exc}", file=sys.stderr)
            return 2
    return exit_code


def _write_metrics(path: str) -> None:
    """Dump the registry in Prometheus text exposition format (the scrape
    body the reference never served; framework/metrics.py docstring)."""
    from tpusim.framework.metrics import register
    from tpusim.obs import analytics

    # fold the latest analytics sample + HBM sources into the gauges,
    # exactly like a live /metrics scrape does
    analytics.refresh_gauges()
    with open(path, "w") as f:
        f.write(register().expose())


def build_stream_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="tpusim stream",
        description="Streaming runtime: hold the compiled cluster resident "
                    "on device and drive it with seeded churn — arrivals, "
                    "evictions, node flaps (tpusim/stream). Warm cycles "
                    "scatter-commit the watch delta instead of re-staging "
                    "the cluster")
    parser.add_argument("--snapshot", default="",
                        help="Combined ClusterSnapshot JSON ({nodes, pods})")
    parser.add_argument("--synthetic-nodes", type=int, default=64,
                        help="Generate N homogeneous synthetic nodes "
                             "(ignored with --snapshot)")
    parser.add_argument("--synthetic-milli-cpu", type=int, default=4000)
    parser.add_argument("--synthetic-memory", type=int, default=16 * 1024**3)
    parser.add_argument("--cycles", type=int, default=50,
                        help="Scheduling cycles to run")
    parser.add_argument("--arrivals", type=int, default=32,
                        help="Fresh pod arrivals per cycle")
    parser.add_argument("--evict-fraction", type=float, default=0.25,
                        help="Fraction of the arrival batch size evicted "
                             "from the bound population per cycle (the "
                             "O(delta) scatter load)")
    parser.add_argument("--flap-every", type=int, default=0,
                        help="Cordon+restore a random node every k-th cycle "
                             "(structural events: forces classified "
                             "restages; 0 = never)")
    parser.add_argument("--label-churn", type=int, default=0,
                        help="Rewrite N random nodes' labels per cycle "
                             "(label-only churn: absorbed by the statics "
                             "scatter, zero restages under a fixed policy "
                             "plan)")
    parser.add_argument("--taint-churn", type=int, default=0,
                        help="Toggle a NoSchedule taint on N random nodes "
                             "per cycle (taint-only churn: scatter path, "
                             "no restage)")
    parser.add_argument("--gang-size", type=int, default=0,
                        help="Members per generated pod group (tpusim/gang: "
                             "all-or-nothing admission with rank-aware "
                             "packing; 0 = no gangs)")
    parser.add_argument("--gang-count", type=int, default=0,
                        help="Pod groups appended to each cycle's arrivals "
                             "(requires --gang-size)")
    parser.add_argument("--seed", type=int, default=0,
                        help="Load-generator seed")
    parser.add_argument("--algorithmprovider", default="DefaultProvider")
    parser.add_argument("--policy-file", default="",
                        help="Scheduler policy JSON (kube-scheduler "
                             "--policy-config-file shape); the compiled "
                             "plan stays device-resident across cycles "
                             "(stream v2)")
    parser.add_argument("--pipeline", action="store_true",
                        help="Pipelined cycles: dispatch cycle N on device, "
                             "decode cycle N-1's placements while it runs "
                             "(identical placements, one cycle of latency)")
    parser.add_argument("--always-restage", action="store_true",
                        help="Disable the O(delta) fast path: full compile + "
                             "re-stage every cycle (the comparison arm; "
                             "placements are identical)")
    parser.add_argument("--verify", action="store_true",
                        help="Cross-check every cycle against a fresh-"
                             "compile JaxBackend dispatch (placement_hash "
                             "byte-parity)")
    parser.add_argument("--chaos-plan", default="",
                        help="Fault-plan JSON: device section plus "
                             "process_crash churn events (other churn/"
                             "fabric faults are the load generator's job)")
    parser.add_argument("--checkpoint-dir", default="",
                        help="Durability directory: every committed watch "
                             "delta and placement appends to a WAL here, "
                             "with periodic device-state checkpoints "
                             "(stream.persist)")
    parser.add_argument("--checkpoint-every", type=int, default=10,
                        help="Cycles between checkpoints (0: genesis "
                             "checkpoint only, WAL replay covers the rest)")
    parser.add_argument("--recover", action="store_true",
                        help="Recover from --checkpoint-dir (checkpoint + "
                             "WAL tail replay) and resume the interrupted "
                             "run; the fold chain proves placement parity "
                             "with the uninterrupted run")
    parser.add_argument("--fsync-every", type=int, default=0,
                        help="fsync the WAL every N appends (0: flush-only "
                             "durability); the mode is stamped into every "
                             "checkpoint manifest")
    parser.add_argument("--replicate-to", default="",
                        help="HOST:PORT of a listening `tpusim follow` "
                             "standby: ship every WAL record + checkpoint "
                             "manifest over the replication protocol "
                             "(stream.replicate) and drain the acks before "
                             "exiting; requires --checkpoint-dir")
    parser.add_argument("--whatif-every", type=int, default=0,
                        help="Serve a live what-if query against the "
                             "device-resident twin every N cycles via a "
                             "copy-on-write overlay (mark -> scan -> roll "
                             "back; the churn chain is byte-unchanged); "
                             "0 = no queries (ISSUE 19)")
    parser.add_argument("--whatif-pods", type=int, default=4,
                        help="Scenario pods per live what-if query")
    parser.add_argument("--platform",
                        default=os.environ.get("TPUSIM_PLATFORM", ""))
    parser.add_argument("--json", action="store_true",
                        help="Print the full summary dict as JSON")
    parser.add_argument("--metrics-out", default="",
                        help="Write the metric families (Prometheus text "
                             "format) after the run — includes "
                             "tpusim_stream_restage_total{reason} and "
                             "tpusim_stream_cycles_total{path}")
    parser.add_argument("--trace-out", default="",
                        help="Write the stream span timeline (Chrome trace "
                             "JSON, or .jsonl for raw spans)")
    add_obs_flags(parser)
    add_explain_flags(parser)
    return parser


def stream_cli(argv) -> int:
    """`tpusim stream`: churn load against the device-resident runtime."""
    args = build_stream_parser().parse_args(argv)
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    snapshot = None
    chaos_plan = None
    policy = None
    try:
        if args.snapshot:
            snapshot = ClusterSnapshot.load(args.snapshot)
        if args.policy_file:
            from tpusim.engine.policy import load_policy_file

            policy = load_policy_file(args.policy_file)
        if args.chaos_plan:
            from tpusim.chaos import load_plan
            from tpusim.chaos.plan import PlanError

            try:
                chaos_plan = load_plan(args.chaos_plan)
            except PlanError as exc:
                print(f"error: --chaos-plan: {exc}", file=sys.stderr)
                return 2
    except (OSError, ValueError) as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2

    recorder = None
    if args.trace_out:
        from tpusim.obs import recorder as flight

        recorder = flight.install(
            flight.FlightRecorder(process_name="tpusim-stream"))

    replicate_to = None
    if args.replicate_to:
        from tpusim.obs.server import parse_listen

        if not args.checkpoint_dir:
            print("error: --replicate-to ships the WAL; pass "
                  "--checkpoint-dir", file=sys.stderr)
            return 2
        try:
            replicate_to = parse_listen(args.replicate_to)
        except ValueError:
            print(f"error: --replicate-to {args.replicate_to!r}: want "
                  "HOST:PORT", file=sys.stderr)
            return 2

    from tpusim.chaos.engine import ProcessCrash
    from tpusim.simulator import run_stream_simulation

    obs_teardown = _arm_observability(args)
    try:
        out = run_stream_simulation(
            snapshot, num_nodes=args.synthetic_nodes, cycles=args.cycles,
            arrivals=args.arrivals, evict_fraction=args.evict_fraction,
            node_flap_every=args.flap_every, seed=args.seed,
            label_churn=args.label_churn, taint_churn=args.taint_churn,
            gang_size=args.gang_size, gang_count=args.gang_count,
            provider=args.algorithmprovider,
            policy=policy, pipeline=args.pipeline,
            always_restage=args.always_restage, verify=args.verify,
            chaos_plan=chaos_plan,
            checkpoint_dir=args.checkpoint_dir or None,
            checkpoint_every=args.checkpoint_every,
            fsync_every=args.fsync_every,
            replicate_to=replicate_to,
            recover=args.recover,
            whatif_every=args.whatif_every,
            whatif_pods=args.whatif_pods)
    except ProcessCrash as exc:
        # the scripted kill: state up to the crash is durable in the WAL;
        # rerun with --recover to resume from it
        print(f"crashed: {exc}", file=sys.stderr)
        print(f"recover with: tpusim stream --checkpoint-dir "
              f"{args.checkpoint_dir} --recover ...", file=sys.stderr)
        return 3
    except (KeyError, ValueError) as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2
    finally:
        obs_teardown()

    exit_code = 0
    if args.json:
        import json

        print(json.dumps(out, indent=2, sort_keys=True))
    else:
        paths = ", ".join(f"{k} x{v}" for k, v in sorted(out["paths"].items()))
        restages = ", ".join(f"{k} x{v}"
                             for k, v in sorted(out["restages"].items()))
        print(f"{out['cycles']} cycles over {out['nodes']} nodes: "
              f"{out['scheduled']}/{out['decisions']} scheduled, "
              f"{out['decisions_per_s']:.0f} decisions/s, cycle p50/p99 "
              f"{out['p50_cycle_ms']:.1f}/{out['p99_cycle_ms']:.1f} ms")
        print(f"paths: {paths or 'none'}; restages: {restages or 'none'}; "
              f"{out['commits']} scatter commits")
        print(f"load: {out['load']['arrivals']} arrivals, "
              f"{out['load']['evictions']} evictions, "
              f"{out['load']['flaps']} flaps; "
              f"placement chain {out['placement_chain'][:16]}")
        if "overlay" in out:
            ov = out["overlay"]
            print(f"live what-if: {ov['answered']}/{ov['queries']} overlay "
                  f"queries answered ({ov['fallbacks']} fell back), query "
                  f"p50/p99 {ov['p50_query_ms']:.1f}/"
                  f"{ov['p99_query_ms']:.1f} ms")
        if out.get("recovered"):
            print(f"recovered: resumed at cycle {out['resume_cycle']} "
                  f"({len(out['recomputed_cycles'])} cycles recomputed, replay "
                  f"{out['replay_ms']:.1f} ms); fold chain "
                  f"{out['fold_chain'][:16]}")
        elif "wal_records" in out:
            print(f"durability: {out['wal_records']} WAL records, "
                  f"{out['checkpoints']} checkpoints; fold chain "
                  f"{out['fold_chain'][:16]}")
        if "replication_acked_seq" in out:
            parity = (out["replication_acked_chain"] == out["fold_chain"])
            print(f"replication: acked through seq "
                  f"{out['replication_acked_seq']}, lag "
                  f"{out['replication_lag_at_close']} record(s) at close; "
                  f"follower chain "
                  f"{'matches' if parity else 'DIVERGED from'} the leader")
    if args.verify:
        if out["verified"]:
            print("verify: every cycle placement_hash-identical to the "
                  "full-restage backend")
        else:
            print(f"verify: FAILED — {out['mismatched_cycles']} cycles "
                  "diverged from the full-restage backend", file=sys.stderr)
            exit_code = 1

    if recorder is not None:
        from tpusim.obs import recorder as flight

        flight.uninstall()
        try:
            recorder.write(args.trace_out)
        except OSError as exc:
            print(f"error: failed to write trace: {exc}", file=sys.stderr)
            return 2
        print(f"trace: {args.trace_out} ({len(recorder.events)} events)",
              file=sys.stderr)
    if args.metrics_out:
        try:
            _write_metrics(args.metrics_out)
        except OSError as exc:
            print(f"error: failed to write metrics: {exc}", file=sys.stderr)
            return 2
    return exit_code


def _add_follow_snapshot_flags(parser: argparse.ArgumentParser) -> None:
    """The twin's snapshot source: MUST reproduce the leader's cycle-0
    picture (same --snapshot file or same synthetic parameters) — the
    shipper replays the journal from its first record."""
    parser.add_argument("--snapshot", default="",
                        help="Combined ClusterSnapshot JSON — the leader's "
                             "cycle-0 snapshot source")
    parser.add_argument("--synthetic-nodes", type=int, default=64,
                        help="Generate N homogeneous synthetic nodes "
                             "(must match the leader's)")
    parser.add_argument("--synthetic-milli-cpu", type=int, default=4000)
    parser.add_argument("--synthetic-memory", type=int, default=16 * 1024**3)
    parser.add_argument("--seed-label-universe", action="store_true",
                        help="Seed the churn label universe across the "
                             "synthetic nodes (required when the leader "
                             "runs --policy-file or label/taint churn)")
    parser.add_argument("--algorithmprovider", default="DefaultProvider")
    parser.add_argument("--policy-file", default="",
                        help="Scheduler policy JSON — must match the "
                             "leader's (the twin re-decides every cycle)")
    parser.add_argument("--always-restage", action="store_true")
    parser.add_argument("--platform",
                        default=os.environ.get("TPUSIM_PLATFORM", ""))
    parser.add_argument("--json", action="store_true",
                        help="Print the summary dict as JSON")


def _load_follow_snapshot(args):
    snapshot = None
    policy = None
    if args.snapshot:
        snapshot = ClusterSnapshot.load(args.snapshot)
    else:
        snapshot = synthetic_cluster(
            args.synthetic_nodes, milli_cpu=args.synthetic_milli_cpu,
            memory=args.synthetic_memory)
    if args.policy_file:
        from tpusim.engine.policy import load_policy_file

        policy = load_policy_file(args.policy_file)
    if not args.snapshot and (policy is not None
                              or args.seed_label_universe):
        from tpusim.stream.loadgen import DEFAULT_LABEL_UNIVERSE

        # the leader's run_stream_simulation seeds synthetic nodes the
        # same way — the twins' cold-start compiles must intern the same
        # label domains
        for i, node in enumerate(snapshot.nodes):
            node.metadata.labels.update(
                {k: vals[i % len(vals)]
                 for k, vals in DEFAULT_LABEL_UNIVERSE.items()})
    return snapshot, policy


def build_follow_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="tpusim follow",
        description="Hot standby: listen for a leader's WAL-shipping "
                    "stream (tpusim stream --replicate-to) and replay "
                    "every shipped cycle through a live scheduler twin, "
                    "cross-checking the placement-hash chain per cycle "
                    "(stream.replicate). With --watch-leader, promote "
                    "automatically when the leader's /healthz dies")
    parser.add_argument("--bind", default="127.0.0.1:0",
                        help="HOST:PORT the replication listener binds "
                             "(':0' picks a free port, printed on start)")
    parser.add_argument("--checkpoint-dir", default="",
                        help="The LEADER's durability directory (shared "
                             "storage): promotion replays its WAL tail "
                             "and journals onward into it; required with "
                             "--watch-leader")
    parser.add_argument("--watch-leader", default="",
                        help="Leader /healthz URL (http://HOST:PORT): "
                             "probe it and promote this twin when it "
                             "misses --misses probes in a row")
    parser.add_argument("--watch-interval", type=float, default=0.25,
                        help="Seconds between leader probes")
    parser.add_argument("--misses", type=int, default=2,
                        help="Consecutive probe misses declaring death")
    parser.add_argument("--watch-timeout", type=float, default=0.0,
                        help="Give up watching after this many seconds "
                             "(0: watch forever)")
    parser.add_argument("--checkpoint-every", type=int, default=10,
                        help="Post-promotion checkpoint cadence")
    parser.add_argument("--fsync-every", type=int, default=0,
                        help="Post-promotion WAL fsync cadence")
    parser.add_argument("--bootstrap", action="store_true",
                        help="Late join (ISSUE 19): request the leader's "
                             "latest checkpoint manifest + WAL offset in "
                             "the hello exchange and rebuild the twin from "
                             "it, instead of replaying from a cycle-0 "
                             "snapshot (--snapshot/--synthetic-nodes are "
                             "then ignored)")
    parser.add_argument("--trace-out", default="",
                        help="Write the follower's flight-recorder trace "
                             "(Chrome trace_event JSON) on exit: replay "
                             "spans carry the leader's trace ids, so "
                             "tools/trace_merge.py joins this file with "
                             "the leader's into one flow graph (ISSUE 20)")
    _add_follow_snapshot_flags(parser)
    add_obs_flags(parser)
    add_explain_flags(parser)
    return parser


def follow_cli(argv) -> int:
    """`tpusim follow`: a live standby twin (ISSUE 18)."""
    import json

    args = build_follow_parser().parse_args(argv)
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    if args.watch_leader and not args.checkpoint_dir:
        print("error: --watch-leader promotes from the leader's WAL; pass "
              "--checkpoint-dir (the shared durability directory)",
              file=sys.stderr)
        return 2

    from tpusim.obs.server import parse_listen

    try:
        bind = parse_listen(args.bind)
        snapshot, policy = _load_follow_snapshot(args)
        if args.bootstrap:
            snapshot = None   # the shipped manifest is the twin's source
    except (OSError, ValueError) as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2

    from tpusim.stream.replicate import (
        FailoverController,
        FollowerTwin,
        PromotionRefused,
        http_probe,
    )

    obs_teardown = _arm_observability(args)
    recorder = None
    if args.trace_out:
        from tpusim.obs import recorder as flight

        recorder = flight.install(
            flight.FlightRecorder(process_name="tpusim-follow"))
    try:
        try:
            follower = FollowerTwin(snapshot,
                                    provider=args.algorithmprovider,
                                    policy=policy,
                                    always_restage=args.always_restage,
                                    listen=bind,
                                    bootstrap=args.bootstrap)
        except (KeyError, ValueError, OSError) as exc:
            print(f"error: {exc}", file=sys.stderr)
            return 2
        host, port = follower.address
        print(f"follower: replication listener on {host}:{port} "
              f"(leader side: tpusim stream --replicate-to {host}:{port})",
              file=sys.stderr)

        def summary(extra=None) -> dict:
            body = {"applied_records": follower.wal_records_applied,
                    "cycles_emitted": follower.cycles_emitted,
                    "chain": follower.chain,
                    "scheduled": follower.scheduled,
                    "decisions": follower.decisions,
                    "divergence": follower.diverged}
            body.update(extra or {})
            return body

        if args.watch_leader:
            url = args.watch_leader.rstrip("/")
            if "://" not in url:   # bare HOST:PORT (the --listen spelling)
                url = "http://" + url
            if not url.endswith("/healthz"):
                url += "/healthz"
            controller = FailoverController(
                http_probe(url), [follower], args.checkpoint_dir,
                interval_s=max(0.01, args.watch_interval),
                misses=max(1, args.misses),
                checkpoint_every=args.checkpoint_every,
                fsync_every=args.fsync_every)
            timeout = args.watch_timeout if args.watch_timeout > 0 else 1e9
            try:
                _, report = controller.run(timeout=timeout)
            except TimeoutError as exc:
                print(f"{exc}; exiting without promotion", file=sys.stderr)
                follower.stop()
                out = summary({"promoted": False})
                print(json.dumps(out, sort_keys=True) if args.json
                      else f"follower: applied {out['applied_records']} "
                           f"records, chain {out['chain'][:16]}")
                return 0
            except PromotionRefused as exc:
                print(f"error: promotion refused: {exc}", file=sys.stderr)
                return 1
            out = summary({
                "promoted": True, "rto_s": report.rto_s,
                "resume_cycle": report.resume_cycle,
                "replayed_records": report.tail_records,
                "recomputed_cycles": list(report.recomputed),
                "settled_live_cycles": list(report.settled_live),
                "promotion_violations": list(report.violations)})
            follower.persist.close()
            if args.json:
                print(json.dumps(out, sort_keys=True))
            else:
                print(f"promoted: resumed at cycle {out['resume_cycle']} "
                      f"(replayed {out['replayed_records']} tail records, "
                      f"RTO {out['rto_s'] * 1e3:.1f} ms); chain "
                      f"{out['chain'][:16]}")
                print(f"resume the churn load with: tpusim stream "
                      f"--checkpoint-dir {args.checkpoint_dir} --recover ...")
            return 1 if out["promotion_violations"] else 0

        try:
            while True:
                time.sleep(1.0)
        except KeyboardInterrupt:
            pass
        follower.stop()
        out = summary()
        print(json.dumps(out, sort_keys=True) if args.json
              else f"follower: applied {out['applied_records']} records "
                   f"over {out['cycles_emitted']} cycles, chain "
                   f"{out['chain'][:16]}"
                   + (f"; DIVERGED: {out['divergence']}"
                      if out["divergence"] else ""))
        return 1 if out["divergence"] else 0
    finally:
        if recorder is not None:
            from tpusim.obs import recorder as flight

            flight.uninstall()
            try:
                recorder.write(args.trace_out)
                print(f"trace: {args.trace_out} "
                      f"({len(recorder.events)} events)", file=sys.stderr)
            except OSError as exc:
                print(f"error: failed to write trace: {exc}",
                      file=sys.stderr)
        obs_teardown()


def build_promote_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="tpusim promote",
        description="Durable-state promotion: replay a dead leader's "
                    "entire WAL (checkpoint dir) through a fresh twin "
                    "via the promotion path — chain cross-checked "
                    "against the durable checkpoint manifest, crash-tail "
                    "cycles re-decided, a fresh checkpoint written. "
                    "Resume the run afterwards with `tpusim stream "
                    "--recover`")
    parser.add_argument("--checkpoint-dir", required=True,
                        help="The dead leader's durability directory")
    parser.add_argument("--checkpoint-every", type=int, default=10)
    parser.add_argument("--fsync-every", type=int, default=0)
    parser.add_argument("--metrics-out", default="",
                        help="Write the metric families (including "
                             "tpusim_replication_*) after promotion")
    _add_follow_snapshot_flags(parser)
    return parser


def promote_cli(argv) -> int:
    """`tpusim promote`: one-shot durable promotion (ISSUE 18)."""
    import json

    args = build_promote_parser().parse_args(argv)
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    try:
        snapshot, policy = _load_follow_snapshot(args)
    except (OSError, ValueError) as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2

    from tpusim.stream.replicate import FollowerTwin, PromotionRefused

    try:
        follower = FollowerTwin(snapshot, provider=args.algorithmprovider,
                                policy=policy,
                                always_restage=args.always_restage)
    except (KeyError, ValueError, OSError) as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2
    try:
        report = follower.promote(args.checkpoint_dir,
                                  checkpoint_every=args.checkpoint_every,
                                  fsync_every=args.fsync_every)
    except PromotionRefused as exc:
        follower.stop()
        print(f"error: promotion refused: {exc}", file=sys.stderr)
        return 1
    except (OSError, ValueError, KeyError) as exc:
        follower.stop()
        print(f"error: {exc}", file=sys.stderr)
        return 2
    follower.persist.close()
    out = {"promoted": True, "chain": report.chain,
           "resume_cycle": report.resume_cycle,
           "replayed_records": report.tail_records,
           "recomputed_cycles": list(report.recomputed),
           "wal_records": report.wal_records,
           "replay_s": report.replay_s,
           "violations": list(report.violations)}
    if args.json:
        print(json.dumps(out, sort_keys=True))
    else:
        print(f"promoted: {out['replayed_records']} WAL records replayed "
              f"({len(out['recomputed_cycles'])} cycles re-decided) in "
              f"{out['replay_s'] * 1e3:.1f} ms; chain {out['chain'][:16]}")
        print(f"resume with: tpusim stream --checkpoint-dir "
              f"{args.checkpoint_dir} --recover ...")
        for violation in out["violations"]:
            print(f"promotion violation: {violation}", file=sys.stderr)
    if args.metrics_out:
        try:
            _write_metrics(args.metrics_out)
        except OSError as exc:
            print(f"error: failed to write metrics: {exc}", file=sys.stderr)
            return 2
    return 1 if out["violations"] else 0


def build_audit_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="tpusim audit",
        description="Chain-divergence forensics (ISSUE 20): bisect two "
                    "WAL directories (checkpoint.json + wal.jsonl pairs, "
                    "e.g. a leader's and a follower's, or two same-seed "
                    "runs) to the FIRST divergent cycle via the sha256 "
                    "digest chain, then re-run that cycle through the "
                    "scheduler with explain lanes on and emit a "
                    "per-decision forensic diff: score parts, top-k "
                    "candidate order, restage classification, shard "
                    "ownership of the flipped node")
    parser.add_argument("wal_a", help="First WAL directory")
    parser.add_argument("wal_b", help="Second WAL directory")
    parser.add_argument("--algorithmprovider", default="DefaultProvider",
                        help="Provider the audited runs used (the replay "
                             "re-decides under the same policy surface)")
    parser.add_argument("--explain-k", type=int, default=3,
                        help="Top-k score-breakdown depth for the "
                             "forensic re-run (default 3; 0 disables "
                             "the score-parts lanes)")
    parser.add_argument("--no-replay", action="store_true",
                        help="Record-level diff only: skip rebuilding "
                             "the shared prefix and re-deciding the "
                             "divergent cycle")
    parser.add_argument("--json", action="store_true",
                        help="Print the full report as one JSON object")
    parser.add_argument("--out", default="",
                        help="Additionally write the JSON report here "
                             "(the repro harness's forensic artifact)")
    parser.add_argument("--platform", default="",
                        help="JAX platform for the replay (e.g. cpu)")
    return parser


def audit_cli(argv) -> int:
    """`tpusim audit`: first-divergence forensics over two WALs."""
    import json

    args = build_audit_parser().parse_args(argv)
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    from tpusim.obs.audit import audit_wal_pair, render_report
    from tpusim.stream.persist import StreamPersistence

    for d in (args.wal_a, args.wal_b):
        if not os.path.exists(os.path.join(d, StreamPersistence.WAL)):
            print(f"error: no {StreamPersistence.WAL} in {d}",
                  file=sys.stderr)
            return 2
    try:
        report = audit_wal_pair(args.wal_a, args.wal_b,
                                provider=args.algorithmprovider,
                                explain_k=args.explain_k,
                                replay=not args.no_replay)
    except (OSError, ValueError, KeyError) as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2
    if args.out:
        try:
            with open(args.out, "w", encoding="utf-8") as f:
                json.dump(report, f, sort_keys=True, indent=2)
                f.write("\n")
        except OSError as exc:
            print(f"error: failed to write report: {exc}", file=sys.stderr)
            return 2
    if args.json:
        print(json.dumps(report, sort_keys=True))
    else:
        print(render_report(report), end="")
    return 1 if report.get("verdict") == "diverged" else 0


def build_explain_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="tpusim explain",
        description="Query a decision-provenance file (--explain-out "
                    "JSONL): why each pod placed where it did, or the "
                    "exact per-predicate failure text when it didn't")
    parser.add_argument("file", help="JSONL file written by --explain-out")
    parser.add_argument("--pod", default="",
                        help="Only records whose pod name contains this "
                             "substring ('ns/name' matches exactly)")
    parser.add_argument("--source", default="",
                        help="Only records from this capture source "
                             "(backend, stream, serve, ...)")
    parser.add_argument("--failed", action="store_true",
                        help="Only unschedulable decisions")
    parser.add_argument("--placed", action="store_true",
                        help="Only placed decisions")
    parser.add_argument("--limit", type=int, default=0,
                        help="Print at most the LAST N matching records "
                             "(0: all)")
    parser.add_argument("--summary", action="store_true",
                        help="Aggregate counts instead of per-record lines: "
                             "placed/failed by source, failure messages by "
                             "frequency")
    parser.add_argument("--json", action="store_true",
                        help="Emit matching records as JSON lines instead "
                             "of the human-readable rendering")
    return parser


def _format_explain_record(rec: dict) -> str:
    where = rec.get("source", "?")
    if rec.get("cycle") is not None:
        where += f" c{rec['cycle']}"
    head = f"#{rec.get('seq', '?')} [{where}] {rec.get('pod', '?')}"
    if rec.get("placed"):
        line = f"{head} -> {rec.get('node')}"
        top = rec.get("top_k") or []
        if top:
            best = top[0]
            parts = best.get("parts") or {}
            breakdown = ", ".join(f"{k}={v}" for k, v in parts.items() if v)
            line += (f"  (score {best.get('score')}"
                     + (f": {breakdown}" if breakdown else "") + ")")
            for alt in top[1:]:
                line += f"\n    runner-up {alt['node']} score {alt['score']}"
        return line
    return (f"{head} UNSCHEDULABLE [{rec.get('reason', '?')}]\n"
            f"    {rec.get('message', '')}")


def explain_cli(argv) -> int:
    """`tpusim explain`: offline queries over an --explain-out file."""
    import json
    from collections import Counter

    from tpusim.obs.provenance import read_jsonl

    args = build_explain_parser().parse_args(argv)
    if args.failed and args.placed:
        print("error: --failed and --placed are mutually exclusive",
              file=sys.stderr)
        return 2

    def matches(rec: dict) -> bool:
        if args.pod:
            pod = rec.get("pod", "")
            if args.pod != pod and args.pod not in pod:
                return False
        if args.source and rec.get("source") != args.source:
            return False
        if args.failed and rec.get("placed"):
            return False
        if args.placed and not rec.get("placed"):
            return False
        return True

    try:
        records = [r for r in read_jsonl(args.file) if matches(r)]
    except (OSError, ValueError) as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2

    if args.summary:
        by_source: Counter = Counter()
        placed = failed = 0
        messages: Counter = Counter()
        nodes: Counter = Counter()
        for rec in records:
            by_source[rec.get("source", "?")] += 1
            if rec.get("placed"):
                placed += 1
                nodes[rec.get("node", "?")] += 1
            else:
                failed += 1
                messages[rec.get("message", "")] += 1
        print(f"{len(records)} decision(s): {placed} placed, "
              f"{failed} unschedulable")
        for source, n in by_source.most_common():
            print(f"  source {source}: {n}")
        if nodes:
            print("top nodes:")
            for node, n in nodes.most_common(10):
                print(f"  {n:6d}  {node}")
        if messages:
            print("failure messages:")
            for message, n in messages.most_common(10):
                print(f"  {n:6d}  {message}")
        return 0

    if args.limit > 0:
        records = records[-args.limit:]
    for rec in records:
        print(json.dumps(rec, sort_keys=True) if args.json
              else _format_explain_record(rec))
    return 0


def build_top_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="tpusim top",
        description="Live cluster view against a running --listen "
                    "endpoint: per-resource utilization/fragmentation, "
                    "feasible nodes, hottest/coldest nodes, HBM residency "
                    "and compile cost (rendered from GET /analytics)")
    parser.add_argument("endpoint",
                        help="A --listen endpoint: http://HOST:PORT, "
                             "HOST:PORT, ':PORT', or 'PORT'")
    parser.add_argument("--interval", type=float, default=2.0,
                        help="Seconds between refreshes (default 2)")
    parser.add_argument("--iterations", type=int, default=0,
                        help="Render this many frames then exit "
                             "(0: until interrupted)")
    parser.add_argument("--once", action="store_true",
                        help="Render a single frame without clearing the "
                             "screen and exit")
    parser.add_argument("--json", action="store_true",
                        help="Print one raw /analytics JSON body and exit")
    return parser


def _fmt_bytes(n: float) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}GiB"


def _render_top(body: dict, url: str) -> str:
    """One `tpusim top` frame from a /analytics body."""
    lines = [f"tpusim top — {url}   samples={body.get('samples', 0)}"]
    latest = body.get("latest")
    if not body.get("enabled"):
        lines.append("analytics plane not armed on this endpoint "
                     "(start the session with --listen or --analytics-out)")
    elif latest is None:
        lines.append("no samples yet (waiting for the first cycle)")
    else:
        where = latest.get("source", "?")
        if latest.get("cycle") is not None:
            where += f" c{latest['cycle']}"
        nodes = latest.get("nodes", {})
        lines.append(f"nodes: {nodes.get('valid', '?')} valid, "
                     f"{nodes.get('feasible', '?')} feasible "
                     f"(cpu+mem+pod headroom)   [latest: {where}]")
        lines.append(f"{'RESOURCE':<10} {'UTIL':>7} {'FRAG':>7} "
                     f"{'REQUESTED':>14} {'ALLOCATABLE':>14} "
                     f"{'LARGEST-FREE':>13}")
        for name, row in latest.get("resources", {}).items():
            util = row.get("utilization")
            util_s = f"{util * 100:.1f}%" if util is not None else "-"
            frag_s = f"{row.get('fragmentation', 0.0) * 100:.1f}%"
            lines.append(f"{name:<10} {util_s:>7} {frag_s:>7} "
                         f"{row.get('requested', 0):>14} "
                         f"{row.get('allocatable', 0):>14} "
                         f"{row.get('largest_free', 0):>13}")
        for label, key in (("hottest", "hot_nodes"),
                           ("coldest", "cold_nodes")):
            entries = latest.get(key) or []
            if entries:
                lines.append(f"{label}: " + "  ".join(
                    f"{e['node']} {e['utilization_ppm'] / 10_000:.1f}%"
                    for e in entries[:5]))
    hbm = body.get("hbm") or {}
    if hbm:
        lines.append("hbm: " + "  ".join(
            f"{comp} {_fmt_bytes(slot.get('bytes', 0))}"
            f"/{slot.get('entries', 0)} entries"
            for comp, slot in sorted(hbm.items())))
    comp = body.get("compile") or {}
    if comp:
        lines.append("compile: " + "  ".join(
            f"{site} {slot.get('traces', 0)} traces "
            f"{slot.get('total_us', 0.0) / 1e6:.2f}s"
            for site, slot in sorted(comp.items())))
    return "\n".join(lines)


def top_cli(argv) -> int:
    """`tpusim top`: live analytics view against a --listen endpoint."""
    import json
    import time as _time
    from urllib.error import URLError
    from urllib.request import urlopen

    args = build_top_parser().parse_args(argv)
    endpoint = args.endpoint.strip()
    if endpoint.startswith("http://") or endpoint.startswith("https://"):
        url = endpoint.rstrip("/")
    else:
        from tpusim.obs.server import parse_listen

        try:
            host, port = parse_listen(endpoint)
        except ValueError:
            print(f"error: bad endpoint {endpoint!r}", file=sys.stderr)
            return 2
        url = f"http://{host}:{port}"

    def fetch() -> dict:
        with urlopen(f"{url}/analytics?limit=1", timeout=5) as resp:
            return json.loads(resp.read().decode())

    frames = 0
    try:
        while True:
            try:
                body = fetch()
            except (URLError, OSError, ValueError) as exc:
                if frames:
                    print(f"endpoint gone ({exc}); exiting", file=sys.stderr)
                    return 0
                print(f"error: cannot reach {url}/analytics: {exc}",
                      file=sys.stderr)
                return 2
            if args.json:
                print(json.dumps(body, sort_keys=True))
                return 0
            frame = _render_top(body, url)
            if not args.once and sys.stdout.isatty():
                print("\x1b[2J\x1b[H" + frame, flush=True)
            else:
                print(frame, flush=True)
            frames += 1
            if args.once or (args.iterations and frames >= args.iterations):
                return 0
            _time.sleep(max(0.1, args.interval))
    except KeyboardInterrupt:
        return 0


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "serve":
        return serve_cli(argv[1:])
    if argv and argv[0] == "stream":
        return stream_cli(argv[1:])
    if argv and argv[0] == "follow":
        return follow_cli(argv[1:])
    if argv and argv[0] == "promote":
        return promote_cli(argv[1:])
    if argv and argv[0] == "audit":
        return audit_cli(argv[1:])
    if argv and argv[0] == "explain":
        return explain_cli(argv[1:])
    if argv and argv[0] == "top":
        return top_cli(argv[1:])
    args = build_parser().parse_args(argv)
    feature_gates = None
    if args.feature_gates:
        from tpusim.engine.providers import parse_feature_gates

        try:
            feature_gates = parse_feature_gates(args.feature_gates)
        except ValueError as exc:
            print(f"error: --feature-gates: {exc}", file=sys.stderr)
            return 2
        # PodPriority / VolumeScheduling gate the same behavior as the
        # dedicated flags (scheduler.go:175,210-213)
        if feature_gates.pop("PodPriority", False):
            args.enable_pod_priority = True
        if feature_gates.pop("VolumeScheduling", False):
            args.enable_volume_scheduling = True

    if args.verbosity >= 2:
        # glog -v analog. The tpusim.* loggers (engine/trace.py slow-
        # schedule traces, backend routing decisions) emit into the root
        # logger, which python leaves handler-less: configure it so V(2)+
        # actually prints. V(5)+ additionally turns on DEBUG, including
        # the engine's per-node score dump.
        import logging

        logging.basicConfig(stream=sys.stderr, format="%(message)s")
        # "tpusim.engine" and "tpusim.trace" inherit the package level
        logging.getLogger("tpusim").setLevel(
            logging.DEBUG if args.verbosity >= 5 else logging.INFO)

    # (An env-level JAX_PLATFORMS=cpu pin is honored by the import-time guard
    # in tpusim/jaxe/__init__.py — every jax-using path imports that module
    # before backend init, so no duplicate check is needed here.)
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    if args.what_if:
        if args.event_log:
            print("error: --event-log cannot be combined with --what-if "
                  "(what-if scenarios carry their own snapshots)",
                  file=sys.stderr)
            return 2
        if args.trace_out:
            print("error: --trace-out cannot be combined with --what-if "
                  "(scenario runs share one process; their spans would "
                  "interleave on a single timeline)", file=sys.stderr)
            return 2
        rc = run_what_if_cli(args)
        if rc == 0 and args.metrics_out:
            _write_metrics(args.metrics_out)
        return rc
    if args.mesh:
        print("error: --mesh applies only to --what-if (the single-run scan "
              "is sequential; scale it via more nodes per snapshot)",
              file=sys.stderr)
        return 2
    if not args.podspec:
        print("error: --podspec is required (or use --what-if)", file=sys.stderr)
        return 2

    try:
        snapshot = load_snapshot(args)
    except (OSError, ValueError) as exc:
        print(f"error: failed to load cluster snapshot: {exc}", file=sys.stderr)
        return 2
    if not snapshot.nodes:
        print("error: no cluster nodes; pass --snapshot, --nodes, or "
              "--synthetic-nodes", file=sys.stderr)
        return 2

    try:
        sim_pods = load_simulation_pods(args.podspec)
    except (OSError, ValueError) as exc:
        print(f"error: failed to parse podspec: {exc}", file=sys.stderr)
        return 2
    pods = expand_simulation_pods(sim_pods, namespace=args.namespace)

    policy, policy_err = load_policy_from_args(args)
    if policy_err:
        print(f"error: {policy_err}", file=sys.stderr)
        return 2

    events = None
    if args.event_log:
        from tpusim.framework.events import load_event_log

        try:
            events = load_event_log(args.event_log)
        except (OSError, ValueError) as exc:
            print(f"error: invalid event log: {exc}", file=sys.stderr)
            return 2

    if args.verbosity >= 5:
        # the per-node score dump is a host-engine trace; the device
        # pipeline is one fused program with no per-node observability
        # point — warn whenever THIS invocation will run on the device
        # (explicit jax, or auto routing away from the host engine; auto
        # sizes AFTER the event-log fold, so count node adds/deletes)
        from tpusim.api.types import Node
        from tpusim.framework.store import ADDED, DELETED
        from tpusim.simulator import auto_routes_to_host

        n_nodes = len(snapshot.nodes)
        for etype, obj in events or []:
            if isinstance(obj, Node):
                n_nodes += 1 if etype == ADDED else \
                    -1 if etype == DELETED else 0
        device_bound = (args.backend == "jax"
                        or (args.backend == "auto" and not auto_routes_to_host(
                            len(pods), n_nodes,
                            args.enable_volume_scheduling)))
        if device_bound:
            print("note: the per-node score dump (--v 5) is produced by "
                  "the host engine; this run uses the fused device "
                  "program. Use --backend reference to see the dump.",
                  file=sys.stderr)

    chaos_plan = None
    if args.chaos_plan or args.chaos_seed is not None:
        from tpusim.chaos import load_plan, random_plan
        from tpusim.chaos.plan import PlanError

        try:
            if args.chaos_plan:
                chaos_plan = load_plan(args.chaos_plan)
                if args.chaos_seed is not None:
                    chaos_plan.seed = args.chaos_seed
            else:
                # seed-only: generate an adversarial plan against the
                # loaded workload (deterministic per seed)
                chaos_plan = random_plan(
                    args.chaos_seed,
                    node_names=[n.name for n in snapshot.nodes],
                    pod_keys=[p.key() for p in pods],
                    attempts=max(len(pods), 1))
        except (OSError, PlanError) as exc:
            print(f"error: invalid chaos plan: {exc}", file=sys.stderr)
            return 2

    recorder = None
    if args.trace_out:
        from tpusim.obs import recorder as flight

        recorder = flight.install(flight.FlightRecorder())

    obs_teardown = _arm_observability(args)
    start = time.perf_counter()
    try:
        status = run_simulation(pods, snapshot, provider=args.algorithmprovider,
                                backend=args.backend,
                                enable_pod_priority=args.enable_pod_priority,
                                enable_volume_scheduling=args.enable_volume_scheduling,
                                policy=policy, events=events,
                                feature_gates=feature_gates,
                                chaos_plan=chaos_plan)
    except (ValueError, KeyError) as exc:
        # invalid policy/provider/plugin names surfaced at build time
        # (PolicyError is a ValueError; the registry raises KeyError)
        print(f"error: {exc}", file=sys.stderr)
        return 2
    finally:
        obs_teardown()
    elapsed = time.perf_counter() - start

    if recorder is not None:
        from tpusim.obs import recorder as flight

        flight.uninstall()
        try:
            recorder.write(args.trace_out)
        except OSError as exc:
            print(f"error: failed to write trace: {exc}", file=sys.stderr)
            return 2
        if not args.quiet:
            print(f"trace: {args.trace_out} "
                  f"({len(recorder.events)} events)", file=sys.stderr)
    if args.metrics_out:
        try:
            _write_metrics(args.metrics_out)
        except OSError as exc:
            print(f"error: failed to write metrics: {exc}", file=sys.stderr)
            return 2

    report = get_report(status)
    if args.print_requirements and not args.quiet:
        spec_print(report.review["success"].spec)
        spec_print(report.review["failed"].spec)
    if not args.quiet:
        cluster_capacity_review_print(report)
    n_ok = len(status.successful_pods)
    n_fail = len(status.failed_pods)
    rate = (n_ok + n_fail) / elapsed if elapsed > 0 else 0.0
    print(f"\n{n_ok} pod(s) scheduled, {n_fail} unschedulable, "
          f"{len(status.scheduled_pods)} pre-scheduled "
          f"[{args.backend} backend, {elapsed:.3f}s, {rate:.0f} pods/s]")
    print(f"StopReason: {status.stop_reason.strip()}")
    if chaos_plan is not None:
        summary = getattr(status, "chaos_summary", None) or {}
        violations = getattr(status, "chaos_violations", None) or []
        fired = summary.get("churn_fired", 0)
        fabric = len(summary.get("fabric_injected", []))
        device = (len(summary.get("device_injected", []))
                  or len(summary.get("breaker_transitions", [])))
        print(f"Chaos: {fired} churn event(s), {fabric} fabric fault(s), "
              f"{device} device fault/transition(s), "
              f"{len(violations)} invariant violation(s) [seed "
              f"{chaos_plan.seed}]")
        if violations:
            for violation in violations:
                print(f"chaos violation: {violation}", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

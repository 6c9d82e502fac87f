"""Multi-snapshot what-if batching tests (BASELINE.json config 5).

Correctness bar: the batched, shape-unified, mesh-sharded run must produce
exactly the same placements as running each scenario alone through JaxBackend
(which itself is differentially tested against the reference loop in
test_jax_parity.py).
"""

import jax
import numpy as np
import pytest

from tpusim.api.snapshot import ClusterSnapshot, make_node, make_pod
from tpusim.backends import get_backend
from tpusim.jaxe import trust
from tpusim.jaxe.sharding import make_mesh
from tpusim.jaxe.trust import FAST
from tpusim.jaxe.whatif import run_what_if

needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the virtual 8-device mesh")


def scenario(seed: int, num_nodes: int, num_pods: int):
    rng = np.random.RandomState(seed)
    nodes = []
    for i in range(num_nodes):
        taints = ([{"key": "dedicated", "value": "batch",
                    "effect": "NoSchedule"}] if i % 4 == 0 else None)
        nodes.append(make_node(
            f"s{seed}-n{i}", milli_cpu=int(rng.choice([2000, 4000, 8000])),
            memory=int(rng.choice([4, 8, 16])) * 1024**3,
            labels={"zone": f"z{i % 3}"}, taints=taints))
    pods = []
    for i in range(num_pods):
        kwargs = {}
        if i % 3 == 0:
            kwargs["tolerations"] = [{"key": "dedicated", "operator": "Equal",
                                      "value": "batch", "effect": "NoSchedule"}]
        if i % 5 == 0:
            kwargs["node_selector"] = {"zone": f"z{i % 3}"}
        pods.append(make_pod(f"s{seed}-p{i}", milli_cpu=int(rng.randint(100, 1500)),
                             memory=int(rng.randint(2**20, 2**30)), **kwargs))
    return ClusterSnapshot(nodes=nodes), pods


def placements_key(placements):
    return [(p.pod.name, p.node_name, p.message) for p in placements]


def singleton_results(scenarios, provider="DefaultProvider"):
    backend = get_backend("jax", provider=provider)
    return [placements_key(backend.schedule(pods, snap))
            for snap, pods in scenarios]


class TestWhatIf:
    def test_heterogeneous_scenarios_match_singleton_runs(self):
        # different node counts, pod counts, and scalar/signature spaces
        scenarios = [scenario(0, 12, 9), scenario(1, 7, 14), scenario(2, 20, 5)]
        batched = run_what_if(scenarios)
        singles = singleton_results(scenarios)
        assert len(batched) == 3
        for got, want in zip(batched, singles):
            assert placements_key(got.placements) == want

    def test_counts(self):
        snap, pods = scenario(3, 6, 8)
        # an impossible pod: bigger than every node
        pods.append(make_pod("impossible", milli_cpu=10**9, memory=2**50))
        [result] = run_what_if([(snap, pods)])
        assert result.total == len(pods)
        assert result.unschedulable >= 1
        impossible = result.placements[-1]
        assert impossible.reason == "Unschedulable"
        assert "Insufficient cpu" in impossible.message

    def test_provider_validation(self):
        with pytest.raises(KeyError):
            run_what_if([scenario(0, 3, 2)], provider="NoSuchProvider")

    def test_empty_scenario_list_rejected(self):
        # an empty study is a caller bug: surface it loudly instead of
        # returning an empty list that reads like "everything scheduled"
        with pytest.raises(ValueError, match="at least one"):
            run_what_if([])

    @needs_8_devices
    def test_mesh_sharded_matches_singleton_runs(self):
        # 3 scenarios on a (snap=2, node=4) mesh: scenario axis padded to 4
        scenarios = [scenario(10, 16, 10), scenario(11, 9, 6),
                     scenario(12, 24, 12)]
        mesh = make_mesh(8, snap=2)
        batched = run_what_if(scenarios, mesh=mesh)
        singles = singleton_results(scenarios)
        assert len(batched) == 3
        for got, want in zip(batched, singles):
            assert placements_key(got.placements) == want

    @needs_8_devices
    def test_mesh_td_provider(self):
        scenarios = [scenario(20, 8, 6), scenario(21, 8, 6)]
        mesh = make_mesh(8, snap=2)
        batched = run_what_if(scenarios, provider="TalkintDataProvider",
                              mesh=mesh)
        singles = singleton_results(scenarios, provider="TalkintDataProvider")
        for got, want in zip(batched, singles):
            assert placements_key(got.placements) == want

    @needs_8_devices
    def test_scenario_mesh_matches_singleton_runs(self):
        # the manual shard_map route: scenarios partitioned over the
        # "scenario" axis, node columns whole per shard — same placements
        # as the GSPMD vmap and the singleton runs
        from tpusim.jaxe.sharding import make_scenario_mesh

        scenarios = [scenario(50 + s, 6 + s, 5 + s) for s in range(5)]
        batched = run_what_if(scenarios, mesh=make_scenario_mesh(8))
        singles = singleton_results(scenarios)
        assert len(batched) == 5
        for got, want in zip(batched, singles):
            assert placements_key(got.placements) == want

    def test_zero_node_scenario_rejected_with_index(self):
        # there is no node axis to pad onto; the error names the offender
        # so a 50-scenario manifest is debuggable
        empty = (ClusterSnapshot(nodes=[]), [make_pod("lonely", milli_cpu=100)])
        scenarios = [scenario(30, 8, 5), empty, scenario(31, 6, 4)]
        with pytest.raises(ValueError, match=r"scenario 1: .*zero-node"):
            run_what_if(scenarios)

    def test_all_scenarios_zero_nodes_rejected(self):
        empty = (ClusterSnapshot(nodes=[]), [make_pod("p", milli_cpu=10)])
        with pytest.raises(ValueError, match=r"scenario 0: .*zero-node"):
            run_what_if([empty, empty])

    def test_unknown_mesh_axes_rejected(self):
        from jax.sharding import Mesh

        bogus = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                     ("model", "data"))
        with pytest.raises(ValueError, match=r"axes \('model', 'data'\)"):
            run_what_if([scenario(40, 4, 3)], mesh=bogus)


class TestFastLoop:
    """The Pallas fast loop replaces the vmap(S)xscan(P) program when every
    scenario is fast-eligible (and the fast path is on); results must be
    byte-identical to the batched program."""

    def _scenarios(self):
        # bucketed (gcd-reducible) memory so the int32 narrowing passes —
        # like the BASELINE workloads; scenario()'s raw random bytes are
        # deliberately int32-ineligible
        out = []
        for seed in range(3):
            rng = np.random.RandomState(100 + seed)
            nodes = [make_node(f"f{seed}-n{i}",
                               milli_cpu=int(rng.choice([2000, 4000])),
                               memory=int(rng.choice([4, 8])) * 1024**3,
                               labels={"zone": f"z{i % 3}"})
                     for i in range(10 + seed)]
            pods = [make_pod(f"f{seed}-p{i}",
                             milli_cpu=int(rng.choice([100, 400, 900])),
                             memory=int(rng.choice([64, 256, 1024]))
                             * 1024 * 1024,
                             node_selector=({"zone": f"z{i % 3}"}
                                            if i % 5 == 0 else None))
                    for i in range(25)]
            out.append((ClusterSnapshot(nodes=nodes), pods))
        return out

    def test_fast_loop_matches_vmap_program(self, monkeypatch):
        scenarios = self._scenarios()
        vmap_results = run_what_if(scenarios)
        from tpusim.jaxe import fastscan

        monkeypatch.setattr(FAST, "mode", lambda: (True, True))
        # 25-pod scenarios are real evidence at this threshold
        monkeypatch.setattr(trust, "PIN_MIN_PODS", 16)
        runs = []
        real = fastscan.fast_scan
        monkeypatch.setattr(
            fastscan, "fast_scan",
            lambda plan, **kw: runs.append(1) or real(plan, **kw))
        fast_results = run_what_if(scenarios)
        assert len(runs) == len(scenarios), "fast loop did not engage"
        for fr, vr in zip(fast_results, vmap_results):
            assert placements_key(fr.placements) == \
                placements_key(vr.placements)
            assert (fr.scheduled, fr.unschedulable) == \
                (vr.scheduled, vr.unschedulable)
        # scenario 0's self-verification pinned process-wide trust
        assert FAST.verified

    def test_ineligible_scenario_keeps_vmap_program(self, monkeypatch):
        scenarios = self._scenarios()
        # make scenario 1 interpod-bound: fast-ineligible
        snap, pods = scenarios[1]
        pods[0] = make_pod(
            "interpod", milli_cpu=100, labels={"app": "a"},
            affinity={"podAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    {"labelSelector": {"matchLabels": {"app": "a"}},
                     "topologyKey": "kubernetes.io/hostname"}]}})
        from tpusim.jaxe import fastscan

        # inter-pod terms are fast-path eligible; a zero topology-key
        # budget makes this scenario's one key exceed it
        monkeypatch.setenv("TPUSIM_FAST_MAX_TOPO_KEYS", "0")
        monkeypatch.setattr(FAST, "mode", lambda: (True, True))
        monkeypatch.setattr(
            fastscan, "fast_scan",
            lambda plan, **kw: (_ for _ in ()).throw(
                AssertionError("fast loop must not engage")))
        results = run_what_if(scenarios)  # falls back to the vmap program
        assert len(results) == len(scenarios)

    def test_kernel_failure_propagates(self, monkeypatch):
        """A kernel that fails to lower is an error, never a quiet re-run
        on the vmap program."""
        scenarios = self._scenarios()
        from tpusim.jaxe import fastscan

        monkeypatch.setattr(FAST, "mode", lambda: (True, True))
        monkeypatch.setattr(
            fastscan, "fast_scan",
            lambda plan, **kw: (_ for _ in ()).throw(
                RuntimeError("mosaic said no")))
        with pytest.raises(RuntimeError, match="mosaic said no"):
            run_what_if(scenarios)

    def test_transient_failure_falls_back_to_vmap(self, monkeypatch):
        """A one-off device OOM reruns the batch on the vmap program, on
        the same device, and leaves the fast path on (ADVICE r4)."""
        scenarios = self._scenarios()
        vmap_results = run_what_if(scenarios)
        from tpusim.jaxe import fastscan

        monkeypatch.setattr(FAST, "mode", lambda: (True, True))
        monkeypatch.setattr(
            fastscan, "fast_scan",
            lambda plan, **kw: (_ for _ in ()).throw(
                RuntimeError("RESOURCE_EXHAUSTED: out of memory")))
        results = run_what_if(scenarios)
        assert FAST.transient == 1
        assert FAST.disabled is False
        for fr, vr in zip(results, vmap_results):
            assert placements_key(fr.placements) == \
                placements_key(vr.placements)


def test_fuzz_what_if_fast_loop_parity(monkeypatch):
    """Randomized eligible batches: the fast loop must match the vmap
    program scenario-for-scenario. TPUSIM_FUZZ_SEEDS scales the sweep."""
    import os
    import random

    from tpusim.jaxe import fastscan

    seeds = min(max(int(os.environ.get("TPUSIM_FUZZ_SEEDS", "2")), 1), 10)
    orig_gate = FAST.mode
    orig_fast = fastscan.fast_scan
    for seed in range(seeds):
        rng = random.Random(7000 + seed)
        scenarios = []
        for s in range(rng.randint(2, 4)):
            nodes = [make_node(f"z{seed}-{s}-n{i}",
                               milli_cpu=rng.choice([1000, 2000, 4000]),
                               memory=rng.choice([2, 4, 8]) * 1024**3,
                               pods=rng.choice([4, 110]),
                               labels={"zone": f"z{i % 2}"})
                     for i in range(rng.randint(3, 8))]
            pods = [make_pod(f"z{seed}-{s}-p{i}",
                             milli_cpu=rng.randrange(1, 10) * 100,
                             memory=rng.randrange(1, 8) * 256 * 1024 * 1024,
                             node_selector=({"zone": f"z{i % 3}"}
                                            if rng.random() < 0.3 else None))
                    for i in range(rng.randint(8, 20))]
            scenarios.append((ClusterSnapshot(nodes=nodes), pods))
        # the reference run must NOT take the fast loop (on TPU the AUTO
        # gate is default-on and earlier tests may have pinned trust)
        monkeypatch.setattr(FAST, "mode", lambda: (False, False))
        vmap_results = run_what_if(scenarios)
        FAST.reset()
        # verify OFF: the fast results must stand on their own — with
        # verification on, a divergence would silently fall back to the
        # vmap program and the parity assert would compare vmap vs vmap
        monkeypatch.setattr(FAST, "mode", lambda: (True, False))
        runs = []
        monkeypatch.setattr(
            fastscan, "fast_scan",
            lambda plan, **kw: runs.append(1) or orig_fast(plan, **kw))
        fast_results = run_what_if(scenarios)
        # un-patch before the next seed's vmap reference run
        monkeypatch.setattr(fastscan, "fast_scan", orig_fast)
        monkeypatch.setattr(FAST, "mode", orig_gate)
        assert runs, f"seed {seed}: fast loop did not engage"
        for i, (fr, vr) in enumerate(zip(fast_results, vmap_results)):
            assert placements_key(fr.placements) == \
                placements_key(vr.placements), f"seed {seed} scenario {i}"


def test_what_if_with_policy_matches_per_scenario_runs():
    """A batch-wide policy: each scenario's what-if placements equal a
    standalone jax policy run over the same snapshot+pods."""
    from tpusim.engine.policy import (
        LabelsPresenceArg,
        Policy,
        PredicateArgument,
        PredicatePolicy,
        PriorityPolicy,
    )
    from tpusim.simulator import run_simulation

    policy = Policy(
        predicates=[
            PredicatePolicy(name="PodFitsResources"),
            PredicatePolicy(name="NeedsDisk", argument=PredicateArgument(
                labels_presence=LabelsPresenceArg(labels=["disktype"],
                                                  presence=True))),
        ],
        priorities=[PriorityPolicy(name="MostRequestedPriority", weight=2)])
    scenarios = []
    for s in range(3):
        nodes = [make_node(f"s{s}-n{i}", milli_cpu=2000 + 1000 * s,
                           labels={"disktype": "ssd"} if i % 2 == 0 else None)
                 for i in range(4 + s)]
        pods = [make_pod(f"s{s}-p{i}", milli_cpu=700) for i in range(6)]
        scenarios.append((ClusterSnapshot(nodes=nodes), pods))

    results = run_what_if([(snap, list(reversed(pods)))
                           for snap, pods in scenarios], policy=policy)
    for (snap, pods), result in zip(scenarios, results):
        solo = run_simulation(list(pods), snap, backend="jax", policy=policy)
        batch_placed = sorted((p.pod.name, p.node_name)
                              for p in result.placements if p.scheduled)
        solo_placed = sorted((p.name, p.spec.node_name)
                             for p in solo.successful_pods)
        assert batch_placed == solo_placed
        # the label predicate held batch-wide
        assert all("-n" in node and int(node.split("-n")[1]) % 2 == 0
                   for _, node in batch_placed)


def test_what_if_rejects_host_bound_policy():
    from tpusim.engine.policy import ExtenderConfig, Policy

    policy = Policy(extender_configs=[ExtenderConfig(url_prefix="http://x",
                                                     filter_verb="filter")])
    snap = ClusterSnapshot(nodes=[make_node("n1", milli_cpu=1000)])
    with pytest.raises(NotImplementedError, match="host-bound"):
        run_what_if([(snap, [make_pod("p", milli_cpu=10)])], policy=policy)


def test_what_if_aca_policy_padding_nodes_stay_invisible():
    """Node-axis padding must not leak into always-check-all reason counts:
    a 2-node scenario batched with a 5-node one reports reasons over 2 nodes
    only."""
    from tpusim.engine.policy import Policy, PredicatePolicy
    from tpusim.simulator import run_simulation

    policy = Policy(predicates=[PredicatePolicy(name="PodFitsResources")],
                    priorities=[], always_check_all_predicates=True)
    small = ClusterSnapshot(nodes=[make_node(f"a{i}", milli_cpu=100)
                                   for i in range(2)])
    big = ClusterSnapshot(nodes=[make_node(f"b{i}", milli_cpu=100)
                                 for i in range(5)])
    pod = make_pod("p", milli_cpu=5000)
    results = run_what_if([(small, [pod]), (big, [pod])], policy=policy)
    msg_small = results[0].placements[0].message
    assert msg_small.startswith("0/2 nodes are available")
    assert "2 Insufficient cpu" in msg_small and "5 " not in msg_small
    assert "Insufficient pods" not in msg_small
    # matches the standalone jax policy run byte-for-byte
    solo = run_simulation([pod], small, backend="jax", policy=policy)
    assert solo.failed_pods[0].status.conditions[-1].message == msg_small


def test_what_if_service_affinity_policy_matches_solo_runs():
    """Service(Anti)Affinity in batched mode: per-scenario locks/domains ride
    the snapshot axis and match standalone jax runs."""
    from tpusim.api.types import Service
    from tpusim.engine.policy import (
        Policy,
        PredicateArgument,
        PredicatePolicy,
        PriorityArgument,
        PriorityPolicy,
        ServiceAffinityArg,
        ServiceAntiAffinityArg,
    )
    from tpusim.simulator import run_simulation

    policy = Policy(
        predicates=[
            PredicatePolicy(name="PodFitsResources"),
            PredicatePolicy(name="ByZone", argument=PredicateArgument(
                service_affinity=ServiceAffinityArg(labels=["zone"]))),
        ],
        priorities=[PriorityPolicy(name="SpreadByZone", weight=2,
                                   argument=PriorityArgument(
                                       service_anti_affinity=
                                       ServiceAntiAffinityArg(label="zone")))])
    svc = Service.from_obj({"metadata": {"name": "db", "namespace": "default"},
                            "spec": {"selector": {"app": "db"}}})
    scenarios = []
    for s in range(3):
        nodes = [make_node(f"s{s}n{i}", milli_cpu=6000,
                           labels={"zone": f"z{i % (2 + s)}"})
                 for i in range(4 + s)]
        seed = make_pod(f"s{s}-seed", milli_cpu=100, node_name=f"s{s}n0",
                        phase="Running", labels={"app": "db"})
        pods = [make_pod(f"s{s}-p{i}", milli_cpu=300,
                         labels={"app": "db"} if i % 2 == 0 else None)
                for i in range(6)]
        scenarios.append((ClusterSnapshot(nodes=nodes, pods=[seed],
                                          services=[svc]), pods))

    results = run_what_if([(snap, list(reversed(pods)))
                           for snap, pods in scenarios], policy=policy)
    for (snap, pods), result in zip(scenarios, results):
        solo = run_simulation(list(pods), snap, backend="jax", policy=policy)
        batch_placed = sorted((p.pod.name, p.node_name)
                              for p in result.placements if p.scheduled)
        solo_placed = sorted((p.name, p.spec.node_name)
                             for p in solo.successful_pods)
        assert batch_placed == solo_placed


@needs_8_devices
def test_cli_what_if_mesh_flag(tmp_path, capsys):
    """`--what-if manifest --mesh 2x4` runs the batch sharded over the
    virtual 8-device mesh and matches the unsharded CLI run."""
    import json

    from tpusim.cli import main

    manifest = []
    for s in range(3):
        snap, _ = scenario(100 + s, 6, 0)
        snap_path = tmp_path / f"snap{s}.json"
        snap.save(str(snap_path))
        podspec = tmp_path / f"pods{s}.yaml"
        podspec.write_text(
            "- name: w\n  num: 5\n  pod:\n    metadata:\n      name: w\n"
            "    spec:\n      containers:\n      - name: c\n"
            "        resources:\n          requests:\n            cpu: 500m\n"
            "            memory: 128Mi\n")
        manifest.append({"snapshot": str(snap_path), "podspec": str(podspec)})
    mpath = tmp_path / "manifest.json"
    mpath.write_text(json.dumps(manifest))

    assert main(["--what-if", str(mpath)]) == 0
    plain = capsys.readouterr().out
    assert main(["--what-if", str(mpath), "--mesh", "2x4"]) == 0
    sharded = capsys.readouterr().out
    # identical per-scenario placement counts, sharded or not
    plain_lines = [line for line in plain.splitlines()
                   if line.startswith("scenario")]
    sharded_lines = [line for line in sharded.splitlines()
                     if line.startswith("scenario")]
    assert plain_lines == sharded_lines


def test_cli_mesh_flag_validation(tmp_path, capsys):
    import json

    from tpusim.cli import main

    snap, pods = scenario(7, 3, 0)
    sp = tmp_path / "s.json"
    snap.save(str(sp))
    podspec = tmp_path / "p.yaml"
    podspec.write_text(
        "- name: w\n  num: 1\n  pod:\n    metadata:\n      name: w\n"
        "    spec:\n      containers:\n      - name: c\n"
        "        resources:\n          requests:\n            cpu: 100m\n")
    mpath = tmp_path / "m.json"
    mpath.write_text(json.dumps([{"snapshot": str(sp),
                                  "podspec": str(podspec)}]))
    assert main(["--what-if", str(mpath), "--mesh", "bogus"]) == 2
    assert "SNAPxNODE" in capsys.readouterr().err
    assert main(["--what-if", str(mpath), "--mesh", "999x9"]) == 2
    assert "devices" in capsys.readouterr().err


def test_cli_mesh_requires_what_if(capsys):
    from tpusim.cli import main

    assert main(["--podspec", "x.yaml", "--mesh", "2x4"]) == 2
    assert "--what-if" in capsys.readouterr().err


def group_scenario(seed: int, num_nodes: int, num_pods: int):
    """Group-bound what-if scenario: services + spreading, inter-pod
    (anti)affinity, host ports, volumes (VERDICT r3 item 4)."""
    from tpusim.api.snapshot import make_pod_volume
    from tpusim.api.types import Service
    from test_jax_groups import port_pod

    rng = np.random.RandomState(seed)
    nodes = [make_node(f"s{seed}-n{i}",
                       milli_cpu=int(rng.choice([4000, 8000])),
                       memory=int(rng.choice([8, 16])) * 1024**3,
                       labels={"zone": f"z{i % 2}",
                               "kubernetes.io/hostname": f"s{seed}-n{i}"})
             for i in range(num_nodes)]
    services = [Service.from_obj(
        {"metadata": {"name": f"s{seed}-svc{k}", "namespace": "default"},
         "spec": {"selector": {"app": f"a{k}"}}}) for k in range(2)]
    placed = [make_pod(f"s{seed}-seed", milli_cpu=100, node_name=f"s{seed}-n0",
                       phase="Running", labels={"app": "a0"})]
    pods = []
    for i in range(num_pods):
        kwargs = {"labels": {"app": f"a{i % 2}"}}
        if i % 4 == 0:
            kwargs["affinity"] = {"podAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [{
                    "labelSelector": {"matchLabels": {"app": f"a{i % 2}"}},
                    "topologyKey": "zone"}]}}
        elif i % 4 == 2:
            kwargs["affinity"] = {"podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [{
                    "labelSelector": {"matchLabels": {"app": f"a{i % 2}"}},
                    "topologyKey": "kubernetes.io/hostname"}]}}
        if i % 5 == 0:
            kwargs["volumes"] = [make_pod_volume(
                "d", source={"gcePersistentDisk": {"pdName": f"pd{i % 3}"}})]
        pods.append(make_pod(f"s{seed}-p{i}",
                             milli_cpu=int(rng.randint(100, 900)),
                             memory=int(rng.randint(2**20, 2**28)), **kwargs))
    pods.append(port_pod(f"s{seed}-port0", 9090))
    pods.append(port_pod(f"s{seed}-port1", 9090))
    return ClusterSnapshot(nodes=nodes, pods=placed, services=services), pods


class TestWhatIfGroupBound:
    @needs_8_devices
    def test_mesh_sharded_group_bound_matches_singleton_runs(self):
        # presence scatters, topo-domain reductions, used_vols, and port
        # masks under a real (snap=2, node=4) mesh, vs single-device runs
        scenarios = [group_scenario(40, 12, 14), group_scenario(41, 8, 10),
                     group_scenario(42, 16, 12)]
        mesh = make_mesh(8, snap=2)
        batched = run_what_if(scenarios, mesh=mesh)
        singles = singleton_results(scenarios)
        assert len(batched) == 3
        for got, want in zip(batched, singles):
            assert placements_key(got.placements) == want

    @needs_8_devices
    def test_mesh_sharded_service_affinity_policy(self):
        # a ServiceAffinity policy rides the sa_lock carry across the mesh
        from tpusim.engine.policy import (
            Policy,
            PredicateArgument,
            PredicatePolicy,
            PriorityPolicy,
            ServiceAffinityArg,
        )
        from tpusim.api.types import Service

        policy = Policy(
            predicates=[
                PredicatePolicy(name="ByZone", argument=PredicateArgument(
                    service_affinity=ServiceAffinityArg(labels=["zone"]))),
                PredicatePolicy(name="PodFitsResources")],
            priorities=[PriorityPolicy(name="LeastRequestedPriority",
                                       weight=1)])

        def sa_scenario(seed):
            rng = np.random.RandomState(seed)
            nodes = [make_node(f"s{seed}-n{i}", milli_cpu=6000,
                               labels={"zone": f"z{i % 3}"})
                     for i in range(9)]
            svc = Service.from_obj(
                {"metadata": {"name": f"s{seed}-db", "namespace": "default"},
                 "spec": {"selector": {"app": "db"}}})
            placed = [make_pod(f"s{seed}-seeddb", milli_cpu=100,
                               node_name=f"s{seed}-n{seed % 3}",
                               phase="Running", labels={"app": "db"})]
            pods = [make_pod(f"s{seed}-p{i}",
                             milli_cpu=int(rng.randint(100, 800)),
                             labels={"app": "db" if i % 2 else "web"})
                    for i in range(10)]
            return (ClusterSnapshot(nodes=nodes, pods=placed,
                                    services=[svc]), pods)

        scenarios = [sa_scenario(50), sa_scenario(51)]
        mesh = make_mesh(8, snap=2)
        batched = run_what_if(scenarios, mesh=mesh, policy=policy)
        backend_singles = []
        from tpusim.backends import get_backend
        backend = get_backend("jax", policy=policy)
        for snap, pods in scenarios:
            backend_singles.append(
                placements_key(backend.schedule(pods, snap)))
        for got, want in zip(batched, backend_singles):
            assert placements_key(got.placements) == want


def test_policy_what_if_fast_loop_matches_vmap(monkeypatch):
    """Round 5: a statically-gateable POLICY batch routes through the
    Pallas fast loop (per-scenario kernels) and matches the batched vmap
    program exactly."""
    import numpy as np

    from tpusim.engine.policy import decode_policy
    from tpusim.jaxe import fastscan

    policy = decode_policy({
        "kind": "Policy", "apiVersion": "v1",
        "predicates": [{"name": "GeneralPredicates"},
                       {"name": "PodToleratesNodeTaints"}],
        "priorities": [{"name": "LeastRequestedPriority", "weight": 1},
                       {"name": "NodeAffinityPriority", "weight": 2}]})
    rng = np.random.RandomState(0)
    scenarios = []
    for s_i in range(3):
        nodes = [make_node(f"n{i}", milli_cpu=4000, memory=16 * 1024**3)
                 for i in range(10 + s_i)]
        pods = [make_pod(f"p{i}", milli_cpu=int(rng.choice([500, 1000])),
                         memory=2**28) for i in range(80)]
        scenarios.append((ClusterSnapshot(nodes=nodes), pods))

    vmap_res = run_what_if(scenarios, policy=policy)

    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    calls = []
    real = fastscan.fast_scan

    def wrapped(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(fastscan, "fast_scan", wrapped)
    fast_res = run_what_if(scenarios, policy=policy)
    assert len(calls) == len(scenarios)
    for a, b in zip(fast_res, vmap_res):
        assert [(p.node_name, p.message) for p in a.placements] \
            == [(p.node_name, p.message) for p in b.placements]

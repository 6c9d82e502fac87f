"""The device paths compile for a TPU v5e, without the chip.

Interpret mode on the CPU accepts what the chip's compiler refuses (64-bit
types in a Mosaic kernel, a lane prefix sum, scalar stores to vector
memory, an int64 max all-reduce). These cases compile each path at the
5,000-node envelope for a described v5e:2x2 (libtpu's compiler is
installed here) and run nothing. The topology is described inside a
fixture, never while a module is imported: only one process may load
libtpu, and every xdist worker imports this file.
"""

import os
import re

import numpy as np
import pytest

import jax
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

NODES = 5_000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _prepared(num_pods=64, policy=None):
    """(config, compiled, cols, ptabs) for the zipf workload at the
    envelope width, or the policy-residue workload with its tables."""
    import bench
    from tpusim.jaxe import ensure_x64

    ensure_x64()
    if policy is not None:
        snap, pods = bench.policy_residue_workload(num_pods, NODES)
        compiled, config, _, _, _, cols, ptabs = bench._prepare_policy(
            snap, pods, policy, to_device=False)
        return config, compiled, cols, ptabs
    from tpusim.api.snapshot import zipf_workload

    snap, pods = zipf_workload(num_pods, NODES)
    compiled, config, _, _, _, cols = bench._prepare(snap, pods,
                                                    to_device=False)
    return config, compiled, cols, None


def _shapes(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        np.shape(a), np.asarray(a).dtype, sharding=sharding), tree)


def test_xla_scan_compiles(one_chip):
    from tpusim.jaxe.kernels import (
        carry_init_host,
        pod_columns_to_host,
        schedule_scan,
        statics_to_host,
    )

    config, compiled, cols, _ = _prepared()
    exe = schedule_scan.lower(
        config, _shapes(carry_init_host(compiled), one_chip),
        _shapes(statics_to_host(compiled), one_chip),
        _shapes(pod_columns_to_host(cols), one_chip)).compile()
    assert exe.memory_analysis().temp_size_in_bytes < 2**30


class _Compiled(Exception):
    pass


def _compile_fast_scan(monkeypatch, one_chip, plan, **kwargs):
    """Drive fast_scan(interpret=False) to its first kernel call, compile
    that call for the described chip, and return the executable."""
    from tpusim.jaxe import fastscan

    build = fastscan._build_call
    out = {}

    def for_chip(*args, **kwargs):
        call = build(*args, **kwargs)

        def compile_first(*operands):
            with jax.enable_x64(False):  # as fast_scan calls it
                lowered = call.lower(*_shapes(list(operands), one_chip))
            out["exe"] = lowered.compile()
            raise _Compiled

        return compile_first

    monkeypatch.setattr(fastscan, "_build_call", for_chip)
    with pytest.raises(_Compiled):
        fastscan.fast_scan(plan, interpret=False, **kwargs)
    # the kernel's op keeps the name the profile readers take for the
    # kernel (benchmark/layers/_scan.py)
    assert re.search(r"^\s*(ROOT )?%tpu_custom_call(\.\d+)? = ",
                     out["exe"].as_text(), re.M)
    return out["exe"]


def test_pallas_group_free_compiles(monkeypatch, one_chip):
    from tpusim.jaxe.fastscan import plan_fast

    config, compiled, cols, _ = _prepared()
    plan, why = plan_fast(config, compiled, cols)
    assert plan is not None, why
    assert plan.num_groups == 0 and plan.policy is None
    exe = _compile_fast_scan(monkeypatch, one_chip, plan)
    assert "tpu_custom_call" in exe.as_text()


# count mode (alwaysCheckAllPredicates), the NoExecute-only taint stage
# and MostRequested scoring: the policy variants POLICY_RESIDUE lacks
COUNT_MODE = {
    "kind": "Policy", "apiVersion": "v1", "alwaysCheckAllPredicates": True,
    "predicates": [
        {"name": "PodFitsResources"},
        {"name": "PodToleratesNodeNoExecuteTaints"},
        {"name": "TestLabelsPresence",
         "argument": {"labelsPresence": {"labels": ["foo"],
                                         "presence": True}}}],
    "priorities": [{"name": "MostRequestedPriority", "weight": 1}],
}


@pytest.mark.parametrize("policy", ["POLICY_RESIDUE", "COUNT_MODE"])
def test_pallas_policy_residue_compiles(monkeypatch, one_chip, policy):
    import bench
    from tpusim.jaxe.fastscan import plan_fast, pol_const_of

    config, compiled, cols, ptabs = _prepared(policy={
        "POLICY_RESIDUE": bench.POLICY_RESIDUE,
        "COUNT_MODE": COUNT_MODE}[policy])
    plan, why = plan_fast(config, compiled, cols, ptabs=ptabs)
    assert plan is not None, why
    assert pol_const_of(plan) is not None
    exe = _compile_fast_scan(monkeypatch, one_chip, plan)
    assert "tpu_custom_call" in exe.as_text()


def _group_workload(features, num_pods=64):
    """A 5,000-node cluster with zone and rack labels, and pods that use
    the pod-group `features` (ports, disk, spread, vol_zone, interpod,
    maxpd): each kernel variant the Pallas route takes for them."""
    from tpusim.api.snapshot import (
        ClusterSnapshot,
        make_node,
        make_pod,
        make_pod_volume,
        make_pv,
        make_pvc,
    )
    from tpusim.api.types import (
        LABEL_ZONE_FAILURE_DOMAIN,
        Affinity,
        ContainerPort,
        Service,
    )

    nodes = [make_node(f"n{i}", milli_cpu=16000, memory=64 * 1024**3,
                       pods=110,
                       labels={LABEL_ZONE_FAILURE_DOMAIN: f"z{i % 3}",
                               "rack": f"r{i % 8}"})
             for i in range(NODES)]
    def term(app, key):
        return {"labelSelector": {"matchLabels": {"app": app}},
                "topologyKey": key}

    existing = [make_pod(f"e{i}", node_name=f"n{i}", phase="Running",
                         milli_cpu=100, labels={"app": f"a{i % 2}"})
                for i in range(6)]
    if "interpod" in features:
        existing[0].spec.affinity = Affinity.from_obj({"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                term("a1", LABEL_ZONE_FAILURE_DOMAIN)]}})
    extra = {}
    if "spread" in features:
        extra["services"] = [Service.from_obj(
            {"metadata": {"name": "web", "namespace": "default"},
             "spec": {"selector": {"app": "a0"}}})]
    if "vol_zone" in features:
        extra["pvs"] = [make_pv(
            "pv-z", labels={LABEL_ZONE_FAILURE_DOMAIN: "z1"})]
        extra["pvcs"] = [make_pvc("claim-z", volume_name="pv-z")]
    pods = []
    for i in range(num_pods):
        kw = {"labels": {"app": f"a{i % 2}"}}
        vols = []
        if "interpod" in features and i % 4 == 0:
            kw["affinity"] = {
                "podAntiAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [
                        term("a1", "rack")]},
                "podAffinity": {
                    "preferredDuringSchedulingIgnoredDuringExecution": [
                        {"weight": 10, "podAffinityTerm": term(
                            "a0", LABEL_ZONE_FAILURE_DOMAIN)}]}}
        if "interpod" in features and i % 4 == 2:
            kw["affinity"] = {
                "podAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [
                        term("a0", "rack")]},
                "podAntiAffinity": {
                    "preferredDuringSchedulingIgnoredDuringExecution": [
                        {"weight": 5, "podAffinityTerm": term(
                            "a1", "rack")}]}}
        if "vol_zone" in features and i % 5 == 0:
            vols.append(make_pod_volume("z", pvc="claim-z"))
        if "disk" in features and i % 3 == 0:
            vols.append(make_pod_volume("d", {"rbd": {
                "monitors": ["m"], "pool": "p", "image": f"x{i % 2}"}}))
        if "maxpd" in features and i % 2 == 0:
            vols.append(make_pod_volume("b", {"awsElasticBlockStore": {
                "volumeID": f"ebs{i % 3}"}}))
        p = make_pod(f"p{i}", milli_cpu=(1 + i % 7) * 100,
                     memory=(1 + i % 5) * 2**26, volumes=vols or None, **kw)
        if "ports" in features and i % 4 == 1:
            p.spec.containers[0].ports = [ContainerPort.from_obj(
                {"containerPort": 80, "hostPort": 8080 + i % 2})]
        pods.append(p)
    return ClusterSnapshot(nodes=nodes, pods=existing, **extra), pods


GROUP_FEATURES = ("ports", "disk", "spread", "vol_zone", "interpod", "maxpd")


@pytest.mark.parametrize("features", [
    *((f,) for f in GROUP_FEATURES), GROUP_FEATURES],
    ids=[*GROUP_FEATURES, "every_group_feature"])
def test_pallas_group_variant_compiles(monkeypatch, one_chip, features):
    """Each pod-group feature's kernel variant (presence carry, inter-pod
    domain carry, volume-count carry), and all of them in one kernel."""
    import bench
    from tpusim.jaxe import ensure_x64
    from tpusim.jaxe.fastscan import ip_const_of, mp_const_of, plan_fast

    ensure_x64()
    snap, pods = _group_workload(set(features))
    compiled, config, _, _, _, cols = bench._prepare(snap, pods,
                                                    to_device=False)
    assert not compiled.unsupported, compiled.unsupported
    plan, why = plan_fast(config, compiled, cols)
    assert plan is not None, why
    assert plan.num_groups > 0 or plan.has_vol_zone or \
        mp_const_of(plan) is not None
    assert (ip_const_of(plan) is not None) == ("interpod" in features)
    assert (mp_const_of(plan) is not None) == ("maxpd" in features)
    exe = _compile_fast_scan(monkeypatch, one_chip, plan)
    assert "tpu_custom_call" in exe.as_text()


def test_pallas_hostname_antiaffinity_compiles(monkeypatch, one_chip):
    """scheduler_perf SchedulingPodAntiAffinity at the envelope: 5,000
    nodes each labelled with its hostname, 1,000 bound and 1,000 pending
    pods under required hostname anti-affinity. The hostname key is
    node-wise, so the variant carries no presence_dom."""
    from tpusim.api.snapshot import ClusterSnapshot, make_node, make_pod
    from tpusim.jaxe import ensure_x64
    from tpusim.jaxe.fastscan import ip_const_of, plan_fast
    from tpusim.jaxe.kernels import config_for
    from tpusim.jaxe.state import NUM_FIXED_BITS, compile_cluster

    ensure_x64()
    aff = {"podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [{
            "labelSelector": {"matchLabels": {"color": "green"}},
            "topologyKey": "kubernetes.io/hostname",
            "namespaces": ["sched-1", "sched-0"]}]}}
    labels = {"color": "green", "name": "test"}
    nodes = [make_node(f"node-{i}", milli_cpu=4000, memory=32 * 2**30)
             for i in range(NODES)]
    bound = [make_pod(f"init-{j}", milli_cpu=100, memory=500 * 2**20,
                      namespace="sched-0", labels=labels, affinity=aff,
                      node_name=f"node-{5 * j}", phase="Running")
             for j in range(1000)]
    pods = [make_pod(f"measure-{j}", milli_cpu=100, memory=500 * 2**20,
                     namespace="sched-1", labels=labels, affinity=aff)
            for j in range(1000)]
    compiled, cols = compile_cluster(ClusterSnapshot(nodes=nodes,
                                                     pods=bound), pods)
    config = config_for([compiled], most_requested=False,
                        num_reason_bits=NUM_FIXED_BITS)
    plan, why = plan_fast(config, compiled, cols)
    assert plan is not None, why
    ip = ip_const_of(plan)
    assert ip.nodewise == (True,) and ip.kd == 0
    exe = _compile_fast_scan(monkeypatch, one_chip, plan)
    assert "tpu_custom_call" in exe.as_text()


def test_pallas_preempt_carry_compiles(monkeypatch, one_chip):
    """The preemption hybrid's call: a plan over placed pods, resumed from
    an explicit carry, one power-of-two bucket, carry returned."""
    import bench
    from tpusim.api.snapshot import zipf_workload
    from tpusim.jaxe import ensure_x64
    from tpusim.jaxe.fastscan import init_carry, plan_fast

    ensure_x64()
    snap, pods = zipf_workload(64, NODES, priorities=True)
    compiled, config, _, _, _, cols = bench._prepare(snap, pods,
                                                    to_device=False)
    plan, why = plan_fast(config, compiled, cols, placed_pods=snap.pods)
    assert plan is not None, why
    exe = _compile_fast_scan(
        monkeypatch, one_chip, plan, chunk=16, start=8, stop=20,
        carry_in=init_carry(plan, rr=3), return_carry=True,
        fixed_chunk=True)
    assert "tpu_custom_call" in exe.as_text()


def test_node_sharded_scan_compiles(topo):
    from dataclasses import replace

    from tpusim.jaxe.kernels import (
        carry_init_host,
        node_partition_specs,
        pod_columns_to_host,
        sharded_scan_fn,
        statics_to_host,
    )
    from tpusim.jaxe.sharding import pad_node_axis

    config, compiled, cols, _ = _prepared()
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("snap", "node"))
    st, ca, _ = pad_node_axis(statics_to_host(compiled),
                              carry_init_host(compiled), 4)
    st_specs, ca_specs, _ = node_partition_specs("node")

    def placed(tree, specs):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            np.shape(a), np.asarray(a).dtype,
            sharding=NamedSharding(mesh, s)), tree, specs)

    exe = sharded_scan_fn(replace(config, shard_axis="node"), mesh).lower(
        placed(ca, ca_specs), placed(st, st_specs),
        _shapes(pod_columns_to_host(cols), NamedSharding(mesh, P()))
    ).compile()
    assert "all-reduce" in exe.as_text()

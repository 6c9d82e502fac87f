"""compile_cluster stages node-side work once per class of nodes that read
alike. Its tables must equal, cell for cell, those of the incremental path
(jaxe.delta.IncrementalCluster), which still evaluates every (signature,
node) cell and every node's static row on its own."""

import json
import random

import numpy as np
import pytest

from tpusim.api.snapshot import ClusterSnapshot, make_node, make_pod
from tpusim.api.types import Node, Pod
from tpusim.engine.priorities import PREFER_AVOID_PODS_ANNOTATION
from tpusim.jaxe.delta import IncrementalCluster
from tpusim.jaxe.state import compile_cluster

SHAPES = [  # (cpu, memory, pods, extra allocatable)
    ("4", "32Gi", "110", {}),
    ("8", "64Gi", "110", {"example.com/fpga": "2"}),
    ("16", "128Gi", "250", {"alpha.kubernetes.io/nvidia-gpu": "4"}),
    ("4000m", "34359738368", "110", {}),  # shape 0 written another way
    ("4", "16Gi", "110", {}),  # shape 0's resources, one value apart
]
TAINTS = [
    {"key": "dedicated", "value": "infra", "effect": "NoSchedule"},
    {"key": "spot", "value": "true", "effect": "PreferNoSchedule"},
    {"key": "maint", "value": "soon", "effect": "NoExecute"},
    {"key": "gpu", "value": "yes", "effect": "NoSchedule"},
    {"key": "dedicated", "value": "infra", "effect": "PreferNoSchedule"},
]
CONDITIONS = [
    ("Ready", ["True", "False", "Unknown"]),
    ("OutOfDisk", ["False", "True"]),
    ("MemoryPressure", ["False", "True"]),
    ("DiskPressure", ["False", "True"]),
    ("NetworkUnavailable", ["False", "True"]),
]
RC_UIDS = ["rc-a", "rc-b"]

# (table, the pod column that holds its signature id)
TABLES = [("selector_ok", "sel_id"), ("taint_ok", "tol_id"),
          ("taint_ok_noexec", "tol_id"), ("intolerable", "tol_id"),
          ("affinity_count", "aff_id"), ("avoid_score", "avoid_id"),
          ("host_ok", "host_id")]


def _node(rnd: random.Random, i: int) -> Node:
    cpu, mem, pods, extra = rnd.choice(SHAPES)
    alloc = {"cpu": cpu, "memory": mem, "pods": pods, **extra}
    labels = {"kubernetes.io/hostname": f"n{i}", "zone": f"z{rnd.randrange(3)}",
              "gen": str(rnd.randrange(1, 6))}
    rack = rnd.random()
    if rack < 0.6:
        labels["rack"] = f"r{rnd.randrange(6)}"
    elif rack < 0.7:
        labels["rack"] = ""  # present and empty, unlike a missing label
    conditions = [{"type": "Ready", "status": "True"}]
    if rnd.random() < 0.4:
        kind, statuses = rnd.choice(CONDITIONS)
        conditions = [{"type": kind, "status": rnd.choice(statuses)}]
        if kind != "Ready":
            conditions.append({"type": "Ready", "status": "True"})
    annotations = {}
    if rnd.random() < 0.2:
        annotations[PREFER_AVOID_PODS_ANNOTATION] = json.dumps({
            "preferAvoidPods": [{"podSignature": {"podController": {
                "kind": "ReplicationController", "uid": rnd.choice(RC_UIDS)}}}]})
    spec = {}
    if rnd.random() < 0.1:
        spec["unschedulable"] = True
    if rnd.random() < 0.4:
        spec["taints"] = rnd.sample(TAINTS, rnd.randrange(1, 3))
    return Node.from_obj({
        "metadata": {"name": f"n{i}", "labels": labels,
                     "annotations": annotations},
        "spec": spec,
        "status": {"capacity": dict(alloc), "allocatable": alloc,
                   "conditions": conditions}})


def _requirement(rnd: random.Random) -> dict:
    return rnd.choice([
        {"key": "zone", "operator": "In", "values": ["z0", "z2"]},
        {"key": "rack", "operator": "NotIn", "values": ["r1"]},
        {"key": "rack", "operator": "Exists"},
        {"key": "rack", "operator": "DoesNotExist"},
        {"key": "gen", "operator": "Gt", "values": ["2"]},
        {"key": "gen", "operator": "Lt", "values": ["4"]},
        {"key": "gen", "operator": "Gt", "values": ["two"]},  # invalid selector
        {"key": "kubernetes.io/hostname", "operator": "In",
         "values": ["n1", "n3", "n5"]},
    ])


def _pending(rnd: random.Random, j: int, n_nodes: int) -> Pod:
    spec = {"containers": [{"name": "c", "resources": {"requests": {
        "cpu": f"{rnd.choice([100, 500, 2000])}m", "memory": "256Mi",
        **({"example.com/fpga": "1"} if rnd.random() < 0.1 else {})}}}]}
    if rnd.random() < 0.3:
        spec["nodeSelector"] = rnd.choice([{"zone": "z1"}, {"rack": "r2"},
                                           {"zone": "z0", "rack": "r0"}])
    node_affinity = {}
    if rnd.random() < 0.4:
        node_affinity["requiredDuringSchedulingIgnoredDuringExecution"] = {
            "nodeSelectorTerms": [
                {"matchExpressions": [_requirement(rnd)
                                      for _ in range(rnd.randrange(1, 3))]}
                for _ in range(rnd.randrange(1, 3))]}
    if rnd.random() < 0.4:
        node_affinity["preferredDuringSchedulingIgnoredDuringExecution"] = [
            {"weight": rnd.choice([0, 1, 5, 20]),
             "preference": {"matchExpressions": [_requirement(rnd)]}}
            for _ in range(rnd.randrange(1, 3))]
    if node_affinity:
        spec["affinity"] = {"nodeAffinity": node_affinity}
    if rnd.random() < 0.5:
        spec["tolerations"] = [rnd.choice([
            {"key": t["key"], "operator": "Equal", "value": t["value"],
             "effect": t["effect"]},
            {"key": t["key"], "operator": "Exists"},
            {"key": t["key"], "operator": "Equal", "value": t["value"]},
        ]) for t in rnd.sample(TAINTS, rnd.randrange(1, 3))]
    if rnd.random() < 0.1:
        spec["nodeName"] = f"n{rnd.randrange(n_nodes + 2)}"  # may not exist
    metadata = {"name": f"p{j}", "namespace": "default", "uid": f"p{j}"}
    if rnd.random() < 0.4:
        kind = rnd.choice(["ReplicationController", "ReplicaSet", "Deployment"])
        metadata["ownerReferences"] = [{
            "kind": kind, "name": "owner", "controller": True,
            "uid": rnd.choice(RC_UIDS)}]
    return Pod.from_obj({"metadata": metadata, "spec": spec, "status": {}})


def _cluster(seed: int):
    rnd = random.Random(seed)
    n = rnd.randrange(50, 301)
    nodes = [_node(rnd, i) for i in range(n)]
    placed = [make_pod(f"run{k}", milli_cpu=rnd.choice([100, 250]),
                       memory=2**27, node_name=f"n{rnd.randrange(n)}",
                       phase="Running",
                       scalars={"example.com/fpga": 1} if k % 7 == 0 else None)
              for k in range(rnd.randrange(0, 3 * n))]
    pods = [_pending(rnd, j, n) for j in range(rnd.randrange(20, 80))]
    return ClusterSnapshot(nodes=nodes, pods=placed), pods


def _by_name(arr: np.ndarray, names) -> dict:
    return {name: arr[:, k] for k, name in enumerate(names)}


def _assert_same(compiled, cols, oracle, oracle_cols):
    """Every column of the statics and the dynamic aggregates, and every
    table gathered per pod through its signature ids, is equal."""
    for part in ("statics", "dynamic"):
        ours, theirs = getattr(compiled, part), getattr(oracle, part)
        for name, value in vars(ours).items():
            other = getattr(theirs, name)
            if name in ("alloc_scalar", "used_scalar"):
                # the two paths name scalar resources in different orders
                value = _by_name(value, compiled.scalar_names)
                other = _by_name(other, oracle.scalar_names)
                assert value.keys() == other.keys(), name
                for res in value:
                    np.testing.assert_array_equal(value[res], other[res],
                                                  err_msg=f"{name}[{res}]")
            elif isinstance(value, np.ndarray):
                assert value.dtype == other.dtype, name
                np.testing.assert_array_equal(value, other, err_msg=name)
            else:
                assert value == other, name
    for table, id_col in TABLES:
        ours = getattr(compiled.tables, table)[getattr(cols, id_col)]
        theirs = getattr(oracle.tables, table)[getattr(oracle_cols, id_col)]
        assert ours.dtype == theirs.dtype, table
        np.testing.assert_array_equal(ours, theirs, err_msg=table)


@pytest.mark.parametrize("need_noexec", [False, True])
@pytest.mark.parametrize("seed", range(8))
def test_tables_equal_the_per_node_oracle(seed, need_noexec):
    snap, pods = _cluster(seed)
    compiled, cols = compile_cluster(snap, pods, need_noexec=need_noexec)
    oracle, oracle_cols = IncrementalCluster(snap).compile(
        pods, need_noexec=need_noexec)
    _assert_same(compiled, cols, oracle, oracle_cols)
    # the random clusters repeat shapes, so classes are fewer than nodes,
    # and the matchers ran on fewer cells than the tables hold
    n = len(snap.nodes)
    assert 1 < compiled.node_classes < n
    cells = sum(getattr(compiled.tables, t).size for t, _ in TABLES
                if need_noexec or t != "taint_ok_noexec")
    assert 0 < compiled.row_evals < cells


@pytest.mark.parametrize("need_noexec", [False, True])
def test_uniform_cluster_is_one_class(need_noexec):
    nodes = [make_node(f"node-{i}", milli_cpu=4000, memory=32 * 2**30)
             for i in range(5000)]
    pods = [make_pod(f"p{j}", milli_cpu=100, memory=500 * 2**20)
            for j in range(10)]
    compiled, _ = compile_cluster(ClusterSnapshot(nodes=nodes), pods,
                                  need_noexec=need_noexec)
    assert compiled.node_classes == 1
    # one signature per table, one group each: six tables, seven with the
    # NoExecute one
    assert compiled.row_evals == (7 if need_noexec else 6)
    assert compiled.tables.selector_ok.shape == (1, 5000)
    assert compiled.tables.selector_ok.all()
    assert (compiled.statics.alloc_cpu == 4000).all()


@pytest.mark.parametrize("need_noexec", [False, True])
def test_every_node_its_own_class(need_noexec):
    n = 40
    nodes = [make_node(f"n{i}", taints=[{
        "key": f"k{i}", "value": "v",
        "effect": TAINTS[i % 3]["effect"]}]) for i in range(n)]
    tolerations = [None, [{"key": "k3", "operator": "Exists"}],
                   [{"operator": "Exists"}],
                   [{"key": "k5", "operator": "Equal", "value": "v"}]]
    pods = [make_pod(f"p{j}", milli_cpu=100, tolerations=tol)
            for j, tol in enumerate(tolerations)]
    snap = ClusterSnapshot(nodes=nodes)
    compiled, cols = compile_cluster(snap, pods, need_noexec=need_noexec)
    assert compiled.node_classes == n
    # each toleration signature meets n taint sets in every taint table
    taint_tables = 3 if need_noexec else 2
    assert compiled.row_evals == 4 * taint_tables * n + 4
    oracle, oracle_cols = IncrementalCluster(snap).compile(
        pods, need_noexec=need_noexec)
    _assert_same(compiled, cols, oracle, oracle_cols)


def test_no_state_between_calls():
    """The same node objects, changed between two calls, give the second
    call's answer: nothing of the first call is kept."""
    nodes = [make_node(f"n{i}", labels={"zone": "a"}) for i in range(6)]
    pods = [make_pod("p", milli_cpu=100, node_selector={"zone": "b"})]
    snap = ClusterSnapshot(nodes=nodes)
    first, _ = compile_cluster(snap, pods)
    assert not first.tables.selector_ok.any()
    nodes[4].metadata.labels["zone"] = "b"
    nodes[2].spec.unschedulable = True
    second, cols = compile_cluster(snap, pods)
    assert second.tables.selector_ok[0].tolist() == [False] * 4 + [True, False]
    assert second.node_classes == 2
    oracle, oracle_cols = IncrementalCluster(snap).compile(pods)
    _assert_same(second, cols, oracle, oracle_cols)

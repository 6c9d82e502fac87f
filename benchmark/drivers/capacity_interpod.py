"""Capacity queries on a deployment whose pods carry required inter-pod
anti-affinity: closed loop, one client. Query q asks
`run_simulation(backlog_q, snapshot_q, backend="jax")`: the snapshot holds
the configuration's nodes and its bound (init) pods, placed one to a node
on distinct nodes drawn from (seed, q) — in scheduler_perf the scheduler
places them, and under their term each lands alone — and the backlog holds
`backlog_pods` measure pods. Every pod is the configuration's pod
template (labels, namespaces, anti-affinity terms, container ports), the
init pods in one namespace and the measure pods in the other. Nothing is
committed. The client builds each query's objects off the clock
(Window.thinking) and holds one query at a time.

The Pallas kernel is this cell's path: a `fast_fallback` (plan_fast
refusing a batch, which then runs on the XLA scan) in the warm-up stops the
run before its window, and check() reports the fallbacks of warm-up and
window as `fast_fallbacks`, limit 0."""

from __future__ import annotations

import gc
from typing import Dict, List

import numpy as np

from benchmark import cluster as C
from benchmark import guard
from benchmark import reference_interpod as RI
from benchmark.traffic import Check, Window, span, status_answers

INIT, MEASURE = 0, 1      # the two pod kinds: init and measure pods


def kinds(config: dict) -> List[RI.Kind]:
    """The pod template as the reference's kinds, init then measure."""
    t = config["pod_template"]
    anti = tuple(RI.term(a["namespaces"], a["match_labels"],
                         a["topology_key"]) for a in t["anti_affinity"])
    labels = tuple(sorted(t["labels"].items()))
    return [RI.Kind(config["namespaces"][role], labels, anti=anti)
            for role in ("init", "measure")]


def pod_objects(config: dict, pods: C.Pods, role: str,
                node_names=None) -> list:
    """The template's pods in the role's namespace: pending, or Running
    on node_names[j]."""
    from tpusim.api.types import Pod

    t = config["pod_template"]
    affinity = {"podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [
            {"labelSelector": {"matchLabels": dict(a["match_labels"])},
             "topologyKey": a["topology_key"],
             "namespaces": list(a["namespaces"])}
            for a in t["anti_affinity"]]}}
    ports = [{"containerPort": p} for p in t["container_ports"]]
    ns = config["namespaces"][role]
    out = []
    for j, name in enumerate(pods.names):
        req = {"cpu": f"{int(pods.cpu[j])}m", "memory": str(int(pods.mem[j]))}
        res = {"requests": req}
        if t["limits_equal_requests"]:
            res["limits"] = dict(req)
        spec = {"containers": [{"name": "pause", "ports": ports,
                                "resources": res}],
                "affinity": affinity}
        status = {}
        if node_names is not None:
            spec["nodeName"] = node_names[j]
            status["phase"] = "Running"
        out.append(Pod.from_obj({
            "metadata": {"name": name, "namespace": ns, "uid": f"{ns}/{name}",
                         "labels": dict(t["labels"])},
            "spec": spec, "status": status}))
    return out


def scan_shape(config: dict, nodes: C.Nodes) -> dict:
    """What benchmark/roofline_interpod.py counts beyond a plain scan,
    from the configuration alone: the presence rows, the match bits per
    pod, and the domain cells of the keys other than the hostname."""
    ks = kinds(config)
    groups = RI.groups_told_apart(ks)
    terms = max(len(k.affinity) + len(k.anti) for k in ks)
    labels = RI.node_labels(nodes)
    keys = {t.key for k in ks for t in k.affinity + k.anti} - {RI.HOSTNAME}
    doms = sum(len({lab[key] for lab in labels if key in lab})
               for key in keys)
    # own terms against each group, and each group's terms against me
    return {"groups": groups, "match_bits": 2 * groups * terms,
            "dom_cells": groups * doms}


def fallbacks(before: dict, after: dict) -> int:
    return int(sum(guard.diff(before, after)["fast_fallback"].values()))


class Driver:
    def __init__(self, cluster: C.Cluster, traffic: dict, seed: int):
        self.cl = cluster
        self.traffic = traffic
        self.seed = seed
        self.n = int(traffic["backlog_pods"])
        self.q = 0                                # queries asked so far
        self.answers: List[Dict[str, str]] = []   # per query asked
        self.fast_fallbacks = 0
        self.shape = scan_shape(cluster.config, cluster.nodes)

    def query(self, q: int):
        """Query q's cluster (its init pods one to a node) and backlog, as
        numbers."""
        cfg = self.cl.config
        gen = C.rng(self.seed, C.BOUND, q)
        bound = C.draw_pods(cfg, gen, int(cfg["bound_pods"]), "init")
        where = gen.choice(len(self.cl.nodes), size=len(bound),
                           replace=False).astype(np.int64)
        return (C.Cluster(cfg, self.cl.nodes, bound, where),
                C.draw_pods(cfg, C.rng(self.seed, C.BACKLOG, q), self.n,
                            f"q{q}"))

    def setup(self) -> None:
        self.nodes = C.node_objects(self.cl)
        self._g0 = guard.read()
        w = Window()
        for _ in range(int(self.traffic["warmup_queries"])):
            self._query(w)
        self.fast_fallbacks = fallbacks(self._g0, guard.read())
        if self.fast_fallbacks:
            raise RuntimeError(
                f"the capacity path left the Pallas kernel in the warm-up "
                f"({self.fast_fallbacks} fast_fallback): this cell measures "
                "the kernel, not the XLA scan")

    def _query(self, w: Window) -> None:
        from tpusim.api.snapshot import ClusterSnapshot
        from tpusim.simulator import run_simulation

        cfg = self.cl.config
        with w.thinking():
            cl, backlog = self.query(self.q)
            names = cl.nodes.names
            snap = ClusterSnapshot(nodes=list(self.nodes), pods=pod_objects(
                cfg, cl.bound, "init", [names[i] for i in cl.bound_node]))
            pods = pod_objects(cfg, backlog, "measure")
        with w.op("query"), span("bench:query"):
            status = run_simulation(pods, snap, backend="jax")
        with span("bench:answers"):
            decided = len(status.successful_pods) + len(status.failed_pods)
            w.decided += decided
            w.scans.append({"pods": decided, "nodes": len(self.cl.nodes),
                            **self.shape})
            self.answers.append(status_answers(status))
            del status, pods, snap
        self.q += 1

    def window(self, seconds: float) -> Window:
        w = Window()
        w.open()
        while w.running_s() < seconds:
            self._query(w)
        w.close()
        self.fast_fallbacks = fallbacks(self._g0, guard.read())
        return w

    def release(self) -> None:
        self.nodes = None
        gc.collect()

    def reference_answers(self, q: int, tie: str = "rr") -> Dict[str, str]:
        """The reference's answers to query q (run_simulation decides the
        list last-first)."""
        cl, pods = self.query(q)
        s = RI.for_nodes(cl.nodes, RI.node_labels(cl.nodes),
                         kinds(cl.config), tie=tie)
        s.place_many(cl.bound_node, cl.bound.cpu, cl.bound.mem,
                     np.full(len(cl.bound), INIT))
        order = np.arange(len(pods))[::-1]
        ch, err = s.schedule(pods.cpu[order], pods.mem[order],
                             pods.tolerates[order],
                             np.full(len(order), MEASURE))
        return RI.R.answers([pods.names[j] for j in order],
                            self.cl.nodes.names, ch, err)

    def stand_in(self, ops: int, tie: str) -> None:
        """The answers of `ops` queries as the reference under `tie` gives
        them, in the program's place (benchmark/control.py)."""
        self.answers = [self.reference_answers(q, tie) for q in range(ops)]
        self.q = ops

    def check(self) -> Check:
        bad = compared = 0
        for q, got in enumerate(self.answers):
            want = self.reference_answers(q)
            bad += RI.R.mismatches(got, want)
            compared += len(want)
        return Check({"wrong_answers": bad,
                      "fast_fallbacks": self.fast_fallbacks}, compared)

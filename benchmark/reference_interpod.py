"""The plain reference with inter-pod terms: the Kubernetes 1.10 default
scheduler's decisions where pods carry required pod (anti-)affinity, in
numpy, importing nothing of the program.

On top of benchmark/reference.py (the resource filters, the taint filter,
LeastRequested + BalancedResourceAllocation, round-robin ties, the FitError
text) it adds the MatchInterPodAffinity predicate, last in the 1.10
predicate order (predicates.go predicatesOrdering), as predicates.go's
InterPodAffinityMatches gives it:

1. the existing pods' anti-affinity first (satisfiesExistingPodsAntiAffinity):
   a node fails when a placed pod has a required anti-affinity term that
   matches the pod (the term's namespaces, or the placed pod's own where
   the term names none, and its matchLabels) and the node and the placed
   pod's node carry the same value of the term's topology key
   (NodesHaveSameTopologyKey: a node without the label never matches);
2. then the pod's own required affinity terms
   (satisfiesPodsAffinityAntiAffinity): a term holds on a node where a
   matching pod sits in the node's domain; where no matching pod exists
   at all, a pod that matches its own term may go anywhere (the first pod
   of its group); otherwise the node fails;
3. then the pod's own required anti-affinity terms: a node fails where a
   matching pod sits in its domain.

For a kubernetes.io/hostname key the pod's own terms look only at the
node's own pods (predicates.go anyPodMatchesPodAffinityTerm), so "a
matching pod exists" is per node there. A failing node reports two reasons
(error.go): "node(s) didn't match pod affinity/anti-affinity" and the rule
that failed first, in the order above.

Scores. InterPodAffinityPriority (priorities/interpod_affinity.go) counts
only preferred terms, of the pod and of the placed pods, and the placed
pods' required affinity terms times hardPodAffinitySymmetricWeight; a
required anti-affinity term is never scored. Where none of those exists
every node's count is 0 and the priority gives every node 0, so the scores
of benchmark/reference.py decide. Kinds carry no preferred terms, and the
reference refuses to decide a pod that a placed pod's required affinity
term matches: under a nonzero hard weight that term would score. An empty
topology key is refused too.

`tie="first"` is the control, as in benchmark/reference.py.

For speed the per-class score memo of benchmark/reference.py is kept, one
memo per pod kind: a bind marks changed every node that shares a domain
with the chosen node under a key some term reads (only that node, for a
hostname key whose values are one per node), and every node where the
bind can turn "no matching pod exists" false for an affinity term.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, FrozenSet, List, Sequence, Tuple

import numpy as np

from benchmark import reference as R

HOSTNAME = "kubernetes.io/hostname"
UMBRELLA = "node(s) didn't match pod affinity/anti-affinity"
EXISTING_ANTI = "node(s) didn't satisfy existing pods anti-affinity rules"
AFFINITY = "node(s) didn't match pod affinity rules"
ANTI = "node(s) didn't match pod anti-affinity rules"
OK, FAIL_EXISTING, FAIL_AFFINITY, FAIL_ANTI = 0, 1, 2, 3
REASONS = {FAIL_EXISTING: EXISTING_ANTI, FAIL_AFFINITY: AFFINITY,
           FAIL_ANTI: ANTI}


@dataclass(frozen=True)
class Term:
    """A required pod (anti-)affinity term with a matchLabels selector."""
    namespaces: FrozenSet[str]        # empty: the owning pod's namespace
    match_labels: Tuple[Tuple[str, str], ...]
    key: str

    def matches(self, owner_ns: str, ns: str, labels: Dict[str, str]) -> bool:
        """Whether a pod in `ns` with `labels` matches the term of a pod
        in `owner_ns` (PodMatchesTermsNamespaceAndSelector)."""
        if ns not in (self.namespaces or {owner_ns}):
            return False
        return all(labels.get(k) == v for k, v in self.match_labels)


@dataclass(frozen=True)
class Kind:
    """Pods alike for the inter-pod predicate."""
    namespace: str
    labels: Tuple[Tuple[str, str], ...]
    affinity: Tuple[Term, ...] = ()
    anti: Tuple[Term, ...] = ()

    def matched_by(self, owner: "Kind", t: Term) -> bool:
        return t.matches(owner.namespace, self.namespace, dict(self.labels))


def term(namespaces: Sequence[str], match_labels: Dict[str, str],
         key: str) -> Term:
    if not key:
        raise ValueError("the reference takes no empty topologyKey")
    return Term(frozenset(namespaces), tuple(sorted(match_labels.items())),
                key)


class Scheduler(R.Scheduler):
    """benchmark/reference.py's scheduler with the inter-pod predicate."""

    def __init__(self, alloc_cpu, alloc_mem, alloc_pods, tainted,
                 node_labels: List[Dict[str, str]], kinds: List[Kind],
                 tie: str = "rr", hard_weight: int = 10):
        super().__init__(alloc_cpu, alloc_mem, alloc_pods, tainted, tie=tie)
        self.hard_weight = hard_weight
        nk = len(kinds)
        self.on_node = np.zeros((nk, self.n), np.int64)
        self.total = np.zeros(nk, np.int64)
        # every topology key some term reads: value ids per node (0: no
        # label), the nodes of each value, the placed pods per kind and value
        keys = sorted({t.key for k in kinds for t in k.affinity + k.anti})
        self.dom: Dict[str, np.ndarray] = {}
        self.members: Dict[str, List[np.ndarray]] = {}
        self.dom_count: Dict[str, np.ndarray] = {}
        for key in keys:
            ids: Dict[str, int] = {}
            d = np.array([ids.setdefault(lab[key], len(ids) + 1)
                          if key in lab else 0 for lab in node_labels],
                         np.int64)
            self.dom[key] = d
            self.members[key] = [np.flatnonzero(d == v)
                                 for v in range(len(ids) + 1)]
            self.dom_count[key] = np.zeros((nk, len(ids) + 1), np.int64)
        self.dom_list = {key: d.tolist() for key, d in self.dom.items()}
        # match[a][b]: kind b matches term t of kind a, per term kind
        self.ex_anti = [[(t, [b for b, kb in enumerate(kinds)
                              if kb.matched_by(ka, t)]) for t in ka.anti]
                        for ka in kinds]
        self.own_aff = [[(t, [b for b, kb in enumerate(kinds)
                              if kb.matched_by(ka, t)],
                          ka.matched_by(ka, t)) for t in ka.affinity]
                        for ka in kinds]
        # existing-side anti terms matching kind p: (owner kind, term)
        self.hits_me = [[(a, t) for a in range(nk) for t, bs in
                         self.ex_anti[a] if p in bs] for p in range(nk)]
        # kinds whose required affinity terms match kind p: they would score
        self.scored_by = [[a for a in range(nk) for _, bs, _ in
                           self.own_aff[a] if p in bs] for p in range(nk)]
        # kinds some non-hostname affinity term matches: their first bind
        # flips "no matching pod exists" for that term on every node
        self.global_exists = {b for a in range(nk)
                              for t, bs, _ in self.own_aff[a]
                              if t.key != HOSTNAME for b in bs}
        self._memos: Dict[int, dict] = {}
        self._kind = 0

    def _use(self, kind: int) -> None:
        if self.hard_weight and any(self.total[a]
                                    for a in self.scored_by[kind]):
            raise ValueError("a placed pod's required affinity term matches "
                             "the pod and would score under "
                             "hardPodAffinitySymmetricWeight: not modelled")
        self._kind = kind
        self._memo = self._memos.setdefault(kind, {})

    # -- state ---------------------------------------------------------------
    def place(self, node: int, cpu: int, mem: int, kind: int) -> None:
        """Bind one pod of `kind` at `node`, marking what it changes."""
        self.add(node, cpu, mem)
        self.on_node[kind, node] += 1
        self.total[kind] += 1
        for key, d in self.dom.items():
            v = d[node]
            if v:
                self.dom_count[key][kind, v] += 1
                if len(self.members[key][v]) > 1:
                    self.changed.extend(self.members[key][v].tolist())
        if kind in self.global_exists and self.total[kind] == 1:
            self.changed.extend(range(self.n))

    def place_many(self, nodes, cpu, mem, kinds) -> None:
        for i, c, m, k in zip(np.asarray(nodes).tolist(),
                              np.asarray(cpu).tolist(),
                              np.asarray(mem).tolist(),
                              np.asarray(kinds).tolist()):
            self.place(i, c, m, k)

    # -- the inter-pod predicate -------------------------------------------
    def _in_domain(self, kinds: List[int], key: str, idx) -> np.ndarray:
        """Pods of `kinds` in the domain of each node of idx under key
        (0 where the node has no label)."""
        d = self.dom[key][idx]
        cnt = self.dom_count[key][kinds].sum(axis=0)[d]
        return np.where(d > 0, cnt, 0)

    def _on_node(self, kinds: List[int], key: str, idx) -> np.ndarray:
        """Pods of `kinds` on each node of idx itself, where it has the
        key's label."""
        return np.where(self.dom[key][idx] > 0,
                        self.on_node[kinds][:, idx].sum(axis=0), 0)

    def interpod(self, p: int, idx) -> np.ndarray:
        """Per node of idx: OK or the first rule that fails for kind p."""
        idx = np.asarray(idx)
        code = np.zeros(len(idx), np.int64)
        for a, t in self.hits_me[p]:
            if self.total[a]:
                bad = self._in_domain([a], t.key, idx) > 0
                code = np.where((code == OK) & bad, FAIL_EXISTING, code)
        for t, bs, self_match in self.own_aff[p]:
            if t.key == HOSTNAME:
                here = self._on_node(bs, t.key, idx)
                exists = self.on_node[bs][:, idx].sum(axis=0) > 0
                ok = (here > 0) | (~exists & self_match)
            else:
                exists = self.total[bs].sum() > 0
                ok = (self._in_domain(bs, t.key, idx) > 0) | (
                    (not exists) and self_match)
            code = np.where((code == OK) & ~ok, FAIL_AFFINITY, code)
        for t, bs in self.ex_anti[p]:
            near = (self._on_node(bs, t.key, idx) if t.key == HOSTNAME
                    else self._in_domain(bs, t.key, idx))
            code = np.where((code == OK) & (near > 0), FAIL_ANTI, code)
        return code

    def _scores(self, idx, cpu, mem, tol):
        s = super()._scores(idx, cpu, mem, tol)
        return np.where(self.interpod(self._kind, idx) == OK, s, -1)

    def _score_one(self, i: int, cpu: int, mem: int, tol: bool) -> int:
        s = super()._score_one(i, cpu, mem, tol)
        if s < 0:
            return s
        return s if self._interpod_one(self._kind, i) == OK else -1

    def _interpod_one(self, p: int, i: int) -> int:
        """interpod() at one node, in Python ints."""
        if self.own_aff[p]:
            return int(self.interpod(p, [i])[0])
        for a, t in self.hits_me[p]:
            v = self.dom_list[t.key][i]
            if v and self.dom_count[t.key][a, v]:
                return FAIL_EXISTING
        for t, bs in self.ex_anti[p]:
            v = self.dom_list[t.key][i]
            if not v:
                continue
            if t.key == HOSTNAME:
                if any(self.on_node[b, i] for b in bs):
                    return FAIL_ANTI
            elif any(self.dom_count[t.key][b, v] for b in bs):
                return FAIL_ANTI
        return OK

    def fit_error(self, cpu: int, mem: int, tol: bool) -> str:
        pods_bad = self.count + 1 > self.alloc_pods
        if cpu == 0 and mem == 0:
            cpu_bad = mem_bad = np.zeros(self.n, bool)
        else:
            cpu_bad = self.used_cpu + cpu > self.alloc_cpu
            mem_bad = self.used_mem + mem > self.alloc_mem
        general = pods_bad | cpu_bad | mem_bad
        taint_bad = ~general & self.tainted & (not tol)
        code = np.where(general | taint_bad, OK,
                        self.interpod(self._kind, np.arange(self.n)))
        counts = {"Insufficient pods": int(pods_bad.sum()),
                  "Insufficient cpu": int(cpu_bad.sum()),
                  "Insufficient memory": int(mem_bad.sum()),
                  R.TAINT_REASON: int(taint_bad.sum()),
                  UMBRELLA: int((code != OK).sum())}
        for c, text in REASONS.items():
            counts[text] = int((code == c).sum())
        parts = sorted(f"{v} {k}" for k, v in counts.items() if v > 0)
        return f"0/{self.n} nodes are available: " + ", ".join(parts) + "."

    # -- one batch -----------------------------------------------------------
    def schedule(self, cpu, mem, tol, kinds=None, bind: bool = True
                 ) -> Tuple[np.ndarray, Dict[int, str]]:
        """Decide pods in the given order, as benchmark/reference.py does,
        each of its kind (index into the kinds given at construction)."""
        if kinds is None:
            kinds = np.zeros(len(cpu), np.int64)
        out = np.full(len(cpu), -1, np.int64)
        errors: Dict[int, str] = {}
        rr = 0
        for j, (c, m, t, k) in enumerate(zip(np.asarray(cpu).tolist(),
                                             np.asarray(mem).tolist(),
                                             np.asarray(tol).tolist(),
                                             np.asarray(kinds).tolist())):
            self._use(k)
            s, _, feasible = self._class_scores(c, m, bool(t))
            if feasible == 0:
                errors[j] = self.fit_error(c, m, bool(t))
                continue
            if feasible == 1:
                node = int(np.flatnonzero(s >= 0)[0])
            else:
                best = s.max()
                if self.tie == "first":
                    node = int(np.argmax(s))
                else:
                    ties = np.flatnonzero(s == best)
                    node = int(ties[rr % len(ties)])
                rr += 1
            out[j] = node
            if bind:
                self.place(node, c, m, k)
        return out, errors


def groups_told_apart(kinds: List[Kind]) -> int:
    """Kinds that no term tells apart and that carry the same terms count
    once: the least number of presence rows a scan needs."""
    terms = [(a, t) for a in kinds for t in a.affinity + a.anti]
    profiles = {(tuple(b.matched_by(a, t) for a, t in terms),
                 b.affinity, b.anti) for b in kinds}
    return len(profiles)


def for_nodes(nodes, node_labels: List[Dict[str, str]], kinds: List[Kind],
              tie: str = "rr", hard_weight: int = 10) -> Scheduler:
    """A Scheduler over benchmark.cluster Nodes, with nothing placed."""
    return Scheduler(nodes.alloc_cpu, nodes.alloc_mem, nodes.alloc_pods,
                     nodes.tainted, node_labels, kinds, tie=tie,
                     hard_weight=hard_weight)


def node_labels(nodes) -> List[Dict[str, str]]:
    """The labels benchmark/cluster.py's node objects carry: the hostname
    (the node's name) and the zone where the configuration has zones."""
    out = []
    for name, zone in zip(nodes.names, nodes.zone):
        lab = {HOSTNAME: name}
        if zone is not None:
            lab["zone"] = zone
        out.append(lab)
    return out

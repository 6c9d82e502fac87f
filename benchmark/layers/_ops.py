"""Operations, roots and leaves among the flight recorder's spans.

An operation is one harness call into the program: a capacity query
(bench:query), or a twin's cycle (bench:ingest, bench:schedule) with its
what-if (bench:overlay), counted once per bench:schedule. A program span
is any span not named bench:*; a leaf is a program span with no span
nested inside it; a root is the program's span of one whole operation,
which carries the collector's time (tpusim/obs/recorder.py root())."""

from typing import List

OPS = ("bench:query", "bench:ingest", "bench:schedule", "bench:overlay")
ROOTS = ("simulate", "stream_cycle", "overlay_query", "stream_ingest")
# the recorder rounds ts and dur to a thousandth of a microsecond each
EPS_US = 0.01


def count(spans: List[dict]) -> int:
    """Operations traced: queries, else cycles."""
    for name in ("bench:query", "bench:schedule"):
        n = sum(1 for s in spans if s["name"] == name)
        if n:
            return n
    return 0


def operations(spans: List[dict]) -> List[dict]:
    return [s for s in spans if s["name"] in OPS]


def _end(s: dict) -> float:
    return s["ts"] + s["dur"]


def _inside(s: dict, outer: dict) -> bool:
    return (s is not outer and s["ts"] >= outer["ts"] - EPS_US
            and _end(s) <= _end(outer) + EPS_US)


def leaves(spans: List[dict]) -> List[dict]:
    """Program spans with no span nested inside them."""
    order = sorted(spans, key=lambda s: (s["ts"], -s["dur"]))
    out = []
    for i, s in enumerate(order):
        if s["name"].startswith("bench:"):
            continue
        nested = False
        for t in (order[j] for j in range(i + 1, len(order))):
            if t["ts"] > _end(s) + EPS_US:
                break
            if _inside(t, s):
                nested = True
                break
        if not nested:
            out.append(s)
    return out


def covered_us(parts: List[dict], outer: dict) -> float:
    """How much of `outer` the union of `parts` covers."""
    lo, hi = outer["ts"], _end(outer)
    ivs = sorted((max(p["ts"], lo), min(_end(p), hi)) for p in parts
                 if _end(p) > lo and p["ts"] < hi)
    total, cur = 0.0, lo
    for a, b in ivs:
        a = max(a, cur)
        if b > a:
            total += b - a
            cur = b
    return total


def in_operations(spans: List[dict], *names: str) -> List[dict]:
    """Spans named among `names` that lie inside some operation."""
    ops = operations(spans)
    return [s for s in spans if s["name"] in names
            and any(_inside(s, o) for o in ops)]


def roots(spans: List[dict]) -> List[dict]:
    """Root spans inside operations, each once: none inside another."""
    rs = in_operations(spans, *ROOTS)
    return [r for r in rs if not any(_inside(r, o) for o in rs)]


def arg(s: dict, key: str):
    return (s.get("args") or {}).get(key)

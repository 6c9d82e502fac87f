"""The least HBM traffic of a scheduling scan whose pods carry inter-pod
terms: benchmark/roofline.py's plain scan, plus what the terms make a scan
read, counted from the configuration's shapes and the same whichever route
(XLA scan or Pallas kernel) implements it:

- per decided pod, its group id (4 bytes) and its match bits — its own
  terms against each group, and each group's terms against it — packed
  into 32-bit words;
- per scan, the presence rows read once: one int32 count per group and
  node;
- per scan, the per-domain counts of the keys other than the hostname
  (presence_dom: one int32 per group and domain); a hostname key's
  domains are its nodes, so the presence rows already hold them.

The bind's writes to the presence rows are left out: a scan need not write
a row it keeps on chip.
"""

from __future__ import annotations

from typing import Iterable

from benchmark import roofline

GROUP_ID_BYTES = 4
COUNT_BYTES = 4


def scan_bytes(pods: int, nodes: int, groups: int, match_bits: int,
               dom_cells: int) -> int:
    words = -(-match_bits // 32)
    return (roofline.scan_bytes(pods, nodes)
            + pods * (GROUP_ID_BYTES + 4 * words)
            + COUNT_BYTES * (groups * nodes + dom_cells))


def total_bytes(scans: Iterable[dict]) -> int:
    """Over the scans a driver recorded with the terms' shapes (groups,
    match_bits, dom_cells); 0 where none was recorded."""
    return sum(scan_bytes(s["pods"], s["nodes"], s["groups"],
                          s["match_bits"], s["dom_cells"])
               for s in scans if "groups" in s)

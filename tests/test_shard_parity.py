"""Cross-shard parity matrix (ISSUE 16): the TPUSIM_SHARDS backend route
at shards ∈ {1, 2, 4} on the virtual CPU mesh must be indistinguishable
from the single-device route — placement hash, per-pod FitError text,
analytics stats, and gang decisions all byte-identical.

These run the FULL JaxBackend dispatch (pad → stage → shard_map scan →
verify-then-trust pin), not the bare kernel (tests/test_sharding.py covers
that layer), so they also lock the seam behavior: the first batch per
(shards, config) signature verifies against the XLA scan and pins; k=1
never builds a mesh at all.
"""

import random

import jax
import numpy as np
import pytest

from tests.test_fuzz_differential import random_cluster, random_pods
from tpusim.api.snapshot import ClusterSnapshot, make_node, make_pod
from tpusim.backends import placement_hash
from tpusim.framework.metrics import register
from tpusim.gang.group import mark_gang
from tpusim.jaxe import trust
from tpusim.jaxe.backend import JaxBackend
from tpusim.jaxe.trust import SHARD
from tpusim.obs import analytics
from tpusim.simulator import run_simulation

needs_8_devices = pytest.mark.skipif(len(jax.devices()) < 8,
                                     reason="needs 8 virtual devices")


def _workload(seed=1234, num_pods=60):
    rng = random.Random(seed)
    snapshot = random_cluster(rng)
    pods = random_pods(rng, num_pods)
    # oversized tail: the FitError reason-histogram text must survive the
    # cross-shard psum merge character-for-character
    pods += [make_pod(f"huge{i}", milli_cpu=10**6) for i in range(3)]
    return snapshot, pods


def _signature(placements):
    """Per-pod decision signature incl. the full FitError message (the
    placement hash covers (name, node, reason) but not the text)."""
    return [(p.pod.metadata.name, p.node_name, p.reason, p.message)
            for p in placements]


def _strip(stats):
    """Analytics sample minus capture-time bookkeeping."""
    return {k: v for k, v in (stats or {}).items() if k not in ("seq", "ts")}


def _schedule(monkeypatch, k, snapshot, pods):
    """One full backend run at shard count k with analytics retained."""
    monkeypatch.setenv("TPUSIM_SHARDS", str(k))
    trust.reset_all()
    log = analytics.install(analytics.ClusterAnalytics(
        keep_inputs=True, sample_interval_s=0.0))
    try:
        placements = JaxBackend().schedule(pods, snapshot)
        stats = log.latest()
        problems = log.verify_against_host()
    finally:
        analytics.uninstall()
    return placements, stats, problems


@needs_8_devices
@pytest.mark.parametrize("k", [2, 4])
def test_backend_parity_matrix(monkeypatch, k):
    snapshot, pods = _workload()
    base, base_stats, base_problems = _schedule(monkeypatch, 1, snapshot,
                                                pods)
    assert base_problems == []
    assert any(p.reason == "Unschedulable" for p in base), \
        "workload drifted: no FitError text to compare"

    got, stats, problems = _schedule(monkeypatch, k, snapshot, pods)
    assert placement_hash(got) == placement_hash(base)
    assert _signature(got) == _signature(base)  # incl. FitError text
    # the two-level analytics merge replays bit-exact on the host AND
    # decodes to the same sample the single-device reduce produced
    assert problems == []
    assert _strip(stats) == _strip(base_stats)
    # the route actually ran sharded and pinned its signature
    assert SHARD.verified and not SHARD.disabled
    m = register()
    assert m.shard_count.value == k
    occupancy = sum(m.shard_node_occupancy.get(str(s)) for s in range(k))
    assert occupancy == len(snapshot.nodes)


@needs_8_devices
def test_shards_one_never_builds_a_mesh(monkeypatch):
    """TPUSIM_SHARDS=1 (and unset, and garbage) is the single-device route:
    no mesh, no verify pin, byte-identical trace to the default."""
    snapshot, pods = _workload(num_pods=24)
    monkeypatch.delenv("TPUSIM_SHARDS", raising=False)
    trust.reset_all()
    base = JaxBackend().schedule(pods, snapshot)
    for env in ("1", "0", "not-a-number"):
        monkeypatch.setenv("TPUSIM_SHARDS", env)
        trust.reset_all()
        got = JaxBackend().schedule(pods, snapshot)
        assert placement_hash(got) == placement_hash(base)
        assert not SHARD.verified, \
            f"TPUSIM_SHARDS={env} took the sharded route"


@needs_8_devices
@pytest.mark.parametrize("k", [2, 4])
def test_gang_decisions_match_across_shards(monkeypatch, k):
    """Gang admission under the sharded lanes (sub-problem b): the joint
    decision — who binds where, who shares which rejection text — must not
    move with the shard count."""
    def cluster():
        nodes = [make_node(f"gn{i}", milli_cpu=4000,
                           labels={"zone": f"z{i % 2}",
                                   "topology.kubernetes.io/rack":
                                   f"rack-{i // 2}"})
                 for i in range(6)]
        return ClusterSnapshot(nodes=nodes, pods=[])

    def feed():
        pods = [make_pod(f"s{i}", milli_cpu=300) for i in range(4)]
        pods += [mark_gang(make_pod(f"g-{j}", milli_cpu=900), "g")
                 for j in range(4)]
        # a gang that cannot fit: every member must share ONE FitError
        pods += [mark_gang(make_pod(f"big-{j}", milli_cpu=3900), "big",
                           min_available=8) for j in range(8)]
        return pods

    def run(shards):
        monkeypatch.setenv("TPUSIM_SHARDS", str(shards))
        trust.reset_all()
        st = run_simulation(feed(), cluster(), backend="jax")
        binds = sorted((p.metadata.name, p.spec.node_name)
                       for p in st.successful_pods)
        fails = sorted((p.metadata.name,
                        p.status.conditions[-1].message)
                       for p in st.failed_pods)
        return binds, fails

    base_binds, base_fails = run(1)
    assert any(name.startswith("g-") for name, _ in base_binds)
    assert len({msg for name, msg in base_fails
                if name.startswith("big-")}) == 1
    got_binds, got_fails = run(k)
    assert got_binds == base_binds
    assert got_fails == base_fails


@needs_8_devices
def test_chunked_sharded_route_parity(monkeypatch):
    """TPUSIM_SCAN_CHUNK + TPUSIM_SHARDS compose: the chunked dispatch
    feeds the same donated shard_map program and lands the same hash."""
    snapshot, pods = _workload(seed=77, num_pods=40)
    base, _, _ = _schedule(monkeypatch, 1, snapshot, pods)
    monkeypatch.setenv("TPUSIM_SCAN_CHUNK", "16")
    got, _, problems = _schedule(monkeypatch, 2, snapshot, pods)
    assert problems == []
    assert _signature(got) == _signature(base)
    assert SHARD.verified and not SHARD.disabled

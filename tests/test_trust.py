"""The verify-then-trust registry (tpusim/jaxe/trust.py) and the two route
decisions built on it: backend.fast_route and sharding.shard_mesh.

The transition names are a contract: benchmark/guard.py reads
`backend_auto_transitions` and fails a run on some of them."""

from dataclasses import replace

import pytest

from tpusim.api.snapshot import ClusterSnapshot, make_node, make_pod
from tpusim.framework.metrics import register
from tpusim.jaxe import trust
from tpusim.jaxe.trust import FAST, GANG, SHARD, VICTIM
from tpusim.obs import recorder as flight

# route -> (override variable, {value: mode}); None is the unset variable.
# The suite runs on the CPU, where AUTO keeps the Pallas route off.
MODES = {
    "fast": (FAST, "TPUSIM_FAST", {None: (False, True), "0": (False, False),
                                   "1": (True, False)}),
    "victim": (VICTIM, "TPUSIM_PREEMPT_DEVICE",
               {None: (True, True), "0": (False, False), "1": (True, False)}),
    "shard": (SHARD, None, {None: (True, True)}),
    "gang": (GANG, "TPUSIM_GANG_KERNEL",
             {None: (True, True), "0": (False, False), "1": (True, False)}),
}


@pytest.fixture
def rec():
    register().reset()
    r = flight.install(flight.FlightRecorder(process_name="test"))
    try:
        yield r
    finally:
        flight.uninstall()


def _instants(rec, prefix):
    return [e["name"][len(prefix):] for e in rec.events
            if e["ph"] == "i" and e["name"].startswith(prefix)]


@pytest.mark.parametrize("route", sorted(MODES))
def test_trust_lifecycle(monkeypatch, route):
    """Mode from the override, pin, disable, and reset_all back to the boot
    state; the fast route also runs its three strikes."""
    t, env, modes = MODES[route]
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    for value, mode in modes.items():
        if value is None:
            monkeypatch.delenv(env or "TPUSIM_UNSET", raising=False)
        else:
            monkeypatch.setenv(env, value)
        assert t.mode() == mode, (route, value)

    assert not t.trusted(("sig", 1))
    assert t.verdict(("sig", 1), True)
    assert t.trusted(("sig", 1)) and not t.disabled
    assert t.verdict(("sig", 2), True, pin=False)
    assert not t.trusted(("sig", 2))
    assert not t.verdict(("sig", 3), False)
    assert t.disabled
    if env is not None:
        monkeypatch.setenv(env, "1")  # a disabled route stays off, forced
    assert t.mode() == (False, False)

    if route == "fast":
        t.reset()
        oom = RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        assert t.note_failure(oom) and t.note_failure(oom)
        t.note_success()  # a completed run ends the run of failures
        assert t.transient == 0
        for _ in range(trust.MAX_TRANSIENT_FAILURES):
            assert t.note_failure(oom)
        assert t.disabled
    t.verified.add(("sig", 4))
    t.transient = 2
    t.disabled = True
    trust.reset_all()
    for other in trust.REGISTRY:
        assert (other.disabled, other.verified, other.transient) == \
            (False, set(), 0)


def test_transition_names_are_todays(monkeypatch, rec):
    """Every route's outcomes emit exactly the names (and order) that the
    guard and the dashboards read."""
    from tpusim.jaxe.backend import fast_route

    # fast: defer (a small batch with nothing pinned is not even planned),
    # a pass that only verifies, a pin, a reuse, three strikes, a failure
    monkeypatch.setattr(FAST, "mode", lambda: (True, True))
    assert fast_route(None, None, None, 5, pinned_only=True).plan is None
    FAST.verdict("f", True, pin=False)
    FAST.verdict("f", True)
    FAST.note_reuse("f")
    for _ in range(3):
        FAST.note_failure(RuntimeError("UNAVAILABLE: device"))
    FAST.reset()
    FAST.verdict("g", False)
    # gang: pass, reuse, failure
    GANG.verdict("gang:4x8", True)
    GANG.note_reuse("gang:4x8")
    GANG.verdict("gang:8x8", False)
    # shard: pin, reuse, failure
    SHARD.verdict((2, "cfg"), True, label="2")
    SHARD.note_reuse("2")
    SHARD.verdict((4, "cfg"), False, label="4")
    # victim: verified, fallback (the victim path counter only)
    VICTIM.verdict("v", True)
    VICTIM.verdict("w", False)

    assert _instants(rec, "auto:") == [
        "defer", "verify_pass", "verify_pass", "pin", "trust",
        "discard_transient", "discard_transient", "discard_transient",
        "discard_permanent", "verify_fail",
        "verify_pass", "trust", "verify_fail",
        "shard_pin", "shard_trust", "shard_verify_fail"]
    assert _instants(rec, "victim:") == ["device_verified", "fallback"]
    m = register()
    assert m.backend_auto_transitions.values == {
        "defer": 1, "verify_pass": 3, "pin": 1, "trust": 2,
        "discard_transient": 3, "discard_permanent": 1, "verify_fail": 2,
        "shard_pin": 1, "shard_trust": 1, "shard_verify_fail": 1}
    assert m.backend_victim_path.values == {"device_verified": 1,
                                            "fallback": 1}
    assert not _instants(rec, "fallback:")  # the defer planned nothing


def _preempt_run():
    from tpusim.jaxe.preempt import run_with_preemption

    nodes = [make_node(f"n{i}", milli_cpu=2000) for i in range(3)]
    pods = [make_pod(f"p{i}", milli_cpu=500) for i in range(4)]
    run_with_preemption(pods, ClusterSnapshot(nodes=nodes))


def _what_if_run():
    from tpusim.jaxe.whatif import run_what_if

    nodes = [make_node(f"n{i}", milli_cpu=2000) for i in range(3)]
    pods = [make_pod(f"p{i}", milli_cpu=500) for i in range(4)]
    run_what_if([(ClusterSnapshot(nodes=nodes), pods)])


@pytest.mark.parametrize("drive", [_preempt_run, _what_if_run],
                         ids=["preempt", "what_if"])
def test_shared_fast_decision_spans_and_counts(monkeypatch, rec, drive):
    """The preemption hybrid and the what-if fast loop decide through
    backend.fast_route: the plan runs in the `plan_fast` span and an
    ineligible plan counts as a fast_fallback, as in run_batch."""
    from tpusim.jaxe import fastscan

    monkeypatch.setattr(FAST, "mode", lambda: (True, True))
    monkeypatch.setattr(fastscan, "plan_fast", lambda *a, **kw: (
        None, "pod groups exceed the fast-path budget"))
    drive()
    assert register().fast_fallback.get("groups_budget") == 1
    spans = [e for e in rec.events
             if e["ph"] == "X" and e["name"] == "plan_fast"]
    assert len(spans) == 1 and spans[0]["args"]["nodes"] == 3
    assert _instants(rec, "fallback:") == ["groups_budget"]


def _config(explain_k=0):
    from tpusim.jaxe.kernels import config_for
    from tpusim.jaxe.state import NUM_FIXED_BITS, compile_cluster

    nodes = [make_node(f"n{i}", milli_cpu=2000) for i in range(4)]
    compiled, _ = compile_cluster(ClusterSnapshot(nodes=nodes),
                                  [make_pod("p", milli_cpu=100)])
    config = config_for([compiled], most_requested=False,
                        num_reason_bits=NUM_FIXED_BITS)
    return replace(config, explain_k=explain_k)


@pytest.mark.parametrize("case", ["unset", "disabled", "ineligible",
                                  "eligible"])
def test_shard_decision(monkeypatch, rec, case):
    """One shard decision for the batch route and the stream residency: a
    mesh only for TPUSIM_SHARDS > 1 on an eligible config while SHARD is
    not disabled; an ineligible config is a classified fallback."""
    import jax

    from tpusim.jaxe.sharding import shard_count, shard_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    if case != "unset":
        monkeypatch.setenv("TPUSIM_SHARDS", "2")
    else:
        monkeypatch.delenv("TPUSIM_SHARDS", raising=False)
    SHARD.disabled = case == "disabled"
    mesh = shard_mesh(_config(explain_k=int(case == "ineligible")),
                      "batch routed single-device")
    fallback = register().shard_fallback.values
    if case == "eligible":
        assert shard_count() == 2 and mesh.shape["node"] == 2
    else:
        assert mesh is None
    assert fallback == ({"explain_lanes": 1} if case == "ineligible" else {})
    assert _instants(rec, "fallback:") == (
        ["shard_explain_lanes"] if case == "ineligible" else [])

"""Flight-recorder tests (ISSUE 2): span/instant export shape, injected-clock
determinism goldens on both backends, Chrome trace_event validity for the CLI
artifact, the --what-if rejection, and the AUTO verify-then-trust transition
counters."""

import itertools
import json
import types

import numpy as np
import pytest

from tpusim import cli
from tpusim.api.snapshot import ClusterSnapshot, make_node, make_pod
from tpusim.framework.metrics import register
from tpusim.obs import recorder as flight
from tpusim.obs import tracectx
from tpusim.obs.recorder import NOOP_SPAN, FlightRecorder
from tpusim.simulator import run_simulation


def _clock():
    """Deterministic 1ms-step clock (Trace-style injected clock)."""
    return itertools.count(0.0, 0.001).__next__


@pytest.fixture(autouse=True)
def _no_leaked_recorder():
    yield
    flight.uninstall()
    register().reset()


def _quickstart():
    nodes = [make_node(f"n{i}", milli_cpu=4000, memory=2**33)
             for i in range(3)]
    pods = [make_pod(f"p{i}", milli_cpu=100, memory=2**20) for i in range(4)]
    return nodes, pods


class TestFlightRecorder:
    def test_span_event_shape(self):
        rec = FlightRecorder(clock=_clock())
        with rec.span("predicates") as sp:
            sp.set("nodes", 3)
        assert list(rec.events) == [{
            "name": "predicates", "cat": "host", "ph": "X",
            "ts": 1000.0, "dur": 1000.0, "pid": 1, "tid": 1,
            "args": {"nodes": 3},
        }]

    def test_device_category_track_and_instant(self):
        rec = FlightRecorder(clock=_clock())
        rec.span("device_dispatch", "device").end()
        rec.instant("route:xla_scan", "device", {"pods": 4})
        assert [e["tid"] for e in rec.events] == [2, 2]
        inst = rec.events[1]
        assert inst["ph"] == "i" and inst["s"] == "g"
        # unknown category gets its own registered track (ISSUE 20), not
        # the shared tool lane — and the track is named in the export
        rec.span("odd", "mystery").end()
        assert rec.events[2]["tid"] == 4
        meta = [e for e in rec.to_chrome()["traceEvents"] if e["ph"] == "M"]
        assert [m["args"]["name"] for m in meta[1:]] == [
            "host", "device", "tool", "mystery"]

    def test_add_span_uses_explicit_readings(self):
        rec = FlightRecorder(clock=_clock())
        t0, t1 = rec.clock(), rec.clock()
        rec.add_span("queue_wait", "host", t0, t1, {"pod": "default/p0"})
        assert rec.events[0]["ts"] == 1000.0
        assert rec.events[0]["dur"] == 1000.0

    def test_chrome_export_metadata(self):
        rec = FlightRecorder(clock=_clock())
        rec.span("x").end()
        doc = rec.to_chrome()
        assert doc["displayTimeUnit"] == "ms"
        meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert meta[0]["args"]["name"] == "tpusim"
        assert [m["args"]["name"] for m in meta[1:]] == ["host", "device",
                                                         "tool"]

    def test_jsonl_export(self):
        rec = FlightRecorder(clock=_clock())
        rec.span("a").end()
        rec.instant("b")
        text = rec.to_jsonl()
        assert text.endswith("\n")
        lines = text.splitlines()
        assert len(lines) == 2
        assert json.loads(lines[0])["name"] == "a"

    def test_write_dispatches_on_extension(self, tmp_path):
        rec = FlightRecorder(clock=_clock())
        rec.span("a").end()
        chrome, jsonl = tmp_path / "t.json", tmp_path / "t.jsonl"
        rec.write(str(chrome))
        rec.write(str(jsonl))
        assert "traceEvents" in json.loads(chrome.read_text())
        assert json.loads(jsonl.read_text().splitlines()[0])["name"] == "a"


class TestDisabledPath:
    def test_span_is_shared_noop_when_uninstalled(self):
        assert flight.get_recorder() is None
        sp = flight.span("pod_attempt")
        assert sp is NOOP_SPAN
        assert sp is flight.span("anything_else")
        assert not sp  # falsy: call sites skip label construction
        sp.set("k", "v")
        sp.end()
        with flight.span("x"):
            pass
        flight.instant("y")  # no-op, no error

    def test_install_uninstall(self):
        rec = flight.install(FlightRecorder(clock=_clock()))
        assert flight.get_recorder() is rec
        assert flight.span("a")  # truthy live span
        flight.uninstall()
        assert flight.get_recorder() is None
        assert flight.span("a") is NOOP_SPAN


def _run_traced(backend):
    nodes, pods = _quickstart()
    ids = itertools.count(1)
    tracectx.set_id_source(lambda: f"{next(ids):016x}")
    rec = flight.install(FlightRecorder(clock=_clock()))
    try:
        status = run_simulation(pods, ClusterSnapshot(nodes=nodes),
                                backend=backend)
    finally:
        flight.uninstall()
        tracectx.set_id_source(None)
    assert len(status.successful_pods) == 4
    return rec


# a root span's measured args: process CPU time and the collector's, the
# only values an injected clock does not pin
MEASURED = ("cpu_ms", "gc_ms", "gc_collections")


def _clocked_json(rec):
    doc = rec.to_chrome()
    for ev in doc["traceEvents"]:
        for key in MEASURED:
            (ev.get("args") or {}).pop(key, None)
    return json.dumps(doc, sort_keys=True)


class TestGoldens:
    def test_reference_backend_span_mix(self):
        rec = _run_traced("reference")
        names = [e["name"] for e in rec.events]
        for expected in ["queue_wait", "pod_attempt", "schedule",
                         "predicates", "priorities", "select_host",
                         "assume", "bind"]:
            assert expected in names, f"missing host span {expected}"
        # per-pod attempt spans: one per scheduled pod
        assert names.count("pod_attempt") == 4
        outcome = [e["args"]["outcome"] for e in rec.events
                   if e["name"] == "pod_attempt"]
        assert outcome == ["bound"] * 4

    def test_reference_backend_byte_stable(self):
        a = _run_traced("reference").to_chrome_json()
        b = _run_traced("reference").to_chrome_json()
        assert a == b

    def test_jax_backend_device_spans(self):
        rec = _run_traced("jax")
        by_cat = {}
        for e in rec.events:
            by_cat.setdefault(e["cat"], []).append(e["name"])
        assert "backend_schedule" in by_cat["host"]
        assert "compile_cluster" in by_cat["host"]
        assert "device_dispatch" in by_cat["device"]
        assert any(n.startswith("route:") for n in by_cat["device"])

    def test_jax_backend_byte_stable(self):
        rec = _run_traced("jax")
        [root] = [e for e in rec.events if e["name"] == "simulate"]
        assert all(root["args"][k] >= 0 for k in MEASURED)
        assert _clocked_json(rec) == _clocked_json(_run_traced("jax"))


class TestChromeValidity:
    def test_cli_trace_artifact_is_valid_chrome_json(self, tmp_path,
                                                     capsys):
        spec = tmp_path / "podspec.yaml"
        spec.write_text(
            "- name: quickstart\n"
            "  num: 4\n"
            "  pod:\n"
            "    metadata:\n"
            "      name: quickstart\n"
            "    spec:\n"
            "      containers:\n"
            "        - resources:\n"
            "            requests:\n"
            "              cpu: \"500m\"\n"
            "              memory: 512Mi\n")
        trace = tmp_path / "trace.json"
        metrics = tmp_path / "metrics.prom"
        rc = cli.main(["--podspec", str(spec), "--synthetic-nodes", "3",
                       "--trace-out", str(trace),
                       "--metrics-out", str(metrics)])
        assert rc == 0
        doc = json.load(trace.open())
        assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
        for ev in doc["traceEvents"]:
            assert ev["ph"] in ("X", "i", "M", "s", "f")
            assert "ts" in ev and "pid" in ev
            if ev["ph"] == "X":
                assert ev["dur"] >= 0
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"pod_attempt", "schedule", "bind"} <= names
        text = metrics.read_text()
        assert text.endswith("\n")
        assert "scheduler_e2e_scheduling_latency_microseconds" in text
        # the CLI leaves no recorder behind for later in-process runs
        assert flight.get_recorder() is None

    def test_trace_out_rejected_with_what_if(self, tmp_path, capsys):
        rc = cli.main(["--what-if", str(tmp_path / "w.yaml"),
                       "--trace-out", str(tmp_path / "t.json")])
        assert rc == 2
        assert "--what-if" in capsys.readouterr().err


class TestAutoTransitions:
    @pytest.fixture(autouse=True)
    def _fresh_auto_state(self):
        from tpusim.jaxe import backend as jb

        saved = {k: (set(v) if isinstance(v, set) else v)
                 for k, v in jb._FAST_AUTO.items()}
        jb._FAST_AUTO.update(disabled=False, verified_sigs=set(),
                             transient=0)
        register().reset()
        yield
        jb._FAST_AUTO.update(saved)

    def test_verify_pass_pins_and_counts(self, monkeypatch):
        from tpusim.jaxe import backend as jb

        monkeypatch.setattr("tpusim.jaxe.fastscan.verify_against_xla",
                            lambda *a, **kw: True)
        cols = types.SimpleNamespace(req_cpu=np.zeros(128))
        sig = ("variant", 0)
        assert jb._auto_verify_and_pin(None, None, cols, None, None, sig)
        assert sig in jb._FAST_AUTO["verified_sigs"]
        m = register()
        assert m.backend_auto_transitions.get("verify_pass") == 1
        assert m.backend_auto_transitions.get("pin") == 1
        text = m.expose()
        assert ('tpusim_backend_auto_transitions_total'
                '{transition="verify_pass"} 1') in text
        assert ('tpusim_backend_auto_transitions_total'
                '{transition="pin"} 1') in text

    def test_verify_fail_disables_and_counts(self, monkeypatch):
        from tpusim.jaxe import backend as jb

        monkeypatch.setattr("tpusim.jaxe.fastscan.verify_against_xla",
                            lambda *a, **kw: False)
        cols = types.SimpleNamespace(req_cpu=np.zeros(128))
        assert not jb._auto_verify_and_pin(None, None, cols, None, None,
                                           ("v", 1))
        assert jb._FAST_AUTO["disabled"]
        assert register().backend_auto_transitions.get("verify_fail") == 1

    def test_trust_bridge_counts(self):
        flight.note_auto_transition("trust", "('v', 2)")
        assert register().backend_auto_transitions.get("trust") == 1

    def test_forced_discard_transient_then_permanent(self):
        from tpusim.jaxe import backend as jb

        for _ in range(3):
            assert jb._note_fast_failure(
                RuntimeError("RESOURCE_EXHAUSTED: hbm"))
        m = register()
        assert m.backend_auto_transitions.get("discard_transient") == 3
        assert m.backend_auto_transitions.get("discard_permanent") == 1
        assert jb._FAST_AUTO["disabled"]
        # the discard is visible on the exposition surface (--metrics-out)
        assert ('tpusim_backend_auto_transitions_total'
                '{transition="discard_permanent"} 1') in m.expose()

    def test_compile_failure_is_not_discarded(self):
        """A lowering error is the caller's to raise: no transition is
        recorded and the path is not quietly switched off."""
        from tpusim.jaxe import backend as jb

        assert not jb._note_fast_failure(ValueError("Mosaic lowering failed"))
        m = register()
        assert m.backend_auto_transitions.get("discard_permanent") == 0
        assert m.backend_auto_transitions.get("discard_transient") == 0
        assert not jb._FAST_AUTO["disabled"]

"""Flight recorder: span/event timeline across the host/device boundary.

Every scheduling attempt — host phases (queue wait, predicates,
priorities, select host, preempt, assume, bind) and device work
(cluster compile, kernel dispatch per variant signature, AUTO
verify-then-trust transitions, victim-path selection) — lands on one
timeline, exported as Chrome ``trace_event`` JSON (loadable in
Perfetto / chrome://tracing) or a raw JSONL span stream.

Design constraints (ISSUE 2):

- **Zero-cost when disabled.** `span()` returns a falsy shared no-op
  singleton when no recorder is installed: no dict, no Span object, no
  per-pod allocation. Call sites guard argument construction with
  ``if sp:`` so label strings are never built on the disabled path.
- **Deterministic under an injected clock.** The recorder never calls
  `time.perf_counter` directly; the clock is a constructor argument so
  goldens can pin span structure byte-for-byte.

One operation (a capacity query, a stream cycle, a what-if, an ingest)
opens a *root* span (`root()`): it carries the operation's trace id and
the process CPU time and garbage-collector time spent inside it, the two
readings that tell a host stall from host work. Those args are measured,
not clocked: they are the only values an injected clock does not pin.

Counters/histograms do NOT live here — they land in the
`framework/metrics.py` registry (`tpusim_backend_*` families) so the
reference exposition surface stays unified; the `note_*` helpers below
bridge both sinks.
"""

from __future__ import annotations

import contextvars
import gc
import json
import threading
import time
from collections import Counter, deque
from typing import Any, Callable, Deque, Dict, List, Optional

from tpusim.framework import metrics as _metrics

PID = 1
# Stable Perfetto track ids per category.
_TIDS = {"host": 1, "device": 2, "tool": 3}


class _NoopSpan:
    """Shared do-nothing span; falsy so call sites can skip building args."""

    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc: object) -> bool:
        return False

    def set(self, key: str, value: Any) -> None:
        pass

    def end(self) -> None:
        pass


NOOP_SPAN = _NoopSpan()


class Span:
    __slots__ = ("rec", "name", "cat", "t0", "args")

    def __init__(self, rec: "FlightRecorder", name: str, cat: str, t0: float):
        self.rec = rec
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.args: Optional[Dict[str, Any]] = None

    def __bool__(self) -> bool:
        return True

    def set(self, key: str, value: Any) -> None:
        if self.args is None:
            self.args = {}
        self.args[key] = value

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc: object) -> bool:
        self.end()
        return False

    def end(self) -> None:
        self.rec._finish(self)


class RootSpan(Span):
    """The span of one whole operation. While it is open it is the
    thread's root (a nested `root()` is a no-op) and, when no trace context
    was active, its own fresh context is; on end it records `cpu_ms`
    (`time.process_time` across it, all threads), `gc_ms` and
    `gc_collections` (the collections that ran inside it, `_GC`)."""

    __slots__ = ("_cpu0", "_gc0", "_ctx_token", "_root_token")

    def __init__(self, rec: "FlightRecorder", name: str, cat: str):
        super().__init__(rec, name, cat, 0.0)
        self._root_token = _open_root.set(self)
        self._ctx_token = None
        if _current_trace() is None:
            self._ctx_token = _tracectx.attach(_tracectx.start())
        self._gc0 = (_GC.seconds, _GC.count)
        self._cpu0 = time.process_time()
        self.t0 = rec.clock()

    def end(self) -> None:
        cpu = time.process_time() - self._cpu0
        self.set("cpu_ms", round(cpu * 1e3, 3))
        self.set("gc_ms", round((_GC.seconds - self._gc0[0]) * 1e3, 3))
        self.set("gc_collections", _GC.count - self._gc0[1])
        self.rec._finish(self)
        _tracectx.detach(self._ctx_token)
        _open_root.reset(self._root_token)


class _GcTotals:
    """The garbage collector's running totals (a gc.callbacks hook that
    `install` adds and `uninstall` removes): seconds and collections so
    far. Root spans read them at start and end."""

    __slots__ = ("seconds", "count", "_t0")

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self._t0: Optional[float] = None

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self.count += 1
            self._t0 = None


_GC = _GcTotals()
_open_root: contextvars.ContextVar[Optional[RootSpan]] = \
    contextvars.ContextVar("tpusim_open_root", default=None)


class FlightRecorder:
    """Collects complete ('X') and instant ('i') trace events in memory.

    The timeline is a bounded ring (``max_events``): an always-on serve or
    stream process can run for days without growing host memory without
    bound. When the ring is full the OLDEST event is dropped and
    ``tpusim_obs_dropped_events_total`` increments, so an exported trace
    that lost its head says so on the scrape."""

    DEFAULT_MAX_EVENTS = 262_144

    def __init__(self, clock: Optional[Callable[[], float]] = None,
                 max_events: Optional[int] = None,
                 pid: Optional[int] = None, process_name: str = "tpusim"):
        self.clock: Callable[[], float] = clock or time.perf_counter
        self._epoch = self.clock()
        self.max_events = (self.DEFAULT_MAX_EVENTS if max_events is None
                           else max(1, int(max_events)))
        self.events: Deque[Dict[str, Any]] = deque(maxlen=self.max_events)
        self.dropped = 0
        self.dropped_by_category: Dict[str, int] = {}
        self.pid = PID if pid is None else int(pid)
        self.process_name = process_name
        # per-instance track registry: unknown categories get their own
        # Perfetto track (ISSUE 20) instead of piling onto the shared
        # "tool" lane — merged multi-process traces stay legible
        self._tids: Dict[str, int] = dict(_TIDS)
        # clock anchors for cross-process alignment (tools/trace_merge.py):
        # name -> recorder-relative microsecond reading of a shared instant
        self.anchors: Dict[str, float] = {}
        self._lock = threading.Lock()

    def _append(self, ev: Dict[str, Any]) -> None:
        # caller holds no lock; the ring drop + counter stay consistent
        with self._lock:
            if len(self.events) == self.max_events:
                cat = self.events[0].get("cat", "meta")
                self.dropped += 1
                self.dropped_by_category[cat] = \
                    self.dropped_by_category.get(cat, 0) + 1
                _metrics.register().obs_dropped_events.inc(cat)
            self.events.append(ev)

    def _tid(self, cat: str) -> int:
        tid = self._tids.get(cat)
        if tid is None:
            with self._lock:
                tid = self._tids.get(cat)
                if tid is None:
                    tid = max(self._tids.values()) + 1
                    self._tids[cat] = tid
        return tid

    # -- timestamps -------------------------------------------------------
    def _ts(self, t: float) -> float:
        return round((t - self._epoch) * 1e6, 3)

    def now_us(self) -> float:
        """Recorder-relative timestamp in microseconds — the clock domain
        shipped in replication hello frames for trace_merge alignment."""
        return self._ts(self.clock())

    def set_anchor(self, name: str, value: Optional[float] = None) -> None:
        """Pin a named clock-anchor reading (now by default); exported in
        ``otherData`` so trace_merge can shift this process's timeline."""
        self.anchors[name] = self.now_us() if value is None else value

    # -- trace-context stamping -------------------------------------------
    def _stamp(self, ev: Dict[str, Any]) -> None:
        """Attach the active TraceContext's ids to an event's args. One
        contextvar read per event — nothing when no context is active."""
        ctx = _current_trace()
        if ctx is not None:
            args = ev.get("args")
            if args is None:
                args = ev["args"] = {}
            args.setdefault("trace_id", ctx.trace_id)
            args.setdefault("span_id", ctx.span_id)

    # -- recording --------------------------------------------------------
    def span(self, name: str, cat: str = "host") -> Span:
        return Span(self, name, cat, self.clock())

    def _finish(self, span: Span) -> None:
        t1 = self.clock()
        ev: Dict[str, Any] = {
            "name": span.name,
            "cat": span.cat,
            "ph": "X",
            "ts": self._ts(span.t0),
            "dur": round((t1 - span.t0) * 1e6, 3),
            "pid": self.pid,
            "tid": self._tid(span.cat),
        }
        if span.args:
            ev["args"] = span.args
        self._stamp(ev)
        self._append(ev)

    def add_span(self, name: str, cat: str, t0: float, t1: float,
                 args: Optional[Dict[str, Any]] = None) -> None:
        """Record a span from explicit clock readings (e.g. queue wait)."""
        ev: Dict[str, Any] = {
            "name": name,
            "cat": cat,
            "ph": "X",
            "ts": self._ts(t0),
            "dur": round((t1 - t0) * 1e6, 3),
            "pid": self.pid,
            "tid": self._tid(cat),
        }
        if args:
            ev["args"] = args
        self._stamp(ev)
        self._append(ev)

    def instant(self, name: str, cat: str = "host",
                args: Optional[Dict[str, Any]] = None) -> None:
        ev: Dict[str, Any] = {
            "name": name,
            "cat": cat,
            "ph": "i",
            "s": "g",
            "ts": self._ts(self.clock()),
            "pid": self.pid,
            "tid": self._tid(cat),
        }
        if args:
            ev["args"] = args
        self._stamp(ev)
        self._append(ev)

    def _flow(self, ph: str, name: str, flow_id: str, cat: str,
              args: Optional[Dict[str, Any]] = None) -> None:
        ev: Dict[str, Any] = {
            "name": name,
            "cat": cat,
            "ph": ph,
            "id": str(flow_id),
            "ts": self._ts(self.clock()),
            "pid": self.pid,
            "tid": self._tid(cat),
        }
        if ph == "f":
            # bind to the enclosing slice's end, the Perfetto-recommended
            # terminator so arrows land on the consuming span
            ev["bp"] = "e"
        if args:
            ev["args"] = args
        self._stamp(ev)
        self._append(ev)

    def flow_start(self, name: str, flow_id: str, cat: str = "host",
                   args: Optional[Dict[str, Any]] = None) -> None:
        """Chrome flow start ('s'): the producing side of a cross-thread /
        cross-process hand-off. Matched to flow_end by (cat, id)."""
        self._flow("s", name, flow_id, cat, args)

    def flow_end(self, name: str, flow_id: str, cat: str = "host",
                 args: Optional[Dict[str, Any]] = None) -> None:
        """Chrome flow finish ('f'): the consuming side of the hand-off."""
        self._flow("f", name, flow_id, cat, args)

    def tail(self, limit: int = 100) -> List[Dict[str, Any]]:
        """The most recent events, oldest-first (the /debug/trace body)."""
        with self._lock:
            events = list(self.events)
        _metrics.register().trace_ring_events.set(len(events))
        if limit > 0:
            events = events[-limit:]
        return events

    # -- export -----------------------------------------------------------
    def to_chrome(self) -> Dict[str, Any]:
        with self._lock:
            events = list(self.events)
            tids = sorted(self._tids.items(), key=lambda kv: kv[1])
        meta = [
            {"name": "process_name", "ph": "M", "ts": 0, "pid": self.pid,
             "tid": 0, "args": {"name": self.process_name}},
        ]
        for cat, tid in tids:
            meta.append({"name": "thread_name", "ph": "M", "ts": 0,
                         "pid": self.pid, "tid": tid, "args": {"name": cat}})
        return {"traceEvents": meta + events, "displayTimeUnit": "ms",
                "otherData": {"pid": self.pid,
                              "process_name": self.process_name,
                              "anchors": dict(self.anchors)}}

    def to_chrome_json(self) -> str:
        return json.dumps(self.to_chrome(), sort_keys=True,
                          separators=(",", ":")) + "\n"

    def to_jsonl(self) -> str:
        with self._lock:
            events = list(self.events)
        return "".join(
            json.dumps(ev, sort_keys=True, separators=(",", ":")) + "\n"
            for ev in events)

    def write(self, path: str) -> None:
        """Chrome trace for ``.json``, raw span stream for ``.jsonl``."""
        text = self.to_jsonl() if path.endswith(".jsonl") else self.to_chrome_json()
        with open(path, "w") as f:
            f.write(text)


# -- trace-context bridge (lazy: tracectx imports this module) -----------

_tracectx: Any = None


def _current_trace() -> Any:
    global _tracectx
    if _tracectx is None:
        from tpusim.obs import tracectx
        _tracectx = tracectx
    return _tracectx.current()


# -- module-level active recorder ----------------------------------------

_active: Optional[FlightRecorder] = None


def install(rec: FlightRecorder) -> FlightRecorder:
    global _active
    _active = rec
    if _GC not in gc.callbacks:
        gc.callbacks.append(_GC)
    return rec


def uninstall() -> None:
    global _active
    _active = None
    if _GC in gc.callbacks:
        gc.callbacks.remove(_GC)


def get_recorder() -> Optional[FlightRecorder]:
    return _active


def span(name: str, cat: str = "host") -> Any:
    """A live Span when a recorder is installed, else the shared no-op.

    Deliberately takes no args kwargs: attach labels via ``span.set``
    inside an ``if sp:`` guard so the disabled path allocates nothing.
    """
    rec = _active
    if rec is None:
        return NOOP_SPAN
    return rec.span(name, cat)


def root(name: str, cat: str = "host") -> Any:
    """The root span of one operation (`RootSpan`) when a recorder is
    installed and no root is open in this thread, else the shared no-op:
    roots do not nest, so an operation that calls another is one root."""
    rec = _active
    if rec is None or _open_root.get() is not None:
        return NOOP_SPAN
    return RootSpan(rec, name, cat)


def instant(name: str, cat: str = "host",
            args: Optional[Dict[str, Any]] = None) -> None:
    rec = _active
    if rec is not None:
        rec.instant(name, cat, args)


def flow_start(name: str, flow_id: str, cat: str = "host", site: str = "",
               args: Optional[Dict[str, Any]] = None) -> None:
    """Emit a flow start ('s') on the active recorder and count it under
    tpusim_trace_flows_total{site}; no-op when tracing is disabled."""
    rec = _active
    if rec is not None:
        rec.flow_start(name, flow_id, cat, args)
        if site:
            _metrics.register().trace_flows.inc(site)


def flow_end(name: str, flow_id: str, cat: str = "host",
             args: Optional[Dict[str, Any]] = None) -> None:
    rec = _active
    if rec is not None:
        rec.flow_end(name, flow_id, cat, args)


# -- telemetry bridges (metrics registry + recorder instants) ------------

def note_auto_transition(kind: str, sig: Optional[str] = None) -> None:
    """AUTO verify-then-trust transition: verify_pass/verify_fail/pin/
    trust/defer (shard_* for the node-sharded route)."""
    _metrics.register().backend_auto_transitions.inc(kind)
    rec = _active
    if rec is not None:
        rec.instant("auto:" + kind, "device",
                    {"sig": sig} if sig is not None else None)


def note_route(route: str, pods: Optional[int] = None) -> None:
    """Batch execution route: fastscan/fastscan_interpret/xla_scan/
    xla_chunked/reference_fallback."""
    _metrics.register().backend_route.inc(route)
    rec = _active
    if rec is not None:
        rec.instant("route:" + route, "device",
                    {"pods": pods} if pods is not None else None)


def note_fast_fallback(reason: str, detail: Optional[str] = None) -> None:
    """plan_fast rejected a batch: `reason` is the low-cardinality blocker
    class (backend._fast_fallback_key), `detail` the full reason string
    (trace-only — too high-cardinality for a metric label)."""
    rec = _active
    if rec is not None:
        rec.instant("fallback:" + reason, "device",
                    {"why": detail} if detail is not None else None)


# per-process count of each preemption's victim-selection path (read by
# tests as jaxe.preempt.PREEMPT_CLASS_STATS)
VICTIM_PATHS: Counter = Counter()


def note_victim_path(path: str) -> None:
    """Preemption victim-selection path: device/device_verified/host/
    fallback (see jaxe.preempt.PREEMPT_CLASS_STATS)."""
    VICTIM_PATHS[path] += 1
    _metrics.register().backend_victim_path.inc(path)
    rec = _active
    if rec is not None:
        rec.instant("victim:" + path, "device")


def note_gang(event: str, args: Optional[Dict[str, Any]] = None) -> None:
    """A gang admission decision (tpusim/gang): admit/reject/rollback/
    release. The matching counters (gang_admitted/gang_rejected/
    gang_partial_rollback) are incremented by the caller, which knows the
    reason label; this bridge only emits the flight-recorder instant."""
    rec = _active
    if rec is not None:
        rec.instant("gang:" + event, "host", args)


def note_fault(kind: str, args: Optional[Dict[str, Any]] = None) -> None:
    """A chaos-injected fault: node_delete/node_cordon/node_flap/
    node_restore/pod_evict, watch_drop/watch_dup/watch_disconnect,
    device_exception/device_corrupt_*, invariant_violation."""
    _metrics.register().fault_injected.inc(kind)
    rec = _active
    if rec is not None:
        rec.instant("fault:" + kind, "host", args)


def note_breaker(name: str, transition: str, state_value: float,
                 detail: Optional[str] = None) -> None:
    """A dispatch circuit-breaker transition: open/half_open/reopen/close.
    Mirrors the live state into the tpusim_breaker_state gauge."""
    reg = _metrics.register()
    reg.breaker_transitions.inc(transition)
    reg.breaker_state.set(state_value)
    rec = _active
    if rec is not None:
        args: Dict[str, Any] = {"breaker": name}
        if detail:
            args["detail"] = detail
        rec.instant("breaker:" + transition, "device", args)


def note_serve(event: str, args: Optional[Dict[str, Any]] = None) -> None:
    """A scenario-fleet lifecycle point: admit/reject/bucket/flush/
    dispatch/decode. Request-scoped phases additionally open `serve:*`
    SPANS at the call sites (tpusim.serve.*) so a trace shows the
    admission -> bucket -> dispatch -> decode pipeline per request; these
    instants mark the zero-duration transitions between them."""
    rec = _active
    if rec is not None:
        rec.instant("serve:" + event, "host", args)


def note_recovery(event: str, args: Optional[Dict[str, Any]] = None) -> None:
    """A crash-recovery lifecycle point (stream.persist): checkpoint /
    replay. Latencies land in the tpusim_recovery_* histograms at the
    call sites; these instants mark the transitions, and the replay
    itself additionally runs under a `recover:replay` span."""
    rec = _active
    if rec is not None:
        rec.instant("recover:" + event, "host", args)


def note_serve_retry(reason: str,
                     args: Optional[Dict[str, Any]] = None) -> None:
    """The serve fleet retried work after a fault: device_fault (injected
    dispatch death), worker_death (the processing thread died mid-request
    and the request was requeued at most once)."""
    _metrics.register().serve_retry.inc(reason)
    rec = _active
    if rec is not None:
        rec.instant("serve_retry:" + reason, "host", args)


def note_serve_degraded(path: str,
                        args: Optional[Dict[str, Any]] = None) -> None:
    """A serve bucket was answered via a degraded path: breaker_open /
    retry_exhausted (host reference fallback) or verify_divergence (host
    results replaced suspect device output)."""
    _metrics.register().serve_degraded.inc(path)
    rec = _active
    if rec is not None:
        rec.instant("serve_degraded:" + path, "host", args)


def note_stream_restage(reason: str, detail: Optional[str] = None) -> None:
    """The stream runtime invalidated its device-resident state and paid a
    full restage: `reason` is the low-cardinality residency-miss class
    (cold_start/policy_plan_change/node_set/groups_dirty/scalar_set/
    new_signature/sig_evict/group_shape/interpod_delta/watch_expired/
    breaker_open/device_fault/verify_divergence/unsupported/recovered —
    the last classifying a crash-recovered session's first restage),
    `detail` trace-only context."""
    _metrics.register().stream_restage.inc(reason)
    rec = _active
    if rec is not None:
        rec.instant("restage:" + reason, "device",
                    {"why": detail} if detail is not None else None)


def note_stream_cycle(path: str, pods: Optional[int] = None) -> None:
    """One StreamSession scheduling cycle: stream_scan (O(delta) resident
    dispatch), pipelined (resident dispatch with deferred decode),
    restage_scan (full re-stage + dispatch), host (reference fallback under
    chaos/unsupported features), gang (multi-pod all-or-nothing group
    cycle via tpusim/gang), or no_nodes (empty cluster — nothing to
    dispatch)."""
    _metrics.register().stream_cycles.inc(path)
    rec = _active
    if rec is not None:
        rec.instant("stream:" + path, "device",
                    {"pods": pods} if pods is not None else None)


def note_slo(event: str, args: Optional[Dict[str, Any]] = None) -> None:
    """An SLO burn-rate threshold crossing (obs.slo): burn_start when the
    windowed burn rate rises to/above the alerting threshold, burn_end when
    it falls back under. The live burn rate itself is the
    tpusim_slo_burn_rate gauge; these instants put the crossings on the
    trace timeline."""
    rec = _active
    if rec is not None:
        rec.instant("slo:" + event, "host", args)


def note_watch_overflow(resource: str) -> None:
    """A watch stream died on buffer overflow (the "410 Gone" analog):
    the consumer must relist to resync."""
    _metrics.register().watch_overflow.inc(resource)
    rec = _active
    if rec is not None:
        rec.instant("watch_overflow", "host", {"resource": resource})


# -- jax.profiler bridge --------------------------------------------------

_annotation_cls: Any = None


def profiled(name: str) -> Any:
    """`jax.profiler.TraceAnnotation` context so XLA profiles line up
    with recorder spans; degrades to a null context without jax. With a
    recorder installed the scope carries `rec_us`, the recorder's clock
    (`now_us`) at its start: a profile's event time minus `rec_us * 1000`
    is the offset that shifts the recorder's timeline onto the profile."""
    global _annotation_cls
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation
            _annotation_cls = TraceAnnotation
        except Exception:  # pragma: no cover - jax is a hard dep in practice
            import contextlib

            def _annotation_cls(name, **_):
                return contextlib.nullcontext()
    rec = _active
    if rec is None:
        return _annotation_cls(name)
    return _annotation_cls(name, rec_us=rec.now_us())

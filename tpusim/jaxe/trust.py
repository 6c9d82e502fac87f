"""AUTO verify-then-trust: one registry for every route that runs a faster
engine only once it has matched a reference engine.

A route's override variable gives its mode: "0" off, "1" on without
verification, unset AUTO. In AUTO the first run of each program signature
runs both engines and compares them: a match pins the signature, a
mismatch disables the route for the process (in every mode) and the
reference result is used, so AUTO never changes behaviour.

  FAST    Pallas fused scan vs the XLA scan, per kernel variant
          (backend.plan_signature); TPUSIM_FAST, on the TPU only unless
          TPUSIM_FAST_INTERPRET=1 comes with TPUSIM_FAST=1. Three
          transient runtime failures in a row also disable it.
  VICTIM  device victim selection vs the host Preempt pipeline, per
          (candidate, victim) bucket; TPUSIM_PREEMPT_DEVICE.
  SHARD   the node-sharded scan vs the single-device scan, per (shard
          count, engine config); always AUTO (TPUSIM_SHARDS is the count).
  GANG    the gang packing kernel vs the host oracle, per pow2 (members,
          nodes) bucket; TPUSIM_GANG_KERNEL.
"""

from __future__ import annotations

import logging
import os
from typing import Callable, Optional

from tpusim.obs import recorder as flight

log = logging.getLogger(__name__)

# pods the fast route's AUTO check replays through the XLA scan
REPLAY_PODS = 512
# the fewest replayed pods that pin a fast-route kernel variant; a smaller
# batch verifies without pinning, and run_batch defers it (jaxe/backend.py)
PIN_MIN_PODS = 64
# consecutive transient fast-route failures that disable the route
MAX_TRANSIENT_FAILURES = 3

_TRANSIENT_MARKERS = ("RESOURCE_EXHAUSTED", "out of memory", "OOM",
                      "UNAVAILABLE", "DEADLINE_EXCEEDED", "CANCELLED")
# a compile-time refusal can carry a runtime marker too (a VMEM or HBM
# overflow is RESOURCE_EXHAUSTED) and is never transient
_COMPILE_MARKERS = ("Mosaic", "compil", "lower", "vmem", "VMEM")


def fast_interpret() -> bool:
    """TPUSIM_FAST=1 with TPUSIM_FAST_INTERPRET=1: the fast route runs the
    Pallas interpreter off the TPU (the `fastscan_interpret` route)."""
    return (os.environ.get("TPUSIM_FAST") == "1"
            and os.environ.get("TPUSIM_FAST_INTERPRET") == "1")


def _on_tpu() -> bool:
    # off the TPU the kernel would run in the Pallas interpreter, far slower
    # than the XLA scan: only the explicit interpreter opt-in takes it there
    if fast_interpret():
        return True
    import jax

    return jax.default_backend() == "tpu"


class Trust:
    """One route's trust: its mode, the signatures pinned by a passed
    verification, `disabled` after a failed one, and (the fast route's)
    run of transient failures. `passed`, `pinned`, `failed` and `reused`
    name the transitions a verdict or a trusted run notes."""

    def __init__(self, name: str, env: Optional[str], *,
                 passed: Optional[str] = None, pinned: Optional[str] = None,
                 failed: str, reused: Optional[str] = None,
                 available: Callable[[], bool] = lambda: True,
                 note: Callable = flight.note_auto_transition):
        self.name, self.env = name, env
        self.passed, self.pinned, self.failed = passed, pinned, failed
        self.reused = reused
        self._available, self._note = available, note
        self.reset()

    def reset(self) -> None:
        self.disabled = False
        self.verified: set = set()
        self.transient = 0

    def mode(self) -> tuple[bool, bool]:
        """(enabled, auto): the route's override variable, read here only."""
        env = os.environ.get(self.env) if self.env else None
        if env == "0" or self.disabled:
            return False, False
        return self._available(), env != "1"

    def trusted(self, sig) -> bool:
        return sig in self.verified

    def note_reuse(self, label: str) -> None:
        """A run whose pinned signature let it skip verification."""
        self._emit(self.reused, label)

    def verdict(self, sig, ok: bool, *, pin: bool = True,
                label: Optional[str] = None) -> bool:
        """Record one verification of `sig`: a match pins it (unless `pin`
        is False), a mismatch disables the route. Returns `ok`."""
        label = str(sig) if label is None else label
        if not ok:
            self.disabled = True
            self._emit(self.failed, label)
            log.warning("%s route DISAGREES with its reference on %s; "
                        "disabling it for this process", self.name, label)
            return False
        self._emit(self.passed, label)
        if pin:
            self.verified.add(sig)
            self._emit(self.pinned, label)
            log.info("%s route verified on %s; trusting it for this "
                     "process", self.name, label)
        return True

    def note_failure(self, exc: Exception) -> bool:
        """Classify a run's failure. A transient runtime error returns True:
        the caller reruns that batch on the reference engine, and
        MAX_TRANSIENT_FAILURES in a row disable the route. Anything else
        (a lowering or compile error) returns False and the caller
        re-raises it: there is no quiet route around the device."""
        msg = f"{type(exc).__name__}: {exc}"
        if (not any(m in msg for m in _TRANSIENT_MARKERS)
                or any(m in msg for m in _COMPILE_MARKERS)):
            return False
        self.transient += 1
        self._emit("discard_transient", None)
        if self.transient >= MAX_TRANSIENT_FAILURES:
            self.disabled = True
            self._emit("discard_permanent", None)
            log.warning("%s route: %d consecutive transient failures; "
                        "disabling it for this process", self.name,
                        self.transient)
        else:
            log.warning("%s route: transient failure %d/%d (%s); this batch "
                        "reruns on the reference engine", self.name,
                        self.transient, MAX_TRANSIENT_FAILURES, msg)
        return True

    def note_success(self) -> None:
        """A run that completed ends the run of transient failures."""
        self.transient = 0

    def _emit(self, kind: Optional[str], label: Optional[str]) -> None:
        if kind is not None:
            self._note(kind, label)


FAST = Trust("fast", "TPUSIM_FAST", passed="verify_pass", pinned="pin",
             failed="verify_fail", reused="trust", available=_on_tpu)
VICTIM = Trust("victim", "TPUSIM_PREEMPT_DEVICE", pinned="device_verified",
               failed="fallback",
               note=lambda kind, _label: flight.note_victim_path(kind))
SHARD = Trust("shard", None, pinned="shard_pin", failed="shard_verify_fail",
              reused="shard_trust")
GANG = Trust("gang", "TPUSIM_GANG_KERNEL", passed="verify_pass",
             failed="verify_fail", reused="trust")
REGISTRY = (FAST, VICTIM, SHARD, GANG)


def reset_all() -> None:
    """Every route back to its boot state. Test isolation only (an autouse
    fixture in tests/conftest.py): a test that trips a verify failure or
    the transient rule would otherwise leak `disabled` or pinned
    signatures into every later test in the process."""
    for t in REGISTRY:
        t.reset()

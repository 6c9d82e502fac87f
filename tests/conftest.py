"""Test configuration: run JAX on a virtual 8-device CPU mesh.

The suite runs on the CPU (JAX_PLATFORMS=cpu, the default here); sharding
correctness is checked on host-platform virtual devices, which
XLA_FLAGS=--xla_force_host_platform_device_count must request before the
backend initializes. What only the chip's compiler can refuse is covered by
tests/test_tpu_compile.py, which compiles for a described v5e.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# test lanes (VERDICT r4 item 9): the full matrix takes ~15 min under
# xdist-4; a smoke lane must exist for iteration. The modules below hold the
# interpreter-mode kernel differentials, fuzz campaigns, and subprocess
# -heavy tests (every test >25s in the round-5 duration profile lives in
# one of them) — they are auto-marked `slow`, so:
#     python -m pytest tests/ -m "not slow" -x -q       # smoke, ~2 min
#     python -m pytest tests/ -q -p xdist -n 4          # full matrix
# ---------------------------------------------------------------------------

import pytest  # noqa: E402

SLOW_MODULES = {
    "test_fastscan", "test_whatif", "test_fuzz_differential",
    "test_multihost", "test_sharding", "test_jax_preempt", "test_delta",
    "test_event_log",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = os.path.basename(item.nodeid.split("::", 1)[0])
        if mod.endswith(".py"):
            mod = mod[:-3]
        if mod in SLOW_MODULES:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _isolate_fast_auto():
    """Reset the jaxe backend's process-wide trust state (fast-path AUTO
    flags, victim-kernel trust, chaos breaker seam) around every test: a
    test tripping the verify path must not flip fast-path
    eligibility for the rest of the session (ISSUE 4 satellite)."""
    from tpusim.jaxe.backend import reset_fast_auto

    reset_fast_auto()
    yield
    reset_fast_auto()

"""No quiet route around the device: a kernel that fails to lower raises,
a shard count the host cannot serve raises, and the persistent compile
cache sits where JAX_COMPILATION_CACHE_DIR says or at one fixed path."""

import json
import os
import subprocess
import sys

import pytest

from tpusim.api.snapshot import zipf_workload

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Lowering(NotImplementedError):
    pass


def _refuse(*_args, **_kwargs):
    raise _Lowering("64-bit types are not supported")


def _batch(snapshot, pods):
    from tpusim.jaxe.backend import JaxBackend

    JaxBackend().schedule(pods, snapshot)


def _what_if(snapshot, pods):
    from tpusim.jaxe.whatif import run_what_if

    run_what_if([(snapshot, pods)])


def _preemption(snapshot, pods):
    from tpusim.jaxe.preempt import run_with_preemption

    run_with_preemption(pods, snapshot)


@pytest.mark.parametrize("entry", [_batch, _what_if, _preemption],
                         ids=["batch", "what_if", "preemption"])
def test_pallas_lowering_error_raises(monkeypatch, entry):
    """Each caller of the Pallas kernel lets its lowering error through
    instead of quietly re-running the batch on the XLA scan."""
    monkeypatch.setenv("TPUSIM_FAST", "1")
    monkeypatch.setenv("TPUSIM_FAST_INTERPRET", "1")
    monkeypatch.setattr("tpusim.jaxe.fastscan.fast_scan", _refuse)
    snapshot, pods = zipf_workload(128, 16, seed=3,
                                   priorities=entry is _preemption)
    with pytest.raises(_Lowering):
        entry(snapshot, pods)


def test_more_shards_than_devices_raises(monkeypatch):
    import jax

    from tpusim.jaxe.backend import JaxBackend

    monkeypatch.setenv("TPUSIM_SHARDS", str(len(jax.devices()) + 1))
    snapshot, pods = zipf_workload(32, 16, seed=5)
    with pytest.raises(ValueError, match="TPUSIM_SHARDS"):
        JaxBackend().schedule(pods, snapshot)


@pytest.mark.parametrize("env_dir", [None, "placed"])
def test_compile_cache_dir_rule(tmp_path, env_dir):
    """Without JAX_COMPILATION_CACHE_DIR the package keeps the cache at
    <checkout>/.jax_cache; with it, JAX's own settings stand untouched,
    the minimum compile time to cache included."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if env_dir is not None:
        want = str(tmp_path / env_dir)
        env["JAX_COMPILATION_CACHE_DIR"] = want
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, jax, tpusim.jaxe as j; print(json.dumps("
         "[jax.config.jax_compilation_cache_dir, j.compile_cache_dir(),"
         " jax.config.jax_persistent_cache_min_compile_time_secs]))"],
        cwd=str(tmp_path), env=dict(env, PYTHONPATH=REPO),
        capture_output=True, text=True, timeout=120, check=True)
    placed, ours, min_s = json.loads(out.stdout.strip().splitlines()[-1])
    assert placed == want
    assert ours == (None if env_dir is not None else want)
    assert min_s == (1.0 if env_dir is not None else 0.5)

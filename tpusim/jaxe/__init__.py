"""The JAX/TPU backend: columnar cluster state + batched scheduling kernels.

Design (SURVEY.md §7, BASELINE.json north star): the reference's per-pod
Filter/Score loop over a 16-worker goroutine fan-out becomes

  1. a host-side COMPILE step — pods' symbolic features (node selectors,
     tolerations, node-affinity terms, controller refs, hostname pins) are
     interned into signature classes and evaluated against the STATIC node
     attributes (labels, taints, conditions — immutable during a simulation)
     into dense [signature, node] tables, using the parity engine's own
     matching functions so semantics match by construction. This subsumes the
     reference's equivalence cache (core/equivalence_cache.go): instead of
     memoizing per-pod predicate results behind an equivalence hash, every
     class×node result is materialized once, up front, vectorized.

  2. a DEVICE scan — `lax.scan` over the pod axis carrying only numeric
     aggregates (requested/nonzero resources, pod counts, the round-robin
     counter). Each step fuses predicate masks + reason codes, priority
     scores, weighted sum, tie-break selection, and the bind scatter-add into
     one compiled program. Exact integer semantics via int64 (x64 mode).

Integer/float precision: scores use int64 (Go int); BalancedResourceAllocation
uses float64 exactly like Go. Memory quantities are byte-exact int64.
"""

import os

import jax


def compile_cache_dir():
    """Where this package points JAX's persistent compilation cache: None
    when JAX_COMPILATION_CACHE_DIR is set (JAX reads that, and the rest of
    the cache settings, itself), else the fixed path <checkout>/.jax_cache,
    the directory above this package — a checkout when run from source,
    which is how this repo is run. The path must not move between
    processes, or no process finds another's entries."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


if compile_cache_dir() is not None:
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


_x64_enabled = False


def ensure_x64() -> None:
    """Enable 64-bit JAX types (process-global) before building device state.

    Go semantics are 64-bit; placement parity requires byte-exact memory sums
    and int64 score arithmetic. On TPU, int64 is emulated 32-bit-pairwise — the
    fast path can later narrow where ranges allow. Called explicitly from the
    backend entry points instead of at import, so that importing tpusim
    leaves a host application's dtypes alone.
    """
    global _x64_enabled
    if not _x64_enabled:
        jax.config.update("jax_enable_x64", True)
        _x64_enabled = True

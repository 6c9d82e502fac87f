"""The batched gang packing kernel behind AUTO verify-then-trust
(jaxe/trust.py, `GANG`): TPUSIM_GANG_KERNEL=0 forces the host oracle, =1
forces the device kernel without verification (benchmark/debug), unset =
AUTO — the first gang solved per (members, nodes) pow2-bucketed signature
runs BOTH sides and compares choices; a match pins the signature (later
gangs of that shape skip the host compute), any disagreement disables the
kernel process-wide and the host result is used, so AUTO can never change
behavior.
"""

from __future__ import annotations

import math
from typing import List

import numpy as np

from tpusim.gang import oracle as _oracle
from tpusim.jaxe.trust import GANG


def _sig(m: int, n: int) -> str:
    bucket = lambda v: 1 << max(0, math.ceil(math.log2(max(1, v))))
    return f"gang:{bucket(m)}x{bucket(n)}"


def gang_choices(feasible: np.ndarray, score: np.ndarray,
                 req_cpu, req_mem, req_gpu, req_eph, zero_request,
                 alloc_cpu, alloc_mem, alloc_gpu, alloc_eph, allowed_pods,
                 used_cpu, used_mem, used_gpu, used_eph, pod_count,
                 zone_dom: np.ndarray, rack_dom: np.ndarray,
                 n_zone: int, n_rack: int) -> List[int]:
    """Solve the joint packing for one gang, routing host/device per the
    AUTO seam. All inputs are host numpy; the device path ships them through
    jit and the result is compared (or trusted) per signature."""
    enabled, auto = GANG.mode()

    def run_host() -> List[int]:
        return _oracle.select_oracle(
            feasible, score, req_cpu, req_mem, req_gpu, req_eph,
            zero_request, alloc_cpu, alloc_mem, alloc_gpu, alloc_eph,
            allowed_pods, used_cpu, used_mem, used_gpu, used_eph,
            pod_count, zone_dom, rack_dom, n_zone, n_rack)

    if not enabled:
        return run_host()

    import jax.numpy as jnp
    from tpusim.jaxe.kernels import GangIn, gang_select

    gi = GangIn(
        alloc_cpu=jnp.asarray(alloc_cpu, dtype=jnp.int64),
        alloc_mem=jnp.asarray(alloc_mem, dtype=jnp.int64),
        alloc_gpu=jnp.asarray(alloc_gpu, dtype=jnp.int64),
        alloc_eph=jnp.asarray(alloc_eph, dtype=jnp.int64),
        allowed_pods=jnp.asarray(allowed_pods, dtype=jnp.int64),
        used_cpu=jnp.asarray(used_cpu, dtype=jnp.int64),
        used_mem=jnp.asarray(used_mem, dtype=jnp.int64),
        used_gpu=jnp.asarray(used_gpu, dtype=jnp.int64),
        used_eph=jnp.asarray(used_eph, dtype=jnp.int64),
        pod_count=jnp.asarray(pod_count, dtype=jnp.int64),
        zone_dom=jnp.asarray(zone_dom, dtype=jnp.int32),
        rack_dom=jnp.asarray(rack_dom, dtype=jnp.int32))
    device = [int(c) for c in np.asarray(gang_select(
        jnp.asarray(feasible, dtype=bool),
        jnp.asarray(score, dtype=jnp.int64),
        jnp.asarray(req_cpu, dtype=jnp.int64),
        jnp.asarray(req_mem, dtype=jnp.int64),
        jnp.asarray(req_gpu, dtype=jnp.int64),
        jnp.asarray(req_eph, dtype=jnp.int64),
        jnp.asarray(zero_request, dtype=bool),
        gi, n_zone=n_zone, n_rack=n_rack))]

    if not auto:
        return device
    sig = _sig(*feasible.shape)
    if GANG.trusted(sig):
        GANG.note_reuse(sig)
        return device
    host = run_host()
    return device if GANG.verdict(sig, host == device) else host

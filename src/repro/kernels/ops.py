"""Public jit'd wrappers for the Pallas kernels.

The kernels compile for the TPU (``interpret=False``, the default).  On a
CPU backend — the test suite runs under ``JAX_PLATFORMS=cpu`` — callers
pass ``interpret=True`` explicitly, which runs the kernel body with jnp
semantics; nothing here picks the mode from the backend.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import dispersed_gemm as _dg
from repro.kernels import flash_attention as _fa
from repro.kernels import ref as ref


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """FlashAttention-2 with GQA support: k/v may have fewer heads than q
    (q heads must be a multiple); they are expanded before the kernel."""
    hq, hkv = q.shape[1], k.shape[1]
    if hkv != hq:
        if hkv == 0 or hq % hkv:
            raise ValueError(
                f"GQA needs q heads ({hq}) to be a multiple of k/v heads "
                f"({hkv})")
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return _fa.flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)


def matmul(a, b, *, working_set: int = 4, block_m: int = 128,
           block_k: int = 512, interpret: bool = False):
    """Grouped (compact-working-set) GEMM — the recommended schedule."""
    return _dg.matmul_grouped(a, b, block_m=block_m, block_k=block_k,
                              working_set=working_set, interpret=interpret)


def matmul_dispersed(a, b, *, block_m: int = 128, block_k: int = 512,
                     interpret: bool = False):
    """Fully-dispersed (HBM round-trip accumulators) GEMM — the W=0 extreme."""
    return _dg.matmul_dispersed(a, b, block_m=block_m, block_k=block_k,
                                interpret=interpret)


hbm_traffic_model = _dg.hbm_traffic_model
flash_traffic_model = _fa.hbm_traffic_model

# Schedule geometries (grid + index maps shared with the pallas_calls) for
# the instrumented traffic count — see repro.kernels.traffic.
grouped_schedule = _dg.grouped_schedule
dispersed_schedule = _dg.dispersed_schedule
flash_schedule = _fa.flash_schedule

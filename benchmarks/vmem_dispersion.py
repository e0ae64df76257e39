"""Beyond-paper TPU analogue: HBM traffic vs VMEM accumulator working-set
size for the dispersed GEMM kernel (kernels/dispersed_gemm.py) — the cVRF
height/traffic trade-off (Fig 4's economics) at the VMEM<->HBM boundary.

Numeric correctness of both schedules is covered by tests; this benchmark
reports the closed-form traffic model on a training-shaped GEMM
(M=8192 tokens x K=4096 x N=14336, granite-8b MLP) and a small numeric
spot-check of the grouped kernel (``interpret=True`` on the CPU backend)."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.kernels import ops


def run(interpret: bool = False) -> list[dict]:
    rows = []
    m, k, n = 8192, 4096, 14336
    for w in (1, 2, 4, 8, 16):
        t = ops.hbm_traffic_model(m, n, k, block_m=128, block_k=512,
                                  working_set=w)
        rows.append(dict(
            name=f"traffic_W{w}", us_per_call=0.0,
            grouped_gb=round(t["grouped"] / 1e9, 2),
            dispersed_gb=round(t["dispersed"] / 1e9, 2),
            ideal_gb=round(t["ideal"] / 1e9, 2),
            vmem_acc_mb=round(t["vmem_acc_bytes"] / 1e6, 2),
        ))
    # small numeric spot-check
    a = jax.random.normal(jax.random.PRNGKey(0), (256, 512), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (512, 256), jnp.float32)
    t0 = time.time()
    got = ops.matmul(a, b, working_set=2, block_m=128, block_k=256,
                     interpret=interpret)
    want = a @ b
    err = float(jnp.max(jnp.abs(got - want)))
    rows.append(dict(name="interpret_check", grouped_gb="", dispersed_gb="",
                     ideal_gb="", vmem_acc_mb="",
                     us_per_call=round((time.time() - t0) * 1e6, 1),
                     max_err=round(err, 6)))
    return rows


def main(interpret: bool = False):
    rows = run(interpret=interpret)
    common.emit(rows, ["name", "us_per_call", "grouped_gb", "dispersed_gb",
                       "ideal_gb", "vmem_acc_mb", "max_err"])
    return rows


if __name__ == "__main__":
    main()

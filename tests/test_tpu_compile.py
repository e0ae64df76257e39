"""Ahead-of-time compiles for a described TPU v5e chip, at real widths.

Nothing runs: each test lowers a hot-path program with shapes placed on
one chip of a described ``v5e:2x2`` topology and compiles it with the
TPU compiler, which refuses what the chip would refuse (scoped VMEM,
tiling, HBM capacity).  The widths are ``chip_smoke.py``'s: phi3-mini's
FFN up-projection and attention, one paper-size engine bucket, the
engine's Pallas scan at the benchmark's dispatch shapes, and the
full-width phi3-mini decode step.

The topology is described inside a fixture (only the worker that runs
this file loads the TPU library), and the persistent compilation cache is
off around the compiles: an entry compiled for a described chip cannot be
read back without one.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import rvv
from repro.configs import get
from repro.core import policies, simulator
from repro.kernels import dispersed_gemm, flash_attention
from repro.models import get_model

GEMM_MKN = (512, 3072, 8192)      # phi3-mini FFN up-projection, 512 tokens
ATTN_BHSD = (1, 32, 2048, 96)     # phi3-mini attention, 2048 tokens
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Pallas kernel
    return compiled


@pytest.mark.parametrize("schedule", ["grouped_W4", "dispersed"])
def test_gemm_compiles_at_phi3_ffn_width(one_chip, schedule):
    m, k, n = GEMM_MKN
    a = _spec(one_chip, (m, k), jnp.bfloat16)
    b = _spec(one_chip, (k, n), jnp.bfloat16)
    if schedule == "dispersed":
        fn = lambda a, b: dispersed_gemm.matmul_dispersed(a, b)
    else:
        fn = lambda a, b: dispersed_gemm.matmul_grouped(a, b, working_set=4)
    _compile_kernel(fn, a, b)


def test_flash_attention_compiles_at_phi3_attention_shape(one_chip):
    q = _spec(one_chip, ATTN_BHSD, jnp.bfloat16)
    _compile_kernel(
        lambda q, k, v: flash_attention.flash_attention(q, k, v, causal=True),
        q, q, q)


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_engine_bucket_compiles(one_chip):
    """The fused (program, config, machine) scan at one bucket: the paper's
    Table 2 kernel count, the smoke test's capacity x policy grid."""
    b = rvv.BENCHMARKS["pathfinder"]
    prep = simulator.prepare(b.build(**b.paper_params).program, fold=True)
    arrays, spill0s, slots_used = simulator._stack(
        [prep] * len(rvv.PAPER_TABLE3))
    sweep = simulator.SweepConfig.product(
        [3, 4, 8, 32], [policies.FIFO, policies.LRU], [False])
    mach = simulator.MachineSweep.from_params([simulator.DEFAULT_MACHINE])

    def spec(x):
        x = np.asarray(x)
        return _spec(one_chip, x.shape, x.dtype)

    simulator._run_grid.lower(
        mach.l1_sets, mach.l1_ways, slots_used, True,
        tuple(spec(a) for a in arrays), spec(spill0s),
        tuple(spec(x) for x in (sweep.capacity, sweep.policy,
                                sweep.alloc_no_fetch)),
        tuple(spec(x) for x in (mach.l1_hit_cycles, mach.uop_hit_cycles,
                                mach.mem_latency))).compile()


# The Pallas scan at the benchmark's dispatch shapes (programs, bucket
# rows, configs, L1 sets): cell 1's largest bucket, a folded paper-size
# trace at 8 capacities x 2 policies, and cell 3's 16 KB dispatch of four
# phi3-mini tile programs at 5 capacities.
SCAN_DISPATCHES = {
    "capacity-sweep-131072": (1, 131072, 16, 256, True),
    "rvv-network-16kb": (4, 16384, 5, 256, False),
}


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize("name", sorted(SCAN_DISPATCHES))
def test_scan_kernel_compiles(one_chip, name):
    """The single-core scan as one Pallas kernel: its SMEM trace blocks
    and VMEM state fit what the chip's compiler allows."""
    programs, rows, configs, l1_sets, track_ab = SCAN_DISPATCHES[name]
    b = rvv.BENCHMARKS["gemv"]
    arrays, _, _ = simulator._stack(
        [simulator.prepare(b.build(**b.reduced_params).program)])

    def spec(shape, dtype=jnp.int32):
        return _spec(one_chip, shape, dtype)

    compiled = simulator._run_grid_pallas.lower(
        l1_sets, 2, (True,) * 5, track_ab,
        tuple(spec((programs, rows) + a.shape[2:], a.dtype) for a in arrays),
        spec((programs,)),
        (spec((configs,)), spec((configs,)), spec((configs,), jnp.bool_)),
        tuple(spec((1,)) for _ in range(3))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_phi3_decode_step_fits_one_chip(one_chip):
    """Full-width phi3-mini (bf16) decode with chip_smoke's 4 slots x 256
    positions: parameters, the cache in and out, and temporaries fit the
    chip's 16 GB of HBM."""
    model = get_model(get("phi3-mini-3.8b"))
    place = lambda tree: jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype), tree)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.init_cache(4, 256)))
    batch = {k: _spec(one_chip, (4, 1), jnp.int32)
             for k in ("tokens", "positions")}
    mem = jax.jit(model.decode_step).lower(
        params, cache, batch).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 7.5e9          # ~3.8 B bf16 params
    assert total < V5E_HBM_BYTES

"""Dispersed-accumulator GEMM: the cVRF trade-off at VMEM granularity.

TPU adaptation of Register Dispersion (DESIGN.md §2.B).  Output tiles of
C = A @ B play the role of *architectural vector registers*; the VMEM
accumulator scratch plays the role of the *compact physical register file*.

Two schedules expose the paper's trade-off:

  * ``matmul_grouped(working_set=W)`` — a compact set of W row-tile
    accumulators is VMEM-resident while the full K reduction completes for
    the group ("registers cached in the cVRF"): grid (groups, k, W).  The B
    panel is fetched once per (group, k) and reused W times, so B HBM
    traffic scales as 1/W — more physical registers => less memory traffic,
    exactly the paper's Fig 4 economics at a different level of the
    hierarchy.  VMEM cost grows linearly in W (the cVRF area analogue).

  * ``matmul_dispersed()`` — the W=0 extreme: every accumulator access
    round-trips through HBM (grid (k, m); the f32 accumulator is an HBM
    buffer aliased input->output, filled into VMEM and spilled back by
    explicit DMAs on every step), i.e. every "register access" is a
    spill+fill.

Both ``pallas_call``s size their scoped-VMEM limit from their own block
footprint (:func:`vmem_footprint`), so full-width model GEMMs compile
instead of tripping the compiler's default limit.

``hbm_traffic_model`` gives the closed-form bytes for the roofline tables;
``grouped_schedule`` / ``dispersed_schedule`` expose the grids and the
*same index-map lambdas* the ``pallas_call``s are built from, so
:func:`repro.kernels.traffic.count` can cross-check the closed form
against the schedule the hardware actually runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import traffic

ACC_BYTES = 4      # both schedules accumulate in f32

# Scoped-VMEM budget: v5e has 128 MiB of physical VMEM per core; a kernel
# may ask for at most this much, leaving room for the compiler's own use.
VMEM_CAP_BYTES = 96 * 2**20
# Added to every footprint for the compiler's internal scratch.
VMEM_SLACK_BYTES = 2 * 2**20


def _check_tiles(m: int, k: int, k2: int, *, block_m: int, block_k: int):
    """Shared kernel/model legality: clamp blocks, then require exact
    tiling.  Raises ``ValueError`` naming the offending dimension (bare
    asserts vanish under ``python -O`` and are useless from jit traces)."""
    if k != k2:
        raise ValueError(
            f"contraction mismatch: a has k={k} columns but b has k={k2} "
            f"rows")
    block_m = min(block_m, m)
    block_k = min(block_k, k)
    if block_m <= 0 or block_k <= 0:
        raise ValueError(
            f"block_m/block_k must be positive, got ({block_m}, {block_k})")
    if m % block_m:
        raise ValueError(
            f"m={m} is not divisible by block_m={block_m}; legal block_m "
            f"values divide m (e.g. {[d for d in (8, 16, 32, 64, 128, 256) if m % d == 0]})")
    if k % block_k:
        raise ValueError(
            f"k={k} is not divisible by block_k={block_k}; legal block_k "
            f"values divide k (e.g. {[d for d in (64, 128, 256, 512) if k % d == 0]})")
    return block_m, block_k, m // block_m, k // block_k


def _check_working_set(working_set: int, nm: int) -> tuple[int, int]:
    """Clamp W to the tile count, then require it to divide ``nm`` —
    the grouped grid is (groups, k, W) with groups = nm / W."""
    if working_set < 1:
        raise ValueError(
            f"working_set must be >= 1, got {working_set} (use "
            f"matmul_dispersed for the W=0 extreme)")
    w = min(working_set, nm)
    if nm % w:
        raise ValueError(
            f"working_set={working_set} (clamped to {w}) does not divide "
            f"the m-tile count nm={nm}; legal working sets: "
            f"{[d for d in range(1, nm + 1) if nm % d == 0]}")
    return w, nm // w


def _grouped_maps(w: int):
    """The grouped schedule's BlockSpec index maps — single source of
    truth for both ``matmul_grouped`` and its traffic schedule."""
    a = lambda g, ik, iw: (g * w + iw, ik)
    b = lambda g, ik, iw: (ik, 0)
    o = lambda g, ik, iw: (g * w + iw, 0)
    return a, b, o


def _dispersed_maps():
    a = lambda ik, im: (im, ik)
    b = lambda ik, im: (ik, 0)
    o = lambda ik, im: (im, 0)
    return a, b, o


def vmem_footprint(schedule: str, m: int, n: int, k: int, *, block_m: int,
                   block_k: int, working_set: int = 1,
                   bytes_per_el: int = 2) -> dict[str, int]:
    """VMEM bytes per buffer of one schedule's ``pallas_call``: the
    double-buffered A/B (and, grouped, C) pipeline blocks, the f32
    accumulator scratch, and the f32 product of one block step."""
    block_m, block_k, nm, _ = _check_tiles(
        m, k, k, block_m=block_m, block_k=block_k)
    acc_tile = block_m * n * ACC_BYTES
    parts = {"a": 2 * block_m * block_k * bytes_per_el,
             "b": 2 * block_k * n * bytes_per_el}
    if schedule == "grouped":
        w, _ = _check_working_set(working_set, nm)
        parts["c"] = 2 * block_m * n * bytes_per_el
        parts["acc"] = w * acc_tile
    elif schedule == "dispersed":
        parts["acc"] = acc_tile
    else:
        raise ValueError(f"unknown schedule {schedule!r}")
    parts["product"] = acc_tile
    return parts


def _compiler_params(name: str, parts: dict[str, int]) -> pltpu.CompilerParams:
    """Scoped-VMEM limit derived from the kernel's block footprint; a shape
    that cannot fit under :data:`VMEM_CAP_BYTES` is refused by name."""
    need = sum(parts.values()) + VMEM_SLACK_BYTES
    if need > VMEM_CAP_BYTES:
        blocks = ", ".join(f"{p}={v / 2**20:.1f} MiB"
                           for p, v in parts.items())
        raise ValueError(
            f"{name}: VMEM footprint {need / 2**20:.1f} MiB exceeds the "
            f"{VMEM_CAP_BYTES / 2**20:.0f} MiB budget ({blocks}); lower "
            f"block_k, block_m or working_set")
    return pltpu.CompilerParams(vmem_limit_bytes=need)


def _mxu_operand(ref):
    """Floating operands feed the MXU at their own width (f32 accumulate);
    integer operands are widened to f32 first."""
    x = ref[...]
    if jnp.issubdtype(x.dtype, jnp.floating):
        return x
    return x.astype(jnp.float32)


def _grouped_kernel(a_ref, b_ref, o_ref, acc_scr, *, nk: int):
    ik = pl.program_id(1)
    iw = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_scr[iw] = jnp.zeros_like(acc_scr[iw])

    acc_scr[iw] += jax.lax.dot_general(
        _mxu_operand(a_ref), _mxu_operand(b_ref),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _writeback():                      # "eviction" at end of reduction
        o_ref[...] = acc_scr[iw].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_k", "working_set", "interpret"))
def matmul_grouped(a, b, *, block_m: int = 128, block_k: int = 512,
                   working_set: int = 4, interpret: bool = False):
    """C = A @ B with a compact, VMEM-resident accumulator working set.

    Grid (groups, k, w), k middle: for each group of ``working_set`` M-tiles
    the full K reduction runs before moving on; the B panel block index
    depends only on k, so Pallas fetches it once per (group, k) and the
    pipeline reuses it across the W inner steps.
    """
    m, k = a.shape
    k2, n = b.shape
    block_m, block_k, nm, nk = _check_tiles(
        m, k, k2, block_m=block_m, block_k=block_k)
    w, groups = _check_working_set(working_set, nm)
    a_map, b_map, o_map = _grouped_maps(w)
    params = _compiler_params("matmul_grouped", vmem_footprint(
        "grouped", m, n, k, block_m=block_m, block_k=block_k,
        working_set=w, bytes_per_el=a.dtype.itemsize))

    out = pl.pallas_call(
        functools.partial(_grouped_kernel, nk=nk),
        grid=(groups, nk, w),
        in_specs=[
            pl.BlockSpec((block_m, block_k), a_map),
            pl.BlockSpec((block_k, n), b_map),
        ],
        out_specs=pl.BlockSpec((block_m, n), o_map),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((w, block_m, n), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(a, b)
    return out


def _dispersed_kernel(a_ref, b_ref, acc_in, acc_hbm, acc_vmem, sem, *,
                      block_m: int):
    # ``acc_in`` and ``acc_hbm`` are one HBM buffer (aliased input->output):
    # every step fills its accumulator tile from HBM, adds this k block's
    # product and spills the tile back before the grid moves on.
    del acc_in
    im = pl.program_id(1)
    rows = acc_hbm.at[pl.ds(pl.multiple_of(im * block_m, block_m), block_m)]
    fill = pltpu.make_async_copy(rows, acc_vmem, sem)
    fill.start()
    fill.wait()
    acc_vmem[...] += jax.lax.dot_general(
        _mxu_operand(a_ref), _mxu_operand(b_ref),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    spill = pltpu.make_async_copy(acc_vmem, rows, sem)
    spill.start()
    spill.wait()


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_k", "interpret"))
def matmul_dispersed(a, b, *, block_m: int = 128, block_k: int = 512,
                     interpret: bool = False):
    """The no-cache extreme: every accumulator revisit spills/fills HBM.

    Grid (k, m) with k outermost.  The f32 accumulator is an HBM buffer
    passed in (zeroed) and aliased to the output; each step DMAs its
    output tile in and back out (2*M*N*nk*4 bytes of accumulator traffic).
    """
    m, k = a.shape
    k2, n = b.shape
    block_m, block_k, nm, nk = _check_tiles(
        m, k, k2, block_m=block_m, block_k=block_k)
    a_map, b_map, _ = _dispersed_maps()
    params = _compiler_params("matmul_dispersed", vmem_footprint(
        "dispersed", m, n, k, block_m=block_m, block_k=block_k,
        bytes_per_el=a.dtype.itemsize))
    hbm = pl.BlockSpec(memory_space=pl.ANY)

    out = pl.pallas_call(
        functools.partial(_dispersed_kernel, block_m=block_m),
        grid=(nk, nm),
        in_specs=[
            pl.BlockSpec((block_m, block_k), a_map),
            pl.BlockSpec((block_k, n), b_map),
            hbm,
        ],
        out_specs=hbm,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, n), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        input_output_aliases={2: 0},
        compiler_params=params,
        interpret=interpret,
    )(a, b, jnp.zeros((m, n), jnp.float32))
    return out.astype(a.dtype)


# ---------------------------------------------------------------------------
# Traffic geometry: the measured side of the roofline's model check.
# ---------------------------------------------------------------------------


def grouped_schedule(m: int, n: int, k: int, *, block_m: int, block_k: int,
                     working_set: int,
                     bytes_per_el: int = 2) -> traffic.Schedule:
    """The grouped schedule's grid + operand parts, built from the same
    index maps as ``matmul_grouped`` (A/B stream in at the input width;
    C is a pure output — the accumulator lives in VMEM scratch)."""
    block_m, block_k, nm, nk = _check_tiles(
        m, k, k, block_m=block_m, block_k=block_k)
    w, groups = _check_working_set(working_set, nm)
    a_map, b_map, o_map = _grouped_maps(w)
    return traffic.Schedule(
        grid=(groups, nk, w),
        parts=(
            traffic.Part("a", block_m * block_k * bytes_per_el, a_map, "in"),
            traffic.Part("b", block_k * n * bytes_per_el, b_map, "in"),
            traffic.Part("c", block_m * n * bytes_per_el, o_map, "out"),
        ))


def dispersed_schedule(m: int, n: int, k: int, *, block_m: int,
                       block_k: int,
                       bytes_per_el: int = 2) -> traffic.Schedule:
    """The dispersed schedule's geometry: C is an HBM-resident accumulator
    (kind ``"acc"``) — every revisit is a fill + spill at f32 width."""
    block_m, block_k, nm, nk = _check_tiles(
        m, k, k, block_m=block_m, block_k=block_k)
    a_map, b_map, o_map = _dispersed_maps()
    return traffic.Schedule(
        grid=(nk, nm),
        parts=(
            traffic.Part("a", block_m * block_k * bytes_per_el, a_map, "in"),
            traffic.Part("b", block_k * n * bytes_per_el, b_map, "in"),
            traffic.Part("c", block_m * n * ACC_BYTES, o_map, "acc"),
        ))


def hbm_traffic_model(m: int, n: int, k: int, *, block_m: int, block_k: int,
                      working_set: int, bytes_per_el: int = 2) -> dict:
    """Closed-form HBM bytes for the two schedules (roofline input).

    grouped: A once, B once per group (= nm/W fetches of the full panel),
    C written once — all at the input element width (the accumulator stays
    in VMEM scratch).
    dispersed: A once, B once (reused across m at fixed k), C spilled AND
    filled on each of the nk k-steps at the f32 accumulator width.

    Legality mirrors the kernels: blocks are clamped to the problem dims,
    tiling must be exact, and ``working_set`` (after clamping to the m-tile
    count) must divide it — ``matmul_grouped`` rejects exactly the same
    configurations, so the model can never quote traffic for a schedule
    the kernel refuses to run.
    """
    block_m, block_k, nm, nk = _check_tiles(
        m, k, k, block_m=block_m, block_k=block_k)
    w, groups = _check_working_set(working_set, nm)
    grouped = (m * k + groups * k * n + m * n) * bytes_per_el
    dispersed = (m * k + k * n) * bytes_per_el + 2 * m * n * nk * ACC_BYTES
    ideal = (m * k + k * n + m * n) * bytes_per_el
    return dict(grouped=grouped, dispersed=dispersed, ideal=ideal,
                vmem_acc_bytes=w * block_m * n * ACC_BYTES)

"""Lowering: LayerOps -> certified-foldable ``Assembler.repeat`` programs.

Every op kind lowers to a fixed-shape *tile* program whose outer loops
certify exact under :mod:`repro.core.folding`:

- ``gemm``: the :mod:`repro.rvv.gemm` broadcast-MAC nest wrapped in a tile
  loop.  Per-tile A and C planes are padded to whole L1 way-spans (8 KB)
  so the tile axis is set-congruent and folds exact, exactly like the mha
  head loop.
- ``attn``: delegates to :func:`repro.rvv.mha.build` at the bridge's
  attention tile — the head loop is already way-span padded there.
- ``scan``: an elementwise recurrence ``h <- a * h + x_t`` (the shared
  shape of the Mamba selective scan and the RG-LRU gate recurrence): ``h``
  and the decay ``a`` live at step-invariant addresses, the per-step input
  plane is way-span padded, so the step loop is set-congruent.

Decode-phase kinds (see :func:`repro.bridge.shapes.decode_ops`):

- ``dgemm`` / ``expert``: the skinny GEMM of a decode step, M = 1..8 token
  rows against streamed weight column blocks.  The M activation rows are
  shared by every tile, each tile's weight block and output are way-span
  padded planes, and one weight vector load feeds M live accumulators:
  fewer rows, fewer live registers.
- ``mla``: absorbed latent-attention decode, one sequence per fold period
  (per-sequence query, cache and output planes way-span padded; weights
  and scratch shared).  The query is absorbed into the latent space
  (``q_nope W_UK``), every cached latent and rope row is loaded once and
  serves all heads (one live score accumulator per head), a softmax runs
  over the context, the probability-weighted latent sum is again one row
  load for all heads, and ``W_UV`` takes it back to the value width.

A tile covers a fixed sub-problem of the real layer; the ratio
real-work / tile-work is the layer's *macro factor*, used when aggregating
tile counters back to network totals.  Tile caps (``K_CAP``/``N_CAP``,
``ATTN_TILE``, ``SCAN_WIDTH_CAP``) bound trace length; the real shape
lives on in the kernel name and the macro factor.

``unroll=True`` on the emitters produces the same instruction stream with
explicit Python loops and literal addresses instead of ``repeat`` strides
— the property tests compare the two row-for-row.
"""

from __future__ import annotations

import numpy as np

from repro.core import isa
from repro.core.simulator import ScalarCost
from repro.core.trace import Assembler, MemoryMap
from repro.rvv import common, mha
from repro.bridge import shapes
from repro.bridge.shapes import TOKEN_BLOCK, LayerOp

# Way-span padding: 8 KB (256 sets x 32 B line) in 4-byte words.  Planes
# padded to this pitch keep outer-loop iterations set-congruent.
_WAY_SPAN_WORDS = 2048

ACC, AR, BR, ZR = 1, 2, 3, 31           # gemm register roles (rvv.gemm)
HR, CR, XR = 4, 5, 6                    # scan register roles

# ---- tile caps (the lowering contract, see docs/bridge.md) ----------------
TILES, MT = 8, 2                        # gemm: 8 way-span tiles x 2 rows
K_CAP, N_CAP = 64, 32                   # gemm reduction / output caps
ATTN_TILE = dict(seq=16, d=32, bc=16, heads=8)
SCAN_STEPS, SCAN_WIDTH_CAP = 12, 512
DM_CAP = 8                              # dgemm/expert: rows (accumulators)
# mla: 128 positions x latent 64 make a sequence's latent plane 32 KB,
# larger than a 16 KB L1, so the score and weighted-sum passes stream the
# cache as a 4,096-position one does (at 32 positions the weighted sum hit
# the lines the score pass had just loaded).
MLA_CAPS = dict(seqs=8, heads=16, nope=16, rope=8, latent=64, vdim=16,
                ctx=128)
MLA_EXP_SQUARINGS = 12                  # exp ~ (1 + x/4096)**4096

ACC0 = 4                                # dgemm accumulators v4..v11
# mla register roles: per-head scores / probabilities live in v4..v19
LR, QR, RR, AR2, BR2, OR = 2, 3, 20, 21, 22, 23
MR, TR, CLR, SR = 24, 25, 26, 27


def _pad(words: int) -> int:
    """Round a plane size up to a whole number of L1 way-spans."""
    return -(-words // _WAY_SPAN_WORDS) * _WAY_SPAN_WORDS


def _round8(x: int) -> int:
    """Clamp to a positive multiple of VL (vector stores need n % 8 == 0)."""
    return max(isa.VL_ELEMS, (x // isa.VL_ELEMS) * isa.VL_ELEMS)


# ---------------------------------------------------------------------------
# gemm tile
# ---------------------------------------------------------------------------


def build_gemm(tiles=TILES, mt=MT, k=K_CAP, n=N_CAP, seed=0,
               unroll=False) -> common.Built:
    """Tiled GEMM: ``tiles`` independent (mt x k) @ (k x n) products against
    a shared B.  A/C planes are way-span padded per tile, so the tile loop
    is set-congruent and folds exact; the inner nest is rvv.gemm's 4-vreg
    broadcast-MAC pattern."""
    assert n % isa.VL_ELEMS == 0 and k >= 1 and mt >= 1
    g = common.rng(seed)
    A = (g.standard_normal((tiles, mt, k)) / np.sqrt(k)).astype(np.float32)
    B = (g.standard_normal((k, n)) / np.sqrt(k)).astype(np.float32)
    pa, pc = _pad(mt * k), _pad(mt * n)

    Abuf = np.zeros((tiles, pa), np.float32)
    Abuf[:, : mt * k] = A.reshape(tiles, mt * k)
    mm = MemoryMap()
    aa = mm.alloc("A", Abuf)
    ab = mm.alloc("B", B)
    ac = mm.alloc("C", tiles * pc)
    az = mm.alloc("zero", np.zeros(1, np.float32))

    a = Assembler("net_gemm")
    a.vbcast(ZR, az)
    chunks = n // isa.VL_ELEMS
    if unroll:
        for t in range(tiles):
            for mi in range(mt):
                for c in range(chunks):
                    a.vmv(ACC, ZR)
                    for kk in range(k):
                        a.vbcast(AR, aa + 4 * kk + k * 4 * mi + pa * 4 * t)
                        a.vle(BR, ab + n * 4 * kk + 32 * c)
                        a.vmacc(ACC, AR, BR)
                    a.vse(ACC, ac + 32 * c + n * 4 * mi + pc * 4 * t)
                    a.scalar(3)
                a.scalar(3)
            a.scalar(3)
    else:
        with a.repeat(tiles):            # way-span-padded tile loop
            with a.repeat(mt):
                with a.repeat(chunks):
                    a.vmv(ACC, ZR)
                    with a.repeat(k):
                        a.vbcast(AR, aa, strides=(4, 0, k * 4, pa * 4))
                        a.vle(BR, ab, strides=(n * 4, 32, 0, 0))
                        a.vmacc(ACC, AR, BR)
                    a.vse(ACC, ac, strides=(32, n * 4, pc * 4))
                    a.scalar(3)
                a.scalar(3)
            a.scalar(3)
    prog = a.finalize(mm)

    C = np.zeros((tiles, pc), np.float32)
    for t in range(tiles):
        C[t, : mt * n] = (A[t].astype(np.float64)
                          @ B.astype(np.float64)).astype(np.float32).ravel()
    return common.Built(prog, {"C": C}, rtol=2e-4, atol=1e-5)


def gemm_scalar_cost(tiles=TILES, mt=MT, k=K_CAP, n=N_CAP, **_) -> ScalarCost:
    macs = tiles * mt * k * n
    return ScalarCost(flop_ops=macs, loads=macs + tiles * mt * k,
                      stores=tiles * mt * n,
                      unique_lines=(tiles * mt * (k + n) + k * n) // 8,
                      loop_iters=macs)


# ---------------------------------------------------------------------------
# scan tile
# ---------------------------------------------------------------------------


def build_scan(steps=SCAN_STEPS, width=SCAN_WIDTH_CAP, seed=0,
               unroll=False) -> common.Built:
    """Elementwise recurrence ``h <- a * h + x_t`` over ``width`` channels
    for ``steps`` steps (the data-flow shape shared by the Mamba selective
    scan and the RG-LRU).  ``h`` and ``a`` sit at step-invariant addresses;
    the per-step input plane is way-span padded, so the step loop is
    set-congruent and folds exact."""
    assert width % isa.VL_ELEMS == 0
    g = common.rng(seed)
    h0 = g.standard_normal(width).astype(np.float32)
    coef = (0.5 + 0.4 * g.random(width)).astype(np.float32)
    X = (g.standard_normal((steps, width)) * 0.1).astype(np.float32)
    pw = _pad(width)

    Xbuf = np.zeros((steps, pw), np.float32)
    Xbuf[:, :width] = X
    mm = MemoryMap()
    ah = mm.alloc("h", h0.copy())
    aco = mm.alloc("coef", coef)
    ax = mm.alloc("X", Xbuf)

    a = Assembler("net_scan")
    chunks = width // isa.VL_ELEMS
    if unroll:
        for t in range(steps):
            for c in range(chunks):
                a.vle(HR, ah + 32 * c)
                a.vle(CR, aco + 32 * c)
                a.vmul(HR, HR, CR)
                a.vle(XR, ax + 32 * c + pw * 4 * t)
                a.vadd(HR, HR, XR)
                a.vse(HR, ah + 32 * c)
                a.scalar(2)
            a.scalar(3)
    else:
        with a.repeat(steps):            # way-span-padded step loop
            with a.repeat(chunks):
                a.vle(HR, ah, strides=(32, 0))
                a.vle(CR, aco, strides=(32, 0))
                a.vmul(HR, HR, CR)
                a.vle(XR, ax, strides=(32, pw * 4))
                a.vadd(HR, HR, XR)
                a.vse(HR, ah, strides=(32, 0))
                a.scalar(2)
            a.scalar(3)
    prog = a.finalize(mm)

    h = h0.astype(np.float64)
    for t in range(steps):
        h = coef.astype(np.float64) * h + X[t].astype(np.float64)
    return common.Built(prog, {"h": h.astype(np.float32)},
                        rtol=2e-4, atol=1e-5)


def scan_scalar_cost(steps=SCAN_STEPS, width=SCAN_WIDTH_CAP,
                     **_) -> ScalarCost:
    updates = steps * width
    return ScalarCost(flop_ops=2 * updates, loads=3 * updates,
                      stores=updates,
                      unique_lines=(2 * width + updates) // 8,
                      loop_iters=updates)


# ---------------------------------------------------------------------------
# attn tile (delegates to the mha kernel)
# ---------------------------------------------------------------------------


def build_attn(seq=ATTN_TILE["seq"], d=ATTN_TILE["d"], bc=ATTN_TILE["bc"],
               heads=ATTN_TILE["heads"], seed=0) -> common.Built:
    """Attention tile: the mha FlashAttention-2 emission with way-span
    padded head planes (certified fold of the head loop)."""
    return mha.build(seq=seq, d=d, bc=bc, heads=heads, seed=seed)


def attn_scalar_cost(**kw) -> ScalarCost:
    return mha.scalar_cost(**kw)


# ---------------------------------------------------------------------------
# dgemm tile (decode: M token rows against streamed weight blocks)
# ---------------------------------------------------------------------------


def dgemm_program(A: np.ndarray, W: np.ndarray,
                  unroll: bool = False) -> common.Built:
    """Skinny GEMM ``C[t] = A @ W[t]`` for every tile ``t``: ``A`` (m, k)
    holds the step's token rows and is shared by every tile; ``W`` (tiles,
    k, n) is one weight column block per tile, each a way-span padded
    plane, as is each tile's output.  Per output chunk, one weight vector
    load feeds ``m`` live accumulators (v4..), so the tile loop folds exact
    and the register footprint follows the rows."""
    m, k = A.shape
    tiles, k2, n = W.shape
    assert k2 == k and n % isa.VL_ELEMS == 0 and 1 <= m <= DM_CAP
    pw, pc = _pad(k * n), _pad(m * n)
    Wbuf = np.zeros((tiles, pw), np.float32)
    Wbuf[:, : k * n] = W.reshape(tiles, k * n)
    mm = MemoryMap()
    aa = mm.alloc("A", A)
    aw = mm.alloc("W", Wbuf)
    ac = mm.alloc("C", tiles * pc)
    az = mm.alloc("zero", np.zeros(1, np.float32))

    a = Assembler("net_dgemm")
    a.vbcast(ZR, az)
    chunks = n // isa.VL_ELEMS
    if unroll:
        for t in range(tiles):
            for c in range(chunks):
                for r in range(m):
                    a.vmv(ACC0 + r, ZR)
                for kk in range(k):
                    a.vle(BR, aw + n * 4 * kk + 32 * c + pw * 4 * t)
                    for r in range(m):
                        a.vbcast(AR, aa + r * k * 4 + 4 * kk)
                        a.vmacc(ACC0 + r, AR, BR)
                for r in range(m):
                    a.vse(ACC0 + r, ac + r * n * 4 + 32 * c + pc * 4 * t)
                a.scalar(3)
            a.scalar(3)
    else:
        with a.repeat(tiles):            # way-span-padded weight blocks
            with a.repeat(chunks):
                for r in range(m):
                    a.vmv(ACC0 + r, ZR)
                with a.repeat(k):
                    a.vle(BR, aw, strides=(n * 4, 32, pw * 4))
                    for r in range(m):
                        a.vbcast(AR, aa + r * k * 4, strides=(4, 0, 0))
                        a.vmacc(ACC0 + r, AR, BR)
                for r in range(m):
                    a.vse(ACC0 + r, ac + r * n * 4, strides=(32, pc * 4))
                a.scalar(3)
            a.scalar(3)
    prog = a.finalize(mm)

    C = np.zeros((tiles, pc), np.float32)
    for t in range(tiles):
        C[t, : m * n] = (A.astype(np.float64)
                         @ W[t].astype(np.float64)).astype(np.float32).ravel()
    return common.Built(prog, {"C": C}, rtol=2e-4, atol=1e-5)


def build_dgemm(tiles=TILES, m=1, k=K_CAP, n=N_CAP, seed=0,
                unroll=False) -> common.Built:
    """:func:`dgemm_program` on seeded random rows and weights."""
    g = common.rng(seed)
    A = (g.standard_normal((m, k)) / np.sqrt(k)).astype(np.float32)
    W = (g.standard_normal((tiles, k, n)) / np.sqrt(k)).astype(np.float32)
    return dgemm_program(A, W, unroll=unroll)


def dgemm_scalar_cost(tiles=TILES, m=1, k=K_CAP, n=N_CAP,
                      **_) -> ScalarCost:
    macs = tiles * m * k * n
    return ScalarCost(flop_ops=macs, loads=macs + tiles * m * k,
                      stores=tiles * m * n,
                      unique_lines=(m * k + tiles * (k * n + m * n)) // 8,
                      loop_iters=macs)


# ---------------------------------------------------------------------------
# mla tile (absorbed latent-attention decode)
# ---------------------------------------------------------------------------


def mla_program(q_nope, q_rope, c_kv, k_rope, w_uk, w_uv, scale: float,
                unroll: bool = False) -> common.Built:
    """Absorbed MLA decode of ``S`` sequences, one new token each.

    Shapes: ``q_nope`` (S, H, nope), ``q_rope`` (S, H, rope) (rotary
    already applied), ``c_kv`` (S, T, latent) and ``k_rope`` (S, T, rope)
    the cache of T positions, ``w_uk`` (H, latent, nope) and ``w_uv`` (H,
    latent, vdim) the key and value up-projections.  Out: ``O`` (S, H,
    vdim) = softmax(scale * (q_nope . k_nope + q_rope . k_rope)) @ v with
    ``k_nope = c_kv @ w_uk[h]`` and ``v = c_kv @ w_uv[h]`` never formed:
    the query is absorbed (``w_uk[h] @ q_nope``) and the latent sum is
    projected after the weighting (``(p @ c_kv) @ w_uv[h]``).  exp is
    ``common.emit_exp`` with :data:`MLA_EXP_SQUARINGS` squarings."""
    S, H, dn = q_nope.shape
    dr = q_rope.shape[2]
    T, L = c_kv.shape[1:]
    dv = w_uv.shape[2]
    VL = isa.VL_ELEMS
    assert H <= 16 and not (dr % VL or L % VL or dv % VL or T % VL)
    pqn, pqr, pck, pkr, po = (_pad(x) for x in (H * dn, H * dr, T * L,
                                                 T * dr, H * dv))

    def planes(x, pitch):                # (S, ...) -> (S, pitch) planes
        out = np.zeros((S, pitch), np.float32)
        out[:, : x[0].size] = x.reshape(S, -1)
        return out

    mm = MemoryMap()
    aqn = mm.alloc("QN", planes(q_nope, pqn))
    aqr = mm.alloc("QR", planes(q_rope, pqr))
    ackv = mm.alloc("CKV", planes(c_kv, pck))
    akr = mm.alloc("KR", planes(k_rope, pkr))
    ao = mm.alloc("O", S * po)
    awuk = mm.alloc("WUKT", np.ascontiguousarray(w_uk.transpose(0, 2, 1)))
    awuv = mm.alloc("WUV", w_uv)
    aql = mm.alloc("QL", H * L)           # absorbed queries
    asc = mm.alloc("S", H * T)            # scores, then probabilities
    aol = mm.alloc("OL", H * L)           # weighted latent sums
    amx = mm.alloc("mx", VL)
    als = mm.alloc("lsum", VL)
    az = mm.alloc("zero", np.zeros(1, np.float32))
    an = mm.alloc("neginf", np.full(1, -1e9, np.float32))
    acl = mm.alloc("clamp", np.full(1, common.EXP_CLAMP, np.float32))
    lc, rc, vc, tc = L // VL, dr // VL, dv // VL, T // VL

    a = Assembler("net_mla")
    a.vbcast(ZR, az)
    a.vbcast(CLR, acl)
    if unroll:
        for s in range(S):
            for h in range(H):                        # absorb the query
                for c in range(lc):
                    a.vmv(OR, ZR)
                    for i in range(dn):
                        a.vbcast(AR2, aqn + pqn * 4 * s + dn * 4 * h + 4 * i)
                        a.vle(BR2, awuk + dn * L * 4 * h + L * 4 * i + 32 * c)
                        a.vmacc(OR, AR2, BR2)
                    a.vse(OR, aql + L * 4 * h + 32 * c)
                    a.scalar(3)
            for j in range(T):                        # scores
                for h in range(H):
                    a.vmv(4 + h, ZR)
                for c in range(lc):
                    a.vle(LR, ackv + pck * 4 * s + L * 4 * j + 32 * c)
                    for h in range(H):
                        a.vle(QR, aql + h * L * 4 + 32 * c)
                        a.vmacc(4 + h, QR, LR)
                for c in range(rc):
                    a.vle(LR, akr + pkr * 4 * s + dr * 4 * j + 32 * c)
                    for h in range(H):
                        a.vle(QR, aqr + pqr * 4 * s + h * dr * 4 + 32 * c)
                        a.vmacc(4 + h, QR, LR)
                for h in range(H):
                    a.vredsum(RR, ZR, 4 + h)
                    a.vmul_sc(RR, RR, scale)
                    a.vses(RR, asc + h * T * 4 + 4 * j)
                a.scalar(2)
            for h in range(H):                        # softmax per head
                a.vbcast(MR, an)
                for c in range(tc):
                    a.vle(TR, asc + T * 4 * h + 32 * c)
                    a.vredmax(MR, MR, TR)
                a.vses(MR, amx)
                a.vbcast(MR, amx)
                a.vmv(SR, ZR)
                for c in range(tc):
                    a.vle(TR, asc + T * 4 * h + 32 * c)
                    a.vsub(TR, TR, MR)
                    common.emit_exp(a, TR, CLR, MLA_EXP_SQUARINGS)
                    a.vse(TR, asc + T * 4 * h + 32 * c)
                    a.vredsum(SR, SR, TR)
                a.vses(SR, als)
                a.vbcast(SR, als)
                for c in range(tc):
                    a.vle(TR, asc + T * 4 * h + 32 * c)
                    a.vdiv(TR, TR, SR)
                    a.vse(TR, asc + T * 4 * h + 32 * c)
                a.scalar(3)
            for c in range(H * lc):                   # weighted latent sum
                a.vse(ZR, aol + 32 * c)
            for j in range(T):
                for h in range(H):
                    a.vbcast(4 + h, asc + h * T * 4 + 4 * j)
                for c in range(lc):
                    a.vle(LR, ackv + pck * 4 * s + L * 4 * j + 32 * c)
                    for h in range(H):
                        a.vle(OR, aol + h * L * 4 + 32 * c)
                        a.vmacc(OR, 4 + h, LR)
                        a.vse(OR, aol + h * L * 4 + 32 * c)
                a.scalar(2)
            for h in range(H):                        # value up-projection
                for c in range(vc):
                    a.vmv(OR, ZR)
                    for i in range(L):
                        a.vbcast(AR2, aol + L * 4 * h + 4 * i)
                        a.vle(BR2, awuv + L * dv * 4 * h + dv * 4 * i
                              + 32 * c)
                        a.vmacc(OR, AR2, BR2)
                    a.vse(OR, ao + po * 4 * s + dv * 4 * h + 32 * c)
                    a.scalar(3)
    else:
        with a.repeat(S):                # way-span-padded sequence planes
            with a.repeat(H):                         # absorb the query
                with a.repeat(lc):
                    a.vmv(OR, ZR)
                    with a.repeat(dn):
                        a.vbcast(AR2, aqn, strides=(4, 0, dn * 4, pqn * 4))
                        a.vle(BR2, awuk, strides=(L * 4, 32, dn * L * 4, 0))
                        a.vmacc(OR, AR2, BR2)
                    a.vse(OR, aql, strides=(32, L * 4, 0))
                    a.scalar(3)
            with a.repeat(T):                         # scores
                for h in range(H):
                    a.vmv(4 + h, ZR)
                with a.repeat(lc):
                    a.vle(LR, ackv, strides=(32, L * 4, pck * 4))
                    for h in range(H):
                        a.vle(QR, aql + h * L * 4, strides=(32, 0, 0))
                        a.vmacc(4 + h, QR, LR)
                with a.repeat(rc):
                    a.vle(LR, akr, strides=(32, dr * 4, pkr * 4))
                    for h in range(H):
                        a.vle(QR, aqr + h * dr * 4,
                              strides=(32, 0, pqr * 4))
                        a.vmacc(4 + h, QR, LR)
                for h in range(H):
                    a.vredsum(RR, ZR, 4 + h)
                    a.vmul_sc(RR, RR, scale)
                    a.vses(RR, asc + h * T * 4, strides=(4, 0))
                a.scalar(2)
            with a.repeat(H):                         # softmax per head
                a.vbcast(MR, an)
                with a.repeat(tc):
                    a.vle(TR, asc, strides=(32, T * 4, 0))
                    a.vredmax(MR, MR, TR)
                a.vses(MR, amx)
                a.vbcast(MR, amx)
                a.vmv(SR, ZR)
                with a.repeat(tc):
                    a.vle(TR, asc, strides=(32, T * 4, 0))
                    a.vsub(TR, TR, MR)
                    common.emit_exp(a, TR, CLR, MLA_EXP_SQUARINGS)
                    a.vse(TR, asc, strides=(32, T * 4, 0))
                    a.vredsum(SR, SR, TR)
                a.vses(SR, als)
                a.vbcast(SR, als)
                with a.repeat(tc):
                    a.vle(TR, asc, strides=(32, T * 4, 0))
                    a.vdiv(TR, TR, SR)
                    a.vse(TR, asc, strides=(32, T * 4, 0))
                a.scalar(3)
            with a.repeat(H * lc):                    # weighted latent sum
                a.vse(ZR, aol, strides=(32, 0))
            with a.repeat(T):
                for h in range(H):
                    a.vbcast(4 + h, asc + h * T * 4, strides=(4, 0))
                with a.repeat(lc):
                    a.vle(LR, ackv, strides=(32, L * 4, pck * 4))
                    for h in range(H):
                        a.vle(OR, aol + h * L * 4, strides=(32, 0, 0))
                        a.vmacc(OR, 4 + h, LR)
                        a.vse(OR, aol + h * L * 4, strides=(32, 0, 0))
                a.scalar(2)
            with a.repeat(H):                         # value up-projection
                with a.repeat(vc):
                    a.vmv(OR, ZR)
                    with a.repeat(L):
                        a.vbcast(AR2, aol, strides=(4, 0, L * 4, 0))
                        a.vle(BR2, awuv,
                              strides=(dv * 4, 32, L * dv * 4, 0))
                        a.vmacc(OR, AR2, BR2)
                    a.vse(OR, ao, strides=(32, dv * 4, po * 4))
                    a.scalar(3)
    prog = a.finalize(mm)

    qn, qr, ck, kr, wk, wv = (np.asarray(x, np.float64) for x in
                              (q_nope, q_rope, c_kv, k_rope, w_uk, w_uv))
    O = np.zeros((S, po), np.float32)
    for s in range(S):
        for h in range(H):
            sc = scale * (ck[s] @ (wk[h] @ qn[s, h]) + kr[s] @ qr[s, h])
            p = common.np_exp_approx(sc - sc.max(), MLA_EXP_SQUARINGS)
            O[s, dv * h: dv * (h + 1)] = (p / p.sum() @ ck[s]) @ wv[h]
    return common.Built(prog, {"O": O}, rtol=5e-3, atol=1e-4)


def build_mla(seqs=8, heads=16, nope=16, rope=8, latent=64, vdim=16,
              ctx=128, seed=0, unroll=False) -> common.Built:
    """:func:`mla_program` on seeded random queries, cache and weights."""
    g = common.rng(seed)

    def draw(*shape, std):
        return (g.standard_normal(shape) * std).astype(np.float32)

    w_std = 1 / np.sqrt(latent)
    return mla_program(
        draw(seqs, heads, nope, std=0.5), draw(seqs, heads, rope, std=0.5),
        draw(seqs, ctx, latent, std=0.5), draw(seqs, ctx, rope, std=0.5),
        draw(heads, latent, nope, std=w_std),
        draw(heads, latent, vdim, std=w_std),
        scale=1.0 / np.sqrt(nope + rope), unroll=unroll)


def mla_scalar_cost(seqs=8, heads=16, nope=16, rope=8, latent=64, vdim=16,
                    ctx=128, **_) -> ScalarCost:
    # the four contractions, plus a libm-style exp (~25 flop-equivalents)
    # per score, as the attention tiles count it.
    macs = shapes.mla_macs(seqs, heads, nope, rope, latent, vdim, ctx)
    scores = seqs * heads * ctx
    return ScalarCost(
        flop_ops=macs + 25 * scores, loads=macs + 2 * scores,
        stores=seqs * heads * (2 * latent + 2 * ctx + vdim),
        unique_lines=(heads * latent * (nope + vdim)
                      + seqs * (ctx * (latent + rope)
                                + heads * (nope + rope + vdim))) // 8,
        loop_iters=macs)


# ---------------------------------------------------------------------------
# tile policy
# ---------------------------------------------------------------------------

_BUILDERS = {"gemm": build_gemm, "scan": build_scan, "attn": build_attn,
             "dgemm": build_dgemm, "mla": build_mla}
_COSTS = {"gemm": gemm_scalar_cost, "scan": scan_scalar_cost,
          "attn": attn_scalar_cost, "dgemm": dgemm_scalar_cost,
          "mla": mla_scalar_cost}
_TILE_OF = {"expert": "dgemm"}          # routed/shared experts: dgemm tiles


def tile_for(op: LayerOp) -> tuple[str, dict, float]:
    """(kernel name, build kwargs, macro factor) for a LayerOp.

    The kernel name encodes the op's *real* shape — ops with equal
    signatures share a kernel (and, since the build kwargs are a function
    of the signature alone, an identical trace); ops with different
    signatures never merge.  The macro factor is real work / tile work at
    the TOKEN_BLOCK workload unit.
    """
    if op.kind == "gemm":
        k, n = op.shape
        kwargs = dict(tiles=TILES, mt=MT, k=min(k, K_CAP),
                      n=_round8(min(n, N_CAP)))
        name = f"net:gemm:{k}x{n}"
        tile_work = kwargs["tiles"] * kwargs["mt"] * kwargs["k"] * kwargs["n"]
    elif op.kind == "attn":
        heads, hd = op.shape
        kwargs = dict(ATTN_TILE)
        name = f"net:attn:{heads}h{hd}"
        tile_work = (2 * kwargs["seq"] * kwargs["seq"] * kwargs["d"]
                     * kwargs["heads"])
    elif op.kind == "scan":
        (width,) = op.shape
        kwargs = dict(steps=SCAN_STEPS, width=_round8(min(width,
                                                          SCAN_WIDTH_CAP)))
        name = f"net:scan:{width}"
        tile_work = kwargs["steps"] * kwargs["width"]
    elif op.kind in ("dgemm", "expert"):
        m, k, n = op.shape
        kwargs = dict(tiles=TILES, m=min(m, DM_CAP), k=min(k, K_CAP),
                      n=_round8(min(n, N_CAP)))
        name = f"net:{op.kind}:{m}x{k}x{n}"
        tile_work = kwargs["tiles"] * kwargs["m"] * kwargs["k"] * kwargs["n"]
    elif op.kind == "mla":
        b, h, dn, dr, r, dv, t = op.shape
        c = MLA_CAPS
        kwargs = dict(seqs=min(b, c["seqs"]), heads=min(h, c["heads"]),
                      nope=min(dn, c["nope"]),
                      rope=_round8(min(dr, c["rope"])),
                      latent=_round8(min(r, c["latent"])),
                      vdim=_round8(min(dv, c["vdim"])),
                      ctx=_round8(min(t, c["ctx"])))
        name = f"net:mla:b{b}:h{h}:n{dn}:r{dr}:l{r}:v{dv}:c{t}"
        tile_work = shapes.mla_macs(*(kwargs[key] for key in MLA_CAPS))
    else:
        raise ValueError(f"unknown LayerOp kind {op.kind!r}")
    return name, kwargs, op.work / tile_work


def builder_for(kind: str):
    return _BUILDERS[_TILE_OF.get(kind, kind)]


def cost_for(kind: str):
    return _COSTS[_TILE_OF.get(kind, kind)]

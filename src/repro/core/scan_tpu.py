"""The single-core dispersion scan as one Pallas TPU kernel.

On the TPU the XLA scan of :func:`repro.core.simulator._run_grid` pays for
every small gather, scatter and select of a step as its own serial
operation.  This kernel runs the same scan, with the same inputs and the
same counters bit for bit, without leaving the core:

* every lane of a program reads the same instruction row, so the packed
  trace block (``ROW_BLOCK`` rows of ``NUM_FIELDS`` words) sits in SMEM and each row's flags, registers, lines, cost
  and weights are scalars;
* the ``C x M`` lanes of a program sit on the vector lane axis (padded to
  128, more lane blocks past 128), and the grid is (programs, lane blocks,
  row blocks) with the row-block axis carrying the state;
* the cVRF metadata is six ``(32 slots, 128 lanes)`` planes in VMEM
  scratch (``PINNED`` is never set by the engine), so a lookup, the free
  slot and the victim are sublane reductions;
* the L1 is ``(sets, 128 lanes)`` planes of tag and ``age << 1 | dirty``
  per way, its sets rotated by the program's spill base: a line lives at
  row ``(line - spill0) mod sets``.  MEM and fill lines are scalars, so
  their set is one dynamic row; a spill line is ``spill0 + tag`` and
  differs per lane, but its row is the victim's tag, so a one-hot over
  the first ``min(32, sets)`` rows reaches it;
* a slot whose scalar valid flag is off does nothing in the XLA body, so
  the kernel skips it (``pl.when``).

The counters that depend only on the trace (instruction cost, register
and memory reads and writes) are sums over rows and are taken outside the
kernel; the kernel accumulates the eight per-lane ones.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import isa, policies
from repro.core.events import NUM_SLOTS

LANES = 128
ROW_BLOCK = 1024            # trace rows per grid step
NUM_FIELDS = 15             # int32 words per packed trace row

# Words of a packed trace row.
_FLAGS, _RG, _LK, _ML, _COST, _NXT, _WT = 0, 1, 4, 6, 8, 9, 12
# Bits of the flags word.
_RV, _VDW, _VDR, _VDNF, _MV, _MW = 0, 3, 4, 5, 6, 8

# Rows of a lane-parameter block.
_CAP, _POL, _ANF, _L1_HIT, _UOP_HIT, _MEM_LAT = range(6)

# The per-lane counters the kernel accumulates, one (8, 128) block per
# weight (wt, then wa and wb when tracked).  Row 0 is cycles less the
# instruction cost, which is a per-row scalar added outside.
_CYC, _STALL, _HITS, _MISSES, _SPILLS, _FILLS, _L1H, _L1M = range(8)

_SLOTS = isa.NUM_ARCH_VREGS
_NUM_META = policies.NEXT_USE + 1     # TAG .. NEXT_USE; PINNED stays 0
_INT_MAX = policies.INT_MAX
_SPILL_ROWS = 32                      # rows a spill line can reach
# Largest L1 the kernel keeps in VMEM (4 MiB: 4,096 sets of 2 ways);
# a larger geometry runs the XLA scan.
MAX_L1_BYTES = 4 * 2**20


def l1_bytes(l1_sets: int, l1_ways: int) -> int:
    """VMEM the kernel's L1 planes take for one lane block."""
    return 2 * l1_ways * l1_sets * LANES * 4


def _rmod(x, n: int):
    """``x mod n`` in [0, n) for int32 ``x`` and a static positive ``n``."""
    if n & (n - 1) == 0:
        return x & (n - 1)
    r = jax.lax.rem(x, n)
    return jnp.where(r < 0, r + n, r)


def _first(mask, iota, n: int):
    """Per lane, the first sublane index where ``mask`` holds, else ``n``."""
    return jnp.min(jnp.where(mask, iota, n), axis=0, keepdims=True)


def _l1_access(get, put, ways, line, is_write, now, gate, hit_cost,
               mem_latency):
    """``simulator._l1_access`` on one row per lane.

    ``get(k)`` reads plane ``k`` (tag ``2w``, ``age << 1 | dirty``
    ``2w + 1``) at the row, ``put(k, mask, new, old)`` writes ``new``
    where ``mask`` holds.  ``gate`` is the per-lane ``active`` (``None``:
    every lane).  Returns ``(cycles, hit)``, cycles gated."""
    tags = [get(2 * w) for w in range(ways)]
    ages = [get(2 * w + 1) for w in range(ways)]
    hit_way = jnp.full_like(tags[0], ways)
    for w in reversed(range(ways)):
        hit_way = jnp.where(tags[w] == line, w, hit_way)
    hit = hit_way < ways
    lru_way, best = jnp.zeros_like(hit_way), ages[0]
    for w in range(1, ways):
        older = ages[w] < best
        lru_way = jnp.where(older, w, lru_way)
        best = jnp.where(older, ages[w], best)
    way = jnp.where(hit, hit_way, lru_way)
    old_tag, old_age = tags[0], ages[0]
    for w in range(1, ways):
        old_tag = jnp.where(way == w, tags[w], old_tag)
        old_age = jnp.where(way == w, ages[w], old_age)
    old_dirty = old_age & 1
    writeback = ~hit & (old_tag >= 0) & (old_dirty == 1)
    cycles = jnp.where(hit, hit_cost,
                       hit_cost + mem_latency
                       + jnp.where(writeback, mem_latency, 0))
    new_age = (now << 1) | jnp.where(hit, old_dirty | is_write, is_write)
    for w in range(ways):
        sel = way == w
        if gate is not None:
            sel = sel & gate
        put(2 * w, sel, line, tags[w])
        put(2 * w + 1, sel, new_age, ages[w])
    if gate is not None:
        cycles = jnp.where(gate, cycles, 0)
    return cycles, hit


def _kernel(spill0_ref, trace_ref, lane_ref, out_ref, meta_ref, l1_ref,
            seq_ref, inc_ref, *, l1_sets, l1_ways, slots_used, track_ab):
    p, rb = pl.program_id(0), pl.program_id(2)
    spill_rows = min(_SPILL_ROWS, l1_sets)

    @pl.when(rb == 0)
    def _init():
        meta_ref[...] = jnp.zeros(meta_ref.shape, jnp.int32)
        meta_ref[policies.TAG] = jnp.full((_SLOTS, LANES), -1, jnp.int32)
        for w in range(l1_ways):
            l1_ref[2 * w] = jnp.full((l1_sets, LANES), -1, jnp.int32)
            l1_ref[2 * w + 1] = jnp.zeros((l1_sets, LANES), jnp.int32)
        seq_ref[...] = jnp.zeros(seq_ref.shape, jnp.int32)
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.int32)

    spill0 = spill0_ref[p]

    def lane(k):
        return lane_ref[k:k + 1, :]

    def bump(k, x):
        inc_ref[k:k + 1, :] += x.astype(jnp.int32)

    def row_access(row, line, is_write, now, gate, hit_cost):
        def get(k):
            return l1_ref[k, pl.ds(row, 1), :]

        def put(k, mask, new, old):
            l1_ref[k, pl.ds(row, 1), :] = jnp.where(mask, new, old)

        return _l1_access(get, put, l1_ways, line, is_write, now, gate,
                          hit_cost, lane(_MEM_LAT))

    def spill_access(row, line, now, gate):
        iota = jax.lax.broadcasted_iota(jnp.int32, (spill_rows, LANES), 0)
        onehot = iota == row
        win = [l1_ref[k, :spill_rows, :] for k in range(2 * l1_ways)]

        def get(k):
            return jnp.sum(jnp.where(onehot, win[k], 0), axis=0,
                           keepdims=True)

        def put(k, mask, new, old):
            l1_ref[k, :spill_rows, :] = jnp.where(onehot & mask, new, win[k])

        return _l1_access(get, put, l1_ways, line, 1, now, gate,
                          lane(_UOP_HIT), lane(_MEM_LAT))

    def reg_slot(s, base, now):
        rg = trace_ref[base + _RG + s]
        nxt = trace_ref[base + _NXT + s]
        flags = trace_ref[base + _FLAGS]
        cap = lane(_CAP)
        full = cap >= isa.NUM_ARCH_VREGS
        iota = jax.lax.broadcasted_iota(jnp.int32, (_SLOTS, LANES), 0)
        valid = iota < cap
        tags = meta_ref[policies.TAG]
        hit_slot = _first((tags == rg) & valid, iota, _SLOTS)
        raw_hit = hit_slot < _SLOTS
        free_slot = _first((tags < 0) & valid, iota, _SLOTS)
        has_free = free_slot < _SLOTS
        # Victim: policies.select_victim over the in-capacity, occupied,
        # unlocked slots (serial tag check: vs2 and vd keep vs1, vd keeps
        # vs2).
        occ = (tags >= 0) & valid
        if s >= 1:
            occ = occ & (tags != trace_ref[base + _LK])
        if s == 2:
            occ = occ & (tags != trace_ref[base + _LK + 1])
        ins = meta_ref[policies.INS_SEQ]
        last = meta_ref[policies.LAST_USE]
        freq = meta_ref[policies.FREQ]
        next_use = meta_ref[policies.NEXT_USE]
        pol = lane(_POL)
        lfu = (jnp.minimum(freq, 511) * (2**21)) + (ins & (2**21 - 1))
        metric = jnp.where(
            pol == policies.LRU, last,
            jnp.where(pol == policies.LFU, lfu,
                      jnp.where(pol == policies.OPT, -next_use, ins)))
        metric = jnp.where(occ, metric, _INT_MAX)
        low = jnp.min(metric, axis=0, keepdims=True)
        victim = _first(metric == low, iota, _SLOTS)
        is_victim = iota == victim
        dirty = meta_ref[policies.DIRTY]
        vtag = jnp.sum(jnp.where(is_victim, tags, 0), axis=0, keepdims=True)
        vdirty = jnp.sum(jnp.where(is_victim, dirty, 0), axis=0,
                         keepdims=True)

        miss = ~raw_hit & ~full
        do_spill = miss & ~has_free & (vdirty == 1)
        if s == 2:
            # fetch = vd_reads | ~(vd_no_fetch & alloc_no_fetch)
            skip = ((flags >> _VDNF) & 1) & (1 - ((flags >> _VDR) & 1))
            do_fill = miss & ((lane(_ANF) * skip) == 0)
            is_write = (flags >> _VDW) & 1
        else:
            do_fill = miss
            is_write = 0
        # Spill the evictee to its reserved line, then fill the register.
        spill_tag = jnp.maximum(vtag, 0)
        c_sp, h_sp = spill_access(_rmod(spill_tag, l1_sets),
                                  spill0 + spill_tag, now, do_spill)
        fill_tag = jnp.maximum(rg, 0)
        c_fl, h_fl = row_access(_rmod(fill_tag, l1_sets), spill0 + fill_tag,
                                0, now, do_fill, lane(_UOP_HIT))

        # policies.apply_access at the hit-or-install slot.
        tgt = jnp.where(raw_hit, hit_slot,
                        jnp.where(has_free, free_slot, victim))
        upd = (iota == tgt) & ~full
        install = upd & ~raw_hit
        seq = seq_ref[...]
        meta_ref[policies.TAG] = jnp.where(install, rg, tags)
        meta_ref[policies.DIRTY] = jnp.where(
            upd, jnp.where(raw_hit, dirty | is_write, is_write), dirty)
        meta_ref[policies.INS_SEQ] = jnp.where(install, seq, ins)
        meta_ref[policies.LAST_USE] = jnp.where(upd, now, last)
        meta_ref[policies.FREQ] = jnp.where(
            upd, jnp.where(raw_hit, freq + 1, 1), freq)
        meta_ref[policies.NEXT_USE] = jnp.where(upd, nxt, next_use)
        seq_ref[...] = seq + miss.astype(jnp.int32)

        stall = c_sp + c_fl
        bump(_CYC, stall)
        bump(_STALL, stall)
        bump(_HITS, raw_hit | full)
        bump(_MISSES, miss)
        bump(_SPILLS, do_spill)
        bump(_FILLS, do_fill)
        bump(_L1H, (do_spill & h_sp).astype(jnp.int32)
             + (do_fill & h_fl).astype(jnp.int32))
        bump(_L1M, (do_spill & ~h_sp).astype(jnp.int32)
             + (do_fill & ~h_fl).astype(jnp.int32))

    def mem_slot(m, base, now):
        flags = trace_ref[base + _FLAGS]
        line = trace_ref[base + _ML + m]
        is_write = (flags >> (_MW + m)) & 1
        cycles, hit = row_access(_rmod(line - spill0, l1_sets), line,
                                 is_write, now, None, lane(_L1_HIT))
        bump(_CYC, cycles)
        bump(_L1H, hit)
        bump(_L1M, ~hit)

    def step(i, carry):
        base = i * NUM_FIELDS
        flags = trace_ref[base + _FLAGS]
        now0 = (rb * ROW_BLOCK + i) * NUM_SLOTS

        @pl.when(flags != 0)
        def _row():
            inc_ref[...] = jnp.zeros(inc_ref.shape, jnp.int32)
            for s in range(3):
                if slots_used[s]:
                    pl.when(((flags >> (_RV + s)) & 1) == 1)(
                        functools.partial(reg_slot, s, base, now0 + s))
            for m in range(2):
                if slots_used[3 + m]:
                    pl.when(((flags >> (_MV + m)) & 1) == 1)(
                        functools.partial(mem_slot, m, base, now0 + 3 + m))
            inc = inc_ref[...]
            weights = (_WT, _WT + 1, _WT + 2) if track_ab else (_WT,)
            for a, f in enumerate(weights):
                out_ref[8 * a:8 * a + 8, :] += inc * trace_ref[base + f]

        return carry

    jax.lax.fori_loop(0, ROW_BLOCK, step, 0)


def _pack_rows(arrays):
    """The 15 trace arrays of ``_run_grid`` as (P, T, NUM_FIELDS) int32."""
    (rv, rg, vdw, vdr, vdnf, lk1, lk2, mv, ml, mw, cost, nxt,
     wt, wa, wb) = arrays
    i32 = lambda x: x.astype(jnp.int32)
    flags = (i32(rv[..., 0]) << _RV | i32(rv[..., 1]) << (_RV + 1)
             | i32(rv[..., 2]) << (_RV + 2) | i32(vdw) << _VDW
             | i32(vdr) << _VDR | i32(vdnf) << _VDNF
             | i32(mv[..., 0]) << _MV | i32(mv[..., 1]) << (_MV + 1)
             | i32(mw[..., 0]) << _MW | i32(mw[..., 1]) << (_MW + 1))
    cols = [flags, rg[..., 0], rg[..., 1], rg[..., 2], lk1, lk2,
            ml[..., 0], ml[..., 1], cost, nxt[..., 0], nxt[..., 1],
            nxt[..., 2], wt, wa, wb]
    return jnp.stack([i32(c) for c in cols], axis=-1)


def _trace_counters(arrays, slots_used, weight):
    """(P, 5) sums over rows of ``weight`` x (cost, reg reads, reg writes,
    mem reads, mem writes): the counters no lane state decides."""
    (rv, _rg, vdw, vdr, _vdnf, _lk1, _lk2, mv, _ml, mw, cost, _nxt,
     *_w) = arrays
    i32 = lambda x: x.astype(jnp.int32)
    zero = jnp.zeros_like(cost)
    rr = sum((i32(rv[..., s]) for s in range(2) if slots_used[s]), zero)
    rw = zero
    if slots_used[2]:
        rr = rr + i32(rv[..., 2] & vdr)
        rw = i32(rv[..., 2] & vdw)
    mr = sum((i32(mv[..., m] & ~mw[..., m]) for m in range(2)
              if slots_used[3 + m]), zero)
    mwr = sum((i32(mv[..., m] & mw[..., m]) for m in range(2)
               if slots_used[3 + m]), zero)
    per_row = jnp.stack([cost, rr, rw, mr, mwr], axis=-1)
    return jnp.sum(per_row * weight[..., None], axis=1)


def scan_grid(l1_sets, l1_ways, slots_used, track_ab, arrays, spill0s, cfg,
              mach, *, interpret=False):
    """``_run_grid``'s scan as one kernel: same inputs, same ``(P, C, M,
    12)`` ``ctr``, ``ctrA`` and ``ctrB``.  Traceable; the caller jits."""
    capacity, policy, anf = cfg
    l1_hit, uop_hit, mem_lat = mach
    n_cfg, n_mach = capacity.shape[0], mem_lat.shape[0]
    n_lanes = n_cfg * n_mach
    lane_blocks = pl.cdiv(n_lanes, LANES)
    pad = lane_blocks * LANES - n_lanes

    def lanes(x, per_cfg, fill):
        x = x.astype(jnp.int32)
        x = jnp.repeat(x, n_mach) if per_cfg else jnp.tile(x, n_cfg)
        return jnp.pad(x, (0, pad), constant_values=fill)

    # Padding lanes get the full VRF: they never touch the cVRF state.
    lane_params = jnp.stack(
        [lanes(capacity, True, isa.NUM_ARCH_VREGS), lanes(policy, True, 0),
         lanes(anf, True, 0), lanes(l1_hit, False, 0),
         lanes(uop_hit, False, 0), lanes(mem_lat, False, 0)]
        + [jnp.zeros(lane_blocks * LANES, jnp.int32)] * 2)

    rows = _pack_rows(arrays)
    n_prog, n_rows = rows.shape[:2]
    row_blocks = pl.cdiv(n_rows, ROW_BLOCK)
    # Rows past the trace are all-inactive: they change nothing.
    rows = jnp.pad(rows, ((0, 0), (0, row_blocks * ROW_BLOCK - n_rows),
                          (0, 0)))
    rows = rows.reshape(-1)
    n_acc = 3 if track_ab else 1

    kernel = functools.partial(
        _kernel, l1_sets=l1_sets, l1_ways=l1_ways,
        slots_used=tuple(slots_used), track_ab=track_ab)
    lane_out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (n_prog, 8 * n_acc, lane_blocks * LANES), jnp.int32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_prog, lane_blocks, row_blocks),
            in_specs=[
                pl.BlockSpec((ROW_BLOCK * NUM_FIELDS,),
                             lambda p, lb, rb, sp: (p * row_blocks + rb,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((8, LANES), lambda p, lb, rb, sp: (0, lb)),
            ],
            out_specs=pl.BlockSpec((None, 8 * n_acc, LANES),
                                   lambda p, lb, rb, sp: (p, 0, lb)),
            scratch_shapes=[
                pltpu.VMEM((_NUM_META, _SLOTS, LANES), jnp.int32),
                pltpu.VMEM((2 * l1_ways, l1_sets, LANES), jnp.int32),
                pltpu.VMEM((1, LANES), jnp.int32),
                pltpu.VMEM((8, LANES), jnp.int32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="dispersion_scan",
        interpret=interpret,
    )(spill0s.astype(jnp.int32), rows, lane_params)

    lane_out = lane_out[:, :, :n_lanes].reshape(
        n_prog, n_acc, 8, n_cfg, n_mach)
    lane_out = jnp.moveaxis(lane_out, 2, -1)          # (P, A, C, M, 8)
    weights = arrays[-3:] if track_ab else arrays[-3:-2]
    outs = []
    for a, w in enumerate(weights):
        tr = _trace_counters(arrays, slots_used, w)[:, None, None, :]
        tr = jnp.broadcast_to(tr, (n_prog, n_cfg, n_mach, 5))
        ln = lane_out[:, a]
        # COUNTER_NAMES order: cycles, stall, vrf hits, vrf misses,
        # spills, fills, l1 hits, l1 misses, reg reads, reg writes, mem
        # reads, mem writes.
        outs.append(jnp.concatenate(
            [ln[..., :1] + tr[..., :1], ln[..., 1:], tr[..., 1:]], axis=-1))
    if not track_ab:
        zero = jnp.zeros_like(outs[0])
        outs += [zero, zero]
    return tuple(outs)

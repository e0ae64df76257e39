"""Cycle-level cVRF / Register Dispersion simulator (fused JAX ``lax.scan``).

Models the paper's microarchitecture (§3, Table 1):

  * compact VRF of ``capacity`` physical 256-bit registers, fully associative,
    tag array checked serially per operand, FIFO (or alternative) replacement;
  * ``v0`` pinned outside the cVRF (its accesses never reach the tag array);
  * every architectural register has a reserved memory address; spills/fills
    are 32-byte transfers through the modelled L1D (16 KB, 2-way, 32 B lines,
    1-cycle hit) backed by a 5-cycle main memory;
  * vector loads/stores share the same L1 port (integrated VPU, Fig 1);
  * a full-size VRF baseline (``capacity >= 32``) in which every operand
    access hits and no fills ever occur (real hardware has no compulsory
    misses — registers simply exist).

Engine architecture (fused instruction-level sweep engine), in one line
each — the full design narrative lives in ``docs/architecture.md``:

  * **One scan step retires one instruction** (``core.events`` packs the
    <=3 REG + <=2 MEM lanes into fixed-width matrices; counters are
    bit-identical to the old per-event engine).
  * **Batched (P, C, M) sweep grid**: :func:`simulate_grid` vmaps programs
    x configs x traced machine-latency points (:class:`MachineSweep`) into
    one dispatch, compiled once per power-of-two program-shape bucket.
  * **On the TPU the scan is one Pallas kernel** (``core.scan_tpu``), bit
    for bit the XLA scan, which the CPU and the cluster engine run.
  * **Exact periodic folding** (``core.folding``): warm-up + two measured
    periods per hot loop, algebraic extrapolation, with the A == B
    ``fold_exact`` certificate evaluated per (C, M) grid point — see
    ``docs/folding.md`` for the certificate semantics and the
    state-snapshot super-period detector.

The whole sweep of Fig 4 (capacities 3..16 x policies x every kernel) is
then one ``vmap(vmap(vmap(scan)))`` dispatch.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import events as ev_mod
from repro.core import folding, isa, policies, scan_tpu
from repro.core.events import NO_NEXT_USE, EventStream
from repro.core.trace import Program

# ---------------------------------------------------------------------------
# Machine parameters (Table 1): static L1 geometry + traced latency axes.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MachineParams:
    """One machine point.  ``l1_sets``/``l1_ways`` are static (they size the
    L1 state arrays); the three latency fields are *traced* by the engine, so
    machines sharing a geometry share one compiled executable."""

    l1_sets: int = 256            # 16 KB / 32 B lines / 2 ways
    l1_ways: int = 2
    l1_hit_cycles: int = 0        # data-path hits overlap the vector pipe
    uop_hit_cycles: int = 1       # spill/fill micro-ops serialize in ID
    mem_latency: int = 5          # main memory @200 MHz (Table 1: 1-5 cycles)

    def tree_flatten(self):  # convenience for static hashing in jit
        return dataclasses.astuple(self)


DEFAULT_MACHINE = MachineParams()


@dataclasses.dataclass
class MachineSweep:
    """Machine sweep axis: M traced latency points over one static L1
    geometry.  The latency arrays are vmapped through the fused step, so the
    whole machine grid shares one executable per program-shape bucket."""

    l1_hit_cycles: np.ndarray     # (M,) int32 data-path L1 hit cycles
    uop_hit_cycles: np.ndarray    # (M,) int32 spill/fill uop hit cycles
    mem_latency: np.ndarray       # (M,) int32 main-memory latency
    l1_sets: int = 256            # static: L1 state shape
    l1_ways: int = 2              # static: L1 state shape

    @staticmethod
    def make(mem_latency, l1_hit_cycles=0, uop_hit_cycles=1,
             l1_sets=256, l1_ways=2) -> "MachineSweep":
        mem = np.atleast_1d(np.asarray(mem_latency, np.int32))
        l1h = np.broadcast_to(np.asarray(l1_hit_cycles, np.int32),
                              mem.shape).copy()
        uop = np.broadcast_to(np.asarray(uop_hit_cycles, np.int32),
                              mem.shape).copy()
        return MachineSweep(l1h, uop, mem, l1_sets, l1_ways)

    @staticmethod
    def product(mem_latencies, l1_hit_cycles=(0,), uop_hit_cycles=(1,),
                l1_sets=256, l1_ways=2) -> "MachineSweep":
        """Cartesian latency grid as one machine axis (parameter order
        mirrors :meth:`make`)."""
        mem, l1h, uop = [], [], []
        for m in mem_latencies:
            for h in l1_hit_cycles:
                for u in uop_hit_cycles:
                    mem.append(m), l1h.append(h), uop.append(u)
        return MachineSweep(np.asarray(l1h, np.int32),
                            np.asarray(uop, np.int32),
                            np.asarray(mem, np.int32), l1_sets, l1_ways)

    @staticmethod
    def from_params(points) -> "MachineSweep":
        """Stack MachineParams points (which must share an L1 geometry)."""
        points = list(points)
        geo = {(p.l1_sets, p.l1_ways) for p in points}
        if len(geo) != 1:
            raise ValueError(
                f"machine points mix L1 geometries {sorted(geo)}; "
                "l1_sets/l1_ways are static (they size the L1 arrays) — "
                "sweep them in an outer loop")
        return MachineSweep(
            np.asarray([p.l1_hit_cycles for p in points], np.int32),
            np.asarray([p.uop_hit_cycles for p in points], np.int32),
            np.asarray([p.mem_latency for p in points], np.int32),
            points[0].l1_sets, points[0].l1_ways)

    def point(self, m: int) -> MachineParams:
        """The m-th machine point as a scalar MachineParams."""
        return MachineParams(self.l1_sets, self.l1_ways,
                             int(self.l1_hit_cycles[m]),
                             int(self.uop_hit_cycles[m]),
                             int(self.mem_latency[m]))

    def __len__(self):
        return len(self.mem_latency)


COUNTER_NAMES = (
    "cycles", "stall_cycles", "vrf_hits", "vrf_misses", "spills", "fills",
    "l1_hits", "l1_misses", "reg_reads", "reg_writes", "mem_reads",
    "mem_writes",
)


@dataclasses.dataclass
class SweepConfig:
    """Per-configuration sweep axes (arrays of equal length C)."""

    capacity: np.ndarray        # physical registers in the cVRF
    policy: np.ndarray          # policies.FIFO / LRU / LFU / OPT
    alloc_no_fetch: np.ndarray  # beyond-paper: skip fetch on full overwrite

    @staticmethod
    def make(capacities, policy=policies.FIFO, alloc_no_fetch=False):
        caps = np.asarray(capacities, np.int32)
        pol = np.broadcast_to(np.asarray(policy, np.int32), caps.shape).copy()
        anf = np.broadcast_to(np.asarray(alloc_no_fetch, bool),
                              caps.shape).copy()
        return SweepConfig(caps, pol, anf)

    @staticmethod
    def product(capacities, policies_, alloc_no_fetch=(False,)):
        """Cartesian grid capacities x policies x anf as one config axis."""
        caps, pols, anfs = [], [], []
        for c in capacities:
            for p in policies_:
                for a in alloc_no_fetch:
                    caps.append(c), pols.append(p), anfs.append(a)
        return SweepConfig(np.asarray(caps, np.int32),
                           np.asarray(pols, np.int32),
                           np.asarray(anfs, bool))

    def __len__(self):
        return len(self.capacity)


# ---------------------------------------------------------------------------
# L1 data cache model.
# ---------------------------------------------------------------------------


def _l1_init(l1_sets: int, l1_ways: int):
    # Packed (sets, ways, 2) int32: [:, :, 0] = line tag (-1 free),
    # [:, :, 1] = age << 1 | dirty.  Age dominates the packed word, so LRU
    # argmin over it matches argmin over the raw age; packing makes the
    # update a single 2-wide scatter per access.
    l1 = jnp.zeros((l1_sets, l1_ways, 2), jnp.int32)
    return l1.at[:, :, 0].set(-1)


def _l1_access(l1, line, is_write, now, active, l1_sets: int,
               hit_cost, mem_latency):
    """One cacheline access, LRU within the set, write-allocate + write-back.

    Returns ``(l1', cycles, hit)``; the state update is a masked scatter at
    the touched (set, way) entry, a no-op when ``active`` is False, and
    ``cycles`` is already gated by ``active``.  ``hit_cost`` (the L1 hit
    cycles of this access class: data path vs spill/fill uop) and
    ``mem_latency`` are traced int32 scalars — machine sweep axes — while
    ``l1_sets`` stays static because it indexes the state array.  Hit/miss
    state transitions do not depend on the latencies, only ``cycles`` does.
    """
    line = line.astype(jnp.int32)
    set_idx = line % l1_sets
    row = l1[set_idx]                              # (ways, 2)
    row_tags = row[:, 0]
    eq = row_tags == line
    hit = eq.any()
    way = jnp.where(hit, jnp.argmax(eq), jnp.argmin(row[:, 1]))
    old = row[way]
    old_dirty = old[1] & 1
    writeback = ~hit & (old[0] >= 0) & (old_dirty == 1)
    cycles = jnp.where(
        hit, hit_cost,
        hit_cost + mem_latency
        + jnp.where(writeback, mem_latency, 0)).astype(jnp.int32)
    w = jnp.int32(is_write)
    new = jnp.stack([line, (now << 1) | jnp.where(hit, old_dirty | w, w)])
    l1_new = l1.at[set_idx, way].set(jnp.where(active, new, old))
    return l1_new, jnp.where(active, cycles, 0), hit


# ---------------------------------------------------------------------------
# Fused per-instruction scan body.
# ---------------------------------------------------------------------------


# L1 access sites one instruction can touch, in engine order: (spill, fill)
# per REG slot 0..2, then the two MEM lanes.  The per-site missed-line
# vector is the per-core L1-miss stream the cluster engine's shared-L2 /
# memory-channel arbiter consumes (repro.cluster).
NUM_MISS_SITES = 8


def _make_body(l1_sets, slots_used, cfg, mach):
    """The per-instruction engine body, shared by the single-core step and
    the cluster engine's vmapped per-core step (:mod:`repro.cluster`).

    Returns ``body(state, xs, spill0, mem_base, now0) -> (state', inc,
    miss_lines)`` where ``state = (cache, l1, seq)``, ``inc`` is the (12,)
    counter increment vector (order = COUNTER_NAMES) and ``miss_lines`` is
    the (NUM_MISS_SITES,) int32 vector of cachelines this instruction
    missed in the L1 (-1 at sites that hit, were inactive, or are unused).
    ``mem_base`` offsets the instruction's own data lines (per-core address
    colouring in a cluster; 0 on the single-core path, where the per-core
    offset is instead folded into ``spill0`` for the spill region).
    """
    capacity, policy, anf = cfg
    l1_hit, uop_hit, mem_lat = mach
    full_vrf = capacity >= isa.NUM_ARCH_VREGS
    valid_mask = jnp.arange(isa.NUM_ARCH_VREGS) < capacity
    F = jnp.bool_(False)
    no_lock = jnp.int8(-1)
    neg1 = jnp.int32(-1)

    def body(state, xs, spill0, mem_base, now0):
        cache, l1, seq = state
        (rv, rg, vdw, vdr, vdnf, lk1, lk2, mv, ml, mw, cost, nxt,
         _wt, _wa, _wb) = xs
        i32 = lambda b: b.astype(jnp.int32)
        z = jnp.int32(0)
        stall = memc = hits = misses = spills = fills = z
        l1h = l1m = rr = rw = mr = mw_ = z
        miss_lines = [neg1] * NUM_MISS_SITES

        # REG lanes in the hardware's serial tag-check order.
        write_of = (F, F, vdw)
        read_of = (jnp.bool_(True), jnp.bool_(True), vdr)
        nofetch_of = (F, F, vdnf)
        locks = ((no_lock, no_lock), (lk1, no_lock), (lk1, lk2))
        for s in range(3):
            if not slots_used[s]:
                continue
            active = rv[s]
            now = now0 + s
            raw_hit, slot = policies.lookup(cache, rg[s], valid_mask)
            raw_hit = raw_hit & active
            has_free, fslot = policies.free_slot(cache, valid_mask)
            la, lb = locks[s]
            victim = policies.select_victim(cache, policy, valid_mask,
                                            la, lb)
            tslot = jnp.where(has_free, fslot, victim)
            vrow = cache.meta[victim]
            miss = active & ~raw_hit & ~full_vrf
            do_spill = miss & ~has_free & (vrow[policies.DIRTY] == 1)
            wr, rd = write_of[s], read_of[s]
            fetch = rd | ~(nofetch_of[s] & anf)
            do_fill = miss & fetch
            # Spill the evictee to its reserved line, then fill the missing
            # register — both 1-cycle uops through the L1.
            spill_line = spill0 + jnp.maximum(vrow[policies.TAG], 0)
            fill_line = spill0 + jnp.maximum(rg[s].astype(jnp.int32), 0)
            l1, c_sp, h_sp = _l1_access(
                l1, spill_line, True, now,
                do_spill, l1_sets, uop_hit, mem_lat)
            l1, c_fl, h_fl = _l1_access(
                l1, fill_line, False,
                now, do_fill, l1_sets, uop_hit, mem_lat)
            cache = policies.apply_access(
                cache, active=active & ~full_vrf, raw_hit=raw_hit,
                hit_slot=slot, install_slot=tslot, tag=rg[s], now=now,
                seq=seq, next_use=nxt[s], is_write=wr)
            seq = seq + i32(miss)
            stall += c_sp + c_fl
            hits += i32(raw_hit | (active & full_vrf))
            misses += i32(miss)
            spills += i32(do_spill)
            fills += i32(do_fill)
            l1h += i32(do_spill & h_sp) + i32(do_fill & h_fl)
            l1m += i32(do_spill & ~h_sp) + i32(do_fill & ~h_fl)
            rr += i32(active & rd)
            rw += i32(active & wr)
            miss_lines[2 * s] = jnp.where(do_spill & ~h_sp,
                                          spill_line.astype(jnp.int32), neg1)
            miss_lines[2 * s + 1] = jnp.where(do_fill & ~h_fl,
                                              fill_line.astype(jnp.int32),
                                              neg1)

        # MEM lanes: the instruction's own data accesses.
        for m in range(2):
            if not slots_used[3 + m]:
                continue
            active = mv[m]
            line = ml[m] + mem_base
            l1, c_m, h_m = _l1_access(l1, line, mw[m], now0 + 3 + m,
                                      active, l1_sets, l1_hit, mem_lat)
            memc += c_m
            l1h += i32(active & h_m)
            l1m += i32(active & ~h_m)
            mr += i32(active & ~mw[m])
            mw_ += i32(active & mw[m])
            miss_lines[6 + m] = jnp.where(active & ~h_m,
                                          line.astype(jnp.int32), neg1)

        # One (12,)-vector FMA per counter set (order = COUNTER_NAMES).
        inc = jnp.stack([
            cost + stall + memc, stall, hits, misses, spills, fills,
            l1h, l1m, rr, rw, mr, mw_,
        ])
        return (cache, l1, seq), inc, jnp.stack(miss_lines)

    return body


def _make_step(l1_sets, slots_used, track_ab, spill0, cfg, mach):
    body = _make_body(l1_sets, slots_used, cfg, mach)
    spill0 = spill0.astype(jnp.int32)
    zero_base = jnp.int32(0)

    def step(carry, xs):
        cache, l1, seq, now0, ctr, ctrA, ctrB = carry
        wt, wa, wb = xs[-3:]
        (cache, l1, seq), inc, _ = body(
            (cache, l1, seq), xs, spill0, zero_base, now0)
        ctr = ctr + inc * wt
        if track_ab:
            ctrA = ctrA + inc * wa
            ctrB = ctrB + inc * wb
        return (cache, l1, seq, now0 + ev_mod.NUM_SLOTS, ctr, ctrA, ctrB), None

    return step


# Number of times the grid engine has been traced (== XLA compiles): the
# body below only executes under jax tracing, so the counter increments
# exactly once per new (static signature, shape bucket) cache entry.
_COMPILES = 0


def compile_count() -> int:
    """Grid-engine compiles so far (one per program-shape bucket)."""
    return _COMPILES


# Grid-engine dispatches (one `_run_grid` call each; a dispatch reuses a
# compiled executable unless its static/shape signature is new).
_DISPATCHES = 0


def dispatch_count() -> int:
    """Grid-engine XLA dispatches so far (compiled-or-cached alike)."""
    return _DISPATCHES


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3),
                   donate_argnums=(4, 5))
def _run_grid(l1_sets, l1_ways, slots_used, track_ab, arrays, spill0s,
              cfg, mach):
    """(P, T) trace grid x (C,) configs x (M,) machines -> (P, C, M, 12).

    The jit cache keyed on the (static) L1-geometry/lane signature and the
    (padded) array shapes is the compiled-executable level of the benchmark
    cache: any suite whose grid pads to the same bucket reuses the build —
    including every machine-latency point, since ``mach`` is traced.  The
    trace grid and spill bases are donated (they are rebuilt from the host
    copies each call), trimming peak memory on accelerator backends.
    """
    global _COMPILES
    _COMPILES += 1

    def one_program(arr, sp0):
        def one_cfg(c):
            def one_machine(m):
                step = _make_step(l1_sets, slots_used, track_ab, sp0, c, m)
                z = jnp.zeros(len(COUNTER_NAMES), jnp.int32)
                carry = (policies.CacheState.init(isa.NUM_ARCH_VREGS),
                         _l1_init(l1_sets, l1_ways), jnp.int32(0),
                         jnp.int32(0), z, z, z)
                (_, _, _, _, ctr, ctrA, ctrB), _ = jax.lax.scan(
                    step, carry, arr)
                return ctr, ctrA, ctrB
            return jax.vmap(one_machine)(mach)
        return jax.vmap(one_cfg)(cfg)

    return jax.vmap(one_program)(arrays, spill0s)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3),
                   static_argnames=("interpret",), donate_argnums=(4, 5))
def _run_grid_pallas(l1_sets, l1_ways, slots_used, track_ab, arrays,
                     spill0s, cfg, mach, interpret=False):
    """:func:`_run_grid` as one Pallas TPU kernel (``core.scan_tpu``):
    the same inputs, the same counters bit for bit, the same compile
    accounting (one per static signature and shape bucket)."""
    global _COMPILES
    _COMPILES += 1
    return scan_tpu.scan_grid(l1_sets, l1_ways, slots_used, track_ab,
                              arrays, spill0s, cfg, mach,
                              interpret=interpret)


def scan_impl(machine: MachineSweep, cfg) -> str:
    """The scan a dispatch runs: ``"pallas"`` where the engine's arrays
    live on a TPU and the kernel's L1 planes fit its VMEM, else the XLA
    body (``"xla"``), which the CPU and the cluster engine always run."""
    platform = next(iter(cfg[0].devices())).platform
    fits = (scan_tpu.l1_bytes(machine.l1_sets, machine.l1_ways)
            <= scan_tpu.MAX_L1_BYTES)
    return "pallas" if platform == "tpu" and fits else "xla"


# ---------------------------------------------------------------------------
# Trace preparation: expansion + optional periodic folding / truncation.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PreparedTrace:
    """An expanded (and possibly folded / truncated) trace, ready to grid."""

    ev: EventStream
    weight: np.ndarray        # (T',) int32 extrapolation weights (ones if
    wa: np.ndarray            # unfolded); wa/wb pick out the two measured
    wb: np.ndarray            # periods whose equality certifies exactness
    num_folds: int
    event_scale: float        # >1 when prefix-truncated via max_events
    spill_line0: int
    certifiable: bool = True  # False: post-fold rows reuse dropped lines,
    #   so A == B cannot certify exactness (folding.FoldPlan.certifiable)

    @property
    def num_rows(self) -> int:
        return self.ev.num_instructions


def _slice_prep(prep: PreparedTrace, t: int) -> PreparedTrace:
    ev = prep.ev
    sliced = EventStream(
        reg_valid=ev.reg_valid[:t], reg=ev.reg[:t],
        vd_writes=ev.vd_writes[:t], vd_reads=ev.vd_reads[:t],
        vd_no_fetch=ev.vd_no_fetch[:t], lock_vs1=ev.lock_vs1[:t],
        lock_vs2=ev.lock_vs2[:t], mem_valid=ev.mem_valid[:t],
        mem_line=ev.mem_line[:t], mem_write=ev.mem_write[:t],
        cost=ev.cost[:t], next_use=ev.next_use[:t],
        events_per_row=ev.events_per_row[:t],
        spill_line0=ev.spill_line0, num_instructions=t, repeats=[],
    )
    return dataclasses.replace(prep, ev=sliced, weight=prep.weight[:t],
                               wa=prep.wa[:t], wb=prep.wb[:t])


def prepare(program_or_events, fold: bool = False,
            max_events: int | None = None,
            warm_lines: int | None = None,
            machine=None) -> PreparedTrace:
    """Expand a trace once; optionally fold its periodic loops (exact for
    steady-state traces) or truncate it to ``max_events`` flat events at an
    instruction boundary (approximate, the legacy prefix mode).

    The two modes are mutually exclusive: truncating a folded trace would
    drop the extrapolation-weighted measured periods and corrupt both the
    counters and the exactness certificate, so ``max_events`` forces
    ``fold`` off.

    ``machine`` (a :class:`MachineParams` or :class:`MachineSweep`) sizes
    the fold warm-up to the static L1 geometry the trace will be swept on
    (2x its line count, see ``folding.warm_lines_for``); traced latency
    axes never affect preparation.  An explicit ``warm_lines`` wins.
    """
    if isinstance(program_or_events, PreparedTrace):
        return program_or_events
    if warm_lines is None:
        geo = machine if machine is not None else DEFAULT_MACHINE
        warm_lines = folding.warm_lines_for(geo.l1_sets, geo.l1_ways)
    if max_events is not None:
        fold = False
    plan = None
    if isinstance(program_or_events, EventStream):
        if fold:
            # Fold planning needs the Program (warm-up sizing reads the raw
            # address stream); refusing beats silently scanning in full.
            raise ValueError(
                "fold=True requires a Program (or a PreparedTrace from "
                "prepare(program, fold=True)), not a pre-expanded "
                "EventStream")
        ev = program_or_events
    else:
        if fold:
            plan = folding.plan(program_or_events, warm_lines=warm_lines)
        ev = ev_mod.expand(
            program_or_events, rows=plan.rows if plan else None)
    T = ev.num_instructions
    if plan is not None:
        prep = PreparedTrace(ev, plan.weight, plan.wa, plan.wb,
                             plan.num_folds, 1.0, ev.spill_line0,
                             certifiable=plan.certifiable)
    else:
        ones = np.ones(T, np.int32)
        zeros = np.zeros(T, np.int32)
        prep = PreparedTrace(ev, ones, zeros, zeros, 0, 1.0, ev.spill_line0)
    total = ev.num_events
    if max_events is not None and total > max_events:
        cum = np.cumsum(ev.events_per_row)
        t = max(int(np.searchsorted(cum, max_events, side="right")), 1)
        prep = _slice_prep(prep, t)
        prep.event_scale = total / float(cum[t - 1])
    return prep


def _bucket(t: int) -> int:
    """Round the grid length up to a power of two so differently folded
    suites reuse one compiled executable per bucket."""
    b = 1024
    while b < t:
        b *= 2
    return b


def _stack(preps: list[PreparedTrace], pad_to: int | None = None):
    t_pad = pad_to or _bucket(max(p.num_rows for p in preps))
    with obs.span("engine.stack", bucket=t_pad,
                  programs=len(preps)) as sp:
        def pad(get, fill, dtype=None):
            outs = []
            for pr in preps:
                a = get(pr)
                if a.ndim == 1:
                    full = np.full(t_pad, fill, a.dtype if dtype is None
                                   else dtype)
                else:
                    full = np.full((t_pad, a.shape[1]), fill,
                                   a.dtype if dtype is None else dtype)
                full[: len(a)] = a
                outs.append(full)
            return np.stack(outs)

        arrays = (
            pad(lambda p: p.ev.reg_valid, False),
            pad(lambda p: p.ev.reg, 0),
            pad(lambda p: p.ev.vd_writes, False),
            pad(lambda p: p.ev.vd_reads, False),
            pad(lambda p: p.ev.vd_no_fetch, False),
            pad(lambda p: p.ev.lock_vs1, -1),
            pad(lambda p: p.ev.lock_vs2, -1),
            pad(lambda p: p.ev.mem_valid, False),
            pad(lambda p: p.ev.mem_line, -1),
            pad(lambda p: p.ev.mem_write, False),
            pad(lambda p: p.ev.cost, 0),
            pad(lambda p: p.ev.next_use, NO_NEXT_USE),
            pad(lambda p: p.weight, 0),
            pad(lambda p: p.wa, 0),
            pad(lambda p: p.wb, 0),
        )
        spill0s = np.asarray([p.spill_line0 for p in preps], np.int32)
        slots_used = tuple(
            bool(arrays[0][:, :, s].any()) for s in range(3)
        ) + tuple(bool(arrays[7][:, :, m].any()) for m in range(2))
        if sp:
            sp.set(bytes=sum(a.nbytes for a in arrays))
    return arrays, spill0s, slots_used


def _dispatch_grid(machine: MachineSweep, slots_used, track_ab, arrays,
                   spill0s, cfg, mach, impl):
    """One `_run_grid` (``impl="xla"``) or `_run_grid_pallas` call with
    donation noise suppressed: the counter outputs are far smaller than
    the donated trace grid, so XLA may decline the alias and warn —
    harmless, the donation is an upper bound."""
    global _DISPATCHES
    _DISPATCHES += 1
    run = _run_grid_pallas if impl == "pallas" else _run_grid
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return run(machine.l1_sets, machine.l1_ways, slots_used, track_ab,
                   tuple(jnp.asarray(a) for a in arrays),
                   jnp.asarray(spill0s), cfg, mach)


def dispatch_span(engine: str, preps: list, sweep: SweepConfig,
                  machines: MachineSweep, fused: bool, impl: str):
    """The ``engine.dispatch`` span of one engine call, from the call to
    its counters on the host (every call blocks on them, so the span
    covers the device's work).  ``steps`` counts bucket rows scanned: once
    for a fused dispatch, whose lanes advance together, once per program
    on the per-program path, whose stacks then nest inside the span.
    ``impl`` names the scan that ran (``"pallas"`` or ``"xla"``), and
    ``l1_sets`` the L1 geometry the kernel's spill window scales with."""
    sp = obs.span("engine.dispatch", engine=engine, impl=impl,
                  l1_sets=machines.l1_sets)
    if sp:
        buckets = [_bucket(p.num_rows) for p in preps]
        sp.set(steps=max(buckets) if fused else sum(buckets),
               programs=len(preps),
               lanes=(len(preps) * len(sweep.capacity)
                      * len(machines.mem_latency)),
               rows=sum(p.num_rows for p in preps),
               dispatches=1 if fused else len(preps))
    return sp


def simulate_grid(preps: list, sweep: SweepConfig,
                  machine=DEFAULT_MACHINE,
                  batch_programs: bool = False) -> dict[str, np.ndarray]:
    """Simulate P prepared traces under C configurations in one sweep call.

    ``machine`` is either one :class:`MachineParams` point (returns (P, C)
    counter arrays, the classic grid) or a :class:`MachineSweep` of M traced
    latency points (returns (P, C, M) arrays — the whole machine grid in the
    same dispatch, one compile per program-shape bucket).  Alongside the raw
    counters the dict carries ``hit_rate`` and, for folded traces,
    ``fold_exact`` (measured periods A == B => the algebraic extrapolation
    is exact, certified independently at every (C, M) grid point).

    ``batch_programs=True`` pads every trace to one bucket and vmaps the
    program axis into a single XLA dispatch — the right shape for
    accelerator backends.  The default dispatches per program (configs
    stay vmapped): on CPU the batched lanes execute serially anyway, so
    per-program dispatches avoid padding every trace to the longest one
    while the power-of-two shape buckets keep executable reuse across
    programs and suites.
    """
    preps = [prepare(p) if not isinstance(p, PreparedTrace) else p
             for p in preps]
    squeeze_m = not isinstance(machine, MachineSweep)
    machines = MachineSweep.from_params([machine]) if squeeze_m else machine
    cfg = (jnp.asarray(sweep.capacity), jnp.asarray(sweep.policy),
           jnp.asarray(sweep.alloc_no_fetch))
    mach = (jnp.asarray(machines.l1_hit_cycles),
            jnp.asarray(machines.uop_hit_cycles),
            jnp.asarray(machines.mem_latency))
    impl = scan_impl(machines, cfg)
    if batch_programs:
        arrays, spill0s, slots_used = _stack(preps)
        track_ab = any(p.num_folds for p in preps)
    c0 = _COMPILES
    with dispatch_span("core", preps, sweep, machines,
                       batch_programs, impl) as sp:
        if batch_programs:
            ctr, ctrA, ctrB = _dispatch_grid(machines, slots_used, track_ab,
                                             arrays, spill0s, cfg, mach,
                                             impl)
            ctr, ctrA, ctrB = (np.asarray(x) for x in (ctr, ctrA, ctrB))
        else:
            outs = []
            for prep in preps:
                arrays, spill0s, slots_used = _stack([prep])
                outs.append(_dispatch_grid(machines, slots_used,
                                           prep.num_folds > 0, arrays,
                                           spill0s, cfg, mach, impl))
            ctr = np.concatenate([np.asarray(o[0]) for o in outs])
            ctrA = np.concatenate([np.asarray(o[1]) for o in outs])
            ctrB = np.concatenate([np.asarray(o[2]) for o in outs])
        sp.set(compiled=_COMPILES != c0)
    if squeeze_m:
        ctr, ctrA, ctrB = ctr[:, :, 0], ctrA[:, :, 0], ctrB[:, :, 0]
    out = {k: ctr[..., i] for i, k in enumerate(COUNTER_NAMES)}
    grid_shape = out["cycles"].shape              # (P, C) or (P, C, M)
    per_prog = (-1,) + (1,) * (len(grid_shape) - 1)
    if any(p.num_folds for p in preps):
        steady = (ctrA == ctrB).all(axis=-1)
        steady &= np.asarray(
            [p.certifiable for p in preps]).reshape(per_prog)
        unfolded = np.asarray([p.num_folds == 0 for p in preps])
        steady[unfolded] = True
        out["fold_exact"] = steady
    total = out["vrf_hits"] + out["vrf_misses"]
    with np.errstate(divide="ignore", invalid="ignore"):
        out["hit_rate"] = np.where(total > 0, out["vrf_hits"] / total, 1.0)
    out["event_scale"] = np.broadcast_to(
        np.asarray([p.event_scale for p in preps]).reshape(per_prog),
        grid_shape).copy()
    return out


def simulate_sweep(program_or_events, sweep: SweepConfig,
                   machine=DEFAULT_MACHINE,
                   max_events: int | None = None,
                   fold: bool = False) -> dict[str, np.ndarray]:
    """Deprecated: use :func:`repro.api.sweep_program` (one raw program) or
    a :class:`repro.api.Session` running a declarative ``Sweep`` (named
    kernels).  This shim delegates to ``repro.api`` and returns the same
    dict of (C,)-shaped — (C, M)-shaped under a :class:`MachineSweep` —
    counter arrays the old entry point produced."""
    warnings.warn(
        "simulator.simulate_sweep is deprecated; use repro.api.sweep_program"
        " (or Session.run with a declarative Sweep) instead",
        DeprecationWarning, stacklevel=2)
    from repro import api  # runtime import: api sits above the core layer
    return api.sweep_program(program_or_events, sweep, machine=machine,
                             fold=fold, max_events=max_events)


def simulate_one(program, capacity, policy=policies.FIFO,
                 alloc_no_fetch=False,
                 machine=DEFAULT_MACHINE,
                 max_events: int | None = None,
                 fold: bool = False) -> dict[str, float]:
    prep = prepare(program, fold=fold, max_events=max_events,
                   machine=machine)
    sweep = SweepConfig.make([capacity], policy, alloc_no_fetch)
    out = simulate_grid([prep], sweep, machine)
    return {k: v[0, 0] for k, v in out.items()}


def full_vrf_baseline(program, machine: MachineParams = DEFAULT_MACHINE,
                      max_events: int | None = None) -> dict[str, float]:
    return simulate_one(program, isa.NUM_ARCH_VREGS, machine=machine,
                        max_events=max_events)


# ---------------------------------------------------------------------------
# Scalar-core baseline (the paper's Table 3 comparison point).
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ScalarCost:
    """Analytic cycle model of the -O2 scalar RISC-V version of a kernel.

    On a 3-stage in-order embedded core (Table 1):
      flop_ops:  FPU ops at ``flop_cycles`` each (low-cost FPUs are not
                 fully pipelined; fmadd ~2 cycles effective)
      int_ops:   1-cycle integer ALU ops (incl. branchy min/max selects)
      loads:     ``load_cycles`` each (L1 hit + average load-use hazard)
      stores:    1 cycle
      unique_lines: distinct cachelines -> compulsory-miss stalls
      loop_iters: per-iteration overhead (addr bump + cmp + taken branch;
                 embedded -O2 without aggressive unrolling)
    """

    flop_ops: int = 0
    int_ops: int = 0
    loads: int = 0
    stores: int = 0
    unique_lines: int = 0
    loop_iters: int = 0
    flop_cycles: float = 2.0
    load_cycles: float = 1.5
    overhead_per_iter: int = 3

    def cycles(self, machine=DEFAULT_MACHINE):
        """Scalar-core cycles; with a :class:`MachineSweep` the result is an
        (M,) int64 array over the swept memory latencies."""
        base = (self.flop_ops * self.flop_cycles
                + self.int_ops
                + self.loads * self.load_cycles
                + self.stores
                + self.loop_iters * self.overhead_per_iter)
        mem = self.unique_lines * np.asarray(machine.mem_latency)
        total = base + mem
        if isinstance(machine, MachineSweep):
            return total.astype(np.int64)
        return int(total)

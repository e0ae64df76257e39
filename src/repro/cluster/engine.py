"""Fused N-core cluster sweep engine.

Lifts the single-core fused engine (``core/simulator.py``) to a cluster
of N homogeneous dispersion cores behind a shared L2 and banked memory
channels (``cluster/contention.py``), still as ONE ``lax.scan``:

  * the per-instruction engine body (``simulator._make_body``) is vmapped
    over a leading core axis — private cVRF, L1 and spill state per core,
    all N cores retiring the same instruction in lockstep (worst-case
    -aligned contention);
  * each core runs the trace in its own **address colour**: core i's
    spill region and data lines are offset by ``i * stride`` (stride =
    the program footprint rounded up to odd, so per-core L1 set mappings
    genuinely differ while core 0 is untouched — the N=1 identity);
  * the cores' per-instruction L1-miss streams
    (``simulator.NUM_MISS_SITES`` sites each) are drained *inside the
    same scan step* through the shared L2 in round-robin core order, and
    the survivors queue on the memory channels
    (:func:`repro.cluster.contention.queue_rounds`), charging each core
    a ``contention_stalls`` increment that is a latency-independent
    multiple of ``mem_latency``.

Counter layout: :data:`CLUSTER_COUNTER_NAMES` = the single-core
``COUNTER_NAMES`` + (``contention_stalls``, ``l2_hits``, ``l2_misses``).
``cycles`` absorbs the contention adjustment
``l2_hits * (l2_hit_cycles - mem_latency) + contention_stalls`` per core,
so per-core cycles stay exactly affine in the traced latencies
(:func:`check_cluster_affine`); the *aggregate* cluster ``cycles`` is the
makespan (max over cores), which is only piecewise affine — the affine
cross-check therefore runs on the per-core grid.

Compile/dispatch accounting increments the same
``simulator._COMPILES`` / ``_DISPATCHES`` counters, so ``repro.api``'s
session accounting sees cluster work with no extra plumbing: one compile
per (shape bucket x L1 geometry x ClusterConfig).
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster import contention
from repro.cluster.contention import ClusterConfig
from repro.core import events as ev_mod
from repro.core import costmodel, isa, policies, simulator
from repro.core.simulator import (DEFAULT_MACHINE, MachineSweep,
                                  PreparedTrace, SweepConfig)

CLUSTER_COUNTER_NAMES = simulator.COUNTER_NAMES + (
    "contention_stalls", "l2_hits", "l2_misses",
)

# Aggregate-only outputs derived from the per-core cycles column.
CORE_CYCLE_AGGREGATES = ("core_cycles_min", "core_cycles_max",
                         "core_cycles_sum")


def _stride(prep: PreparedTrace) -> int:
    """Per-core address-colour stride: one core's whole footprint (spill
    region + data lines), rounded up to odd so consecutive colours land on
    different L1/L2 sets (set counts are powers of two)."""
    mem_max = int(np.max(prep.ev.mem_line, initial=-1))
    footprint = max(prep.spill_line0 + isa.NUM_ARCH_VREGS, mem_max + 1)
    return footprint | 1


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4),
                   donate_argnums=(5, 6, 7))
def _run_cluster_grid(cluster, l1_sets, l1_ways, slots_used, track_ab,
                      arrays, spill0s, strides, cfg, mach):
    """(P, T) trace grid x (C,) configs x (M,) machines x N lockstep cores
    -> (P, C, M, N, 15) per-core cluster counters (x3 for the A/B fold
    certificate).  Statics mirror ``simulator._run_grid`` plus the whole
    (hashable) :class:`ClusterConfig`; the jit cache therefore compiles
    once per (bucket, L1 geometry, cluster) plan group."""
    simulator._COMPILES += 1
    N = cluster.n_cores
    n_ctr = len(CLUSTER_COUNTER_NAMES)
    core_ids = jnp.arange(N, dtype=jnp.int32)

    def one_program(arr, sp0, stride):
        def one_cfg(c):
            def one_machine(m):
                body = simulator._make_body(l1_sets, slots_used, c, m)
                mem_lat = m[2]
                spill_bases = sp0.astype(jnp.int32) + core_ids * stride
                mem_bases = core_ids * stride
                caches = jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x, (N,) + x.shape),
                    policies.CacheState.init(isa.NUM_ARCH_VREGS))
                l1s = jnp.broadcast_to(
                    simulator._l1_init(l1_sets, l1_ways),
                    (N, l1_sets, l1_ways, 2))
                z = jnp.zeros((N, n_ctr), jnp.int32)
                # The L2 access clock starts at 1: stored ages stay
                # strictly positive, so a just-filled line never ties with
                # a free way (age 0) in the LRU argmin.
                carry = (caches, l1s, jnp.zeros(N, jnp.int32),
                         contention.l2_init(cluster.l2_sets,
                                            cluster.l2_ways),
                         jnp.int32(1), jnp.int32(0), jnp.int32(0),
                         z, z, z)

                def step(carry, xs):
                    (caches, l1s, seqs, l2, clk, t, now0,
                     ctr, ctrA, ctrB) = carry
                    wt, wa, wb = xs[-3:]
                    (caches, l1s, seqs), incs, miss_lines = jax.vmap(
                        lambda st, sb, mb: body(st, xs, sb, mb, now0)
                    )((caches, l1s, seqs), spill_bases, mem_bases)
                    # Shared L2 + channel arbiter, in RR core order.
                    order = contention.rank_order(N, t)
                    lines_rr = miss_lines[order].reshape(
                        N * simulator.NUM_MISS_SITES)
                    if cluster.l2_sets:
                        def l2_step(c2, line):
                            l2_, clk_ = c2
                            l2_, hit = contention.l2_access(
                                l2_, line, clk_, cluster.l2_sets)
                            return (l2_, clk_ + (line >= 0)), hit
                        (l2, clk), hits_rr = jax.lax.scan(
                            l2_step, (l2, clk), lines_rr)
                    else:
                        hits_rr = jnp.zeros(lines_rr.shape, bool)
                    site_hit = hits_rr.reshape(
                        N, simulator.NUM_MISS_SITES)
                    site_req = (lines_rr >= 0).reshape(
                        N, simulator.NUM_MISS_SITES) & ~site_hit
                    l2h_rr = site_hit.sum(1).astype(jnp.int32)
                    reqs_rr = site_req.sum(1).astype(jnp.int32)
                    q_rr = contention.queue_rounds(reqs_rr,
                                                   cluster.mem_channels)
                    zc = jnp.zeros(N, jnp.int32)      # rank -> core scatter
                    l2h = zc.at[order].set(l2h_rr)
                    reqs = zc.at[order].set(reqs_rr)
                    stall = zc.at[order].set(q_rr) * mem_lat
                    cyc = (incs[:, 0] + stall
                           + l2h * (cluster.l2_hit_cycles - mem_lat))
                    inc_full = jnp.concatenate(
                        [cyc[:, None], incs[:, 1:], stall[:, None],
                         l2h[:, None], reqs[:, None]], axis=1)
                    ctr = ctr + inc_full * wt
                    if track_ab:
                        ctrA = ctrA + inc_full * wa
                        ctrB = ctrB + inc_full * wb
                    return (caches, l1s, seqs, l2, clk, t + 1,
                            now0 + ev_mod.NUM_SLOTS, ctr, ctrA, ctrB), None

                out = jax.lax.scan(step, carry, arr)[0]
                return out[-3], out[-2], out[-1]
            return jax.vmap(one_machine)(mach)
        return jax.vmap(one_cfg)(cfg)

    return jax.vmap(one_program)(arrays, spill0s, strides)


def _dispatch_cluster_grid(cluster, machine, slots_used, track_ab, arrays,
                           spill0s, strides, cfg, mach):
    simulator._DISPATCHES += 1
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return _run_cluster_grid(
            cluster, machine.l1_sets, machine.l1_ways, slots_used, track_ab,
            tuple(jnp.asarray(a) for a in arrays), jnp.asarray(spill0s),
            jnp.asarray(strides), cfg, mach)


def simulate_cluster_grid(preps: list, sweep: SweepConfig,
                          machine=DEFAULT_MACHINE,
                          cluster: ClusterConfig = ClusterConfig(),
                          batch_programs: bool = False,
                          return_per_core: bool = False) -> dict:
    """Cluster analogue of :func:`repro.core.simulator.simulate_grid`.

    Returns (P, C) — or (P, C, M) under a :class:`MachineSweep` — arrays
    for every :data:`CLUSTER_COUNTER_NAMES` counter, aggregated over the N
    cores: ``cycles`` is the cluster **makespan** (max over cores, the
    time until the last core retires), every other counter is the sum.
    ``core_cycles_min/max/sum`` expose the per-core cycles spread (the
    fairness margin), and ``fold_exact`` / ``hit_rate`` / ``event_scale``
    carry over with their single-core semantics (a fold is certified only
    if A == B on *every* core's full counter vector).

    ``return_per_core=True`` additionally returns ``out["per_core"]``, a
    dict of (..., N) per-core counter grids — the input shape for
    :func:`check_cluster_affine` (makespan is only piecewise affine in the
    latencies; each core's counters are exactly affine).
    """
    preps = [simulator.prepare(p) if not isinstance(p, PreparedTrace) else p
             for p in preps]
    squeeze_m = not isinstance(machine, MachineSweep)
    machines = MachineSweep.from_params([machine]) if squeeze_m else machine
    cfg = (jnp.asarray(sweep.capacity), jnp.asarray(sweep.policy),
           jnp.asarray(sweep.alloc_no_fetch))
    mach = (jnp.asarray(machines.l1_hit_cycles),
            jnp.asarray(machines.uop_hit_cycles),
            jnp.asarray(machines.mem_latency))
    strides = np.asarray([_stride(p) for p in preps], np.int32)
    if batch_programs:
        arrays, spill0s, slots_used = simulator._stack(preps)
        track_ab = any(p.num_folds for p in preps)
    c0 = simulator._COMPILES
    with simulator.dispatch_span("cluster", preps, sweep, machines,
                                 batch_programs) as sp:
        if batch_programs:
            ctr, ctrA, ctrB = _dispatch_cluster_grid(
                cluster, machines, slots_used, track_ab, arrays, spill0s,
                strides, cfg, mach)
            ctr, ctrA, ctrB = (np.asarray(x) for x in (ctr, ctrA, ctrB))
        else:
            outs = []
            for prep, stride in zip(preps, strides):
                arrays, spill0s, slots_used = simulator._stack([prep])
                outs.append(_dispatch_cluster_grid(
                    cluster, machines, slots_used, prep.num_folds > 0,
                    arrays, spill0s, stride[None], cfg, mach))
            ctr = np.concatenate([np.asarray(o[0]) for o in outs])
            ctrA = np.concatenate([np.asarray(o[1]) for o in outs])
            ctrB = np.concatenate([np.asarray(o[2]) for o in outs])
        sp.set(compiled=simulator._COMPILES != c0)
    if squeeze_m:                                   # (P, C, M, N, 15)
        ctr, ctrA, ctrB = ctr[:, :, 0], ctrA[:, :, 0], ctrB[:, :, 0]
    per_core = {k: ctr[..., i] for i, k in enumerate(CLUSTER_COUNTER_NAMES)}
    cyc = per_core["cycles"]
    out = {"cycles": cyc.max(axis=-1)}
    for name in CLUSTER_COUNTER_NAMES[1:]:
        out[name] = per_core[name].sum(axis=-1)
    out["core_cycles_min"] = cyc.min(axis=-1)
    out["core_cycles_max"] = cyc.max(axis=-1)
    out["core_cycles_sum"] = cyc.sum(axis=-1)
    grid_shape = out["cycles"].shape              # (P, C) or (P, C, M)
    per_prog = (-1,) + (1,) * (len(grid_shape) - 1)
    if any(p.num_folds for p in preps):
        steady = (ctrA == ctrB).all(axis=(-1, -2))
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
    if return_per_core:
        out["per_core"] = per_core
    return out


def check_cluster_affine(per_core: dict, machines: MachineSweep) -> dict:
    """Machine-latency affinity cross-check, per core.

    ``per_core`` is ``simulate_cluster_grid(..., return_per_core=True)
    ["per_core"]`` with shape (..., M, N).  Each core's ``cycles`` /
    ``stall_cycles`` / ``contention_stalls`` must be exactly affine in the
    traced latencies and every other counter machine-invariant — the L2
    and arbiter only ever consult hit/miss decisions.  The ``mem_latency``
    slope floor is ``l1_misses - l2_hits``: every L2 hit converts one
    memory transfer into a (static) ``l2_hit_cycles`` term, while channel
    queueing only adds whole ``mem_latency`` rounds on top.
    """
    cnt = {k: np.swapaxes(np.asarray(v), -1, -2)      # (..., N, M)
           for k, v in per_core.items()}
    floor = cnt["l1_misses"][..., 0] - cnt["l2_hits"][..., 0]
    return costmodel.check_machine_affine(
        cnt, machines,
        timing=("cycles", "stall_cycles", "contention_stalls"),
        mem_slope_floor=floor)

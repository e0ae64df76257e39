"""Driver: an architect's design-space session on the dispersion engine.

One ``repro.api.Session`` per run and ``Session.run`` back to back, each
on a fresh ``Sweep`` built from the traffic file (kernels or a lowered
network, capacity x policy x L1 geometry).  Every sweep asks for a machine
point no earlier sweep of the run asked for: the traffic file's ``machine``
names one traced latency and its values, and the seed orders them.  The
shapes never change, so nothing compiles after the first sweep, which is
set-up.  The window ends at the first sweep completion after ``seconds``,
or earlier where the next sweep would need a machine point the traffic
file lacks (``pool_exhausted``); either way it holds completed sweeps only.

``correct``: once the window has closed, one grid point per kernel, drawn
from the seed among all the window's sweeps, is simulated again by the
plain reference (``harness.engine_ref``) from the kernel's instruction
trace, in worker processes that do not touch JAX.  A point whose
``fold_exact`` certificate holds must match on all twelve counters.  Where
the traffic file allows extrapolation, a point the engine flags as
extrapolated (``fold_exact`` False) must match on the register-file and
access counts and lie within a relative limit on cycles; otherwise it
must match on all twelve.  Lowered networks must consist of the layers the
configuration's reference lists.  The control, which only
``bench/readings.py`` runs, puts the reference's answer for the nearest
other memory latency of the pool in the program's place.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import multiprocessing
import os
import time

import numpy as np

from harness import engine_ref, spec, trace


class Sweeps:
    """Builds the j-th sweep of a run from the traffic file and the seed."""

    def __init__(self, traffic: dict, seed: int):
        from repro import api
        self.api = api
        self.t = traffic
        m = traffic["machine"]
        order = np.random.default_rng(seed).permutation(len(m["values"]))
        self.values = [m["values"][i] for i in order]

    def __len__(self):
        return len(self.values)

    def machine(self, j: int) -> dict:
        m = self.t["machine"]
        point = {k: v for k, v in m.items() if k not in ("vary", "values")}
        point[m["vary"]] = self.values[j]
        return point

    def make(self, j: int):
        if j >= len(self.values):
            raise RuntimeError(
                f"sweep {j} needs a machine point no earlier sweep used, "
                f"but the traffic file lists {len(self.values)}")
        t, m = self.t, self.machine(j)
        ways = t.get("l1_ways", 2)
        return self.api.Sweep(
            kernels=tuple(t.get("kernels", ())),
            network=tuple(t.get("network", ())),
            capacity=tuple(t["capacity"]), policy=tuple(t["policy"]),
            l1_geometry=tuple((kb * 1024 // 32 // ways, ways)
                              for kb in t["l1_kb"]),
            kernel_params=t["kernel_params"],
            mem_latency=(m["mem_latency"],),
            l1_hit_cycles=(m["l1_hit_cycles"],),
            uop_hit_cycles=(m["uop_hit_cycles"],))


def instructions(session, sweep, result) -> int:
    """Simulated instructions a sweep stands for: each kernel's full
    (unfolded) trace at every grid point."""
    per_kernel = result.meta["points"] // len(sweep.kernels)
    return sum(session.built(k, sweep.kernel_params).program.num_instructions
               for k in sweep.kernels) * per_kernel


def plan_counts(session, sweep, result) -> dict:
    """Scan steps and lane-rows the recorded plan issued."""
    geos = {str(g): g for g in sweep.l1_geometry}
    steps = padded = rows = 0
    for entry in result.meta["plan"]:
        machine = sweep.machine_sweep(geos[entry["l1_geometry"]])
        steps += entry["bucket"]
        padded += entry["bucket"] * len(entry["kernels"])
        rows += sum(session.prepared(k, machine=machine,
                                     params=sweep.kernel_params).num_rows
                    for k in entry["kernels"])
    return dict(scan_steps=steps, padded_rows=padded, rows=rows)


def _index(result, **chosen) -> tuple:
    return tuple(chosen.get(a.name, 0) for a in result.axes)


# Counters that a fold without its certificate still extrapolates
# exactly: the register file's and the instruction stream's own counts.
# Its cycles are an extrapolation; its stall cycles and L1 hits and misses
# are not compared (see PERF.md).
EXACT_WHEN_EXTRAPOLATED = ("vrf_hits", "vrf_misses", "spills", "fills",
                           "reg_reads", "reg_writes", "mem_reads",
                           "mem_writes")


def _neighbour(machine: dict, pool: list) -> dict:
    """The control's machine: the nearest other memory latency of the
    pool, the answer a results cache would give for a nearby point."""
    lat = machine["mem_latency"]
    other = min((v for v in pool if v != lat), key=lambda v: (abs(v - lat),
                                                               v))
    return dict(machine, mem_latency=other)


def check(ctx, session, window, reference) -> list:
    """The comparisons that decide ``correct``: [(name, value, limit)]."""
    t = ctx.cell.traffic
    limits = t["limits"]
    extrapolate = "extrapolated_cycles_rel_err" in limits
    rng = np.random.default_rng([ctx.seed, 1])
    jobs, points = [], []
    for k in window[0][0].kernels:
        j = int(rng.integers(len(window)))
        sweep, res = window[j]
        ci = int(rng.integers(len(sweep.capacity)))
        pi = int(rng.integers(len(sweep.policy)))
        gi = int(rng.integers(len(sweep.l1_geometry)))
        idx = _index(res, kernel=sweep.kernels.index(k), capacity=ci,
                     policy=pi, l1_geometry=gi)
        geo = sweep.l1_geometry[gi]
        m = sweep.machine_sweep(geo)
        machine = dict(capacity=sweep.capacity[ci], policy=sweep.policy[pi],
                       l1_sets=geo.sets, l1_ways=geo.ways,
                       l1_hit_cycles=int(m.l1_hit_cycles[0]),
                       uop_hit_cycles=int(m.uop_hit_cycles[0]),
                       mem_latency=int(m.mem_latency[0]))
        prog = session.built(k, sweep.kernel_params).program
        arrays = {f: getattr(prog, f) for f in engine_ref.TRACE_FIELDS}
        got = {c: int(res.data[c][idx]) for c in engine_ref.COUNTERS}
        certified = bool(res.data["fold_exact"][idx]) \
            if "fold_exact" in res.data else True
        points.append(dict(kernel=k, sweep=j, certified=certified,
                           got=got, **machine))
        jobs.append((arrays, prog.memory.nbytes, machine))
        if ctx.control:
            jobs.append((arrays, prog.memory.nbytes,
                         _neighbour(machine, t["machine"]["values"])))
    ctx.log(f"reference: {len(jobs)} points in worker processes")
    t0 = time.perf_counter()
    workers = max(1, min(len(jobs), os.cpu_count() or 1, 8))
    with concurrent.futures.ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn")) as ex:
        want = list(ex.map(engine_ref.simulate_trace, *zip(*jobs)))
    ctx.log(f"reference: {time.perf_counter() - t0:.3f} s")
    if ctx.control:
        # The control stands in the program's place: the reference's
        # answer for the neighbouring machine point.
        for p, stale in zip(points, want[1::2]):
            p["got"] = stale
        want = want[0::2]

    mismatches, cycles_err = 0, 0.0
    for p, w in zip(points, want):
        p["cycles_rel_err"] = abs(p["got"]["cycles"] - w["cycles"]) / \
            w["cycles"]
        counters = engine_ref.COUNTERS
        if extrapolate and not p["certified"]:
            counters = EXACT_WHEN_EXTRAPOLATED
            cycles_err = max(cycles_err, p["cycles_rel_err"])
        p["mismatched"] = [c for c in counters if p["got"][c] != w[c]]
        mismatches += len(p["mismatched"])
        ctx.log("point " + " ".join(
            f"{k}={p[k]}" for k in ("kernel", "sweep", "capacity", "policy",
                                    "l1_sets", "mem_latency", "certified",
                                    "mismatched", "cycles_rel_err")))
    ctx.details["points"] = points
    out = [("exact_mismatches", mismatches, limits["exact_mismatches"])]
    if extrapolate:
        out.append(("extrapolated_cycles_rel_err", cycles_err,
                    limits["extrapolated_cycles_rel_err"]))
    if t.get("network"):
        out.append(("network_mismatches",
                    _network_mismatches(ctx, window, reference), 0))
    return out


def _network_mismatches(ctx, window, reference) -> int:
    """Lowered networks of every window sweep against the layer list of
    the configuration's reference."""
    want = reference.lowered_summary(ctx.cell.config)
    bad = 0
    for _sweep, res in window:
        for net in res.meta["networks"]:
            got = dict(kernels=sorted(net["kernels"]), units=net["units"],
                       instances=net["instances"])
            if got != want:
                ctx.log(f"lowered {net['model']}: {got} != {want}")
                bad += 1
    return bad


def run(ctx) -> dict:
    from repro import api

    t = ctx.cell.traffic
    sweeps = Sweeps(t, ctx.seed)
    reference = (spec.config_reference(ctx.cell) if t.get("network")
                 else None)
    session = api.Session()
    with ctx.spans.span("setup.sweep"):
        session.run(sweeps.make(0))           # traces, folds, compiles
    ctx.mark_setup_done()

    window, ends, total_instr, pool_exhausted = [], [], 0, 0
    traced_n = int(t.get("trace_sweeps", 1)) if ctx.trace else 0
    traced = dict(scan_steps=0, padded_rows=0, rows=0)
    c0 = ctx.compiles.count
    t0 = time.perf_counter()
    with contextlib.ExitStack() as stack:
        j = 1
        while True:
            if j == 1 and traced_n:
                stack.enter_context(trace.capture(ctx.trace_dir))
                stack.enter_context(ctx.spans.span("window"))
            with ctx.spans.span("engine.lower"):
                sweep = sweeps.make(j)
            with ctx.spans.span("engine.sweep"):
                res = session.run(sweep)
            window.append((sweep, res))
            total_instr += instructions(session, sweep, res)
            if j <= traced_n:
                for k, v in plan_counts(session, sweep, res).items():
                    traced[k] += v
            if j == traced_n:
                stack.close()
            elapsed = time.perf_counter() - t0
            ends.append(round(elapsed, 3))
            if elapsed >= ctx.seconds:
                break
            if j + 1 == len(sweeps):
                pool_exhausted = 1
                ctx.log(f"window: the pool's {len(sweeps)} machine points "
                        f"are used up after {elapsed:.3f} s of "
                        f"{ctx.seconds} s")
                break
            j += 1
    window_compiles = ctx.compiles.count - c0
    ctx.log(f"window: {len(window)} sweeps, {elapsed:.3f} s, "
            f"{total_instr} simulated instructions, "
            f"{window_compiles} compiles inside; sweeps ended at {ends} s")
    ctx.details["window"] = dict(seconds=elapsed, instructions=total_instr)
    peak = ctx.memory_peak()
    checks = check(ctx, session, window, reference)
    checks.append(("window_compiles", window_compiles, 0))
    return dict(
        attempted=len(window), failed=0, memory_peak_bytes=peak,
        end_to_end=dict(sim_instr_per_s=total_instr / elapsed),
        counts=dict(traced, sweeps=len(window), traced_sweeps=traced_n,
                    pool_exhausted=pool_exhausted),
        checks=checks)

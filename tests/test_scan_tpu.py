"""The Pallas scan kernel (``core.scan_tpu``) against the XLA scan.

The kernel runs in the Pallas interpreter on the CPU and must return the
XLA ``_run_grid``'s ``ctr``, ``ctrA`` and ``ctrB`` bit for bit: all 12
counters at every (program, config, machine) point, over every policy,
capacities up to the full VRF, alloc-no-fetch on and off, the 4 KB and
16 KB L1 and an odd geometry, a folded trace with its A/B periods, a
fused dispatch of two programs of different lengths, and more than 128
lanes.
"""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, rvv
from repro.core import policies, scan_tpu, simulator

ALL_POLICIES = (policies.FIFO, policies.LRU, policies.LFU, policies.OPT)


def _prepared(name, params=None, fold=False):
    b = rvv.BENCHMARKS[name]
    program = b.build(**(params or b.reduced_params)).program
    return simulator.prepare(program, fold=fold)


# Most cases share one config x machine grid: 3 capacities (up to the
# full VRF) x 4 policies x alloc-no-fetch off and on, 2 memory latencies.
GRID = dict(capacities=(3, 8, 32), policies=ALL_POLICIES,
            anf=(False, True), mem=(1, 5))
DENSENET = ("densenet121_l105",)
DROPOUT_FOLDED = ("dropout", {"n": 4096, "scale": 0.5}, True)

CASES = {
    # The golden kernels at reduced size, fused: one dispatch of two
    # programs of different lengths, on the 4 KB L1.
    "golden-fused-4kb": dict(
        GRID, programs=[DENSENET, ("flashattention2",)], geometry=(64, 2)),
    "golden-densenet-16kb": dict(GRID, programs=[DENSENET],
                                 geometry=(256, 2)),
    # A folded trace: the A and B periods are tracked.
    "folded-dropout-track-ab": dict(
        GRID, programs=[DROPOUT_FOLDED], geometry=(256, 2)),
    # 8 x 4 x 2 configs x 3 machines = 192 lanes (two lane blocks), on an
    # L1 of fewer sets than spill lines, not a power of two, three ways.
    "lanes-over-128-odd-l1": dict(
        programs=[("pathfinder",)],
        capacities=(3, 4, 5, 6, 7, 8, 16, 32), policies=ALL_POLICIES,
        anf=(False, True), mem=(1, 4, 9), geometry=(24, 3)),
}


def _case(case):
    preps = [_prepared(*p) for p in case["programs"]]
    sweep = simulator.SweepConfig.product(
        case["capacities"], case["policies"], case["anf"])
    sets, ways = case["geometry"]
    machines = simulator.MachineSweep.product(
        case["mem"], l1_sets=sets, l1_ways=ways)
    return preps, sweep, machines


def _xla_args(preps, sweep, machines):
    arrays, spill0s, slots_used = simulator._stack(preps)
    cfg = tuple(jnp.asarray(x) for x in (sweep.capacity, sweep.policy,
                                         sweep.alloc_no_fetch))
    mach = tuple(jnp.asarray(x) for x in (machines.l1_hit_cycles,
                                          machines.uop_hit_cycles,
                                          machines.mem_latency))
    track_ab = any(p.num_folds for p in preps)
    return (machines.l1_sets, machines.l1_ways, slots_used, track_ab,
            tuple(jnp.asarray(a) for a in arrays), jnp.asarray(spill0s),
            cfg, mach)


def _pallas_args(preps, sweep, machines):
    rows, sums = scan_tpu.pack([simulator._columns(p) for p in preps],
                               simulator._FILLS,
                               max(simulator._bucket(p.num_rows)
                                   for p in preps))
    lanes = scan_tpu.lane_params(
        (sweep.capacity, sweep.policy, sweep.alloc_no_fetch),
        (machines.l1_hit_cycles, machines.uop_hit_cycles,
         machines.mem_latency))
    spill0s = np.asarray([p.spill_line0 for p in preps], np.int32)
    return (machines.l1_sets, machines.l1_ways, simulator._slots_used(preps),
            any(p.num_folds for p in preps), len(machines),
            jnp.asarray(rows), jnp.asarray(sums), jnp.asarray(spill0s),
            jnp.asarray(lanes))


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_matches_the_xla_scan(name):
    preps, sweep, machines = _case(CASES[name])
    want = simulator._run_grid(*_xla_args(preps, sweep, machines))
    got = simulator._run_grid_pallas(*_pallas_args(preps, sweep, machines),
                                     interpret=True)
    for label, w, g in zip(("ctr", "ctrA", "ctrB"), want, got):
        w, g = np.asarray(w), np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape, label
        bad = np.argwhere(w != g)
        assert not len(bad), (
            f"{label} differs at {len(bad)} entries, first (program, "
            f"config, machine, counter) {tuple(bad[0])}: "
            f"{simulator.COUNTER_NAMES[bad[0][-1]]} {g[tuple(bad[0])]} != "
            f"{w[tuple(bad[0])]}")
    if any(p.num_folds for p in preps):
        assert np.asarray(want[1]).any()      # the periods were tracked


@pytest.mark.parametrize("fused", [False, True])
def test_simulate_grid_switches_to_the_kernel(monkeypatch, fused):
    """Where ``scan_impl`` says ``pallas``, both the fused and the
    per-program dispatch run the kernel: the counters and the
    ``fold_exact`` certificate are those of the XLA scan, the span names
    the kernel, and a second run compiles nothing."""
    if fused:
        preps, sweep, machines = _case(CASES["golden-fused-4kb"])
    else:
        preps, sweep, machines = _case(dict(
            GRID, programs=[DENSENET, DROPOUT_FOLDED], geometry=(256, 2)))
    want = simulator.simulate_grid(preps, sweep, machines,
                                   batch_programs=fused)
    monkeypatch.setattr(simulator, "scan_impl", lambda *a: "pallas")
    monkeypatch.setattr(simulator, "_run_grid_pallas", functools.partial(
        simulator._run_grid_pallas, interpret=True))
    obs.reset()
    with obs.recording():
        got = simulator.simulate_grid(preps, sweep, machines,
                                      batch_programs=fused)
    (span,) = [s for s in obs.spans() if s.name == "engine.dispatch"]
    obs.reset()
    assert span.attrs["impl"] == "pallas"
    assert span.attrs["l1_sets"] == machines.l1_sets
    assert sorted(got) == sorted(want)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    c0 = simulator.compile_count()
    simulator.simulate_grid(preps, sweep, machines, batch_programs=fused)
    assert simulator.compile_count() == c0


def test_kernel_compiles_once_per_signature():
    """The kernel's jitted wrapper counts one compile per new (geometry,
    slots, track_ab, bucket) signature, and none for new latencies."""
    preps, sweep, machines = _case(dict(
        GRID, programs=[("gemv",)], geometry=(32, 2)))
    c0 = simulator.compile_count()
    simulator._run_grid_pallas(*_pallas_args(preps, sweep, machines),
                               interpret=True)
    assert simulator.compile_count() == c0 + 1
    machines.mem_latency = machines.mem_latency + 3
    simulator._run_grid_pallas(*_pallas_args(preps, sweep, machines),
                               interpret=True)
    assert simulator.compile_count() == c0 + 1


class _On:
    """A device stand-in of ``platform``."""

    def __init__(self, platform):
        self.platform = platform


@pytest.mark.parametrize("platform,sets,want", [
    ("cpu", 256, "xla"), ("tpu", 256, "pallas"), ("tpu", 64, "pallas"),
    ("tpu", 1 << 16, "xla")])
def test_scan_impl_follows_the_arrays_platform(platform, sets, want):
    """The kernel runs where the engine's arrays are on a TPU, unless the
    L1 planes would not fit the kernel's VMEM."""
    machines = simulator.MachineSweep.make([5], l1_sets=sets)
    assert simulator.scan_impl(machines, _On(platform)) == want

"""The plain timing-model reference agrees with the engine, counter for
counter, on every Table 2 kernel at its reduced size (the engine is
exact on unfolded traces; the chip gives the CPU's counters)."""

import pytest

from harness import engine_ref

MACHINES = [dict(l1_sets=256, l1_ways=2, l1_hit_cycles=0, uop_hit_cycles=1,
                 mem_latency=5),
            dict(l1_sets=64, l1_ways=2, l1_hit_cycles=1, uop_hit_cycles=2,
                 mem_latency=3)]


@pytest.mark.parametrize("kernel", ["pathfinder", "jacobi2d", "somier",
                                    "gemv", "dropout", "conv2d_7x7",
                                    "densenet121_l105", "resnet50_l10",
                                    "flashattention2"])
def test_reference_matches_the_engine_unfolded(kernel):
    from repro import rvv
    from repro.core import simulator
    from repro.core.simulator import MachineParams

    b = rvv.BENCHMARKS[kernel]
    prog = b.build(**b.reduced_params).program
    fields = engine_ref.decode(*(getattr(prog, f)
                                 for f in engine_ref.TRACE_FIELDS))
    for m in MACHINES:
        for cap in (3, 5, 8, 32):
            for pol in (engine_ref.FIFO, engine_ref.LRU):
                want = simulator.simulate_one(prog, cap, pol,
                                              machine=MachineParams(**m))
                got = engine_ref.simulate(fields, prog.memory.nbytes,
                                          capacity=cap, policy=pol, **m)
                assert got == {c: int(want[c])
                               for c in engine_ref.COUNTERS}, (cap, pol, m)

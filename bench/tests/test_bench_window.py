"""The window of ``drivers/engine_sweep.py``: it ends at the first sweep
completion after ``--seconds``, or when the pool of machine points is used
up, whichever comes first, and counts completed sweeps only.  Runs the
whole of ``engine_sweep.run`` on the CPU at the kernels' reduced sizes
with a pool of 4 memory latencies."""

import time

import pytest

from harness import runner, spans, spec

POOL = [2, 5, 9, 13]


@pytest.fixture
def pool_cell(small_engine_cell):
    small_engine_cell.traffic["kernels"] = ["gemv", "dropout"]
    small_engine_cell.traffic["machine"]["values"] = POOL
    return small_engine_cell


def _run_sweeps(cell, monkeypatch, seconds):
    """``engine_sweep.run``'s own result, with the memory latency of every sweep
    ``Session.run`` was handed, set-up's first."""
    from repro import api
    latencies = []
    orig = api.Session.run

    def run(self, sweep):
        latencies.append(sweep.mem_latency)
        return orig(self, sweep)
    monkeypatch.setattr(api.Session, "run", run)
    ctx = runner.Context(
        cell=cell, seed=2**33 + 3, seconds=seconds, trace=False,
        spans=spans.Spans(), compiles=spans.Compiles().install(),
        trace_dir=None, started=time.perf_counter())
    t0 = time.perf_counter()
    result = spec.driver(cell).run(ctx)
    return result, ctx, latencies, time.perf_counter() - t0


def _instructions_per_sweep(cell):
    from repro import api
    t = cell.traffic
    session = api.Session()
    points = len(t["capacity"]) * len(t["policy"]) * len(t["l1_kb"])
    return points * sum(
        session.built(k, t["kernel_params"]).program.num_instructions
        for k in t["kernels"])


@pytest.mark.parametrize("seconds,sweeps,exhausted", [(1e6, 3, 1), (0, 1, 0)],
                         ids=["pool-used-up-first", "seconds-reached-first"])
def test_the_window_ends_at_its_seconds_or_with_its_pool(
        pool_cell, monkeypatch, seconds, sweeps, exhausted):
    result, ctx, latencies, wall = _run_sweeps(pool_cell, monkeypatch,
                                               seconds)
    # Set-up's sweep and the window's, none at a repeated point.
    assert len(latencies) == sweeps + 1 == len(set(latencies))
    assert result["attempted"] == result["counts"]["sweeps"] == sweeps
    assert result["counts"]["pool_exhausted"] == exhausted
    window = ctx.details["window"]
    assert window["instructions"] == \
        sweeps * _instructions_per_sweep(pool_cell)
    assert 0 < window["seconds"] < wall
    assert result["end_to_end"]["sim_instr_per_s"] == \
        window["instructions"] / window["seconds"]
    assert all(v <= limit for _, v, limit in result["checks"]), \
        result["checks"]

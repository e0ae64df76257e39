"""Per-layer metrics from the program's spans, on synthetic span lists
and on the spans of a small CPU sweep."""

import types

import pytest

from harness import program_spans as ps


def span(name, start, end, id, parent=None, **attrs):
    return types.SimpleNamespace(name=name, start_ns=start, end_ns=end,
                                 id=id, parent=parent, attrs=attrs)


def two_sweeps():
    """Two sweeps: one with a compiling dispatch and a refine, one warm;
    plus a dispatch outside any sweep (a bare ``Session.grid`` call)."""
    return [
        span("session.run", 0, 10_000_000, 0),
        span("session.prepare", 0, 1_000_000, 1, 0),
        span("engine.dispatch", 1_000_000, 5_000_000, 2, 0, steps=1024,
             compiled=True),
        span("session.refine", 5_000_000, 8_000_000, 3, 0, programs=1),
        span("engine.dispatch", 5_000_000, 7_000_000, 4, 3, steps=2048,
             compiled=False),
        span("session.run", 20_000_000, 26_000_000, 5),
        span("engine.dispatch", 21_000_000, 24_000_000, 6, 5, steps=1024,
             compiled=False),
        span("engine.dispatch", 30_000_000, 39_000_000, 7, steps=1024,
             compiled=False),
    ]


def test_dispatch_us_per_step_leaves_compiling_dispatches_out():
    # Warm: 2 ms / 2048 + 3 ms / 1024 + 9 ms / 1024 -> 14 ms / 4096 steps.
    assert ps.dispatch_us_per_step(two_sweeps()) == \
        pytest.approx(14e6 / 4096 / 1e3)
    only_compiles = [span("engine.dispatch", 0, 5, 0, steps=8,
                          compiled=True)]
    assert ps.dispatch_us_per_step(only_compiles) is None


def test_run_host_ms_subtracts_only_the_dispatches_under_its_own_run():
    # Sweep 1: 10 ms - (4 ms + 2 ms nested in its refine) = 4 ms;
    # sweep 2: 6 ms - 3 ms = 3 ms; the bare dispatch belongs to neither.
    assert ps.run_host_ms(two_sweeps()) == pytest.approx(3.5)


def test_lower_ms_is_the_mean_lowering_wall():
    spans = [span("bridge.lower", 0, 2_000_000, 0, model="m"),
             span("bridge.lower", 5_000_000, 9_000_000, 1, model="m"),
             span("session.run", 10_000_000, 20_000_000, 2)]
    assert ps.lower_ms(spans) == pytest.approx(3.0)


@pytest.mark.parametrize("metric", [ps.dispatch_us_per_step, ps.run_host_ms,
                                    ps.lower_ms])
def test_no_spans_read_none(metric):
    assert metric([]) is None


def test_metrics_of_recorded_cpu_sweeps():
    """The program's own spans of small sweeps (CPU: structure only,
    never a device time)."""
    from repro import api, obs

    ses = api.Session(batch_programs=True)
    sweep = api.Sweep(kernels=("gemv",), capacity=(3,),
                      kernel_params="reduced")
    obs.reset()
    try:
        with obs.recording():
            ses.run(sweep)
            ses.run(sweep)                       # warm: nothing compiles
            api.Sweep(network=("phi3-mini-3.8b",))
        spans = ps.recorded()
    finally:
        obs.reset()
    assert ps.dispatch_us_per_step(spans) > 0
    assert ps.run_host_ms(spans) > 0
    assert ps.lower_ms(spans) > 0

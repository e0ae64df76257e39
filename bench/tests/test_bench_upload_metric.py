"""``engine.upload_mb_per_sweep``: bytes copied to the device per traced
sweep, on a synthetic window, on an engine that does not count its
copies, and on the spans of small CPU sweeps."""

import types

import pytest

from harness import program_spans, spec


def span(name, id, parent=None, **attrs):
    return types.SimpleNamespace(name=name, start_ns=0, end_ns=1, id=id,
                                 parent=parent, attrs=attrs)


def read(spans):
    reader = spec.metric_reader("engine.upload_mb_per_sweep")
    return reader.read(dict(program_spans=spans))


def test_the_reader_reads_a_synthetic_window():
    """Two traced sweeps: the first packs a group whose traces were not
    resident (6 MB) beside a resident one; the second copies lane
    parameters only (4 KB a dispatch)."""
    spans = [
        span("session.run", 0),
        span("engine.dispatch", 1, 0, resident=False,
             upload_bytes=6_000_000 + 4096),
        span("engine.stack", 2, 1, bytes=6_000_000),
        span("engine.dispatch", 3, 0, resident=True, upload_bytes=4096),
        span("session.run", 4),
        span("engine.dispatch", 5, 4, resident=True, upload_bytes=4096),
        span("engine.dispatch", 6, 4, resident=True, upload_bytes=4096),
    ]
    assert read(spans) == pytest.approx((6e6 + 4 * 4096) / 2 / 1e6)


def test_an_engine_that_does_not_count_its_copies_reads_none():
    """The engine before resident traces stacked every sweep's traces and
    recorded no ``upload_bytes``; with no spans there is nothing either."""
    parent = [
        span("session.run", 0),
        span("engine.stack", 1, 0, bucket=1024, programs=1, bytes=61440),
        span("engine.dispatch", 2, 0, steps=1024, compiled=False),
    ]
    assert read(parent) is None
    assert read([]) is None
    assert read([span("engine.dispatch", 0, upload_bytes=8)]) is None


def test_recorded_cpu_sweeps_copy_only_their_parameters_once_resident():
    """The program's own spans of two sweeps of one kernel (CPU: the XLA
    scan, whose parameters are the config and machine arrays)."""
    from repro import api, obs

    ses = api.Session(batch_programs=True)
    sweeps = [api.Sweep(kernels=("gemv",), capacity=(3,), mem_latency=(m,),
                        kernel_params="reduced") for m in (5, 9)]
    obs.reset()
    try:
        ses.run(sweeps[0])
        with obs.recording():
            ses.run(sweeps[1])
        spans = program_spans.recorded()
    finally:
        obs.reset()
    # capacity and policy (int32), alloc-no-fetch (bool), 3 latencies
    assert read(spans) == pytest.approx((4 + 4 + 1 + 3 * 4) / 1e6)

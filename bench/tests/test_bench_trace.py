"""The reduction from profiler trace to metrics, on synthetic traces."""

import pytest

from harness import peaks, trace


def test_union_merges_overlapping_and_touching_intervals():
    merged = trace.union([0, 5, 2, 20, 30], [3, 10, 6, 25, 30])
    assert merged.tolist() == [[0, 10], [20, 25], [30, 30]]


def test_busy_is_the_union_clipped_to_the_window():
    merged = trace.union([0, 2, 8], [5, 6, 12])       # [0, 6], [8, 12]
    assert trace.busy(merged, 0, 100) == 10
    assert trace.busy(merged, 4, 10) == 4             # [4, 6] + [8, 10]
    assert trace.busy(merged, 6, 8) == 0


def test_busy_never_exceeds_the_window_with_nested_ops():
    # Ops nested in others (a fusion inside a loop) count once.
    merged = trace.union([0, 1, 2, 3], [10, 9, 8, 7])
    assert trace.busy(merged, 0, 10) == 10


def test_gaps_are_the_complement_inside_the_window():
    merged = trace.union([10, 40], [20, 50])
    assert trace.gaps(merged, 0, 60) == [(0, 10), (20, 40), (50, 60)]
    assert trace.gaps(trace.union([], []), 0, 5) == [(0, 5)]


def test_idle_gaps_are_named_by_the_innermost_open_span():
    merged = trace.union([10, 40, 95], [20, 90, 100])
    spans = [("engine.sweep", 0, 100), ("engine.lower", 25, 35)]
    got = trace.idle_gaps(merged, 0, 100, spans, top=3)
    # Longest first: [20, 40] has its midpoint in engine.lower.
    assert got[0] == ["engine.lower", pytest.approx(20e-9)]
    assert [g[0] for g in got] == ["engine.lower", "engine.sweep",
                                   "engine.sweep"]
    assert trace.name_gap((200, 210), spans) == "outside spans"


def test_top_ops_sum_per_name_inside_the_window():
    got = trace.top_ops([0, 10, 20], [5, 15, 40], ["a", "b", "a"], 0, 30)
    assert got == [["a", pytest.approx(15e-9)], ["b", pytest.approx(5e-9)]]


def test_reduce_synthetic_trace():
    tr = trace.Trace(
        devices={"/device:TPU:0": [("XLA Ops", 100, 150, "scan"),
                                   ("XLA Ops", 300, 50, "fusion"),
                                   ("Async copies", 120, 20, "copy")]},
        spans=[("window", 100, 500), ("serve.step", 100, 360),
               ("serve.admit", 360, 500)],
        line_names={})
    red = trace.reduce(tr)
    assert red.window_ns == 400 and red.busy_ns == 200
    assert red.idle_gaps[0][0] == "serve.admit"
    assert [n for n, _ in red.device_ops] == ["scan", "fusion"]
    assert trace.span_busy(red.merged, red.spans, "serve.step") == \
        [(260, 200.0)]


def test_peaks_known_and_unknown_device_kinds():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")


def test_events_fall_back_to_the_module_line_past_the_cap():
    from types import SimpleNamespace as NS
    ev = lambda t, d, n: NS(start_ns=t, duration_ns=d, name=n)
    lines = [NS(name="XLA Modules", events=[ev(0, 100, "jit_step")]),
             NS(name="XLA Ops", events=[ev(i, 1, "fusion")
                                        for i in range(0, 100, 10)])]
    ops = lambda n: n not in trace._SUMMARY_LINES
    assert len(trace._events(lines, ops, cap=20)) == 10
    assert trace._events(lines, ops, cap=5) is None
    assert trace._events(lines, lambda n: n == "XLA Modules") == \
        [("XLA Modules", 0, 100, "jit_step")]

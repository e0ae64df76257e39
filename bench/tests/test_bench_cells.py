"""Cells, configurations, traffic mixes and metric readers are found by
name, and a new cell needs new files only."""

import json
import re
import shutil

import pytest

from conftest import ROOT
from harness import spec

BENCH_JSON = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", [w["name"] for w in BENCH_JSON["workloads"]])
def test_every_cell_loads_by_name(name):
    cell = spec.cell(name)
    assert cell.config["name"] == cell.config_name
    assert (ROOT / "bench" / "drivers" / f"{cell.driver}.py").is_file()
    assert (ROOT / "bench" / cell.config["reference"]).is_file()
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH_JSON["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(spec.metric_reader(metric).read)


def test_names_units_and_sizes_keep_the_format():
    for entry in (BENCH_JSON["configs"] + BENCH_JSON["workloads"]
                  + BENCH_JSON["end_to_end"] + BENCH_JSON["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
    for m in BENCH_JSON["end_to_end"] + BENCH_JSON["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH_JSON["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_an_added_cell_is_found_by_name(tmp_path):
    """A new traffic mix and cell are files plus entries; nothing that
    exists changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    bench = dict(BENCH_JSON)
    traffic = json.loads(
        (ROOT / "bench" / "workloads" / "capacity-sweep.json").read_text())
    traffic["kernels"] = ["gemv", "dropout"]
    (tmp_path / "bench" / "workloads" / "two-kernels.json").write_text(
        json.dumps(traffic))
    bench["workloads"] = bench["workloads"] + [dict(
        name="table2.two-kernels", config="paper-table2",
        traffic="two-kernels", chips=1, why="test")]
    bench["end_to_end"] = [dict(m) for m in bench["end_to_end"]]
    for m in bench["end_to_end"]:
        if m["name"] == "sim_instr_per_s":
            m["workloads"] = m["workloads"] + ["table2.two-kernels"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.cell("table2.two-kernels", root=tmp_path)
    assert cell.traffic["kernels"] == ["gemv", "dropout"]
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "sim_instr_per_s"}
    assert spec.driver(cell).__name__ == "bench_engine_sweep"
    with pytest.raises(KeyError):
        spec.cell("table2.missing", root=tmp_path)


# Readers of the chat cell, which waits for chip readings before it joins
# BENCHMARK.json (PERF.md, open questions).
WAITING_FOR_CHAT = ("serve.decode_hbm_share", "serve.decode_mfu",
                    "serve.decode_step_ms", "serve.device_idle_share",
                    "serve.host_ms_per_step")


def test_every_reader_reads_a_synthetic_window():
    """Every reader under bench/metrics, in the cells or waiting for one,
    on a synthetic traced window and the program's spans of two sweeps:
    shares stay within 0-100 %, span metrics read their exact values."""
    import types

    import numpy as np

    from harness import peaks, trace

    def span(name, start, end, id, parent=None, **attrs):
        return types.SimpleNamespace(name=name, start_ns=start, end_ns=end,
                                     id=id, parent=parent, attrs=attrs)

    spans = [("window", 0, 1e9), ("engine.sweep", 0, 6e8),
             ("serve.step", 0, 5e8), ("serve.step", 5e8, 1e9)]
    red = trace.Reduced(window_ns=1e9, busy_ns=8e8,
                        merged=trace.union([0, 5e8], [4e8, 9e8]),
                        spans=spans, device_ops=[], idle_gaps=[])
    # Sweep 1 compiles its first dispatch (left out of the step time);
    # sweep 2 lowers a model before its run.
    program = [
        span("session.run", 0, 10_000_000, 0),
        span("engine.dispatch", 1_000_000, 5_000_000, 1, 0, steps=1024,
             compiled=True),
        span("engine.dispatch", 5_000_000, 7_000_000, 2, 0, steps=2048,
             compiled=False),
        span("bridge.lower", 20_000_000, 20_500_000, 3, model="m"),
        span("session.run", 21_000_000, 27_000_000, 4),
        span("engine.dispatch", 22_000_000, 25_000_000, 5, 4, steps=1024,
             compiled=False),
    ]
    cfg = json.loads((ROOT / "bench" / "configs" /
                      "phi3-mini-3.8b.json").read_text())
    rec = dict(trace=red, program_spans=program, config=cfg, traffic={},
               chips=1, peaks=peaks.peaks("TPU v5 lite"),
               counts=dict(scan_steps=1000, padded_rows=4096, rows=3000,
                           traced_steps=2, traced_tokens_fed=8, slots=4,
                           max_len=1024))
    names = sorted(p.stem for p in (ROOT / "bench" / "metrics").glob("*.py"))
    assert names == sorted({m["name"] for m in BENCH_JSON["per_layer"]}
                           | set(WAITING_FOR_CHAT))
    values = {}
    for name in names:
        value = values[name] = spec.metric_reader(name).read(rec)
        assert value is not None and np.isfinite(value), name
        if name.endswith(("share", "mfu")):
            assert 0 <= value <= 100, (name, value)
    # Warm dispatches: (2 ms + 3 ms) over 3072 steps.
    assert values["engine.dispatch_us_per_step"] == \
        pytest.approx(5e6 / 3072 / 1e3)
    # Sweep 1: 10 ms - 4 ms - 2 ms; sweep 2: 6 ms - 3 ms.
    assert values["engine.run_host_ms"] == pytest.approx(3.5)
    assert values["engine.lower_ms"] == pytest.approx(0.5)
    assert values["engine.scan_useful_share"] == pytest.approx(3000 / 4096
                                                               * 100)

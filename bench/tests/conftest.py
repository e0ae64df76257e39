"""Shared set-up for the benchmark's CPU tests: the harness and the
program's sources on the path, and small in-memory cells."""

import copy
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (BENCH, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def run_small(cell, *, seed=7, seconds=1.0, control=False, trace=False,
              tmp_path=None):
    """One run of a cell on the CPU, the device check skipped."""
    import time

    from harness import runner, spans
    ctx = runner.Context(
        cell=cell, seed=seed, seconds=seconds, trace=trace,
        spans=spans.Spans(annotate=trace),
        compiles=spans.Compiles().install(),
        trace_dir=(tmp_path or pathlib.Path("/nonexistent")) / "trace",
        started=time.perf_counter(), control=control)
    line = runner.run_cell(ctx, dict(platform="cpu", kind="cpu", count=1))
    return line, ctx


@pytest.fixture
def small_engine_cell():
    """table2.capacity-sweep at the kernels' reduced sizes."""
    from harness import spec
    cell = copy.deepcopy(spec.cell("table2.capacity-sweep"))
    cell.traffic.update(kernel_params="reduced", capacity=[3, 8, 32])
    cell.traffic["limits"]["extrapolated_cycles_rel_err"] = 0.015
    return cell


def chat_cell():
    """The chat-decode traffic on phi3-mini, built from its files (the
    cell waits for chip readings before it joins BENCHMARK.json)."""
    import json

    from harness import spec
    read = lambda *p: json.loads(BENCH.joinpath(*p).read_text())
    return spec.Cell(name="phi3-mini.chat-decode", chips=1,
                     config_name="phi3-mini-3.8b",
                     config=read("configs", "phi3-mini-3.8b.json"),
                     traffic_name="chat-decode",
                     traffic=read("workloads", "chat-decode.json"),
                     end_to_end=[], per_layer=[], root=ROOT)


@pytest.fixture
def small_decode_cell():
    """The chat cell with a tiny decoder of the same layout."""
    cell = chat_cell()
    cell.config.update(hidden_size=64, intermediate_size=128,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=4, vocab_size=256,
                       max_position_embeddings=48)
    cell.traffic["schedule"] = [[p % 16 + 4, o % 12 + 4]
                                for p, o in cell.traffic["schedule"]]
    # The tiny decoder's own readings (CPU, seeds 1-8, 8 requests checked,
    # 42-49 served tokens): the bf16 program's widest gap 0.000-0.029, the
    # fp8 control's 0.160-0.313; the limit lies between them.
    cell.traffic["check_requests"] = 8
    cell.traffic["limits"]["served_token_gap"] = 0.08
    return cell

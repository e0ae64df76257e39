"""Harness robustness: every benchmark suite's run() yields sane rows at
reduced event budgets (keeps the paper tables regenerable)."""

import pytest

# Fast-tier kernel subset: skips the two expensive paper-size trace builds
# (resnet50_l10, flashattention2 — both covered at reduced size by the
# conformance matrix and at paper size by `make bench` / the slow tier).
NAMES = ("pathfinder", "jacobi2d", "somier", "gemv", "dropout",
         "conv2d_7x7", "densenet121_l105")


@pytest.mark.parametrize("mod,kw", [
    ("benchmarks.table3_speedup", {"max_events": 12_000, "names": NAMES}),
    ("benchmarks.fig4_cvrf_sweep", {"names": ["dropout"],
                                    "max_events": 12_000}),
    ("benchmarks.fig5_min_regs", {"max_events": 12_000, "names": NAMES}),
    ("benchmarks.fig6_equal_area", {"max_events": 12_000, "names": NAMES}),
    ("benchmarks.fig2_area_model", {}),
    ("benchmarks.fig8_power", {"max_events": 12_000, "names": NAMES}),
    ("benchmarks.pareto_frontier", {"max_events": 12_000,
                                    "names": ["dropout", "gemv"]}),
    ("benchmarks.vmem_dispersion", {"interpret": True}),
    ("benchmarks.kv_dispersion", {"steps": 150}),
    ("benchmarks.network_sweep", {"models": ("granite-8b",), "caps": (4, 8),
                                  "l1_kbytes": (4,), "max_events": 120}),
    ("benchmarks.cluster_sweep", {"names": ("dropout",), "cores": (1, 2),
                                  "caps": (4,), "l1_kbytes": (4,),
                                  "max_events": 4000}),
    # The machine-latency grid is traced (no per-machine rebuilds), but the
    # fast suite already exercises this run in tests/test_machine_grid.py,
    # so the harness duplicate stays out of the default selection.
    pytest.param("benchmarks.ablation_sensitivity", {"max_events": 20_000},
                 marks=pytest.mark.slow),
])
def test_suite_produces_rows(mod, kw):
    m = __import__(mod, fromlist=["run"])
    rows = m.run(**kw)
    assert len(rows) > 0
    for r in rows:
        assert "name" in r


@pytest.fixture
def no_repo_compile_cache(monkeypatch, tmp_path):
    """``run.main`` turns on the persistent compile cache; with the
    variable set it leaves the process's setting alone, so a test never
    writes compiled programs into the checkout."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def test_run_json_schema(tmp_path, no_repo_compile_cache):
    """The front door's --json report: schema 7, --kernels subsetting, the
    metric-registry catalog (incl. the macro-model catalog), and per-sweep
    derived-metric metadata."""
    import json

    from benchmarks import run as runner
    out = tmp_path / "bench.json"
    rc = runner.main(["--json", str(out), "--kernels", "dropout",
                      "--max-events", "12000", "fig2", "fig6"])
    assert rc == 0
    rep = json.loads(out.read_text())
    assert rep["schema"] == 7
    assert set(rep["macro_models"]) >= {"flop", "sram6t", "table"}
    assert rep["metrics"]["silicon_area"]["kind"] == "model"
    assert rep["metrics"]["speedup"]["kind"] == "relational"
    assert rep["metrics"]["application_power"]["kind"] == "model"
    fig6 = rep["suites"]["fig6"]
    assert fig6["rows"] == 1                      # --kernels took effect
    derived = [d["metric"] for s in fig6["sweeps"] for d in s["derived"]]
    assert "equal_area_advantage" in derived and "speedup" in derived
    assert runner.main(["nope"]) == 2


def test_roofline_report_over_results():
    """The measured roofline needs no results/dryrun sweep: the smoke grid
    must emit >0 rows, each cross-checked against hbm_traffic_model."""
    import benchmarks.roofline as rl
    gemm, flash, rows = rl.run_measured(smoke=True, interpret=True)
    assert len(rows) > 0
    for r in rows:
        assert r["model_agree"] is True          # counted == closed form
        assert r["counted_bytes"] > 0 and r["model_bytes"] > 0
        assert r["us_per_call"] > 0
    # W and precision are labeled SweepResult axes with registry metrics
    assert [a.name for a in gemm.axes] == ["case", "working_set",
                                           "precision"]
    assert 0 in gemm.axis("working_set").values  # the dispersed extreme
    for grid in (gemm, flash):
        assert "arithmetic_intensity" in grid.data
        assert "achieved_gflops" in grid.data
    extra = rl.json_extra()
    assert len(extra["rows"]) == len(rows)
    stats = rl.perf_stats()
    assert stats["dispatches"] > 0 and stats["compiles"] > 0


def test_roofline_json_extra_schema_guard(tmp_path, no_repo_compile_cache):
    """The regression this PR fixes: the front door must never again record
    a silent 0-row roofline.  Runs the suite through run.py --json and
    pins rows/dispatches > 0 plus the measured/model row schema."""
    import json

    from benchmarks import run as runner
    out = tmp_path / "roofline.json"
    rc = runner.main(["--json", str(out), "--max-events", "120",
                      "--interpret", "roofline"])
    assert rc == 0
    rep = json.loads(out.read_text())["suites"]["roofline"]
    assert rep["rows"] > 0
    assert rep["dispatches"] > 0
    for row in rep["extra"]["rows"]:
        for key in ("us_per_call", "counted_bytes", "model_bytes",
                    "model_agree", "working_set", "precision"):
            assert key in row, key
    assert set(rep["extra"]["axes"]) == {"case", "working_set", "precision"}


def test_roofline_int8_precision_point():
    """int8 is a first-class roofline precision: operands stream at one
    byte per element (the model halves again from bf16) while the f32
    accumulator terms stay fixed, and counted == model still holds on the
    measured point."""
    import benchmarks.roofline as rl
    assert "int8" in rl.PRECISIONS
    assert rl._BYTES["int8"] == 1
    p8 = rl._gemm_point("g", 128, 256, 128, 1, "int8", block_m=64,
                        block_k=128, interpret=True, repeats=1)
    p16 = rl._gemm_point("g", 128, 256, 128, 1, "bf16", block_m=64,
                         block_k=128, interpret=True, repeats=1)
    assert p8["model_agree"] is True and p16["model_agree"] is True
    # grouped W>=1 traffic is pure operand streaming: exactly bpe-linear
    assert p8["model_bytes"] * 2 == p16["model_bytes"]
    assert p8["name"].endswith("_int8")
    """The legacy dry-run table: warns (instead of silently emitting
    nothing) when results/dryrun is absent; load_cells reports corrupt
    cells instead of swallowing them."""
    import os
    import benchmarks.roofline as rl
    if not os.path.isdir(rl.RESULTS):
        with pytest.warns(UserWarning, match="dry-run sweep"):
            assert rl.run("single") == []
    else:
        rows = rl.run("single")
        ok = [r for r in rows if r.get("status") == "ok"]
        for r in ok:
            assert r["bottleneck"] in ("compute", "memory", "collective")


def test_roofline_load_cells_reports_corrupt_files(tmp_path, monkeypatch):
    import benchmarks.roofline as rl
    good = [dict(arch="a", shape="s", status="skip")]
    (tmp_path / "a_single.json").write_text("{corrupt")
    import json
    (tmp_path / "b_single.json").write_text(json.dumps(good))
    monkeypatch.setattr(rl, "RESULTS", str(tmp_path))
    with pytest.warns(UserWarning, match="skipped 1 unreadable"):
        cells = rl.load_cells("single")
    assert cells == good

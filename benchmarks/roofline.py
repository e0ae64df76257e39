"""Measured roofline over the Pallas kernels (+ the legacy dry-run table).

The suite times the three dispersed-accumulator schedules —
``matmul_grouped`` (working set W >= 1), ``matmul_dispersed`` (the W=0
spill/fill extreme) and ``flash_attention`` — compiled on the TPU, or in
the Pallas interpreter when the caller passes ``interpret=True`` (the CPU
smoke path) — and cross-checks every point against the closed-form
``hbm_traffic_model`` bytes: the instrumented traffic count
(:mod:`repro.kernels.traffic`, walking the schedule's actual BlockSpec
index maps) must agree with the model, and each row carries both
arithmetic-intensity columns plus a per-row ``model_agree`` flag.

The accumulator working set ``W`` and the input precision (f32 / bf16 /
int8, SPEED's multi-precision angle — int8 streams operands at one byte
per element while the accumulators stay f32) are first-class labeled
axes: rows are
assembled through :meth:`repro.api.SweepResult.from_table`, so the
``derive`` / ``normalize`` / ``pareto`` machinery applies — the suite
derives ``arithmetic_intensity`` / ``achieved_gflops`` from the metric
registry, normalizes time against the W=0 extreme, and reports the
VMEM-footprint-vs-time Pareto front per shape.  An equal-VMEM study
mirrors fig6: at a fixed VMEM accumulator budget, which (W, block_m,
block_k) point wins.

``run(mesh=...)`` keeps the legacy dry-run table (``results/dryrun/*.json``
from the launch sweep) but now *warns* when the sweep is absent instead of
silently emitting nothing; ``load_cells`` reports unreadable cell files.
``json_extra()`` exports the per-point measured/model rows for
``run.py --json`` (schema >= 4) and ``perf_stats()`` its Pallas
compile/dispatch counts, so ``BENCH_core.json`` can never again record a
silent ``{"rows": 0}``.
"""

from __future__ import annotations

import glob
import json
import os
import time
import warnings

import jax
import jax.numpy as jnp

from benchmarks import common
from repro import api
from repro.configs import SHAPES, get
from repro.kernels import dispersed_gemm, flash_attention, traffic
from repro.launch import analytic

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")

# (m, k, n) GEMM cases and (b, h, s, d) attention cases, sized so the
# interpret-mode sweep stays CPU-affordable; on the TPU the same axes time
# the compiled kernels.
GEMM_CASES = {"gemm_256x512x256": (256, 512, 256),
              "gemm_512x512x256": (512, 512, 256)}
FLASH_CASES = {"attn_b1h2_s256_d64": (1, 2, 256, 64)}
W_AXIS = (0, 1, 2, 4)                  # 0 = the dispersed (spill/fill) extreme
PRECISIONS = ("f32", "bf16", "int8")
BLOCK_M, BLOCK_K = 64, 128
FLASH_BLOCK = 64

SMOKE_GEMM_CASES = {"gemm_128x256x128": (128, 256, 128)}
SMOKE_FLASH_CASES = {"attn_b1h1_s128_d64": (1, 1, 128, 64)}
SMOKE_W_AXIS = (0, 1, 2)

# Counted-vs-model agreement: both sides are exact byte counts, so the
# tolerance only absorbs float round-off in the ratio.
AGREE_RTOL = 0.01

_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}
_BYTES = {"f32": 4, "bf16": 2, "int8": 1}

_LAST_EXTRA: dict = {}
_STATS = {"compiles": 0, "dispatches": 0}
_SEEN_SIGNATURES: set = set()


def _measure(fn, signature, repeats: int) -> float:
    """Median wall-clock us per call (one warm-up, ``repeats`` timed).
    Tracks Pallas compiles (first sighting of a jit signature) and
    dispatches for ``perf_stats()``."""
    if signature not in _SEEN_SIGNATURES:
        _SEEN_SIGNATURES.add(signature)
        _STATS["compiles"] += 1
    fn().block_until_ready()                      # warm-up / compile
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn().block_until_ready()
        times.append(time.perf_counter() - t0)
    _STATS["dispatches"] += repeats + 1
    times.sort()
    return times[len(times) // 2] * 1e6


def _gemm_point(case, m, k, n, w, prec, *, block_m, block_k, interpret,
                repeats) -> dict:
    dtype, bpe = _DTYPES[prec], _BYTES[prec]
    a = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    a, b = a.astype(dtype), b.astype(dtype)
    model = dispersed_gemm.hbm_traffic_model(
        m, n, k, block_m=block_m, block_k=block_k,
        working_set=max(w, 1), bytes_per_el=bpe)
    if w == 0:
        fn = lambda: dispersed_gemm.matmul_dispersed(
            a, b, block_m=block_m, block_k=block_k, interpret=interpret)
        schedule = dispersed_gemm.dispersed_schedule(
            m, n, k, block_m=block_m, block_k=block_k, bytes_per_el=bpe)
        model_bytes, vmem_acc = model["dispersed"], 0
        name = f"{case}_dispersed_{prec}"
    else:
        fn = lambda: dispersed_gemm.matmul_grouped(
            a, b, block_m=block_m, block_k=block_k, working_set=w,
            interpret=interpret)
        schedule = dispersed_gemm.grouped_schedule(
            m, n, k, block_m=block_m, block_k=block_k, working_set=w,
            bytes_per_el=bpe)
        model_bytes, vmem_acc = model["grouped"], model["vmem_acc_bytes"]
        name = f"{case}_W{w}_{prec}"
    counted = traffic.count(schedule)["total"]
    us = _measure(fn, ("gemm", m, k, n, w, block_m, block_k, prec),
                  repeats)
    return dict(
        name=name, case=case, kernel="gemm", working_set=w, precision=prec,
        block_m=block_m, block_k=block_k, us_per_call=round(us, 1),
        flops=2 * m * n * k, counted_bytes=counted, model_bytes=model_bytes,
        model_agree=abs(counted - model_bytes) <= AGREE_RTOL * model_bytes,
        vmem_acc_bytes=vmem_acc)


def _flash_point(case, b, h, s, d, prec, *, interpret, repeats) -> dict:
    dtype, bpe = _DTYPES[prec], _BYTES[prec]
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.float32).astype(dtype)
               for kk in keys)
    model = flash_attention.hbm_traffic_model(
        b, h, s, s, d, block_q=FLASH_BLOCK, block_k=FLASH_BLOCK,
        bytes_per_el=bpe)
    counted = traffic.count(flash_attention.flash_schedule(
        b, h, s, s, d, block_q=FLASH_BLOCK, block_k=FLASH_BLOCK,
        bytes_per_el=bpe))["total"]
    fn = lambda: flash_attention.flash_attention(
        q, k, v, block_q=FLASH_BLOCK, block_k=FLASH_BLOCK,
        interpret=interpret)
    us = _measure(fn, ("flash", b, h, s, d, prec), repeats)
    return dict(
        name=f"{case}_{prec}", case=case, kernel="flash",
        working_set=1, precision=prec, block_m=FLASH_BLOCK,
        block_k=FLASH_BLOCK, us_per_call=round(us, 1),
        flops=4 * b * h * s * s * d, counted_bytes=counted,
        model_bytes=model["flash"],
        model_agree=abs(counted - model["flash"])
        <= AGREE_RTOL * model["flash"],
        vmem_acc_bytes=model["vmem_acc_bytes"])


def _grid_fields(rows):
    keep = ("us_per_call", "flops", "counted_bytes", "model_bytes",
            "model_agree", "vmem_acc_bytes")
    return [{k: r[k] for k in
             ("case", "working_set", "precision") + keep} for r in rows]


def equal_vmem_points(m: int) -> list[tuple[int, int, int]]:
    """fig6 mirrored at VMEM granularity: (W, block_m, block_k) points
    with the same accumulator footprint W*block_m*n*4 — more, smaller
    registers vs fewer, taller ones at equal area."""
    pts = [(4, 64, 128), (2, 128, 128), (1, 256, 64)]
    return [(w, bm, bk) for (w, bm, bk) in pts
            if m % bm == 0 and (m // bm) % w == 0]


def run_measured(smoke: bool = False, repeats: int = 3,
                 interpret: bool = False):
    """Execute the measured suite (``interpret=True`` runs the kernels in
    the Pallas interpreter, for the CPU backend).

    Returns ``(gemm_result, flash_result, rows)``: two labeled
    :class:`repro.api.SweepResult` grids (axes ``case`` x ``working_set``
    x ``precision`` and ``case`` x ``precision``) with the registry
    metrics derived, plus the flat row list (including the equal-VMEM
    study rows, which vary ``block_m``/``block_k`` off the main grid).
    """
    gemm_cases = SMOKE_GEMM_CASES if smoke else GEMM_CASES
    flash_cases = SMOKE_FLASH_CASES if smoke else FLASH_CASES
    w_axis = SMOKE_W_AXIS if smoke else W_AXIS
    precisions = ("f32",) if smoke else PRECISIONS
    repeats = 1 if smoke else repeats

    rows = []
    for case, (m, k, n) in gemm_cases.items():
        for w in w_axis:
            for prec in precisions:
                rows.append(_gemm_point(
                    case, m, k, n, w, prec, block_m=BLOCK_M,
                    block_k=BLOCK_K, interpret=interpret, repeats=repeats))
    gemm_result = api.SweepResult.from_table(
        dict(case=tuple(gemm_cases), working_set=w_axis,
             precision=precisions),
        _grid_fields(rows),
        values=["us_per_call", "flops", "counted_bytes", "model_bytes",
                "model_agree", "vmem_acc_bytes"])
    gemm_result = (gemm_result.derive("arithmetic_intensity")
                   .derive("model_arithmetic_intensity")
                   .derive("achieved_gflops"))
    # time normalized to the W=0 spill/fill extreme: > 1 means the compact
    # working set pays off (Fig 4's economics, measured)
    rel = gemm_result.normalize("us_per_call",
                                baseline=dict(working_set=0))
    for r in rows:
        r["speedup_vs_dispersed"] = round(
            1.0 / rel.value("us_per_call", case=r["case"],
                            working_set=r["working_set"],
                            precision=r["precision"]), 3)
        r["ai_measured"] = round(gemm_result.value(
            "arithmetic_intensity", case=r["case"],
            working_set=r["working_set"], precision=r["precision"]), 2)
        r["ai_model"] = round(gemm_result.value(
            "model_arithmetic_intensity", case=r["case"],
            working_set=r["working_set"], precision=r["precision"]), 2)

    flash_rows = []
    for case, (b, h, s, d) in flash_cases.items():
        for prec in precisions:
            flash_rows.append(_flash_point(
                case, b, h, s, d, prec, interpret=interpret,
                repeats=repeats))
    flash_result = api.SweepResult.from_table(
        dict(case=tuple(flash_cases), precision=precisions),
        [{k: r[k] for k in ("case", "precision", "us_per_call", "flops",
                            "counted_bytes", "model_bytes", "model_agree",
                            "vmem_acc_bytes")} for r in flash_rows],
        values=["us_per_call", "flops", "counted_bytes", "model_bytes",
                "model_agree", "vmem_acc_bytes"])
    flash_result = (flash_result.derive("arithmetic_intensity")
                    .derive("model_arithmetic_intensity")
                    .derive("achieved_gflops"))
    for r in flash_rows:
        r["speedup_vs_dispersed"] = ""
        r["ai_measured"] = round(flash_result.value(
            "arithmetic_intensity", case=r["case"],
            precision=r["precision"]), 2)
        r["ai_model"] = round(flash_result.value(
            "model_arithmetic_intensity", case=r["case"],
            precision=r["precision"]), 2)
    rows += flash_rows

    # equal-VMEM study (fig6 at VMEM granularity): fixed accumulator
    # budget, which (W, block_m, block_k) schedule wins?
    equal_vmem = []
    if not smoke:
        for case, (m, k, n) in gemm_cases.items():
            pts = []
            for w, bm, bk in equal_vmem_points(m):
                p = _gemm_point(case, m, k, n, w, "f32", block_m=bm,
                                block_k=bk, interpret=interpret,
                                repeats=repeats)
                p["name"] = f"eqvmem_{case}_W{w}_bm{bm}_bk{bk}"
                p["speedup_vs_dispersed"] = ""
                p["ai_measured"] = round(
                    p["flops"] / p["counted_bytes"], 2)
                p["ai_model"] = round(p["flops"] / p["model_bytes"], 2)
                pts.append(p)
            if not pts:
                continue
            budgets = {p["vmem_acc_bytes"] for p in pts}
            measured_win = min(pts, key=lambda p: p["us_per_call"])
            # Equal budget => equal groups => the closed form often
            # predicts a byte tie; measured timing breaks it.
            best_bytes = min(p["model_bytes"] for p in pts)
            model_wins = [p["name"] for p in pts
                          if p["model_bytes"] == best_bytes]
            equal_vmem.append(dict(
                case=case, vmem_budget_bytes=sorted(budgets),
                points=[dict(working_set=p["working_set"],
                             block_m=p["block_m"], block_k=p["block_k"],
                             us_per_call=p["us_per_call"],
                             model_bytes=p["model_bytes"]) for p in pts],
                measured_winner=measured_win["name"],
                model_winner=(model_wins[0] if len(model_wins) == 1
                              else "tie(" + ", ".join(model_wins) + ")")))
            rows += pts

    global _LAST_EXTRA
    _LAST_EXTRA = dict(
        rows=[{k: (v if not isinstance(v, bool) else bool(v))
               for k, v in r.items()} for r in rows],
        equal_vmem=equal_vmem,
        pareto={case: gemm_result.pareto(
            "vmem_acc_bytes", "us_per_call", case=case, precision=prec)
            for case in gemm_cases for prec in precisions[:1]},
        axes=dict(case=list(gemm_cases) + list(flash_cases),
                  working_set=list(w_axis), precision=list(precisions)),
        interpret=interpret,
    )
    return gemm_result, flash_result, rows


# ---------------------------------------------------------------------------
# Legacy dry-run table (results/dryrun/*.json from the launch sweep).
# ---------------------------------------------------------------------------


def load_cells(mesh: str = "single") -> list[dict]:
    """Load the dry-run sweep cells; unreadable/corrupt files are counted
    and reported (a warning naming each file) instead of silently
    dropped."""
    cells, skipped = [], []
    for f in sorted(glob.glob(os.path.join(RESULTS, f"*_{mesh}.json"))):
        try:
            with open(f) as fh:
                cells.extend(json.load(fh))
        except Exception as e:
            skipped.append(f"{os.path.basename(f)} ({e})")
    if skipped:
        warnings.warn(
            f"load_cells: skipped {len(skipped)} unreadable dry-run cell "
            f"file(s): {'; '.join(skipped)}", stacklevel=2)
    return cells


def run(mesh: str = "single") -> list[dict]:
    """The dry-run-cells roofline table (unchanged schema).  Warns — loudly
    but non-fatally — when the ``results/dryrun`` sweep has never been
    generated, instead of silently emitting an empty table."""
    if not os.path.isdir(RESULTS):
        warnings.warn(
            f"no dry-run sweep at {os.path.normpath(RESULTS)}; the "
            f"dry-run roofline table is empty (the *measured* Pallas "
            f"roofline via main()/run_measured() does not need it)",
            stacklevel=2)
        return []
    rows = []
    for cell in load_cells(mesh):
        name = f"{cell['arch']}/{cell['shape']}"
        if cell["status"] == "skip":
            rows.append(dict(name=name, us_per_call=0.0, status="skip"))
            continue
        if cell["status"] != "ok":
            rows.append(dict(name=name, us_per_call=0.0, status="error"))
            continue
        cfg = get(cell["arch"])
        shape = SHAPES[cell["shape"]]
        t = analytic.roofline_terms(cell, cfg, shape)
        rows.append(dict(
            name=name, us_per_call=0.0, status="ok",
            t_compute_ms=round(t["t_compute"] * 1e3, 3),
            t_memory_ms=round(t["t_memory"] * 1e3, 3),
            t_mem_ub_ms=round(t["t_memory_opbytes_ub"] * 1e3, 3),
            t_collective_ms=round(t["t_collective"] * 1e3, 3),
            bottleneck=t["bottleneck"],
            roofline_frac=round(t["roofline_fraction"], 3),
            useful_flop_ratio=round(t["useful_flop_ratio"], 3),
            mem_gb_per_dev=round(cell.get("bytes_per_device", 0) / 1e9, 2),
            fits_16g=cell.get("fits_16g", ""),
            compile_s=round(cell.get("compile_s", 0), 1),
        ))
    return rows


# ---------------------------------------------------------------------------
# Front door.
# ---------------------------------------------------------------------------

_HEADER = ["name", "us_per_call", "working_set", "precision",
           "speedup_vs_dispersed", "ai_measured", "ai_model", "model_agree",
           "counted_bytes", "model_bytes", "vmem_acc_bytes"]


def main(max_events: int | None = None,
         interpret: bool = False) -> list[dict]:
    smoke = max_events is not None and max_events <= 5000
    _, _, rows = run_measured(smoke=smoke, interpret=interpret)
    common.emit(rows, _HEADER)
    for study in _LAST_EXTRA.get("equal_vmem", ()):
        print(f"# equal-VMEM {study['case']}: measured winner "
              f"{study['measured_winner']}, model winner "
              f"{study['model_winner']}")
    if os.path.isdir(RESULTS):
        print("# legacy dry-run table (results/dryrun)")
        dr = run("single")
        common.emit(dr, [
            "name", "us_per_call", "status", "t_compute_ms", "t_memory_ms",
            "t_mem_ub_ms", "t_collective_ms", "bottleneck", "roofline_frac",
            "useful_flop_ratio", "mem_gb_per_dev", "fits_16g", "compile_s"])
    return rows


def json_extra() -> dict:
    """Per-point measured/model rows, the equal-VMEM winners and the
    footprint-vs-time Pareto fronts, for ``run.py --json`` (schema >= 4)."""
    return _LAST_EXTRA


def perf_stats() -> dict:
    """Pallas-side compile/dispatch counts for the run.py suite record
    (the simulator counters never see these kernels)."""
    return dict(_STATS)


if __name__ == "__main__":
    main()

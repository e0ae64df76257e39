"""Benchmark harness: one function per paper table/figure (+ beyond-paper
studies).  Prints ``name,derived...`` CSV blocks per benchmark.

  python -m benchmarks.run                       # everything
  python -m benchmarks.run table3 fig4           # subset
  python -m benchmarks.run --json BENCH_core.json fig4 table3
  python -m benchmarks.run --kernels dropout,gemv --json BENCH_smoke.json

``--kernels a,b`` restricts every suite whose ``main()`` takes a kernel
list (table3/fig4/fig5/fig6/fig8/pareto) to that subset; fixed-roster
studies (fig2, policy_headroom, ablation_sensitivity, ...) run their own
set and say so.  ``make bench-smoke`` uses it to guard the JSON schema
cheaply.  ``--max-events N`` forwards the legacy truncation budget the
same way, and ``--interpret`` runs the Pallas suites (roofline,
vmem_dispersion) in the Pallas interpreter — needed on the CPU backend,
where the kernels cannot compile.

``--json PATH`` writes a versioned report (``schema: 7``): per-suite
wall-clock, XLA compile AND dispatch counts (the fused engine compiles once
per (program-shape bucket, L1 geometry) — machine-latency grids are traced,
so they add rows, not compiles), the sweep-axis metadata of every
``repro.api`` sweep the suite ran *including the metrics it derived*
(name, kind, baseline, params), the full ``repro.metrics`` registry
catalog, per-kernel cycle counts (the perf trajectory record for this
machine), and — schema 4 — any per-suite ``json_extra()`` payload (the
serving SLO suite exports its footprint-vs-latency Pareto fronts there;
the roofline suite its per-point measured/model rows and equal-VMEM
winners).  Suites exposing ``perf_stats()`` add their own Pallas
compile/dispatch counts to the suite record.  Schema 5 adds the
``network_sweep`` suite: whole registry models lowered through
``repro.bridge``, with per-model footprint/cycles/energy rows and the
lowered-network summaries (kernels, units, instances) in its ``extra``
payload, plus ``networks`` on any sweep meta that used the ``network``
axis.  Schema 6 adds the ``cluster_sweep`` suite (``repro.cluster``:
N lockstep dispersion cores behind a shared L2 + banked memory channels,
one compile per (bucket, geometry, cores) plan group) with per-point
cluster counters and iso-SRAM-budget / iso-area Pareto fronts in its
``extra`` payload.  Schema 7 adds the ``dse`` suite
(:mod:`repro.silicon`: pluggable SRAM macro models pricing one capacity
x L1 x cores grid per silicon backend, 3-objective area/cycles/energy
fronts with per-point provenance, the arXiv:2410.08396 reduced-register
RVV design as a labeled external baseline, and the flop -> sram6t
iso-area winner diff in its ``extra`` payload) plus the top-level
``macro_models`` catalog naming the silicon every report's areas assume.
"""

from __future__ import annotations

import inspect
import json
import sys
import time

from repro import api, metrics, silicon
from repro.core import simulator
from repro.launch.cache import use_compile_cache

SCHEMA_VERSION = 7

_MODULES = {
    "table3": "benchmarks.table3_speedup",
    "fig4": "benchmarks.fig4_cvrf_sweep",
    "fig5": "benchmarks.fig5_min_regs",
    "fig6": "benchmarks.fig6_equal_area",
    "fig2": "benchmarks.fig2_area_model",
    "fig8": "benchmarks.fig8_power",
    "pareto": "benchmarks.pareto_frontier",
    "policy_headroom": "benchmarks.policy_headroom",
    "vmem_dispersion": "benchmarks.vmem_dispersion",
    "kv_dispersion": "benchmarks.kv_dispersion",
    "serving_slo": "benchmarks.serving_slo",
    "ablation_sensitivity": "benchmarks.ablation_sensitivity",
    "roofline": "benchmarks.roofline",
    "network_sweep": "benchmarks.network_sweep",
    "cluster_sweep": "benchmarks.cluster_sweep",
    "dse": "benchmarks.dse",
}

SUITES = tuple(_MODULES)

_CYCLE_KEYS = ("vec_cycles", "scalar_cycles", "fifo_cycles",
               "fifo_no_fetch_cycles", "cycles")


def _sweep_meta(history_slice: list[dict]) -> list[dict]:
    """Axis + derived-metric metadata for the suite's ``Session.run``
    calls (JSON-safe)."""
    return [dict(axes=h["axes"], points=h["points"],
                 compiles=h["compiles"], dispatches=h["dispatches"],
                 fold=h["fold"], kernel_params=h["kernel_params"],
                 derived=list(h.get("derived", ())),
                 **({"networks": h["networks"]} if "networks" in h else {}))
            for h in history_slice]


def _call_main(mod, kernels, max_events, interpret):
    """Invoke a suite's main(), forwarding only the kwargs it accepts."""
    params = inspect.signature(mod.main).parameters
    kw = {}
    if interpret and "interpret" in params:
        kw["interpret"] = True
    if kernels:
        if "names" in params:
            kw["names"] = list(kernels)
        else:
            print("(fixed-roster suite: --kernels ignored)", flush=True)
    if max_events and "max_events" in params:
        kw["max_events"] = max_events
    return mod.main(**kw) or []


def _pop_flag(args: list, flag: str):
    if flag not in args:
        return None
    i = args.index(flag)
    if i + 1 >= len(args):
        raise SystemExit(f"error: {flag} requires a value")
    value = args[i + 1]
    del args[i:i + 2]
    return value


def main(argv=None) -> int:
    args = list(argv if argv is not None else sys.argv[1:])
    interpret = "--interpret" in args
    args = [a for a in args if a != "--interpret"]
    try:
        json_path = _pop_flag(args, "--json")
        kernels = _pop_flag(args, "--kernels")
        max_events = _pop_flag(args, "--max-events")
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 2
    kernels = [k for k in kernels.split(",") if k] if kernels else None
    if max_events is not None:
        try:
            max_events = int(max_events)
            if max_events <= 0:
                raise ValueError
        except ValueError:
            print(f"error: --max-events needs a positive integer, got "
                  f"{max_events!r}", file=sys.stderr)
            return 2
    suites = args or list(SUITES)
    unknown = [s for s in suites if s not in _MODULES]
    if unknown:
        print(f"error: unknown suite(s) {', '.join(unknown)}; "
              f"choose from: {', '.join(SUITES)}", file=sys.stderr)
        return 2
    use_compile_cache()
    session = api.default_session()
    report = {"schema": SCHEMA_VERSION, "suites": {}, "kernels": {},
              "metrics": metrics.catalog(),
              "macro_models": silicon.macro_catalog()}
    t00 = time.time()
    for suite in suites:
        mod = __import__(_MODULES[suite], fromlist=["main"])
        print(f"\n## {suite} ({_MODULES[suite]})", flush=True)
        t0 = time.time()
        c0 = simulator.compile_count()
        d0 = simulator.dispatch_count()
        h0 = len(session.history)
        ps0 = mod.perf_stats() if hasattr(mod, "perf_stats") else {}
        rows = _call_main(mod, kernels, max_events, interpret)
        dt = time.time() - t0
        print(f"## {suite} done in {dt:.1f}s", flush=True)
        report["suites"][suite] = {
            "wall_s": round(dt, 2),
            "rows": len(rows),
            "compiles": simulator.compile_count() - c0,
            "dispatches": simulator.dispatch_count() - d0,
            "sweeps": _sweep_meta(session.history[h0:]),
        }
        # Suites that drive Pallas kernels directly (the roofline) count
        # their own compiles/dispatches — the simulator probes never see
        # those executions.
        if hasattr(mod, "perf_stats"):
            ps = mod.perf_stats()
            for key in ("compiles", "dispatches"):
                report["suites"][suite][key] += \
                    ps.get(key, 0) - ps0.get(key, 0)
        # schema 4: suites may export a JSON-safe payload of their own
        # (e.g. serving_slo's footprint-vs-latency Pareto fronts)
        if hasattr(mod, "json_extra"):
            report["suites"][suite]["extra"] = mod.json_extra()
        for r in rows:
            cyc = {k: r[k] for k in _CYCLE_KEYS if k in r}
            if cyc and isinstance(r.get("name"), str):
                kern = report["kernels"].setdefault(r["name"], {})
                # Every grid field the row carries keys the record, so
                # e.g. pareto rows at the same capacity but different L1
                # geometries never overwrite each other.
                suffix = "".join(
                    f"_{tag}{r[f]}" for tag, f in
                    (("cap", "capacity"), ("l1", "l1_kb")) if f in r)
                for k, v in cyc.items():
                    kern[f"{suite}{suffix}.{k}"] = v
    total = time.time() - t00
    print(f"\nALL BENCHMARKS DONE in {total:.1f}s")
    if json_path:
        report["total_wall_s"] = round(total, 2)
        report["total_compiles"] = simulator.compile_count()
        report["total_dispatches"] = simulator.dispatch_count()
        with open(json_path, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        print(f"wrote {json_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

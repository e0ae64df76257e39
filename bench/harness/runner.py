"""One run of one cell: device check, set-up, window, readers, result line.

``run.py`` calls :func:`main`; the tests call :func:`run_cell` with the
device check skipped and a cell built in memory.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import shutil
import sys
import time

from harness import peaks, program_spans, spec, spans, trace

OUT = spec.BENCH / "out"


@dataclasses.dataclass
class Context:
    cell: spec.Cell
    seed: int
    seconds: float
    trace: bool
    spans: spans.Spans
    compiles: spans.Compiles
    trace_dir: pathlib.Path
    started: float                      # perf_counter at process start
    control: bool = False
    setup_s: float | None = None
    details: dict = dataclasses.field(default_factory=dict)

    def log(self, msg: str) -> None:
        print(f"[{self.cell.name}] {msg}", file=sys.stderr, flush=True)

    def mark_setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.started
        self.log(f"set-up {self.setup_s:.3f} s, {self.compiles.count} "
                 f"executables built or loaded, "
                 f"{self.compiles.seconds:.3f} s compiling")

    def memory_peak(self) -> int:
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))


def use_cache(root: pathlib.Path) -> pathlib.Path:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, holding every program however small or quick to build."""
    import jax
    path = root / "bench" / "out" / "jax_cache"
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int) -> dict:
    """The accelerator JAX found; raises when it is not a TPU with at
    least ``chips`` devices."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"needs a TPU, JAX found {devs[0].platform!r} "
                         f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=chips)


def read_per_layer(ctx: Context, result: dict, dev: dict) -> tuple:
    """Reduce the trace; run every per-layer reader of the cell."""
    t0 = time.perf_counter()
    tr = trace.read(ctx.trace_dir)
    red = trace.reduce(tr, ctx.cell.chips)
    ctx.log(f"trace: {sum(len(d) for d in tr.devices.values())} device "
            f"events on {sorted(tr.devices)}, {len(tr.spans)} spans, read "
            f"and reduced in {time.perf_counter() - t0:.3f} s; lines "
            f"{ {k: v for k, v in tr.line_names.items() if 'device' in k} }")
    record = dict(trace=red, counts=result["counts"],
                  program_spans=program_spans.recorded(),
                  config=ctx.cell.config, traffic=ctx.cell.traffic,
                  peaks=peaks.peaks(dev["kind"]), chips=ctx.cell.chips)
    metrics = {}
    for m in ctx.cell.per_layer:
        value = spec.metric_reader(m["name"], ctx.cell.root).read(record)
        if value is not None:
            metrics[m["name"]] = dict(value=value, unit=m["unit"])
    breakdown = dict(device_ops=red.device_ops, idle_gaps=red.idle_gaps)
    return metrics, breakdown, red


def run_cell(ctx: Context, dev: dict) -> dict:
    """Set-up, window and check through the cell's driver; the result
    line as a dict."""
    result = spec.driver(ctx.cell).run(ctx)
    checks = [dict(name=n, value=v, limit=lim) for n, v, lim in
              result["checks"]]
    correct = all(c["limit"] is not None and c["value"] <= c["limit"]
                  for c in checks)
    device = dict(dev, memory_peak_bytes=result["memory_peak_bytes"])
    line = dict(correct=correct, attempted=result["attempted"],
                failed=result["failed"])
    if ctx.trace:
        metrics, breakdown, red = read_per_layer(ctx, result, dev)
        device.update(busy_s=red.busy_ns / 1e9, window_s=red.window_ns / 1e9)
        line.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        units = {m["name"]: m["unit"] for m in ctx.cell.end_to_end}
        values = dict(result["end_to_end"], setup_s=ctx.setup_s)
        line.update(metrics={k: dict(value=values[k], unit=units[k])
                             for k in units}, device=device)
    line["checks"] = {c["name"]: dict(value=c["value"], limit=c["limit"])
                      for c in checks}
    return line


def main(argv, started: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = spec.ROOT
    if not (root / "src" / "repro").is_dir():
        print(f"no program sources at {root / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(root / "src"))
    cell = spec.cell(args.workload, root)
    dev = device_info(cell.chips)
    use_cache(root)
    comp = spans.Compiles().install()
    trace_dir = OUT / "trace" / cell.name
    if trace_dir.exists():
        shutil.rmtree(trace_dir)
    # Seeds of any size and sign map onto the generators' range.
    ctx = Context(cell=cell, seed=args.seed % 2**63, seconds=args.seconds,
                  trace=bool(args.trace),
                  spans=spans.Spans(annotate=bool(args.trace)),
                  compiles=comp, trace_dir=trace_dir, started=started)
    line = run_cell(ctx, dev)
    ctx.log(f"run {time.perf_counter() - started:.3f} s, correct="
            f"{line['correct']}")
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0

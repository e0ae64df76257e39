"""Readings that set the limits of ``correct``: a cell's run and its
control on several seeds, in one process.

  python3 bench/readings.py --workload <name> --seeds 1,2,3 --seconds 20 \
      [--control]

For each seed this runs the cell's driver as ``bench/run.py`` does (set-up,
window, check) and prints one JSON line with the numbers compared.  With
``--control`` the control stands in the program's place: for an engine
cell the reference's answer for the nearest other memory latency of the
pool (a stale answer from a results cache), for a served model the
reference with fp8 weights, whose gap is read at the same positions as
the program's.  The benchmark's own runs never run the
control.  Needs the chip, like ``bench/run.py``.
"""

import sys
import time

STARTED = time.perf_counter()


def main(argv) -> int:
    import argparse
    import json

    from harness import runner, spans, spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(spec.ROOT / "src"))
    cell = spec.cell(args.workload)
    dev = runner.device_info(cell.chips)
    runner.use_cache(spec.ROOT)
    comp = spans.Compiles().install()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        ctx = runner.Context(
            cell=cell, seed=seed, seconds=args.seconds, trace=False,
            spans=spans.Spans(), compiles=comp,
            trace_dir=runner.OUT / "trace" / "readings", started=t0,
            control=args.control)
        line = runner.run_cell(ctx, dev)
        points = [{k: p[k] for k in ("kernel", "capacity", "policy",
                                     "l1_sets", "mem_latency", "certified",
                                     "mismatched", "cycles_rel_err")}
                  for p in ctx.details.get("points", [])]
        print(json.dumps(dict(
            seed=seed, control=args.control, correct=line["correct"],
            checks=line["checks"], points=points,
            details={k: v for k, v in ctx.details.items() if k != "points"},
            seconds=time.perf_counter() - t0)), flush=True)
    return 0


if __name__ == "__main__":
    import pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    sys.exit(main(sys.argv[1:]))

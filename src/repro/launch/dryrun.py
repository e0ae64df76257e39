import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, proving the distribution config is coherent without
hardware.  MUST be run as a script or via ``run_cell`` in a fresh process —
the XLA_FLAGS line above executes before any jax import.

Per cell this reports:
  - compile success,
  - memory_analysis (bytes per device -> fits 16 GB v5e HBM?),
  - cost_analysis (FLOPs / bytes for the roofline),
  - collective bytes parsed from the optimized HLO (all-gather,
    all-reduce, reduce-scatter, all-to-all, collective-permute).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
      --shape train_4k --mesh single --out /tmp/cell.json
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi
"""

import argparse
import json
import sys


# Per-arch microbatch counts for train_4k (activation-memory fit on 16 GB).
TRAIN_MICROBATCHES = {
    "command-r-plus-104b": 8,
    "phi3.5-moe-42b-a6.6b": 4,
    "deepseek-v2-lite-16b": 2,
    "falcon-mamba-7b": 2,
}

def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             parse_collectives: bool = True, verbose: bool = True) -> dict:
    import jax
    from repro.configs import SHAPES, cell_runnable, get
    from repro.launch import sharding as shr
    from repro.launch import specs as specs_mod
    from repro.launch.mesh import make_production_mesh
    from repro.models import common, get_model
    from repro.optim import adamw
    from repro.train.train_step import make_train_step

    cfg = get(arch_name)
    shape = SHAPES[shape_name]
    ok, why = cell_runnable(cfg, shape)
    result = {"arch": arch_name, "shape": shape_name,
              "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        result.update(status="skip", reason=why)
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    common.set_mesh(mesh)
    sp = specs_mod.input_specs(arch_name, shape)
    params_sh = shr.params_shardings(sp["params"], mesh)
    batch_sh = shr.batch_shardings(sp["batch"], mesh, shape.kind)

    if shape.kind == "train":
        mb = TRAIN_MICROBATCHES.get(arch_name, 1)
        opt_cfg = adamw.OptConfig()
        step = make_train_step(cfg, opt_cfg, microbatches=mb)
        opt_sh = shr.opt_shardings(sp["opt"], params_sh, mesh)
        fn = jax.jit(step,
                     in_shardings=(params_sh, opt_sh, None, batch_sh),
                     out_shardings=(params_sh, opt_sh, None, None))
        args = (sp["params"], sp["opt"], None, sp["batch"])
        result["microbatches"] = mb
    elif shape.kind == "decode":
        model = get_model(cfg)
        cache_sh = shr.cache_shardings(sp["cache"], mesh)

        def serve_step(params, cache, batch):
            return model.decode_step(params, cache, batch)
        fn = jax.jit(serve_step,
                     in_shardings=(params_sh, cache_sh, batch_sh),
                     out_shardings=(None, cache_sh))
        args = (sp["params"], sp["cache"], sp["batch"])
    else:  # prefill
        model = get_model(cfg)

        def prefill_step(params, batch):
            return model.prefill(params, batch)
        fn = jax.jit(prefill_step, in_shardings=(params_sh, batch_sh),
                     out_shardings=None)
        args = (sp["params"], sp["batch"])

    import time
    t0 = time.time()
    lowered = fn.lower(*args)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    result.update(status="ok", lower_s=round(t1 - t0, 1),
                  compile_s=round(t2 - t1, 1))

    mem = compiled.memory_analysis()
    if mem is not None:
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                result[attr] = int(v)
        n_dev = mesh.size
        args_b = result.get("argument_size_in_bytes", 0)
        temp_b = result.get("temp_size_in_bytes", 0)
        result["bytes_per_device"] = int(args_b + temp_b)
        result["fits_16g"] = bool(result["bytes_per_device"] < 16e9)
        del n_dev
    c = compiled.cost_analysis()
    if c:
        result["flops"] = float(c.get("flops", -1))
        result["bytes_accessed"] = float(c.get("bytes accessed", -1))
        result["transcendentals"] = float(c.get("transcendentals", 0))
    if parse_collectives:
        try:
            from repro.launch import hlo_cost
            txt = compiled.as_text()
            result["hlo_chars"] = len(txt)
            hc = hlo_cost.analyze(txt)
            result["collectives"] = {
                k: hc[k] for k in ("all-gather", "all-reduce",
                                   "reduce-scatter", "all-to-all",
                                   "collective-permute",
                                   "collective_bytes", "collective_count")}
            result["dot_flops_loop_corrected"] = hc["dot_flops"]
            result["bytes_loop_corrected"] = hc["bytes_accessed"]
            del txt
        except Exception as e:  # pragma: no cover
            result["collectives_error"] = str(e)
    if verbose:
        print(json.dumps(result, indent=1), flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-collectives", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from repro.configs import ARCHS, SHAPES
    cells = ([(args.arch, args.shape)] if not args.all else
             [(a, s) for a in ARCHS for s in SHAPES])
    results = []
    for arch, shape in cells:
        try:
            results.append(run_cell(arch, shape, args.mesh == "multi",
                                    not args.no_collectives))
        except Exception as e:
            results.append({"arch": arch, "shape": shape,
                            "status": "error", "error": repr(e)[:500]})
            print(results[-1], file=sys.stderr, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    bad = [r for r in results if r["status"] == "error"]
    print(f"\n{len(results) - len(bad)}/{len(results)} cells ok")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

"""Bring-up check on one TPU chip: the repo's three hot paths at real sizes.

  python chip_smoke.py

One process runs four phases in order and prints each one's result, wall
time and compile time on lines of its own:

  (a) device   the platform, device kind and count, and the compile cache;
  (b) engine   the dispersion engine through ``repro.api.Session`` with
               the backend's default dispatch: the paper's Table 2 kernels
               at paper size, capacities {3, 4, 8, 32} x FIFO/LRU, plus a
               small cluster sweep (cores 1 and 4).  Every counter and
               ``fold_exact`` bit must equal the same sweeps run on the
               host's CPU backend, so the chip's single-core scan (the
               Pallas kernel, which the phase requires and prints) is
               checked against the CPU's XLA scan; the golden counter
               table and the pathfinder capacity-3 pin must hold on the
               chip;
  (c) kernels  the Pallas GEMMs (grouped W=1 and W=4, dispersed) at
               phi3-mini's FFN up-projection and causal flash attention at
               its attention shape, compiled (``interpret=False``) and
               checked against ``repro.kernels.ref`` computed on the CPU;
  (d) serving  full-width ``phi3-mini-3.8b`` in bf16 through
               ``repro.launch.serve`` and ``ServeEngine``; the first
               request's decode logits are checked against a float32
               full-sequence forward of the same weights on the CPU.

The last line of standard output is one JSON object naming the device,
printed only when every phase passed.  Without a TPU, or without the
repository's ``src/`` next to this file, the script exits non-zero and
prints no result.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import statistics
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent

# (b) engine grid.
CAPACITIES = (3, 4, 8, 32)
CLUSTER_KERNELS = ("dropout", "gemv")
CLUSTER_CORES = (1, 4)
CLUSTER_CAPACITIES = (4, 8)
# fig4's pathfinder row at capacity 3 (FIFO, paper size).
PATHFINDER_PIN = dict(norm_perf=0.5683, hit_rate=0.5, spills=741, fills=868)

# (c) kernel shapes: phi3-mini's FFN up-projection (512 tokens x d_model
# 3072 -> d_ff 8192) and its attention (32 heads of 96, 2048 tokens).
GEMM_SHAPE = (512, 3072, 8192)
ATTN_SHAPE = (1, 32, 2048, 96)
# bf16 outputs: one bf16 rounding of the result is ~4e-3 relative, so a
# correct kernel lands well inside |got - ref| <= ATOL + RTOL * |ref|.
KERNEL_RTOL = 2e-2
KERNEL_ATOL = 2e-2

# (d) serving at full width.
SERVE_ARCH = "phi3-mini-3.8b"
SERVE = dict(slots=4, max_len=256, requests=8, max_new=16, prompt_len=8)
# Decode steps of the first request checked against the f32 reference.
CHECK_STEPS = 8
# bf16 decode against the f32 reference: the relative L2 error of each
# step's logits must stay under LOGIT_REL_TOL, and the argmax must agree
# on at least TOP1_MIN of the checked steps (random weights give near-tied
# top logits, so an exact match on every step is not required).
LOGIT_REL_TOL = 0.05
TOP1_MIN = 0.75

_COMPILE_S = [0.0]


def _on_duration(name: str, secs: float, **_):
    if name.startswith("/jax/core/compile/"):
        _COMPILE_S[0] += secs


def _timed(fn, *args, **kw):
    t0, c0 = time.perf_counter(), _COMPILE_S[0]
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0, _COMPILE_S[0] - c0


def _cpu():
    import jax
    return jax.devices("cpu")[0]


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------


def phase_device(cache_dir: str) -> dict:
    import jax
    dev = jax.devices()[0]
    return dict(platform=dev.platform, kind=dev.device_kind,
                count=len(jax.devices()), compile_cache=cache_dir)


# ---------------------------------------------------------------------------
# (b) dispersion engine
# ---------------------------------------------------------------------------


def _load_golden() -> dict:
    path = ROOT / "tests" / "test_golden_counters.py"
    spec = importlib.util.spec_from_file_location("_golden_counters", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.GOLDEN


def _mismatches(got, want) -> list[str]:
    import numpy as np
    bad = [k for k in want.data
           if k not in got.data or not np.array_equal(got.data[k],
                                                      want.data[k])]
    return bad + [k for k in got.data if k not in want.data]


def _scan_impls(spans) -> dict:
    """Engine (``core``, ``cluster``) -> the scans its recorded
    ``engine.dispatch`` spans ran (``pallas`` or ``xla``)."""
    impls: dict = {}
    for s in spans:
        if s.name == "engine.dispatch":
            impls.setdefault(s.attrs["engine"], set()).add(s.attrs["impl"])
    return {k: sorted(v) for k, v in impls.items()}


def phase_engine() -> dict:
    import jax
    from repro import api, obs, rvv
    from repro.core import policies, simulator

    kernels = list(rvv.PAPER_TABLE3)
    pols = (policies.FIFO, policies.LRU)
    sweep = api.Sweep(kernels=kernels, capacity=CAPACITIES, policy=pols,
                      kernel_params="paper")
    cl_sweep = api.Sweep(kernels=list(CLUSTER_KERNELS),
                         capacity=CLUSTER_CAPACITIES, policy=pols,
                         cores=CLUSTER_CORES, kernel_params="paper")

    chip = api.Session()
    obs.reset()
    with obs.recording():
        res, cold_s, cold_compile_s = _timed(chip.run, sweep)
        # The second run reuses the prepared traces and the compiled
        # engine: its wall time is dominated by the scan on the device
        # (Session.run returns host arrays, so it ends after the device
        # has finished).
        res_warm, warm_s, _ = _timed(chip.run, sweep)
        res_cl, cl_s, cl_compile_s = _timed(chip.run, cl_sweep)
    chip_impl = _scan_impls(obs.spans())

    obs.reset()
    with jax.default_device(_cpu()), obs.recording():
        host = api.Session(batch_programs=False)
        ref, ref_s, _ = _timed(host.run, sweep)
        ref_cl = host.run(cl_sweep)
    cpu_impl = _scan_impls(obs.spans())
    obs.reset()
    print(f"engine scans: chip {chip_impl}, cpu {cpu_impl}", flush=True)

    failures = []
    if chip_impl.get("core") != ["pallas"]:
        failures.append(f"the chip ran {chip_impl.get('core')} for the "
                        "single-core scan, not the Pallas kernel")
    for label, got, want in (("sweep", res, ref),
                             ("warm sweep", res_warm, ref),
                             ("cluster sweep", res_cl, ref_cl)):
        bad = _mismatches(got, want)
        if bad:
            failures.append(f"{label}: chip != cpu for {bad}")

    golden = _load_golden()
    golden_bad = []
    for (name, cap, pol), want in sorted(golden.items()):
        b = rvv.BENCHMARKS[name]
        out = simulator.simulate_one(b.build(**b.reduced_params).program,
                                     cap, pol)
        got = {k: int(out[k]) for k in want}
        if got != want:
            golden_bad.append((name, cap, pol, got, want))
    if golden_bad:
        failures.append(f"golden counters differ: {golden_bad}")

    r = res.derive("speedup", baseline=dict(capacity=32))
    pt = dict(kernel="pathfinder", capacity=3, policy=policies.FIFO)
    pin = dict(norm_perf=round(r.value("speedup", **pt), 4),
               hit_rate=round(r.value("hit_rate", **pt), 4),
               spills=int(r.value("spills", **pt)),
               fills=int(r.value("fills", **pt)))
    if pin != PATHFINDER_PIN:
        failures.append(f"pathfinder pin {pin} != {PATHFINDER_PIN}")

    # Instructions the sweep stands for: every kernel's full (unfolded)
    # trace at every config point.
    n_cfg = len(CAPACITIES) * len(pols)
    sim_instr = sum(chip.built(k, "paper").program.num_instructions
                    for k in kernels) * n_cfg
    return dict(
        ok=not failures, failures=failures,
        batch_programs=chip.batch_programs, kernels=len(kernels),
        scan_impl=dict(chip=chip_impl, cpu=cpu_impl),
        points=res.meta["points"], cluster_points=res_cl.meta["points"],
        fold_exact_share=float(res.data["fold_exact"].mean()),
        golden_checked=len(golden), pathfinder_pin=pin,
        cold_run_s=cold_s, cold_compile_s=cold_compile_s,
        warm_run_s=warm_s, cluster_run_s=cl_s,
        cluster_compile_s=cl_compile_s, cpu_reference_s=ref_s,
        simulated_instructions=sim_instr,
        sim_instr_per_s_warm=sim_instr / warm_s)


# ---------------------------------------------------------------------------
# (c) Pallas kernels
# ---------------------------------------------------------------------------


def _check_close(name, got, want) -> dict:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = np.abs(got - want)
    ok = bool(np.all(err <= KERNEL_ATOL + KERNEL_RTOL * np.abs(want))
              and np.isfinite(got).all())
    return dict(kernel=name, ok=ok, max_abs_err=float(err.max()),
                shape=list(got.shape))


def _run_kernel(fn, args, repeats=3) -> tuple:
    import jax
    compiled, _, compile_s = _timed(lambda: jax.jit(fn).lower(*args).compile())
    out = jax.block_until_ready(compiled(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        times.append(time.perf_counter() - t0)
    return out, compile_s, statistics.median(times)


def phase_kernels(gemm_shape=GEMM_SHAPE, attn_shape=ATTN_SHAPE,
                  interpret=False) -> dict:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import dispersed_gemm, flash_attention, ref

    m, k, n = gemm_shape
    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(ka, (m, k), jnp.float32).astype(jnp.bfloat16)
    b = (jax.random.normal(kb, (k, n), jnp.float32)
         / np.sqrt(k)).astype(jnp.bfloat16)
    with jax.default_device(_cpu()), jax.default_matmul_precision("highest"):
        want_mm = ref.matmul_ref(jax.device_put(a, _cpu()),
                                 jax.device_put(b, _cpu()))
    gemms = {
        "matmul_grouped_W4": functools.partial(
            dispersed_gemm.matmul_grouped, working_set=4,
            interpret=interpret),
        "matmul_grouped_W1": functools.partial(
            dispersed_gemm.matmul_grouped, working_set=1,
            interpret=interpret),
        "matmul_dispersed": functools.partial(
            dispersed_gemm.matmul_dispersed, interpret=interpret),
    }
    rows = []
    for name, fn in gemms.items():
        out, compile_s, run_s = _run_kernel(fn, (a, b))
        rows.append(dict(_check_close(name, out, want_mm),
                         compile_s=compile_s, median_call_s=run_s))

    bsz, h, s, d = attn_shape
    q, kk, v = (jax.random.normal(key, attn_shape, jnp.float32)
                .astype(jnp.bfloat16)
                for key in jax.random.split(jax.random.PRNGKey(1), 3))
    with jax.default_device(_cpu()), jax.default_matmul_precision("highest"):
        want_attn = ref.attention_ref(
            *(jax.device_put(x, _cpu()) for x in (q, kk, v)), causal=True)
    fa = functools.partial(flash_attention.flash_attention, causal=True,
                           interpret=interpret)
    out, compile_s, run_s = _run_kernel(fa, (q, kk, v))
    rows.append(dict(_check_close("flash_attention_causal", out, want_attn),
                     compile_s=compile_s, median_call_s=run_s))
    return dict(ok=all(r["ok"] for r in rows), interpret=interpret,
                gemm_mkn=list(gemm_shape), attn_bhsd=list(attn_shape),
                kernels=rows)


# ---------------------------------------------------------------------------
# (d) serving
# ---------------------------------------------------------------------------


def _f32_reference_logits(cfg, params, tokens) -> tuple:
    """Full-sequence forward of ``tokens`` (positions 0..n-1) in float32
    on the CPU backend.  The weights come to the host in one bulk copy and
    are upcast one layer at a time; returns the logits and the copy's
    seconds."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.model import Model

    t0 = time.perf_counter()
    host = jax.device_get(params)
    copy_s = time.perf_counter() - t0
    model = Model(dataclasses.replace(cfg, dtype="float32"))
    f32 = lambda tree: jax.tree.map(lambda w: w.astype(jnp.float32), tree)
    with jax.default_device(_cpu()), jax.default_matmul_precision("highest"):
        batch = {"tokens": jnp.asarray(tokens, jnp.int32)[None],
                 "positions": jnp.arange(len(tokens), dtype=jnp.int32)[None]}
        x = model._embed(f32({"embed": jnp.asarray(host["embed"])}), batch)
        block = jax.jit(
            lambda bp, h, pos: model._block_apply(f32(bp), h, pos)[0])
        for i in range(cfg.num_layers):
            x = block(jax.tree.map(lambda w: w[i], host["blocks"]), x,
                      batch["positions"])
        x = model.apply_norm(f32(jax.tree.map(jnp.asarray,
                                              host["final_norm"])),
                             x, cfg.norm_eps)
        name = "embed" if cfg.tie_embeddings else "lm_head"
        logits = model._lm_head(f32({name: jnp.asarray(host[name])}), x)
        return np.asarray(logits[0], np.float32), copy_s


def phase_serving(cfg=None, serve_kw=None) -> dict:
    import jax
    import numpy as np
    from repro.configs import get
    from repro.launch import serve
    from repro.models import get_model

    cfg = cfg or get(SERVE_ARCH)
    kw = dict(SERVE, **(serve_kw or {}))
    decode = jax.jit(get_model(cfg).decode_step)
    step_s, slot0 = [], []

    def timed_decode(params, cache, batch):
        t0 = time.perf_counter()
        logits, cache = jax.block_until_ready(decode(params, cache, batch))
        step_s.append(time.perf_counter() - t0)
        if len(slot0) < CHECK_STEPS:
            slot0.append((int(batch["tokens"][0, 0]),
                          int(batch["positions"][0, 0]),
                          np.asarray(logits[0, 0], np.float32)))
        return logits, cache

    c0 = _COMPILE_S[0]
    engine, init_s, _ = _timed(lambda: jax.block_until_ready(
        serve.build_engine(cfg, slots=kw["slots"], max_len=kw["max_len"],
                           decode_fn=timed_decode)))
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree.leaves(engine.params))
    reqs = serve.make_requests(cfg, kw["requests"], max_new=kw["max_new"],
                               prompt_len=kw["prompt_len"])
    _, serve_s, _ = _timed(engine.run, reqs)
    compile_s = _COMPILE_S[0] - c0
    stats = jax.devices()[0].memory_stats() or {}

    failures = []
    statuses = [r.status for r in reqs]
    if not all(r.done for r in reqs):
        failures.append(f"requests not all done: {statuses}")
    short = [len(r.out) for r in reqs if len(r.out) < kw["max_new"]]
    if short:
        failures.append(f"requests generated too few tokens: {short}")
    positions = [p for _, p, _ in slot0]
    if positions != list(range(len(slot0))):
        failures.append(f"first request's positions {positions} are not "
                        f"0..{len(slot0) - 1}")

    tokens = [t for t, _, _ in slot0]
    got = np.stack([lg for _, _, lg in slot0])
    (want, copy_s), ref_s, _ = _timed(_f32_reference_logits, cfg,
                                      engine.params, tokens)
    rel = (np.linalg.norm(got - want, axis=-1)
           / np.linalg.norm(want, axis=-1))
    top1 = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    if not np.isfinite(got).all():
        failures.append("non-finite decode logits")
    if rel.max() > LOGIT_REL_TOL:
        failures.append(f"logit relative error {rel.max():.4f} > "
                        f"{LOGIT_REL_TOL}")
    if top1 < TOP1_MIN:
        failures.append(f"top-1 agreement {top1:.3f} < {TOP1_MIN}")

    return dict(
        ok=not failures, failures=failures, arch=cfg.name,
        params=n_params, dtype=cfg.dtype, **kw,
        statuses=sorted(set(statuses)),
        tokens_generated=sum(len(r.out) for r in reqs),
        decode_steps=len(step_s),
        first_step_s=step_s[0] if step_s else None,
        median_decode_step_s=(statistics.median(step_s[1:])
                              if len(step_s) > 1 else None),
        init_s=init_s, serve_s=serve_s, compile_s=compile_s,
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        bytes_in_use=stats.get("bytes_in_use"),
        checked_steps=len(slot0), logit_rel_err_max=float(rel.max()),
        logit_rel_err_median=float(np.median(rel)), top1_agreement=top1,
        logit_rel_tol=LOGIT_REL_TOL, top1_min=TOP1_MIN,
        cpu_reference_s=ref_s, weights_to_host_s=copy_s)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def _report(name: str, result: dict, wall_s: float, compile_s: float):
    status = "ok" if result.get("ok", True) else "FAIL"
    print(f"[{name}] {status} wall_s={wall_s:.3f} compile_s={compile_s:.3f}",
          flush=True)
    for key, value in result.items():
        if key != "ok":
            print(f"[{name}]   {key}: {json.dumps(value, default=str)}",
                  flush=True)


def main() -> int:
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repository sources at {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    from repro.launch.cache import use_compile_cache

    cache_dir = use_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1

    phases = (("a.device", lambda: phase_device(cache_dir)),
              ("b.engine", phase_engine),
              ("c.kernels", phase_kernels),
              ("d.serving", phase_serving))
    failed = []
    for name, fn in phases:
        t0, c0 = time.perf_counter(), _COMPILE_S[0]
        try:
            result = fn()
        except Exception as e:
            traceback.print_exc()
            result = dict(ok=False, error=f"{type(e).__name__}: {e}")
        _report(name, result, time.perf_counter() - t0, _COMPILE_S[0] - c0)
        if not result.get("ok", True):
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

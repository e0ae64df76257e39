"""Serving launcher CLI: batched generation with continuous batching.

  PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b \
      --reduced --requests 8 --max-new 12

Without ``--reduced`` the model is served at its published widths (on a
TPU: ``phi3-mini-3.8b`` in bf16 takes about 7.6 GB of HBM).
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get
from repro.launch.cache import use_compile_cache
from repro.models import get_model
from repro.serve import Request, ServeEngine


def build_engine(cfg, *, slots: int, max_len: int, temperature: float = 0.0,
                 decode_fn=None) -> ServeEngine:
    # Random weights drawn on the device by one jitted program (an eager
    # init would hold each stacked weight in f32 before casting it).
    params = jax.jit(get_model(cfg).init)(jax.random.PRNGKey(0))
    return ServeEngine(cfg, params, slots=slots, max_len=max_len,
                       temperature=temperature, decode_fn=decode_fn)


def make_requests(cfg, n: int, *, max_new: int,
                  prompt_len: int = 6) -> list[Request]:
    rng = np.random.default_rng(0)
    return [Request(prompt=list(rng.integers(1, cfg.vocab_size, prompt_len)),
                    max_new_tokens=max_new)
            for _ in range(n)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-mini-3.8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    engine = build_engine(cfg, slots=args.slots, max_len=args.max_len,
                          temperature=args.temperature)
    reqs = make_requests(cfg, args.requests, max_new=args.max_new)
    engine.run(reqs)
    done = sum(r.done for r in reqs)
    print(f"served {done}/{len(reqs)} requests "
          f"({args.slots} slots, continuous batching)")
    for r in reqs[:3]:
        print("  out:", r.out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Training launcher CLI.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
      --steps 100 --seq-len 128 --global-batch 8

Runs the fault-tolerant Trainer (checkpoint/restart, heartbeat, straggler
policy).  On a real cluster this entrypoint runs per host under
``jax.distributed.initialize`` with the mesh from ``launch.mesh``; in this
container it runs single-process (reduced configs recommended).
"""

from __future__ import annotations

import argparse

from repro.configs import get
from repro.launch.cache import use_compile_cache
from repro.optim import OptConfig
from repro.train import TrainConfig, Trainer


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tc = TrainConfig(seq_len=args.seq_len, global_batch=args.global_batch,
                     microbatches=args.microbatches, steps=args.steps,
                     checkpoint_every=max(args.steps // 4, 1),
                     checkpoint_dir=args.ckpt_dir)
    oc = OptConfig(peak_lr=args.lr, min_lr=args.lr / 10,
                   warmup_steps=max(args.steps // 20, 1),
                   total_steps=args.steps,
                   compress_grads=args.compress_grads)
    out = Trainer(cfg, tc, oc).run()
    h = out["history"]
    print(f"final loss {h[-1]['loss']:.4f} after {len(h)} steps "
          f"(restartable from {args.ckpt_dir})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

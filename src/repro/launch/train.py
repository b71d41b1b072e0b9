"""Training launcher: end-to-end distributed training driver.

Runs real training on whatever devices exist (the production meshes need
real hardware; smoke-scale runs use --smoke and the local device), with
checkpoint-restart fault tolerance via repro.runtime.driver.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
      --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse

import jax

from repro import configs
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.driver import TrainDriver, TrainJobConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="cosine",
                    choices=("cosine", "wsd", "const"))
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(
        args.arch)
    job = TrainJobConfig(
        arch=cfg, steps=args.steps, global_batch=args.batch,
        seq_len=args.seq, lr=args.lr, schedule=args.schedule,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        microbatches=args.microbatches, remat=args.remat, seed=args.seed,
    )
    driver = TrainDriver(job)
    state = driver.run(resume=args.resume)
    print(f"final step={state.step} loss={state.last_loss:.4f}")


if __name__ == "__main__":
    main()

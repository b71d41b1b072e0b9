"""Serving launcher: continuous batched generation with the Engine.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
      --batch 4 --prompt-len 32 --new-tokens 16

Requests go through the handle/stream API: ``submit()`` returns a
``RequestHandle`` per prompt and ``drain()`` runs the continuous
scheduler — mixed prompt lengths are fine (``--ragged`` randomizes
them), short requests finish and free their slot while long ones keep
decoding.

Crash-safe serving: give it a journal directory and a snapshot cadence
and every admission/token/terminal transition is journaled, with
periodic engine snapshots; after a kill, ``--resume`` replays the
journal (and newest snapshot) and finishes the interrupted batch with
bit-identical greedy tokens:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
      --journal-dir /tmp/serve-journal --snapshot-every 4
  # ... SIGKILL mid-decode, then:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
      --journal-dir /tmp/serve-journal --resume
"""
from __future__ import annotations

import argparse
import sys

import jax
import numpy as np

from repro import configs
from repro.models import lm
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve.engine import Engine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--ragged", action="store_true",
                    help="randomize prompt lengths in [1, prompt-len] "
                         "(exercises the continuous scheduler)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--journal-dir", default=None,
                    help="enable the durable request journal (WAL) + "
                         "snapshots under this directory")
    ap.add_argument("--snapshot-every", type=int, default=None,
                    help="engine snapshot cadence in decode steps "
                         "(default: REPRO_SNAPSHOT_EVERY)")
    ap.add_argument("--resume", action="store_true",
                    help="recover journaled requests after a crash and "
                         "finish serving them")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(
        args.arch)
    params = lm.init_model(cfg, jax.random.PRNGKey(args.seed))
    engine = Engine(cfg, params,
                    max_len=args.prompt_len + args.new_tokens + 8,
                    journal_dir=args.journal_dir,
                    snapshot_every=args.snapshot_every)
    if args.resume:
        reqs = engine.restore()
        engine.serve(reqs)
        print(f"resumed {len(reqs)} journaled request(s):")
    else:
        rng = np.random.default_rng(args.seed)
        lens = (rng.integers(1, args.prompt_len + 1, args.batch)
                if args.ragged
                else np.full(args.batch, args.prompt_len))
        reqs = [engine.submit(
                    rng.integers(0, cfg.vocab_size, int(n)).astype(
                        np.int32),
                    args.new_tokens)
                for n in lens]
        engine.drain()
    for r in reqs:
        print(f"  req{r.rid} [{r.state.value}] "
              f"prompt={len(r.prompt)}: {r.out_tokens}")
    stats = engine.stats()
    print(f"engine: admitted={stats['admitted']} "
          f"completed={stats['completed']} retries={stats['retries']} "
          f"demotions={stats['demotions']} "
          f"degraded_steps={stats['degraded_steps']} "
          f"snapshots={stats['snapshots_saved']} "
          f"recovered={stats['recovered']} "
          f"replayed_steps={stats['replayed_steps']} "
          f"failed={stats['failed']}")
    if stats["failed"]:
        sys.exit(1)


if __name__ == "__main__":
    main()

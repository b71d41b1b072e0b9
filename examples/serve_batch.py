"""End-to-end driver: serve a small model with batched requests.

    PYTHONPATH=src python examples/serve_batch.py [--arch qwen3-1.7b]

The paper is an inference paper, so the end-to-end example is serving:
batched prompts -> prefill -> greedy decode through the KV-cached
serve_step (the same function the decode_32k dry-run cells lower), now
via the handle/stream API (PR 8): ``submit`` returns a
``RequestHandle``, the first request's tokens are *streamed* (each
``next()`` steps the continuous scheduler), and ``drain`` finishes the
rest — mixed prompt lengths welcome (``--ragged``).  The run ends with
the engine's admission/degradation stats, scheduler occupancy and
health ledger.  Try a fault drill:

    REPRO_FAULT_PLAN="serve.decode_step:3:raise" \
        PYTHONPATH=src python examples/serve_batch.py

and watch the demotion + retry land in the report (see
docs/robustness.md).

Resume-after-kill drill: journal to a directory, SIGKILL the loop
mid-decode (the `kill` fault kind delivers a real SIGKILL), and rerun
with --resume — the restarted engine recovers every in-flight request
from the journal + newest snapshot and finishes with the exact greedy
tokens the uninterrupted run would have produced:

    REPRO_FAULT_PLAN="serve.decode_step:10:kill" \
        PYTHONPATH=src python examples/serve_batch.py \
        --journal-dir /tmp/serve-crash --snapshot-every 4 || true
    PYTHONPATH=src python examples/serve_batch.py \
        --journal-dir /tmp/serve-crash --resume
"""
import argparse
import sys
import time

import jax
import numpy as np

from repro import configs
from repro.models import lm
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve.engine import Engine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--ragged", action="store_true",
                    help="randomize prompt lengths (continuous "
                         "scheduler demo)")
    ap.add_argument("--journal-dir", default=None,
                    help="journal requests (WAL) + snapshots here; "
                         "enables --resume after a kill")
    ap.add_argument("--snapshot-every", type=int, default=None,
                    help="snapshot cadence in decode steps")
    ap.add_argument("--resume", action="store_true",
                    help="recover and finish journaled requests")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = configs.get_smoke(args.arch)
    print(f"serving {cfg.name} ({cfg.param_count()/1e6:.1f}M params, "
          f"reduced config)")
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    engine = Engine(cfg, params,
                    max_len=args.prompt_len + args.new_tokens + 8,
                    journal_dir=args.journal_dir,
                    snapshot_every=args.snapshot_every)

    t0 = time.time()
    if args.resume:
        reqs = engine.restore()
        print(f"restored {len(reqs)} journaled request(s), "
              f"{engine.stats()['recovered']} in flight")
        engine.serve(reqs)
    else:
        rng = np.random.default_rng(0)
        lens = (rng.integers(1, args.prompt_len + 1, args.batch)
                if args.ragged
                else np.full(args.batch, args.prompt_len))
        reqs = [engine.submit(
                    rng.integers(0, cfg.vocab_size, int(n)).astype(
                        np.int32),
                    args.new_tokens)
                for n in lens]
        # stream the first handle token by token (each next() steps
        # the scheduler), then drain the rest of the batch
        print(f"  req{reqs[0].rid} streaming:", end="", flush=True)
        for tok in reqs[0].tokens():
            print(f" {tok}", end="", flush=True)
        print()
        engine.drain()
    dt = time.time() - t0
    total_new = sum(len(r.out_tokens) for r in reqs)
    print(f"batch={len(reqs)} prompt<={args.prompt_len} "
          f"new={args.new_tokens}: {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s incl. prefill+compile)")
    for r in reqs:
        print(f"  req{r.rid} [{r.state.value}] prompt={len(r.prompt)}: "
              f"{r.out_tokens[:12]}...")
    stats = engine.stats()
    health = stats.pop("health")
    print(f"engine stats: {stats}")
    print(f"health: {health}")
    print(f"scheduler: {engine.scheduler_report()}")
    if stats["failed"]:
        sys.exit(1)


if __name__ == "__main__":
    main()

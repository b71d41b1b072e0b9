#!/usr/bin/env python3
"""Smoke run of the serving main path on one TPU chip.

    python3 chip_smoke.py

Serves qwen3-1.7b at its published widths (28 layers, d_model 2048,
16 query / 8 KV heads of 128, d_ff 6144, vocab 151936, bf16) with
seeded random weights and the dataflow kernels on, through the entry
points a user calls: ``Engine.submit`` -> ``ContinuousScheduler`` ->
paged decode -> ``kernels.ops``.  It fails (non-zero exit) unless:

  * every request ends DONE with its whole token budget;
  * the engine counts no demotion, retry, degraded step or failure (a
    demotion's health-ledger detail is printed, so a kernel lowering
    error shows instead of being absorbed by the XLA fallback);
  * the scheduler took the paged datapath;
  * the compiled prefill and paged-decode steps hold ``tpu_custom_call``
    (the Pallas kernels ran, not the XLA reference);
  * the kernel path's last-position prefill logits, and its logits of
    one paged decode step, agree with the XLA path's (the same step
    traced under ``layers.forced_backend("xla")``) on the same chip
    within ``REL_L2_BOUND``.

Without a TPU, or on a TPU kind ``cost_model`` has no model for, it
exits non-zero before serving.  On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Every wall-clock figure it prints is a smoke timing, not a benchmark.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen3-1.7b"
SEED = 0
MAX_BATCH = 8
PAGE_SIZE = 16
MAX_LEN = 2048
# Two prompt lengths: the B=1 whole-prompt prefill compiles once per
# distinct length.
PROMPT_LENS = (128, 512, 128, 512, 128, 512)
NEW_TOKENS = 32
# Relative L2 error of the kernel path's logits (prefill or decode)
# against the XLA path's.  Both compute in bf16 (unit roundoff
# u = 2**-8) but round at different points: the kernels accumulate a
# whole GEMM or attention block in f32 and round once (silu fused
# before the rounding), XLA rounds each einsum output to bf16 first.
# That gives about four differently rounded tensors per layer
# (attention output, gate, up, down), each an independent relative
# perturbation of rms u/sqrt(3).  Over 28 layers they add as a random
# walk: sqrt(4 * 28) * u / sqrt(3) = 0.024 at the final hidden state,
# which the unembedding carries to the logits.  The bound is twice
# that.  A format with four fewer mantissa bits (fp8 e4m3) would err
# ~16x more and fail it.
REL_L2_BOUND = 0.05


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def log(msg: str) -> None:
    print(msg, flush=True)


def memory(device, phase: str) -> None:
    """Device memory at a phase boundary, and what the live arrays hold."""
    import jax

    stats = device.memory_stats() or {}
    live = jax.live_arrays()
    log(f"memory after {phase}: in use {stats.get('bytes_in_use')} B, "
        f"peak {stats.get('peak_bytes_in_use')} B, largest free block "
        f"{stats.get('largest_free_block_bytes')} B, limit "
        f"{stats.get('bytes_limit')} B; {len(live)} live arrays hold "
        f"{sum(a.nbytes for a in live)} B")


def largest_live_arrays(n: int = 12) -> None:
    """The live arrays by total size per (shape, dtype): what a failure
    left on the device."""
    import collections

    import jax

    groups = collections.Counter()
    counts = collections.Counter()
    for a in jax.live_arrays():
        key = (tuple(a.shape), str(a.dtype))
        groups[key] += a.nbytes
        counts[key] += 1
    for (shape, dtype), nbytes in groups.most_common(n):
        log(f"  live {counts[(shape, dtype)]} x {shape} {dtype}: "
            f"{nbytes} B")


def build_config():
    from repro import configs

    return dataclasses.replace(configs.get(ARCH), use_pallas_kernels=True)


def check_custom_calls(name: str, compiled) -> None:
    """The compiled step must hold a Pallas kernel, not only XLA ops."""
    n = compiled.as_text().count("tpu_custom_call")
    log(f"{name}: {n} tpu_custom_call op(s) in the compiled step")
    if n == 0:
        raise SmokeFailure(f"{name} compiled without a tpu_custom_call: "
                           f"the kernels were swapped for the reference")


def compile_steps(engine, prompt_lens):
    """Compile the engine's own prefill (one per prompt length) and
    paged-decode steps, timing each and checking for the kernels."""
    import jax.numpy as jnp
    import numpy as np

    for plen in sorted(set(prompt_lens)):
        toks = jnp.zeros((1, plen), jnp.int32)
        t0 = time.perf_counter()
        compiled = engine._prefill.lower(engine.params, toks).compile()
        log(f"compile prefill[1x{plen}]: "
            f"{time.perf_counter() - t0:.2f} s (smoke timing)")
        check_custom_calls(f"prefill[1x{plen}]", compiled)

    sched = engine._ensure_scheduler()
    mb = sched.cc.max_batch
    args = (engine.params, sched.paged.k_pages, sched.paged.v_pages,
            jnp.zeros((mb, 1), jnp.int32),
            jnp.zeros((mb, sched.max_pages), jnp.int32),
            jnp.asarray(np.zeros(mb, np.int32)),
            jnp.asarray(np.full(mb, sched.paged.scratch, np.int32)),
            jnp.asarray(np.zeros(mb, np.int32)))
    t0 = time.perf_counter()
    compiled = sched._paged_fns()[0].lower(*args).compile()
    log(f"compile paged_decode[{mb} rows]: "
        f"{time.perf_counter() - t0:.2f} s (smoke timing)")
    check_custom_calls(f"paged_decode[{mb} rows]", compiled)


def rel_l2(name: str, got, want, vocab: int) -> float:
    """Relative L2 error of kernel-path logits against XLA-path logits
    over the real vocabulary (the padding rows are masked to -inf)."""
    import numpy as np

    got = np.asarray(got, np.float32)[:vocab]
    want = np.asarray(want, np.float32)[:vocab]
    if not np.all(np.isfinite(got)) or not np.all(np.isfinite(want)):
        raise SmokeFailure(f"non-finite {name} logits")
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def compare_prefill(cfg, engine, handles) -> float:
    """Last-position prefill logits: the engine's kernel path against
    its XLA twin (``lm.prefill`` traced under
    ``layers.forced_backend("xla")``).  Returns the worst error."""
    import jax.numpy as jnp
    import numpy as np

    worst = 0.0
    agree = 0
    for h in handles:
        toks = jnp.asarray(h.prompt[None])
        got, _ = engine._prefill(engine.params, toks)
        want, _ = engine._prefill_degraded(engine.params, toks)
        rel = rel_l2(f"req{h.rid} prefill", got[0], want[0], cfg.vocab_size)
        worst = max(worst, rel)
        xla_tok = int(np.argmax(np.asarray(want[0])))
        agree += xla_tok == h.out_tokens[0]
        log(f"req{h.rid} prompt={len(h.prompt)}: prefill logits rel L2 "
            f"{rel:.6f} (kernel vs xla); first token kernel="
            f"{h.out_tokens[0]} xla={xla_tok}")
    log(f"first decode tokens agreeing with xla: {agree}/{len(handles)} "
        f"(read only: random-weight logits have near-ties)")
    return worst


def compare_decode(cfg, engine, handles) -> float:
    """One paged decode step, kernel path against its XLA twin, with
    every request in its own row: each prompt's prefilled KV is stored
    in the engine's page pool and its first token decoded at position
    ``len(prompt)``, the step that produced its second token in the
    drain.  Returns the worst error."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.paged_cache import pages_for

    sched = engine._ensure_scheduler()
    pool = sched.paged
    mb, ps = sched.cc.max_batch, pool.page_size
    toks = np.zeros((mb, 1), np.int32)
    tables = np.zeros((mb, sched.max_pages), np.int32)
    kv_lens = np.zeros(mb, np.int32)
    write_pids = np.full(mb, pool.scratch, np.int32)
    write_offs = np.zeros(mb, np.int32)
    held = []
    for row, h in enumerate(handles):
        prompt = np.asarray(h.prompt, np.int32)
        plen = len(prompt)
        _, rcache = engine._prefill(engine.params, jnp.asarray(prompt[None]))
        pages = pool.alloc(pages_for(plen + 1, ps))
        held += pages
        pool.store(prompt, pages, 0, rcache["k"][:, 0], rcache["v"][:, 0])
        toks[row, 0] = h.out_tokens[0]
        tables[row, :len(pages)] = pages
        kv_lens[row] = plen
        write_pids[row] = pages[plen // ps]
        write_offs[row] = plen % ps
    args = [jnp.asarray(a) for a in (toks, tables, kv_lens, write_pids,
                                     write_offs)]
    primary, degraded = sched._paged_fns()
    # [0]: the logits; each step's new pools are dropped at once
    got = primary(engine.params, pool.k_pages, pool.v_pages, *args)[0]
    want = degraded(engine.params, pool.k_pages, pool.v_pages, *args)[0]
    pool.release(held)
    worst = 0.0
    for row, h in enumerate(handles):
        rel = rel_l2(f"req{h.rid} decode", got[row], want[row],
                     cfg.vocab_size)
        worst = max(worst, rel)
        log(f"req{h.rid}: decode logits rel L2 {rel:.6f} (kernel vs xla); "
            f"second token drain={h.out_tokens[1]} kernel="
            f"{int(np.argmax(np.asarray(got[row])))} xla="
            f"{int(np.argmax(np.asarray(want[row])))}")
    return worst


def compare_with_xla(cfg, engine, handles) -> None:
    """The kernel path's prefill and decode logits against the XLA
    path's, on the same chip, within ``REL_L2_BOUND``."""
    worst = max(compare_prefill(cfg, engine, handles),
                compare_decode(cfg, engine, handles))
    log(f"worst logits rel L2: {worst:.6f} (bound {REL_L2_BOUND})")
    if worst > REL_L2_BOUND:
        raise SmokeFailure(f"kernel path differs from the xla path: rel "
                           f"L2 {worst:.6f} > {REL_L2_BOUND}")


def run(device) -> None:
    import jax
    import numpy as np

    from repro.core import cost_model
    from repro.models import lm
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.serve.engine import Engine, RequestState
    from repro.serve.scheduler import SchedulerConfig

    hw = cost_model.hardware_for(device)
    log(f"hardware model: {hw.name} ({hw.peak_flops:.3g} FLOP/s bf16, "
        f"{hw.hbm_bw:.3g} B/s HBM)")
    log(f"compile cache: {enable_compile_cache()}")

    cfg = build_config()
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(lambda key: lm.init_model(
        cfg, key))(jax.random.PRNGKey(SEED)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"{cfg.name}: {n_params} parameters on device "
        f"({cfg.param_count()} analytic, embedding rows padded to "
        f"{cfg.padded_vocab}); init {time.perf_counter() - t0:.2f} s "
        f"(smoke timing)")
    memory(device, "init")

    engine = Engine(cfg, params, max_len=MAX_LEN, scheduler_config=(
        SchedulerConfig(max_batch=MAX_BATCH, page_size=PAGE_SIZE)))
    compile_steps(engine, PROMPT_LENS)
    memory(device, "engine and page pools")

    rng = np.random.default_rng(SEED)
    handles = [engine.submit(rng.integers(0, cfg.vocab_size, n)
                             .astype(np.int32), NEW_TOKENS)
               for n in PROMPT_LENS]
    t0 = time.perf_counter()
    try:
        engine.drain()
    finally:
        # printed even when the drain raises: a demotion's detail is
        # the kernel error the XLA fallback would otherwise absorb
        dt = time.perf_counter() - t0
        for h in handles:
            log(f"req{h.rid} [{h.state.value}] prompt={len(h.prompt)} "
                f"tokens={len(h.out_tokens)}: {h.out_tokens[:8]}...")
        total = sum(len(h.out_tokens) for h in handles)
        log(f"drain: {total} tokens in {dt:.2f} s (smoke timing, not a "
            f"benchmark)")
        stats = engine.stats()
        report = engine.scheduler_report()
        log(f"scheduler_report: {json.dumps(report, sort_keys=True)}")
        log("engine counters: " + json.dumps(
            {k: stats[k] for k in ("submitted", "completed", "failed",
                                   "evicted", "demotions", "retries",
                                   "degraded_steps", "preemptions",
                                   "spills")}))
        for ev in engine.monitor.events_of("demotion"):
            log(f"DEMOTION at {ev.site} step {ev.step}: {ev.detail}")
    memory(device, "drain")

    bad = [h for h in handles if h.state != RequestState.DONE
           or len(h.out_tokens) != NEW_TOKENS]
    if bad:
        raise SmokeFailure("requests not DONE with their budget: " + ", ".join(
            f"req{h.rid} {h.state.value} {len(h.out_tokens)} tokens "
            f"({h.error})" for h in bad))
    faults = {k: stats[k] for k in ("demotions", "retries",
                                    "degraded_steps", "failed") if stats[k]}
    if faults:
        raise SmokeFailure(f"the kernel path did not hold: {faults}")
    if not report["paged_decode"]:
        raise SmokeFailure("the scheduler did not take the paged datapath")
    compare_with_xla(cfg, engine, handles)
    memory(device, "the xla comparison")


def main() -> int:
    # a new, seed-derived autotune store: the specs come from the
    # explorer in this checkout, not from a store an earlier run left
    store = ROOT / ".smoke" / f"autotune-seed{SEED}.json"
    store.unlink(missing_ok=True)
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(store)

    import jax

    device = jax.devices()[0]
    log(f"device: platform={device.platform} kind={device.device_kind} "
        f"count={len(jax.devices())}")
    if device.platform != "tpu":
        log(f"FAIL: needs a TPU, JAX found {device.platform}")
        return 1
    try:
        run(device)
    except Exception as e:
        if not isinstance(e, SmokeFailure):
            traceback.print_exc()
        log(f"FAIL: {type(e).__name__}: {e}")
        memory(device, "the failure")
        largest_live_arrays()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Runs one cell of the serving benchmark once, on the chip it starts on.

    python3 benchmarks/serve/run.py --workload qwen3-1.7b.chat_backlog \
        --seed 7 --seconds 40 --trace 0

A run finds the accelerator (it never falls back to the CPU), turns on
the compile cache, makes the weights on the device from the seed, builds
the program's ``Engine`` from the configuration, warms every prompt
length the mix uses, and then drives the measured window through
``Engine.submit`` and ``Engine.step``.  Everything before the window is
``setup_s``.  After the window it reads the device's peak memory, frees
the program, and compares the served tokens of a seeded sample of
requests with a plain float32 reference (``references/``).  The last
line of stdout is one JSON object; the numbers compared, each with its
limit, are the last lines of stderr and the last key of that object.

``--trace 1`` records the window with the profiler and reports the
per-layer metrics (``metrics/<name>.py``) in place of the end-to-end
ones.  ``--control 1`` puts the fp8 control in the program's place in
the comparison: the same reference scores the control's tokens at the
same positions, its numbers are judged against the same limits, and the
run comes out not correct.  The benchmark's own runs leave it off.
"""
from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for _p in (str(ROOT / "src"), str(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

import catalog  # noqa: E402
import serving  # noqa: E402
import tracing  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

CACHE = HERE / ".cache"
AFTER_WINDOW_S = 60.0          # wait for first tokens of requests due
SAMPLE_TOKENS = 1000           # served tokens the reference compares, at least
SAMPLE_MIN, SAMPLE_MAX = 12, 32  # finished requests in that sample
MIN_TOKENS_COMPARED = 200


class NoAccelerator(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def accelerator(chips: int):
    """The devices a cell runs on; no accelerator, or too few, raises."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoAccelerator("JAX found no accelerator, only the CPU")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Counts traces and compiles while ``active``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax

        self.active = False
        self.counts = {e: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_: Any) -> None:
        if self.active and event in self.counts:
            self.counts[event] += 1

    def summary(self) -> str:
        return (f"compiles in the window: "
                f"{self.counts[self.EVENTS[0]]} backend compiles, "
                f"{self.counts[self.EVENTS[1]]} traces")


@dataclasses.dataclass
class RunView:
    """What a metric reader sees of one run."""
    config: Dict[str, Any]
    mix: traffic.Mix
    peak: Dict[str, float]
    flows: List[serving.Flow]
    steps: List[serving.Step]          # the window's steps
    t0: float
    t1: float
    trace: Optional[tracing.Trace]

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


def end_to_end(view: RunView, setup_s: float) -> Dict[str, Optional[float]]:
    flows, t0, t1 = view.flows, view.t0, view.t1
    gaps = serving.inter_token_gaps(flows, t0, t1)
    out = {
        "output_tokens_per_s": serving.window_tokens(flows, t0, t1)
        / view.window_s,
        "itl_p95_ms": _ms(serving.percentile(gaps, 95)),
        "itl_p99_ms": _ms(serving.percentile(gaps, 99)),
        "setup_s": setup_s,
    }
    if gaps:
        p50, p90, p95, p99 = np.percentile(gaps, [50, 90, 95, 99]) * 1e3
        log(f"itl ms over {len(gaps)} gaps: p50 {p50:.1f}, p90 {p90:.1f}, "
            f"p95 {p95:.1f}, p99 {p99:.1f}")
    if view.mix.arrival == "poisson":
        ttft = serving.ttfts(flows, t0, t1)
        out["ttft_mean_ms"] = _ms(float(np.mean(ttft)) if ttft else None)
        if ttft:
            p50, p75, p90 = np.percentile(ttft, [50, 75, 90]) * 1e3
            log(f"ttft ms over {len(ttft)} requests due: mean "
                f"{out['ttft_mean_ms']:.1f}, p50 {p50:.1f}, p75 {p75:.1f}, "
                f"p90 {p90:.1f}")
    return out


def _ms(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else seconds * 1e3


def sample(flows: List[serving.Flow], seed: int) -> List[serving.Flow]:
    """A seeded sample of finished requests with the longest among them."""
    served = [f for f in flows if f.state == "done" and f.handle.out_tokens]
    if not served:
        return []
    served.sort(key=lambda f: (-len(f.handle.out_tokens), f.request.index))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    rest = [served[i + 1] for i in rng.permutation(len(served) - 1)]
    picked, tokens = [], 0
    for f in [served[0]] + rest:
        if len(picked) >= SAMPLE_MAX or (
                tokens >= SAMPLE_TOKENS and len(picked) >= SAMPLE_MIN):
            break
        picked.append(f)
        tokens += len(f.handle.out_tokens)
    return picked


def compare(cell: catalog.Cell, seed: int, items, control: bool
            ) -> Dict[str, float]:
    """Widest gap by which a served token's logit lies below the
    reference's best at its position, over the sample.  With ``control``
    the control's token at each position stands in the program's place,
    and the program's own numbers are kept under ``program_``."""
    ref = catalog.reference(cell.config["reference"])
    scored = ref.score(cell.config, seed, items, control=control)

    def numbers(key):
        gaps = np.concatenate([s["best"] - s[key] for s in scored])
        return {"logit_gap_max": float(np.max(gaps)),
                "tokens_compared": float(len(gaps)),
                "tokens_not_reference_best": float(np.sum(gaps > 0))}

    if not control:
        return numbers("at")
    return {**numbers("ctl_at"),
            **{"program_" + k: v for k, v in numbers("at").items()}}


def checks(cell: catalog.Cell, compared: Dict[str, float],
           failed: int) -> Dict[str, Dict[str, Any]]:
    limit = cell.limits.get("logit_gap_max", {}).get("limit")
    gap = compared.get("logit_gap_max", math.inf)
    n = compared.get("tokens_compared", 0.0)
    return {
        "requests_failed": {"value": failed, "limit": 0,
                            "ok": failed == 0},
        "tokens_compared": {"value": n, "limit": MIN_TOKENS_COMPARED,
                            "ok": n >= MIN_TOKENS_COMPARED},
        "logit_gap_max": {"value": gap, "limit": limit,
                          "ok": limit is not None and gap <= limit},
    }


def per_layer(cell: catalog.Cell, view: RunView) -> Dict[str, Any]:
    out = {}
    for m in cell.per_layer:
        value = catalog.metric_reader(m["name"]).read(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(trace: tracing.Trace) -> Dict[str, List]:
    return {"device_ops": [[n, s] for n, s in trace.op_seconds()[:10]],
            "idle_gaps": [[n, s] for n, s in trace.idle_gaps()[:10]]}


def build_engine(cell: catalog.Cell, seed: int):
    import jax

    from repro.models import lm
    from repro.serve.engine import Engine
    from repro.serve.scheduler import SchedulerConfig

    import weights

    cfg = catalog.arch_config(cell.config)
    params = weights.make_params(cell.config, seed)
    weights.check_layout(params, jax.eval_shape(
        lambda k: lm.init_model(cfg, k), jax.random.PRNGKey(0)))
    s = cell.config["serving"]
    engine = Engine(cfg, params, max_len=s["max_len"],
                    scheduler_config=SchedulerConfig(
                        max_batch=s["max_batch"], page_size=s["page_size"],
                        prefill_chunk=s["prefill_chunk"],
                        prefix_reuse=s["prefix_reuse"]))
    log(f"{cell.config_name}: {weights.count(params)} parameters on the "
        f"device, {cfg.n_layers} layers")
    return engine


def run_cell(cell: catalog.Cell, mix: traffic.Mix, seed: int,
             seconds: float, trace: bool, devices, control: bool = False,
             process_start: float = PROCESS_START) -> Dict[str, Any]:
    """One run of a cell on ``devices``; returns the result object."""
    import jax

    counter = CompileCounter()
    # off the chip (the CPU tests) the peaks are the modelled v5e's
    peak = work.peaks(devices[0].device_kind if devices[0].platform != "cpu"
                      else "TPU v5 lite")
    s = cell.config["serving"]
    vocab = cell.config["vocab_size"]
    engine = build_engine(cell, seed)
    driver = serving.Driver(engine, s["max_batch"])
    warm_rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    driver.warm([warm_rng.integers(0, vocab, n).astype(np.int32)
                 for n in mix.prompt_lengths])
    source = traffic.stream(mix, seed, vocab)
    depth = mix.queued_per_row * s["max_batch"]
    if mix.arrival == "backlog":
        driver.fill(source, depth)
    log(f"prompt buckets warmed: {mix.prompt_lengths}")

    profiler = tracing.Profiler(str(CACHE / "trace" / cell.name))
    if trace:
        profiler.start()
    first = len(driver.steps)
    counter.active = True
    t0 = time.monotonic()
    setup_s = t0 - process_start
    with tracing.span(tracing.WINDOW_SPAN):
        if mix.arrival == "backlog":
            t1 = driver.backlog_window(source, depth, seconds, t0)
        else:
            t1 = driver.open_window(source, seconds, t0)
    counter.active = False
    window_steps = driver.steps[first:]
    trace_data = None
    if trace:
        path = profiler.stop()
        trace_data = tracing.reduce(path) if path else None
    log(counter.summary())
    if mix.arrival != "backlog":
        driver.finish_first_tokens(t1, AFTER_WINDOW_S)
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)
    stats = engine.stats()
    log(f"engine: demotions {stats['demotions']}, retries "
        f"{stats['retries']}, degraded steps {stats['degraded_steps']}, "
        f"preemptions {stats['preemptions']}, token restarts "
        f"{driver.resets}; {len(window_steps)} steps in the window")
    if window_steps:
        took = np.asarray([st.end - st.begin for st in window_steps])
        log(f"step seconds: median {np.median(took):.4f}, p95 "
            f"{np.percentile(took, 95):.4f}, max {took.max():.4f}; "
            f"{int(np.sum(took > 1.0))} steps over 1 s")

    flows = list(driver.flows)
    view = RunView(cell.config, mix, peak, flows, window_steps, t0, t1,
                   trace_data)
    summary = serving.summary(flows)
    # open loop: a request due in the window that never got its first
    # token counts as failed; a backlog's queue is work not yet due
    unserved = 0 if mix.arrival == "backlog" else sum(
        1 for f in serving.due_in(flows, t0, t1)
        if not f.token_times and f.state not in serving.FAILED)
    failed = summary["failed"] + driver.rejected + unserved
    attempted = summary["requests"] + driver.rejected
    if trace:
        metrics = per_layer(cell, view)
    else:
        e2e = end_to_end(view, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end
                   if e2e.get(m["name"]) is not None
                   and math.isfinite(e2e[m["name"]])}

    picked = sample(flows, seed)
    items = [(np.asarray(f.request.prompt), list(f.handle.out_tokens))
             for f in picked]
    # free the program before the reference: its peak is already read
    for f in flows:
        f.handle.engine = None
    del engine, driver, view
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    log(f"after freeing the program: {live} B in live arrays; reference "
        f"over {len(items)} requests, "
        f"{sum(len(t) for _, t in items)} served tokens")
    t_ref = time.monotonic()
    compared = compare(cell, seed, items, control) if items else {}
    log(f"reference took {time.monotonic() - t_ref:.1f} s over "
        f"{sum(len(p) + len(t) - 1 for p, t in items)} positions")
    result: Dict[str, Any] = {
        "correct": False, "attempted": attempted, "failed": failed,
        "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": int(memory_peak)},
    }
    if trace_data is not None:
        result["device"]["busy_s"] = trace_data.busy_s()
        result["device"]["window_s"] = trace_data.window_s
        result["breakdown"] = breakdown(trace_data)
    what = " (the fp8 control in the program's place)" if control else ""
    log(f"compared{what}: {json.dumps(compared)}")
    result["checks"] = checks(cell, compared, failed)
    result["correct"] = all(c["ok"] for c in result["checks"].values())
    return result


def finite(value):
    """The result with every non-finite number as null: strict JSON."""
    if isinstance(value, dict):
        return {k: finite(v) for k, v in value.items()}
    if isinstance(value, list):
        return [finite(v) for v in value]
    if isinstance(value, float) and not math.isfinite(value):
        return None
    return value


def print_checks(result: Dict[str, Any]) -> None:
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']}) "
            f"{'ok' if c['ok'] else 'FAILED'}")


def main(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)
    try:
        cell = catalog.find_cell(args.workload)
        mix = catalog.mix(cell)
        devices = accelerator(cell.chips)
    except (NoAccelerator, KeyError, FileNotFoundError, ValueError) as e:
        log(f"cannot run {args.workload}: {e}")
        return 2
    CACHE.mkdir(exist_ok=True)
    os.environ.setdefault("REPRO_AUTOTUNE_CACHE", str(CACHE / "autotune.json"))
    from repro.runtime.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {devices[0].platform} {devices[0].device_kind} x "
        f"{len(devices)}")
    result = run_cell(cell, mix, args.seed, args.seconds, bool(args.trace),
                      devices, control=bool(args.control))
    print_checks(result)
    print(json.dumps(finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

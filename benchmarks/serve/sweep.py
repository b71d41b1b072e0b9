#!/usr/bin/env python3
"""Finds the knee of an open-loop cell: the highest offered rate the
system sustains without a growing backlog, by one sweep on the chip.

    python3 benchmarks/serve/sweep.py --workload mistral-nemo-12b.code_open \
        --seeds 5 --seconds 30 --rates 1,1.5,2,2.5,3

One process builds the cell's engine once, warms it, and offers each rate
in turn for ``--seconds``, on each seed, draining what is left between
windows.  For each it prints the requests offered and served in the
window, the queue left at its end, and the time to first token of the
first and the second half of the requests due in it: a backlog that grows
shows as a second half slower than the first and a queue that does not
empty.  ``offered/<cell>.json`` is then set by hand to four fifths of the
knee.  ``--paths seed`` draws each window's arrival path from its seed
in place of the mix's fixed ``path_seed``, to show what one fixed path
does to the time to first token against fresh Poisson paths.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import numpy as np

import run  # noqa: F401  (sets the import path)
import catalog
import serving
import traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--paths", choices=("mix", "seed"), default="mix")
    args = ap.parse_args(argv)
    cell = catalog.find_cell(args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    seeds = [int(x) for x in args.seeds.split(",")]
    mix = catalog.mix(cell) if cell.offered else traffic.load(
        catalog.traffic_file(cell.traffic), cell.traffic, rates[0])
    if mix.arrival != "poisson":
        raise SystemExit(f"{args.workload}: a backlog mix has no knee")
    if args.paths == "seed":
        mix = dataclasses.replace(mix, path_seed=None)
    devices = run.accelerator(cell.chips)
    from repro.runtime.compile_cache import enable_compile_cache

    run.CACHE.mkdir(exist_ok=True)
    enable_compile_cache()
    s = cell.config["serving"]
    vocab = cell.config["vocab_size"]
    engine = run.build_engine(cell, seeds[0])
    driver = serving.Driver(engine, s["max_batch"])
    rng = np.random.default_rng(np.random.SeedSequence([seeds[0], 1]))
    driver.warm([rng.integers(0, vocab, n).astype(np.int32)
                 for n in mix.prompt_lengths])
    rows = []
    for rate in rates:
        for seed in seeds:
            offered = dataclasses.replace(mix, rate_per_s=rate)
            driver.flows.clear()
            driver.steps.clear()
            t0 = time.monotonic()
            t1 = driver.open_window(traffic.stream(offered, seed, vocab),
                                    args.seconds, t0)
            left = driver.queued()
            due = serving.due_in(driver.flows, t0, t1)
            driver.drain()
            half = len(due) // 2
            ttft = np.asarray([f.token_times[0] - f.due for f in due]) * 1e3
            row = {"rate_per_s": rate, "seed": seed, "paths": args.paths,
                   "offered": len(due),
                   "served_first_token_in_window": sum(
                       1 for f in due if f.token_times[0] <= t1),
                   "queued_at_window_end": left,
                   "ttft_p50_first_half_ms": float(np.median(ttft[:half])),
                   "ttft_p50_second_half_ms": float(np.median(ttft[half:])),
                   "ttft_mean_ms": float(np.mean(ttft)),
                   "ttft_p90_ms": float(np.percentile(ttft, 90)),
                   "steps": len(driver.steps),
                   "step_ms_median": 1e3 * float(np.median(
                       [st.end - st.begin for st in driver.steps]))}
            rows.append(row)
            print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "device":
                      devices[0].device_kind, "sweep": rows}), flush=True)
    return 0

if __name__ == "__main__":
    sys.exit(main())

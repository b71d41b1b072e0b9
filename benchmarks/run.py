"""Benchmark runner: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (see benchmarks/common.py for
column semantics).  The roofline table additionally requires dry-run
artifacts (python -m repro.launch.dryrun --all); it is skipped with a
note if they are absent.

``--suites a,b`` runs a comma-separated subset (CI smoke uses
``--suites fig2_basic_dataflows,fused_epilogue,fused_conv,binary``).

``--out-dir DIR`` redirects the ``BENCH_*.json`` files the JSON-writing
suites (fused_epilogue, fused_conv, binary) produce into ``DIR`` instead
of overwriting the committed repo-root baselines — this is how CI
generates the fresh measurements ``benchmarks/check_regression.py``
gates on (and uploads as a workflow artifact).
"""
from __future__ import annotations

import argparse
import inspect
import os
import sys
import traceback


def main(argv=None) -> None:
    from benchmarks import (
        bench_attention,
        bench_basic_dataflows,
        bench_binary,
        bench_conv,
        bench_e2e_int8,
        bench_extended_dataflows,
        bench_fused,
        bench_heuristics,
        bench_roofline,
        bench_trace,
    )

    suites = [
        ("fig2_basic_dataflows", bench_basic_dataflows.run),
        ("fig7_extended_dataflows", bench_extended_dataflows.run),
        ("table1_heuristics", bench_heuristics.run),
        ("fig8_e2e_int8", bench_e2e_int8.run),
        ("fig9_binary", bench_binary.run),
        ("binary", bench_binary.run_smoke),
        ("attention", bench_attention.run_smoke),
        ("fused_epilogue", bench_fused.run),
        ("fused_conv", bench_conv.run),
        ("roofline", bench_roofline.run),
        ("trace_spans", bench_trace.run),
    ]
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--suites", default=None,
        help="comma-separated subset of: "
             + ",".join(name for name, _ in suites),
    )
    ap.add_argument(
        "--out-dir", default=None,
        help="write BENCH_*.json outputs here instead of the repo root "
             "(suites without a JSON artifact are unaffected)",
    )
    args = ap.parse_args(argv)
    if args.suites:
        wanted = set(args.suites.split(","))
        unknown = wanted - {name for name, _ in suites}
        if unknown:
            ap.error(f"unknown suites: {sorted(unknown)}")
        suites = [(n, f) for n, f in suites if n in wanted]
    if args.out_dir:
        os.makedirs(args.out_dir, exist_ok=True)

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        print(f"# --- {name} ---")
        kw = {}
        if args.out_dir and "out_path" in inspect.signature(fn).parameters:
            default = inspect.signature(fn).parameters["out_path"].default
            kw["out_path"] = os.path.join(args.out_dir,
                                          os.path.basename(default))
        try:
            fn(**kw)
        except Exception as e:
            failures += 1
            print(f"# {name} FAILED: {type(e).__name__}: {e}")
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()

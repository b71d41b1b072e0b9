"""Whole step: model FLOPs of the tokens processed in the window (every
matmul, and attention at each token's real context; ``work.py``) over
the window's length times the chip's bf16 peak (%)."""
import work


def read(run):
    flops = 0.0
    for s in run.steps:
        if s.prefill_plen:
            flops += work.prefill_flops(run.config, s.prefill_plen)
        flops += sum(work.decode_flops(run.config, c) for c in s.contexts)
    if not flops:
        return None
    return 100.0 * flops / (run.window_s * run.peak["bf16_flops_per_s"])

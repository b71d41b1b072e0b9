"""Kernels: the paged decode attention kernel's share of its roofline (%).
The least time is what the bytes and operations of each row's real
context need at the chip's peaks (``work.paged_attention_call``), once
per layer and step; the kernel's time is its device time in the decode
programs of the window."""
import tracing
import work


def read(run):
    if run.trace is None:
        return None
    kernel_s = tracing.kernel_seconds(run.trace, tracing.DECODE_PROGRAM,
                                      tracing.PAGED_ATTENTION_KERNEL)
    least = 0.0
    for s in run.steps:
        if s.contexts:
            flops, nbytes = work.paged_attention_call(run.config, s.contexts)
            least += run.config["num_hidden_layers"] * work.least_seconds(
                flops, nbytes, run.peak)
    if not kernel_s or not least:
        return None
    return 100.0 * least / kernel_s

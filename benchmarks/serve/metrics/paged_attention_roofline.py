"""Kernels: the paged decode attention kernel's share of its roofline (%).
The least time is what the bytes and operations of each row's real
context need at the chip's peaks (``work.paged_attention_call``, summed
over the layers), once per step; the kernel's time is its device time in
the decode programs of the window."""
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
            least += work.least_seconds(
                *work.paged_attention_call(run.config, s.contexts), run.peak)
    if not kernel_s or not least:
        return None
    return 100.0 * least / kernel_s

"""Kernels: the dataflow GEMM kernels' share of their roofline inside the
prefill programs (%).  The least time is 2*M*K*N and each operand's
bytes at the chip's peaks (``work.gemm``) for the three SwiGLU GEMMs of
every layer of every prompt prefilled in the window; the kernels' time
is their device time in the prefill programs."""
import tracing
import work


def read(run):
    if run.trace is None:
        return None
    kernel_s = tracing.kernel_seconds(run.trace, tracing.PREFILL_PROGRAM,
                                      tracing.MATMUL_KERNEL)
    least = 0.0
    for s in run.steps:
        if s.prefill_plen:
            for m, k, n in work.mlp_gemms(run.config, s.prefill_plen):
                least += run.config["num_hidden_layers"] * work.least_seconds(
                    *work.gemm(run.config, m, k, n), run.peak)
    if not kernel_s or not least:
        return None
    return 100.0 * least / kernel_s

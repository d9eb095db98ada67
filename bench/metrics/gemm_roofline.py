"""Spike GEMMs (tokenizer convs 1-3 and the blocks' q/k/v/proj/fc1/fc2): the
least time their FLOPs and bytes allow at the chip's peaks, over the device
time of the spike-GEMM kernels (HLO instructions ``*spike_matmul_op*``), in percent."""

from benchlib import traces, work

KERNELS = "*spike_matmul_op*"


def read(ctx):
    lo, hi = ctx["lo"], ctx["hi"]
    _, calls = traces.span_ns(ctx["trace"], "bench.request", lo, hi)
    kernel_s = traces.kernel_ns(ctx["trace"]["device"], KERNELS, lo, hi) / 1e9
    if calls == 0 or kernel_s == 0:
        return None
    least, _bound = work.gemm_least_s(ctx["arch"], ctx["batch"],
                                      ctx["peaks"]["bf16_flops_per_s"],
                                      ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * calls * least / kernel_s

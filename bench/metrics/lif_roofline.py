"""LIF epilogues (every LIF, LIF+IAND and pack dispatch): the least time of
their bytes (float32 drive read, packed skip words read where the AND-NOT
residual is fused, packed words written) at the chip's HBM bandwidth, over
the device time of the LIF kernels (HLO instructions ``lif*_op*``), in percent."""

from benchlib import traces, work

KERNELS = "lif*_op*"


def read(ctx):
    lo, hi = ctx["lo"], ctx["hi"]
    _, calls = traces.span_ns(ctx["trace"], "bench.request", lo, hi)
    kernel_s = traces.kernel_ns(ctx["trace"]["device"], KERNELS, lo, hi) / 1e9
    if calls == 0 or kernel_s == 0:
        return None
    least = work.lif_least_s(ctx["arch"], ctx["batch"], ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * calls * least / kernel_s

"""Spiking self-attention (every block's ``block{i}/ssa`` scope): the least
time of its FLOPs and bytes at the chip's peaks (:mod:`benchlib.ssa_work`),
over the device time of the operations in those scopes (the compiled
executor's scope table, :mod:`benchlib.scopes`), in percent."""

from benchlib import scopes, ssa_work, traces

SCOPE = "block*/ssa"


def read(ctx):
    lo, hi = ctx["lo"], ctx["hi"]
    _, calls = traces.span_ns(ctx["trace"], "bench.request", lo, hi)
    scope_s = scopes.scope_ns(ctx["trace"]["device"], ctx["op_scopes"], SCOPE, lo, hi) / 1e9
    if calls == 0 or scope_s == 0:
        return None
    least, _bound = ssa_work.ssa_least_s(ctx["arch"], ctx["batch"],
                                         ctx["peaks"]["bf16_flops_per_s"],
                                         ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * calls * least / scope_s

"""Dense-equivalent model FLOPs of every executor call in the traced window,
over the time spent inside their requests (from the image's copy-in to its
logits on the host, the ``bench.request`` spans) times the chip's bf16
peak, in percent.  Time between requests, where a client that sends at a
fixed rate waits for the next frame, is not the executor's."""

from benchlib import traces, work


def read(ctx):
    request_ns, calls = traces.span_ns(ctx["trace"], "bench.request", ctx["lo"], ctx["hi"])
    if calls == 0:
        return None
    flops = calls * work.model_flops(ctx["arch"], ctx["batch"])
    return 100.0 * flops / (request_ns / 1e9 * ctx["peaks"]["bf16_flops_per_s"])

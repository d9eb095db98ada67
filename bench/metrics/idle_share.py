"""Share of the time inside requests (the ``bench.request`` spans, from the
image's copy-in to its logits on the host) in which no operation ran on the
device, in percent.  Time between requests, where a client that sends at a
fixed rate waits for the next frame, is left out, so a faster executor
cannot read as more idle."""

from benchlib import traces


def read(ctx):
    lo, hi = ctx["lo"], ctx["hi"]
    request_ns, count = traces.span_ns(ctx["trace"], "bench.request", lo, hi)
    if count == 0:
        return None
    idle_ns, _ = traces.idle_ns_in_spans(ctx["trace"], "bench.request", lo, hi)
    return 100.0 * idle_ns / request_ns

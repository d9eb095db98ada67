"""Mean host time per request in which the device is idle: copying the image
in, dispatching the executor and fetching the logits back, from the
benchmark's ``bench.request`` spans and the device's busy intervals on the
profiler's clock."""

from benchlib import traces


def read(ctx):
    idle_ns, count = traces.idle_ns_in_spans(ctx["trace"], "bench.request",
                                             ctx["lo"], ctx["hi"])
    if count == 0:
        return None
    return idle_ns / count / 1e6

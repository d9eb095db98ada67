"""Mean time of the traced window's requests (``bench.request`` spans), in ms."""

from benchlib import traces


def read(ctx):
    total, count = traces.span_ns(ctx["trace"], "bench.request", ctx["lo"], ctx["hi"])
    return total / count / 1e6 if count else None

"""95th percentile of the request latencies, each from when the request was
due to when its logits were on the host, over the requests of a traced run
that were sent before the profiler started (the tracer slows the rest).
The same quantity as the end-to-end tail, without a bound: its runs swing
with the number of the runtime's stalls of about 120 ms that fall in a
window."""

import statistics

MIN_REQUESTS = 20


def read(ctx):
    rows = ctx["rows"][:ctx["untraced"]]
    if len(rows) < MIN_REQUESTS:
        return None
    lat = [(done - due) * 1e3 for _, due, done, _ in rows]
    return statistics.quantiles(lat, n=20, method="inclusive")[-1]

"""Peak device memory in use over the run (``memory_stats()``), in GiB."""


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return peak / 2 ** 30 if peak else None

"""From a profiler trace to numbers: device busy time, kernel time, idle
gaps and what the host was doing in them.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
two lists of ``(name, start_ns, duration_ns)``: the operations that ran on
the device (the ``XLA Ops`` line of each TPU plane) and the host spans that
the benchmark opened with ``jax.profiler.TraceAnnotation`` (names starting
``bench.``).  Both are on the profiler's one clock.  Everything else here is
arithmetic on those lists, so the tests check it on a small recorded trace.
"""

from __future__ import annotations

import bisect
import fnmatch
import glob
import os

DEVICE_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def op_name(event_name: str) -> str:
    """The HLO instruction's name: a TPU trace names each operation by its
    whole HLO text (``%packed_spike_matmul_op.51 = f32[...] custom-call(...)``),
    and a Pallas kernel's instruction carries the name of the op that
    launched it."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str) -> dict:
    """{"device": [...], "spans": [...], "devices": n, "lines": {...}} from
    the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    prof = ProfileData.from_file(files[-1])
    device, spans, lines, devices = [], [], {}, 0
    for plane in prof.planes:
        on_tpu = plane.name.startswith("/device:TPU:")
        devices += on_tpu and plane.name.count(":") == 2
        for line in plane.lines:
            events = list(line.events)
            lines[f"{plane.name} | {line.name}"] = len(events)
            if on_tpu and line.name == DEVICE_LINE:
                device += [(op_name(e.name), e.start_ns, e.duration_ns) for e in events]
            elif not on_tpu:
                spans += [(e.name, e.start_ns, e.duration_ns) for e in events
                          if e.name.startswith(SPAN_PREFIX)]
    device.sort(key=lambda e: e[1])
    spans.sort(key=lambda e: e[1])
    return {"device": device, "spans": spans, "devices": max(devices, 1),
            "lines": lines}


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted [start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_ns(device, lo: float, hi: float) -> float:
    """Time in [lo, hi) in which some operation ran on the device."""
    return sum(e - s for s, e in clip(union((s, s + d) for _, s, d in device), lo, hi))


def window(trace: dict) -> tuple[float, float]:
    """The traced window: from the first to the end of the last request span."""
    reqs = [(s, s + d) for n, s, d in trace["spans"] if n == "bench.request"]
    if not reqs:
        raise ValueError("the trace holds no bench.request span")
    return reqs[0][0], max(e for _, e in reqs)


def span_ns(trace: dict, name: str, lo: float, hi: float) -> tuple[float, int]:
    """(total time of the spans called ``name`` that lie whole in [lo, hi],
    their count)."""
    inside = [d for n, s, d in trace["spans"] if n == name and s >= lo and s + d <= hi]
    return float(sum(inside)), len(inside)


def kernel_ns(device, pattern: str, lo: float, hi: float) -> float:
    """Device time of the operations whose name matches the glob ``pattern``."""
    return sum(min(s + d, hi) - max(s, lo) for n, s, d in device
               if fnmatch.fnmatchcase(n, pattern) and s + d > lo and s < hi)


def top_ops(device, lo: float, hi: float, n: int = 10) -> list[list]:
    """The ``n`` operation names with the most device time, in seconds."""
    total: dict[str, float] = {}
    for name, s, d in device:
        if s + d > lo and s < hi:
            total[name] = total.get(name, 0.0) + min(s + d, hi) - max(s, lo)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def gaps(device, lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle intervals of the device inside [lo, hi)."""
    busy = clip(union((s, s + d) for _, s, d in device), lo, hi)
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def innermost_span(spans, at: float) -> str:
    """Name of the innermost (latest-starting) host span holding time ``at``."""
    best = None
    for name, s, d in spans:
        if s > at:
            break
        if s <= at < s + d:
            best = name
    return best or "no span"


def idle_gaps(trace: dict, lo: float, hi: float, n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps, each named by the host span at its
    midpoint, in seconds."""
    longest = sorted(gaps(trace["device"], lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[innermost_span(trace["spans"], (s + e) / 2), (e - s) / 1e9]
            for s, e in longest]


def idle_ns_in_spans(trace: dict, name: str, lo: float, hi: float) -> tuple[float, int]:
    """(device-idle time inside the spans called ``name``, their count)."""
    busy = clip(union((s, s + d) for _, s, d in trace["device"]), lo, hi)
    starts = [s for s, _ in busy]
    idle, count = 0.0, 0
    for n, s, d in trace["spans"]:
        if n != name or s < lo or s + d > hi:
            continue
        count += 1
        first = max(bisect.bisect_right(starts, s) - 1, 0)
        last = bisect.bisect_left(starts, s + d)
        covered = sum(e2 - s2 for s2, e2 in clip(busy[first:last], s, s + d))
        idle += d - covered
    return idle, count

"""Run one benchmark cell once on the chip and print one JSON result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``: configuration x traffic mix) is found by name,
and its configuration's ``family`` names the driver (``bench/families/``)
that knows the model: the run makes the weights and inputs from the seed,
has the driver build and warm what the window drives (set-up), lets it serve
for ``--seconds`` (the window), then has it check what the window served
against the family's own plain reference.  With ``--trace 1`` the profiler
records the end of the window and the result carries the per-layer metrics;
otherwise the end-to-end ones.

It refuses to run (exit 3, no result line) when JAX finds no TPU or fewer
chips than the cell asks for.  Earlier lines report the compile cache's hits
and misses, compilations inside the window, the program's monitoring events
and what the family logs; the last lines of standard error give each number
that decides ``correct`` beside its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from benchlib import spec  # noqa: E402

EXIT_NO_CHIP = 3


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class Counters:
    """Counts of JAX's monitoring events and the seconds of its duration
    events: the compile cache's hits and misses, backend compilations, and
    the program's own (``/repro/engine/compile_plan``,
    ``/repro/engine/ssa_route/*``)."""

    def __init__(self):
        import jax

        self.events = collections.Counter()
        self.seconds = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda event, **_: self.events.update([event]))
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, **_):
        self.events[event] += 1
        self.seconds[event] += secs

    def summary(self) -> dict:
        """{event: (count, summed seconds)}."""
        return {k: (n, self.seconds[k]) for k, n in self.events.items()}

    @property
    def hits(self) -> int:
        return self.events["/jax/compilation_cache/cache_hits"]

    @property
    def misses(self) -> int:
        return self.events["/jax/compilation_cache/cache_misses"]

    @property
    def compiles(self) -> int:
        return self.events["/jax/core/compile/backend_compile_duration"]


def enable_compile_cache() -> str:
    """The program's persistent cache (``$JAX_COMPILATION_CACHE_DIR`` or a
    fixed directory in the checkout), with every executable of the cell
    written to it however quickly it compiled."""
    import jax

    spec.program_on_path()
    from repro.launch.compile_info import enable_compile_cache as program_cache

    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class Window:
    """The measured window, as every family's request loop runs it.

    ``due(offsets)`` yields ``(i, due)`` for each request of the traffic's
    arrivals (:mod:`benchlib.arrivals`; an offset of None is a closed loop,
    due when the client is free) until ``seconds`` have passed since the
    window's ``start``; it waits until each request is due.  With
    ``trace_dir`` the profiler records the window's last ``trace_seconds``
    (``sent``, the requests before that, run untraced) and writes its trace
    once the window has closed.  ``request()`` is the ``bench.request`` span
    that each request's work runs in."""

    def __init__(self, seconds: float, trace_dir: str | None, trace_seconds: float):
        import jax

        self._span = jax.profiler.TraceAnnotation
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.trace_seconds = trace_seconds
        self.start = None
        self.sent = 0
        self.untraced = None

    def due(self, offsets):
        import jax

        lags, scheduled = [], False
        start = self.start = time.perf_counter()
        try:
            for i, offset in enumerate(offsets):
                now = time.perf_counter()
                scheduled |= offset is not None
                due = now if offset is None else start + offset
                if max(now, due) - start >= self.seconds:
                    break
                if (self.trace_dir is not None and self.untraced is None
                        and max(now, due) - start >= self.seconds - self.trace_seconds):
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0     # no Python calls
                    opts.host_tracer_level = 1       # the benchmark's spans, not the runtime's
                    jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
                    self.untraced = self.sent
                    now = time.perf_counter()
                while now < due:
                    now = time.perf_counter()
                lags.append(now - due)
                self.sent += 1
                yield i, due
        finally:
            if self.untraced is not None:
                jax.profiler.stop_trace()
        if scheduled and lags:
            log(f"requests sent after they were due: {sum(g > 1e-3 for g in lags)} of "
                f"{len(lags)} by more than 1 ms, at most {max(lags) * 1e3:.3f} ms")

    def request(self):
        return self._span("bench.request")


def end_to_end(name: str, ctx: dict, family) -> float:
    """The end-to-end metrics, taken by the harness on the host's clock:
    ``setup_s`` and ``latency_p50_ms`` (each request from when it was due to
    when its result was on the host) for every family, the rest by the
    family's driver."""
    if name == "setup_s":
        return ctx["setup_s"]
    if name == "latency_p50_ms":
        return statistics.median([(row[2] - row[1]) * 1e3 for row in ctx["rows"]])
    return family.end_to_end(name, ctx)


def per_layer(cell: str, ctx: dict) -> dict:
    out = {}
    for m in spec.cell_metrics(cell, "per_layer"):
        value = spec.load_metric(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def memory_peak(dev, compiled) -> int:
    """Peak device memory of the run, in bytes.  On the TPU the runtime
    counts buffers (``peak_bytes_in_use``) apart from the region it
    reserves for executors' temporaries (``peak_bytes_reserved``), so the
    peak is their sum; where the compiler's account of an executable
    (arguments, outputs and temporaries, live together while it runs) is
    larger, that is the peak."""
    stats = dev.memory_stats() or {}
    footprint = 0
    for c in compiled:
        mem = c.memory_analysis()
        if mem is not None:
            footprint = max(footprint, mem.argument_size_in_bytes + mem.output_size_in_bytes
                            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    log("memory: " + " ".join(f"{k}={v}" for k, v in sorted(stats.items()))
        + f" executor_footprint={footprint}")
    runtime = stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0)
    return int(max(runtime, footprint))


def log_trace(ctx: dict, kernels: int) -> None:
    """Device operations and Pallas kernels a request, beside the kernels the
    executables hold (a trace that lost events shows fewer, and its rooflines
    read too high), and device ms a request by top-level scope."""
    from benchlib import traces

    tr, lo, hi, table = ctx["trace"], ctx["lo"], ctx["hi"], ctx["op_scopes"]
    _, calls = traces.span_ns(tr, "bench.request", lo, hi)
    if calls == 0:
        return
    ops = kernel_ops = 0
    by_scope = collections.Counter()
    for name, s, d in tr["device"]:
        if s + d <= lo or s >= hi:
            continue
        scope, is_kernel = table.get(name, (None, False))
        ops += 1
        kernel_ops += is_kernel
        by_scope[scope.split("/")[0] if scope else "(none)"] += min(s + d, hi) - max(s, lo)
    log(f"trace: {ops / calls:.1f} device ops and {kernel_ops / calls:.1f} Pallas kernel "
        f"ops a request over {calls} requests; the executables hold {kernels} kernels")
    log("device ms a request by top-level scope: " + " ".join(
        f"{k}={v / calls / 1e6:.4f}" for k, v in sorted(by_scope.items())))


def run_cell(cell: str, cfg: dict, traffic: dict, seed: int, seconds: float,
             trace: bool, *, interpret: bool = False, peaks: dict | None = None,
             fault=None) -> dict:
    """One run of one cell; returns the result object (and ``extras``).

    The configuration's ``family`` names the driver (``bench/families/``)
    that makes the weights and inputs, builds and warms what the window
    drives, serves the window and checks it.  ``interpret`` runs the kernels
    in Pallas interpret mode (tests on the CPU); ``fault`` is handed to the
    family's ``build`` and returns what the window drives in the compiled
    executable's place (the control, and tests that break the timed path)."""
    import jax

    from benchlib import scopes, traces

    family = spec.load_family(cfg["family"])
    counters = Counters()
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    weights = family.make_weights(cfg, seed)
    jax.block_until_ready(weights)
    pool = family.pool(cfg, traffic, seed)
    driven, compiled = family.build(cfg, weights, traffic, pool, interpret=interpret,
                                    fault=fault)
    texts = {name: c.as_text() for name, c in compiled.items()}
    kernels = sum(t.count("tpu_custom_call") for t in texts.values())
    if not interpret and kernels == 0:
        raise RuntimeError("the compiled executables hold no Pallas kernel")
    log(f"cell {cell}: executables {sorted(compiled)} tpu_custom_call={kernels}")
    compiles_before = counters.compiles
    setup_s = time.perf_counter() - T_START
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    window = Window(seconds, trace_dir, traffic["trace_seconds"])
    rows = family.serve(cfg, driven, pool, traffic, seed, window)
    in_window = counters.compiles - compiles_before
    log(f"compile cache {cache_dir}: hits={counters.hits} misses={counters.misses}")
    log(f"compilations inside the window: {in_window}")
    peak = memory_peak(dev, compiled.values())
    op_scopes = {}
    if trace:
        for text in texts.values():
            op_scopes.update(scopes.op_scopes(text))
    ours = {id(a) for a in jax.tree_util.tree_leaves(weights)}
    for leaf in jax.tree_util.tree_leaves(driven):
        if isinstance(leaf, jax.Array) and id(leaf) not in ours:
            leaf.delete()              # free the program's own state
    del compiled, driven, texts

    ctx = {"rows": rows, "start": window.start,
           "untraced": window.sent if window.untraced is None else window.untraced,
           "setup_s": setup_s, "memory_peak_bytes": peak, "events": counters.summary(),
           **family.context(cfg, traffic)}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": family.attempted(traffic, rows),
              "failed": 0, "metrics": {}, "device": device}
    if trace:
        tr = traces.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = traces.window(tr)
        ctx.update(trace=tr, lo=lo, hi=hi, op_scopes=op_scopes,
                   peaks=peaks if peaks is not None else spec.peaks(dev.device_kind))
        device["busy_s"] = traces.busy_ns(tr["device"], lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["metrics"] = per_layer(cell, ctx)
        result["breakdown"] = {"device_ops": traces.top_ops(tr["device"], lo, hi),
                               "idle_gaps": traces.idle_gaps(tr, lo, hi)}
        log(f"trace: {len(tr['device'])} device ops, {len(tr['spans'])} host spans, "
            f"window {device['window_s']:.3f}s, busy {device['busy_s']:.3f}s")
        log_trace(ctx, kernels)
    else:
        for m in spec.cell_metrics(cell, "end_to_end"):
            result["metrics"][m["name"]] = {"value": end_to_end(m["name"], ctx, family),
                                            "unit": m["unit"]}
    log("program events: " + " ".join(
        f"{k}={n}/{s:.3f}s" for k, (n, s) in sorted(ctx["events"].items())
        if not k.startswith("/jax/")))

    numbers, extras = family.check(cfg, traffic, seed, weights, pool, rows)
    result["correct"] = all(v <= lim for v, lim in numbers.values())
    result["failed"] = 0 if result["correct"] else result["attempted"]
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in numbers.items()}
    if trace:
        extras.update(trace=ctx["trace"], op_scopes=op_scopes)
    result["extras"] = extras
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    wl = spec.workload(args.workload)
    cfg = spec.load_config(wl["config"])
    traffic = spec.load_traffic(wl["traffic"])
    import jax

    found = jax.default_backend()
    if found != "tpu" or len(jax.devices()) < wl["chips"]:
        print(f"[bench] refused: cell {args.workload} needs {wl['chips']} TPU "
              f"chip(s); JAX found {len(jax.devices())} {found!r} device(s)",
              file=sys.stderr)
        return EXIT_NO_CHIP
    result = run_cell(args.workload, cfg, traffic, args.seed, args.seconds,
                      bool(args.trace))
    result.pop("extras")
    for k, c in result["checks"].items():
        print(f"[bench] check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"[bench] correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())

"""Run one benchmark cell once on the chip and print one JSON result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``: configuration x traffic mix) is found by name.
The run makes the weights and images from the seed, folds the weights into
the program's ``pallas+packed`` deploy plan, compiles and warms the jitted
executor (set-up), drives it for ``--seconds`` (the window), then checks what
the window served against the benchmark's own plain reference.  With
``--trace 1`` the profiler records the end of the window and the result
carries the per-layer metrics; otherwise the end-to-end ones.

It refuses to run (exit 3, no result line) when JAX finds no TPU or fewer
chips than the cell asks for.  Earlier lines report the compile cache's hits
and misses, compilations inside the window, the batch size and the firing
rate of every stage; the last lines of standard error give each number that
decides ``correct`` beside its limit.
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

import numpy as np  # noqa: E402

from benchlib import spec  # noqa: E402

EXIT_NO_CHIP = 3


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class Counters:
    """Compile-cache hits/misses and backend compilations, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.events = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda event, **_: self.events.update([event]))
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _secs, **_: self.events.update([event]))

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

    from benchlib import cell  # noqa: F401  (puts the program on the path)
    from repro.launch.compile_info import enable_compile_cache as program_cache

    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def image_pool(cfg: dict, traffic: dict, seed: int) -> np.ndarray:
    """(pool, batch, H, W, C) float32 images, uniform in [0, 1), on the host."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    shape = (traffic["pool"], traffic["batch"], cfg["img_size"], cfg["img_size"],
             cfg["in_channels"])
    return rng.random(shape, dtype=np.float32)


def serve(step, params, pool: np.ndarray, traffic: dict, seed: int, seconds: float,
          trace_dir: str | None, trace_seconds: float):
    """The window: one client sends the pool's batches in turn, each as a
    host array that is copied in, run, and whose logits come back to the
    host.  Each request is sent when it is due (:mod:`benchlib.arrivals`;
    in a closed loop, when the last one returned) or as soon as the client
    is free after that, and is timed from when it was due.  With
    ``trace_dir`` the profiler records the window's last ``trace_seconds``
    and writes its trace once the window has closed, so the requests before
    it run untraced.  Returns the window's start, per-request (pool index,
    due, done, logits) and the number of requests sent before the profiler
    started."""
    import jax

    from benchlib import arrivals

    rows, lags = [], []
    untraced = None
    start = time.perf_counter()
    for i, offset in enumerate(arrivals.due_times(traffic, seed)):
        now = time.perf_counter()
        due = now if offset is None else start + offset
        if max(now, due) - start >= seconds:
            break
        if (trace_dir is not None and untraced is None
                and max(now, due) - start >= seconds - trace_seconds):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # no Python calls
            opts.host_tracer_level = 1       # the benchmark's spans, not the runtime's
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            untraced = len(rows)
            now = time.perf_counter()
        while now < due:
            now = time.perf_counter()
        lags.append(now - due)
        j = i % len(pool)
        with jax.profiler.TraceAnnotation("bench.request"):
            with jax.profiler.TraceAnnotation("bench.copy_in"):
                x = jax.device_put(pool[j])
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                out = step(params, x)
            with jax.profiler.TraceAnnotation("bench.fetch"):
                logits = np.asarray(out)
        rows.append((j, due, time.perf_counter(), logits))
    if untraced is not None:
        jax.profiler.stop_trace()
    if traffic["arrival"] != "closed":
        log(f"requests sent after they were due: {sum(g > 1e-3 for g in lags)} of "
            f"{len(rows)} by more than 1 ms, at most {max(lags) * 1e3:.3f} ms")
    return start, rows, len(rows) if untraced is None else untraced


def end_to_end(name: str, ctx: dict) -> float:
    """The end-to-end metrics, taken by the harness on the host's clock."""
    rows = ctx["rows"]
    lat = [(done - due) * 1e3 for _, due, done, _ in rows]
    if name == "setup_s":
        return ctx["setup_s"]
    if name == "images_per_s":
        return len(rows) * ctx["batch"] / (rows[-1][2] - ctx["start"])
    if name == "latency_p50_ms":
        return statistics.median(lat)
    raise KeyError(f"no end-to-end metric {name!r} in the harness")


def check(cfg: dict, traffic: dict, seed: int, params, state, pool,
          rows) -> tuple[dict, dict]:
    """Compare a sample of the images the window served with the plain
    reference.  Returns ({number: (value, limit)}, extras)."""
    import jax
    import jax.numpy as jnp

    from benchlib import model

    arch = model.Arch.from_config(cfg)
    limits = cfg["limits"]
    first: dict[int, np.ndarray] = {}
    mismatched = 0
    for j, _, _, logits in rows:
        if j in first:
            mismatched += not np.array_equal(first[j], logits)
        else:
            first[j] = logits
    served = [(j, r) for j in sorted(first) for r in range(traffic["batch"])]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    pick = rng.choice(len(served), size=min(traffic["sample"], len(served)),
                      replace=False)
    pick = [served[p] for p in sorted(pick)]
    imgs = np.stack([pool[j][r] for j, r in pick])
    got = np.stack([first[j][r] for j, r in pick])

    operands = cfg["precision"]["matmul_operands"]
    folded = jax.jit(model.fold)(params, state)
    ref_fn = jax.jit(lambda f, x: model.forward(None, None, x, arch, operands=operands,
                                                folded=f, rates=True))
    block = traffic["reference_block"]
    refs, rates = [], collections.defaultdict(list)
    for s in range(0, len(imgs), block):
        chunk = imgs[s:s + block]
        pad = block - len(chunk)
        if pad:
            chunk = np.concatenate([chunk, np.repeat(chunk[-1:], pad, 0)])
        out, seen = ref_fn(folded, jnp.asarray(chunk))
        refs.append(np.asarray(out)[:block - pad])
        for k, v in seen.items():
            rates[k].append(float(v))
    ref = np.concatenate(refs)
    extras = {"rates": {k: float(np.mean(v)) for k, v in rates.items()}}
    numbers = gaps(got, ref)
    numbers["repeat_mismatch"] = mismatched
    for k in sorted(set(numbers) - set(limits)):
        log(f"{k} (not compared): {numbers[k]!r}")
    return {k: (v, limits[k]) for k, v in numbers.items() if k in limits}, extras


def gaps(got: np.ndarray, ref: np.ndarray) -> dict:
    """Per image, the widest logit gap over classes as a share of the
    reference logits' RMS; their largest and median over the sample."""
    rms = np.sqrt(np.mean(ref.astype(np.float64) ** 2, axis=1))
    per = np.max(np.abs(got.astype(np.float64) - ref), axis=1) / rms
    return {"gap_max": float(np.max(per)), "gap_median": float(np.median(per))}


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
    peak is their sum; where the compiler's account of the executor
    (arguments, outputs and temporaries, live together while it runs) is
    larger, that is the peak."""
    stats = dev.memory_stats() or {}
    mem = compiled.memory_analysis()
    footprint = 0
    if mem is not None:
        footprint = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    log("memory: " + " ".join(f"{k}={v}" for k, v in sorted(stats.items()))
        + f" executor_footprint={footprint}")
    runtime = stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0)
    return int(max(runtime, footprint))


def run_cell(cell: str, cfg: dict, traffic: dict, seed: int, seconds: float,
             trace: bool, *, interpret: bool = False, peaks: dict | None = None,
             fault=None) -> dict:
    """One run of one cell; returns the result object (and ``extras``).

    ``interpret`` runs the kernels in Pallas interpret mode (tests on the
    CPU); ``fault(compiled)`` returns what the window drives in the
    compiled executor's place (the control, and tests that break the timed
    path)."""
    import jax

    from benchlib import cell as program
    from benchlib import model, traces

    counters = Counters()
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    params, state = program.make_weights(cfg, seed)
    jax.block_until_ready(params)
    with program.precision_context(cfg):
        plan, step = program.build(cfg, params, state, interpret=interpret)
        pool = image_pool(cfg, traffic, seed)
        compiled = step.lower(plan.params, pool[0]).compile()
        kernels = compiled.as_text().count("tpu_custom_call")
        if not interpret and kernels == 0:
            raise RuntimeError("the compiled executor holds no Pallas kernel")
        call = compiled if fault is None else fault(compiled)
        for _ in range(2):
            jax.block_until_ready(call(plan.params, jax.device_put(pool[0])))
        log(f"cell {cell}: batch={traffic['batch']} pool={traffic['pool']} "
            f"arrival={traffic['arrival']} tpu_custom_call={kernels}")
        compiles_before = counters.compiles
        setup_s = time.perf_counter() - T_START
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        start, rows, untraced = serve(call, plan.params, pool, traffic, seed, seconds,
                                      trace_dir, traffic["trace_seconds"])
        in_window = counters.compiles - compiles_before
    log(f"compile cache {cache_dir}: hits={counters.hits} misses={counters.misses}")
    log(f"compilations inside the window: {in_window}")
    peak = memory_peak(dev, compiled)
    ours = {id(a) for a in jax.tree_util.tree_leaves(params)}
    for leaf in jax.tree_util.tree_leaves(plan.params):
        if id(leaf) not in ours:       # free the program's folded weights
            leaf.delete()
    del compiled, call, step, plan

    ctx = {"rows": rows, "start": start, "untraced": untraced, "batch": traffic["batch"],
           "setup_s": setup_s, "memory_peak_bytes": peak,
           "arch": model.Arch.from_config(cfg)}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": len(rows) * traffic["batch"],
              "failed": 0, "metrics": {}, "device": device}
    if trace:
        tr = traces.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = traces.window(tr)
        ctx.update(trace=tr, lo=lo, hi=hi,
                   peaks=peaks if peaks is not None else spec.peaks(dev.device_kind))
        device["busy_s"] = traces.busy_ns(tr["device"], lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["metrics"] = per_layer(cell, ctx)
        result["breakdown"] = {"device_ops": traces.top_ops(tr["device"], lo, hi),
                               "idle_gaps": traces.idle_gaps(tr, lo, hi)}
        log(f"trace: {len(tr['device'])} device ops, {len(tr['spans'])} host spans, "
            f"window {device['window_s']:.3f}s, busy {device['busy_s']:.3f}s")
    else:
        for m in spec.cell_metrics(cell, "end_to_end"):
            result["metrics"][m["name"]] = {"value": end_to_end(m["name"], ctx),
                                            "unit": m["unit"]}

    with program.precision_context(cfg):
        numbers, extras = check(cfg, traffic, seed, params, state, pool, rows)
    rates = extras["rates"]
    log("firing rates: " + " ".join(
        f"{k}={v:.4f}" for k, v in rates.items() if "." not in k))
    log("branch rates (mean over blocks): " + " ".join(
        f"{u}={np.mean([v for k, v in rates.items() if k.endswith('.' + u)]):.4f}"
        for u in ("q", "k", "v", "attn", "proj", "fc1", "fc2")))
    result["correct"] = all(v <= lim for v, lim in numbers.values())
    result["failed"] = 0 if result["correct"] else result["attempted"]
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in numbers.items()}
    if trace:
        extras["trace"] = ctx["trace"]
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

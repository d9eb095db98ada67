"""The vision family: Spike-IAND-Former image classification.

The window drives the program's ``pallas+packed`` deploy plan: the
benchmark's seeded weights folded by ``engine.compile_plan`` and the jitted
executor of ``engine.make_apply_fn``, the same objects
``launch/serve.serve_vision`` serves with.  One request is one batch of
host images, copied in, run, and its logits fetched back.  The plain
reference is :mod:`benchlib.model`.  The contract this module keeps is the
docstring of ``bench/families/__init__.py``.
"""

from __future__ import annotations

import collections
import contextlib
import time

import jax
import numpy as np

from benchlib import model, spec

spec.program_on_path()

GIB = 2 ** 30


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def vision_config(cfg: dict):
    from repro.core.spikformer import SpikformerConfig

    return SpikformerConfig(
        img_size=cfg["img_size"], in_channels=cfg["in_channels"],
        num_classes=cfg["num_classes"], embed_dim=cfg["embed_dim"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        mlp_ratio=cfg["mlp_ratio"], t=cfg["t"], residual=cfg["residual"],
        theta=cfg["theta"], lam=cfg["lam"], attn_scale=cfg["attn_scale"],
        tokenizer_pools=tuple(cfg["tokenizer_pools"]))


def backend(cfg: dict, interpret: bool = False):
    """The configuration's backend with compiled kernels (``interpret=False``
    on the chip; tests on the CPU pass ``True``)."""
    from repro import engine

    be = engine.resolve_backend(cfg["backend"])
    if be.kind != "pallas":
        return be
    return engine.Backend("pallas", interpret=interpret, packed=be.packed,
                          sparse=be.sparse)


def precision_context(cfg: dict):
    """The matmul precision the configuration serves at ("default": JAX's
    own, as a user's call gets it)."""
    prec = cfg["precision"]["matmul"]
    if prec == "default":
        return contextlib.nullcontext()
    return jax.default_matmul_precision(prec)


def make_weights(cfg: dict, seed: int):
    """(params, state) on the device from the seed, in one jitted call:
    seeded conv/linear weights and BatchNorm affine parameters, then the
    BatchNorm running statistics from a seeded calibration batch."""
    arch = model.Arch.from_config(cfg)
    w_cfg = cfg["weights"]

    @jax.jit
    def make(key):
        k_w, k_cal = jax.random.split(key)
        params, state = model.init_weights(k_w, arch, w_cfg)
        cal = jax.random.uniform(k_cal, (w_cfg["calibration_images"], cfg["img_size"],
                                         cfg["img_size"], cfg["in_channels"]))
        return params, model.forward(params, state, cal, arch, calibrate=True)

    return make(model.key_from_seed(seed))


def pool(cfg: dict, traffic: dict, seed: int) -> np.ndarray:
    """(pool, batch, H, W, C) float32 images, uniform in [0, 1), on the host."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    shape = (traffic["pool"], traffic["batch"], cfg["img_size"], cfg["img_size"],
             cfg["in_channels"])
    return rng.random(shape, dtype=np.float32)


def build(cfg: dict, weights, traffic: dict, pool: np.ndarray, *, interpret: bool = False,
          fault=None):
    """((executor call, plan params), {"step": compiled executor}): the
    weights folded into the deploy plan, its jitted executor compiled for one
    batch of the pool and warmed twice (through ``fault`` where given)."""
    from repro import engine

    params, state = weights
    with precision_context(cfg):
        plan = engine.compile_plan(params, state, vision_config(cfg),
                                   backend=backend(cfg, interpret))
        step = jax.jit(engine.make_apply_fn(plan))
        compiled = step.lower(plan.params, pool[0]).compile()
        call = compiled if fault is None else fault(compiled)
        for _ in range(2):
            jax.block_until_ready(call(plan.params, jax.device_put(pool[0])))
    log(f"batch={traffic['batch']} pool={traffic['pool']} arrival={traffic['arrival']}")
    return (call, plan.params), {"step": compiled}


def serve(cfg: dict, driven, pool: np.ndarray, traffic: dict, seed: int, window):
    """One client sends the pool's batches in turn, each as a host array
    that is copied in, run, and whose logits come back to the host.  Rows
    are (pool index, due, done, logits)."""
    from benchlib import arrivals

    call, params = driven
    rows = []
    with precision_context(cfg):
        for i, due in window.due(arrivals.due_times(traffic, seed)):
            j = i % len(pool)
            with window.request():
                with jax.profiler.TraceAnnotation("bench.copy_in"):
                    x = jax.device_put(pool[j])
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    out = call(params, x)
                with jax.profiler.TraceAnnotation("bench.fetch"):
                    logits = np.asarray(out)
            rows.append((j, due, time.perf_counter(), logits))
    return rows


def attempted(traffic: dict, rows) -> int:
    return len(rows) * traffic["batch"]


def end_to_end(name: str, ctx: dict) -> float:
    """``images_per_s``: images whose logits reached the host in the window,
    over the window (its start to the last logits)."""
    rows = ctx["rows"]
    if name == "images_per_s":
        return len(rows) * ctx["batch"] / (rows[-1][2] - ctx["start"])
    raise KeyError(f"no end-to-end metric {name!r} in the vision family")


def context(cfg: dict, traffic: dict) -> dict:
    return {"arch": model.Arch.from_config(cfg), "batch": traffic["batch"]}


def check(cfg: dict, traffic: dict, seed: int, weights, pool, rows) -> tuple[dict, dict]:
    """Compare a sample of the images the window served with the plain
    reference, and log its firing rates.  Returns ({number: (value, limit)},
    {"rates": ...})."""
    import jax.numpy as jnp

    params, state = weights
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
    with precision_context(cfg):
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
    rates = {k: float(np.mean(v)) for k, v in rates.items()}
    numbers = gaps(got, ref)
    numbers["repeat_mismatch"] = mismatched
    for k in sorted(set(numbers) - set(limits)):
        log(f"{k} (not compared): {numbers[k]!r}")
    log("firing rates: " + " ".join(
        f"{k}={v:.4f}" for k, v in rates.items() if "." not in k))
    log("branch rates (mean over blocks): " + " ".join(
        f"{u}={np.mean([v for k, v in rates.items() if k.endswith('.' + u)]):.4f}"
        for u in ("q", "k", "v", "attn", "proj", "fc1", "fc2")))
    return {k: (v, limits[k]) for k, v in numbers.items() if k in limits}, {"rates": rates}


def gaps(got: np.ndarray, ref: np.ndarray) -> dict:
    """Per image, the widest logit gap over classes as a share of the
    reference logits' RMS; their largest and median over the sample."""
    rms = np.sqrt(np.mean(ref.astype(np.float64) ** 2, axis=1))
    per = np.max(np.abs(got.astype(np.float64) - ref), axis=1) / rms
    return {"gap_max": float(np.max(per)), "gap_median": float(np.median(per))}


def control(cfg: dict, seed: int, traffic: dict):
    """A ``fault`` hook: the reference one precision below the
    configuration's (int8 weights where it states bfloat16 operands,
    bfloat16 operands where it states float32), on the benchmark's weights
    for ``seed``, serving each batch in blocks of the mix's
    ``reference_block`` rows."""
    import jax.numpy as jnp

    arch = model.Arch.from_config(cfg)
    block = traffic["reference_block"]
    params, state = make_weights(cfg, seed)
    folded = jax.jit(model.fold)(params, state)
    operands = cfg["precision"]["matmul_operands"]
    if operands == "bfloat16":
        folded, operands = jax.jit(model.quantize_int8)(folded), "float32"
    elif operands == "float32":
        operands = "bfloat16"
    else:
        raise ValueError(f"no control below {operands!r} operands")
    fn = jax.jit(lambda f, x: model.forward(None, None, x, arch, operands=operands,
                                            folded=f))

    def served(_params, x):
        return jnp.concatenate([fn(folded, x[s:s + block])
                                for s in range(0, x.shape[0], block)])

    return lambda _compiled: served


def abstract_plan(cfg: dict):
    """(plan param shapes, plan meta) without any arrays: for compiling for a
    chip that is only described."""
    from repro import engine

    arch = model.Arch.from_config(cfg)
    holder = {}

    def fold(params, state):
        plan = engine.compile_plan(params, state, vision_config(cfg),
                                   backend=backend(cfg))
        holder["meta"] = plan.meta
        return plan.params

    p, s = jax.eval_shape(lambda k: model.init_weights(k, arch, cfg["weights"]),
                          jax.random.PRNGKey(0))
    shapes = jax.eval_shape(fold, p, s)
    return shapes, holder["meta"]


def rehearse(cfg: dict, sizes: list[int], device) -> None:
    """For each batch size, whether the whole jitted executor compiles for
    ``device``, how many Pallas kernels it holds and the compiler's memory
    analysis (temp + arguments + outputs)."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro import engine
    from repro.engine.plan import DeployPlan

    one = SingleDeviceSharding(device)
    plan_shapes, meta = abstract_plan(cfg)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), plan_shapes)
    fn = jax.jit(engine.make_apply_fn(DeployPlan(meta=meta, params=plan_shapes)))
    name = cfg["name"]
    for b in sizes:
        img = jax.ShapeDtypeStruct((b, cfg["img_size"], cfg["img_size"],
                                    cfg["in_channels"]), jnp.float32, sharding=one)
        try:
            compiled = fn.lower(params, img).compile()
        except Exception as e:  # report the compiler's refusal and go on
            print(f"{name} B={b}: DOES NOT COMPILE: {type(e).__name__}: {e}"[:2000])
            continue
        mem = compiled.memory_analysis()
        total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                 + mem.output_size_in_bytes)
        print(f"{name} B={b}: compiles, "
              f"tpu_custom_call={compiled.as_text().count('tpu_custom_call')} "
              f"temp={mem.temp_size_in_bytes / GIB:.3f}GiB "
              f"args={mem.argument_size_in_bytes / GIB:.3f}GiB "
              f"out={mem.output_size_in_bytes / GIB:.4f}GiB "
              f"total={total / GIB:.3f}GiB", flush=True)

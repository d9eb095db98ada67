"""On-chip smoke test: the serving entry points on a TPU, checked end to end.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py              # one chip: vision + spiking-LM phases
    python chip_smoke.py --mesh 2x2   # four chips: mesh-sharded vision only

Default phases (one chip):

* vision: ``serve_vision`` on Spike-IAND-Former 8-768 (224x224, 8 layers,
  d=768, 12 heads, T=4; random weights from ``--seed``) serves 32 images in
  slot batches of 8 under ``pallas``, ``pallas+packed`` and
  ``pallas+packed+sparse`` with compiled (not interpreted) kernels.  Each
  compiled executor must contain Pallas kernels (``tpu_custom_call``), the
  three backends' logits must be bit-identical, and they must match the
  ``jnp`` backend's logits at the engine tests' tolerance (atol 1e-4);
* LM: ``serve_spiking_lm_continuous`` on ``llama3.2-1b_smoke`` under
  ``pallas+packed`` with mixed prompt lengths and chunked prefill; every
  request must complete with the same tokens as the ``jnp`` backend.

``--mesh 2x2`` runs only the mesh-sharded vision phase: 8-768 under
``pallas+packed`` on a 2x2 mesh of four distinct devices, with weights
committed across the mesh and logits bit-identical to single-device serving
in the same process.

Every phase runs at JAX's default matmul precision, as a user's call does.
On the TPU that is not float32: XLA and the Pallas kernels contract with the
same reduced-precision passes, which is why ``jnp`` and the kernels agree
bit for bit there.

Everything runs in this one process, which holds the chip.  The script fails
(non-zero exit, no result line) when JAX finds no TPU or any check fails.
The last line of standard output is the JSON result; wall times on earlier
lines are informational.
"""

from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro import engine  # noqa: E402  (needs src/ on the path)
from repro.launch.compile_info import enable_compile_cache  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    serve_spiking_lm_continuous, serve_vision)

VISION_ARCH = "spike-iand-former-8-768"
VISION_IMAGES = 32
VISION_SLOTS = 8
VISION_BACKENDS = ("pallas", "pallas+packed", "pallas+packed+sparse")
REF_ATOL = 1e-4                 # tests/test_engine.py engine-vs-reference

LM_ARCH = "llama3.2-1b_smoke"
LM_PROMPT_LENS = (5, 12, 23, 40)
LM_REQUESTS = 8
LM_SLOTS = 4
LM_MAX_NEW = 8
LM_PREFILL_CHUNK = 8


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke failed: {msg}")


def expect(failures: list, ok: bool, msg: str) -> None:
    """Record a failed result check; ``main`` fails once every phase ran."""
    if not ok:
        log(f"FAILED: {msg}")
        failures.append(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def backend(spec: str):
    """A compiled-kernel Backend for ``spec`` (never interpret mode)."""
    be = engine.resolve_backend(spec)
    if be.kind != "pallas":
        return be
    return engine.Backend("pallas", interpret=False, packed=be.packed,
                          sparse=be.sparse)


def kernel_count(jitted, *args) -> int:
    """Pallas kernels (``tpu_custom_call``) in ``jitted``'s compiled HLO."""
    return jitted.lower(*args).compile().as_text().count("tpu_custom_call")


def geometry(plan) -> str:
    cfg = plan.cfg
    return f"{VISION_ARCH} {cfg.img_size}x{cfg.img_size} T={cfg.t}"


def serve_images(spec: str, seed: int, mesh=None) -> dict:
    t0 = time.perf_counter()
    done, stats = serve_vision(
        VISION_ARCH, num_requests=VISION_IMAGES, slots=VISION_SLOTS,
        backend=backend(spec), mesh=mesh, seed=seed, verbose=False,
        return_stats=True)
    stats["setup_and_serve_s"] = time.perf_counter() - t0
    logits = stats["logits"]
    classes = stats["plan"].cfg.num_classes
    check(len(done) == VISION_IMAGES, f"{spec}: {len(done)} images served")
    check(logits.shape == (VISION_IMAGES, classes),
          f"{spec}: logits shape {logits.shape}")
    check(bool(np.isfinite(logits).all()), f"{spec}: non-finite logits")
    return stats


def vision_phase(seed: int, failures: list) -> None:
    ref = serve_images("jnp", seed)
    log(f"vision {geometry(ref['plan'])} backend=jnp (reference) "
        f"images={VISION_IMAGES} batch={VISION_SLOTS} "
        f"wall={ref['setup_and_serve_s']:.2f}s (informational)")
    first = None
    for spec in VISION_BACKENDS:
        stats = serve_images(spec, seed)
        plan, cfg = stats["plan"], stats["plan"].cfg
        batch = np.zeros((VISION_SLOTS, cfg.img_size, cfg.img_size,
                          cfg.in_channels), np.float32)
        kernels = kernel_count(stats["executor"], plan.params, batch)
        logits = stats["logits"]
        first = logits if first is None else first
        exact = bool(np.array_equal(logits, first))
        diff = np.abs(logits - ref["logits"])
        per_image = diff.max(axis=1)
        log(f"vision {geometry(plan)} backend={spec} "
            f"images={VISION_IMAGES} batch={VISION_SLOTS} "
            f"tpu_custom_call={kernels} "
            f"bit_exact_vs_{VISION_BACKENDS[0]}={exact} "
            f"max_abs_diff_vs_jnp={diff.max():.3e} "
            f"images_within_{REF_ATOL:g}={int((per_image <= REF_ATOL).sum())}"
            f"/{VISION_IMAGES} "
            f"argmax_agree={int((logits.argmax(1) == ref['logits'].argmax(1)).sum())}"
            f"/{VISION_IMAGES} "
            f"serve_wall={stats['wall_s']:.3f}s "
            f"setup_and_serve_wall={stats['setup_and_serve_s']:.2f}s "
            "(walls informational)")
        expect(failures, kernels > 0,
               f"{spec}: no tpu_custom_call in the executor")
        expect(failures, exact,
               f"{spec}: logits differ from {VISION_BACKENDS[0]}")
        expect(failures, bool(diff.max() <= REF_ATOL),
               f"{spec}: max |logit - jnp| {diff.max():.3e} > {REF_ATOL}")


def serve_lm(spec: str, seed: int):
    done, stats = serve_spiking_lm_continuous(
        LM_ARCH, num_requests=LM_REQUESTS, prompt_len=max(LM_PROMPT_LENS),
        max_new=LM_MAX_NEW, slots=LM_SLOTS, backend=backend(spec),
        seed=seed, prompt_lens=list(LM_PROMPT_LENS), max_new_spread=3,
        prefill_chunk=LM_PREFILL_CHUNK, verbose=False, return_stats=True)
    check(len(done) == LM_REQUESTS,
          f"LM {spec}: {len(done)}/{LM_REQUESTS} requests completed")
    return dict(done), stats


def lm_phase(seed: int, failures: list) -> None:
    ref, _ = serve_lm("jnp", seed)
    got, stats = serve_lm("pallas+packed", seed)
    plan = stats["plan"]
    step = kernel_count(
        jax.jit(engine.make_decode_step_fn(plan)), plan.params,
        engine.decode_state_batch_init(plan.meta, LM_SLOTS),
        jnp.zeros((LM_SLOTS,), jnp.int32))
    chunk = kernel_count(
        jax.jit(engine.make_prefill_chunk_fn(plan)), plan.params,
        engine.decode_state_init(plan.meta, 1),
        jnp.zeros((1, LM_PREFILL_CHUNK), jnp.int32))
    same = all(np.array_equal(got[r], ref[r]) for r in ref)
    log(f"lm {LM_ARCH} continuous backend=pallas+packed "
        f"requests={len(got)}/{LM_REQUESTS} slots={LM_SLOTS} "
        f"prompt_lens={list(LM_PROMPT_LENS)} prefill_chunk={LM_PREFILL_CHUNK} "
        f"chunks={stats['prefill_chunks']} steps={stats['steps']} "
        f"new_tokens={stats['new_tokens']} tokens_match_jnp={same} "
        f"tpu_custom_call_step={step} tpu_custom_call_chunk={chunk} "
        f"wall={stats['wall_s']:.3f}s (informational)")
    expect(failures, sorted(got) == sorted(ref),
           "LM: request ids differ from jnp")
    expect(failures, same, "LM: pallas+packed tokens differ from jnp")
    expect(failures, step > 0 and chunk > 0,
           "LM: no tpu_custom_call in step/chunk")


def mesh_phase(shape: str, seed: int, failures: list) -> None:
    spec = "pallas+packed"
    single = serve_images(spec, seed)
    meshed = serve_images(spec, seed, mesh=shape)
    w = meshed["plan"].params["blocks"][0]["q"]["w"]
    devices = {dev for leaf in jax.tree_util.tree_leaves(meshed["plan"].params)
               for dev in leaf.devices()}
    d, m = (int(s) for s in shape.split("x"))
    exact = bool(np.array_equal(meshed["logits"], single["logits"]))
    log(f"mesh {shape} vision {geometry(meshed['plan'])} backend={spec} "
        f"images={VISION_IMAGES} batch={VISION_SLOTS} "
        f"devices={len(devices)} q_weight_shards={len(w.sharding.device_set)} "
        f"bit_exact_vs_single_device={exact} "
        f"serve_wall={meshed['wall_s']:.3f}s single_device_serve_wall="
        f"{single['wall_s']:.3f}s (walls informational)")
    expect(failures, len(devices) == d * m,
           f"mesh {shape}: plan weights on {len(devices)} devices")
    expect(failures, len(w.sharding.device_set) == d * m
           and not w.sharding.is_fully_replicated,
           f"mesh {shape}: block weights are not sharded across the mesh")
    expect(failures, exact,
           f"mesh {shape}: logits differ from single-device serving")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="run only the mesh-sharded vision phase, e.g. 2x2")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    check(jax.default_backend() == "tpu",
          f"JAX found no TPU (default backend {jax.default_backend()!r})")
    cache_dir = enable_compile_cache()
    events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **_: events.update([event]))

    t0 = time.perf_counter()
    failures: list[str] = []
    if args.mesh:
        mesh_phase(args.mesh, args.seed, failures)
    else:
        vision_phase(args.seed, failures)
        lm_phase(args.seed, failures)
    dev = jax.devices()[0]
    log(f"compile cache {cache_dir}: "
        f"hits={events['/jax/compilation_cache/cache_hits']} "
        f"misses={events['/jax/compilation_cache/cache_misses']}")
    log(f"peak_bytes_in_use={dev.memory_stats()['peak_bytes_in_use']} "
        f"(device 0); total wall {time.perf_counter() - t0:.1f}s "
        "(informational)")
    check(not failures, "; ".join(failures))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

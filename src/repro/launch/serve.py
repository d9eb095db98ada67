"""Batched serving launcher: synchronous slots and continuous batching.

The legacy loops (``serve``, ``serve_vision``, ``serve_spiking_lm``) run
SYNCHRONOUS slots: prefill a batch, decode it to completion, admit the next
batch.  ``--continuous`` (``serve_spiking_lm_continuous``) upgrades the
spiking-LM path to true continuous batching via ``launch.scheduler``:
admission queue + backpressure, per-slot ``DecodeState`` paging into one live
batched state, and ragged completion/eviction -- finished sequences retire
mid-flight and freed slots refill immediately, with greedy outputs bit-exact
per request vs the synchronous path (scheduling is the only difference).

Vision serving goes through the deploy engine: ``--vision`` compiles the
Spike-(IAND-)Former into a folded/fused deploy plan (``repro.engine``) once at
startup -- BN folded into the weight reads, AND-NOT residuals fused into the
LIF epilogues -- and classifies image batches with the jitted plan executor.

Spiking-LM serving (``--spiking-lm``) decodes from a compiled LM deploy plan:
RMSNorm gains folded into the GEMM weights, the embedding norm folded into
the table, causal SSA dispatched through the plan's backend (quadratic or
chunked-linear ordering, packed spike activations under ``+packed``).  Decode
is true incremental decode: a jitted prefill initialises the O(d^2)-per-head
linear-SSA ``DecodeState`` from the prompt, then a jitted ``decode_step``
advances one token at a time -- no full-prefix re-scoring, one warm shape per
batch size, per-token cost flat in context length.

``--mesh DxM`` serves from a mesh-sharded deploy plan (``repro.engine``'s
``compile_plan(..., mesh=...)``): slot batches fan out over the data axis,
attention heads shard over the model axis, and under a packed backend every
cross-device spike edge moves uint32 bitplane words.  The shape is ELASTIC:
when the live fleet is short (a dead shard), ``fault_tolerance.plan_remesh``
shrinks the data axis and the slot count proportionally -- capacity degrades,
the service stays up.  A fleet too small for even one model group is an
error: serving never drops to one device behind the caller's back.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b_smoke \
        --requests 8 --prompt-len 32 --max-new 16
    PYTHONPATH=src python -m repro.launch.serve --vision \
        --arch spike-iand-former_smoke --requests 16 --slots 4 --backend jnp
    PYTHONPATH=src python -m repro.launch.serve --spiking-lm \
        --requests 4 --prompt-len 16 --max-new 8 --backend pallas+packed
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.launch.serve --spiking-lm \
        --backend jnp+packed --mesh 2x2 --requests 4 --max-new 8
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import DataConfig, make_batch
from repro.models import lm, transformer as T


def greedy_sample(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def parse_mesh(spec):
    """``--mesh dxm`` -> (data, model), e.g. "2x1" -> (2, 1)."""
    if spec is None or isinstance(spec, tuple):
        return spec
    d, m = (int(s) for s in spec.lower().split("x"))
    return (d, m)


def _elastic_mesh(shape, slots: int, *, verbose: bool = True):
    """The serving mesh that actually fits the live device fleet.

    Routes the requested (data, model) shape through
    :func:`repro.distributed.fault_tolerance.plan_remesh`: a dead shard
    SHRINKS capacity (fewer data replicas, proportionally fewer slots)
    instead of killing the service.  A fleet too small for even one model
    group raises: serving a mesh request on one device would hide the
    missing devices.
    """
    from repro.distributed.fault_tolerance import plan_remesh

    plan = plan_remesh(tuple(shape), jax.device_count(), slots)
    if plan.action == "continue":
        return tuple(shape), slots
    if plan.action == "remesh":
        if verbose:
            print(f"[serve] mesh {tuple(shape)} needs "
                  f"{shape[0] * shape[1]} devices, have "
                  f"{jax.device_count()}: degrading to {plan.new_shape} "
                  f"({plan.new_global_batch} slots) -- capacity shrinks, "
                  "service stays up")
        return plan.new_shape, max(1, plan.new_global_batch)
    raise RuntimeError(
        f"mesh {tuple(shape)} is infeasible on {jax.device_count()} "
        "device(s): the model axis alone does not fit")


def _pad_batch(x, mult: int):
    """Pad the leading (request) axis to a multiple of the data-parallel
    degree by repeating the last row; returns (padded, true_size).  The
    executor shards the batch over the data axis, so every slot batch must
    divide evenly -- padded rows are dead weight, truncated from outputs."""
    b = x.shape[0]
    r = (-b) % mult
    if r:
        x = jnp.concatenate([x, jnp.repeat(x[-1:], r, axis=0)], axis=0)
    return x, b


def _warm_sizes(slots: int, num_requests: int) -> set[int]:
    """Every batch shape the slot loop will see: the full slot plus the
    ragged final batch -- warming both keeps reported throughput free of
    mid-serving recompiles."""
    sizes = {min(slots, num_requests)}
    if num_requests % slots:
        sizes.add(num_requests % slots)
    return sizes


def _warm_padded_sizes(slots: int, num_requests: int,
                       data_par: int = 1) -> set[int]:
    """The POST-padding warm shapes: what actually traces.  Two ragged sizes
    that collapse to the same padded batch (e.g. {4, 3} at data_par=2 -> both
    4) must warm ONCE -- deduping pre-padding sizes and then padding each
    defeats the set semantics and trace-warms the shared shape twice."""
    return {b + ((-b) % data_par) for b in _warm_sizes(slots, num_requests)}


def serve(arch: str, *, num_requests: int, prompt_len: int, max_new: int,
          slots: int = 4, seed: int = 0, verbose: bool = True,
          return_stats: bool = False):
    cfg = lm.get_config(arch)
    assert cfg.modality == "text", "serving demo targets text archs"
    params = T.init_lm(jax.random.PRNGKey(seed), cfg)
    serve_step = jax.jit(lm.make_serve_step(cfg))

    cap = prompt_len + max_new
    dcfg = DataConfig(seed=seed, vocab_size=cfg.vocab_size, seq_len=prompt_len,
                      global_batch=num_requests)
    prompts = make_batch(dcfg, 0)["tokens"]

    for b in _warm_sizes(slots, num_requests):
        jax.block_until_ready(serve_step(
            params, T.cache_init(cfg, b, cap),
            {"token": jnp.zeros((b, 1), jnp.int32)}, jnp.asarray(0))[0])

    # prompt feed and generation are timed SEPARATELY: the prompt-feed loop
    # runs prompt_len extra serve_step calls per batch, so folding it into
    # one wall-clock interval understates decode throughput by the factor
    # prompt_len/max_new (the old single-dt report did exactly that)
    done, prefill_s, decode_s = [], 0.0, 0.0
    for start in range(0, num_requests, slots):
        batch_prompts = jnp.asarray(prompts[start : start + slots])
        b = batch_prompts.shape[0]
        cache = T.cache_init(cfg, b, cap)
        # feed the prompt through serve_step to fill the decode cache (one
        # code path for prompt and generation; production would run a batched
        # prefill and reshard its cache instead)
        t0 = time.perf_counter()
        for t in range(prompt_len):
            logits, cache = serve_step(
                params, cache, {"token": batch_prompts[:, t : t + 1]},
                jnp.asarray(t))
        jax.block_until_ready(logits)
        t1 = time.perf_counter()
        prefill_s += t1 - t0
        tok = greedy_sample(logits[:, -1])
        outs = [tok]
        for i in range(max_new - 1):
            logits, cache = serve_step(
                params, cache, {"token": tok[:, None]},
                jnp.asarray(prompt_len + i))
            tok = greedy_sample(logits[:, -1])
            outs.append(tok)
        gen = jax.block_until_ready(jnp.stack(outs, axis=1))
        decode_s += time.perf_counter() - t1
        for j in range(b):
            done.append((start + j, np.asarray(gen[j])))
        if verbose:
            print(f"[serve] slot batch {start//slots}: generated "
                  f"{b}x{max_new} tokens")
    tot = num_requests * max_new
    fed = num_requests * prompt_len
    stats = {
        "prefill_s": prefill_s,
        "decode_s": decode_s,
        "prompt_tokens": fed,
        "new_tokens": tot,
        "prefill_tokens_per_s": fed / prefill_s if prefill_s else float("inf"),
        "decode_tokens_per_s": tot / decode_s if decode_s else float("inf"),
    }
    if verbose:
        dev = jax.devices()[0]
        print(f"[serve] {num_requests} requests on {dev.platform} "
              f"({dev.device_kind}): prefill {fed} prompt "
              f"tokens in {prefill_s:.2f}s "
              f"({stats['prefill_tokens_per_s']:.1f} tok/s), decode {tot} new "
              f"tokens in {decode_s:.2f}s "
              f"({stats['decode_tokens_per_s']:.1f} tok/s)")
    if return_stats:
        return done, stats
    return done


def serve_vision(arch: str, *, num_requests: int, slots: int = 4,
                 backend: str = "jnp", mesh=None, seed: int = 0,
                 verbose: bool = True, return_stats: bool = False):
    """Serve a vision Spikformer through the deploy engine.

    The (params, state, cfg) triple is compiled ONCE into a deploy plan --
    ConvBN/LinearBN folded, IAND fused into the neuron epilogue, backend a
    plan property -- then slot batches of images run the jitted executor.
    ``mesh`` ("dxm" or (data, model)) compiles a mesh-sharded plan, commits
    its weights to the mesh and fans slot batches over the data axis; the
    shape degrades elastically (:func:`_elastic_mesh`) when devices are
    missing.  ``return_stats`` also returns ``{"logits": (N, classes)
    array, "wall_s", "plan", "executor"}`` -- the executor is the jitted
    ``fn(params, images)`` that served.
    """
    from repro import engine
    from repro.configs.spike_iand_former import get_vision_config
    from repro.core import spikformer as sf

    mesh = parse_mesh(mesh)
    data_par = 1
    if mesh is not None:
        mesh, slots = _elastic_mesh(mesh, slots, verbose=verbose)
        data_par = mesh[0]
    cfg = get_vision_config(arch)
    params, state = sf.init(jax.random.PRNGKey(seed), cfg)
    plan = engine.place_params(
        engine.compile_plan(params, state, cfg, backend=backend, mesh=mesh))
    step = jax.jit(engine.make_apply_fn(plan))

    imgs = jax.random.uniform(
        jax.random.PRNGKey(seed + 1),
        (num_requests, cfg.img_size, cfg.img_size, cfg.in_channels))

    # warm so the reported throughput is steady-state inference, not
    # trace+compile time (warm the PADDED shapes -- those are what runs, and
    # ragged sizes that pad to the same shape warm once)
    for bp in sorted(_warm_padded_sizes(slots, num_requests, data_par)):
        warm, _ = _pad_batch(imgs[:min(bp, num_requests)], data_par)
        jax.block_until_ready(step(plan.params, warm))

    done, rows, t0 = [], [], time.perf_counter()
    for start in range(0, num_requests, slots):
        batch, b = _pad_batch(imgs[start : start + slots], data_par)
        logits = np.asarray(step(plan.params, batch)[:b])
        rows.append(logits)
        for j, c in enumerate(logits.argmax(axis=-1)):
            done.append((start + j, int(c)))
        if verbose:
            print(f"[serve] slot batch {start//slots}: classified "
                  f"{b} images")
    dt = time.perf_counter() - t0
    if verbose:
        stats = engine.plan_stats(plan)
        where = (f"{mesh[0]}x{mesh[1]} mesh" if mesh is not None
                 else jax.default_backend())
        print(f"[serve] {num_requests} images in {dt:.2f}s "
              f"({num_requests/dt:.1f} img/s on {where}; "
              f"deploy plan: {stats['folded_conv_bn'] + stats['folded_linear_bn']} "
              f"folded BN pairs, {stats['fused_lif_iand_dispatches']} fused "
              f"LIF+IAND dispatches, backend={stats['backend']}"
              f"{', packed spikes' if stats['packed'] else ''}"
              f"{' + occupancy skip' if stats['sparse'] else ''})")
    if return_stats:
        return done, {"logits": np.concatenate(rows), "wall_s": dt,
                      "plan": plan, "executor": step}
    return done


def spiking_lm_config(arch: str):
    """Spiking deploy flavour of a text arch config (the same adaptation the
    LM test/bench suites use: heads sized for binary spike trains)."""
    cfg = lm.get_config(arch)
    assert cfg.modality == "text", "spiking-LM serving targets text archs"
    return cfg.replace(spiking=True, spike_t=4, num_heads=4, head_dim=None)


def _compile_lm_serving(arch: str, *, backend, ordering, mesh, slots, seed,
                        verbose):
    """Shared setup of both spiking-LM serving modes: elastic mesh
    resolution, config adaptation, param init, and the ONE plan compile --
    returns (cfg, plan, data_par, resolved_slots)."""
    from repro import engine
    from repro.models import spiking_lm as slm

    mesh = parse_mesh(mesh)
    data_par = 1
    if mesh is not None:
        mesh, slots = _elastic_mesh(mesh, slots, verbose=verbose)
        data_par = mesh[0]
    cfg = spiking_lm_config(arch)
    params = slm.init_spiking_lm(jax.random.PRNGKey(seed), cfg)
    plan = engine.place_params(engine.compile_plan(
        params, None, cfg, backend=backend, ordering=ordering, mesh=mesh))
    return cfg, plan, data_par, slots


def serve_spiking_lm(arch: str, *, num_requests: int, prompt_len: int,
                     max_new: int, slots: int = 4, backend: str = "jnp",
                     ordering: str = "quadratic", mesh=None, seed: int = 0,
                     verbose: bool = True):
    """Serve a spiking LM from a compiled deploy plan (greedy decode).

    The (params, cfg) pair is folded ONCE into an LM deploy plan --
    Linear+RMSNorm units gain-folded, embedding norm pre-applied to the
    table, causal SSA on the plan's backend -- and decode is TRUE incremental
    decode: one jitted ``prefill`` scores the prompt and initialises the
    O(d^2)-per-head linear-SSA ``DecodeState``, then one jitted
    ``decode_step`` advances a token at a time at a cost flat in context
    length.  The token loop never re-scores the prefix, so only ONE warm
    shape per slot batch size is needed (the old full-forward loop recompiled
    per sequence length), and the per-token cost at 500k tokens of context
    equals the per-token cost at 8.
    """
    from repro import engine

    cfg, plan, data_par, slots = _compile_lm_serving(
        arch, backend=backend, ordering=ordering, mesh=mesh, slots=slots,
        seed=seed, verbose=verbose)
    prefill = jax.jit(engine.make_prefill_fn(plan))
    step = jax.jit(engine.make_decode_step_fn(plan))

    dcfg = DataConfig(seed=seed, vocab_size=cfg.vocab_size, seq_len=prompt_len,
                      global_batch=num_requests)
    prompts = make_batch(dcfg, 0)["tokens"]

    # warm ONE (batch, prompt_len) prefill shape and ONE step shape per slot
    # batch size (plus the ragged final batch; padded to the data-parallel
    # degree, POST-padding deduped -- ragged sizes that collapse to the same
    # padded shape warm once) -- the step shape serves every subsequent
    # token, however long the decode runs
    for bp in sorted(_warm_padded_sizes(slots, num_requests, data_par)):
        logits, st = prefill(plan.params,
                             jnp.zeros((bp, prompt_len), jnp.int32))
        jax.block_until_ready(
            step(plan.params, st, jnp.zeros((bp,), jnp.int32))[0])

    done, t0 = [], time.perf_counter()
    for start in range(0, num_requests, slots):
        seq, b = _pad_batch(jnp.asarray(prompts[start : start + slots]),
                            data_par)
        logits, state = prefill(plan.params, seq)
        tok = greedy_sample(logits[:, -1])
        outs = [tok]
        for _ in range(max_new - 1):
            logits, state = step(plan.params, state, tok)
            tok = greedy_sample(logits)
            outs.append(tok)
        gen = jnp.stack(outs, axis=1)
        for j in range(b):
            done.append((start + j, np.asarray(gen[j])))
        if verbose:
            print(f"[serve] slot batch {start//slots}: generated "
                  f"{b}x{max_new} tokens")
    dt = time.perf_counter() - t0
    tot = num_requests * max_new
    if verbose:
        stats = engine.plan_stats(plan)
        where = _plan_where(plan)
        print(f"[serve] {num_requests} requests, {tot} new tokens in {dt:.2f}s "
              f"({tot/dt:.1f} tok/s on {where}; LM plan: "
              f"{stats['folded_linear_rmsnorm']} folded Linear+RMSNorm units, "
              f"{stats['fused_lif_iand_dispatches']} fused LIF+IAND "
              f"dispatches, ordering={stats['attn_ordering']}, "
              f"backend={stats['backend']}"
              f"{', packed spikes' if stats['packed'] else ''}"
              f"{' + occupancy skip' if stats['sparse'] else ''}; "
              f"prefill+step decode, {stats['decode_state_bytes']} B "
              f"state/seq, flat in context)")
    return done


def _plan_where(plan) -> str:
    """Human-readable execution locus of a plan for the serve reports."""
    scfg = plan.meta.sharding
    if scfg is not None:
        return f"{scfg.data}x{scfg.model} mesh"
    return jax.default_backend()


def serving_requests(prompts, *, prompt_lens, max_new, max_new_spread: int = 0,
                     eos_id: int | None = None):
    """Request list for continuous serving from a (N, S_max) prompt batch:
    request ``i`` takes the first ``prompt_lens[i % len(prompt_lens)]`` tokens
    of row ``i`` (mixed length buckets) and decodes
    ``max_new - (i % (max_new_spread + 1))`` tokens (ragged completion --
    spread 0 is uniform).  Deterministic, so the bit-exactness tests can
    rebuild the exact same workload for the reference paths."""
    from repro.launch.scheduler import Request

    prompts = np.asarray(prompts)
    lens = [int(s) for s in prompt_lens]
    reqs = []
    for i in range(prompts.shape[0]):
        s = lens[i % len(lens)]
        reqs.append(Request(
            rid=i, prompt=prompts[i, :s].astype(np.int32),
            max_new=max(1, max_new - (i % (max_new_spread + 1))),
            eos_id=eos_id))
    return reqs


def serve_spiking_lm_continuous(arch: str, *, num_requests: int,
                                prompt_len: int, max_new: int, slots: int = 4,
                                backend: str = "jnp",
                                ordering: str = "quadratic", mesh=None,
                                seed: int = 0, prompt_lens=None,
                                max_new_spread: int = 0,
                                max_pending: int | None = None,
                                prefill_chunk: int | None = None,
                                verbose: bool = True,
                                return_stats: bool = False):
    """Serve a spiking LM with CONTINUOUS batching (greedy decode).

    Same plan, same prompts, same sampler as :func:`serve_spiking_lm` -- the
    difference is purely scheduling: a ``launch.scheduler``
    ``ContinuousScheduler`` pages each admitted prompt's ``DecodeState`` into
    a freed slot of one live batched state and retires finished sequences
    mid-flight, so the decode step keeps ONE warm shape (the full slot batch)
    and freed capacity never idles behind a slow batch member.  Greedy
    outputs are bit-exact per request vs the synchronous-slots path.

    ``prompt_lens`` (defaults to ``[prompt_len]``) cycles mixed prompt-length
    buckets across requests -- the MULTISET as given, so repeated lengths
    keep their requested mixture ratio (dedup happens only for shape
    warming); ``max_new_spread`` staggers per-request decode lengths to
    force ragged completion.  ``prefill_chunk`` switches admission to
    decode-interleaved chunked prefill (one resumable chunk per scheduler
    tick -- bounds the decode stall of a long-prompt admission).
    ``return_stats`` also returns the scheduler's counters plus ``plan``.
    """
    from repro import engine
    from repro.launch.scheduler import ContinuousScheduler

    cfg, plan, data_par, slots = _compile_lm_serving(
        arch, backend=backend, ordering=ordering, mesh=mesh, slots=slots,
        seed=seed, verbose=verbose)
    # the requested mixture, verbatim -- sorted({...}) here would collapse
    # "32,32,64" (a 2:1 mix) into a 1:1 cycle
    lens = [int(s) for s in (prompt_lens or [prompt_len])]
    dcfg = DataConfig(seed=seed, vocab_size=cfg.vocab_size, seq_len=max(lens),
                      global_batch=num_requests)
    prompts = make_batch(dcfg, 0)["tokens"]
    reqs = serving_requests(prompts, prompt_lens=lens, max_new=max_new,
                            max_new_spread=max_new_spread)

    sched = ContinuousScheduler(
        plan, slots=slots,
        max_pending=max_pending if max_pending is not None
        else max(num_requests, 1),
        prefill_chunk=prefill_chunk)
    warmed = sched.warm(sorted(set(lens)))
    t0 = time.perf_counter()
    completed = sched.run(reqs)
    dt = time.perf_counter() - t0
    done = [(r.rid, np.asarray(r.tokens, np.int32)) for r in completed]
    sstats = sched.stats()
    sstats.update(wall_s=dt, warm_prefill_shapes=warmed, warm_step_shapes=1,
                  plan=plan)
    if verbose:
        stats = engine.plan_stats(plan)
        print(f"[serve] continuous: {len(completed)}/{num_requests} requests, "
              f"{sstats['new_tokens']} new tokens in {dt:.2f}s "
              f"({sstats['new_tokens']/dt:.1f} tok/s on {_plan_where(plan)}; "
              f"{sstats['steps']} steps at {slots} slots, occupancy "
              f"{sstats['slot_occupancy']:.2f}, queue high-water "
              f"{sstats['queue_high_water']}, {warmed} prefill shape(s) + 1 "
              f"step shape; backend={stats['backend']}, "
              f"ordering={stats['attn_ordering']})")
    if return_stats:
        return done, sstats
    return done


def main():
    from repro.launch.compile_info import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b_smoke")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--vision", action="store_true",
                    help="serve a vision Spikformer via the deploy engine")
    ap.add_argument("--spiking-lm", action="store_true",
                    help="greedy-decode a spiking LM from a compiled deploy "
                         "plan (RMSNorm folded, backend-dispatched causal SSA)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching decode service (spiking-lm "
                         "mode): admission queue + backpressure, per-slot "
                         "DecodeState paging, ragged completion/eviction -- "
                         "one warm step shape per slot count")
    ap.add_argument("--prompt-lens", default=None, metavar="L1,L2,...",
                    help="mixed prompt-length buckets for --continuous "
                         "(cycled across requests; default: --prompt-len)")
    ap.add_argument("--max-new-spread", type=int, default=0,
                    help="stagger per-request decode lengths by up to this "
                         "many tokens (--continuous: forces ragged "
                         "completion/eviction)")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="admission-queue bound for --continuous "
                         "(backpressure; default: no practical bound)")
    ap.add_argument("--prefill-chunk", type=int, default=None, metavar="C",
                    help="decode-interleaved chunked admission for "
                         "--continuous: prefill advances one resumable "
                         "C-token chunk per scheduler tick, bounding the "
                         "decode stall of a long-prompt admission (memory "
                         "flat in prompt length; default: one-shot prefill)")
    ap.add_argument("--backend", default="jnp",
                    choices=("jnp", "pallas", "jnp+packed", "pallas+packed",
                             "jnp+packed+sparse", "pallas+packed+sparse"),
                    help="deploy-plan backend (vision / spiking-lm modes); "
                         "+packed serves bit-packed inter-layer spike "
                         "activations, +sparse adds occupancy-map zero-word "
                         "skipping (bit-exact)")
    ap.add_argument("--ordering", default="quadratic",
                    choices=("quadratic", "linear"),
                    help="causal-SSA dataflow of the LM plan: (QK^T)V vs the "
                         "chunked-linear Q(K^TV) long-sequence path")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve from a mesh-sharded plan, e.g. 2x1 (data-"
                         "parallel fan-out) or 2x2 (+ tensor-parallel heads); "
                         "packed backends move uint32 spike words between "
                         "devices; a short fleet elastically degrades "
                         "capacity, and a fleet that cannot hold one model "
                         "group is an error")
    args = ap.parse_args()
    if args.vision:
        serve_vision(args.arch, num_requests=args.requests, slots=args.slots,
                     backend=args.backend, mesh=args.mesh)
        return
    if args.spiking_lm:
        if args.continuous:
            lens = ([int(s) for s in args.prompt_lens.split(",")]
                    if args.prompt_lens else None)
            serve_spiking_lm_continuous(
                args.arch, num_requests=args.requests,
                prompt_len=args.prompt_len, max_new=args.max_new,
                slots=args.slots, backend=args.backend,
                ordering=args.ordering, mesh=args.mesh, prompt_lens=lens,
                max_new_spread=args.max_new_spread,
                max_pending=args.max_pending,
                prefill_chunk=args.prefill_chunk)
            return
        serve_spiking_lm(args.arch, num_requests=args.requests,
                         prompt_len=args.prompt_len, max_new=args.max_new,
                         slots=args.slots, backend=args.backend,
                         ordering=args.ordering, mesh=args.mesh)
        return
    serve(args.arch, num_requests=args.requests, prompt_len=args.prompt_len,
          max_new=args.max_new, slots=args.slots)


if __name__ == "__main__":
    main()

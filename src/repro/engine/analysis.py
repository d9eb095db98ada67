"""Jaxpr-level op accounting for the fused-vs-naive claim.

The deploy plan's promise is structural: BatchNorm is folded at plan-compile
time and the AND-NOT residual rides the LIF epilogue.  These helpers verify
the promise on the traced graph itself: :func:`op_histogram` walks a
function's jaxpr (including nested/closed sub-jaxprs) and counts primitives,
and :func:`bn_op_count` reports how many BN-signature ops (``rsqrt`` /
``batch_norm*``) the graph still contains -- 0 for any compiled plan.
"""

from __future__ import annotations

import math
from collections import Counter

import jax
from jax.extend import core as jcore


_BN_PRIMS = ("rsqrt",)  # eval-mode BN lowers to rsqrt(var+eps); VISION-ONLY
                        # signature: nothing else in the vision model uses
                        # rsqrt, but LM graphs do (RMSNorm / the folded
                        # units' dynamic normalizer) -- LM plans are checked
                        # with rmsnorm_op_count, never bn_op_count


def iter_eqns(jaxpr):
    """Yield every equation of ``jaxpr`` and of all jaxprs nested in equation
    params (ClosedJaxpr / Jaxpr, bare or inside tuples/lists) -- the ONE
    traversal every jaxpr-accounting helper in this module shares."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            items = v if isinstance(v, (tuple, list)) else (v,)
            for item in items:
                if isinstance(item, jcore.ClosedJaxpr):
                    yield from iter_eqns(item.jaxpr)
                elif isinstance(item, jcore.Jaxpr):
                    yield from iter_eqns(item)


def op_histogram(fn, *args, **kwargs) -> Counter:
    """Primitive-name -> count over ``fn``'s jaxpr, nested jaxprs included."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return Counter(eqn.primitive.name for eqn in iter_eqns(closed.jaxpr))


def jaxpr_dims(fn, *args, **kwargs) -> set:
    """Every axis length appearing in any value of ``fn``'s jaxpr -- inputs,
    consts, and every equation's operands and outputs, nested jaxprs
    included.

    The falsifiable form of a "cost is flat in S" claim: trace the function
    and assert the sequence length S is NOT in this set -- a computation
    that secretly re-scored an S-token prefix (or carried the prompt in its
    state) would have an S-sized axis somewhere.  Operand (invar) shapes are
    collected too, so even a single reducing op that consumes an S-sized
    input straight down to a flat output cannot hide."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    dims: set = set()
    for v in closed.jaxpr.invars + closed.jaxpr.constvars:
        dims.update(getattr(v.aval, "shape", ()))
    for eqn in iter_eqns(closed.jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            dims.update(getattr(aval, "shape", ()))
    return dims


def bn_op_count(fn, *args, **kwargs) -> int:
    """Number of BatchNorm-signature ops in ``fn``'s jaxpr (vision graphs
    only -- LM graphs legitimately use rsqrt in their dynamic normalizers;
    count those with :func:`rmsnorm_op_count` instead)."""
    hist = op_histogram(fn, *args, **kwargs)
    return sum(hist[p] for p in _BN_PRIMS) + sum(
        n for name, n in hist.items() if name.startswith("batch_norm"))


def rmsnorm_op_count(fn, *args, **kwargs) -> int:
    """Number of standalone RMSNorm applications in ``fn``'s jaxpr.

    ``models.layers.rmsnorm_apply`` is jitted, so every application is a
    named ``jit`` node -- the RMSNorm counterpart of :func:`bn_op_count`
    (RMSNorm's rsqrt cannot be the signature here: the folded units keep a
    gain-free data-dependent normalizer, which also uses rsqrt; what folding
    removes is the parameterised norm LAYER, counted by name).
    """
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return sum(1 for eqn in iter_eqns(closed.jaxpr)
               if eqn.primitive.name == "jit"
               and eqn.params.get("name") == "rmsnorm_apply")


def spike_traffic(cfg, *, batch: int = 1, img_size: int | None = None,
                  backend=None, mesh=None) -> dict:
    """Inter-layer spike-activation bytes of one forward pass, dense vs
    packed.

    Walks :func:`repro.engine.layout.spike_edges` (every binary tensor a LIF
    epilogue writes and the next consumer reads) and prices each edge two
    ways: dense f32 over T time steps (``4*T`` bytes/element) vs bit-packed
    uint32 bitplane words (``4*ceil(T/32)`` bytes/element).  ``packed_bytes``
    / ``reduction`` are the datapath contract (every edge carried packed).

    The SSA-boundary q/k/v edges depend on the backend: under a backend whose
    ``closes_ssa_boundary`` resolves True (packed Pallas route; quadratic
    attention ordering) the packed SSA kernel consumes the words directly and
    ``packed_bytes_ssa_dense`` / ``reduction_ssa_dense`` EQUAL the packed
    contract; with ``backend=None`` (or any backend that unpacks at the
    attention op's boundary) they conservatively price those edges dense.
    Both are what ``benchmarks/packed_traffic.py`` reports against the
    Table-I configs.

    ``mesh`` (ShardingCfg | "dxm" | (data, model)) additionally prices each
    edge's CROSS-DEVICE bytes under the sharded vision plan, instead of one
    blended on-chip number: an edge whose feature axis maps to a >1 model
    axis is produced feature-sharded and all-gathered by its consumer
    (fleet-total wire bytes = full edge bytes x (m-1), the ring all-gather
    cost), EXCEPT the ssa_boundary q/k/v edges, whose consumer is the
    head-local SSA and which never cross.  Data-parallel replicas move no
    activations between them, so the data axis adds nothing.
    """
    from repro.engine.layout import spike_edges

    boundary_closed = _boundary_closed(backend, cfg.attn_ordering)
    return _price_edges(spike_edges(cfg, img_size=img_size), cfg.t,
                        batch=batch, boundary_closed=boundary_closed,
                        sparse=_is_sparse(backend),
                        scfg=_traffic_sharding(mesh, "vision"))


def lm_spike_traffic(cfg, *, seq_len: int, batch: int = 1, backend=None,
                     ordering: str = "quadratic", mesh=None) -> dict:
    """Inter-layer spike-activation bytes of one spiking-LM forward pass at
    ``seq_len`` tokens (``cfg`` is an ``ArchConfig``; same pricing and
    SSA-boundary semantics as :func:`spike_traffic`).  ``mesh`` prices
    cross-device bytes under the head-sharded LM schedule: the attention
    LIF output is the one crossing edge per block (embed/ffn edges are
    consumed by model-replicated units, q/k/v by the head-local SSA)."""
    from repro.engine.layout import lm_spike_edges

    boundary_closed = _boundary_closed(backend, ordering)
    return _price_edges(lm_spike_edges(cfg, seq_len=seq_len), cfg.spike_t,
                        batch=batch, boundary_closed=boundary_closed,
                        sparse=_is_sparse(backend),
                        scfg=_traffic_sharding(mesh, "lm"))


def lm_decode_traffic(cfg, *, batch: int = 1, backend=None,
                      mesh=None) -> dict:
    """Per-generated-token traffic of the incremental decode mode: the S=1
    spike edges (:func:`repro.engine.layout.lm_decode_spike_edges`) plus the
    O(d^2) SSA state each step reads and writes back.

    Everything here is FLAT in the prefix length -- the number that fills the
    ``@S500k`` benchmark rows: a 500k-token context costs the same per new
    token as an 8-token one.  The packed decode step consumes q/k/v words
    directly under ``Backend.closes_ssa_boundary`` (there is no quadratic
    score tile in the step, so the ordering condition of the full-forward
    pricing does not apply); other backends unpack at the op boundary and
    price those edges dense.

    ``mesh`` prices cross-device bytes per step (head-sharded schedule --
    the attention edge crosses, everything else is shard-local); the K^T V
    decode state is PINNED to its head shard (``DecodeState`` sharded over
    heads), so state bytes never cross devices at any mesh size."""
    from repro.engine.layout import lm_decode_spike_edges
    from repro.engine.backend import resolve

    closed = backend is not None and resolve(backend).closes_ssa_boundary
    priced = _price_edges(lm_decode_spike_edges(cfg), cfg.spike_t,
                          batch=batch, boundary_closed=closed,
                          sparse=_is_sparse(backend),
                          scfg=_traffic_sharding(mesh, "lm"))
    dh = cfg.d_model // cfg.num_heads
    state_bytes = 4 * cfg.num_layers * cfg.spike_t * batch * cfg.num_heads * dh * dh
    priced["decode_state_bytes"] = state_bytes
    # each step reads the state and writes the updated one back
    priced["state_bytes_per_step"] = 2 * state_bytes
    priced["dense_bytes_per_step"] = priced["dense_bytes"] + 2 * state_bytes
    priced["packed_bytes_per_step"] = (priced["packed_bytes_ssa_dense"]
                                       + 2 * state_bytes)
    if mesh is not None:
        priced["cross_device_state_bytes"] = 0   # state pinned to its shard
    return priced


def decode_slot_report(plan, *, slots: int, budget_bytes: int | None = None,
                       prompt_lens=()) -> dict:
    """Decode-slot accounting of a continuous-batching service on ``plan``:
    per-slot and whole-batch ``DecodeState`` bytes, per-step wire bytes at the
    slot count (state read+write plus the S=1 spike edges), the slot capacity
    a device-memory budget buys (``max_slots`` -- exact, the state has no
    context-length term), and the warm-shape bill: ONE step shape for the
    slot batch plus one prefill shape per distinct prompt-length bucket."""
    meta = plan.meta
    entry = meta.decode
    if entry is None:
        raise ValueError("decode-slot stats are an LM-plan mode "
                         f"(family={meta.family!r})")
    cfg = meta.cfg.arch
    traffic = lm_decode_traffic(cfg, batch=slots, backend=meta.backend,
                                mesh=meta.sharding)
    report = {
        "slots": slots,
        "state_bytes_per_slot": entry.state_bytes(1),
        "state_bytes_batch": entry.state_bytes(slots),
        "bytes_per_step_dense": traffic["dense_bytes_per_step"],
        "bytes_per_step_packed": traffic["packed_bytes_per_step"],
        "warm_step_shapes": 1,
        "warm_prefill_shapes": len(set(prompt_lens)),
        "prompt_len_buckets": tuple(sorted(set(prompt_lens))),
    }
    if budget_bytes is not None:
        report["budget_bytes"] = budget_bytes
        report["max_slots"] = entry.max_slots(budget_bytes)
    return report


def prefill_chunk_report(plan, *, seq_len: int, chunk: int,
                         batch: int = 1) -> dict:
    """Resident-memory accounting of chunked vs one-shot prefill at prompt
    length ``seq_len``: the dominant activation plane of an LM prefill is a
    (T, B, S, d_model) f32 spike/drive tensor per block edge, so one-shot
    residency scales with S while the chunked path holds only a C-token
    plane plus the O(d^2) carried ``DecodeState`` -- flat in S.  Analytic
    (the jaxpr flatness check is the structural proof; this prices it), so
    the 500k row costs nothing to produce.  ``chunk_buckets`` is the
    warm-shape bill (the chunk size plus the ragged tail, if any)."""
    meta = plan.meta
    entry = meta.decode
    if entry is None:
        raise ValueError("prefill-chunk stats are an LM-plan mode "
                         f"(family={meta.family!r})")
    cfg = meta.cfg.arch
    t, d = cfg.spike_t, cfg.d_model
    plane = 4 * t * batch * d                       # bytes per token column
    full, ragged = divmod(seq_len, chunk)
    buckets = ([chunk] if full else []) + ([ragged] if ragged else [])
    return {
        "seq_len": seq_len,
        "chunk": chunk,
        "num_chunks": full + (1 if ragged else 0),
        "chunk_buckets": buckets,
        "state_bytes": entry.state_bytes(batch),
        "oneshot_plane_bytes": plane * seq_len,
        "chunked_plane_bytes": plane * chunk + entry.state_bytes(batch),
        "plane_reduction": (plane * seq_len
                            / (plane * chunk + entry.state_bytes(batch))),
    }


def _traffic_sharding(mesh, family: str):
    """Coerce a traffic function's ``mesh=`` argument into the family's
    resolved ``ShardingCfg`` (None passes through)."""
    if mesh is None:
        return None
    from repro.engine.plan import _resolve_sharding

    return _resolve_sharding(mesh, family)


def _edge_mesh_degree(edge, rules: dict, sizes: dict) -> int:
    """Tensor-parallel degree of one spike edge: the product of mesh-axis
    sizes its FEATURE (last) logical axis maps to under the plan rules
    (1 = the edge is replicated / shard-local)."""
    if not edge.axes:
        return 1
    mapped = rules.get(edge.axes[-1])
    if mapped is None:
        return 1
    names = mapped if isinstance(mapped, tuple) else (mapped,)
    m = 1
    for n in names:
        m *= sizes.get(n, 1)
    return m


def _is_sparse(backend) -> bool:
    from repro.engine.backend import resolve

    return backend is not None and resolve(backend).sparse


def _boundary_closed(backend, ordering: str) -> bool:
    from repro.engine.backend import resolve

    if backend is None:
        return False
    # both orderings close under the packed kernel route: quadratic through
    # ``packed_ssa_merged_op`` (vision) / ``packed_ssa_op`` (LM), linear
    # through the in-register shift-and-mask scans
    # (``ssa_linear_packed`` / ``ssa_causal_linear_with_state_packed``)
    return (resolve(backend).closes_ssa_boundary
            and ordering in ("quadratic", "linear"))


def _price_edges(edges, t: int, *, batch: int, boundary_closed: bool,
                 sparse: bool = False, scfg=None) -> dict:
    from repro.core import packing

    per_edge = [{
        "name": e.name,
        "elems": e.elems * batch,
        "ssa_boundary": e.ssa_boundary,
        "dense_bytes": packing.dense_nbytes(t, e.elems * batch),
        "packed_bytes": packing.packed_nbytes(t, e.elems * batch),
        "occupancy_bytes": packing.occupancy_nbytes(t, e.elems * batch),
    } for e in edges]
    if scfg is not None:
        sizes = dict(zip(scfg.mesh_axes, scfg.mesh_shape))
        rules = scfg.rules_dict
        for e, pe in zip(edges, per_edge):
            m = _edge_mesh_degree(e, rules, sizes)
            # an ssa_boundary edge's consumer (the per-head-local SSA) reads
            # only the local head shard: sharded, but never on the wire
            crosses = m > 1 and not e.ssa_boundary
            pe["tp_degree"] = m
            pe["crosses_devices"] = crosses
            # fleet-total ring-all-gather wire bytes over the whole (global)
            # batch: every shard's block travels to the m-1 other shards
            pe["cross_device_dense_bytes"] = (
                (m - 1) * pe["dense_bytes"] if crosses else 0)
            pe["cross_device_packed_bytes"] = (
                (m - 1) * pe["packed_bytes"] if crosses else 0)
    dense = sum(e["dense_bytes"] for e in per_edge)
    packed = sum(e["packed_bytes"] for e in per_edge)
    occupancy = sum(e["occupancy_bytes"] for e in per_edge)
    packed_ssa_dense = sum(
        e["dense_bytes"] if e["ssa_boundary"] and not boundary_closed
        else e["packed_bytes"]
        for e in per_edge)
    out = {
        "t": t,
        "batch": batch,
        "ssa_boundary_closed": boundary_closed,
        "edges": per_edge,
        "dense_bytes": dense,
        "packed_bytes": packed,
        "reduction": dense / packed,
        "packed_bytes_ssa_dense": packed_ssa_dense,
        "reduction_ssa_dense": dense / packed_ssa_dense,
    }
    if sparse:
        # the sparse datapath moves the SAME packed words plus the occupancy
        # metadata (1/128 of the words); its win is skipped COMPUTE, priced by
        # the measured skip rates of ``sparsity_report``, not here
        out["occupancy_bytes"] = occupancy
        out["packed_sparse_bytes"] = packed + occupancy
        out["reduction_sparse"] = dense / (packed + occupancy)
    if scfg is not None:
        xd = sum(e["cross_device_dense_bytes"] for e in per_edge)
        xp = sum(e["cross_device_packed_bytes"] for e in per_edge)
        out["mesh"] = {"shape": tuple(scfg.mesh_shape),
                       "axes": tuple(scfg.mesh_axes)}
        out["cross_device_dense_bytes"] = xd
        out["cross_device_packed_bytes"] = xp
        # exactly t / ceil(t/32): every crossing edge moves words, so the
        # interconnect keeps the full packing factor (8x at T=8, 32x at T=32)
        out["cross_device_reduction"] = (xd / xp) if xp else None
    return out


def collective_report(fn, *args, **kwargs) -> dict:
    """Every cross-device collective in ``fn``'s jaxpr (shard_map bodies
    included via :func:`iter_eqns`), with operand dtype and analytic wire
    bytes -- the measured face of the sharded-traffic pricing, and the
    falsifiable form of the packed-boundary contract: under a packed backend
    every collective operand must be uint32 (no ``packing.unpack`` output
    ever crosses devices).

    Wire bytes are ring-algorithm totals PER MODEL GROUP (one data-parallel
    replica): all_gather moves (size-1) x out_bytes, reduce_scatter
    (size-1) x in_bytes, psum the sum of both.  Collectives whose axis size
    is not recorded in the jaxpr (bare ``psum``) report ``wire_bytes=None``.
    """
    _WIRE = {
        "all_gather": lambda size, inb, outb: (size - 1) * outb,
        "reduce_scatter": lambda size, inb, outb: (size - 1) * inb,
        "psum_scatter": lambda size, inb, outb: (size - 1) * inb,
        "psum": lambda size, inb, outb: 2 * (size - 1) * inb,
        "all_to_all": lambda size, inb, outb: (size - 1) * inb // size,
    }
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    colls = []
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name not in _WIRE:
            continue
        inv = eqn.invars[0].aval
        outv = eqn.outvars[0].aval
        size = eqn.params.get("axis_size")
        inb = math.prod(inv.shape) * inv.dtype.itemsize
        outb = math.prod(outv.shape) * outv.dtype.itemsize
        colls.append({
            "primitive": name,
            "dtype": str(inv.dtype),
            "shape": tuple(int(s) for s in outv.shape),
            "axis_size": None if size is None else int(size),
            "wire_bytes": (None if size is None
                           else int(_WIRE[name](int(size), inb, outb))),
        })
    known = [c["wire_bytes"] for c in colls if c["wire_bytes"] is not None]
    return {
        "num_collectives": len(colls),
        "collectives": colls,
        "wire_bytes": sum(known),
        "dtypes": sorted({c["dtype"] for c in colls}),
    }


def sparsity_report(plan, batch) -> dict:
    """MEASURED occupancy of every packed spike train a plan's forward moves
    on ``batch`` (run eagerly through ``engine.execute.capture_spikes``).

    Reports, per LIF tap and aggregated, the skip rates each sparse consumer
    sees on these real activations:

    * ``word_zero_rate`` -- fraction of uint32 words that are all-zero (the
      finest exact-skip granule);
    * ``occ_tile_zero_rate`` -- fraction of ``packing.OCC_TILE``-element
      occupancy tiles that are all-zero (what the sparse Pallas GEMM skips);
    * ``token_granule_zero_rate`` -- fraction of 8-token granules with no
      spike at any feature/time step (what the jnp sparse GEMM route skips);
    * ``spike_rate`` -- plain spike density over (T, elements).
    """
    import jax.numpy as jnp

    from repro.core import packing
    from repro.engine import execute

    with execute.capture_spikes() as taps:
        execute.apply(plan, batch)
    if not taps:
        raise ValueError(
            "plan produced no packed spike trains -- sparsity_report needs a "
            "packed backend (Backend.packed=True)")
    per_tap = []
    tot = {"words": 0, "zero_words": 0, "tiles": 0, "zero_tiles": 0,
           "granules": 0, "zero_granules": 0, "spikes": 0, "slots": 0}
    for ps in taps:
        words = ps.words
        occ = ps.occ if ps.occ is not None else packing.occupancy_map(words)
        # token granules: rows of the (tokens, features) view, all word planes
        flat = words.reshape(words.shape[0], -1, words.shape[-1])
        row_alive = jnp.any(flat != 0, axis=(0, 2))             # per token row
        g = 8
        row_alive_p = jnp.pad(row_alive, (0, (-row_alive.shape[0]) % g))
        gran_alive = jnp.any(row_alive_p.reshape(-1, g), axis=1)
        n_words = int(words.size)
        n_zero_words = int((words == 0).sum())
        n_tiles = int(occ.size)
        n_zero_tiles = int((occ == 0).sum())
        n_gran = int(gran_alive.size)
        n_zero_gran = int((~gran_alive).sum())
        n_spikes = int(packing.spike_counts(ps).sum())
        n_slots = ps.t * math.prod(ps.elem_shape)
        per_tap.append({
            "shape": tuple(int(s) for s in ps.dense_shape),
            "word_zero_rate": n_zero_words / n_words,
            "occ_tile_zero_rate": n_zero_tiles / n_tiles,
            "token_granule_zero_rate": n_zero_gran / n_gran,
            "spike_rate": n_spikes / n_slots,
        })
        tot["words"] += n_words
        tot["zero_words"] += n_zero_words
        tot["tiles"] += n_tiles
        tot["zero_tiles"] += n_zero_tiles
        tot["granules"] += n_gran
        tot["zero_granules"] += n_zero_gran
        tot["spikes"] += n_spikes
        tot["slots"] += n_slots
    return {
        "num_taps": len(per_tap),
        "taps": per_tap,
        "word_zero_rate": tot["zero_words"] / tot["words"],
        "occ_tile_zero_rate": tot["zero_tiles"] / tot["tiles"],
        "token_granule_zero_rate": tot["zero_granules"] / tot["granules"],
        "spike_rate": tot["spikes"] / tot["slots"],
    }

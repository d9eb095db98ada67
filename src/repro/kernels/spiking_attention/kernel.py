"""Pallas TPU kernel: tick-batched softmax-free spiking self-attention.

Computes SSA(Q,K,V) = (Q K^T) V * scale for binary spike Q, K, V with NO
softmax (Spikformer's key simplification -- the score matrix is already
non-negative).  The leading grid axis folds (time x batch x heads), so all T
time steps' attention products ride the same kernel launch: the parallel
tick-batching dataflow.  On the MXU the binary operands ride bf16/f32 lanes;
the ASIC's AND-gate datapath does not transfer (DESIGN.md S8.1), softmax
elimination and single-pass weight reads do.

Layout: q (G, N, D), k (G, M, D), v (G, M, D) -> out (G, N, D), G = T*B*H.
Query rows are blocked (block_q x D tiles); K/V for one g live in VMEM whole
(vision-scale N; the long-sequence path uses the LINEAR ordering Q(K^T V) in
``repro.core.spiking_attention`` -- legal only because there is no softmax).
VMEM per program ~= block_q*D + 2*M*D + block_q*M floats.

``packed_ssa_fwd`` is the packed-operand variant: q/k/v arrive as uint32
bitplane words (G = B*H, time lives in the bits), each bitplane is unpacked
per-tile in VMEM, and the output is the dense (T, G, N, D) drive -- spikes
never materialise dense outside VMEM on the operand side.

``packed_ssa_merged_fwd`` is the vision layout of the same product: q/k/v stay
token-major (W, B, N, D) with heads as lane slices of D, one program per image
and lane group of whole heads, all N tokens at once -- no head split or merge
around the kernel and no padding of the head dim.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spike_matmul.kernel import bitplane


def _causal_tile_mask(bq: int, m: int):
    """(bq, m) lower-triangular mask for the current query block: row r of
    block qi is global token ``qi*bq + r`` (softmax-free, so masking writes 0
    into the score tile -- no -inf bookkeeping)."""
    qi = pl.program_id(1)
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, m), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, m), 1)
    return cols <= rows


def ssa_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool):
    q = q_ref[0]            # (block_q, D)
    k = k_ref[0]            # (M, D)
    v = v_ref[0]            # (M, D)
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32)   # (block_q, M)
    if causal:
        scores = jnp.where(_causal_tile_mask(*scores.shape), scores, 0.0)
    out = jnp.dot(scores, v, preferred_element_type=jnp.float32) * scale
    o_ref[0] = out.astype(o_ref.dtype)


def _block_q(n: int) -> int:
    """Query block size: ``n`` must already be sublane-aligned (ops.py pads
    ragged token counts), so the fallback never launches an unaligned block."""
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if n % cand == 0:
            return cand
    raise ValueError(f"query token count {n} is not sublane-aligned (pad to 8)")


def ssa_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *, scale: float,
            interpret: bool, causal: bool = False) -> jax.Array:
    g, n, d = q.shape
    m = k.shape[1]
    bq = _block_q(n)
    grid = (g, n // bq)
    return pl.pallas_call(
        functools.partial(ssa_kernel, scale=scale, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda gi, qi: (gi, qi, 0)),
            pl.BlockSpec((1, m, d), lambda gi, qi: (gi, 0, 0)),
            pl.BlockSpec((1, m, d), lambda gi, qi: (gi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda gi, qi: (gi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((g, n, d), q.dtype),
        interpret=interpret,
    )(q, k, v)


def packed_ssa_kernel(qw_ref, kw_ref, vw_ref, o_ref, *, t_total: int,
                      scale: float, causal: bool):
    """SSA on bit-packed operands: unpack q/k/v bitplanes per-tile in VMEM.

    ``qw_ref``/``kw_ref``/``vw_ref`` are uint32 word tiles -- bit ``t % 32``
    of word ``t // 32`` is the spike at time step ``t`` (the
    ``repro.core.packing`` layout), so one HBM read of each operand tile
    covers ALL T time steps; the dense kernel reads T f32 planes.  Each
    bitplane is extracted with the GEMM kernel's shift-and-mask
    (:func:`~repro.kernels.spike_matmul.kernel.bitplane`) and fed to the
    two MXU contractions; the T output planes share the q/k/v words already
    resident in VMEM.
    """
    mask = (_causal_tile_mask(qw_ref.shape[2], kw_ref.shape[2])
            if causal else None)
    for t in range(t_total):
        wi, bit = divmod(t, 32)
        qt = bitplane(qw_ref[wi, 0], bit)
        kt = bitplane(kw_ref[wi, 0], bit)
        vt = bitplane(vw_ref[wi, 0], bit)
        scores = jnp.dot(qt, kt.T, preferred_element_type=jnp.float32)
        if mask is not None:
            scores = jnp.where(mask, scores, 0.0)
        out = jnp.dot(scores, vt, preferred_element_type=jnp.float32) * scale
        o_ref[t, 0] = out.astype(o_ref.dtype)


def sparse_packed_ssa_kernel(occ_ref, qw_ref, kw_ref, vw_ref, o_ref, *,
                             t_total: int, scale: float, causal: bool):
    """Occupancy-predicated packed SSA: each bitplane's two MXU contractions
    run only when the plane is live for this (b, h) fold -- ``occ_ref`` is
    the whole (G, T) liveness map, flattened to int32 and scalar-prefetched
    into SMEM; entry ``g*T + t`` is 1 iff q, k AND v all carry at least one
    spike at time step ``t`` (ops.py derives it from a bitwise-OR reduce of
    the words).  A dead plane's output is exactly zero (one of the two
    contractions has an all-zero operand), so it is written as zeros without
    unpacking anything -- bit-exact vs :func:`packed_ssa_kernel` because
    bitplanes are independent.
    """
    mask = (_causal_tile_mask(qw_ref.shape[2], kw_ref.shape[2])
            if causal else None)
    base = pl.program_id(0) * t_total
    for t in range(t_total):
        wi, bit = divmod(t, 32)

        @pl.when(occ_ref[base + t] > 0)
        def _live(t=t, wi=wi, bit=bit):
            qt = bitplane(qw_ref[wi, 0], bit)
            kt = bitplane(kw_ref[wi, 0], bit)
            vt = bitplane(vw_ref[wi, 0], bit)
            scores = jnp.dot(qt, kt.T, preferred_element_type=jnp.float32)
            if mask is not None:
                scores = jnp.where(mask, scores, 0.0)
            out = jnp.dot(scores, vt, preferred_element_type=jnp.float32) * scale
            o_ref[t, 0] = out.astype(o_ref.dtype)

        @pl.when(occ_ref[base + t] == 0)
        def _dead(t=t):
            o_ref[t, 0] = jnp.zeros_like(o_ref[t, 0])


def sparse_packed_ssa_fwd(qw: jax.Array, kw: jax.Array, vw: jax.Array,
                          occ: jax.Array, *, t_total: int, scale: float,
                          interpret: bool, causal: bool = False) -> jax.Array:
    """Sparse variant of :func:`packed_ssa_fwd`; ``occ`` is the (G, T)
    per-(fold, bitplane) liveness map."""
    w, g, n, d = qw.shape
    m = kw.shape[2]
    bq = _block_q(n)
    grid = (g, n // bq)
    if occ.shape != (g, t_total):
        raise ValueError(f"liveness map {occ.shape} is not ({g}, {t_total})")
    return pl.pallas_call(
        functools.partial(sparse_packed_ssa_kernel, t_total=t_total,
                          scale=scale, causal=causal),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((w, 1, bq, d), lambda gi, qi, occ: (0, gi, qi, 0)),
                pl.BlockSpec((w, 1, m, d), lambda gi, qi, occ: (0, gi, 0, 0)),
                pl.BlockSpec((w, 1, m, d), lambda gi, qi, occ: (0, gi, 0, 0)),
            ],
            out_specs=pl.BlockSpec((t_total, 1, bq, d),
                                   lambda gi, qi, occ: (0, gi, qi, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((t_total, g, n, d), jnp.float32),
        interpret=interpret,
    )(occ.reshape(-1).astype(jnp.int32), qw, kw, vw)


def packed_ssa_fwd(qw: jax.Array, kw: jax.Array, vw: jax.Array, *,
                   t_total: int, scale: float, interpret: bool,
                   causal: bool = False) -> jax.Array:
    """qw (W, G, N, D), kw/vw (W, G, M, D) uint32 spike words (W = ceil(T/32)
    words per train -- multi-word trains supported) -> (T, G, N, D) f32 drive.
    """
    w, g, n, d = qw.shape
    m = kw.shape[2]
    bq = _block_q(n)
    grid = (g, n // bq)
    return pl.pallas_call(
        functools.partial(packed_ssa_kernel, t_total=t_total, scale=scale,
                          causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((w, 1, bq, d), lambda gi, qi: (0, gi, qi, 0)),
            pl.BlockSpec((w, 1, m, d), lambda gi, qi: (0, gi, 0, 0)),
            pl.BlockSpec((w, 1, m, d), lambda gi, qi: (0, gi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((t_total, 1, bq, d), lambda gi, qi: (0, gi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((t_total, g, n, d), jnp.float32),
        interpret=interpret,
    )(qw, kw, vw)


def head_group(d: int, dh: int) -> int:
    """Lanes per program of the head-merged SSA: the fewest that hold whole
    heads and form a legal block -- a multiple of 128 lanes or the whole
    width ``d`` (128 itself whenever ``dh`` divides 128 and 128 divides ``d``)."""
    for g in range(dh, d + 1, dh):
        if d % g == 0 and (g % 128 == 0 or g == d):
            return g
    raise ValueError(f"head dim {dh} does not divide width {d}")


def packed_ssa_merged_kernel(qw_ref, kw_ref, vw_ref, o_ref, *, t_total: int,
                             dh: int, scale: float):
    """Head-merged packed SSA over one image and one lane group of whole heads.

    Each bitplane of q, k and v is unpacked once per program.  Head ``j``'s
    scores contract q masked to its lanes against all of k (the other heads'
    lanes add zeros), and its output is scores times v masked to its lanes,
    so each head writes only its own lanes of the (N, g) tile.  Spikes are
    0/1 and a head's score counts at most ``dh`` lanes, so both MXU operands
    are exact in bfloat16 for ``dh <= 256``; every sum is an integer below
    2^24 in float32 and ``scale`` is a power of two: the drive is bit-exact.
    """
    g = qw_ref.shape[-1]
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, g), 1) // dh
    dt = jnp.bfloat16 if dh <= 256 else jnp.float32
    for t in range(t_total):
        wi, bit = divmod(t, 32)
        q = bitplane(qw_ref[wi, 0], bit)
        k = bitplane(kw_ref[wi, 0], bit).astype(dt)
        v = bitplane(vw_ref[wi, 0], bit)
        out = None
        for j in range(g // dh):
            in_head = lane_head == j
            qj = jnp.where(in_head, q, 0.0).astype(dt)
            vj = jnp.where(in_head, v, 0.0).astype(dt)
            scores = jnp.dot(qj, k.T, preferred_element_type=jnp.float32)
            part = jnp.dot(scores.astype(dt), vj,
                           preferred_element_type=jnp.float32)
            out = part if out is None else out + part
        o_ref[t, 0] = out * scale


def packed_ssa_merged_fwd(qw: jax.Array, kw: jax.Array, vw: jax.Array, *,
                          t_total: int, heads: int, scale: float,
                          interpret: bool) -> jax.Array:
    """qw/kw/vw (W, B, N, D) token-major uint32 spike words, ``heads`` heads
    as lane slices of D -> (T, B, N, D) f32 drive.  Grid (B, D / g), blocks
    (W, 1, N, g) in and (T, 1, N, g) out: the token block is the whole N."""
    w, b, n, d = qw.shape
    if d % heads:
        raise ValueError(f"width {d} does not split into {heads} heads")
    dh = d // heads
    g = head_group(d, dh)
    words = pl.BlockSpec((w, 1, n, g), lambda bi, gi: (0, bi, 0, gi))
    return pl.pallas_call(
        functools.partial(packed_ssa_merged_kernel, t_total=t_total, dh=dh,
                          scale=scale),
        grid=(b, d // g),
        in_specs=[words, words, words],
        out_specs=pl.BlockSpec((t_total, 1, n, g), lambda bi, gi: (0, bi, 0, gi)),
        out_shape=jax.ShapeDtypeStruct((t_total, b, n, d), jnp.float32),
        interpret=interpret,
    )(qw, kw, vw)

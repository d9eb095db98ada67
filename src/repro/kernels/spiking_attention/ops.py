"""Jitted wrappers for the spiking_attention Pallas kernels.

Folds (T, B, H, N, Dh) -> (G, N, Dh), pads Dh to lane alignment and the token
axes to sublane alignment (zero padding is exact for SSA: padded lanes/rows
contribute 0 to both contractions), and calls the kernel.  Backward: SSA is
bilinear with no softmax, so the VJP is two more SSA-shaped contractions -- we
let JAX differentiate the kernel-free oracle via a custom VJP to keep training
correct while the forward uses the kernel.

``packed_ssa_op`` is the packed-operand entry point: q/k/v arrive as uint32
bitplane words (``repro.core.packing`` layout, multi-word trains supported),
so the attention operands stay packed end to end -- the kernel unpacks
bitplanes per-tile in VMEM.  Inference-only (packed trains do not carry
gradients).  ``packed_ssa_merged_op`` takes the same words token-major
(W, B, N, D), heads as lane slices of D, and returns the drive in the
(T, B, N, D) layout the attention LIF consumes: no head split, merge or pad.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lif_parallel.ops import resolve_interpret
from repro.kernels.spiking_attention import kernel as K
from repro.kernels.spiking_attention.ref import ssa_ref


def _pad_d(x):
    d = x.shape[-1]
    pad = (-d) % 128
    if pad:
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))
    return x, d


def _pad_tokens(x, axis: int):
    """Pad a token axis to sublane alignment (8): zero rows are exact for SSA
    (padded queries write zero rows that are sliced away; padded keys/values
    contribute 0 to both contractions)."""
    n = x.shape[axis]
    pad = (-n) % 8
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x, n


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ssa(q, k, v, scale, interpret, causal):
    qp, d = _pad_d(q)
    kp, _ = _pad_d(k)
    vp, _ = _pad_d(v)
    qp, n = _pad_tokens(qp, 1)
    kp, _ = _pad_tokens(kp, 1)
    vp, _ = _pad_tokens(vp, 1)
    out = K.ssa_fwd(qp, kp, vp, scale=scale, interpret=interpret, causal=causal)
    return out[:, :n, :d]


def _ssa_fwd(q, k, v, scale, interpret, causal):
    return _ssa(q, k, v, scale, interpret, causal), (q, k, v)


def _ssa_bwd(scale, interpret, causal, res, g):
    q, k, v = res
    # d/dq [(qk^T)v s] = (g v^T) k s ; d/dk = (g^T q)^T ... all bilinear:
    _, vjp = jax.vjp(
        lambda a, b, c: ssa_ref(a, b, c, scale=scale, causal=causal), q, k, v)
    return vjp(g)


_ssa.defvjp(_ssa_fwd, _ssa_bwd)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "causal"))
def ssa_op(q: jax.Array, k: jax.Array, v: jax.Array, *, scale: float = 0.125,
           interpret: bool | None = None, causal: bool = False) -> jax.Array:
    """Tick-batched spiking attention. q,k,v: (T, B, H, N, Dh) -> same shape.
    ``causal`` masks the spike score matrix to the lower triangle in-kernel."""
    t, b, h, n, dh = q.shape
    fold = lambda x: x.reshape(t * b * h, x.shape[3], dh)
    out = _ssa(fold(q), fold(k), fold(v), float(scale),
               resolve_interpret(interpret), causal)
    return out.reshape(t, b, h, n, dh)


@functools.partial(jax.jit, static_argnames=("t", "scale", "interpret", "causal"))
def packed_ssa_op(qw: jax.Array, kw: jax.Array, vw: jax.Array, *, t: int,
                  scale: float = 0.125, interpret: bool | None = None,
                  causal: bool = False) -> jax.Array:
    """Packed-operand tick-batched spiking attention.

    qw/kw/vw: (W, B, H, N, Dh) uint32 spike words carrying all ``t`` time
    steps bit-packed along the word axis (W = ceil(t/32); multi-word trains
    are unrolled inside the kernel) -> dense drive (T, B, H, N, Dh) f32.
    The operand read from HBM is 1/min(t,32) of the dense kernel's; bitplanes
    are unpacked per-tile in VMEM.
    """
    w, b, h, n, dh = qw.shape
    fold = lambda x: x.reshape(w, b * h, x.shape[3], dh)
    qf, d = _pad_d(fold(qw))
    kf, _ = _pad_d(fold(kw))
    vf, _ = _pad_d(fold(vw))
    qf, n = _pad_tokens(qf, 2)
    kf, _ = _pad_tokens(kf, 2)
    vf, _ = _pad_tokens(vf, 2)
    out = K.packed_ssa_fwd(qf, kf, vf, t_total=t, scale=float(scale),
                           interpret=resolve_interpret(interpret),
                           causal=causal)
    return out[:, :, :n, :d].reshape(t, b, h, n, dh)


@functools.partial(jax.jit, static_argnames=("t", "heads", "scale", "interpret"))
def packed_ssa_merged_op(qw: jax.Array, kw: jax.Array, vw: jax.Array, *,
                         t: int, heads: int, scale: float = 0.125,
                         interpret: bool | None = None) -> jax.Array:
    """Head-merged packed SSA: qw/kw/vw (W, B, N, D) token-major uint32 spike
    words with ``heads`` heads as lane slices of D -> dense drive
    (T, B, N, D) f32, bit-identical to
    ``merge_heads(packed_ssa_op(split_heads_packed(...)))``.  One kernel
    program per image and lane group of whole heads (``kernel.head_group``),
    all N tokens at once; non-causal (the vision SSA)."""
    return K.packed_ssa_merged_fwd(qw, kw, vw, t_total=t, heads=heads,
                                   scale=float(scale),
                                   interpret=resolve_interpret(interpret))


def _plane_liveness(qf, kf, vf, t: int) -> jax.Array:
    """Per-(fold, bitplane) liveness of three packed operands: (G, T)
    uint32, 1 iff q, k and v all spike somewhere at that time step.

    One bitwise-OR reduce over the token/feature axes collapses each operand
    to (W, G) or-words whose bit ``t % 32`` says "plane t has a spike" -- the
    SSA analogue of the GEMM's popcount occupancy map, at bitplane (not tile)
    granularity and computed without unpacking.
    """
    ors = [jax.lax.reduce(x, jnp.uint32(0), jax.lax.bitwise_or, (2, 3))
           for x in (qf, kf, vf)]
    comb = ors[0] & ors[1] & ors[2]                       # (W, G)
    steps = jnp.arange(t, dtype=jnp.uint32)
    live = (comb[steps // 32] >> (steps % 32)[:, None]) & jnp.uint32(1)
    return live.T                                         # (G, T)


@functools.partial(jax.jit, static_argnames=("t", "scale", "interpret", "causal"))
def sparse_packed_ssa_op(qw: jax.Array, kw: jax.Array, vw: jax.Array, *,
                         t: int, scale: float = 0.125,
                         interpret: bool | None = None,
                         causal: bool = False) -> jax.Array:
    """Occupancy-gated packed SSA: bit-exact vs :func:`packed_ssa_op`
    (bitplanes are independent, so skipping dead planes re-associates
    nothing), but time steps where q, k or v is silent for a (b, h) fold --
    the common case late in IAND-thinned trains -- never unpack or touch the
    MXU; their output planes are written as zeros."""
    w, b, h, n, dh = qw.shape
    fold = lambda x: x.reshape(w, b * h, x.shape[3], dh)
    qf, d = _pad_d(fold(qw))
    kf, _ = _pad_d(fold(kw))
    vf, _ = _pad_d(fold(vw))
    qf, n = _pad_tokens(qf, 2)
    kf, _ = _pad_tokens(kf, 2)
    vf, _ = _pad_tokens(vf, 2)
    occ = _plane_liveness(qf, kf, vf, t)
    out = K.sparse_packed_ssa_fwd(qf, kf, vf, occ, t_total=t,
                                  scale=float(scale),
                                  interpret=resolve_interpret(interpret),
                                  causal=causal)
    return out[:, :, :n, :d].reshape(t, b, h, n, dh)

"""Pallas TPU kernel: T-folded spike x weight GEMM.

The accelerator's 8x9 PE array supports 3x3 conv, 1x1 conv and matmul through
one vectorized dataflow with two accumulation directions (Fig. 4/6).  The TPU
analogue is ONE tiled GEMM schedule feeding the MXU: 3x3 conv arrives as an
im2col GEMM, 1x1 conv and matmul arrive directly (ops.py does the folding).
Time steps are folded into the M dimension, so every weight tile is read from
HBM once for all T time steps -- the paper's single-weight-read property
(measured in benchmarks/table2_weight_traffic.py).

Grid (M/bm, C/bc, K/bk); K is the innermost (arbitrary-order) axis with a VMEM
f32 accumulator, written back on the last K step. Tiles are 128-aligned for
the MXU. Spike operands are {0,1} in the input dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def matmul_kernel(x_ref, w_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _write():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _tile(dim: int, prefs: tuple[int, ...]) -> int:
    for cand in prefs:
        if dim % cand == 0:
            return cand
    return dim


def bitplane(words: jax.Array, bit: int) -> jax.Array:
    """Bitplane ``bit`` of a uint32 word tile as f32 {0, 1}: a shift-and-mask
    in VMEM.  The 0/1 value crosses to f32 through int32 because Mosaic has
    no uint32 -> float32 cast (the detour is exact for 0/1)."""
    plane = (words >> jnp.uint32(bit)) & jnp.uint32(1)
    return plane.astype(jnp.int32).astype(jnp.float32)


def packed_matmul_kernel(xw_ref, w_ref, o_ref, acc_ref, *, t_total: int):
    """GEMM on bit-packed spike operands: unpack per-tile in VMEM.

    ``xw_ref`` is a (bm, bk) tile of uint32 words -- bit t of each word is the
    spike of that (row, k) element at time step t (one HBM read covers all T
    time steps; the dense equivalent reads T f32 planes).  Each bitplane is
    extracted in VMEM with a shift-and-mask and fed to the MXU; the f32
    accumulator holds all T output planes so each weight tile is also read
    once for every time step.
    """
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    words = xw_ref[...]
    w = w_ref[...]
    for t in range(t_total):
        acc_ref[t] += jnp.dot(bitplane(words, t), w,
                              preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _write():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def sparse_packed_matmul_kernel(occ_ref, xw_ref, w_ref, o_ref, acc_ref, *,
                                t_total: int):
    """Occupancy-predicated packed GEMM tile: the unpack-and-accumulate body
    runs only when the occupancy map says the (bm, bk) word tile carries at
    least one spike.  Skipping is exact -- an all-zero spike tile's
    contribution to the accumulator is exactly 0.0 -- and saves both the T
    shift-and-mask unpacks and the T MXU dots of a dead tile.

    ``occ_ref`` is the whole per-(M-tile, K-tile) popcount map, flattened
    row-major to int32 and scalar-prefetched into SMEM (ops.py reduces the
    pack-time occupancy map to this grid's tiling); a (1, 1) VMEM block of it
    would violate the (8, 128) tiling rule.
    """
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tile = pl.program_id(0) * pl.num_programs(2) + pl.program_id(2)

    @pl.when(occ_ref[tile] > 0)
    def _body():
        words = xw_ref[...]
        w = w_ref[...]
        for t in range(t_total):
            acc_ref[t] += jnp.dot(bitplane(words, t), w,
                                  preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _write():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def sparse_packed_spike_matmul_fwd(xw: jax.Array, w: jax.Array,
                                   occ_tiles: jax.Array, *, t_total: int,
                                   interpret: bool) -> jax.Array:
    """Sparse variant of :func:`packed_spike_matmul_fwd`: same grid and tile
    schedule, with the tile body predicated on ``occ_tiles`` (the
    (m/bm, k/bk) per-tile popcounts).  Bit-exact vs the dense-tile kernel:
    the K accumulation order of surviving tiles is unchanged."""
    if t_total > 32:
        raise ValueError(f"packed GEMM holds T<=32 steps per word, got {t_total}")
    m, k = xw.shape
    _, c = w.shape
    bm = _tile(m, (256, 128, 64, 32, 16, 8))
    bc = _tile(c, (256, 128))
    bk = _tile(k, (512, 256, 128))
    grid = (m // bm, c // bc, k // bk)
    if occ_tiles.shape != (m // bm, k // bk):
        raise ValueError(
            f"occupancy tiles {occ_tiles.shape} do not match the "
            f"({m // bm}, {k // bk}) grid tiling")
    kern = functools.partial(sparse_packed_matmul_kernel, t_total=t_total)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, l, occ: (i, l)),
                pl.BlockSpec((bk, bc), lambda i, j, l, occ: (l, j)),
            ],
            out_specs=pl.BlockSpec((t_total, bm, bc),
                                   lambda i, j, l, occ: (0, i, j)),
            scratch_shapes=[pltpu.VMEM((t_total, bm, bc), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t_total, m, c), jnp.float32),
        interpret=interpret,
    )(occ_tiles.reshape(-1).astype(jnp.int32), xw, w)


def packed_spike_matmul_fwd(xw: jax.Array, w: jax.Array, *, t_total: int,
                            interpret: bool) -> jax.Array:
    """xw: (M, K) uint32 packed spike words (T <= 32 time steps per word),
    w: (K, C) weights -> (T, M, C) f32 accumulated."""
    if t_total > 32:
        raise ValueError(f"packed GEMM holds T<=32 steps per word, got {t_total}")
    m, k = xw.shape
    _, c = w.shape
    # T f32 output planes share the accumulator, so keep tiles MXU-minimal
    bm = _tile(m, (256, 128, 64, 32, 16, 8))
    bc = _tile(c, (256, 128))
    bk = _tile(k, (512, 256, 128))
    grid = (m // bm, c // bc, k // bk)
    kern = functools.partial(packed_matmul_kernel, t_total=t_total)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bc), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((t_total, bm, bc), lambda i, j, l: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((t_total, m, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((t_total, bm, bc), jnp.float32)],
        interpret=interpret,
    )(xw, w)


def spike_matmul_fwd(x: jax.Array, w: jax.Array, *, interpret: bool) -> jax.Array:
    """x: (M, K) spikes, w: (K, C) weights -> (M, C) f32 accumulated."""
    m, k = x.shape
    _, c = w.shape
    bm = _tile(m, (512, 256, 128, 64, 32, 16, 8))
    bc = _tile(c, (512, 256, 128))
    bk = _tile(k, (512, 256, 128))
    grid = (m // bm, c // bc, k // bk)
    return pl.pallas_call(
        matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bc), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bc), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bc), jnp.float32)],
        interpret=interpret,
    )(x, w)

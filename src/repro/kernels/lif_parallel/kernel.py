"""Pallas TPU kernel: reconfigurable unrolled multi-time-step LIF (+ fused IAND).

This is the TPU mapping of the paper's core hardware contribution (Fig. 5):

* The drive for ALL T time steps of a feature block is resident in one VMEM
  tile; the T-step membrane chain is unrolled *inside* the kernel, so membrane
  potentials live only in registers/VMEM and generate **zero HBM traffic** --
  the analogue of eliminating the membrane SRAM.
* HBM traffic is exactly: read drive once, write spikes once. A serial
  (scan-over-T) schedule reads/writes the membrane every step.
* ``chain_len`` reproduces the 3-mux reconfigurability (111/101/000 for
  T=4/2/1): the T slots form independent chains whose membrane resets at chain
  boundaries; the unrolled datapath is identical, only the boundary mask
  changes.
* The IAND residual (paper's AND-NOT gate replacing the residual adder) is an
  optional fused epilogue: ``out = skip * (1 - spike)`` -- binary in, binary
  out, no extra HBM round-trip for the residual connective.

Layout: drive is (T, N) with N the flattened feature dim; blocks are
(T, block_n) with block_n a multiple of 128 (lane-aligned); T <= 8 occupies the
sublane dim. The backward kernel recomputes the membrane chain in VMEM
(activation remat at the kernel level) and propagates the surrogate/boxcar
gradient through the unrolled chain, including the hard-reset path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _chain(t_total: int, chain_len: int, lam: float, theta: float,
           reset: str, drive_rows):
    """Unrolled membrane chain over rows ``drive_rows[t]``; returns (spikes, us)."""
    spikes, us = [], []
    v = jnp.zeros_like(drive_rows[0])
    for t in range(t_total):
        if t % chain_len == 0:  # mux: chain boundary -> fresh membrane
            v = jnp.zeros_like(v)
        u = lam * v + drive_rows[t]
        s = (u >= theta).astype(u.dtype)
        v = u * (1.0 - s) if reset == "hard" else u - theta * s
        spikes.append(s)
        us.append(u)
    return spikes, us


def lif_fwd_kernel(drive_ref, out_ref, *, t_total: int, chain_len: int,
                   lam: float, theta: float, reset: str):
    rows = [drive_ref[t, :] for t in range(t_total)]
    spikes, _ = _chain(t_total, chain_len, lam, theta, reset, rows)
    for t in range(t_total):
        out_ref[t, :] = spikes[t]


def lif_iand_fwd_kernel(drive_ref, skip_ref, out_ref, *, t_total: int,
                        chain_len: int, lam: float, theta: float, reset: str):
    rows = [drive_ref[t, :] for t in range(t_total)]
    spikes, _ = _chain(t_total, chain_len, lam, theta, reset, rows)
    for t in range(t_total):  # fused IAND epilogue: skip AND NOT spike
        out_ref[t, :] = skip_ref[t, :] * (1.0 - spikes[t])


def lif_bwd_kernel(drive_ref, g_ref, dx_ref, *, t_total: int, chain_len: int,
                   lam: float, theta: float, reset: str, width: float):
    """Backward of the unrolled chain w.r.t. drive (surrogate boxcar).

    Recomputes u_t in VMEM (kernel-level remat), then walks the chain in
    reverse, accumulating the spike cotangent BEFORE multiplying by the
    surrogate -- ds_t = g_t - dv_t * u_t (hard reset), du_t = ds_t * surr'(u_t)
    + dv_t * (1 - s_t) -- the exact grouping JAX autodiff produces for the jnp
    oracle, so the chain-carried dv path stays bit-identical across time-step
    boundaries (distributing surr over the sum instead drifts by ~1 ulp per
    chained step).
    """
    rows = [drive_ref[t, :] for t in range(t_total)]
    spikes, us = _chain(t_total, chain_len, lam, theta, reset, rows)
    dv = jnp.zeros_like(rows[0])
    for t in reversed(range(t_total)):
        u, s = us[t], spikes[t]
        surr = (jnp.abs(u - theta) < (width / 2.0)).astype(u.dtype) / width
        if reset == "hard":
            ds = g_ref[t, :] - dv * u      # spike cotangent incl. reset path
            du = ds * surr + dv * (1.0 - s)
        else:
            ds = g_ref[t, :] - theta * dv
            du = ds * surr + dv
        dx_ref[t, :] = du
        # membrane flowing back across a chain boundary is cut by the mux
        dv = lam * du if t % chain_len != 0 else jnp.zeros_like(du)


_WORD_BITS = 32


def _pack_rows(spikes):
    """Pack T spike rows (f32 {0,1}) into ``ceil(T/32)`` uint32 word rows.

    The packing runs inside the kernel epilogue, so the spike train leaves
    VMEM already packed -- HBM sees one uint32 word per neuron per 32 steps
    instead of T f32 writes (the packed datapath's traffic win starts here).
    The 0/1 spike crosses to uint32 through int32 because Mosaic has no
    float32 -> uint32 cast (the detour is exact for 0/1).
    """
    t_total = len(spikes)
    words = []
    for w in range(-(-t_total // _WORD_BITS)):
        acc = jnp.zeros_like(spikes[0], dtype=jnp.uint32)
        for t in range(w * _WORD_BITS, min((w + 1) * _WORD_BITS, t_total)):
            bit = spikes[t].astype(jnp.int32).astype(jnp.uint32)
            acc = acc | (bit << jnp.uint32(t % _WORD_BITS))
        words.append(acc)
    return words


def lif_pack_fwd_kernel(drive_ref, out_ref, *, t_total: int, chain_len: int,
                        lam: float, theta: float, reset: str):
    """Unrolled LIF whose epilogue emits packed uint32 spike words."""
    rows = [drive_ref[t, :] for t in range(t_total)]
    spikes, _ = _chain(t_total, chain_len, lam, theta, reset, rows)
    for w, word in enumerate(_pack_rows(spikes)):
        out_ref[w, :] = word


def lif_iand_pack_fwd_kernel(drive_ref, skip_ref, out_ref, *, t_total: int,
                             chain_len: int, lam: float, theta: float,
                             reset: str):
    """Packed-in/packed-out fused LIF+IAND: the AND-NOT residual is a single
    bitwise ``skip & ~spikes`` on the packed words (the paper's AND-NOT gate,
    literally one gate per 32 time steps)."""
    rows = [drive_ref[t, :] for t in range(t_total)]
    spikes, _ = _chain(t_total, chain_len, lam, theta, reset, rows)
    for w, word in enumerate(_pack_rows(spikes)):
        out_ref[w, :] = skip_ref[w, :] & ~word


def _block_n(n: int) -> int:
    for cand in (8192, 4096, 2048, 1024, 512, 256, 128):
        if n % cand == 0:
            return cand
    return n  # unaligned tail: single block (interpret mode tolerates this)


def lif_parallel_fwd(drive: jax.Array, *, chain_len: int, lam: float,
                     theta: float, reset: str, skip: jax.Array | None,
                     interpret: bool) -> jax.Array:
    """drive: (T, N) -> spikes (T, N) (or IAND(skip, spikes) if skip given)."""
    t_total, n = drive.shape
    bn = _block_n(n)
    grid = (n // bn,)
    spec = pl.BlockSpec((t_total, bn), lambda i: (0, i))
    if skip is None:
        kern = functools.partial(
            lif_fwd_kernel, t_total=t_total, chain_len=chain_len, lam=lam,
            theta=theta, reset=reset)
        in_specs = [spec]
        args = (drive,)
    else:
        kern = functools.partial(
            lif_iand_fwd_kernel, t_total=t_total, chain_len=chain_len, lam=lam,
            theta=theta, reset=reset)
        in_specs = [spec, spec]
        args = (drive, skip)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(drive.shape, drive.dtype),
        interpret=interpret,
    )(*args)


def lif_parallel_pack_fwd(drive: jax.Array, *, chain_len: int, lam: float,
                          theta: float, reset: str,
                          skip_words: jax.Array | None,
                          interpret: bool) -> jax.Array:
    """drive: (T, N) -> packed spike words (W, N) uint32, W = ceil(T/32).

    ``skip_words``: optional packed (W, N) residual; if given the epilogue is
    the bitwise IAND ``skip & ~spikes`` (packed in, packed out).
    """
    t_total, n = drive.shape
    w_total = -(-t_total // _WORD_BITS)
    bn = _block_n(n)
    grid = (n // bn,)
    dspec = pl.BlockSpec((t_total, bn), lambda i: (0, i))
    wspec = pl.BlockSpec((w_total, bn), lambda i: (0, i))
    if skip_words is None:
        kern = functools.partial(
            lif_pack_fwd_kernel, t_total=t_total, chain_len=chain_len, lam=lam,
            theta=theta, reset=reset)
        in_specs = [dspec]
        args = (drive,)
    else:
        kern = functools.partial(
            lif_iand_pack_fwd_kernel, t_total=t_total, chain_len=chain_len,
            lam=lam, theta=theta, reset=reset)
        in_specs = [dspec, wspec]
        args = (drive, skip_words)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=wspec,
        out_shape=jax.ShapeDtypeStruct((w_total, n), jnp.uint32),
        interpret=interpret,
    )(*args)


def lif_parallel_bwd(drive: jax.Array, g: jax.Array, *, chain_len: int,
                     lam: float, theta: float, reset: str, width: float,
                     interpret: bool) -> jax.Array:
    t_total, n = drive.shape
    bn = _block_n(n)
    spec = pl.BlockSpec((t_total, bn), lambda i: (0, i))
    kern = functools.partial(
        lif_bwd_kernel, t_total=t_total, chain_len=chain_len, lam=lam,
        theta=theta, reset=reset, width=width)
    return pl.pallas_call(
        kern,
        grid=(n // bn,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(drive.shape, drive.dtype),
        interpret=interpret,
    )(drive, g)

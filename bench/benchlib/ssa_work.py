"""The least time of the spiking self-attention (SSA) of one executor call,
counted as :mod:`benchlib.work` counts the other layers: the packed q, k
and v words read once, the float32 drive of every time step written once,
and the dense-equivalent FLOPs of :func:`work.ssa_flops`."""

from __future__ import annotations

from benchlib import work
from benchlib.model import Arch


def ssa_bytes(arch: Arch, batch: int) -> int:
    """HBM bytes of every block's SSA: q, k and v as uint32 words (one word
    carries all T <= 32 steps of an element) and the (T, B, N, D) float32
    output."""
    elems = batch * work.tokens(arch) * arch.embed_dim
    words = -(-arch.t // 32)
    per_block = 3 * words * elems * work.WORD + arch.t * elems * work.F32
    return per_block * arch.num_layers


def ssa_least_s(arch: Arch, batch: int, flops_per_s: float,
                bytes_per_s: float) -> tuple[float, str]:
    """Least time of every block's SSA in one call, the larger of its FLOP
    and byte bound, and which bound holds."""
    tf = work.ssa_flops(arch, batch) / flops_per_s
    tb = ssa_bytes(arch, batch) / bytes_per_s
    return max(tf, tb), "flops" if tf >= tb else "bytes"

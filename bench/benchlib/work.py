"""Operations and bytes that one executor call needs, from the shapes alone.

These count the algorithm's work, not what a kernel happens to do, so a
kernel that is replaced keeps the same yardstick:

* FLOPs are dense-equivalent: a spike operand counts as an operand, each of
  the T time steps of a spike GEMM counts, a multiply-add is 2 FLOPs.
* Bytes are the least a layer must move through HBM: its packed spike
  input read once (one uint32 word per element carries all T <= 32 steps),
  its float32 weights read once, its output written once.

Shapes follow the model's definition (see ``model.py``): the tokenizer's
3x3 convs run at their input's resolution and pool afterwards; a block holds
q, k, v, proj (d x d), fc1 (d x 4d), fc2 (4d x d) and the SSA over N tokens.
"""

from __future__ import annotations

from dataclasses import dataclass

from benchlib.model import UNITS, Arch

F32 = 4
WORD = 4


@dataclass(frozen=True)
class Gemm:
    """One spike x weight GEMM of a call: ``m`` rows, contraction ``k``,
    ``n`` columns, over ``t`` time steps (the spike operand is packed)."""

    name: str
    m: int
    k: int
    n: int
    t: int
    in_elems: int       # distinct spike elements read (a conv reads each once)

    @property
    def flops(self) -> int:
        return 2 * self.t * self.m * self.k * self.n

    @property
    def bytes(self) -> int:
        return self.in_elems * WORD + self.k * self.n * F32 + self.t * self.m * self.n * F32


def grids(arch: Arch) -> list[int]:
    """Side of each tokenizer stage's input grid (the conv runs at it)."""
    side, out = arch.img_size, []
    for pool in arch.tokenizer_pools:
        out.append(side)
        side //= 2 if pool else 1
    return out + [side]


def tokens(arch: Arch) -> int:
    return grids(arch)[-1] ** 2


def spike_gemms(arch: Arch, batch: int) -> list[Gemm]:
    """The spike GEMMs of one call: tokenizer convs 1.. (as im2col GEMMs) and
    every block's six units, in execution order."""
    sides, chans = grids(arch), arch.stage_channels
    out = []
    for i in range(1, len(chans)):
        m = batch * sides[i] ** 2
        out.append(Gemm(f"tok{i}", m, 9 * chans[i - 1], chans[i], arch.t,
                        m * chans[i - 1]))
    m = batch * tokens(arch)
    for b in range(arch.num_layers):
        for u in UNITS:
            d_in, d_out = arch.unit_dims(u)
            out.append(Gemm(f"block{b}.{u}", m, d_in, d_out, arch.t, m * d_in))
    return out


def encode_flops(arch: Arch, batch: int) -> int:
    """The analog encoding conv: once per image, not per time step."""
    side = arch.img_size
    return 2 * batch * side * side * 9 * arch.in_channels * arch.stage_channels[0]


def ssa_flops(arch: Arch, batch: int) -> int:
    """(q k^T) v per head and time step: two N x N x Dh contractions."""
    n = tokens(arch)
    return 2 * 2 * arch.t * batch * n * n * arch.embed_dim * arch.num_layers


def head_flops(arch: Arch, batch: int) -> int:
    return 2 * batch * arch.embed_dim * arch.num_classes


def model_flops(arch: Arch, batch: int) -> int:
    """Dense-equivalent FLOPs of one executor call on ``batch`` images."""
    return (sum(g.flops for g in spike_gemms(arch, batch)) + encode_flops(arch, batch)
            + ssa_flops(arch, batch) + head_flops(arch, batch))


def gemm_least_s(arch: Arch, batch: int, flops_per_s: float,
                 bytes_per_s: float) -> tuple[float, str]:
    """Least time of all spike GEMMs of one call, each at the larger of its
    FLOP and byte bound, and which bound holds for most of that time."""
    by = {"flops": 0.0, "bytes": 0.0}
    for g in spike_gemms(arch, batch):
        tf, tb = g.flops / flops_per_s, g.bytes / bytes_per_s
        by["flops" if tf >= tb else "bytes"] += max(tf, tb)
    return by["flops"] + by["bytes"], max(by, key=by.get)


@dataclass(frozen=True)
class Lif:
    """One LIF dispatch: ``elems`` neurons over ``t`` steps; ``iand`` when
    the AND-NOT residual is fused (it also reads the skip words)."""

    name: str
    elems: int
    t: int
    iand: bool

    @property
    def bytes(self) -> int:
        return self.t * self.elems * F32 + self.elems * WORD * (2 if self.iand else 1)


def lifs(arch: Arch, batch: int, *, encode: bool = True) -> list[Lif]:
    """The LIF dispatches of one call (after pooling, where a stage pools)."""
    sides, chans = grids(arch), arch.stage_channels
    out = [Lif(f"tok{i}", batch * sides[i + 1] ** 2 * chans[i], arch.t, False)
           for i in range(0 if encode else 1, len(chans))]
    m, d = batch * tokens(arch), arch.embed_dim
    for b in range(arch.num_layers):
        for u in ("q", "k", "v", "attn", "proj", "fc1", "fc2"):
            width = arch.hidden if u == "fc1" else d
            out.append(Lif(f"block{b}.{u}", m * width, arch.t, u in ("proj", "fc2")))
    return out


def lif_least_s(arch: Arch, batch: int, bytes_per_s: float) -> float:
    return sum(x.bytes for x in lifs(arch, batch)) / bytes_per_s

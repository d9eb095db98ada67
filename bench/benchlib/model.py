"""Seeded weights and the plain reference of Spike-IAND-Former.

Nothing here imports the program under test.  The weights are made by the
benchmark from the seed, in the parameter layout the program's
``engine.compile_plan`` folds (live BatchNorm, unfolded), and the reference
computes the paper's forward pass from those weights in straightforward
``jax.numpy``, float32 throughout except where a configuration states that
matmul operands are rounded (:class:`_Math`):

    tokenizer: 4 x (3x3 conv -> BatchNorm -> [2x2 max pool] -> LIF), the
               first on the analog image (direct encoding, broadcast over T)
    8 blocks:  q/k/v = LIF(BN(x W)); a = LIF(SSA(q, k, v));
               x = x AND NOT LIF(BN(a W_proj));
               h = LIF(BN(x W_fc1)); x = x AND NOT LIF(BN(h W_fc2))
    head:      logits = mean_{T, tokens}(x) W_head + b_head

LIF: u_t = lam * v_{t-1} + I_t, s_t = [u_t >= theta], v_t = u_t (1 - s_t).
SSA: (q k^T) v * scale per head, no softmax.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

BN_EPS = 1e-5
UNITS = ("q", "k", "v", "proj", "fc1", "fc2")


@dataclass(frozen=True)
class Arch:
    """The sizes of one configuration file, as the reference reads them."""

    img_size: int
    in_channels: int
    num_classes: int
    embed_dim: int
    num_layers: int
    num_heads: int
    mlp_ratio: float
    t: int
    theta: float
    lam: float
    attn_scale: float
    tokenizer_pools: tuple[bool, ...]

    @classmethod
    def from_config(cls, cfg: dict) -> "Arch":
        return cls(**{k: (tuple(cfg[k]) if k == "tokenizer_pools" else cfg[k])
                      for k in cls.__dataclass_fields__})

    @property
    def hidden(self) -> int:
        return int(self.embed_dim * self.mlp_ratio)

    @property
    def stage_channels(self) -> tuple[int, ...]:
        d = self.embed_dim
        return (d // 8, d // 4, d // 2, d)

    def unit_dims(self, name: str) -> tuple[int, int]:
        d, f = self.embed_dim, self.hidden
        return {"fc1": (d, f), "fc2": (f, d)}.get(name, (d, d))


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (wider than 32 bits
    too): the seed is hashed to 32 bits by numpy's SeedSequence."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    word = np.random.SeedSequence(seed).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def init_weights(key, arch: Arch, w_cfg: dict):
    """(params, state) in the program's unfolded layout, all on the device.

    Conv/linear weights are uniform in +-1/sqrt(fan_in) with zero bias (the
    program's own initialiser).  BatchNorm is not the identity: scale, shift
    and running statistics are drawn from ``w_cfg`` so that folding them is
    real work and the spike trains of every stage stay alive (see PERF.md,
    firing rates).
    """
    chans = arch.stage_channels
    keys = iter(jax.random.split(key, 5 * (len(chans) + 6 * arch.num_layers) + 1))

    def uniform(shape, fan_in):
        s = 1.0 / math.sqrt(fan_in)
        return jax.random.uniform(next(keys), shape, jnp.float32, -s, s)

    def bn(c, role):
        lo, hi = w_cfg["bn_scale"]
        shift = w_cfg["bn_shift"].get(role, w_cfg["bn_shift"]["default"])
        p = {"scale": jax.random.uniform(next(keys), (c,), jnp.float32, lo, hi),
             "bias": shift + w_cfg["bn_shift_spread"] * jax.random.normal(
                 next(keys), (c,), jnp.float32)}
        s = {"mean": w_cfg["bn_mean_spread"] * jax.random.normal(
                 next(keys), (c,), jnp.float32),
             "var": jax.random.uniform(next(keys), (c,), jnp.float32,
                                       *w_cfg["bn_var"])}
        return p, s

    params, state = {"tokenizer": {}}, {"tokenizer": {}}
    c_in = arch.in_channels
    for i, c in enumerate(chans):
        params["tokenizer"][f"conv{i}"] = {"w": uniform((3, 3, c_in, c), 9 * c_in)}
        params["tokenizer"][f"bn{i}"], state["tokenizer"][f"bn{i}"] = bn(
            c, "encode" if i == 0 else "tokenizer")
        c_in = c
    for b in range(arch.num_layers):
        bp, bs = {}, {}
        for u in UNITS:
            d_in, d_out = arch.unit_dims(u)
            p_bn, s_bn = bn(d_out, u)
            bp[u] = {"lin": {"w": uniform((d_in, d_out), d_in),
                             "b": jnp.zeros((d_out,), jnp.float32)},
                     "bn": p_bn}
            bs[u] = {"bn": s_bn}
        params[f"block{b}"], state[f"block{b}"] = bp, bs
    params["head"] = {"w": uniform((arch.embed_dim, arch.num_classes), arch.embed_dim),
                      "b": jnp.zeros((arch.num_classes,), jnp.float32)}
    return params, state


# -- forward ------------------------------------------------------------------

def fold(params, state):
    """BatchNorm folded into the preceding conv/linear, as a deploy does:
    w' = w * g, b' = shift - mean * g + b * g with g = scale / sqrt(var + eps).
    Returns the same tree with each layer's (w, b) replaced and no BN."""
    def one(lin, p, s):
        g = p["scale"] * jax.lax.rsqrt(s["var"] + BN_EPS)
        b = p["bias"] - s["mean"] * g
        if "b" in lin:
            b = b + lin["b"] * g
        return {"w": lin["w"] * g, "b": b}

    tp, ts = params["tokenizer"], state["tokenizer"]
    out = {"tokenizer": {f"conv{i}": one(tp[f"conv{i}"], tp[f"bn{i}"], ts[f"bn{i}"])
                         for i in range(len(ts))},
           "head": params["head"]}
    for name, bp in params.items():
        if name.startswith("block"):
            out[name] = {u: one(bp[u]["lin"], bp[u]["bn"], state[name][u]["bn"])
                         for u in bp}
    return out


def quantize_int8(folded):
    """Every conv/linear weight of a folded tree rounded to symmetric int8 per
    output channel (dequantized back to float32): the narrower weight storage
    a later change could try.  Spike operands are exact in int8, so computing
    with these at float32 is what an int8 datapath with int32 accumulation
    gives."""
    def q(path, w):
        if path[-1].key != "w":
            return w
        axes = tuple(range(w.ndim - 1))
        scale = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return jnp.round(w / scale) * scale
    return jax.tree_util.tree_map_with_path(q, folded)


def _lif(drive, theta, lam):
    out, v = [], jnp.zeros_like(drive[0])
    for t in range(drive.shape[0]):
        u = lam * v + drive[t]
        s = (u >= theta).astype(jnp.float32)
        v = u * (1.0 - s)
        out.append(s)
    return jnp.stack(out)


def _pool(y):
    return jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


class _Math:
    """Dots and convs with both operands in ``operands`` and float32
    accumulation: bfloat16 is what JAX's default precision does on the TPU;
    float32 runs at the highest precision."""

    def __init__(self, operands: str):
        self.dt = jnp.dtype(operands)
        self.prec = "highest" if self.dt == jnp.float32 else None

    def conv(self, x, w):
        return jax.lax.conv_general_dilated(
            x.astype(self.dt), w.astype(self.dt), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=self.prec,
            preferred_element_type=jnp.float32)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, a.astype(self.dt), b.astype(self.dt),
                          precision=self.prec, preferred_element_type=jnp.float32)


def forward(params, state, images, arch: Arch, *, operands: str = "float32",
            rates: bool = False, calibrate: bool = False, folded=None):
    """images (B, H, W, C) in [0, 1] -> logits (B, classes).

    Runs on ``folded`` weights (see :func:`fold`) when given, else on the
    unfolded ``params``/``state`` with BatchNorm applied after each layer.
    ``rates`` also returns a dict of mean spike rates: each tokenizer stage's
    output, each block's output (the residual stream) and each block's
    branch trains.  ``calibrate`` runs every BatchNorm on the batch's own
    statistics and returns, instead of logits, the state that holds them as
    running statistics (how a trained model's BatchNorm is set)."""
    m = _Math(operands)
    t = arch.t
    seen: dict = {}
    stats: dict = {"tokenizer": {}}

    def layer(path, x, apply):
        """One conv/linear ``apply(x, w)`` plus its bias and BatchNorm."""
        if folded is not None:
            p = folded
            for k in path:
                p = p[k]
            return apply(x, p["w"]) + p["b"]
        if path[0] == "tokenizer":
            i = path[1][4:]
            lin, bn, st = (params["tokenizer"][path[1]], params["tokenizer"][f"bn{i}"],
                           state["tokenizer"][f"bn{i}"])
        else:
            lin, bn, st = (params[path[0]][path[1]]["lin"], params[path[0]][path[1]]["bn"],
                           state[path[0]][path[1]]["bn"])
        y = apply(x, lin["w"])
        if "b" in lin:
            y = y + lin["b"]
        if calibrate:
            axes = tuple(range(y.ndim - 1))
            st = {"mean": jnp.mean(y, axes), "var": jnp.var(y, axes)}
            if path[0] == "tokenizer":
                stats["tokenizer"][f"bn{path[1][4:]}"] = st
            else:
                stats.setdefault(path[0], {})[path[1]] = {"bn": st}
        return (y - st["mean"]) * jax.lax.rsqrt(st["var"] + BN_EPS) * bn["scale"] + bn["bias"]

    x = None
    for i, pool in enumerate(arch.tokenizer_pools):
        src = images if i == 0 else x.reshape((-1,) + x.shape[2:])
        y = layer(("tokenizer", f"conv{i}"), src, m.conv)
        if pool:
            y = _pool(y)
        if i == 0:     # direct encoding: one analog conv, the same drive at every T
            drive = jnp.broadcast_to(y[None], (t,) + y.shape)
        else:
            drive = y.reshape((t, -1) + y.shape[1:])
        x = _lif(drive, arch.theta, arch.lam)
        seen[f"tok{i}"] = jnp.mean(x)
    t_, b, h, w, d = x.shape
    x = x.reshape(t_, b, h * w, d)

    heads = arch.num_heads
    for blk in range(arch.num_layers):
        def unit(name, a):
            y = layer((f"block{blk}", name), a,
                      lambda a, w: m.einsum("tbnc,cd->tbnd", a, w))
            out = _lif(y, arch.theta, arch.lam)
            seen[f"block{blk}.{name}"] = jnp.mean(out)
            return out

        q, k, v = (unit(u, x).reshape(t_, b, h * w, heads, d // heads)
                   for u in ("q", "k", "v"))
        scores = m.einsum("tbnhc,tbmhc->tbhnm", q, k)
        attn = m.einsum("tbhnm,tbmhc->tbnhc", scores, v)
        a = _lif(attn.reshape(t_, b, h * w, d) * arch.attn_scale, arch.theta, arch.lam)
        seen[f"block{blk}.attn"] = jnp.mean(a)
        x = x * (1.0 - unit("proj", a))
        x = x * (1.0 - unit("fc2", unit("fc1", x)))
        seen[f"block{blk}"] = jnp.mean(x)
    if calibrate:
        return stats
    feats = jnp.sum(x, axis=(0, 2)) / jnp.float32(t_ * h * w)
    head = params["head"] if folded is None else folded["head"]
    logits = m.einsum("bc,ck->bk", feats, head["w"]) + head["b"]
    if rates:
        return logits, seen
    return logits

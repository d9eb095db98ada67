"""The benchmark's FLOP and byte arithmetic against the program's own layer
layout (``engine/layout.py``) at smoke, 8-384 and 8-768 widths, and its FLOP
count against the dots and convs of the plain reference's jaxpr."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import model, spec, work

SMOKE = dict(img_size=32, num_classes=10, embed_dim=64, num_layers=2, num_heads=4,
             tokenizer_pools=[False, False, True, True])


def _cfg(name):
    cfg = spec.load_config("sif-8-384")
    if name == "smoke":
        cfg.update(SMOKE)
    else:
        cfg = spec.load_config(name)
    return cfg


@pytest.mark.parametrize("name", ["smoke", "sif-8-384", "sif-8-768"])
def test_shapes_match_the_program_layout(name):
    from repro.engine.layout import (block_layout, spike_edges, tokenizer_grid,
                                     tokenizer_layout)

    cfg = _cfg(name)
    arch = model.Arch.from_config(cfg)
    vcfg = spec.load_family("vision").vision_config(cfg)
    tcfg = vcfg.tokenizer_config()
    stages = tokenizer_layout(tcfg)
    grid = tokenizer_grid(tcfg, vcfg.img_size)
    batch = 3
    gemms = work.spike_gemms(arch, batch)
    toks = gemms[:len(stages) - 1]
    for g, st, (gh, gw) in zip(toks, stages[1:], grid[:-1]):
        assert (g.k, g.n) == (9 * st.c_in, st.c_out)
        assert g.m == batch * gh * gw            # the conv runs before the pool
    n = grid[-1][0] * grid[-1][1]
    assert work.tokens(arch) == n
    units = block_layout(vcfg)
    blocks = gemms[len(stages) - 1:]
    assert len(blocks) == vcfg.num_layers * len(units)
    for i, g in enumerate(blocks):
        u = units[i % len(units)]
        assert (g.name.split(".")[1], g.m, g.k, g.n) == (u.name, batch * n, u.d_in, u.d_out)
    edges = {e.name: e.elems for e in spike_edges(vcfg)}
    for x in work.lifs(arch, batch):
        assert x.elems == batch * edges[x.name], x.name


def _jaxpr_flops(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():       # pjit and friends nest a jaxpr
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                total += _jaxpr_flops(inner)
        out = eqn.outvars[0].aval.shape
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            total += 2 * math.prod(out) * math.prod(lhs[a] for a in lc)
        elif eqn.primitive.name == "conv_general_dilated":
            kh, kw, cin, _ = eqn.invars[1].aval.shape
            total += 2 * math.prod(out) * kh * kw * cin
    return total


@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
def test_flops_match_the_reference_jaxpr(operands):
    cfg = _cfg("smoke")
    arch = model.Arch.from_config(cfg)
    batch = 2
    params, state = jax.eval_shape(
        lambda k: model.init_weights(k, arch, cfg["weights"]), jax.random.PRNGKey(0))
    img = jax.ShapeDtypeStruct((batch, arch.img_size, arch.img_size, 3), jnp.float32)
    folded = jax.eval_shape(model.fold, params, state)
    jaxpr = jax.make_jaxpr(lambda f, x: model.forward(None, None, x, arch,
                                                      operands=operands, folded=f))(folded, img)
    assert _jaxpr_flops(jaxpr.jaxpr) == work.model_flops(arch, batch)


def test_work_per_image():
    """8-768: 17.8 GMAC per time step in the tokenizer's spike convs (3 x 2.08)
    and the blocks (8 x 1.45, SSA included), 142.6 GFLOP over T=4, plus the
    encoding conv and the head: 142.76 GFLOP per image.  8-384: 36.70."""
    big = model.Arch.from_config(spec.load_config("sif-8-768"))
    small = model.Arch.from_config(spec.load_config("sif-8-384"))
    per_t = (sum(g.flops for g in work.spike_gemms(big, 1)) + work.ssa_flops(big, 1)) / 8
    assert per_t / 1e9 == pytest.approx(17.8, abs=0.05)
    assert work.model_flops(big, 1) / 1e9 == pytest.approx(142.76, abs=0.01)
    assert work.model_flops(small, 1) / 1e9 == pytest.approx(36.70, abs=0.01)


def test_gemm_bytes_and_bounds():
    g = work.Gemm("x", m=256, k=768, n=768, t=4, in_elems=256 * 768)
    assert g.flops == 2 * 4 * 256 * 768 * 768
    assert g.bytes == 256 * 768 * 4 + 768 * 768 * 4 + 4 * 256 * 768 * 4
    arch = model.Arch.from_config(spec.load_config("sif-8-768"))
    fast = work.gemm_least_s(arch, 64, 1e30, 1.0)
    assert fast[1] == "bytes"
    assert fast[0] == pytest.approx(sum(g.bytes for g in work.spike_gemms(arch, 64)))
    slow = work.gemm_least_s(arch, 64, 1.0, 1e30)
    assert slow == (pytest.approx(sum(g.flops for g in work.spike_gemms(arch, 64))), "flops")
    lif = work.lif_least_s(arch, 1, 1.0)
    assert lif == sum(x.bytes for x in work.lifs(arch, 1))
    assert np.isfinite(lif)

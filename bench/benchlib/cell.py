"""The system under test, as the benchmark drives it.

Only this module imports the program (``repro``, from the checkout's
``src/``): it builds the configuration object, folds the benchmark's seeded
weights into a deploy plan with ``engine.compile_plan`` and returns the
jitted executor of ``engine.make_apply_fn`` that the window drives -- the
same objects ``launch/serve.serve_vision`` serves with.
"""

from __future__ import annotations

import contextlib
import sys

import jax

from benchlib import model
from benchlib.spec import ROOT

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def vision_config(cfg: dict):
    from repro.core.spikformer import SpikformerConfig

    return SpikformerConfig(
        img_size=cfg["img_size"], in_channels=cfg["in_channels"],
        num_classes=cfg["num_classes"], embed_dim=cfg["embed_dim"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        mlp_ratio=cfg["mlp_ratio"], t=cfg["t"], residual=cfg["residual"],
        theta=cfg["theta"], lam=cfg["lam"], attn_scale=cfg["attn_scale"],
        tokenizer_pools=tuple(cfg["tokenizer_pools"]))


def backend(cfg: dict, interpret: bool = False):
    """The configuration's backend with compiled kernels (``interpret=False``
    on the chip; tests on the CPU pass ``True``)."""
    from repro import engine

    be = engine.resolve_backend(cfg["backend"])
    if be.kind != "pallas":
        return be
    return engine.Backend("pallas", interpret=interpret, packed=be.packed,
                          sparse=be.sparse)


def make_weights(cfg: dict, seed: int):
    """(params, state) on the device from the seed, in one jitted call:
    seeded conv/linear weights and BatchNorm affine parameters, then the
    BatchNorm running statistics from a seeded calibration batch."""
    arch = model.Arch.from_config(cfg)
    w_cfg = cfg["weights"]

    @jax.jit
    def make(key):
        k_w, k_cal = jax.random.split(key)
        params, state = model.init_weights(k_w, arch, w_cfg)
        cal = jax.random.uniform(k_cal, (w_cfg["calibration_images"], cfg["img_size"],
                                         cfg["img_size"], cfg["in_channels"]))
        return params, model.forward(params, state, cal, arch, calibrate=True)

    return make(model.key_from_seed(seed))


def build(cfg: dict, params, state, *, interpret: bool = False):
    """(plan, jitted executor) of the program for these weights."""
    from repro import engine

    plan = engine.compile_plan(params, state, vision_config(cfg),
                               backend=backend(cfg, interpret))
    return plan, jax.jit(engine.make_apply_fn(plan))


def abstract_plan(cfg: dict):
    """(plan param shapes, plan meta) without any arrays: for compiling for a
    chip that is only described."""
    from repro import engine

    arch = model.Arch.from_config(cfg)
    holder = {}

    def fold(params, state):
        plan = engine.compile_plan(params, state, vision_config(cfg),
                                   backend=backend(cfg))
        holder["meta"] = plan.meta
        return plan.params

    p, s = jax.eval_shape(lambda k: model.init_weights(k, arch, cfg["weights"]),
                          jax.random.PRNGKey(0))
    shapes = jax.eval_shape(fold, p, s)
    return shapes, holder["meta"]


def precision_context(cfg: dict):
    """The matmul precision the configuration serves at ("default": JAX's
    own, as a user's call gets it)."""
    prec = cfg["precision"]["matmul"]
    if prec == "default":
        return contextlib.nullcontext()
    return jax.default_matmul_precision(prec)

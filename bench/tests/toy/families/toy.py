"""A toy model family, added by files alone: one dense layer ``y = x @ w``
served one batch of rows a request, checked against NumPy in float64.  It
keeps the contract of ``bench/families/__init__.py`` but ``rehearse``, which
only ``bench/rehearse.py`` calls.

The tests copy this directory elsewhere and put it on the harness's search
paths (``spec.FAMILY_DIRS``, ``spec.METRIC_DIRS``) to show that a family
needs no edit to any harness file."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import arrivals


def make_weights(cfg, seed):
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed % 2 ** 31)
    return jax.jit(lambda k: jax.random.normal(k, (cfg["width"], cfg["outputs"])))(key)


def pool(cfg, traffic, seed):
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    return rng.standard_normal((traffic["pool"], traffic["batch"], cfg["width"]),
                               dtype=np.float32)


def _step(w, x):
    return jnp.dot(x, w, precision="highest")


def build(cfg, weights, traffic, pool, *, interpret=False, fault=None):
    compiled = jax.jit(_step).lower(weights, pool[0]).compile()
    call = compiled if fault is None else fault(compiled)
    jax.block_until_ready(call(weights, jax.device_put(pool[0])))
    return (call, weights), {"step": compiled}


def serve(cfg, driven, pool, traffic, seed, window):
    call, w = driven
    rows = []
    for i, due in window.due(arrivals.due_times(traffic, seed)):
        j = i % len(pool)
        with window.request():
            out = np.asarray(call(w, jax.device_put(pool[j])))
        rows.append((j, due, time.perf_counter(), out))
    return rows


def attempted(traffic, rows):
    return len(rows) * traffic["batch"]


def end_to_end(name, ctx):
    if name == "rows_per_s":
        return len(ctx["rows"]) * ctx["batch"] / (ctx["rows"][-1][2] - ctx["start"])
    raise KeyError(name)


def context(cfg, traffic):
    return {"batch": traffic["batch"]}


def check(cfg, traffic, seed, weights, pool, rows):
    w = np.asarray(weights, dtype=np.float64)
    gap = max(float(np.max(np.abs(out - pool[j].astype(np.float64) @ w)))
              for j, _, _, out in rows)
    return {"gap_max": (gap, cfg["limits"]["gap_max"])}, {"requests": len(rows)}


def control(cfg, seed, traffic):
    w16 = make_weights(cfg, seed).astype(jnp.float16)
    return lambda _compiled: (lambda _w, x: (x.astype(jnp.float16) @ w16).astype(jnp.float32))

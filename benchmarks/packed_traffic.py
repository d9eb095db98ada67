"""Packed vs dense spike datapath: inter-layer activation bytes + wall clock.

The tentpole claim of the bit-packed deploy engine, measured two ways:

* **Analytic traffic** on the paper's Table-I configs (8-384/8-512/8-768,
  ImageNet geometry): every inter-layer spike tensor priced dense-f32 vs
  bit-packed uint32 words via ``engine.analysis.spike_traffic``.  At T=8 the
  packed datapath moves 1/8 the spike-activation bytes (1/32 at T=32) --
  the acceptance bar is >= 8x at T=8.  Priced under the packed Pallas
  backend, whose ``packed_ssa_merged_op`` kernel consumes the q/k/v words directly
  (``closes_ssa_boundary``), the SSA-boundary column EQUALS the full packed
  contract: 8x at T=8, 32x at T=32, guaranteed on every edge.  The open
  column (jnp oracle backend, operands unpacked at the attention boundary)
  is also reported.
* **Executed equivalence + wall clock** on the CPU-sized 4-192 CIFAR
  geometry: the packed plan must produce IDENTICAL logits to the dense plan
  (same backend), and we report wall time for both (on CPU/interpret the
  pack/unpack shifts cost more than the saved bytes; the byte win is the HBM
  story the analytic table captures).
"""

from __future__ import annotations

import time
from dataclasses import replace

import jax
import numpy as np

from repro import engine
from repro.core import spikformer as sf
from repro.engine import analysis

BATCH = 4

TABLE1 = (
    ("8-384", sf.SPIKFORMER_8_384),
    ("8-512", sf.SPIKFORMER_8_512),
    ("8-768", sf.SPIKFORMER_8_768),
)


def _wall(fn, *args, iters=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return np.asarray(out), (time.perf_counter() - t0) / iters


# the deploy backend that closes the SSA boundary: packed datapath + Pallas
# kernels, with the spike GEMM / packed SSA route forced on (on TPU the
# ``matmul_kernel=None`` auto resolves to the same route)
CLOSED_BACKEND = engine.Backend("pallas", matmul_kernel=True, packed=True)


def analytic_table(t: int, img_size: int = 224, backend=CLOSED_BACKEND) -> list[dict]:
    rows = []
    for name, cfg in TABLE1:
        tr = analysis.spike_traffic(replace(cfg, t=t), img_size=img_size,
                                    backend=backend)
        # the conservative open-boundary column alongside (backend=None
        # prices the q/k/v edges dense)
        tr_open = analysis.spike_traffic(replace(cfg, t=t), img_size=img_size)
        rows.append({
            "config": name, "t": t,
            "dense_bytes": tr["dense_bytes"],
            "packed_bytes": tr["packed_bytes"],
            "reduction": tr["reduction"],
            "ssa_boundary_closed": tr["ssa_boundary_closed"],
            "reduction_ssa_dense": tr["reduction_ssa_dense"],
            "reduction_ssa_open": tr_open["reduction_ssa_dense"],
        })
    return rows


def measured_small(t: int = 4) -> dict:
    cfg = sf.SpikformerConfig(
        embed_dim=192, num_layers=4, num_heads=8, t=t, img_size=32,
        num_classes=10, tokenizer_pools=(False, False, True, True))
    params, state = sf.init(jax.random.PRNGKey(0), cfg)
    img = jax.random.uniform(jax.random.PRNGKey(1), (BATCH, 32, 32, 3))

    dense_plan = engine.compile_plan(params, state, cfg, backend="jnp")
    packed_plan = engine.compile_plan(params, state, cfg, backend="jnp+packed")
    dense_out, dense_s = _wall(jax.jit(engine.make_apply_fn(dense_plan)),
                               dense_plan.params, img)
    packed_out, packed_s = _wall(jax.jit(engine.make_apply_fn(packed_plan)),
                                 packed_plan.params, img)
    np.testing.assert_array_equal(packed_out, dense_out)  # identical logits

    # traffic priced the same two ways as the analytic table, so the
    # ssa_dense / ssa_open columns mean the same thing in every row: closed
    # under the packed-SSA deploy backend, open under the jnp oracle (the
    # backend this CPU row actually measured, which unpacks q/k/v at the
    # attention op boundary)
    tr = analysis.spike_traffic(cfg, batch=BATCH, backend=CLOSED_BACKEND)
    tr_open = analysis.spike_traffic(cfg, batch=BATCH, backend="jnp+packed")
    tokens = (cfg.img_size // 4) ** 2            # two pooling stages
    return {
        "config": "4-192-cifar", "t": t, "batch": BATCH,
        "dense_wall_s": dense_s, "packed_wall_s": packed_s,
        "dense_tokens_per_s": BATCH * tokens / dense_s,
        "packed_tokens_per_s": BATCH * tokens / packed_s,
        "dense_bytes": tr["dense_bytes"],
        "packed_bytes": tr["packed_bytes"],
        "reduction": tr["reduction"],
        "ssa_boundary_closed": tr["ssa_boundary_closed"],
        "reduction_ssa_dense": tr["reduction_ssa_dense"],
        "reduction_ssa_open": tr_open["reduction_ssa_dense"],
    }


def main():
    rows8 = analytic_table(t=8)
    rows32 = analytic_table(t=32)
    rows4 = analytic_table(t=4)
    measured = measured_small(t=4)

    print("packed_traffic: inter-layer spike-activation bytes, "
          "dense f32 vs bit-packed uint32 words (per image; 'ssa closed' "
          "prices the q/k/v edges under the packed Pallas backend, whose "
          "packed_ssa_merged_op kernel consumes the words directly; 'ssa open' is "
          "the conservative jnp-oracle number, operands unpacked at the "
          "attention boundary)")
    print(f"{'config':10s} {'T':>3s} {'dense MB':>10s} {'packed MB':>10s} "
          f"{'reduction':>10s} {'ssa closed':>10s} {'ssa open':>10s}")
    for row in rows4 + rows8 + rows32:
        print(f"{row['config']:10s} {row['t']:3d} "
              f"{row['dense_bytes']/1e6:10.2f} {row['packed_bytes']/1e6:10.2f} "
              f"{row['reduction']:9.1f}x {row['reduction_ssa_dense']:9.1f}x "
              f"{row['reduction_ssa_open']:9.1f}x")
    assert all(r["reduction"] >= 8.0 for r in rows8), \
        "acceptance: >= 8x spike-activation byte reduction at T=8"
    assert all(r["reduction_ssa_dense"] == r["reduction"] for r in rows8 + rows32), \
        "acceptance: packed SSA closes the boundary -- q/k/v edges move packed"
    assert all(r["reduction"] >= 32.0 for r in rows32), \
        "closed-boundary contract: >= 32x at T=32"

    m = measured
    print(f"\nexecuted (jnp backend, {m['config']}, T={m['t']}, "
          f"batch {m['batch']}; packed logits IDENTICAL to dense):")
    print(f"  dense : {m['dense_wall_s']*1e3:8.1f} ms  "
          f"{m['dense_tokens_per_s']:10.0f} tokens/s  "
          f"{m['dense_bytes']/1e6:8.2f} MB spikes")
    print(f"  packed: {m['packed_wall_s']*1e3:8.1f} ms  "
          f"{m['packed_tokens_per_s']:10.0f} tokens/s  "
          f"{m['packed_bytes']/1e6:8.2f} MB spikes "
          f"({m['reduction']:.1f}x fewer inter-layer bytes; "
          f"{m['reduction_ssa_open']:.1f}x as measured on the jnp oracle, "
          f"which unpacks q/k/v at the attention boundary)")
    return {"table1_t8": rows8, "table1_t32": rows32, "table1_t4": rows4,
            "measured": measured}


if __name__ == "__main__":
    main()

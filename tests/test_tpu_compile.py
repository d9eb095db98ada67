"""Compile rehearsal: every main-path Pallas kernel lowers for a TPU v5e.

Interpret mode (what the rest of the suite runs) never asks Mosaic, so a
kernel can pass every bit-exactness test and still be refused by the chip's
compiler -- an unsupported cast, a block shape off the (8, 128) tiling, an
SMEM/VMEM overrun.  These tests compile each kernel for a *described*
``v5e:2x2`` topology (no chip attached) at Spike-IAND-Former 8-768 widths:
batch 8, 196 tokens, d=768, 12 heads, MLP 3072 (and the head-merged SSA at
8-384's d=384 and batch 1 as well).

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
every test file.  Keep all compile tests in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.lif_parallel import ops as lif_ops
from repro.kernels.spike_matmul import ops as mm_ops
from repro.kernels.spiking_attention import ops as ssa_ops

B, N, D, H = 8, 196, 768, 12     # 8-768 at 224x224: 14x14 tokens
M = B * N


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _case(name, t, s):
    """(fn, arg specs) of one kernel entry point at 8-768 widths, T=t."""
    u32, f32 = jnp.uint32, jnp.float32
    words = _spec(s, (M, D), u32)
    w_fc = _spec(s, (D, 4 * D), f32)
    qkv_words = tuple(_spec(s, (1, B, H, N, D // H), u32) for _ in range(3))
    drive = _spec(s, (t, B, N, D), f32)
    return {
        "spike_matmul": (
            lambda x, w: mm_ops.spike_matmul_op(x, w, interpret=False),
            (_spec(s, (t * M, D), f32), _spec(s, (D, D), f32))),
        "packed_gemm": (
            lambda x, w: mm_ops.packed_spike_matmul_op(
                x, w, t=t, interpret=False),
            (words, w_fc)),
        "sparse_packed_gemm": (
            lambda x, w, occ: mm_ops.sparse_packed_spike_matmul_op(
                x, w, t=t, occ=occ, interpret=False),
            (words, w_fc, _spec(s, (M, D // 128), u32))),
        "ssa": (
            lambda q, k, v: ssa_ops.ssa_op(q, k, v, interpret=False),
            tuple(_spec(s, (t, B, H, N, D // H), f32) for _ in range(3))),
        "packed_ssa": (
            lambda q, k, v: ssa_ops.packed_ssa_op(
                q, k, v, t=t, interpret=False),
            qkv_words),
        "sparse_packed_ssa": (
            lambda q, k, v: ssa_ops.sparse_packed_ssa_op(
                q, k, v, t=t, interpret=False),
            qkv_words),
        "packed_ssa_merged": (
            lambda q, k, v: ssa_ops.packed_ssa_merged_op(
                q, k, v, t=t, heads=H, interpret=False),
            tuple(_spec(s, (1, B, N, D), u32) for _ in range(3))),
        "packed_ssa_merged_8_384_b1": (
            lambda q, k, v: ssa_ops.packed_ssa_merged_op(
                q, k, v, t=t, heads=H, interpret=False),
            tuple(_spec(s, (1, 1, N, D // 2), u32) for _ in range(3))),
        "lif": (
            lambda x: lif_ops.lif_parallel_op(x, interpret=False), (drive,)),
        "lif_pack": (
            lambda x: lif_ops.lif_pack_op(x, interpret=False, occupancy=True),
            (drive,)),
        "lif_iand_pack": (
            lambda x, skip: lif_ops.lif_iand_pack_op(x, skip, interpret=False),
            (drive, _spec(s, (1, B, N, D), u32))),
    }[name]


@pytest.mark.parametrize("name,t", [
    ("spike_matmul", 4),
    ("packed_gemm", 4), ("packed_gemm", 32),
    ("sparse_packed_gemm", 4), ("sparse_packed_gemm", 32),
    ("ssa", 4),
    ("packed_ssa", 4), ("packed_ssa", 32),
    ("sparse_packed_ssa", 4), ("sparse_packed_ssa", 32),
    ("packed_ssa_merged", 4), ("packed_ssa_merged", 32),
    ("packed_ssa_merged_8_384_b1", 4),
    ("lif", 4),
    ("lif_pack", 4), ("lif_iand_pack", 4),
])
def test_kernel_compiles_for_v5e(one_chip, name, t):
    fn, args = _case(name, t, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_executor_layers_named_for_v5e(one_chip):
    """The whole ``pallas+packed`` executor of Spike-IAND-Former 8-384 at
    batch 1 (the edge cell's) compiles for the described chip with every
    instruction in a layer of the plan: each of the 119 Pallas kernels has
    its named scope, each block's SSA scope holds its one SSA kernel and each
    unit scope its spike GEMM.  The table is the benchmark's own
    (``bench/benchlib/scopes.py``), which maps a device trace onto layers."""
    import fnmatch
    import re
    import sys
    from pathlib import Path

    bench = Path(__file__).resolve().parents[1] / "bench"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    from benchlib import cell, scopes, spec

    from repro import engine
    from repro.engine.plan import DeployPlan

    cfg = spec.load_config("sif-8-384")
    shapes, meta = cell.abstract_plan(cfg)
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype), shapes)
    img = _spec(one_chip, (1, cfg["img_size"], cfg["img_size"], cfg["in_channels"]),
                jnp.float32)
    fn = jax.jit(engine.make_apply_fn(DeployPlan(meta=meta, params=shapes)))
    hlo = fn.lower(params, img).compile().as_text()
    table = scopes.op_scopes(hlo)

    units = ("q", "k", "v", "ssa", "attn_lif", "proj", "fc1", "fc2")
    layout = ({f"tokenizer/stage{i}" for i in range(4)} | {"head"}
              | {f"block{b}/{u}" for b in range(cfg["num_layers"]) for u in units})
    assert {scope for scope, _ in table.values()} - {None} == layout
    kernels = {name: scope for name, (scope, is_kernel) in table.items() if is_kernel}
    assert len(kernels) == hlo.count("tpu_custom_call") == 119
    assert all(kernels.values())
    for b in range(cfg["num_layers"]):
        ssa = [n for n, s in kernels.items() if s == f"block{b}/ssa"]
        assert len(ssa) == 1 and fnmatch.fnmatchcase(ssa[0], "*ssa*"), ssa
        for u in ("q", "k", "v", "proj", "fc1", "fc2"):
            assert any(fnmatch.fnmatchcase(n, "*spike_matmul*")
                       for n, s in kernels.items() if s == f"block{b}/{u}"), (b, u)
    exempt = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
    opcodes = dict(re.findall(
        r"(?m)^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(", hlo))
    loose = [n for n, (s, _) in table.items() if s is None and opcodes.get(n) not in exempt]
    assert not loose, loose[:20]

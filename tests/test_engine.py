"""Deploy-engine equivalence suite: the folded/fused plan vs the train graph.

Covers the ISSUE-1 acceptance criteria:
  * fold_linear_bn / fold_conv_bn folding accuracy (atol ~1e-5),
  * bit-exact IAND fusion in the LIF epilogue (both backends),
  * end-to-end logits equivalence train-graph vs deploy plan across
    residual x chain_len x backend and the three Table-I configs,
  * the deploy jaxpr contains zero BatchNorm ops and the standalone IAND
    connective is never invoked (the residual runs only in the fused
    epilogue).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import nn as cnn
from repro.core import spikformer as sf
from repro.core.lif import lif
from repro.engine import analysis

KEY = jax.random.PRNGKey(0)


def _perturb_bn(tree, seed=0):
    """Give BatchNorm non-trivial running stats / affine params so folding is
    actually exercised (fresh init is mean=0, var=1, scale=1, bias=0 -- the
    fold would be a near-no-op)."""
    rng = np.random.default_rng(seed)

    def visit(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else None
        a = np.asarray(leaf)
        if name == "mean":
            return jnp.asarray(a + rng.normal(0, 0.2, a.shape).astype(a.dtype))
        if name == "var":
            return jnp.asarray(a * rng.uniform(0.5, 1.5, a.shape).astype(a.dtype))
        if name == "scale":
            return jnp.asarray(a * rng.uniform(0.7, 1.3, a.shape).astype(a.dtype))
        if name == "bias":
            return jnp.asarray(a + rng.normal(0, 0.2, a.shape).astype(a.dtype))
        return leaf

    return jax.tree_util.tree_map_with_path(visit, tree)


def _tiny(**kw):
    return sf.SpikformerConfig(embed_dim=64, num_layers=2, num_heads=4, t=4, **kw)


@pytest.fixture(scope="module")
def tiny_trained():
    """Tiny model with perturbed BN stats (a 'trained' stand-in)."""
    cfg = _tiny()
    params, state = sf.init(KEY, cfg)
    params = _perturb_bn(params, seed=1)
    state = _perturb_bn(state, seed=2)
    img = jax.random.uniform(jax.random.PRNGKey(3), (2, 32, 32, 3))
    return params, state, img


# -- folding ------------------------------------------------------------------

def test_fold_linear_bn_matches_bn_eval():
    k1, k2 = jax.random.split(KEY)
    lin = cnn.linear_init(k1, 48, 96)
    bn_p, bn_s = cnn.bn_init(96)
    bn_p = _perturb_bn(bn_p, seed=4)
    bn_s = _perturb_bn(bn_s, seed=5)
    x = jax.random.normal(k2, (32, 48))
    want, _ = cnn.bn_apply(bn_p, bn_s, cnn.linear_apply(lin, x), train=False)
    got = cnn.linear_apply(cnn.fold_linear_bn(lin, bn_p, bn_s), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_fold_conv_bn_matches_bn_eval():
    k1, k2 = jax.random.split(KEY)
    conv = cnn.conv_init(k1, 8, 16, 3)
    bn_p, bn_s = cnn.bn_init(16)
    bn_p = _perturb_bn(bn_p, seed=6)
    bn_s = _perturb_bn(bn_s, seed=7)
    x = jax.random.normal(k2, (2, 8, 8, 8))
    want, _ = cnn.bn_apply(bn_p, bn_s, cnn.conv_apply(conv, x), train=False)
    got = cnn.conv_apply(cnn.fold_conv_bn(conv, bn_p, bn_s), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# -- fused IAND epilogue ------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True])
def test_lif_iand_fusion_bit_exact(use_kernel):
    """skip*(1-LIF(drive)) fused into the dispatch == standalone connective."""
    drive = jax.random.normal(KEY, (4, 256))
    skip = (jax.random.uniform(jax.random.PRNGKey(1), (4, 256)) > 0.5).astype(jnp.float32)
    fused = lif(drive, use_kernel=use_kernel, iand_skip=skip)
    standalone = skip * (1.0 - lif(drive, use_kernel=use_kernel))
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(standalone))
    assert bool(jnp.all((fused == 0) | (fused == 1)))


# -- end-to-end equivalence ---------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("chain_len", [1, 2, 4])
@pytest.mark.parametrize("residual", ["iand", "add"])
def test_engine_matches_train_graph(tiny_trained, residual, chain_len, backend):
    params, state, img = tiny_trained
    cfg = _tiny(residual=residual, chain_len=chain_len)
    want, _ = sf.apply(params, state, img, cfg, train=False)
    plan = engine.compile_plan(params, state, cfg, backend=backend)
    got = engine.apply(plan, img)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_engine_serial_schedule_and_jit(tiny_trained):
    params, state, img = tiny_trained
    cfg = _tiny(lif_schedule="serial")
    want, _ = sf.apply(params, state, img, cfg, train=False)
    plan = engine.compile_plan(params, state, cfg)
    fn = jax.jit(engine.make_apply_fn(plan))
    np.testing.assert_allclose(
        np.asarray(fn(plan.params, img)), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("cfg", [
    sf.SPIKFORMER_8_384, sf.SPIKFORMER_8_512, sf.SPIKFORMER_8_768,
], ids=["8-384", "8-512", "8-768"])
def test_engine_table1_configs(cfg):
    """Acceptance: logits equivalence on the Table-I configs, with the IAND
    residual executing only through the fused Pallas kernel epilogue."""
    params, state = sf.init(KEY, cfg)
    params = _perturb_bn(params, seed=8)
    state = _perturb_bn(state, seed=9)
    img = jax.random.uniform(jax.random.PRNGKey(10), (1, 32, 32, 3))
    want, _ = sf.apply(params, state, img, cfg, train=False)
    plan = engine.compile_plan(params, state, cfg, backend="pallas")
    got = engine.apply(plan, img)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


# -- packed spike datapath ----------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp+packed", "pallas+packed"])
def test_engine_packed_matches_dense_plan(tiny_trained, backend):
    """The packed plan is bit-exact vs the unpacked plan: identical logits."""
    params, state, img = tiny_trained
    cfg = _tiny()
    dense = engine.apply(engine.compile_plan(params, state, cfg), img)
    packed = engine.apply(
        engine.compile_plan(params, state, cfg, backend=backend), img)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(dense))


def test_engine_packed_gemm_kernel_route(tiny_trained):
    """Packed words fed straight to the packed spike-GEMM kernel (forced on,
    interpret mode) still reproduce the dense plan's logits."""
    params, state, img = tiny_trained
    cfg = _tiny()
    dense = engine.apply(engine.compile_plan(params, state, cfg), img)
    be = engine.Backend("pallas", matmul_kernel=True, packed=True)
    packed = engine.apply(
        engine.compile_plan(params, state, cfg, backend=be), img)
    np.testing.assert_allclose(np.asarray(packed), np.asarray(dense), atol=1e-4)


@pytest.mark.parametrize("cfg", [
    sf.SPIKFORMER_8_384, sf.SPIKFORMER_8_512, sf.SPIKFORMER_8_768,
], ids=["8-384", "8-512", "8-768"])
def test_engine_packed_table1_configs(cfg):
    """Acceptance: packed deploy plan bit-exact vs the unpacked plan
    (identical logits) on the Table-I configs."""
    params, state = sf.init(KEY, cfg)
    params = _perturb_bn(params, seed=8)
    state = _perturb_bn(state, seed=9)
    img = jax.random.uniform(jax.random.PRNGKey(10), (1, 32, 32, 3))
    dense = engine.apply(
        engine.compile_plan(params, state, cfg, backend="pallas"), img)
    packed = engine.apply(
        engine.compile_plan(params, state, cfg, backend="pallas+packed"), img)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(dense))


def test_engine_packed_jit(tiny_trained):
    params, state, img = tiny_trained
    plan = engine.compile_plan(params, state, _tiny(), backend="jnp+packed")
    fn = jax.jit(engine.make_apply_fn(plan))
    dense = engine.apply(engine.compile_plan(params, state, _tiny()), img)
    np.testing.assert_array_equal(np.asarray(fn(plan.params, img)),
                                  np.asarray(dense))


def test_engine_packed_rejects_add_residual(tiny_trained):
    params, state, _ = tiny_trained
    with pytest.raises(ValueError, match="residual"):
        engine.compile_plan(params, state, _tiny(residual="add"),
                            backend="jnp+packed")


def test_spike_traffic_accounting(tiny_trained):
    """T=8 moves 8x fewer inter-layer spike bytes; edge walk covers every
    tokenizer stage and block unit."""
    from repro.engine import analysis

    cfg = _tiny()
    tr8 = analysis.spike_traffic(
        sf.SpikformerConfig(embed_dim=64, num_layers=2, num_heads=4, t=8))
    assert tr8["reduction"] == 8.0
    tr4 = analysis.spike_traffic(cfg)
    assert tr4["reduction"] == 4.0
    names = [e["name"] for e in tr4["edges"]]
    assert "tok0" in names and "block1.attn" in names and "block0.fc2" in names
    # q/k/v are SSA-boundary edges: the conservative number prices them dense
    assert all(e["ssa_boundary"] == (e["name"].split(".")[-1] in "qkv")
               for e in tr4["edges"] if e["name"].startswith("block"))
    assert tr4["packed_bytes"] < tr4["packed_bytes_ssa_dense"] < tr4["dense_bytes"]
    assert tr4["reduction_ssa_dense"] < tr4["reduction"]
    # doubling the batch doubles both sides, not the ratio
    tr4b = analysis.spike_traffic(cfg, batch=2)
    assert tr4b["dense_bytes"] == 2 * tr4["dense_bytes"]
    assert tr4b["reduction"] == tr4["reduction"]


# -- structural properties ----------------------------------------------------

def test_no_bn_op_in_deploy_jaxpr(tiny_trained):
    """Folded inference never materialises a BatchNorm op; the train graph
    does (rsqrt is BN's signature primitive in this model)."""
    params, state, img = tiny_trained
    cfg = _tiny()
    plan = engine.compile_plan(params, state, cfg)
    assert analysis.bn_op_count(engine.make_apply_fn(plan), plan.params, img) == 0
    naive = lambda p, s, im: sf.apply(p, s, im, cfg, train=False)[0]
    assert analysis.bn_op_count(naive, params, state, img) > 0


def test_standalone_iand_never_called_in_deploy(tiny_trained, monkeypatch):
    """The AND-NOT residual executes only inside the LIF dispatch epilogue."""
    import importlib

    iand_mod = importlib.import_module("repro.core.iand")

    def boom(x, y):
        raise AssertionError("standalone IAND connective invoked in deploy path")

    monkeypatch.setattr(iand_mod, "iand", boom)
    params, state, img = tiny_trained
    plan = engine.compile_plan(params, state, _tiny(residual="iand"))
    logits = engine.apply(plan, img)
    assert logits.shape == (2, 10)


def test_plan_stats(tiny_trained):
    params, state, img = tiny_trained
    cfg = _tiny()
    stats = engine.plan_stats(engine.compile_plan(params, state, cfg))
    assert stats["bn_ops"] == 0
    assert stats["standalone_iand_ops"] == 0
    assert stats["fused_lif_iand_dispatches"] == 2 * cfg.num_layers
    assert stats["folded_linear_bn"] == 6 * cfg.num_layers
    assert stats["folded_conv_bn"] == 4
    add_stats = engine.plan_stats(
        engine.compile_plan(params, state, _tiny(residual="add")))
    assert add_stats["fused_lif_iand_dispatches"] == 0
    assert add_stats["standalone_add_ops"] == 2 * cfg.num_layers


# -- layer names in the compiled executor -------------------------------------

def _hlo_scopes(hlo_text):
    """The named-scope paths of a compiled module's ``op_name`` metadata,
    without the ``jit(...)`` wrappers and the primitive at each path's end."""
    import re

    out = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo_text):
        parts = [p for p in name.split("/") if "(" not in p]
        if name.startswith("jit(") and len(parts) > 1:
            out.add("/".join(parts[:-1]))
    return out


@pytest.mark.parametrize("backend", ["jnp", "jnp+packed"])
def test_executor_names_every_layer(tiny_trained, backend):
    """Each tokenizer stage, block unit (with the SSA and attention LIF) and
    the head runs under its named scope, in both vision walkers, taken from
    the plan's own layout."""
    params, state, img = tiny_trained
    plan = engine.compile_plan(params, state, _tiny(), backend=backend)
    meta = plan.meta
    assert len(meta.tok_stages) == 4
    units = [u.name for u in meta.block_units]
    assert units == ["q", "k", "v", "proj", "fc1", "fc2"]
    want = ({f"tokenizer/stage{i}" for i in range(4)} | {"head"}
            | {f"block{b}/{u}" for b in range(meta.num_layers)
               for u in units + ["ssa", "attn_lif"]})
    hlo = jax.jit(engine.make_apply_fn(plan)).lower(plan.params, img).compile().as_text()
    got = _hlo_scopes(hlo)
    assert want <= got
    assert {s.split("/")[0] for s in got} == {"tokenizer", "head"} | {
        f"block{b}" for b in range(meta.num_layers)}


def test_compile_plan_reports_its_duration(tiny_trained):
    """One ``/repro/engine/compile_plan`` duration event per fold, whatever
    the family or backend."""
    from repro.engine.plan import FOLD_EVENT

    params, state, _ = tiny_trained
    seen = []

    def listen(event, secs, **_):
        if event == FOLD_EVENT:
            seen.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        engine.compile_plan(params, state, _tiny())
        assert len(seen) == 1
        engine.compile_plan(params, state, _tiny(), backend="jnp+packed")
        assert len(seen) == 2
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert all(s > 0 for s in seen)
    assert FOLD_EVENT == "/repro/engine/compile_plan"


def test_backend_resolution():
    assert engine.resolve_backend(None) == engine.JNP
    assert engine.resolve_backend(True) == engine.PALLAS
    assert engine.resolve_backend(False) == engine.JNP
    assert engine.resolve_backend("pallas").kind == "pallas"
    assert engine.resolve_backend(engine.PALLAS) is engine.PALLAS
    with pytest.raises(ValueError):
        engine.resolve_backend("cuda")


def test_backend_resolution_edge_cases():
    """Satellite coverage: legacy bools, packed suffixes, bad kinds/flags/types."""
    assert engine.resolve_backend("jnp+packed") == engine.JNP_PACKED
    assert engine.resolve_backend("pallas+packed") == engine.PALLAS_PACKED
    assert engine.resolve_backend("pallas+packed").packed
    assert not engine.resolve_backend("pallas").packed
    assert not engine.resolve_backend(True).packed        # legacy bool: dense
    with pytest.raises(ValueError):
        engine.resolve_backend("pallas+quantized")        # unknown flag
    with pytest.raises(ValueError):
        engine.resolve_backend("pallas+")                 # dangling separator
    with pytest.raises(ValueError):
        engine.resolve_backend("+packed")                 # empty kind
    with pytest.raises(ValueError):
        engine.resolve_backend("cuda+packed")             # bad kind, good flag
    with pytest.raises(TypeError):
        engine.resolve_backend(3.14)
    with pytest.raises(TypeError):
        engine.resolve_backend(["pallas"])


def test_vision_serve_path():
    from repro.launch.serve import serve_vision

    done = serve_vision("spike-iand-former_smoke", num_requests=4, slots=2,
                        verbose=False)
    assert len(done) == 4
    assert all(0 <= c < 10 for _, c in done)

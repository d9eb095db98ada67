"""SSA backend-dispatch suite (ISSUE 3): attention rides the plan's kernels.

Covers the dispatch-gap fix and the packed-operand SSA kernel:
  * ``packed_ssa_op`` bit-exact vs the dense oracle for T in {1, 8, 32, 40}
    (multi-word trains) and at a ragged token count,
  * ``ssa_op`` at a ragged N (65): the query block is padded to sublane
    alignment instead of launching unaligned,
  * ``packed_ssa_merged_op`` (token-major words, heads as lane slices) bit-exact
    vs the head-split kernel in its split/merge and vs the oracle, and the
    packed vision walker's route events (``head_merged`` / ``head_split``),
  * engine plans route attention through ``backend.ssa_apply`` /
    ``ssa_apply_packed`` (regression: the executor used to call the jnp
    einsum directly, leaving the Pallas kernel dead code),
  * plan logits agree across jnp / pallas / +packed backends on the Table-I
    head shapes and for both ``attn_ordering`` values,
  * under ``Backend.closes_ssa_boundary`` nothing in the deploy path ever
    unpacks a spike train (tokenizer-to-head packed),
  * traffic accounting flips the conservative SSA-dense column exactly when
    the backend closes the boundary,
  * text ``serve()`` regression: output matches a full-forward greedy
    reference after dropping the dead prefill compile.
"""

import collections
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import packing
from repro.core import spikformer as sf
from repro.engine import analysis
from repro.core.spiking_attention import (
    merge_heads, split_heads, split_heads_packed)
from repro.kernels.spiking_attention.ops import (
    packed_ssa_merged_op, packed_ssa_op, ssa_op)
from repro.kernels.spiking_attention.ref import ssa_ref

KEY = jax.random.PRNGKey(0)

# forced-on kernel routes (the ``None`` auto keeps kernels off in interpret
# mode off-TPU, which would route everything to the oracle and test nothing)
PALLAS_KERNEL = engine.Backend("pallas", matmul_kernel=True)
PALLAS_PACKED_KERNEL = engine.Backend("pallas", matmul_kernel=True, packed=True)


def _spikes(key, shape):
    return (jax.random.uniform(key, shape) > 0.5).astype(jnp.float32)


def _fold(x):
    t, b, h, n, dh = x.shape
    return x.reshape(t * b * h, n, dh)


def _tiny(**kw):
    return sf.SpikformerConfig(embed_dim=64, num_layers=2, num_heads=4, t=4, **kw)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = _tiny()
    params, state = sf.init(KEY, cfg)
    img = jax.random.uniform(jax.random.PRNGKey(3), (2, 32, 32, 3))
    return params, state, img


# -- packed SSA kernel vs dense oracle ---------------------------------------

@pytest.mark.parametrize("t", [1, 8, 32, 40], ids=lambda t: f"T{t}")
def test_packed_ssa_op_bit_exact(t):
    """Word-operand SSA == dense oracle, bit-for-bit, including multi-word
    trains (T=40 -> 2 words) -- binary operands make SSA exact integer
    arithmetic, so there is no tolerance to hide behind."""
    b, h, n, dh = 2, 3, 64, 48
    q, k, v = (_spikes(kk, (t, b, h, n, dh)) for kk in jax.random.split(KEY, 3))
    qw, kw, vw = (packing.pack(x).words for x in (q, k, v))
    got = packed_ssa_op(qw, kw, vw, t=t)
    want = ssa_ref(_fold(q), _fold(k), _fold(v)).reshape(t, b, h, n, dh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [65, 196], ids=["N65", "N196"])
def test_ssa_op_ragged_token_count(n):
    """Regression: N not a multiple of 8 used to launch an unaligned query
    block; the token axis is now padded to sublane alignment and sliced."""
    t, b, h, dh = 2, 1, 2, 24
    q, k, v = (_spikes(kk, (t, b, h, n, dh)) for kk in jax.random.split(KEY, 3))
    got = ssa_op(q, k, v)
    want = ssa_ref(_fold(q), _fold(k), _fold(v)).reshape(t, b, h, n, dh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_packed_ssa_op_ragged_token_count():
    t, b, h, n, dh = 8, 1, 2, 65, 24
    q, k, v = (_spikes(kk, (t, b, h, n, dh)) for kk in jax.random.split(KEY, 3))
    got = packed_ssa_op(*(packing.pack(x).words for x in (q, k, v)), t=t)
    want = ssa_ref(_fold(q), _fold(k), _fold(v)).reshape(t, b, h, n, dh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- head-merged packed SSA kernel (the packed vision walker's) --------------

MERGED_WIDTHS = [(768, 12), (384, 12), (512, 4), (192, 3)]     # (D, H)
MERGED_CASES = (  # (D, H, N, B, T): every width at T=4 on each N and B
    [(d, h, n, b, 4) for d, h in MERGED_WIDTHS for n in (196, 7) for b in (1, 3)]
    + [(d, h, 196, 1 + 2 * (i % 2), 1) for i, (d, h) in enumerate(MERGED_WIDTHS)]
    + [(d, h, 7, 3 - 2 * (i % 2), 33) for i, (d, h) in enumerate(MERGED_WIDTHS)])


@pytest.mark.parametrize("d,h,n,b,t", MERGED_CASES)
def test_packed_ssa_merged_op_bit_exact(d, h, n, b, t):
    """Token-major words in, (T, B, N, D) drive out: bit-identical to the
    head-split kernel wrapped in the split/merge it replaces and to the dense
    oracle, on the cells' widths (two and four heads per 128-lane group), one
    head per group (Dh 128) and a tensor-parallel local width whose group is
    the whole of D (192 = 3 heads of 64), with multi-word trains (T=33)."""
    dh = d // h
    key = jax.random.PRNGKey(d + 10 * n + 100 * b + t)
    ks = jax.random.split(key, 4)
    density = jax.random.uniform(ks[0], (), minval=0.1, maxval=0.6)
    q, k, v = ((jax.random.uniform(kk, (t, b, n, d)) < density)
               .astype(jnp.float32) for kk in ks[1:])
    qp, kp, vp = (packing.pack(x) for x in (q, k, v))
    got = packed_ssa_merged_op(qp.words, kp.words, vp.words, t=t, heads=h)
    assert got.shape == (t, b, n, d) and got.dtype == jnp.float32
    split = merge_heads(packed_ssa_op(
        *(split_heads_packed(x, h).words for x in (qp, kp, vp)), t=t))
    fold = lambda x: split_heads(x, h).reshape(t * b * h, n, dh)
    oracle = merge_heads(
        ssa_ref(fold(q), fold(k), fold(v)).reshape(t, b, h, n, dh))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(split))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))


@pytest.mark.parametrize("d,dh,g", [(768, 64, 128), (384, 32, 128),
                                    (512, 128, 128), (192, 64, 192),
                                    (768, 96, 384), (64, 16, 64), (96, 32, 96)])
def test_head_group_holds_whole_heads_in_a_legal_block(d, dh, g):
    from repro.kernels.spiking_attention.kernel import head_group

    assert head_group(d, dh) == g


# -- backend ssa_apply routing ------------------------------------------------

TABLE1_HEAD_SHAPES = [  # (H, Dh) of the Table-I configs, N = 64 tokens
    pytest.param(12, 32, id="8-384"),
    pytest.param(8, 64, id="8-512"),
    pytest.param(12, 64, id="8-768"),
]


@pytest.mark.parametrize("h,dh", TABLE1_HEAD_SHAPES)
def test_ssa_apply_identical_across_backends(h, dh):
    """jnp oracle, Pallas kernel, and packed-operand kernel produce identical
    drives on the Table-I head shapes (T=8, N=64)."""
    t, b, n = 8, 2, 64
    q, k, v = (_spikes(kk, (t, b, h, n, dh)) for kk in jax.random.split(KEY, 3))
    want = engine.ssa_apply(engine.JNP, q, k, v, scale=0.125)
    kern = engine.ssa_apply(PALLAS_KERNEL, q, k, v, scale=0.125)
    qp, kp, vp = (packing.pack(x) for x in (q, k, v))
    packed = engine.ssa_apply_packed(
        PALLAS_PACKED_KERNEL, qp, kp, vp, scale=0.125)
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(want))


def test_engine_routes_attention_through_ssa_kernel(tiny_model, monkeypatch):
    """Regression for the dispatch gap: a pallas plan with the kernel route
    on must actually invoke ``ssa_op`` (it used to call the jnp einsum
    directly, leaving the kernel dead code outside tests/benches)."""
    import repro.kernels.spiking_attention.ops as aops

    params, state, img = tiny_model
    cfg = _tiny()
    calls = {"n": 0}
    orig = aops.ssa_op

    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(aops, "ssa_op", counting)
    plan = engine.compile_plan(params, state, cfg, backend=PALLAS_KERNEL)
    engine.apply(plan, img)
    assert calls["n"] == cfg.num_layers  # one SSA per block

    calls["n"] = 0
    engine.apply(engine.compile_plan(params, state, cfg), img)  # jnp oracle
    assert calls["n"] == 0


def _count_calls(monkeypatch, *names):
    """Count calls of the named spiking_attention ops (at trace time under
    jit), still running the real op."""
    import repro.kernels.spiking_attention.ops as aops

    calls = dict.fromkeys(names, 0)
    for name in names:
        def counting(*a, _name=name, _orig=getattr(aops, name), **kw):
            calls[_name] += 1
            return _orig(*a, **kw)

        monkeypatch.setattr(aops, name, counting)
    return calls


@contextlib.contextmanager
def _ssa_route_events():
    """The SSA route events recorded inside the block, by route."""
    seen = collections.Counter()
    routes = {engine.backend.SSA_ROUTE_MERGED: "head_merged",
              engine.backend.SSA_ROUTE_SPLIT: "head_split"}

    def listen(event, **_):
        if event in routes:
            seen[routes[event]] += 1

    jax.monitoring.register_event_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_listener(listen)


def test_engine_routes_packed_attention_through_packed_kernel(tiny_model, monkeypatch):
    """The packed vision walker hands token-major words to the head-merged
    kernel, once per block; the head-split kernel is not called."""
    params, state, img = tiny_model
    cfg = _tiny()
    calls = _count_calls(monkeypatch, "packed_ssa_op", "packed_ssa_merged_op")
    plan = engine.compile_plan(params, state, cfg, backend=PALLAS_PACKED_KERNEL)
    with _ssa_route_events() as seen:
        engine.apply(plan, img)
    assert calls == {"packed_ssa_op": 0, "packed_ssa_merged_op": cfg.num_layers}
    assert seen == {"head_merged": cfg.num_layers}


@pytest.mark.parametrize("backend,route", [
    (PALLAS_PACKED_KERNEL, "head_merged"),
    ("jnp+packed", "head_split"),
    (engine.Backend("pallas", matmul_kernel=True, packed=True, sparse=True),
     "head_split"),
], ids=["pallas+packed", "jnp+packed", "pallas+packed+sparse"])
def test_packed_vision_plan_ssa_route(backend, route, monkeypatch):
    """Spike-IAND-Former 8-384 (Table I widths, CIFAR geometry), traced and
    not run: on the compiled-Pallas route every block goes through the
    head-merged kernel and records one ``head_merged`` event; the oracle and
    sparse routes split heads around their own SSA and record ``head_split``."""
    cfg = sf.SPIKFORMER_8_384
    params, state = sf.init(KEY, cfg)
    img = jax.ShapeDtypeStruct((1, cfg.img_size, cfg.img_size, 3), jnp.float32)
    calls = _count_calls(monkeypatch, "packed_ssa_op", "packed_ssa_merged_op",
                         "sparse_packed_ssa_op")
    plan = engine.compile_plan(params, state, cfg, backend=backend)
    with _ssa_route_events() as seen:
        jax.jit(engine.make_apply_fn(plan)).lower(plan.params, img)
    assert seen == {route: cfg.num_layers}
    merged = cfg.num_layers if route == "head_merged" else 0
    assert calls["packed_ssa_merged_op"] == merged
    assert calls["packed_ssa_op"] == 0
    assert calls["sparse_packed_ssa_op"] == (
        cfg.num_layers if engine.resolve_backend(backend).sparse else 0)


def test_lm_packed_plan_keeps_head_split_kernel(monkeypatch):
    """The spiking LM's causal SSA keeps the head-split ``packed_ssa_op``
    and records no vision route event."""
    from repro.models import spiking_lm as slm
    from repro.models.lm import get_config

    cfg = get_config("llama3.2-1b_smoke").replace(
        spiking=True, spike_t=4, num_heads=4, head_dim=None)
    params = slm.init_spiking_lm(KEY, cfg)
    tokens = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    calls = _count_calls(monkeypatch, "packed_ssa_op", "packed_ssa_merged_op")
    plan = engine.compile_plan(params, None, cfg, backend=PALLAS_PACKED_KERNEL)
    with _ssa_route_events() as seen:
        jax.jit(engine.make_apply_fn(plan)).lower(plan.params, tokens)
    assert calls == {"packed_ssa_op": cfg.num_layers, "packed_ssa_merged_op": 0}
    assert not seen


def test_packed_plan_never_unpacks_under_closed_boundary(tiny_model, monkeypatch):
    """Acceptance: with the packed SSA kernel closing the last dense hop,
    NOTHING in the deploy path unpacks a spike train -- spikes stay packed
    tokenizer-to-head (the head rate-decodes by popcount)."""
    params, state, img = tiny_model
    cfg = _tiny()
    dense = engine.apply(engine.compile_plan(params, state, cfg), img)

    def boom(*a, **kw):
        raise AssertionError("packing.unpack called in the closed-boundary path")

    monkeypatch.setattr(packing, "unpack", boom)
    plan = engine.compile_plan(params, state, cfg, backend=PALLAS_PACKED_KERNEL)
    got = engine.apply(plan, img)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense), atol=1e-4)


# -- engine-level equivalence across backends and orderings -------------------

@pytest.mark.parametrize("ordering", ["quadratic", "linear"])
def test_engine_ssa_equivalence_across_backends(tiny_model, ordering):
    """Plan logits agree across jnp / pallas(kernel) / +packed for both
    attention orderings; the packed plan is bit-identical to its dense
    counterpart on the same route."""
    params, state, img = tiny_model
    cfg = _tiny(attn_ordering=ordering)
    base = engine.apply(engine.compile_plan(params, state, cfg), img)
    jnp_packed = engine.apply(
        engine.compile_plan(params, state, cfg, backend="jnp+packed"), img)
    np.testing.assert_array_equal(np.asarray(jnp_packed), np.asarray(base))
    kern = engine.apply(
        engine.compile_plan(params, state, cfg, backend=PALLAS_KERNEL), img)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(base), atol=1e-4)
    kern_packed = engine.apply(
        engine.compile_plan(params, state, cfg, backend=PALLAS_PACKED_KERNEL),
        img)
    np.testing.assert_allclose(np.asarray(kern_packed), np.asarray(base),
                               atol=1e-4)


def test_train_graph_use_kernel_routes_ssa(tiny_model, monkeypatch):
    """The legacy ``use_kernel`` flag now also selects the SSA kernel in the
    training graph, with logits unchanged (the kernel's custom VJP keeps the
    oracle backward)."""
    import repro.kernels.spiking_attention.ops as aops

    params, state, img = tiny_model
    calls = {"n": 0}
    orig = aops.ssa_op

    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(aops, "ssa_op", counting)
    cfg = _tiny(use_kernel=True)
    want, _ = sf.apply(params, state, img, _tiny(), train=False)
    got, _ = sf.apply(params, state, img, cfg, train=False)
    assert calls["n"] == cfg.num_layers
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


# -- traffic accounting -------------------------------------------------------

def test_spike_traffic_boundary_flip():
    """The conservative SSA-dense column collapses onto the packed contract
    exactly when the backend closes the boundary."""
    cfg = sf.SpikformerConfig(embed_dim=64, num_layers=2, num_heads=4, t=8)
    open_tr = analysis.spike_traffic(cfg)
    assert not open_tr["ssa_boundary_closed"]
    assert open_tr["reduction_ssa_dense"] < open_tr["reduction"] == 8.0

    closed = analysis.spike_traffic(cfg, backend=PALLAS_PACKED_KERNEL)
    assert closed["ssa_boundary_closed"]
    assert closed["packed_bytes_ssa_dense"] == closed["packed_bytes"]
    assert closed["reduction_ssa_dense"] == closed["reduction"] == 8.0

    # backends that unpack at the attention op boundary stay conservative
    for be in ("jnp+packed", engine.PALLAS):
        tr = analysis.spike_traffic(cfg, backend=be)
        assert not tr["ssa_boundary_closed"]
        assert tr["reduction_ssa_dense"] == open_tr["reduction_ssa_dense"]

    # the linear ordering rides its own packed route (ssa_linear_packed
    # shifts bitplanes out in-register): boundary closed here too
    lin = analysis.spike_traffic(
        sf.SpikformerConfig(embed_dim=64, num_layers=2, num_heads=4, t=8,
                            attn_ordering="linear"),
        backend=PALLAS_PACKED_KERNEL)
    assert lin["ssa_boundary_closed"]
    assert lin["packed_bytes_ssa_dense"] == lin["packed_bytes"]


def test_spike_traffic_closed_t32():
    cfg = sf.SpikformerConfig(embed_dim=64, num_layers=2, num_heads=4, t=32)
    closed = analysis.spike_traffic(cfg, backend=PALLAS_PACKED_KERNEL)
    assert closed["reduction_ssa_dense"] == closed["reduction"] == 32.0


# -- text serve(): dead-prefill removal regression ----------------------------

def test_serve_text_matches_full_forward_greedy():
    """``serve()`` output is unchanged by dropping the dead prefill: every
    generated token matches a teacher-forced full-forward greedy decode
    (also exercises the ragged final slot batch, which is now warmed)."""
    from repro.data.pipeline import DataConfig, make_batch
    from repro.launch.serve import serve
    from repro.models import lm, transformer as T

    n_req, p_len, max_new = 3, 8, 4
    done = serve("llama3.2-1b_smoke", num_requests=n_req, prompt_len=p_len,
                 max_new=max_new, slots=2, verbose=False)
    assert len(done) == n_req

    cfg = lm.get_config("llama3.2-1b_smoke")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    dcfg = DataConfig(seed=0, vocab_size=cfg.vocab_size, seq_len=p_len,
                      global_batch=n_req)
    seq = jnp.asarray(make_batch(dcfg, 0)["tokens"])
    outs = []
    for _ in range(max_new):
        logits, _, _ = T.forward(params, {"tokens": seq}, cfg)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        outs.append(tok)
        seq = jnp.concatenate([seq, tok[:, None]], axis=1)
    ref = np.asarray(jnp.stack(outs, axis=1))
    got = np.stack([gen for _, gen in sorted(done)])
    np.testing.assert_array_equal(got, ref)

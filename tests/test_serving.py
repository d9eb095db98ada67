"""Continuous-batching serving suite (ISSUE 9): slot paging, ragged
eviction, admission backpressure -- and the serving-path bugfix sweep.

Covers the acceptance criteria:
  * ``decode_state_scatter`` / ``decode_state_gather`` paging primitives:
    scattering individually-prefilled sequences into one batched
    ``DecodeState`` is BIT-equal to batched prefill (rows are independent
    through every engine op -- the fact that makes paging legal at all),
    round-trips exactly, and refuses a scalar-pos target,
  * ``ContinuousScheduler``: greedy outputs bit-exact per request vs the
    synchronous per-request reference under mixed prompt-length buckets,
    ragged ``max_new``, and EOS-triggered mid-flight eviction; no request
    lost or duplicated; evicted slots refill,
  * admission backpressure: the bounded queue refuses at ``max_pending``
    (``reject`` drops and counts, ``defer`` retries to completion),
  * hypothesis property: random admission orders / slot counts / ragged
    lengths never lose or duplicate a request, and every completed request's
    tokens equal its single-stream reference decode,
  * ``serve_spiking_lm_continuous`` == ``serve_spiking_lm`` token-for-token
    at equal slot count (the scheduling discipline is the ONLY difference),
  * satellite bugfixes, each locked by a regression test here or in
    ``test_substrate.py``: the ``serve()`` prefill/decode timing split, the
    post-padding warm-shape dedupe, and the ``plan_remesh`` divisor search,
  * ``analysis.decode_slot_report`` / ``DecodeEntry.max_slots`` capacity
    accounting.

Mesh-mode tests skip under 2 devices; CI's serve-smoke/shard-smoke jobs
force host devices via ``XLA_FLAGS=--xla_force_host_platform_device_count``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.engine import analysis
from repro.launch import serve as serve_mod
from repro.launch.scheduler import (
    AdmissionQueue, ContinuousScheduler, Request, greedy)
from repro.launch.serve import _warm_padded_sizes, _warm_sizes
from repro.models import spiking_lm as slm
from repro.models.lm import get_config

KEY = jax.random.PRNGKey(0)
VOCAB = 64


def _small_cfg(t=4):
    return get_config("llama3.2-1b_smoke").replace(
        spiking=True, spike_t=t, num_layers=1, d_model=32, num_heads=2,
        head_dim=None, d_ff=64, vocab_size=VOCAB)


@functools.lru_cache(maxsize=None)
def _small_plan(t=4, ordering="linear", backend=None):
    cfg = _small_cfg(t)
    params = slm.init_spiking_lm(KEY, cfg)
    return engine.compile_plan(params, None, cfg, ordering=ordering,
                               backend=backend)


def _prompt(rid, s):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(1000 + rid), (s,), 0, VOCAB),
        np.int32)


_REF_CACHE: dict = {}


def _reference_decode(plan, prompt, max_new, eos_id=None) -> list[int]:
    """The synchronous single-stream oracle: batch-1 prefill + greedy step
    chain with the scheduler's exact completion rule."""
    key = (id(plan), bytes(np.asarray(prompt, np.int32)), max_new, eos_id)
    if key in _REF_CACHE:
        return _REF_CACHE[key]
    logits, state = engine.prefill(plan, jnp.asarray(prompt, jnp.int32)[None])
    toks = [int(greedy(logits[0, -1]))]
    while len(toks) < max_new and (eos_id is None or toks[-1] != eos_id):
        logits, state = engine.decode_step(
            plan, state, jnp.asarray([toks[-1]], jnp.int32))
        toks.append(int(greedy(logits[0])))
    _REF_CACHE[key] = toks
    return toks


# -- paging primitives: scatter / gather ---------------------------------------

def test_decode_state_batch_init_geometry():
    plan = _small_plan()
    st = engine.decode_state_batch_init(plan.meta, 3)
    assert st.pos.shape == (3,) and st.pos.dtype == jnp.int32
    assert tuple(kv.shape for kv in st.kv) == plan.meta.decode.state_shapes(3)


def test_scatter_equals_batched_prefill():
    """THE paging-legality lockdown: prefilling rows one at a time and
    scattering each into its slot builds the SAME batched state (bit-for-bit,
    kv and pos) as one batched prefill -- and one decode step from either
    state yields identical logits."""
    plan = _small_plan()
    seq = jnp.asarray(np.stack([_prompt(i, 6) for i in range(3)]))
    _, want = engine.prefill(plan, seq)
    st = engine.decode_state_batch_init(plan.meta, 3)
    for slot in (2, 0, 1):                      # out of admission order
        _, row = engine.prefill(plan, seq[slot][None])
        st = engine.decode_state_scatter(st, slot, row, 0)
    for got_kv, want_kv in zip(st.kv, want.kv):
        np.testing.assert_array_equal(np.asarray(got_kv), np.asarray(want_kv))
    assert np.all(np.asarray(st.pos) == 6)
    tok = jnp.zeros((3,), jnp.int32)
    got_logits, _ = engine.decode_step(plan, st, tok)
    want_logits, _ = engine.decode_step(plan, want, tok)
    np.testing.assert_array_equal(np.asarray(got_logits),
                                  np.asarray(want_logits))


def test_scatter_gather_roundtrip_mixed_lengths():
    """Sequences prefilled at DIFFERENT prompt lengths page into one batch
    (the state has no context-length axis) and gather back bit-exactly,
    carrying each slot's own position."""
    plan = _small_plan()
    st = engine.decode_state_batch_init(plan.meta, 2)
    rows = []
    for slot, s in enumerate((4, 9)):
        _, row = engine.prefill(plan, jnp.asarray(_prompt(slot, s))[None])
        rows.append(row)
        st = engine.decode_state_scatter(st, slot, row, 0)
    assert list(np.asarray(st.pos)) == [4, 9]
    for slot, row in enumerate(rows):
        back = engine.decode_state_gather(st, slot)
        assert int(back.pos) == int(row.pos)
        for got, want in zip(back.kv, row.kv):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_scatter_src_row_selection():
    """``src`` picks which row of a (padded) prefill batch pages in -- the
    mesh path prefills at the data degree and takes row 0."""
    plan = _small_plan()
    seq = jnp.asarray(np.stack([_prompt(7, 5), _prompt(8, 5)]))
    _, both = engine.prefill(plan, seq)
    _, solo = engine.prefill(plan, seq[1][None])
    st = engine.decode_state_scatter(
        engine.decode_state_batch_init(plan.meta, 1), 0, both, 1)
    for got, want in zip(st.kv, solo.kv):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_scatter_requires_pos_vector():
    plan = _small_plan()
    _, row = engine.prefill(plan, jnp.asarray(_prompt(0, 4))[None])
    scalar_target = engine.decode_state_init(plan.meta, 1)
    with pytest.raises(ValueError, match="per-slot pos"):
        engine.decode_state_scatter(scalar_target, 0, row, 0)


# -- scheduler: bit-exactness, eviction, slot reuse ----------------------------

def test_scheduler_bit_exact_ragged_mixed_lengths():
    """Mixed prompt-length buckets + ragged max_new at 2 slots over 5
    requests: every request completes with tokens EQUAL to its single-stream
    reference decode, no request lost or duplicated, and the service ends
    with every slot free again."""
    plan = _small_plan()
    reqs = [Request(rid=i, prompt=_prompt(i, (4, 7)[i % 2]),
                    max_new=(5, 3, 1, 4, 2)[i]) for i in range(5)]
    sched = ContinuousScheduler(plan, slots=2, max_pending=8)
    done = sched.run(reqs)
    assert sorted(r.rid for r in done) == [0, 1, 2, 3, 4]
    for r in done:
        assert r.tokens == _reference_decode(plan, r.prompt, r.max_new), r.rid
        assert len(r.tokens) == r.max_new
    stats = sched.stats()
    assert stats["completed"] == stats["admitted"] == 5
    assert stats["rejected"] == 0
    assert len(sched._free) == sched.slots       # all slots evicted + freed
    assert stats["new_tokens"] == sum(r.max_new for r in reqs)
    assert 0.0 < stats["slot_occupancy"] <= 1.0


def test_scheduler_eos_mid_flight_eviction():
    """EOS retires a sequence mid-flight: the evicted slot refills with a
    LATER request while earlier admissions keep decoding, and the stopped
    request's tokens end at (and include) the EOS -- matching its
    reference."""
    plan = _small_plan()
    base = _reference_decode(plan, _prompt(0, 5), 8)
    eos = base[1]                                # stops request 0 at token 2
    reqs = [Request(rid=0, prompt=_prompt(0, 5), max_new=8, eos_id=eos),
            Request(rid=1, prompt=_prompt(1, 5), max_new=8),
            Request(rid=2, prompt=_prompt(2, 5), max_new=4)]
    sched = ContinuousScheduler(plan, slots=2, max_pending=8)
    done = {r.rid: r for r in sched.run(reqs)}
    assert sorted(done) == [0, 1, 2]
    assert done[0].tokens == base[:2] and done[0].tokens[-1] == eos
    assert done[1].tokens == _reference_decode(plan, reqs[1].prompt, 8)
    assert done[2].tokens == _reference_decode(plan, reqs[2].prompt, 4)
    # request 2 could only run because request 0's slot freed mid-flight
    assert sched.stats()["steps"] < 8 + 4


def test_scheduler_max_new_one_never_occupies_slot():
    """max_new=1 finishes at prefill: zero decode steps, slot never taken."""
    plan = _small_plan()
    reqs = [Request(rid=i, prompt=_prompt(i, 4), max_new=1) for i in range(3)]
    sched = ContinuousScheduler(plan, slots=2, max_pending=8)
    done = sched.run(reqs)
    assert sorted(r.rid for r in done) == [0, 1, 2]
    assert sched.stats()["steps"] == 0
    for r in done:
        assert r.tokens == _reference_decode(plan, r.prompt, 1)


def test_scheduler_warm_dedupes_prompt_buckets():
    plan = _small_plan()
    sched = ContinuousScheduler(plan, slots=2)
    assert sched.warm([5, 7, 5, 7, 7]) == 2


def test_scheduler_validation():
    plan = _small_plan()
    with pytest.raises(ValueError, match="positive multiple"):
        ContinuousScheduler(plan, slots=0)
    with pytest.raises(ValueError, match="max_pending"):
        AdmissionQueue(max_pending=0)
    with pytest.raises(ValueError, match="admission policy"):
        AdmissionQueue(policy="drop-newest")
    from repro.core import spikformer as sf
    vcfg = sf.SpikformerConfig(embed_dim=32, num_layers=1, num_heads=2, t=2)
    vp, vs = sf.init(KEY, vcfg)
    vplan = engine.compile_plan(vp, vs, vcfg)
    with pytest.raises(ValueError, match="LM-plan"):
        ContinuousScheduler(vplan, slots=2)


# -- admission backpressure ----------------------------------------------------

def test_backpressure_reject_drops_and_counts():
    """``reject`` policy: once ``max_pending`` waits, further arrivals are
    dropped and counted -- never silently lost, never served."""
    plan = _small_plan()
    reqs = [Request(rid=i, prompt=_prompt(i, 4), max_new=2) for i in range(5)]
    sched = ContinuousScheduler(plan, slots=1, max_pending=1,
                                admission="reject")
    done = sched.run(reqs)
    stats = sched.stats()
    assert stats["completed"] + stats["rejected"] == 5
    assert stats["rejected"] == stats["queue_refused"] > 0
    done_rids = {r.rid for r in done}
    rej_rids = {r.rid for r in sched.rejected}
    assert done_rids | rej_rids == set(range(5))
    assert not (done_rids & rej_rids)
    for r in done:                               # served work is still exact
        assert r.tokens == _reference_decode(plan, r.prompt, r.max_new)


def test_backpressure_defer_retries_to_completion():
    """``defer`` policy: refused arrivals retry after the tick -- everything
    completes, and the refusal count proves the bound actually bit."""
    plan = _small_plan()
    reqs = [Request(rid=i, prompt=_prompt(i, 4), max_new=2) for i in range(4)]
    sched = ContinuousScheduler(plan, slots=1, max_pending=1,
                                admission="defer")
    done = sched.run(reqs)
    stats = sched.stats()
    assert sorted(r.rid for r in done) == [0, 1, 2, 3]
    assert stats["rejected"] == 0
    assert stats["queue_refused"] > 0
    assert stats["queue_high_water"] == 1


# -- hypothesis property -------------------------------------------------------

def test_scheduler_property_no_loss_no_dup_bit_exact():
    """Property: under RANDOM admission orders, slot counts, prompt-length
    mixes, and ragged decode lengths, the scheduler (a) completes every
    request exactly once, (b) ends with all slots free, and (c) every
    request's greedy tokens equal its single-stream reference -- continuous
    batching is a scheduling choice, never a numerics choice."""
    pytest.importorskip("hypothesis")
    import hypothesis.strategies as st
    from hypothesis import given, settings

    plan = _small_plan()

    @settings(deadline=None, max_examples=10)
    @given(
        slots=st.integers(1, 3),
        n=st.integers(1, 6),
        lens=st.lists(st.sampled_from([2, 3, 5]), min_size=1, max_size=3),
        max_news=st.lists(st.integers(1, 5), min_size=6, max_size=6),
        order=st.permutations(list(range(6))),
        max_pending=st.integers(1, 6),
        chunk=st.one_of(st.none(), st.integers(1, 6)),
    )
    def check(slots, n, lens, max_news, order, max_pending, chunk):
        reqs = [Request(rid=i, prompt=_prompt(i, lens[i % len(lens)]),
                        max_new=max_news[i],
                        arrival_s=float(order[i]))    # admission order
                for i in range(n)]
        sched = ContinuousScheduler(plan, slots=slots,
                                    max_pending=max_pending,
                                    admission="defer",
                                    prefill_chunk=chunk)
        done = sched.run(reqs)
        assert sorted(r.rid for r in done) == list(range(n))
        assert len(sched._free) == slots
        assert all(s is None for s in sched._active)
        for r in done:
            assert r.tokens == _reference_decode(plan, r.prompt, r.max_new)

    check()


# -- serve-function level: continuous == synchronous ---------------------------

def test_continuous_matches_sync_serve():
    """Acceptance: ``serve_spiking_lm_continuous`` reproduces
    ``serve_spiking_lm`` token-for-token per request at equal slot count --
    the scheduling discipline is the only difference between the paths."""
    kw = dict(num_requests=5, prompt_len=6, max_new=4, slots=2,
              backend="jnp", ordering="linear", verbose=False)
    sync = dict(serve_mod.serve_spiking_lm("llama3.2-1b_smoke", **kw))
    cont, stats = serve_mod.serve_spiking_lm_continuous(
        "llama3.2-1b_smoke", return_stats=True, **kw)
    cont = dict(cont)
    assert sorted(cont) == sorted(sync) == [0, 1, 2, 3, 4]
    for rid in sync:
        np.testing.assert_array_equal(cont[rid], np.asarray(sync[rid]),
                                      err_msg=f"rid={rid}")
    assert stats["completed"] == 5
    assert stats["warm_step_shapes"] == 1
    assert stats["warm_prefill_shapes"] == 1     # one prompt-length bucket


def test_continuous_ragged_matches_reference():
    """Mixed prompt-length buckets + staggered max_new through the full
    ``serve_spiking_lm_continuous`` entry point: rebuild the identical plan
    and workload (both are seed-deterministic) and check every request
    against its single-stream reference decode."""
    from repro.data.pipeline import DataConfig, make_batch
    from repro.launch.serve import serving_requests, spiking_lm_config

    lens, max_new, spread, n = [4, 7], 5, 2, 5
    cont, stats = serve_mod.serve_spiking_lm_continuous(
        "llama3.2-1b_smoke", num_requests=n, prompt_len=max(lens),
        max_new=max_new, slots=2, backend="jnp", ordering="linear",
        prompt_lens=lens, max_new_spread=spread, verbose=False,
        return_stats=True)
    cont = dict(cont)
    assert sorted(cont) == list(range(n))
    assert stats["warm_prefill_shapes"] == 2     # two length buckets

    cfg = spiking_lm_config("llama3.2-1b_smoke")
    params = slm.init_spiking_lm(jax.random.PRNGKey(0), cfg)
    plan = engine.compile_plan(params, None, cfg, backend="jnp",
                               ordering="linear")
    dcfg = DataConfig(seed=0, vocab_size=cfg.vocab_size, seq_len=max(lens),
                      global_batch=n)
    prompts = make_batch(dcfg, 0)["tokens"]
    for req in serving_requests(prompts, prompt_lens=sorted(lens),
                                max_new=max_new, max_new_spread=spread):
        ref = _reference_decode(plan, req.prompt, req.max_new)
        assert list(cont[req.rid]) == ref, f"rid={req.rid}"


# -- satellite regressions -----------------------------------------------------

def test_serve_timing_split(monkeypatch):
    """Regression: legacy ``serve()`` folded the prompt-feed loop into the
    decode wall-clock interval, understating decode throughput by a factor
    ~prompt_len/max_new.  With a fake clock that ticks 1s per serve_step
    call, prefill_s must count EXACTLY the prompt-feed steps and decode_s
    exactly the generation steps."""
    clock = {"t": 0.0}
    monkeypatch.setattr(serve_mod.time, "perf_counter", lambda: clock["t"])
    monkeypatch.setattr(serve_mod.jax, "jit", lambda fn, **kw: fn)

    def fake_make_serve_step(cfg):
        def step(params, cache, batch, t):
            clock["t"] += 1.0
            b = batch["token"].shape[0]
            return jnp.zeros((b, 1, cfg.vocab_size)), cache
        return step

    monkeypatch.setattr(serve_mod.lm, "make_serve_step", fake_make_serve_step)
    n, p, m, slots = 4, 3, 5, 2                  # 2 slot batches
    done, stats = serve_mod.serve("llama3.2-1b_smoke", num_requests=n,
                                  prompt_len=p, max_new=m, slots=slots,
                                  verbose=False, return_stats=True)
    assert len(done) == n
    nb = n // slots
    assert stats["prefill_s"] == nb * p          # prompt-feed steps only
    assert stats["decode_s"] == nb * (m - 1)     # generation steps only
    assert stats["prompt_tokens"] == n * p and stats["new_tokens"] == n * m
    assert stats["prefill_tokens_per_s"] == (n * p) / (nb * p)
    assert stats["decode_tokens_per_s"] == (n * m) / (nb * (m - 1))


def test_warm_padded_sizes_dedupes_post_padding():
    """Regression: padding each pre-padding warm size independently lets two
    ragged sizes collapse to the SAME padded shape and warm twice (slots=4,
    requests=7, data_par=2: {4, 3} -> both pad to 4)."""
    assert _warm_sizes(4, 7) == {4, 3}
    assert _warm_padded_sizes(4, 7, 2) == {4}
    assert _warm_padded_sizes(4, 7, 1) == {4, 3}
    assert _warm_padded_sizes(4, 8, 2) == {4}
    assert _warm_padded_sizes(2, 5, 4) == {4}    # 2 and 1 both pad to 4
    assert _warm_padded_sizes(4, 3, 2) == {4}    # short run: min(slots, n)=3


def _skip_under(n):
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices, have {jax.device_count()} "
                    "(CI forces host devices via "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def test_serve_spiking_lm_warm_calls_once_per_padded_shape(monkeypatch):
    """Counting regression on a forced 2-device mesh: with slots=4 and 7
    requests at data_par=2, warm must prefill ONCE (the deduped padded shape
    {4}), so total prefill calls = 1 warm + 2 slot batches.  The old
    per-entry padding warmed the same (4, S) shape twice."""
    _skip_under(2)
    calls = []
    orig = engine.make_prefill_fn

    def counting_make(plan):
        fn = orig(plan)

        def wrapped(params, tokens):
            # debug.callback fires per EXECUTION (not per trace), so the
            # count sees every warm + serving prefill run even under jit
            shape = tuple(tokens.shape)
            jax.debug.callback(lambda: calls.append(shape))
            return fn(params, tokens)
        return wrapped

    monkeypatch.setattr(engine, "make_prefill_fn", counting_make)
    done = serve_mod.serve_spiking_lm(
        "llama3.2-1b_smoke", num_requests=7, prompt_len=4, max_new=2,
        slots=4, mesh="2x1", backend="jnp", ordering="linear", verbose=False)
    jax.effects_barrier()
    assert len(done) == 7
    assert len(calls) == 3                       # 1 warm + ceil(7/4) batches
    assert set(calls) == {(4, 4)}                # every call the padded shape


def test_continuous_mesh_matches_single_device():
    """Continuous serving under a data-parallel mesh: same tokens per request
    as the single-device continuous path (and the slot count must divide the
    data degree)."""
    _skip_under(2)
    kw = dict(num_requests=3, prompt_len=5, max_new=3, slots=2,
              backend="jnp", ordering="linear", verbose=False)
    single = dict(serve_mod.serve_spiking_lm_continuous(
        "llama3.2-1b_smoke", **kw))
    meshed = dict(serve_mod.serve_spiking_lm_continuous(
        "llama3.2-1b_smoke", mesh="2x1", **kw))
    assert sorted(meshed) == sorted(single)
    for rid in single:
        np.testing.assert_array_equal(meshed[rid], single[rid],
                                      err_msg=f"rid={rid}")
    _, plan, _, _ = serve_mod._compile_lm_serving(
        "llama3.2-1b_smoke", backend="jnp", ordering="linear",
        mesh=(2, 1), slots=2, seed=0, verbose=False)
    with pytest.raises(ValueError, match="positive multiple"):
        ContinuousScheduler(plan, slots=3)


def test_elastic_mesh_abort_raises():
    """Regression: a mesh whose model axis cannot fit the fleet used to fall
    back to single-device serving; it must raise instead, on every serve
    path, before any plan is compiled."""
    too_wide = (1, jax.device_count() + 1)
    with pytest.raises(RuntimeError, match="infeasible"):
        serve_mod._elastic_mesh(too_wide, 4, verbose=False)
    with pytest.raises(RuntimeError, match="infeasible"):
        serve_mod.serve_vision("spike-iand-former_smoke", num_requests=2,
                               slots=2, mesh=too_wide, verbose=False)
    with pytest.raises(RuntimeError, match="infeasible"):
        serve_mod.serve_spiking_lm_continuous(
            "llama3.2-1b_smoke", num_requests=1, prompt_len=4, max_new=2,
            mesh=too_wide, verbose=False)


def test_serve_vision_mesh_places_weights():
    """Mesh-sharded vision serving commits the plan's block weights across
    the mesh (no per-call weight transfer from device 0) and returns logits
    bit-equal to single-device serving."""
    _skip_under(2)
    kw = dict(num_requests=4, slots=2, backend="jnp+packed", verbose=False,
              return_stats=True)
    _, single = serve_mod.serve_vision("spike-iand-former_smoke", **kw)
    _, meshed = serve_mod.serve_vision("spike-iand-former_smoke",
                                       mesh="1x2", **kw)
    np.testing.assert_array_equal(meshed["logits"], single["logits"])
    assert meshed["logits"].shape == (4, 10)
    w = meshed["plan"].params["blocks"][0]["q"]["w"]
    assert len(w.sharding.device_set) == 2
    assert not w.sharding.is_fully_replicated


@pytest.mark.parametrize("env_dir", [None, "custom"])
def test_enable_compile_cache(monkeypatch, tmp_path, env_dir):
    """The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says (and nothing is set in code), else to the fixed in-checkout
    ``.jax_cache`` directory."""
    from pathlib import Path

    from repro.launch.compile_info import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        got = enable_compile_cache()
        if env_dir is None:
            checkout = Path(serve_mod.__file__).resolve().parents[3]
            assert got == str(checkout / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# -- capacity accounting -------------------------------------------------------

def test_decode_slot_report():
    plan = _small_plan()
    entry = plan.meta.decode
    rep = analysis.decode_slot_report(plan, slots=4, prompt_lens=(4, 7, 4))
    assert rep["slots"] == 4
    assert rep["state_bytes_per_slot"] == entry.state_bytes(1)
    assert rep["state_bytes_batch"] == entry.state_bytes(4)
    assert rep["state_bytes_batch"] == 4 * rep["state_bytes_per_slot"]
    assert rep["warm_step_shapes"] == 1
    assert rep["warm_prefill_shapes"] == 2
    assert rep["prompt_len_buckets"] == (4, 7)
    assert rep["bytes_per_step_dense"] > 0
    budget = 10 * entry.state_bytes(1) + 3
    rep2 = analysis.decode_slot_report(plan, slots=4, budget_bytes=budget)
    assert rep2["max_slots"] == entry.max_slots(budget) == 10
    from repro.core import spikformer as sf
    vcfg = sf.SpikformerConfig(embed_dim=32, num_layers=1, num_heads=2, t=2)
    vp, vs = sf.init(KEY, vcfg)
    with pytest.raises(ValueError, match="LM-plan"):
        analysis.decode_slot_report(engine.compile_plan(vp, vs, vcfg), slots=2)


def test_max_slots_exact():
    entry = _small_plan().meta.decode
    per = entry.state_bytes(1)
    assert entry.max_slots(0) == 0
    assert entry.max_slots(per - 1) == 0
    assert entry.max_slots(per) == 1
    assert entry.max_slots(7 * per + per - 1) == 7


# -- chunked resumable prefill (ISSUE 10) --------------------------------------

def _chunked_prefill(plan, prompt, chunk):
    """Reference driver: feed ``prompt`` (B, S) through ``engine.prefill_chunk``
    in C-token pieces (ragged tail included), concatenating the logits."""
    st = engine.decode_state_init(plan.meta, prompt.shape[0])
    outs = []
    for lo in range(0, prompt.shape[1], chunk):
        logits, st = engine.prefill_chunk(plan, st, prompt[:, lo:lo + chunk])
        outs.append(logits)
    return jnp.concatenate(outs, axis=1), st


@pytest.mark.parametrize("backend", [None, "jnp+packed", "pallas+packed",
                                     "pallas+packed+sparse"])
@pytest.mark.parametrize("ordering", ["linear", "quadratic"])
def test_prefill_chunk_bit_exact(backend, ordering):
    """THE resumability lockdown: chunked prefill (ragged tail included)
    concatenates to one-shot prefill's logits and reproduces its DecodeState
    bit-for-bit -- on every backend and both orderings, because the chunk
    carry is exact integer arithmetic on binary spikes."""
    plan = _small_plan(4, ordering, backend)
    prompt = jnp.asarray(np.stack([_prompt(0, 13), _prompt(1, 13)]))
    want_logits, want = engine.prefill(plan, prompt)
    got_logits, got = _chunked_prefill(plan, prompt, 5)      # 5+5+3 ragged
    np.testing.assert_array_equal(np.asarray(got_logits),
                                  np.asarray(want_logits))
    for a, b in zip(got.kv, want.kv):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(got.pos) == int(want.pos) == 13


def test_prefill_chunk_property_bit_exact():
    """Hypothesis property: ``chunked_prefill(p, C) == prefill(p)`` (logits
    AND DecodeState, bit-exact) over random prompt lengths, chunk sizes
    including C=1, ragged tails, C >= S, and multi-word packed trains
    (T=40 spans two uint32 bitplane words)."""
    pytest.importorskip("hypothesis")
    import hypothesis.strategies as st
    from hypothesis import given, settings

    @settings(deadline=None, max_examples=10)
    @given(
        t=st.sampled_from([1, 8, 32, 40]),
        ordering=st.sampled_from(["linear", "quadratic"]),
        backend=st.sampled_from([None, "pallas+packed"]),
        s=st.integers(1, 40),
        c=st.sampled_from(["1", "4", "13", "512", "S", "S+7"]),
    )
    def check(t, ordering, backend, s, c):
        chunk = {"S": s, "S+7": s + 7}.get(c) or int(c)
        plan = _small_plan(t, ordering, backend)
        prompt = jnp.asarray(_prompt(s, s))[None]
        want_logits, want = engine.prefill(plan, prompt)
        got_logits, got = _chunked_prefill(plan, prompt, chunk)
        np.testing.assert_array_equal(np.asarray(got_logits),
                                      np.asarray(want_logits))
        for a, b in zip(got.kv, want.kv):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(got.pos) == int(want.pos) == s

    check()


def test_prefill_chunk_jaxpr_flat_in_prompt_len():
    """Structural flatness (the PR-5 check, prefill edition): the chunk
    step's jaxpr -- traced AFTER a long prefix has been consumed -- mentions
    the CHUNK length but never the full prompt length, so a 500k prompt's
    memory is set by C, not S."""
    plan = _small_plan()
    long_s, chunk = 37, 5          # 37 collides with no model/chunk dim
    _, st = engine.prefill(plan, jnp.asarray(_prompt(0, long_s))[None])
    fn = engine.make_prefill_chunk_fn(plan)
    tokens = jnp.zeros((1, chunk), jnp.int32)
    dims = analysis.jaxpr_dims(fn, plan.params, st, tokens)
    assert chunk in dims
    assert long_s not in dims
    assert int(st.pos) == long_s


def test_scheduler_chunked_interleaves_with_decode():
    """Decode-interleaved admission: with a decode in flight, a long-prompt
    admission advances AT MOST ONE prefill chunk per scheduler tick (decode
    steps strictly interleave the chunks), and every request's tokens still
    equal the single-stream reference."""
    plan = _small_plan()
    reqs = [Request(rid=0, prompt=_prompt(0, 3), max_new=12),
            Request(rid=1, prompt=_prompt(1, 11), max_new=4)]  # 3+3+3+2 chunks
    sched = ContinuousScheduler(plan, slots=2, max_pending=8, prefill_chunk=3)
    chunk_steps = []
    orig = sched._prefill_chunk

    def counting(params, st, tokens):
        chunk_steps.append(sched.steps)
        return orig(params, st, tokens)

    sched._prefill_chunk = counting
    done = {r.rid: r for r in sched.run(reqs)}
    assert sorted(done) == [0, 1]
    for rid, r in done.items():
        assert r.tokens == _reference_decode(plan, r.prompt, r.max_new), rid
    # request 0 admits on tick 1 (one chunk); request 1's four chunks then
    # land on four DISTINCT decode ticks -- never two chunks between steps
    assert len(chunk_steps) == 5
    assert chunk_steps == sorted(set(chunk_steps))
    assert sched.stats()["prefill_chunks"] == 5
    # TTFT ordering survives interleaving: rid 0 seats before rid 1
    assert done[0].first_token_s < done[1].first_token_s


def test_scheduler_chunked_warm_buckets():
    """Chunked warming bills one shape per CHUNK bucket (C plus each ragged
    tail), not per prompt length -- 5 and 7 at C=3 share the full-chunk
    shape and add tails 2 and 1."""
    plan = _small_plan()
    sched = ContinuousScheduler(plan, slots=2, prefill_chunk=3)
    assert sched.warm([5, 7, 5]) == 3            # shapes {3, 2, 1}
    sched2 = ContinuousScheduler(plan, slots=2, prefill_chunk=4)
    assert sched2.warm([8, 12]) == 1             # all chunks full: {4}
    with pytest.raises(ValueError, match="prefill_chunk"):
        ContinuousScheduler(plan, slots=2, prefill_chunk=0)


def test_admit_ttft_monotone_across_drain():
    """Satellite regression (stale-``now`` TTFT): requests admitted in ONE
    drain must each read a fresh clock -- ``admit_s``/``first_token_s``
    strictly increase across the drain and TTFT includes the preceding
    prefills' time.  The old code stamped every admission with the loop-entry
    ``now``, so a drain's requests all reported identical timestamps."""
    plan = _small_plan()

    ticks = [0.0]

    def clock():
        ticks[0] += 1.0
        return ticks[0]

    reqs = [Request(rid=i, prompt=_prompt(i, 4), max_new=2) for i in range(3)]
    sched = ContinuousScheduler(plan, slots=4, max_pending=8, clock=clock)
    done = sorted(sched.run(reqs), key=lambda r: r.rid)
    admits = [r.admit_s for r in done]
    firsts = [r.first_token_s for r in done]
    assert admits == sorted(admits) and len(set(admits)) == 3
    assert firsts == sorted(firsts) and len(set(firsts)) == 3
    for r in done:
        assert r.first_token_s > r.admit_s       # prefill time is visible


def test_continuous_prompt_lens_multiset_preserved(monkeypatch):
    """Satellite regression (prompt-length mixture corruption):
    ``--prompt-lens 4,4,7`` is a 2:1 mixture and must reach
    ``serving_requests`` as the full multiset (the old ``sorted({...})``
    collapsed it to a 1:1 cycle); dedup applies only to shape warming."""
    import collections

    seen = {}
    orig = serve_mod.serving_requests

    def spy(prompts, *, prompt_lens, **kw):
        seen["lens"] = list(prompt_lens)
        reqs = orig(prompts, prompt_lens=prompt_lens, **kw)
        seen["hist"] = collections.Counter(r.prompt_len for r in reqs)
        return reqs

    monkeypatch.setattr(serve_mod, "serving_requests", spy)
    done, stats = serve_mod.serve_spiking_lm_continuous(
        "llama3.2-1b_smoke", num_requests=6, prompt_len=8,
        prompt_lens=[4, 4, 7], max_new=2, slots=2, backend="jnp",
        ordering="linear", verbose=False, return_stats=True)
    assert seen["lens"] == [4, 4, 7]             # multiset, order preserved
    assert seen["hist"] == {4: 4, 7: 2}          # the requested 2:1 mixture
    assert stats["warm_prefill_shapes"] == 2     # warming deduped to {4, 7}
    assert len(done) == 6


def test_serve_continuous_chunked_matches_oneshot():
    """Serve-entry-point equivalence: ``--prefill-chunk`` changes scheduling
    only -- token streams are bit-exact vs one-shot admission, and the warm
    bill shrinks to the chunk buckets."""
    kw = dict(num_requests=5, prompt_len=8, prompt_lens=[4, 8], max_new=3,
              slots=2, backend="jnp", ordering="linear", verbose=False)
    base = dict(serve_mod.serve_spiking_lm_continuous(
        "llama3.2-1b_smoke", **kw))
    chunked, stats = serve_mod.serve_spiking_lm_continuous(
        "llama3.2-1b_smoke", prefill_chunk=3, return_stats=True, **kw)
    chunked = dict(chunked)
    assert sorted(chunked) == sorted(base)
    for rid in base:
        np.testing.assert_array_equal(chunked[rid], np.asarray(base[rid]),
                                      err_msg=f"rid={rid}")
    assert stats["prefill_chunk"] == 3
    assert stats["prefill_chunks"] > 0
    assert stats["warm_prefill_shapes"] == 3     # buckets {3, 2, 1}


def test_continuous_mesh_chunked_matches_single_device():
    """Chunked admission composes with a data-parallel mesh: same tokens per
    request as the single-device one-shot continuous path."""
    _skip_under(2)
    kw = dict(num_requests=3, prompt_len=5, max_new=3, slots=2,
              backend="jnp", ordering="linear", verbose=False)
    single = dict(serve_mod.serve_spiking_lm_continuous(
        "llama3.2-1b_smoke", **kw))
    meshed = dict(serve_mod.serve_spiking_lm_continuous(
        "llama3.2-1b_smoke", mesh="2x1", prefill_chunk=2, **kw))
    assert sorted(meshed) == sorted(single)
    for rid in single:
        np.testing.assert_array_equal(meshed[rid], single[rid],
                                      err_msg=f"rid={rid}")


def test_prefill_chunk_report():
    plan = _small_plan()
    rep = analysis.prefill_chunk_report(plan, seq_len=11, chunk=4)
    assert rep["num_chunks"] == 3
    assert rep["chunk_buckets"] == [4, 3]
    assert rep["state_bytes"] == plan.meta.decode.state_bytes(1)
    # residency flat in S: growing the prompt 64x leaves the chunked bytes
    # unchanged while one-shot residency scales with it
    long = analysis.prefill_chunk_report(plan, seq_len=4096, chunk=64)
    assert long["chunked_plane_bytes"] == analysis.prefill_chunk_report(
        plan, seq_len=64 * 4096, chunk=64)["chunked_plane_bytes"]
    assert long["oneshot_plane_bytes"] > long["chunked_plane_bytes"]
    assert long["plane_reduction"] > 1.0
    exact = analysis.prefill_chunk_report(plan, seq_len=8, chunk=4)
    assert exact["num_chunks"] == 2 and exact["chunk_buckets"] == [4]
    from repro.core import spikformer as sf
    vcfg = sf.SpikformerConfig(embed_dim=32, num_layers=1, num_heads=2, t=2)
    vp, vs = sf.init(KEY, vcfg)
    with pytest.raises(ValueError, match="LM-plan"):
        analysis.prefill_chunk_report(engine.compile_plan(vp, vs, vcfg),
                                      seq_len=8, chunk=4)

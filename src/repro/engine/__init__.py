"""Deploy-time fused inference engine (the paper's accelerator view).

Layer-plan / execute split:

* :func:`compile_plan` folds a trained ``(params, state, cfg)`` into a
  :class:`DeployPlan`: ConvBN/LinearBN pairs become single weight reads,
  AND-NOT residuals are marked for the fused LIF epilogue, and the backend
  (jnp vs Pallas, interpret vs compiled) becomes a plan property.
* :func:`apply` / :func:`make_apply_fn` execute a plan (the latter returns a
  pure jit-friendly ``fn(params, image)``).
* :func:`plan_stats` and :mod:`repro.engine.analysis` account for the ops the
  deploy view eliminated (BN passes, standalone IAND passes, repeated weight
  reads).

The layer list itself lives in :mod:`repro.engine.layout` and is shared with
the training graph in ``repro.core`` -- one definition, two views.

``Backend.packed`` switches the executor to the bit-packed spike datapath:
inter-layer activations travel as uint32 bitplane words
(``repro.core.packing``), cutting inter-layer spike traffic by up to 32x
(8x at T=8) while staying bit-exact with the dense plan.

``compile_plan(..., mesh=...)`` makes a plan mesh-aware end to end
(:class:`ShardingCfg` on ``PlanMeta``): executors run under ``shard_map`` on
a (data, model) host mesh, and every cross-device spike edge moves as uint32
bitplane words through the packed-word collectives
(:func:`word_allgather` / :func:`word_psum` / :func:`word_reduce_scatter`) --
bit-exact vs the single-device plan on every backend and ordering.
"""

from repro.engine.backend import (
    JNP, JNP_PACKED, PALLAS, PALLAS_PACKED, Backend,
    resolve as resolve_backend, spike_allgather, spike_shard, ssa_apply,
    ssa_apply_packed, ssa_decode_step, ssa_decode_step_packed,
    ssa_prefill_apply, ssa_prefill_apply_packed, ssa_prefill_chunk,
    ssa_prefill_chunk_packed, ssa_prefill_state, ssa_prefill_state_packed,
    unit_partition_specs, word_allgather, word_psum, word_reduce_scatter,
)
from repro.engine.execute import (
    DecodeState, apply, decode_state_batch_init, decode_state_gather,
    decode_state_init, decode_state_scatter, decode_step, make_apply_fn,
    make_decode_step_fn, make_prefill_chunk_fn, make_prefill_fn,
    place_params, prefill, prefill_chunk,
)
from repro.engine.layout import (
    ProjUnit, SpikeEdge, TokStage, block_layout, lm_block_layout,
    lm_decode_spike_edges, lm_spike_edges, spike_edges, tokenizer_layout,
)
from repro.engine.plan import (
    DecodeEntry, DeployPlan, LMDeployCfg, PlanMeta, ShardingCfg, compile_plan,
    plan_stats,
)

__all__ = [
    "JNP", "JNP_PACKED", "PALLAS", "PALLAS_PACKED", "Backend",
    "resolve_backend", "spike_allgather", "spike_shard", "ssa_apply",
    "ssa_apply_packed", "ssa_decode_step", "ssa_decode_step_packed",
    "ssa_prefill_apply", "ssa_prefill_apply_packed", "ssa_prefill_chunk",
    "ssa_prefill_chunk_packed", "ssa_prefill_state",
    "ssa_prefill_state_packed", "unit_partition_specs", "word_allgather",
    "word_psum", "word_reduce_scatter",
    "DecodeState", "apply", "decode_state_batch_init", "decode_state_gather",
    "decode_state_init", "decode_state_scatter", "decode_step",
    "make_apply_fn", "make_decode_step_fn", "make_prefill_chunk_fn",
    "make_prefill_fn", "place_params", "prefill", "prefill_chunk",
    "ProjUnit", "SpikeEdge", "TokStage", "block_layout", "lm_block_layout",
    "lm_decode_spike_edges", "lm_spike_edges", "spike_edges",
    "tokenizer_layout",
    "DecodeEntry", "DeployPlan", "LMDeployCfg", "PlanMeta", "ShardingCfg",
    "compile_plan", "plan_stats",
]

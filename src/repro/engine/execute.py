"""Deploy-plan executor: folded weights in, logits out.

Walks the same layer list (``engine.layout``) as the training graph, but in
the accelerator's deploy view:

* each stage/unit is ONE folded weight read (Conv/Linear with the BN baked
  in) -- no separate BN pass over the activations;
* every AND-NOT residual executes inside the LIF dispatch's epilogue
  (``iand_skip``), so spikes are written once -- no standalone IAND pass;
* all Conv/Linear compute is tick-batched (T folded into the batch: one
  weight read serves all time steps);
* with ``Backend.packed``, spikes move between layers bit-packed along time
  (``repro.core.packing``): LIF epilogues emit uint32 bitplane words, the
  IAND residual is the bitwise ``skip & ~s`` on words, GEMMs AND the SSA take
  the words as operands (unpacked per-tile in VMEM on the compiled Pallas
  route), and the head rate-decodes by popcount -- dense spike tensors only
  ever materialise inside kernels, tokenizer-to-head.

All compute -- linears, convs, and attention alike -- goes through
``repro.engine.backend``; the executor never calls a kernel or oracle
directly, so the plan's backend fully decides the compute route.

LM plans (``PlanMeta.family == "lm"``) walk the same structure with the LM
specifics: folded Linear+RMSNorm units (GEMM on gain-folded weights + the
gain-free normalizer epilogue), causal SSA, every residual join fused
(all-spike IAND), a pre-normalized embedding table in place of the
tokenizer, and the rate-decoded head whose inline normalization is the one
irreducible norm of the plan.  LM plans also expose TRUE incremental decode
(:func:`prefill` / :func:`decode_step` and their ``make_*_fn`` factories):
the causal SSA's linear ordering admits an O(d^2)-per-head running K^T V
state (:class:`DecodeState`), so generation never re-scores the prefix --
per-token cost is flat in context length, bit-exact vs the full forward.

Executors are pure functions of (folded params, image); static plan metadata
is closed over, so ``jax.jit(make_apply_fn(plan))`` caches per plan shape.
"""

from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import nn as cnn
from repro.core import packing
from repro.core.iand import connective
from repro.core.spiking_attention import merge_heads, split_heads, split_heads_packed
from repro.engine import backend as B
from repro.engine.plan import DeployPlan, PlanMeta

# active spike tap (``capture_spikes``): every packed train a LIF epilogue
# emits is appended here, so measured-occupancy reports see exactly the
# activations the executor moved -- None when no capture is active
_spike_tap: list | None = None


@contextlib.contextmanager
def capture_spikes():
    """Capture every packed spike train the executor's LIF epilogues emit.

    ``with capture_spikes() as taps: engine.apply(plan, batch)`` leaves
    ``taps`` holding one ``PackedSpikes`` per LIF dispatch, in execution
    order -- the measured-sparsity input of ``engine.analysis.sparsity_report``
    (run UNJITTED so the captured leaves are concrete arrays)."""
    global _spike_tap
    prev, _spike_tap = _spike_tap, []
    try:
        yield _spike_tap
    finally:
        _spike_tap = prev


def _lif(meta: PlanMeta, drive, iand_skip=None, pack_output=False,
         occupancy=None):
    cfg = meta.cfg
    out = B.lif_apply(
        meta.backend, drive, theta=cfg.theta, lam=cfg.lam,
        schedule=cfg.lif_schedule, chain_len=cfg.chain_len,
        iand_skip=iand_skip, pack_output=pack_output, occupancy=occupancy)
    if _spike_tap is not None and isinstance(out, packing.PackedSpikes):
        _spike_tap.append(out)
    return out


# -- mesh execution ----------------------------------------------------------
#
# A sharded plan runs the SAME walkers under ``shard_map``, with every
# cross-shard exchange routed through one small op table (:class:`_MeshOps`).
# The table's null value is the identity on every method, and the walkers
# default to it -- so the single-device path is byte-identical to before and
# the sharded path cannot structurally diverge from it.  The two families
# shard differently (see ``distributed.sharding.ENGINE_FAMILY_OVERRIDES``):
#
# * vision (``feature_tp``): column-parallel units -- the residual spike
#   stream lives feature-sharded between joins, and each unit consumes the
#   gathered full-feature stream (``gather_stream``, cached per stream
#   version) while producing only its local output columns.  Exactly four
#   feature all-gathers per block, each a packed-word collective under
#   packed backends.
# * lm: units replicated (the folded RMSNorm epilogue reduces over the full
#   feature row -- column slices would reassociate it), TP shards the SSA
#   heads instead: ``wrap_ssa`` slices the local heads out of the head-split
#   q/k/v, and the attention LIF output is the ONE cross-device spike edge
#   per block (``gather_heads``).


def _slice_heads(x, idx, h_loc: int):
    """Local head block of head-split q/k/v: dense (T, B, H, N, Dh) or packed
    words (W, B, H, N, Dh) -> the ``h_loc`` heads starting at ``idx * h_loc``
    (head axis is axis 2 in both layouts)."""
    sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                           start_index=idx * h_loc, slice_size=h_loc, axis=2)
    if isinstance(x, packing.PackedSpikes):
        return packing.PackedSpikes(
            sl(x.words), x.t, occ=None if x.occ is None else sl(x.occ))
    return sl(x)


@dataclass(frozen=True)
class _MeshOps:
    """Cross-shard exchange table of one sharded execution (static: closed
    over by the shard_map body).  ``tp`` is the model-axis size; with
    ``tp == 1`` every method is the identity (:data:`_NULL_OPS`)."""

    tp_axis: str | None = None
    tp: int = 1
    feature_tp: bool = True     # vision column-parallel vs LM head-sharded

    def local_heads(self, h: int) -> int:
        """Heads resident on this shard (vision: the q/k/v units already
        produced only the local head columns)."""
        return h // self.tp if (self.feature_tp and self.tp > 1) else h

    def gather_stream(self, x):
        """Feature-sharded residual stream -> full feature row (the view
        every column-parallel unit GEMM consumes)."""
        if self.feature_tp and self.tp > 1:
            return B.spike_allgather(x, self.tp_axis)
        return x

    def shard_stream(self, x):
        """Replicated spikes -> this shard's feature block (lands the
        tokenizer output onto the feature-sharded residual stream)."""
        if self.feature_tp and self.tp > 1:
            return B.spike_shard(x, self.tp_axis, self.tp)
        return x

    def gather_heads(self, x):
        """Locally-produced spike features -> full feature row (the
        post-attention / post-fc1 all-gather; packed words on the wire
        under packed backends)."""
        if self.tp > 1:
            return B.spike_allgather(x, self.tp_axis)
        return x

    def wrap_ssa(self, ssa):
        """LM head parallelism: run the walker's attention on this shard's
        head block only (binary-spike SSA is exact integer arithmetic per
        head, so head-local compute is bit-exact)."""
        if self.feature_tp or self.tp == 1:
            return ssa

        def sharded_ssa(q, k, v):
            h = (q.words if isinstance(q, packing.PackedSpikes) else q).shape[2]
            idx = jax.lax.axis_index(self.tp_axis)
            h_loc = h // self.tp
            return ssa(_slice_heads(q, idx, h_loc),
                       _slice_heads(k, idx, h_loc),
                       _slice_heads(v, idx, h_loc))

        return sharded_ssa


_NULL_OPS = _MeshOps()


def _tokenizer_exec(meta: PlanMeta, tok_params, image):
    """image: (B, H, W, C) analog in [0, 1] -> spikes (T, B, N, D)."""
    cfg = meta.cfg
    x = None
    for i, (stage, p) in enumerate(zip(meta.tok_stages, tok_params)):
        with jax.named_scope(f"stage{i}"):
            if stage.encode:
                # encoding layer: analog conv once, broadcast across T (the
                # input is not binary, so it stays on the jnp conv even under
                # the spike-GEMM backend)
                y = cnn.conv_apply(p, image)
                if stage.pool:
                    y = cnn.maxpool(y)
                drive = jnp.broadcast_to(y[None], (cfg.t,) + y.shape)
            else:
                flat = cnn.fold_time(x)      # (T*B, H, W, C): one weight read
                y = B.conv3x3_apply(meta.backend, p, flat)
                if stage.pool:
                    y = cnn.maxpool(y)
                drive = cnn.unfold_time(y, cfg.t)
            x = _lif(meta, drive)
    t, b, h, w, d = x.shape
    return x.reshape(t, b, h * w, d)


def _unit_linear(meta: PlanMeta, p, x):
    """Tick-batched folded linear on (T, B, N, Din) spikes."""
    t, b, n, _ = x.shape
    y = B.linear_apply(meta.backend, p, x.reshape(t * b * n, -1))
    return y.reshape(t, b, n, -1)


def _block_exec(meta: PlanMeta, bparams, x, *, ops: _MeshOps = _NULL_OPS,
                xg=None):
    """One block in deploy form. x: (T, B, N, D) spikes (the local feature
    block under a feature-sharded mesh; ``xg`` caches the gathered full
    row per residual-stream version -- callers that already hold the full
    row, like the first block after the replicated tokenizer, pass it in
    so no redundant gather runs)."""
    cfg = meta.cfg
    res = connective(cfg.residual)  # only reached for residual="add"
    acts: dict = {}
    h = None
    for u in meta.block_units:
        if u.role == "attn_out":
            heads = ops.local_heads(cfg.num_heads)
            with jax.named_scope("ssa"):
                attn = B.ssa_apply(
                    meta.backend,
                    split_heads(acts["q"], heads),
                    split_heads(acts["k"], heads),
                    split_heads(acts["v"], heads),
                    scale=cfg.attn_scale, ordering=cfg.attn_ordering)
            with jax.named_scope("attn_lif"):
                attn = _lif(meta, merge_heads(attn))      # attn spikes
        with jax.named_scope(u.name):
            if u.role == "qkv":
                if xg is None:
                    xg = ops.gather_stream(x)
                acts[u.name] = _lif(meta, _unit_linear(meta, bparams[u.name], xg))
                continue
            if u.role == "attn_out":
                drive = _unit_linear(meta, bparams[u.name], ops.gather_heads(attn))
            elif u.role == "mlp_hidden":
                if xg is None:
                    xg = ops.gather_stream(x)
                h = _lif(meta, _unit_linear(meta, bparams[u.name], xg))
                continue
            elif u.role == "mlp_out":
                drive = _unit_linear(meta, bparams[u.name], ops.gather_heads(h))
            else:
                raise ValueError(f"unknown unit role: {u.role}")
            if u.fuse_residual:      # AND-NOT inside the LIF epilogue
                x = _lif(meta, drive, iand_skip=x)
            else:
                x = res(x, _lif(meta, drive))
        xg = None                # the residual stream advanced: stale gather
    return x


# -- packed datapath ---------------------------------------------------------

def _tokenizer_exec_packed(meta: PlanMeta, tok_params, image) -> packing.PackedSpikes:
    """image: (B, H, W, C) analog -> packed spikes, words (W, B, N, D)."""
    cfg = meta.cfg
    xp = None
    for i, (stage, p) in enumerate(zip(meta.tok_stages, tok_params)):
        with jax.named_scope(f"stage{i}"):
            if stage.encode:
                # analog encoding conv: same as the dense path (input not binary)
                y = cnn.conv_apply(p, image)
                if stage.pool:
                    y = cnn.maxpool(y)
                drive = jnp.broadcast_to(y[None], (cfg.t,) + y.shape)
            else:
                drive = B.conv3x3_apply_packed(meta.backend, p, xp)  # (T,B,H,W,C)
                if stage.pool:
                    drive = cnn.unfold_time(cnn.maxpool(cnn.fold_time(drive)),
                                            cfg.t)
            xp = _lif(meta, drive, pack_output=True)
    w, b, h, wd, d = xp.words.shape
    return xp.reshape_elems(b, h * wd, d)


def _unit_linear_packed(meta: PlanMeta, p, xp: packing.PackedSpikes):
    """Packed-operand folded linear: words (W, B, N, Din) -> drive (T, B, N, Dout)."""
    return B.linear_apply_packed(meta.backend, p, xp)


def _block_exec_packed(meta: PlanMeta, bparams, xp: packing.PackedSpikes, *,
                       ops: _MeshOps = _NULL_OPS, xg=None):
    """One block on packed activations.  Only reached for residual='iand'
    (compile_plan rejects packed ADD plans), so every residual join is the
    bitwise AND-NOT in a LIF epilogue.  Under a mesh every cross-shard
    gather here moves uint32 words (``backend.word_allgather``); ``xg`` as
    in :func:`_block_exec`."""
    cfg = meta.cfg
    acts: dict = {}
    h = None
    for u in meta.block_units:
        if u.role == "attn_out":
            # q/k/v stay packed and token-major; the backend feeds the words
            # straight to the head-merged SSA kernel (or splits heads and
            # unpacks at ITS op boundary on the other routes -- never here)
            with jax.named_scope("ssa"):
                attn = B.ssa_apply_packed_merged(
                    meta.backend, acts["q"], acts["k"], acts["v"],
                    heads=ops.local_heads(cfg.num_heads),
                    scale=cfg.attn_scale, ordering=cfg.attn_ordering)
            with jax.named_scope("attn_lif"):
                attn_sp = _lif(meta, attn, pack_output=True)
        with jax.named_scope(u.name):
            if u.role == "qkv":
                if xg is None:
                    xg = ops.gather_stream(xp)
                acts[u.name] = _lif(
                    meta, _unit_linear_packed(meta, bparams[u.name], xg),
                    pack_output=True)
                continue
            if u.role == "attn_out":
                drive = _unit_linear_packed(meta, bparams[u.name],
                                            ops.gather_heads(attn_sp))
            elif u.role == "mlp_hidden":
                if xg is None:
                    xg = ops.gather_stream(xp)
                h = _lif(meta, _unit_linear_packed(meta, bparams[u.name], xg),
                         pack_output=True)
                continue
            elif u.role == "mlp_out":
                drive = _unit_linear_packed(meta, bparams[u.name],
                                            ops.gather_heads(h))
            else:
                raise ValueError(f"unknown unit role: {u.role}")
            xp = _lif(meta, drive, iand_skip=xp, pack_output=True)
        xg = None                # the residual stream advanced: stale gather
    return xp


def _head_packed(meta: PlanMeta, head_params, xp: packing.PackedSpikes):
    """Rate decoding by popcount: mean over (T, tokens) without unpacking."""
    counts = packing.spike_counts(xp)                 # (B, N, D) uint32
    n = xp.elem_shape[1]
    feats = jnp.sum(counts, axis=1, dtype=jnp.uint32).astype(jnp.float32)
    feats = feats / jnp.float32(xp.t * n)
    return cnn.linear_apply(head_params, feats)


# -- spiking LM ---------------------------------------------------------------

def _lm_unit(meta: PlanMeta, p, x):
    """Tick-batched folded Linear+RMSNorm unit on (T, B, S, Din) spikes."""
    t, b, s, _ = x.shape
    y = B.normed_linear_apply(meta.backend, p, x.reshape(t * b * s, -1),
                              eps=meta.cfg.norm_eps)
    return y.reshape(t, b, s, -1)


def _lm_full_ssa(meta: PlanMeta, packed: bool, q, k, v):
    """The walker's default attention: full causal SSA on the plan's backend
    (split q/k/v in, dense drive out)."""
    op = B.ssa_apply_packed if packed else B.ssa_apply
    return op(meta.backend, q, k, v, scale=meta.cfg.attn_scale,
              ordering=meta.cfg.attn_ordering, causal=True)


def _lm_block_exec(meta: PlanMeta, bparams, x, *, packed: bool, ssa=None,
                   lif_occupancy=None, ops: _MeshOps = _NULL_OPS):
    """One spiking-LM decoder block in deploy form: x is (T, B, S, D) spikes
    dense, a ``PackedSpikes`` (words (W, B, S, D)) when ``packed``.

    ONE walker for every datapath -- same unit walk as the vision block, with
    causal SSA and every residual join fused (the LM is all-spike: IAND
    only); ``packed`` only swaps the unit/split ops and makes the LIF
    epilogues emit words, and ``ssa`` (a callable over the head-split q/k/v,
    defaulting to the full causal SSA) is the ONLY thing the incremental
    prefill/decode executors replace -- so the full, prefill, and per-token
    step plans cannot structurally diverge."""
    cfg = meta.cfg
    unit = _lm_unit_packed if packed else _lm_unit
    split = split_heads_packed if packed else split_heads
    if ssa is None:
        ssa = functools.partial(_lm_full_ssa, meta, packed)
    ssa = ops.wrap_ssa(ssa)     # head-sharded mesh: local head block only
    acts: dict = {}
    h = None
    for u in meta.block_units:
        if u.role == "qkv":
            acts[u.name] = _lif(meta, unit(meta, bparams[u.name], x),
                                pack_output=packed, occupancy=lif_occupancy)
            continue
        if u.role == "attn_out":
            attn = ssa(
                split(acts["q"], cfg.num_heads),
                split(acts["k"], cfg.num_heads),
                split(acts["v"], cfg.num_heads))
            attn_sp = _lif(meta, merge_heads(attn), pack_output=packed,
                           occupancy=lif_occupancy)
            # the LM's one cross-device spike edge: local-head attention
            # spikes -> the full feature row the replicated proj consumes
            drive = unit(meta, bparams[u.name], ops.gather_heads(attn_sp))
        elif u.role == "mlp_hidden":
            h = _lif(meta, unit(meta, bparams[u.name], x), pack_output=packed,
                     occupancy=lif_occupancy)
            continue
        elif u.role == "mlp_out":
            drive = unit(meta, bparams[u.name], h)
        else:
            raise ValueError(f"unknown unit role: {u.role}")
        # AND-NOT inside the LIF epilogue (bitwise ``skip & ~s`` on words)
        x = _lif(meta, drive, iand_skip=x, pack_output=packed,
                 occupancy=lif_occupancy)
    return x


def _lm_unit_packed(meta: PlanMeta, p, xp: packing.PackedSpikes):
    """Packed-operand folded Linear+RMSNorm: words (W, B, S, Din) -> drive
    (T, B, S, Dout)."""
    return B.normed_linear_apply_packed(meta.backend, p, xp,
                                        eps=meta.cfg.norm_eps)


def _lm_head(meta: PlanMeta, params, rate):
    """Rate (B, S, D) -> logits (B, S, V).

    The head normalization is the one irreducible norm of the LM plan: its
    input is the analog rate code (produced by the mean over T, not by a
    linear), so there is no weight read to fold the gain into without
    perturbing the logits bitwise.  It executes inline in the head epilogue
    via ``rmsnorm_raw`` -- the same arithmetic the train graph's (jitted,
    jaxpr-counted) ``rmsnorm_apply`` wraps."""
    from repro.models.layers import rmsnorm_raw

    normed = rmsnorm_raw(params["final_norm"], rate, eps=meta.cfg.norm_eps)
    return normed @ params["head"]["w"].astype(normed.dtype)


def _lm_embed_drive(meta: PlanMeta, embed_params, tokens):
    """tokens (B, S) -> LIF drive (T, B, S, D) from the pre-normalized
    embedding table (the embed RMSNorm was folded into the table rows at
    plan-compile time -- no norm runs here)."""
    emb = jnp.take(embed_params["table"], tokens, axis=0)
    return jnp.broadcast_to(emb[None], (meta.cfg.t,) + emb.shape)


def _lm_rate(meta: PlanMeta, params, x, *, packed: bool):
    """Spike train -> analog rate code (B, S, D): mean over T dense, popcount
    over words packed.  Packed counts are exact integers <= T, and T is a
    power of two on the supported configs, so counts/T == mean bit-for-bit."""
    if not packed:
        return x.mean(axis=0)
    dtype = params["embed"]["table"].dtype
    return packing.spike_counts(x).astype(dtype) / jnp.asarray(x.t, dtype)


def _lm_exec(meta: PlanMeta, params, tokens, *, packed: bool,
             ops: _MeshOps = _NULL_OPS):
    x = _lif(meta, _lm_embed_drive(meta, params["embed"], tokens),
             pack_output=packed)
    for bparams in params["blocks"]:
        x = _lm_block_exec(meta, bparams, x, packed=packed, ops=ops)
    return _lm_head(meta, params, _lm_rate(meta, params, x, packed=packed))


def _execute(meta: PlanMeta, params, batch, *, ops: _MeshOps = _NULL_OPS):
    if meta.family == "lm":
        tokens = batch["tokens"] if isinstance(batch, dict) else batch
        return _lm_exec(meta, params, tokens, packed=meta.backend.packed,
                        ops=ops)
    # every op runs under a named scope of the plan's layout
    # (``tokenizer/stage{i}``, ``block{i}/{unit}`` with ``block{i}/ssa`` and
    # ``block{i}/attn_lif``, ``head``), kept as the compiled HLO's
    # ``op_name`` metadata, so a device trace's operations map back to the
    # layer they serve.  Metadata only: scopes change no instruction.
    packed = meta.backend.packed
    tokenizer = _tokenizer_exec_packed if packed else _tokenizer_exec
    block = _block_exec_packed if packed else _block_exec
    with jax.named_scope("tokenizer"):
        xg = tokenizer(meta, params["tokenizer"], batch)
    x = ops.shard_stream(xg)            # land on the feature-sharded stream
    for i, bparams in enumerate(params["blocks"]):
        # the replicated tokenizer output doubles as the first block's
        # gathered view -- the tokenizer edge never crosses devices
        with jax.named_scope(f"block{i}"):
            x = block(meta, bparams, x, ops=ops, xg=xg)
        xg = None
    with jax.named_scope("head"):
        x = ops.gather_stream(x)        # replicated head reads the full row
        if packed:
            return _head_packed(meta, params["head"], x)
        feats = x.mean(axis=(0, 2))          # rate decoding over (T, tokens)
        return cnn.linear_apply(params["head"], feats)


# -- incremental LM decode ----------------------------------------------------
#
# The causal SSA has no softmax, so the linear ordering Q(K^T V) gives every
# layer an O(d^2)-per-head running state: serving never re-scores the prefix.
# ``prefill`` runs the full walker once over the prompt and captures each
# layer's K^T V state; ``decode_step`` advances one token at a cost flat in
# context length.  Everything OUTSIDE the SSA is positionally local in the LM
# block -- folded units, RMS epilogues, and the LIF chains act per token, and
# the IAND skip of a token is that same token's own residual spikes (computed
# inside the step, never carried) -- so the SSA states are the ONLY cross-
# token memory a decode needs, and stepping is bit-exact vs the full forward
# (binary spikes make the attention exact integer arithmetic; every other op
# runs row-identical at S=1).


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class DecodeState:
    """Carried state of an incremental LM decode: one (T, B, H, Dh, Dh)
    linear-SSA K^T V accumulator per layer (all T bitplanes), plus the number
    of tokens consumed.  A pytree -- flows through jitted step functions
    unchanged; constant-size at any context length (``PlanMeta.decode``
    records the geometry).

    Nothing else carries: softmax-free attention has no normalizer, so there
    is no running K-sum denominator, and the IAND skip is each token's own
    residual spikes, recomputed inside the step (the state-carry property in
    ``tests/test_lm_decode.py`` proves the states here are sufficient)."""

    kv: tuple[jax.Array, ...]        # per-layer (T, B, H, Dh, Dh)
    pos: jax.Array                   # () int32: tokens consumed so far

    def tree_flatten(self):
        return (self.kv, self.pos), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(kv=children[0], pos=children[1])


def decode_state_init(meta: PlanMeta, batch: int) -> DecodeState:
    """Zero ``DecodeState`` for ``batch`` sequences (the state ``prefill``
    starts from -- exposed for tests and empty-prompt decode)."""
    entry = _decode_entry(meta)
    return DecodeState(
        kv=tuple(jnp.zeros(s, jnp.float32) for s in entry.state_shapes(batch)),
        pos=jnp.zeros((), jnp.int32))


# -- decode-state paging (continuous batching) --------------------------------
#
# A slot batch's DecodeState is independent per batch row: the kv accumulators
# carry no cross-row terms (SSA state is per sequence) and nothing in the step
# mixes rows.  So a serving scheduler can PAGE sequences in and out of a live
# batched state -- prefill a new prompt at its own length, scatter its per-
# layer K^T V planes into a freed slot, keep stepping the one warm batch shape
# -- which is what ``launch.scheduler`` builds on.  The helpers below are the
# whole device-side contract: pure jnp index updates over the DecodeState
# pytree, jittable (slot/src may be traced), and layout-preserving -- under a
# head-sharded mesh the update touches only the batch axis, so each kv plane
# stays resident on the shard that owns its heads.


def decode_state_batch_init(meta: PlanMeta, slots: int) -> DecodeState:
    """Zero batched ``DecodeState`` for a ``slots``-wide serving batch, with a
    PER-SLOT position vector ``pos: (slots,) int32`` (slots decode at ragged
    depths under continuous batching, so a scalar token count cannot describe
    the batch; ``decode_step``'s ``pos + 1`` advances it elementwise)."""
    entry = _decode_entry(meta)
    return DecodeState(
        kv=tuple(jnp.zeros(s, jnp.float32) for s in entry.state_shapes(slots)),
        pos=jnp.zeros((slots,), jnp.int32))


def decode_state_scatter(batch_state: DecodeState, slot, seq_state: DecodeState,
                         src=0) -> DecodeState:
    """Page row ``src`` of ``seq_state`` into slot ``slot`` of a batched
    state: every per-layer kv accumulator is a ``dynamic_update_index_in_dim``
    on the batch axis (axis 1 of the (T, B, H, Dh, Dh) planes), and the
    per-slot position picks up the source's token count.  Pure and jittable --
    the admission path of the continuous scheduler."""
    row = jax.tree.map(
        lambda kv: jax.lax.dynamic_index_in_dim(kv, src, axis=1,
                                                keepdims=False),
        seq_state.kv)
    kv = jax.tree.map(
        lambda bkv, r: jax.lax.dynamic_update_index_in_dim(bkv, r, slot,
                                                           axis=1),
        batch_state.kv, row)
    src_pos = (seq_state.pos if seq_state.pos.ndim == 0
               else jax.lax.dynamic_index_in_dim(seq_state.pos, src, axis=0,
                                                 keepdims=False))
    if batch_state.pos.ndim == 0:
        raise ValueError(
            "scatter target must carry a per-slot pos vector (use "
            "decode_state_batch_init for the serving batch)")
    pos = jax.lax.dynamic_update_index_in_dim(batch_state.pos, src_pos, slot,
                                              axis=0)
    return DecodeState(kv=kv, pos=pos)


def decode_state_gather(batch_state: DecodeState, slot) -> DecodeState:
    """Slot ``slot`` of a batched state as a batch-1 ``DecodeState`` (the
    inverse of :func:`decode_state_scatter`; eviction introspection, state
    migration, and the paging round-trip tests)."""
    kv = jax.tree.map(
        lambda bkv: jax.lax.dynamic_slice_in_dim(bkv, slot, 1, axis=1),
        batch_state.kv)
    pos = (batch_state.pos if batch_state.pos.ndim == 0
           else jax.lax.dynamic_index_in_dim(batch_state.pos, slot, axis=0,
                                             keepdims=False))
    return DecodeState(kv=kv, pos=pos)


def _decode_entry(meta: PlanMeta):
    if meta.decode is None:
        raise ValueError(
            f"incremental decode is an LM-plan mode; family={meta.family!r} "
            "plans have no causal running-state decomposition")
    return meta.decode


def _prefill_ssa(meta: PlanMeta, packed: bool, out_kv: list):
    """Walker attention for prefill: full causal SSA, PLUS capture of the
    layer's end-of-prefix K^T V state -- on the linear ordering the state is
    the causal scan's own final carry (the prefix is contracted once), on
    the quadratic ordering one extra batched contraction (word-consuming
    under the closed packed boundary, op-boundary unpack otherwise)."""

    def ssa(q, k, v):
        op = B.ssa_prefill_apply_packed if packed else B.ssa_prefill_apply
        drive, state = op(meta.backend, q, k, v, scale=meta.cfg.attn_scale,
                          ordering=meta.cfg.attn_ordering)
        out_kv.append(state)
        return drive

    return ssa


def _decode_ssa(meta: PlanMeta, packed: bool, kv, out_kv: list):
    """Walker attention for one decode step: the O(d^2) state update + read
    in place of the full causal SSA (the only non-local op of the block)."""

    def ssa(q, k, v):
        step = B.ssa_decode_step_packed if packed else B.ssa_decode_step
        new_kv, drive = step(meta.backend, kv, q, k, v,
                             scale=meta.cfg.attn_scale)
        out_kv.append(new_kv)
        return drive

    return ssa


def _chunk_ssa(meta: PlanMeta, packed: bool, kv, out_kv: list):
    """Walker attention for one resumable prefill chunk: intra-chunk causal
    SSA seeded by the layer's running K^T V state (the scan carry on the
    linear ordering, a cross-prefix state read on the quadratic), capturing
    the advanced state -- :func:`_prefill_ssa` and :func:`_decode_ssa`'s
    middle ground."""

    def ssa(q, k, v):
        op = B.ssa_prefill_chunk_packed if packed else B.ssa_prefill_chunk
        drive, new_kv = op(meta.backend, kv, q, k, v,
                           scale=meta.cfg.attn_scale,
                           ordering=meta.cfg.attn_ordering)
        out_kv.append(new_kv)
        return drive

    return ssa


def _lm_prefill(meta: PlanMeta, params, tokens, *, ops: _MeshOps = _NULL_OPS):
    """tokens (B, S) -> (logits (B, S, V), DecodeState after the prompt).

    Under a head-sharded mesh the captured K^T V states are the LOCAL head
    block's (the walker's ssa runs inside ``ops.wrap_ssa``), so each layer's
    accumulator lives on its owning shard -- decode never gathers state."""
    packed = meta.backend.packed
    _decode_entry(meta)
    x = _lif(meta, _lm_embed_drive(meta, params["embed"], tokens),
             pack_output=packed)
    kvs: list = []
    for bparams in params["blocks"]:
        x = _lm_block_exec(meta, bparams, x, packed=packed,
                           ssa=_prefill_ssa(meta, packed, kvs), ops=ops)
    logits = _lm_head(meta, params, _lm_rate(meta, params, x, packed=packed))
    state = DecodeState(kv=tuple(kvs),
                        pos=jnp.asarray(tokens.shape[1], jnp.int32))
    return logits, state


def _lm_prefill_chunk(meta: PlanMeta, params, state: DecodeState, tokens, *,
                      ops: _MeshOps = _NULL_OPS):
    """One prefill chunk: tokens (B, C) of the prompt's NEXT C tokens ->
    (logits (B, C, V), advanced DecodeState).

    Chained over a prompt split any way, the per-chunk logits concatenate to
    :func:`_lm_prefill`'s and the final state is bit-equal -- everything in
    the block except SSA is positionally local, and the SSA carry is exact
    integer arithmetic on binary spikes.  The chunk's jaxpr mentions only C,
    never the full prompt length, so a 500k prompt runs as S/C warm-shaped
    steps with memory flat in S (the flatness check in the bench asserts
    this on the jaxpr)."""
    packed = meta.backend.packed
    entry = _decode_entry(meta)
    if len(state.kv) != entry.num_layers:
        raise ValueError(
            f"DecodeState carries {len(state.kv)} layer states, plan has "
            f"{entry.num_layers} layers")
    x = _lif(meta, _lm_embed_drive(meta, params["embed"], tokens),
             pack_output=packed)
    kvs: list = []
    for bparams, kv in zip(params["blocks"], state.kv):
        x = _lm_block_exec(meta, bparams, x, packed=packed,
                           ssa=_chunk_ssa(meta, packed, kv, kvs), ops=ops)
    logits = _lm_head(meta, params, _lm_rate(meta, params, x, packed=packed))
    return logits, DecodeState(kv=tuple(kvs),
                               pos=state.pos + tokens.shape[1])


def _lm_decode_step(meta: PlanMeta, params, state: DecodeState, token, *,
                    ops: _MeshOps = _NULL_OPS):
    """One generated token: (B,) int32 -> (logits (B, V), advanced state).

    The step's jaxpr mentions no prefix-length dimension at all -- its cost
    is O(d^2) per layer, flat in S (the property the decode test suite pins
    with an op-count check)."""
    packed = meta.backend.packed
    entry = _decode_entry(meta)
    if len(state.kv) != entry.num_layers:
        raise ValueError(
            f"DecodeState carries {len(state.kv)} layer states, plan has "
            f"{entry.num_layers} layers")
    tokens = token.reshape(token.shape[0], 1)          # (B,) -> (B, 1)
    # occupancy=False: no S=1 consumer reads the map (the sparse decode step
    # derives word liveness in-register; the GEMM skip granule needs >= 8
    # token rows), so the pack epilogues skip the popcount pass per step
    if packed and "train_words" in params["embed"]:
        # sparse train re-use (core.bundling.attach_train_table): the
        # encoding train is a pure function of the embedding row, so the
        # step fetches the token's precomputed packed train instead of
        # re-running the T-step encoding LIF per generated token
        words = jnp.take(params["embed"]["train_words"], tokens, axis=1)
        x = packing.PackedSpikes(words, meta.cfg.t)     # (W, B, 1, D)
    else:
        x = _lif(meta, _lm_embed_drive(meta, params["embed"], tokens),
                 pack_output=packed, occupancy=False)
    kvs: list = []
    for bparams, kv in zip(params["blocks"], state.kv):
        x = _lm_block_exec(meta, bparams, x, packed=packed,
                           ssa=_decode_ssa(meta, packed, kv, kvs),
                           lif_occupancy=False, ops=ops)
    logits = _lm_head(meta, params, _lm_rate(meta, params, x, packed=packed))
    return logits[:, 0], DecodeState(kv=tuple(kvs), pos=state.pos + 1)


# -- sharded executor construction -------------------------------------------


def _sharded_context(meta: PlanMeta):
    """(mesh, data_size, _MeshOps) of a sharded plan: the concrete host mesh
    (largest feasible shape if the host is smaller than the plan asked for --
    the ops table reads the ACTUAL axis sizes, so a shrunk mesh still runs
    correctly) plus the cross-shard op table the walkers thread."""
    scfg = meta.sharding
    mesh = scfg.build_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes.get(scfg.model_axis, 1)
    ops = _MeshOps(tp_axis=scfg.model_axis, tp=tp,
                   feature_tp=(meta.family != "lm"))
    return mesh, sizes.get(scfg.data_axis, 1), ops


def _param_specs(meta: PlanMeta, params):
    """PartitionSpec pytree mirroring the plan params.  LM plans replicate
    every unit (the TP axis lives in the SSA heads); vision plans shard each
    block unit by its layout ``w_axes`` through the plan's rules (tokenizer
    and head replicated)."""
    from jax.sharding import PartitionSpec as P

    specs = jax.tree_util.tree_map(lambda _: P(), params)
    if meta.family == "lm":
        return specs
    rules = meta.sharding.rules_dict
    specs["blocks"] = tuple(
        {u.name: B.unit_partition_specs(u, bp[u.name], rules)
         for u in meta.block_units}
        for bp in params["blocks"])
    return specs


def _shard_mapped(meta: PlanMeta, body, batch_specs, out_specs):
    """Wrap a walker body in ``shard_map`` on the plan's mesh: params by
    :func:`_param_specs`, batch/state/outputs by the given specs.  Explicit
    shard_map (not GSPMD constraints) so the per-op collectives are exactly
    the ones the walkers emit -- which is what makes 'no unpack crosses
    devices' checkable on the jaxpr (``analysis.collective_report``)."""
    from jax.experimental.shard_map import shard_map

    mesh, _, ops = _sharded_context(meta)

    def fn(params, *args):
        in_specs = (_param_specs(meta, params),) + batch_specs
        sharded = shard_map(functools.partial(body, ops=ops), mesh=mesh,
                            in_specs=in_specs, out_specs=out_specs,
                            check_rep=False)
        return sharded(params, *args)

    return fn


def _decode_state_specs(meta: PlanMeta):
    from jax.sharding import PartitionSpec as P

    scfg = meta.sharding
    # per-layer (T, B, H, Dh, Dh): batch over data, heads over model -- each
    # accumulator lives on the shard that owns its heads, for good
    kv = P(None, scfg.data_axis, scfg.model_axis, None, None)
    return DecodeState(kv=tuple(kv for _ in range(meta.num_layers)), pos=P())


def make_prefill_fn(plan: DeployPlan):
    """Pure ``fn(params, tokens) -> (logits, DecodeState)`` (jit-friendly;
    LM plans only).  Sharded plans return the shard_map-wrapped executor on
    the plan's mesh (``DecodeState`` sharded over heads x batch)."""
    meta = plan.meta
    _decode_entry(meta)
    if meta.sharding is None:
        return functools.partial(_lm_prefill, meta)
    from jax.sharding import PartitionSpec as P

    da = meta.sharding.data_axis
    return _shard_mapped(
        meta, functools.partial(_lm_prefill, meta),
        batch_specs=(P(da, None),),
        out_specs=(P(da, None, None), _decode_state_specs(meta)))


def make_prefill_chunk_fn(plan: DeployPlan):
    """Pure ``fn(params, state, tokens) -> (logits, state')`` scoring the
    prompt's next chunk against the running state -- ONE warm shape per
    chunk size serves any prompt length.  Sharded plans run under shard_map
    with the state resident on its head shard, like the decode step."""
    meta = plan.meta
    _decode_entry(meta)
    if meta.sharding is None:
        return functools.partial(_lm_prefill_chunk, meta)
    from jax.sharding import PartitionSpec as P

    da = meta.sharding.data_axis
    state_specs = _decode_state_specs(meta)
    return _shard_mapped(
        meta, functools.partial(_lm_prefill_chunk, meta),
        batch_specs=(state_specs, P(da, None)),
        out_specs=(P(da, None, None), state_specs))


def make_decode_step_fn(plan: DeployPlan):
    """Pure ``fn(params, state, token) -> (logits, state')`` -- ONE warm
    shape per batch size serves the whole decode, at any context length.
    Sharded plans step under shard_map with the K^T V state resident on its
    head shard (no state movement per token)."""
    meta = plan.meta
    _decode_entry(meta)
    if meta.sharding is None:
        return functools.partial(_lm_decode_step, meta)
    from jax.sharding import PartitionSpec as P

    da = meta.sharding.data_axis
    state_specs = _decode_state_specs(meta)
    return _shard_mapped(
        meta, functools.partial(_lm_decode_step, meta),
        batch_specs=(state_specs, P(da)),
        out_specs=(P(da, None), state_specs))


def prefill(plan: DeployPlan, tokens) -> tuple[jax.Array, DecodeState]:
    """One-shot convenience: score a prompt and initialise decode state."""
    return make_prefill_fn(plan)(plan.params, jnp.asarray(tokens))


def prefill_chunk(plan: DeployPlan, state: DecodeState,
                  tokens) -> tuple[jax.Array, DecodeState]:
    """One-shot convenience: consume the prompt's next chunk resumably."""
    return make_prefill_chunk_fn(plan)(plan.params, state,
                                       jnp.asarray(tokens))


def decode_step(plan: DeployPlan, state: DecodeState, token):
    """One-shot convenience: advance the decode by one token."""
    return make_decode_step_fn(plan)(plan.params, state, jnp.asarray(token))


def make_apply_fn(plan: DeployPlan):
    """Pure ``fn(params, batch) -> logits`` with the plan's static metadata
    closed over (jit-friendly: arrays stay arguments, not constants).
    ``batch`` is an image batch for vision plans, a (B, S) token array (or a
    ``{"tokens": ...}`` dict) for LM plans.

    Plans compiled with ``mesh=`` return the shard_map-wrapped executor:
    batch data-parallel over the mesh's data axis (the global batch must
    divide by it), the family's tensor-parallel schedule over the model
    axis, bit-exact vs the unsharded plan."""
    meta = plan.meta
    if meta.sharding is None:
        return functools.partial(_execute, meta)
    from jax.sharding import PartitionSpec as P

    da = meta.sharding.data_axis
    if meta.family == "lm":
        body_specs = (P(da, None),)              # (B, S) tokens
        out_specs = P(da, None, None)            # (B, S, V) logits

        def body(params, tokens, *, ops):
            return _execute(meta, params, tokens, ops=ops)

        sharded = _shard_mapped(meta, body, body_specs, out_specs)

        def fn(params, batch):
            tokens = batch["tokens"] if isinstance(batch, dict) else batch
            return sharded(params, tokens)

        return fn
    body_specs = (P(da, None, None, None),)      # (B, H, W, C) images
    out_specs = P(da, None)                      # (B, classes) logits
    return _shard_mapped(meta, functools.partial(_execute, meta),
                         body_specs, out_specs)


def place_params(plan: DeployPlan) -> DeployPlan:
    """Commit a sharded plan's params to its mesh, each leaf with the
    PartitionSpec its executor reads it under, so serving moves no weights
    per call.  Plans compiled without ``mesh=`` come back unchanged."""
    meta = plan.meta
    if meta.sharding is None:
        return plan
    from jax.sharding import NamedSharding

    mesh, _, _ = _sharded_context(meta)
    specs = _param_specs(meta, plan.params)
    params = jax.tree_util.tree_map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        plan.params, specs)
    return DeployPlan(meta=meta, params=params)


def apply(plan: DeployPlan, batch) -> jax.Array:
    """One-shot convenience: run the plan on a batch (images or tokens)."""
    return make_apply_fn(plan)(plan.params, batch)

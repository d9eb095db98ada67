"""Deploy-plan compiler: (params, state, cfg) -> the accelerator's view.

``compile_plan`` performs the paper's deploy-time transformations once, ahead
of serving.  It covers two config families:

* vision (``SpikformerConfig``-shaped, anything with ``tokenizer_config``):
  every Conv+BN pair of the tokenizer is folded into a single (w, b) via
  ``fold_conv_bn``, every Linear+BN pair of every block via
  ``fold_linear_bn`` -- the BN disappears from the graph entirely;
* spiking LM (``ArchConfig`` with ``spiking=True``): every Linear+RMSNorm
  unit is folded via ``fold_linear_rmsnorm`` (gain into the GEMM weights,
  gain-free normalizer left as the unit epilogue), the embedding norm is
  folded INTO the embedding table at compile time (rows are normalized
  independently, so the whole table pre-normalizes exactly), and the SSA is
  causal-masked with the plan-level ``ordering`` choosing quadratic
  (QK^T)V vs chunked-linear Q(K^TV) dataflow.

In both families the block layout records which LIFs fuse the AND-NOT
residual into their epilogue (execution never runs a standalone IAND pass)
and the backend (jnp oracle vs Pallas kernels, interpret vs compiled, packed
spikes) is a plan property, not a per-call-site flag.

Each ``compile_plan`` call runs inside the profiler span
``engine.compile_plan`` and reports its host wall time as the JAX monitoring
duration event :data:`FOLD_EVENT`, so a start-up profile or an event listener
sees the fold apart from compilation and warm-up.

The plan splits into hashable static metadata (:class:`PlanMeta`) and a plain
pytree of folded arrays, so executors jit cleanly with the metadata closed
over and the arrays as arguments.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any

import jax

from repro.core import nn as cnn
from repro.engine.backend import Backend, resolve
from repro.engine.layout import (
    ProjUnit, TokStage, block_layout, lm_block_layout, tokenizer_layout,
)


@dataclass(frozen=True)
class ShardingCfg:
    """Mesh-awareness of a deploy plan: mesh axes plus the logical-axis rules
    that resolve the layout annotations (``ProjUnit.w_axes`` /
    ``SpikeEdge.axes``) into ``PartitionSpec``s.

    Hashable (rules stored as a sorted item tuple), so it rides on
    :class:`PlanMeta` and jitted executors cache per sharding.  The rules
    come from ``distributed.sharding.engine_rules(family, preset=...)`` --
    the same rules dict the training substrate uses, with the engine
    families' bit-exactness overrides applied.  The concrete ``jax.Mesh`` is
    NOT stored here (device objects are process state); the executor builds
    it from ``mesh_shape`` via ``launch.mesh.make_host_mesh`` at
    ``make_*_fn`` time, so a plan compiled for ``(2, 2)`` still runs -- at
    reduced parallelism, with a warning -- on a host with fewer devices.
    """

    mesh_shape: tuple[int, int] = (1, 1)
    mesh_axes: tuple[str, str] = ("data", "model")
    preset: str = "base"
    rules: tuple[tuple[str, Any], ...] = field(default=(), repr=False)

    @property
    def data_axis(self) -> str:
        return self.mesh_axes[0]

    @property
    def model_axis(self) -> str:
        return self.mesh_axes[1]

    @property
    def data(self) -> int:
        return self.mesh_shape[0]

    @property
    def model(self) -> int:
        return self.mesh_shape[1]

    @property
    def rules_dict(self) -> dict[str, Any]:
        return dict(self.rules)

    def build_mesh(self):
        """Concrete host mesh for this cfg (largest feasible shape if the
        host has fewer devices than ``mesh_shape`` asks for)."""
        from repro.launch.mesh import make_host_mesh

        return make_host_mesh(self.mesh_shape, self.mesh_axes)


def _resolve_sharding(mesh, family: str) -> ShardingCfg | None:
    """Coerce a user-facing mesh spec -- ShardingCfg | "dxm" | (d, m) | None
    -- into a ShardingCfg with the family's engine rules resolved."""
    from repro.distributed import sharding as shd

    if mesh is None:
        return None
    if isinstance(mesh, ShardingCfg):
        cfg = mesh
    else:
        if isinstance(mesh, str):
            try:
                d, m = (int(p) for p in mesh.lower().split("x"))
            except ValueError:
                raise ValueError(
                    f"mesh spec must be 'dxm' (e.g. '2x1'), got {mesh!r}")
            shape = (d, m)
        else:
            shape = tuple(int(s) for s in mesh)
            if len(shape) != 2:
                raise ValueError(
                    f"mesh shape must be (data, model), got {shape}")
        cfg = ShardingCfg(mesh_shape=shape)
    if min(cfg.mesh_shape) < 1:
        raise ValueError(f"mesh axes must be >= 1, got {cfg.mesh_shape}")
    if not cfg.rules:
        rules = shd.engine_rules(family, preset=cfg.preset)
        cfg = ShardingCfg(
            mesh_shape=cfg.mesh_shape, mesh_axes=cfg.mesh_axes,
            preset=cfg.preset, rules=tuple(sorted(rules.items())))
    return cfg


def _validate_sharding(scfg: ShardingCfg, cfg, family: str) -> None:
    """Divisibility the bit-exact sharded schedules require.  Batch
    divisibility by the data axis is checked at shard_map call time (batch
    size is not a plan property)."""
    m = scfg.model
    if m == 1:
        return
    heads = cfg.num_heads
    if heads % m:
        raise ValueError(
            f"model axis {m} must divide num_heads={heads} (the SSA runs "
            "per-head-local on its shard)")
    if family == "vision":
        d = cfg.embed_dim
        hidden = int(cfg.embed_dim * cfg.mlp_ratio)
        if d % m or hidden % m:
            raise ValueError(
                f"model axis {m} must divide embed_dim={d} and the MLP "
                f"hidden dim {hidden} (column-parallel unit shards)")


@dataclass(frozen=True)
class LMDeployCfg:
    """Deploy view of a spiking-LM ``ArchConfig``: exposes the attribute
    names the executor shares with ``SpikformerConfig`` (``t``,
    ``chain_len``, ``theta``, ...), plus the plan-level attention ordering.
    The wrapped ``ArchConfig`` stays reachable as ``arch``."""

    arch: Any                          # ArchConfig (frozen dataclass)
    attn_ordering: str = "quadratic"   # "quadratic" | "linear" (chunked scan)

    @property
    def t(self) -> int:
        return self.arch.spike_t

    @property
    def chain_len(self):
        return self.arch.spike_chain_len

    @property
    def theta(self) -> float:
        from repro.core.lif import THETA_DEFAULT

        return THETA_DEFAULT

    @property
    def lam(self) -> float:
        from repro.core.lif import LAM_DEFAULT

        return LAM_DEFAULT

    @property
    def lif_schedule(self) -> str:
        return "parallel"

    @property
    def attn_scale(self) -> float:
        from repro.models.spiking_lm import ATTN_SCALE

        return ATTN_SCALE

    @property
    def norm_eps(self) -> float:
        return self.arch.norm_eps

    @property
    def num_heads(self) -> int:
        return self.arch.num_heads

    @property
    def num_layers(self) -> int:
        return self.arch.num_layers

    @property
    def d_model(self) -> int:
        return self.arch.d_model

    @property
    def d_ff(self) -> int:
        return self.arch.d_ff

    @property
    def residual(self) -> str:
        return "iand"                  # the LM is all-spike by construction


@dataclass(frozen=True)
class DecodeEntry:
    """Static description of a plan's incremental-decode entry point.

    LM plans decode with an O(d^2)-per-head running K^T V state instead of
    re-scoring the prefix (legal because the spiking attention has no
    softmax): ``engine.prefill`` initialises a ``DecodeState`` from the
    prompt, ``engine.decode_step`` advances it one token at a time at a cost
    independent of context length.  This entry records the state geometry --
    one (T, B, H, Dh, Dh) accumulator per layer."""

    num_layers: int
    t: int                             # time steps (the bitplane axis)
    num_heads: int
    head_dim: int

    def state_shapes(self, batch: int) -> tuple[tuple[int, ...], ...]:
        """Per-layer SSA-state shapes of a ``DecodeState`` at this batch."""
        shp = (self.t, batch, self.num_heads, self.head_dim, self.head_dim)
        return tuple(shp for _ in range(self.num_layers))

    def state_bytes(self, batch: int, itemsize: int = 4) -> int:
        """Decode-state footprint: constant in context length (the number the
        500k-token serving claim rests on -- a full-attention KV cache grows
        as S * D, this state never grows)."""
        return sum(
            itemsize * s[0] * s[1] * s[2] * s[3] * s[4]
            for s in self.state_shapes(batch))

    def max_slots(self, budget_bytes: int, itemsize: int = 4) -> int:
        """Largest slot count whose batched ``DecodeState`` fits in
        ``budget_bytes`` -- the capacity planning number of the continuous-
        batching scheduler (state is per-slot linear: no context-length term,
        so the answer is exact, not an estimate)."""
        per_slot = self.state_bytes(1, itemsize)
        return budget_bytes // per_slot if per_slot else 0


@dataclass(frozen=True)
class PlanMeta:
    """Static (hashable) half of a deploy plan."""

    cfg: Any                          # SpikformerConfig | LMDeployCfg (frozen)
    backend: Backend
    tok_stages: tuple[TokStage, ...]
    block_units: tuple[ProjUnit, ...]
    num_layers: int
    family: str = "vision"            # "vision" | "lm"
    bundle: Any = None                # core.bundling.BundleInfo | None
    sharding: ShardingCfg | None = None   # None = single-device plan

    @property
    def decode(self) -> DecodeEntry | None:
        """Incremental-decode entry point: present on every LM plan (the
        causal SSA admits the O(d^2) linear-ordering state in either plan
        ordering -- stepping is bit-exact vs both), absent on vision plans
        (non-causal attention has no running-state decomposition)."""
        if self.family != "lm":
            return None
        cfg = self.cfg
        return DecodeEntry(
            num_layers=self.num_layers, t=cfg.t, num_heads=cfg.num_heads,
            head_dim=cfg.d_model // cfg.num_heads)


@dataclass(frozen=True)
class DeployPlan:
    meta: PlanMeta
    params: dict                      # folded-weight pytree

    @property
    def cfg(self):
        return self.meta.cfg

    @property
    def backend(self) -> Backend:
        return self.meta.backend


FOLD_EVENT = "/repro/engine/compile_plan"


def _timed_fold(fold):
    """Run ``fold`` inside the ``engine.compile_plan`` span and record its
    host wall time as :data:`FOLD_EVENT`.  The fold's eager ops dispatch
    asynchronously: device work still pending when it returns lands in
    whatever next waits on the plan."""

    @functools.wraps(fold)
    def timed(*args, **kwargs):
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation("engine.compile_plan"):
            plan = fold(*args, **kwargs)
        jax.monitoring.record_event_duration_secs(
            FOLD_EVENT, time.perf_counter() - start)
        return plan

    return timed


@_timed_fold
def compile_plan(params, state, cfg, *, backend="jnp",
                 ordering: str | None = None, checkpoint: str | None = None,
                 bundle: float | None = None, mesh=None) -> DeployPlan:
    """Fold a trained (params, state, cfg) into a deploy plan.

    ``backend``: Backend | "jnp" | "pallas" | bool (legacy ``use_kernel``).
    ``ordering`` selects the LM plan's causal-SSA dataflow ("quadratic" |
    "linear"); vision plans take it from ``cfg.attn_ordering`` instead.
    ``checkpoint``: optional ``repro.checkpoint`` directory -- the trained
    arrays are restored into the passed ``params``/``state`` skeleton
    (shapes/dtypes/structure come from the skeleton, values from disk)
    before folding, so serving goes checkpoint -> plan without a separate
    restore step.
    ``bundle``: optional max-abs logit-error budget for the embedding
    row-bundling transform (:mod:`repro.core.bundling`; LM plans only;
    ``0.0`` = exact duplicate-train dedup).
    ``mesh``: optional :class:`ShardingCfg` | ``"dxm"`` | ``(data, model)``
    -- makes the plan mesh-aware: the executors run under ``shard_map`` on a
    (data, model) host mesh, batch data-parallel over ``data`` and the
    family's tensor-parallel schedule over ``model`` (vision: column-parallel
    units + feature-sharded residual stream; LM: head-sharded SSA + decode
    state), with every cross-device spike edge a packed-word all-gather under
    packed backends.  Bit-exact vs the ``mesh=None`` plan by construction.
    """
    if checkpoint is not None:
        from repro.checkpoint import checkpoint as ckpt

        target = (params if state is None
                  else {"params": params, "state": state})
        restored, _manifest = ckpt.restore(checkpoint, target)
        if state is None:
            params = restored
        else:
            params, state = restored["params"], restored["state"]
    if not hasattr(cfg, "tokenizer_config"):
        plan = _compile_lm_plan(params, state, cfg, backend=backend,
                                ordering=ordering or "quadratic",
                                mesh=mesh)
        if bundle is not None:
            from repro.core import bundling

            plan = bundling.bundle(plan, budget=bundle)
        if plan.meta.backend.sparse:
            # sparse train re-use: precompute every vocab row's packed
            # encoding train so the decode step fetches instead of re-running
            # the T-step encoding LIF per generated token
            from repro.core import bundling

            plan = bundling.attach_train_table(plan)
        return plan
    if bundle is not None:
        raise ValueError(
            "row bundling applies to LM embedding tables only; vision plans "
            "have no token-row/spike-train factorisation to bundle")
    if ordering is not None:
        raise ValueError(
            "ordering is a plan-compile choice only for LM configs; vision "
            "plans read cfg.attn_ordering")
    be = resolve(backend)
    if be.packed and cfg.residual != "iand":
        raise ValueError(
            "packed backends require residual='iand': the ADD residual sums "
            "spike trains into non-binary tensors, which cannot be bit-packed")
    scfg = _resolve_sharding(mesh, "vision")
    if scfg is not None:
        _validate_sharding(scfg, cfg, "vision")
    tcfg = cfg.tokenizer_config()
    tok_stages = tokenizer_layout(tcfg)
    units = block_layout(cfg)

    tp, ts = params["tokenizer"], state["tokenizer"]
    folded_tok = tuple(
        cnn.fold_conv_bn(tp[st.conv], tp[st.bn], ts[st.bn])
        for st in tok_stages)

    folded_blocks = []
    for i in range(cfg.num_layers):
        bp, bs = params[f"block{i}"], state[f"block{i}"]
        folded_blocks.append({
            u.name: cnn.fold_linear_bn(
                bp[u.name]["lin"], bp[u.name]["bn"], bs[u.name]["bn"])
            for u in units})

    meta = PlanMeta(cfg=cfg, backend=be, tok_stages=tok_stages,
                    block_units=units, num_layers=cfg.num_layers,
                    sharding=scfg)
    plan_params = {
        "tokenizer": folded_tok,
        "blocks": tuple(folded_blocks),
        "head": params["head"],
    }
    return DeployPlan(meta=meta, params=plan_params)


def _compile_lm_plan(params, state, cfg, *, backend, ordering,
                     mesh=None) -> DeployPlan:
    """Fold a spiking-LM ``ArchConfig`` model (``models.spiking_lm`` params)
    into a deploy plan: RMSNorm gains into the GEMM weights
    (``fold_linear_rmsnorm``), the embedding norm into the embedding table,
    per-layer params unstacked from the scanned pytree."""
    from repro.models.layers import rmsnorm_apply

    if not getattr(cfg, "spiking", False):
        raise ValueError(
            f"LM deploy plans cover the spiking LM family only; config "
            f"'{getattr(cfg, 'name', cfg)}' has spiking=False")
    if state is not None:
        raise ValueError("the spiking LM carries no BN state; pass state=None")
    if ordering not in ("quadratic", "linear"):
        raise ValueError(f"unknown attention ordering: {ordering!r}")
    be = resolve(backend)
    dcfg = LMDeployCfg(arch=cfg, attn_ordering=ordering)
    scfg = _resolve_sharding(mesh, "lm")
    if scfg is not None:
        _validate_sharding(scfg, cfg, "lm")
    units = lm_block_layout(cfg)

    # embedding norm: token rows are normalized independently, so the fold is
    # the full RMSNorm precomputed over the table (exact, bit-for-bit)
    embed = {"table": rmsnorm_apply(params["embed"]["norm"],
                                    params["embed"]["table"],
                                    eps=cfg.norm_eps)}

    folded_blocks = []
    for i in range(cfg.num_layers):
        bp = jax.tree_util.tree_map(lambda x, i=i: x[i], params["layers"])
        folded_blocks.append({
            u.name: cnn.fold_linear_rmsnorm(
                {"w": bp[u.name]["w"]}, bp[u.name]["norm"])
            for u in units})

    meta = PlanMeta(cfg=dcfg, backend=be, tok_stages=(), block_units=units,
                    num_layers=cfg.num_layers, family="lm", sharding=scfg)
    plan_params = {
        "embed": embed,
        "blocks": tuple(folded_blocks),
        "final_norm": params["final_norm"],
        "head": {"w": params["lm_head"]["w"]},
    }
    return DeployPlan(meta=meta, params=plan_params)


def plan_stats(plan: DeployPlan) -> dict:
    """Structural op accounting of the deploy plan (what the paper's Table II
    argues about): every BN is folded away, every IAND rides a LIF epilogue."""
    meta = plan.meta
    cfg = meta.cfg
    if meta.family == "lm":
        n_units = len(meta.block_units)
        decode = meta.decode
        return {
            # incremental decode: per-sequence O(d^2) SSA state, flat in S
            "decode_entry": True,
            "decode_state_bytes": decode.state_bytes(1),
            # every Linear+RMSNorm unit carries gain-folded weights, plus the
            # pre-normalized embedding table
            "folded_linear_rmsnorm": n_units * meta.num_layers,
            "folded_embed_norm": 1,
            "rmsnorm_ops": 0,          # folded at plan-compile time
            "fused_lif_iand_dispatches": 2 * meta.num_layers,
            "standalone_iand_ops": 0,
            "standalone_add_ops": 0,
            # encoding LIF + per block: q,k,v, attn, proj, fc1, fc2
            "lif_dispatches": 1 + (n_units + 1) * meta.num_layers,
            "weight_reads": 1 + n_units * meta.num_layers + 1,
            "attn_ordering": cfg.attn_ordering,
            "backend": meta.backend.kind,
            "packed": meta.backend.packed,
            "sparse": meta.backend.sparse,
            "bits_per_spike": (32 * -(-cfg.t // 32) / cfg.t
                               if meta.backend.packed else 32),
            "param_count": sum(
                p.size for p in jax.tree_util.tree_leaves(plan.params)),
            # row bundling: the MEASURED oracle deviation of the applied
            # transform (None when bundling is off)
            "bundled": meta.bundle is not None,
            "bundle_rows_merged": (meta.bundle.rows_merged
                                   if meta.bundle else 0),
            "bundle_radius": meta.bundle.radius if meta.bundle else None,
            "bundle_budget": meta.bundle.budget if meta.bundle else None,
            "bundle_logit_err": (meta.bundle.logit_err
                                 if meta.bundle else None),
        }
    n_tok = len(meta.tok_stages)
    n_units = len(meta.block_units)
    fused = sum(u.fuse_residual for u in meta.block_units) * meta.num_layers
    residuals_per_block = 2
    standalone = (0 if cfg.residual == "iand"
                  else residuals_per_block * meta.num_layers)
    return {
        "decode_entry": False,        # vision: non-causal SSA, no step mode
        "folded_conv_bn": n_tok,
        "folded_linear_bn": n_units * meta.num_layers,
        "bn_ops": 0,                          # folded at plan-compile time
        "fused_lif_iand_dispatches": fused,
        "standalone_iand_ops": 0,  # IAND only ever executes in the fused epilogue
        "standalone_add_ops": standalone,
        # one LIF dispatch per tokenizer stage; per block: q,k,v, attn, proj,
        # fc1, fc2
        "lif_dispatches": n_tok + (n_units + 1) * meta.num_layers,
        # tick-batched: each folded weight is read once per image batch for
        # all T time steps
        "weight_reads": n_tok + n_units * meta.num_layers + 1,
        "backend": meta.backend.kind,
        "packed": meta.backend.packed,
        "sparse": meta.backend.sparse,
        # bits per spike moved between layers: 32 (f32) dense, or the packed
        # word amortised over the T steps it carries
        "bits_per_spike": (32 * -(-cfg.t // 32) / cfg.t
                           if meta.backend.packed else 32),
        "param_count": sum(
            p.size for p in jax.tree_util.tree_leaves(plan.params)),
    }

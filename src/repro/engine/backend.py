"""Backend selection as a plan property.

The seed code threaded a ``use_kernel`` bool through five modules
(SpikformerConfig -> TokenizerConfig -> every ``_lif`` call site); interpret
mode was a module-level constant inside each kernel package.  Here both become
one frozen :class:`Backend` value carried by the deploy plan (and derivable
from the legacy flag for the training path):

* ``kind``: ``"jnp"`` (pure-XLA oracle graph) or ``"pallas"`` (Pallas kernels
  for LIF and optionally the spike GEMMs).
* ``interpret``: Pallas interpret mode -- ``None`` auto-selects (interpret
  off-TPU), ``False`` forces compiled lowering (TPU), ``True`` forces
  interpretation.
* ``matmul_kernel``: route deploy-time linears/convs through the
  ``spike_matmul`` GEMM kernel as well.  ``None`` (the default) auto-enables
  it exactly where it is fast: Pallas kernels compiled on TPU; interpret-mode
  GEMMs are CPU-slow, so off-TPU the auto stays on the XLA dot.
* ``packed``: carry inter-layer spike activations bit-packed along time
  (uint32 bitplane words, ``repro.core.packing``) -- LIF epilogues emit
  packed words, the IAND residual is a bitwise ``skip & ~s``, and GEMMs
  unpack per-tile in VMEM (or at the op boundary on the jnp oracle path).

Every compute op of the deploy plan routes through this module -- including
attention: :func:`ssa_apply` (jnp einsum oracle vs the ``ssa_op`` Pallas
kernel, gated like the spike GEMMs) and :func:`ssa_apply_packed` (uint32
bitplane words consumed directly by ``packed_ssa_op`` when
``Backend.closes_ssa_boundary``; unpacked at the op boundary otherwise), and
the incremental-decode ops :func:`ssa_decode_step` / :func:`ssa_prefill_state`
and their ``_packed`` variants (words consumed in-register under the closed
boundary, so the packed datapath survives decode).  The packed vision walker
calls :func:`ssa_apply_packed_merged` on token-major words, which takes the
head-merged packed SSA kernel where the boundary closes and splits heads
around :func:`ssa_apply_packed` everywhere else.  The executor never calls
a kernel or an oracle directly, so a plan's kernel route is a property of its
Backend, with no silent exemptions.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax

from repro.core import packing
from repro.core.lif import lif as _lif_dispatch


@dataclass(frozen=True)
class Backend:
    kind: str = "jnp"                  # "jnp" | "pallas"
    interpret: bool | None = None      # None = auto (interpret off-TPU)
    matmul_kernel: bool | None = None  # None = auto (on for compiled pallas)
    packed: bool = False               # bit-packed inter-layer spikes
    sparse: bool = False               # occupancy-gated zero-word skipping

    def __post_init__(self):
        if self.kind not in ("jnp", "pallas"):
            raise ValueError(f"unknown backend kind: {self.kind}")
        if self.sparse and not self.packed:
            raise ValueError(
                "Backend.sparse requires packed=True: occupancy maps are "
                "pack-time metadata of the bit-packed datapath")

    @property
    def use_matmul_kernel(self) -> bool:
        """Resolved spike-GEMM routing: an explicit bool wins; ``None`` means
        on exactly when the Pallas kernels lower compiled (TPU) -- interpret
        mode keeps GEMMs on the XLA dot, where they are orders faster on CPU.
        """
        if self.matmul_kernel is None:
            from repro.kernels.lif_parallel.ops import resolve_interpret

            return self.kind == "pallas" and not resolve_interpret(self.interpret)
        return bool(self.matmul_kernel)

    @property
    def closes_ssa_boundary(self) -> bool:
        """True when packed q/k/v words feed the packed SSA kernel directly:
        no unpack at the attention boundary, so the q/k/v edges genuinely move
        packed bytes (``engine.analysis.spike_traffic`` prices them packed
        exactly under this condition).  Requires the packed datapath AND the
        Pallas matmul-kernel route (the jnp oracle consumes dense operands)."""
        return self.packed and self.kind == "pallas" and self.use_matmul_kernel


JNP = Backend("jnp")
PALLAS = Backend("pallas")
JNP_PACKED = Backend("jnp", packed=True)
PALLAS_PACKED = Backend("pallas", packed=True)
JNP_SPARSE = Backend("jnp", packed=True, sparse=True)
PALLAS_SPARSE = Backend("pallas", packed=True, sparse=True)


def resolve(spec) -> Backend:
    """Coerce user-facing specs into a Backend: Backend | "jnp" | "pallas" |
    "jnp+packed" | "pallas+packed" | "jnp+packed+sparse" (or the shorthand
    "jnp+sparse", which implies packed) | bool (legacy use_kernel) | None."""
    if isinstance(spec, Backend):
        return spec
    if spec is None:
        return JNP
    if isinstance(spec, bool):
        return PALLAS if spec else JNP
    if isinstance(spec, str):
        kind, sep, rest = spec.partition("+")
        flag_list = rest.split("+") if sep else []
        if sep and (not kind or "" in flag_list):
            raise ValueError(f"malformed backend spec: {spec!r}")
        flags = set(flag_list)
        if flags - {"packed", "sparse"}:
            bad = sorted(flags - {"packed", "sparse"})
            raise ValueError(f"unknown backend flag(s): {bad} in {spec!r}")
        return Backend(kind, packed=bool(flags), sparse="sparse" in flags)
    raise TypeError(f"cannot resolve backend from {spec!r}")


# ---------------------------------------------------------------------------
# Packed-word collectives: the cross-DEVICE face of the closed boundary.
#
# Under a sharded plan the tensor-parallel shards exchange inter-layer spike
# activations.  These helpers keep that exchange in the packed domain: the
# collective operand is the uint32 word tensor of a ``PackedSpikes`` train
# (never the unpacked f32 spikes), so cross-device activation bytes shrink by
# the same ceil(T/32)/T factor as on-chip traffic -- the multi-chip version
# of the paper's spike-domain interconnect.  Occupancy maps reshard alongside
# when their OCC_TILE tiling survives the reshape (local feature dim a
# multiple of the tile), and are recomputed from the resharded words
# otherwise -- either way the map stays exactly consistent with the words.
# All helpers are shard_map-internal (they require a bound ``axis_name``).
# ---------------------------------------------------------------------------


def word_allgather(xp: packing.PackedSpikes,
                   axis_name: str) -> packing.PackedSpikes:
    """All-gather a feature-sharded packed train along its LAST (feature)
    axis: local words (W, ..., F/m) -> full words (W, ..., F), uint32 on the
    wire.  The gather is ``tiled`` so shard i's columns land at block i --
    exactly the single-device feature order, which is what keeps downstream
    GEMMs bit-exact."""
    from jax import lax

    words = lax.all_gather(xp.words, axis_name, axis=xp.words.ndim - 1,
                           tiled=True)
    occ = None
    if xp.occ is not None:
        if xp.words.shape[-1] % packing.OCC_TILE == 0:
            occ = lax.all_gather(xp.occ, axis_name, axis=xp.occ.ndim - 1,
                                 tiled=True)
        else:
            occ = packing.occupancy_map(words)
    return packing.PackedSpikes(words, xp.t, occ=occ)


def word_psum(xp: packing.PackedSpikes,
              axis_name: str) -> packing.PackedSpikes:
    """Sum partial packed trains across shards -- valid ONLY when the shards'
    set bits are disjoint (each spike produced by exactly one shard), where
    the uint32 sum IS the bitwise OR (the same disjoint-positions trick as
    ``packing.pack``).  That is the packed analogue of an activation
    all-reduce, at 1/32 of the wire bytes per word plane.  Occupancy
    popcounts are additive under the same disjointness, so the map psums
    alongside and stays exact."""
    from jax import lax

    words = lax.psum(xp.words, axis_name)
    occ = None if xp.occ is None else lax.psum(xp.occ, axis_name)
    return packing.PackedSpikes(words, xp.t, occ=occ)


def word_reduce_scatter(xp: packing.PackedSpikes,
                        axis_name: str) -> packing.PackedSpikes:
    """Disjoint-support sum (see :func:`word_psum`) that leaves each shard
    owning only ITS block of the feature axis: words (W, ..., F) ->
    (W, ..., F/m).  The memory-lean half of a psum when the consumer is
    itself feature-sharded; ``word_reduce_scatter`` then ``word_allgather``
    composes to exactly :func:`word_psum`."""
    from jax import lax

    words = lax.psum_scatter(xp.words, axis_name,
                             scatter_dimension=xp.words.ndim - 1, tiled=True)
    occ = None
    if xp.occ is not None:
        # scatter blocks align with OCC_TILE boundaries iff the per-shard
        # feature dim is a tile multiple (which also makes the tile count
        # divisible by the axis size); otherwise recompute from the words
        if words.shape[-1] % packing.OCC_TILE == 0:
            occ = lax.psum_scatter(xp.occ, axis_name,
                                   scatter_dimension=xp.occ.ndim - 1,
                                   tiled=True)
        else:
            occ = packing.occupancy_map(words)
    return packing.PackedSpikes(words, xp.t, occ=occ)


def spike_allgather(x, axis_name: str):
    """Backend-polymorphic feature all-gather of one spike edge: packed
    trains take :func:`word_allgather` (uint32 words on the wire), dense
    trains take a plain f32 all-gather of the last axis.  This is the ONE
    entry point the executor uses for a cross-device edge, so 'packed
    backends never move unpacked spikes between devices' is a property of
    the dispatch, not of call-site discipline."""
    from jax import lax

    if isinstance(x, packing.PackedSpikes):
        return word_allgather(x, axis_name)
    return lax.all_gather(x, axis_name, axis=x.ndim - 1, tiled=True)


def spike_shard(x, axis_name: str, size: int):
    """Local feature block of a replicated spike tensor: (..., F) ->
    (..., F/m), shard i taking columns [i*F/m, (i+1)*F/m).  The inverse of
    :func:`spike_allgather` (round-trips bit-exactly); used to land the
    replicated tokenizer output onto the feature-sharded residual stream.
    ``size`` is the (static) axis size m -- slice extents must be static, and
    jax 0.4 has no ``lax.axis_size``."""
    from jax import lax

    idx = lax.axis_index(axis_name)
    m = size
    if isinstance(x, packing.PackedSpikes):
        f = x.words.shape[-1]
        words = lax.dynamic_slice_in_dim(x.words, idx * (f // m), f // m,
                                         axis=x.words.ndim - 1)
        occ = None
        if x.occ is not None:
            occ = (lax.dynamic_slice_in_dim(
                       x.occ, idx * (x.occ.shape[-1] // m),
                       x.occ.shape[-1] // m, axis=x.occ.ndim - 1)
                   if f // m % packing.OCC_TILE == 0
                   else packing.occupancy_map(words))
        return packing.PackedSpikes(words, x.t, occ=occ)
    f = x.shape[-1]
    return lax.dynamic_slice_in_dim(x, idx * (f // m), f // m,
                                    axis=x.ndim - 1)


def unit_partition_specs(u, params: dict, rules: dict) -> dict:
    """PartitionSpecs of one folded unit's param dict, resolved from the
    layout's logical ``w_axes`` through the plan's sharding rules: the weight
    is (d_in, d_out)-annotated, every other leaf (bias, RMS normalizer) is a
    per-OUTPUT-feature vector and shards with the output dim."""
    from repro.distributed.sharding import spec

    wspec = spec(*u.w_axes, rules=rules)
    outspec = spec(u.w_axes[1], rules=rules)
    return {k: (wspec if k == "w" else outspec) for k in params}


def lif_apply(backend: Backend, drive: jax.Array, *, theta, lam, schedule,
              chain_len, iand_skip=None, reset: str = "hard",
              pack_output: bool = False, occupancy: bool | None = None):
    """Route a LIF (optionally with the fused IAND epilogue) through the
    unified neuron dispatch on this backend.  With ``pack_output`` the spike
    train returns bit-packed (and ``iand_skip`` must be packed); under
    ``Backend.sparse`` the pack epilogue also attaches the occupancy map, so
    every packed train the executor produces carries its skip index.
    ``occupancy`` overrides that default -- the decode executor passes False
    because no S=1 consumer reads the map (the sparse decode step derives
    word liveness in-register), so computing it would be pure epilogue
    overhead on the per-token path."""
    if occupancy is None:
        occupancy = pack_output and backend.sparse
    return _lif_dispatch(
        drive, theta=theta, lam=lam, reset=reset, schedule=schedule,
        chain_len=chain_len, use_kernel=(backend.kind == "pallas"),
        iand_skip=iand_skip, interpret=backend.interpret,
        pack_output=pack_output,
        pack_occupancy=pack_output and occupancy)


def linear_apply(backend: Backend, p, x2d: jax.Array) -> jax.Array:
    """Folded linear (w, b) on tick-folded 2-D activations."""
    if backend.kind == "pallas" and backend.use_matmul_kernel:
        from repro.kernels.spike_matmul.ops import spike_matmul_op

        y = spike_matmul_op(x2d, p["w"], interpret=backend.interpret)
    else:
        import jax.numpy as jnp

        y = jnp.dot(x2d, p["w"])
    if "b" in p:
        y = y + p["b"]
    return y


def ssa_apply(backend: Backend, q: jax.Array, k: jax.Array, v: jax.Array, *,
              scale: float, ordering: str = "quadratic",
              causal: bool = False) -> jax.Array:
    """Spiking self-attention on this backend. q/k/v: (T, B, H, N, Dh) binary
    spikes -> (T, B, H, N, Dh) f32 drive (the caller re-spikes through LIF).

    Routing mirrors :func:`linear_apply`: the Pallas ``ssa_op`` kernel on the
    matmul-kernel route (quadratic ordering only -- the kernel IS the
    quadratic N^2 dataflow), the jnp einsum oracle otherwise.  The linear
    ordering Q(K^T V) always takes the oracle: it is the O(d^2) long-sequence
    path whose whole point is avoiding the N x N score tile.

    ``causal`` (the LM decode order) masks the spike score matrix to the
    lower triangle -- in-kernel on the Pallas route, as the chunked running
    K^T V scan in the linear ordering.
    """
    if (ordering == "quadratic" and backend.kind == "pallas"
            and backend.use_matmul_kernel):
        from repro.kernels.spiking_attention.ops import ssa_op

        return ssa_op(q, k, v, scale=scale, interpret=backend.interpret,
                      causal=causal)
    from repro.core.spiking_attention import ssa

    return ssa(q, k, v, scale=scale, ordering=ordering, causal=causal)


def ssa_apply_packed(backend: Backend, qp: packing.PackedSpikes,
                     kp: packing.PackedSpikes, vp: packing.PackedSpikes, *,
                     scale: float, ordering: str = "quadratic",
                     causal: bool = False) -> jax.Array:
    """Spiking self-attention on packed q/k/v trains (words (W, B, H, N, Dh))
    -> dense drive (T, B, H, N, Dh).

    On the compiled Pallas matmul-kernel route the uint32 words are the
    attention operands, closing the last dense spike hop of the packed
    datapath: quadratic ordering through ``packed_ssa_op`` (bitplanes
    unpacked per-tile in VMEM; the ``sparse_packed_ssa_op`` variant under
    ``Backend.sparse`` skips dead bitplanes), linear ordering through the
    in-register shift-and-mask scan ``ssa_linear_packed`` (the O(d^2)
    long-sequence path now also consumes words directly).  Otherwise the
    trains are unpacked at the op boundary and the dense route runs -- the
    jnp oracle (under ``Backend.sparse`` the per-bitplane ``lax.cond``
    variant ``ssa_packed_sparse`` runs instead, skipping silent planes).
    """
    if ordering == "quadratic" and backend.closes_ssa_boundary:
        if backend.sparse:
            from repro.kernels.spiking_attention.ops import sparse_packed_ssa_op

            return sparse_packed_ssa_op(qp.words, kp.words, vp.words, t=qp.t,
                                        scale=scale,
                                        interpret=backend.interpret,
                                        causal=causal)
        from repro.kernels.spiking_attention.ops import packed_ssa_op

        return packed_ssa_op(qp.words, kp.words, vp.words, t=qp.t,
                             scale=scale, interpret=backend.interpret,
                             causal=causal)
    if ordering == "linear" and backend.closes_ssa_boundary:
        from repro.core.spiking_attention import ssa_linear_packed

        return ssa_linear_packed(qp.words, kp.words, vp.words, t=qp.t,
                                 scale=scale, causal=causal)
    if ordering == "quadratic" and backend.sparse:
        from repro.core.spiking_attention import ssa_packed_sparse

        return ssa_packed_sparse(qp.words, kp.words, vp.words, t=qp.t,
                                 scale=scale, causal=causal)
    q, k, v = (packing.unpack(p) for p in (qp, kp, vp))
    return ssa_apply(backend, q, k, v, scale=scale, ordering=ordering,
                     causal=causal)


# JAX monitoring events, one per traced vision block, naming the SSA route
SSA_ROUTE_MERGED = "/repro/engine/ssa_route/head_merged"
SSA_ROUTE_SPLIT = "/repro/engine/ssa_route/head_split"


def ssa_apply_packed_merged(backend: Backend, qp: packing.PackedSpikes,
                            kp: packing.PackedSpikes, vp: packing.PackedSpikes,
                            *, heads: int, scale: float,
                            ordering: str = "quadratic") -> jax.Array:
    """Spiking self-attention on token-major packed q/k/v trains (words
    (W, B, N, D), ``heads`` heads as lane slices of D) -> dense drive
    (T, B, N, D), the layout the attention LIF consumes.

    On the compiled Pallas route (``closes_ssa_boundary``, quadratic
    ordering, not sparse) the words feed ``packed_ssa_merged_op`` as they
    are: no head split, merge or pad.  Every other route splits the heads,
    runs :func:`ssa_apply_packed` and merges them back.  Each call records
    :data:`SSA_ROUTE_MERGED` or :data:`SSA_ROUTE_SPLIT` as a JAX monitoring
    event (at trace time under ``jit``).
    """
    if (ordering == "quadratic" and backend.closes_ssa_boundary
            and not backend.sparse):
        from repro.kernels.spiking_attention.ops import packed_ssa_merged_op

        jax.monitoring.record_event(SSA_ROUTE_MERGED)
        return packed_ssa_merged_op(qp.words, kp.words, vp.words, t=qp.t,
                                    heads=heads, scale=scale,
                                    interpret=backend.interpret)
    from repro.core.spiking_attention import merge_heads, split_heads_packed

    jax.monitoring.record_event(SSA_ROUTE_SPLIT)
    q, k, v = (split_heads_packed(p, heads) for p in (qp, kp, vp))
    return merge_heads(ssa_apply_packed(backend, q, k, v, scale=scale,
                                        ordering=ordering))


def ssa_decode_step(backend: Backend, state: jax.Array, q: jax.Array,
                    k: jax.Array, v: jax.Array, *, scale: float):
    """One O(d^2) linear-SSA decode step on this backend.  ``state``:
    (T, B, H, Dh, Dh) running K^T V; q/k/v: (T, B, H, 1, Dh) spikes of the
    new token.  Returns ``(state', drive)``.

    Always the jnp oracle, mirroring :func:`ssa_apply`'s linear ordering: the
    whole point of the O(d^2) path is avoiding the N x N score tile, so there
    is no quadratic kernel to route to -- the step is two tiny contractions.
    """
    from repro.core.spiking_attention import ssa_linear_decode_step

    return ssa_linear_decode_step(state, q, k, v, scale=scale)


def ssa_decode_step_packed(backend: Backend, state: jax.Array,
                           qp: packing.PackedSpikes, kp: packing.PackedSpikes,
                           vp: packing.PackedSpikes, *, scale: float):
    """Decode step on packed q/k/v trains (words (W, B, H, 1, Dh)).

    Under ``Backend.closes_ssa_boundary`` the uint32 words are the step's
    operands (bitplanes shifted out in-register -- no dense spike train and
    no ``packing.unpack`` anywhere in the decode path, so the closed
    tokenizer-to-head boundary survives decode); otherwise the trains are
    unpacked at the op boundary and the dense step runs -- the jnp oracle.
    ``Backend.sparse`` routes through the per-bitplane ``lax.cond`` variant
    on either packed route: a decode step's q/k/v are single-token trains,
    so silent planes (most of them, late in a thinned train) skip both the
    state update and the output contraction.
    """
    if backend.sparse:
        from repro.core.spiking_attention import (
            ssa_linear_decode_step_packed_sparse)

        return ssa_linear_decode_step_packed_sparse(
            state, qp.words, kp.words, vp.words, t=qp.t, scale=scale)
    if backend.closes_ssa_boundary:
        from repro.core.spiking_attention import ssa_linear_decode_step_packed

        return ssa_linear_decode_step_packed(
            state, qp.words, kp.words, vp.words, t=qp.t, scale=scale)
    q, k, v = (packing.unpack(p) for p in (qp, kp, vp))
    return ssa_decode_step(backend, state, q, k, v, scale=scale)


def ssa_prefill_state(backend: Backend, k: jax.Array, v: jax.Array) -> jax.Array:
    """K^T V decode state after a whole prefix: k/v (T, B, H, S, Dh) spikes
    -> (T, B, H, Dh, Dh).  jnp oracle on every route (one batched GEMM)."""
    from repro.core.spiking_attention import ssa_kv_state

    return ssa_kv_state(k, v)


def ssa_prefill_state_packed(backend: Backend, kp: packing.PackedSpikes,
                             vp: packing.PackedSpikes) -> jax.Array:
    """Prefill decode state from packed k/v trains; word-consuming under
    ``Backend.closes_ssa_boundary`` (gated exactly like
    :func:`ssa_decode_step_packed`), op-boundary unpack otherwise."""
    if backend.closes_ssa_boundary:
        from repro.core.spiking_attention import ssa_kv_state_packed

        return ssa_kv_state_packed(kp.words, vp.words, t=kp.t)
    k, v = packing.unpack(kp), packing.unpack(vp)
    return ssa_prefill_state(backend, k, v)


def ssa_prefill_apply(backend: Backend, q: jax.Array, k: jax.Array,
                      v: jax.Array, *, scale: float, ordering: str):
    """Full causal SSA over a prompt PLUS the end-of-prefix K^T V decode
    state: ``(drive, state)``.

    On the linear ordering the state is the causal scan's final carry
    (:func:`ssa_causal_linear_with_state`) -- the prefix is contracted ONCE,
    which matters at 500k tokens.  The quadratic ordering has no running
    state to reuse, so it pays one extra batched GEMM
    (:func:`ssa_prefill_state`)."""
    if ordering == "linear":
        from repro.core.spiking_attention import ssa_causal_linear_with_state

        return ssa_causal_linear_with_state(q, k, v, scale=scale)
    drive = ssa_apply(backend, q, k, v, scale=scale, ordering=ordering,
                      causal=True)
    return drive, ssa_prefill_state(backend, k, v)


def ssa_prefill_apply_packed(backend: Backend, qp: packing.PackedSpikes,
                             kp: packing.PackedSpikes,
                             vp: packing.PackedSpikes, *, scale: float,
                             ordering: str):
    """Packed-train counterpart of :func:`ssa_prefill_apply`.  Under the
    closed boundary both the drive and the state consume the words directly:
    the quadratic route through the packed kernel plus one ``ssa_kv_state``
    GEMM, the linear route through the in-register shift-and-mask causal
    scan ``ssa_causal_linear_with_state_packed`` whose final carry IS the
    decode state -- the prefix is contracted once, with no unpack anywhere,
    so the packed T-fold reduction finally survives long-sequence prefill.
    Otherwise the trains are unpacked at the op boundary and the dense route
    runs (incl. the fused linear-ordering scan-carry state)."""
    if ordering == "quadratic" and backend.closes_ssa_boundary:
        from repro.core.spiking_attention import ssa_kv_state_packed

        drive = ssa_apply_packed(backend, qp, kp, vp, scale=scale,
                                 ordering=ordering, causal=True)
        return drive, ssa_kv_state_packed(kp.words, vp.words, t=kp.t)
    if ordering == "linear" and backend.closes_ssa_boundary:
        from repro.core.spiking_attention import (
            ssa_causal_linear_with_state_packed)

        return ssa_causal_linear_with_state_packed(
            qp.words, kp.words, vp.words, t=qp.t, scale=scale)
    q, k, v = (packing.unpack(p) for p in (qp, kp, vp))
    return ssa_prefill_apply(backend, q, k, v, scale=scale, ordering=ordering)


def ssa_prefill_chunk(backend: Backend, state: jax.Array, q: jax.Array,
                      k: jax.Array, v: jax.Array, *, scale: float,
                      ordering: str):
    """One resumable prefill chunk: causal SSA over ``q/k/v`` of a chunk of
    the prompt, seeded by the running K^T V ``state`` of everything already
    consumed.  Returns ``(drive, state')`` -- feeding a prompt through this
    in any chunking yields per-chunk drives and a final state bit-equal to
    :func:`ssa_prefill_apply` over the whole prompt at once (binary spikes:
    exact integer sums in any association).

    Linear ordering seeds the existing scan carry directly; quadratic pays
    the intra-chunk N^2 score plus one cross-prefix state read
    (:func:`~repro.core.spiking_attention.ssa_state_read`) and one state
    GEMM -- N is now the CHUNK length, so memory is flat in the prompt."""
    if ordering == "linear":
        from repro.core.spiking_attention import ssa_causal_linear_with_state

        return ssa_causal_linear_with_state(q, k, v, scale=scale, state=state)
    from repro.core.spiking_attention import ssa_state_read

    drive = ssa_apply(backend, q, k, v, scale=scale, ordering=ordering,
                      causal=True)
    drive = drive + ssa_state_read(state, q, scale=scale)
    return drive, state + ssa_prefill_state(backend, k, v)


def ssa_prefill_chunk_packed(backend: Backend, state: jax.Array,
                             qp: packing.PackedSpikes,
                             kp: packing.PackedSpikes,
                             vp: packing.PackedSpikes, *, scale: float,
                             ordering: str):
    """Packed-train counterpart of :func:`ssa_prefill_chunk`: under the
    closed boundary the chunk's uint32 words are the operands everywhere --
    the linear route seeds the packed scan carry, the quadratic route runs
    the packed kernel plus word-consuming cross-prefix read and state GEMM
    -- so the 1/min(t,32) HBM read survives chunked long-prompt prefill.
    Otherwise the chunk is unpacked at the op boundary."""
    if ordering == "linear" and backend.closes_ssa_boundary:
        from repro.core.spiking_attention import (
            ssa_causal_linear_with_state_packed)

        return ssa_causal_linear_with_state_packed(
            qp.words, kp.words, vp.words, t=qp.t, scale=scale, state=state)
    if ordering == "quadratic" and backend.closes_ssa_boundary:
        from repro.core.spiking_attention import ssa_state_read_packed

        drive = ssa_apply_packed(backend, qp, kp, vp, scale=scale,
                                 ordering=ordering, causal=True)
        drive = drive + ssa_state_read_packed(state, qp.words, t=qp.t,
                                              scale=scale)
        return drive, state + ssa_prefill_state_packed(backend, kp, vp)
    q, k, v = (packing.unpack(p) for p in (qp, kp, vp))
    return ssa_prefill_chunk(backend, state, q, k, v, scale=scale,
                             ordering=ordering)


def normed_linear_apply(backend: Backend, p, x2d: jax.Array, *,
                        eps: float) -> jax.Array:
    """Folded Linear+RMSNorm unit (``fold_linear_rmsnorm``) on tick-folded
    2-D spikes: the GEMM rides the backend's spike-matmul route exactly like
    :func:`linear_apply`; the gain-free normalizer runs as the epilogue."""
    from repro.core import nn as cnn

    return cnn.rms_epilogue(p["nrm"], linear_apply(backend, p, x2d), eps=eps)


def normed_linear_apply_packed(backend: Backend, p,
                               xp: packing.PackedSpikes, *,
                               eps: float) -> jax.Array:
    """Folded Linear+RMSNorm on a packed spike train (W, ..., Din) -> dense
    normalized drive (T, ..., Dout); GEMM routing as in
    :func:`linear_apply_packed`."""
    from repro.core import nn as cnn

    return cnn.rms_epilogue(p["nrm"], linear_apply_packed(backend, p, xp),
                            eps=eps)


def conv3x3_apply(backend: Backend, p, x: jax.Array) -> jax.Array:
    """Folded 3x3 SAME conv on (N, H, W, C) spikes."""
    if backend.kind == "pallas" and backend.use_matmul_kernel:
        from repro.kernels.spike_matmul.ops import conv3x3_op

        y = conv3x3_op(x, p["w"], interpret=backend.interpret)
        if "b" in p:
            y = y + p["b"]
        return y
    from repro.core import nn as cnn

    return cnn.conv_apply(p, x)


def _kernel_takes_packed(backend: Backend, xp: packing.PackedSpikes) -> bool:
    """Feed words straight to the packed GEMM kernel?  Needs the Pallas GEMM
    route and a single-word train (T <= 32 -- always, for the paper's T)."""
    return (backend.kind == "pallas" and backend.use_matmul_kernel
            and xp.words.shape[0] == 1)


_SPARSE_TOKEN_TILE = 8   # token rows per jnp-route skip granule (sublane row)


def _sparse_linear_packed_jnp(xp: packing.PackedSpikes, w: jax.Array) -> jax.Array:
    """Occupancy-gated packed x weight GEMM for the jnp route: (W, M, K)
    words -> (T, M, C).

    The token axis is cut into :data:`_SPARSE_TOKEN_TILE`-row granules and
    each granule runs under a ``lax.cond`` -- a genuine branch, so an
    all-zero granule (every neuron of those tokens silent at every time
    step, the common case late in IAND-thinned trains) skips BOTH the
    bitplane unpack and the dot.  Skipped granules contribute rows that are
    exactly zero, and surviving granules keep the full-K contraction of the
    dense route, so the result is bit-exact vs unpack-then-dot.

    The granule liveness comes from the pack-time occupancy map when the
    train carries one (summed over feature tiles), else from one popcount
    pass over the words.
    """
    import jax.numpy as jnp
    from jax import lax

    words, t = xp.words, xp.t
    wcnt, m, kdim = words.shape
    tile = _SPARSE_TOKEN_TILE
    pad = (-m) % tile
    if xp.occ is not None:
        row_occ = jnp.sum(xp.occ, axis=(0, 2), dtype=jnp.uint32)   # (M,)
    else:
        row_occ = jnp.sum(lax.population_count(words), axis=(0, 2),
                          dtype=jnp.uint32)
    if pad:
        words = jnp.pad(words, ((0, 0), (0, pad), (0, 0)))
        row_occ = jnp.pad(row_occ, (0, pad))
    nt = words.shape[1] // tile
    wt = words.reshape(wcnt, nt, tile, kdim).transpose(1, 0, 2, 3)
    occ_t = row_occ.reshape(nt, tile).sum(axis=1)
    c = w.shape[1]

    def granule(tile_words, alive):
        def live():
            dense = packing.unpack(packing.PackedSpikes(tile_words, t))
            return jnp.dot(dense.reshape(t * tile, kdim), w).reshape(t, tile, c)

        return lax.cond(alive > 0, live,
                        lambda: jnp.zeros((t, tile, c), jnp.float32))

    ys = lax.map(lambda args: granule(*args), (wt, occ_t))   # (nt, T, tile, C)
    return ys.transpose(1, 0, 2, 3).reshape(t, nt * tile, c)[:, :m]


def linear_apply_packed(backend: Backend, p, xp: packing.PackedSpikes) -> jax.Array:
    """Folded linear on a packed spike train (W, ..., Din) -> dense drive
    (T, ..., Dout).

    On the compiled Pallas route the uint32 words are the GEMM operand
    (unpacked per-tile in VMEM); otherwise the train is unpacked at the op
    boundary and the tick-folded XLA dot runs -- the jnp oracle.  Under
    ``Backend.sparse`` both routes consult the occupancy map and skip
    all-zero word tiles (bit-exact; see the sparse variants' docstrings).
    """
    lead = xp.elem_shape[:-1]
    d_in = xp.elem_shape[-1]
    if _kernel_takes_packed(backend, xp):
        if backend.sparse:
            from repro.kernels.spike_matmul.ops import sparse_packed_spike_matmul_op

            occ = (xp.occ[0].reshape(-1, xp.occ.shape[-1])
                   if xp.occ is not None else None)
            y = sparse_packed_spike_matmul_op(
                xp.words[0].reshape(-1, d_in), p["w"], t=xp.t, occ=occ,
                interpret=backend.interpret)
        else:
            from repro.kernels.spike_matmul.ops import packed_spike_matmul_op

            y = packed_spike_matmul_op(
                xp.words[0].reshape(-1, d_in), p["w"], t=xp.t,
                interpret=backend.interpret)
        y = y.reshape((xp.t,) + lead + (p["w"].shape[1],))
        if "b" in p:
            y = y + p["b"]
        return y
    if backend.sparse and math.prod(lead) >= _SPARSE_TOKEN_TILE:
        flat = xp.reshape_elems(-1, d_in)                # occ rides along
        y = _sparse_linear_packed_jnp(flat, p["w"])
        y = y.reshape((xp.t,) + lead + (p["w"].shape[1],))
        if "b" in p:
            y = y + p["b"]
        return y
    # under sparse with fewer token rows than one skip granule (the S=1
    # decode regime) the granule gate has nothing to skip and padding to a
    # full tile would MULTIPLY the contraction, so the dense packed route
    # runs (bit-exact either way)
    x = packing.unpack(xp)                           # (T, ..., Din)
    y2d = linear_apply(backend, p, x.reshape(-1, d_in))
    return y2d.reshape((xp.t,) + lead + (-1,))


def conv3x3_apply_packed(backend: Backend, p, xp: packing.PackedSpikes) -> jax.Array:
    """Folded 3x3 SAME conv on packed spikes (W, N, H, Wd, C) -> dense drive
    (T, N, H, Wd, Cout).  Under ``Backend.sparse`` the patch GEMM skips
    all-zero word tiles (spatially-silent patch rows) on both routes."""
    if _kernel_takes_packed(backend, xp):
        if backend.sparse:
            from repro.kernels.spike_matmul.ops import sparse_packed_conv3x3_op

            y = sparse_packed_conv3x3_op(
                xp.words[0], p["w"], t=xp.t, interpret=backend.interpret)
        else:
            from repro.kernels.spike_matmul.ops import packed_conv3x3_op

            y = packed_conv3x3_op(
                xp.words[0], p["w"], t=xp.t, interpret=backend.interpret)
        if "b" in p:
            y = y + p["b"]
        return y
    if backend.sparse and xp.words.shape[0] == 1:
        # im2col on the words, then the occupancy-gated jnp patch GEMM --
        # the patch gather scrambles the feature axis, so liveness is
        # recomputed on the gathered words (sparse GEMM popcount pass)
        from repro.kernels.spike_matmul.ops import _im2col

        n, h, wd, c = xp.words.shape[1:]
        cout = p["w"].shape[-1]
        cols = _im2col(xp.words[0], 3)               # (N*H*W, 9*Cin) words
        colsp = packing.PackedSpikes(cols[None], xp.t)
        y = _sparse_linear_packed_jnp(colsp, p["w"].reshape(9 * c, cout))
        y = y.reshape(xp.t, n, h, wd, cout)
        if "b" in p:
            y = y + p["b"]
        return y
    x = packing.unpack(xp)                           # (T, N, H, Wd, C)
    t, n = x.shape[0], x.shape[1]
    y = conv3x3_apply(backend, p, x.reshape((t * n,) + x.shape[2:]))
    return y.reshape((t, n) + y.shape[1:])

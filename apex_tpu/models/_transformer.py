"""Shared transformer backbone for the model zoo (GPT, BERT).

The reference's standalone_gpt.py and standalone_bert.py share Megatron's
ParallelMLP/ParallelAttention/ParallelTransformer internals; here the shared
plumbing lives in :class:`TransformerBase` and the models keep only their own
semantics (pre-LN causal LM vs post-LN masked LM, heads, losses).

Both models use the same per-layer parameter tree
``{ln1, ln2, qkv, proj, fc1, fc2}`` stacked on a leading ``num_layers`` dim
and driven by ``lax.scan`` (compile time O(1) in depth, natural pipeline-stage
slicing); only ``_layer`` — where LN sits relative to the residual — differs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.ops.flash_attention import flash_attention
from apex_tpu.ops.layer_norm import layer_norm as fused_layer_norm_op
from apex_tpu.transformer import tensor_parallel as tp
from apex_tpu.utils.nn import inverted_dropout

#: the ONE rejection text for ``zero3_prefetch`` without unrolled layers —
#: shared by the trace-time check here (run_layers) and the build-time
#: check in ``transformer.amp.build_zero_train_step`` so harness and audit
#: reject with identical words (tests pin the equality)
ZERO3_PREFETCH_NEEDS_UNROLL = (
    "zero3_prefetch needs unroll_layers=True: the double-buffered gather "
    "schedule is a static unrolled structure (a lax.scan has one gather "
    "call site to prefetch around)")

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class SegmentMask:
    """Attention masking by SEGMENT IDS instead of an additive bias.

    Flows through the same ``bias`` channel as additive masks
    (run_layers → _layer → _attention → _attend) but reaches the flash
    kernel's segment-id path — which, unlike a dense bias, works under
    sequence/context parallelism: the per-shard kv-id slices rotate around
    the ring with their K/V shard (transformer/ring.py). This is how BERT
    padding masks (bert_extended_attention_mask,
    standalone_bert.py:10-23) are expressed under ``context_axis``
    (VERDICT r3 ask #4).

    ``q_seg``/``kv_seg``: ``(b, s)`` int arrays (LOCAL shards under CP);
    keys with id ``pad_id`` are never attended and fully-padded query rows
    output exactly 0.
    """

    q_seg: jax.Array
    kv_seg: jax.Array
    pad_id: Optional[int] = None


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0) -> jax.Array:
    """Rotary position embedding (split-half / NeoX convention) on
    ``(b, nh, s, d)`` with explicit GLOBAL ``positions`` of shape ``(s,)``.

    Scores become functions of relative distance only —
    ``rope(q, p)·rope(k, p') == rope(q, p+s)·rope(k, p'+s)`` (unit-tested)
    — so the per-shard global positions make it exact under ring/Ulysses
    context parallelism, and no position table exists at all: at 1M
    tokens the learned table alone is ~3.75 GB of params+optimizer state.
    Beyond-reference capability (the reference's GPT is learned-position
    only, standalone_gpt.py embeddings)."""
    return _rope_rotate(x, positions, theta, batched=False)


def apply_rope_at(x: jax.Array, positions: jax.Array,
                  theta: float = 10000.0) -> jax.Array:
    """:func:`apply_rope` with PER-SEQUENCE positions: ``x`` is
    ``(b, nh, s, d)`` and ``positions`` is ``(b, s)`` — the decode-tick
    form, where every serving slot sits at its own context position. One
    shared angle/rotation body (:func:`_rope_rotate`), so a decoded
    token's rotation matches the training forward's bit for bit at equal
    position by construction."""
    return _rope_rotate(x, positions, theta, batched=True)


def _rope_rotate(x, positions, theta, *, batched):
    """Shared rope body: angles from the K-split reduction, then the
    split-half rotation. ``batched=False``: ``positions`` is ``(s,)``
    shared across the batch; ``True``: ``(b, s)`` per sequence (the
    angle tensor gains a leading batch dim, broadcast over heads).
    Per-element the two forms run the identical f32 op sequence — the
    serve equivalence gate rests on that."""
    import numpy as np

    d = x.shape[-1]
    half = d // 2
    # Angle precision at long context: pos · inv_freq in f32 carries a
    # relative 1e-7 error, which at pos = 1e6 is up to ~0.1 rad for the
    # highest frequency. Split the (exact, integer) position as
    # a·K + r and pre-reduce K·inv_freq modulo 2π in float64 at trace
    # time, so every f32 product stays small (≲ 3e3 rad → ≤ 3e-4 rad
    # error at 1M tokens).
    K = 2048
    inv64 = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / d)
    kmod = jnp.asarray(np.mod(K * inv64, 2 * np.pi), jnp.float32)
    inv_freq = jnp.asarray(inv64, jnp.float32)
    a = (positions // K).astype(jnp.float32)[..., None]  # (s, 1) | (b, s, 1)
    r = (positions % K).astype(jnp.float32)[..., None]
    ang = a * kmod + r * inv_freq                        # (..., s, half)
    cos = jnp.cos(ang)[:, None] if batched else jnp.cos(ang)  # + head bcast
    sin = jnp.sin(ang)[:, None] if batched else jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _remat_policy(name: Optional[str]):
    """Selective activation-checkpoint policies (reference: the sharded
    activation buffer knob of tensor_parallel/random.py:45-76 — the
    memory/recompute dial, redesigned as jax.checkpoint policies):

    - None/"full": recompute everything (lowest memory);
    - "save_attn": save the flash-attention kernel outputs (tagged
      "flash_out"/"flash_lse" in ops/flash_attention._flash_fwd) so
      backward skips re-running the attention forward — the layer's most
      FLOP-expensive recompute — for O(b*h*s*d) extra memory per layer;
    - "dots": XLA's dots_with_no_batch_dims_saveable (save GEMM outputs).
    """
    if name in (None, "full"):
        return None
    if name == "save_attn":
        return jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse")
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(f"unknown remat_policy {name!r}")


def _prefetched_zero3_drive(layer_fn, gather_fn, n: int, prefetch: int):
    """Software-pipelined (double-buffered) ZeRO-3 layer drive for the
    UNROLLED path: issue layer ``i+prefetch``'s chunk all-gather before
    layer ``i``'s compute, forward AND backward, so the gathers stand as
    structurally independent collectives ahead of the compute that hides
    them (the cross-replica weight-sharding layout of Xu et al. driven as
    an explicit prefetch schedule; tripwire:
    ``lint.trace.unprefetched_gather_hazards``).

    The serialized drive keeps each gather INSIDE the rematerialized scan
    body (run_layers ``chunk_meta``), which pins it to that body's
    schedule; this drive replaces ``jax.checkpoint`` with a
    ``jax.custom_vjp`` whose backward re-gathers each layer's weights
    (prefetched ``prefetch`` layers ahead of the reverse sweep) and
    rematerializes the layer forward under a fresh ``jax.vjp`` — identical
    remat semantics, same math (the gather's AD transpose still
    reduce-scatters that layer's grads on the spot, via ``jax.vjp`` of the
    same gather), but the gathered weights are never residuals: peak param
    residency is ``prefetch + 1`` layers plus chunks.

    ``layer_fn(p_full, h) -> h``; ``gather_fn(chunk_row) -> p_full``;
    ``n`` = layer count. Returns ``drive(chunks, h) -> h`` (chunks: the
    ``(L, k)`` per-row chunk stack).
    """
    pf = max(int(prefetch), 0)

    def _row(chunks, i):
        return jax.tree.map(lambda v: v[i], chunks)

    def _fwd(chunks, h):
        window = [gather_fn(_row(chunks, j)) for j in range(min(pf, n))]
        hs = []
        for i in range(n):
            if i + pf < n:
                # layer i+pf's gather is issued BEFORE layer i's compute
                window.append(gather_fn(_row(chunks, i + pf)))
            p = window.pop(0)
            hs.append(h)
            h = layer_fn(p, h)
        return h, (chunks, jnp.stack(hs))

    def _bwd(res, g):
        chunks, h_stack = res
        idxs = list(reversed(range(n)))
        window = [jax.vjp(gather_fn, _row(chunks, j))
                  for j in idxs[:min(pf, n)]]
        g_rows = [None] * n
        for pos, i in enumerate(idxs):
            if pos + pf < n:
                # the backward RE-gather for the layer prefetch steps
                # ahead of the current layer's VJP compute
                window.append(jax.vjp(gather_fn, _row(chunks, idxs[pos + pf])))
            p, gvjp = window.pop(0)
            _, lvjp = jax.vjp(layer_fn, p, h_stack[i])
            g_p, g = lvjp(g)
            (g_rows[i],) = gvjp(g_p)
        g_chunks = jax.tree.map(lambda *rows: jnp.stack(rows), *g_rows)
        return g_chunks, g

    @jax.custom_vjp
    def drive(chunks, h):
        return _fwd(chunks, h)[0]

    drive.defvjp(_fwd, _bwd)
    return drive


def stack_specs(spec_tree):
    """Prefix each PartitionSpec with the stacked (num_layers) dim."""
    return jax.tree.map(
        lambda s: P(None, *s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


class TransformerBase:
    """TP-sharded transformer plumbing shared by the model zoo.

    Subclasses define ``causal`` and ``_layer(p, h, key, bias)``, and their
    own ``init``/``specs``/``embed``/``head``. The config must provide the
    common fields (hidden_size, num_attention_heads, num_layers, ffn, axis,
    params_dtype, compute_dtype, hidden_dropout, init_method_std, remat,
    attention_impl, vocab_size).
    """

    causal: bool = True

    def __init__(self, config):
        self.cfg = c = config
        if c.hidden_size % c.num_attention_heads:
            raise ValueError("hidden_size must divide evenly into heads")
        # Megatron-style sequence parallelism over the TP axis
        # (cfg.sequence_parallel): the row-parallel forward psums decompose
        # into psum_scatter + a later pre-GEMM all-gather, and everything
        # between them (LN, dropout, residual) runs on (b, s/tp, h) shards.
        # Serial (axis=None) ignores the knob — one code path.
        self._sp = bool(getattr(c, "sequence_parallel", False)) and c.axis is not None
        # Quantized wire dtype of the sequence-parallel conjugates
        # (cfg.activation_comm_dtype -> the encode/decode pair of
        # parallel/quantize.py): activations quantize more safely than
        # grads — fresh values every step, per-shard scales bound the
        # error — so no residual state rides along (quantize.py module
        # doc). Only meaningful when the conjugates exist at all.
        self._acd = getattr(c, "activation_comm_dtype", None)
        if self._acd is not None:
            from apex_tpu.parallel.quantize import canon_wire_dtype

            self._acd = canon_wire_dtype(self._acd)
            if c.axis is None:
                # serial twin convention (same as sequence_parallel, which
                # is "ignored when axis is None"): the serial build of a
                # sharded config must run, one code path — there is no
                # wire to quantize
                self._acd = None
            elif not self._sp:
                raise ValueError(
                    "activation_comm_dtype requires sequence_parallel=True: "
                    "the quantized wire dtype rides the sequence-parallel "
                    "scatter/gather conjugates — plain-TP all-reduces have "
                    "no encode/decode seam")
        if self._sp:
            # seq % tp == 0 is a runtime property (the axis size lives in
            # the mesh), but when the mesh is already up we can fail HERE
            # with the knob named, instead of deep inside the embedding's
            # reduce-scatter with a bare divisibility error
            from apex_tpu.parallel import mesh as mesh_lib

            if mesh_lib.model_parallel_is_initialized():
                tp_size = mesh_lib.get_tensor_model_parallel_world_size()
                if tp_size > 1 and c.max_seq_len % tp_size:
                    raise ValueError(
                        f"sequence_parallel=True needs max_seq_len "
                        f"({c.max_seq_len}) divisible by the tensor-"
                        f"parallel size ({tp_size}): the embedding "
                        f"reduce-scatter shards the sequence tp ways")
        init = tp.scaled_normal(c.init_method_std)
        # Megatron scales output-layer init by 1/sqrt(2L)
        # (standalone_gpt.py scaled_init_method_normal).
        out_init = tp.scaled_normal(c.init_method_std / (2 * c.num_layers) ** 0.5)
        self._init = init
        self.embedding = tp.VocabParallelEmbedding(
            c.vocab_size, c.hidden_size, axis=c.axis,
            sequence_parallel=self._sp, comm_dtype=self._acd,
            params_dtype=c.params_dtype, init_method=init,
        )
        self.qkv = tp.ColumnParallelLinear(
            c.hidden_size, 3 * c.hidden_size, axis=c.axis, gather_output=False,
            sequence_parallel=self._sp, comm_dtype=self._acd,
            params_dtype=c.params_dtype, init_method=init,
        )
        self.proj = tp.RowParallelLinear(
            c.hidden_size, c.hidden_size, axis=c.axis, input_is_parallel=True,
            sequence_parallel=self._sp, comm_dtype=self._acd,
            params_dtype=c.params_dtype, init_method=out_init,
        )
        self.fc1 = tp.ColumnParallelLinear(
            c.hidden_size, c.ffn, axis=c.axis, gather_output=False,
            sequence_parallel=self._sp, comm_dtype=self._acd,
            params_dtype=c.params_dtype, init_method=init,
        )
        self.fc2 = tp.RowParallelLinear(
            c.ffn, c.hidden_size, axis=c.axis, input_is_parallel=True,
            sequence_parallel=self._sp, comm_dtype=self._acd,
            params_dtype=c.params_dtype, init_method=out_init,
        )

    # -- parameter helpers --------------------------------------------------

    def _ln_init(self) -> Params:
        c = self.cfg
        return {
            "scale": jnp.ones((c.hidden_size,), c.params_dtype),
            "bias": jnp.zeros((c.hidden_size,), c.params_dtype),
        }

    def _dense_init(self, key, n_in, n_out) -> Params:
        c = self.cfg
        return {
            "kernel": self._init(key, (n_in, n_out), c.params_dtype),
            "bias": jnp.zeros((n_out,), c.params_dtype),
        }

    def _layer_init(self, k) -> Params:
        ks = jax.random.split(k, 4)
        return {
            "ln1": self._ln_init(),
            "qkv": self.qkv.init(ks[0]),
            "proj": self.proj.init(ks[1]),
            "ln2": self._ln_init(),
            "fc1": self.fc1.init(ks[2]),
            "fc2": self.fc2.init(ks[3]),
        }

    def init_layer_stack(self, key) -> Params:
        """Stack per-layer trees along a leading num_layers dim (vmap over
        init is the cleanest way to build the scan-shaped stack)."""
        return jax.vmap(self._layer_init)(
            jax.random.split(key, self.cfg.num_layers))

    def layer_stack_specs(self) -> Params:
        ln = {"scale": P(), "bias": P()}
        return {
            "ln1": stack_specs(ln),
            "qkv": stack_specs(self.qkv.specs()),
            "proj": stack_specs(self.proj.specs()),
            "ln2": stack_specs(ln),
            "fc1": stack_specs(self.fc1.specs()),
            "fc2": stack_specs(self.fc2.specs()),
        }

    # -- compute helpers ----------------------------------------------------

    def _sp_param(self, x: jax.Array) -> jax.Array:
        """A REPLICATED parameter about to be consumed in a sequence-sharded
        region: each TP rank sees only its tokens, so AD alone would leave a
        PARTIAL per-rank gradient — and the harnesses' spec-aware reduction
        (allreduce_gradients_by_spec) never psums over the model axis for
        replicated params. The identity-forward/psum-backward ``copy_to``
        restores the plain-TP convention (full, identical grads on every TP
        rank) inside the differentiated function — the in-AD form of
        Megatron's sequence-parallel grad all-reduce."""
        if not self._sp:
            return x
        return tp.copy_to_tensor_model_parallel_region(x, self.cfg.axis)

    def _ln(self, p: Params, x: jax.Array,
            sequence_region: Optional[bool] = None) -> jax.Array:
        # Mixed-dtype fused LN: bf16 activations, fp32 γβ
        # (MixedFusedLayerNorm, fused_layer_norm.py:398-436). LNs sit in the
        # sequence-sharded region under sequence parallelism (that sharding
        # is the mode's memory win), so γβ ride _sp_param by default; head
        # LNs past the sequence gather pass sequence_region=False.
        scale, bias = p["scale"], p["bias"]
        with jax.named_scope("layer_norm"):
            if sequence_region is None or sequence_region:
                scale, bias = self._sp_param(scale), self._sp_param(bias)
            return fused_layer_norm_op(x, scale, bias)

    def _dense(self, p: Params, x: jax.Array) -> jax.Array:
        return x @ p["kernel"].astype(x.dtype) + p["bias"].astype(x.dtype)

    def _dropout(self, x, key, rank_unique: bool = False):
        c = self.cfg
        if key is None or c.hidden_dropout == 0.0:
            return x
        if self._sp:
            # sequence-sharded region: every hidden-dropout site in the
            # model zoo sits between a reduce-scatter and the next gather,
            # so each TP rank holds DIFFERENT tokens — fold the rank in
            # (tensor_parallel/random.py sequence_parallel_key) or the
            # shards would draw correlated masks
            key = tp.sequence_parallel_key(key, c.axis)
        elif rank_unique and c.axis is not None:
            key = tp.model_parallel_key(key, c.axis)
        return inverted_dropout(x, key, c.hidden_dropout)

    def _qkv_heads(self, p_qkv: Params, h: jax.Array,
                   positions: Optional[jax.Array] = None):
        """``(q, k, v)`` head tensors ``(b, n_local, s, d)`` from the fused
        QKV projection — the shared front half of :meth:`_attention`, also
        driven standalone by the serving prefill/decode paths (which need
        the raw k/v heads for the paged cache). ``positions`` overrides the
        rope positions with explicit PER-SEQUENCE ``(b, s)`` values (decode:
        each slot sits at its own context position); default is the
        training-forward :meth:`_token_positions`."""
        c = self.cfg
        b = h.shape[0]
        qkv = self.qkv.apply(p_qkv, h)  # (b, s, 3*H/tp)
        # under sequence parallelism h arrives (b, s/tp, H) and the
        # column layer's pre-GEMM all-gather restores the full
        # (context-local) sequence — read s from the GATHERED tensor
        s = qkv.shape[1]
        # (heads, 3, head_dim) layout: a TP shard holds whole heads — the
        # layout contract of ParallelAttention (standalone_gpt.py:560-640).
        n_local = qkv.shape[-1] // (3 * c.head_dim)
        qkv = qkv.reshape(b, s, n_local, 3, c.head_dim).transpose(0, 2, 3, 1, 4)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # (b, nh, s, d)
        if getattr(c, "position_embedding", "learned") == "rope":
            theta = getattr(c, "rope_theta", 10000.0)
            if positions is None:
                pos = self._token_positions(s)
                q = apply_rope(q, pos, theta)
                k = apply_rope(k, pos, theta)
            else:
                q = apply_rope_at(q, positions, theta)
                k = apply_rope_at(k, positions, theta)
        return q, k, v

    def _attn_out(self, p: Params, attn: jax.Array) -> jax.Array:
        """Head-merge + output projection — the shared back half of
        :meth:`_attention` (also the serving decode epilogue)."""
        b, n_local, s, _ = attn.shape
        attn = attn.transpose(0, 2, 1, 3).reshape(
            b, s, n_local * self.cfg.head_dim)
        return self.proj.apply(p["proj"], attn)

    def _attention(self, p: Params, h: jax.Array, bias=None) -> jax.Array:
        # named scope = the per-op attribution key of pyprof.report (the
        # NVTX range the reference's nvmarker.py pushes around each module)
        with jax.named_scope("attention"):
            q, k, v = self._qkv_heads(p["qkv"], h)
            # the kernel apart from the projections and the head
            # split/merge that share "attention"
            with jax.named_scope("attention_core"):
                attn = self._attend(q, k, v, bias)
            return self._attn_out(p, attn)

    def _seq_shard_start(self, s_local: int):
        """Global position of this shard's first token for a tensor whose
        sequence dim is ``s_local`` long: the context-parallel offset
        (tokens arrive pre-sliced over ``context_axis``) plus the
        sequence-parallel offset (the embedding's reduce-scatter slices the
        context-local sequence a further tp ways). Returns a static 0 when
        neither axis shards the sequence."""
        c = self.cfg
        ctx = getattr(c, "context_axis", None)
        start = 0
        if ctx is not None:
            cp_local = s_local * (lax.axis_size(c.axis) if self._sp else 1)
            start = lax.axis_index(ctx) * cp_local
        if self._sp:
            start = start + lax.axis_index(c.axis) * s_local
        return start

    def _positions(self, pos_table: jax.Array, s_local: int) -> jax.Array:
        """Slice the learned position table for this shard's tokens —
        ``s_local`` is the LOCAL sequence length of the activation the
        positions are added to (context- and/or sequence-parallel-sharded);
        global positions start at :meth:`_seq_shard_start`. The table is a
        replicated param consumed per-shard, so under sequence parallelism
        it rides :meth:`_sp_param` for the grad bookkeeping (the
        context-axis slice needs no such wrap: the harness's pmean over the
        gradient-reduction axes recovers disjoint-row sums exactly)."""
        pos_table = self._sp_param(pos_table)
        ctx = getattr(self.cfg, "context_axis", None)
        if ctx is None and not self._sp:
            return pos_table[:s_local]
        return lax.dynamic_slice_in_dim(
            pos_table, self._seq_shard_start(s_local), s_local, axis=0)

    def _token_positions(self, s_local: int) -> jax.Array:
        """GLOBAL positions of this shard's tokens (for rotary embedding).
        Called on the GATHERED sequence inside attention, where only the
        context axis still shards the sequence — the sequence-parallel
        offset never applies here."""
        ctx = getattr(self.cfg, "context_axis", None)
        start = lax.axis_index(ctx) * s_local if ctx is not None else 0
        return start + jnp.arange(s_local, dtype=jnp.int32)

    def _attend(self, q, k, v, bias):
        """Core attention on (b, nh, s, d). With ``cfg.context_axis`` set the
        sequence dim is sharded over that mesh axis and attention runs as
        ring (ppermute KV block exchange) or Ulysses (all_to_all head
        exchange) sequence parallelism — shared by every model in the zoo
        (SURVEY.md §2.3 row SP: a new capability vs the reference)."""
        c = self.cfg
        ctx = getattr(c, "context_axis", None)
        win = getattr(c, "attention_window", None)
        seg = bias if isinstance(bias, SegmentMask) else None
        if ctx is None:
            if seg is not None:
                return flash_attention(
                    q, k, v, segment_ids=(seg.q_seg, seg.kv_seg),
                    pad_id=seg.pad_id, causal=self.causal,
                    impl=c.attention_impl, window=win)
            return flash_attention(q, k, v, bias=bias, causal=self.causal,
                                   impl=c.attention_impl, window=win)
        from apex_tpu.transformer.ring import ring_attention, ulysses_attention

        if bias is not None and seg is None:
            raise NotImplementedError(
                "a dense attention bias is not supported under sequence "
                "parallelism (it would have to be materialized (sq, SK) per "
                "shard); express masking as a SegmentMask — padding masks "
                "map directly (models/bert.py) — or run with "
                "context_axis=None")
        impls = {"ring": ring_attention, "ulysses": ulysses_attention}
        impl_name = getattr(c, "sequence_parallel_impl", "ring")
        if impl_name not in impls:
            raise ValueError(
                f"sequence_parallel_impl must be 'ring' or 'ulysses', "
                f"got {impl_name!r}")
        seg_kw = {}
        if seg is not None:
            seg_kw = dict(segment_ids=(seg.q_seg, seg.kv_seg),
                          pad_id=seg.pad_id)
        return impls[impl_name](
            q, k, v, axis=ctx, causal=self.causal, impl=c.attention_impl,
            window=win, **seg_kw)

    def _mlp(self, p: Params, h: jax.Array) -> jax.Array:
        with jax.named_scope("mlp"):
            return self.fc2.apply(
                p["fc2"], jax.nn.gelu(self.fc1.apply(p["fc1"], h)))

    def _layer(self, p: Params, h: jax.Array, key, bias=None) -> jax.Array:
        raise NotImplementedError

    # -- per-layer aux hooks (override point for layers that emit side
    # losses, e.g. MoE routers) ---------------------------------------------

    def _aux_init(self):
        """Zero-valued aux accumulator pytree, or None when layers emit no
        aux (the default)."""
        return None

    def _layer_aux(self, p: Params, h: jax.Array, key, bias):
        """``(h, aux)`` for one layer; default layers emit no aux."""
        return self._layer(p, h, key, bias), None

    def run_layers(
        self,
        layers: Params,
        h: jax.Array,
        attn_bias: Optional[jax.Array] = None,
        dropout_key: Optional[jax.Array] = None,
        return_aux: bool = False,
        chunk_meta=None,
    ):
        """:meth:`_run_layers` under the scope ``layers``: in a device
        trace, what lies under the step and under none of ``embed``,
        ``layers``, ``head`` and the optimizer's scopes is the step's
        glue."""
        with jax.named_scope("layers"):
            return self._run_layers(layers, h, attn_bias, dropout_key,
                                    return_aux, chunk_meta)

    def _run_layers(self, layers, h, attn_bias, dropout_key, return_aux,
                    chunk_meta):
        """Scan the (stacked) layer params over the hidden state. ``layers``
        may be any contiguous slice of the stack — a pipeline stage's chunk.
        Activation checkpointing is ``jax.checkpoint`` on the scanned body
        (reference: tensor_parallel/random.py:224-294 CheckpointFunction).

        ``chunk_meta`` (optimizers.distributed.ChunkedMeta, per-LAYER local
        shapes) switches to the ZeRO-3 fully-sharded drive: ``layers`` is
        then a ``(L, k)`` per-row chunk stack and each layer's full weight
        tree is all-gathered JUST IN TIME inside the body — so peak param
        residency is one layer plus chunks, not the whole stack. The body
        is always rematerialized in this mode (even with ``cfg.remat``
        off): backward then RE-GATHERS each layer instead of saving the
        gathered weights as residuals, and the gather's AD transpose
        reduce-scatters that layer's grads on the spot. On the unrolled
        path the per-layer gathers are static, independent collectives;
        with ``cfg.zero3_prefetch > 0`` they are DOUBLE-BUFFERED
        explicitly (:func:`_prefetched_zero3_drive`: layer i+prefetch's
        gather issues before layer i's compute, forward and backward)
        instead of leaving the overlap to XLA's latency-hiding scheduler
        — the structural form the ``unprefetched_gather_hazards``
        tripwire checks for (peak residency: prefetch+1 layers + chunks).

        When the model's layers emit aux losses (``_aux_init`` not None),
        they accumulate in the scan carry and the caller MUST pass
        ``return_aux=True`` — silently discarding router losses would turn
        the MoE balancing knobs into no-ops. A model with
        ``aux_per_layer = True`` gets what each layer's ``_layer_aux``
        returned stacked over the layers instead (counters, which a sum
        over layers would blur).

        ``h`` is whatever the model's ``_layer_aux`` carries from layer to
        layer: one stream, or a pytree of them (models/instella.py carries
        the stream now and as it stood one sub-block ago)."""
        n = jax.tree.leaves(layers)[0].shape[0]
        keys = None if dropout_key is None else jax.random.split(dropout_key, n)
        aux0 = self._aux_init()
        if aux0 is not None and not return_aux:
            raise ValueError(
                "this model's layers emit aux losses (MoE router); call "
                "run_layers(..., return_aux=True) and fold them into the "
                "loss — dropping them silently disables load balancing. "
                "Under the pipeline schedules, pass run_layers with "
                "return_aux=True plus aux_to_loss to pipelined_loss_fn."
            )
        if chunk_meta is not None:
            from apex_tpu.optimizers.distributed import gather_chunked_tree

            prefetch = int(getattr(self.cfg, "zero3_prefetch", 0) or 0)
            if prefetch > 0:
                if not getattr(self.cfg, "unroll_layers", False):
                    raise ValueError(ZERO3_PREFETCH_NEEDS_UNROLL)
                if aux0 is not None:
                    raise ValueError(
                        "zero3_prefetch does not support aux-emitting "
                        "layers (MoE routers) — ZeRO rejects data-sharded "
                        "experts anyway")
                if keys is not None or attn_bias is not None:
                    raise NotImplementedError(
                        "zero3_prefetch drives the dense dropout-off path "
                        "only: the custom-VJP drive would need dropout-key"
                        "/attention-bias cotangent plumbing no ZeRO-3 "
                        "harness exercises")
                drive = _prefetched_zero3_drive(
                    lambda p, hh: self._layer(p, hh, None, None),
                    lambda c: gather_chunked_tree(c, chunk_meta),
                    n, prefetch)
                h = drive(layers, h)
                return (h, None) if return_aux else h

        per_layer = getattr(self, "aux_per_layer", False)

        def body(carry, xs):
            h, acc = carry
            p, k = xs
            if chunk_meta is not None:
                p = gather_chunked_tree(p, chunk_meta)
            h, aux = self._layer_aux(p, h, k, attn_bias)
            if per_layer:
                return (h, acc), aux
            if acc is not None:
                acc = jax.tree.map(
                    jnp.add, acc,
                    jax.tree.map(lambda v: v.astype(jnp.float32), aux))
            return (h, acc), None

        if self.cfg.remat or chunk_meta is not None:
            # inside a loop the recompute cannot merge with the first
            # forward pass; a stack of ONE layer is no loop once compiled,
            # and a model whose pattern leaves such runs (run_pattern) asks
            # for the barrier there (``remat_barrier_single_layer``)
            body = jax.checkpoint(
                body, prevent_cse=n == 1 and getattr(
                    self, "remat_barrier_single_layer", False),
                policy=_remat_policy(getattr(self.cfg, "remat_policy", None)),
            )
        if getattr(self.cfg, "unroll_layers", False):
            # Unrolled drive of the SAME stacked params: static per-layer
            # slices in a Python loop. The scan's backward writes each
            # layer's grads through dynamic-update-slice fusions (~28 ms
            # per 345M grad step on-chip, 11%) which the static-slice
            # adjoints avoid entirely — measured 230 -> 188 ms (PERF_NOTES
            # r5). Same math, same order, same tree; compile time grows
            # O(depth).
            carry, each = (h, aux0), []
            for i in range(n):
                xs = (jax.tree.map(lambda v: v[i], layers),
                      None if keys is None else keys[i])
                carry, y = body(carry, xs)
                each.append(y)
            h, aux = carry
            if per_layer:
                aux = jax.tree.map(lambda *ys: jnp.stack(ys), *each)
            return (h, aux) if return_aux else h
        (h, aux), each = lax.scan(body, (h, aux0), (layers, keys))
        if per_layer:
            aux = each
        return (h, aux) if return_aux else h

    # -- a stack whose pattern is data ---------------------------------------

    def init_pattern(self, key, kinds, layer_init) -> Params:
        """The layers of a stack whose pattern is a list: ``kinds`` names
        each layer's kind in order (any hashable), ``layer_init(key, kind)``
        makes one layer's tree. Runs of like consecutive layers
        (:func:`layer_runs`) are stacked on a leading axis, one stack a run,
        under the run's index as two digits (``"00"``, ``"01"``, ...: the
        names sort in the stack's order)."""
        keys = jax.random.split(key, len(kinds))
        return {f"{r:02d}": jax.vmap(lambda k, kind=kind: layer_init(k, kind))(
                    keys[first:first + count])
                for r, (kind, first, count) in enumerate(layer_runs(kinds))}

    def run_pattern(self, stacks: Params, h, attn_bias=None,
                    dropout_key=None):
        """``h`` through :meth:`init_pattern`'s stacks in the order of their
        names, each run one scan of :meth:`run_layers` under the model's
        recompute policy: the compiled step grows with the runs of the
        pattern, not with its depth, and no pattern needs a class of its
        own (what kind a layer is shows in the tree ``_layer_aux`` is
        given). Returns ``(h, [each run's aux])``."""
        names = sorted(stacks)
        keys = ([None] * len(names) if dropout_key is None
                else jax.random.split(dropout_key, len(names)))
        auxes = []
        for name, key in zip(names, keys):
            h, aux = self.run_layers(stacks[name], h, attn_bias, key,
                                     return_aux=True)
            auxes.append(aux)
        return h, auxes


def latent_kv(model, p: Params, u: jax.Array, rank: int, heads: int):
    """Latent attention's down-projection, latent norm and up-projection
    (DeepSeek-V2/V3 MLA, expanded form; no reference analog), under the scope
    ``attn_latent``: ``[c; k_R] = u W_kva``, ``c' = RMSNorm(c)`` over the
    ``rank`` of the latent, ``kv = c' W_kvb`` as ``heads`` heads. Returns
    ``(u W_kva, kv)``: ``(b, s, rank + rope)`` and ``(b, heads, s, nope +
    v)``. ``model`` gives ``_proj`` and ``_rms``; what is rotated, and
    whether anything is, is the caller's."""
    b, s, _ = u.shape
    with jax.named_scope("attn_latent"):
        kva = model._proj(p["kv_a"], u)
        latent = model._rms(p["kv_norm"], kva[..., :rank])
        kv = model._proj(p["kv_b"], latent).reshape(
            b, s, heads, -1).transpose(0, 2, 1, 3)
    return kva, kv


def layer_runs(kinds) -> list:
    """``[(kind, first, count)]``: the runs of like consecutive entries of
    ``kinds``, one layer's kind each."""
    runs = []
    for i, kind in enumerate(kinds):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, i, 1])
    return [tuple(r) for r in runs]

"""Megatron-style GPT (reference: apex/transformer/testing/standalone_gpt.py:236-1517).

The reference vendors a full Megatron GPT (ParallelMLP, ParallelAttention,
ParallelTransformer, Embedding, GPTModel) as the test/benchmark vehicle for
its transformer framework. This is the TPU-native counterpart, built from
apex_tpu.transformer.tensor_parallel layers:

- token embedding: ``VocabParallelEmbedding`` (+ learned positions);
- per layer: LN → fused-QKV ``ColumnParallelLinear`` (no gather; output laid
  out ``(heads, 3, head_dim)`` so a TP shard holds whole heads, the layout
  contract of ParallelAttention, standalone_gpt.py:560-640) → flash attention
  on local heads → ``RowParallelLinear`` projection → residual → LN →
  column/row MLP with GeLU → residual;
- final LN → tied vocab-parallel LM head → ``vocab_parallel_cross_entropy``.

TPU-first structural choices (vs the reference's per-layer nn.ModuleList):

- layer parameters are **stacked** on a leading ``(num_layers, ...)`` dim and
  the stack is driven by ``lax.scan`` — one traced layer body regardless of
  depth (compile time O(1) in layers), and the natural shape for pipeline
  stages to slice;
- activation checkpointing is ``jax.checkpoint`` on the scanned body
  (reference: tensor_parallel/random.py:224-294 CheckpointFunction);
- dropout randomness comes from an explicit key, split per layer and folded
  per TP rank where state must differ (random.py:174-191 semantics).

Serial (``axis=None``) and shard_map-parallel execution use the same params
and the same code path, like the rest of the framework.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.models._transformer import TransformerBase
from apex_tpu.parallel.mesh import AXIS_MODEL
from apex_tpu.transformer import tensor_parallel as tp

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    """Model hyperparameters (reference: testing/arguments.py essentials)."""

    vocab_size: int = 50304
    hidden_size: int = 1024
    num_layers: int = 24
    num_attention_heads: int = 16
    max_seq_len: int = 1024
    ffn_hidden_size: Optional[int] = None  # default 4*hidden
    axis: Optional[str] = AXIS_MODEL  # tensor-parallel mesh axis (None=serial)
    # Megatron-style sequence parallelism ON THE TP AXIS (distinct from
    # context_axis/sequence_parallel_impl below, which shard attention
    # itself): each layer's two forward TP all-reduces decompose into
    # psum_scatter + all_gather conjugates and the LN/dropout/residual
    # regions run sequence-sharded (b, s/tp, h) — 1/tp the activation
    # bytes there, and two schedulable collectives instead of one
    # synchronous all-reduce (VERDICT r5: all 9 TP all-reduces compiled
    # synchronous). Ignored when axis is None; requires max_seq_len
    # divisible by tp. No reference analog (apex predates Megatron SP).
    sequence_parallel: bool = False
    # Quantized wire dtype ("int8" | "e5m2") for the sequence-parallel
    # activation conjugates (requires sequence_parallel=True): the
    # scatter/gather payloads encode to 1 B/elem with per-shard fp32
    # scales riding a tiny side-channel (parallel/quantize.py), summed in
    # fp32 after decode. Activations carry no error-feedback residual —
    # fresh values every step bound the error by the per-shard scale.
    # None = exact wire (the default; traces bit-identical to pre-knob).
    activation_comm_dtype: Optional[str] = None
    params_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    hidden_dropout: float = 0.1
    init_method_std: float = 0.02
    remat: bool = True  # activation checkpointing per layer
    # selective checkpoint policy: None/"full" | "save_attn" | "dots"
    # (models/_transformer._remat_policy)
    remat_policy: Optional[str] = None
    attention_impl: str = "auto"  # flash_attention impl switch
    # Sliding-window (local) attention: each token attends only its
    # `attention_window` most recent positions (flash_attention's `window`
    # semantics). O(s·w) attention cost — the standard long-context pairing
    # with the streamed kernels; composes with context parallelism (the
    # window is defined in global positions and rides the ring offsets).
    # None = full attention. Beyond-reference capability.
    attention_window: Optional[int] = None
    # Position encoding: "learned" (reference parity — a trained
    # (max_seq_len, hidden) table) | "rope" (rotary on q/k, NO position
    # params at all: at 1M tokens the learned table is ~3.75 GB of
    # params + Adam state; relative-distance property makes it exact
    # under context parallelism with shard-offset positions) | "none".
    position_embedding: str = "learned"
    rope_theta: float = 10000.0
    # Drive the (still stacked) layer params with an unrolled Python loop
    # of static per-layer slices instead of lax.scan. Measured on-chip at
    # 345M: the scan's backward accumulates layer grads through
    # dynamic-update-slice fusions (~28 ms/step, 11% of the grad step) and
    # pins the remat recompute; the unrolled body drops the grad step
    # 230 -> 188 ms (PERF_NOTES r5). Cost: compile time O(depth) instead
    # of O(1) — fine at flagship depth, keep False for very deep or
    # pipelined configs (pipeline stages already slice the stack).
    unroll_layers: bool = False
    # ZeRO-3 gather prefetch depth (unrolled path only): double-buffer the
    # per-layer just-in-time chunk all-gathers — issue layer i+N's gather
    # before layer i's compute, forward AND backward re-gathers
    # (models/_transformer._prefetched_zero3_drive), so the gathers stand
    # structurally ahead of the compute that hides them instead of pinned
    # inside the rematerialized body. 0 = the serialized in-body gather;
    # N=1 is classic double buffering. Peak param residency grows to
    # N+1 layers + chunks. Tripwire: lint.trace.unprefetched_gather_hazards.
    zero3_prefetch: int = 0
    # chunked fused LM-head CE (ops/lm_head_loss): avoids materializing the
    # (tokens, vocab) logits when computing the loss. Serial (axis=None) only;
    # under TP the vocab is already sharded V/tp ways.
    lm_head_chunks: Optional[int] = None
    # sequence/context parallelism (long-context; NEW vs the reference,
    # SURVEY.md §2.3 row SP): shard the sequence dim over this mesh axis and
    # attend with ring attention (ppermute block exchange) or Ulysses
    # all-to-all. Run under shard_map with tokens sharded on dim 1.
    context_axis: Optional[str] = None
    sequence_parallel_impl: str = "ring"  # 'ring' | 'ulysses'
    # mixture-of-experts FFN (NEW vs the reference, SURVEY.md §2.3 row EP):
    # when moe_num_experts is set, every layer's dense FFN becomes a top-k
    # routed MoEMLP (transformer/moe.py). moe_expert_axis shards experts
    # over that mesh axis with all_to_all dispatch — run under shard_map
    # with the batch dim sharded over the same axis (the data axis).
    moe_num_experts: Optional[int] = None
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_expert_axis: Optional[str] = None
    moe_aux_loss_weight: float = 0.01
    moe_z_loss_weight: float = 1e-3
    # Quantized wire dtype ("int8" | "e5m2") for the expert-parallel
    # dispatch/combine all_to_all payloads (requires moe_expert_axis when
    # set; ignored on a serial build — the serial-twin convention of
    # activation_comm_dtype): token buckets encode to 1 B/elem with fp32
    # per-destination-block scales riding a tiny side-channel, forward AND
    # backward (parallel/quantize.quantized_all_to_all). Activations carry
    # no error-feedback residual. None = exact wire.
    moe_dispatch_dtype: Optional[str] = None

    @property
    def ffn(self) -> int:
        return self.ffn_hidden_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


class GPTModel(TransformerBase):
    """Functional GPT with TP-sharded params (GPTModel, standalone_gpt.py:1361+).

    ``init(key)`` → full param tree; ``specs()`` → PartitionSpec tree;
    ``apply(params, tokens, targets=..., dropout_key=...)`` → per-token loss
    (or logits when ``targets`` is None). ``embed`` / ``run_layers`` /
    ``head`` expose the stage boundaries pipeline schedules need (the
    functional replacement for the reference's pre_process/post_process
    flags and set_input_tensor, pipeline_parallel/schedules/common.py:24-112).
    Shared transformer plumbing lives in TransformerBase (models/_transformer).
    """

    causal = True

    def __init__(self, config):
        super().__init__(config)
        c = config
        if c.position_embedding not in ("learned", "rope", "none"):
            raise ValueError(
                f"position_embedding must be learned|rope|none, got "
                f"{c.position_embedding!r}")
        if c.position_embedding == "rope" and c.head_dim % 2:
            raise ValueError(
                f"rope needs an even head_dim, got {c.head_dim}")
        if c.sequence_parallel and c.moe_num_experts is not None:
            raise ValueError(
                "sequence_parallel does not compose with MoE FFNs yet: the "
                "router must see gathered tokens (the dense fc1/fc2 gather/"
                "reduce-scatter pair has no MoE counterpart here)")
        if c.moe_num_experts is not None:
            from apex_tpu.transformer.moe import MoEMLP

            self.moe = MoEMLP(
                c.hidden_size, c.ffn, num_experts=c.moe_num_experts,
                top_k=c.moe_top_k, capacity_factor=c.moe_capacity_factor,
                expert_axis=c.moe_expert_axis,
                tp_axis=c.axis,  # expert FFNs ride the model axis (EP x TP)
                params_dtype=c.params_dtype,
                init_method=tp.scaled_normal(c.init_method_std),
                # serial-twin convention (activation_comm_dtype): a serial
                # build of an expert-parallel config must run — there is
                # no dispatch wire to quantize without the expert axis
                dispatch_dtype=(c.moe_dispatch_dtype
                                if c.moe_expert_axis is not None else None),
            )

    # -- parameters ---------------------------------------------------------

    def _layer_init(self, k: jax.Array) -> Params:
        if self.cfg.moe_num_experts is None:
            return super()._layer_init(k)
        # build only what the MoE block uses — initializing the dense
        # fc1/fc2 just to discard them would materialize the full FFN
        # weights once per layer under the vmapped stack init
        ks = jax.random.split(k, 3)
        return {
            "ln1": self._ln_init(),
            "qkv": self.qkv.init(ks[0]),
            "proj": self.proj.init(ks[1]),
            "ln2": self._ln_init(),
            "moe": self.moe.init(ks[2]),
        }

    def layer_stack_specs(self) -> Params:
        if self.cfg.moe_num_experts is None:
            return super().layer_stack_specs()
        from apex_tpu.models._transformer import stack_specs

        ln = {"scale": P(), "bias": P()}
        return {
            "ln1": stack_specs(ln),
            "qkv": stack_specs(self.qkv.specs()),
            "proj": stack_specs(self.proj.specs()),
            "ln2": stack_specs(ln),
            "moe": stack_specs(self.moe.specs()),
        }

    def init(self, key: jax.Array) -> Params:
        c = self.cfg
        keys = jax.random.split(key, 4)
        tree = {
            "embedding": self.embedding.init(keys[0]),
            "layers": self.init_layer_stack(keys[2]),
            "ln_f": self._ln_init(),
        }
        if c.position_embedding == "learned":
            tree["position"] = tp.scaled_normal(c.init_method_std)(
                keys[1], (c.max_seq_len, c.hidden_size), c.params_dtype)
        return tree

    def specs(self) -> Params:
        tree = {
            "embedding": self.embedding.specs(),
            "layers": self.layer_stack_specs(),
            "ln_f": {"scale": P(), "bias": P()},
        }
        if self.cfg.position_embedding == "learned":
            tree["position"] = P()
        return tree

    # -- stages -------------------------------------------------------------

    def embed(self, params: Params, tokens: jax.Array) -> jax.Array:
        c = self.cfg
        with jax.named_scope("embed"):
            h = self.embedding.apply(params["embedding"], tokens)
            if c.position_embedding == "learned":
                # positions add AFTER the embedding's closing collective
                # (h.shape[1] is the sequence-parallel shard under SP):
                # adding them to the pre-reduce partial sums would count
                # them once per TP rank through the psum/psum_scatter
                h = h + self._positions(params["position"], h.shape[1])
            # "rope": positions enter at the q/k rotation in _attention;
            # "none": no positional signal at the embedding
            return h.astype(c.compute_dtype)

    def _layer(self, p: Params, h: jax.Array, key, bias=None) -> jax.Array:
        """Pre-LN block: residual + sublayer(LN(h))."""
        return self._layer_aux(p, h, key, bias)[0]

    def _aux_init(self):
        if self.cfg.moe_num_experts is None:
            return None
        return {"load_balancing_loss": jnp.zeros(()),
                "router_z_loss": jnp.zeros(()),
                # summed over layers by run_layers; divide by num_layers
                # for the mean per-layer drop rate (pure metric)
                "dropped_fraction": jnp.zeros(())}

    def _layer_aux(self, p: Params, h: jax.Array, key, bias):
        """One pre-LN block body for both FFN variants: dense MLP (aux is
        None) or routed experts (aux = router losses)."""
        c = self.cfg
        k1, k2 = (None, None) if key is None else tuple(jax.random.split(key))
        # Post-residual dropout is replicated across TP ranks (same key);
        # the reference draws it from the default (data-parallel) RNG state.
        h = h + self._dropout(self._attention(p, self._ln(p["ln1"], h), bias), k1)
        x = self._ln(p["ln2"], h)
        if c.moe_num_experts is None:
            out, aux = self._mlp(p, x), None
        elif c.moe_expert_axis is not None:
            out, aux = self.moe.apply_expert_parallel(p["moe"], x)
        else:
            out, aux = self.moe.apply(p["moe"], x)
        return h + self._dropout(out, k2), aux

    def head(
        self, params: Params, h: jax.Array,
        targets: Optional[jax.Array] = None,
    ):
        """Final LN + tied LM head (+ per-token loss when targets given)
        (post_language_model_processing, standalone_gpt.py:1361+)."""
        c = self.cfg
        with jax.named_scope("head"):
            h = self._ln(params["ln_f"], h)
            if c.axis is None and c.lm_head_chunks and targets is not None:
                from apex_tpu.ops.lm_head_loss import lm_head_cross_entropy

                return lm_head_cross_entropy(
                    h, params["embedding"]["embedding"], targets,
                    c.lm_head_chunks)
            wte = params["embedding"]["embedding"].astype(h.dtype)  # (V/tp, H)
            if c.axis is not None:
                if c.sequence_parallel:
                    # close the sequence-sharded region: all-gather forward;
                    # the backward reduce-scatter sums the per-vocab-shard
                    # partial cotangents AND re-shards the sequence — the
                    # copy_to psum and the scatter in one conjugate
                    h = tp.gather_from_sequence_parallel_region(
                        h, c.axis, True, self._acd)
                else:
                    h = tp.copy_to_tensor_model_parallel_region(h, c.axis)
            logits = jnp.einsum("bsh,vh->bsv", h, wte)  # vocab-sharded logits
            if targets is None:
                return logits
            return tp.vocab_parallel_cross_entropy(logits, targets, axis=c.axis)

    def aux_to_loss(self, aux) -> jax.Array:
        """Canonical (linear) fold of accumulated router aux losses into a
        scalar loss term — the single definition shared by serial ``apply``,
        the pipelined ``aux_to_loss`` hook, and the multi-chip gate."""
        c = self.cfg
        return (c.moe_aux_loss_weight * aux["load_balancing_loss"]
                + c.moe_z_loss_weight * aux["router_z_loss"]) / c.num_layers

    def apply(
        self,
        params: Params,
        tokens: jax.Array,
        targets: Optional[jax.Array] = None,
        dropout_key: Optional[jax.Array] = None,
        layer_chunk_meta=None,
    ):
        """``layer_chunk_meta`` drives the ZeRO-3 fully-sharded path:
        ``params["layers"]`` is then a per-row chunk stack gathered
        just-in-time per layer (run_layers ``chunk_meta``); the non-layer
        params must arrive already gathered (the step wrapper's job —
        transformer/amp.build_zero_train_step)."""
        h = self.embed(params, tokens)
        h, aux = self.run_layers(params["layers"], h, dropout_key=dropout_key,
                                 return_aux=True,
                                 chunk_meta=layer_chunk_meta)
        out = self.head(params, h, targets)
        if aux is not None and targets is not None:
            # fold per-layer-averaged router losses into the per-token loss
            # (a scalar added uniformly keeps the mean-loss contract)
            out = out + self.aux_to_loss(aux).astype(out.dtype)
        return out

    # -- serving drives (apex_tpu/serve/engine.py) --------------------------
    # Inference-only siblings of embed/run_layers/head: same parameter tree,
    # same per-token math (so greedy decode bit-matches the training
    # forward's argmax — the serve equivalence gate), but threaded through
    # the paged KV cache instead of recomputing the whole context per token.

    def check_servable(self) -> None:
        """Serving composes with TP, attention_window, and MoE FFNs
        (serial experts or expert-parallel decode: per-tick top-k routing
        is data, not shapes, so the decode program stays shape-stable —
        :meth:`_serve_ffn`); the modes that reshape the sequence (CP
        rings, Megatron SP) have no decode-cache story yet — fail loudly
        at engine build. An expert-parallel build (``moe_expert_axis``)
        additionally needs the mesh at the engine (engine-side check)."""
        c = self.cfg
        if getattr(c, "context_axis", None) is not None:
            raise ValueError(
                "serving does not support context parallelism: the paged "
                "cache is per-slot, not ring-sharded — run decode with "
                "context_axis=None")
        if self._sp:
            raise ValueError(
                "serving does not support sequence_parallel=True: decode "
                "works on single-token sequences that cannot shard s/tp "
                "ways — build the serve model with sequence_parallel=False")

    def embed_at(self, params: Params, tokens: jax.Array,
                 positions: jax.Array) -> jax.Array:
        """:meth:`embed` at EXPLICIT per-slot positions ``(b, s)`` — at a
        decode tick every slot's new token sits at its own context
        position, so the training method's ``[0, s)`` slice cannot serve.
        Same math (embedding collective + position-row add) at equal
        positions."""
        c = self.cfg
        with jax.named_scope("embed"):
            h = self.embedding.apply(params["embedding"], tokens)
            if c.position_embedding == "learned":
                h = h + jnp.take(params["position"], positions, axis=0)
            return h.astype(c.compute_dtype)

    def _serve_ffn(self, p: Params, x: jax.Array) -> jax.Array:
        """The FFN half of a serving layer: the dense MLP, or the routed
        MoE block at inference (aux losses dropped — nothing trains).
        Expert-parallel builds dispatch through the token-replicated
        conjugate (``MoEMLP.apply_expert_sharded``: identical routing on
        every rank, local-expert compute, one psum combine — the same
        function as serial ``apply``, so greedy streams match the serial
        engine's bit for bit)."""
        c = self.cfg
        if c.moe_num_experts is None:
            return self._mlp(p, x)
        if c.moe_expert_axis is not None:
            return self.moe.apply_expert_sharded(p["moe"], x)
        return self.moe.apply(p["moe"], x)[0]

    def serve_layers_prefill(self, layers: Params, h: jax.Array):
        """Run the layer stack over a PROMPT, collecting every layer's k/v
        head tensors for the cache fill. Returns ``(h, k, v)`` with k/v
        shaped ``(num_layers, b, n_local_heads, s, head_dim)``. Attention
        is the training `_attend` (causal + ``attention_window``), so
        prefill hidden states match the training forward exactly."""

        def body(h, p):
            x = self._ln(p["ln1"], h)
            q, k, v = self._qkv_heads(p["qkv"], x)
            h = h + self._attn_out(p, self._attend(q, k, v, None))
            h = h + self._serve_ffn(p, self._ln(p["ln2"], h))
            return h, (k, v)

        h, (ks, vs) = lax.scan(body, h, layers)
        return h, ks, vs

    def serve_layers_decode(self, layers: Params, h: jax.Array,
                            k_pages: jax.Array, v_pages: jax.Array,
                            block_tables: jax.Array, write_flat: jax.Array,
                            attend_lengths: jax.Array,
                            positions: jax.Array):
        """One decode tick through the layer stack: for each layer, write
        the new token's k/v heads into the paged cache (``write_flat``:
        per-slot flat position index ``block_id * block + offset`` — the
        engine owns the page arithmetic; idle slots point at the reserved
        null page), then flash-decode the token's query over the pages.
        ``h`` is ``(b, 1, hidden)``; the caches are layer-stacked
        ``(L, num_blocks, kv_heads, block, head_dim)`` (block in the
        sublane dim — serve/cache.py layout) and scan ys rebuild them
        updated. ``attend_lengths`` includes the token just written
        (0 = idle slot, output exactly 0)."""
        from apex_tpu.ops.flash_decode import flash_decode

        c = self.cfg

        def body(h, xs):
            p, kp, vp = xs
            blk = kp.shape[2]
            bi, off = write_flat // blk, write_flat % blk
            x = self._ln(p["ln1"], h)
            q, k, v = self._qkv_heads(p["qkv"], x,
                                      positions=positions[:, None])
            # advanced indices split by the head slice land in front:
            # kp[bi, :, off] is (b, kv_heads, d), matching the new heads
            kp = kp.at[bi, :, off].set(k[:, :, 0, :].astype(kp.dtype))
            vp = vp.at[bi, :, off].set(v[:, :, 0, :].astype(vp.dtype))
            attn = flash_decode(
                q[:, :, 0, :], kp, vp, block_tables, attend_lengths,
                window=c.attention_window, impl=c.attention_impl)
            h = h + self._attn_out(p, attn[:, :, None, :])
            h = h + self._serve_ffn(p, self._ln(p["ln2"], h))
            return h, (kp, vp)

        h, (kps, vps) = lax.scan(body, h, (layers, k_pages, v_pages))
        return h, kps, vps

    def serve_layers_multi(self, layers: Params, h: jax.Array,
                           k_pages: jax.Array, v_pages: jax.Array,
                           block_tables: jax.Array, write_flat: jax.Array,
                           attend_lengths: jax.Array,
                           positions: jax.Array):
        """K-token sibling of :meth:`serve_layers_decode`: per layer, write
        K new tokens' k/v heads per slot into the paged cache (``write_flat``
        ``(b, K)`` flat position indices; masked rows point at the null
        page), then K-query flash-decode over the pages with TRAILING-query
        semantics (``attend_lengths[b]`` = keys visible to the FINAL query;
        query ``j`` sees ``attend_lengths[b] - (K-1-j)`` — in-chunk
        causality by length arithmetic). ``h`` is ``(b, K, hidden)``,
        ``positions`` ``(b, K)``. Drives both chunked prefill (one slot, K
        = chunk) and speculative verify (every slot, K = drafts + 1) from
        the same compiled structure."""
        from apex_tpu.ops.flash_decode import flash_decode_multi

        c = self.cfg

        def body(h, xs):
            p, kp, vp = xs
            blk = kp.shape[2]
            bi, off = write_flat // blk, write_flat % blk
            x = self._ln(p["ln1"], h)
            q, k, v = self._qkv_heads(p["qkv"], x, positions=positions)
            # (b, nh, K, d) -> (b, K, nh, d): kp[bi, :, off] is
            # (b, K, kv_heads, d) with the (b, K) advanced indices in front
            kp = kp.at[bi, :, off].set(
                k.transpose(0, 2, 1, 3).astype(kp.dtype))
            vp = vp.at[bi, :, off].set(
                v.transpose(0, 2, 1, 3).astype(vp.dtype))
            attn = flash_decode_multi(
                q, kp, vp, block_tables, attend_lengths,
                window=c.attention_window, impl=c.attention_impl)
            h = h + self._attn_out(p, attn)
            h = h + self._serve_ffn(p, self._ln(p["ln2"], h))
            return h, (kp, vp)

        h, (kps, vps) = lax.scan(body, h, (layers, k_pages, v_pages))
        return h, kps, vps

    def serve_head(self, params: Params, h: jax.Array) -> jax.Array:
        """Final LN + tied LM head returning FULL-vocab logits on every
        rank: under TP the vocab-sharded logits all-gather over the model
        axis (the mappings.py conjugate), so argmax/sampling is one
        consistent decision everywhere — the serving replacement for the
        training head's sharded-logit + vocab-parallel-CE pair."""
        c = self.cfg
        with jax.named_scope("head"):
            x = self._ln(params["ln_f"], h)
            wte = params["embedding"]["embedding"].astype(x.dtype)  # (V/tp, H)
            if c.axis is not None:
                x = tp.copy_to_tensor_model_parallel_region(x, c.axis)
            logits = jnp.einsum("bsh,vh->bsv", x, wte)
            if c.axis is not None:
                logits = tp.gather_from_tensor_model_parallel_region(
                    logits, c.axis)
            return logits

    def loss(self, params, tokens, targets, dropout_key=None,
             layer_chunk_meta=None) -> jax.Array:
        """Mean per-token loss — the fwd_step_func contract
        (schedules/common.py:196-255 loss reduction)."""
        per_token = self.apply(params, tokens, targets, dropout_key,
                               layer_chunk_meta=layer_chunk_meta)
        with jax.named_scope("head"):
            return jnp.mean(per_token)

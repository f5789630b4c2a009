"""Megatron-style BERT (reference: apex/transformer/testing/standalone_bert.py).

The reference vendors a Megatron BERT (BertModel + BertLMHead +
post_language_model_processing, standalone_bert.py:35-216) as the second test
vehicle for its transformer framework; the BASELINE.md config-3 workload is
BERT-large pretraining with FusedLAMB + FusedLayerNorm. This is the TPU-native
counterpart, sharing the GPT model's structural choices (stacked layer params
driven by ``lax.scan``, `jax.checkpoint` remat, serial==sharded code path) with
BERT's own semantics:

- bidirectional attention under a **padding mask** built from
  ``attention_mask`` (bert_extended_attention_mask, standalone_bert.py:10-23 —
  additive -10000 bias instead of masked_fill);
- word + learned-position + **tokentype** embeddings, then embedding LN +
  dropout (Megatron Embedding with tokentype, standalone_gpt.py:236-420);
- **post-LN** encoder blocks (residual add *then* LayerNorm);
- MLM head: dense+gelu+LN then the tied vocab-parallel decode with bias
  (BertLMHead, standalone_bert.py:35-74);
- optional binary (NSP) head on the pooled [CLS] (Pooler + binary head,
  post_language_model_processing, standalone_bert.py:76-98);
- masked-LM loss = vocab-parallel cross entropy over masked positions only
  (loss-mask weighting, the lm_loss_/loss_mask contract of the reference's
  bert fwd_step, run_bert_minimal_test.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.models._transformer import SegmentMask, TransformerBase
from apex_tpu.parallel.mesh import AXIS_MODEL
from apex_tpu.transformer import tensor_parallel as tp

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class BertConfig:
    """BERT hyperparameters (bert-large defaults; testing/arguments.py)."""

    vocab_size: int = 30592  # 30522 padded to a TP-friendly multiple
    hidden_size: int = 1024
    num_layers: int = 24
    num_attention_heads: int = 16
    max_seq_len: int = 512
    type_vocab_size: int = 2
    ffn_hidden_size: Optional[int] = None
    axis: Optional[str] = AXIS_MODEL
    # Megatron-style sequence parallelism on the TP axis (see
    # GPTConfig.sequence_parallel): decomposed TP collectives +
    # sequence-sharded LN/dropout/residual regions; the MLM head gathers
    # the sequence back at entry (the [CLS] pooler and the tied decode see
    # the full sequence). Ignored when axis is None.
    sequence_parallel: bool = False
    # Quantized wire dtype ("int8" | "e5m2") for the sequence-parallel
    # activation conjugates (requires sequence_parallel=True) — see
    # GPTConfig.activation_comm_dtype. None = exact wire.
    activation_comm_dtype: Optional[str] = None
    params_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    hidden_dropout: float = 0.1
    init_method_std: float = 0.02
    remat: bool = True
    add_binary_head: bool = True
    attention_impl: str = "auto"
    # symmetric sliding-window attention (bidirectional band
    # [p-w+1, p+w-1]; flash_attention `window` semantics). None = full.
    attention_window: Optional[int] = None
    # unrolled layer drive (same stacked params, static per-layer slices):
    # avoids the layer scan's dynamic-update-slice grad stacking — see
    # GPTConfig.unroll_layers and PERF_NOTES r5
    unroll_layers: bool = False
    # ZeRO-3 gather prefetch depth on the unrolled path (double-buffered
    # per-layer chunk all-gathers — see GPTConfig.zero3_prefetch); the
    # prefetch drive is dense/dropout-off only, so BERT runs it through
    # the pipelined ZeRO-3 step, not the SegmentMask attention path
    zero3_prefetch: int = 0
    # sequence (context) parallelism over this mesh axis — the shared
    # TransformerBase._attend ring/Ulysses path (bidirectional here).
    # Padding attention_masks work: they become segment ids whose kv
    # shards ride the K/V ring (SegmentMask, models/_transformer.py), and
    # the NSP pooler replicates the global [CLS] across shards
    context_axis: Optional[str] = None
    sequence_parallel_impl: str = "ring"  # 'ring' | 'ulysses'

    @property
    def ffn(self) -> int:
        return self.ffn_hidden_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def extended_attention_mask(attention_mask: jax.Array) -> jax.Array:
    """(b, s) 1/0 padding mask → additive (b, 1, 1, s) bias
    (bert_extended_attention_mask, standalone_bert.py:10-23)."""
    bias = (1.0 - attention_mask.astype(jnp.float32)) * -10000.0
    return bias[:, None, None, :]


class BertModel(TransformerBase):
    """Functional BERT with TP-sharded params.

    ``apply(params, tokens, attention_mask, tokentype_ids=..., ...)`` returns
    ``(lm_logits, binary_logits)``; ``loss(...)`` the masked-LM (+NSP) loss.
    ``embed`` / ``run_layers`` / ``head`` expose pipeline stage boundaries
    like GPTModel. Shared transformer plumbing lives in TransformerBase
    (models/_transformer); BERT keeps post-LN blocks and a padding-mask bias.
    """

    causal = False

    # -- parameters ---------------------------------------------------------

    def init(self, key: jax.Array) -> Params:
        c = self.cfg
        keys = jax.random.split(key, 8)
        pos = tp.scaled_normal(c.init_method_std)(
            keys[1], (c.max_seq_len, c.hidden_size), c.params_dtype)
        tokentype = tp.scaled_normal(c.init_method_std)(
            keys[2], (c.type_vocab_size, c.hidden_size), c.params_dtype)

        layers = self.init_layer_stack(keys[3])

        params = {
            "embedding": self.embedding.init(keys[0]),
            "position": pos,
            "tokentype": tokentype,
            "ln_emb": self._ln_init(),
            "layers": layers,
            # BertLMHead (standalone_bert.py:46-74): dense+gelu+LN, then the
            # tied decode plus a vocab-sharded output bias.
            "lm_dense": self._dense_init(keys[4], c.hidden_size, c.hidden_size),
            "lm_ln": self._ln_init(),
            "lm_bias": jnp.zeros((c.vocab_size,), c.params_dtype),
        }
        if c.add_binary_head:
            params["pooler"] = self._dense_init(keys[5], c.hidden_size, c.hidden_size)
            params["binary_head"] = self._dense_init(keys[6], c.hidden_size, 2)
        return params

    def specs(self) -> Params:
        c = self.cfg
        ln = {"scale": P(), "bias": P()}
        dense = {"kernel": P(), "bias": P()}

        specs = {
            "embedding": self.embedding.specs(),
            "position": P(),
            "tokentype": P(),
            "ln_emb": ln,
            "layers": self.layer_stack_specs(),
            "lm_dense": dense,
            "lm_ln": ln,
            "lm_bias": P(c.axis) if c.axis else P(),
        }
        if c.add_binary_head:
            specs["pooler"] = dense
            specs["binary_head"] = dense
        return specs

    # -- stages -------------------------------------------------------------

    def embed(
        self,
        params: Params,
        tokens: jax.Array,
        tokentype_ids: Optional[jax.Array] = None,
        dropout_key: Optional[jax.Array] = None,
    ) -> jax.Array:
        c = self.cfg
        with jax.named_scope("embed"):
            h = self.embedding.apply(params["embedding"], tokens)
            # h.shape[1] is the sequence-parallel shard length under SP
            # (the embedding reduce-scattered); positions/tokentypes add
            # after the closing collective, never to the partial sums
            h = h + self._positions(params["position"], h.shape[1])
            if tokentype_ids is not None:
                if self._sp:
                    s_local = h.shape[1]
                    tokentype_ids = lax.dynamic_slice_in_dim(
                        tokentype_ids, lax.axis_index(c.axis) * s_local,
                        s_local, axis=1)
                h = h + jnp.take(self._sp_param(params["tokentype"]),
                                 tokentype_ids, axis=0)
            h = self._ln(params["ln_emb"], h.astype(c.compute_dtype))
            return self._dropout(h, dropout_key).astype(c.compute_dtype)

    def _layer(self, p: Params, h: jax.Array, key, bias=None) -> jax.Array:
        """Post-LN block: LN(residual + sublayer(h))."""
        k1, k2 = (None, None) if key is None else tuple(jax.random.split(key))
        h = self._ln(p["ln1"], h + self._dropout(self._attention(p, h, bias), k1))
        h = self._ln(p["ln2"], h + self._dropout(self._mlp(p, h), k2))
        return h

    def head(
        self,
        params: Params,
        h: jax.Array,
        masked_lm_labels: Optional[jax.Array] = None,
    ):
        """MLM decode (+ binary logits). With labels: per-token vocab-parallel
        CE (post_language_model_processing, standalone_bert.py:76-98)."""
        c = self.cfg
        with jax.named_scope("head"):
            if self._sp:
                # close the sequence-sharded region before anything reads
                # global positions (the [CLS] pooler) or the tied decode.
                # Everything downstream — lm_dense, lm_ln, the copy_to'd
                # decode, the CE psums — is REPLICATED across TP ranks, so
                # the gather's adjoint is a plain slice of the replicated
                # cotangent (tensor_parallel_output_grad=False); a
                # reduce-scatter there would double-count what copy_to's
                # backward psum already summed.
                h = tp.gather_from_sequence_parallel_region(
                    h, c.axis, False, self._acd)
            binary_logits = None
            if c.add_binary_head:
                cls = h[:, 0]
                if c.context_axis is not None:
                    # The global [CLS] (global position 0) lives on rank 0's
                    # shard; replicate it with a BARE psum of the rank-0-
                    # masked slice. Gradient bookkeeping: under
                    # check_vma=False psum transposes to psum, so rank 0's
                    # h[:, 0] cotangent arrives ×axis_size while other
                    # ranks get 0 — exactly cancelled by the pmean-over-
                    # context gradient reduction for replicated params
                    # (allreduce_gradients_by_spec / the CP test harness),
                    # the same bookkeeping as the ×n LM term in loss().
                    rank = lax.axis_index(c.context_axis)
                    cls = lax.psum(
                        jnp.where(rank == 0, cls, jnp.zeros_like(cls)),
                        c.context_axis)
                pooled = jnp.tanh(self._dense(params["pooler"], cls))
                binary_logits = self._dense(params["binary_head"],
                                            pooled.astype(jnp.float32))
            g = jax.nn.gelu(self._dense(params["lm_dense"], h))
            # past the head gather: replicated region, no γβ grad wrap
            g = self._ln(params["lm_ln"], g, sequence_region=False)
            if c.axis is not None:
                g = tp.copy_to_tensor_model_parallel_region(g, c.axis)
            wte = params["embedding"]["embedding"].astype(g.dtype)  # (V/tp, H)
            logits = (jnp.einsum("bsh,vh->bsv", g, wte)
                      + params["lm_bias"].astype(g.dtype))
            if masked_lm_labels is None:
                return logits, binary_logits
            lm_loss = tp.vocab_parallel_cross_entropy(
                logits, masked_lm_labels, axis=c.axis)
            return lm_loss, binary_logits

    def apply(
        self,
        params: Params,
        tokens: jax.Array,
        attention_mask: Optional[jax.Array] = None,
        tokentype_ids: Optional[jax.Array] = None,
        masked_lm_labels: Optional[jax.Array] = None,
        dropout_key: Optional[jax.Array] = None,
        layer_chunk_meta=None,
    ):
        if attention_mask is None:
            bias = None
        elif self.cfg.context_axis is not None:
            # Under sequence sharding the padding mask becomes SEGMENT IDS
            # (valid=1, pad=0 with pad_id=0): the kv-id shards ride the
            # K/V ring, so no (sq, SK) bias ever materializes. Same
            # function as the additive -10000 bias for every position the
            # loss can see: padded KEYS are never attended either way, and
            # padded query rows (output 0 here vs a normal mix under the
            # bias) are exactly the rows loss_mask zeroes.
            seg = attention_mask.astype(jnp.int32)
            bias = SegmentMask(q_seg=seg, kv_seg=seg, pad_id=0)
        else:
            bias = extended_attention_mask(attention_mask)
        k_emb = k_layers = None
        if dropout_key is not None:
            k_emb, k_layers = jax.random.split(dropout_key)
        h = self.embed(params, tokens, tokentype_ids, k_emb)
        # layer_chunk_meta = the ZeRO-3 fully-sharded drive (per-layer JIT
        # weight gather, models/_transformer.run_layers chunk_meta)
        h = self.run_layers(params["layers"], h, bias, k_layers,
                            chunk_meta=layer_chunk_meta)
        return self.head(params, h, masked_lm_labels)

    def loss(
        self,
        params: Params,
        tokens: jax.Array,
        attention_mask: jax.Array,
        loss_mask: jax.Array,
        masked_lm_labels: jax.Array,
        nsp_labels: Optional[jax.Array] = None,
        tokentype_ids: Optional[jax.Array] = None,
        dropout_key: Optional[jax.Array] = None,
        layer_chunk_meta=None,
    ) -> jax.Array:
        """lm_loss averaged over masked positions (+ NSP CE), the bert
        fwd_step contract (run_bert_minimal_test.py loss_func).

        Under ``context_axis`` the return is the LOCAL term whose
        pmean-over-context equals the global loss (the repo's local-loss +
        pmean-gradients convention): the masked mean normalizes by the
        GLOBAL weight sum — a per-shard mean would mis-weight shards with
        unequal masked-token counts — scaled by axis_size so the harness's
        pmean recovers sum/W exactly."""
        lm_loss, binary_logits = self.apply(
            params, tokens, attention_mask, tokentype_ids,
            masked_lm_labels, dropout_key,
            layer_chunk_meta=layer_chunk_meta)
        with jax.named_scope("head"):
            return self._masked_loss(lm_loss, binary_logits, loss_mask,
                                     nsp_labels)

    def _masked_loss(self, lm_loss, binary_logits, loss_mask, nsp_labels):
        c = self.cfg
        w = loss_mask.astype(jnp.float32)
        local = jnp.sum(lm_loss * w)
        if c.context_axis is not None:
            n = lax.axis_size(c.context_axis)
            total_w = lax.psum(jnp.sum(w), c.context_axis)
            # total_w has no parameter dependence: safe outside the grad
            # path (stop_gradient makes that explicit)
            loss = local * n / jnp.maximum(lax.stop_gradient(total_w), 1.0)
        else:
            loss = local / jnp.maximum(jnp.sum(w), 1.0)
        if nsp_labels is not None and binary_logits is not None:
            logp = jax.nn.log_softmax(binary_logits.astype(jnp.float32))
            nsp = -jnp.mean(jnp.take_along_axis(logp, nsp_labels[:, None], axis=1))
            loss = loss + nsp
        return loss

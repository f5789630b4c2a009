"""A causal LM of Liquid AI's LFM2 family with routed experts
(``model_type: lfm2_moe``, LFM2-8B-A1B), as one rank of an expert-parallel
stage holds it. No reference analog: apex's model zoo is one dense pre-LN
block whose every layer attends.

Most layers mix tokens with a gated short convolution and a few with
grouped-query attention, in an order a list gives (``layer_types``; the
published list is not strictly periodic). The first ``num_dense_layers`` feed
forward through a gated MLP, the rest through routed experts. So a layer is
one of four kinds, and the stack is built from the two keys alone: runs of
like layers, each one scan (``TransformerBase.init_pattern`` /
``run_pattern``). Every size is a field of :class:`Lfm2Config`. Bias-free;
every layer is ``x = x + operator(RMSNorm(x)); x = x + ffn(RMSNorm(x))``.

- **conv operator** (``Lfm2ShortConv``): ``B, C, u`` = the three thirds of
  ``in_proj(h)``; ``out_proj(C * conv(B * u))`` with a causal depthwise
  filter of ``conv_taps`` taps (``conv_L_cache``), no activation
  (:func:`apex_tpu.ops.short_conv.gated_short_conv`).
- **attention operator**: ``num_attention_heads`` query heads over
  ``num_kv_heads`` key-value heads, RMSNorm over the head's width of every
  query and key head ahead of the rotation, rotary over the whole head
  (halves rotated together), causal ``flash_attention`` with the key-value
  heads read through the kernels' index maps.
- **feed-forward**: a gated SiLU MLP, or routed experts without dropped
  tokens (:class:`apex_tpu.transformer.moe.DroplessExperts`: sigmoid scores,
  the ``top_k`` largest of score + a held selection bias, weights normed over
  the chosen), of which this rank holds ``experts_held`` from
  ``first_expert_held`` on. No shared expert.
- Final RMSNorm, head tied to the embedding. RMSNorm is
  ``ops/layer_norm.rms_norm``.

Scopes (the contract of tests/test_step_scopes.py): ``embed``, ``layers``,
``conv_operator`` (inside it ``conv_mix``: gate, filter, gate; the two
projections outside), ``attention`` (inside it ``layer_norm/qk_norm``,
``rope``, ``attention_core``), ``layer_norm``, ``mlp``, ``moe`` (inside it
``moe_route``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``), ``head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.models._transformer import TransformerBase, apply_rope
from apex_tpu.ops.flash_attention import flash_attention
from apex_tpu.ops.layer_norm import rms_norm
from apex_tpu.ops.short_conv import gated_short_conv
from apex_tpu.transformer import tensor_parallel as tp
from apex_tpu.transformer.moe import DroplessExperts

Params = Dict[str, Any]

#: a layer's operator, by the names ``layer_types`` gives
CONV, ATTENTION = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """Defaults: LFM2-8B-A1B's published widths, and one chip's share of a
    4-way expert-parallel stage (8 of 32 experts, a quarter of the
    vocabulary, the leading dense layer and one period of the pattern)."""

    vocab_size: int = 16384
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = (CONV, ATTENTION, CONV, CONV, CONV)
    num_dense_layers: int = 1
    num_attention_heads: int = 32
    num_kv_heads: int = 8
    conv_taps: int = 3                  # conv_L_cache
    ffn_hidden_size: int = 7168         # the dense layers' MLP
    moe_ffn_hidden_size: int = 1792     # one expert
    num_experts: int = 32               # what the router scores
    experts_held: Optional[int] = 8     # None: all of them
    first_expert_held: int = 0
    top_k: int = 4
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_seq_len: int = 8192
    axis: Optional[str] = None          # serial: this rank's share only
    params_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    hidden_dropout: float = 0.0
    init_method_std: float = 0.02
    remat: bool = True
    remat_policy: Optional[str] = None
    attention_impl: str = "auto"
    unroll_layers: bool = False
    lm_head_chunks: Optional[int] = None

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def ffn(self) -> int:
        return self.ffn_hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def layer_kinds(self) -> Tuple[Tuple[str, bool], ...]:
        """``(operator, routed)`` of each layer: the pattern, as data."""
        return tuple((op, i >= self.num_dense_layers)
                     for i, op in enumerate(self.layer_types))


class Lfm2Model(TransformerBase):
    """``init(key)`` → params; ``loss(params, tokens, targets)`` → ``(mean
    loss, stats)``; ``embed`` / ``run_stacks`` / ``head`` are the stage
    boundaries. ``stats`` holds the routed experts' counters, one entry an
    expert layer."""

    causal = True
    #: run_layers stacks what each layer's _layer_aux returns
    aux_per_layer = True

    def __init__(self, config: Lfm2Config):
        super().__init__(config)
        c = config
        if c.axis is not None:
            raise ValueError(
                "this model runs one expert-parallel rank's share serially; "
                "the exchange between ranks is not built (ROADMAP B2)")
        unknown = set(c.layer_types) - {CONV, ATTENTION}
        if unknown or not c.layer_types:
            raise ValueError(f"layer_types holds {sorted(unknown)}: a layer "
                             f"is {CONV!r} or {ATTENTION!r}")
        if not 0 <= c.num_dense_layers <= c.num_layers:
            raise ValueError("num_dense_layers is not within layer_types")
        if c.num_attention_heads % c.num_kv_heads:
            raise ValueError("num_kv_heads must divide num_attention_heads")
        if c.head_dim % 2:
            raise ValueError("rotary needs an even head size")
        self.experts = DroplessExperts(
            c.hidden_size, c.moe_ffn_hidden_size, c.num_experts, c.top_k,
            held=c.experts_held, first_held=c.first_expert_held,
            routed_scaling_factor=c.routed_scaling_factor,
            params_dtype=c.params_dtype, init_method=self._init)

    # -- parameters ---------------------------------------------------------

    def _kernel(self, key, n_in, n_out) -> Params:
        return {"kernel": self._init(key, (n_in, n_out),
                                     self.cfg.params_dtype)}

    def _scale(self, n) -> Params:
        return {"scale": jnp.ones((n,), self.cfg.params_dtype)}

    def _layer_init(self, key, kind) -> Params:
        c = self.cfg
        operator, routed = kind
        h, d = c.hidden_size, c.head_dim
        k = jax.random.split(key, 8)
        p = {"norm1": self._scale(h), "norm2": self._scale(h)}
        if operator == CONV:
            p["conv"] = {"in": self._kernel(k[0], h, 3 * h),
                         "taps": self._init(k[1], (c.conv_taps, h),
                                            c.params_dtype),
                         "out": self._kernel(k[2], h, h)}
        else:
            p["attn"] = {"q": self._kernel(k[0], h, c.num_attention_heads * d),
                         "k": self._kernel(k[1], h, c.num_kv_heads * d),
                         "v": self._kernel(k[2], h, c.num_kv_heads * d),
                         "q_norm": self._scale(d), "k_norm": self._scale(d),
                         "o": self._kernel(k[3], c.num_attention_heads * d,
                                           h)}
        if routed:
            p.update(self.experts.init(k[4]))
        else:
            p["mlp"] = {"gate": self._kernel(k[4], h, c.ffn_hidden_size),
                        "up": self._kernel(k[5], h, c.ffn_hidden_size),
                        "down": self._kernel(k[6], c.ffn_hidden_size, h)}
        return p

    def init(self, key: jax.Array) -> Params:
        """``layers`` holds one stack for each run of like layers
        (``init_pattern``); the head is the embedding's table."""
        ke, kl = jax.random.split(key)
        return {"embedding": self.embedding.init(ke),
                "norm_f": self._scale(self.cfg.hidden_size),
                "layers": self.init_pattern(kl, self.cfg.layer_kinds,
                                            self._layer_init)}

    # -- the block ----------------------------------------------------------

    def _rms(self, p: Params, x: jax.Array) -> jax.Array:
        with jax.named_scope("layer_norm"):
            return rms_norm(x, p["scale"], self.cfg.norm_eps)

    def _proj(self, p: Params, x: jax.Array) -> jax.Array:
        return x @ p["kernel"].astype(x.dtype)

    def _conv_operator(self, p: Params, u: jax.Array) -> jax.Array:
        with jax.named_scope("conv_operator"):
            bcu = self._proj(p["in"], u)
            with jax.named_scope("conv_mix"):
                mixed = gated_short_conv(bcu, p["taps"])
            return self._proj(p["out"], mixed)

    def _head_norm(self, p: Params, x: jax.Array) -> jax.Array:
        """RMSNorm over a head's width. The lax path, which fuses into the
        products around it: the kernel keeps a statistic a row, and a
        million rows of 64 give it ``(rows, 1)`` tables that the chip pads
        to 128 lanes (1.8 GB of scratch at 4 x 8192 tokens)."""
        with jax.named_scope("layer_norm"), jax.named_scope("qk_norm"):
            return rms_norm(x, p["scale"], self.cfg.norm_eps, impl="xla")

    def _attention(self, p: Params, u: jax.Array, bias=None) -> jax.Array:
        c = self.cfg
        b, s, _ = u.shape
        nh, nkv, d = c.num_attention_heads, c.num_kv_heads, c.head_dim
        with jax.named_scope("attention"):
            q = self._head_norm(
                p["q_norm"], self._proj(p["q"], u).reshape(b, s, nh, d))
            k = self._head_norm(
                p["k_norm"], self._proj(p["k"], u).reshape(b, s, nkv, d))
            v = self._proj(p["v"], u).reshape(b, s, nkv, d)
            q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
            with jax.named_scope("rope"):
                pos = self._token_positions(s)
                q = apply_rope(q, pos, c.rope_theta)
                k = apply_rope(k, pos, c.rope_theta)
            with jax.named_scope("attention_core"):
                # k and v keep their own heads: the kernels' index maps
                # hand each group of query heads its key-value head
                a = flash_attention(q, k, v, causal=True,
                                    impl=c.attention_impl)
            a = a.transpose(0, 2, 1, 3).reshape(b, s, nh * d)
            return self._proj(p["o"], a)

    def _feed_forward(self, p: Params, u: jax.Array):
        if "mlp" in p:
            m = p["mlp"]
            with jax.named_scope("mlp"):
                return self._proj(m["down"], jax.nn.silu(
                    self._proj(m["gate"], u)) * self._proj(m["up"], u)), None
        return self.experts.apply(p, u)

    def _layer_aux(self, p: Params, x, key, bias=None):
        """One layer; which of the four kinds it is shows in the tree it is
        given."""
        u = self._rms(p["norm1"], x)
        x = x + (self._conv_operator(p["conv"], u) if "conv" in p
                 else self._attention(p["attn"], u, bias))
        out, stats = self._feed_forward(p, self._rms(p["norm2"], x))
        return x + out, stats

    def _layer(self, p: Params, x, key, bias=None):
        return self._layer_aux(p, x, key, bias)[0]

    # -- the model ----------------------------------------------------------

    def embed(self, params: Params, tokens: jax.Array) -> jax.Array:
        with jax.named_scope("embed"):
            return self.embedding.apply(params["embedding"], tokens).astype(
                self.cfg.compute_dtype)

    def run_stacks(self, params: Params, h: jax.Array):
        """The runs of like layers in order, each one scan. Returns the
        stream and the expert layers' counters, one entry an expert
        layer (``None`` with no expert layer)."""
        h, auxes = self.run_pattern(params["layers"], h)
        routed = [a for a in auxes if a is not None]
        stats = jax.tree.map(lambda *xs: jnp.concatenate(xs), *routed) \
            if routed else None
        return h, stats

    def head(self, params: Params, h: jax.Array,
             targets: Optional[jax.Array] = None):
        """Final RMSNorm and the head, tied to the embedding: per-token
        loss with ``targets``, else logits."""
        c = self.cfg
        with jax.named_scope("head"):
            h = self._rms(params["norm_f"], h)
            w = params["embedding"]["embedding"]
            if c.lm_head_chunks and targets is not None:
                from apex_tpu.ops.lm_head_loss import lm_head_cross_entropy

                return lm_head_cross_entropy(h, w, targets, c.lm_head_chunks)
            logits = jnp.einsum("bsh,vh->bsv", h, w.astype(h.dtype))
            if targets is None:
                return logits
            return tp.vocab_parallel_cross_entropy(logits, targets, axis=None)

    def apply(self, params: Params, tokens: jax.Array,
              targets: Optional[jax.Array] = None):
        """``(per-token loss or logits, stats)``."""
        h, stats = self.run_stacks(params, self.embed(params, tokens))
        return self.head(params, h, targets), stats

    def loss(self, params: Params, tokens: jax.Array, targets: jax.Array):
        """``(mean next-token loss, stats)``."""
        per_token, stats = self.apply(params, tokens, targets)
        return jnp.mean(per_token.astype(jnp.float32)), stats

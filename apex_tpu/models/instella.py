"""A DeepSeek-V3-style causal LM with latent attention, shared and routed
experts and a far-skip residual, as one rank of an expert-parallel stage
holds it. No reference analog: apex's model zoo (standalone_gpt.py,
standalone_bert.py) is one dense pre-LN block.

The block is Instella-MoE-16B-A3B-Base's (``model_type: deepseek_v3`` with
``farskip``, ``gated_attention``, ``qk_layernorm``); every size is a field
of :class:`InstellaConfig`. Bias-free; ``u`` is a sub-block's normed input.

- **Residual path** (``farskip``; FarSkip-Collective, arXiv:2511.11505):
  sub-blocks ``f_1 .. f_2L``, attention then feed-forward of each layer,
  ``s_k = s_{k-1} + f_k(RMSNorm_k(s_{max(k-2, 0)}))``: a sub-block reads the
  stream as it stood before the sub-block just ahead of it added its
  output, so that that output's collective can overlap. The layer scan
  therefore carries a pair, the stream now and one sub-block ago
  (``TransformerBase.run_layers`` takes any pytree). Off: the usual
  ``s_{k-1}``.
- **Latent attention** (DeepSeek-V2/V3 MLA, expanded form, the training
  path): ``q = u W_q`` as heads of ``[q_N; q_R]``; ``[c; k_R] = u W_kva``;
  ``c' = RMSNorm(c)``; ``[k_N,h; v_h] = c' W_kvb``; rotary (pairs
  interleaved, YaRN frequencies) on ``q_R`` and on the one ``k_R`` every
  head shares; ``flash_attention`` with ``scale`` carrying YaRN's factor;
  an elementwise sigmoid gate ``sigmoid(u W_g)`` on the heads' output ahead
  of ``W_o`` (Qiu et al., arXiv:2505.06708).
- **Feed-forward**: the first ``num_dense_layers`` layers a gated SiLU MLP;
  the rest shared experts (one gated MLP of their summed width) beside
  routed experts without dropped tokens
  (:class:`apex_tpu.transformer.moe.DroplessExperts`), of which this rank
  holds ``experts_held`` from ``first_expert_held`` on.
- Untied head; RMSNorm is ``ops/layer_norm.rms_norm``.

Scopes (the contract of tests/test_step_scopes.py): ``embed``, ``layers``,
``attention`` (inside it ``attn_latent``, ``rope``, ``attention_core``,
``attn_gate``), ``layer_norm``, ``mlp`` (the dense layer's), ``moe_shared``,
``moe`` (inside it ``moe_route``, ``moe_dispatch``, ``moe_experts``,
``moe_combine``), ``head``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models._transformer import TransformerBase, latent_kv
from apex_tpu.ops.flash_attention import flash_attention
from apex_tpu.ops.layer_norm import rms_norm
from apex_tpu.transformer import tensor_parallel as tp
from apex_tpu.transformer.moe import DroplessExperts

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class InstellaConfig:
    """Defaults: the published widths, and one chip's share of an 8-way
    expert-parallel stage (8 of 64 experts, an eighth of the vocabulary
    padded to a multiple of 128, one dense layer and four expert layers)."""

    vocab_size: int = 16128
    hidden_size: int = 2048
    num_layers: int = 5
    num_dense_layers: int = 1
    num_attention_heads: int = 16
    qk_nope_head_dim: int = 96
    qk_rope_head_dim: int = 32
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    ffn_hidden_size: int = 10944        # the dense layers' MLP
    moe_ffn_hidden_size: int = 1408     # one expert
    num_shared_experts: int = 2
    num_experts: int = 64               # what the router scores
    experts_held: Optional[int] = 8     # None: all of them
    first_expert_held: int = 0
    top_k: int = 6
    routed_scaling_factor: float = 2.5
    selection_bias_std: float = 0.01
    farskip: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 8e6
    yarn_factor: float = 40.0
    yarn_original_seq: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0
    max_seq_len: int = 4096
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
    def ffn(self) -> int:
        return self.ffn_hidden_size

    @property
    def head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, theta: float, factor: float, original_seq: int,
                     beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's ``dim / 2`` rotary frequencies (Peng et al., arXiv:2309.00071,
    as DeepSeek-V3 computes them): pairs that turn more than ``beta_fast``
    times over the original context keep the base's frequency, those that
    turn fewer than ``beta_slow`` times get it divided by ``factor``, and a
    linear ramp over the pair index joins the two."""
    pair = np.arange(dim // 2, dtype=np.float64)
    base = theta ** (-2.0 * pair / dim)

    def pair_turning(times):
        return dim * math.log(original_seq / (times * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(pair_turning(beta_fast)), 0)
    hi = min(math.ceil(pair_turning(beta_slow)), dim - 1)
    width = (hi - lo) or 0.001
    slowed = np.clip((pair - lo) / width, 0.0, 1.0)
    return base * (1.0 - slowed) + base / factor * slowed


class InstellaModel(TransformerBase):
    """``init(key)`` → params; ``loss(params, tokens, targets)`` → ``(mean
    loss, stats)``; ``embed`` / ``run_stacks`` / ``head`` are the stage
    boundaries. ``stats`` holds the routed experts' counters, one entry an
    expert layer."""

    causal = True
    #: run_layers stacks what each layer's _layer_aux returns
    aux_per_layer = True

    def __init__(self, config: InstellaConfig):
        super().__init__(config)
        c = config
        if c.axis is not None:
            raise ValueError(
                "this model runs one expert-parallel rank's share serially; "
                "the exchange between ranks is not built (ROADMAP B2)")
        if not 0 <= c.num_dense_layers <= c.num_layers:
            raise ValueError("num_dense_layers is not within num_layers")
        if c.qk_rope_head_dim % 2:
            raise ValueError("rotary needs an even qk_rope_head_dim")
        self.experts = DroplessExperts(
            c.hidden_size, c.moe_ffn_hidden_size, c.num_experts, c.top_k,
            held=c.experts_held, first_held=c.first_expert_held,
            routed_scaling_factor=c.routed_scaling_factor,
            params_dtype=c.params_dtype,
            init_method=self._init, bias_std=c.selection_bias_std)
        m = yarn_mscale(c.yarn_factor, c.yarn_mscale_all_dim)
        self.softmax_scale = c.head_dim ** -0.5 * m * m
        self._rotary_scale = yarn_mscale(c.yarn_factor, c.yarn_mscale) / m
        self._inv_freq = yarn_frequencies(
            c.qk_rope_head_dim, c.rope_theta, c.yarn_factor,
            c.yarn_original_seq, c.yarn_beta_fast, c.yarn_beta_slow)

    # -- parameters ---------------------------------------------------------

    def _kernel(self, key, n_in, n_out) -> Params:
        return {"kernel": self._init(key, (n_in, n_out),
                                     self.cfg.params_dtype)}

    def _scale(self, n) -> Params:
        return {"scale": jnp.ones((n,), self.cfg.params_dtype)}

    def _gated_init(self, key, width) -> Params:
        kg, ku, kd = jax.random.split(key, 3)
        h = self.cfg.hidden_size
        return {"gate": self._kernel(kg, h, width),
                "up": self._kernel(ku, h, width),
                "down": self._kernel(kd, width, h)}

    def _layer_init(self, key, dense: bool) -> Params:
        c = self.cfg
        h, nh = c.hidden_size, c.num_attention_heads
        k = jax.random.split(key, 8)
        p = {"norm1": self._scale(h), "norm2": self._scale(h),
             "attn": {
                 "q": self._kernel(k[0], h, nh * c.head_dim),
                 "kv_a": self._kernel(
                     k[1], h, c.kv_lora_rank + c.qk_rope_head_dim),
                 "kv_norm": self._scale(c.kv_lora_rank),
                 "kv_b": self._kernel(
                     k[2], c.kv_lora_rank,
                     nh * (c.qk_nope_head_dim + c.v_head_dim)),
                 "gate": self._kernel(k[3], h, nh * c.v_head_dim),
                 "o": self._kernel(k[4], nh * c.v_head_dim, h)}}
        if dense:
            p["mlp"] = self._gated_init(k[5], c.ffn_hidden_size)
        else:
            p["shared"] = self._gated_init(
                k[5], c.moe_ffn_hidden_size * c.num_shared_experts)
            p.update(self.experts.init(k[6]))
        return p

    def init(self, key: jax.Array) -> Params:
        """The leading dense layers stacked under ``dense``, the expert
        layers under ``layers``: two kinds of layer, two scans."""
        c = self.cfg
        ke, kh, kd, kl = jax.random.split(key, 4)
        nd = c.num_dense_layers

        def stack(k, n, dense):
            return jax.vmap(lambda kk: self._layer_init(kk, dense))(
                jax.random.split(k, n))

        return {"embedding": self.embedding.init(ke),
                "lm_head": {"kernel": self._init(
                    kh, (c.vocab_size, c.hidden_size), c.params_dtype)},
                "norm_f": self._scale(c.hidden_size),
                "dense": stack(kd, nd, True),
                "layers": stack(kl, c.num_layers - nd, False)}

    # -- the block ----------------------------------------------------------

    def _rms(self, p: Params, x: jax.Array) -> jax.Array:
        with jax.named_scope("layer_norm"):
            return rms_norm(x, p["scale"], self.cfg.rms_norm_eps)

    def _proj(self, p: Params, x: jax.Array) -> jax.Array:
        return x @ p["kernel"].astype(x.dtype)

    def _rotate(self, x: jax.Array, cos, sin) -> jax.Array:
        """Rotary on ``(..., s, d)`` whose pairs lie interleaved. The pairs
        come out apart, evens then odds: queries and keys alike, so every
        score is what it is with the pairs left in place."""
        x32 = x.astype(jnp.float32)
        a, b = x32[..., 0::2], x32[..., 1::2]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                               axis=-1).astype(x.dtype)

    def _attention(self, p: Params, u: jax.Array, bias=None) -> jax.Array:
        c = self.cfg
        b, s, _ = u.shape
        nh, dn, dr = c.num_attention_heads, c.qk_nope_head_dim, \
            c.qk_rope_head_dim
        with jax.named_scope("attention"):
            q = self._proj(p["q"], u).reshape(b, s, nh, dn + dr)
            q = q.transpose(0, 2, 1, 3)
            kva, kv = latent_kv(self, p, u, c.kv_lora_rank, nh)
            with jax.named_scope("rope"):
                ang = self._token_positions(s).astype(jnp.float32)[:, None] \
                    * jnp.asarray(self._inv_freq, jnp.float32)
                cos = jnp.cos(ang) * self._rotary_scale
                sin = jnp.sin(ang) * self._rotary_scale
                q_r = self._rotate(q[..., dn:], cos, sin)
                k_r = self._rotate(kva[..., c.kv_lora_rank:], cos, sin)
                q = jnp.concatenate([q[..., :dn], q_r], axis=-1)
                k = jnp.concatenate(
                    [kv[..., :dn],
                     jnp.broadcast_to(k_r[:, None], (b, nh, s, dr))], axis=-1)
            with jax.named_scope("attention_core"):
                a = flash_attention(q, k, kv[..., dn:], causal=True,
                                    scale=self.softmax_scale,
                                    impl=c.attention_impl)
            a = a.transpose(0, 2, 1, 3).reshape(b, s, nh * c.v_head_dim)
            with jax.named_scope("attn_gate"):
                a = a * jax.nn.sigmoid(self._proj(p["gate"], u))
            return self._proj(p["o"], a)

    def _gated_mlp(self, p: Params, u: jax.Array) -> jax.Array:
        return self._proj(p["down"], jax.nn.silu(self._proj(p["gate"], u))
                          * self._proj(p["up"], u))

    def _feed_forward(self, p: Params, u: jax.Array):
        if "mlp" in p:
            with jax.named_scope("mlp"):
                return self._gated_mlp(p["mlp"], u), None
        routed, stats = self.experts.apply(p, u)
        with jax.named_scope("moe_shared"):
            return routed + self._gated_mlp(p["shared"], u), stats

    def _layer_aux(self, p: Params, carry, key, bias=None):
        """One layer on the pair ``(stream now, stream one sub-block
        ago)``; which kind of layer it is shows in the tree it is given."""
        far = self.cfg.farskip
        now, before = carry
        read = before if far else now
        now, before = now + self._attention(
            p["attn"], self._rms(p["norm1"], read), bias), now
        read = before if far else now
        out, stats = self._feed_forward(p, self._rms(p["norm2"], read))
        return (now + out, now), stats

    def _layer(self, p: Params, carry, key, bias=None):
        return self._layer_aux(p, carry, key, bias)[0]

    # -- the model ----------------------------------------------------------

    def embed(self, params: Params, tokens: jax.Array) -> jax.Array:
        with jax.named_scope("embed"):
            return self.embedding.apply(params["embedding"], tokens).astype(
                self.cfg.compute_dtype)

    def run_stacks(self, params: Params, h: jax.Array):
        """The dense layers, then the expert layers, each stack one scan
        of ``run_layers``. Returns the stream and the expert layers'
        counters."""
        carry, stats = (h, h), None
        for name in ("dense", "layers"):
            stack = params[name]
            if jax.tree.leaves(stack)[0].shape[0]:
                carry, got = self.run_layers(stack, carry, return_aux=True)
                stats = got if got is not None else stats
        return carry[0], stats

    def head(self, params: Params, h: jax.Array,
             targets: Optional[jax.Array] = None):
        """Final RMSNorm and the untied head: per-token loss with
        ``targets``, else logits."""
        c = self.cfg
        with jax.named_scope("head"):
            h = self._rms(params["norm_f"], h)
            w = params["lm_head"]["kernel"]
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

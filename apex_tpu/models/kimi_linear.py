"""A causal LM of Moonshot AI's Kimi Linear family (``model_type:
kimi_linear``, Kimi-Linear-48B-A3B; arXiv:2510.26692), as one rank of an
expert-parallel stage holds it. No reference analog: apex's model zoo is one
dense pre-LN block whose every layer attends with a softmax.

Most layers mix tokens with Kimi Delta Attention (KDA), a linear attention
with a state a head, and every fourth with latent attention that takes no
positions at all (``mla_use_nope``); which layer is which is read from the
config's own lists (``kda_layers``, ``full_attn_layers``, counted from 1),
and the first ``first_k_dense_replace`` layers feed forward through a gated
MLP, the rest through a shared expert beside routed ones. So a layer is one
of a few kinds and the stack is built from those keys alone: runs of like
layers, each one scan (``TransformerBase.init_pattern`` / ``run_pattern``).
Every size is a field of :class:`KimiLinearConfig`. Bias-free; every layer is
``x = x + operator(RMSNorm(x)); x = x + ffn(RMSNorm(x))``.

- **KDA operator**: ``q, k = l2norm(silu(conv(u W_q))), l2norm(silu(conv(u
  W_k)))`` over each head, ``v = silu(conv(u W_v))``, ``conv`` a causal
  depthwise filter of ``conv_taps`` taps
  (:func:`apex_tpu.ops.short_conv.short_conv`); a log-decay for every head
  and key channel, ``g = -exp(A_log[h]) softplus(W_fb (W_fa u) + dt_bias)``,
  and a step ``beta = sigmoid(u W_b)`` a head; the delta rule over the
  tokens (:func:`apex_tpu.ops.kda.kda`, the chunked form); ``W_o (RMSNorm_d(o)
  * sigmoid(W_gb (W_ga u)))``, the norm over each head's width with a scale.
- **latent attention**: ``q = u W_q`` as heads of ``[q_N; q_R]``; ``[c; k_R]
  = u W_kva``; ``[k_N,h; v_h] = RMSNorm(c) W_kvb``
  (:func:`apex_tpu.models._transformer.latent_kv`, the expert model's); a
  key is ``[k_N,h; k_R]`` with ``k_R`` shared by the heads; nothing is
  rotated; causal ``flash_attention`` with scores over ``qk_nope_head_dim +
  qk_rope_head_dim`` and values of ``v_head_dim``.
- **feed-forward**: a gated SiLU MLP, or one shared expert (a gated MLP)
  beside routed experts without dropped tokens
  (:class:`apex_tpu.transformer.moe.DroplessExperts`: sigmoid scores, the
  ``top_k`` largest of score + a held selection bias, weights normed over
  the chosen and scaled), of which this rank holds ``experts_held`` from
  ``first_expert_held`` on.
- Final RMSNorm, untied head. RMSNorm is ``ops/layer_norm.rms_norm``.

Scopes (the contract of tests/test_step_scopes.py): ``embed``, ``layers``,
``kda_operator`` (inside it ``conv_mix``: the three filters with their SiLU;
``kda_gates``: decay, step and the output gate's two products; ``kda_scan``:
the chunked delta rule and nothing else; ``layer_norm``: the gated head
norm), ``attention`` (inside it ``attn_latent``, ``attention_core``),
``layer_norm``, ``mlp``, ``moe_shared``, ``moe`` (inside it ``moe_route``,
``moe_dispatch``, ``moe_experts``, ``moe_combine``), ``head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.models._transformer import TransformerBase, latent_kv
from apex_tpu.ops.flash_attention import flash_attention
from apex_tpu.ops.kda import chunk_log_decay, kda
from apex_tpu.ops.layer_norm import rms_norm
from apex_tpu.ops.short_conv import short_conv
from apex_tpu.transformer import tensor_parallel as tp
from apex_tpu.transformer.moe import DroplessExperts

Params = Dict[str, Any]

#: a layer's operator
KDA, LATENT = "kda", "full_attention"


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """Defaults: Kimi-Linear-48B-A3B's published widths, and one chip's
    share of a 32-way expert-parallel stage (8 of 256 experts, an eighth of
    the vocabulary, the leading dense layer and one period of the
    pattern)."""

    vocab_size: int = 20480
    hidden_size: int = 2304
    num_layers: int = 5
    kda_layers: Tuple[int, ...] = (1, 2, 3, 5)      # counted from 1
    full_attn_layers: Tuple[int, ...] = (4,)
    num_dense_layers: int = 1                       # first_k_dense_replace
    num_attention_heads: int = 32                   # the latent attention's
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    kda_heads: int = 32
    kda_head_dim: int = 128
    conv_taps: int = 4                  # short_conv_kernel_size
    kda_chunk: int = 64
    ffn_hidden_size: int = 9216         # the dense layers' MLP
    moe_ffn_hidden_size: int = 1024     # one expert
    num_shared_experts: int = 1
    num_experts: int = 256              # what the router scores
    experts_held: Optional[int] = 8     # None: all of them
    first_expert_held: int = 0
    top_k: int = 8
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    l2_norm_eps: float = 1e-6
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
    def ffn(self) -> int:
        return self.ffn_hidden_size

    @property
    def layer_kinds(self) -> Tuple[Tuple[str, bool], ...]:
        """``(operator, routed)`` of each layer: the pattern, as data."""
        kinds = []
        for i in range(1, self.num_layers + 1):
            if (i in self.kda_layers) == (i in self.full_attn_layers):
                raise ValueError(
                    f"layer {i} is in both or neither of kda_layers "
                    f"{self.kda_layers} and full_attn_layers "
                    f"{self.full_attn_layers}")
            kinds.append((KDA if i in self.kda_layers else LATENT,
                          i > self.num_dense_layers))
        return tuple(kinds)


def _inverse_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


class KimiLinearModel(TransformerBase):
    """``init(key)`` → params; ``loss(params, tokens, targets)`` → ``(mean
    loss, stats)``; ``embed`` / ``run_stacks`` / ``head`` are the stage
    boundaries. ``stats`` holds the routed experts' counters, one entry an
    expert layer, and the KDA layers' (``kda_min_chunk_log_decay``,
    ``kda_chunks``), one entry a KDA layer."""

    causal = True
    #: run_layers stacks what each layer's _layer_aux returns
    aux_per_layer = True
    #: a run of one layer (three of the cut's four) is no loop once compiled:
    #: without the barrier the compiler merges its recompute with the first
    #: forward pass and the layer's activations live from one to the other
    #: (2.4 GB a run at 2 x 8192 tokens, compiled for a described v5e)
    remat_barrier_single_layer = True

    def __init__(self, config: KimiLinearConfig):
        super().__init__(config)
        c = config
        if c.axis is not None:
            raise ValueError(
                "this model runs one expert-parallel rank's share serially; "
                "the exchange between ranks is not built (ROADMAP B2)")
        if not 0 <= c.num_dense_layers <= c.num_layers:
            raise ValueError("num_dense_layers is not within num_layers")
        c.layer_kinds       # raises where the lists do not name every layer
        self.experts = DroplessExperts(
            c.hidden_size, c.moe_ffn_hidden_size, c.num_experts, c.top_k,
            held=c.experts_held, first_held=c.first_expert_held,
            routed_scaling_factor=c.routed_scaling_factor,
            params_dtype=c.params_dtype, init_method=self._init)
        self.softmax_scale = (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5

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

    def _kda_init(self, key) -> Params:
        """``A_log`` is ``log(U(1, 16))`` a head and ``dt_bias`` the inverse
        softplus of a step drawn log-uniformly from [0.001, 0.1], as the
        flash-linear-attention project's layer draws them; both train."""
        c = self.cfg
        # the two low-rank pairs are as wide as a head
        h, nh, d, r = c.hidden_size, c.kda_heads, c.kda_head_dim, \
            c.kda_head_dim
        k = jax.random.split(key, 14)
        wide = nh * d
        step = jnp.exp(jax.random.uniform(
            k[12], (wide,), minval=jnp.log(0.001), maxval=jnp.log(0.1)))
        p = {name: self._kernel(kk, h, wide)
             for name, kk in zip(("q", "k", "v"), k[:3])}
        p.update({f"{name}_conv": self._init(kk, (c.conv_taps, wide),
                                             c.params_dtype)
                  for name, kk in zip(("q", "k", "v"), k[3:6])})
        p.update({
            "f_a": self._kernel(k[6], h, r), "f_b": self._kernel(k[7], r, wide),
            "A_log": jnp.log(jax.random.uniform(
                k[8], (nh,), minval=1.0, maxval=16.0)).astype(c.params_dtype),
            "dt_bias": _inverse_softplus(step).astype(c.params_dtype),
            "b": self._kernel(k[9], h, nh),
            "g_a": self._kernel(k[10], h, r),
            "g_b": self._kernel(k[11], r, wide),
            "o_norm": self._scale(d),
            "o": self._kernel(k[13], wide, h)})
        return p

    def _latent_init(self, key) -> Params:
        c = self.cfg
        h, nh = c.hidden_size, c.num_attention_heads
        k = jax.random.split(key, 4)
        return {"q": self._kernel(
                    k[0], h, nh * (c.qk_nope_head_dim + c.qk_rope_head_dim)),
                "kv_a": self._kernel(
                    k[1], h, c.kv_lora_rank + c.qk_rope_head_dim),
                "kv_norm": self._scale(c.kv_lora_rank),
                "kv_b": self._kernel(
                    k[2], c.kv_lora_rank,
                    nh * (c.qk_nope_head_dim + c.v_head_dim)),
                "o": self._kernel(k[3], nh * c.v_head_dim, h)}

    def _layer_init(self, key, kind) -> Params:
        c = self.cfg
        operator, routed = kind
        k = jax.random.split(key, 4)
        p = {"norm1": self._scale(c.hidden_size),
             "norm2": self._scale(c.hidden_size)}
        if operator == KDA:
            p["kda"] = self._kda_init(k[0])
        else:
            p["attn"] = self._latent_init(k[0])
        if routed:
            p["shared"] = self._gated_init(
                k[1], c.moe_ffn_hidden_size * c.num_shared_experts)
            p.update(self.experts.init(k[2]))
        else:
            p["mlp"] = self._gated_init(k[1], c.ffn_hidden_size)
        return p

    def init(self, key: jax.Array) -> Params:
        """``layers`` holds one stack for each run of like layers
        (``init_pattern``)."""
        c = self.cfg
        ke, kh, kl = jax.random.split(key, 3)
        return {"embedding": self.embedding.init(ke),
                "lm_head": {"kernel": self._init(
                    kh, (c.vocab_size, c.hidden_size), c.params_dtype)},
                "norm_f": self._scale(c.hidden_size),
                "layers": self.init_pattern(kl, c.layer_kinds,
                                            self._layer_init)}

    # -- the block ----------------------------------------------------------

    def _rms(self, p: Params, x: jax.Array) -> jax.Array:
        with jax.named_scope("layer_norm"):
            return rms_norm(x, p["scale"], self.cfg.rms_norm_eps)

    def _proj(self, p: Params, x: jax.Array) -> jax.Array:
        return x @ p["kernel"].astype(x.dtype)

    def _l2norm(self, x: jax.Array) -> jax.Array:
        x32 = x.astype(jnp.float32)
        return (x32 * jax.lax.rsqrt(
            jnp.sum(x32 * x32, axis=-1, keepdims=True)
            + self.cfg.l2_norm_eps)).astype(x.dtype)

    def _kda_inputs(self, p: Params, u: jax.Array):
        """What the delta rule reads, and the output gate: ``(q, k, v, g,
        beta, gate, counters)``, the first four ``(b, heads, s, d)``."""
        c = self.cfg
        b, s, _ = u.shape
        nh, d = c.kda_heads, c.kda_head_dim
        heads = lambda x: x.reshape(b, s, nh, -1).transpose(0, 2, 1, 3)
        q, k, v = (self._proj(p[n], u) for n in ("q", "k", "v"))
        with jax.named_scope("conv_mix"):
            q, k, v = (short_conv(x, p[f"{n}_conv"])
                       for n, x in (("q", q), ("k", k), ("v", v)))
        with jax.named_scope("kda_gates"):
            g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None, None] \
                * heads(jax.nn.softplus(
                    self._proj(p["f_b"], self._proj(p["f_a"], u)).astype(
                        jnp.float32) + p["dt_bias"].astype(jnp.float32)))
            beta = jax.nn.sigmoid(self._proj(p["b"], u).astype(
                jnp.float32)).transpose(0, 2, 1)
            gate = self._proj(p["g_b"], self._proj(p["g_a"], u))
            per_chunk = jax.lax.stop_gradient(
                chunk_log_decay(g, c.kda_chunk))
            counters = {
                "kda_min_chunk_log_decay": jnp.min(per_chunk),
                "kda_chunks": jnp.float32(per_chunk.size // d)}
        return (self._l2norm(heads(q)), self._l2norm(heads(k)), heads(v), g,
                beta, gate, counters)

    def _kda_operator(self, p: Params, u: jax.Array):
        """``(out, counters)``."""
        c = self.cfg
        b, s, _ = u.shape
        nh, d = c.kda_heads, c.kda_head_dim
        with jax.named_scope("kda_operator"):
            q, k, v, g, beta, gate, counters = self._kda_inputs(p, u)
            with jax.named_scope("kda_scan"):
                o, _ = kda(q, k, v, g, beta, chunk=c.kda_chunk)
            o = o.transpose(0, 2, 1, 3)
            with jax.named_scope("layer_norm"):
                # the lax path: a statistic a row of 128 would be padded to
                # 128 lanes (models/lfm2._head_norm says the same)
                o = rms_norm(o, p["o_norm"]["scale"], c.rms_norm_eps,
                             impl="xla") * jax.nn.sigmoid(
                                 gate.reshape(b, s, nh, d))
            return self._proj(p["o"], o.reshape(b, s, nh * d)), counters

    def _attention(self, p: Params, u: jax.Array, bias=None) -> jax.Array:
        c = self.cfg
        b, s, _ = u.shape
        nh, dn, dr = c.num_attention_heads, c.qk_nope_head_dim, \
            c.qk_rope_head_dim
        with jax.named_scope("attention"):
            q = self._proj(p["q"], u).reshape(b, s, nh, dn + dr)
            q = q.transpose(0, 2, 1, 3)
            kva, kv = latent_kv(self, p, u, c.kv_lora_rank, nh)
            # no rotation anywhere: k_R goes to every head as it is
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(
                    kva[:, None, :, c.kv_lora_rank:], (b, nh, s, dr))],
                axis=-1)
            with jax.named_scope("attention_core"):
                a = flash_attention(q, k, kv[..., dn:], causal=True,
                                    scale=self.softmax_scale,
                                    impl=c.attention_impl)
            a = a.transpose(0, 2, 1, 3).reshape(b, s, nh * c.v_head_dim)
            return self._proj(p["o"], a)

    def _gated_mlp(self, p: Params, u: jax.Array) -> jax.Array:
        return self._proj(p["down"], jax.nn.silu(self._proj(p["gate"], u))
                          * self._proj(p["up"], u))

    def _feed_forward(self, p: Params, u: jax.Array):
        if "mlp" in p:
            with jax.named_scope("mlp"):
                return self._gated_mlp(p["mlp"], u), {}
        routed, stats = self.experts.apply(p, u)
        with jax.named_scope("moe_shared"):
            return routed + self._gated_mlp(p["shared"], u), stats

    def _layer_aux(self, p: Params, x, key, bias=None):
        """One layer; which kind it is shows in the tree it is given."""
        u = self._rms(p["norm1"], x)
        if "kda" in p:
            mixed, counters = self._kda_operator(p["kda"], u)
        else:
            mixed, counters = self._attention(p["attn"], u, bias), {}
        x = x + mixed
        out, stats = self._feed_forward(p, self._rms(p["norm2"], x))
        return x + out, {**stats, **counters}

    def _layer(self, p: Params, x, key, bias=None):
        return self._layer_aux(p, x, key, bias)[0]

    # -- the model ----------------------------------------------------------

    def embed(self, params: Params, tokens: jax.Array) -> jax.Array:
        with jax.named_scope("embed"):
            return self.embedding.apply(params["embedding"], tokens).astype(
                self.cfg.compute_dtype)

    def run_stacks(self, params: Params, h: jax.Array):
        """The runs of like layers in order, each one scan. Returns the
        stream and the layers' counters: each name's entries in the order of
        the layers that count it."""
        h, auxes = self.run_pattern(params["layers"], h)
        stats: Dict[str, list] = {}
        for aux in auxes:
            for name, each in (aux or {}).items():
                stats.setdefault(name, []).append(each)
        return h, {k: jnp.concatenate(v) for k, v in stats.items()}

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

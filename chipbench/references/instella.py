"""Instella-MoE-16B-A3B-Base as its ``config.json`` (``model_type:
deepseek_v3``) and the papers behind its three boolean keys describe it, as
one chip's share of a layer. Float32 ``jax.numpy``; no kernels, no sorting,
no cache. Bias-free throughout; ``u`` is a sub-block's normed input.

The equations are plain; their arrangement is not free. ``train.follow``
keeps six float32 trees of this model beside the one this module's gradient
is written to (29 bytes a parameter, 14.5 of the chip's 15.75 GiB), so the
gradient's scratch has to stay near one gigabyte for two rows of 4096
tokens. Hence: each expert layer's weights are a tree of their own (the
gradient of a stacked leaf is assembled in scratch first, a second copy);
attention goes head by head and block of queries by block; whatever is as
wide as a feed-forward or the vocabulary goes ``CHUNK`` positions at a time;
every such piece sits under a checkpoint.

- Residual path (``farskip``; FarSkip-Collective, arXiv:2511.11505):
  sub-blocks ``f_1 .. f_2L`` (attention, then feed-forward, of each layer),
  ``s_0`` the embedding, ``s_k = s_{k-1} + f_k(RMSNorm_k(s_{max(k-2, 0)}))``;
  with the key off, ``f_k`` reads ``s_{k-1}``. The output is
  ``RMSNorm_f(s_2L)`` into the untied head.
- Attention (DeepSeek-V3's latent attention, expanded form): ``q = u W_q`` as
  heads of ``[q_N; q_R]``; ``[c; k_R] = u W_kva``; ``c' = RMSNorm(c)``
  (``qk_layernorm``: Megatron-core's ``MLASelfAttention`` norms the
  compressed latents, and ``q_lora_rank`` is null, so this is the one);
  ``[k_N,h; v_h] = c' W_kvb``; rotary on ``q_R`` and on the one ``k_R`` all
  heads share, pairs interleaved, YaRN frequencies; causal softmax of
  ``q_h . k_h`` times ``qk_head_dim^-0.5 m^2``; ``g = sigmoid(u W_g)``
  (``gated_attention``; Qiu et al., arXiv:2505.06708: elementwise,
  head-specific, from the normed input); output ``(concat_h(a_h) * g) W_o``.
- Expert feed-forward: ``p = sigmoid(u W_r)`` over all published experts;
  the choice is the ``k`` largest of ``p + b`` (``noaux_tc``; ``b`` takes no
  gradient); ``w_i = routed_scaling_factor p_i / (sum of chosen p + 1e-20)``;
  ``F(u) = sum over chosen i held here of w_i E_i(u) + S(u)`` with gated
  SiLU experts. The first ``first_k_dense_replace`` layers are the gated MLP
  at ``intermediate_size``.
- The share: the file's ``n_routed_experts`` experts are held, ids
  ``deployment.first_expert_held`` on, of ``deployment.experts_published``
  the router scores; what the absent experts would add is left out.

Departures: none from the equations above. The embedding and the head hold
``padded_vocab_size`` rows; ids are drawn from ``vocab_size``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common

CAUSAL = True


def head_positions(mix: dict) -> float:
    """Every position is decoded to the vocabulary."""
    return 1.0


def sizes(cfg: dict) -> dict:
    dep = cfg.get("deployment", {})
    held = cfg["n_routed_experts"]
    return {
        "hidden": cfg["hidden_size"],
        "layers": cfg.get("n_layer", cfg["num_hidden_layers"]),
        "dense_layers": cfg["first_k_dense_replace"],
        "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "latent": cfg["kv_lora_rank"],
        "dense_ffn": cfg["intermediate_size"],
        "expert_ffn": cfg["moe_intermediate_size"],
        "shared_ffn": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        "held": held,
        "experts": dep.get("experts_published", held),
        "first_held": dep.get("first_expert_held", 0),
        "top_k": cfg["num_experts_per_tok"],
        "vocab": common.table_rows(cfg),
    }


# -- YaRN, as DeepSeek-V3's published YarnRotaryEmbedding computes it --------

def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` rotary frequencies: the base's where a
    pair turns more than ``beta_fast`` times over the original context, the
    base's over ``factor`` where fewer than ``beta_slow``, a linear ramp
    between."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    powers = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extra, inter = 1.0 / powers, 1.0 / (rs["factor"] * powers)

    def correction_dim(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def rotary_scale(cfg: dict) -> float:
    """What cos and sin are multiplied by."""
    rs = cfg["rope_scaling"]
    return (yarn_mscale(rs["factor"], rs["mscale"])
            / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return cfg["qk_head_dim"] ** -0.5 * m * m


# -- weights -----------------------------------------------------------------

def _layer_shapes(z: dict, n: int, std: float, ffn: dict) -> dict:
    h, nh = z["hidden"], z["heads"]
    flat = {"norm1/scale": ((n, h), "ones"), "norm2/scale": ((n, h), "ones"),
            "attn/q/kernel": ((n, h, nh * (z["nope"] + z["rope"])), std),
            "attn/kv_a/kernel": ((n, h, z["latent"] + z["rope"]), std),
            "attn/kv_norm/scale": ((n, z["latent"]), "ones"),
            "attn/kv_b/kernel": (
                (n, z["latent"], nh * (z["nope"] + z["v"])), std),
            "attn/gate/kernel": ((n, h, nh * z["v"]), std),
            "attn/o/kernel": ((n, nh * z["v"], h), std)}
    flat.update(ffn)
    return flat


def _gated(prefix: str, lead: tuple, h: int, f: int, std: float) -> dict:
    return {f"{prefix}/gate/kernel": (lead + (h, f), std),
            f"{prefix}/up/kernel": (lead + (h, f), std),
            f"{prefix}/down/kernel": (lead + (f, h), std)}


def weight_shapes(cfg: dict) -> dict:
    z = sizes(cfg)
    h = z["hidden"]
    std = cfg.get("initializer_range", 0.02)
    nd, ne = z["dense_layers"], z["layers"] - z["dense_layers"]
    flat = {"embedding/embedding": ((z["vocab"], h), std),
            "lm_head/kernel": ((z["vocab"], h), std),
            "norm_f/scale": ((h,), "ones")}
    dense = _layer_shapes(z, nd, std,
                          _gated("mlp", (nd,), h, z["dense_ffn"], std))
    flat.update({f"dense/{k}": v for k, v in dense.items()})
    ffn = _gated("shared", (1,), h, z["shared_ffn"], std)
    ffn["router/kernel"] = ((1, h, z["experts"]), std)
    # the selection bias: drawn from the seed and held (``assumed``)
    ffn["router/bias"] = ((1, z["experts"]),
                          cfg["assumed"]["selection_bias_std"])
    for name, shape in (("gate", (h, z["expert_ffn"])),
                        ("up", (h, z["expert_ffn"])),
                        ("down", (z["expert_ffn"], h))):
        ffn[f"experts/{name}"] = ((1, z["held"]) + shape, std)
    layer = _layer_shapes(z, 1, std, ffn)
    for i in range(ne):
        flat.update({f"layers/{i}/{k}": v for k, v in layer.items()})
    return flat


def init_weights(cfg: dict, key, dtype=jnp.float32) -> dict:
    """Seeded weights as a nested dict: the leading dense layers stacked
    under ``dense``; each expert layer a tree of its own under
    ``layers/<i>``, its leaves with a leading axis of 1. (A program stacks
    them on that axis. Here they stay apart: the gradient of a stacked leaf
    is put together in a buffer of its own before it is handed out, a
    second copy of 1.6 GB that the chip has no room for beside the float32
    state ``train.follow`` keeps.)"""
    return common.nest(common.normal_leaves(key, weight_shapes(cfg), dtype))


def fused_parts(cfg: dict, name: str, x):
    """A layer leaf ``(1, ...)`` as ``(1, parts, elements)``: the routed
    experts' leaves hold one matrix an expert."""
    if "/experts/" in name:
        return x.reshape(x.shape[0], x.shape[1], -1)
    return x.reshape(x.shape[0], 1, -1)


# -- the model ---------------------------------------------------------------

def rms_norm(x, scale, eps):
    return scale * x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def rotate_pairs(x, cos, sin):
    """Rotary on ``(..., positions, dim)`` with the pairs interleaved:
    ``(x_2i, x_2i+1)`` turns by the ``i``-th frequency."""
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


#: positions a block of the wide products takes at a time
CHUNK = 1024


def in_chunks(f, *xs):
    """``f`` over ``CHUNK`` positions at a time, each under a checkpoint:
    what is as wide as the dense feed-forward or the vocabulary is then
    never alive for all positions at once. ``xs``: ``(rows, positions,
    ...)`` arrays; ``f`` maps ``(CHUNK, ...)`` blocks to ``(CHUNK, ...)``
    or to a scalar."""
    n = xs[0].shape[0] * xs[0].shape[1]
    size = CHUNK if n % CHUNK == 0 else n
    cut = [x.reshape(n // size, size, *x.shape[2:]) for x in xs]
    out = jax.lax.map(lambda c: jax.checkpoint(f)(*c), tuple(cut))
    if out.ndim == 1:
        return out
    return out.reshape(*xs[0].shape[:2], *out.shape[2:])


def gated_mlp(dot, u, p):
    return in_chunks(
        lambda c: dot(jax.nn.silu(dot(c, p["gate"]["kernel"]))
                      * dot(c, p["up"]["kernel"]), p["down"]["kernel"]), u)


def attention(cfg: dict, dot, u, p):
    """Head by head, each under a checkpoint, the heads' outputs summed
    through their rows of ``W_o``: one head's queries, keys, values and
    scores are alive at a time."""
    z = sizes(cfg)
    s = u.shape[1]
    nh, dn, dr, dv = z["heads"], z["nope"], z["rope"], z["v"]
    kva = dot(u, p["kv_a"]["kernel"])
    c = rms_norm(kva[..., :z["latent"]], p["kv_norm"]["scale"],
                 cfg["rms_norm_eps"])
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None]
           * jnp.asarray(yarn_inv_freq(cfg), jnp.float32))
    cos, sin = (jnp.cos(ang) * rotary_scale(cfg),
                jnp.sin(ang) * rotary_scale(cfg))
    k_r = rotate_pairs(kva[..., z["latent"]:], cos, sin)   # one for all heads
    positions = jnp.arange(s)

    def by_head(w, width):
        """``(in, heads * width)`` as ``(heads, in, width)``."""
        return jnp.moveaxis(w.reshape(w.shape[0], nh, width), 1, 0)

    @jax.checkpoint
    def head(w_q, w_kv, w_g, w_o):
        q, kv = dot(u, w_q), dot(c, w_kv)
        q = jnp.concatenate(
            [q[..., :dn], rotate_pairs(q[..., dn:], cos, sin)], -1)
        k = jnp.concatenate([kv[..., :dn], k_r], -1)

        def row(q, k, v):
            def some(qc, at):
                scores = jnp.matmul(qc, k.T, precision=common.HIGHEST) \
                    * softmax_scale(cfg)
                scores = jnp.where(at[:, None] >= positions[None, :],
                                   scores, -jnp.inf)
                return jnp.matmul(jax.nn.softmax(scores, -1), v,
                                  precision=common.HIGHEST)

            return in_chunks(some, q[None], positions[None])[0]

        # a row at a time, CHUNK queries at a time: one block of scores
        a = jax.lax.map(lambda qkv: row(*qkv), (q, k, kv[..., dn:]))
        return dot(a * jax.nn.sigmoid(dot(u, w_g)), w_o)

    out, _ = jax.lax.scan(
        lambda acc, ws: (acc + head(*ws), None), jnp.zeros_like(u),
        (by_head(p["q"]["kernel"], dn + dr),
         by_head(p["kv_b"]["kernel"], dn + dv),
         by_head(p["gate"]["kernel"], dv),
         p["o"]["kernel"].reshape(nh, dv, -1)))
    return out


def route(cfg: dict, dot, u, router):
    """``(..., experts)``: the weight of every published expert for every
    token, 0 where it was not chosen."""
    z = sizes(cfg)
    p = jax.nn.sigmoid(dot(u, router["kernel"]))
    _, chosen = jax.lax.top_k(p + jax.lax.stop_gradient(router["bias"]),
                              z["top_k"])
    picked = jnp.take_along_axis(p, chosen, -1)
    w = cfg["routed_scaling_factor"] * picked / (
        jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return jnp.sum(w[..., None] * jax.nn.one_hot(chosen, z["experts"]), -2)


def routed_experts(cfg: dict, dot, u, p):
    """What the experts held here add: a dense loop over them, each over
    every token, under the router's weight (0 where it was not chosen)."""
    z = sizes(cfg)
    weights = route(cfg, dot, u, p["router"])
    mine = weights[..., z["first_held"]:z["first_held"] + z["held"]]

    def expert(gate, up, down, w):
        return in_chunks(
            lambda uc, wc: wc[..., None] * dot(
                jax.nn.silu(dot(uc, gate)) * dot(uc, up), down), u, w)

    e = p["experts"]
    out, _ = jax.lax.scan(
        lambda acc, xs: (acc + expert(*xs), None), jnp.zeros_like(u),
        (e["gate"], e["up"], e["down"], jnp.moveaxis(mine, -1, 0)))
    return out


def feed_forward(cfg: dict, dot, u, p):
    if "mlp" in p:
        return gated_mlp(dot, u, p["mlp"])
    return routed_experts(cfg, dot, u, p) + gated_mlp(dot, u, p["shared"])


def layer(cfg: dict, dot, carry, p):
    """One layer on ``carry``, the stream now and as it stood one
    sub-block ago."""
    eps = cfg["rms_norm_eps"]
    for norm, f in (("norm1", lambda u: attention(cfg, dot, u, p["attn"])),
                    ("norm2", lambda u: feed_forward(cfg, dot, u, p))):
        now, before = carry
        read = before if cfg["farskip"] else now
        carry = (now + f(rms_norm(read, p[norm]["scale"], eps)), now)
    return carry


def through(cfg: dict, dot, carry, trees: list):
    """``carry`` through the layers ``trees``, each under a checkpoint: the
    backward pass keeps the stream at each layer's input and, of one layer
    at a time, what it computes inside."""
    for p in trees:
        carry = jax.checkpoint(lambda c, p: layer(cfg, dot, c, p))(carry, p)
    return carry


def layer_trees(w: dict) -> list:
    """The layers in order, each without its leading axis."""
    dense = [jax.tree.map(lambda a: a[i], w["dense"])
             for i in range(jax.tree.leaves(w["dense"])[0].shape[0])]
    return dense + [jax.tree.map(lambda a: a[0], w["layers"][i])
                    for i in sorted(w["layers"], key=int)]


def final_hidden(cfg: dict, w: dict, tokens, precision="float32"):
    """(rows, positions) token ids to the normed stream the head reads."""
    dot = common.DOTS[precision]
    s0 = jnp.take(w["embedding"]["embedding"], tokens, axis=0)
    now, _ = through(cfg, dot, (s0, s0), layer_trees(w))
    return rms_norm(now, w["norm_f"]["scale"], cfg["rms_norm_eps"])


def logits(cfg: dict, w: dict, tokens, precision="float32"):
    """(rows, positions) token ids to (rows, positions, vocab) logits."""
    return common.DOTS[precision](final_hidden(cfg, w, tokens, precision),
                                  w["lm_head"]["kernel"].T)


def loss_numerators(cfg: dict, w: dict, block: dict, precision="float32"):
    """The loss is one mean: the sum of these rows' per-token losses, the
    head and the softmax taken ``CHUNK`` positions at a time."""
    dot = common.DOTS[precision]
    h = final_hidden(cfg, w, block["tokens"], precision)
    head = w["lm_head"]["kernel"].T
    per_chunk = in_chunks(
        lambda hc, tc: jnp.sum(common.cross_entropy(dot(hc, head), tc)),
        h, block["targets"])
    return jnp.sum(per_chunk)[None]


def denominators(batch: dict):
    """What each numerator is divided by, over the whole batch."""
    return np.asarray([batch["tokens"].size], np.float64)


def make_batch(cfg: dict, mix: dict, rng, rows: int) -> dict:
    """Rows of random token ids of the slice of the vocabulary held here
    (the padded rows are never drawn); the target of a position is the next
    token and the last position's wraps to the row's first."""
    toks = rng.integers(0, cfg["vocab_size"], (rows, mix["seq"]),
                        dtype=np.int32)
    return {"tokens": toks, "targets": np.roll(toks, -1, axis=-1)}

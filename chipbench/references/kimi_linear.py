"""Kimi-Linear-48B-A3B as its ``config.json`` (``model_type: kimi_linear``),
arXiv:2510.26692 ("Kimi Linear") and the flash-linear-attention project's
``KimiDeltaAttention`` layer and ``naive_recurrent_kda`` describe it, as one
chip's share of a layer. Float32 ``jax.numpy``; no kernels, no chunked scan,
no sorting, no cache. Bias-free throughout; ``u`` is a sub-block's normed
input.

- Every layer: ``x = x + operator(RMSNorm(x)); x = x + ffn(RMSNorm(x))``
  (``rms_norm_eps``). ``linear_attn_config`` says which layers mix tokens
  with Kimi Delta Attention (``kda_layers``) and which attend
  (``full_attn_layers``), both counted from 1; the first
  ``first_k_dense_replace`` layers feed forward through the gated MLP at
  ``intermediate_size``, the rest through routed experts and a shared one.
  The output is ``RMSNorm(x)`` into an untied head.
- ``kda`` (``linear_attn_config.num_heads`` heads of ``head_dim``): ``q, k =
  l2norm(silu(conv(u W_q))), l2norm(silu(conv(u W_k)))`` over each head
  (``x / sqrt(sum x^2 + 1e-6)``), ``v = silu(conv(u W_v))``; ``conv`` is
  torch's ``Conv1d(groups=channels, padding=L-1)`` cut to the sequence, ``L``
  = ``short_conv_kernel_size``, no bias: tap ``L - 1`` lies on the token
  itself; the filter is kept taps first, ``(L, channels)``. Decay, for every
  head and key channel: ``g = -exp(A_log[h]) softplus(W_fb (W_fa u) +
  dt_bias)``; step: ``beta = sigmoid(u W_b)`` a head. With a state ``S`` of
  ``(head_dim, head_dim)`` a head, zero at a row's start: ``S' =
  diag(exp(g_t)) S_{t-1}``; ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``;
  ``o_t = S_t^T q_t head_dim^-0.5``, token by token. Out: ``W_o
  (RMSNorm_head(o_t) * sigmoid(W_gb (W_ga u)))``, the norm over each head's
  width with a scale of its own (``rms_norm_eps``).
- ``full_attention`` (latent, ``mla_use_nope``): ``q = u W_q`` as
  ``num_attention_heads`` heads of ``qk_nope_head_dim + qk_rope_head_dim``;
  ``[c | k_R] = u W_kva`` (``kv_lora_rank`` | ``qk_rope_head_dim``); ``[k_N,h
  | v_h] = RMSNorm(c) W_kvb``; a head's key is ``[k_N,h | k_R]``, ``k_R``
  the same for every head; nothing is rotated; causal softmax of ``q . k``
  at ``(qk_nope_head_dim + qk_rope_head_dim)^-0.5``; ``concat_h(a_h) W_o``.
- Routed experts: ``p = sigmoid(u W_r)`` over all published experts
  (``moe_router_activation_func``); the choice is the
  ``num_experts_per_token`` largest of ``p + b`` (``num_expert_group`` 1: no
  grouping; ``b`` is a buffer and takes no gradient); ``w_i =
  routed_scaling_factor p_i / (sum of chosen p + 1e-20)``
  (``moe_renormalize``); ``F(u) = shared(u) + sum over chosen i held here of
  w_i E_i(u)`` with gated SiLU experts.
- The share: the file's ``num_experts`` experts are held, ids
  ``deployment.first_expert_held`` on, of ``deployment.experts_published``
  the router scores; what the absent experts would add is left out.

No departure of the program from this file is known. What the config does
not give is under ``assumed`` in the configuration's file.

As in ``instella.py``, whatever is as wide as a feed-forward or the
vocabulary goes ``CHUNK`` positions at a time, attention goes head by head
and block of queries by block, and every layer sits under a checkpoint. The
recurrence runs ``SCAN_BLOCK`` tokens at a time under a checkpoint, so its
gradient keeps a state a block and not a state a token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from .instella import gated_mlp, in_chunks, rms_norm

CAUSAL = True

KDA, ATTENTION = "kda", "full_attention"

#: tokens of the recurrence under one checkpoint
SCAN_BLOCK = 64

L2_EPS = 1e-6


def head_positions(mix: dict) -> float:
    """Every position is decoded to the vocabulary."""
    return 1.0


def sizes(cfg: dict) -> dict:
    dep = cfg.get("deployment", {})
    lin = cfg["linear_attn_config"]
    layers = cfg.get("n_layer", cfg["num_hidden_layers"])
    kinds = []
    for i in range(1, layers + 1):
        if (i in lin["kda_layers"]) == (i in lin["full_attn_layers"]):
            raise ValueError(f"layer {i} is in both or neither of kda_layers "
                             "and full_attn_layers")
        kinds.append(KDA if i in lin["kda_layers"] else ATTENTION)
    held = cfg["num_experts"]
    return {
        "hidden": cfg["hidden_size"],
        "layer_types": kinds, "layers": layers,
        "dense_layers": cfg["first_k_dense_replace"],
        "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "latent": cfg["kv_lora_rank"],
        "kda_heads": lin["num_heads"], "kda_head": lin["head_dim"],
        "taps": lin["short_conv_kernel_size"],
        # the two low-rank pairs are as wide as a head (assumed)
        "gate_rank": lin["head_dim"],
        "dense_ffn": cfg["intermediate_size"],
        "expert_ffn": cfg["moe_intermediate_size"],
        "shared_ffn": cfg["moe_intermediate_size"]
        * cfg["num_shared_experts"],
        "held": held,
        "experts": dep.get("experts_published", held),
        "first_held": dep.get("first_expert_held", 0),
        "top_k": cfg["num_experts_per_token"],
        "vocab": common.table_rows(cfg),
    }


# -- weights -----------------------------------------------------------------

def _gated(prefix: str, h: int, f: int, std: float) -> dict:
    return {f"{prefix}/gate/kernel": ((1, h, f), std),
            f"{prefix}/up/kernel": ((1, h, f), std),
            f"{prefix}/down/kernel": ((1, f, h), std)}


def layer_shapes(z: dict, i: int, std: float) -> dict:
    """Layer ``i``'s leaves, each with a leading axis of 1. ``A_log`` and
    ``dt_bias`` are drawn by :func:`init_weights`."""
    h = z["hidden"]
    flat = {"norm1/scale": ((1, h), "ones"), "norm2/scale": ((1, h), "ones")}
    if z["layer_types"][i] == KDA:
        wide, r = z["kda_heads"] * z["kda_head"], z["gate_rank"]
        for n in ("q", "k", "v"):
            flat[f"kda/{n}/kernel"] = ((1, h, wide), std)
            flat[f"kda/{n}_conv"] = ((1, z["taps"], wide), std)
        flat.update({"kda/f_a/kernel": ((1, h, r), std),
                     "kda/f_b/kernel": ((1, r, wide), std),
                     "kda/A_log": ((1, z["kda_heads"]), "zeros"),
                     "kda/dt_bias": ((1, wide), "zeros"),
                     "kda/b/kernel": ((1, h, z["kda_heads"]), std),
                     "kda/g_a/kernel": ((1, h, r), std),
                     "kda/g_b/kernel": ((1, r, wide), std),
                     "kda/o_norm/scale": ((1, z["kda_head"]), "ones"),
                     "kda/o/kernel": ((1, wide, h), std)})
    else:
        nh = z["heads"]
        flat.update({
            "attn/q/kernel": ((1, h, nh * (z["nope"] + z["rope"])), std),
            "attn/kv_a/kernel": ((1, h, z["latent"] + z["rope"]), std),
            "attn/kv_norm/scale": ((1, z["latent"]), "ones"),
            "attn/kv_b/kernel": ((1, z["latent"],
                                  nh * (z["nope"] + z["v"])), std),
            "attn/o/kernel": ((1, nh * z["v"], h), std)})
    if i < z["dense_layers"]:
        flat.update(_gated("mlp", h, z["dense_ffn"], std))
    else:
        f = z["expert_ffn"]
        flat.update(_gated("shared", h, z["shared_ffn"], std))
        flat.update({"router/kernel": ((1, h, z["experts"]), std),
                     # the selection bias starts at 0 and is held (assumed)
                     "router/bias": ((1, z["experts"]), "zeros"),
                     "experts/gate": ((1, z["held"], h, f), std),
                     "experts/up": ((1, z["held"], h, f), std),
                     "experts/down": ((1, z["held"], f, h), std)})
    return flat


def weight_shapes(cfg: dict) -> dict:
    z = sizes(cfg)
    std = cfg.get("initializer_range", 0.02)
    flat = {"embedding/embedding": ((z["vocab"], z["hidden"]), std),
            "lm_head/kernel": ((z["vocab"], z["hidden"]), std),
            "norm_f/scale": ((z["hidden"],), "ones")}
    for i in range(z["layers"]):
        flat.update({f"layers/{i}/{k}": v
                     for k, v in layer_shapes(z, i, std).items()})
    return flat


def init_weights(cfg: dict, key, dtype=jnp.float32) -> dict:
    """Seeded weights as a nested dict: each layer a tree of its own under
    ``layers/<i>``, its leaves with a leading axis of 1 (a program stacks
    runs of like layers on that axis). A KDA layer's ``A_log`` is ``log(U(1,
    16))`` a head and its ``dt_bias`` the inverse softplus of a step drawn
    log-uniformly from [0.001, 0.1], as the flash-linear-attention
    project's layer draws them (assumed), from the same key."""
    shapes = weight_shapes(cfg)
    flat = common.normal_leaves(key, shapes, dtype)
    for i, name in enumerate(sorted(shapes)):
        k = jax.random.fold_in(jax.random.fold_in(key, 0x4B44), i)
        if name.endswith("/A_log"):
            flat[name] = jnp.log(jax.random.uniform(
                k, shapes[name][0], minval=1.0, maxval=16.0)).astype(dtype)
        elif name.endswith("/dt_bias"):
            step = jnp.exp(jax.random.uniform(
                k, shapes[name][0], minval=np.log(0.001),
                maxval=np.log(0.1)))
            flat[name] = (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    return common.nest(flat)


def fused_parts(cfg: dict, name: str, x):
    """A layer leaf ``(1, ...)`` as ``(1, parts, elements)``: the routed
    experts' leaves hold one matrix an expert."""
    if "/experts/" in name:
        return x.reshape(x.shape[0], x.shape[1], -1)
    return x.reshape(x.shape[0], 1, -1)


# -- the model ---------------------------------------------------------------

def conv_silu(x, taps):
    """``silu`` of the causal depthwise filter: ``x`` ``(rows, positions,
    channels)``, ``taps`` ``(L, channels)``."""
    n, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    return jax.nn.silu(sum(taps[j] * padded[:, j:j + s] for j in range(n)))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta):
    """The recurrence token by token. ``q``, ``k``, ``v``, ``g`` of ``(rows,
    positions, heads, d)``, ``beta`` ``(rows, positions, heads)``; ``q``
    comes scaled. Every product is elementwise and summed in float32: no
    matrix unit's rounding. ``SCAN_BLOCK`` tokens at a time under a
    checkpoint."""
    rows, s, nh, d = q.shape
    size = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s

    def token(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - jnp.sum(state * kt[..., None], -2))
        state = state + kt[..., None] * u[..., None, :]
        return state, jnp.sum(state * qt[..., None], -2)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    cut = lambda x: jnp.moveaxis(x, 1, 0).reshape(
        s // size, size, rows, *x.shape[2:])
    _, o = jax.lax.scan(block, jnp.zeros((rows, nh, d, v.shape[-1]),
                                         jnp.float32),
                        tuple(cut(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(s, rows, nh, -1), 0, 1)


def kda(cfg: dict, dot, u, p):
    z = sizes(cfg)
    rows, s, _ = u.shape
    nh, d = z["kda_heads"], z["kda_head"]
    heads = lambda x: x.reshape(rows, s, nh, d)
    proj = lambda w: in_chunks(lambda c: dot(c, w), u)
    q, k, v = (heads(conv_silu(proj(p[n]["kernel"]), p[f"{n}_conv"]))
               for n in ("q", "k", "v"))
    low = lambda a, b: in_chunks(
        lambda c: dot(dot(c, p[a]["kernel"]), p[b]["kernel"]), u)
    g = -jnp.exp(p["A_log"])[:, None] * heads(
        jax.nn.softplus(low("f_a", "f_b") + p["dt_bias"]))
    beta = jax.nn.sigmoid(dot(u, p["b"]["kernel"]))
    o = delta_rule(l2norm(q) * d ** -0.5, l2norm(k), v, g, beta)
    o = rms_norm(o, p["o_norm"]["scale"], cfg["rms_norm_eps"]) \
        * jax.nn.sigmoid(heads(low("g_a", "g_b")))
    return in_chunks(lambda c: dot(c, p["o"]["kernel"]),
                     o.reshape(rows, s, nh * d))


def attention(cfg: dict, dot, u, p):
    """Head by head, each under a checkpoint, the heads' outputs summed
    through their rows of ``W_o``: one head's queries and scores are alive
    at a time."""
    z = sizes(cfg)
    rows, s, hidden = u.shape
    nh, dn, dr, dv = z["heads"], z["nope"], z["rope"], z["v"]
    scale = (dn + dr) ** -0.5
    positions = jnp.arange(s)
    kva = dot(u, p["kv_a"]["kernel"])
    latent = rms_norm(kva[..., :z["latent"]], p["kv_norm"]["scale"],
                      cfg["rms_norm_eps"])
    k_r = kva[..., z["latent"]:]                    # every head's, unrotated

    @jax.checkpoint
    def head(w_q, w_kvb, w_o):
        q = dot(u, w_q)                             # (rows, s, dn + dr)
        kv = dot(latent, w_kvb)                     # (rows, s, dn + dv)
        k = jnp.concatenate([kv[..., :dn], k_r], -1)
        v = kv[..., dn:]

        def row(q, k, v):
            def some(qc, at):
                scores = jnp.matmul(qc, k.T, precision=common.HIGHEST) \
                    * scale
                scores = jnp.where(at[:, None] >= positions[None, :],
                                   scores, -jnp.inf)
                return jnp.matmul(jax.nn.softmax(scores, -1), v,
                                  precision=common.HIGHEST)

            return in_chunks(some, q[None], positions[None])[0]

        return dot(jax.lax.map(lambda qkv: row(*qkv), (q, k, v)), w_o)

    w_q = jnp.moveaxis(p["q"]["kernel"].reshape(hidden, nh, dn + dr), 1, 0)
    w_kvb = jnp.moveaxis(
        p["kv_b"]["kernel"].reshape(z["latent"], nh, dn + dv), 1, 0)
    out, _ = jax.lax.scan(
        lambda acc, ws: (acc + head(*ws), None), jnp.zeros_like(u),
        (w_q, w_kvb, p["o"]["kernel"].reshape(nh, dv, hidden)))
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


def layer(cfg: dict, dot, x, p):
    """One layer; which operator and which feed-forward it has shows in its
    tree."""
    eps = cfg["rms_norm_eps"]
    u = rms_norm(x, p["norm1"]["scale"], eps)
    x = x + (kda(cfg, dot, u, p["kda"]) if "kda" in p
             else attention(cfg, dot, u, p["attn"]))
    u = rms_norm(x, p["norm2"]["scale"], eps)
    if "mlp" in p:
        return x + gated_mlp(dot, u, p["mlp"])
    return x + gated_mlp(dot, u, p["shared"]) + routed_experts(cfg, dot, u, p)


def layer_trees(w: dict) -> list:
    """The layers in order, each without its leading axis."""
    return [jax.tree.map(lambda a: a[0], w["layers"][i])
            for i in sorted(w["layers"], key=int)]


def final_hidden(cfg: dict, w: dict, tokens, precision="float32"):
    """(rows, positions) token ids to the normed stream the head reads,
    each layer under a checkpoint."""
    dot = common.DOTS[precision]
    x = jnp.take(w["embedding"]["embedding"], tokens, axis=0)
    for p in layer_trees(w):
        x = jax.checkpoint(lambda x, p: layer(cfg, dot, x, p))(x, p)
    return rms_norm(x, w["norm_f"]["scale"], cfg["rms_norm_eps"])


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
    """Rows of random token ids of the slice of the vocabulary held here;
    the target of a position is the next token and the last position's wraps
    to the row's first."""
    toks = rng.integers(0, cfg["vocab_size"], (rows, mix["seq"]),
                        dtype=np.int32)
    return {"tokens": toks, "targets": np.roll(toks, -1, axis=-1)}

"""LFM2-8B-A1B as its ``config.json`` (``model_type: lfm2_moe``) and the
family's published modelling code describe it, as one chip's share of a
layer. Float32 ``jax.numpy``; no kernels, no sorting, no cache. Bias-free
throughout; ``u`` is a sub-block's normed input.

- Every layer: ``x = x + operator(RMSNorm(x)); x = x + ffn(RMSNorm(x))``
  (``operator_norm``, ``ffn_norm``, ``norm_eps``). ``layer_types`` says which
  operator a layer has, in order; the first ``num_dense_layers`` layers feed
  forward through the gated MLP at ``intermediate_size``, the rest through
  routed experts. The output is ``RMSNorm(x)`` (``embedding_norm``) into a
  head tied to the embedding.
- ``conv`` (``Lfm2ShortConv``, ``conv_bias`` false): ``[B; C; x] = u W_in``
  (three thirds of ``3 * hidden``); ``z = B * x``; ``c_t = sum_j w_j z_{t -
  (L-1) + j}`` for each channel apart, zeros to the left, ``L`` =
  ``conv_L_cache`` (torch's ``Conv1d(groups=hidden, padding=L-1)`` cut to
  the sequence: tap ``L - 1`` lies on the token itself); ``(C * c) W_out``.
  No activation. The filter is kept taps first, ``(L, hidden)``.
- ``full_attention``: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key-value heads of ``hidden / heads``, query heads
  ``i g .. i g + g - 1`` reading key-value head ``i``; RMSNorm over the
  head's width of every query and key head (``q_layernorm``,
  ``k_layernorm``) ahead of the rotation; rotary over the whole head,
  ``(x_i, x_{i + d/2})`` turning by ``pos * rope_theta^(-2i/d)``; causal
  softmax of ``q . k d^-0.5``; ``concat_h(a_h) W_o``.
- Routed experts (``Lfm2MoeSparseMoeBlock``): ``p = sigmoid(u W_r)`` over all
  published experts; the choice is the ``num_experts_per_tok`` largest of
  ``p + b`` (``use_expert_bias``; ``b`` is a buffer and takes no gradient);
  ``w_i = routed_scaling_factor p_i / (sum of chosen p + 1e-6)``
  (``norm_topk_prob``); ``F(u) = sum over chosen i held here of w_i E_i(u)``
  with gated SiLU experts, no shared expert.
- The share: the file's ``num_experts`` experts are held, ids
  ``deployment.first_expert_held`` on, of ``deployment.experts_published``
  the router scores; what the absent experts would add is left out.

The program departs in one place: its router divides by the chosen scores'
sum + 1e-20 (``transformer/moe.DroplessExperts``, DeepSeek-V3's), where this
file keeps the source's 1e-6: half a millionth of a weight, under bf16's
rounding two thousand times over.

As in ``instella.py``, whatever is as wide as a feed-forward or the
vocabulary goes ``CHUNK`` positions at a time, attention goes head by head
and block of queries by block, and every layer sits under a checkpoint: a
block of two rows of 8192 tokens stays near a gigabyte of scratch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from .instella import gated_mlp, in_chunks, rms_norm

CAUSAL = True

CONV, ATTENTION = "conv", "full_attention"


def head_positions(mix: dict) -> float:
    """Every position is decoded to the vocabulary."""
    return 1.0


def sizes(cfg: dict) -> dict:
    dep = cfg.get("deployment", {})
    held = cfg["num_experts"]
    kinds = list(cfg["layer_types"])
    if len(kinds) != cfg.get("n_layer", cfg["num_hidden_layers"]):
        raise ValueError("layer_types does not name every layer held")
    return {
        "hidden": cfg["hidden_size"],
        "layer_types": kinds, "layers": len(kinds),
        "dense_layers": cfg["num_dense_layers"],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head": cfg["hidden_size"] // cfg["num_attention_heads"],
        "taps": cfg["conv_L_cache"],
        "dense_ffn": cfg["intermediate_size"],
        "expert_ffn": cfg["moe_intermediate_size"],
        "held": held,
        "experts": dep.get("experts_published", held),
        "first_held": dep.get("first_expert_held", 0),
        "top_k": cfg["num_experts_per_tok"],
        "vocab": common.table_rows(cfg),
    }


# -- weights -----------------------------------------------------------------

def layer_shapes(z: dict, i: int, std: float) -> dict:
    """Layer ``i``'s leaves, each with a leading axis of 1."""
    h, d = z["hidden"], z["head"]
    flat = {"norm1/scale": ((1, h), "ones"), "norm2/scale": ((1, h), "ones")}
    if z["layer_types"][i] == CONV:
        flat.update({"conv/in/kernel": ((1, h, 3 * h), std),
                     "conv/taps": ((1, z["taps"], h), std),
                     "conv/out/kernel": ((1, h, h), std)})
    else:
        flat.update({"attn/q/kernel": ((1, h, z["heads"] * d), std),
                     "attn/k/kernel": ((1, h, z["kv_heads"] * d), std),
                     "attn/v/kernel": ((1, h, z["kv_heads"] * d), std),
                     "attn/q_norm/scale": ((1, d), "ones"),
                     "attn/k_norm/scale": ((1, d), "ones"),
                     "attn/o/kernel": ((1, z["heads"] * d, h), std)})
    if i < z["dense_layers"]:
        f = z["dense_ffn"]
        flat.update({"mlp/gate/kernel": ((1, h, f), std),
                     "mlp/up/kernel": ((1, h, f), std),
                     "mlp/down/kernel": ((1, f, h), std)})
    else:
        f = z["expert_ffn"]
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
            "norm_f/scale": ((z["hidden"],), "ones")}
    for i in range(z["layers"]):
        flat.update({f"layers/{i}/{k}": v
                     for k, v in layer_shapes(z, i, std).items()})
    return flat


def init_weights(cfg: dict, key, dtype=jnp.float32) -> dict:
    """Seeded weights as a nested dict: each layer a tree of its own under
    ``layers/<i>``, its leaves with a leading axis of 1 (a program stacks
    runs of like layers on that axis; ``instella.py`` says why they stay
    apart here)."""
    return common.nest(common.normal_leaves(key, weight_shapes(cfg), dtype))


def fused_parts(cfg: dict, name: str, x):
    """A layer leaf ``(1, ...)`` as ``(1, parts, elements)``: the routed
    experts' leaves hold one matrix an expert."""
    if "/experts/" in name:
        return x.reshape(x.shape[0], x.shape[1], -1)
    return x.reshape(x.shape[0], 1, -1)


# -- the model ---------------------------------------------------------------

def short_conv(cfg: dict, dot, u, p):
    h = u.shape[-1]
    taps = p["taps"]                                    # (L, hidden)
    bcx = in_chunks(lambda c: dot(c, p["in"]["kernel"]), u)
    z = bcx[..., :h] * bcx[..., 2 * h:]
    n, s = taps.shape[0], u.shape[1]
    padded = jnp.pad(z, ((0, 0), (n - 1, 0), (0, 0)))
    mixed = sum(taps[j] * padded[:, j:j + s] for j in range(n))
    return in_chunks(lambda c: dot(c, p["out"]["kernel"]),
                     bcx[..., h:2 * h] * mixed)


def rotate_halves(x, cos, sin):
    """Rotary on ``(..., positions, d)``: ``(x_i, x_{i + d/2})`` turns by
    the ``i``-th frequency."""
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(cfg: dict, dot, u, p):
    """Key-value head by key-value head and, inside, query head by query
    head of its group, each under a checkpoint, the heads' outputs summed
    through their rows of ``W_o``: one head's queries and scores are alive
    at a time."""
    z = sizes(cfg)
    rows, s, hidden = u.shape
    nh, nkv, d = z["heads"], z["kv_heads"], z["head"]
    g, eps = nh // nkv, cfg["norm_eps"]
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None]
           * float(cfg["rope_theta"]) ** (
               -jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    positions = jnp.arange(s)
    k = dot(u, p["k"]["kernel"]).reshape(rows, s, nkv, d)
    k = rotate_halves(jnp.moveaxis(
        rms_norm(k, p["k_norm"]["scale"], eps), 2, 0), cos, sin)
    v = jnp.moveaxis(dot(u, p["v"]["kernel"]).reshape(rows, s, nkv, d), 2, 0)

    @jax.checkpoint
    def head(w_q, w_o, k, v):
        q = rotate_halves(rms_norm(dot(u, w_q), p["q_norm"]["scale"], eps),
                          cos, sin)

        def row(q, k, v):
            def some(qc, at):
                scores = jnp.matmul(qc, k.T, precision=common.HIGHEST) \
                    * d ** -0.5
                scores = jnp.where(at[:, None] >= positions[None, :],
                                   scores, -jnp.inf)
                return jnp.matmul(jax.nn.softmax(scores, -1), v,
                                  precision=common.HIGHEST)

            return in_chunks(some, q[None], positions[None])[0]

        # a row at a time, CHUNK queries at a time: one block of scores
        return dot(jax.lax.map(lambda qkv: row(*qkv), (q, k, v)), w_o)

    def group(acc, xs):
        w_q, w_o, k, v = xs
        out, _ = jax.lax.scan(
            lambda a, ws: (a + head(*ws, k, v), None), acc, (w_q, w_o))
        return out, None

    w_q = jnp.moveaxis(p["q"]["kernel"].reshape(hidden, nkv, g, d), 0, 2)
    out, _ = jax.lax.scan(
        group, jnp.zeros_like(u),
        (w_q, p["o"]["kernel"].reshape(nkv, g, d, hidden), k, v))
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
        jnp.sum(picked, -1, keepdims=True) + 1e-6)
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
    eps = cfg["norm_eps"]
    u = rms_norm(x, p["norm1"]["scale"], eps)
    x = x + (short_conv(cfg, dot, u, p["conv"]) if "conv" in p
             else attention(cfg, dot, u, p["attn"]))
    u = rms_norm(x, p["norm2"]["scale"], eps)
    return x + (gated_mlp(dot, u, p["mlp"]) if "mlp" in p
                else routed_experts(cfg, dot, u, p))


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
    return rms_norm(x, w["norm_f"]["scale"], cfg["norm_eps"])


def logits(cfg: dict, w: dict, tokens, precision="float32"):
    """(rows, positions) token ids to (rows, positions, vocab) logits."""
    return common.DOTS[precision](final_hidden(cfg, w, tokens, precision),
                                  w["embedding"]["embedding"].T)


def loss_numerators(cfg: dict, w: dict, block: dict, precision="float32"):
    """The loss is one mean: the sum of these rows' per-token losses, the
    tied head and the softmax taken ``CHUNK`` positions at a time."""
    dot = common.DOTS[precision]
    h = final_hidden(cfg, w, block["tokens"], precision)
    head = w["embedding"]["embedding"].T
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

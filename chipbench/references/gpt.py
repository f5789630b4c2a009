"""GPT-2 as published (Radford et al. 2019): learned positions, pre-LN
blocks, fused QKV, the activation and LayerNorm epsilon its ``config.json``
names, tied output embedding, mean next-token loss. Float32 ``jax.numpy``;
no kernels, no cache, no batching tricks.

Departures: the embedding table holds ``padded_vocab_size`` rows, as
Megatron-LM trains GPT-2, and the softmax runs over all of them; the fused
QKV output is laid out ``(head, {q,k,v}, head_dim)``, the layout the seeded
weights are drawn in.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from . import common


CAUSAL = True


def head_positions(mix: dict) -> float:
    """Every position is decoded to the vocabulary."""
    return 1.0


def sizes(cfg: dict) -> dict:
    h = cfg["n_embd"]
    return {"hidden": h, "layers": cfg["n_layer"], "heads": cfg["n_head"],
            "vocab": common.table_rows(cfg), "positions": cfg["n_positions"],
            "ffn": cfg.get("n_inner") or 4 * h}


def fused_parts(cfg: dict, name: str, x):
    """A stacked layer leaf ``(layers, ...)`` as ``(layers, parts,
    elements)``: the fused QKV kernel and bias hold three matrices."""
    if name.startswith("layers/qkv/"):
        return common.split_qkv(x, cfg["n_head"])
    return x.reshape(x.shape[0], 1, -1)


def weight_shapes(cfg: dict) -> dict:
    z = sizes(cfg)
    h, n, f = z["hidden"], z["layers"], z["ffn"]
    std = cfg.get("initializer_range", 0.02)
    out_std = std / (2 * n) ** 0.5
    flat = {"embedding/embedding": ((z["vocab"], h), std),
            "position": ((z["positions"], h), std),
            "ln_f/scale": ((h,), "ones"), "ln_f/bias": ((h,), "zeros")}
    for ln in ("ln1", "ln2"):
        flat[f"layers/{ln}/scale"] = ((n, h), "ones")
        flat[f"layers/{ln}/bias"] = ((n, h), "zeros")
    for name, n_in, n_out, s in (("qkv", h, 3 * h, std),
                                 ("proj", h, h, out_std),
                                 ("fc1", h, f, std), ("fc2", f, h, out_std)):
        flat[f"layers/{name}/kernel"] = ((n, n_in, n_out), s)
        flat[f"layers/{name}/bias"] = ((n, n_out), "zeros")
    return flat


def init_weights(cfg: dict, key, dtype=jnp.float32) -> dict:
    """Seeded weights as a nested dict, layers stacked on a leading axis."""
    return common.nest(common.normal_leaves(key, weight_shapes(cfg), dtype))


def _block(cfg, dot, h, p):
    z = sizes(cfg)
    b, s, _ = h.shape
    nh = z["heads"]
    d = z["hidden"] // nh
    eps = cfg["layer_norm_epsilon"]
    act = common.ACTIVATIONS[cfg["activation_function"]]
    x = common.layer_norm(h, p["ln1"]["scale"], p["ln1"]["bias"], eps)
    qkv = dot(x, p["qkv"]["kernel"]) + p["qkv"]["bias"]
    qkv = qkv.reshape(b, s, nh, 3, d).transpose(3, 0, 2, 1, 4)
    q, k, v = qkv[0], qkv[1], qkv[2]                     # (b, nh, s, d)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        precision=common.HIGHEST) / d ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v,
                      precision=common.HIGHEST)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, nh * d)
    h = h + dot(attn, p["proj"]["kernel"]) + p["proj"]["bias"]
    x = common.layer_norm(h, p["ln2"]["scale"], p["ln2"]["bias"], eps)
    x = act(dot(x, p["fc1"]["kernel"]) + p["fc1"]["bias"])
    return h + dot(x, p["fc2"]["kernel"]) + p["fc2"]["bias"]


def logits(cfg: dict, w: dict, tokens, precision="float32"):
    """(rows, positions) token ids to (rows, positions, vocab) logits."""
    dot = common.DOTS[precision]
    s = tokens.shape[1]
    h = jnp.take(w["embedding"]["embedding"], tokens, axis=0) \
        + w["position"][:s]

    def body(h, p):
        return _block(cfg, dot, h, p), None

    h, _ = jax.lax.scan(jax.checkpoint(body), h, w["layers"])
    h = common.layer_norm(h, w["ln_f"]["scale"], w["ln_f"]["bias"],
                          cfg["layer_norm_epsilon"])
    return dot(h, w["embedding"]["embedding"].T)


def loss_numerators(cfg: dict, w: dict, block: dict, precision="float32"):
    """The loss is one mean: the sum of these rows' per-token losses."""
    nll = common.cross_entropy(logits(cfg, w, block["tokens"], precision),
                               block["targets"])
    return jnp.sum(nll)[None]


def denominators(batch: dict):
    """What each numerator is divided by, over the whole batch."""
    import numpy as np

    return np.asarray([batch["tokens"].size], np.float64)


def make_batch(cfg: dict, mix: dict, rng, rows: int) -> dict:
    """Rows of distinct random token ids of the published vocabulary (the
    padded rows are never drawn); the target of a position is the next
    token and the last position's wraps to the row's first, as the
    trainer's synthetic stream has it."""
    import numpy as np

    toks = rng.integers(0, cfg["vocab_size"], (rows, mix["seq"]),
                        dtype=np.int32)
    return {"tokens": toks, "targets": np.roll(toks, -1, axis=-1)}

"""BERT as published (Devlin et al. 2019): token, position and segment
embeddings under a LayerNorm, post-LN blocks with the activation
(``hidden_act``: the exact GeLU) and LayerNorm epsilon its ``config.json``
names, a masked-LM head (dense, GeLU, LayerNorm, tied decoder with a bias)
and a next-sentence head on the tanh pooler. The loss is the masked-LM loss
averaged over the masked positions plus the mean next-sentence loss.
Float32 ``jax.numpy``; no kernels.

Departures: the embedding table holds ``padded_vocab_size`` rows, as
Megatron-LM trains BERT; Q, K and V are one fused matrix whose output is
laid out ``(head, {q,k,v}, head_dim)``; no dropout. Where the program
departs from the source (``PERF.md``, Open questions), the reference does
not follow it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common

CAUSAL = False


def head_positions(mix: dict) -> float:
    """Only the masked positions need decoding to the vocabulary."""
    return mix["masked_share"]


def sizes(cfg: dict) -> dict:
    return {"hidden": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
            "heads": cfg["num_attention_heads"],
            "vocab": common.table_rows(cfg),
            "positions": cfg["max_position_embeddings"],
            "types": cfg["type_vocab_size"], "ffn": cfg["intermediate_size"]}


def fused_parts(cfg: dict, name: str, x):
    """As GPT-2's: the fused QKV leaves hold three matrices."""
    if name.startswith("layers/qkv/"):
        return common.split_qkv(x, cfg["num_attention_heads"])
    return x.reshape(x.shape[0], 1, -1)


def weight_shapes(cfg: dict) -> dict:
    z = sizes(cfg)
    h, n, f = z["hidden"], z["layers"], z["ffn"]
    std = cfg.get("initializer_range", 0.02)
    out_std = std / (2 * n) ** 0.5
    flat = {"embedding/embedding": ((z["vocab"], h), std),
            "position": ((z["positions"], h), std),
            "tokentype": ((z["types"], h), std),
            "lm_dense/kernel": ((h, h), std), "lm_dense/bias": ((h,), "zeros"),
            "lm_bias": ((z["vocab"],), "zeros"),
            "pooler/kernel": ((h, h), std), "pooler/bias": ((h,), "zeros"),
            "binary_head/kernel": ((h, 2), std),
            "binary_head/bias": ((2,), "zeros")}
    for ln in ("ln_emb", "lm_ln"):
        flat[f"{ln}/scale"] = ((h,), "ones")
        flat[f"{ln}/bias"] = ((h,), "zeros")
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
    return common.nest(common.normal_leaves(key, weight_shapes(cfg), dtype))


def _block(cfg, dot, h, p):
    z = sizes(cfg)
    b, s, _ = h.shape
    nh = z["heads"]
    d = z["hidden"] // nh
    eps = cfg["layer_norm_eps"]
    qkv = dot(h, p["qkv"]["kernel"]) + p["qkv"]["bias"]
    qkv = qkv.reshape(b, s, nh, 3, d).transpose(3, 0, 2, 1, 4)
    q, k, v = qkv[0], qkv[1], qkv[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        precision=common.HIGHEST) / d ** 0.5
    attn = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v,
                      precision=common.HIGHEST)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, nh * d)
    h = common.layer_norm(
        h + dot(attn, p["proj"]["kernel"]) + p["proj"]["bias"],
        p["ln1"]["scale"], p["ln1"]["bias"], eps)
    x = common.ACTIVATIONS[cfg["hidden_act"]](
        dot(h, p["fc1"]["kernel"]) + p["fc1"]["bias"])
    return common.layer_norm(
        h + dot(x, p["fc2"]["kernel"]) + p["fc2"]["bias"],
        p["ln2"]["scale"], p["ln2"]["bias"], eps)


def loss_numerators(cfg: dict, w: dict, block: dict, precision="float32"):
    """``[sum of the masked positions' losses, sum of the rows'
    next-sentence losses]`` of these rows. Every key is attended (the
    traffic pads nothing)."""
    dot = common.DOTS[precision]
    toks = block["tokens"]
    s = toks.shape[1]
    h = (jnp.take(w["embedding"]["embedding"], toks, axis=0)
         + w["position"][:s]
         + jnp.take(w["tokentype"], block["types"], axis=0))
    eps = cfg["layer_norm_eps"]
    h = common.layer_norm(h, w["ln_emb"]["scale"], w["ln_emb"]["bias"], eps)

    def body(h, p):
        return _block(cfg, dot, h, p), None

    h, _ = jax.lax.scan(jax.checkpoint(body), h, w["layers"])
    pooled = jnp.tanh(dot(h[:, 0], w["pooler"]["kernel"])
                      + w["pooler"]["bias"])
    binary = dot(pooled, w["binary_head"]["kernel"]) \
        + w["binary_head"]["bias"]
    nsp = common.cross_entropy(binary, block["nsp"])
    g = common.ACTIVATIONS[cfg["hidden_act"]](
        dot(h, w["lm_dense"]["kernel"]) + w["lm_dense"]["bias"])
    g = common.layer_norm(g, w["lm_ln"]["scale"], w["lm_ln"]["bias"], eps)
    logits = dot(g, w["embedding"]["embedding"].T) + w["lm_bias"]
    lm = common.cross_entropy(logits, block["labels"])
    return jnp.stack([jnp.sum(lm * block["loss_mask"]), jnp.sum(nsp)])


def denominators(batch: dict):
    return np.asarray([max(float(batch["loss_mask"].sum()), 1.0),
                       float(len(batch["nsp"]))], np.float64)


def make_batch(cfg: dict, mix: dict, rng, rows: int) -> dict:
    """Rows of distinct random token ids of the published vocabulary, no
    padding, one segment; ``masked_share`` of the positions, drawn one by
    one, carry a random label; a random next-sentence label a row."""
    n_ids = cfg["vocab_size"]
    seq = mix["seq"]
    return {
        "tokens": rng.integers(0, n_ids, (rows, seq), dtype=np.int32),
        "attention": np.ones((rows, seq), np.int32),
        "loss_mask": (rng.random((rows, seq))
                      < mix["masked_share"]).astype(np.int32),
        "labels": rng.integers(0, n_ids, (rows, seq), dtype=np.int32),
        "nsp": rng.integers(0, 2, (rows,), dtype=np.int32),
        "types": np.zeros((rows, seq), np.int32)}

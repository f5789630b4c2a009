"""Operations and bytes that the algorithm needs, from shapes alone.

What the program executes (recompute under activation checkpointing, padded
positions, empty grid steps) is not counted: a share of a peak is useful
work over what the chip could do in the time.
"""

from __future__ import annotations


def transformer_sizes(cfg: dict) -> dict:
    """Hidden size, depth, heads, feed-forward width and vocabulary from a
    configuration file in either family's published key names."""
    hidden = cfg.get("n_embd", cfg.get("hidden_size"))
    return {
        "hidden": hidden,
        "layers": cfg.get("n_layer", cfg.get("num_hidden_layers")),
        "heads": cfg.get("n_head", cfg.get("num_attention_heads")),
        "ffn": (cfg.get("n_inner") or cfg.get("intermediate_size")
                or 4 * hidden),
        "vocab": cfg["vocab_size"],
    }


def forward_flops_per_token(cfg: dict, context: float, *, causal: bool,
                            head_positions: float = 1.0) -> float:
    """Multiply-adds x 2 of one token's forward pass: the four matrices of
    every block (12 h^2 with a feed-forward of 4 h), attention against
    ``context`` keys (halved by the caller's choice of ``context`` where
    causal: pass the mean number of keys a query sees), and the output
    embedding for the share ``head_positions`` of positions that are decoded
    to the vocabulary. A model-specific head (BERT's dense + pooler) is
    under a percent and left out."""
    z = transformer_sizes(cfg)
    h, n, f = z["hidden"], z["layers"], z["ffn"]
    per_layer = 2 * (4 * h * h + 2 * h * f)
    attn = 2 * 2 * context * h          # QK^T and PV, all heads together
    if causal:
        attn *= 0.5
    return n * (per_layer + attn) + head_positions * 2 * z["vocab"] * h


def train_flops_per_token(cfg: dict, seq: int, *, causal: bool,
                          head_positions: float = 1.0) -> float:
    """Forward plus backward (twice the forward); recompute not counted."""
    return 3.0 * forward_flops_per_token(
        cfg, seq, causal=causal, head_positions=head_positions)


def attention_layers(cfg: dict) -> int:
    """Layers that run ``attention_core``: every one."""
    return transformer_sizes(cfg)["layers"]


def attention_core(cfg: dict, rows: int, seq: int, *, causal: bool,
                   backward: bool, bytes_per_el: int = 2) -> dict:
    """softmax(Q K^T) V over all heads of one layer for ``rows`` sequences.

    Forward: two products of 2 s^2 d each per head; reads Q, K, V and
    writes O. Backward: five such products (S again, dV, dP, dQ, dK);
    reads Q, K, V, O, dO and writes dQ, dK, dV. A causal mask halves the
    products. The row statistics are s floats a head and left out."""
    z = transformer_sizes(cfg)
    nh = z["heads"]
    d = z["hidden"] // nh
    products = 5 if backward else 2
    flops = products * 2.0 * rows * nh * seq * seq * d
    if causal:
        flops *= 0.5
    tensors = 8 if backward else 4
    return {"flops": flops,
            "bytes": float(tensors * rows * nh * seq * d * bytes_per_el)}


def least_seconds(work: dict, peak: dict) -> float:
    """The roofline: the larger of operations over the peak rate and bytes
    over the peak bandwidth."""
    return max(work["flops"] / peak["flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])

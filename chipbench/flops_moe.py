"""Operations and bytes that a DeepSeek-V3-style expert model's algorithm
needs, as one chip's share of its layers holds it, from the configuration's
keys alone. ``flops.py`` reads every layer as four ``h x h`` projections and
a two-matrix feed-forward; this block is neither.

Counted: the latent projections and the gate, the attention core (causal,
halved), the dense layers' gated MLP, the shared experts, the router over
every published expert, the routed experts held here in expectation
(``num_experts_per_tok * held / published`` assignments a token), the head
over the slice of the vocabulary. Recompute, buffer rows that hold nothing
and the sort that fills them are not work.
"""

from __future__ import annotations

from . import flops


def sizes(cfg: dict) -> dict:
    """The reference family's reading of the keys, with the layers by kind,
    the head's width ``qk`` and the vocabulary as ids are drawn from it (the
    padded rows are weights, not work the algorithm needs)."""
    from .references import instella

    z = instella.sizes(cfg)
    return dict(z, qk=z["nope"] + z["rope"],
                expert_layers=z["layers"] - z["dense_layers"],
                vocab=cfg["vocab_size"])


def assignments_per_token(cfg: dict) -> float:
    """Assignments to experts held here that a token makes in a layer, in
    expectation under an even router."""
    z = sizes(cfg)
    return z["top_k"] * z["held"] / z["experts"]


def forward_parts_per_token(cfg: dict, seq: int, *, causal: bool = True,
                            head_positions: float = 1.0) -> dict:
    """Multiply-adds x 2 of one token's forward pass, part by part."""
    z = sizes(cfg)
    h, nh = z["hidden"], z["heads"]
    gated = lambda width: 3 * 2 * h * width
    projections = 2 * (h * nh * z["qk"] + h * (z["latent"] + z["rope"])
                       + z["latent"] * nh * (z["nope"] + z["v"])
                       + h * nh * z["v"] + nh * z["v"] * h)
    # QK^T and PV against seq keys, halved by a causal mask
    core = (0.5 if causal else 1.0) * 2 * seq * nh * (z["qk"] + z["v"])
    layers = z["dense_layers"] + z["expert_layers"]
    return {
        "attention_projections": layers * projections,
        "attention_core": layers * core,
        "dense_mlp": z["dense_layers"] * gated(z["dense_ffn"]),
        "shared_experts": z["expert_layers"] * gated(z["shared_ffn"]),
        "router": z["expert_layers"] * 2 * h * z["experts"],
        "routed_experts": z["expert_layers"] * assignments_per_token(cfg)
        * gated(z["expert_ffn"]),
        "head": head_positions * 2 * z["vocab"] * h,
    }


def train_flops_per_token(cfg: dict, seq: int, *, causal: bool = True,
                          head_positions: float = 1.0) -> float:
    """Forward plus backward (twice the forward); recompute not counted."""
    return 3.0 * sum(forward_parts_per_token(
        cfg, seq, causal=causal, head_positions=head_positions).values())


#: the attention core and the layers that run it, as ``flops.py`` counts
#: them: every layer attends, and this model's heads are as wide for values
#: (``v_head_dim``) as for queries and keys (``qk_nope_head_dim +
#: qk_rope_head_dim``): ``hidden_size // num_attention_heads``, all 128. A
#: family whose two widths differ writes its own.
attention_layers = flops.attention_layers
attention_core = flops.attention_core


def grouped_products(cfg: dict, tokens: int, *, bytes_per_el: int = 2) -> dict:
    """The routed experts' products of one expert layer for one step over
    ``tokens`` tokens: 3 forward (gate, up, down) and 6 backward (each one's
    gradient to its input and to its weight), every one
    ``2 * assignments * hidden * width``. Bytes: each product reads its two
    operands and writes its result once; the weights are read whole."""
    z = sizes(cfg)
    rows = tokens * assignments_per_token(cfg)
    h, f = z["hidden"], z["expert_ffn"]
    weight = z["held"] * h * f
    act = rows * (h + f)
    return {"flops": 9 * 2.0 * rows * h * f,
            # forward and input-gradient products read a weight and write
            # an activation; weight-gradient products read two activations
            # and write a weight
            "bytes": float(9 * (weight + act) * bytes_per_el)}

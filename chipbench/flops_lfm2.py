"""Operations and bytes that an LFM2 expert model's algorithm needs, as one
chip's share of its layers holds it, from the configuration's keys alone.
``flops.py`` reads every layer as attention over four ``h x h`` projections
and a two-matrix feed-forward; here most layers mix tokens with a gated
short convolution, the few that attend share key-value heads, and the
feed-forward is dense in the leading layers and routed in the rest.

Counted: the conv layers' two projections (``2 x hidden x 4 hidden`` a
token) and the filter with its gates, the attention layers' projections
(grouped: K and V are ``kv_heads`` wide) and core (causal, halved), the
dense layers' gated MLP, the router over every published expert, the routed
experts held here in expectation (``num_experts_per_tok * held / published``
assignments a token), the tied head over the slice of the vocabulary.
Recompute, buffer rows that hold nothing and the sort that fills them are
not work.
"""

from __future__ import annotations


def sizes(cfg: dict) -> dict:
    """The reference family's reading of the keys, with the layers by
    kind."""
    from .references import lfm2

    z = lfm2.sizes(cfg)
    attn = sum(t == lfm2.ATTENTION for t in z["layer_types"])
    return dict(z, attn_layers=attn, conv_layers=z["layers"] - attn,
                expert_layers=z["layers"] - z["dense_layers"],
                vocab=cfg["vocab_size"])


def assignments_per_token(cfg: dict) -> float:
    """Assignments to experts held here that a token makes in a layer, in
    expectation under an even router."""
    z = sizes(cfg)
    return z["top_k"] * z["held"] / z["experts"]


#: operations a token and channel in the gated filter: forward the gate
#: ``B * u``, ``L`` products and ``L - 1`` sums, the gate ``C * c``;
#: backward those again for ``c`` (the gates' own gradients need it), the
#: two gates' four products, the filter's transpose and the taps' own
#: gradient (a product and a sum a tap)
def _mix_ops(taps: int, backward: bool) -> int:
    forward = 2 * taps + 1
    return forward + 4 + (2 * taps - 1) + 2 * taps if backward else forward


def forward_parts_per_token(cfg: dict, seq: int, *, causal: bool = True,
                            head_positions: float = 1.0) -> dict:
    """Multiply-adds x 2 of one token's forward pass, part by part."""
    z = sizes(cfg)
    h, nh, nkv, d = z["hidden"], z["heads"], z["kv_heads"], z["head"]
    gated = lambda width: 3 * 2 * h * width
    return {
        "conv_projections": z["conv_layers"] * 2 * h * 4 * h,
        "conv_mix": z["conv_layers"] * _mix_ops(z["taps"], False) * h,
        "attention_projections": z["attn_layers"] * 2 * (
            2 * h * nh * d + 2 * h * nkv * d),
        # QK^T and PV against seq keys, halved by a causal mask
        "attention_core": z["attn_layers"] * (0.5 if causal else 1.0)
        * 2 * seq * nh * 2 * d,
        "dense_mlp": z["dense_layers"] * gated(z["dense_ffn"]),
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


def attention_layers(cfg: dict) -> int:
    """Layers that run ``attention_core``: those ``layer_types`` calls
    ``full_attention``."""
    return sizes(cfg)["attn_layers"]


def attention_core(cfg: dict, rows: int, seq: int, *, causal: bool,
                   backward: bool, bytes_per_el: int = 2) -> dict:
    """softmax(Q K^T) V over all query heads of one attention layer for
    ``rows`` sequences, as ``flops.attention_core`` counts it, with the
    bytes of grouped heads: Q, O and their gradients are ``heads`` wide, K,
    V and theirs ``kv_heads`` (each read once: a group shares one)."""
    z = sizes(cfg)
    nh, nkv, d = z["heads"], z["kv_heads"], z["head"]
    flops = (5 if backward else 2) * 2.0 * rows * nh * seq * seq * d
    if causal:
        flops *= 0.5
    wide, narrow = (4, 4) if backward else (2, 2)
    return {"flops": flops,
            "bytes": float((wide * nh + narrow * nkv) * rows * seq * d
                           * bytes_per_el)}


def conv_mix(cfg: dict, rows: int, seq: int, *, backward: bool,
             bytes_per_el: int = 2) -> dict:
    """The gated filter of every conv layer of one step over ``rows``
    sequences, between the two projections, whatever implements it.
    Forward it reads ``B``, ``C`` and ``u`` and writes one value a token
    and channel; backward it reads those three and the result's gradient
    and writes the three's gradients (the taps and theirs are a few
    kilobytes)."""
    z = sizes(cfg)
    each = rows * seq * z["hidden"] * z["conv_layers"]
    return {"flops": float(_mix_ops(z["taps"], backward) * each),
            "bytes": float((7 if backward else 4) * each * bytes_per_el)}

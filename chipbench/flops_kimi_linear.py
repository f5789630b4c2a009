"""Operations and bytes that a Kimi Linear expert model's algorithm needs, as
one chip's share of its layers holds it, from the configuration's keys
alone. ``flops.py`` reads every layer as attention over four ``h x h``
projections and a two-matrix feed-forward; here most layers mix tokens with
Kimi Delta Attention (a state a head, no score matrix over the sequence),
the few that attend score over a wider head than they sum values of, and the
feed-forward is dense in the leading layers and a shared expert beside routed
ones in the rest.

Counted: the KDA layers' projections (q, k, v and o, the two low-rank pairs,
the step), their three filters with SiLU, and the delta rule in its chunked
form at ``CHUNK`` tokens (below); the latent attention's projections and core
(causal, halved; 192 for scores, 128 for values); the dense layers' gated
MLP, the shared expert, the router over every published expert, the routed
experts held here in expectation (``num_experts_per_token * held /
published`` assignments a token), the head over the slice of the vocabulary.
Recompute, buffer rows that hold nothing and the sort that fills them are
not work.
"""

from __future__ import annotations

#: tokens in a chunk of the delta rule's chunked form, as the trainer runs it
CHUNK = 64


def sizes(cfg: dict) -> dict:
    """The reference family's reading of the keys, with the layers by
    kind."""
    from .references import kimi_linear

    z = kimi_linear.sizes(cfg)
    kda = sum(t == kimi_linear.KDA for t in z["layer_types"])
    return dict(z, kda_layers=kda, attn_layers=z["layers"] - kda,
                expert_layers=z["layers"] - z["dense_layers"],
                qk=z["nope"] + z["rope"], vocab=cfg["vocab_size"])


def assignments_per_token(cfg: dict) -> float:
    """Assignments to experts held here that a token makes in a layer, in
    expectation under an even router."""
    z = sizes(cfg)
    return z["top_k"] * z["held"] / z["experts"]


#: operations a token and channel in a filter with SiLU: forward ``L``
#: products and ``L - 1`` sums and SiLU (a sigmoid and a product); backward
#: the filter again (SiLU's gradient needs its input), SiLU's gradient (a
#: sigmoid and four more), the filter's transpose and the taps' own
#: gradient (a product and a sum a tap)
def _mix_ops(taps: int, backward: bool) -> int:
    forward = 2 * taps - 1 + 2
    return forward + 5 + (2 * taps - 1) + 2 * taps if backward else forward


def _scan_flops_per_token_head(d_k: int, d_v: int, chunk: int) -> float:
    """Multiply-adds x 2 a token and head of the chunked delta rule's
    forward pass. Inside a chunk of ``C`` tokens (per token: divided by
    ``C``): the decayed products ``A`` (keys with keys) and ``B`` (queries
    with keys) below the diagonal, ``C^2 / 2`` pairs of ``d_k`` each; the
    inverse of ``I + A`` by substitution, ``C^3 / 3``; ``W`` and ``U_0``,
    the triangular inverse times ``(C, d_k)`` and ``(C, d_v)``; ``B U``;
    and the three products with the ``(d_k, d_v)`` state: ``W S``, ``Q S``
    and ``K^T U``."""
    c = chunk
    return (2 * c * d_k                  # A and B: 2 x (C^2 / 2) d_k / C x 2
            + 2 * c * c / 3              # (I + A)^-1
            + c * d_k + c * d_v          # W, U_0
            + c * d_v                    # B U
            + 3 * 2 * d_k * d_v)         # W S, Q S, K^T U


def forward_parts_per_token(cfg: dict, seq: int, *, causal: bool = True,
                            head_positions: float = 1.0) -> dict:
    """Multiply-adds x 2 of one token's forward pass, part by part."""
    z = sizes(cfg)
    h, nh = z["hidden"], z["heads"]
    kh, kd, r = z["kda_heads"], z["kda_head"], z["gate_rank"]
    wide = kh * kd
    gated = lambda width: 3 * 2 * h * width
    return {
        "kda_projections": z["kda_layers"] * 2 * (
            4 * h * wide + 2 * (h * r + r * wide) + h * kh),
        "conv_mix": z["kda_layers"] * _mix_ops(z["taps"], False) * 3 * wide,
        "kda_scan": z["kda_layers"] * kh * _scan_flops_per_token_head(
            kd, kd, CHUNK),
        "attention_projections": z["attn_layers"] * 2 * (
            h * nh * z["qk"] + h * (z["latent"] + z["rope"])
            + z["latent"] * nh * (z["nope"] + z["v"]) + nh * z["v"] * h),
        # QK^T over qk and PV over v against seq keys, halved by a causal
        # mask
        "attention_core": z["attn_layers"] * (0.5 if causal else 1.0)
        * 2 * seq * nh * (z["qk"] + z["v"]),
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


def attention_layers(cfg: dict) -> int:
    """Layers that run ``attention_core``: those ``full_attn_layers``
    names."""
    return sizes(cfg)["attn_layers"]


def attention_core(cfg: dict, rows: int, seq: int, *, causal: bool,
                   backward: bool, bytes_per_el: int = 2) -> dict:
    """softmax(Q K^T) V over all heads of one latent-attention layer for
    ``rows`` sequences, with scores over ``qk`` = ``qk_nope_head_dim +
    qk_rope_head_dim`` and values of ``v_head_dim``. Forward: ``Q K^T`` (2
    s^2 qk) and ``P V`` (2 s^2 v) a head; reads Q, K, V, writes O. Backward:
    S again, dQ and dK over ``qk``, dP and dV over ``v``; reads Q, K, V, O,
    dO and writes dQ, dK, dV. A causal mask halves the products."""
    z = sizes(cfg)
    nh, qk, v = z["heads"], z["qk"], z["v"]
    widths = (3 * qk + 2 * v) if backward else (qk + v)
    flops = 2.0 * rows * nh * seq * seq * widths
    if causal:
        flops *= 0.5
    per_token = (4 * qk + 4 * v) if backward else (2 * qk + 2 * v)
    return {"flops": flops,
            "bytes": float(per_token * rows * nh * seq * bytes_per_el)}


def conv_mix(cfg: dict, rows: int, seq: int, *, backward: bool,
             bytes_per_el: int = 2) -> dict:
    """The three filters with their SiLU of every KDA layer of one step over
    ``rows`` sequences, whatever implements them. Forward a filter reads one
    value a token and channel and writes one; backward it reads that and the
    result's gradient and writes the input's (the taps and theirs are a few
    kilobytes)."""
    z = sizes(cfg)
    each = rows * seq * 3 * z["kda_heads"] * z["kda_head"] * z["kda_layers"]
    return {"flops": float(_mix_ops(z["taps"], backward) * each),
            "bytes": float((3 if backward else 2) * each * bytes_per_el)}


def kda_scan(cfg: dict, rows: int, seq: int, *, backward: bool,
             bytes_per_el: int = 2) -> dict:
    """The delta rule of every KDA layer of one step over ``rows``
    sequences, in its chunked form at ``CHUNK`` tokens, whatever implements
    it (:func:`_scan_flops_per_token_head`; backward twice the forward, as
    each product has two gradients). Bytes, forward: q, k, v in and o out in
    ``bytes_per_el``, the log-decays (float32, ``d_k`` a token and head) and
    the step in, and each chunk's ``(d_k, d_v)`` float32 state written once;
    backward: those read again with the states and o's gradient, and the
    five gradients written."""
    z = sizes(cfg)
    kh, kd = z["kda_heads"], z["kda_head"]
    tokens = rows * seq * z["kda_layers"]
    forward = tokens * kh * _scan_flops_per_token_head(kd, kd, CHUNK)
    narrow = tokens * kh * kd * bytes_per_el       # one of q, k, v, o
    decays = tokens * kh * (kd + 1) * 4            # g and beta
    states = -(-seq // CHUNK) * rows * z["kda_layers"] * kh * kd * kd * 4
    if backward:
        return {"flops": 2.0 * forward,
                "bytes": float(8 * narrow + 2 * decays + states)}
    return {"flops": float(forward),
            "bytes": float(4 * narrow + decays + states)}

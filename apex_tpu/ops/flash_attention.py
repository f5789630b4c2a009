"""Flash attention — blockwise fused attention as a Pallas TPU kernel.

This one kernel family subsumes three of the reference's CUDA extensions
(SURVEY.md §2.2): ``fmhalib`` (flash-style fused MHA, fp16 seq ≤ 512, SM80 —
apex/contrib/fmha/fmha.py:33-74), ``fast_multihead_attn`` (fused self/encdec
attention, apex/contrib/multihead_attn/), and the two Megatron fused-softmax
kernels (csrc/megatron/scaled_(upper_triang_)masked_softmax.h, sk ≤ 2048)
whose job was to keep the score matrix out of HBM. Blockwise online softmax
(the published FlashAttention recurrence) never materializes scores at all,
and has no 512/2048 sequence cap — the envelope is VMEM, and beyond that the
``context``-axis ring attention (apex_tpu.transformer.ring) tiles over chips.

Layout: ``(batch, heads, seq, head_dim)`` — the reference's score layout
``(b, np, sq, sk)`` (fused_softmax.py:67-92) with head_dim restored.

Forward saves only O and the per-row logsumexp; backward recomputes scores
blockwise (the fmha/FlashAttention memory plan) in two passes: one gridded
over q-blocks for dQ, one over k-blocks for dK/dV (on ``S^T = K Q^T``, so
neither of its products transposes an operand).

Tiles: the score tile's edges are derived from the shape and the masks
(:func:`flash_tile_plan`) unless the caller gives ``block_q`` / ``block_k``.
Causal or windowed attention takes the smallest edge whose computed tiles
the resident kernels can unroll (512 at 1024 tokens: 3 of the square's 4
tiles); tiles whose every score is masked are never computed, and only tiles
the diagonal or the window's edge crosses pay mask arithmetic. Where the
tile bounds are known at trace time (no ring ``offsets``, no
contiguous-segment bounds) the kernels walk them with static bounds, one
branch per outer block, so the scheduler overlaps one tile's softmax with
the next tile's products; traced bounds keep a dynamic ``fori_loop``. Read
on the chip: PERF.md Findings, PR 27.

Masking: ``causal=True`` for the upper-triangular variant, and/or an additive
``bias`` broadcastable to ``(b, h, sq, sk)`` (the additive-mask path of
fast_multihead_attn; boolean masks become ``-10000`` biases upstream, matching
the reference's masked_fill value), and/or ``segment_ids`` — packed-varlen
attention (the reference fmha's cu_seqlens contract, fmha.py:33-74): tokens
attend only within their segment, and for the contiguous (non-decreasing-ids)
layout the kernel SKIPS score blocks whose q/k segment ranges cannot
intersect, so a batch of short sequences pays ~sum(len_i^2) FLOPs instead of
the padded total^2 — the entire point of the reference's packed kernel.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.layer_norm import (
    _interpret,
    _pallas_unsupported,
    _resolve_impl,
)

_NEG_INF = -1e30
# TPU vreg geometry: segment ids ride in a lane-major layout (q ids
# replicated over lanes, kv ids over sublanes) so the in-kernel equality
# test is a plain vector compare — the standard Pallas idiom.
_NUM_LANES = 128
_NUM_SUBLANES = 8


def _pick_block(n: int, target: int, mult: int = 8) -> int:
    """Largest multiple-of-``mult`` divisor of n that is <= target (n if
    none)."""
    best = None
    for cand in range(min(n, target), mult - 1, -1):
        if n % cand == 0 and cand % mult == 0:
            best = cand
            break
    return best if best is not None else n


def _supported(sq: int, sk: int, d: int) -> bool:
    """Shapes the Pallas path handles without padding: 8-aligned seqs.

    The analog of the reference's ``is_kernel_available`` envelope
    (fused_softmax.py:151-171) — unsupported shapes fall back to the XLA
    path, like the reference falls back to torch softmax."""
    return sq % 8 == 0 and sk % 8 == 0 and d >= 8


# ---------------------------------------------------------------------------
# The tile plan: which (q block, k block) score tiles a call computes, which
# of those need mask arithmetic, and whether the walk over them is known when
# the kernel is traced.
# ---------------------------------------------------------------------------

# Tile edges the derived plan tries, smallest first, for shapes whose masks
# leave whole tiles empty (causal, window): the first whose computed tiles
# unroll in at most _MAX_STATIC_TILES. Unmasked shapes have nothing to skip
# and take the largest. Read on the chip, PERF.md Findings, PR 27.
_DERIVED_EDGES = (512, 1024)
# The most tiles one kernel unrolls: past it the program text (and the
# Mosaic compile) grows with the sequence, so the dynamic loop takes over.
_MAX_STATIC_TILES = 10
# The most scores one program's unrolled tiles hold: Mosaic gives every
# unrolled tile its own VMEM temporaries, and one 1024 x 1024 tile is what
# fits (two refuse to compile: the dK/dV pass at 2048 non-causal).
_MAX_STATIC_AREA = 1024 * 1024


def _tile_kinds(sq, sk, blk_q, blk_k, causal, window):
    """``(nq, nk)`` array: how the causal diagonal and the window's edges cut
    each score tile at unsharded positions. 0: every score masked, the tile
    is never computed (it would have added ``exp(-1e30 - m) = 0`` exactly);
    1: some masked, the tile pays :func:`_apply_pos_masks`; 2: none."""
    q0 = np.arange(sq // blk_q)[:, None] * blk_q
    k0 = np.arange(sk // blk_k)[None, :] * blk_k
    q1, k1 = q0 + blk_q - 1, k0 + blk_k - 1
    skip = np.zeros(np.broadcast_shapes(q0.shape, k0.shape), bool)
    cut = skip.copy()
    if causal:  # masked where k_pos > q_pos
        skip |= k0 > q1
        cut |= k1 > q0
    if window is not None:  # masked where q_pos - k_pos >= window
        skip |= q0 - k1 >= window
        cut |= q1 - k0 >= window
        if not causal:  # and where k_pos - q_pos >= window
            skip |= k0 - q1 >= window
            cut |= k1 - q0 >= window
    return np.where(skip, 0, np.where(cut, 1, 2))


def _unrolls(kinds, blk_q, blk_k) -> bool:
    """Whether the resident kernels unroll the walk over these tiles: at
    most ``_MAX_STATIC_TILES`` in a kernel and ``_MAX_STATIC_AREA`` scores
    in one program (one q block's tiles, or one k block's)."""
    live = kinds > 0
    most = max(live.sum(0).max(), live.sum(1).max())
    return bool(live.sum() <= _MAX_STATIC_TILES
                and most * blk_q * blk_k <= _MAX_STATIC_AREA)


def _static_rows(sq, sk, blk_q, blk_k, causal, window):
    """The static walk of the resident kernels, ``(by_q, by_k)``: per outer
    block (q blocks for the forward and dQ passes, k blocks for dK/dV) the
    inner blocks it visits, each with whether the tile needs its position
    mask. ``(None, None)`` where the walk does not unroll."""
    kinds = _tile_kinds(sq, sk, blk_q, blk_k, causal, window)
    if not _unrolls(kinds, blk_q, blk_k):
        return None, None
    return tuple(tuple(tuple((int(j), bool(row[j] == 1))
                             for j in np.flatnonzero(row)) for row in kk)
                 for kk in (kinds, kinds.T))


class TilePlan(NamedTuple):
    """What :func:`flash_tile_plan` derives: the tile edges, whether the
    resident kernels walk the tiles with bounds known at trace time, and the
    share of the ``sq x sk`` square's tiles that is computed at all."""
    blk_q: int
    blk_k: int
    static: bool
    share: float


def flash_tile_plan(sq: int, sk: int, causal: bool = False,
                    window: Optional[int] = None, *,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    has_segments: bool = False,
                    contiguous_segments: bool = False) -> TilePlan:
    """The tile plan of one attention call: a pure function of static shapes
    and of which masks the call carries, so how often the causal skip
    engages is known when the call is traced.

    Causal or windowed shapes take the smallest edge of ``_DERIVED_EDGES``
    whose computed tiles unroll within ``_MAX_STATIC_TILES``: at 1024 x 1024
    causal, edge 512 computes 3 of 4 tiles where one 1024 tile computed the
    whole square and masked half of it away. Unmasked shapes keep one
    largest tile. An explicit ``block_q`` / ``block_k`` wins. Segment ids
    need 128-aligned k blocks. Contiguous-segment bounds are traced values:
    such a call keeps the dynamic loop and the largest edge, and ``share``
    counts its position masks only. (So do ring attention's calls, whose
    shard ``offsets`` are traced: ``transformer/ring.py`` picks their
    edges.)"""
    mult_k = _NUM_LANES if has_segments else 8
    masked = causal or window is not None
    dynamic = has_segments and contiguous_segments

    def edges(e):
        return (_pick_block(sq, block_q or e),
                _pick_block(sk, block_k or e, mult=mult_k))

    def kinds(blk):
        return _tile_kinds(sq, sk, *blk, causal, window)

    blk = edges(_DERIVED_EDGES[-1])
    if masked and not dynamic and (block_q is None or block_k is None):
        blk = next((edges(e) for e in _DERIVED_EDGES
                    if _unrolls(kinds(edges(e)), *edges(e))), blk)
    return TilePlan(*blk, not dynamic and _unrolls(kinds(blk), *blk),
                    np.count_nonzero(kinds(blk)) / kinds(blk).size)


def _rows_can_lose_every_key(b_ref, qs_ref, off_ref, window) -> bool:
    """Whether a query row can have every key masked, so that ``exp`` of its
    scores needs the guard against ``exp(-inf - -inf)``: only through a
    bias, segment ids, a window, or a ring shard that lies wholly above the
    diagonal. Causal alone always leaves key 0."""
    return (b_ref is not None or qs_ref is not None or off_ref is not None
            or window is not None)


def _walk(rows, oi, row_fn):
    """Run ``row_fn(i, tiles)`` for the outer block ``oi`` this program
    holds. ``rows is None``: the dynamic loop, ``row_fn(oi, None)``. Else one
    branch per outer block, each with its tiles unrolled, so a block's
    bounds are constants and the scheduler sees all of its tiles at once."""
    if rows is None:
        row_fn(oi, None)
    elif len(rows) == 1:
        row_fn(0, rows[0])
    else:
        for i, tiles in enumerate(rows):
            pl.when(oi == i)(functools.partial(row_fn, i, tiles))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _apply_pos_masks(s, causal, window, q_base, k_base, transposed=False):
    """Causal and/or sliding-window masking of a score block, in GLOBAL
    positions (``q_base``/``k_base`` are the block's first row/column
    positions including any ring-attention shard offset, so the window is
    correct across context-parallel sequence shards).

    ``window=w`` keeps, for each query position p, the keys in
    ``[p-w+1, p]`` when causal (the Mistral/Longformer sliding-window
    convention: w attended positions including self) and the symmetric
    band ``[p-w+1, p+w-1]`` when not. No reference counterpart — the
    reference's fmha/fused-softmax kernels have no local-attention mode;
    this is the standard long-context pairing for the streamed kernels
    (O(s·w) score work instead of O(s²)).

    One vector subtraction gives every score's k index minus its q index
    within the block (columns minus rows; rows minus columns for the
    ``transposed`` block ``S^T`` of the dK/dV pass); the block's bases enter
    as one scalar (a constant where the walk is static), so each mask is a
    compare against a scalar and a select."""
    if not causal and window is None:
        return s
    q_dim, k_dim = (1, 0) if transposed else (0, 1)
    rel = (jax.lax.broadcasted_iota(jnp.int32, s.shape, k_dim)
           - jax.lax.broadcasted_iota(jnp.int32, s.shape, q_dim))
    off = q_base - k_base  # k_pos - q_pos == rel - off
    if causal:
        s = jnp.where(rel > off, _NEG_INF, s)
    if window is not None:
        s = jnp.where(rel <= off - window, _NEG_INF, s)
        if not causal:
            s = jnp.where(rel >= off + window, _NEG_INF, s)
    return s


def _dense_pos_masks(s, q_pos, k_pos, causal, window, neg=_NEG_INF):
    """The XLA-path twin of :func:`_apply_pos_masks` (shared by
    ``mha_reference`` and the ring's ``_partial_attn_xla``): same causal +
    window semantics on a dense score tensor with broadcastable position
    arrays instead of in-kernel iotas."""
    if causal:
        s = jnp.where(k_pos > q_pos, neg, s)
    if window is not None:
        s = jnp.where(q_pos - k_pos >= window, neg, s)
        if not causal:
            s = jnp.where(k_pos - q_pos >= window, neg, s)
    return s


def _window_k_range(lo, hi, qi, blk_q, blk_k, q_off, k_off, causal, window):
    """Clip the k-block loop range [lo, hi) for a q block under a sliding
    window: k blocks wholly left of the window's trailing edge (and, when
    not causal, wholly right of its leading edge) are never computed —
    the block-skip that makes window cost O(s·w). Floor division keeps
    the bounds conservative for partially-covered blocks."""
    if window is None:
        return lo, hi
    t = q_off - k_off + qi * blk_q - window + 1  # min valid local k_pos
    lo = jnp.maximum(lo, t // blk_k)
    if not causal:
        u = q_off - k_off + (qi + 1) * blk_q + window - 2  # max valid
        hi = jnp.clip(u // blk_k + 1, 0, hi)
    return lo, hi


def _window_q_range(lo, hi, ki, blk_q, blk_k, q_off, k_off, causal, window):
    """The dK/dV-pass mirror of :func:`_window_k_range`: clip the q-block
    loop range [lo, hi) for a k block."""
    if window is None:
        return lo, hi
    u = k_off - q_off + (ki + 1) * blk_k + window - 2  # max valid local q_pos
    hi = jnp.clip(u // blk_q + 1, 0, hi)
    if not causal:
        t = k_off - q_off + ki * blk_k - window + 1
        lo = jnp.maximum(lo, t // blk_q)
    return lo, hi


def _window_grid(blk_outer, blk_inner, n_inner, causal, window,
                 inner_is_k=True):
    """Window-restricted inner grid dimension for the STREAMED kernels.

    The TPU grid is sequential — trips cannot be skipped, so with the
    plain (nq, nk) grid a window saves MXU/VPU work but still pays the
    DMA and trip overhead of every block pair: O(s²) traffic for O(s·w)
    math (measured: 256k-token windowed training was trip-bound). This
    helper instead shrinks the inner grid extent to the band's worst-case
    block width and returns ``(width, base)`` where ``base(outer_idx)``
    maps a trip to its first global inner block — used both by the
    BlockSpec index maps (clamped, so DMA stays in bounds) and inside the
    kernels (unclamped, so the existing [lo, hi) predicate skips the
    clamped-over trips). Only usable when positions are statically known
    (no ring ``offsets``: index maps see program ids only, not operands).

    ``inner_is_k``: inner dim iterates k blocks for a q block (fwd/dQ);
    False for the dK/dV pass (q blocks for a k block), where the causal
    band extends FORWARD from the diagonal instead of backward."""
    if window is None:
        return None
    # the band spans the outer block plus (window-1) on the trailing side,
    # plus another (window-1) leading when bidirectional; under causal the
    # trailing side is behind the diagonal for k-inner (fwd/dQ) but AHEAD
    # of it for q-inner (dK/dV), which only moves the band's start:
    #   k-inner: k_pos ∈ [q_pos - window + 1, q_pos | q_pos + window - 1]
    #   q-inner: q_pos ∈ [k_pos | k_pos - window + 1, k_pos + window - 1]
    span = (blk_outer - 1) + (window - 1) + (0 if causal else (window - 1))
    back = 0 if (causal and not inner_is_k) else window - 1

    def base(oi):
        return (oi * blk_outer - back) // blk_inner

    width = span // blk_inner + 2  # +1 block-misalignment, +1 conservative
    if width >= n_inner:
        return None  # the band covers (nearly) everything: keep the full grid
    return width, base


def _lse_group(nq):
    """Row-group size for the dense (b, h, nq, blk_q) lse/delta tables.

    Groups of 8 rows keep the in-VMEM window at 8·blk_q·4 bytes no matter
    the sequence length (the whole-table window is sq·4 bytes, which blew
    the 16 MB scoped-VMEM limit at 1M tokens); 8 divides every large
    power-of-two nq, and the whole-table fallback only triggers for small
    odd nq where the table is tiny anyway. The second-minor block dim must
    be a multiple of 8 or the full dim — both branches satisfy that."""
    return 8 if nq % 8 == 0 and nq >= 8 else nq


def _window_grid_maps(blk_outer, blk_inner, n_inner, causal, window, offsets,
                      inner_is_k=True):
    """Shared unpack of :func:`_window_grid` for the three streamed
    pallas_calls: returns ``(extent, base, index_map)`` where ``extent``
    is the inner grid dimension, ``base`` feeds the kernel's trip→block
    remap (None = unrestricted), and ``index_map(outer, inner)`` is the
    CLAMPED block index for the BlockSpecs (edge trips fetch a clamped
    block; the kernels' [lo, hi) predicate never reads it)."""
    wg = _window_grid(blk_outer, blk_inner, n_inner, causal, window,
                      inner_is_k) if offsets is None else None
    if wg is None:
        return n_inner, None, (lambda oi, ij: ij)
    extent, base = wg
    return extent, base, (
        lambda oi, ij: jnp.clip(base(oi) + ij, 0, n_inner - 1))


def _seg_mask(s, q_ids, ks_ref, j, blk_k, pad_id):
    """Mask ``s`` (blk_q, blk_k) to -inf where the q/k segment ids differ
    (or the key is padding). ``q_ids`` is the lane-replicated (blk_q, 128)
    tile; kv ids arrive sublane-replicated (slices of (SUBLANES, sk))."""
    q_col = jnp.tile(q_ids, (1, s.shape[-1] // _NUM_LANES))
    k_ids = ks_ref[0, 0:1, pl.ds(j * blk_k, blk_k)]
    valid = q_col == k_ids
    if pad_id is not None:
        valid = valid & (k_ids != pad_id)
    return jnp.where(valid, s, _NEG_INF)


def _seg_mask_if_needed(s, qs_ref, ks_ref, kmm_ref, j_meta, j_slice, blk_k,
                        pad_id, qmin, qmax):
    """Apply the segment mask only on blocks that need it — the splash-
    attention full/partial block distinction: an interior block whose q and
    k segment ranges are the same single (non-pad) segment is fully valid,
    so the mask is skipped via a real branch. ``kmm_ref`` holds per-k-block
    (min, max) ids in SMEM. The STREAMED kernels' only: in the resident
    kernels the branch cost more than the mask it saves, taken or not (read
    on the chip at one 512 x 512 tile, PERF.md Findings, PR 27), so they
    mask every tile; nobody has read it in the streamed ones.

    ``j_meta`` indexes the per-block metadata (always the global k-block
    number); ``j_slice`` indexes into ``ks_ref``, which holds only the
    current block in the streamed layout (j_slice == 0)."""
    kmin = kmm_ref[0, 0, j_meta]
    kmax = kmm_ref[0, 1, j_meta]
    uniform_ok = (qmin == qmax) & (kmin == kmax) & (kmin == qmin)
    if pad_id is not None:
        uniform_ok = uniform_ok & (qmin != pad_id)
    return jax.lax.cond(
        uniform_ok,
        lambda s: s,
        lambda s: _seg_mask(s, qs_ref[0], ks_ref, j_slice, blk_k, pad_id),
        s,
    )


def _fwd_kernel(q_ref, k_ref, v_ref, b_ref, qs_ref, ks_ref, bnd_ref,
                off_ref, o_ref, lse_ref, *, scale, causal, blk_q, blk_k,
                pad_id, window=None, lse_group=1, rows=None):
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (blk_q, d)
    sk = k_ref.shape[2]
    d = q.shape[-1]
    # Global-position offsets of this q/k shard (ring attention over the
    # ``context`` axis passes the shard's start positions so causal masking
    # is correct across sequence shards; 0 for unsharded attention).
    q_off = off_ref[0] if off_ref is not None else 0
    k_off = off_ref[1] if off_ref is not None else 0
    guard = _rows_can_lose_every_key(b_ref, qs_ref, off_ref, window)

    def tile(j, carry, q_base, k_base, masked):
        k = k_ref[0, 0, pl.ds(j * blk_k, blk_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(j * blk_k, blk_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (blk_q, blk_k)
        if b_ref is not None:
            s = s + b_ref[0, 0, :, pl.ds(j * blk_k, blk_k)].astype(jnp.float32)
        if qs_ref is not None:
            s = _seg_mask(s, qs_ref[0], ks_ref, j, blk_k, pad_id)
        if masked:
            s = _apply_pos_masks(s, causal, window, q_base, k_base)
        m_tile = jnp.max(s, axis=-1, keepdims=True)
        m_new = m_tile if carry is None else jnp.maximum(carry[1], m_tile)
        p = jnp.exp(s - m_new)
        if guard:
            # fully-masked rows keep m == -inf: exp(s - m) would be exp(0);
            # zero their probabilities so l stays 0 and the output stays 0
            p = jnp.where(m_new <= _NEG_INF / 2, 0.0, p)
        l_new = jnp.sum(p, axis=-1, keepdims=True)
        acc_new = jax.lax.dot(p, v, preferred_element_type=jnp.float32)
        if carry is not None:  # the first tile of a static row has no past
            acc, m, l = carry
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + l_new
            acc_new = acc * alpha + acc_new
        return acc_new, m_new, l_new

    def row(qi, tiles):
        init = (jnp.zeros((blk_q, v_ref.shape[-1]), jnp.float32),
                jnp.full((blk_q, 1), _NEG_INF, jnp.float32),
                jnp.zeros((blk_q, 1), jnp.float32))
        if tiles is None:
            lo, nk = 0, sk // blk_k
            if bnd_ref is not None:
                # contiguous-segment block bounds (precomputed host-side): k
                # blocks outside [lo, hi) cannot share a segment with this q
                # block — the packed-varlen FLOP saving (sum len_i^2, not
                # total^2)
                lo = bnd_ref[0, 0, qi]
                nk = jnp.minimum(nk, bnd_ref[0, 1, qi])
            if causal:
                # skip k-blocks strictly above the diagonal (fully masked):
                # the triangular-work saving the reference's upper-triang
                # kernel gets from its tiling
                # (scaled_upper_triang_masked_softmax.h).
                lim = (q_off - k_off + (qi + 1) * blk_q + blk_k - 1) // blk_k
                nk = jnp.clip(lim, 0, nk)
            lo, nk = _window_k_range(lo, nk, qi, blk_q, blk_k, q_off, k_off,
                                     causal, window)
            carry = jax.lax.fori_loop(
                lo, nk,
                lambda j, c: tile(j, c, q_off + qi * blk_q,
                                  k_off + j * blk_k, True), init)
        else:
            carry = None
            for j, masked in tiles:
                carry = tile(j, carry, qi * blk_q, j * blk_k, masked)
        acc, m, l = init if carry is None else carry
        # Fully-masked rows (padding segments, all -inf bias rows) have l == 0.
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
        # lse rides in the dense (b, h, nq, blk_q) table layout (grouped rows;
        # see _flash_fwd_stream's note — the (b, h, sq, 1) shape lane-pads
        # 128x at the custom-call boundary)
        lse_ref[0, 0, pl.ds(qi % lse_group, 1), :] = jnp.transpose(
            m + jnp.log(l_safe), (1, 0))

    _walk(rows, pl.program_id(2), row)


# ---------------------------------------------------------------------------
# Backward: dQ pass (grid over q-blocks), then dK/dV pass (grid over k-blocks)
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, b_ref, qs_ref, ks_ref, bnd_ref, off_ref,
    do_ref, lse_ref, delta_ref, dq_ref, db_ref,
    *, scale, causal, blk_q, blk_k, pad_id, b_bcast, h_bcast, dims,
    window=None, lse_group=1, rows=None,
):
    q = q_ref[0, 0].astype(jnp.float32) * scale
    do = do_ref[0, 0].astype(jnp.float32)
    sk = k_ref.shape[2]
    q_off = off_ref[0] if off_ref is not None else 0
    k_off = off_ref[1] if off_ref is not None else 0
    guard = _rows_can_lose_every_key(b_ref, qs_ref, off_ref, window)

    if db_ref is not None:
        # A bias broadcast over batch/heads maps several grid steps onto the
        # same dbias block. Pallas only keeps an output window live across
        # consecutive same-index steps, so the broadcast dims iterate
        # innermost (see _dq_grid_order); zero on the first visit, then
        # accumulate.
        conds = []
        if b_bcast:
            conds.append(pl.program_id(dims["b"]) == 0)
        if h_bcast:
            conds.append(pl.program_id(dims["h"]) == 0)
        if conds:
            pred = conds[0]
            for c in conds[1:]:
                pred = pred & c

            @pl.when(pred)
            def _zero():
                db_ref[0, 0] = jnp.zeros_like(db_ref[0, 0])

        else:
            db_ref[0, 0] = jnp.zeros_like(db_ref[0, 0])

    def row(qi, tiles):
        # dense (b, h, nq, blk_q) table layout (see _flash_fwd_stream)
        lse = jnp.transpose(lse_ref[0, 0, pl.ds(qi % lse_group, 1), :],
                            (1, 0))
        delta = jnp.transpose(delta_ref[0, 0, pl.ds(qi % lse_group, 1), :],
                              (1, 0))

        def tile(j, dq, q_base, k_base, masked):
            k = k_ref[0, 0, pl.ds(j * blk_k, blk_k), :].astype(jnp.float32)
            v = v_ref[0, 0, pl.ds(j * blk_k, blk_k), :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if b_ref is not None:
                s = s + b_ref[0, 0, :, pl.ds(j * blk_k, blk_k)].astype(
                    jnp.float32)
            if qs_ref is not None:
                s = _seg_mask(s, qs_ref[0], ks_ref, j, blk_k, pad_id)
            if masked:
                s = _apply_pos_masks(s, causal, window, q_base, k_base)
            p = jnp.exp(s - lse)
            if guard:
                # fully-masked rows carry lse == -inf; exp(s - lse) would be
                # exp(0)
                p = jnp.where(lse <= _NEG_INF / 2, 0.0, p)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta)
            if db_ref is not None:
                cur = db_ref[0, 0, :, pl.ds(j * blk_k, blk_k)]
                db_ref[0, 0, :, pl.ds(j * blk_k, blk_k)] = cur + ds
            part = jax.lax.dot(ds, k, preferred_element_type=jnp.float32)
            return part if dq is None else dq + part

        if tiles is None:
            lo, nk = 0, sk // blk_k
            if bnd_ref is not None:
                lo = bnd_ref[0, 0, qi]
                nk = jnp.minimum(nk, bnd_ref[0, 1, qi])
            if causal:
                lim = (q_off - k_off + (qi + 1) * blk_q + blk_k - 1) // blk_k
                nk = jnp.clip(lim, 0, nk)
            lo, nk = _window_k_range(lo, nk, qi, blk_q, blk_k, q_off, k_off,
                                     causal, window)
            dq = jax.lax.fori_loop(
                lo, nk,
                lambda j, c: tile(j, c, q_off + qi * blk_q,
                                  k_off + j * blk_k, True),
                jnp.zeros(q.shape, jnp.float32))
        else:
            dq = None
            for j, masked in tiles:
                dq = tile(j, dq, qi * blk_q, j * blk_k, masked)
            dq = jnp.zeros(q.shape, jnp.float32) if dq is None else dq
        # the scores' scale, once for the block and not once a tile
        dq_ref[0, 0] = (scale * dq).astype(dq_ref.dtype)

    # dims maps logical (b, h, q) grid coordinates to program_id positions —
    # _flash_bwd orders the grid so dbias revisits are *consecutive*.
    _walk(rows, pl.program_id(dims["q"]), row)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, b_ref, ks_ref, qs_ref, bnd_ref,
    off_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, scale, causal, blk_q, blk_k, pad_id, window=None, rows=None,
    group=1, acc_refs=(),
):
    """dK and dV of one k block, in the TRANSPOSED domain: the scores are
    computed as ``S^T = K Q^T`` (k along rows), so ``dV = P^T dO`` and
    ``dK = dS^T Q`` are plain products with no transposed operand, and lse
    and delta are read as the rows the dense tables store. (As ``P`` and
    ``dS`` with q along rows, both products contract their left operand's
    rows: two ``(blk_q, blk_k)`` transposes a tile.) Segment ids arrive the
    other way round for it: k ids lane-replicated ``(blk_k, 128)``, q ids
    sublane-replicated ``(8, sq)``. With grouped-query heads (``group`` >
    1) the grid's last axis walks the query heads that read this key-value
    head and ``acc_refs`` sum their parts."""
    k = k_ref[0, 0].astype(jnp.float32)  # (blk_k, d)
    v = v_ref[0, 0].astype(jnp.float32)
    sq = q_ref.shape[2]
    q_off = off_ref[0] if off_ref is not None else 0
    k_off = off_ref[1] if off_ref is not None else 0
    guard = _rows_can_lose_every_key(b_ref, qs_ref, off_ref, window)

    def row(ki, tiles):
        def seg_mask_dkv(st, i):
            # whole lane tiles where the q block has them (a plain vector
            # compare), else one column broadcast over the lanes
            k_ids = (jnp.tile(ks_ref[0], (1, blk_q // _NUM_LANES))
                     if blk_q % _NUM_LANES == 0 else ks_ref[0][:, :1])
            q_ids = qs_ref[0, 0:1, pl.ds(i * blk_q, blk_q)]
            valid = k_ids == q_ids
            if pad_id is not None:
                valid = valid & (k_ids != pad_id)
            return jnp.where(valid, st, _NEG_INF)

        def tile(i, carry, q_base, k_base, masked):
            # on q the scale rides into both of its products: the scores,
            # and dK = dS^T (scale q)
            q = q_ref[0, 0, pl.ds(i * blk_q, blk_q), :].astype(
                jnp.float32) * scale
            do = do_ref[0, 0, pl.ds(i * blk_q, blk_q), :].astype(jnp.float32)
            # dense (b, h, nq, blk_q) tables, full-resident here (sq·4 bytes
            # — 64x less VMEM than the lane-padded (sq, 1) windows they
            # replace); a q block's lse is a row, as S^T wants it
            lse = lse_ref[0, 0, pl.ds(i, 1), :]  # (1, blk_q)
            delta = delta_ref[0, 0, pl.ds(i, 1), :]
            st = jax.lax.dot_general(
                k, q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (blk_k, blk_q)
            if b_ref is not None:
                st = st + jnp.transpose(
                    b_ref[0, 0, pl.ds(i * blk_q, blk_q), :].astype(
                        jnp.float32), (1, 0))
            if qs_ref is not None:
                st = seg_mask_dkv(st, i)
            if masked:
                st = _apply_pos_masks(st, causal, window, q_base, k_base,
                                      transposed=True)
            pt = jnp.exp(st - lse)  # (blk_k, blk_q)
            if guard:
                # fully-masked rows carry lse == -inf; exp(s - lse) would be
                # exp(0)
                pt = jnp.where(lse <= _NEG_INF / 2, 0.0, pt)
            dv_part = jax.lax.dot(pt, do, preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(
                v, do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dst = pt * (dpt - delta)
            dk_part = jax.lax.dot(dst, q, preferred_element_type=jnp.float32)
            if carry is None:  # the first tile of a static walk
                return dk_part, dv_part
            return carry[0] + dk_part, carry[1] + dv_part

        init = (jnp.zeros(k.shape, jnp.float32),
                jnp.zeros(v.shape, jnp.float32))
        if tiles is None:
            nq = sq // blk_q
            # Under causal masking, q-blocks entirely left of this k-block's
            # diagonal contribute nothing — start at the first intersecting
            # block.
            start = (jnp.clip((k_off - q_off + ki * blk_k) // blk_q, 0, nq)
                     if causal else 0)
            if bnd_ref is not None:
                # contiguous-segment bounds over q blocks for this k block
                start = jnp.maximum(start, bnd_ref[0, 0, ki])
                nq = jnp.minimum(nq, bnd_ref[0, 1, ki])
            start, nq = _window_q_range(start, nq, ki, blk_q, blk_k, q_off,
                                        k_off, causal, window)
            carry = jax.lax.fori_loop(
                start, nq,
                lambda i, c: tile(i, c, q_off + i * blk_q,
                                  k_off + ki * blk_k, True), init)
        else:
            carry = None
            for i, masked in tiles:
                carry = tile(i, carry, i * blk_q, ki * blk_k, masked)
        dk, dv = init if carry is None else carry
        if group == 1:
            dk_ref[0, 0] = dk.astype(dk_ref.dtype)
            dv_ref[0, 0] = dv.astype(dv_ref.dtype)
            return
        for acc, part, out in zip(acc_refs, (dk, dv), (dk_ref, dv_ref)):
            acc[...] = jnp.where(gi == 0, part, acc[...] + part)

            @pl.when(gi == group - 1)
            def _write(acc=acc, out=out):
                out[0, 0] = acc[...].astype(out.dtype)

    gi = pl.program_id(3) if group > 1 else None
    _walk(rows, pl.program_id(2), row)


# ---------------------------------------------------------------------------
# Streamed kernels: the k-loop (q-loop for dK/dV) lives in the GRID, so K/V
# (resp. Q/dO) arrive in blk-sized tiles and VMEM residency is bounded by
# BLOCK sizes, not sequence length — the fix for the 16 MB wall the resident
# layout hits at s≈8k with segment operands (VERDICT r3 weak #3 / ADVICE
# medium). Online-softmax state (acc, m, l) persists across the inner grid
# dimension in VMEM scratch; outputs are written on the last inner step.
# Blocks outside the segment bounds / causal limit skip their compute via
# pl.when (the DMA still runs — on TPU the sequential grid cannot skip
# trips, so the packed saving here is MXU/VPU work, not bandwidth).
# Streamed mode supports causal + segment ids + ring offsets; dense bias
# stays on the resident path (a (sq, sk) bias at streaming sizes is the
# wrong tool — packed segment ids are the long-sequence masking story).
# ---------------------------------------------------------------------------


def _fwd_kernel_stream(q_ref, k_ref, v_ref, qs_ref, ks_ref, kmm_ref, qmm_ref,
                       bnd_ref, off_ref, o_ref, lse_ref, acc_ref, m_ref,
                       l_ref, *, scale, causal, blk_q, blk_k, pad_id, nk,
                       window=None, k_base=None, lse_group=1):
    qi = pl.program_id(2)
    kj_raw = pl.program_id(3)
    # window-restricted grid (_window_grid): trip kj_raw covers global k
    # block k_base(qi) + kj_raw; kb may fall outside [0, nk) on the band's
    # edge trips — the [lo, hi) predicate below skips those (their DMA
    # fetched a clamped block, never read)
    kj = k_base(qi) + kj_raw if k_base is not None else kj_raw
    q_off = off_ref[0] if off_ref is not None else 0
    k_off = off_ref[1] if off_ref is not None else 0

    @pl.when(kj_raw == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    lo = jnp.int32(0)
    hi = jnp.int32(nk)
    if bnd_ref is not None:
        lo = bnd_ref[0, 0, qi]
        hi = jnp.minimum(hi, bnd_ref[0, 1, qi])
    if causal:
        lim = (q_off - k_off + (qi + 1) * blk_q + blk_k - 1) // blk_k
        hi = jnp.clip(lim, 0, hi)
    lo, hi = _window_k_range(lo, hi, qi, blk_q, blk_k, q_off, k_off,
                             causal, window)

    @pl.when((kj >= lo) & (kj < hi))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (blk_q, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (blk_k, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if qs_ref is not None:
            # per-block (min, max) ids from SMEM metadata, not a per-trip
            # VPU reduction over the (blk_q, 128) id tile
            qmin = qmm_ref[0, 0, qi]
            qmax = qmm_ref[0, 1, qi]
            s = _seg_mask_if_needed(s, qs_ref, ks_ref, kmm_ref, kj, 0, blk_k,
                                    pad_id, qmin, qmax)
        s = _apply_pos_masks(s, causal, window, q_off + qi * blk_q,
                             k_off + kj * blk_k)
        m = m_ref[...]
        l = l_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(m_new <= _NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        alpha = jnp.exp(m - m_new)
        l_ref[...] = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(kj_raw == pl.num_programs(3) - 1)
    def _finalize():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        # lse rides in the DENSE (b, h, nq, blk_q) layout (see
        # _flash_bwd_stream): transpose this block's (blk_q, 1) column
        # into row qi of the per-head table (windowed in lse_group rows)
        lse_ref[0, 0, pl.ds(qi % lse_group, 1), :] = jnp.transpose(
            m_ref[...] + jnp.log(l_safe), (1, 0))


def _bwd_dq_kernel_stream(q_ref, k_ref, v_ref, qs_ref, ks_ref, kmm_ref,
                          qmm_ref, bnd_ref, off_ref, do_ref, lse_ref,
                          delta_ref, dq_ref, dq_acc_ref,
                          *, scale, causal, blk_q, blk_k, pad_id, nk,
                          window=None, k_base=None, lse_group=1):
    qi = pl.program_id(2)
    kj_raw = pl.program_id(3)
    kj = k_base(qi) + kj_raw if k_base is not None else kj_raw
    q_off = off_ref[0] if off_ref is not None else 0
    k_off = off_ref[1] if off_ref is not None else 0

    @pl.when(kj_raw == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    lo = jnp.int32(0)
    hi = jnp.int32(nk)
    if bnd_ref is not None:
        lo = bnd_ref[0, 0, qi]
        hi = jnp.minimum(hi, bnd_ref[0, 1, qi])
    if causal:
        lim = (q_off - k_off + (qi + 1) * blk_q + blk_k - 1) // blk_k
        hi = jnp.clip(lim, 0, hi)
    lo, hi = _window_k_range(lo, hi, qi, blk_q, blk_k, q_off, k_off,
                             causal, window)

    @pl.when((kj >= lo) & (kj < hi))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        # dense-table layout: row qi of (nq, blk_q), reoriented to a
        # (blk_q, 1) column (see _flash_fwd_stream's lse note)
        lse = jnp.transpose(lse_ref[0, 0, pl.ds(qi % lse_group, 1), :],
                            (1, 0))
        delta = jnp.transpose(delta_ref[0, 0, pl.ds(qi % lse_group, 1), :],
                              (1, 0))
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if qs_ref is not None:
            qmin = qmm_ref[0, 0, qi]
            qmax = qmm_ref[0, 1, qi]
            s = _seg_mask_if_needed(s, qs_ref, ks_ref, kmm_ref, kj, 0, blk_k,
                                    pad_id, qmin, qmax)
        s = _apply_pos_masks(s, causal, window, q_off + qi * blk_q,
                             k_off + kj * blk_k)
        p = jnp.where(lse <= _NEG_INF / 2, 0.0, jnp.exp(s - lse))
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_acc_ref[...] = dq_acc_ref[...] + scale * jax.lax.dot(
            ds, k, preferred_element_type=jnp.float32)

    @pl.when(kj_raw == pl.num_programs(3) - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel_stream(q_ref, k_ref, v_ref, qs_ref, ks_ref, qmm_ref,
                           kmm_ref, bnd_ref, off_ref, do_ref, lse_ref,
                           delta_ref, dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                           *, scale, causal, blk_q, blk_k, pad_id, nq,
                           window=None, q_base=None, lse_group=1, group=1):
    ki = pl.program_id(2)
    # grouped-query heads put the group's query heads on axis 3, ahead of
    # the q blocks: the accumulators then run over both
    inner = 3 if group == 1 else 4
    qi_raw = pl.program_id(inner)
    qi = q_base(ki) + qi_raw if q_base is not None else qi_raw
    q_off = off_ref[0] if off_ref is not None else 0
    k_off = off_ref[1] if off_ref is not None else 0
    first = qi_raw == 0
    if group > 1:
        first &= pl.program_id(3) == 0

    @pl.when(first)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    lo = jnp.int32(0)
    hi = jnp.int32(nq)
    if causal:
        lo = jnp.clip((k_off - q_off + ki * blk_k) // blk_q, 0, nq)
    if bnd_ref is not None:
        lo = jnp.maximum(lo, bnd_ref[0, 0, ki])
        hi = jnp.minimum(hi, bnd_ref[0, 1, ki])
    lo, hi = _window_q_range(lo, hi, ki, blk_q, blk_k, q_off, k_off,
                             causal, window)

    @pl.when((qi >= lo) & (qi < hi))
    def _compute():
        k = k_ref[0, 0].astype(jnp.float32)  # (blk_k, d)
        v = v_ref[0, 0].astype(jnp.float32)
        q = q_ref[0, 0].astype(jnp.float32)  # (blk_q, d)
        do = do_ref[0, 0].astype(jnp.float32)
        # dense-table layout; qi is the (possibly remapped) global q
        # block — in range whenever this trip computes (the predicate),
        # so the fetched group is the one containing it
        lse = jnp.transpose(lse_ref[0, 0, pl.ds(qi % lse_group, 1), :],
                            (1, 0))
        delta = jnp.transpose(delta_ref[0, 0, pl.ds(qi % lse_group, 1), :],
                              (1, 0))
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (blk_q, blk_k)
        if qs_ref is not None:
            # same classifier+mask as the fwd/dQ kernels: kmm indexed by
            # this kernel's global k block (ki), ks sliced at 0 (streamed
            # block layout), q range from the SMEM metadata
            qmin = qmm_ref[0, 0, qi]
            qmax = qmm_ref[0, 1, qi]
            s = _seg_mask_if_needed(s, qs_ref, ks_ref, kmm_ref, ki, 0,
                                    blk_k, pad_id, qmin, qmax)
        s = _apply_pos_masks(s, causal, window, q_off + qi * blk_q,
                             k_off + ki * blk_k)
        p = jnp.where(lse <= _NEG_INF / 2, 0.0, jnp.exp(s - lse))
        dv_acc_ref[...] = dv_acc_ref[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc_ref[...] = dk_acc_ref[...] + scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    last = qi_raw == pl.num_programs(inner) - 1
    if group > 1:
        last &= pl.program_id(3) == group - 1

    @pl.when(last)
    def _finalize():
        dk_ref[0, 0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------


def _bias_spec(bias, blk_q, sk):
    """BlockSpec for an additive bias of shape (b|1, h|1, sq, sk), for grids
    ordered (b, h, q). Size-1 batch/head dims pin the index map to 0; size-1
    sq/sk dims are canonicalized away by ``flash_attention`` (broadcast_to)
    before the custom_vjp boundary, so they never reach here.
    """
    bb, bh = bias.shape[0], bias.shape[1]

    def idx(bi, hi, qi):
        return (bi if bb > 1 else 0, hi if bh > 1 else 0, qi, 0)

    return pl.BlockSpec((1, 1, blk_q, sk), idx, memory_space=pltpu.VMEM)


def _dq_grid_order(bias, b_bcast, h_bcast):
    """Logical-(b, h, q) → grid-position order for the dQ pass.

    dbias blocks are revisited across the broadcast dims, and Pallas output
    windows persist only across *consecutive* same-index steps — so whichever
    dims collapse in the dbias index map must iterate innermost."""
    if bias is None:
        return ("b", "h", "q")
    if b_bcast and not h_bcast:
        return ("q", "h", "b")
    return ("q", "b", "h")  # h broadcast, or both, or neither


def _offsets_spec():
    """SMEM spec for the (q_off, k_off) global-position scalars."""
    return pl.BlockSpec((2,), lambda *_: (0,), memory_space=pltpu.SMEM)


def _of_width(spec, width: int):
    """``spec`` with its last axis ``width`` wide: the block of values (or
    of the output, or of its gradient) beside a block of keys or queries
    whose head is of another size."""
    return pl.BlockSpec((*spec.block_shape[:-1], width), spec.index_map,
                        memory_space=spec.memory_space)


def _kv_head(group: int):
    """The key-value head that query head ``hi`` reads, for the index maps:
    ``group`` query heads in a row share one (grouped-query attention), so K
    and V keep their own ``heads // group`` heads and are never repeated in
    memory. Equal heads keep the identity, and their index maps trace as
    they always did."""
    return ((lambda hi: hi) if group == 1
            else (lambda hi: jax.lax.div(hi, jnp.int32(group))))


def _group_axis(group: int):
    """``(of_q, of_kv)`` for the dK/dV passes of grouped-query attention,
    whose grids walk the key-value heads and, one axis further in, the
    ``group`` query heads that read each: an index map written over
    ``(bi, hi, ki[, qi])`` is wrapped to take the grid's ``(bi, hk, ki, gi[,
    qi])``, ``hi`` being the query head for the operands that have one
    (``of_q``) and the key-value head for the rest (``of_kv``). Equal heads:
    the maps as they are."""
    if group == 1:
        return (lambda f: f), (lambda f: f)
    return ((lambda f: lambda bi, hk, ki, gi, *qi: f(bi, hk * group + gi, ki,
                                                     *qi)),
            (lambda f: lambda bi, hk, ki, gi, *qi: f(bi, hk, ki, *qi)))


def _seg_layouts(q_seg, kv_seg):
    """Lane/sublane-replicated segment-id layouts for the kernels:
    q ids ``(b, sq, NUM_LANES)``, kv ids ``(b, NUM_SUBLANES, sk)``."""
    b, sq = q_seg.shape
    sk = kv_seg.shape[1]
    qs = jax.lax.broadcast_in_dim(
        q_seg.astype(jnp.int32), (b, sq, _NUM_LANES), (0, 1))
    ks = jax.lax.broadcast_in_dim(
        kv_seg.astype(jnp.int32), (b, _NUM_SUBLANES, sk), (0, 2))
    return qs, ks


def _seg_metadata(q_seg, kv_seg, blk_q, blk_k, pad_id=None):
    """Per-block metadata for CONTIGUOUS (non-decreasing) segment ids.

    Returns ``(bounds_q, bounds_k, qmm, kmm)``: ``bounds_q[b, 0/1, i]`` is
    the [start, end) k-block range intersecting q block ``i``'s segment span
    (symmetrically ``bounds_k`` over q blocks), and ``qmm``/``kmm`` are the
    per-block (min, max) segment ids — the full/partial block classifier.
    With ``pad_id`` set, all-padding blocks get EMPTY ranges and ranges
    never extend into the all-padding suffix, so trailing padding costs no
    score blocks at all. Computed with plain XLA reductions OUTSIDE the
    kernel and read from SMEM inside — the Pallas-native replacement for
    the reference kernel's cu_seqlens binary search per CTA (fmha kernel
    launch geometry)."""
    b, sq = q_seg.shape
    sk = kv_seg.shape[1]
    nq, nk = sq // blk_q, sk // blk_k
    qb = q_seg.reshape(b, nq, blk_q)
    kb = kv_seg.reshape(b, nk, blk_k)
    qmin, qmax = qb.min(-1), qb.max(-1)  # (b, nq)
    kmin, kmax = kb.min(-1), kb.max(-1)  # (b, nk)
    # monotone ids: blocks wholly before/after the span count as offsets
    start_q = jnp.sum(kmax[:, None, :] < qmin[:, :, None], axis=-1)
    end_q = nk - jnp.sum(kmin[:, None, :] > qmax[:, :, None], axis=-1)
    start_k = jnp.sum(qmax[:, None, :] < kmin[:, :, None], axis=-1)
    end_k = nq - jnp.sum(qmin[:, None, :] > kmax[:, :, None], axis=-1)
    if pad_id is not None:
        # monotone ids put all-padding blocks (min == pad) in a suffix:
        # give them empty ranges and stop every range at the suffix
        real_k = nk - jnp.sum(kmin == pad_id, axis=-1, keepdims=True)
        end_q = jnp.minimum(end_q, real_k)
        pad_q = qmin == pad_id
        start_q = jnp.where(pad_q, 0, start_q)
        end_q = jnp.where(pad_q, 0, end_q)
        real_q = nq - jnp.sum(qmin == pad_id, axis=-1, keepdims=True)
        end_k = jnp.minimum(end_k, real_q)
        pad_k = kmin == pad_id
        start_k = jnp.where(pad_k, 0, start_k)
        end_k = jnp.where(pad_k, 0, end_k)
    bounds_q = jnp.stack([start_q, end_q], axis=1).astype(jnp.int32)
    bounds_k = jnp.stack([start_k, end_k], axis=1).astype(jnp.int32)
    qmm = jnp.stack([qmin, qmax], axis=1).astype(jnp.int32)  # (b, 2, nq)
    kmm = jnp.stack([kmin, kmax], axis=1).astype(jnp.int32)  # (b, 2, nk)
    return bounds_q, bounds_k, qmm, kmm


def _seg_specs(blk_q, sk, reorder=None):
    """(q-ids, kv-ids) BlockSpecs for grids ordered (b, h, q)."""
    r = reorder if reorder is not None else (lambda f: f)
    return [
        pl.BlockSpec((1, blk_q, _NUM_LANES),
                     r(lambda bi, hi, qi: (bi, qi, 0)),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, _NUM_SUBLANES, sk),
                     r(lambda bi, hi, qi: (bi, 0, 0)),
                     memory_space=pltpu.VMEM),
    ]


def _smem_pair_spec(n, reorder=None):
    """SMEM spec for a (b, 2, n) per-block metadata array (bounds, min/max)."""
    r = reorder if reorder is not None else (lambda f: f)
    return pl.BlockSpec((1, 2, n), r(lambda bi, hi, qi: (bi, 0, 0)),
                        memory_space=pltpu.SMEM)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "blk_q", "blk_k", "pad_id",
                     "contiguous", "stream", "window"),
)
def _flash_fwd(q, k, v, bias, offsets, q_seg=None, kv_seg=None, *,
               scale, causal, blk_q, blk_k, pad_id=None, contiguous=True,
               stream=False, window=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if stream:
        assert bias is None, "streamed path does not support dense bias"
        return _flash_fwd_stream(q, k, v, offsets, q_seg, kv_seg,
                                 scale=scale, causal=causal, blk_q=blk_q,
                                 blk_k=blk_k, pad_id=pad_id,
                                 contiguous=contiguous, window=window)
    nq = sq // blk_q
    grid = (b, h, nq)
    lse_g = _lse_group(nq)
    qspec = pl.BlockSpec((1, 1, blk_q, d), lambda bi, hi, qi: (bi, hi, qi, 0),
                         memory_space=pltpu.VMEM)
    kvh = _kv_head(h // k.shape[1])
    kspec = pl.BlockSpec((1, 1, sk, d),
                         lambda bi, hi, qi: (bi, kvh(hi), 0, 0),
                         memory_space=pltpu.VMEM)
    ospec = qspec
    vspec, dv = kspec, v.shape[-1]
    if dv != d:   # values of a width of their own, and so the output
        vspec, ospec = (_of_width(x, dv) for x in (kspec, qspec))
    lspec = pl.BlockSpec((1, 1, lse_g, blk_q),
                         lambda bi, hi, qi: (bi, hi, qi // lse_g, 0),
                         memory_space=pltpu.VMEM)
    in_specs = [qspec, kspec, vspec]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(_bias_spec(bias, blk_q, sk))
        args.append(bias)
    if q_seg is not None:
        qs, ks = _seg_layouts(q_seg, kv_seg)
        in_specs += _seg_specs(blk_q, sk)
        args += [qs, ks]
        if contiguous:
            in_specs.append(_smem_pair_spec(sq // blk_q))
            args.append(_seg_metadata(q_seg, kv_seg, blk_q, blk_k,
                                      pad_id)[0])
    if offsets is not None:
        in_specs.append(_offsets_spec())
        args.append(offsets)
    has_bias, has_off = bias is not None, offsets is not None
    has_seg, has_bnd = q_seg is not None, q_seg is not None and contiguous
    # traced bounds (ring offsets, contiguous-segment ranges): dynamic loop
    rows = None if has_off or has_bnd else _static_rows(
        sq, sk, blk_q, blk_k, causal, window)[0]

    def kern(*refs):
        refs = list(refs)
        qr, kr, vr = refs[:3]
        i = 3
        br = refs[i] if has_bias else None
        i += has_bias
        qsr = refs[i] if has_seg else None
        ksr = refs[i + 1] if has_seg else None
        i += 2 * has_seg
        bndr = refs[i] if has_bnd else None
        i += has_bnd
        offr = refs[i] if has_off else None
        i += has_off
        orf, lr = refs[i], refs[i + 1]
        _fwd_kernel(qr, kr, vr, br, qsr, ksr, bndr, offr, orf, lr,
                    scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k,
                    pad_id=pad_id, window=window, lse_group=lse_g, rows=rows)

    o, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[ospec, lspec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, nq, blk_q), jnp.float32),
        ],
        interpret=_interpret(),
    )(*args)
    lse = lse.reshape(b, h, sq, 1)  # dense either way outside the call
    # Named for selective activation checkpointing: a remat policy saving
    # these (e.g. GPTConfig.remat_policy="save_attn") keeps the kernel's
    # output + logsumexp so backward never re-runs the forward kernel —
    # O(b*h*s*d) memory buys back the most expensive recompute in the layer.
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, lse


def _flash_fwd_stream(q, k, v, offsets, q_seg, kv_seg, *, scale, causal,
                      blk_q, blk_k, pad_id, contiguous, window=None):
    """Streamed forward: grid (b, h, nq, nk); K/V arrive blockwise. With a
    ``window`` and static positions (no ring offsets) the k extent shrinks
    to the band's block width via :func:`_window_grid` — O(s·w) trips and
    DMA instead of O(s²)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq, nk = sq // blk_q, sk // blk_k
    nkw, k_base, kmap = _window_grid_maps(blk_q, blk_k, nk, causal, window,
                                          offsets)
    grid = (b, h, nq, nkw)
    kvh = _kv_head(h // k.shape[1])
    qspec = pl.BlockSpec((1, 1, blk_q, d),
                         lambda bi, hi, qi, kj: (bi, hi, qi, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, 1, blk_k, d),
                         lambda bi, hi, qi, kj: (bi, kvh(hi), kmap(qi, kj), 0),
                         memory_space=pltpu.VMEM)
    # lse travels as a DENSE (b, h, nq, blk_q) table — a (b, h, sq, 1)
    # custom-call operand gets the T(8, 128) layout, which lane-pads the
    # size-1 minor dim 128x: at 512k tokens that is a 2 GB HBM buffer for
    # 16 MB of logsumexp (measured; the official TPU flash/splash kernels
    # pay the same via their (..., 128) replication). The table is
    # windowed in _lse_group-row groups (constant VMEM at any sequence
    # length) and each block reads or writes its row with a cheap
    # (1, blk) <-> (blk, 1) transpose.
    lse_g = _lse_group(nq)
    lse_spec = pl.BlockSpec((1, 1, lse_g, blk_q),
                            lambda bi, hi, qi, kj: (bi, hi, qi // lse_g, 0),
                            memory_space=pltpu.VMEM)
    vspec, ospec, dv = kspec, qspec, v.shape[-1]
    if dv != d:   # values of a width of their own, and so the output
        vspec, ospec = (_of_width(x, dv) for x in (kspec, qspec))
    in_specs = [qspec, kspec, vspec]
    args = [q, k, v]
    has_seg = q_seg is not None
    has_bnd = has_seg and contiguous
    if has_seg:
        qs, ks = _seg_layouts(q_seg, kv_seg)
        bounds_q, _, qmm, kmm = _seg_metadata(q_seg, kv_seg, blk_q, blk_k,
                                              pad_id)
        in_specs += [
            pl.BlockSpec((1, blk_q, _NUM_LANES),
                         lambda bi, hi, qi, kj: (bi, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _NUM_SUBLANES, blk_k),
                         lambda bi, hi, qi, kj: (bi, 0, kmap(qi, kj)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2, nk), lambda bi, hi, qi, kj: (bi, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 2, nq), lambda bi, hi, qi, kj: (bi, 0, 0),
                         memory_space=pltpu.SMEM),
        ]
        args += [qs, ks, kmm, qmm]
        if has_bnd:
            in_specs.append(
                pl.BlockSpec((1, 2, nq), lambda bi, hi, qi, kj: (bi, 0, 0),
                             memory_space=pltpu.SMEM))
            args.append(bounds_q)
    has_off = offsets is not None
    if has_off:
        in_specs.append(_offsets_spec())
        args.append(offsets)

    def kern(*refs):
        refs = list(refs)
        qr, kr, vr = refs[:3]
        i = 3
        qsr = refs[i] if has_seg else None
        ksr = refs[i + 1] if has_seg else None
        kmmr = refs[i + 2] if has_seg else None
        qmmr = refs[i + 3] if has_seg else None
        i += 4 * has_seg
        bndr = refs[i] if has_bnd else None
        i += has_bnd
        offr = refs[i] if has_off else None
        i += has_off
        orf, lr = refs[i], refs[i + 1]
        accr, mr, lr2 = refs[i + 2], refs[i + 3], refs[i + 4]
        _fwd_kernel_stream(qr, kr, vr, qsr, ksr, kmmr, qmmr, bndr, offr,
                           orf, lr, accr, mr, lr2, scale=scale,
                           causal=causal, blk_q=blk_q, blk_k=blk_k,
                           pad_id=pad_id, nk=nk, window=window,
                           k_base=k_base, lse_group=lse_g)

    o, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[ospec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, nq, blk_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, dv), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
        ],
        interpret=_interpret(),
    )(*args)
    # external interface stays (b, h, sq, 1) — a plain XLA reshape, dense
    # either way outside the custom call
    lse = lse.reshape(b, h, sq, 1)
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, lse


def _flash_bwd_stream(q, k, v, offsets, o, lse, do, q_seg, kv_seg, *,
                      scale, causal, blk_q, blk_k, pad_id, contiguous,
                      window=None):
    """Streamed backward: dQ over grid (b, h, nq, nk) with K/V blockwise;
    dK/dV over grid (b, h, nk, nq) with Q/dO/lse/delta blockwise. VMEM
    residency is block-bounded — in particular the lane-replicated q-id
    tile arrives per q-block instead of whole-sq (the ADVICE r3 medium)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq, nk = sq // blk_q, sk // blk_k
    # lse/delta in the dense (b, h, nq, blk_q) table layout (see
    # _flash_fwd_stream) — the (b, h, sq, 1) shape would be lane-padded
    # 128x at the custom-call boundary
    lse = lse.reshape(b, h, nq, blk_q)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1).reshape(b, h, nq, blk_q)
    has_seg = q_seg is not None
    has_bnd = has_seg and contiguous
    has_off = offsets is not None
    if has_seg:
        qs_l, ks_l = _seg_layouts(q_seg, kv_seg)
        bounds_q, bounds_k, qmm, kmm = _seg_metadata(
            q_seg, kv_seg, blk_q, blk_k, pad_id)
    # window-restricted inner grids (see _flash_fwd_stream / _window_grid)
    nkw, k_base, kmap = _window_grid_maps(blk_q, blk_k, nk, causal, window,
                                          offsets)

    # dQ pass
    group = h // k.shape[1]
    kvh = _kv_head(group)
    qspec = pl.BlockSpec((1, 1, blk_q, d),
                         lambda bi, hi, qi, kj: (bi, hi, qi, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, 1, blk_k, d),
                         lambda bi, hi, qi, kj: (bi, kvh(hi), kmap(qi, kj), 0),
                         memory_space=pltpu.VMEM)
    lse_g = _lse_group(nq)
    lblk = pl.BlockSpec((1, 1, lse_g, blk_q),
                        lambda bi, hi, qi, kj: (bi, hi, qi // lse_g, 0),
                        memory_space=pltpu.VMEM)
    # values, and with them the output's gradient, of a width of their own
    vspec, dospec, dv = kspec, qspec, v.shape[-1]
    if dv != d:
        vspec, dospec = (_of_width(x, dv) for x in (kspec, qspec))
    in_specs = [qspec, kspec, vspec]
    args = [q, k, v]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, blk_q, _NUM_LANES),
                         lambda bi, hi, qi, kj: (bi, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _NUM_SUBLANES, blk_k),
                         lambda bi, hi, qi, kj: (bi, 0, kmap(qi, kj)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2, nk), lambda bi, hi, qi, kj: (bi, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 2, nq), lambda bi, hi, qi, kj: (bi, 0, 0),
                         memory_space=pltpu.SMEM),
        ]
        args += [qs_l, ks_l, kmm, qmm]
        if has_bnd:
            in_specs.append(
                pl.BlockSpec((1, 2, nq), lambda bi, hi, qi, kj: (bi, 0, 0),
                             memory_space=pltpu.SMEM))
            args.append(bounds_q)
    if has_off:
        in_specs.append(_offsets_spec())
        args.append(offsets)
    in_specs += [dospec, lblk, lblk]
    args += [do, lse, delta]

    def dq_kern(*refs):
        refs = list(refs)
        qr, kr, vr = refs[:3]
        i = 3
        qsr = refs[i] if has_seg else None
        ksr = refs[i + 1] if has_seg else None
        kmmr = refs[i + 2] if has_seg else None
        qmmr = refs[i + 3] if has_seg else None
        i += 4 * has_seg
        bndr = refs[i] if has_bnd else None
        i += has_bnd
        offr = refs[i] if has_off else None
        i += has_off
        dor, lr, dr, dqr, dq_accr = refs[i:i + 5]
        _bwd_dq_kernel_stream(qr, kr, vr, qsr, ksr, kmmr, qmmr, bndr, offr,
                              dor, lr, dr, dqr, dq_accr, scale=scale,
                              causal=causal, blk_q=blk_q, blk_k=blk_k,
                              pad_id=pad_id, nk=nk, window=window,
                              k_base=k_base, lse_group=lse_g)

    dq = pl.pallas_call(
        dq_kern,
        grid=(b, h, nq, nkw),
        in_specs=in_specs,
        out_specs=[qspec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
        scratch_shapes=[pltpu.VMEM((blk_q, d), jnp.float32)],
        interpret=_interpret(),
    )(*args)[0]

    # dK/dV pass
    nqw, q_base, qmap = _window_grid_maps(blk_k, blk_q, nq, causal, window,
                                          offsets, inner_is_k=False)
    # grouped-query heads: the grid walks the key-value heads and, ahead of
    # the q blocks, the query heads of each one's group
    of_q, of_kv = _group_axis(group)
    qspec2 = pl.BlockSpec((1, 1, blk_q, d),
                          of_q(lambda bi, hi, ki, qi: (bi, hi, qmap(ki, qi),
                                                       0)),
                          memory_space=pltpu.VMEM)
    kspec2 = pl.BlockSpec((1, 1, blk_k, d),
                          of_kv(lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
                          memory_space=pltpu.VMEM)
    lblk2 = pl.BlockSpec((1, 1, lse_g, blk_q),
                         of_q(lambda bi, hi, ki, qi: (bi, hi,
                                                      qmap(ki, qi) // lse_g,
                                                      0)),
                         memory_space=pltpu.VMEM)
    vspec2, dospec2 = kspec2, qspec2
    if dv != d:
        vspec2, dospec2 = (_of_width(x, dv) for x in (kspec2, qspec2))
    in_specs2 = [qspec2, kspec2, vspec2]
    args2 = [q, k, v]
    if has_seg:
        in_specs2 += [
            pl.BlockSpec((1, blk_q, _NUM_LANES),
                         of_kv(lambda bi, hi, ki, qi: (bi, qmap(ki, qi), 0)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _NUM_SUBLANES, blk_k),
                         of_kv(lambda bi, hi, ki, qi: (bi, 0, ki)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2, nq),
                         of_kv(lambda bi, hi, ki, qi: (bi, 0, 0)),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 2, nk),
                         of_kv(lambda bi, hi, ki, qi: (bi, 0, 0)),
                         memory_space=pltpu.SMEM),
        ]
        args2 += [qs_l, ks_l, qmm, kmm]
        if has_bnd:
            in_specs2.append(
                pl.BlockSpec((1, 2, nk),
                             of_kv(lambda bi, hi, ki, qi: (bi, 0, 0)),
                             memory_space=pltpu.SMEM))
            args2.append(bounds_k)
    if has_off:
        in_specs2.append(_offsets_spec())
        args2.append(offsets)
    in_specs2 += [dospec2, lblk2, lblk2]
    args2 += [do, lse, delta]

    def dkv_kern(*refs):
        refs = list(refs)
        qr, kr, vr = refs[:3]
        i = 3
        qsr = refs[i] if has_seg else None
        ksr = refs[i + 1] if has_seg else None
        qmmr = refs[i + 2] if has_seg else None
        kmmr = refs[i + 3] if has_seg else None
        i += 4 * has_seg
        bndr = refs[i] if has_bnd else None
        i += has_bnd
        offr = refs[i] if has_off else None
        i += has_off
        dor, lr, dr, dkr, dvr, dk_accr, dv_accr = refs[i:i + 7]
        _bwd_dkv_kernel_stream(qr, kr, vr, qsr, ksr, qmmr, kmmr, bndr, offr,
                               dor, lr, dr, dkr, dvr, dk_accr, dv_accr,
                               scale=scale, causal=causal, blk_q=blk_q,
                               blk_k=blk_k, pad_id=pad_id, nq=nq,
                               window=window, q_base=q_base,
                               lse_group=lse_g, group=group)

    dk, dv = pl.pallas_call(
        dkv_kern,
        grid=((b, h, nk, nqw) if group == 1
              else (b, h // group, nk, group, nqw)),
        in_specs=in_specs2,
        out_specs=[kspec2, vspec2],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, d), jnp.float32),
            pltpu.VMEM((blk_k, dv), jnp.float32),
        ],
        interpret=_interpret(),
    )(*args2)
    return dq, dk, dv, None


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "blk_q", "blk_k", "pad_id",
                     "contiguous", "stream", "window"),
)
def _flash_bwd(q, k, v, bias, offsets, o, lse, do, q_seg=None, kv_seg=None, *,
               scale, causal, blk_q, blk_k, pad_id=None, contiguous=True,
               stream=False, window=None):
    if stream:
        assert bias is None, "streamed path does not support dense bias"
        return _flash_bwd_stream(q, k, v, offsets, o, lse, do, q_seg, kv_seg,
                                 scale=scale, causal=causal, blk_q=blk_q,
                                 blk_k=blk_k, pad_id=pad_id,
                                 contiguous=contiguous, window=window)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq = sq // blk_q
    lse_g = _lse_group(nq)
    # dense (b, h, nq, blk_q) lse/delta tables (see _flash_fwd_stream)
    lse = lse.reshape(b, h, nq, blk_q)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1).reshape(b, h, nq, blk_q)
    has_seg = q_seg is not None
    has_bnd = has_seg and contiguous
    if has_seg:
        qs_l, ks_l = _seg_layouts(q_seg, kv_seg)
    if has_bnd:
        bounds_q, bounds_k, _, _ = _seg_metadata(
            q_seg, kv_seg, blk_q, blk_k, pad_id)

    # dQ pass: grid over (b, h, q-blocks), reordered so dbias accumulation
    # over broadcast dims happens on consecutive steps (see _dq_grid_order);
    # also emits dS accumulated into dbias.
    b_bcast = bias is not None and bias.shape[0] == 1
    h_bcast = bias is not None and bias.shape[1] == 1
    order = _dq_grid_order(bias, b_bcast, h_bcast)
    dims = {name: pos for pos, name in enumerate(order)}
    sizes = {"b": b, "h": h, "q": sq // blk_q}
    grid = tuple(sizes[name] for name in order)

    def reorder(fn):
        """Wrap a logical (bi, hi, qi) index map for the reordered grid."""

        def idx(*a):
            return fn(a[dims["b"]], a[dims["h"]], a[dims["q"]])

        return idx

    group = h // k.shape[1]
    kvh = _kv_head(group)
    qspec = pl.BlockSpec((1, 1, blk_q, d), reorder(lambda bi, hi, qi: (bi, hi, qi, 0)),
                         memory_space=pltpu.VMEM)
    kfull = pl.BlockSpec((1, 1, sk, d),
                         reorder(lambda bi, hi, qi: (bi, kvh(hi), 0, 0)),
                         memory_space=pltpu.VMEM)
    lblk = pl.BlockSpec((1, 1, lse_g, blk_q),
                        reorder(lambda bi, hi, qi: (bi, hi, qi // lse_g, 0)),
                        memory_space=pltpu.VMEM)

    # values, and with them the output's gradient, of a width of their own
    vfull, dospec, dv = kfull, qspec, v.shape[-1]
    if dv != d:
        vfull, dospec = (_of_width(x, dv) for x in (kfull, qspec))
    in_specs = [qspec, kfull, vfull]
    args = [q, k, v]
    if bias is not None:
        bb, bh = bias.shape[0], bias.shape[1]
        in_specs.append(pl.BlockSpec(
            (1, 1, blk_q, sk),
            reorder(lambda bi, hi, qi: (bi if bb > 1 else 0, hi if bh > 1 else 0, qi, 0)),
            memory_space=pltpu.VMEM,
        ))
        args.append(bias)
    if has_seg:
        in_specs += _seg_specs(blk_q, sk, reorder=reorder)
        args += [qs_l, ks_l]
        if has_bnd:
            in_specs.append(_smem_pair_spec(sq // blk_q, reorder=reorder))
            args.append(bounds_q)
    if offsets is not None:
        in_specs.append(_offsets_spec())
        args.append(offsets)
    in_specs += [dospec, lblk, lblk]
    args += [do, lse, delta]
    has_bias, has_off = bias is not None, offsets is not None
    # traced bounds (ring offsets, contiguous-segment ranges): dynamic loop
    rows_q, rows_k = (None, None) if has_off or has_bnd else _static_rows(
        sq, sk, blk_q, blk_k, causal, window)

    def dq_kern(*refs):
        refs = list(refs)
        qr, kr, vr = refs[:3]
        i = 3
        br = refs[i] if has_bias else None
        i += has_bias
        qsr = refs[i] if has_seg else None
        ksr = refs[i + 1] if has_seg else None
        i += 2 * has_seg
        bndr = refs[i] if has_bnd else None
        i += has_bnd
        offr = refs[i] if has_off else None
        i += has_off
        dor, lr, dr, dqr = refs[i:i + 4]
        dbr = refs[i + 4] if has_bias else None
        _bwd_dq_kernel(qr, kr, vr, br, qsr, ksr, bndr, offr, dor, lr,
                       dr, dqr, dbr,
                       scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k,
                       pad_id=pad_id, b_bcast=b_bcast, h_bcast=h_bcast,
                       dims=dims, window=window, lse_group=lse_g,
                       rows=rows_q)

    out_specs = [qspec]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if bias is not None:
        out_specs.append(pl.BlockSpec(
            (1, 1, blk_q, sk),
            reorder(lambda bi, hi, qi: (bi if bb > 1 else 0, hi if bh > 1 else 0, qi, 0)),
            memory_space=pltpu.VMEM,
        ))
        out_shape.append(jax.ShapeDtypeStruct(bias.shape, jnp.float32))
    res = pl.pallas_call(
        dq_kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=_interpret(),
    )(*args)
    dq, dbias = (res[0], res[1]) if bias is not None else (res[0], None)

    # dK/dV pass: grid over k blocks; q/do/lse/delta stream in full.
    # Grouped-query heads: the grid walks the key-value heads and, one
    # axis further in, the query heads of each one's group, whose dK and dV
    # are summed in float32 scratch before the block is written.
    of_q, of_kv = _group_axis(group)
    qfull = pl.BlockSpec((1, 1, sq, d),
                         of_q(lambda bi, hi, ki: (bi, hi, 0, 0)),
                         memory_space=pltpu.VMEM)
    kblk = pl.BlockSpec((1, 1, blk_k, d),
                        of_kv(lambda bi, hi, ki: (bi, hi, ki, 0)),
                        memory_space=pltpu.VMEM)
    lfull = pl.BlockSpec((1, 1, nq, blk_q),
                         of_q(lambda bi, hi, ki: (bi, hi, 0, 0)),
                         memory_space=pltpu.VMEM)
    vblk, dofull = kblk, qfull
    if dv != d:
        vblk, dofull = (_of_width(x, dv) for x in (kblk, qfull))
    in_specs2 = [qfull, kblk, vblk]
    args2 = [q, k, v]
    if bias is not None:
        bb, bh = bias.shape[0], bias.shape[1]
        bspec2 = pl.BlockSpec(
            (1, 1, sq, blk_k),
            of_q(lambda bi, hi, ki: (bi if bb > 1 else 0,
                                     hi if bh > 1 else 0, 0, ki)),
            memory_space=pltpu.VMEM,
        )
        in_specs2.append(bspec2)
        args2.append(bias)
    if has_seg:
        # the pass works on S^T: k ids lane-replicated per k block, q ids
        # sublane-replicated and whole; bounds indexed by k block
        ks_t, qs_t = _seg_layouts(kv_seg, q_seg)
        in_specs2 += [
            pl.BlockSpec((1, blk_k, _NUM_LANES),
                         of_kv(lambda bi, hi, ki: (bi, ki, 0)),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _NUM_SUBLANES, sq),
                         of_kv(lambda bi, hi, ki: (bi, 0, 0)),
                         memory_space=pltpu.VMEM),
        ]
        args2 += [ks_t, qs_t]
        if has_bnd:
            in_specs2.append(pl.BlockSpec(
                (1, 2, sk // blk_k), of_kv(lambda bi, hi, ki: (bi, 0, 0)),
                memory_space=pltpu.SMEM))
            args2.append(bounds_k)
    if offsets is not None:
        in_specs2.append(_offsets_spec())
        args2.append(offsets)
    in_specs2 += [dofull, lfull, lfull]
    args2 += [do, lse, delta]

    def dkv_kern(*refs):
        refs = list(refs)
        qr, kr, vr = refs[:3]
        i = 3
        br = refs[i] if has_bias else None
        i += has_bias
        ksr = refs[i] if has_seg else None
        qsr = refs[i + 1] if has_seg else None
        i += 2 * has_seg
        bndr = refs[i] if has_bnd else None
        i += has_bnd
        offr = refs[i] if has_off else None
        i += has_off
        dor, lr, dr, dkr, dvr = refs[i:i + 5]
        _bwd_dkv_kernel(qr, kr, vr, br, ksr, qsr, bndr, offr,
                        dor, lr, dr, dkr, dvr,
                        scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k,
                        pad_id=pad_id, window=window, rows=rows_k,
                        group=group, acc_refs=refs[i + 5:i + 7])

    grouped = {} if group == 1 else {"scratch_shapes": [
        pltpu.VMEM((blk_k, d), jnp.float32),
        pltpu.VMEM((blk_k, dv), jnp.float32)]}
    dk, dv = pl.pallas_call(
        dkv_kern,
        grid=((b, h, sk // blk_k) if group == 1
              else (b, h // group, sk // blk_k, group)),
        in_specs=in_specs2,
        out_specs=[kblk, vblk],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=_interpret(),
        **grouped,
    )(*args2)
    return dq, dk, dv, dbias


# ---------------------------------------------------------------------------
# custom_vjp + public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(6, 7, 8, 9, 10, 11, 12, 13))
def _flash(q, k, v, bias, q_seg, kv_seg, scale, causal, blk_q, blk_k,
           pad_id, contiguous, stream, window):
    o, _ = _flash_fwd(q, k, v, bias, None, q_seg, kv_seg,
                      scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k,
                      pad_id=pad_id, contiguous=contiguous, stream=stream,
                      window=window)
    return o


def _flash_vjp_fwd(q, k, v, bias, q_seg, kv_seg, scale, causal, blk_q, blk_k,
                   pad_id, contiguous, stream, window):
    o, lse = _flash_fwd(q, k, v, bias, None, q_seg, kv_seg,
                        scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k,
                        pad_id=pad_id, contiguous=contiguous, stream=stream,
                        window=window)
    return o, (q, k, v, bias, q_seg, kv_seg, o, lse)


def _flash_vjp_bwd(scale, causal, blk_q, blk_k, pad_id, contiguous, stream,
                   window, res, do):
    q, k, v, bias, q_seg, kv_seg, o, lse = res
    dq, dk, dv, dbias = _flash_bwd(q, k, v, bias, None, o, lse, do,
                                   q_seg, kv_seg, scale=scale,
                                   causal=causal, blk_q=blk_q, blk_k=blk_k,
                                   pad_id=pad_id, contiguous=contiguous,
                                   stream=stream, window=window)
    if dbias is not None:
        dbias = dbias.astype(bias.dtype)
    # segment ids are integer inputs: symbolically-zero cotangents
    return dq, dk, dv, dbias, None, None


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# The resident layout's worst-case per-program VMEM residency (bytes); when
# it exceeds this budget the streamed kernels take over. ~16 MB VMEM minus
# headroom for double buffering, accumulators, and Mosaic temporaries.
_RESIDENT_VMEM_BUDGET = 6 * 1024 * 1024

# one-time hint that a packed (non-decreasing) segment layout was passed
# without opting into block skipping (ADVICE r4 low #4)
_WARNED_PACKED_OPT_IN = False


def _resident_vmem_bytes(sq, sk, d, blk_q, blk_k, itemsize, has_bias,
                         has_seg):
    """Dominant per-program VMEM residency of the resident layout, for the
    fwd/dQ passes (whole K+V) and the dK/dV pass (whole Q/dO — residency
    scales with TOTAL tokens, not max_seqlen, on the packed path; its
    segment ids are one lane-replicated k-id block and the whole
    sublane-replicated q ids, the pass working on S^T).

    VMEM tiles pad the MINOR dim to the 128-lane vreg width: a head_dim
    of 32 occupies 128 lanes — observed live: a d=32, s=8192 resident
    dK/dV pass allocates 17.3 MB where the unpadded arithmetic says
    1.6 MB. The estimate must count PADDED bytes or 'auto' keeps
    resident layouts that cannot compile. (lse/delta now travel as dense
    (nq, blk_q) tables — sq·4 bytes each, no lane padding — so they no
    longer dominate; the q/do/K/V operand padding does.)"""
    d_eff = -(-d // _NUM_LANES) * _NUM_LANES
    seg_fwd = (blk_q * _NUM_LANES + _NUM_SUBLANES * sk) * 4 if has_seg else 0
    fwd = (2 * sk * d_eff * itemsize
           + (blk_q * sk * 4 if has_bias else 0) + seg_fwd)
    seg_dkv = (blk_k * _NUM_LANES + _NUM_SUBLANES * sq) * 4 if has_seg else 0
    dkv = (3 * sq * d_eff * itemsize  # q, do (+ dq-pass K/V ≈ fwd term)
           + 2 * sq * 4  # lse + delta dense tables
           + (sq * blk_k * 4 if has_bias else 0) + seg_dkv)
    return max(fwd, dkv)


# ---------------------------------------------------------------------------
# lint/analyzer introspection hooks (apex_tpu.lint.trace lane-padding
# auditor; monitor/hbm.py documents the same tiling for HBM): the lane and
# sublane constants the 'auto' layout decision compiles by, and the
# resident-layout residency estimator, public so analyzers estimate with
# the exact rules this kernel is calibrated against.
# ---------------------------------------------------------------------------

NUM_LANES = _NUM_LANES
NUM_SUBLANES = _NUM_SUBLANES
resident_vmem_bytes = _resident_vmem_bytes


# Measurement basis of the stream='auto' throughput crossover: d=64 bf16
# on-chip fwd+bwd. The re-streamed q/do rows move LANE-PADDED bytes
# (minor dim pads to the 128-lane vreg width, same rule as
# _resident_vmem_bytes), so the basis row is 128 lanes x 2 B = 256 B.
_CROSSOVER_SEQ = 4096
_CROSSOVER_ROW_BYTES = _NUM_LANES * 2


def _auto_stream(sq, sk, d, blk_q, blk_k, itemsize, has_bias, has_seg):
    """The stream='auto' decision, shared with ``ring_attention``:
    ``(vmem_wall, crossover)``.

    ``vmem_wall``: the resident layout's estimated residency exceeds the
    VMEM budget — it cannot compile, streaming is mandatory.
    ``crossover``: a measured THROUGHPUT boundary, not a memory wall: the
    resident dK/dV pass re-streams whole-sq q/do per k block (O(nk·sq·d)
    DMA) and falls behind the streamed layout past ~2k — on-chip fwd+bwd
    AT d=64 bf16: s=2048 resident 12.2 vs streamed 13.4 ms, s=4096
    resident 27.4 vs streamed 17.7 ms. (The dense lse tables made
    4096-resident COMPILE, so the wall check alone would pick the slower
    layout.) That re-streamed traffic moves PADDED rows — the minor dim
    pads to 128 lanes, so every d <= 128 DMAs the same
    ``128 * itemsize`` bytes/row and the measured 4096 boundary stands
    across the whole d=32..128 bf16 family (a naive ``d * itemsize``
    scaling would halve it for d=128 where the physical traffic is
    unchanged). The boundary moves DOWN only when the padded row grows:
    fp32 doubles it (any d <= 128 -> 2048), as does d > 128. The d=64
    bf16 measurement is the only calibrated point; other (d, itemsize)
    boundaries are this traffic-proportional extrapolation."""
    wall = _resident_vmem_bytes(sq, sk, d, blk_q, blk_k, itemsize,
                                has_bias, has_seg) > _RESIDENT_VMEM_BUDGET
    row_bytes = (-(-d // _NUM_LANES) * _NUM_LANES) * itemsize
    crossover_seq = min(_CROSSOVER_SEQ,
                        _CROSSOVER_SEQ * _CROSSOVER_ROW_BYTES
                        // max(row_bytes, 1))
    return wall, max(sq, sk) >= crossover_seq


def mha_reference(
    q: jax.Array, k: jax.Array, v: jax.Array,
    bias: Optional[jax.Array] = None,
    *, causal: bool = False, scale: Optional[float] = None,
    segment_ids: Optional[Tuple[jax.Array, jax.Array]] = None,
    pad_id: Optional[int] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Unfused XLA attention (the torch-softmax fallback path,
    fused_softmax.py:193-199 forward_torch_softmax equivalent)."""
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    if k.shape[1] != q.shape[1]:
        # grouped-query heads: this dense path repeats what the kernels read
        # through their index maps
        k, v = (jnp.repeat(x, q.shape[1] // k.shape[1], axis=1)
                for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    # a cross-shape (sq != sk) window can fully mask rows too (queries
    # past sk + window), so they need the same exact-zero treatment as
    # segment-masked rows
    masked = segment_ids is not None or window is not None
    if segment_ids is not None:
        q_seg, kv_seg = segment_ids
        valid = q_seg[:, None, :, None] == kv_seg[:, None, None, :]
        if pad_id is not None:
            valid = valid & (kv_seg != pad_id)[:, None, None, :]
        s = jnp.where(valid, s, _NEG_INF)
    if causal or window is not None:
        sq, sk = s.shape[-2], s.shape[-1]
        s = _dense_pos_masks(s, jnp.arange(sq)[:, None],
                             jnp.arange(sk)[None, :], causal, window)
    p = jax.nn.softmax(s, axis=-1)
    if masked:
        # match the kernel: rows with no visible key output exactly zero
        # (softmax of an all -inf row would be uniform, not zero). Derived
        # AFTER all masks: a row whose same-segment keys all sit above the
        # causal diagonal is fully masked too (ADVICE r3 low #2 — deciding
        # from the segment mask alone diverged from the kernel there).
        fully_masked = jnp.max(s, axis=-1, keepdims=True) <= _NEG_INF / 2
        p = jnp.where(fully_masked, 0.0, p)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    segment_ids: Optional[Tuple[jax.Array, jax.Array]] = None,
    pad_id: Optional[int] = None,
    contiguous_segments: bool = False,
    causal: bool = False,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    impl: str = "auto",
    stream: str = "auto",
) -> jax.Array:
    """Fused multi-head attention.

    Args:
      q, k, v: ``(batch, heads, seq, head_dim)``; kv seq may differ from q seq
        (encoder-decoder attention, apex/contrib/multihead_attn encdec path).
        ``v`` may have a width of its own (latent attention scores over 192
        and sums values of 128): the output and dV have ``v``'s.
        ``k`` and ``v`` may hold fewer heads than ``q``, a divisor of its
        count (grouped-query attention): query heads ``i * g .. i * g + g -
        1`` read key-value head ``i`` through the kernels' index maps,
        nothing is repeated in memory, and dK and dV come back summed over
        each group, in the key-value heads' own shape.
      bias: optional additive bias broadcastable to ``(b, h, sq, sk)``
        (additive-mask attention; use -10000 for masked positions like the
        reference's masked_fill).
      segment_ids: optional ``(q_seg, kv_seg)`` int arrays of shape
        ``(b, sq)`` / ``(b, sk)``: a query attends only keys with an EQUAL
        segment id — packed-varlen attention (the reference fmha's
        cu_seqlens semantics, apex/contrib/fmha/fmha.py:33-74). Rows whose
        every key is masked output exactly 0.
      pad_id: segment id marking padding: such keys are never attended
        (and padded query rows output 0).
      contiguous_segments: ids are non-decreasing along the sequence (the
        packed layout). Enables block skipping: k blocks whose segment
        range cannot intersect the q block's are never computed, so cost
        scales with ``sum(len_i^2)`` instead of ``total^2``. Default False
        (mask-only): with NON-monotone ids skipping silently drops valid
        q/k pairs, and under ``jit`` (traced ids — the common training
        case) the monotonicity check below cannot run, so opting in is the
        caller asserting the packed layout (``contrib.fmha`` does; ADVICE
        r3 low #3).
      causal: upper-triangular masking (scaled_upper_triang_masked_softmax).
      scale: score scale; defaults to 1/sqrt(head_dim).
      window: sliding-window (local) attention — each query attends only
        the ``window`` most recent positions ``[p-window+1, p]`` when
        causal (the Mistral/Longformer convention) or the symmetric band
        ``[p-window+1, p+window-1]`` when not. Blocks wholly outside the
        band are skipped, so score cost is O(s·window) instead of O(s²).
        Beyond-reference capability: the reference's fmha kernels have
        no local-attention mode; this is the standard long-context
        pairing for the streamed kernels. Composes with ``causal``,
        ``segment_ids``, ``bias``, and streaming.
      block_q, block_k: the score tile's edges (each the largest divisor of
        its sequence at or under the value). Default None: derived from the
        shape and the masks by :func:`flash_tile_plan` — causal or windowed
        attention takes the smallest edge whose unmasked tiles the resident
        kernels can walk with static bounds (512 at 1024 tokens: 3 of the
        square's 4 tiles are computed, and only the 2 on the diagonal are
        masked), everything else one tile of up to 1024.
      impl: 'auto' | 'pallas' | 'xla'.
      stream: 'auto' | 'never' | 'always' — streamed kernels move the
        K/V loop into the Pallas grid so VMEM residency is block-bounded
        rather than sequence-bounded. 'auto' switches over when the
        resident layout's estimated residency exceeds the VMEM budget
        (long sequences / large packed token counts). The streamed path
        does not take a dense ``bias`` ('auto' then stays resident).
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if k.shape[1] != v.shape[1] or h % k.shape[1]:
        raise ValueError(
            f"k and v hold {k.shape[1]} and {v.shape[1]} heads under {h} "
            "query heads: they must agree and divide the query heads")
    scale = (d ** -0.5) if scale is None else float(scale)
    if window is not None:
        window = int(window)
        if window < 1:
            raise ValueError(f"window must be a positive int, got {window}")
        if window >= max(sq, sk):
            window = None  # the band covers everything: dense attention
    use = _resolve_impl(impl)
    if use == "pallas" and not _supported(sq, sk, d):
        use = _pallas_unsupported(
            "flash_attention", impl,
            f"sq={sq}, sk={sk}, d={d} is outside the kernel's envelope "
            "(8-aligned sequences, head_dim >= 8)")
    global _WARNED_PACKED_OPT_IN
    if block_q is None and block_k is None and d > _NUM_LANES:
        # a head wider than the 128 lanes is padded to 256 in VMEM: beside
        # the four float32 score tiles of the backward pass, a tile of 1024
        # no longer fits the 16 MB a kernel may use (192-wide keys, v5e)
        block_q = block_k = 512
    blk_q, blk_k, _, _ = flash_tile_plan(
        sq, sk, causal, window, block_q=block_q, block_k=block_k,
        has_segments=segment_ids is not None,
        contiguous_segments=contiguous_segments)
    if segment_ids is not None:
        q_seg, kv_seg = segment_ids
        if q_seg.shape != (b, sq) or kv_seg.shape != (b, sk):
            raise ValueError(
                f"segment_ids shapes {q_seg.shape}/{kv_seg.shape} do not "
                f"match (batch, seq) = ({b}, {sq})/({b}, {sk})")
        if (contiguous_segments or not _WARNED_PACKED_OPT_IN) and not any(
                isinstance(s, jax.core.Tracer) for s in (q_seg, kv_seg)):
            # once the one-time hint has fired, mask-only callers skip the
            # scan entirely — np.asarray on concrete device arrays is a
            # host fetch per call
            # block skipping is only sound for non-decreasing ids; with
            # concrete ids enforce it here (traced ids: the caller owns the
            # guarantee, like the reference's static bucket dispatch)
            import numpy as _np

            monotone = True
            for name, ids in (("q", q_seg), ("kv", kv_seg)):
                a = _np.asarray(ids)
                if (_np.diff(a, axis=-1) < 0).any():
                    monotone = False
                    if contiguous_segments:
                        raise ValueError(
                            f"{name} segment ids are not non-decreasing; "
                            "pass contiguous_segments=False for non-packed "
                            "layouts (mask-only, no block skipping)")
            if monotone and not contiguous_segments:
                # packed layout detected but block skipping left off: the
                # default is the safe mask-only path, which computes
                # total^2 score blocks instead of sum(len_i^2) — tell the
                # caller once so genuinely packed layouts learn to opt in
                if not _WARNED_PACKED_OPT_IN:
                    _WARNED_PACKED_OPT_IN = True
                    import warnings

                    warnings.warn(
                        "flash_attention: segment ids are non-decreasing "
                        "(packed layout) but contiguous_segments=False; "
                        "pass contiguous_segments=True to enable block "
                        "skipping (cost sum(len_i^2) instead of total^2)",
                        stacklevel=2)
        # the lane-replicated kernel layout needs 128-aligned k blocks
        if use == "pallas" and (blk_k % _NUM_LANES or sk % blk_k):
            use = _pallas_unsupported(
                "flash_attention", impl,
                f"segment_ids need a 128-aligned k block dividing sk={sk}")
    if stream not in ("auto", "never", "always"):
        raise ValueError(f"stream must be auto|never|always, got {stream!r}")
    if use == "xla":
        # explicit impl="xla" (or an unsupported-shape fallback): the dense
        # path supports bias and ignores streaming, so return before the
        # stream-vs-bias checks (ADVICE r4: stream="always" + bias must not
        # reject an explicitly requested, working XLA path)
        return mha_reference(q, k, v, bias, causal=causal, scale=scale,
                             segment_ids=segment_ids, pad_id=pad_id,
                             window=window)
    vmem_wall, crossover = _auto_stream(
        sq, sk, d, blk_q, blk_k, q.dtype.itemsize, bias is not None,
        segment_ids is not None)
    do_stream = stream == "always" or (
        stream == "auto" and (vmem_wall or crossover))
    if do_stream and bias is not None:
        if stream == "always":
            raise ValueError("stream='always' does not support dense bias; "
                             "use segment_ids/causal for long sequences")
        # auto: the streamed path lacks the dbias pass. If the RESIDENT
        # layout cannot fit VMEM, proceeding into it would die with an
        # opaque Mosaic allocation failure — take the XLA path
        # (functional, HBM-bound) instead. A throughput-crossover-only
        # trigger keeps the resident kernel: it compiles and beats dense
        # XLA attention even past the crossover.
        do_stream = False
        if vmem_wall:
            use = _pallas_unsupported(
                "flash_attention", impl,
                f"a dense bias at sq={sq}, sk={sk} exceeds the resident "
                "kernel's VMEM budget and the streamed kernel takes no bias")
    if use == "xla":
        return mha_reference(q, k, v, bias, causal=causal, scale=scale,
                             segment_ids=segment_ids, pad_id=pad_id,
                             window=window)
    if bias is not None:
        if bias.ndim != 4:
            raise ValueError(f"bias must be rank-4 broadcastable, got shape {bias.shape}")
        # Canonicalize size-1 sq/sk dims away (the kernels tile dims 2/3 at
        # full size). This sits outside the custom_vjp, so AD of broadcast_to
        # sums dbias back to the caller's original shape.
        bb, bh = bias.shape[0], bias.shape[1]
        if bb not in (1, b) or bh not in (1, h):
            raise ValueError(f"bias shape {bias.shape} not broadcastable to "
                             f"({b}, {h}, {sq}, {sk})")
        bias = jnp.broadcast_to(bias, (bb, bh, sq, sk))
    q_seg, kv_seg = segment_ids if segment_ids is not None else (None, None)
    return _flash(q, k, v, bias, q_seg, kv_seg, scale, bool(causal),
                  blk_q, blk_k,
                  None if pad_id is None else int(pad_id),
                  bool(contiguous_segments), do_stream, window)

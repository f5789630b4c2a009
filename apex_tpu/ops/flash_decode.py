"""Flash-decode — single-query attention over a PAGED KV cache.

The serving-side sibling of ops/flash_attention.py: at decode time each
sequence contributes ONE query that must attend every cached key, and the
keys live in fixed-size blocks of a preallocated page pool (apex_tpu/serve/
cache.py) addressed through a per-sequence block table — never in a
contiguous per-request buffer whose growth would recompile the step or
lane-pad per request. This is the split-KV decode primitive: the same
online-softmax recurrence as the streamed training kernels
(flash_attention._fwd_kernel), gridded over (batch, kv_head, page) with the
page index READ FROM THE BLOCK TABLE via Pallas scalar prefetch, so one
compiled program serves any mix of sequence lengths.

Reference: the fused single-pass attention of apex/contrib/fmha/fmha.py:33-74
(whose cu_seqlens contract is the per-sequence ``lengths`` here) — the paging
and the decode grid are beyond-reference capability, per the operation-fusion
framing of PAPERS.md (LLM inference acceleration via op fusion).

Layouts (the T(8,128) reasoning, PERF_NOTES r11 + the ISSUE 13 static-hbm
catch): pages are ``(num_blocks, kv_heads, block, head_dim)`` with head_dim
MINOR — the lane dim is head_dim (full vregs at d >= 128, the same
4x-pad-at-d-32 tax as training) — and the BLOCK SIZE second-minor, so the
sublane dim is a multiple of 8 by construction and the pool's padded
residency is the head_dim padding alone (the earlier kv_heads-second-minor
order padded 4 heads to 8 sublanes: 4x total at f32/h4/d64, static-hbm's
first real catch); a page never pays the 128x ``(.., 1)`` column tax the
lse tables were redesigned to avoid.

GQA-style head broadcasting: ``q`` carries ``H`` query heads over ``KH``
kv heads (``H % KH == 0``); each kernel program owns one kv head and its
``H/KH`` query-head group. ``window`` applies the causal sliding-window
convention of ``flash_attention`` (the decoding query sits at position
``length - 1``, so keys ``[length - window, length)`` are kept).

K-query extension (ISSUE 12): :func:`flash_decode_multi` attends K
TRAILING queries per sequence over the same pages — query ``j`` of slot
``b`` sits at position ``lengths[b] - K + j`` and sees exactly the keys a
sequential single-query decode would have seen at that position (in-chunk
causality falls out of the per-query length mask, since later in-chunk keys
hold larger positions). One program serves both chunked prefill (one slot,
C prompt positions per launch) and speculative verify (every slot, k
drafted tokens + the pending token in ONE batched shape-stable forward —
the whole-step operation fusion of PAPERS.md applied to decode).

No gradients: decode is inference-only (a custom VJP would re-gather pages;
training uses flash_attention).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.flash_attention import _NEG_INF, _NUM_LANES
from apex_tpu.ops.layer_norm import (
    _interpret,
    _pallas_unsupported,
    _resolve_impl,
)


def paged_attention_reference(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Unfused XLA twin of :func:`flash_decode` (the mha_reference analog):
    gather the pages dense, mask by length/window, one-pass softmax. The
    oracle the kernel is tested against, and the off-TPU default."""
    b, h, d = q.shape
    _, kh, blk, _ = k_pages.shape
    g = h // kh
    scale = (d ** -0.5) if scale is None else float(scale)
    s_max = block_tables.shape[1] * blk
    # (b, nb, kh, blk, d) -> (b, s_max, kh, d): positions contiguous
    k = k_pages[block_tables].transpose(0, 1, 3, 2, 4).reshape(
        b, s_max, kh, d)
    v = v_pages[block_tables].transpose(0, 1, 3, 2, 4).reshape(
        b, s_max, kh, d)
    qg = q.reshape(b, kh, g, d).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(jnp.float32)) * scale
    pos = jnp.arange(s_max, dtype=jnp.int32)
    valid = pos[None, :] < lengths[:, None]
    if window is not None:
        valid = valid & (pos[None, :] >= lengths[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # rows with no visible key (idle slots: length 0) output exactly 0,
    # matching the kernel's l == 0 guard and mha_reference's masked rows
    fully_masked = jnp.max(s, axis=-1, keepdims=True) <= _NEG_INF / 2
    p = jnp.where(fully_masked, 0.0, p)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return o.reshape(b, h, d).astype(q.dtype)


def paged_attention_multi_reference(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Unfused XLA twin of :func:`flash_decode_multi`: gather the pages
    dense, mask per query by its own trailing position, one-pass softmax.
    ``q`` is ``(batch, heads, K, head_dim)``; query ``j`` sees
    ``lengths[b] - (K - 1 - j)`` keys."""
    b, h, kq, d = q.shape
    _, kh, blk, _ = k_pages.shape
    g = h // kh
    scale = (d ** -0.5) if scale is None else float(scale)
    s_max = block_tables.shape[1] * blk
    k = k_pages[block_tables].transpose(0, 1, 3, 2, 4).reshape(
        b, s_max, kh, d)
    v = v_pages[block_tables].transpose(0, 1, 3, 2, 4).reshape(
        b, s_max, kh, d)
    qg = q.reshape(b, kh, g, kq, d).astype(jnp.float32)
    s = jnp.einsum("bkgqd,bskd->bkgqs", qg,
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(s_max, dtype=jnp.int32)
    qlen = (lengths[:, None]
            - (kq - 1 - jnp.arange(kq, dtype=jnp.int32))[None, :])  # (b, K)
    valid = pos[None, None, :] < qlen[:, :, None]  # (b, K, s)
    if window is not None:
        valid = valid & (pos[None, None, :] >= qlen[:, :, None] - window)
    s = jnp.where(valid[:, None, None, :, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    fully_masked = jnp.max(s, axis=-1, keepdims=True) <= _NEG_INF / 2
    p = jnp.where(fully_masked, 0.0, p)
    o = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))
    return o.reshape(b, h, kq, d).astype(q.dtype)


def _decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, blk, nb, window):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (blk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (G, blk)
    pos = j * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = pos < length
    if window is not None:
        valid = valid & (pos >= length - window)
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = m_ref[:, 0:1]
    l_prev = l_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # fully-masked so far: exp(s - m) would be exp(0); zero the probs so l
    # stays 0 and the output stays 0 (same guard as _fwd_kernel)
    p = jnp.where(m_new <= _NEG_INF / 2, 0.0, jnp.exp(s - m_new))
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nb - 1)
    def _done():
        l = l_ref[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def flash_decode(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    impl: str = "auto",
) -> jax.Array:
    """Single-query attention over a paged KV cache.

    Args:
      q: ``(batch, heads, head_dim)`` — one query per sequence slot (the
        token being decoded, already written to the cache so it attends
        itself; ``lengths`` includes it).
      k_pages, v_pages: ``(num_blocks, kv_heads, block, head_dim)`` page
        pools (apex_tpu.serve.cache layout: block in the sublane dim;
        ``heads % kv_heads == 0``, query-head groups broadcast over each
        kv head — GQA).
      block_tables: ``(batch, max_blocks)`` int32 — page ids per sequence,
        position ``p`` living in table slot ``p // block`` at offset
        ``p % block``. Slots beyond a sequence's allocation must point at
        a valid (e.g. the reserved null) page: trips are MASKED by
        ``lengths``, not skipped — the TPU grid is sequential, so the cost
        of a tick is O(max_blocks) DMA regardless of length (the price of
        one shape-stable program; see serve/engine.py).
      lengths: ``(batch,)`` int32 — valid keys per slot (0 = idle slot;
        its output is exactly 0).
      scale: score scale; defaults to ``1/sqrt(head_dim)``.
      window: causal sliding window — keep keys ``[length-window, length)``
        (the flash_attention convention seen from the newest position).
      impl: 'auto' | 'pallas' | 'xla' (auto = pallas on TPU, xla off —
        interpret mode keeps the Pallas path testable on CPU).

    Returns ``(batch, heads, head_dim)`` in ``q.dtype``.
    """
    b, h, d = q.shape
    n_pages, kh, blk, d2 = k_pages.shape
    if d2 != d or v_pages.shape != k_pages.shape:
        raise ValueError(
            f"page shapes {k_pages.shape}/{v_pages.shape} do not match "
            f"q head_dim {d}")
    if h % kh:
        raise ValueError(f"heads ({h}) must be a multiple of kv_heads ({kh})")
    if window is not None and int(window) < 1:
        raise ValueError(f"window must be a positive int, got {window}")
    nb = block_tables.shape[1]
    scale = (d ** -0.5) if scale is None else float(scale)
    use = _resolve_impl(impl)
    if use == "pallas" and (blk % 8 or d < 8):
        use = _pallas_unsupported(
            "flash_decode", impl,
            f"page block={blk}, head_dim={d} is below one (8, 8) tile")
    if use == "xla":
        return paged_attention_reference(
            q, k_pages, v_pages, block_tables, lengths,
            scale=scale, window=window)

    g = h // kh
    qg = q.reshape(b, kh, g, d)
    tables = block_tables.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kh, nb),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bi, ki, j, tbl, ln: (bi, ki, 0, 0)),
            # the paged fetch: the PAGE index comes from the prefetched
            # block table, so the same compiled program walks any table
            # (page rows are (block, head_dim) — block in the sublane dim)
            pl.BlockSpec((1, 1, blk, d),
                         lambda bi, ki, j, tbl, ln: (tbl[bi, j], ki, 0, 0)),
            pl.BlockSpec((1, 1, blk, d),
                         lambda bi, ki, j, tbl, ln: (tbl[bi, j], ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda bi, ki, j, tbl, ln: (bi, ki, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, _NUM_LANES), jnp.float32),
            pltpu.VMEM((g, _NUM_LANES), jnp.float32),
        ],
    )
    import functools

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, blk=blk, nb=nb,
                          window=None if window is None else int(window)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), q.dtype),
        interpret=_interpret(),
    )(tables, lens, qg, k_pages, v_pages)
    return out.reshape(b, h, d)


def _decode_multi_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, scale, blk, nb,
                         window, kq):
    """:func:`_decode_kernel` with K trailing queries per (batch, kv-head)
    program: the q block rows are ``(group, query)`` flattened with the
    query index MINOR, so row ``r``'s query index is ``r % K`` and its own
    visible-key count is ``length - (K - 1 - r % K)`` — the per-row length
    mask that realizes in-chunk causality."""
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (G*K, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (blk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (G*K, blk)
    pos = j * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % kq
    qlen = length - (kq - 1 - qi)
    valid = pos < qlen
    if window is not None:
        valid = valid & (pos >= qlen - window)
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = m_ref[:, 0:1]
    l_prev = l_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(m_new <= _NEG_INF / 2, 0.0, jnp.exp(s - m_new))
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nb - 1)
    def _done():
        l = l_ref[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def flash_decode_multi(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    impl: str = "auto",
) -> jax.Array:
    """K-query attention over a paged KV cache (trailing-query semantics).

    Args:
      q: ``(batch, heads, K, head_dim)`` — K TRAILING queries per slot:
        query ``j`` sits at position ``lengths[b] - K + j`` (already
        written to the cache, so it attends itself) and sees exactly
        ``lengths[b] - (K - 1 - j)`` keys — the keys a sequential decode
        would have seen at that position. Chunked prefill drives this with
        one slot and K = chunk; speculative verify with every slot and
        K = drafts + 1.
      k_pages, v_pages, block_tables, lengths, scale, window, impl: as in
        :func:`flash_decode`; ``lengths[b]`` counts the keys visible to
        the FINAL query (0 = idle slot, all K outputs exactly 0).

    Returns ``(batch, heads, K, head_dim)`` in ``q.dtype``.
    """
    b, h, kq, d = q.shape
    n_pages, kh, blk, d2 = k_pages.shape
    if d2 != d or v_pages.shape != k_pages.shape:
        raise ValueError(
            f"page shapes {k_pages.shape}/{v_pages.shape} do not match "
            f"q head_dim {d}")
    if h % kh:
        raise ValueError(f"heads ({h}) must be a multiple of kv_heads ({kh})")
    if window is not None and int(window) < 1:
        raise ValueError(f"window must be a positive int, got {window}")
    nb = block_tables.shape[1]
    scale = (d ** -0.5) if scale is None else float(scale)
    use = _resolve_impl(impl)
    if use == "pallas" and (blk % 8 or d < 8):
        use = _pallas_unsupported(
            "flash_decode_multi", impl,
            f"page block={blk}, head_dim={d} is below one (8, 8) tile")
    if use == "pallas" and (h // kh) * kq > 1024:
        # the kernel's scratch (acc (g*K, d) + m/l (g*K, lanes), all f32)
        # scales linearly with the query rows — past ~1k rows it crowds
        # VMEM (serve/engine.py clamps its chunk width below this)
        use = _pallas_unsupported(
            "flash_decode_multi", impl,
            f"{(h // kh) * kq} query rows per kv head exceed the 1024 the "
            "kernel's VMEM scratch holds")
    if use == "xla":
        return paged_attention_multi_reference(
            q, k_pages, v_pages, block_tables, lengths,
            scale=scale, window=window)

    g = h // kh
    # rows are (group, query) flattened with the query index MINOR — the
    # kernel recovers it as row % K
    qg = q.reshape(b, kh, g * kq, d)
    tables = block_tables.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kh, nb),
        in_specs=[
            pl.BlockSpec((1, 1, g * kq, d),
                         lambda bi, ki, j, tbl, ln: (bi, ki, 0, 0)),
            pl.BlockSpec((1, 1, blk, d),
                         lambda bi, ki, j, tbl, ln: (tbl[bi, j], ki, 0, 0)),
            pl.BlockSpec((1, 1, blk, d),
                         lambda bi, ki, j, tbl, ln: (tbl[bi, j], ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g * kq, d),
                               lambda bi, ki, j, tbl, ln: (bi, ki, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g * kq, d), jnp.float32),
            pltpu.VMEM((g * kq, _NUM_LANES), jnp.float32),
            pltpu.VMEM((g * kq, _NUM_LANES), jnp.float32),
        ],
    )
    import functools

    out = pl.pallas_call(
        functools.partial(_decode_multi_kernel, scale=scale, blk=blk, nb=nb,
                          window=None if window is None else int(window),
                          kq=kq),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g * kq, d), q.dtype),
        interpret=_interpret(),
    )(tables, lens, qg, k_pages, v_pages)
    return out.reshape(b, h, kq, d)

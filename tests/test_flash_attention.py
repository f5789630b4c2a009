"""Flash attention vs unfused reference — forward and gradients.

Reference test pattern: tests/L0/run_transformer/test_fused_softmax.py
(fused vs torch softmax equivalence) extended to full attention, covering
the surface of fmhalib/fast_multihead_attn (causal, additive mask,
cross-attention kv length, bf16).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import flash_attention as fa
from apex_tpu.ops.flash_attention import (
    flash_attention,
    flash_tile_plan,
    mha_reference,
)

B, H, SQ, D = 2, 4, 128, 32


def _qkv(key, sq=SQ, sk=SQ, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, sq, D), dtype)
    k = jax.random.normal(kk, (B, H, sk, D), dtype)
    v = jax.random.normal(kv, (B, H, sk, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    out = flash_attention(q, k, v, causal=causal, impl="pallas")
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(1), sq=64, sk=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, impl="pallas",
                                       block_q=16, block_k=16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_additive_bias_mask():
    q, k, v = _qkv(jax.random.PRNGKey(2))
    # padding mask: last 32 keys masked for batch element 1 (b,1,1→sq,sk bias)
    bias = jnp.zeros((B, 1, SQ, SQ))
    bias = bias.at[1, :, :, -32:].set(-10000.0)
    out = flash_attention(q, k, v, bias, impl="pallas")
    ref = mha_reference(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    g = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, bias, impl="pallas")))(q)
    gr = jax.grad(lambda q: jnp.sum(mha_reference(q, k, v, bias)))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=1e-4, atol=1e-4)


def test_bias_gradient_matches_reference():
    q, k, v = _qkv(jax.random.PRNGKey(8), sq=64, sk=64)
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (B, H, 64, 64))

    gf = jax.grad(lambda b_: jnp.sum(
        flash_attention(q, k, v, b_, impl="pallas", block_q=16, block_k=16) ** 2))(bias)
    gr = jax.grad(lambda b_: jnp.sum(mha_reference(q, k, v, b_) ** 2))(bias)
    assert float(jnp.max(jnp.abs(gr))) > 1e-3  # reference grad is nonzero
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), rtol=1e-4, atol=1e-4)


def test_broadcast_bias_gradient():
    """ALiBi/T5-style bias broadcast over batch (1,h,sq,sk) and the key-padding
    shape (b,1,1,sk) must both work and receive summed gradients."""
    q, k, v = _qkv(jax.random.PRNGKey(10), sq=32, sk=32)
    for shape in [(1, H, 32, 32), (B, 1, 1, 32), (1, 1, 32, 32)]:
        bias = 0.1 * jax.random.normal(jax.random.PRNGKey(11), shape)
        out = flash_attention(q, k, v, bias, impl="pallas", block_q=8, block_k=8)
        ref = mha_reference(q, k, v, bias)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=str(shape))
        gf = jax.grad(lambda b_: jnp.sum(
            flash_attention(q, k, v, b_, impl="pallas", block_q=8, block_k=8) ** 2))(bias)
        gr = jax.grad(lambda b_: jnp.sum(mha_reference(q, k, v, b_) ** 2))(bias)
        assert gf.shape == shape
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-4, atol=1e-4, err_msg=str(shape))


def test_causal_bias_gradient():
    q, k, v = _qkv(jax.random.PRNGKey(12), sq=64, sk=64)
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(13), (1, H, 64, 64))
    gf = jax.grad(lambda b_: jnp.sum(
        flash_attention(q, k, v, b_, causal=True, impl="pallas",
                        block_q=16, block_k=16) ** 2))(bias)
    gr = jax.grad(lambda b_: jnp.sum(mha_reference(q, k, v, b_, causal=True) ** 2))(bias)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), rtol=1e-4, atol=1e-4)


def test_cross_attention_kv_longer():
    q, k, v = _qkv(jax.random.PRNGKey(3), sq=32, sk=128)
    out = flash_attention(q, k, v, impl="pallas", block_q=16, block_k=32)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_bf16_tolerance():
    q, k, v = _qkv(jax.random.PRNGKey(4), dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, impl="pallas")
    ref = mha_reference(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=3e-2, atol=3e-2
    )


def test_unaligned_falls_back_to_xla():
    q, k, v = _qkv(jax.random.PRNGKey(5), sq=30, sk=30)
    out = flash_attention(q, k, v, impl="auto")  # 30 % 8 != 0 → xla path
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_fused_scale_mask_softmax_module():
    from apex_tpu.transformer.functional import AttnMaskType, FusedScaleMaskSoftmax

    x = jax.random.normal(jax.random.PRNGKey(6), (2, 4, 16, 16), jnp.bfloat16)
    mask = jax.random.bernoulli(jax.random.PRNGKey(7), 0.3, (2, 1, 16, 16))
    sm = FusedScaleMaskSoftmax(attn_mask_type=AttnMaskType.padding, scale=0.5)
    y = sm(x, mask)
    assert y.dtype == jnp.float32  # softmax_in_fp32 default
    ref = FusedScaleMaskSoftmax(attn_mask_type=AttnMaskType.padding, scale=0.5,
                                fused=False)(x, mask)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=2e-2, atol=2e-2)

    causal = FusedScaleMaskSoftmax(attn_mask_type=AttnMaskType.causal,
                                   softmax_in_fp32=False)
    yc = causal(x)
    assert yc.dtype == jnp.bfloat16
    # each row sums to 1 and is upper-triangular-masked
    s = np.asarray(yc, np.float32).sum(-1)
    np.testing.assert_allclose(s, np.ones_like(s), rtol=2e-2)
    assert np.asarray(yc, np.float32)[0, 0, 0, 1:].max() == 0.0

    # causal + padding mask composed in one fused pass
    both = FusedScaleMaskSoftmax(attn_mask_type=AttnMaskType.causal)(x, mask)
    ref_both = FusedScaleMaskSoftmax(attn_mask_type=AttnMaskType.causal,
                                     fused=False)(x, mask)
    np.testing.assert_allclose(np.asarray(both), np.asarray(ref_both),
                               rtol=2e-2, atol=2e-2)

    # unaligned sk falls back to the unfused path instead of the kernel
    x_odd = jax.random.normal(jax.random.PRNGKey(8), (2, 2, 12, 30))
    y_odd = FusedScaleMaskSoftmax()(x_odd)
    ref_odd = FusedScaleMaskSoftmax(fused=False)(x_odd)
    np.testing.assert_allclose(np.asarray(y_odd), np.asarray(ref_odd), rtol=1e-5)


# ---------------------------------------------------------------------------
# Packed varlen (segment ids): the fmha cu_seqlens semantics computed
# natively by the kernel with block skipping (VERDICT r2 missing #2).
# ---------------------------------------------------------------------------


def _packed_case(key, lengths, h=4, d=32, dtype=jnp.float32):
    total = sum(lengths)
    qkv = jax.random.normal(key, (total, 3, h, d), dtype)
    cu = jnp.asarray(np.cumsum([0] + list(lengths)), jnp.int32)
    return qkv, cu


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("lengths", [[128, 64, 192, 128], [512], [8, 8, 496]])
def test_fmha_packed_matches_reference(causal, lengths):
    from apex_tpu.contrib.fmha import fmha, fmha_reference

    qkv, cu = _packed_case(jax.random.PRNGKey(0), lengths)
    out = fmha(qkv, cu, max_seqlen=512, causal=causal)
    ref = fmha_reference(qkv, cu, causal=causal)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_fmha_trailing_padding_rows_are_zero():
    """Tokens past cu_seqlens[-1] are padding: output exactly 0."""
    from apex_tpu.contrib.fmha import fmha

    qkv = jax.random.normal(jax.random.PRNGKey(0), (256, 3, 4, 32))
    cu = jnp.asarray([0, 100, 180], jnp.int32)  # 76 trailing pad tokens
    out = fmha(qkv, cu, max_seqlen=512)
    np.testing.assert_array_equal(np.asarray(out[180:]), 0.0)


def test_fmha_gradients_match_padded_reference():
    """Grads through the packed kernel == per-sequence dense grads."""
    from apex_tpu.contrib.fmha import fmha

    lengths = [128, 256, 128]
    qkv, cu = _packed_case(jax.random.PRNGKey(1), lengths)
    w = jax.random.normal(jax.random.PRNGKey(2), (sum(lengths), 4, 32))

    def packed_loss(qkv):
        return jnp.sum(fmha(qkv, cu, max_seqlen=512, causal=True) * w)

    def dense_loss(qkv):
        total = 0.0
        for i in range(len(lengths)):
            s, e = int(cu[i]), int(cu[i + 1])
            q, k, v = (qkv[s:e, j].transpose(1, 0, 2)[None] for j in range(3))
            o = mha_reference(q, k, v, causal=True)
            total = total + jnp.sum(o[0].transpose(1, 0, 2) * w[s:e])
        return total

    g_packed = jax.grad(packed_loss)(qkv)
    g_dense = jax.grad(dense_loss)(qkv)
    np.testing.assert_allclose(np.asarray(g_packed), np.asarray(g_dense),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_segment_ids_pallas_matches_xla(causal):
    """Direct segment-ids surface: kernel (with block skip) vs XLA mask."""
    q, k, v = _qkv(jax.random.PRNGKey(3), sq=256, sk=256)
    seg = jnp.asarray(
        np.repeat([1, 2, 3, 9], [64, 96, 64, 32])[None].repeat(B, 0))
    out_p = flash_attention(q, k, v, segment_ids=(seg, seg), pad_id=9,
                            causal=causal, impl="pallas")
    out_x = flash_attention(q, k, v, segment_ids=(seg, seg), pad_id=9,
                            causal=causal, impl="xla")
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                               rtol=2e-5, atol=2e-5)


def test_segment_block_skip_equals_mask_only():
    """contiguous_segments=True (block skipping) computes the same function
    as mask-only evaluation — skipped blocks really were all-masked."""
    q, k, v = _qkv(jax.random.PRNGKey(4), sq=512, sk=512)
    seg = jnp.asarray(
        np.repeat([1, 2, 3], [128, 256, 128])[None].repeat(B, 0))
    out_skip = flash_attention(q, k, v, segment_ids=(seg, seg),
                               contiguous_segments=True, impl="pallas")
    out_mask = flash_attention(q, k, v, segment_ids=(seg, seg),
                               contiguous_segments=False, impl="pallas")
    np.testing.assert_allclose(np.asarray(out_skip), np.asarray(out_mask),
                               rtol=1e-6, atol=1e-6)


# -- streamed kernels (block-bounded VMEM, VERDICT r3 ask #3) ----------------


@pytest.mark.parametrize("causal", [False, True])
def test_streamed_matches_resident(causal):
    """stream='always' (K/V loop in the grid, scratch accumulators) computes
    the same function — values AND grads — as the resident layout."""
    q, k, v = _qkv(jax.random.PRNGKey(5), sq=256, sk=256)
    kw = dict(causal=causal, impl="pallas", block_q=64, block_k=64)
    out_s = flash_attention(q, k, v, stream="always", **kw)
    out_r = flash_attention(q, k, v, stream="never", **kw)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)

    def loss(mode):
        return lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, stream=mode, **kw) ** 2)

    gs = jax.grad(loss("always"), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss("never"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gs, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("contiguous", [False, True])
def test_streamed_segments_match_xla(contiguous):
    """Streamed segment path (ids + metadata arriving blockwise) vs the XLA
    mask, with padding and causal, fwd + grads."""
    q, k, v = _qkv(jax.random.PRNGKey(6), sq=256, sk=256)
    seg = jnp.asarray(
        np.repeat([1, 2, 3, 9], [64, 96, 64, 32])[None].repeat(B, 0))
    kw = dict(segment_ids=(seg, seg), pad_id=9, causal=True)
    out_s = flash_attention(q, k, v, stream="always", impl="pallas",
                            block_q=64, block_k=128,
                            contiguous_segments=contiguous, **kw)
    out_x = flash_attention(q, k, v, impl="xla", **kw)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_x),
                               rtol=2e-5, atol=2e-5)
    gs = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, stream="always", impl="pallas", block_q=64, block_k=128,
        contiguous_segments=contiguous, **kw) ** 2))(q)
    gx = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, impl="xla", **kw) ** 2))(q)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gx),
                               rtol=1e-4, atol=1e-4)


def test_streamed_ring_offsets_match_resident():
    """The ring-attention entry points (_flash_fwd/_flash_bwd with global
    position offsets) agree between streamed and resident layouts."""
    from apex_tpu.ops.flash_attention import _flash_bwd, _flash_fwd

    q, k, v = _qkv(jax.random.PRNGKey(7), sq=128, sk=128)
    offs = jnp.asarray([256, 128], jnp.int32)  # q shard after k shard
    kw = dict(scale=D ** -0.5, causal=True, blk_q=64, blk_k=64)
    o_s, lse_s = _flash_fwd(q, k, v, None, offs, stream=True, **kw)
    o_r, lse_r = _flash_fwd(q, k, v, None, offs, stream=False, **kw)
    np.testing.assert_allclose(np.asarray(o_s), np.asarray(o_r),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(lse_s), np.asarray(lse_r),
                               rtol=1e-6, atol=1e-6)
    do = jax.random.normal(jax.random.PRNGKey(8), q.shape, q.dtype)
    g_s = _flash_bwd(q, k, v, None, offs, o_s, lse_s, do, stream=True, **kw)
    g_r = _flash_bwd(q, k, v, None, offs, o_r, lse_r, do, stream=False, **kw)
    for a, b in zip(g_s[:3], g_r[:3]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_stream_auto_threshold():
    """'auto' stays resident at model shapes and switches to streamed when
    the resident residency estimate crosses the VMEM budget (the s≈8k
    segment configs that hit the 16 MB wall in r3)."""
    from apex_tpu.ops.flash_attention import (
        _RESIDENT_VMEM_BUDGET,
        _resident_vmem_bytes,
    )

    small = _resident_vmem_bytes(1024, 1024, 64, 1024, 1024, 2, False, False)
    assert small <= _RESIDENT_VMEM_BUDGET
    # packed fmha at realistic total token counts (ADVICE r3 medium):
    # 32k packed tokens with segment operands must stream
    packed = _resident_vmem_bytes(32768, 32768, 64, 1024, 1024, 2, False, True)
    assert packed > _RESIDENT_VMEM_BUDGET
    # long-context causal at 8k with segments (r3's VMEM-wall case)
    long_seg = _resident_vmem_bytes(8192, 8192, 64, 1024, 1024, 2, False, True)
    assert long_seg > _RESIDENT_VMEM_BUDGET
    # LANE PADDING must be counted: at d=32, s=8192 the resident dK/dV
    # pass allocates 17.3 MB on TPU (minor dims pad to 128 lanes; the
    # (sq, 1) lse/delta windows cost sq*128*4 each) though the unpadded
    # arithmetic says 1.6 MB — the un-streamable config that failed to
    # compile live in r4. Must stream.
    d32 = _resident_vmem_bytes(8192, 8192, 32, 1024, 1024, 2, False, False)
    assert d32 > _RESIDENT_VMEM_BUDGET
    # and the padding floor must not push model shapes (1k-2k, d=64) off
    # the measured-faster resident path
    assert _resident_vmem_bytes(
        2048, 2048, 64, 1024, 1024, 2, False, False) <= _RESIDENT_VMEM_BUDGET


def test_fully_masked_causal_segment_row_is_zero_both_impls():
    """ADVICE r3 low #2: a row whose same-segment keys all sit ABOVE the
    causal diagonal is fully masked only once the causal mask is applied;
    kernel and XLA fallback must agree it outputs exactly 0."""
    sq = sk = 128
    q, k, v = _qkv(jax.random.PRNGKey(9), sq=sq, sk=sk)
    # q position 0 belongs to segment 2, but all segment-2 keys live in the
    # upper half of the sequence (causally invisible from position 0)
    q_seg = jnp.asarray(np.r_[[2], np.ones(sq - 1, int)][None].repeat(B, 0))
    kv_seg = jnp.asarray(np.repeat([1, 2], [64, 64])[None].repeat(B, 0))
    for impl in ("pallas", "xla"):
        out = flash_attention(q, k, v, segment_ids=(q_seg, kv_seg),
                              causal=True, impl=impl,
                              contiguous_segments=False)
        np.testing.assert_array_equal(
            np.asarray(out[:, :, 0, :]), 0.0,
            err_msg=f"{impl}: causally-fully-masked row must be zero")


def test_segment_bounds_cover_exact_blocks():
    """The precomputed block ranges are tight: for blk=128 segments aligned
    to block boundaries, each q block's [start, end) spans exactly its own
    segment's k blocks."""
    from apex_tpu.ops.flash_attention import _seg_metadata

    seg = jnp.asarray(np.repeat([1, 2, 2, 3], 128)[None])  # (1, 512)
    bq, bk, _, _ = _seg_metadata(seg, seg, 128, 128)
    np.testing.assert_array_equal(np.asarray(bq[0, 0]), [0, 1, 1, 3])
    np.testing.assert_array_equal(np.asarray(bq[0, 1]), [1, 3, 3, 4])
    np.testing.assert_array_equal(np.asarray(bk[0, 0]), [0, 1, 1, 3])
    np.testing.assert_array_equal(np.asarray(bk[0, 1]), [1, 3, 3, 4])


# -- sliding-window (local) attention (beyond-reference capability) ----------


def _window_bias(sq, sk, window, causal):
    """Explicit additive mask implementing the window semantics, for
    checking mha_reference's window path independently."""
    q_pos = np.arange(sq)[:, None]
    k_pos = np.arange(sk)[None, :]
    bad = (q_pos - k_pos) >= window
    if causal:
        bad |= k_pos > q_pos
    else:
        bad |= (k_pos - q_pos) >= window
    return jnp.asarray(np.where(bad, -1e30, 0.0)[None, None])


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("window", [16, 24, 100])
def test_window_reference_matches_explicit_mask(causal, window):
    """mha_reference's window path equals dense attention under the
    equivalent explicit mask (window 24 is not a block multiple; 100
    covers most of the 128-seq band)."""
    q, k, v = _qkv(jax.random.PRNGKey(20))
    got = mha_reference(q, k, v, causal=causal, window=window)
    want = mha_reference(q, k, v, _window_bias(SQ, SQ, window, causal),
                         causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("window", [16, 24, 100])
def test_window_pallas_matches_xla(causal, window):
    """Kernel window path (with block-range skipping at block_q/k=16, so
    the clip bounds are exercised hard) vs the XLA window path — values
    and all three input gradients."""
    q, k, v = _qkv(jax.random.PRNGKey(21), sq=64, sk=64)
    kw = dict(causal=causal, window=window)

    out_p = flash_attention(q, k, v, impl="pallas", block_q=16, block_k=16,
                            **kw)
    out_x = flash_attention(q, k, v, impl="xla", **kw)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                               rtol=2e-5, atol=2e-5)

    gp = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, impl="pallas", block_q=16, block_k=16, **kw) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, impl="xla", **kw) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_window_streamed_matches_resident(causal):
    """Streamed kernels (grid-level pl.when skip) compute the same window
    function as the resident layout — values and grads."""
    q, k, v = _qkv(jax.random.PRNGKey(22), sq=256, sk=256)
    kw = dict(causal=causal, window=48, impl="pallas", block_q=64,
              block_k=64)
    out_s = flash_attention(q, k, v, stream="always", **kw)
    out_r = flash_attention(q, k, v, stream="never", **kw)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)
    gs = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, stream="always", **kw) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, stream="never", **kw) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gs, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_window_composes_with_segments():
    """Window + packed segment ids: both masks apply (a query sees only
    same-segment keys inside its window), kernel vs XLA."""
    q, k, v = _qkv(jax.random.PRNGKey(23), sq=256, sk=256)
    seg = jnp.asarray(
        np.repeat([1, 2, 3, 9], [64, 96, 64, 32])[None].repeat(B, 0))
    kw = dict(segment_ids=(seg, seg), pad_id=9, causal=True, window=40)
    out_p = flash_attention(q, k, v, impl="pallas",
                            contiguous_segments=True, **kw)
    out_x = flash_attention(q, k, v, impl="xla", **kw)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                               rtol=2e-5, atol=2e-5)


def test_window_covering_everything_is_dense():
    """window >= seq is dense attention (and takes the no-window kernel)."""
    q, k, v = _qkv(jax.random.PRNGKey(24), sq=64, sk=64)
    out_w = flash_attention(q, k, v, causal=True, window=64, impl="pallas",
                            block_q=16, block_k=16)
    out_d = flash_attention(q, k, v, causal=True, impl="pallas",
                            block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out_w), np.asarray(out_d),
                               rtol=0, atol=0)


def test_window_validation():
    q, k, v = _qkv(jax.random.PRNGKey(25), sq=64, sk=64)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, window=0)


def test_window_ring_offsets_match_global():
    """Window masking uses GLOBAL positions: running the kernels shard-wise
    with ring offsets reproduces the corresponding block of full-sequence
    window attention (the context-parallel contract)."""
    from apex_tpu.ops.flash_attention import _flash_fwd

    sq = 128
    q, k, v = _qkv(jax.random.PRNGKey(26), sq=2 * sq, sk=2 * sq)
    want = mha_reference(q, k, v, causal=True, window=48)
    # shard 1's q block against shard 0's k block plus its own: two ring
    # steps of a cp=2 ring (q_off = sq; k_off = 0 then sq)
    kw = dict(scale=D ** -0.5, causal=True, blk_q=64, blk_k=64, window=48)
    q1 = q[:, :, sq:]
    o_parts = []
    lse_parts = []
    for k_off, ks in ((0, slice(0, sq)), (sq, slice(sq, 2 * sq))):
        offs = jnp.asarray([sq, k_off], jnp.int32)
        o_s, lse_s = _flash_fwd(q1, k[:, :, ks], v[:, :, ks], None, offs,
                                **kw)
        o_parts.append(o_s)
        lse_parts.append(lse_s)
    # online-softmax merge of the two ring steps (what ring.py does)
    m = jnp.maximum(lse_parts[0], lse_parts[1])
    w0 = jnp.exp(lse_parts[0] - m)
    w1 = jnp.exp(lse_parts[1] - m)
    merged = (o_parts[0] * w0 + o_parts[1] * w1) / (w0 + w1)
    np.testing.assert_allclose(np.asarray(merged),
                               np.asarray(want[:, :, sq:]),
                               rtol=2e-5, atol=2e-5)


def test_window_cross_shape_fully_masked_rows_zero_both_impls():
    """Cross-attention (sq != sk) with a window: queries whose whole band
    lies beyond the key sequence are fully masked and must output exactly
    0 on BOTH impls (the XLA path's zeroing is gated on `masked`, which
    must include the window case — r5 review finding)."""
    q, k, v = _qkv(jax.random.PRNGKey(27), sq=128, sk=32)
    for impl in ("pallas", "xla"):
        out = flash_attention(q, k, v, causal=True, window=16, impl=impl,
                              block_q=16, block_k=16)
        # rows p >= sk + window - 1 = 47 see no keys at all
        np.testing.assert_array_equal(
            np.asarray(out[:, :, 48:, :]), 0.0,
            err_msg=f"{impl}: window-fully-masked rows must be zero")
    out_p = flash_attention(q, k, v, causal=True, window=16, impl="pallas",
                            block_q=16, block_k=16)
    out_x = flash_attention(q, k, v, causal=True, window=16, impl="xla")
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_window_restricted_streamed_grid(causal):
    """The window-RESTRICTED streamed grid (inner extent < nk, trips
    remapped via _window_grid) — both causal and bidirectional branches
    must be live (sq=512, blk=64, window=16 -> width 3 of nk=8) and match
    the resident layout, values and grads."""
    from apex_tpu.ops.flash_attention import _window_grid

    assert _window_grid(64, 64, 8, causal, 16) is not None
    q, k, v = _qkv(jax.random.PRNGKey(28), sq=512, sk=512)
    kw = dict(causal=causal, window=16, impl="pallas", block_q=64,
              block_k=64)
    out_s = flash_attention(q, k, v, stream="always", **kw)
    out_r = flash_attention(q, k, v, stream="never", **kw)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)
    gs = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, stream="always", **kw) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, stream="never", **kw) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gs, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("contiguous", [False, True])
def test_window_restricted_grid_with_segments(contiguous):
    """Restricted windowed grid + segment ids: the remapped kmap/qmap
    BlockSpecs must fetch the RIGHT id blocks and metadata (sq=512,
    window=32, blk 64/128 -> restricted), kernel vs XLA, fwd + grads.

    Grads over argnums=(0, 1, 2): dq exercises the remapped dQ pass, but
    dk/dv come from the SEPARATE streamed dK/dV pass, whose qmap remap
    (which q trips each k block sees under the window restriction) the
    dq assertion cannot catch (ADVICE finding: a qmap-remap bug slipped
    through while only dq was value-asserted)."""
    from apex_tpu.ops.flash_attention import _window_grid

    assert _window_grid(64, 128, 4, True, 32) is not None
    q, k, v = _qkv(jax.random.PRNGKey(29), sq=512, sk=512)
    seg = jnp.asarray(
        np.repeat([1, 2, 3, 9], [128, 192, 128, 64])[None].repeat(B, 0))
    kw = dict(segment_ids=(seg, seg), pad_id=9, causal=True, window=32)
    out_s = flash_attention(q, k, v, stream="always", impl="pallas",
                            block_q=64, block_k=128,
                            contiguous_segments=contiguous, **kw)
    out_x = flash_attention(q, k, v, impl="xla", **kw)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_x),
                               rtol=2e-5, atol=2e-5)
    gs = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, stream="always", impl="pallas", block_q=64, block_k=128,
        contiguous_segments=contiguous, **kw) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, impl="xla", **kw) ** 2), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), gs, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"{name} mismatch")


def test_stream_auto_crossover_at_4k():
    """'auto' streams at s >= 4096 even though the resident layout now
    COMPILES there (dense lse tables removed its VMEM wall): measured
    on-chip, resident dK/dV falls behind streamed past ~2k (27.4 vs
    17.7 ms at 4096 d=64) because it re-streams whole-sq q/do per k
    block. Asserted on the shared decision helper (jit-cache-proof)."""
    from apex_tpu.ops.flash_attention import _auto_stream

    wall, crossover = _auto_stream(4096, 4096, 64, 1024, 1024, 2,
                                   False, False)
    assert crossover and not wall  # streams on throughput, not memory
    wall, crossover = _auto_stream(2048, 2048, 64, 1024, 1024, 2,
                                   False, False)
    assert not crossover and not wall  # model shapes stay resident


def test_stream_auto_crossover_scales_with_row_bytes():
    """The crossover was MEASURED at d=64 bf16; the resident dK/dV DMA
    bill moves LANE-PADDED rows (minor dim pads to 128 lanes — the same
    rule _resident_vmem_bytes counts), so every d <= 128 bf16 shares the
    measured 4096 boundary, and the boundary halves only when the padded
    row actually doubles: fp32 itemsize, or d > 128 (ADVICE finding: the
    scaling must be documented against its d=64 measurement basis, not
    guessed from unpadded arithmetic)."""
    from apex_tpu.ops.flash_attention import _auto_stream

    # the whole d=32..128 bf16 family DMAs identical 256 B padded rows:
    # one measured boundary, 4096
    for d in (32, 64, 128):
        _, crossover = _auto_stream(2048, 2048, d, 1024, 1024, 2,
                                    False, False)
        assert not crossover, d
        _, crossover = _auto_stream(4096, 4096, d, 1024, 1024, 2,
                                    False, False)
        assert crossover, d
    # fp32 doubles the padded row -> boundary halves to 2048
    _, crossover = _auto_stream(2048, 2048, 64, 1024, 1024, 4,
                                False, False)
    assert crossover
    _, crossover = _auto_stream(1024, 1024, 64, 1024, 1024, 4,
                                False, False)
    assert not crossover
    # d=256 bf16: two padded lanes-groups per row -> 2048 as well
    _, crossover = _auto_stream(2048, 2048, 256, 1024, 1024, 2,
                                False, False)
    assert crossover


def test_bias_past_crossover_keeps_resident_kernel(monkeypatch):
    """Dense bias + the >= 4k crossover: the streamed path has no dbias
    pass, but the resident kernel COMPILES there (no VMEM wall) and
    beats dense XLA attention — auto must keep it rather than fall back
    to mha_reference (r5 review finding)."""
    from apex_tpu.ops.flash_attention import _auto_stream

    # blk_q=128 keeps the resident bias window small: crossover fires
    # but the wall does NOT — the branch under test
    wall, crossover = _auto_stream(4096, 4096, D, 128, 128, 2, True, False)
    assert crossover and not wall
    q, k, v = _qkv(jax.random.PRNGKey(31), sq=4096, sk=4096,
                   dtype=jnp.bfloat16)
    bias = jnp.zeros((B, 1, 4096, 4096))
    bias = bias.at[1, :, :, -64:].set(-10000.0)
    ref = mha_reference(q, k, v, bias, causal=True)
    # the oracle below compares against mha_reference, so an XLA-fallback
    # regression would pass trivially — assert the dispatch itself: the
    # fallback must NOT run inside this flash_attention call
    import apex_tpu.ops.flash_attention as fa

    def no_fallback(*a, **kw):
        raise AssertionError(
            "crossover-only bias case fell back to mha_reference")

    monkeypatch.setattr(fa, "mha_reference", no_fallback)
    out = fa.flash_attention(q, k, v, bias, causal=True, impl="pallas",
                             block_q=128, block_k=128)
    monkeypatch.undo()
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# The tile plan derived from the shape (PR 27): causal attention at GPT's
# 1024 tokens is computed as a triangle of tiles, walked with static bounds
# and masked only where the diagonal or the window's edge crosses a tile.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case,args,kw,want", [
    # the GPT cell: 2 x 16 heads of 1024 x 1024 causal
    ("gpt cell", (1024, 1024, True), {}, dict(static=True, most=0.75)),
    # the BERT cell: one 512 x 512 tile under segment ids, nothing to skip
    ("bert cell", (512, 512, False), dict(has_segments=True),
     dict(blk=(512, 512), static=True, share=1.0)),
    ("explicit blocks win", (1024, 1024, True),
     dict(block_q=1024, block_k=1024),
     dict(blk=(1024, 1024), static=True, share=1.0)),
    ("one explicit edge, the other derived", (1024, 1024, True),
     dict(block_q=256), dict(blk=(256, 512), static=True, share=0.75)),
    ("unmasked keeps one largest tile", (1024, 1024, False), {},
     dict(blk=(1024, 1024), static=True, share=1.0)),
    ("window counts the band", (1024, 1024, True, 256), {},
     dict(blk=(512, 512), static=True, share=0.75)),
    # traced bounds keep the dynamic loop and the largest edge
    ("a band both ways", (1024, 1024, False, 200), {},
     dict(blk=(512, 512), static=True, share=1.0)),
    ("contiguous segments", (1024, 1024, True),
     dict(has_segments=True, contiguous_segments=True),
     dict(blk=(1024, 1024), static=False)),
    # too many tiles to unroll: the dynamic loop at the largest edge
    ("long causal", (8192, 8192, True), {},
     dict(blk=(1024, 1024), static=False, share=36 / 64)),
    # two unrolled 1024 tiles a program do not fit VMEM
    ("2048 unmasked", (2048, 2048, False), {},
     dict(blk=(1024, 1024), static=False, share=1.0)),
    ("2048 causal", (2048, 2048, True), {},
     dict(blk=(512, 512), static=True, share=10 / 16)),
])
def test_flash_tile_plan(case, args, kw, want):
    plan = flash_tile_plan(*args, **kw)
    if "blk" in want:
        assert (plan.blk_q, plan.blk_k) == want["blk"], plan
    assert plan.static == want["static"], plan
    if "share" in want:
        assert plan.share == pytest.approx(want["share"]), plan
    if "most" in want:
        assert plan.share <= want["most"] and plan.blk_q < 1024, plan


@pytest.mark.parametrize("sq,sk,blk_q,blk_k,causal,window", [
    (1024, 1024, 512, 512, True, None),
    (1024, 1024, 256, 256, True, None),
    (512, 1024, 128, 256, True, None),
    (1024, 1024, 256, 256, True, 300),
    (1024, 1024, 256, 128, False, 200),
    (1024, 512, 256, 128, True, 129),
])
def test_tile_kinds_agree_with_the_dense_mask(sq, sk, blk_q, blk_k, causal,
                                              window):
    """A tile is skipped only if every score in it is masked, and runs
    without mask arithmetic only if none is."""
    dense = np.asarray(fa._dense_pos_masks(
        jnp.zeros((sq, sk)), jnp.arange(sq)[:, None], jnp.arange(sk)[None, :],
        causal, window)) < 0
    tiles = dense.reshape(sq // blk_q, blk_q, sk // blk_k, blk_k)
    kinds = fa._tile_kinds(sq, sk, blk_q, blk_k, causal, window)
    np.testing.assert_array_equal(kinds == 0, tiles.all(axis=(1, 3)))
    np.testing.assert_array_equal(kinds == 2, ~tiles.any(axis=(1, 3)))
    by_q, by_k = fa._static_rows(sq, sk, blk_q, blk_k, causal, window)
    if by_q is not None:
        walked = {(i, j, m) for i, r in enumerate(by_q) for j, m in r}
        assert walked == {(i, j, m) for j, r in enumerate(by_k)
                          for i, m in r}
        assert walked == {(i, j, kinds[i, j] == 1)
                          for i, j in zip(*np.nonzero(kinds))}


def _cell_qkv(dtype, s=1024, d=64, h=2):
    ks = jax.random.split(jax.random.PRNGKey(27), 4)
    q, k, v, w = (jax.random.normal(kk, (1, h, s, d), jnp.float32)
                  for kk in ks)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), w


def _out_and_grads(fn, q, k, v, w, *extra):
    """Output and gradients of ``sum(fn(...) * w)`` in q, k, v, extras."""
    def loss(*a):
        o = fn(*a)
        return jnp.sum(o.astype(jnp.float32) * w), o

    (_, o), g = jax.value_and_grad(
        loss, argnums=tuple(range(3 + len(extra))), has_aux=True)(
            q, k, v, *extra)
    return (o,) + g


@pytest.mark.parametrize("dtype,tol_o,tol_g", [
    (jnp.float32, 2e-5, 1e-4), (jnp.bfloat16, 3e-2, 3e-2)])
def test_derived_plan_matches_reference_at_gpt_shape(dtype, tol_o, tol_g):
    """Causal s=1024, d=64 through the derived edge (a static triangle of
    tiles): float32 inputs keep float32 operands and the file's float32
    tolerances; bf16 inputs feed the MXU bf16 and hold its 3e-2."""
    q, k, v, w = _cell_qkv(dtype)
    assert flash_tile_plan(1024, 1024, True).share <= 0.75
    got = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=True, impl="pallas"),
        q, k, v, w)
    ref = _out_and_grads(
        lambda q, k, v: mha_reference(q, k, v, causal=True), q, k, v, w)
    assert got[0].dtype == dtype
    for a, b, tol in zip(got, ref, (tol_o, tol_g, tol_g, tol_g)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol)


def test_derived_plan_equals_one_tile_plan():
    """Skipping a tile whose every score is masked changes nothing: the
    triangle of 512-tiles gives what one masked 1024-tile gives."""
    q, k, v, w = _cell_qkv(jnp.float32, h=1)
    derived = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=True, impl="pallas"),
        q, k, v, w)
    one = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=True, impl="pallas",
                                        block_q=1024, block_k=1024),
        q, k, v, w)
    for a, b in zip(derived, one):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", ["window", "bias"])
def test_derived_plan_masks_only_where_an_edge_crosses(case):
    """Causal + window and causal + dense bias through the derived plan:
    tiles off the diagonal and inside the band run with no mask arithmetic,
    tiles outside the band are skipped, and the result is the reference's."""
    q, k, v, w = _cell_qkv(jnp.float32, h=1)
    if case == "window":
        kinds = fa._tile_kinds(1024, 1024, 512, 512, True, 300)
        assert sorted(kinds.ravel()) == [0, 1, 1, 1]  # band crosses (1, 0)
        kw, extra = dict(causal=True, window=300), ()
    else:
        bias = 0.5 * jax.random.normal(jax.random.PRNGKey(3),
                                       (1, 1, 1024, 1024), jnp.float32)
        kw, extra = dict(causal=True), (bias,)
    got = _out_and_grads(
        lambda q, k, v, *b: flash_attention(q, k, v, *b, impl="pallas",
                                            **kw), q, k, v, w, *extra)
    ref = _out_and_grads(
        lambda q, k, v, *b: mha_reference(q, k, v, *b, **kw),
        q, k, v, w, *extra)
    for a, b, tol in zip(got, ref, (2e-5,) + (1e-4,) * 4):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=tol, atol=tol)


# -- grouped-query heads ------------------------------------------------------

def _dense_softmax(q, k, v, causal):
    """Float32 softmax(Q K^T) V with each key-value head repeated for its
    group of query heads: what the kernels read through their index maps."""
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision="highest") * q.shape[-1] ** -0.5
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v,
                      precision="highest")


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("kv_heads", [2, 8], ids=["4to1", "1to1"])
@pytest.mark.parametrize("stream", ["never", "always"],
                         ids=["resident", "streamed"])
def test_grouped_query_heads_match_a_dense_softmax(stream, kv_heads, causal):
    """Forward, dQ, dK and dV with 8 query heads over 2 key-value heads (and
    over 8, the kernels' old case) against a dense float32 softmax. K and V
    keep their own heads, and so do their gradients, summed over each
    group."""
    b, h, s, d = 2, 8, 256, 16
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(keys[0], (b, h, s, d))
    k = jax.random.normal(keys[1], (b, kv_heads, s, d))
    v = jax.random.normal(keys[2], (b, kv_heads, s, d))
    w = jax.random.normal(keys[3], (b, h, s, d))
    kernel = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, impl="pallas", stream=stream, block_q=128,
        block_k=128)
    np.testing.assert_allclose(np.asarray(kernel(q, k, v)),
                               np.asarray(_dense_softmax(q, k, v, causal)),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense_softmax(*a, causal) * w),
                    (0, 1, 2))(q, k, v)
    assert got[1].shape == k.shape and got[2].shape == v.shape
    for a, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


def test_grouped_query_heads_on_the_dense_path_and_refused_shapes():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 32, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 32, 8))
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, k, causal=True, impl="xla")),
        np.asarray(_dense_softmax(q, k, k, True)), rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="divide the query heads"):
        flash_attention(q, k[:, :1].repeat(3, 1), k[:, :1].repeat(3, 1))
    with pytest.raises(ValueError, match="must agree"):
        flash_attention(q, k, k[:, :1])


def _pallas_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_eqns(sub)


@pytest.mark.parametrize("stream", ["never", "always"],
                         ids=["resident", "streamed"])
def test_equal_heads_lower_as_before_and_grouped_heads_repeat_nothing(
        monkeypatch, stream):
    """With as many key-value heads as query heads every index map is the
    one it was (no division by the group's size: the kernels' serialized
    text is the old one but for line numbers) and the dK/dV grids keep
    their rank; with grouped heads K and V go into every call in their own
    shape, never repeated."""
    from apex_tpu.ops import layer_norm

    monkeypatch.setattr(layer_norm, "_on_tpu", lambda: True)

    def calls(kv_heads):
        q = jax.ShapeDtypeStruct((1, 8, 256, 64), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, kv_heads, 256, 64), jnp.bfloat16)
        f = lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, stream=stream, block_q=128,
            block_k=128).astype(jnp.float32))
        return list(_pallas_eqns(jax.make_jaxpr(
            jax.grad(f, (0, 1, 2)))(q, k, k).jaxpr))

    equal, grouped = calls(8), calls(2)
    assert len(equal) == len(grouped) == 3         # forward, dQ, dK/dV
    def prims(eqn, operand):
        """What the index map of ``operand`` (0 q, 1 k, 2 v) computes."""
        jaxpr = eqn.params["grid_mapping"].block_mappings[
            operand].index_map_jaxpr.jaxpr
        return [e.primitive.name for e in jaxpr.eqns]

    for eqn in equal:
        for operand in (0, 1, 2):
            assert not {"div", "mul"} & set(prims(eqn, operand))
    # (where a group shares a head the maps show it: the forward and the dQ
    # pass divide the query head for K and V, the dK/dV pass counts the
    # group's query heads out for q)
    assert [prims(e, 1) for e in grouped[:2]] == [["div"], ["div"]]
    assert prims(grouped[2], 1) == [] and "mul" in prims(grouped[2], 0)
    ranks = lambda eqns: [len(e.params["grid_mapping"].grid) for e in eqns]
    inner = 4 if stream == "always" else 3
    assert ranks(equal) == [inner] * 3
    assert ranks(grouped) == [inner, inner, inner + 1]
    for eqn in grouped:
        shapes = [v.aval.shape for v in eqn.invars]
        assert (1, 2, 256, 64) in shapes            # K and V as they came
        assert shapes.count((1, 8, 256, 64)) <= 2   # q and dO, nothing else


# -- values of a width of their own (latent attention: 192 | 128) -------------

def _dense_softmax_wide(q, k, v, causal, scale):
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v,
                      precision="highest")


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("kv_heads", [4, 2], ids=["1to1", "2to1"])
@pytest.mark.parametrize("stream", ["never", "always"],
                         ids=["resident", "streamed"])
def test_values_of_their_own_width_match_a_dense_softmax(stream, kv_heads,
                                                         causal):
    """Scores over 24 and values of 16 (192 and 128 in the Kimi Linear
    model's latent attention): forward, dQ, dK and dV of all four training
    kernels against a dense float32 softmax, and the lax path against it.
    The output and dV have the values' width, never a narrower score."""
    b, h, s, d, dv = 2, 4, 256, 24, 16
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    q = jax.random.normal(keys[0], (b, h, s, d))
    k = jax.random.normal(keys[1], (b, kv_heads, s, d))
    v = jax.random.normal(keys[2], (b, kv_heads, s, dv))
    w = jax.random.normal(keys[3], (b, h, s, dv))
    scale = d ** -0.5
    dense = lambda *a: _dense_softmax_wide(*a, causal, scale)
    for impl in ("pallas", "xla"):
        kernel = lambda q, k, v: flash_attention(
            q, k, v, causal=causal, impl=impl, stream=stream, block_q=128,
            block_k=128)
        out = kernel(q, k, v)
        assert out.shape == (b, h, s, dv)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(dense(q, k, v)),
                                   rtol=2e-5, atol=2e-5)
        got = jax.grad(lambda *a: jnp.sum(kernel(*a) * w), (0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
        assert [x.shape for x in got] == [q.shape, k.shape, v.shape]
        for a, r in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("stream", ["never", "always"],
                         ids=["resident", "streamed"])
def test_equal_widths_trace_as_before_and_a_wide_head_takes_smaller_tiles(
        monkeypatch, stream):
    """With values as wide as keys every block of every call is what it was
    (the jaxpr's text, file positions cut out, is what the tree before the
    value width gave, for equal and for grouped heads); with 192 | 128 the
    values, the output and its gradient go into the calls 128 wide, nothing
    is padded, and the tile's edge is 512: a head over 128 lanes is padded
    to 256 in VMEM, and a tile of 1024 then no longer fits beside the
    backward pass's score tiles."""
    import hashlib
    import re

    from apex_tpu.ops import layer_norm

    monkeypatch.setattr(layer_norm, "_on_tpu", lambda: True)

    def text(kv_heads):
        q = jax.ShapeDtypeStruct((1, 8, 256, 64), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, kv_heads, 256, 64), jnp.bfloat16)
        f = lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, stream=stream, block_q=128,
            block_k=128).astype(jnp.float32))
        return re.sub(r" at /[^\s\]]*:\d+", "", str(jax.make_jaxpr(
            jax.grad(f, (0, 1, 2)))(q, k, k)))

    # (the suite's default precision, named so that the text does not hang
    # on where the test runs)
    with jax.default_matmul_precision("highest"):
        digest = hashlib.sha256(
            "\n".join([text(8), text(2)]).encode()).hexdigest()
    assert digest == {
        "never": "edb80025dd44d4a67ef5cd700d473e4ca759024cf3fa07ed31ee503e"
                 "0ffb60bc",
        "always": "8ce793e93192b0adcca52eb52cbc99a05a918b6230cd19152153be40"
                  "6af96605"}[stream]

    q = jax.ShapeDtypeStruct((1, 4, 2048, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 4, 2048, 128), jnp.bfloat16)
    f = lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, stream=stream).astype(jnp.float32))
    calls = list(_pallas_eqns(jax.make_jaxpr(
        jax.grad(f, (0, 1, 2)))(q, q, v).jaxpr))
    assert len(calls) == 3
    for eqn in calls:
        shapes = [x.aval.shape for x in eqn.invars + eqn.outvars]
        assert (1, 4, 2048, 128) in shapes and (1, 4, 2048, 192) in shapes
        assert not any(s[-1] == 256 for s in shapes if len(s) == 4)
        size = lambda x: int(getattr(x, "block_size", x))
        blocks = [[size(x) for x in bm.block_shape] for bm in
                  eqn.params["grid_mapping"].block_mappings]
        edges = {blk[2] for blk in blocks
                 if len(blk) == 4 and blk[3] in (128, 192)}
        assert max(edges) in ((512, 2048) if stream == "never" else (512,))

"""Kernel numerics self-test: every Pallas kernel against its XLA twin.

The one copy of these comparisons. ``bench.py`` records them, and
``chip_smoke.py`` gates on them: forward and backward of flash attention
(resident and streamed), LayerNorm/RMSNorm, scaled-masked softmax, the
fused cross-entropy and the chunked LM-head loss, plus ``flash_decode`` /
``flash_decode_multi`` against the paged references. Sizes are parameters
so the tests run the same code small on the CPU (interpret mode); the
defaults are the sizes a chip run compares at.

Nothing is caught here: a kernel that fails to compile or run raises out
of :func:`kernel_selftest`, and a comparison outside its tolerance clears
``all_ok``.

No reference-file citation: the reference checks its extensions against
PyTorch ops per test file (tests/L0/); this is the on-device analog.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _max_errs(a, b):
    """(max abs error, scale-normalized error): the normalized form divides
    by the reference tensor's max magnitude, the right yardstick for bf16
    tensors whose values span decades (pointwise relative error explodes on
    near-zero entries; plain abs error penalizes large-magnitude grads)."""
    a = np.asarray(jax.device_get(a), np.float64)
    b = np.asarray(jax.device_get(b), np.float64)
    if not a.size:
        return 0.0, 0.0
    abs_err = float(np.max(np.abs(a - b)))
    scale = max(float(np.max(np.abs(b))), 1e-6)
    return abs_err, abs_err / scale


def compare(fn_pallas, fn_xla, args, tol_norm, grad_argnums=None):
    """fwd + bwd max abs / scale-normalized error between two impls of the
    same math; ``ok`` gates on the normalized error."""
    fwd_p = jax.jit(fn_pallas)(*args)
    fwd_x = jax.jit(fn_xla)(*args)
    abs_err, norm_err = _max_errs(fwd_p, fwd_x)
    entry = {"fwd_max_abs_err": round(abs_err, 6),
             "fwd_norm_err": round(norm_err, 6)}
    if grad_argnums is not None:
        # random (fixed-key) cotangent: grads of sum(out * w)
        w = jax.random.normal(jax.random.PRNGKey(7), fwd_p.shape,
                              jnp.float32).astype(fwd_p.dtype)

        def loss(fn):
            return lambda *a: jnp.sum(fn(*a).astype(jnp.float32)
                                      * w.astype(jnp.float32))

        g_p = jax.jit(jax.grad(loss(fn_pallas), argnums=grad_argnums))(*args)
        g_x = jax.jit(jax.grad(loss(fn_xla), argnums=grad_argnums))(*args)
        g_abs = g_norm = 0.0
        for a, b in zip(jax.tree.leaves(g_p), jax.tree.leaves(g_x)):
            ae, ne = _max_errs(a, b)
            g_abs, g_norm = max(g_abs, ae), max(g_norm, ne)
        entry["bwd_max_abs_err"] = round(g_abs, 6)
        entry["bwd_norm_err"] = round(g_norm, 6)
    entry["tol_norm"] = tol_norm
    worst = max(v for k, v in entry.items() if k.endswith("norm_err"))
    entry["ok"] = bool(np.isfinite(worst) and worst <= tol_norm)
    return entry


def _paged_case(key, *, slots, heads, kv_heads, head_dim, block, ctx,
                queries):
    """A paged-KV decode problem: bf16 pools, a shuffled block table (no
    page shared, page 0 left as the null page) and lengths from an idle
    slot through a few tokens to the full context."""
    nb = ctx // block
    n_pages = slots * nb + 1
    kq, kk, kv, kt = jax.random.split(key, 4)
    q_shape = ((slots, heads, head_dim) if queries == 1
               else (slots, heads, queries, head_dim))
    q = jax.random.normal(kq, q_shape, jnp.bfloat16)
    k_pages = jax.random.normal(
        kk, (n_pages, kv_heads, block, head_dim), jnp.bfloat16)
    v_pages = jax.random.normal(
        kv, (n_pages, kv_heads, block, head_dim), jnp.bfloat16)
    tables = (1 + jax.random.permutation(kt, slots * nb)).reshape(
        slots, nb).astype(jnp.int32)
    # slot 0 idle, slot 1 shorter than one page, the rest spread to full
    spread = np.linspace(queries + 1, ctx, slots).astype(np.int32)
    spread[0] = 0
    spread[1] = min(block // 2 + queries, ctx)
    return q, k_pages, v_pages, tables, jnp.asarray(spread)


def kernel_selftest(*, seq: int = 1024, stream_seq: int = 8192,
                    hidden: int = 1024, vocab: int = 8192,
                    decode_ctx: int = 1024) -> dict:
    """Per-kernel compiled-vs-XLA max errors on THIS backend (interpret
    mode off-TPU). Returns ``{name: entry, ..., "platform", "all_ok"}``."""
    from apex_tpu.ops.flash_attention import flash_attention
    from apex_tpu.ops.flash_decode import (
        flash_decode,
        flash_decode_multi,
        paged_attention_multi_reference,
        paged_attention_reference,
    )
    from apex_tpu.ops.layer_norm import layer_norm, rms_norm
    from apex_tpu.ops.lm_head_loss import (
        lm_head_cross_entropy,
        lm_head_cross_entropy_reference,
    )
    from apex_tpu.ops.softmax import scaled_masked_softmax
    from apex_tpu.ops.xentropy import softmax_cross_entropy

    results = {"platform": jax.default_backend()}
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)

    # flash attention: bf16 production dtype, causal (the GPT path)
    b, h, d = 2, 8, 64
    q = jax.random.normal(kq, (b, h, seq, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, h, seq, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, h, seq, d), jnp.bfloat16)
    results["flash_attention"] = compare(
        partial(flash_attention, causal=True, impl="pallas"),
        partial(flash_attention, causal=True, impl="xla"),
        (q, k, v), tol_norm=2e-2, grad_argnums=(0, 1, 2))

    # long-sequence STREAMED flash attention: packed segment ids + causal —
    # the config that hits the resident layout's VMEM wall. Compared
    # against the XLA mask at small heads so the dense reference fits HBM.
    q8 = jax.random.normal(kq, (1, 2, stream_seq, d), jnp.bfloat16)
    k8 = jax.random.normal(kk, (1, 2, stream_seq, d), jnp.bfloat16)
    v8 = jax.random.normal(kv, (1, 2, stream_seq, d), jnp.bfloat16)
    seg = jnp.repeat(jnp.arange(8, dtype=jnp.int32), stream_seq // 8)[None]
    results["flash_attention_segments_streamed"] = compare(
        partial(flash_attention, segment_ids=(seg, seg), causal=True,
                contiguous_segments=True, impl="pallas", stream="always"),
        partial(flash_attention, segment_ids=(seg, seg), causal=True,
                contiguous_segments=True, impl="xla"),
        (q8, k8, v8), tol_norm=2e-2, grad_argnums=(0, 1, 2))

    # fused LN / RMSNorm: bf16 x, fp32 gamma/beta (the MixedFused contract)
    x = jax.random.normal(key, (512, hidden), jnp.bfloat16)
    wln = 1.0 + 0.1 * jax.random.normal(kq, (hidden,), jnp.float32)
    bln = 0.1 * jax.random.normal(kk, (hidden,), jnp.float32)
    results["layer_norm"] = compare(
        partial(layer_norm, impl="pallas"), partial(layer_norm, impl="xla"),
        (x, wln, bln), tol_norm=2e-2, grad_argnums=(0, 1, 2))
    results["rms_norm"] = compare(
        partial(rms_norm, impl="pallas"), partial(rms_norm, impl="xla"),
        (x, wln), tol_norm=2e-2, grad_argnums=(0, 1))

    # scaled-mask softmax (causal, the Megatron kernel pair)
    ss = min(seq, 256)
    logits = jax.random.normal(key, (4, 8, ss, ss), jnp.bfloat16)
    results["scaled_masked_softmax"] = compare(
        partial(scaled_masked_softmax, scale=0.125, causal=True,
                impl="pallas"),
        partial(scaled_masked_softmax, scale=0.125, causal=True, impl="xla"),
        (logits,), tol_norm=2e-2, grad_argnums=(0,))

    # fused label-smoothing CE (fp32 logits like the vocab head)
    vlog = jax.random.normal(key, (seq, vocab), jnp.float32)
    labels = jax.random.randint(kq, (seq,), 0, vocab)
    results["xentropy"] = compare(
        partial(softmax_cross_entropy, smoothing=0.1, impl="pallas"),
        partial(softmax_cross_entropy, smoothing=0.1, impl="xla"),
        (vlog, labels), tol_norm=1e-3, grad_argnums=(0,))

    # chunked LM-head CE vs the unchunked reference (both XLA; the chunk
    # scan's accumulation order is what is under test)
    hs = jax.random.normal(key, (4, ss, hidden // 2), jnp.bfloat16)
    wte = jax.random.normal(kk, (vocab, hidden // 2), jnp.bfloat16)
    tgt = jax.random.randint(kv, (4, ss), 0, vocab)
    results["lm_head_loss"] = compare(
        lambda hh, ww: lm_head_cross_entropy(hh, ww, tgt, num_chunks=8),
        lambda hh, ww: lm_head_cross_entropy_reference(hh, ww, tgt),
        (hs, wte), tol_norm=2e-2, grad_argnums=(0, 1))

    # paged decode attention, one query and K=4 trailing queries, at the
    # engine's MHA geometry (g=1, page 16, d=64) and at GQA g=8, d=128
    for tag, geom in (("mha_d64", dict(heads=16, kv_heads=16, head_dim=64)),
                      ("gqa8_d128", dict(heads=32, kv_heads=4,
                                         head_dim=128))):
        for name, pallas_fn, ref_fn, queries in (
                ("flash_decode", flash_decode, paged_attention_reference, 1),
                ("flash_decode_multi", flash_decode_multi,
                 paged_attention_multi_reference, 4)):
            q_d, kp, vp, tables, lengths = _paged_case(
                key, slots=8, block=16, ctx=decode_ctx, queries=queries,
                **geom)
            results[f"{name}_{tag}"] = compare(
                lambda qq, kk_, vv_: pallas_fn(qq, kk_, vv_, tables, lengths,
                                               impl="pallas"),
                lambda qq, kk_, vv_: ref_fn(qq, kk_, vv_, tables, lengths),
                (q_d, kp, vp), tol_norm=2e-2)

    results["all_ok"] = all(
        v["ok"] for v in results.values() if isinstance(v, dict))
    return results

"""Fused LayerNorm / RMSNorm — Pallas TPU kernels with custom VJP.

Reference: csrc/layer_norm_cuda_kernel.cu (1 170 LoC of warp-shuffle
reductions) behind apex/normalization/fused_layer_norm.py. The CUDA kernel's
job — one HBM pass for stats+normalize in forward, one fused pass for
dx/dγ/dβ in backward — maps to a Pallas kernel blocked over rows with the
whole hidden dimension resident in VMEM (the reference's fast_layer_norm
supports hidden ≤ 65536, apex/contrib/layer_norm/layer_norm.py:8-53; a
65536-wide fp32 row is 256 KB, comfortably inside ~16 MB VMEM).

Semantics preserved:

- affine / non-affine / bias-free variants (layer_norm_cuda.cpp:428-441);
- mixed dtype: bf16/fp16 activations with fp32 γ/β ("MixedFused",
  fused_layer_norm.py:398-436) — stats and math are always fp32;
- RMSNorm shares the kernel with the mean term dropped
  (manual_rms_norm reference, fused_layer_norm.py:16-29).

``impl='xla'`` provides the lax fallback (the reference falls back to
``F.layer_norm`` when its extension is missing, fused_layer_norm.py:204-219);
``impl='auto'`` picks Pallas on TPU. Interpret mode keeps the Pallas path
testable on CPU.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not _on_tpu()


def _resolve_impl(impl: str) -> str:
    if impl == "auto":
        return "pallas" if _on_tpu() else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"impl must be 'auto' | 'pallas' | 'xla', got {impl!r}")
    return impl


def _pallas_unsupported(op: str, impl: str, why: str) -> str:
    """A call the Pallas kernel cannot take. An explicit ``impl='pallas'``
    raises; ``'auto'`` says so (Python shows a warning once per call site)
    and returns ``'xla'`` — a kernel that silently became its XLA twin is
    how a run on the chip "passes" without running the kernel."""
    if impl == "pallas":
        raise ValueError(f"{op}: impl='pallas' but {why}")
    warnings.warn(f"{op}: {why}; taking the XLA path", stacklevel=3)
    return "xla"


def _row_block(n_rows: int, hidden: int) -> int:
    """Rows per grid step: target ~1 MB of fp32 activations per block,
    8-row aligned (fp32 sublane tile)."""
    target = max(1, (1 << 20) // max(1, hidden * 4))
    blk = max(8, min(1024, (target // 8) * 8))
    return min(blk, max(8, ((n_rows + 7) // 8) * 8))


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps, rms):
    x = x_ref[...].astype(jnp.float32)
    if rms:
        mu = jnp.zeros((x.shape[0], 1), jnp.float32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    else:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mu) * rstd
    y = xhat
    if w_ref is not None:
        y = y * w_ref[...].astype(jnp.float32)
    if b_ref is not None:
        y = y + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = mu
    rstd_ref[...] = rstd


def _ln_bwd_kernel(
    g_ref, x_ref, mean_ref, rstd_ref, w_ref, dx_ref, dw_ref, db_ref, *, rms
):
    g = g_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    mu = mean_ref[...]
    rstd = rstd_ref[...]
    xhat = (x - mu) * rstd
    wg = g if w_ref is None else g * w_ref[...].astype(jnp.float32)
    c1 = jnp.mean(wg * xhat, axis=-1, keepdims=True)
    if rms:
        dx = rstd * (wg - xhat * c1)
    else:
        c2 = jnp.mean(wg, axis=-1, keepdims=True)
        dx = rstd * (wg - c2 - xhat * c1)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    # Per-block partial γ/β grads (summed over the row axis outside the
    # kernel) — the two-pass part reduction of layer_norm_cuda_kernel.cu's
    # cuComputePartGradGammaBeta.
    if dw_ref is not None:
        dw_ref[...] = jnp.sum(g * xhat, axis=0).reshape(dw_ref.shape)
    if db_ref is not None:
        db_ref[...] = jnp.sum(g, axis=0).reshape(db_ref.shape)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


def _pad_rows(x2d, blk):
    rows = x2d.shape[0]
    pad = (-rows) % blk
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    return x2d, rows


@functools.partial(jax.jit, static_argnames=("eps", "rms", "has_w", "has_b"))
def _fwd_pallas(x2d, w, b, *, eps, rms, has_w, has_b):
    rows, hidden = x2d.shape
    blk = _row_block(rows, hidden)
    x2d, true_rows = _pad_rows(x2d, blk)
    grid = x2d.shape[0] // blk

    row_spec = pl.BlockSpec((blk, hidden), lambda i: (i, 0), memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((blk, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((hidden,), lambda i: (0,), memory_space=pltpu.VMEM)

    in_specs = [row_spec]
    args = [x2d]
    if has_w:
        in_specs.append(vec_spec)
        args.append(w)
    if has_b:
        in_specs.append(vec_spec)
        args.append(b)

    def kernel(*refs):
        idx = 1
        w_ref = refs[idx] if has_w else None
        idx += has_w
        b_ref = refs[idx] if has_b else None
        idx += has_b
        _ln_fwd_kernel(
            refs[0], w_ref, b_ref, refs[idx], refs[idx + 1], refs[idx + 2],
            eps=eps, rms=rms,
        )

    y, mean, rstd = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[row_spec, stat_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
            jax.ShapeDtypeStruct((x2d.shape[0], 1), jnp.float32),
            jax.ShapeDtypeStruct((x2d.shape[0], 1), jnp.float32),
        ],
        interpret=_interpret(),
    )(*args)
    return y[:true_rows], mean[:true_rows], rstd[:true_rows]


@functools.partial(jax.jit, static_argnames=("rms", "has_w", "has_b"))
def _bwd_pallas(g2d, x2d, mean, rstd, w, *, rms, has_w, has_b):
    rows, hidden = x2d.shape
    blk = _row_block(rows, hidden)
    g2d, true_rows = _pad_rows(g2d, blk)
    x2d, _ = _pad_rows(x2d, blk)
    mean, _ = _pad_rows(mean, blk)
    rstd, _ = _pad_rows(rstd, blk)
    grid = x2d.shape[0] // blk

    row_spec = pl.BlockSpec((blk, hidden), lambda i: (i, 0), memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((blk, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((hidden,), lambda i: (0,), memory_space=pltpu.VMEM)
    # Per-grid-step partial γ/β sums. Mosaic requires a block's trailing two
    # dims to be 8/128-divisible or equal to the array's; a (1, hidden) block
    # over (grid, hidden) violates the sublane rule, so the partials are
    # (grid, 1, hidden) with the grid axis leading and the block covering the
    # trailing (1, hidden) exactly.
    part_spec = pl.BlockSpec(
        (1, 1, hidden), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
    )

    in_specs = [row_spec, row_spec, stat_spec, stat_spec]
    args = [g2d, x2d, mean, rstd]
    if has_w:
        in_specs.append(vec_spec)
        args.append(w)

    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct(x2d.shape, x2d.dtype)]
    if has_w:
        out_specs.append(part_spec)
        out_shape.append(jax.ShapeDtypeStruct((grid, 1, hidden), jnp.float32))
    if has_b:
        out_specs.append(part_spec)
        out_shape.append(jax.ShapeDtypeStruct((grid, 1, hidden), jnp.float32))

    def kernel(*refs):
        w_ref = refs[4] if has_w else None
        outs = refs[4 + has_w :]
        dw_ref = outs[1] if has_w else None
        db_ref = outs[1 + has_w] if has_b else None
        _ln_bwd_kernel(
            refs[0], refs[1], refs[2], refs[3], w_ref, outs[0], dw_ref, db_ref,
            rms=rms,
        )

    outs = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=_interpret(),
    )(*args)
    dx = outs[0][:true_rows]
    i = 1
    dw = db = None
    if has_w:
        dw = jnp.sum(outs[i], axis=(0, 1))
        i += 1
    if has_b:
        db = jnp.sum(outs[i], axis=(0, 1))
    return dx, dw, db


# ---------------------------------------------------------------------------
# XLA reference path (fallback and ground truth for tests)
# ---------------------------------------------------------------------------


def _norm_xla(x, w, b, eps, rms):
    x32 = x.astype(jnp.float32)
    if rms:
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        xhat = x32 * jax.lax.rsqrt(var + eps)
    else:
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
        xhat = (x32 - mu) * jax.lax.rsqrt(var + eps)
    y = xhat
    if w is not None:
        y = y * w.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Public functional API with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_norm(x, w, b, eps, rms):
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    y, _, _ = _fwd_pallas(
        x2d, w, b, eps=eps, rms=rms, has_w=w is not None, has_b=b is not None
    )
    return y.reshape(shape)


def _fused_norm_fwd(x, w, b, eps, rms):
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    y, mean, rstd = _fwd_pallas(
        x2d, w, b, eps=eps, rms=rms, has_w=w is not None, has_b=b is not None
    )
    return y.reshape(shape), (x2d, mean, rstd, w, b is not None, shape)


def _fused_norm_bwd(eps, rms, res, gy):
    x2d, mean, rstd, w, has_b, shape = res
    g2d = gy.reshape(-1, shape[-1])
    dx, dw, db = _bwd_pallas(
        g2d, x2d, mean, rstd, w, rms=rms, has_w=w is not None, has_b=has_b
    )
    dx = dx.reshape(shape)
    dw = None if w is None else dw.astype(w.dtype)
    db_out = db.astype(w.dtype if w is not None else jnp.float32) if has_b else None
    return dx, dw, db_out


_fused_norm.defvjp(_fused_norm_fwd, _fused_norm_bwd)


def layer_norm(
    x: jax.Array,
    weight: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    eps: float = 1e-5,
    *,
    impl: str = "auto",
) -> jax.Array:
    """Fused LayerNorm over the last dimension.

    The functional form of the reference's ``fused_layer_norm(_affine)``
    (apex/normalization/fused_layer_norm.py:168-202). Stats are fp32
    regardless of input dtype; γ/β may be fp32 with bf16 inputs (the
    MixedFused contract).

    ``impl``: 'pallas' forces the kernel (interpret mode off-TPU), 'xla' the
    lax composition, 'auto' picks pallas on TPU and xla elsewhere."""
    if _resolve_impl(impl) == "xla":
        return _norm_xla(x, weight, bias, eps, rms=False)
    return _fused_norm(x, weight, bias, eps, False)


def rms_norm(
    x: jax.Array,
    weight: Optional[jax.Array] = None,
    eps: float = 1e-5,
    *,
    impl: str = "auto",
) -> jax.Array:
    """Fused RMSNorm (apex/normalization/fused_layer_norm.py:300-396)."""
    if _resolve_impl(impl) == "xla":
        return _norm_xla(x, weight, None, eps, rms=True)
    return _fused_norm(x, weight, None, eps, True)


def layer_norm_reference(x, weight=None, bias=None, eps=1e-5):
    """Pure-XLA ground truth for equivalence tests (the reference tests
    compare against torch.nn.functional.layer_norm, SURVEY.md §4)."""
    return _norm_xla(x, weight, bias, eps, rms=False)


def rms_norm_reference(x, weight=None, eps=1e-5):
    return _norm_xla(x, weight, None, eps, rms=True)

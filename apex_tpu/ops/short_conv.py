"""The gated short convolution of the LFM2 family's conv layers (Liquid
AI's ``Lfm2ShortConv``; no reference analog: apex has no such operator).

Between the layer's two projections, for every channel apart:

    z_t = B_t * u_t
    c_t = sum_j w[j] * z_{t - (L-1) + j}        (causal: zeros to the left)
    y_t = C_t * c_t

with ``B, C, u`` the three thirds of the in-projection's output and ``w`` one
``L``-tap filter a channel (``L`` = ``conv_L_cache``, 3 as published). No
activation beside the two gates.

It is bound by memory: forward it reads three values and writes one for each
token and channel, backward it reads those and the result's gradient and
writes three, with a few dozen operations between. XLA's fusions of the
plain ``jax.numpy`` form (:func:`gated_short_conv_xla`: shifts and products
in float32) read 23% of that roofline inside the trainer's step on a v5e, in
five fusions a layer (``PERF.md``, Findings, PR 34), so on the chip the
operator is one Pallas kernel each way: a tile of ``TILE`` tokens by all
channels, the two tokens ahead of it (behind it, for the transpose) read
from an eight-row block beside the tile, the shifts taken in registers, the
taps' gradient summed over the grid in float32. Products and sums are in
float32 either way. The caller names the scope.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.layer_norm import (
    _interpret,
    _pallas_unsupported,
    _resolve_impl,
)

#: tokens in a tile, and rows of the block beside it that holds the tokens a
#: tile needs from its neighbour (a block of the sequence axis is a multiple
#: of 8 rows)
TILE, EDGE = 128, 8
#: channels worked on at a time inside a tile
LANES = 512


def gated_short_conv_xla(bcu: jax.Array, taps: jax.Array) -> jax.Array:
    """``bcu``: ``(..., seq, 3 * channels)``, the in-projection's output,
    ``B``, ``C`` and ``u`` side by side; ``taps``: ``(L, channels)``, tap
    ``L - 1`` the one on the token itself. Returns ``(..., seq, channels)``
    in ``bcu``'s type. Plain ``jax.numpy``; its gradient is autodiff's."""
    channels = taps.shape[1]
    if bcu.shape[-1] != 3 * channels:
        raise ValueError(f"bcu holds {bcu.shape[-1]} channels, not 3 x "
                         f"{channels}")
    b, c, u = (bcu[..., i * channels:(i + 1) * channels].astype(jnp.float32)
               for i in range(3))
    z = b * u
    w = taps.astype(jnp.float32)
    taps_n, seq = w.shape[0], z.shape[-2]
    pad = [(0, 0)] * (z.ndim - 2) + [(taps_n - 1, 0), (0, 0)]
    zp = jnp.pad(z, pad)
    mixed = sum(w[j] * jax.lax.slice_in_dim(zp, j, j + seq, axis=-2)
                for j in range(taps_n))
    return (c * mixed).astype(bcu.dtype)


# -- the kernels ---------------------------------------------------------------

def _rows(block, at):
    """Rows ``at`` of an ``(EDGE, n)`` block, each as ``(1, n)``, by a masked
    sum over the eight: no slice that the tiling would refuse."""
    row = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    return [jnp.sum(jnp.where(row == i, block, 0.0), axis=0, keepdims=True)
            for i in at]


def _shifted(x, edge_rows, down: bool):
    """``x`` of a tile moved one row and two rows down (``down``: row ``t``
    holds ``x[t - 1]``, ``x[t - 2]``) or up, the rows that fall off the
    tile's end replaced by ``edge_rows``, the neighbour's two nearest (in
    the sequence's order)."""
    n = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    far, near = edge_rows
    if down:
        one = jnp.where(row == 0, near, pltpu.roll(x, 1, 0))
        two = jnp.where(row == 0, far, jnp.where(
            row == 1, near, pltpu.roll(x, 2, 0)))
    else:
        one = jnp.where(row == n - 1, near, pltpu.roll(x, n - 1, 0))
        two = jnp.where(row == n - 1, far, jnp.where(
            row == n - 2, near, pltpu.roll(x, n - 2, 0)))
    return one, two


def _gates(ref, lanes, h):
    """``B``, ``C`` and ``u`` of a block of ``bcu`` over ``lanes``, in
    float32."""
    return [ref[0, :, pl.ds(i * h + lanes, LANES)].astype(jnp.float32)
            for i in range(3)]


def _fwd_kernel(bcu_ref, before_ref, taps_ref, out_ref, *, h):
    first = pl.program_id(1) == 0
    for lanes in range(0, h, LANES):
        b, c, u = _gates(bcu_ref, lanes, h)
        bb, _, ub = _gates(before_ref, lanes, h)
        # nothing lies ahead of a sequence's first token
        edge = _rows(jnp.where(first, 0.0, bb * ub), (EDGE - 2, EDGE - 1))
        z = b * u
        one, two = _shifted(z, edge, down=True)
        w = [taps_ref[pl.ds(j, 1), pl.ds(lanes, LANES)] for j in range(3)]
        out_ref[0, :, pl.ds(lanes, LANES)] = (
            c * (w[0] * two + w[1] * one + w[2] * z)).astype(out_ref.dtype)


def _bwd_kernel(bcu_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                taps_ref, dbcu_ref, dtaps_ref, *, h):
    ti = pl.program_id(1)
    first, last = ti == 0, ti == pl.num_programs(1) - 1

    @pl.when((pl.program_id(0) == 0) & first)
    def _init():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    for lanes in range(0, h, LANES):
        b, c, u = _gates(bcu_ref, lanes, h)
        bb, _, ub = _gates(before_ref, lanes, h)
        _, ca, _ = _gates(after_ref, lanes, h)
        dy = dy_ref[0, :, pl.ds(lanes, LANES)].astype(jnp.float32)
        dya = dy_after_ref[0, :, pl.ds(lanes, LANES)].astype(jnp.float32)
        w = [taps_ref[pl.ds(j, 1), pl.ds(lanes, LANES)] for j in range(3)]
        z = b * u
        one, two = _shifted(
            z, _rows(jnp.where(first, 0.0, bb * ub), (EDGE - 2, EDGE - 1)),
            down=True)
        dm = dy * c                      # the filter's result's gradient
        # ... of which a token's product reaches the two tokens after it
        up_one, up_two = _shifted(
            dm, _rows(jnp.where(last, 0.0, dya * ca), (1, 0)), down=False)
        dz = w[2] * dm + w[1] * up_one + w[0] * up_two
        for i, part in enumerate((dz * u,
                                  dy * (w[0] * two + w[1] * one + w[2] * z),
                                  dz * b)):
            dbcu_ref[0, :, pl.ds(i * h + lanes, LANES)] = part.astype(
                dbcu_ref.dtype)
        for j, moved in enumerate((two, one, z)):
            dtaps_ref[pl.ds(j, 1), pl.ds(lanes, LANES)] += jnp.sum(
                dm * moved, axis=0, keepdims=True)


def _specs(batch, seq, h):
    """Block specs over the grid ``(batch, seq // TILE)``: a tile of
    ``width`` channels, and the eight rows before and after it (clamped at
    the sequence's ends, where the kernels put zeros)."""
    per = TILE // EDGE
    tile = lambda width: pl.BlockSpec(
        (1, TILE, width), lambda bi, ti: (bi, ti, 0))
    before = lambda width: pl.BlockSpec(
        (1, EDGE, width), lambda bi, ti: (bi, jnp.maximum(ti * per - 1, 0),
                                          0))
    after = lambda width: pl.BlockSpec(
        (1, EDGE, width), lambda bi, ti: (bi, jnp.minimum(
            (ti + 1) * per, seq // EDGE - 1), 0))
    taps = pl.BlockSpec((3, h), lambda bi, ti: (0, 0))
    return tile, before, after, taps


@jax.custom_vjp
def _pallas(bcu, taps):
    return _pallas_fwd(bcu, taps)[0]


def _pallas_fwd(bcu, taps):
    batch, seq, h = bcu.shape[0], bcu.shape[1], taps.shape[1]
    tile, before, _, taps_spec = _specs(batch, seq, h)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, h=h),
        grid=(batch, seq // TILE),
        in_specs=[tile(3 * h), before(3 * h), taps_spec],
        out_specs=tile(h),
        out_shape=jax.ShapeDtypeStruct((batch, seq, h), bcu.dtype),
        interpret=_interpret(),
    )(bcu, bcu, taps.astype(jnp.float32))
    return out, (bcu, taps)


def _pallas_bwd(res, dy):
    bcu, taps = res
    batch, seq, h = bcu.shape[0], bcu.shape[1], taps.shape[1]
    tile, before, after, taps_spec = _specs(batch, seq, h)
    dbcu, dtaps = pl.pallas_call(
        functools.partial(_bwd_kernel, h=h),
        grid=(batch, seq // TILE),
        in_specs=[tile(3 * h), before(3 * h), after(3 * h), tile(h),
                  after(h), taps_spec],
        out_specs=[tile(3 * h), taps_spec],
        out_shape=[jax.ShapeDtypeStruct(bcu.shape, bcu.dtype),
                   jax.ShapeDtypeStruct((3, h), jnp.float32)],
        interpret=_interpret(),
    )(bcu, bcu, bcu, dy, dy, taps.astype(jnp.float32))
    return dbcu, dtaps.astype(taps.dtype)


_pallas.defvjp(_pallas_fwd, _pallas_bwd)


def gated_short_conv(bcu: jax.Array, taps: jax.Array, *,
                     impl: str = "auto") -> jax.Array:
    """``bcu``: ``(batch, seq, 3 * channels)``, the in-projection's output,
    ``B``, ``C`` and ``u`` side by side; ``taps``: ``(L, channels)``, tap
    ``L - 1`` the one on the token itself. Returns ``(batch, seq,
    channels)`` in ``bcu``'s type. ``impl``: 'pallas' forces the kernels
    (interpret mode off-TPU), 'xla' the ``jax.numpy`` form, 'auto' picks
    the kernels on TPU, where the shape is one they take: 3 taps, a whole
    number of ``TILE``-token tiles a sequence and of ``LANES``-channel
    blocks."""
    use = _resolve_impl(impl)
    if use == "pallas" and not (
            bcu.ndim == 3 and taps.shape[0] == 3
            and bcu.shape[1] % TILE == 0 and taps.shape[1] % LANES == 0
            and bcu.shape[2] == 3 * taps.shape[1]):
        use = _pallas_unsupported(
            "gated_short_conv", impl,
            f"bcu {bcu.shape} with taps {taps.shape} is outside the "
            f"kernels' envelope (3 taps, sequences in tiles of {TILE} "
            f"tokens, channels in blocks of {LANES})")
    if use == "xla":
        return gated_short_conv_xla(bcu, taps)
    return _pallas(bcu, taps)

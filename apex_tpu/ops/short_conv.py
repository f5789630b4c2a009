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

:func:`short_conv` is the plain filter of Kimi Delta Attention's q, k and v
(arXiv:2510.26692; ``short_conv_kernel_size`` taps, 4 as published): the
same causal depthwise filter with no gates, then SiLU. It runs through the
same two kernels: the tap count (3 or 4: the taps a tile needs from its
neighbour still come from one eight-row block) and the gates are their
arguments. SiLU stays outside them, a ``jax.numpy`` line that XLA fuses into
what reads it: inside, its gradient at a tile's end would need the filter's
result in the next tile's first rows, a second halo.
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
    """``x`` of a tile moved down by 1 .. ``len(edge_rows)`` rows (``down``:
    row ``t`` of the ``k``-th holds ``x[t - k]``) or up, the rows that fall
    off the tile's end replaced by ``edge_rows``, the neighbour's nearest
    rows, the farthest first (in the sequence's order for ``down``)."""
    n = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)

    def moved(k, r=0):
        """Rows ``r`` on of the tile moved by ``k``: row ``r < k`` takes
        the neighbour's row at distance ``k - r``."""
        if r == k:
            return pltpu.roll(x, k if down else n - k, 0)
        return jnp.where(row == (r if down else n - 1 - r),
                         edge_rows[len(edge_rows) - (k - r)], moved(k, r + 1))

    return [moved(k) for k in range(1, len(edge_rows) + 1)]


def _mixed(w, moved):
    """``sum_j w[j] * moved[j]``."""
    acc = w[0] * moved[0]
    for wj, mj in zip(w[1:], moved[1:]):
        acc = acc + wj * mj
    return acc


def _gates(ref, lanes, h):
    """``B``, ``C`` and ``u`` of a block of ``bcu`` over ``lanes``, in
    float32."""
    return [ref[0, :, pl.ds(i * h + lanes, LANES)].astype(jnp.float32)
            for i in range(3)]


def _plain(ref, lanes):
    """A block of the ungated filter's input over ``lanes``, in float32."""
    return ref[0, :, pl.ds(lanes, LANES)].astype(jnp.float32)


def _taps(taps_ref, lanes):
    return [taps_ref[pl.ds(j, 1), pl.ds(lanes, LANES)]
            for j in range(taps_ref.shape[0])]


def _fwd_kernel(x_ref, before_ref, taps_ref, out_ref, *, h, gated=True):
    first = pl.program_id(1) == 0
    reach = taps_ref.shape[0] - 1       # tokens ahead that a token reads
    for lanes in range(0, h, LANES):
        if gated:
            b, c, u = _gates(x_ref, lanes, h)
            bb, _, ub = _gates(before_ref, lanes, h)
            ahead = bb * ub
        else:
            z, ahead = _plain(x_ref, lanes), _plain(before_ref, lanes)
        # nothing lies ahead of a sequence's first token
        edge = _rows(jnp.where(first, 0.0, ahead),
                     tuple(range(EDGE - reach, EDGE)))
        if gated:
            z = b * u
        moved = _shifted(z, edge, down=True)[::-1] + [z]
        w = _taps(taps_ref, lanes)
        out_ref[0, :, pl.ds(lanes, LANES)] = (
            c * _mixed(w, moved) if gated else _mixed(w, moved)
        ).astype(out_ref.dtype)


def _bwd_kernel(*refs, h, gated=True):
    if gated:
        x_ref, before_ref, after_ref, dy_ref, dy_after_ref, taps_ref, \
            dx_ref, dtaps_ref = refs
    else:
        x_ref, before_ref, dy_ref, dy_after_ref, taps_ref, dx_ref, \
            dtaps_ref = refs
    ti = pl.program_id(1)
    first, last = ti == 0, ti == pl.num_programs(1) - 1
    reach = taps_ref.shape[0] - 1

    @pl.when((pl.program_id(0) == 0) & first)
    def _init():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    for lanes in range(0, h, LANES):
        if gated:
            b, c, u = _gates(x_ref, lanes, h)
            bb, _, ub = _gates(before_ref, lanes, h)
            _, ca, _ = _gates(after_ref, lanes, h)
        else:
            z, ahead = _plain(x_ref, lanes), _plain(before_ref, lanes)
        dy = dy_ref[0, :, pl.ds(lanes, LANES)].astype(jnp.float32)
        dya = dy_after_ref[0, :, pl.ds(lanes, LANES)].astype(jnp.float32)
        w = _taps(taps_ref, lanes)
        if gated:
            z = b * u
            ahead = bb * ub
        moved = _shifted(
            z, _rows(jnp.where(first, 0.0, ahead),
                     tuple(range(EDGE - reach, EDGE))),
            down=True)[::-1] + [z]
        # the filter's result's gradient ...
        dm, behind = (dy * c, dya * ca) if gated else (dy, dya)
        # ... of which a token's product reaches the tokens after it
        dz = _mixed(w[::-1], [dm] + _shifted(
            dm, _rows(jnp.where(last, 0.0, behind),
                      tuple(range(reach - 1, -1, -1))), down=False))
        parts = (dz * u, dy * _mixed(w, moved), dz * b) if gated else (dz,)
        for i, part in enumerate(parts):
            dx_ref[0, :, pl.ds(i * h + lanes, LANES)] = part.astype(
                dx_ref.dtype)
        for j, mj in enumerate(moved):
            dtaps_ref[pl.ds(j, 1), pl.ds(lanes, LANES)] += jnp.sum(
                dm * mj, axis=0, keepdims=True)


def _specs(batch, seq, h, n_taps=3):
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
    taps = pl.BlockSpec((n_taps, h), lambda bi, ti: (0, 0))
    return tile, before, after, taps


def _call_fwd(x, taps, gated):
    batch, seq, h = x.shape[0], x.shape[1], taps.shape[1]
    wide = 3 * h if gated else h
    tile, before, _, taps_spec = _specs(batch, seq, h, taps.shape[0])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, h=h, gated=gated),
        grid=(batch, seq // TILE),
        in_specs=[tile(wide), before(wide), taps_spec],
        out_specs=tile(h),
        out_shape=jax.ShapeDtypeStruct((batch, seq, h), x.dtype),
        interpret=_interpret(),
    )(x, x, taps.astype(jnp.float32))


def _call_bwd(x, taps, dy, gated):
    batch, seq, h = x.shape[0], x.shape[1], taps.shape[1]
    wide = 3 * h if gated else h
    tile, before, after, taps_spec = _specs(batch, seq, h, taps.shape[0])
    # the gates of the tokens after a tile weigh their gradient; the plain
    # filter needs the gradient alone
    behind = ([after(wide)], [x]) if gated else ([], [])
    dx, dtaps = pl.pallas_call(
        functools.partial(_bwd_kernel, h=h, gated=gated),
        grid=(batch, seq // TILE),
        in_specs=[tile(wide), before(wide), *behind[0], tile(h), after(h),
                  taps_spec],
        out_specs=[tile(wide), taps_spec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(taps.shape, jnp.float32)],
        interpret=_interpret(),
    )(x, x, *behind[1], dy, dy, taps.astype(jnp.float32))
    return dx, dtaps.astype(taps.dtype)


@jax.custom_vjp
def _pallas(bcu, taps):
    return _call_fwd(bcu, taps, True)


_pallas.defvjp(lambda bcu, taps: (_call_fwd(bcu, taps, True), (bcu, taps)),
               lambda res, dy: _call_bwd(*res, dy, True))


@jax.custom_vjp
def _pallas_plain(x, taps):
    return _call_fwd(x, taps, False)


_pallas_plain.defvjp(
    lambda x, taps: (_call_fwd(x, taps, False), (x, taps)),
    lambda res, dy: _call_bwd(*res, dy, False))


def _in_envelope(x, taps, wide: int) -> bool:
    """Whether the kernels take the shape: 3 or 4 taps (what a tile reads
    of its neighbour lies in one eight-row block), a whole number of
    ``TILE``-token tiles a sequence and of ``LANES``-channel blocks."""
    return (x.ndim == 3 and taps.shape[0] in (3, 4)
            and x.shape[1] % TILE == 0 and taps.shape[1] % LANES == 0
            and x.shape[2] == wide * taps.shape[1])


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
    if use == "pallas" and not (taps.shape[0] == 3
                                and _in_envelope(bcu, taps, 3)):
        use = _pallas_unsupported(
            "gated_short_conv", impl,
            f"bcu {bcu.shape} with taps {taps.shape} is outside the "
            f"kernels' envelope (3 taps, sequences in tiles of {TILE} "
            f"tokens, channels in blocks of {LANES})")
    if use == "xla":
        return gated_short_conv_xla(bcu, taps)
    return _pallas(bcu, taps)


def short_conv_xla(x: jax.Array, taps: jax.Array) -> jax.Array:
    """``silu(c)``, ``c_t = sum_j w[j] x_{t - (L-1) + j}`` for every channel
    apart: ``x`` ``(..., seq, channels)``, ``taps`` ``(L, channels)``, tap
    ``L - 1`` the one on the token itself. Plain ``jax.numpy`` in float32;
    its gradient is autodiff's."""
    if x.shape[-1] != taps.shape[1]:
        raise ValueError(f"x holds {x.shape[-1]} channels, the taps "
                         f"{taps.shape[1]}")
    w = taps.astype(jnp.float32)
    taps_n, seq = w.shape[0], x.shape[-2]
    pad = [(0, 0)] * (x.ndim - 2) + [(taps_n - 1, 0), (0, 0)]
    xp = jnp.pad(x.astype(jnp.float32), pad)
    mixed = sum(w[j] * jax.lax.slice_in_dim(xp, j, j + seq, axis=-2)
                for j in range(taps_n))
    return jax.nn.silu(mixed).astype(x.dtype)


def short_conv(x: jax.Array, taps: jax.Array, *,
               impl: str = "auto") -> jax.Array:
    """The plain causal depthwise filter, then SiLU: ``x`` ``(batch, seq,
    channels)``, ``taps`` ``(L, channels)``. Returns ``x``'s shape and type.
    ``impl`` as :func:`gated_short_conv`'s; the kernels take 3 or 4 taps."""
    use = _resolve_impl(impl)
    if use == "pallas" and not _in_envelope(x, taps, 1):
        use = _pallas_unsupported(
            "short_conv", impl,
            f"x {x.shape} with taps {taps.shape} is outside the kernels' "
            f"envelope (3 or 4 taps, sequences in tiles of {TILE} tokens, "
            f"channels in blocks of {LANES})")
    if use == "xla":
        return short_conv_xla(x, taps)
    return jax.nn.silu(_pallas_plain(x, taps).astype(jnp.float32)).astype(
        x.dtype)

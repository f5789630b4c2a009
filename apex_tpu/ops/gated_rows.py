"""The gated activation between the routed experts' grouped products, over
the rows of the buffer that hold an assignment and no others (no reference
analog: apex has no routed experts).

:class:`apex_tpu.transformer.moe.DroplessExperts` runs its experts over a
buffer of static shape of which the first ``filled`` rows hold an
assignment, a quarter of it under an even router. The grouped products
before and after the activation stop at the groups' sizes; the activation
itself, left to XLA, is elementwise passes over every row of the buffer,
forward and in the gradient (40 ms of a 675-ms step where 10 were asked
for, ``PERF.md``, Findings, PR 35). Here it is one kernel each way whose
walk over the rows ends at ``filled``, a scalar the kernel is handed at run
time:

    act = silu(g) * u                       ``[g | u]`` side by side in ``gu``

Float32 inside and one rounding to ``gu``'s type at the end, each way. That
is what the chip's compiler makes of the plain graph ``jax.nn.silu(g) * u``
in bfloat16 (it keeps the excess precision between the operations of one
fusion: 99.9% of the kernel's results are that fusion's bit for bit on a
v5e, where a ``silu`` rounded before the product leaves 73%, ``PERF.md``,
Findings, PR 35). Rows from ``filled`` to the end of the last
tile visited are written as zeros, whatever they held (NaN too: they are
chosen away, not multiplied away). **Rows past that tile are not written at
all**: they hold whatever the memory held, and nothing may read them (the
grouped products, ``collect_rows`` and ``spread_rows``' transpose all stop
at ``filled``). The ``jax.numpy`` form writes zeros there. The caller names
the scope.
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

#: bytes of VMEM the gradient's blocks of one tile may take, each held
#: twice (the result's gradient, ``gu`` and the gradient of ``gu``: five
#: blocks of ``tile x ffn``); the tile is the largest that fits
TILE_BYTES = 12 << 20
#: elements worked on at a time inside a tile: eight registers of float32
WORK = 8192


def _split(gu):
    ffn = gu.shape[-1] // 2
    return gu[..., :ffn], gu[..., ffn:]


def _act(g, u):
    """``silu(g) * u``, all float32."""
    return g * jax.nn.sigmoid(g) * u


def _act_grads(d, g, u):
    """``(dg, du)`` for the gradient ``d`` of :func:`_act`'s result, all
    float32."""
    sig = jax.nn.sigmoid(g)
    return d * u * (sig * (1.0 + g * (1.0 - sig))), d * (g * sig)


# -- the jax.numpy form --------------------------------------------------------

@jax.custom_vjp
def gated_rows_xla(gu: jax.Array, filled: jax.Array) -> jax.Array:
    """``gu``: ``(rows, 2 * ffn)``, the gate's and the up product side by
    side; ``filled``: how many of the rows hold an assignment. Returns
    ``(rows, ffn)`` in ``gu``'s type, zero from row ``filled`` on. Plain
    ``jax.numpy`` over every row, with the kernels' arithmetic."""
    return _xla_fwd(gu, filled)[0]


def _live(rows: int, filled) -> jax.Array:
    return (jnp.arange(rows) < filled)[:, None]


def _xla_fwd(gu, filled):
    g, u = (a.astype(jnp.float32) for a in _split(gu))
    act = jnp.where(_live(gu.shape[0], filled), _act(g, u), 0.0)
    return act.astype(gu.dtype), (gu, filled)


def _xla_bwd(res, d):
    gu, filled = res
    g, u = (a.astype(jnp.float32) for a in _split(gu))
    both = jnp.concatenate(
        _act_grads(d.astype(jnp.float32), g, u), axis=-1)
    return jnp.where(_live(gu.shape[0], filled), both, 0.0).astype(
        gu.dtype), None


gated_rows_xla.defvjp(_xla_fwd, _xla_bwd)


# -- the kernels ---------------------------------------------------------------

def _tile(rows: int, ffn: int, itemsize: int):
    """Rows of a tile: the largest power of two that divides ``rows`` and
    whose blocks fit ``TILE_BYTES``; ``None`` if fewer than 8 do."""
    tile = 512
    while tile >= 8 and (rows % tile
                         or 10 * itemsize * tile * ffn > TILE_BYTES):
        tile //= 2
    return tile if tile >= 8 else None


def _tiles(filled, tile: int):
    """Tiles a walk visits: those that hold a filled row, and the first
    whatever it holds (with nothing filled it is written as zeros)."""
    return jnp.maximum((filled + tile - 1) // tile, 1)


def _piece(tile: int, ffn: int):
    """``(rows, columns)`` of the pieces a tile is worked in, about ``WORK``
    elements each: the widest whole number of 128-lane registers up to four
    that divides ``ffn`` (all of it, for a width only interpret mode takes),
    and a power of two of rows, as the tile's are."""
    cols = next((c for c in (512, 384, 256, 128) if ffn % c == 0), ffn)
    sub = 16
    while sub * 2 * cols <= WORK:
        sub *= 2
    return min(tile, sub), cols


def _pieces(filled_ref, tile, ffn, work):
    """``work(rows, at, cols, live)`` for each piece of this step's tile:
    its rows, where its columns start in either half and how many they are,
    and which of its elements lie in a filled row."""
    sub, cols = _piece(tile, ffn)
    left = filled_ref[0] - pl.program_id(0) * tile

    def some_rows(r, carry):
        at = pl.multiple_of(r * sub, sub)
        live = at + jax.lax.broadcasted_iota(
            jnp.int32, (sub, cols), 0) < left
        for c in range(0, ffn, cols):
            work(pl.ds(at, sub), c, cols, live)
        return carry

    jax.lax.fori_loop(0, tile // sub, some_rows, 0)


def _halves(gu_ref, rows, at, cols, ffn):
    """``g`` and ``u`` of a piece, in float32."""
    return [gu_ref[rows, pl.ds(half + at, cols)].astype(jnp.float32)
            for half in (0, ffn)]


def _fwd_kernel(filled_ref, gu_ref, act_ref, *, tile, ffn):
    def work(rows, at, cols, live):
        g, u = _halves(gu_ref, rows, at, cols, ffn)
        act_ref[rows, pl.ds(at, cols)] = jnp.where(
            live, _act(g, u), 0.0).astype(act_ref.dtype)

    _pieces(filled_ref, tile, ffn, work)


def _bwd_kernel(filled_ref, d_ref, gu_ref, dgu_ref, *, tile, ffn):
    def work(rows, at, cols, live):
        d = d_ref[rows, pl.ds(at, cols)].astype(jnp.float32)
        g, u = _halves(gu_ref, rows, at, cols, ffn)
        for half, part in zip((0, ffn), _act_grads(d, g, u)):
            dgu_ref[rows, pl.ds(half + at, cols)] = jnp.where(
                live, part, 0.0).astype(dgu_ref.dtype)

    _pieces(filled_ref, tile, ffn, work)


def _walk(kernel, filled, ffn, operands, out_width):
    """``kernel`` over the tiles of ``operands`` (each ``(rows, width)``)
    that hold a filled row: the grid's extent is read from ``filled`` at run
    time, so a tile past it costs nothing, not even a step."""
    rows, dtype = operands[-1].shape[0], operands[-1].dtype
    tile = _tile(rows, ffn, dtype.itemsize)
    block = lambda width: pl.BlockSpec((tile, width), lambda i, f: (i, 0))
    return pl.pallas_call(
        functools.partial(kernel, tile=tile, ffn=ffn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(_tiles(filled, tile),),
            in_specs=[block(a.shape[1]) for a in operands],
            out_specs=block(out_width)),
        out_shape=jax.ShapeDtypeStruct((rows, out_width), dtype),
        interpret=_interpret(),
    )(filled.reshape(1).astype(jnp.int32), *operands)


@jax.custom_vjp
def _pallas(gu, filled):
    return _pallas_fwd(gu, filled)[0]


def _pallas_fwd(gu, filled):
    ffn = gu.shape[1] // 2
    return _walk(_fwd_kernel, filled, ffn, (gu,), ffn), (gu, filled)


def _pallas_bwd(res, d):
    gu, filled = res
    ffn = gu.shape[1] // 2
    return _walk(_bwd_kernel, filled, ffn, (d, gu), 2 * ffn), None


_pallas.defvjp(_pallas_fwd, _pallas_bwd)


def _walked(gu, impl: str):
    """The rows of a tile if the kernels take ``gu`` (a shaped value),
    ``None`` if the ``jax.numpy`` form does."""
    if _resolve_impl(impl) == "xla":
        return None
    tile = None
    if gu.ndim == 2 and gu.shape[1] % 2 == 0:
        tile = _tile(gu.shape[0], gu.shape[1] // 2, gu.dtype.itemsize)
    # on the chip a half is a whole number of 128-lane registers and a tile
    # a few packed rows; interpret mode takes what divides
    if tile is None or not _interpret() and (gu.shape[1] % 256 or tile < 32):
        _pallas_unsupported(
            "gated_rows", impl,
            f"gu {gu.shape} is outside the kernels' envelope (rows in tiles "
            f"of 32 or more, each half a whole number of 128 columns)")
        return None
    return tile


def rows_visited(filled: jax.Array, gu, *, impl: str = "auto") -> jax.Array:
    """Rows of the buffer :func:`gated_rows` touches for ``gu`` (a shaped
    value), each way: the kernels' own bound, whole tiles up to ``filled``;
    every row for the ``jax.numpy`` form."""
    tile = _walked(gu, impl)
    if tile is None:
        return jnp.asarray(gu.shape[0], jnp.int32)
    return (_tiles(filled, tile) * tile).astype(jnp.int32)


def gated_rows(gu: jax.Array, filled: jax.Array, *,
               impl: str = "auto") -> jax.Array:
    """``gu``: ``(rows, 2 * ffn)``, the gate's and the up product side by
    side; ``filled``: a scalar, how many of the rows hold an assignment.
    Returns ``(rows, ffn)`` in ``gu``'s type: ``silu(g) * u`` in the first
    ``filled`` rows, zeros to the end of the last tile visited, and past it
    nothing that may be read (the module's docstring). ``impl``: 'pallas'
    forces the kernels (interpret mode off-TPU), 'xla' the ``jax.numpy``
    form, 'auto' picks the kernels on TPU, where the shape is one they
    take."""
    if _walked(gu, impl) is None:
        return gated_rows_xla(gu, filled)
    return _pallas(gu, filled)

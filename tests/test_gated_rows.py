"""``ops/gated_rows.py``: the routed experts' gated activation over the rows
filled, the kernels in interpret mode against the ``jax.numpy`` forms.

Two references. The plain graph the layer held before (``jax.nn.silu(g) *
u``, autodiff's gradient) in float32, where nothing is rounded on the way:
the kernels agree to 1e-6. And the module's own ``jax.numpy`` form, which
shares the kernels' arithmetic, in bfloat16: the result and ``du`` bit for
bit; ``dg`` to one rounding, because its three float32 products may be
contracted differently by the two compilers before the one rounding to
bfloat16. Against the plain graph in bfloat16 nothing can be held to the bit
by a test on the CPU: that graph rounds the sigmoid, the ``silu`` and the
product each to bfloat16 where a compiler keeps them apart, and the chip's
does not (float32 inside one fusion: 99.9% of the kernel's bits are its
bits there, ``PERF.md``, Findings, PR 35); so the kernels are held to one
rounding of the plain graph taken in float32.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import gated_rows as gr
from apex_tpu.ops.gated_rows import gated_rows, gated_rows_xla, rows_visited

TILE, ROWS = 256, 768
#: nothing, one row, one row short of a tile, a tile, one past it, all
FILLS = (0, 1, TILE - 1, TILE, TILE + 1, ROWS)
WIDTHS = (1408, 1792)
EPS = 2.0 ** -8                 # one rounding to bfloat16, at most


def _f32(x):
    return np.asarray(x, np.float32)


def _inputs(ffn, dtype, rows=ROWS):
    kg, kd = jax.random.split(jax.random.PRNGKey(ffn))
    gu = (2.0 * jax.random.normal(kg, (rows, 2 * ffn))).astype(dtype)
    d = jax.random.normal(kd, (rows, ffn)).astype(dtype)
    return gu, d


def _plain(gu):
    """The three lines ``DroplessExperts.apply`` held before PR 35, as far
    as they lie between the products."""
    ffn = gu.shape[1] // 2
    return jax.nn.silu(gu[:, :ffn]) * gu[:, ffn:]


def _both(fn, gu, d):
    out, vjp = jax.vjp(fn, gu)
    return out, vjp(d)[0]


def test_the_tile_follows_the_width_and_the_type():
    for ffn in WIDTHS:
        # five blocks of a tile, each held twice, in 12 MiB of VMEM
        assert gr._tile(ROWS, ffn, 2) == gr._tile(131072, ffn, 2) == TILE
        assert gr._tile(ROWS, ffn, 4) == TILE // 2
        sub, cols = gr._piece(TILE, ffn)
        assert ffn % cols == 0 and cols % 128 == 0
        assert TILE % sub == 0 and sub * cols <= gr.WORK
    assert gr._tile(96, 24, 2) == 32 and gr._tile(7, 24, 2) is None
    assert gr._piece(8, 24) == (8, 24)


@pytest.mark.parametrize("filled", FILLS)
@pytest.mark.parametrize("ffn", WIDTHS)
def test_float32_against_the_plain_graph(ffn, filled):
    gu, d = _inputs(ffn, jnp.float32)
    f = jnp.asarray(filled, jnp.int32)
    got, dgot = _both(lambda x: gated_rows(x, f, impl="pallas"), gu, d)
    want, dwant = _both(_plain, gu, d)
    np.testing.assert_allclose(got[:filled], want[:filled], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(dgot[:filled], dwant[:filled], rtol=1e-6,
                               atol=4e-6)
    # zeros from the last filled row to the end of the last tile visited
    visited = int(rows_visited(f, gu, impl="pallas"))
    tile = TILE // 2                    # float32: half the rows of bfloat16
    assert visited == max(-(-filled // tile), 1) * tile
    assert not np.any(_f32(got[filled:visited]))
    assert not np.any(_f32(dgot[filled:visited]))


@pytest.mark.parametrize("filled", FILLS)
@pytest.mark.parametrize("ffn", WIDTHS)
def test_bfloat16_bit_for_bit_the_jax_numpy_form(ffn, filled):
    gu, d = _inputs(ffn, jnp.bfloat16)
    f = jnp.asarray(filled, jnp.int32)
    visited = int(rows_visited(f, gu, impl="pallas"))
    got, dgot = _both(lambda x: gated_rows(x, f, impl="pallas"), gu, d)
    want, dwant = _both(lambda x: gated_rows_xla(x, f), gu, d)
    assert got.dtype == dgot.dtype == jnp.bfloat16
    np.testing.assert_array_equal(_f32(got[:visited]), _f32(want[:visited]))
    np.testing.assert_array_equal(_f32(dgot[:visited, ffn:]),
                                  _f32(dwant[:visited, ffn:]))
    # dg: three float32 products, then the one rounding, which a last bit
    # of float32 can turn the other way: one step of bfloat16, 2 EPS
    np.testing.assert_allclose(_f32(dgot[:visited, :ffn]),
                               _f32(dwant[:visited, :ffn]), rtol=2 * EPS,
                               atol=1e-30)
    # and the one rounding from the plain graph taken in float32 (the sum
    # of ``dg``'s two terms may cancel: measured against their size)
    plain, dplain = _both(_plain, gu.astype(jnp.float32),
                          d.astype(jnp.float32))
    np.testing.assert_allclose(_f32(got[:filled]), plain[:filled],
                               rtol=1.01 * EPS, atol=1e-30)
    size = np.abs(_f32(d))[:filled] * np.maximum(
        1.0, np.abs(_f32(gu[:filled, :ffn])))
    for part in (slice(0, ffn), slice(ffn, None)):
        other = _f32(gu[:filled, ffn:]) if part.start == 0 else 1.0
        assert np.all(np.abs(_f32(dgot[:filled, part])
                             - dplain[:filled, part])
                      <= 1.01 * EPS * (np.abs(dplain[:filled, part])
                                    + size * np.abs(other)))


@pytest.mark.parametrize("filled", FILLS[:-1])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rows_past_filled_are_never_read(dtype, filled):
    """NaN in every input past ``filled`` changes no row below it and no
    gradient, and the tail of the last tile is zero all the same."""
    ffn = WIDTHS[0]
    gu, d = _inputs(ffn, jnp.dtype(dtype))
    f = jnp.asarray(filled, jnp.int32)
    fn = lambda x: gated_rows(x, f, impl="pallas")
    visited = int(rows_visited(f, gu, impl="pallas"))
    want, dwant = _both(fn, gu, d)
    got, dgot = _both(fn, gu.at[filled:].set(jnp.nan),
                      d.at[filled:].set(jnp.nan))
    np.testing.assert_array_equal(_f32(got[:visited]), _f32(want[:visited]))
    np.testing.assert_array_equal(_f32(dgot[:visited]),
                                  _f32(dwant[:visited]))
    assert not np.any(_f32(got[filled:visited]))
    assert not np.any(_f32(dgot[filled:visited]))
    # the jax.numpy form too, over all the rows
    got, dgot = _both(lambda x: gated_rows_xla(x, f),
                      gu.at[filled:].set(jnp.nan), d.at[filled:].set(jnp.nan))
    np.testing.assert_array_equal(_f32(got[:visited]), _f32(want[:visited]))
    assert not np.any(_f32(got[filled:])) and not np.any(_f32(dgot[filled:]))


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_under_checkpoint_inside_a_scan(dtype, impl):
    """As the models call it: a scanned stack of checkpointed layers, the
    rows filled another number in each. The gradient of every layer's
    input, and of what the layers share, against the jax.numpy form taken
    straight."""
    ffn, layers = 128, 3
    rows = 2048
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    gus = jax.random.normal(ks[0], (layers, rows, 2 * ffn)).astype(dtype)
    shared = jax.random.normal(ks[1], (rows, 2 * ffn)).astype(dtype)
    d = jax.random.normal(ks[2], (rows, ffn)).astype(dtype)
    fills = jnp.asarray([5, rows - 1, 300], jnp.int32)

    def total(fn, wrap, gus, shared):
        def layer(carry, xs):
            gu, f = xs
            return carry + wrap(fn)(gu + shared, f).astype(jnp.float32), None
        out, _ = jax.lax.scan(layer, jnp.zeros((rows, ffn)), (gus, fills))
        live = jnp.arange(rows)[:, None] < jnp.min(fills)
        return jnp.sum(jnp.where(live, out * d.astype(jnp.float32), 0.0))

    got = jax.jit(jax.value_and_grad(
        lambda *a: total(lambda gu, f: gated_rows(gu, f, impl=impl),
                         jax.checkpoint, *a), (0, 1)))(gus, shared)
    want = jax.value_and_grad(
        lambda *a: total(gated_rows_xla, lambda fn: fn, *a), (0, 1))(
            gus, shared)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" else dict(
        rtol=4 * EPS, atol=4 * EPS)
    np.testing.assert_allclose(got[0], want[0], rtol=tol["rtol"])
    for f, a, b in zip(fills, got[1][0], want[1][0]):
        np.testing.assert_allclose(_f32(a[:int(f)]), _f32(b[:int(f)]), **tol)
    np.testing.assert_allclose(_f32(got[1][1][:5]), _f32(want[1][1][:5]),
                               **tol)


def test_dispatch_and_the_rows_visited():
    gu, _ = _inputs(128, jnp.float32, rows=1024)
    f = jnp.asarray(300, jnp.int32)
    # off the chip 'auto' is the jax.numpy form, which passes over all rows
    np.testing.assert_array_equal(gated_rows(gu, f), gated_rows_xla(gu, f))
    assert int(rows_visited(f, gu)) == 1024
    assert int(rows_visited(f, gu, impl="pallas")) == 512
    for filled, want in ((0, 512), (512, 512), (513, 1024), (1024, 1024)):
        assert int(rows_visited(jnp.asarray(filled), gu,
                                impl="pallas")) == want
    with pytest.raises(ValueError, match="envelope"):
        gated_rows(gu[:1023], f, impl="pallas")
    with pytest.raises(ValueError, match="impl must be"):
        gated_rows(gu, f, impl="mosaic")

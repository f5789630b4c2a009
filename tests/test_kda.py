"""Kimi Delta Attention's chunked form (``apex_tpu/ops/kda.py``) against the
recurrence token by token: outputs, final state and every gradient, at chunk
sizes that do and do not divide the sequence, with an initial state, and at
decays strong enough that a chunk's cumulative log-decay passes -100, where
a factorisation that leaves float32's range gives inf or nan. Each through
the ``jax.numpy`` chunk-local part (``impl='xla'``) and through its two
kernels (``'pallas'``, interpret mode here): the forward's must compute what
``_local`` does output by output, the transpose's what ``jax.vjp`` of
``_local`` does cotangent by cotangent."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import kda as kda_module
from apex_tpu.ops.kda import (
    BLOCK,
    NEAR,
    _by_chunk,
    _decayed_products,
    _local,
    _local_kernel,
    _local_transpose,
    _unit_lower_inverse,
    chunk_log_decay,
    kda,
    kda_recurrent,
    kernel_chunks,
)

IMPLS = ("xla", "pallas")

B, H, DK, DV = 2, 3, 16, 24


def _inputs(seq, decay, seed=0, dtype=jnp.float32):
    """Normed q and k, a step in (0, 1), log-decays down to ``-decay`` a
    token and channel."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, H, seq, DK))).astype(dtype)
    k = unit(jax.random.normal(ks[1], (B, H, seq, DK))).astype(dtype)
    v = jax.random.normal(ks[2], (B, H, seq, DV)).astype(dtype)
    g = -decay * jax.random.uniform(ks[3], (B, H, seq, DK), minval=0.05,
                                    maxval=1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, H, seq)))
    state = jax.random.normal(ks[5], (B, H, DK, DV))
    return q, k, v, g, beta, state


def _both(fn, args, with_state=True):
    """``((o, final), gradients)`` of a weighted sum of both results."""
    *xs, state = args

    def loss(*a):
        o, final = fn(*a[:5], initial_state=a[5] if with_state else None)
        wo = jnp.cos(jnp.arange(o.size, dtype=jnp.float32)).reshape(o.shape)
        wf = jnp.sin(jnp.arange(final.size, dtype=jnp.float32)).reshape(
            final.shape)
        return (jnp.sum(o.astype(jnp.float32) * wo)
                + jnp.sum(final * wf)), (o, final)

    (_, out), grads = jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True)(*xs, state)
    return out, grads


@pytest.fixture
def transposes(monkeypatch):
    """Counts the transpose kernel's bodies traced (interpret mode traces
    one a grid step)."""
    seen = []
    body = kda_module._transpose_kernel

    def counted(*refs, **kw):
        seen.append(1)
        return body(*refs, **kw)

    monkeypatch.setattr(kda_module, "_transpose_kernel", counted)
    return seen


def _close(got, want, tol):
    for a, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, r, rtol=0,
                                   atol=tol * max(np.abs(r).max(), 1e-30))


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("seq,chunk,decay", [
    (100, 64, 0.1),     # mild decay, the sequence no whole number of chunks
    (128, 64, 2.0),     # a chunk's cumulative log-decay near -80
    (40, 16, 2.0),      # two and a half chunks
    (24, 8, 2.0),       # a chunk is one block of pairs: no halving at all
    (128, 64, 4.0),     # past -100 a chunk: exp(-G) alone is inf
    (64, 32, 30.0),     # -30 a token: past -600 a chunk
], ids=lambda x: str(x))
def test_chunked_equals_the_recurrence_and_so_do_its_gradients(
        seq, chunk, decay, impl, transposes):
    """With ``impl='pallas'`` the gradient goes through the transpose's
    kernel, and through nothing else for the chunk-local part."""
    args = _inputs(seq, decay)
    if decay >= 4.0:
        worst = float(chunk_log_decay(args[3], chunk).min())
        assert worst < -100.0, worst
        # the plain split of the decays is what this case plants
        assert not np.isfinite(np.exp(np.float32(-worst)))
    got = _both(lambda *a, **kw: kda(*a, chunk=chunk, impl=impl, **kw), args)
    assert bool(transposes) == (impl == "pallas")
    want = _both(kda_recurrent, args)
    _close(got, want, 2e-5)


def test_without_an_initial_state():
    args = _inputs(72, 1.0, seed=3)
    got = _both(lambda *a, **kw: kda(*a, chunk=32, **kw), args,
                with_state=False)
    want = _both(kda_recurrent, args, with_state=False)
    _close(got[0], want[0], 2e-5)
    _close(got[1][:5], want[1][:5], 2e-5)
    # no gradient reaches a state that was not given
    assert not np.any(np.asarray(got[1][5]))


def test_the_state_carries_from_one_call_to_the_next():
    q, k, v, g, beta, state = _inputs(96, 1.0, seed=5)
    whole, final = kda(q, k, v, g, beta, chunk=32, initial_state=state)
    cut = lambda x, a, b: x[:, :, a:b]
    first, mid = kda(*(cut(x, 0, 40) for x in (q, k, v, g, beta)), chunk=32,
                     initial_state=state)
    second, end = kda(*(cut(x, 40, 96) for x in (q, k, v, g, beta)),
                      chunk=32, initial_state=mid)
    _close((jnp.concatenate([first, second], 2), end), (whole, final), 2e-5)


@pytest.mark.parametrize("impl", IMPLS)
def test_bf16_operands_float32_state(impl):
    """The trainer's types: bf16 q, k, v, float32 decays and state. The
    products round their operands to bf16, so the result is the float32
    one to a few roundings of a last bit."""
    args = _inputs(128, 1.0, seed=7, dtype=jnp.bfloat16)
    o, final = kda(*args[:5], chunk=64, initial_state=args[5], impl=impl)
    ref_o, ref_final = kda_recurrent(*args[:5], initial_state=args[5])
    assert o.dtype == jnp.bfloat16 and final.dtype == jnp.float32
    _close((o, final), (ref_o, ref_final), 2e-2)
    grads = jax.grad(lambda q, k, v, g, beta: jnp.sum(
        kda(q, k, v, g, beta, chunk=64, impl=impl)[0].astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4))(*args[:5])
    assert [x.dtype for x in grads] == [x.dtype for x in args[:5]]
    assert all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
               for x in grads)


@pytest.mark.parametrize("impl", IMPLS)
def test_every_exponent_taken_is_at_most_zero(monkeypatch, impl,
                                              transposes):
    """The hazard, held at its root: through forward and backward at a
    decay of -30 a token, ``exp`` never sees a positive argument (inside the
    kernels too, the transpose's among them: interpret mode runs their
    bodies as ``jax.numpy``)."""
    seen = []
    real = jnp.exp

    def watched(x):
        jax.debug.callback(lambda m: seen.append(float(m)), jnp.max(x))
        return real(x)

    monkeypatch.setattr(jnp, "exp", watched)
    q, k, v, g, beta, _ = _inputs(64, 30.0)
    grad = jax.grad(lambda g: jnp.sum(
        kda(q, k, v, g, beta, chunk=32, impl=impl)[0]))(g)
    jax.block_until_ready(grad)
    jax.effects_barrier()
    assert len(seen) > 10 and max(seen) <= 0.0
    assert bool(transposes) == (impl == "pallas")


def test_decayed_products_pair_by_pair():
    c = 4 * NEAR
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (c, DK))
    y = jax.random.normal(ks[1], (c, DK))
    big_g = jnp.cumsum(-3.0 * jax.random.uniform(ks[2], (c, DK)), axis=0)
    for strict in (True, False):
        want = np.zeros((c, c), np.float64)
        for i in range(c):
            for j in range(i if strict else i + 1):
                want[i, j] = np.sum(
                    np.float64(x[i]) * np.float64(y[j])
                    * np.exp(np.float64(big_g[i]) - np.float64(big_g[j])))
        got = _decayed_products(x, y, big_g, strict, jnp.float32)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                                   atol=1e-5)
        assert not np.any(np.triu(np.asarray(got), 0 if strict else 1))


@pytest.mark.parametrize("product", ["highest", "bf16_6x"])
def test_unit_lower_inverse(product):
    """Through ``precision=HIGHEST`` products (the ``jax.numpy`` form) and
    through the kernel's: float32 split into three bf16 parts, six
    products of them as XLA:TPU's ``HIGHEST`` takes them, which agree with
    a float64 product to float32's rounding."""
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(2), (3, 64, 64)), -1)
    eye = jnp.eye(64)
    if product == "highest":
        inv = _unit_lower_inverse(0.2 * a)
    else:
        inv = jax.vmap(lambda x: _unit_lower_inverse(
            x, kda_module._split3, kda_module._highest))(0.2 * a)
        x, y = np.asarray(a[0], np.float64), np.asarray(a[1], np.float64)
        got = kda_module._highest(kda_module._split3(a[0]),
                                  kda_module._split3(a[1]))
        assert np.abs(np.asarray(got) - x @ y).max() < 1e-6 * np.abs(
            x @ y).max()
    np.testing.assert_allclose(
        np.asarray(jnp.matmul(eye + 0.2 * a, inv, precision="highest")),
        np.broadcast_to(np.asarray(eye), (3, 64, 64)), atol=1e-4)


def test_refused_chunks():
    q, k, v, g, beta, _ = _inputs(32, 0.1)
    for chunk in (12, 24, 60):
        with pytest.raises(ValueError, match="power of two"):
            kda(q, k, v, g, beta, chunk=chunk)
    with pytest.raises(ValueError, match="power of two"):
        kda(q, k, v, g, beta, chunk=4)


@pytest.mark.parametrize("chunk,decay,dtype", [
    (NEAR, 2.0, "float32"),     # a chunk is one block of pairs
    (32, 2.0, "float32"),
    (64, 30.0, "float32"),      # -30 a token: past -1,900 a chunk
    (64, 1.0, "bfloat16"),      # the trainer's types
], ids=lambda x: str(x))
def test_the_kernel_computes_what_local_does(chunk, decay, dtype):
    """The six results, one by one, with the chunks in front as the scan
    reads them: the float32 ones to float32's rounding, the bf16 ones to a
    last bit of bf16 (a sum in another order can round the other way)."""
    dtype = jnp.dtype(dtype)
    q, k, v, g, beta, _ = _inputs(3 * chunk, decay, seed=11, dtype=dtype)
    parts = lambda x: x.reshape(B * H, 3, chunk, *x.shape[3:])
    args = tuple(parts(x) for x in (q, k, v, g, beta))
    want = _local(*args, dtype)
    got = _local_kernel(*args, nb=2)
    assert len(got) == len(want) == 6
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    for a, r in zip(got, want):
        assert a.dtype == r.dtype and a.shape == (3, B * H, *r.shape[2:])
        _close(jnp.moveaxis(a, 0, 1), r, tol)


@pytest.mark.parametrize("chunk", [32, 64, 128])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("steep", [False, True], ids=["mild", "steep"])
def test_the_transpose_kernel_computes_what_vjp_of_local_does(chunk, dtype,
                                                              steep):
    """The five cotangents, one by one, given random cotangents of the six
    results with the chunks in front, as the reverse scan leaves them;
    steep decays put a chunk's cumulative log-decay past -88, where the
    plain split of the decays would leave float32. float32 to a few
    roundings of the running sum ``G`` (the decays' exponents are
    differences of it, -600 deep at 30 a token), bf16 to a rounding or two
    of bf16 (the ``jax.numpy`` form rounds ``T``'s and the right-hand
    sides' cotangents to bf16, the kernel keeps them float32)."""
    dtype = jnp.dtype(dtype)
    decay = {32: 30.0, 64: 4.0, 128: 2.0}[chunk] if steep else 0.1
    q, k, v, g, beta, _ = _inputs(3 * chunk, decay, seed=17, dtype=dtype)
    assert (float(chunk_log_decay(g, chunk).min()) < -88.0) == steep
    parts = lambda x: x.reshape(B * H, 3, chunk, *x.shape[3:])
    args = tuple(parts(x) for x in (q, k, v, g, beta))
    local = lambda *xs: _local(*xs, dtype)
    keys = jax.random.split(jax.random.PRNGKey(chunk), 6)
    cts = tuple(jax.random.normal(key, r.shape).astype(r.dtype) for key, r
                in zip(keys, jax.eval_shape(local, *args)))
    want = jax.vjp(local, *args)[1](cts)
    got = _local_transpose(*args, _by_chunk(cts), nb=2)
    assert len(got) == len(want) == 5
    tol = 1e-4 if dtype == jnp.float32 else 1.5e-2
    for a, r in zip(got, want):
        assert a.dtype == r.dtype and a.shape == r.shape
        _close(a, r, tol)


def test_auto_takes_the_kernel_inside_its_envelope_alone(monkeypatch):
    """On the chip (its path forced on here) 'auto' takes the kernel for
    lane-wide heads and chunks of 64, and today's code, with a warning, for
    the tiny config's 16-wide heads; an explicit 'pallas' there refuses."""
    from apex_tpu.ops import layer_norm

    monkeypatch.setattr(layer_norm, "_on_tpu", lambda: True)
    wide = jax.ShapeDtypeStruct((1, 8, 8192, 128), jnp.bfloat16)
    assert kernel_chunks(wide, wide, chunk=64) == 8 * 128
    assert kda_module._block(8, 64, 128, 128, "auto") == BLOCK
    args = _inputs(64, 1.0, seed=13)
    with pytest.warns(UserWarning, match="envelope"):
        assert kernel_chunks(args[0], args[2], chunk=32) == 0
    with pytest.warns(UserWarning, match="envelope"):
        got = _both(lambda *a, **kw: kda(*a, chunk=32, **kw), args)
    # today's code, which runs here: the kernel would need the chip
    _close(got, _both(lambda *a, **kw: kda(*a, chunk=32, impl="xla", **kw),
                      args), 0.0)
    with pytest.raises(ValueError, match="envelope"):
        kda(*args[:5], chunk=32, impl="pallas")

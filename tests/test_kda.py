"""Kimi Delta Attention's chunked form (``apex_tpu/ops/kda.py``) against the
recurrence token by token: outputs, final state and every gradient, at chunk
sizes that do and do not divide the sequence, with an initial state, and at
decays strong enough that a chunk's cumulative log-decay passes -100, where
a factorisation that leaves float32's range gives inf or nan."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.kda import (
    NEAR,
    _decayed_products,
    _unit_lower_inverse,
    chunk_log_decay,
    kda,
    kda_recurrent,
)

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


def _close(got, want, tol):
    for a, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, r, rtol=0,
                                   atol=tol * max(np.abs(r).max(), 1e-30))


@pytest.mark.parametrize("seq,chunk,decay", [
    (100, 64, 0.1),     # mild decay, the sequence no whole number of chunks
    (128, 64, 2.0),     # a chunk's cumulative log-decay near -80
    (40, 16, 2.0),      # two and a half chunks
    (24, 8, 2.0),       # a chunk is one block of pairs: no halving at all
    (128, 64, 4.0),     # past -100 a chunk: exp(-G) alone is inf
    (64, 32, 30.0),     # -30 a token: past -600 a chunk
], ids=lambda x: str(x))
def test_chunked_equals_the_recurrence_and_so_do_its_gradients(seq, chunk,
                                                               decay):
    args = _inputs(seq, decay)
    if decay >= 4.0:
        worst = float(chunk_log_decay(args[3], chunk).min())
        assert worst < -100.0, worst
        # the plain split of the decays is what this case plants
        assert not np.isfinite(np.exp(np.float32(-worst)))
    got = _both(lambda *a, **kw: kda(*a, chunk=chunk, **kw), args)
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


def test_bf16_operands_float32_state():
    """The trainer's types: bf16 q, k, v, float32 decays and state. The
    products round their operands to bf16, so the result is the float32
    one to a few roundings of a last bit."""
    args = _inputs(128, 1.0, seed=7, dtype=jnp.bfloat16)
    o, final = kda(*args[:5], chunk=64, initial_state=args[5])
    ref_o, ref_final = kda_recurrent(*args[:5], initial_state=args[5])
    assert o.dtype == jnp.bfloat16 and final.dtype == jnp.float32
    _close((o, final), (ref_o, ref_final), 2e-2)
    grads = jax.grad(lambda q, k, v, g, beta: jnp.sum(
        kda(q, k, v, g, beta, chunk=64)[0].astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4))(*args[:5])
    assert [x.dtype for x in grads] == [x.dtype for x in args[:5]]
    assert all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
               for x in grads)


def test_every_exponent_taken_is_at_most_zero(monkeypatch):
    """The hazard, held at its root: through forward and backward at a
    decay of -30 a token, ``exp`` never sees a positive argument."""
    seen = []
    real = jnp.exp

    def watched(x):
        jax.debug.callback(lambda m: seen.append(float(m)), jnp.max(x))
        return real(x)

    monkeypatch.setattr(jnp, "exp", watched)
    q, k, v, g, beta, _ = _inputs(64, 30.0)
    grad = jax.grad(lambda g: jnp.sum(kda(q, k, v, g, beta, chunk=32)[0]))(g)
    jax.block_until_ready(grad)
    jax.effects_barrier()
    assert len(seen) > 10 and max(seen) <= 0.0


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


def test_unit_lower_inverse():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(2), (3, 64, 64)), -1)
    eye = jnp.eye(64)
    np.testing.assert_allclose(
        np.asarray(jnp.matmul(eye + 0.2 * a, _unit_lower_inverse(0.2 * a),
                              precision="highest")),
        np.broadcast_to(np.asarray(eye), (3, 64, 64)), atol=1e-4)


def test_refused_chunks():
    q, k, v, g, beta, _ = _inputs(32, 0.1)
    for chunk in (12, 24, 60):
        with pytest.raises(ValueError, match="power of two"):
            kda(q, k, v, g, beta, chunk=chunk)
    with pytest.raises(ValueError, match="power of two"):
        kda(q, k, v, g, beta, chunk=4)

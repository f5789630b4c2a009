"""The row movers of ``DroplessExperts`` (``spread_rows``, ``collect_rows``,
``sort_with`` and the plan ``place`` makes for them) against a float32
scatter-add written here: values and the gradients of the tokens, the
weights and the buffer, at the loads that bend a loop over the filled rows:
none, one trip, one row past a trip, the whole buffer, more than it holds.
And the counter that says the movers follow the load."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.transformer import moe
from apex_tpu.transformer.moe import DroplessExperts

N, D, K, HELD, FIRST = 1024, 16, 3, 4, 4


def _layer(experts: int = 16) -> DroplessExperts:
    return DroplessExperts(D, 8, experts, K, held=HELD, first_held=FIRST)


def _to(*experts) -> np.ndarray:
    """Every token chooses ``experts``."""
    return np.tile(np.asarray(experts, np.int32), (N, 1))


def _even() -> np.ndarray:
    rng = np.random.default_rng(0)
    return np.argsort(rng.random((N, 16)), axis=1)[:, :K].astype(np.int32)


def _past_a_trip() -> np.ndarray:
    """``MOVE_ROWS`` + 1 assignments held: 683 tokens' three choices."""
    assert moe.MOVE_ROWS == 2048
    chosen = _to(0, 1, 2)
    chosen[:683] = (4, 6, 7)
    return chosen


#: name: (experts the router scores, the choices, rows filled, rows lost)
CASES = {
    "even": (16, _even, None, 0),
    "all_to_the_first_held": (16, lambda: _to(4, 0, 1), N, 0),
    "all_to_the_last_held": (16, lambda: _to(0, 7, 1), N, 0),
    "none_held": (16, lambda: _to(0, 1, 2), 0, 0),
    "the_whole_buffer": (16, lambda: _to(4, 5, 7), 3 * N, 0),
    "one_past_a_trip": (16, _past_a_trip, 2049, 0),
    "more_than_the_buffer": (32, lambda: _to(4, 5, 6), 3 * N // 2,
                             3 * N // 2),
}


def _rows_of(chosen: np.ndarray, rows: int) -> np.ndarray:
    """The buffer row of each assignment, ``rows`` for none: held experts
    first, by expert then token, as far as the buffer goes."""
    local = chosen.reshape(-1) - FIRST
    key = np.where((local >= 0) & (local < HELD), local, HELD)
    order = np.argsort(key, kind="stable")
    rank = np.empty_like(order)
    rank[order] = np.arange(order.size)
    filled = min(int((key < HELD).sum()), rows)
    return np.where(rank < filled, rank, rows).reshape(chosen.shape)


def _reference(x, weights, b, at, rows):
    """Scatter the tokens into the buffer, multiply row by row with ``b``,
    scatter-add back weighted: float32 throughout."""
    x, weights, b = (a.astype(jnp.float32) for a in (x, weights, b))
    tok = jnp.broadcast_to(jnp.arange(x.shape[0])[:, None], at.shape)
    xb = jnp.zeros((rows + 1, x.shape[1])).at[at.reshape(-1)].set(
        x[tok.reshape(-1)])[:rows]
    yb = jnp.concatenate([xb * b, jnp.zeros((1, x.shape[1]))])
    return jnp.zeros_like(x).at[tok.reshape(-1)].add(
        yb[at.reshape(-1)] * weights.reshape(-1, 1))


def _moved(layer, chosen, x, weights, b):
    plan, wb, _ = layer.place(chosen, weights)
    return moe.collect_rows(moe.spread_rows(x, plan) * b, wb, plan)


@pytest.mark.parametrize("case", CASES)
def test_movers_against_a_float32_scatter_add(case):
    experts, choices, filled, lost = CASES[case]
    layer, chosen = _layer(experts), choices()
    rows = layer.buffer_rows(N)
    at = _rows_of(chosen, rows)
    kx, kw, kb, kg = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(kx, (N, D))
    weights = jax.random.uniform(kw, (N, K), minval=0.5, maxval=1.5)
    b = jax.random.normal(kb, (rows, D))
    g = jax.random.normal(kg, (N, D))

    plan, _, counts = layer.place(jnp.asarray(chosen), weights)
    if filled is not None:
        assert int(plan["filled"]) == filled
    assert int(counts.sum()) - int(plan["filled"]) == lost
    assert int((at < rows).sum()) == int(plan["filled"])

    got, got_grads = jax.value_and_grad(
        lambda *a: jnp.sum(_moved(layer, jnp.asarray(chosen), *a) * g),
        argnums=(0, 1, 2))(x, weights, b)
    want, want_grads = jax.value_and_grad(
        lambda *a: jnp.sum(_reference(*a, jnp.asarray(at), rows) * g),
        argnums=(0, 1, 2))(x, weights, b)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for name, a, w in zip(("x", "weights", "buffer"), got_grads, want_grads):
        np.testing.assert_allclose(a, w, rtol=1e-5, atol=1e-5, err_msg=name)
    out = _moved(layer, jnp.asarray(chosen), x, weights, b)
    np.testing.assert_allclose(
        out, _reference(x, weights, b, jnp.asarray(at), rows),
        rtol=1e-5, atol=1e-5)
    if filled == 0:
        assert not np.any(np.asarray(out))


def test_a_tokens_rows_are_summed_in_float32_and_rounded_once():
    """Three rows of one token in bfloat16: 256 + 1 + 1 is 258 when summed
    in float32 and 256 when each partial sum is rounded."""
    layer, chosen = _layer(), jnp.asarray(_to(4, 5, 7))
    plan, wb, _ = layer.place(chosen, jnp.ones((N, K)))
    rows = layer.buffer_rows(N)
    expert = np.arange(rows) // N          # rows lie by expert, then token
    buf = jnp.asarray(np.where(expert == 0, 256.0, 1.0)[:, None]
                      * np.ones((1, D)), jnp.bfloat16)
    out = moe.collect_rows(buf, wb, plan)
    assert out.dtype == jnp.bfloat16
    assert np.all(np.asarray(out, np.float32) == 258.0)


@pytest.mark.parametrize("held_choices,trips", [(1, 1), (3, 2)])
def test_rows_moved_follows_the_assignments_not_the_buffer(
        held_choices, trips):
    """One choice of three held, then all three: the forward movers touch
    about 2 x assignments + N rows, to the trip, and never the buffer's
    98,304-to-24,576 of ``rows + N * top_k``."""
    layer = _layer()
    p = layer.init(jax.random.PRNGKey(0))
    to = [4, 5, 7][:held_choices] + [0, 1, 2][:K - held_choices]
    p["router"] = {"kernel": jnp.zeros_like(p["router"]["kernel"]),
                   "bias": jnp.zeros((16,)).at[jnp.asarray(to)].set(1.0)}
    x = jax.random.normal(jax.random.PRNGKey(2), (N, D))
    _, stats = layer.apply(p, x)
    held, rows = held_choices * N, layer.buffer_rows(N)
    assert float(stats["assignments"]) == held
    moved = float(stats["rows_moved"])
    assert moved == trips * (2 * moe.MOVE_ROWS + K) + N
    assert 2 * held + N <= moved <= 2 * held + N + trips * (
        2 * moe.MOVE_ROWS + K)
    assert moved != rows + N * K

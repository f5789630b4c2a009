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


# -- the experts between the movers (PR 35) ------------------------------------

from apex_tpu.ops import gated_rows as gated  # noqa: E402

FFN = 8


def _parent_apply(layer, params, h):
    """``DroplessExperts.apply`` with the three lines it held between
    ``spread_rows`` and ``collect_rows`` before PR 35: two products over
    ``gate`` and ``up`` apart, XLA's ``silu`` and product over every row."""
    x = h.reshape(-1, h.shape[-1])
    rows = layer.buffer_rows(x.shape[0])
    chosen, weights = layer.route(params["router"], x)
    plan, wb, counts = layer.place(chosen, weights)
    sizes = jnp.diff(jnp.minimum(jnp.cumsum(counts), rows), prepend=0)
    xb = moe.spread_rows(x, plan)
    e, dt = params["experts"], x.dtype
    act = jax.nn.silu(jax.lax.ragged_dot(xb, e["gate"].astype(dt), sizes))
    act = act * jax.lax.ragged_dot(xb, e["up"].astype(dt), sizes)
    yb = jax.lax.ragged_dot(act, e["down"].astype(dt), sizes)
    return moe.collect_rows(yb, wb, plan).reshape(h.shape)


#: name: (experts the router scores, the selection bias of some of them)
LOADS = {
    "even": (16, {}),
    "one_expert_at_twice_the_mean": (16, {5: 0.4}),
    "an_expert_with_no_row": (16, {6: -10.0}),
    "more_than_the_buffer": (32, {4: 10.0, 5: 10.0, 6: 10.0}),
}


def _loaded(case, dtype=jnp.float32):
    experts, bias = LOADS[case]
    layer = DroplessExperts(D, FFN, experts, K, held=HELD, first_held=FIRST,
                            params_dtype=dtype,
                            init_method=moe.tp.scaled_normal(0.5))
    p = layer.init(jax.random.PRNGKey(0))
    p["router"]["bias"] = jnp.zeros((experts,), dtype).at[
        jnp.asarray(list(bias), jnp.int32)].set(
            jnp.asarray(list(bias.values()), dtype))
    kh, kg = jax.random.split(jax.random.PRNGKey(5))
    h = jax.random.normal(kh, (2, N // 2, D), dtype)
    return layer, p, h, jax.random.normal(kg, (2, N // 2, D), dtype)


@pytest.fixture(params=["xla", "pallas"])
def impl(request, monkeypatch):
    """The layer takes no ``impl``: off the chip its 'auto' is the
    ``jax.numpy`` form, and the kernels (interpret mode) by this patch."""
    monkeypatch.setattr(gated, "_resolve_impl", lambda _: request.param)
    return request.param


@pytest.mark.parametrize("case", LOADS)
def test_the_layer_against_the_three_lines_it_held(case, impl):
    layer, p, h, g = _loaded(case)
    rows = layer.buffer_rows(N)
    def mine(p, h):
        out, stats = layer.apply(p, h)
        return jnp.sum(out * g), (out, stats)

    def parents(p, h):
        out = _parent_apply(layer, p, h)
        return jnp.sum(out * g), out

    (got, (out, stats)), got_grads = jax.value_and_grad(
        mine, argnums=(0, 1), has_aux=True)(p, h)
    (want, want_out), want_grads = jax.value_and_grad(
        parents, argnums=(0, 1), has_aux=True)(p, h)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(out, want_out, rtol=1e-5, atol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path((got_grads, want_grads))
    named = {jax.tree_util.keystr(k): v for k, v in flat}
    for name in ("['router']['kernel']", "['experts']['gate']",
                 "['experts']['up']", "['experts']['down']"):
        a, w = named["[0][0]" + name], named["[1][0]" + name]
        assert np.any(np.asarray(w)), name
        np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-5 * float(
            jnp.max(jnp.abs(w))), err_msg=name)
    np.testing.assert_allclose(named["[0][1]"], named["[1][1]"], rtol=1e-4,
                               atol=1e-5 * float(jnp.max(jnp.abs(
                                   named["[1][1]"]))), err_msg="h")
    # the loads are what the case says
    filled = float(stats["assignments"] - stats["overflow"])
    if case == "even":
        assert float(stats["max_load_over_mean"]) < 1.2
    elif case == "one_expert_at_twice_the_mean":
        assert 1.7 < float(stats["max_load_over_mean"]) < 2.3
    elif case == "an_expert_with_no_row":
        _, _, counts = layer.place(*layer.route(p["router"],
                                                h.reshape(-1, D)))
        assert int(counts[6 - FIRST]) == 0 and int(counts.min()) == 0
    else:
        assert float(stats["overflow"]) == 3 * N - rows > 0
        assert filled == rows
    # and the experts' kernels walk the rows filled, to the tile
    tile = gated._tile(rows, FFN, 4)
    assert float(stats["expert_rows"]) == (
        rows if impl == "xla" else max(-(-int(filled) // tile), 1) * tile)


def test_the_layer_in_bfloat16_against_the_three_lines_it_held(impl):
    """Under O2 the layer runs in bfloat16: the same against the three
    lines, to the roundings a sum of 16 and 8 terms of bfloat16 leaves."""
    layer, p, h, g = _loaded("even", jnp.bfloat16)
    f32 = lambda t: jax.tree.map(lambda a: np.asarray(a, np.float32), t)
    got = f32(jax.grad(lambda p, h: jnp.sum(
        (layer.apply(p, h)[0] * g).astype(jnp.float32)), (0, 1))(p, h))
    want = f32(jax.grad(lambda p, h: jnp.sum(
        (_parent_apply(layer, p, h) * g).astype(jnp.float32)), (0, 1))(p, h))
    for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.linalg.norm(a - w) <= 0.02 * np.linalg.norm(w) + 1e-12


@pytest.mark.parametrize("held_choices", [1, 3])
def test_expert_rows_follow_the_assignments_not_the_buffer(
        held_choices, monkeypatch):
    """The twin of ``rows_moved``: one choice of three held, then all
    three. The kernels between the products visit whole tiles up to the
    rows filled, a third of the buffer and then all of it; the
    ``jax.numpy`` form passes over every row and says so."""
    layer = _layer()
    p = layer.init(jax.random.PRNGKey(0))
    to = [4, 5, 7][:held_choices] + [0, 1, 2][:K - held_choices]
    p["router"] = {"kernel": jnp.zeros_like(p["router"]["kernel"]),
                   "bias": jnp.zeros((16,)).at[jnp.asarray(to)].set(1.0)}
    x = jax.random.normal(jax.random.PRNGKey(2), (N, D))
    rows = layer.buffer_rows(N)
    assert float(layer.apply(p, x)[1]["expert_rows"]) == rows == 3 * N
    monkeypatch.setattr(gated, "_resolve_impl", lambda _: "pallas")
    out, stats = layer.apply(p, x)
    assert float(stats["assignments"]) == held_choices * N
    assert float(stats["expert_rows"]) == held_choices * N
    assert N % gated._tile(rows, 8, 4) == 0
    np.testing.assert_allclose(out, _parent_apply(layer, p, x), rtol=1e-5,
                               atol=1e-6)


def test_the_parameter_tree_is_the_parents():
    """``gate`` and ``up`` stay two leaves: the references and the
    benchmark's adapters place seeded weights in this tree. They are joined
    in the step, for the length of one product."""
    p = jax.eval_shape(_layer().init, jax.random.PRNGKey(0))
    shapes = {jax.tree_util.keystr(k): v.shape
              for k, v in jax.tree_util.tree_flatten_with_path(p)[0]}
    assert shapes == {
        "['router']['kernel']": (D, 16), "['router']['bias']": (16,),
        "['experts']['gate']": (HELD, D, 8),
        "['experts']['up']": (HELD, D, 8),
        "['experts']['down']": (HELD, 8, D)}
    assert DroplessExperts.BUFFER_FACTOR == 4

"""Kimi Delta Attention's token mixer (arXiv:2510.26692, "Kimi Linear"; the
flash-linear-attention project's ``naive_recurrent_kda`` states the same
recurrence). No reference analog: apex has no recurrence over tokens.

For one head, with a state ``S`` of ``(d_k, d_v)``, zero at a sequence's
start, a log-decay ``g_t <= 0`` for every key channel and a step
``beta_t``:

    S' = diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T (d_k^-0.5 q_t)

:func:`kda_recurrent` is that, token by token: the oracle of the tests and
not the trainer's path. :func:`kda` is the chunked form. Inside a chunk of
``C`` tokens, with ``G_i`` the cumulative log-decay from the chunk's start
to token ``i`` and ``u_i = beta_i (v_i - S'_i^T k_i)``,

    (I + A) U = beta * V - (beta * K * exp(G)) S_0
    O = (d_k^-0.5 Q * exp(G)) S_0 + B U
    S_C = diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)      (j < i)
    B_ij = d_k^-0.5 sum_c q_ic k_jc exp(G_ic - G_jc)    (j <= i)

so everything but two products with ``S_0`` is the same for every chunk and
runs for all chunks at once (``T = (I + A)^-1``, ``W = T (beta K exp(G))``,
``U_0 = T (beta V)``: a WY representation), and a scan over the chunks
carries the state: ``U = U_0 - W S_0``.

**The decays never leave float32's range.** ``exp(G_ic - G_jc)`` is a
decay between two tokens and at most 1, but it differs by channel, so it
stands inside the sum over channels and a product of matrices needs it
split into a factor for ``i`` and one for ``j``. The plain split,
``exp(G_i)`` and ``exp(-G_j)``, overflows once a chunk's cumulative decay
passes e^88, which 64 tokens at -1.6 a token do. Here every factor is a
decay towards a point that lies between the two tokens: the chunk is halved
again and again down to blocks of :data:`NEAR` tokens, the pairs with ``i``
in a block's second half and ``j`` in its first are split at the last token
of the first half (``exp(G_i - G_ref)`` and ``exp(G_ref - G_j)``, both
exponents at most 0), and inside a block of :data:`NEAR` the decays are
taken pair by pair, ``exp(G_i - G_j)`` itself. No exponent is ever above 0,
whatever ``g``; a factor that flushes to zero belongs to a pair whose decay
is below float32's least value.

Operands of the products are in the inputs' type (bf16 in the trainer);
the state, the decays, the sums and ``T`` are float32. The gradient is
chunked too (``custom_vjp``): the forward keeps each chunk's incoming state
and nothing else, the backward runs the scan in reverse and then the
chunk-local part's transpose for all chunks at once. The chunk-local part
runs a group of heads at a time, both ways, so that its float32 temporaries
stay a fraction of an operand's size (all heads at once, the step of the
Kimi Linear cell held 9.9 GB of them where it holds 4.2, compiled for a
described v5e). The caller names the scope.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

#: tokens in a block whose decays are taken pair by pair
NEAR = 8

_HIGHEST = lax.Precision.HIGHEST
_F32 = jnp.float32


def kda_recurrent(q, k, v, g, beta, *,
                  initial_state=None) -> Tuple[jax.Array, jax.Array]:
    """The recurrence token by token in float32: ``q``, ``k``, ``g`` of
    ``(batch, heads, seq, d_k)``, ``v`` of ``(batch, heads, seq, d_v)``,
    ``beta`` of ``(batch, heads, seq)``. Returns ``(o, final_state)``, ``o``
    in ``v``'s type and the state ``(batch, heads, d_k, d_v)`` in float32."""
    b, h, _, dk = q.shape
    scale = dk ** -0.5
    state = (jnp.zeros((b, h, dk, v.shape[-1]), _F32)
             if initial_state is None else initial_state.astype(_F32))

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt,
                                             precision=_HIGHEST))
        s = s + kt[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt * scale,
                             precision=_HIGHEST)

    by_token = [jnp.moveaxis(x.astype(_F32), 2, 0)
                for x in (q, k, v, g, beta)]
    state, o = lax.scan(step, state, by_token)
    return jnp.moveaxis(o, 0, 2).astype(v.dtype), state


def chunk_log_decay(g: jax.Array, chunk: int = 64) -> jax.Array:
    """``(..., chunks, d_k)``: the cumulative log-decay over each chunk of
    ``chunk`` tokens of ``g`` ``(..., seq, d_k)``; how near the plain split
    of the decays would run to float32's range (``exp(88)``)."""
    seq, dk = g.shape[-2:]
    pad = -seq % chunk
    g = jnp.pad(g.astype(_F32), [(0, 0)] * (g.ndim - 2) + [(0, pad), (0, 0)])
    return jnp.sum(g.reshape(*g.shape[:-2], -1, chunk, dk), axis=-2)


# -- the chunk-local part ------------------------------------------------------

def _mm(a, b, dtype):
    """``a @ b`` with operands in ``dtype``, summed in float32."""
    return jnp.matmul(a.astype(dtype), b.astype(dtype),
                      preferred_element_type=_F32)


def _blocks_on_diagonal(blocks):
    """``(..., n, r, c)`` blocks as one ``(..., n * r, n * c)`` matrix with
    block ``i`` at ``(i, i)`` and zeros elsewhere."""
    n, r, c = blocks.shape[-3:]
    out = blocks[..., :, :, None, :] * jnp.eye(n, dtype=blocks.dtype)[
        :, None, :, None]
    return out.reshape(*blocks.shape[:-3], n * r, n * c)


def _near_products(x, y, big_g, strict: bool):
    """The pairs inside each block of :data:`NEAR` tokens, decay by decay:
    ``sum_c x_ic y_jc exp(G_ic - G_jc)`` for ``j <= i`` (``j < i`` if
    ``strict``), ``(..., blocks, NEAR, NEAR)`` from operands ``(..., blocks,
    NEAR, d_k)``. The decays of all pairs and channels are eight times an
    operand, which is why the chunk-local part runs a group of heads at a
    time (:func:`_in_groups`)."""
    i = lax.broadcasted_iota(jnp.int32, (NEAR, NEAR, 1), 0)
    j = lax.broadcasted_iota(jnp.int32, (NEAR, NEAR, 1), 1)
    keep = (j < i) if strict else (j <= i)
    decay = jnp.exp(jnp.where(
        keep, big_g[..., :, None, :] - big_g[..., None, :, :], -jnp.inf))
    return jnp.sum(x[..., :, None, :] * y[..., None, :, :] * decay, axis=-1)


def _decayed_products(x, y, big_g, strict: bool, dtype):
    """``M_ij = sum_c x_ic y_jc exp(G_ic - G_jc)`` for ``j <= i`` (``j <
    i`` if ``strict``) and 0 above, over the last two axes ``(C, d_k)`` of
    float32 ``x``, ``y`` and ``G``. Every exponent taken is at most 0 (the
    module's docstring says how)."""
    c, dk = x.shape[-2:]
    lead = x.shape[:-2]
    near = lambda a: a.reshape(*lead, c // NEAR, NEAR, dk)
    out = _blocks_on_diagonal(
        _near_products(near(x), near(y), near(big_g), strict))
    half = c // 2
    while half >= NEAR:
        halves = lambda a: a.reshape(*lead, c // (2 * half), 2, half, dk)
        xh, yh, gh = halves(x), halves(y), halves(big_g)
        ref = gh[..., 0, half - 1:half, :]      # last token of a first half
        rows = xh[..., 1, :, :] * jnp.exp(gh[..., 1, :, :] - ref)
        cols = yh[..., 0, :, :] * jnp.exp(ref - gh[..., 0, :, :])
        block = _mm(rows, jnp.swapaxes(cols, -1, -2), dtype)
        # the second half's rows against the first half's columns
        block = jnp.pad(block, [(0, 0)] * (block.ndim - 2)
                        + [(half, 0), (0, half)])
        out = out + _blocks_on_diagonal(block)
        half //= 2
    return out


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower triangular ``a`` of ``(..., C,
    C)``, in float32: ``a^C = 0``, so the inverse is the product
    ``(I - a)(I + a^2)(I + a^4) ...`` up to the power ``C / 2``."""
    c = a.shape[-1]
    mm = lambda x, y: jnp.matmul(x, y, precision=_HIGHEST)
    inv = jnp.eye(c, dtype=a.dtype) - a
    power = mm(a, a)
    for step in range(int(math.log2(c)) - 1):
        if step:
            power = mm(power, power)
        inv = inv + mm(inv, power)
    return inv


def _local(q, k, v, g, beta, dtype):
    """What a chunk computes without its incoming state, for all chunks at
    once. Operands ``(heads, chunks, C, d)``, ``beta`` ``(heads, chunks, C)``.
    Returns ``W``, ``U_0``, ``B``, ``d_k^-0.5 Q * exp(G)``, ``K * exp(G_C -
    G)`` in ``dtype`` and ``exp(G_C)`` in float32."""
    big_g = jnp.cumsum(g.astype(_F32), axis=-2)
    end = big_g[..., -1:, :]
    qf, kf = q.astype(_F32) * q.shape[-1] ** -0.5, k.astype(_F32)
    bf = beta.astype(_F32)[..., None]
    inv = _unit_lower_inverse(
        bf * _decayed_products(kf, kf, big_g, True, dtype))
    w = _mm(inv, bf * kf * jnp.exp(big_g), dtype)
    u0 = _mm(inv, bf * v.astype(_F32), dtype)
    b = _decayed_products(qf, kf, big_g, False, dtype)
    return (w.astype(dtype), u0.astype(dtype), b.astype(dtype),
            (qf * jnp.exp(big_g)).astype(dtype),
            (kf * jnp.exp(end - big_g)).astype(dtype),
            jnp.exp(end[..., 0, :]))


def _carry(state, w, u0, b, qg, kg, end_decay, dtype):
    """One chunk given its incoming state ``(heads, d_k, d_v)``: ``(outgoing
    state, outputs)``, both float32."""
    u = u0.astype(_F32) - _mm(w, state, dtype)
    o = _mm(qg, state, dtype) + _mm(b, u, dtype)
    state = end_decay[..., None] * state + _mm(
        jnp.swapaxes(kg, -1, -2), u, dtype)
    return state, o


def _by_chunk(parts):
    """``(heads, chunks, ...)`` arrays with the chunks in front, for a
    scan."""
    return tuple(jnp.moveaxis(p, 1, 0) for p in parts)


#: float32 bytes of one ``(tokens, d_k)`` operand that the chunk-local part
#: works on at a time: it makes a few dozen temporaries of that size, and
#: the decays of the near pairs eight times it
_GROUP_BYTES = 16 * 2 ** 20


def _in_groups(fn, args):
    """``fn`` over the leading axis (a sequence's heads, all sequences
    together) of every array of ``args``, in as few groups of heads as keep
    a group's operand within ``_GROUP_BYTES``, one group after another: the
    chunk-local part is the same for every head, and what it holds at a
    time is a group's."""
    n = args[0].shape[0]
    row = 4 * args[0][0].size
    groups = next(g for g in range(1, n + 1)
                  if n % g == 0 and n // g * row <= max(_GROUP_BYTES, row))
    if groups == 1:
        return fn(*args)
    cut = lambda x: x.reshape(groups, n // groups, *x.shape[1:])
    out = lax.map(lambda xs: fn(*xs), tuple(cut(x) for x in args))
    return jax.tree.map(lambda x: x.reshape(n, *x.shape[2:]), out)


@jax.custom_vjp
def _chunked(q, k, v, g, beta, state):
    return _chunked_fwd(q, k, v, g, beta, state)[0]


def _chunked_fwd(q, k, v, g, beta, state):
    dtype = q.dtype
    parts = _in_groups(lambda *xs: _local(*xs, dtype), (q, k, v, g, beta))

    def step(s, chunk):
        s_out, o = _carry(s, *chunk, dtype)
        return s_out, (s, o.astype(v.dtype))

    final, (states, o) = lax.scan(step, state, _by_chunk(parts))
    return (jnp.moveaxis(o, 0, 1), final), (q, k, v, g, beta, states)


def _chunked_bwd(res, cts):
    q, k, v, g, beta, states = res
    do, dfinal = cts
    dtype = q.dtype
    local = lambda *xs: _local(*xs, dtype)
    # made again, not kept: kept, the six results of every chunk read 1.25
    # GB more a step (compiled for a described v5e, at the chip's edge)
    parts = _in_groups(local, (q, k, v, g, beta))

    def step(ds, xs):
        s_in, d_o, chunk = xs
        _, carry_vjp = jax.vjp(
            lambda s, *c: _carry(s, *c, dtype), s_in, *chunk)
        ds_in, *d_chunk = carry_vjp((ds, d_o.astype(_F32)))
        return ds_in, tuple(d_chunk)

    dstate, d_parts = lax.scan(
        step, dfinal.astype(_F32),
        (states, jnp.moveaxis(do, 1, 0), _by_chunk(parts)), reverse=True)
    d_parts = tuple(jnp.moveaxis(d, 0, 1) for d in d_parts)
    # the chunk-local part's transpose, a group of heads at a time: its
    # forward is made once more there, and nothing of it is kept
    grads = _in_groups(
        lambda *xs: jax.vjp(local, *xs[:5])[1](tuple(xs[5:])),
        (q, k, v, g, beta, *d_parts))
    return (*grads, dstate)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
        beta: jax.Array, *, chunk: int = 64,
        initial_state: Optional[jax.Array] = None
        ) -> Tuple[jax.Array, jax.Array]:
    """The chunked form. ``q``, ``k`` of ``(batch, heads, seq, d_k)`` (the
    caller norms them), ``v`` of ``(batch, heads, seq, d_v)``, ``g`` the
    log-decays ``(batch, heads, seq, d_k)`` (at most 0; float32), ``beta``
    ``(batch, heads, seq)``, ``initial_state`` ``(batch, heads, d_k, d_v)``
    or zeros. ``chunk`` is a power of two, :data:`NEAR` or more; a sequence
    that is no whole number of chunks is filled up with tokens that leave
    the state as it is. Returns
    ``(o, final_state)``: ``o`` of ``v``'s shape and type, the state in
    float32."""
    b, h, seq, dk = q.shape
    if chunk < NEAR or chunk & (chunk - 1):
        raise ValueError(f"chunk ({chunk}) is not a power of two, {NEAR} or "
                         "more")
    dv = v.shape[-1]
    state = (jnp.zeros((b * h, dk, dv), _F32) if initial_state is None
             else initial_state.astype(_F32).reshape(b * h, dk, dv))
    pad = -seq % chunk

    def chunks(x):
        """``(batch, heads, seq, ...)`` as ``(batch x heads, chunks, chunk,
        ...)``."""
        # a token of zeros with beta 0 and no decay changes nothing
        x = jnp.pad(x, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 3))
        return x.reshape(b * h, (seq + pad) // chunk, chunk, *x.shape[3:])

    o, final = _chunked(chunks(q), chunks(k), chunks(v),
                        chunks(g.astype(_F32)), chunks(beta), state)
    return (o.reshape(b, h, seq + pad, dv)[:, :, :seq],
            final.reshape(b, h, dk, dv))

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
and nothing else, the backward makes the chunk-local results again, runs
the scan in reverse and then the chunk-local part's transpose for all
chunks at once.

**What is a kernel.** On the chip the chunk-local part is two Pallas
kernels, each a grid step one chunk of :data:`BLOCK` heads kept in VMEM
from its inputs to its results. :func:`_kernel` makes :func:`_local`'s six
results (three times a step: forward, recompute, and the backward's
operands of the reverse scan), the same arithmetic in the same precisions
(the running sum as float32 adds, the decays split as above, ``T`` at
``HIGHEST``), and writes them with the chunks in front, as the scan reads
them. :func:`_transpose_kernel` is its transpose, written out: it takes the
six results' cotangents as the reverse scan leaves them, makes ``G``,
``A``, ``T`` and the right-hand sides again, and gives ``q``'s, ``k``'s,
``v``'s, ``g``'s and ``beta``'s cotangents (``dA = -(T^T dT T^T)`` at
``HIGHEST``; the decayed products' transposes with every decay split as
the forward splits it, so no exponent there is above 0 either). Still
``jax.numpy``: the scans over the chunks, which carry the state. Off the
chip and outside the kernels' envelope the chunk-local part is
:func:`_local` and its transpose ``jax.vjp`` of it, a group of heads at a
time so that the float32 temporaries stay a fraction of an operand's size
(all heads at once, the step of the Kimi Linear cell held 9.9 GB of them
where it held 4.2, compiled for a described v5e). The caller names the
scope; the kernels keep it.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.layer_norm import (
    _interpret,
    _pallas_unsupported,
    _resolve_impl,
)

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


def _unit_lower_inverse(a, split=lambda x: x, mm=None):
    """``(I + a)^-1`` for strictly lower triangular ``a`` of ``(..., C,
    C)``, in float32: ``a^C = 0``, so the inverse is the product
    ``(I - a)(I + a^2)(I + a^4) ...`` up to the power ``C / 2``. ``mm``
    multiplies two operands as ``split`` leaves them, at ``HIGHEST``
    precision (the kernel's: :func:`_split3`, :func:`_highest`)."""
    c = a.shape[-1]
    if mm is None:
        mm = lambda x, y: jnp.matmul(x, y, precision=_HIGHEST)
    inv = jnp.eye(c, dtype=a.dtype) - a
    parts = split(a)
    power = mm(parts, parts)
    for step in range(int(math.log2(c)) - 1):
        parts = split(power)
        if step:
            power = mm(parts, parts)
            parts = split(power)
        inv = inv + mm(split(inv), parts)
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


# -- the chunk-local part as one kernel -----------------------------------------

#: heads a grid step works on, one chunk of each after another: the rows of
#: ``beta`` and of ``exp(G_C)`` a step reads and writes (8 on the chip)
BLOCK = 8


def _dot(a, b, dtype, contract: int = -2, over: int = -1):
    """:func:`_mm` inside the kernel, over ``b``'s rows (``contract`` -2)
    or its columns (-1) and ``a``'s columns (``over`` -1) or its rows
    (-2, ``a`` transposed), the leading axes a batch: one pass of the
    operands' type, named (Mosaic refuses a float32 contraction of bf16
    operands, which a caller's default precision would ask for)."""
    batch = tuple(range(a.ndim - 2))
    return lax.dot_general(
        a.astype(dtype), b.astype(dtype),
        (((a.ndim + over,), (b.ndim + contract,)), (batch, batch)),
        precision=lax.Precision.DEFAULT, preferred_element_type=_F32)


def _split3(x):
    """float32 ``x`` as three bf16 parts whose sum is ``x`` to float32's
    last bit."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(_F32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(_F32)).astype(jnp.bfloat16)


def _highest(x, y):
    """``x @ y`` of two float32 matrices given as their :func:`_split3`
    parts, as XLA:TPU computes a float32 product at ``HIGHEST``: the six
    products of parts down to the third's order (bf16_6x), exact, summed in
    float32. Here they are three products twice as deep, the parts side by
    side: at ``C`` = 64 each fills the MXU's 128 rows of depth, where the
    six of Mosaic's float32 contraction fill half of it."""
    (xh, xm, xl), (yh, ym, yl) = x, y
    side = lambda a, b: jnp.concatenate([a, b], axis=-1)
    over = lambda a, b: jnp.concatenate([a, b], axis=-2)
    dot = lambda a, b: _dot(a, b, jnp.bfloat16)
    return (dot(side(xh, xm), over(yh, yh)) + dot(side(xl, xh), over(yh, ym))
            + dot(side(xm, xh), over(ym, yl)))


# The kernels' building blocks. A chunk's ``(C, d)`` operands live in VMEM
# scratch below ``lift`` rows of zeros (and above as many, where a kernel
# moves rows up), so that a load ``s`` rows higher reads the operand moved
# down by ``s``: row ``i`` then holds ``x[i - s]``, zeros above.

def _shifted(ref, lift: int, at: int, n: int):
    """Rows ``at`` to ``at + n`` of the operand ``ref`` holds below its
    ``lift`` rows of zeros: a negative ``at`` reads it moved down by
    ``-at``, a positive one moved up, zeros where it has no rows."""
    return ref[lift + at:lift + at + n, :]


def _running_sum(x, pad_ref, lift: int, reverse: bool = False):
    """``x``'s running sum down its rows (up them, if ``reverse``) in
    log2(C) adds of ``x`` moved by 1, 2, 4, ... rows through ``pad_ref``
    (zeros in the ``lift`` rows above the operand, and below it too if
    ``reverse``)."""
    c = x.shape[0]
    s = 1
    while s < c:
        pad_ref[lift:lift + c, :] = x
        x = x + _shifted(pad_ref, lift, s if reverse else -s, c)
        s *= 2
    return x


def _running_sums(g_ref, out_ref):
    """The running sum down the rows of every chunk of ``g_ref`` ``(heads,
    C, d)`` into ``out_ref``, a row at a time for all heads at once: each
    ``G_i`` is one rounding from ``G_{i - 1}``, so that a decay's exponent,
    a difference of two, holds to float32's rounding of ``G``.
    :func:`_running_sum` rounds each row several times: at 30 a token that
    doubled the error of ``g``'s cotangent, where the forward kernel's
    results hardly notice it."""
    acc = g_ref[:, 0:1, :]
    out_ref[:, 0:1, :] = acc
    for i in range(1, g_ref.shape[1]):
        acc = acc + g_ref[:, i:i + 1, :]
        out_ref[:, i:i + 1, :] = acc


def _near_pairs(big_g, big_g_ref, lift: int):
    """The pairs inside each block of :data:`NEAR` tokens, one offset
    ``gap = i - j`` at a time: ``(gap, decay)``, ``decay[i] = exp(G_i -
    G_{i - gap})`` where token ``i - gap`` lies in ``i``'s block and 0
    where it does not (``big_g_ref`` holds ``G`` as the block comment
    above says)."""
    c = big_g.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    for gap in range(1, min(NEAR, c)):
        yield gap, jnp.exp(jnp.where(
            row % NEAR >= gap,
            big_g - _shifted(big_g_ref, lift, -gap, c), -jnp.inf))


def _half_blocks(big_g, big_g_ref, lift: int):
    """The halves of every block, from the chunk's own down to blocks of
    twice :data:`NEAR`: ``(same, rows, cols)`` for the pairs with ``i`` in a
    block's second half and ``j`` in its first, split at the first half's
    last token ``r``: ``rows[i] = exp(G_i - G_r)`` (0 in a first half),
    ``cols[j] = exp(G_r - G_j)`` (0 in a second half), ``same[i, j]`` where
    ``i`` and ``j`` lie in one block."""
    c = big_g.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    half = c // 2
    while half >= NEAR:
        span = 2 * half
        second = row % span >= half
        ref = _shifted(big_g_ref, lift, half - 1, 1)
        for n in range(1, c // span):
            ref = jnp.where(row // span == n,
                            _shifted(big_g_ref, lift, n * span + half - 1, 1),
                            ref)
        yield (row // span == col // span,
               jnp.exp(jnp.where(second, big_g - ref, -jnp.inf)),
               jnp.exp(jnp.where(second, -jnp.inf, ref - big_g)))
        half //= 2


def _decayed(xs, y, y_ref, big_g, big_g_ref, lift: int, dtype):
    """:func:`_decayed_products` inside a kernel, strictly below the
    diagonal, for each ``x`` of ``xs`` against one ``y`` (``y_ref`` and
    ``big_g_ref`` hold ``y`` and ``G`` padded): the near pairs decay by
    decay, the halves' products in ``dtype``."""
    c = y.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    out = [jnp.zeros((c, c), _F32) for _ in xs]
    for gap, decay in _near_pairs(big_g, big_g_ref, lift):
        yj = _shifted(y_ref, lift, -gap, c)
        at = col == row - gap
        out = [m + jnp.where(at, jnp.sum(x * yj * decay, axis=1,
                                         keepdims=True), 0.0)
               for m, x in zip(out, xs)]
    for same, rows, cols in _half_blocks(big_g, big_g_ref, lift):
        cols = y * cols
        out = [m + jnp.where(same, _dot(x * rows, cols, dtype, -1), 0.0)
               for m, x in zip(out, xs)]
    return out


def _decayed_transpose(dms, xs, y, y_ref, up_ref, big_g, big_g_ref,
                       lift: int, dtype):
    """The transpose of :func:`_decayed` given the cotangent ``dm`` of each
    product (strictly below the diagonal): ``(dxs, dy)``, ``dx_i = sum_j
    dm_ij y_j exp(G_i - G_j)`` for each ``x`` and ``dy_j = sum_i dm_ij x_i
    exp(G_i - G_j)`` summed over the products, with every decay split as
    the products split it. ``G``'s cotangent is ``x * dx - y * dy`` (the
    split point's share cancels), the caller's. ``up_ref`` has zeros below
    the operand, for the near pairs' terms moved up to ``j``'s row."""
    c = y.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    dxs = [jnp.zeros(y.shape, _F32) for _ in xs]
    dy = jnp.zeros(y.shape, _F32)
    for gap, decay in _near_pairs(big_g, big_g_ref, lift):
        yj = _shifted(y_ref, lift, -gap, c) * decay
        at = col == row - gap
        ms = [jnp.sum(jnp.where(at, dm, 0.0), axis=1, keepdims=True)
              for dm in dms]
        dxs = [dx + m * yj for dx, m in zip(dxs, ms)]
        up_ref[lift:lift + c, :] = sum(m * x for m, x in zip(ms, xs)) * decay
        dy = dy + _shifted(up_ref, lift, gap, c)
    for same, rows, cols in _half_blocks(big_g, big_g_ref, lift):
        # the products' cotangents one above the other, so that each
        # product is one, as deep as all of them
        dm = jnp.concatenate([jnp.where(same, dm, 0.0) for dm in dms], 0)
        dx = _dot(dm, y * cols, dtype)
        dxs = [d + rows * dx[n * c:(n + 1) * c] for n, d in enumerate(dxs)]
        dy = dy + cols * _dot(
            dm, jnp.concatenate([x * rows for x in xs], 0), dtype, over=-2)
    return dxs, dy


def _row_of(block, j):
    """Row ``j`` of a ``(heads, n)`` block, as ``(1, n)``."""
    at = lax.broadcasted_iota(jnp.int32, (block.shape[0], 1), 0) == j
    return jnp.sum(jnp.where(at, block, 0.0), axis=0, keepdims=True)


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
            w_ref, u0_ref, b_ref, qg_ref, kg_ref, end_ref,
            pad_ref, big_g_ref, a_ref, *, lift):
    """:func:`_local` for the chunks of one grid step, in VMEM from their
    inputs to their six results: chunk by chunk up to ``A`` (into
    ``a_ref``), then ``T`` and ``W``, ``U_0`` for all of them at once.
    ``pad_ref`` holds the running sum's operand and then ``K``,
    ``big_g_ref`` ``G``, both padded (the building blocks' comment)."""
    nb, c, dk = q_ref.shape
    dtype = q_ref.dtype
    scale = dk ** -0.5
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    diag = lax.broadcasted_iota(jnp.int32, (c, c), 0) == col
    chunk_of = lax.broadcasted_iota(jnp.int32, (nb, 1), 0)
    for ref in (pad_ref, big_g_ref):
        ref[:lift, :] = jnp.zeros((lift, dk), _F32)

    def one(j, ends):
        q = q_ref[j].astype(_F32) * scale
        k = k_ref[j].astype(_F32)
        big_g = _running_sum(g_ref[j], pad_ref, lift)
        big_g_ref[lift:, :] = big_g
        pad_ref[lift:, :] = k
        beta = jnp.sum(jnp.where(diag, _row_of(beta_ref[...], j), 0.0),
                       axis=1, keepdims=True)                # (C, 1)
        # A's pairs strictly below the diagonal, B's on it too
        a, b = _decayed((k, q), k, pad_ref, big_g, big_g_ref, lift, dtype)
        b = b + jnp.where(diag, jnp.sum(q * k, axis=1, keepdims=True), 0.0)
        a_ref[j] = beta * a
        # W's and U_0's right-hand sides, in their results' place until T
        # is known
        w_ref[j] = (beta * k * jnp.exp(big_g)).astype(dtype)
        u0_ref[j] = (beta * v_ref[j].astype(_F32)).astype(dtype)
        end = _shifted(big_g_ref, lift, c - 1, 1)
        b_ref[j] = b.astype(dtype)
        qg_ref[j] = (q * jnp.exp(big_g)).astype(dtype)
        kg_ref[j] = (k * jnp.exp(end - big_g)).astype(dtype)
        return jnp.where(chunk_of == j, jnp.exp(end), ends)

    end_ref[...] = lax.fori_loop(0, nb, one, jnp.zeros((nb, dk), _F32))
    # the step's inverses side by side: each is a chain of ten products,
    # and the chains of different chunks hide each other's latency
    inv = _unit_lower_inverse(a_ref[...], _split3, _highest)
    w_ref[...] = _dot(inv, w_ref[...], dtype).astype(dtype)
    u0_ref[...] = _dot(inv, u0_ref[...], dtype).astype(dtype)


def _transpose_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                      dw_ref, du0_ref, db_ref, dqg_ref, dkg_ref, dend_ref,
                      dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                      pad_ref, big_g_ref, k_pad_ref, a_ref, dkk_ref,
                      g_all_ref, rw_ref, ru_ref, drw_ref, dru_ref, *, lift):
    """The transpose of :func:`_local` for the chunks of one grid step, in
    VMEM from the inputs and the six results' cotangents to the five
    inputs' cotangents. ``G`` for all chunks at once, a row at a time
    (:func:`_running_sums`), then chunk by chunk ``A`` and the right-hand
    sides made again as :func:`_kernel` makes them; then for all chunks at
    once ``T``, ``dT = dW (beta K e^G)^T + dU_0 (beta V)^T``, the
    right-hand sides' cotangents ``T^T dW`` and ``T^T dU_0`` and ``dA =
    -(T^T dT T^T)`` below the diagonal (``T``'s products at ``HIGHEST``);
    then chunk by chunk the decayed products' transposes, every decay split
    as the forward splits it, and the rest elementwise. ``pad_ref`` has
    zeros above and below the operand (the running sums, the near pairs'
    terms moved up)."""
    nb, c, dk = q_ref.shape
    dtype = q_ref.dtype
    scale = dk ** -0.5
    row = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    diag = lax.broadcasted_iota(jnp.int32, (c, c), 0) == col
    chunk_of = lax.broadcasted_iota(jnp.int32, (nb, 1), 0)
    for ref in (pad_ref, big_g_ref, k_pad_ref):
        ref[:lift, :] = jnp.zeros((lift, dk), _F32)
    pad_ref[lift + c:, :] = jnp.zeros((lift, dk), _F32)
    beta_of = lambda j: jnp.sum(jnp.where(
        diag, _row_of(beta_ref[...], j), 0.0), axis=1, keepdims=True)

    _running_sums(g_ref, g_all_ref)

    def forward(j, carry):
        k = k_ref[j].astype(_F32)
        big_g = g_all_ref[j]
        big_g_ref[lift:, :] = big_g
        k_pad_ref[lift:, :] = k
        beta = beta_of(j)
        a, = _decayed((k,), k, k_pad_ref, big_g, big_g_ref, lift, dtype)
        dkk_ref[j] = a
        a_ref[j] = beta * a
        rw_ref[j] = (beta * k * jnp.exp(big_g)).astype(dtype)
        ru_ref[j] = (beta * v_ref[j].astype(_F32)).astype(dtype)
        return carry

    lax.fori_loop(0, nb, forward, 0)
    inv = _unit_lower_inverse(a_ref[...], _split3, _highest)
    dw, du0 = dw_ref[...], du0_ref[...]
    d_inv = (_dot(dw, rw_ref[...], dtype, -1)
             + _dot(du0, ru_ref[...], dtype, -1))
    drw_ref[...] = _dot(inv, dw, dtype, over=-2)
    dru_ref[...] = _dot(inv, du0, dtype, over=-2)
    inv_t = _split3(jnp.swapaxes(inv, -1, -2))
    below = (lax.broadcasted_iota(jnp.int32, (nb, c, c), 1)
             > lax.broadcasted_iota(jnp.int32, (nb, c, c), 2))
    a_ref[...] = jnp.where(below, -_highest(
        _split3(_highest(inv_t, _split3(d_inv))), inv_t), 0.0)

    def backward(j, d_betas):
        q = q_ref[j].astype(_F32) * scale
        k = k_ref[j].astype(_F32)
        big_g = g_all_ref[j]
        big_g_ref[lift:, :] = big_g
        k_pad_ref[lift:, :] = k
        beta = beta_of(j)
        decay = jnp.exp(big_g)
        end = _shifted(big_g_ref, lift, c - 1, 1)
        to_end = jnp.exp(end - big_g)
        d_a = a_ref[j]
        d_b = jnp.where(col <= row, db_ref[j].astype(_F32), 0.0)
        d_rw, d_ru = drw_ref[j], dru_ref[j]
        d_qg = dqg_ref[j].astype(_F32)
        d_kg = dkg_ref[j].astype(_F32) * to_end
        (dx_a, dx_b), dy = _decayed_transpose(
            (beta * d_a, d_b), (k, q), k, k_pad_ref, pad_ref, big_g,
            big_g_ref, lift, dtype)
        # K e^{G_C - G}'s share of G's cotangent: -x at G and the sum of x
        # at G_C, the last row's own two shares left out (they cancel)
        last = row == c - 1
        to_g_end = jnp.where(last, 0.0, k * d_kg)
        d_end = (jnp.sum(to_g_end, axis=0, keepdims=True)
                 + _row_of(dend_ref[...], j) * jnp.exp(end))
        d_g = (k * (d_rw * beta * decay + dx_a - dy) - to_g_end
               + q * (dx_b + d_qg * decay) + jnp.where(last, d_end, 0.0))
        dg_ref[j] = _running_sum(d_g, pad_ref, lift, reverse=True)
        # B's diagonal: no decay, and no share of G's cotangent
        d_diag = jnp.sum(jnp.where(diag, d_b, 0.0), axis=1, keepdims=True)
        dq_ref[j] = (scale * (d_qg * decay + dx_b + d_diag * k)).astype(
            dq_ref.dtype)
        dk_ref[j] = (d_rw * beta * decay + d_kg + dx_a + dy
                     + d_diag * q).astype(dk_ref.dtype)
        dv_ref[j] = (beta * d_ru).astype(dv_ref.dtype)
        d_beta = (jnp.sum(d_a * dkk_ref[j], axis=1, keepdims=True)
                  + jnp.sum(d_rw * k * decay, axis=1, keepdims=True)
                  + jnp.sum(d_ru * v_ref[j].astype(_F32), axis=1,
                            keepdims=True))
        return jnp.where(chunk_of == j, jnp.sum(
            jnp.where(diag, d_beta, 0.0), axis=0, keepdims=True), d_betas)

    dbeta_ref[...] = lax.fori_loop(0, nb, backward,
                                   jnp.zeros((nb, c), _F32))


def _block(heads: int, c: int, dk: int, dv: int,
           impl: str) -> Optional[int]:
    """Heads a grid step takes if the kernel computes the chunk-local part
    of ``heads`` (sequences x heads) with chunks of ``c`` tokens, ``None``
    if :func:`_local` does."""
    if _resolve_impl(impl) == "xla":
        return None
    nb = BLOCK
    while heads % nb:
        nb //= 2
    # on the chip a chunk is whole (8, 128) tiles and a step 8 of them;
    # interpret mode takes what divides
    if not _interpret() and (dk % 128 or dv % 128 or c not in (32, 64, 128)
                             or nb < BLOCK):
        _pallas_unsupported(
            "kda", impl,
            f"{heads} heads with chunks of {c} tokens, d_k {dk}, d_v {dv} "
            f"are outside the kernel's envelope (d_k and d_v whole numbers "
            f"of 128 lanes, chunks of 32, 64 or 128 tokens, heads in steps "
            f"of {BLOCK})")
        return None
    return nb


def _specs(nb: int):
    """A grid step's blocks, ``(into, out)``: ``nb`` heads of one chunk of
    a ``(heads, chunks, ...)`` operand, and of one with the chunks in front
    (``(chunks, heads, ...)``), each given the shape after those two axes."""
    into = lambda *shape: pl.BlockSpec(
        (nb, None, *shape), lambda t, h: (h, t) + (0,) * len(shape))
    out = lambda *shape: pl.BlockSpec(
        (None, nb, *shape), lambda t, h: (t, h) + (0,) * len(shape))
    return into, out


def _local_kernel(q, k, v, g, beta, nb: int):
    """:func:`_local`'s six results for every ``(head, chunk)`` of its
    operands as one kernel, with the chunks in front as the scan reads them
    (``(chunks, heads, ...)``): a grid step is one chunk of ``nb`` heads."""
    n, m, c, dk = q.shape
    dv = v.shape[-1]
    into, out = _specs(nb)
    lift = max(c // 2, 8)
    return tuple(pl.pallas_call(
        functools.partial(_kernel, lift=lift),
        grid=(m, n // nb),
        in_specs=[into(c, dk), into(c, dk), into(c, dv), into(c, dk),
                  out(c)],
        out_specs=[out(c, dk), out(c, dv), out(c, c), out(c, dk),
                   out(c, dk), out(dk)],
        out_shape=[jax.ShapeDtypeStruct((m, n, *shape), dt) for shape, dt in (
            ((c, dk), q.dtype), ((c, dv), q.dtype), ((c, c), q.dtype),
            ((c, dk), q.dtype), ((c, dk), q.dtype), ((dk,), _F32))],
        scratch_shapes=[pltpu.VMEM((lift + c, dk), _F32)] * 2
        + [pltpu.VMEM((nb, c, c), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_interpret(),
    )(q, k, v, g, jnp.swapaxes(beta, 0, 1).astype(_F32)))


def _local_transpose(q, k, v, g, beta, d_parts, nb: int):
    """``jax.vjp`` of :func:`_local` for every ``(head, chunk)`` as one
    kernel: the cotangents of ``q``, ``k``, ``v``, ``g``, ``beta`` given
    those of the six results with the chunks in front, as the reverse scan
    leaves them; a grid step is one chunk of ``nb`` heads."""
    n, m, c, dk = q.shape
    dv = v.shape[-1]
    into, out = _specs(nb)
    lift = max(c // 2, 8)
    vmem = lambda *shape, dt=_F32: pltpu.VMEM(shape, dt)
    *grads, d_beta = pl.pallas_call(
        functools.partial(_transpose_kernel, lift=lift),
        grid=(m, n // nb),
        in_specs=[into(c, dk), into(c, dk), into(c, dv), into(c, dk),
                  out(c), out(c, dk), out(c, dv), out(c, c), out(c, dk),
                  out(c, dk), out(dk)],
        out_specs=[into(c, dk), into(c, dk), into(c, dv), into(c, dk),
                   out(c)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v, g)]
        + [jax.ShapeDtypeStruct((m, n, c), _F32)],
        scratch_shapes=[vmem(2 * lift + c, dk), vmem(lift + c, dk),
                        vmem(lift + c, dk), vmem(nb, c, c), vmem(nb, c, c),
                        vmem(nb, c, dk), vmem(nb, c, dk, dt=q.dtype),
                        vmem(nb, c, dv, dt=q.dtype), vmem(nb, c, dk),
                        vmem(nb, c, dv)],
        # chunks of 128 take 16.8 MiB, past the 16 a kernel is given unasked
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=32 * 2 ** 20),
        interpret=_interpret(),
    )(q, k, v, g, jnp.swapaxes(beta, 0, 1).astype(_F32), *d_parts)
    return (*grads, jnp.swapaxes(d_beta, 0, 1).astype(beta.dtype))


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


def _parts(nb, q, k, v, g, beta):
    """:func:`_local`'s six results with the chunks in front, for the scan:
    the kernel's, ``nb`` heads a grid step, or (``nb`` None) the ``jax.numpy``
    form's, a group of heads at a time."""
    if nb:
        return _local_kernel(q, k, v, g, beta, nb)
    return _by_chunk(_in_groups(lambda *xs: _local(*xs, q.dtype),
                                (q, k, v, g, beta)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunked(nb, q, k, v, g, beta, state):
    return _chunked_fwd(nb, q, k, v, g, beta, state)[0]


def _chunked_fwd(nb, q, k, v, g, beta, state):
    dtype = q.dtype

    def step(s, chunk):
        s_out, o = _carry(s, *chunk, dtype)
        return s_out, (s, o.astype(v.dtype))

    final, (states, o) = lax.scan(step, state, _parts(nb, q, k, v, g, beta))
    return (jnp.moveaxis(o, 0, 1), final), (q, k, v, g, beta, states)


def _chunked_bwd(nb, res, cts):
    q, k, v, g, beta, states = res
    do, dfinal = cts
    dtype = q.dtype
    # made again, not kept: kept, the six results of every chunk read 1.25
    # GB more a step (compiled for a described v5e, at the chip's edge)
    parts = _parts(nb, q, k, v, g, beta)

    def step(ds, xs):
        s_in, d_o, chunk = xs
        _, carry_vjp = jax.vjp(
            lambda s, *c: _carry(s, *c, dtype), s_in, *chunk)
        ds_in, *d_chunk = carry_vjp((ds, d_o.astype(_F32)))
        return ds_in, tuple(d_chunk)

    dstate, d_parts = lax.scan(
        step, dfinal.astype(_F32),
        (states, jnp.moveaxis(do, 1, 0), parts), reverse=True)
    if nb:
        return (*_local_transpose(q, k, v, g, beta, d_parts, nb), dstate)
    # the chunk-local part's transpose, a group of heads at a time, through
    # the jax.numpy form: its forward is made once more there, and nothing
    # of it is kept
    local = lambda *xs: _local(*xs, dtype)
    grads = _in_groups(
        lambda *xs: jax.vjp(local, *xs[:5])[1](tuple(xs[5:])),
        (q, k, v, g, beta, *_by_chunk(d_parts)))
    return (*grads, dstate)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def kernel_chunks(q, v, *, chunk: int = 64, impl: str = "auto") -> int:
    """``(sequence, head, chunk)`` triples whose chunk-local forward the
    kernel computes in :func:`kda` for ``q`` ``(batch, heads, seq, d_k)``
    and ``v`` ``(batch, heads, seq, d_v)`` (shaped values), and whose
    transpose the gradient's kernel does: all of them, or 0 where the
    ``jax.numpy`` form does."""
    b, h, seq, dk = q.shape
    chunks = b * h * -(-seq // chunk)
    return chunks if _block(b * h, chunk, dk, v.shape[-1], impl) else 0


def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
        beta: jax.Array, *, chunk: int = 64,
        initial_state: Optional[jax.Array] = None, impl: str = "auto"
        ) -> Tuple[jax.Array, jax.Array]:
    """The chunked form. ``q``, ``k`` of ``(batch, heads, seq, d_k)`` (the
    caller norms them), ``v`` of ``(batch, heads, seq, d_v)``, ``g`` the
    log-decays ``(batch, heads, seq, d_k)`` (at most 0; float32), ``beta``
    ``(batch, heads, seq)``, ``initial_state`` ``(batch, heads, d_k, d_v)``
    or zeros. ``chunk`` is a power of two, :data:`NEAR` or more; a sequence
    that is no whole number of chunks is filled up with tokens that leave
    the state as it is. Returns
    ``(o, final_state)``: ``o`` of ``v``'s shape and type, the state in
    float32. ``impl``: 'pallas' forces the chunk-local forward's kernel
    (interpret mode off-TPU), 'xla' the ``jax.numpy`` form, 'auto' picks
    the kernel on TPU, where the shape is one it takes. The gradient's
    chunk-local transpose follows: its kernel where the forward's is taken,
    ``jax.vjp`` of the ``jax.numpy`` form where it is not."""
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

    nb = _block(b * h, chunk, dk, dv, impl)
    o, final = _chunked(nb, chunks(q), chunks(k), chunks(v),
                        chunks(g.astype(_F32)), chunks(beta), state)
    return (o.reshape(b, h, seq + pad, dv)[:, :, :seq],
            final.reshape(b, h, dk, dv))

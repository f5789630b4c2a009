"""What the plain references share: seeded weights, LayerNorm, the two
matrix products (float32 at ``highest``, and the int8 control), Adam and
LAMB. ``jax`` and ``numpy`` only: nothing of the program under test.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number up to 2**32 and a little beyond: the low
    31 bits seed it and the rest is folded in, so no seed wraps."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def normal_leaves(key, shapes: dict, dtype) -> dict:
    """``{name: (shape, std | "ones" | "zeros")}`` to arrays of ``dtype``,
    drawn in float32 from one key split by the sorted names."""
    names = sorted(shapes)
    keys = jax.random.split(key, len(names))
    out = {}
    for k, name in zip(keys, names):
        shape, how = shapes[name]
        if how == "ones":
            out[name] = jnp.ones(shape, dtype)
        elif how == "zeros":
            out[name] = jnp.zeros(shape, dtype)
        else:
            out[name] = (how * jax.random.normal(k, shape, jnp.float32)
                         ).astype(dtype)
    return out


def nest(flat: dict) -> dict:
    """``{"a/b": x}`` to ``{"a": {"b": x}}``."""
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def dot_f32(a, b):
    """The reference's product: float32 operands, six bf16 passes."""
    return jnp.matmul(a, b, precision=HIGHEST)


def _int8_rows(x, axis):
    """Symmetric int8 with one scale per slice along ``axis``, returned in
    float32: what an int8 kernel with per-row scales would multiply."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.round(x / scale) * scale


def _fp8_rows(x, axis):
    """fp8 (e4m3: three bits of mantissa) with one scale per slice along
    ``axis`` that puts the slice's largest value at the format's largest,
    returned in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rounded_dot(rows):
    """A product with both operands rounded by ``rows`` (a scale per row
    of ``a`` and per column of ``b``), accumulated in float32. The
    gradient is that of the product of the rounded operands, and the
    incoming gradient is rounded the same way, as a kernel of that
    precision would take it."""

    @jax.custom_vjp
    def dot(a, b):
        return jnp.matmul(rows(a, -1), rows(b, -2), precision=HIGHEST)

    def fwd(a, b):
        qa, qb = rows(a, -1), rows(b, -2)
        return jnp.matmul(qa, qb, precision=HIGHEST), (qa, qb)

    def bwd(res, g):
        qa, qb = res
        g = rows(g, -1)
        ga = jnp.matmul(g, jnp.swapaxes(qb, -1, -2), precision=HIGHEST)
        gb = jnp.matmul(jnp.swapaxes(qa, -1, -2), g, precision=HIGHEST)
        # sum the weight gradient over the batch dims the weight lacks
        while gb.ndim > qb.ndim:
            gb = gb.sum(0)
        return ga, gb

    dot.defvjp(fwd, bwd)
    return dot


#: the reference's product and the controls': every product of a linear
#: layer in the precision below bf16
DOTS = {"float32": dot_f32, "int8": _rounded_dot(_int8_rows),
        "fp8": _rounded_dot(_fp8_rows)}


def split_qkv(x, heads: int):
    """A stacked fused-QKV leaf ``(layers, ..., heads * 3 * head_dim)``,
    laid out ``(head, {q,k,v}, head_dim)`` along its last axis, as
    ``(layers, 3, elements)``: the three matrices apart."""
    n = x.shape[0]
    x = x.reshape(n, -1, heads, 3, x.shape[-1] // (3 * heads))
    return jnp.moveaxis(x, 3, 1).reshape(n, 3, -1)


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def gelu_erf(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x * 0.7071067811865476))


#: the activation by the name the source's config.json gives it
ACTIVATIONS = {"gelu": gelu_erf, "gelu_new": gelu_tanh}


def table_rows(cfg: dict) -> int:
    """Rows of the embedding table: the published vocabulary, padded where
    the configuration file says so (Megatron-LM pads the table to a multiple
    of 128; the padded rows are weights, take part in the softmax, and are
    never drawn as ids)."""
    return cfg.get("padded_vocab_size", cfg["vocab_size"])


def cross_entropy(logits, labels):
    """Per-position negative log-likelihood, float32."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - picked


# -- optimizers, as published ------------------------------------------------

def adam_init(params):
    # two trees of buffers: the update donates its state
    return {"step": jnp.zeros((), jnp.int32),
            "m": jax.tree.map(jnp.zeros_like, params),
            "v": jax.tree.map(jnp.zeros_like, params)}


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps",
                                             "weight_decay"),
                   donate_argnums=(0, 1))
def adam_update(params, state, grads, *, lr, b1=0.9, b2=0.999, eps=1e-8,
                weight_decay=0.0):
    """Adam with decoupled weight decay and bias correction (Kingma & Ba
    2015; Loshchilov & Hutter 2019)."""
    step = state["step"] + 1
    t = step.astype(jnp.float32)
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def one(p, g, m, v):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
        return p - lr * (upd + weight_decay * p), m, v

    out = jax.tree.map(one, params, grads, state["m"], state["v"])
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), {"step": step, "m": pick(1), "v": pick(2)}

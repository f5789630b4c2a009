"""LAMB (You et al., arXiv:1904.00962) as NVIDIA's FusedLAMB documents it
and the BERT recipe runs it: the gradient clipped to a global norm, Adam's
moments with bias correction, decoupled weight decay inside the update, and
the update of each layer's tensor scaled by ||w|| / ||update|| (1 where
either is zero). The reference stores a kind of layer tensor stacked over
the layers, and takes the ratio layer by layer, as the paper does."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def lamb_init(params):
    return {"step": jnp.zeros((), jnp.int32),
            "m": jax.tree.map(jnp.zeros_like, params),
            "v": jax.tree.map(jnp.zeros_like, params)}


def clipped(grads, max_grad_norm: float):
    """The gradient as the moments get it."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    return jax.tree.map(lambda g: g / jnp.maximum(1.0, norm / max_grad_norm),
                        grads)


@functools.partial(jax.jit, static_argnames=(
    "lr", "b1", "b2", "eps", "weight_decay", "max_grad_norm"),
    donate_argnums=(0, 1))
def lamb_update(params, state, grads, *, lr, b1=0.9, b2=0.999, eps=1e-6,
                weight_decay=0.01, max_grad_norm=1.0):
    step = state["step"] + 1
    t = step.astype(jnp.float32)
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    grads = clipped(grads, max_grad_norm)

    def one(path, p, g, m, v):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + weight_decay * p
        stacked = str(getattr(path[0], "key", "")) == "layers"
        axes = tuple(range(1, p.ndim)) if stacked else None
        wn = jnp.sqrt(jnp.sum(jnp.square(p), axis=axes, keepdims=stacked))
        un = jnp.sqrt(jnp.sum(jnp.square(upd), axis=axes, keepdims=stacked))
        ratio = jnp.where((wn > 0) & (un > 0), wn / jnp.where(
            un > 0, un, 1.0), 1.0)
        return p - lr * ratio * upd, m, v

    out = jax.tree_util.tree_map_with_path(
        one, params, grads, state["m"], state["v"])
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), {"step": step, "m": pick(1), "v": pick(2)}

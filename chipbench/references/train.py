"""Follows the first optimizer steps of a training cell in the plain
reference: float32 weights from the seed, the loss and its gradient in
blocks of rows, the published optimizer. Gives the readings the timed
step is compared with: each step's loss, the first gradient's norm leaf by
leaf, and the norm of the weights' change over the steps, leaf by leaf.

``precision="int8"`` is the control (every product of a linear layer with
both operands rounded to int8); ``rows_kept`` plants the fault "half of the
batch left out, the mean taken over the rest".
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from . import common


def family(name: str):
    return importlib.import_module(f"{__package__}.{name}")


def leaf_norms(tree, fused=None) -> dict:
    """``{path: norms}``: one norm per leaf; one per layer for a leaf of
    the stacked ``layers`` (its leading axis); and, where the family's
    ``fused(cfg-free name, x)`` says that a leaf holds several matrices
    side by side (the fused Q, K and V), one for each of them: a key's bias
    has no gradient under softmax and must not hide in its leaf."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                        for k in path)
        x = leaf.astype(jnp.float32)
        if name.startswith("layers/"):
            x = x.reshape(x.shape[0], 1, -1) if fused is None \
                else fused(name, x)
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=-1)).reshape(-1)
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
    return out


def leaf_sizes(tree, fused) -> dict:
    """``{path: elements}`` of each group ``leaf_norms`` takes a norm of."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                        for k in path)
        groups = jax.eval_shape(functools.partial(fused, name), leaf).shape \
            if name.startswith("layers/") else (1, 1, leaf.size)
        out[name] = np.full(groups[0] * groups[1], groups[2], np.int64)
    return out


#: elements drawn from each leaf (each layer and fused part of it) for the
#: comparison of the first gradient element by element
SAMPLE = 4096


def sample_key(seed: int):
    return jax.random.fold_in(common.seed_key(seed), 0x5A3B)


def leaf_samples(tree, fused, key, n: int = SAMPLE) -> dict:
    """``{path: (groups, n)}``: the same ``n`` places, drawn from ``key``,
    of every group ``leaf_norms`` takes a norm of. Both sides draw with
    this function, so they read the same places."""
    out = {}
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for i, (path, leaf) in enumerate(leaves):
        name = "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                        for k in path)
        x = leaf.astype(jnp.float32)
        x = fused(name, x) if name.startswith("layers/") \
            else x.reshape(1, 1, -1)
        at = jax.random.randint(jax.random.fold_in(key, i), (n,), 0,
                                x.shape[-1])
        out[name] = x[:, :, at].reshape(-1, n)
    return out


def _tree_sub(a, b):
    return jax.tree.map(lambda x, y: x.astype(jnp.float32)
                        - y.astype(jnp.float32), a, b)


@functools.partial(jax.jit, donate_argnums=(0,))
def _tree_add(acc, g):
    return jax.tree.map(jnp.add, acc, g)


def optimizer(name: str):
    """``(init, update, seen)`` of a published optimizer: ``seen(grads,
    **options)`` is the gradient as its moments get it."""
    if name == "adam":
        return common.adam_init, common.adam_update, lambda g, **_: g
    if name == "lamb":
        from . import lamb

        return (lamb.lamb_init, lamb.lamb_update,
                lambda g, max_grad_norm=1.0, **_: lamb.clipped(
                    g, max_grad_norm))
    raise ValueError(f"no reference optimizer {name!r}")


def follow(model: str, cfg: dict, mix: dict, seed: int, batches, *,
           steps: int, precision: str = "float32", rows_kept=None,
           block_rows: int = 2) -> dict:
    """``batches``: the first ``steps`` batches (dicts of numpy arrays) the
    timed step was fed. Returns numpy readings."""
    fam = family(model)
    fused = functools.partial(fam.fused_parts, cfg)
    opt = dict(mix["optimizer"])
    init_opt, update, seen = optimizer(opt.pop("name"))
    w0 = jax.jit(lambda k: jax.tree.map(
        lambda a: a.astype(jnp.float32),
        fam.init_weights(cfg, k, jnp.dtype(mix["weights_dtype"]))))(
            common.seed_key(seed))
    w = jax.tree.map(jnp.copy, w0)
    state = init_opt(w)

    @jax.jit
    def grad_block(w, block, inv_den):
        def f(w):
            nums = fam.loss_numerators(cfg, w, block, precision)
            return jnp.sum(nums * inv_den)
        return jax.value_and_grad(f)(w)

    losses, grad_norms, grad_sample = [], None, None
    for i in range(steps):
        batch = batches[i]
        if rows_kept is not None:
            batch = {k: v[:rows_kept] for k, v in batch.items()}
        inv_den = jnp.asarray(1.0 / fam.denominators(batch), jnp.float32)
        rows = len(next(iter(batch.values())))
        loss, grads = 0.0, None
        for r in range(0, rows, block_rows):
            block = {k: jnp.asarray(v[r:r + block_rows])
                     for k, v in batch.items()}
            l, g = grad_block(w, block, inv_den)
            loss += float(l)
            grads = g if grads is None else _tree_add(grads, g)
        losses.append(loss)
        if i == 0:
            g = seen(grads, **opt)
            grad_norms = jax.device_get(leaf_norms(g, fused))
            grad_sample = jax.device_get(
                leaf_samples(g, fused, sample_key(seed)))
            del g
        w, state = update(w, state, grads, **opt)
        del grads
    update_norms = jax.device_get(leaf_norms(_tree_sub(w, w0), fused))
    return {"losses": np.asarray(losses, np.float64),
            "grad_norms": grad_norms, "grad_sample": grad_sample,
            "part_sizes": leaf_sizes(w0, fused),
            "update_norms": update_norms}

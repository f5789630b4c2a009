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


def _to_host(tree):
    """``tree`` as numpy arrays, its device buffers freed at once."""
    host = jax.device_get(tree)
    for leaf in jax.tree.leaves(tree):
        leaf.delete()
    return host


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


def block_gradient(fam, cfg: dict, precision: str):
    """``grad_block(w, block, inv_den)``: a block of rows' share of the
    batch's loss and its gradient."""
    @jax.jit
    def grad_block(w, block, inv_den):
        def f(w):
            nums = fam.loss_numerators(cfg, w, block, precision)
            return jnp.sum(nums * inv_den)
        return jax.value_and_grad(f)(w)
    return grad_block


#: rows of the batch in a block of the gradient's sum: at 2 x 4096 tokens a
#: block's scratch is 0.33 GB beside the trees (the expert model's cell)
BLOCK_ROWS = 2


def follow(model: str, cfg: dict, mix: dict, seed: int, batches, *,
           steps: int, precision: str = "float32", rows_kept=None) -> dict:
    """``batches``: the first ``steps`` batches (dicts of numpy arrays) the
    timed step was fed. Returns numpy readings.

    The gradient is summed over blocks of ``BLOCK_ROWS`` rows. At most four
    float32 trees of the model are alive on the device at a time: the
    weights, the sum of the gradient, and either one block's gradient or
    the two moments, which wait on the host while the gradient is summed.
    The seeded weights are not kept: they are made again at the end, by the
    same program from the same key.

    Four is what the count of live arrays says, and a test holds that. What
    the chip allocates also rests on the two ``block_until_ready`` lines
    below, which no test can hold: the CPU's count of live arrays is the
    same without them. On the chip, without them, the process's peak read
    five and a half and six trees where it reads four (``PERF.md``,
    Findings, PR 32): read the driver's ``reference: ... peak`` line on
    standard error after any change here."""
    fam = family(model)
    fused = functools.partial(fam.fused_parts, cfg)
    opt = dict(mix["optimizer"])
    init_opt, update, seen = optimizer(opt.pop("name"))
    seeded = jax.jit(lambda k: jax.tree.map(
        lambda a: a.astype(jnp.float32),
        fam.init_weights(cfg, k, jnp.dtype(mix["weights_dtype"]))))
    w = seeded(common.seed_key(seed))
    state = None
    grad_block = block_gradient(fam, cfg, precision)
    losses, grad_norms, grad_sample = [], None, None
    for i in range(steps):
        batch = batches[i]
        if rows_kept is not None:
            batch = {k: v[:rows_kept] for k, v in batch.items()}
        inv_den = jnp.asarray(1.0 / fam.denominators(batch), jnp.float32)
        rows = len(next(iter(batch.values())))
        loss, grads = 0.0, None
        for r in range(0, rows, BLOCK_ROWS):
            block = {k: jnp.asarray(v[r:r + BLOCK_ROWS])
                     for k, v in batch.items()}
            l, g = grad_block(w, block, inv_den)
            loss += float(l)
            grads = g if grads is None else _tree_add(grads, g)
            # or two blocks' gradients are alive in the next call: buffers
            # are found when a call is dispatched, and a tree that a
            # running sum still reads is not free yet
            del g
            jax.block_until_ready(grads)
        losses.append(loss)
        if i == 0:
            g = seen(grads, **opt)
            grad_norms = jax.device_get(leaf_norms(g, fused))
            grad_sample = jax.device_get(
                leaf_samples(g, fused, sample_key(seed)))
            del g
        # the moments: made for the first update, on the host between two
        state = init_opt(w) if state is None else jax.device_put(state)
        w, state = update(w, state, grads, **opt)
        del grads
        if i + 1 < steps:
            state = _to_host(state)
    jax.block_until_ready(w)    # the update's operands are free by now
    del state
    w0 = seeded(common.seed_key(seed))
    update_norms = jax.device_get(leaf_norms(_tree_sub(w, w0), fused))
    return {"losses": np.asarray(losses, np.float64),
            "grad_norms": grad_norms, "grad_sample": grad_sample,
            "part_sizes": leaf_sizes(w0, fused),
            "update_norms": update_norms}

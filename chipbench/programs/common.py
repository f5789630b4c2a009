"""What the adapters of training programs share: how the comparison's
readings are taken from the optimizer's state."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..references import common
from ..references.train import family, leaf_norms, leaf_samples, sample_key


def seeded_weights(fam, cfg: dict, mix: dict, abstract):
    """``weights(key)``: the family's seeded weights, drawn in the mix's
    ``weights_dtype`` and cast leaf by leaf to the types of ``abstract``,
    the program's own tree (O2 keeps the norms in float32: the same values,
    wider). A tree of other leaves or shapes is the harness's fault and
    raises."""
    dtype = jnp.dtype(mix["weights_dtype"])
    dtypes = jax.tree.map(lambda a: a.dtype, abstract)

    def weights(key):
        return jax.tree.map(lambda w, t: w.astype(t),
                            fam.init_weights(cfg, key, dtype), dtypes)

    sig = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
    got = jax.eval_shape(weights, jax.random.PRNGKey(0))
    if sig(got) != sig(abstract):
        raise RuntimeError("the seeded weights do not match the program's "
                           f"tree: {sig(got)} against {sig(abstract)}")
    return weights


class TrainProgram:
    """A compiled training step and how to feed it. ``step(params,
    opt_state, *batch)`` returns ``(params, opt_state, loss, metrics)``. A
    subclass sets ``step``, ``rows``, ``tokens_per_step`` and calls
    ``_readers`` with the function that makes the seeded weights."""

    def __init__(self, cfg: dict, mix: dict):
        self.cfg, self.mix = cfg, mix
        self.fam = family(cfg["reference"])
        self._fused = functools.partial(self.fam.fused_parts, cfg)

    def _readers(self, weights, b1: float):
        """``weights(key)``: the seeded weights in the program's tree.
        ``b1``: the first moment's decay, so that m = (1 - b1) g after one
        step."""
        self._weights = weights
        self._grad_norms = jax.jit(lambda m: jax.tree.map(
            lambda n: n / (1.0 - b1), leaf_norms(m, self._fused)))
        self._grad_sample = jax.jit(lambda m, key: jax.tree.map(
            lambda x: x / (1.0 - b1), leaf_samples(m, self._fused, key)))
        self._update_norms = jax.jit(lambda master, key: leaf_norms(
            jax.tree.map(lambda a, b: a - b.astype(jnp.float32), master,
                         weights(key)), self._fused))

    def first_gradient(self, opt_state, seed: int) -> dict:
        """Of the gradient the optimizer's moments were given in the first
        step, read from the first moment after that step: its norm leaf by
        leaf, and a sample of its elements drawn from the seed."""
        m = opt_state.inner.exp_avg
        return {"grad_norms": self._grad_norms(m),
                "grad_sample": self._grad_sample(m, sample_key(seed))}

    def update_norms(self, opt_state, seed: int):
        """Leaf norms of (float32 master weights now - seeded weights)."""
        return self._update_norms(opt_state.master, common.seed_key(seed))

    def compiles(self) -> int:
        """How many times the step has compiled: 1 where nothing compiled
        in the window."""
        return self.step._cache_size()

    def hlo_text(self, params, opt_state, batch) -> str:
        return self.step.lower(params, opt_state, *batch).compile().as_text()

    def free(self):
        """Drops the compiled step before the reference runs."""
        self.step = None

"""The expert-model trainer at the user's entry point,
``examples/instella/pretrain_instella.py``: its ``build`` makes the model,
the optimizer and the jitted, state-donating step from the same argv its
``main`` takes, and touches no device, so ``rehearse.py`` compiles the same
step for a described chip. The seeded weights of the reference family are
placed in the program's own tree, with a fresh optimizer state made by the
library call ``main`` makes (``MixedPrecisionOptimizer(FusedAdam).init``).

The two trees hold the same leaves in two arrangements: the program stacks
its expert layers on a leading axis under ``layers`` (it scans them), the
reference keeps each expert layer a tree of its own under ``layers/<i>``
with a leading axis of 1 (``references/instella.py`` says why).
``stacked`` and ``apart`` turn one into the other; the readings are taken
from the program's state in the reference's arrangement, inside the jitted
readers, so no copy of the state is made.
"""

from __future__ import annotations

import os
import sys

import jax

import jax.numpy as jnp

from ..references import common
from ..references.train import leaf_norms, leaf_samples
from .common import TrainProgram


def stacked(tree: dict) -> dict:
    """The reference's arrangement as the program's: the expert layers'
    trees joined on their leading axis."""
    each = [tree["layers"][i] for i in sorted(tree["layers"], key=int)]
    return dict(tree, layers=jax.tree.map(
        lambda *xs: jnp.concatenate(xs, axis=0), *each))


def apart(tree: dict) -> dict:
    """The program's arrangement as the reference's."""
    n = jax.tree.leaves(tree["layers"])[0].shape[0]
    return dict(tree, layers={
        str(i): jax.tree.map(lambda a: a[i:i + 1], tree["layers"])
        for i in range(n)})


def _entry(root: str):
    sys.path.insert(0, os.path.join(root, "examples", "instella"))
    import pretrain_instella

    return pretrain_instella


def argv(cfg: dict, mix: dict) -> list:
    """The configuration file and the mix as the trainer's arguments. What
    the trainer has no argument for must stand at the value it builds in."""
    from ..references import instella

    z = instella.sizes(cfg)
    rs = cfg["rope_scaling"]
    fixed = {"beta_fast": 32, "beta_slow": 1, "mscale": 1,
             "mscale_all_dim": 1, "type": "yarn"}
    for key, value in fixed.items():
        if rs[key] != value:
            raise ValueError(f"the trainer builds rope_scaling.{key} = "
                             f"{value}; the configuration says {rs[key]}")
    if cfg["rms_norm_eps"] != 1e-6 or cfg["hidden_act"] != "silu":
        raise ValueError("the trainer builds rms_norm_eps 1e-6 and silu")
    opt = mix["optimizer"]
    if opt["name"] != "adam":
        raise ValueError("pretrain_instella.py trains with FusedAdam")
    if mix["num_microbatches"] != 1:
        raise ValueError("pretrain_instella.py takes a step's rows as one "
                         "microbatch")
    out = ["--hidden", z["hidden"], "--layers", z["layers"],
           "--dense-layers", z["dense_layers"], "--heads", z["heads"],
           "--qk-nope-dim", z["nope"], "--qk-rope-dim", z["rope"],
           "--v-dim", z["v"], "--kv-lora-rank", z["latent"],
           "--ffn", z["dense_ffn"], "--moe-ffn", z["expert_ffn"],
           "--shared-experts", cfg["n_shared_experts"],
           "--experts", z["experts"], "--experts-held", z["held"],
           "--first-expert-held", z["first_held"], "--top-k", z["top_k"],
           "--routed-scaling", cfg["routed_scaling_factor"],
           "--vocab", z["vocab"], "--rope-theta", cfg["rope_theta"],
           "--yarn-factor", rs["factor"], "--yarn-original-seq",
           rs["original_max_position_embeddings"], "--seq", mix["seq"],
           "--micro-batch", mix["batch"], "--lr", opt["lr"],
           "--opt-level", mix["opt_level"]]
    if not cfg["farskip"]:
        out.append("--no-farskip")
    return [str(a) for a in out]


def build(cfg: dict, mix: dict, root: str | None = None):
    """``(model, policy, mp_opt, train_step)`` as the trainer's ``main``
    builds them."""
    root = root or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    entry = _entry(root)
    return entry.build(entry.parse_args(argv(cfg, mix)))


class Program(TrainProgram):

    FEED = ("tokens", "targets")

    def __init__(self, root: str, cfg: dict, mix: dict, n_chips: int):
        from apex_tpu import amp

        if n_chips != 1:
            raise ValueError("one rank's share runs on one chip")
        super().__init__(cfg, mix)
        model, policy, mp_opt, self._jitted = build(cfg, mix, root)
        self.rows = mix["batch"]
        self.tokens_per_step = mix["batch"] * mix["seq"]
        self._moe = None
        abstract = jax.eval_shape(lambda k: amp.cast_params(
            model.init(k), policy), jax.random.PRNGKey(0))
        dtypes = jax.tree.map(lambda a: a.dtype, abstract)
        drawn = jnp.dtype(mix["weights_dtype"])

        def weights(key):
            """The family's seeded weights, drawn in the mix's
            ``weights_dtype``, in the program's arrangement and types (O2
            keeps the norms in float32: the same values, wider)."""
            return jax.tree.map(
                lambda w, t: w.astype(t),
                stacked(self.fam.init_weights(cfg, key, drawn)), dtypes)

        sig = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
        got = jax.eval_shape(weights, jax.random.PRNGKey(0))
        if sig(got) != sig(abstract):
            raise RuntimeError("the seeded weights do not match the "
                               f"program's tree: {sig(got)} against "
                               f"{sig(abstract)}")
        # b1: FusedAdam's default, which the trainer leaves alone; after
        # one step the first moment is (1 - b1) times the gradient
        b1 = 0.9
        self._grad_norms = jax.jit(lambda m: jax.tree.map(
            lambda n: n / (1.0 - b1), leaf_norms(apart(m), self._fused)))
        self._grad_sample = jax.jit(lambda m, key: jax.tree.map(
            lambda x: x / (1.0 - b1),
            leaf_samples(apart(m), self._fused, key)))
        self._update_norms = jax.jit(lambda master, key: leaf_norms(
            apart(jax.tree.map(lambda a, b: a - b.astype(jnp.float32),
                               master, weights(key))), self._fused))
        self._make = jax.jit(
            lambda key: (lambda p: (p, mp_opt.init(p)))(weights(key)))

    def step(self, params, opt_state, *batch):
        """The trainer's step; the routed experts' counters of the last
        step dispatched are kept for ``free`` to report."""
        out = self._jitted(params, opt_state, *batch)
        self._moe = out[3]["moe"]
        return out

    def state(self, seed: int):
        """Seeded weights and a fresh optimizer state, made on the device
        in one call."""
        return self._make(common.seed_key(seed))

    def place(self, batch: dict):
        return tuple(jax.device_put(batch[k]) for k in self.FEED)

    def compiles(self) -> int:
        return self._jitted._cache_size()

    def hlo_text(self, params, opt_state, batch) -> str:
        return self._jitted.lower(
            params, opt_state, *batch).compile().as_text()

    def free(self):
        """Drops the compiled step before the reference runs, and says on
        standard error what the routed experts counted in the last step,
        expert layer by expert layer."""
        if self._moe is not None:
            moe = {k: [float(x) for x in v]
                   for k, v in jax.device_get(self._moe).items()}
            print(f"routed experts, last step, by layer: {moe}",
                  file=sys.stderr)
        self._jitted = self._moe = None

"""The GPT trainer at the user's entry point: ``pretrain_gpt.main(argv)``
builds the mesh, the model and the jitted step, and hands the step back.

``main`` has no ``--seed`` (its weights are ``PRNGKey(0)``'s and its batches
``default_rng(0)``'s), so it is run for the fewest steps that compile the
step; its own state is then dropped and the benchmark's seeded weights are
placed under the same shardings, with a fresh optimizer state made by the
library call ``main`` makes (``MixedPrecisionOptimizer(FusedAdam).init``).
"""

from __future__ import annotations

import os
import sys

import jax

from ..references import common
from .common import TrainProgram, seeded_weights


class Program(TrainProgram):

    def __init__(self, root: str, cfg: dict, mix: dict, n_chips: int):
        super().__init__(cfg, mix)
        sys.path.insert(0, os.path.join(root, "examples", "gpt"))
        import pretrain_gpt

        z = self.fam.sizes(cfg)
        opt = mix["optimizer"]
        if opt["name"] != "adam":
            raise ValueError("pretrain_gpt.py trains with FusedAdam")
        argv = ["--hidden", z["hidden"], "--layers", z["layers"],
                "--heads", z["heads"], "--seq", mix["seq"],
                "--vocab", z["vocab"], "--micro-batch", mix["micro_batch"],
                "--num-microbatches", mix["num_microbatches"],
                "--lr", opt["lr"], "--opt-level", mix["opt_level"],
                "--steps", 1]
        run = pretrain_gpt.main([str(a) for a in argv])
        # take the state out of the record: the chip holds two copies of
        # the training state, not three
        self.step = run.pop("train_step")
        params, opt_state = run.pop("params"), run.pop("opt_state")
        example = run["next_batch"]()
        self.rows = int(example[0].shape[0])
        self.tokens_per_step = int(run["tokens_per_step"])
        self._batch_sharding = example[0].sharding
        shardings = jax.tree.map(lambda a: a.sharding, (params, opt_state))
        abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        del params, opt_state, run, example

        from apex_tpu import amp
        from apex_tpu.optimizers import FusedAdam

        mp_opt = amp.MixedPrecisionOptimizer(
            FusedAdam(lr=opt["lr"]), amp.get_policy(mix["opt_level"]))
        # b1: FusedAdam's default, which main leaves alone
        self._readers(seeded_weights(self.fam, cfg, mix, abstract), b1=0.9)
        self._make = jax.jit(
            lambda key: (lambda p: (p, mp_opt.init(p)))(self._weights(key)),
            out_shardings=shardings)

    def state(self, seed: int):
        """Seeded weights and a fresh optimizer state, made on the device
        in one call under the shardings ``main`` gave its own."""
        return self._make(common.seed_key(seed))

    def place(self, batch: dict):
        return tuple(jax.device_put(batch[k], self._batch_sharding)
                     for k in ("tokens", "targets"))

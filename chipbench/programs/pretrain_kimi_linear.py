"""The Kimi Linear trainer at the user's entry point,
``examples/kimi_linear/pretrain_kimi_linear.py``: its ``build`` makes the
model, the optimizer and the jitted, state-donating step from the same argv
its ``main`` takes, and touches no device, so ``rehearse.py`` compiles the
same step for a described chip. The seeded weights of the reference family
are placed in the program's own tree, with a fresh optimizer state made by
the library call ``main`` makes (``MixedPrecisionOptimizer(FusedAdam).init``).

The two trees hold the same leaves in two arrangements, as the LFM2
adapter's do (``pretrain_lfm2.stacked`` and ``apart`` turn one into the
other): the program stacks each run of like layers under ``layers/<run, two
digits>``, the reference keeps every layer a tree of its own under
``layers/<i>``. The seeded weights are made by a call of their own
(``PERF.md``, Findings, PR 34).
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

from ..references import common
from ..references.train import leaf_norms, leaf_samples
from .common import TrainProgram
from .pretrain_lfm2 import apart, stacked


def _entry(root: str):
    sys.path.insert(0, os.path.join(root, "examples", "kimi_linear"))
    import pretrain_kimi_linear

    return pretrain_kimi_linear


def argv(cfg: dict, mix: dict) -> list:
    """The configuration file and the mix as the trainer's arguments. What
    the trainer has no argument for must stand at the value it builds in."""
    from ..references import kimi_linear

    z = kimi_linear.sizes(cfg)
    lin = cfg["linear_attn_config"]
    built_in = {"mla_use_nope": True, "moe_renormalize": True,
                "moe_router_activation_func": "sigmoid",
                "num_expert_group": 1, "topk_group": 1, "moe_layer_freq": 1,
                "tie_word_embeddings": False, "q_lora_rank": None,
                "hidden_act": "silu", "num_nextn_predict_layers": 0}
    for key, value in built_in.items():
        if cfg[key] != value:
            raise ValueError(f"the trainer builds {key} = {value}; the "
                             f"configuration says {cfg[key]}")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("the latent attention gives every head its own "
                         "keys and values")
    opt = mix["optimizer"]
    if opt["name"] != "adam":
        raise ValueError("pretrain_kimi_linear.py trains with FusedAdam")
    if mix["num_microbatches"] != 1:
        raise ValueError("pretrain_kimi_linear.py takes a step's rows as "
                         "one microbatch")
    join = lambda xs: ",".join(str(x) for x in xs)
    out = ["--hidden", z["hidden"], "--layers", z["layers"],
           "--kda-layers", join(lin["kda_layers"]),
           "--full-attn-layers", join(lin["full_attn_layers"]),
           "--dense-layers", z["dense_layers"], "--heads", z["heads"],
           "--qk-nope-dim", z["nope"], "--qk-rope-dim", z["rope"],
           "--v-dim", z["v"], "--kv-lora-rank", z["latent"],
           "--kda-heads", z["kda_heads"], "--kda-head-dim", z["kda_head"],
           "--conv-taps", z["taps"], "--ffn", z["dense_ffn"],
           "--moe-ffn", z["expert_ffn"],
           "--shared-experts", cfg["num_shared_experts"],
           "--experts", z["experts"], "--experts-held", z["held"],
           "--first-expert-held", z["first_held"], "--top-k", z["top_k"],
           "--routed-scaling", cfg["routed_scaling_factor"],
           "--vocab", z["vocab"], "--norm-eps", cfg["rms_norm_eps"],
           "--seq", mix["seq"], "--micro-batch", mix["batch"],
           "--lr", opt["lr"], "--opt-level", mix["opt_level"]]
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
        self._steps, self._moe_first, self._moe_last = 0, None, None
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
        # The seeded weights are made by a call of their own and handed on
        # as arrays: inside one program with what reads them the compiler
        # may keep the float32 draw where the bf16 weight is meant (a
        # convert there and back is "excess precision" it is allowed to
        # drop), and the masters or the weights' change would then start a
        # rounding away from the reference's (it did, on the chip, in the
        # one small leaf of a run of one layer: PERF.md, Findings, PR 34).
        self._seeded = jax.jit(weights)
        self._update_norms = jax.jit(lambda master, seeded: leaf_norms(
            apart(jax.tree.map(lambda a, b: a - b.astype(jnp.float32),
                               master, seeded)), self._fused))
        self._make = jax.jit(lambda p: (p, mp_opt.init(p)),
                             donate_argnums=0)

    def step(self, params, opt_state, *batch):
        """The trainer's step; the layers' counters of the
        window's first step (the one after the three the reference
        follows) and of the last step dispatched are kept for ``free`` to
        report."""
        out = self._jitted(params, opt_state, *batch)
        self._steps += 1
        self._moe_last = out[3]["moe"]
        if self._steps == 4:
            self._moe_first = self._moe_last
        return out

    def state(self, seed: int):
        """Seeded weights and a fresh optimizer state, made on the
        device."""
        return self._make(self._seeded(common.seed_key(seed)))

    def update_norms(self, opt_state, seed: int):
        """Leaf norms of (float32 master weights now - seeded weights)."""
        return self._update_norms(opt_state.master,
                                  self._seeded(common.seed_key(seed)))

    def place(self, batch: dict):
        return tuple(jax.device_put(batch[k]) for k in self.FEED)

    def compiles(self) -> int:
        return self._jitted._cache_size()

    def hlo_text(self, params, opt_state, batch) -> str:
        return self._jitted.lower(
            params, opt_state, *batch).compile().as_text()

    def free(self):
        """Drops the compiled step before the reference runs, and says on
        standard error what the layers counted in the last step (the routed
        experts' counters expert layer by expert layer, and
        ``kda_min_chunk_log_decay`` and ``kda_chunks`` KDA layer by KDA
        layer), and the held load over an even router's (all expert layers
        together) in the window's first step and in its last."""
        if self._moe_last:
            z = self.fam.sizes(self.cfg)
            even = (self.tokens_per_step * z["top_k"] * z["held"]
                    / z["experts"])
            first, last = jax.device_get(
                [self._moe_first or self._moe_last, self._moe_last])
            share = lambda s: float(s["assignments"].mean() / even)
            print(f"held load over even: window's first step "
                  f"{share(first):.4f}, last {share(last):.4f}",
                  file=sys.stderr)
            print("counters, last step, by layer: "
                  f"{ {k: [float(x) for x in v] for k, v in last.items()} }",
                  file=sys.stderr)
        self._jitted = self._moe_first = self._moe_last = None

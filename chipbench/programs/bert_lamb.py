"""The BERT trainer. ``examples/bert/pretrain_bert.py``'s ``main`` takes no
argv and returns nothing, so the harness cannot call it: the step is
composed here from the same library calls as its plain (non-ZeRO) branch,
``pretrain_bert.py:235-248``: ``BertModel`` in bf16 with recompute,
``MixedPrecisionOptimizer(FusedLAMB(lr, weight_decay=0.01), O2)``, the
scaled loss differentiated and applied in one jitted step."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..references import common
from .common import TrainProgram, seeded_weights


def build(cfg: dict, mix: dict):
    """``(model, policy, mp_opt, train_step)`` as ``pretrain_bert.py``'s
    plain branch builds them; nothing touches a device yet, so
    ``rehearse.py`` can compile the step for a described chip."""
    from apex_tpu import amp
    from apex_tpu.models import BertConfig, BertModel
    from apex_tpu.optimizers import FusedLAMB

    from ..references import bert

    z = bert.sizes(cfg)
    opt = mix["optimizer"]
    if opt["name"] != "lamb":
        raise ValueError("this step trains with FusedLAMB")
    policy = amp.get_policy(mix["opt_level"])
    model = BertModel(BertConfig(
        vocab_size=z["vocab"], hidden_size=z["hidden"],
        num_layers=z["layers"], num_attention_heads=z["heads"],
        max_seq_len=mix["seq"], type_vocab_size=z["types"],
        ffn_hidden_size=z["ffn"], hidden_dropout=0.0, axis=None,
        compute_dtype=jnp.bfloat16, remat=True))
    mp_opt = amp.MixedPrecisionOptimizer(
        FusedLAMB(lr=opt["lr"], weight_decay=opt["weight_decay"]), policy)

    @jax.jit
    def train_step(p, s, toks, attn, lmask, labels, nsp, types):
        def scaled(p):
            return mp_opt.scale_loss(
                model.loss(p, toks, attn, lmask, labels, nsp, types), s)

        ls, gs = jax.value_and_grad(scaled)(p)
        np_, ns, m = mp_opt.apply_gradients(s, p, gs)
        return np_, ns, ls / s.scaler.loss_scale, m

    return model, policy, mp_opt, train_step


class Program(TrainProgram):

    FEED = ("tokens", "attention", "loss_mask", "labels", "nsp", "types")

    def __init__(self, root: str, cfg: dict, mix: dict, n_chips: int):
        from apex_tpu import amp

        if n_chips != 1:
            raise ValueError("the plain BERT step runs on one chip")
        super().__init__(cfg, mix)
        model, policy, mp_opt, train_step = build(cfg, mix)
        self.step = train_step
        self.rows = mix["batch"]
        self.tokens_per_step = mix["batch"] * mix["seq"]
        abstract = jax.eval_shape(lambda k: amp.cast_params(
            model.init(k), policy), jax.random.PRNGKey(0))
        # b1: FusedLAMB's default decay; its moments get the gradient
        # after the clip to a global norm of 1
        self._readers(seeded_weights(self.fam, cfg, mix, abstract), b1=0.9)
        self._make = jax.jit(
            lambda key: (lambda p: (p, mp_opt.init(p)))(self._weights(key)))

    def state(self, seed: int):
        """Seeded weights and a fresh optimizer state, made on the device
        in one call."""
        return self._make(common.seed_key(seed))

    def place(self, batch: dict):
        return tuple(jax.device_put(batch[k]) for k in self.FEED)

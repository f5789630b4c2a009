"""Pretraining a DeepSeek-V3-style expert model (latent attention, shared
and routed experts without dropped tokens, a far-skip residual) as ONE rank
of an expert-parallel stage trains it: ``apex_tpu.models.InstellaModel``
under amp O2 with ``MixedPrecisionOptimizer(FusedAdam)``, the dynamic loss
scale, full recompute over scanned layers, the chunked head loss. The
defaults are Instella-MoE-16B-A3B-Base's published widths and one chip's
share of an 8-way expert-parallel stage (8 of 64 experts, an eighth of the
vocabulary, one dense and four expert layers).

    python examples/instella/pretrain_instella.py --steps 10
    python examples/instella/pretrain_instella.py --hidden 64 --heads 4 \
        --qk-nope-dim 12 --qk-rope-dim 4 --v-dim 16 --kv-lora-rank 32 \
        --ffn 160 --moe-ffn 24 --experts 16 --experts-held 4 --top-k 3 \
        --vocab 512 --seq 64 --micro-batch 2 --steps 5     # on the CPU

The step donates ``params`` and ``opt_state``: undonated it would hold the
float32 masters and both moments twice, and this model's share does not fit
the chip that way. A caller that keeps driving the step rebinds both from
its outputs, as ``main`` does. No exchange between ranks is built: the
layer routes over every expert and adds its own experts' terms.

``main(argv)`` returns the run's record, as ``pretrain_gpt.main`` does:
``losses``, ``loss_scales``, ``found_inf``, ``moe`` (the routed experts'
counters of the last step), ``first_step_seconds``, ``seconds_per_step``,
``tokens_per_step`` and the live ``train_step`` / ``params`` / ``opt_state``
/ ``next_batch``.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import amp
from apex_tpu.models import InstellaConfig, InstellaModel
from apex_tpu.optimizers import FusedAdam
from apex_tpu.utils.compile_cache import enable_compile_cache

_D = InstellaConfig()


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    # the model's sizes
    p.add_argument("--hidden", type=int, default=_D.hidden_size)
    p.add_argument("--layers", type=int, default=_D.num_layers,
                   help="layers held, the dense ones included")
    p.add_argument("--dense-layers", type=int, default=_D.num_dense_layers)
    p.add_argument("--heads", type=int, default=_D.num_attention_heads)
    p.add_argument("--qk-nope-dim", type=int, default=_D.qk_nope_head_dim)
    p.add_argument("--qk-rope-dim", type=int, default=_D.qk_rope_head_dim)
    p.add_argument("--v-dim", type=int, default=_D.v_head_dim)
    p.add_argument("--kv-lora-rank", type=int, default=_D.kv_lora_rank)
    p.add_argument("--ffn", type=int, default=_D.ffn_hidden_size,
                   help="width of the dense layers' MLP")
    p.add_argument("--moe-ffn", type=int, default=_D.moe_ffn_hidden_size,
                   help="width of one expert")
    p.add_argument("--shared-experts", type=int,
                   default=_D.num_shared_experts)
    p.add_argument("--experts", type=int, default=_D.num_experts,
                   help="experts the router scores")
    p.add_argument("--top-k", type=int, default=_D.top_k)
    p.add_argument("--routed-scaling", type=float,
                   default=_D.routed_scaling_factor)
    p.add_argument("--vocab", type=int, default=_D.vocab_size,
                   help="rows of the embedding and the head held here")
    p.add_argument("--rope-theta", type=float, default=_D.rope_theta)
    p.add_argument("--yarn-factor", type=float, default=_D.yarn_factor)
    p.add_argument("--yarn-original-seq", type=int,
                   default=_D.yarn_original_seq)
    p.add_argument("--no-farskip", action="store_true",
                   help="the usual residual path")
    # the experts held
    p.add_argument("--experts-held", type=int, default=_D.experts_held,
                   help="how many of --experts this rank holds")
    p.add_argument("--first-expert-held", type=int,
                   default=_D.first_expert_held)
    # the run
    p.add_argument("--seq", type=int, default=_D.max_seq_len)
    p.add_argument("--micro-batch", type=int, default=8)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--steps", type=int, default=10)
    return p.parse_args(argv)


def build(args):
    """``(model, policy, mp_opt, train_step)`` from parsed arguments.
    Nothing touches a device, so the step can be compiled for a described
    chip (``chipbench/rehearse.py``)."""
    policy = amp.get_policy(args.opt_level)
    tokens = args.micro_batch * args.seq
    model = InstellaModel(InstellaConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_layers=args.layers, num_dense_layers=args.dense_layers,
        num_attention_heads=args.heads, qk_nope_head_dim=args.qk_nope_dim,
        qk_rope_head_dim=args.qk_rope_dim, v_head_dim=args.v_dim,
        kv_lora_rank=args.kv_lora_rank, ffn_hidden_size=args.ffn,
        moe_ffn_hidden_size=args.moe_ffn,
        num_shared_experts=args.shared_experts, num_experts=args.experts,
        experts_held=args.experts_held,
        first_expert_held=args.first_expert_held, top_k=args.top_k,
        routed_scaling_factor=args.routed_scaling,
        farskip=not args.no_farskip, rope_theta=args.rope_theta,
        yarn_factor=args.yarn_factor,
        yarn_original_seq=args.yarn_original_seq, max_seq_len=args.seq,
        compute_dtype=jnp.bfloat16 if args.opt_level != "O0"
        else jnp.float32,
        # the logits of a microbatch in float32, 512 MiB a chunk at most
        lm_head_chunks=max(1, -(-tokens * args.vocab * 4 // 2**29)),
        remat=True))
    mp_opt = amp.MixedPrecisionOptimizer(FusedAdam(lr=args.lr), policy)

    def train_step(params, opt_state, tokens, targets):
        scale = opt_state.scaler.loss_scale

        def scaled(p):
            loss, stats = model.loss(p, tokens, targets)
            return loss * scale, (loss, stats)

        (_, (loss, stats)), grads = jax.value_and_grad(
            scaled, has_aux=True)(params)
        stats = stats or {}            # no expert layer, no counters
        # an assignment the buffer could not hold is never lost in
        # silence: the step is skipped, as one with an overflowed gradient
        overflowed = jnp.sum(stats.get("overflow", 0.0)) > 0
        of_grads = []

        def skip_too(found_inf):
            of_grads.append(found_inf)
            return found_inf | overflowed

        new_params, new_state, metrics = mp_opt.apply_gradients(
            opt_state, params, grads, found_inf_reducer=skip_too)
        # ... but a full buffer says nothing of the loss scale: only an
        # overflowed gradient moves it
        spare = overflowed & ~of_grads[0]
        scaler = jax.tree.map(lambda old, new: jnp.where(spare, old, new),
                              opt_state.scaler, new_state.scaler)
        metrics["loss_scale"] = scaler.loss_scale
        metrics["moe"] = stats
        return new_params, new_state._replace(scaler=scaler), loss, metrics

    return model, policy, mp_opt, jax.jit(train_step, donate_argnums=(0, 1))


def main(argv=None):
    t_entry = time.perf_counter()
    args = parse_args(argv)
    enable_compile_cache()
    model, policy, mp_opt, train_step = build(args)

    @jax.jit
    def state(key):
        params = amp.cast_params(model.init(key), policy)
        return params, mp_opt.init(params)

    params, opt_state = state(jax.random.PRNGKey(0))
    batch = args.micro_batch
    rng = np.random.default_rng(0)

    def next_batch():
        toks = jnp.asarray(rng.integers(0, args.vocab, (batch, args.seq)),
                           jnp.int32)
        return toks, jnp.roll(toks, -1, axis=-1)

    log, metrics = [], None
    first_step_seconds, t_steady = None, None
    for i in range(args.steps):
        params, opt_state, loss, metrics = train_step(
            params, opt_state, *next_batch())
        log.append((loss, metrics["loss_scale"], metrics["found_inf"]))
        if i == 0:
            first_step_seconds = (loss.block_until_ready(),
                                  time.perf_counter() - t_entry)[1]
            t_steady = time.perf_counter()
    log = jax.device_get(log)
    dt = ((time.perf_counter() - t_steady) / (args.steps - 1)
          if args.steps > 1 else float("nan"))
    for i, (loss, scale, skipped) in enumerate(log):
        print(f"step {i}: loss {float(loss):.4f} scale {float(scale):.0f}"
              + (" (skipped)" if skipped else ""))
    moe = None if metrics is None else {
        k: [float(x) for x in v]
        for k, v in jax.device_get(metrics["moe"]).items()}
    if moe:
        print(f"routed experts, last step, by layer: {moe}")
    return {
        "losses": [float(l) for l, _, _ in log],
        "loss_scales": [float(s) for _, s, _ in log],
        "found_inf": [bool(f) for _, _, f in log],
        "moe": moe,
        "first_step_seconds": first_step_seconds,
        "seconds_per_step": dt,
        "tokens_per_step": batch * args.seq,
        "train_step": train_step,
        "params": params,
        "opt_state": opt_state,
        "next_batch": next_batch,
    }


if __name__ == "__main__":
    main()

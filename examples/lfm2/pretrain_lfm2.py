"""Pretraining an LFM2 expert model (gated short convolutions beside
grouped-query attention in an order a list gives, routed experts without
dropped tokens) as ONE rank of an expert-parallel stage trains it:
``apex_tpu.models.Lfm2Model`` under amp O2 with
``MixedPrecisionOptimizer(FusedAdam)``, the dynamic loss scale, full
recompute over the scanned runs of like layers, the chunked head loss on the
tied table. The defaults are LFM2-8B-A1B's published widths and one chip's
share of a 4-way expert-parallel stage (8 of 32 experts, a quarter of the
vocabulary, the leading dense layer and one period of the pattern).

    python examples/lfm2/pretrain_lfm2.py --steps 10
    python examples/lfm2/pretrain_lfm2.py --hidden 64 --heads 4 \
        --kv-heads 2 --ffn 96 --moe-ffn 32 --experts 8 --experts-held 4 \
        --top-k 2 --vocab 512 --seq 64 --micro-batch 2 --steps 5  # the CPU

The step donates ``params`` and ``opt_state``; a caller that keeps driving it
rebinds both from its outputs, as ``main`` does. No exchange between ranks
is built: a layer routes over every expert and adds its own experts' terms.

``main(argv)`` returns the run's record, as ``pretrain_instella.main`` does:
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
from apex_tpu.models import Lfm2Config, Lfm2Model
from apex_tpu.optimizers import FusedAdam
from apex_tpu.transformer.amp import build_dropless_train_step
from apex_tpu.utils.compile_cache import enable_compile_cache

_D = Lfm2Config()


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    # the model's sizes
    p.add_argument("--hidden", type=int, default=_D.hidden_size)
    p.add_argument("--layer-types", default=",".join(_D.layer_types),
                   help="each layer's operator in order, 'conv' or "
                        "'full_attention', comma-separated")
    p.add_argument("--dense-layers", type=int, default=_D.num_dense_layers,
                   help="leading layers whose feed-forward is the dense MLP")
    p.add_argument("--heads", type=int, default=_D.num_attention_heads)
    p.add_argument("--kv-heads", type=int, default=_D.num_kv_heads)
    p.add_argument("--conv-taps", type=int, default=_D.conv_taps)
    p.add_argument("--ffn", type=int, default=_D.ffn_hidden_size,
                   help="width of the dense layers' MLP")
    p.add_argument("--moe-ffn", type=int, default=_D.moe_ffn_hidden_size,
                   help="width of one expert")
    p.add_argument("--experts", type=int, default=_D.num_experts,
                   help="experts the router scores")
    p.add_argument("--top-k", type=int, default=_D.top_k)
    p.add_argument("--routed-scaling", type=float,
                   default=_D.routed_scaling_factor)
    p.add_argument("--vocab", type=int, default=_D.vocab_size,
                   help="rows of the tied table held here")
    p.add_argument("--rope-theta", type=float, default=_D.rope_theta)
    p.add_argument("--norm-eps", type=float, default=_D.norm_eps)
    # the experts held
    p.add_argument("--experts-held", type=int, default=_D.experts_held,
                   help="how many of --experts this rank holds")
    p.add_argument("--first-expert-held", type=int,
                   default=_D.first_expert_held)
    # the run
    p.add_argument("--seq", type=int, default=_D.max_seq_len)
    p.add_argument("--micro-batch", type=int, default=4)
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
    model = Lfm2Model(Lfm2Config(
        vocab_size=args.vocab, hidden_size=args.hidden,
        layer_types=tuple(args.layer_types.split(",")),
        num_dense_layers=args.dense_layers,
        num_attention_heads=args.heads, num_kv_heads=args.kv_heads,
        conv_taps=args.conv_taps, ffn_hidden_size=args.ffn,
        moe_ffn_hidden_size=args.moe_ffn, num_experts=args.experts,
        experts_held=args.experts_held,
        first_expert_held=args.first_expert_held, top_k=args.top_k,
        routed_scaling_factor=args.routed_scaling, norm_eps=args.norm_eps,
        rope_theta=args.rope_theta, max_seq_len=args.seq,
        compute_dtype=jnp.bfloat16 if args.opt_level != "O0"
        else jnp.float32,
        # the logits of a microbatch in float32, 512 MiB a chunk at most
        lm_head_chunks=max(1, -(-tokens * args.vocab * 4 // 2**29)),
        remat=True))
    mp_opt = amp.MixedPrecisionOptimizer(FusedAdam(lr=args.lr), policy)
    train_step = build_dropless_train_step(model, mp_opt)
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

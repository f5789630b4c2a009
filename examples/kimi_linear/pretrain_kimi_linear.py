"""Pretraining a Kimi Linear expert model (Kimi Delta Attention beside
latent attention without positions, three to one, in an order the config's
lists give; a shared expert beside routed ones without dropped tokens) as ONE
rank of an expert-parallel stage trains it:
``apex_tpu.models.KimiLinearModel`` under amp O2 with
``MixedPrecisionOptimizer(FusedAdam)``, the dynamic loss scale, full
recompute over the scanned runs of like layers, the chunked head loss. The
defaults are Kimi-Linear-48B-A3B's published widths and one chip's share of
a 32-way expert-parallel stage (8 of 256 experts, an eighth of the
vocabulary, the leading dense layer and one period of the pattern).

    python examples/kimi_linear/pretrain_kimi_linear.py --steps 10
    python examples/kimi_linear/pretrain_kimi_linear.py --hidden 64 \
        --heads 4 --qk-nope-dim 16 --qk-rope-dim 8 --v-dim 16 \
        --kv-lora-rank 32 --kda-heads 4 --kda-head-dim 16 --ffn 96 \
        --moe-ffn 32 --experts 16 --experts-held 4 --top-k 2 --vocab 512 \
        --seq 64 --micro-batch 2 --steps 5                       # the CPU

The step donates ``params`` and ``opt_state``; a caller that keeps driving it
rebinds both from its outputs, as ``main`` does. No exchange between ranks
is built: a layer routes over every expert and adds its own experts' terms.

``main(argv)`` returns the run's record, as ``pretrain_lfm2.main`` does:
``losses``, ``loss_scales``, ``found_inf``, ``moe`` (the counters of the last
step: the routed experts', and the KDA layers' ``kda_min_chunk_log_decay``,
``kda_chunks``, ``kda_kernel_chunks`` and ``kda_transpose_chunks``),
``first_step_seconds``, ``seconds_per_step``, ``tokens_per_step`` and the
live ``train_step`` / ``params`` / ``opt_state`` / ``next_batch``.
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
from apex_tpu.models import KimiLinearConfig, KimiLinearModel
from apex_tpu.optimizers import FusedAdam
from apex_tpu.transformer.amp import build_dropless_train_step
from apex_tpu.utils.compile_cache import enable_compile_cache

_D = KimiLinearConfig()


def _ints(text: str):
    return tuple(int(x) for x in text.split(",") if x)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    join = lambda xs: ",".join(str(x) for x in xs)
    # the model's sizes
    p.add_argument("--hidden", type=int, default=_D.hidden_size)
    p.add_argument("--layers", type=int, default=_D.num_layers)
    p.add_argument("--kda-layers", type=_ints, default=join(_D.kda_layers),
                   help="the layers that mix with KDA, counted from 1, "
                        "comma-separated")
    p.add_argument("--full-attn-layers", type=_ints,
                   default=join(_D.full_attn_layers),
                   help="the layers that attend (latent attention)")
    p.add_argument("--dense-layers", type=int, default=_D.num_dense_layers,
                   help="leading layers whose feed-forward is the dense MLP")
    p.add_argument("--heads", type=int, default=_D.num_attention_heads)
    p.add_argument("--qk-nope-dim", type=int, default=_D.qk_nope_head_dim)
    p.add_argument("--qk-rope-dim", type=int, default=_D.qk_rope_head_dim)
    p.add_argument("--v-dim", type=int, default=_D.v_head_dim)
    p.add_argument("--kv-lora-rank", type=int, default=_D.kv_lora_rank)
    p.add_argument("--kda-heads", type=int, default=_D.kda_heads)
    p.add_argument("--kda-head-dim", type=int, default=_D.kda_head_dim)
    p.add_argument("--conv-taps", type=int, default=_D.conv_taps)
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
                   help="rows of the table and of the head held here")
    p.add_argument("--norm-eps", type=float, default=_D.rms_norm_eps)
    # the experts held
    p.add_argument("--experts-held", type=int, default=_D.experts_held,
                   help="how many of --experts this rank holds")
    p.add_argument("--first-expert-held", type=int,
                   default=_D.first_expert_held)
    # the run
    p.add_argument("--seq", type=int, default=_D.max_seq_len)
    p.add_argument("--micro-batch", type=int, default=2)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--steps", type=int, default=10)
    return p.parse_args(argv)


def build(args):
    """``(model, policy, mp_opt, train_step)`` from parsed arguments.
    Nothing touches a device, so the step can be compiled for a described
    chip (``chipbench/rehearse.py``)."""
    policy = amp.get_policy(args.opt_level)
    # the logits of a microbatch in float32, 512 MiB a chunk at most, in a
    # number of chunks that divides the vocabulary
    head_chunks = max(1, -(-args.micro_batch * args.seq * args.vocab * 4
                           // 2**29))
    while args.vocab % head_chunks:
        head_chunks += 1
    model = KimiLinearModel(KimiLinearConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_layers=args.layers, kda_layers=args.kda_layers,
        full_attn_layers=args.full_attn_layers,
        num_dense_layers=args.dense_layers,
        num_attention_heads=args.heads,
        qk_nope_head_dim=args.qk_nope_dim, qk_rope_head_dim=args.qk_rope_dim,
        v_head_dim=args.v_dim, kv_lora_rank=args.kv_lora_rank,
        kda_heads=args.kda_heads, kda_head_dim=args.kda_head_dim,
        conv_taps=args.conv_taps,
        ffn_hidden_size=args.ffn, moe_ffn_hidden_size=args.moe_ffn,
        num_shared_experts=args.shared_experts, num_experts=args.experts,
        experts_held=args.experts_held,
        first_expert_held=args.first_expert_held, top_k=args.top_k,
        routed_scaling_factor=args.routed_scaling,
        rms_norm_eps=args.norm_eps, max_seq_len=args.seq,
        compute_dtype=jnp.bfloat16 if args.opt_level != "O0"
        else jnp.float32,
        lm_head_chunks=head_chunks, remat=True))
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
        print(f"counters, last step, by layer: {moe}")
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

"""Long-context GPT training: ring-attention context parallelism + streamed
flash kernels.

The capability recipe the reference cannot express (its long-sequence story
is activation checkpointing plus the sk<=2048 fused-softmax fallback,
apex/transformer/functional/fused_softmax.py:151-171): sequences shard over
the ``context`` mesh axis, attention runs as a ppermute ring with exact
cross-shard causal masking, and per-shard attention uses the STREAMED Pallas
flash kernels (K/V loop in the grid, VMEM block-bounded) so a single shard
handles 8k-16k tokens. Padding masks ride the ring as segment ids — no
(sq, SK) bias ever materializes.

Run on 8 virtual devices (cp=4 x dp=2, 4096-token context, 1024/shard):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/longcontext/train_long_context.py --cp 4 --dp 2 \
        --seq 4096 --steps 3
Run serial on one real TPU chip at 8k context (streamed kernels engage):
    python examples/longcontext/train_long_context.py --cp 1 --dp 1 \
        --seq 8192 --steps 3
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu import amp
from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.optimizers import FusedAdam
from apex_tpu.parallel import mesh as mesh_lib
from apex_tpu.parallel.distributed import allreduce_gradients_by_spec
from apex_tpu.transformer import tensor_parallel as tp_mod
from apex_tpu.utils.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cp", type=int, default=4, help="context-parallel size")
    ap.add_argument("--dp", type=int, default=2, help="data-parallel size")
    ap.add_argument("--seq", type=int, default=4096, help="GLOBAL context length")
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--batch", type=int, default=None,
                    help="global batch (default: dp)")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--sp-impl", choices=["ring", "ulysses"], default="ring")
    ap.add_argument("--lm-head-chunks", type=int, default=None,
                    help="chunked LM-head CE (at 32k tokens the full "
                         "(tokens, vocab) logits tensor alone is ~2 GB; "
                         "chunking keeps the head's peak HBM flat). "
                         "Size chunks to >=16k tokens each: every chunk "
                         "pays a read+write of the full dW_out gradient "
                         "(h x vocab) in backward, so over-chunking is "
                         "DMA-bound — measured at 1M tokens: 1024 chunks "
                         "27k tok/s, 32 chunks 288k tok/s, same loss")
    ap.add_argument("--window", type=int, default=None,
                    help="sliding-window attention (GPTConfig."
                         "attention_window): O(s*window) attention cost "
                         "instead of O(s^2) — the local-attention pairing "
                         "for very long contexts")
    ap.add_argument("--pos", choices=["learned", "rope", "none"],
                    default="learned",
                    help="position encoding; rope has NO position table "
                         "(a learned table at 1M tokens is ~3.75 GB of "
                         "params + Adam state)")
    ap.add_argument("--output", default=None,
                    help="write a JSON measurement record")
    args = ap.parse_args()
    enable_compile_cache()

    n = args.cp * args.dp
    batch = args.batch or args.dp
    serial = n == 1

    cfg = GPTConfig(
        vocab_size=args.vocab,
        hidden_size=args.hidden,
        num_layers=args.layers,
        num_attention_heads=args.heads,
        max_seq_len=args.seq,
        hidden_dropout=0.0,
        axis=None,
        context_axis=None if serial else mesh_lib.AXIS_CONTEXT,
        sequence_parallel_impl=args.sp_impl,
        compute_dtype=jnp.bfloat16,
        remat=True,
        lm_head_chunks=args.lm_head_chunks,
        attention_window=args.window,
        position_embedding=args.pos,
    )
    model = GPTModel(cfg)
    policy = amp.get_policy("O2")
    mp_opt = amp.MixedPrecisionOptimizer(FusedAdam(lr=1e-4), policy)
    params = amp.cast_params(model.init(jax.random.PRNGKey(0)), policy)
    opt_state = mp_opt.init(params)

    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, args.seq), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=-1)

    if serial:
        @jax.jit
        def step(params, opt_state, toks, tgts):
            def scaled(p):
                return mp_opt.scale_loss(model.loss(p, toks, tgts), opt_state)

            ls, gs = jax.value_and_grad(scaled)(params)
            new_p, new_s, _ = mp_opt.apply_gradients(opt_state, params, gs)
            return new_p, new_s, ls / opt_state.scaler.loss_scale
    else:
        mesh = mesh_lib.make_virtual_mesh(
            n, context_parallel_size=args.cp)
        specs = model.specs()
        params = tp_mod.shard_params(params, specs, mesh)
        opt_state = mp_opt.init(params)
        data_spec = P(mesh_lib.AXIS_DATA, mesh_lib.AXIS_CONTEXT)
        tokens = jax.device_put(tokens, NamedSharding(mesh, data_spec))
        targets = jax.device_put(targets, NamedSharding(mesh, data_spec))
        grad_axes = mesh_lib.get_gradient_reduction_axes()

        def sharded(p, toks, tgts, scale):
            # local-mean loss + spec-aware gradient reduction (the repo's
            # standard data/context recipe — CLAUDE.md conventions)
            def scaled(p):
                return model.loss(p, toks, tgts) * scale

            ls, gs = jax.value_and_grad(scaled)(p)
            gs = allreduce_gradients_by_spec(gs, specs)
            from apex_tpu.parallel import collectives

            return collectives.pmean(ls, grad_axes), gs

        shard_fn = jax.shard_map(
            sharded, mesh=mesh,
            in_specs=(specs, data_spec, data_spec, P()),
            out_specs=(P(), specs), check_vma=False)

        @jax.jit
        def step(params, opt_state, toks, tgts):
            ls, gs = shard_fn(params, toks, tgts,
                              opt_state.scaler.loss_scale)
            new_p, new_s, _ = mp_opt.apply_gradients(opt_state, params, gs)
            return new_p, new_s, ls / opt_state.scaler.loss_scale

    loss = None
    t0 = time.perf_counter()
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        loss_val = float(loss)  # device->host fetch: waits for the step
        if i == 0:
            t0 = time.perf_counter()  # exclude compile
        print(f"step {i}: loss {loss_val:.4f}", file=sys.stderr)
    steps_timed = max(args.steps - 1, 1)
    dt = (time.perf_counter() - t0) / steps_timed
    mode = "serial" if serial else args.sp_impl
    tok_s = batch * args.seq / dt
    print(f"{tok_s:.0f} tokens/s at context {args.seq} "
          f"(cp={args.cp}, dp={args.dp}, {mode})")
    if args.output:
        import json

        os.makedirs(os.path.dirname(args.output) or ".", exist_ok=True)
        with open(args.output, "w") as f:
            json.dump({
                "metric": "longcontext_train_tokens_per_sec",
                "platform": jax.default_backend(),
                "seq": args.seq, "cp": args.cp, "dp": args.dp,
                "mode": mode, "batch": batch,
                "hidden": args.hidden, "layers": args.layers,
                "lm_head_chunks": args.lm_head_chunks,
                "window": args.window,
                "position_embedding": args.pos,
                "steps_timed": steps_timed,
                "tokens_per_sec": round(tok_s, 1),
                "loss_final": round(float(loss), 4),
            }, f, indent=1)
    if not serial:
        mesh_lib.destroy_model_parallel()


if __name__ == "__main__":
    main()

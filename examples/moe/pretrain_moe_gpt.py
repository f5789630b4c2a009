"""MoE GPT pretraining with expert parallelism over the data axis.

New-capability recipe (the reference has no MoE): GPT whose FFNs are top-k
routed expert layers (transformer/moe.py), experts sharded over the mesh's
``data`` axis with all_to_all dispatch, amp O2 mixed precision, FusedAdam,
and the Switch load-balancing + router z losses folded into training.

Run on 4 virtual devices (tokens and experts both sharded over ``data``):
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python examples/moe/pretrain_moe_gpt.py --experts 8 --steps 10
Run serial on one real TPU chip (experts local, no all_to_all):
    python examples/moe/pretrain_moe_gpt.py --experts 8 --ep 1 --steps 10
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
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu import amp
from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.optimizers import FusedAdam
from apex_tpu.parallel import collectives, mesh as mesh_lib
from apex_tpu.parallel.distributed import allreduce_gradients_by_spec
from apex_tpu.utils.compile_cache import enable_compile_cache


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--experts", type=int, default=8)
    p.add_argument("--top-k", type=int, default=2)
    p.add_argument("--capacity-factor", type=float, default=1.25)
    p.add_argument("--ep", type=int, default=0,
                   help="expert-parallel size (0 = all devices; 1 = serial)")
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--vocab", type=int, default=2048)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--lr", type=float, default=3e-4)
    args = p.parse_args()
    enable_compile_cache()

    ep = args.ep or len(jax.devices())
    serial = ep == 1
    cfg = GPTConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_layers=args.layers, num_attention_heads=args.heads,
        max_seq_len=args.seq, hidden_dropout=0.0, axis=None,
        compute_dtype=jnp.bfloat16, remat=True,
        moe_num_experts=args.experts, moe_top_k=args.top_k,
        moe_capacity_factor=args.capacity_factor,
        moe_expert_axis=None if serial else mesh_lib.AXIS_DATA,
    )
    model = GPTModel(cfg)
    policy = amp.get_policy("O2")
    mp_opt = amp.MixedPrecisionOptimizer(FusedAdam(lr=args.lr), policy)
    params = amp.cast_params(model.init(jax.random.PRNGKey(0)), policy)
    opt_state = mp_opt.init(params)

    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, args.vocab, (args.batch, args.seq)))
    tgts = jnp.roll(toks, -1, axis=-1)

    if serial:
        @jax.jit
        def train_step(params, opt_state, toks, tgts):
            ls, gs = jax.value_and_grad(
                lambda q: mp_opt.scale_loss(model.loss(q, toks, tgts),
                                            opt_state))(params)
            params, opt_state, _ = mp_opt.apply_gradients(opt_state, params, gs)
            return params, opt_state, ls / opt_state.scaler.loss_scale
    else:
        mesh = mesh_lib.make_virtual_mesh(ep)  # experts over the data axis
        specs = model.specs()
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                 is_leaf=lambda v: isinstance(v, P))
        params = jax.device_put(params, shardings)
        # optimizer state (masters, moments) mirrors the param layout —
        # replicating it would gather/scatter every expert weight each step
        from apex_tpu.amp.frontend import MPOptState

        param_sh = shardings
        opt_state = jax.device_put(
            opt_state,
            MPOptState(
                inner=type(opt_state.inner)(
                    NamedSharding(mesh, P()), param_sh, param_sh),
                master=param_sh,
                scaler=jax.tree.map(lambda _: NamedSharding(mesh, P()),
                                    opt_state.scaler),
            ))
        data_spec = P(mesh_lib.AXIS_DATA)

        def sharded_grads(p, toks, tgts, scale):
            # local-mean loss + spec-aware reduction: replicated grads
            # pmean over data; expert-sharded grads skip the psum but keep
            # the averaging factor (the MoE gradient convention,
            # transformer/moe.py apply_expert_parallel docstring)
            loss, g = jax.value_and_grad(
                lambda q: model.loss(q, toks, tgts) * scale)(p)
            g = allreduce_gradients_by_spec(g, specs)
            return collectives.pmean(loss, (mesh_lib.AXIS_DATA,)), g

        shard_fn = jax.shard_map(
            sharded_grads, mesh=mesh,
            in_specs=(specs, data_spec, data_spec, P()),
            out_specs=(P(), specs), check_vma=False)

        @jax.jit
        def train_step(params, opt_state, toks, tgts):
            sl, sg = shard_fn(params, toks, tgts,
                              opt_state.scaler.loss_scale)
            params, opt_state, _ = mp_opt.apply_gradients(opt_state, params, sg)
            return params, opt_state, sl / opt_state.scaler.loss_scale

    t0 = time.perf_counter()
    for step in range(args.steps):
        params, opt_state, loss = train_step(params, opt_state, toks, tgts)
        if step % max(1, args.steps // 5) == 0 or step == args.steps - 1:
            print(f"step {step:3d} loss {float(loss):.4f} "
                  f"scale {float(opt_state.scaler.loss_scale):.0f}")
    print(f"{args.steps} steps in {time.perf_counter() - t0:.1f}s "
          f"({'serial' if serial else f'expert-parallel x{ep}'}, "
          f"{args.experts} experts, top-{args.top_k})")
    if not serial:
        mesh_lib.destroy_model_parallel()


if __name__ == "__main__":
    main()

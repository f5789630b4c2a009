"""GPT pretraining with hybrid TP x PP x DP over a device mesh.

The flagship recipe (reference: apex/transformer/testing/standalone_gpt.py
driven by run_gpt_minimal_test.py / gpt_scaling_test.py): Megatron-style GPT
with tensor parallelism over the ``model`` axis, SPMD pipeline over ``pipe``,
data parallelism over ``data``, O2 mixed precision with fused Adam and
dynamic loss scaling, streaming token batches (native TokenLoader or
synthetic), and periodic checkpointing.

Run on 8 virtual devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/gpt/pretrain_gpt.py --tp 2 --pp 2 --steps 10
Run serial on one real TPU chip:
    python examples/gpt/pretrain_gpt.py --tp 1 --pp 1 --steps 10

The ``train_step`` that ``main`` returns consumes the state it is given
(``params`` and ``opt_state`` are donated): a caller steps it as
``params, opt_state, loss, metrics = train_step(params, opt_state, ...)``
and never reads the trees it handed in again.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu import amp, checkpoint
from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.monitor.tracing import maybe_span
from apex_tpu.optimizers import FusedAdam
from apex_tpu.parallel import collectives, mesh as mesh_lib
from apex_tpu.parallel.distributed import (
    allreduce_gradients,
    allreduce_gradients_by_spec,
)
from apex_tpu.parallel.multiproc import initialize_distributed
from apex_tpu.transformer import tensor_parallel as tp_mod
from apex_tpu.transformer.pipeline_parallel import pipeline_specs, pipelined_loss_fn
from apex_tpu.utils.compile_cache import enable_compile_cache


def _apply_plan(args):
    """Run the static placement search (``apex_tpu.plan``, ISSUE 18) over
    this run's model shape on the ambient device count and write the
    winner's placement back onto ``args`` — the same knobs a human would
    have passed. Prints ONE strict-JSON plan line; the winner's predicted
    anatomy rides on ``args.plan_predicted`` so the ledger's predicted
    block carries the planner's numbers (hbm/bubble/comm/step-seconds)
    for the calibrate join."""
    from apex_tpu import plan as plan_mod

    spec = plan_mod.ModelSpec(
        "pretrain_gpt", args.vocab, args.hidden, args.layers, args.heads,
        args.seq, moe_experts=args.moe_experts or 0,
        moe_top_k=args.moe_top_k)
    result = plan_mod.search(
        spec, mesh=len(jax.devices()), hbm_gb=args.plan_hbm_gb,
        micro_batch=args.micro_batch,
        num_microbatches=args.num_microbatches,
        # this harness exposes no sequence-parallel or attention-window
        # knobs — search only what it can express
        constraints={"sp": False, "attention_window": None})
    winner = result["winner"]
    if winner is None:
        by = {}
        for r in result["rejected"]:
            by[r["rejected_by"]] = by.get(r["rejected_by"], 0) + 1
        raise SystemExit(
            f"--plan auto: no feasible placement for this shape under "
            f"{args.plan_hbm_gb} GiB/rank (rejected: {by}); raise "
            f"--plan-hbm-gb or add devices")
    c = winner["candidate"]
    args.tp, args.pp = c["tp"], c["pp"]
    if c["schedule"]:
        args.pp_schedule = c["schedule"]
        if c["schedule"] == "interleaved":
            args.vpp = c["vpp"]
    args.unroll = bool(c["unroll"])
    args.zero = c["zero_level"] > 0
    args.zero_level = c["zero_level"] or None
    args.zero3_prefetch = c["zero3_prefetch"]
    args.zero_gather = c["gather_dtype"]
    args.reduce_dtype = c["reduce_dtype"]
    if c["moe_expert_axis"]:
        args.moe_dispatch_dtype = c["moe_dispatch_dtype"]
    args.plan_predicted = winner["predicted"]
    print(json.dumps({"plan": {
        "winner": c,
        "predicted": {
            "hbm_bytes": winner["predicted"]["hbm_bytes"],
            "comm_bytes_by_tier":
                winner["predicted"]["comm_bytes_by_tier"],
            "bubble_floor": winner["predicted"]["bubble_floor"],
            "step_seconds": winner["predicted"]["step_seconds"],
        },
        "mesh": result["mesh"],
        "hbm_budget_bytes": result["hbm_budget_bytes"],
        "n_ranked": len(result["ranked"]),
        "n_rejected": len(result["rejected"]),
        "peak_source": result["peak_spec"]["source"],
        "ici_source": result["ici_spec"]["source"]}}))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--vocab", type=int, default=50304)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--micro-batch", type=int, default=2)
    p.add_argument("--num-microbatches", type=int, default=2)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--pp-schedule", default="1f1b",
                   choices=["gpipe", "1f1b", "interleaved", "zerobubble"],
                   help="pipeline schedule (schedule-as-data planners, "
                        "transformer/pipeline_parallel/schedules.py). "
                        "gpipe|1f1b share the compiled SPMD ring (the "
                        "AD-transposed drain IS 1F1B's cooldown); "
                        "interleaved adds vpp virtual chunks per stage "
                        "(--vpp); zerobubble drives the explicit W/B-split "
                        "executor (schedule_grads_fn: bwd_weight slots of "
                        "early microbatches fill the cooldown — needs "
                        "pp>1, tp=1, zero level < 3)")
    p.add_argument("--vpp", type=int, default=None,
                   help="virtual pipeline chunks per stage for "
                        "--pp-schedule interleaved (default 2 there, "
                        "1 otherwise); layers are interleave_stack-"
                        "permuted, checkpoints store that order")
    p.add_argument("--zero3-prefetch", type=int, default=0, metavar="N",
                   help="double-buffer the ZeRO-3 per-layer chunk "
                        "all-gathers N layers ahead (forward and backward "
                        "re-gathers; needs --zero-level 3 and --unroll — "
                        "models/_transformer._prefetched_zero3_drive)")
    p.add_argument("--unroll", action="store_true",
                   help="drive the layer stack with static slices instead "
                        "of lax.scan (kills the scan backward's grad "
                        "stacking, PERF_NOTES r5; compile time O(depth))")
    p.add_argument("--zero", action="store_true",
                   help="ZeRO: shard fp32 masters + Adam moments over the "
                        "data axis (optimizer memory / dp; the grad "
                        "all-reduce becomes psum_scatter + all_gather)")
    p.add_argument("--zero-level", type=int, default=None, choices=(1, 2, 3),
                   help="ZeRO stage (implies --zero). 1/2: masters+moments "
                        "shard 1/dp, bf16 params replicated. 3: the bf16 "
                        "params shard too — each layer's weights are "
                        "all-gathered just-in-time inside the layer loop "
                        "and grads reduce-scatter per layer (no bulk "
                        "post-update gather)")
    p.add_argument("--zero-gather", default=None, choices=["bf16", "int8"],
                   help="compress the ZeRO param all-gather payload "
                        "(bf16 halves gather bytes; int8 quantizes to "
                        "1 B/elem at a per-chunk fp32 scale — "
                        "parallel/quantize.py; fp32 masters stay exact)")
    p.add_argument("--reduce-dtype", default=None, choices=["int8", "e5m2"],
                   help="quantize the ZeRO grad reduce-scatter wire "
                        "(requires --zero, levels 1/2): the fp32 "
                        "psum_scatter becomes the encoded all_to_all pair "
                        "at 1 B/elem + per-chunk fp32 scales, with an "
                        "error-feedback residual in the sharded optimizer "
                        "state (parallel/quantize.py)")
    p.add_argument("--moe-experts", type=int, default=None, metavar="E",
                   help="route every layer's FFN through a top-k MoE with "
                        "E experts (transformer/moe.py); with dp > 1 the "
                        "experts shard over the data axis and tokens "
                        "dispatch with all_to_all (expert parallelism — "
                        "EP x TP when --tp > 1); aux router losses fold "
                        "into the loss via aux_to_loss")
    p.add_argument("--moe-top-k", type=int, default=2,
                   help="experts per token (1 = Switch, 2 = GShard)")
    p.add_argument("--moe-capacity-factor", type=float, default=1.25,
                   help="capacity slack over the balanced share; tokens "
                        "over an expert's cap are dropped (the "
                        "dropped_fraction aux metric reports the rate)")
    p.add_argument("--moe-dispatch-dtype", default=None,
                   choices=["int8", "e5m2"],
                   help="quantize the expert-parallel dispatch/combine "
                        "all_to_all wire to 1 B/elem + fp32 per-block "
                        "scales (parallel/quantize.quantized_all_to_all; "
                        "needs --moe-experts and dp > 1)")
    p.add_argument("--plan", default=None, metavar="auto",
                   help="'auto': run the static placement search "
                        "(apex_tpu.plan) over THIS model shape on the "
                        "ambient device count and adopt the winner's "
                        "placement (tp/pp/schedule/zero/prefetch/wire/"
                        "unroll knobs overridden; one JSON plan line is "
                        "printed; the winner's predicted anatomy seeds "
                        "the ledger's predicted block)")
    p.add_argument("--plan-hbm-gb", type=float, default=16.0,
                   help="per-rank HBM budget the --plan search prices "
                        "candidates against (GiB)")
    p.add_argument("--data", default=None, help="dir of .bin int32 token files")
    p.add_argument("--save-dir", default=None)
    p.add_argument("--save-every", type=int, default=100)
    p.add_argument("--journal", default=None, metavar="PATH",
                   help="write a per-step JSON-lines metrics journal "
                        "(apex_tpu.monitor: wall time, tokens/s, loss, "
                        "grad-norm, loss-scale state, HBM samples); adds "
                        "one loss fetch per step")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="write a span trace (apex_tpu.monitor.tracing): "
                        "per-step spans, ZeRO grads/apply phase spans "
                        "(two-program step build), a traced pipeline "
                        "tick drive measuring per-rank bubble fraction "
                        "(pp>1, tp=1), and a Chrome trace-event export "
                        "next to PATH (chrome://tracing / Perfetto)")
    p.add_argument("--ledger", nargs="?", const="out/ledger.jsonl",
                   default=None, metavar="PATH",
                   help="append one fingerprinted run record (config + "
                        "environment stamp + measured rollup + predicted "
                        "block) to the run ledger "
                        "(apex_tpu.monitor.ledger; analyze with `python "
                        "-m apex_tpu.monitor.ledger "
                        "{list,trend,regress,calibrate}`); "
                        "APEX_TPU_LEDGER=<path> arms it too")
    p.add_argument("--flight", nargs="?", const="auto", default=None,
                   metavar="PATH",
                   help="arm the flight recorder (apex_tpu.monitor."
                        "flight): a bounded in-memory ring of recent "
                        "journal/span records + breadcrumbs dumped as "
                        "strict JSON on unhandled exception, SIGTERM, or "
                        "watchdog kill — with an HBM snapshot and the "
                        "last loss-scale state. Default PATH: "
                        "<journal>.flight.json")
    args = p.parse_args(argv)
    if args.plan:
        if args.plan != "auto":
            p.error("--plan accepts 'auto' (the static placement search)")
        _apply_plan(args)
    if not args.ledger and os.environ.get("APEX_TPU_LEDGER"):
        args.ledger = os.environ["APEX_TPU_LEDGER"]
    if args.flight == "auto":
        args.flight = ((args.journal + ".flight.json") if args.journal
                       else "out/pretrain_gpt.flight.json")
    if args.zero_level is not None:
        args.zero = True
    elif args.zero:
        args.zero_level = 2
    if args.zero_gather and not args.zero:
        p.error("--zero-gather requires --zero")
    if args.reduce_dtype and not args.zero:
        p.error("--reduce-dtype requires --zero (it is the ZeRO grad "
                "reduce-scatter wire dtype)")
    if args.vpp is None:
        args.vpp = 2 if args.pp_schedule == "interleaved" else 1
    if args.vpp > 1 and args.pp_schedule != "interleaved":
        p.error("--vpp > 1 is the interleaved schedule's knob")
    if args.pp_schedule == "interleaved" and args.vpp < 2:
        p.error("--pp-schedule interleaved needs --vpp >= 2")
    if args.pp_schedule == "zerobubble":
        if args.pp < 2 or args.tp > 1:
            p.error("--pp-schedule zerobubble needs --pp >= 2 and --tp 1 "
                    "(the explicit-backward executor drives the pipe axis "
                    "only)")
        if (args.zero_level or 0) >= 3:
            p.error("--pp-schedule zerobubble composes with ZeRO levels "
                    "1/2 only (level 3 rebuilds the pipelined loss)")
    if args.zero3_prefetch:
        if (args.zero_level or 0) < 3:
            p.error("--zero3-prefetch requires --zero-level 3 (it "
                    "double-buffers the per-layer chunk gathers)")
        if not args.unroll:
            p.error("--zero3-prefetch requires --unroll (the prefetch "
                    "schedule is a static unrolled structure)")
    if args.moe_dispatch_dtype and not args.moe_experts:
        p.error("--moe-dispatch-dtype requires --moe-experts (it is the "
                "expert-parallel dispatch wire dtype)")
    if args.moe_experts:
        if (args.zero_level or 0) >= 3:
            p.error("--moe-experts composes with ZeRO levels 1/2 only "
                    "(level 3's chunk drive has no expert-shard story)")
        if args.pp_schedule == "zerobubble":
            p.error("--moe-experts does not compose with --pp-schedule "
                    "zerobubble (the W/B-split executor has no aux-loss "
                    "plumbing)")
    return args


def main(argv=None):
    """Train; returns the run's record — per-step ``losses``,
    ``loss_scales`` and ``found_inf`` flags, ``first_step_seconds`` (entry
    to the first loss on the host: set-up, compile and one step),
    ``seconds_per_step`` after it, and the live ``train_step`` /
    ``params`` / ``opt_state`` / ``next_batch`` for a caller that keeps
    driving the step (``chip_smoke.py`` reads its compiled text and times
    it)."""
    t_entry = time.perf_counter()
    args = parse_args(argv)
    enable_compile_cache()
    initialize_distributed()  # no-op single-process
    n_dev = len(jax.devices())
    mesh = mesh_lib.make_virtual_mesh(
        n_dev,
        tensor_model_parallel_size=args.tp,
        pipeline_model_parallel_size=args.pp,
    )
    dp = mesh_lib.get_data_parallel_world_size()
    assert args.layers % max(args.pp * args.vpp, 1) == 0

    moe_kwargs = {}
    if args.moe_experts:
        # experts shard over the data axis (the standard MoE mapping:
        # token shards ARE the expert shards) when dp > 1; serial experts
        # otherwise (one code path — the serial twin of the same config)
        moe_kwargs = dict(
            moe_num_experts=args.moe_experts,
            moe_top_k=args.moe_top_k,
            moe_capacity_factor=args.moe_capacity_factor,
            moe_expert_axis=mesh_lib.AXIS_DATA if dp > 1 else None,
            moe_dispatch_dtype=args.moe_dispatch_dtype,
        )
    cfg = GPTConfig(
        vocab_size=args.vocab,
        hidden_size=args.hidden,
        num_layers=args.layers,
        num_attention_heads=args.heads,
        max_seq_len=args.seq,
        hidden_dropout=0.0,
        axis=mesh_lib.AXIS_MODEL if args.tp > 1 else None,
        compute_dtype=jnp.bfloat16 if args.opt_level in ("O1", "O2", "O3") else jnp.float32,
        remat=True,
        unroll_layers=args.unroll,
        zero3_prefetch=args.zero3_prefetch,
        **moe_kwargs,
    )
    model = GPTModel(cfg)
    policy = amp.get_policy(args.opt_level)
    # journaled runs also want the global grad-norm AND the per-group
    # breakdown (overflow forensics, monitor/diagnose.py) in the metrics;
    # un-journaled programs stay byte-identical (both flags default off)
    mp_opt = amp.MixedPrecisionOptimizer(
        FusedAdam(lr=args.lr), policy,
        log_grad_norm=bool(args.journal),
        log_group_norms=bool(args.journal),
        zero_axis=mesh_lib.AXIS_DATA if args.zero else None,
        zero_level=args.zero_level or 2,
        gather_dtype=args.zero_gather,
        reduce_dtype=args.reduce_dtype)

    full = amp.cast_params(model.init(jax.random.PRNGKey(0)), policy)
    all_specs = model.specs()
    specs = dict(
        {k: v for k, v in all_specs.items() if k != "layers"},
        layers=pipeline_specs(all_specs["layers"]),
    )
    if args.vpp > 1:
        # interleaved chunk placement: stage s chunk c holds serial slab
        # c*pp + s; training/checkpointing in this order is
        # self-consistent (schedules.interleave_stack)
        from apex_tpu.transformer.pipeline_parallel.schedules import (
            interleave_stack,
        )

        full = dict(full, layers=interleave_stack(
            full["layers"], args.pp, args.vpp))
    params = tp_mod.shard_params(full, specs, mesh)

    tracer = None
    if args.trace:
        from apex_tpu.monitor import tracing

        tracer = tracing.arm(
            args.trace,
            meta={"run": "pretrain_gpt", "tp": args.tp, "pp": args.pp,
                  "zero_level": args.zero_level or 0})
    if args.flight:
        # black box (monitor/flight.py): journal/span records and
        # breadcrumbs ring in memory; a crash/SIGTERM/watchdog kill dumps
        # them with an HBM snapshot — disarmed runs are byte-identical
        from apex_tpu.monitor import flight as flight_mod

        flight_mod.arm(args.flight,
                       meta={"run": "pretrain_gpt", "tp": args.tp,
                             "pp": args.pp, "dp": dp,
                             "zero_level": args.zero_level or 0})

    batch = args.micro_batch * dp * args.num_microbatches
    data_spec = P(mesh_lib.AXIS_DATA)
    rest_specs = {k: v for k, v in all_specs.items() if k != "layers"}
    grad_axes = mesh_lib.get_gradient_reduction_axes()
    # MoE layers emit router aux losses: thread them through the ring and
    # fold with aux_to_loss (run_layers refuses to drop them silently)
    with_aux = bool(args.moe_experts)
    pipe_loss = pipelined_loss_fn(
        embed=model.embed,
        run_layers=(lambda lp, h: model.run_layers(lp, h, return_aux=True))
        if with_aux else (lambda lp, h: model.run_layers(lp, h)),
        head_loss=lambda p, h, t: model.head(p, h, t),
        num_microbatches=args.num_microbatches,
        virtual_pipeline_size=args.vpp,
        aux_to_loss=model.aux_to_loss if with_aux else None,
    )
    zb_vg = None
    if args.pp_schedule == "zerobubble":
        # schedule-as-data: the zero-bubble plan (W/B-split backward
        # slots) interpreted by the compiled executor, a drop-in for
        # value_and_grad of the pipelined loss
        from apex_tpu.transformer.pipeline_parallel import (
            plan_schedule,
            zero_bubble_grads_fn,
        )

        zb_plan = plan_schedule("zero-bubble", args.num_microbatches,
                                args.pp)
        zb_vg = zero_bubble_grads_fn(model, args.num_microbatches, args.pp)
        from apex_tpu.monitor.tracing import expected_bubble_fraction

        print(f"pp-schedule zerobubble: {zb_plan.ticks} ticks, "
              f"{zb_plan.idle_slots()[0]} idle/rank (analytic bubble "
              f"{expected_bubble_fraction('zero-bubble', args.num_microbatches, args.pp):.4f} "
              f"vs 1f1b "
              f"{expected_bubble_fraction('1f1b', args.num_microbatches, args.pp):.4f})")

    def sharded_grads(p, toks, tgts, scale):
        rest = {k: v for k, v in p.items() if k != "layers"}
        if zb_vg is not None:
            loss, rest_g, layer_g = zb_vg(rest, p["layers"], toks, tgts,
                                          scale)
        else:
            def scaled_loss(rest, layers):
                return pipe_loss(rest, layers, toks, tgts) * scale

            loss, (rest_g, layer_g) = jax.value_and_grad(
                scaled_loss, argnums=(0, 1))(rest, p["layers"])
        rest_g = allreduce_gradients_by_spec(rest_g, rest_specs)
        layer_g = allreduce_gradients(layer_g, grad_axes)
        return collectives.pmean(loss, grad_axes), dict(rest_g, layers=layer_g)

    if args.zero:
        # ZeRO: the whole step — backward, spec-aware reduction over every
        # NON-data axis, and the sharded optimizer (psum_scatter → chunked
        # Adam → compressed all_gather) — runs inside ONE shard_map; the
        # shared builder drops the data axis from the harness reduction
        # (the scatter IS it) and OR-reduces the overflow flag over the
        # model/pipe axes like the reference's model-parallel GradScaler.
        from apex_tpu.transformer.amp import build_zero_train_step

        if args.zero_level >= 3:
            # ZeRO-3: the bf16 params persist as 1/dp chunk trees and
            # each layer's weights gather just-in-time inside the layer
            # loop (models/_transformer.run_layers chunk_meta); grads
            # reduce-scatter per layer via the gather transposes, and
            # the updated chunks ARE the state — no post-update gather
            # (tripwire: lint.trace.zero3_gather_hazards)
            z3 = mp_opt.zero3_init(params, mesh, specs)
            params = z3.params
            opt_state = z3.opt_state
            train_step = build_zero_train_step(
                mp_opt, mesh, None, None, None,
                rest_specs=rest_specs, layer_specs=specs["layers"],
                grad_axes=grad_axes,
                data_spec=data_spec, zero_axis=mesh_lib.AXIS_DATA,
                zero3=z3, model=model,
                num_microbatches=args.num_microbatches,
                # the layer stack is interleave_stack-permuted when
                # vpp > 1: the rebuilt pipelined loss must drive it with
                # the same chunk placement
                virtual_pipeline_size=args.vpp,
                traced=bool(args.trace), tracer=tracer)
        else:
            opt_state, state_specs = mp_opt.zero_init(params, mesh, specs)
            train_step = build_zero_train_step(
                mp_opt, mesh, specs, state_specs, pipe_loss,
                rest_specs=rest_specs, grad_axes=grad_axes,
                data_spec=data_spec, zero_axis=mesh_lib.AXIS_DATA,
                traced=bool(args.trace), tracer=tracer,
                pipe_value_and_grad=zb_vg)
    else:
        opt_state = mp_opt.init(params)
        # the masters of the leaves O2 keeps in float32 (the norms) are the
        # params' own arrays, and a donated step cannot be handed one
        # buffer twice: those masters get buffers of their own
        shared = {id(a) for a in jax.tree.leaves(params)}
        opt_state = jax.tree.map(
            lambda a: jnp.copy(a) if id(a) in shared else a, opt_state)
        shard_fn = jax.shard_map(
            sharded_grads, mesh=mesh,
            in_specs=(specs, data_spec, data_spec, P()),
            out_specs=(P(), specs), check_vma=False,
        )

        @jax.jit
        def train_step(params, opt_state, tokens, targets):
            scaled_loss, scaled_grads = shard_fn(
                params, tokens, targets, opt_state.scaler.loss_scale)
            new_params, new_state, metrics = mp_opt.apply_gradients(
                opt_state, params, scaled_grads)
            return new_params, new_state, scaled_loss / opt_state.scaler.loss_scale, metrics

    if hasattr(train_step, "lower"):
        # A jitted step returns its state under shardings spelled XLA's
        # way (size-1 axes dropped, scalars committed to the mesh), not
        # the way the state was built, so a step fed its own output would
        # compile a second time — inside the timed steps. Commit the
        # state to the mesh and pin the step's outputs to its inputs'
        # shardings: the placement is the same, and so is the cache key.
        # Every leaf of the state then has a successor of its own shape,
        # type and placement, so the state is donated whole and updated
        # in place. Held twice, it cost a copy of the float32 masters and
        # moments at the top of every step, and at GPT-2 345M on one chip
        # pushed the step over the compiler's memory budget (PERF.md,
        # Findings, PR 31). (The two-program traced drive is host code
        # with no `.lower`: it stays as it is, nothing donated.)
        replicated = NamedSharding(mesh, P())
        params, opt_state = jax.tree.map(
            lambda a: a if isinstance(a.sharding, NamedSharding)
            else jax.device_put(a, replicated), (params, opt_state))
        state_shardings = jax.tree.map(lambda a: a.sharding,
                                       (params, opt_state))
        train_step = jax.jit(train_step,
                             out_shardings=(*state_shardings, None, None),
                             donate_argnums=(0, 1))

    if args.data:
        from apex_tpu.csrc import TokenLoader
        files = sorted(
            os.path.join(args.data, f) for f in os.listdir(args.data)
            if f.endswith(".bin"))
        batches = iter(TokenLoader(files, (batch, args.seq + 1), loop=True))

        def next_batch():
            arr = jnp.asarray(next(batches) % args.vocab)
            return arr[:, :-1], arr[:, 1:]
    else:
        rng = np.random.default_rng(0)

        def next_batch():
            toks = jnp.asarray(rng.integers(0, args.vocab, (batch, args.seq)))
            return toks, jnp.roll(toks, -1, axis=-1)

    shard = lambda a: jax.device_put(a, NamedSharding(mesh, data_spec))
    start = 0
    if args.save_dir and (step := checkpoint.latest_step(args.save_dir)) is not None:
        restored = checkpoint.restore_checkpoint(
            args.save_dir, {"params": params, "opt": opt_state})
        params, opt_state = restored["params"], restored["opt"]
        start = step
        print(f"resumed from step {step}")

    # one config dict, two consumers: the journal's kind="meta" header
    # and the ledger record's fingerprinted config block (same knobs →
    # same fingerprint, so journal and ledger join trivially)
    run_config = {"run": "pretrain_gpt", "tp": args.tp, "pp": args.pp,
                  "dp": dp, "hidden": args.hidden, "layers": args.layers,
                  "seq": args.seq, "batch": batch,
                  "schedule": args.pp_schedule, "vpp": args.vpp,
                  "unroll": bool(args.unroll), "zero": bool(args.zero),
                  "zero_level": args.zero_level or 0,
                  "zero3_prefetch": args.zero3_prefetch or 0,
                  "reduce_dtype": args.reduce_dtype or "fp32",
                  "moe_experts": args.moe_experts or 0,
                  "moe_dispatch_dtype": args.moe_dispatch_dtype or "none"}
    ledger_pred = {}  # predicted block, filled at arm time (off-TPU math)
    if getattr(args, "plan_predicted", None):
        # the planner's predicted anatomy seeds the ledger keys the
        # calibrate join reads; traced statics (journal arming below)
        # overwrite the comm figure with the booked census when available
        pred = args.plan_predicted
        ledger_pred.setdefault("hbm_peak_bytes", pred["hbm_bytes"])
        ledger_pred.setdefault("bubble_floor", pred["bubble_floor"])
        ledger_pred.setdefault("comm_bytes_per_step",
                               pred["comm_bytes_by_tier"]["ici"])
        ledger_pred.setdefault("modeled_step_s", pred["step_seconds"])
    journal = forensics = None
    if args.journal:
        from apex_tpu.monitor import (
            MetricsJournal,
            OverflowForensics,
            RecompileTracker,
        )
        from apex_tpu.monitor import mfu as mfu_lib

        from apex_tpu.monitor.health import HealthMonitor

        journal = MetricsJournal(
            args.journal, sample_hbm_every=10,
            meta=run_config,
            # online health rules (monitor/health.py): every record
            # streams through the detectors; kind="alert" rows land in
            # this same journal for report's alerts section and the
            # `report compare --max-alerts` gate
            health=HealthMonitor())
        try:
            # per-rank residency footprints (monitor/hbm.py): the ZeRO
            # bytes/rank ÷ dp claim — and under --zero-level 3 the
            # param bytes/rank ÷ dp claim — as journaled numbers, rolled
            # up by `python -m apex_tpu.monitor.report`
            from apex_tpu.monitor.hbm import opt_state_bytes, param_bytes

            journal.set_opt_state_bytes(opt_state_bytes(opt_state))
            journal.set_param_bytes(param_bytes(params))
        except Exception as e:  # noqa: BLE001 - telemetry must not kill a run
            print(f"residency-bytes arming failed: {e}")
        # diagnostics engine (monitor/diagnose.py): overflow/loss-spike
        # forensics keyed off the per-group grad norms above, plus the
        # shape-churn detector around the jitted step — both host-side
        forensics = OverflowForensics(journal)
        try:
            # one extra TRACE (no compile) arms per-step MFU/roofline
            # fields: jaxpr FLOPs/bytes per token joined against the
            # peak-spec table (env-calibratable, monitor/mfu.py). Traced
            # BEFORE the recompile wrapper so arming never journals as a
            # spurious compile, and on zeros so no real batch from
            # --data is consumed just for tracing (bench.py's
            # _register_window_costs idiom)
            from apex_tpu.monitor import comm_accounting

            z = shard(jnp.zeros((batch, args.seq), jnp.int32))
            # the same trace also books collective payload bytes, so the
            # journal's step-anatomy fields (compute/comm/stall fractions
            # + overlap, monitor/tracing.py step_anatomy) arm for free
            with comm_accounting() as acct:
                costs = mfu_lib.traced_step_costs(
                    train_step, params, opt_state, z, z)
            journal.set_step_costs(
                flops_per_token=costs["flops"] / (batch * args.seq),
                bytes_per_token=costs["bytes"] / (batch * args.seq),
                method=costs["method"])
            journal.set_step_comm(acct.total_bytes())
            # the same statics ARE the ledger's predicted block
            ledger_pred.update(flops_per_step=costs["flops"],
                               bytes_per_step=costs["bytes"],
                               comm_bytes_per_step=acct.total_bytes())
        except Exception as e:  # noqa: BLE001 - telemetry must not kill a run
            print(f"mfu arming failed (journal continues without): {e}")
        train_step = RecompileTracker(journal).wrap(train_step,
                                                    name="train_step")

    if (args.trace and args.pp > 1 and args.tp == 1
            and (args.zero_level or 0) < 3):
        # measure the pipeline's per-rank bubble fraction for real: one
        # tick-by-tick traced drive of the SELECTED schedule (the ring
        # drive for interleaved/vpp; the plan executor for the vpp=1
        # planners incl. zerobubble), spans into the trace file, the
        # measured-vs-analytic stamp into every journal record
        try:
            from apex_tpu.monitor import tracing as tracing_mod
            from apex_tpu.transformer.pipeline_parallel import (
                plan_schedule,
                traced_pipeline_timeline,
                traced_schedule_timeline,
            )

            probe_rows = args.micro_batch * args.num_microbatches
            ptoks = jnp.zeros((probe_rows, args.seq), jnp.int32)
            if args.pp_schedule == "interleaved":
                _, _, anatomy = traced_pipeline_timeline(
                    mesh, embed=model.embed,
                    run_layers=lambda lp, h: model.run_layers(lp, h),
                    head_loss=lambda p, h, t: model.head(p, h, t),
                    rest_params={k: v for k, v in params.items()
                                 if k != "layers"},
                    layers=params["layers"], layer_specs=specs["layers"],
                    batch=ptoks, targets=ptoks,
                    num_microbatches=args.num_microbatches,
                    virtual_pipeline_size=args.vpp,
                    tracer=tracer, step=-1)
            else:
                probe_plan = plan_schedule(
                    "zero-bubble" if args.pp_schedule == "zerobubble"
                    else args.pp_schedule,
                    args.num_microbatches, args.pp)
                _, _, anatomy = traced_schedule_timeline(
                    probe_plan, mesh, embed=model.embed,
                    run_layers=lambda lp, h: model.run_layers(lp, h),
                    head_loss=lambda p, h, t: model.head(p, h, t),
                    rest_params={k: v for k, v in params.items()
                                 if k != "layers"},
                    layers=params["layers"], layer_specs=specs["layers"],
                    batch=ptoks, targets=ptoks, tracer=tracer, step=-1)
            print(f"measured bubble fraction "
                  f"{anatomy['bubble_fraction']['mean']} "
                  f"(analytic floor {anatomy['expected_bubble_fraction']})")
            if journal is not None:
                journal.set_bubble_fraction(
                    anatomy["bubble_fraction"]["mean"],
                    anatomy["expected_bubble_fraction"])
            ledger_pred.setdefault(
                "bubble_floor", anatomy["expected_bubble_fraction"])
        except Exception as e:  # noqa: BLE001 - telemetry must not kill a run
            print(f"bubble probe failed (run continues without): {e}")

    # per-step device scalars, fetched after the loop so the record costs
    # the timed steps no host sync
    step_log = []
    first_step_seconds = None
    t0 = time.perf_counter()
    for i in range(start, start + args.steps):
        # the profiler's own step view, and the program's span beside it:
        # on the profiler's clock whether or not the file tracer is armed
        with jax.profiler.StepTraceAnnotation("train", step_num=i):
            toks, tgts = next_batch()
            if journal is not None:
                journal.step_start()
            if tracer is not None:
                tracer.step = i
            with maybe_span(tracer, "step", step=i) as sp:
                params, opt_state, loss, metrics = train_step(
                    params, opt_state, shard(toks), shard(tgts))
                sp.barrier(loss)  # disarmed: a no-op, so no sync
        step_log.append((loss, metrics["loss_scale"], metrics["found_inf"]))
        if journal is not None:
            # the journal's float(loss) IS the step's execution barrier;
            # metrics/scaler fetches ride after it
            journal.step_end(step=i, loss=loss, tokens=batch * args.seq,
                             metrics=metrics, scaler=opt_state.scaler)
            forensics.observe(step=i, loss=loss, metrics=metrics)
        if i == start:
            float(loss)  # exclude compile
            t0 = time.perf_counter()
            first_step_seconds = t0 - t_entry
        if i % 5 == 0 or i == start + args.steps - 1:
            print(f"step {i:5d} loss {float(loss):.4f} "
                  f"scale {float(metrics['loss_scale']):.0f}")
        if args.save_dir and (i + 1) % args.save_every == 0:
            checkpoint.save_checkpoint(
                args.save_dir, i + 1, {"params": params, "opt": opt_state})
    if journal is not None:
        journal.close()
    if tracer is not None:
        from apex_tpu.monitor import tracing as tracing_mod

        tracing_mod.disarm()  # flush + close
        try:
            tracing_mod.write_chrome_trace(
                args.trace, args.trace + ".chrome.json")
            print(f"chrome trace: {args.trace}.chrome.json")
        except Exception as e:  # noqa: BLE001
            print(f"chrome export failed: {e}")
    if args.flight:
        from apex_tpu.monitor import flight as flight_mod

        flight_mod.disarm()  # clean exit: restore hooks, no dump
    n_done = max(args.steps - 1, 1)
    dt = (time.perf_counter() - t0) / n_done
    print(f"{batch * args.seq / dt:.0f} tokens/s | mesh: tp={args.tp} pp={args.pp} "
          f"dp={dp} | {dt * 1e3:.1f} ms/step")
    if args.ledger:
        try:
            from apex_tpu.monitor import ledger as ledger_mod

            # journal-less runs still ledger: a minimal measured block
            # in the report-rollup key shapes regress/trend read
            measured = None
            if not args.journal:
                measured = {"step_records": args.steps,
                            "tokens_per_sec":
                                {"p50": round(batch * args.seq / dt, 1)},
                            "wall_s": {"p50": round(dt, 6)},
                            "loss": {"last": float(loss)}}
            rec = ledger_mod.append_run(
                args.ledger, run="pretrain_gpt", config=run_config,
                journal=args.journal, measured=measured,
                predicted=ledger_pred)
            print(f"ledger: {rec['fingerprint']} -> {args.ledger}")
        except Exception as e:  # noqa: BLE001 - telemetry must not kill a run
            print(f"ledger append failed: {e}")
    mesh_lib.destroy_model_parallel()
    return {
        "losses": [float(l) for l, _, _ in step_log],
        "loss_scales": [float(s) for _, s, _ in step_log],
        "found_inf": [bool(f) for _, _, f in step_log],
        "first_step_seconds": first_step_seconds,
        "seconds_per_step": dt,
        "tokens_per_step": batch * args.seq,
        "train_step": train_step,
        "params": params,
        "opt_state": opt_state,
        "next_batch": lambda: tuple(shard(a) for a in next_batch()),
    }


if __name__ == "__main__":
    main()

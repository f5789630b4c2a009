"""BERT pretraining with FusedLAMB + fused LayerNorm (BASELINE.md config 3).

Reference workload: BERT-large MLM+NSP pretraining with apex FusedLAMB and
FusedLayerNorm (the apex README's flagship BERT recipe). Synthetic masked
batches by default.

    JAX_PLATFORMS=cpu python examples/bert/pretrain_bert.py --steps 10
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
from apex_tpu.models import BertConfig, BertModel
from apex_tpu.monitor.tracing import maybe_span
from apex_tpu.optimizers import FusedLAMB
from apex_tpu.utils.compile_cache import enable_compile_cache


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=2e-3)
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--zero", action="store_true",
                   help="ZeRO: data-parallel over every device with fp32 "
                        "masters + LAMB moments sharded 1/dp "
                        "(LAMB trust-ratio norms psum across the shards); "
                        "batch must divide the device count")
    p.add_argument("--zero-level", type=int, default=None, choices=(1, 2, 3),
                   help="ZeRO stage (implies --zero). 3 shards the bf16 "
                        "params too: 1/dp chunk trees with per-layer "
                        "just-in-time weight gathers in the layer loop")
    p.add_argument("--reduce-dtype", default=None, choices=["int8", "e5m2"],
                   help="quantize the ZeRO grad reduce-scatter wire "
                        "(requires --zero, levels 1/2): encoded all_to_all "
                        "at 1 B/elem + per-chunk fp32 scales, with an "
                        "error-feedback residual in the sharded state "
                        "(parallel/quantize.py)")
    p.add_argument("--journal", default=None, metavar="PATH",
                   help="write a per-step JSON-lines metrics journal "
                        "(apex_tpu.monitor: wall time, tokens/s, loss, "
                        "loss-scale state, HBM samples, online health "
                        "alerts); adds one loss fetch per step")
    p.add_argument("--ledger", nargs="?", const="out/ledger.jsonl",
                   default=None, metavar="PATH",
                   help="append one fingerprinted run record (config + "
                        "environment stamp + measured rollup + predicted "
                        "block) to the run ledger "
                        "(apex_tpu.monitor.ledger); "
                        "APEX_TPU_LEDGER=<path> arms it too")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="write a span trace (apex_tpu.monitor.tracing): "
                        "one barriered span per step plus a Chrome "
                        "trace-event export next to PATH")
    p.add_argument("--flight", nargs="?", const="auto", default=None,
                   metavar="PATH",
                   help="arm the flight recorder (apex_tpu.monitor."
                        "flight): recent records + breadcrumbs dumped as "
                        "strict JSON on crash/SIGTERM/watchdog kill. "
                        "Default PATH: out/pretrain_bert.flight.json")
    args = p.parse_args()
    if not args.ledger and os.environ.get("APEX_TPU_LEDGER"):
        args.ledger = os.environ["APEX_TPU_LEDGER"]
    if args.flight == "auto":
        args.flight = "out/pretrain_bert.flight.json"
    if args.zero_level is not None:
        args.zero = True
    elif args.zero:
        args.zero_level = 2
    if args.reduce_dtype and not args.zero:
        p.error("--reduce-dtype requires --zero (it is the ZeRO grad "
                "reduce-scatter wire dtype)")
    return args


def synthetic_batch(rng, batch, seq, vocab):
    toks = rng.integers(0, vocab, (batch, seq))
    attn = np.ones((batch, seq), np.int32)
    lmask = (rng.random((batch, seq)) < 0.15).astype(np.int32)
    labels = rng.integers(0, vocab, (batch, seq))
    nsp = rng.integers(0, 2, (batch,))
    types = np.zeros((batch, seq), np.int32)
    return tuple(jnp.asarray(a) for a in (toks, attn, lmask, labels, nsp, types))


def main():
    args = parse_args()
    enable_compile_cache()
    cfg = BertConfig(
        hidden_size=args.hidden, num_layers=args.layers,
        num_attention_heads=args.heads, max_seq_len=args.seq,
        hidden_dropout=0.0, axis=None,
        compute_dtype=jnp.bfloat16 if args.opt_level != "O0" else jnp.float32,
        remat=True,
    )
    model = BertModel(cfg)
    policy = amp.get_policy(args.opt_level)
    if args.zero:
        # ZeRO over every local device: local-mean loss per batch shard,
        # unreduced grads into the sharded LAMB step (the psum_scatter is
        # the gradient averaging; norm_psum_axis restores exact per-tensor
        # trust ratios across the chunks), bf16-compressed param gather
        from jax.sharding import Mesh, PartitionSpec as P

        from apex_tpu.parallel import collectives

        n_dev = len(jax.devices())
        if args.batch % n_dev:
            raise SystemExit(f"--batch {args.batch} must divide the "
                             f"device count {n_dev} under --zero")
        mesh = Mesh(np.asarray(jax.devices()), ("data",))
        mp_opt = amp.MixedPrecisionOptimizer(
            FusedLAMB(lr=args.lr, weight_decay=0.01,
                      norm_psum_axis="data"),
            policy, zero_axis="data",
            zero_level=args.zero_level,
            # bf16 gather is free only when the model params already live
            # in half precision (cast O2/O3); for fp32-param policies
            # (O0/O1) it would round the weights every step.
            gather_dtype="bf16" if policy.cast_model_type is not None
            else None,
            reduce_dtype=args.reduce_dtype)
        params = amp.cast_params(model.init(jax.random.PRNGKey(0)), policy)
        pspecs = jax.tree.map(lambda _: P(), params)
        data_spec = P("data")

        if args.zero_level >= 3:
            # fully-sharded: the bf16 params persist as 1/dp chunk trees;
            # each layer's weights gather just-in-time inside the layer
            # loop (run_layers chunk_meta) and grads arrive per-layer
            # reduce-scattered via the gather transposes
            from apex_tpu.optimizers.distributed import gather_chunked_tree

            z3 = mp_opt.zero3_init(params, mesh, pspecs)
            layer_meta = z3.meta.subtree("layers")
            rest_meta = z3.meta.select(
                [k for k in z3.meta.shapes if k != "layers"])
            params, state = z3.params, z3.opt_state
            pspecs, zero_specs = z3.param_specs, z3.state_specs

            def zero_step(p, s, toks, attn, lmask, labels, nsp, types):
                rest_c = {k: v for k, v in p.items() if k != "layers"}

                def scaled(rest_c, layer_c):
                    rest = gather_chunked_tree(rest_c, rest_meta)
                    return mp_opt.scale_loss(
                        model.loss(dict(rest, layers=layer_c), toks, attn,
                                   lmask, labels, nsp, types,
                                   layer_chunk_meta=layer_meta), s)

                ls, (rg, lg) = jax.value_and_grad(scaled, argnums=(0, 1))(
                    rest_c, p["layers"])
                np_, ns, m = mp_opt.apply_gradients(
                    s, p, dict(rg, layers=lg))
                return np_, ns, collectives.pmean(ls, "data"), m
        else:
            state, zero_specs = mp_opt.zero_init(params, mesh, pspecs)

            def zero_step(p, s, toks, attn, lmask, labels, nsp, types):
                def scaled(p):
                    return mp_opt.scale_loss(
                        model.loss(p, toks, attn, lmask, labels, nsp,
                                   types), s)

                ls, gs = jax.value_and_grad(scaled)(p)
                np_, ns, m = mp_opt.apply_gradients(s, p, gs)
                return np_, ns, collectives.pmean(ls, "data"), m

        zero_fn = jax.shard_map(
            zero_step, mesh=mesh,
            in_specs=(pspecs, zero_specs) + (data_spec,) * 6,
            out_specs=(pspecs, zero_specs, P(), P()), check_vma=False)

        @jax.jit
        def train_step(p, s, *batch):
            np_, ns, ls, m = zero_fn(p, s, *batch)
            return np_, ns, ls / s.scaler.loss_scale, m
    else:
        # FusedLAMB: the layer-adaptive optimizer the reference pairs
        # with BERT
        mp_opt = amp.MixedPrecisionOptimizer(
            FusedLAMB(lr=args.lr, weight_decay=0.01), policy)
        params = amp.cast_params(model.init(jax.random.PRNGKey(0)), policy)
        state = mp_opt.init(params)

        @jax.jit
        def train_step(p, s, toks, attn, lmask, labels, nsp, types):
            def scaled(p):
                return mp_opt.scale_loss(
                    model.loss(p, toks, attn, lmask, labels, nsp, types), s)

            ls, gs = jax.value_and_grad(scaled)(p)
            np_, ns, m = mp_opt.apply_gradients(s, p, gs)
            return np_, ns, ls / s.scaler.loss_scale, m

    if args.steps < 2:
        raise SystemExit("--steps must be >= 2 (step 0 is compile warmup)")
    tracer = None
    if args.trace:
        from apex_tpu.monitor import tracing

        tracer = tracing.arm(args.trace,
                             meta={"run": "pretrain_bert",
                                   "zero_level": args.zero_level or 0})
    if args.flight:
        from apex_tpu.monitor import flight as flight_mod

        flight_mod.arm(args.flight,
                       meta={"run": "pretrain_bert",
                             "zero_level": args.zero_level or 0})
    # one config dict for the journal's kind="meta" header AND the
    # ledger record's fingerprinted config block
    run_config = {"run": "pretrain_bert", "hidden": args.hidden,
                  "layers": args.layers, "seq": args.seq,
                  "batch": args.batch, "opt_level": args.opt_level,
                  "zero": bool(args.zero),
                  "zero_level": args.zero_level or 0,
                  "reduce_dtype": args.reduce_dtype or "fp32"}
    ledger_pred = {}
    journal = None
    if args.journal:
        from apex_tpu.monitor import MetricsJournal
        from apex_tpu.monitor import mfu as mfu_lib
        from apex_tpu.monitor.health import HealthMonitor

        journal = MetricsJournal(args.journal, sample_hbm_every=10,
                                 meta=run_config, health=HealthMonitor())
        try:
            # one extra trace (no compile) arms per-step MFU/anatomy
            # fields and fills the ledger's predicted block
            from apex_tpu.monitor import comm_accounting

            probe = synthetic_batch(np.random.default_rng(1), args.batch,
                                    args.seq, cfg.vocab_size)
            with comm_accounting() as acct:
                costs = mfu_lib.traced_step_costs(
                    train_step, params, state, *probe)
            toks_per_step = args.batch * args.seq
            journal.set_step_costs(
                flops_per_token=costs["flops"] / toks_per_step,
                bytes_per_token=costs["bytes"] / toks_per_step,
                method=costs["method"])
            journal.set_step_comm(acct.total_bytes())
            ledger_pred.update(flops_per_step=costs["flops"],
                               bytes_per_step=costs["bytes"],
                               comm_bytes_per_step=acct.total_bytes())
        except Exception as e:  # noqa: BLE001 - telemetry must not kill a run
            print(f"mfu arming failed (journal continues without): {e}")
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(args.steps):
        with jax.profiler.StepTraceAnnotation("train", step_num=i):
            batch = synthetic_batch(rng, args.batch, args.seq,
                                    cfg.vocab_size)
            if journal is not None:
                journal.step_start()
            if tracer is not None:
                tracer.step = i
            with maybe_span(tracer, "step", step=i) as sp:
                params, state, loss, metrics = train_step(
                    params, state, *batch)
                sp.barrier(loss)  # disarmed: a no-op, so no sync
        if journal is not None:
            # float(loss) inside step_end is the step's execution barrier
            journal.step_end(step=i, loss=loss,
                             tokens=args.batch * args.seq,
                             metrics=metrics, scaler=state.scaler)
        if i == 0:
            float(loss)
            t0 = time.perf_counter()
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d} mlm+nsp loss {float(loss):.4f} "
                  f"scale {float(metrics['loss_scale']):.0f}")
    if tracer is not None:
        from apex_tpu.monitor import tracing

        tracing.disarm()
        try:
            tracing.write_chrome_trace(args.trace,
                                       args.trace + ".chrome.json")
        except Exception as e:  # noqa: BLE001 - telemetry must not kill a run
            print(f"chrome export failed: {e}")
    if args.flight:
        from apex_tpu.monitor import flight as flight_mod

        flight_mod.disarm()  # clean exit: restore hooks, no dump
    if journal is not None:
        journal.close()
    n = max(args.steps - 1, 1)
    dt = (time.perf_counter() - t0) / n
    print(f"{args.batch * args.seq / dt:.0f} tokens/s "
          f"({args.opt_level}, FusedLAMB, {dt*1e3:.1f} ms/step)")
    if args.ledger:
        try:
            from apex_tpu.monitor import ledger as ledger_mod

            measured = None
            if not args.journal:
                measured = {"step_records": args.steps,
                            "tokens_per_sec":
                                {"p50": round(args.batch * args.seq / dt, 1)},
                            "wall_s": {"p50": round(dt, 6)},
                            "loss": {"last": float(loss)}}
            rec = ledger_mod.append_run(
                args.ledger, run="pretrain_bert", config=run_config,
                journal=args.journal, measured=measured,
                predicted=ledger_pred)
            print(f"ledger: {rec['fingerprint']} -> {args.ledger}")
        except Exception as e:  # noqa: BLE001 - telemetry must not kill a run
            print(f"ledger append failed: {e}")


if __name__ == "__main__":
    main()

"""BASELINE.md target #5 functional check at REAL width: GPT-3-1.3B-class
hidden size (h=2048, 32 heads) under TP x PP interleaved, loss-matched
against the unpipelined serial model.

The reference frames this target as "GPT-3 1.3B, TP=8 x PP=4 interleaved
on v5e-64: runs, loss-match vs no-pipelining" (BASELINE.md target #5; the
reference's own harness pattern is the pipeline-vs-serial equivalence of
tests/L0/run_transformer/run_pipeline_parallel_test.py:33-80 at the
gpt_scaling_test.py:49-70 model scales). Multi-chip hardware is not
available in this environment, so the check runs the REAL WIDTH (the
dimension that stresses sharded-GEMM correctness) at reduced depth/seq on
the 8-device virtual CPU mesh: tp=2 x pp=4 with interleaved vpp=2, one
full O-level-free fp32 train-step loss vs the serial model on identical
data. Depth and sequence are scaled down only for single-core CPU wall
clock; every parallel mechanism (column/row-parallel GEMMs at h=2048,
vocab-parallel embedding/CE, SPMD pipeline ring with virtual chunks)
runs at production width.

Run:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python benchmarks/gpt_1p3b_check.py --output out/gpt_1p3b_width_check.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from apex_tpu.utils.io import atomic_write_json  # noqa: E402

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.parallel import collectives, mesh as mesh_lib
from apex_tpu.transformer.pipeline_parallel import prepare_pipelined_model


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--layers", type=int, default=8,
                    help="must divide pp*vpp; reduced from 24 for CPU time")
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--vocab", type=int, default=50304)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--pp", type=int, default=4)
    ap.add_argument("--vpp", type=int, default=2)
    ap.add_argument("--micro", type=int, default=4,
                    help="microbatches (interleaved schedule needs a "
                         "multiple of pp)")
    ap.add_argument("--output", default=None)
    args = ap.parse_args()

    n = args.tp * args.pp
    cfg = GPTConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_layers=args.layers, num_attention_heads=args.heads,
        max_seq_len=args.seq, hidden_dropout=0.0,
        axis=mesh_lib.AXIS_MODEL, compute_dtype=jnp.float32, remat=True)
    serial_cfg = GPTConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_layers=args.layers, num_attention_heads=args.heads,
        max_seq_len=args.seq, hidden_dropout=0.0,
        axis=None, compute_dtype=jnp.float32, remat=True)

    model = GPTModel(cfg)
    serial_model = GPTModel(serial_cfg)
    params = serial_model.init(jax.random.PRNGKey(0))
    batch = args.micro
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, args.seq),
                                0, args.vocab)
    targets = jnp.roll(tokens, -1, axis=-1)

    t0 = time.perf_counter()
    serial_loss = float(serial_model.loss(params, tokens, targets))
    t_serial = time.perf_counter() - t0
    print(f"serial loss {serial_loss:.6f} ({t_serial:.1f}s)", file=sys.stderr)

    mesh = mesh_lib.make_virtual_mesh(
        n, tensor_model_parallel_size=args.tp,
        pipeline_model_parallel_size=args.pp,
        virtual_pipeline_model_parallel_size=args.vpp if args.vpp > 1 else None,
    )
    try:
        specs, sharded, pipe_loss = prepare_pipelined_model(
            model, params, mesh, num_microbatches=args.micro,
            virtual_pipeline_size=args.vpp)

        def fn(p, toks, tgts):
            rest = {k: v for k, v in p.items() if k != "layers"}
            loss = pipe_loss(rest, p["layers"], toks, tgts)
            return collectives.pmean(
                loss, mesh_lib.get_gradient_reduction_axes())

        data_spec = P(mesh_lib.AXIS_DATA)
        tokens_s = jax.device_put(tokens, NamedSharding(mesh, data_spec))
        targets_s = jax.device_put(targets, NamedSharding(mesh, data_spec))
        t0 = time.perf_counter()
        piped_loss = float(jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(specs, data_spec, data_spec),
            out_specs=P(), check_vma=False))(sharded, tokens_s, targets_s))
        t_pipe = time.perf_counter() - t0
        print(f"tp{args.tp} x pp{args.pp} (vpp={args.vpp}) loss "
              f"{piped_loss:.6f} ({t_pipe:.1f}s)", file=sys.stderr)
    finally:
        mesh_lib.destroy_model_parallel()

    rel = abs(piped_loss - serial_loss) / max(abs(serial_loss), 1e-9)
    record = {
        "metric": f"gpt_h{args.hidden}_L{args.layers}_tp{args.tp}"
                  f"_pp{args.pp}_vpp{args.vpp}_loss_match",
        "hidden": args.hidden, "heads": args.heads, "layers": args.layers,
        "seq": args.seq, "tp": args.tp, "pp": args.pp, "vpp": args.vpp,
        "serial_loss": round(serial_loss, 6),
        "pipelined_loss": round(piped_loss, 6),
        "rel_err": rel,
        "ok": bool(rel < 1e-4),
    }
    print(json.dumps(record))
    if args.output:
        # atomic (tmp + rename): no torn artifacts on crash
        atomic_write_json(args.output, record)
    if not record["ok"]:
        sys.exit(1)


if __name__ == "__main__":
    main()

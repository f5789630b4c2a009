"""GPT-2 345M on-chip convergence probe: warmup, discriminating endpoint,
CPU cross-check band.

VERDICT r4 ask #5: the r4 probe (loss 11.03 -> 8.01 in 300 steps, no
warmup, early 9.2 -> 15.9 spike) demonstrated numeric health but its
endpoint could not discriminate a subtle amp/master-weight bug from
healthy training. This probe
  1. uses linear lr warmup (kills the step-20 no-warmup spike),
  2. runs long enough to push loss unambiguously below random-init
     (~10.8): the acceptance bar is <= 6,
  3. replays the first K steps with IDENTICAL config + PRNG keys on the
     CPU backend in a subprocess and records the max relative loss-curve
     deviation (``cpu_curve_max_rel_dev``) under a stated band — the
     chip-vs-CPU numeric divergence of the full O2 stack as a checked
     property (reference analog: tests/L1/common/compare.py's
     loss-by-loss comparison across builds; SURVEY §7's stated
     tolerance-band adaptation).

The memorization corpus is 2 fixed batches (the r4 protocol) at
batch 2 x seq 512 — sized so the CPU leg is tractable on one core while
the model is the real 345M stack (h=1024, L=24, flash kernels, fused LN,
chunked LM-head CE, fp32 masters, dynamic scaling).

Run on the chip:
    python benchmarks/convergence_probe.py \
        --output out/convergence_345m_r05.json
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from apex_tpu.utils.io import atomic_write_json  # noqa: E402

import jax
import jax.numpy as jnp


def run_probe(steps, *, lr, warmup, batch, seq, fetch_every=1):
    """Train the 345M O2 stack on the fixed 2-batch corpus; returns
    (losses, overflow_count, final_scale)."""
    from apex_tpu import amp
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam

    cfg = GPTConfig(
        vocab_size=50304, hidden_size=1024, num_layers=24,
        num_attention_heads=16, max_seq_len=seq, hidden_dropout=0.0,
        axis=None, compute_dtype=jnp.bfloat16, remat=True,
        lm_head_chunks=8)
    model = GPTModel(cfg)
    policy = amp.get_policy("O2")
    mp_opt = amp.MixedPrecisionOptimizer(FusedAdam(lr=lr), policy)
    params = amp.cast_params(model.init(jax.random.PRNGKey(0)), policy)
    opt_state = mp_opt.init(params)
    corpus = jax.random.randint(jax.random.PRNGKey(1), (2, batch, seq),
                                0, cfg.vocab_size)

    @jax.jit
    def step(params, opt_state, tokens, lr_t):
        targets = jnp.roll(tokens, -1, axis=-1)

        def scaled(p):
            return mp_opt.scale_loss(model.loss(p, tokens, targets),
                                     opt_state)

        loss_s, grads = jax.value_and_grad(scaled)(params)
        new_p, new_s, metrics = mp_opt.apply_gradients(
            opt_state, params, grads, lr_t=lr_t)
        return new_p, new_s, loss_s / opt_state.scaler.loss_scale, metrics

    losses, overflows = [], 0
    for i in range(steps):
        lr_t = jnp.float32(lr * min(1.0, (i + 1) / max(warmup, 1)))
        params, opt_state, loss, metrics = step(
            params, opt_state, corpus[i % 2], lr_t)
        losses.append(float(loss))
        overflows += int(metrics["found_inf"])
        if i % 50 == 0:
            print(f"step {i}: loss {losses[-1]:.4f} "
                  f"scale {float(metrics['loss_scale']):.0f}",
                  file=sys.stderr)
    return losses, overflows, float(opt_state.scaler.loss_scale)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=50)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--cpu-check-steps", type=int, default=6,
                    help="first-K-step CPU replay; 0 disables")
    ap.add_argument("--cpu-band", type=float, default=0.05,
                    help="accepted max relative per-step loss deviation")
    ap.add_argument("--emit-curve", type=int, default=0,
                    help="internal: run N steps, print the loss list, exit"
                         " (the CPU-leg subprocess entry)")
    ap.add_argument("--output", default=None)
    args = ap.parse_args()

    if args.emit_curve:
        losses, _, _ = run_probe(args.emit_curve, lr=args.lr,
                                 warmup=args.warmup, batch=args.batch,
                                 seq=args.seq)
        print(json.dumps(losses))
        return

    t0 = time.perf_counter()
    losses, overflows, final_scale = run_probe(
        args.steps, lr=args.lr, warmup=args.warmup, batch=args.batch,
        seq=args.seq)
    wall = time.perf_counter() - t0

    record = {
        "metric": "gpt2_345m_o2_convergence",
        "platform": jax.default_backend(),
        "steps": args.steps, "lr": args.lr, "warmup_steps": args.warmup,
        "batch": args.batch, "seq": args.seq,
        "loss_first": round(losses[0], 4),
        "loss_final": round(losses[-1], 4),
        "loss_max_after_warmup": round(max(losses[args.warmup:]), 4),
        "overflow_steps": overflows,
        "final_loss_scale": final_scale,
        "wall_seconds": round(wall, 1),
        "curve_every_10": [round(x, 4) for x in losses[::10]],
        "ok": bool(losses[-1] <= 6.0),
    }

    if args.cpu_check_steps:
        k = args.cpu_check_steps
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        try:
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--emit-curve", str(k), "--lr", str(args.lr),
                 "--warmup", str(args.warmup), "--batch", str(args.batch),
                 "--seq", str(args.seq)],
                capture_output=True, text=True, env=env, timeout=3600)
            cpu_curve = json.loads(out.stdout.strip().splitlines()[-1])
            dev = max(abs(a - b) / max(abs(b), 1e-6)
                      for a, b in zip(losses[:k], cpu_curve))
            record["cpu_check"] = {
                "steps": k,
                "tpu_curve": [round(x, 4) for x in losses[:k]],
                "cpu_curve": [round(x, 4) for x in cpu_curve],
                "cpu_curve_max_rel_dev": round(dev, 5),
                "band": args.cpu_band,
                "ok": bool(dev <= args.cpu_band),
            }
            record["ok"] = bool(record["ok"] and record["cpu_check"]["ok"])
        except Exception as e:  # noqa: BLE001 - record the failure, keep probe
            record["cpu_check"] = {"error": str(e)[:300]}

    print(json.dumps(record))
    if args.output:
        # atomic (tmp + rename): a crash mid-write must never leave a
        # torn artifact for a later evidence check to trip on
        atomic_write_json(args.output, record)
    sys.exit(0 if record["ok"] else 1)


if __name__ == "__main__":
    main()

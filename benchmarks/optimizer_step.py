"""Optimizer-step microbenchmark: fused tree-map step vs unfused eager Adam.

BASELINE.md target #3 ("fused-optimizer step >= 3x an unfused eager Adam")
measured directly, the way the reference frames it: its multi-tensor fused
optimizers exist to replace the per-parameter, per-op kernel launches of an
eager `torch.optim.Adam` loop (csrc/multi_tensor_apply.cuh:16-133,
tests/L0/run_optimizers/test_fused_optimizer.py).

TPU-native translation of the two sides:
- **fused**: `FusedAdam`'s whole-tree update inside one `jax.jit` — XLA
  compiles one fused elementwise pass over every parameter (the
  multi-tensor-launch-batching equivalent).
- **eager**: the same Adam math, one parameter at a time, *outside* jit —
  every `jnp` op is dispatched individually, exactly like eager torch issuing
  separate kernels per param and per op.

Run standalone (`python benchmarks/optimizer_step.py`) for a JSON line, or
call :func:`measure_speedup` (bench.py does, to record the ratio in the
driver's benchmark artifact).
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp


def gpt2_like_param_tree(hidden=768, layers=12, vocab=50304, seq=1024, dtype=jnp.float32):
    """A GPT-2-124M-shaped parameter pytree (~148 leaves, ~124M params):
    realistic leaf-count/size mix for the launch-overhead comparison."""
    k = jax.random.PRNGKey(0)

    def rnd(shape):
        nonlocal k
        k, sub = jax.random.split(k)
        return (jax.random.normal(sub, shape, jnp.float32) * 0.02).astype(dtype)

    tree = {
        "wte": rnd((vocab, hidden)),
        "wpe": rnd((seq, hidden)),
        "ln_f": {"scale": jnp.ones((hidden,), dtype), "bias": jnp.zeros((hidden,), dtype)},
    }
    for i in range(layers):
        tree[f"h{i}"] = {
            "ln_1": {"scale": jnp.ones((hidden,), dtype), "bias": jnp.zeros((hidden,), dtype)},
            "attn": {
                "qkv_w": rnd((hidden, 3 * hidden)),
                "qkv_b": jnp.zeros((3 * hidden,), dtype),
                "proj_w": rnd((hidden, hidden)),
                "proj_b": jnp.zeros((hidden,), dtype),
            },
            "ln_2": {"scale": jnp.ones((hidden,), dtype), "bias": jnp.zeros((hidden,), dtype)},
            "mlp": {
                "fc_w": rnd((hidden, 4 * hidden)),
                "fc_b": jnp.zeros((4 * hidden,), dtype),
                "proj_w": rnd((4 * hidden, hidden)),
                "proj_b": jnp.zeros((hidden,), dtype),
            },
        }
    return tree


def _fetch(tree):
    """Force execution: device->host fetch of a scalar whose dependency
    chain covers every leaf."""
    return float(sum(jnp.sum(l[..., :1]) for l in jax.tree.leaves(tree)))


def eager_adam_step(params, m, v, grads, t, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8):
    """Unfused eager Adam: per-leaf python loop, no jit — each jnp op is its
    own dispatch (the eager `torch.optim.Adam` analog)."""
    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(m)
    flat_v = treedef.flatten_up_to(v)
    bc1 = 1.0 - b1 ** t
    bc2 = 1.0 - b2 ** t
    out_p, out_m, out_v = [], [], []
    for p, g, mi, vi in zip(flat_p, flat_g, flat_m, flat_v):
        mi = b1 * mi + (1.0 - b1) * g
        vi = b2 * vi + (1.0 - b2) * jnp.square(g)
        m_hat = mi / bc1
        v_hat = vi / bc2
        p = p - lr * m_hat / (jnp.sqrt(v_hat) + eps)
        out_p.append(p)
        out_m.append(mi)
        out_v.append(vi)
    unflatten = treedef.unflatten
    return unflatten(out_p), unflatten(out_m), unflatten(out_v)


def measure_speedup(hidden=768, layers=12, fused_steps=10, eager_steps=3,
                    windows=3, verbose=True):
    """Returns (speedup, fused_ms, eager_ms) for one optimizer step.

    Both sides are timed as MEDIANS over ``windows`` INTERLEAVED windows
    (fused, eager, fused, eager, …) — on the shared chip of the 2026-07
    rounds a single un-windowed sample swung several-fold (2.9x–38x
    across identical runs), and interleaving keeps the ratio a
    comparison of the same minutes (PERF_NOTES.md discipline)."""
    import optax

    from apex_tpu.optimizers import FusedAdam

    params = gpt2_like_param_tree(hidden=hidden, layers=layers)
    grads = jax.tree.map(lambda p: jnp.full_like(p, 1e-4), params)

    tx = FusedAdam(lr=1e-3)
    state = tx.init(params)

    @jax.jit
    def fused_step(params, state, grads):
        updates, state = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    # warmups: compile the fused program, exercise the eager dispatch path
    p, s = fused_step(params, state, grads)
    _fetch(p)
    m = jax.tree.map(lambda x: jnp.zeros_like(x), params)
    v = jax.tree.map(lambda x: jnp.zeros_like(x), params)
    ep, em, ev = eager_adam_step(params, m, v, grads, t=1)
    _fetch(ep)

    fused_samples, eager_samples = [], []
    t = 2
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(fused_steps):
            p, s = fused_step(p, s, grads)
        _fetch(p)
        fused_samples.append((time.perf_counter() - t0) / fused_steps * 1e3)

        t0 = time.perf_counter()
        for _ in range(eager_steps):
            ep, em, ev = eager_adam_step(ep, em, ev, grads, t=t)
            t += 1
        _fetch(ep)
        eager_samples.append((time.perf_counter() - t0) / eager_steps * 1e3)

    import statistics

    # pair SAME-WINDOW samples: the median of per-window ratios compares
    # the two sides under the same minutes of drift, which independent
    # medians (possibly from different windows) would not
    speedup = statistics.median(
        e / f for f, e in zip(fused_samples, eager_samples))
    fused_ms = statistics.median(fused_samples)
    eager_ms = statistics.median(eager_samples)
    if verbose:
        print(
            f"optimizer step ({layers}-layer/{hidden}-hidden tree, "
            f"{len(jax.tree.leaves(params))} leaves): fused {fused_ms:.2f} ms, "
            f"eager {eager_ms:.2f} ms, speedup {speedup:.1f}x",
            file=sys.stderr,
        )
    return speedup, fused_ms, eager_ms


def main():
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
    speedup, fused_ms, eager_ms = measure_speedup()
    print(
        json.dumps(
            {
                "metric": "fused_adam_step_vs_eager_adam_step",
                "value": round(speedup, 2),
                "unit": "x",
                "fused_ms": round(fused_ms, 3),
                "eager_ms": round(eager_ms, 3),
            }
        )
    )


if __name__ == "__main__":
    main()

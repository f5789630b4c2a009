"""The row movers of ``DroplessExperts`` alone, on the chip, at the expert
model's shapes (32,768 tokens of 2048 bf16, 6 choices, 8 of 64 experts
held, a buffer of 98,304 rows), at three loads: each mover as shipped
against XLA's plain gathers, which are paid by the row whatever it holds
(``jnp.take`` over the whole buffer; one gather a choice over all tokens).

    chiprun -- python benchmarks/dropless_movers.py

One JSON line a reading: ``ms`` a call (median of ``--calls``) and ``ns_row``,
that time over the rows the load fills. Times come from a TPU or not at all.
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from apex_tpu.transformer import moe  # noqa: E402

N, D, K, HELD, EXPERTS = 32768, 2048, 6, 8, 64


def plain_spread(x, tok):
    return jnp.take(x, tok, axis=0)


def plain_collect(buf, slots):
    """A gather a choice over every token, ``C`` naming no row: what the
    layer did before its movers followed the load."""
    c = buf.shape[0]
    acc = jnp.zeros((slots.shape[0], buf.shape[1]), jnp.float32)
    for idx in slots.T:
        rows = jnp.take(buf, jnp.minimum(idx, c - 1), axis=0)
        acc += jnp.where((idx < c)[:, None], rows.astype(jnp.float32), 0.0)
    return acc.astype(buf.dtype)


def routing(load: float, rows: int, seed: int):
    """``(plan, slots, weights)`` for scores drawn evenly, the held experts
    lifted until they take ``load`` times their even share."""
    rng = np.random.default_rng(seed)
    scores = rng.random((N, EXPERTS), np.float32)
    want, lo, hi = load * N * K * HELD / EXPERTS, -1.0, 1.0
    for _ in range(30):
        lift = (lo + hi) / 2
        s = scores.copy()
        s[:, :HELD] += lift
        chosen = np.argpartition(-s, K, axis=1)[:, :K]
        lo, hi = (lift, hi) if (chosen < HELD).sum() < want else (lo, lift)
    key = jnp.asarray(np.minimum(chosen, HELD).reshape(-1), jnp.int32)
    order = jnp.argsort(key, stable=True)
    rank = jnp.argsort(order)
    filled = jnp.minimum(jnp.sum(key < HELD), rows).astype(jnp.int32)
    slots = jnp.where(rank < filled, rank, rows).astype(jnp.int32)
    slots = slots.reshape(N, K)
    plan = moe.row_plan(order[:rows] // K, slots, filled)
    weights = jnp.asarray(rng.random(rows, np.float32))
    return plan, slots, weights


def timed(fn, args, calls: int) -> float:
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        took.append(time.perf_counter() - t0)
    return float(np.median(took)) * 1e3


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--calls", type=int, default=10)
    p.add_argument("--loads", type=float, nargs="+",
                   default=[0.25, 1.0, 1.65])
    args = p.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"a time is read on a TPU, not on {dev.platform}")
    rows = N * K * HELD // EXPERTS * 4
    kx, kb = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (N, D), jnp.bfloat16)
    buf = jax.random.normal(kb, (rows, D), jnp.bfloat16)
    for load in args.loads:
        plan, slots, w = routing(load, rows, seed=int(load * 100))
        filled = int(plan["filled"])
        movers = {
            "spread.plain": (plain_spread, (x, plan["tok"])),
            "spread": (lambda a, pl: moe._gather_rows(a, pl)[0], (x, plan)),
            "spread.weighted": (
                lambda a, pl, ww, b: moe._gather_rows(a, pl, ww, b),
                (x, plan, w, buf)),
            "collect.plain": (plain_collect, (buf, slots)),
            "collect": (moe._sum_rows, (buf, plan)),
            "collect.weighted": (moe._sum_rows, (buf, plan, w)),
            "plan": (moe.row_plan, (plan["tok"], slots, plan["filled"])),
        }
        for name, (fn, a) in movers.items():
            ms = timed(fn, a, args.calls)
            print(json.dumps({
                "mover": name, "load": load, "filled": filled, "rows": rows,
                "ms": ms, "ns_row": ms * 1e6 / filled,
                "device": dev.device_kind}), flush=True)


if __name__ == "__main__":
    main()

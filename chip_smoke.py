#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call, at
the full width of GPT-2 345M (hidden 1024, 24 layers, 16 heads, 1024
tokens, vocabulary 50304; random weights from a seed):

- train:   ``examples/gpt/pretrain_gpt.py``'s own ``main`` — O2, FusedAdam,
  dynamic loss scale, 8 sequences a step — then reads the compiled step
  for its Mosaic kernels, the state it aliases (the step donates
  ``params`` and ``opt_state``), device memory, two ways of timing a step
  and one profiled step;
- serve:   ``apex_tpu.serve.Engine`` in bf16 over prompts from a few
  tokens to several hundred, checked against one full-context forward;
- kernels: every Pallas kernel against its XLA twin
  (``apex_tpu.ops.selftest``);
- four chips, when four are visible: the trainer as ``--tp 2 --pp 2`` and
  as ``--zero-level 2``, first loss against the train phase's.

One process does everything, so one process holds the chip. Any failed
check raises, and the run exits non-zero without printing a result. The
first act refuses any backend but a TPU whose ``device_kind`` has a row in
the peak table: off-TPU every ``impl="auto"`` kernel takes its XLA path and
the run would "pass" on a CPU.

Last line of stdout on success:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
"""

from __future__ import annotations

import collections
import gc
import json
import math
import os
import re
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: GPT-2 345M at full width; the chip run cuts nothing
GPT2_345M = dict(hidden=1024, layers=24, heads=16, seq=1024, vocab=50304)

#: args+out+temp-alias of the one-chip train step with its state donated,
#: compile-only v5e topology (ISSUE 21; 12.4 GiB undonated): what
#: peak_bytes_in_use is printed beside
TRAIN_STEP_ESTIMATE_BYTES = int(8.3 * 2**30)

#: the Mosaic custom calls a compiled train step must hold, by the jitted
#: function the pallas_call sits in: flash forward, flash backward (dQ and
#: dK/dV are two calls), LayerNorm forward and backward
MOSAIC_CALLS = {"_flash_fwd": 1, "_flash_bwd": 2,
                "_fwd_pallas": 1, "_bwd_pallas": 1}


class SmokeError(RuntimeError):
    """A check of the smoke failed."""


def _require(cond, msg):
    if not cond:
        raise SmokeError(msg)


def _say(phase, **facts):
    print(json.dumps({"phase": phase, **facts}), flush=True)


def require_tpu(backend: str, platform: str, kind: str, count: int) -> dict:
    """The device the run reports, or an error naming what JAX found."""
    from apex_tpu.monitor.mfu import peak_spec

    _require(backend == "tpu",
             f"chip_smoke needs a TPU: jax.default_backend() is "
             f"{backend!r} ({count} x {kind!r})")
    peak_spec(f"{platform} {kind}")  # no row for this device_kind: raises
    return {"platform": platform, "kind": kind, "count": count}


def mosaic_calls(hlo_text: str) -> dict:
    """Mosaic custom calls in a compiled program's text, counted by the
    jitted function each ``pallas_call`` was traced in."""
    counts = collections.Counter()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="[^"]*jit\((\w+)\)/pallas_call', line)
        counts[m.group(1) if m else "<unnamed>"] += 1
    return dict(counts)


def _pretrain_gpt():
    sys.path.insert(0, os.path.join(ROOT, "examples", "gpt"))
    import pretrain_gpt

    return pretrain_gpt


def _program_bytes(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"args": m.argument_size_in_bytes, "out": m.output_size_in_bytes,
            "temp": m.temp_size_in_bytes, "alias": m.alias_size_in_bytes,
            "total": (m.argument_size_in_bytes + m.output_size_in_bytes
                      + m.temp_size_in_bytes - m.alias_size_in_bytes)}


def _run_trainer(*, hidden, layers, heads, seq, vocab, micro_batch,
                 num_microbatches, steps, parallel=()):
    """``pretrain_gpt.main`` at these sizes, with the checks every
    configuration must pass: every loss finite, the first at ln(vocab),
    at most one step skipped by the loss scaler."""
    run = _pretrain_gpt().main([
        "--hidden", str(hidden), "--layers", str(layers),
        "--heads", str(heads), "--seq", str(seq), "--vocab", str(vocab),
        "--micro-batch", str(micro_batch),
        "--num-microbatches", str(num_microbatches),
        "--steps", str(steps), *parallel])
    losses = run["losses"]
    _require(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    _require(all(math.isfinite(x) for x in losses),
             f"non-finite loss: {losses}")
    _require(abs(losses[0] - math.log(vocab)) < 0.3,
             f"first loss {losses[0]:.4f} is not within 0.3 of "
             f"ln({vocab}) = {math.log(vocab):.4f}")
    _require(sum(run["found_inf"]) <= 1,
             f"loss scaler skipped {sum(run['found_inf'])} steps: "
             f"scales {run['loss_scales']}")
    return run


def train_phase(*, hidden, layers, heads, seq, vocab, micro_batch,
                num_microbatches, steps=6, tpu=True) -> dict:
    """The trainer through its own ``main`` (``steps`` - 1 optimizer steps
    after the compile step), then what only a device can say about its
    step. ``tpu=False`` (the CPU tests) leaves out the checks a CPU cannot
    meet: kernels in the compiled text, device memory, a device trace."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import pyprof

    run = _run_trainer(hidden=hidden, layers=layers, heads=heads, seq=seq,
                       vocab=vocab, micro_batch=micro_batch,
                       num_microbatches=num_microbatches, steps=steps)
    facts = {"losses": [round(x, 4) for x in run["losses"]],
             "loss_scales": run["loss_scales"],
             "skipped_steps": sum(run["found_inf"]),
             "first_step_seconds": round(run["first_step_seconds"], 3),
             "seconds_per_step": round(run["seconds_per_step"], 5),
             "tokens_per_step": run["tokens_per_step"]}
    # take the state out of the record: a second reference to the first
    # copy would keep it alive beside every later one, and the chip has
    # room for two
    step, params, opt_state = (run.pop("train_step"), run.pop("params"),
                               run.pop("opt_state"))
    tokens, targets = run["next_batch"]()
    _require(step._cache_size() == 1,
             f"the train step compiled {step._cache_size()} times in "
             f"{steps} steps")

    weights = (params["embedding"]["embedding"],
               params["layers"]["qkv"]["kernel"],
               params["layers"]["fc1"]["kernel"])
    _require(all(w.dtype == jnp.bfloat16 for w in weights),
             f"O2 weights are not bf16: {[str(w.dtype) for w in weights]}")

    compiled = step.lower(params, opt_state, tokens, targets).compile()
    facts["program_bytes"] = _program_bytes(compiled)
    # the step consumes its state: every leaf is updated in its own buffer
    state_bytes = sum(a.nbytes for a in jax.tree.leaves((params, opt_state)))
    _require(facts["program_bytes"]["alias"] >= state_bytes,
             f"the train step aliases {facts['program_bytes']['alias']} "
             f"bytes of a state of {state_bytes}: a leaf is not donated")
    if tpu:
        platforms = {d.platform for leaf in jax.tree.leaves(params)
                     for d in leaf.devices()}
        _require(platforms == {"tpu"}, f"parameters live on {platforms}")
        calls = mosaic_calls(compiled.as_text())
        facts["mosaic_calls"] = calls
        _require(all(calls.get(k, 0) >= n for k, n in MOSAIC_CALLS.items()),
                 f"compiled train step lacks Mosaic kernels: found {calls}, "
                 f"need {MOSAIC_CALLS}")
        stats = jax.devices()[0].memory_stats()
        facts["peak_bytes_in_use"] = stats["peak_bytes_in_use"]
        facts["bytes_limit"] = stats.get("bytes_limit")
        facts["estimate_bytes"] = TRAIN_STEP_ESTIMATE_BYTES

    # the same step timed two ways: to block_until_ready, and to a host
    # fetch of the loss (the convention the tree's timers follow)
    def timed(sync, n=5):
        nonlocal params, opt_state
        out = []
        for _ in range(n):
            t0 = time.perf_counter()
            params, opt_state, loss, _ = step(params, opt_state, tokens,
                                              targets)
            sync(loss)
            out.append(time.perf_counter() - t0)
        return statistics.median(out)

    facts["step_seconds_block_until_ready"] = round(
        timed(jax.block_until_ready), 5)
    facts["step_seconds_host_fetch"] = round(timed(float), 5)

    if tpu:
        # one profiled step through the repo's own trace reduction, which
        # calls what it is given again on the same arguments: the step's
        # undonated twin, whose scopes are the step's
        scopes = pyprof.measured_scope_seconds(
            jax.jit(step.__wrapped__), params, opt_state, tokens, targets,
            steps=1, depth=2)
        total = scopes.pop("<total_device>", 0.0)
        facts["trace_total_device_seconds"] = round(total, 5)
        facts["trace_top_scopes_seconds"] = {
            k: round(v, 5) for k, v in sorted(
                scopes.items(), key=lambda kv: -kv[1])[:6]}
        _require(total > 0, "the profiler trace held no device event the "
                            "reduction in pyprof/prof.py could read")
    return facts


def serve_phase(*, hidden, layers, heads, vocab, max_seq, prompt_lens,
                new_tokens=32, max_batch=4, block_size=16) -> dict:
    """``serve.Engine`` on a bf16 ``GPTModel`` with the weights an O2
    trainer leaves, through ``Engine.run``: every request served in full,
    nothing left allocated, one compile of each program, and each greedy
    token at (or within bf16 rounding of) the argmax of ONE full-context
    forward over the finished sequence — the repo's engine oracle
    (tests/test_serve.py) with a tolerance set from the dtype."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.serve import Engine, Request, ServeConfig

    model = GPTModel(GPTConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_attention_heads=heads, max_seq_len=max_seq, hidden_dropout=0.0,
        axis=None, compute_dtype=jnp.bfloat16, remat=False))
    params = amp.cast_params(model.init(jax.random.PRNGKey(0)),
                             amp.get_policy("O2"))
    engine = Engine(model, params, ServeConfig(
        max_batch=max_batch, max_seq=max_seq, block_size=block_size))
    rng = np.random.default_rng(0)
    requests = [Request(prompt=[int(t) for t in rng.integers(0, vocab, n)],
                        max_new_tokens=new_tokens, request_id=i)
                for i, n in enumerate(prompt_lens)]
    t0 = time.perf_counter()
    results = engine.run(requests)
    serve_s = time.perf_counter() - t0

    _require(len(results) == len(requests),
             f"served {len(results)} of {len(requests)} requests")
    for r in results.values():
        _require(len(r.tokens) == new_tokens
                 and all(0 <= t < vocab for t in r.tokens),
                 f"request {r.request_id}: tokens {r.tokens}")
    stats = engine.stats
    _require(stats["pages_used"] == 0 and stats["active_slots"] == 0
             and engine.batcher.idle, f"engine not drained: {stats}")
    compiles = {"prefill": engine._prefill_fn._cache_size(),
                "decode": engine._decode_fn._cache_size()}
    _require(compiles == {"prefill": 1, "decode": 1},
             f"a serving program compiled more than once: {compiles}")

    # the oracle: right-pad every finished sequence to one length (causal
    # attention never looks right), one forward, and at each generated
    # position the chosen token's logit against the row maximum
    seqs = [list(r.prompt) + r.tokens for r in results.values()]
    width = -(-max(len(s) for s in seqs) // 128) * 128
    width = min(width, max_seq)
    batch = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        batch[i, :len(s)] = s
    logits = np.asarray(jax.jit(model.apply)(params, jnp.asarray(batch)),
                        np.float32)
    _require(np.isfinite(logits).all(), "oracle logits are not finite")
    worst, exact, total = 0.0, 0, 0
    for i, r in enumerate(results.values()):
        for t in range(len(r.prompt), len(seqs[i])):
            row = logits[i, t - 1]
            regret = float(row.max() - row[seqs[i][t]])
            worst = max(worst, regret / max(float(np.abs(row).max()), 1e-6))
            exact += int(row.argmax() == seqs[i][t])
            total += 1
    _require(worst <= 2e-2,
             f"a served token sits {worst:.4f} (scale-normalized) below "
             f"the full-forward argmax; bf16 tolerance is 2e-2")
    ttft = sorted(r.ttft_s for r in results.values())
    itl = sorted(s for r in results.values() for s in r.itl_s)
    return {"requests": len(results), "prompt_lens": list(prompt_lens),
            "new_tokens": new_tokens, "ticks": engine.ticks,
            "serve_seconds": round(serve_s, 3), "compiles": compiles,
            "stats": stats, "oracle_exact_tokens": f"{exact}/{total}",
            "oracle_worst_norm_regret": round(worst, 5),
            "ttft_seconds_median": round(statistics.median(ttft), 4),
            "itl_seconds_median": round(statistics.median(itl), 5)}


def kernel_phase(**sizes) -> dict:
    """Every Pallas kernel against its XLA twin, forward and backward,
    inside the selftest's tolerances."""
    from apex_tpu.ops.selftest import kernel_selftest

    results = kernel_selftest(**sizes)
    bad = {k: v for k, v in results.items()
           if isinstance(v, dict) and not v["ok"]}
    _require(results["all_ok"] and not bad,
             f"kernel comparisons outside tolerance: {bad}")
    return results


def four_chip_phase(*, first_loss, hidden, layers, heads, seq, vocab,
                    steps=4) -> dict:
    """The trainer over a 2x2 mesh two ways — tensor x pipeline parallel,
    and ZeRO-2 over four data-parallel ranks — on the train phase's
    tokens: first loss within bf16 tolerance of ``first_loss``, and every
    device holding parameter shards and live bytes."""
    import jax

    facts = {}
    for name, micro_batch, num_microbatches, parallel in (
            ("tp2_pp2", 2, 4, ("--tp", "2", "--pp", "2")),
            ("zero2_dp4", 1, 2, ("--zero-level", "2"))):
        run = _run_trainer(hidden=hidden, layers=layers, heads=heads,
                           seq=seq, vocab=vocab, micro_batch=micro_batch,
                           num_microbatches=num_microbatches, steps=steps,
                           parallel=parallel)
        _require(abs(run["losses"][0] - first_loss) <= 2e-2,
                 f"{name}: first loss {run['losses'][0]:.4f} against "
                 f"{first_loss:.4f} on the same tokens")
        shard_bytes = collections.Counter()
        for leaf in jax.tree.leaves(run["params"]):
            for shard in leaf.addressable_shards:
                shard_bytes[shard.device.id] += shard.data.nbytes
        live = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.devices()}
        _require(all(shard_bytes[d.id] > 0 for d in jax.devices()),
                 f"{name}: parameter bytes by device {dict(shard_bytes)}")
        _require(all(live[d.id] for d in jax.devices()),
                 f"{name}: live bytes by device {live}")
        facts[name] = {
            "losses": [round(x, 4) for x in run["losses"]],
            "first_step_seconds": round(run["first_step_seconds"], 3),
            "seconds_per_step": round(run["seconds_per_step"], 5),
            "param_shard_bytes_by_device": dict(shard_bytes),
            "bytes_in_use_by_device": live}
        del run
        gc.collect()
    return facts


def main() -> int:
    t_start = time.perf_counter()
    import jax

    dev = jax.devices()[0]
    device = require_tpu(jax.default_backend(), dev.platform,
                         dev.device_kind, len(jax.devices()))
    _require(device["count"] in (1, 4),
             f"the smoke is sized for 1 or 4 chips, not {device['count']}")

    import jaxlib

    from apex_tpu import csrc
    from apex_tpu.monitor.ledger import environment_stamp
    from apex_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()

    def cache_entries():
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    entries_before = cache_entries()
    _say("start", device=device, jax=jax.__version__,
         jaxlib=jaxlib.__version__, stamp=environment_stamp(),
         csrc_available=csrc.available(), compile_cache_dir=cache_dir,
         compile_cache_entries=entries_before)

    def phase(name, fn, **kw):
        t0 = time.perf_counter()
        facts = fn(**kw)
        _say(name, seconds=round(time.perf_counter() - t0, 2), **facts)
        gc.collect()
        return facts

    # 8 sequences a step: 4 microbatches of 2 on one chip, one microbatch
    # of 2 on each of four data-parallel chips — the same tokens
    t_train = time.perf_counter()
    train = phase("train", train_phase, **GPT2_345M, micro_batch=2,
                  num_microbatches=4 if device["count"] == 1 else 1)
    _say("time_to_first_step", seconds=round(
        t_train - t_start + train["first_step_seconds"], 2))
    widths = {k: v for k, v in GPT2_345M.items() if k != "seq"}
    phase("serve", serve_phase, **widths, max_seq=GPT2_345M["seq"],
          prompt_lens=(3, 17, 64, 200, 450, 700))
    phase("kernels", kernel_phase)
    if device["count"] == 4:
        phase("four_chips", four_chip_phase, first_loss=train["losses"][0],
              **GPT2_345M)
    else:
        _say("four_chips", skipped=f"{device['count']} device")
    _say("end", seconds=round(time.perf_counter() - t_start, 2),
         compile_cache_entries_before=entries_before,
         compile_cache_entries_after=cache_entries())
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

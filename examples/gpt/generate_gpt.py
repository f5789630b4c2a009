"""GPT serving: prompts → tokens through the paged-KV inference engine.

The decode-side sibling of pretrain_gpt.py: loads (or randomly initializes)
a GPT, builds an ``apex_tpu.serve.Engine`` (paged KV cache, flash-decode,
continuous batching over a fixed slot array), serves a prompt file, and
prints per-request tokens plus TTFT/ITL latency. TP-sharded decode with
``--tp``; sliding-window attention with ``--window``; the same ``--journal``
/ ``--trace`` observability hooks as the trainers.

ISSUE 12 knobs: ``--prefix-cache`` shares matched prompt-prefix KV blocks
by refcount (COW on divergence), ``--prefill-chunk N`` splits prompts into
N-token static chunks interleaved with decode ticks, ``--spec-k K`` drafts
K tokens per tick and verifies them in one batched forward (greedy only;
``--draft-layers`` builds a smaller randomly-initialized draft — omit it to
self-draft with the target, which demonstrates full acceptance), and
``--shared-prefix N`` prepends a common N-token system prompt to every
synthetic request so the prefix cache has something to share.

Run on 8 virtual devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
        python examples/gpt/generate_gpt.py --tp 2 --max-new-tokens 16
Prompt file format: one request per line, space-separated token ids.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import checkpoint
from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.parallel import mesh as mesh_lib
from apex_tpu.serve import Engine, Request, ServeConfig
from apex_tpu.utils.compile_cache import enable_compile_cache


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--vocab", type=int, default=50304)
    p.add_argument("--max-seq", type=int, default=256)
    p.add_argument("--window", type=int, default=None,
                   help="sliding-window attention (flash_attention/"
                        "flash_decode window semantics)")
    p.add_argument("--pos", default="learned",
                   choices=["learned", "rope", "none"])
    p.add_argument("--max-batch", type=int, default=4)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy; otherwise categorical at this "
                        "temperature with per-slot PRNG keys")
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--prefix-cache", action="store_true",
                   help="share matched prompt-prefix KV blocks between "
                        "requests (refcounts + copy-on-write; prefill "
                        "skips to the divergence point)")
    p.add_argument("--prefill-chunk", type=int, default=None, metavar="N",
                   help="split prompts into N-token static chunks, one "
                        "per tick interleaved with decode (a long prompt "
                        "never stalls running streams)")
    p.add_argument("--spec-k", type=int, default=0, metavar="K",
                   help="speculative decoding: K draft tokens per slot "
                        "per tick, verified in one batched forward "
                        "(greedy only)")
    p.add_argument("--draft-layers", type=int, default=None, metavar="L",
                   help="with --spec-k: build an L-layer randomly-"
                        "initialized draft model (default: self-draft "
                        "with the target weights)")
    p.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                   help="prepend a common N-token system prompt to every "
                        "synthetic request (the shared-prefix workload "
                        "knob for --prefix-cache)")
    p.add_argument("--prompt-file", default=None,
                   help="one request per line, space-separated token ids "
                        "(default: a few synthetic prompts)")
    p.add_argument("--load-dir", default=None,
                   help="restore {'params': ...} from a training "
                        "checkpoint dir (apex_tpu.checkpoint); ZeRO-3 "
                        "states export via Engine.params_from_zero3")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--journal", default=None, metavar="PATH",
                   help="write per-tick + per-request JSON-lines metrics "
                        "(TTFT/ITL/queue depth/occupancy; roll up with "
                        "python -m apex_tpu.monitor.report)")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="write serve.prefill/serve.decode spans "
                        "(apex_tpu.monitor.tracing) + a Chrome export "
                        "next to PATH")
    p.add_argument("--flight", nargs="?", const="auto", default=None,
                   metavar="PATH",
                   help="arm the flight recorder (apex_tpu.monitor."
                        "flight): recent tick/request records + "
                        "breadcrumbs dumped as strict JSON on crash/"
                        "SIGTERM/watchdog kill. Default PATH: "
                        "<journal>.flight.json")
    p.add_argument("--slo-ttft-ms", type=float, default=None,
                   help="TTFT target in ms: with --journal, the engine "
                        "emits per-window kind=\"slo\" attainment/goodput "
                        "records (monitor.report slo section; the "
                        "slo-burn health rule gates attainment)")
    p.add_argument("--slo-itl-ms", type=float, default=None,
                   help="ITL target in ms (see --slo-ttft-ms)")
    p.add_argument("--trace-sample-n", type=int, default=16, metavar="N",
                   help="tail-based sampling rate for request span trees "
                        "under --trace: every SLO violator keeps its full "
                        "tree, plus 1-in-N compliant requests; the rest "
                        "fold into one bounded kind=\"reqhist\" record")
    p.add_argument("--ledger", nargs="?", const="out/ledger.jsonl",
                   default=None, metavar="PATH",
                   help="append one fingerprinted run record (serve "
                        "config + environment stamp + measured TTFT/ITL "
                        "rollup) to the run ledger "
                        "(apex_tpu.monitor.ledger); "
                        "APEX_TPU_LEDGER=<path> arms it too")
    args = p.parse_args(argv)
    if not args.ledger and os.environ.get("APEX_TPU_LEDGER"):
        args.ledger = os.environ["APEX_TPU_LEDGER"]
    if args.flight == "auto":
        args.flight = ((args.journal + ".flight.json") if args.journal
                       else "out/generate_gpt.flight.json")
    return args


def load_prompts(args) -> list:
    if args.prompt_file:
        prompts = []
        with open(args.prompt_file) as f:
            for line in f:
                toks = [int(t) % args.vocab for t in line.split()]
                if toks:
                    prompts.append(toks)
        return prompts
    rng = np.random.default_rng(args.seed)
    shared = list(rng.integers(0, args.vocab, args.shared_prefix))
    return [shared + list(rng.integers(0, args.vocab, n))
            for n in (5, 12, 3, 9, 17, 7)]


def main(argv=None):
    """Serve; returns the run's record — per-request ``tokens``,
    ``ttft_s`` and ``itl_s`` keyed by request id, the engine's ``ticks``
    and ``stats``."""
    args = parse_args(argv)
    enable_compile_cache()
    mesh = None
    if args.tp > 1:
        mesh = mesh_lib.make_virtual_mesh(
            len(jax.devices()), tensor_model_parallel_size=args.tp)
    cfg = GPTConfig(
        vocab_size=args.vocab,
        hidden_size=args.hidden,
        num_layers=args.layers,
        num_attention_heads=args.heads,
        max_seq_len=args.max_seq,
        hidden_dropout=0.0,
        axis=mesh_lib.AXIS_MODEL if args.tp > 1 else None,
        compute_dtype=jnp.float32,
        remat=False,
        attention_window=args.window,
        position_embedding=args.pos,
    )
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    if args.load_dir:
        params = checkpoint.restore_checkpoint(
            args.load_dir, {"params": params})["params"]
        print(f"restored params from {args.load_dir}")

    tracer = None
    if args.trace:
        from apex_tpu.monitor import tracing

        tracer = tracing.arm(args.trace,
                             meta={"run": "generate_gpt", "tp": args.tp})
    # one serve-config dict for the journal's kind="meta" header AND the
    # ledger record's fingerprinted config block
    run_config = {"run": "generate_gpt", "tp": args.tp,
                  "max_batch": args.max_batch, "max_seq": args.max_seq,
                  "block_size": args.block_size,
                  "window": args.window or 0,
                  "prefix_cache": bool(args.prefix_cache),
                  "prefill_chunk": args.prefill_chunk or 0,
                  "spec_k": args.spec_k or 0}
    journal = None
    if args.journal:
        from apex_tpu.monitor import MetricsJournal
        from apex_tpu.monitor.health import HealthMonitor

        journal = MetricsJournal(
            args.journal,
            meta=run_config,
            # stream every tick/request/slo record through the online
            # health rules; alerts land in this journal
            health=HealthMonitor())
    if args.flight:
        from apex_tpu.monitor import flight as flight_mod

        flight_mod.arm(args.flight,
                       meta={"run": "generate_gpt", "tp": args.tp})

    draft_model = draft_params = None
    if args.spec_k and args.draft_layers:
        import dataclasses

        draft_model = GPTModel(dataclasses.replace(
            cfg, num_layers=args.draft_layers))
        draft_params = draft_model.init(jax.random.PRNGKey(args.seed + 1))
    engine = Engine(model, params, ServeConfig(
        max_batch=args.max_batch, max_seq=args.max_seq,
        block_size=args.block_size, temperature=args.temperature,
        top_k=args.top_k, seed=args.seed,
        prefix_cache=args.prefix_cache, prefill_chunk=args.prefill_chunk,
        spec_k=args.spec_k,
        slo_ttft_ms=args.slo_ttft_ms, slo_itl_ms=args.slo_itl_ms,
        trace_sample_n=args.trace_sample_n),
        mesh=mesh,
        draft_model=draft_model, draft_params=draft_params)
    prompts = load_prompts(args)
    budget = args.max_seq - args.max_new_tokens
    reqs = [Request(prompt=pr[:max(budget, 1)],
                    max_new_tokens=args.max_new_tokens, request_id=i)
            for i, pr in enumerate(prompts)]
    results = engine.run(reqs, journal=journal)

    for rid in sorted(results):
        r = results[rid]
        itl_ms = (1e3 * float(np.median(r.itl_s)) if r.itl_s else None)
        cached = f" | cached {r.cached_tokens} tok" if r.cached_tokens else ""
        print(f"request {rid}: prompt {len(r.prompt)} tok -> "
              f"{len(r.tokens)} new | ttft {1e3 * r.ttft_s:.1f} ms | "
              f"itl p50 {itl_ms and round(itl_ms, 2)} ms{cached}")
        print(f"  tokens: {r.tokens}")
    print(f"{len(results)} request(s) in {engine.ticks} decode tick(s) | "
          f"mesh tp={args.tp} | pool "
          f"{engine.allocator.num_blocks - 1} x {args.block_size} tokens")
    stats = engine.stats
    if args.prefix_cache or args.spec_k:
        print("serving stats: " + ", ".join(
            f"{k}={v}" for k, v in stats.items()))
    engine.drop_prefix_cache()

    if journal is not None:
        journal.close()
    if args.ledger:
        try:
            from apex_tpu.monitor import ledger as ledger_mod

            measured = None
            if not args.journal:
                # journal-less serve: a minimal measured block in the
                # report-rollup key shapes (serving section percentiles)
                ttfts = sorted(1e3 * r.ttft_s for r in results.values())
                itls = sorted(1e3 * s for r in results.values()
                              for s in r.itl_s)
                mid = lambda xs: xs[len(xs) // 2] if xs else None  # noqa: E731
                serving = {"requests": len(results)}
                if ttfts:
                    serving["ttft_ms"] = {"p50": round(mid(ttfts), 3)}
                if itls:
                    serving["itl_ms"] = {"p50": round(mid(itls), 3)}
                # attribution rides the ledger even journal-less, so
                # `ledger regress` can gate TTFT-attribution drift
                from apex_tpu.monitor.report import attribution_rollup

                attr = attribution_rollup(
                    [r.attribution for r in results.values()
                     if isinstance(r.attribution, dict)])
                if attr:
                    serving["attribution"] = attr
                measured = {"step_records": engine.ticks,
                            "serving": serving}
            rec = ledger_mod.append_run(
                args.ledger, run="generate_gpt", config=run_config,
                journal=args.journal, measured=measured,
                extra={"ticks": engine.ticks})
            print(f"ledger: {rec['fingerprint']} -> {args.ledger}")
        except Exception as e:  # noqa: BLE001 - telemetry must not kill a run
            print(f"ledger append failed: {e}")
    if args.flight:
        from apex_tpu.monitor import flight as flight_mod

        flight_mod.disarm()  # clean exit: restore hooks, no dump
    if tracer is not None:
        from apex_tpu.monitor import tracing

        tracing.disarm()
        try:
            tracing.write_chrome_trace(args.trace,
                                       args.trace + ".chrome.json")
            print(f"chrome trace: {args.trace}.chrome.json")
        except Exception as e:  # noqa: BLE001
            print(f"chrome export failed: {e}")
    if mesh is not None:
        mesh_lib.destroy_model_parallel()
    return {
        "tokens": {rid: list(r.tokens) for rid, r in results.items()},
        "ttft_s": {rid: r.ttft_s for rid, r in results.items()},
        "itl_s": {rid: list(r.itl_s) for rid, r in results.items()},
        "ticks": engine.ticks,
        "stats": stats,
    }


if __name__ == "__main__":
    main()

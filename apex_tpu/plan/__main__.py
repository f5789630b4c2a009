"""CLI for the static auto-parallelism planner.

``python -m apex_tpu.plan --model gpt-345m --mesh 8 --hbm-gb 16``
prints a ranked placement table (text) or the full strict-JSON search
result (``--format json``) — off-TPU, no device execution. Exit 0 when
a feasible winner exists, 1 when every candidate is rejected (the
rejection provenance tells you why), 2 on bad arguments.

No reference analog: the reference trains at one hand-chosen placement
per script (reference examples/*); nothing searches.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m apex_tpu.plan",
        description="static placement search: enumerate (dp,tp,pp,"
                    "schedule,zero,wire,...) candidates, price each "
                    "against the HBM budget and the calibrated peak "
                    "specs, rank by modeled step seconds")
    p.add_argument("--model", type=str, default="gpt-345m",
                   help="preset name (gpt-110m/gpt-345m/gpt-2.7b/"
                        "gpt-13b) or vocab,hidden,layers,heads,seq")
    p.add_argument("--mesh", type=int, default=8,
                   help="total device count to factorize")
    p.add_argument("--hbm-gb", type=float, default=16.0,
                   help="per-rank HBM budget in GiB")
    p.add_argument("--micro-batch", type=int, default=1)
    p.add_argument("--num-microbatches", type=int, default=1)
    p.add_argument("--window", type=int, default=None,
                   help="also enumerate attention_window=W candidates")
    p.add_argument("--platform", type=str, default=None,
                   help="peak-spec platform override (e.g. cpu, v4, "
                        "v5e); default autodetects")
    p.add_argument("--top", type=int, default=10,
                   help="rows in the text table (json always emits all)")
    p.add_argument("--format", choices=("text", "json"), default="text")
    args = p.parse_args(argv)

    # the search is host-side analysis: it runs on the virtual CPU mesh
    # whatever accelerator the machine has
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # noqa: BLE001 - backend already up: run on it
        pass

    from apex_tpu import plan as plan_mod

    if "," in args.model:
        try:
            vocab, hidden, layers, heads, seq = (
                int(s) for s in args.model.split(","))
        except ValueError:
            print(f"bad --model {args.model!r}: expected a preset name "
                  "or vocab,hidden,layers,heads,seq", file=sys.stderr)
            return 2
        spec = plan_mod.ModelSpec("custom", vocab, hidden, layers,
                                  heads, seq)
    elif args.model in plan_mod.MODEL_PRESETS:
        spec = plan_mod.MODEL_PRESETS[args.model]
    else:
        print(f"unknown model preset {args.model!r}; known: "
              f"{sorted(plan_mod.MODEL_PRESETS)}", file=sys.stderr)
        return 2

    result = plan_mod.search(
        spec, mesh=args.mesh, hbm_gb=args.hbm_gb,
        micro_batch=args.micro_batch,
        num_microbatches=args.num_microbatches, window=args.window,
        platform=args.platform)

    if args.format == "json":
        print(json.dumps(result, default=str))
        return 0 if result["winner"] else 1

    def fmt(rec):
        c, pred = rec["candidate"], rec["predicted"]
        knobs = [f"dp{c['dp']}"]
        if c["tp"] > 1:
            knobs.append(f"tp{c['tp']}" + ("+sp" if c["sp"] else ""))
        if c["pp"] > 1:
            knobs.append(f"pp{c['pp']}:{c['schedule']}"
                         + (f"x{c['vpp']}" if c["vpp"] > 1 else ""))
        if c["zero_level"]:
            knobs.append(f"zero{c['zero_level']}"
                         + (f"+pf{c['zero3_prefetch']}"
                            if c["zero3_prefetch"] else ""))
        if c["reduce_dtype"]:
            knobs.append(f"wire:{c['reduce_dtype']}")
        if c["moe_expert_axis"]:
            knobs.append("ep" + (f":{c['moe_dispatch_dtype']}"
                                 if c["moe_dispatch_dtype"] else ""))
        if c["unroll"]:
            knobs.append("unroll")
        return (" ".join(knobs),
                pred["hbm_bytes"] / 1024**3,
                pred["comm_bytes_by_tier"]["ici"] / 1e9,
                pred["bubble_floor"],
                pred["step_seconds"])

    print(f"plan: {result['model']['name']} on {result['mesh']} devices, "
          f"{result['hbm_budget_bytes'] / 1024**3:.1f} GiB/rank budget "
          f"(peak: {result['peak_spec']['source']}, "
          f"ici: {result['ici_spec']['source']})")
    print(f"{'#':>3} {'placement':<40} {'hbm GiB':>8} {'wire GB':>8} "
          f"{'bubble':>7} {'step s':>10}")
    for i, rec in enumerate(result["ranked"][:args.top]):
        name, hbm, wire, bub, step = fmt(rec)
        print(f"{i:>3} {name:<40} {hbm:>8.2f} {wire:>8.2f} "
              f"{bub:>7.3f} {step:>10.4g}")
    n_rej = len(result["rejected"])
    if n_rej:
        by: dict = {}
        for r in result["rejected"]:
            by[r["rejected_by"]] = by.get(r["rejected_by"], 0) + 1
        print(f"rejected {n_rej}: "
              + ", ".join(f"{k}={v}" for k, v in sorted(by.items())))
    if not result["winner"]:
        print("no feasible candidate (see rejection provenance)")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

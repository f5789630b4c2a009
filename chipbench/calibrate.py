#!/usr/bin/env python3
"""Reads, on the chip and at the cell's own size, the numbers a training
cell's ``correct`` compares: the program against the reference on many
seeds (the lower readings), and on a few seeds the controls (the reference
with every linear layer's product in int8 and in fp8, put in the program's
place) and the faults "a step that leaves its state unchanged" (the
reference with a learning rate of 0) and "half of the batch left out" (the
upper readings). One process builds the program once; a run of the
benchmark never calls this.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--out chiprun_out/calibrate.jsonl]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from chipbench import harness, manifest

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    cell = manifest.cell(manifest.load(ROOT), args.workload, ROOT)
    cfg, mix = cell["config"], cell["mix"]
    chips = cell["workload"]["chips"]
    harness.require_chips(chips)
    harness.enable_compile_cache(ROOT)
    out = open(args.out, "a", encoding="utf-8") if args.out else None

    def say(**row):
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    try:
        training(cell, seeds, control, say)
    finally:
        if out:
            out.close()
    return 0


def training(cell, seeds, control, say):
    from chipbench import compare, manifest, programs
    from chipbench.drivers import train
    from chipbench.references import train as ref_train

    cfg, mix = cell["config"], cell["mix"]
    chips = cell["workload"]["chips"]
    groups = manifest.part_groups(ROOT, cell)
    program = programs.load(mix["program"]).Program(ROOT, cfg, mix, chips)
    rows = mix.get("batch") or (mix["micro_batch"] * mix["num_microbatches"]
                                * chips)
    for seed in seeds:
        t0 = time.perf_counter()
        pool = train.make_pool(cfg, mix, seed, rows)
        first = pool[:train.FOLLOWED]
        loop, got = train.first_steps(program, pool, seed)
        losses, skipped = loop.fetched()
        got["losses"] = losses
        loop.free()
        t1 = time.perf_counter()
        ref = ref_train.follow(cfg["reference"], cfg, mix, seed, first,
                               steps=train.FOLLOWED)
        t2 = time.perf_counter()
        say(seed=seed, who="program", skipped=int(skipped.sum()),
            program_s=t1 - t0, reference_s=t2 - t1,
            losses=[float(x) for x in losses],
            ref_losses=[float(x) for x in ref["losses"]],
            **compare.train_readings(got, ref, groups))
        if seed in control:
            for precision in ("int8", "fp8"):
                low = ref_train.follow(
                    cfg["reference"], cfg, mix, seed, first,
                    steps=train.FOLLOWED, precision=precision)
                say(seed=seed, who=f"control_{precision}",
                    **compare.train_readings(low, ref, groups))
            still = ref_train.follow(
                cfg["reference"], cfg,
                dict(mix, optimizer=dict(mix["optimizer"], lr=0.0)), seed,
                first, steps=train.FOLLOWED)
            say(seed=seed, who="fault_state_unchanged",
                **compare.train_readings(still, ref, groups))
            half = ref_train.follow(cfg["reference"], cfg, mix, seed, first,
                                    steps=train.FOLLOWED,
                                    rows_kept=rows // 2)
            say(seed=seed, who="fault_half_batch",
                **compare.train_readings(half, ref, groups))


if __name__ == "__main__":
    sys.exit(main())

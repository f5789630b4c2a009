#!/usr/bin/env python3
"""``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell on the machine it is started on.

One process, which holds the chip. The last line of standard output is the
result; any fault exits with a code other than 0 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse
import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from chipbench import harness, manifest

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep-trace", default=None, metavar="DIR",
                   help="with --trace 1, also write the capture's events "
                        "there as JSON (how tests/recorded_trace.json "
                        "was cut)")
    args = p.parse_args(argv)
    if args.seed < 0:
        p.error("--seed is a whole number, 0 or more")

    bench = manifest.load(ROOT)
    cell = manifest.cell(bench, args.workload, ROOT)
    try:
        device = harness.require_chips(cell["workload"]["chips"])
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache(ROOT)
    driver = importlib.import_module(
        f"chipbench.drivers.{cell['mix']['driver']}")
    try:
        line = driver.run(cell, root=ROOT, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          t_start=T_START, device=device,
                          keep_trace=args.keep_trace)
    except harness.NothingToRead as e:
        print(f"chipbench: nothing to read for {e}", file=sys.stderr)
        return 4
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

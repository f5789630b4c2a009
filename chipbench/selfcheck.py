#!/usr/bin/env python3
"""Everything about the benchmark that can be checked without the chip.
Run it before the first chip call and after every change to the manifest:

    JAX_PLATFORMS=cpu python3 chipbench/selfcheck.py [--quick]

(a) ``BENCHMARK.json`` and every file it names against the contract's rules
    of form, and again with the tiny cells added the way a later PR adds a
    cell (files and entries of their own, no edit to a file that is there);
    every configuration's flops module (its ``"flops"`` key, else
    ``flops.py``) is there and has the functions the driver and the readers
    call;
(b) the trace reduction on the recorded trace under ``tests/``, against
    numbers worked out by hand beside it;
(c) each driver end to end at a tiny size, checking the result line's keys
    (left out with ``--quick``);
(d) with no TPU, ``run.py`` exits with a code other than 0 and prints no
    metric (left out with ``--quick``).

Exits 0 only if all hold.
"""

from __future__ import annotations

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import harness, manifest, trace_reduce  # noqa: E402

RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device",
               "checks")


def flops_problems(bench: dict) -> list:
    """A configuration whose flops module is missing or lacks a function
    would end its first traced run on the chip."""
    bad = []
    for c in bench["configs"]:
        cfg = manifest.load_json(os.path.join(ROOT, c["file"]))
        try:
            module = harness.flops_module(cfg)
        except ImportError as e:
            bad.append(f"config {c['name']!r}: its flops module: {e}")
            continue
        bad += [f"config {c['name']!r}: {module.__name__} has no {f}()"
                for f in harness.FLOPS_FUNCTIONS
                if not callable(getattr(module, f, None))]
    return bad


def check_manifest() -> list:
    from chipbench.tests import tiny_instella

    tiny_bench = tiny_instella.bench(ROOT)
    bad = [f"BENCHMARK.json: {p}"
           for p in manifest.problems(manifest.load(ROOT), ROOT)]
    bad += [f"with the tiny cells: {p}"
            for p in manifest.problems(tiny_bench, ROOT)]
    bad += flops_problems(tiny_bench)
    for w in manifest.load(ROOT)["workloads"]:
        try:
            manifest.limits(ROOT, manifest.cell(manifest.load(ROOT),
                                                w["name"], ROOT))
        except (FileNotFoundError, KeyError) as e:
            bad.append(f"workload {w['name']!r}: {e}")
    return bad


def check_trace_reduction() -> list:
    """The recorded trace is cut from a traced run of cell 1 on the chip;
    ``recorded_trace.expected.json`` holds what a person works out from it
    with a pencil (see ``recorded_trace.md``)."""
    here = os.path.join(ROOT, "chipbench", "tests")
    rec = manifest.load_json(os.path.join(here, "recorded_trace.json"))
    want = manifest.load_json(
        os.path.join(here, "recorded_trace.expected.json"))
    ctx = trace_reduce.context(
        rec["events"], hlo_text="", scopes=rec["scopes"],
        module=rec["module"], host_spans=rec["host_spans"])
    plane = ctx["planes"][0]
    ops = ctx["ops"][plane]
    gaps = trace_reduce.step_gaps(ctx["events"], plane, rec["module"])
    got = {
        "window_s": ctx["window_s"],
        "busy_s": ctx["busy_s"],
        "idle_share": 1.0 - ctx["busy_s"] / ctx["window_s"],
        "runs": len(ctx["runs"][plane]),
        "step_gaps_s": gaps,
        "flash_fwd_s": trace_reduce.seconds_of(trace_reduce.matching(
            ops, want["patterns"]["flash_fwd"])),
        "outside_grad_s": trace_reduce.seconds_of(trace_reduce.matching(
            ops, want["patterns"]["outside_include"],
            want["patterns"]["outside_exclude"])),
        "exposed_s": trace_reduce.exposed_seconds(
            ctx["events"], plane, want["patterns"]["collective"]),
        "top_op": ctx["breakdown"]["device_ops"][0][0],
        "longest_gap_label": ctx["breakdown"]["idle_gaps"][0][0],
    }
    bad = []
    for key, expect in want["numbers"].items():
        have = got[key]
        same = (have == expect if isinstance(expect, (str, int))
                else all(abs(a - b) <= 1e-9 for a, b in zip(
                    have if isinstance(have, list) else [have],
                    expect if isinstance(expect, list) else [expect]))
                and (not isinstance(expect, list)
                     or len(have) == len(expect)))
        if not same:
            bad.append(f"recorded trace: {key} reads {have!r}, "
                       f"by hand {expect!r}")
    return bad


def check_drivers() -> list:
    from chipbench.tests import tiny

    bad = []
    for name in sorted(tiny.TINY):
        line = tiny.run_tiny(name)
        missing = [k for k in RESULT_KEYS if k not in line]
        if missing or list(line)[-1] != "checks":
            bad.append(f"{name}: result line lacks {missing} or does not "
                       "end with checks")
        if line.get("correct") is not True:
            bad.append(f"{name}: correct is {line.get('correct')!r}: "
                       f"{line.get('checks')}")
        dev = line.get("device", {})
        if not {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev):
            bad.append(f"{name}: device is {dev}")
    return bad


def check_no_tpu() -> list:
    cell = manifest.load(ROOT)["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    bad = []
    if out.returncode == 0:
        bad.append("with no TPU run.py exited 0")
    if "metrics" in out.stdout:
        bad.append(f"with no TPU run.py printed {out.stdout[-300:]!r}")
    return bad


def main(argv=None) -> int:
    quick = "--quick" in (argv if argv is not None else sys.argv[1:])
    steps = [("manifest", check_manifest),
             ("trace reduction", check_trace_reduction)]
    if not quick:
        steps += [("drivers", check_drivers), ("no TPU", check_no_tpu)]
    failed = 0
    for name, fn in steps:
        bad = fn()
        print(f"{name}: {'ok' if not bad else 'FAILED'}")
        for b in bad:
            print(f"  {b}")
        failed += len(bad)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

"""The Kimi Linear model's tiny cell, added to the manifest the way
``tiny_lfm2.bench`` adds its own: files and entries, no edit to a file that
is there. It reports what ``kimi_linear_48b_a3b.pretrain_b2s8192`` does."""

from __future__ import annotations

import importlib
import json
import time

from .. import harness, manifest
from . import tiny_lfm2

CELL = "kimi_linear_tiny.pretrain_kimi_linear_tiny"
REAL = "kimi_linear_48b_a3b.pretrain_b2s8192"


def bench(root: str = manifest.ROOT) -> dict:
    b = tiny_lfm2.bench(root)
    b["configs"].append({
        "name": "kimi_linear_tiny", "source": "chipbench/tests",
        "reduced": [],
        "file": "chipbench/tests/configs/kimi_linear_tiny.json",
        "why": "rehearsal on the CPU"})
    b["workloads"].append({
        "name": CELL, "config": "kimi_linear_tiny",
        "traffic": "pretrain_kimi_linear_tiny", "chips": 1,
        "why": "rehearsal on the CPU"})
    for m in b["end_to_end"] + b["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    return b


def cell(root: str = manifest.ROOT) -> dict:
    return manifest.cell(bench(root), CELL, root)


def run(*, seed: int = 7, seconds: float = 1.0, program=None,
        root: str = manifest.ROOT) -> dict:
    """One run of the tiny cell, the harness's look for a chip skipped;
    returns the result line, parsed."""
    c = cell(root)
    driver = importlib.import_module(
        f"chipbench.drivers.{c['mix']['driver']}")
    kw = {} if program is None else {"program": program}
    return json.loads(driver.run(
        c, root=root, seed=seed, seconds=seconds, trace=False,
        t_start=time.perf_counter(), device=harness.device_info(), **kw))

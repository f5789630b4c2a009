"""The tiny cells: each driver end to end on the CPU, added to the manifest
the way a later PR adds a cell: a configuration file, a traffic mix file,
a limits file and entries, with no edit to a file that is there."""

from __future__ import annotations

import copy
import json
import time

from .. import harness, manifest

TINY = {
    "gpt_tiny.pretrain_tiny": ("gpt_tiny", "pretrain_tiny"),
    "bert_tiny.pretrain_bert_tiny": ("bert_tiny", "pretrain_bert_tiny"),
}


def tiny_bench(root: str = manifest.ROOT) -> dict:
    """``BENCHMARK.json`` with the tiny cells added under a second
    directory of ``paths``."""
    bench = copy.deepcopy(manifest.load(root))
    bench["paths"] = bench["paths"] + ["chipbench/tests"]
    for name, (cfg, mix) in TINY.items():
        if not any(c["name"] == cfg for c in bench["configs"]):
            bench["configs"].append({
                "name": cfg, "source": "chipbench/tests", "reduced": [],
                "file": f"chipbench/tests/configs/{cfg}.json",
                "why": "rehearsal on the CPU"})
        driver = manifest.load_json(manifest.find(
            root, bench["paths"], "traffic", mix))["driver"]
        twin = next(w["name"] for w in bench["workloads"]
                    if manifest.cell(bench, w["name"], root)["mix"]["driver"]
                    == driver)
        bench["workloads"].append({
            "name": name, "config": cfg, "traffic": mix, "chips": 1,
            "why": "rehearsal on the CPU"})
        # the tiny cell reports what the first real cell of its driver does
        for m in bench["end_to_end"] + bench["per_layer"]:
            if twin in m.get("workloads", []):
                m["workloads"].append(name)
    return bench


def run_tiny(name: str, *, seed: int = 7, seconds: float = 1.0,
             program=None, root: str = manifest.ROOT) -> dict:
    """One run of a tiny cell, the harness's look for a chip skipped;
    returns the result line, parsed."""
    import importlib

    cell = manifest.cell(tiny_bench(root), name, root)
    driver = importlib.import_module(
        f"chipbench.drivers.{cell['mix']['driver']}")
    kw = {} if program is None else {"program": program}
    line = driver.run(cell, root=root, seed=seed, seconds=seconds,
                      trace=False, t_start=time.perf_counter(),
                      device=harness.device_info(), **kw)
    return json.loads(line)

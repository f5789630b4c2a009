"""Reads ``BENCHMARK.json`` and the files it names, and holds them to the
contract's rules of form. ``run.py`` loads through here, and
``selfcheck.py`` runs ``problems`` before any chip time is spent.
"""

from __future__ import annotations

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
LAYERS = ("trainer", "engine", "model_step", "collectives", "kernels",
          "device")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj",
               "head_dim", "head_size", "expansion", "experts_per_tok",
               "n_embd", "n_inner", "d_model", "d_ff")


def load_json(path: str):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def cell(bench: dict, workload: str, root: str = ROOT) -> dict:
    """Everything one cell is made of, found by the names in the manifest:
    the workload entry, its configuration file, its traffic mix file, and
    the metrics it reports."""
    w = by_name(bench["workloads"], workload, "workload")
    c = by_name(bench["configs"], w["config"], "config")
    cfg = load_json(os.path.join(root, c["file"]))
    mix = load_json(find(root, bench["paths"], "traffic", w["traffic"]))
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return {"workload": w, "config": cfg, "mix": mix, "end_to_end": e2e,
            "per_layer": per_layer, "paths": bench["paths"]}


def find(root: str, paths, kind: str, name: str) -> str:
    """``<path>/<kind>/<name>.json`` in the first directory of ``paths``
    that has it, so a later PR may bring a directory of its own."""
    for p in paths:
        f = os.path.join(root, p, kind, name + ".json")
        if os.path.exists(f):
            return f
    raise FileNotFoundError(f"no {kind}/{name}.json under {paths}")


def limits(root: str, cell: dict) -> dict:
    """The limits of the numbers the cell's ``correct`` compares, from
    ``limits/<cell>.json``: a file of the cell's own, so that a later PR
    brings its cell's limits and edits no other's."""
    return load_json(find(root, cell["paths"], "limits",
                          cell["workload"]["name"]))["limits"]


def part_groups(root: str, cell: dict) -> dict:
    """``{group: pattern}`` from the same file: the groups of leaves whose
    gradient the cell reads apart (``compare.train_readings``)."""
    return load_json(find(root, cell["paths"], "limits",
                          cell["workload"]["name"])).get("part_groups", {})


def metric_file(root: str, paths, name: str) -> dict:
    """A per-layer metric's own file: its reader and the reader's
    parameters. The first directory of ``paths`` that has it wins, so a
    later PR may bring a directory of its own."""
    return load_json(find(root, paths, "metrics", name))


def _line(s, lo=1, hi=200) -> bool:
    return (isinstance(s, str) and lo <= len(s) <= hi
            and "\n" not in s and "\t" not in s and "\r" not in s)


def _is_width(key: str) -> bool:
    k = key.lower()
    return (k.endswith("_dim") or k.endswith("_rank")
            or any(w in k for w in WIDTH_WORDS))


def problems(bench: dict, root: str = ROOT, layers=LAYERS) -> list:
    """Every breach of the contract's rules of form, as sentences."""
    bad = []
    say = bad.append
    if set(bench) != TOP_KEYS:
        say(f"top-level keys {sorted(bench)} are not {sorted(TOP_KEYS)}")
        return bad
    raw = os.path.join(root, "BENCHMARK.json")
    if os.path.exists(raw) and os.path.getsize(raw) > 64 * 1024:
        say("BENCHMARK.json is over 64 KiB")

    paths = bench["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        say("paths: 1 to 16 directories")
    for p in paths:
        if not (isinstance(p, str) and PATH.match(p)) or p.startswith("/") \
                or ".." in p.split("/"):
            say(f"paths: {p!r} is not a relative path of the allowed "
                "characters")
    cmd = bench["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32
            and all(_line(c) for c in cmd)):
        say("command: a list of 1 to 32 strings of 1 to 200 characters")
    else:
        for c in cmd:
            if c.startswith("/") or ".." in c.split("/"):
                say(f"command: {c!r} leaves the repo")
            if "/" in c and not any(
                    c == p or c.startswith(p + "/") for p in paths):
                say(f"command: {c!r} names a file outside paths")
    rs = bench["run_seconds"]
    if not (isinstance(rs, int) and not isinstance(rs, bool)
            and 1 <= rs <= 51):
        say("run_seconds: a whole number from 1 to 51")

    def names(entries, what, keys, optional=()):
        seen = set()
        for e in entries:
            extra = set(e) - set(keys) - set(optional)
            missing = set(keys) - set(e)
            label = f"{what} {e.get('name')!r}"
            if extra or missing:
                say(f"{label}: keys {sorted(e)} (extra {sorted(extra)}, "
                    f"missing {sorted(missing)})")
            n = e.get("name")
            if not (isinstance(n, str) and NAME.match(n)):
                say(f"{label}: name is not 1 to 64 of letters, digits, "
                    "'_', '.', '-'")
            if n in seen:
                say(f"{label}: name used twice")
            seen.add(n)
        return seen

    configs = bench["configs"]
    if not 1 <= len(configs) <= 24:
        say("configs: 1 to 24")
    config_names = names(configs, "config",
                         ("name", "source", "file", "reduced", "why"))
    files = set()
    for c in configs:
        label = f"config {c.get('name')!r}"
        if not _line(c.get("source")) or not _line(c.get("why")):
            say(f"{label}: source and why are one line of 1 to 200")
        f = c.get("file", "")
        if not (PATH.match(f) and any(f.startswith(p + "/") for p in paths)):
            say(f"{label}: file {f!r} is not under paths")
        if f in files:
            say(f"{label}: file {f!r} is another configuration's")
        files.add(f)
        red = c.get("reduced", [])
        if not (isinstance(red, list) and len(red) <= 16):
            say(f"{label}: reduced has at most 16 keys")
        for k in red:
            if not (isinstance(k, str) and NAME.match(k)):
                say(f"{label}: reduced key {k!r} is not a name")
            elif _is_width(k):
                say(f"{label}: reduced names a width, {k!r}")
        full = os.path.join(root, f)
        if not os.path.exists(full):
            say(f"{label}: {f} does not exist")
        else:
            body = load_json(full)
            if not isinstance(body, dict):
                say(f"{label}: {f} is not a JSON object")
            for k in red:
                if isinstance(body, dict) and k not in body:
                    say(f"{label}: reduced key {k!r} is not in {f}")

    cells = bench["workloads"]
    if not 1 <= len(cells) <= 24:
        say("workloads: 1 to 24")
    cell_names = names(cells, "workload",
                       ("name", "config", "traffic", "chips", "why"))
    pairs, used = set(), set()
    for w in cells:
        label = f"workload {w.get('name')!r}"
        if w.get("config") not in config_names:
            say(f"{label}: config {w.get('config')!r} is not defined")
        used.add(w.get("config"))
        t = w.get("traffic")
        if not (isinstance(t, str) and NAME.match(t)):
            say(f"{label}: traffic {t!r} is not a name")
        elif not any(os.path.exists(os.path.join(
                root, p, "traffic", t + ".json")) for p in paths):
            say(f"{label}: no traffic/{t}.json under paths")
        if (w.get("config"), t) in pairs:
            say(f"{label}: this pair of config and traffic appears twice")
        pairs.add((w.get("config"), t))
        if w.get("chips") not in (1, 4):
            say(f"{label}: chips is 1 or 4")
        if not _line(w.get("why")):
            say(f"{label}: why is one line of 1 to 200 characters")
    for c in config_names - used:
        say(f"config {c!r} is used by no cell")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        say(f"{four} of {len(cells)} cells ask for 4 chips")

    e2e = bench["end_to_end"]
    if not 1 <= len(e2e) <= 16:
        say("end_to_end: 1 to 16")
    e2e_names = names(e2e, "end_to_end",
                      ("name", "unit", "better", "bound", "source"),
                      ("workloads",))
    if "setup_s" not in e2e_names:
        say("end_to_end lacks setup_s")
    per = bench["per_layer"]
    if not 1 <= len(per) <= 128:
        say("per_layer: 1 to 128")
    per_names = names(per, "per_layer",
                      ("name", "unit", "better", "source", "layer", "moves"),
                      ("workloads",))
    for n in e2e_names & per_names:
        say(f"metric {n!r} is both end-to-end and per-layer")

    def reports(metric, cell_name):
        return cell_name in metric.get("workloads", [cell_name])

    for m in e2e + per:
        label = f"metric {m.get('name')!r}"
        if not (isinstance(m.get("unit"), str) and UNIT.match(m["unit"])):
            say(f"{label}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            say(f"{label}: better is lower or higher")
        if m.get("source") not in SOURCES:
            say(f"{label}: source {m.get('source')!r}")
        for w in m.get("workloads", []):
            if w not in cell_names:
                say(f"{label}: workloads names {w!r}, which is no cell")
        if "workloads" in m and not m["workloads"]:
            say(f"{label}: workloads is empty")
    for m in e2e:
        label = f"end_to_end {m.get('name')!r}"
        if m.get("source") not in ("host_clock", "device_trace"):
            say(f"{label}: source is host_clock or device_trace")
        b = m.get("bound")
        if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.1):
            say(f"{label}: bound {b!r} is not between 0.01 and 0.1")
    for m in per:
        label = f"per_layer {m.get('name')!r}"
        layer = m.get("layer")
        if not (isinstance(layer, str) and NAME.match(layer)):
            say(f"{label}: layer {layer!r} is not one token of letters, "
                "digits, '_', '.', '-'")
        elif layers and layer not in layers:
            say(f"{label}: layer {layer!r} is not one of {list(layers)}")
        moved = m.get("moves")
        if moved not in e2e_names:
            say(f"{label}: moves {moved!r}, which is no end-to-end metric")
            continue
        target = by_name(e2e, moved, "end_to_end")
        for w in m.get("workloads", cell_names):
            if "workloads" in m and not reports(target, w):
                say(f"{label}: cell {w!r} does not report {moved!r}")
        if (m.get("name", "").endswith("_roofline")
                or "mfu" in m.get("name", "")) and m.get("unit") != "%":
            say(f"{label}: a share of a roofline or of a peak has unit %")
        try:
            metric_file(root, paths, m["name"])
        except FileNotFoundError as e:
            say(f"{label}: {e}")
    for w in cells:
        n = w.get("name")
        mine = [m for m in e2e if reports(m, n)]
        if not any(m["name"] == "setup_s" for m in mine):
            say(f"workload {n!r} does not report setup_s")
        if len(mine) < 2:
            say(f"workload {n!r} reports no end-to-end metric but setup_s")
        got = {m["name"] for m in mine}
        if not any((n in m["workloads"]) if "workloads" in m
                   else m.get("moves") in got for m in per):
            say(f"workload {n!r} reports no per-layer metric")
    return bad

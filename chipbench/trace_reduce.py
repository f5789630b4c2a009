"""From a profiler trace to numbers. The benchmark's own copy of the
reduction, so that no later PR can change how a device time is read.

Everything works on a flat list of events, each a dict
``{"plane", "line", "name", "start", "dur", "stats"}`` with times in
seconds on the profile's clock. ``load_xplane`` makes that list from the
``.xplane.pb`` a ``jax.profiler`` capture leaves; the recorded trace under
``tests/`` is the same list as JSON.

A device's plane is ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one
event per executed HLO instruction, named by the instruction's whole text
(``%fusion.12 = bf16[..] fusion(...)``): ``load_xplane`` keeps the name
before `` = `` as ``name`` and the operation as ``op``. A ``while``,
``conditional`` or ``call`` there is an envelope around the events of its
body: envelopes are left out of every sum (counting them bills a scanned
stack twice) and change no union. Its line ``XLA Modules`` holds one event
per run of a compiled program.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
ENVELOPES = ("while", "conditional", "call")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")


def instruction(text: str):
    """``(name, op)`` of an HLO instruction's text: ``%cond.2 = (f32[8],
    s32[]) conditional(...)`` gives ``("cond.2", "conditional")``. A text
    that is no instruction is its own name."""
    name, eq, rest = text.partition(" = ")
    if not eq:
        return text.lstrip("%"), ""
    if rest.startswith("("):          # a tuple type: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    m = _OPCODE.match(rest)
    return name.lstrip("%"), m.group(1) if m else ""


def load_xplane(log_dir: str) -> list:
    """Every event of the newest capture under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            for e in line.events:
                name, op = (instruction(e.name) if line.name == OPS_LINE
                            else (e.name, ""))
                events.append({
                    "plane": plane.name, "line": line.name, "name": name,
                    "op": op,
                    "start": e.start_ns * 1e-9, "dur": e.duration_ns * 1e-9,
                    "stats": {k: v for k, v in e.stats
                              if isinstance(v, (str, int, float))}})
    return events


def device_planes(events) -> list:
    """Names of the device planes, by device number."""
    found = {e["plane"] for e in events if _DEVICE_PLANE.match(e["plane"])}
    return sorted(found, key=lambda p: int(_DEVICE_PLANE.match(p).group(1)))


def clip(events, lo: float, hi: float) -> list:
    """Events cut to the window [lo, hi]; those outside it are dropped."""
    out = []
    for e in events:
        a, b = max(e["start"], lo), min(e["start"] + e["dur"], hi)
        if b > a:
            out.append({**e, "start": a, "dur": b - a})
    return out


def ops(events, plane: str) -> list:
    """The executed instructions of one device, envelopes left out."""
    return [e for e in events
            if e["plane"] == plane and e["line"] == OPS_LINE
            and e.get("op") not in ENVELOPES]


def union(intervals) -> list:
    """Sorted, merged ``(start, end)`` intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def spans(events) -> list:
    return [(e["start"], e["start"] + e["dur"]) for e in events]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes) -> list:
    """The parts of ``intervals`` (merged) that no hole (merged) covers."""
    out = []
    holes = list(holes)
    for a, b in intervals:
        at = a
        for ha, hb in holes:
            if hb <= at or ha >= b:
                continue
            if ha > at:
                out.append((at, ha))
            at = max(at, hb)
            if at >= b:
                break
        if at < b:
            out.append((at, b))
    return out


_HLO_INSTR = re.compile(
    r"%?([\w.\-]+)\s*=.*metadata=\{[^}]*op_name=\"([^\"]+)\"")


def hlo_scopes(hlo_text: str) -> dict:
    """Instruction name to the ``op_name`` the compiler kept for it
    (``jit(train_step)/transpose(jvp(...))/attention/dot_general``), from
    a compiled program's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTR.search(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def scope_of(event, scopes: dict) -> str:
    """Where the instruction came from in the program: by its name in the
    compiled text, else whatever path the profiler itself filed."""
    found = scopes.get(event["name"])
    if found:
        return found
    for key in ("tf_op", "long_name", "hlo_op"):
        v = event["stats"].get(key)
        if isinstance(v, str) and "/" in v:
            return v
    return ""


def matching(events, include: str, exclude: str = "",
             where: str = "scope") -> list:
    """Events whose scope (or name) matches ``include`` and not
    ``exclude``. The events carry their scope (see ``context``)."""
    inc = re.compile(include)
    exc = re.compile(exclude) if exclude else None
    out = []
    for e in events:
        text = e.get(where) or ""
        if inc.search(text) and not (exc and exc.search(text)):
            out.append(e)
    return out


def seconds_of(events) -> float:
    """Sum of device durations (no union: one line, one instruction at a
    time; envelopes are already out)."""
    return sum(e["dur"] for e in events)


def module_runs(events, plane: str, pattern: str) -> list:
    """``(start, end)`` of each run of the compiled program whose name
    matches, in time order."""
    rx = re.compile(pattern)
    return sorted((e["start"], e["start"] + e["dur"]) for e in events
                  if e["plane"] == plane and e["line"] == MODULES_LINE
                  and rx.search(e["name"]))


def step_gaps(events, plane: str, pattern: str) -> list:
    """Seconds between the last instruction of one run of the program and
    the first of the next."""
    runs = module_runs(events, plane, pattern)
    out = []
    for (a0, b0), (a1, b1) in zip(runs, runs[1:]):
        inside0 = [e for e in ops(events, plane)
                   if a0 <= e["start"] < b0]
        inside1 = [e for e in ops(events, plane)
                   if a1 <= e["start"] < b1]
        if not inside0 or not inside1:
            continue
        last = max(e["start"] + e["dur"] for e in inside0)
        first = min(e["start"] for e in inside1)
        out.append(max(first - last, 0.0))
    return out


def exposed_seconds(events, plane: str, pattern: str) -> float:
    """Seconds inside events whose name matches (the collectives) during
    which no other instruction runs on that device."""
    rx = re.compile(pattern)
    mine = ops(events, plane)
    coll = union(spans([e for e in mine if rx.search(e["name"])]))
    rest = union(spans([e for e in mine if not rx.search(e["name"])]))
    return total(subtract(coll, rest))


def top_ops(events, planes, n: int = 10) -> list:
    """``[name, seconds]`` of the instructions that took most device time,
    numbered instances of one instruction (``fusion.12``) kept apart,
    averaged over the devices."""
    acc: dict = {}
    for plane in planes:
        for e in ops(events, plane):
            acc[e["name"]] = acc.get(e["name"], 0.0) + e["dur"]
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec / max(len(planes), 1)] for name, sec in top]


def label_gaps(idle, host_events, names, n: int = 10) -> list:
    """``[label, seconds]`` for the ``n`` longest idle intervals: the host
    span of ``names`` that covers most of the interval, or ``untraced``."""
    host = [e for e in host_events if e["name"] in names]
    out = []
    for a, b in idle[:n]:
        best, cover = "untraced", 0.0
        for e in host:
            c = min(b, e["start"] + e["dur"]) - max(a, e["start"])
            if c > cover:
                best, cover = e["name"], c
        out.append([best, b - a])
    return out


def context(events, *, hlo_text: str, module: str, host_spans,
            window_span: str = "window", scopes=None) -> dict:
    """What the readers are given: the executed instructions of each
    device inside the traced window, each with its ``scope``; the window
    on the profile's clock, which is the host span ``window_span`` (or,
    without one, from the first device event to the last); the runs of the
    compiled program ``module``; busy seconds averaged over the devices;
    and the breakdown for the result line."""
    planes = device_planes(events)
    if not planes:
        raise RuntimeError("the capture holds no device plane")
    host = [e for e in events if not _DEVICE_PLANE.match(e["plane"])]
    marks = [e for e in host if e["name"] == window_span]
    if marks:
        lo = min(e["start"] for e in marks)
        hi = max(e["start"] + e["dur"] for e in marks)
    else:
        on_device = [e for p in planes for e in ops(events, p)]
        lo = min(e["start"] for e in on_device)
        hi = max(e["start"] + e["dur"] for e in on_device)
    inside = clip(events, lo, hi)
    scopes = hlo_scopes(hlo_text) if scopes is None else scopes
    by_plane = {}
    for p in planes:
        mine = ops(inside, p)
        for e in mine:
            e["scope"] = scope_of(e, scopes)
        by_plane[p] = mine
    busy = [total(union(spans(by_plane[p]))) for p in planes]
    idle = subtract([(lo, hi)], union(spans(by_plane[planes[0]])))
    idle.sort(key=lambda ab: ab[0] - ab[1])
    return {
        "events": inside, "planes": planes, "ops": by_plane,
        "lo": lo, "hi": hi, "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy), "module": module,
        "runs": {p: module_runs(inside, p, module) for p in planes},
        "breakdown": {
            "device_ops": top_ops(inside, planes),
            "idle_gaps": label_gaps(idle, host, host_spans)},
    }

"""The comparisons that decide ``correct``: what the timed path produced
against what the plain reference gives, each number beside a limit that
``limits/<cell>.json`` holds. ``PERF.md`` gives the readings every limit
was set from."""

from __future__ import annotations

import re

import numpy as np

#: a leaf whose gradient in the reference is under this share of the median
#: leaf's moves under Adam or LAMB by round-off alone: left out of the
#: comparison of the weights' change
DEAD_GRADIENT = 1e-3


def _flat(norms: dict):
    names, values = [], []
    for path in sorted(norms):
        arr = np.asarray(norms[path], np.float64).reshape(-1)
        for i, v in enumerate(arr):
            names.append(path if arr.size == 1 else f"{path}[{i}]")
            values.append(v)
    return names, np.asarray(values)


def norm_gaps(prog: dict, ref: dict, keep=None):
    """Leaf by leaf, the gap between the program's norm and the
    reference's (not the norm of their difference), against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger. Returns ``(names, gaps)`` of the leaves kept."""
    names, r = _flat(ref)
    names_p, p = _flat(prog)
    if names != names_p:
        raise ValueError("the program's leaves are not the reference's: "
                         f"{sorted(set(names) ^ set(names_p))[:6]}")
    gaps = np.abs(p - r) / np.maximum(r, np.median(r))
    if keep is not None:
        names = [n for n, k in zip(names, keep) if k]
        gaps = gaps[keep]
    return names, gaps


def sample_gaps(prog: dict, ref: dict):
    """Group by group (a leaf, or a layer and fused part of one), the root
    mean square of the difference between the two samples of the first
    gradient, against that of the reference's sample of the group or of
    the median group, whichever is larger. Rounding noise, which no norm
    sees, shows here: it is what tells bf16 from a lower precision."""
    names, gaps = [], []
    rms = lambda a: np.sqrt(np.mean(np.square(a), axis=-1))
    scale = np.median(np.concatenate(
        [rms(np.asarray(ref[k], np.float64)) for k in sorted(ref)]))
    for path in sorted(ref):
        r = np.asarray(ref[path], np.float64)
        p = np.asarray(prog[path], np.float64)
        g = rms(p - r) / np.maximum(rms(r), scale)
        names += [path if g.size == 1 else f"{path}[{i}]"
                  for i in range(g.size)]
        gaps.append(g)
    return names, np.concatenate(gaps)


def whole_difference(prog: dict, ref: dict, sizes: dict):
    """``(names, norms)``: part by part, the norm of the difference between
    the two first gradients as the samples estimate it: the root mean
    square of the sampled differences times the root of the part's size."""
    names, norms = [], []
    for path in sorted(ref):
        d = np.asarray(prog[path], np.float64) \
            - np.asarray(ref[path], np.float64)
        e = np.sqrt(np.mean(np.square(d), axis=-1) * sizes[path])
        names += [path if e.size == 1 else f"{path}[{i}]"
                  for i in range(e.size)]
        norms.append(e)
    return names, np.concatenate(norms)


def train_readings(prog: dict, ref: dict, part_groups=None) -> dict:
    """Every number a training cell compares, by name, with the leaf that
    read worst beside the two norms. ``part_groups`` (``{group: pattern}``,
    from the cell's limits file) is for a cell in which part of the loss
    rests on a few labels, whose gradient comes and goes with the seed and
    takes the noise of every part that it reaches with it. It adds, for the
    parts whose name matches (those that this loss does not reach):
    ``grad_sample_diff.<group>``, the worst sample gap among them, and
    ``grad_diff_over.<group>``, the norm of the whole first gradient's
    difference (``whole_difference``) over the norm of the reference's
    gradient of the group, which scales with the rest of the loss."""
    out = {}
    for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out[f"loss_step{i}"] = abs(float(p) - float(r)) / abs(float(r))
    names, gaps = norm_gaps(prog["grad_norms"], ref["grad_norms"])
    out["grad_norm_gap"] = float(gaps.max())
    out["grad_norm_gap_leaf"] = names[int(gaps.argmax())]
    out["grad_norm_gap_median"] = float(np.median(gaps))
    names, gaps = sample_gaps(prog["grad_sample"], ref["grad_sample"])
    out["grad_sample_diff"] = float(gaps.max())
    out["grad_sample_diff_leaf"] = names[int(gaps.argmax())]
    out["grad_sample_diff_median"] = float(np.median(gaps))
    if part_groups:
        _, diff = whole_difference(prog["grad_sample"], ref["grad_sample"],
                                   ref["part_sizes"])
        _, norms = _flat(ref["grad_norms"])
    for group, pattern in (part_groups or {}).items():
        inside = np.asarray([bool(re.search(pattern, n)) for n in names])
        out[f"grad_sample_diff.{group}"] = float(gaps[inside].max())
        out[f"grad_diff_over.{group}"] = float(
            np.linalg.norm(diff) / np.linalg.norm(norms[inside]))
    _, g = _flat(ref["grad_norms"])
    alive = g >= DEAD_GRADIENT * np.median(g)
    names, gaps = norm_gaps(prog["update_norms"], ref["update_norms"],
                            keep=alive)
    out["update_norm_gap"] = float(gaps.max())
    out["update_norm_gap_leaf"] = names[int(gaps.argmax())]
    out["update_norm_gap_median"] = float(np.median(gaps))
    out["leaves_left_out"] = int((~alive).sum())
    return out


def checks(readings: dict, limits: dict) -> list:
    """``[{"name", "value", "limit"}]`` for each number the cell's limits
    file holds."""
    return [{"name": name, "value": float(readings[name]),
             "limit": float(limit)} for name, limit in limits.items()]

"""Cost-model calibration: predicted-vs-measured joins + fitted peaks.

The repo predicts a run (static-hbm peak bytes, comm bytes per verb,
``tracing.expected_bubble_fraction`` floors, pyprof FLOPs → modeled step
seconds) and measures one (journal → ``report.analyze``); the run ledger
(``monitor/ledger.py``) persists both blocks per completed run. This
module closes the loop:

- :func:`join` — per-record error ratios (measured / predicted) for each
  model: ``hbm_ratio`` (measured peak live bytes over the static
  estimate), ``bubble_ratio`` (measured bubble fraction over the
  analytic floor), ``comm_ratio`` (booked collective bytes over the
  static census), ``wall_ratio`` (measured step seconds over the
  modeled compute+wire seconds).
- :func:`fit` — effective peak constants from many records: the peak
  FLOP/s and ICI GB/s that make the cost model's compute/comm seconds
  meet the measured walls — exactly the denominators
  ``mfu.peak_spec``/``tracing.ici_spec`` consume today via the
  ``APEX_TPU_PEAK_*`` env knobs, fitted instead of hand-set.
- :func:`save`/:func:`load`/:func:`active` — the calibration file.
  Arming is explicit: set ``APEX_TPU_CALIBRATION=<path>`` (or pass the
  file to a consumer) and ``peak_spec``/``ici_spec`` resolve their
  constants from it with ``source="calibrated"``. **When armed, the
  file takes precedence over the ``APEX_TPU_PEAK_*`` env overrides**
  (a fitted constant from real measurements outranks a hand-typed one);
  when the env var is unset nothing changes — disarmed programs and
  their journals stay byte-identical.

Pure host-side stdlib (+ ``utils/io`` for the atomic write): no jax
import, safe inside ``peak_spec`` on any platform.

No reference-file citation: NVIDIA Apex has no cost-model layer; this
is the calibration substrate the auto-parallelism planner reads from.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Sequence

ENV_CALIBRATION = "APEX_TPU_CALIBRATION"

SCHEMA_VERSION = 1

#: keys a calibration file may carry, all optional: peak FLOP/s, ICI
#: bytes/s and HBM bytes/s denominators (absolute units, not GB/s).
FITTED_KEYS = ("peak_flops", "peak_ici_bytes_per_sec",
               "peak_hbm_bytes_per_sec")

# one-entry (path, mtime) cache: peak_spec may resolve once per journal
# record arming; re-stat instead of re-parse when the file is unchanged
_CACHE: Dict[str, Any] = {}


def _median(vals: List[float]) -> Optional[float]:
    s = sorted(v for v in vals if isinstance(v, (int, float)) and v > 0)
    if not s:
        return None
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


# ---------------------------------------------------------------------------
# the calibration file
# ---------------------------------------------------------------------------


def save(path: str, calibration: Dict[str, Any]) -> str:
    """Atomically write a calibration file (``utils/io`` discipline —
    a torn calibration would silently poison every later denominator)."""
    from apex_tpu.utils.io import atomic_write_json

    out = {"v": SCHEMA_VERSION}
    out.update(calibration)
    return atomic_write_json(path, out)


def load(path: str) -> Optional[Dict[str, Any]]:
    """Read a calibration file; None on a missing/corrupt/alien file
    (a consumer must degrade to its table row, never crash)."""
    try:
        with open(path) as f:
            obj = json.load(f)
    except Exception:  # noqa: BLE001 - degrade to the table row
        return None
    if not isinstance(obj, dict):
        return None
    if not any(isinstance(obj.get(k), (int, float)) and obj[k] > 0
               for k in FITTED_KEYS):
        return None
    return obj


def active() -> Optional[Dict[str, Any]]:
    """The armed calibration: the ``APEX_TPU_CALIBRATION`` file when the
    env var is set and the file parses, else None. Cached by (path,
    mtime) so per-record consumers don't re-parse an unchanged file."""
    path = os.environ.get(ENV_CALIBRATION)
    if not path:
        return None
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return None
    if _CACHE.get("path") == path and _CACHE.get("mtime") == mtime:
        return _CACHE.get("cal")
    cal = load(path)
    _CACHE.update(path=path, mtime=mtime, cal=cal)
    return cal


# ---------------------------------------------------------------------------
# predicted-vs-measured joins
# ---------------------------------------------------------------------------


def _measured_wall_s(measured: Dict[str, Any]) -> Optional[float]:
    w = (measured.get("wall_s") or {}).get("p50")
    return float(w) if isinstance(w, (int, float)) and w > 0 else None


def _booked_comm_bytes(measured: Dict[str, Any]) -> Optional[float]:
    total = 0.0
    seen = False
    # by_verb_dtype is the finer booking; fall back to the axis rollup
    for key in ("comm_bytes_by_verb_dtype", "comm_bytes_by_axis"):
        table = measured.get(key)
        if isinstance(table, dict) and table:
            for row in table.values():
                if isinstance(row, dict) and isinstance(
                        row.get("bytes"), (int, float)):
                    total += row["bytes"]
                    seen = True
            break
    return total if seen else None


def join(record: Dict[str, Any]) -> Dict[str, Any]:
    """Per-record error ratios: each is measured / predicted, so 1.0 is a
    perfect model, 2.0 means the measurement is twice the prediction.
    Ratios are emitted only when both sides carry the signal."""
    measured = record.get("measured") or {}
    predicted = record.get("predicted") or {}
    out: Dict[str, Any] = {"fingerprint": record.get("fingerprint"),
                           "run": record.get("run"), "ts": record.get("ts")}

    # hbm: measured peak live bytes vs the static-hbm pass estimate
    peak = (measured.get("hbm") or {}).get("peak_bytes")
    est = predicted.get("hbm_peak_bytes")
    if isinstance(peak, (int, float)) and isinstance(est, (int, float)) \
            and est > 0:
        out["hbm_ratio"] = round(peak / est, 4)

    # bubble: measured pipeline bubble fraction vs the analytic floor
    bub = ((measured.get("timeline") or {}).get("bubble_fraction")
           or {}).get("p50")
    floor = predicted.get("bubble_floor")
    if isinstance(bub, (int, float)) and isinstance(floor, (int, float)) \
            and floor > 0:
        out["bubble_ratio"] = round(bub / floor, 4)

    # comm: booked collective bytes (CommAccount tables riding the
    # journal) vs the static per-step census
    booked = _booked_comm_bytes(measured)
    static = predicted.get("comm_bytes_per_step")
    if booked is not None and isinstance(static, (int, float)) and static > 0:
        out["comm_ratio"] = round(booked / static, 4)

    # wall: measured p50 step seconds vs the modeled compute+wire seconds
    wall = _measured_wall_s(measured)
    modeled = predicted.get("modeled_step_s")
    if wall is not None and isinstance(modeled, (int, float)) and modeled > 0:
        out["wall_ratio"] = round(wall / modeled, 4)
    return out


def summarize(records: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Roll per-record joins up per fingerprint: median of each ratio
    plus the record count — the trend view ``ledger calibrate`` prints."""
    by_fp: Dict[str, List[Dict[str, Any]]] = {}
    for rec in records:
        if rec.get("kind") != "run":
            continue
        j = join(rec)
        by_fp.setdefault(str(j.get("fingerprint")), []).append(j)
    out: Dict[str, Any] = {}
    for fp, joins in by_fp.items():
        row: Dict[str, Any] = {"records": len(joins),
                               "run": joins[-1].get("run")}
        for key in ("hbm_ratio", "bubble_ratio", "comm_ratio", "wall_ratio"):
            med = _median([j.get(key) for j in joins
                           if isinstance(j.get(key), (int, float))])
            if med is not None:
                row[key] = round(med, 4)
        out[fp] = row
    return out


# ---------------------------------------------------------------------------
# fitting the effective peaks
# ---------------------------------------------------------------------------


def fit(records: Sequence[Dict[str, Any]],
        *, min_comm_frac: float = 0.05) -> Dict[str, Any]:
    """Fit effective peak constants from run records.

    - ``peak_flops``: the median achieved FLOP/s
      (``predicted.flops_per_step / measured wall p50``) — the ceiling
      under which the cost model's compute seconds equal the measured
      wall for compute-bound runs.
    - ``peak_ici_bytes_per_sec``: the median of booked-or-predicted comm
      bytes over the non-compute residual of the wall (clamped to at
      least ``min_comm_frac`` of the wall so a compute-saturated record
      can't fit an infinite wire).
    - ``peak_hbm_bytes_per_sec``: the median achieved bytes/s when
      records carry ``predicted.bytes_per_step`` (jaxpr operand+result
      totals — a pre-fusion upper bound, flagged by the journal's
      ``mfu_method``).

    Returns the calibration dict (:func:`save`-ready) with ``n_records``
    per constant; constants without enough signal are omitted.
    """
    ach_flops: List[float] = []
    ach_ici: List[float] = []
    ach_hbm: List[float] = []
    for rec in records:
        if rec.get("kind") != "run":
            continue
        measured = rec.get("measured") or {}
        predicted = rec.get("predicted") or {}
        wall = _measured_wall_s(measured)
        if wall is None:
            continue
        flops = predicted.get("flops_per_step")
        eff_f = None
        if isinstance(flops, (int, float)) and flops > 0:
            eff_f = flops / wall
            ach_flops.append(eff_f)
        nbytes = predicted.get("bytes_per_step")
        if isinstance(nbytes, (int, float)) and nbytes > 0:
            ach_hbm.append(nbytes / wall)
        comm = _booked_comm_bytes(measured)
        if comm is None:
            comm = predicted.get("comm_bytes_per_step")
        if isinstance(comm, (int, float)) and comm > 0:
            # attribute the non-compute residual of the wall to the wire;
            # the clamp keeps a compute-saturated step from dividing by ~0
            residual = wall
            if eff_f is not None and ach_flops:
                compute_s = flops / max(ach_flops[-1], 1e-30)
                residual = max(wall - compute_s, min_comm_frac * wall)
            ach_ici.append(comm / residual)
    out: Dict[str, Any] = {"source": "calibrated",
                           "n_records": {}}
    f = _median(ach_flops)
    if f is not None:
        out["peak_flops"] = round(f, 1)
        out["n_records"]["peak_flops"] = len(ach_flops)
    i = _median(ach_ici)
    if i is not None:
        out["peak_ici_bytes_per_sec"] = round(i, 1)
        out["n_records"]["peak_ici_bytes_per_sec"] = len(ach_ici)
    h = _median(ach_hbm)
    if h is not None:
        out["peak_hbm_bytes_per_sec"] = round(h, 1)
        out["n_records"]["peak_hbm_bytes_per_sec"] = len(ach_hbm)
    return out

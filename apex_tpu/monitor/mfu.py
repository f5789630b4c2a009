"""MFU / roofline reporting: achieved vs peak FLOP/s and HBM bandwidth.

The journal (``monitor/journal.py``) records what a step DID (tokens/s,
wall time); this module records what the chip COULD have done, so every
journal window carries a utilization verdict instead of a raw rate:

- ``mfu``: achieved FLOP/s over the platform's peak — the model-FLOPs
  utilization number veScale-style eager-SPMD systems report per step
  (PAPERS.md, arxiv 2509.07003) and PERF_NOTES argues by hand for the
  345M headline (17.4 TFLOP / 257.7 ms = 67.5 TF/s).
- ``hbm_bw_util``: achieved bytes/s over peak HBM bandwidth.
- ``bound``: the roofline verdict — whichever of the two time floors
  (flops/peak_flops vs bytes/peak_bw) dominates is what the step is
  limited by; ties within 10% report ``"balanced"``.

FLOPs/bytes come from the pyprof cost layer (``pyprof.cost_analysis`` /
``per_scope_costs``): :func:`compiled_step_costs` reads the XLA cost
model off a compiled executable (taking ``max`` with the jaxpr count
when given — the cost model sees zero FLOPs inside Pallas custom-calls,
pyprof.profile_fn's documented undercount), and :func:`traced_step_costs`
needs only a trace (no compile) — its bytes are algorithmic
operand+result sizes (pre-fusion upper bound), flagged by ``method``.

Peak specs: a small per-platform table (public bf16 peak / HBM BW per
TPU generation), overridable via ``APEX_TPU_PEAK_FLOPS`` /
``APEX_TPU_PEAK_HBM_GBPS``. Every record names its spec ``source`` so an
env-overridden mfu is never confused with a datasheet one. A device with
no row in the table is an error, not a default.

All host-side and trace-time only: nothing here touches the hot path,
and programs compiled with reporting disabled are byte-identical.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

ENV_PEAK_FLOPS = "APEX_TPU_PEAK_FLOPS"
ENV_PEAK_HBM_GBPS = "APEX_TPU_PEAK_HBM_GBPS"

#: platform substring -> (peak bf16 FLOP/s, peak HBM bytes/s). Public
#: datasheet numbers; matched case-insensitively against device_kind so
#: "TPU v5 lite" and "tpu v5e" both land on the v5e row.
PEAK_SPECS = {
    "v6e": (918e12, 1640e9),
    "v6": (918e12, 1640e9),
    "v5p": (459e12, 2765e9),
    "v5e": (197e12, 819e9),
    "v5 lite": (197e12, 819e9),
    "v4": (275e12, 1228e9),
    "v3": (123e12, 900e9),
    "v2": (45e12, 700e9),
    # CPU rows exist so the virtual-mesh CI path produces *labelled*
    # numbers (source="table:cpu") rather than crashing; they are
    # order-of-magnitude host figures, not measurements.
    "cpu": (2e11, 50e9),
}


def _detect_platform() -> str:
    try:
        import jax

        dev = jax.devices()[0]
        kind = getattr(dev, "device_kind", "") or ""
        return f"{dev.platform} {kind}".strip()
    except Exception:  # noqa: BLE001 - no backend: stay host-side
        return "unknown"


def peak_spec(platform: Optional[str] = None) -> Dict[str, Any]:
    """Resolve ``{platform, peak_flops, peak_hbm_bytes_per_sec, source}``.

    The table row whose key is a substring of the platform string;
    env overrides (``APEX_TPU_PEAK_FLOPS`` in FLOP/s,
    ``APEX_TPU_PEAK_HBM_GBPS`` in decimal GB/s) replace its numbers. A
    platform with no row raises ``ValueError``: pricing an unknown
    accelerator as some other chip yields a utilization of nothing.
    """
    plat = (platform or _detect_platform()).lower()
    flops, bw, source = None, None, None
    for key, (f, b) in PEAK_SPECS.items():
        if key in plat:
            flops, bw, source = f, b, f"table:{key}"
            break
    if flops is None:
        raise ValueError(
            f"no peak spec for device {plat!r}; add its published peaks "
            f"to PEAK_SPECS (known: {sorted(PEAK_SPECS)})")
    # per-knob overrides with per-knob provenance: overriding only the
    # FLOP ceiling must not stamp the datasheet HBM number "env" (and a
    # malformed value in one knob must not discard the other's)
    src_f = src_b = source
    try:
        env_f = os.environ.get(ENV_PEAK_FLOPS)
        if env_f:
            flops, src_f = float(env_f), "env"
    except ValueError:
        pass  # malformed override: keep the table row
    try:
        env_b = os.environ.get(ENV_PEAK_HBM_GBPS)
        if env_b:
            bw, src_b = float(env_b) * 1e9, "env"
    except ValueError:
        pass
    # an armed calibration file (APEX_TPU_CALIBRATION) outranks the env
    # knobs: a constant fitted from this machine's measured runs beats a
    # hand-typed one. Disarmed (env var unset): nothing changes.
    try:
        from apex_tpu.monitor import calibrate as _calibrate

        cal = _calibrate.active()
    except Exception:  # noqa: BLE001 - calibration is best-effort
        cal = None
    if cal:
        cf = cal.get("peak_flops")
        if isinstance(cf, (int, float)) and cf > 0:
            flops, src_f = float(cf), "calibrated"
        cb = cal.get("peak_hbm_bytes_per_sec")
        if isinstance(cb, (int, float)) and cb > 0:
            bw, src_b = float(cb), "calibrated"
    source = src_f if src_f == src_b else f"flops:{src_f}|hbm:{src_b}"
    return {"platform": plat, "peak_flops": flops,
            "peak_hbm_bytes_per_sec": bw, "source": source}


def modeled_compute_seconds(
    flops: float,
    *,
    spec: Optional[Dict[str, Any]] = None,
    platform: Optional[str] = None,
) -> float:
    """Compute-time floor of ``flops`` against the resolved peak spec.

    The planner's (``apex_tpu.plan``) compute leg: honors the same
    calibrated > env > table precedence as :func:`peak_spec`,
    so an armed ``APEX_TPU_CALIBRATION`` file closes the
    predicted-vs-measured loop with no planner-side knobs. Returns
    ``inf`` when the spec resolves no FLOP ceiling (nothing to divide
    by — an infeasible time floor, never a silent 0).
    """
    spec = spec or peak_spec(platform)
    pf = spec.get("peak_flops") or 0.0
    return float(flops) / pf if pf > 0 else float("inf")


def mfu_metrics(
    *,
    flops: float,
    bytes_accessed: float,
    wall_s: float,
    tokens: Optional[int] = None,
    platform: Optional[str] = None,
    spec: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Join one step's cost-model totals with its measured wall time.

    Returns the journal-ready fields: ``mfu``, ``hbm_bw_util``,
    ``bound`` (``"compute"`` / ``"memory"`` / ``"balanced"``), achieved
    TFLOP/s, the roofline's ridge intensity, and the peak-spec
    provenance. ``flops``/``bytes_accessed`` are per
    executed region (multiply per-step costs by the step count yourself
    when timing multi-step windows).
    """
    spec = spec or peak_spec(platform)
    out: Dict[str, Any] = {"peak_source": spec["source"]}
    if wall_s <= 0:
        return out
    ach_f = flops / wall_s
    ach_b = bytes_accessed / wall_s
    out["achieved_tflops"] = round(ach_f / 1e12, 4)
    pf, pb = spec["peak_flops"], spec["peak_hbm_bytes_per_sec"]
    if pf:
        out["mfu"] = round(ach_f / pf, 4)
    if pb:
        out["hbm_bw_util"] = round(ach_b / pb, 4)
    if pf and pb:
        # roofline: each resource imposes a time floor; the larger floor
        # is the binding constraint for this step's cost totals
        t_compute = flops / pf
        t_memory = bytes_accessed / pb
        floor = max(t_compute, t_memory)
        if floor > 0:
            if abs(t_compute - t_memory) <= 0.1 * floor:
                out["bound"] = "balanced"
            else:
                out["bound"] = "compute" if t_compute > t_memory else "memory"
        if bytes_accessed > 0:
            out["ridge_intensity"] = round(pf / pb, 2)
    if tokens and flops:
        out["flops_per_token"] = round(flops / tokens, 1)
    return out


# ---------------------------------------------------------------------------
# step-cost extraction (the pyprof join)
# ---------------------------------------------------------------------------


def traced_step_costs(fn, *args, **kwargs) -> Dict[str, Any]:
    """FLOPs/bytes of ``fn(*args)`` from a trace only (no compile).

    Uses ``pyprof.per_scope_costs``'s jaxpr walk: FLOPs follow the
    reference handler table (GEMM shape arithmetic etc.); bytes are
    algorithmic operand+result sizes — an upper bound on HBM traffic
    (pre-fusion), so ``hbm_bw_util`` from this path overstates. Cheap
    enough to run once per prepared config when a journal is armed.
    """
    from apex_tpu.pyprof.prof import per_scope_costs

    total = per_scope_costs(fn, *args, **kwargs)["<total>"]
    return {"flops": float(total["flops"]), "bytes": float(total["bytes"]),
            "method": "jaxpr"}


def compiled_step_costs(compiled, *, jaxpr_flops: float = 0.0) -> Dict[str, Any]:
    """FLOPs/bytes off a compiled executable's XLA cost model.

    ``jaxpr_flops`` (from :func:`traced_step_costs` or
    ``pyprof._walk_flops_only``) guards the Pallas undercount: the cost
    model reports zero FLOPs inside custom-calls, so the larger of the
    two counts wins (same policy as ``pyprof.profile_fn``).
    """
    analysis = compiled.cost_analysis()
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0]
    analysis = dict(analysis)
    cm = float(analysis.get("flops", 0.0))
    flops = max(cm, float(jaxpr_flops or 0.0))
    return {
        "flops": flops,
        "bytes": float(analysis.get("bytes accessed", 0.0)),
        "method": "cost_model" if flops == cm else "cost_model+jaxpr",
    }

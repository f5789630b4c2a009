"""Journal analysis + regression CLI: ``python -m apex_tpu.monitor.report``.

The judgment layer over ``MetricsJournal`` files — an operator (or the
driver) asks one question per mode:

- ``report <run.jsonl>``: is this run healthy? Prints throughput
  percentiles, stall gaps (wall-clock holes between step records — the
  wedged-device / contended-chip signature), the loss-spike list,
  HBM-growth trend (the below-Python leak detector's journal-side view),
  per-rank straggler skew, comm-bytes-per-axis rollup, MFU summary, and
  recompile/forensics rollups.
- ``compare <A.jsonl> <B.jsonl> [--threshold 0.05]``: did B regress
  against A? Exits non-zero on regression so the bench trajectory gets a
  machine gate instead of a human eyeballing two JSON lines.

Pure stdlib + host-side: no jax import, runs anywhere (including the
off-TPU CI that produced the journal on a virtual mesh). Input is
whatever ``MetricsJournal`` wrote — bench windows, ``pretrain_gpt.py
--journal`` steps, scaling-harness rows — including crash-truncated
files (``MetricsJournal.read`` tolerates a torn final line).

No reference-file citation: NVIDIA Apex has no journal/analysis layer;
this is the evidence-discipline extension (PERF_NOTES instrumentation
note) the ISSUE's diagnostics engine closes.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from typing import Any, Dict, List, Optional, Sequence

# stdlib-only sibling: the shared spike predicate / median keep the
# offline rollups here in lockstep with the online forensics triggers
from apex_tpu.monitor.diagnose import is_loss_spike, median as _median


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    if len(sorted_vals) == 1:
        return sorted_vals[0]
    pos = q * (len(sorted_vals) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = pos - lo
    return sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac


def _dist(vals: List[float]) -> Dict[str, float]:
    s = sorted(v for v in vals if v is not None)
    if not s:
        return {}
    return {"p10": round(_percentile(s, 0.10), 3),
            "p50": round(_percentile(s, 0.50), 3),
            "p90": round(_percentile(s, 0.90), 3),
            "min": round(s[0], 3), "max": round(s[-1], 3), "n": len(s)}


def _dist_tail(vals: List[float]) -> Dict[str, float]:
    """:func:`_dist` plus the p99 tail — latency-shaped metrics (serving
    TTFT/ITL), where the tail IS the product claim."""
    s = sorted(v for v in vals if v is not None)
    if not s:
        return {}
    out = _dist(vals)
    out["p99"] = round(_percentile(s, 0.99), 3)
    return out


def attribution_rollup(rows: Sequence[Any]) -> Dict[str, Any]:
    """Aggregate per-request ``attribution`` dicts (the reqtrace shape:
    ``{"ttft": {...}, "itl": {...}}`` fraction dicts, each summing to
    1.0) into one wall-weighted rollup per class whose fractions STILL
    sum to 1.0 — the last sorted key absorbs the rounding residue, the
    same discipline :func:`apex_tpu.serve.reqtrace.attribution_fractions`
    applies per request. Lives here (not in serve) so journal analysis
    stays jax-free."""
    out: Dict[str, Any] = {}
    for cls in ("ttft", "itl"):
        frs = [(r.get(cls) or {}) for r in rows if isinstance(r, dict)]
        frs = [f for f in frs
               if isinstance(f.get("wall_s"), (int, float))
               and f["wall_s"] > 0]
        if not frs:
            continue
        walls = [float(f["wall_s"]) for f in frs]
        keys = sorted({k for f in frs for k in f if k.endswith("_frac")})
        if not keys:
            continue
        sums = {k: sum(float(f.get(k) or 0.0) * w
                       for f, w in zip(frs, walls)) for k in keys}
        norm = sum(sums.values()) or 1.0
        row: Dict[str, Any] = {
            "n": len(frs),
            "wall_s_mean": round(sum(walls) / len(walls), 6),
        }
        acc = 0.0
        for k in keys[:-1]:
            v = round(sums[k] / norm, 4)
            row[k] = v
            acc += v
        row[keys[-1]] = round(max(1.0 - acc, 0.0), 4)
        out[cls] = row
    return out


def _lstsq_slope(ys: List[float]) -> float:
    """Least-squares slope of ys over their indices (trend per record)."""
    n = len(ys)
    if n < 2:
        return 0.0
    xm = (n - 1) / 2.0
    ym = sum(ys) / n
    num = sum((i - xm) * (y - ym) for i, y in enumerate(ys))
    den = sum((i - xm) ** 2 for i in range(n))
    return num / den if den else 0.0


def load(path: str) -> List[Dict[str, Any]]:
    from apex_tpu.monitor.journal import MetricsJournal

    return MetricsJournal.read(path)


# ---------------------------------------------------------------------------
# analysis
# ---------------------------------------------------------------------------


def analyze(
    records: Sequence[Dict[str, Any]],
    *,
    stall_factor: float = 5.0,
    spike_factor: float = 3.0,
    spike_window: int = 16,
    max_list: int = 20,
) -> Dict[str, Any]:
    """Roll a journal up into the operator-facing health summary."""
    steps = [r for r in records if r.get("kind") == "step"]
    out: Dict[str, Any] = {
        "records": len(records),
        "step_records": len(steps),
        "truncated": bool(getattr(records, "truncated", False)),
        "bad_lines": int(getattr(records, "bad_lines", 0)),
    }
    meta = next((r for r in records if r.get("kind") == "meta"), None)
    if meta:
        out["meta"] = {k: v for k, v in meta.items()
                       if k not in ("v", "kind", "ts", "rank", "rank_info")}

    # throughput / wall-time percentiles
    rates = [r["tokens_per_sec"] for r in steps
             if isinstance(r.get("tokens_per_sec"), (int, float))]
    walls = [r["wall_s"] for r in steps
             if isinstance(r.get("wall_s"), (int, float))]
    if rates:
        out["tokens_per_sec"] = _dist(rates)
    if walls:
        out["wall_s"] = _dist(walls)

    # stall gaps: holes between consecutive step timestamps well beyond
    # the median cadence — the journal-side wedge/co-tenant signature
    ts = [(r.get("step", r.get("window")), r["ts"]) for r in steps
          if isinstance(r.get("ts"), (int, float))]
    gaps = [b[1] - a[1] for a, b in zip(ts, ts[1:])]
    med_gap = _median(gaps)
    stalls = []
    if med_gap and med_gap > 0:
        for (label, _), gap in zip(ts, gaps):
            if gap > stall_factor * med_gap:
                stalls.append({"after_step": label, "gap_s": round(gap, 3),
                               "x_median": round(gap / med_gap, 1)})
    out["stalls"] = {"median_cadence_s": round(med_gap, 3) if med_gap else None,
                     "count": len(stalls), "gaps": stalls[:max_list]}

    # loss spikes: rolling prior-window median baseline (same trigger
    # logic as diagnose.OverflowForensics), plus sanitized-NaN losses
    spikes, nonfinite = [], []
    history: List[float] = []
    for r in steps:
        label = r.get("step", r.get("window"))
        keys = r.get("nonfinite_keys") or []
        if any(k == "loss" or k.endswith(".loss") for k in keys):
            nonfinite.append(label)
            continue
        if r.get("found_inf"):
            # overflow steps never enter the spike baseline or spike
            # list — matching OverflowForensics, whose found_inf branch
            # wins over (and excludes the loss from) the spike trigger
            continue
        loss = r.get("loss")
        if not isinstance(loss, (int, float)):
            continue
        base = (_median(history[-spike_window:])
                if len(history) >= 4 else None)
        if base is not None and is_loss_spike(loss, base, spike_factor):
            spikes.append({"step": label, "loss": round(loss, 4),
                           "baseline": round(base, 4)})
        # spiked losses still enter the rolling baseline (matching
        # OverflowForensics): a sustained level shift flags a few steps
        # while the median catches up, then self-heals — it must not
        # brand every remaining step a spike
        history.append(loss)
    losses = [r["loss"] for r in steps
              if isinstance(r.get("loss"), (int, float))]
    out["loss"] = {
        "first": round(losses[0], 4) if losses else None,
        "last": round(losses[-1], 4) if losses else None,
        "spikes": spikes[:max_list], "spike_count": len(spikes),
        "nonfinite_steps": nonfinite[:max_list],
        "nonfinite_count": len(nonfinite),
    }

    # HBM trend: samples ride step records ("hbm" sub-dict) and
    # standalone kind="hbm" rows (HBMMonitor.sample)
    hbm = []
    for r in records:
        if r.get("kind") == "hbm" and isinstance(r.get("live_bytes"), (int, float)):
            hbm.append(r["live_bytes"])
        elif isinstance(r.get("hbm"), dict) and isinstance(
                r["hbm"].get("live_bytes"), (int, float)):
            hbm.append(r["hbm"]["live_bytes"])
    if hbm:
        out["hbm"] = {
            "samples": len(hbm),
            "first_bytes": int(hbm[0]), "last_bytes": int(hbm[-1]),
            "peak_bytes": int(max(hbm)),
            "growth_bytes": int(hbm[-1] - hbm[0]),
            "trend_bytes_per_sample": round(_lstsq_slope(hbm), 1),
        }

    # per-rank straggler skew: a rank whose median rate trails the
    # fastest marks the straggler (MPMD pipeline telemetry)
    by_rank: Dict[Any, List[float]] = {}
    for r in steps:
        if isinstance(r.get("tokens_per_sec"), (int, float)):
            by_rank.setdefault(r.get("rank", 0), []).append(r["tokens_per_sec"])
    if by_rank:
        rank_med = {rk: _median(v) for rk, v in by_rank.items()}
        fastest = max(rank_med.values())
        slowest_rank = min(rank_med, key=lambda rk: rank_med[rk])
        out["ranks"] = {
            "count": len(rank_med),
            "median_tokens_per_sec": {str(k): round(v, 1)
                                      for k, v in sorted(rank_med.items())},
            "straggler_rank": slowest_rank,
            "skew": (round(fastest / rank_med[slowest_rank], 3)
                     if rank_med[slowest_rank] else None),
        }

    # comm-bytes-per-axis rollup (rows carrying comm_bytes_by_axis —
    # scaling-harness configs, or meta records)
    comm: Dict[str, Dict[str, int]] = {}
    for r in records:
        table = r.get("comm_bytes_by_axis")
        if not isinstance(table, dict):
            continue
        for axis, row in table.items():
            agg = comm.setdefault(axis, {"bytes": 0, "calls": 0})
            agg["bytes"] += int(row.get("bytes", 0))
            agg["calls"] += int(row.get("calls", 0))
    if comm:
        out["comm_bytes_by_axis"] = comm

    # per-wire-dtype comm rollup (rows carrying comm_bytes_by_verb_dtype —
    # CommAccount.by_verb_dtype tables from quantized-collective configs):
    # a quantized reduce's int8 payload and its fp32 scale side-channel
    # land as distinct "<verb>[<dtype>]" rows, so the compression ratio
    # (and the side-channel's cost) read straight off the analysis
    comm_dt: Dict[str, Dict[str, int]] = {}
    for r in records:
        table = r.get("comm_bytes_by_verb_dtype")
        if not isinstance(table, dict):
            continue
        for key, row in table.items():
            agg = comm_dt.setdefault(key, {"bytes": 0, "calls": 0})
            agg["bytes"] += int(row.get("bytes", 0))
            agg["calls"] += int(row.get("calls", 0))
    if comm_dt:
        out["comm_bytes_by_verb_dtype"] = comm_dt

    # MFU / roofline summary (records journaled with step costs armed)
    mfus = [r["mfu"] for r in steps if isinstance(r.get("mfu"), (int, float))]
    if mfus:
        bw = [r["hbm_bw_util"] for r in steps
              if isinstance(r.get("hbm_bw_util"), (int, float))]
        bounds: Dict[str, int] = {}
        for r in steps:
            if r.get("bound"):
                bounds[r["bound"]] = bounds.get(r["bound"], 0) + 1
        out["mfu"] = dict(_dist(mfus), bound=bounds,
                          peak_source=next((r.get("peak_source") for r in steps
                                            if r.get("peak_source")), None))
        if bw:
            out["mfu"]["hbm_bw_util_p50"] = _dist(bw).get("p50")

    # timeline rollup (records from --trace-armed runs: bubble-fraction
    # stamps from the traced pipeline drive, anatomy fractions and
    # overlap from set_step_comm's step_anatomy join)
    tl: Dict[str, Any] = {}
    bub = [r["bubble_fraction"] for r in steps
           if isinstance(r.get("bubble_fraction"), (int, float))]
    if bub:
        tl["bubble_fraction"] = {"last": round(bub[-1], 4),
                                 "p50": _dist(bub).get("p50")}
        exp = next((r["bubble_fraction_expected"] for r in steps
                    if isinstance(r.get("bubble_fraction_expected"),
                                  (int, float))), None)
        if exp is not None:
            tl["bubble_fraction_expected"] = exp
    ovl = [r["overlap_fraction"] for r in steps
           if isinstance(r.get("overlap_fraction"), (int, float))]
    if ovl:
        tl["overlap_fraction"] = _dist(ovl)
    for key in ("compute_frac", "comm_frac", "stall_frac"):
        vals = [r[key] for r in steps
                if isinstance(r.get(key), (int, float))]
        if vals:
            tl[f"{key}_mean"] = round(sum(vals) / len(vals), 4)
    if tl:
        out["timeline"] = tl

    # optimizer-state footprint (journals armed via set_opt_state_bytes —
    # the per-rank ZeRO claim: bytes/rank ÷ dp vs a replicated run)
    osb = [r["opt_state_bytes"] for r in steps
           if isinstance(r.get("opt_state_bytes"), (int, float))]
    if osb:
        out["opt_state_bytes"] = {"last": int(osb[-1]),
                                  "peak": int(max(osb))}

    # working-param footprint (set_param_bytes — the ZeRO-3 claim: the
    # bf16 params themselves at 1/dp vs a replicated run)
    pb = [r["param_bytes"] for r in steps
          if isinstance(r.get("param_bytes"), (int, float))]
    if pb:
        out["param_bytes"] = {"last": int(pb[-1]), "peak": int(max(pb))}

    # serving rollup (kind="request" records from apex_tpu.serve.Engine,
    # plus the queue/occupancy fields its decode ticks stamp on step
    # records): request latency in MILLISECONDS (journals carry seconds;
    # the 3-decimal rounding would erase sub-ms off-TPU latencies) with
    # the p99 tail — the serving product claim — and tokens/s/user from
    # each request's end-to-end time
    reqs = [r for r in records if r.get("kind") == "request"]
    if reqs:
        sv: Dict[str, Any] = {"requests": len(reqs)}
        ttft = [1e3 * r["ttft_s"] for r in reqs
                if isinstance(r.get("ttft_s"), (int, float))]
        itl = [1e3 * v for r in reqs for v in (r.get("itl_s") or [])
               if isinstance(v, (int, float))]
        if ttft:
            sv["ttft_ms"] = _dist_tail(ttft)
        if itl:
            sv["itl_ms"] = _dist_tail(itl)
        tps_user = [r["new_tokens"] / r["e2e_s"] for r in reqs
                    if isinstance(r.get("e2e_s"), (int, float))
                    and r["e2e_s"] > 0
                    and isinstance(r.get("new_tokens"), (int, float))]
        if tps_user:
            sv["tokens_per_sec_per_user"] = _dist(tps_user)
        qd = [r["queue_depth"] for r in steps
              if isinstance(r.get("queue_depth"), (int, float))]
        occ = [r["slot_occupancy"] for r in steps
               if isinstance(r.get("slot_occupancy"), (int, float))]
        if qd:
            sv["queue_depth"] = _dist(qd)
        if occ:
            sv["slot_occupancy"] = _dist(occ)
        # ISSUE 12 rollups — prefill records carry the prefix-sharing and
        # chunked-prefill evidence, step records the accepted draft length
        pf = [r for r in records if r.get("kind") == "prefill"]
        cached = [(r["cached_tokens"], r.get("prompt_len", 0)) for r in pf
                  if isinstance(r.get("cached_tokens"), (int, float))]
        if cached:
            tot_prompt = sum(p for _, p in cached)
            # token-level hit rate: the fraction of prompt tokens whose
            # prefill was SKIPPED by a cached prefix (the FLOPs claim)
            sv["prefix_hit_rate"] = round(
                sum(c for c, _ in cached) / tot_prompt, 4) if tot_prompt \
                else 0.0
            sv["pages_saved"] = int(sum(
                r.get("pages_shared", 0) for r in pf
                if isinstance(r.get("pages_shared"), (int, float))))
            sv["cow_forks"] = int(sum(
                r.get("cow_forks", 0) for r in pf
                if isinstance(r.get("cow_forks"), (int, float))))
        qdel = [1e3 * r["queue_delay_s"] for r in pf
                if isinstance(r.get("queue_delay_s"), (int, float))]
        if qdel:
            sv["prefill_queue_delay_ms"] = _dist(qdel)
        chunks = [r["chunks"] for r in pf
                  if isinstance(r.get("chunks"), (int, float))]
        if chunks:
            sv["prefill_chunks"] = int(sum(chunks))
        acc = [r["accepted_len"] for r in steps
               if isinstance(r.get("accepted_len"), (int, float))]
        if acc:
            sv["accepted_len"] = _dist(acc)
        # ISSUE 17: TTFT/ITL decomposed into queue / prefill-serialization
        # / compute / barrier fractions (wall-weighted over the request
        # records' per-request attribution dicts; each class sums to 1.0)
        attr = attribution_rollup([r.get("attribution") for r in reqs])
        if attr:
            sv["attribution"] = attr
        out["serving"] = sv

    # serve SLO windows (kind="slo" records from serve.Engine when
    # ServeConfig targets are set): per-window attainment — the fraction
    # of tokens inside their TTFT/ITL targets — plus goodput (in-SLO
    # tokens/s). Lives beside "serving" even for journals with slo rows
    # but no request records (crash-truncated runs).
    slo_rows = [r for r in records if r.get("kind") == "slo"]
    if slo_rows:
        att = [r["attainment"] for r in slo_rows
               if isinstance(r.get("attainment"), (int, float))]
        gp = [r["goodput_tokens_per_sec"] for r in slo_rows
              if isinstance(r.get("goodput_tokens_per_sec"), (int, float))]
        slo: Dict[str, Any] = {"windows": len(slo_rows)}
        if att:
            slo["attainment"] = _dist(att)
        if gp:
            slo["goodput_tokens_per_sec"] = _dist(gp)
        tgt = next((r.get("target") for r in slo_rows
                    if isinstance(r.get("target"), (int, float))), None)
        if tgt is not None:
            slo["target"] = tgt
        out["slo"] = slo

    # health alerts (monitor/health.py): the DERIVED count replays the
    # streaming rules over this journal (so the --max-alerts gate works
    # on journals that never armed a monitor); "journaled" counts the
    # kind="alert" rows an armed monitor wrote live. Always present, so
    # compare's alert check never skips on a clean run.
    try:
        from apex_tpu.monitor import health as health_mod

        derived = health_mod.scan(records)
        rollup = health_mod.summarize(derived)
    except Exception:  # noqa: BLE001 - analysis must survive a bad journal
        derived, rollup = [], {"count": 0, "by_rule": {}}
    out["alerts"] = dict(
        rollup,
        journaled=sum(1 for r in records if r.get("kind") == "alert"),
        list=derived[:max_list],
    )

    # overflow / forensics / recompile rollups
    overflows = [r["overflows"] for r in steps
                 if isinstance(r.get("overflows"), (int, float))]
    out["overflows"] = int(max(overflows)) if overflows else 0
    forensics = [r for r in records if r.get("kind") == "forensics"]
    if forensics:
        by_trigger: Dict[str, int] = {}
        for r in forensics:
            by_trigger[r.get("trigger", "?")] = (
                by_trigger.get(r.get("trigger", "?"), 0) + 1)
        out["forensics"] = {
            "count": len(forensics), "by_trigger": by_trigger,
            "nonfinite_groups": sorted({g for r in forensics
                                        for g in r.get("nonfinite_groups", [])}),
        }
    recompiles = [r for r in records if r.get("kind") == "recompile"]
    if recompiles:
        by_fn: Dict[str, Dict[str, Any]] = {}
        for r in recompiles:
            row = by_fn.setdefault(r.get("fn", "?"),
                                   {"compiles": 0, "compile_s": 0.0,
                                    "signatures": set()})
            row["compiles"] += 1
            row["compile_s"] += float(r.get("compile_s", 0.0))
            row["signatures"].add(r.get("signature", ""))
        out["recompiles"] = {
            fn: {"compiles": v["compiles"],
                 "compile_s": round(v["compile_s"], 3),
                 "signatures": len(v["signatures"])}
            for fn, v in by_fn.items()}
    return out


def render(analysis: Dict[str, Any], file=None) -> None:
    """Human-readable view of :func:`analyze` (the JSON is the API)."""
    file = file or sys.stdout
    p = lambda *a: print(*a, file=file)  # noqa: E731
    p(f"records: {analysis['records']} "
      f"(steps: {analysis['step_records']}"
      + (", TRUNCATED final line" if analysis["truncated"] else "")
      + (f", {analysis['bad_lines']} bad line(s)" if analysis["bad_lines"] else "")
      + ")")
    meta = analysis.get("meta")
    if meta:
        env = meta.get("env") or {}
        bits = []
        if meta.get("run"):
            bits.append(f"run {meta['run']}")
        if meta.get("fingerprint"):
            bits.append(f"fingerprint {meta['fingerprint']}")
        if env.get("git"):
            bits.append(f"git {env['git']}")
        if env.get("jax"):
            bits.append(f"jax {env['jax']}")
        if env.get("device_platform"):
            bits.append(env["device_platform"])
        if env.get("peak_overrides"):
            bits.append("peak overrides "
                        + ",".join(sorted(env["peak_overrides"])))
        if bits:
            p("meta: " + "  ".join(bits))
    tp = analysis.get("tokens_per_sec")
    if tp:
        p(f"throughput tok/s: p10 {tp['p10']}  p50 {tp['p50']}  "
          f"p90 {tp['p90']}  (min {tp['min']}, max {tp['max']}, n={tp['n']})")
    mfu = analysis.get("mfu")
    if mfu:
        p(f"mfu: p50 {mfu.get('p50')}  (min {mfu.get('min')}, max "
          f"{mfu.get('max')}; bound {mfu.get('bound')}; "
          f"hbm_bw_util p50 {mfu.get('hbm_bw_util_p50')}; "
          f"peak source {mfu.get('peak_source')})")
    st = analysis.get("stalls", {})
    p(f"stalls: {st.get('count', 0)} "
      f"(median cadence {st.get('median_cadence_s')}s)")
    for g in st.get("gaps", []):
        p(f"  after step {g['after_step']}: {g['gap_s']}s "
          f"({g['x_median']}x median)")
    lo = analysis.get("loss", {})
    p(f"loss: first {lo.get('first')} -> last {lo.get('last')}; "
      f"{lo.get('spike_count', 0)} spike(s), "
      f"{lo.get('nonfinite_count', 0)} non-finite")
    for s in lo.get("spikes", []):
        p(f"  spike at step {s['step']}: {s['loss']} "
          f"(baseline {s['baseline']})")
    hbm = analysis.get("hbm")
    if hbm:
        p(f"hbm: growth {hbm['growth_bytes'] / 1e6:.1f} MB over "
          f"{hbm['samples']} samples (peak {hbm['peak_bytes'] / 1e6:.1f} MB, "
          f"trend {hbm['trend_bytes_per_sample'] / 1e6:.2f} MB/sample)")
    rk = analysis.get("ranks")
    if rk and rk["count"] > 1:
        p(f"ranks: {rk['count']}, straggler rank {rk['straggler_rank']} "
          f"(skew {rk['skew']}x)")
    comm = analysis.get("comm_bytes_by_axis")
    if comm:
        for axis, row in sorted(comm.items()):
            p(f"comm[{axis}]: {row['bytes'] / 1e6:.2f} MB over "
              f"{row['calls']} call site(s)")
    comm_dt = analysis.get("comm_bytes_by_verb_dtype")
    if comm_dt:
        for key, row in sorted(comm_dt.items()):
            p(f"comm {key}: {row['bytes'] / 1e6:.2f} MB over "
              f"{row['calls']} call site(s)")
    tl = analysis.get("timeline")
    if tl:
        bf = tl.get("bubble_fraction") or {}
        parts = []
        if bf:
            exp = tl.get("bubble_fraction_expected")
            parts.append(f"bubble p50 {bf.get('p50')}"
                         + (f" (analytic floor {exp})"
                            if exp is not None else ""))
        if tl.get("overlap_fraction"):
            parts.append(f"overlap p50 {tl['overlap_fraction'].get('p50')}")
        fr = [f"{k[:-10]} {tl[k]}" for k in
              ("compute_frac_mean", "comm_frac_mean", "stall_frac_mean")
              if k in tl]
        if fr:
            parts.append("anatomy " + "/".join(fr))
        p("timeline: " + "; ".join(parts))
    osb = analysis.get("opt_state_bytes")
    if osb:
        p(f"opt state: {osb['last'] / 1e6:.1f} MB/rank "
          f"(peak {osb['peak'] / 1e6:.1f} MB)")
    pb = analysis.get("param_bytes")
    if pb:
        p(f"params: {pb['last'] / 1e6:.1f} MB/rank "
          f"(peak {pb['peak'] / 1e6:.1f} MB)")
    sv = analysis.get("serving")
    if sv:
        parts = [f"{sv['requests']} request(s)"]
        if sv.get("ttft_ms"):
            parts.append(f"ttft p50 {sv['ttft_ms']['p50']}ms "
                         f"p99 {sv['ttft_ms']['p99']}ms")
        if sv.get("itl_ms"):
            parts.append(f"itl p50 {sv['itl_ms']['p50']}ms "
                         f"p99 {sv['itl_ms']['p99']}ms")
        if sv.get("tokens_per_sec_per_user"):
            parts.append(
                f"tok/s/user p50 {sv['tokens_per_sec_per_user']['p50']}")
        if sv.get("queue_depth"):
            parts.append(f"queue p50 {sv['queue_depth']['p50']}")
        if sv.get("slot_occupancy"):
            parts.append(f"occupancy p50 {sv['slot_occupancy']['p50']}")
        if sv.get("prefix_hit_rate") is not None:
            parts.append(f"prefix hit-rate {sv['prefix_hit_rate']} "
                         f"({sv.get('pages_saved', 0)} page(s) shared, "
                         f"{sv.get('cow_forks', 0)} COW fork(s))")
        if sv.get("prefill_queue_delay_ms"):
            parts.append(
                f"prefill queue delay p50 "
                f"{sv['prefill_queue_delay_ms']['p50']}ms")
        if sv.get("accepted_len"):
            parts.append(f"accepted draft len p50 "
                         f"{sv['accepted_len']['p50']}")
        p("serving: " + "; ".join(parts))
        attr = sv.get("attribution") or {}
        for cls in ("ttft", "itl"):
            row = attr.get(cls)
            if row:
                fr = ", ".join(
                    f"{k[:-5]} {row[k]}" for k in sorted(row)
                    if k.endswith("_frac"))
                p(f"  {cls} attribution (n={row['n']}, "
                  f"wall mean {row['wall_s_mean']}s): {fr}")
    slo = analysis.get("slo")
    if slo:
        att = slo.get("attainment") or {}
        gp = slo.get("goodput_tokens_per_sec") or {}
        p(f"slo: {slo['windows']} window(s), attainment p50 "
          f"{att.get('p50')} (min {att.get('min')}"
          + (f", target {slo['target']}" if slo.get("target") is not None
             else "")
          + (f"), goodput p50 {gp.get('p50')} tok/s" if gp else ")"))
    al = analysis.get("alerts")
    if al:
        rules = ", ".join(f"{k}: {v}"
                          for k, v in sorted(al["by_rule"].items()))
        live = (f"; {al['journaled']} journaled live"
                if al.get("journaled") else "")
        p(f"alerts: {al['count']} ({rules or 'none'}{live})")
        for a in al.get("list", [])[:8]:
            p(f"  [{a['rule']}] step {a.get('step')}: {a.get('message')}")
    p(f"overflows: {analysis.get('overflows', 0)}")
    fo = analysis.get("forensics")
    if fo:
        p(f"forensics: {fo['count']} record(s) {fo['by_trigger']}"
          + (f", non-finite groups: {fo['nonfinite_groups']}"
             if fo["nonfinite_groups"] else ""))
    rc = analysis.get("recompiles")
    if rc:
        for fn, row in sorted(rc.items()):
            p(f"recompiles[{fn}]: {row['compiles']} "
              f"({row['compile_s']}s, {row['signatures']} signature(s))")


# ---------------------------------------------------------------------------
# compare (the machine regression gate)
# ---------------------------------------------------------------------------


def must_not_drop(threshold: float):
    """Shared fractional-drop predicate: B regresses iff it falls more
    than ``threshold`` below A (throughput/MFU-shaped metrics)."""
    return lambda va, vb: vb < va * (1.0 - threshold)


def must_not_grow(threshold: float, slack: float = 0.0):
    """Shared fractional-growth predicate: B regresses iff it exceeds A
    by more than ``threshold`` (plus an absolute ``slack`` floor for
    near-zero baselines — a 0.001 bubble must not gate on timer noise).
    Residency-bytes and bubble-fraction-shaped metrics."""
    return lambda va, vb: vb > va * (1.0 + threshold) + slack


def compare(
    a: Sequence[Dict[str, Any]],
    b: Sequence[Dict[str, Any]],
    *,
    threshold: float = 0.05,
    hbm_slack_bytes: int = 64 << 20,
    loss_threshold: Optional[float] = None,
    bubble_threshold: Optional[float] = None,
    overlap_threshold: Optional[float] = None,
    max_alerts: Optional[int] = None,
) -> Dict[str, Any]:
    """Compare run B against baseline A; ``regressed`` iff B is worse.

    Checks (each skipped when either side lacks the signal): B must have
    step records when A did; p50 throughput and p50 MFU must not drop by
    more than ``threshold`` (fractional; MFU compared only when both
    runs share a peak-spec provenance); the per-step overflow rate must
    not more than double past a 1%-of-steps floor; HBM growth must not
    exceed A's by more than ``hbm_slack_bytes``; B must not introduce
    non-finite losses A did not have; the per-rank ``opt_state_bytes``/
    ``param_bytes`` stamps must not grow past the threshold (a candidate
    that silently dropped ZeRO/ZeRO-3 re-replicates O(model) state at
    identical throughput — only these stamps would see it).

    ``loss_threshold`` (off by default — timing gates must not fail on
    stochastic loss noise) arms the CONVERGENCE check: B's final loss
    must not exceed A's by more than this fraction of A's loss drop
    (``first - last``; falls back to ``|last|`` when A never improved).
    Scaling by the drop makes the tolerance mean "fraction of the
    learning progress given back" — the machine gate for paired
    fp32-wire vs quantized-wire training runs (the quantized-collectives
    convergence bar, parallel/quantize.py).

    ``overlap_threshold`` tunes the comm/compute OVERLAP gate (defaults
    to ``threshold`` when journals carry ``overlap_fraction`` stamps —
    ``set_step_comm``'s step-anatomy join): B's overlap fraction must not
    DROP past it — the machine gate for structural-prefetch work (the
    ZeRO-3 double-buffered gathers whose win IS the overlap fraction,
    ``models/_transformer._prefetched_zero3_drive``), sharing the same
    :func:`must_not_drop` predicate as throughput.

    Serving journals (``kind="request"`` records from ``apex_tpu.serve``)
    gate symmetrically: B must still serve requests when A did, TTFT/ITL
    p50 must not grow past ``threshold`` (+0.05 ms timer-noise slack), and
    per-user tokens/s must not drop — the latency-shaped regression gate
    ISSUE 10's satellite adds. ISSUE 12 extends them: the ITL p99 TAIL
    must not grow (+0.5 ms slack — the monolithic-long-prompt stall the
    chunked prefill exists to remove lives in the tail), and the prefix
    hit-rate / mean accepted draft length (``kind="prefill"`` and step
    ``accepted_len`` stamps) must not DROP — the same
    :func:`must_not_drop` predicate throughput uses. ISSUE 17 adds the
    attribution gates (``ttft_queue_frac``/``itl_queue_frac`` must not
    grow — the queue share of each latency class, from the request
    records' per-request attribution) and degrades the mixed serve/train
    pair gracefully: when exactly one journal has serving records and
    the other is a train journal, the serving gates are skipped with a
    note instead of failing.

    ``max_alerts`` (off by default) arms the health-alert gate: the
    candidate's derived alert count (``monitor/health.py`` rules replayed
    over the journal by ``analyze``) may not exceed the budget nor the
    baseline's own count — so a self-compare always passes and a noisy
    baseline never fails its identical twin.

    ``bubble_threshold`` tunes the pipeline bubble-fraction gate
    independently of ``threshold`` (it defaults to ``threshold`` when
    journals carry ``bubble_fraction`` stamps): B's bubble fraction must
    not grow past it — the machine before/after for schedule work
    (ROADMAP item 5; the analytic floor rides the journal as
    ``bubble_fraction_expected``). All fractional tolerances share one
    predicate pair (:func:`must_not_drop` / :func:`must_not_grow`).
    """
    ra, rb = analyze(a), analyze(b)
    checks: List[Dict[str, Any]] = []

    def check(name, va, vb, *, worse):
        if va is None or vb is None:
            return
        checks.append({"check": name, "a": va, "b": vb,
                       "regressed": bool(worse(va, vb))})

    # structural gate FIRST: a candidate that journaled nothing (crashed
    # before its first step record) must FAIL, not skip every signal
    # check and sail through green
    check("step_records", ra["step_records"], rb["step_records"],
          worse=lambda va, vb: va > 0 and vb == 0)
    check("tokens_per_sec_p50",
          (ra.get("tokens_per_sec") or {}).get("p50"),
          (rb.get("tokens_per_sec") or {}).get("p50"),
          worse=must_not_drop(threshold))
    # MFU is only comparable against the SAME peak denominator: a
    # baseline armed with an env-calibrated ceiling vs a candidate on
    # the datasheet row would regress ~4x at identical throughput
    src_a = (ra.get("mfu") or {}).get("peak_source")
    src_b = (rb.get("mfu") or {}).get("peak_source")
    if src_a == src_b:
        check("mfu_p50",
              (ra.get("mfu") or {}).get("p50"),
              (rb.get("mfu") or {}).get("p50"),
              worse=must_not_drop(threshold))
    else:
        checks.append({"check": "mfu_p50", "a": src_a, "b": src_b,
                       "regressed": False,
                       "skipped": "peak_source mismatch"})
    # overflow comparison is per-step (a longer healthy run accumulates
    # more warmup overflows at the same rate); regression = the rate
    # more than doubles past a 1%-of-steps floor
    rate = lambda r: (r["overflows"] / r["step_records"]  # noqa: E731
                      if r["step_records"] else 0.0)
    check("overflow_rate", round(rate(ra), 4), round(rate(rb), 4),
          worse=lambda va, vb: vb > 2.0 * va + 0.01)
    check("hbm_growth_bytes",
          (ra.get("hbm") or {}).get("growth_bytes"),
          (rb.get("hbm") or {}).get("growth_bytes"),
          worse=lambda va, vb: vb > va + hbm_slack_bytes)
    check("nonfinite_losses",
          (ra.get("loss") or {}).get("nonfinite_count", 0),
          (rb.get("loss") or {}).get("nonfinite_count", 0),
          worse=lambda va, vb: vb > va)
    if loss_threshold is not None:
        # convergence gate: final loss within loss_threshold x A's loss
        # drop (docstring) — the tolerance is denominated in learning
        # progress, so short runs with small absolute drops gate tightly
        la = ra.get("loss") or {}
        drop = None
        if isinstance(la.get("first"), (int, float)) and isinstance(
                la.get("last"), (int, float)):
            drop = la["first"] - la["last"]
            if drop <= 0:
                drop = abs(la["last"]) or 1.0
        check("loss_last", la.get("last"),
              (rb.get("loss") or {}).get("last"),
              worse=lambda va, vb: vb > va + loss_threshold * (
                  drop if drop is not None else abs(va) or 1.0))
    # per-rank residency stamps (set_opt_state_bytes/set_param_bytes):
    # regression = the static footprint GROWS past the threshold — a
    # candidate that quietly dropped ZeRO(-3) re-replicates O(model)
    # state at identical throughput, which no other check would see
    check("opt_state_bytes_last",
          (ra.get("opt_state_bytes") or {}).get("last"),
          (rb.get("opt_state_bytes") or {}).get("last"),
          worse=must_not_grow(threshold))
    check("param_bytes_last",
          (ra.get("param_bytes") or {}).get("last"),
          (rb.get("param_bytes") or {}).get("last"),
          worse=must_not_grow(threshold))
    # pipeline bubble fraction (journals stamped by set_bubble_fraction):
    # regression = the measured bubble GROWS past the tolerance — the
    # machine gate schedule rewrites are judged by. The 0.01 absolute
    # slack keeps near-zero-bubble baselines from gating on timer noise.
    check("bubble_fraction_p50",
          ((ra.get("timeline") or {}).get("bubble_fraction") or {}).get("p50"),
          ((rb.get("timeline") or {}).get("bubble_fraction") or {}).get("p50"),
          worse=must_not_grow(
              threshold if bubble_threshold is None else bubble_threshold,
              slack=0.01))
    # comm/compute overlap fraction (set_step_comm's step-anatomy join):
    # regression = the measured overlap DROPS past the tolerance — the
    # machine gate for structural-prefetch work (ZeRO-3 double-buffered
    # gathers); higher is better, so the drop predicate
    check("overlap_fraction_p50",
          ((ra.get("timeline") or {}).get("overlap_fraction") or {}).get("p50"),
          ((rb.get("timeline") or {}).get("overlap_fraction") or {}).get("p50"),
          worse=must_not_drop(
              threshold if overlap_threshold is None else overlap_threshold))
    # serving latency gates (kind="request" journals from the serve
    # engine): TTFT/ITL p50 must not GROW past the threshold — the same
    # machine gate training throughput gets, pointed at the latency-shaped
    # metrics (lower is better, so the growth predicate). The 0.05 ms
    # absolute slack keeps tiny off-TPU runs from gating on timer noise.
    sva = ra.get("serving") or {}
    svb = rb.get("serving") or {}
    # mixed serve/train pair (ISSUE 17 satellite): when exactly one side
    # served and the serve-less side is a TRAIN journal (it has loss
    # records — a crashed serve candidate has neither), the pair is mixed
    # on purpose; note it and skip the serving gates instead of erroring
    # or failing the crash guard below
    if bool(sva.get("requests")) != bool(svb.get("requests")):
        other = rb if sva.get("requests") else ra
        which = "b" if sva.get("requests") else "a"
        if ((other.get("loss") or {}).get("first")) is not None:
            checks.append({
                "check": "serve_requests",
                "a": sva.get("requests", 0), "b": svb.get("requests", 0),
                "regressed": False,
                "skipped": f"no serving records in {which} (train journal)",
            })
            sva, svb = {}, {}  # every serving check below skips on None
    # a candidate that served NOTHING has no "serving" section at all —
    # default its count to 0 (not None, which would skip the check and
    # sail a crashed candidate through green) whenever A served requests
    check("serve_requests", sva.get("requests"),
          svb.get("requests", 0) if sva.get("requests") else
          svb.get("requests"),
          worse=lambda va, vb: va > 0 and vb == 0)
    for key in ("ttft_ms", "itl_ms"):
        check(f"{key}_p50",
              (sva.get(key) or {}).get("p50"),
              (svb.get(key) or {}).get("p50"),
              worse=must_not_grow(threshold, slack=0.05))
    # the ITL TAIL gates too (ISSUE 12): a monolithic long-prompt prefill
    # stalls every running stream for the whole prompt — a p99 spike the
    # p50 can hide when only a few samples land in the stall. Larger
    # absolute slack: the tail of a tiny off-TPU run is timer-noisy.
    check("itl_ms_p99",
          (sva.get("itl_ms") or {}).get("p99"),
          (svb.get("itl_ms") or {}).get("p99"),
          worse=must_not_grow(threshold, slack=0.5))
    check("tokens_per_sec_per_user_p50",
          (sva.get("tokens_per_sec_per_user") or {}).get("p50"),
          (svb.get("tokens_per_sec_per_user") or {}).get("p50"),
          worse=must_not_drop(threshold))
    # prefix-sharing / speculative-decoding regression gates (ISSUE 12):
    # the prefix hit-rate and the mean accepted draft length are
    # higher-is-better — a candidate that silently dropped sharing or
    # whose draft stopped agreeing regresses through the SAME
    # must_not_drop predicate throughput uses
    check("prefix_hit_rate", sva.get("prefix_hit_rate"),
          svb.get("prefix_hit_rate"),
          worse=must_not_drop(threshold))
    check("accepted_len_p50",
          (sva.get("accepted_len") or {}).get("p50"),
          (svb.get("accepted_len") or {}).get("p50"),
          worse=must_not_drop(threshold))
    # latency ATTRIBUTION gates (ISSUE 17): the queue fraction of each
    # request class must not GROW — a candidate whose TTFT held steady by
    # trading compute for admission wait is a scheduling regression the
    # raw percentiles can hide. Same predicate family; the 0.05 absolute
    # slack covers near-zero-queue baselines.
    for cls in ("ttft", "itl"):
        check(f"{cls}_queue_frac",
              ((sva.get("attribution") or {}).get(cls) or {}).get(
                  "queue_frac"),
              ((svb.get("attribution") or {}).get(cls) or {}).get(
                  "queue_frac"),
              worse=must_not_grow(threshold, slack=0.05))
    # serve SLO attainment (kind="slo" window records): the fraction of
    # tokens inside their latency targets must not DROP — the serving
    # health twin of the throughput gate
    check("slo_attainment_p50",
          ((ra.get("slo") or {}).get("attainment") or {}).get("p50"),
          ((rb.get("slo") or {}).get("attainment") or {}).get("p50"),
          worse=must_not_drop(threshold))
    if max_alerts is not None:
        # health-alert gate (--max-alerts): the candidate's DERIVED alert
        # count (health.scan — works on journals that never armed a live
        # monitor) may not exceed the budget nor the baseline's own count
        # (a noisy baseline doesn't fail its twin; self-compare always
        # passes)
        check("alerts",
              (ra.get("alerts") or {}).get("count", 0),
              (rb.get("alerts") or {}).get("count", 0),
              worse=lambda va, vb: vb > max(va, max_alerts))
    regressed = [c["check"] for c in checks if c["regressed"]]
    return {"threshold": threshold, "checks": checks,
            "regressed": regressed, "ok": not regressed,
            "a": {"step_records": ra["step_records"]},
            "b": {"step_records": rb["step_records"]}}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "compare":
        p = argparse.ArgumentParser(
            prog="python -m apex_tpu.monitor.report compare",
            description="Regression gate between two journals "
                        "(exit 1 on regression).")
        p.add_argument("baseline")
        p.add_argument("candidate")
        p.add_argument("--threshold", type=float, default=0.05,
                       help="max fractional drop in p50 throughput/MFU "
                            "(default 0.05)")
        p.add_argument("--hbm-slack-mb", type=float, default=64.0,
                       help="allowed HBM-growth excess over baseline (MiB)")
        p.add_argument("--loss-threshold", type=float, default=None,
                       help="arm the convergence gate: candidate final loss "
                            "must be within this fraction of the baseline's "
                            "loss drop (off by default — see compare())")
        p.add_argument("--bubble-threshold", type=float, default=None,
                       help="max fractional growth in the pipeline bubble "
                            "fraction (defaults to --threshold when "
                            "journals carry bubble_fraction stamps)")
        p.add_argument("--overlap-threshold", type=float, default=None,
                       help="max fractional DROP in the comm/compute "
                            "overlap fraction (defaults to --threshold "
                            "when journals carry overlap_fraction stamps "
                            "— the structural-prefetch gate)")
        p.add_argument("--max-alerts", type=int, default=None,
                       help="arm the health-alert gate: the candidate's "
                            "derived alert count (monitor/health.py rules "
                            "replayed over the journal) may not exceed "
                            "this budget nor the baseline's own count")
        p.add_argument("--json", action="store_true",
                       help="print the full comparison as one JSON object")
        p.add_argument("--format", choices=("text", "json"), default=None,
                       help="output format (json == --json; parity with "
                            "`python -m apex_tpu.lint --format json`)")
        args = p.parse_args(argv[1:])
        res = compare(load(args.baseline), load(args.candidate),
                      threshold=args.threshold,
                      # MiB, matching compare()'s 64 << 20 default exactly
                      hbm_slack_bytes=int(args.hbm_slack_mb * (1 << 20)),
                      loss_threshold=args.loss_threshold,
                      bubble_threshold=args.bubble_threshold,
                      overlap_threshold=args.overlap_threshold,
                      max_alerts=args.max_alerts)
        if args.json or args.format == "json":
            print(json.dumps(res))
        else:
            for c in res["checks"]:
                mark = "REGRESSED" if c["regressed"] else "ok"
                print(f"{c['check']:<22} A={c['a']} B={c['b']}  {mark}")
            print("REGRESSION: " + ", ".join(res["regressed"])
                  if res["regressed"] else "no regression")
        return 0 if res["ok"] else 1

    p = argparse.ArgumentParser(
        prog="python -m apex_tpu.monitor.report",
        description=(
            "Analyze a MetricsJournal JSON-lines file (or: "
            "'compare <A> <B>' for the regression gate)."))
    p.add_argument("journal")
    p.add_argument("--json", action="store_true",
                   help="print the analysis as one JSON object")
    p.add_argument("--format", choices=("text", "json"), default=None,
                   help="output format: json emits the full rollup as one "
                        "JSON object (same as --json; parity with "
                        "`python -m apex_tpu.lint --format json`, so "
                        "CI/driver consumers stop scraping text)")
    p.add_argument("--stall-factor", type=float, default=5.0)
    p.add_argument("--spike-factor", type=float, default=3.0)
    args = p.parse_args(argv)
    analysis = analyze(load(args.journal), stall_factor=args.stall_factor,
                       spike_factor=args.spike_factor)
    if args.json or args.format == "json":
        print(json.dumps(analysis))
    else:
        render(analysis)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Auto-parallelism planner evidence (ISSUE 18).

Executable off-TPU proof that the static placement search picks right
and that its cost model closes against a measured run, as one JSON
artifact (``out/plan_evidence.json``, ok:true):

(a) **three blind picks** — the planner, given only shape + mesh + HBM
    budget, reproduces decisions this repo earned empirically:

    - 2.7B on 8 ranks under 16 GiB → ZeRO-3 (replicated AND ZeRO-1/2
      carry ``static-hbm`` rejection provenance — the gpt_scaling
      placement-rung verdict, now searched not hand-checked);
    - 345M pinned at pp=4 → the zero-bubble schedule outranks
      interleaved and 1F1B on modeled step seconds via its lower
      analytic bubble floor;
    - 345M at dp=8/ZeRO-2 → fp32 wire on the default ICI table (int8
      rejected ``wire-not-binding``, the EQuARX deployment rule), int8
      wire once ``APEX_TPU_PEAK_ICI_GBPS`` narrows the modeled wire to
      where comm binds;

(b) **110M analytic join** — the planner's ZeRO-3 residency columns for
    the pinned 110M-class shape equal ``monitor.hbm.param_state_report``
    (same bytes, two independent code paths — the no-drift claim);

(c) **calibration closure** — a real (tiny) ``pretrain_gpt --plan auto
    --ledger --journal`` run in a fresh process adopts the planner's
    winner and appends a ledger record carrying the planner's predicted
    block; ``ledger calibrate`` fits effective peak constants from that
    record; ARMED (``APEX_TPU_CALIBRATION``), re-scoring the SAME winner
    resolves ``source="calibrated"`` specs and lands modeled step
    seconds within [0.25, 4]x of the measured wall p50 AND strictly
    tighter than the uncalibrated model (~100x off on this backend: the
    CPU table's peak is not this container's) — the planner's clock
    closes the loop against its own run. The band is loose because the
    8-rank mesh is virtual (every "rank" shares 2 host cores, so the
    per-rank flop division is fictional); on hardware the same closure
    rides ``ledger regress``.

    JAX_PLATFORMS=cpu python benchmarks/plan_evidence.py

Artifacts write atomically (``utils/io.py``).
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from apex_tpu.utils.io import atomic_write_json  # noqa: E402

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

#: env knobs that would skew the blind picks if a shell left them set
_PEAK_ENV = ("APEX_TPU_PEAK_FLOPS", "APEX_TPU_PEAK_HBM_GBPS",
             "APEX_TPU_PEAK_ICI_GBPS", "APEX_TPU_CALIBRATION")


@contextlib.contextmanager
def _clean_peak_env(**overrides):
    saved = {k: os.environ.pop(k, None) for k in _PEAK_ENV}
    os.environ.update(overrides)
    try:
        yield
    finally:
        for k in overrides:
            os.environ.pop(k, None)
        for k, v in saved.items():
            if v is not None:
                os.environ[k] = v


# ---------------------------------------------------------------------------
# (a) three blind picks
# ---------------------------------------------------------------------------


def check_blind_picks() -> dict:
    from apex_tpu import plan as plan_mod

    out: dict = {}
    with _clean_peak_env():
        # pick 1: the placement-rung verdict, searched
        r = plan_mod.search("gpt-2.7b", mesh=8, hbm_gb=16.0)
        w = r["winner"]["candidate"]
        hbm_rej = [x for x in r["rejected"]
                   if x.get("rejected_by") == "static-hbm"
                   and x["candidate"].get("dp") == 8]
        rej_levels = sorted({x["candidate"]["zero_level"] for x in hbm_rej})
        out["pick_27b"] = {
            "winner": {k: w[k] for k in ("dp", "tp", "pp", "zero_level",
                                         "zero3_prefetch", "unroll")},
            "dp8_static_hbm_rejected_zero_levels": rej_levels,
            "ok": bool(w["zero_level"] == 3 and 0 in rej_levels
                       and 2 in rej_levels),
        }

        # pick 2: the schedule ladder at a pinned pp
        r2 = plan_mod.search("gpt-345m", mesh=8, hbm_gb=16.0,
                             num_microbatches=4, constraints={"pp": 4})
        best: dict = {}
        for rec in r2["ranked"]:
            s = rec["candidate"]["schedule"]
            best.setdefault(s, rec["predicted"]["step_seconds"])
        ws = r2["winner"]["candidate"]["schedule"]
        out["pick_zerobubble"] = {
            "winner_schedule": ws,
            "best_step_seconds_by_schedule":
                {k: round(v, 4) for k, v in best.items()},
            "winner_bubble_floor":
                r2["winner"]["predicted"]["bubble_floor"],
            "ok": bool(ws == "zerobubble"
                       and best["zerobubble"] < best["interleaved"]
                       and best["zerobubble"] < best["1f1b"]),
        }

        # pick 3, default wire: int8 rejected wire-not-binding
        r3 = plan_mod.search("gpt-345m", mesh=8, hbm_gb=16.0,
                             constraints={"dp": 8, "zero_level": 2})
        wnb = [x for x in r3["rejected"]
               if x.get("rejected_by") == "wire-not-binding"]
        default_rd = r3["winner"]["candidate"]["reduce_dtype"]

    # pick 3, narrowed wire: the SAME search flips to int8
    with _clean_peak_env(APEX_TPU_PEAK_ICI_GBPS="0.001"):
        r4 = plan_mod.search("gpt-345m", mesh=8, hbm_gb=16.0,
                             constraints={"dp": 8, "zero_level": 2})
        narrow_rd = r4["winner"]["candidate"]["reduce_dtype"]
    out["pick_int8_wire"] = {
        "default_winner_reduce_dtype": default_rd,
        "default_wire_not_binding_rejections": len(wnb),
        "narrowed_winner_reduce_dtype": narrow_rd,
        "ok": bool(default_rd is None and len(wnb) >= 1
                   and narrow_rd == "int8"),
    }
    out["ok"] = all(out[k]["ok"] for k in
                    ("pick_27b", "pick_zerobubble", "pick_int8_wire"))
    return out


# ---------------------------------------------------------------------------
# (b) the 110M analytic join: planner residency == monitor.hbm
# ---------------------------------------------------------------------------


def check_110m_join() -> dict:
    from apex_tpu import plan as plan_mod
    from apex_tpu.monitor.hbm import param_state_report

    spec = plan_mod.MODEL_PRESETS["gpt-110m"]
    report = param_state_report(plan_mod.abstract_params(spec), 8)
    with _clean_peak_env():
        rec = plan_mod.score_candidate(
            spec, plan_mod.Candidate(dp=8, zero_level=3,
                                     gather_dtype="bf16", unroll=True))
    res = rec["predicted"]["hbm"]["residency"]
    z3 = report["per_rank"]["zero3"]
    out = {
        "planner_param_bytes": res["param_bytes"],
        "planner_opt_bytes": res["opt_bytes"],
        "report_param_bytes": z3["param_bytes"],
        "report_opt_bytes": z3["opt_bytes"],
        "planner_total_with_activations": rec["predicted"]["hbm_bytes"],
    }
    out["ok"] = bool(res["param_bytes"] == z3["param_bytes"]
                     and res["opt_bytes"] == z3["opt_bytes"])
    return out


# ---------------------------------------------------------------------------
# (c) calibration closure through a real --plan auto run
# ---------------------------------------------------------------------------

#: the tiny shape the closure executes (CPU-feasible in seconds; big
#: enough that the matmul-dominated flop model is not pure noise)
_CLOSURE_SHAPE = dict(vocab=512, hidden=64, layers=4, heads=4, seq=64)
_CLOSURE_STEPS = 5
_WALL_RATIO_BAND = (0.25, 4.0)


def check_calibration_closure() -> dict:
    from apex_tpu import plan as plan_mod
    from apex_tpu.monitor import ledger

    d = tempfile.mkdtemp(prefix="plan_ev_c_")
    jpath = os.path.join(d, "run.jsonl")
    lpath = os.path.join(d, "ledger.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=8"
                          ).strip(),
               PYTHONPATH=os.pathsep.join(
                   [REPO] + os.environ.get("PYTHONPATH", "").split(
                       os.pathsep)))
    for k in _PEAK_ENV + ("APEX_TPU_LEDGER",):
        env.pop(k, None)
    sh = _CLOSURE_SHAPE
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "gpt",
                                      "pretrain_gpt.py"),
         "--plan", "auto",
         "--hidden", str(sh["hidden"]), "--layers", str(sh["layers"]),
         "--heads", str(sh["heads"]), "--vocab", str(sh["vocab"]),
         "--seq", str(sh["seq"]), "--steps", str(_CLOSURE_STEPS),
         "--journal", jpath, "--ledger", lpath],
        env=env, capture_output=True, text=True, timeout=600)
    out: dict = {"harness_rc": proc.returncode}
    if proc.returncode != 0:
        out["stderr_tail"] = (proc.stderr or "")[-500:]
        out["ok"] = False
        return out
    plan_line = next((json.loads(ln) for ln in proc.stdout.splitlines()
                      if ln.startswith('{"plan"')), {})
    rec = [r for r in ledger.read(lpath) if r.get("kind") == "run"][-1]
    wall = ((rec.get("measured") or {}).get("wall_s") or {}).get("p50")
    out["adopted_winner"] = (plan_line.get("plan") or {}).get("winner")
    out["uncalibrated_modeled_s"] = \
        (rec.get("predicted") or {}).get("modeled_step_s")
    out["measured_wall_p50_s"] = wall

    cal_path = os.path.join(d, "cal.json")
    with contextlib.redirect_stdout(io.StringIO()):
        cal_rc = ledger.main(["calibrate", lpath, "--output", cal_path])
    out["calibrate_rc"] = cal_rc
    if cal_rc != 0 or not out["adopted_winner"] or not wall:
        out["ok"] = False
        return out

    spec = plan_mod.ModelSpec("pretrain_gpt", sh["vocab"], sh["hidden"],
                              sh["layers"], sh["heads"], sh["seq"])
    cand = plan_mod.Candidate(**out["adopted_winner"])
    with _clean_peak_env(APEX_TPU_CALIBRATION=cal_path):
        from apex_tpu.monitor import mfu, tracing

        peak = mfu.peak_spec()
        ici = tracing.ici_spec()
        scored = plan_mod.score_candidate(spec, cand, peak=peak, ici=ici)
    import math

    cal_s = scored["predicted"]["step_seconds"]
    ratio = cal_s / wall
    uncal_ratio = out["uncalibrated_modeled_s"] / wall
    out.update({
        "calibrated_peak_source": peak.get("source"),
        "calibrated_ici_source": ici.get("source"),
        "calibrated_modeled_s": cal_s,
        "uncalibrated_wall_ratio": round(uncal_ratio, 6),
        "wall_ratio": round(ratio, 4),
        "wall_ratio_band": list(_WALL_RATIO_BAND),
    })
    out["ok"] = bool("calibrated" in str(peak.get("source"))
                     and _WALL_RATIO_BAND[0] <= ratio
                     <= _WALL_RATIO_BAND[1]
                     and abs(math.log(ratio))
                     < abs(math.log(uncal_ratio)))
    return out


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--output", default=os.path.join("out",
                                                    "plan_evidence.json"))
    args = p.parse_args()

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # noqa: BLE001 - backend already up: run on it
        pass

    record = {"evidence": "auto-parallelism planner: blind picks + "
                          "calibration closure (ISSUE 18)"}
    record["blind_picks"] = check_blind_picks()
    record["join_110m"] = check_110m_join()
    record["calibration_closure"] = check_calibration_closure()
    record["ok"] = all(record[k]["ok"] for k in
                       ("blind_picks", "join_110m",
                        "calibration_closure"))
    print(json.dumps(record))
    atomic_write_json(args.output, record)
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

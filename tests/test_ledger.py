"""Tests for apex_tpu.monitor.ledger + calibrate (ISSUE 16) — append
durability (truncated trailing line, mid-file corruption salvage,
concurrent appends from two processes), config-fingerprint stability,
the N-run regression gate (self-history passes, a seeded throughput drop
fails with report compare's machine shape), the predicted-vs-measured
calibration joins, and the armed-calibration-file precedence over the
``APEX_TPU_PEAK_*`` env overrides. All host-side and CPU-safe."""

import json
import os
import subprocess
import sys

from apex_tpu.monitor import calibrate, ledger
from apex_tpu.monitor.journal import MetricsJournal

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run_record(rate=1000.0, wall=0.1, steps=8, **extra):
    measured = {"step_records": steps,
                "tokens_per_sec": {"p50": rate},
                "wall_s": {"p50": wall},
                "loss": {"last": 2.0}}
    measured.update(extra.pop("measured", {}))
    rec = {"kind": "run", "run": "t", "config": {"tp": 2, "pp": 1},
           "measured": measured, "predicted": {}}
    rec.update(extra)
    return rec


# ---------------------------------------------------------------------------
# append durability
# ---------------------------------------------------------------------------


def test_truncated_trailing_line_still_parses(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    ledger.append(path, {"run": "a"})
    ledger.append(path, {"run": "b"})
    with open(path, "a") as f:
        f.write('{"v": 1, "kind": "run", "run": "torn')  # kill mid-write
    rows = ledger.read(path)
    assert [r["run"] for r in rows] == ["a", "b"]
    assert rows.truncated and rows.bad_lines == 1


def test_corrupt_mid_file_record_salvages_the_rest(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    ledger.append(path, {"run": "a"})
    with open(path, "a") as f:
        f.write("NOT JSON AT ALL\n")
    ledger.append(path, {"run": "b"})
    rows = ledger.read(path)
    assert [r["run"] for r in rows] == ["a", "b"]
    assert rows.bad_lines == 1 and not rows.truncated


def test_append_sanitizes_nonfinite_to_strict_json(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    ledger.append(path, {"run": "a",
                         "measured": {"loss": {"last": float("nan")}}})
    rows = ledger.read(path)
    assert rows[0]["measured"]["loss"]["last"] is None
    assert any("loss" in k for k in rows[0]["nonfinite_keys"])


def test_concurrent_appends_interleave_whole_lines(tmp_path):
    # two writer processes hammer the same file; O_APPEND single-write
    # appends must interleave whole lines — every record parses
    path = str(tmp_path / "ledger.jsonl")
    prog = ("import sys; from apex_tpu.monitor import ledger\n"
            "for i in range(20):\n"
            "    ledger.append(sys.argv[1], {'run': sys.argv[2],"
            " 'pad': 'x' * 512})\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    procs = [subprocess.Popen([sys.executable, "-c", prog, path, name],
                              env=env) for name in ("w1", "w2")]
    for pr in procs:
        assert pr.wait(timeout=120) == 0
    rows = ledger.read(path)
    assert len(rows) == 40 and rows.bad_lines == 0 and not rows.truncated
    assert sorted({r["run"] for r in rows}) == ["w1", "w2"]


# ---------------------------------------------------------------------------
# fingerprint stability
# ---------------------------------------------------------------------------


def test_fingerprint_stable_under_key_order_and_none_omission():
    a = ledger.config_fingerprint({"tp": 2, "pp": 1, "schedule": None})
    b = ledger.config_fingerprint({"pp": 1, "tp": 2})
    assert a == b and len(a) == 12


def test_fingerprint_changes_on_any_knob_flip():
    base = {"dp": 4, "tp": 2, "pp": 1, "zero_level": 1,
            "reduce_dtype": None}
    fps = {ledger.config_fingerprint(base)}
    for knob, val in (("tp", 4), ("pp", 2), ("zero_level", 3),
                      ("reduce_dtype", "int8"), ("vpp", 2)):
        fps.add(ledger.config_fingerprint(dict(base, **{knob: val})))
    assert len(fps) == 6  # every flip is a new fingerprint


# ---------------------------------------------------------------------------
# append_run: the harness hook
# ---------------------------------------------------------------------------


def test_append_run_carries_both_blocks_and_modeled_step(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    jpath = str(tmp_path / "run.jsonl")
    with MetricsJournal(jpath, meta={"run": "t", "tp": 2}) as j:
        for step in range(4):
            j.log({"kind": "step", "step": step, "wall_s": 0.1,
                   "loss": 2.0 - 0.1 * step, "tokens": 1024,
                   "tokens_per_sec": 1000.0, "overflows": 0,
                   "bubble_fraction_expected": 0.25})
    rec = ledger.append_run(
        path, run="t", config={"run": "t", "tp": 2}, journal=jpath,
        predicted={"flops_per_step": 1e9, "comm_bytes_per_step": 1e6,
                   "hbm_peak_bytes": 1 << 20})
    assert rec["kind"] == "run" and rec["v"] == 1
    assert rec["fingerprint"] == ledger.config_fingerprint(
        {"run": "t", "tp": 2})
    assert rec["measured"]["step_records"] == 4
    assert rec["measured"]["tokens_per_sec"]["p50"] == 1000.0
    # the journal's armed floor stamp was salvaged into the predicted
    # block, and the modeled step seconds carry spec provenance
    assert rec["predicted"]["bubble_floor"] == 0.25
    assert rec["predicted"]["modeled_step_s"] > 0
    assert "peak_flops_source" in rec["predicted"]["spec"]
    assert rec["env"].get("python")
    # round-trips through the crash-tolerant reader
    assert ledger.read(path)[0]["fingerprint"] == rec["fingerprint"]


# ---------------------------------------------------------------------------
# journal meta enrichment (satellite: kind="meta" header provenance)
# ---------------------------------------------------------------------------


def test_journal_meta_header_enriched_with_fingerprint_and_env(tmp_path):
    jpath = str(tmp_path / "run.jsonl")
    meta = {"run": "t", "tp": 2, "pp": 1}
    with MetricsJournal(jpath, meta=dict(meta)):
        pass
    rows = MetricsJournal.read(jpath)
    assert rows[0]["kind"] == "meta"
    assert rows[0]["fingerprint"] == ledger.config_fingerprint(meta)
    assert rows[0]["env"].get("python")
    # a bare journal (no meta) stays headerless — disarmed programs are
    # byte-identical (test_monitor pins the record counts)
    bare = str(tmp_path / "bare.jsonl")
    with MetricsJournal(bare) as j:
        j.log({"kind": "step", "step": 0})
    assert [r["kind"] for r in MetricsJournal.read(bare)] == ["step"]


# ---------------------------------------------------------------------------
# trend + regress (the N-run gate)
# ---------------------------------------------------------------------------


def test_regress_first_run_and_self_history_pass(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    ledger.append(path, _run_record())
    res = ledger.regress(ledger.read(path))
    assert res["ok"] and res["checks"] == []  # no history: every check skips
    for _ in range(3):
        ledger.append(path, _run_record())
    res = ledger.regress(ledger.read(path))
    assert res["ok"] and not res["regressed"]
    assert any(c["check"] == "tokens_per_sec_p50" for c in res["checks"])


def test_regress_fails_seeded_throughput_drop_with_compare_shape(tmp_path):
    from apex_tpu.monitor import report

    path = str(tmp_path / "ledger.jsonl")
    for _ in range(3):
        ledger.append(path, _run_record(rate=1000.0))
    ledger.append(path, _run_record(rate=700.0))  # 30% drop
    res = ledger.regress(ledger.read(path), threshold=0.05)
    assert not res["ok"] and res["regressed"] == ["tokens_per_sec_p50"]
    # machine-shape parity with report compare --format json: same top
    # keys, same per-check row keys (satellite 2's contract)
    cmp = report.compare([{"kind": "step", "step": 0, "wall_s": 0.1,
                           "tokens": 8, "tokens_per_sec": 100.0}] * 2,
                         [{"kind": "step", "step": 0, "wall_s": 0.1,
                           "tokens": 8, "tokens_per_sec": 100.0}] * 2)
    assert set(res) >= set(cmp), (set(cmp) - set(res))
    assert {tuple(sorted(c)) for c in res["checks"]} == {
        tuple(sorted(c)) for c in cmp["checks"]}
    json.dumps(res)  # strict machine shape


def test_regress_gates_structure_median_and_fingerprint(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    # one noisy predecessor can't poison the median baseline
    for rate in (1000.0, 10.0, 1000.0):
        ledger.append(path, _run_record(rate=rate))
    ledger.append(path, _run_record(rate=990.0))
    assert ledger.regress(ledger.read(path))["ok"]
    # a run that journaled nothing fails the structural gate
    ledger.append(path, _run_record(measured={"step_records": 0,
                                              "tokens_per_sec": {},
                                              "wall_s": {}}, steps=0))
    res = ledger.regress(ledger.read(path))
    assert not res["ok"] and "step_records" in res["regressed"]
    # fingerprint filtering: a different config's history is invisible
    other = dict(_run_record(rate=5000.0), config={"tp": 8})
    other["fingerprint"] = ledger.config_fingerprint({"tp": 8})
    ledger.append(path, other)
    res = ledger.regress(ledger.read(path),
                         fingerprint=other["fingerprint"])
    assert res["ok"] and res["a"]["runs"] == 0


def test_trend_groups_by_fingerprint(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    for tp in (1, 1, 2):
        rec = dict(_run_record(), config={"tp": tp},
                   fingerprint=ledger.config_fingerprint({"tp": tp}))
        ledger.append(path, rec)
    tr = ledger.trend(ledger.read(path))
    assert len(tr) == 2
    counts = sorted(len(v["rows"]) for v in tr.values())
    assert counts == [1, 2]


# ---------------------------------------------------------------------------
# calibrate: joins, fit, file precedence
# ---------------------------------------------------------------------------


def test_calibrate_join_ratios():
    rec = _run_record(
        wall=0.2,
        measured={"hbm": {"peak_bytes": 4 << 20},
                  "timeline": {"bubble_fraction": {"p50": 0.30}},
                  "comm_bytes_by_axis": {"data": {"bytes": 2e6}}},
        predicted={"hbm_peak_bytes": 2 << 20, "bubble_floor": 0.25,
                   "comm_bytes_per_step": 1e6, "modeled_step_s": 0.1})
    j = calibrate.join(rec)
    assert j["hbm_ratio"] == 2.0
    assert j["bubble_ratio"] == 1.2
    assert j["comm_ratio"] == 2.0
    assert j["wall_ratio"] == 2.0
    # missing sides emit no ratio
    assert "hbm_ratio" not in calibrate.join(_run_record())


def test_calibrate_fit_and_file_round_trip(tmp_path, monkeypatch):
    recs = [_run_record(wall=0.1,
                        predicted={"flops_per_step": 2e11,
                                   "bytes_per_step": 1e10,
                                   "comm_bytes_per_step": 1e9})
            for _ in range(3)]
    fit = calibrate.fit(recs)
    assert fit["source"] == "calibrated"
    assert fit["peak_flops"] == 2e12  # 2e11 flops / 0.1 s
    assert fit["peak_hbm_bytes_per_sec"] == 1e11
    assert fit["n_records"]["peak_flops"] == 3
    path = str(tmp_path / "cal.json")
    calibrate.save(path, fit)
    loaded = calibrate.load(path)
    assert loaded["peak_flops"] == 2e12 and loaded["v"] == 1
    # corrupt/alien files degrade to None, never raise
    with open(path, "w") as f:
        f.write("{torn")
    assert calibrate.load(path) is None
    with open(path, "w") as f:
        json.dump({"unrelated": 1}, f)
    assert calibrate.load(path) is None


def test_calibration_file_outranks_peak_env(tmp_path, monkeypatch):
    from apex_tpu.monitor import mfu, tracing

    path = str(tmp_path / "cal.json")
    calibrate.save(path, {"source": "calibrated", "peak_flops": 2e12,
                          "peak_ici_bytes_per_sec": 5e10,
                          "peak_hbm_bytes_per_sec": 3e11})
    monkeypatch.setenv("APEX_TPU_PEAK_FLOPS", "9e99")  # the hand-typed lie
    monkeypatch.setenv(calibrate.ENV_CALIBRATION, path)
    spec = mfu.peak_spec("tpu v4")
    assert spec["peak_flops"] == 2e12
    assert "calibrated" in spec["source"]
    ici = tracing.ici_spec()
    assert ici["ici_bytes_per_sec"] == 5e10
    assert ici["source"] == "calibrated"
    # disarmed: env override wins again, nothing calibrated
    monkeypatch.delenv(calibrate.ENV_CALIBRATION)
    spec = mfu.peak_spec("tpu v4")
    assert spec["peak_flops"] == 9e99
    assert "calibrated" not in spec["source"]


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_list_trend_regress_calibrate(tmp_path, capsys):
    path = str(tmp_path / "ledger.jsonl")
    for rate in (1000.0, 1000.0, 700.0):
        ledger.append(path, dict(
            _run_record(rate=rate), fingerprint=ledger.config_fingerprint(
                {"tp": 2, "pp": 1}),
            predicted={"flops_per_step": 1e9}))
    assert ledger.main(["list", path, "--format", "json"]) == 0
    rows = json.loads(capsys.readouterr().out)
    assert len(rows) == 3 and rows[0]["tokens_per_sec_p50"] == 1000.0
    assert ledger.main(["trend", path, "--format", "json"]) == 0
    capsys.readouterr()
    # the seeded 30% drop exits non-zero with the machine shape on stdout
    assert ledger.main(["regress", path, "--format", "json"]) == 1
    res = json.loads(capsys.readouterr().out)
    assert res["regressed"] == ["tokens_per_sec_p50"]
    cal = str(tmp_path / "cal.json")
    assert ledger.main(["calibrate", path, "--output", cal,
                        "--format", "json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["fit"].get("peak_flops") and os.path.exists(cal)
    # a missing ledger file degrades to the empty verdict, rc 0
    assert ledger.main(["regress", str(tmp_path / "nope.jsonl")]) == 0
    capsys.readouterr()

"""Tests for apex_tpu.monitor.mfu (peak specs, roofline join, cost
extraction) and apex_tpu.monitor.report (journal analysis + the compare
regression gate, including the CLI surface)."""

import json
import math

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.monitor import MetricsJournal, mfu_metrics, peak_spec
from apex_tpu.monitor import mfu as mfu_lib
from apex_tpu.monitor import report


# ---------------------------------------------------------------------------
# mfu: peak specs
# ---------------------------------------------------------------------------


def test_peak_spec_table_rows(monkeypatch):
    monkeypatch.delenv(mfu_lib.ENV_PEAK_FLOPS, raising=False)
    monkeypatch.delenv(mfu_lib.ENV_PEAK_HBM_GBPS, raising=False)
    v4 = peak_spec("TPU v4")
    assert v4["peak_flops"] == 275e12
    assert v4["peak_hbm_bytes_per_sec"] == 1228e9
    assert v4["source"] == "table:v4"
    # device_kind variants land on the right row
    assert peak_spec("tpu TPU v5 lite")["peak_flops"] == 197e12
    assert peak_spec("cpu")["source"] == "table:cpu"
    # a device with no row is an error, never priced as some other chip
    with pytest.raises(ValueError, match="weird-accelerator"):
        peak_spec("weird-accelerator")


def test_peak_spec_env_overrides(monkeypatch):
    """The env knobs replace the table row's numbers, and the record
    says so via source='env'."""
    monkeypatch.setenv(mfu_lib.ENV_PEAK_FLOPS, "78e12")
    monkeypatch.setenv(mfu_lib.ENV_PEAK_HBM_GBPS, "900")
    spec = peak_spec("tpu v4")
    assert spec["peak_flops"] == 78e12
    assert spec["peak_hbm_bytes_per_sec"] == 900e9
    assert spec["source"] == "env"
    # malformed overrides fall back to the table row
    monkeypatch.setenv(mfu_lib.ENV_PEAK_FLOPS, "not-a-number")
    monkeypatch.delenv(mfu_lib.ENV_PEAK_HBM_GBPS, raising=False)
    spec = peak_spec("tpu v4")
    assert spec["peak_flops"] == 275e12 and spec["source"] == "table:v4"
    # one-sided override: per-knob provenance, never a blanket 'env'
    monkeypatch.setenv(mfu_lib.ENV_PEAK_FLOPS, "78e12")
    spec = peak_spec("tpu v4")
    assert spec["peak_flops"] == 78e12
    assert spec["peak_hbm_bytes_per_sec"] == 1228e9  # datasheet kept
    assert spec["source"] == "flops:env|hbm:table:v4"
    # a malformed HBM knob must not discard the valid FLOPS one
    monkeypatch.setenv(mfu_lib.ENV_PEAK_HBM_GBPS, "fast")
    spec = peak_spec("tpu v4")
    assert spec["peak_flops"] == 78e12
    assert spec["source"] == "flops:env|hbm:table:v4"


# ---------------------------------------------------------------------------
# mfu: roofline join
# ---------------------------------------------------------------------------

_SPEC = {"platform": "test", "peak_flops": 100e12,
         "peak_hbm_bytes_per_sec": 1e12, "source": "test"}


def test_mfu_metrics_compute_bound():
    # 10 TFLOP + 0.1 GB in 0.2 s: mfu 0.5, bw_util 0.0005 -> compute-bound
    m = mfu_metrics(flops=10e12, bytes_accessed=1e8, wall_s=0.2, spec=_SPEC)
    assert m["mfu"] == pytest.approx(0.5, abs=1e-4)
    assert m["hbm_bw_util"] == pytest.approx(5e-4, abs=1e-4)
    assert m["bound"] == "compute"
    assert m["achieved_tflops"] == pytest.approx(50.0, abs=0.01)
    assert m["ridge_intensity"] == pytest.approx(100.0, abs=0.01)
    assert m["peak_source"] == "test"


def test_mfu_metrics_memory_bound_and_balanced():
    # 1 GFLOP + 100 GB: memory time 0.1 s >> compute time 1e-5 s
    m = mfu_metrics(flops=1e9, bytes_accessed=100e9, wall_s=0.5, spec=_SPEC)
    assert m["bound"] == "memory"
    # on the ridge (intensity == peak_flops/peak_bw = 100): balanced
    m = mfu_metrics(flops=100e12, bytes_accessed=1e12, wall_s=1.0, spec=_SPEC)
    assert m["bound"] == "balanced"


def test_mfu_metrics_degenerate_inputs():
    assert "mfu" not in mfu_metrics(flops=1e12, bytes_accessed=1e9,
                                    wall_s=0.0, spec=_SPEC)
    m = mfu_metrics(flops=0.0, bytes_accessed=0.0, wall_s=1.0, spec=_SPEC)
    assert m["mfu"] == 0.0 and "bound" not in m


def test_traced_step_costs_matmul():
    costs = mfu_lib.traced_step_costs(
        lambda a, b: a @ b, jnp.ones((16, 32)), jnp.ones((32, 8)))
    assert costs["flops"] == 2 * 16 * 8 * 32
    # algorithmic bytes: operands + result, f32
    assert costs["bytes"] == (16 * 32 + 32 * 8 + 16 * 8) * 4
    assert costs["method"] == "jaxpr"


def test_compiled_step_costs_with_jaxpr_floor():
    compiled = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((16, 32)), jnp.ones((32, 8))).compile()
    costs = mfu_lib.compiled_step_costs(compiled)
    assert costs["flops"] > 0 and costs["bytes"] > 0
    # the jaxpr floor wins when the cost model undercounts (Pallas case)
    floored = mfu_lib.compiled_step_costs(compiled, jaxpr_flops=1e18)
    assert floored["flops"] == 1e18
    assert floored["method"] == "cost_model+jaxpr"


def test_pyprof_program_costs_join():
    from apex_tpu.pyprof import program_costs

    costs = program_costs(lambda a, b: a @ b,
                          jnp.ones((16, 32)), jnp.ones((32, 8)))
    assert costs["flops"] >= 2 * 16 * 8 * 32
    assert costs["flops_jaxpr"] == 2 * 16 * 8 * 32
    assert "bytes_accessed" in costs and "flops_undercounted" in costs


def test_journal_step_costs_arm_mfu_fields(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with MetricsJournal(path) as j:
        j.set_step_costs(flops_per_token=1e9, bytes_per_token=1e6,
                         platform="tpu v4")
        j.step_end(step=0, loss=jnp.asarray(1.0), tokens=1000, wall_s=0.1)
        j.step_end(step=1, loss=jnp.asarray(1.0))  # no tokens: no mfu
    rows = [r for r in MetricsJournal.read(path) if r["kind"] == "step"]
    # 1e12 flops / 0.1 s = 10 TF/s over the 275 TF/s v4 peak
    assert rows[0]["mfu"] == pytest.approx(1e13 / 275e12, abs=1e-4)
    assert rows[0]["hbm_bw_util"] == pytest.approx(1e10 / 1228e9, abs=1e-4)
    assert rows[0]["bound"] == "compute"
    assert "mfu" not in rows[1]


# ---------------------------------------------------------------------------
# report: analysis
# ---------------------------------------------------------------------------


def _step(step, ts, rate=1000.0, loss=2.0, rank=0, **extra):
    rec = {"v": 1, "kind": "step", "step": step, "ts": ts, "wall_s": 0.1,
           "tokens": 100, "tokens_per_sec": rate, "loss": loss,
           "rank": rank, "overflows": 0}
    rec.update(extra)
    return rec


def test_analyze_percentiles_and_stalls():
    # steady 1 s cadence with one 30 s hole after step 4
    recs = [_step(i, 100.0 + i + (29.0 if i > 4 else 0.0),
                  rate=900.0 + 20 * i) for i in range(10)]
    a = report.analyze(recs)
    assert a["step_records"] == 10
    assert a["tokens_per_sec"]["p50"] == pytest.approx(990.0, abs=1.0)
    assert a["tokens_per_sec"]["min"] == 900.0
    assert a["stalls"]["count"] == 1
    assert a["stalls"]["gaps"][0]["after_step"] == 4
    assert a["stalls"]["gaps"][0]["gap_s"] == pytest.approx(30.0, abs=0.1)


def test_analyze_loss_spikes_and_nonfinite():
    recs = [_step(i, 100.0 + i, loss=1.0) for i in range(8)]
    recs.append(_step(8, 108.0, loss=50.0))                    # spike
    nan_rec = _step(9, 109.0)
    nan_rec["loss"] = None
    nan_rec["nonfinite_keys"] = ["loss"]                       # sanitized NaN
    recs.append(nan_rec)
    a = report.analyze(recs)
    assert a["loss"]["spike_count"] == 1
    assert a["loss"]["spikes"][0]["step"] == 8
    assert a["loss"]["nonfinite_count"] == 1
    assert a["loss"]["nonfinite_steps"] == [9]


def test_analyze_hbm_trend_and_ranks_and_comm():
    recs = []
    for i in range(6):
        recs.append(_step(i, 100.0 + i, rate=1000.0, rank=0,
                          hbm={"live_bytes": 1000 + 100 * i}))
        recs.append(_step(i, 100.2 + i, rate=500.0, rank=1))
    recs.append({"kind": "meta", "ts": 99.0,
                 "comm_bytes_by_axis": {"data": {"bytes": 4096, "calls": 2},
                                        "model": {"bytes": 512, "calls": 1}}})
    a = report.analyze(recs)
    assert a["hbm"]["growth_bytes"] == 500
    assert a["hbm"]["trend_bytes_per_sample"] == pytest.approx(100.0, abs=1.0)
    assert a["ranks"]["straggler_rank"] == 1
    assert a["ranks"]["skew"] == pytest.approx(2.0, abs=0.01)
    assert a["comm_bytes_by_axis"]["data"] == {"bytes": 4096, "calls": 2}


def test_analyze_mfu_forensics_recompile_rollups():
    recs = [_step(i, 100.0 + i, mfu=0.3 + 0.01 * i, hbm_bw_util=0.5,
                  bound="compute", peak_source="env") for i in range(5)]
    recs.append({"kind": "forensics", "ts": 105.0, "trigger": "overflow",
                 "nonfinite_groups": ["layers"]})
    recs.append({"kind": "recompile", "ts": 106.0, "fn": "train_step",
                 "signature": "f32[8]", "compile_s": 1.5})
    recs.append({"kind": "recompile", "ts": 107.0, "fn": "train_step",
                 "signature": "f32[16]", "compile_s": 2.5})
    a = report.analyze(recs)
    assert a["mfu"]["p50"] == pytest.approx(0.32, abs=1e-6)
    assert a["mfu"]["bound"] == {"compute": 5}
    assert a["mfu"]["peak_source"] == "env"
    assert a["forensics"] == {"count": 1, "by_trigger": {"overflow": 1},
                              "nonfinite_groups": ["layers"]}
    assert a["recompiles"]["train_step"] == {"compiles": 2, "compile_s": 4.0,
                                             "signatures": 2}


def test_analyze_empty_and_render_smoke(capsys):
    a = report.analyze([])
    assert a["step_records"] == 0 and a["overflows"] == 0
    report.render(a)
    report.render(report.analyze(
        [_step(0, 100.0, hbm={"live_bytes": 10}, mfu=0.5, bound="compute")]))
    out = capsys.readouterr().out
    assert "records:" in out and "throughput" in out


# ---------------------------------------------------------------------------
# report: compare gate
# ---------------------------------------------------------------------------


def test_compare_ok_and_regressed():
    a = [_step(i, 100.0 + i, rate=1000.0) for i in range(8)]
    same = report.compare(a, list(a))
    assert same["ok"] and not same["regressed"]
    b = [_step(i, 100.0 + i, rate=800.0) for i in range(8)]  # -20%
    res = report.compare(a, b, threshold=0.05)
    assert not res["ok"] and "tokens_per_sec_p50" in res["regressed"]
    # within threshold: ok
    c = [_step(i, 100.0 + i, rate=970.0) for i in range(8)]  # -3%
    assert report.compare(a, c, threshold=0.05)["ok"]


def test_analyze_timeline_section():
    recs = [_step(i, 100.0 + i, bubble_fraction=0.27,
                  bubble_fraction_expected=0.25, overlap_fraction=0.4,
                  compute_frac=0.7, comm_frac=0.2, stall_frac=0.1)
            for i in range(4)]
    tl = report.analyze(recs)["timeline"]
    assert tl["bubble_fraction"] == {"last": 0.27, "p50": 0.27}
    assert tl["bubble_fraction_expected"] == 0.25
    assert tl["overlap_fraction"]["p50"] == 0.4
    assert tl["compute_frac_mean"] == 0.7
    assert "timeline" not in report.analyze(
        [_step(i, 100.0 + i) for i in range(4)])


def test_compare_overlap_threshold_gate():
    """The comm/compute overlap fraction gates like throughput (higher is
    better, so must_not_drop) — the machine gate for the ZeRO-3
    double-buffered gather work."""
    a = [_step(i, 100.0 + i, overlap_fraction=0.60) for i in range(6)]
    worse = [_step(i, 100.0 + i, overlap_fraction=0.40) for i in range(6)]
    res = report.compare(a, worse, overlap_threshold=0.10)
    assert "overlap_fraction_p50" in res["regressed"]
    # within tolerance: ok
    near = [_step(i, 100.0 + i, overlap_fraction=0.57) for i in range(6)]
    assert report.compare(a, near, overlap_threshold=0.10)["ok"]
    # defaults to --threshold when unset
    res2 = report.compare(a, worse, threshold=0.05)
    assert "overlap_fraction_p50" in res2["regressed"]
    # a HIGHER overlap (the prefetch-improvement direction) never
    # regresses, and absent stamps skip the check
    better = [_step(i, 100.0 + i, overlap_fraction=0.90) for i in range(6)]
    assert report.compare(a, better)["ok"]
    plain = [_step(i, 100.0 + i) for i in range(6)]
    res3 = report.compare(plain, plain, overlap_threshold=0.10)
    assert "overlap_fraction_p50" not in [c["check"] for c in res3["checks"]]
    # CLI surface
    import os
    import tempfile

    d = tempfile.mkdtemp(prefix="apex_tpu_overlap_gate_")
    try:
        pa, pb = os.path.join(d, "a.jsonl"), os.path.join(d, "b.jsonl")
        for path, rows in ((pa, a), (pb, worse)):
            with open(path, "w") as f:
                for r in rows:
                    f.write(json.dumps(r) + "\n")
        import contextlib
        import io

        with contextlib.redirect_stdout(io.StringIO()):
            assert report.main(
                ["compare", pa, pb, "--overlap-threshold", "0.10"]) == 1
            assert report.main(
                ["compare", pa, pa, "--overlap-threshold", "0.10"]) == 0
    finally:
        import shutil

        shutil.rmtree(d, ignore_errors=True)


def test_compare_bubble_threshold_gate():
    a = [_step(i, 100.0 + i, bubble_fraction=0.20) for i in range(6)]
    worse = [_step(i, 100.0 + i, bubble_fraction=0.30) for i in range(6)]
    res = report.compare(a, worse, bubble_threshold=0.10)
    assert "bubble_fraction_p50" in res["regressed"]
    # within tolerance (threshold 0.10 + the 0.01 abs slack): ok
    near = [_step(i, 100.0 + i, bubble_fraction=0.225) for i in range(6)]
    assert report.compare(a, near, bubble_threshold=0.10)["ok"]
    # bubble_threshold defaults to --threshold when unset
    res2 = report.compare(a, worse, threshold=0.05)
    assert "bubble_fraction_p50" in res2["regressed"]
    # a LOWER bubble (the schedule-improvement direction) never regresses
    better = [_step(i, 100.0 + i, bubble_fraction=0.05) for i in range(6)]
    assert report.compare(a, better)["ok"]
    # CLI surface
    import os
    import tempfile

    d = tempfile.mkdtemp()
    try:
        pa, pb = os.path.join(d, "a.jsonl"), os.path.join(d, "b.jsonl")
        for path, rows in ((pa, a), (pb, worse)):
            with open(path, "w") as f:
                for r in rows:
                    f.write(json.dumps(r) + "\n")
        assert report.main(
            ["compare", pa, pb, "--bubble-threshold", "0.1", "--json"]) == 1
        assert report.main(
            ["compare", pa, pb, "--bubble-threshold", "0.6", "--json"]) == 0
    finally:
        import shutil

        shutil.rmtree(d, ignore_errors=True)


def test_shared_tolerance_predicates():
    """The one predicate pair every fractional gate shares (satellite:
    no copy-pasted tolerance handling per metric)."""
    drop = report.must_not_drop(0.05)
    assert drop(100.0, 94.9) and not drop(100.0, 95.1)
    grow = report.must_not_grow(0.05)
    assert grow(100.0, 105.1) and not grow(100.0, 104.9)
    slack = report.must_not_grow(0.10, slack=0.01)
    assert not slack(0.0, 0.009) and slack(0.0, 0.011)


def test_compare_overflow_and_hbm_and_nonfinite_regressions():
    a = [_step(i, 100.0 + i, hbm={"live_bytes": 1000}) for i in range(6)]
    b = [dict(_step(i, 100.0 + i, hbm={"live_bytes": 1000 + 50_000_000 * i}),
              overflows=3) for i in range(6)]
    res = report.compare(a, b)
    assert "overflow_rate" in res["regressed"]
    assert "hbm_growth_bytes" in res["regressed"]
    n = [_step(i, 100.0 + i) for i in range(6)]
    n[3] = dict(n[3], loss=None, nonfinite_keys=["loss"])
    assert "nonfinite_losses" in report.compare(a, n)["regressed"]


def test_compare_overflow_rate_tolerates_warmup_and_length():
    """A longer healthy run with the same per-step overflow rate (or a
    couple of warmup overflows) must not regress; a rate explosion must."""
    a = [dict(_step(i, 100.0 + i), overflows=min(i, 2)) for i in range(100)]
    b = [dict(_step(i, 100.0 + i), overflows=min(i, 3)) for i in range(200)]
    assert report.compare(a, b)["ok"]  # 2/100 vs 3/200: rate went DOWN
    bad = [dict(_step(i, 100.0 + i), overflows=i) for i in range(100)]
    assert "overflow_rate" in report.compare(a, bad)["regressed"]


def test_compare_mfu_skipped_on_peak_source_mismatch():
    """An env-calibrated baseline vs a datasheet candidate must not fake
    an MFU regression — the check is skipped and labelled."""
    a = [_step(i, 100.0 + i, mfu=0.8, peak_source="env") for i in range(6)]
    b = [_step(i, 100.0 + i, mfu=0.2, peak_source="table:v4")
         for i in range(6)]
    res = report.compare(a, b)
    row = next(c for c in res["checks"] if c["check"] == "mfu_p50")
    assert row.get("skipped") == "peak_source mismatch"
    assert not row["regressed"] and res["ok"]
    # same provenance: the 4x drop IS a regression
    b2 = [_step(i, 100.0 + i, mfu=0.2, peak_source="env") for i in range(6)]
    assert "mfu_p50" in report.compare(a, b2)["regressed"]


def test_compare_fails_candidate_with_no_step_records():
    """A candidate that crashed before journaling any step must FAIL the
    gate, not skip every signal check and pass green."""
    a = [_step(i, 100.0 + i) for i in range(5)]
    res = report.compare(a, [{"kind": "meta", "ts": 99.0}])
    assert not res["ok"] and "step_records" in res["regressed"]
    # two empty journals compare as equals (nothing to regress FROM)
    assert report.compare([], [])["ok"]


def test_compare_missing_signals_are_skipped():
    """Journals without mfu/hbm rows: those checks silently skip rather
    than crash or false-positive."""
    a = [_step(i, 100.0 + i) for i in range(4)]
    res = report.compare(a, list(a))
    names = {c["check"] for c in res["checks"]}
    assert "mfu_p50" not in names and "hbm_growth_bytes" not in names
    assert res["ok"]


# ---------------------------------------------------------------------------
# report: CLI (the operator surface)
# ---------------------------------------------------------------------------


def _write_journal(path, rate, steps=6):
    with MetricsJournal(str(path)) as j:
        for i in range(steps):
            j.step_end(step=i, loss=jnp.asarray(2.0 - 0.1 * i),
                       tokens=1024, wall_s=1024.0 / rate)


def test_cli_report_and_json(tmp_path, capsys):
    path = tmp_path / "run.jsonl"
    _write_journal(path, rate=2000.0)
    assert report.main([str(path)]) == 0
    assert "throughput tok/s" in capsys.readouterr().out
    assert report.main([str(path), "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["step_records"] == 6
    assert payload["tokens_per_sec"]["p50"] == pytest.approx(2000.0, rel=1e-3)


def test_cli_compare_exit_codes(tmp_path, capsys):
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _write_journal(a, rate=2000.0)
    _write_journal(b, rate=1000.0)
    assert report.main(["compare", str(a), str(a)]) == 0
    assert report.main(["compare", str(a), str(b)]) == 1
    capsys.readouterr()
    assert report.main(["compare", str(a), str(b), "--json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert "tokens_per_sec_p50" in payload["regressed"]
    # a generous threshold accepts the 2x drop
    assert report.main(["compare", str(a), str(b),
                        "--threshold", "0.9"]) == 0


def test_cli_tolerates_truncated_journal(tmp_path, capsys):
    """A watchdog-killed run's torn final line must not kill the report
    (the whole point of a crash-time journal)."""
    path = tmp_path / "torn.jsonl"
    _write_journal(path, rate=2000.0)
    with open(path, "a") as f:
        f.write('{"v": 1, "kind": "step", "step": 6, "tokens_per')
    assert report.main([str(path), "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["truncated"] is True
    assert payload["step_records"] == 6


def test_report_loss_ignores_scaled_nan_free_floats(tmp_path):
    """math.isfinite guard sanity: plain inf in a record round-trips as
    null via the journal, and analyze counts it non-finite."""
    path = tmp_path / "inf.jsonl"
    with MetricsJournal(str(path)) as j:
        j.step_end(step=0, loss=jnp.asarray(float("inf")), tokens=10,
                   wall_s=0.1)
    rows = MetricsJournal.read(path)
    steps = [r for r in rows if r["kind"] == "step"]
    assert steps[0]["loss"] is None
    assert "loss" in steps[0]["nonfinite_keys"]
    a = report.analyze(rows)
    assert a["loss"]["nonfinite_count"] == 1


def test_report_rolls_up_opt_state_bytes(tmp_path):
    """Journals armed with set_opt_state_bytes (the ZeRO bytes/rank ÷ dp
    claim) roll up into analyze() and the rendered view."""
    import io

    path = tmp_path / "zero.jsonl"
    with MetricsJournal(str(path)) as j:
        j.set_opt_state_bytes(512 << 20)
        for step in range(3):
            j.step_end(step=step, loss=jnp.float32(2.0), tokens=1024,
                       wall_s=0.1)
    a = report.analyze(MetricsJournal.read(path))
    assert a["opt_state_bytes"] == {"last": 512 << 20, "peak": 512 << 20}
    buf = io.StringIO()
    report.render(a, file=buf)
    assert "opt state: 536.9 MB/rank" in buf.getvalue()


def test_percentile_helper():
    assert report._percentile([1.0], 0.5) == 1.0
    assert report._percentile([1.0, 2.0, 3.0, 4.0], 0.5) == 2.5
    assert report._percentile([1.0, 2.0, 3.0], 0.0) == 1.0
    assert report._percentile([1.0, 2.0, 3.0], 1.0) == 3.0
    assert math.isclose(report._percentile([0.0, 10.0], 0.9), 9.0)

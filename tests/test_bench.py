"""Unit tests for bench.py's degradation machinery (no TPU, no heavy
compute): the OOM-cause chain walk, the headline salvage contract (the O2
value must survive an unplaceable fp32 baseline — VERDICT r3 ask #1), and
the degraded-rung ladder. The measurement paths themselves are exercised
on-chip by the driver's bench run.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402


def test_stats_median_min_max():
    s = bench._stats([3.0, 1.0, 2.0])
    assert s == {"median": 2.0, "min": 1.0, "max": 3.0, "windows": 3}
    s = bench._stats([4.0, 1.0, 2.0, 3.0])
    assert s["median"] == 2.5


def test_qcomm_env_value_mapping(monkeypatch):
    """BENCH_QCOMM: '1' aliases int8, explicit dtypes pass through,
    unset/empty means the exact fp32 wire."""
    monkeypatch.delenv("BENCH_QCOMM", raising=False)
    assert bench._qcomm_env() is None
    monkeypatch.setenv("BENCH_QCOMM", "")
    assert bench._qcomm_env() is None
    monkeypatch.setenv("BENCH_QCOMM", "1")
    assert bench._qcomm_env() == "int8"
    monkeypatch.setenv("BENCH_QCOMM", "e5m2")
    assert bench._qcomm_env() == "e5m2"
    monkeypatch.setenv("BENCH_QCOMM", "INT8")
    assert bench._qcomm_env() == "int8"


def test_is_oom_walks_cause_chain():
    assert bench._is_oom(RuntimeError("RESOURCE_EXHAUSTED: TPU oom"))
    # the ladder re-raises with the allocator message embedded
    assert bench._is_oom(RuntimeError("O2: OOM even at batch 1; last: x"))
    inner = ValueError("RESOURCE_EXHAUSTED: hbm")
    outer = RuntimeError("wrapper without the marker")
    outer.__cause__ = inner
    assert bench._is_oom(outer)
    assert not bench._is_oom(ValueError("unrelated failure"))


def _stats_of(m):
    return {"median": m, "min": m, "max": m, "windows": 3}


def test_headline_evidence_full_record(monkeypatch):
    monkeypatch.setattr(bench, "gpt_headline", lambda *a, **k: (
        _stats_of(100.0), _stats_of(40.0), 8, True))
    frag, errs = bench._gpt_headline_evidence(8, 1024, 10)
    assert errs == {}
    assert frag["value"] == 100.0
    assert frag["vs_baseline"] == 2.5
    assert frag["spread"]["interleaved"] is True
    assert "effective_batch" not in frag  # common == requested batch


def test_headline_evidence_salvages_value_without_baseline(monkeypatch):
    """When the fp32 leg is unplaceable, the O2 value is still reported
    and vs_baseline is omitted with an errors.baseline note — losing the
    ratio must not lose the headline."""
    monkeypatch.setattr(bench, "gpt_headline", lambda *a, **k: (
        _stats_of(100.0), None, 4, False))
    frag, errs = bench._gpt_headline_evidence(8, 1024, 10)
    assert frag["value"] == 100.0
    assert "vs_baseline" not in frag
    assert frag["effective_batch"] == 4
    assert "baseline" in errs


def test_headline_evidence_records_total_failure(monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("O2: OOM even at batch 1; last: RESOURCE_EXHAUSTED")

    monkeypatch.setattr(bench, "gpt_headline", boom)
    frag, errs = bench._gpt_headline_evidence(8, 1024, 10)
    assert frag == {}
    assert "headline" in errs


def test_headline_evidence_reraises_non_oom(monkeypatch):
    def boom(*a, **k):
        raise ValueError("a real bug, not memory pressure")

    monkeypatch.setattr(bench, "gpt_headline", boom)
    with pytest.raises(ValueError):
        bench._gpt_headline_evidence(8, 1024, 10)


def test_watchdog_passes_through_child_json(monkeypatch, capsys):
    """A healthy child's JSON line is printed verbatim."""
    # -S skips site imports so the stub children start fast enough to
    # beat the deadline
    code = "import json; print(json.dumps({'value': 42}))"
    monkeypatch.setenv("BENCH_DEADLINE", "30")
    rc = bench._watchdog(cmd=[sys.executable, "-S", "-c", code])
    assert rc == 0
    assert '"value": 42' in capsys.readouterr().out


def test_watchdog_prints_partial_on_hang(monkeypatch, capsys):
    """A WEDGED child (the r5 regime: device calls never return)
    is killed at the deadline and its last per-stage checkpoint is
    printed with a watchdog error — the JSON line survives no matter
    what."""
    import json as _json

    code = (
        "import json, os, time\n"
        "with open(os.environ['BENCH_PARTIAL_PATH'], 'w') as f:\n"
        "    json.dump({'value': 7.0, 'metric': 'm'}, f)\n"
        "time.sleep(60)\n"
    )
    monkeypatch.setenv("BENCH_DEADLINE", "5")
    rc = bench._watchdog(cmd=[sys.executable, "-S", "-c", code])
    assert rc == 0
    rec = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["value"] == 7.0
    assert "watchdog" in rec["errors"]


def test_watchdog_recovers_partial_on_child_crash(monkeypatch, capsys):
    """A child that DIES with no stdout (segfault/abort in the native
    plugin) must not end the round with no JSON line — the partial
    checkpoint is recovered exactly as in the hang case."""
    import json as _json

    code = (
        "import json, os, sys\n"
        "with open(os.environ['BENCH_PARTIAL_PATH'], 'w') as f:\n"
        "    json.dump({'value': 9.0, 'metric': 'm'}, f)\n"
        "os._exit(134)\n"  # simulated SIGABRT death, nothing printed
    )
    monkeypatch.setenv("BENCH_DEADLINE", "30")
    rc = bench._watchdog(cmd=[sys.executable, "-S", "-c", code])
    assert rc == 0
    rec = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["value"] == 9.0
    assert "no JSON line" in rec["errors"]["watchdog"]


def test_watchdog_hang_before_any_checkpoint(monkeypatch, capsys):
    import json as _json

    monkeypatch.setenv("BENCH_DEADLINE", "2")
    rc = bench._watchdog(
        cmd=[sys.executable, "-S", "-c", "import time; time.sleep(30)"])
    assert rc == 0
    rec = _json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["value"] is None
    assert "watchdog" in rec["errors"]


def test_o0_evidence_success(monkeypatch):
    """The fresh-process fp32 leg returns stats + the batch it landed at
    (the parent states both batches when computing the per-token ratio)."""
    rung = {"remat": "full", "scan": 8, "unroll": True}
    monkeypatch.setattr(bench, "measure_resilient",
                        lambda *a, **k: ([40.0, 41.0, 42.0], 4, rung))
    frag, errs = bench._gpt_o0_evidence(8, 1024, 10)
    assert errs == {}
    assert frag["o0"]["median"] == 41.0
    assert frag["o0"]["batch"] == 4
    assert frag["o0"]["rung"] == rung  # the record shows WHICH rung ran


def test_o0_evidence_records_oom(monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("O0: OOM even at batch 1; last: RESOURCE_EXHAUSTED")

    monkeypatch.setattr(bench, "measure_resilient", boom)
    frag, errs = bench._gpt_o0_evidence(8, 1024, 10)
    assert frag == {}
    assert "o0_baseline" in errs


def test_o0_evidence_reraises_non_oom(monkeypatch):
    def boom(*a, **k):
        raise ValueError("a real bug, not memory pressure")

    monkeypatch.setattr(bench, "measure_resilient", boom)
    with pytest.raises(ValueError):
        bench._gpt_o0_evidence(8, 1024, 10)


def test_degraded_evidence_falls_to_smaller_rung(monkeypatch):
    calls = []

    def fake(batch, seq, steps, windows=3, hidden=None, layers=None):
        calls.append((hidden, layers))
        if hidden == 768:
            raise RuntimeError("O2: OOM even at batch 1; last: RESOURCE_EXHAUSTED")
        return _stats_of(50.0), _stats_of(25.0), 2, True

    monkeypatch.setattr(bench, "gpt_headline", fake)
    frag, errs = bench._gpt_degraded_evidence(4, 1024, 10)
    assert calls == [(768, 12), (512, 4)]
    d = frag["gpt_degraded"]
    assert d["hidden"] == 512 and d["layers"] == 4
    assert d["tokens_per_sec"] == 50.0 and d["vs_baseline"] == 2.0
    # the 768 failure is recorded even though the 512 rung succeeded
    assert "gpt_degraded" in errs


def test_degraded_evidence_handles_missing_baseline(monkeypatch):
    monkeypatch.setattr(bench, "gpt_headline", lambda *a, **k: (
        _stats_of(50.0), None, 2, False))
    frag, _ = bench._gpt_degraded_evidence(4, 1024, 10)
    d = frag["gpt_degraded"]
    assert d["tokens_per_sec"] == 50.0
    assert "vs_baseline" not in d and "o0" not in d["spread"]


# -- BERT + profile degraded-rung ladders (VERDICT r5 top_next: every
# flagship config must carry a number with rung provenance, not an errors
# entry, under simulated co-tenant OOM) ------------------------------------


def _oom(msg="RESOURCE_EXHAUSTED: simulated co-tenant occupation"):
    raise RuntimeError(msg)


def test_bert_resilient_flagship_passes_through():
    """A healthy flagship run gains NO degraded marker."""
    def measure(batch, steps, windows, hidden=None, layers=None):
        assert hidden is None and layers is None
        return dict(_stats_of(9000.0), batch=8, unroll=True)

    rec = bench.bench_bert_resilient(8, 10, 3, measure=measure)
    assert rec["median"] == 9000.0
    assert "degraded" not in rec


def test_bert_resilient_degrades_with_provenance():
    """Flagship OOM (even at batch 1) → the 768/12 rung's number is
    recorded WITH rung provenance including the flagship's OOM message."""
    calls = []

    def measure(batch, steps, windows, hidden=None, layers=None):
        calls.append((hidden, layers))
        if hidden is None:
            _oom("bert: OOM even at batch 1; last: RESOURCE_EXHAUSTED")
        return dict(_stats_of(4000.0), batch=4, unroll=True)

    rec = bench.bench_bert_resilient(8, 10, 3, measure=measure)
    assert calls == [(None, None), (768, 12)]
    assert rec["median"] == 4000.0
    assert rec["degraded"]["hidden"] == 768
    assert rec["degraded"]["layers"] == 12
    assert "RESOURCE_EXHAUSTED" in rec["degraded"]["flagship_oom"]


def test_bert_resilient_exhausted_ladder_raises_oom_marker():
    def measure(batch, steps, windows, hidden=None, layers=None):
        _oom()

    with pytest.raises(RuntimeError, match="smallest degraded rung"):
        bench.bench_bert_resilient(8, 10, 3, measure=measure)


def test_bert_resilient_reraises_non_oom():
    def measure(batch, steps, windows, hidden=None, layers=None):
        raise ValueError("a real bug, not memory pressure")

    with pytest.raises(ValueError):
        bench.bench_bert_resilient(8, 10, 3, measure=measure)


def test_profile_evidence_degrades_with_provenance(monkeypatch):
    """The --gpt-profile leg: flagship-shape OOM (the whole internal remat/
    batch ladder exhausted) → the 768/12 rung's profile is the record, with
    rung provenance, and the leg reports NO error."""
    def fake_profile(batch, seq, steps=3, hidden=None, layers=None):
        if hidden is None:
            return None, {"pyprof_345m": "RESOURCE_EXHAUSTED: hbm"}
        return {"model": f"gpt_h{hidden}_L{layers}", "batch": batch,
                "seq": seq, "total_ms": 42.0}, {}

    monkeypatch.setattr(bench, "_profile_345m", fake_profile)
    frag, errs = bench._gpt_profile_evidence(8, 1024, 10)
    assert errs == {}
    prof = frag["pyprof_scope_seconds"]
    assert prof["total_ms"] == 42.0
    assert prof["degraded"]["hidden"] == 768
    assert "RESOURCE_EXHAUSTED" in prof["degraded"]["flagship_oom"]


def test_profile_evidence_flagship_passes_through(monkeypatch):
    monkeypatch.setattr(
        bench, "_profile_345m",
        lambda batch, seq, steps=3, hidden=None, layers=None: (
            {"model": "gpt2_345m", "total_ms": 260.0}, {}))
    frag, errs = bench._gpt_profile_evidence(8, 1024, 10)
    assert errs == {}
    assert frag["pyprof_scope_seconds"]["total_ms"] == 260.0
    assert "degraded" not in frag["pyprof_scope_seconds"]


def test_profile_evidence_all_rungs_oom(monkeypatch):
    monkeypatch.setattr(
        bench, "_profile_345m",
        lambda batch, seq, steps=3, hidden=None, layers=None: (
            None, {"pyprof_345m": "RESOURCE_EXHAUSTED: hbm"}))
    frag, errs = bench._gpt_profile_evidence(8, 1024, 10)
    assert frag == {}
    assert "OOM at every profile rung" in errs["pyprof_345m"]


def test_profile_evidence_non_tpu_noop(monkeypatch):
    """Off-TPU the profile returns (None, {}) — no degradation loop, no
    error entry."""
    monkeypatch.setattr(
        bench, "_profile_345m",
        lambda batch, seq, steps=3, hidden=None, layers=None: (None, {}))
    frag, errs = bench._gpt_profile_evidence(8, 1024, 10)
    assert frag == {} and errs == {}

"""Request-scoped serving traces (ISSUE 17).

The tier-1 gate for the serve observability vertical: the serializable
TraceContext round-trips (the cross-worker handoff seam), per-request
TTFT/ITL attribution fractions sum to 1.0 per class, tail-based sampling
retains every SLO violator plus a deterministic 1-in-N compliant sample
(the rest folding into ONE bounded reqhist record), a disarmed engine
emits byte-identical token streams, journal request records carry
trace_id + attribution into report.analyze's serving-attribution rollup,
report.compare gates queue-fraction growth (and degrades a mixed
serve/train pair to a skip note while a crashed serve candidate still
fails), monitor.status surfaces the worst in-flight request, the
slo-burn alert names its dominant phase, the flight recorder dumps the
in-flight request table, Chrome export gives each sampled request its
own lane, and ledger regress gates attribution drift.
"""

import json

import pytest

import jax
import jax.numpy as jnp

from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.monitor import report, tracing
from apex_tpu.monitor.journal import MetricsJournal
from apex_tpu.serve import Engine, Request, ServeConfig
from apex_tpu.serve.reqtrace import (
    HIST_EDGES_S,
    PhaseHistogram,
    TraceContext,
    attribution_fractions,
)

TINY = dict(vocab_size=41, hidden_size=16, num_layers=1,
            num_attention_heads=2, max_seq_len=32, hidden_dropout=0.0,
            axis=None, compute_dtype=jnp.float32, remat=False)
SCFG = dict(max_batch=2, max_seq=24, block_size=8)


def make_requests():
    return [Request(prompt=[3, 1, 4, 1, 5], max_new_tokens=4,
                    request_id="a"),
            Request(prompt=[2, 7], max_new_tokens=3, request_id="b"),
            Request(prompt=[6, 2, 8], max_new_tokens=3, request_id="c")]


def frac_sum(fr):
    return sum(v for k, v in fr.items() if k.endswith("_frac"))


class TestPureHelpers:
    def test_trace_context_round_trip(self):
        ctx = TraceContext.new("r1")
        assert ctx.trace_id.startswith("req-r1-")
        d = ctx.child("span-7").to_dict()
        back = TraceContext.from_dict(json.loads(json.dumps(d)))
        assert back.trace_id == ctx.trace_id
        assert back.parent_span == "span-7"
        assert TraceContext.new("r2").trace_id != ctx.trace_id

    def test_attribution_fractions_sum_and_clip(self):
        fr = attribution_fractions(
            1.0, {"queue": 0.25, "compute": 0.5, "barrier": 0.1},
            residual="prefill_serial")
        assert frac_sum(fr) == pytest.approx(1.0, abs=1e-9)
        assert fr["queue_frac"] == 0.25 and fr["compute_frac"] == 0.5
        # components clip cumulatively to the wall; residual floors at 0
        over = attribution_fractions(
            1.0, {"compute": 5.0, "barrier": 3.0}, residual="queue")
        assert over["compute_frac"] == 1.0 and over["barrier_frac"] == 0.0
        assert over["queue_frac"] == 0.0
        assert attribution_fractions(0.0, {"compute": 1.0},
                                     residual="queue") is None

    def test_phase_histogram_bounded(self):
        h = PhaseHistogram()
        assert h.empty
        for s in (1e-6, 1e-3, 0.5, 100.0):
            h.add("ttft", s)
        h.add("itl", 0.002)
        rec = h.record()
        assert rec["kind"] == "reqhist"
        assert rec["edges_s"] == list(HIST_EDGES_S)
        ttft = rec["phases"]["ttft"]
        assert len(ttft["counts"]) == len(HIST_EDGES_S) + 1
        assert ttft["n"] == sum(ttft["counts"]) == 4
        h.reset()
        assert h.empty


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Three runs of the same tiny workload: every-request-violates
    (full retention), nothing-violates (1-in-2 sampling), disarmed."""
    model = GPTModel(GPTConfig(**TINY))
    params = model.init(jax.random.PRNGKey(0))
    d = tmp_path_factory.mktemp("reqtrace")

    vj = str(d / "violator.jsonl")
    eng_v = Engine(model, params, ServeConfig(
        slo_itl_ms=1e-6, trace_sample_n=10 ** 6, **SCFG))
    tr_v = tracing.Tracer(None, keep=True)
    with tracing.scoped(tr_v):
        with MetricsJournal(vj, meta={"run": "reqtrace_test"}) as j:
            res_v = eng_v.run(make_requests(), journal=j)

    eng_s = Engine(model, params, ServeConfig(
        slo_itl_ms=1e9, trace_sample_n=2, **SCFG))
    tr_s = tracing.Tracer(None, keep=True)
    with tracing.scoped(tr_s):
        res_s = eng_s.run(make_requests())

    eng_d = Engine(model, params, ServeConfig(**SCFG))
    res_d = eng_d.run(make_requests())
    return dict(vj=vj, eng_v=eng_v, tr_v=tr_v, res_v=res_v,
                eng_s=eng_s, tr_s=tr_s, res_s=res_s,
                eng_d=eng_d, res_d=res_d)


class TestEngineTracing:
    def test_violators_fully_retained(self, served):
        roots = [r for r in served["tr_v"].records
                 if r.get("name") == "serve.request"]
        assert len(roots) == 3
        assert served["eng_v"].trace_violators == 3
        assert all(r.get("sampled") == "slo_violation" for r in roots)
        kids = [r for r in served["tr_v"].records
                if r.get("cat") == "serve-req" and r.get("depth") == 1]
        names = {r["name"] for r in kids}
        assert {"req.queue", "req.prefill", "req.first_token_barrier",
                "req.decode_tick"} <= names, names
        assert all(r.get("request") for r in kids)

    def test_tick_span_is_the_parent_of_the_phase_spans(self, served):
        """One ``serve.tick`` per tick; prefill and decode are its
        children, so its self time is the host's scheduling."""
        phases = ("serve.prefill", "serve.prefill_chunk", "serve.decode",
                  "serve.spec")
        spans = [r for r in served["tr_v"].records
                 if r.get("kind") == "span"
                 and r["name"] in phases + ("serve.tick",)]
        ticks = [r for r in spans if r["name"] == "serve.tick"]
        assert len(ticks) == served["eng_v"].ticks > 0
        assert [t["tick"] for t in ticks] == list(range(len(ticks)))
        assert all(t["depth"] == 0 and t["cat"] == "host" for t in ticks)
        # records land at exit: a tick's children come just before it
        kids, seen = [], {p: 0 for p in phases}
        for r in spans:
            if r["name"] != "serve.tick":
                assert r["depth"] == 1, r
                kids.append(r)
                seen[r["name"]] += 1
                continue
            assert sum(k["dur_s"] for k in kids) <= r["dur_s"]
            assert all(k["ts"] >= r["ts"] - 1e-3 for k in kids)
            kids = []
        assert not kids, "a phase span outside every tick"
        assert seen["serve.prefill"] == 3 and seen["serve.decode"] > 0

    def test_tick_spans_reach_a_capture_with_no_tracer_armed(self, served):
        from chipbench import harness

        model, params = served["eng_d"].model, served["eng_d"].params
        eng = Engine(model, params, ServeConfig(**SCFG))
        with tracing.scoped(None), harness.Capture() as capture:
            eng.run(make_requests())
        host = [e for e in capture.events
                if not e["plane"].startswith("/device:")]
        ticks = [e for e in host if e["name"] == "serve.tick"]
        decodes = [e for e in host if e["name"] == "serve.decode"]
        assert len(ticks) == eng.ticks
        assert sorted(e["stats"]["tick"] for e in ticks) == list(
            range(eng.ticks))
        assert decodes and all(any(
            t["start"] <= k["start"]
            and k["start"] + k["dur"] <= t["start"] + t["dur"]
            for t in ticks) for k in decodes)

    def test_deterministic_sampling_and_histogram(self, served):
        roots = [r for r in served["tr_s"].records
                 if r.get("name") == "serve.request"]
        hists = [r for r in served["tr_s"].records
                 if r.get("kind") == "reqhist"]
        assert len(roots) == 2  # ceil(3/2) with trace_sample_n=2
        assert served["eng_s"].trace_sampled == 2
        assert len(hists) == 1
        ttft = hists[0]["phases"]["ttft"]
        assert ttft["n"] == 1  # the one non-sampled request folded here

    def test_disarmed_byte_identity_and_attribution(self, served):
        for rid, req in served["res_d"].items():
            assert req.tokens == served["res_v"][rid].tokens
            assert (req.trace or {}).get("trace_id")
            for fr in (req.attribution or {}).values():
                assert frac_sum(fr) == pytest.approx(1.0, abs=1e-3)

    def test_external_trace_context_propagates(self, served):
        """The ROADMAP item 4 seam: a context provided at submit rides
        through unchanged instead of being reassigned."""
        ext = Request(prompt=[2, 7], max_new_tokens=2, request_id="x",
                      trace={"trace_id": "upstream-1",
                             "parent_span": "root-span"})
        res = served["eng_d"].run([ext])
        assert res["x"].trace == {"trace_id": "upstream-1",
                                  "parent_span": "root-span"}

    def test_chrome_export_one_lane_per_request(self, served):
        chrome = tracing.chrome_trace(served["tr_v"].records)
        lanes = [e for e in chrome["traceEvents"]
                 if e.get("ph") == "M" and e.get("name") == "thread_name"
                 and str((e.get("args") or {}).get("name", "")
                         ).startswith("request ")]
        assert len(lanes) == 3
        req_spans = [e for e in chrome["traceEvents"]
                     if e.get("ph") == "X"
                     and (e.get("args") or {}).get("request")]
        assert req_spans and all(e["tid"] >= 16 for e in req_spans)


class TestJournalAndReport:
    def test_request_records_carry_trace_and_attribution(self, served):
        rows = MetricsJournal.read(served["vj"])
        reqs = [r for r in rows if r.get("kind") == "request"]
        assert len(reqs) == 3
        for r in reqs:
            assert r.get("trace_id")
            for fr in (r.get("attribution") or {}).values():
                assert frac_sum(fr) == pytest.approx(1.0, abs=1e-3)
        attr = (report.analyze(rows).get("serving") or {}).get(
            "attribution") or {}
        assert set(attr) == {"ttft", "itl"}
        for row in attr.values():
            assert frac_sum(row) == pytest.approx(1.0, abs=1e-3)
            assert row["n"] == 3 and row["wall_s_mean"] > 0

    def test_compare_gates_queue_inflation_and_passes_self(self, served):
        rows = MetricsJournal.read(served["vj"])
        assert report.compare(rows, rows, threshold=0.1)["ok"]
        inflated = []
        for r in rows:
            r2 = dict(r)
            if r2.get("kind") == "request" and isinstance(
                    r2.get("attribution"), dict):
                at2 = {}
                for cls, fr in r2["attribution"].items():
                    fr2 = dict(fr)
                    fr2["queue_frac"] = min(
                        (fr.get("queue_frac") or 0.0) + 0.5, 1.0)
                    others = [k for k in fr2 if k.endswith("_frac")
                              and k != "queue_frac"]
                    rest = 1.0 - fr2["queue_frac"]
                    tot = sum(fr.get(k) or 0.0 for k in others) or 1.0
                    for k in others:
                        fr2[k] = round((fr.get(k) or 0.0) * rest / tot, 4)
                    at2[cls] = fr2
                r2["attribution"] = at2
            inflated.append(r2)
        res = report.compare(rows, inflated, threshold=0.1)
        assert not res["ok"]
        assert "itl_queue_frac" in res["regressed"]
        # ONLY attribution differs, so only the queue gates may trip
        assert set(res["regressed"]) <= {"ttft_queue_frac",
                                         "itl_queue_frac"}

    def test_compare_mixed_serve_train_pair_skips_with_note(self, served):
        rows = MetricsJournal.read(served["vj"])
        train = [{"kind": "meta", "run": "train"},
                 {"kind": "step", "step": 0, "loss": 2.0, "ts": 1.0},
                 {"kind": "step", "step": 1, "loss": 1.5, "ts": 2.0}]
        for a, b, which in ((rows, train, "b"), (train, rows, "a")):
            res = report.compare(a, b, threshold=0.1)
            assert res["ok"], res["regressed"]
            note = [c for c in res["checks"]
                    if c["check"] == "serve_requests" and c.get("skipped")]
            assert note and f"no serving records in {which}" in \
                note[0]["skipped"]
            assert not any(c["check"].endswith("_queue_frac")
                           for c in res["checks"])

    def test_compare_crashed_serve_candidate_still_fails(self, served):
        rows = MetricsJournal.read(served["vj"])
        crashed = [r for r in rows if r.get("kind") != "request"]
        res = report.compare(rows, crashed, threshold=0.1)
        assert "serve_requests" in res["regressed"]


class TestOperatorSurfaces:
    def test_status_once_json_machine_parseable(self, served, capsys):
        from apex_tpu.monitor import status

        rc = status.main([served["vj"], "--once", "--format", "json"])
        assert rc == 0
        snap = json.loads(capsys.readouterr().out)
        assert snap["step_records"] > 0
        assert isinstance(snap.get("queue_depth"), (int, float))
        assert isinstance((snap.get("slo") or {}).get("attainment"),
                          (int, float))
        wr = snap.get("worst_request")
        assert isinstance(wr, dict), snap
        assert wr.get("id") is not None
        assert wr.get("phase") in ("queued", "prefill", "decode")
        assert isinstance(wr.get("age_s"), (int, float))
        assert "slot" in wr

    def test_slo_burn_alert_names_dominant_phase(self, served):
        from apex_tpu.monitor import health

        rows = MetricsJournal.read(served["vj"])
        slo_rows = [r for r in rows if r.get("kind") == "slo"]
        assert slo_rows and all(
            r.get("dominant_phase") in ("queue", "prefill_serial",
                                        "compute", "barrier")
            for r in slo_rows)
        burns = [a for a in health.scan(rows) if a["rule"] == "slo-burn"]
        assert burns and "-dominated: " in burns[0]["message"]

    def test_flight_dump_carries_inflight_table(self, served, tmp_path):
        from apex_tpu.monitor import flight

        path = str(tmp_path / "reqtrace.flight.json")
        flight.arm(path, meta={"run": "reqtrace_test"}, hooks=False)
        seen = []

        def on_tick(engine):
            if not seen:  # dump once, mid-run, with slots occupied
                seen.append(flight.dump("test"))

        try:
            served["eng_d"].run(make_requests(), on_tick=on_tick)
        finally:
            flight.disarm()
        assert seen == [path]
        dumpd = flight.load(path)
        table = dumpd.get("inflight_requests")
        assert isinstance(table, list) and table
        for row in table:
            assert row.get("phase") in ("queued", "prefill", "decode")
            assert "id" in row and "age_s" in row
        # disarm cleared the provider: a later snapshot has no table
        assert not flight.armed()

    def test_ledger_regress_gates_attribution_drift(self, served,
                                                    tmp_path):
        from apex_tpu.monitor import ledger

        path = str(tmp_path / "ledger.jsonl")
        cfg = {"run": "reqtrace_test", "tp": 1}

        def measured(queue_frac):
            return {"step_records": 4, "serving": {
                "requests": 3,
                "attribution": {"ttft": {"n": 3, "wall_s_mean": 0.1,
                                         "queue_frac": queue_frac}}}}

        for q in (0.1, 0.1, 0.5):
            ledger.append_run(path, run="reqtrace_test", config=cfg,
                              measured=measured(q))
        res = ledger.regress(ledger.read(path))
        assert "ttft_queue_frac" in res["regressed"]

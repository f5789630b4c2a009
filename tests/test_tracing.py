"""Step-anatomy tracing tests (apex_tpu/monitor/tracing.py +
schedules.traced_pipeline_timeline + the traced ZeRO step build).

Pins the tentpole claims: spans are strict JSON and crash-tolerant like
the journal; the analytic bubble floors and the anatomy fraction
invariant hold; the traced tick drive computes the SAME loss/grads as
the serial model while measuring a bubble fraction within tolerance of
the analytic floor; Chrome export is structurally loadable; and a
tracer that is DISARMED leaves the ZeRO step program byte-identical.
"""

import io
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.monitor import tracing
from apex_tpu.monitor.journal import MetricsJournal


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_span_nesting_depth_step_and_barrier():
    tr = tracing.Tracer(None, meta={"run": "t"})
    tr.step = 7
    with tr.span("step") as outer:
        with tr.span("inner", cat="compute", phase="fwd") as sp:
            sp.barrier(jnp.ones((4,)))
            sp.annotate(extra=1)
        outer.barrier(jnp.zeros(()))
    spans = [r for r in tr.records if r["kind"] == "span"]
    assert [s["name"] for s in spans] == ["inner", "step"]
    inner, outer_rec = spans
    assert inner["depth"] == 1 and outer_rec["depth"] == 0
    assert inner["step"] == 7 and outer_rec["step"] == 7
    assert inner["extra"] == 1 and inner["cat"] == "compute"
    assert 0 <= inner["dur_s"] <= outer_rec["dur_s"]


def test_span_records_error_flag_and_propagates():
    tr = tracing.Tracer(None)
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    assert tr.records[-1]["name"] == "boom"
    assert tr.records[-1]["error"] is True


def _captured(fn):
    """Host events of a plain ``jax.profiler`` capture around ``fn``."""
    from chipbench import harness

    with harness.Capture() as capture:
        fn()
    return {e["name"]: e for e in capture.events
            if not e["plane"].startswith("/device:")}


@pytest.mark.parametrize("armed", [False, True],
                         ids=["disarmed", "armed"])
def test_span_is_a_trace_annotation_on_the_profilers_clock(armed):
    """With or without a tracer, a capture holds the span under its name
    with its attrs, nested as the program nested it, and closed on the
    error path."""
    tr = tracing.Tracer(None) if armed else None

    def work():
        with tracing.maybe_span(tr, "outer", step=3) as sp:
            sp.barrier(jnp.ones(()))
            with tracing.maybe_span(tr, "inner", cat="compute", slot=1):
                pass
        with pytest.raises(RuntimeError):
            with tracing.maybe_span(tr, "boom"):
                raise RuntimeError("x")
        with tracing.maybe_span(tr, "after"):
            pass

    got = _captured(work)
    assert {"outer", "inner", "boom", "after"} <= set(got)
    end = lambda e: e["start"] + e["dur"]  # noqa: E731
    assert got["outer"]["start"] <= got["inner"]["start"]
    assert end(got["inner"]) <= end(got["outer"])
    assert got["outer"]["stats"]["step"] == 3
    assert got["inner"]["stats"]["slot"] == 1
    # the raise closed the annotation: what follows is no child of it
    assert end(got["boom"]) <= got["after"]["start"]
    assert end(got["outer"]) <= got["boom"]["start"]


def test_armed_span_enters_the_annotation_with_its_attrs(monkeypatch):
    seen = []

    class Recorder:
        def __init__(self, name, **attrs):
            self.what = (name, attrs)

        def __enter__(self):
            seen.append(("enter",) + self.what)

        def __exit__(self, *exc):
            seen.append(("exit", self.what[0], exc[0]))
            return False

    monkeypatch.setattr(tracing, "TraceAnnotation", Recorder)
    tr = tracing.Tracer(None)
    with tr.span("a", cat="compute", bucket=2):
        with pytest.raises(KeyError):
            with tr.span("b"):
                raise KeyError("k")
    assert seen == [("enter", "a", {"bucket": 2}), ("enter", "b", {}),
                    ("exit", "b", KeyError), ("exit", "a", None)]
    # the JSON-lines record is what it was: the annotation adds no field
    b, a = [r for r in tr.records if r["kind"] == "span"]
    assert set(a) == {"v", "kind", "ts", "name", "cat", "dur_s", "depth",
                      "bucket", "rank"}
    assert set(b) == {"v", "kind", "ts", "name", "cat", "dur_s", "depth",
                      "error", "rank"}
    assert (a["name"], a["cat"], a["depth"], a["bucket"]) == (
        "a", "compute", 0, 2)
    assert (b["name"], b["depth"], b["error"]) == ("b", 1, True)


def test_disarmed_span_records_nothing_and_syncs_nowhere(monkeypatch):
    def no_fetch(value):
        raise AssertionError("a disarmed span fetched from the device")

    monkeypatch.setattr(tracing, "fetch_barrier", no_fetch)
    with tracing.scoped(None):
        with tracing.maybe_span(tracing.get_tracer(), "step", step=0) as sp:
            sp.barrier(jnp.ones((2,)))
            sp.annotate(extra=1)
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    assert not hasattr(sp, "dur_s")


def test_nonfinite_span_values_serialize_strict_json():
    buf = io.StringIO()
    tr = tracing.Tracer(buf)
    tr.record("w", dur_s=float("nan"), cat="host", metric=float("inf"))
    line = buf.getvalue().strip()
    rec = json.loads(line)  # strict parser: bare NaN/Infinity would raise
    assert rec["dur_s"] is None and rec["metric"] is None
    assert sorted(rec["nonfinite_keys"]) == ["dur_s", "metric"]


def test_trace_read_tolerates_corrupt_and_truncated_lines(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    with tracing.Tracer(path) as tr:
        with tr.span("a"):
            pass
        with tr.span("b"):
            pass
    with open(path, "a") as f:
        f.write("not json at all\n")
        f.write('{"kind": "span", "name": "torn')
    rows = tracing.Tracer.read(path)
    assert len(rows) == 2
    assert rows.bad_lines == 2 and rows.truncated  # journal semantics
    # and the chrome export of the torn file still works off the prefix
    trace = tracing.chrome_trace(rows)
    assert len([e for e in trace["traceEvents"] if e["ph"] == "X"]) == 2


def test_scoped_arming_restores_previous_state():
    assert tracing.get_tracer() is None
    tr = tracing.Tracer(None)
    with tracing.scoped(tr):
        assert tracing.get_tracer() is tr
        with tracing.maybe_span(tracing.get_tracer(), "x") as sp:
            sp.barrier(1.0)
    assert tracing.get_tracer() is None
    assert tr.records and tr.records[-1]["name"] == "x"
    # maybe_span with no tracer is a no-op null span
    with tracing.maybe_span(None, "y") as sp:
        sp.barrier(1.0)
        sp.annotate(z=1)


# ---------------------------------------------------------------------------
# analytic floors + anatomy math
# ---------------------------------------------------------------------------


def test_expected_bubble_fraction_known_points():
    """Hand-computed floors for all four planners: gpipe/1f1b share
    (S-1)/(M+S-1), interleaved divides the live slots by vpp, and the
    zero-bubble W/B split lands at (S-1)/(3M+S-1) — 3M live slots per
    rank, only the S-1 fill ticks idle."""
    ebf = tracing.expected_bubble_fraction
    assert math.isclose(ebf("gpipe", 8, 4), 3 / 11)
    assert math.isclose(ebf("1f1b", 8, 4), 3 / 11)
    assert math.isclose(ebf("interleaved", 8, 4, 2), 3 / 19)
    assert math.isclose(ebf("interleaved", 4, 4, 1), 3 / 7)
    # zero-bubble hand points: S=4,M=8 -> 3/27; S=2,M=4 -> 1/13; S=3,M=3
    # -> 2/11 — each strictly below the 1f1b floor at the same (S, M)
    assert math.isclose(ebf("zero-bubble", 8, 4), 3 / 27)
    assert math.isclose(ebf("zero-bubble", 4, 2), 1 / 13)
    assert math.isclose(ebf("zero-bubble", 3, 3), 2 / 11)
    for M, S in ((8, 4), (4, 2), (3, 3)):
        assert ebf("zero-bubble", M, S) < ebf("1f1b", M, S)
    assert ebf("1f1b", 8, 1) == 0.0  # no pipeline, no bubble
    assert ebf("zero-bubble", 8, 1) == 0.0
    with pytest.raises(ValueError):
        ebf("mystery", 8, 4)
    with pytest.raises(ValueError):
        ebf("1f1b", 0, 4)


def test_schedule_plans_meet_closed_form_floors():
    """Schedule-as-data pinning: the greedy planners' COUNTED idle
    fractions equal the closed-form floors at every tested (S, M), and
    the interleaved plan mirrors the ring algebra's tick count."""
    from apex_tpu.transformer.pipeline_parallel import plan_schedule
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        pipeline_tick_count,
    )

    for sched in ("gpipe", "1f1b", "zero-bubble"):
        for S in (2, 3, 4):
            for M in (S, 4, 8):
                if M < S:
                    continue
                plan = plan_schedule(sched, M, S)
                floor = tracing.expected_bubble_fraction(sched, M, S)
                assert math.isclose(plan.bubble_fraction(), floor), (
                    sched, S, M, plan.bubble_fraction(), floor)
                want_ticks = (3 * M + S - 1 if sched == "zero-bubble"
                              else 2 * (M + S - 1))
                assert plan.ticks == want_ticks, (sched, S, M, plan.ticks)
    for vpp in (1, 2):
        plan = plan_schedule("interleaved", 4, 4, vpp)
        assert plan.ticks == 2 * pipeline_tick_count(4, 4, vpp)
        assert math.isclose(
            plan.bubble_fraction(),
            tracing.expected_bubble_fraction("interleaved", 4, 4, vpp))


def test_schedule_plan_dependencies_and_counts():
    """Replay each plan against the pipeline dependency graph: every
    (rank, microbatch) does each of its slot kinds exactly once, forwards
    arrive only after the upstream rank's forward, input-grads only after
    the downstream rank's, weight-grads only after the rank's own
    input-grad — the W/B factoring's soundness condition."""
    from apex_tpu.transformer.pipeline_parallel import plan_schedule

    for sched in ("gpipe", "1f1b", "zero-bubble"):
        S, M = 3, 4
        plan = plan_schedule(sched, M, S)
        done = {}  # (kind, s, m) -> tick
        for t in range(plan.ticks):
            for s in range(S):
                sl = plan.ranks[s][t]
                if sl.kind == "idle":
                    continue
                key = (sl.kind, s, sl.microbatch)
                assert key not in done, key
                done[key] = t
                m = sl.microbatch
                if sl.kind == "fwd" and s > 0:
                    assert done[("fwd", s - 1, m)] < t, (sched, key)
                if sl.kind in ("bwd", "bwd_input"):
                    assert done[("fwd", s, m)] < t, (sched, key)
                    if s < S - 1:
                        assert done[(sl.kind, s + 1, m)] < t, (sched, key)
                if sl.kind == "bwd_weight":
                    assert done[("bwd_input", s, m)] < t, (sched, key)
        kinds = (("fwd", "bwd_input", "bwd_weight")
                 if sched == "zero-bubble" else ("fwd", "bwd"))
        for k in kinds:
            for s in range(S):
                for m in range(M):
                    assert (k, s, m) in done, (sched, k, s, m)


def test_step_anatomy_fractions_sum_to_one():
    for wall, comp, comm in ((0.1, 0.06, 0.06), (0.1, 0.1, 0.0),
                             (0.1, 0.02, 0.01), (0.2, 0.3, 0.05),
                             (0.05, 0.0, 0.0)):
        an = tracing.step_anatomy(wall_s=wall, compute_s=comp, comm_s=comm)
        assert abs(an["compute_frac"] + an["comm_frac"]
                   + an["stall_frac"] - 1.0) < 1e-6, an
    # hand point: 60+60ms in a 100ms wall → 20ms overlapped = 1/3 of min
    an = tracing.step_anatomy(wall_s=0.1, compute_s=0.06, comm_s=0.06)
    assert abs(an["overlap_fraction"] - 1 / 3) < 1e-3
    # nothing to overlap → no overlap_fraction field
    assert "overlap_fraction" not in tracing.step_anatomy(
        wall_s=0.1, compute_s=0.05, comm_s=0.0)


def test_step_anatomy_modeled_sources_and_ici_override(monkeypatch):
    spec = {"platform": "x", "peak_flops": 1e12,
            "peak_hbm_bytes_per_sec": 1e11, "source": "test"}
    ici = {"platform": "x", "ici_bytes_per_sec": 1e9, "source": "test"}
    an = tracing.step_anatomy(wall_s=0.1, flops=5e10, comm_bytes=2e7,
                              spec=spec, ici=ici)
    assert abs(an["compute_s"] - 0.05) < 1e-9
    assert abs(an["comm_s"] - 0.02) < 1e-9
    assert an["compute_source"].startswith("cost_model")
    assert an["comm_source"].startswith("wire_model")
    monkeypatch.setenv(tracing.ENV_PEAK_ICI_GBPS, "123")
    got = tracing.ici_spec("tpu v4")
    assert got["ici_bytes_per_sec"] == 123e9 and got["source"] == "env"
    monkeypatch.delenv(tracing.ENV_PEAK_ICI_GBPS)
    got = tracing.ici_spec("tpu v4")
    assert got["ici_bytes_per_sec"] == tracing.ICI_SPECS["v4"]
    assert got["source"] == "table:v4"


def test_pipeline_anatomy_synthetic_timeline_and_chrome_export():
    # 2 ranks, 3 units, 4 ticks per direction, uniform 10ms slots:
    # one idle slot per rank per direction → bubble = 1/4 == 1F1B floor
    tr = tracing.Tracer(None)
    for phase in ("fwd", "bwd"):
        for t in range(4):
            for s in range(2):
                live = 0 <= t - s < 3
                kw = {"microbatch": t - s} if live else {}
                tr.record(phase if live else "bubble", dur_s=0.01,
                          cat="pipe", rank=s, tick=t, phase=phase, **kw)
            tr.record("send", dur_s=0.002, cat="pipe-comm", rank=0,
                      tick=t, phase=phase)
    pa = tracing.pipeline_anatomy(tr.records)
    assert math.isclose(pa["bubble_fraction"]["mean"], 0.25)
    assert math.isclose(
        pa["bubble_fraction"]["mean"],
        tracing.expected_bubble_fraction("1f1b", 3, 2))
    assert pa["ranks"]["0"]["fwd_s"] == pytest.approx(0.03)
    assert pa["ranks"]["0"]["send_s"] == pytest.approx(0.016)  # 4x2 phases
    # per-microbatch slot rollup: every unit saw one fwd and one bwd
    # slot on each of the 2 ranks
    assert pa["microbatches"]["0"]["fwd_s"] == pytest.approx(0.02)
    assert pa["microbatches"]["0"]["bwd_s"] == pytest.approx(0.02)

    trace = json.loads(json.dumps(tracing.chrome_trace(tr.records)))
    ev = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert len(ev) == 24 and {e["pid"] for e in meta} == {0, 1}
    for e in ev:
        assert {"name", "cat", "pid", "tid", "ts", "dur", "args"} <= set(e)
        assert e["ts"] >= 0 and e["dur"] >= 0
    # pipe slots ride the compute track, send/recv the comm track
    assert {e["tid"] for e in ev if e["cat"] == "pipe"} == {0}
    assert {e["tid"] for e in ev if e["cat"] == "pipe-comm"} == {1}

    summary = tracing.timeline_summary(tr.records)
    assert summary["pipeline"]["bubble_fraction"]["mean"] == 0.25
    assert summary["by_cat"]["pipe"]["count"] == 16


# ---------------------------------------------------------------------------
# journal integration (set_step_comm / set_bubble_fraction)
# ---------------------------------------------------------------------------


def test_journal_anatomy_and_bubble_fields(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with MetricsJournal(path) as j:
        j.set_step_costs(flops_per_token=1e6, bytes_per_token=10.0,
                         platform="tpu v4")
        j.set_step_comm(1e6, platform="tpu v4")
        j.set_bubble_fraction(0.27, 0.25)
        j.step_start()
        j.step_end(step=0, loss=jnp.asarray(2.0), tokens=4096)
    rec = [r for r in MetricsJournal.read(path) if r["kind"] == "step"][-1]
    # fractions round to 4dp in the record; the invariant holds to that
    assert abs(rec["compute_frac"] + rec["comm_frac"]
               + rec["stall_frac"] - 1.0) < 2e-3
    assert rec["bubble_fraction"] == 0.27
    assert rec["bubble_fraction_expected"] == 0.25
    # and the report rolls them into the timeline section
    from apex_tpu.monitor import report

    analysis = report.analyze(MetricsJournal.read(path))
    tl = analysis["timeline"]
    assert tl["bubble_fraction"]["last"] == 0.27
    assert tl["bubble_fraction_expected"] == 0.25
    assert "compute_frac_mean" in tl


# ---------------------------------------------------------------------------
# the traced pipeline tick drive (measured bubble vs analytic floor)
# ---------------------------------------------------------------------------

TINY = dict(
    vocab_size=64, hidden_size=32, num_layers=4, num_attention_heads=4,
    max_seq_len=16, hidden_dropout=0.0, compute_dtype=jnp.float32,
    remat=False)


def _drive_setup(S, vpp):
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.parallel import mesh as mesh_lib
    from apex_tpu.transformer import tensor_parallel as tp_mod
    from apex_tpu.transformer.pipeline_parallel import pipeline_specs
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        interleave_stack,
    )

    mesh = mesh_lib.make_virtual_mesh(S, pipeline_model_parallel_size=S)
    model = GPTModel(GPTConfig(axis=None, **TINY))
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
    tgt = jnp.roll(toks, -1, axis=-1)
    layer_specs = pipeline_specs(model.specs()["layers"])
    layers = params["layers"]
    if vpp > 1:
        layers = interleave_stack(layers, S, vpp)
    layers_sh = tp_mod.shard_params(layers, layer_specs, mesh)
    rest = {k: v for k, v in params.items() if k != "layers"}
    return mesh, model, params, rest, layers_sh, layer_specs, toks, tgt


def test_traced_drive_matches_serial_and_measures_bubble():
    from apex_tpu.parallel import mesh as mesh_lib
    from apex_tpu.transformer.pipeline_parallel import (
        traced_pipeline_timeline,
    )
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        deinterleave_stack,
    )

    S, vpp, M = 2, 2, 4
    mesh, model, params, rest, layers_sh, layer_specs, toks, tgt = (
        _drive_setup(S, vpp))
    try:
        tr = tracing.Tracer(None)
        loss, grads, anatomy = traced_pipeline_timeline(
            mesh, embed=model.embed,
            run_layers=lambda lp, h: model.run_layers(lp, h),
            head_loss=lambda p, h, t: model.head(p, h, t),
            rest_params=rest, layers=layers_sh, layer_specs=layer_specs,
            batch=toks, targets=tgt, num_microbatches=M,
            virtual_pipeline_size=vpp, tracer=tr, step=0)

        # equivalence: the timeline is the anatomy of the REAL function
        sl, sg = jax.value_and_grad(
            lambda p: model.loss(p, toks, tgt))(params)
        assert abs(float(loss) - float(sl)) < 1e-5
        gl = deinterleave_stack(grads["layers"], S, vpp)
        for a, b in zip(jax.tree.leaves(gl), jax.tree.leaves(sg["layers"])):
            np.testing.assert_allclose(a, b, atol=1e-5)
        for k in rest:
            for a, b in zip(jax.tree.leaves(grads[k]),
                            jax.tree.leaves(sg[k])):
                np.testing.assert_allclose(a, b, atol=1e-5)

        # measured bubble within tolerance of the analytic floor (all
        # ranks execute every tick in SPMD, so slot durations are near
        # uniform; contended-CI tolerance of half the floor + 0.04 abs)
        expected = anatomy["expected_bubble_fraction"]
        measured = anatomy["bubble_fraction"]["mean"]
        assert math.isclose(
            expected,
            tracing.expected_bubble_fraction("interleaved", M, S, vpp),
            rel_tol=1e-3)
        assert abs(measured - expected) <= max(0.04, 0.5 * expected), anatomy

        # every slot kind landed as spans; analyzer agrees with anatomy
        names = {r["name"] for r in tr.records if r.get("cat") == "pipe"}
        assert {"fwd", "bwd", "bubble"} <= names
        comm_names = {r["name"] for r in tr.records
                      if r.get("cat") == "pipe-comm"}
        assert comm_names == {"send", "recv"}
        pa = tracing.pipeline_anatomy(tr.records)
        assert pa["bubble_fraction"]["mean"] == pytest.approx(
            measured, abs=1e-6)
    finally:
        mesh_lib.destroy_model_parallel()


def test_traced_schedule_timeline_zero_bubble_beats_1f1b():
    """The plan executor's measured drive: loss AND grads equal the
    serial model for BOTH the 1f1b and zero-bubble plans, and the span
    records it emits carry the plan's own bubble: per rank, idle ticks
    over all ticks is the closed form, and the zero-bubble W/B split
    leaves fewer idle ticks than 1f1b at the same (S, M). Counts, not
    wall-clock: a CPU's tick durations follow no schedule algebra (an
    idle tick there costs next to nothing, so the measured mean reads
    0.05 where the algebra says 0.2)."""
    from apex_tpu.parallel import mesh as mesh_lib
    from apex_tpu.transformer.pipeline_parallel import (
        plan_schedule,
        traced_schedule_timeline,
    )

    S, M = 2, 4
    mesh, model, params, rest, layers_sh, layer_specs, toks, tgt = (
        _drive_setup(S, 1))
    try:
        sl, sg = jax.value_and_grad(
            lambda p: model.loss(p, toks, tgt))(params)
        idle_ticks = {}
        for sched in ("1f1b", "zero-bubble"):
            tr = tracing.Tracer(None)
            plan = plan_schedule(sched, M, S)
            loss, grads, anatomy = traced_schedule_timeline(
                plan, mesh, embed=model.embed,
                run_layers=lambda lp, h: model.run_layers(lp, h),
                head_loss=lambda p, h, t: model.head(p, h, t),
                rest_params=rest, layers=layers_sh,
                layer_specs=layer_specs, batch=toks, targets=tgt,
                tracer=tr, step=0)
            assert abs(float(loss) - float(sl)) < 1e-5, sched
            for a, b in zip(jax.tree.leaves(grads["layers"]),
                            jax.tree.leaves(sg["layers"])):
                np.testing.assert_allclose(a, b, atol=1e-5)
            for k in rest:
                for a, b in zip(jax.tree.leaves(grads[k]),
                                jax.tree.leaves(sg[k])):
                    np.testing.assert_allclose(
                        np.asarray(a, np.float32), b, atol=1e-5)
            floor = tracing.expected_bubble_fraction(sched, M, S)
            # the plan's counted floor must match the closed form, and
            # so must the ticks the drive recorded, rank by rank
            assert math.isclose(anatomy["plan_bubble_fraction"], floor,
                                abs_tol=1e-4)
            slots = [r for r in tr.records if r.get("cat") == "pipe"]
            assert len(slots) == plan.ticks * S
            idle_ticks[sched] = 0
            for rank in range(S):
                mine = [r["name"] for r in slots if r["rank"] == rank]
                assert len(mine) == plan.ticks
                assert math.isclose(mine.count("bubble") / len(mine), floor,
                                    abs_tol=1e-9), (sched, rank, mine)
                idle_ticks[sched] += mine.count("bubble")
            assert all(math.isfinite(v) and 0 <= v < 1 for v in
                       anatomy["bubble_fraction"].values()), anatomy
            # W/B spans land as bwd slots with the wb attr
            if sched == "zero-bubble":
                wb = {r.get("wb") for r in tr.records
                      if r.get("cat") == "pipe" and r.get("wb")}
                assert wb == {"B", "W"}, wb
        assert idle_ticks["zero-bubble"] < idle_ticks["1f1b"], idle_ticks
    finally:
        mesh_lib.destroy_model_parallel()


def test_untimed_schedule_tripwire_on_real_drives():
    from apex_tpu.lint import trace as lint_trace
    from apex_tpu.parallel import mesh as mesh_lib
    from apex_tpu.transformer.pipeline_parallel import (
        pipelined_loss_fn,
        traced_pipeline_timeline,
    )

    S, vpp, M = 2, 1, 4
    mesh, model, params, rest, layers_sh, layer_specs, toks, tgt = (
        _drive_setup(S, vpp))
    try:
        # the compiled ring under an armed tracer emits no spans: hazard
        pipe_loss = pipelined_loss_fn(
            embed=model.embed,
            run_layers=lambda lp, h: model.run_layers(lp, h),
            head_loss=lambda p, h, t: model.head(p, h, t),
            num_microbatches=M)
        compiled_drive = jax.shard_map(
            pipe_loss, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), rest), layer_specs,
                      P(), P()),
            out_specs=P(), check_vma=False)
        bad = lint_trace.untimed_schedule_hazards(
            lambda: jax.make_jaxpr(compiled_drive)(
                rest, layers_sh, toks, tgt))
        assert bad["hazard"] and bad["drives"] == 1
        assert bad["findings"][0]["rule"] == "untimed-schedule"

        # the traced tick drive passes: spans flow to the scoped tracer
        ok = lint_trace.untimed_schedule_hazards(
            lambda: traced_pipeline_timeline(
                mesh, embed=model.embed,
                run_layers=lambda lp, h: model.run_layers(lp, h),
                head_loss=lambda p, h, t: model.head(p, h, t),
                rest_params=rest, layers=layers_sh,
                layer_specs=layer_specs, batch=toks, targets=tgt,
                num_microbatches=M))
        assert not ok["hazard"] and ok["pipe_spans"] > 0
    finally:
        mesh_lib.destroy_model_parallel()


# ---------------------------------------------------------------------------
# traced ZeRO step: phase spans + disarmed byte-identity
# ---------------------------------------------------------------------------


def _zero_setup(traced, tracer=None):
    from apex_tpu import amp
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import mesh as mesh_lib
    from apex_tpu.transformer.amp import build_zero_train_step
    from apex_tpu.transformer.pipeline_parallel import (
        prepare_pipelined_model,
    )

    mesh = mesh_lib.make_virtual_mesh(8)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_seq_len=16,
                    hidden_dropout=0.0, compute_dtype=jnp.bfloat16,
                    remat=False)
    model = GPTModel(cfg)
    policy = amp.get_policy("O2")
    mp_opt = amp.MixedPrecisionOptimizer(
        FusedAdam(lr=1e-3), policy, zero_axis=mesh_lib.AXIS_DATA,
        zero_level=2)
    full = amp.cast_params(model.init(jax.random.PRNGKey(0)), policy)
    specs, params, pipe_loss = prepare_pipelined_model(
        model, full, mesh, num_microbatches=2)
    rest_specs = {k: v for k, v in specs.items() if k != "layers"}
    opt_state, state_specs = mp_opt.zero_init(params, mesh, specs)
    step = build_zero_train_step(
        mp_opt, mesh, specs, state_specs, pipe_loss,
        rest_specs=rest_specs,
        grad_axes=mesh_lib.get_gradient_reduction_axes(),
        data_spec=P(mesh_lib.AXIS_DATA), zero_axis=mesh_lib.AXIS_DATA,
        traced=traced, tracer=tracer)
    toks = jax.random.randint(jax.random.PRNGKey(1), (16, 16), 0, 64)
    shard = lambda a: jax.device_put(  # noqa: E731
        a, NamedSharding(mesh, P(mesh_lib.AXIS_DATA)))
    return step, params, opt_state, shard(toks), shard(
        jnp.roll(toks, -1, axis=-1))


def test_traced_zero_step_matches_untraced_and_emits_phase_spans():
    from apex_tpu.parallel import mesh as mesh_lib

    try:
        step_u, p, s, toks, tgts = _zero_setup(False)
        p_u, s_u, loss_u, _ = step_u(p, s, toks, tgts)
        mesh_lib.destroy_model_parallel()
        tr = tracing.Tracer(None)
        step_t, p, s, toks, tgts = _zero_setup(True, tr)
        p_t, s_t, loss_t, _ = step_t(p, s, toks, tgts)
        assert float(loss_u) == float(loss_t)
        for a, b in zip(jax.tree.leaves(p_u), jax.tree.leaves(p_t)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        names = [r["name"] for r in tr.records if r["kind"] == "span"]
        assert names == ["zero.grads", "zero.apply"]
        grads_span = tr.records[0]
        apply_span = tr.records[1]
        assert grads_span["cat"] == "compute"
        assert apply_span["cat"] == "comm"
        # the phase spans carry the comm-accounting join: the level-2
        # apply phase moves the psum_scatter + gather payloads
        assert apply_span["comm_bytes"] > 0
    finally:
        mesh_lib.destroy_model_parallel()


def test_disarmed_tracer_leaves_zero_step_program_byte_identical():
    """Arming the GLOBAL tracer must not change a traced=False build —
    the acceptance criterion that --trace stays an opt-in and disarmed
    harness programs are byte-identical."""
    from apex_tpu.parallel import mesh as mesh_lib

    try:
        step_a, p, s, toks, tgts = _zero_setup(False)
        text_a = step_a.lower(p, s, toks, tgts).as_text()
        mesh_lib.destroy_model_parallel()
        with tracing.scoped(tracing.Tracer(None)):
            step_b, p, s, toks, tgts = _zero_setup(False)
            text_b = step_b.lower(p, s, toks, tgts).as_text()
        assert text_a == text_b
    finally:
        mesh_lib.destroy_model_parallel()

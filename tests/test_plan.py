"""Auto-parallelism planner (ISSUE 18): blind-reproduction picks this
repo earned empirically (ZeRO-3 at 2.7B, zero-bubble at S=4/M=4, int8
wire only under a narrowed ICI), the residency pin against
monitor.hbm.param_state_report, the search-table contract, the CLI, and
the ONE shared zero3_prefetch-needs-unroll rejection text."""

import json

import jax
import pytest

from apex_tpu import plan as plan_mod

TINY = plan_mod.ModelSpec("plan-tiny", 128, 64, 4, 4, 32)


@pytest.fixture(autouse=True)
def _clean_peak_env(monkeypatch):
    """The picks are blind: no shell-leaked peak overrides or armed
    calibration file may skew the modeled clocks."""
    for k in ("APEX_TPU_PEAK_FLOPS", "APEX_TPU_PEAK_HBM_GBPS",
              "APEX_TPU_PEAK_ICI_GBPS", "APEX_TPU_CALIBRATION"):
        monkeypatch.delenv(k, raising=False)


# ---------------------------------------------------------------------------
# the three blind picks
# ---------------------------------------------------------------------------


def test_blind_pick_zero3_for_27b_under_16gib():
    """Given only shape + mesh + budget, the search lands on the
    placement-rung verdict: ZeRO-3 places a 2.7B-class model on 8 ranks
    under 16 GiB; replicated and ZeRO-1/2 carry static-hbm provenance."""
    r = plan_mod.search("gpt-2.7b", mesh=8, hbm_gb=16.0)
    w = r["winner"]["candidate"]
    assert w["zero_level"] == 3
    assert r["winner"]["predicted"]["hbm_bytes"] < 16 * 1024**3
    rej_levels = {x["candidate"]["zero_level"]
                  for x in r["rejected"]
                  if x.get("rejected_by") == "static-hbm"
                  and x["candidate"].get("dp") == 8}
    assert {0, 2} <= rej_levels
    # a rejection is auditable, not a verdict: it still carries the
    # predicted anatomy that sank it
    over = next(x for x in r["rejected"]
                if x.get("rejected_by") == "static-hbm")
    assert over["predicted"]["hbm_bytes"] > 16 * 1024**3
    assert "exceeds budget" in over["reason"]


def test_blind_pick_zerobubble_at_pinned_pp():
    """Pinned at pp=4 with 4 microbatches, the zero-bubble schedule wins
    on modeled step seconds through its lower analytic floor
    ((S-1)/(3M+S-1) vs 1F1B's (S-1)/(M+S-1))."""
    from apex_tpu.monitor import tracing

    r = plan_mod.search("gpt-345m", mesh=8, hbm_gb=16.0,
                        num_microbatches=4, constraints={"pp": 4})
    assert r["winner"]["candidate"]["schedule"] == "zerobubble"
    best = {}
    for rec in r["ranked"]:
        best.setdefault(rec["candidate"]["schedule"],
                        rec["predicted"]["step_seconds"])
    assert best["zerobubble"] < best["interleaved"] < best["1f1b"]
    assert r["winner"]["predicted"]["bubble_floor"] == pytest.approx(
        tracing.expected_bubble_fraction("zerobubble", 4, 4))


def test_blind_pick_int8_wire_only_where_ici_binds(monkeypatch):
    """The EQuARX deployment rule as feasibility: on the default wire
    model the int8 candidate is rejected wire-not-binding; narrow the
    modeled ICI and the SAME search flips to the quantized wire."""
    r = plan_mod.search("gpt-345m", mesh=8, hbm_gb=16.0,
                        constraints={"dp": 8, "zero_level": 2})
    assert r["winner"]["candidate"]["reduce_dtype"] is None
    wnb = [x for x in r["rejected"]
           if x.get("rejected_by") == "wire-not-binding"]
    assert wnb and "int8" == wnb[0]["candidate"]["reduce_dtype"]
    # so too the 13B analytic rung at mesh=64 and v4 datasheet clocks
    big = plan_mod.search("gpt-13b", mesh=64, hbm_gb=16.0,
                          num_microbatches=2, platform="v4")
    assert big["winner"]["candidate"]["reduce_dtype"] is None

    monkeypatch.setenv("APEX_TPU_PEAK_ICI_GBPS", "0.001")
    narrowed = plan_mod.search("gpt-345m", mesh=8, hbm_gb=16.0,
                               constraints={"dp": 8, "zero_level": 2})
    assert narrowed["winner"]["candidate"]["reduce_dtype"] == "int8"


# ---------------------------------------------------------------------------
# the cost model's anchors
# ---------------------------------------------------------------------------


def test_residency_columns_equal_param_state_report():
    """One cost model, no drift: the planner's ZeRO-3 param/opt columns
    at tp=pp=1 are byte-identical to monitor.hbm.param_state_report's
    (the 345M @ dp=8 710 -> 89 MB pin rides the same arithmetic)."""
    from apex_tpu.monitor.hbm import param_state_report

    spec = plan_mod.MODEL_PRESETS["gpt-345m"]
    report = param_state_report(plan_mod.abstract_params(spec), 8)
    rec = plan_mod.score_candidate(
        spec, plan_mod.Candidate(dp=8, zero_level=3, gather_dtype="bf16"))
    res = rec["predicted"]["hbm"]["residency"]
    z3 = report["per_rank"]["zero3"]
    assert res["param_bytes"] == z3["param_bytes"]
    assert res["opt_bytes"] == z3["opt_bytes"]
    # the pin itself: bf16 working params 710 -> 89 MB at dp=8
    repl = report["per_rank"]["replicated"]["param_bytes"]
    assert repl / 2**20 == pytest.approx(710, rel=0.05)
    assert z3["param_bytes"] / 2**20 == pytest.approx(89, rel=0.05)


def test_search_table_contract_and_winner_roundtrip():
    """Every ranked record carries the full predicted anatomy; the
    winner's candidate round-trips through Candidate(**...); an
    impossible budget rejects everything with named provenance."""
    r = plan_mod.search(TINY, mesh=8, hbm_gb=16.0)
    assert r["n_enumerated"] > len(r["ranked"]) > 0
    for rec in r["ranked"][:5] + [r["winner"]]:
        p = rec["predicted"]
        assert p["hbm_bytes"] > 0 and p["step_seconds"] > 0
        assert "ici" in p["comm_bytes_by_tier"]
        assert 0.0 <= p["bubble_floor"] < 1.0
    cand = plan_mod.Candidate(**r["winner"]["candidate"])
    assert cand.dp * cand.tp * cand.pp == 8

    broke = plan_mod.search(TINY, mesh=8, hbm_bytes=1 << 10)
    assert broke["winner"] is None
    assert broke["rejected"]
    assert all(x["rejected_by"] for x in broke["rejected"])


def test_search_constraints_filter_not_reject():
    """Pinning a knob narrows the space without inventing rejections."""
    r = plan_mod.search(TINY, mesh=8, hbm_gb=16.0,
                        constraints={"zero_level": 3, "pp": 1})
    assert all(rec["candidate"]["zero_level"] == 3
               and rec["candidate"]["pp"] == 1 for rec in r["ranked"])
    assert not any(x["rejected_by"].startswith("constraint:zero_level")
                   for x in r["rejected"])


def test_cli_json_and_bad_model(capsys):
    from apex_tpu.plan.__main__ import main

    rc = main(["--model", "128,64,4,4,32", "--mesh", "8",
               "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["winner"] is not None
    assert out["ranked"][0] == out["winner"]
    assert main(["--model", "gpt-9000t"]) == 2


# ---------------------------------------------------------------------------
# the shared rejection text (tentpole satellite: one message, two sites)
# ---------------------------------------------------------------------------


def test_zero3_prefetch_needs_unroll_message_shared():
    """run_layers (trace time) and build_zero_train_step (build time)
    reject a prefetch-without-unroll config with the SAME constant — the
    harness/audit asymmetry was a config that built fine and only died
    deep inside the first trace."""
    import types

    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.models._transformer import ZERO3_PREFETCH_NEEDS_UNROLL
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.plan.search import model_config_kwargs
    from apex_tpu.transformer.amp import build_zero_train_step

    kw = model_config_kwargs(TINY)
    kw.update(remat=True, zero3_prefetch=1)  # unroll_layers NOT set
    model = GPTModel(GPTConfig(**kw))
    abstract = plan_mod.abstract_params(TINY)
    mp3 = amp.MixedPrecisionOptimizer(
        FusedAdam(lr=1e-4), amp.get_policy("O2"), zero_axis="data",
        zero_level=3)
    meta = mp3.zero3_meta(abstract)
    layer_meta = meta.subtree("layers")
    rest_meta = meta.select([k for k in meta.shapes if k != "layers"])
    toks = jax.ShapeDtypeStruct((1, TINY.seq), jnp.int32)

    def zero3_loss(p, t):
        from apex_tpu.optimizers.distributed import gather_chunked_tree

        chunks = mp3.zero3_shard(p)
        rest = gather_chunked_tree(
            {k: v for k, v in chunks.items() if k != "layers"}, rest_meta)
        return model.loss(dict(rest, layers=chunks["layers"]), t, t,
                          layer_chunk_meta=layer_meta)

    from apex_tpu.lint import ir as lint_ir

    with pytest.raises(ValueError) as trace_err:
        lint_ir.trace_ir(zero3_loss, abstract, toks, axes={"data": 4})
    assert str(trace_err.value) == ZERO3_PREFETCH_NEEDS_UNROLL

    with pytest.raises(ValueError) as build_err:
        build_zero_train_step(
            mp3, mesh=None, specs=None, state_specs=None, pipe_loss=None,
            rest_specs=None, grad_axes=("data",),
            data_spec=None, zero3=types.SimpleNamespace(),
            model=model, num_microbatches=1)
    assert str(build_err.value) == ZERO3_PREFETCH_NEEDS_UNROLL

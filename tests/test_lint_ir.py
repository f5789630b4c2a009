"""Tests for apex_tpu.lint.ir (the shared single-trace jaxpr walker + pass
framework) and the four whole-program passes (engine 3, ISSUE 13):
collective-consistency, static-hbm, dtype-drift, comm-bytes — each tested
both ways (a minimal step that fires the finding + the clean/fixed twin
that passes), plus the step-audit gate and the static-HBM-vs-measured
cross-check."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.lint import ir as lint_ir
from apex_tpu.lint.passes import (
    collective_consistency_pass,
    comm_bytes_pass,
    dtype_drift_pass,
    static_hbm_pass,
)


def _mesh(n=4, name="i"):
    return Mesh(np.array(jax.devices()[:n]).reshape(n), (name,))


def _ring(n=4):
    return [(a, (a + 1) % n) for a in range(n)]


# ---------------------------------------------------------------------------
# the walker: one trace, one walk, threaded context
# ---------------------------------------------------------------------------


def test_step_ir_threads_context_and_duck_types():
    """The walk carries shard_map axis sizes, remat containment, and
    cond-branch indices; a StepIR quacks like a ClosedJaxpr so every
    legacy analyzer accepts it unchanged."""
    mesh = _mesh()

    def body(x):
        y = lax.psum(x, "i")
        inner = jax.checkpoint(lambda h: jnp.tanh(h) * 2.0)
        y = inner(y)
        return lax.cond(jnp.sum(y) > 0,
                        lambda z: z * 2.0, lambda z: z + 1.0, y)

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("i"),
                       out_specs=P("i"), check_vma=False)
    ir = lint_ir.trace_ir(fn, jnp.ones((8, 4)))
    assert hasattr(ir, "jaxpr") and hasattr(ir, "invars")  # duck-typing

    psums = [n for n in ir.nodes if n.eqn.primitive.name == "psum"]
    assert psums and psums[0].axis_sizes == {"i": 4}
    assert psums[0].in_shard_map and not psums[0].in_remat

    remat_nodes = [n for n in ir.nodes if n.in_remat]
    assert remat_nodes, "checkpoint body equations must be marked in_remat"
    branch_nodes = {n.branch for n in ir.nodes if n.branch is not None}
    assert branch_nodes == {0, 1}, branch_nodes

    # the legacy iteration order still sees every equation
    assert len(list(lint_ir.ensure_ir(ir).iter_eqns())) == len(ir.nodes)


def test_ensure_ir_shares_one_walk():
    """Handing the same pre-traced jaxpr to N analyzers reuses one cached
    walk (the dedupe tests/test_lint.py's fixtures lean on)."""
    jx = jax.make_jaxpr(lambda x: jnp.tanh(x) * 2.0)(jnp.ones((4,)))
    a, b = lint_ir.ensure_ir(jx), lint_ir.ensure_ir(jx)
    assert a is b
    assert a.nodes is b.nodes


def test_run_passes_aggregates_and_rejects_unknown():
    res = lint_ir.run_passes(lambda x: x * 2.0, jnp.ones((4,)))
    assert set(res["passes"]) == set(lint_ir.PASS_REGISTRY)
    assert res["ok"] and res["errors"] == 0
    with pytest.raises(ValueError, match="unknown lint pass"):
        lint_ir.run_passes(lambda x: x, jnp.ones((2,)),
                           passes=["no-such-pass"])


def test_apply_suppressions_honors_source_grammar(tmp_path):
    """A jaxpr-level finding with provenance is waived by the standard
    '# lint: disable=<rule> -- why' comment at its source line; a finding
    with no provenance stays unsuppressed (waivers must be auditable)."""
    mod = tmp_path / "widening.py"
    mod.write_text("x = 1\n"
                   "y = upcast(x)  # lint: disable=dtype-drift -- fp32 "
                   "softmax numerics\n")
    findings = [
        {"rule": "dtype-drift", "path": str(mod), "line": 2, "message": "m"},
        {"rule": "dtype-drift", "path": str(mod), "line": 1, "message": "m"},
        {"rule": "dtype-drift", "message": "no provenance"},
    ]
    lint_ir.apply_suppressions(findings, root=str(tmp_path))
    assert findings[0].get("suppressed") is True
    assert "softmax" in findings[0]["justification"]
    assert not findings[1].get("suppressed")
    assert not findings[2].get("suppressed")
    assert findings[0]["path"] == "widening.py"  # repo-relative rewrite


# ---------------------------------------------------------------------------
# collective-consistency: both ways
# ---------------------------------------------------------------------------


def test_collective_consistency_flags_divergent_cond_branches():
    mesh = _mesh()

    def body(x):
        y = lax.psum(x, "i")
        return lax.cond(jnp.sum(y) > 0,
                        lambda z: lax.ppermute(z, "i", _ring()),
                        lambda z: z,  # no collective: the deadlock shape
                        y)

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("i"),
                       out_specs=P("i"), check_vma=False)
    res = collective_consistency_pass(lint_ir.trace_ir(fn, jnp.ones((8, 4))))
    kinds = [f["kind"] for f in res["findings"]]
    assert kinds == ["branch-divergence"], res
    assert "deadlock" in res["findings"][0]["message"]


def test_collective_consistency_passes_agreeing_branches_and_ring():
    mesh = _mesh()

    def body(x):
        ring = lambda z: lax.ppermute(z, "i", _ring())  # noqa: E731
        return lax.cond(jnp.sum(x) > 0,
                        lambda z: ring(z) * 2.0,
                        lambda z: ring(z) + 1.0, x)

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("i"),
                       out_specs=P("i"), check_vma=False)
    res = collective_consistency_pass(lint_ir.trace_ir(fn, jnp.ones((8, 4))))
    assert not res["findings"], res
    assert res["conds_checked"] == 1 and res["ppermutes_checked"] == 2


def test_collective_consistency_flags_malformed_ppermute():
    mesh = _mesh()

    # two ranks send to slot 1; rank 3 out of nowhere receives nothing
    def body(x):
        return lax.ppermute(x, "i", [(0, 1), (2, 1)])

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("i"),
                       out_specs=P("i"), check_vma=False)
    res = collective_consistency_pass(lint_ir.trace_ir(fn, jnp.ones((8, 4))))
    assert [f["kind"] for f in res["findings"]] == ["malformed-ppermute"]
    assert "destination" in res["findings"][0]["message"]

    ok = jax.shard_map(lambda x: lax.ppermute(x, "i", _ring()), mesh=mesh,
                       in_specs=P("i"), out_specs=P("i"), check_vma=False)
    assert not collective_consistency_pass(
        lint_ir.trace_ir(ok, jnp.ones((8, 4))))["findings"]


# ---------------------------------------------------------------------------
# static-hbm: both ways + the acceptance synthetics
# ---------------------------------------------------------------------------


def test_static_hbm_peak_tracks_live_ranges():
    """Hand-computable program: peak = inputs + both intermediates live at
    the residual add; the estimate must sit between the resident floor
    and the sum of every value ever created (frees DO happen)."""
    w = jnp.ones((256, 256), jnp.float32)   # 256 KiB
    x = jnp.ones((256, 256), jnp.float32)

    def f(w, x):
        h1 = jnp.tanh(x @ w)      # 256 KiB
        h2 = jnp.tanh(h1 @ w)     # 256 KiB, h1 still live for the add
        return h1 + h2

    res = static_hbm_pass(lint_ir.trace_ir(f, w, x))
    kib = 256 * 256 * 4
    assert res["resident_in_bytes"] == 2 * kib
    # the worst point holds exactly 3 arrays (w + h1 + t2 at the second
    # matmul: x and each tanh input die at their last use); never the sum
    # of everything ever created (5+)
    assert 3 * kib <= res["peak_bytes"] <= 4 * kib, res["peak_bytes"]
    assert res["peak_padded_bytes"] >= res["peak_bytes"]


def test_static_hbm_flags_bhs1_operand_at_boundary():
    """The acceptance synthetic: a (b, h, s, 1) f32 operand crossing a
    custom-call boundary occupies 128x its nbytes under T(8,128); the
    dense (b, h, s, 128) twin is pad-free."""
    def bad(x):
        y = jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y.sum()

    x = jnp.ones((2, 4, 512, 1), jnp.float32)
    res = static_hbm_pass(lint_ir.trace_ir(bad, x), min_bytes=0)
    hits = [f for f in res["findings"] if f["shape"] == [2, 4, 512, 1]
            and "pure_callback" in f["where"]]
    assert hits and hits[0]["waste_ratio"] == 128.0, res["findings"]
    assert hits[0]["rule"] == "static-hbm"
    assert "dense" in hits[0]["message"]  # the lse-table remediation hint

    dense = jnp.ones((2, 4, 512, 128), jnp.float32)
    res2 = static_hbm_pass(lint_ir.trace_ir(bad, dense), min_bytes=0)
    assert not res2["findings"], res2["findings"]


def test_static_hbm_estimate_within_2x_of_measured():
    """The cross-check the acceptance pins at 110M (the slow test below):
    the pass's estimated peak bytes vs monitor.hbm's MEASURED live bytes
    after one materialized O2 train step, within 2x — here on a small GPT
    so it rides tier-1."""
    from apex_tpu.lint.audit import hbm_crosscheck

    res = hbm_crosscheck(
        materialize=True,
        config=dict(vocab_size=512, hidden_size=128, num_layers=2,
                    num_attention_heads=4, max_seq_len=64))
    assert res["ok"], res
    assert 0.5 <= res["ratio"] <= 2.0, res


@pytest.mark.slow
def test_static_hbm_estimate_within_2x_of_measured_110m():
    """The pinned 110M-class dense config (bench.py's (768, 12) profile
    shape): estimated peak within 2x of the measured figure."""
    from apex_tpu.lint.audit import hbm_crosscheck

    res = hbm_crosscheck(materialize=True)
    assert res["ok"], res


# ---------------------------------------------------------------------------
# dtype-drift: both ways
# ---------------------------------------------------------------------------

_BIG = (64, 1024)  # 64 Ki elements: over the default model-sized floor


def test_dtype_drift_flags_silent_fp32_round_trip():
    def drift(x):
        wide = x.astype(jnp.float32) * jnp.float32(2.0)
        return wide.astype(jnp.bfloat16).sum()

    res = dtype_drift_pass(
        lint_ir.trace_ir(drift, jnp.ones(_BIG, jnp.bfloat16)))
    assert len(res["findings"]) == 1, res
    f = res["findings"][0]
    assert f["rule"] == "dtype-drift" and f["dtype"] == "float32"
    assert f["bytes"] == 64 * 1024 * 4
    assert "path" in f and "line" in f  # provenance for suppression
    assert res["upcasts"] >= 1


def test_dtype_drift_passes_narrow_weak_promotion_and_anchored_fp32():
    """`2.0 * x` stays bf16 (weak promotion resolves down) — clean; an
    fp32 excursion that touches GENUINE fp32 state (a master/moment/LN
    weight) is intentional mixed precision — clean."""
    x = jnp.ones(_BIG, jnp.bfloat16)

    # (.sum()'s f32 accumulator IS a large upcast — booked in the stats —
    # but it reduces to a scalar and never round-trips large: clean)
    res = dtype_drift_pass(lint_ir.trace_ir(lambda x: (x * 2.0).sum(), x))
    assert not res["findings"], res

    master = jnp.ones(_BIG, jnp.float32)

    def anchored(x, m):
        return (x.astype(jnp.float32) + m).astype(jnp.bfloat16).sum()

    res2 = dtype_drift_pass(lint_ir.trace_ir(anchored, x, master))
    assert not res2["findings"], res2


def test_dtype_drift_respects_min_elems_floor():
    small = jnp.ones((8, 8), jnp.bfloat16)  # 64 elems: numerics, not drift

    def drift(x):
        return (x.astype(jnp.float32) * jnp.float32(2.0)) \
            .astype(jnp.bfloat16).sum()

    assert not dtype_drift_pass(lint_ir.trace_ir(drift, small))["findings"]


def test_dtype_drift_clean_on_real_zero_amp_step():
    """The real O2 ZeRO step's fp32 work all touches genuine fp32 state
    (masters, moments) — no drift finding."""
    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam

    opt = amp.MixedPrecisionOptimizer(
        FusedAdam(lr=1e-2), amp.get_policy("O2"), zero_axis="data")
    params = {"w": jnp.ones((256, 1024), jnp.bfloat16)}
    grads = {"w": jnp.ones((256, 1024), jnp.float32)}

    def step(p, g):
        st = opt.init(p)
        return opt.apply_gradients(st, p, g)[0]

    res = dtype_drift_pass(
        lint_ir.trace_ir(step, params, grads, axes={"data": 8}))
    assert not res["findings"], res["findings"]


# ---------------------------------------------------------------------------
# comm-bytes: both ways
# ---------------------------------------------------------------------------


def test_comm_bytes_flags_unbooked_collective_traffic():
    """A bare lax.psum moves bulk wire bytes the comm: accounting never
    books — the finding; the scoped verb (parallel/collectives.psum)
    reconciles clean. Both read the account attached by the SAME single
    trace (trace_ir(comm=True))."""
    from apex_tpu.parallel import collectives

    big = jnp.ones((4, 64, 128), jnp.float32)

    bare = lint_ir.trace_ir(
        jax.vmap(lambda x: lax.psum(x, "i"), axis_name="i"), big, comm=True)
    res = comm_bytes_pass(bare)
    assert len(res["findings"]) == 1, res
    assert res["findings"][0]["dtype"] == "float32"
    assert "comm:" in res["findings"][0]["message"]
    assert res["booked_total_bytes"] == 0

    scoped = lint_ir.trace_ir(
        jax.vmap(lambda x: collectives.psum(x, "i"), axis_name="i"),
        big, comm=True)
    res2 = comm_bytes_pass(scoped)
    assert not res2["findings"], res2
    assert res2["booked_total_bytes"] > 0
    assert "psum[float32]" in res2["static_by_verb_dtype"]


def test_comm_bytes_without_account_reports_table_only():
    res = comm_bytes_pass(lint_ir.trace_ir(
        jax.vmap(lambda x: lax.psum(x, "i"), axis_name="i"),
        jnp.ones((4, 64, 128), jnp.float32)))
    assert not res["findings"]  # nothing to reconcile against
    assert res["booked_by_verb_dtype"] is None
    assert res["static_total_bytes"] > 0


def test_comm_bytes_scalar_traffic_stays_under_floor():
    """Tiny unbooked collectives (the found_inf pmax class) never flag:
    the floor keeps the reconciliation about BULK wire traffic."""
    res = comm_bytes_pass(lint_ir.trace_ir(
        jax.vmap(lambda x: lax.pmax(jnp.sum(x), "i"), axis_name="i"),
        jnp.ones((4, 16), jnp.float32), comm=True))
    assert not res["findings"], res


# ---------------------------------------------------------------------------
# the audit gate (the full program set runs in monitor.selftest + the
# CLI; here the cheap subset proves the wiring end to end in tier-1)
# ---------------------------------------------------------------------------


def test_audit_subset_runs_clean():
    from apex_tpu.lint import audit as lint_audit

    verdict = lint_audit.run_audit(
        programs=("zero3_prefetch", "serve_decode"))
    assert verdict["all_ok"], verdict
    z3 = verdict["programs"]["zero3_prefetch"]
    assert set(z3["passes"]) == set(lint_ir.PASS_REGISTRY)
    assert not z3["tripwires"]["zero3-bulk-gather"]["hazard"]
    assert not z3["tripwires"]["unprefetched-gather"]["hazard"]
    sd = verdict["programs"]["serve_decode"]
    assert not sd["tripwires"]["decode-recompile"]["hazard"]


def test_audit_rejects_unknown_program_names():
    """A typo'd CI subset must never audit 0 programs and exit green."""
    from apex_tpu.lint import audit as lint_audit

    with pytest.raises(ValueError, match="unknown audit program"):
        lint_audit.run_audit(programs=("zero3-prefetch",))


def test_audit_step_program_reports_injected_hazard():
    """The gate actually gates: a step with a divergent-cond collective
    audits NOT ok, with the finding attributed to its pass."""
    from apex_tpu.lint import audit as lint_audit

    mesh = _mesh()

    def body(x):
        return lax.cond(jnp.sum(x) > 0,
                        lambda z: lax.psum(z, "i"), lambda z: z, x)

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("i"),
                       out_specs=P("i"), check_vma=False)
    verdict = lint_audit.audit_step_program(fn, jnp.ones((8, 4)),
                                            label="injected")
    assert not verdict["ok"]
    assert verdict["passes"]["collective-consistency"]["findings"]


# ---------------------------------------------------------------------------
# plan-feasibility: planner claim vs traced step (ISSUE 18)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def _tiny_plan_spec():
    from apex_tpu import plan as plan_mod

    return plan_mod.ModelSpec("lintir-tiny", 128, 64, 4, 4, 32)


def test_plan_feasibility_clean_on_planner_zero3_steps(_tiny_plan_spec):
    """Both ZeRO-3 drives the planner can emit (scan + remat, unrolled +
    prefetch) trace to per-layer gathers — the pass stays silent and the
    census shows the gather anatomy it checked."""
    from apex_tpu import plan as plan_mod

    for cand in (plan_mod.Candidate(dp=4, zero_level=3),
                 plan_mod.Candidate(dp=4, zero_level=3, zero3_prefetch=1,
                                    unroll=True)):
        step = plan_mod.feasibility_step(_tiny_plan_spec, cand)
        sir = lint_ir.trace_ir(step["fn"], *step["args"],
                               axes=step["axes"])
        res = lint_ir.run_passes(
            sir, passes=["plan-feasibility"],
            options={"plan-feasibility": {
                "plan": step["plan"],
                "model_elems": step["model_elems"]}})
        r = res["passes"]["plan-feasibility"]
        assert res["ok"], r
        assert r["audited"] and not r["findings"]
        z3 = r["census"]["zero3_gather"]
        assert not z3["hazard"] and z3["layer_gathers"] > 0


def test_plan_feasibility_flags_bulk_gather_claimed_as_zero3(
        _tiny_plan_spec):
    """A step that gathers the whole layer stack up front (the
    O(model)-rematerialization class) contradicts a ZeRO-3 score: the
    pass adopts the zero3-bulk-gather finding under its own rule with
    the plan claim attached. Without a plan option the pass is inert."""
    import jax.numpy as jnp

    from apex_tpu import amp, plan as plan_mod
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.optimizers.distributed import (
        gather_chunked_tree,
        gather_stacked_leaf,
    )
    from apex_tpu.plan.search import abstract_params, model_config_kwargs

    spec = _tiny_plan_spec
    kw = model_config_kwargs(spec)
    kw.update(remat=True)
    model = GPTModel(GPTConfig(**kw))
    abstract = abstract_params(spec)
    mp3 = amp.MixedPrecisionOptimizer(
        FusedAdam(lr=1e-4), amp.get_policy("O2"), zero_axis="data",
        zero_level=3)
    meta = mp3.zero3_meta(abstract)
    layer_meta = meta.subtree("layers")
    rest_meta = meta.select([k for k in meta.shapes if k != "layers"])
    toks = jax.ShapeDtypeStruct((1, spec.seq), jnp.int32)

    def bulk_loss(p, toks, tgts):
        chunks = mp3.zero3_shard(p)
        rest = gather_chunked_tree(
            {k: v for k, v in chunks.items() if k != "layers"}, rest_meta)
        layers = jax.tree.map(
            lambda c, s: gather_stacked_leaf(c, s.shape, s.dtype,
                                             meta.axis),
            chunks["layers"], layer_meta.shapes,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        return model.loss(dict(rest, layers=layers), toks, tgts)

    step = plan_mod.feasibility_step(
        spec, plan_mod.Candidate(dp=4, zero_level=3))
    sir = lint_ir.trace_ir(jax.value_and_grad(bulk_loss), abstract, toks,
                           toks, axes={"data": 4})
    res = lint_ir.run_passes(
        sir, passes=["plan-feasibility"],
        options={"plan-feasibility": {"plan": step["plan"],
                                      "model_elems": step["model_elems"]}})
    r = res["passes"]["plan-feasibility"]
    assert not res["ok"] and r["findings"]
    f = r["findings"][0]
    assert f["rule"] == "plan-feasibility"
    assert "plan scored as" in f["message"]
    assert f["plan_claim"].startswith("ZeRO-3")
    # inert without the plan option: not every audited program is planned
    inert = lint_ir.run_passes(sir, passes=["plan-feasibility"])
    assert inert["ok"]
    assert inert["passes"]["plan-feasibility"] == {
        "findings": [], "audited": False, "census": {}}


def test_plan_feasibility_moe_dispatch_both_ways():
    """The expert-parallel claim: the planner's EP step carries its
    dispatch all_to_alls (silent); a serial-expert step scored as EP
    fires the adopted moe-dispatch finding."""
    import jax.numpy as jnp

    from apex_tpu import plan as plan_mod
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.plan.search import model_config_kwargs

    spec = plan_mod.ModelSpec("lintir-tinymoe", 128, 64, 4, 4, 32,
                              moe_experts=4)
    cand = plan_mod.Candidate(dp=4, moe_expert_axis="data",
                              moe_dispatch_dtype="int8")
    step = plan_mod.feasibility_step(spec, cand)
    sir = lint_ir.trace_ir(step["fn"], *step["args"], axes=step["axes"])
    opts = {"plan-feasibility": {"plan": step["plan"],
                                 "model_elems": step["model_elems"]}}
    r = lint_ir.run_passes(sir, passes=["plan-feasibility"],
                           options=opts)["passes"]["plan-feasibility"]
    assert r["audited"] and not r["findings"], r

    kw = model_config_kwargs(spec)
    kw.update(remat=True)
    serial = GPTModel(GPTConfig(**kw))
    full = jax.eval_shape(serial.init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((1, spec.seq), jnp.int32)
    sir_s = lint_ir.trace_ir(
        jax.value_and_grad(lambda p, a, b: serial.loss(p, a, b)),
        full, toks, toks, axes={"data": 4})
    rs = lint_ir.run_passes(sir_s, passes=["plan-feasibility"],
                            options=opts)["passes"]["plan-feasibility"]
    assert rs["findings"]
    assert "all_to_all" in rs["findings"][0]["message"]
    assert rs["findings"][0]["plan_claim"].startswith("expert-parallel")


def test_audit_plan_program_runs_clean():
    """The registered `plan` audit program: search a tiny spec, trace the
    winner's feasibility step, and the plan-feasibility pass must audit
    it (not skip) and find nothing."""
    from apex_tpu.lint import audit as lint_audit

    verdict = lint_audit.run_audit(programs=("plan",))
    assert verdict["all_ok"], verdict
    prog = verdict["programs"]["plan"]
    pf = prog["passes"]["plan-feasibility"]
    assert pf["audited"] and not pf["findings"]
    assert pf["plan"]["zero_level"] == 3

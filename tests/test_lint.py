"""Tests for apex_tpu.lint — the project-invariant linter (engine 1: source
AST rules) and the jaxpr hazard analyzers (engine 2: lane padding,
collective-transpose, recompile hazards) — plus the tier-1 contract that the
repo itself lints clean with every suppression justified.

The REAL-step tripwire tests share module-scoped StepIR fixtures (ISSUE
13): each canonical step callable traces ONCE on the shared walker
(apex_tpu.lint.ir) and the same IR feeds every analyzer that reads it —
the dedupe that measurably cut this module's wall time (PERF_NOTES.md).
The IR walker and pass framework have their own suite in
tests/test_lint_ir.py."""

import json
import textwrap

import jax.numpy as jnp
import pytest
from jax import lax

from apex_tpu.lint import RULES, Suppressions, comm_scope_check, run_paths
from apex_tpu.lint import ir as lint_ir
from apex_tpu.lint import trace
from apex_tpu.lint.cli import main as lint_main


def _write(tmp_path, relpath, body):
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(body))
    return path


# ---------------------------------------------------------------------------
# module-scoped step IRs: each real step callable traces ONCE, every
# analyzer below reads the same shared walk
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def zero3_gpt_irs():
    """StepIRs of the REAL fully-sharded (ZeRO-3) GPT drives: the
    serialized unrolled chunk_meta step (zero3_prefetch=0), the
    double-buffered drive (=1), and the bulk whole-stack-gather
    regression — one ``value_and_grad`` trace each for the whole
    module."""
    import jax

    from apex_tpu import amp
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.optimizers.distributed import (
        gather_chunked_tree,
        gather_stacked_leaf,
    )

    base = dict(vocab_size=64, hidden_size=16, num_layers=4,
                num_attention_heads=2, max_seq_len=8, hidden_dropout=0.0,
                axis=None, unroll_layers=True)
    params = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(GPTModel(GPTConfig(**base)).init,
                       jax.random.PRNGKey(0)))
    mp_opt = amp.MixedPrecisionOptimizer(
        FusedAdam(lr=1e-3), amp.get_policy("O2"),
        zero_axis="data", zero_level=3)
    meta = mp_opt.zero3_meta(params)
    layer_meta = meta.subtree("layers")
    rest_meta = meta.select([k for k in meta.shapes if k != "layers"])
    toks = jnp.zeros((2, 8), jnp.int32)

    def loss_fn(prefetch):
        model = GPTModel(GPTConfig(zero3_prefetch=prefetch, **base))

        def fn(p):
            chunks = mp_opt.zero3_shard(p)
            rest = gather_chunked_tree(
                {k: v for k, v in chunks.items() if k != "layers"},
                rest_meta)
            return model.loss(dict(rest, layers=chunks["layers"]),
                              toks, toks, layer_chunk_meta=layer_meta)
        return fn

    def bulk_loss(p):
        chunks = mp_opt.zero3_shard(p)
        layers = jax.tree.map(
            lambda c, s: gather_stacked_leaf(c, s.shape, s.dtype, "data"),
            chunks["layers"], layer_meta.shapes,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        rest = gather_chunked_tree(
            {k: v for k, v in chunks.items() if k != "layers"}, rest_meta)
        return GPTModel(GPTConfig(**base)).loss(
            dict(rest, layers=layers), toks, toks)

    def mk(fn):
        return lint_ir.trace_ir(jax.value_and_grad(fn), params,
                                axes={"data": 8})

    return {"serialized": mk(loss_fn(0)), "prefetched": mk(loss_fn(1)),
            "bulk": mk(bulk_loss), "num_layers": base["num_layers"]}


@pytest.fixture(scope="module")
def gpt_sp_forward_irs():
    """StepIRs of the plain-TP and sequence-parallel GPT forwards — the
    model-level SP regression gate's two traces, shared module-wide."""
    import jax

    from apex_tpu.models import GPTConfig, GPTModel

    tiny = dict(vocab_size=64, hidden_size=32, num_layers=2,
                num_attention_heads=4, max_seq_len=16, hidden_dropout=0.0,
                compute_dtype=jnp.float32, remat=False)
    toks = jnp.zeros((2, 16), jnp.int32)
    irs = {}
    for sp in (False, True):
        model = GPTModel(GPTConfig(axis="model", sequence_parallel=sp,
                                   **tiny))
        params = jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype),
            jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        irs[sp] = lint_ir.trace_ir(
            lambda p, t, m=model: m.apply(p, t, jnp.roll(t, -1, -1)),
            params, toks, axes={"model": 2})
    return irs


@pytest.fixture(scope="module")
def zero_amp_step_irs():
    """StepIRs of the real MixedPrecisionOptimizer steps the redundancy
    and quantized-wire tripwires pin: the ZeRO LAMB step, the replicated
    twin, the int8-wire step (+ its residual tree), and the fp32-wire
    ZeRO Adam step — four traces for the whole module."""
    import types

    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam, FusedLAMB
    from apex_tpu.parallel.distributed import allreduce_gradients

    policy = amp.get_policy("O2")
    params = {"w": jnp.ones((64, 64), jnp.bfloat16)}
    grads = {"w": jnp.ones((64, 64), jnp.float32)}

    def step(opt, reduce_first=False):
        def fn(p, g):
            st = opt.init(p)
            if reduce_first:
                g = allreduce_gradients(g, ("data",))
            return opt.apply_gradients(st, p, g)[0]
        return lint_ir.trace_ir(fn, params, grads, axes={"data": 8})

    lamb_zero = amp.MixedPrecisionOptimizer(
        FusedLAMB(lr=1e-2, norm_psum_axis="data"), policy,
        zero_axis="data", gather_dtype="bf16", log_grad_norm=True)
    replicated = amp.MixedPrecisionOptimizer(FusedLAMB(lr=1e-2), policy)
    q8 = amp.MixedPrecisionOptimizer(
        FusedAdam(lr=1e-2), policy, zero_axis="data", reduce_dtype="int8")
    fp32_adam = amp.MixedPrecisionOptimizer(
        FusedAdam(lr=1e-2), policy, zero_axis="data")
    residual = q8.zero_abstract_state(
        params, types.SimpleNamespace(shape={"data": 8})).residual
    return {"zero": step(lamb_zero),
            "replicated": step(replicated, reduce_first=True),
            "q8": step(q8), "fp32_wire": step(fp32_adam),
            "residual": residual}


# ---------------------------------------------------------------------------
# the tier-1 contract: the repo lints clean
# ---------------------------------------------------------------------------


def test_repo_lints_clean_with_justified_suppressions():
    """Every invariant the linter mechanizes must HOLD over the tree — an
    unsuppressed finding here is a real regression of a documented
    convention (CLAUDE.md), and a suppression without a justification is a
    waiver nobody can audit."""
    rep = run_paths()
    assert not rep.errors, "\n".join(f.format() for f in rep.errors)
    assert rep.files_scanned >= 100, rep.files_scanned
    assert set(rep.rules_run) == set(RULES)
    for f in rep.suppressed:
        assert f.justification, f"unjustified suppression: {f.format()}"


def test_cli_strict_exits_zero_on_repo(capsys):
    assert lint_main(["--strict"]) == 0
    assert lint_main(["--json"]) == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    payload = json.loads(out)
    assert payload["errors"] == 0
    assert payload["files_scanned"] >= 100


def test_cli_list_rules_and_unknown_rule(capsys):
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for name in RULES:
        assert name in out
    assert "lane-padding" in out  # the trace analyzers are advertised
    assert lint_main(["--rules", "not-a-rule"]) == 2


# ---------------------------------------------------------------------------
# acceptance fixture: three distinct named rules on seeded hazards
# ---------------------------------------------------------------------------


def test_seeded_hazards_flagged_by_three_named_rules(tmp_path):
    """The ISSUE acceptance: a bare pmean(loss) under grad, a missing
    comm: scope, and a (sq, 1) f32 operand are each flagged by a distinct
    named rule (grad-collective, comm-scope, lane-padding)."""
    bad = _write(tmp_path, "bad_step.py", '''
        """Deliberately-hazardous fixture."""
        import jax
        from jax import lax

        from apex_tpu.monitor.comms import collective_scope

        def unscoped_verb(tree, axis):
            return lax.psum(tree, axis)

        def loss_fn(params, batch):
            loss = lax.pmean((params * batch).sum(), "data")
            return loss

        step_grads = jax.grad(loss_fn)
    ''')
    rep = run_paths(paths=[str(bad)], root=str(tmp_path))
    by_rule = {}
    for f in rep.errors:
        by_rule.setdefault(f.rule, []).append(f.message)
    assert "comm-scope" in by_rule, rep.findings
    assert any("unscoped_verb" in m for m in by_rule["comm-scope"])
    assert "grad-collective" in by_rule, rep.findings
    assert any("pmean" in m for m in by_rule["grad-collective"])

    # third distinct rule, engine 2: the (sq, 1) f32 operand
    pad = trace.lane_padding_report(
        lambda w: w * 2.0, jnp.ones((512, 1), jnp.float32), min_bytes=0)
    flagged = [f for f in pad["findings"] if f["shape"] == [512, 1]]
    assert flagged and flagged[0]["rule"] == "lane-padding"
    assert {"comm-scope", "grad-collective", flagged[0]["rule"]} == {
        "comm-scope", "grad-collective", "lane-padding"}


# ---------------------------------------------------------------------------
# engine 1 rules, one fixture each
# ---------------------------------------------------------------------------


def test_comm_scope_check_reports_violations_and_verbs(tmp_path):
    path = _write(tmp_path, "verbs.py", '''
        from jax import lax
        from apex_tpu.monitor.comms import collective_scope as _comm

        def good(tree, axis):
            with _comm("psum", axis, tree):
                return lax.psum(tree, axis)

        def bad(tree, axis):
            return lax.pmean(tree, axis)
    ''')
    violations, verbs = comm_scope_check(str(path))
    assert verbs == 2
    assert violations == [("bad", ["pmean"])]


def test_comm_scope_skips_files_outside_contract(tmp_path):
    # raw lax collectives WITHOUT the scope-helper import or marker are
    # other rules' business (model code psums activations legitimately)
    path = _write(tmp_path, "model.py", '''
        from jax import lax

        def stats(x, axis):
            return lax.pmean(x, axis)
    ''')
    rep = run_paths(paths=[str(path)], root=str(tmp_path))
    assert not [f for f in rep.findings if f.rule == "comm-scope"]


def test_comm_scope_marker_opts_in(tmp_path):
    path = _write(tmp_path, "marked.py", '''
        from jax import lax

        LINT_COMM_SCOPE = True

        def verb(x, axis):
            return lax.psum(x, axis)
    ''')
    rep = run_paths(paths=[str(path)], root=str(tmp_path))
    assert [f for f in rep.errors if f.rule == "comm-scope"]


def test_grad_collective_lambda_and_clean_variants(tmp_path):
    path = _write(tmp_path, "grads.py", '''
        import jax
        from jax import lax
        from apex_tpu.parallel import collectives

        g1 = jax.value_and_grad(lambda p: collectives.pmean(p.sum(), "data"))

        def clean_loss(p):
            return p.sum() * 2.0

        def train(p):
            loss, grads = jax.value_and_grad(clean_loss)(p)
            # reducing AFTER the grad call is the documented-correct shape
            return collectives.pmean(loss, "data"), grads
    ''')
    rep = run_paths(paths=[str(path)], root=str(tmp_path))
    hits = [f for f in rep.errors if f.rule == "grad-collective"]
    assert len(hits) == 1 and "<lambda>" in hits[0].message


def test_pallas_interpret_rule(tmp_path):
    path = _write(tmp_path, "kern.py", '''
        from jax.experimental import pallas as pl

        def good(x):
            return pl.pallas_call(kernel, out_shape=x, interpret=True)(x)

        def bad(x):
            return pl.pallas_call(kernel, out_shape=x)(x)
    ''')
    rep = run_paths(paths=[str(path)], root=str(tmp_path))
    hits = [f for f in rep.errors if f.rule == "pallas-interpret"]
    assert len(hits) == 1 and hits[0].line == 8


def test_module_citation_rule(tmp_path):
    flagged = _write(tmp_path, "apex_tpu/nocite.py", '"""Does things."""\n')
    cited = _write(tmp_path, "apex_tpu/cited.py",
                   '"""X (reference: apex/foo/bar.py:10-20)."""\n')
    waived = _write(tmp_path, "apex_tpu/waived.py",
                    '"""Y. No reference analog: invented here."""\n')
    outside = _write(tmp_path, "examples/nocite.py", '"""Free-form."""\n')
    rep = run_paths(paths=[str(p) for p in (flagged, cited, waived, outside)],
                    root=str(tmp_path))
    hits = [f for f in rep.errors if f.rule == "module-citation"]
    assert [f.path for f in hits] == ["apex_tpu/nocite.py"]


def test_exception_retention_rule(tmp_path):
    path = _write(tmp_path, "oom.py", '''
        def retains(fn):
            errs = []
            try:
                fn()
            except Exception as e:
                errs.append(e)
            return errs

        def stores(self, fn):
            try:
                fn()
            except Exception as e:
                self.last = e

        def sanitizes(fn):
            try:
                fn()
            except Exception as e:
                return {"error": str(e)[:100]}
    ''')
    rep = run_paths(paths=[str(path)], root=str(tmp_path))
    hits = [f for f in rep.errors if f.rule == "exception-retention"]
    assert sorted(f.line for f in hits) == [7, 14]  # append + attr store
    assert not any(f.line > 14 for f in hits)  # str(e) never flags


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------


def test_suppression_inline_and_comment_line_above(tmp_path):
    path = _write(tmp_path, "sup.py", '''
        from jax.experimental import pallas as pl

        def a(x):
            return pl.pallas_call(k)(x)  # lint: disable=pallas-interpret -- helper resolves it

        def b(x):
            # lint: disable=pallas-interpret -- wrapped by caller
            return pl.pallas_call(k)(x)

        def c(x):
            return pl.pallas_call(k)(x)
    ''')
    rep = run_paths(paths=[str(path)], root=str(tmp_path))
    hits = [f for f in rep.findings if f.rule == "pallas-interpret"]
    assert len(hits) == 3
    assert [f.suppressed for f in sorted(hits, key=lambda f: f.line)] == [
        True, True, False]
    assert all(f.justification for f in hits if f.suppressed)


def test_suppression_file_wide():
    sup = Suppressions(
        "# lint: disable-file=comm-scope -- generated file\nx = 1\n")
    assert sup.match("comm-scope", 99) == (True, "generated file")
    assert sup.match("grad-collective", 99) is None


def test_suppression_directive_inside_string_is_documentation():
    """A directive quoted in a docstring or string literal documents the
    grammar; it must never become a live file-wide waiver."""
    sup = Suppressions(
        '"""Grammar doc:\n'
        "    # lint: disable-file=comm-scope -- generated file\n"
        '"""\n'
        "s = '# lint: disable=grad-collective -- also quoted'\n"
        "x = 1\n")
    assert sup.match("comm-scope", 5) is None
    assert sup.match("grad-collective", 4) is None
    assert sup.file_wide == {}


def test_suppression_pending_does_not_leak_past_inline_directive():
    """A comment-only directive above a line that carries its own inline
    directive binds to THAT line (both apply) — it must not skip ahead and
    waive an unrelated later violation."""
    sup = Suppressions(
        "# lint: disable=rule-a -- above\n"
        "x = foo()  # lint: disable=rule-b -- inline\n"
        "y = bar()\n")
    assert sup.match("rule-a", 2) == (True, "above")
    assert sup.match("rule-b", 2) == (True, "inline")
    assert sup.match("rule-a", 3) is None


def test_nonexistent_path_fails_loudly(tmp_path):
    """A typo'd CI path must never lint 0 files and exit green."""
    with pytest.raises(ValueError, match="does not exist"):
        run_paths(paths=[str(tmp_path / "no_such_tree")])
    assert lint_main(["--strict", str(tmp_path / "no_such_tree")]) == 2


# ---------------------------------------------------------------------------
# engine 2: lane-padding auditor against the known numbers
# ---------------------------------------------------------------------------


def test_lane_padding_known_numbers():
    """The satellite contract: d=32 pads 4x to 128 lanes; a (sq, 1) f32
    window costs sq*128*4 resident bytes; a dense (b, h, nq, blk_q) lse
    table is pad-free (the flash_attention streamed-kernel design)."""

    def fn(q, w, lse):
        return (q * 2.0).sum() + w.sum() + lse.sum()

    q = jnp.ones((2, 4, 128, 32), jnp.float32)    # d=32 head
    w = jnp.ones((512, 1), jnp.float32)           # (sq, 1) f32 window
    lse = jnp.ones((2, 4, 8, 128), jnp.float32)   # dense (b, h, nq, blk_q)
    rep = trace.lane_padding_report(fn, q, w, lse, min_bytes=0)
    by_shape = {tuple(f["shape"]): f for f in rep["findings"]}

    head = by_shape[(2, 4, 128, 32)]
    assert head["waste_ratio"] == 4.0
    assert head["padded_bytes"] == 4 * head["bytes"]
    assert "pads to 128 lanes" in head["message"]

    window = by_shape[(512, 1)]
    assert window["padded_bytes"] == 512 * 128 * 4
    assert window["waste_ratio"] == 128.0
    assert "dense" in window["message"]  # the lse-table remediation hint

    assert (2, 4, 8, 128) not in by_shape  # dense tables are pad-free
    assert rep["audited"] >= 3
    assert rep["waste_bytes"] == (head["padded_bytes"] - head["bytes"]
                                  + window["padded_bytes"] - window["bytes"])


def test_tiling_constants_single_source_of_truth():
    """The auditor's byte math (monitor.hbm.lane_padded_bytes) and the
    calibrated flash-attention constants it is documented against must
    agree — if flash_attention ever recalibrates NUM_LANES/NUM_SUBLANES,
    this failure is the signal to update the hbm tiling rule too, instead
    of the two silently diverging."""
    from apex_tpu.monitor.hbm import lane_padded_bytes
    from apex_tpu.ops import flash_attention as fa

    assert fa.NUM_LANES == 128 and fa.NUM_SUBLANES == 8
    # one f32 tile row: lanes x sublanes x itemsize under both rule sets
    assert lane_padded_bytes((1, 1), 4) == fa.NUM_LANES * fa.NUM_SUBLANES * 4
    # the public resident-layout estimator counts the same lane padding
    # the auditor reports: d=32 occupies a full 128-lane tile in K+V
    sk, d, item = 2048, 32, 2
    d_eff = -(-d // fa.NUM_LANES) * fa.NUM_LANES
    assert fa.resident_vmem_bytes(2048, sk, d, 512, 512, item,
                                  False, False) >= 2 * sk * d_eff * item


def test_lane_padding_min_bytes_and_truncation():
    def fn(w):
        return w * 2.0

    w = jnp.ones((8, 1), jnp.float32)  # 4 KB padded: under the default floor
    assert not trace.lane_padding_report(fn, w)["findings"]
    full = trace.lane_padding_report(fn, w, min_bytes=0, max_findings=1)
    # input + output both flagged; truncation is reported, never silent
    assert len(full["findings"]) == 1 and full["findings_truncated"] == 1


def test_lane_padding_audits_pallas_boundaries():
    """Operands crossing a pallas_call boundary are audited even when the
    top-level signature is clean (the custom-call HBM-layout tax)."""
    from apex_tpu.ops.softmax import scaled_masked_softmax

    x = jnp.ones((2, 2, 8, 256), jnp.float32)  # minor dim 256: pad-free
    rep = trace.lane_padding_report(
        lambda a: scaled_masked_softmax(a, impl="pallas"), x)
    assert rep["audited"] >= 4  # signature + pallas operands/results
    assert not rep["findings"]


# ---------------------------------------------------------------------------
# engine 2: collective-transpose hazard detector
# ---------------------------------------------------------------------------


def test_transpose_hazard_flags_bare_pmean_under_grad():
    def bare(x):
        return lax.pmean(jnp.sum(x * x), "i")

    hz = trace.transpose_hazards(bare, jnp.ones((4,)), axes={"i": 8})
    assert hz["hazard"]
    assert hz["extra_in_backward"] == {"psum": 1}  # pmean lowers to psum+div
    assert hz["findings"][0]["rule"] == "grad-transpose"
    assert "over-counts" in hz["findings"][0]["message"]


def test_transpose_hazard_passes_identity_backward_psum():
    """The pipeline loss aggregation uses the identity-backward psum
    (reduce_from_tensor_model_parallel_region) — its custom_vjp leaves NO
    collective in the backward, so it must not be flagged."""
    from apex_tpu.transformer.tensor_parallel.mappings import (
        reduce_from_tensor_model_parallel_region)

    def wrapped(x):
        return reduce_from_tensor_model_parallel_region(jnp.sum(x * x), "i")

    hz = trace.transpose_hazards(wrapped, jnp.ones((4,)), axes={"i": 8})
    assert not hz["hazard"], hz
    assert hz["forward"].get("psum", 0) >= 1  # the forward psum WAS seen
    assert hz["extra_in_backward"] == {}


def test_transpose_hazard_ignores_nonscalar_collectives():
    """psums of activations/grad tensors (e.g. the conjugate TP pair) are
    not loss-shaped; only scalar collectives count."""
    def loss(x):
        y = lax.psum(x * 2.0, "i")  # activation psum: non-scalar
        return jnp.sum(y * y)

    hz = trace.transpose_hazards(loss, jnp.ones((4,)), axes={"i": 8})
    assert hz["forward"] == {} and not hz["hazard"]


# ---------------------------------------------------------------------------
# engine 2: sequence-parallel decomposition tripwire
# ---------------------------------------------------------------------------


def test_sequence_parallel_hazard_flags_activation_psum():
    def regressed(x):
        y = lax.psum(x, "model")  # (b, s, h) all-reduce: the regression
        return y * 2.0

    hz = trace.sequence_parallel_hazards(
        regressed, jnp.ones((2, 8, 4)), axes={"model": 4})
    assert hz["hazard"] and hz["activation_psums"] == 1
    assert hz["findings"][0]["rule"] == "sp-regression"
    assert "psum_scatter/all_gather" in hz["findings"][0]["message"]


def test_sequence_parallel_hazard_passes_decomposed_and_scalar():
    """The decomposed conjugates (reduce_scatter/all_gather) and the
    scalar/rank-2 psums of the vocab-parallel CE are NOT hazards — and the
    census reports them under their buckets."""
    from apex_tpu.parallel.collectives import (
        SEQUENCE_PARALLEL_DECOMPOSED_PRIMS)
    from apex_tpu.transformer.tensor_parallel import mappings

    def decomposed(x):
        y = mappings.reduce_scatter_to_sequence_parallel_region(x, "model")
        y = mappings.gather_from_sequence_parallel_region(y, "model")
        loss2d = lax.psum(jnp.sum(y, -1), "model")  # (b, s): CE-shaped
        return loss2d

    hz = trace.sequence_parallel_hazards(
        decomposed, jnp.ones((2, 8, 4)), axes={"model": 4})
    assert not hz["hazard"], hz
    assert set(hz["census"]["activation"]) == set(
        SEQUENCE_PARALLEL_DECOMPOSED_PRIMS)
    assert hz["census"]["other"] == {"psum": 1}


def test_sequence_parallel_hazard_on_gpt_models(gpt_sp_forward_irs):
    """The model-level regression gate (ISSUE 4 evidence): a
    sequence-parallel GPT forward jaxpr carries ZERO activation psums on
    the TP axis (embedding + per-layer all decomposed), while the plain-TP
    twin shows the all-reduces the mode removes. (Both forwards come
    pre-traced from the module fixture — the analyzer reads the shared
    walk.)"""
    counts = {sp: trace.sequence_parallel_hazards(ir, tp_axis="model")
              for sp, ir in gpt_sp_forward_irs.items()}
    assert counts[True]["activation_psums"] == 0
    assert not counts[True]["hazard"]
    # plain TP: embedding psum + the per-layer pair (scanned body counts
    # call sites once — trace.sequence_parallel_hazards docstring)
    assert counts[False]["activation_psums"] == 3
    assert counts[False]["hazard"]
    # the decomposition is VISIBLE in the SP census, not merely absent
    assert counts[True]["census"]["activation"].get("reduce_scatter", 0) >= 3
    assert counts[True]["census"]["activation"].get("all_gather", 0) >= 3


# ---------------------------------------------------------------------------
# engine 2: ZeRO-redundancy tripwire
# ---------------------------------------------------------------------------


def test_zero_redundancy_flags_bulk_data_psum():
    def double_reduced(g):
        return lax.psum(g, "data") * 2.0  # full-size grad all-reduce

    hz = trace.zero_redundancy_hazards(
        double_reduced, jnp.ones((64, 128)), axes={"data": 8})
    assert hz["hazard"] and hz["bulk_psums"] == 1
    assert hz["findings"][0]["rule"] == "zero-redundancy"
    assert "psum_scatter" in hz["findings"][0]["message"]


def test_zero_redundancy_passes_decomposed_and_scalar():
    """The optimizer's scatter/gather conjugates pass; scalar collectives
    (loss pmean, found_inf pmax, LAMB norm psums) are exempt — reported
    under census['other'] — and the bulk census shows the decomposition
    (the gather is bulk by its RESULT: the per-rank operand is the small
    chunk, the output is the full param)."""
    from apex_tpu.optimizers.distributed import gather_leaf, scatter_chunk
    from apex_tpu.parallel.collectives import ZERO_DECOMPOSED_PRIMS

    def decomposed(g):
        chunk = scatter_chunk(g, 8, "data") / 8
        full = gather_leaf(chunk, g.shape, g.dtype, "data",
                           gather_dtype=jnp.bfloat16)
        loss = lax.pmean(jnp.sum(full), "data")
        bad = lax.pmax(jnp.float32(0.0), "data")
        norm = lax.psum(jnp.sum(chunk * chunk), "data")
        return loss + bad + norm

    hz = trace.zero_redundancy_hazards(
        decomposed, jnp.ones((64, 128)), axes={"data": 8})
    assert not hz["hazard"], hz
    assert set(hz["census"]["bulk"]) == set(ZERO_DECOMPOSED_PRIMS)
    assert hz["census"]["other"].get("pmax") == 1
    assert hz["census"]["other"].get("psum") >= 1  # the norm + loss pmean


def test_zero_redundancy_on_real_mixed_precision_step(zero_amp_step_irs):
    """The actual ZeRO amp step (MixedPrecisionOptimizer(zero_axis=...))
    traces clean; the replicated harness pattern (allreduce_gradients on
    the data axis) is exactly the flagged regression. (Both steps come
    pre-traced from the module fixture.)"""
    hz = trace.zero_redundancy_hazards(zero_amp_step_irs["zero"])
    assert not hz["hazard"], hz
    assert hz["census"]["bulk"].get("reduce_scatter") == 1

    hz = trace.zero_redundancy_hazards(zero_amp_step_irs["replicated"])
    assert hz["hazard"] and hz["bulk_psums"] >= 1


# ---------------------------------------------------------------------------
# engine 2: ZeRO-3 bulk-gather tripwire
# ---------------------------------------------------------------------------


def test_zero3_gather_flags_whole_stack_gather():
    """A whole-stack (model-sized) param gather in a fully-sharded step is
    the O(model) rematerialization; the result-sized rule catches it even
    though the OPERAND is the small per-rank chunk stack."""
    from apex_tpu.optimizers.distributed import gather_stacked_leaf

    chunks = jnp.ones((8, 64), jnp.float32)  # (L, k) at n=8

    hz = trace.zero3_gather_hazards(
        lambda c: gather_stacked_leaf(c, (8, 64), jnp.float32, "data"),
        chunks, axes={"data": 8}, model_elems=8 * 512)
    assert hz["hazard"] and hz["bulk_gathers"] == 1, hz
    assert hz["findings"][0]["rule"] == "zero3-bulk-gather"
    assert hz["census"]["bulk_sites"][0]["result_elems"] == 8 * 512
    assert "per-layer" in hz["findings"][0]["message"]


def test_zero3_gather_passes_per_layer_gathers():
    from apex_tpu.optimizers.distributed import gather_leaf

    L, row = 8, (8, 64)
    chunks = jnp.ones((L, 64), jnp.float32)

    def per_layer(c):
        return jnp.stack([gather_leaf(c[i], row, jnp.float32, "data",
                                      gather_dtype=jnp.bfloat16)
                          for i in range(L)])

    hz = trace.zero3_gather_hazards(per_layer, chunks, axes={"data": 8},
                                    model_elems=L * 512)
    assert not hz["hazard"], hz
    assert hz["layer_gathers"] == L and hz["bulk_gathers"] == 0
    # threshold derivation: bulk_fraction (0.25 default) of the model
    assert hz["min_model_elems"] == L * 512 // 4


def test_zero3_gather_on_real_gpt_step(zero3_gpt_irs):
    """The real fully-sharded drive (zero3_shard + run_layers chunk_meta)
    traces clean through value_and_grad — every gather, forward AND the
    remat re-gathers in backward, is one layer's params — while
    materializing the stacked leaves whole before the loss is flagged.
    (All drives come pre-traced from the module fixture: one trace each,
    shared with the prefetch tripwire below.)"""
    # any single-layer row gather is <= ~1k elems; a stacked-leaf gather
    # is L x that — 4096 splits them at the fixture's (h=16, L=4) shape
    hz = trace.zero3_gather_hazards(zero3_gpt_irs["serialized"],
                                    min_model_elems=4096)
    assert not hz["hazard"], hz
    assert hz["layer_gathers"] >= zero3_gpt_irs["num_layers"]  # unrolled

    hz = trace.zero3_gather_hazards(zero3_gpt_irs["bulk"],
                                    min_model_elems=4096)
    assert hz["hazard"] and hz["bulk_gathers"] >= 1, hz


# ---------------------------------------------------------------------------
# engine 2: ZeRO-3 gather-prefetch tripwire
# ---------------------------------------------------------------------------


def test_unprefetched_gather_flags_remat_fused_gathers():
    """Per-layer gathers INSIDE rematerialized bodies (the serialized
    unrolled ZeRO-3 drive) are pinned to their layer's schedule — the
    hazard; free-standing gathers issued ahead of the compute (the
    double-buffered drive's structure) pass."""
    import jax

    from apex_tpu.optimizers.distributed import gather_leaf

    row = (16, 16)
    chunks = jnp.ones((4, 32), jnp.float32)  # 4 layers, k=32 at n=8
    h0 = jnp.ones((2, 16), jnp.float32)

    def serialized(c, h):
        for i in range(4):
            body = jax.checkpoint(
                lambda ci, hh: jnp.tanh(
                    hh @ gather_leaf(ci, row, jnp.float32, "data")))
            h = body(c[i], h)
        return jnp.sum(h * h)

    def prefetched(c, h):
        gathered = [gather_leaf(c[i], row, jnp.float32, "data")
                    for i in range(4)]
        for p in gathered:
            h = jnp.tanh(h @ p)
        return jnp.sum(h * h)

    bad = trace.unprefetched_gather_hazards(
        jax.grad(serialized, argnums=0), chunks, h0, axes={"data": 8})
    assert bad["hazard"] and bad["fused_gathers"] >= 2, bad
    assert bad["findings"][0]["rule"] == "unprefetched-gather"
    ok = trace.unprefetched_gather_hazards(
        jax.grad(prefetched, argnums=0), chunks, h0, axes={"data": 8})
    assert not ok["hazard"] and ok["free_gathers"] >= 4, ok


def test_unprefetched_gather_on_real_zero3_step(zero3_gpt_irs):
    """Both ways on the REAL drives: the serialized unrolled chunk_meta
    step (zero3_prefetch=0) flags; the double-buffered drive
    (zero3_prefetch=1, models/_transformer._prefetched_zero3_drive)
    traces clean with its gathers free — and still passes the bulk-gather
    tripwire (per-layer gathers only). The SAME StepIRs the bulk-gather
    test reads: one trace, N analyzers (the single-trace-walker
    contract)."""
    bad = trace.unprefetched_gather_hazards(zero3_gpt_irs["serialized"])
    assert bad["hazard"] and bad["fused_gathers"] >= 2, bad
    ok = trace.unprefetched_gather_hazards(zero3_gpt_irs["prefetched"])
    assert not ok["hazard"] and ok["free_gathers"] >= 4, ok
    # the prefetched drive must not regress the bulk-gather tripwire
    bulk = trace.zero3_gather_hazards(zero3_gpt_irs["prefetched"],
                                      min_model_elems=4096)
    assert not bulk["hazard"], bulk


# ---------------------------------------------------------------------------
# engine 2: quantized-collective tripwire
# ---------------------------------------------------------------------------


def test_quantized_comm_flags_fat_wire():
    """A step that requests a quantized grad reduce but still moves an
    fp32-sized bulk reduce payload on the zero axis is the fat-wire
    regression (the itemsize-keyed census catches the surviving
    psum_scatter AND an unencoded bulk all_to_all)."""
    from apex_tpu.optimizers.distributed import scatter_chunk

    big = jnp.ones((64, 128), jnp.float32)
    hz = trace.quantized_comm_hazards(
        lambda g: scatter_chunk(g, 8, "data") / 8, big, axes={"data": 8})
    assert hz["hazard"] and hz["fat_reduces"] == 1, hz
    assert hz["findings"][0]["rule"] == "quantized-comm-fat-wire"
    assert hz["census"] == {"4": {"reduce_scatter": 1}}

    # a bf16 wire is still fat (2 B/elem): only the 1-byte dtypes pass
    hz2 = trace.quantized_comm_hazards(
        lambda g: scatter_chunk(g.astype(jnp.bfloat16), 8, "data"),
        big, axes={"data": 8})
    assert hz2["hazard"] and hz2["census"] == {"2": {"reduce_scatter": 1}}


def test_quantized_comm_passes_encoded_pair_and_checks_residual():
    """The encoded all_to_all pair traces clean (the fp32 scale
    side-channel sits below the bulk floor); a quantized GRAD reduce whose
    state lacks the 'err' residual tree flags the error-feedback check."""
    from apex_tpu.parallel.quantize import quantized_reduce_scatter

    big = jnp.ones((64, 128), jnp.float32)

    def good(g):
        chunk, _ = quantized_reduce_scatter(g, 8, "data", "int8")
        return chunk / 8

    hz = trace.quantized_comm_hazards(good, big, axes={"data": 8},
                                      residual={"err": {"w": None}})
    assert not hz["hazard"], hz
    assert hz["quantized_reduces"] == 1 and hz["census"] == {
        "1": {"all_to_all": 1}}

    hz_nores = trace.quantized_comm_hazards(good, big, axes={"data": 8},
                                            residual=None)
    assert hz_nores["hazard"]
    assert hz_nores["findings"][0]["rule"] == "quantized-comm-no-residual"
    # default: residual unchecked (activation-only traffic has none)
    assert not trace.quantized_comm_hazards(
        good, big, axes={"data": 8})["hazard"]


def test_quantized_comm_on_real_mixed_precision_step(zero_amp_step_irs):
    """The actual reduce_dtype='int8' amp step traces clean with its
    residual state; the SAME step read at reduce_dtype=None is the
    flagged fat-wire pattern — the tripwire pair the selftest runs.
    (Pre-traced by the module fixture, shared with the redundancy
    test.)"""
    hz = trace.quantized_comm_hazards(
        zero_amp_step_irs["q8"], residual=zero_amp_step_irs["residual"])
    assert not hz["hazard"], hz
    assert hz["quantized_reduces"] >= 1

    hz = trace.quantized_comm_hazards(zero_amp_step_irs["fp32_wire"])
    assert hz["hazard"] and hz["fat_reduces"] >= 1


# ---------------------------------------------------------------------------
# engine 2: MoE dispatch tripwire (ISSUE 15)
# ---------------------------------------------------------------------------


def _moe_fixture(dispatch_dtype=None):
    """An expert-parallel MoE layer + (full, per-shard) param pair at a
    shape whose dispatch buckets clear the bulk floor (E=8, C=128, d=8:
    8192 elems/bucket)."""
    import jax

    from apex_tpu.transformer.moe import MoEMLP

    layer = MoEMLP(8, 16, num_experts=8, top_k=2, capacity_factor=2.0,
                   expert_axis="data", dispatch_dtype=dispatch_dtype)
    full = layer.init(jax.random.PRNGKey(0))
    local = {"router": full["router"],
             "fc1": jax.tree.map(lambda v: v[:1], full["fc1"]),
             "fc2": jax.tree.map(lambda v: v[:1], full["fc2"])}
    return layer, full, local, jnp.ones((256, 8), jnp.float32)


def test_moe_dispatch_flags_replicated_experts():
    """An expert-parallel request whose trace has NO dispatch-shaped
    all_to_all on the expert axis silently runs every expert on every
    rank — the replicated-expert regression."""
    layer, full, _, x = _moe_fixture()
    hz = trace.moe_dispatch_hazards(layer.apply, full, x, axes={"data": 8})
    assert hz["hazard"] and hz["dispatch_all_to_alls"] == 0, hz
    assert hz["findings"][0]["rule"] == "moe-dispatch-missing"


def test_moe_dispatch_passes_expert_parallel_and_checks_wire():
    """The real all_to_all dispatch passes; the SAME exact-wire dispatch
    under a quantized-wire request flags fat-wire; the encoded exchange
    (dispatch_dtype='int8') passes the wire check with its fp32 scale
    side-channel below the bulk floor."""
    layer, _, local, x = _moe_fixture()
    hz = trace.moe_dispatch_hazards(
        layer.apply_expert_parallel, local, x, axes={"data": 8})
    assert not hz["hazard"] and hz["dispatch_all_to_alls"] == 2, hz
    assert hz["census"]["dispatch"] == {"4": {"all_to_all": 2}}

    fat = trace.moe_dispatch_hazards(
        layer.apply_expert_parallel, local, x, axes={"data": 8},
        wire_dtype="int8")
    assert fat["hazard"] and fat["fat_dispatches"] == 2, fat
    assert fat["findings"][0]["rule"] == "moe-dispatch-fat-wire"

    qlayer, _, qlocal, _ = _moe_fixture(dispatch_dtype="int8")
    ok = trace.moe_dispatch_hazards(
        qlayer.apply_expert_parallel, qlocal, x, axes={"data": 8},
        wire_dtype="int8")
    assert not ok["hazard"], ok
    assert ok["census"]["dispatch"] == {"1": {"all_to_all": 2}}


def test_moe_dispatch_ignores_zero_grad_chunk_all_to_alls():
    """The quantized ZeRO grad reduce's rank-2 chunk-row all_to_alls on
    the SAME mesh axis land in census['chunk'], never the dispatch table
    — a zero+moe hybrid step audits each wire independently."""
    from apex_tpu.parallel.quantize import quantized_reduce_scatter

    def grad_reduce(g):
        chunk, _ = quantized_reduce_scatter(g, 8, "data", "int8")
        return chunk / 8

    hz = trace.moe_dispatch_hazards(
        grad_reduce, jnp.ones((64, 128), jnp.float32), axes={"data": 8},
        wire_dtype="int8")
    assert not hz["census"]["dispatch"], hz
    assert hz["census"]["chunk"] == {"1": {"all_to_all": 1}}
    # missing-dispatch still fires (there IS no dispatch) — callers hand
    # the tripwire the MoE step, not a bare grad reduce
    assert hz["findings"][0]["rule"] == "moe-dispatch-missing"


# ---------------------------------------------------------------------------
# engine 2: recompile-hazard scanner
# ---------------------------------------------------------------------------


def test_untimed_schedule_hazard_flags_spanless_drive():
    """A pipeline ring drive traced under an armed tracer with no pipe
    spans is the census-only regression (the step-anatomy tripwire); a
    span-emitting drive and a drive-free fn pass. The REAL compiled-vs-
    traced-drive pairing is pinned in tests/test_tracing.py."""
    import jax

    from apex_tpu.transformer.pipeline_parallel import schedules

    run_stage = lambda lp, h: h * (1.0 + jnp.sum(lp))  # noqa: E731
    layers_l = jnp.ones((4, 2, 2))
    h_mb = jnp.ones((4, 3, 5))
    ring = jax.vmap(
        lambda ll, hm: schedules._pipeline_ring(run_stage, ll, hm, "i"),
        axis_name="i")

    bad = trace.untimed_schedule_hazards(
        lambda: jax.make_jaxpr(ring)(layers_l, h_mb))
    assert bad["hazard"] and bad["drives"] == 1 and bad["pipe_spans"] == 0
    assert bad["findings"][0]["rule"] == "untimed-schedule"

    def timed():
        from apex_tpu.monitor import tracing

        jax.make_jaxpr(ring)(layers_l, h_mb)
        tracing.get_tracer().record("fwd", dur_s=0.01, cat="pipe", rank=0)

    ok = trace.untimed_schedule_hazards(timed)
    assert not ok["hazard"] and ok["pipe_spans"] == 1

    none = trace.untimed_schedule_hazards(lambda: jnp.ones(()) * 2)
    assert not none["hazard"] and none["drives"] == 0


def test_recompile_hazards_name_offending_leaves():
    haz = trace.recompile_hazards(
        {"opt": {"loss_scale": 2.0 ** 16}, "x": jnp.ones((2,), jnp.float32)},
        weak=jnp.asarray(1.0))
    kinds = {h["kind"]: h for h in haz}
    assert set(kinds) == {"python-scalar", "weak-type"}
    assert "loss_scale" in kinds["python-scalar"]["where"]
    assert kinds["weak-type"]["where"].startswith("kwargs")
    assert all(h["rule"] == "recompile-hazard" for h in haz)


def test_recompile_hazards_clean_signature():
    assert trace.recompile_hazards(
        jnp.ones((2, 2), jnp.bfloat16),
        {"step": jnp.asarray(0, jnp.int32)}) == []


def test_step_report_composite():
    rep = trace.step_report(
        lambda w, s: (w * s).sum(),
        jnp.ones((512, 1), jnp.float32), 2.0, min_bytes=0)
    assert rep["lane_padding"]["flagged"] >= 1
    assert rep["lane_padding"]["worst"][0]["shape"] == [512, 1]
    assert [h["kind"] for h in rep["recompile_hazards"]] == ["python-scalar"]


# ---------------------------------------------------------------------------
# decode-recompile tripwire (serving; the real engine stream is pinned in
# tests/test_serve.py)
# ---------------------------------------------------------------------------


def test_decode_recompile_flags_growing_kv_and_scalar_leaks():
    """A decode argument stream whose per-request KV grows with the
    sequence — or that ships python-int positions — is one recompile per
    generated token (the latency cliff the paged cache exists to
    prevent)."""
    grow = trace.decode_recompile_hazards(
        lambda t: (jnp.ones((1, 2, t + 4, 8), jnp.float32),
                   jnp.zeros((2,), jnp.int32)), ticks=3)
    assert grow["hazard"]
    rules = {f["rule"] for f in grow["findings"]}
    assert "decode-shape-churn" in rules
    assert any("recompile" in f["message"] for f in grow["findings"])

    leak = trace.decode_recompile_hazards(
        lambda t: (jnp.ones((4,), jnp.float32), {"tick": t}), ticks=2)
    assert leak["hazard"]
    assert any(f.get("kind") == "python-scalar" for f in leak["findings"])

    struct = trace.decode_recompile_hazards(
        lambda t: tuple(jnp.zeros((2,), jnp.int32) for _ in range(t + 1)),
        ticks=2)
    assert struct["hazard"]
    assert struct["findings"][0]["rule"] == "decode-structure-churn"


def test_decode_recompile_passes_shape_stable_stream():
    """The engine contract: identical shapes/dtypes every tick — fixed
    slot arrays, the paged pool, committed int32 positions, a traced
    tick scalar."""
    def args(t):
        return (jnp.zeros((2, 8, 4, 4), jnp.float32),   # page pool
                jnp.zeros((4, 6), jnp.int32),            # block tables
                jnp.zeros((4,), jnp.int32),              # lengths
                jnp.asarray(t, jnp.int32))               # traced tick

    ok = trace.decode_recompile_hazards(args, ticks=4)
    assert not ok["hazard"] and ok["ticks"] == 4 and ok["leaves"] == 4


def test_decode_recompile_audits_extra_streams_both_ways():
    """ISSUE 12: the extended tripwire audits the chunked-prefill and
    speculative-verify argument streams by the same rules — clean static
    streams pass (with per-stream leaf counts), a chunk width that grows
    with the prompt or a python-int draft length is flagged WITH its
    stream name (one recompile per request otherwise)."""
    decode = lambda t: (jnp.zeros((2, 8, 4, 4), jnp.float32),  # noqa: E731
                        jnp.asarray(t, jnp.int32))
    chunk_ok = lambda t: (jnp.zeros((1, 16), jnp.int32),       # noqa: E731
                          jnp.asarray(t * 16, jnp.int32),
                          jnp.asarray(16, jnp.int32))
    verify_ok = lambda t: (jnp.zeros((4, 3), jnp.int32),       # noqa: E731
                           jnp.zeros((4,), jnp.int32))
    ok = trace.decode_recompile_hazards(
        decode, ticks=3,
        extra_streams={"chunk": chunk_ok, "verify": verify_ok})
    assert not ok["hazard"], ok["findings"][:3]
    assert ok["stream_leaves"] == {"decode": 2, "chunk": 3, "verify": 2}

    # a chunk buffer that grows with the prompt = a fresh signature per
    # request; a python-int draft length = weak-typed cache churn
    bad = trace.decode_recompile_hazards(
        decode, ticks=2,
        extra_streams={
            "chunk": lambda t: (jnp.zeros((1, 16 * (t + 1)), jnp.int32),),
            "verify": lambda t: (jnp.zeros((4, 3), jnp.int32), 3)})
    assert bad["hazard"]
    tagged = {(f["stream"], f["rule"]) for f in bad["findings"]}
    assert ("chunk", "decode-shape-churn") in tagged, tagged
    assert ("verify", "recompile-hazard") in tagged, tagged
    assert all(f["stream"] != "decode" for f in bad["findings"])

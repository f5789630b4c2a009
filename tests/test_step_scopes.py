"""The step names its own phases: the scopes of ``CHANGES.md``'s table
(PR 26) are a contract between the program and ``chipbench/metrics/``.

On the CPU, at a tiny size, for the GPT step ``pretrain_gpt.main`` builds
(O2, FusedAdam, microbatch ring), the BERT + LAMB step the benchmark's
adapter composes, the expert model's step ``pretrain_instella.build``
makes (PR 28: a second block under the same contract, with scopes of its
own inside it), the LFM2 model's step ``pretrain_lfm2.build`` makes (PR
34: a stack of four kinds of layer, the conv operator's scopes beside the
attention's) and the Kimi Linear model's step ``pretrain_kimi_linear.build``
makes (PR 36: the KDA operator's scopes beside the latent attention's and
the experts'): every scope names instructions where its phase runs, every
``chipbench/metrics/train.*.json`` that reads scopes finds something to read
(a rename would end a traced chip run with exit code 4, after the chip time
is spent), and the scopes change nothing but metadata in the compiled step.
"""

import contextlib
import glob
import os
import re
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "examples", "gpt"))

from chipbench import manifest, trace_reduce  # noqa: E402
from chipbench.readers import phase_time  # noqa: E402

#: scopes of the model: forward, recompute (under ``layers``) and backward
MODEL_SCOPES = ("layers", "attention_core", "layer_norm", "head")
#: scopes of the update: after the gradient, so under no ``jvp(``
UPDATE_SCOPES = ("amp_unscale", "optimizer_update", "amp_cast",
                 "amp_scale_update")
#: the second block's own scopes, all inside the scanned, checkpointed stack
INSTELLA_SCOPES = ("attention", "attn_latent", "rope", "attn_gate", "mlp",
                   "moe_shared", "moe", "moe_route", "moe_dispatch",
                   "moe_experts", "moe_combine")
#: the LFM2 block's: the conv operator and the filter between its two
#: projections, the head norms under ``layer_norm``, the routed experts'
LFM2_SCOPES = ("conv_operator", "conv_mix", "attention", "qk_norm", "rope",
               "mlp", "moe", "moe_route", "moe_dispatch", "moe_experts",
               "moe_combine")
#: the Kimi Linear block's: the KDA operator from its first projection to
#: ``W_o`` with the filters, the gates and the chunked scan inside it, the
#: latent attention's as the expert model names them (no rotation: no
#: ``rope``), the shared and the routed experts'
KIMI_SCOPES = ("kda_operator", "conv_mix", "kda_gates", "kda_scan",
               "attention", "attn_latent", "mlp", "moe_shared", "moe",
               "moe_route", "moe_dispatch", "moe_experts", "moe_combine")
OWN_SCOPES = {"instella": INSTELLA_SCOPES, "lfm2": LFM2_SCOPES,
              "kimi": KIMI_SCOPES}
PROGRAMS = ("gpt", "bert", "instella", "lfm2", "kimi")
#: the cell of the benchmark that runs each program
CELLS = {"gpt": "gpt2_345m.pretrain_b8s1024",
         "bert": "bert_large.pretrain_b8s512",
         "instella": "instella_moe_16b_a3b.pretrain_b8s4096",
         "lfm2": "lfm2_8b_a1b.pretrain_b4s8192",
         "kimi": "kimi_linear_48b_a3b.pretrain_b2s8192"}
METRICS = sorted(
    os.path.basename(f)[:-len(".json")]
    for f in glob.glob(os.path.join(ROOT, "chipbench", "metrics", "*.json"))
    if manifest.load_json(f)["reader"] == "phase_time")


def token(scope: str) -> str:
    """A scope as ``op_name`` carries it: ``/layers/`` inside a scanned
    body, ``jvp(layers)`` where the gradient was taken around it."""
    return rf"[/(]{scope}[/)]"


def _gpt_text() -> str:
    import pretrain_gpt

    run = pretrain_gpt.main(
        "--hidden 64 --layers 2 --heads 4 --seq 32 --vocab 256 "
        "--micro-batch 1 --num-microbatches 2 --opt-level O2 "
        "--steps 1".split())
    toks, tgts = run["next_batch"]()
    return run["train_step"].lower(
        run["params"], run["opt_state"], toks, tgts).compile().as_text()


def _bert_text() -> str:
    import numpy as np

    from apex_tpu import amp
    from chipbench.programs import bert_lamb
    from chipbench.references import train as ref_train
    from chipbench.tests import tiny

    cell = manifest.cell(tiny.tiny_bench(ROOT),
                         "bert_tiny.pretrain_bert_tiny", ROOT)
    cfg, mix = cell["config"], cell["mix"]
    model, policy, mp_opt, step = bert_lamb.build(cfg, mix)

    def state(key):
        params = amp.cast_params(model.init(key), policy)
        return params, mp_opt.init(params)

    batch = ref_train.family(cfg["reference"]).make_batch(
        cfg, mix, np.random.default_rng(0), mix["batch"])
    return step.lower(
        *jax.eval_shape(state, jax.random.PRNGKey(0)),
        *(batch[k] for k in bert_lamb.Program.FEED)).compile().as_text()


def _trainer_step(adapter, cell):
    """``(model, step, its arguments)`` as an adapter's ``build`` makes
    them for a tiny cell."""
    import numpy as np

    from apex_tpu import amp
    from chipbench.references import train as ref_train

    cfg, mix = cell["config"], cell["mix"]
    model, policy, mp_opt, step = adapter.build(cfg, mix, ROOT)

    def state(key):
        params = amp.cast_params(model.init(key), policy)
        return params, mp_opt.init(params)

    batch = ref_train.family(cfg["reference"]).make_batch(
        cfg, mix, np.random.default_rng(0), mix["batch"])
    return model, step, (*jax.eval_shape(state, jax.random.PRNGKey(0)),
                         *(batch[k] for k in adapter.Program.FEED))


def _trainer_text(adapter, cell) -> str:
    """The compiled step an adapter's ``build`` makes for a tiny cell."""
    _, step, args = _trainer_step(adapter, cell)
    return step.lower(*args).compile().as_text()


def _instella_text() -> str:
    from chipbench.programs import pretrain_instella
    from chipbench.tests import tiny_instella

    return _trainer_text(pretrain_instella, tiny_instella.cell(ROOT))


def _lfm2_text() -> str:
    from chipbench.programs import pretrain_lfm2
    from chipbench.tests import tiny_lfm2

    return _trainer_text(pretrain_lfm2, tiny_lfm2.cell(ROOT))


def _kimi_text() -> str:
    from chipbench.programs import pretrain_kimi_linear
    from chipbench.tests import tiny_kimi_linear

    return _trainer_text(pretrain_kimi_linear, tiny_kimi_linear.cell(ROOT))


BUILD = {"gpt": _gpt_text, "bert": _bert_text, "instella": _instella_text,
         "lfm2": _lfm2_text, "kimi": _kimi_text}


@pytest.fixture(scope="module")
def texts(tmp_path_factory):
    """Each program's compiled text as it is, and built again with
    ``jax.named_scope`` a no-op."""
    mp = pytest.MonkeyPatch()
    # with the variable set the trainer's cache helper configures nothing,
    # so the rest of the suite keeps running without a persistent cache
    mp.setenv("JAX_COMPILATION_CACHE_DIR",
              str(tmp_path_factory.mktemp("cache")))
    try:
        named = {p: BUILD[p]() for p in PROGRAMS}
        mp.setattr(jax, "named_scope",
                   lambda name: contextlib.nullcontext())
        bare = {p: BUILD[p]() for p in PROGRAMS}
    finally:
        mp.undo()
    return {"named": named, "bare": bare}


@pytest.fixture(scope="module")
def scopes(texts):
    return {p: list(trace_reduce.hlo_scopes(t).values())
            for p, t in texts["named"].items()}


def _some(scopes, *patterns, none=()):
    return [s for s in scopes
            if all(re.search(p, s) for p in patterns)
            and not any(re.search(p, s) for p in none)]


@pytest.mark.parametrize("program,scope", [
    (p, s) for p in PROGRAMS for s in MODEL_SCOPES
] + [(p, s) for p, own in OWN_SCOPES.items() for s in own])
def test_model_scope_names_forward_backward_and_recompute(
        scopes, program, scope):
    mine = scopes[program]
    assert _some(mine, token(scope), r"jvp\(", none=(r"transpose\(",)), \
        f"{scope}: nothing in the forward pass"
    assert _some(mine, token(scope), r"transpose\(",
                 none=("rematted_computation",)), \
        f"{scope}: nothing in the backward pass"
    # the head is outside the checkpointed stack, and its recompute stops
    # at the grouped products: what combine's backward reads (the
    # products' rows, the weights, the plan) is made under other scopes
    if scope not in ("head", "moe_combine"):
        assert _some(mine, token(scope), token("layers"),
                     "/rematted_computation/"), \
            f"{scope}: nothing in the recompute"


@pytest.mark.parametrize("scope", UPDATE_SCOPES)
@pytest.mark.parametrize("program", PROGRAMS)
def test_update_scope_names_instructions_outside_the_gradient(
        scopes, program, scope):
    mine = _some(scopes[program], token(scope))
    assert mine, f"{scope}: names no instruction"
    assert not _some(mine, r"jvp\("), f"{scope}: inside the gradient"


#: what a row mover of the dropless layer compiles to
MOVER_OPS = ("gather", "scatter", "dynamic-slice", "dynamic-update-slice",
             "while", "sort", "custom-call")


def test_the_dropless_movers_stay_under_dispatch_and_combine(texts):
    """``train.moe_route_ms`` cuts by scope, so a mover that loses its scope
    is time renamed, not saved: under ``moe`` every gather, slice, loop,
    sort and custom call (the loops' bodies and the transposed passes
    included) names ``moe_dispatch`` or ``moe_combine`` (the router's own
    gather ``moe_route``), and each mover's loop is where its pass runs."""
    inner = "|".join(token(s) for s in (
        "moe_route", "moe_dispatch", "moe_combine", "moe_experts"))
    found = []
    for line in texts["named"]["instella"].splitlines():
        m = re.search(r"\s(%s)\(.*op_name=\"([^\"]+)\"" % "|".join(MOVER_OPS),
                      line)
        if m and re.search(token("moe"), m.group(2)):
            assert re.search(inner, m.group(2)), line
            found.append(m.groups())
    # and whatever is the size of the buffer is a mover's or a product's
    # (the zeros a loop writes into: a constant would lose the scope)
    from chipbench.programs import pretrain_instella
    from chipbench.tests import tiny_instella

    cell = tiny_instella.cell(ROOT)
    model = pretrain_instella.build(cell["config"], cell["mix"], ROOT)[0]
    rows = model.experts.buffer_rows(cell["mix"]["batch"] * cell["mix"]["seq"])
    sized = re.compile(
        r"= \w+\[(%d|%d),%d\]\S* ([\w\-]+)\(.*op_name=\"([^\"]+)\"" % (
            rows, rows + 1, model.cfg.hidden_size))
    # (the CPU's compiler widens the products' operands to float32 under
    # the stack's name alone: its own ``convert``s are no mover's)
    sizes = [m.group(3) for m in map(sized.search,
                                     texts["named"]["instella"].splitlines())
             if m and m.group(2) != "convert"]
    assert len(sizes) > 20
    assert not _some(sizes, "^", none=(inner,)), _some(
        sizes, "^", none=(inner,))
    loops = [name for op, name in found if op == "while"]
    bodies = [name for op, name in found
              if op == "gather" and "/while/body/" in name]
    for some in (loops, bodies):
        for scope in ("moe_dispatch", "moe_combine"):
            mine = _some(some, token(scope))
            assert _some(mine, r"jvp\(", none=(r"transpose\(",)), scope
            assert _some(mine, r"transpose\(",
                         none=("rematted_computation",)), scope
            if scope == "moe_dispatch":   # the recompute stops at the products
                assert _some(mine, "/rematted_computation/"), scope


def _traced(jaxpr, stack=""):
    """``(primitive, name, result shapes)`` of every equation of a traced
    program, those inside its scans, checkpoints and calls included, each
    named as the compiled instruction's ``op_name`` names it. A kernel is
    one equation here (``pallas_call``), on the chip one Mosaic call."""
    from apex_tpu.lint import ir

    for eqn in jaxpr.eqns:
        name = "/".join(
            part for part in (stack, str(eqn.source_info.name_stack)) if part)
        inner = ir.sub_jaxprs(eqn)
        if inner and eqn.primitive.name != "pallas_call":
            for sub in inner:
                yield from _traced(sub, name)
        else:
            yield (eqn.primitive.name, f"{name}/{eqn.primitive.name}",
                   [getattr(v.aval, "shape", ()) for v in eqn.outvars])


@pytest.mark.parametrize("program", sorted(OWN_SCOPES))
def test_under_moe_experts_only_products_and_kernels_are_buffer_sized(
        program, monkeypatch):
    """Everything between ``spread_rows`` and ``collect_rows`` costs by the
    rows filled (PR 35): in the traced step, with the kernels taken as the
    chip takes them, whatever under ``moe_experts`` has the buffer's rows
    is a grouped product or a kernel whose walk ends at ``filled``: no
    elementwise pass, no sum of two cotangents. And the kernels keep the
    scope ``train.moe_experts_ms`` reads, forward, in the recompute and
    backward: one that lost it would read as a gain in that row and a loss
    under ``train.unattributed_ms``."""
    from apex_tpu.ops import gated_rows
    from chipbench.programs import (
        pretrain_instella,
        pretrain_kimi_linear,
        pretrain_lfm2,
    )
    from chipbench.tests import tiny_instella, tiny_kimi_linear, tiny_lfm2

    adapter, tiny = {"instella": (pretrain_instella, tiny_instella),
                     "lfm2": (pretrain_lfm2, tiny_lfm2),
                     "kimi": (pretrain_kimi_linear, tiny_kimi_linear)
                     }[program]
    # off the chip 'auto' is the jax.numpy form: the kernels, as there
    monkeypatch.setattr(gated_rows, "_resolve_impl", lambda _: "pallas")
    cell = tiny.cell(ROOT)
    model, step, args = _trainer_step(adapter, cell)
    rows = model.experts.buffer_rows(cell["mix"]["batch"] * cell["mix"]["seq"])
    scope = manifest.metric_file(
        ROOT, ["chipbench"], "train.moe_experts_ms")["params"]["scope"]
    under = [(prim, name, shapes) for prim, name, shapes in _traced(
        jax.make_jaxpr(step)(*args).jaxpr) if re.search(scope, name)]
    mine = [(prim, name) for prim, name, shapes in under
            if any(s[:1] == (rows,) for s in shapes)]
    assert {prim for prim, _ in mine} == {"ragged_dot_general", "pallas_call"}
    for prim in ("ragged_dot_general", "pallas_call"):
        names = [name for p, name in mine if p == prim]
        assert _some(names, r"jvp\(", none=(r"transpose\(",)), prim
        assert _some(names, token("layers"), "/rematted_computation/"), prim
        assert _some(names, r"transpose\(",
                     none=("rematted_computation",)), prim
    # eight products to a layer's three kernels, where twelve were: the
    # gate's and the up product are one, so the rows have one cotangent
    count = lambda prim: len([p for p, _, _ in under if p == prim])
    assert 3 * count("ragged_dot_general") == 8 * count("pallas_call") > 0



def test_the_kda_kernel_keeps_the_scan_scope(monkeypatch):
    """With the chunk-local kernels taken as the chip takes them, the
    forward's ``pallas_call`` lies under ``kda_scan`` in the forward pass,
    in the recompute and in the backward pass (the scan's operands made
    again), and the transpose's (five results, not six) under ``kda_scan``
    inside ``transpose(jvp(...))``, once a KDA layer: a kernel that lost
    the scope would read as a gain in ``train.kda_scan_ms`` and as an
    impossible share in ``train.kda_scan_roofline``. Off the chip every
    other kernel of the step takes its ``jax.numpy`` form, so each
    ``pallas_call`` is one of these two."""
    from apex_tpu.ops import kda
    from chipbench.programs import pretrain_kimi_linear
    from chipbench.tests import tiny_kimi_linear

    monkeypatch.setattr(kda, "_resolve_impl", lambda _: "pallas")
    _, step, args = _trainer_step(pretrain_kimi_linear,
                                  tiny_kimi_linear.cell(ROOT))
    found = [(name, len(shapes)) for prim, name, shapes in _traced(
        jax.make_jaxpr(step)(*args).jaxpr) if prim == "pallas_call"]
    calls = [name for name, _ in found]
    assert calls and _some(calls, token("kda_scan")) == calls, calls
    forward = [name for name, results in found if results == 6]
    transposes = [name for name, results in found if results == 5]
    assert len(forward) + len(transposes) == len(calls)
    assert _some(forward, r"jvp\(", none=(r"transpose\(",
                                          "rematted_computation"))
    assert _some(forward, token("layers"), "/rematted_computation/")
    assert _some(forward, r"transpose\(", none=("rematted_computation",))
    # forward, recompute and the backward's operands: three a layer
    assert transposes and len(forward) == 3 * len(transposes)
    assert _some(transposes, r"transpose\(jvp\(",
                 none=("rematted_computation",)) == transposes


def _reader_ctx(scopes):
    ops = [{"scope": s, "dur": 2e-3} for s in scopes]
    return {"ops": {"/device:TPU:0": ops},
            "runs": {"/device:TPU:0": [(0.0, 1.0), (1.0, 2.0)]}}


def _listed(metric: str) -> list:
    """The programs whose cell the manifest lists under the metric."""
    listed = next(m for m in manifest.load(ROOT)["per_layer"]
                  if m["name"] == metric)["workloads"]
    return [p for p in PROGRAMS if CELLS[p] in listed]


@pytest.mark.parametrize("program,metric", [
    (p, m) for m in METRICS for p in _listed(m)])
def test_metric_file_finds_something_to_read(scopes, program, metric):
    params = manifest.metric_file(ROOT, ["chipbench"], metric)["params"]
    ctx = _reader_ctx(scopes[program])
    assert trace_reduce.matching(ctx["ops"]["/device:TPU:0"],
                                 params["since"]), \
        "the scope that tells a program with names from one without"
    got = phase_time.read(ctx, **params)
    assert got is not None and got > 0, (metric, params)


#: PR 26's seven, which every cell reports
SHARED = ["train.amp_unscale_ms", "train.attention_proj_ms",
          "train.layer_norm_ms", "train.lm_head_ms",
          "train.optimizer_update_ms", "train.recompute_ms",
          "train.unattributed_ms"]
#: PR 28's two: the latent attention's scopes only the expert model's
#: block has, the routed experts' the LFM2 block has too
INSTELLA_ONLY = ["train.attn_latent_ms"]
ROUTED = ["train.moe_route_ms"]
#: PR 34's two, which read the conv operator's scopes; the filter's the
#: KDA operator names too
LFM2_ONLY = ["train.conv_mix_ms", "train.conv_proj_ms"]
#: PR 36's two, which read the KDA operator's scopes
KIMI_ONLY = ["train.kda_scan_ms", "train.kda_proj_ms"]


def test_the_metrics_that_read_scopes():
    """Seven that every cell reports: ``amp_cast`` has no metric of its own,
    since the compiler fuses the cast into the update (``PERF.md``,
    Findings, PR 26) and ``train.optimizer_update_ms`` reads both scopes.
    Two more for the expert model's cell, of which the LFM2 model's cell
    reports one (their third, ``train.moe_experts_ms``, also reads the
    grouped-product calls by name and has a reader of its own), two for the
    LFM2 model's cell, and two for the Kimi Linear model's cell alone, which
    also reports the latent attention's, the routed experts' and the
    filter's."""
    assert METRICS == sorted(SHARED + INSTELLA_ONLY + ROUTED + LFM2_ONLY
                             + KIMI_ONLY)
    update = manifest.metric_file(ROOT, ["chipbench"],
                                  "train.optimizer_update_ms")
    for scope in ("optimizer_update", "amp_cast"):
        assert re.search(update["params"]["include"], f"jit(f)/{scope}/mul")


@pytest.mark.parametrize("case,scopes_,want", [
    ("found", ["jit(f)/layers/x", "jit(f)/optimizer_update/add"], 1.0),
    # a program built before the scopes: the overlaid parent of PR 26
    ("no name at all", ["jit(f)/jvp()/while/body/mul", ""], 0.0),
    # the names are there and this one is not: renamed, so nothing
    ("renamed", ["jit(f)/layers/x", "jit(f)/update/add"], None),
])
def test_phase_time_reader(case, scopes_, want):
    got = phase_time.read(_reader_ctx(scopes_),
                          include=token("optimizer_update"),
                          since=token("layers"))
    assert got == want, case


def _instructions(text: str) -> list:
    """The compiled text with nothing but what executes: metadata cut from
    every instruction, and the tables of files, functions and stack frames
    that head the module left out."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    return [line for line in text.splitlines()
            if not re.match(r"^\d+ ", line)
            and not line.startswith(("FileNames", "FunctionNames",
                                     "FileLocations", "StackFrames"))]


@pytest.mark.parametrize("program", PROGRAMS)
def test_scopes_change_nothing_but_metadata(texts, program):
    named = _instructions(texts["named"][program])
    bare = _instructions(texts["bare"][program])
    assert len(named) == len(bare)
    if program in OWN_SCOPES:
        # the two builds number a few of this step's instructions apart
        # (%call.17 against %call.20, the same call of the same
        # computation): compared with the numbers off
        number = re.compile(r"(%[A-Za-z_][\w\-]*?)[.\d]*\b")
        named = [number.sub(r"\1", line) for line in named]
        bare = [number.sub(r"\1", line) for line in bare]
    assert named == bare
    mine = OWN_SCOPES.get(program, ())
    for scope in MODEL_SCOPES + UPDATE_SCOPES + mine:
        assert not re.search(token(scope), texts["bare"][program])


def test_every_scope_metric_is_in_the_manifest():
    listed = {m["name"]: m for m in manifest.load(ROOT)["per_layer"]}
    for metric in METRICS:
        assert listed[metric]["source"] == "program_span"
        assert listed[metric]["workloads"] == (
            list(CELLS.values()) if metric in SHARED
            else [CELLS["instella"], CELLS["lfm2"], CELLS["kimi"]]
            if metric in ROUTED
            else [CELLS["lfm2"], CELLS["kimi"]]
            if metric == "train.conv_mix_ms"
            else [CELLS["lfm2"]] if metric in LFM2_ONLY
            else [CELLS["kimi"]] if metric in KIMI_ONLY
            else [CELLS["instella"], CELLS["kimi"]])


@pytest.mark.parametrize("metric", [
    "train.moe_grouped_roofline", "train.moe_experts_ms",
    "train.flash_fwd_roofline", "train.flash_bwd_roofline"])
def test_the_expert_models_kernel_metrics_find_their_instructions(
        scopes, metric):
    """The grouped products' metrics read a scope of the compiled step and,
    by name, the call XLA:TPU makes of ``ragged_dot`` (which keeps no
    ``op_name``); the flash rooflines read kernel names the CPU's text does
    not carry (``tests/test_flash_scopes.py`` holds those)."""
    from chipbench.readers import moe_experts_time

    params = manifest.metric_file(ROOT, ["chipbench"], metric)["params"]
    if metric.startswith("train.moe_"):
        assert _some(scopes["instella"], params["scope"], r"jvp\(")
        assert _some(scopes["instella"], params["scope"], r"transpose\(")
        ops = [{"name": "%ragged-dot-none.5 = bf16[98304,1408] custom-call",
                "scope": "", "dur": 2e-3},
               {"name": "%fusion.7", "scope": "jit(f)/moe/moe_experts/mul",
                "dur": 1e-3},
               {"name": "%fusion.8", "scope": "jit(f)/moe/moe_route/top_k",
                "dur": 4e-3}]
        assert len(moe_experts_time.products(ops, **params)) == 2
        ctx = {"ops": {"d": ops}, "runs": {"d": [(0.0, 1.0), (1.0, 2.0)]}}
        assert moe_experts_time.read(ctx, **params) == pytest.approx(1.5)
        assert moe_experts_time.read(
            {"ops": {"d": ops[2:]}, "runs": ctx["runs"]}, **params) is None
    else:
        assert CELLS["instella"] in next(
            m for m in manifest.load(ROOT)["per_layer"]
            if m["name"] == metric)["workloads"]


def test_the_kda_scopes_cut_the_operator_as_the_metrics_read_it(scopes):
    """``train.kda_scan_ms`` and ``train.kda_scan_roofline`` read
    ``kda_scan``, the chunked delta rule and nothing else: every instruction
    under it lies inside ``kda_operator``, none under the filter's or the
    gates' scope, and its loops over the chunks (forward, recompute and the
    reverse one) keep the name. ``train.kda_proj_ms`` reads the operator
    without the scan and the filter: the projections, the gates and the
    head norm. ``train.conv_mix_roofline`` and ``train.kda_scan_roofline``
    find the scope and the flops module's count."""
    from chipbench import flops_kimi_linear
    from chipbench.readers import scope_roofline

    # (a reduction's scalar body is a computation of its own in the text,
    # named from the scope inwards: no instruction of the step)
    mine = [s for s in scopes["kimi"] if s.startswith("jit(")]
    scan = _some(mine, token("kda_scan"))
    assert scan and not _some(scan, "^", none=(token("kda_operator"),))
    assert not _some(scan, token("conv_mix")) \
        and not _some(scan, token("kda_gates"))
    loops = _some(scan, "/while/body/")
    assert _some(loops, r"jvp\(", none=(r"transpose\(",))
    assert _some(loops, token("layers"), "/rematted_computation/")
    assert _some(loops, r"transpose\(")
    proj = manifest.metric_file(ROOT, ["chipbench"],
                                "train.kda_proj_ms")["params"]
    left = _some(mine, proj["include"], none=(proj["exclude"],))
    assert _some(left, token("kda_gates")) and _some(left, "dot_general")
    assert _some(left, token("layer_norm"))
    cell = manifest.cell(manifest.load(ROOT), CELLS["kimi"], ROOT)
    ctx = dict(_reader_ctx(mine), planes=["/device:TPU:0"],
               flops=flops_kimi_linear, cfg=cell["config"], mix=cell["mix"],
               rows=cell["mix"]["batch"], chips=1,
               peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    for metric in ("train.kda_scan_roofline", "train.conv_mix_roofline"):
        params = manifest.metric_file(ROOT, ["chipbench"], metric)["params"]
        got = scope_roofline.read(ctx, **params)
        assert got is not None and got > 0, metric

"""The Kimi Linear expert model (``apex_tpu/models/kimi_linear.py``, the
chunked delta rule of ``ops/kda.py``, the plain filter of
``ops/short_conv.py``, the flash kernels' value width, the trainer
``examples/kimi_linear``) against the plain reference
``chipbench/references/kimi_linear.py``, on the CPU at a small size: seeded
weights, the published widths scaled down, 8 experts of which 4 are held. The
reference imports nothing of ``apex_tpu``; this file is where the two meet.
"""

import hashlib
import os
import re
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "examples", "kimi_linear"))

from apex_tpu.models._transformer import layer_runs  # noqa: E402
from apex_tpu.ops.short_conv import (  # noqa: E402
    LANES,
    TILE,
    gated_short_conv,
    short_conv,
    short_conv_xla,
)
from chipbench import manifest  # noqa: E402
from chipbench.programs import pretrain_kimi_linear as adapter  # noqa: E402
from chipbench.references import common, kimi_linear as ref  # noqa: E402

TINY = os.path.join(ROOT, "chipbench", "tests")
#: the tiny cell's configuration: the cut's five layers
CFG = manifest.load_json(
    os.path.join(TINY, "configs", "kimi_linear_tiny.json"))
MIX = manifest.load_json(
    os.path.join(TINY, "traffic", "pretrain_kimi_linear_tiny.json"))
#: the published pattern, whole: 27 layers, 7 attend, the last two together
PUBLISHED = manifest.load_json(os.path.join(
    ROOT, "chipbench", "configs", "kimi_linear_48b_a3b.json"))["deployment"]
PATTERNS = {
    "cut": CFG,
    "published": dict(CFG, num_hidden_layers=27, linear_attn_config=dict(
        CFG["linear_attn_config"],
        kda_layers=PUBLISHED["kda_layers_published"],
        full_attn_layers=PUBLISHED["full_attn_layers_published"]))}
#: the published pattern's first nine layers: two whole periods behind the
#: dense layer, six runs (the gradient's test compiles in half the time)
PATTERNS["two_periods"] = dict(
    CFG, num_hidden_layers=9, linear_attn_config=dict(
        CFG["linear_attn_config"],
        kda_layers=[i for i in PUBLISHED["kda_layers_published"] if i <= 9],
        full_attn_layers=[4, 8]))
DOT = common.DOTS["float32"]
TINY_ARGV = ("--hidden 64 --heads 4 --qk-nope-dim 16 --qk-rope-dim 8 "
             "--v-dim 16 --kv-lora-rank 32 --kda-heads 4 --kda-head-dim 16 "
             "--ffn 96 --moe-ffn 32 --experts 8 --experts-held 4 "
             "--first-expert-held 2 --top-k 2 --vocab 512 --seq 128 "
             "--micro-batch 2").split()


def _batch(cfg, seed=0):
    b = ref.make_batch(cfg, MIX, np.random.default_rng(seed), MIX["batch"])
    return jnp.asarray(b["tokens"]), jnp.asarray(b["targets"])


def _rel(a, b):
    """Leaf by leaf, the norm of the difference over the reference's norm
    (0 where both are 0: the selection bias has no gradient)."""
    return jax.tree.map(
        lambda x, y: float(jnp.linalg.norm(x.astype(jnp.float32) - y)
                           / jnp.maximum(jnp.linalg.norm(y), 1e-30)), a, b)


# -- the pattern, as data ---------------------------------------------------

@pytest.mark.parametrize("pattern,runs", [("cut", 4), ("published", 15)])
def test_the_programs_tree_is_the_references_leaf_for_leaf(pattern, runs):
    """The pattern comes from the config's own lists (``kda_layers``,
    ``full_attn_layers``, ``first_k_dense_replace``). The same leaves in two
    arrangements: the program stacks each run of like layers (by operator,
    and dense or routed) under ``layers/<run>``, the reference keeps every
    layer a tree of its own under ``layers/<i>``."""
    cfg = PATTERNS[pattern]
    model = adapter.build(cfg, MIX)[0]
    lin = cfg["linear_attn_config"]
    assert [op for op, _ in model.cfg.layer_kinds] == [
        "kda" if i in lin["kda_layers"] else "full_attention"
        for i in range(1, cfg["num_hidden_layers"] + 1)]
    assert [routed for _, routed in model.cfg.layer_kinds] == [
        i >= cfg["first_k_dense_replace"]
        for i in range(cfg["num_hidden_layers"])]
    mine = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda k: ref.init_weights(cfg, k),
                            jax.random.PRNGKey(0))
    shapes = lambda t: jax.tree.map(lambda a: a.shape, t)
    assert shapes(mine) == shapes(jax.eval_shape(adapter.stacked, theirs))
    assert shapes(jax.eval_shape(adapter.apart, mine)) == shapes(theirs)
    assert sorted(mine["layers"]) == [f"{r:02d}" for r in range(runs)]
    for name, ((operator, routed), _, count) in zip(
            sorted(mine["layers"]), layer_runs(model.cfg.layer_kinds)):
        run = mine["layers"][name]
        assert jax.tree.leaves(run)[0].shape[0] == count
        assert ("kda" in run) == (operator == "kda")
        assert ("attn" in run) == (operator == "full_attention")
        assert ("experts" in run) == ("shared" in run) == routed
        assert ("mlp" in run) != routed


def test_a_pattern_the_model_cannot_read_is_refused():
    from apex_tpu.models import KimiLinearConfig, KimiLinearModel

    with pytest.raises(ValueError, match="both or neither"):
        KimiLinearModel(KimiLinearConfig(kda_layers=(1, 2, 3)))
    with pytest.raises(ValueError, match="both or neither"):
        KimiLinearModel(KimiLinearConfig(full_attn_layers=(4, 5)))
    with pytest.raises(ValueError, match="num_dense_layers"):
        KimiLinearModel(KimiLinearConfig(num_dense_layers=6))


def test_the_seeded_decays_are_drawn_as_the_source_draws_them():
    """``A_log`` in ``log([1, 16])`` a head, ``dt_bias`` the inverse
    softplus of a step in [0.001, 0.1], both sides."""
    from apex_tpu.models import KimiLinearConfig, KimiLinearModel

    w = ref.init_weights(CFG, common.seed_key(5), jnp.float32)
    model = KimiLinearModel(KimiLinearConfig(
        hidden_size=64, num_attention_heads=4, kda_heads=4, kda_head_dim=16,
        vocab_size=512, ffn_hidden_size=96, moe_ffn_hidden_size=32,
        num_experts=8, experts_held=4, top_k=2, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32))
    mine = model.init(jax.random.PRNGKey(5))["layers"]["00"]["kda"]
    for p in (jax.tree.map(lambda a: a[0], w["layers"]["0"]["kda"]),
              jax.tree.map(lambda a: a[0], mine)):
        a, step = np.exp(p["A_log"]), np.asarray(jax.nn.softplus(p["dt_bias"]))
        assert a.shape == (4,) and step.shape == (64,)
        assert np.all((a >= 1.0) & (a <= 16.0)) and a.std() > 0.5
        assert np.all((step >= 0.00099) & (step <= 0.1001))


# -- against the reference --------------------------------------------------

@pytest.mark.parametrize("pattern", ["cut", "two_periods"])
def test_loss_and_every_gradient_equal_the_references_in_float32(pattern):
    cfg = PATTERNS[pattern]
    toks, tgts = _batch(cfg)
    model = adapter.build(cfg, dict(MIX, opt_level="O0"))[0]
    w = ref.init_weights(cfg, common.seed_key(3), jnp.float32)
    loss_r, grad_r = jax.jit(jax.value_and_grad(
        lambda w: ref.loss_numerators(
            cfg, w, {"tokens": toks, "targets": tgts})[0] / toks.size))(w)
    # the program's gradient, in the reference's arrangement
    loss_m, grad_m = jax.jit(lambda w: (lambda l, g: (l, adapter.apart(g)))(
        *jax.value_and_grad(lambda p: model.loss(p, toks, tgts)[0])(
            adapter.stacked(w))))(w)
    # float32 on both sides: the recurrence token by token against the
    # chunked scan, one softmax against the flash kernels' lax path
    assert abs(float(loss_m) - float(loss_r)) <= 1e-6 * float(loss_r)
    # (nine layers sum more roundings than five)
    worst = max(jax.tree.leaves(_rel(grad_m, grad_r)))
    assert worst <= (5e-5 if pattern == "cut" else 2e-4), _rel(grad_m, grad_r)
    # the selection bias is a buffer: no gradient reaches it, either side
    routed = str(cfg["first_k_dense_replace"])
    for g in (grad_m, grad_r):
        assert not np.any(np.asarray(g["layers"][routed]["router"]["bias"]))
    # every other leaf has one, the decays' own parameters among them
    kda = grad_m["layers"]["0"]["kda"]
    assert all(np.any(np.asarray(kda[n])) for n in ("A_log", "dt_bias"))


def test_the_operators_alone_equal_the_references():
    """One KDA operator and one latent attention, outputs in float32."""
    model = adapter.build(CFG, dict(MIX, opt_level="O0"))[0]
    w = ref.init_weights(CFG, common.seed_key(9), jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 128, 64))
    for layer, name in (("1", "kda"), ("3", "attn")):
        p = jax.tree.map(lambda a: a[0], w["layers"][layer][name])
        if name == "kda":
            got, counters = model._kda_operator(p, u)
            want = ref.kda(CFG, DOT, u, p)
            # 2 rows x 4 heads x 2 chunks of 64 tokens
            assert float(counters["kda_chunks"]) == 16.0
            assert float(counters["kda_min_chunk_log_decay"]) < 0.0
        else:
            got = model._attention(p, u)
            want = ref.attention(CFG, DOT, u, p)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=2e-5 * float(
                                       jnp.abs(want).max()))


# -- the share --------------------------------------------------------------

def _share_cfg(first, held):
    return dict(CFG, num_experts=held, deployment={
        "experts_published": 8, "first_expert_held": first})


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed results of all four shares of one routed layer, and the
    shared expert counted once, sum to the uncut reference's feed-forward
    for the whole layer; each rank computes the shared expert alike."""
    whole_cfg = _share_cfg(0, 8)
    w = ref.init_weights(whole_cfg, common.seed_key(11), jnp.float32)
    p = jax.tree.map(lambda a: a[0], w["layers"]["2"])
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 48, CFG["hidden_size"]))
    shared = ref.gated_mlp(DOT, u, p["shared"])
    whole = shared + ref.routed_experts(whole_cfg, DOT, u, p)
    by_ref = by_model = 0.0
    for first in (0, 2, 4, 6):
        cfg = _share_cfg(first, 2)
        cut = dict(p, experts=jax.tree.map(lambda a: a[first:first + 2],
                                           p["experts"]))
        one = ref.routed_experts(cfg, DOT, u, cut)
        by_ref = by_ref + one
        model = adapter.build(cfg, dict(MIX, opt_level="O0"))[0]
        assert (model.experts.first_held, model.experts.held) == (first, 2)
        # the rank's feed-forward: its experts' terms and the shared expert
        mine = model._feed_forward(cut, u)[0]
        by_model = by_model + (mine - shared)
        np.testing.assert_allclose(np.asarray(mine - shared),
                                   np.asarray(one), rtol=0, atol=1e-5 * float(
                                       jnp.abs(whole).max()))
    scale = float(jnp.abs(whole).max())
    # float32 sums in another order
    assert float(jnp.abs(shared + by_ref - whole).max()) <= 1e-5 * scale
    assert float(jnp.abs(shared + by_model - whole).max()) <= 1e-5 * scale
    # and a share alone, or the shared expert counted four times, is not
    # the layer
    assert float(jnp.abs(shared + one - whole).max()) > 1e-2 * scale
    assert float(jnp.abs(4 * shared + by_ref - whole).max()) > 1e-2 * scale


# -- the plain filter -------------------------------------------------------

def _conv_by_lax(x, taps):
    """``silu(conv(x))`` with ``lax.conv_general_dilated``: depthwise, ``L -
    1`` zeros to the left, as torch's ``Conv1d(groups=channels, padding=L -
    1)`` cut to the sequence."""
    n, channels = taps.shape
    return jax.nn.silu(jax.lax.conv_general_dilated(
        x, taps[:, None, :], window_strides=(1,), padding=[(n - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=channels, precision="highest"))


@pytest.mark.parametrize("taps", [2, 4])
def test_short_conv_and_its_gradient(taps):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (taps, 16))
    g = jax.random.normal(jax.random.PRNGKey(2), (2, 40, 16))
    np.testing.assert_allclose(short_conv_xla(x, w), _conv_by_lax(x, w),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(short_conv_xla(x, w), ref.conv_silu(x, w),
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(short_conv_xla(*a) * g), (0, 1))(x, w)
    want = jax.grad(lambda *a: jnp.sum(_conv_by_lax(*a) * g), (0, 1))(x, w)
    for a, r in zip(got, want):
        np.testing.assert_allclose(a, r, rtol=1e-4, atol=1e-4)
    # causal: a token's result does not move with what follows it
    later = x.at[:, 20:].add(1.0)
    np.testing.assert_array_equal(short_conv(later, w)[:, :20],
                                  short_conv(x, w)[:, :20])
    assert short_conv(x.astype(jnp.bfloat16), w).dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="channels"):
        short_conv_xla(x[..., :8], w)
    # a shape the kernels do not take: asked for by name it is refused
    with pytest.raises(ValueError, match="outside the kernels' envelope"):
        short_conv(x, w, impl="pallas")


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 2 ** -6)])
def test_the_conv_kernels_take_the_plain_filter(dtype, tol, taps):
    """The same two Pallas kernels (interpret mode here), the gates off and
    the tap count an argument, against the ``jax.numpy`` form, forward and
    both gradients, over three tiles a sequence and two blocks of channels.
    In bf16 the kernels' result is rounded once before SiLU, the plain
    form's not at all: a rounding apart."""
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(keys[0], (2, 3 * TILE, 2 * LANES), dtype)
    w = jax.random.normal(keys[1], (taps, 2 * LANES), dtype)
    g = jax.random.normal(keys[2], (2, 3 * TILE, 2 * LANES), dtype)
    kernel = lambda *a: short_conv(*a, impl="pallas")
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    np.testing.assert_allclose(f32(kernel(x, w)), f32(short_conv_xla(x, w)),
                               rtol=tol, atol=tol)
    got = jax.vjp(kernel, x, w)[1](g)
    want = jax.vjp(short_conv_xla, x, w)[1](g)
    for a, r in zip(got, want):
        assert a.dtype == r.dtype
        np.testing.assert_allclose(f32(a), f32(r), rtol=tol,
                                   atol=tol * float(jnp.abs(r).max()))


def test_the_gated_filter_traces_as_before(monkeypatch):
    """The LFM2 call of ``gated_short_conv``, forward and gradient, as the
    kernels' tap count and gates became arguments: the jaxpr's text, file
    positions cut out, is what the tree before this change gave."""
    from apex_tpu.ops import layer_norm

    monkeypatch.setattr(layer_norm, "_on_tpu", lambda: True)
    bcu = jax.ShapeDtypeStruct((2, 256, 3 * 512), jnp.bfloat16)
    taps = jax.ShapeDtypeStruct((3, 512), jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(
        lambda b, t: jnp.sum(gated_short_conv(b, t).astype(jnp.float32)),
        (0, 1)))(bcu, taps))
    text = re.sub(r" at /[^\s\]]*:\d+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "653d4484074d92f0c7d37a3be4a48a7986cc0c2ce5af4e51f9a7e27bc045f838")


# -- the trainer ------------------------------------------------------------

def _scans(jaxpr):
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "scan"
        n += sum(_scans(sub) for sub in jax.core.jaxprs_in_params(eqn.params)
                 if eqn.primitive.name != "scan")
    return n


def test_one_scan_a_run_and_one_compile_for_the_stack():
    """The stack's forward pass is one scan for each run of like layers, 4
    at the cut's pattern and 15 at the published one, whatever the depth of
    a run; and the trainer's step compiles once."""
    for pattern, runs in (("cut", 4), ("published", 15)):
        model = adapter.build(PATTERNS[pattern], MIX)[0]
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        h = jax.ShapeDtypeStruct((2, 128, 64), jnp.float32)
        jaxpr = jax.make_jaxpr(lambda p, h: model.run_stacks(p, h)[0])(
            params, h)
        assert _scans(jaxpr.jaxpr) == runs
    import pretrain_kimi_linear

    run = pretrain_kimi_linear.main([*TINY_ARGV, "--steps", "3"])
    assert run["train_step"]._cache_size() == 1
    assert np.all(np.isfinite(run["losses"])) and not any(run["found_inf"])
    # the counters: the experts' one entry an expert layer, KDA's one entry
    # a KDA layer
    assert set(run["moe"]) == {
        "assignments", "max_load_over_mean", "overflow", "rows_moved",
        "expert_rows", "kda_min_chunk_log_decay", "kda_chunks",
        "kda_kernel_chunks", "kda_transpose_chunks"}
    assert all(len(v) == 4 for v in run["moe"].values())
    assert sum(run["moe"]["overflow"]) == 0
    # 2 rows x 4 heads x 2 chunks of 64 tokens a layer; off the chip 'auto'
    # computes their chunk-local forward and its transpose in jax.numpy, not
    # in the kernels
    assert run["moe"]["kda_chunks"] == [16.0] * 4
    assert run["moe"]["kda_kernel_chunks"] == [0.0] * 4
    assert run["moe"]["kda_transpose_chunks"] == [0.0] * 4
    assert all(-1e4 < x < 0 for x in run["moe"]["kda_min_chunk_log_decay"])



def test_the_kernel_counts_every_chunk_it_computes(monkeypatch):
    """With the chunk-local kernels forced (interpret mode here, as the chip
    would take them), ``kda_kernel_chunks`` and ``kda_transpose_chunks``
    are ``kda_chunks`` in every KDA layer, and the trainer's losses are the
    ``jax.numpy`` form's to bf16's rounding (the second step's after a
    gradient through the transpose's kernel)."""
    import pretrain_kimi_linear
    from apex_tpu.ops import kda as kda_module

    plain = pretrain_kimi_linear.main([*TINY_ARGV, "--steps", "2"])
    monkeypatch.setattr(kda_module, "_resolve_impl", lambda _: "pallas")
    run = pretrain_kimi_linear.main([*TINY_ARGV, "--steps", "2"])
    assert run["moe"]["kda_kernel_chunks"] == run["moe"]["kda_chunks"] \
        == run["moe"]["kda_transpose_chunks"] == [16.0] * 4
    np.testing.assert_allclose(run["losses"], plain["losses"], rtol=1e-2)


def test_state_is_donated_whole():
    import pretrain_kimi_linear

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        run = pretrain_kimi_linear.main([*TINY_ARGV, "--steps", "2"])
        lowered = run["train_step"].lower(
            run["params"], run["opt_state"], *run["next_batch"]())
        compiled = lowered.compile()
    unusable = [str(w.message) for w in caught
                if "donated buffers were not usable" in str(w.message)]
    assert not unusable, unusable
    (params, opt_state, *batch), _ = lowered.args_info
    flags = lambda tree: [a.donated for a in jax.tree.leaves(tree)]
    state = flags((params, opt_state))
    assert all(state) and not any(flags(batch))
    header = compiled.as_text().split("\n", 1)[0]   # input_output_alias
    assert header.count("-alias)") == len(state), header

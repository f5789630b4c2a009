"""The LFM2 expert model (``apex_tpu/models/lfm2.py``, the gated short
convolution of ``ops/short_conv.py``, the stack whose pattern is data of
``models/_transformer.py``, the trainer ``examples/lfm2``) against the plain
reference ``chipbench/references/lfm2.py``, on the CPU at a small size:
seeded weights, the published widths scaled down, 8 experts of which 4 are
held. The reference imports nothing of ``apex_tpu``; this file is where the
two meet.
"""

import os
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "examples", "lfm2"))

from apex_tpu.models._transformer import layer_runs  # noqa: E402
from apex_tpu.ops.short_conv import (  # noqa: E402
    LANES,
    TILE,
    gated_short_conv,
    gated_short_conv_xla,
)
from chipbench import manifest  # noqa: E402
from chipbench.programs import pretrain_lfm2 as adapter  # noqa: E402
from chipbench.references import common, lfm2 as ref  # noqa: E402

TINY = os.path.join(ROOT, "chipbench", "tests")
#: the tiny cell's configuration: the cut's five layers
CFG = manifest.load_json(os.path.join(TINY, "configs", "lfm2_tiny.json"))
MIX = manifest.load_json(
    os.path.join(TINY, "traffic", "pretrain_lfm2_tiny.json"))
#: the published pattern, whole: 24 entries, 6 attend, the tail not periodic
PUBLISHED = manifest.load_json(os.path.join(
    ROOT, "chipbench", "configs", "lfm2_8b_a1b.json"))["deployment"]
PATTERNS = {
    "cut": CFG,
    "published": dict(CFG, layer_types=PUBLISHED["layer_types_published"],
                      num_hidden_layers=24, num_dense_layers=2)}
DOT = common.DOTS["float32"]
TINY_ARGV = ("--hidden 64 --heads 8 --kv-heads 2 --ffn 96 --moe-ffn 32 "
             "--experts 8 --experts-held 4 --first-expert-held 2 --top-k 2 "
             "--vocab 512 --seq 64 --micro-batch 2").split()


def _batch(cfg, seed=0):
    b = ref.make_batch(cfg, MIX, np.random.default_rng(seed), MIX["batch"])
    return jnp.asarray(b["tokens"]), jnp.asarray(b["targets"])


def _rel(a, b):
    """Leaf by leaf, the norm of the difference over the reference's norm
    (0 where both are 0: the selection bias has no gradient)."""
    return jax.tree.map(
        lambda x, y: float(jnp.linalg.norm(x.astype(jnp.float32) - y)
                           / jnp.maximum(jnp.linalg.norm(y), 1e-30)), a, b)


def test_layer_runs():
    assert layer_runs("aabaaab") == [("a", 0, 2), ("b", 2, 1), ("a", 3, 3),
                                     ("b", 6, 1)]
    assert layer_runs([]) == []


@pytest.mark.parametrize("pattern,runs", [("cut", 3), ("published", 13)])
def test_the_programs_tree_is_the_references_leaf_for_leaf(pattern, runs):
    """The same leaves in two arrangements: the program stacks each run of
    like layers (by operator, and dense or routed) under ``layers/<run>``
    with the layer axis first, the reference keeps every layer a tree of
    its own under ``layers/<i>``; the adapter's ``stacked`` and ``apart``
    turn one into the other."""
    cfg = PATTERNS[pattern]
    model = adapter.build(cfg, MIX)[0]
    mine = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda k: ref.init_weights(cfg, k),
                            jax.random.PRNGKey(0))
    shapes = lambda t: jax.tree.map(lambda a: a.shape, t)
    assert shapes(mine) == shapes(jax.eval_shape(adapter.stacked, theirs))
    assert shapes(jax.eval_shape(adapter.apart, mine)) == shapes(theirs)
    assert sorted(mine["layers"]) == [f"{r:02d}" for r in range(runs)]
    assert len(theirs["layers"]) == len(cfg["layer_types"])
    # run by run: the layers the pattern says, stacked
    kinds = layer_runs(model.cfg.layer_kinds)
    for name, ((operator, routed), _, count) in zip(sorted(mine["layers"]),
                                                    kinds):
        run = mine["layers"][name]
        assert jax.tree.leaves(run)[0].shape[0] == count
        assert ("conv" in run) == (operator == "conv")
        assert ("attn" in run) == (operator == "full_attention")
        assert ("experts" in run) == routed and ("mlp" in run) != routed
    w = ref.init_weights(cfg, jax.random.PRNGKey(1))
    back = adapter.apart(adapter.stacked(w))
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree.leaves(w), jax.tree.leaves(back)))


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
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
    # float32 on both sides, the same equations in another order of
    # operations (and the weights' sum + 1e-6 against + 1e-20: half a
    # millionth of a weight)
    assert abs(float(loss_m) - float(loss_r)) <= 1e-6 * float(loss_r)
    worst = max(jax.tree.leaves(_rel(grad_m, grad_r)))
    assert worst <= 2e-5, _rel(grad_m, grad_r)
    # the selection bias is a buffer: no gradient reaches it, either side
    routed = str(cfg["num_dense_layers"])
    for g in (grad_m, grad_r):
        assert not np.any(np.asarray(g["layers"][routed]["router"]["bias"]))


# -- the share --------------------------------------------------------------

def _share_cfg(first, held):
    return dict(CFG, num_experts=held, deployment={
        "experts_published": 8, "first_expert_held": first})


def test_the_shares_add_up_to_the_uncut_layer():
    """The feed-forward results of all four shares of one routed layer (no
    shared expert: nothing is counted twice) sum to the uncut reference's
    result for the whole layer."""
    whole_cfg = _share_cfg(0, 8)
    w = ref.init_weights(whole_cfg, common.seed_key(11), jnp.float32)
    p = jax.tree.map(lambda a: a[0], w["layers"]["2"])
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 48, CFG["hidden_size"]))
    whole = ref.routed_experts(whole_cfg, DOT, u, p)
    by_ref = by_model = 0.0
    for first in (0, 2, 4, 6):
        cfg = _share_cfg(first, 2)
        cut = dict(p, experts=jax.tree.map(lambda a: a[first:first + 2],
                                           p["experts"]))
        by_ref = by_ref + ref.routed_experts(cfg, DOT, u, cut)
        model = adapter.build(cfg, dict(MIX, opt_level="O0"))[0]
        assert (model.experts.first_held, model.experts.held) == (first, 2)
        by_model = by_model + model._feed_forward(cut, u)[0]
        one = ref.routed_experts(cfg, DOT, u, cut)
    scale = float(jnp.abs(whole).max())
    # float32 sums in another order
    assert float(jnp.abs(by_ref - whole).max()) <= 1e-5 * scale
    assert float(jnp.abs(by_model - whole).max()) <= 1e-5 * scale
    # and a share alone is not the layer
    assert float(jnp.abs(one - whole).max()) > 1e-2 * scale


# -- the gated short convolution --------------------------------------------

def _conv_by_lax(bcu, taps):
    """``C * conv(B * u)`` with ``lax.conv_general_dilated``: depthwise,
    ``L - 1`` zeros to the left, as torch's ``Conv1d(groups=channels,
    padding=L - 1)`` cut to the sequence."""
    b, c, u = jnp.split(bcu, 3, axis=-1)
    n, channels = taps.shape
    mixed = jax.lax.conv_general_dilated(
        b * u, taps[:, None, :], window_strides=(1,),
        padding=[(n - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=channels, precision="highest")
    return c * mixed


@pytest.mark.parametrize("taps", [3, 4])
def test_gated_short_conv_and_its_gradient(taps):
    bcu = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3 * 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (taps, 16))
    g = jax.random.normal(jax.random.PRNGKey(2), (2, 40, 16))
    np.testing.assert_allclose(gated_short_conv_xla(bcu, w),
                               _conv_by_lax(bcu, w), rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(gated_short_conv_xla(*a) * g), (0, 1))(
        bcu, w)
    want = jax.grad(lambda *a: jnp.sum(_conv_by_lax(*a) * g), (0, 1))(bcu, w)
    for a, r in zip(got, want):
        np.testing.assert_allclose(a, r, rtol=1e-4, atol=1e-4)
    # causal: a token's result does not move with what follows it
    later = bcu.at[:, 20:].add(1.0)
    np.testing.assert_array_equal(gated_short_conv(later, w)[:, :20],
                                  gated_short_conv(bcu, w)[:, :20])
    assert gated_short_conv(bcu.astype(jnp.bfloat16), w).dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="3 x"):
        gated_short_conv_xla(bcu[..., :40], w)
    # a shape the kernels do not take: asked for by name it is refused
    with pytest.raises(ValueError, match="outside the kernels' envelope"):
        gated_short_conv(bcu, w, impl="pallas")


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 2 ** -7)])
def test_the_conv_kernels_equal_the_plain_form(dtype, tol):
    """The Pallas kernels (interpret mode here) against the ``jax.numpy``
    form, forward and both gradients, over three tiles a sequence (so a
    tile has a neighbour on either side, and the ends have none) and two
    blocks of channels; float32 inside either way, so bf16 results agree
    to a rounding of the last bit."""
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    bcu = jax.random.normal(keys[0], (2, 3 * TILE, 3 * 2 * LANES), dtype)
    w = jax.random.normal(keys[1], (3, 2 * LANES), dtype)
    g = jax.random.normal(keys[2], (2, 3 * TILE, 2 * LANES), dtype)
    kernel = lambda *a: gated_short_conv(*a, impl="pallas")
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    np.testing.assert_allclose(f32(kernel(bcu, w)),
                               f32(gated_short_conv_xla(bcu, w)),
                               rtol=tol, atol=tol)
    got = jax.vjp(kernel, bcu, w)[1](g)
    want = jax.vjp(gated_short_conv_xla, bcu, w)[1](g)
    for a, r in zip(got, want):
        assert a.dtype == r.dtype
        np.testing.assert_allclose(f32(a), f32(r), rtol=tol,
                                   atol=tol * float(jnp.abs(r).max()))


# -- the trainer ------------------------------------------------------------

def _scans(jaxpr):
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "scan"
        n += sum(_scans(sub) for sub in jax.core.jaxprs_in_params(eqn.params)
                 if eqn.primitive.name != "scan")
    return n


def test_one_scan_a_run_and_one_compile_for_the_stack():
    """The stack's forward pass is one scan for each run of like layers,
    3 at the cut's pattern and 13 at the published one, whatever the depth
    of a run; and the trainer's step compiles once."""
    for pattern, runs in (("cut", 3), ("published", 13)):
        model = adapter.build(PATTERNS[pattern], MIX)[0]
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        h = jax.ShapeDtypeStruct((2, 64, 64), jnp.float32)
        jaxpr = jax.make_jaxpr(lambda p, h: model.run_stacks(p, h)[0])(
            params, h)
        assert _scans(jaxpr.jaxpr) == runs
    import pretrain_lfm2

    run = pretrain_lfm2.main([*TINY_ARGV, "--steps", "3"])
    assert run["train_step"]._cache_size() == 1
    assert np.all(np.isfinite(run["losses"])) and not any(run["found_inf"])
    # the counters, one entry an expert layer
    assert set(run["moe"]) == {"assignments", "max_load_over_mean",
                               "overflow", "rows_moved", "expert_rows"}
    assert all(len(v) == 4 for v in run["moe"].values())
    assert sum(run["moe"]["overflow"]) == 0


def test_state_is_donated_whole():
    import pretrain_lfm2

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        run = pretrain_lfm2.main([*TINY_ARGV, "--steps", "2"])
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


def test_a_pattern_the_model_cannot_read_is_refused():
    from apex_tpu.models import Lfm2Config, Lfm2Model

    with pytest.raises(ValueError, match="layer_types"):
        Lfm2Model(Lfm2Config(layer_types=("conv", "window")))
    with pytest.raises(ValueError, match="num_kv_heads"):
        Lfm2Model(Lfm2Config(num_kv_heads=5))
    with pytest.raises(ValueError, match="num_dense_layers"):
        Lfm2Model(Lfm2Config(num_dense_layers=6))

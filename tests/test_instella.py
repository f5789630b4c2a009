"""The expert model (``apex_tpu/models/instella.py``, the dropless experts
of ``transformer/moe.py``, the trainer ``examples/instella``) against the
plain reference ``chipbench/references/instella.py``, on the CPU at a small
size: seeded weights, the published widths scaled down, 16 experts of which
4 are held. The reference imports nothing of ``apex_tpu``; this file is
where the two meet.
"""

import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from apex_tpu import amp  # noqa: E402
from apex_tpu.transformer.moe import DroplessExperts  # noqa: E402
from chipbench import compare, manifest  # noqa: E402
from chipbench.programs import pretrain_instella as adapter  # noqa: E402
from chipbench.references import common, instella as ref  # noqa: E402
from chipbench.references import train as ref_train  # noqa: E402

TINY = os.path.join(ROOT, "chipbench", "tests")
#: the tiny cell's configuration, with 4 of the 16 experts held (ids 4 to 7)
CFG = dict(manifest.load_json(
    os.path.join(TINY, "configs", "instella_tiny.json")), n_routed_experts=4)
MIX = manifest.load_json(
    os.path.join(TINY, "traffic", "pretrain_instella_tiny.json"))
DOT = common.DOTS["float32"]


def _cfg(**changes):
    return dict(CFG, **changes)


def _batch(seed=0):
    b = ref.make_batch(CFG, MIX, np.random.default_rng(seed), MIX["batch"])
    return jnp.asarray(b["tokens"]), jnp.asarray(b["targets"])


def _ref_loss(cfg, w, toks, tgts, precision="float32"):
    block = {"tokens": toks, "targets": tgts}
    return ref.loss_numerators(cfg, w, block, precision)[0] / toks.size


def _rel(a, b):
    """Leaf by leaf, the norm of the difference over the reference's norm
    (0 where both are 0: the selection bias has no gradient)."""
    return jax.tree.map(
        lambda x, y: float(jnp.linalg.norm(x.astype(jnp.float32) - y)
                           / jnp.maximum(jnp.linalg.norm(y), 1e-30)), a, b)


def test_the_programs_tree_is_the_references_leaf_for_leaf():
    """The same leaves in two arrangements: the program stacks its expert
    layers under ``layers`` with the layer axis first, the reference keeps
    each a tree of its own under ``layers/<i>``; the adapter's ``stacked``
    and ``apart`` turn one into the other."""
    model = adapter.build(CFG, MIX)[0]
    mine = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda k: ref.init_weights(CFG, k),
                            jax.random.PRNGKey(0))
    shapes = lambda t: jax.tree.map(lambda a: a.shape, t)
    assert shapes(mine) == shapes(jax.eval_shape(adapter.stacked, theirs))
    assert shapes(jax.eval_shape(adapter.apart, mine)) == shapes(theirs)
    assert mine["layers"]["experts"]["gate"].shape[:2] == (2, 4)
    assert sorted(theirs["layers"]) == ["0", "1"]
    w = ref.init_weights(CFG, jax.random.PRNGKey(1))
    back = adapter.apart(adapter.stacked(w))
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree.leaves(w), jax.tree.leaves(back)))


@pytest.fixture(scope="module")
def float32_runs():
    """Loss and gradient of the program and of the reference in float32,
    with ``farskip`` on and off, from the same seeded weights and batch."""
    toks, tgts = _batch()
    out = {}
    for far in (True, False):
        cfg = _cfg(farskip=far)
        model = adapter.build(cfg, dict(MIX, opt_level="O0"))[0]
        w = ref.init_weights(cfg, common.seed_key(3), jnp.float32)
        out[far] = {
            "ref": jax.jit(jax.value_and_grad(
                lambda w: _ref_loss(cfg, w, toks, tgts)))(w),
            # the program's gradient, in the reference's arrangement
            "model": jax.jit(lambda w: (lambda l, g: (l, adapter.apart(g)))(
                *jax.value_and_grad(lambda p: model.loss(p, toks, tgts)[0])(
                    adapter.stacked(w))))(w)}
    return out


@pytest.mark.parametrize("farskip", [True, False])
def test_loss_and_every_gradient_equal_the_references_in_float32(
        float32_runs, farskip):
    (loss_r, grad_r), (loss_m, grad_m) = (float32_runs[farskip]["ref"],
                                          float32_runs[farskip]["model"])
    # float32 on both sides, the same equations in another order of
    # operations: 1e-6 is ten roundings of a sum of a few thousand terms
    assert abs(float(loss_m) - float(loss_r)) <= 1e-6 * float(loss_r)
    # a gradient passes through some 20 products on its way down; each
    # leaf read 1e-6 or under, and a wrong equation reads 1e-2 and over
    worst = max(jax.tree.leaves(_rel(grad_m, grad_r)))
    assert worst <= 2e-5, _rel(grad_m, grad_r)
    # the selection bias is a buffer: no gradient reaches it, either side
    for g in (grad_m, grad_r):
        assert not np.any(np.asarray(g["layers"]["0"]["router"]["bias"]))


def test_farskip_on_and_off_are_different_models(float32_runs):
    on, off = float32_runs[True], float32_runs[False]
    # random weights of 0.02 leave every sub-block's output small beside
    # the stream, so the two losses lie 1.8e-5 apart (relative), where each
    # agrees with its own reference to 1e-6; the gradients tell them apart
    for who in ("ref", "model"):
        assert abs(float(on[who][0]) - float(off[who][0])) \
            > 1e-5 * float(off[who][0])
    apart = _rel(on["model"][1], off["model"][1])
    assert apart["layers"]["1"]["shared"]["up"]["kernel"] > 1e-2, apart


def _o2_readings(precision=None):
    """The program under O2 (or the reference in ``precision``) against
    the float32 reference, from weights drawn in bf16: the gradient's norm
    gap and sample gap over the leaves routing does not decide, as the
    cell's ``part_groups`` reads them, and over all."""
    toks, tgts = _batch(1)
    key = common.seed_key(5)
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32),
                       ref.init_weights(CFG, key, jnp.bfloat16))
    grad_r = jax.jit(jax.grad(
        lambda w: _ref_loss(CFG, w, toks, tgts)))(w32)
    if precision is None:
        model, policy = adapter.build(CFG, MIX)[:2]
        params = jax.tree.map(
            lambda a, t: a.astype(t.dtype), adapter.stacked(w32),
            jax.eval_shape(
                lambda k: amp.cast_params(model.init(k), policy), key))
        grad_p = jax.jit(lambda p: adapter.apart(jax.grad(
            lambda p: model.loss(p, toks, tgts)[0])(p)))(params)
    else:
        grad_p = jax.jit(jax.grad(
            lambda w: _ref_loss(CFG, w, toks, tgts, precision)))(w32)
    fused = functools.partial(ref.fused_parts, CFG)
    skey = ref_train.sample_key(5)
    names, gaps = compare.sample_gaps(
        jax.device_get(ref_train.leaf_samples(grad_p, fused, skey, 512)),
        jax.device_get(ref_train.leaf_samples(grad_r, fused, skey, 512)))
    group = manifest.load_json(os.path.join(
        TINY, "limits", "instella_tiny.pretrain_instella_tiny.json"))[
            "part_groups"]["dense_parts"]
    inside = np.asarray([bool(re.search(group, n)) for n in names])
    return {"dense_parts": float(gaps[inside].max()),
            "all": float(gaps.max())}


def test_o2_is_within_bf16s_band_and_a_lower_precision_is_not():
    got = _o2_readings()
    low = _o2_readings("int8")
    # the leaves routing does not decide: bf16 reads 0.039 here (512
    # elements a leaf), int8 with a scale per row 0.33 and fp8 0.090: a
    # float16's mantissa would pass, as it should, and nothing coarser
    assert got["dense_parts"] <= 0.06, got
    assert low["dense_parts"] > 0.06, low
    # the routed experts and the router: a choice that flips under bf16
    # moves a token's whole gradient from one expert to another; 0.17 here
    # against int8's 0.33
    assert got["all"] <= 0.25 < low["all"], (got, low)


# -- the share --------------------------------------------------------------

def _layer_weights(key, experts=16):
    cfg = _cfg(n_routed_experts=experts,
               deployment={"experts_published": 16, "first_expert_held": 0})
    w = ref.init_weights(cfg, key, jnp.float32)
    return jax.tree.map(lambda a: a[0], w["layers"]["0"])


def _share(p, first, held=4):
    cut = dict(p)
    cut["experts"] = jax.tree.map(lambda a: a[first:first + held],
                                  p["experts"])
    cfg = _cfg(n_routed_experts=held,
               deployment={"experts_published": 16,
                           "first_expert_held": first})
    return cfg, cut


def test_the_shares_add_up_to_the_uncut_layer():
    """The feed-forward results of all four shares of one layer, the
    shared experts (which every chip computes alike) counted once, sum to
    the uncut reference's result for the whole layer."""
    p = _layer_weights(common.seed_key(11))
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 48, CFG["hidden_size"]))
    uncut = _cfg(n_routed_experts=16,
                 deployment={"experts_published": 16, "first_expert_held": 0})
    whole = ref.feed_forward(uncut, DOT, u, p)
    shared = ref.gated_mlp(DOT, u, p["shared"])
    by_ref, by_model = shared, shared
    for first in (0, 4, 8, 12):
        cfg, cut = _share(p, first)
        by_ref = by_ref + ref.feed_forward(cfg, DOT, u, cut) - shared
        model = adapter.build(cfg, dict(MIX, opt_level="O0"))[0]
        assert model.experts.first_held == first
        by_model = by_model + model._feed_forward(cut, u)[0] - shared
    scale = float(jnp.abs(whole).max())
    # float32 sums in another order
    assert float(jnp.abs(by_ref - whole).max()) <= 1e-5 * scale
    assert float(jnp.abs(by_model - whole).max()) <= 1e-5 * scale
    # and a share alone is not the layer
    assert float(jnp.abs(ref.feed_forward(*_share(p, 0)[:1], DOT, u,
                                          _share(p, 0)[1]) - whole).max()) \
        > 1e-2 * scale


def _forced(p, to, held_first=4):
    """Router weights that send every token to the held experts ``to``:
    scores all 0.5, the selection bias decides."""
    bias = jnp.zeros((16,)).at[jnp.asarray(to) + held_first].set(1.0)
    return dict(p, router={"kernel": jnp.zeros_like(p["router"]["kernel"]),
                           "bias": bias})


@pytest.mark.parametrize("to", [0, 3])
def test_dropless_under_imbalance(to):
    """Every token sent to ONE held expert, the first or the last: 16 times
    its even share. No assignment is lost and the result is the
    reference's."""
    p = _layer_weights(common.seed_key(12))
    cfg, cut = _share(_forced(p, [to]), 4)
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 40, CFG["hidden_size"]))
    layer = DroplessExperts(
        CFG["hidden_size"], CFG["moe_intermediate_size"], 16, 3, held=4,
        first_held=4, routed_scaling_factor=2.5)
    out, stats = layer.apply(cut, u)
    want = ref.routed_experts(cfg, DOT, u, cut)
    assert float(stats["assignments"]) == 80.0
    assert float(stats["overflow"]) == 0.0
    assert float(stats["max_load_over_mean"]) == 4.0
    assert float(jnp.abs(want).max()) > 0
    assert float(jnp.abs(out - want).max()) <= 1e-5 * float(
        jnp.abs(want).max())


def test_a_buffer_too_small_counts_what_it_lost_and_the_step_is_skipped():
    """Three of every token's choices to held experts, 4 of 32: eight times
    the even share, twice what the buffer of four times the even share
    holds. The layer counts the overflow and the trainer's step leaves its
    weights and moments as they were, as after an overflowed gradient:
    never a silent loss."""
    cfg = _cfg(deployment={"experts_published": 32, "first_expert_held": 4})
    model, policy, mp_opt, step = adapter.build(cfg, MIX)
    params = amp.cast_params(model.init(jax.random.PRNGKey(0)), policy)
    router = params["layers"]["router"]
    bias = jnp.zeros((32,)).at[jnp.asarray([4, 5, 6])].set(1.0)
    params["layers"]["router"] = {
        "kernel": jnp.zeros_like(router["kernel"]),
        "bias": jnp.broadcast_to(bias, router["bias"].shape).astype(
            router["bias"].dtype)}
    before = jax.tree.map(np.asarray, params)
    toks, tgts = _batch(2)
    # the float32 masters of the norms would be the params' own buffers,
    # and the step donates both trees: give the state buffers of its own
    opt_state = mp_opt.init(jax.tree.map(jnp.copy, params))
    scale = np.asarray(opt_state.scaler.loss_scale)
    new, state, loss, metrics = step(params, opt_state, toks, tgts)
    n = toks.size
    assert list(np.asarray(metrics["moe"]["assignments"])) == [3 * n, 3 * n]
    assert list(np.asarray(metrics["moe"]["overflow"])) == [1.5 * n] * 2
    assert bool(metrics["found_inf"])
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(new)):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert not np.any(np.asarray(
        state.inner.exp_avg["layers"]["shared"]["up"]["kernel"]))
    # a full buffer is no overflowed gradient: the loss scale stays, where
    # a gradient that is not finite halves it
    assert float(metrics["loss_scale"]) == float(scale)
    assert float(state.scaler.loss_scale) == float(scale)


def test_a_gradient_that_is_not_finite_still_halves_the_loss_scale():
    """The other way a step is skipped, untouched by the routing
    overflow's: the scaler answers an overflowed gradient as it does in
    every trainer."""
    model, policy, mp_opt, step = adapter.build(CFG, MIX)
    params = amp.cast_params(model.init(jax.random.PRNGKey(0)), policy)
    params["norm_f"]["scale"] = params["norm_f"]["scale"] * jnp.inf
    opt_state = mp_opt.init(jax.tree.map(jnp.copy, params))
    scale = float(opt_state.scaler.loss_scale)
    _, state, _, metrics = step(params, opt_state, *_batch(2))
    assert bool(metrics["found_inf"])
    assert float(np.sum(metrics["moe"]["overflow"])) == 0.0
    assert float(metrics["loss_scale"]) == scale / 2
    assert float(state.scaler.loss_scale) == scale / 2


# -- YaRN -------------------------------------------------------------------

#: theta^(-i/16) (1 - r_i + r_i / 40) with theta = 8e6 and the ramp r = 0
#: up to pair 3, then 1/4, 1/2, 3/4, and 1 from pair 7 on: 32 turns over the
#: original 4096 positions fall at pair 3.03 and one turn at pair 6.52
YARN_32 = [1.0, 0.3703027, 0.1371241, 0.05077742, 0.01421978, 0.003568439,
           0.0006929306, 2.386922e-05, 8.838835e-06, 3.273044e-06,
           1.212017e-06, 4.488132e-07, 1.661967e-07, 6.15431e-08,
           2.278958e-08, 8.439042e-09]


@pytest.mark.parametrize("who", ["reference", "program"])
def test_yarn_frequencies_and_the_softmax_scale(who):
    real = manifest.load_json(os.path.join(
        ROOT, "chipbench", "configs", "instella_moe_16b_a3b.json"))
    if who == "reference":
        freq, scale = ref.yarn_inv_freq(real), ref.softmax_scale(real)
        rotary = ref.rotary_scale(real)
    else:
        from apex_tpu.models import InstellaConfig, InstellaModel

        model = InstellaModel(InstellaConfig())
        freq, scale = model._inv_freq, model.softmax_scale
        rotary = model._rotary_scale
    np.testing.assert_allclose(freq, YARN_32, rtol=2e-6)
    # m = 0.1 ln 40 + 1 = 1.3688879; 128^-0.5 m^2
    assert abs(scale - 0.16562688) < 1e-7
    assert rotary == 1.0

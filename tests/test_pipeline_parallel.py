"""Pipeline-parallel schedule tests.

Reference pattern: tests/L0/run_transformer/run_pipeline_parallel_test.py —
sweep {no_pipelining, 1F1B, interleaved} and assert loss parity; the SPMD
pipeline must match the serial model bit-for-tolerance (forward AND grads)
because it computes the identical function.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.parallel import mesh as mesh_lib
from apex_tpu.parallel.distributed import allreduce_gradients_by_spec
from apex_tpu.transformer import tensor_parallel as tp
from apex_tpu.transformer.microbatches import (
    ConstantNumMicroBatches,
    RampupBatchsizeNumMicroBatches,
    build_num_microbatches_calculator,
)
from apex_tpu.transformer.pipeline_parallel import (
    forward_backward_no_pipelining,
    pipeline_specs,
    pipelined_loss_fn,
)
from apex_tpu.transformer.pipeline_parallel.schedules import (
    deinterleave_stack,
    interleave_stack,
)

TINY = dict(
    vocab_size=64,
    hidden_size=32,
    num_layers=4,
    num_attention_heads=4,
    max_seq_len=16,
    hidden_dropout=0.0,
    compute_dtype=jnp.float32,
    remat=False,
)


def _setup(pp, tp_size=1, **cfg_overrides):
    mesh = mesh_lib.make_virtual_mesh(
        pp * tp_size, tensor_model_parallel_size=tp_size,
        pipeline_model_parallel_size=pp,
    )
    axis = "model" if tp_size > 1 else None
    cfg = dict(TINY, **cfg_overrides)
    serial = GPTModel(GPTConfig(axis=None, **cfg))
    par = GPTModel(GPTConfig(axis=axis, **cfg))
    params = serial.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
    tgt = jnp.roll(toks, -1, axis=-1)
    return mesh, serial, par, params, toks, tgt


def _pipeline_value_and_grad(par, mesh, params, toks, tgt, M, vpp=1):
    specs = par.specs()
    layer_specs = pipeline_specs(specs["layers"])
    rest_specs = {k: v for k, v in specs.items() if k != "layers"}
    layers = params["layers"]
    if vpp > 1:
        layers = interleave_stack(layers, mesh.shape["pipe"], vpp)
    rest = {k: v for k, v in params.items() if k != "layers"}
    sharded_layers = tp.shard_params(layers, layer_specs, mesh)
    sharded_rest = tp.shard_params(rest, rest_specs, mesh)

    loss_fn = pipelined_loss_fn(
        embed=par.embed,
        run_layers=lambda lp, h: par.run_layers(lp, h),
        head_loss=lambda p, h, t: par.head(p, h, t),
        num_microbatches=M,
        virtual_pipeline_size=vpp,
    )

    def step(rest, layers, toks, tgt):
        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            rest, layers, toks, tgt
        )
        rest_g, layer_g = grads
        rest_g = allreduce_gradients_by_spec(rest_g, rest_specs)
        return loss, rest_g, layer_g

    fn = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(rest_specs, layer_specs, P(), P()),
        out_specs=(P(), rest_specs, layer_specs),
        check_vma=False,
    ))
    loss, rest_g, layer_g = fn(sharded_rest, sharded_layers, toks, tgt)
    layer_g = jax.device_get(layer_g)
    if vpp > 1:
        layer_g = deinterleave_stack(layer_g, mesh.shape["pipe"], vpp)
    return float(loss), jax.device_get(rest_g), layer_g


@pytest.mark.parametrize("pp,vpp", [(2, 1), (4, 1), (2, 2)])
def test_pipeline_matches_serial(pp, vpp):
    mesh, serial, par, params, toks, tgt = _setup(pp)
    try:
        v_s, g_s = jax.value_and_grad(serial.loss)(params, toks, tgt)
        loss, rest_g, layer_g = _pipeline_value_and_grad(
            par, mesh, params, toks, tgt, M=4, vpp=vpp
        )
        np.testing.assert_allclose(float(v_s), loss, rtol=1e-5)
        for name in ("embedding", "position", "ln_f"):
            a = jax.tree.leaves(g_s[name])
            b = jax.tree.leaves(rest_g[name])
            for x, y in zip(a, b):
                np.testing.assert_allclose(x, np.asarray(y), rtol=2e-4, atol=2e-4,
                                           err_msg=name)
        for x, y in zip(jax.tree.leaves(g_s["layers"]), jax.tree.leaves(layer_g)):
            np.testing.assert_allclose(x, np.asarray(y), rtol=2e-4, atol=2e-4)
    finally:
        mesh_lib.destroy_model_parallel()


def test_pipeline_with_tensor_parallel():
    """Hybrid PP×TP on 8 virtual devices (the gpt_scaling_test.py (2,1,4) /
    (1,2,4) configs)."""
    mesh, serial, par, params, toks, tgt = _setup(pp=2, tp_size=2)
    try:
        v_s = float(serial.loss(params, toks, tgt))
        loss, _, _ = _pipeline_value_and_grad(par, mesh, params, toks, tgt, M=2)
        np.testing.assert_allclose(v_s, loss, rtol=1e-5)
    finally:
        mesh_lib.destroy_model_parallel()


def test_no_pipelining_grad_accumulation_matches_full_batch():
    model = GPTModel(GPTConfig(axis=None, **TINY))
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
    tgt = jnp.roll(toks, -1, axis=-1)
    loss_fn = lambda p, b, t: model.loss(p, b, t)
    l_acc, g_acc = forward_backward_no_pipelining(loss_fn, params, toks, tgt, 4)
    l_full, g_full = jax.value_and_grad(model.loss)(params, toks, tgt)
    np.testing.assert_allclose(float(l_full), float(l_acc), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_acc)):
        np.testing.assert_allclose(a, np.asarray(b), rtol=1e-5, atol=1e-6)


def test_interleave_stack_round_trip():
    layers = {"w": jnp.arange(8.0)[:, None] * jnp.ones((8, 3))}
    perm = interleave_stack(layers, 2, 2)
    # stage 0 (first half) must hold slabs 0 and 2; stage 1 slabs 1 and 3
    np.testing.assert_array_equal(np.asarray(perm["w"][:, 0]),
                                  [0, 1, 4, 5, 2, 3, 6, 7])
    back = deinterleave_stack(perm, 2, 2)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(layers["w"]))


def test_microbatch_calculators():
    c = build_num_microbatches_calculator(64, 4, 2)
    assert isinstance(c, ConstantNumMicroBatches)
    assert c.get() == 8
    r = build_num_microbatches_calculator(64, 4, 2, rampup_batch_size=[16, 16, 300])
    assert isinstance(r, RampupBatchsizeNumMicroBatches)
    assert r.get_current_global_batch_size() == 16
    r.update(150, True)
    assert r.get_current_global_batch_size() == 32
    r.update(400, True)
    assert r.get_current_global_batch_size() == 64
    assert r.get() == 8
    with pytest.raises(ValueError):
        build_num_microbatches_calculator(63, 4, 2)


def test_pipeline_o2_with_mesh_grad_scaler():
    """The dtype x grad-scaler leg of the reference sweep
    (run_pipeline_parallel_test.py:33-80): bf16 O2 pipelined step matches
    the serial O2 loss and the scaler stays on its clean-step schedule.
    (Uniform cross-stage skip is covered by test_mesh_grad_scaler.py on both
    the model and pipe axes.)"""
    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam

    cfg = dict(TINY)
    cfg["compute_dtype"] = jnp.bfloat16
    mesh = mesh_lib.make_virtual_mesh(2, pipeline_model_parallel_size=2)
    try:
        serial = GPTModel(GPTConfig(axis=None, **cfg))
        par = GPTModel(GPTConfig(axis=None, **cfg))
        policy = amp.get_policy("O2")
        mp_opt = amp.MixedPrecisionOptimizer(FusedAdam(lr=1e-3), policy)
        params = amp.cast_params(serial.init(jax.random.PRNGKey(0)), policy)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
        tgt = jnp.roll(toks, -1, axis=-1)

        # serial O2 reference loss
        v_s = float(serial.loss(params, toks, tgt))

        specs = par.specs()
        layer_specs = pipeline_specs(specs["layers"])
        rest_specs = {k: v for k, v in specs.items() if k != "layers"}
        all_specs = dict(rest_specs, layers=layer_specs)
        sharded = tp.shard_params(params, all_specs, mesh)
        opt_state = mp_opt.init(sharded)

        loss_fn = pipelined_loss_fn(
            embed=par.embed,
            run_layers=lambda lp, h: par.run_layers(lp, h),
            head_loss=lambda p, h, t: par.head(p, h, t),
            num_microbatches=4,
        )

        def sharded_grads(p, toks, tgt, scale):
            rest = {k: v for k, v in p.items() if k != "layers"}

            def scaled(rest, layers):
                return loss_fn(rest, layers, toks, tgt) * scale

            loss, (rg, lg) = jax.value_and_grad(scaled, argnums=(0, 1))(
                rest, p["layers"])
            rg = allreduce_gradients_by_spec(rg, rest_specs)
            return jax.lax.pmean(loss, "pipe"), dict(rg, layers=lg)

        shard_fn = jax.shard_map(
            sharded_grads, mesh=mesh,
            in_specs=(all_specs, P(), P(), P()),
            out_specs=(P(), all_specs), check_vma=False)

        @jax.jit
        def train_step(params, opt_state, toks, tgt):
            sl, sg = shard_fn(params, toks, tgt, opt_state.scaler.loss_scale)
            np_, ns, m = mp_opt.apply_gradients(opt_state, params, sg)
            return np_, ns, sl / opt_state.scaler.loss_scale, m

        new_params, new_state, loss, metrics = train_step(
            sharded, opt_state, toks, tgt)
        np.testing.assert_allclose(float(loss), v_s, rtol=2e-5)
        assert not bool(metrics["found_inf"])
        assert float(new_state.scaler.loss_scale) == 2.0 ** 16
        # params actually moved
        delta = jnp.abs(
            new_params["position"].astype(jnp.float32)
            - jax.device_get(sharded["position"]).astype(jnp.float32)).max()
        assert float(delta) > 0
    finally:
        mesh_lib.destroy_model_parallel()


def test_deep_interleaved_pipeline_matches_serial():
    """The BASELINE config-5 shape at test scale: pp=4 with 2 virtual chunks
    per stage (8 layer slabs), loss AND all grads must match serial."""
    mesh, serial, par, params, toks, tgt = _setup(pp=4, num_layers=8)
    try:
        v_s, g_s = jax.value_and_grad(serial.loss)(params, toks, tgt)
        loss, rest_g, layer_g = _pipeline_value_and_grad(
            par, mesh, params, toks, tgt, M=4, vpp=2)
        np.testing.assert_allclose(float(v_s), loss, rtol=1e-5)
        for name in ("embedding", "position", "ln_f"):
            for x, y in zip(jax.tree.leaves(g_s[name]),
                            jax.tree.leaves(rest_g[name])):
                np.testing.assert_allclose(x, np.asarray(y), rtol=2e-4,
                                           atol=2e-4, err_msg=name)
        for x, y in zip(jax.tree.leaves(g_s["layers"]), jax.tree.leaves(layer_g)):
            np.testing.assert_allclose(x, np.asarray(y), rtol=2e-4, atol=2e-4)
    finally:
        mesh_lib.destroy_model_parallel()


@pytest.mark.parametrize("schedule,unroll", [
    ("gpipe", False), ("1f1b", True),
    ("zero-bubble", False), ("zero-bubble", True),
], ids=["gpipe-scan", "1f1b-unroll", "zb-scan", "zb-unroll"])
def test_plan_executor_matches_serial(schedule, unroll):
    """The schedule-as-data COMPILED drive (schedule_grads_fn: one scan
    interpreting the plan arrays, explicit backward slots — the
    zero-bubble entries exercising the W/B-split VJP factoring) computes
    the serial model's loss AND grads, on the scan and unroll layer
    drives."""
    from apex_tpu.transformer.pipeline_parallel import (
        plan_schedule,
        schedule_grads_fn,
    )

    S, M = 2, 4
    mesh, serial, par, params, toks, tgt = _setup(
        S, unroll_layers=unroll)
    try:
        v_s, g_s = jax.value_and_grad(serial.loss)(params, toks, tgt)
        specs = par.specs()
        layer_specs = pipeline_specs(specs["layers"])
        rest_specs = {k: v for k, v in specs.items() if k != "layers"}
        rest = {k: v for k, v in params.items() if k != "layers"}
        layers_sh = tp.shard_params(params["layers"], layer_specs, mesh)

        fn = schedule_grads_fn(
            plan_schedule(schedule, M, S),
            embed=par.embed,
            run_layers=lambda lp, h: par.run_layers(lp, h),
            head_loss=lambda p, h, t: par.head(p, h, t))

        def step(rest, layers, toks, tgt):
            loss, rest_g, layer_g = fn(rest, layers, toks, tgt)
            rest_g = allreduce_gradients_by_spec(rest_g, rest_specs)
            return loss, rest_g, layer_g

        sm = jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=(rest_specs, layer_specs, P(), P()),
            out_specs=(P(), rest_specs, layer_specs), check_vma=False))
        loss, rest_g, layer_g = sm(rest, layers_sh, toks, tgt)
        np.testing.assert_allclose(float(v_s), float(loss), rtol=1e-5)
        for name in ("embedding", "position", "ln_f"):
            for x, y in zip(jax.tree.leaves(g_s[name]),
                            jax.tree.leaves(rest_g[name])):
                np.testing.assert_allclose(x, np.asarray(y), rtol=2e-4,
                                           atol=2e-4, err_msg=name)
        for x, y in zip(jax.tree.leaves(g_s["layers"]),
                        jax.tree.leaves(layer_g)):
            np.testing.assert_allclose(x, np.asarray(y), rtol=2e-4,
                                       atol=2e-4)
    finally:
        mesh_lib.destroy_model_parallel()


def test_plan_executor_loss_scale_seeds_grads():
    """The executor's scale argument must scale loss AND grads exactly
    (the harness loss-scaling contract value_and_grad provides for
    free)."""
    from apex_tpu.transformer.pipeline_parallel import (
        plan_schedule,
        schedule_grads_fn,
    )

    S, M = 2, 2
    mesh, serial, par, params, toks, tgt = _setup(S)
    try:
        specs = par.specs()
        layer_specs = pipeline_specs(specs["layers"])
        rest_specs = {k: v for k, v in specs.items() if k != "layers"}
        rest = {k: v for k, v in params.items() if k != "layers"}
        layers_sh = tp.shard_params(params["layers"], layer_specs, mesh)
        fn = schedule_grads_fn(
            plan_schedule("zero-bubble", M, S),
            embed=par.embed,
            run_layers=lambda lp, h: par.run_layers(lp, h),
            head_loss=lambda p, h, t: par.head(p, h, t))
        sm = jax.jit(jax.shard_map(
            lambda r, l, b, t, s: fn(r, l, b, t, s),
            mesh=mesh,
            in_specs=(rest_specs, layer_specs, P(), P(), P()),
            out_specs=(P(), rest_specs, layer_specs), check_vma=False),
            static_argnums=())
        l1, _, g1 = sm(rest, layers_sh, toks, tgt,
                       jnp.asarray(1.0, jnp.float32))
        l4, _, g4 = sm(rest, layers_sh, toks, tgt,
                       jnp.asarray(4.0, jnp.float32))
        np.testing.assert_allclose(float(l4), 4.0 * float(l1), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g4), jax.tree.leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), 4.0 * np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
    finally:
        mesh_lib.destroy_model_parallel()


def _scan_lengths(jaxpr):
    """All lax.scan trip counts in a (closed) jaxpr, recursively."""

    lengths = []
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            lengths.append(eqn.params["length"])
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else [v]
            for item in vs:
                if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                    lengths.extend(_scan_lengths(item))
    return lengths


def test_interleaved_tick_count_shrinks_bubble():
    """The interleaved schedule must run in vpp*M + S - 1 ticks, strictly
    fewer than the vpp*(M + S - 1) of sequential per-chunk rings (the
    reference's whole reason for fwd_bwd_pipelining_with_interleaving)."""
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        pipeline_tick_count,
    )

    S, M, vpp = 4, 4, 2
    assert pipeline_tick_count(M, S, vpp) == vpp * M + S - 1 == 11
    assert pipeline_tick_count(M, S, vpp) < vpp * (M + S - 1) == 14

    # and the traced program really scans that many ticks
    mesh, serial, par, params, toks, tgt = _setup(pp=S, num_layers=8)
    try:
        specs = par.specs()
        layer_specs = pipeline_specs(specs["layers"])
        rest_specs = {k: v for k, v in specs.items() if k != "layers"}
        layers = interleave_stack(params["layers"], S, vpp)
        rest = {k: v for k, v in params.items() if k != "layers"}

        loss_fn = pipelined_loss_fn(
            embed=par.embed,
            run_layers=lambda lp, h: par.run_layers(lp, h),
            head_loss=lambda p, h, t: par.head(p, h, t),
            num_microbatches=M,
            virtual_pipeline_size=vpp,
        )
        fn = jax.shard_map(
            loss_fn, mesh=mesh,
            in_specs=(rest_specs, layer_specs, P(), P()),
            out_specs=P(), check_vma=False,
        )
        jaxpr = jax.make_jaxpr(fn)(rest, layers, toks, tgt)
        lengths = _scan_lengths(jaxpr)
        assert lengths, "no scan found in pipelined loss"
        assert max(lengths) == pipeline_tick_count(M, S, vpp)
        assert vpp * (M + S - 1) not in lengths
    finally:
        mesh_lib.destroy_model_parallel()


def test_sharded_head_flops_match_serial():
    """With the pipe-sharded LM head, total pipelined FLOPs at pp=4 must be
    within ~1.15x of the serial step (VERDICT round-1 criterion); with the
    replicated head they are several x (head paid S times)."""
    S, M = 4, 16
    cfg = dict(TINY, vocab_size=2048, num_layers=4)
    mesh = mesh_lib.make_virtual_mesh(S, pipeline_model_parallel_size=S)
    try:
        serial = GPTModel(GPTConfig(axis=None, **cfg))
        par = GPTModel(GPTConfig(axis=None, **cfg))
        params = serial.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (32, 16), 0, 2048)
        tgt = jnp.roll(toks, -1, axis=-1)

        def compiled_flops(compiled):
            return compiled.cost_analysis()["flops"]

        serial_flops = compiled_flops(
            jax.jit(jax.value_and_grad(serial.loss))
            .lower(params, toks, tgt).compile()
        )

        specs = par.specs()
        layer_specs = pipeline_specs(specs["layers"])
        rest_specs = {k: v for k, v in specs.items() if k != "layers"}
        rest = {k: v for k, v in params.items() if k != "layers"}

        def per_device_flops(shard_head):
            loss_fn = pipelined_loss_fn(
                embed=par.embed,
                run_layers=lambda lp, h: par.run_layers(lp, h),
                head_loss=lambda p, h, t: par.head(p, h, t),
                num_microbatches=M,
                shard_head=shard_head,
            )
            fn = jax.jit(jax.shard_map(
                lambda r, l, b, t: jax.value_and_grad(loss_fn, argnums=(0, 1))(
                    r, l, b, t),
                mesh=mesh,
                in_specs=(rest_specs, layer_specs, P(), P()),
                out_specs=(P(), (rest_specs, layer_specs)),
                check_vma=False,
            ))
            return compiled_flops(
                fn.lower(rest, params["layers"], toks, tgt).compile())

        # cost_analysis reports the per-device SPMD program; x S for totals
        sharded_total = per_device_flops(True) * S
        replicated_total = per_device_flops(False) * S
        assert sharded_total <= 1.15 * serial_flops, (
            f"sharded-head pipeline {sharded_total/serial_flops:.2f}x serial")
        assert replicated_total >= 2.0 * serial_flops, (
            "replicated head should cost ~S x the serial head; got "
            f"{replicated_total/serial_flops:.2f}x — test no longer discriminates")
    finally:
        mesh_lib.destroy_model_parallel()

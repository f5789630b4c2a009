"""``python -m apex_tpu.lint.audit`` — the whole-program step audit gate.

Runs every registered IR pass (:mod:`apex_tpu.lint.passes`:
collective-consistency, static-hbm, dtype-drift, comm-bytes) plus the
program-relevant legacy tripwires (:mod:`apex_tpu.lint.trace`) over the
repo's CANONICAL step programs, each traced exactly once on the shared
walker (:mod:`apex_tpu.lint.ir`) — all off-TPU, on the 8-device virtual
CPU mesh:

- ``dense``          — the O2 train step over a tp=2 x pp=2 x dp=2 mesh
                       (the compiled 1F1B pipeline ring; the AD-transposed
                       drain IS the cooldown, CLAUDE.md);
- ``zero``           — the same hybrid with the ZeRO-sharded optimizer
                       (``build_zero_train_step``, level 2);
- ``zero3_prefetch`` — the fully-sharded double-buffered drive
                       (``zero3_prefetch=1``, unrolled layers) under
                       ``value_and_grad``;
- ``zerobubble``     — the schedule-as-data W/B-split executor
                       (``zero_bubble_grads_fn``) over pp=2 x dp=4;
- ``moe``            — the expert-parallel MoE grads program (int8
                       dispatch wire) under ``value_and_grad`` at dp=8,
                       with the ``moe-dispatch`` tripwire armed
                       (ISSUE 15);
- ``serve_prefill``/``serve_decode`` — the serving engine's two
                       shape-stable jitted programs over the paged cache;
- ``plan``           — the auto-parallelism planner's loop closed: a
                       ZeRO-3-constrained ``apex_tpu.plan.search`` winner
                       traced via its ``feasibility_step`` and audited by
                       the ``plan-feasibility`` pass — the trace must
                       match the prediction class the planner priced
                       (ISSUE 18).

Emits ONE JSON line (``{"audit": {..., "all_ok": bool}}``) and exits 0
iff every program audits clean: no unsuppressed pass findings, no
tripwire hazards. Intentional jaxpr-level findings are waived at their
source line with the standard ``# lint: disable=<rule> -- why`` grammar
(provenance-resolved, apex_tpu/lint/ir.py). Wired into
``monitor.selftest`` (a small dense+zero audit rides every selftest) and
``__graft_entry__.dryrun_multichip`` (the first train config's step is
audited in place).

``--hbm-check`` adds the static-HBM cross-check on the pinned 110M-class
dense config (bench.py's (768, 12) profile shape): the pass's estimated
peak bytes next to ``monitor.hbm``'s figure — analytic
(``param_state_report``) by default, measured (``live_array_stats`` after
materializing the step state) with ``--materialize``; the verdict gates
on the ratio staying within 2x.

No reference analog: the reference ships no static analysis
(apex_tpu/lint/__init__.py).
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

# the pinned 110M-class dense shape (bench.py: "(768, 12) ~= 110M-ish")
HBM_CHECK_CONFIG = dict(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_attention_heads=12, max_seq_len=512)


def audit_step_program(fn, *args,
                       label: str = "",
                       axes: Optional[Dict[str, int]] = None,
                       options: Optional[Dict[str, Dict[str, Any]]] = None,
                       tripwires: Iterable[Tuple[str, Callable]] = (),
                       comm: bool = True,
                       **kwargs) -> Dict[str, Any]:
    """Audit ONE step program: trace once, run every registered pass over
    the shared walk, then each ``(name, fn(ir) -> result)`` tripwire on
    the SAME IR. Returns ``{passes, tripwires, errors, suppressed, ok}``
    — ``ok`` iff no unsuppressed pass finding and no tripwire hazard."""
    from apex_tpu.lint import ir as ir_mod

    ir = ir_mod.trace_ir(fn, *args, axes=axes, comm=comm, label=label,
                         **kwargs)
    verdict = ir_mod.run_passes(ir, options=options)
    trips: Dict[str, Any] = {}
    for name, trip in tripwires:
        res = trip(ir)
        trips[name] = {"hazard": bool(res.get("hazard")),
                       "findings": res.get("findings", [])}
    verdict["tripwires"] = trips
    verdict["ok"] = verdict["ok"] and not any(
        t["hazard"] for t in trips.values())
    verdict["label"] = label
    # compact: per-pass finding summaries only (full detail is an API call
    # away; the gate artifact is one line)
    for name, res in verdict["passes"].items():
        res.pop("booked_by_verb_dtype", None)
        res.pop("static_by_verb_dtype", None)
    return verdict


# ---------------------------------------------------------------------------
# canonical program builders (tiny shapes; trace-only, nothing executes)
# ---------------------------------------------------------------------------


def _build_dense_or_zero(zero_level: int = 0):
    """The pipelined O2 train step over tp=2 x pp=2 x dp=2 — plain
    (``zero_level=0``, the compiled 1F1B ring + replicated optimizer) or
    ZeRO (level 2, ``build_zero_train_step``). Returns ``(fn, args,
    cleanup)``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import collectives, mesh as mesh_lib
    from apex_tpu.parallel.distributed import allreduce_gradients_by_spec
    from apex_tpu.transformer.pipeline_parallel import (
        prepare_pipelined_model,
    )

    tp, pp, dp, n_micro = 2, 2, 2, 2
    mesh = mesh_lib.make_virtual_mesh(
        tp * pp * dp, tensor_model_parallel_size=tp,
        pipeline_model_parallel_size=pp)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2 * pp,
                    num_attention_heads=4, max_seq_len=32,
                    hidden_dropout=0.0, axis=mesh_lib.AXIS_MODEL,
                    compute_dtype=jnp.bfloat16, remat=True)
    model = GPTModel(cfg)
    policy = amp.get_policy("O2")
    mp_opt = amp.MixedPrecisionOptimizer(
        FusedAdam(lr=1e-3), policy,
        zero_axis=mesh_lib.AXIS_DATA if zero_level else None,
        gather_dtype="bf16" if zero_level else None)
    full = amp.cast_params(model.init(jax.random.PRNGKey(0)), policy)
    specs, params, pipe_loss = prepare_pipelined_model(
        model, full, mesh, num_microbatches=n_micro)
    rest_specs = {k: v for k, v in specs.items() if k != "layers"}
    grad_axes = mesh_lib.get_gradient_reduction_axes()
    data_spec = P(mesh_lib.AXIS_DATA)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2 * dp * n_micro, 32), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=-1)
    tokens = jax.device_put(tokens, NamedSharding(mesh, data_spec))
    targets = jax.device_put(targets, NamedSharding(mesh, data_spec))

    if zero_level:
        from apex_tpu.transformer.amp import build_zero_train_step

        opt_state, state_specs = mp_opt.zero_init(params, mesh, specs)
        train_step = build_zero_train_step(
            mp_opt, mesh, specs, state_specs, pipe_loss,
            rest_specs=rest_specs, layer_specs=specs["layers"],
            grad_axes=grad_axes, data_spec=data_spec,
            zero_axis=mesh_lib.AXIS_DATA)
    else:
        opt_state = mp_opt.init(params)

        def sharded_grads(p, toks, tgts, scale):
            rest = {k: v for k, v in p.items() if k != "layers"}

            def scaled_loss(rest, layers):
                return pipe_loss(rest, layers, toks, tgts) * scale

            loss, (rest_g, layer_g) = jax.value_and_grad(
                scaled_loss, argnums=(0, 1))(rest, p["layers"])
            rest_g = allreduce_gradients_by_spec(rest_g, rest_specs)
            layer_g = allreduce_gradients_by_spec(layer_g, specs["layers"])
            return collectives.pmean(loss, grad_axes), \
                dict(rest_g, layers=layer_g)

        shard_fn = jax.shard_map(
            sharded_grads, mesh=mesh,
            in_specs=(specs, data_spec, data_spec, P()),
            out_specs=(P(), specs), check_vma=False)

        @jax.jit
        def train_step(params, opt_state, tokens, targets):
            loss, grads = shard_fn(params, tokens, targets,
                                   opt_state.scaler.loss_scale)
            new_p, new_s, metrics = mp_opt.apply_gradients(
                opt_state, params, grads)
            return new_p, new_s, loss / opt_state.scaler.loss_scale, metrics

    return (train_step, (params, opt_state, tokens, targets),
            mesh_lib.destroy_model_parallel)


def _build_zero3_prefetch():
    """The fully-sharded double-buffered drive (``zero3_prefetch=1``,
    unrolled layers) under ``value_and_grad`` at dp=8 — the canonical
    prefetched ZeRO-3 program the gather tripwires pin."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.optimizers.distributed import gather_chunked_tree

    pcfg = dict(vocab_size=128, hidden_size=32, num_layers=4,
                num_attention_heads=4, max_seq_len=16, hidden_dropout=0.0,
                axis=None, compute_dtype=jnp.bfloat16, unroll_layers=True)
    policy = amp.get_policy("O2")
    mp3 = amp.MixedPrecisionOptimizer(
        FusedAdam(lr=1e-4), policy, zero_axis="data", zero_level=3,
        gather_dtype="bf16")
    params = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(
            lambda k: amp.cast_params(
                GPTModel(GPTConfig(**pcfg)).init(k), policy),
            jax.random.PRNGKey(0)))
    meta = mp3.zero3_meta(params)
    layer_meta = meta.subtree("layers")
    rest_meta = meta.select([k for k in meta.shapes if k != "layers"])
    toks = jnp.zeros((2, 16), jnp.int32)
    model = GPTModel(GPTConfig(zero3_prefetch=1, **pcfg))

    def loss_fn(p):
        chunks = mp3.zero3_shard(p)
        rest = gather_chunked_tree(
            {k: v for k, v in chunks.items() if k != "layers"}, rest_meta)
        return model.loss(dict(rest, layers=chunks["layers"]), toks, toks,
                          layer_chunk_meta=layer_meta)

    return jax.value_and_grad(loss_fn), (params,), None


def _build_zerobubble():
    """The schedule-as-data zero-bubble executor (explicit W/B-split
    backward slots) over pp=2 x dp=4 — the grads program
    ``build_zero_train_step(pipe_value_and_grad=...)`` wires."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.parallel import collectives, mesh as mesh_lib
    from apex_tpu.parallel.distributed import allreduce_gradients_by_spec
    from apex_tpu.transformer.pipeline_parallel import (
        prepare_pipelined_model,
        zero_bubble_grads_fn,
    )

    pp, dp, n_micro = 2, 4, 2
    mesh = mesh_lib.make_virtual_mesh(
        pp * dp, pipeline_model_parallel_size=pp)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2 * pp,
                    num_attention_heads=4, max_seq_len=32,
                    hidden_dropout=0.0, axis=None,
                    compute_dtype=jnp.bfloat16, remat=True)
    model = GPTModel(cfg)
    policy = amp.get_policy("O2")
    full = amp.cast_params(model.init(jax.random.PRNGKey(0)), policy)
    specs, params, _pipe_loss = prepare_pipelined_model(
        model, full, mesh, num_microbatches=n_micro)
    rest_specs = {k: v for k, v in specs.items() if k != "layers"}
    grad_axes = mesh_lib.get_gradient_reduction_axes()
    data_spec = P(mesh_lib.AXIS_DATA)
    zb_vg = zero_bubble_grads_fn(model, n_micro, pp)

    def sharded_grads(p, toks, tgts):
        rest = {k: v for k, v in p.items() if k != "layers"}
        loss, rest_g, layer_g = zb_vg(rest, p["layers"], toks, tgts,
                                      jnp.float32(1.0))
        rest_g = allreduce_gradients_by_spec(rest_g, rest_specs)
        layer_g = allreduce_gradients_by_spec(layer_g, specs["layers"])
        return collectives.pmean(loss, grad_axes), \
            dict(rest_g, layers=layer_g)

    fn = jax.jit(jax.shard_map(
        sharded_grads, mesh=mesh,
        in_specs=(specs, data_spec, data_spec),
        out_specs=(P(), specs), check_vma=False))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2 * dp * n_micro, 32), 0, cfg.vocab_size)
    tokens = jax.device_put(tokens, NamedSharding(mesh, data_spec))
    targets = jnp.roll(tokens, -1, axis=-1)
    return (fn, (tokens, targets, ),
            mesh_lib.destroy_model_parallel), params


def _build_moe():
    """The expert-parallel MoE grads program (ISSUE 15): value_and_grad
    of the EP GPT loss on per-shard params under ``axes={"data": 8}``,
    with the int8 dispatch wire armed — the canonical program the
    ``moe-dispatch`` tripwire pins (dispatch all_to_alls present, every
    dispatch-shaped bulk payload at 1 B/elem)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import GPTConfig, GPTModel

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_seq_len=16,
                    hidden_dropout=0.0, axis=None,
                    compute_dtype=jnp.bfloat16, remat=True,
                    moe_num_experts=8, moe_top_k=2,
                    moe_capacity_factor=2.0, moe_expert_axis="data",
                    moe_dispatch_dtype="int8")
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # per-shard (dp=8) param view: one expert per rank (stacked moe
    # leaves carry the expert dim at axis 1), everything else replicated
    layers = dict(params["layers"])
    layers["moe"] = {
        "router": layers["moe"]["router"],
        "fc1": jax.tree.map(lambda v: v[:, :1], layers["moe"]["fc1"]),
        "fc2": jax.tree.map(lambda v: v[:, :1], layers["moe"]["fc2"]),
    }
    local = dict(params, layers=layers)
    toks = jnp.zeros((2, 16), jnp.int32)

    def loss_fn(p):
        return model.loss(p, toks, toks)

    return jax.value_and_grad(loss_fn), (local,)


def _build_plan():
    """The planner's loop closed: search the tiny spec under a ZeRO-3
    constraint (every other knob free), then build the winner's claimed
    grads program (``plan.feasibility_step``) so the ``plan-feasibility``
    pass can audit the trace against the plan's prediction class."""
    from apex_tpu import plan as plan_mod

    spec = plan_mod.ModelSpec("plan-tiny", 128, 64, 4, 4, 32)
    result = plan_mod.search(spec, mesh=8, hbm_gb=16.0, platform="cpu",
                             constraints={"zero_level": 3, "pp": 1})
    winner = result["winner"]
    if winner is None:  # 16 GiB fits the tiny spec by construction
        raise RuntimeError("plan audit program: no feasible ZeRO-3 "
                           "candidate for the tiny spec")
    cand = plan_mod.Candidate(**winner["candidate"])
    step = plan_mod.feasibility_step(spec, cand)
    if step is None:
        raise RuntimeError(f"plan audit program: winner {cand} has no "
                           "feasibility trace")
    return step


def _build_serve():
    """The serving engine's two shape-stable jitted programs (prefill,
    decode) on a serial tiny build — the argument streams come from the
    engine's own provenance hooks (``prefill_args``/``decode_args``)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.serve import Engine, ServeConfig

    cfg = GPTConfig(vocab_size=41, hidden_size=16, num_layers=1,
                    num_attention_heads=2, max_seq_len=32,
                    hidden_dropout=0.0, axis=None,
                    compute_dtype=jnp.float32, remat=False)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params,
                 ServeConfig(max_batch=2, max_seq=24, block_size=8))
    return eng


def run_audit(programs: Optional[Iterable[str]] = None,
              hbm_check: bool = False,
              materialize: bool = False) -> Dict[str, Any]:
    """Audit the canonical step programs (every registered pass + the
    program-relevant tripwires over ONE trace each). ``programs`` selects
    a subset by name. Returns the full verdict dict; ``all_ok`` gates."""
    from apex_tpu.lint import trace as lint_trace

    known = {"dense", "zero", "zero3_prefetch", "zerobubble", "moe",
             "serve_prefill", "serve_decode", "plan"}
    wanted = set(programs) if programs else None
    if wanted is not None and wanted - known:
        # a typo'd CI subset must never audit 0 programs and exit green
        raise ValueError(f"unknown audit program(s): "
                         f"{sorted(wanted - known)}; known: {sorted(known)}")
    out: Dict[str, Any] = {"programs": {}}
    # the audit shapes are deliberately TINY (h=64, seq=32 — trace-only,
    # seconds off-TPU), so the blowup floors scale down with them: a
    # 2x minor-dim pad on a (4, 256, 64) activation is an artifact of the
    # test hidden size, not a defect; real findings at these shapes are
    # the >= 2 MiB wastes (the 128x (rows, 1) class the pass exists for)
    opts = {"static-hbm": {"min_bytes": 1 << 21}}

    def want(name):
        return wanted is None or name in wanted

    def record(name, verdict):
        out["programs"][name] = verdict

    if want("dense"):
        fn, args, cleanup = _build_dense_or_zero(zero_level=0)
        record("dense", audit_step_program(fn, *args, label="dense",
                                           options=opts))
        cleanup()
    if want("zero"):
        fn, args, cleanup = _build_dense_or_zero(zero_level=2)
        record("zero", audit_step_program(
            fn, *args, label="zero", options=opts,
            tripwires=[
                ("zero-redundancy", lambda ir: lint_trace.
                 zero_redundancy_hazards(ir, zero_axis="data")),
            ]))
        cleanup()
    if want("zero3_prefetch"):
        fn, args, _ = _build_zero3_prefetch()
        record("zero3_prefetch", audit_step_program(
            fn, *args, label="zero3_prefetch", axes={"data": 8},
            options=opts,
            tripwires=[
                # the largest single-layer leaf at h=32 is 4096 elems
                # (fc1); the whole stack is ~13x that -- 16384 splits them
                ("zero3-bulk-gather", lambda ir: lint_trace.
                 zero3_gather_hazards(ir, min_model_elems=16384)),
                ("unprefetched-gather", lambda ir: lint_trace.
                 unprefetched_gather_hazards(ir)),
            ]))
    if want("zerobubble"):
        (fn, args, cleanup), params = _build_zerobubble()
        record("zerobubble", audit_step_program(
            fn, params, *args, label="zerobubble", options=opts))
        cleanup()
    if want("moe"):
        fn, args = _build_moe()
        record("moe", audit_step_program(
            fn, *args, label="moe", axes={"data": 8}, options=opts,
            tripwires=[
                ("moe-dispatch", lambda ir: lint_trace.moe_dispatch_hazards(
                    ir, expert_axis="data", wire_dtype="int8")),
            ]))
    if want("plan"):
        step = _build_plan()
        record("plan", audit_step_program(
            step["fn"], *step["args"], label="plan", axes=step["axes"],
            options={**opts, "plan-feasibility": {
                "plan": step["plan"],
                "model_elems": step["model_elems"]}}))
    if want("serve_prefill") or want("serve_decode"):
        eng = _build_serve()
        if want("serve_prefill"):
            record("serve_prefill", audit_step_program(
                eng._prefill_fn, *eng.prefill_args(0),
                label="serve_prefill", options=opts))
        if want("serve_decode"):
            record("serve_decode", audit_step_program(
                eng._decode_fn, *eng.decode_args(0), label="serve_decode",
                options=opts,
                tripwires=[
                    ("decode-recompile", lambda _ir: lint_trace.
                     decode_recompile_hazards(eng.decode_args, ticks=3)),
                ]))

    if hbm_check:
        out["hbm_check"] = hbm_crosscheck(materialize=materialize)

    out["errors"] = sum(v["errors"] for v in out["programs"].values())
    out["suppressed"] = sum(
        v["suppressed"] for v in out["programs"].values())
    out["all_ok"] = all(v["ok"] for v in out["programs"].values()) and (
        out.get("hbm_check", {"ok": True})["ok"])
    return out


def hbm_crosscheck(materialize: bool = False,
                   config: Optional[Dict[str, Any]] = None,
                   batch: int = 2) -> Dict[str, Any]:
    """The static-HBM pass's estimated peak bytes for the pinned
    110M-class dense config next to ``monitor.hbm``'s figure.

    The static side traces the O2 train step from ``ShapeDtypeStruct``
    args (no HBM touched even at 110M). The reference side is
    ``monitor.hbm.param_state_report``'s analytic replicated params+state
    bytes by default; ``materialize=True`` instead materializes the step
    state and reads ``live_array_stats`` (the truly measured figure —
    tests/test_lint_ir.py pins the same comparison on a small config).
    ``ok`` iff the estimate is within 2x of the reference."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import amp
    from apex_tpu.lint.passes import static_hbm_pass
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.monitor import hbm as hbm_mod
    from apex_tpu.optimizers import FusedAdam

    cfg = GPTConfig(hidden_dropout=0.0, axis=None,
                    compute_dtype=jnp.bfloat16, remat=True,
                    **(config or HBM_CHECK_CONFIG))
    model = GPTModel(cfg)
    policy = amp.get_policy("O2")
    mp_opt = amp.MixedPrecisionOptimizer(FusedAdam(lr=1e-3), policy)
    abstract = jax.eval_shape(
        lambda k: amp.cast_params(model.init(k), policy),
        jax.random.PRNGKey(0))

    def train_step(p, opt_state, toks, tgts):
        def scaled(p):
            return model.loss(p, toks, tgts) * opt_state.scaler.loss_scale

        loss, grads = jax.value_and_grad(scaled)(p)
        new_p, new_s, metrics = mp_opt.apply_gradients(opt_state, p, grads)
        return new_p, new_s, loss / opt_state.scaler.loss_scale, metrics

    abstract_state = jax.eval_shape(mp_opt.init, abstract)
    toks = jax.ShapeDtypeStruct((batch, cfg.max_seq_len), jnp.int32)
    est = static_hbm_pass(jax.make_jaxpr(train_step)(
        abstract, abstract_state, toks, toks))

    if materialize:
        params = amp.cast_params(model.init(jax.random.PRNGKey(0)), policy)
        opt_state = mp_opt.init(params)
        toks_v = jnp.zeros((batch, cfg.max_seq_len), jnp.int32)
        outs = jax.jit(train_step)(params, opt_state, toks_v, toks_v)
        jax.block_until_ready(outs)
        reference = hbm_mod.live_array_stats()["live_bytes"]
        basis = "live_array_stats after one materialized step"
        del outs, params, opt_state
        bound = 2.0
    else:
        rep = hbm_mod.param_state_report(abstract, dp=1)
        reference = rep["per_rank"]["replicated"]["total_bytes"]
        basis = "param_state_report replicated params+state (analytic)"
        # one resident copy is the analytic floor, but the step programs
        # do not donate their inputs, so old and new state are live
        # together and the estimate legitimately sits near 2x
        bound = 2.5
    ratio = est["peak_bytes"] / max(reference, 1)
    return {"estimated_peak_bytes": est["peak_bytes"],
            "reference_bytes": int(reference), "basis": basis,
            "ratio": round(ratio, 3), "bound": bound,
            "ok": bool(0.5 <= ratio <= bound)}


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m apex_tpu.lint.audit",
        description="whole-program jaxpr audit over the canonical step "
                    "programs (one JSON verdict line; exit 0 iff clean)")
    p.add_argument("--programs", type=str, default=None,
                   help="comma-separated subset (dense,zero,"
                        "zero3_prefetch,zerobubble,moe,serve_prefill,"
                        "serve_decode,plan)")
    p.add_argument("--hbm-check", action="store_true",
                   help="add the 110M-class static-vs-monitor.hbm "
                        "peak-bytes cross-check")
    p.add_argument("--materialize", action="store_true",
                   help="with --hbm-check: materialize the step state and "
                        "compare against measured live_array_stats "
                        "(slower; default is the analytic figure)")
    args = p.parse_args(argv)

    # the audit traces programs, it does not run them: it uses the
    # 8-device virtual CPU mesh whatever accelerator the machine has
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # noqa: BLE001 - backend already up: run on it
        pass

    programs = ([s.strip() for s in args.programs.split(",")]
                if args.programs else None)
    try:
        verdict = run_audit(programs=programs, hbm_check=args.hbm_check,
                            materialize=args.materialize)
    except ValueError as e:  # unknown program name: the lint-CLI rc
        print(str(e), file=sys.stderr)
        return 2
    print(json.dumps({"audit": verdict}, default=str))
    return 0 if verdict["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

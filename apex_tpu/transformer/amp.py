"""Model-parallel-aware grad scaling
(reference: apex/transformer/amp/grad_scaler.py:8-106 ``GradScaler``).

The reference subclasses torch's GradScaler to all-reduce ``found_inf``
across the **model-parallel group** in ``_maybe_opt_step`` (:25-36) and
``update`` (:80-94) so every TP/PP rank takes the same skip decision.

Here the scaler state machine lives in :class:`apex_tpu.amp.LossScaler`;
the model-parallel reduction plugs into
``MixedPrecisionOptimizer.apply_gradients(found_inf_reducer=...)``.
:class:`MeshGradScaler` packages that reducer for the current mesh, and
:func:`build_zero_train_step` packages the full ZeRO-sharded train step
(the reference's DistributedFusedAdam step loop,
distributed_fused_adam.py:2130-2230) for the GPT pipelined harnesses.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from apex_tpu.parallel.mesh import AXIS_DATA, AXIS_MODEL, AXIS_PIPE

AxisNames = Union[str, Tuple[str, ...]]


def model_parallel_found_inf_reducer(
    axes: AxisNames = (AXIS_MODEL, AXIS_PIPE),
):
    """found_inf OR-reduction over the model-parallel axes — apply inside
    ``shard_map`` (grad_scaler.py:25-36: ``all_reduce(found_inf, MAX,
    model_parallel_group)``)."""
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)

    def reduce(found_inf: jax.Array) -> jax.Array:
        return lax.pmax(found_inf.astype(jnp.float32), axes_t) > 0

    return reduce


class MeshGradScaler:
    """Convenience bundle: pass ``scaler.found_inf_reducer`` into
    ``MixedPrecisionOptimizer.apply_gradients`` when training under a mesh
    with model-parallel axes.

    >>> scaler = MeshGradScaler()                     # ('model', 'pipe')
    >>> mp_opt.apply_gradients(state, params, grads,
    ...                        found_inf_reducer=scaler.found_inf_reducer)
    """

    def __init__(self, axes: AxisNames = (AXIS_MODEL, AXIS_PIPE)):
        self.axes = (axes,) if isinstance(axes, str) else tuple(axes)
        self.found_inf_reducer = model_parallel_found_inf_reducer(self.axes)


def build_zero_train_step(
    mp_opt,
    mesh,
    specs,
    state_specs,
    pipe_loss,
    *,
    rest_specs,
    grad_axes: Tuple[str, ...],
    data_spec: PartitionSpec,
    zero_axis: str = AXIS_DATA,
    layer_specs=None,
    zero3=None,
    model=None,
    num_microbatches: Optional[int] = None,
    virtual_pipeline_size: int = 1,
    with_aux: bool = False,
    traced: bool = False,
    tracer=None,
    pipe_value_and_grad=None,
):
    """One jitted GPT train step with the whole ZeRO update inside a single
    ``shard_map``: backward, spec-aware grad reduction over every
    non-``zero_axis`` axis, then the sharded optimizer — whose
    ``psum_scatter`` IS the ``zero_axis`` reduction, so that axis is
    dropped from the harness reduction (tripwire:
    ``lint.trace.zero_redundancy_hazards``) — with the overflow flag
    OR-reduced over the model/pipe axes (grad_scaler.py:25-36 semantics).

    ``pipe_loss(rest, layers, tokens, targets)`` is the unscaled pipelined
    loss over a ``{"layers": ..., **rest}`` param dict — the shape every
    GPT harness here shares.  Layer grads reduce spec-aware when
    ``layer_specs`` is given, otherwise uniformly over the non-zero axes.
    ``(specs, state_specs)`` come from ``mp_opt.zero_init``.

    Quantized grad reduce (``mp_opt.reduce_dtype``) needs no extra wiring
    here: ``apply_gradients`` swaps its psum_scatter for the encoded
    all_to_all pair (parallel/quantize.py) and the error-feedback residual
    rides :class:`apex_tpu.amp.MPOptState` — ``zero_init``'s state_specs
    already cover it (1-D per-rank leaves behind the universal chunk
    spec), so the same builder serves both wires. Tripwire:
    ``lint.trace.quantized_comm_hazards``.

    At ``zero_level=3`` (``mp_opt.zero_level``) pass ``zero3`` (the
    :class:`apex_tpu.amp.Zero3Setup` from ``mp_opt.zero3_init``) plus
    ``model`` and the pipeline shape (``num_microbatches``, optionally
    ``virtual_pipeline_size``/``with_aux``) instead of ``pipe_loss``/
    ``specs`` — the builder then rebuilds the pipelined loss around the
    fully-sharded drive: non-layer params all-gather once at step entry,
    each LAYER's weights all-gather just-in-time inside the layer loop
    (models/_transformer.run_layers ``chunk_meta``; re-gathered in the
    backward by per-layer remat), the gathers' AD transposes
    reduce-scatter that layer's grads on the spot, and ``apply_gradients``
    finishes on chunks with NO post-update gather (tripwire:
    ``lint.trace.zero3_gather_hazards``). ``rest_specs``/``layer_specs``
    stay the ORIGINAL param specs — chunk grads reduce spec-aware over the
    non-zero axes exactly like full grads (only axis names are read).

    Returns ``train_step(params, opt_state, tokens, targets) ->
    (params, opt_state, loss, metrics)`` with the loss unscaled; at level
    3 ``params`` is the persistent chunk tree (``zero3.params``).

    ``pipe_value_and_grad`` swaps the backward's DERIVATION: instead of
    ``jax.value_and_grad`` of ``pipe_loss`` (the AD-transposed SPMD ring),
    pass ``(rest, layers, toks, tgts, scale) -> (scaled_loss, rest_g,
    layer_g)`` — e.g. ``schedules.schedule_grads_fn(plan_schedule(
    "zero-bubble", ...))``, whose EXPLICIT backward slots are the only way
    the W/B split can fill the pipeline cooldown. Levels 1/2 only (the
    ZeRO-3 branch rebuilds the pipelined loss itself); the grads contract
    is identical (per-stage partial rest grads, per-stage layer chunks),
    so the spec-aware reduction and the sharded optimizer see no
    difference.

    ``traced=True`` (the ``--trace``/``BENCH_TRACE`` opt-in) splits the
    step into its two anatomy phases — backward+reduction
    (``zero.grads``, the ZeRO-3 just-in-time gathers and their
    reduce-scatter transposes live here) and the sharded-optimizer
    update (``zero.apply``: the level-1/2 grad psum_scatter + param
    all_gather) — each its own jitted program wrapped in a
    ``monitor.tracing`` span with a device→host fetch barrier and the
    phase's traced collective payload bytes attached, so journals and
    ``monitor.report``'s timeline section get measured phase seconds
    instead of a single opaque wall time. Identical math (same programs'
    contents, one extra host handoff); ``traced=False`` (default) builds
    the ORIGINAL single-program step — byte-identical, tier-1 pins it.
    """
    from apex_tpu.parallel import collectives
    from apex_tpu.parallel.distributed import (
        allreduce_gradients,
        allreduce_gradients_by_spec,
    )

    reducer = MeshGradScaler().found_inf_reducer
    nonzero_axes = tuple(a for a in grad_axes if a != zero_axis)

    def reduce_nonzero(rest_g, layer_g):
        # nonzero_axes already excludes zero_axis: the sharded optimizer's
        # psum_scatter (level 2) / the gather transposes (level 3) ARE the
        # reduction over it
        rest_g = allreduce_gradients_by_spec(
            rest_g, rest_specs, data_axes=nonzero_axes)
        layer_g = (
            allreduce_gradients_by_spec(
                layer_g, layer_specs, data_axes=nonzero_axes)
            if layer_specs is not None
            else allreduce_gradients(layer_g, nonzero_axes))
        return rest_g, layer_g

    if getattr(mp_opt, "zero_level", 2) >= 3:
        if pipe_value_and_grad is not None:
            raise ValueError(
                "pipe_value_and_grad (the zero-bubble schedule engine) "
                "composes with ZeRO levels 1/2 only: the level-3 branch "
                "rebuilds the pipelined loss around the fully-sharded "
                "chunk drive")
        if zero3 is None or model is None or num_microbatches is None:
            raise ValueError(
                "zero_level=3 needs zero3=(mp_opt.zero3_init(...)), model= "
                "and num_microbatches= — the builder rebuilds the pipelined "
                "loss around the per-layer JIT weight gather")
        # reject at BUILD time with the same words run_layers uses at
        # trace time — the harness/audit asymmetry was a prefetch config
        # that built fine and only died deep inside the first trace
        if (int(getattr(model.cfg, "zero3_prefetch", 0) or 0) > 0
                and not getattr(model.cfg, "unroll_layers", False)):
            from apex_tpu.models._transformer import (
                ZERO3_PREFETCH_NEEDS_UNROLL,
            )

            raise ValueError(ZERO3_PREFETCH_NEEDS_UNROLL)
        from apex_tpu.optimizers.distributed import gather_chunked_tree
        from apex_tpu.transformer.pipeline_parallel import pipelined_loss_fn

        meta = zero3.meta
        layer_meta = meta.subtree("layers")
        rest_meta = meta.select(
            [k for k in meta.shapes if k != "layers"])
        if with_aux:
            run_layers = lambda lp, h: model.run_layers(  # noqa: E731
                lp, h, return_aux=True, chunk_meta=layer_meta)
            aux_to_loss = model.aux_to_loss
        else:
            run_layers = lambda lp, h: model.run_layers(  # noqa: E731
                lp, h, chunk_meta=layer_meta)
            aux_to_loss = None
        pipe_loss3 = pipelined_loss_fn(
            embed=model.embed,
            run_layers=run_layers,
            head_loss=lambda p, h, t: model.head(p, h, t),
            num_microbatches=num_microbatches,
            virtual_pipeline_size=virtual_pipeline_size,
            aux_to_loss=aux_to_loss)

        def zero3_step(p, opt_state, toks, tgts):
            rest_c = {k: v for k, v in p.items() if k != "layers"}

            def scaled_loss(rest_c, layer_c):
                # non-layer params (embedding, head LN) gather once per
                # step — the unavoidable O(embedding) working set; the
                # layer stack stays chunked and gathers inside the loop
                rest = gather_chunked_tree(rest_c, rest_meta)
                return pipe_loss3(rest, layer_c, toks, tgts) \
                    * opt_state.scaler.loss_scale

            loss, (rest_g, layer_g) = jax.value_and_grad(
                scaled_loss, argnums=(0, 1))(rest_c, p["layers"])
            # grads are CHUNK trees, already reduce-scattered over the
            # zero axis by the gather transposes — only the other axes
            # (context partials, pipe embedding ties) reduce here
            rest_g, layer_g = reduce_nonzero(rest_g, layer_g)
            new_p, new_state, metrics = mp_opt.apply_gradients(
                opt_state, p, dict(rest_g, layers=layer_g),
                found_inf_reducer=reducer)
            return (new_p, new_state,
                    collectives.pmean(loss, grad_axes), metrics)

        zero_fn = jax.shard_map(
            zero3_step, mesh=mesh,
            in_specs=(zero3.param_specs, zero3.state_specs,
                      data_spec, data_spec),
            out_specs=(zero3.param_specs, zero3.state_specs,
                       PartitionSpec(), PartitionSpec()),
            check_vma=False)

        if traced:
            # the grads phase owns the per-layer JIT gathers and their
            # reduce-scatter transposes — the ZeRO-3 gather/scatter span
            def traced_grads(p, opt_state, toks, tgts):
                rest_c = {k: v for k, v in p.items() if k != "layers"}

                def scaled_loss(rest_c, layer_c):
                    rest = gather_chunked_tree(rest_c, rest_meta)
                    return pipe_loss3(rest, layer_c, toks, tgts) \
                        * opt_state.scaler.loss_scale

                loss, (rest_g, layer_g) = jax.value_and_grad(
                    scaled_loss, argnums=(0, 1))(rest_c, p["layers"])
                rest_g, layer_g = reduce_nonzero(rest_g, layer_g)
                return (collectives.pmean(loss, grad_axes),
                        rest_g, layer_g)

            traced_param_specs = zero3.param_specs
            traced_state_specs = zero3.state_specs
    else:

        def value_and_grad(rest, layers, toks, tgts, scale):
            if pipe_value_and_grad is not None:
                # explicit-backward schedule engine (zero-bubble W/B
                # split); same (loss, rest_g, layer_g) contract as the
                # AD path below
                return pipe_value_and_grad(rest, layers, toks, tgts, scale)

            def scaled_loss(rest, layers):
                return pipe_loss(rest, layers, toks, tgts) * scale

            loss, (rest_g, layer_g) = jax.value_and_grad(
                scaled_loss, argnums=(0, 1))(rest, layers)
            return loss, rest_g, layer_g

        def zero_step(p, opt_state, toks, tgts):
            rest = {k: v for k, v in p.items() if k != "layers"}
            loss, rest_g, layer_g = value_and_grad(
                rest, p["layers"], toks, tgts, opt_state.scaler.loss_scale)
            rest_g, layer_g = reduce_nonzero(rest_g, layer_g)
            new_p, new_state, metrics = mp_opt.apply_gradients(
                opt_state, p, dict(rest_g, layers=layer_g),
                found_inf_reducer=reducer)
            return (new_p, new_state,
                    collectives.pmean(loss, grad_axes), metrics)

        zero_fn = jax.shard_map(
            zero_step, mesh=mesh,
            in_specs=(specs, state_specs, data_spec, data_spec),
            out_specs=(specs, state_specs, PartitionSpec(), PartitionSpec()),
            check_vma=False)

        if traced:

            def traced_grads(p, opt_state, toks, tgts):
                rest = {k: v for k, v in p.items() if k != "layers"}
                loss, rest_g, layer_g = value_and_grad(
                    rest, p["layers"], toks, tgts,
                    opt_state.scaler.loss_scale)
                rest_g, layer_g = reduce_nonzero(rest_g, layer_g)
                return (collectives.pmean(loss, grad_axes),
                        rest_g, layer_g)

            traced_param_specs = specs
            traced_state_specs = state_specs

    if traced:
        # the two-phase anatomy build (docstring): same math, two jitted
        # programs, host spans with fetch barriers between them. The
        # apply phase is where the level-1/2 gather/scatter collectives
        # live (psum_scatter + compressed all_gather); at level 3 those
        # ride the grads phase's per-layer gather transposes instead.
        from apex_tpu.monitor import comms as comms_mod
        from apex_tpu.monitor import tracing as tracing_mod

        rest_gspecs = {k: v for k, v in traced_param_specs.items()
                       if k != "layers"}
        layer_gspecs = traced_param_specs["layers"]

        def traced_apply(p, opt_state, rest_g, layer_g):
            return mp_opt.apply_gradients(
                opt_state, p, dict(rest_g, layers=layer_g),
                found_inf_reducer=reducer)

        grad_fn = jax.jit(jax.shard_map(
            traced_grads, mesh=mesh,
            in_specs=(traced_param_specs, traced_state_specs,
                      data_spec, data_spec),
            out_specs=(PartitionSpec(), rest_gspecs, layer_gspecs),
            check_vma=False))
        apply_fn = jax.jit(jax.shard_map(
            traced_apply, mesh=mesh,
            in_specs=(traced_param_specs, traced_state_specs,
                      rest_gspecs, layer_gspecs),
            out_specs=(traced_param_specs, traced_state_specs,
                       PartitionSpec()),
            check_vma=False))

        phase_comm: dict = {}

        def _arm_phase_bytes(key, fn, *args) -> None:
            # join each phase span with the comm: scope byte accounting
            # (monitor/comms.py): ONE extra trace per phase, host-side,
            # so every span carries the phase's collective payload bytes
            try:
                with comms_mod.comm_accounting() as acct:
                    jax.make_jaxpr(fn)(*args)
                phase_comm[key] = acct.total_bytes()
            except Exception:  # noqa: BLE001 - telemetry must not kill a run
                phase_comm[key] = None

        def traced_train_step(params, opt_state, tokens, targets):
            tr = tracer if tracer is not None else tracing_mod.get_tracer()
            try:
                # a jax re-trace of this step (mfu arming, cost censuses)
                # executes the body with abstract values — suppress the
                # spans, a trace-time "duration" is not a measurement
                if tr is not None and not jax.core.trace_state_clean():
                    tr = None
            except Exception:  # noqa: BLE001 - older/newer jax: keep spans
                pass
            if "grads" not in phase_comm:
                _arm_phase_bytes("grads", grad_fn,
                                 params, opt_state, tokens, targets)
            with tracing_mod.maybe_span(
                    tr, "zero.grads", cat="compute",
                    comm_bytes=phase_comm.get("grads")) as sp:
                scaled, rest_g, layer_g = grad_fn(
                    params, opt_state, tokens, targets)
                sp.barrier(scaled)
            if "apply" not in phase_comm:
                _arm_phase_bytes("apply", apply_fn,
                                 params, opt_state, rest_g, layer_g)
            with tracing_mod.maybe_span(
                    tr, "zero.apply", cat="comm",
                    comm_bytes=phase_comm.get("apply")) as sp:
                new_p, new_state, metrics = apply_fn(
                    params, opt_state, rest_g, layer_g)
                sp.barrier(metrics["loss_scale"])
            return (new_p, new_state,
                    scaled / opt_state.scaler.loss_scale, metrics)

        return traced_train_step

    @jax.jit
    def train_step(params, opt_state, tokens, targets):
        new_p, new_state, scaled, metrics = zero_fn(
            params, opt_state, tokens, targets)
        return (new_p, new_state,
                scaled / opt_state.scaler.loss_scale, metrics)

    return train_step


def build_dropless_train_step(model, mp_opt):
    """``train_step(params, opt_state, tokens, targets)`` →
    ``(params, opt_state, loss, metrics)`` for a model whose ``loss`` returns
    ``(mean loss, stats)`` with the counters of
    :class:`apex_tpu.transformer.moe.DroplessExperts` (``None`` or an empty
    dict with no expert layer), under
    :class:`apex_tpu.amp.MixedPrecisionOptimizer` ``mp_opt``: the dynamic
    loss scale, and a step skipped when the experts' buffer could not hold
    every assignment. The trainers jit it with ``params`` and ``opt_state``
    donated. ``metrics["moe"]`` holds the counters."""

    def train_step(params, opt_state, tokens, targets):
        scale = opt_state.scaler.loss_scale

        def scaled(p):
            loss, stats = model.loss(p, tokens, targets)
            return loss * scale, (loss, stats)

        (_, (loss, stats)), grads = jax.value_and_grad(
            scaled, has_aux=True)(params)
        stats = stats or {}            # no expert layer, no counters
        # an assignment the buffer could not hold is never lost in
        # silence: the step is skipped, as one with an overflowed gradient
        overflowed = jnp.sum(stats.get("overflow", 0.0)) > 0
        of_grads = []

        def skip_too(found_inf):
            of_grads.append(found_inf)
            return found_inf | overflowed

        new_params, new_state, metrics = mp_opt.apply_gradients(
            opt_state, params, grads, found_inf_reducer=skip_too)
        # ... but a full buffer says nothing of the loss scale: only an
        # overflowed gradient moves it
        spare = overflowed & ~of_grads[0]
        scaler = jax.tree.map(lambda old, new: jnp.where(spare, old, new),
                              opt_state.scaler, new_state.scaler)
        metrics["loss_scale"] = scaler.loss_scale
        metrics["moe"] = stats
        return new_params, new_state._replace(scaler=scaler), loss, metrics

    return train_step

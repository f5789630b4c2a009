"""amp frontend — initialize, mixed-precision optimizer, train state.

The TPU-native re-design of apex.amp's user surface:

- ``initialize(params, optimizer, opt_level=..., **overrides)`` mirrors
  ``apex.amp.initialize`` (reference: apex/amp/frontend.py:195-358 +
  _initialize.py:145-263): casts params per policy, wraps the optimizer with
  master weights + loss scaling + overflow skip.
- ``MixedPrecisionOptimizer`` replaces the reference's in-place optimizer
  surgery (_process_optimizer.py:321-489: ``_amp_stash`` master clones, patched
  ``step``/``zero_grad``, pre/post-backward hooks). In functional JAX all of
  that state is an explicit pytree and "patching step" is a ``lax.cond``.
- ``AmpTrainState`` is the convenience bundle (flax TrainState analog) used by
  the examples.

What has no analog and why: O1's namespace monkey-patching
(apex/amp/amp.py:68-177) casts call sites at runtime; under tracing, casts are
explicit in the model code, so O1 here means "params fp32, compute bf16" via
policy-aware modules (see apex_tpu.precision.Policy.op_dtype).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu import precision as _precision
from apex_tpu.amp.scaler import LossScaler
from apex_tpu.ops.multi_tensor import tree_l2norm, tree_scale
from apex_tpu.optimizers._common import ClassOptimizer, sharded_tree_sumsq


class MPOptState(NamedTuple):
    """Optimizer + amp carried state.

    ``master`` holds fp32 master weights when the policy asks for them
    (the ``_amp_stash`` fp32_from_fp16 groups of _process_optimizer.py:28-90);
    otherwise None. ``inner`` is the wrapped transform's state, always built
    over the fp32 view of params. ``scaler`` is the loss-scale state machine.

    Under ``zero_axis`` (the ZeRO path, contrib distributed_fused_adam.py
    semantics) ``master`` is ALWAYS present and holds this rank's 1-D fp32
    chunk tree (1/n of every leaf); ``inner`` is built over the chunks, so
    the whole optimizer footprint is 1/n per rank.

    ``residual`` (None unless ``reduce_dtype`` arms the quantized grad
    reduce-scatter) is the error-feedback state riding the sharded trees:
    ``{"err": <tree of flat fp32 leaves in the chunk layout — this rank's
    send error per destination chunk, concatenated>[, "key": <per-rank
    PRNG key when stochastic rounding is armed>]}``. Like masters and
    moments it is per-rank state behind the universal chunk specs, and an
    overflow-skipped step leaves it bit-identical on every rank.
    """

    inner: Any
    master: Any
    scaler: LossScaler
    residual: Any = None


class Zero3Setup(NamedTuple):
    """Host-side wiring bundle for fully-sharded (ZeRO-3) training, built
    by :meth:`MixedPrecisionOptimizer.zero3_init`.

    ``params`` is the persistent working-param CHUNK tree (each leaf this
    rank's 1/n slice, in the model dtype): the bf16 params are never
    materialized whole — layers all-gather just-in-time inside the layer
    loop (models/_transformer.run_layers ``chunk_meta``) and free after
    use. ``param_specs``/``state_specs`` are the shard_map in/out specs for
    the chunk trees; ``meta`` (optimizers.distributed.ChunkedMeta) carries
    the static local full shapes the JIT gathers rebuild."""

    params: Any
    param_specs: Any
    opt_state: Any
    state_specs: Any
    meta: Any


def _spec_axis_names(entry):
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)


def _canon_gather_dtype(dt):
    if dt is None:
        return None
    if isinstance(dt, str):
        low = dt.lower()
        if low in ("bf16", "bfloat16"):
            return jnp.dtype(jnp.bfloat16)
        if low in ("e5m2", "fp8", "float8_e5m2"):
            # the reference's e5m2-compressed allgather spelling: a bare
            # cast-and-gather at 1 B/elem (no scales — the float8 dynamic
            # range carries the value; use "int8" for the scaled wire)
            return jnp.dtype(jnp.float8_e5m2)
        if low == "int8":
            # quantized param gather: per-chunk fp32 scale side-channel,
            # decode after the collective (parallel/quantize.py;
            # optimizers.distributed.gather_leaf routes on the int dtype)
            return jnp.dtype(jnp.int8)
    canon = jnp.dtype(dt)
    if jnp.issubdtype(canon, jnp.integer) and canon != jnp.dtype(jnp.int8):
        # the only integer wire is the quantized int8 path — a wider int
        # would silently route through the 8-bit encode (gather_leaf
        # dispatches on integer-ness), delivering less precision than
        # the name promises
        raise ValueError(
            f"unsupported integer gather_dtype {dt!r}: the quantized "
            f"param-gather wire is 'int8' only (parallel/quantize.py); "
            f"use 'int8', 'bf16', or a float dtype")
    return canon


def _scaler_from_policy(policy: _precision.Policy, **scaler_kwargs) -> LossScaler:
    return LossScaler.create(loss_scale=policy.loss_scale, **scaler_kwargs)


class MixedPrecisionOptimizer:
    """Wraps an optax transform with amp semantics.

    Per step (cf. the reference's scale_loss exit path, handle.py:107-154, and
    patched step, _process_optimizer.py:353-364):

    1. unscale grads by 1/loss_scale into fp32, detecting non-finites;
    2. all-reduce of found_inf is the caller's job when running under a mesh
       (see apex_tpu.transformer.amp.MeshGradScaler);
    3. ``lax.cond(found_inf)``: skip (state unchanged) or apply the inner
       update to the fp32 master params;
    4. cast masters back to the model dtypes (multi_tensor_scale copy-out,
       _process_optimizer.py:14-25);
    5. scaler.update(found_inf).

    ``zero_axis`` switches steps 3-4 to the ZeRO path (the first-class
    spelling of ``optimizers.distributed``'s contrib
    DistributedFusedAdam/LAMB math): masters + inner state live as 1/n
    fp32 chunks, the grads arrive UNREDUCED over that axis (psum_scatter
    performs the reduction), and the updated params come back through a
    (optionally bf16-compressed) all-gather. See :meth:`zero_init`.
    """

    def __init__(
        self,
        optimizer: Union[optax.GradientTransformation, ClassOptimizer],
        policy: _precision.Policy,
        log_grad_norm: bool = False,
        log_group_norms: bool = False,
        zero_axis: Optional[str] = None,
        zero_level: int = 2,
        gather_dtype: Optional[Any] = None,
        reduce_dtype: Optional[str] = None,
        stochastic_rounding: bool = False,
        stacked_keys: Tuple[str, ...] = ("layers",),
        **scaler_kwargs,
    ):
        self.inner = (
            optimizer.transform if isinstance(optimizer, ClassOptimizer) else optimizer
        )
        self.policy = policy
        #: mesh axis the fp32 masters + inner optimizer state are ZeRO-
        #: sharded over (optimizers/distributed.py math: psum_scatter of
        #: the UNREDUCED grads is the data-parallel reduction, then a
        #: sharded inner step over 1/n chunks, then an all-gather of the
        #: updated params). init/apply_gradients must then run inside
        #: shard_map binding the axis — see :meth:`zero_init`. At levels
        #: 1/2 params SHARDED over the axis (MoE experts with
        #: ``moe_expert_axis`` == the zero axis) compose: their masters
        #: and moments stay the local expert shard (already 1/n of the
        #: leaf — Xu et al.'s weight-update sharding per parameter group),
        #: their grads skip the psum_scatter (the all_to_all transpose
        #: already summed every shard's cotangents) but keep the 1/n
        #: averaging, and no post-update gather touches them. Level 3
        #: still requires every param replicated over the axis (the chunk
        #: drive has no expert-shard story).
        self.zero_axis = zero_axis
        #: bool tree over the model params (True on leaves SHARDED over
        #: ``zero_axis`` — expert leaves); None until the ZeRO wiring
        #: (``zero_abstract_state``/``zero_init``) reads the param specs,
        #: which also fills ``_zero_expert_specs`` (local shape -> the
        #: param's own PartitionSpec, for the sharded state's out-specs).
        self._zero_sharded = None
        self._zero_expert_specs = None
        #: ZeRO stage under ``zero_axis``. 1/2 (one implementation here:
        #: masters AND moments always shard together) keep the bf16 working
        #: params replicated and all-gather them after every update. 3
        #: shards the *model* too: the working params persist as chunk
        #: trees (see :meth:`zero3_init`), each layer's weights are
        #: all-gathered just-in-time inside the layer loop (and re-gathered
        #: in backward via per-layer remat), grads arrive as per-layer
        #: reduce-scattered chunks (the JIT gather's AD transpose), and
        #: ``apply_gradients`` skips the post-update bulk gather entirely —
        #: the updated chunks ARE the persistent state.
        self.zero_level = int(zero_level)
        if self.zero_level not in (1, 2, 3):
            raise ValueError(f"zero_level must be 1, 2 or 3, got {zero_level}")
        if self.zero_level >= 3 and zero_axis is None:
            raise ValueError("zero_level=3 requires zero_axis (the mesh axis "
                             "the params shard over)")
        #: top-level param-dict keys holding scan-stacked layer trees
        #: (leading num_layers dim): under ``zero_level=3`` these chunk
        #: PER ROW — ``(L, ...) -> (L, k)`` — so one layer gathers at a
        #: time (optimizers.distributed.local_chunk_stacked).
        self.stacked_keys = tuple(stacked_keys)
        #: wire dtype of the updated-param all-gather under ``zero_axis``
        #: (the reference's e5m2-compressed allgather knob,
        #: distributed_fused_adam.py:64): "bf16" halves the gather bytes.
        #: fp32 masters stay exact — only the broadcast payload is cast,
        #: so the params every rank sees are the bf16-rounded view of the
        #: masters (free under O2, opt-in precision trade elsewhere).
        self.gather_dtype = _canon_gather_dtype(gather_dtype)
        if self.gather_dtype is not None and zero_axis is None:
            raise ValueError("gather_dtype only applies with zero_axis set "
                             "(it is the ZeRO param-gather wire dtype)")
        if (self.gather_dtype is not None and self.zero_level >= 3
                and jnp.issubdtype(self.gather_dtype, jnp.integer)):
            raise ValueError(
                "gather_dtype='int8' does not compose with zero_level=3: "
                "the ZeRO-3 per-layer gathers sit INSIDE the differentiated "
                "region and the int8 encode's round() would zero the "
                "gradients flowing through its AD transpose — quantize the "
                "level-1/2 post-update gather, or use 'bf16' for the JIT "
                "gathers")
        #: wire dtype of the GRADIENT reduce-scatter under ``zero_axis``
        #: ("int8" | "e5m2"): the fp32 psum_scatter becomes the quantized
        #: all_to_all decode-then-accumulate pair (parallel/quantize.py) —
        #: 1 B/elem on the wire plus a tiny fp32 per-chunk scale
        #: side-channel — with a sender-side error-feedback residual
        #: carried in :class:`MPOptState.residual` so quantization errors
        #: telescope instead of accumulating. The decode-accumulate and
        #: the /n averaging stay exact fp32. Memory note: the residual is
        #: per-rank fp32 state at the FULL (padded) leaf size — the
        #: standard EF/1-bit-Adam trade of state bytes for wire bytes;
        #: arm it when the interconnect, not HBM, is the bottleneck.
        from apex_tpu.parallel.quantize import canon_wire_dtype

        self.reduce_dtype = canon_wire_dtype(reduce_dtype)
        if self.reduce_dtype is not None and zero_axis is None:
            raise ValueError("reduce_dtype only applies with zero_axis set "
                             "(it is the ZeRO grad reduce-scatter wire "
                             "dtype)")
        if self.reduce_dtype is not None and self.zero_level >= 3:
            raise ValueError(
                "reduce_dtype does not compose with zero_level=3 yet: the "
                "ZeRO-3 grads reduce-scatter inside the per-layer gather "
                "transposes (optimizers.distributed.gather_leaf AD), not "
                "in apply_gradients — quantize at level 1/2, or use "
                "gather_dtype for the JIT gathers")
        #: int8-only uniform dither before the round (zero-mean per-element
        #: error) — an option on top of, not a substitute for, the
        #: error-feedback residual. Carries a per-rank PRNG key in
        #: ``MPOptState.residual["key"]``.
        self.stochastic_rounding = bool(stochastic_rounding)
        if self.stochastic_rounding and self.reduce_dtype != "int8":
            raise ValueError("stochastic_rounding requires "
                             "reduce_dtype='int8' (e5m2's ulp is value-"
                             "dependent; None has nothing to round)")
        #: when True, ``apply_gradients`` metrics include the global L2 norm
        #: of the unscaled grads — the journal hook (monitor/journal.py).
        #: Off by default: the extra tree reduction, while small next to the
        #: step's matmuls, must be opt-in so uninstrumented programs stay
        #: byte-identical.
        self.log_grad_norm = bool(log_grad_norm)
        #: when True, metrics also carry ``grad_norm_by_group`` — the L2
        #: norm per top-level parameter group (monitor/diagnose.py's
        #: overflow-forensics breakdown: a group whose norm is non-finite
        #: names the first non-finite layer from the journal alone). Same
        #: opt-in byte-identity contract as ``log_grad_norm``.
        self.log_group_norms = bool(log_group_norms)
        #: per-leaf tuples of mesh axes each param is SHARDED over (from
        #: the param_specs seen by ``zero_abstract_state``/``zero_init``):
        #: the norm metrics psum over ``zero_axis`` plus these, so
        #: tp/pp-hybrid shards count once and replicated leaves are not
        #: double-counted. None until the ZeRO wiring runs.
        self._zero_norm_axes = None
        self._scaler_kwargs = scaler_kwargs

    def _stacked_tree(self, params) -> Any:
        """Bool tree: True on leaves under a ``stacked_keys`` top-level
        entry (scan-stacked layer params) — only consulted at
        ``zero_level=3``, where those leaves chunk per row."""
        if self.zero_level < 3 or not isinstance(params, dict):
            return jax.tree.map(lambda _: False, params)
        return {k: jax.tree.map(lambda _: k in self.stacked_keys, v)
                for k, v in params.items()}

    def _sharded_tree(self, params) -> Any:
        """Bool tree: True on leaves SHARDED over the zero axis (expert
        leaves — recorded by the ZeRO wiring from the param specs); all
        False when no wiring ran (dense models, ad-hoc test harnesses)."""
        if self._zero_sharded is None:
            return jax.tree.map(lambda _: False, params)
        return self._zero_sharded

    def _chunk_tree(self, params, dtype=None):
        """This rank's per-leaf ZeRO state: a 1-D chunk of every
        zero-axis-REPLICATED leaf (stacked-aware at level 3); leaves
        SHARDED over the zero axis (expert params, levels 1/2) pass
        through as their local shard — already 1/n of the global leaf.
        Must run inside shard_map (or an axis_env trace) binding the
        zero axis."""
        from apex_tpu.optimizers.distributed import (
            local_chunk,
            local_chunk_stacked,
        )

        n = lax.axis_size(self.zero_axis)
        idx = lax.axis_index(self.zero_axis)

        def chunk(p, st, sh):
            if dtype is not None:
                p = p.astype(dtype)
            if sh:
                return p
            return (local_chunk_stacked if st else local_chunk)(p, n, idx)

        return jax.tree.map(chunk, params, self._stacked_tree(params),
                            self._sharded_tree(params))

    def _init_residual(self, model_params):
        """The error-feedback state for the quantized grad reduce-scatter
        (None when ``reduce_dtype`` is unset, so the state structure —
        and every ``reduce_dtype=None`` trace — is bit-identical to the
        unquantized path). Must run inside shard_map (or an axis_env
        trace) binding the zero axis, like :meth:`init`."""
        if self.reduce_dtype is None:
            return None
        from apex_tpu.optimizers.distributed import chunk_size

        n = lax.axis_size(self.zero_axis)
        # zero-axis-SHARDED leaves (MoE experts) have no reduce wire —
        # their grads never leave the rank — so they carry an EMPTY
        # residual leaf (structure preserved, zero bytes)
        err = jax.tree.map(
            lambda p, sh: jnp.zeros(
                (0,) if sh else (chunk_size(p.size, n) * n,), jnp.float32),
            model_params, self._sharded_tree(model_params))
        residual = {"err": err}
        if self.stochastic_rounding:
            # per-rank dither stream: senders round independently
            residual["key"] = jax.random.fold_in(
                jax.random.PRNGKey(0), lax.axis_index(self.zero_axis))
        return residual

    def zero3_shard(self, model_params) -> Any:
        """The persistent ZeRO-3 working-param chunk tree (model dtypes):
        stacked layer leaves become ``(L, k)`` per-row chunks, everything
        else a 1-D chunk. Traced counterpart of :meth:`zero3_init`'s
        placement — also usable directly under an ``axis_env`` trace
        (the evidence censuses)."""
        if self.zero_level < 3:
            raise ValueError("zero3_shard requires zero_level=3")
        return self._chunk_tree(model_params)

    def init(self, model_params) -> MPOptState:
        if self.zero_axis is not None:
            # ZeRO: keep only this rank's fp32 chunk of every leaf — the
            # chunks ARE the masters (exact fp32 regardless of
            # policy.master_weights: without them the sharded update could
            # not be applied without re-gathering params first). Must run
            # inside shard_map binding the axis (zero_init wraps this).
            # At zero_level=3 the masters mirror the working-param chunk
            # layout (per-row chunks for stacked layer leaves) so the
            # sharded update consumes the per-layer-scattered grads as-is.
            master = self._chunk_tree(model_params, dtype=jnp.float32)
            return MPOptState(
                inner=self.inner.init(master),
                master=master,
                scaler=_scaler_from_policy(self.policy, **self._scaler_kwargs),
                residual=self._init_residual(model_params),
            )
        if self.policy.master_weights:
            master = _precision.upcast_params(model_params)
        else:
            master = None
        inner = self.inner.init(master if master is not None else model_params)
        return MPOptState(
            inner=inner,
            master=master,
            scaler=_scaler_from_policy(self.policy, **self._scaler_kwargs),
        )

    def scale_loss(self, loss: jax.Array, state: MPOptState) -> jax.Array:
        """``with amp.scale_loss(...)`` enter path (handle.py:113)."""
        return state.scaler.scale(loss)

    def apply_gradients(
        self,
        state: MPOptState,
        model_params,
        scaled_grads,
        *,
        found_inf_reducer: Optional[Callable[[jax.Array], jax.Array]] = None,
        **update_kwargs,
    ):
        """Returns ``(new_model_params, new_state, metrics)``.

        ``scaled_grads`` are grads of the *scaled* loss w.r.t. model params.
        ``found_inf_reducer`` lets callers all-reduce the overflow flag across
        a mesh axis (the model-parallel reduction of
        apex/transformer/amp/grad_scaler.py:25-36).

        Under ``zero_axis``, ``scaled_grads`` must be the *unreduced*
        local-mean grads — the psum_scatter IS the data-axis reduction
        (same 1/n averaging factor as ``allreduce_gradients``); reduce over
        every OTHER grad axis (context/pipe ties) before calling. The
        overflow flag is pmax'd over the zero axis internally so the
        sharded state stays bit-identical on every rank through a skipped
        step; pass ``found_inf_reducer`` for the model/pipe axes as usual.

        Under ``zero_level=3`` both ``model_params`` and ``scaled_grads``
        are CHUNK trees: the grads arrive already reduce-scattered over
        the zero axis (each JIT layer gather's AD transpose is a per-layer
        psum_scatter — sum semantics, so the 1/n averaging still happens
        here), the sharded update runs directly on the chunks, and no
        gather follows: the new bf16 chunks (cast from the stepped
        masters) ARE the returned model params.
        """
        # the step's phases carry names (amp_unscale, optimizer_update,
        # amp_cast, amp_scale_update): a device trace is cut by them
        with jax.named_scope("amp_unscale"):
            grads32, found_inf = state.scaler.unscale(
                scaled_grads, out_dtype=jnp.float32)
            if self.zero_axis is not None:
                from apex_tpu.parallel import collectives as _coll

                # each rank unscaled a DIFFERENT local grad: the skip
                # decision must agree along the shard axis or the chunks
                # diverge
                found_inf = _coll.pmax(
                    found_inf.astype(jnp.float32), self.zero_axis) > 0
            if found_inf_reducer is not None:
                found_inf = found_inf_reducer(found_inf)

        if self.zero_axis is not None:
            if self.zero_level >= 3:
                return self._apply_zero3(
                    state, model_params, grads32, found_inf, update_kwargs)
            return self._apply_zero(
                state, model_params, grads32, found_inf, update_kwargs)

        step_params = state.master if state.master is not None else model_params

        def _do_step(operand):
            params, inner_state = operand
            updates, new_inner = self.inner.update(
                grads32, inner_state, params, **update_kwargs
            )
            new_params = optax.apply_updates(params, updates)
            return new_params, new_inner

        def _skip_step(operand):
            return operand

        with jax.named_scope("optimizer_update"):
            new_step_params, new_inner = jax.lax.cond(
                found_inf, _skip_step, _do_step, (step_params, state.inner)
            )

        if state.master is not None:
            # master -> model copy-out in the model dtypes.
            with jax.named_scope("amp_cast"):
                new_model = jax.tree.map(
                    lambda mp, p: mp.astype(p.dtype), new_step_params,
                    model_params)
            new_master = new_step_params
        else:
            new_model = new_step_params
            new_master = None

        with jax.named_scope("amp_scale_update"):
            new_scaler = state.scaler.update(found_inf)
        metrics = {
            "found_inf": found_inf,
            "loss_scale": new_scaler.loss_scale,
        }
        if self.log_grad_norm:
            # fp16_utils.FP16_Optimizer.step reports this unconditionally;
            # here it rides the metrics dict only when asked for
            metrics["grad_norm"] = tree_l2norm(grads32)
        if self.log_group_norms:
            from apex_tpu.monitor.diagnose import group_grad_norms

            metrics["grad_norm_by_group"] = group_grad_norms(grads32)
        return new_model, MPOptState(new_inner, new_master, new_scaler), metrics

    # -- the ZeRO step (contrib distributed_fused_adam.py:397-477 math) -----
    def _apply_zero(self, state, model_params, grads32, found_inf,
                    update_kwargs):
        """Sharded step: scatter → inner update on chunks → compressed
        gather. Collectives run UNCONDITIONALLY (uniform SPMD schedule —
        a collective inside a cond branch is a lowering hazard), so the
        overflow skip is a select back to the old chunks: the discarded
        update's non-finites never touch state, and since ``found_inf`` is
        axis-consistent every rank selects the same way — a skipped step
        leaves the sharded state bit-identical on every rank."""
        from apex_tpu.optimizers.distributed import gather_leaf, scatter_chunk

        axis = self.zero_axis
        n = lax.axis_size(axis)
        sharded = self._sharded_tree(grads32)
        new_residual = state.residual
        if self.reduce_dtype is not None:
            # quantized reduce-scatter (parallel/quantize.py): encoded
            # all_to_all + fp32 decode-then-accumulate — SUM semantics
            # identical to scatter_chunk, 1 B/elem on the wire. The
            # error-feedback residual compensates next step's payload;
            # its update is selected back on overflow below, with the
            # masters, so a skipped step leaves it bit-identical per rank.
            # Zero-axis-SHARDED leaves (MoE experts) have no wire at all:
            # their grads arrive complete (the dispatch all_to_all
            # transpose summed every shard's cotangents) and pass through
            # with their empty residual leaf untouched.
            from apex_tpu.parallel.quantize import quantized_reduce_scatter

            err_tree = state.residual["err"]
            key = state.residual.get("key")
            leaves, treedef = jax.tree.flatten(grads32)
            err_leaves = treedef.flatten_up_to(err_tree)
            sh_leaves = treedef.flatten_up_to(sharded)
            if key is not None:
                new_key, *subkeys = jax.random.split(key, len(leaves) + 1)
            else:
                new_key, subkeys = None, [None] * len(leaves)
            pairs = [(g, e) if sh else quantized_reduce_scatter(
                g, n, axis, self.reduce_dtype, residual=e, key=k)
                for g, e, k, sh in zip(leaves, err_leaves, subkeys,
                                       sh_leaves)]
            g_chunks = treedef.unflatten([c / n for c, _ in pairs])
            stepped_err = treedef.unflatten([e for _, e in pairs])
            new_residual = {"err": stepped_err}
            if new_key is not None:
                # the key advances unconditionally (it is a dither stream,
                # not model state): ranks stay in lockstep through skips
                new_residual["key"] = new_key
        else:
            # the scatter IS the data-axis gradient reduction; /n is the
            # same averaging factor allreduce_gradients applies. Sharded
            # (expert) leaves skip the scatter — their grad is already
            # this rank's complete shard — but keep the averaging factor
            # (the allreduce_gradients_by_spec convention).
            g_chunks = jax.tree.map(
                lambda g, sh: (g if sh else scatter_chunk(g, n, axis)) / n,
                grads32, sharded)

        keep = lambda new, old: jax.tree.map(  # noqa: E731
            lambda a, b: jnp.where(found_inf, b, a), new, old)
        with jax.named_scope("optimizer_update"):
            updates, stepped_inner = self.inner.update(
                g_chunks, state.inner, state.master, **update_kwargs)
            stepped_master = optax.apply_updates(state.master, updates)
            new_master = keep(stepped_master, state.master)
            new_inner = keep(stepped_inner, state.inner)
        if self.reduce_dtype is not None:
            new_residual = dict(
                new_residual,
                err=keep(new_residual["err"], state.residual["err"]))

        # all-gather the updated params; with gather_dtype the payload is
        # compressed on the wire, then stored back in each param's dtype.
        # Sharded (expert) leaves never gather: the stepped local master
        # IS the new local shard — just the dtype copy-out.
        with jax.named_scope("amp_cast"):
            new_model = jax.tree.map(
                lambda c, p, sh: (c.astype(p.dtype) if sh else
                                  gather_leaf(c, p.shape, p.dtype, axis,
                                              gather_dtype=self.gather_dtype)),
                new_master, model_params, sharded)

        with jax.named_scope("amp_scale_update"):
            new_scaler = state.scaler.update(found_inf)
        metrics = {
            "found_inf": found_inf,
            "loss_scale": new_scaler.loss_scale,
        }
        if self.log_grad_norm:
            # norm of the REDUCED gradient, from this rank's chunks: the
            # per-leaf shard-psum (zero axis + the param's own sharded
            # axes) reproduces tree_l2norm on the full tree under hybrid
            # meshes too (chunk padding contributes exact zeros)
            metrics["grad_norm"] = jnp.sqrt(sharded_tree_sumsq(
                g_chunks, axis, self._zero_norm_axes))
        if self.log_group_norms:
            from apex_tpu.monitor.diagnose import group_grad_norms

            metrics["grad_norm_by_group"] = group_grad_norms(
                g_chunks, psum_axis=axis,
                extra_axes=self._zero_norm_axes)
        return (new_model,
                MPOptState(new_inner, new_master, new_scaler, new_residual),
                metrics)

    # -- the ZeRO-3 step: no scatter (grads arrive as chunks), no gather ----
    def _apply_zero3(self, state, param_chunks, grads32, found_inf,
                     update_kwargs):
        """Fully-sharded step: the grads were reduce-scattered layer by
        layer in the backward (gather transposes), so the update is pure
        per-chunk arithmetic — inner step on the fp32 master chunks,
        overflow select back to the old chunks (axis-consistent, so a
        skipped step leaves every rank's shard bit-identical), then the
        new working params are the bf16-cast of the new masters. Zero
        collectives: the PR-5 bulk post-update all-gather is gone —
        updated chunks are already the persistent state."""
        axis = self.zero_axis
        n = lax.axis_size(axis)
        # the gather transposes SUMMED over the axis; /n is the same
        # averaging factor allreduce_gradients applies
        g_chunks = jax.tree.map(lambda g: g / n, grads32)

        keep = lambda new, old: jax.tree.map(  # noqa: E731
            lambda a, b: jnp.where(found_inf, b, a), new, old)
        with jax.named_scope("optimizer_update"):
            updates, stepped_inner = self.inner.update(
                g_chunks, state.inner, state.master, **update_kwargs)
            stepped_master = optax.apply_updates(state.master, updates)
            new_master = keep(stepped_master, state.master)
            new_inner = keep(stepped_inner, state.inner)

        # master -> model copy-out in the model dtypes, chunk for chunk
        with jax.named_scope("amp_cast"):
            new_params = jax.tree.map(
                lambda m, c: m.astype(c.dtype), new_master, param_chunks)

        with jax.named_scope("amp_scale_update"):
            new_scaler = state.scaler.update(found_inf)
        metrics = {
            "found_inf": found_inf,
            "loss_scale": new_scaler.loss_scale,
        }
        if self.log_grad_norm:
            metrics["grad_norm"] = jnp.sqrt(sharded_tree_sumsq(
                g_chunks, axis, self._zero_norm_axes))
        if self.log_group_norms:
            from apex_tpu.monitor.diagnose import group_grad_norms

            metrics["grad_norm_by_group"] = group_grad_norms(
                g_chunks, psum_axis=axis,
                extra_axes=self._zero_norm_axes)
        return new_params, MPOptState(new_inner, new_master, new_scaler), metrics

    # -- ZeRO wiring helpers (host side) ------------------------------------
    def zero_abstract_state(self, model_params, mesh, param_specs=None):
        """Per-device ShapeDtypeStruct tree of the ZeRO :class:`MPOptState`.

        Built WITHOUT binding the mesh axes (the chicken-and-egg of
        shard_map out_specs): each leaf's local shape is derived from its
        PartitionSpec (sharded dims divide by their axis sizes), then the
        1-D fp32 chunk is 1/n of that, and the chunk tree is fed through
        the real ``inner.init`` under ``eval_shape`` so arbitrarily nested
        inner states come out with the right structure."""
        from apex_tpu.optimizers.distributed import chunk_size

        if self.zero_axis is None:
            raise ValueError("zero_abstract_state requires zero_axis")
        n = mesh.shape[self.zero_axis]
        leaves, treedef = jax.tree.flatten(model_params)
        if param_specs is None:
            spec_leaves = [None] * len(leaves)
        else:
            spec_leaves = jax.tree.leaves(
                param_specs, is_leaf=lambda x: isinstance(x, P))
            if len(spec_leaves) != len(leaves):
                raise ValueError(
                    f"param_specs tree has {len(spec_leaves)} specs for "
                    f"{len(leaves)} params")

        def leaf_struct(p, spec):
            """(state struct, sharded-over-zero-axis) for one param: the
            1-D fp32 chunk for zero-axis-REPLICATED leaves, the fp32
            LOCAL shard for zero-axis-sharded (expert) leaves — Xu et
            al.'s weight-update sharding applied per parameter group."""
            shape = list(p.shape)
            over_zero = False
            for d, entry in enumerate(spec or ()):
                for ax in _spec_axis_names(entry):
                    if ax == self.zero_axis:
                        over_zero = True
                    shape[d] //= mesh.shape[ax]
            if over_zero:
                if len(shape) < 2:
                    raise ValueError(
                        f"param of shape {tuple(p.shape)} is sharded over "
                        f"the zero axis {self.zero_axis!r} with a 1-D "
                        f"local shard: the sharded-state specs classify "
                        f"1-D leaves as chunks, so rank-1 expert leaves "
                        f"are unsupported — stack them (E, 1) or keep "
                        f"them replicated")
                return jax.ShapeDtypeStruct(tuple(shape), jnp.float32), True
            size = 1
            for s in shape:
                size *= s
            return (jax.ShapeDtypeStruct((chunk_size(size, n),),
                                         jnp.float32), False)

        def sharded_axes(spec):
            out = []
            for entry in (spec or ()):
                if entry is None:
                    continue
                for ax in (entry if isinstance(entry, (tuple, list))
                           else (entry,)):
                    if ax not in out:
                        out.append(ax)
            return tuple(out)

        self._zero_norm_axes = treedef.unflatten(
            [sharded_axes(s) for s in spec_leaves])
        structs, flags = zip(*[leaf_struct(p, s)
                               for p, s in zip(leaves, spec_leaves)])
        self._zero_sharded = treedef.unflatten(list(flags))
        expert_specs: dict = {}
        for st, sp, fl in zip(structs, spec_leaves, flags):
            if not fl:
                continue
            prev = expert_specs.get(st.shape)
            if prev is not None and prev != sp:
                raise ValueError(
                    f"two zero-axis-sharded params share the local shape "
                    f"{st.shape} but carry different specs ({prev} vs "
                    f"{sp}): the shape-keyed sharded-state specs cannot "
                    f"disambiguate them")
            expert_specs[st.shape] = sp
        self._zero_expert_specs = expert_specs
        chunks = treedef.unflatten(list(structs))
        scaler = _scaler_from_policy(self.policy, **self._scaler_kwargs)
        residual = None
        if self.reduce_dtype is not None:
            # error-feedback state: per-rank flat fp32 leaves in the chunk
            # layout (n chunks concatenated — this rank's send error per
            # destination), mirroring _init_residual exactly; sharded
            # (expert) leaves have no wire and carry an empty leaf
            residual = {"err": treedef.unflatten([
                jax.ShapeDtypeStruct((0,) if fl else (st.shape[0] * n,),
                                     jnp.float32)
                for st, fl in zip(structs, flags)])}
            if self.stochastic_rounding:
                residual["key"] = jax.ShapeDtypeStruct((2,), jnp.uint32)

        def fake_init(c):
            return MPOptState(inner=self.inner.init(c), master=c,
                              scaler=scaler)

        # residual structs attach AFTER eval_shape: they are already
        # abstract (ShapeDtypeStructs), not closure constants to trace
        return jax.eval_shape(fake_init, chunks)._replace(residual=residual)

    def zero_state_specs(self, state, mesh):
        """shard_map specs for a ZeRO :class:`MPOptState` (or its abstract
        shapes): chunk leaves (1-D) carry the universal per-device spec
        ``P(tuple(mesh.axis_names))`` — each device owns exactly its chunk,
        with no replication assumption over ANY axis, so chunks of model-
        and pipe-sharded params round-trip correctly too; scalars (step
        counters, the loss-scale machine) are replicated. Zero-axis-SHARDED
        (expert) leaves — whose masters/moments are the fp32 LOCAL shard,
        rank >= 2 by construction — carry their param's own PartitionSpec,
        matched by local shape (``zero_abstract_state`` records the
        table and rejects ambiguous shapes)."""
        from apex_tpu.optimizers.distributed import state_specs as _specs

        base = _specs(state, tuple(mesh.axis_names))
        expert = self._zero_expert_specs
        if not expert:
            return base
        return jax.tree.map(
            lambda x, sp: expert.get(
                tuple(getattr(x, "shape", ()) or ()), sp),
            state, base)

    def zero_init(self, model_params, mesh, param_specs):
        """Initialize the sharded state from host-side (global) params.

        Returns ``(opt_state, state_specs)``; thread ``state_specs``
        through the train step's shard_map in/out specs. ``param_specs``
        is the params' PartitionSpec tree (the same one the step uses).
        """
        if self.zero_level >= 3:
            raise ValueError("zero_level=3 shards the params themselves; "
                             "wire with zero3_init (returns the chunked "
                             "param tree + specs + gather metadata)")
        abstract = self.zero_abstract_state(model_params, mesh, param_specs)
        sspecs = self.zero_state_specs(abstract, mesh)
        init = jax.jit(jax.shard_map(
            self.init, mesh=mesh, in_specs=(param_specs,),
            out_specs=sspecs, check_vma=False))
        return init(model_params), sspecs

    # -- ZeRO-3 wiring (host side) ------------------------------------------
    def _zero3_local_shapes(self, model_params, mesh, param_specs):
        """Per-leaf LOCAL (per-device) full shapes: each dim divided by the
        sizes of the mesh axes its PartitionSpec shards it over — what a
        JIT gather must rebuild inside shard_map. Also validates that no
        param is sharded over the zero axis (the level-1/2 constraint,
        unchanged) and records ``_zero_norm_axes``."""
        leaves, treedef = jax.tree.flatten(model_params)
        if param_specs is None:
            spec_leaves = [None] * len(leaves)
        else:
            spec_leaves = jax.tree.leaves(
                param_specs, is_leaf=lambda x: isinstance(x, P))
            if len(spec_leaves) != len(leaves):
                raise ValueError(
                    f"param_specs tree has {len(spec_leaves)} specs for "
                    f"{len(leaves)} params")

        def local_shape(p, spec):
            shape = [int(d) for d in p.shape]
            for d, entry in enumerate(spec or ()):
                for ax in _spec_axis_names(entry):
                    if ax == self.zero_axis:
                        raise ValueError(
                            f"param of shape {tuple(p.shape)} is SHARDED "
                            f"over the zero axis {self.zero_axis!r} — "
                            f"zero_level=3 requires every param replicated "
                            f"over it (expert-axis-sharded MoE params "
                            f"compose at ZeRO levels 1/2 only: the chunk "
                            f"drive has no expert-shard gather story)")
                    if mesh is not None:
                        shape[d] //= mesh.shape[ax]
            return tuple(shape)

        def sharded_axes(spec):
            out = []
            for entry in (spec or ()):
                for ax in _spec_axis_names(entry):
                    if ax not in out:
                        out.append(ax)
            return tuple(out)

        self._zero_norm_axes = treedef.unflatten(
            [sharded_axes(s) for s in spec_leaves])
        shapes = treedef.unflatten(
            [local_shape(p, s) for p, s in zip(leaves, spec_leaves)])
        return shapes, treedef, spec_leaves

    def zero3_meta(self, model_params, mesh=None, param_specs=None):
        """The static gather metadata (optimizers.distributed.ChunkedMeta)
        for a ZeRO-3 chunk tree of ``model_params``: per-leaf LOCAL full
        ``ShapeDtypeStruct``s — the per-LAYER row shape for stacked layer
        leaves — plus the axis and wire dtype. Without ``mesh`` the global
        shapes are used (axis_env traces, serial censuses)."""
        shapes, treedef, _ = self._zero3_local_shapes(
            model_params, mesh, param_specs)
        return self._zero3_meta_from(
            model_params, shapes, self._stacked_tree(model_params))

    def _zero3_meta_from(self, model_params, shapes, stacked):
        """ChunkedMeta from precomputed local shapes (one traversal:
        zero3_init already holds them)."""
        from apex_tpu.optimizers.distributed import ChunkedMeta

        def struct(p, ls, st):
            return jax.ShapeDtypeStruct(tuple(ls[1:]) if st else tuple(ls),
                                        p.dtype)

        return ChunkedMeta(
            shapes=jax.tree.map(struct, model_params, shapes, stacked),
            axis=self.zero_axis,
            gather_dtype=self.gather_dtype)

    def zero3_init(self, model_params, mesh, param_specs) -> Zero3Setup:
        """Initialize fully-sharded training state from host-side (global)
        params: places the working-param chunk tree, the fp32 master
        chunks + inner optimizer state (same per-row layout), and returns
        the :class:`Zero3Setup` bundle the train-step builder consumes
        (transformer.amp.build_zero_train_step). The chunk specs carry no
        replication assumption over ANY axis — stacked leaves shard their
        leading (layer) dim exactly as the param spec does (the pipeline
        axis), their chunk dim over everything else — so TP/pipe-sharded
        params round-trip correctly."""
        from apex_tpu.optimizers.distributed import chunk_size

        if self.zero_level < 3:
            raise ValueError("zero3_init requires zero_level=3")
        n = mesh.shape[self.zero_axis]
        shapes, treedef, spec_leaves = self._zero3_local_shapes(
            model_params, mesh, param_specs)
        stacked = self._stacked_tree(model_params)
        meta = self._zero3_meta_from(model_params, shapes, stacked)

        def prod(xs):
            size = 1
            for s in xs:
                size *= s
            return size

        def chunk_struct(p, ls, st, dtype):
            if st:
                return jax.ShapeDtypeStruct(
                    (ls[0], chunk_size(prod(ls[1:]), n)), dtype)
            return jax.ShapeDtypeStruct((chunk_size(prod(ls), n),), dtype)

        master_structs = jax.tree.map(
            lambda p, ls, st: chunk_struct(p, ls, st, jnp.float32),
            model_params, shapes, stacked)

        universal = P(tuple(mesh.axis_names))

        def chunk_spec(spec, st):
            if not st:
                return universal
            dim0 = spec[0] if spec is not None and len(spec) else None
            d0_axes = _spec_axis_names(dim0)
            rest = tuple(a for a in mesh.axis_names if a not in d0_axes)
            return P(dim0, rest)

        st_leaves = [bool(s) for s in jax.tree.leaves(stacked)]
        chunk_specs = treedef.unflatten(
            [chunk_spec(s, st) for s, st in zip(spec_leaves, st_leaves)])
        stacked_specs = {chunk_spec(s, True) for s, st
                         in zip(spec_leaves, st_leaves) if st}
        if len(stacked_specs) > 1:
            raise ValueError(
                f"stacked layer leaves carry inconsistent leading-dim "
                f"specs {sorted(map(str, stacked_specs))}: the sharded "
                f"optimizer-state specs need one uniform (L, chunk) "
                f"placement")
        stacked_spec = (stacked_specs.pop() if stacked_specs
                        else P(None, tuple(mesh.axis_names)))

        scaler = _scaler_from_policy(self.policy, **self._scaler_kwargs)
        abstract_state = jax.eval_shape(
            lambda m: MPOptState(inner=self.inner.init(m), master=m,
                                 scaler=scaler),
            master_structs)
        # chunks are 1-D (or (L, chunk) for stacked leaves) BY CONSTRUCTION,
        # so rank alone classifies state leaves: scalars (step counters, the
        # scaler) replicate, everything else is a per-device shard
        state_specs = jax.tree.map(
            lambda x: (stacked_spec if getattr(x, "ndim", 0) == 2
                       else universal if getattr(x, "ndim", 0) == 1
                       else P()),
            abstract_state)

        init = jax.jit(jax.shard_map(
            lambda p: (self.zero3_shard(p), self.init(p)),
            mesh=mesh, in_specs=(param_specs,),
            out_specs=(chunk_specs, state_specs), check_vma=False))
        chunks, state = init(model_params)
        return Zero3Setup(params=chunks, param_specs=chunk_specs,
                          opt_state=state, state_specs=state_specs,
                          meta=meta)

    def zero3_materialize(self, setup: Zero3Setup, mesh, param_specs,
                          param_chunks=None):
        """Gather the full (global) params back from a chunk tree — for
        checkpointed-weight export, eval harnesses, and the equivalence
        tests. Host-side helper (one jitted shard_map); the TRAIN path
        never calls this — materializing the whole model is exactly what
        ZeRO-3 removes. Wire dtype is each leaf's own (exact round-trip)."""
        from apex_tpu.optimizers.distributed import (
            gather_leaf,
            gather_stacked_leaf,
        )

        chunks = setup.params if param_chunks is None else param_chunks
        stacked = self._stacked_tree(chunks)
        meta = setup.meta

        def gather_all(c_tree):
            return jax.tree.map(
                lambda c, s, st: (
                    gather_stacked_leaf(c, s.shape, s.dtype, self.zero_axis)
                    if st else
                    gather_leaf(c, s.shape, s.dtype, self.zero_axis)),
                c_tree, meta.shapes, stacked)

        fn = jax.jit(jax.shard_map(
            gather_all, mesh=mesh, in_specs=(setup.param_specs,),
            out_specs=param_specs, check_vma=False))
        return fn(chunks)

    # -- checkpointing (apex/amp/frontend.py:361-400) -----------------------
    def state_dict(self, state: MPOptState):
        return {"scaler": state.scaler.state_dict()}

    def load_state_dict(self, state: MPOptState, payload) -> MPOptState:
        return state._replace(scaler=state.scaler.load_state_dict(payload["scaler"]))


class AmpTrainState(struct.PyTreeNode):
    """Bundled train state: params + amp optimizer state + step counter.

    The functional analog of "model, optimizer = amp.initialize(...)" followed
    by a torch train loop; built by :func:`initialize`.
    """

    step: jax.Array
    params: Any
    opt_state: MPOptState
    apply_fn: Callable = struct.field(pytree_node=False)
    mp_optimizer: MixedPrecisionOptimizer = struct.field(pytree_node=False)

    @classmethod
    def create(cls, *, apply_fn, params, mp_optimizer):
        return cls(
            step=jnp.zeros([], jnp.int32),
            params=params,
            opt_state=mp_optimizer.init(params),
            apply_fn=apply_fn,
            mp_optimizer=mp_optimizer,
        )

    @property
    def scaler(self) -> LossScaler:
        return self.opt_state.scaler

    def scale_loss(self, loss):
        return self.mp_optimizer.scale_loss(loss, self.opt_state)

    def apply_gradients(self, scaled_grads, *, found_inf_reducer=None, **kw):
        new_params, new_opt, metrics = self.mp_optimizer.apply_gradients(
            self.opt_state,
            self.params,
            scaled_grads,
            found_inf_reducer=found_inf_reducer,
            **kw,
        )
        return (
            self.replace(step=self.step + 1, params=new_params, opt_state=new_opt),
            metrics,
        )


def initialize(
    params,
    optimizers=None,
    opt_level: str = "O1",
    *,
    apply_fn: Optional[Callable] = None,
    cast_model_type=None,
    keep_batchnorm_fp32=None,
    master_weights=None,
    loss_scale=None,
    min_loss_scale: Optional[float] = None,
    max_loss_scale: float = 2.0 ** 24,
    half_dtype=jnp.bfloat16,
    verbosity: int = 1,
):
    """TPU-native ``amp.initialize`` (reference: apex/amp/frontend.py:195-358).

    Args mirror the reference's keyword surface where meaningful.
    ``optimizers`` may be a single optax transform / ClassOptimizer, or None
    for inference-only use (the reference's optimizers=None path,
    _initialize.py:220-222).

    Returns:
      - with an optimizer and ``apply_fn``: an :class:`AmpTrainState`;
      - with an optimizer, no ``apply_fn``: ``(cast_params, mp_optimizer)``;
      - with ``optimizers=None``: ``(cast_params, policy)``.
    """
    policy = _precision.get_policy(
        opt_level,
        half_dtype=half_dtype,
        cast_model_type=cast_model_type,
        keep_batchnorm_fp32=keep_batchnorm_fp32,
        master_weights=master_weights,
        loss_scale=loss_scale,
    )
    if verbosity:
        from apex_tpu.utils.log_util import maybe_print

        maybe_print(
            f"apex_tpu.amp: opt_level={policy.opt_level} cast_model_type="
            f"{policy.cast_model_type} master_weights={policy.master_weights} "
            f"loss_scale={policy.loss_scale}",
            rank0=True,
        )

    # arm the O1-style function registries (amp.py:68-177's patch install)
    from apex_tpu.amp.functions import set_active_policy

    set_active_policy(policy)
    cast = _precision.cast_params(params, policy)
    if optimizers is None:
        if apply_fn is not None:
            raise ValueError(
                "apply_fn without an optimizer has nothing to train; call "
                "initialize(params, opt_level=...) for inference casting, or "
                "pass an optimizer to build an AmpTrainState."
            )
        return cast, policy

    mp_opt = MixedPrecisionOptimizer(
        optimizers,
        policy,
        min_loss_scale=min_loss_scale,
        max_loss_scale=max_loss_scale,
    )
    if apply_fn is not None:
        return AmpTrainState.create(apply_fn=apply_fn, params=cast, mp_optimizer=mp_opt)
    return cast, mp_opt

"""Mixture-of-experts FFN with expert parallelism (NEW capability — the
reference has none: SURVEY.md §2.3 lists expert parallel as absent).

Design (TPU-first, the GShard/Switch dense-dispatch recipe):

- **Routing**: softmax router over E experts, top-k gates, with the
  Switch-style load-balancing auxiliary loss and router z-loss. All
  routing math is dense einsums over one-hot dispatch/combine tensors —
  no gather/scatter, so XLA tiles everything onto the MXU and shapes stay
  static under jit.
- **Capacity**: each expert processes at most C = ceil(top_k · N · cf / E)
  tokens; over-capacity tokens fall through (their combine weight is 0),
  the standard Switch behavior. (:class:`DroplessExperts`, at the end of
  this module, is the layer that drops nothing.)
- **Expert parallelism**: experts shard over a mesh axis. Inside
  ``shard_map`` with tokens sharded on the *same* axis (the standard MoE
  mapping: the data shards are the expert shards),
  :meth:`MoEMLP.apply_expert_parallel` dispatches locally, exchanges
  token buckets with one ``lax.all_to_all`` on the expert dim, runs the
  local experts, and all_to_alls back — two collectives per layer, both
  riding ICI. This is the NCCL all-to-all pattern of DeepSpeed-MoE /
  Tutel expressed as a named-axis collective.

Serial ``apply`` and sharded ``apply_expert_parallel`` compute the same
function **when no tokens are dropped** (tests assert value and gradient
equivalence at ample capacity). Under congestion they diverge by design:
capacity is enforced per token shard in the parallel path (each shard caps
its contribution to every expert at C_local), while the serial path caps
globally — per-shard capacity is what keeps the all_to_all buckets static-
shaped, and is the standard behavior of sharded MoE implementations.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.monitor.comms import collective_scope as _comm
from apex_tpu.ops.gated_rows import gated_rows, rows_visited
from apex_tpu.transformer import tensor_parallel as tp

Params = Dict[str, Any]

#: every collective verb in this module runs under a ``comm:`` scope (the
#: lint comm-scope rule) so CommAccount books dispatch bytes per (verb,
#: axis, wire dtype) — the marker opts the file in even if imports change
LINT_COMM_SCOPE = True


def _pmean_value_local_grad(v: jax.Array, axis: str) -> jax.Array:
    """Cross-shard mean in the value, local-only gradient: returns
    ``pmean(v)`` but backpropagates the identity onto the local ``v`` —
    each shard's gradient covers its local tokens at full scale, exactly
    like the local-mean CE loss's gradient, so the standard data-parallel
    reduction (``allreduce_gradients_by_spec``: pmean replicated-param
    grads) recovers the full-batch gradient. Keeps the collective itself
    out of the backward graph (its transpose over-counts under
    ``check_vma=False``)."""
    with _comm("pmean", axis, v):
        bar = lax.pmean(lax.stop_gradient(v), axis)
    return v + (bar - lax.stop_gradient(v))


class MoEMLP:
    """Drop-in MoE replacement for the transformer FFN block.

    **This router drops tokens.** Each expert takes at most its capacity
    ``C``; an assignment over it is lost (its combine weight is 0, and
    ``dropped_fraction`` counts it). That keeps the ``(N, E, C)`` one-hot
    dispatch dense and static, which is what ``--moe-experts`` and
    ``examples/moe/`` train with. :class:`DroplessExperts`, below in this
    module, loses no assignment (sorted dispatch, grouped products over the
    rows filled) and is what ``models/instella.py`` trains with. The two
    share no code: this one's experts are biased GeLU MLPs run as two dense
    ``(E, C, d)`` einsums and its statistics are the softmax router's
    balance and z losses; that one's are gated SiLU experts run as ragged
    products over one sorted buffer, and its statistics are counters.

    Args:
      hidden_size / ffn_hidden_size: per-expert FFN dims.
      num_experts: E. Must divide by the expert-axis size when sharded.
      top_k: experts per token (1 = Switch, 2 = GShard default).
      capacity_factor: slack over the perfectly-balanced C.
      expert_axis: mesh axis name the expert dim shards over (``specs``).
      tp_axis: mesh axis name each expert's FFN shards over — Megatron
        column/row parallelism INSIDE every expert (fc1 splits the ffn
        dim, fc2 consumes the local shard; one identity-backward psum per
        layer, exactly the Row/ColumnParallelLinear pair), composing
        EP × TP for GPT-3-scale ffn widths.
      params_dtype: parameter dtype (router stays fp32 — routing logits
        are precision-sensitive, like vocab logits).
      dispatch_dtype: quantized wire dtype ("int8" | "e5m2") for the
        dispatch/combine ``all_to_all`` payloads — the encoded exchange of
        ``parallel/quantize.quantized_all_to_all``: 1 B/elem + a tiny fp32
        per-destination-block scale side-channel, backward re-quantized
        through the transposed exchange. No EF residual (activations are
        fresh every step — the quantize.py activation convention).
        ``None`` = exact wire (traces bit-identical to pre-knob).
    """

    def __init__(
        self,
        hidden_size: int,
        ffn_hidden_size: int,
        num_experts: int,
        top_k: int = 2,
        capacity_factor: float = 1.25,
        expert_axis: Optional[str] = None,
        tp_axis: Optional[str] = None,
        params_dtype: Any = jnp.float32,
        init_method=None,
        dispatch_dtype: Optional[str] = None,
    ):
        if top_k < 1 or top_k > num_experts:
            raise ValueError(f"top_k ({top_k}) must be in [1, {num_experts}]")
        self.hidden = hidden_size
        self.ffn = ffn_hidden_size
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.expert_axis = expert_axis
        self.tp_axis = tp_axis
        self.params_dtype = params_dtype
        self.init_method = init_method or tp.scaled_normal(0.02)
        from apex_tpu.parallel.quantize import canon_wire_dtype

        self.dispatch_dtype = canon_wire_dtype(dispatch_dtype)
        if self.dispatch_dtype is not None and expert_axis is None:
            raise ValueError(
                "dispatch_dtype requires expert_axis: the quantized wire "
                "rides the expert-parallel all_to_all dispatch/combine "
                "exchange — a serial MoE layer has no wire to quantize")

    # -- parameters ---------------------------------------------------------

    def init(self, key) -> Params:
        kr, k1, k2 = jax.random.split(key, 3)
        E, d, f = self.num_experts, self.hidden, self.ffn

        def per_expert(k, shape):
            return jax.vmap(lambda kk: self.init_method(kk, shape,
                                                        self.params_dtype))(
                jax.random.split(k, E))

        return {
            "router": {"kernel": self.init_method(kr, (d, E), jnp.float32)},
            "fc1": {"kernel": per_expert(k1, (d, f)),
                    "bias": jnp.zeros((E, f), self.params_dtype)},
            "fc2": {"kernel": per_expert(k2, (f, d)),
                    "bias": jnp.zeros((E, d), self.params_dtype)},
        }

    def specs(self) -> Params:
        ax, tx = self.expert_axis, self.tp_axis
        return {
            "router": {"kernel": P()},
            # fc1 column-parallel (split ffn out-dim), fc2 row-parallel
            # (split ffn in-dim); fc2 bias replicated over tp (added once,
            # after the reduction)
            "fc1": {"kernel": P(ax, None, tx), "bias": P(ax, tx)},
            "fc2": {"kernel": P(ax, tx, None), "bias": P(ax, None)},
        }

    # -- routing ------------------------------------------------------------

    def _capacity(self, n_tokens: int) -> int:
        return max(1, math.ceil(
            self.top_k * n_tokens * self.capacity_factor / self.num_experts))

    def _route(self, params: Params, h2d: jax.Array):
        """(N, d) → dispatch (N, E, C) bool, combine (N, E, C) float,
        aux losses. Dense one-hot formulation (GShard §3.2)."""
        E, C = self.num_experts, self._capacity(h2d.shape[0])
        logits = (h2d.astype(jnp.float32)
                  @ params["router"]["kernel"].astype(jnp.float32))  # (N, E)
        probs = jax.nn.softmax(logits, axis=-1)

        # top-k expert mask, built greedily so gate normalization matches
        # the k=1 Switch and k=2 GShard formulations
        gates = jnp.zeros_like(probs)
        masked = probs
        for _ in range(self.top_k):
            idx = jnp.argmax(masked, axis=-1)
            onehot = jax.nn.one_hot(idx, E, dtype=probs.dtype)
            gates = gates + onehot * probs
            masked = masked * (1.0 - onehot)
        sel = gates > 0  # (N, E) — the chosen experts

        # position of each token within its expert's buffer, in token order
        pos = jnp.cumsum(sel.astype(jnp.int32), axis=0) - 1  # (N, E)
        keep = sel & (pos < C)
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos, C), C,
                                dtype=probs.dtype)  # (N, E, C); C -> dropped
        dispatch = pos_oh * keep[..., None]
        if self.top_k == 1:
            # Switch (top-1): combine with the UNNORMALIZED router prob p_i —
            # p_i/p_i == 1 would starve the router of task-loss gradient
            # (one_hot(argmax) is non-differentiable), whereas scaling the
            # expert output by p_i is exactly how Switch Transformer routes
            # gradient to the router through the model loss.
            combine = dispatch * gates[..., None]
        else:
            # k>=2: normalize gates over the k *selections* (GShard combine);
            # a dropped expert's share is lost, NOT redistributed —
            # renormalizing over kept gates would silently amplify the
            # surviving expert's output ~2x under congestion
            denom = jnp.sum(gates, axis=-1, keepdims=True)
            combine = dispatch * (gates / jnp.maximum(denom, 1e-9))[..., None]

        # per-batch routing statistics; the losses combine them in
        # _aux_losses so the expert-parallel path can average stats across
        # shards FIRST (E*sum(me*ce) is nonlinear — pmean of per-shard
        # losses would be biased)
        stats = {
            "me": jnp.mean(probs, axis=0),  # mean router prob per expert
            "ce": jnp.mean(sel.astype(jnp.float32), axis=0) / self.top_k,
            "zsq": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
            # fraction of routing selections dropped by the capacity cap
            # (sel is exactly top_k per token, so the N*k denominator is
            # shard-constant and the cross-shard pmean in the EP path is
            # the exact global fraction) — the congestion observability
            # metric (VERDICT r3 ask #6)
            "dropped_frac": jnp.sum((sel & ~keep).astype(jnp.float32))
            / float(sel.shape[0] * self.top_k),
        }
        return dispatch, combine, stats

    def _aux_losses(self, stats) -> Dict[str, jax.Array]:
        """Switch load-balance loss E*sum(me*ce) + ST-MoE router z-loss,
        plus the dropped-selection fraction as a pure METRIC (not folded
        into the loss — GPTModel.aux_to_loss reads only the loss keys)."""
        return {
            "load_balancing_loss": self.num_experts * jnp.sum(
                stats["me"] * stats["ce"]),
            "router_z_loss": stats["zsq"],
            "dropped_fraction": lax.stop_gradient(stats["dropped_frac"]),
        }

    # -- expert compute -----------------------------------------------------

    def _experts(self, params: Params, x: jax.Array) -> jax.Array:
        """(E_local, C', d) → (E_local, C', d): per-expert FFN, batched as
        one einsum pair so all experts' GEMMs fuse into two MXU calls.

        With ``tp_axis`` the ffn dim is sharded (fc1 column-parallel, fc2
        row-parallel): the input rides the identity-forward/psum-backward
        ``copy_to`` (Megatron's f conjugate — each model rank consumes the
        same tokens but backpropagates only its ffn slice's partial
        cotangent, so without the backward psum every upstream gradient
        would be 1/tp short: the EP x TP backward bug ISSUE 15's
        equivalence suite caught) and the fc2 einsum's partial sums reduce
        through one identity-backward psum — the full Row/Column pair
        inside every expert."""
        dt = x.dtype
        if self.tp_axis is not None:
            x = tp.copy_to_tensor_model_parallel_region(x, self.tp_axis)
        h = jnp.einsum("ecd,edf->ecf", x,
                       params["fc1"]["kernel"].astype(dt))
        h = jax.nn.gelu(h + params["fc1"]["bias"].astype(dt)[:, None, :])
        out = jnp.einsum("ecf,efd->ecd", h,
                         params["fc2"]["kernel"].astype(dt))
        if self.tp_axis is not None:
            out = tp.reduce_from_tensor_model_parallel_region(
                out, self.tp_axis)
        return out + params["fc2"]["bias"].astype(dt)[:, None, :]

    # -- serial forward -----------------------------------------------------

    def apply(self, params: Params, h: jax.Array) -> Tuple[jax.Array, Dict]:
        """``(…, d) → (…, d)`` plus aux losses — all experts local."""
        with jax.named_scope("moe"):
            shape = h.shape
            h2d = h.reshape(-1, shape[-1])
            dispatch, combine, stats = self._route(params, h2d)
            xs = jnp.einsum("nec,nd->ecd", dispatch.astype(h2d.dtype), h2d)
            ys = self._experts(params, xs)
            out = jnp.einsum("nec,ecd->nd", combine.astype(h2d.dtype), ys)
            return out.reshape(shape), self._aux_losses(stats)

    # -- expert-parallel forward --------------------------------------------

    def _dispatch_exchange(self, x: jax.Array, *, split_axis: int,
                           concat_axis: int) -> jax.Array:
        """One dispatch/combine ``all_to_all`` over the expert axis, booked
        in CommAccount at its wire dtype: the exact fp32/bf16 exchange by
        default, the encoded 1 B/elem pair under ``dispatch_dtype``
        (parallel/quantize.quantized_all_to_all — same EQuARX-shaped
        machinery as the ZeRO grad wire, minus the residual)."""
        ax = self.expert_axis
        if self.dispatch_dtype is not None:
            from apex_tpu.parallel.quantize import quantized_all_to_all

            return quantized_all_to_all(
                x, ax, self.dispatch_dtype,
                split_axis=split_axis, concat_axis=concat_axis)
        with _comm("all_to_all", ax, x):
            return lax.all_to_all(x, ax, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)

    def apply_expert_parallel(self, params_local: Params,
                              h_local: jax.Array) -> Tuple[jax.Array, Dict]:
        """Run inside ``shard_map`` with tokens sharded over
        ``expert_axis`` (dim 0 of the flattened tokens) and ``params``
        sharded by :meth:`specs`. Each shard routes its local tokens to
        **all** experts, all_to_alls the buckets so shard ``i`` receives
        every shard's bucket for its local experts, runs them, and
        all_to_alls back. Aux losses are means over the full batch.

        Gradient convention — the standard data-parallel recipe of this
        codebase: compute the **local-mean** loss per shard (aux losses
        included; their stats helper backpropagates at local scale to
        match) and reduce gradients with ``allreduce_gradients_by_spec``:
        replicated params (router, attention, …) pmean over the data
        axes, while expert-sharded params skip the psum but still apply
        the 1/axis-size averaging factor (their AD gradient already sums
        all shards' cotangents through the all_to_all transpose). Do not
        differentiate through a hand-written ``lax.psum`` of the loss —
        its transpose over-counts by the axis size under
        ``check_vma=False``."""
        ax = self.expert_axis
        if ax is None:
            raise ValueError("expert_axis is required for expert parallelism")
        ep = lax.axis_size(ax)
        E = self.num_experts
        if E % ep:
            raise ValueError(f"num_experts ({E}) must divide by the "
                             f"{ax!r} axis size ({ep})")
        shape = h_local.shape
        h2d = h_local.reshape(-1, shape[-1])
        # router params are replicated; local routing over local tokens
        dispatch, combine, stats = self._route(params_local, h2d)
        xs = jnp.einsum("nec,nd->ecd", dispatch.astype(h2d.dtype), h2d)
        # exchange: split the expert dim across shards, collect every
        # shard's bucket for our experts along the capacity dim (booked in
        # CommAccount; encoded to 1 B/elem under dispatch_dtype)
        xs = self._dispatch_exchange(xs, split_axis=0, concat_axis=1)
        ys = self._experts(params_local, xs)  # (E/ep, ep*C, d)
        ys = self._dispatch_exchange(ys, split_axis=1, concat_axis=0)
        out = jnp.einsum("nec,ecd->nd", combine.astype(h2d.dtype), ys)
        # average the raw statistics across shards BEFORE combining — the
        # load-balance loss is bilinear in (me, ce), so averaging finished
        # per-shard losses would not equal the full-batch loss. The
        # collective itself sits under stop_gradient with the gradient
        # routed through the local term (value identical): under
        # shard_map(check_vma=False) the transpose of pmean over-counts by
        # the axis size, and each shard should own exactly its local
        # tokens' router gradient anyway (the caller psums router grads
        # like any replicated-param gradient).
        stats = {k: _pmean_value_local_grad(v, ax) for k, v in stats.items()}
        return out.reshape(shape), self._aux_losses(stats)

    # -- expert-sharded inference forward (the serving conjugate) -----------

    def apply_expert_sharded(self, params_local: Params,
                             h: jax.Array) -> jax.Array:
        """Inference forward with experts sharded over ``expert_axis`` and
        tokens REPLICATED across it — the serving decode mapping
        (apex_tpu/serve/engine.py): every rank holds the same per-slot
        token batch, so there is no token bucket to exchange; instead each
        rank routes ALL tokens with the replicated router (bit-identical
        routing everywhere, same global capacity as serial ``apply``),
        computes only its local experts' contributions, and one ``psum``
        over the expert axis combines them. Exactly serial ``apply``'s
        function — including its global capacity drops — with the combine
        sum distributed; per-tick top-k indices are data, not shapes, so
        the decode program's jit signature stays stable
        (``lint.trace.decode_recompile_hazards``).

        Inference-only (no aux, no gradient contract): training uses
        :meth:`apply_expert_parallel`, whose token-sharded all_to_all
        dispatch is the production path."""
        ax = self.expert_axis
        if ax is None:
            raise ValueError("expert_axis is required for expert-sharded "
                             "inference")
        ep = lax.axis_size(ax)
        E = self.num_experts
        if E % ep:
            raise ValueError(f"num_experts ({E}) must divide by the "
                             f"{ax!r} axis size ({ep})")
        e_local = E // ep
        shape = h.shape
        h2d = h.reshape(-1, shape[-1])
        dispatch, combine, _ = self._route(params_local, h2d)
        # this rank's expert slab: dispatch/combine columns and the local
        # expert weights address the same [idx*e_local, (idx+1)*e_local)
        # window of the global expert dim (specs() shards dim 0 over ax)
        e0 = lax.axis_index(ax) * e_local
        disp_l = lax.dynamic_slice_in_dim(dispatch, e0, e_local, axis=1)
        comb_l = lax.dynamic_slice_in_dim(combine, e0, e_local, axis=1)
        xs = jnp.einsum("nec,nd->ecd", disp_l.astype(h2d.dtype), h2d)
        ys = self._experts(params_local, xs)  # (e_local, C, d)
        out = jnp.einsum("nec,ecd->nd", comb_l.astype(h2d.dtype), ys)
        with _comm("psum", ax, out):
            out = lax.psum(out, ax)
        return out.reshape(shape)


# -- routed experts without dropped tokens ------------------------------------
#
# ``MoEMLP`` above keeps its shapes static with a capacity per expert and
# loses what goes over it. The layer below loses nothing: it sorts the
# assignments by expert, gathers their tokens into one buffer and runs the
# experts as grouped products over the rows actually filled
# (``lax.ragged_dot``, which XLA:TPU compiles to its grouped-matmul call and
# whose time follows the group sizes, not the buffer: a call takes the same
# 2.43 ms in a buffer of one, two and four times the rows filled, ``PERF.md``,
# Findings, PR 35), two forward: one over ``[gate | up]`` side by side, one
# over ``down``. The gated activation between them is
# ``ops/gated_rows.py``'s, kernels whose walk ends at the rows filled; an
# elementwise pass left to XLA is paid over the whole buffer.
#
# The rows are moved the same way: every mover below walks the rows that
# hold an assignment, ``MOVE_ROWS`` at a trip of a loop whose trip count is
# read from ``filled`` at run time, so its time follows the load as the
# products' does. Two orders of the same rows serve the two directions: the
# buffer's own (by expert, then token), in which a row is gathered from its
# token, and the token's (by token), in which the rows of one token lie side
# by side and are summed by shifted adds, no scatter-add.

#: rows a trip of a mover's loop moves (fewer where the buffer is smaller)
MOVE_ROWS = 2048


def _chunk(rows: int) -> int:
    """Rows a trip moves over a buffer of ``rows``."""
    return min(rows, MOVE_ROWS)


def _trips(filled: jax.Array, rows: int) -> jax.Array:
    """Trips of a mover's loop over a buffer of ``rows`` of which the first
    ``filled`` hold an assignment."""
    return (filled + _chunk(rows) - 1) // _chunk(rows)


def _window(i, rows: int) -> jax.Array:
    """Where trip ``i`` starts: the last window is moved back to end with
    the buffer (every mover writes a row the same whichever window it
    falls in, so rows moved twice are moved right)."""
    return jnp.minimum(i * _chunk(rows), rows - _chunk(rows))


def _zeros_here(shape, dtype, filled: jax.Array) -> jax.Array:
    """Zeros for a mover's loop to write into, made where the layer runs and
    under its scope. A constant made in a scanned layer is lifted out of
    the scan when its gradient is taken, and a loop that writes into what
    every layer shares first copies it (1.2 ms a buffer, under no scope);
    one the compiler can fold it makes again under the loop's name alone.
    ``filled`` is never negative, which only the run knows."""
    return jnp.broadcast_to((filled < 0).astype(dtype), shape)


def row_plan(tok: jax.Array, slots: jax.Array, filled: jax.Array) -> Dict:
    """The partial permutation between ``N`` tokens and ``C`` buffer rows,
    read from both sides, for :func:`spread_rows` and :func:`collect_rows`.
    ``tok`` ``(C,)``: the token of each buffer row; ``slots`` ``(N, k)``:
    the buffer row of each of a token's choices, ``C`` for none; the first
    ``filled`` rows hold an assignment. The token's side is one sort of the
    filled rows by token: ``by_token[j]`` is the buffer row that is ``j``-th
    in that order, ``token_of[j]`` its token (``N`` past the filled ones and
    in the ``reach`` entries of padding a window reads past its end), and
    ``head[n]`` where token ``n``'s rows start, ``C`` if it has none."""
    rows, (n, k) = tok.shape[0], slots.shape
    key = jnp.where(jnp.arange(rows) < filled, tok, n).astype(jnp.int32)
    token_of, by_token = lax.sort(
        (key, jnp.arange(rows, dtype=jnp.int32)), num_keys=2, is_stable=False)
    held = jnp.sum(slots < rows, axis=1, dtype=jnp.int32)
    head = jnp.where(held > 0, jnp.cumsum(held) - held, rows)
    return {"tok": tok.astype(jnp.int32), "filled": filled, "head": head,
            "by_token": jnp.pad(by_token, (0, k)),
            "token_of": jnp.pad(token_of, (0, k), constant_values=n)}


def _gather_rows(src, plan, weights=None, other=None):
    """``(N, d)`` to ``(C, d)``: buffer row ``r`` is ``src[tok[r]]`` for
    the filled rows, zero past the last trip. With ``weights`` ``(C,)`` and
    ``other`` ``(C, d)`` a filled row is scaled by its weight in float32,
    and the row's product with ``other``'s row comes back beside it
    (``collect_rows``' two gradients, from one visit of the row)."""
    tok, filled = plan["tok"], plan["filled"]
    rows, d = tok.shape[0], src.shape[1]
    chunk = _chunk(rows)

    def move(i, carry):
        out, dots = carry
        lo = _window(i, rows)
        got = jnp.take(src, lax.dynamic_slice(tok, (lo,), (chunk,)), axis=0,
                       mode="clip")
        if weights is not None:
            live = lo + jnp.arange(chunk) < filled
            got32 = got.astype(jnp.float32)
            theirs = lax.dynamic_slice(other, (lo, 0), (chunk, d))
            dots = lax.dynamic_update_slice(dots, jnp.where(live, jnp.sum(
                got32 * theirs.astype(jnp.float32), axis=1), 0.0), (lo,))
            w = lax.dynamic_slice(weights, (lo,), (chunk,))
            got = jnp.where(live[:, None], got32 * w[:, None],
                            0.0).astype(src.dtype)
        return lax.dynamic_update_slice(out, got, (lo, 0)), dots

    return lax.fori_loop(
        0, _trips(filled, rows), move,
        (_zeros_here((rows, d), src.dtype, filled),
         _zeros_here((rows,), jnp.float32, filled)))


def _sum_rows(buf, plan, weights=None):
    """``(C, d)`` to ``(N, d)``: token ``n`` gets the sum of the filled
    rows that are its own, each times its weight if ``weights`` ``(C,)`` is
    given (in float32, rounded to the buffer's type as a row of its own,
    then summed in float32 and rounded once). The rows are visited in the
    token's order, so a token's rows are a run of at most ``reach`` and one
    pass of shifted adds sums every run at its head; a token then reads its
    head, or the zero row past the last."""
    by_token, token_of, head = plan["by_token"], plan["token_of"], plan["head"]
    rows, d = plan["tok"].shape[0], buf.shape[1]
    chunk, reach = _chunk(rows), by_token.shape[0] - rows

    def move(i, heads):
        lo = _window(i, rows)
        idx = lax.dynamic_slice(by_token, (lo,), (chunk + reach,))
        t = lax.dynamic_slice(token_of, (lo,), (chunk + reach,))
        got = jnp.take(buf, idx, axis=0, mode="clip")
        if weights is not None:
            w = jnp.take(weights, idx, mode="clip")
            got = (got.astype(jnp.float32) * w[:, None]).astype(buf.dtype)
        acc = got[:chunk].astype(jnp.float32)
        for s in range(1, reach):
            acc += jnp.where((t[s:s + chunk] == t[:chunk])[:, None],
                             got[s:s + chunk].astype(jnp.float32), 0.0)
        return lax.dynamic_update_slice(heads, acc.astype(buf.dtype), (lo, 0))

    heads = lax.fori_loop(
        0, _trips(plan["filled"], rows), move,
        _zeros_here((rows + 1, d), buf.dtype, plan["filled"]))
    return jnp.take(heads, head, axis=0, mode="clip")


@jax.custom_vjp
def spread_rows(x: jax.Array, plan: Dict) -> jax.Array:
    """``(N, d)`` to ``(C, d)``: buffer row ``r`` is ``x[tok[r]]``. The
    transpose of a gather by a permutation is the sum over its inverse, so
    the backward pass is :func:`collect_rows`' forward, unweighted."""
    return _gather_rows(x, plan)[0]


def _spread_fwd(x, plan):
    return _gather_rows(x, plan)[0], plan


def _spread_bwd(plan, g):
    return _sum_rows(g, plan), None


spread_rows.defvjp(_spread_fwd, _spread_bwd)


@jax.custom_vjp
def collect_rows(buf: jax.Array, weights: jax.Array, plan: Dict) -> jax.Array:
    """The transpose of :func:`spread_rows`, weighted: ``(C, d)`` back to
    ``(N, d)``, a token's rows each times its weight ``(C,)`` and summed."""
    return _sum_rows(buf, plan, weights)


def _collect_fwd(buf, weights, plan):
    return _sum_rows(buf, plan, weights), (buf, weights, plan)


def _collect_bwd(res, g):
    buf, weights, plan = res
    return (*_gather_rows(g, plan, weights, buf), None)


collect_rows.defvjp(_collect_fwd, _collect_bwd)


@jax.custom_vjp
def sort_with(key: jax.Array, values: jax.Array):
    """``(order, values[order])`` for the stable sort of ``key`` (told as
    the sort by key, then place: no two compare equal). The values ride
    through the sort, and their gradient rides back through the sort of
    ``order``, so neither way is a gather by the element."""
    at = jnp.arange(key.shape[0], dtype=jnp.int32)
    _, order, carried = lax.sort((key, at, values), num_keys=2,
                                 is_stable=False)
    return order, carried


def _sort_with_fwd(key, values):
    order, carried = sort_with.fun(key, values)
    return (order, carried), order


def _sort_with_bwd(order, g):
    return None, lax.sort((order, g[1]), num_keys=1, is_stable=False)[1]


sort_with.defvjp(_sort_with_fwd, _sort_with_bwd)


class DroplessExperts:
    """The routed experts of a DeepSeek-V3-style layer, for a layer that
    holds a share of them (one rank of an expert-parallel group), with no
    token dropped.

    The router scores every token against all ``num_experts`` with a sigmoid
    in float32, chooses the ``top_k`` largest of score + selection bias (the
    ``noaux_tc`` bias, a buffer: no gradient reaches it), and weights each
    choice by ``routed_scaling_factor * p_i / sum of the chosen p``
    (DeepSeek-V3 ``MoEGate``, arXiv:2412.19437 §2.1.2). The layer is told
    which experts it holds, ``first_held`` on, ``held`` of them, and returns
    only their terms of the sum: on one chip that partial result is the
    layer's output, under expert parallelism it is what the exchange would
    sum. Gated SiLU experts, no biases. Shared experts are the caller's (they
    see every token and need no routing).

    Shapes are static, so the assignments to held experts go into a buffer
    of ``BUFFER_FACTOR`` times their number under an even router (never more
    than the worst case, ``min(top_k, held)`` a token). Every assignment
    that fits is computed whatever the imbalance between experts, and the
    time of the products, of the activation between them and of the row
    movers follows the rows filled, not the buffer: on the chip no
    instruction between ``spread_rows`` and ``collect_rows`` touches a row
    past the tile that holds the last filled one (off it the activation is
    plain ``jax.numpy`` over every row), and what the rows past it hold is
    never read. What still passes over the whole buffer is the zeros each
    mover's loop starts from. If more assignments arrive than the buffer
    holds, ``stats["overflow"]`` counts them and the caller must skip the
    step (``pretrain_instella`` does, and the driver counts it failed):
    never a silent loss.

    The selection bias is a held buffer here: nothing moves it. (Moving it
    as ``noaux_tc`` does in training, 0.001 a step against each expert's
    load, was tried on the chip and changed nothing that could be measured:
    a share trained alone at a full learning rate swings thirty times
    faster, ``PERF.md``, Findings, PR 28.)

    ``apply`` returns ``(out, stats)`` with the counters ``assignments`` (to
    held experts), ``max_load_over_mean`` (the fullest held expert over the
    mean), ``overflow``, ``rows_moved`` (the rows of ``hidden_size`` the
    forward movers touched, from their own trip counts: about twice the
    assignments plus the tokens, whatever the buffer) and ``expert_rows``
    (the rows of the buffer the activation between the products visits, from
    the kernels' own bound: the rows filled to the tile; all of them where
    the ``jax.numpy`` form runs).
    """

    #: rows of the buffer over the assignments an even router makes to the
    #: held experts. On the chip a layer's held experts took up to 1.9
    #: times that within 56 steps of a seeded state (``PERF.md``, Findings,
    #: PR 28); four leaves twice that room, at 5% of the step
    BUFFER_FACTOR = 4

    def __init__(self, hidden_size: int, ffn_hidden_size: int,
                 num_experts: int, top_k: int, *,
                 held: Optional[int] = None, first_held: int = 0,
                 routed_scaling_factor: float = 1.0,
                 params_dtype: Any = jnp.float32, init_method=None,
                 bias_std: float = 0.0):
        held = num_experts if held is None else held
        if not 0 < top_k <= num_experts:
            raise ValueError(f"top_k ({top_k}) must be in [1, {num_experts}]")
        if first_held < 0 or first_held + held > num_experts or held < 1:
            raise ValueError(
                f"experts {first_held}..{first_held + held - 1} are not "
                f"among the {num_experts} the router scores")
        self.hidden, self.ffn = hidden_size, ffn_hidden_size
        self.num_experts, self.top_k = num_experts, top_k
        self.held, self.first_held = held, first_held
        self.scaling = routed_scaling_factor
        self.params_dtype = params_dtype
        self.init_method = init_method or tp.scaled_normal(0.02)
        self.bias_std = bias_std

    def init(self, key) -> Params:
        kr, kb, kg, ku, kd = jax.random.split(key, 5)
        d, f = self.hidden, self.ffn

        def per_expert(k, shape):
            return jax.vmap(lambda kk: self.init_method(
                kk, shape, self.params_dtype))(jax.random.split(k, self.held))

        return {
            "router": {
                "kernel": self.init_method(kr, (d, self.num_experts),
                                           self.params_dtype),
                "bias": (self.bias_std * jax.random.normal(
                    kb, (self.num_experts,))).astype(self.params_dtype)},
            "experts": {"gate": per_expert(kg, (d, f)),
                        "up": per_expert(ku, (d, f)),
                        "down": per_expert(kd, (f, d))},
        }

    def buffer_rows(self, n_tokens: int) -> int:
        worst = n_tokens * min(self.top_k, self.held)
        want = math.ceil(self.BUFFER_FACTOR * n_tokens * self.top_k
                         * self.held / self.num_experts)
        return min(worst, -(-want // 128) * 128)

    def route(self, router: Params, x2d: jax.Array):
        """``(chosen, weights)``, both ``(N, top_k)``: the experts each
        token chose, of all ``num_experts``, and their weights."""
        scores = jax.nn.sigmoid(jnp.matmul(
            x2d.astype(jnp.float32), router["kernel"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        bias = lax.stop_gradient(router["bias"].astype(jnp.float32))
        _, chosen = lax.top_k(scores + bias, self.top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weights = self.scaling * picked / (
            jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
        return chosen, weights

    def place(self, chosen: jax.Array, weights: jax.Array):
        """Where each assignment goes: ``(plan, wb, counts)`` for the
        ``(N, top_k)`` experts chosen and their weights. The assignments are
        sorted by held expert (those to experts held elsewhere sort last and
        stay out); ``plan`` is :func:`row_plan`'s, ``wb`` ``(C,)`` the
        weight of each buffer row, ``counts`` the assignments to each held
        expert, whether or not the buffer holds them all."""
        n, k = chosen.shape
        held, rows = self.held, self.buffer_rows(n)
        local = chosen.reshape(-1) - self.first_held
        key = jnp.where((local >= 0) & (local < held), local, held)
        order, carried = sort_with(key, weights.reshape(-1))
        # the inverse permutation: place of each assignment in the order
        rank = lax.sort((order, jnp.arange(n * k, dtype=jnp.int32)),
                        num_keys=1, is_stable=False)[1]
        counts = jnp.sum(
            key[:, None] == jnp.arange(held, dtype=key.dtype)[None],
            axis=0, dtype=jnp.int32)
        filled = jnp.minimum(jnp.sum(counts), rows)
        slots = jnp.where(rank < filled, rank, rows).astype(jnp.int32)
        plan = row_plan(order[:rows] // k, slots.reshape(n, k), filled)
        return plan, carried[:rows], counts

    def apply(self, params: Params, h: jax.Array) -> Tuple[jax.Array, Dict]:
        with jax.named_scope("moe"):
            shape = h.shape
            x = h.reshape(-1, shape[-1])
            n, rows = x.shape[0], self.buffer_rows(x.shape[0])
            with jax.named_scope("moe_route"):
                chosen, weights = self.route(params["router"], x)
            with jax.named_scope("moe_dispatch"):
                plan, wb, counts = self.place(chosen, weights)
                filled = plan["filled"]
                sizes = jnp.diff(jnp.minimum(jnp.cumsum(counts), rows),
                                 prepend=0)
                xb = spread_rows(x, plan)
            e = params["experts"]
            with jax.named_scope("moe_experts"):
                dt = x.dtype
                # the gate's and the up product as one, over [gate | up]:
                # the rows are read once and have one cotangent
                gu = lax.ragged_dot(xb, jnp.concatenate(
                    [e["gate"], e["up"]], axis=-1).astype(dt), sizes)
                # rows past the filled ones hold whatever the products and
                # the kernels between them left: nothing visits them
                yb = lax.ragged_dot(gated_rows(gu, filled),
                                    e["down"].astype(dt), sizes)
            with jax.named_scope("moe_combine"):
                out = collect_rows(yb, wb, plan)
            total = jnp.sum(counts)
            stats = {
                "assignments": total.astype(jnp.float32),
                "max_load_over_mean": jnp.max(counts) * self.held
                / jnp.maximum(total, 1).astype(jnp.float32),
                "overflow": (total - filled).astype(jnp.float32),
                "rows_moved": (
                    _trips(filled, rows) * (2 * _chunk(rows) + self.top_k)
                    + n).astype(jnp.float32),
                "expert_rows": rows_visited(filled, gu).astype(jnp.float32),
            }
            return out.reshape(shape), stats

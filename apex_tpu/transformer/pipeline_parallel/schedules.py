"""Pipeline-parallel schedules, single-program SPMD (reference:
apex/transformer/pipeline_parallel/schedules/).

The reference drives 1F1B with a host loop per rank: batched NCCL
isend/irecv between stages (p2p_communication.py:29-184), explicit
warmup/steady/cooldown phases (fwd_bwd_pipelining_without_interleaving.py:
155-345), and a ``torch.cuda.synchronize`` after every p2p batch — a
host-latency-bound design that eager CUDA forces.

The TPU-native schedule is **one jitted SPMD program** over the ``pipe`` mesh
axis:

- the stacked layer parameters are sharded on their leading (layer) dim over
  ``pipe`` — a device's shard *is* its stage;
- a ``lax.scan`` over M + S - 1 "ticks" rotates activations between stages
  with ``ppermute`` (the p2p ring), every stage computing every tick
  (uniform SPMD; fill/drain bubbles are the idle ticks, fraction
  (S-1)/(M+S-1), the reference's warmup+cooldown);
- **backward is the AD transpose of the forward scan** — reversing the scan
  and the ppermutes mechanically yields the drain-side pipeline the
  reference hand-writes as its cooldown phase. XLA sees forward+backward as
  one program and overlaps compute with the permute collectives (the
  side-stream overlap of p2p_communication, for free).

The embedding gather runs replicated across ``pipe`` (negligible FLOPs) with
its loss contribution attributed to stage 0; the LM head is **sharded over
``pipe``**: the last stage's finished activations are ``psum_scatter``-ed so
each stage receives a 1/S batch slice (1/S the comm volume of an all_gather;
the AD transpose — an all_gather — sums the slice cotangents back onto the
last stage), each stage computes the vocab projection on its slice, and the
spec-aware psum over ``pipe`` — the reference's
embedding-tie allreduce over the embedding group (parallel_state.py:165-184)
— combines both the tied-weight grads and the sharded head grads. Net
effect: head FLOPs match the serial model instead of being paid S times.

**Schedule as data** (JaxPP's MPMD framing, PAPERS.md): a schedule is a
per-rank list of ``{fwd, bwd, bwd_input, bwd_weight, idle}`` slots produced
by a per-schedule planner (:func:`plan_schedule`: gpipe, 1f1b,
1f1b-interleaved, zero-bubble) and interpreted by ONE executor — the
compiled drive (:func:`schedule_grads_fn`, a single ``lax.scan`` over the
plan's tick arrays) and the measured tick-by-tick drive
(:func:`traced_schedule_timeline`) share the same tick body and the same
plan arrays, so measurement and execution cannot diverge. The interleaved
ring below consumes the SAME decode (:func:`_ring_decode`) the interleaved
planner emits. The **zero-bubble** planner splits weight-grad from
input-grad compute (the ZB-H1 W/B split: ``jax.vjp`` w.r.t. the input only
vs w.r.t. the weights only, each rematerializing the stage forward) so the
``bwd_weight`` slots of early microbatches fill the cooldown where 1F1B
idles: per-rank idle slots drop from ``2(S-1)`` in ``2(M+S-1)`` ticks to
``S-1`` in ``3M+S-1`` ticks (the floor
``tracing.expected_bubble_fraction("zero-bubble", ...)`` pins).

Interleaved virtual pipelining (reference
fwd_bwd_pipelining_with_interleaving.py:25-333) is a **single ring** with
Megatron's chunk placement — stage ``s`` chunk ``c`` holds the serial layer
slab ``c*S + s`` (see :func:`interleave_stack`). At tick ``t`` stage ``s``
decodes its work unit ``k = t - s`` into (microbatch, chunk) as
``j = k mod S``, ``q = (k div S) mod vpp``, ``m = (k div S*vpp)*S + j``: the
timing algebra makes every ``ppermute`` deliver exactly the item the next
stage must process, including the wrap from the last stage's chunk ``q``
output to stage 0's chunk ``q+1`` input, with no idle tick in between. The
schedule therefore takes ``vpp*M + S - 1`` ticks where sequential per-chunk
rings take ``vpp*(M + S - 1)`` — the bubble shrinks by a factor of ``vpp``,
the entire point of the reference's interleaved schedule. Like the
reference, ``M`` must divide by ``S`` when ``vpp > 1``
(fwd_bwd_pipelining_with_interleaving.py's divisibility assertion).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.parallel.mesh import AXIS_PIPE
from apex_tpu.transformer.tensor_parallel.mappings import (
    reduce_from_tensor_model_parallel_region as _psum_identity_bwd,
)

# schedule-drive trace counter: bumped whenever a pipeline ring is traced
# (the compiled scan) or a traced tick drive runs — the observable the
# ``lint.trace.untimed_schedule_hazards`` tripwire joins against span
# output (a drive that traced while a tracer was armed but emitted no
# pipe spans is the census-only regression this counter exists to catch).
_RING_DRIVES = 0


def ring_drive_count() -> int:
    """Process-global count of pipeline-ring drives traced so far."""
    return _RING_DRIVES


def pipeline_specs(specs: Any, axis: str = AXIS_PIPE) -> Any:
    """Shard a stacked-layer PartitionSpec tree's leading (layer) dim over
    the pipeline axis — turning the scan stack into per-stage shards."""
    return jax.tree.map(
        lambda s: P(axis, *s[1:]),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def interleave_stack(layers: Any, pipeline_size: int, virtual_pipeline_size: int) -> Any:
    """Permute a stacked layer tree so that, sharded over ``pipe``, stage
    ``s``'s local chunk ``c`` holds serial layer slab ``c*S + s`` — the
    interleaved-schedule placement (reference parallel_state.py:104-111 +
    build_model's virtual chunks, schedules/common.py:52-65). Apply before
    ``shard_params``; training/checkpointing in the permuted order is
    self-consistent, and :func:`deinterleave_stack` restores serial order."""
    S, vpp = pipeline_size, virtual_pipeline_size
    L = jax.tree.leaves(layers)[0].shape[0]
    if L % (S * vpp):
        raise ValueError(f"num_layers ({L}) must divide by pp*vpp ({S * vpp})")
    per = L // (S * vpp)
    order = np.concatenate(
        [np.arange(per) + (c * S + s) * per for s in range(S) for c in range(vpp)]
    )
    return jax.tree.map(lambda x: x[order], layers)


def deinterleave_stack(layers: Any, pipeline_size: int, virtual_pipeline_size: int) -> Any:
    S, vpp = pipeline_size, virtual_pipeline_size
    L = jax.tree.leaves(layers)[0].shape[0]
    per = L // (S * vpp)
    order = np.concatenate(
        [np.arange(per) + (c * S + s) * per for s in range(S) for c in range(vpp)]
    )
    inv = np.argsort(order)
    return jax.tree.map(lambda x: x[inv], layers)


# ---------------------------------------------------------------------------
# schedule-as-data: slots, plans, planners
# ---------------------------------------------------------------------------

#: slot-kind codes, shared by the planners and both executor drives
K_IDLE, K_FWD, K_BWD, K_BWD_INPUT, K_BWD_WEIGHT = 0, 1, 2, 3, 4
KIND_CODES = {"idle": K_IDLE, "fwd": K_FWD, "bwd": K_BWD,
              "bwd_input": K_BWD_INPUT, "bwd_weight": K_BWD_WEIGHT}
KIND_NAMES = {v: k for k, v in KIND_CODES.items()}

#: the planner menu (canonical spellings; plan_schedule also accepts
#: "zerobubble"/"zb"/"1f1b-interleaved"/"vpp")
PLANNERS = ("gpipe", "1f1b", "interleaved", "zero-bubble")


@dataclasses.dataclass(frozen=True)
class Slot:
    """One tick of one rank's timeline: what the rank does and to which
    (microbatch, chunk) work unit. ``bwd`` is the combined input+weight
    gradient (gpipe/1f1b/interleaved); the zero-bubble planner splits it
    into ``bwd_input`` / ``bwd_weight``."""

    kind: str
    microbatch: int = -1
    chunk: int = 0


@dataclasses.dataclass(frozen=True)
class SchedulePlan:
    """A pipeline schedule as DATA: ``ranks[s][t]`` is rank ``s``'s slot at
    tick ``t``. Produced by :func:`plan_schedule`; interpreted by
    :func:`schedule_grads_fn` (compiled scan) and
    :func:`traced_schedule_timeline` (measured tick drive)."""

    schedule: str
    stages: int
    num_microbatches: int
    virtual_pipeline_size: int
    ranks: Tuple[Tuple[Slot, ...], ...]

    @property
    def ticks(self) -> int:
        return len(self.ranks[0])

    def idle_slots(self):
        """Per-rank idle (fill/drain) slot counts."""
        return [sum(1 for sl in row if sl.kind == "idle")
                for row in self.ranks]

    def bubble_fraction(self) -> float:
        """Analytic per-rank bubble fraction of THIS plan under uniform slot
        durations — counted from the slot data, so a planner and the
        closed-form ``tracing.expected_bubble_fraction`` floor can be pinned
        against each other (tests do)."""
        idles = self.idle_slots()
        return sum(i / self.ticks for i in idles) / self.stages

    def arrays(self):
        """The plan compiled to ``(T, S)`` int32 arrays — the single data
        source both executor drives index: ``kind``/``mb``/``chunk`` per
        (tick, rank), plus the wire-deposit decode ``dep_f``/``dep_b``
        (which microbatch's payload, if any, the forward/backward ppermute
        delivers into this rank's stash at this tick; -1 = none)."""
        T, S = self.ticks, self.stages
        kind = np.zeros((T, S), np.int32)
        mb = np.full((T, S), -1, np.int32)
        chunk = np.zeros((T, S), np.int32)
        dep_f = np.full((T, S), -1, np.int32)
        dep_b = np.full((T, S), -1, np.int32)
        for s in range(S):
            for t, sl in enumerate(self.ranks[s]):
                kind[t, s] = KIND_CODES[sl.kind]
                mb[t, s] = sl.microbatch
                chunk[t, s] = sl.chunk
        for t in range(1, T):
            for s in range(S):
                if s > 0 and kind[t - 1, s - 1] == K_FWD:
                    # rank s-1's fwd output rides the +1 ppermute and lands
                    # in rank s's h stash at the next tick (the last rank's
                    # output wraps to rank 0, which injects from the
                    # embedding instead — never deposited)
                    dep_f[t, s] = mb[t - 1, s - 1]
                if (s < S - 1
                        and kind[t - 1, s + 1] in (K_BWD, K_BWD_INPUT)):
                    # rank s+1's input-grad rides the -1 ppermute into rank
                    # s's cotangent stash (rank 0's input-grad is the
                    # embedding cotangent, accumulated locally, and its
                    # wire wrap to rank S-1 is never deposited)
                    dep_b[t, s] = mb[t - 1, s + 1]
        return {"kind": kind, "mb": mb, "chunk": chunk,
                "dep_f": dep_f, "dep_b": dep_b}


def _ring_decode(t: int, s: int, M: int, S: int, vpp: int):
    """The interleaved SPMD ring's work-unit decode at tick ``t`` on stage
    ``s`` — the ONE implementation shared by the compiled ring scan, the
    traced tick drive, and the interleaved planner (k = t - s; see the
    module docstring's timing algebra). Returns ``(live, m, q)``."""
    n_units = vpp * M
    k_raw = t - s
    k = min(max(k_raw, 0), n_units - 1)
    j = k % S
    q = (k // S) % vpp
    m = (k // (S * vpp)) * S + j
    return (0 <= k_raw < n_units), m, q


def _ring_plan_arrays(M: int, S: int, vpp: int):
    """(T_f, S) int32/bool arrays of the forward ring's decode — the scan
    xs of :func:`_pipeline_ring` and the traced drive's tick programs."""
    T = pipeline_tick_count(M, S, vpp)
    live = np.zeros((T, S), np.int32)
    m_arr = np.zeros((T, S), np.int32)
    q_arr = np.zeros((T, S), np.int32)
    for t in range(T):
        for s in range(S):
            lv, m, q = _ring_decode(t, s, M, S, vpp)
            live[t, s], m_arr[t, s], q_arr[t, s] = int(lv), m, q
    return {"live": live, "mb": m_arr, "chunk": q_arr}


def _greedy_plan(schedule: str, M: int, S: int) -> SchedulePlan:
    """Greedy lockstep-tick list scheduler over the pipeline dependency
    graph — each tick every rank picks its highest-priority eligible task
    (completions strictly earlier than the current tick). Priorities encode
    the schedules: gpipe = forwards first with backwards gated on the
    rank's full forward phase; 1f1b = input-grads first (the warmup /
    steady 1F1B / cooldown pattern emerges from the dependencies);
    zero-bubble = input-grads > forwards > weight-grads, so ``bwd_weight``
    slots of early microbatches fill what would be cooldown idles. The
    greedy plans meet the closed-form floors exactly (gpipe/1f1b:
    ``2(S-1)`` idles in ``2(M+S-1)`` ticks; zero-bubble: ``S-1`` idles in
    ``3M+S-1`` ticks — tests pin this)."""
    split = schedule == "zero-bubble"
    gpipe = schedule == "gpipe"
    fwd = [[None] * M for _ in range(S)]
    bwd = [[None] * M for _ in range(S)]
    wgt = [[None] * M for _ in range(S)]
    ranks: list = [[] for _ in range(S)]
    total = S * M * (3 if split else 2)
    done, t = 0, 0
    limit = 6 * (3 * M + S + 4)
    while done < total and t < limit:
        picks = []
        for s in range(S):
            def f_ok(m):
                return (fwd[s][m] is None
                        and (s == 0 or fwd[s - 1][m] is not None)
                        and (m == 0 or fwd[s][m - 1] is not None))

            def b_ok(m):
                if bwd[s][m] is not None or fwd[s][m] is None:
                    return False
                if gpipe and any(v is None for v in fwd[s]):
                    return False  # gpipe: all-forward phase first
                if s < S - 1 and bwd[s + 1][m] is None:
                    return False
                return m == 0 or bwd[s][m - 1] is not None

            def w_ok(m):
                return (split and wgt[s][m] is None
                        and bwd[s][m] is not None
                        and (m == 0 or wgt[s][m - 1] is not None))

            if gpipe:
                order = [("fwd", f_ok), ("bwd", b_ok)]
            elif split:
                order = [("bwd_input", b_ok), ("fwd", f_ok),
                         ("bwd_weight", w_ok)]
            else:
                order = [("bwd", b_ok), ("fwd", f_ok)]
            pick = None
            for kind, ok in order:
                ms = [m for m in range(M) if ok(m)]
                if ms:
                    pick = (kind, ms[0])
                    break
            picks.append(pick)
        for s, pick in enumerate(picks):
            if pick is None:
                ranks[s].append(Slot("idle"))
                continue
            kind, m = pick
            ranks[s].append(Slot(kind, m))
            table = {"fwd": fwd, "bwd": bwd, "bwd_input": bwd,
                     "bwd_weight": wgt}[kind]
            table[s][m] = t
            done += 1
        t += 1
    if done != total:
        raise RuntimeError(
            f"greedy planner wedged: {schedule} M={M} S={S} placed "
            f"{done}/{total} slots in {t} ticks")
    return SchedulePlan(schedule, S, M, 1,
                        tuple(tuple(r) for r in ranks))


def plan_schedule(schedule: str, num_microbatches: int, stages: int,
                  virtual_pipeline_size: int = 1) -> SchedulePlan:
    """Build a :class:`SchedulePlan` for one of :data:`PLANNERS`.

    ``gpipe``/``1f1b`` come from the greedy list scheduler (combined
    ``bwd`` slots); ``zero-bubble`` from the same scheduler with the W/B
    split; ``interleaved`` from :func:`_ring_decode` — the compiled ring's
    own algebra, forward ticks followed by the AD-transposed (mirrored)
    backward ticks, so the plan IS what the scan executes. Only
    ``interleaved`` accepts ``virtual_pipeline_size > 1``.
    """
    M, S, vpp = int(num_microbatches), int(stages), int(virtual_pipeline_size)
    if M <= 0 or S <= 0 or vpp <= 0:
        raise ValueError(f"need positive M/S/vpp, got {M}/{S}/{vpp}")
    name = schedule.lower().replace("_", "-")
    if name in ("zerobubble", "zb"):
        name = "zero-bubble"
    if name in ("1f1b-interleaved", "vpp"):
        name = "interleaved"
    if name not in PLANNERS:
        raise ValueError(f"unknown schedule {schedule!r}; known: {PLANNERS}")
    if name != "interleaved" and vpp != 1:
        raise ValueError(
            f"virtual_pipeline_size > 1 is the interleaved planner's knob; "
            f"{name!r} plans are vpp=1")
    if name == "interleaved":
        if vpp > 1 and M % S:
            raise ValueError(
                f"interleaved schedule needs num_microbatches ({M}) "
                f"divisible by pipeline size ({S}), as in the reference")
        T = pipeline_tick_count(M, S, vpp)
        ranks = []
        for s in range(S):
            row = []
            for t in range(T):
                lv, m, q = _ring_decode(t, s, M, S, vpp)
                row.append(Slot("fwd", m, q) if lv else Slot("idle"))
            # the AD transpose drives the same ticks mirrored in reverse
            for t in reversed(range(T)):
                lv, m, q = _ring_decode(t, s, M, S, vpp)
                row.append(Slot("bwd", m, q) if lv else Slot("idle"))
            ranks.append(tuple(row))
        return SchedulePlan(name, S, M, vpp, tuple(ranks))
    if S == 1:
        # no pipeline: M fwd slots then M bwd(+W) slots, no idles
        kinds = (["fwd"] * M + ["bwd_input"] * M + ["bwd_weight"] * M
                 if name == "zero-bubble" else ["fwd"] * M + ["bwd"] * M)
        mbs = (list(range(M)) * 3 if name == "zero-bubble"
               else list(range(M)) * 2)
        return SchedulePlan(name, 1, M, 1, (tuple(
            Slot(k, m) for k, m in zip(kinds, mbs)),))
    return _greedy_plan(name, M, S)


def prepare_pipelined_model(
    model: Any,
    params: Any,
    mesh: Any,
    *,
    num_microbatches: int,
    virtual_pipeline_size: int = 1,
    with_aux: bool = False,
):
    """The shared TP x PP setup every pipelined harness needs (reference:
    the build_model + _forward_backward_pipelining plumbing the Megatron
    test harnesses repeat, apex/transformer/pipeline_parallel/schedules/
    common.py:52-65 driven by run_pipeline_parallel_test.py): shard the
    layer-stack specs over the pipe axis, interleave virtual chunks,
    place the params on the mesh, and build the pipelined loss.

    Returns ``(specs, sharded_params, pipe_loss)`` where ``pipe_loss`` is
    ``pipelined_loss_fn``'s ``loss(rest_params, layers_local, batch,
    targets)``. Callers own the gradient/step assembly (which legitimately
    differs between harnesses); this factors the wiring that must NOT
    drift between them (__graft_entry__, benchmarks/gpt_scaling.py,
    benchmarks/gpt_1p3b_check.py).

    ``with_aux=True`` threads layer aux losses (MoE routers) through
    ``model.run_layers(..., return_aux=True)`` and ``model.aux_to_loss``.
    """
    from apex_tpu.parallel import mesh as mesh_lib
    from apex_tpu.transformer import tensor_parallel as tp_mod

    all_specs = model.specs()
    specs = dict(
        {k: v for k, v in all_specs.items() if k != "layers"},
        layers=pipeline_specs(all_specs["layers"]),
    )
    full = dict(params)
    if virtual_pipeline_size > 1:
        pp = mesh_lib.get_pipeline_model_parallel_world_size()
        full["layers"] = interleave_stack(
            full["layers"], pp, virtual_pipeline_size)
    sharded = tp_mod.shard_params(full, specs, mesh)
    if with_aux:
        run_layers = lambda lp, h: model.run_layers(lp, h, return_aux=True)  # noqa: E731
        aux_to_loss = model.aux_to_loss
    else:
        run_layers = lambda lp, h: model.run_layers(lp, h)  # noqa: E731
        aux_to_loss = None
    pipe_loss = pipelined_loss_fn(
        embed=model.embed,
        run_layers=run_layers,
        head_loss=lambda p, h, t: model.head(p, h, t),
        num_microbatches=num_microbatches,
        virtual_pipeline_size=virtual_pipeline_size,
        aux_to_loss=aux_to_loss,
    )
    return specs, sharded, pipe_loss


def pipeline_tick_count(
    num_microbatches: int, pipeline_size: int, virtual_pipeline_size: int = 1
) -> int:
    """Scan length of the interleaved SPMD ring: ``vpp*M + S - 1`` — every
    stage does its ``vpp*M`` real work units back-to-back after an ``s``-tick
    fill, vs ``vpp*(M + S - 1)`` for sequential per-chunk rings. The saved
    ``(vpp-1)*(S-1)`` ticks are the interleaving bubble win (reference:
    fwd_bwd_pipelining_with_interleaving.py:25-333)."""
    return virtual_pipeline_size * num_microbatches + pipeline_size - 1


def _pipeline_ring(
    run_stage: Callable[[Any, jax.Array], jax.Array],
    layers_local: Any,
    h_microbatches: jax.Array,  # (M, mb, ...) — replicated across pipe
    axis: str,
    vpp: int = 1,
) -> jax.Array:
    """Rotate M microbatches through the stage ring, through all ``vpp``
    local chunks per stage (interleaved schedule). Returns completed
    activations (M, mb, ...), valid on the last stage (garbage elsewhere).

    Work-unit decode at tick ``t`` on stage ``s`` (k = t - s):
    ``j = k mod S`` (microbatch within its group of S), ``q = (k div S) mod
    vpp`` (local chunk), ``r = k div (S*vpp)`` (group), microbatch
    ``m = r*S + j``. Stage s+1 processes unit k one tick after stage s
    emitted it, and the last stage's chunk-q output arrives at stage 0
    exactly when stage 0 is due to process (m, q+1) — one ppermute per tick
    moves every in-flight item, with finished items exiting the ring on the
    ticks when stage 0 injects fresh microbatches.
    """
    global _RING_DRIVES
    _RING_DRIVES += 1
    S = lax.axis_size(axis)
    s_idx = lax.axis_index(axis)
    M = h_microbatches.shape[0]
    if vpp > 1 and M % S:
        raise ValueError(
            f"interleaved schedule needs num_microbatches ({M}) divisible by "
            f"pipeline size ({S}), as in the reference"
        )
    n_ticks = pipeline_tick_count(M, S, vpp)
    # the schedule as DATA: the scan consumes the SAME per-tick decode the
    # interleaved planner emits (_ring_decode), as (T, S) arrays — one
    # source of truth for execution, the traced drive, and plan_schedule
    ring = _ring_plan_arrays(M, S, vpp)
    xs_live = jnp.asarray(ring["live"])
    xs_mb = jnp.asarray(ring["mb"])
    xs_chunk = jnp.asarray(ring["chunk"])

    n_local = jax.tree.leaves(layers_local)[0].shape[0]
    if n_local % vpp:
        raise ValueError(
            f"per-stage layer count ({n_local}) must divide by "
            f"virtual_pipeline_size ({vpp})"
        )
    per = n_local // vpp

    mb_shape = h_microbatches.shape[1:]
    out0 = jnp.zeros((M,) + mb_shape, h_microbatches.dtype)
    buf0 = jnp.zeros(mb_shape, h_microbatches.dtype)
    perm = [(i, (i + 1) % S) for i in range(S)]

    # probe whether run_stage emits per-chunk aux losses (MoE routers):
    # (h, aux_tree) return → accumulate aux over live ticks
    probe = jax.eval_shape(
        run_stage,
        jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((per,) + x.shape[1:], x.dtype),
            layers_local,
        ),
        jax.ShapeDtypeStruct(mb_shape, h_microbatches.dtype),
    )
    returns_tuple = isinstance(probe, tuple)
    # a dense model called with return_aux=True returns (h, None): unwrap
    # the tuple but don't treat it as aux-emitting
    with_aux = returns_tuple and probe[1] is not None
    aux0 = (
        jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32), probe[1])
        if with_aux else None
    )

    def tick(carry, xs):
        buf, out, aux_acc = carry
        row_live, row_mb, row_chunk = xs
        live = row_live[s_idx] > 0
        m = row_mb[s_idx]
        q = row_chunk[s_idx]
        inject = (s_idx == 0) & (q == 0)
        h_in = jnp.where(
            inject, lax.dynamic_index_in_dim(h_microbatches, m, 0, keepdims=False), buf
        )
        if vpp == 1:
            chunk = layers_local
        else:
            chunk = jax.tree.map(
                lambda x: lax.dynamic_slice_in_dim(x, q * per, per, axis=0),
                layers_local,
            )
        if with_aux:
            h_out, aux = run_stage(chunk, h_in)
            # fill/drain ticks process garbage activations; only live
            # ticks are real (microbatch, chunk) units, each processed
            # exactly once across the ring — masked sum = full-batch aux
            aux_acc = jax.tree.map(
                lambda a, v: a + jnp.where(live, v.astype(jnp.float32), 0.0),
                aux_acc, aux)
        elif returns_tuple:
            h_out, _ = run_stage(chunk, h_in)
        else:
            h_out = run_stage(chunk, h_in)
        finished = (s_idx == S - 1) & (q == vpp - 1) & live
        cur = lax.dynamic_index_in_dim(out, m, 0, keepdims=False)
        out = lax.dynamic_update_index_in_dim(
            out, jnp.where(finished, h_out, cur), m, 0
        )
        buf = lax.ppermute(h_out, axis, perm)
        return (buf, out, aux_acc), None

    (_, out, aux_sum), _ = lax.scan(
        tick, (buf0, out0, aux0), (xs_live, xs_mb, xs_chunk))
    return (out, aux_sum) if with_aux else out


def pipelined_loss_fn(
    *,
    embed: Callable[[Any, Any], jax.Array],
    run_layers: Callable[[Any, jax.Array], jax.Array],
    head_loss: Callable[[Any, jax.Array, Any], jax.Array],
    num_microbatches: int,
    axis: str = AXIS_PIPE,
    virtual_pipeline_size: int = 1,
    shard_head: bool = True,
    aux_to_loss: Optional[Callable[[Any], jax.Array]] = None,
) -> Callable:
    """Build ``loss(params, layers_local, batch, targets) -> scalar`` running
    the layer stack through the SPMD pipeline.

    Args:
      embed: ``(params, batch) -> (B, ...) activations`` (replicated work).
      run_layers: ``(layer_chunk_params, h) -> h`` applying a stage chunk —
        or ``-> (h, aux_tree)`` for layers that emit side losses (MoE
        routers: pass ``lambda lp, h: model.run_layers(lp, h,
        return_aux=True)``). Aux trees accumulate over every live
        (microbatch, chunk) unit across stages; the per-microbatch mean
        goes through ``aux_to_loss``.
      head_loss: ``(params, h, targets) -> per-element loss``.
      aux_to_loss: maps the accumulated aux tree to a scalar added to the
        loss. **Must be linear** (a weighted sum): it is applied to each
        stage's local accumulator and the results sum across stages via
        the identity-backward psum. Required when run_layers emits aux;
        silently dropping router losses would disable load balancing.

        Aux semantics: each (microbatch, chunk) unit contributes the aux
        its layers computed **on that microbatch**, and the total is
        averaged over microbatches — i.e. the mean over microbatches of
        per-microbatch aux losses, which is how microbatched/
        gradient-accumulating training (and Megatron-style MoE) computes
        router losses. This differs from a single full-batch forward by
        the bilinearity of the load-balance loss (an O(variance/M) gap);
        the exact reference is the serial model run per microbatch with
        losses averaged (tests pin this).
      num_microbatches: M; the batch dim must divide by it.
      axis: pipeline mesh axis (bound inside shard_map).
      virtual_pipeline_size: interleaved chunks per stage; layer stacks must
        be pre-permuted with :func:`interleave_stack` when > 1.
      shard_head: compute the (vocab-sized, expensive) head on a 1/S batch
        slice per stage instead of replicating it — total head FLOPs then
        match the serial model. Falls back to the replicated+masked head
        when the batch does not divide by S.

    Run inside ``shard_map`` with layer params sharded by
    :func:`pipeline_specs`; ``params`` holds the non-pipelined parameters
    (embedding, head, final norm — replicated over ``axis``).
    """
    M = num_microbatches
    vpp = virtual_pipeline_size

    def loss_fn(params, layers_local, batch, targets):
        S = lax.axis_size(axis)
        s_idx = lax.axis_index(axis)
        h = embed(params, batch)
        bsz = h.shape[0]
        if bsz % M:
            raise ValueError(f"batch ({bsz}) must divide by microbatches ({M})")
        h_mb = h.reshape((M, bsz // M) + h.shape[1:])

        ring = _pipeline_ring(run_layers, layers_local, h_mb, axis, vpp=vpp)
        if isinstance(ring, tuple):
            out, aux_sum = ring
            if aux_to_loss is None:
                raise ValueError(
                    "run_layers emits aux losses (MoE router) but no "
                    "aux_to_loss was given — dropping them silently would "
                    "disable load balancing")
        else:
            out, aux_sum = ring, None
            if aux_to_loss is not None:
                raise ValueError(
                    "aux_to_loss was given but run_layers emits no aux "
                    "losses (it returned a bare array or (h, None)) — "
                    "either the model has no aux-emitting layers (drop "
                    "aux_to_loss) or run_layers isn't wired with "
                    "return_aux=True")
        h_full = out.reshape((bsz,) + out.shape[2:])

        if shard_head and S > 1 and bsz % S == 0:
            # Scatter the last stage's finished activations: mask non-last
            # stages to zero, then reduce-scatter so stage s receives batch
            # rows [s*share, (s+1)*share) — 1/S the comm volume of an
            # all_gather, and psum_scatter's AD transpose (an all_gather)
            # sums the per-stage slice cotangents back onto the last stage.
            # Each stage then projects only its slice through the vocab
            # head, so head FLOPs total the serial model's.
            share = bsz // S
            h_masked = jnp.where(s_idx == S - 1, h_full, jnp.zeros_like(h_full))
            h_loc = lax.psum_scatter(h_masked, axis, scatter_dimension=0, tiled=True)
            t_loc = jax.tree.map(
                lambda t: lax.dynamic_slice_in_dim(t, s_idx * share, share, axis=0),
                targets,
            )
            per_loss = head_loss(params, h_loc, t_loc)
            # each stage contributes mean(slice)/S; the identity-backward
            # psum makes the sum the full-batch mean while routing each
            # stage's head grads through its own slice only. (The mean is
            # the head's own work: it carries the models' scope.)
            with jax.named_scope("head"):
                local = jnp.mean(per_loss) / S
        else:
            per_loss = head_loss(params, h_full, targets)
            # Only the last stage holds real outputs; mask then psum
            # (identity backward, Megatron cotangent convention) so
            # head/embedding grads attribute to their owning stage.
            with jax.named_scope("head"):
                local = jnp.where(
                    s_idx == S - 1,
                    jnp.mean(per_loss),
                    jnp.zeros((), per_loss.dtype),
                )
        if aux_sum is not None:
            # per-stage masked sums over live units; /M gives the
            # per-microbatch mean, matching the serial run_layers aux
            # scale (summed over layers). Stage-local contributions ride
            # the same identity-backward psum as the head loss. Promote
            # the head loss to f32 rather than round the f32-accumulated
            # aux down to a low-precision head dtype.
            local = local.astype(jnp.float32) + aux_to_loss(
                jax.tree.map(lambda a: a / M, aux_sum)
            ).astype(jnp.float32)
        return _psum_identity_bwd(local, axis)

    return loss_fn


def forward_backward_no_pipelining(
    loss_fn: Callable,
    params: Any,
    batch: Any,
    targets: Any,
    num_microbatches: int,
):
    """Gradient accumulation over microbatches without pipelining
    (reference: fwd_bwd_no_pipelining.py:31+ — grad sync once at the end,
    which a single traced scan gives by construction).

    Returns ``(mean_loss, mean_grads)``.
    """
    M = num_microbatches

    def split(x):
        return x.reshape((M, x.shape[0] // M) + x.shape[1:])

    b_mb = jax.tree.map(split, batch)
    t_mb = jax.tree.map(split, targets)

    def body(carry, xs):
        acc_loss, acc_grads = carry
        b, t = xs
        l, g = jax.value_and_grad(loss_fn)(params, b, t)
        return (acc_loss + l, jax.tree.map(jnp.add, acc_grads, g)), None

    zero_grads = jax.tree.map(jnp.zeros_like, params)
    (loss, grads), _ = lax.scan(body, (jnp.zeros(()), zero_grads), (b_mb, t_mb))
    scale = 1.0 / M
    return loss * scale, jax.tree.map(lambda g: g * scale, grads)


def traced_pipeline_timeline(
    mesh: Any,
    *,
    embed: Callable[[Any, Any], jax.Array],
    run_layers: Callable[[Any, jax.Array], jax.Array],
    head_loss: Callable[[Any, jax.Array, Any], jax.Array],
    rest_params: Any,
    layers: Any,
    layer_specs: Any,
    batch: Any,
    targets: Any,
    num_microbatches: int,
    virtual_pipeline_size: int = 1,
    axis: str = AXIS_PIPE,
    tracer: Any = None,
    step: int = 0,
    warmup: bool = True,
):
    """Tick-by-tick eager drive of the SAME interleaved ring the compiled
    ``pipelined_loss_fn`` scans — the measurement substrate for step
    anatomy (veScale-style eager-observable SPMD, PAPERS.md): each tick's
    compute and its ppermute run as separate jitted device calls with a
    device→host fetch barrier between them, so every 1F1B/vpp slot lands
    as a per-rank span ({fwd, bwd, send, recv}; idle fill/drain slots as
    ``bubble``) and the per-rank bubble fraction is MEASURED instead of
    asserted from the tick algebra.

    The backward is driven explicitly in reverse: each tick's VJP
    recomputes the tick under ``jax.vjp`` inside one jitted call (the
    same rematerialize-in-backward semantics the compiled scan pays),
    with the ppermute transpose (the inverse ring) timed as its own
    send/recv slot. Loss AND grads equal the compiled pipelined loss —
    tier-1 pins the equivalence against the serial model — so the
    timeline is the anatomy of the real computation, not a mock.

    Restrictions (an observability drive, not a training path): the mesh
    region must be pipe-only for the layer stack (``layer_specs`` =
    :func:`pipeline_specs` output; no TP axis inside ``run_layers``),
    ``run_layers`` must not emit aux losses, dropout must be off, and
    the drive retains per-tick carries for the backward (O(ticks ×
    microbatch) activations — fine at probe scale, do not 512k-token it).

    Args mirror :func:`pipelined_loss_fn`; ``layers`` must already be
    :func:`interleave_stack`-permuted when ``virtual_pipeline_size > 1``
    and sharded over ``axis``. ``tracer`` (or the armed global
    ``monitor.tracing`` tracer) receives the spans; pass None to only
    get the returned anatomy.

    Returns ``(loss, grads, anatomy)``: the scalar full-batch mean loss,
    ``grads = {"layers": <in the given interleaved order>, **rest}``,
    and the anatomy dict (per-rank slot seconds, measured
    ``bubble_fraction``, the analytic
    ``expected_bubble_fraction`` floor, per-microbatch slot timings).
    """
    global _RING_DRIVES
    _RING_DRIVES += 1
    from apex_tpu.monitor import tracing as tracing_mod
    from jax.sharding import NamedSharding

    tr = tracer if tracer is not None else tracing_mod.get_tracer()
    # every span ALSO lands in this in-memory collector, so the returned
    # anatomy is derived through the one rollup implementation
    # (tracing.pipeline_anatomy) whether or not a tracer is armed
    collector = tracing_mod.Tracer(None)
    M = int(num_microbatches)
    vpp = int(virtual_pipeline_size)
    S = int(mesh.shape[axis])
    if vpp > 1 and M % S:
        raise ValueError(
            f"interleaved schedule needs num_microbatches ({M}) divisible "
            f"by pipeline size ({S}), as in the reference")
    n_ticks = pipeline_tick_count(M, S, vpp)
    # the same plan arrays the compiled ring scans (schedule-as-data: one
    # decode for execution, measurement, and the planner)
    ring_arrays = _ring_plan_arrays(M, S, vpp)
    r_live = jnp.asarray(ring_arrays["live"])
    r_mb = jnp.asarray(ring_arrays["mb"])
    r_chunk = jnp.asarray(ring_arrays["chunk"])
    L = jax.tree.leaves(layers)[0].shape[0]
    if L % S:
        raise ValueError(f"layer count ({L}) must divide by stages ({S})")
    n_local = L // S
    if n_local % vpp:
        raise ValueError(
            f"per-stage layer count ({n_local}) must divide by vpp ({vpp})")
    per = n_local // vpp

    def _record(name: str, **kw) -> None:
        collector.record(name, **kw)
        if tr is not None:
            tr.record(name, **kw)

    def _tick_spans(t: int, dur: float, *, phase: str, wall0: float) -> None:
        """One measured tick interval → S per-rank slot spans (live/idle
        decoded from the SAME plan arrays the programs scan)."""
        for s in range(S):
            live = bool(ring_arrays["live"][t, s])
            attrs: Dict[str, Any] = {"tick": t, "stage": s,
                                     "phase": phase, "step": step}
            if live:
                attrs["microbatch"] = int(ring_arrays["mb"][t, s])
                attrs["chunk"] = int(ring_arrays["chunk"][t, s])
            _record(phase if live else "bubble", dur_s=dur,
                    cat="pipe", rank=s, ts=wall0, **attrs)

    def _comm_spans(t: int, dur: float, *, phase: str, wall0: float) -> None:
        """One measured ppermute interval → send+recv spans per rank (the
        ring: every rank sends to s+1 and receives from s-1 each tick;
        the transposed ring in the backward inverts the peers)."""
        fwd = phase == "fwd"
        for s in range(S):
            to_peer = (s + 1) % S if fwd else (s - 1) % S
            from_peer = (s - 1) % S if fwd else (s + 1) % S
            _record("send", dur_s=dur, cat="pipe-comm", rank=s,
                    ts=wall0, tick=t, stage=s, phase=phase,
                    peer=to_peer, step=step)
            _record("recv", dur_s=dur, cat="pipe-comm", rank=s,
                    ts=wall0, tick=t, stage=s, phase=phase,
                    peer=from_peer, step=step)

    # -- embed (replicated work, outside the ring) --------------------------
    wall0, t0 = time.time(), time.perf_counter()
    h, vjp_embed = jax.vjp(lambda p: embed(p, batch), rest_params)
    tracing_mod.fetch_barrier(h)
    if tr is not None:
        tr.record("embed", dur_s=time.perf_counter() - t0, cat="compute",
                  ts=wall0, phase="fwd", step=step)
    bsz = h.shape[0]
    if bsz % M:
        raise ValueError(f"batch ({bsz}) must divide by microbatches ({M})")
    h_mb = h.reshape((M, bsz // M) + h.shape[1:])
    mb_shape = h_mb.shape[1:]
    perm = [(i, (i + 1) % S) for i in range(S)]
    perm_inv = [(j, i) for i, j in perm]

    # -- the per-tick programs (compiled once, reused every tick) -----------
    def _compute(buf, out, layers_loc, h_mb_l, t):
        s_idx = lax.axis_index(axis)
        live = r_live[t, s_idx] > 0
        m = r_mb[t, s_idx]
        q = r_chunk[t, s_idx]
        inject = (s_idx == 0) & (q == 0)
        h_in = jnp.where(
            inject,
            lax.dynamic_index_in_dim(h_mb_l, m, 0, keepdims=False),
            buf[0])
        if vpp == 1:
            chunk = layers_loc
        else:
            chunk = jax.tree.map(
                lambda x: lax.dynamic_slice_in_dim(x, q * per, per, axis=0),
                layers_loc)
        h_out = run_layers(chunk, h_in)
        if isinstance(h_out, tuple):
            if h_out[1] is not None:
                raise ValueError(
                    "traced_pipeline_timeline does not support aux-emitting "
                    "layers (MoE routers) — time the dense ring")
            h_out = h_out[0]
        finished = (s_idx == S - 1) & (q == vpp - 1) & live
        cur = lax.dynamic_index_in_dim(out[0], m, 0, keepdims=False)
        out_new = lax.dynamic_update_index_in_dim(
            out[0], jnp.where(finished, h_out, cur), m, 0)
        return h_out[None], out_new[None]

    compute_sm = jax.shard_map(
        _compute, mesh=mesh,
        in_specs=(P(axis), P(axis), layer_specs, P(), P()),
        out_specs=(P(axis), P(axis)), check_vma=False)
    compute_fwd = jax.jit(compute_sm)

    @jax.jit
    def compute_bwd(buf, out, layers_loc, h_mb_l, t, g_hout, g_out,
                    g_l_acc, g_hm_acc):
        # rematerialize the tick under vjp INSIDE one jitted call: one
        # compile covers every backward tick, and the recompute mirrors
        # the remat the compiled scan's backward pays anyway
        _, vjp = jax.vjp(
            lambda b, o, l, hm: compute_sm(b, o, l, hm, t),
            buf, out, layers_loc, h_mb_l)
        g_buf, g_out_prev, g_l, g_hm = vjp((g_hout, g_out))
        return (g_buf, g_out_prev,
                jax.tree.map(jnp.add, g_l_acc, g_l), g_hm_acc + g_hm)

    permute_fwd = jax.jit(jax.shard_map(
        lambda x: lax.ppermute(x, axis, perm), mesh=mesh,
        in_specs=P(axis), out_specs=P(axis), check_vma=False))
    permute_bwd = jax.jit(jax.shard_map(
        lambda x: lax.ppermute(x, axis, perm_inv), mesh=mesh,
        in_specs=P(axis), out_specs=P(axis), check_vma=False))

    # carries committed to the ring sharding up front, so every tick hits
    # the same compiled program (an unsharded zeros carry at tick 0 would
    # recompile AND time the compile into the first span)
    ring_sharding = NamedSharding(mesh, P(axis))
    buf = jax.device_put(jnp.zeros((S,) + mb_shape, h.dtype), ring_sharding)
    out = jax.device_put(jnp.zeros((S, M) + mb_shape, h.dtype),
                         ring_sharding)
    g_layers0 = jax.tree.map(jnp.zeros_like, layers)
    g_hmb0 = jnp.zeros_like(h_mb)

    if warmup:
        # compile all four tick programs outside the measured spans —
        # TWO chained iterations each way, because the loop's second
        # iteration feeds each program its own outputs back (committed
        # shardings can differ from the hand-placed initial carries, and
        # a cache miss inside the measured region would land a ~compile
        # worth of wall time on whichever slot it hits, wrecking the
        # bubble-fraction measurement)
        tt0 = jnp.asarray(0, jnp.int32)
        h_w, o_w = compute_fwd(buf, out, layers, h_mb, tt0)
        b_w = permute_fwd(h_w)
        h_w2, o_w2 = compute_fwd(b_w, o_w, layers, h_mb, tt0)
        g_w = permute_bwd(b_w)
        r1 = compute_bwd(buf, out, layers, h_mb, tt0,
                         g_w, jnp.zeros_like(o_w), g_layers0, g_hmb0)
        g_w2 = permute_bwd(r1[0])
        r2 = compute_bwd(b_w, o_w, layers, h_mb, tt0,
                         g_w2, r1[1], r1[2], r1[3])
        tracing_mod.fetch_barrier(r2[0])

    # -- forward ticks ------------------------------------------------------
    saved = []
    for t in range(n_ticks):
        tt = jnp.asarray(t, jnp.int32)
        saved.append((buf, out, tt))
        wall0, t0 = time.time(), time.perf_counter()
        h_out, out = compute_fwd(buf, out, layers, h_mb, tt)
        tracing_mod.fetch_barrier(h_out)
        _tick_spans(t, time.perf_counter() - t0, phase="fwd", wall0=wall0)
        wall0, t0 = time.time(), time.perf_counter()
        buf = permute_fwd(h_out)
        tracing_mod.fetch_barrier(buf)
        _comm_spans(t, time.perf_counter() - t0, phase="fwd", wall0=wall0)

    # -- head (replicated loss on the last stage's finished rows) -----------
    wall0, t0 = time.time(), time.perf_counter()
    out_last = out[S - 1]
    h_full = out_last.reshape((bsz,) + out_last.shape[2:])
    loss, vjp_head = jax.vjp(
        lambda r, hf: jnp.mean(head_loss(r, hf, targets)), rest_params,
        h_full)
    tracing_mod.fetch_barrier(loss)
    if tr is not None:
        tr.record("head", dur_s=time.perf_counter() - t0, cat="compute",
                  ts=wall0, phase="fwd", step=step)

    # -- backward ticks (the transposed ring, driven in reverse) ------------
    g_rest_h, g_hfull = vjp_head(jnp.ones_like(loss))
    g_out = jnp.zeros_like(out).at[S - 1].set(
        g_hfull.reshape((M,) + mb_shape))
    g_buf = jnp.zeros_like(buf)
    g_layers, g_hmb = g_layers0, g_hmb0
    for t in reversed(range(n_ticks)):
        sbuf, sout, tt = saved[t]
        wall0, t0 = time.time(), time.perf_counter()
        g_hout = permute_bwd(g_buf)
        tracing_mod.fetch_barrier(g_hout)
        _comm_spans(t, time.perf_counter() - t0, phase="bwd", wall0=wall0)
        wall0, t0 = time.time(), time.perf_counter()
        g_buf, g_out, g_layers, g_hmb = compute_bwd(
            sbuf, sout, layers, h_mb, tt, g_hout, g_out, g_layers, g_hmb)
        tracing_mod.fetch_barrier(g_buf)
        _tick_spans(t, time.perf_counter() - t0, phase="bwd", wall0=wall0)

    wall0, t0 = time.time(), time.perf_counter()
    (g_rest_e,) = vjp_embed(g_hmb.reshape(h.shape))
    rest_grads = jax.tree.map(jnp.add, g_rest_h, g_rest_e)
    tracing_mod.fetch_barrier(jax.tree.leaves(rest_grads)[0])
    if tr is not None:
        tr.record("embed", dur_s=time.perf_counter() - t0, cat="compute",
                  ts=wall0, phase="bwd", step=step)

    # -- anatomy: the ONE rollup implementation (tracing.pipeline_anatomy)
    # over the in-memory collector, so a tracer-armed run and the
    # returned dict can never disagree
    pa = tracing_mod.pipeline_anatomy(collector.records)
    anatomy = {
        "schedule": "interleaved",
        "stages": S, "vpp": vpp, "num_microbatches": M,
        "ticks": n_ticks, "units": vpp * M,
        "expected_bubble_fraction": round(
            tracing_mod.expected_bubble_fraction(
                "interleaved", M, S, virtual_pipeline_size=vpp), 4),
        "per_rank": pa["ranks"],
        "bubble_fraction": pa["bubble_fraction"],
        "microbatches": pa.get("microbatches", {}),
    }
    return loss, dict(rest_grads, layers=g_layers), anatomy


# ---------------------------------------------------------------------------
# the plan executor: ONE tick body, two drives (compiled scan / traced ticks)
# ---------------------------------------------------------------------------


def _plan_tick_fn(plan: SchedulePlan, *, run_layers, head_loss, axis):
    """Build the ONE tick body both executor drives interpret.

    ``tick(state, fwd_wire, bwd_wire, t, layers_local, rest, h_mb, tgt_mb,
    seed) -> (state', fwd_out, bwd_out)`` executes this rank's slot at tick
    ``t`` per the plan arrays: deposits the incoming ppermute payloads into
    the microbatch stashes, then switches on the slot kind —

    - ``fwd``: run the stage chunk on the stashed (or, on rank 0, injected)
      activation;
    - ``bwd``: the combined VJP w.r.t. (weights, input) — gpipe/1f1b slots;
    - ``bwd_input``: the INPUT-grad closure only (``jax.vjp`` w.r.t. the
      activation, rematerializing the stage forward) — releases the
      upstream rank's dependency without paying the weight grads;
    - ``bwd_weight``: the WEIGHT-grad closure only (``jax.vjp`` w.r.t. the
      stage params) — the slots the zero-bubble planner parks in what
      would be cooldown idles.

    The last stage's backward slots run the head loss chained onto the
    stage (per-microbatch mean, seeded ``scale/M`` so the summed slots
    equal the scaled full-batch mean); rank 0's input-grads accumulate as
    the embedding cotangent. ``state = (h_stash, g_stash, g_layers,
    g_rest, g_hmb, loss)``; wires ppermute OUTSIDE this body so the traced
    drive can time them as their own send/recv slots.
    """
    arrays = plan.arrays()
    a_kind = jnp.asarray(arrays["kind"])
    a_mb = jnp.asarray(arrays["mb"])
    a_depf = jnp.asarray(arrays["dep_f"])
    a_depb = jnp.asarray(arrays["dep_b"])
    M, S = plan.num_microbatches, plan.stages
    if plan.virtual_pipeline_size != 1:
        raise ValueError(
            "the plan executor drives vpp=1 plans; interleaved (vpp>1) "
            "schedules run through the SPMD ring (_pipeline_ring / "
            "traced_pipeline_timeline)")

    def run_chunk(p, h):
        out = run_layers(p, h)
        if isinstance(out, tuple):
            if out[1] is not None:
                raise ValueError(
                    "the plan executor does not support aux-emitting layers "
                    "(MoE routers) — drive the dense stack")
            out = out[0]
        return out

    def _deposit(stash, dep, wire):
        idx = jnp.maximum(dep, 0)
        cur = lax.dynamic_index_in_dim(stash, idx, 0, keepdims=False)
        return lax.dynamic_update_index_in_dim(
            stash, jnp.where(dep >= 0, wire, cur), idx, 0)

    def tick(state, fwd_wire, bwd_wire, t, layers_local, rest, h_mb,
             tgt_mb, seed):
        h_stash, g_stash, g_layers, g_rest, g_hmb, loss = state
        s_idx = lax.axis_index(axis)
        kind = a_kind[t, s_idx]
        m = jnp.maximum(a_mb[t, s_idx], 0)
        h_stash = _deposit(h_stash, a_depf[t, s_idx], fwd_wire)
        g_stash = _deposit(g_stash, a_depb[t, s_idx], bwd_wire)
        cur_m = lax.dynamic_index_in_dim(h_stash, m, 0, keepdims=False)
        h_in = jnp.where(
            s_idx == 0,
            lax.dynamic_index_in_dim(h_mb, m, 0, keepdims=False), cur_m)
        # rank 0 stashes its injected activation at fwd time so its later
        # bwd_input/bwd_weight slots rematerialize from the same input
        h_stash = lax.dynamic_update_index_in_dim(
            h_stash, jnp.where(kind == K_FWD, h_in, cur_m), m, 0)
        g_out = lax.dynamic_index_in_dim(g_stash, m, 0, keepdims=False)
        tgt_m = jax.tree.map(
            lambda x: lax.dynamic_index_in_dim(x, m, 0, keepdims=False),
            tgt_mb)
        is_last = s_idx == S - 1
        z_mb = jnp.zeros_like(h_in)
        z_layers = jax.tree.map(jnp.zeros_like, layers_local)
        z_rest = jax.tree.map(jnp.zeros_like, rest)
        z = jnp.zeros((), jnp.float32)

        def chain(p, r, h):
            # the last stage's slot: head loss chained onto the stage so
            # its VJPs factor the same way the stage's do
            return jnp.mean(head_loss(r, run_chunk(p, h), tgt_m)
                            ).astype(jnp.float32)

        def br_idle(h_in, g_out):
            return z_mb, z_mb, z_layers, z_rest, z

        def br_fwd(h_in, g_out):
            return run_chunk(layers_local, h_in), z_mb, z_layers, z_rest, z

        def br_bwd(h_in, g_out):
            def last():
                lm, vjp = jax.vjp(chain, layers_local, rest, h_in)
                g_p, g_r, g_h = vjp(seed)
                return g_h, g_p, g_r, lm * seed

            def mid():
                _, vjp = jax.vjp(
                    lambda p, h: run_chunk(p, h), layers_local, h_in)
                g_p, g_h = vjp(g_out)
                return g_h, g_p, z_rest, z

            g_h, g_p, g_r, dl = lax.cond(is_last, last, mid)
            return z_mb, g_h, g_p, g_r, dl

        def br_bwd_input(h_in, g_out):
            def last():
                lm, vjp = jax.vjp(lambda h: chain(layers_local, rest, h),
                                  h_in)
                (g_h,) = vjp(seed)
                return g_h, lm * seed

            def mid():
                _, vjp = jax.vjp(lambda h: run_chunk(layers_local, h), h_in)
                (g_h,) = vjp(g_out)
                return g_h, z

            g_h, dl = lax.cond(is_last, last, mid)
            return z_mb, g_h, z_layers, z_rest, dl

        def br_bwd_weight(h_in, g_out):
            def last():
                _, vjp = jax.vjp(lambda p, r: chain(p, r, h_in),
                                 layers_local, rest)
                g_p, g_r = vjp(seed)
                return g_p, g_r

            def mid():
                _, vjp = jax.vjp(lambda p: run_chunk(p, h_in), layers_local)
                (g_p,) = vjp(g_out)
                return g_p, z_rest

            g_p, g_r = lax.cond(is_last, last, mid)
            return z_mb, z_mb, g_p, g_r, z

        fwd_out, g_in, d_layers, d_rest, d_loss = lax.switch(
            kind, (br_idle, br_fwd, br_bwd, br_bwd_input, br_bwd_weight),
            h_in, g_out)
        g_layers = jax.tree.map(jnp.add, g_layers, d_layers)
        g_rest = jax.tree.map(jnp.add, g_rest, d_rest)
        loss = loss + d_loss
        # rank 0's input-grad IS the embedding cotangent for microbatch m
        emit = ((kind == K_BWD) | (kind == K_BWD_INPUT)) & (s_idx == 0)
        gh_m = lax.dynamic_index_in_dim(g_hmb, m, 0, keepdims=False)
        g_hmb = lax.dynamic_update_index_in_dim(
            g_hmb, gh_m + jnp.where(emit, g_in, jnp.zeros_like(g_in)), m, 0)
        return ((h_stash, g_stash, g_layers, g_rest, g_hmb, loss),
                fwd_out, g_in)

    return tick


def schedule_grads_fn(plan: SchedulePlan, *, embed, run_layers, head_loss,
                      axis: str = AXIS_PIPE):
    """The COMPILED drive of a :class:`SchedulePlan`: one ``lax.scan`` over
    the plan's tick arrays, interpreting the same tick body the traced
    drive times (:func:`_plan_tick_fn` — schedule-as-data's whole point).

    Returns ``grads_fn(rest, layers_local, batch, targets, scale=1.0) ->
    (loss, rest_g, layer_g)`` to run INSIDE ``shard_map`` with the layer
    stack sharded by :func:`pipeline_specs` — a drop-in for
    ``jax.value_and_grad(scaled pipe_loss, argnums=(0, 1))``: the loss is
    the scaled full-batch mean (identity-backward psum over ``axis``, like
    ``pipelined_loss_fn``), ``rest_g`` is per-stage partial (head grads on
    the last stage, embedding grads on stage 0 — the harness's spec-aware
    reduction over ``axis`` completes them), ``layer_g`` is this stage's
    chunk grads. Unlike the AD-transposed ring, the backward here is
    EXPLICIT slots — the only way the zero-bubble W/B split can fill the
    cooldown. vpp=1 plans only; every backward slot rematerializes its
    stage forward (the compiled scan's remat semantics).
    """
    tick = _plan_tick_fn(plan, run_layers=run_layers, head_loss=head_loss,
                         axis=axis)
    M, S, T = plan.num_microbatches, plan.stages, plan.ticks

    def grads_fn(rest, layers_local, batch, targets, scale=1.0):
        global _RING_DRIVES
        _RING_DRIVES += 1
        perm = [(i, (i + 1) % S) for i in range(S)]
        perm_inv = [(j, i) for i, j in perm]
        h, vjp_embed = jax.vjp(lambda r: embed(r, batch), rest)
        bsz = h.shape[0]
        if bsz % M:
            raise ValueError(
                f"batch ({bsz}) must divide by microbatches ({M})")
        h_mb = h.reshape((M, bsz // M) + h.shape[1:])
        tgt_mb = jax.tree.map(
            lambda x: x.reshape((M, bsz // M) + x.shape[1:]), targets)
        mb_shape = h_mb.shape[1:]
        seed = (jnp.asarray(scale, jnp.float32) / M)
        state0 = (
            jnp.zeros((M,) + mb_shape, h.dtype),          # h_stash
            jnp.zeros((M,) + mb_shape, h.dtype),          # g_stash
            jax.tree.map(jnp.zeros_like, layers_local),   # g_layers
            jax.tree.map(jnp.zeros_like, rest),           # g_rest
            jnp.zeros((M,) + mb_shape, h.dtype),          # g_hmb
            jnp.zeros((), jnp.float32),                   # loss
        )
        wire0 = jnp.zeros(mb_shape, h.dtype)

        def scan_tick(carry, t):
            state, fwd_wire, bwd_wire = carry
            state, f_out, b_out = tick(state, fwd_wire, bwd_wire, t,
                                       layers_local, rest, h_mb, tgt_mb,
                                       seed)
            fwd_wire = lax.ppermute(f_out, axis, perm)
            bwd_wire = lax.ppermute(b_out, axis, perm_inv)
            return (state, fwd_wire, bwd_wire), None

        (state, _, _), _ = lax.scan(
            scan_tick, (state0, wire0, wire0), jnp.arange(T))
        _, _, g_layers, g_rest, g_hmb, loss = state
        (g_rest_e,) = vjp_embed(g_hmb.reshape(h.shape))
        rest_g = jax.tree.map(jnp.add, g_rest, g_rest_e)
        return _psum_identity_bwd(loss, axis), rest_g, g_layers

    return grads_fn


def zero_bubble_grads_fn(model: Any, num_microbatches: int, stages: int):
    """The harness one-liner: a zero-bubble :func:`schedule_grads_fn` over
    a model-zoo model's stage hooks (embed / run_layers / head) — the ONE
    wiring every harness shares (pretrain_gpt ``--pp-schedule zerobubble``,
    gpt_scaling's ``"zb"`` row, the multichip gate's zerobubble config),
    so the executor contract has a single call-site shape."""
    return schedule_grads_fn(
        plan_schedule("zero-bubble", num_microbatches, stages),
        embed=model.embed,
        run_layers=lambda lp, h: model.run_layers(lp, h),
        head_loss=lambda p, h, t: model.head(p, h, t))


def traced_schedule_timeline(
    plan: SchedulePlan,
    mesh: Any,
    *,
    embed,
    run_layers,
    head_loss,
    rest_params: Any,
    layers: Any,
    layer_specs: Any,
    batch: Any,
    targets: Any,
    axis: str = AXIS_PIPE,
    tracer: Any = None,
    step: int = 0,
    warmup: bool = True,
    loss_scale: float = 1.0,
):
    """The MEASURED drive of a :class:`SchedulePlan`: each tick's compute
    and its two ppermutes run as separate jitted device calls with
    device→host fetch barriers, interpreting the SAME tick body the
    compiled scan interprets (:func:`_plan_tick_fn`) — so the per-rank
    bubble fraction is measured on the anatomy of the real computation
    (loss AND grads equal the compiled drive and the serial model; tier-1
    pins it). The generalization of :func:`traced_pipeline_timeline` to
    arbitrary vpp=1 plans — in particular the zero-bubble planner, whose
    measured bubble must land strictly below 1F1B's at the same (S, M)
    (benchmarks/overlap_evidence.py --timeline gates it).

    Same restrictions as the ring drive (pipe-only mesh region for the
    layer stack, no aux, dropout off); the per-rank W/B slots of the
    zero-bubble plan land as ``bwd`` spans with a ``wb`` attr.

    Returns ``(loss, grads, anatomy)``: the scaled full-batch mean loss,
    ``grads = {"layers": <stacked>, **rest}`` comparable to the serial
    model, and the anatomy dict (measured per-rank slot seconds + the
    plan's analytic floor).
    """
    global _RING_DRIVES
    _RING_DRIVES += 1
    from apex_tpu.monitor import tracing as tracing_mod
    from jax.sharding import NamedSharding

    tr = tracer if tracer is not None else tracing_mod.get_tracer()
    collector = tracing_mod.Tracer(None)
    M, S, T = plan.num_microbatches, plan.stages, plan.ticks
    if int(mesh.shape[axis]) != S:
        raise ValueError(
            f"plan has {S} stages but mesh axis {axis!r} is "
            f"{int(mesh.shape[axis])} wide")
    tick = _plan_tick_fn(plan, run_layers=run_layers, head_loss=head_loss,
                         axis=axis)
    arrays = plan.arrays()

    def _record(name: str, **kw) -> None:
        collector.record(name, **kw)
        if tr is not None:
            tr.record(name, **kw)

    def _tick_spans(t: int, dur: float, *, wall0: float) -> None:
        for s in range(S):
            code = int(arrays["kind"][t, s])
            name = KIND_NAMES[code]
            attrs: Dict[str, Any] = {"tick": t, "stage": s, "step": step,
                                     "schedule": plan.schedule}
            if code == K_IDLE:
                name = "bubble"
            else:
                attrs["microbatch"] = int(arrays["mb"][t, s])
                attrs["phase"] = "fwd" if code == K_FWD else "bwd"
                if code in (K_BWD_INPUT, K_BWD_WEIGHT):
                    attrs["wb"] = "W" if code == K_BWD_WEIGHT else "B"
                if code != K_FWD:
                    name = "bwd"
            _record(name, dur_s=dur, cat="pipe", rank=s, ts=wall0, **attrs)

    def _comm_spans(t: int, dur: float, *, wall0: float) -> None:
        for s in range(S):
            _record("send", dur_s=dur, cat="pipe-comm", rank=s, ts=wall0,
                    tick=t, stage=s, step=step)
            _record("recv", dur_s=dur, cat="pipe-comm", rank=s, ts=wall0,
                    tick=t, stage=s, step=step)

    # -- embed (replicated work, outside the timeline) ----------------------
    wall0, t0 = time.time(), time.perf_counter()
    h, vjp_embed = jax.vjp(lambda r: embed(r, batch), rest_params)
    tracing_mod.fetch_barrier(h)
    if tr is not None:
        tr.record("embed", dur_s=time.perf_counter() - t0, cat="compute",
                  ts=wall0, phase="fwd", step=step)
    bsz = h.shape[0]
    if bsz % M:
        raise ValueError(f"batch ({bsz}) must divide by microbatches ({M})")
    h_mb = h.reshape((M, bsz // M) + h.shape[1:])
    tgt_mb = jax.tree.map(
        lambda x: x.reshape((M, bsz // M) + x.shape[1:]), targets)
    mb_shape = h_mb.shape[1:]
    perm = [(i, (i + 1) % S) for i in range(S)]
    perm_inv = [(j, i) for i, j in perm]
    seed = float(loss_scale) / M
    rest_specs = jax.tree.map(lambda _: P(), rest_params)

    # -- the per-tick programs (compiled once, reused every tick) -----------
    def _tick_global(h_st, g_st, g_lay, g_rest, g_hmb, loss, fw, bw,
                     layers_loc, rest, h_mb_l, tgt_l, t):
        state = (h_st[0], g_st[0], g_lay,
                 jax.tree.map(lambda x: x[0], g_rest), g_hmb[0], loss[0])
        state, f_out, b_out = tick(state, fw[0], bw[0], t, layers_loc,
                                   rest, h_mb_l, tgt_l, seed)
        h_st, g_st, g_lay, g_rest, g_hmb, loss = state
        return (h_st[None], g_st[None], g_lay,
                jax.tree.map(lambda x: x[None], g_rest), g_hmb[None],
                loss[None], f_out[None], b_out[None])

    rank_specs = (P(axis), P(axis), layer_specs,
                  jax.tree.map(lambda _: P(axis), rest_params), P(axis),
                  P(axis))
    tick_fn = jax.jit(jax.shard_map(
        _tick_global, mesh=mesh,
        in_specs=rank_specs + (P(axis), P(axis), layer_specs, rest_specs,
                               P(), P(), P()),
        out_specs=rank_specs + (P(axis), P(axis)), check_vma=False))
    permute_fn = jax.jit(jax.shard_map(
        lambda f, b: (lax.ppermute(f, axis, perm),
                      lax.ppermute(b, axis, perm_inv)),
        mesh=mesh, in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis)), check_vma=False))

    ring_sharding = NamedSharding(mesh, P(axis))
    put = lambda a: jax.device_put(a, ring_sharding)  # noqa: E731
    h_st = put(jnp.zeros((S, M) + mb_shape, h.dtype))
    g_st = put(jnp.zeros((S, M) + mb_shape, h.dtype))
    g_hmb = put(jnp.zeros((S, M) + mb_shape, h.dtype))
    g_lay = jax.tree.map(jnp.zeros_like, layers)
    g_rest = jax.tree.map(
        lambda x: put(jnp.zeros((S,) + x.shape, x.dtype)), rest_params)
    loss_acc = put(jnp.zeros((S,), jnp.float32))
    fw = put(jnp.zeros((S,) + mb_shape, h.dtype))
    bw = put(jnp.zeros((S,) + mb_shape, h.dtype))

    if warmup:
        # two chained iterations of both programs outside the measured
        # spans (committed-sharding cache warm; a compile inside the
        # measured region would wreck the bubble measurement)
        tt0 = jnp.asarray(0, jnp.int32)
        w = tick_fn(h_st, g_st, g_lay, g_rest, g_hmb, loss_acc, fw, bw,
                    layers, rest_params, h_mb, tgt_mb, tt0)
        fw_w, bw_w = permute_fn(w[6], w[7])
        w2 = tick_fn(*w[:6], fw_w, bw_w, layers, rest_params, h_mb,
                     tgt_mb, tt0)
        fw_w2, bw_w2 = permute_fn(w2[6], w2[7])
        tracing_mod.fetch_barrier(fw_w2)

    for t in range(T):
        tt = jnp.asarray(t, jnp.int32)
        wall0, t0 = time.time(), time.perf_counter()
        out = tick_fn(h_st, g_st, g_lay, g_rest, g_hmb, loss_acc, fw, bw,
                      layers, rest_params, h_mb, tgt_mb, tt)
        h_st, g_st, g_lay, g_rest, g_hmb, loss_acc, f_out, b_out = out
        tracing_mod.fetch_barrier(loss_acc)
        _tick_spans(t, time.perf_counter() - t0, wall0=wall0)
        wall0, t0 = time.time(), time.perf_counter()
        fw, bw = permute_fn(f_out, b_out)
        tracing_mod.fetch_barrier(fw)
        _comm_spans(t, time.perf_counter() - t0, wall0=wall0)

    # -- totals: per-rank partials summed on the host, embed VJP closed ----
    wall0, t0 = time.time(), time.perf_counter()
    loss = float(np.asarray(jax.device_get(loss_acc)).sum())
    g_hmb_total = np.asarray(jax.device_get(g_hmb)).sum(axis=0)
    (g_rest_e,) = vjp_embed(jnp.asarray(g_hmb_total.reshape(h.shape),
                                        h.dtype))
    rest_grads = jax.tree.map(
        lambda part, e: jnp.asarray(
            np.asarray(jax.device_get(part)).sum(axis=0)) + e,
        g_rest, g_rest_e)
    tracing_mod.fetch_barrier(jax.tree.leaves(rest_grads)[0])
    if tr is not None:
        tr.record("embed", dur_s=time.perf_counter() - t0, cat="compute",
                  ts=wall0, phase="bwd", step=step)

    pa = tracing_mod.pipeline_anatomy(collector.records)
    anatomy = {
        "schedule": plan.schedule,
        "stages": S, "vpp": 1, "num_microbatches": M, "ticks": T,
        "expected_bubble_fraction": round(
            tracing_mod.expected_bubble_fraction(plan.schedule, M, S), 4),
        "plan_bubble_fraction": round(plan.bubble_fraction(), 4),
        "per_rank": pa["ranks"],
        "bubble_fraction": pa["bubble_fraction"],
        "microbatches": pa.get("microbatches", {}),
    }
    return loss, dict(rest_grads, layers=g_lay), anatomy


def get_forward_backward_func(
    pipeline_model_parallel_size: int,
    virtual_pipeline_model_parallel_size: Optional[int] = None,
):
    """Dispatcher (reference: schedules/__init__.py:16-34): no-pipelining for
    pp=1; the SPMD pipeline (with or without interleaving) otherwise."""
    if pipeline_model_parallel_size > 1:
        if virtual_pipeline_model_parallel_size is not None:
            return lambda **kw: pipelined_loss_fn(
                virtual_pipeline_size=virtual_pipeline_model_parallel_size, **kw
            )
        return pipelined_loss_fn
    return forward_backward_no_pipelining

"""Named-axis collectives — the communication backend over ICI.

Reference: apex uses torch.distributed/NCCL process-group verbs —
``all_reduce`` (apex/parallel/distributed.py:449-451,
apex/transformer/tensor_parallel/mappings.py:31), ``broadcast``
(distributed.py:253,296), ``all_gather`` (mappings.py:69), batched
``isend/irecv`` (pipeline_parallel/p2p_communication.py:29-67), with CUDA
streams for comm/compute overlap (distributed.py:425-475). SURVEY.md §2.4.

Here each verb is a thin, documented wrapper over the XLA collective that
rides ICI: process groups become mesh axis names, streams/overlap become
XLA's async-collective latency hiding, and point-to-point pipeline traffic
becomes ``ppermute`` ring shifts. All of these are only meaningful inside a
``shard_map`` (or vmapped/pjitted context) that binds the axis name.

Everything is a tree-map: apex's multi-tensor bucketing (flatten → NCCL →
unflatten, distributed.py:425-475) exists to amortize launch overhead in
eager CUDA; XLA already coalesces collectives, so a pytree maps directly.

Telemetry: every verb runs under a ``comm:<verb>[<axis>]`` named scope
(``apex_tpu.monitor.comms``), so pyprof trace-joins attribute measured comm
seconds per mesh axis and ``monitor.comms.comm_accounting`` tallies payload
bytes per (verb, axis) at trace time. Zero runtime cost: the scope exists
only while tracing.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple, Union

import jax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from apex_tpu.monitor.comms import collective_scope as _comm

AxisNames = Union[str, Tuple[str, ...]]

# ---------------------------------------------------------------------------
# lint introspection hooks (apex_tpu.lint comm-scope rule; read STATICALLY
# via ast.literal_eval, so keep both values plain literals). The prims are
# the data-moving named-axis collectives -- axis_index/axis_size are
# rank/topology queries, not communication; the helpers are the call names
# that satisfy the comm:-scope contract documented above.
# ---------------------------------------------------------------------------

COMM_SCOPE_PRIMS = {"psum", "pmean", "pmax", "pmin", "all_gather",
                    "psum_scatter", "ppermute", "all_to_all", "pshuffle",
                    "all_gather_invariant"}
# Call names that satisfy the comm:-scope contract: the scope helpers
# themselves, plus the conjugate sequence-parallel mappings
# (tensor_parallel/mappings.py) whose forward AND custom-VJP backward each
# run under their own comm: scope — a composite verb built on them needs no
# re-scoping. The quantized wire-dtype collectives (parallel/quantize.py)
# carry their own scopes too: each books its encoded payload AND its fp32
# scale side-channel as separate comm: call sites, so the by-wire-dtype
# accounting (monitor/comms.CommAccount.by_verb_dtype) stays complete.
COMM_SCOPE_HELPERS = ("_comm", "collective_scope",
                      "scatter_to_sequence_parallel_region",
                      "gather_from_sequence_parallel_region",
                      "reduce_scatter_to_sequence_parallel_region",
                      "quantized_reduce_scatter",
                      "quantized_psum_scatter",
                      "quantized_all_gather",
                      "quantized_gather_chunk",
                      "quantized_all_to_all")

# The jaxpr-level decomposition contract of sequence parallelism (read
# statically by apex_tpu.lint.trace.sequence_parallel_hazards, like the
# comm-scope sets above): in a sequence-parallel forward trace, activation
# traffic on the TP axis must appear ONLY as these primitives — a bare
# ``psum`` of an activation there means the psum_scatter/all_gather
# decomposition silently regressed to a synchronous all-reduce.
SEQUENCE_PARALLEL_DECOMPOSED_PRIMS = ("reduce_scatter", "all_gather")

# The same contract for the ZeRO optimizer path
# (apex_tpu.lint.trace.zero_redundancy_hazards): in a step whose optimizer
# is sharded over the data axis, BULK gradient traffic there must appear
# only as the reduce-scatter/all-gather conjugate pair
# (optimizers/distributed.py) — a full-size grad ``psum`` on that axis
# means the step still all-reduces what the scatter already reduces.
ZERO_DECOMPOSED_PRIMS = ("reduce_scatter", "all_gather")

# The quantized-collective contract (apex_tpu.lint.trace.
# quantized_comm_hazards, read statically like the sets above): in a step
# that requests a quantized grad reduce (MixedPrecisionOptimizer
# ``reduce_dtype``), BULK reduce traffic on the zero axis must move at a
# 1-byte wire dtype — the encoded ``all_to_all`` pair of
# parallel/quantize.py — with only the tiny fp32 scale side-channel wider.
# A surviving bulk fp32 ``reduce_scatter``/``all_to_all`` payload means the
# quantization silently regressed to the 4 B/elem wire.
QUANTIZED_WIRE_ITEMSIZE = 1
QUANTIZED_REDUCE_PRIMS = ("reduce_scatter", "all_to_all")

# The expert-parallel dispatch contract (apex_tpu.lint.trace.
# moe_dispatch_hazards, read statically like the sets above): a step that
# requests expert parallelism (``GPTConfig.moe_expert_axis``) must move
# its token buckets as ``all_to_all`` over the expert axis — a trace with
# no dispatch all_to_all means the experts silently run replicated; and
# under ``moe_dispatch_dtype`` the DISPATCH-SHAPED payloads (rank >=
# MOE_DISPATCH_MIN_RANK — the (experts, capacity, hidden) buckets, vs the
# rank-2 ZeRO grad-chunk rows that may share the same mesh axis) must
# move at the 1-byte wire dtype (parallel/quantize.quantized_all_to_all).
MOE_DISPATCH_PRIMS = ("all_to_all",)
MOE_DISPATCH_MIN_RANK = 3

#: every verb in this module must run under a ``comm:`` scope; the marker
#: opts the file into the lint rule even if the import shape changes
LINT_COMM_SCOPE = True


def axis_rank(axis: AxisNames) -> jax.Array:
    """This shard's index along ``axis`` (torch.distributed.get_rank(group)
    equivalent, parallel_state.py:263-299)."""
    return lax.axis_index(axis)


def axis_size(axis: AxisNames) -> int:
    """Static size of ``axis`` (get_world_size(group) equivalent)."""
    return lax.axis_size(axis)


def psum(tree: Any, axis: AxisNames) -> Any:
    """All-reduce-sum over a mesh axis (dist.all_reduce SUM)."""
    with _comm("psum", axis, tree):
        return lax.psum(tree, axis)


def pmean(tree: Any, axis: AxisNames) -> Any:
    """Averaging all-reduce — the DDP gradient reduction semantic
    (apex/parallel/distributed.py:449-457: allreduce then divide by
    world size)."""
    with _comm("pmean", axis, tree):
        return lax.pmean(tree, axis)


def pmax(tree: Any, axis: AxisNames) -> Any:
    """All-reduce-max (used by vocab-parallel cross entropy,
    tensor_parallel/cross_entropy.py:30-33, and overflow checks,
    transformer/amp/grad_scaler.py:25-36)."""
    with _comm("pmax", axis, tree):
        return jax.tree.map(lambda x: lax.pmax(x, axis), tree)


def all_gather(tree: Any, axis: AxisNames, *, gather_axis: int = 0, tiled: bool = True) -> Any:
    """Gather shards along ``axis``, concatenating on ``gather_axis``
    (dist.all_gather + cat, tensor_parallel/mappings.py:61-70)."""
    with _comm("all_gather", axis, tree):
        return jax.tree.map(
            lambda x: lax.all_gather(x, axis, axis=gather_axis, tiled=tiled), tree
        )


def reduce_scatter(tree: Any, axis: AxisNames, *, scatter_axis: int = 0) -> Any:
    """Sum-reduce then scatter shards along ``scatter_axis`` — the ZeRO grad
    primitive (contrib DistributedFusedAdam reduce-scatter pipeline,
    distributed_fused_adam.py:397-441)."""
    with _comm("reduce_scatter", axis, tree):
        return jax.tree.map(
            lambda x: lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=True),
            tree,
        )


def ppermute_shift(tree: Any, axis: AxisNames, shift: int = 1) -> Any:
    """Ring shift: each shard sends to ``(rank + shift) % size`` — the TPU
    replacement for batched isend/irecv pipeline p2p
    (p2p_communication.py:29-67) and the transport for ring attention."""
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    with _comm("ppermute", axis, tree):
        return jax.tree.map(lambda x: lax.ppermute(x, axis, perm), tree)


def broadcast(tree: Any, axis: AxisNames, src: int = 0) -> Any:
    """Broadcast ``src``'s shard to all ranks along ``axis``
    (dist.broadcast; tensor_parallel/data.py:50, distributed.py:253)."""

    def _bcast(x):
        # all_gather then static index: XLA lowers this to a broadcast-shaped
        # collective; avoids a host round-trip.
        return lax.all_gather(x, axis, axis=0, tiled=False)[src]

    with _comm("broadcast", axis, tree):
        return jax.tree.map(_bcast, tree)


def all_to_all(
    x: jax.Array, axis: AxisNames, *, split_axis: int, concat_axis: int
) -> jax.Array:
    """All-to-all reshard (basis of Ulysses-style sequence parallelism —
    absent in the reference, SURVEY.md §2.3 row SP)."""
    with _comm("all_to_all", axis, x):
        return lax.all_to_all(x, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=True)


# ---------------------------------------------------------------------------
# Sharding helpers (host side)
# ---------------------------------------------------------------------------


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))


def constrain(x: Any, *spec) -> Any:
    """``with_sharding_constraint`` with a PartitionSpec — the GSPMD
    annotation that replaces the reference's hand-written conjugate
    collectives (mappings.py:23-159) in pjit-traced code."""
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*spec))


def shard_map_over(
    mesh: Mesh,
    in_specs,
    out_specs,
    check_vma: bool = False,
) -> Callable[[Callable], Callable]:
    """Decorator sugar for ``jax.shard_map`` over ``mesh``."""

    def deco(fn):
        return jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
        )

    return deco

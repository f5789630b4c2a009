"""Model-parallel topology as a named device mesh — the TPU-native "MPU".

Reference: apex/transformer/parallel_state.py:57-184 builds NCCL process
groups for data/tensor/pipeline/model/embedding parallelism from
``(tp_size, pp_size, virtual_pp_size, pp_split_rank)`` and records the
calling rank's position in each. On TPU there are no process groups: a
single ``jax.sharding.Mesh`` with named axes carries the whole topology, and
every "group" becomes a mesh axis name passed to a collective.

Topology contract preserved from the reference (parallel_state.py:119-184):

- tensor-parallel ranks are **contiguous** device blocks (``:142-149``) —
  here the ``model`` axis is the fastest-varying mesh dimension, so TP
  collectives ride the fastest ICI links ("adjacent ranks share a box",
  ``:83-86``);
- data-parallel ranks stride by tp_size within a pipeline block
  (``:119-131``) — the ``data`` axis varies next;
- pipeline-parallel ranks stride widest (``:159-164``) — the ``pipe`` axis is
  slowest-varying, matching PP's tolerance for higher-latency links;
- the ``context`` axis (sequence/ring parallelism — absent in the reference,
  SURVEY.md §2.3) sits between ``data`` and ``model`` so ring-attention
  ppermutes stay on fast links.

Flattened device order is therefore ``pipe → data → context → model`` with
``model`` innermost; ``rank_coords`` exposes the inverse map for tests that
verify parity with the reference's rank arithmetic.

Virtual-pipeline (interleaved schedule) state mirrors
parallel_state.py:367-382; embedding-group membership (first + last + optional
split stage, ``:165-184``) is exposed as stage predicates rather than a
process group — weight-tying grad reduction happens inside the pipeline
schedule (see apex_tpu.transformer.pipeline_parallel).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_PIPE = "pipe"
AXIS_DATA = "data"
AXIS_CONTEXT = "context"
AXIS_MODEL = "model"

#: Canonical axis order, slowest- to fastest-varying across the device list.
MESH_AXIS_NAMES: Tuple[str, ...] = (AXIS_PIPE, AXIS_DATA, AXIS_CONTEXT, AXIS_MODEL)


@dataclasses.dataclass
class _ParallelState:
    """Module-global topology record (the reference keeps ~15 globals,
    parallel_state.py:24-54; one dataclass is easier to destroy/inspect)."""

    mesh: Optional[Mesh] = None
    virtual_pipeline_world_size: Optional[int] = None
    virtual_pipeline_rank: Optional[int] = None
    pipeline_split_rank: Optional[int] = None


_STATE = _ParallelState()


def initialize_model_parallel(
    tensor_model_parallel_size: int = 1,
    pipeline_model_parallel_size: int = 1,
    virtual_pipeline_model_parallel_size: Optional[int] = None,
    pipeline_model_parallel_split_rank: Optional[int] = None,
    context_parallel_size: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build and install the global mesh (parallel_state.py:57-184 equivalent).

    The data-parallel size is inferred as
    ``n_devices // (tp * cp * pp)``, mirroring the reference's
    ``world_size % (tp * pp) == 0`` sanity check (``:88-94``).

    Args:
      tensor_model_parallel_size: size of the ``model`` axis.
      pipeline_model_parallel_size: size of the ``pipe`` axis.
      virtual_pipeline_model_parallel_size: number of interleaved model chunks
        per pipeline stage (reference ``:104-111``).
      pipeline_model_parallel_split_rank: stage where the encoder/decoder
        split sits, for T5-style models (reference ``:96-102,165-184``).
      context_parallel_size: size of the ``context`` (sequence) axis — a new
        capability relative to the reference.
      devices: explicit device list; defaults to ``jax.devices()``.

    Returns:
      The installed ``jax.sharding.Mesh``.
    """
    tp = int(tensor_model_parallel_size)
    pp = int(pipeline_model_parallel_size)
    cp = int(context_parallel_size)
    devs = list(devices) if devices is not None else jax.devices()
    world_size = len(devs)
    denom = tp * pp * cp
    if world_size % denom != 0:
        raise RuntimeError(
            f"world size ({world_size}) is not divisible by tensor parallel "
            f"size ({tp}) x pipeline parallel size ({pp}) x context parallel "
            f"size ({cp})"
        )
    dp = world_size // denom
    if virtual_pipeline_model_parallel_size is not None and pp < 2:
        raise RuntimeError(
            "pipeline-model-parallel size should be greater than 1 with "
            "interleaved schedule"
        )

    grid = np.asarray(devs, dtype=object).reshape(pp, dp, cp, tp)
    mesh = Mesh(grid, MESH_AXIS_NAMES)

    _STATE.mesh = mesh
    _STATE.virtual_pipeline_world_size = virtual_pipeline_model_parallel_size
    _STATE.virtual_pipeline_rank = (
        0 if virtual_pipeline_model_parallel_size is not None else None
    )
    _STATE.pipeline_split_rank = pipeline_model_parallel_split_rank
    return mesh


def model_parallel_is_initialized() -> bool:
    """parallel_state.py:198-203 equivalent."""
    return _STATE.mesh is not None


def get_mesh() -> Mesh:
    if _STATE.mesh is None:
        raise RuntimeError(
            "model parallel mesh is not initialized "
            "(call apex_tpu.parallel.initialize_model_parallel first)"
        )
    return _STATE.mesh


def destroy_model_parallel() -> None:
    """parallel_state.py:428-453 equivalent."""
    _STATE.mesh = None
    _STATE.virtual_pipeline_world_size = None
    _STATE.virtual_pipeline_rank = None
    _STATE.pipeline_split_rank = None


# ---------------------------------------------------------------------------
# World sizes (static — known from the mesh shape).
# Ranks are *per-device* values: inside shard_map use
# collectives.axis_rank(axis); these module-level getters cover host-side
# schedule construction, where the reference queried torch.distributed
# (parallel_state.py:205-425).
# ---------------------------------------------------------------------------


def _axis_size(name: str) -> int:
    return get_mesh().shape[name]


def get_tensor_model_parallel_world_size() -> int:
    return _axis_size(AXIS_MODEL)


def get_pipeline_model_parallel_world_size() -> int:
    return _axis_size(AXIS_PIPE)


def get_data_parallel_world_size() -> int:
    return _axis_size(AXIS_DATA)


def get_context_parallel_world_size() -> int:
    return _axis_size(AXIS_CONTEXT)


def get_gradient_reduction_axes() -> Tuple[str, ...]:
    """Mesh axes over which parameter gradients must be averaged.

    With context parallelism each sequence shard produces partial gradients
    for the *full* parameter set, so grad reduction spans ``data`` and
    ``context`` (the reference's data-parallel group, distributed.py:449-451,
    covers only ``data`` because CP does not exist there)."""
    return (AXIS_DATA, AXIS_CONTEXT)


def get_pipeline_model_parallel_split_rank() -> Optional[int]:
    return _STATE.pipeline_split_rank


def get_rank_info_str() -> str:
    """Topology suffix for log records and journal lines.

    The reference formats a per-process (dp, tp, pp, vpp) rank tuple into
    every log record (apex/transformer/log_util.py); under single-process
    SPMD a process holds EVERY rank, so the honest per-process equivalent
    is the mesh topology itself. ``utils.log_util.RankInfoFilter`` and
    ``monitor.journal`` both consume this; empty when no mesh is installed.
    """
    if _STATE.mesh is None:
        return ""
    pp, dp, cp, tp = (_STATE.mesh.shape[a] for a in MESH_AXIS_NAMES)
    vpp = _STATE.virtual_pipeline_world_size
    return (f" mesh(pp{pp} dp{dp} cp{cp} tp{tp}"
            f"{f' vpp{vpp}' if vpp else ''})")


# -- virtual pipeline (interleaved schedule) state --------------------------
# Mirrors parallel_state.py:367-382: the schedule sets the current model
# chunk index while building/running the interleaved 1F1B loop.


def get_virtual_pipeline_model_parallel_world_size() -> Optional[int]:
    return _STATE.virtual_pipeline_world_size


def get_virtual_pipeline_model_parallel_rank() -> Optional[int]:
    return _STATE.virtual_pipeline_rank


def set_virtual_pipeline_model_parallel_rank(rank: Optional[int]) -> None:
    _STATE.virtual_pipeline_rank = rank


# ---------------------------------------------------------------------------
# Stage predicates (host-side, per pipeline stage index).
# The reference's is_pipeline_{first,last}_stage consult the calling rank
# (parallel_state.py:308-330); in SPMD form the pipeline schedule iterates
# stages explicitly, so these take the stage index as an argument.
# ---------------------------------------------------------------------------


def is_pipeline_first_stage(stage: int, ignore_virtual: bool = False) -> bool:
    if not ignore_virtual and _STATE.virtual_pipeline_world_size is not None:
        if _STATE.virtual_pipeline_rank != 0:
            return False
    return stage == 0


def is_pipeline_last_stage(stage: int, ignore_virtual: bool = False) -> bool:
    if not ignore_virtual and _STATE.virtual_pipeline_world_size is not None:
        if _STATE.virtual_pipeline_rank != _STATE.virtual_pipeline_world_size - 1:
            return False
    return stage == get_pipeline_model_parallel_world_size() - 1


def embedding_stages() -> List[int]:
    """Pipeline stages holding (tied) embedding weights: first + last
    (+ encoder/decoder split), reference parallel_state.py:165-184."""
    pp = get_pipeline_model_parallel_world_size()
    stages = [0]
    split = _STATE.pipeline_split_rank
    if split is not None and split not in stages:
        stages.append(split)
    if pp - 1 not in stages:
        stages.append(pp - 1)
    return stages


# ---------------------------------------------------------------------------
# Rank arithmetic parity helpers
# ---------------------------------------------------------------------------


def rank_coords(flat_rank: int) -> Tuple[int, int, int, int]:
    """Map a flat device index to ``(pipe, data, context, model)`` coords.

    Inverse of the flattened mesh order; lets tests assert the reference's
    rank→group contract: TP contiguous (parallel_state.py:142-149), DP
    striding by tp within a pipe block (:119-131), PP striding widest
    (:159-164)."""
    mesh = get_mesh()
    pp, dp, cp, tp = (mesh.shape[a] for a in MESH_AXIS_NAMES)
    if not 0 <= flat_rank < pp * dp * cp * tp:
        raise ValueError(f"rank {flat_rank} out of range")
    m = flat_rank % tp
    c = (flat_rank // tp) % cp
    d = (flat_rank // (tp * cp)) % dp
    p = flat_rank // (tp * cp * dp)
    return (p, d, c, m)


def make_virtual_mesh(
    n_devices: int,
    tensor_model_parallel_size: int = 1,
    pipeline_model_parallel_size: int = 1,
    context_parallel_size: int = 1,
    **kwargs,
) -> Mesh:
    """Convenience for tests/dry-runs: initialize over the first
    ``n_devices`` of ``jax.devices()`` (virtual CPU devices in CI)."""
    devs = jax.devices()
    if len(devs) < n_devices:
        raise RuntimeError(
            f"need {n_devices} devices, have {len(devs)} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N)"
        )
    return initialize_model_parallel(
        tensor_model_parallel_size=tensor_model_parallel_size,
        pipeline_model_parallel_size=pipeline_model_parallel_size,
        context_parallel_size=context_parallel_size,
        devices=devs[:n_devices],
        **kwargs,
    )

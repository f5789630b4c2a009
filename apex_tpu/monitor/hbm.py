"""HBM occupancy monitor: live-array byte curves + lane-padded estimates.

Two regimes of the r4/r5 rounds (2026-07, PERF_NOTES.md) motivated this: a
long-lived process accumulated HBM *below* ``jax.live_arrays()`` (a config
that OOM'd at batch 1 ran fine in a fresh process), and another job's
occupation of the chip made placement fail while compute ran fine. Both
were diagnosed postmortem from bench stderr; this module turns them into
sampled curves: what Python CAN see (``jax.live_arrays()`` totals, padded
and unpadded) over time, so the *visible* residency can be subtracted from
an OOM to expose the below-Python remainder.

Padded accounting: TPU HBM layouts tile the two minor dims — minor to the
128-lane vreg width, second-minor to the sublane count for the dtype (8 for
4-byte, 16 for 2-byte, 32 for 1-byte elements). A ``(b, h, sq, 1)`` f32
operand therefore occupies 128x its ``nbytes`` at a custom-call boundary
(2 GB for 16 MB of lse at 512k tokens — the measured tax that forced the
streamed kernels' dense lse tables, ``ops/flash_attention.py``). The same
rule is applied per live array here, as an estimate of placed footprint.

All functions are host-side only: no device syncs, safe to call on the hot
path after a step's loss fetch.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

_NUM_LANES = 128
_SUBLANE_BYTES = 32  # sublanes x itemsize is constant: 8x4 = 16x2 = 32x1


def lane_padded_bytes(shape, itemsize: int) -> int:
    """Bytes of one array under TPU (sublane, lane) tiling.

    Minor dim pads to 128 lanes; second-minor pads to ``32 // itemsize``
    sublanes (f32: 8, bf16: 16, int8: 32). Rank-0/1 arrays are laid out as
    a single (1, n) tile row.
    """
    itemsize = max(int(itemsize), 1)
    dims = [int(d) for d in shape] or [1]
    if len(dims) == 1:
        dims = [1] + dims
    sublanes = max(_SUBLANE_BYTES // itemsize, 1)
    minor = -(-dims[-1] // _NUM_LANES) * _NUM_LANES
    second = -(-dims[-2] // sublanes) * sublanes
    n = minor * second
    for d in dims[:-2]:
        n *= d
    return n * itemsize


def live_array_stats(platform: Optional[str] = None) -> Dict[str, Any]:
    """Snapshot of Python-visible device residency.

    Returns ``{"live_bytes", "padded_bytes", "count", "largest_bytes"}``
    summed over ``jax.live_arrays(platform)``. ``live_bytes`` counts logical
    ``nbytes`` (global, for sharded arrays); ``padded_bytes`` applies the
    lane/sublane tiling estimate per array. Deleted arrays report 0.
    """
    import jax

    live = padded = largest = 0
    count = 0
    try:
        arrays = jax.live_arrays(platform) if platform else jax.live_arrays()
    except Exception:  # noqa: BLE001 - no backend yet
        arrays = []
    for a in arrays:
        try:
            if getattr(a, "is_deleted", lambda: False)():
                continue
            nb = int(a.nbytes)
            pb = lane_padded_bytes(a.shape, a.dtype.itemsize)
        except Exception:  # noqa: BLE001 - tokens/exotic avals
            continue
        live += nb
        padded += pb
        largest = max(largest, nb)
        count += 1
    return {"live_bytes": live, "padded_bytes": padded, "count": count,
            "largest_bytes": largest}


# ---------------------------------------------------------------------------
# Sequence-parallel activation accounting: the tp-x memory claim as a number
# ---------------------------------------------------------------------------

#: the (b, s, h)-shaped tensors a transformer layer materializes OUTSIDE the
#: TP GEMM regions — between a row-parallel reduce (psum or psum_scatter)
#: and the next column-parallel entry. These are exactly the tensors
#: sequence parallelism shrinks by tp: under plain TP they are replicated
#: full-sequence on every TP rank; under ``sequence_parallel=True`` each
#: rank holds its (b, s/tp, h) shard (models/_transformer.py regions).
SEQUENCE_REGION_SITES = (
    "ln1_out",          # LN before attention (input to the qkv column GEMM)
    "attn_dropout_out",  # post-attention dropout output
    "residual1",        # first residual sum
    "ln2_out",          # LN before the MLP
    "mlp_dropout_out",  # post-MLP dropout output
    "residual2",        # second residual sum (the layer's carry)
)


def sequence_region_layer_bytes(
    batch: int,
    seq: int,
    hidden: int,
    *,
    tp: int = 1,
    sequence_parallel: bool = False,
    itemsize: int = 2,
    padded: bool = True,
) -> Dict[str, Any]:
    """Per-layer bytes of the sequence-region activations on ONE TP rank.

    ``sequence_parallel=True`` divides the sequence dim by ``tp`` (the
    reduce-scatter shard); ``padded`` applies :func:`lane_padded_bytes`
    (the T(8,128) layout these tensors occupy when resident). A trace-time
    ESTIMATE of the shape algebra, not a measurement — remat/fusion decide
    which sites are simultaneously live, but every site shrinks by the same
    factor, so the plain/SP ratio is exact.
    """
    s_local = seq // tp if (sequence_parallel and tp > 1) else seq
    shape = (batch, s_local, hidden)
    per_site = (lane_padded_bytes(shape, itemsize) if padded
                else batch * s_local * hidden * itemsize)
    return {
        "shape": list(shape),
        "seq_local": s_local,
        "per_site_bytes": per_site,
        "sites": list(SEQUENCE_REGION_SITES),
        "layer_bytes": per_site * len(SEQUENCE_REGION_SITES),
    }


def sequence_parallel_activation_report(
    batch: int,
    seq: int,
    hidden: int,
    num_layers: int,
    tp: int,
    *,
    itemsize: int = 2,
) -> Dict[str, Any]:
    """Plain-TP vs sequence-parallel per-layer activation bytes, per rank.

    The evidence artifact behind the "every activation in the non-TP
    regions shrinks by tp" claim (benchmarks/overlap_evidence.py,
    PERF_NOTES.md): same shape algebra as the layer regions, reported as
    numbers rather than prose."""
    plain = sequence_region_layer_bytes(
        batch, seq, hidden, tp=tp, sequence_parallel=False,
        itemsize=itemsize)
    sp = sequence_region_layer_bytes(
        batch, seq, hidden, tp=tp, sequence_parallel=True, itemsize=itemsize)
    return {
        "batch": batch, "seq": seq, "hidden": hidden,
        "num_layers": num_layers, "tp": tp, "itemsize": itemsize,
        "sites_per_layer": len(SEQUENCE_REGION_SITES),
        "plain_per_layer_bytes": plain["layer_bytes"],
        "sp_per_layer_bytes": sp["layer_bytes"],
        "plain_total_bytes": plain["layer_bytes"] * num_layers,
        "sp_total_bytes": sp["layer_bytes"] * num_layers,
        "savings_bytes_per_layer":
            plain["layer_bytes"] - sp["layer_bytes"],
        "ratio": round(plain["layer_bytes"] / max(sp["layer_bytes"], 1), 3),
    }


# ---------------------------------------------------------------------------
# Optimizer-state accounting: the ZeRO memory claim as a number
# ---------------------------------------------------------------------------

#: fp32 arrays the O2 optimizer keeps per parameter: master + Adam/LAMB
#: exp_avg + exp_avg_sq (amp/frontend.py MPOptState + FusedAdamState)
OPTIMIZER_STATE_COPIES = 3


def optimizer_state_report(
    params: Any,
    dp: int,
    *,
    state_copies: int = OPTIMIZER_STATE_COPIES,
    itemsize: int = 4,
) -> Dict[str, Any]:
    """Replicated vs ZeRO-sharded optimizer-state bytes on ONE rank.

    ``params`` is any pytree with shaped leaves (arrays or
    ShapeDtypeStructs — e.g. ``jax.eval_shape(model.init, key)`` for the
    345M flagship shape without touching HBM). Replicated: every rank
    holds ``state_copies`` fp32 arrays per param, lane-padded in the
    param's own shape. ZeRO over ``dp`` ranks
    (``amp.MixedPrecisionOptimizer(zero_axis=...)``): every rank holds
    ``state_copies`` 1-D fp32 chunks of ``ceil(size/dp)`` elements — 1-D
    chunks tile as a single (1, n) row, so the padded footprint is also
    ~1/dp. Same shape-algebra-as-evidence discipline as
    :func:`sequence_parallel_activation_report`."""
    import jax

    from apex_tpu.optimizers.distributed import chunk_size

    # a ZeRO chunk is a large CONTIGUOUS flat buffer resident in HBM, not
    # a (1, n) operand row at a custom-call boundary: model it as packed
    # linear storage rounded up to whole (sublanes x 128-lane) tile
    # granules — the (1, n) single-row rule (lane_padded_bytes on rank-1)
    # would book an 8x sublane tax that a multi-MB flat vector does not pay
    sublanes = max(_SUBLANE_BYTES // max(int(itemsize), 1), 1)
    granule = sublanes * _NUM_LANES

    repl = repl_padded = zero = zero_padded = 0
    count = n_leaves = 0
    for leaf in jax.tree.leaves(params):
        shape = tuple(int(d) for d in getattr(leaf, "shape", ()) or ())
        size = 1
        for d in shape:
            size *= d
        k = chunk_size(size, dp)
        repl += size * itemsize
        repl_padded += lane_padded_bytes(shape, itemsize)
        zero += k * itemsize
        zero_padded += -(-k // granule) * granule * itemsize
        count += size
        n_leaves += 1
    return {
        "dp": dp, "param_count": count, "param_leaves": n_leaves,
        "state_copies": state_copies, "itemsize": itemsize,
        "replicated_bytes_per_rank": repl * state_copies,
        "replicated_padded_bytes_per_rank": repl_padded * state_copies,
        "zero_bytes_per_rank": zero * state_copies,
        "zero_padded_bytes_per_rank": zero_padded * state_copies,
        "savings_bytes_per_rank": (repl - zero) * state_copies,
        "ratio": round(repl / max(zero, 1), 3),
    }


def param_state_report(
    params: Any,
    dp: int,
    *,
    state_copies: int = OPTIMIZER_STATE_COPIES,
    master_itemsize: int = 4,
) -> Dict[str, Any]:
    """Replicated vs ZeRO-1/2 vs ZeRO-3 per-rank param+master+moment bytes.

    Extends :func:`optimizer_state_report` to the WORKING params — the last
    replicated O(model) tensor ZeRO-3 removes. ``params`` is any pytree
    with shaped leaves (arrays or ShapeDtypeStructs, e.g.
    ``jax.eval_shape(model.init, key)`` cast to the compute policy, so each
    leaf's own dtype prices the working copy — bf16 under O2). Columns,
    all per rank:

    - ``replicated``  — full working params + ``state_copies`` full fp32
      arrays per param (no ZeRO);
    - ``zero12``      — full working params + fp32 state as 1-D
      ``ceil(size/dp)`` chunks (PR-5 ``zero_axis=...``: one
      implementation, masters and moments always shard together, so
      ZeRO-1 and ZeRO-2 price identically here);
    - ``zero3``       — working params AND fp32 state as chunks
      (``zero_level=3``: the bf16 model persists 1/dp, each layer
      all-gathered just-in-time inside the layer loop — the transient
      gather working set is O(1 layer), not priced as residency).

    Chunks are priced as packed linear storage rounded to whole tile
    granules (the :func:`optimizer_state_report` rule).
    """
    import jax
    import numpy as np

    from apex_tpu.optimizers.distributed import chunk_size

    def tile_granule(itemsize):
        sublanes = max(_SUBLANE_BYTES // max(int(itemsize), 1), 1)
        return sublanes * _NUM_LANES

    granule = tile_granule(master_itemsize)

    p_full = p_full_padded = p_chunk = 0
    o_full = o_full_padded = o_chunk = 0
    count = n_leaves = 0
    for leaf in jax.tree.leaves(params):
        shape = tuple(int(d) for d in getattr(leaf, "shape", ()) or ())
        try:
            itemsize = int(np.dtype(leaf.dtype).itemsize)
        except Exception:  # noqa: BLE001 - dtype-less leaves price as bf16
            itemsize = 2
        size = 1
        for d in shape:
            size *= d
        k = chunk_size(size, dp)
        # working chunks round to the granule of THEIR dtype (bf16: 2048
        # elems), masters/moments to the fp32 granule
        p_granule = tile_granule(itemsize)
        p_full += size * itemsize
        p_full_padded += lane_padded_bytes(shape, itemsize)
        p_chunk += -(-k // p_granule) * p_granule * itemsize
        o_full += size * master_itemsize
        o_full_padded += lane_padded_bytes(shape, master_itemsize)
        o_chunk += -(-k // granule) * granule * master_itemsize
        count += size
        n_leaves += 1
    o_full *= state_copies
    o_full_padded *= state_copies
    o_chunk *= state_copies
    table = {
        "replicated": {"param_bytes": p_full, "opt_bytes": o_full,
                       "total_bytes": p_full + o_full},
        "zero12": {"param_bytes": p_full, "opt_bytes": o_chunk,
                   "total_bytes": p_full + o_chunk},
        "zero3": {"param_bytes": p_chunk, "opt_bytes": o_chunk,
                  "total_bytes": p_chunk + o_chunk},
    }
    return {
        "dp": dp, "param_count": count, "param_leaves": n_leaves,
        "state_copies": state_copies, "master_itemsize": master_itemsize,
        "per_rank": table,
        "replicated_padded_param_bytes": p_full_padded,
        "param_ratio": round(p_full / max(p_chunk, 1), 3),
        "total_ratio": round((p_full + o_full)
                             / max(p_chunk + o_chunk, 1), 3),
    }


def opt_state_bytes(opt_state: Any) -> int:
    """Per-rank bytes of a (possibly sharded) optimizer-state pytree.

    For committed global arrays the first addressable shard's bytes ARE
    the per-device footprint — a replicated leaf's shard is the full
    array, a ZeRO chunk leaf's shard is 1/n of it — so the same call
    reports the honest per-rank number either way. Host-side only; used
    to arm ``MetricsJournal.set_opt_state_bytes``.
    """
    import jax

    total = 0
    for leaf in jax.tree.leaves(opt_state):
        try:
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                total += int(shards[0].data.nbytes)
            else:
                total += int(leaf.nbytes)
        except Exception:  # noqa: BLE001 - abstract/exotic leaves
            continue
    return total


def param_bytes(params: Any) -> int:
    """Per-rank bytes of a (possibly chunk-sharded) working-param pytree —
    the same addressable-shard accounting as :func:`opt_state_bytes`: a
    replicated leaf books its full array, a ZeRO-3 chunk leaf its 1/n
    shard. Host-side only; arms ``MetricsJournal.set_param_bytes``."""
    return opt_state_bytes(params)


class HBMMonitor:
    """Sampling monitor over :func:`live_array_stats`.

    >>> mon = HBMMonitor(journal=journal)   # journal optional
    >>> mon.sample("before")                # establishes the baseline
    >>> ...training...
    >>> mon.sample("after")
    >>> mon.growth_bytes()                  # retained-leak detector

    ``growth_bytes`` is last-sample minus baseline ``live_bytes``: a loop
    that retains arrays (or exception tracebacks pinning device buffers —
    the bench.py OOM-ladder trap) shows monotone growth; a healthy loop is
    flat. The below-Python regime is the complement: an OOM whose ladder
    rung exceeds HBM while ``growth_bytes`` stays ~0 means the occupation
    is NOT Python-visible (fresh-process territory, bench.py stage 0).
    """

    def __init__(self, journal=None, label: str = ""):
        self.journal = journal
        self.label = label
        self.samples = []

    def sample(self, tag: str = "") -> Dict[str, Any]:
        stats = live_array_stats()
        stats["tag"] = tag
        self.samples.append(stats)
        if self.journal is not None:
            self.journal.log(dict(stats, kind="hbm", label=self.label))
        return stats

    @property
    def baseline(self) -> Optional[Dict[str, Any]]:
        return self.samples[0] if self.samples else None

    def growth_bytes(self) -> int:
        """Python-visible residency growth since the first sample."""
        if len(self.samples) < 2:
            return 0
        return self.samples[-1]["live_bytes"] - self.samples[0]["live_bytes"]

    def peak_bytes(self) -> int:
        return max((s["live_bytes"] for s in self.samples), default=0)

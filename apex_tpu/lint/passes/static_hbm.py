"""static-hbm pass: live-range peak-bytes estimate + lane-padding blowups.

Two HBM facts this repo has paid for on chip (CLAUDE.md gotchas,
PERF_NOTES.md) become whole-program checks over the shared walk
(:mod:`apex_tpu.lint.ir`):

1. **peak residency estimate** — a live-range scan over the step program:
   walk each jaxpr body in order, birth a value's bytes at its defining
   equation, free them after its last use (never freeing the body's
   outputs), and recurse into call-like equations by charging the inner
   body's peak OVER its operands at the call point. Reported both logical
   and under the Mosaic T(8,128) tiling model (minor dim -> 128 lanes,
   second-minor -> ``32/itemsize`` sublanes; ``monitor.hbm.
   lane_padded_bytes``, the same rule ``ops/flash_attention.py``
   calibrates). An ESTIMATE, deliberately conservative: XLA fuses
   intermediates and schedules frees earlier, so the figure upper-bounds
   the placed footprint — cross-checkable against ``monitor.hbm``'s
   measured ``live_array_stats`` (the audit and tests pin the ratio
   within 2x).
2. **lane-padded blowups** — every operand/result of a custom-call
   boundary (``pallas_call`` et al.) and the step signature audited for
   the padding tax: a ``(b, h, sq, 1)`` f32 operand occupies 128x its
   ``nbytes`` at such a boundary (2 GB for 16 MB of lse at 512k tokens —
   the measured tax that forced the streamed kernels' dense lse tables).

3. **sharded residency model** (:func:`sharded_residency`, ISSUE 18) —
   the per-rank persistent-state arithmetic for a PLACEMENT CANDIDATE
   without tracing it: working params, fp32 master/moment chunks,
   transient grads, the error-feedback residual and the ZeRO-3 gather
   window ((``zero3_prefetch``+1) layers), each under the same chunk
   granule pricing as ``monitor.hbm.param_state_report`` (tests pin the
   tp=pp=1 columns equal). This is what the auto-parallelism planner
   (:mod:`apex_tpu.plan`) prices HBM feasibility with for ZeRO-1/2/3
   candidates — the live-range scan above needs a traced program; the
   residency model needs only the abstract param tree.

No reference analog: the reference ships no static analysis
(apex_tpu/lint/__init__.py).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from apex_tpu.lint import ir as ir_mod

RULE = "static-hbm"

#: monitor.hbm tiling constants (T(8,128): 128 lanes, 32-byte sublane
#: group) — a ZeRO chunk prices as packed linear storage rounded to whole
#: (sublanes x lanes) granules, the ``param_state_report`` rule
_NUM_LANES = 128
_SUBLANE_BYTES = 32

#: fp32 arrays the O2 optimizer keeps per parameter (master + exp_avg +
#: exp_avg_sq — monitor.hbm.OPTIMIZER_STATE_COPIES)
_STATE_COPIES = 3


def _var_bytes(var) -> Tuple[int, int]:
    """(logical, lane-padded) bytes of one jaxpr variable; (0, 0) for
    literals/tokens."""
    if ir_mod.is_literal(var):
        return 0, 0
    aval = ir_mod.aval_of(var)
    if aval is None:
        return 0, 0
    return (ir_mod.aval_bytes(aval, padded=False),
            ir_mod.aval_bytes(aval, padded=True))


def _jaxpr_peak(jaxpr) -> Tuple[int, int]:
    """(peak logical, peak padded) bytes of one body via live-range scan.

    Inputs/consts live from entry; each equation births its outputs at its
    program point; a value dies after its last consuming equation unless
    it is a body output. A call-like equation charges, at its point, the
    inner body's peak minus the inner inputs (those bytes are the
    operands, already live here) — the transient the call adds above its
    arguments. cond charges the worst branch.
    """
    last_use: Dict[int, int] = {}
    for idx, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not ir_mod.is_literal(v):
                last_use[id(v)] = idx
    never_free = {id(v) for v in jaxpr.outvars if not ir_mod.is_literal(v)}

    live = live_pad = 0
    sizes: Dict[int, Tuple[int, int]] = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        if id(v) in sizes:
            continue
        nb, pb = _var_bytes(v)
        sizes[id(v)] = (nb, pb)
        live += nb
        live_pad += pb
    peak, peak_pad = live, live_pad

    for idx, eqn in enumerate(jaxpr.eqns):
        inner_extra = inner_extra_pad = 0
        for sub in ir_mod.sub_jaxprs(eqn):
            sp, spp = _jaxpr_peak(sub)
            sub_in = sum(_var_bytes(v)[0] for v in sub.invars)
            sub_in_pad = sum(_var_bytes(v)[1] for v in sub.invars)
            inner_extra = max(inner_extra, sp - sub_in)
            inner_extra_pad = max(inner_extra_pad, spp - sub_in_pad)
        out_b = out_pb = 0
        for v in eqn.outvars:
            nb, pb = _var_bytes(v)
            sizes[id(v)] = (nb, pb)
            # an output nothing ever consumes (DropVar) dies on the spot
            last_use.setdefault(id(v), idx)
            out_b += nb
            out_pb += pb
        if eqn.primitive.name in ("scan", "while"):
            # stacked loop outputs accumulate WHILE the body's transients
            # are live: charge both
            point, point_pad = out_b + inner_extra, out_pb + inner_extra_pad
        else:
            # a plain call's (pjit/cond/remat/custom_vjp) inner peak
            # already holds the outputs at body end — max, not sum, or
            # every nested jit double-books its own results
            point = max(out_b, inner_extra)
            point_pad = max(out_pb, inner_extra_pad)
        peak = max(peak, live + max(point, 0))
        peak_pad = max(peak_pad, live_pad + max(point_pad, 0))
        live += out_b
        live_pad += out_pb
        freed = set()
        for v in list(eqn.invars) + list(eqn.outvars):
            if ir_mod.is_literal(v):
                continue
            vid = id(v)
            if (vid not in freed and last_use.get(vid) == idx
                    and vid not in never_free and vid in sizes):
                freed.add(vid)
                nb, pb = sizes.pop(vid)
                live -= nb
                live_pad -= pb
    return peak, peak_pad


def _audit_boundary_aval(aval, where: str, threshold: float,
                         min_bytes: int) -> Dict[str, Any]:
    """One lane-padding blowup finding, or None (the trace.py
    ``_audit_aval`` rule, emitted under this pass's name)."""
    nb = ir_mod.aval_bytes(aval, padded=False)
    pb = ir_mod.aval_bytes(aval, padded=True)
    if getattr(aval, "size", 0) <= 1:
        return None  # a scalar cannot avoid its one tile; pure noise
    if nb <= 0 or pb < threshold * nb or (pb - nb) < min_bytes:
        return None
    shape = tuple(int(d) for d in aval.shape)
    hint = ""
    if shape and shape[-1] == 1:
        hint = ("; carry per-row stats as dense (rows, blk) tables, not "
                "(rows, 1) columns (flash_attention.py lse/delta)")
    elif shape and shape[-1] < 128:
        hint = ("; prefer minor dims that are multiples of 128 (e.g. "
                "head_dim 128 at extreme sequence lengths)")
    return {
        "rule": RULE, "where": where, "shape": list(shape),
        "dtype": str(aval.dtype), "bytes": nb, "padded_bytes": pb,
        "waste_ratio": round(pb / nb, 2),
        "message": (f"{where}: {shape} {aval.dtype} occupies {pb} bytes "
                    f"under T(8,128) tiling ({round(pb / nb, 1)}x its {nb})"
                    f"{hint}"),
    }


def static_hbm_pass(ir, *, threshold: float = 2.0,
                    min_bytes: int = 1 << 16,
                    max_findings: int = 20) -> Dict[str, Any]:
    """Peak-bytes estimate + boundary lane-padding findings over one
    shared walk. Returns ``{peak_bytes, peak_padded_bytes,
    resident_in_bytes, resident_out_bytes, findings, audited,
    findings_truncated}`` — findings sorted by wasted bytes, worst first.
    """
    ir = ir_mod.ensure_ir(ir)
    jaxpr = ir.jaxpr
    peak, peak_pad = _jaxpr_peak(jaxpr)
    res_in = sum(_var_bytes(v)[0] for v in jaxpr.invars)
    res_out = sum(_var_bytes(v)[0] for v in jaxpr.outvars)

    findings: List[Dict[str, Any]] = []
    audited = 0
    seen = set()

    def audit(var, where, node=None):
        nonlocal audited
        aval = ir_mod.aval_of(var)
        if aval is None or not hasattr(aval, "shape"):
            return
        key = (where, tuple(aval.shape), str(aval.dtype))
        if key in seen:
            return
        seen.add(key)
        audited += 1
        f = _audit_boundary_aval(aval, where, threshold, min_bytes)
        if f is not None:
            if node is not None:
                src = node.source()
                if src:
                    f["path"], f["line"] = src
            findings.append(f)

    for i, v in enumerate(jaxpr.invars):
        audit(v, f"input[{i}]")
    for i, v in enumerate(jaxpr.outvars):
        audit(v, f"output[{i}]")
    for node in ir.nodes:
        name = node.eqn.primitive.name
        if name not in ir_mod.BOUNDARY_PRIMS:
            continue
        for v in node.eqn.invars:
            audit(v, f"{name} operand", node)
        for v in node.eqn.outvars:
            audit(v, f"{name} result", node)

    findings.sort(key=lambda f: f["bytes"] - f["padded_bytes"])
    truncated = max(0, len(findings) - max_findings)
    return {
        "peak_bytes": int(peak),
        "peak_padded_bytes": int(peak_pad),
        "resident_in_bytes": int(res_in),
        "resident_out_bytes": int(res_out),
        "findings": findings[:max_findings],
        "findings_truncated": truncated,
        "audited": audited,
    }


ir_mod.register_pass(
    RULE,
    "live-range peak-bytes estimate under the T(8,128) tiling model + "
    "lane-padded blowups at custom-call boundaries")(static_hbm_pass)


# ---------------------------------------------------------------------------
# sharded residency model (the planner's HBM feasibility arithmetic)
# ---------------------------------------------------------------------------


def _tile_granule(itemsize: int) -> int:
    sublanes = max(_SUBLANE_BYTES // max(int(itemsize), 1), 1)
    return sublanes * _NUM_LANES


def _chunk_bytes(k: int, itemsize: int) -> int:
    """Packed linear chunk of ``k`` elements rounded to whole tile
    granules — byte-identical to ``param_state_report``'s pricing."""
    granule = _tile_granule(itemsize)
    return -(-k // granule) * granule * itemsize


def _walk_params(tree, path=()):
    if isinstance(tree, dict):
        for key in tree:
            yield from _walk_params(tree[key], path + (str(key),))
    elif isinstance(tree, (list, tuple)):
        for i, sub in enumerate(tree):
            yield from _walk_params(sub, path + (str(i),))
    elif tree is not None:
        yield path, tree


def sharded_residency(
    params: Any,
    *,
    dp: int = 1,
    model_shards: int = 1,
    zero_level: int = 0,
    zero3_prefetch: int = 0,
    reduce_dtype: Optional[str] = None,
    vocab_size: Optional[int] = None,
    vocab_shards: Optional[int] = None,
    layer_key: str = "layers",
    expert_shards: int = 1,
    state_copies: int = _STATE_COPIES,
    update_copies: int = 2,
    master_itemsize: int = 4,
) -> Dict[str, Any]:
    """Per-rank persistent HBM bytes of one placement candidate.

    ``params`` is any nested-dict pytree with shaped leaves (e.g. the
    ``jax.eval_shape`` abstract init cast to the compute policy — leaf
    dtypes price the working copies). Sharding model:

    - leaves under ``layer_key`` divide by ``model_shards`` (tp*pp: the
      layer slab is split across tensor columns and pipeline stages);
      MoE expert leaves (path contains ``"moe"``, router excluded)
      additionally divide by ``expert_shards`` (the expert axis);
    - other leaves with a ``vocab_size`` dim (the vocab-parallel
      embedding / output head) divide by ``vocab_shards`` (default
      ``model_shards``; the planner passes the tp factor alone — under
      pp the embedding lives whole on its boundary stage, so dividing
      by tp*pp would undercount the worst rank);
    - remaining non-layer leaves (final LN, learned positions) stay
      replicated.

    On top of the sharded leaf sizes, the ZeRO columns reprice exactly as
    ``monitor.hbm.param_state_report`` (chunks = packed linear storage
    rounded to whole T(8,128) granules of their own dtype; masters and
    ``state_copies-1`` moments at ``master_itemsize``), plus the pieces
    the report leaves out because they are planner concerns:

    - ``grad_bytes``: the transient working-dtype grad tree (full for
      zero<3; two layers' worth + the non-layer leaves at zero3 — grads
      scatter per layer inside the loop);
    - ``residual_bytes``: the quantized-collective error-feedback
      residual (``reduce_dtype`` set, zero 1/2): fp32 at FULL padded
      leaf size per rank (``amp.frontend._init_residual``), empty for
      expert-sharded leaves;
    - ``gather_bytes``: the ZeRO-3 just-in-time gather window —
      ``(zero3_prefetch + 1)`` fully-gathered layers
      (``models/_transformer`` run_layers / ``_prefetched_zero3_drive``:
      peak param residency N+1 layers + chunks);
    - ``update_bytes``: ``(update_copies - 1) x`` (params + opt state) —
      a NON-DONATING step holds old and new state simultaneously (the
      step programs are built without donation; the same 2x the audit's
      ``--hbm-check`` bound documents).

    Returns the component dict + ``total_bytes``; tests pin the
    tp=pp=1 ``param_bytes``/``opt_bytes`` columns equal to
    ``param_state_report``'s (345M @ dp=8: 710 -> 89 MB).
    """
    import numpy as np

    from apex_tpu.optimizers.distributed import chunk_size

    dp = max(int(dp), 1)
    model_shards = max(int(model_shards), 1)
    expert_shards = max(int(expert_shards), 1)
    zero = int(zero_level or 0)

    param_bytes = opt_bytes = grad_bytes = residual_bytes = 0
    layer_slab_bytes = 0
    num_layers = None
    param_count = 0

    for path, leaf in _walk_params(params):
        shape = tuple(int(d) for d in getattr(leaf, "shape", ()) or ())
        try:
            itemsize = int(np.dtype(leaf.dtype).itemsize)
        except Exception:  # noqa: BLE001 - dtype-less leaves price as bf16
            itemsize = 2
        size = 1
        for d in shape:
            size *= d
        in_layers = layer_key in path
        is_expert = (in_layers and expert_shards > 1 and "moe" in path
                     and "router" not in path)
        div = 1
        if in_layers:
            div *= model_shards
            if num_layers is None and shape:
                num_layers = shape[0]
            if is_expert:
                div *= expert_shards
        elif vocab_size and vocab_size in shape:
            div *= max(int(vocab_shards or model_shards), 1)
        size_rank = -(-size // div)
        param_count += size_rank
        # expert leaves are already data-axis-sharded: ZeRO keeps the
        # fp32 state as the LOCAL shard, never chunks further, and the
        # residual leaf is empty (amp.frontend: sharded leaves -> (0,))
        zdiv = 1 if is_expert else dp
        k = chunk_size(size_rank, zdiv)
        p_here = (_chunk_bytes(k, itemsize) if zero >= 3
                  else size_rank * itemsize)
        o_here = ((_chunk_bytes(k, master_itemsize) if zero >= 1
                   else size_rank * master_itemsize) * state_copies)
        param_bytes += p_here
        opt_bytes += o_here
        if in_layers:
            layer_slab_bytes += size_rank * itemsize
        if zero < 3:
            grad_bytes += size_rank * itemsize
        if reduce_dtype and zero in (1, 2) and not is_expert:
            residual_bytes += chunk_size(size_rank, zdiv) * zdiv * 4

    per_layer_bytes = (layer_slab_bytes // max(num_layers or 1, 1))
    gather_bytes = 0
    if zero >= 3:
        window = int(zero3_prefetch or 0) + 1
        gather_bytes = window * per_layer_bytes
        # zero3 grads scatter per layer inside the loop: ~2 in-flight
        # full layers (the layer being differentiated + the chunk
        # all_to_all in flight), never the whole tree
        grad_bytes = 2 * per_layer_bytes
    update_bytes = max(int(update_copies) - 1, 0) * (param_bytes + opt_bytes)
    total = (param_bytes + opt_bytes + grad_bytes + residual_bytes
             + gather_bytes + update_bytes)
    return {
        "dp": dp, "model_shards": model_shards, "zero_level": zero,
        "zero3_prefetch": int(zero3_prefetch or 0),
        "param_count": int(param_count),
        "num_layers": int(num_layers or 0),
        "per_layer_bytes": int(per_layer_bytes),
        "param_bytes": int(param_bytes),
        "opt_bytes": int(opt_bytes),
        "grad_bytes": int(grad_bytes),
        "residual_bytes": int(residual_bytes),
        "gather_bytes": int(gather_bytes),
        "update_bytes": int(update_bytes),
        "total_bytes": int(total),
    }

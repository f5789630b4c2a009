"""Engine 2: jaxpr-level hazard analyzers for jitted step functions.

Hazards XLA will compile without complaint but that this repo has paid for
on chip (PERF_NOTES.md, CLAUDE.md gotchas):

- ``lane-padding``     (:func:`lane_padding_report`) -- bytes lost to the
  T(8,128) minor-dim tiling at HBM/custom-call boundaries: a ``(b,h,sq,1)``
  f32 operand occupies 128x its ``nbytes`` (2 GB for 16 MB of lse at 512k
  tokens), ``d=32`` heads pad 4x. Uses the same tiling rules as the
  resident-layout estimator in ``ops/flash_attention.py``
  (``_resident_vmem_bytes``, exported ``NUM_LANES``) via
  ``monitor.hbm.lane_padded_bytes``.
- ``grad-transpose``   (:func:`transpose_hazards`) -- a ``psum``/``pmean``
  of the scalar loss inside the differentiated region: its transpose shows
  up as an EXTRA scalar collective in the backward jaxpr and over-counts
  gradients by the axis size under ``check_vma=False``
  (parallel/collectives.py conventions; the identity-backward wrapper in
  tensor_parallel/mappings.py:62-79 leaves no backward collective).
- ``recompile-hazard`` (:func:`recompile_hazards`) -- weak-type / python-
  scalar leakage in a step signature, the shape/dtype churn the
  ``monitor.diagnose.RecompileTracker`` counts at runtime; this scanner
  names the offending leaves before the first recompile.
- ``sp-regression``    (:func:`sequence_parallel_hazards`) -- a ``psum`` of
  an ACTIVATION on the TP axis inside a sequence-parallel forward: the
  mode's whole point is that those all-reduces decompose into
  ``psum_scatter``/``all_gather`` conjugates
  (tensor_parallel/mappings.py table 2), and a refactor that reintroduces
  one compiles without complaint -- this scanner is the only tripwire.
- ``zero-redundancy``  (:func:`zero_redundancy_hazards`) -- a full-size
  grad ``psum`` on the data axis in a step whose optimizer is ZeRO-sharded
  (``MixedPrecisionOptimizer(zero_axis=...)``): the optimizer's
  psum_scatter IS that reduction, so the surviving all-reduce silently
  double-counts the averaging; same tripwire shape as ``sp-regression``.
- ``zero3-bulk-gather`` (:func:`zero3_gather_hazards`) -- a MODEL-SIZED
  ``all_gather`` result on the zero axis in a fully-sharded (ZeRO-3) step:
  params must stay 1/n chunks gathered just-in-time per layer
  (models/_transformer.run_layers ``chunk_meta``); a whole-stack or
  post-update bulk gather silently returns peak HBM to O(model).
- ``unprefetched-gather`` (:func:`unprefetched_gather_hazards`) -- an
  UNROLLED ZeRO-3 step whose per-layer chunk all-gathers sit inside the
  rematerialized layer bodies: each gather (and its backward re-gather)
  is then strictly serialized with that layer's compute, so the exposed
  gather time the step-anatomy overlap fraction measures cannot shrink;
  the double-buffered drive (``zero3_prefetch``) lifts them out as free
  equations issued N layers ahead.
- ``untimed-schedule``  (:func:`untimed_schedule_hazards`) -- a pipeline
  schedule drive that ran while a span tracer was armed but emitted no
  pipe spans (``monitor/tracing.py``): the step-anatomy layer exists so
  bubble fraction and slot timings are MEASURED, and a harness that
  drives the compiled ring under an armed tracer without the traced
  tick drive silently regresses the timeline back to census-only.
- ``quantized-comm``    (:func:`quantized_comm_hazards`) -- a step that
  requests a quantized grad reduce (``MixedPrecisionOptimizer
  reduce_dtype``) but whose jaxpr still moves a >= 2-byte bulk reduce
  payload on the zero axis (the fp32 psum_scatter survived), or that
  quantizes grads with no error-feedback residual leaf in the optimizer
  state -- bias then accumulates instead of telescoping.

- ``moe-dispatch``      (:func:`moe_dispatch_hazards`) -- an expert-
  parallel MoE step with NO dispatch ``all_to_all`` over the expert axis
  in its trace (the experts silently run replicated -- dense FLOPs at
  sparse prices), or a step that requests a quantized dispatch wire
  (``GPTConfig.moe_dispatch_dtype``) yet ships a dispatch-SHAPED bulk
  ``all_to_all`` payload at >= 2 bytes/elem. Dispatch payloads are
  classified by rank (>= 3: the (experts, capacity, hidden) token
  buckets) so the rank-2 ZeRO grad-chunk all_to_alls sharing the same
  mesh axis never pollute the verdict.

- ``decode-recompile``  (:func:`decode_recompile_hazards`) -- a serving
  decode step whose jit signature DRIFTS across ticks (growing per-request
  KV shapes, python-int position/tick leaks): one recompile per generated
  token, the latency cliff the paged cache + fixed slot arrays exist to
  prevent (apex_tpu/serve/engine.py). ``extra_streams`` audits the chunked
  -prefill and speculative-verify programs' tick argument streams by the
  same rules (a growing chunk count or python-int draft length = one
  recompile per request).

All analyzers are trace-time only (``jax.make_jaxpr``; no compile, no
device work) and return plain dicts/lists of findings shaped like engine
1's (rule/message), so CLI and journal consumers render them uniformly.

Since ISSUE 13 every analyzer here runs on the SHARED single-trace walker
(:mod:`apex_tpu.lint.ir`): ``fn`` may be a callable (traced once), a
pre-traced ``ClosedJaxpr``, or a :class:`apex_tpu.lint.ir.StepIR` — hand
the same StepIR to N analyzers and the step traces and walks exactly once
(the audit gate and tests/test_lint.py's module-scoped fixtures do).
Public signatures are unchanged.
"""

from __future__ import annotations

from collections import Counter
from typing import Any, Dict, Iterable, List, Optional, Tuple

from apex_tpu.lint import ir as _ir
from apex_tpu.monitor.hbm import lane_padded_bytes


def _num_lanes() -> int:
    """The 128-lane vreg width, read from the SAME module whose tiling
    rule computes the padded bytes (monitor/hbm.py) so hint text and byte
    math can never disagree; flash_attention's exported calibration
    constants are pinned consistent with it by tests/test_lint.py."""
    from apex_tpu.monitor import hbm

    return int(getattr(hbm, "_NUM_LANES", 128))


# ---------------------------------------------------------------------------
# jaxpr traversal
# ---------------------------------------------------------------------------


def _sub_jaxprs(eqn) -> List[Any]:
    """Every inner jaxpr of a call-like equation (pjit, scan, while, cond,
    shard_map, custom_vjp, pallas_call, ...) -- all branches, no multipliers:
    these analyzers report presence/residency, not totals per step.
    (Delegates to the shared walker, apex_tpu/lint/ir.py.)"""
    return _ir.sub_jaxprs(eqn)


def iter_eqns(jaxpr) -> Iterable[Any]:
    """Depth-first over every equation, descending into inner jaxprs —
    the shared walk (:mod:`apex_tpu.lint.ir`): a ``StepIR``, ClosedJaxpr,
    or open jaxpr walks once and the node list is cached/reused."""
    return _ir.ensure_ir(jaxpr).iter_eqns()


def _aval_of(var):
    return getattr(var, "aval", None)


def _aval_bytes(aval) -> Tuple[int, int]:
    """(logical nbytes, lane-padded nbytes) of one shaped aval."""
    import numpy as np

    shape = tuple(int(d) for d in aval.shape)
    itemsize = int(np.dtype(aval.dtype).itemsize)
    n = itemsize
    for d in shape:
        n *= d
    return n, lane_padded_bytes(shape, itemsize)


# ---------------------------------------------------------------------------
# lane-padding waste auditor
# ---------------------------------------------------------------------------


def _audit_aval(aval, where: str, threshold: float, min_bytes: int):
    try:
        nb, pb = _aval_bytes(aval)
    except Exception:  # noqa: BLE001 - tokens/abstract avals have no bytes
        return None
    if getattr(aval, "size", 0) <= 1:
        return None  # a scalar cannot avoid its one tile; pure noise
    if nb <= 0 or pb < threshold * nb or (pb - nb) < min_bytes:
        return None
    shape = tuple(int(d) for d in aval.shape)
    lanes = _num_lanes()
    hints = []
    if len(shape) >= 1 and shape[-1] < lanes:
        hints.append(f"minor dim {shape[-1]} pads to {lanes} lanes")
        if shape[-1] == 1:
            hints.append("carry per-row stats as dense (rows, blk) tables, "
                         "not (rows, 1) columns (flash_attention.py lse/delta)")
        elif 1 < shape[-1] < lanes:
            hints.append("prefer minor dims that are multiples of 128 "
                         "(e.g. head_dim 128 at extreme sequence lengths)")
    if len(shape) >= 2 or not hints:
        import numpy as np

        sublanes = max(32 // int(np.dtype(aval.dtype).itemsize), 1)
        second = shape[-2] if len(shape) >= 2 else 1
        if second % sublanes:
            hints.append(f"second-minor dim {second} pads to a multiple of "
                         f"{sublanes} sublanes for {aval.dtype}")
    msg = (f"{where}: {shape} {aval.dtype} occupies {pb} bytes under "
           f"T(8,128) tiling ({round(pb / nb, 1)}x its {nb})")
    return {
        "rule": "lane-padding",
        "where": where,
        "shape": list(shape),
        "dtype": str(aval.dtype),
        "bytes": nb,
        "padded_bytes": pb,
        "waste_ratio": round(pb / nb, 2),
        "message": msg + ("; " + "; ".join(hints) if hints else ""),
    }


# the call-like primitives whose operands/results XLA materializes in the
# padded HBM layout (jaxpr primitive names: "custom_call" itself is an
# HLO-level op and never appears in a jaxpr)
_BOUNDARY_PRIMS = ("pallas_call", "ffi_call", "pure_callback", "io_callback")


def lane_padding_report(fn, *args,
                        threshold: float = 2.0,
                        min_bytes: int = 1 << 16,
                        max_findings: int = 20,
                        axes: Optional[Dict[str, int]] = None,
                        **kwargs) -> Dict[str, Any]:
    """Estimate bytes lost to T(8,128) minor-dim padding in ``fn(*args)``.

    Audits the step signature (top-level invars/outvars -- those arrays are
    HBM-resident between steps) and every operand/result of custom-call
    boundaries (``pallas_call`` et al., where XLA materializes the padded
    layout -- the 2 GB-for-16 MB lse tax). ``fn`` may also be a
    ``ClosedJaxpr``. Intermediates fused by XLA are NOT flagged: padding
    only becomes real at residency/boundary points.

    Returns ``{findings, waste_bytes, audited, findings_truncated}`` with
    findings sorted by wasted bytes, worst first; ``findings_truncated``
    counts drops beyond ``max_findings`` (never silently).
    """
    ir = _ir.trace_ir(fn, *args, axes=axes, **kwargs)
    jaxpr = ir.jaxpr
    findings: List[Dict[str, Any]] = []
    audited = 0
    seen = set()

    def audit(var, where):
        nonlocal audited
        aval = _aval_of(var)
        if aval is None or not hasattr(aval, "shape"):
            return
        key = (where, tuple(getattr(aval, "shape", ())), str(getattr(aval, "dtype", "")))
        if key in seen:
            return
        seen.add(key)
        audited += 1
        f = _audit_aval(aval, where, threshold, min_bytes)
        if f is not None:
            findings.append(f)

    for i, v in enumerate(jaxpr.invars):
        audit(v, f"input[{i}]")
    for i, v in enumerate(jaxpr.outvars):
        audit(v, f"output[{i}]")
    for eqn in ir.iter_eqns():
        name = eqn.primitive.name
        if name not in _BOUNDARY_PRIMS:
            continue
        for v in eqn.invars:
            audit(v, f"{name} operand")
        for v in eqn.outvars:
            audit(v, f"{name} result")

    findings.sort(key=lambda f: f["bytes"] - f["padded_bytes"])
    truncated = max(0, len(findings) - max_findings)
    waste = sum(f["padded_bytes"] - f["bytes"] for f in findings)
    return {
        "findings": findings[:max_findings],
        "waste_bytes": waste,
        "audited": audited,
        "findings_truncated": truncated,
    }


# ---------------------------------------------------------------------------
# collective-transpose hazard detector
# ---------------------------------------------------------------------------

_LOSS_COLLECTIVES = ("psum", "pmean", "pmax", "pmin")


def scalar_collective_counts(jaxpr) -> Dict[str, int]:
    """Count psum/pmean-family equations whose operands are all scalar
    (size <= 1) -- loss-shaped collectives. pmean lowers to psum+div, so
    both traces of a comparison see the same primitive names."""
    counts: Counter = Counter()
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name not in _LOSS_COLLECTIVES:
            continue
        sizes = [int(getattr(_aval_of(v), "size", 0) or 0)
                 for v in eqn.invars if _aval_of(v) is not None]
        if sizes and all(s <= 1 for s in sizes):
            counts[eqn.primitive.name] += 1
    return dict(counts)


def transpose_hazards(loss_fn, *args,
                      axes: Optional[Dict[str, int]] = None,
                      argnums=0, **kwargs) -> Dict[str, Any]:
    """Detect a psum/pmean of the loss inside the differentiated region.

    Traces ``loss_fn`` twice under ``axes`` (name -> size bindings, e.g.
    ``{"data": 8}``): once plain, once under ``jax.value_and_grad``. A bare
    ``pmean(loss)`` leaves an EXTRA scalar collective in the grad trace
    (its transpose); the identity-backward psum
    (``reduce_from_tensor_model_parallel_region``) leaves none. ``loss_fn``
    that binds its own axes (shard_map inside) needs no ``axes``.

    Returns ``{hazard, forward, grad, extra_in_backward, findings}``.
    """
    import jax

    fwd = scalar_collective_counts(
        _ir.trace_ir(loss_fn, *args, axes=axes, **kwargs))
    grad_fn = jax.value_and_grad(loss_fn, argnums=argnums)
    bwd = scalar_collective_counts(
        _ir.trace_ir(grad_fn, *args, axes=axes, **kwargs))
    extra = {k: bwd[k] - fwd.get(k, 0) for k in bwd
             if bwd[k] > fwd.get(k, 0)}
    findings = [{
        "rule": "grad-transpose",
        "message": f"backward jaxpr carries {n} extra scalar {verb} -- a "
                   f"bare collective of the loss was differentiated; its "
                   f"transpose over-counts gradients by the axis size "
                   f"(reduce AFTER grad, or use the identity-backward "
                   f"psum from tensor_parallel/mappings.py)",
        "verb": verb, "extra": n,
    } for verb, n in sorted(extra.items())]
    return {"hazard": bool(extra), "forward": fwd, "grad": bwd,
            "extra_in_backward": extra, "findings": findings}


# ---------------------------------------------------------------------------
# sequence-parallel decomposition tripwire
# ---------------------------------------------------------------------------

# the primitive names an eqn binds its axis under, per collective family
_AXIS_PARAM_KEYS = ("axes", "axis_name")

# shared with the IR walker so the two can never disagree on the binding
_eqn_axis_names = _ir.eqn_axis_names


def tp_collective_census(jaxpr, tp_axis: str,
                         min_activation_rank: int = 3) -> Dict[str, Any]:
    """Count collectives over ``tp_axis`` in a jaxpr, split into ACTIVATION
    traffic (any operand of rank >= ``min_activation_rank`` -- the
    ``(b, s, h)`` tensors whose all-reduce sequence parallelism decomposes)
    and the rest (loss/softmax scalars and ``(b, s)`` reductions of the
    vocab-parallel cross entropy, which legitimately stay psums)."""
    activation: Counter = Counter()
    other: Counter = Counter()
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in ("psum", "pmean", "pmax", "pmin", "all_gather",
                        "reduce_scatter", "all_to_all"):
            continue
        if tp_axis not in _eqn_axis_names(eqn):
            continue
        ranks = [len(getattr(_aval_of(v), "shape", ()) or ())
                 for v in eqn.invars if _aval_of(v) is not None]
        bucket = activation if ranks and max(ranks) >= min_activation_rank \
            else other
        bucket[name] += 1
    return {"activation": dict(activation), "other": dict(other)}


def sequence_parallel_hazards(fn, *args,
                              tp_axis: str = "model",
                              axes: Optional[Dict[str, int]] = None,
                              num_layers: Optional[int] = None,
                              min_activation_rank: int = 3,
                              **kwargs) -> Dict[str, Any]:
    """Verify a sequence-parallel FORWARD decomposed its TP all-reduces.

    Traces ``fn(*args)`` under ``axes`` (name -> size bindings, e.g.
    ``{"model": 2}``; omit when ``fn`` binds its own axes via shard_map)
    and censuses collectives on ``tp_axis``. A ``psum``/``pmean`` whose
    operand is activation-shaped (rank >= ``min_activation_rank``) is a
    finding: under ``sequence_parallel=True`` every such all-reduce must
    have become the ``reduce_scatter``/``all_gather`` conjugate pair
    (``SEQUENCE_PARALLEL_DECOMPOSED_PRIMS``, parallel/collectives.py) --
    XLA compiles the regression silently. Scalar/rank-2 psums (loss, the
    vocab-parallel CE reductions) are exempt and reported under
    ``census["other"]``.

    Returns ``{hazard, census, activation_psums, per_layer, findings}``.
    Counts are CALL SITES per trace, like the comm accounting
    (monitor/comms.py): a body inside ``lax.scan`` counts once, not once
    per layer. ``per_layer`` divides the activation counts by
    ``num_layers`` when given -- only meaningful when the trace unrolls
    the layers (``unroll_layers=True``) or ``fn`` IS a single layer body
    with ``num_layers`` omitted (the "all-reduce count per layer 2 -> 0"
    evidence number, benchmarks/overlap_evidence.py).
    """
    jaxpr = _ir.trace_ir(fn, *args, axes=axes, **kwargs)
    census = tp_collective_census(
        jaxpr, tp_axis, min_activation_rank=min_activation_rank)
    n_psum = sum(n for verb, n in census["activation"].items()
                 if verb in ("psum", "pmean"))
    findings = []
    if n_psum:
        findings.append({
            "rule": "sp-regression",
            "message": (
                f"forward jaxpr carries {n_psum} psum/pmean of "
                f"activation-shaped operands on the '{tp_axis}' axis -- a "
                f"sequence-parallel region regressed to a synchronous "
                f"all-reduce; route it through the psum_scatter/all_gather "
                f"conjugates (tensor_parallel/mappings.py table 2)"),
            "verb": "psum", "extra": n_psum,
        })
    out = {
        "hazard": bool(n_psum),
        "census": census,
        "activation_psums": n_psum,
        "findings": findings,
    }
    if num_layers:
        out["per_layer"] = {
            verb: round(n / num_layers, 3)
            for verb, n in census["activation"].items()}
    return out


# ---------------------------------------------------------------------------
# ZeRO-redundancy tripwire
# ---------------------------------------------------------------------------


def zero_collective_census(jaxpr, zero_axis: str,
                           min_bulk_elems: int = 1 << 12) -> Dict[str, Any]:
    """Count collectives over ``zero_axis`` in a jaxpr, split into BULK
    traffic (any operand OR result with >= ``min_bulk_elems`` elements —
    gradient/param payloads; a ZeRO all_gather's per-rank operand is the
    small 1/n chunk but its result is the full param) and the rest (the
    loss pmean, the overflow-flag pmax, LAMB's scalar norm psums, which
    legitimately stay all-reduces)."""
    bulk: Counter = Counter()
    other: Counter = Counter()
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in ("psum", "pmean", "pmax", "pmin", "all_gather",
                        "reduce_scatter", "all_to_all"):
            continue
        if zero_axis not in _eqn_axis_names(eqn):
            continue
        sizes = [int(getattr(_aval_of(v), "size", 0) or 0)
                 for v in list(eqn.invars) + list(eqn.outvars)
                 if _aval_of(v) is not None]
        bucket = bulk if sizes and max(sizes) >= min_bulk_elems else other
        bucket[name] += 1
    return {"bulk": dict(bulk), "other": dict(other)}


def zero_redundancy_hazards(fn, *args,
                            zero_axis: str = "data",
                            axes: Optional[Dict[str, int]] = None,
                            min_bulk_elems: int = 1 << 12,
                            **kwargs) -> Dict[str, Any]:
    """Verify a ZeRO-sharded train step decomposed its data-axis reduction.

    Traces ``fn(*args)`` under ``axes`` (name -> size bindings, e.g.
    ``{"data": 8}``; omit when ``fn`` binds its own axes via shard_map) and
    censuses collectives on ``zero_axis``. A ``psum``/``pmean`` with a
    bulk operand (>= ``min_bulk_elems`` elements) is a finding: under
    ``MixedPrecisionOptimizer(zero_axis=...)`` the data-axis gradient
    all-reduce is subsumed by the optimizer's reduce-scatter/all-gather
    pair (``ZERO_DECOMPOSED_PRIMS``, parallel/collectives.py;
    optimizers/distributed.py), so a surviving full-size psum means the
    harness still all-reduces what the scatter already reduces —
    double-counted averaging XLA compiles without complaint. Scalar
    collectives (loss pmean, found_inf pmax, LAMB norm psums) are exempt
    and reported under ``census["other"]``.

    Returns ``{hazard, census, bulk_psums, findings}`` — call-site counts
    per trace, like :func:`sequence_parallel_hazards`.
    """
    jaxpr = _ir.trace_ir(fn, *args, axes=axes, **kwargs)
    census = zero_collective_census(
        jaxpr, zero_axis, min_bulk_elems=min_bulk_elems)
    n_psum = sum(n for verb, n in census["bulk"].items()
                 if verb in ("psum", "pmean"))
    findings = []
    if n_psum:
        findings.append({
            "rule": "zero-redundancy",
            "message": (
                f"step jaxpr carries {n_psum} psum/pmean of bulk operands "
                f"on the '{zero_axis}' axis alongside a ZeRO-sharded "
                f"optimizer -- the grad all-reduce there is subsumed by "
                f"the optimizer's psum_scatter (same averaging factor); "
                f"drop the axis from the harness reduction "
                f"(allreduce_gradients_by_spec(zero_axis=...))"),
            "verb": "psum", "extra": n_psum,
        })
    return {
        "hazard": bool(n_psum),
        "census": census,
        "bulk_psums": n_psum,
        "findings": findings,
    }


# ---------------------------------------------------------------------------
# ZeRO-3 bulk-gather tripwire
# ---------------------------------------------------------------------------


def param_gather_census(jaxpr, zero_axis: str,
                        min_model_elems: int) -> Dict[str, Any]:
    """Census of ``all_gather`` equations over ``zero_axis``, classified by
    RESULT size (the same result-sized rule as :func:`zero_collective_census`
    — a gather's operand is the small 1/n chunk, its result the materialized
    param): results with >= ``min_model_elems`` elements are MODEL-SIZED
    bulk gathers (a whole layer stack or the PR-5 post-update param
    gather), everything below is a per-layer/per-leaf JIT gather. Counts
    are call sites per trace (a gather inside ``lax.scan`` counts once,
    like the comm accounting)."""
    per_layer: Counter = Counter()
    bulk: Counter = Counter()
    bulk_sites: List[Dict[str, Any]] = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "all_gather":
            continue
        if zero_axis not in _eqn_axis_names(eqn):
            continue
        out_sizes = [int(getattr(_aval_of(v), "size", 0) or 0)
                     for v in eqn.outvars if _aval_of(v) is not None]
        result = max(out_sizes, default=0)
        if result >= min_model_elems:
            bulk["all_gather"] += 1
            aval = _aval_of(eqn.outvars[0])
            bulk_sites.append({
                "result_shape": [int(d) for d in
                                 getattr(aval, "shape", ()) or ()],
                "result_elems": result,
                "dtype": str(getattr(aval, "dtype", "")),
            })
        else:
            per_layer["all_gather"] += 1
    return {"per_layer": dict(per_layer), "bulk": dict(bulk),
            "bulk_sites": bulk_sites}


def zero3_gather_hazards(fn, *args,
                         zero_axis: str = "data",
                         axes: Optional[Dict[str, int]] = None,
                         model_elems: Optional[int] = None,
                         bulk_fraction: float = 0.25,
                         min_model_elems: Optional[int] = None,
                         **kwargs) -> Dict[str, Any]:
    """Verify a ZeRO-3 (fully-sharded-param) train step gathers its weights
    PER LAYER, never whole-model.

    Traces ``fn(*args)`` under ``axes`` (omit when ``fn`` binds its own
    axes via shard_map) and censuses ``all_gather`` results on
    ``zero_axis``. Under ``MixedPrecisionOptimizer(zero_level=3)`` the bf16
    params persist as 1/n chunks and each layer's weight tree is gathered
    just-in-time inside the layer loop (models/_transformer.run_layers
    ``chunk_meta``), so every gather result is one layer's params — a
    MODEL-SIZED gather result (the whole stacked-layer leaf, or the PR-5
    post-update bulk param gather) means a refactor silently rematerialized
    the replicated model that ZeRO-3 exists to remove; peak HBM returns to
    O(model) and XLA compiles it without complaint.

    The model-sized threshold is ``min_model_elems`` when given, else
    ``bulk_fraction * model_elems`` (pass ``model_elems`` = the total
    param count; one layer of an L-layer stack sits at ~1/L of it, far
    below a 0.25 fraction, while a whole-stack gather is most of the
    model), else a 4Mi-element default.

    Returns ``{hazard, census, bulk_gathers, layer_gathers, findings}`` —
    call-site counts per trace, like :func:`zero_redundancy_hazards`.
    """
    if min_model_elems is None:
        min_model_elems = (max(int(bulk_fraction * model_elems), 1)
                           if model_elems else 1 << 22)
    jaxpr = _ir.trace_ir(fn, *args, axes=axes, **kwargs)
    census = param_gather_census(jaxpr, zero_axis, min_model_elems)
    n_bulk = sum(census["bulk"].values())
    findings = []
    if n_bulk:
        findings.append({
            "rule": "zero3-bulk-gather",
            "message": (
                f"step jaxpr carries {n_bulk} model-sized all_gather "
                f"result(s) on the '{zero_axis}' axis in a fully-sharded "
                f"(ZeRO-3) step -- the bf16 params must stay 1/n chunks "
                f"with per-layer just-in-time gathers (run_layers "
                f"chunk_meta); a bulk gather rematerializes the replicated "
                f"model and peak HBM returns to O(model)"),
            "verb": "all_gather", "extra": n_bulk,
        })
    return {
        "hazard": bool(n_bulk),
        "census": census,
        "bulk_gathers": n_bulk,
        "layer_gathers": sum(census["per_layer"].values()),
        "min_model_elems": int(min_model_elems),
        "findings": findings,
    }


# ---------------------------------------------------------------------------
# ZeRO-3 gather-prefetch tripwire
# ---------------------------------------------------------------------------

#: primitives that open a rematerialized region (jax.checkpoint lowers to
#: remat2 on this jax; shared with the IR walker)
_REMAT_PRIMS = _ir.REMAT_PRIMS


def prefetch_gather_census(jaxpr, zero_axis: str) -> Dict[str, int]:
    """Classify every ``all_gather`` over ``zero_axis`` by whether it sits
    INSIDE a rematerialized region (``jax.checkpoint`` body — the
    serialized ZeRO-3 drive's in-body gather, re-issued inside the
    backward's recompute and pinned to that body's schedule) or stands
    FREE in the surrounding jaxpr (the double-buffered drive's
    structurally prefetchable form, ``models/_transformer.
    _prefetched_zero3_drive``). Counts are call sites per trace; remat
    containment comes from the shared walk's context
    (:class:`apex_tpu.lint.ir.EqnNode.in_remat`)."""
    fused = free = regions = 0
    for node in _ir.ensure_ir(jaxpr).nodes:
        name = node.eqn.primitive.name
        if name in _REMAT_PRIMS:
            regions += 1
        if (name == "all_gather"
                and zero_axis in _eqn_axis_names(node.eqn)):
            if node.in_remat:
                fused += 1
            else:
                free += 1
    return {"fused": fused, "free": free, "remat_regions": regions}


def unprefetched_gather_hazards(fn, *args,
                                zero_axis: str = "data",
                                axes: Optional[Dict[str, int]] = None,
                                min_fused: int = 2,
                                **kwargs) -> Dict[str, Any]:
    """Verify a ZeRO-3 UNROLLED step double-buffers its per-layer gathers.

    Traces ``fn(*args)`` under ``axes`` (omit when ``fn`` binds its own
    axes via shard_map) and censuses ``all_gather`` call sites over
    ``zero_axis`` by remat containment (:func:`prefetch_gather_census`).
    The serialized chunk drive gathers each layer's weights INSIDE the
    rematerialized body: the gather is then pinned to that body's schedule
    — the forward issues it back-to-back with the body's compute and the
    backward re-issues it inside the recompute, strictly serialized with
    the cotangent chain — so no jaxpr-level ordering (and no
    latency-hiding hoist across the remat's optimization barriers) can
    start layer i+1's gather under layer i's compute. The double-buffered
    drive (``GPTConfig.zero3_prefetch``; ``models/_transformer.
    _prefetched_zero3_drive``) lifts the gathers out of remat into free
    equations issued ``prefetch`` layers ahead, which is the structure
    this analyzer accepts.

    Hazard iff >= ``min_fused`` remat-fused gathers (the per-layer
    unrolled pattern; a lax.scan drive books ONE in-body gather site and
    is out of scope — this tripwire polices the unrolled path the
    prefetch knob exists for). Returns ``{hazard, census, fused_gathers,
    free_gathers, findings}`` — call-site counts per trace, like
    :func:`zero3_gather_hazards`.
    """
    jaxpr = _ir.trace_ir(fn, *args, axes=axes, **kwargs)
    census = prefetch_gather_census(jaxpr, zero_axis)
    findings = []
    if census["fused"] >= min_fused:
        findings.append({
            "rule": "unprefetched-gather",
            "message": (
                f"step jaxpr carries {census['fused']} per-layer "
                f"all_gather(s) on the '{zero_axis}' axis INSIDE "
                f"rematerialized bodies in an unrolled ZeRO-3 step -- each "
                f"gather is serialized with its layer's compute (and its "
                f"backward re-gather with the recompute chain); "
                f"double-buffer them with zero3_prefetch > 0 so layer "
                f"i+N's gather issues before layer i's compute "
                f"(models/_transformer._prefetched_zero3_drive)"),
            "verb": "all_gather", "extra": census["fused"],
        })
    return {
        "hazard": bool(findings),
        "census": census,
        "fused_gathers": census["fused"],
        "free_gathers": census["free"],
        "findings": findings,
    }


# ---------------------------------------------------------------------------
# quantized-collective tripwire
# ---------------------------------------------------------------------------


def quantized_comm_census(jaxpr, zero_axis: str,
                          min_bulk_elems: int = 1 << 12) -> Dict[str, Any]:
    """Census of BULK reduce traffic (``reduce_scatter``/``all_to_all``
    equations with an operand of >= ``min_bulk_elems`` elements) over
    ``zero_axis``, keyed by the payload's wire itemsize in bytes — so an
    int8/e5m2-encoded reduce tallies under ``"1"`` and a surviving fp32
    payload under ``"4"``. The fp32 per-chunk scale side-channels are n
    elements each (far below the bulk floor) and never pollute the table."""
    import numpy as np

    by_itemsize: Dict[str, Counter] = {}
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in ("reduce_scatter", "all_to_all"):
            continue
        if zero_axis not in _eqn_axis_names(eqn):
            continue
        bulk_ops = [v for v in eqn.invars
                    if _aval_of(v) is not None
                    and int(getattr(_aval_of(v), "size", 0) or 0)
                    >= min_bulk_elems]
        if not bulk_ops:
            continue
        itemsize = max(int(np.dtype(_aval_of(v).dtype).itemsize)
                       for v in bulk_ops)
        by_itemsize.setdefault(str(itemsize), Counter())[name] += 1
    return {k: dict(v) for k, v in sorted(by_itemsize.items())}


def quantized_comm_hazards(fn, *args,
                           zero_axis: str = "data",
                           axes: Optional[Dict[str, int]] = None,
                           residual: Any = "unchecked",
                           min_bulk_elems: int = 1 << 12,
                           **kwargs) -> Dict[str, Any]:
    """Verify a step that REQUESTS a quantized grad reduce actually moves
    its bulk reduce payload at the 1-byte wire dtype.

    Traces ``fn(*args)`` under ``axes`` (omit when ``fn`` binds its own
    axes via shard_map) and censuses bulk reduce traffic
    (``reduce_scatter``/``all_to_all``, the ZeRO reduction verbs —
    ``QUANTIZED_REDUCE_PRIMS``, parallel/collectives.py) on ``zero_axis``
    by wire itemsize. Under ``MixedPrecisionOptimizer(reduce_dtype=...)``
    every bulk reduce payload must be 1 byte/elem (the encoded
    ``all_to_all`` pair of parallel/quantize.py; only the tiny fp32 scale
    side-channels ride wider, below the bulk floor) — a surviving >= 2-byte
    bulk payload means the quantization silently regressed to the fat wire,
    and XLA compiles the regression without complaint.

    ``residual`` guards the second silent failure mode: quantizing GRADS
    with no error-feedback state accumulates bias instead of telescoping
    it. Pass the optimizer state's residual tree (``MPOptState.residual``)
    — a finding is raised when it is None or lacks the ``"err"`` chunk
    tree. Leave the default to skip the check (activation-only traffic
    carries no residual by design).

    Returns ``{hazard, census, fat_reduces, findings}`` — call-site counts
    per trace, like :func:`zero_redundancy_hazards`.
    """
    jaxpr = _ir.trace_ir(fn, *args, axes=axes, **kwargs)
    census = quantized_comm_census(
        jaxpr, zero_axis, min_bulk_elems=min_bulk_elems)
    fat = sum(n for size, verbs in census.items() if int(size) > 1
              for n in verbs.values())
    thin = sum(n for size, verbs in census.items() if int(size) == 1
               for n in verbs.values())
    findings = []
    if fat:
        findings.append({
            "rule": "quantized-comm-fat-wire",
            "message": (
                f"step jaxpr carries {fat} bulk reduce payload(s) on the "
                f"'{zero_axis}' axis at >= 2 bytes/elem in a step that "
                f"requests a quantized grad reduce -- the fp32 "
                f"psum_scatter survived (or an all_to_all shipped an "
                f"unencoded payload); route it through "
                f"parallel/quantize.quantized_reduce_scatter so the wire "
                f"moves 1 B/elem plus the fp32 scale side-channel"),
            "verb": "reduce_scatter", "extra": fat,
        })
    if residual != "unchecked" and (
            not isinstance(residual, dict) or "err" not in residual):
        findings.append({
            "rule": "quantized-comm-no-residual",
            "message": (
                "quantized GRAD reduce with no error-feedback residual "
                "state: MPOptState.residual lacks the 'err' chunk tree, so "
                "per-step quantization error accumulates as bias instead "
                "of telescoping (the EF/1-bit-Adam construction, "
                "parallel/quantize.py module doc)"),
            "verb": "all_to_all", "extra": 1,
        })
    return {
        "hazard": bool(findings),
        "census": census,
        "fat_reduces": fat,
        "quantized_reduces": thin,
        "findings": findings,
    }


# ---------------------------------------------------------------------------
# MoE dispatch tripwire
# ---------------------------------------------------------------------------


def moe_dispatch_census(jaxpr, expert_axis: str,
                        min_bulk_elems: int = 1 << 12,
                        min_dispatch_rank: int = 3) -> Dict[str, Any]:
    """Census of BULK ``all_to_all`` traffic over ``expert_axis``, split
    into DISPATCH-shaped payloads (an operand of rank >=
    ``min_dispatch_rank`` — the (experts, capacity, hidden) token buckets
    of ``transformer/moe.py``, or their split-block quantized form) and
    chunk-shaped ones (the rank-2 ZeRO grad rows of
    ``parallel/quantize.quantized_reduce_scatter``, which legitimately
    share the same mesh axis), each keyed by the payload's wire itemsize
    in bytes — an int8-encoded dispatch tallies under ``"1"``, a
    surviving fp32 bucket under ``"4"``. The tiny fp32 scale
    side-channels sit below the bulk floor and never pollute the table.
    Counts are call sites per trace (a dispatch inside ``lax.scan``
    counts once, like the comm accounting)."""
    import numpy as np

    dispatch: Dict[str, Counter] = {}
    chunk: Dict[str, Counter] = {}
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "all_to_all":
            continue
        if expert_axis not in _eqn_axis_names(eqn):
            continue
        bulk_ops = [v for v in eqn.invars
                    if _aval_of(v) is not None
                    and int(getattr(_aval_of(v), "size", 0) or 0)
                    >= min_bulk_elems]
        if not bulk_ops:
            continue
        itemsize = max(int(np.dtype(_aval_of(v).dtype).itemsize)
                       for v in bulk_ops)
        rank = max(len(getattr(_aval_of(v), "shape", ()) or ())
                   for v in bulk_ops)
        table = dispatch if rank >= min_dispatch_rank else chunk
        table.setdefault(str(itemsize), Counter())["all_to_all"] += 1
    return {"dispatch": {k: dict(v) for k, v in sorted(dispatch.items())},
            "chunk": {k: dict(v) for k, v in sorted(chunk.items())}}


def moe_dispatch_hazards(fn, *args,
                         expert_axis: str = "data",
                         axes: Optional[Dict[str, int]] = None,
                         wire_dtype: Optional[str] = None,
                         min_bulk_elems: int = 1 << 12,
                         min_dispatch_rank: int = 3,
                         **kwargs) -> Dict[str, Any]:
    """Verify an expert-parallel MoE step actually DISPATCHES its tokens
    over the expert axis — and, when a quantized dispatch wire was
    requested, that the buckets move at 1 byte/elem.

    Traces ``fn(*args)`` under ``axes`` (name -> size bindings; omit when
    ``fn`` binds its own axes via shard_map) and censuses bulk
    ``all_to_all`` traffic on ``expert_axis``
    (:func:`moe_dispatch_census`). Two silent regressions this names:

    - **replicated experts**: a step built with ``moe_expert_axis`` whose
      trace carries NO dispatch-shaped all_to_all — a refactor routed the
      tokens through the dense one-hot einsums on every rank (serial
      ``apply`` under shard_map compiles fine and computes E× the FLOPs);
    - **fat dispatch wire** (``wire_dtype`` given): a dispatch payload at
      >= 2 bytes/elem where ``moe_dispatch_dtype`` promised the encoded
      1 B/elem exchange (``parallel/quantize.quantized_all_to_all``).

    Dispatch payloads are rank-classified (>= ``min_dispatch_rank``) so
    ZeRO's rank-2 grad-chunk all_to_alls on the same axis are reported
    under ``census["chunk"]`` and never counted — hand the tripwire
    either the forward loss or the whole train step.

    Returns ``{hazard, census, dispatch_all_to_alls, fat_dispatches,
    findings}`` — call-site counts per trace, like
    :func:`zero_redundancy_hazards`.
    """
    jaxpr = _ir.trace_ir(fn, *args, axes=axes, **kwargs)
    census = moe_dispatch_census(
        jaxpr, expert_axis, min_bulk_elems=min_bulk_elems,
        min_dispatch_rank=min_dispatch_rank)
    n_dispatch = sum(n for verbs in census["dispatch"].values()
                     for n in verbs.values())
    fat = sum(n for size, verbs in census["dispatch"].items()
              if int(size) > 1 for n in verbs.values())
    findings = []
    if not n_dispatch:
        findings.append({
            "rule": "moe-dispatch-missing",
            "message": (
                f"step jaxpr carries NO dispatch-shaped all_to_all on the "
                f"'{expert_axis}' axis in an expert-parallel MoE step -- "
                f"the experts silently run replicated (every rank computes "
                f"all E experts' FFNs); route the token buckets through "
                f"MoEMLP.apply_expert_parallel's all_to_all exchange "
                f"(transformer/moe.py)"),
            "verb": "all_to_all", "extra": 0,
        })
    if wire_dtype is not None and fat:
        findings.append({
            "rule": "moe-dispatch-fat-wire",
            "message": (
                f"step jaxpr ships {fat} dispatch-shaped bulk all_to_all "
                f"payload(s) on the '{expert_axis}' axis at >= 2 "
                f"bytes/elem in a step that requests a quantized dispatch "
                f"wire ({wire_dtype}) -- route dispatch/combine through "
                f"parallel/quantize.quantized_all_to_all so the buckets "
                f"move 1 B/elem plus the fp32 scale side-channel"),
            "verb": "all_to_all", "extra": fat,
        })
    return {
        "hazard": bool(findings),
        "census": census,
        "dispatch_all_to_alls": n_dispatch,
        "fat_dispatches": fat,
        "findings": findings,
    }


# ---------------------------------------------------------------------------
# recompile-hazard scanner
# ---------------------------------------------------------------------------


def untimed_schedule_hazards(fn, *args, tracer=None,
                             **kwargs) -> Dict[str, Any]:
    """Flag a pipeline schedule drive whose slots emit no trace spans
    while tracing is armed — the census-only regression.

    Runs ``fn(*args, **kwargs)`` with an in-memory ``monitor.tracing``
    tracer installed as the global, then joins two observables: the
    schedule-drive counter
    (``transformer.pipeline_parallel.schedules.ring_drive_count``, which
    every ring trace AND every traced tick drive advances) against the
    pipe-cat spans the tracer collected. A drive with no spans is the
    hazard; a span-emitting drive (``schedules.traced_pipeline_timeline``)
    passes; a fn with no pipeline drive at all trivially passes.

    Hand ``fn`` a FRESH step callable: a jit-cached step that does not
    re-trace cannot advance the drive counter (documented analyzer
    limitation — presence detection, like the other tripwires).
    """
    from apex_tpu.monitor import tracing as tracing_mod
    from apex_tpu.transformer.pipeline_parallel import schedules

    tr = tracer if tracer is not None else tracing_mod.Tracer(None)
    # the analyzer reads tr.records: a caller-supplied file-backed tracer
    # (keep=False) would otherwise turn every span-emitting drive into a
    # false-positive hazard
    tr.keep = True
    before = schedules.ring_drive_count()
    with tracing_mod.scoped(tr):
        fn(*args, **kwargs)
    drives = schedules.ring_drive_count() - before
    pipe_spans = [r for r in tr.records
                  if r.get("cat") in ("pipe", "pipe-comm")]
    hazard = drives > 0 and not pipe_spans
    findings: List[Dict[str, Any]] = []
    if hazard:
        findings.append({
            "rule": "untimed-schedule",
            "message": (
                f"{drives} pipeline schedule drive(s) traced under an "
                "armed tracer with NO pipe spans emitted — the timeline "
                "regressed to census-only; drive pipelined steps through "
                "schedules.traced_pipeline_timeline when tracing is "
                "armed (monitor/tracing.py)"),
        })
    return {"hazard": hazard, "drives": drives,
            "pipe_spans": len(pipe_spans), "findings": findings}


def recompile_hazards(*args, **kwargs) -> List[Dict[str, Any]]:
    """Scan a step-function argument pytree for signature churn sources.

    Flags python scalars (weak-typed: alternating them with committed
    arrays, or marking them static, recompiles per value/dtype) and
    weak-typed jax arrays (a ``2.0 * x``-style leaf whose signature differs
    from an explicitly-dtyped array -- the churn
    ``monitor.diagnose.RecompileTracker`` counts after the fact). Pass the
    exact args the jitted step receives.
    """
    import jax
    from jax.tree_util import keystr, tree_flatten_with_path

    findings: List[Dict[str, Any]] = []
    for label, tree in (("args", args), ("kwargs", kwargs)):
        leaves, _ = tree_flatten_with_path(tree)
        for path, leaf in leaves:
            where = f"{label}{keystr(path)}"
            if isinstance(leaf, (bool, int, float, complex)):
                findings.append({
                    "rule": "recompile-hazard", "where": where,
                    "kind": "python-scalar",
                    "message": f"{where} is a python {type(leaf).__name__} -- "
                               f"weak-typed in the jit signature; pass a "
                               f"jnp array with an explicit dtype so the "
                               f"cache key is stable (RecompileTracker "
                               f"shape-churn class)",
                })
            elif isinstance(leaf, jax.Array) and getattr(leaf, "weak_type", False):
                findings.append({
                    "rule": "recompile-hazard", "where": where,
                    "kind": "weak-type",
                    "message": f"{where} is a weak-typed {leaf.dtype} array "
                               f"-- its signature differs from a committed "
                               f"array of the same dtype, churning the jit "
                               f"cache; build it with an explicit dtype",
                })
    return findings


def _audit_arg_stream(step_args_fn, ticks: int, stream: str,
                      findings: List[Dict[str, Any]]) -> int:
    """Audit ONE jitted serving program's per-tick argument stream for
    signature churn (the shared body of :func:`decode_recompile_hazards`).
    Appends findings tagged with ``stream``; returns the leaf count."""
    from jax.tree_util import keystr, tree_flatten_with_path

    def signature(tree):
        leaves, _ = tree_flatten_with_path((tree,))
        out = []
        for path, leaf in leaves:
            shape = tuple(getattr(leaf, "shape", ()) or ())
            dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
            weak = bool(getattr(leaf, "weak_type", False))
            out.append((keystr(path), shape, dtype, weak))
        return out

    base = None
    for t in range(int(ticks)):
        args = step_args_fn(t)
        if t == 0:
            for f in recompile_hazards(args):
                findings.append(dict(f, stream=stream))
            base = signature(args)
            continue
        sig = signature(args)
        if [s[0] for s in sig] != [s[0] for s in base]:
            findings.append({
                "rule": "decode-structure-churn", "stream": stream,
                "message": (
                    f"{stream} args pytree STRUCTURE changed between tick 0 "
                    f"and tick {t} ({len(base)} vs {len(sig)} leaves) -- "
                    f"every tick must ship the same tree (fixed max_batch "
                    f"slot arrays, the paged pool; serve/engine.py)"),
            })
            continue
        for (where, shape, dtype, weak), (_, s0, d0, w0) in zip(sig, base):
            if (shape, dtype, weak) == (s0, d0, w0):
                continue
            findings.append({
                "rule": "decode-shape-churn",
                "where": where, "stream": stream,
                "message": (
                    f"{stream} arg {where} changed from {s0}/{d0}"
                    f"{'/weak' if w0 else ''} at tick 0 to {shape}/{dtype}"
                    f"{'/weak' if weak else ''} at tick {t} -- a fresh jit "
                    f"signature (and a recompile) per tick; per-request KV "
                    f"must live in the fixed paged pool, chunk/draft counts "
                    f"must be static program dimensions, and positions must "
                    f"be committed int32 arrays (serve/cache.py)"),
            })
    return len(base or [])


def decode_recompile_hazards(step_args_fn, ticks: int = 3,
                             extra_streams=None) -> Dict[str, Any]:
    """Verify a serving decode step's jit signature is SHAPE-STABLE across
    ticks — the decode-recompile tripwire.

    ``step_args_fn(t)`` must return the exact argument pytree the jitted
    decode step would receive at tick ``t`` (``apex_tpu.serve.Engine.
    decode_args``). The engine's whole design contract is that every tick
    compiles once: a per-request KV tensor that grows with the sequence, a
    python-int position/tick, or a weak-typed leaf makes XLA recompile PER
    TOKEN — the latency cliff this scanner names before the first tick
    runs (``monitor.diagnose.RecompileTracker`` counts it after the fact).

    ``extra_streams`` (ISSUE 12) audits the OTHER serving programs' tick
    argument streams by the same rules: a dict of ``name -> args_fn`` —
    the engine exposes ``chunk_args`` (chunked prefill: a growing chunk
    count would recompile per request) and ``spec_args`` (speculative
    verify: a python-int draft length would recompile per tick). Their
    findings carry ``stream=name``; per-stream leaf counts land in
    ``stream_leaves``.

    Findings: ``decode-shape-churn`` (a leaf's shape/dtype/weak-type
    differs between ticks — e.g. contiguous per-request KV instead of the
    paged pool), ``decode-structure-churn`` (the pytree itself changes),
    plus tick-0 :func:`recompile_hazards` findings (python scalars /
    weak types in the signature). Host-side only; nothing is compiled.

    Returns ``{hazard, findings, ticks, leaves, stream_leaves}``.
    """
    findings: List[Dict[str, Any]] = []
    leaves = _audit_arg_stream(step_args_fn, ticks, "decode", findings)
    stream_leaves = {"decode": leaves}
    for name, fn in (extra_streams or {}).items():
        stream_leaves[str(name)] = _audit_arg_stream(
            fn, ticks, str(name), findings)
    return {"hazard": bool(findings), "findings": findings,
            "ticks": int(ticks), "leaves": leaves,
            "stream_leaves": stream_leaves}


# ---------------------------------------------------------------------------
# composite report (the gpt_scaling.py per-config wiring)
# ---------------------------------------------------------------------------


def step_report(fn, *args,
                axes: Optional[Dict[str, int]] = None,
                top: int = 3,
                threshold: float = 2.0,
                min_bytes: int = 1 << 16,
                **kwargs) -> Dict[str, Any]:
    """Compact per-config hazard report for a full train step: lane-padding
    summary (worst ``top`` offenders) + signature recompile hazards.
    ``kwargs`` are the step function's own keyword args (scanned like
    ``args``). The transpose detector needs the raw loss function, not the
    train step -- run :func:`transpose_hazards` on that separately."""
    pad = lane_padding_report(fn, *args, axes=axes, threshold=threshold,
                              min_bytes=min_bytes, **kwargs)
    return {
        "lane_padding": {
            "waste_bytes": pad["waste_bytes"],
            "flagged": len(pad["findings"]) + pad["findings_truncated"],
            "audited": pad["audited"],
            "worst": [{k: f[k] for k in
                       ("where", "shape", "dtype", "waste_ratio",
                        "padded_bytes")}
                      for f in pad["findings"][:top]],
        },
        "recompile_hazards": recompile_hazards(*args, **kwargs),
    }

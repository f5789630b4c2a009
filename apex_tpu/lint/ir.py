"""Shared jaxpr IR walk: one trace, one recursive descent, N analyses.

The trace analyzers (``apex_tpu.lint.trace``) each used to re-trace a step
callable and re-walk the jaxpr with bespoke recursion — every new subsystem
needed another hand-rolled detector, and whole-program properties
(collective ordering across ``lax.cond`` branches, peak HBM under the
T(8,128) lane-padding tax, silent fp32 upcasts in a bf16 step) had no
checker at all. veScale (PAPERS.md, arxiv 2509.07003) argues SPMD
consistency should be verified by the framework, not by convention; this
module is the verification substrate:

- :func:`trace_ir` traces a step callable ONCE (``jax.make_jaxpr``; no
  compile, no device work) into a :class:`StepIR`;
- :class:`StepIR` materializes the recursive walk once — every equation,
  descending into ``pjit``/``scan``/``while``/``cond``/``remat``/
  ``custom_vjp``/``shard_map``/``pallas_call`` sub-jaxprs — as a flat list
  of :class:`EqnNode` entries that thread the shard_map mesh/axis-name
  context, remat containment, cond-branch position, and a lazy
  eqn → source-provenance map;
- registered analysis passes (:mod:`apex_tpu.lint.passes`; the
  ``register_pass`` decorator) run over that shared walk via
  :func:`run_passes`, emitting structured findings shaped like engine 1's
  (rule/message, plus path/line provenance) — and
  :func:`apply_suppressions` honors the SAME source-comment grammar
  (``# lint: disable=<rule> -- why``, findings.py) at each finding's
  provenance line, so an intentional jaxpr-level hazard is waived in the
  source file that creates it.

``StepIR`` duck-types a ``ClosedJaxpr`` (``.jaxpr``/``.invars``/
``.outvars``/``.eqns``), so every legacy analyzer that accepted a
pre-traced jaxpr accepts a ``StepIR`` unchanged — hand one IR to N
analyzers and the step traces and walks once (tests/test_lint.py's
module-scoped fixtures; ``apex_tpu.lint.audit``).

No reference analog: NVIDIA Apex ships no static analysis; the walk
encodes this repo's jaxpr-level invariants (package docstring).
"""

from __future__ import annotations

import dataclasses
import os
import weakref
from typing import Any, Callable, Dict, Iterable, Iterator, List, Mapping, \
    Optional, Tuple

# ---------------------------------------------------------------------------
# the walk
# ---------------------------------------------------------------------------

#: primitives that open a rematerialized region (jax.checkpoint lowers to
#: remat2 on this jax; older/newer spellings kept for robustness)
REMAT_PRIMS = ("remat", "remat2", "checkpoint")

#: the call-like primitives whose operands/results XLA materializes in the
#: padded HBM layout ("custom_call" itself is HLO-level and never appears
#: in a jaxpr)
BOUNDARY_PRIMS = ("pallas_call", "ffi_call", "pure_callback", "io_callback")

#: named-axis collectives that move data (axis_index/axis_size are
#: rank/topology queries, not communication)
COLLECTIVE_PRIMS = ("psum", "pmax", "pmin", "all_gather", "reduce_scatter",
                    "all_to_all", "ppermute", "pshuffle",
                    "all_gather_invariant", "psum_invariant")

_AXIS_PARAM_KEYS = ("axes", "axis_name")


def eqn_axis_names(eqn) -> Tuple[str, ...]:
    """Named axes a collective equation reduces/moves over (psum binds
    ``axes``; all_gather/reduce_scatter/all_to_all/ppermute bind
    ``axis_name``)."""
    for key in _AXIS_PARAM_KEYS:
        if key in eqn.params:
            v = eqn.params[key]
            if isinstance(v, (tuple, list)):
                return tuple(str(a) for a in v)
            return (str(v),)
    return ()


def sub_jaxprs(eqn) -> List[Any]:
    """Every inner jaxpr of a call-like equation (pjit, scan, while, cond,
    shard_map, custom_vjp, pallas_call, ...) — all branches, no
    multipliers: the analyzers report presence/residency, not totals per
    step."""
    import jax

    out = []

    def collect(v):
        if isinstance(v, jax.extend.core.ClosedJaxpr):
            out.append(v.jaxpr)
        elif hasattr(v, "eqns"):  # open Jaxpr (remat, pallas_call)
            out.append(v)
        elif isinstance(v, (list, tuple)):
            for item in v:
                collect(item)

    for v in eqn.params.values():
        collect(v)
    return out


@dataclasses.dataclass
class EqnNode:
    """One equation of the shared walk, with its whole-program context."""

    eqn: Any
    #: nesting depth (0 = the root jaxpr's own equations)
    depth: int
    #: enclosing call-primitive names, outermost first
    path: Tuple[str, ...]
    #: named axes bound here: the root ``axes=`` binding plus every
    #: enclosing shard_map's mesh shape (name -> size)
    axis_sizes: Mapping[str, int]
    #: True inside a rematerialized (jax.checkpoint) body — the region
    #: whose equations re-execute in the backward's recompute
    in_remat: bool
    #: True inside at least one shard_map body (per-shard SPMD code)
    in_shard_map: bool
    #: branch index of the innermost enclosing ``lax.cond`` body, else None
    branch: Optional[int]

    def source(self) -> Optional[Tuple[str, int]]:
        """``(file, line)`` of the user frame that bound this equation,
        or None (computed lazily — provenance is only needed for the
        handful of flagged equations, not the whole walk)."""
        return eqn_source(self.eqn)


def eqn_source(eqn) -> Optional[Tuple[str, int]]:
    """Lazy source provenance of one equation (user frame file:line)."""
    from jax._src import source_info_util

    try:
        traceback = eqn.source_info.traceback
    except AttributeError:  # an equation built with no source info
        return None
    fr = source_info_util.user_frame(traceback)
    if fr is None:
        return None
    return (str(fr.file_name), int(fr.start_line))


def _shard_map_axis_sizes(eqn) -> Dict[str, int]:
    mesh = eqn.params.get("mesh")
    try:
        return {str(k): int(v) for k, v in dict(mesh.shape).items()}
    except Exception:  # noqa: BLE001 - AbstractMesh/exotic meshes
        return {}


def _walk(jaxpr, *, depth: int, path: Tuple[str, ...],
          axis_sizes: Mapping[str, int], in_remat: bool,
          in_shard_map: bool, branch: Optional[int],
          out: List[EqnNode]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        out.append(EqnNode(eqn=eqn, depth=depth, path=path,
                           axis_sizes=axis_sizes, in_remat=in_remat,
                           in_shard_map=in_shard_map, branch=branch))
        sub_path = path + (name,)
        sub_remat = in_remat or name in REMAT_PRIMS
        sub_axes = axis_sizes
        sub_shard = in_shard_map
        if name == "shard_map":
            bound = _shard_map_axis_sizes(eqn)
            if bound:
                sub_axes = {**axis_sizes, **bound}
            sub_shard = True
        if name == "cond":
            # branches are positional: thread each body's index so the
            # consistency pass can compare per-branch collective sequences
            branches = eqn.params.get("branches") or ()
            for idx, br in enumerate(branches):
                inner = br.jaxpr if hasattr(br, "jaxpr") else br
                _walk(inner, depth=depth + 1, path=sub_path,
                      axis_sizes=sub_axes, in_remat=sub_remat,
                      in_shard_map=sub_shard, branch=idx, out=out)
            continue
        for sub in sub_jaxprs(eqn):
            _walk(sub, depth=depth + 1, path=sub_path,
                  axis_sizes=sub_axes, in_remat=sub_remat,
                  in_shard_map=sub_shard, branch=branch, out=out)


class StepIR:
    """One traced step program + its materialized walk.

    Duck-types a ``ClosedJaxpr`` (``.jaxpr``, ``.invars``, ``.outvars``,
    ``.eqns``) so the legacy trace analyzers accept it unchanged; the walk
    (``.nodes``) is built once and shared by every pass/analyzer that
    reads it.
    """

    def __init__(self, jaxpr_like, *, axes: Optional[Dict[str, int]] = None,
                 comm_account=None, label: str = ""):
        self._closed = jaxpr_like
        self.root_axes: Dict[str, int] = dict(axes or {})
        #: a :class:`apex_tpu.monitor.comms.CommAccount` filled during the
        #: same single trace (``trace_ir(comm=True)``), or None
        self.comm_account = comm_account
        self.label = label
        self._nodes: Optional[List[EqnNode]] = None

    @property
    def jaxpr(self):
        """The open root jaxpr (ClosedJaxpr duck-typing)."""
        inner = self._closed
        return inner.jaxpr if hasattr(inner, "jaxpr") else inner

    @property
    def invars(self):
        return self.jaxpr.invars

    @property
    def outvars(self):
        return self.jaxpr.outvars

    @property
    def eqns(self):
        return self.jaxpr.eqns

    @property
    def nodes(self) -> List[EqnNode]:
        """The flat recursive walk, built once and cached."""
        if self._nodes is None:
            out: List[EqnNode] = []
            _walk(self.jaxpr, depth=0, path=(), axis_sizes=self.root_axes,
                  in_remat=False, in_shard_map=False, branch=None, out=out)
            self._nodes = out
        return self._nodes

    def iter_eqns(self) -> Iterator[Any]:
        """Depth-first over every equation (the legacy iteration order)."""
        return (n.eqn for n in self.nodes)

    def collectives(self) -> Iterator[EqnNode]:
        for n in self.nodes:
            if n.eqn.primitive.name in COLLECTIVE_PRIMS:
                yield n


# one StepIR per already-traced jaxpr object, so repeated analyzer calls
# on the same trace share one walk (tests hand the SAME jaxpr to several
# censuses); weak keys keep the cache from pinning dead traces
_IR_CACHE: "weakref.WeakValueDictionary[int, StepIR]" = \
    weakref.WeakValueDictionary()


def ensure_ir(obj) -> StepIR:
    """Wrap ``obj`` (StepIR | ClosedJaxpr | open Jaxpr) as a StepIR,
    reusing the cached walk when the same trace was wrapped before."""
    if isinstance(obj, StepIR):
        return obj
    try:
        key = id(obj.jaxpr if hasattr(obj, "jaxpr") else obj)
        cached = _IR_CACHE.get(key)
        if cached is not None and cached.jaxpr is (
                obj.jaxpr if hasattr(obj, "jaxpr") else obj):
            return cached
        ir = StepIR(obj)
        _IR_CACHE[key] = ir
        return ir
    except Exception:  # noqa: BLE001 - unhashable/exotic: fresh wrap
        return StepIR(obj)


def trace_ir(fn, *args, axes: Optional[Dict[str, int]] = None,
             comm: bool = False, label: str = "",
             **kwargs) -> StepIR:
    """The single trace: ``fn(*args, **kwargs)`` -> :class:`StepIR`.

    ``fn`` may already be a StepIR (returned as-is), a ``ClosedJaxpr`` or
    open jaxpr (wrapped, walk shared via :func:`ensure_ir`), or a callable
    (traced once with ``jax.make_jaxpr`` under ``axes`` name->size
    bindings). ``comm=True`` runs the trace inside
    ``monitor.comms.comm_accounting`` so the returned IR carries the
    booked per-(verb, axis, wire-dtype) payload bytes of the SAME trace
    (``StepIR.comm_account`` — the comm-bytes pass's reconciliation
    input); ignored for pre-traced inputs.
    """
    if isinstance(fn, StepIR):
        return fn
    if hasattr(fn, "jaxpr") or hasattr(fn, "eqns"):
        ir = ensure_ir(fn)
        if axes:
            ir.root_axes.update(axes)
        return ir
    import jax

    env = list(axes.items()) if axes else None
    account = None
    if comm:
        from apex_tpu.monitor.comms import comm_accounting

        with comm_accounting() as account:
            closed = jax.make_jaxpr(fn, axis_env=env)(*args, **kwargs)
    else:
        closed = jax.make_jaxpr(fn, axis_env=env)(*args, **kwargs)
    return StepIR(closed, axes=axes, comm_account=account, label=label)


# ---------------------------------------------------------------------------
# aval byte helpers shared by the passes
# ---------------------------------------------------------------------------


def aval_of(var):
    return getattr(var, "aval", None)


def aval_bytes(aval, *, padded: bool = False) -> int:
    """Logical (or T(8,128) lane-padded) bytes of one shaped aval; 0 for
    tokens/abstract avals.

    Rank-0/1 arrays price as PACKED linear storage rounded to whole
    (sublanes x 128-lane) tile granules, not as a ``(1, n)`` operand row —
    the ``monitor.hbm.optimizer_state_report`` rule: a flat multi-MB ZeRO
    chunk resident in HBM does not pay the single-row 8x sublane tax that
    ``lane_padded_bytes`` books at custom-call boundaries."""
    import numpy as np

    from apex_tpu.monitor.hbm import lane_padded_bytes

    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    try:
        itemsize = int(np.dtype(aval.dtype).itemsize)
    except Exception:  # noqa: BLE001 - dtype-less avals have no bytes
        return 0
    n = itemsize
    for d in shape:
        n *= int(d)
    if not padded:
        return n
    if len(shape) <= 1:
        sublanes = max(32 // itemsize, 1)
        granule = sublanes * 128 * itemsize
        return -(-n // granule) * granule
    return lane_padded_bytes(tuple(int(d) for d in shape), itemsize)


def is_literal(var) -> bool:
    return hasattr(var, "val")


# ---------------------------------------------------------------------------
# pass registry + runner
# ---------------------------------------------------------------------------

PASS_REGISTRY: Dict[str, Tuple[Callable, str]] = {}


def register_pass(name: str, description: str):
    """Register an IR analysis pass: ``fn(ir: StepIR, **options) -> dict``
    returning at least ``{"findings": [...]}`` — each finding a dict with
    ``rule``/``message`` plus optional ``path``/``line`` provenance (see
    passes/README.md for the author guide)."""

    def deco(fn):
        PASS_REGISTRY[name] = (fn, description)
        return fn

    return deco


def _load_registry() -> None:
    import apex_tpu.lint.passes  # noqa: F401 - registration side effect


def apply_suppressions(findings: List[Dict[str, Any]],
                       root: Optional[str] = None) -> None:
    """Mark findings suppressed via the engine-1 source-comment grammar
    (``# lint: disable=<rule> -- why``) at each finding's provenance line.
    Findings without provenance, or whose provenance file is unreadable,
    stay unsuppressed (a waiver must be auditable). Mutates in place;
    paths under the repo root are rewritten repo-relative."""
    from apex_tpu.lint.findings import Suppressions
    from apex_tpu.lint.rules_source import repo_root

    root = os.path.abspath(root or repo_root())
    cache: Dict[str, Optional[Suppressions]] = {}
    for f in findings:
        path, line = f.get("path"), f.get("line")
        if not path or not line:
            continue
        abspath = path if os.path.isabs(path) else os.path.join(root, path)
        abspath = os.path.abspath(abspath)
        if abspath.startswith(root + os.sep):
            f["path"] = os.path.relpath(abspath, root).replace(os.sep, "/")
        if abspath not in cache:
            try:
                cache[abspath] = Suppressions(
                    open(abspath, encoding="utf-8").read())
            except OSError:
                cache[abspath] = None
        sup = cache[abspath]
        hit = sup.match(f.get("rule", ""), int(line)) if sup else None
        if hit:
            f["suppressed"] = True
            f["justification"] = hit[1]


def run_passes(ir_or_fn, *args,
               passes: Optional[Iterable[str]] = None,
               options: Optional[Dict[str, Dict[str, Any]]] = None,
               axes: Optional[Dict[str, int]] = None,
               comm: bool = False,
               **kwargs) -> Dict[str, Any]:
    """Run registered passes over ONE shared trace/walk.

    ``ir_or_fn`` is a :class:`StepIR` (or pre-traced jaxpr), or a callable
    traced once via :func:`trace_ir`. ``passes`` selects by name (default:
    every registered pass); ``options`` maps pass name -> keyword options.
    Findings are suppression-resolved (:func:`apply_suppressions`).

    Returns ``{"passes": {name: result}, "errors": n_unsuppressed,
    "suppressed": n, "ok": errors == 0}``.
    """
    _load_registry()
    ir = trace_ir(ir_or_fn, *args, axes=axes, comm=comm, **kwargs)
    wanted = list(passes) if passes else sorted(PASS_REGISTRY)
    unknown = set(wanted) - set(PASS_REGISTRY)
    if unknown:
        raise ValueError(f"unknown lint pass(es): {sorted(unknown)}")
    results: Dict[str, Any] = {}
    errors = suppressed = 0
    for name in wanted:
        fn, _desc = PASS_REGISTRY[name]
        res = fn(ir, **(options or {}).get(name, {}))
        apply_suppressions(res.get("findings", []))
        for f in res.get("findings", ()):
            if f.get("suppressed"):
                suppressed += 1
            else:
                errors += 1
        results[name] = res
    return {"passes": results, "errors": errors, "suppressed": suppressed,
            "ok": errors == 0}

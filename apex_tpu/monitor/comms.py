"""Collective accounting: named comm scopes + per-axis byte counters.

Every collective verb in ``parallel/collectives.py`` and every conjugate
TP collective in ``transformer/tensor_parallel/mappings.py`` runs under a
``jax.named_scope`` of the form ``comm:<verb>[<axis>]``. Two consumers:

1. **Trace-join attribution** (measured): the scope lands in HLO op_name
   metadata, so ``pyprof.measured_scope_seconds`` / ``_measured_join`` rows
   now carry per-axis comm time (``comm:psum[data]``, ``comm:ppermute[pipe]``,
   ...) exactly like the model's attention/mlp scopes — the per-stage timing
   telemetry MPMD pipeline work uses to find stragglers.
2. **Algorithmic byte counters** (traced): inside a
   :func:`comm_accounting` context, each traced collective call site adds
   its payload bytes to a :class:`CommAccount`, keyed by verb and axis.
   Like ``pyprof.per_scope_costs`` these are attribution shares at trace
   time — a call site inside ``lax.scan`` is counted once per trace, not
   per trip (document per-step multipliers yourself when scanning).

Host-side and allocation-free when no account is active: the only always-on
cost is the ``named_scope`` context, which exists at trace time only.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional, Tuple, Union

AxisNames = Union[str, Tuple[str, ...]]

# active accounts (innermost last). Plain module list: tracing is
# single-threaded per process; nested contexts both observe a call.
_ACTIVE: List["CommAccount"] = []


def _axis_label(axis: AxisNames) -> str:
    if isinstance(axis, (tuple, list)):
        return "+".join(str(a) for a in axis)
    return str(axis)


def _tree_bytes(tree: Any) -> Tuple[int, str]:
    """``(payload bytes, wire dtype)`` of a pytree of arrays/tracers
    (aval shape x itemsize). The dtype label is the leaves' common dtype
    ("mixed" when a multi-dtype tree rides one collective) — the wire-
    dtype dimension of the accounting, so an int8-quantized payload and
    its fp32 scale side-channel tally as separate rows."""
    import jax
    import numpy as np

    total = 0
    dtypes = set()
    for leaf in jax.tree.leaves(tree):
        try:
            size = int(np.prod(leaf.shape)) if leaf.shape else 1
            total += size * np.dtype(leaf.dtype).itemsize
            dtypes.add(str(np.dtype(leaf.dtype)))
        except Exception:  # noqa: BLE001 - tokens, python scalars
            continue
    if not dtypes:
        dtype = "none"
    elif len(dtypes) == 1:
        dtype = dtypes.pop()
    else:
        dtype = "mixed"
    return total, dtype


class CommAccount:
    """Byte/count tallies per (verb, axis, wire dtype) collective call
    site."""

    def __init__(self):
        self.records: List[Dict[str, Any]] = []

    def add(self, verb: str, axis: str, nbytes: int, dtype: str = "none"):
        self.records.append({"verb": verb, "axis": axis, "bytes": nbytes,
                             "dtype": dtype})

    def _group(self, key: str) -> Dict[str, Dict[str, int]]:
        out: Dict[str, Dict[str, int]] = {}
        for r in self.records:
            row = out.setdefault(r[key], {"bytes": 0, "calls": 0})
            row["bytes"] += r["bytes"]
            row["calls"] += 1
        return out

    def by_axis(self) -> Dict[str, Dict[str, int]]:
        """``{axis: {"bytes", "calls"}}`` — the dp/tp/pp/cp attribution."""
        return self._group("axis")

    def by_verb(self) -> Dict[str, Dict[str, int]]:
        return self._group("verb")

    def by_verb_dtype(self, axis: Optional[str] = None
                      ) -> Dict[str, Dict[str, int]]:
        """``{"<verb>[<dtype>]": {"bytes", "calls"}}`` — the wire-dtype
        rollup: a quantized reduce books its int8 payload and its fp32
        scale side-channel as distinct rows, so the 1/4-bytes compression
        claim (and the side-channel's cost) read straight off the table.
        ``axis`` restricts to one mesh axis (the evidence harnesses' view
        of the data-axis wire)."""
        out: Dict[str, Dict[str, int]] = {}
        for r in self.records:
            if axis is not None and r["axis"] != axis:
                continue
            key = f"{r['verb']}[{r.get('dtype', 'none')}]"
            row = out.setdefault(key, {"bytes": 0, "calls": 0})
            row["bytes"] += r["bytes"]
            row["calls"] += 1
        return out

    def total_bytes(self) -> int:
        return sum(r["bytes"] for r in self.records)

    def summary(self) -> Dict[str, Any]:
        return {"total_bytes": self.total_bytes(),
                "by_axis": self.by_axis(), "by_verb": self.by_verb(),
                "by_verb_dtype": self.by_verb_dtype()}


@contextlib.contextmanager
def comm_accounting():
    """Collect collective payload bytes for everything traced inside.

    >>> with comm_accounting() as acct:
    ...     jax.make_jaxpr(train_step)(params, opt_state, toks, tgts)
    >>> acct.by_axis()   # {"data": {"bytes": ..., "calls": ...}, ...}
    """
    acct = CommAccount()
    _ACTIVE.append(acct)
    try:
        yield acct
    finally:
        _ACTIVE.remove(acct)


def collective_scope(verb: str, axis: AxisNames, tree: Any):
    """Scope a collective call site: named range + byte accounting.

    Returns a context manager to wrap the ``lax`` collective in. The scope
    name ``comm:<verb>[<axis>]`` is the trace-join key; byte tallies go to
    every active :func:`comm_accounting` context.
    """
    import jax

    label = _axis_label(axis)
    if _ACTIVE:
        nbytes, dtype = _tree_bytes(tree)
        for acct in _ACTIVE:
            acct.add(verb, label, nbytes, dtype)
    try:
        # hang-attribution breadcrumb (monitor/flight.py): stamp the
        # scope being ENTERED so a process wedged inside it dies with
        # its name in the structured heartbeat (watchdog kill report).
        # This call site runs at TRACE time (and in the eager per-tick
        # drives), so it attributes compile-/trace-time and eager-drive
        # hangs; a COMPILED step wedged on-device is attributed by the
        # fetch-point breadcrumbs instead. A dict assignment when no
        # flight/heartbeat consumer is armed; the compiled program is
        # untouched either way.
        from apex_tpu.monitor import flight as _flight

        _flight.breadcrumb(f"comm:{verb}[{label}]")
    except Exception:  # noqa: BLE001 - telemetry must not kill tracing
        pass
    return jax.named_scope(f"comm:{verb}[{label}]")

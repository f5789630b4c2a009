"""Milliseconds of device time per step in the routed experts' grouped
products, on the busiest device: the instructions under the scope ``scope``
(the activation between the products and their gradients' sums) and the
grouped-product calls themselves, found by ``names`` on the instruction's
name: XLA:TPU rewrites ``ragged_dot`` into a custom call of its own
(``ragged-dot-none``) that keeps no ``op_name``, so no scope finds it.
Nothing where neither is found, as in a program built before the layer."""

import re

from .. import trace_reduce


def products(ops, scope, names):
    """The events of ``ops`` that belong to the grouped products."""
    by_scope, by_name = re.compile(scope), re.compile(names)
    return [e for e in ops if by_scope.search(e.get("scope") or "")
            or by_name.search(e.get("name") or "")]


def read(ctx, scope, names):
    worst = None
    for plane, ops in ctx["ops"].items():
        runs = len(ctx["runs"][plane])
        found = products(ops, scope, names)
        if not runs or not found:
            continue
        ms = trace_reduce.seconds_of(found) / runs * 1e3
        worst = ms if worst is None else max(worst, ms)
    return worst

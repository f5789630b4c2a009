"""Milliseconds of device time per step under a scope the program names
itself (``jax.named_scope``), on the busiest device: ``scope_time``, for a
metric that may be laid over a program built before the scopes existed.

The manifest promises a metric for a cell, and a promised metric that reads
nothing ends the run (``harness.read_metrics``): that is how a renamed scope
is seen. A program that names no phase at all is another case: no instruction
of it matches ``since`` (the scope every step of a program with the names
carries), no time was spent under a name it never gave, and the metric reads
0. Where ``since`` is found and ``include`` is not, a name went away, and
the reader returns nothing as ``scope_time`` does."""

from .. import trace_reduce
from . import scope_time


def read(ctx, include, exclude="", since=""):
    value = scope_time.read(ctx, include, exclude)
    if value is None and since and any(ctx["runs"].values()) and not any(
            trace_reduce.matching(ops, since) for ops in ctx["ops"].values()):
        return 0.0
    return value

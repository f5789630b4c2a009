"""Milliseconds of device time per step of the instructions whose scope
matches ``include`` and not ``exclude``, on the busiest device."""

from .. import trace_reduce


def read(ctx, include, exclude=""):
    worst = None
    for plane, ops in ctx["ops"].items():
        runs = len(ctx["runs"][plane])
        found = trace_reduce.matching(ops, include, exclude)
        if not runs or not found:
            continue
        ms = trace_reduce.seconds_of(found) / runs * 1e3
        worst = ms if worst is None else max(worst, ms)
    return worst

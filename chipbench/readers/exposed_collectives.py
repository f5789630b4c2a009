"""Milliseconds per step, on the worst device, inside collective
instructions (found by ``pattern`` on the instruction's name) during which
no other instruction runs on that device. 0 where the step ran and none
was exposed; nothing where no step ran."""

from .. import trace_reduce


def read(ctx, pattern):
    worst = None
    for plane in ctx["planes"]:
        runs = len(ctx["runs"][plane])
        if not runs:
            continue
        ms = trace_reduce.exposed_seconds(
            ctx["events"], plane, pattern) / runs * 1e3
        worst = ms if worst is None else max(worst, ms)
    return worst

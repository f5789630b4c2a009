"""Milliseconds, at the median, between the last instruction of one run of
the step program and the first of the next, on the first device."""

import statistics

from .. import trace_reduce


def read(ctx):
    plane = ctx["planes"][0]
    gaps = trace_reduce.step_gaps(ctx["events"], plane, ctx["module"])
    return statistics.median(gaps) * 1e3 if gaps else None

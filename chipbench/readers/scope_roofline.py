"""A scope's share of its roofline: the least time the chip could take for
what the scope computes in the traced steps (the larger of the algorithm's
operations over the peak rate and its bytes over the peak bandwidth,
forward and backward), over the device time of the instructions under the
scope ``pattern``. What the scope computes in one step is the
configuration's flops module's to say: its function ``work``, called as
``work(cfg, rows, seq, backward=...)``, counts the same whatever implements
the scope (XLA's fusions or a kernel). Executions that are recompute count
as time and not as work. Nothing where the flops module has no such
function or the program no such scope."""

from .. import flops, trace_reduce


def read(ctx, pattern, work):
    count = getattr(ctx["flops"], work, None)
    plane = ctx["planes"][0]
    found = trace_reduce.matching(ctx["ops"][plane], pattern)
    steps = len(ctx["runs"][plane])
    took = trace_reduce.seconds_of(found)
    if count is None or not found or not steps or took <= 0:
        return None
    rows, seq = ctx["rows"] // ctx["chips"], ctx["mix"]["seq"]
    least = steps * sum(
        flops.least_seconds(count(ctx["cfg"], rows, seq, backward=b),
                            ctx["peak"]) for b in (False, True))
    return 100.0 * least / took

"""An attention kernel's share of its roofline: the least time the chip
could take for softmax(Q K^T) V over every layer of the traced steps (the
larger of the algorithm's operations over the peak rate and its bytes over
the peak bandwidth), over the device time of the events that implement it,
found by ``pattern``. One layer's work and the number of attention layers
are the configuration's flops module's to say (``ctx["flops"]``): heads,
key-value heads and the head's width are read there, and a hybrid stack
counts only the layers that attend. Executions that are recompute count as
time and not as work."""

from .. import flops, trace_reduce


def read(ctx, pattern, backward, where="scope"):
    plane = ctx["planes"][0]
    found = trace_reduce.matching(ctx["ops"][plane], pattern, where=where)
    steps = len(ctx["runs"][plane])
    took = trace_reduce.seconds_of(found)
    if not found or not steps or took <= 0:
        return None
    work = ctx["flops"].attention_core(
        ctx["cfg"], ctx["rows"] // ctx["chips"], ctx["mix"]["seq"],
        causal=ctx["causal"], backward=backward)
    least = flops.least_seconds(work, ctx["peak"]) \
        * ctx["flops"].attention_layers(ctx["cfg"]) * steps
    return 100.0 * least / took

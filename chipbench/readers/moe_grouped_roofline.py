"""The routed experts' grouped products' share of their roofline: the least
time the chip could take for the products of the expected assignments
(``flops_moe.grouped_products``: 3 forward and 6 backward an expert layer a
step) over the device time ``moe_experts_time`` finds for them (the scope
``scope`` and the grouped-product calls by ``names``), in every expert layer
of the traced steps. Executions that are recompute count as time and not as
work. Nothing where the program has neither, as one built before the layer
existed."""

from .. import flops, flops_moe, trace_reduce
from .moe_experts_time import products


def read(ctx, scope, names):
    cfg = ctx["cfg"]
    if "moe_intermediate_size" not in cfg:
        return None
    plane = ctx["planes"][0]
    found = products(ctx["ops"][plane], scope, names)
    steps = len(ctx["runs"][plane])
    took = trace_reduce.seconds_of(found)
    if not found or not steps or took <= 0:
        return None
    work = flops_moe.grouped_products(
        cfg, ctx["rows"] // ctx["chips"] * ctx["mix"]["seq"])
    least = flops.least_seconds(work, ctx["peak"]) \
        * flops_moe.sizes(cfg)["expert_layers"] * steps
    return 100.0 * least / took

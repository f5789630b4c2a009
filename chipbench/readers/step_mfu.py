"""The whole step's share of the chips' peak: the operations the algorithm
needs for the tokens of the traced window (``train_flops_per_token`` of the
configuration's flops module; recompute and padding are not work) over the
traced window times the chips' peak rate."""


def read(ctx):
    need = ctx["flops_per_token"] * ctx["tokens"]
    if not need or ctx["window_s"] <= 0:
        return None
    return 100.0 * need / (ctx["window_s"] * ctx["chips"]
                           * ctx["peak"]["flops_per_s"])

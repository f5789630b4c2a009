"""The whole step's share of the chips' peak for a model ``flops.py`` does
not describe: the operations ``flops_moe.py`` counts for the tokens of the
traced window over the window times the chips' peak rate. Nothing where the
configuration is not of that kind."""

from .. import flops_moe


def read(ctx):
    cfg = ctx["cfg"]
    if "moe_intermediate_size" not in cfg or ctx["window_s"] <= 0:
        return None
    need = flops_moe.train_flops_per_token(cfg, ctx["mix"]["seq"]) \
        * ctx["tokens"]
    if not need:
        return None
    return 100.0 * need / (ctx["window_s"] * ctx["chips"]
                           * ctx["peak"]["flops_per_s"])

"""train_mfu: the window's required FLOP/s over the chips' bf16 peak, in %.

Valid tokens trained in the window x required FLOPs per token
(``bench/flops.py``), over window seconds x chips x peak.  Padding rows,
rematerialized passes and host time are not credited, so every one of
them lowers it; it moves ``train_tokens_per_s`` one for one."""


def read(ctx):
    peak = ctx["peak"]["bf16_flops_per_s"]
    done = ctx["valid_tokens"] * ctx["flops_per_token"]
    return 100.0 * done / (ctx["window_s"] * ctx["chips"] * peak)

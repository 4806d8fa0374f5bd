"""step_mfu: the whole train step's share of the chip's bf16 peak, in
percent: model operations of the window's tokens (the configuration's
``model_flops_per_token``: forward and backward once, no recomputation,
no MoE dispatch) over the window's seconds on the host clock times the
peak of the device kind.  Bounds what any kernel's gain can add.
Moves tokens_per_s."""


def read(ctx):
    t0, t1 = ctx["window"]
    ops = ctx["flops_per_token"] * ctx["tokens"]
    return 100.0 * ops / ((t1 - t0) * ctx["peak"]["bf16_flops"])

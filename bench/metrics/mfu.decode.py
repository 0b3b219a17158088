"""The whole step's share of the chip's peak: the model operations of
every token the window computed (decoded tokens and prompt chunks, each
attending its context) over the window, against the bf16 peak."""


def read(ctx):
    res = ctx.result
    total = ctx.flops.window_model_flops(ctx.family, ctx.cfg, res.steps,
                                         0.0, res.window_s)
    return 100.0 * total / res.window_s / ctx.peak["bf16_flops_per_s"]

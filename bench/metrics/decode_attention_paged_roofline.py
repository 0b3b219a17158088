"""The paged decode kernel's share of its roofline in the traced window:
the least time the chip could take for the steps' useful work (QK^T and
PV over the live cache tokens of every layer, each live K and V row read
once: the block family's ``decode_attention_step``) over the kernel's
summed device time (ops named ``decode_attention_paged.<n>``)."""
from bench import trace as T


def read(ctx):
    lo, hi = ctx.traced
    spent = T.kernel_seconds(T.clip(ctx.trace.ops[0], lo, hi),
                             "decode_attention_paged")
    if spent <= 0:
        return None
    least = 0.0
    for s in ctx.traced_steps:
        if s.live:
            fl, by = ctx.family.decode_attention_step(ctx.cfg, s.live)
            least += ctx.flops.roofline_seconds(fl, by, ctx.peak)
    return 100.0 * least / spent

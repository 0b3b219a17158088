"""Share of the traced window in which no operation ran on the chip while
the innermost program span open on the host was the scheduler's
(``repro.sched.*``: admission, planning, prompt chunks, first tokens,
activation); none where the program wrote no span in the window."""
from bench import program_spans as PS


def read(ctx):
    lo, hi = ctx.traced
    return PS.idle_share(ctx.trace.ops[0], ctx.trace.program, lo, hi,
                         "scheduler")

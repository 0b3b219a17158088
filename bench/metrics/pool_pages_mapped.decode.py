"""Share of the page pool mapped at the fullest engine step that began in
the traced window (``pages_used`` and ``pages_free`` of
``repro.engine.step``); none where no step carries them."""
from bench import program_spans as PS


def read(ctx):
    lo, hi = ctx.traced
    return PS.pool_pages_mapped(ctx.trace.program, lo, hi)

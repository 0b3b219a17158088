"""Share of the decode's block-table entries that are mapped, summed over
the engine steps that began in the traced window (``table_pages_live``
of ``table_pages`` on ``repro.engine.step``): the part of the
(slots x table width) grid the paged decode kernel walks; none where no
step carries the counts."""
from bench import program_spans as PS


def read(ctx):
    lo, hi = ctx.traced
    steps = [stats for name, start, _, stats in ctx.trace.program
             if name == PS.POOL_SPAN and lo <= start <= hi
             and stats.get("table_pages")]
    if not steps:
        return None
    return 100.0 * sum(a["table_pages_live"] for a in steps) \
        / sum(a["table_pages"] for a in steps)

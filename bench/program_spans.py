#!/usr/bin/env python3
"""Where the chip waits, by the program's own spans.

The serving program writes spans on the profiler's host timeline, on the
device's clock (``repro.sched.*`` for the scheduler, ``repro.engine.*``
for the engine and its page pool), each with host integers as stats
(``repro.engine.step`` carries the pool's ``pages_used`` and
``pages_free``).  ``bench.trace.load`` keeps them from an ``.xplane.pb``
as ``Trace.program``, and this module does the arithmetic on those plain
tuples, so it is checked on hand-made intervals and on a recorded trace
alike (``bench/tests/test_bench_program_spans.py``).  It matches spans
by prefix and imports nothing of the program.

Each idle gap of the device (``bench.trace.idle_gaps``) is split by the
innermost program span open at each instant: under a scheduler span, an
engine span, or none ("harness": the benchmark's loop and whatever else
the host did between steps).  The parts sum to the idle time.

  python3 bench/program_spans.py <trace dir or .xplane.pb>

prints one JSON object: the window (the ``bench.window`` span, else the
device's first to last op), its idle seconds by layer and by span, the
pool's largest share mapped, and the counts of steps and iterations.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import trace as T  # noqa: E402

ProgramEvent = T.ProgramEvent
Segment = Tuple[float, float, Optional[str]]  # innermost span, or None

LAYERS = (("repro.sched.", "scheduler"),
          ("repro.engine.", "engine + page pool"))
HARNESS = "harness"
POOL_SPAN = "repro.engine.step"


def layer_of(name: str) -> Optional[str]:
    for prefix, layer in LAYERS:
        if name.startswith(prefix):
            return layer
    return None


def in_window(program: Sequence[ProgramEvent], lo: float, hi: float
              ) -> List[ProgramEvent]:
    return [ev for ev in program if ev[2] > lo and ev[1] < hi]


def segments(program: Sequence[ProgramEvent], lo: float, hi: float
             ) -> List[Segment]:
    """[lo, hi] cut wherever a span starts or ends, each piece with the
    innermost (shortest) span open over it.  Spans of one thread nest."""
    spans = sorted((s, e, n) for n, s, e, _ in in_window(program, lo, hi))
    cuts = sorted({lo, hi} | {t for s, e, _ in spans for t in (s, e)
                              if lo < t < hi})
    out: List[Segment] = []
    i, open_ = 0, []
    for a, b in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i][0] <= a:
            open_.append(spans[i])
            i += 1
        open_ = [sp for sp in open_ if sp[1] > a]
        inner = min(open_, key=lambda sp: sp[1] - sp[0], default=None)
        out.append((a, b, inner[2] if inner else None))
    return out


def split_idle(ops: Sequence[T.Event], program: Sequence[ProgramEvent],
               lo: float, hi: float,
               key: Callable[[str], str] = layer_of) -> Dict[str, float]:
    """Idle seconds of [lo, hi] by ``key`` of the innermost program span
    open in them (``HARNESS`` under none); they sum to the idle time."""
    segs = segments(program, lo, hi)
    out: Dict[str, float] = {}
    j = 0
    for gs, ge in T.idle_gaps(ops, lo, hi):
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < ge:
            a, b, name = segs[k]
            d = min(b, ge) - max(a, gs)
            if d > 0:
                label = key(name) if name is not None else HARNESS
                out[label] = out.get(label, 0.0) + d
            k += 1
    return out


def idle_share(ops: Sequence[T.Event], program: Sequence[ProgramEvent],
               lo: float, hi: float, layer: str) -> Optional[float]:
    """Percent of [lo, hi] in which no op ran while the innermost program
    span was one of ``layer``'s; None where the window holds no program
    span (a program that writes none cannot say, and 0 would be false)."""
    if not in_window(program, lo, hi):
        return None
    return 100.0 * split_idle(ops, program, lo, hi).get(layer, 0.0) \
        / (hi - lo)


def pool_pages_mapped(program: Sequence[ProgramEvent], lo: float,
                      hi: float) -> Optional[float]:
    """Percent of the page pool mapped at the fullest engine step that
    began in [lo, hi]; None where no step carries the pool's counts."""
    shares = [100.0 * a["pages_used"] / (a["pages_used"] + a["pages_free"])
              for n, s, _, a in program
              if n == POOL_SPAN and lo <= s <= hi and "pages_used" in a]
    return max(shares) if shares else None


def summary(tr: T.Trace) -> Dict:
    ops, program = tr.ops[0], tr.program
    if any(n == T.SPAN_PREFIX + "window" for n, _, _ in tr.spans):
        lo, hi = T.window_of(tr, T.SPAN_PREFIX + "window")
    else:
        lo, hi = min(s for _, s, _ in ops), max(e for _, _, e in ops)
    idle = sum(e - s for s, e in T.idle_gaps(ops, lo, hi))
    return {
        "window_s": hi - lo,
        "idle_s": idle,
        "idle_s_by_layer": split_idle(ops, program, lo, hi),
        "idle_s_by_span": split_idle(ops, program, lo, hi, key=str),
        "pool_pages_mapped_pct": pool_pages_mapped(program, lo, hi),
        "iterations": sum(1 for n, s, _, _ in program
                          if n == "repro.sched.iteration" and lo <= s <= hi),
        "harness_steps": sum(1 for n, s, _ in tr.spans
                             if n == T.SPAN_PREFIX + "step"
                             and lo <= s <= hi),
    }


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = args[0]
    if Path(path).is_dir():
        path = T.find_xplane(path)
    tr = T.load(path)
    if not tr.ops:
        print(f"no device operations in {path}", file=sys.stderr)
        return 1
    print(json.dumps(summary(tr)))
    return 0


if __name__ == "__main__":
    sys.exit(main())

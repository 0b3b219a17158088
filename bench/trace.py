"""Reduction of a profiler trace to device busy time, idle gaps and
per-operation device time.

``jax.profiler`` writes an ``.xplane.pb``; ``load`` reads it with
``jax.profiler.ProfileData`` into plain tuples, and everything after that
works on tuples, so the arithmetic is checked on a small recorded trace
and on hand-made intervals alike (``bench/tests/test_bench_trace.py``).

Device planes are named ``/device:TPU:<n>``.  Their ``XLA Ops`` line
holds one event per operation run on the chip, named by its HLO text
(``%decode_attention_paged.9 = bf16[...] custom-call(...)``: a Pallas
kernel by its ``name=``); ``load`` keeps the instruction name before
`` = ``.  A loop's ``while`` op spans the ops of its body, which are
events of their own.  The ``XLA Modules`` line holds one event per
program run (``jit_fwd(<id>)``, kept as ``jit_fwd``).  The harness's own
spans (``jax.profiler.TraceAnnotation``, named ``bench.*``) are on the
host plane, on the same clock.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # (start_s, end_s)
Event = Tuple[str, float, float]  # (name, start_s, end_s)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:"
SPAN_PREFIX = "bench."


@dataclass
class Trace:
    ops: List[List[Event]] = field(default_factory=list)  # per device
    modules: List[List[Event]] = field(default_factory=list)  # per device
    spans: List[Event] = field(default_factory=list)  # harness spans


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and "CPU" not in plane.name:
            ops: List[Event] = []
            mods: List[Event] = []
            for line in plane.lines:
                dest = {OPS_LINE: ops, MODULES_LINE: mods}.get(line.name)
                if dest is None:
                    continue
                for e in line.events:
                    dest.append((short_name(e.name), e.start_ns * 1e-9,
                                 e.end_ns * 1e-9))
            if ops:
                tr.ops.append(ops)
                tr.modules.append(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append((e.name, e.start_ns * 1e-9,
                                         e.end_ns * 1e-9))
    return tr


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion.12``;
    ``jit_fwd(123)`` -> ``jit_fwd``."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return name.split("(", 1)[0]


CONTAINERS = ("while", "conditional", "call")


def leaf_ops(events: Sequence[Event]) -> List[Event]:
    """The operations that hold no others (a loop's ``while`` spans its
    body's ops, which are events of their own)."""
    return [ev for ev in events if ev[0].split(".")[0] not in CONTAINERS]


def window_of(tr: Trace, name: str) -> Interval:
    """The interval of the (one) harness span called ``name``."""
    hits = [(s, e) for n, s, e in tr.spans if n == name]
    if len(hits) != 1:
        raise RuntimeError(f"expected one {name!r} span, found {len(hits)}")
    return hits[0]


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for n, s, e in events:
        s2, e2 = max(s, lo), min(e, hi)
        if e2 > s2:
            out.append((n, s2, e2))
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals into disjoint ones, in time order."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_seconds(events: Sequence[Event], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which some operation ran."""
    return sum(e - s for s, e in union([(s, e) for _, s, e in
                                        clip(events, lo, hi)]))


def idle_gaps(events: Sequence[Event], lo: float, hi: float
              ) -> List[Interval]:
    """The stretches of [lo, hi] in which no operation ran."""
    gaps, t = [], lo
    for s, e in union([(s, e) for _, s, e in clip(events, lo, hi)]):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def sum_by_name(events: Sequence[Event]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for n, s, e in events:
        out[n] = out.get(n, 0.0) + (e - s)
    return out


def kernel_seconds(events: Sequence[Event], kernel: str) -> float:
    """Summed duration of one kernel's events (``<kernel>.<n>``)."""
    return sum(e - s for n, s, e in events if n.rsplit(".", 1)[0] == kernel)


def label(t: float, spans: Sequence[Event]) -> str:
    """The innermost harness span open at time ``t``; "harness" between
    spans (the loop's own bookkeeping)."""
    best: Optional[Event] = None
    for n, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (n, s, e)
    return best[0] if best else "harness"


def breakdown(tr: Trace, lo: float, hi: float, top: int = 10) -> Dict:
    """The device operations that took most time, and the longest idle
    gaps with what the host was doing, on the first device."""
    ops = clip(tr.ops[0], lo, hi)
    by_op = sorted(sum_by_name(leaf_ops(ops)).items(),
                   key=lambda kv: -kv[1])[:top]
    spans = [sp for sp in tr.spans if sp[0] != SPAN_PREFIX + "window"]
    gaps = sorted(idle_gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, s] for n, s in by_op],
            "idle_gaps": [[label((s + e) / 2, spans), e - s]
                          for s, e in gaps]}

"""The trace reduction: busy union, idle gaps, per-operation sums and the
labelling of gaps by the harness span open at the time."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace as T  # noqa: E402

OPS = [("fusion.1", 0.0, 1.0), ("decode_attention_paged", 0.5, 2.0),
       ("fusion.1", 3.0, 4.0), ("copy", 3.5, 3.8), ("fusion.2", 6.0, 9.0)]
SPANS = [("bench.window", 0.0, 8.0), ("bench.step", 0.0, 2.5),
         ("bench.step", 2.8, 4.5), ("bench.admit", 4.5, 4.9)]


def test_busy_is_the_union_clipped_to_the_window():
    assert T.busy_seconds(OPS, 0.0, 8.0) == pytest.approx(2.0 + 1.0 + 2.0)
    assert T.busy_seconds(OPS, 1.0, 3.5) == pytest.approx(1.0 + 0.5)


def test_idle_gaps_cover_the_rest():
    gaps = T.idle_gaps(OPS, 0.0, 8.0)
    assert gaps == [(2.0, 3.0), (4.0, 6.0)]
    assert sum(e - s for s, e in gaps) + T.busy_seconds(OPS, 0, 8) == 8.0
    assert T.idle_gaps([], 1.0, 2.0) == [(1.0, 2.0)]


def test_sums_by_name_and_by_needle():
    sums = T.sum_by_name(T.clip(OPS, 0.0, 8.0))
    assert sums["fusion.1"] == pytest.approx(2.0)
    assert sums["fusion.2"] == pytest.approx(2.0)
    assert T.kernel_seconds([("decode_attention_paged.9", 0.0, 1.5),
                             ("decode_attention_paged_q8.2", 0.0, 1.0)],
                            "decode_attention_paged") == pytest.approx(1.5)


def test_gaps_are_labelled_by_the_innermost_span():
    assert T.label(2.6, SPANS) == "bench.window"
    assert T.label(1.0, SPANS) == "bench.step"
    assert T.label(9.0, SPANS) == "harness"
    tr = T.Trace(ops=[OPS], modules=[[]], spans=SPANS)
    bd = T.breakdown(tr, 0.0, 8.0)
    assert bd["device_ops"][0][0] in ("fusion.1", "fusion.2")
    assert bd["idle_gaps"][0] == ["harness", pytest.approx(2.0)]
    assert bd["idle_gaps"][1] == ["bench.step", pytest.approx(1.0)]


RECORDED = Path(__file__).resolve().parent / "data" / "decode_2layer.xplane.pb"


def test_recorded_trace_of_a_decode_window():
    """A trace recorded on one TPU v5e: 15 decode steps of Mistral-7B
    widths cut to 2 layers, 32 slots (``bench/run.py --trace 1`` with a
    0.5 s traced window)."""
    tr = T.load(str(RECORDED))
    assert len(tr.ops) == 1
    lo, hi = T.window_of(tr, "bench.window")
    assert hi - lo == pytest.approx(0.468882, abs=1e-6)
    ops = T.clip(tr.ops[0], lo, hi)
    busy = T.busy_seconds(ops, lo, hi)
    assert busy == pytest.approx(0.421021, abs=1e-6)
    gaps = T.idle_gaps(ops, lo, hi)
    assert sum(e - s for s, e in gaps) + busy == pytest.approx(hi - lo)
    kernel = [ev for ev in ops if ev[0] == "decode_attention_paged.9"]
    assert len(kernel) == 30  # 2 layers x 15 steps
    assert T.kernel_seconds(ops, "decode_attention_paged") == \
        pytest.approx(0.345265, abs=1e-6)
    mods = T.sum_by_name(T.clip(tr.modules[0], lo, hi))
    assert set(mods) == {"jit_fwd", "jit__argmax"}
    assert sum(1 for n, _, _ in T.clip(tr.modules[0], lo, hi)
               if n == "jit_fwd") == 15
    bd = T.breakdown(tr, lo, hi)
    assert bd["device_ops"][0][0] == "decode_attention_paged.9"
    assert all(not n.startswith("while") for n, _ in bd["device_ops"])
    assert {g[0] for g in bd["idle_gaps"]} <= {"bench.step", "harness"}
    steps = [sp for sp in tr.spans if sp[0] == "bench.step"]
    assert len(steps) >= 14

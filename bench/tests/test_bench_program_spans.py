"""Idle gaps put down to the program's own spans: the split of each gap by
the innermost span, the pool's share mapped, no value from a trace with
no program span, and a trace recorded on the chip."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import program_spans as PS  # noqa: E402
from bench import trace as T  # noqa: E402

SCHED, ENGINE = "scheduler", "engine + page pool"
# device busy in [0, 1], [3, 4] and [6, 9]: gaps (1, 3) and (4, 6) of [0, 8]
OPS = [("fusion.1", 0.0, 1.0), ("fusion.2", 3.0, 4.0),
       ("decode_attention_paged.9", 6.0, 9.0)]
PROGRAM = [
    ("repro.sched.iteration", 0.5, 4.5, {"n_decode": 4}),
    ("repro.sched.admit", 0.6, 1.5, {"admitted": 1, "deferred": 0}),
    ("repro.engine.step", 1.8, 4.2, {"n_active": 4, "pages_used": 30,
                                     "pages_free": 70, "preempted": 0}),
    ("repro.engine.sync", 2.5, 3.5, {}),
    ("repro.sched.iteration", 5.5, 9.0, {"n_decode": 4}),
    ("repro.engine.step", 5.6, 8.8, {"n_active": 4, "pages_used": 45,
                                     "pages_free": 55, "preempted": 1}),
]


def test_gaps_split_by_the_innermost_span_sum_to_the_idle_time():
    split = PS.split_idle(OPS, PROGRAM, 0.0, 8.0)
    idle = sum(e - s for s, e in T.idle_gaps(OPS, 0.0, 8.0))
    assert sum(split.values()) == pytest.approx(idle) == 4.0
    # gap (1, 3): admit to 1.5, iteration to 1.8, engine.step to 2.5,
    # sync to 3; gap (4, 6): step to 4.2, iteration to 4.5, none to 5.5,
    # iteration to 5.6, the second step to 6
    assert split == pytest.approx({SCHED: 0.5 + 0.3 + 0.3 + 0.1,
                                   ENGINE: 0.7 + 0.5 + 0.2 + 0.4,
                                   PS.HARNESS: 1.0})
    by_span = PS.split_idle(OPS, PROGRAM, 0.0, 8.0, key=str)
    assert by_span["repro.engine.sync"] == pytest.approx(0.5)
    assert by_span["repro.sched.admit"] == pytest.approx(0.5)


def test_a_gap_is_split_where_a_span_boundary_falls_inside_it():
    ops = [("a", 0.0, 1.0), ("b", 2.0, 3.0)]
    prog = [("repro.engine.step", 0.0, 1.25, {}),
            ("repro.sched.plan", 1.75, 2.0, {})]
    assert PS.split_idle(ops, prog, 0.0, 3.0) == pytest.approx(
        {ENGINE: 0.25, PS.HARNESS: 0.5, SCHED: 0.25})
    segs = PS.segments(prog, 0.0, 3.0)
    assert [s[2] for s in segs] == ["repro.engine.step", None,
                                    "repro.sched.plan", None]


def test_idle_shares_and_the_pool_share():
    assert PS.idle_share(OPS, PROGRAM, 0.0, 8.0, SCHED) == \
        pytest.approx(100 * 1.2 / 8)
    assert PS.idle_share(OPS, PROGRAM, 0.0, 8.0, ENGINE) == \
        pytest.approx(100 * 1.8 / 8)
    assert PS.pool_pages_mapped(PROGRAM, 0.0, 8.0) == pytest.approx(45.0)
    assert PS.pool_pages_mapped(PROGRAM, 0.0, 5.0) == pytest.approx(30.0)


@pytest.mark.parametrize("program", [[], [("repro.sched.plan", 9.0, 9.5,
                                           {})]],
                         ids=["no_span", "span_outside_the_window"])
def test_no_program_span_gives_no_value(program):
    """A program that writes no span (an older one) gives no reading, not
    a zero."""
    for layer in (SCHED, ENGINE):
        assert PS.idle_share(OPS, program, 0.0, 8.0, layer) is None
    assert PS.pool_pages_mapped(program, 0.0, 8.0) is None


def test_harness_spans_are_not_program_spans():
    assert PS.layer_of("bench.step") is None
    assert PS.layer_of("repro.sched.chunk") == SCHED
    assert PS.layer_of("repro.engine.sync") == ENGINE


DATA = Path(__file__).resolve().parent / "data"
OLD = DATA / "decode_2layer.xplane.pb"
RECORDED = DATA / "decode_2layer_spans.xplane.pb"


def test_a_trace_without_program_spans_reads_none():
    """The trace recorded before the program wrote spans: no value."""
    tr = T.load(str(OLD))
    lo, hi = T.window_of(tr, "bench.window")
    program = tr.program
    assert program == []
    assert PS.idle_share(tr.ops[0], program, lo, hi, SCHED) is None
    assert PS.pool_pages_mapped(program, lo, hi) is None


def test_recorded_trace_with_program_spans():
    """A trace recorded on one TPU v5e with the program's spans: 14 decode
    steps of Mistral-7B widths cut to 2 layers, 32 slots (``bench/run.py
    --trace 1`` with a 0.44 s traced window)."""
    tr = T.load(str(RECORDED))
    lo, hi = T.window_of(tr, "bench.window")
    assert hi - lo == pytest.approx(0.437088, abs=1e-6)
    # the harness's reduction reads what it read before the program wrote
    # spans: its own spans only, gaps labelled by them
    assert all(n.startswith("bench.") for n, _, _ in tr.spans)
    bd = T.breakdown(tr, lo, hi)
    assert bd["device_ops"][0][0] == "decode_attention_paged.9"
    assert {g[0] for g in bd["idle_gaps"]} <= {"bench.step", "harness"}
    mods = T.sum_by_name(T.clip(tr.modules[0], lo, hi))
    assert {"jit_decode_step", "jit_sample_greedy"} <= set(mods)
    assert "jit_fwd" not in mods
    program = tr.program
    assert program
    steps = [sp for sp in tr.spans
             if sp[0] == "bench.step" and lo <= sp[1] and sp[2] <= hi]
    iters = [ev for ev in program if ev[0] == "repro.sched.iteration"]
    assert len(steps) == 14
    for _, s, e in steps:
        assert sum(1 for ev in iters if s <= ev[1] and ev[2] <= e) == 1
    # the host blocks in sync while the decode program runs on the chip
    decode = [(s, e) for n, s, e in tr.modules[0]
              if n == "jit_decode_step"]
    syncs = [ev for ev in program if ev[0] == "repro.engine.sync"]
    assert len(syncs) >= 14
    for _, s, e, _ in syncs:
        assert any(a < e and s < b for a, b in decode)
    split = PS.split_idle(tr.ops[0], program, lo, hi)
    idle = sum(e - s for s, e in T.idle_gaps(tr.ops[0], lo, hi))
    assert sum(split.values()) == pytest.approx(idle)
    assert split[ENGINE] > split[SCHED] > 0
    assert PS.idle_share(tr.ops[0], program, lo, hi, SCHED) + \
        PS.idle_share(tr.ops[0], program, lo, hi, ENGINE) <= \
        100.0 * idle / (hi - lo)
    assert PS.pool_pages_mapped(program, lo, hi) == pytest.approx(
        55.045573, abs=1e-5)


def test_cli_prints_the_summary(capsys):
    assert PS.main([str(RECORDED)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["iterations"] == out["harness_steps"] == 14
    assert sum(out["idle_s_by_layer"].values()) == \
        pytest.approx(out["idle_s"])
    assert max(out["idle_s_by_span"], key=out["idle_s_by_span"].get) == \
        "repro.engine.sync"


READERS = ("device_idle_sched.decode", "device_idle_engine.decode",
           "pool_pages_mapped.decode", "table_pages_live.decode")


def read_all(tr, lo, hi):
    """Each program-span metric as ``bench/run.py`` reads it: its reader
    file, given the trace as ``bench.trace.load`` keeps it."""
    from types import SimpleNamespace

    from bench.spec import Spec
    ctx = SimpleNamespace(trace=tr, traced=(lo, hi),
                          busy_s=T.busy_seconds(tr.ops[0], lo, hi))
    names = READERS + ("device_idle.decode",)
    return {m: Spec().reader(m)(ctx) for m in names}


def test_readers_on_the_recorded_trace():
    """The trace recorded with the program's spans (before the engine
    counted its block tables): the scheduler's and the engine's part of
    the idle time, within the device's, and the pool's share mapped."""
    tr = T.load(str(RECORDED))
    lo, hi = T.window_of(tr, "bench.window")
    got = read_all(tr, lo, hi)
    assert got["device_idle_sched.decode"] == pytest.approx(0.321413,
                                                            abs=1e-6)
    assert got["device_idle_engine.decode"] == pytest.approx(9.388704,
                                                             abs=1e-6)
    assert got["device_idle_sched.decode"] + \
        got["device_idle_engine.decode"] <= got["device_idle.decode"]
    assert got["pool_pages_mapped.decode"] == pytest.approx(55.045573,
                                                            abs=1e-5)
    assert got["table_pages_live.decode"] is None


def test_readers_read_nothing_without_program_spans():
    tr = T.load(str(OLD))
    lo, hi = T.window_of(tr, "bench.window")
    got = read_all(tr, lo, hi)
    assert all(got[m] is None for m in READERS)
    assert got["device_idle.decode"] > 0


def test_table_share_sums_the_steps_begun_in_the_window():
    def step(t, live, table):
        return ("repro.engine.step", t, t + 0.5,
                {"pages_used": live, "pages_free": 200 - live,
                 "table_pages_live": live, "table_pages": table})

    program = [step(1.0, 30, 100), ("repro.engine.sync", 1.1, 1.4, {}),
               step(3.0, 50, 100), step(9.0, 100, 100)]
    tr = T.Trace(ops=[OPS], modules=[[]], spans=[], program=program)
    got = read_all(tr, 0.0, 8.0)
    assert got["table_pages_live.decode"] == pytest.approx(40.0)
    assert got["pool_pages_mapped.decode"] == pytest.approx(25.0)

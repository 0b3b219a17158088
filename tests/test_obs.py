"""Observability subsystem tests: metrics math, trace invariants, the
Perfetto export, engine integration, and the off-by-default contract.

The trace-invariant block is the load-bearing part: every submitted
request must reach exactly ONE terminal ("finish") event — through
preempt/resume cycles included — spans on a track must be non-overlapping
and time-monotonic, and the bounded ring must drop OLDEST-first without
ever corrupting an open span.  A hypothesis test drives the observer
hooks with the same request-trace generator shape as
``test_paged_properties`` (admit / step×4 / release over a tiny slot
set), and an engine integration test replays ``test_paged``'s
pool-starved preempt/resume recipe with obs on.  The program spans are
read back from a real profiler trace of a scheduled engine: one
iteration span per step, the engine's spans nested in its step span,
the pool's counts as stats.
"""
import math
import os

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dev dep — property tests skip
    from _hypothesis_stub import given, settings, st

from repro import obs as O

MAX_EXAMPLES = int(os.environ.get("PROPERTY_EXAMPLES", "25"))


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    m = O.MetricsRegistry()
    c = m.counter("c", "help text")
    c.inc()
    c.inc(3)
    assert c.value == 4
    assert m.counter("c") is c, "get-or-create returns the live object"
    g = m.gauge("g")
    g.set(5)
    g.set(2)
    assert g.value == 2 and g.high_water == 5
    g.set_max(1)
    assert g.value == 2, "set_max is a ratchet, never lowers"
    g.set_max(9)
    assert g.value == 9 and g.high_water == 9


def test_lazy_gauge_reads_at_collect_time():
    m = O.MetricsRegistry()
    box = {"v": 1}
    m.gauge_fn("lazy", lambda: box["v"])
    assert m["lazy"].collect()["value"] == 1
    box["v"] = 7
    assert m["lazy"].collect()["value"] == 7, "evaluated at collect, not set"


def test_histogram_percentiles_and_exclusion():
    m = O.MetricsRegistry()
    h = m.histogram("h", lo=1e-3, hi=1e3)
    values = [0.002, 0.01, 0.05, 0.05, 0.2, 1.0, 5.0, 40.0]
    for v in values:
        h.observe(v)
    h.observe(None)
    h.observe(float("nan"))
    col = h.collect()
    assert col["count"] == len(values) and col["n_excluded"] == 2
    assert col["min"] == 0.002 and col["max"] == 40.0
    assert abs(col["sum"] - sum(values)) < 1e-12
    # quantiles are order-respecting and clamped to the observed range
    p50, p90, p99 = h.percentile(0.5), h.percentile(0.9), h.percentile(0.99)
    assert 0.002 <= p50 <= p90 <= p99 <= 40.0
    assert p50 <= col["mean"] * 5  # same order of magnitude, log buckets
    # empty histogram: everything None, never a crash or a zero
    h2 = O.Histogram("empty")
    assert h2.percentile(0.5) is None and h2.mean is None
    assert h2.collect()["min"] is None


def test_histogram_bucket_edges_are_exclusive_lower_inclusive_upper():
    h = O.Histogram("h", lo=1.0, hi=100.0, per_decade=1)
    # edges are [10, 100]; 10.0 must land in bucket 0 (le=10), 10.1 in 1
    h.observe(10.0)
    h.observe(10.1)
    assert h.buckets[0] == 1 and h.buckets[1] == 1
    h.observe(0.5)  # under lo -> underflow, still counted
    assert h.underflow == 1 and h.count == 3


def test_prometheus_text_format():
    m = O.MetricsRegistry()
    m.counter("reqs", "requests").inc(3)
    m.gauge("depth").set(2)
    h = m.histogram("lat", lo=0.1, hi=10.0, per_decade=1)
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = m.to_prometheus()
    assert "# TYPE reqs counter" in text and "reqs_total 3" in text
    assert "depth 2" in text
    # cumulative le buckets: each line's count >= the previous
    lines = [ln for ln in text.splitlines() if ln.startswith("lat_bucket")]
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in lines]
    assert counts == sorted(counts) and counts[-1] == 3
    assert 'le="+Inf"' in lines[-1]
    assert "lat_sum" in text and "lat_count 3" in text


# ---------------------------------------------------------------------------
# trace ring
# ---------------------------------------------------------------------------

def test_trace_ring_drops_oldest_without_corrupting_open_spans():
    tr = O.TraceBuffer(capacity=4)
    rt = O.request_track(0)
    tr.begin(rt, "decode", t=0.0)  # open span: lives OUTSIDE the ring
    for i in range(10):
        tr.instant(O.engine_track(), f"i{i}", t=1.0 + i)
    assert len(tr) == 4 and tr.n_dropped == 6
    # oldest dropped first: the survivors are the LAST four instants
    assert [e["name"] for e in tr.events()] == ["i6", "i7", "i8", "i9"]
    assert tr.open_spans() == [(rt, "decode")], "drop must not touch opens"
    doc = tr.to_perfetto()
    O.validate_perfetto(doc)
    assert doc["otherData"]["n_dropped"] == 6
    tr.end(rt, "decode", t=20.0)  # still closable after heavy churn
    assert tr.open_spans() == []
    assert tr.events()[-1]["name"] == "decode"
    O.validate_perfetto(tr.to_perfetto())


def test_trace_end_without_begin_is_noop():
    tr = O.TraceBuffer()
    tr.end(O.request_track(1), "never-begun", t=1.0)
    assert len(tr) == 0 and tr.open_spans() == []


def test_trace_nested_spans_close_innermost_first():
    tr = O.TraceBuffer()
    et = O.engine_track()
    tr.begin(et, "outer", t=0.0)
    tr.begin(et, "inner", t=1.0)
    tr.end(et, "inner", t=2.0)
    tr.end(et, "outer", t=3.0)
    evs = tr.events()
    assert [(e["name"], e["t0"], e["dur"]) for e in evs] == [
        ("inner", 1.0, 1.0), ("outer", 0.0, 3.0)]
    O.validate_perfetto(tr.to_perfetto())


def test_perfetto_export_structure():
    tr = O.TraceBuffer()
    tr.complete(O.slot_track(2), "prefill", 0.0, 0.5, rid=7)
    tr.instant(O.request_track(7), "finish", t=0.5)
    tr.counter(O.engine_track(), "pool", 3, t=0.6)
    tr.begin(O.request_track(8), "decode", t=0.7)  # stays open
    doc = tr.to_perfetto()
    counts = O.validate_perfetto(doc)
    assert counts["X"] == 1 and counts["i"] == 1 and counts["C"] == 1
    assert counts["B"] == 1  # the open span exports as unfinished B
    # one lane per family: slots pid != requests pid != engine pid
    pids = {ev["pid"] for ev in doc["traceEvents"] if ev["ph"] != "M"}
    assert len(pids) == 3
    # timestamps are non-negative microseconds from the earliest event
    ts = [ev["ts"] for ev in doc["traceEvents"] if ev["ph"] != "M"]
    assert min(ts) == 0.0 and max(ts) == pytest.approx(0.7e6)


# ---------------------------------------------------------------------------
# observer trace invariants (hypothesis — the test_paged_properties
# request-trace generator shape: admit / step x4 / release)
# ---------------------------------------------------------------------------

N_SLOTS = 4


def _trace_strategy():
    return st.lists(
        st.tuples(
            st.sampled_from(["admit", "step", "step", "step", "step",
                             "release"]),
            st.integers(min_value=0, max_value=7),
            st.integers(min_value=1, max_value=40),
        ),
        min_size=1, max_size=60)


class _FakeClockObserver(O.Observer):
    """Observer with a deterministic strictly-increasing clock, so span
    monotonicity is checkable exactly."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self._t = 0.0

    def clock(self):
        self._t += 0.25
        return self._t


class _Req:
    def __init__(self, rid, t_arrival):
        self.rid = rid
        self.prompt = np.zeros((4,), np.int32)
        self.out_tokens = []
        self.t_arrival = t_arrival
        self.t_first = None
        self.t_last = None


@settings(max_examples=MAX_EXAMPLES, deadline=None)
@given(trace=_trace_strategy())
def test_observer_request_lifecycle_invariants(trace):
    obs = _FakeClockObserver(trace_capacity=4096)  # big enough: no drops
    free = list(range(N_SLOTS))
    active, preempted, finished = {}, [], set()
    rids = []

    def finish(slot):
        req = active.pop(slot)
        free.append(slot)
        req.t_last = obs.clock()
        obs.request_finished(req, decode_tok_s=None, ttft_s=0.1)
        finished.add(req.rid)

    for op, sel, n in trace:
        if op == "admit" and free:
            resume = bool(preempted) and sel % 2 == 0
            if resume:
                req = preempted.pop(0)
            else:
                req = _Req(len(rids), obs.clock())
                rids.append(req.rid)
            slot = free.pop(0)
            t_p0 = obs.clock()
            if not resume:
                req.t_first = req.t_last = obs.clock()
                req.out_tokens = [1]
            active[slot] = req
            obs.request_admitted(req, slot, n_shared=0, resume=resume,
                                 bucket_len=8, t_prefill0=t_p0)
        elif op == "step" and active:
            t0 = obs.clock()
            for req in active.values():
                req.out_tokens.append(1)
                req.t_last = obs.clock()
            obs.step_done(t0, obs.clock(), n_active=len(active),
                          n_tokens=len(active))
        elif op == "release" and active:
            slot = sorted(active)[sel % len(active)]
            if n % 3 == 0:  # preempt instead of finishing
                req = active.pop(slot)
                free.append(slot)
                obs.request_preempted(req, slot)
                preempted.append(req)
            else:
                finish(slot)
    # drain: resume-then-finish everything still live, as generate() does
    for slot in sorted(active):
        finish(slot)
    while preempted:
        req = preempted.pop(0)
        slot = free.pop(0)
        obs.request_admitted(req, slot, n_shared=0, resume=True,
                             bucket_len=8, t_prefill0=obs.clock())
        active[slot] = req
        finish(slot)

    assert obs.trace.n_dropped == 0  # invariants below need every event
    events = obs.trace.events()
    # 1. exactly one terminal event per finished request, zero for others
    for rid in rids:
        n_fin = sum(1 for e in events
                    if e["track"] == O.request_track(rid)
                    and e["name"] == "finish")
        assert n_fin == (1 if rid in finished else 0), (rid, n_fin)
    assert finished == set(rids)  # the drain finishes everyone
    # 2. every timestamp sits inside the run's clock envelope, and the
    # terminal instant is the LAST event ever recorded on its track
    t_final = obs.clock()
    for e in events:
        assert 0.0 < e["t0"] <= e["t0"] + e.get("dur", 0.0) <= t_final, e
    for rid in rids:
        on_track = [e for e in events
                    if e["track"] == O.request_track(rid)]
        assert on_track[-1]["name"] == "finish", rid
    # 3. spans on ANY track are non-overlapping and monotonic: a request
    # (or slot, or the engine loop) is in exactly one state at a time
    by_track = {}
    for e in events:
        if e["ph"] == "X":
            by_track.setdefault(e["track"], []).append(e)
    for track, spans in by_track.items():
        end = -math.inf
        for e in sorted(spans, key=lambda e: e["t0"]):
            assert e["t0"] >= end, (track, e)
            end = e["t0"] + e["dur"]
    # 4. no span left open, and the export is structurally valid
    assert obs.trace.open_spans() == []
    if events:  # all-no-op traces export an empty document
        counts = O.validate_perfetto(obs.trace.to_perfetto())
        assert counts.get("B", 0) == 0
    # 5. metrics agree with the model
    m = obs.metrics
    assert m["serve_requests_finished"].value == len(finished)
    assert m["serve_ttft_seconds"].count == len(rids)


# ---------------------------------------------------------------------------
# engine integration (slow-ish: real models) + off-mode contract
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_model():
    import jax
    from repro.configs import get_config, reduce_config
    from repro.models import init_params
    cfg = reduce_config(get_config("llama3.2-1b"))
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def test_engine_obs_off_by_default(small_model):
    from repro.serving import Engine, ServeConfig
    cfg, params = small_model
    eng = Engine(cfg, params, ServeConfig(n_slots=2, max_len=32))
    assert eng.obs is O.NULL and not eng.obs.enabled
    outs = eng.generate([np.arange(6), np.arange(4)], max_new_tokens=3)
    # stats read through the always-on registry even with obs off
    assert eng.stats == {"peak_active": 2, "n_preempted": 0, "n_deferred": 0}
    assert eng.metrics["serve_peak_active"].high_water == 2
    assert all(o.decode_tok_s is None or o.decode_tok_s > 0 for o in outs)
    snap = O.snapshot(eng)
    assert snap["engine"]["obs_enabled"] is False


def test_engine_obs_preempt_resume_single_terminal(small_model):
    """test_paged's pool-starved recipe, instrumented: preemptions fire,
    every request still reaches exactly one terminal span, and the
    Perfetto export stays structurally valid."""
    from repro.serving import Engine, PagedCacheAdapter, ServeConfig
    cfg, params = small_model
    eng = Engine(cfg, params,
                 ServeConfig(n_slots=3, max_len=64, obs=True),
                 cache=PagedCacheAdapter(block_size=8, n_blocks=7))
    prompts = [np.arange(8) + i for i in range(3)]
    outs = eng.generate(prompts, max_new_tokens=20)
    assert eng.stats["n_preempted"] > 0, "workload sized to force preemption"
    assert all(len(o) == 20 for o in outs)

    tr = eng.obs.trace
    for rid in range(3):
        evs = O.request_events(tr, rid)
        assert sum(e["name"] == "finish" for e in evs) == 1, (rid, evs)
        # preempt instants pair with later resumes: the request's decode
        # spans never overlap
        spans = sorted((e for e in evs if e["ph"] == "X"),
                       key=lambda e: e["t0"])
        end = -math.inf
        for e in spans:
            assert e["t0"] >= end - 1e-9, (rid, e)
            end = e["t0"] + e["dur"]
    n_preempts = sum(1 for e in tr.events() if e["name"] == "preempt")
    assert n_preempts == eng.stats["n_preempted"]
    assert tr.open_spans() == []
    O.validate_perfetto(tr.to_perfetto())

    m = eng.metrics
    assert m["serve_requests_finished"].value == 3
    assert m["serve_requests_resumed"].value > 0
    assert m["serve_decode_step_seconds"].count > 0
    # pool telemetry is lifted as lazy gauges
    assert m["pool_peak_used"].collect()["value"] == \
        eng.pm.allocator.peak_used
    doc = O.serving_obs_doc(eng)
    assert doc["headline"]["preempted"] == eng.stats["n_preempted"]
    assert doc["headline"]["ttft_p99_ms"] > 0


def test_single_token_request_tok_s_is_excluded_not_zero(small_model):
    """A request generating exactly one token has no steady-state decode
    rate: decode_tok_s must be None (not 0.0) and histogram-excluded."""
    from repro.serving import Engine, ServeConfig
    cfg, params = small_model
    eng = Engine(cfg, params, ServeConfig(n_slots=2, max_len=32, obs=True))
    outs = eng.generate([np.arange(6)], max_new_tokens=1)
    assert outs[0].decode_tok_s is None
    assert outs[0].stats["decode_tok_s"] is None
    h = eng.metrics["serve_decode_tok_s"]
    assert h.count == 0 and h.n_excluded == 1
    assert h.mean is None, "no zero pollution of the aggregate"


def test_engine_source_uses_monotonic_clock_only():
    """Durations must come from time.perf_counter (monotonic) — a
    wall-clock time.time() skews TTFT/tok_s under NTP steps.  Pin the
    engine source."""
    import inspect
    from repro.serving import engine
    src = inspect.getsource(engine)
    assert "time.time(" not in src
    assert "time.perf_counter(" in src


def test_null_observer_is_inert():
    assert O.NULL.clock() == 0.0
    # every hook is the shared no-op and accepts anything
    O.NULL.request_admitted("x", 1, n_shared=0, resume=False,
                            bucket_len=8, t_prefill0=0.0)
    O.NULL.step_done(0, 0, n_active=0, n_tokens=0)
    O.NULL.compile_event("decode", None, 0, 0.0)
    assert O.get_active() is O.NULL


def test_activated_scopes_the_active_observer():
    obs = O.Observer()
    assert O.get_active() is O.NULL
    with O.activated(obs) as got:
        assert got is obs and O.get_active() is obs
        with O.activated(O.NULL):
            assert O.get_active() is O.NULL
        assert O.get_active() is obs
    assert O.get_active() is O.NULL


@pytest.mark.parametrize("scheduled", [False, True],
                         ids=["engine", "scheduled"])
def test_observer_on_and_off_serve_the_same_greedy_tokens(small_model,
                                                          scheduled):
    """Observation must never perturb what it observes: the same prompts
    served greedy with the observer off and on give the same tokens."""
    from repro.serving import (Engine, PagedCacheAdapter, SchedConfig,
                               ScheduledEngine, ServeConfig)
    cfg, params = small_model
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, size=(int(n),))
               .astype(np.int32) for n in rng.randint(4, 24, size=6)]
    outs = {}
    for on in (False, True):
        sc = ServeConfig(n_slots=4, max_len=64, obs=on)
        cache = PagedCacheAdapter(block_size=8, n_blocks=32)
        eng = (ScheduledEngine(cfg, params, sc,
                               scfg=SchedConfig(token_budget=32,
                                                chunk_tokens=16),
                               cache=cache) if scheduled
               else Engine(cfg, params, sc, cache=cache))
        outs[on] = eng.generate(prompts, max_new_tokens=8)
        assert eng.obs.enabled is on
    assert [list(o) for o in outs[False]] == [list(o) for o in outs[True]]


# ---------------------------------------------------------------------------
# program spans on the profiler's timeline
# ---------------------------------------------------------------------------

def _program_spans(log_dir):
    """(name, start_ns, end_ns, stats) of every ``repro.`` host event of
    the one trace under ``log_dir``, in time order."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda sp: (sp[1], -sp[2]))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_program_spans_nest_per_iteration_and_carry_pool_counts(
        small_model, tmp_path):
    """Under a profiler session every ``ScheduledEngine.step`` writes one
    ``repro.sched.iteration``; every other span lies inside one, and the
    ``repro.engine.*`` spans inside the iteration's ``repro.engine.step``,
    whose ``pages_used`` is the pool's pages in use as the step begins,
    and whose ``table_pages_live`` counts the mapped entries of the decode's
    block tables (shielded slots' rows as -1) out of ``table_pages``."""
    import jax
    from repro.serving import (PagedCacheAdapter, SchedConfig,
                               ScheduledEngine, ServeConfig)
    cfg, params = small_model
    eng = ScheduledEngine(cfg, params, ServeConfig(n_slots=3, max_len=64),
                          scfg=SchedConfig(token_budget=32, chunk_tokens=16),
                          cache=PagedCacheAdapter(block_size=8, n_blocks=24))
    prompts = [np.arange(20) + i for i in range(4)]
    eng.generate(prompts, max_new_tokens=4)  # compile outside the trace
    used, live = [], []
    make_appendable = eng._make_appendable

    def spy():
        used.append(eng.pm.allocator.n_used)
        tables = np.asarray(eng.kv.device_cache().block_tables)
        live.append(int(np.count_nonzero(tables >= 0)))
        make_appendable()

    eng._make_appendable = spy
    n0 = eng.n_iterations
    with jax.profiler.trace(str(tmp_path)):
        outs = eng.generate(prompts, max_new_tokens=6)
    assert all(len(o) == 6 for o in outs)
    spans = _program_spans(str(tmp_path))
    assert {sp[0] for sp in spans} == set(O.SPANS)
    iters = [sp for sp in spans if sp[0] == "repro.sched.iteration"]
    assert len(iters) == eng.n_iterations - n0
    steps = [sp for sp in spans if sp[0] == "repro.engine.step"]
    assert [sp[3]["pages_used"] for sp in steps] == used
    assert [sp[3]["table_pages_live"] for sp in steps] == live
    assert any(0 < n < 3 * 8 for n in live)
    for sp in steps:
        assert sp[3]["pages_used"] + sp[3]["pages_free"] == 24
        assert sp[3]["table_pages"] == 3 * 64 // 8  # slots x table width
        assert sp[3]["n_active"] >= 1 and sp[3]["preempted"] >= 0
    for it in iters:
        assert set(it[3]) == {"waiting", "prefilling", "n_decode",
                              "n_chunks", "chunk_tokens", "budget_used"}
        assert sum(_inside(sp, it) for sp in steps) <= 1
    for sp in spans:
        if sp[0] == "repro.sched.iteration":
            continue
        assert sum(_inside(sp, it) for it in iters) == 1, sp
        if sp[0].startswith("repro.engine.") and sp[0] != "repro.engine.step":
            assert sum(_inside(sp, st) for st in steps) == 1, sp
    chunks = [sp for sp in spans if sp[0] == "repro.sched.chunk"]
    assert sum(sp[3]["tokens"] for sp in chunks) == 4 * 20
    assert sum(sp[3]["final"] for sp in chunks) == 4
    admits = [sp for sp in spans if sp[0] == "repro.sched.admit"]
    assert sum(sp[3]["admitted"] for sp in admits) == 4


def test_program_span_table_names_each_layer_by_prefix():
    """The benchmark matches program spans by prefix alone, so a span's
    name says its layer; with no profiler session a span is inert."""
    prefix = {"scheduler": "repro.sched.",
              "engine + page pool": "repro.engine."}
    for name, layer in O.SPANS.items():
        assert name.startswith(prefix[layer]), (name, layer)
    with O.span("repro.engine.step", n_active=1) as sp:
        sp.set_metadata(pages_used=3)
    assert not type(sp).is_enabled()


@pytest.mark.parametrize("cache", ["dense", "paged", "paged_q8"])
def test_serving_programs_lower_under_their_names(small_model, cache):
    """The profiler names a program run by its module: the decode step,
    the whole-prompt and chunk prefills and greedy sampling each lower
    under a name of their own."""
    import jax
    import jax.numpy as jnp
    from repro.serving import (PagedCacheAdapter, PagedQ8CacheAdapter,
                               SchedConfig, ScheduledEngine, ServeConfig)
    from repro.serving.engine import sample_greedy
    cfg, params = small_model
    adapter = {"dense": lambda: "dense",
               "paged": lambda: PagedCacheAdapter(block_size=8, n_blocks=24),
               "paged_q8": lambda: PagedQ8CacheAdapter(block_size=8,
                                                       n_blocks=24)}[cache]
    eng = ScheduledEngine(cfg, params, ServeConfig(n_slots=2, max_len=64),
                          scfg=SchedConfig(token_budget=32, chunk_tokens=16),
                          cache=adapter())
    lowered = []
    chunk = eng.kv._chunk

    def spy(*args):
        lowered.append(chunk.lower(*args).as_text())
        return chunk(*args)

    eng.kv._chunk = spy
    eng.generate([np.arange(20)], max_new_tokens=2)
    assert lowered and all("@jit_chunk_prefill" in t for t in lowered)
    assert "HloModule jit_decode_step" in eng.compiled_decode().as_text()
    assert "HloModule jit_prompt_prefill" in \
        eng.compiled_prefill(16).as_text()
    logits = jax.ShapeDtypeStruct((2, cfg.vocab_size + 3), jnp.float32)
    assert "@jit_sample_greedy" in sample_greedy.lower(
        logits, vocab_size=cfg.vocab_size).as_text()

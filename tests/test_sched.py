"""The continuous-batching scheduler: chunked prefill must be pure
scheduling — never math.

The acceptance grid pins the ScheduledEngine greedy-token-identical to
the synchronous whole-prompt engine's oracle across every (cache_kind ×
style × impl) serving combo, plus the sliding-window row (paged: the
ring pins chunk width == block size; dense: a BINDING window falls back
to monolithic jobs — still asynchronous admission, still identical
tokens) and a pool-starved paged cell where mid-prefill chunks preempt
live decoders and deferred admissions resume.

Around the grid: planner unit semantics (budget accounting, FCFS
head-blocking, monolithic cost clamp), config/build-time validation
errors, the observer integration (sched_iteration / chunk spans land on
the right tracks; NullObserver carries both hooks as no-ops), and the
``NoSyncPrefillInSubmit`` lint audit — clean on the scheduled engines,
FIRING on the synchronous engine it exists to deprecate.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs as O
from repro.configs import get_config, reduce_config
from repro.core import merge_skipless
from repro.lint import submitpath
from repro.models import forward_seq, init_params
from repro.serving import (Engine, PagedCacheAdapter, PagedQ8CacheAdapter,
                           ServeConfig, SchedConfig, ScheduledEngine)
from repro.serving.engine import Request
from repro.serving.sched import PrefillJob, plan_iteration

MAX_NEW = 4
CHUNK = 8
WIN = 3           # sliding-window row: window smaller than prompt 0
WIN_BLOCK = 2     # paged ring pins chunk width == block size there
WIN_MAX_NEW = 5


def _greedy_oracle(params, cfg, prompt, n):
    toks = list(prompt)
    out = []
    for _ in range(n):
        lg, _, _ = forward_seq(params, cfg,
                               jnp.asarray(toks, jnp.int32)[None])
        t = int(jnp.argmax(lg[0, -1, :cfg.vocab_size]))
        out.append(t)
        toks.append(t)
    return out


@pytest.fixture(scope="module")
def setup():
    """Base model + merged rewrites + full-sequence oracle streams (MHA
    so kp/vp apply; scaled float32 embeddings keep greedy argmax
    well-conditioned — the test_backend_registry recipe)."""
    cfg = reduce_config(get_config("mistral-7b")).with_(
        block_style="skipless", dtype="float32", param_dtype="float32",
        n_kv_heads=4, sliding_window=0)  # windowless: dense cells chunk
    params = init_params(jax.random.PRNGKey(0), cfg)
    params["embed"]["table"] = params["embed"]["table"] * 50.0
    models = {"generic": (cfg, params)}
    for variant in ("qp", "kp", "vp"):
        mp, mc = merge_skipless(params, cfg, variant)
        models[variant] = (mc, mp)
    # prompt 1 longer than one chunk, prompt 0 shorter: both chunk-count
    # classes in one serve
    prompts = [np.arange(5) % cfg.vocab_size,
               (np.arange(11) * 3 + 2) % cfg.vocab_size]
    oracle = [_greedy_oracle(params, cfg, p, MAX_NEW) for p in prompts]
    return models, prompts, oracle


def _sched_engine(cfg, params, cache_kind, impl="xla", n_slots=2,
                  max_len=48, n_blocks=None, block=CHUNK, chunk=CHUNK,
                  budget=None, obs=False):
    cache = PagedCacheAdapter(
        block_size=block,
        n_blocks=n_blocks if n_blocks is not None
        else n_slots * max_len // block) \
        if cache_kind == "paged" else "dense"
    return ScheduledEngine(
        cfg, params, ServeConfig(n_slots=n_slots, max_len=max_len, obs=obs),
        scfg=SchedConfig(token_budget=budget or 4 * chunk,
                         chunk_tokens=chunk),
        impl=impl, cache=cache)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("style", ["generic", "qp", "kp", "vp"])
@pytest.mark.parametrize("cache_kind", ["dense", "paged"])
def test_chunked_matches_whole_prompt_oracle(setup, cache_kind, style,
                                             impl):
    """The equivalence grid: chunked prefill + planned iterations emit
    greedy streams identical to the unmerged full-sequence oracle (which
    test_backend_registry already pins to the synchronous whole-prompt
    engine) on every registered serving combo."""
    models, prompts, oracle = setup
    cfg, params = models[style]
    eng = _sched_engine(cfg, params, cache_kind, impl=impl)
    assert eng._chunked, "windowless attn combos must chunk"
    outs = eng.generate(prompts, max_new_tokens=MAX_NEW)
    for p, o, want in zip(prompts, outs, oracle):
        assert o == want, (cache_kind, style, impl, list(p[:3]))
    assert eng.n_iterations > 0 and eng.n_chunks_run >= len(prompts)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("style", ["generic", "qp"])
def test_chunked_q8_matches_synchronous_q8(setup, style, impl):
    """The paged_q8 row of the chunked grid: chunk-by-chunk quantized
    prefill (each chunk masked-quantized into its own pages, scales
    frontier-tracked through ``PagedQ8ChunkDest``) must emit a greedy
    stream bit-identical to the synchronous whole-prompt paged_q8
    engine's — the determinism contract is that chunked and whole
    prefill write the SAME int8 pool bits, so the comparison is
    identity, not closeness (the fp oracle would differ by quantization
    noise; this gate pins the scheduling seam only)."""
    models, prompts, _ = setup
    cfg, params = models[style]
    sync = Engine(cfg, params, ServeConfig(n_slots=2, max_len=48),
                  impl="xla", cache=PagedQ8CacheAdapter(block_size=CHUNK))
    want = sync.generate(prompts, max_new_tokens=MAX_NEW)
    eng = ScheduledEngine(
        cfg, params, ServeConfig(n_slots=2, max_len=48),
        scfg=SchedConfig(token_budget=4 * CHUNK, chunk_tokens=CHUNK),
        impl=impl,
        cache=PagedQ8CacheAdapter(block_size=CHUNK,
                                  n_blocks=2 * 48 // CHUNK))
    assert eng._chunked, "windowless q8 combos must chunk like fp paged"
    outs = eng.generate(prompts, max_new_tokens=MAX_NEW)
    for p, o, w in zip(prompts, outs, want):
        assert o == w, (style, impl, list(p[:3]), o, w)
    assert eng.n_chunks_run >= len(prompts)


@pytest.fixture(scope="module")
def setup_windowed():
    cfg = reduce_config(get_config("mistral-7b")).with_(
        block_style="skipless", dtype="float32", param_dtype="float32",
        n_kv_heads=4, sliding_window=WIN)
    params = init_params(jax.random.PRNGKey(1), cfg)
    params["embed"]["table"] = params["embed"]["table"] * 50.0
    prompts = [np.arange(7) % cfg.vocab_size,
               (np.arange(2) * 7 + 2) % cfg.vocab_size]
    oracle = [_greedy_oracle(params, cfg, p, WIN_MAX_NEW) for p in prompts]
    return cfg, params, prompts, oracle


@pytest.mark.parametrize("cache_kind", ["dense", "paged"])
def test_sliding_window_row(setup_windowed, cache_kind):
    """The window row: paged rings chunk at the block width and stays
    chunked; dense with a BINDING window cannot hold a partial prompt in
    its ring, so jobs fall back to monolithic whole-prompt prefills —
    admission is still queue-only, tokens still match the oracle."""
    cfg, params, prompts, oracle = setup_windowed
    if cache_kind == "paged":
        eng = _sched_engine(cfg, params, "paged", block=WIN_BLOCK,
                            chunk=WIN_BLOCK, budget=8, n_blocks=24)
        assert eng._chunked
    else:
        eng = _sched_engine(cfg, params, "dense", chunk=CHUNK)
        assert not eng._chunked, \
            "binding-window dense must fall back to monolithic jobs"
    outs = eng.generate(prompts, max_new_tokens=WIN_MAX_NEW)
    for o, want in zip(outs, oracle):
        assert o == want, cache_kind


def test_tiny_pool_preemption_and_deferral_identical(setup):
    """Pool-starved paged cell: deferred admissions queue (FCFS), chunks
    evict live decoders when no page maps, preempted requests resume —
    and the streams STILL match the oracle exactly."""
    models, prompts, oracle = setup
    cfg, params = models["generic"]
    many = prompts + [(np.arange(9) * 5 + 1) % cfg.vocab_size,
                      (np.arange(6) * 11 + 4) % cfg.vocab_size]
    want = oracle + [_greedy_oracle(params, cfg, p, MAX_NEW)
                     for p in many[2:]]
    # budget wide enough that all four requests chunk in the SAME
    # iteration and stay alive together: 8 final blocks demanded of 6
    eng = _sched_engine(cfg, params, "paged", n_slots=4, n_blocks=6,
                        budget=64)
    outs = eng.generate(many, max_new_tokens=MAX_NEW)
    assert eng.stats["n_deferred"] + eng.stats["n_preempted"] > 0, \
        "pool sized to starve: deferral or preemption must fire"
    for o, w in zip(outs, want):
        assert o == w
    eng.pm.drop_prefix_cache()
    assert eng.pm.allocator.n_used == 0, "drained pool leaks no pages"


# ---------------------------------------------------------------------------
# planner unit semantics
# ---------------------------------------------------------------------------

def _job(n, monolithic=False, slot=0, cursor=0):
    r = Request(prompt=np.zeros((n,), np.int32), max_new_tokens=4)
    j = PrefillJob(req=r, toks=np.zeros((n,), np.int32),
                   monolithic=monolithic)
    j.slot, j.cursor = slot, cursor
    return j


def test_planner_budget_and_fcfs_head_blocking():
    scfg = SchedConfig(token_budget=16, chunk_tokens=8)
    jobs = [_job(20), _job(8), _job(8)]
    # 10 decode slots leave 6 < 8 budget: the HEAD doesn't fit, and FCFS
    # must not skip ahead to a later job (that is what starves the head)
    s = plan_iteration(scfg, 10, jobs)
    assert s.chunks == [] and s.budget_used == 10
    # 0 decodes: head always fits (budget >= chunk) — no starvation
    s = plan_iteration(scfg, 0, jobs)
    assert [c.job for c in s.chunks] == [jobs[0], jobs[1]]
    assert s.budget_used == 16 and s.budget == 16
    assert (s.chunks[0].start, s.chunks[0].end, s.chunks[0].final) \
        == (0, 8, False)
    assert (s.chunks[1].start, s.chunks[1].end, s.chunks[1].final) \
        == (0, 8, True)


def test_planner_monolithic_cost_clamp_preserves_liveness():
    """A monolithic job longer than the whole budget charges min(total,
    budget) — otherwise it could NEVER fit and the queue would starve
    behind it forever."""
    scfg = SchedConfig(token_budget=16, chunk_tokens=8)
    s = plan_iteration(scfg, 0, [_job(40, monolithic=True)])
    assert len(s.chunks) == 1
    c = s.chunks[0]
    assert (c.start, c.end, c.cost, c.final) == (0, 40, 16, True)
    # with even one decode active it must wait (cost clamp, not zero)
    assert plan_iteration(scfg, 1, [_job(40, monolithic=True)]).chunks == []


def test_planner_skips_done_and_resumes_cursor():
    scfg = SchedConfig(token_budget=32, chunk_tokens=8)
    done = _job(8, cursor=8)
    mid = _job(20, cursor=8)
    s = plan_iteration(scfg, 0, [done, mid])
    assert [c.job for c in s.chunks] == [mid]
    assert (s.chunks[0].start, s.chunks[0].end, s.chunks[0].final) \
        == (8, 16, False)


def test_config_and_build_validation(setup):
    models, _, _ = setup
    cfg, params = models["generic"]
    with pytest.raises(ValueError):
        SchedConfig(token_budget=4, chunk_tokens=8)  # budget < chunk
    with pytest.raises(ValueError):
        SchedConfig(token_budget=8, chunk_tokens=0)
    with pytest.raises(ValueError, match="multiple of"):
        _sched_engine(cfg, params, "dense", max_len=44)  # 44 % 8 != 0
    with pytest.raises(ValueError, match="block size"):
        # paged chunk width must be block-aligned (chunk 4, block 8)
        ScheduledEngine(cfg, params, ServeConfig(n_slots=2, max_len=48),
                        scfg=SchedConfig(token_budget=16, chunk_tokens=4),
                        cache=PagedCacheAdapter(block_size=8))
    eng = _sched_engine(cfg, params, "dense")
    with pytest.raises(ValueError, match="attention-only"):
        eng.submit(Request(prompt=np.arange(4), max_new_tokens=2),
                   vision=np.zeros((2, 2), np.float32))
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.submit(Request(prompt=np.zeros((47,), np.int32),
                           max_new_tokens=8))


# ---------------------------------------------------------------------------
# observer integration + off-mode contract
# ---------------------------------------------------------------------------

def test_scheduler_observability(setup):
    """Scheduler decisions must land in repro.obs: per-iteration spans on
    the engine track with the budget counter track, chunk spans on BOTH
    the request and slot tracks, the always-on counters agreeing with the
    engine's own telemetry — and the export still structurally valid."""
    models, prompts, _ = setup
    cfg, params = models["generic"]
    eng = _sched_engine(cfg, params, "paged", obs=True)
    eng.generate(prompts, max_new_tokens=MAX_NEW)
    assert eng.obs.enabled

    m = eng.obs.metrics
    assert m["sched_iterations"].value == eng.n_iterations > 0
    assert m["sched_chunks"].value == eng.n_chunks_run > 0
    assert m["sched_chunk_tokens"].value >= eng.n_chunks_run * CHUNK
    assert m["sched_chunk_seconds"].count == eng.n_chunks_run

    evs = eng.obs.trace.events()
    iters = [e for e in evs if e["name"] == "sched_iteration"]
    assert len(iters) == eng.n_iterations
    assert all(e["track"] == O.engine_track() for e in iters)
    budget = [e for e in evs if e["name"] == "sched_budget_used"]
    assert budget and all(e["ph"] == "C" and
                          0 < e["args"]["value"] <= 4 * CHUNK
                          for e in budget)
    chunks = [e for e in evs if e["name"] == "chunk"]
    req_tracks = {e["track"] for e in chunks}
    slot_tracks = {e["track"] for e in chunks}
    assert any(t == O.request_track(0) for t in req_tracks)
    assert any(t == O.slot_track(0) or t == O.slot_track(1)
               for t in slot_tracks)
    assert len(chunks) == 2 * eng.n_chunks_run, \
        "each executed chunk spans its request AND its slot track"
    assert eng.obs.trace.open_spans() == []
    O.validate_perfetto(eng.obs.trace.to_perfetto())


def test_null_observer_carries_sched_hooks_as_noops(setup):
    """The off-mode contract extends to the scheduler's hooks: obs-off
    engines bind the module NULL singleton whose sched hooks are the
    shared no-op, one attribute load and an empty call each."""
    models, prompts, _ = setup
    cfg, params = models["generic"]
    eng = _sched_engine(cfg, params, "dense")
    assert eng.obs is O.NULL and not eng.obs.enabled
    noop = type(O.NULL).step_done
    assert type(O.NULL).sched_iteration is noop
    assert type(O.NULL).chunk_done is noop
    outs = eng.generate(prompts, max_new_tokens=MAX_NEW)
    assert all(len(o) == MAX_NEW for o in outs)
    # always-on telemetry still reads through stats with obs off
    assert eng.stats["sched_iterations"] == eng.n_iterations > 0


# ---------------------------------------------------------------------------
# NoSyncPrefillInSubmit
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lint_model():
    cfg = reduce_config(get_config("llama3.2-1b"))
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def test_submit_audit_clean_on_scheduled_engine(lint_model):
    cfg, params = lint_model
    eng = ScheduledEngine(cfg, params, ServeConfig(n_slots=2, max_len=48),
                          scfg=SchedConfig(token_budget=32,
                                           chunk_tokens=16))
    assert submitpath.audit_submit(eng, "sched") == []


def test_submit_audit_fires_on_synchronous_engine(lint_model):
    """The negative control: the rule must actually DETECT the class it
    bans — the synchronous engine's submit dispatches its prefill through
    the spied seam and the audit reports it."""
    cfg, params = lint_model
    sync = Engine(cfg, params, ServeConfig(n_slots=4, max_len=48))
    findings = submitpath.audit_submit(sync, "sync")
    assert findings, "synchronous submit must trip NoSyncPrefillInSubmit"
    assert all(f.rule == submitpath.RULE_SUBMIT for f in findings)
    assert any(f.detail["seam"] == "kv._prefill" for f in findings)
    # and the positive control recognises the same engine as observable
    assert submitpath.positive_control(
        Engine(cfg, params, ServeConfig(n_slots=2, max_len=48)),
        "sync") == []


def test_positive_control_fails_vacuous_spies(lint_model):
    """If the spied seam observes NO dispatch from the synchronous
    engine, the audit must fail itself rather than certify vacuously."""
    cfg, params = lint_model

    class _Deaf:
        """An 'engine' whose submit never touches the spied seams."""
        def __init__(self, real):
            self.kv = real.kv
            self.cfg = real.cfg
            self._decode = lambda *a, **k: None

        def submit(self, req, vision=None):
            return True

    deaf = _Deaf(Engine(cfg, params, ServeConfig(n_slots=2, max_len=48)))
    findings = submitpath.positive_control(deaf, "deaf")
    assert len(findings) == 1
    assert "positive control FAILED" in findings[0].message

"""repro.lint: every rule proven LIVE (negative-fire on a bad program),
the registry sweep proven COMPLETE (target count == registry size), and
the host-aliasing detector proven both clean on the real engines and
firing on sabotaged ones."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.lint import (DonationEffective, Finding, LintRule, LintTarget,
                        NoDequantizedPoolBuffer, NoDtypePromotionDrift,
                        NoForbiddenMatmul, NoHostTransferInObsHooks,
                        NoHostTransferInStepLoop, NoOversizedBuffer,
                        NoPerLayerPoolSlice, aliasing,
                        get_rule, register_rule, registered_rules, run_rules,
                        sweep, walker)
from repro.lint.builtin import HOST_TRANSFER_PRIMITIVES
from repro.models import backends, init_params
from repro.serving import Engine, ServeConfig
from repro.serving.hostbufs import ALIGN, aligned_empty, aligned_zeros
from repro.serving.paged_kv_cache import PagedDecodeCache

MAX_LEN = 160  # collides with no reduced model dim (cf. test_paged_prefill)


# ---------------------------------------------------------------------------
# walker
# ---------------------------------------------------------------------------

def test_walker_recurses_into_scan_and_cond():
    def inner(c, x):
        y = jnp.where(c.sum() > 0,
                      jnp.dot(c, x),          # dot inside the branch
                      jnp.dot(x, c))
        return y, y

    def f(c, xs):
        return jax.lax.scan(inner, c, xs)

    c = jnp.zeros((3, 3))
    xs = jnp.zeros((5, 3, 3))
    jx = jax.make_jaxpr(f)(c, xs)
    # both dots live inside the scan body: a non-recursive count sees 0
    assert walker.count_primitive(jx, "dot_general") == 2
    assert sum(1 for e in walker.as_jaxpr(jx).eqns
               if e.primitive.name == "dot_general") == 0
    # aval stream includes scan-internal shapes
    assert any(tuple(getattr(a, "shape", ())) == (3, 3)
               for a in walker.iter_avals(jx))
    assert "scan" in walker.primitive_names(jx)


def test_donated_flat_indices_count_pytree_leaves():
    args = ({"a": jnp.zeros(2), "b": jnp.zeros(2)}, jnp.zeros(3),
            [jnp.zeros(1)] * 3)
    assert walker.donated_flat_indices(args, (1,)) == [2]
    assert walker.donated_flat_indices(args, (0, 2)) == [0, 1, 3, 4, 5]


# ---------------------------------------------------------------------------
# rule registry
# ---------------------------------------------------------------------------

def test_rule_registry_latest_wins_and_loud_unknown():
    class Probe(LintRule):
        name = "test_probe_rule"
        description = "x"

        def applies(self, t):
            return False

        def check(self, t):
            return []

    try:
        first, second = Probe(), Probe()
        register_rule(first)
        register_rule(second)
        assert get_rule("test_probe_rule") is second
        assert "test_probe_rule" in registered_rules()
        with pytest.raises(KeyError, match="registered rules"):
            get_rule("no_such_rule")
    finally:
        from repro.lint.rules import _RULES
        _RULES.pop("test_probe_rule", None)


# ---------------------------------------------------------------------------
# negative fire: every built-in rule must trigger on a bad program
# ---------------------------------------------------------------------------

def _target(**kw):
    base = dict(phase="decode", cache_kind="dense", style="generic",
                impl="xla", jaxpr=None)
    base.update(kw)
    return LintTarget(**base)


def test_no_forbidden_matmul_fires_when_q_is_left_in():
    # a "merged" program that is secretly the UNMERGED one: same count as
    # its source, so the required (-2) delta is violated
    f = jax.make_jaxpr(lambda a, b: a @ b @ b)(jnp.zeros((3, 3)),
                                               jnp.zeros((3, 3)))
    t = _target(style="merged", jaxpr=f, source_jaxpr=f)
    findings = NoForbiddenMatmul().check(t)
    assert findings and findings[0].rule == "NoForbiddenMatmul"
    assert findings[0].detail == {"merged": 2, "source": 2}
    # ...and stays quiet on an honest -2 delta
    g = jax.make_jaxpr(lambda a, b: a @ b @ b @ a @ b)(jnp.zeros((3, 3)),
                                                       jnp.zeros((3, 3)))
    assert NoForbiddenMatmul().check(_target(style="merged", jaxpr=f,
                                             source_jaxpr=g)) == []


def test_no_oversized_buffer_fires_on_max_len_intermediate():
    bad = jax.make_jaxpr(
        lambda x: (jnp.zeros((1, MAX_LEN, 4)) + x).sum())(jnp.zeros((4,)))
    t = _target(phase="prefill", cache_kind="paged", jaxpr=bad,
                max_len=MAX_LEN)
    findings = NoOversizedBuffer().check(t)
    assert findings and str(MAX_LEN) in findings[0].message
    ok = jax.make_jaxpr(lambda x: x * 2)(jnp.zeros((4,)))
    assert NoOversizedBuffer().check(_target(
        phase="prefill", cache_kind="paged", jaxpr=ok, max_len=MAX_LEN)) == []


def test_donation_effective_fires_on_dropped_donation():
    # b is donated but NO output matches its aval -> jax silently drops
    # the donation; the rule must not
    a = jax.ShapeDtypeStruct((4,), jnp.float32)
    b = jax.ShapeDtypeStruct((6,), jnp.float32)

    def f(x, y):
        return x + y.sum()

    lowered = jax.jit(f, donate_argnums=(1,)).lower(a, b)
    t = _target(jaxpr=None, lowered=lowered,
                donated_flat=tuple(walker.donated_flat_indices((a, b), (1,))))
    findings = DonationEffective().check(t)
    assert findings and "donat" in findings[0].message
    # effective donation (same-aval output) passes
    lowered_ok = jax.jit(lambda x, y: (x.sum(), y + 1),
                         donate_argnums=(1,)).lower(a, b)
    t_ok = _target(jaxpr=None, lowered=lowered_ok,
                   donated_flat=tuple(walker.donated_flat_indices((a, b),
                                                                  (1,))))
    assert DonationEffective().check(t_ok) == []


def test_dtype_promotion_drift_fires_on_fp32_shadow():
    shape = (4, 8)
    k = jnp.zeros(shape, jnp.bfloat16)

    def drift(x):  # a full cache-shaped fp32 shadow of a bf16 buffer
        return (x.astype(jnp.float32) + 1.0).astype(jnp.bfloat16)

    t = _target(jaxpr=jax.make_jaxpr(drift)(k), cache_shapes=(shape,),
                cache_dtype=jnp.bfloat16)
    findings = NoDtypePromotionDrift().check(t)
    assert findings and "float32" in str(findings[0].detail)

    def clean(x):
        return x + jnp.bfloat16(1.0)

    t2 = _target(jaxpr=jax.make_jaxpr(clean)(k), cache_shapes=(shape,),
                 cache_dtype=jnp.bfloat16)
    assert NoDtypePromotionDrift().check(t2) == []


def test_no_dequantized_pool_buffer_fires_on_fp32_shadow():
    """A paged_q8 program that materializes ``pool.astype(f32)`` — the
    convenience bug the rule exists for — must fire; tile-bounded or
    gathered-row dequant (different shapes) must not."""
    shape = (6, 8, 2, 4)  # (n_blocks, block, heads, d_head) int8 pool
    pool = jnp.zeros(shape, jnp.int8)
    scale = jnp.zeros((6, 2), jnp.float32)

    def bad(p, s):  # a full-precision shadow of the whole pool
        return (p.astype(jnp.float32) * s[:, None, :, None]).sum()

    t = _target(cache_kind="paged_q8", jaxpr=jax.make_jaxpr(bad)(pool, scale),
                cache_shapes=(shape,), cache_dtype=jnp.int8)
    findings = NoDequantizedPoolBuffer().check(t)
    assert findings and findings[0].rule == "NoDequantizedPoolBuffer"
    assert "float32" in str(findings[0].detail)

    def clean(p, s):  # gathered-rows dequant: NOT pool-shaped
        rows = p[jnp.array([0, 2])].astype(jnp.float32)
        return (rows * s[jnp.array([0, 2])][:, None, :, None]).sum()

    t2 = _target(cache_kind="paged_q8",
                 jaxpr=jax.make_jaxpr(clean)(pool, scale),
                 cache_shapes=(shape,), cache_dtype=jnp.int8)
    assert NoDequantizedPoolBuffer().check(t2) == []
    # int32 would be just as fatal as float32 — itemsize is the test
    def bad_int(p):
        return p.astype(jnp.int32).sum()
    t3 = _target(cache_kind="paged_q8",
                 jaxpr=jax.make_jaxpr(bad_int)(pool),
                 cache_shapes=(shape,), cache_dtype=jnp.int8)
    assert NoDequantizedPoolBuffer().check(t3)
    # rule is scoped to paged_q8 programs only
    assert not NoDequantizedPoolBuffer().applies(
        _target(cache_kind="paged", cache_shapes=(shape,)))


def test_no_per_layer_pool_slice_fires_on_sliced_layer_scan(sweep_report):
    """A layer scan that takes each layer's pool as its xs and stacks the
    written pools as its ys (the paged programs' old scan) holds one
    layer's (NB, bs, Hkv, D) pool and fires, at any dtype; the same scan
    carrying the stack and writing at ``[layer, page, offset]`` is quiet,
    and so is every paged decode and chunk program of the sweep."""
    shape = (3, 6, 8, 2, 4)  # (L, n_blocks, block, heads, d_head)
    h0 = jnp.ones((2, 4), jnp.bfloat16)

    def sliced(pools):
        def f(h, pool):
            return h, pool.at[1, 0].set(h.astype(pool.dtype))
        return jax.lax.scan(f, h0, pools)[1]

    def carried(pools):
        def f(st, layer):
            return st.at[layer, 1, 0].set(h0.astype(st.dtype)), None
        return jax.lax.scan(f, pools, jnp.arange(shape[0]))[0]

    for dtype in (jnp.bfloat16, jnp.int8):
        pools = jnp.zeros(shape, dtype)
        for phase, kind in (("decode", "paged"), ("chunk", "paged_q8")):
            def target(fn):
                return _target(phase=phase, cache_kind=kind,
                               cache_shapes=(shape,),
                               jaxpr=jax.make_jaxpr(fn)(pools))

            findings = NoPerLayerPoolSlice().check(target(sliced))
            assert findings and findings[0].rule == "NoPerLayerPoolSlice"
            assert [6, 8, 2, 4] in [h[0] for h in findings[0].detail["hits"]]
            assert NoPerLayerPoolSlice().check(target(carried)) == []
    # scoped to the paged decode and chunk programs
    assert not NoPerLayerPoolSlice().applies(
        _target(phase="prefill", cache_kind="paged", cache_shapes=(shape,)))
    assert not NoPerLayerPoolSlice().applies(
        _target(phase="decode", cache_kind="dense", cache_shapes=(shape,)))
    paged = [t for t in sweep_report.targets
             if t.phase in ("decode", "chunk")
             and t.cache_kind in ("paged", "paged_q8")]
    assert {(t.phase, t.cache_kind) for t in paged} == {
        (p, k) for p in ("decode", "chunk") for k in ("paged", "paged_q8")}
    for t in paged:
        assert "NoPerLayerPoolSlice" in t.rules_run, t.key
        assert not [f for f in t.findings
                    if f.rule == "NoPerLayerPoolSlice"], t.key


def test_host_transfer_fires_on_debug_print_in_step():
    def leaky(x):
        jax.debug.print("tok {}", x[0])
        return x * 2

    jx = jax.make_jaxpr(leaky)(jnp.zeros((3,)))
    # the primitive jax.debug.print lowers to is on the denylist
    assert set(walker.primitive_names(jx)) & HOST_TRANSFER_PRIMITIVES
    findings = NoHostTransferInStepLoop().check(_target(jaxpr=jx))
    assert findings and "host" in findings[0].message
    clean = jax.make_jaxpr(lambda x: x * 2)(jnp.zeros((3,)))
    assert NoHostTransferInStepLoop().check(_target(jaxpr=clean)) == []


def test_obs_hooks_rule_fires_on_instrumentation_staged_into_program():
    """A program that consults the active observer and stages a
    debug_print when obs is on: the count DIFF between the plain and
    instrumented traces is what must fire, not mere presence."""
    from repro.obs import Observer, activated, get_active

    def f(x):
        if get_active().enabled:  # the forbidden temptation
            jax.debug.print("tok {}", x[0])
        return x * 2

    plain = jax.make_jaxpr(f)(jnp.zeros((3,)))
    with activated(Observer(trace_capacity=16)):
        # fresh lambda: defeat jax's (fn identity, avals) trace cache,
        # exactly as the sweep's _instrumented_jaxpr must
        instr = jax.make_jaxpr(lambda x: f(x))(jnp.zeros((3,)))
    t = _target(jaxpr=plain, instrumented_jaxpr=instr)
    findings = NoHostTransferInObsHooks().check(t)
    assert findings and findings[0].rule == "NoHostTransferInObsHooks"
    assert findings[0].detail["new"], findings[0].detail
    assert "host-side" in findings[0].message


def test_obs_hooks_rule_quiet_on_identical_and_preexisting_transfers():
    # identical traces: clean
    clean = jax.make_jaxpr(lambda x: x * 2)(jnp.zeros((3,)))
    assert NoHostTransferInObsHooks().check(
        _target(jaxpr=clean, instrumented_jaxpr=clean)) == []

    # a host transfer present in BOTH traces is NoHostTransferInStepLoop's
    # business — the count diff is zero, so this rule stays quiet
    def leaky(x):
        jax.debug.print("tok {}", x[0])
        return x * 2

    jx = jax.make_jaxpr(leaky)(jnp.zeros((3,)))
    assert NoHostTransferInObsHooks().check(
        _target(jaxpr=jx, instrumented_jaxpr=jx)) == []

    # no instrumented trace recorded -> rule does not apply
    assert not NoHostTransferInObsHooks().applies(_target(jaxpr=clean))


def test_run_rules_scopes_by_applies():
    jx = jax.make_jaxpr(lambda x: x + 1)(jnp.zeros((2,)))
    ran, findings = run_rules(_target(phase="prefill", jaxpr=jx))
    assert "NoHostTransferInStepLoop" not in ran  # decode-only rule
    assert findings == []


# ---------------------------------------------------------------------------
# the registry sweep
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sweep_report():
    return sweep()


def test_sweep_covers_every_registered_backend(sweep_report):
    rep = sweep_report
    assert rep.n_decode_targets == len(backends.registered_backends())
    assert rep.n_prefill_targets == len(backends.registered_prefill_backends())
    assert rep.ok, [str(f) for f in rep.findings]
    by_key = {t.key: t for t in rep.targets}
    assert len(by_key) == len(rep.targets)  # no duplicate targets
    for t in rep.targets:
        if t.style == "merged":
            assert "NoForbiddenMatmul" in t.rules_run, t.key
        if t.phase == "prefill" and t.cache_kind in ("paged", "paged_q8"):
            assert "NoOversizedBuffer" in t.rules_run, t.key
        if t.cache_kind == "paged_q8":
            assert "NoDequantizedPoolBuffer" in t.rules_run, t.key
        if t.phase == "decode":
            assert "NoHostTransferInStepLoop" in t.rules_run, t.key
        assert "NoDtypePromotionDrift" in t.rules_run, t.key
        # every builder re-traces under an active observer, so the obs
        # host-transfer diff must have run everywhere
        assert "NoHostTransferInObsHooks" in t.rules_run, t.key
        if t.impl in ("xla", "pallas_interpret") and (
                t.phase == "decode" or t.cache_kind == "paged"):
            # production donates the cache/pools; the sweep must prove
            # the donation survives lowering wherever lowering works
            assert "DonationEffective" in t.rules_run, t.key


def test_sweep_flags_unregisterable_combo_loudly():
    """A registered backend the sweep has no builder/model for must be a
    loud SweepCoverage ERROR, never a silently-unlinted combo."""
    step = backends.get_backend("dense", "generic", "xla").step
    backends.register_backend("quantized9", "generic", step, impls=("xla",))
    try:
        rep = sweep()
        assert not rep.ok
        cov = [f for f in rep.findings if f.rule == "SweepCoverage"]
        assert cov and "quantized9" in cov[0].target
        # still covers the whole (now larger) registry
        assert rep.n_decode_targets == len(backends.registered_backends())
    finally:
        from repro.models.backends import _REGISTRY
        _REGISTRY.pop(("quantized9", "generic", "xla"), None)


# ---------------------------------------------------------------------------
# host-aliasing detector
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_model():
    cfg = reduce_config(get_config("llama3.2-1b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(small_model, kind):
    cfg, params = small_model
    return Engine(cfg, params, ServeConfig(n_slots=2, max_len=48),
                  cache=kind)


def test_hostbufs_are_aligned_and_zero_copy_certain():
    buf = aligned_zeros((7, 3), np.int32)
    assert buf.ctypes.data % ALIGN == 0
    assert buf.flags.c_contiguous and buf.flags.writeable
    # the whole point: ingestion of an aligned buffer is zero-copy, so a
    # missing .copy() always aliases (never "only on lucky mallocs")
    assert np.shares_memory(np.asarray(jnp.asarray(buf)), buf)


@pytest.mark.parametrize("kind", ["dense", "paged", "paged_q8"])
def test_audit_clean_on_real_engines(small_model, kind):
    findings = aliasing.audit_engine(_engine(small_model, kind))
    assert findings == [], [str(f) for f in findings]


def test_audit_flags_noncopying_device_cache(small_model):
    """Reintroduce the PR 5 bug (block table handed to the device without
    a copy) — the jit-boundary spy must flag it."""
    eng = _engine(small_model, "paged")

    def bad(self):
        return PagedDecodeCache(k=self.k, v=self.v,
                                block_tables=jnp.asarray(self.tables),
                                length=jnp.asarray(self.lengths))

    eng.kv.pm.device_cache = types.MethodType(bad, eng.kv.pm)
    findings = aliasing.audit_engine(eng)
    assert any(f.rule == aliasing.RULE_JIT_INPUT for f in findings)
    assert any("pm.tables" in f.message for f in findings)


def test_audit_flags_zero_copy_ingestion(small_model):
    """Drop the copy at the host->device seam (the submit/step ingestion
    fix) — both the seam check and the jit-boundary spy must fire."""
    eng = _engine(small_model, "dense")
    eng.host_to_device = lambda x, dtype=None: jnp.asarray(
        np.asarray(x, dtype))
    rules = {f.rule for f in aliasing.audit_engine(eng)}
    assert aliasing.RULE_INGEST in rules
    assert aliasing.RULE_JIT_INPUT in rules  # the prompt reached the jit


def test_check_host_views_flags_device_backed_numpy():
    view = np.asarray(jnp.zeros((2,), jnp.uint32))  # read-only, pins buffer
    findings = aliasing.check_host_views({"request[0].key_state": view}, "t")
    assert findings and findings[0].rule == aliasing.RULE_HOST_VIEW
    owned = np.array(jnp.zeros((2,), jnp.uint32))
    assert aliasing.check_host_views({"k": owned}, "t") == []


def test_preempted_key_state_owns_its_memory(small_model):
    """Regression for the engine._preempt fix: the resume key handed back
    to a request must be an OWNED copy, not a read-only device view."""
    from repro.serving import Request
    eng = _engine(small_model, "paged")
    p = aligned_empty((8,), np.int32)
    p[:] = np.arange(8) % eng.cfg.vocab_size
    assert eng.submit(Request(prompt=p, max_new_tokens=4))
    slot = next(iter(eng.active))
    eng._preempt(slot)
    req = eng.preempted[0]
    assert req.key_state is not None
    assert req.key_state.base is None and req.key_state.flags.writeable
    # and the preempted request still resumes to completion
    assert eng.submit(req)
    while eng.active:
        eng.step()
    assert len(req.out_tokens) >= 4


def test_engine_declares_its_host_mutable_buffers(small_model):
    named = _engine(small_model, "paged").host_mutable_buffers()
    assert {"engine._last_token", "pm.tables", "pm.lengths",
            "pm.allocator.ref"} <= set(named)
    for buf in named.values():
        assert isinstance(buf, np.ndarray)
    assert _engine(small_model, "dense").host_mutable_buffers().keys() == \
        {"engine._last_token"}


def test_findings_serialize():
    f = Finding(rule="R", target="t", message="m", detail={"n": 1})
    d = f.to_dict()
    assert d["rule"] == "R" and d["detail"] == {"n": 1}
    assert "error" in str(f)


# ---------------------------------------------------------------------------
# retention audit (NoWriteIntoHeldPage)
# ---------------------------------------------------------------------------

def test_retention_audit_clean_on_real_managers():
    """The full audit — fp absolute + ring + q8 managers AND the
    sabotaged positive control — must come back empty."""
    from repro.lint import retention
    findings = retention.audit_retention()
    assert findings == [], [str(f) for f in findings]


def test_retention_audit_flags_write_into_shared_page(small_model):
    """Strip detach-on-shared from ensure_appendable (the PR 5-era bug
    class, now also covering tree-retained pages) — the append seam must
    fire with the refcount evidence."""
    from repro.lint import retention
    from repro.serving.paged_kv_cache import PagedCacheManager
    cfg, _ = small_model
    pm = PagedCacheManager(cfg, n_slots=4, max_len=64, block_size=8,
                           n_blocks=24)

    def bad(self, slot):
        info = self._slots[slot]
        li = int(self.lengths[slot]) // self.bs
        if self.ring or li >= len(info.blocks):
            return PagedCacheManager.ensure_appendable(self, slot)
        return True  # append in place even when the page is held

    pm.ensure_appendable = types.MethodType(bad, pm)
    findings = retention.audit_manager(pm, "sabotaged")
    assert findings, "stripped detach-on-shared must fire the audit"
    assert all(f.rule == retention.RULE_RETENTION for f in findings)
    assert any(f.detail and f.detail.get("seam") == "ensure_appendable"
               and f.detail.get("ref", 0) > 1 for f in findings)


def test_retention_audit_flags_eviction_of_live_page(small_model):
    """Evict with the refcount guard stripped while a live slot is
    re-sharing the retained chain — the eviction seam must flag every
    victim a request still reads."""
    from repro.lint import retention
    from repro.serving.paged_kv_cache import PagedCacheManager
    cfg, _ = small_model
    pm = PagedCacheManager(cfg, n_slots=4, max_len=64, block_size=8,
                           n_blocks=10)
    findings = []
    with retention._armed(pm, findings, "sabotaged-evict"):
        prompt = (np.arange(27, dtype=np.int32) * 3 + 1) % cfg.vocab_size
        assert pm.admit(0, prompt) == 0
        pm.release(0)                      # chain retained by the tree
        assert pm.admit(1, prompt.copy()) == 4  # warm hit: retained+live
        # the sabotage: evict regardless of refcount (the manager's real
        # call sites always pass the ref==1 guard)
        assert pm.tree.evict(4, lambda p: True)
    assert any(f.detail and f.detail.get("seam") == "tree.evict"
               and f.detail.get("ref", 0) != 1 for f in findings), findings

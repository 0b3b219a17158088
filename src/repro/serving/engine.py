"""Serving engine: prefill + batched decode with continuous batching.

Design (vLLM-style, TPU/JAX-native): the engine schedules requests over a
fixed number of serving SLOTS and drives exactly TWO seams —

  * a ``KVCacheAdapter`` (``serving.adapters``) owning the cache: its
    device state, shapes/partition specs, admission control and the
    prefill-insert path.  Two adapters ship: "dense" (every slot owns a
    worst-case ``max_len`` stretch of one batched ``DecodeCache``) and
    "paged" (slots map fixed-size pages from a shared block pool with
    free-list allocation, prefix sharing + copy-on-write, deferral and
    youngest-preemption-with-exact-resume — at equal HBM the pool
    sustains strictly more concurrent streams on mixed-length traffic,
    which is what amortizes the merged fast path's K*/V*-only weight
    reads).  Paged prefill writes prompt KV DIRECT-TO-PAGE from inside
    the prefill program (``forward_prefill(dest=PagedPrefillDest(…))``,
    pools donated): no worst-case-length intermediate cache, no
    post-prefill scatter.
  * the ``models.backends`` registries, keyed (cache_kind, style, impl)
    for BOTH serving phases: the jitted ``serve_step`` is ONE function,
    ``models.forward_step``, which looks up its per-layer attention route
    in the ``AttentionBackend`` registry, and the adapter's prefill
    program is ONE dispatcher, ``models.forward_prefill``, which looks up
    its whole-sequence route in the ``PrefillBackend`` registry.  Merged
    (Q/P-removed) "qp" models take the fast path in both phases — the
    stream is the query, attention reads only the K*/V* weights, the
    output lands in the FFN-input basis (``merged_fast_path`` /
    ``merged_prefill_fast_path``); kp/vp merged variants route through
    the generic backends (their eliminated projection is an identity
    inside ``_project_qkv``) token-identically to their unmerged source
    model.  Unknown combos fail at Engine construction with the
    registry's KeyError, not mid-serve.

Scheduling facts (unchanged by the redesign): prompt lengths are BUCKETED
(padded to the next power of two, exact logits/cache via ``true_len``) so
a realistic traffic mix compiles O(log max_len) prefill programs; sampling
is greedy / temperature / top-k with PER-SLOT PRNG streams (each request's
key derives from (engine seed, submission index) and advances only with
its own samples, so sampled continuations are traffic-independent and
preemption-exact).  Under a mesh the engine shards params/caches with the
distribution-layer rules (the adapter supplies its cache's specs) and
re-anchors TP head sharding on q/k/v for merged layouts (no wq matmul to
propagate it from).

``generate`` returns per-request :class:`RequestResult`s — a list of
token ids that also carries (prompt_len, new_tokens, ttft_s,
decode_tok_s), so time-to-first-token wins (e.g. paged direct-to-page
prefill) are readable without the benchmark harness.

Backward compatibility: ``ServeConfig(cache_kind=…)`` still works as a
deprecated alias for ``Engine(…, cache=…)``.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distribution import sharding as shd
from repro.models import (backends, forward_step, prefill_style_key,
                          serving_style_key)
from repro.obs import NULL, MetricsRegistry, Observer
from repro.serving import hostbufs
from repro.serving.adapters import KVCacheAdapter, make_adapter


@dataclasses.dataclass
class ServeConfig:
    n_slots: int = 8
    max_len: int = 512
    temperature: float = 0.0  # 0 => greedy
    top_k: int = 0
    eos_token: int = -1  # -1 => run to max_new_tokens
    seed: int = 0
    cache_kind: Optional[str] = None  # DEPRECATED: use Engine(cache=…)
    block_size: int = 16  # paged: tokens per physical page
    n_blocks: int = 0  # paged pool size; 0 => dense-equivalent HBM
    bucket_prompts: bool = True  # pad prompts to power-of-two buckets
    # observability (repro.obs).  False (default) => the engine's observer
    # is the shared NullObserver: every hook a no-op, clock() == 0.0 — the
    # zero-overhead-off guarantee.  True => a fresh Observer (metrics +
    # trace ring); an Observer instance is adopted as-is (its registry
    # becomes Engine.metrics).
    obs: Any = False


# eq=False: requests are identities, not values.  The generated __eq__
# would compare the prompt ARRAYS, and ``inflight.remove(r)`` /
# ``preempted.remove(r)`` then raise on any ragged out-of-order finish
# (numpy refuses to broadcast (40,) against (24,)).
@dataclasses.dataclass(eq=False)
class Request:
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 32
    out_tokens: Optional[List[int]] = None
    slot: int = -1  # >=0 active; -1 idle/finished; -2 preempted
    remaining: int = 0
    rid: int = -1  # submission index (per-request PRNG stream id)
    key_state: Optional[np.ndarray] = None  # advanced PRNG key (preemption)
    # serving telemetry (host wall-clock, seconds)
    t_arrival: Optional[float] = None  # entered the engine's queue
    t_first: Optional[float] = None  # first token emitted (prefill sample)
    t_last: Optional[float] = None  # most recent token emitted


class RequestResult(list):
    """A finished request's generated token ids — it IS the token list
    (equality/len/slicing behave like before) — plus per-request stats:

      prompt_len    tokens in the submitted prompt
      new_tokens    tokens generated (== len(self))
      ttft_s        arrival -> first token, queueing + prefill included
      decode_tok_s  steady-state decode rate after the first token —
                    None for single-token requests (there IS no steady
                    state to measure; a 0.0 here would pollute means)
    """

    def __init__(self, tokens, *, prompt_len: int, ttft_s: float,
                 decode_tok_s: Optional[float]):
        super().__init__(tokens)
        self.prompt_len = prompt_len
        self.new_tokens = len(tokens)
        self.ttft_s = ttft_s
        self.decode_tok_s = decode_tok_s

    @property
    def stats(self) -> Dict[str, Any]:
        return {"prompt_len": self.prompt_len, "new_tokens": self.new_tokens,
                "ttft_s": self.ttft_s, "decode_tok_s": self.decode_tok_s}


def _timings_of(req: Request) -> Tuple[float, Optional[float]]:
    """(ttft_s, decode_tok_s) from a request's host timestamps.

    decode_tok_s is None — NOT 0.0 — when there is no decode phase to
    rate (single-token requests, missing timestamps): the histogram
    excludes it (``n_excluded``) instead of averaging in a zero."""
    ttft = (req.t_first - req.t_arrival
            if req.t_first is not None and req.t_arrival is not None else 0.0)
    n = len(req.out_tokens)
    tok_s = None
    if n > 1 and req.t_last is not None and req.t_first is not None \
            and req.t_last > req.t_first:
        tok_s = (n - 1) / (req.t_last - req.t_first)
    return ttft, tok_s


def _result_of(req: Request) -> RequestResult:
    ttft, tok_s = _timings_of(req)
    return RequestResult(req.out_tokens, prompt_len=len(req.prompt),
                         ttft_s=ttft, decode_tok_s=tok_s)


class Engine:
    def __init__(self, cfg: ModelConfig, params, sc: ServeConfig, mesh=None,
                 impl: str = "xla",
                 cache: Union[None, str, KVCacheAdapter] = None):
        assert cfg.causal, "serving requires a decoder"
        cfg.validate_style()  # merged styles need a square Q basis
        self.cfg, self.sc, self.mesh = cfg, sc, mesh
        self.params = params
        self.impl = impl

        if sc.cache_kind is not None:
            warnings.warn(
                "ServeConfig.cache_kind is deprecated; pass "
                "Engine(..., cache='dense'|'paged') or a KVCacheAdapter "
                "instance", DeprecationWarning, stacklevel=2)
            if cache is None:
                cache = sc.cache_kind
        if cache is None:
            cache = "dense"
        self.kv: KVCacheAdapter = (make_adapter(cache, sc)
                                   if isinstance(cache, str) else cache)
        # resolve BOTH phases' backends NOW: an unknown (cache_kind,
        # style, impl) combo must fail at construction, not mid-serve
        self.backend = backends.get_backend(self.kv.kind,
                                            serving_style_key(cfg), impl)
        self.prefill_backend = backends.get_prefill_backend(
            self.kv.kind, prefill_style_key(cfg), impl)

        self.free_slots = list(range(sc.n_slots))
        self.active: Dict[int, Request] = {}
        self.preempted: List[Request] = []
        self.key = jax.random.PRNGKey(sc.seed)
        self._slot_keys = jnp.zeros((sc.n_slots, 2), jnp.uint32)
        self._rid = 0
        # observability: the engine ALWAYS owns a MetricsRegistry (the
        # always-on scheduler counters below cost one attribute update,
        # same as the dict they replaced — Engine.stats reads through
        # them).  Heavier telemetry (timestamps, histograms, spans) is
        # the Observer's, off by default (NULL: every hook a no-op).
        if isinstance(sc.obs, Observer):
            self.obs = sc.obs
            self.metrics = sc.obs.metrics
        elif sc.obs:
            self.obs = Observer()
            self.metrics = self.obs.metrics
        else:
            self.obs = NULL
            self.metrics = MetricsRegistry()
        self._g_peak = self.metrics.gauge(
            "serve_peak_active", "most slots concurrently decoding")
        self._c_preempted = self.metrics.counter(
            "serve_preempted", "requests evicted mid-decode")
        self._c_deferred = self.metrics.counter(
            "serve_deferred", "admissions deferred (pool exhausted)")
        # bucketing needs positions to be paddable: causal attention masks
        # padded tails, but SSM prefill state is not position-masked, and a
        # dense sliding-window cache is a window-sized ring that would drop
        # real positions when the padded tail pushes them out (the paged
        # cache stores absolute positions, so it buckets window configs too)
        self._bucketing = (sc.bucket_prompts and cfg.has_attention
                           and not cfg.ssm_state
                           and (self.paged or not cfg.sliding_window))

        self.kv.init(cfg, sc)
        self._build_steps()

        # aligned: deterministically on jax's zero-copy path, so a missing
        # copy at ingestion fails every run (serving.hostbufs rationale)
        self._last_token = hostbufs.aligned_zeros((sc.n_slots,), np.int32)
        if sc.temperature > 0:
            self._sample_rows = jax.jit(partial(
                _sample_rows, temperature=sc.temperature, top_k=sc.top_k,
                vocab_size=cfg.vocab_size))
        # lifts the adapter/pool telemetry in as LAZY gauges (no-op off)
        self.obs.attach_engine(self)

    @property
    def stats(self) -> Dict[str, int]:
        """Read-through view of the always-on scheduler counters (the
        pre-obs ``Engine.stats`` dict, now backed by ``self.metrics``)."""
        return {"peak_active": int(self._g_peak.high_water),
                "n_preempted": int(self._c_preempted.value),
                "n_deferred": int(self._c_deferred.value)}

    # ------------------------------------------------------------------
    def _build_steps(self):
        """Wire the jitted serve_step + the adapter's prefill: both are
        registry/adapter lookups — no per-cache-kind engine code."""
        impl, mesh = self.impl, self.mesh
        psh = csh = qkv_sh = None
        if mesh is not None:
            rules = shd.make_rules(mesh, batch=self.sc.n_slots)
            pshape = jax.eval_shape(lambda: self.params)
            psh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                               shd.evenly(shd.param_pspecs(pshape, rules),
                                          pshape, mesh))
            self.params = jax.device_put(self.params, psh)
            if self.merged_fast_path:
                # K*/V*-only layout: re-anchor TP head sharding explicitly
                qkv_sh = NamedSharding(
                    mesh, P(rules.dp, None, rules.axis("heads"), None))
            cshape = self.kv.spec()
            csh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                               shd.evenly(self.kv.pspecs(rules), cshape,
                                          mesh))
            # place the cache now: left to the first jitted call, the
            # whole pool would sit on one device until a request arrives
            self.kv.update(jax.device_put(self.kv.device_cache(), csh))

        def fwd(p, t, c):
            return forward_step(p, self.cfg, t, c, impl=impl,
                                qkv_sharding=qkv_sh)

        if mesh is not None:
            self._decode = jax.jit(
                fwd, donate_argnums=(2,),
                in_shardings=(psh, NamedSharding(mesh, P()), csh),
                out_shardings=(None, csh))
        else:
            self._decode = jax.jit(fwd, donate_argnums=(2,))
        self.kv.build_prefill(impl, mesh=mesh, params_sharding=psh,
                              cache_shardings=csh, qkv_sharding=qkv_sh)
        # stashed for additional adapter programs (e.g. the scheduler's
        # chunk program) built after construction
        self._shardings = (psh, csh, qkv_sh)
        # introspection alias (tests count compiled prefill buckets here)
        self._prefill = self.kv._prefill

    # ------------------------------------------------------------------
    @staticmethod
    def host_to_device(x, dtype=None) -> jnp.ndarray:
        """The ONE host→device ingestion seam: always copies.

        ``jnp.asarray`` of an aligned dtype-matching numpy array is
        ZERO-copy on CPU, and dispatch is async — ingesting a
        caller-owned buffer (a prompt) or engine-mutated state without a
        copy lets an in-flight program read memory the owner has since
        rewritten.  ``repro.lint.aliasing`` audits this seam; keep every
        numpy→device conversion of externally-owned data routed here."""
        return jnp.asarray(np.array(x, dtype=dtype, copy=True))

    def host_mutable_buffers(self) -> Dict[str, np.ndarray]:
        """Named host-side numpy buffers this engine mutates across steps
        — the ``repro.lint.aliasing`` detector checks every jitted call's
        inputs for shared memory with these."""
        named = {"engine._last_token": self._last_token}
        named.update(self.kv.host_mutable_buffers())
        return named

    @property
    def paged(self) -> bool:
        """True for every block-pool cache kind (fp "paged" AND the int8
        "paged_q8") — scheduling semantics (absolute positions, admission
        control, preemption) are the pool's, not the quantization's."""
        return self.kv.kind != "dense"

    @property
    def cache(self):
        """Dense adapters' batched DecodeCache (None for other kinds) —
        kept for callers that inspect the cache directly."""
        return self.kv.device_cache() if self.kv.kind == "dense" else None

    @property
    def pm(self):
        """Paged adapters' host-side PagedCacheManager (telemetry)."""
        return self.kv.pm

    @property
    def merged_fast_path(self) -> bool:
        """True when serve_step routes through the merged (Q/P-removed)
        decode fast path: no Q or P weights exist, so per-token attention
        streams only K*/V* from HBM.  kp/vp merged variants return False —
        they serve through the generic backend (still token-exact)."""
        return self.backend.fast_path

    @property
    def merged_prefill_fast_path(self) -> bool:
        """True when this engine's prefill routes through the merged
        (Q/P-removed) PREFILL fast path: every self-attention layer of the
        prompt forward runs the stream-as-query flash core — no Q or P
        weight reads, no head-major transposes — cutting prefill HBM
        traffic and TTFT.  kp/vp merged variants and non-attention stacks
        return False (generic prefill backend, still token-exact)."""
        return self.prefill_backend.fast_path

    def compiled_decode(self):
        """Lower + compile serve_step for inspection (no execution).

        Used by benchmarks to read ``cost_analysis()`` / HLO of the exact
        program the engine runs — e.g. HBM bytes/token with and without
        the eliminated Q/P weight reads, or the dense-vs-paged cache
        traffic."""
        pshape = jax.eval_shape(lambda: self.params)
        tshape = jax.ShapeDtypeStruct((self.sc.n_slots,), jnp.int32)
        t0 = self.obs.clock()
        compiled = self._decode.lower(pshape, tshape, self.kv.spec()).compile()
        self._compile_event("decode", None, compiled, t0)
        return compiled

    def compiled_prefill(self, bucket_len: int):
        """Lower + compile this engine's prefill program for one prompt
        bucket (no execution) — e.g. to read the prefill HBM bytes that
        direct-to-page paged prefill saves over dense."""
        t0 = self.obs.clock()
        compiled = self.kv.compiled_prefill(self.params, bucket_len)
        self._compile_event("prefill", bucket_len, compiled, t0)
        return compiled

    def _compile_event(self, phase: str, bucket_len: Optional[int],
                       compiled, t0: float) -> None:
        """Emit a compile metric/span — obs-on only (``as_text`` is
        expensive; the off path must never pay for it)."""
        if not self.obs.enabled:
            return
        t1 = self.obs.clock()
        try:
            hlo_bytes = len(compiled.as_text())
        except Exception:
            hlo_bytes = 0  # backends without HLO text introspection
        self.obs.compile_event(phase, bucket_len, hlo_bytes, t1 - t0)

    # ------------------------------------------------------------------
    def _bucket_pad(self, toks: np.ndarray) -> Tuple[np.ndarray, int]:
        """Right-pad to the next power-of-two bucket (>= 8) so the prefill
        jit compiles O(log max_len) programs; true length is passed to
        ``forward_prefill`` so logits and cache are exact."""
        n = len(toks)
        align = self.kv.bucket_align
        if not self._bucketing or n >= self.sc.max_len:
            # even unbucketed prompts must honor the adapter's alignment
            # (paged_q8 pages quantize whole: no prefill may end mid-page)
            b = -(-n // align) * align
            if b == n:
                return toks, n
            return np.concatenate([toks, np.zeros((b - n,), np.int32)]), n
        b = 8
        while b < n:
            b *= 2
        b = min(b, self.sc.max_len)
        b = -(-b // align) * align
        if b == n:
            return toks, n
        return np.concatenate([toks, np.zeros((b - n,), np.int32)]), n

    def submit(self, req: Request, vision: Optional[np.ndarray] = None) -> bool:
        """Prefill a request into a free slot.  Returns False when no slot
        is free or the adapter can't admit the prompt (paged: pool
        exhausted) — the caller retries after other requests finish
        (admission control).

        A request with ``out_tokens`` already populated is a RESUME
        (preempted earlier): its generated tokens re-prefill with the
        prompt and decoding continues where it left off.
        """
        if req.t_arrival is None:
            req.t_arrival = time.perf_counter()
        if not self.free_slots:
            return False
        # fail FAST on a request that cannot finish: decode would run past
        # max_len mid-serve (paged: off the block table; dense non-window:
        # silently wrapping the cache over live positions).  Dense sliding-
        # window rings legitimately outlive max_len — the window masks.
        if self.paged or not self.cfg.sliding_window:
            if len(req.prompt) + req.max_new_tokens > self.sc.max_len:
                raise ValueError(
                    f"prompt ({len(req.prompt)}) + max_new_tokens "
                    f"({req.max_new_tokens}) exceeds max_len "
                    f"({self.sc.max_len})")
        resume = bool(req.out_tokens)
        toks = np.asarray(req.prompt, np.int32)
        if resume and len(req.out_tokens) > 1:
            toks = np.concatenate(
                [toks, np.asarray(req.out_tokens[:-1], np.int32)])
        slot = self.free_slots[0]
        n_shared = self.kv.admit(slot, toks)
        if n_shared is None:
            self._c_deferred.inc()
            return False
        self.free_slots.pop(0)
        t_p0 = self.obs.clock()  # slot granted: queued span ends here

        padded, n = self._bucket_pad(toks)
        # host_to_device (copy), NOT jnp.asarray: for a bucket-exact int32
        # prompt, `padded` IS the caller's buffer, and the async prefill
        # would read it after submit() returns — a caller reusing its
        # prompt array corrupts an in-flight program (the PR 5 race, at
        # the engine's public boundary)
        vs = None if vision is None else self.host_to_device(vision)[None]
        logits = self.kv.prefill(self.params, slot,
                                 self.host_to_device(padded, np.int32)[None],
                                 n, n_shared, vs)

        if req.rid < 0:
            req.rid = self._rid
            self._rid += 1
        # per-request PRNG stream: key = f(engine seed, submission index);
        # a preempted request resumes from its ADVANCED key, not the start
        # of its stream — replayed draws would make the continuation depend
        # on whether preemption happened
        self._slot_keys = self._slot_keys.at[slot].set(
            jnp.asarray(req.key_state) if req.key_state is not None
            else jax.random.fold_in(self.key, req.rid))
        req.slot = slot
        if resume:
            tok = req.out_tokens[-1]
        else:
            tok = int(self._sample(logits, [slot])[0])
            req.out_tokens = [tok]
            req.remaining = req.max_new_tokens - 1
            now = time.perf_counter()
            req.t_first = req.t_last = now
        self.active[slot] = req
        self._last_token[slot] = int(tok)
        self._g_peak.set_max(len(self.active))
        self.obs.request_admitted(req, slot, n_shared=n_shared,
                                  resume=resume, bucket_len=len(padded),
                                  t_prefill0=t_p0)
        if not resume and (req.remaining <= 0 or tok == self.sc.eos_token):
            # the prefill-sampled token already satisfied the budget (or
            # is EOS): finish now — a decode step would overshoot
            # max_new_tokens by one
            self.kv.release(slot)
            req.slot = -1
            del self.active[slot]
            self.free_slots.append(slot)
            if self.obs.enabled:  # terminal hook: exactly once
                ttft, tok_s = _timings_of(req)
                self.obs.request_finished(req, decode_tok_s=tok_s,
                                          ttft_s=ttft)
        return True

    def step(self) -> Dict[int, int]:
        """One batched decode step for all active slots; returns slot->token."""
        if not self.active:
            return {}
        t0 = self.obs.clock()  # step span includes appendability/preempts
        self._make_appendable()
        if not self.active:
            return {}
        # host_to_device copies: jax CPU zero-copies numpy buffers, and
        # _last_token is mutated in place right after this step dispatches
        tokens = self.host_to_device(self._last_token, np.int32)
        logits, new_cache = self._decode(self.params, tokens,
                                         self.kv.device_cache())
        self.kv.update(new_cache)
        next_tokens = np.asarray(self._sample(
            logits, np.arange(self.sc.n_slots)))
        now = time.perf_counter()
        emitted: Dict[int, int] = {}
        for slot, req in list(self.active.items()):
            tok = int(next_tokens[slot])
            req.out_tokens.append(tok)
            req.remaining -= 1
            req.t_last = now
            self._last_token[slot] = tok
            emitted[slot] = tok
            self.kv.advance(slot)
            done = req.remaining <= 0 or tok == self.sc.eos_token
            if done:
                self.kv.release(slot)
                req.slot = -1
                del self.active[slot]
                self.free_slots.append(slot)
                if self.obs.enabled:  # terminal hook: exactly once
                    ttft, tok_s = _timings_of(req)
                    self.obs.request_finished(req, decode_tok_s=tok_s,
                                              ttft_s=ttft)
        self.obs.step_done(t0, self.obs.clock(), n_active=len(self.active),
                           n_tokens=len(emitted))
        return emitted

    def _make_appendable(self):
        """Guarantee every active slot can write its next token (paged:
        map/CoW the target page), preempting the youngest request(s) when
        the adapter is out of resources.  Dense adapters always succeed."""
        while True:
            blocked = [s for s in sorted(self.active)
                       if not self.kv.ensure_appendable(s)]
            if not blocked:
                return
            if len(self.active) == 1:
                raise RuntimeError(
                    "paged pool too small for a single request; raise "
                    "ServeConfig.n_blocks")
            victim = max(self.active, key=lambda s: self.active[s].rid)
            self._preempt(victim)

    def _preempt(self, slot: int):
        req = self.active.pop(slot)
        self.kv.release(slot)
        self.free_slots.append(slot)
        req.slot = -2
        # np.array (copy), NOT np.asarray: asarray of a device array is a
        # READ-ONLY view that pins the device buffer into host state — the
        # request must own its resume key (lint: NoHostViewOfDeviceBuffer)
        req.key_state = np.array(self._slot_keys[slot])  # resume in place
        self.preempted.append(req)
        self._c_preempted.inc()
        self.obs.request_preempted(req, slot)

    def generate(self, prompts: Sequence[np.ndarray], max_new_tokens: int = 32,
                 vision: Optional[Sequence[np.ndarray]] = None
                 ) -> List[RequestResult]:
        """Continuous batching driver: keeps slots full until all done.

        Returns one :class:`RequestResult` per prompt — the generated
        token ids (list semantics preserved) plus prompt_len / new_tokens
        / ttft_s / decode_tok_s."""
        t_gen0 = self.obs.clock()
        t_arrival = time.perf_counter()
        pending = [Request(prompt=np.asarray(p, np.int32),
                           max_new_tokens=max_new_tokens,
                           t_arrival=t_arrival) for p in prompts]
        results: List[Optional[RequestResult]] = [None] * len(pending)
        order = {id(r): i for i, r in enumerate(pending)}
        queue = list(pending)
        inflight: List[Request] = []
        vis = list(vision) if vision is not None else [None] * len(pending)
        vqueue = list(vis)
        while queue or self.active or self.preempted:
            self.obs.queue_depth(len(queue) + len(self.preempted))
            while self.free_slots:
                if self.preempted:  # resumes have progress: highest priority
                    if not self.submit(self.preempted[0]):
                        break
                    self.preempted.pop(0)
                elif queue:
                    if not self.submit(queue[0], vision=vqueue[0]):
                        break
                    inflight.append(queue.pop(0))
                    vqueue.pop(0)
                else:
                    break
            if not self.active:
                if queue or self.preempted:
                    raise RuntimeError(
                        "serving stalled: pool cannot admit any pending "
                        "request (raise n_blocks or max_len)")
                break
            self.step()
            for r in list(inflight):
                if r.slot == -1:  # finished (not preempted, not active)
                    results[order[id(r)]] = _result_of(r)
                    inflight.remove(r)
        for r in inflight:  # finished at submit time on the final pass
            if r.slot == -1:
                results[order[id(r)]] = _result_of(r)
        if self.obs.enabled:
            self.obs.generate_done(
                t_gen0, self.obs.clock(), n_requests=len(pending),
                n_tokens=sum(r.new_tokens for r in results if r is not None))
        return results  # type: ignore

    # ------------------------------------------------------------------
    def _sample(self, logits: jnp.ndarray, slots) -> jnp.ndarray:
        """Sample one token per row of ``logits``; ``slots`` names the slot
        each row belongs to so temperature sampling draws from that slot's
        private PRNG stream."""
        if self.sc.temperature <= 0.0:
            if logits.shape[-1] > self.cfg.vocab_size:  # mask padded ids
                pad_mask = jnp.arange(logits.shape[-1]) < self.cfg.vocab_size
                logits = jnp.where(pad_mask, logits, -1e30)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sl = jnp.asarray(np.asarray(slots, np.int32))
        toks, new_keys = self._sample_rows(logits, self._slot_keys[sl])
        self._slot_keys = self._slot_keys.at[sl].set(new_keys)
        return toks


def _sample_rows(logits: jnp.ndarray, keys: jnp.ndarray, *,
                 temperature: float, top_k: int, vocab_size: int):
    """Temperature/top-k sampling, one private PRNG key per row.

    Returns (tokens, advanced keys) — each row's key advances only when
    that row samples, so a request's continuation is a pure function of
    (params, prompt, engine seed, submission index)."""
    if logits.shape[-1] > vocab_size:  # mask padded vocab ids
        pad_mask = jnp.arange(logits.shape[-1]) < vocab_size
        logits = jnp.where(pad_mask, logits, -1e30)
    scaled = logits / temperature
    if top_k > 0:
        vals, _ = jax.lax.top_k(scaled, top_k)
        scaled = jnp.where(scaled < vals[..., -1:], -1e30, scaled)
    split = jax.vmap(jax.random.split)(keys)  # (R, 2, 2)
    toks = jax.vmap(jax.random.categorical)(split[:, 1], scaled)
    return toks.astype(jnp.int32), split[:, 0]

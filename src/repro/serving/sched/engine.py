"""ScheduledEngine: continuous batching with chunked prefill.

The base ``Engine.submit`` runs a whole-prompt, batch-of-1 prefill
SYNCHRONOUSLY at admission: every arrival freezes all in-flight decode
streams for a full (bucket-compiled) prefill.  ``ScheduledEngine`` splits
the two halves of submit apart:

  * ``submit`` only ENQUEUES — validation, rid assignment (arrival
    order), and a :class:`~repro.serving.sched.plan.PrefillJob` on the
    waiting queue.  No device work; no prefill program ever traces on
    the submit path (lint: ``NoSyncPrefillInSubmit``).
  * ``step`` runs one planned ITERATION: admit waiting jobs into free
    slots (FCFS; resumes first), ask the planner for this iteration's
    decode/chunk mix under the token budget, execute the chunks, run the
    base batched decode step, then activate newly-completed prefills.

Chunks execute against the SHARED batched cache while other slots keep
decoding; mid-prefill slots are protected per cache kind (dense: host
lengths park the decode write at the chunk frontier; paged: the slot's
table row ships masked to -1 so decode writes drop — see
``serving.adapters``).  Activation happens AFTER the iteration's decode
dispatched: an unshielded slot sharing its trailing partial page with a
live request must not take decode writes until ``_make_appendable`` has
had a chance to copy-on-write that page (next iteration, once active).

Adapters that cannot chunk (dense with a BINDING sliding window — the
ring cache holds no partial prompt) fall back to monolithic whole-prompt
jobs: admission is still asynchronous and budget-charged, the prefill is
just unsplittable.

Token identity: chunked prefill writes bit-identical KV to whole-prompt
prefill (``tests/test_sched.py`` pins the full backend grid), per-request
PRNG streams key off the same (seed, rid) fold, and rids are assigned in
arrival order — so greedy AND sampled continuations match the synchronous
engine exactly.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import layer_plan
from repro.obs import span
from repro.serving.adapters import KVCacheAdapter
from repro.serving.engine import (Engine, Request, RequestResult, ServeConfig,
                                  _result_of, _timings_of)
from repro.serving.sched.plan import (ChunkPlan, PrefillJob, SchedConfig,
                                      Schedule, plan_iteration)


class ScheduledEngine(Engine):
    """Engine with queue admission + per-iteration chunk/decode plans."""

    def __init__(self, cfg: ModelConfig, params, sc: ServeConfig,
                 scfg: Optional[SchedConfig] = None, mesh=None,
                 impl: str = "xla",
                 cache: Union[None, str, KVCacheAdapter] = None):
        super().__init__(cfg, params, sc, mesh=mesh, impl=impl, cache=cache)
        self.scfg = scfg if scfg is not None else SchedConfig()
        # chunk programs are attention-only (ssm/hybrid state has no
        # mid-prompt checkpoint; vlm interleaves cross-attention) — other
        # families and binding-window dense fall back to monolithic jobs
        self._chunked = (self.kv.supports_chunked
                         and layer_plan(cfg)["kind"] == "attn")
        if self._chunked:
            if sc.max_len % self.scfg.chunk_tokens:
                raise ValueError(
                    f"max_len ({sc.max_len}) must be a multiple of "
                    f"chunk_tokens ({self.scfg.chunk_tokens}): the final "
                    f"chunk's padded tail may not write past the cache")
            self.kv.enable_chunked()
            psh, csh, qkv_sh = self._shardings
            self.kv.build_chunk(self.scfg.chunk_tokens, self.impl,
                                mesh=self.mesh, params_sharding=psh,
                                cache_shardings=csh, qkv_sharding=qkv_sh)
        self.waiting: List[PrefillJob] = []  # FCFS; resumes at the front
        self.prefilling: List[PrefillJob] = []  # admitted, chunks landing
        self.last_schedule: Optional[Schedule] = None
        self._progress = True
        self.n_iterations = 0  # always-on (obs-off) planner telemetry
        self.n_chunks_run = 0

    @property
    def stats(self) -> Dict[str, int]:
        s = Engine.stats.fget(self)  # type: ignore[attr-defined]
        s["sched_iterations"] = self.n_iterations
        s["sched_chunks"] = self.n_chunks_run
        return s

    # ------------------------------------------------------------------
    def submit(self, req: Request,
               vision: Optional[np.ndarray] = None) -> bool:
        """Enqueue ONLY — no prefill runs here (the whole point).  Always
        returns True: admission control moved into ``step``, where a full
        pool defers the queue head instead of bouncing the caller."""
        if vision is not None:
            raise ValueError(
                "ScheduledEngine is attention-only (no vision prefill); "
                "use the base Engine for vlm serving")
        if req.t_arrival is None:
            req.t_arrival = time.perf_counter()
        if self.paged or not self.cfg.sliding_window:
            if len(req.prompt) + req.max_new_tokens > self.sc.max_len:
                raise ValueError(
                    f"prompt ({len(req.prompt)}) + max_new_tokens "
                    f"({req.max_new_tokens}) exceeds max_len "
                    f"({self.sc.max_len})")
        # rid at ENQUEUE, in arrival order — the same (seed, rid) PRNG
        # fold the synchronous engine would assign at its submit
        if req.rid < 0:
            req.rid = self._rid
            self._rid += 1
        resume = bool(req.out_tokens)
        toks = np.asarray(req.prompt, np.int32)
        if resume and len(req.out_tokens) > 1:
            toks = np.concatenate(
                [toks, np.asarray(req.out_tokens[:-1], np.int32)])
        job = PrefillJob(req=req, toks=toks, resume=resume,
                         monolithic=not self._chunked)
        if resume:  # resumes have progress: highest priority
            self.waiting.insert(0, job)
        else:
            self.waiting.append(job)
        return True

    # ------------------------------------------------------------------
    def _admit(self) -> int:
        """Grant free slots to waiting jobs, strictly FCFS: a deferred
        head (pool exhausted) blocks everything behind it — skipping
        ahead is what starves the head."""
        n = 0
        while self.waiting and self.free_slots:
            job = self.waiting[0]
            slot = self.free_slots[0]
            if job.monolithic:
                n_shared = self.kv.admit(slot, job.toks)
            else:
                n_shared = self.kv.admit_chunked(slot, job.toks)
            if n_shared is None:
                self._c_deferred.inc()
                break
            self.free_slots.pop(0)
            self.waiting.pop(0)
            job.slot, job.n_shared = slot, n_shared
            job.cursor = 0
            job.t_slot = self.obs.clock()
            self.prefilling.append(job)
            n += 1
        return n

    def _self_preempt(self, job: PrefillJob) -> None:
        """A chunk's pages cannot map and no decoder is left to evict:
        give this prefill's resources back and retry from scratch once
        something else releases (stall detection catches the pathological
        single-occupant case)."""
        self.kv.release(job.slot)
        job.slot = -1
        job.cursor = 0
        self.waiting.insert(0, job)
        self.prefilling = [j for j in self.prefilling if j is not job]
        self._c_deferred.inc()

    def _run_chunk(self, cp: ChunkPlan):
        """Execute one planned chunk; returns the (1, V) logits of the
        chunk's last real position, or None if the job self-preempted."""
        job = cp.job
        if job.monolithic:
            padded, n = self._bucket_pad(job.toks)
            logits = self.kv.prefill(
                self.params, job.slot,
                self.host_to_device(padded, np.int32)[None], n,
                job.n_shared, None)
            self.kv.set_length(job.slot, n)
            job.cursor = job.total
            return logits
        while not self.kv.chunk_ready(job.slot, cp.start, cp.end):
            if self.active:
                victim = max(self.active,
                             key=lambda s: self.active[s].rid)
                self._preempt(victim)
            else:
                self._self_preempt(job)
                return None
        C = self.scfg.chunk_tokens
        row = np.zeros((C,), np.int32)
        row[:cp.end - cp.start] = job.toks[cp.start:cp.end]
        logits = self.kv.chunk_step(
            self.params, job.slot,
            self.host_to_device(row, np.int32)[None], cp.start, job.total)
        job.cursor = cp.end
        return logits

    def _finish_prefill(self, job: PrefillJob, logits):
        """All of ``job``'s tokens landed: sample/restore the first token
        and stage the request for activation (or finish it outright)."""
        req, slot = job.req, job.slot
        if not job.monolithic:
            self.kv.finish_chunked(slot, job.toks)
        self._slot_keys = self._slot_keys.at[slot].set(
            jnp.asarray(req.key_state) if req.key_state is not None
            else jax.random.fold_in(self.key, req.rid))
        req.slot = slot
        if job.resume:
            tok = req.out_tokens[-1]
        else:
            tok = int(self._sample(logits, [slot])[0])
            req.out_tokens = [tok]
            req.remaining = req.max_new_tokens - 1
            now = time.perf_counter()
            req.t_first = req.t_last = now
        self._last_token[slot] = int(tok)
        self.prefilling = [j for j in self.prefilling if j is not job]
        C = self.scfg.chunk_tokens
        bucket = job.total if job.monolithic else -(-job.total // C) * C
        self.obs.request_admitted(req, slot, n_shared=job.n_shared,
                                  resume=job.resume, bucket_len=bucket,
                                  t_prefill0=job.t_slot)
        if not job.resume and (req.remaining <= 0
                               or tok == self.sc.eos_token):
            # first token already satisfied the budget (or is EOS)
            self.kv.release(slot)
            req.slot = -1
            self.free_slots.append(slot)
            if self.obs.enabled:
                ttft, tok_s = _timings_of(req)
                self.obs.request_finished(req, decode_tok_s=tok_s,
                                          ttft_s=ttft)
            return None
        if job.monolithic:
            # no shield / host-length machinery in play (monolithic ⇒
            # the engine is not in chunked mode): activate NOW — deferred
            # activation would let this iteration's decode advance the
            # parked slot's device length past the inserted prompt
            self.active[slot] = req
            return None
        return (slot, req)

    def step(self) -> Dict[int, int]:
        """One scheduler ITERATION: admit → plan → chunks → decode →
        activate.  Returns slot -> token for the decode portion."""
        with span("repro.sched.iteration",
                  waiting=len(self.waiting) + len(self.preempted),
                  prefilling=len(self.prefilling)) as iteration:
            with span("repro.sched.admit") as admit:
                n_deferred = self._c_deferred.value
                while self.preempted:  # re-enter the queue at the front
                    self.submit(self.preempted.pop(0))
                t0 = self.obs.clock()
                n_admitted = self._admit()
                admit.set_metadata(
                    admitted=n_admitted,
                    deferred=int(self._c_deferred.value - n_deferred))
            with span("repro.sched.plan"):
                schedule = plan_iteration(self.scfg, len(self.active),
                                          self.prefilling)
            self.last_schedule = schedule
            n_chunks = n_chunk_tokens = 0
            activated = []
            for cp in schedule.chunks:
                tc = self.obs.clock()
                with span("repro.sched.chunk", start=cp.start,
                          tokens=cp.end - cp.start, final=int(cp.final)):
                    logits = self._run_chunk(cp)
                if logits is None:
                    continue
                n_chunks += 1
                n_chunk_tokens += cp.cost
                self.obs.chunk_done(cp.job.req, cp.job.slot, cp.start,
                                    cp.end - cp.start, tc, self.obs.clock(),
                                    final=cp.final)
                if cp.job.done:
                    with span("repro.sched.first_token"):
                        act = self._finish_prefill(cp.job, logits)
                    if act is not None:
                        activated.append(act)
            emitted = super().step()
            # activate AFTER the decode dispatched: this iteration's
            # decode program shipped the shielded view, so a shared
            # trailing partial page can't take this slot's writes before
            # CoW sees it
            with span("repro.sched.activate", activated=len(activated)):
                for slot, req in activated:
                    self.active[slot] = req
                    self.kv.unshield(slot)
            self._g_peak.set_max(len(self.active))
            self.n_iterations += 1
            self.n_chunks_run += n_chunks
            self.obs.sched_iteration(t0, self.obs.clock(),
                                     n_decode=schedule.n_decode,
                                     n_chunks=n_chunks,
                                     n_chunk_tokens=n_chunk_tokens,
                                     budget_used=schedule.budget_used)
            iteration.set_metadata(n_decode=schedule.n_decode,
                                   n_chunks=n_chunks,
                                   chunk_tokens=n_chunk_tokens,
                                   budget_used=schedule.budget_used)
        self._progress = bool(emitted) or n_chunks > 0 or n_admitted > 0
        return emitted

    # ------------------------------------------------------------------
    def generate(self, prompts: Sequence[np.ndarray],
                 max_new_tokens: int = 32,
                 vision=None) -> List[RequestResult]:
        """Drain a batch of prompts through the scheduler; same contract
        (and, greedy, the same tokens) as ``Engine.generate``."""
        if vision is not None and any(v is not None for v in vision):
            raise ValueError("ScheduledEngine is attention-only (no vlm)")
        t_arrival = time.perf_counter()
        pending = [Request(prompt=np.asarray(p, np.int32),
                           max_new_tokens=max_new_tokens,
                           t_arrival=t_arrival) for p in prompts]
        results: List[Optional[RequestResult]] = [None] * len(pending)
        order = {id(r): i for i, r in enumerate(pending)}
        for r in pending:
            self.submit(r)
        inflight = list(pending)
        while (self.waiting or self.prefilling or self.active
               or self.preempted):
            self.obs.queue_depth(len(self.waiting) + len(self.prefilling)
                                 + len(self.preempted))
            self.step()
            if not self._progress:
                raise RuntimeError(
                    "serving stalled: no admission, chunk, or decode "
                    "progressed (raise n_blocks/token_budget or shrink "
                    "prompts)")
            for r in list(inflight):
                if r.slot == -1 and r.out_tokens:  # finished
                    results[order[id(r)]] = _result_of(r)
                    # identity removal: Request.__eq__ compares arrays
                    inflight = [x for x in inflight if x is not r]
        return results  # type: ignore

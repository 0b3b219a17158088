"""KVCacheAdapter: the cache side of the serving API seam.

The engine speaks to its cache through ONE interface, so cache layouts
stop leaking into the scheduling code: ``DenseCacheAdapter`` owns the
per-slot ring-buffer ``DecodeCache`` and ``PagedCacheAdapter`` owns the
block-pool ``PagedDecodeCache`` (wrapping ``paged_kv_cache``'s host-side
manager).  Each adapter owns its cache's

  * ``spec()`` / ``pspecs(rules)``  — shapes for jit input specs and the
    mesh partition specs (the shape logic the engine used to re-derive),
  * ``init()`` / ``device_cache()`` / ``update(new)`` — the device state
    the jitted ``forward_step`` consumes and returns (donated),
  * request lifecycle — ``admit`` (admission control; dense always
    admits, paged defers when the pool is exhausted), ``prefill`` (runs
    the adapter's own jitted prefill program: dense inserts a batch-1
    ``DecodeCache`` into the slot; paged writes prompt KV DIRECT-TO-PAGE
    via ``forward_prefill(dest=PagedPrefillDest(…))`` — no
    worst-case-length intermediate
    and no scatter pass), ``ensure_appendable`` / ``advance`` /
    ``release``.

Selecting a backend is then data, not code: ``Engine(cfg, params, sc,
cache=PagedCacheAdapter(block_size=16))`` or ``cache="paged"`` — and a new
cache layout is a new adapter plus its registered attention backends
(``models.backends``), with zero engine changes.

Chunked prefill (``repro.serving.sched``) adds a THIRD program per
adapter: ``build_chunk`` compiles one fixed-width slice program
(``models.forward_prefill_chunk``) and ``admit_chunked`` / ``chunk_ready``
/ ``chunk_step`` / ``finish_chunked`` / ``unshield`` drive a prompt into
the SHARED batched cache chunk by chunk while decode keeps stepping the
other slots.  The mid-prefill safety contracts differ per kind:

  * dense — the adapter keeps HOST lengths for every slot and overrides
    ``device_cache().length`` from them, so the batched decode step's
    write for a mid-prefill slot parks AT the chunk frontier (the next
    chunk overwrites it) and never advances the slot;
  * paged — the manager SHIELDS mid-prefill slots (their block-table rows
    ship as -1, decode writes drop on the floor) while the chunk program
    receives the true row; ``unshield`` flips the slot live only after
    the activating decode step has dispatched.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distribution import sharding as shd
from repro.models import (DenseChunkDest, DensePrefillDest, PagedChunkDest,
                          PagedPrefillDest, PagedQ8ChunkDest,
                          PagedQ8PrefillDest, forward_prefill,
                          forward_prefill_chunk, init_cache)
from repro.serving import hostbufs
from repro.serving import kv_cache as kvc
from repro.serving import paged_kv_cache as pkv


class KVCacheAdapter:
    """Interface the engine drives; see module docstring.  Subclasses set
    ``kind`` to the cache_kind axis of the backend-registry key."""

    kind: str = "?"

    #: prompts handed to prefill must be padded to a multiple of this
    #: (the engine's ``_bucket_pad`` rounds its power-of-two bucket up).
    #: paged_q8 overrides it with the page size: pages are quantized
    #: whole on write, so a prefill may not end mid-page.
    bucket_align: int = 1

    # -- lifecycle ------------------------------------------------------
    def init(self, cfg: ModelConfig, sc) -> None:
        """Allocate the device cache for (cfg, ServeConfig)."""
        raise NotImplementedError

    def build_prefill(self, impl: str, mesh=None, params_sharding=None,
                      cache_shardings=None, qkv_sharding=None) -> None:
        """Compile-wrap this cache kind's prefill program (a
        ``models.forward_prefill`` dispatch — the cache kind picks the
        destination, the model config picks the generic/merged style).
        ``qkv_sharding`` re-anchors TP head sharding for merged layouts
        under a mesh (no wq matmul to propagate it from)."""
        raise NotImplementedError

    # -- device state ---------------------------------------------------
    def spec(self):
        """ShapeDtypeStruct tree of ``device_cache()`` (jit input specs)."""
        return jax.eval_shape(self.device_cache)

    def pspecs(self, rules):
        """PartitionSpec tree matching ``spec()`` (mesh serving)."""
        return shd.serving_cache_pspecs(self.cfg, rules, self.spec())

    def device_cache(self):
        raise NotImplementedError

    def update(self, new) -> None:
        """Absorb the (donated) cache returned by the jitted step."""
        raise NotImplementedError

    @property
    def cache_bytes(self) -> int:
        raise NotImplementedError

    # -- request lifecycle ---------------------------------------------
    def admit(self, slot: int, tokens: np.ndarray) -> Optional[int]:
        """Admission control.  Returns the number of prefix-shared pages
        (0 where the concept doesn't apply), or None to DEFER the request
        (resource-exhausted; the engine retries after others finish)."""
        raise NotImplementedError

    def prefill(self, params, slot: int, padded_row, true_n: int,
                n_shared: int, vision):
        """Prefill ``padded_row`` (1, S) and install its KV for ``slot``;
        returns the last real position's logits (1, V)."""
        raise NotImplementedError

    def ensure_appendable(self, slot: int) -> bool:
        """Make the next token's write target safely writable; False means
        resource-exhausted (the engine preempts)."""
        return True

    def advance(self, slot: int) -> None:
        """Host-side length bookkeeping after a decoded token (the device
        cache advances inside the jitted step)."""

    def release(self, slot: int) -> None:
        """Return a finished/preempted request's cache resources."""
        raise NotImplementedError

    # -- chunked prefill (repro.serving.sched) --------------------------
    @property
    def supports_chunked(self) -> bool:
        """True when this adapter (as initialised) can run chunked
        prefill.  False routes every request through the scheduler's
        monolithic whole-prompt fallback (still asynchronous admission —
        just one unsplittable job per prompt)."""
        return False

    def enable_chunked(self) -> None:
        """Switch the adapter into chunked mode (host-side bookkeeping
        only; must precede the first ``device_cache()`` the scheduled
        engine ships)."""
        raise NotImplementedError

    def build_chunk(self, chunk_tokens: int, impl: str, mesh=None,
                    params_sharding=None, cache_shardings=None,
                    qkv_sharding=None) -> None:
        """Compile-wrap this cache kind's fixed-width chunk program
        (``models.forward_prefill_chunk``): ONE program serves chunk
        ``[start, start+chunk_tokens)`` of every prompt."""
        raise NotImplementedError

    def admit_chunked(self, slot: int, tokens: np.ndarray) -> Optional[int]:
        """Admission control for a chunked prefill: reserve ``slot`` for
        ``tokens`` without running anything.  Returns prefix-shared pages
        (0 where the concept doesn't apply) or None to DEFER."""
        raise NotImplementedError

    def chunk_ready(self, slot: int, start: int, end: int) -> bool:
        """Make the chunk's write targets safely writable (paged: map the
        covering pages / recycle ring pages); False means
        resource-exhausted (the scheduler preempts)."""
        return True

    def chunk_step(self, params, slot: int, chunk_row, start: int,
                   true_len: int):
        """Run ONE chunk of ``slot``'s prompt through the compiled chunk
        program; returns the chunk-local last real position's logits
        (1, V) — meaningful only on the final chunk."""
        raise NotImplementedError

    def finish_chunked(self, slot: int, tokens: np.ndarray) -> None:
        """All chunks landed: publish the slot's full length (paged:
        register the prompt's pages for prefix sharing)."""
        raise NotImplementedError

    def unshield(self, slot: int) -> None:
        """Expose the slot to batched decode writes (paged shield off).
        Call AFTER the activating decode step has dispatched — a shared
        trailing partial page must not take this slot's decode writes
        while an in-flight program still reads it."""

    def set_length(self, slot: int, n: int) -> None:
        """Sync host-side length bookkeeping after a MONOLITHIC prefill
        installed ``n`` tokens into ``slot`` (the scheduler's fallback
        path for unsplittable prompts on a chunked adapter)."""

    # -- introspection --------------------------------------------------
    def compiled_prefill(self, params, bucket_len: int):
        """Lower + compile the prefill program for one prompt bucket (no
        execution) — benchmarks read its cost_analysis (prefill HBM
        traffic, e.g. dense-vs-paged TTFT bytes)."""
        raise NotImplementedError

    def host_mutable_buffers(self) -> Dict[str, np.ndarray]:
        """Named host-side numpy buffers this adapter mutates across steps
        (``repro.lint.aliasing`` checks jit inputs against them).  Dense
        caches live entirely on device: nothing to declare."""
        return {}

    def obs_gauges(self):
        """name -> (zero-arg callable, help) of this cache's telemetry,
        lifted into ``Engine.metrics`` as LAZY gauges — evaluated only at
        ``collect()`` time, never on the serving path.  Dense caches have
        no pool to report."""
        return {}

    def span_counts(self):
        """name -> host integer carried by each ``repro.engine.step``
        span: the pool's pages mapped and free, read in O(1).  Dense
        caches have no pool to report."""
        return {}


class DenseCacheAdapter(KVCacheAdapter):
    """Worst-case-length slot cache: every slot owns a ``max_len`` stretch
    of one batched ``DecodeCache``; insert/evict are O(1) dynamic slices
    (``serving.kv_cache``).  Supports every family (attn/ssm/hybrid/vlm)."""

    kind = "dense"

    def init(self, cfg, sc):
        self.cfg, self.sc = cfg, sc
        self._cache = init_cache(cfg, sc.n_slots, sc.max_len)
        self._chunked = False

    def build_prefill(self, impl, mesh=None, params_sharding=None,
                      cache_shardings=None, qkv_sharding=None):
        cfg = self.cfg
        dest = DensePrefillDest(cache_len=self.sc.max_len)

        def prompt_prefill(p, tk, vs, tl):
            return forward_prefill(
                p, cfg, tk, dest, vision=vs, impl=impl, true_len=tl,
                qkv_sharding=qkv_sharding)

        if mesh is not None:
            self._prefill = jax.jit(
                prompt_prefill,
                in_shardings=(params_sharding, None, None, None))
        else:
            self._prefill = jax.jit(prompt_prefill)

    def device_cache(self):
        if not self._chunked:
            return self._cache
        # chunked mode: HOST lengths are authoritative.  A mid-prefill
        # slot's length is its chunk frontier, so the batched decode
        # step's write for that slot parks AT the frontier (the next
        # chunk overwrites it) instead of advancing past real positions.
        # .copy() before ingestion: _lengths is engine-mutated host state
        # and jnp.asarray of an aligned buffer is zero-copy (lint:
        # aliasing audit).
        return self._cache._replace(
            length=jnp.asarray(self._lengths.copy()))

    def update(self, new):
        self._cache = new

    @property
    def cache_bytes(self):
        k = self._cache.k
        return int(k.size + self._cache.v.size) * k.dtype.itemsize

    def admit(self, slot, tokens):
        return 0

    def prefill(self, params, slot, padded_row, true_n, n_shared, vision):
        tl = jnp.full((1,), true_n, jnp.int32)
        logits, one = self._prefill(params, padded_row, vision, tl)
        self._cache = kvc.insert_request(self._cache, one, jnp.int32(slot))
        return logits

    def advance(self, slot):
        if self._chunked:
            self._lengths[slot] += 1

    def release(self, slot):
        self._cache = kvc.clear_slot(self._cache, jnp.int32(slot))
        if self._chunked:
            self._lengths[slot] = 0

    def host_mutable_buffers(self):
        if self._chunked:
            return {"dense._lengths": self._lengths}
        return {}

    # -- chunked prefill ------------------------------------------------
    @property
    def supports_chunked(self):
        # a BINDING sliding window (window < max_len) makes the dense
        # cache a window-sized ring that cannot hold a partial prompt at
        # absolute positions; the scheduler falls back to monolithic
        # whole-prompt jobs there.  Non-binding windows chunk exactly
        # like window-free configs.
        w = self.cfg.sliding_window
        return not (w and w < self.sc.max_len)

    def enable_chunked(self):
        self._chunked = True
        self._lengths = hostbufs.aligned_zeros((self.sc.n_slots,), np.int32)

    def build_chunk(self, chunk_tokens, impl, mesh=None, params_sharding=None,
                    cache_shardings=None, qkv_sharding=None):
        cfg, max_len = self.cfg, self.sc.max_len
        self._chunk_tokens = chunk_tokens

        def chunk_prefill(p, tk, s, tl, slot, cache):
            return forward_prefill_chunk(
                p, cfg, tk, DenseChunkDest(cache, slot), start=s,
                true_len=tl, impl=impl, qkv_sharding=qkv_sharding,
                max_len=max_len)

        if mesh is not None:
            self._chunk = jax.jit(
                chunk_prefill, donate_argnums=(5,),
                in_shardings=(params_sharding, None, None, None, None,
                              cache_shardings),
                out_shardings=(None, cache_shardings))
        else:
            self._chunk = jax.jit(chunk_prefill, donate_argnums=(5,))

    def admit_chunked(self, slot, tokens):
        self._lengths[slot] = 0
        return 0

    def chunk_step(self, params, slot, chunk_row, start, true_len):
        s = jnp.full((1,), start, jnp.int32)
        tl = jnp.full((1,), true_len, jnp.int32)
        sl = jnp.full((1,), slot, jnp.int32)
        logits, new_cache = self._chunk(params, chunk_row, s, tl, sl,
                                        self.device_cache())
        self._cache = new_cache
        self._lengths[slot] = min(start + self._chunk_tokens, true_len)
        return logits

    def finish_chunked(self, slot, tokens):
        self._lengths[slot] = len(tokens)

    def set_length(self, slot, n):
        if self._chunked:
            self._lengths[slot] = n

    def compiled_prefill(self, params, bucket_len):
        pshape = jax.eval_shape(lambda: params)
        tk = jax.ShapeDtypeStruct((1, bucket_len), jnp.int32)
        tl = jax.ShapeDtypeStruct((1,), jnp.int32)
        return self._prefill.lower(pshape, tk, None, tl).compile()


class PagedCacheAdapter(KVCacheAdapter):
    """Block-pool cache: slots map variable numbers of fixed-size physical
    pages (free-list allocation, prefix sharing, copy-on-write, admission
    control — ``serving.paged_kv_cache``).  Attention-only stacks.

    ``block_size``/``n_blocks`` default to the ServeConfig's values at
    ``init`` (n_blocks 0 ⇒ dense-equivalent HBM: n_slots·max_len/bs pages).
    Prefill writes prompt KV directly into the mapped pages from inside
    the prefill program (``forward_prefill(dest=PagedPrefillDest(…))``):
    the jit is donated
    on the pools, so submit-time cache traffic is ONLY the prompt's own
    pages — no max_len-sized intermediate buffer, no second scatter pass.
    """

    kind = "paged"

    def __init__(self, block_size: Optional[int] = None,
                 n_blocks: Optional[int] = None,
                 prefix_retention: bool = True):
        self._block_size, self._n_blocks = block_size, n_blocks
        self._prefix_retention = prefix_retention

    def init(self, cfg, sc):
        self.cfg, self.sc = cfg, sc
        bs = self._block_size or sc.block_size
        n_blocks = self._n_blocks or sc.n_blocks \
            or sc.n_slots * (sc.max_len // bs)
        self.pm = pkv.PagedCacheManager(
            cfg, n_slots=sc.n_slots, max_len=sc.max_len,
            block_size=bs, n_blocks=n_blocks,
            prefix_retention=self._prefix_retention)

    def build_prefill(self, impl, mesh=None, params_sharding=None,
                      cache_shardings=None, qkv_sharding=None):
        cfg = self.cfg

        def prompt_prefill(p, tk, tl, kp, vp, bids):
            return forward_prefill(
                p, cfg, tk, PagedPrefillDest(kp, vp, bids), impl=impl,
                true_len=tl, qkv_sharding=qkv_sharding)

        if mesh is not None:
            pool_k, pool_v = cache_shardings.k, cache_shardings.v
            self._prefill = jax.jit(
                prompt_prefill, donate_argnums=(3, 4),
                in_shardings=(params_sharding, None, None, pool_k, pool_v,
                              None),
                out_shardings=(None, (pool_k, pool_v)))
        else:
            self._prefill = jax.jit(prompt_prefill, donate_argnums=(3, 4))

    def device_cache(self):
        return self.pm.device_cache()

    def update(self, new):
        self.pm.update_pools(new)

    def host_mutable_buffers(self):
        return self.pm.host_mutable_buffers()

    @property
    def cache_bytes(self):
        return self.pm.pool_bytes

    def admit(self, slot, tokens):
        return self.pm.admit(slot, tokens)

    def prefill(self, params, slot, padded_row, true_n, n_shared, vision):
        assert vision is None, "paged serving is attention-only (no vlm)"
        bids = self.pm.prefill_block_ids(slot, padded_row.shape[1])
        tl = jnp.full((1,), true_n, jnp.int32)
        logits, (k, v) = self._prefill(params, padded_row, tl,
                                       self.pm.k, self.pm.v,
                                       jnp.asarray(bids))
        self.pm.k, self.pm.v = k, v
        return logits

    def ensure_appendable(self, slot):
        return self.pm.ensure_appendable(slot)

    def advance(self, slot):
        self.pm.advance(slot)

    def release(self, slot):
        self.pm.release(slot)

    # -- chunked prefill ------------------------------------------------
    @property
    def supports_chunked(self):
        return True

    def enable_chunked(self):
        pass  # shield/frontier machinery lives in the manager, always on

    def build_chunk(self, chunk_tokens, impl, mesh=None, params_sharding=None,
                    cache_shardings=None, qkv_sharding=None):
        cfg = self.cfg
        if chunk_tokens % self.pm.bs:
            raise ValueError(
                f"chunk_tokens ({chunk_tokens}) must be a multiple of the "
                f"block size ({self.pm.bs})")
        if self.pm.ring and chunk_tokens != self.pm.bs:
            raise ValueError(
                f"ring (windowed) paged chunking pins chunk_tokens to one "
                f"block ({self.pm.bs}); got {chunk_tokens}")
        self._chunk_tokens = chunk_tokens

        def chunk_prefill(p, tk, s, tl, kp, vp, trow, bids):
            return forward_prefill_chunk(
                p, cfg, tk, PagedChunkDest(kp, vp, trow, bids), start=s,
                true_len=tl, impl=impl, qkv_sharding=qkv_sharding)

        if mesh is not None:
            pool_k, pool_v = cache_shardings.k, cache_shardings.v
            self._chunk = jax.jit(
                chunk_prefill, donate_argnums=(4, 5),
                in_shardings=(params_sharding, None, None, None, pool_k,
                              pool_v, None, None),
                out_shardings=(None, (pool_k, pool_v)))
        else:
            self._chunk = jax.jit(chunk_prefill, donate_argnums=(4, 5))

    def admit_chunked(self, slot, tokens):
        return self.pm.admit_chunked(slot, tokens)

    def chunk_ready(self, slot, start, end):
        return self.pm.ensure_chunk(slot, start, end)

    def chunk_step(self, params, slot, chunk_row, start, true_len):
        C = self._chunk_tokens
        bids = self.pm.chunk_block_ids(slot, start, start + C, true_len)
        s = jnp.full((1,), start, jnp.int32)
        tl = jnp.full((1,), true_len, jnp.int32)
        # the TRUE table row (the decode view masks shielded slots to -1);
        # .copy() before ingestion — tables is host-mutated (aliasing)
        trow = jnp.asarray(self.pm.tables[slot:slot + 1].copy())
        logits, (k, v) = self._chunk(params, chunk_row, s, tl,
                                     self.pm.k, self.pm.v, trow,
                                     jnp.asarray(bids))
        self.pm.k, self.pm.v = k, v
        self.pm.set_frontier(slot, min(start + C, true_len))
        return logits

    def finish_chunked(self, slot, tokens):
        self.pm.finish_chunked(slot, tokens)

    def unshield(self, slot):
        self.pm.unshield(slot)

    def compiled_prefill(self, params, bucket_len):
        pshape = jax.eval_shape(lambda: params)
        tk = jax.ShapeDtypeStruct((1, bucket_len), jnp.int32)
        tl = jax.ShapeDtypeStruct((1,), jnp.int32)
        kp = jax.eval_shape(lambda: self.pm.k)
        vp = jax.eval_shape(lambda: self.pm.v)
        nbk = -(-bucket_len // self.pm.bs)
        bids = jax.ShapeDtypeStruct((nbk,), jnp.int32)
        return self._prefill.lower(pshape, tk, tl, kp, vp, bids).compile()

    def span_counts(self):
        """Pages mapped and free in the pool, and the decode's block
        tables: ``table_pages`` entries (slots x table width, the table
        the kernel reads) of which ``table_pages_live`` are mapped,
        shielded slots (their rows read as -1 on the device) counted 0."""
        a, pm = self.pm.allocator, self.pm
        live = np.count_nonzero(pm.tables >= 0)
        for slot in pm.shielded:
            live -= np.count_nonzero(pm.tables[slot] >= 0)
        return {"pages_used": a.n_used, "pages_free": a.n_free,
                "table_pages_live": int(live),
                "table_pages": int(pm.tables.size)}

    def obs_gauges(self):
        a = self.pm.allocator
        return {
            "pool_blocks_used": (lambda: a.n_used, "pages mapped now"),
            "pool_blocks_free": (lambda: a.n_free, "pages on the free list"),
            "pool_peak_used": (lambda: a.peak_used,
                               "pool occupancy high-water (pages)"),
            "pool_recycled": (lambda: a.n_recycled,
                              "ring pages recycled in place"),
            "pool_cow": (lambda: a.n_cow, "copy-on-write page splits"),
            "pool_prefix_hits": (lambda: a.n_shared_hits,
                                 "prefix pages shared at admit"),
            "prefix_tree_nodes": (lambda: self.pm.tree.n_nodes,
                                  "radix prefix-tree nodes resident"),
            "prefix_retained_pages": (
                lambda: len(self.pm.tree.retained),
                "pages held only by the prefix tree (retention)"),
            "prefix_hit_tokens": (lambda: self.pm.tree.hit_tokens,
                                  "prompt tokens served from the prefix "
                                  "cache"),
            "prefix_evicted": (lambda: self.pm.tree.n_evicted,
                               "retained pages evicted under pressure"),
        }


class PagedQ8CacheAdapter(PagedCacheAdapter):
    """Quantized block-pool cache: the paged layout with int8 pages and
    per-(page, kv-head) float32 scales (``pkv.PagedQ8CacheManager``).

    Everything host-side — allocator, block tables, CoW, ring recycle,
    prefix registry, shields — is inherited UNCHANGED: a page id means the
    same thing, its scale rows just travel with it (``copy_block_q8``
    copies all four arrays).  What changes is the device programs: prefill
    and chunk ship the scale arrays next to the pools (donated together)
    and the destinations are the q8 variants, which quantize-on-write; the
    decode step reads ``PagedQ8DecodeCache`` and the registered
    ``paged_q8`` backends dequantize in-kernel.  HBM for the pools is
    ~quarter of an fp32 pool (int8 pages + one f32 scale pair per
    (page, head)), which is where the equal-HBM stream-count win in
    ``benchmarks.bench_paged_serving`` comes from.
    """

    kind = "paged_q8"

    @property
    def bucket_align(self) -> int:
        # pages quantize whole on write: prefill may not end mid-page
        return self.pm.bs

    def init(self, cfg, sc):
        self.cfg, self.sc = cfg, sc
        bs = self._block_size or sc.block_size
        n_blocks = self._n_blocks or sc.n_blocks \
            or sc.n_slots * (sc.max_len // bs)
        self.pm = pkv.PagedQ8CacheManager(
            cfg, n_slots=sc.n_slots, max_len=sc.max_len,
            block_size=bs, n_blocks=n_blocks,
            prefix_retention=self._prefix_retention)

    def build_prefill(self, impl, mesh=None, params_sharding=None,
                      cache_shardings=None, qkv_sharding=None):
        cfg = self.cfg

        def prompt_prefill(p, tk, tl, kp, vp, ks, vs, bids):
            return forward_prefill(
                p, cfg, tk, PagedQ8PrefillDest(kp, vp, ks, vs, bids),
                impl=impl, true_len=tl, qkv_sharding=qkv_sharding)

        if mesh is not None:
            cs = cache_shardings
            self._prefill = jax.jit(
                prompt_prefill, donate_argnums=(3, 4, 5, 6),
                in_shardings=(params_sharding, None, None, cs.k, cs.v,
                              cs.k_scale, cs.v_scale, None),
                out_shardings=(None, (cs.k, cs.v, cs.k_scale, cs.v_scale)))
        else:
            self._prefill = jax.jit(prompt_prefill,
                                    donate_argnums=(3, 4, 5, 6))

    def prefill(self, params, slot, padded_row, true_n, n_shared, vision):
        assert vision is None, "paged serving is attention-only (no vlm)"
        bids = self.pm.prefill_block_ids(slot, padded_row.shape[1])
        tl = jnp.full((1,), true_n, jnp.int32)
        logits, (k, v, ks, vs) = self._prefill(
            params, padded_row, tl, self.pm.k, self.pm.v,
            self.pm.k_scale, self.pm.v_scale, jnp.asarray(bids))
        self.pm.k, self.pm.v = k, v
        self.pm.k_scale, self.pm.v_scale = ks, vs
        return logits

    def build_chunk(self, chunk_tokens, impl, mesh=None, params_sharding=None,
                    cache_shardings=None, qkv_sharding=None):
        cfg = self.cfg
        if chunk_tokens % self.pm.bs:
            raise ValueError(
                f"chunk_tokens ({chunk_tokens}) must be a multiple of the "
                f"block size ({self.pm.bs})")
        if self.pm.ring and chunk_tokens != self.pm.bs:
            raise ValueError(
                f"ring (windowed) paged chunking pins chunk_tokens to one "
                f"block ({self.pm.bs}); got {chunk_tokens}")
        self._chunk_tokens = chunk_tokens

        def chunk_prefill(p, tk, s, tl, kp, vp, ks, vs, trow, bids):
            return forward_prefill_chunk(
                p, cfg, tk, PagedQ8ChunkDest(kp, vp, ks, vs, trow, bids),
                start=s, true_len=tl, impl=impl, qkv_sharding=qkv_sharding)

        if mesh is not None:
            cs = cache_shardings
            self._chunk = jax.jit(
                chunk_prefill, donate_argnums=(4, 5, 6, 7),
                in_shardings=(params_sharding, None, None, None, cs.k, cs.v,
                              cs.k_scale, cs.v_scale, None, None),
                out_shardings=(None, (cs.k, cs.v, cs.k_scale, cs.v_scale)))
        else:
            self._chunk = jax.jit(chunk_prefill, donate_argnums=(4, 5, 6, 7))

    def chunk_step(self, params, slot, chunk_row, start, true_len):
        C = self._chunk_tokens
        bids = self.pm.chunk_block_ids(slot, start, start + C, true_len)
        s = jnp.full((1,), start, jnp.int32)
        tl = jnp.full((1,), true_len, jnp.int32)
        # the TRUE table row (the decode view masks shielded slots to -1);
        # .copy() before ingestion — tables is host-mutated (aliasing)
        trow = jnp.asarray(self.pm.tables[slot:slot + 1].copy())
        logits, (k, v, ks, vs) = self._chunk(
            params, chunk_row, s, tl, self.pm.k, self.pm.v,
            self.pm.k_scale, self.pm.v_scale, trow, jnp.asarray(bids))
        self.pm.k, self.pm.v = k, v
        self.pm.k_scale, self.pm.v_scale = ks, vs
        self.pm.set_frontier(slot, min(start + C, true_len))
        return logits

    def compiled_prefill(self, params, bucket_len):
        pshape = jax.eval_shape(lambda: params)
        tk = jax.ShapeDtypeStruct((1, bucket_len), jnp.int32)
        tl = jax.ShapeDtypeStruct((1,), jnp.int32)
        kp = jax.eval_shape(lambda: self.pm.k)
        vp = jax.eval_shape(lambda: self.pm.v)
        ks = jax.eval_shape(lambda: self.pm.k_scale)
        vs = jax.eval_shape(lambda: self.pm.v_scale)
        nbk = -(-bucket_len // self.pm.bs)
        bids = jax.ShapeDtypeStruct((nbk,), jnp.int32)
        return self._prefill.lower(pshape, tk, tl, kp, vp, ks, vs,
                                   bids).compile()

    def obs_gauges(self):
        g = dict(super().obs_gauges())
        pm = self.pm

        def q8_bytes():
            return pm.pool_bytes

        def saved_vs_fp16():
            elems = int(pm.k.size) + int(pm.v.size)  # int8: 1 byte each
            return elems * 2 - pm.pool_bytes

        g.update({
            "q8_pool_bytes": (q8_bytes,
                              "int8 pool + scale bytes resident"),
            "q8_bytes_saved_vs_fp16": (
                saved_vs_fp16,
                "HBM saved vs an fp16 pool of the same page count"),
        })
        return g


def make_adapter(kind: str, sc) -> KVCacheAdapter:
    """Adapter for a cache_kind name (the string form of the new API, and
    the target of the deprecated ``ServeConfig.cache_kind``)."""
    if kind == "dense":
        return DenseCacheAdapter()
    if kind == "paged":
        return PagedCacheAdapter(block_size=sc.block_size,
                                 n_blocks=sc.n_blocks)
    if kind == "paged_q8":
        return PagedQ8CacheAdapter(block_size=sc.block_size,
                                   n_blocks=sc.n_blocks)
    raise ValueError(
        f"unknown cache kind {kind!r}; expected 'dense', 'paged', "
        "'paged_q8', or a KVCacheAdapter instance")

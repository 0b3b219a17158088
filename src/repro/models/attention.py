"""Attention cores: MHA/MQA/GQA, causal & sliding-window, prefill & decode.

Projections (Q/K/V/P) live in the *block* modules (``core.blocks`` /
``models.transformer``) because the paper's merged form changes which
projections exist.  This module only computes attention given projected
(and RoPE'd) q/k/v.

Three implementations:
  * ``impl="xla"`` — chunked exact attention (lax.map over query chunks) so
    the materialized score buffer is O(chunk × S_k), never O(S_q × S_k).
    This is the path the multi-pod dry-run lowers.
  * ``impl="pallas"`` — TPU Pallas flash-attention kernel (kernels/).
  * ``impl="pallas_interpret"`` — same kernel, interpret mode (CPU tests).

Cores fetch their Pallas route from ``kernels.ops.ATTENTION_KERNELS``,
keyed (phase, cache_kind, style) like the serving backend registries
(``models.backends``) — one table says which combos have fused kernels.
``attention_core_merged`` is the prefill face of the paper's merged
(Q/P-removed) layout: stream-as-query, no head-major transposes, output
in the FFN-input basis.

GQA is computed grouped (q reshaped to (…, n_kv, group, d)) — KV heads are
never materialized repeated.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.paging import paged_ring_active
from repro.kernels import quant

NEG_INF = -1e30


def _mask_bias(
    q_pos: jnp.ndarray,  # (B, Sq) int32
    kv_pos: jnp.ndarray,  # (B, Sk) int32
    *,
    causal: bool,
    sliding_window: int,
    kv_valid: Optional[jnp.ndarray],  # (B, Sk) bool
) -> jnp.ndarray:
    """Additive bias (B, 1, Sq, Sk) fp32: 0 where attendable, NEG_INF else."""
    ok = jnp.ones((q_pos.shape[0], q_pos.shape[1], kv_pos.shape[1]), bool)
    if causal:
        ok &= kv_pos[:, None, :] <= q_pos[:, :, None]
    if sliding_window > 0:
        ok &= q_pos[:, :, None] - kv_pos[:, None, :] < sliding_window
    if kv_valid is not None:
        ok &= kv_valid[:, None, :]
    return jnp.where(ok, 0.0, NEG_INF)[:, None, :, :].astype(jnp.float32)


def _attend_block(q, k, v, bias, scale):
    """q (B,Sq,Hkv,G,D) k/v (B,Sk,Hkv,D) bias (B,1,Sq,Sk) -> (B,Sq,Hkv,G,D)."""
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale + bias[:, :, None, :, :]
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)


def attention_core(
    q: jnp.ndarray,  # (B, Sq, Hq, D)
    k: jnp.ndarray,  # (B, Sk, Hkv, D)
    v: jnp.ndarray,  # (B, Sk, Hkv, D)
    *,
    q_positions: jnp.ndarray,  # (B, Sq) int32
    kv_positions: jnp.ndarray,  # (B, Sk) int32
    causal: bool = True,
    sliding_window: int = 0,
    kv_valid: Optional[jnp.ndarray] = None,  # (B, Sk) bool (padded caches)
    query_chunk: int = 1024,
    impl: str = "xla",
) -> jnp.ndarray:
    """Exact softmax attention; returns (B, Sq, Hq, D) in v.dtype."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    scale = 1.0 / (D ** 0.5)

    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops

        return kops.attention_kernel("prefill", "dense", "generic")(
            q, k, v,
            q_positions=q_positions, kv_positions=kv_positions,
            causal=causal, sliding_window=sliding_window, kv_valid=kv_valid,
            interpret=(impl == "pallas_interpret"),
        )

    qg = q.reshape(B, Sq, Hkv, G, D)

    if Sq <= query_chunk or Sq % query_chunk != 0:
        bias = _mask_bias(q_positions, kv_positions, causal=causal,
                          sliding_window=sliding_window, kv_valid=kv_valid)
        out = _attend_block(qg, k, v, bias, scale)
        return out.reshape(B, Sq, Hq, D)

    # chunked over query blocks: score buffer is (B, chunk, …) not (B, Sq, …)
    n_chunks = Sq // query_chunk
    qg_c = qg.reshape(B, n_chunks, query_chunk, Hkv, G, D).transpose(1, 0, 2, 3, 4, 5)
    qp_c = q_positions.reshape(B, n_chunks, query_chunk).transpose(1, 0, 2)

    def one_chunk(args):
        qc, qpc = args
        bias = _mask_bias(qpc, kv_positions, causal=causal,
                          sliding_window=sliding_window, kv_valid=kv_valid)
        return _attend_block(qc, k, v, bias, scale)

    out = jax.lax.map(one_chunk, (qg_c, qp_c))  # (n_chunks, B, chunk, Hkv, G, D)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, Hq, D)
    return out


def attention_core_merged(
    u: jnp.ndarray,  # (B, Sq, d_model) — RoPE'd residual stream (merged query)
    k: jnp.ndarray,  # (B, Sk, Hkv, D) — K*, native (sequence-major) layout
    v: jnp.ndarray,  # (B, Sk, Hkv, D) — V*
    *,
    q_positions: jnp.ndarray,  # (B, Sq) int32
    kv_positions: jnp.ndarray,  # (B, Sk) int32
    n_kv_heads: int,
    causal: bool = True,
    sliding_window: int = 0,
    query_chunk: int = 1024,
    impl: str = "xla",
    cache_kind: str = "dense",
    k_scale: Optional[jnp.ndarray] = None,  # (B, Sk//sg, Hkv) f32 (q8 only)
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Merged (Q/P-removed, paper Fig 1b) full-sequence attention — the
    PREFILL sibling of ``decode_attention_core_merged``.

    In ``skipless_merged`` qp-variant blocks the residual stream *is* the
    query basis (Q folded into the producers of u) and no P projection
    exists, so this core takes the stream directly — the grouped-head view
    is a bitcast — and returns the (B, Sq, d_model) FFN-input stream.  The
    pallas route is the stream-as-query flash kernel reading K*/V* tiles
    in their native layout; numerics are identical to ``attention_core``
    on the bitcast head view.  ``cache_kind`` selects the prefill row of
    ``kernels.ops.ATTENTION_KERNELS`` (both cache kinds currently share
    the flash kernel — paging changes the KV *write*, not the math).

    ``k_scale``/``v_scale`` flip the core into q8 mode (the ``paged_q8``
    kind's prefill fake-quant): k/v arrive as int8 at the pool's
    quantization and the scales are per-(page, head).  The pallas route
    hands the int8 tiles + scales to the in-kernel-dequant flash kernel;
    the XLA route dequantizes the sequence once (O(Sk·Hkv·D), same as any
    other kv buffer this core already holds) and falls through.
    """
    B, Sq, d = u.shape
    D = k.shape[3]
    quantized = k_scale is not None

    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops

        kw = {}
        if quantized:
            kw = dict(k_scale=k_scale, v_scale=v_scale)
        return kops.attention_kernel("prefill", cache_kind, "merged")(
            u, k, v, n_kv_heads=n_kv_heads,
            q_positions=q_positions, kv_positions=kv_positions,
            causal=causal, sliding_window=sliding_window,
            interpret=(impl == "pallas_interpret"), **kw,
        )

    if quantized:
        k = quant.q8_dequant_seq(k, k_scale, u.dtype)
        v = quant.q8_dequant_seq(v, v_scale, u.dtype)

    out = attention_core(
        u.reshape(B, Sq, d // D, D), k, v,
        q_positions=q_positions, kv_positions=kv_positions,
        causal=causal, sliding_window=sliding_window,
        query_chunk=query_chunk, impl=impl)
    return out.reshape(B, Sq, d)


def decode_attention_core(
    q: jnp.ndarray,  # (B, Hq, D) — single new token per sequence
    k_cache: jnp.ndarray,  # (B, S, Hkv, D)
    v_cache: jnp.ndarray,  # (B, S, Hkv, D)
    *,
    cache_len: jnp.ndarray,  # (B,) int32 — number of valid cache entries
    sliding_window: int = 0,
    impl: str = "xla",
) -> jnp.ndarray:
    """One-token attention against a (padded) KV cache -> (B, Hq, D).

    The query's position is ``cache_len`` (0-indexed next position); the
    cache holds positions [0, cache_len).  For sliding-window archs the
    cache may be a ring buffer — ``kv_positions`` are then supplied by the
    cache layer via ``decode_attention_core_positions``.
    """
    B, S, Hkv, D = k_cache.shape
    kv_positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return decode_attention_core_positions(
        q, k_cache, v_cache, kv_positions=kv_positions,
        q_position=cache_len, sliding_window=sliding_window, impl=impl,
    )


def decode_attention_core_merged(
    u: jnp.ndarray,  # (B, d_model) — RoPE'd residual stream (merged query)
    k_cache: jnp.ndarray,  # (B, S, Hkv, D) — K*, native serving layout
    v_cache: jnp.ndarray,  # (B, S, Hkv, D) — V*
    *,
    kv_positions: jnp.ndarray,  # (B, S) int32; -1 marks empty slots
    q_position: jnp.ndarray,  # (B,) int32
    n_kv_heads: int,
    sliding_window: int = 0,
    impl: str = "xla",
) -> jnp.ndarray:
    """Merged (Q/P-removed, paper Fig 1b) decode attention.

    In ``skipless_merged`` qp-variant blocks the residual stream *is* the
    query basis (Q folded into the producers of u), and no P projection
    exists — the attention output is already the FFN input.  So this core
    takes the stream directly, skips any q projection, and returns the
    (B, d_model) stream for the FFN.  Numerics are identical to
    ``decode_attention_core_positions`` on the bitcast head view.
    """
    B, d = u.shape
    D = k_cache.shape[3]

    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops

        return kops.decode_kernel("dense", "merged")(
            u, k_cache, v_cache, kv_positions=kv_positions,
            q_position=q_position, n_kv_heads=n_kv_heads,
            sliding_window=sliding_window,
            interpret=(impl == "pallas_interpret"),
        )

    out = decode_attention_core_positions(
        u.reshape(B, d // D, D), k_cache, v_cache,
        kv_positions=kv_positions, q_position=q_position,
        sliding_window=sliding_window, impl=impl)
    return out.reshape(B, d)


def decode_attention_core_positions(
    q: jnp.ndarray,  # (B, Hq, D)
    k_cache: jnp.ndarray,  # (B, S, Hkv, D)
    v_cache: jnp.ndarray,  # (B, S, Hkv, D)
    *,
    kv_positions: jnp.ndarray,  # (B, S) int32; -1 marks empty slots
    q_position: jnp.ndarray,  # (B,) int32
    sliding_window: int = 0,
    impl: str = "xla",
) -> jnp.ndarray:
    B, Hq, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    scale = 1.0 / (D ** 0.5)

    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops

        return kops.decode_kernel("dense", "generic")(
            q, k_cache, v_cache, kv_positions=kv_positions,
            q_position=q_position, sliding_window=sliding_window,
            interpret=(impl == "pallas_interpret"),
        )

    qg = q.reshape(B, Hkv, G, D)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    ok = (kv_positions >= 0) & (kv_positions[:, :] <= q_position[:, None])
    if sliding_window > 0:
        ok &= q_position[:, None] - kv_positions < sliding_window
    bias = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)  # (B, S)
    probs = jax.nn.softmax(scores + bias[:, None, None, :], axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs.astype(v_cache.dtype), v_cache)
    return out.reshape(B, Hq, D)


# ---------------------------------------------------------------------------
# paged decode cores (block-table gather over a physical page pool)
# ---------------------------------------------------------------------------

def paged_kv_positions(block_tables: jnp.ndarray, block_size: int,
                       q_position: Optional[jnp.ndarray] = None,
                       ring_blocks: int = 0) -> jnp.ndarray:
    """kv positions of a slot's densified page view; unmapped (-1) blocks
    stay -1 (empty-slot mask).

    Absolute addressing (``ring_blocks`` = 0): logical block j covers
    [j*bs, (j+1)*bs).  Ring addressing (windowed tables bounded at
    ceil(window/bs)+1 recycled slots — see ``kernels.paging``): slot j
    holds the latest absolute block ≡ j (mod ring) not beyond the query's
    current block, so positions are reconstructed from ``q_position``;
    slots reconstructing to b < 0 (never entered) are -1."""
    B, MB = block_tables.shape
    if ring_blocks:
        j = jnp.arange(MB, dtype=jnp.int32)[None, :]
        lb = (jnp.asarray(q_position, jnp.int32) // block_size).reshape(B, 1)
        b = lb - ((lb + ring_blocks - j) % ring_blocks)
        pos = jnp.repeat(b * block_size, block_size, axis=1) + \
            jnp.tile(jnp.arange(block_size, dtype=jnp.int32), MB)[None, :]
        mapped = jnp.repeat((block_tables >= 0) & (b >= 0), block_size,
                            axis=1)
        return jnp.where(mapped, pos, -1)
    pos = jnp.arange(MB * block_size, dtype=jnp.int32)[None, :]
    mapped = jnp.repeat(block_tables >= 0, block_size, axis=1)
    return jnp.broadcast_to(jnp.where(mapped, pos, -1), (B, MB * block_size))


def _paged_gather(pool: jnp.ndarray, block_tables: jnp.ndarray,
                  layer) -> jnp.ndarray:
    """(L, NB, bs, Hkv, D) pool + (B, MB) tables -> layer ``layer``'s
    (B, MB*bs, Hkv, D) view, in one gather from the stack."""
    B, MB = block_tables.shape
    g = pool[layer, jnp.maximum(block_tables, 0)]  # (B, MB, bs, Hkv, D)
    return g.reshape(B, MB * pool.shape[2], *pool.shape[3:])


def decode_attention_core_paged(
    q: jnp.ndarray,  # (B, Hq, D)
    k_pool: jnp.ndarray,  # (L, NB, bs, Hkv, D) — layer-stacked page pool
    v_pool: jnp.ndarray,  # (L, NB, bs, Hkv, D)
    *,
    block_tables: jnp.ndarray,  # (B, MB) int32 page ids, -1 unmapped
    q_position: jnp.ndarray,  # (B,) int32
    layer,  # int32 scalar: the layer of the stack attended
    sliding_window: int = 0,
    impl: str = "xla",
) -> jnp.ndarray:
    """One-token attention against layer ``layer`` of a paged KV pool ->
    (B, Hq, D).

    The pallas path hands the whole stack, the layer and the table
    straight to the paged kernel (pages are gathered block-by-block inside
    the grid); the XLA path densifies the slot's logical view of the layer
    first and defers to the dense core.
    Ring addressing (windowed tables bounded at ceil(window/bs)+1 recycled
    slots) is derived from the window and the table width
    (``kernels.paging``), with positions reconstructed per query.
    """
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops

        return kops.decode_kernel("paged", "generic")(
            q, k_pool, v_pool, block_tables=block_tables,
            q_position=q_position, layer=layer,
            sliding_window=sliding_window,
            interpret=(impl == "pallas_interpret"))

    bs = k_pool.shape[2]
    ring = paged_ring_active(sliding_window, bs, block_tables.shape[1])
    return decode_attention_core_positions(
        q, _paged_gather(k_pool, block_tables, layer),
        _paged_gather(v_pool, block_tables, layer),
        kv_positions=paged_kv_positions(block_tables, bs, q_position, ring),
        q_position=q_position, sliding_window=sliding_window, impl=impl)


def decode_attention_core_paged_merged(
    u: jnp.ndarray,  # (B, d_model) — RoPE'd residual stream (merged query)
    k_pool: jnp.ndarray,  # (L, NB, bs, Hkv, D) — K* page pool
    v_pool: jnp.ndarray,  # (L, NB, bs, Hkv, D) — V* page pool
    *,
    block_tables: jnp.ndarray,  # (B, MB) int32 page ids, -1 unmapped
    q_position: jnp.ndarray,  # (B,) int32
    layer,  # int32 scalar: the layer of the stack attended
    n_kv_heads: int,
    sliding_window: int = 0,
    impl: str = "xla",
) -> jnp.ndarray:
    """Merged (Q/P-removed) decode attention over a paged KV pool.

    Same contract as ``decode_attention_core_merged`` — the stream is the
    query and the output stays in the FFN-input basis — with the cache
    behind a block table instead of a dense per-slot buffer.
    """
    B, d = u.shape
    D = k_pool.shape[4]

    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops

        return kops.decode_kernel("paged", "merged")(
            u, k_pool, v_pool, block_tables=block_tables,
            q_position=q_position, layer=layer, n_kv_heads=n_kv_heads,
            sliding_window=sliding_window,
            interpret=(impl == "pallas_interpret"))

    out = decode_attention_core_paged(
        u.reshape(B, d // D, D), k_pool, v_pool, block_tables=block_tables,
        q_position=q_position, layer=layer, sliding_window=sliding_window,
        impl=impl)
    return out.reshape(B, d)


# ---------------------------------------------------------------------------
# quantized (paged_q8) decode cores: int8 pools + per-(page, head) scales
# ---------------------------------------------------------------------------

def _paged_gather_q8(pool: jnp.ndarray, scale: jnp.ndarray,
                     block_tables: jnp.ndarray, layer,
                     out_dtype) -> jnp.ndarray:
    """Densify + dequantize a slot's logical view of layer ``layer`` of
    an int8 pool: (L, NB, bs, Hkv, D) int8 + (L, NB, Hkv) f32 scales +
    (B, MB) tables -> (B, MB*bs, Hkv, D) ``out_dtype``.  Unmapped blocks
    gather page 0 (masked to -1 positions by callers, as in
    ``_paged_gather``)."""
    B, MB = block_tables.shape
    bt = jnp.maximum(block_tables, 0)
    g = pool[layer, bt].astype(jnp.float32)  # (B, MB, bs, Hkv, D)
    g = g * scale[layer, bt][:, :, None, :, None]
    return g.reshape(B, MB * pool.shape[2], *pool.shape[3:]).astype(out_dtype)


def decode_attention_core_paged_q8(
    q: jnp.ndarray,  # (B, Hq, D)
    k_pool: jnp.ndarray,  # (L, NB, bs, Hkv, D) int8 page pool
    v_pool: jnp.ndarray,  # (L, NB, bs, Hkv, D) int8
    k_scale: jnp.ndarray,  # (L, NB, Hkv) float32 per-(page, head) scales
    v_scale: jnp.ndarray,  # (L, NB, Hkv) float32
    *,
    block_tables: jnp.ndarray,  # (B, MB) int32 page ids, -1 unmapped
    q_position: jnp.ndarray,  # (B,) int32
    layer,  # int32 scalar: the layer of the stack attended
    sliding_window: int = 0,
    impl: str = "xla",
) -> jnp.ndarray:
    """One-token attention against an int8 paged pool -> (B, Hq, D).

    Pallas hands pools + scales to the in-kernel-dequant paged kernel (the
    full-precision view never exists); XLA densifies the slot's logical
    view dequantized page-by-page and defers to the dense core, mirroring
    ``decode_attention_core_paged``.
    """
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops

        return kops.decode_kernel("paged_q8", "generic")(
            q, k_pool, v_pool, k_scale=k_scale, v_scale=v_scale,
            block_tables=block_tables, q_position=q_position, layer=layer,
            sliding_window=sliding_window,
            interpret=(impl == "pallas_interpret"))

    bs = k_pool.shape[2]
    ring = paged_ring_active(sliding_window, bs, block_tables.shape[1])
    return decode_attention_core_positions(
        q, _paged_gather_q8(k_pool, k_scale, block_tables, layer, q.dtype),
        _paged_gather_q8(v_pool, v_scale, block_tables, layer, q.dtype),
        kv_positions=paged_kv_positions(block_tables, bs, q_position, ring),
        q_position=q_position, sliding_window=sliding_window, impl=impl)


def decode_attention_core_paged_q8_merged(
    u: jnp.ndarray,  # (B, d_model) — RoPE'd residual stream (merged query)
    k_pool: jnp.ndarray,  # (L, NB, bs, Hkv, D) int8 K* page pool
    v_pool: jnp.ndarray,  # (L, NB, bs, Hkv, D) int8 V* page pool
    k_scale: jnp.ndarray,  # (L, NB, Hkv) float32 per-(page, head) scales
    v_scale: jnp.ndarray,  # (L, NB, Hkv) float32
    *,
    block_tables: jnp.ndarray,  # (B, MB) int32 page ids, -1 unmapped
    q_position: jnp.ndarray,  # (B,) int32
    layer,  # int32 scalar: the layer of the stack attended
    n_kv_heads: int,
    sliding_window: int = 0,
    impl: str = "xla",
) -> jnp.ndarray:
    """Merged (Q/P-removed) decode attention over an int8 paged pool —
    the paper's serving fast path at a quarter of the page-pool HBM
    traffic.  Contract as ``decode_attention_core_paged_merged``."""
    B, d = u.shape
    D = k_pool.shape[4]

    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops

        return kops.decode_kernel("paged_q8", "merged")(
            u, k_pool, v_pool, k_scale=k_scale, v_scale=v_scale,
            block_tables=block_tables, q_position=q_position, layer=layer,
            n_kv_heads=n_kv_heads, sliding_window=sliding_window,
            interpret=(impl == "pallas_interpret"))

    out = decode_attention_core_paged_q8(
        u.reshape(B, d // D, D), k_pool, v_pool, k_scale, v_scale,
        block_tables=block_tables, q_position=q_position, layer=layer,
        sliding_window=sliding_window, impl=impl)
    return out.reshape(B, d)

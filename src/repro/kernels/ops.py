"""jit'd wrappers exposing the Pallas kernels in model-layer layouts.

These adapt model tensors to the kernels' block layouts (grouped-head and
lane-column views, see the kernel modules' TPU block rule), pick block
sizes the TPU can tile, and fail loudly (assert) rather than silently when
an unsupported configuration is requested.  On TPU the calls compile to
Mosaic; ``interpret=True`` runs the same kernel bodies in Python, which is
how the CPU test suite validates them.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import (flash_attention_bhsd,
                                           flash_attention_merged_bsd)
from repro.kernels.decode_attention import (decode_attention_bsd,
                                            decode_attention_paged_bsd)
from repro.kernels.paging import paged_ring_active
from repro.kernels.ssd_scan import ssd_scan_pallas


def _pick_block(S: int, target: int) -> int:
    """Largest divisor of S that is <= target and a multiple of 8 (the TPU
    sublane tile), else S itself — a block may always span its dim."""
    for b in range(min(target, S), 7, -1):
        if S % b == 0 and b % 8 == 0:
            return b
    return S


def _grouped(x: jnp.ndarray, n_kv_heads: int) -> jnp.ndarray:
    """(B, Hq, D) query heads -> (B, Hkv, G, D): kv head h owns query
    heads [h·G, (h+1)·G) (a free row-major reshape)."""
    return x.reshape(x.shape[0], n_kv_heads, -1, x.shape[-1])


@partial(jax.jit, static_argnames=("causal", "sliding_window", "interpret",
                                   "block_q", "block_k"))
def flash_attention(
    q: jnp.ndarray,  # (B, Sq, Hq, D)
    k: jnp.ndarray,  # (B, Sk, Hkv, D)
    v: jnp.ndarray,  # (B, Sk, Hkv, D)
    *,
    q_positions=None,  # accepted for API parity; kernel assumes arange
    kv_positions=None,
    causal: bool = True,
    sliding_window: int = 0,
    kv_valid=None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    assert kv_valid is None, "flash kernel: use the decode kernel for padded caches"
    B, Sq, Hq, D = q.shape
    Sk = k.shape[1]
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    out = flash_attention_bhsd(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=causal, sliding_window=sliding_window,
        block_q=bq, block_k=bk, interpret=interpret)
    return out.transpose(0, 2, 1, 3)  # back to (B, Sq, Hq, D)


@partial(jax.jit, static_argnames=("n_kv_heads", "causal", "sliding_window",
                                   "interpret", "block_q", "block_k"))
def flash_attention_merged(
    u: jnp.ndarray,  # (B, Sq, d_model) — RoPE'd residual stream = merged query
    k: jnp.ndarray,  # (B, Sk, Hkv, D) — K*, native layout
    v: jnp.ndarray,  # (B, Sk, Hkv, D) — V*, native layout
    *,
    n_kv_heads: int,
    q_positions=None,  # accepted for API parity; kernel assumes arange
    kv_positions=None,
    causal: bool = True,
    sliding_window: int = 0,
    kv_valid=None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Merged (Q/P-removed) flash PREFILL -> (B, Sq, d_model) FFN-input
    stream.

    No q projection exists in merged configs, so the stream is handed to
    the kernel directly — the (B, Sq, Hq, D) view is a bitcast — and
    K*/V* are consumed in their native sequence-major layout: none of the
    four head-major transposes of the generic ``flash_attention`` wrapper
    appear in the program.
    """
    assert kv_valid is None, "flash kernel: use the decode kernel for padded caches"
    B, Sq, d = u.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    assert Hkv == n_kv_heads, (Hkv, n_kv_heads)
    D = k.shape[3]
    assert d % D == 0 and (d // D) % Hkv == 0, (d, D, Hkv)
    return flash_attention_merged_bsd(
        u, k.reshape(B, Sk, Hkv * D), v.reshape(B, Sk, Hkv * D), d_head=D,
        causal=causal, sliding_window=sliding_window,
        block_q=_pick_block(Sq, block_q), block_k=_pick_block(Sk, block_k),
        interpret=interpret)


@partial(jax.jit, static_argnames=("sliding_window", "interpret", "block_k"))
def decode_attention(
    q: jnp.ndarray,  # (B, Hq, D)
    k_cache: jnp.ndarray,  # (B, S, Hkv, D)
    v_cache: jnp.ndarray,  # (B, S, Hkv, D)
    *,
    kv_positions: jnp.ndarray,  # (B, S) int32, -1 empty
    q_position: jnp.ndarray,  # (B,) int32
    sliding_window: int = 0,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    out = decode_attention_bsd(
        _grouped(q, Hkv), k_cache, v_cache, kv_positions, q_position,
        sliding_window=sliding_window, block_k=_pick_block(S, block_k),
        interpret=interpret)
    return out.reshape(q.shape)


@partial(jax.jit, static_argnames=("n_kv_heads", "sliding_window", "interpret",
                                   "block_k"))
def decode_attention_merged(
    u: jnp.ndarray,  # (B, d_model) — RoPE'd residual stream = merged query
    k_cache: jnp.ndarray,  # (B, S, Hkv, D) — K*, native serving layout
    v_cache: jnp.ndarray,  # (B, S, Hkv, D) — V*, native layout
    *,
    kv_positions: jnp.ndarray,  # (B, S) int32, -1 empty
    q_position: jnp.ndarray,  # (B,) int32
    n_kv_heads: int,
    sliding_window: int = 0,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Merged (Q/P-removed) decode fast path -> (B, d_model) FFN-input stream.

    No q projection exists in merged configs, so the stream is handed to
    the kernel directly — the grouped-head view is a bitcast, and the
    cache is consumed untransposed (the same kernel as
    ``decode_attention``: in this layout the two differ only in where the
    query came from).
    """
    B, d = u.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    assert Hkv == n_kv_heads, (Hkv, n_kv_heads)
    D = k_cache.shape[3]
    assert d % D == 0 and (d // D) % Hkv == 0, (d, D, Hkv)
    out = decode_attention_bsd(
        _grouped(u.reshape(B, d // D, D), Hkv), k_cache, v_cache,
        kv_positions, q_position, sliding_window=sliding_window,
        block_k=_pick_block(S, block_k), interpret=interpret)
    return out.reshape(B, d)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_jit(x, dt, a, Bm, Cm, chunk, interpret):
    return ssd_scan_pallas(x, dt, a, Bm, Cm, chunk=chunk, interpret=interpret)


def ssd_scan(
    x: jnp.ndarray,  # (B, S, H, P)
    dt: jnp.ndarray,  # (B, S, H) post-softplus
    A: jnp.ndarray,  # (H,) negative
    Bm: jnp.ndarray,  # (B, S, H, N)
    Cm: jnp.ndarray,  # (B, S, H, N)
    *,
    chunk: int,
    D: Optional[jnp.ndarray] = None,
    init_state=None,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    assert init_state is None, (
        "pallas ssd kernel starts from zero state; use impl='xla' for "
        "mid-sequence continuation")
    a = (dt.astype(jnp.float32) * A.astype(jnp.float32)).astype(jnp.float32)
    y, fin = _ssd_jit(x, dt.astype(jnp.float32), a, Bm, Cm,
                      chunk=min(chunk, x.shape[1]), interpret=interpret)
    if D is not None:
        y = y + D[None, None, :, None].astype(jnp.float32) * x.astype(jnp.float32)
    return y.astype(x.dtype), fin


@partial(jax.jit, static_argnames=("sliding_window", "interpret"))
def decode_attention_paged(
    q: jnp.ndarray,  # (B, Hq, D)
    k_pool: jnp.ndarray,  # (NB, bs, Hkv, D) — physical page pool
    v_pool: jnp.ndarray,  # (NB, bs, Hkv, D)
    *,
    block_tables: jnp.ndarray,  # (B, MB) int32 page ids, -1 unmapped
    q_position: jnp.ndarray,  # (B,) int32
    sliding_window: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Generic decode attention over a paged KV pool (block-table gather).

    Ring addressing (windowed tables bounded at ceil(window/bs)+1 recycled
    slots) is derived from the static window and the table width — see
    ``kernels.paging`` — so callers never thread a ring flag."""
    ring = paged_ring_active(sliding_window, k_pool.shape[1],
                             block_tables.shape[1])
    out = decode_attention_paged_bsd(
        _grouped(q, k_pool.shape[2]), k_pool, v_pool, block_tables,
        q_position, sliding_window=sliding_window, ring_blocks=ring,
        interpret=interpret)
    return out.reshape(q.shape)


@partial(jax.jit, static_argnames=("n_kv_heads", "sliding_window",
                                   "interpret"))
def decode_attention_paged_merged(
    u: jnp.ndarray,  # (B, d_model) — RoPE'd residual stream = merged query
    k_pool: jnp.ndarray,  # (NB, bs, Hkv, D) — K* page pool, native layout
    v_pool: jnp.ndarray,  # (NB, bs, Hkv, D) — V* page pool
    *,
    block_tables: jnp.ndarray,  # (B, MB) int32 page ids, -1 unmapped
    q_position: jnp.ndarray,  # (B,) int32
    n_kv_heads: int,
    sliding_window: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Merged (Q/P-removed) decode fast path over a paged KV pool.  Ring
    addressing derived as in ``decode_attention_paged``."""
    B, d = u.shape
    Hkv, D = k_pool.shape[2], k_pool.shape[3]
    assert Hkv == n_kv_heads, (Hkv, n_kv_heads)
    assert d % D == 0 and (d // D) % Hkv == 0, (d, D, Hkv)
    ring = paged_ring_active(sliding_window, k_pool.shape[1],
                             block_tables.shape[1])
    out = decode_attention_paged_bsd(
        _grouped(u.reshape(B, d // D, D), Hkv), k_pool, v_pool, block_tables,
        q_position, sliding_window=sliding_window, ring_blocks=ring,
        interpret=interpret)
    return out.reshape(B, d)


# ---------------------------------------------------------------------------
# quantized (paged_q8) wrappers: int8 pools + per-(page, head) scales
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("sliding_window", "interpret"))
def decode_attention_paged_q8(
    q: jnp.ndarray,  # (B, Hq, D)
    k_pool: jnp.ndarray,  # (NB, bs, Hkv, D) int8 page pool
    v_pool: jnp.ndarray,  # (NB, bs, Hkv, D) int8
    *,
    k_scale: jnp.ndarray,  # (NB, Hkv) float32 per-(page, head) scales
    v_scale: jnp.ndarray,  # (NB, Hkv) float32
    block_tables: jnp.ndarray,  # (B, MB) int32 page ids, -1 unmapped
    q_position: jnp.ndarray,  # (B,) int32
    sliding_window: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Generic decode attention over an int8 paged pool — the q8 face of
    ``decode_attention_paged``: same block-table gather and ring
    derivation, with the gathered page dequantized inside the kernel from
    its scalar-prefetched scale."""
    ring = paged_ring_active(sliding_window, k_pool.shape[1],
                             block_tables.shape[1])
    out = decode_attention_paged_bsd(
        _grouped(q, k_pool.shape[2]), k_pool, v_pool, block_tables,
        q_position, k_scale=k_scale, v_scale=v_scale,
        sliding_window=sliding_window, ring_blocks=ring, interpret=interpret)
    return out.reshape(q.shape)


@partial(jax.jit, static_argnames=("n_kv_heads", "sliding_window",
                                   "interpret"))
def decode_attention_paged_q8_merged(
    u: jnp.ndarray,  # (B, d_model) — RoPE'd residual stream = merged query
    k_pool: jnp.ndarray,  # (NB, bs, Hkv, D) int8 K* page pool
    v_pool: jnp.ndarray,  # (NB, bs, Hkv, D) int8 V* page pool
    *,
    k_scale: jnp.ndarray,  # (NB, Hkv) float32 per-(page, head) scales
    v_scale: jnp.ndarray,  # (NB, Hkv) float32
    block_tables: jnp.ndarray,  # (B, MB) int32 page ids, -1 unmapped
    q_position: jnp.ndarray,  # (B,) int32
    n_kv_heads: int,
    sliding_window: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Merged (Q/P-removed) decode fast path over an int8 paged pool."""
    B, d = u.shape
    Hkv, D = k_pool.shape[2], k_pool.shape[3]
    assert Hkv == n_kv_heads, (Hkv, n_kv_heads)
    assert d % D == 0 and (d // D) % Hkv == 0, (d, D, Hkv)
    ring = paged_ring_active(sliding_window, k_pool.shape[1],
                             block_tables.shape[1])
    out = decode_attention_paged_bsd(
        _grouped(u.reshape(B, d // D, D), Hkv), k_pool, v_pool, block_tables,
        q_position, k_scale=k_scale, v_scale=v_scale,
        sliding_window=sliding_window, ring_blocks=ring, interpret=interpret)
    return out.reshape(B, d)


@partial(jax.jit, static_argnames=("n_kv_heads", "causal", "sliding_window",
                                   "interpret", "block_q", "block_k"))
def flash_attention_merged_q8(
    u: jnp.ndarray,  # (B, Sq, d_model) — RoPE'd residual stream = merged query
    k: jnp.ndarray,  # (B, Sk, Hkv, D) int8 — K* at pool quantization
    v: jnp.ndarray,  # (B, Sk, Hkv, D) int8 — V*
    *,
    k_scale: jnp.ndarray,  # (B, Sk // sg, Hkv) float32 per-(page, head)
    v_scale: jnp.ndarray,  # (B, Sk // sg, Hkv) float32
    n_kv_heads: int,
    q_positions=None,  # accepted for API parity; kernel assumes arange
    kv_positions=None,
    causal: bool = True,
    sliding_window: int = 0,
    kv_valid=None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Merged (Q/P-removed) flash PREFILL over int8 K*/V* — the q8 face of
    ``flash_attention_merged``; dequant happens tile-by-tile inside the
    kernel (no full-precision K/V buffer in the program).  The per-page
    scales are expanded to per-key rows here (Sk·Hkv floats, noise beside
    the int8 K/V), so the kv block need not align to pages."""
    assert kv_valid is None, "flash kernel: use the decode kernel for padded caches"
    B, Sq, d = u.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    assert Hkv == n_kv_heads, (Hkv, n_kv_heads)
    D = k.shape[3]
    assert d % D == 0 and (d // D) % Hkv == 0, (d, D, Hkv)
    sg = Sk // k_scale.shape[1]  # serving page size: scale granularity
    assert sg * k_scale.shape[1] == Sk, (Sk, k_scale.shape)
    return flash_attention_merged_bsd(
        u, k.reshape(B, Sk, Hkv * D), v.reshape(B, Sk, Hkv * D), d_head=D,
        k_scale=jnp.repeat(k_scale, sg, axis=1),
        v_scale=jnp.repeat(v_scale, sg, axis=1),
        causal=causal, sliding_window=sliding_window,
        block_q=_pick_block(Sq, block_q), block_k=_pick_block(Sk, block_k),
        interpret=interpret)


# ---------------------------------------------------------------------------
# attention-kernel table: the kernel-layer face of the serving backend
# registries (models.backends' AttentionBackend AND PrefillBackend)
# ---------------------------------------------------------------------------

# keyed (phase, cache_kind, style) — like models.backends plus the phase
# axis, minus the impl axis (every wrapper here IS the pallas route;
# ``interpret=True`` is the CPU-validation mode of the same kernel).
# models.attention's cores fetch their pallas path here, so "which (phase ×
# cache layout × projection style) combos have a fused kernel" is read off
# one table instead of eight call sites.  Prefill COMPUTE is cache-kind-
# independent — paging changes where the collected KV is written (see
# ``models.transformer``'s paged prefill backend), not the attention math —
# so both prefill cache kinds map to the same flash wrapper.
ATTENTION_KERNELS = {
    ("decode", "dense", "generic"): decode_attention,
    ("decode", "dense", "merged"): decode_attention_merged,
    ("decode", "paged", "generic"): decode_attention_paged,
    ("decode", "paged", "merged"): decode_attention_paged_merged,
    ("prefill", "dense", "generic"): flash_attention,
    ("prefill", "dense", "merged"): flash_attention_merged,
    ("prefill", "paged", "generic"): flash_attention,
    ("prefill", "paged", "merged"): flash_attention_merged,
    # q8: decode dequantizes pool pages in-kernel; merged prefill
    # dequantizes fake-quantized kv tiles in-kernel; the generic q8
    # prefill dequantizes upstream (models.transformer) and rides the
    # plain flash kernel.
    ("decode", "paged_q8", "generic"): decode_attention_paged_q8,
    ("decode", "paged_q8", "merged"): decode_attention_paged_q8_merged,
    ("prefill", "paged_q8", "generic"): flash_attention,
    ("prefill", "paged_q8", "merged"): flash_attention_merged_q8,
}


def attention_kernel(phase: str, cache_kind: str, style: str):
    """Pallas attention kernel wrapper for one (phase, cache_kind, style)
    combo; unknown combos raise KeyError naming the registered ones."""
    try:
        return ATTENTION_KERNELS[(phase, cache_kind, style)]
    except KeyError:
        raise KeyError(
            f"no Pallas attention kernel for (phase={phase!r}, "
            f"cache_kind={cache_kind!r}, style={style!r}); available: "
            f"{sorted(ATTENTION_KERNELS)}") from None


# backward-compatible decode view of the unified table
DECODE_KERNELS = {(ck, st): fn for (ph, ck, st), fn in ATTENTION_KERNELS.items()
                  if ph == "decode"}


def decode_kernel(cache_kind: str, style: str):
    """Pallas decode kernel wrapper for one (cache_kind, style) combo;
    unknown combos raise KeyError naming the registered ones.  (The decode
    face of ``attention_kernel`` — kept for existing callers.)"""
    try:
        return DECODE_KERNELS[(cache_kind, style)]
    except KeyError:
        raise KeyError(
            f"no Pallas decode kernel for (cache_kind={cache_kind!r}, "
            f"style={style!r}); available: {sorted(DECODE_KERNELS)}") from None

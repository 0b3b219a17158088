"""Shared int8 (q8) KV quantization helpers for the ``paged_q8`` cache kind.

Layout contract (mirrors the fp paged pool, ``serving.paged_kv_cache``):
  * pools are int8 pages (…, NB, bs, Hkv, Dh);
  * every (page, kv-head) pair owns ONE float32 scale — the scale arrays
    are (…, NB, Hkv) and travel with their page through CoW / recycle;
  * dequant is ``ints * scale`` (symmetric, zero-point-free: RoPE'd K and
    V are zero-centred, and a zero-point would break the "unwritten page
    dequantizes to exactly 0" property the causal mask relies on).

Determinism contract: quantize-on-write runs in plain XLA inside every
impl's program (xla / pallas / pallas_interpret share it), so the pool
BITS are impl-independent; only the dequantizing attention read differs
per impl.  Whole-prompt prefill, chunked prefill, and the in-attention
fake-quant all route through ``q8_quantize_pages`` on identically masked
inputs, which is what makes prefill attention see bit-exactly what decode
later reads back from the pool.

Decode appends use a MONOTONE per-page scale merge (``q8_append_token``):
the page scale only grows while the page is live, so already-stored
tokens are only ever rescaled by a ratio <= 1 and tokens quantized while
the scale was already final are bit-stable.  A page's scale resets when
decode enters it at offset 0 (fresh/recycled pages hold stale garbage —
content AND scale — that the causal mask hides).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Q8_MAX = 127.0
# floor for every stored scale: keeps ratio/quantize divisions finite on
# all-zero blocks without changing their (all-zero) quantized content
Q8_EPS = 1e-8


def q8_quantize_pages(blocks: jnp.ndarray):
    """Quantize block-shaped KV: (..., nbk, bs, Hkv, D) float ->
    ((..., nbk, bs, Hkv, D) int8, (..., nbk, Hkv) float32 scales).

    One scale per (block, kv head) = absmax/127 over the block's (bs, D)
    entries — exactly the pool's scale granularity."""
    x = blocks.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=(-3, -1))  # (..., nbk, Hkv)
    scale = jnp.maximum(amax / Q8_MAX, Q8_EPS)
    ints = jnp.clip(jnp.round(x / scale[..., :, None, :, None]),
                    -Q8_MAX, Q8_MAX).astype(jnp.int8)
    return ints, scale


def q8_quantize_seq(kv: jnp.ndarray, block_size: int, valid=None):
    """Quantize a sequence-major KV tensor at pool granularity.

    kv (B, S, Hkv, D) float with S % block_size == 0; ``valid`` (B, S)
    bool masks bucket padding / positions >= true_len to zero BEFORE the
    per-block absmax, so padding garbage never inflates a real block's
    scale (and the resulting bits match what ``_finish_paged_q8`` /
    chunked writes store, which mask identically).
    Returns ((B, S, Hkv, D) int8, (B, S//block_size, Hkv) float32)."""
    B, S, Hkv, D = kv.shape
    x = kv.astype(jnp.float32)
    if valid is not None:
        x = jnp.where(valid[..., None, None], x, 0.0)
    nbk = S // block_size
    ints, scale = q8_quantize_pages(x.reshape(B, nbk, block_size, Hkv, D))
    return ints.reshape(B, S, Hkv, D), scale


def q8_dequant_seq(ints: jnp.ndarray, scale: jnp.ndarray, out_dtype):
    """Inverse of ``q8_quantize_seq``: (B, S, Hkv, D) int8 +
    (B, nbk, Hkv) scales -> (B, S, Hkv, D) ``out_dtype``."""
    B, S, Hkv, D = ints.shape
    bs = S // scale.shape[1]
    s = jnp.repeat(scale, bs, axis=1)  # (B, S, Hkv)
    return (ints.astype(jnp.float32) * s[..., None]).astype(out_dtype)


def q8_append_token(pool: jnp.ndarray, scale: jnp.ndarray,
                    new_tok: jnp.ndarray, safe: jnp.ndarray,
                    off: jnp.ndarray, layer):
    """Quantize-on-write of one decode token per batch slot into layer
    ``layer`` of the stacked pool, in place.

    pool (L, NB, bs, Hkv, D) int8, scale (L, NB, Hkv) f32, new_tok (B,
    Hkv, D) float, safe (B,) physical page (== NB drops the write —
    unmapped slot), off (B,) in-page offset.  Monotone scale merge: at
    off == 0 the page is being (re-)entered — fresh alloc, ring recycle,
    or detach — so its stale scale is ignored and reset from this token;
    at off > 0 the page's live prefix was written by prefill/chunk/
    earlier decode steps under a valid scale, which only GROWS
    (new = max(old, tok)), with the stored ints rescaled by old/new <= 1
    when it does (a no-op round when it does not)."""
    NB = pool.shape[1]
    read = jnp.minimum(safe, NB - 1)  # in-range gather; dropped writes
    newf = new_tok.astype(jnp.float32)  # (B, Hkv, D)
    tok_scale = jnp.maximum(jnp.max(jnp.abs(newf), axis=-1) / Q8_MAX, Q8_EPS)
    old = scale[layer, read]  # (B, Hkv)
    fresh = (off == 0)[:, None]  # (B, 1) — first write of this page
    base = jnp.where(fresh, Q8_EPS, old)
    new_scale = jnp.maximum(base, tok_scale)
    page = pool[layer, read].astype(jnp.float32)  # (B, bs, Hkv, D)
    ratio = jnp.where(fresh, 1.0, base / new_scale)  # <= 1; fresh skips
    page = jnp.clip(jnp.round(page * ratio[:, None, :, None]),
                    -Q8_MAX, Q8_MAX)
    tok_q = jnp.clip(jnp.round(newf / new_scale[..., None]), -Q8_MAX, Q8_MAX)
    page = jax.vmap(
        lambda pg, t, o: jax.lax.dynamic_update_slice(pg, t[None], (o, 0, 0))
    )(page, tok_q, off)
    pool = pool.at[layer, safe].set(page.astype(jnp.int8), mode="drop")
    scale = scale.at[layer, safe].set(new_scale, mode="drop")
    return pool, scale

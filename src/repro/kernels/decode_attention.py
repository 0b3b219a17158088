"""Single-token GQA decode attention vs a dense KV cache or a paged pool.

Flash-decoding adapted to TPU: grid = (batch, kv_blocks); the kv block
axis is sequential ("arbitrary") and carries the online-softmax state of
all Hq = Hkv·G query heads in VMEM scratch.  Each grid step DMAs ONE kv
block holding every kv head — a (bk, Hkv, D) stretch of the cache's
native (B, S, Hkv, D) layout, or one physical (bs, Hkv, D) page of a
(NB, bs, Hkv, D) pool — and updates the G query heads of each kv head in
turn.  Cache validity/causality/sliding-window are evaluated from
positions (−1 = empty slot); the kernel is layout-agnostic about rings.

One kernel serves both projection styles.  The query arrives grouped as
(B, Hkv, G, D): a separately projected q (generic), or the RoPE'd
residual stream viewed as heads — the paper's merged (Q/P-removed)
serving path, where d_model = Hq·D and the (B, d_model) stream IS the
query (Fig 1b) and the output lands straight in the FFN-input basis.  In
this layout the two differ only in where q came from, so the cache is
read untransposed either way.

TPU block rule (shared with ``flash_attention`` and ``ssd_scan``): the
last two dims of every block are whole array dims — (G, D) of the query,
(Hkv, D) of a cache row or page — and per-position vectors travel as
(1, n) rows of a (-1, 1, n) view.  Scalars (query positions, block
tables, page scales) are scalar-prefetch operands in SMEM.

Paged variant (``decode_attention_paged_bsd``): each slot owns a
per-request block table (B, MB) of physical page ids (-1 = unmapped).
The sequential kv axis of the grid walks LOGICAL blocks; the block table
is a scalar-prefetch operand so the k/v BlockSpec index_maps gather the
mapped physical page (clamped to page 0 when unmapped — the in-kernel
mask zeroes those scores).  kv positions are not stored: with absolute
addressing logical block j covers positions [j·bs, (j+1)·bs); with ring
addressing (``ring_blocks`` > 0 — sliding-window tables bounded at
ceil(window/bs)+1 recycled slots, see ``kernels.paging``) slot j holds
the latest absolute block ≡ j (mod ring) not beyond the query's block,
so the kernel reconstructs positions from the grid index and the query
position.  With ``k_scale``/``v_scale`` the pool holds int8 pages with
one float32 scale per (page, kv head) (``kernels.quant``): the scales
ride along as extra scalar-prefetch operands and each (bs, D) tile is
dequantized in VMEM, so no full-precision pool ever exists in HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _online_softmax_block(ik, q, k, v, kpos, qpos, m_scr, l_scr, acc_scr,
                          *, scale: float, window: int):
    """Shared flash-decoding state update of one kv head for one kv block.

    ``q`` (G, D) and ``k``/``v`` (bk, D) are already sliced from the block
    layout, ``kpos`` is the (1, bk) row of the block's positions; the
    m/l/acc scratch carries the online-softmax state across the
    sequential kv-block axis.
    """
    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qf = q.astype(jnp.float32) * scale  # (G, D)
    s = jax.lax.dot_general(qf, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bk)

    ok = (kpos >= 0) & (kpos <= qpos)
    if window > 0:
        ok &= qpos - kpos < window
    mask = jnp.broadcast_to(ok, s.shape)
    s = jnp.where(mask, s, NEG)

    m_prev = m_scr[:, :1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_next = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.where(mask, jnp.exp(s - m_next), 0.0)

    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p, v.astype(jnp.float32), preferred_element_type=jnp.float32)
    l_scr[:, :1] = l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)


def _attend_all_heads(ik, nk, q_ref, k_ref, v_ref, o_ref, kpos, qpos,
                      m_scr, l_scr, acc_scr, *, scale: float, window: int,
                      dequant=None):
    """Run the online-softmax update for every kv head of one block, and
    write the normalised output after the last block.

    ``q_ref`` (1, Hkv, G, D), ``k_ref``/``v_ref`` (1, bk, Hkv, D); kv head
    h reads the strided (bk, D) slice ``[0, :, h]``.  ``dequant(h, k, v)``
    (int8 pools) scales the slices of kv head h."""
    n_kv = k_ref.shape[2]
    for h in range(n_kv):
        k = k_ref[0, :, h].astype(jnp.float32)
        v = v_ref[0, :, h].astype(jnp.float32)
        if dequant is not None:
            k, v = dequant(h, k, v)
        _online_softmax_block(ik, q_ref[0, h], k, v, kpos, qpos,
                              m_scr.at[h], l_scr.at[h], acc_scr.at[h],
                              scale=scale, window=window)

    @pl.when(ik == nk - 1)
    def _finish():
        denom = l_scr[:, :, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _scratch(n_kv: int, G: int, D: int):
    return [pltpu.VMEM((n_kv, G, 128), jnp.float32),
            pltpu.VMEM((n_kv, G, 128), jnp.float32),
            pltpu.VMEM((n_kv, G, D), jnp.float32)]


def _decode_kernel(qpos_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, window: int,
                   nk: int):
    b = pl.program_id(0)
    ik = pl.program_id(1)
    _attend_all_heads(ik, nk, q_ref, k_ref, v_ref, o_ref, kpos_ref[0],
                      qpos_ref[b], m_scr, l_scr, acc_scr,
                      scale=scale, window=window)


def decode_attention_bsd(
    q: jnp.ndarray,  # (B, Hkv, G, D) — grouped query heads (or stream view)
    k: jnp.ndarray,  # (B, S, Hkv, D) — cache, NATIVE serving layout
    v: jnp.ndarray,  # (B, S, Hkv, D)
    kv_positions: jnp.ndarray,  # (B, S) int32; -1 marks empty slots
    q_position: jnp.ndarray,  # (B,) int32
    *,
    sliding_window: int = 0,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Decode attention against a dense per-slot cache -> (B, Hkv, G, D)."""
    B, Hkv, G, D = q.shape
    S = k.shape[1]
    bk = min(block_k, S)
    assert S % bk == 0, (S, bk)
    nk = S // bk
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_decode_kernel, scale=scale,
                               window=sliding_window, nk=nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), lambda b, j, qp: (b, 0, 0, 0)),
            pl.BlockSpec((1, bk, Hkv, D), lambda b, j, qp: (b, j, 0, 0)),
            pl.BlockSpec((1, bk, Hkv, D), lambda b, j, qp: (b, j, 0, 0)),
            # positions as (1, bk) rows of a (B·nk, 1, bk) view
            pl.BlockSpec((1, 1, bk), lambda b, j, qp, nk=nk: (b * nk + j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, D), lambda b, j, qp: (b, 0, 0, 0)),
        scratch_shapes=_scratch(Hkv, G, D),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention",
    )(q_position.astype(jnp.int32), q, k, v,
      kv_positions.astype(jnp.int32).reshape(B * nk, 1, bk))


# ---------------------------------------------------------------------------
# paged variant: block-table gather over a physical page pool
# ---------------------------------------------------------------------------

def _paged_kpos(block_id, j, bs, qpos, ring):
    """(1, bs) positions covered by table slot ``j`` (-1 if unmapped).

    Absolute addressing (``ring`` = 0): slot j IS logical block j.  Ring
    addressing: slot j holds the latest absolute block ≡ j (mod ring) the
    request has entered — reconstructed from the query's block ``lb``;
    never-entered slots (b < 0) are unmapped anyway but masked for safety.
    2D iota: TPU vector units have no 1D iota."""
    off = jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    if ring:
        lb = qpos // bs
        b = lb - ((lb + ring - j) % ring)
        return jnp.where((block_id >= 0) & (b >= 0), b * bs + off, -1)
    return jnp.where(block_id >= 0, j * bs + off, -1)


def _paged_kernel(*refs, scale: float, window: int, bs: int, nb: int,
                  ring: int, quantized: bool):
    if quantized:
        (bt_ref, qpos_ref, ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (bt_ref, qpos_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    qpos = qpos_ref[b]
    dequant = None
    if quantized:
        n_kv = k_ref.shape[2]
        row = jnp.maximum(bt_ref[b, j], 0) * n_kv  # the gathered page's scales

        def dequant(h, k, v):
            return k * ks_ref[row + h], v * vs_ref[row + h]

    _attend_all_heads(j, nb, q_ref, k_ref, v_ref, o_ref,
                      _paged_kpos(bt_ref[b, j], j, bs, qpos, ring), qpos,
                      m_scr, l_scr, acc_scr, scale=scale, window=window,
                      dequant=dequant)


def decode_attention_paged_bsd(
    q: jnp.ndarray,  # (B, Hkv, G, D) — grouped query heads (or stream view)
    k_pool: jnp.ndarray,  # (NB, bs, Hkv, D) — physical page pool (int8 if q8)
    v_pool: jnp.ndarray,  # (NB, bs, Hkv, D)
    block_tables: jnp.ndarray,  # (B, MB) int32 physical page ids; -1 unmapped
    q_position: jnp.ndarray,  # (B,) int32
    *,
    k_scale=None,  # (NB, Hkv) float32 per-(page, head) scales — int8 pools
    v_scale=None,  # (NB, Hkv) float32
    sliding_window: int = 0,
    ring_blocks: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged decode -> (B, Hkv, G, D): the kv-block axis walks the slot's
    block table and gathers one physical page (all kv heads) per step.
    The pool keeps the serving cache's native (…, bs, Hkv, D) page layout
    — pages are written once at append time and never transposed.
    ``ring_blocks`` > 0 means the table is ring-addressed (windowed
    requests recycle pages; see ``kernels.paging``) and slot positions are
    reconstructed from the query position.  ``k_scale``/``v_scale`` switch
    to an int8 pool dequantized per tile in VMEM."""
    B, Hkv, G, D = q.shape
    NB, bs = k_pool.shape[0], k_pool.shape[1]
    MB = block_tables.shape[1]
    quantized = k_scale is not None
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_paged_kernel, scale=scale,
                               window=sliding_window, bs=bs, nb=MB,
                               ring=ring_blocks, quantized=quantized)

    def page(b, j, bt, *_):  # physical page for logical block j of slot b
        return (jnp.maximum(bt[b, j], 0), 0, 0, 0)

    def head(b, j, *_):
        return (b, 0, 0, 0)

    prefetch = [block_tables.astype(jnp.int32), q_position.astype(jnp.int32)]
    if quantized:  # flat (NB·Hkv,) scale vectors: page-major, like the pool
        prefetch += [k_scale.astype(jnp.float32).reshape(-1),
                     v_scale.astype(jnp.float32).reshape(-1)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, MB),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), head),
            pl.BlockSpec((1, bs, Hkv, D), page),
            pl.BlockSpec((1, bs, Hkv, D), page),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, D), head),
        scratch_shapes=_scratch(Hkv, G, D),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention_paged_q8" if quantized
        else "decode_attention_paged",
    )(*prefetch, q, k_pool, v_pool)

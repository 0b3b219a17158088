"""Single-token GQA decode attention vs a dense KV cache or a paged pool.

Dense variant (``decode_attention_bsd``), flash-decoding adapted to TPU:
grid = (batch, kv_blocks); the kv block axis is sequential ("arbitrary")
and carries the online-softmax state of all Hq = Hkv·G query heads in
VMEM scratch.  Each grid step DMAs ONE kv block holding every kv head — a
(bk, Hkv, D) stretch of the cache's native (B, S, Hkv, D) layout — and
updates the G query heads of each kv head in turn.  Cache validity,
causality and the sliding window are evaluated from positions (−1 =
empty slot); the kernel is layout-agnostic about rings.

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
per-request block table (B, MB) of physical page ids (-1 = unmapped), a
scalar-prefetch operand.  The grid walks the slots in order; each slot's
step loops over its table in kv blocks of P pages (``pages_per_block``)
up to its live extent, copying each block's live pages by DMA from the
pool, which stays in HBM, into a double-buffered VMEM block while the
previous block computes (a slot's last block prefetches the next slot's
first).  A page is live when it is mapped and its positions reach the
query (and the window); a block with no live page issues neither a DMA
nor a dot, and a dead page inside a live block is masked.  Per block,
every query head meets every (token, kv head) column of the block in
one (Hq, P·bs·Hkv) score tile, on the MXU in the cache's dtype with
float32 accumulation, the off-head columns masked.  kv positions are not
stored: with absolute addressing logical block j covers positions
[j·bs, (j+1)·bs); with ring addressing (``ring_blocks`` > 0 —
sliding-window tables bounded at ceil(window/bs)+1 recycled slots, see
``kernels.paging``) slot j holds the latest absolute block ≡ j (mod
ring) not beyond the query's block, so the kernel reconstructs positions
from the table index and the query position.  The pool operand is the
serving cache's whole layer stack (L, NB, bs, Hkv, D) and the layer to
read is one more scalar-prefetch operand: each DMA reads page ``pid`` of
layer ``layer`` in place, so the caller's layer scan never slices a
layer's pool out of the stack (a single-layer pool is passed as
``pool[None]`` with layer 0).  With ``k_scale``/
``v_scale`` the pool holds int8 pages with one float32 scale per (page,
kv head) (``kernels.quant``): the scales ride along as extra
scalar-prefetch operands and are applied in VMEM to each page's score
and probability columns, so no full-precision pool ever exists in HBM.
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
# paged variant: walk a slot's live pages, several per step, by manual DMA
# ---------------------------------------------------------------------------

#: tokens a paged kv block covers: P = BLOCK_TOKENS // bs pages (16 of 16
#: tokens), so a block of Mistral widths is a (2048, 128) bf16 tile, 512 KB
#: of K and of V.  On a v5e a layer of 32 slots of 320-1,800 tokens took
#: 0.32 ms at 256 tokens a block, 0.37 ms at 128, 1.1 ms at one page
BLOCK_TOKENS = 256
DEAD = 1 << 30  # first position of a dead page: beyond every query


def pages_per_block(block_size: int, table_blocks: int) -> int:
    """Pages a paged kv block gathers, from static shapes alone."""
    return max(1, min(table_blocks, BLOCK_TOKENS // block_size))


def _paged_kernel(*refs, scale: float, window: int, bs: int, mb: int,
                  ppb: int, ring: int, quantized: bool):
    if quantized:
        (bt_ref, qpos_ref, layer_ref, ks_ref, vs_ref, q_ref, k_hbm, v_hbm,
         o_ref, kbuf, vbuf, sem, first_buf, q_scr, m_scr, l_scr,
         acc_scr) = refs
    else:
        (bt_ref, qpos_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
         kbuf, vbuf, sem, first_buf, q_scr, m_scr, l_scr, acc_scr) = refs
    b = pl.program_id(0)
    qpos = qpos_ref[b]
    layer = layer_ref[0]
    _, n_kv, g, _ = q_ref.shape
    hq = n_kv * g
    rows = bs * n_kv  # a page's rows in the (P·bs·Hkv, D) view of a block
    cols = ppb * rows
    cdt = q_ref.dtype if quantized else kbuf.dtype  # the MXU's input dtype

    def page(s, j):
        """(physical id, first position) of table slot j of batch slot
        s; DEAD first when the slot is unmapped, past the table, not yet
        entered, after the query, or wholly out of the window."""
        qp = qpos_ref[s]
        lb = qp // bs  # the block the query writes
        pid = bt_ref[s, jnp.minimum(j, mb - 1)]
        blk = lb - (lb + ring - j) % ring if ring else j
        first = blk * bs
        live = (j < mb) & (pid >= 0) & (blk >= 0) & (first <= qp)
        if window > 0:
            live &= qp - (first + bs - 1) < window
        return jnp.maximum(pid, 0), jnp.where(live, first, DEAD)

    def block(s, i):
        """Pages of kv block i of batch slot s, and whether any is live."""
        pages = [page(s, i * ppb + p) for p in range(ppb)]
        return pages, functools.reduce(
            jnp.logical_or, [first < DEAD for _, first in pages])

    def copies(pages, slot):
        for p, (pid, first) in enumerate(pages):
            yield (first < DEAD, p,
                   pltpu.make_async_copy(k_hbm.at[layer, pid],
                                         kbuf.at[slot, p], sem.at[0, slot]),
                   pltpu.make_async_copy(v_hbm.at[layer, pid],
                                         vbuf.at[slot, p], sem.at[1, slot]))

    def fetch(pages, slot):
        for live, p, ck, cv in copies(pages, slot):
            @pl.when(live)
            def _start():
                ck.start()
                cv.start()

            # a dead page's scores are masked to p = 0, and 0·v must be 0:
            # its V rows are zeroed, not left as stale (or NaN) VMEM
            @pl.when(jnp.logical_not(live))
            def _zero():
                vbuf[slot, p] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)

    def wait(pages, slot):
        for live, _, ck, cv in copies(pages, slot):
            @pl.when(live)
            def _wait():
                ck.wait()
                cv.wait()

    # static column maps of a block's (Hq, P·bs·Hkv) score tile: column c
    # is token c // Hkv of the block, kv head c % Hkv, page c // rows
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    col_tok = (col // n_kv) % bs
    col_page = col // rows
    col_head = col % n_kv
    own_head = (jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 0) // g
                == jax.lax.broadcasted_iota(jnp.int32, (hq, cols), 1) % n_kv)

    def col_first(pages):  # (1, cols) first position of each column's page
        out = jnp.zeros((1, cols), jnp.int32)
        for p, (_, first) in enumerate(pages):
            out = jnp.where(col_page == p, first, out)
        return out

    def dequant_row(scales, pages):
        """(1, cols) per-(page, kv head) scale of each column; 0 on dead
        pages, whose V rows are zero."""
        out = jnp.zeros((1, cols), jnp.float32)
        for p, (pid, first) in enumerate(pages):
            for h in range(n_kv):
                s = jnp.where(first < DEAD, scales[pid * n_kv + h], 0.0)
                out = jnp.where((col_page == p) & (col_head == h), s, out)
        return out

    def attend(pages, slot):
        s = jax.lax.dot_general(q, kbuf[slot].reshape(cols, -1).astype(cdt),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale  # (Hq, cols): every query head against every column
        if quantized:
            s = s * dequant_row(ks_ref, pages)
        pos = col_tok + col_first(pages)  # past every query on dead pages
        ok = pos <= qpos
        if window > 0:
            ok &= qpos - pos < window
        mask = own_head & ok
        s = jnp.where(mask, s, NEG)
        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.where(mask, jnp.exp(s - m_next), 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_next
        if quantized:
            p = p * dequant_row(vs_ref, pages)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(cdt), vbuf[slot].reshape(cols, -1).astype(cdt),
            preferred_element_type=jnp.float32)

    # the grouped (Hkv, G, D) query as one (Hq, D) tile: query head
    # h·G + g is row h·G + g, as the kv head of column c is c % Hkv
    for h in range(n_kv):
        q_scr[h * g:(h + 1) * g, :] = q_ref[0, h].astype(jnp.float32)
    q = q_scr[...].astype(cdt)
    m_scr[...] = jnp.full_like(m_scr, NEG)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def prefetch(s, i, slot):
        pages, live = block(s, i)

        @pl.when(live)
        def _fetch():
            fetch(pages, slot)

    # the grid walks the slots in order, and each slot's last block
    # prefetches the next slot's first; first_buf says where it landed
    @pl.when(b == 0)
    def _prologue():
        first_buf[0] = 0
        prefetch(0, 0, 0)

    base = first_buf[0]
    # absolute tables end at the query's block; a ring may hold any slot
    n_blocks = (pl.cdiv(mb, ppb) if ring
                else jnp.minimum(qpos // bs, mb - 1) // ppb + 1)

    def step(i, carry):
        slot = (base + i) % 2

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            prefetch(b, i + 1, 1 - slot)

        @pl.when((i + 1 == n_blocks) & (b + 1 < pl.num_programs(0)))
        def _next_slot():
            prefetch(b + 1, 0, 1 - slot)

        pages, live = block(b, i)

        @pl.when(live)
        def _compute():
            wait(pages, slot)
            attend(pages, slot)

        return carry

    jax.lax.fori_loop(0, n_blocks, step, 0)
    first_buf[0] = (base + n_blocks) % 2
    for h in range(n_kv):
        denom = l_scr[h * g:(h + 1) * g, :]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0, h] = (acc_scr[h * g:(h + 1) * g, :] / denom
                       ).astype(o_ref.dtype)


def decode_attention_paged_bsd(
    q: jnp.ndarray,  # (B, Hkv, G, D) — grouped query heads (or stream view)
    k_pool: jnp.ndarray,  # (L, NB, bs, Hkv, D) — layer stack (int8 if q8)
    v_pool: jnp.ndarray,  # (L, NB, bs, Hkv, D)
    block_tables: jnp.ndarray,  # (B, MB) int32 physical page ids; -1 unmapped
    q_position: jnp.ndarray,  # (B,) int32
    layer,  # int32 scalar: the layer of the stack to read
    *,
    k_scale=None,  # (L, NB, Hkv) f32 per-(page, head) scales — int8 pools
    v_scale=None,  # (L, NB, Hkv) float32
    sliding_window: int = 0,
    ring_blocks: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged decode -> (B, Hkv, G, D).  One grid step per slot walks the
    slot's block table in blocks of ``pages_per_block`` pages up to its
    live extent, gathering each block's live pages by DMA into VMEM while
    the previous block computes; a block with no live page costs neither
    a DMA nor a dot.  The pools stay in HBM in the serving cache's native
    (L, NB, bs, Hkv, D) layout and every DMA reads layer ``layer`` of
    the stack in place.  ``ring_blocks`` > 0 means the table is
    ring-addressed (windowed requests recycle pages; see
    ``kernels.paging``) and slot positions are reconstructed from the
    query position.  ``k_scale``/``v_scale`` switch to an int8 pool: its
    pages are cast in VMEM and each page's per-head scales applied to its
    score and probability columns."""
    B, Hkv, G, D = q.shape
    bs = k_pool.shape[2]
    MB = block_tables.shape[1]
    quantized = k_scale is not None
    ppb = pages_per_block(bs, MB)

    kernel = functools.partial(_paged_kernel, scale=1.0 / math.sqrt(D),
                               window=sliding_window, bs=bs, mb=MB, ppb=ppb,
                               ring=ring_blocks, quantized=quantized)

    def head(b, *_):
        return (b, 0, 0, 0)

    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    prefetch = [block_tables.astype(jnp.int32), q_position.astype(jnp.int32),
                layer]
    if quantized:  # the layer's flat (NB·Hkv,) scale vectors, page-major
        # like the pool: (NB, Hkv) is small, the whole (L·NB·Hkv) is not
        prefetch += [
            jax.lax.dynamic_index_in_dim(s, layer[0], keepdims=False)
            .astype(jnp.float32).reshape(-1) for s in (k_scale, v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), head),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, D), head),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, bs, Hkv, D), k_pool.dtype),
            pltpu.VMEM((2, ppb, bs, Hkv, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((Hkv * G, D), jnp.float32),
            pltpu.VMEM((Hkv * G, 1), jnp.float32),
            pltpu.VMEM((Hkv * G, 1), jnp.float32),
            pltpu.VMEM((Hkv * G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # sequential: a slot's DMAs prefetch the next slot's block
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="decode_attention_paged_q8" if quantized
        else "decode_attention_paged",
    )(*prefetch, q, k_pool, v_pool)

"""Blockwise causal/sliding-window GQA flash attention (TPU Pallas).

TPU-native design (not a CUDA port):
  * grid = (batch, q_heads, q_blocks, kv_blocks); kv is the innermost,
    "arbitrary" (sequential) dimension — the online-softmax row state
    (m, l, acc) lives in VMEM scratch and is carried across kv blocks.
  * BlockSpecs tile q/k/v/o into VMEM with MXU-aligned (multiple-of-128)
    block shapes on the matmul dims; d_head is kept whole (<= 256).
  * GQA: the kv BlockSpec index_map folds the q-head -> kv-head mapping
    (h // group), so KV blocks are fetched once per kv head group without
    materializing repeated heads in HBM.
  * masking is two-level: scores are masked to a large-negative BEFORE the
    row max, and probabilities are explicitly zeroed, so fully-masked rows
    stay exactly zero (no NaN rescue needed); fully-masked kv blocks are
    skipped via pl.when on block-level bounds.

Two variants (mirroring ``decode_attention``'s generic/merged pair):
  * ``flash_attention_bhsd`` — generic: q is a separately-projected
    head-major (B, Hq, Sq, D) tensor, k/v arrive head-major too.
  * ``flash_attention_merged_bsd`` — the paper's merged (Q/P-removed)
    PREFILL fast path: there is NO q projection, the RoPE'd residual
    stream (B, Sq, d_model) *is* the query (d_model = Hq·D for merged
    configs, paper Fig 1b).  The kernel tiles the stream itself, one
    (bq, D) lane column per head, and reads K*/V* as (bk, D) columns of
    their sequence-major (B, Sk, Hkv·D) rows — no head-major transpose of
    q/k/v/o bracketing the kernel — then writes the attention output
    straight back into the stream (FFN-input) basis.

TPU block rule (shared with ``decode_attention``): the last two dims of
every block are (rows, D) with rows a multiple of 8 or the whole dim, and
D a whole dim or a multiple of 128; per-key vectors travel as (1, n) rows
of a (-1, 1, n) view.

Accumulation is float32 regardless of input dtype.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _flash_body(iq, ik, load_q, load_k, load_v, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, window: int, bq: int, bk: int,
                load_scales=None):
    """Shared online-softmax state update for one (bq, bk) block pair.

    ``load_q``/``load_k``/``load_v`` are thunks returning (bq, D)/(bk, D)
    tiles — the generic and merged kernels slice their differently-shaped
    VMEM refs there, and the loads stay INSIDE the fully-masked-block skip
    (pl.when below) either way.  ``load_scales`` (int8 k/v) returns the
    (1, bk) per-key dequant rows (k_scale, v_scale): q·(k·s) = (q·k)·s, so
    the k scale multiplies score columns and the v scale probability
    columns, and the int8 tiles enter the matmuls as they are.
    """
    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    rows_max = iq * bq + bq - 1
    cols_min = ik * bk
    cols_max = ik * bk + bk - 1
    rows_min = iq * bq

    run = True
    if causal:
        run = jnp.logical_and(run, cols_min <= rows_max)
    if window > 0:
        run = jnp.logical_and(run, rows_min - cols_max < window)

    @pl.when(run)
    def _body():
        q = load_q().astype(jnp.float32) * scale  # (bq, D)
        k = load_k().astype(jnp.float32)  # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        if load_scales is not None:
            k_cols, v_cols = load_scales()
            s = s * k_cols

        rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= cols <= rows
        if window > 0:
            mask &= rows - cols < window
        s = jnp.where(mask, s, NEG)

        m_prev = m_scr[:, :1]  # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)  # (bq, 1)
        p = jnp.where(mask, jnp.exp(s - m_next), 0.0)  # (bq, bk)

        v = load_v().astype(jnp.float32)  # (bk, D)
        pv = p if load_scales is None else p * v_cols
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            pv, v, preferred_element_type=jnp.float32)
        l_scr[:, :1] = l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)


def _flash_finish(l_scr, acc_scr):
    denom = l_scr[:, :1]
    denom = jnp.where(denom == 0.0, 1.0, denom)
    return acc_scr[...] / denom


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  bq: int, bk: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    _flash_body(iq, ik, lambda: q_ref[0, 0], lambda: k_ref[0, 0],
                lambda: v_ref[0, 0], m_scr, l_scr, acc_scr,
                scale=scale, causal=causal, window=window, bq=bq, bk=bk)

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, 0] = _flash_finish(l_scr, acc_scr).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jnp.ndarray,  # (B, Hq, Sq, D)
    k: jnp.ndarray,  # (B, Hkv, Sk, D)
    v: jnp.ndarray,  # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    sliding_window: int = 0,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=sliding_window, bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)


def _flash_kernel_merged(q_ref, k_ref, v_ref, *refs, scale: float,
                         causal: bool, window: int, bq: int, bk: int, nk: int,
                         quantized: bool):
    """Same online-softmax recurrence as ``_flash_kernel`` (shared
    ``_flash_body``); the refs are (1, rows, D) lane columns of the
    sequence-major (B, S, heads·D) layouts, so the only difference is the
    slicing.  With ``quantized`` two (1, 1, bk) per-key scale rows follow
    k/v."""
    load_scales = None
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs

        def load_scales():
            return ks_ref[0], vs_ref[0]
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    _flash_body(iq, ik, lambda: q_ref[0], lambda: k_ref[0],
                lambda: v_ref[0], m_scr, l_scr, acc_scr,
                scale=scale, causal=causal, window=window, bq=bq, bk=bk,
                load_scales=load_scales)

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0] = _flash_finish(l_scr, acc_scr).astype(o_ref.dtype)


def flash_attention_merged_bsd(
    u: jnp.ndarray,  # (B, Sq, Hq·D) — RoPE'd residual stream = merged query
    k: jnp.ndarray,  # (B, Sk, Hkv·D) — K*, sequence-major, heads along lanes
    v: jnp.ndarray,  # (B, Sk, Hkv·D) — V*
    *,
    d_head: int,
    k_scale=None,  # (B, Sk, Hkv) float32 per-key scales — int8 k/v
    v_scale=None,  # (B, Sk, Hkv) float32
    causal: bool = True,
    sliding_window: int = 0,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Merged-weight (Q/P-removed) flash PREFILL: stream-as-query.

    Grid and softmax state as in ``flash_attention_bhsd``; the BlockSpecs
    differ so that q tiles are (bq, D) lane columns of the (B, Sq, d_model)
    residual stream itself and K*/V* tiles are (bk, D) columns of the
    cache's sequence-major rows — the head-major transposes of q, k, v AND
    o that bracket the generic kernel are simply not in the program.  The
    output lands as (B, Sq, d_model), the FFN-input stream the merged
    block consumes next.  On TPU a lane column must be whole vregs, so
    ``d_head`` is a multiple of 128 there.

    ``k_scale``/``v_scale`` switch to int8 K*/V* (the ``paged_q8`` pool's
    quantization applied to the in-flight sequence): each tile's per-key
    scales ride in as (1, bk) rows of a (B·Hkv·nk, 1, bk) view.  Output
    dtype follows ``u`` (the stream), since int8 inputs carry no float
    dtype.
    """
    B, Sq, d = u.shape
    Sk = k.shape[1]
    D = d_head
    Hq, Hkv = d // D, k.shape[2] // D
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk
    quantized = k_scale is not None

    # kv head h // G owns query head h: lane column h of u, h // G of k/v
    q_spec = pl.BlockSpec((1, bq, D), lambda b, h, i, j: (b, i, h))
    kv_spec = pl.BlockSpec((1, bk, D), lambda b, h, i, j, G=G: (b, j, h // G))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [u, k, v]
    if quantized:
        def rows(s):  # (B, Sk, Hkv) -> (B·Hkv·nk, 1, bk)
            return s.astype(jnp.float32).transpose(0, 2, 1).reshape(
                B * Hkv * nk, 1, bk)
        in_specs += [pl.BlockSpec(
            (1, 1, bk),
            lambda b, h, i, j, G=G: ((b * Hkv + h // G) * nk + j, 0, 0))] * 2
        operands += [rows(k_scale), rows(v_scale)]

    kernel = functools.partial(_flash_kernel_merged, scale=scale,
                               causal=causal, window=sliding_window,
                               bq=bq, bk=bk, nk=nk, quantized=quantized)
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, d), u.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_merged_q8" if quantized
        else "flash_attention_merged",
    )(*operands)

"""Mamba2 SSD chunked scan (TPU Pallas).

grid = (batch, heads, chunks); the chunk axis is sequential ("arbitrary")
and carries the per-(batch, head) SSM state (P, N) in VMEM scratch.  Within
a chunk the SSD "duality" turns the recurrence into two MXU matmuls:

  y_intra = (tril(exp(Acum_i − Acum_j)) ∘ (C Bᵀ) ∘ dt_j) X        (L,L)@(L,P)
  y_inter = (C Sᵀ) ∘ exp(Acum)                                    (L,N)@(N,P)
  S'      = exp(a_sum) S + Xᵀ (B ∘ dt ∘ exp(a_sum − Acum))        (P,L)@(L,N)

All decay exponents are ≤ 0 (dt > 0, A < 0), so the exps are stable.
Inputs are pre-activated: dt is post-softplus, a = dt·A.  The D-skip and
gating/norm live in the ops wrapper / mamba2 module.

TPU block rule (shared with the attention kernels): x/B/C are tiled
head-major, (L, P) and (L, N) blocks of (B, H, S, ·); the per-position
dt and a travel as (1, L) rows of a (B·H·nc, 1, L) view.  The prefix sum
is a matmul with a triangular mask, and the two per-position columns the
recurrence needs are read off the diagonal — no lane↔sublane reshape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, fin_ref, s_scr,
                *, L: int, nc: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    x = x_ref[0, 0].astype(jnp.float32)  # (L, P)
    Bm = b_ref[0, 0].astype(jnp.float32)  # (L, N)
    Cm = c_ref[0, 0].astype(jnp.float32)  # (L, N)
    dt = dt_ref[0].astype(jnp.float32)  # (1, L)
    a = a_ref[0].astype(jnp.float32)  # (1, L)

    rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)

    def column(r):  # (1, L) row -> (L, 1) column, off the diagonal
        return jnp.sum(jnp.where(rows == cols, r, 0.0), axis=1, keepdims=True)

    # inclusive prefix sum: A_cum_i = Σ_{j<=i} a_j
    A_cum = jax.lax.dot(a, (rows <= cols).astype(jnp.float32),
                        preferred_element_type=jnp.float32)  # (1, L)
    A_col = column(A_cum)  # (L, 1)
    a_sum = jnp.sum(a, axis=1, keepdims=True)  # (1, 1)
    decay_out = jnp.exp(A_col)  # (L, 1)
    decay_end = jnp.exp(a_sum - A_col)  # (L, 1)

    # intra-chunk
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    seg = A_col - A_cum  # (L, L): Acum_i − Acum_j
    kern = jnp.where(rows >= cols, jnp.exp(seg), 0.0) * CB * dt
    y = jax.lax.dot(kern, x, preferred_element_type=jnp.float32)  # (L, P)

    # inter-chunk (state entering this chunk)
    state = s_scr[...]  # (P, N)
    y += jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * decay_out

    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update
    wB = Bm * decay_end * column(dt)  # (L, N)
    s_new = state * jnp.exp(a_sum) + jax.lax.dot_general(
        x, wB, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    s_scr[...] = s_new

    @pl.when(c_idx == nc - 1)
    def _finish():
        fin_ref[0, 0] = s_new.astype(fin_ref.dtype)


def ssd_scan_pallas(
    x: jnp.ndarray,  # (B, S, H, P)
    dt: jnp.ndarray,  # (B, S, H) post-softplus
    a: jnp.ndarray,  # (B, S, H) = dt * A  (<= 0)
    Bm: jnp.ndarray,  # (B, S, H, N)
    Cm: jnp.ndarray,  # (B, S, H, N)
    *,
    chunk: int,
    interpret: bool = False,
):
    """Returns (y (B,S,H,P) fp32, final_state (B,H,P,N) fp32)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    L = chunk if S % chunk == 0 else S
    nc = S // L

    def heads(t):  # (B, S, H, ·) -> (B, H, S, ·)
        return t.transpose(0, 2, 1, 3)

    def rows(t):  # (B, S, H) -> (B·H·nc, 1, L)
        return t.transpose(0, 2, 1).reshape(B * H * nc, 1, L)

    def seq(width):  # (L, width) tile of a head-major (B, H, S, width) array
        return pl.BlockSpec((1, 1, L, width), lambda b, h, c: (b, h, c, 0))

    row = pl.BlockSpec((1, 1, L), lambda b, h, c: ((b * H + h) * nc + c, 0, 0))
    kernel = functools.partial(_ssd_kernel, L=L, nc=nc)
    y, fin = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[seq(P), seq(N), seq(N), row, row],
        out_specs=[
            seq(P),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ssd_scan",
    )(heads(x), heads(Bm), heads(Cm), rows(dt), rows(a))
    return heads(y), fin

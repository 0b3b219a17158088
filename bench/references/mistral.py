"""Plain float32 reference of the Mistral block family, in ``jax.numpy``.

Two block styles, written from their published equations and nothing of
the program (weights come from ``bench.weights`` and the seed):

  standard         Mistral-7B (arXiv:2310.06825): pre-norm residual block,
                   RMSNorm, RoPE ("half" layout), grouped-query attention
                   with a sliding window, SwiGLU FFN.
                     h = u + Attn(RMS(u) Wq, RMS(u) Wk, RMS(u) Wv) Wp
                     out = h + (silu(RMS(h) Wg) * RMS(h) Wu) Wd
  residual_qpfree  paper Fig 4 (arXiv:2404.12362 §5): the same block with
                   Q and P removed; the normed stream is the query and the
                   attention output joins the residual directly.
                     h = u + Attn(RMS(u), RMS(u) Wk, RMS(u) Wv)

Rows are packed: several sequences share a row, told apart by segment
ids, each with its own positions.  The reference runs layer by layer (a
scan that draws each layer's weights from the seed inside the step), in
float32 at ``Precision.HIGHEST``, with attention and the unembedding in
blocks of query rows, so that it fits beside nothing else on the chip.

``weights="fp8"`` is the control: every weight rounded to float8 e4m3
with one scale per output channel, the step below the configuration's
bfloat16 that a later change might be tempted to take.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
FP8_MAX = 448.0


def _fp8_round(w):
    """Round a matrix to float8 e4m3 with one scale per output column
    (per row for an embedding table), back in float32."""
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    q = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _weight(seed_k, path, layer, shape, std, served, mode):
    w = W.top_leaf(seed_k, path, shape, std, served) if layer is None \
        else W.layer_leaf(seed_k, path, layer, shape, std, served)
    w = w.astype(jnp.float32)
    if mode == "fp8" and std != 0.0:
        if path.endswith("table"):
            w = _fp8_round(w.T).T  # one scale per vocabulary row
        else:
            w = _fp8_round(w)
    return w


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta):
    """x (R, S, H, D); rotate the two halves of D (Mistral/Llama layout)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, pos, seg, window):
    """Causal, windowed, segment-masked GQA.  q (R,S,H,D), k/v (R,S,Hk,D)
    -> (R, S, H*D), in blocks of ``QUERY_BLOCK`` query rows."""
    R, S, H, D = q.shape
    Hk = k.shape[2]
    G = H // Hk
    qb = min(QUERY_BLOCK, S)
    nb = S // qb
    qg = q.reshape(R, nb, qb, Hk, G, D).transpose(1, 0, 2, 3, 4, 5)
    qpos = pos.reshape(R, nb, qb).transpose(1, 0, 2)
    qseg = seg.reshape(R, nb, qb).transpose(1, 0, 2)

    def block(args):
        qq, qp, qs = args  # (R, qb, Hk, G, D), (R, qb), (R, qb)
        s = jnp.einsum("rqhgd,rkhd->rhgqk", qq, k, precision=HI)
        s = s / np.sqrt(D)
        ok = ((qs[:, :, None] == seg[:, None, :]) & (qs[:, :, None] >= 0)
              & (pos[:, None, :] <= qp[:, :, None]))
        if window:
            ok = ok & (qp[:, :, None] - pos[:, None, :] < window)
        s = jnp.where(ok[:, None, None], s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        m = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(s - m)
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        o = jnp.einsum("rhgqk,rkhd->rqhgd", p, v, precision=HI)
        return o.reshape(R, qb, H * D)

    out = jax.lax.map(block, (qg, qpos, qseg))  # (nb, R, qb, H*D)
    return out.transpose(1, 0, 2, 3).reshape(R, S, H * D)


def _block(cfg, lw, h, pos, seg):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    window = cfg.get("sliding_window") or 0
    R, S, d = h.shape
    H, Hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = d // H
    x = _rms(h, lw["norm1/scale"], eps)
    if cfg["block_style"] == "standard":
        q = jnp.dot(x, lw["attn/wq"], precision=HI)
    else:  # residual_qpfree: the normed stream is the query
        q = x
    k = jnp.dot(x, lw["attn/wk"], precision=HI).reshape(R, S, Hk, D)
    v = jnp.dot(x, lw["attn/wv"], precision=HI).reshape(R, S, Hk, D)
    q = _rope(q.reshape(R, S, H, D), pos, theta)
    k = _rope(k, pos, theta)
    a = _attention(q, k, v, pos, seg, window)
    if cfg["block_style"] == "standard":
        a = jnp.dot(a, lw["attn/wp"], precision=HI)
    h = h + a
    x = _rms(h, lw["norm2/scale"], eps)
    g = jnp.dot(x, lw["ffn/w_gate"], precision=HI)
    u = jnp.dot(x, lw["ffn/w_up"], precision=HI)
    return h + jnp.dot(jax.nn.silu(g) * u, lw["ffn/w_down"], precision=HI)


def make_forward(cfg: Dict, mode: str = "served"):
    """Jitted ``f(seed_key, tokens, positions, segs, targets)`` over packed
    (R, S) rows -> (best logit, logit of ``targets``, argmax id), each
    (R, S) at every position.  ``mode`` "served": the served
    (bfloat16) weights in float32 arithmetic; "fp8": the control."""
    served = jnp.dtype(cfg["torch_dtype"])
    n_layers = cfg["num_hidden_layers"]
    vocab = cfg["vocab_size"]
    leaves = W.layer_leaves(cfg)
    top = {p: (s, sd) for p, s, sd in W.top_leaves(cfg)}

    def fwd(seed_k, tokens, pos, seg, targets):
        tw = functools.partial(_weight, seed_k, layer=None, served=served,
                               mode=mode)
        emb = tw("embed/table", shape=top["embed/table"][0],
                 std=top["embed/table"][1])
        h = emb[tokens]

        def layer(hh, i):
            lw = {p: _weight(seed_k, p, i, s, sd, served, mode)
                  for p, s, sd in leaves}
            return _block(cfg, lw, hh, pos, seg), None

        h, _ = jax.lax.scan(layer, h, jnp.arange(n_layers))
        h = _rms(h, tw("final_norm/scale", shape=top["final_norm/scale"][0],
                       std=0.0), cfg["rms_norm_eps"])
        un = tw("unembed/table", shape=top["unembed/table"][0],
                std=top["unembed/table"][1])
        R, S, d = h.shape
        qb = min(QUERY_BLOCK, S)
        hb = h.reshape(R, S // qb, qb, d).transpose(1, 0, 2, 3)
        tb = targets.reshape(R, S // qb, qb).transpose(1, 0, 2)

        def logits_block(args):
            hh, tt = args
            lg = jnp.einsum("rqd,vd->rqv", hh, un, precision=HI)
            lg = jnp.where(jnp.arange(lg.shape[-1]) < vocab, lg, -jnp.inf)
            tgt = jnp.take_along_axis(lg, tt[..., None], axis=-1)[..., 0]
            return jnp.max(lg, -1), tgt, jnp.argmax(lg, -1).astype(jnp.int32)

        best, tgt, arg = jax.lax.map(logits_block, (hb, tb))
        back = lambda x: x.transpose(1, 0, 2).reshape(R, S)  # noqa: E731
        return back(best), back(tgt), back(arg)

    return jax.jit(fwd)

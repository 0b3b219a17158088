"""Transformer stacks for every assigned family, in all paper block styles.

Block styles (paper mapping):
  standard         pre-norm residual blocks (public-literature baseline)
  skipless         Fig 1(a): no skips / no norms, full Q,K,V,P
  skipless_merged  Fig 1(b): Q and P removed.  Serial layout is the paper's
                   exact rewrite (see core/merge.py); parallel layout is the
                   paper's Fig 3(a) architecture.
  residual_qpfree  Fig 4: Q/P-free blocks *with* norms and skips (paper §5)

Layer kinds: "attn" (self-attn + FFN/MoE), "cross" (vlm cross-attn + FFN),
"ssm" (mamba2 mixer), "hybrid" (attn ∥ ssm heads + FFN).

All stacks scan over layer-stacked params so the lowered HLO is O(1) in
depth (required for tractable 512-device compiles; also the production
choice). The VLM interleave (cross-attn every Nth layer) scans over
"super-blocks" of (N-1) self layers + 1 cross layer.

Modes:
  forward_train / forward_encode : full-sequence, returns logits (+aux)
  forward_prefill                : full-sequence prefill DISPATCHER — the
                                   destination (``DensePrefillDest`` /
                                   ``PagedPrefillDest``) picks the
                                   cache_kind axis and the config picks
                                   the style axis (``prefill_style_key``)
                                   of the ``models.backends`` PREFILL
                                   registry; merged qp layouts run the
                                   stream-as-query flash core end to end
  forward_step                   : one token vs either serving cache
                                   (serve_step body); the per-layer
                                   attention route is looked up in the
                                   ``models.backends`` registry keyed on
                                   (cache_kind, style, impl)

Both serving dispatchers validate at the boundary (ValueError — survives
``python -O``, unlike the asserts they replaced) and fail unknown
registry combos with KeyError before any compute.  ``forward_decode`` /
``forward_decode_paged`` remain as deprecated shims over ``forward_step``;
``forward_prefill``'s old ``cache_len=``/``pages=`` mega-signature remains
as a deprecated shim over the ``dest=`` dispatch.
"""
from __future__ import annotations

import warnings
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import paging
from repro.kernels import quant
from repro.models import attention as attn_mod
from repro.models import backends
from repro.models import ffn as ffn_mod
from repro.models import mamba2 as m2
from repro.models import moe as moe_mod
from repro.models.layers import (
    apply_conv_pos,
    apply_embedding,
    apply_rmsnorm,
    apply_rope,
    apply_unembedding,
    dense_init,
    dtype_of,
    init_conv_pos,
    init_embedding,
    init_rmsnorm,
    orthogonal_init,
)


def _init_fn_for(cfg: ModelConfig):
    """Orthogonal init for skipless styles (norm-preserving, cond(Q)≈1 so
    the merged runtime is numerically clean); lecun-normal otherwise."""
    if cfg.init_style == "orthogonal":
        return orthogonal_init
    if cfg.init_style == "normal":
        return dense_init
    return (orthogonal_init if cfg.block_style in ("skipless", "skipless_merged")
            else dense_init)

# ---------------------------------------------------------------------------
# layer kind layout per config
# ---------------------------------------------------------------------------

def layer_plan(cfg: ModelConfig) -> Dict[str, Any]:
    """Describes how layers are stacked/scanned for this config."""
    if cfg.family == "ssm":
        return {"kind": "ssm", "n": cfg.n_layers}
    if cfg.family == "hybrid":
        return {"kind": "hybrid", "n": cfg.n_layers}
    if cfg.family == "vlm":
        per = cfg.cross_attn_every
        assert cfg.n_layers % per == 0
        return {"kind": "vlm", "n_groups": cfg.n_layers // per, "self_per_group": per - 1}
    return {"kind": "attn", "n": cfg.n_layers}


# ---------------------------------------------------------------------------
# per-layer param init
# ---------------------------------------------------------------------------

def _init_attn_proj(key, cfg: ModelConfig, dtype, merged: bool, cross: bool):
    """Q/K/V/P params for one attention sub-module.

    Merged styles omit the eliminated pair per ``cfg.merged_variant``
    (paper Table 1): "qp" drops wq+wp, "kp" drops wk+wp, "vp" drops wv+wp.
    Cross-attention always keeps wk/wv (they read the vision tokens, which
    are not in the rotated stream basis) — only "qp" is legal for cross.
    """
    d, ad, kd = cfg.d_model, cfg.attn_dim, cfg.kv_dim
    init_fn = _init_fn_for(cfg)
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {}
    variant = cfg.merged_variant if merged else ""
    if variant and cross and variant != "qp":
        raise ValueError("cross-attention supports only the qp merged variant")
    if variant != "qp":
        p["wq"] = init_fn(ks[0], d, ad, dtype)
        if cfg.qkv_bias:
            p["bq"] = jnp.zeros((ad,), dtype)
    if variant != "kp":
        p["wk"] = init_fn(ks[1], d, kd, dtype)
        if cfg.qkv_bias:
            p["bk"] = jnp.zeros((kd,), dtype)
    if variant != "vp":
        p["wv"] = init_fn(ks[2], d, kd, dtype)
        if cfg.qkv_bias:
            p["bv"] = jnp.zeros((kd,), dtype)
    if not merged:
        p["wp"] = init_fn(ks[3], ad, d, dtype)
    return p


def _needs_norms(style: str) -> bool:
    return style in ("standard", "residual_qpfree")


def _is_merged(style: str) -> bool:
    return style in ("skipless_merged", "residual_qpfree")


def init_block(key, cfg: ModelConfig, kind: str, dtype) -> Dict[str, Any]:
    style = cfg.block_style
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {}
    merged = _is_merged(style)

    if kind in ("attn", "cross", "hybrid"):
        p["attn"] = _init_attn_proj(ks[0], cfg, dtype, merged, cross=(kind == "cross"))
    if kind == "hybrid":
        # hybrid merged style removes Q only (P must stay: FFN input is the
        # fused attn+ssm stream — see DESIGN.md §5), so keep wp always.
        if merged and "wp" not in p["attn"]:
            p["attn"]["wp"] = dense_init(ks[5], cfg.attn_dim, cfg.d_model, dtype)
        p["ssm"] = m2.init_mamba2(ks[1], cfg, dtype)
    if kind == "ssm":
        p["ssm"] = m2.init_mamba2(ks[1], cfg, dtype)

    if cfg.has_ffn and kind != "ssm":
        # merged serial dense/moe/vlm: FFN input dim is attn_dim (P folded in)
        ffn_in = cfg.attn_dim if (merged and not cfg.parallel_block and kind != "hybrid") else cfg.d_model
        if cfg.n_experts and kind == "attn":
            p["moe"] = moe_mod.init_moe(ks[2], ffn_in, cfg.d_ff, cfg.d_model,
                                        cfg.n_experts, cfg.ffn_type, dtype)
        else:
            p["ffn"] = ffn_mod.init_ffn(ks[2], ffn_in, cfg.d_ff, cfg.d_model,
                                        cfg.ffn_type, dtype,
                                        init_fn=_init_fn_for(cfg),
                                        out_gain=cfg.ffn_out_gain)

    if _needs_norms(style):
        p["norm1"] = init_rmsnorm(cfg.d_model, dtype)
        if cfg.has_ffn and kind != "ssm":
            p["norm2"] = init_rmsnorm(cfg.d_model, dtype)
    return p


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    dtype = dtype_of(cfg.param_dtype)
    plan = layer_plan(cfg)
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {}
    params["embed"] = init_embedding(keys[0], cfg.padded_vocab, cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["unembed"] = init_embedding(keys[1], cfg.padded_vocab, cfg.d_model, dtype)

    def stack_layers(k, n, kind):
        lk = jax.random.split(k, n)
        layers = [init_block(lki, cfg, kind, dtype) for lki in lk]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)

    if plan["kind"] == "vlm":
        ng, spg = plan["n_groups"], plan["self_per_group"]
        sk = jax.random.split(keys[2], ng)
        groups = [stack_layers(ski, spg, "attn") for ski in sk]
        params["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *groups)  # (ng, spg, …)
        params["cross_layers"] = stack_layers(keys[3], ng, "cross")  # (ng, …)
    else:
        params["layers"] = stack_layers(keys[2], plan["n"], plan["kind"])

    if _needs_norms(cfg.block_style):
        params["final_norm"] = init_rmsnorm(cfg.d_model, dtype)
    if cfg.conv_pos_width:
        params["conv_pos"] = init_conv_pos(keys[4], cfg.d_model, cfg.conv_pos_width, dtype)
    return params


def count_params(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# attention sub-module apply (projections + rope + core)
# ---------------------------------------------------------------------------

def _project_qkv(lp, cfg: ModelConfig, u, kv_src, merged: bool):
    """u: (B,S,d) query-side stream; kv_src: (B,Sk,d) key/value source.

    In merged styles the projection named by ``cfg.merged_variant`` is the
    identity: the stream is already in that projection's output basis
    (paper Fig 2b/c/d).
    """
    ad, kd, Dh = cfg.attn_dim, cfg.kv_dim, cfg.d_head
    variant = cfg.merged_variant if merged else ""

    def proj(name, src):
        y = src @ lp["w" + name].astype(u.dtype)
        if "b" + name in lp:
            y = y + lp["b" + name].astype(u.dtype)
        return y

    q = u if variant == "qp" else proj("q", u)
    k = kv_src if variant == "kp" else proj("k", kv_src)
    v = kv_src if variant == "vp" else proj("v", kv_src)
    B, Sq = u.shape[0], u.shape[1]
    Sk = kv_src.shape[1]
    q = q.reshape(B, Sq, cfg.n_heads, Dh)
    k = k.reshape(B, Sk, cfg.n_kv_heads, Dh)
    v = v.reshape(B, Sk, cfg.n_kv_heads, Dh)
    return q, k, v


def _self_attention_seq(lp, cfg: ModelConfig, u, positions, merged: bool,
                        impl: str, qkv_sharding=None,
                        merged_core: bool = False, cache_kind: str = "dense",
                        q8_block: int = 0, q8_true_len=None):
    """``merged_core`` selects the stream-as-query attention core (merged
    qp layouts only: q below is an identity view of u, so handing it to
    ``attention_core_merged`` keeps every tensor in its native layout —
    the prefill twin of the merged decode fast path).

    ``q8_block`` > 0 (paged_q8 prefill) quantizes K/V at pool granularity
    — int8 per ``q8_block``-token × kv-head block, positions >=
    ``q8_true_len`` masked to zero first — and attends over the QUANTIZED
    view (in-kernel dequant on the merged route, an XLA dequant
    otherwise), so prefill logits see exactly the pool bytes that
    ``_finish_paged_q8`` later stores.  The RAW float K/V is still what's
    collected: the finish path re-quantizes it with the same function and
    mask, landing bit-identical ints + scales in the pool."""
    q, k, v = _project_qkv(lp, cfg, u, u, merged)
    if qkv_sharding is not None:
        # merged styles lose the TP sharding anchor for q (no wq matmul to
        # propagate head-sharding from): without this constraint GSPMD
        # computes attention replicated over the model axis (§Perf)
        q = jax.lax.with_sharding_constraint(q, qkv_sharding)
        k = jax.lax.with_sharding_constraint(k, qkv_sharding)
        v = jax.lax.with_sharding_constraint(v, qkv_sharding)
    q = apply_rope(q, positions, style=cfg.rope_style, theta=cfg.rope_theta,
                   fraction=cfg.rope_fraction)
    k = apply_rope(k, positions, style=cfg.rope_style, theta=cfg.rope_theta,
                   fraction=cfg.rope_fraction)
    B, S = u.shape[0], u.shape[1]
    if q8_block:
        valid = None if q8_true_len is None else \
            (positions < q8_true_len[:, None])
        kq, ksc = quant.q8_quantize_seq(k, q8_block, valid)
        vq, vsc = quant.q8_quantize_seq(v, q8_block, valid)
        if merged_core:
            out = attn_mod.attention_core_merged(
                q.reshape(B, S, cfg.attn_dim), kq, vq,
                q_positions=positions, kv_positions=positions,
                n_kv_heads=cfg.n_kv_heads, causal=cfg.causal,
                sliding_window=cfg.sliding_window, impl=impl,
                query_chunk=cfg.query_chunk or S, cache_kind=cache_kind,
                k_scale=ksc, v_scale=vsc)
            return out, (k, v)
        kd = quant.q8_dequant_seq(kq, ksc, k.dtype)
        vd = quant.q8_dequant_seq(vq, vsc, v.dtype)
        out = attn_mod.attention_core(
            q, kd, vd, q_positions=positions, kv_positions=positions,
            causal=cfg.causal, sliding_window=cfg.sliding_window, impl=impl,
            query_chunk=cfg.query_chunk or q.shape[1])
        return out.reshape(B, S, cfg.attn_dim), (k, v)
    if merged_core:
        out = attn_mod.attention_core_merged(
            q.reshape(B, S, cfg.attn_dim), k, v,
            q_positions=positions, kv_positions=positions,
            n_kv_heads=cfg.n_kv_heads, causal=cfg.causal,
            sliding_window=cfg.sliding_window, impl=impl,
            query_chunk=cfg.query_chunk or S, cache_kind=cache_kind)
        return out, (k, v)
    out = attn_mod.attention_core(
        q, k, v, q_positions=positions, kv_positions=positions,
        causal=cfg.causal, sliding_window=cfg.sliding_window, impl=impl,
        query_chunk=cfg.query_chunk or q.shape[1])
    return out.reshape(B, S, cfg.attn_dim), (k, v)


def _cross_attention_seq(lp, cfg: ModelConfig, u, vision, merged: bool, impl: str):
    """Cross-attn: queries from text stream, K/V from vision tokens (no rope)."""
    q, k, v = _project_qkv(lp, cfg, u, vision, merged)
    B, S = u.shape[0], u.shape[1]
    nv = vision.shape[1]
    qpos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    kpos = jnp.broadcast_to(jnp.arange(nv, dtype=jnp.int32), (B, nv))
    out = attn_mod.attention_core(q, k, v, q_positions=qpos, kv_positions=kpos,
                                  causal=False, sliding_window=0, impl=impl,
                                  query_chunk=cfg.query_chunk or q.shape[1])
    return out.reshape(B, S, cfg.attn_dim), (k, v)


def _attn_out_proj(lp, cat):
    return cat @ lp["wp"].astype(cat.dtype)


# ---------------------------------------------------------------------------
# FFN dispatch (dense or MoE)
# ---------------------------------------------------------------------------

def _apply_ffn_or_moe(p, cfg: ModelConfig, x, dropless: bool = False):
    """Returns (out, aux_loss)."""
    if "moe" in p:
        out, aux = moe_mod.apply_moe(
            p["moe"], x, n_experts=cfg.n_experts,
            experts_per_token=cfg.experts_per_token,
            capacity_factor=cfg.capacity_factor, ffn_type=cfg.ffn_type,
            dropless=dropless, impl=cfg.moe_impl,
            group_size=cfg.moe_group or x.shape[0] * x.shape[1])
        return out, aux
    return ffn_mod.apply_ffn(p["ffn"], x, cfg.ffn_type), jnp.float32(0.0)


# ---------------------------------------------------------------------------
# one block, sequence mode
# ---------------------------------------------------------------------------

def apply_block_seq(p, cfg: ModelConfig, kind: str, u, ctx) -> Tuple[jnp.ndarray, jnp.ndarray, Any]:
    """Returns (out_stream, aux_loss, kv_for_cache)."""
    style = cfg.block_style
    merged = _is_merged(style)
    impl = ctx.get("impl", "xla")
    positions = ctx["positions"]
    aux = jnp.float32(0.0)
    kv = None

    if kind == "ssm":
        if style == "standard":
            out = u + m2.apply_mamba2_seq(p["ssm"], apply_rmsnorm(p["norm1"], u), cfg, impl=impl) \
                if "norm1" in p else u + m2.apply_mamba2_seq(p["ssm"], u, cfg, impl=impl)
        else:  # skipless ssm (no paper technique applicable)
            out = m2.apply_mamba2_seq(p["ssm"], u, cfg, impl=impl)
        return out, aux, None

    def attn_fn(x):
        nonlocal kv
        if kind == "cross":
            cat, kv_ = _cross_attention_seq(p["attn"], cfg, x, ctx["vision"], merged, impl)
        else:
            cat, kv_ = _self_attention_seq(
                p["attn"], cfg, x, positions, merged, impl,
                qkv_sharding=ctx.get("qkv_sharding"),
                merged_core=ctx.get("merged_core", False),
                cache_kind=ctx.get("cache_kind", "dense"),
                q8_block=ctx.get("q8_block", 0),
                q8_true_len=ctx.get("q8_true_len"))
        kv = kv_
        return cat

    def mixer_fn(x):
        """kind-specific token mixer producing a d_model stream delta."""
        cat = attn_fn(x)
        if kind == "hybrid":
            a = _attn_out_proj(p["attn"], cat)
            s = m2.apply_mamba2_seq(p["ssm"], x, cfg, impl=impl)
            return 0.5 * (a + s)
        if merged:
            return cat  # no P; FFN input matrices carry the P fold
        return _attn_out_proj(p["attn"], cat)

    if style == "standard":
        if cfg.parallel_block:
            n = apply_rmsnorm(p["norm1"], u)
            f, aux = _apply_ffn_or_moe(p, cfg, n)
            out = u + mixer_fn(n) + f
        else:
            h = u + mixer_fn(apply_rmsnorm(p["norm1"], u))
            f, aux = _apply_ffn_or_moe(p, cfg, apply_rmsnorm(p["norm2"], h))
            out = h + f
    elif style == "residual_qpfree":
        if cfg.parallel_block:
            n = apply_rmsnorm(p["norm1"], u)
            f, aux = _apply_ffn_or_moe(p, cfg, n)
            out = u + mixer_fn(n) + f
        else:
            h = u + mixer_fn(apply_rmsnorm(p["norm1"], u))
            f, aux = _apply_ffn_or_moe(p, cfg, apply_rmsnorm(p["norm2"], h))
            out = h + f
    elif style == "skipless":
        if cfg.parallel_block:
            f, aux = _apply_ffn_or_moe(p, cfg, u)
            out = mixer_fn(u) + f
        else:
            mid = mixer_fn(u)
            out, aux = _apply_ffn_or_moe(p, cfg, mid)
    elif style == "skipless_merged":
        if cfg.parallel_block:
            f, aux = _apply_ffn_or_moe(p, cfg, u)
            out = mixer_fn(u) + f  # Fig 3(a): cat adds directly (no P)
        else:
            mid = mixer_fn(u)  # = cat for dense/moe/vlm; fused for hybrid
            out, aux = _apply_ffn_or_moe(p, cfg, mid)
        if "b_out" in p:  # folded b_q of the NEXT block (affine merge)
            out = out + p["b_out"].astype(out.dtype)
    else:
        raise ValueError(style)

    return out, aux, kv


# ---------------------------------------------------------------------------
# full-sequence forward (train / encode / prefill)
# ---------------------------------------------------------------------------

def _scan_blocks_seq(params, cfg: ModelConfig, h, ctx, collect_kv: bool,
                     remat: bool = False, unroll: bool = False):
    plan = layer_plan(cfg)
    aux0 = jnp.float32(0.0)
    u = True if unroll else 1

    def block_fn(kind):
        def f(carry, lp):
            h, aux = carry
            out, a, kv = apply_block_seq(lp, cfg, kind, h, ctx)
            if ctx.get("stream_sharding") is not None:
                # sequence parallelism on the layer-boundary stream: the
                # saved-for-backward carries shard over (dp, seq-tp) instead
                # of being replicated across the model axis (§Perf H6)
                out = jax.lax.with_sharding_constraint(
                    out, ctx["stream_sharding"])
            return (out, aux + a), (kv if collect_kv else None)
        if remat == "dots":
            # partial remat: keep matmul outputs, recompute the cheap
            # elementwise/softmax glue — trades some of full-remat's
            # recompute FLOPs for modest extra saved bytes (§Perf H7b)
            return jax.checkpoint(
                f, policy=jax.checkpoint_policies.dots_saveable,
                prevent_cse=False)
        if remat:
            return jax.checkpoint(f, prevent_cse=False)
        return f

    if plan["kind"] == "vlm":
        def group_fn(carry, gp):
            (h, aux) = carry
            (h, aux), kvs_self = jax.lax.scan(block_fn("attn"), (h, aux),
                                              gp["self"], unroll=u)
            (h, aux), kv_cross = block_fn("cross")((h, aux), gp["cross"])
            return (h, aux), (kvs_self, kv_cross)
        gparams = {"self": params["layers"], "cross": params["cross_layers"]}
        (h, aux), kvs = jax.lax.scan(group_fn, (h, aux0), gparams, unroll=u)
        return h, aux, kvs
    else:
        (h, aux), kvs = jax.lax.scan(block_fn(plan["kind"]), (h, aux0),
                                     params["layers"], unroll=u)
        return h, aux, kvs


def embed_inputs(params, cfg: ModelConfig, tokens_or_frames):
    cdt = dtype_of(cfg.dtype)
    if tokens_or_frames.dtype in (jnp.int32, jnp.int64):
        h = apply_embedding(params["embed"], tokens_or_frames, cdt)
        if cfg.block_style in ("skipless", "skipless_merged"):
            # skipless stacks have no residual to carry scale, and GLU FFNs
            # attenuate sub-unit signals quadratically (silu(g)·u ~ 0.5·s²),
            # so 0.02-std embeddings collapse to zero logits and zero grads.
            # Scale the embedding output to the GLU fixed point (std ≈ 2,
            # where silu(g)·u sustains its input scale); He et al. use
            # comparable signal-preserving inits for skipless nets.
            h = h * (2.0 / 0.02)
    else:
        h = tokens_or_frames.astype(cdt)  # stubbed modality frontend output
    if "conv_pos" in params:
        h = apply_conv_pos(params["conv_pos"], h)
    # merged models: frame inputs can't fold Q_0 into an embedding table, so
    # the merge keeps Q_0 as an explicit input projection (see core/merge.py)
    if "input_proj" in params:
        h = h @ params["input_proj"].astype(h.dtype)
    if "embed_bias" in params:  # folded b_q of the first block (affine merge)
        h = h + params["embed_bias"].astype(h.dtype)
    return h


def forward_seq(params, cfg: ModelConfig, inputs, *, positions=None,
                vision=None, impl: str = "xla", remat: bool = False,
                collect_kv: bool = False, unroll: bool = False,
                stream_sharding=None, qkv_sharding=None,
                merged_core: bool = False, cache_kind: str = "dense",
                q8_block: int = 0, q8_true_len=None):
    """Full-sequence forward. inputs: int tokens (B,S) or frames (B,S,d).

    ``merged_core`` routes self-attention through the stream-as-query
    merged core (prefill backends set it for merged qp layouts);
    ``cache_kind`` tags which prefill kernel-table row the core fetches.
    ``q8_block``/``q8_true_len`` (paged_q8 prefill) make every self-
    attention layer attend over the pool-granularity QUANTIZED K/V view
    (``_self_attention_seq``) while collecting the raw floats.
    """
    B, S = inputs.shape[0], inputs.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    h = embed_inputs(params, cfg, inputs)
    ctx = {"positions": positions, "vision": None if vision is None else
           vision.astype(h.dtype), "impl": impl,
           "stream_sharding": stream_sharding, "qkv_sharding": qkv_sharding,
           "merged_core": merged_core, "cache_kind": cache_kind,
           "q8_block": q8_block, "q8_true_len": q8_true_len}
    h, aux, kvs = _scan_blocks_seq(params, cfg, h, ctx, collect_kv, remat,
                                   unroll=unroll)
    if "final_norm" in params:
        h = apply_rmsnorm(params["final_norm"], h)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = apply_unembedding(table, h)
    return logits, aux, kvs


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def lm_loss(logits: jnp.ndarray, labels: jnp.ndarray, *, z_loss: float = 1e-4,
            ignore_index: int = -100, vocab_size: int = 0):
    """Token-mean cross entropy (fp32) + z-loss. labels (B,S) int32.

    ``vocab_size``: logical vocab — logits for padded ids (>= vocab_size)
    are masked out of the softmax (see ModelConfig.padded_vocab)."""
    logits = logits.astype(jnp.float32)
    if vocab_size and vocab_size < logits.shape[-1]:
        pad_mask = jnp.arange(logits.shape[-1]) < vocab_size
        logits = jnp.where(pad_mask, logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    mask = (labels != ignore_index).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# decode cache
# ---------------------------------------------------------------------------

class DecodeCache(NamedTuple):
    k: Optional[jnp.ndarray]  # (L, B, Sc, Hkv, Dh) — Sc = window or max_len
    v: Optional[jnp.ndarray]
    kv_pos: Optional[jnp.ndarray]  # (B, Sc) int32, -1 = empty (shared across layers)
    length: jnp.ndarray  # (B,) int32 — tokens generated so far (= next position)
    ssm: Optional[m2.SSMState]  # stacked (L, …) for ssm/hybrid
    cross_k: Optional[jnp.ndarray]  # (Lc, B, nv, Hkv, Dh)
    cross_v: Optional[jnp.ndarray]


def n_attn_layers(cfg: ModelConfig) -> int:
    """Number of self-attention layers holding per-token KV (the leading
    cache axis of both serving cache kinds)."""
    plan = layer_plan(cfg)
    if plan["kind"] in ("attn", "hybrid"):
        return plan["n"]
    if plan["kind"] == "vlm":
        return plan["n_groups"] * plan["self_per_group"]
    return 0


def cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    """Shapes for an empty cache (used by init and by input_specs)."""
    plan = layer_plan(cfg)
    cdt = dtype_of(cfg.dtype)
    Sc = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    spec: Dict[str, Any] = {}
    n_attn = n_attn_layers(cfg)
    if n_attn:
        spec["k"] = ((n_attn, batch, Sc, cfg.n_kv_heads, cfg.d_head), cdt)
        spec["v"] = spec["k"]
        spec["kv_pos"] = ((batch, Sc), jnp.int32)
    spec["length"] = ((batch,), jnp.int32)
    if cfg.ssm_state:
        n_ssm = plan["n"]
        spec["ssm"] = {
            "ssm": ((n_ssm, batch, cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
            "conv": ((n_ssm, batch, cfg.ssm_conv_width - 1, m2.conv_channels(cfg)), jnp.float32),
        }
    if plan["kind"] == "vlm":
        spec["cross_k"] = ((plan["n_groups"], batch, cfg.n_vision_tokens,
                            cfg.n_kv_heads, cfg.d_head), cdt)
        spec["cross_v"] = spec["cross_k"]
    return spec


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> DecodeCache:
    spec = cache_spec(cfg, batch, max_len)

    def z(name, fill=0):
        if name not in spec:
            return None
        sh, dt = spec[name]
        return jnp.full(sh, fill, dt)

    ssm = None
    if "ssm" in spec:
        ssm = m2.SSMState(
            ssm=jnp.zeros(spec["ssm"]["ssm"][0], jnp.float32),
            conv=jnp.zeros(spec["ssm"]["conv"][0], jnp.float32),
        )
    return DecodeCache(
        k=z("k"), v=z("v"),
        kv_pos=None if "kv_pos" not in spec else jnp.full(spec["kv_pos"][0], -1, jnp.int32),
        length=jnp.zeros((batch,), jnp.int32),
        ssm=ssm, cross_k=z("cross_k"), cross_v=z("cross_v"),
    )


# ---------------------------------------------------------------------------
# prefill: full-sequence forward that also fills the cache
# ---------------------------------------------------------------------------

def _last_logits_and_length(logits, true_len, B, S):
    """Gather the last REAL position's logits (bucketed prompts are
    right-padded; causality keeps positions < true_len exact)."""
    if true_len is None:
        return logits[:, -1, :], jnp.full((B,), S, jnp.int32)
    true_len = jnp.asarray(true_len, jnp.int32)
    last = jnp.take_along_axis(
        logits, (true_len - 1)[:, None, None], axis=1)[:, 0, :]
    return last, true_len


class DensePrefillDest(NamedTuple):
    """Destination of a dense prefill: build a fresh ``DecodeCache`` of
    ``cache_len`` positions.  ``full_cache`` keeps the cache ``cache_len``
    long even for sliding-window configs (whose dense serving cache is a
    window-sized ring buffer), for callers that need every prompt
    position.  Both fields are STATIC (python ints/bools, resolved at
    trace time)."""
    cache_len: int
    full_cache: bool = False


class PagedPrefillDest(NamedTuple):
    """Destination of a direct-to-page paged prefill: the prompt's KV is
    written straight into the mapped physical blocks of the pool — no
    worst-case-length intermediate cache and no post-prefill scatter pass.

    ``k_pool``/``v_pool`` are (L, NB, bs, Hkv, Dh) page pools;
    ``block_ids`` is (ceil(S/bs),) int32 mapping this request's logical
    (absolute) block j to its physical page, with -1 for blocks that must
    NOT be written (prefix-shared pages already holding the prefix —
    possibly extended by another live request's decoded tokens — bucket-
    padding blocks past the prompt, and, under a sliding window, prompt
    blocks wholly out of every future query's window: a windowed request's
    table is a bounded ring of ceil(window/bs)+1 recycled slots and only
    the live window's blocks are mapped — ``kernels.paging``)."""
    k_pool: Any
    v_pool: Any
    block_ids: Any


class PagedQ8PrefillDest(NamedTuple):
    """Destination of a direct-to-page QUANTIZED paged prefill: the
    ``PagedPrefillDest`` contract over int8 pools — ``k_pool``/``v_pool``
    are (L, NB, bs, Hkv, Dh) int8 pages, ``k_scale``/``v_scale`` their
    (L, NB, Hkv) float32 per-(page, kv-head) scales (``kernels.quant``),
    and ``block_ids`` is the same (ceil(S/bs),) physical mapping with -1
    dropping the write.  The prefill program quantizes the collected
    prompt KV at pool granularity and scatters ints AND scales into the
    mapped pages — a full-precision pool never exists."""
    k_pool: Any
    v_pool: Any
    k_scale: Any
    v_scale: Any
    block_ids: Any


def prefill_style_key(cfg: ModelConfig) -> str:
    """Projection-style axis of the PREFILL backend registry key.

    "merged" iff the whole-prompt forward can run the stream-as-query
    attention core in every self-attention layer (qp variant of the merged
    styles on attention-only stacks: the stream IS the query and no P
    exists, so prefill attention reads only K*/V* weights).  kp/vp merged
    variants stay "generic" — their eliminated projection is an identity
    inside ``_project_qkv`` and no dedicated route exists (exactly as at
    decode).  ssm/hybrid/vlm stacks are "generic" too (hybrid keeps P;
    vlm interleaves cross-attention layers that read vision tokens).
    """
    if layer_plan(cfg)["kind"] != "attn":
        return "generic"
    if cfg.block_style in ("skipless_merged", "residual_qpfree") \
            and cfg.merged_variant == "qp":
        return "merged"
    return "generic"


def _prefill_seq(params, cfg: ModelConfig, inputs, ctx, *,
                 merged_core: bool, cache_kind: str):
    """The shared full-sequence pass every prefill backend starts with."""
    return forward_seq(params, cfg, inputs, vision=ctx.get("vision"),
                       impl=ctx.get("impl", "xla"), collect_kv=True,
                       unroll=ctx.get("unroll", False),
                       qkv_sharding=ctx.get("qkv_sharding"),
                       merged_core=merged_core, cache_kind=cache_kind)


def _finish_paged(cfg: ModelConfig, logits, kvs, dest: PagedPrefillDest, ctx,
                  B: int, S: int):
    """Scatter the collected prompt KV direct-to-page (see
    ``PagedPrefillDest``) and gather the last real position's logits."""
    k_pool, v_pool, block_ids = dest
    last_logits, _ = _last_logits_and_length(logits, ctx.get("true_len"), B, S)
    ks, vs = kvs  # (L, 1, S, Hkv, Dh)
    L, bs, NB = k_pool.shape[0], k_pool.shape[2], k_pool.shape[1]
    nbk = block_ids.shape[0]
    pad = nbk * bs - S
    if pad:
        ks = jnp.pad(ks, [(0, 0), (0, 0), (0, pad), (0, 0), (0, 0)])
        vs = jnp.pad(vs, [(0, 0), (0, 0), (0, pad), (0, 0), (0, 0)])
    kb = ks[:, 0].reshape(L, nbk, bs, *ks.shape[3:])
    vb = vs[:, 0].reshape(L, nbk, bs, *vs.shape[3:])
    # unmapped/-1 destinations are clamped out of range and DROPPED:
    # shared-prefix pages (owned content, maybe another request's
    # decoded tail) and bucket-padding blocks are never touched
    safe = jnp.where(block_ids >= 0, block_ids, NB).astype(jnp.int32)
    k_pool = k_pool.at[:, safe].set(kb.astype(k_pool.dtype), mode="drop")
    v_pool = v_pool.at[:, safe].set(vb.astype(v_pool.dtype), mode="drop")
    return last_logits, (k_pool, v_pool)


def _finish_paged_q8(cfg: ModelConfig, logits, kvs, dest: PagedQ8PrefillDest,
                     ctx, B: int, S: int):
    """Quantize the collected prompt KV at pool granularity and scatter
    ints + scales direct-to-page.  Positions >= true_len are masked to
    zero BEFORE the per-block absmax — the same mask and the same
    ``quant.q8_quantize_pages`` the prefill attention fake-quanted with,
    so the pool bytes are bit-identical to what the prompt's own logits
    already attended over (and padding garbage never inflates a real
    block's scale)."""
    k_pool, v_pool, k_scale, v_scale, block_ids = dest
    last_logits, _ = _last_logits_and_length(logits, ctx.get("true_len"), B, S)
    ks, vs = kvs  # (L, 1, S, Hkv, Dh)
    L, bs, NB = k_pool.shape[0], k_pool.shape[2], k_pool.shape[1]
    nbk = block_ids.shape[0]
    pad = nbk * bs - S
    if pad:
        ks = jnp.pad(ks, [(0, 0), (0, 0), (0, pad), (0, 0), (0, 0)])
        vs = jnp.pad(vs, [(0, 0), (0, 0), (0, pad), (0, 0), (0, 0)])
    true_len = ctx.get("true_len")
    limit = jnp.int32(S) if true_len is None else \
        jnp.asarray(true_len, jnp.int32).reshape(B)[0]
    pos = jnp.arange(nbk * bs, dtype=jnp.int32)
    valid = (pos < limit)[None, None, :, None, None]
    ks = jnp.where(valid, ks.astype(jnp.float32), 0.0)
    vs = jnp.where(valid, vs.astype(jnp.float32), 0.0)
    kb = ks[:, 0].reshape(L, nbk, bs, *ks.shape[3:])
    vb = vs[:, 0].reshape(L, nbk, bs, *vs.shape[3:])
    kq, ksc = quant.q8_quantize_pages(kb)  # ints (L,nbk,bs,Hkv,Dh), (L,nbk,Hkv)
    vq, vsc = quant.q8_quantize_pages(vb)
    # same drop-scatter as _finish_paged, extended to the scale rows:
    # a page and its scale move as one unit
    safe = jnp.where(block_ids >= 0, block_ids, NB).astype(jnp.int32)
    k_pool = k_pool.at[:, safe].set(kq, mode="drop")
    v_pool = v_pool.at[:, safe].set(vq, mode="drop")
    k_scale = k_scale.at[:, safe].set(ksc, mode="drop")
    v_scale = v_scale.at[:, safe].set(vsc, mode="drop")
    return last_logits, (k_pool, v_pool, k_scale, v_scale)


def _finish_dense(params, cfg: ModelConfig, inputs, logits, kvs,
                  dest: DensePrefillDest, ctx, B: int, S: int):
    """Place the collected prompt KV into a fresh ``DecodeCache`` (ring-
    phased under a sliding window) and gather the last real logits."""
    true_len = ctx.get("true_len")
    cache_cfg = cfg.with_(sliding_window=0) if dest.full_cache else cfg
    cache = init_cache(cache_cfg, B, dest.cache_len)
    Sc = cache.k.shape[2] if cache.k is not None else 0

    def place(kv_stacked):
        # kv_stacked: (L, B, S, Hkv, Dh) -> keep the last Sc positions,
        # ROLLED into ring phase: decode writes position p at slot p % Sc,
        # so position S-Sc+i must land at index (S-Sc+i) % Sc — without the
        # roll, decode after a longer-than-window prompt overwrites live
        # in-window entries instead of the expired ones.
        if S >= Sc:
            kept = kv_stacked[:, :, S - Sc:, :, :]
            shift = (S - Sc) % Sc
            return jnp.roll(kept, shift, axis=2) if shift else kept
        pad = [(0, 0), (0, 0), (0, Sc - S), (0, 0), (0, 0)]
        return jnp.pad(kv_stacked, pad)

    last_logits, length = _last_logits_and_length(logits, true_len, B, S)
    if true_len is not None:
        true_len = length  # normalized int32 view for the kv_pos mask below
    new = cache._replace(length=length)
    plan = layer_plan(cfg)
    if plan["kind"] == "vlm":
        kv_self, kv_cross = kvs  # ((ng, spg, B,S,H,D)×2, (ng, B,nv,H,D)×2)
        ks, vs = kv_self
        ng, spg = ks.shape[0], ks.shape[1]
        ks = ks.reshape(ng * spg, *ks.shape[2:])
        vs = vs.reshape(ng * spg, *vs.shape[2:])
        new = new._replace(k=place(ks), v=place(vs),
                           cross_k=kv_cross[0], cross_v=kv_cross[1])
    elif cfg.has_attention:
        ks, vs = kvs
        new = new._replace(k=place(ks), v=place(vs))
    if new.kv_pos is not None:
        pos = jnp.arange(Sc, dtype=jnp.int32)[None, :] + max(S - Sc, 0)
        valid = pos < (S if true_len is None else true_len[:, None])
        kvp = jnp.where(valid, pos, -1).astype(jnp.int32) * \
            jnp.ones((B, 1), jnp.int32)
        if S >= Sc and (S - Sc) % Sc:  # match place()'s ring phase
            kvp = jnp.roll(kvp, (S - Sc) % Sc, axis=1)
        new = new._replace(kv_pos=kvp)
    if cfg.ssm_state:
        # re-run mamba path collecting final states (cheap relative to attn).
        # NOTE: SSM state is not position-masked, so bucketed (padded)
        # prompts are unsupported here — the engine disables bucketing for
        # ssm/hybrid families.
        ssm = _prefill_ssm_states(params, cfg, inputs, ctx.get("vision"),
                                  ctx.get("impl", "xla"),
                                  ctx.get("unroll", False))
        new = new._replace(ssm=ssm)
    return last_logits, new


# --- the four registered prefill routes --------------------------------------

def _prefill_dense_generic(params, cfg: ModelConfig, inputs, dest, ctx):
    """Registered prefill backend ("dense", "generic"): projects q/k/v as
    the config dictates (kp/vp merged variants pass through — their
    eliminated projection is an identity) and fills a ``DecodeCache``.
    Covers every family (attn/ssm/hybrid/vlm)."""
    B, S = inputs.shape[0], inputs.shape[1]
    logits, _, kvs = _prefill_seq(params, cfg, inputs, ctx,
                                  merged_core=False, cache_kind="dense")
    return _finish_dense(params, cfg, inputs, logits, kvs, dest, ctx, B, S)


def _prefill_dense_merged(params, cfg: ModelConfig, inputs, dest, ctx):
    """Registered prefill backend ("dense", "merged"): the Q/P-removed
    PREFILL fast path — every self-attention layer runs the stream-as-
    query core (``attention_core_merged`` / the merged flash kernel), so
    the whole-prompt forward reads no Q or P weights and moves no
    head-major transposes; the filled cache is byte-identical in layout
    to the generic backend's."""
    B, S = inputs.shape[0], inputs.shape[1]
    logits, _, kvs = _prefill_seq(params, cfg, inputs, ctx,
                                  merged_core=True, cache_kind="dense")
    return _finish_dense(params, cfg, inputs, logits, kvs, dest, ctx, B, S)


def _prefill_paged_generic(params, cfg: ModelConfig, inputs, dest, ctx):
    """Registered prefill backend ("paged", "generic"): generic projection
    path, prompt KV written DIRECT-TO-PAGE into the mapped pool blocks."""
    B, S = inputs.shape[0], inputs.shape[1]
    logits, _, kvs = _prefill_seq(params, cfg, inputs, ctx,
                                  merged_core=False, cache_kind="paged")
    return _finish_paged(cfg, logits, kvs, dest, ctx, B, S)


def _prefill_paged_merged(params, cfg: ModelConfig, inputs, dest, ctx):
    """Registered prefill backend ("paged", "merged"): stream-as-query
    attention AND direct-to-page KV writes — prefill HBM traffic is the
    prompt's own pages plus K*/V*-only weight reads."""
    B, S = inputs.shape[0], inputs.shape[1]
    logits, _, kvs = _prefill_seq(params, cfg, inputs, ctx,
                                  merged_core=True, cache_kind="paged")
    return _finish_paged(cfg, logits, kvs, dest, ctx, B, S)


def _prefill_seq_q8(params, cfg: ModelConfig, inputs,
                    dest: PagedQ8PrefillDest, ctx, *, merged_core: bool):
    """Quantized-pool variant of ``_prefill_seq``: thread the pool's
    block size + the prompt's true length into the stack so every layer
    fake-quants its K/V at pool granularity (``_self_attention_seq``)."""
    true_len = ctx.get("true_len")
    q8_true_len = None if true_len is None else \
        jnp.asarray(true_len, jnp.int32).reshape(inputs.shape[0])
    return forward_seq(params, cfg, inputs, vision=ctx.get("vision"),
                       impl=ctx.get("impl", "xla"), collect_kv=True,
                       unroll=ctx.get("unroll", False),
                       qkv_sharding=ctx.get("qkv_sharding"),
                       merged_core=merged_core, cache_kind="paged_q8",
                       q8_block=int(dest.k_pool.shape[2]),
                       q8_true_len=q8_true_len)


def _prefill_paged_q8_generic(params, cfg: ModelConfig, inputs, dest, ctx):
    """Registered prefill backend ("paged_q8", "generic"): generic
    projection path attending over the quantized K/V view, prompt KV
    quantized and written direct-to-page as int8 + scales."""
    B, S = inputs.shape[0], inputs.shape[1]
    logits, _, kvs = _prefill_seq_q8(params, cfg, inputs, dest, ctx,
                                     merged_core=False)
    return _finish_paged_q8(cfg, logits, kvs, dest, ctx, B, S)


def _prefill_paged_q8_merged(params, cfg: ModelConfig, inputs, dest, ctx):
    """Registered prefill backend ("paged_q8", "merged"): stream-as-query
    attention with IN-KERNEL dequant (the q8 merged flash kernel) AND
    int8 direct-to-page writes — prefill attention streams one byte per
    pooled element."""
    B, S = inputs.shape[0], inputs.shape[1]
    logits, _, kvs = _prefill_seq_q8(params, cfg, inputs, dest, ctx,
                                     merged_core=True)
    return _finish_paged_q8(cfg, logits, kvs, dest, ctx, B, S)


backends.register_prefill_backend("dense", "generic", _prefill_dense_generic)
backends.register_prefill_backend("dense", "merged", _prefill_dense_merged,
                                  fast_path=True)
backends.register_prefill_backend("paged", "generic", _prefill_paged_generic)
backends.register_prefill_backend("paged", "merged", _prefill_paged_merged,
                                  fast_path=True)
backends.register_prefill_backend("paged_q8", "generic",
                                  _prefill_paged_q8_generic)
backends.register_prefill_backend("paged_q8", "merged",
                                  _prefill_paged_q8_merged, fast_path=True)


def forward_prefill(params, cfg: ModelConfig, inputs, dest=None, *,
                    cache_len: int = 0, vision=None, impl: str = "xla",
                    unroll: bool = False, qkv_sharding=None, true_len=None,
                    full_cache: bool = False, pages=None):
    """Cache-aware prefill — the single dispatcher over the
    ``models.backends`` PREFILL registry.

    ``dest`` names the destination cache and selects the cache_kind axis
    of the registry key; the config selects the style axis
    (``prefill_style_key``), so merged (Q/P-removed) "qp" models take the
    stream-as-query fast path in every self-attention layer while every
    other combination routes through the generic backend:

    * ``DensePrefillDest(cache_len, full_cache=False)`` — returns
      (last_token_logits (B, V), ``DecodeCache`` of ``cache_len``
      positions).
    * ``PagedPrefillDest(k_pool, v_pool, block_ids)`` — writes the
      prompt's KV DIRECTLY into the mapped physical pool blocks (see the
      dest's docstring for the block_ids contract) and returns
      (last_token_logits (B, V), (k_pool, v_pool)).

    ``true_len`` (B,) int32 supports bucketed prompts: ``inputs`` may be
    RIGHT-padded to a bucket length, and causality guarantees positions
    < true_len are unaffected by the padding — the returned logits are
    gathered at ``true_len - 1`` and the cache marks padded positions
    empty (dense: kv_pos = -1; paged: in-page positions past ``length``,
    hidden by the causal mask) with ``length = true_len``, so decode
    overwrites them in order.  ``None`` means the whole sequence is real.

    Invalid requests (paged on a non-attention stack, paged batch > 1,
    too few mapped blocks, non-positive dense cache_len) raise ValueError
    at the dispatch boundary; unknown (cache_kind, style, impl) combos
    raise the registry's KeyError.  DEPRECATED: calling without ``dest``
    — the old ``cache_len=``/``pages=`` mega-signature — still works via
    a compatibility shim but emits DeprecationWarning (matching the
    ``forward_decode``/``forward_decode_paged`` shims).
    """
    if dest is None:
        warnings.warn(
            "forward_prefill's cache_len=/pages= mega-signature is "
            "deprecated; pass dest=DensePrefillDest(cache_len, full_cache) "
            "or dest=PagedPrefillDest(k_pool, v_pool, block_ids) — the "
            "dispatcher routes it through the models.backends prefill "
            "registry either way", DeprecationWarning, stacklevel=2)
        dest = (PagedPrefillDest(*pages) if pages is not None
                else DensePrefillDest(cache_len=cache_len,
                                      full_cache=full_cache))
    elif pages is not None or cache_len or full_cache:
        # a half-migrated call mixing both conventions would silently drop
        # the legacy arguments — fail instead of prefilling the wrong kind
        raise ValueError(
            "forward_prefill got both dest= and legacy cache_len=/pages=/"
            "full_cache= arguments; the destination lives entirely in "
            "dest — drop the legacy kwargs")

    B, S = int(inputs.shape[0]), int(inputs.shape[1])
    if isinstance(dest, (PagedPrefillDest, PagedQ8PrefillDest)):
        quantized = isinstance(dest, PagedQ8PrefillDest)
        kind = "paged_q8" if quantized else "paged"
        plan = layer_plan(cfg)
        if plan["kind"] != "attn":
            raise ValueError(
                f"paged prefill supports attention-only stacks, not "
                f"{plan['kind']!r} (family {cfg.family!r})")
        if B != 1:
            raise ValueError(
                f"paged prefill inserts one request at a time, got batch "
                f"size {B}")
        nbk, bs = int(dest.block_ids.shape[0]), int(dest.k_pool.shape[2])
        if nbk * bs < S:
            raise ValueError(
                f"{type(dest).__name__}.block_ids maps {nbk} blocks of "
                f"{bs} tokens — too few for a {S}-token prompt")
        if quantized and S % bs:
            # the whole-prompt fake-quant reshapes (B, S) into S/bs pool
            # blocks, so the bucket length must tile exactly (every
            # serving bucket is a power of two >= the block size)
            raise ValueError(
                f"paged_q8 prefill needs the (padded) prompt length to be "
                f"a multiple of the page size: {S} % {bs} != 0")
    elif isinstance(dest, DensePrefillDest):
        kind = "dense"
        if dest.cache_len <= 0:
            raise ValueError(
                "dense prefill needs DensePrefillDest.cache_len > 0, got "
                f"{dest.cache_len!r}")
    else:
        raise ValueError(
            f"unknown prefill destination {type(dest).__name__!r}; expected "
            "DensePrefillDest, PagedPrefillDest, or PagedQ8PrefillDest (or "
            "register a PrefillBackend for a new cache kind)")

    backend = backends.get_prefill_backend(kind, prefill_style_key(cfg), impl)
    ctx = {"vision": vision, "impl": impl, "unroll": unroll,
           "qkv_sharding": qkv_sharding, "true_len": true_len}
    return backend.run(params, cfg, inputs, dest, ctx)


def _prefill_ssm_states(params, cfg: ModelConfig, inputs, vision, impl,
                        unroll: bool = False):
    """Second pass over ssm/hybrid layers to collect final SSM states.

    Exactness note: for ``hybrid``/``ssm`` families the stream must be
    identical to the main pass — it is, because we rerun the same blocks; we
    just additionally thread ``return_state``. Implemented as a dedicated scan
    to keep the common (attention-only) prefill path free of SSM plumbing.
    """
    B, S = inputs.shape[0], inputs.shape[1]
    h = embed_inputs(params, cfg, inputs)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def f(carry, lp):
        h = carry
        kind = layer_plan(cfg)["kind"]
        # mirror apply_block_seq but thread state out of the ssm mixer
        if kind == "ssm":
            if cfg.block_style == "standard":
                delta, st = m2.apply_mamba2_seq(lp["ssm"], apply_rmsnorm(lp["norm1"], h),
                                                cfg, return_state=True, impl=impl)
                out = h + delta
            else:
                out, st = m2.apply_mamba2_seq(lp["ssm"], h, cfg, return_state=True, impl=impl)
            return out, st
        # hybrid
        style = cfg.block_style
        merged = _is_merged(style)
        x = apply_rmsnorm(lp["norm1"], h) if "norm1" in lp else h
        cat, _ = _self_attention_seq(lp["attn"], cfg, x, positions, merged, impl)
        a = _attn_out_proj(lp["attn"], cat)
        s, st = m2.apply_mamba2_seq(lp["ssm"], x, cfg, return_state=True, impl=impl)
        mix = 0.5 * (a + s)
        if style == "standard" or style == "residual_qpfree":
            hh = h + mix
            f_, _ = _apply_ffn_or_moe(lp, cfg, apply_rmsnorm(lp["norm2"], hh))
            out = hh + f_
        else:
            out, _ = _apply_ffn_or_moe(lp, cfg, mix)
        return out, st

    _, states = jax.lax.scan(f, h, params["layers"], unroll=True if unroll else 1)
    return states


# ---------------------------------------------------------------------------
# decode: one token against the cache
# ---------------------------------------------------------------------------

def _rope_and_insert(cfg: ModelConfig, q, k_new, v_new, k_layer, v_layer,
                     length):
    """RoPE the step's q/k at position ``length`` and write the new k/v into
    the ring-buffer slot (slot = length % Sc under sliding window).
    Returns (q, k_layer, v_layer)."""
    pos = length[:, None]  # (B,1)
    q = apply_rope(q, pos, style=cfg.rope_style, theta=cfg.rope_theta,
                   fraction=cfg.rope_fraction)
    k_new = apply_rope(k_new, pos, style=cfg.rope_style, theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)
    Sc = k_layer.shape[1]
    slot = (length % Sc).astype(jnp.int32)

    def upd(cache, new, i):
        return jax.lax.dynamic_update_slice(cache, new, (i, 0, 0))

    k_layer = jax.vmap(upd)(k_layer, k_new.astype(k_layer.dtype), slot)
    v_layer = jax.vmap(upd)(v_layer, v_new.astype(v_layer.dtype), slot)
    return q, k_layer, v_layer


def _attn_step_dense(lp, cfg: ModelConfig, u1, k_layer, v_layer, ctx):
    """Registered backend ("dense", "generic"): projects q/k/v as the
    config dictates (kp/vp merged variants pass through — their eliminated
    projection is an identity inside ``_project_qkv``).

    u1 (B,1,d); k_layer/v_layer (B,Sc,Hkv,Dh). Returns (cat, new_k, new_v).
    """
    B, length = u1.shape[0], ctx["length"]
    merged = _is_merged(cfg.block_style)
    q, k_new, v_new = _project_qkv(lp, cfg, u1, u1, merged)
    q, k_layer, v_layer = _rope_and_insert(cfg, q, k_new, v_new,
                                           k_layer, v_layer, length)
    out = attn_mod.decode_attention_core_positions(
        q[:, 0], k_layer, v_layer,
        kv_positions=ctx["kv_pos"], q_position=length,
        sliding_window=cfg.sliding_window, impl=ctx["impl"])
    return out.reshape(B, 1, cfg.attn_dim), k_layer, v_layer


def _qkv_reanchor(ctx, q, k_new, v_new):
    """Merged styles lose the TP sharding anchor for q (no wq matmul to
    propagate head-sharding from) — same fix as ``_self_attention_seq``."""
    sh = ctx.get("qkv_sharding")
    if sh is None:
        return q, k_new, v_new
    return (jax.lax.with_sharding_constraint(q, sh),
            jax.lax.with_sharding_constraint(k_new, sh),
            jax.lax.with_sharding_constraint(v_new, sh))


def _attn_step_dense_merged(lp, cfg: ModelConfig, u1, k_layer, v_layer, ctx):
    """Registered backend ("dense", "merged"): the Q/P-removed decode fast
    path — paper Fig 1b cashed in at serve time.  The residual stream is
    the query basis, so the only attention-side weights read per token are
    K*/V*: no d×d Q matmul, no P matmul, and the attention output lands
    directly in the FFN-input basis (the kernel also consumes the cache
    in its native layout).  Numerically identical to the generic backend
    with variant "qp"; it exists so serving never touches the eliminated
    projections.
    """
    B, length = u1.shape[0], ctx["length"]
    # variant "qp": _project_qkv returns the stream itself as q (identity)
    q, k_new, v_new = _project_qkv(lp, cfg, u1, u1, True)
    q, k_new, v_new = _qkv_reanchor(ctx, q, k_new, v_new)
    q, k_layer, v_layer = _rope_and_insert(cfg, q, k_new, v_new,
                                           k_layer, v_layer, length)
    out = attn_mod.decode_attention_core_merged(
        q.reshape(B, cfg.attn_dim), k_layer, v_layer,
        kv_positions=ctx["kv_pos"], q_position=length,
        n_kv_heads=cfg.n_kv_heads,
        sliding_window=cfg.sliding_window, impl=ctx["impl"])
    return out.reshape(B, 1, cfg.attn_dim), k_layer, v_layer


def _cross_attn_step(lp, cfg: ModelConfig, u1, ck, cv, merged: bool, impl: str):
    B = u1.shape[0]
    if merged:
        q = u1
    else:
        q = u1 @ lp["wq"].astype(u1.dtype)
        if "bq" in lp:
            q = q + lp["bq"].astype(u1.dtype)
    q = q.reshape(B, cfg.n_heads, cfg.d_head)
    nv = ck.shape[1]
    kv_pos = jnp.broadcast_to(jnp.arange(nv, dtype=jnp.int32), (B, nv))
    out = attn_mod.decode_attention_core_positions(
        q, ck, cv, kv_positions=kv_pos,
        q_position=jnp.full((B,), nv, jnp.int32) + 1,  # attend to all vision tokens
        sliding_window=0, impl=impl)
    return out.reshape(B, 1, cfg.attn_dim)


def apply_block_step(p, cfg: ModelConfig, kind: str, u1, layer_cache, ctx):
    """One block, one token. layer_cache: dict of this layer's cache slices."""
    style = cfg.block_style
    merged = _is_merged(style)
    impl = ctx.get("impl", "xla")
    new_cache = dict(layer_cache)

    if kind == "ssm":
        st = m2.SSMState(ssm=layer_cache["ssm"], conv=layer_cache["conv"])
        x = apply_rmsnorm(p["norm1"], u1) if "norm1" in p else u1
        delta, st2 = m2.apply_mamba2_step(p["ssm"], x[:, 0], cfg, st)
        new_cache.update(ssm=st2.ssm, conv=st2.conv)
        out = u1 + delta[:, None] if style == "standard" else delta[:, None]
        return out, new_cache

    def mixer_fn(x):
        if kind == "cross":
            cat = _cross_attn_step(p["attn"], cfg, x, layer_cache["ck"],
                                   layer_cache["cv"], merged, impl)
            return cat if merged else _attn_out_proj(p["attn"], cat)
        # the registry seam: the per-layer attention route (cache layout ×
        # projection style × impl) was resolved once by forward_step
        cat, nk, nv = ctx["backend"].step(
            p["attn"], cfg, x, layer_cache["k"], layer_cache["v"], ctx)
        new_cache.update(k=nk, v=nv)
        if kind == "hybrid":
            st = m2.SSMState(ssm=layer_cache["ssm"], conv=layer_cache["conv"])
            a = _attn_out_proj(p["attn"], cat)
            s, st2 = m2.apply_mamba2_step(p["ssm"], x[:, 0], cfg, st)
            new_cache.update(ssm=st2.ssm, conv=st2.conv)
            return 0.5 * (a + s[:, None])
        if merged:
            return cat
        return _attn_out_proj(p["attn"], cat)

    if style in ("standard", "residual_qpfree"):
        if cfg.parallel_block:
            n = apply_rmsnorm(p["norm1"], u1)
            f, _ = _apply_ffn_or_moe(p, cfg, n, dropless=True)
            out = u1 + mixer_fn(n) + f
        else:
            h = u1 + mixer_fn(apply_rmsnorm(p["norm1"], u1))
            f, _ = _apply_ffn_or_moe(p, cfg, apply_rmsnorm(p["norm2"], h), dropless=True)
            out = h + f
    else:
        if cfg.parallel_block:
            f, _ = _apply_ffn_or_moe(p, cfg, u1, dropless=True)
            out = mixer_fn(u1) + f
        else:
            mid = mixer_fn(u1)
            out, _ = _apply_ffn_or_moe(p, cfg, mid, dropless=True)
        if style == "skipless_merged" and "b_out" in p:
            out = out + p["b_out"].astype(out.dtype)
    return out, new_cache


def serving_style_key(cfg: ModelConfig) -> str:
    """Projection-style axis of the backend registry key for this config.

    "merged" iff the per-token step can skip every eliminated projection
    (qp variant of the merged styles on attention/vlm stacks — the stream
    IS the query and no P exists).  kp/vp merged variants return
    "generic": their eliminated projection is an identity inside
    ``_project_qkv``, so no dedicated route exists (or is needed — they
    decode token-identically through the generic backend).  Hybrid stacks
    are "generic" too: their merged form keeps P (the FFN input is the
    fused attn+ssm stream), so the fast path's contract doesn't hold.
    """
    plan = layer_plan(cfg)
    if plan["kind"] not in ("attn", "vlm"):
        return "generic"
    if cfg.block_style in ("skipless_merged", "residual_qpfree") \
            and cfg.merged_variant == "qp":
        return "merged"
    return "generic"


def forward_step(params, cfg: ModelConfig, token, cache, *,
                 impl: str = "xla", unroll: bool = False,
                 qkv_sharding=None):
    """One decode step against EITHER serving cache — the single serving
    entry point (serve_step body).

    token: (B,) int32 (or (B,d) frames). Returns (logits (B,V), new cache)
    where ``cache`` (and the return) is a ``DecodeCache`` or a
    ``PagedDecodeCache``; the cache type selects the cache_kind axis of
    the backend registry key and the config selects the style axis
    (``serving_style_key``), so merged (Q/P-removed) "qp" models take the
    fast path — per-token attention reads only K*/V* weights and the
    merged ``b_out`` bias is applied in-stream after the FFN — while
    every other combination routes through the generic backend.
    ``qkv_sharding`` re-anchors TP head sharding for merged styles (no wq
    matmul).  Unknown (cache_kind, style, impl) combinations raise
    KeyError from the registry before any compute.
    """
    paged_q8 = isinstance(cache, PagedQ8DecodeCache)
    paged = paged_q8 or isinstance(cache, PagedDecodeCache)
    plan = layer_plan(cfg)
    if paged:
        assert plan["kind"] == "attn", (
            "paged decode supports attention-only stacks; got " + plan["kind"])
    kind = "paged_q8" if paged_q8 else ("paged" if paged else "dense")
    backend = backends.get_backend(kind, serving_style_key(cfg), impl)
    # embed through the same front-end as the seq path: skipless styles
    # scale the embedding output, and merged trees fold Q_0 into the table
    # plus optional input_proj / embed_bias — skipping any of these makes
    # decode diverge from prefill
    inputs = token[:, None] if token.dtype in (jnp.int32, jnp.int64) \
        else token[:, None, :]
    h = embed_inputs(params, cfg, inputs)

    if paged:
        ctx = {"length": cache.length, "block_tables": cache.block_tables,
               "impl": impl, "qkv_sharding": qkv_sharding,
               "backend": backend}
        # q8 stores travel as (pool, scale) pairs — apply_block_step
        # passes them through to the backend step opaquely
        stores = {"k": (cache.k, cache.k_scale),
                  "v": (cache.v, cache.v_scale)} if paged_q8 else \
            {"k": cache.k, "v": cache.v}
        h, ncs = _scan_pool_layers(params, cfg, h, stores, ctx,
                                   unroll=unroll)
        if "final_norm" in params:
            h = apply_rmsnorm(params["final_norm"], h)
        table = params["embed"] if cfg.tie_embeddings else params["unembed"]
        logits = apply_unembedding(table, h)[:, 0, :]
        if paged_q8:
            return logits, cache._replace(
                k=ncs["k"][0], k_scale=ncs["k"][1],
                v=ncs["v"][0], v_scale=ncs["v"][1],
                length=cache.length + 1)
        return logits, cache._replace(k=ncs["k"], v=ncs["v"],
                                      length=cache.length + 1)

    # mark the new token's slot as valid BEFORE attention so it attends to
    # itself (ring-buffer slot = length % Sc under sliding window)
    kv_pos = cache.kv_pos
    if kv_pos is not None:
        Sc = kv_pos.shape[1]
        slot = (cache.length % Sc).astype(jnp.int32)
        kv_pos = jax.vmap(lambda pr, s, ln: pr.at[s].set(ln))(
            kv_pos, slot, cache.length)
    ctx = {"length": cache.length, "kv_pos": kv_pos, "impl": impl,
           "qkv_sharding": qkv_sharding, "backend": backend}

    def layer_cache_slices(kind):
        if kind == "ssm":
            return {"ssm": cache.ssm.ssm, "conv": cache.ssm.conv}
        d = {"k": cache.k, "v": cache.v}
        if kind == "hybrid":
            d.update(ssm=cache.ssm.ssm, conv=cache.ssm.conv)
        return d

    new_cache = cache
    if plan["kind"] == "vlm":
        ng, spg = plan["n_groups"], plan["self_per_group"]
        ks = cache.k.reshape(ng, spg, *cache.k.shape[1:])
        vs = cache.v.reshape(ng, spg, *cache.v.shape[1:])

        def group_fn(h, xs):
            gp, klayers, vlayers, ck, cv = xs

            def self_fn(h, xs2):
                lp, kl, vl = xs2
                out, nc = apply_block_step(lp, cfg, "attn", h,
                                           {"k": kl, "v": vl}, ctx)
                return out, (nc["k"], nc["v"])

            h, (nk, nv) = jax.lax.scan(self_fn, h, (gp["self"], klayers, vlayers),
                                       unroll=True if unroll else 1)
            out, _ = apply_block_step(gp["cross"], cfg, "cross", h,
                                      {"ck": ck, "cv": cv}, ctx)
            return out, (nk, nv)

        gparams = {"self": params["layers"], "cross": params["cross_layers"]}
        h, (nk, nv) = jax.lax.scan(group_fn, h,
                                   (gparams, ks, vs, cache.cross_k, cache.cross_v),
                                   unroll=True if unroll else 1)
        new_cache = new_cache._replace(k=nk.reshape(cache.k.shape),
                                       v=nv.reshape(cache.v.shape))
    else:
        kind = plan["kind"]

        def f(h, xs):
            lp, lc = xs
            out, nc = apply_block_step(lp, cfg, kind, h, lc, ctx)
            return out, nc

        lcaches = {}
        if kind in ("attn", "hybrid"):
            lcaches.update(k=cache.k, v=cache.v)
        if kind in ("ssm", "hybrid"):
            lcaches.update(ssm=cache.ssm.ssm, conv=cache.ssm.conv)
        h, ncs = jax.lax.scan(f, h, (params["layers"], lcaches),
                              unroll=True if unroll else 1)
        if "k" in ncs:
            new_cache = new_cache._replace(k=ncs["k"], v=ncs["v"])
        if "ssm" in ncs:
            new_cache = new_cache._replace(
                ssm=m2.SSMState(ssm=ncs["ssm"], conv=ncs["conv"]))

    if "final_norm" in params:
        h = apply_rmsnorm(params["final_norm"], h)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = apply_unembedding(table, h)[:, 0, :]

    # advance shared cache bookkeeping
    if kv_pos is not None:
        new_cache = new_cache._replace(kv_pos=kv_pos)
    new_cache = new_cache._replace(length=cache.length + 1)
    return logits, new_cache


# ---------------------------------------------------------------------------
# paged decode: block-table cache (pool of physical pages) — serving layer
# allocates/frees pages host-side (serving.paged_kv_cache), this module only
# consumes the device-side view
# ---------------------------------------------------------------------------

class PagedDecodeCache(NamedTuple):
    """Device view of the paged KV cache (attention-only stacks).

    ``k``/``v`` are pools of physical pages shared by every serving slot;
    ``block_tables[b, j]`` maps slot b's logical block j to a physical page
    (-1 = unmapped).  Sliding-window configs bound the table at
    ``ceil(window/bs)+1`` RING slots (absolute block j lives at slot
    j % ring and out-of-window pages are recycled in place); readers
    reconstruct each slot's absolute positions from ``length``, so the
    ring phase is carried by the cache exactly as the dense ring buffer
    carries it (``kernels.paging``).  Page content beyond a slot's
    ``length`` may be stale (freed/reused/recycled pages are not
    scrubbed) — the causal mask hides it, and decode always writes
    position ``length`` before attending.
    """
    k: jnp.ndarray  # (L, n_blocks, block_size, Hkv, Dh) — physical pages
    v: jnp.ndarray
    block_tables: jnp.ndarray  # (B, MB) int32 page ids, -1 unmapped
    length: jnp.ndarray  # (B,) int32 — tokens so far (= next position)


def paged_table_blocks(cfg: ModelConfig, block_size: int, max_len: int) -> int:
    """Block-table width for one serving slot: ``ceil(max_len/bs)`` slots
    in absolute addressing, or the ring bound ``ceil(window/bs)+1`` when a
    sliding window makes that strictly smaller — windowed requests then
    wrap the table and recycle out-of-window pages in place (the paged
    sibling of the dense window-sized ring buffer; ``kernels.paging``)."""
    mb = -(-max_len // block_size)
    r = paging.paged_ring_blocks(cfg.sliding_window, block_size)
    return r if 0 < r < mb else mb


def paged_cache_spec(cfg: ModelConfig, n_blocks: int, block_size: int,
                     n_slots: int, max_len: int):
    """Shapes for an empty paged cache (init and jit input specs)."""
    plan = layer_plan(cfg)
    if plan["kind"] != "attn":
        raise ValueError(
            f"paged KV cache supports attention-only stacks, not "
            f"{plan['kind']!r} (family {cfg.family!r})")
    cdt = dtype_of(cfg.dtype)
    mb = paged_table_blocks(cfg, block_size, max_len)
    pool = ((plan["n"], n_blocks, block_size, cfg.n_kv_heads, cfg.d_head), cdt)
    return {"k": pool, "v": pool,
            "block_tables": ((n_slots, mb), jnp.int32),
            "length": ((n_slots,), jnp.int32)}


def init_paged_cache(cfg: ModelConfig, n_blocks: int, block_size: int,
                     n_slots: int, max_len: int) -> PagedDecodeCache:
    spec = paged_cache_spec(cfg, n_blocks, block_size, n_slots, max_len)
    return PagedDecodeCache(
        k=jnp.zeros(*spec["k"]), v=jnp.zeros(*spec["v"]),
        block_tables=jnp.full(spec["block_tables"][0], -1, jnp.int32),
        length=jnp.zeros(*spec["length"]))


class PagedQ8DecodeCache(NamedTuple):
    """Device view of the QUANTIZED paged KV cache: the
    ``PagedDecodeCache`` contract with int8 pools plus per-(page,
    kv-head) float32 scale arrays (``kernels.quant`` layout).  Scales are
    part of the page: CoW copies them with the bytes
    (``serving.paged_kv_cache.copy_block_q8``) and recycled pages'
    stale scales are garbage hidden exactly like stale page bytes —
    decode's quantize-on-write resets a page's scale when it enters the
    page at offset 0."""
    k: jnp.ndarray  # (L, n_blocks, block_size, Hkv, Dh) int8 pages
    v: jnp.ndarray
    k_scale: jnp.ndarray  # (L, n_blocks, Hkv) float32
    v_scale: jnp.ndarray
    block_tables: jnp.ndarray  # (B, MB) int32 page ids, -1 unmapped
    length: jnp.ndarray  # (B,) int32 — tokens so far (= next position)


def paged_q8_cache_spec(cfg: ModelConfig, n_blocks: int, block_size: int,
                        n_slots: int, max_len: int):
    """Shapes for an empty quantized paged cache (init and jit specs)."""
    spec = paged_cache_spec(cfg, n_blocks, block_size, n_slots, max_len)
    plan = layer_plan(cfg)
    pool = (spec["k"][0], jnp.int8)
    scale = ((plan["n"], n_blocks, cfg.n_kv_heads), jnp.float32)
    return {"k": pool, "v": pool, "k_scale": scale, "v_scale": scale,
            "block_tables": spec["block_tables"],
            "length": spec["length"]}


def init_paged_q8_cache(cfg: ModelConfig, n_blocks: int, block_size: int,
                        n_slots: int, max_len: int) -> PagedQ8DecodeCache:
    spec = paged_q8_cache_spec(cfg, n_blocks, block_size, n_slots, max_len)
    return PagedQ8DecodeCache(
        k=jnp.zeros(*spec["k"]), v=jnp.zeros(*spec["v"]),
        k_scale=jnp.zeros(*spec["k_scale"]),
        v_scale=jnp.zeros(*spec["v_scale"]),
        block_tables=jnp.full(spec["block_tables"][0], -1, jnp.int32),
        length=jnp.zeros(*spec["length"]))


def _scan_pool_layers(params, cfg: ModelConfig, h, stores, ctx, *,
                      unroll: bool = False):
    """Run the attention-only block stack over a paged pool, carrying the
    whole (L, NB, bs, Hkv, Dh) stores through the scan: each layer's
    backend step reads and writes its own layer of the stack, by the
    traced index ``ctx["layer"]``, in place.  No layer's pool is sliced
    out of the stack or stacked back (XLA copies both, and the whole
    stack once more when it is both scanned over and rebuilt).  Returns
    (h, new stores)."""
    n = layer_plan(cfg)["n"]

    def f(carry, xs):
        hh, st = carry
        lp, layer = xs
        out, nc = apply_block_step(lp, cfg, "attn", hh, st,
                                   dict(ctx, layer=layer))
        return (out, nc), None

    (h, stores), _ = jax.lax.scan(
        f, (h, stores), (params["layers"], jnp.arange(n, dtype=jnp.int32)),
        unroll=True if unroll else 1)
    return h, stores


def _rope_and_insert_paged(cfg: ModelConfig, q, k_new, v_new, k_pool, v_pool,
                           block_tables, length, layer):
    """RoPE the step's q/k at position ``length`` and scatter the new k/v
    into layer ``layer`` of the stacked pools, at each slot's mapped page
    (page = table[length // bs], offset = length % bs; ring-addressed
    windowed tables wrap the table index — ``kernels.paging``).  Unmapped
    slots (idle batch rows) drop the write."""
    pos = length[:, None]  # (B,1)
    q = apply_rope(q, pos, style=cfg.rope_style, theta=cfg.rope_theta,
                   fraction=cfg.rope_fraction)
    k_new = apply_rope(k_new, pos, style=cfg.rope_style, theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)
    safe, off = _paged_write_slot(cfg, k_pool, block_tables, length)
    k_pool = k_pool.at[layer, safe, off].set(
        k_new[:, 0].astype(k_pool.dtype), mode="drop")
    v_pool = v_pool.at[layer, safe, off].set(
        v_new[:, 0].astype(v_pool.dtype), mode="drop")
    return q, k_pool, v_pool


def _paged_write_slot(cfg: ModelConfig, pool, block_tables, length):
    """(page, in-page offset) each slot's token at ``length`` is written
    to in the (L, NB, bs, …) pool; page NB (out of range, so the write
    drops) where the slot's table leaves it unmapped."""
    NB, bs = pool.shape[1], pool.shape[2]
    MB = block_tables.shape[1]
    ring = paging.paged_ring_active(cfg.sliding_window, bs, MB)
    lb = (length // bs).astype(jnp.int32)
    lb = (lb % ring) if ring else jnp.minimum(lb, MB - 1)
    off = (length % bs).astype(jnp.int32)
    blk = jnp.take_along_axis(block_tables, lb[:, None], axis=1)[:, 0]
    return jnp.where(blk >= 0, blk, NB), off


def _attn_step_paged(lp, cfg: ModelConfig, u1, k_pool, v_pool, ctx):
    """Registered backend ("paged", "generic"): decode step vs layer
    ``ctx["layer"]`` of a paged pool.  u1 (B,1,d); k_pool/v_pool the whole
    (L,NB,bs,Hkv,Dh) stacks.  Returns (cat, new_k_pool, new_v_pool)."""
    B, length, layer = u1.shape[0], ctx["length"], ctx["layer"]
    block_tables = ctx["block_tables"]
    merged = _is_merged(cfg.block_style)
    q, k_new, v_new = _project_qkv(lp, cfg, u1, u1, merged)
    q, k_pool, v_pool = _rope_and_insert_paged(cfg, q, k_new, v_new,
                                               k_pool, v_pool, block_tables,
                                               length, layer)
    out = attn_mod.decode_attention_core_paged(
        q[:, 0], k_pool, v_pool, block_tables=block_tables,
        q_position=length, layer=layer, sliding_window=cfg.sliding_window,
        impl=ctx["impl"])
    return out.reshape(B, 1, cfg.attn_dim), k_pool, v_pool


def _attn_step_paged_merged(lp, cfg: ModelConfig, u1, k_pool, v_pool, ctx):
    """Registered backend ("paged", "merged"): the Q/P-removed fast path
    vs a paged pool — per token the attention-side HBM traffic is K*/V*
    weights plus the slot's mapped pages: no Q/P weight reads AND no dense
    worst-case-length cache."""
    B, length, layer = u1.shape[0], ctx["length"], ctx["layer"]
    block_tables = ctx["block_tables"]
    # variant "qp": _project_qkv returns the stream itself as q (identity)
    q, k_new, v_new = _project_qkv(lp, cfg, u1, u1, True)
    q, k_new, v_new = _qkv_reanchor(ctx, q, k_new, v_new)
    q, k_pool, v_pool = _rope_and_insert_paged(cfg, q, k_new, v_new,
                                               k_pool, v_pool, block_tables,
                                               length, layer)
    out = attn_mod.decode_attention_core_paged_merged(
        q.reshape(B, cfg.attn_dim), k_pool, v_pool,
        block_tables=block_tables, q_position=length, layer=layer,
        n_kv_heads=cfg.n_kv_heads, sliding_window=cfg.sliding_window,
        impl=ctx["impl"])
    return out.reshape(B, 1, cfg.attn_dim), k_pool, v_pool


def _rope_and_insert_paged_q8(cfg: ModelConfig, q, k_new, v_new,
                              k_pool, v_pool, k_scale, v_scale,
                              block_tables, length, layer):
    """``_rope_and_insert_paged`` over int8 pools: RoPE, then QUANTIZE the
    new token into layer ``layer`` of each slot's mapped page under the
    page's monotone scale merge (``kernels.quant.q8_append_token`` — the
    scale row is written in the same drop-scatter as the page bytes).
    Runs in plain XLA inside every impl's program, so pool bits are
    impl-independent."""
    pos = length[:, None]  # (B,1)
    q = apply_rope(q, pos, style=cfg.rope_style, theta=cfg.rope_theta,
                   fraction=cfg.rope_fraction)
    k_new = apply_rope(k_new, pos, style=cfg.rope_style, theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)
    safe, off = _paged_write_slot(cfg, k_pool, block_tables, length)
    k_pool, k_scale = quant.q8_append_token(k_pool, k_scale, k_new[:, 0],
                                            safe, off, layer)
    v_pool, v_scale = quant.q8_append_token(v_pool, v_scale, v_new[:, 0],
                                            safe, off, layer)
    return q, k_pool, v_pool, k_scale, v_scale


def _attn_step_paged_q8(lp, cfg: ModelConfig, u1, k_store, v_store, ctx):
    """Registered backend ("paged_q8", "generic"): decode step vs layer
    ``ctx["layer"]`` of the quantized pool.  The scan-carried stores are
    whole (pool, scale) stacks in pytree pairs — ``apply_block_step``
    treats them opaquely, so the block wiring is untouched.  Returns
    (cat, (k_pool, k_scale), (v_pool, v_scale))."""
    B, length, layer = u1.shape[0], ctx["length"], ctx["layer"]
    block_tables = ctx["block_tables"]
    k_pool, k_scale = k_store
    v_pool, v_scale = v_store
    merged = _is_merged(cfg.block_style)
    q, k_new, v_new = _project_qkv(lp, cfg, u1, u1, merged)
    q, k_pool, v_pool, k_scale, v_scale = _rope_and_insert_paged_q8(
        cfg, q, k_new, v_new, k_pool, v_pool, k_scale, v_scale,
        block_tables, length, layer)
    out = attn_mod.decode_attention_core_paged_q8(
        q[:, 0], k_pool, v_pool, k_scale, v_scale,
        block_tables=block_tables, q_position=length, layer=layer,
        sliding_window=cfg.sliding_window, impl=ctx["impl"])
    return out.reshape(B, 1, cfg.attn_dim), (k_pool, k_scale), \
        (v_pool, v_scale)


def _attn_step_paged_q8_merged(lp, cfg: ModelConfig, u1, k_store, v_store,
                               ctx):
    """Registered backend ("paged_q8", "merged"): the Q/P-removed fast
    path vs the quantized pool — per token the attention-side HBM traffic
    is K*/V* weights plus ONE BYTE per mapped pooled element (the pallas
    kernel dequantizes per tile in VMEM; no full-precision pool view is
    ever materialized)."""
    B, length, layer = u1.shape[0], ctx["length"], ctx["layer"]
    block_tables = ctx["block_tables"]
    k_pool, k_scale = k_store
    v_pool, v_scale = v_store
    # variant "qp": _project_qkv returns the stream itself as q (identity)
    q, k_new, v_new = _project_qkv(lp, cfg, u1, u1, True)
    q, k_new, v_new = _qkv_reanchor(ctx, q, k_new, v_new)
    q, k_pool, v_pool, k_scale, v_scale = _rope_and_insert_paged_q8(
        cfg, q, k_new, v_new, k_pool, v_pool, k_scale, v_scale,
        block_tables, length, layer)
    out = attn_mod.decode_attention_core_paged_q8_merged(
        q.reshape(B, cfg.attn_dim), k_pool, v_pool, k_scale, v_scale,
        block_tables=block_tables, q_position=length, layer=layer,
        n_kv_heads=cfg.n_kv_heads, sliding_window=cfg.sliding_window,
        impl=ctx["impl"])
    return out.reshape(B, 1, cfg.attn_dim), (k_pool, k_scale), \
        (v_pool, v_scale)


# the serving attention routes, one per (cache layout × projection
# style); each registration covers xla/pallas/pallas_interpret (the steps
# read ``impl`` from ctx and the cores dispatch on it)
backends.register_backend("dense", "generic", _attn_step_dense)
backends.register_backend("dense", "merged", _attn_step_dense_merged,
                          fast_path=True)
backends.register_backend("paged", "generic", _attn_step_paged)
backends.register_backend("paged", "merged", _attn_step_paged_merged,
                          fast_path=True)
backends.register_backend("paged_q8", "generic", _attn_step_paged_q8)
backends.register_backend("paged_q8", "merged", _attn_step_paged_q8_merged,
                          fast_path=True)


# ---------------------------------------------------------------------------
# deprecated per-cache-kind entry points (thin shims over forward_step)
# ---------------------------------------------------------------------------

def forward_decode(params, cfg: ModelConfig, token, cache: DecodeCache, *,
                   impl: str = "xla", unroll: bool = False,
                   qkv_sharding=None):
    """DEPRECATED: use ``forward_step`` (it dispatches on the cache type)."""
    warnings.warn(
        "forward_decode is deprecated; use forward_step, which serves "
        "every (cache_kind, style, impl) combo through the backend "
        "registry", DeprecationWarning, stacklevel=2)
    return forward_step(params, cfg, token, cache, impl=impl, unroll=unroll,
                        qkv_sharding=qkv_sharding)


def forward_decode_paged(params, cfg: ModelConfig, token,
                         cache: PagedDecodeCache, *, impl: str = "xla",
                         unroll: bool = False, qkv_sharding=None):
    """DEPRECATED: use ``forward_step`` (it dispatches on the cache type)."""
    warnings.warn(
        "forward_decode_paged is deprecated; use forward_step, which "
        "serves every (cache_kind, style, impl) combo through the backend "
        "registry", DeprecationWarning, stacklevel=2)
    return forward_step(params, cfg, token, cache, impl=impl, unroll=unroll,
                        qkv_sharding=qkv_sharding)


# ---------------------------------------------------------------------------
# chunked prefill: fixed-size prompt slices against the SERVING cache — the
# third phase of the backend registry (repro.serving.sched interleaves these
# programs with decode steps inside one engine iteration)
# ---------------------------------------------------------------------------

# causal-mask sentinel for kv positions that must never be attended (empty
# dense ring entries / unmapped pages): larger than any real position, so the
# causal test kv_pos <= q_pos excludes them without a separate validity mask
# (attention_core_merged has no kv_valid parameter)
_CHUNK_POS_SENTINEL = jnp.int32(2 ** 30)


class DenseChunkDest(NamedTuple):
    """Destination of one dense prefill chunk: batch row ``slot`` of the
    BATCHED serving ``DecodeCache`` (not a fresh single-request cache — the
    chunk writes in place at positions [start, start+C) of that row, so the
    caller should donate the cache).  ``slot`` is a (1,) int32 array
    (traced, so one compiled program serves every slot)."""
    cache: Any
    slot: Any


class PagedChunkDest(NamedTuple):
    """Destination of one paged prefill chunk, written direct-to-page.

    ``block_table`` is THIS slot's (1, MB) table row (true mapping, not the
    shield-masked decode view); ``block_ids`` maps the chunk's C//bs
    logical blocks to physical pages, -1 = drop the write (prefix-shared
    pages already holding the prefix, and blocks past the prompt under
    final-chunk padding — exactly the ``PagedPrefillDest.block_ids``
    contract, per chunk)."""
    k_pool: Any
    v_pool: Any
    block_table: Any
    block_ids: Any


class PagedQ8ChunkDest(NamedTuple):
    """Destination of one QUANTIZED paged prefill chunk: the
    ``PagedChunkDest`` contract over int8 pools + per-(page, kv-head)
    float32 scales — the chunk quantizes its K/V at pool granularity and
    writes ints AND scale rows in the same drop-scatter."""
    k_pool: Any
    v_pool: Any
    k_scale: Any
    v_scale: Any
    block_table: Any
    block_ids: Any


def _chunk_last_logits(logits, start, true_len, C):
    """Last REAL position's logits within the chunk: index true_len-1-start
    clipped into [0, C) — meaningful on the final chunk (where the prompt's
    last token lies in [start, start+C)), arbitrary-but-finite otherwise."""
    idx = jnp.clip(true_len - 1 - start, 0, C - 1)  # (1,)
    return jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0, :]


def _scan_layer_slices(params, cfg: ModelConfig, h, stores, ctx):
    """Run the attention-only block stack with each layer's slice of the
    (L, …) stores as the scan's xs, the updated slices stacked as its ys
    (the dense cache's route).  Returns (h, new stores)."""
    def f(hh, xs):
        lp, lc = xs
        out, nc = apply_block_step(lp, cfg, "attn", hh, lc, ctx)
        return out, nc

    return jax.lax.scan(f, h, (params["layers"], stores))


def _chunk_block_scan(params, cfg: ModelConfig, h, chunk_attn, k_stack,
                      v_stack, impl, qkv_sharding, scan_layers):
    """Run the block stack over a chunk stream with ``chunk_attn`` as the
    attention route, by ``scan_layers`` — ``_scan_layer_slices`` (dense)
    or ``_scan_pool_layers`` (paged), as ``forward_step`` scans its cache
    (``apply_block_step``'s backend seam is shape-agnostic in the stream's
    sequence extent, so the whole block wiring — styles, b_out, norms,
    FFN — is reused as-is)."""
    ctx = {"impl": impl, "qkv_sharding": qkv_sharding,
           "backend": backends.AttentionBackend(
               cache_kind="chunk", style="chunk", impl=impl,
               step=chunk_attn)}
    h, ncs = scan_layers(params, cfg, h, {"k": k_stack, "v": v_stack}, ctx)
    if "final_norm" in params:
        h = apply_rmsnorm(params["final_norm"], h)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return apply_unembedding(table, h), ncs  # (1, C, V), {"k","v"} stacks


def _chunk_rope(cfg: ModelConfig, q, k_new, positions):
    q = apply_rope(q, positions, style=cfg.rope_style, theta=cfg.rope_theta,
                   fraction=cfg.rope_fraction)
    k_new = apply_rope(k_new, positions, style=cfg.rope_style,
                       theta=cfg.rope_theta, fraction=cfg.rope_fraction)
    return q, k_new


def _chunk_dense(params, cfg: ModelConfig, chunk, dest, ctx, *,
                 merged_core: bool):
    """Shared body of both dense chunk routes.

    Per layer: write the chunk's K/V into the slot's rows at [start,
    start+C) FIRST, then attend over the full row — the chunk attends to
    itself and every earlier chunk, and overwrites the frontier-parked
    garbage a concurrent batched decode step may have deposited (the
    scheduler pins a mid-prefill slot's device length at the chunk
    frontier, so that garbage never lands anywhere else).  Positions use
    the XLA positions-based cores for every impl — the flash kernels
    assume arange positions, so a fused chunk kernel is a follow-up
    (ROADMAP) — and kv_pos validity rides the causal mask via a > max
    position sentinel.  Padded final-chunk positions get kv_pos =
    absolute position >= true_len: no earlier query attends to them and
    decode overwrites them in order (the bucketed-prefill invariant)."""
    cache, slot = dest.cache, dest.slot
    start, true_len = ctx["start"], ctx["true_len"]
    impl = ctx.get("impl", "xla")
    C = chunk.shape[1]
    s0, p0 = slot[0], start[0]
    Sc = cache.k.shape[2]
    Hkv, Dh = cfg.n_kv_heads, cfg.d_head
    merged = _is_merged(cfg.block_style)
    pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # (1,C)

    kv_pos = jax.lax.dynamic_update_slice(cache.kv_pos, pos,
                                          (s0, p0))
    kv_row = jax.lax.dynamic_slice(kv_pos, (s0, jnp.int32(0)), (1, Sc))
    kv_eff = jnp.where(kv_row >= 0, kv_row, _CHUNK_POS_SENTINEL)

    def chunk_attn(lp, cfg_, x, k_layer, v_layer, actx):
        q, k_new, v_new = _project_qkv(lp, cfg_, x, x, merged)
        q, k_new, v_new = _qkv_reanchor(actx, q, k_new, v_new)
        q, k_new = _chunk_rope(cfg_, q, k_new, pos)
        k_layer = jax.lax.dynamic_update_slice(
            k_layer, k_new.astype(k_layer.dtype), (s0, p0, 0, 0))
        v_layer = jax.lax.dynamic_update_slice(
            v_layer, v_new.astype(v_layer.dtype), (s0, p0, 0, 0))
        k_row = jax.lax.dynamic_slice(k_layer, (s0, 0, 0, 0),
                                      (1, Sc, Hkv, Dh))
        v_row = jax.lax.dynamic_slice(v_layer, (s0, 0, 0, 0),
                                      (1, Sc, Hkv, Dh))
        if merged_core:
            out = attn_mod.attention_core_merged(
                q.reshape(1, C, cfg_.attn_dim), k_row, v_row,
                q_positions=pos, kv_positions=kv_eff,
                n_kv_heads=cfg_.n_kv_heads, causal=cfg_.causal,
                sliding_window=cfg_.sliding_window, query_chunk=C,
                impl="xla", cache_kind="dense")
            return out, k_layer, v_layer
        out = attn_mod.attention_core(
            q, k_row, v_row, q_positions=pos, kv_positions=kv_eff,
            causal=cfg_.causal, sliding_window=cfg_.sliding_window,
            query_chunk=C, impl="xla")
        return out.reshape(1, C, cfg_.attn_dim), k_layer, v_layer

    h = embed_inputs(params, cfg, chunk)
    logits, ncs = _chunk_block_scan(params, cfg, h, chunk_attn,
                                    cache.k, cache.v, impl,
                                    ctx.get("qkv_sharding"),
                                    _scan_layer_slices)
    last = _chunk_last_logits(logits, start, true_len, C)
    new_len = cache.length.at[s0].set(jnp.minimum(p0 + C, true_len[0]))
    return last, cache._replace(k=ncs["k"], v=ncs["v"], kv_pos=kv_pos,
                                length=new_len)


def _chunk_paged(params, cfg: ModelConfig, chunk, dest, ctx, *,
                 merged_core: bool):
    """Shared body of both paged chunk routes: per layer, write the
    chunk's pages into that layer of the carried pool stack (drop-scatter
    at ``[layer, block_ids]``, ``PagedChunkDest.block_ids`` contract),
    then attend over the slot's densified page view of the layer.  The
    gather materializes a (1, MB*bs) view per layer — same extent as the
    XLA paged decode core; a fused chunk kernel that walks the table is
    the follow-up
    (``NoOversizedBuffer`` deliberately does not cover the chunk phase).
    Ring (sliding-window) tables: the dispatcher pins C == block_size, so
    the whole chunk lives in one ring slot and position reconstruction at
    the chunk's last query is exact for every query in it."""
    k_pool, v_pool, table, bids = dest
    start, true_len = ctx["start"], ctx["true_len"]
    impl = ctx.get("impl", "xla")
    C = chunk.shape[1]
    NB, bs = k_pool.shape[1], k_pool.shape[2]
    MB = table.shape[1]
    nbk = C // bs
    Hkv, Dh = cfg.n_kv_heads, cfg.d_head
    merged = _is_merged(cfg.block_style)
    pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # (1,C)
    ring = paging.paged_ring_active(cfg.sliding_window, bs, MB)
    kvpos = attn_mod.paged_kv_positions(table, bs, start + (C - 1), ring)
    kv_eff = jnp.where(kvpos >= 0, kvpos, _CHUNK_POS_SENTINEL)
    safe = jnp.where(bids >= 0, bids, NB).astype(jnp.int32)  # (nbk,)

    def chunk_attn(lp, cfg_, x, kp, vp, actx):
        layer = actx["layer"]
        q, k_new, v_new = _project_qkv(lp, cfg_, x, x, merged)
        q, k_new, v_new = _qkv_reanchor(actx, q, k_new, v_new)
        q, k_new = _chunk_rope(cfg_, q, k_new, pos)
        kb = k_new[0].astype(kp.dtype).reshape(nbk, bs, Hkv, Dh)
        vb = v_new[0].astype(vp.dtype).reshape(nbk, bs, Hkv, Dh)
        kp = kp.at[layer, safe].set(kb, mode="drop")
        vp = vp.at[layer, safe].set(vb, mode="drop")
        gk = attn_mod._paged_gather(kp, table, layer)  # (1, MB*bs, Hkv, Dh)
        gv = attn_mod._paged_gather(vp, table, layer)
        if merged_core:
            out = attn_mod.attention_core_merged(
                q.reshape(1, C, cfg_.attn_dim), gk, gv,
                q_positions=pos, kv_positions=kv_eff,
                n_kv_heads=cfg_.n_kv_heads, causal=cfg_.causal,
                sliding_window=cfg_.sliding_window, query_chunk=C,
                impl="xla", cache_kind="paged")
            return out, kp, vp
        out = attn_mod.attention_core(
            q, gk, gv, q_positions=pos, kv_positions=kv_eff,
            causal=cfg_.causal, sliding_window=cfg_.sliding_window,
            query_chunk=C, impl="xla")
        return out.reshape(1, C, cfg_.attn_dim), kp, vp

    h = embed_inputs(params, cfg, chunk)
    logits, ncs = _chunk_block_scan(params, cfg, h, chunk_attn,
                                    k_pool, v_pool, impl,
                                    ctx.get("qkv_sharding"),
                                    _scan_pool_layers)
    last = _chunk_last_logits(logits, start, true_len, C)
    return last, (ncs["k"], ncs["v"])


def _chunk_paged_q8(params, cfg: ModelConfig, chunk, dest, ctx, *,
                    merged_core: bool):
    """Shared body of both paged_q8 chunk routes: ``_chunk_paged`` over
    the quantized pool.  The chunk's K/V is quantized at pool granularity
    (positions >= true_len masked to zero first — the same
    ``quant.q8_quantize_seq`` call the whole-prompt q8 prefill fake-quants
    with, so a chunked prompt lands bit-identical pool bytes) and the
    attention view is the DEQUANTIZED page gather, matching what decode's
    q8 cores reconstruct.  Scan-carried stores are whole (pool, scale)
    stacks in pairs, exactly as in the q8 decode step."""
    k_pool, v_pool, k_scale, v_scale, table, bids = dest
    start, true_len = ctx["start"], ctx["true_len"]
    impl = ctx.get("impl", "xla")
    C = chunk.shape[1]
    NB, bs = k_pool.shape[1], k_pool.shape[2]
    MB = table.shape[1]
    nbk = C // bs
    Hkv, Dh = cfg.n_kv_heads, cfg.d_head
    merged = _is_merged(cfg.block_style)
    pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # (1,C)
    ring = paging.paged_ring_active(cfg.sliding_window, bs, MB)
    kvpos = attn_mod.paged_kv_positions(table, bs, start + (C - 1), ring)
    kv_eff = jnp.where(kvpos >= 0, kvpos, _CHUNK_POS_SENTINEL)
    safe = jnp.where(bids >= 0, bids, NB).astype(jnp.int32)  # (nbk,)
    valid = pos < true_len[:, None]  # (1,C)

    def chunk_attn(lp, cfg_, x, kst, vst, actx):
        layer = actx["layer"]
        kp, ks = kst
        vp, vs = vst
        q, k_new, v_new = _project_qkv(lp, cfg_, x, x, merged)
        q, k_new, v_new = _qkv_reanchor(actx, q, k_new, v_new)
        q, k_new = _chunk_rope(cfg_, q, k_new, pos)
        kq, ksc = quant.q8_quantize_seq(k_new, bs, valid)
        vq, vsc = quant.q8_quantize_seq(v_new, bs, valid)
        kp = kp.at[layer, safe].set(kq[0].reshape(nbk, bs, Hkv, Dh),
                                    mode="drop")
        vp = vp.at[layer, safe].set(vq[0].reshape(nbk, bs, Hkv, Dh),
                                    mode="drop")
        ks = ks.at[layer, safe].set(ksc[0], mode="drop")
        vs = vs.at[layer, safe].set(vsc[0], mode="drop")
        gk = attn_mod._paged_gather_q8(kp, ks, table, layer, x.dtype)
        gv = attn_mod._paged_gather_q8(vp, vs, table, layer, x.dtype)
        if merged_core:
            out = attn_mod.attention_core_merged(
                q.reshape(1, C, cfg_.attn_dim), gk, gv,
                q_positions=pos, kv_positions=kv_eff,
                n_kv_heads=cfg_.n_kv_heads, causal=cfg_.causal,
                sliding_window=cfg_.sliding_window, query_chunk=C,
                impl="xla", cache_kind="paged_q8")
            return out, (kp, ks), (vp, vs)
        out = attn_mod.attention_core(
            q, gk, gv, q_positions=pos, kv_positions=kv_eff,
            causal=cfg_.causal, sliding_window=cfg_.sliding_window,
            query_chunk=C, impl="xla")
        return out.reshape(1, C, cfg_.attn_dim), (kp, ks), (vp, vs)

    h = embed_inputs(params, cfg, chunk)
    logits, ncs = _chunk_block_scan(params, cfg, h, chunk_attn,
                                    (k_pool, k_scale), (v_pool, v_scale),
                                    impl, ctx.get("qkv_sharding"),
                                    _scan_pool_layers)
    last = _chunk_last_logits(logits, start, true_len, C)
    return last, (ncs["k"][0], ncs["v"][0], ncs["k"][1], ncs["v"][1])


# --- the four registered chunk routes ----------------------------------------

def _chunk_dense_generic(params, cfg: ModelConfig, chunk, dest, ctx):
    """Registered chunk backend ("dense", "generic")."""
    return _chunk_dense(params, cfg, chunk, dest, ctx, merged_core=False)


def _chunk_dense_merged(params, cfg: ModelConfig, chunk, dest, ctx):
    """Registered chunk backend ("dense", "merged"): the Q/P-removed fast
    path chunk-by-chunk — the chunk program reads no Q or P weights."""
    return _chunk_dense(params, cfg, chunk, dest, ctx, merged_core=True)


def _chunk_paged_generic(params, cfg: ModelConfig, chunk, dest, ctx):
    """Registered chunk backend ("paged", "generic")."""
    return _chunk_paged(params, cfg, chunk, dest, ctx, merged_core=False)


def _chunk_paged_merged(params, cfg: ModelConfig, chunk, dest, ctx):
    """Registered chunk backend ("paged", "merged"): stream-as-query
    attention AND direct-to-page chunk writes."""
    return _chunk_paged(params, cfg, chunk, dest, ctx, merged_core=True)


def _chunk_paged_q8_generic(params, cfg: ModelConfig, chunk, dest, ctx):
    """Registered chunk backend ("paged_q8", "generic")."""
    return _chunk_paged_q8(params, cfg, chunk, dest, ctx, merged_core=False)


def _chunk_paged_q8_merged(params, cfg: ModelConfig, chunk, dest, ctx):
    """Registered chunk backend ("paged_q8", "merged"): stream-as-query
    attention AND int8 direct-to-page chunk writes."""
    return _chunk_paged_q8(params, cfg, chunk, dest, ctx, merged_core=True)


backends.register_chunk_backend("dense", "generic", _chunk_dense_generic)
backends.register_chunk_backend("dense", "merged", _chunk_dense_merged,
                                fast_path=True)
backends.register_chunk_backend("paged", "generic", _chunk_paged_generic)
backends.register_chunk_backend("paged", "merged", _chunk_paged_merged,
                                fast_path=True)
backends.register_chunk_backend("paged_q8", "generic",
                                _chunk_paged_q8_generic)
backends.register_chunk_backend("paged_q8", "merged", _chunk_paged_q8_merged,
                                fast_path=True)


def forward_prefill_chunk(params, cfg: ModelConfig, chunk, dest, *,
                          start, true_len, impl: str = "xla",
                          qkv_sharding=None, max_len: Optional[int] = None):
    """One fixed-size prefill chunk against the SERVING cache — the single
    dispatcher over the ``models.backends`` CHUNK registry.

    ``chunk`` is (1, C) int32: tokens [start, start+C) of ONE stream's
    prompt, right-padded past ``true_len`` on the final chunk.  ``start``
    and ``true_len`` are (1,) int32 (traced — one compiled program serves
    every chunk of every prompt).  Returns (last_logits (1, V) — the
    prompt's last real position, meaningful on the final chunk — plus the
    filled destination, mirroring ``forward_prefill``):

    * ``DenseChunkDest(cache, slot)`` — writes rows [start, start+C) of
      batch row ``slot`` in place and returns the updated ``DecodeCache``.
      Sliding-window dense configs are rejected (the window-sized ring
      cache can't hold a partial prompt at absolute positions; the
      scheduler falls back to its monolithic whole-prompt path there).
    * ``PagedChunkDest(k_pool, v_pool, block_table, block_ids)`` — writes
      the chunk's pages and returns (k_pool, v_pool).  C must be a
      multiple of the block size; ring (windowed) tables additionally pin
      C == block_size so every chunk occupies exactly one ring slot.

    Attention-only stacks only (ssm/hybrid state has no mid-prompt
    checkpoint; vlm interleaves cross-attention).  MoE FFNs route
    dropless, like decode.  The chunk programs use the positions-based
    XLA attention cores internally for every impl — the flash kernels
    assume arange positions — so a fused chunk kernel is follow-up work;
    weight-side fast-path structure (no Q/P reads when merged) is intact
    and jaxpr-asserted by the lint sweep's chunk phase.
    """
    B, C = int(chunk.shape[0]), int(chunk.shape[1])
    if B != 1:
        raise ValueError(f"chunked prefill feeds one stream at a time, got "
                         f"batch size {B}")
    plan = layer_plan(cfg)
    if plan["kind"] != "attn":
        raise ValueError(
            f"chunked prefill supports attention-only stacks, not "
            f"{plan['kind']!r} (family {cfg.family!r})")
    if isinstance(dest, (PagedChunkDest, PagedQ8ChunkDest)):
        kind = "paged_q8" if isinstance(dest, PagedQ8ChunkDest) else "paged"
        bs = int(dest.k_pool.shape[2])
        MB = int(dest.block_table.shape[1])
        if C % bs:
            raise ValueError(f"chunk width {C} must be a multiple of the "
                             f"page size {bs}")
        if paging.paged_ring_active(cfg.sliding_window, bs, MB) and C != bs:
            raise ValueError(
                f"ring (sliding-window) paged chunks must be exactly one "
                f"block: chunk width {C} != block size {bs}")
        if int(dest.block_ids.shape[0]) != C // bs:
            raise ValueError(
                f"{type(dest).__name__}.block_ids maps "
                f"{int(dest.block_ids.shape[0])} blocks; a {C}-token chunk "
                f"over {bs}-token pages needs {C // bs}")
    elif isinstance(dest, DenseChunkDest):
        kind = "dense"
        # a BINDING window (window < max_len) makes the dense cache a
        # window-sized ring buffer, which can't park a partial prompt at
        # absolute positions; a window >= max_len never masks or wraps
        # anything and chunks exactly like window=0.  The cache alone
        # can't distinguish the two (Sc = min(max_len, window)), so the
        # static ``max_len`` hint carries the check — the serving adapter
        # always passes it, and the scheduler routes binding-window dense
        # requests through its monolithic whole-prompt fallback instead.
        if cfg.sliding_window and max_len is not None \
                and cfg.sliding_window < max_len:
            raise ValueError(
                "dense sliding-window chunked prefill is unsupported (the "
                "window-sized ring cache can't park a partial prompt at "
                "absolute positions); use the scheduler's monolithic "
                "fallback or the paged cache")
    else:
        raise ValueError(
            f"unknown chunk destination {type(dest).__name__!r}; expected "
            "DenseChunkDest, PagedChunkDest, or PagedQ8ChunkDest (or "
            "register a ChunkBackend for a new cache kind)")

    backend = backends.get_chunk_backend(kind, prefill_style_key(cfg), impl)
    ctx = {"start": jnp.asarray(start, jnp.int32).reshape(1),
           "true_len": jnp.asarray(true_len, jnp.int32).reshape(1),
           "impl": impl, "qkv_sharding": qkv_sharding}
    return backend.run(params, cfg, chunk, dest, ctx)

"""Exact weight-removal transforms for skipless transformers (the paper).

``merge_skipless(params, cfg, variant)`` maps a ``block_style="skipless"``
(Fig 1a) parameter tree to a mathematically identical
``block_style="skipless_merged"`` tree (Fig 1b/c/d per Table 1):

  variant "qp" (MHA/MQA/GQA):  O*_{i-1} = O_{i-1} Q_i ;  K* = Q⁻¹K ; V* = Q⁻¹V
  variant "kp" (MHA only):     O*_{i-1} = O_{i-1} K_i ;  Q* = K⁻¹Q ; V* = K⁻¹V
  variant "vp" (MHA only):     O*_{i-1} = O_{i-1} V_i ;  Q* = V⁻¹Q ; K* = V⁻¹K
  all variants:                M*_i = P_i M_i

General rule implemented here: removing projection T_i of block i rewrites
the block-i input basis ``u* = u T_i (+ b_T)``.  This requires
  (a) right-multiplying every *producer* of u (the previous block's output
      matrix — FFN w_down / expert w_down — or the embedding table for i=0)
      by T_i, and
  (b) left-multiplying every OTHER *consumer* of u in block i by T_i⁻¹
      (the remaining attention projections; for hybrid blocks also the SSM
      in_proj).
Affine extension (ours — the paper is bias-free): with QKV biases,
``u* = u T + b_T``, so consumers get ``b'_c = b_c − b_T (T⁻¹ W_c)`` and the
previous block's output gains ``b_out = b_T`` (the embedding gains
``embed_bias``).

P-removal folds P into the FFN input matrices (and MoE router + every
expert's input matrices — same shapes, so MoE merging is free), except:
  * hybrid blocks keep P (the FFN reads the fused attn+ssm stream, see
    DESIGN.md §5) — hybrid gets the Q-removal half only;
  * parallel blocks (paper Fig 3) are a trainable architecture, not an
    exact rewrite — this module only handles serial stacks (the paper's §4
    equivalence experiment is serial Fig 1b/2b as well).

Continuous-input models (audio frames, family="audio") cannot fold T_0 into
an embedding table; the merge emits an explicit ``input_proj`` (= T_0)
instead, so one d×d matrix of savings is forgone for block 0 only.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.transformer import layer_plan

# All merge math runs on host in numpy float64: this is an offline,
# init/conversion-time transform, and float64 keeps the rewrite exact even
# for ill-conditioned Q/K/V (cond ~ 1e3 costs ~1e-13 relative in f64).
# Layer-batched products are ``@`` (BLAS per layer), never ``np.einsum``:
# einsum's generic loop is ~100x slower, tens of minutes per matrix at
# Mistral-7B widths.


def _f64(x) -> np.ndarray:
    return np.asarray(x, dtype=np.float64)


def _inv(mat) -> np.ndarray:
    return np.linalg.inv(_f64(mat))


def _t_of(attn: Dict[str, jnp.ndarray], variant: str):
    """The projection being removed (T) and its bias, for one layer (stacked ok)."""
    w = attn["w" + variant[0]]
    b = attn.get("b" + variant[0])
    return w, b


def condition_numbers(params, cfg: ModelConfig, variant: str = "qp") -> np.ndarray:
    """cond₂(T_i) per layer — the paper §4 invertibility audit."""
    plan = layer_plan(cfg)
    mats = []
    if plan["kind"] == "vlm":
        qs = params["layers"]["attn"]["w" + variant[0]]
        mats.append(np.asarray(qs.reshape(-1, *qs.shape[-2:])))
        mats.append(np.asarray(params["cross_layers"]["attn"]["w" + variant[0]]))
    else:
        mats.append(np.asarray(params["layers"]["attn"]["w" + variant[0]]))
    conds = []
    for stack in mats:
        for m in stack:
            s = np.linalg.svd(m.astype(np.float64), compute_uv=False)
            conds.append(s[0] / s[-1])
    return np.asarray(conds)


# ---------------------------------------------------------------------------
# the merge
# ---------------------------------------------------------------------------

def merge_skipless(params: Dict[str, Any], cfg: ModelConfig,
                   variant: str = "qp") -> Tuple[Dict[str, Any], ModelConfig]:
    """Exact (Fig 1) merge of a serial skipless model.  Returns
    (merged_params, merged_cfg)."""
    if cfg.block_style != "skipless":
        raise ValueError("merge_skipless expects block_style='skipless'")
    if cfg.parallel_block:
        raise ValueError(
            "exact merging applies to the serial layout (paper Fig 1/2; "
            "the parallel Fig 3 forms are trainable architectures)")
    mcfg = cfg.with_(block_style="skipless_merged", merged_variant=variant)
    mcfg.validate_style()

    plan = layer_plan(cfg)
    out: Dict[str, Any] = {k: v for k, v in params.items()
                           if k not in ("layers", "cross_layers", "embed")}
    out["embed"] = dict(params["embed"])

    if plan["kind"] == "vlm":
        return _merge_vlm(params, cfg, mcfg, variant, out)

    layers = params["layers"]
    attn = layers["attn"]
    T, bT = _t_of(attn, variant)  # (L, d, d), optional (L, d)
    T = _f64(T)
    bT = None if bT is None else _f64(bT)
    Tinv = _inv(T)  # batched over the layer axis

    new_layers = _merge_layer_stack(layers, cfg, variant, T, bT, Tinv,
                                    next_T=_shifted(T),
                                    next_bT=_shifted_bias(bT))
    out["layers"] = new_layers

    # fold T_0 (+ b_T0) into the embedding / input projection
    dt = params["embed"]["table"].dtype
    T0 = T[0]
    if cfg.family == "audio":
        out["input_proj"] = jnp.asarray(T0, dt)
        if bT is not None:
            out["embed_bias"] = jnp.asarray(bT[0], dt)
    else:
        out["embed"]["table"] = jnp.asarray(
            _f64(params["embed"]["table"]) @ T0, dt)
        if bT is not None:
            out["embed_bias"] = jnp.asarray(bT[0], dt)
        if cfg.tie_embeddings:
            # the unembedding must keep the ORIGINAL table: basis rotation
            # applies to the input side only. Untie.
            out["unembed"] = {"table": params["embed"]["table"]}
            mcfg = mcfg.with_(tie_embeddings=False)
    return out, mcfg


def _shifted(T: np.ndarray) -> np.ndarray:
    """next_T[i] = T[i+1]; last gets identity (no next block)."""
    eye = np.eye(T.shape[-1], dtype=T.dtype)[None]
    return np.concatenate([_f64(T)[1:], eye], axis=0)


def _shifted_bias(bT):
    if bT is None:
        return None
    zero = np.zeros_like(bT[:1])
    return np.concatenate([bT[1:], zero], axis=0)


def _merge_layer_stack(layers, cfg: ModelConfig, variant: str,
                       T, bT, Tinv, next_T, next_bT) -> Dict[str, Any]:
    """Merge a homogeneous stacked layer tree (dense/moe/hybrid/audio)."""
    attn = layers["attn"]
    new: Dict[str, Any] = {}
    new_attn: Dict[str, Any] = {}

    # (b) consumers of u: remaining attention projections  W' = T⁻¹ W,
    #     biases b' = b − b_T (T⁻¹ W)
    for name in ("q", "k", "v"):
        if name == variant[0]:
            continue  # eliminated / identity
        w = attn["w" + name]
        w2 = Tinv @ _f64(w)
        new_attn["w" + name] = jnp.asarray(w2, w.dtype)
        b = attn.get("b" + name)
        if bT is not None:
            b0 = 0.0 if b is None else _f64(b)
            new_attn["b" + name] = jnp.asarray(
                b0 - (bT[:, None] @ w2)[:, 0], w.dtype)
        elif b is not None:
            new_attn["b" + name] = b

    is_hybrid = "ssm" in layers and "attn" in layers
    keep_p = is_hybrid  # hybrid: P stays (Q-removal only)

    if keep_p:
        new_attn["wp"] = attn["wp"]
        # SSM in_proj is a consumer of u too
        new_ssm = dict(layers["ssm"])
        w = new_ssm["in_proj"]
        new_ssm["in_proj"] = jnp.asarray(Tinv @ _f64(w), w.dtype)
        if bT is not None:
            raise NotImplementedError("hybrid merge with QKV biases")
        new["ssm"] = new_ssm

    new["attn"] = new_attn

    # P-fold into FFN/MoE input matrices; w_down absorbs next block's T
    def fold_P(w_in):  # (L, d, f) -> (L, ad, f)
        if keep_p:
            return w_in
        P = attn["wp"]  # (L, ad, d)
        return jnp.asarray(_f64(P) @ _f64(w_in), w_in.dtype)

    def absorb_next(w_down):  # (L, f, d) @ next_T (L, d, d)
        return jnp.asarray(_f64(w_down) @ _f64(next_T), w_down.dtype)

    if "ffn" in layers:
        ffn = dict(layers["ffn"])
        if "w_gate" in ffn:
            ffn["w_gate"] = fold_P(ffn["w_gate"])
            ffn["w_up"] = fold_P(ffn["w_up"])
            ffn["w_down"] = absorb_next(ffn["w_down"])
        else:
            ffn["w_in"] = fold_P(ffn["w_in"])
            ffn["w_out"] = absorb_next(ffn["w_out"])
        new["ffn"] = ffn
    if "moe" in layers:
        moe = dict(layers["moe"])
        if not keep_p:
            P = _f64(attn["wp"])
            moe["router"] = jnp.asarray(P @ _f64(moe["router"]), jnp.float32)
            moe["w_gate"] = jnp.asarray(P[:, None] @ _f64(moe["w_gate"]),
                                        moe["w_gate"].dtype)
            moe["w_up"] = jnp.asarray(P[:, None] @ _f64(moe["w_up"]),
                                      moe["w_up"].dtype)
        moe["w_down"] = jnp.asarray(
            _f64(moe["w_down"]) @ _f64(next_T)[:, None], moe["w_down"].dtype)
        new["moe"] = moe
    if "ssm" in layers and not is_hybrid:
        raise ValueError("pure SSM stacks have no Q/K/V/P to merge")

    # b_out: next block's folded bias enters the stream after w_down
    if next_bT is not None:
        new["b_out"] = jnp.asarray(next_bT, jax.tree.leaves(attn)[0].dtype)

    return new


def _merge_vlm(params, cfg: ModelConfig, mcfg: ModelConfig, variant: str, out):
    """VLM: interleaved self/cross stacks. Layer order is
    [self(g,0)…self(g,spg-1), cross(g)] for g in 0..ng-1."""
    if variant != "qp":
        raise ValueError("VLM merge supports the qp variant (cross-attn K/V "
                         "read vision tokens, which are not stream-basis)")
    if cfg.qkv_bias:
        raise NotImplementedError("vlm merge with QKV biases")
    slf = params["layers"]  # (ng, spg, …)
    crs = params["cross_layers"]  # (ng, …)
    ng = jax.tree.leaves(crs)[0].shape[0]
    spg = jax.tree.leaves(slf)[0].shape[1]
    d = cfg.d_model

    Tq_self = _f64(slf["attn"]["wq"])  # (ng, spg, d, d)
    Tq_cross = _f64(crs["attn"]["wq"])  # (ng, d, d)

    # next_T for self(g,s): self(g,s+1) if s<spg-1 else cross(g)
    next_T_self = np.concatenate(
        [Tq_self[:, 1:], Tq_cross[:, None]], axis=1)  # (ng, spg, d, d)
    # next_T for cross(g): self(g+1, 0); last cross gets identity
    eye = np.eye(d)[None]
    next_T_cross = np.concatenate([Tq_self[1:, 0], eye], axis=0)  # (ng, d, d)

    def flat(tree, n):  # (ng, spg, …) -> (ng*spg, …)
        return jax.tree.map(lambda x: x.reshape((n,) + x.shape[2:]), tree)

    slf_flat = flat(slf, ng * spg)
    T = _f64(slf_flat["attn"]["wq"])
    Tinv = _inv(T)
    merged_self = _merge_layer_stack(
        slf_flat, cfg, variant, T, None, Tinv,
        next_T=next_T_self.reshape(ng * spg, d, d), next_bT=None)
    out["layers"] = jax.tree.map(
        lambda x: x.reshape((ng, spg) + x.shape[1:]), merged_self)

    # cross layers: only consumer of u is Q (K/V read vision) -> no (b) step
    new_cross: Dict[str, Any] = {"attn": {
        "wk": crs["attn"]["wk"], "wv": crs["attn"]["wv"]}}
    P = _f64(crs["attn"]["wp"])
    ffn = dict(crs["ffn"])
    dtf = ffn["w_gate"].dtype
    ffn["w_gate"] = jnp.asarray(P @ _f64(ffn["w_gate"]), dtf)
    ffn["w_up"] = jnp.asarray(P @ _f64(ffn["w_up"]), dtf)
    ffn["w_down"] = jnp.asarray(_f64(ffn["w_down"]) @ next_T_cross, dtf)
    new_cross["ffn"] = ffn
    out["cross_layers"] = new_cross

    dt = params["embed"]["table"].dtype
    out["embed"]["table"] = jnp.asarray(
        _f64(params["embed"]["table"]) @ Tq_self[0, 0], dt)
    return out, mcfg


# ---------------------------------------------------------------------------
# weight-savings accounting for a merged tree (used by benchmarks/tests)
# ---------------------------------------------------------------------------

def removed_weight_count(params_before, params_after) -> int:
    n_before = sum(int(x.size) for x in jax.tree.leaves(params_before))
    n_after = sum(int(x.size) for x in jax.tree.leaves(params_after))
    return n_before - n_after

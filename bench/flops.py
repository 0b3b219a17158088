"""Operations and bytes, counted from a configuration's shapes.

The parameter arithmetic follows ``benchmarks/bench_weight_table.py``
(weight bytes per layer by block style); the kernel counts are those of
the paged decode kernel's useful work: the live cache tokens only.
"""
from __future__ import annotations

from typing import Dict, Iterable


def _dims(cfg: Dict):
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    return (d, cfg["num_attention_heads"] * hd,
            cfg["num_key_value_heads"] * hd, cfg["intermediate_size"], hd)


def layer_matmul_params(cfg: Dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, ad, kd, ff, _ = _dims(cfg)
    attn = 2 * d * kd  # K and V
    if cfg["block_style"] == "standard":
        attn += d * ad + ad * d  # Q and P
    return attn + 3 * d * ff  # SwiGLU gate, up, down


def layer_params(cfg: Dict) -> int:
    """Every weight of one layer: matrices and the two norm scales."""
    return layer_matmul_params(cfg) + 2 * cfg["hidden_size"]


def unembed_params(cfg: Dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def weight_bytes_per_step(cfg: Dict, itemsize: int = 2) -> int:
    """Weights one decode step reads: every layer plus the unembedding."""
    return itemsize * (cfg["num_hidden_layers"] * layer_params(cfg)
                       + unembed_params(cfg) + cfg["hidden_size"])


def kv_bytes_per_token_layer(cfg: Dict, itemsize: int = 2) -> int:
    _, _, kd, _, _ = _dims(cfg)
    return 2 * kd * itemsize


def token_flops(cfg: Dict, context: int, logits: bool) -> float:
    """Model operations for one token attending ``context`` positions
    (itself included): the matmuls through every layer, QK^T and PV, and
    the unembedding where the token's logits are used."""
    _, ad, _, _, _ = _dims(cfg)
    n = cfg["num_hidden_layers"]
    f = 2.0 * n * layer_matmul_params(cfg) + 4.0 * n * context * ad
    if logits:
        f += 2.0 * unembed_params(cfg)
    return f


def prefill_flops(cfg: Dict, start: int, end: int, logits: bool) -> float:
    """Operations of prompt positions [start, end): position p attends
    p + 1 keys."""
    n_tok = end - start
    ctx_sum = (start + end + 1) * n_tok / 2.0  # sum of (p + 1)
    _, ad, _, _, _ = _dims(cfg)
    n = cfg["num_hidden_layers"]
    f = 2.0 * n * layer_matmul_params(cfg) * n_tok + 4.0 * n * ad * ctx_sum
    if logits:
        f += 2.0 * unembed_params(cfg)
    return f


def decode_attention_paged(cfg: Dict, live: Iterable[int]):
    """(operations, bytes) of one decode-attention call over a batch whose
    slots hold ``live`` cache tokens each (the new token included): QK^T
    and PV over every query head, and each live K and V row read once."""
    _, ad, kd, _, _ = _dims(cfg)
    tokens = sum(live)
    flops = 4.0 * ad * tokens
    byts = tokens * kv_bytes_per_token_layer(cfg)
    return flops, byts


def roofline_seconds(flops: float, byts: float, peak: Dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               byts / peak["hbm_bytes_per_s"])


def window_model_flops(cfg: Dict, steps, lo: float, hi: float) -> float:
    """Model operations of every step run inside [lo, hi]: each decoded
    token attending its context, and each prompt chunk."""
    total = 0.0
    for s in steps:
        if s.t0 < lo or s.t1 > hi:
            continue
        total += sum(token_flops(cfg, n, logits=True) for n in s.live)
        total += sum(prefill_flops(cfg, a, b, logits=final)
                     for a, b, final in s.chunks)
    return total

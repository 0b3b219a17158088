"""A tiny mixture-of-experts block family (``"model_type": "toy_moe"``),
added as files only to show that the harness serves and judges a block
family other than Mistral's (``bench/tests/test_bench_families.py``).

Pre-norm residual layers, RMSNorm, RoPE, grouped-query attention with Q
and P, and in place of the FFN ``num_experts`` SwiGLU experts of width
``moe_intermediate_size``, of which a softmax router picks
``num_experts_per_tok`` a token, their gates renormalised
(``norm_topk_prob``).  Served in float32, so that no routing tie between
two experts falls differently in the program and the reference.
"""
from __future__ import annotations

from typing import Dict, Iterable

from bench.weights import EMBED_STD, padded_vocab


def program_config(cfg: Dict):
    from repro.configs.base import ModelConfig
    if not cfg["norm_topk_prob"]:
        raise ValueError("the program renormalises the chosen gates")
    dtype = cfg["torch_dtype"]
    return ModelConfig(
        name=cfg["name"], family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), ffn_type="swiglu",
        n_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        tie_embeddings=cfg["tie_word_embeddings"],
        init_style="normal", dtype=dtype, param_dtype=dtype)


def _dims(cfg: Dict):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = d // h
    return (d, h * hd, cfg["num_key_value_heads"] * hd,
            cfg["moe_intermediate_size"], cfg["num_experts"])


def layer_leaves(cfg: Dict):
    d, ad, kd, f, e = _dims(cfg)
    return [("attn/wq", (d, ad), d ** -0.5),
            ("attn/wk", (d, kd), d ** -0.5),
            ("attn/wv", (d, kd), d ** -0.5),
            ("attn/wp", (ad, d), ad ** -0.5),
            ("moe/router", (d, e), d ** -0.5),
            ("moe/w_gate", (e, d, f), d ** -0.5),
            ("moe/w_up", (e, d, f), d ** -0.5),
            ("moe/w_down", (e, f, d), f ** -0.5),
            ("norm1/scale", (d,), 0.0),
            ("norm2/scale", (d,), 0.0)]


def top_leaves(cfg: Dict):
    v, d = padded_vocab(cfg), cfg["hidden_size"]
    return [("embed/table", (v, d), EMBED_STD),
            ("unembed/table", (v, d), EMBED_STD),
            ("final_norm/scale", (d,), 0.0)]


# -- counts: a token multiplies through its active experts only; a decode
# step of a full batch reads every expert

def layer_matmul_params(cfg: Dict) -> int:
    d, ad, kd, f, e = _dims(cfg)
    return 2 * d * ad + 2 * d * kd + d * e \
        + cfg["num_experts_per_tok"] * 3 * d * f


def layer_params(cfg: Dict) -> int:
    d, ad, kd, f, e = _dims(cfg)
    return 2 * d * ad + 2 * d * kd + d * e + e * 3 * d * f + 2 * d


def weight_bytes_per_step(cfg: Dict, itemsize: int = 4) -> int:
    d = cfg["hidden_size"]
    return itemsize * (cfg["num_hidden_layers"] * layer_params(cfg)
                       + cfg["vocab_size"] * d + d)


def kv_bytes_per_token_layer(cfg: Dict, itemsize: int = 4) -> int:
    return 2 * _dims(cfg)[2] * itemsize


def _flops(cfg: Dict, n_tok: int, attended: float, logits: bool) -> float:
    n = cfg["num_hidden_layers"]
    f = 2.0 * n * layer_matmul_params(cfg) * n_tok \
        + 4.0 * n * _dims(cfg)[1] * attended
    return f + (2.0 * cfg["vocab_size"] * cfg["hidden_size"] if logits
                else 0.0)


def token_flops(cfg: Dict, context: int, logits: bool) -> float:
    return _flops(cfg, 1, context, logits)


def prefill_flops(cfg: Dict, start: int, end: int, logits: bool) -> float:
    return _flops(cfg, end - start, (start + end + 1) * (end - start) / 2.0,
                  logits)


def decode_attention_step(cfg: Dict, live: Iterable[int]):
    n, tokens = cfg["num_hidden_layers"], sum(live)
    return (n * 4.0 * _dims(cfg)[1] * tokens,
            n * tokens * kv_bytes_per_token_layer(cfg))

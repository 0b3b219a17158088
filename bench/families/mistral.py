"""The Mistral block family (``"model_type": "mistral"``): everything of the
harness that depends on the block.

Mistral-7B (arXiv:2310.06825) and the paper's Q/P-free form of it
(``block_style`` ``residual_qpfree``, arXiv:2404.12362 Fig 4): pre-norm
residual layers, RMSNorm, RoPE, grouped-query attention with a sliding
window, a SwiGLU FFN; head size ``hidden_size // num_attention_heads``.

  program_config   the file as the program's ``ModelConfig``
  layer_leaves,    (path, shape, std) of every weight, as the program lays
  top_leaves       them out; ``bench.weights`` draws them from the seed
  counts           parameters, bytes and operations from the shapes
                   (``bench.flops`` sums them over a window)

The parameter arithmetic follows ``benchmarks/bench_weight_table.py``
(weight bytes per layer by block style); the attention counts are the
useful work: every cache token a query attends, in each layer at most
that layer's window (``bench.windows``).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

from bench.weights import EMBED_STD, padded_vocab
from bench.windows import layer_windows

#: ``hidden_act`` as the program's ``ffn_type``
ACTS = {"silu": "swiglu"}
STYLES = ("standard", "residual_qpfree")


def program_config(cfg: Dict):
    """Mapped: the widths and depth, ``vocab_size``, the window,
    ``rope_theta``, ``hidden_act``, ``tie_word_embeddings``,
    ``torch_dtype``, ``block_style`` and ``merged_variant``.
    ``rms_norm_eps`` has no counterpart: the program's RMSNorm fixes its
    eps at 1e-6, which the file records under ``program_departs``.  The
    program has one window for every layer: layers with different
    windows raise."""
    from repro.configs.base import ModelConfig
    windows = set(layer_windows(cfg))
    if len(windows) != 1:
        raise ValueError(f"the program runs one window for every layer; "
                         f"this configuration has {sorted(windows)}")
    (window,) = windows
    dtype = cfg["torch_dtype"]
    optional = {k: cfg[k] for k in ("merged_variant",) if k in cfg}
    mc = ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        sliding_window=window,
        rope_theta=float(cfg["rope_theta"]), ffn_type=ACTS[cfg["hidden_act"]],
        block_style=cfg["block_style"],
        tie_embeddings=cfg["tie_word_embeddings"],
        init_style="normal", dtype=dtype, param_dtype=dtype, **optional)
    mc.validate_style()
    return mc


def _dims(cfg: Dict):
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    return (d, cfg["num_attention_heads"] * hd,
            cfg["num_key_value_heads"] * hd, cfg["intermediate_size"], hd)


def _standard(cfg: Dict) -> bool:
    if cfg["block_style"] not in STYLES:
        raise ValueError(f"unknown block_style {cfg['block_style']!r}")
    return cfg["block_style"] == "standard"


def layer_leaves(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], float]]:
    """(path, per-layer shape, std) of every layer leaf; std 0 = ones."""
    d, ad, kd, ff, _ = _dims(cfg)
    leaves = []
    if _standard(cfg):
        leaves.append(("attn/wq", (d, ad), d ** -0.5))
    leaves += [("attn/wk", (d, kd), d ** -0.5),
               ("attn/wv", (d, kd), d ** -0.5)]
    if _standard(cfg):
        leaves.append(("attn/wp", (ad, d), ad ** -0.5))
    leaves += [("ffn/w_gate", (d, ff), d ** -0.5),
               ("ffn/w_up", (d, ff), d ** -0.5),
               ("ffn/w_down", (ff, d), ff ** -0.5),
               ("norm1/scale", (d,), 0.0),
               ("norm2/scale", (d,), 0.0)]
    return leaves


def top_leaves(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], float]]:
    v, d = padded_vocab(cfg), cfg["hidden_size"]
    return [("embed/table", (v, d), EMBED_STD),
            ("unembed/table", (v, d), EMBED_STD),
            ("final_norm/scale", (d,), 0.0)]


# -- counts -------------------------------------------------------------------

def layer_matmul_params(cfg: Dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, ad, kd, ff, _ = _dims(cfg)
    attn = 2 * d * kd  # K and V
    if _standard(cfg):
        attn += d * ad + ad * d  # Q and P
    return attn + 3 * d * ff  # SwiGLU gate, up, down


def layer_params(cfg: Dict) -> int:
    """Every weight of one layer: matrices and the two norm scales."""
    return layer_matmul_params(cfg) + 2 * cfg["hidden_size"]


def _unembed_params(cfg: Dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def weight_bytes_per_step(cfg: Dict, itemsize: int = 2) -> int:
    """Weights one decode step reads: every layer plus the unembedding."""
    return itemsize * (cfg["num_hidden_layers"] * layer_params(cfg)
                       + _unembed_params(cfg) + cfg["hidden_size"])


def kv_bytes_per_token_layer(cfg: Dict, itemsize: int = 2) -> int:
    _, _, kd, _, _ = _dims(cfg)
    return 2 * kd * itemsize


def _attended(cfg: Dict, context: int) -> int:
    """Positions one token with ``context`` positions (itself included)
    attends, summed over the layers: each at most its layer's window."""
    return sum(min(context, w) if w else context for w in layer_windows(cfg))


def _attended_span(cfg: Dict, start: int, end: int) -> int:
    """``_attended`` summed over prompt positions [start, end): position
    p has p + 1 positions of context."""
    total = 0
    for w in layer_windows(cfg):
        top = min(end, w) if w else end  # contexts up to here are uncapped
        if top > start:
            total += (start + 1 + top) * (top - start) // 2
        total += w * (end - max(start, top))
    return total


def token_flops(cfg: Dict, context: int, logits: bool) -> float:
    """Model operations for one token attending ``context`` positions
    (itself included): the matmuls through every layer, QK^T and PV over
    the attended positions, and the unembedding where the token's logits
    are used."""
    _, ad, _, _, _ = _dims(cfg)
    n = cfg["num_hidden_layers"]
    f = 2.0 * n * layer_matmul_params(cfg) + 4.0 * ad * _attended(cfg, context)
    if logits:
        f += 2.0 * _unembed_params(cfg)
    return f


def prefill_flops(cfg: Dict, start: int, end: int, logits: bool) -> float:
    """Operations of prompt positions [start, end): position p attends
    p + 1 keys, at most each layer's window."""
    n_tok = end - start
    _, ad, _, _, _ = _dims(cfg)
    n = cfg["num_hidden_layers"]
    f = 2.0 * n * layer_matmul_params(cfg) * n_tok \
        + 4.0 * ad * _attended_span(cfg, start, end)
    if logits:
        f += 2.0 * _unembed_params(cfg)
    return f


def decode_attention_step(cfg: Dict, live: Iterable[int]):
    """(operations, bytes) of one decode step's paged attention over every
    layer, for slots that hold ``live`` cache tokens each (the new token
    included): QK^T and PV over every query head, and each attended K and
    V row read once; a layer attends at most its window."""
    _, ad, _, _, _ = _dims(cfg)
    tokens = sum(_attended(cfg, t) for t in live)
    return 4.0 * ad * tokens, tokens * kv_bytes_per_token_layer(cfg)

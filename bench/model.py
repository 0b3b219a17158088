"""A configuration file as the program's ``ModelConfig``.

The configuration files in ``bench/configs`` use the key names of the
published ``config.json``; this maps them onto the system under test.
Mapped: the widths and depth, ``vocab_size``, ``sliding_window``,
``rope_theta``, ``hidden_act`` (through ``ACTS``),
``tie_word_embeddings``, ``torch_dtype``, ``block_style`` and
``merged_variant``.  ``rms_norm_eps`` has no counterpart: the program's
RMSNorm fixes its eps at 1e-6, which the file records under
``program_departs``.
"""
from __future__ import annotations

from typing import Dict

ACTS = {"silu": "swiglu"}


def program_config(cfg: Dict):
    from repro.configs.base import ModelConfig
    dtype = cfg["torch_dtype"]
    optional = {k: cfg[k] for k in ("merged_variant",) if k in cfg}
    mc = ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        sliding_window=cfg.get("sliding_window") or 0,
        rope_theta=float(cfg["rope_theta"]), ffn_type=ACTS[cfg["hidden_act"]],
        block_style=cfg["block_style"],
        tie_embeddings=cfg["tie_word_embeddings"],
        init_style="normal", dtype=dtype, param_dtype=dtype, **optional)
    mc.validate_style()
    return mc

"""Seeded random weights for a configuration file, made on the device.

The benchmark makes the weights, not the program: ``make_params`` builds
the serving pytree in one jitted call from ``--seed``, and the plain
reference (``bench/references``) regenerates any single layer from the
same seed with ``layer_leaf``.  Every leaf is drawn from its own key,
``fold_in(fold_in(seed_key, leaf index), layer)``, so a layer can be
rebuilt alone and a stacked leaf equals its layers drawn one by one.

Matrices are normal with variance 1/fan_in, embedding tables normal with
std 0.02 (the program's ``init_style="normal"``), norm scales ones.  The
values are drawn in float32 and rounded once to the served dtype.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

EMBED_STD = 0.02


def seed_key(seed: int):
    """PRNG key from a seed of any size: ``PRNGKey`` keeps only the low
    32 bits, so the high bits are folded in."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def layer_leaves(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], float]]:
    """(path, per-layer shape, std) of every layer leaf; std 0 = ones."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    kd = cfg["num_key_value_heads"] * hd
    ad = cfg["num_attention_heads"] * hd
    leaves = []
    if cfg["block_style"] == "standard":
        leaves.append(("attn/wq", (d, ad), d ** -0.5))
    elif cfg["block_style"] != "residual_qpfree":
        raise ValueError(f"unknown block_style {cfg['block_style']!r}")
    leaves += [("attn/wk", (d, kd), d ** -0.5),
               ("attn/wv", (d, kd), d ** -0.5)]
    if cfg["block_style"] == "standard":
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


def padded_vocab(cfg: Dict) -> int:
    return -(-cfg["vocab_size"] // 128) * 128


def _draw(key, shape, std, dtype):
    if std == 0.0:
        return jnp.ones(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _leaf_key(seed_k, group: str, index: int):
    return jax.random.fold_in(jax.random.fold_in(seed_k, hash_name(group)),
                              index)


def hash_name(name: str) -> int:
    """Stable 31-bit id of a leaf path (Python's ``hash`` is salted)."""
    h = 0
    for ch in name.encode():
        h = (h * 131 + ch) % (2 ** 31 - 1)
    return h


def layer_leaf(seed_k, path: str, layer: int, shape, std, dtype):
    """Layer ``layer``'s value of one stacked leaf."""
    return _draw(_leaf_key(seed_k, path, layer), shape, std, dtype)


def top_leaf(seed_k, path: str, shape, std, dtype):
    return _draw(_leaf_key(seed_k, path, 0), shape, std, dtype)


def _nest(flat: Dict[str, jnp.ndarray]) -> Dict:
    out: Dict = {}
    for path, x in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = x
    return out


def build_params(seed_k, cfg: Dict, dtype):
    """The serving pytree (traceable): embed/unembed tables, stacked
    ``layers`` and the final norm, as ``repro.models`` lays them out."""
    n = cfg["num_hidden_layers"]
    flat = {}
    for path, shape, std in top_leaves(cfg):
        flat[path] = top_leaf(seed_k, path, shape, std, dtype)
    layers = {}
    for path, shape, std in layer_leaves(cfg):
        keys = jax.vmap(lambda i, p=path: _leaf_key(seed_k, p, i))(
            jnp.arange(n))
        layers[path] = jax.vmap(
            lambda k, s=shape, sd=std: _draw(k, s, sd, dtype))(keys)
    params = _nest(flat)
    params["layers"] = _nest(layers)
    return params


def make_params(cfg: Dict, seed: int, dtype=jnp.bfloat16):
    """Build every weight on the default device in one jitted call."""
    fn = jax.jit(lambda k: build_params(k, cfg, dtype))
    return fn(seed_key(seed))

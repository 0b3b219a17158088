"""Seeded random weights for a configuration file, made on the device.

The benchmark makes the weights, not the program: ``make_params`` builds
the serving pytree in one jitted call from ``--seed``, and the plain
reference (``bench/references``) regenerates any single layer from the
same seed with ``layer_leaf``.  Every leaf is drawn from its own key,
``fold_in(fold_in(seed_key, leaf index), layer)``, so a layer can be
rebuilt alone and a stacked leaf equals its layers drawn one by one.
Which leaves there are, with their shapes and stds, the configuration's
block family says (``layer_leaves`` and ``top_leaves`` of
``bench/families/<model_type>.py``).

Matrices are normal with variance 1/fan_in, embedding tables normal with
std 0.02 (the program's ``init_style="normal"``), norm scales ones.  The
values are drawn in float32 and rounded once to the served dtype.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

EMBED_STD = 0.02


def seed_key(seed: int):
    """PRNG key from a seed of any size: ``PRNGKey`` keeps only the low
    32 bits, so the high bits are folded in."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


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


def build_params(seed_k, family, cfg: Dict, dtype):
    """The serving pytree (traceable): the family's top leaves and its
    stacked ``layers``, as ``repro.models`` lays them out."""
    n = cfg["num_hidden_layers"]
    flat = {}
    for path, shape, std in family.top_leaves(cfg):
        flat[path] = top_leaf(seed_k, path, shape, std, dtype)
    layers = {}
    for path, shape, std in family.layer_leaves(cfg):
        keys = jax.vmap(lambda i, p=path: _leaf_key(seed_k, p, i))(
            jnp.arange(n))
        layers[path] = jax.vmap(
            lambda k, s=shape, sd=std: _draw(k, s, sd, dtype))(keys)
    params = _nest(flat)
    params["layers"] = _nest(layers)
    return params


def make_params(family, cfg: Dict, seed: int, dtype=jnp.bfloat16):
    """Build every weight on the default device in one jitted call."""
    fn = jax.jit(lambda k: build_params(k, family, cfg, dtype))
    return fn(seed_key(seed))

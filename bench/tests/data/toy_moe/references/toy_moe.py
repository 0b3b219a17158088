"""Plain float32 reference of the toy mixture-of-experts family: the Mistral
reference's embedding, attention and unembedding, and in place of its
FFN a softmax router over every expert, the top ``num_experts_per_tok``
of them, their gates renormalised to sum to one, and SwiGLU experts.
Every expert runs on every token, weighted by its gate (0 where it was
not chosen): plain, and cheap at the sizes it is run at."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from bench.references import mistral as M


def _experts(cfg, lw, x):
    probs = jax.nn.softmax(jnp.dot(x, lw["moe/router"], precision=M.HI), -1)
    gates, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(idx, cfg["num_experts"])
                     * gates[..., None], axis=-2)  # (R, S, E)
    g = jnp.einsum("rsd,edf->rsef", x, lw["moe/w_gate"], precision=M.HI)
    u = jnp.einsum("rsd,edf->rsef", x, lw["moe/w_up"], precision=M.HI)
    y = jnp.einsum("rsef,efd->rsed", jax.nn.silu(g) * u, lw["moe/w_down"],
                   precision=M.HI)
    return jnp.einsum("rse,rsed->rsd", weight, y, precision=M.HI)


def _block(cfg, lw, h, pos, seg, layer):
    eps = cfg["rms_norm_eps"]
    h = h + M.attend(cfg, lw, M.rms(h, lw["norm1/scale"], eps), pos, seg,
                     layer.window)
    return h + _experts(cfg, lw, M.rms(h, lw["norm2/scale"], eps))


def make_forward(cfg: Dict, family, mode: str = "served"):
    return M.forward(cfg, family, _block, mode)

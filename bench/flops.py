"""Operations and bytes over a window, from a block family's counts.

What one token, prompt chunk or decode step computes and reads is the
family's (``token_flops``, ``prefill_flops``, ``decode_attention_step``
and the parameter counts of ``bench/families/<model_type>.py``); this
module adds them up over the steps a window ran and holds them against
the chip's peaks.  Readers find this module as ``ctx.flops`` and the
family as ``ctx.family``.
"""
from __future__ import annotations

from typing import Dict

#: what every family module counts (``test_bench_spec`` holds each to it)
COUNTS = ("layer_matmul_params", "layer_params", "weight_bytes_per_step",
          "kv_bytes_per_token_layer", "token_flops", "prefill_flops",
          "decode_attention_step")


def roofline_seconds(flops: float, byts: float, peak: Dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               byts / peak["hbm_bytes_per_s"])


def window_model_flops(family, cfg: Dict, steps, lo: float,
                       hi: float) -> float:
    """Model operations of every step run inside [lo, hi]: each decoded
    token attending its context, and each prompt chunk."""
    total = 0.0
    for s in steps:
        if s.t0 < lo or s.t1 > hi:
            continue
        total += sum(family.token_flops(cfg, n, logits=True) for n in s.live)
        total += sum(family.prefill_flops(cfg, a, b, logits=final)
                     for a, b, final in s.chunks)
    return total


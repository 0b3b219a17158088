"""The attention window of every layer, read once from a configuration's
published keys.

  layer_types          one entry a layer: ``"sliding_attention"`` takes
                       ``sliding_window``, ``"full_attention"`` none
  sliding_window       without ``layer_types``, every layer's window
  use_sliding_window   ``false`` turns every window off

A window of 0 is full attention.  The counts, the reference's masks and
the contract's tests all take a layer's window from here and nowhere
else.
"""
from __future__ import annotations

from typing import Dict, List

LAYER_TYPES = ("sliding_attention", "full_attention")


def layer_windows(cfg: Dict) -> List[int]:
    """One window a layer, 0 for full attention."""
    n = cfg["num_hidden_layers"]
    window = cfg.get("sliding_window") or 0
    if cfg.get("use_sliding_window") is False:
        window = 0
    types = cfg.get("layer_types")
    if types is None:
        return [window] * n
    if len(types) != n:
        raise ValueError(f"layer_types has {len(types)} entries for "
                         f"{n} layers")
    unknown = sorted(set(types) - set(LAYER_TYPES))
    if unknown:
        raise ValueError(f"unknown layer types {unknown}")
    return [window if t == "sliding_attention" else 0 for t in types]

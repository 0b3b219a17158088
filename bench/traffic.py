"""One generator for every traffic mix: a mix is a data file of parameters.

A mix file (``bench/traffic/<name>.json``) holds:

  loop       "closed": ``clients`` requests kept in the system, each
             replaced by a new one as it finishes (the only kind so far).
  prompt, output
             lognormal lengths: ``median``, ``sigma``, clipped to
             [``min``, ``max``].
  size_seed  the seed of the SET of sizes.  Every run draws the same set
             and ``--seed`` only orders it and draws the token ids, so
             runs with different seeds do the same work.
  set_size   lengths in the set; the first ``clients`` requests get
             residual lifetimes (a length-biased draw cut at a uniform
             point, the part already generated joining the prompt), so a
             window opens in steady state.
  engine     the serving settings (slots, pages, budget, ``max_len``).
  check      how many packed rows of ``max_len`` the reference runs.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np


@dataclass
class Arrival:
    due: float  # seconds from the window's start (negative: before it)
    prompt_len: int  # tokens the request carries (context already built)
    output_len: int  # tokens it asks for
    client: int = -1  # closed loop: the client it belongs to


def _lengths(rng, spec: Dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


class Mix:
    """The requests of one run: ``initial``, the clients' first requests,
    admitted before the window, then ``next_for`` as each client frees."""

    def __init__(self, spec: Dict, seed: int, vocab: int):
        if spec["loop"] != "closed":
            raise ValueError(f"unknown loop {spec['loop']!r}")
        self.spec, self.vocab = spec, vocab
        self.tok_rng = np.random.default_rng([seed, 1])
        order = np.random.default_rng([seed, 2])
        sizes = np.random.default_rng(spec["size_seed"])
        n_cl, n = spec["clients"], spec["set_size"]
        prompts = _lengths(sizes, spec["prompt"], n)
        outputs = _lengths(sizes, spec["output"], n)
        # residual lifetimes: length-biased lengths, cut uniformly
        pool = _lengths(sizes, spec["output"], 64 * n_cl)
        biased = sizes.choice(pool, size=n_cl, p=pool / pool.sum())
        left = np.maximum(1, np.ceil(sizes.uniform(size=n_cl) * biased))
        first = _lengths(sizes, spec["prompt"], n_cl)
        perm = order.permutation(n_cl)
        self.initial: List[Arrival] = []
        for c in range(n_cl):
            i = perm[c]
            done = int(biased[i] - left[i])
            self.initial.append(Arrival(0.0, int(first[i]) + done,
                                        int(left[i]), client=c))
        self._next = order.permutation(n)
        self._sizes = (prompts, outputs)
        self._k = 0

    def next_for(self, client: int, now: float) -> Arrival:
        """Closed loop: the request a client sends when its last one
        finishes (the set is cycled when exhausted)."""
        prompts, outputs = self._sizes
        i = self._next[self._k % len(self._next)]
        self._k += 1
        return Arrival(now, int(prompts[i]), int(outputs[i]), client=client)

    def tokens(self, n: int) -> np.ndarray:
        """A prompt's token ids, uniform over the vocabulary."""
        return self.tok_rng.integers(0, self.vocab, size=n, dtype=np.int32)

"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the served requests is packed into a fixed number of rows of ``max_len``
positions: the longest finished request, then every request admitted
inside the window (its chunked prefill ran in the window), then others,
finished or still decoding, in an order drawn from the seed.  The
reference runs once over each prompt with its served tokens, and the
compared number is the widest gap by which a served token's logit lies
below the reference's best logit at that position.  Greedy decoding
serves the best token, so a sound program reads only its rounding there.

The control puts the reference, with its weights rounded to float8, in
the program's place: at every position of the same rows it reads the
gap of the token that the control ranks first.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def sample(served: Sequence, seed: int, rows: int, width: int) -> List:
    """Pack requests first-fit into ``rows`` rows of ``width``: the
    longest finished one, then those admitted inside the window, then the
    rest in an order drawn from the seed.  Each item of ``served`` has
    ``prompt`` and ``out`` token lists and the flags ``finished`` and
    ``admitted_in_window``."""
    fits = [f for f in served if len(f.prompt) + len(f.out) - 1 <= width]
    done = [f for f in fits if f.finished]
    head = [max(done, key=lambda f: len(f.prompt) + len(f.out))] \
        if done else []
    new = [f for f in fits if f.admitted_in_window
           and all(f is not h for h in head)]
    taken = {id(f) for f in head + new}
    rest = [f for f in fits if id(f) not in taken]
    order = np.random.default_rng([seed, 3]).permutation(len(rest))
    free = [width] * rows
    placed: List[Tuple[int, int, object]] = []  # (row, offset, item)
    for f in head + new + [rest[i] for i in order]:
        n = len(f.prompt) + len(f.out) - 1
        for r in range(rows):
            if free[r] >= n:
                placed.append((r, width - free[r], f))
                free[r] -= n
                break
    return placed


def pack(placed, rows: int, width: int):
    """Rows of tokens, positions, segment ids (-1 = empty) and targets,
    plus the (row, position) of every served token with its id."""
    tok = np.zeros((rows, width), np.int32)
    pos = np.zeros((rows, width), np.int32)
    seg = np.full((rows, width), -1, np.int32)
    tgt = np.zeros((rows, width), np.int32)
    served = []
    for k, (r, o, f) in enumerate(placed):
        seq = np.concatenate([np.asarray(f.prompt, np.int32),
                              np.asarray(f.out[:-1], np.int32)])
        n = len(seq)
        tok[r, o:o + n] = seq
        pos[r, o:o + n] = np.arange(n)
        seg[r, o:o + n] = k
        p0 = o + len(f.prompt) - 1
        for j, t in enumerate(f.out):
            tgt[r, p0 + j] = t
            served.append((r, p0 + j))
    return tok, pos, seg, tgt, served


def served_gaps(fwd, key, rows, vocab: int) -> Dict:
    """Run the reference over packed rows; the gap of every served token."""
    tok, pos, seg, tgt, served = rows
    oov = int(sum(1 for r, p in served if not 0 <= tgt[r, p] < vocab))
    best, got, _ = (np.asarray(x) for x in fwd(key, tok, pos, seg,
                                               np.clip(tgt, 0, vocab - 1)))
    gaps = np.asarray([best[r, p] - got[r, p] for r, p in served])
    return {"gaps": gaps, "out_of_vocab": oov}


def control_gaps(fwd, control, key, rows) -> np.ndarray:
    """At each served position, the reference's gap of the token that the
    control (the reference in float8) ranks first."""
    tok, pos, seg, tgt, served = rows
    _, _, pick = (np.asarray(x) for x in control(key, tok, pos, seg, tgt))
    best, got, _ = (np.asarray(x) for x in fwd(key, tok, pos, seg, pick))
    return np.asarray([best[r, p] - got[r, p] for r, p in served])

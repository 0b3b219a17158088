"""The traffic generator: reproducible from the seed, and every seed gets
the same set of sizes (only their order and the token ids change)."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.traffic import Mix  # noqa: E402

BIG = 2 ** 33 + 12345  # seeds are whole numbers above 32 bits too


def mix_file(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def traffic_names():
    return sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))


def sizes(m: Mix):
    """The clients' first requests, then one pass over the set."""
    later = [m.next_for(0, 0.0) for _ in range(m.spec["set_size"])]
    return [(a.due, a.prompt_len, a.output_len) for a in m.initial + later]


@pytest.mark.parametrize("name", traffic_names())
def test_same_seed_same_requests(name):
    a, b = Mix(mix_file(name), BIG, 32000), Mix(mix_file(name), BIG, 32000)
    assert sizes(a) == sizes(b)
    assert (a.tokens(300) == b.tokens(300)).all()


@pytest.mark.parametrize("name", traffic_names())
def test_other_seed_same_set_other_order(name):
    a = Mix(mix_file(name), BIG, 32000)
    b = Mix(mix_file(name), BIG + 1, 32000)
    pa = sorted((p, o) for _, p, o in sizes(a))
    pb = sorted((p, o) for _, p, o in sizes(b))
    assert pa == pb
    assert sizes(a) != sizes(b)
    assert not (a.tokens(64) == b.tokens(64)).all()


@pytest.mark.parametrize("name", traffic_names())
def test_requests_fit_the_engine(name):
    spec = mix_file(name)
    m = Mix(spec, BIG, 32000)
    reqs = m.initial + [m.next_for(0, 0.0) for _ in range(spec["set_size"])]
    assert all(a.prompt_len + a.output_len <= spec["engine"]["max_len"]
               for a in reqs)
    assert all(a.output_len >= 1 and a.prompt_len >= 1 for a in reqs)


def test_unknown_loop_is_refused():
    with pytest.raises(ValueError, match="loop"):
        Mix(dict(mix_file("decode"), loop="open"), BIG, 32000)


def test_closed_loop_residual_start():
    spec = mix_file("decode")
    m = Mix(spec, BIG, 32000)
    assert len(m.initial) == spec["clients"]
    outs = [a.output_len for a in m.initial]
    # residual lifetimes: shorter than the full answers on average
    full = [m.next_for(0, 0.0).output_len for _ in range(200)]
    assert np.mean(outs) < 0.8 * np.mean(full)

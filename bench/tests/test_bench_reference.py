"""The float32 reference against the program's own whole-sequence forward
(``repro.models.forward_seq``) at tiny widths, for both block styles,
with the same seeded weights; and packed rows against separate ones."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import tiny, weights  # noqa: E402
from bench.model import program_config  # noqa: E402
from bench.references.mistral import make_forward  # noqa: E402

SEED = 2 ** 33 + 5


def tiny_cfg(style, window=0):
    c = dict(name="tiny", rope_theta=10000.0, rms_norm_eps=1e-6,
             sliding_window=window, hidden_act="silu",
             tie_word_embeddings=False, torch_dtype="float32",
             block_style=style)
    c.update(tiny.CONFIG)
    return c


@pytest.mark.parametrize("style", ["standard", "residual_qpfree"])
@pytest.mark.parametrize("window", [0, 8])
def test_reference_matches_program_forward(style, window):
    from repro.models import forward_seq, init_params
    cfg = tiny_cfg(style, window)
    mc = program_config(cfg)
    p = weights.make_params(cfg, SEED, jnp.float32)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), mc))
    assert jax.tree.structure(shapes) == jax.tree.structure(p)
    S = 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, S), 0, 128)
    with jax.default_matmul_precision("highest"):
        lg = np.asarray(forward_seq(p, mc, toks)[0])
    tg = jnp.roll(toks, -1, axis=1)
    best, got, arg = (np.asarray(x) for x in make_forward(cfg)(
        weights.seed_key(SEED), toks, jnp.arange(S)[None],
        jnp.zeros((1, S), jnp.int32), tg))
    scale = np.abs(lg).max()
    assert np.abs(best - lg.max(-1)).max() <= 1e-5 * scale
    want = np.take_along_axis(lg, np.asarray(tg)[..., None], -1)[..., 0]
    assert np.abs(got - want).max() <= 1e-5 * scale
    assert (arg == lg.argmax(-1)).all()


def test_packed_rows_equal_separate_rows():
    cfg = tiny_cfg("standard")
    f = make_forward(cfg)
    key = weights.seed_key(SEED)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 64), 0, 128)
    seg = jnp.repeat(jnp.arange(2), 32)[None]
    pos = jnp.tile(jnp.arange(32), 2)[None]
    packed = np.asarray(f(key, toks, pos, seg, toks)[0])
    for k in range(2):
        part = toks[:, 32 * k:32 * (k + 1)]
        alone = np.asarray(f(key, jnp.pad(part, ((0, 0), (0, 32))),
                             jnp.arange(64)[None],
                             jnp.where(jnp.arange(64) < 32, 0, -1)[None],
                             jnp.pad(part, ((0, 0), (0, 32))))[0])
        np.testing.assert_allclose(packed[0, 32 * k:32 * (k + 1)],
                                   alone[0, :32], rtol=1e-5, atol=1e-6)


def test_layer_regenerated_alone_equals_stacked_leaf():
    cfg = tiny_cfg("standard")
    p = weights.make_params(cfg, SEED, jnp.bfloat16)
    key = weights.seed_key(SEED)
    for path, shape, std in weights.layer_leaves(cfg):
        a, b = path.split("/")
        w1 = weights.layer_leaf(key, path, 1, shape, std, jnp.bfloat16)
        assert (np.asarray(p["layers"][a][b][1]) == np.asarray(w1)).all()


def test_seeds_above_32_bits_differ():
    a = weights.seed_key(5)
    b = weights.seed_key(5 + 2 ** 32)
    assert not (np.asarray(a) == np.asarray(b)).all()


def test_sample_orders_longest_finished_then_admitted_in_window():
    from types import SimpleNamespace

    from bench.check import pack, sample

    def req(n_prompt, n_out, finished=False, new=False):
        return SimpleNamespace(prompt=np.arange(n_prompt) % 7,
                               out=list(range(1, n_out + 1)),
                               finished=finished, admitted_in_window=new)

    short_done = req(10, 5, finished=True)
    long_done = req(30, 20, finished=True)  # 49 positions
    new_live = req(10, 6, new=True)  # 15: fills the first row
    old_live = [req(20, 10) for _ in range(4)]  # 29: two fit the second
    too_long = req(60, 10)
    placed = sample([short_done, too_long, *old_live, new_live, long_done],
                    SEED, rows=2, width=64)
    items = [f for _, _, f in placed]
    assert items[0] is long_done and items[1] is new_live
    assert all(f is not too_long for f in items)
    assert len(items) == 4
    tok, pos, seg, tgt, served = pack(placed, 2, 64)
    assert len(served) == sum(len(f.out) for f in items)
    r, o, f = placed[1]
    p0 = o + len(f.prompt) - 1
    assert list(tgt[r, p0:p0 + len(f.out)]) == f.out
    assert (seg[r, o:o + len(f.prompt) + len(f.out) - 1] == 1).all()

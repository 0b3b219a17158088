"""A configuration's block family is found by the file's ``model_type``
(``bench/families/<model_type>.py``), and the Mistral family gives what
the harness gave before it had families: the same ``ModelConfig``, the
same weights leaf by leaf, the same counts (values recorded from the
harness before the move).  And a family that is not Mistral's, added as
files only, is served and judged: a sound run is correct, one with the
program's experts per token one lower is not.  So is a configuration
whose window binds: correct, and not correct with the window dropped."""
import dataclasses
import json
import shutil
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import flops, tiny, weights  # noqa: E402
from bench.run import run_cell  # noqa: E402
from bench.spec import Spec  # noqa: E402

CONFIGS = ("mistral-7b", "mistral-7b-qpfree")
SEED = 2 ** 33 + 5


def family_and_cfg(name):
    spec = Spec()
    cfg = spec.config(name)
    return spec.family(cfg), cfg


@pytest.mark.parametrize("name", CONFIGS)
def test_program_config_is_the_one_the_harness_built(name):
    from repro.configs.base import ModelConfig
    fam, cfg = family_and_cfg(name)
    style = "standard" if name == "mistral-7b" else "residual_qpfree"
    want = ModelConfig(
        name=name, family="dense", n_layers=16, d_model=4096, n_heads=32,
        n_kv_heads=8, d_head=128, d_ff=14336, vocab_size=32000,
        sliding_window=4096, rope_theta=10000.0, ffn_type="swiglu",
        block_style=style, merged_variant="qp", tie_embeddings=False,
        init_style="normal", dtype="bfloat16", param_dtype="bfloat16")
    assert fam.program_config(cfg) == want


# (sum, sum of squares) in float64 of every leaf of ``make_params`` at
# tiny widths, bfloat16, seed 2**33 + 5, as the harness drew them before
# block families; the Q/P-free style has the same leaves without Q and P
LEAF_SUMS = {
    "['embed']['table']": (-2.2247327864170074, 3.288446142548127),
    "['final_norm']['scale']": (64.0, 64.0),
    "['layers']['attn']['wk']": (2.7059640884399414, 64.14352322852255),
    "['layers']['attn']['wp']": (-7.755960464477539, 130.69685851341637),
    "['layers']['attn']['wq']": (-1.1234630346298218, 131.427584956467),
    "['layers']['attn']['wv']": (-13.80175793170929, 65.039898784333),
    "['layers']['ffn']['w_down']": (-5.008335590362549, 126.7360430095724),
    "['layers']['ffn']['w_gate']": (-15.369503438472748, 196.85185080378824),
    "['layers']['ffn']['w_up']": (-1.1227065846323967, 194.29875252594758),
    "['layers']['norm1']['scale']": (128.0, 128.0),
    "['layers']['norm2']['scale']": (128.0, 128.0),
    "['unembed']['table']": (-1.1554865464568138, 3.3624753518062054),
}


@pytest.mark.parametrize("style", ["standard", "residual_qpfree"])
def test_weights_are_the_ones_the_harness_drew(style):
    cfg = dict(name="tiny", model_type="mistral", rope_theta=10000.0,
               rms_norm_eps=1e-6, sliding_window=0, hidden_act="silu",
               tie_word_embeddings=False, torch_dtype="bfloat16",
               block_style=style, **tiny.CONFIG)
    p = weights.make_params(Spec().family(cfg), cfg, SEED, jnp.bfloat16)
    got = {}
    for path, x in jax.tree_util.tree_flatten_with_path(p)[0]:
        x = np.asarray(x, np.float32)
        got[jax.tree_util.keystr(path)] = (
            float(np.sum(x, dtype=np.float64)),
            float(np.sum(np.square(x), dtype=np.float64)))
    want = {k: v for k, v in LEAF_SUMS.items()
            if style == "standard" or not k.endswith(("['wq']", "['wp']"))}
    assert got == want


# counts at the committed widths, as the harness counted them before
# (``test_bench_flops.py`` holds the parameters a layer, the KV bytes and
# one decode step's attention to the sizes worked out by hand)
COUNTS = {
    "mistral-7b": dict(
        layer_matmul_params=218_103_808,
        weight_bytes_per_step=7_241_736_192, token_flops=7445151744.0,
        token_flops_nl=6980632576.0, prefill=3676821258240.0,
        prefill_nl=2105632358400.0),
    "mistral-7b-qpfree": dict(
        layer_matmul_params=184_549_376,
        weight_bytes_per_step=6_167_994_368, token_flops=6371409920.0,
        token_flops_nl=5906890752.0, prefill=3127065444352.0,
        prefill_nl=1783509811200.0),
}


@pytest.mark.parametrize("name", CONFIGS)
def test_counts_are_the_ones_the_harness_counted(name):
    fam, cfg = family_and_cfg(name)
    want = COUNTS[name]
    assert fam.layer_matmul_params(cfg) == want["layer_matmul_params"]
    assert fam.weight_bytes_per_step(cfg) == want["weight_bytes_per_step"]
    assert fam.token_flops(cfg, 777, logits=True) == want["token_flops"]
    assert fam.token_flops(cfg, 5, logits=False) == want["token_flops_nl"]
    assert fam.prefill_flops(cfg, 512, 1024, logits=True) == want["prefill"]
    assert fam.prefill_flops(cfg, 0, 300, logits=False) == \
        want["prefill_nl"]


@pytest.mark.parametrize("name", CONFIGS)
def test_a_prompt_chunk_counts_as_its_positions_one_by_one(name):
    fam, cfg = family_and_cfg(name)
    one = [fam.token_flops(cfg, p + 1, logits=False) for p in range(60, 70)]
    assert fam.prefill_flops(cfg, 60, 70, logits=False) == \
        pytest.approx(sum(one))


def test_window_model_flops_sums_the_familys_counts():
    from types import SimpleNamespace
    fam, cfg = family_and_cfg("mistral-7b")
    steps = [SimpleNamespace(t0=0.0, t1=1.0, live=[10, 20],
                             chunks=[(0, 64, False)]),
             SimpleNamespace(t0=1.0, t1=3.0, live=[11], chunks=[])]
    want = fam.token_flops(cfg, 10, True) + fam.token_flops(cfg, 20, True) \
        + fam.prefill_flops(cfg, 0, 64, False)
    assert flops.window_model_flops(fam, cfg, steps, 0.0, 2.0) == want


DATA = Path(__file__).resolve().parent / "data"
TOY_SEED = 2 ** 33 + 11


def toy_benchmark(tmp_path, toy="toy_moe", config="toy-moe",
                  traffic="tiny"):
    """A checkout of the benchmark with a toy's files added (for the toy
    mixture of experts a family, its reference, a configuration and a
    traffic mix) and entries for its cell ``<config>.<traffic>`` in
    ``BENCHMARK.json``; no harness file is edited."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for src in sorted(p for p in (DATA / toy).rglob("*")
                      if p.suffix in (".py", ".json")):
        dst = bench / src.relative_to(DATA / toy)
        assert not dst.exists()
        dst.parent.mkdir(exist_ok=True)
        shutil.copy(src, dst)
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": config, "source": "x",
                           "file": f"bench/configs/{config}.json",
                           "reduced": [], "why": "x"})
    doc["workloads"].append({"name": f"{config}.{traffic}", "config": config,
                             "traffic": traffic, "chips": 1, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    return tmp_path, bench


class FewerExperts(Spec):
    """The program run with one expert a token fewer than the file says,
    set through the family's ``program_config``."""

    def family(self, cfg):
        fam = super().family(cfg)
        build = fam.program_config

        def program_config(c):
            mc = build(c)
            return mc.with_(experts_per_token=mc.experts_per_token - 1)

        fam.program_config = program_config
        return fam


# over seven seeds on the CPU, sound runs read a widest gap of 0.0 (served
# in float32, the program's greedy token is the reference's best) and runs
# with one expert fewer 0.41-0.67; the toy file's limit is 0.01
@pytest.mark.parametrize("spec_cls,correct", [(Spec, True),
                                              (FewerExperts, False)],
                         ids=["sound", "one_expert_fewer"])
def test_a_family_added_as_files_is_served_and_judged(tmp_path, spec_cls,
                                                      correct):
    root, bench = toy_benchmark(tmp_path)
    spec = spec_cls(root, bench)
    fam = spec.family(spec.config("toy-moe"))
    assert all(callable(getattr(fam, n)) for n in flops.COUNTS)
    assert fam.program_config(spec.config("toy-moe")).family == "moe"
    out = run_cell(spec, "toy-moe.tiny", TOY_SEED, 1.5, False, impl="xla",
                   allow_cpu=True)
    assert out["correct"] is correct, out["compared"]
    gap = out["compared"]["worst_logit_gap"]
    assert (gap["value"] <= gap["limit"]) is correct
    assert out["attempted"] > 0 and out["failed"] == 0


def test_the_toy_reference_matches_the_programs_forward(tmp_path):
    """The toy's reference against the program's whole-sequence forward
    with the same seeded weights, at every position."""
    from repro.models import forward_seq, init_params
    root, bench = toy_benchmark(tmp_path)
    spec = Spec(root, bench)
    cfg = spec.config("toy-moe")
    fam = spec.family(cfg)
    mc = fam.program_config(cfg)
    p = weights.make_params(fam, cfg, TOY_SEED, jnp.float32)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), mc))
    assert jax.tree.structure(shapes) == jax.tree.structure(p)
    assert [x.shape for x in jax.tree.leaves(shapes)] == \
        [x.shape for x in jax.tree.leaves(p)]
    S = 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, S), 0, 128)
    # one group, and capacity for every token: the training forward drops
    # none
    mc = dataclasses.replace(mc, moe_group=0,
                             capacity_factor=float(mc.n_experts))
    with jax.default_matmul_precision("highest"):
        lg = np.asarray(forward_seq(p, mc, toks)[0])
    best, _, arg = (np.asarray(x) for x in spec.reference(cfg).make_forward(
        cfg, fam)(weights.seed_key(TOY_SEED), toks, jnp.arange(S)[None],
                  jnp.zeros((1, S), jnp.int32), toks))
    assert np.abs(best - lg.max(-1)).max() <= 1e-5 * np.abs(lg).max()
    assert (arg == lg.argmax(-1)).all()


class NoWindow(Spec):
    """The program run without the window the file states, set through
    the family's ``program_config``."""

    def family(self, cfg):
        fam = super().family(cfg)
        build = fam.program_config
        fam.program_config = lambda c: build(c).with_(sliding_window=0)
        return fam


# a Mistral-family toy with one window of 32 for every layer, served
# through the paged ring in chunks of one page.  Over seven seeds on the
# CPU, sound runs read a widest gap of 0.0 and runs with the window
# dropped 0.71-0.98, the longest compared request 156-170 positions; the
# toy file's limit is 0.01
@pytest.mark.parametrize("spec_cls,correct", [(Spec, True),
                                              (NoWindow, False)],
                         ids=["sound", "window_dropped"])
def test_a_window_that_binds_is_served_and_judged(tmp_path, monkeypatch,
                                                  spec_cls, correct):
    from bench import check
    root, bench = toy_benchmark(tmp_path, "toy_window", "toy-window",
                                "tiny_window")
    spec = spec_cls(root, bench)
    cfg = spec.config("toy-window")
    assert spec.family(cfg).program_config(cfg).sliding_window == \
        (32 if correct else 0)
    compared = []
    sample = check.sample

    def recorded(*args, **kw):
        placed = sample(*args, **kw)
        compared.extend(len(f.prompt) + len(f.out) for _, _, f in placed)
        return placed

    monkeypatch.setattr(check, "sample", recorded)
    out = run_cell(spec, "toy-window.tiny_window", TOY_SEED, 1.5, False,
                   impl="xla", allow_cpu=True)
    assert max(compared) > 2 * 32
    assert out["correct"] is correct, out["compared"]
    gap = out["compared"]["worst_logit_gap"]
    assert (gap["value"] <= gap["limit"]) is correct
    assert out["attempted"] > 0 and out["failed"] == 0

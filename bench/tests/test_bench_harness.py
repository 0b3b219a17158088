"""The harness end to end at tiny sizes on the CPU (its look for a chip
skipped): a sound run is correct, and runs with the timed path broken
underneath are not.  And without a TPU, or without the program beside
it, ``bench/run.py`` exits non-zero and prints no result."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import tiny  # noqa: E402
from bench.run import run_cell  # noqa: E402
from bench.spec import Spec  # noqa: E402

SEED = 2 ** 33 + 11
# tiny widths: over six seeds of each configuration sound runs read worst
# gaps of 0.0024-0.0109 here and the float8 control 0.062-0.147
TINY_LIMIT = 0.04


def run(workload="mistral-7b.decode", control=False, pool="paged",
        mix=None):
    cfg = dict(tiny.CONFIG, check={"max_logit_gap": TINY_LIMIT})
    return run_cell(Spec(), workload, SEED, 1.5, False, impl="xla",
                    pool=pool, allow_cpu=True, control=control,
                    config_override=cfg, mix_override=mix or tiny.MIX)


def cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mistral-7b.decode",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def no_result(p):
    return not any(line.startswith("{") for line in p.stdout.splitlines())


def test_no_tpu_exits_nonzero_with_no_result():
    p = cli(ROOT)
    assert p.returncode != 0 and no_result(p)
    assert "no TPU" in p.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = cli(tmp_path)
    assert p.returncode != 0 and no_result(p)


def test_sound_run_is_correct_and_control_is_not():
    out = run(control=True)
    c = out["compared"]
    assert out["correct"], c
    assert set(out["metrics"]) == {"decode_tok_s", "itl_p95_ms", "setup_s"}
    assert out["metrics"]["decode_tok_s"]["value"] > 0
    ctl = out["control"]
    assert ctl["correct"] is False, ctl
    assert ctl["compared"]["worst_logit_gap"]["value"] > TINY_LIMIT
    assert ctl["compared"]["worst_logit_gap"]["value"] >= \
        3 * c["worst_logit_gap"]["value"]
    assert list(out)[-1] == "compared"


def test_check_covers_requests_admitted_in_the_window(monkeypatch):
    """The sample holds requests that were still decoding when the window
    closed, among them some that were admitted (and prefilled) in it
    (with rows enough for every served request)."""
    from bench import check
    seen = []
    orig = check.sample

    def sample(served, seed, rows, width):
        placed = orig(served, seed, rows, width)
        seen.extend(f for _, _, f in placed)
        return placed

    monkeypatch.setattr(check, "sample", sample)
    out = run(mix=dict(tiny.MIX, check={"rows": 48}))
    assert out["correct"], out["compared"]
    live = [f for f in seen if not f.finished]
    # a client whose new request is still in prefill has nothing to compare
    assert 1 <= len(live) <= tiny.MIX["clients"]
    assert any(f.admitted_in_window for f in live)


def _alter_tokens(monkeypatch):
    from repro.serving.engine import Engine
    orig = Engine._sample

    def sample(self, logits, slots):
        toks = orig(self, logits, slots)
        return toks.at[0].set((toks[0] + 1) % self.cfg.vocab_size)

    monkeypatch.setattr(Engine, "_sample", sample)


def _drop_decode_state(monkeypatch):
    """The decode step hands back the cache it was given: no token's K/V
    lands in the pool after the prompt."""
    import repro.serving.engine as E
    orig = E.forward_step

    def step(params, cfg, token, cache, **kw):
        return orig(params, cfg, token, cache, **kw)[0], cache

    monkeypatch.setattr(E, "forward_step", step)


@pytest.mark.parametrize("fault", [_alter_tokens, _drop_decode_state],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = run()
    assert not out["correct"], out["compared"]
    assert out["compared"]["worst_logit_gap"]["value"] > TINY_LIMIT

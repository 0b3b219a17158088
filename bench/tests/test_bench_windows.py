"""Every layer's window is read in one place (``bench.windows``) from the
published keys, and the Mistral family's counts stop growing past its
window: a token attends at most 4096 positions in each of its layers."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.spec import Spec  # noqa: E402
from bench.windows import layer_windows  # noqa: E402

MELLUM_LIKE = ["sliding_attention"] * 3 + ["full_attention"]


@pytest.mark.parametrize("keys,want", [
    ({}, [0, 0, 0, 0]),
    ({"sliding_window": None}, [0, 0, 0, 0]),
    ({"sliding_window": 1024}, [1024] * 4),
    ({"sliding_window": 1024, "use_sliding_window": True}, [1024] * 4),
    ({"sliding_window": 1024, "use_sliding_window": False}, [0] * 4),
    ({"sliding_window": 1024, "layer_types": MELLUM_LIKE},
     [1024, 1024, 1024, 0]),
    ({"sliding_window": 1024, "layer_types": ["full_attention"] * 4},
     [0] * 4),
    ({"sliding_window": 1024, "layer_types": MELLUM_LIKE,
      "use_sliding_window": False}, [0] * 4),
], ids=["none", "null", "uniform", "switched_on", "switched_off",
        "pattern", "all_full", "pattern_switched_off"])
def test_layer_windows_from_the_published_keys(keys, want):
    assert layer_windows(dict(num_hidden_layers=4, **keys)) == want


@pytest.mark.parametrize("types", [MELLUM_LIKE[:3], MELLUM_LIKE + ["x"],
                                   MELLUM_LIKE[:3] + ["chunked_attention"]],
                         ids=["too_few", "too_many", "unknown_type"])
def test_layer_windows_refuses_a_pattern_it_cannot_read(types):
    with pytest.raises(ValueError):
        layer_windows(dict(num_hidden_layers=4, sliding_window=8,
                           layer_types=types))


def family_and_cfg(name):
    spec = Spec()
    cfg = spec.config(name)
    return spec.family(cfg), cfg


CONFIGS = ("mistral-7b", "mistral-7b-qpfree")


@pytest.mark.parametrize("name", CONFIGS)
def test_mistral_counts_stop_at_the_window(name):
    fam, cfg = family_and_cfg(name)
    at = fam.token_flops(cfg, 4096, logits=False)
    # 16 layers of matmuls, and QK^T and PV over 4096 positions in each
    assert at == 2.0 * 16 * fam.layer_matmul_params(cfg) \
        + 4.0 * 4096 * 16 * 4096
    for context in (4097, 5000, 16384):
        assert fam.token_flops(cfg, context, logits=False) == at
    assert fam.decode_attention_step(cfg, [5000, 9000]) == \
        fam.decode_attention_step(cfg, [4096, 4096]) == \
        (16 * 4.0 * 4096 * 8192, 16 * 8192 * 4096)
    assert fam.prefill_flops(cfg, 4096, 6000, logits=False) == 1904 * at
    assert fam.prefill_flops(cfg, 4000, 4200, logits=False) == sum(
        fam.token_flops(cfg, p + 1, logits=False) for p in range(4000, 4200))


@pytest.mark.parametrize("name", CONFIGS)
def test_the_program_takes_one_window_for_every_layer(name):
    fam, cfg = family_and_cfg(name)
    mixed = dict(cfg, num_hidden_layers=4, layer_types=MELLUM_LIKE)
    with pytest.raises(ValueError):
        fam.program_config(mixed)
    full = dict(mixed, layer_types=["full_attention"] * 4)
    assert fam.program_config(full).sliding_window == 0

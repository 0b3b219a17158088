"""Operation and byte counts of the Mistral block family against the sizes
worked out by hand for Mistral-7B widths (d 4096, 32/8 heads, d_ff 14336,
vocab 32000)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import flops  # noqa: E402
from bench.spec import Spec  # noqa: E402

FAMILY = Spec().family({"model_type": "mistral"})


def cfg(name):
    c = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    c["name"] = name
    return c


def test_layer_params_standard_vs_qpfree():
    std, qpf = cfg("mistral-7b"), cfg("mistral-7b-qpfree")
    assert FAMILY.layer_params(std) == 218_112_000
    assert FAMILY.layer_params(qpf) == 184_557_568
    saving = 1 - FAMILY.layer_params(qpf) / FAMILY.layer_params(std)
    assert round(100 * saving, 1) == 15.4


@pytest.mark.parametrize("name,gb", [("mistral-7b", 7.24),
                                     ("mistral-7b-qpfree", 6.17)])
def test_weight_bytes_per_decode_step(name, gb):
    assert round(FAMILY.weight_bytes_per_step(cfg(name)) / 1e9, 2) == gb


def test_kv_bytes_per_token():
    c = cfg("mistral-7b")
    per_tok = FAMILY.kv_bytes_per_token_layer(c)
    assert per_tok == 4096  # K and V rows of 8 heads x 128 in bf16
    assert per_tok * c["num_hidden_layers"] == 65_536
    pool = json.loads((ROOT / "bench" / "traffic" / "decode.json")
                      .read_text())["engine"]["pool_pages"]
    assert round(pool * 16 * per_tok * 16 / 1e9, 2) == 3.22  # the pool


def test_agrees_with_the_programs_weight_table():
    """Independent arithmetic, same answer as ``repro.core.weight_table``
    (which ``benchmarks/bench_weight_table.py`` holds to the paper)."""
    from repro.configs import get_config
    from repro.core import weight_table
    t = weight_table(get_config("mistral-7b"))
    std, qpf = cfg("mistral-7b"), cfg("mistral-7b-qpfree")
    removed = FAMILY.layer_matmul_params(std) \
        - FAMILY.layer_matmul_params(qpf)
    assert removed == t["qp_per_layer"]


def test_decode_attention_counts_live_tokens_only():
    c = cfg("mistral-7b")
    fl, by = FAMILY.decode_attention_step(c, [100, 28])
    assert fl == 16 * 4.0 * 4096 * 128  # 16 layers of QK^T and PV
    assert by == 16 * 128 * 4096
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(fl, by, peak) == by / 819e9


def test_prefill_flops_sum_positions():
    c = cfg("mistral-7b")
    one = [FAMILY.token_flops(c, p + 1, logits=False) for p in range(10, 14)]
    assert FAMILY.prefill_flops(c, 10, 14, logits=False) == pytest.approx(
        sum(one))

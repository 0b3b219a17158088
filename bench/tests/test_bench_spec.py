"""The harness finds configurations, mixes and per-layer metrics by their
names in ``BENCHMARK.json``, so a later change adds files and entries and
edits none; and the committed ``BENCHMARK.json`` keeps to its contract."""
import json
import re
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.spec import Spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_dummy_entries_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((bench / "configs" / "mistral-7b.json").read_text())
    cfg["num_hidden_layers"] = 3
    (bench / "configs" / "dummy-model.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "decode.json").read_text())
    mix["clients"] = 7
    (bench / "traffic" / "dummy_mix.json").write_text(json.dumps(mix))
    (bench / "metrics" / "dummy_count.decode.py").write_text(
        "def read(ctx):\n    return 41.0 + ctx\n")
    doc["configs"].append({"name": "dummy-model", "source": "x",
                           "file": "bench/configs/dummy-model.json",
                           "reduced": [], "why": "x"})
    doc["workloads"].append({"name": "dummy-model.dummy_mix",
                             "config": "dummy-model",
                             "traffic": "dummy_mix", "chips": 1,
                             "why": "x"})
    doc["per_layer"].append({"name": "dummy_count.decode", "unit": "n",
                             "better": "higher", "source": "program_counter",
                             "layer": "device", "moves": "decode_tok_s",
                             "workloads": ["dummy-model.dummy_mix"]})
    e2e = next(m for m in doc["end_to_end"] if m["name"] == "decode_tok_s")
    e2e["workloads"].append("dummy-model.dummy_mix")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    spec = Spec(tmp_path, bench)
    wl = spec.workload("dummy-model.dummy_mix")
    assert spec.config(wl["config"])["num_hidden_layers"] == 3
    assert spec.traffic(wl["traffic"])["clients"] == 7
    names = [m["name"] for m in spec.per_layer(wl["name"])]
    assert names == ["dummy_count.decode"]
    assert spec.reader("dummy_count.decode")(1.0) == 42.0
    assert "decode_tok_s" in [m["name"] for m in spec.end_to_end(wl["name"])]


def test_committed_benchmark_resolves_and_keeps_its_contract():
    spec = Spec()
    doc = spec.doc
    assert doc["command"][1] == "bench/run.py" and doc["paths"] == ["bench"]
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    cells = doc["workloads"]
    for wl in cells:
        assert NAME.match(wl["name"]) and len(wl["why"]) <= 200
        cfg = spec.config(wl["config"])
        assert spec.reference(cfg).make_forward
        mix = spec.traffic(wl["traffic"])
        assert mix["engine"]["max_len"] <= cfg["sliding_window"]
        reported = [m["name"] for m in spec.end_to_end(wl["name"])]
        assert "setup_s" in reported and len(reported) >= 2
        layer = spec.per_layer(wl["name"])
        assert layer
        for m in layer:
            assert m["moves"] in reported
            assert callable(spec.reader(m["name"]))
    for c in doc["configs"]:
        body = json.loads((ROOT / c["file"]).read_text())
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert body["source"] == c["source"]
        assert any(wl["config"] == c["name"] for wl in cells)
    layers = {}
    for m in doc["per_layer"]:
        assert NAME.match(m["name"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.setdefault(m["layer"], m["layer"])
    assert len(json.dumps(doc)) < 64 * 1024


def test_reference_computes_what_the_program_runs_where_it_departs():
    """A key the program cannot take stays at its published value in the
    file; the reference computes at the value the program runs."""
    from bench.run import reference_config
    spec = Spec()
    for c in spec.doc["configs"]:
        cfg = spec.config(c["name"])
        ref = reference_config(cfg)
        for key, dep in cfg.get("program_departs", {}).items():
            assert key not in c["reduced"]
            assert ref[key] == dep["runs"] != cfg[key]
        assert {k: v for k, v in ref.items()
                if k not in cfg.get("program_departs", {})} == \
            {k: v for k, v in cfg.items()
             if k not in cfg.get("program_departs", {})}

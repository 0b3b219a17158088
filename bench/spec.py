"""``BENCHMARK.json`` and the files it names, found by name.

A configuration is ``configs/<name>.json`` (its ``file`` in
``BENCHMARK.json``), a traffic mix ``traffic/<name>.json``, a per-layer
metric ``metrics/<name>.py`` with a ``read(ctx)`` function, a
configuration's block family ``families/<model_type>.py`` (by the
published ``model_type`` its file states) and its reference
``references/<reference>.py``.  Adding any of them is adding a file and
an entry; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

ROOT = Path(__file__).resolve().parent.parent
BENCH = Path(__file__).resolve().parent


class Spec:
    def __init__(self, root: Path = ROOT, bench: Path = BENCH):
        self.root, self.bench = Path(root), Path(bench)
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> Dict:
        return _named(self.doc["workloads"], name, "workload")

    def config(self, name: str) -> Dict:
        entry = _named(self.doc["configs"], name, "configuration")
        cfg = json.loads((self.root / entry["file"]).read_text())
        cfg["name"] = name
        return cfg

    def traffic(self, name: str) -> Dict:
        return json.loads((self.bench / "traffic" / f"{name}.json")
                          .read_text())

    def reference(self, cfg: Dict):
        """The module of the configuration's plain reference."""
        path = self.bench / "references" / f"{cfg['reference']}.py"
        return _load(path, f"bench_reference_{cfg['reference']}")

    def family(self, cfg: Dict):
        """The module of the configuration's block family: its
        ``program_config``, ``layer_leaves``, ``top_leaves`` and counts
        (``bench.flops.COUNTS``)."""
        path = self.bench / "families" / f"{cfg['model_type']}.py"
        return _load(path, "bench_family_" + _ident(cfg["model_type"]))

    def end_to_end(self, workload: str) -> List[Dict]:
        return [m for m in self.doc["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> List[Dict]:
        """Per-layer metrics of a cell: those that list it, and those
        without a list whose end-to-end metric the cell reports."""
        reported = {m["name"] for m in self.end_to_end(workload)}
        out = []
        for m in self.doc["per_layer"]:
            if "workloads" in m:
                if workload in m["workloads"]:
                    out.append(m)
            elif m["moves"] in reported:
                out.append(m)
        return out

    def reader(self, metric: str) -> Callable:
        path = self.bench / "metrics" / f"{metric}.py"
        return _load(path, "bench_metric_" + _ident(metric)).read


def _named(entries: List[Dict], name: str, what: str) -> Dict:
    hits = [e for e in entries if e["name"] == name]
    if len(hits) != 1:
        raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")
    return hits[0]


def _ident(name: str) -> str:
    return name.replace(".", "_").replace("-", "_")


def _load(path: Path, modname: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

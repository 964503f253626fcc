"""Finds a cell's pieces by name, so that a cell, a configuration, a traffic
mix or a metric is added with files and entries alone.

- ``BENCHMARK.json`` at the checkout root: the cells and metrics;
- ``<configuration's file>``: sizes, store settings and guarantees;
- ``bench/traffic/<mix>.json``: who reads and writes, and how fast;
- ``bench/metrics/<metric>.py``: ``read(run)`` -> a number, or ``None``
  where the run has nothing to read for it;
- ``bench/work/<program>.py``: ``work(...)`` -> (operations, bytes);
- ``bench/peaks.json``: each device kind's peaks.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


class Spec:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / self.bench["paths"][0]

    def cell(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"bench: no workload named {name!r}")

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise SystemExit(f"bench: no configuration named {name!r}")

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def metrics(self, cell: str, traced: bool) -> list:
        """The cell's end-to-end metrics, or with ``traced`` its per-layer
        ones; a metric with a ``workloads`` key only in those cells."""
        kind = "per_layer" if traced else "end_to_end"
        return [m for m in self.bench[kind] if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        return _load(self.dir / "metrics" / f"{metric}.py").read

    def work(self, program: str):
        return _load(self.dir / "work" / f"{program}.py").work

    def peaks(self, device_kind: str) -> dict:
        return peaks(self.dir / "peaks.json", device_kind)


def peaks(path: Path, device_kind: str) -> dict:
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def _load(path: Path):
    name = "bench_" + re.sub(r"\W", "_", f"{path.parent.name}_{path.stem}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

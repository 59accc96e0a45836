"""Find a cell's parts by name: ``BENCHMARK.json`` names them, and each is
a file of its own under the benchmark's home directory (``bench/``):

    configs/<config>.json   traffic/<traffic>.json   metrics/<metric>.py

Adding a configuration, a traffic mix or a metric is adding its file and
its entry in ``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Callable

HOME = pathlib.Path(__file__).resolve().parent
SPEC = HOME.parent / "BENCHMARK.json"


@dataclasses.dataclass
class Layout:
    spec: dict
    home: pathlib.Path

    @classmethod
    def load(cls, spec: pathlib.Path = SPEC, home: pathlib.Path = HOME) -> "Layout":
        return cls(json.loads(pathlib.Path(spec).read_text()), pathlib.Path(home))

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def metrics(self, workload: str, trace: bool) -> list[dict]:
        """The cell's metrics: per-layer ones for a traced run, end-to-end
        ones otherwise; a metric with a ``workloads`` list belongs to those
        cells only."""
        entries = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in entries if workload in m.get("workloads", [workload])]

    def reader(self, metric: str) -> Callable:
        """``read(run) -> float | None`` of ``metrics/<metric>.py``."""
        path = self.home / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
        if spec is None or spec.loader is None:
            raise FileNotFoundError(path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def _json(self, kind: str, name: str) -> dict:
        return json.loads((self.home / kind / f"{name}.json").read_text())

"""Find every part of a cell by its name.

``BENCHMARK.json`` names the cells, configurations, mixes and metrics;
each lives in a file of its own under ``bench/``:

* configuration ``<c>``: ``bench/configs/<c>.json``, whose ``driver`` key
  names ``bench/drivers/<driver>.py`` and whose ``reference`` key names
  the plain reference ``bench/reference/<reference>.py``;
* traffic mix ``<t>``: ``bench/traffic/<t>.json``;
* metric ``<m>``: its reader ``bench/metrics/<m>.py``, a module with
  ``read(ctx) -> float | None``;
* peaks: ``bench/peaks.json``.

A later change adds a cell by adding files and entries; no file here
needs an edit for it.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class Registry:
    def __init__(self, root: pathlib.Path = ROOT,
                 bench: pathlib.Path = BENCH):
        self.root = pathlib.Path(root)
        self.bench = pathlib.Path(bench)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self._modules: dict = {}

    # -- names --------------------------------------------------------
    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def metrics_of(self, workload: str, trace: bool) -> list:
        """The metric entries a run of ``workload`` reports: its
        end-to-end metrics, or with ``trace`` its per-layer ones."""
        key = "per_layer" if trace else "end_to_end"
        return [m for m in self.spec[key]
                if workload in m.get("workloads", [workload])]

    # -- files ----------------------------------------------------------
    def config_path(self, name: str) -> pathlib.Path:
        return self.bench / "configs" / f"{name}.json"

    def traffic_path(self, name: str) -> pathlib.Path:
        return self.bench / "traffic" / f"{name}.json"

    def metric_path(self, name: str) -> pathlib.Path:
        return self.bench / "metrics" / f"{name}.py"

    def config(self, name: str) -> dict:
        return json.loads(self.config_path(name).read_text())

    def peaks(self) -> dict:
        return json.loads((self.bench / "peaks.json").read_text())

    def _load(self, kind: str, path: pathlib.Path):
        key = (kind, str(path))
        if key not in self._modules:
            if not path.is_file():
                raise FileNotFoundError(f"no {kind} file {path}")
            mod_name = "bench_" + kind + "_" + "".join(
                c if c.isalnum() else "_" for c in path.stem)
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[mod_name] = mod
            spec.loader.exec_module(mod)
            self._modules[key] = mod
        return self._modules[key]

    def metric_reader(self, name: str):
        return self._load("metric", self.metric_path(name))

    def driver(self, name: str):
        return self._load("driver", self.bench / "drivers" / f"{name}.py")

    def reference(self, name: str):
        return self._load("reference",
                          self.bench / "reference" / f"{name}.py")

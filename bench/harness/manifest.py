"""Find every part of the benchmark by the name ``BENCHMARK.json`` gives it.

A configuration is ``bench/configs/<config>.json``, a traffic mix is
``bench/traffic/<traffic>.json``, a per-layer metric is
``bench/metrics/<metric>.py`` and a kernel's operation and byte count is
one ``bench/kernels/<kernel>.py`` each.  Adding any of them is adding a
file and an entry; no file here names a cell, a model or a metric.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Optional

#: the checkout root: bench/harness/manifest.py -> parents[2]
ROOT = pathlib.Path(__file__).resolve().parents[2]


class Manifest:
    def __init__(self, root: Optional[pathlib.Path] = None):
        self.root = pathlib.Path(root or ROOT)
        self.data = json.loads((self.root / "BENCHMARK.json").read_text())
        self.bench = self.root / "bench"

    # -- entries -----------------------------------------------------------

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config_entry(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def _applies(self, metric: dict, cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def end_to_end(self, cell: str) -> list:
        return [m for m in self.data["end_to_end"] if self._applies(m, cell)]

    def per_layer(self, cell: str) -> list:
        return [m for m in self.data["per_layer"] if self._applies(m, cell)]

    # -- files found by name -----------------------------------------------

    def config(self, name: str) -> dict:
        return json.loads((self.root / self.config_entry(name)["file"])
                          .read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.bench / "traffic" / f"{name}.json")
                          .read_text())

    def metric_reader(self, name: str):
        """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
        return load_module(self.bench / "metrics" / f"{name}.py").read

    def kernels(self) -> list:
        """Every kernel count module under ``bench/kernels/``."""
        return [load_module(p)
                for p in sorted((self.bench / "kernels").glob("*.py"))]

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.bench / "peaks.json").read_text())
        if device_kind not in table["devices"]:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           f"bench/peaks.json")
        return table["devices"][device_kind]


def load_module(path: pathlib.Path):
    """Import one file by path (metric and kernel files are named by
    metric and kernel names, which may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

"""Find every part of the benchmark by the name ``BENCHMARK.json`` gives it.

A configuration is ``bench/configs/<config>.json``, a traffic mix is
``bench/traffic/<traffic>.json``, a per-layer metric is
``bench/metrics/<metric>.py`` and a kernel's operation and byte count is
one ``bench/kernels/<kernel>.py`` each.  A configuration file names its
architecture with the key ``"arch"``, and ``bench/arch/<arch>.py`` holds
every fact of it the benchmark needs.  Adding any of them is adding a
file and an entry; no file here names a cell, a model, an architecture
or a metric.

An architecture module gives (``bench/arch/gqa.py`` says each in full):
``dims(cfg)``, a frozen, hashable dataclass of the sizes with at least
``vocab``; ``describe(d)``, the sizes for the set-up line;
``make_params(d, seed)``, every weight from the seed in one jitted call,
``lm_head`` at the top of the tree; ``program_config(cfg)``, the
program's ``ModelConfig``; ``final_hidden(params, d, tokens, read_pos,
lowp)``, the plain reference's normed final hidden state (``lowp``: the
fp8 control's); ``step_flops(d, span)``, model FLOPs of one recorded
step; ``layer_calls(d, path)``, the layer calls of one step through the
kernel of dispatch path ``path``.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from typing import Optional

#: the checkout root: bench/harness/manifest.py -> parents[2]
ROOT = pathlib.Path(__file__).resolve().parents[2]


class Manifest:
    def __init__(self, root: Optional[pathlib.Path] = None):
        self.root = pathlib.Path(root or ROOT)
        self.data = json.loads((self.root / "BENCHMARK.json").read_text())
        self.bench = self.root / "bench"
        self._arch: dict = {}

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

    def arch(self, name: str):
        """The architecture module ``bench/arch/<name>.py``, loaded once
        per manifest (its reference caches compiled layers)."""
        if name not in self._arch:
            self._arch[name] = load_module(self.bench / "arch" / f"{name}.py")
        return self._arch[name]

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
    metric and kernel names, which may hold dots).  It is put in
    ``sys.modules`` before it runs, as a dataclass defined in it needs."""
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod

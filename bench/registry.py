"""Everything the harness loads, found by the name ``BENCHMARK.json`` gives.

A cell names a configuration and a traffic mix; each is a file of its own:

* ``bench/configs/<config>.json``: the configuration as it is run, with its
  module ``<config>.py`` (the program's objects) and plain reference
  ``<config>_ref.py`` beside it;
* ``bench/traffic/<traffic>.json``: the Monte-Carlo call mix;
* ``bench/limits/<cell>.json``: the correctness limits of one cell;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric;
* ``bench/kernels/<kernel>.py``: the operation and byte count of one Pallas
  kernel, by the name it has in the trace.

A later cell, configuration, traffic mix or metric is a new file and a new
entry; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class Registry:
    """Files of one benchmark tree (``root`` holds ``BENCHMARK.json``)."""

    def __init__(self, root: Path = ROOT, bench_dir: Path | None = None):
        self.root = Path(root)
        self.dir = Path(bench_dir) if bench_dir else self.root / "bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.spec["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def limits(self, cell: str) -> dict:
        return self._json("limits", cell)

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.dir / "peaks.json").read_text())
        if device_kind not in table:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           "bench/peaks.json")
        return table[device_kind]

    def module(self, kind: str, name: str):
        """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
        path = self.dir / kind / f"{name}.py"
        if not path.is_file():
            raise FileNotFoundError(f"no {kind} module {path}")
        key = f"bench_{kind}_{name}".replace(".", "_")
        if key in sys.modules:
            return sys.modules[key]
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
        return mod

    def per_layer(self, cell: dict) -> list:
        """Per-layer metric entries reported in ``cell``."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        out = []
        for m in self.spec["per_layer"]:
            cells = m.get("workloads")
            if (cell["name"] in cells) if cells is not None else (
                    m["moves"] in e2e):
                out.append(m)
        return out

    def end_to_end(self, cell: dict) -> list:
        return [m for m in self.spec["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]

    def _json(self, kind: str, name: str) -> dict:
        path = self.dir / kind / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"no {kind} file {path}")
        return json.loads(path.read_text())

"""Find everything of a cell by the names in ``BENCHMARK.json``.

A configuration is ``configs/<config>.json`` (the entry's ``file``), its
model family ``models/<family>.py`` (named by the configuration), a
traffic mix ``traffic/<traffic>.json``, a driver ``drivers/<driver>.py``
(named by the mix), the limits of a cell's correctness check
``limits/<workload>.json`` and a per-layer metric ``metrics/<metric>.py``.
Adding a configuration, a family, a cell, a mix or a metric adds files; no
file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class SpecError(RuntimeError):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def _read_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_module(path: Path, name: str):
    """Import the Python file at ``path`` under the module name ``name``."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def family(cfg: dict, *, root: Path = ROOT):
    """The model family module ``bench/models/<family>.py`` that the
    configuration names by its ``"family"`` key."""
    if "family" not in cfg:
        raise SpecError('the configuration names no "family"')
    name = cfg["family"]
    return load_module(Path(root) / "bench" / "models" / f"{name}.py",
                       f"bench_family_{name}")


class Cell:
    """One workload of BENCHMARK.json with its files loaded."""

    def __init__(self, name: str, *, root: Path = ROOT):
        self.root = Path(root)
        self.dir = self.root / "bench"
        self.bench = _read_json(self.root / "BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = _read_json(self.root / self.config_entry["file"])
        self.traffic = _read_json(
            self.dir / "traffic" / f"{self.entry['traffic']}.json")
        self.limits = _read_json(self.dir / "limits" / f"{name}.json")
        self.end_to_end = [m for m in self.bench["end_to_end"]
                           if _covers(m, name)]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in self.bench["per_layer"]
                          if ("workloads" in m and name in m["workloads"])
                          or ("workloads" not in m and m["moves"] in moved)]

    def driver(self):
        kind = self.traffic["driver"]
        return load_module(self.dir / "drivers" / f"{kind}.py",
                           f"bench_driver_{kind}")

    def family(self):
        return family(self.config, root=self.root)

    def metric_readers(self) -> dict:
        """name -> module with ``read(ctx) -> float | None``."""
        out = {}
        for m in self.per_layer:
            mod_name = "bench_metric_" + m["name"].replace(".", "_") \
                .replace("-", "_")
            out[m["name"]] = load_module(
                self.dir / "metrics" / f"{m['name']}.py", mod_name)
        return out


def _covers(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]

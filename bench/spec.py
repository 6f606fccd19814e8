"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

A cell names a configuration and a traffic mix; the configuration's
file is given in ``BENCHMARK.json``, the mix is ``bench/mixes/<traffic>.json``,
the entry the configuration names for that mix is
``bench/entries/<entry>.py``, and each per-layer metric is
``bench/metrics/<metric>.py``.  Adding a cell, a mix, an entry or a metric
means adding files and entries, never editing one that is there.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def entry(self) -> str:
        """The entry this cell's configuration runs for its mix's kind."""
        return self.config["entry"][self.mix["entry"]]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(metric: dict, cell_name: str) -> bool:
    return cell_name in metric.get("workloads", [cell_name])


def resolve(bench: dict, name: str, root: str = ROOT) -> Cell:
    """The cell called ``name``, with its configuration and mix loaded and
    the metrics it reports selected.  Raises ``KeyError`` for an unknown
    cell and ``FileNotFoundError`` for a missing file."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(mix_path(w["traffic"], root)) as f:
        mix = json.load(f)
    cell = Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])
    if not os.path.isfile(entry_path(cell.entry, root)):
        raise FileNotFoundError(entry_path(cell.entry, root))
    for m in cell.per_layer:
        if not os.path.isfile(metric_path(m["name"], root)):
            raise FileNotFoundError(metric_path(m["name"], root))
    return cell


def mix_path(traffic: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "mixes", f"{traffic}.json")


def entry_path(entry: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "entries", f"{entry}.py")


def metric_path(metric: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "metrics", f"{metric}.py")


def load_file(path: str, modname: Optional[str] = None):
    """Import a Python file by path (metric and entry names hold dots,
    so they are not importable by name)."""
    modname = modname or "bench_file_" + "".join(
        ch if ch.isalnum() else "_" for ch in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

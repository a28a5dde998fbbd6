"""Finding a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or metric is
a file of its own, found here by name:

* ``BENCHMARK.json``'s ``configs[].file``   — the configuration's sizes;
* ``bench/traffic/<traffic>.json``           — the traffic mix's parameters,
  whose ``kind`` names the generator ``bench/traffic/<kind>.py``;
* ``bench/limits/<cell>.json``               — the limits of the cell's
  correctness comparison;
* ``bench/metrics/<metric>.py``              — one reader per metric;
* ``bench/models/<model_type>.py``           — the architecture module of a
  configuration, by the file's ``model_type`` (``bench/lib/arch.py``).

The harness names no cell, configuration or metric itself.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re

BENCH_DIR = "bench"
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@dataclasses.dataclass
class Cell:
    root: pathlib.Path
    name: str
    chips: int
    config: dict  # the configuration file's contents
    traffic_name: str
    traffic: dict  # the traffic mix's parameters
    limits: dict  # the correctness limits of this cell
    end_to_end: list[dict]  # metric entries reported with --trace 0
    per_layer: list[dict]  # metric entries reported with --trace 1


def _check_name(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: pathlib.Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with every file it names."""
    root = pathlib.Path(root)
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = read_json(root / configs[w["config"]]["file"])
    traffic = read_json(root / BENCH_DIR / "traffic" / f"{_check_name(w['traffic'])}.json")
    limits = read_json(root / BENCH_DIR / "limits" / f"{_check_name(name)}.json")
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in reported)
    ]
    return Cell(root, name, int(w["chips"]), config, w["traffic"], traffic, limits,
                e2e, per_layer)


def load_module(path: pathlib.Path, label: str):
    """Import one Python file by path (metric and generator files carry dots
    and dashes in their names, so they are not importable by name)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", label), path
    )
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic_module(cell: Cell):
    kind = _check_name(cell.traffic["kind"])
    return load_module(cell.root / BENCH_DIR / "traffic" / f"{kind}.py", f"traffic_{kind}")


def metric_reader(root: pathlib.Path, metric: str):
    return load_module(
        pathlib.Path(root) / BENCH_DIR / "metrics" / f"{_check_name(metric)}.py",
        f"metric_{metric}",
    ).read

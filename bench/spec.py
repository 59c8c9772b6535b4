"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own under this directory, named as
``BENCHMARK.json`` names it: ``configs/<config>.json``,
``traffic/<traffic>.json`` and ``metrics/<metric>.py``. Adding a cell or a
metric therefore adds files and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Callable

__all__ = ["Cell", "load_cell", "per_layer_readers", "ROOT"]

#: the checkout root: this file is <root>/bench/spec.py
ROOT = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    """One ``workloads`` entry with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _benchmark(root: pathlib.Path) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its config and
    traffic files read and the metrics it reports selected."""
    spec = _benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
    )


def per_layer_readers(cell: Cell, root: pathlib.Path = ROOT) -> dict[str, Callable]:
    """``{metric name: read}`` for the cell's per-layer metrics.

    Each reader is ``bench/metrics/<name>.py`` and defines
    ``read(run) -> float | None``; ``None`` means it found nothing to read,
    and the metric is then left out of the result line.
    """
    readers = {}
    for m in cell.per_layer:
        path = root / "bench" / "metrics" / f"{m['name']}.py"
        mod_name = "bench_metric_" + m["name"].replace(".", "_").replace("-", "_")
        loader = importlib.util.spec_from_file_location(mod_name, path)
        if loader is None:
            raise FileNotFoundError(path)
        mod = importlib.util.module_from_spec(loader)
        loader.loader.exec_module(mod)
        readers[m["name"]] = mod.read
    return readers

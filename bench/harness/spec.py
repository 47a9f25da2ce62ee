"""Find a cell's files by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix, and each
per-layer metric. Everything that belongs to one of them is a file of its
own under ``bench/``:

- ``configs/<config>.json``: the deployment (sizes, source, cuts);
- ``traffic/<traffic>.json``: the mix, read by ``generators/<kind>.py``;
- ``metrics/<metric>.py`` (or ``metrics/<base>.py`` for a metric named
  ``<base>.<suffix>``): a reader with ``read(ctx) -> float | None``;
- ``limits/<cell>.json``: the limits of the numbers that decide ``correct``.

Adding a cell, mix, configuration or metric adds files and entries; no
existing file changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Optional

BENCH_DIR = Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list     # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def _load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_dir: Path = BENCH_DIR,
              benchmark: Optional[Path] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files read."""
    bench_dir = Path(bench_dir)
    benchmark = Path(benchmark or bench_dir.parent / "BENCHMARK.json")
    spec = _load_json(benchmark)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {benchmark}; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    config = _load_json(bench_dir / "configs" / f"{w['config']}.json")
    traffic = _load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(bench_dir / "limits" / f"{name}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        limits=limits,
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)])


def _load_module(path: Path, modname: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(metric_name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The reader module of a per-layer metric: ``metrics/<name>.py``, else
    ``metrics/<base>.py`` where ``<base>`` is the name before its first
    dot (``idle_share.rate`` and ``idle_share.bulk`` share one reader)."""
    mdir = Path(bench_dir) / "metrics"
    for stem in (metric_name, metric_name.split(".", 1)[0]):
        path = mdir / f"{stem}.py"
        if path.exists():
            return _load_module(path, "bench_metric_" + stem.replace(".", "_"))
    raise FileNotFoundError(f"no reader for metric {metric_name!r} in {mdir}")


def traffic_generator(kind: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The generator of a traffic kind: ``generators/<kind>.py``."""
    path = Path(bench_dir) / "generators" / f"{kind}.py"
    if not path.exists():
        raise FileNotFoundError(f"no generator for traffic kind {kind!r}: {path}")
    return _load_module(path, "bench_generator_" + kind)


def load_peaks(bench_dir: Path = BENCH_DIR) -> dict:
    return _load_json(Path(bench_dir) / "peaks.json")["devices"]

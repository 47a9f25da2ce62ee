"""Cells that wait for their knee sweep on the chip before they join
``BENCHMARK.json`` (``data/waiting-cells.json``): the open-loop combined
cell and the 1% predicate cell on ``cohere768-flat``. The tests run them on
the CPU, and the knee sweep on the chip, from a copy of the benchmark with
these cells added.

    python bench/tests/waiting.py <root>   # add them to the copy at <root>
"""
import json
import shutil
import sys
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"
FLAT = "cohere768-flat.combined-rate"
PRED = "cohere768-flat.filter1pct-rate"


def add_waiting_cells(root: Path):
    """Add the waiting cells, their files and their metrics to the
    benchmark at ``root`` (a copy: ``root/BENCHMARK.json``, ``root/bench``)."""
    root = Path(root)
    extra = json.loads((DATA / "waiting-cells.json").read_text())
    for dst, src in extra["files"].items():
        shutil.copy(DATA / src, root / dst)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        spec[key] += extra[key]
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))


if __name__ == "__main__":
    add_waiting_cells(Path(sys.argv[1]))

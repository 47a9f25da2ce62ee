"""Cells, configurations, traffic mixes and metrics are found by name; a new
one is added by adding files, and no existing file changes."""
import hashlib
import json
import shutil
from pathlib import Path

import pytest

from harness import spec

BENCH = Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def _digest(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


WAITING = [w["name"] for w in json.loads(
    (BENCH / "tests" / "data" / "waiting-cells.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]]
                         + WAITING)
def test_every_cell_resolves(cell, tmp_path):
    """Each cell of ``BENCHMARK.json``, and each waiting cell once it is
    added to a copy of the benchmark, finds its files and readers."""
    bench, benchmark = BENCH, None
    if cell in WAITING:
        from waiting import add_waiting_cells

        shutil.copytree(BENCH, tmp_path / "bench",
                        ignore=shutil.ignore_patterns("__pycache__", "_out"))
        shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
        add_waiting_cells(tmp_path)
        bench, benchmark = tmp_path / "bench", tmp_path / "BENCHMARK.json"
    c = spec.load_cell(cell, bench_dir=bench, benchmark=benchmark)
    assert c.config["name"] == cell.split(".")[0]
    assert c.traffic["kind"] in ("open_loop", "bulk")
    spec.traffic_generator(c.traffic["kind"], bench)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "hbm_peak_gb"}
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert callable(spec.metric_reader(m["name"], bench).read)
    assert c.limits and all("limit" in v for v in c.limits.values())


def test_added_cell_is_found_and_nothing_existing_changes(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "_out"))
    bench = root / "bench"
    before = _digest(bench)
    spec_new = json.loads(json.dumps(SPEC))
    cfg = json.loads((bench / "configs" / "cohere768-flat.json").read_text())
    cfg["name"] = "cohere768-flat-int8"
    cfg["fcvi"]["storage_dtype"] = "int8"
    (bench / "configs" / "cohere768-flat-int8.json").write_text(
        json.dumps(cfg))
    (bench / "traffic" / "hot-64qps.json").write_text(json.dumps(
        {"kind": "open_loop", "mode": "similarity", "rate_qps": 64,
         "max_per_call": 64, "query_noise": 0.5, "drain_s": 60,
         "sample": 8}))
    (bench / "metrics" / "cache_hit_share.py").write_text(
        "def read(ctx):\n    c = ctx.counters\n"
        "    return 100.0 * c['cache_hits'] / c['queries']\n")
    (bench / "limits" / "cohere768-flat-int8.hot.json").write_text(
        json.dumps({"bad_answers": {"limit": 0}}))
    spec_new["configs"].append({"name": "cohere768-flat-int8"})
    spec_new["workloads"].append(
        {"name": "cohere768-flat-int8.hot", "config": "cohere768-flat-int8",
         "traffic": "hot-64qps", "chips": 1, "why": "test"})
    spec_new["per_layer"].append(
        {"name": "cache_hit_share", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "cache", "moves": "p50_ms",
         "workloads": ["cohere768-flat-int8.hot"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec_new))

    c = spec.load_cell("cohere768-flat-int8.hot", bench_dir=bench)
    assert c.config["fcvi"]["storage_dtype"] == "int8"
    assert c.traffic["rate_qps"] == 64
    assert [m["name"] for m in c.per_layer][-1] == "cache_hit_share"
    reader = spec.metric_reader("cache_hit_share", bench)
    ctx = type("Ctx", (), {"counters": {"cache_hits": 1, "queries": 4}})
    assert reader.read(ctx) == 25.0
    # the existing cells still resolve, and no existing file changed
    for w in SPEC["workloads"]:
        spec.load_cell(w["name"], bench_dir=bench)
    after = _digest(bench)
    assert {p: h for p, h in after.items() if p in before} == before


def test_suffixed_metric_uses_its_base_reader():
    a = spec.metric_reader("idle_share.rate")
    b = spec.metric_reader("idle_share.bulk")
    assert a.__file__ == b.__file__


def test_unknown_names_fail():
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric")
    with pytest.raises(FileNotFoundError):
        spec.traffic_generator("no_such_kind")

"""``bench/run.py`` end to end on the CPU at a small size, steered through
``multi_run.py`` (the look for a chip skipped, fewer rows, a lower rate): it
refuses to run off a TPU, on an unknown chip or without the program; a clean
run passes the checks that the lower-precision control and each planted
fault fail.

The combined open-loop cell and the predicate cell run as cells added to a
copy of the benchmark (``waiting.py``): they wait for their knee sweeps on
the chip before they join ``BENCHMARK.json``."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest
from waiting import FLAT, PRED, add_waiting_cells

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
RUN = BENCH / "run.py"
IVF = "sift1m-ivf.bulk"
# 16,384 rows: at 2,048-4,096 rows the flat engine's candidate search misses
# 12-16% of the exact combined-score top 10 (32 clusters of 64-128 rows);
# from 16,384 rows on it misses under 1%, as the full-size corpus does.
SMALL = {FLAT: {"rows": 16384, "rate": 40, "allow_cpu": True},
         PRED: {"rows": 16384, "rate": 40, "allow_cpu": True}}
MULTI = Path("bench") / "tests" / "multi_run.py"


def _env(cache=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    if cache is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    return env


def _with_waiting_cells(root: Path) -> Path:
    """A copy of the benchmark under ``root`` with the waiting cells added;
    the program is linked in. Returns the copy's root."""
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "_out"))
    (root / "src").symlink_to(ROOT / "src")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    add_waiting_cells(root)
    return root


def _run(args, cache=None, cwd=ROOT, run=RUN, timeout=600):
    p = subprocess.run([sys.executable, str(run), *args], cwd=cwd,
                       env=_env(cache), capture_output=True, text=True,
                       timeout=timeout)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if p.returncode == 0 and lines else None)


BASE = ["--seed", "3000000007", "--seconds", "1.5", "--trace", "0"]


def test_refuses_to_run_off_a_tpu():
    p, out = _run(["--workload", IVF, *BASE])
    assert p.returncode == 2 and out is None and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_an_unknown_chip(monkeypatch):
    import jax

    sys.path.insert(0, str(BENCH))
    import run
    from harness import spec

    class Dev:
        platform, device_kind = "tpu", "TPU v99 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(SystemExit) as e:
        run.check_devices(spec.load_cell(IVF))
    assert e.value.code == 2


def _multi(items, root, cache, timeout):
    p = subprocess.run([sys.executable, str(root / MULTI), json.dumps(items)],
                       cwd=root, env=_env(cache), capture_output=True,
                       text=True, timeout=timeout)
    assert p.returncode == 0, p.stderr[-3000:]
    return [json.loads(line) for line in p.stdout.strip().splitlines()], p


def test_refuses_to_run_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "_out"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    got, _ = _multi([{"argv": ["--workload", IVF, *BASE],
                      "variant": {"rows": 4096, "allow_cpu": True}}],
                    tmp_path, None, 300)
    assert got[0]["rc"] != 0 and got[0]["result"] is None


VARIANTS = {"clean": {}, "control": {"serve": "control"},
            "drop_half": {"fault": "drop_half"}, "alter": {"fault": "alter"}}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Every cell x variant, in one process (compiled programs shared)."""
    items = [{"argv": ["--workload", cell, *BASE],
              "variant": dict(SMALL[cell], **how), "how": name}
             for cell in (FLAT, PRED) for name, how in VARIANTS.items()]
    root = _with_waiting_cells(tmp_path_factory.mktemp("repo"))
    lines, p = _multi([{k: it[k] for k in ("argv", "variant")}
                       for it in items], root,
                      tmp_path_factory.mktemp("jax_cache"), 900)
    got = {}
    for it, r in zip(items, lines):
        assert r["rc"] == 0, (r["argv"], p.stderr[-3000:])
        got[it["argv"][1], it["how"]] = r["result"]
    return got


def test_clean_runs(runs):
    flat, pred = runs[FLAT, "clean"], runs[PRED, "clean"]
    for out in (flat, pred):
        assert out["correct"] and out["failed"] == 0, out["checks"]
    assert set(flat["metrics"]) == {"p50_ms", "p99_ms", "hbm_peak_gb",
                                    "setup_s"}
    assert list(flat)[-1] == "checks"


@pytest.mark.parametrize("cell", [FLAT, PRED])
@pytest.mark.parametrize("how", ["control", "drop_half", "alter"])
def test_breaking_the_timed_path_makes_it_incorrect(cell, how, runs):
    out = runs[cell, how]
    assert out["correct"] is False
    failed = [n for n, c in out["checks"].items() if c["value"] > c["limit"]]
    passed_clean = [n for n, c in runs[cell, "clean"]["checks"].items()
                    if c["value"] <= c["limit"]]
    assert set(failed) & set(passed_clean), (failed, passed_clean)

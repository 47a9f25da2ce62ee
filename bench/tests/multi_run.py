"""Runs of ``bench/run.py``, steered as the benchmark's tests and tools need
and never as a measured run is: each run may give a variant

- ``rows``: corpus rows instead of the configuration's;
- ``rate``: the open-loop rate instead of the traffic file's (a knee sweep);
- ``allow_cpu``: skip the look for a chip;
- ``serve``: ``"control"``, the bf16 reference in the program's place;
- ``fault``: a fault planted under the timed path (``harness/faults.py``);
- ``keep_trace``: a directory to copy the profiler's file into.

The variant replaces functions of ``run`` for the one run; the command's
own options stay ``--workload --seed --seconds --trace``.

    python bench/tests/multi_run.py '[{"argv": [...], "variant": {...}}, ...]'

The runs share one process, so the compiled programs are shared and the runs
after the first are quick. Prints one line per run:
{"argv": [...], "variant": {...}, "rc": .., "result": {...} | null}.
"""
import contextlib
import dataclasses
import io
import json
import shutil
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402
from harness import spec, tracing  # noqa: E402

ORIGINAL = {name: getattr(run, name)
            for name in ("check_devices", "load_cell", "make_server")}
ORIGINAL_LOAD = tracing.load


def apply(variant: dict):
    """Replace the functions of ``run`` (and the trace reader) that the
    variant steers; ``restore()`` undoes it."""
    restore()
    rows, rate = variant.get("rows"), variant.get("rate")
    serve, fault = variant.get("serve"), variant.get("fault")
    keep = variant.get("keep_trace")
    if rows is not None or rate is not None:
        def load_cell(name):
            cell = spec.load_cell(name)
            cfg, traffic = dict(cell.config), dict(cell.traffic)
            if rows is not None:
                cfg["n"] = int(rows)
            if rate is not None:
                traffic["rate_qps"] = float(rate)
            return dataclasses.replace(cell, config=cfg, traffic=traffic)
        run.load_cell = load_cell
    if variant.get("allow_cpu"):
        def check_devices(cell):
            import jax

            devs = jax.devices()[:cell.chips]
            return devs, spec.load_peaks().get(devs[0].device_kind)
        run.check_devices = check_devices
    if serve == "control" or fault:
        def make_server(cfg, traffic, vectors, filters_host, ranges):
            from harness.faults import Faulty
            from reference.control import Control

            if serve == "control":
                server = Control(cfg, traffic, vectors, filters_host, ranges)
            else:
                server = ORIGINAL["make_server"](cfg, traffic, vectors,
                                                 filters_host, ranges)
            if fault:
                server = Faulty(server, fault, int(cfg["n"]))
            return server
        run.make_server = make_server
    if keep:
        def load(path):
            found = sorted(Path(path).rglob("*.xplane.pb"))
            Path(keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(found[-1], keep)
            return ORIGINAL_LOAD(path)
        tracing.load = load


def restore():
    for name, fn in ORIGINAL.items():
        setattr(run, name, fn)
    tracing.load = ORIGINAL_LOAD


def run_one(argv, variant=None):
    """(rc, result line as a dict or None) of one run."""
    buf = io.StringIO()
    rc = 0
    apply(variant or {})
    try:
        with contextlib.redirect_stdout(buf):
            rc = run.main(argv)
    except SystemExit as e:
        rc = e.code
    finally:
        restore()
    lines = buf.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if rc == 0 and lines else None)


def main():
    for item in json.loads(sys.argv[1]):
        rc, result = run_one(item["argv"], item.get("variant"))
        print(json.dumps({"argv": item["argv"],
                          "variant": item.get("variant", {}), "rc": rc,
                          "result": result}), flush=True)


if __name__ == "__main__":
    main()

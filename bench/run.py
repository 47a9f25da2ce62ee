#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix; both
are files under ``bench/``. The run generates the corpus and the queries on
the device from ``--seed``, builds the index and ``FCVIEngine`` over it,
warms every shape the window will use, and then drives
``FCVIEngine.search`` with the traffic for ``--seconds``. Afterwards it
compares a seeded sample of the answers served in the window with the plain
fp64 reference (``bench/reference``) and prints one JSON line last:

- ``--trace 0``: the cell's end-to-end metrics (latency or throughput, peak
  HBM, set-up time);
- ``--trace 1``: the cell's per-layer metrics, read from a profiler trace of
  the window by the readers in ``bench/metrics``.

Off a TPU, with fewer chips than the cell asks for, or on a chip missing
from ``bench/peaks.json``, it exits with code 2 and prints no result. A
traced window in which no operation ran on the chip, or no ``search`` call
ran, exits with code 3.

The benchmark's own tests and tools steer a run (a smaller corpus, a
planted fault, the control in the program's place) by replacing
``check_devices``, ``load_cell`` or ``make_server`` of this module, never
through an option of the command.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_devices(cell):
    """(devices used, peak entry); exits 2 where the cell cannot run."""
    import jax

    from harness import spec

    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        log(f"bench: needs a TPU; JAX found {devs[0].platform!r}")
        sys.exit(2)
    if len(devs) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips; JAX sees "
            f"{len(devs)}")
        sys.exit(2)
    peak = spec.load_peaks().get(kind)
    if peak is None:
        log(f"bench: device kind {kind!r} is not in bench/peaks.json")
        sys.exit(2)
    return devs[:cell.chips], peak


def load_cell(name):
    from harness import spec

    return spec.load_cell(name)


def make_server(cfg, traffic, vectors, filters_host, ranges):
    """The system under test: ``FCVIEngine`` over the corpus."""
    from harness.system import Server

    return Server(cfg, traffic, vectors, filters_host)


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def ranges_of(cfg, traffic):
    from harness import data

    p = traffic["predicate"]
    col = data.attr_names(cfg).index(p["attr"])
    return [(col, float(p["lo"]), float(p["hi"]))]


def main(argv=None):
    args = parse_args(argv)
    from harness import spec

    cell = load_cell(args.workload)
    cfg, traffic = cell.config, cell.traffic
    mode = traffic["mode"]
    devs, peak = check_devices(cell)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"bench: the program (src/repro) is not in {ROOT}")
        sys.exit(2)
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    from jax import monitoring

    from harness import data, tracing, work
    from reference import check
    from repro.launch.cache import enable_compile_cache

    log(f"[setup] compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = [0]
    monitoring.register_event_duration_secs_listener(
        lambda ev, _d, **_kw: compiles.__setitem__(
            0, compiles[0] + (ev == COMPILE_EVENT)))

    gen = spec.traffic_generator(traffic["kind"])
    seed, seconds = args.seed, args.seconds
    t0 = time.perf_counter()
    vectors, filters = data.corpus(cfg, seed)
    n = int(vectors.shape[0])
    filters_host = np.asarray(filters)

    def make_queries(stream, count):
        return data.queries(cfg, vectors, filters, seed, stream, count,
                            traffic["query_noise"])

    plan = gen.plan(traffic, seed, seconds, make_queries)
    bs = int(cfg["batch_size"])
    wq, wfq = make_queries(2, 2 * bs + 32)
    del filters
    log(f"[setup] corpus n={n} d={cfg['d']} and traffic generated in "
        f"{time.perf_counter() - t0:.1f} s")
    ranges = ranges_of(cfg, traffic) if mode == "predicate" else None
    server = make_server(cfg, traffic, vectors, filters_host, ranges)
    del vectors
    gc.collect()
    t0 = time.perf_counter()
    server.warm(wq, wfq, int(traffic.get("max_per_call", bs)))
    log(f"[setup] warm-up {time.perf_counter() - t0:.1f} s")
    setup_s = time.perf_counter() - T_START

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    span = tracing.span_factory(bool(args.trace))
    c0, k0 = server.counters(), compiles[0]
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    with span("window"):
        res = gen.run(server, plan, seconds, traffic, span)
    if trace_dir:
        jax.profiler.stop_trace()
    c1, k1 = server.counters(), compiles[0]
    in_window = k1 - k0
    mem = memory_peak(devs)
    counters = {key: c1[key] - c0.get(key, 0) for key in c1}
    log(f"[window] {gen.describe(res)}")
    log(f"[window] programs compiled or loaded inside the window: "
        f"{in_window} (engine step traces: "
        f"{counters.get('trace_count', 0)})")
    log(f"[window] counters: {json.dumps(counters)}")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    metrics, breakdown = {}, None
    if args.trace:
        trace = tracing.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        red = tracing.reduce(trace, chips=len(devs))
        try:
            tracing.require_device_work(red)
        except ValueError as e:
            log(f"bench: {e}")
            sys.exit(3)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        n_elig = (int(check.plain.eligible(filters_host, ranges).sum())
                  if ranges else 0)
        ctx = SimpleNamespace(
            reduction=red, counters=counters, mode=mode, peak=peak,
            scan_work=lambda q: work.scan_work(cfg, mode, q, n, n_elig))
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        breakdown = {"device_ops": [[a, b] for a, b in red.top_ops],
                     "idle_gaps": [[a, b] for a, b in red.idle_gaps]}
        log(f"[trace] idle seconds by host span: "
            f"{json.dumps(red.idle_by_label)}")
    else:
        e2e = gen.end_to_end(res)
        e2e["setup_s"] = setup_s
        e2e["hbm_peak_gb"] = mem / 1e9
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}

    # -- correctness: after the window, with the system under test freed --
    server.close()
    del server
    gc.collect()
    t0 = time.perf_counter()
    served = np.nonzero(res["served"])[0]
    rng = np.random.default_rng([int(seed) & (2**64 - 1), 2])
    take = np.sort(rng.choice(served, min(int(traffic["sample"]),
                                          served.size), replace=False))
    rv, rf = data.corpus(cfg, seed)
    rv, rf = np.asarray(rv), np.asarray(rf)
    ids, scores = res["ids"][take], res["scores"][take]
    if mode == "predicate":
        numbers = check.predicate(rv, rf, cfg["fcvi"].get("alpha", 1.0),
                                  ranges, res["q"][take], ids, scores)
    else:
        numbers = check.similarity(rv, rf, cfg["fcvi"]["lam"],
                                   res["q"][take], res["fq"][take], ids,
                                   scores)
    del rv, rf
    ok, rows = check.verdict(numbers, cell.limits)
    correct = bool(ok and res["failed"] == 0)
    log(f"[check] {take.size} answers compared with the fp64 reference in "
        f"{time.perf_counter() - t0:.1f} s; {res['failed']} requests "
        f"never answered")
    out = {"correct": correct, "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": value, "limit": lim}
                     for name, value, lim in rows}
    for name, value, lim in rows:
        log(f"check {name} = {value!r} limit {lim!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""End-to-end query-path benchmark: FCVIEngine.search throughput.

The repo's perf-trajectory artifact. Times the serving engine — whose
per-batch hot path is one jax.jit-compiled step — on the flat, IVF and PQ
backends, with and without the Pallas kernels, fp32 and bf16 corpus storage,
at batch sizes 64 and 256, against a live delta buffer (the production
steady state: inserts pending, compaction not yet triggered). Also times a
faithful re-implementation of the pre-batching per-query engine loop
(per-query cache keys + per-query numpy delta merge) as the ``legacy``
baseline, so the speedup of the loop-free path is measured on the same host
and corpus.

Writes BENCH_query_path.json next to this file:

  {"results": [{backend, use_pallas, storage_dtype, batch, qps,
                ms_per_query, recall_vs_fp32}, ...],
   "routed": [{backend, routing, filter_mix, qps, shard_skip_rate,
               router_fallback_frac}, ...],
   "filtered": [{backend, filter_mix, plan, est_selectivity, qps,
                 fold_fallback_frac}, ...],
   "legacy": {...}, "speedup_batch64_flat_vs_legacy": ...,
   "speedup_batch64_flat_vs_pr1_jnp": ...}

``recall_vs_fp32`` compares each reduced-precision row's
final top-k ids against the fp32 row of the same config (1.0 = the
exact-refine pass fully recovered the fp32 ranking).

``--host-devices N`` forces N host (CPU) devices BEFORE jax initialises and
adds mesh-sharded engine rows (flat + IVF on a 1-device and an N-device
mesh), exercising the shard_map batch step end to end, plus the dense-vs-
routed rows on filter-centric (cluster) placement: a selective filter mix
(every query targets one category) against a broad mix, with the fraction
of per-batch shard scans the router skipped and the dense-fallback rate.
NOTE: off-TPU hosts run the Pallas kernels in interpret mode and host
"devices" share the same cores, so ``use_pallas=true`` and ``sharded`` rows
measure dispatch correctness and sharding overhead, not TPU performance.

Usage: PYTHONPATH=src python benchmarks/query_path.py [--n 8192] [--quick]
           [--host-devices 8]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

import numpy as np


def _early_host_devices():
    """XLA reads XLA_FLAGS at first jax init — must run before jax imports.

    Handles both ``--host-devices N`` and ``--host-devices=N``; malformed
    values fall through so argparse can report them properly.
    """
    n = None
    for i, arg in enumerate(sys.argv):
        if arg == "--host-devices" and i + 1 < len(sys.argv):
            n = sys.argv[i + 1]
        elif arg.startswith("--host-devices="):
            n = arg.split("=", 1)[1]
    try:
        n = int(n) if n is not None else 0
    except ValueError:
        return
    if n > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}")


_early_host_devices()

import jax
import jax.numpy as jnp

from repro.core import FCVIConfig, build, fcvi
from repro.core.filters import F, compile_predicate
from repro.data.synthetic import CorpusSpec, make_corpus, sample_queries
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.serve.engine import EngineConfig, FCVIEngine

# batch-64 flat jnp engine throughput recorded in PR 1 (pre-jitted step)
PR1_FLAT64_QPS = 1135.0


def legacy_search(engine: FCVIEngine, queries: np.ndarray,
                  filters: np.ndarray):
    """The pre-change engine loop: O(batch) host-side python per query."""
    n = queries.shape[0]
    k = engine.cfg.k
    out_scores = np.zeros((n, k), np.float32)
    out_ids = np.zeros((n, k), np.int64)

    def cache_key(q, f):
        r = engine.cfg.cache_round
        return (np.round(q / r).astype(np.int32).tobytes() + b"#"
                + np.round(f / r).astype(np.int32).tobytes())

    def merge_delta(q, f, scores, ids):
        if not engine._delta_v:
            return scores, ids
        dv = np.concatenate(engine._delta_v)
        df = np.concatenate(engine._delta_f)
        tfm = engine.index.transform
        qn = np.asarray(tfm.vec_norm.apply(jnp.asarray(q[None])))[0]
        fqn = np.asarray(tfm.filt_norm.apply(jnp.asarray(f[None])))[0]
        dvn = np.asarray(tfm.vec_norm.apply(jnp.asarray(dv)))
        dfn = np.asarray(tfm.filt_norm.apply(jnp.asarray(df)))

        def cos(a, b):
            return (a @ b) / (np.linalg.norm(a, axis=-1)
                              * np.linalg.norm(b) + 1e-8)

        lam = engine.index.config.lam
        s = lam * cos(dvn, qn) + (1 - lam) * cos(dfn, fqn)
        base = engine.index.size
        all_s = np.concatenate([scores, s])
        all_i = np.concatenate([ids, base + np.arange(len(s))])
        top = np.argsort(-all_s)[:k]
        return all_s[top].astype(np.float32), all_i[top]

    todo = []
    for i in range(n):
        hit = engine._cache_get(cache_key(queries[i], filters[i]))
        if hit is not None:
            out_scores[i], out_ids[i] = hit
        else:
            todo.append(i)
    bs = engine.cfg.batch_size
    for s in range(0, len(todo), bs):
        idxs = todo[s:s + bs]
        pad = bs - len(idxs)
        q = np.concatenate([queries[idxs],
                            np.zeros((pad, queries.shape[1]), np.float32)])
        f = np.concatenate([filters[idxs],
                            np.zeros((pad, filters.shape[1]), np.float32)])
        scores, ids = engine._staged_query(jnp.asarray(q), jnp.asarray(f), k)
        scores, ids = np.asarray(scores), np.asarray(ids)
        for j, i in enumerate(idxs):
            sc, di = merge_delta(queries[i], filters[i], scores[j], ids[j])
            out_scores[i], out_ids[i] = sc, di
            engine._cache_put(cache_key(queries[i], filters[i]), (sc, di))
    return out_scores, out_ids


def make_engine(corpus, backend: str, use_pallas: bool, batch: int,
                n_delta: int, storage_dtype: str = "float32",
                mesh_devices: int = 0, placement: str = "contiguous",
                routing: str = "dense", alpha: float = 1.0,
                index=None) -> FCVIEngine:
    cfg = FCVIConfig(alpha=alpha, lam=0.6, c=8.0, backend=backend,
                     nlist=64, nprobe=8, pq_ksub=64, pq_coarse=16,
                     use_pallas=use_pallas, storage_dtype=storage_dtype)
    idx = index if index is not None else build(
        jnp.asarray(corpus.vectors), jnp.asarray(corpus.filters), cfg)
    mesh = (make_mesh((mesh_devices, 1), ("data", "model"))
            if mesh_devices else None)
    eng = FCVIEngine(idx, EngineConfig(k=10, batch_size=batch,
                                       compact_threshold=4 * n_delta),
                     mesh=mesh, placement=placement, routing=routing,
                     attributes=(np.asarray(corpus.filters, np.float32)
                                 if backend != "pq" else None))
    if n_delta:
        r = np.random.default_rng(99)
        eng.insert(r.normal(size=(n_delta, corpus.spec.d)).astype(np.float32),
                   corpus.filters[:n_delta].copy())
    return eng


def sample_selective_queries(corpus, n: int, seed: int = 5, cat: int = 1):
    """Filter-selective traffic: every query targets the SAME category filter
    (drawn from that category's rows), the workload filter-centric placement
    concentrates onto few shards. ``cat=1`` picks a mid-size Zipf category —
    the head category genuinely spans several shards by row count alone."""
    rng = np.random.default_rng(seed)
    members = np.nonzero(corpus.cat_labels == cat)[0]
    idx = members[rng.integers(0, len(members), n)]
    q = (corpus.vectors[idx] + 0.25 * corpus.spec.noise
         * rng.normal(size=(n, corpus.spec.d))).astype(np.float32)
    return q, corpus.filters[idx].copy()


def time_search(fn, queries, filters, iters: int):
    fn(queries, filters)                       # warmup (jit compile)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(queries, filters)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=8192)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--n-delta", type=int, default=512)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="flat backend, batch 64 only")
    ap.add_argument("--host-devices", type=int, default=1,
                    help="force N host devices (set before jax init) and add "
                    "mesh-sharded engine rows on 1- and N-device meshes")
    ap.add_argument("--storage-dtype", default=None,
                    choices=["float32", "bfloat16", "int8"],
                    help="pin every meshless flat/IVF row to one storage "
                    "rung (CI smoke: --quick --storage-dtype int8 exercises "
                    "the quantized scan + exact-refine path end to end)")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default: BENCH_query_path.json "
                    "next to this script; CI smoke runs point this at a "
                    "scratch path so the committed artifact keeps the full-"
                    "config numbers)")
    args = ap.parse_args()
    enable_compile_cache()

    spec = CorpusSpec(n=args.n, d=args.d, n_categories=6, n_numeric=2, seed=0)
    corpus = make_corpus(spec)

    # (backend, use_pallas, batch, storage_dtype, mesh_devices [0 = no mesh])
    combos = [("flat", False, 64, "float32", 0),
              ("flat", True, 64, "float32", 0),
              ("flat", False, 64, "bfloat16", 0),
              ("flat", False, 64, "int8", 0)]
    if not args.quick:
        combos += [("flat", False, 256, "float32", 0),
                   ("flat", True, 256, "float32", 0),
                   ("flat", True, 64, "bfloat16", 0),
                   ("flat", True, 64, "int8", 0),
                   ("ivf", False, 64, "float32", 0),
                   ("ivf", True, 64, "float32", 0),
                   ("ivf", False, 256, "float32", 0),
                   ("ivf", True, 256, "float32", 0),
                   ("ivf", False, 64, "bfloat16", 0),
                   ("ivf", False, 64, "int8", 0),
                   ("ivf", True, 64, "int8", 0),
                   ("pq", False, 64, "float32", 0),
                   ("pq", True, 64, "float32", 0)]
    if args.storage_dtype:
        # CI smoke: pin every meshless row to one storage rung
        combos = [(b, up, bt, args.storage_dtype, md) if md == 0 and
                  b != "pq" else (b, up, bt, st, md)
                  for (b, up, bt, st, md) in combos]
        combos = list(dict.fromkeys(combos))
    ndev = min(args.host_devices, len(jax.devices()))
    if ndev > 1:
        # mesh-sharded engine rows: 1-device vs all-device mesh (host
        # "devices" share cores off-TPU — dispatch/overhead check, not speed)
        combos += [("flat", False, 64, "float32", 1),
                   ("flat", False, 64, "float32", ndev)]
        if not args.quick:
            combos += [("ivf", False, 64, "float32", 1),
                       ("ivf", False, 64, "float32", ndev),
                       ("flat", True, 64, "float32", ndev),
                       ("ivf", True, 64, "float32", ndev)]

    results = []
    fp32_ids = {}   # (backend, use_pallas, batch) -> fp32 final ids
    for backend, use_pallas, batch, storage_dtype, mesh_devices in combos:
        q, fq = sample_queries(corpus, batch, seed=1)
        q, fq = np.asarray(q), np.asarray(fq)
        eng = make_engine(corpus, backend, use_pallas, batch, args.n_delta,
                          storage_dtype, mesh_devices)

        def run(queries, filters, eng=eng):
            eng._cache.clear()                 # measure compute, not cache
            return eng.search(queries, filters)

        _, ids = run(q, fq)                    # warmup (jit compile)
        ids = np.asarray(ids)
        t = time_search(run, q, fq, args.iters)
        row = dict(backend=backend, use_pallas=use_pallas,
                   storage_dtype=storage_dtype, batch=batch,
                   mesh_devices=mesh_devices,
                   qps=batch / t, ms_per_query=1e3 * t / batch)
        key = (backend, use_pallas, batch)
        if storage_dtype == "float32" and mesh_devices == 0:
            fp32_ids[key] = ids
        elif mesh_devices == 0 and key in fp32_ids:
            # post-refine recall of the reduced-precision rung vs fp32
            row["recall_vs_fp32"] = round(
                float((ids == fp32_ids[key]).mean()), 4)
        results.append(row)
        print(f"{backend:4s} pallas={int(use_pallas)} "
              f"st={storage_dtype:8s} batch={batch:3d} "
              f"mesh={mesh_devices} "
              f"qps={row['qps']:9.1f}  {row['ms_per_query']:.3f} ms/q"
              + (f"  recall={row['recall_vs_fp32']:.3f}"
                 if "recall_vs_fp32" in row else ""))

    # routed vs dense sharded serving on filter-centric (cluster) placement:
    # alpha=2.0 strengthens the filter fold so selective traffic is
    # geometrically local (the routed win is a geometry property — weakly
    # folded corpora route conservatively and fall back dense more often)
    routed_rows = []
    if ndev > 1:
        for backend in (["flat"] if args.quick else ["flat", "ivf"]):
            idx_cache = {}
            for mix in ("selective", "broad"):
                if mix == "selective":
                    q, fq = sample_selective_queries(corpus, 64)
                else:
                    q, fq = sample_queries(corpus, 64, seed=1)
                    q, fq = np.asarray(q), np.asarray(fq)
                for routing in ("dense", "routed"):
                    eng = make_engine(corpus, backend, False, 64,
                                      args.n_delta, mesh_devices=ndev,
                                      placement="cluster", routing=routing,
                                      alpha=2.0, index=idx_cache.get(backend))
                    idx_cache[backend] = eng.index

                    def run(queries, filters, eng=eng):
                        eng._cache.clear()
                        return eng.search(queries, filters)

                    run(q, fq)                 # warmup (jit compile)
                    eng.stats = type(eng.stats)()  # count timed runs only
                    ts = []
                    for _ in range(args.iters):
                        t0 = time.perf_counter()
                        run(q, fq)
                        ts.append(time.perf_counter() - t0)
                    t = float(np.median(ts))
                    st = eng.stats
                    row = dict(backend=backend, routing=routing,
                               placement="cluster", filter_mix=mix,
                               batch=64, mesh_devices=ndev, alpha=2.0,
                               qps=64 / t, ms_per_query=1e3 * t / 64,
                               shard_skip_rate=round(st.shard_skip_rate, 4),
                               router_fallback_frac=round(
                                   st.router_fallbacks / max(st.queries, 1),
                                   4))
                    routed_rows.append(row)
                    print(f"{backend:4s} {routing:6s} mix={mix:9s} "
                          f"mesh={ndev} qps={row['qps']:9.1f}  "
                          f"skip={row['shard_skip_rate']:.2f} "
                          f"fb={row['router_fallback_frac']:.2f}")

    # degraded-mode serving: 1 of ndev shards dead — qps (the dead shard's
    # cond branch is zero-work, so degraded throughput should not collapse)
    # plus the coverage rate the certificate reports for this traffic
    degraded_rows = []
    if ndev > 1:
        for backend in (["flat"] if args.quick else ["flat", "ivf"]):
            idx_cache = None
            for routing in ("dense", "routed"):
                q, fq = sample_selective_queries(corpus, 64)
                eng = make_engine(corpus, backend, False, 64, args.n_delta,
                                  mesh_devices=ndev, placement="cluster",
                                  routing=routing, alpha=2.0,
                                  index=idx_cache)
                idx_cache = eng.index
                eng.health.mark_dead([ndev - 1])

                def run(queries, filters, eng=eng):
                    eng._cache.clear()
                    return eng.search(queries, filters)

                run(q, fq)                     # warmup (jit compile)
                eng.stats = type(eng.stats)()  # count timed runs only
                ts = []
                for _ in range(args.iters):
                    t0 = time.perf_counter()
                    run(q, fq)
                    ts.append(time.perf_counter() - t0)
                t = float(np.median(ts))
                st = eng.stats
                row = dict(backend=backend, routing=routing,
                           placement="cluster", alpha=2.0, batch=64,
                           mesh_devices=ndev, dead_shards=1,
                           qps=64 / t, ms_per_query=1e3 * t / 64,
                           coverage_rate=round(st.coverage_rate, 4),
                           uncovered_per_batch=round(
                               st.uncovered_queries / max(
                                   st.degraded_batches, 1), 2))
                degraded_rows.append(row)
                print(f"{backend:4s} {routing:6s} DEGRADED 1/{ndev} dead "
                      f"qps={row['qps']:9.1f}  "
                      f"cov={row['coverage_rate']:.2f}")

    # predicate-filtered serving: the general filter algebra across three
    # selectivity bands — the planner's chosen physical plan rides along in
    # each row (fold for broad single-attribute, mask for mid conjunctions,
    # routed for selective predicates on prunable structure)
    filtered_rows = []
    mixes = [
        ("broad_range", F.range("f6", 0.05, 0.95)),
        ("mid_conjunction",
         F.range("f6", 0.2, 0.6) & F.range("f7", 0.0, 0.7)),
        ("narrow_isin_range", F.isin("f4", [1.0]) & F.range("f6", 0.0, 0.15)),
    ]
    for backend in (["flat"] if args.quick else ["flat", "ivf"]):
        eng = make_engine(corpus, backend, False, 64, args.n_delta)
        q, _ = sample_queries(corpus, 64, seed=1)
        q = np.asarray(q)
        for mix, pred in mixes:
            cpp = compile_predicate(pred, eng._attr_names)
            plan = eng.planner.choose(cpp)
            sel = eng.planner.selectivity(cpp)

            def run(queries, filters=None, eng=eng, pred=pred):
                return eng.search(queries, filter=pred)

            t = time_search(run, q, None, args.iters)
            eng.stats = type(eng.stats)()
            run(q)
            st = eng.stats
            row = dict(backend=backend, filter_mix=mix, plan=plan,
                       est_selectivity=round(float(sel), 4), batch=64,
                       qps=64 / t, ms_per_query=1e3 * t / 64,
                       fold_fallback_frac=round(
                           st.filtered_fallbacks / max(st.queries, 1), 4))
            filtered_rows.append(row)
            print(f"{backend:4s} filtered mix={mix:16s} plan={plan:6s} "
                  f"sel={row['est_selectivity']:.3f} "
                  f"qps={row['qps']:9.1f}  "
                  f"fb={row['fold_fallback_frac']:.2f}")

    # legacy per-query loop baseline (jnp kernels off, flat, batch 64)
    q, fq = sample_queries(corpus, 64, seed=1)
    q, fq = np.asarray(q), np.asarray(fq)
    eng = make_engine(corpus, "flat", False, 64, args.n_delta)

    def run_legacy(queries, filters, eng=eng):
        eng._cache.clear()
        return legacy_search(eng, queries, filters)

    t = time_search(run_legacy, q, fq, args.iters)
    legacy = dict(backend="flat", use_pallas=False, batch=64, qps=64 / t,
                  ms_per_query=1e3 * t / 64)
    print(f"legacy loop       batch= 64 qps={legacy['qps']:9.1f}  "
          f"{legacy['ms_per_query']:.3f} ms/q")

    base_dtype = args.storage_dtype or "float32"
    new64 = next(r for r in results
                 if r["backend"] == "flat" and not r["use_pallas"]
                 and r["batch"] == 64 and r["storage_dtype"] == base_dtype
                 and r["mesh_devices"] == 0)
    out = dict(
        config=dict(
            n=args.n, d=args.d, n_delta=args.n_delta, k=10, iters=args.iters,
            host_devices=ndev,
            note=("use_pallas rows run the Pallas kernels in interpret mode "
                  "on non-TPU hosts (dispatch correctness, not TPU perf); "
                  "recall_vs_fp32 compares reduced-precision final ids with "
                  "the fp32 row (1.0 after the exact-refine pass); "
                  "the engine batch step is one jax.jit-compiled function; "
                  "mesh_devices>0 rows run the shard_map sharded step — "
                  "forced host devices share cores, so those rows measure "
                  "sharding overhead, not scaling; 'routed' rows compare "
                  "dense vs filter-routed serving on cluster placement "
                  "(alpha=2): shard_skip_rate is the fraction of per-batch "
                  "shard scans the router skipped, router_fallback_frac the "
                  "queries re-run dense because the clipping bound could "
                  "not certify exactness; 'degraded' rows serve the same "
                  "cluster-placed engines with 1 shard marked dead — "
                  "results are bit-identical to a search over surviving "
                  "rows, coverage_rate is the fraction of queries the "
                  "ball-bound/list-ownership certificate proved unaffected "
                  "by the dead shard; 'filtered' rows serve composable "
                  "predicates (range/eq/IN-list conjunctions) through the "
                  "selectivity-aware planner — 'plan' is the physical plan "
                  "it chose (fold/mask/routed), fold_fallback_frac the "
                  "fold-plan queries whose certificate failed and re-ran "
                  "under mask"),
        ),
        results=results,
        routed=routed_rows,
        degraded=degraded_rows,
        filtered=filtered_rows,
        legacy=legacy,
        speedup_batch64_flat_vs_legacy=new64["qps"] / legacy["qps"],
    )
    if args.n == 8192 and args.d == 64 and args.n_delta == 512:
        # PR-1 recorded 1135 qps for this exact flat/jnp/batch-64 config
        # before the engine step was fused into a single jitted function;
        # the ratio is only meaningful for the same corpus shape
        out["speedup_batch64_flat_vs_pr1_jnp"] = new64["qps"] / PR1_FLAT64_QPS
    path = (pathlib.Path(args.out) if args.out
            else pathlib.Path(__file__).parent / "BENCH_query_path.json")
    path.write_text(json.dumps(out, indent=2))
    vs_pr1 = out.get("speedup_batch64_flat_vs_pr1_jnp")
    print(f"speedup (batch-64 flat vs legacy loop): "
          f"{out['speedup_batch64_flat_vs_legacy']:.2f}x"
          + (f"; vs PR-1 jnp baseline: {vs_pr1:.2f}x" if vs_pr1 else "")
          + f" -> {path}")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Smoke run of the filtered-search engine on a TPU, checked against fp64.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the mesh-sharded engine on 4 chips

One chip: a seeded synthetic corpus (``CorpusSpec``, n = 2^20 rows of
d = 768, m = 8 filter columns) is indexed and served through
``FCVIEngine.search`` with ``EngineConfig(k=10, batch_size=64)`` as

  (a) flat, Pallas kernels;  (b) flat, pure XLA;
  (c) IVF (nlist 1024, nprobe 32), Pallas kernels;
  (d) one predicate batch, ``filter=F.range(...) & F.isin(...)``, on (a).

(a)-(c) must reach recall@10 >= 0.95 against an fp64 NumPy brute force of
the paper's combined score; (d) must return the exact filtered top-k by L2
apart from near-ties. ``--chips 4`` runs only the mesh path: flat and IVF,
dense and routed, and one shard marked dead, each compared with the
meshless engine (bit-identical results) and the fp64 reference.

Everything is generated from ``--seed``; nothing is read from disk. Timings
are set-up information (wall clock around calls that return host arrays),
not metrics. The last line of stdout is one JSON object naming the device.
Off a TPU the script exits non-zero before building anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

K = 10
BATCH = 64
RECALL_BAR = 0.95           # the README quickstart's bar
LAM, C = 0.6, 8.0           # k' = c*k/lam = 133 (Alg. 1 line 7)


def log(*parts):
    print(*parts, flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=1 << 20,
                    help="corpus rows of the flat phases")
    ap.add_argument("--n-ivf", type=int, default=1 << 19,
                    help="corpus rows of the IVF phases (a prefix of the "
                    "flat corpus)")
    ap.add_argument("--d", type=int, default=768)
    ap.add_argument("--nlist", type=int, default=1024)
    ap.add_argument("--nprobe", type=int, default=32)
    ap.add_argument("--batches", type=int, default=3,
                    help="query batches served per path (the first compiles)")
    ap.add_argument("--checked", type=int, default=16,
                    help="queries checked against the fp64 reference")
    ap.add_argument("--backends", default="flat,ivf",
                    help="comma-separated backends of the --chips 4 run")
    return ap.parse_args(argv)


# ---------------------------------------------------------------------------
# fp64 references (host NumPy, row-chunked so host RAM is not the limit)
# ---------------------------------------------------------------------------

CHUNK = 1 << 16


def combined_topk_f64(vectors, filters, tfm, q, fq, k=K):
    """Exact top-k of lam*cos(v, q) + (1-lam)*cos(f, F_q) over the
    normalized corpus, in fp64, with the index's fitted normalizers."""
    mv, sv = (np.asarray(a, np.float64) for a in (tfm.vec_norm.mean,
                                                  tfm.vec_norm.std))
    mf, sf = (np.asarray(a, np.float64) for a in (tfm.filt_norm.mean,
                                                  tfm.filt_norm.std))

    def unit(x):
        return x / (np.linalg.norm(x, axis=-1, keepdims=True) + 1e-30)

    qu = unit((np.asarray(q, np.float64) - mv) / sv)
    fu = unit((np.asarray(fq, np.float64) - mf) / sf)
    best_s = np.full((q.shape[0], 0), -np.inf)
    best_i = np.zeros((q.shape[0], 0), np.int64)
    for a in range(0, vectors.shape[0], CHUNK):
        vn = unit((vectors[a:a + CHUNK].astype(np.float64) - mv) / sv)
        fn = unit((filters[a:a + CHUNK].astype(np.float64) - mf) / sf)
        s = LAM * (qu @ vn.T) + (1.0 - LAM) * (fu @ fn.T)
        ids = np.broadcast_to(np.arange(a, a + s.shape[1]), s.shape)
        best_s = np.concatenate([best_s, s], axis=1)
        best_i = np.concatenate([best_i, ids], axis=1)
        keep = np.argsort(-best_s, axis=1, kind="stable")[:, :k]
        best_s = np.take_along_axis(best_s, keep, 1)
        best_i = np.take_along_axis(best_i, keep, 1)
    return best_i


def recall(pred_ids, true_ids):
    hits = [len(set(p) & set(t)) for p, t in zip(pred_ids, true_ids)]
    return float(np.mean(hits)) / true_ids.shape[1]


def filtered_topk_f64(rows_dev, q_t, elig, k=K):
    """Exact filtered top-k by squared L2 in fp64 over the stored rows, with
    the (d2, id) tie-break and the near-tie flags of the repository's
    filter oracle (``tests/test_filter_oracle.py``). The tie tolerance grows
    with the distance: the engine sums 768 fp32 terms, so two rows whose
    fp64 distances differ by less than that rounding may swap."""
    q_t = np.asarray(q_t, np.float64)
    ids_all = np.nonzero(elig)[0]
    d2 = np.empty((q_t.shape[0], ids_all.shape[0]))
    for a in range(0, ids_all.shape[0], CHUNK):
        sel = ids_all[a:a + CHUNK]
        rows = np.asarray(rows_dev[sel], np.float64)
        d2[:, a:a + sel.shape[0]] = (
            (q_t * q_t).sum(-1)[:, None] - 2.0 * (q_t @ rows.T)
            + (rows * rows).sum(-1)[None, :])
    order = np.lexsort((np.broadcast_to(ids_all, d2.shape), d2), axis=-1)
    sd2 = np.take_along_axis(d2, order, -1)[:, :k + 1]
    tol = 1e-4 + 2e-6 * np.abs(sd2)
    gap_prev = np.diff(sd2, axis=-1, prepend=-np.inf)
    gap_next = np.diff(sd2, axis=-1, append=np.inf)
    amb = (gap_prev < tol) | (gap_next < tol)
    return (-sd2[:, :k]).astype(np.float32), ids_all[order[:, :k]], amb[:, :k]


# ---------------------------------------------------------------------------
# serving helpers
# ---------------------------------------------------------------------------

def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def serve(engine, q, fq, label):
    """All batches through ``engine.search``; returns (scores, ids). Prints
    the first call (compile + run) and the later ones as set-up timings."""
    scores, ids, times = [], [], []
    for s in range(0, q.shape[0], BATCH):
        t0 = time.perf_counter()
        out = engine.search(q[s:s + BATCH], fq[s:s + BATCH])
        times.append(time.perf_counter() - t0)   # host arrays: blocked
        scores.append(out[0])
        ids.append(out[1])
    steady = ", ".join(f"{t:.3f}" for t in times[1:]) or "-"
    log(f"[setup] {label}: first batch (compile + run) {times[0]:.1f} s; "
        f"later batches {steady} s each; escalations "
        f"{engine.stats.escalations}")
    return np.concatenate(scores), np.concatenate(ids)


def build_index(corpus_v, corpus_f, backend, args, use_pallas):
    import jax.numpy as jnp

    from repro.core import FCVIConfig, build

    cfg = FCVIConfig(alpha=1.0, lam=LAM, c=C, backend=backend,
                     nlist=args.nlist, nprobe=args.nprobe,
                     use_pallas=use_pallas)
    t0 = time.perf_counter()
    index = build(jnp.asarray(corpus_v), jnp.asarray(corpus_f), cfg)
    index.vectors_n.block_until_ready()
    log(f"[setup] {backend} index over {corpus_v.shape[0]} rows built in "
        f"{time.perf_counter() - t0:.1f} s")
    return index


def with_pallas(index, use_pallas):
    cfg = dataclasses.replace(index.config, use_pallas=use_pallas)
    return dataclasses.replace(index, config=cfg)


def check_recall(label, ids, truth):
    r = recall(ids[:truth.shape[0]], truth)
    log(f"{label}: recall@{K} vs fp64 = {r:.4f} over {truth.shape[0]} "
        f"queries")
    if r < RECALL_BAR:
        raise SystemExit(f"FAIL {label}: recall {r:.4f} < {RECALL_BAR}")
    return r


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def run_one_chip(args, dev, corpus, q, fq):
    from repro.core.filters import F, compile_predicate
    from repro.core import fcvi
    from repro.serve.engine import EngineConfig, FCVIEngine

    v, f = corpus.vectors, corpus.filters
    nc = args.checked
    index = build_index(v, f, "flat", args, use_pallas=True)
    log(f"[setup] peak device bytes after flat build: {peak_bytes(dev)}")
    truth = combined_topk_f64(v, f, index.transform, q[:nc], fq[:nc])

    eng_p = FCVIEngine(index, EngineConfig(k=K, batch_size=BATCH),
                       attributes=f)
    _, ids_p = serve(eng_p, q, fq, "(a) flat pallas")
    check_recall("(a) flat pallas", ids_p, truth)

    eng_x = FCVIEngine(with_pallas(index, False),
                       EngineConfig(k=K, batch_size=BATCH))
    _, ids_x = serve(eng_x, q, fq, "(b) flat xla")
    check_recall("(b) flat xla", ids_x, truth)
    same_pos = float(np.mean(ids_p == ids_x))
    same_set = recall(ids_p, ids_x)
    log(f"pallas vs xla (flat, {ids_p.shape[0]} queries): ids equal at "
        f"{same_pos:.4f} of positions, top-{K} sets overlap {same_set:.4f}")
    log(f"peak device bytes after flat serving: {peak_bytes(dev)}")

    # (d) one predicate batch: category AND numeric range (two attributes,
    # so the planner cannot fold and picks mask or routed)
    pred = F.isin("f1", [1.0]) & F.range("f5", 0.2, 0.7)
    cp = compile_predicate(pred, eng_p._attr_names)
    elig = cp.eval_np(eng_p._attrs_np)
    t0 = time.perf_counter()
    s_d, i_d = eng_p.search(q[:BATCH], filter=pred)
    plan = [p for p in ("fold", "mask", "routed")
            if getattr(eng_p.stats, f"plan_{p}")]
    log(f"[setup] (d) predicate batch (plan {plan}, selectivity "
        f"{elig.mean():.4f}): {time.perf_counter() - t0:.1f} s")
    q_t = fcvi.fold_queries(index, q[:BATCH], cp.fold_target_raw(
        eng_p._col_means))[:nc]
    want_s, want_i, amb = filtered_topk_f64(index.backend.vectors, q_t, elig)
    exact = (i_d[:nc] == want_i) | amb
    log(f"(d) predicate: {int(exact.sum())}/{exact.size} top-{K} slots "
        f"match the fp64 filtered top-k ({int(amb.sum())} near-ties)")
    if not exact.all():
        raise SystemExit("FAIL (d): predicate top-k differs from fp64")
    np.testing.assert_allclose(s_d[:nc], want_s, rtol=1e-4, atol=1e-4)
    del eng_p, eng_x, index
    gc.collect()

    # (c) IVF on a prefix of the corpus
    n_ivf = min(args.n_ivf, v.shape[0])
    if n_ivf < v.shape[0]:
        log(f"cut: IVF phase at n={n_ivf} (the k-means build holds two "
            f"(n, nlist) fp32 temporaries beside the corpus, its normalized "
            f"copy and the list-padded slab)")
    index = build_index(v[:n_ivf], f[:n_ivf], "ivf", args, use_pallas=True)
    be = index.backend
    log(f"[setup] IVF lists: max {be.max_list}, mean "
        f"{float(np.mean(np.asarray(be.list_sizes))):.1f}; slab "
        f"{be.grouped.nbytes} bytes; peak device bytes {peak_bytes(dev)}")
    truth = combined_topk_f64(v[:n_ivf], f[:n_ivf], index.transform,
                              q[:nc], fq[:nc])
    eng = FCVIEngine(index, EngineConfig(k=K, batch_size=BATCH))
    _, ids_i = serve(eng, q, fq, "(c) ivf pallas")
    check_recall("(c) ivf pallas", ids_i, truth)
    log(f"peak device bytes after IVF serving: {peak_bytes(dev)}")


# ---------------------------------------------------------------------------
# four chips: the mesh-sharded engine vs the meshless one
# ---------------------------------------------------------------------------

def run_four_chips(args, devs, corpus, q, fq):
    from repro.launch.mesh import make_host_mesh
    from repro.serve import faultinject
    from repro.serve.engine import EngineConfig, FCVIEngine

    mesh = make_host_mesh()
    log(f"mesh: {dict(mesh.shape)} over {len(devs)} devices")
    nc = args.checked
    ecfg = EngineConfig(k=K, batch_size=BATCH)

    def same(label, got, want):
        ids_ok = np.array_equal(got[1], want[1])
        s_ok = np.array_equal(got[0], want[0])
        log(f"{label}: ids identical {ids_ok}, scores identical {s_ok} "
            f"(max |diff| {float(np.max(np.abs(got[0] - want[0]))):.3g})")
        if not (ids_ok and s_ok):
            raise SystemExit(f"FAIL {label}: sharded != meshless")

    sizes = {"flat": args.n, "ivf": args.n_ivf}
    for backend in args.backends.split(","):
        n = min(sizes[backend], corpus.vectors.shape[0])
        v, f = corpus.vectors[:n], corpus.filters[:n]
        index = build_index(v, f, backend, args, use_pallas=True)
        if backend == "ivf":
            be = index.backend
            log(f"[setup] IVF lists: max {be.max_list}, mean "
                f"{float(np.mean(np.asarray(be.list_sizes))):.1f}")
        log(f"[setup] {backend}: device 0 peak bytes after build "
            f"{peak_bytes(devs[0])}")
        truth = combined_topk_f64(v, f, index.transform, q[:nc], fq[:nc])
        meshless = serve(FCVIEngine(index, ecfg), q, fq,
                         f"{backend} meshless")
        check_recall(f"{backend} meshless", meshless[1], truth)
        log(f"[setup] {backend}: device 0 peak bytes after meshless serving "
            f"{peak_bytes(devs[0])}")
        placement = "cluster" if backend == "flat" else "contiguous"
        for routing in ("dense", "routed"):
            eng = FCVIEngine(index, ecfg, mesh=mesh, routing=routing,
                             placement=placement)
            got = serve(eng, q, fq, f"{backend} sharded {routing}")
            same(f"{backend} sharded {routing} vs meshless", got, meshless)
            check_recall(f"{backend} sharded {routing}", got[1], truth)
            del eng
        eng = FCVIEngine(index, ecfg, mesh=mesh, placement=placement)
        eng.health.mark_dead([1])
        got = serve(eng, q, fq, f"{backend} sharded, shard 1 dead")
        ref = faultinject.surviving_reference(eng)
        want = serve(ref, q, fq, f"{backend} meshless over survivors")
        same(f"{backend} degraded vs meshless-over-survivors", got, want)
        log(f"{backend} degraded: {int(eng.stats.uncovered_queries)} of "
            f"{q.shape[0]} queries flagged uncovered")
        del eng, ref, index
        gc.collect()
        log(f"{backend} peak device bytes per device: "
            f"{[peak_bytes(d) for d in devs]}")


def main(argv=None):
    args = parse_args(argv)
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 2
    devs = devs[:args.chips]
    log(f"device_kind: {dev.device_kind} ({len(devs)} used of "
        f"{len(jax.devices())})")

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.data.synthetic import CorpusSpec, make_corpus, sample_queries
    from repro.launch.cache import enable_compile_cache

    log(f"[setup] compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    spec = CorpusSpec(n=args.n, d=args.d, n_categories=5, n_numeric=3,
                      seed=args.seed)
    corpus = make_corpus(spec)
    q, fq = sample_queries(corpus, BATCH * args.batches, seed=args.seed + 1)
    log(f"[setup] corpus n={spec.n} d={spec.d} m={spec.m} generated in "
        f"{time.perf_counter() - t0:.1f} s")
    if args.chips == 4:
        run_four_chips(args, devs, corpus, q, fq)
    else:
        run_one_chip(args, dev, corpus, q, fq)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Serving launcher: build an FCVI index over a synthetic corpus and serve
batched filtered queries through the engine (caching, adaptive k',
escalation, live inserts).

    PYTHONPATH=src python -m repro.launch.serve --n 50000 --queries 512
"""
from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro.core import FCVIConfig, build, ground_truth_combined, recall_at_k
from repro.data.synthetic import CorpusSpec, make_corpus, sample_queries
from repro.launch.cache import enable_compile_cache
from repro.serve.engine import EngineConfig, FCVIEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=50000)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--queries", type=int, default=512)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--backend", default="flat", choices=["flat", "ivf", "pq"])
    ap.add_argument("--alpha", type=float, default=1.0)
    ap.add_argument("--lam", type=float, default=0.6)
    args = ap.parse_args()
    enable_compile_cache()

    spec = CorpusSpec(n=args.n, d=args.d, n_categories=6, n_numeric=2, seed=0)
    corpus = make_corpus(spec)
    t0 = time.perf_counter()
    index = build(jnp.asarray(corpus.vectors), jnp.asarray(corpus.filters),
                  FCVIConfig(alpha=args.alpha, lam=args.lam, c=16.0,
                             backend=args.backend, nlist=128, nprobe=16))
    print(f"built fcvi-{args.backend} over {args.n} vectors "
          f"in {time.perf_counter()-t0:.1f}s")

    engine = FCVIEngine(index, EngineConfig(k=args.k, batch_size=64))
    q, fq = sample_queries(corpus, args.queries, seed=1)

    t0 = time.perf_counter()
    scores, ids = engine.search(q, fq)
    dt = time.perf_counter() - t0

    qn, fqn = index.transform.normalize(jnp.asarray(q), jnp.asarray(fq))
    _, ref = ground_truth_combined(index.vectors_n, index.filters_n, qn, fqn,
                                   args.k, args.lam)
    rec = float(recall_at_k(jnp.asarray(ids), ref))
    print(f"{args.queries} queries in {dt:.2f}s -> {args.queries/dt:.0f} qps, "
          f"recall@{args.k}={rec:.3f}")
    print(f"engine stats: {engine.stats.cache_hits} cache hits, "
          f"{engine.stats.escalations} escalations")

    # repeat -> cache hits
    t0 = time.perf_counter()
    engine.search(q[:128], fq[:128])
    print(f"cached re-serve of 128 queries: "
          f"{(time.perf_counter()-t0)*1e3:.0f}ms "
          f"({engine.stats.cache_hits} total cache hits)")


if __name__ == "__main__":
    main()

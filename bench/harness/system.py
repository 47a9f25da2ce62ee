"""The system under test: ``FCVIEngine.search``, built from a configuration
file, and its warm-up.

``Server`` is what a traffic generator calls: ``serve(q, fq)`` answers a block of
requests through the one entry the benchmark drives, in similarity mode
(``search(q, filters=fq)``) or in predicate mode (``search(q, filter=...)``),
and returns host arrays. ``counters()`` reads the engine's own counters.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from harness import data


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def fcvi_config(cfg: dict):
    """The index configuration: the file's ``fcvi`` group plus its
    top-level IVF sizes."""
    from repro.core import FCVIConfig

    extra = {key: int(cfg[key]) for key in ("nlist", "nprobe") if key in cfg}
    return FCVIConfig(**cfg["fcvi"], **extra)


def predicate(traffic: dict):
    """The traffic's predicate as the program's filter expression."""
    from repro.core.filters import F

    p = traffic["predicate"]
    return F.range(p["attr"], float(p["lo"]), float(p["hi"]))


class Server:
    """One ``FCVIEngine`` over the configuration's corpus."""

    def __init__(self, cfg: dict, traffic: dict, vectors, filters_host):
        from repro.core import build
        from repro.serve.engine import EngineConfig, FCVIEngine

        self.mode = traffic["mode"]
        fcfg = fcvi_config(cfg)
        t0 = time.perf_counter()
        index = build(vectors, filters_host, fcfg)
        index.vectors_n.block_until_ready()
        log(f"[setup] {fcfg.backend} index over {index.size} rows built in "
            f"{time.perf_counter() - t0:.1f} s")
        self.engine = FCVIEngine(
            index, EngineConfig(k=int(cfg["k"]),
                                batch_size=int(cfg["batch_size"])),
            attributes=filters_host, attr_names=data.attr_names(cfg))
        self.pred = predicate(traffic) if self.mode == "predicate" else None

    def serve(self, q, fq):
        if self.pred is not None:
            return self.engine.search(q, filter=self.pred)
        return self.engine.search(q, fq)

    def counters(self) -> dict:
        from repro.serve.engine import trace_count

        s = self.engine.stats
        return {"queries": s.queries, "escalations": s.escalations,
                "plan_fold": s.plan_fold, "plan_mask": s.plan_mask,
                "plan_routed": s.plan_routed,
                "filtered_fallbacks": s.filtered_fallbacks,
                "cache_hits": s.cache_hits, "trace_count": trace_count()}

    def close(self):
        self.engine = None

    # -- warm-up ------------------------------------------------------------
    def warm(self, wq, wfq, max_block: int):
        """Run every shape the window can meet through ``search`` itself.

        Predicate mode: one call of each block size 1..``max_block``.
        Similarity mode: the main step, then one call with exactly ``n``
        escalated queries for each ``n`` in 1..batch size, so that the
        escalation sub-batch (each power-of-two bucket) and the host-side
        scatter of ``n`` rows exist before the window. The ``n`` queries are
        copies of one query that escalates, each moved by one cache-key step
        on two coordinates so that no copy hits the result cache.
        """
        bs = self.engine.cfg.batch_size
        if self.mode == "predicate":
            for n in range(1, min(max_block, bs) + 1):
                self.serve(wq[:n], wfq[:n])
            return
        self.serve(wq[:bs], wfq[:bs])
        base = None
        for j in range(bs, min(bs + 32, wq.shape[0])):
            before = self.engine.stats.escalations
            self.serve(wq[j:j + 1], wfq[j:j + 1])
            if self.engine.stats.escalations > before:
                base = j
                break
        if base is None:
            log("[setup] warm-up: no query escalated; escalation shapes not "
                "warmed")
            return
        step = self.engine.cfg.cache_round
        d = wq.shape[1]
        covered = 0
        for n in range(1, bs + 1):
            q = np.repeat(wq[base:base + 1], n, axis=0)
            q[np.arange(n), np.arange(n) % d] += step
            q[np.arange(n), (bs + n) % d] += step
            fq = np.repeat(wfq[base:base + 1], n, axis=0)
            before = self.engine.stats.escalations
            self.serve(q, fq)
            covered += self.engine.stats.escalations - before == n
        log(f"[setup] warm-up: {covered} of {bs} escalation counts met "
            f"exactly")

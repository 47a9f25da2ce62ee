"""Backlogged traffic: blocks of ``block`` queries, each ``search`` call
starting as soon as the last returned (batch retrieval).

Traffic file keys: ``block`` (queries per call), ``pool`` (distinct queries
drawn from the seed and cycled; larger than the engine's result cache, so a
query comes round again only after the cache has dropped it),
``query_noise``. The window runs calls until ``seconds`` have passed; the
last call finishes. ``qps`` is every query completed over the time from the
window's start to the end of the last call.
"""
from __future__ import annotations

import time

import numpy as np


def plan(traffic, seed, seconds, make_queries):
    q, fq = make_queries(1, int(traffic["pool"]))
    return {"q": q, "fq": fq}


def run(server, plan, seconds, traffic, span):
    q, fq = plan["q"], plan["fq"]
    pool, block = q.shape[0], int(traffic["block"])
    calls = []
    served = np.zeros(pool, bool)
    ids = scores = None
    t0 = time.perf_counter()
    te = t0
    pos = 0
    while te - t0 < seconds:
        sel = np.arange(pos, pos + block) % pool
        pos += block
        with span("search", queries=block):
            ts = time.perf_counter()
            s, d = server.serve(q[sel], fq[sel])
            te = time.perf_counter()
        calls.append((ts, te, block))
        if ids is None:
            ids = np.full((pool, d.shape[1]), -1, np.int64)
            scores = np.full((pool, d.shape[1]), -np.inf, np.float32)
        ids[sel], scores[sel], served[sel] = d, s, True
    total = sum(c[2] for c in calls)
    return {"t0": t0, "t_end": te, "calls": calls, "attempted": total,
            "failed": 0, "served": served, "ids": ids, "scores": scores,
            "q": q, "fq": fq, "completed": total}


def end_to_end(res):
    return {"qps": res["completed"] / (res["t_end"] - res["t0"])}


def describe(res):
    took = " ".join(f"{te - ts:.3f}" for ts, te, _ in res["calls"])
    return (f"{res['completed']} queries in {len(res['calls'])} calls of "
            f"{res['calls'][0][2]} over {res['t_end'] - res['t0']:.3f} s; "
            f"seconds per call: {took}")

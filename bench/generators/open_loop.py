"""Open-loop traffic: requests due on a fixed schedule, whatever the server
does (independent users).

Traffic file keys: ``rate_qps`` (offered load), ``max_per_call`` (the most
requests one ``search`` call takes), ``query_noise`` (queries are corpus rows
plus this many corpus noise sigmas), ``drain_s`` (how long past the window
requests due in it are still served). When a call returns, the next call
takes every request then due, oldest first, up to ``max_per_call``. Each
request is timed from its due time to the return of the call that served it.
"""
from __future__ import annotations

import time

import numpy as np


def plan(traffic, seed, seconds, make_queries):
    from harness import data

    due = data.arrivals(seed, float(traffic["rate_qps"]), seconds)
    q, fq = make_queries(1, due.shape[0])
    return {"due": due, "q": q, "fq": fq}


def run(server, plan, seconds, traffic, span):
    due, q, fq = plan["due"], plan["q"], plan["fq"]
    n = due.shape[0]
    cap = int(traffic["max_per_call"])
    drain = float(traffic["drain_s"])
    done = np.full(n, np.nan)
    start = np.full(n, np.nan)     # when the call serving a request began
    idle_before = np.zeros(n, bool)
    ids = scores = None
    calls = []
    i = 0
    t0 = time.perf_counter()
    abs_due = t0 + due
    t_prev_end = t0
    while i < n:
        now = time.perf_counter()
        if now - t0 > seconds + drain:
            break
        if abs_due[i] > now:
            with span("wait"):
                time.sleep(abs_due[i] - now)
            continue
        j = min(int(np.searchsorted(abs_due, now, side="right")), i + cap)
        with span("search", queries=j - i):
            ts = time.perf_counter()
            s, d = server.serve(q[i:j], fq[i:j])
            te = time.perf_counter()
        if ids is None:
            ids = np.full((n, d.shape[1]), -1, np.int64)
            scores = np.full((n, d.shape[1]), -np.inf, np.float32)
        ids[i:j], scores[i:j] = d, s
        start[i:j], done[i:j] = ts, te
        idle_before[i:j] = abs_due[i:j] >= t_prev_end
        calls.append((ts, te, j - i))
        t_prev_end = te
        i = j
    served = ~np.isnan(done)
    lat = (done - abs_due)[served]
    late = (start - abs_due)[served & idle_before]
    return {"t0": t0, "t_end": t_prev_end, "calls": calls,
            "seconds": seconds,
            "done_in_window": int(np.sum(done[served] - t0 <= seconds)),
            "attempted": n, "failed": int(n - served.sum()),
            "served": served, "ids": ids, "scores": scores, "q": q, "fq": fq,
            "latency_s": lat, "send_late_s": late}


def end_to_end(res):
    lat = res["latency_s"]
    out = {}
    if lat.size:
        out["p50_ms"] = float(np.percentile(lat, 50) * 1e3)
        out["p99_ms"] = float(np.percentile(lat, 99) * 1e3)
    return out


def describe(res):
    """One line for the log: load, completions and generator lateness."""
    late = res["send_late_s"]
    p99_late = float(np.percentile(late, 99) * 1e3) if late.size else 0.0
    span = res["t_end"] - res["t0"]
    return (f"offered {res['attempted']} requests, served "
            f"{int(res['served'].sum())} in {len(res['calls'])} calls over "
            f"{span:.3f} s, {res['done_in_window']} of them within the "
            f"{res['seconds']:g} s window; generator lateness p99 "
            f"{p99_late:.3f} ms over {late.size} requests that found the "
            f"server idle")

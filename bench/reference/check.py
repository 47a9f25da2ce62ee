"""The numbers that decide ``correct``, each held against its limit.

A sample of the answers served in the window (drawn from the seed) is
compared with the plain fp64 reference (``plain.py``):

similarity traffic
  ``bad_answers``  answers with an id outside the corpus, a repeated id or a
                   score that is not finite (limit 0);
  ``recall_miss``  1 - mean recall@k against the exact combined-score top-k;
  ``score_err``    the widest gap between a served score and the fp64
                   combined score of the id it was served with.
predicate traffic
  ``bad_answers``  as above, plus ids that fail the predicate (limit 0);
  ``id_mismatch``  slots whose id differs from the exact filtered top-k and
                   that are not near-ties there (limit 0);
  ``d2_rel_err``   the widest gap between a served distance (-score) and the
                   fp64 squared distance of its id, over max(1, that
                   distance).
"""
from __future__ import annotations

import numpy as np

from reference import plain


def _bad_rows(ids, scores, n, elig=None):
    bad = (ids < 0) | (ids >= n) | ~np.isfinite(scores)
    if elig is not None:
        bad |= ~elig[np.clip(ids, 0, n - 1)]
    srt = np.sort(ids, axis=1)
    dup = np.zeros_like(bad)
    dup[:, 1:] = srt[:, 1:] == srt[:, :-1]
    return bad.any(1) | dup.any(1), bad


def similarity(vectors, filters, lam, q, fq, ids, scores):
    k = ids.shape[1]
    ref = plain.Combined(vectors, filters, lam)
    _, want = ref.topk(q, fq, k)
    bad_row, bad_slot = _bad_rows(ids, scores, vectors.shape[0])
    hits = [len(set(a[~b]) & set(w)) for a, b, w in zip(ids, bad_slot, want)]
    got = ref.scores_of(q, fq, ids)
    err = np.where(bad_slot, 0.0, np.abs(scores.astype(np.float64) - got))
    return {"bad_answers": float(bad_row.sum()),
            "recall_miss": 1.0 - float(np.mean(hits)) / k,
            "score_err": float(err.max())}


def predicate(vectors, filters, alpha, ranges, q, ids, scores):
    k = ids.shape[1]
    ref = plain.Filtered(vectors, filters, alpha, ranges)
    _, want, near = ref.topk(q, k)
    bad_row, bad_slot = _bad_rows(ids, scores, vectors.shape[0], ref.elig)
    mismatch = (ids != want) & ~near
    d2 = ref.d2_of(q, ids)
    rel = np.abs(-scores.astype(np.float64) - d2) / np.maximum(1.0, d2)
    rel = np.where(bad_slot, 0.0, rel)
    return {"bad_answers": float(bad_row.sum()),
            "id_mismatch": float(mismatch.sum()),
            "d2_rel_err": float(rel.max())}


def verdict(numbers: dict, limits: dict):
    """(correct, [(name, value, limit)]): each number against its limit;
    a number above its limit, or one with no limit, fails."""
    rows, ok = [], True
    for name, value in numbers.items():
        lim = limits.get(name, {}).get("limit")
        passed = lim is not None and np.isfinite(value) and value <= lim
        ok &= bool(passed)
        rows.append((name, value, lim))
    return ok, rows

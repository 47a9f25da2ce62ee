"""Plain fp64 NumPy references of the two query semantics, independent of
the program: they take the raw corpus (as generated from the seed) and the
queries, and nothing the program computed.

- Similarity (``search(q, filters=fq)``): the paper's combined score
  lam * cos(v, q) + (1 - lam) * cos(f, F_q) over per-dimension standardized
  vectors and filters (mean and population std of the corpus, std + 1e-6),
  exact top-k.
- Predicate (``search(q, filter=...)``): exact top-k by squared L2 over the
  rows that satisfy the predicate, in the partition-transformed space
  psi(v, f) = [v^(1) - alpha f, ..., v^(d/m) - alpha f] of the standardized
  row, against the query folded with the predicate's representative filter
  point (interval midpoint on a constrained column, the column mean on the
  others); ties broken by id, near-ties flagged.
"""
from __future__ import annotations

import numpy as np

CHUNK = 1 << 15
STD_EPS = 1e-6


def moments(x: np.ndarray):
    """fp64 (mean, std + STD_EPS) per column, over row chunks."""
    n = x.shape[0]
    total = np.zeros(x.shape[1])
    for a in range(0, n, CHUNK):
        total += x[a:a + CHUNK].sum(0, dtype=np.float64)
    mean = total / n
    ss = np.zeros(x.shape[1])
    for a in range(0, n, CHUNK):
        dx = x[a:a + CHUNK].astype(np.float64) - mean
        ss += (dx * dx).sum(0)
    return mean, np.sqrt(ss / n) + STD_EPS


def _unit(x):
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-300)


class Combined:
    """Exact combined-score search over a raw corpus."""

    def __init__(self, vectors: np.ndarray, filters: np.ndarray, lam: float):
        self.v, self.f, self.lam = vectors, filters, float(lam)
        self.vm, self.vs = moments(vectors)
        self.fm, self.fs = moments(filters)

    def _q(self, q, fq):
        return (_unit((np.asarray(q, np.float64) - self.vm) / self.vs),
                _unit((np.asarray(fq, np.float64) - self.fm) / self.fs))

    def scores_of(self, q, fq, ids):
        """fp64 combined scores of rows ``ids`` (S, k) for each query."""
        qu, fu = self._q(q, fq)
        ids = np.clip(ids, 0, self.v.shape[0] - 1)
        vu = _unit((self.v[ids].astype(np.float64) - self.vm) / self.vs)
        fn = _unit((self.f[ids].astype(np.float64) - self.fm) / self.fs)
        return (self.lam * np.einsum("skd,sd->sk", vu, qu)
                + (1.0 - self.lam) * np.einsum("skm,sm->sk", fn, fu))

    def topk(self, q, fq, k):
        """(scores (S, k), ids (S, k)) of the exact top-k, best first."""
        qu, fu = self._q(q, fq)
        best_s = np.full((qu.shape[0], 0), -np.inf)
        best_i = np.zeros((qu.shape[0], 0), np.int64)
        for a in range(0, self.v.shape[0], CHUNK):
            vu = _unit((self.v[a:a + CHUNK].astype(np.float64) - self.vm)
                       / self.vs)
            fn = _unit((self.f[a:a + CHUNK].astype(np.float64) - self.fm)
                       / self.fs)
            s = self.lam * (qu @ vu.T) + (1.0 - self.lam) * (fu @ fn.T)
            ids = np.broadcast_to(np.arange(a, a + s.shape[1]), s.shape)
            best_s = np.concatenate([best_s, s], axis=1)
            best_i = np.concatenate([best_i, ids], axis=1)
            if best_s.shape[1] > k:
                keep = np.argpartition(-best_s, k - 1, axis=1)[:, :k]
                best_s = np.take_along_axis(best_s, keep, 1)
                best_i = np.take_along_axis(best_i, keep, 1)
        order = np.lexsort((best_i, -best_s), axis=-1)
        return (np.take_along_axis(best_s, order, 1),
                np.take_along_axis(best_i, order, 1))


def eligible(filters: np.ndarray, ranges) -> np.ndarray:
    """(n,) bool: every (column, lo, hi) of ``ranges`` holds, lo <= a <= hi,
    compared on the stored fp32 values."""
    ok = np.ones(filters.shape[0], bool)
    for col, lo, hi in ranges:
        a = filters[:, col]
        ok &= (a >= np.float32(lo)) & (a <= np.float32(hi))
    return ok


class Filtered:
    """Exact predicate-filtered L2 search over a raw corpus."""

    def __init__(self, vectors: np.ndarray, filters: np.ndarray, alpha: float,
                 ranges):
        self.v, self.f, self.alpha = vectors, filters, float(alpha)
        self.vm, self.vs = moments(vectors)
        self.fm, self.fs = moments(filters)
        self.elig = eligible(filters, ranges)
        self.ids = np.nonzero(self.elig)[0]
        fold = self.fm.copy()
        for col, lo, hi in ranges:
            fold[col] = 0.5 * (float(np.float32(lo)) + float(np.float32(hi)))
        self.fold_n = (fold - self.fm) / self.fs

    def _psi(self, vn, fn):
        d, m = vn.shape[-1], fn.shape[-1]
        t = vn.reshape(*vn.shape[:-1], d // m, m) - self.alpha * fn[..., None, :]
        return t.reshape(vn.shape)

    def rows(self, ids):
        vn = (self.v[ids].astype(np.float64) - self.vm) / self.vs
        fn = (self.f[ids].astype(np.float64) - self.fm) / self.fs
        return self._psi(vn, fn)

    def fold(self, q):
        qn = (np.asarray(q, np.float64) - self.vm) / self.vs
        return self._psi(qn, np.broadcast_to(self.fold_n, (qn.shape[0],
                                                           self.fold_n.size)))

    def d2_of(self, q, ids):
        """fp64 squared distances of rows ``ids`` (S, k) to folded queries."""
        qt = self.fold(q)
        r = self.rows(np.clip(ids, 0, self.v.shape[0] - 1))
        return ((r - qt[:, None, :]) ** 2).sum(-1)

    def topk(self, q, k):
        """(d2 (S, k), ids (S, k), near_tie (S, k)) of the exact filtered
        top-k by (d2, id). A slot is a near-tie when its d2 lies within
        1e-4 + 2e-6 * d2 of a neighbour's: fp32 sums of d terms may swap
        such rows."""
        qt = self.fold(q)
        d2 = np.empty((qt.shape[0], self.ids.shape[0]))
        for a in range(0, self.ids.shape[0], CHUNK):
            sel = self.ids[a:a + CHUNK]
            r = self.rows(sel)
            d2[:, a:a + sel.shape[0]] = (
                (qt * qt).sum(-1)[:, None] - 2.0 * (qt @ r.T)
                + (r * r).sum(-1)[None, :])
        order = np.lexsort((np.broadcast_to(self.ids, d2.shape), d2), axis=-1)
        sd2 = np.take_along_axis(d2, order, -1)[:, :k + 1]
        tol = 1e-4 + 2e-6 * np.abs(sd2)
        gap_prev = np.diff(sd2, axis=-1, prepend=-np.inf)
        gap_next = np.diff(sd2, axis=-1, append=np.inf)
        amb = (gap_prev < tol) | (gap_next < tol)
        return sd2[:, :k], self.ids[order[:, :k]], amb[:, :k]

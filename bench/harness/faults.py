"""Faults planted under the timed path, to show that ``correct`` catches
them (``tests/multi_run.py``; never used by a measured run).

- ``drop_half``: the answers to the second half of each call's requests are
  left out (returned empty: id -1, score -inf);
- ``alter``: each answer's best id is replaced by the next row's id where it
  is produced, its score kept.
"""
from __future__ import annotations

KINDS = ("drop_half", "alter")


class Faulty:
    def __init__(self, inner, kind: str, n_rows: int):
        if kind not in KINDS:
            raise ValueError(f"unknown fault {kind!r}; one of {KINDS}")
        self.inner, self.kind, self.n_rows = inner, kind, n_rows
        self.mode = inner.mode

    def serve(self, q, fq):
        s, d = self.inner.serve(q, fq)
        s, d = s.copy(), d.copy()
        if self.kind == "drop_half":
            h = q.shape[0] - q.shape[0] // 2
            d[h:], s[h:] = -1, float("-inf")
        else:
            d[:, 0] = (d[:, 0] + 1) % self.n_rows
        return s, d

    def counters(self):
        return self.inner.counters()

    def warm(self, wq, wfq, max_block):
        self.inner.warm(wq, wfq, max_block)

    def close(self):
        self.inner.close()

"""The control: the plain reference put in the program's place, computed in
bfloat16 (the precision next below the configuration's fp32).

It serves the same requests through the same traffic generator, on the device, and
must come out not correct. Standardization is fp32; the standardized
vectors, filters, transformed rows and queries are rounded to bfloat16, and
the products accumulate in fp32 (as the matrix unit does), so the top-k is
selected and returned on fp32 scores of bfloat16 data. (``dtype`` exists so that a test can show that the same code in
fp32 agrees with the fp64 reference: the control fails by its precision.)
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

STD_EPS = 1e-6


def _std(x):
    mean = jnp.mean(x, axis=0)
    return mean, jnp.std(x, axis=0) + STD_EPS


def _unit(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-30)


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


@partial(jax.jit, static_argnames=("k",))
def _similarity(vu, fu, vm, vs, fm, fs, q, fq, *, k, lam):
    dt = vu.dtype
    qu = _unit((q - vm) / vs).astype(dt)
    fqu = _unit((fq - fm) / fs).astype(dt)
    s = lam * _dot(qu, vu) + (1.0 - lam) * _dot(fqu, fu)
    return jax.lax.top_k(s, k)


@partial(jax.jit, static_argnames=("k",))
def _filtered(rows, sq, elig, qt, *, k):
    qb = qt.astype(rows.dtype)
    q2 = jnp.sum(qb.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    d2 = q2 - 2.0 * _dot(qb, rows) + sq[None, :]
    d2 = jnp.where(elig[None, :], d2, jnp.inf)
    return jax.lax.top_k(-d2, k)


class Control:
    """A ``Server`` (see ``harness/system.py``) answering with the bf16
    reference."""

    def __init__(self, cfg: dict, traffic: dict, vectors, filters_host,
                 ranges=None, dtype=jnp.bfloat16):
        self.k, self.bs = int(cfg["k"]), int(cfg["batch_size"])
        self.mode = traffic["mode"]
        self.lam = float(cfg["fcvi"]["lam"])
        f = jnp.asarray(filters_host)
        self.vm, self.vs = _std(vectors)
        self.fm, self.fs = _std(f)
        vn = (vectors - self.vm) / self.vs
        fn = (f - self.fm) / self.fs
        self.queries = 0
        if self.mode == "predicate":
            alpha = float(cfg["fcvi"].get("alpha", 1.0))
            d, m = vn.shape[1], fn.shape[1]
            rows = (vn.reshape(-1, d // m, m)
                    - alpha * fn[:, None, :]).reshape(-1, d)
            self.rows = rows.astype(dtype)
            self.sq = jnp.sum(self.rows.astype(jnp.float32) ** 2, axis=-1)
            a = np.asarray(filters_host)
            elig = np.ones(a.shape[0], bool)
            fold = np.asarray(jnp.mean(f, axis=0)).astype(np.float32)
            for col, lo, hi in ranges:
                elig &= (a[:, col] >= np.float32(lo)) & (a[:, col]
                                                         <= np.float32(hi))
                fold[col] = 0.5 * (np.float32(lo) + np.float32(hi))
            self.elig = jnp.asarray(elig)
            foldn = (jnp.asarray(fold) - self.fm) / self.fs
            self.fold_rep = (jnp.zeros((d // m, m)) + foldn).reshape(d)
            self.alpha = alpha
        else:
            self.vu = _unit(vn).astype(dtype)
            self.fu = _unit(fn).astype(dtype)

    def _batch(self, q, fq):
        if self.mode == "predicate":
            qt = (jnp.asarray(q) - self.vm) / self.vs - self.alpha * self.fold_rep
            return _filtered(self.rows, self.sq, self.elig, qt, k=self.k)
        return _similarity(self.vu, self.fu, self.vm, self.vs, self.fm,
                           self.fs, jnp.asarray(q), jnp.asarray(fq),
                           k=self.k, lam=self.lam)

    def serve(self, q, fq):
        n = q.shape[0]
        out_s = np.empty((n, self.k), np.float32)
        out_i = np.empty((n, self.k), np.int64)
        for a in range(0, n, self.bs):
            qb, fb = q[a:a + self.bs], fq[a:a + self.bs]
            pad = self.bs - qb.shape[0]
            if pad:
                qb = np.concatenate([qb, np.repeat(qb[-1:], pad, 0)])
                fb = np.concatenate([fb, np.repeat(fb[-1:], pad, 0)])
            s, i = self._batch(qb, fb)
            m = min(self.bs, n - a)
            out_s[a:a + m] = np.asarray(s)[:m]
            out_i[a:a + m] = np.asarray(i)[:m]
        self.queries += n
        return out_s, out_i

    def counters(self) -> dict:
        return {"queries": self.queries}

    def warm(self, wq, wfq, max_block: int):
        self.serve(wq[:1], wfq[:1])

    def close(self):
        self.__dict__.pop("rows", None)
        self.__dict__.pop("vu", None)

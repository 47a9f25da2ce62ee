"""Jit'd public wrappers for the Pallas kernels — the serving dispatch layer.

Every function takes a ``use_pallas`` switch: ``True`` runs the Pallas kernel
(compiled on TPU, interpret mode on the CPU, an error on any other backend),
``False`` runs the pure-jnp oracle from ``repro.kernels.ref``. The two paths
are semantically identical, so every call site can be A/B-checked (see
``tests/test_parity_pallas.py``).

These wrappers are the *actual* serving path, not a side demo: the index
backends dispatch here when ``FCVIConfig.use_pallas`` is set —

  * ``score_topk``        <- ``repro.index.flat.search`` (fused distance +
    running top-k over streamed corpus blocks),
  * ``ivf_score_topk_dedup`` <- ``repro.index.ivf.search`` (scalar-prefetch
    DMA over the grouped (nlist, max_list, d) slab layout, probe-major over
    the batch's deduplicated probed lists),
  * ``pq_score_batch``    <- ``repro.index.pq.search`` (one-hot-matmul ADC
    over the residual-PQ combined (coarse, code) LUT),
  * ``rescore``           <- ``repro.core.fcvi.rescore`` / ``multi_probe_query``
    (fused combined-cosine re-ranking),
  * ``fused_transform``   <- offline transform path.

Score conventions: ``score_topk`` returns full negative squared L2;
``ivf_score_topk*`` drops the ``||q||^2`` constant (the caller re-adds it);
``pq_score*`` returns squared distances.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.fcvi_transform import fused_transform as _fused_transform
from repro.kernels.fused_score_topk import score_topk as _score_topk
from repro.kernels.rescore import rescore as _rescore
from repro.kernels.ivf_score import (dedup_probes,
                                     ivf_score_topk as _ivf_score_topk,
                                     ivf_score_topk_batch as _ivf_score_topk_batch,
                                     ivf_score_topk_dedup as _ivf_score_topk_dedup)
from repro.kernels.pq_lut import (pq_lut_qdot as _pq_lut_qdot,
                                  pq_score as _pq_score,
                                  pq_score_batch as _pq_score_batch)


def _interpret() -> bool:
    """Pallas kernels compile natively on a TPU and run in interpret mode on
    the CPU (tests, local runs). Any other backend is an error: interpreting
    there would hide the device behind a slow emulation."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run on 'tpu' (compiled) or 'cpu' (interpreted); "
        f"the default backend is {backend!r}. Use use_pallas=False there.")


def fused_transform(v, f, proj, alpha, mean_v, std_v, mean_f, std_f,
                    *, use_pallas: bool = True, block_rows: int = 256):
    """Fused normalize+project+subtract. Rows are zero-padded to the kernel's
    block multiple and sliced back off, so any (n, d)/(n, m) shape works —
    this is what lets the QUERY path (arbitrary batch sizes) dispatch here,
    not just the offline corpus transform."""
    if not use_pallas:
        return ref.ref_fused_transform(v, f, proj, alpha, mean_v, std_v,
                                       mean_f, std_f)
    n = v.shape[0]
    br = min(block_rows, n)
    pad = -n % br
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad, v.shape[1]), v.dtype)], axis=0)
        f = jnp.concatenate([f, jnp.zeros((pad, f.shape[1]), f.dtype)], axis=0)
    out = _fused_transform(v, f, proj, alpha, mean_v, std_v, mean_f, std_f,
                           block_rows=br, interpret=_interpret())
    return out[:n]


def score_topk(corpus, sq_norms, queries, k, *, scales=None, mask=None,
               use_pallas: bool = True, block_rows: int = 128,
               block_q: int = 64):
    if not use_pallas:
        return ref.ref_score_topk(corpus, sq_norms, queries, k, scales=scales,
                                  mask=mask)
    return _score_topk(corpus, sq_norms, queries, k, scales=scales, mask=mask,
                       block_rows=block_rows, block_q=block_q,
                       interpret=_interpret())


def score_topk_padded(corpus, sq_norms, queries, k, *, scales=None, mask=None,
                      use_pallas: bool = True, block_rows: int = 128,
                      block_q: int = 64):
    """``score_topk`` for arbitrary shapes: zero-pads the queries to the
    kernel's query tile and slices the padding back off; the corpus goes in
    as it is (the kernel reads a ragged last row block in place, so no scan
    copies the slab). This is the dispatch used by flat candidate generation
    AND the IVF coarse quantizer (centroid scoring is just a small
    score_topk). ``mask`` (n,) float 0/1 routes to the filtered kernel
    variants (ineligible rows score -inf inside the scan)."""
    if not use_pallas:
        return ref.ref_score_topk(corpus, sq_norms, queries, k, scales=scales,
                                  mask=mask)
    n, d = corpus.shape
    nq = queries.shape[0]
    br = min(block_rows, n)
    bq = min(block_q, nq)
    q_pad = -nq % bq
    if q_pad:
        queries = jnp.concatenate(
            [queries, jnp.zeros((q_pad, d), queries.dtype)], axis=0)
    vals, idx = _score_topk(corpus, sq_norms, queries, k, scales=scales,
                            mask=mask, block_rows=br, block_q=bq,
                            interpret=_interpret())
    return vals[:nq], idx[:nq]


def rescore(cand_v, cand_f, qn, fqn, lam, *, use_pallas: bool = True,
            block_b: int = 8):
    """Candidates may arrive bf16 / int8-dequantized: both paths cast to
    fp32 up front so the cosine norms and dots accumulate at full precision
    (a no-op for fp32 inputs)."""
    cand_v = cand_v.astype(jnp.float32)
    cand_f = cand_f.astype(jnp.float32)
    qn = qn.astype(jnp.float32)
    fqn = fqn.astype(jnp.float32)
    if not use_pallas:
        return ref.ref_rescore(cand_v, cand_f, qn, fqn, lam)
    return _rescore(cand_v, cand_f, qn, fqn, lam, block_b=block_b,
                    interpret=_interpret())


def ivf_score_topk(grouped, grouped_sq, valid, probes, query, k, *,
                   scales=None, use_pallas: bool = True):
    if not use_pallas:
        return ref.ref_ivf_score_topk(grouped, grouped_sq, valid > 0.5,
                                      probes, query, k)
    return _ivf_score_topk(grouped, grouped_sq, valid, probes, query, k,
                           scales=scales, interpret=_interpret())


def ivf_score_topk_batch(grouped, grouped_sq, valid, probes, queries, k, *,
                         scales=None, use_pallas: bool = True):
    """Batched probed-slab search: probes (b, nprobe), queries (b, d)."""
    if not use_pallas:
        return ref.ref_ivf_score_topk_batch(grouped, grouped_sq, valid > 0.5,
                                            probes, queries, k, scales=scales)
    return _ivf_score_topk_batch(grouped, grouped_sq, valid, probes, queries,
                                 k, scales=scales, interpret=_interpret())


def ivf_score_topk_dedup(grouped, grouped_sq, valid, uniq, member, queries, k,
                         *, scales=None, mask=None, count_steps: bool = False,
                         use_pallas: bool = True):
    """Probe-major deduplicated batched slab search: uniq (s,), member (s, b),
    queries (b, d). Shared lists are DMA'd once per batch (see
    ``ivf_score.dedup_probes`` for building uniq/member from a probe matrix).
    ``mask`` (nlist, max_list) float 0/1 is the filter algebra's candidate
    mask, folded into the validity operand the kernel streams.
    ``count_steps`` also returns the kernel's selection steps (an int32
    scalar); the oracle takes none, so it needs ``use_pallas``.
    """
    if not use_pallas:
        if count_steps:
            raise ValueError("count_steps counts the Pallas kernel's "
                             "selection steps; it needs use_pallas=True")
        return ref.ref_ivf_score_topk_dedup(grouped, grouped_sq, valid > 0.5,
                                            uniq, member > 0.5, queries, k,
                                            scales=scales, mask=mask)
    return _ivf_score_topk_dedup(grouped, grouped_sq, valid, uniq, member,
                                 queries, k, scales=scales, mask=mask,
                                 count_steps=count_steps,
                                 interpret=_interpret())


def pq_score(codes, lut, *, use_pallas: bool = True, block_rows: int = 512):
    if not use_pallas:
        return ref.ref_pq_score(codes, lut)
    return _pq_score(codes, lut, block_rows=block_rows,
                     interpret=_interpret())


def pq_score_batch(codes, luts, *, use_pallas: bool = True,
                   block_rows: int = 256):
    """Multi-query ADC: codes (n, M), luts (q, M, ksub) -> (q, n) scores."""
    if not use_pallas:
        return ref.ref_pq_score_batch(codes, luts)
    return _pq_score_batch(codes, luts, block_rows=block_rows,
                           interpret=_interpret())


def pq_lut_qdot(queries_sub, codebooks, *, use_pallas: bool = True,
                block_q: int = 128):
    """PQ LUT construction's q.codebook cross term — the one matmul that
    dominates ``repro.index.pq.compute_luts``: queries_sub (q, M, dsub) x
    codebooks (M, ksub, dsub) -> (q, M, ksub)."""
    if not use_pallas:
        return ref.ref_pq_lut_qdot(queries_sub, codebooks)
    return _pq_lut_qdot(queries_sub, codebooks, block_q=block_q,
                        interpret=_interpret())

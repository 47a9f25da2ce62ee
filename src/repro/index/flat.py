"""Exact (flat) top-k search — the TPU-native 'HNSW replacement'.

Brute-force tiled matmul + running top-k is the roofline-optimal search
primitive on MXU hardware for per-device shards up to ~10M vectors: arithmetic
intensity of the distance matmul is d/2 FLOPs per corpus byte, which is
compute-bound for d >= ~512 at bf16 and keeps the MXU busy, unlike
pointer-chasing graph indexes.

Two candidate-generation paths, selected by ``use_pallas``:

  * jnp (default): one big matmul, or — with ``block_rows`` — a lax.scan that
    streams the corpus in row blocks with a running (value, index) top-k merge
    so the working set stays constant in N.
  * Pallas: ``repro.kernels.ops.score_topk``, the fused distance + running
    top-k kernel (queries are zero-padded to the kernel's query tile; the
    corpus is read in place, a ragged last row block included).

Both paths over-retrieve ``k + REFINE_PAD`` candidates and finish with an
exact refinement: the matmul expansion ||q||^2 - 2<q,x> + ||x||^2 loses
~1e-4 absolute precision at fp32 when norms are large (catastrophic
cancellation) and can misorder near-ties, so the retrieved rows are re-scored
with a direct (q - x)^2 pass, which restores exact ordering at O(q*k*d) cost.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.index import quant
from repro.kernels import ops

Array = jax.Array

# extra candidates fetched before the exact-refine pass; absorbs ordering
# flips at the top-k boundary caused by fp32 expansion error
REFINE_PAD = 8


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FlatIndex:
    """Corpus matrix + precomputed squared norms.

    ``scales`` is the int8 storage rung's per-row dequantization scale
    (None for float32/bfloat16 storage): stored rows dequantize as
    ``vectors.astype(f32) * scales[:, None]``.
    """

    vectors: Array   # (n, d) fp32 / bf16 / int8 codes
    sq_norms: Array  # (n,) fp32, of the (dequantized) stored rows
    scales: Optional[Array] = None  # (n,) fp32 per-row scales (int8 only)

    def tree_flatten(self):
        return (self.vectors, self.sq_norms, self.scales), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def size(self) -> int:
        return self.vectors.shape[0]

    @property
    def dim(self) -> int:
        return self.vectors.shape[1]

    def search(self, queries: Array, k: int, *, use_pallas: bool = False,
               **opts):
        """SearchBackend protocol entry point."""
        return search(self, queries, k, use_pallas=use_pallas, **opts)

    def slab(self):
        """The serving-layout view of this index (see ``repro.index.slab``):
        what the mesh-sharding and checkpoint layers consume."""
        from repro.index.slab import FlatSlab

        return FlatSlab(vectors=self.vectors, sq_norms=self.sq_norms,
                        scales=self.scales)


def build(vectors: Array, storage_dtype=None) -> FlatIndex:
    """``storage_dtype`` (bfloat16 or int8) stores the corpus at reduced
    precision for 2x / 4x effective HBM bandwidth on the scan. Squared norms
    are computed in fp32 FROM the stored (cast or dequantized) values, so
    candidate scores are exact for the stored corpus; the exact-refine pass
    then keeps top-k ordering correct w.r.t. the stored rows (accumulation
    stays fp32 throughout). int8 storage additionally carries one fp32
    scale per row (see ``repro.index.quant``)."""
    vectors = jnp.asarray(vectors)
    if quant.is_quantized(storage_dtype):
        codes, scales = quant.quantize_rows(vectors)
        return FlatIndex(vectors=codes, sq_norms=quant.sq_norms_of(codes, scales),
                         scales=scales)
    if storage_dtype is not None:
        vectors = vectors.astype(storage_dtype)
    return FlatIndex(vectors=vectors, sq_norms=row_sq_norms(vectors))


@jax.jit
def row_sq_norms(vectors: Array) -> Array:
    """fp32 squared row norms in one fused pass (no (n, d) temporary)."""
    return jnp.sum(vectors.astype(jnp.float32) ** 2, axis=-1)


def merge_topk(vals_a: Array, idx_a: Array, vals_b: Array, idx_b: Array, k: int):
    """Merge two score/index candidate sets into the joint top-k (max-score).

    The merge primitive shared by the blocked scan, the engine's delta merge,
    and the cross-shard tree merge (``distributed.merge_over_axis``), so it
    must stay total over shard-shaped inputs: candidate sets smaller than
    ``k`` (the output is padded with ``-inf`` scores / id 0, matching the
    backend convention for unfillable rows), all-padding inputs (``-inf``
    rows simply lose the merge), and duplicate ids across the two sets (both
    occurrences compete; callers that need set semantics dedup upstream, as
    ``multi_probe_query`` does — the engine's shard/delta id spaces are
    disjoint by construction).
    """
    vals = jnp.concatenate([vals_a, vals_b], axis=-1)
    idxs = jnp.concatenate([idx_a, idx_b], axis=-1)
    total = vals.shape[-1]
    if k > total:
        pad = k - total
        vals = jnp.concatenate(
            [vals, jnp.full((*vals.shape[:-1], pad), -jnp.inf, vals.dtype)],
            axis=-1)
        idxs = jnp.concatenate(
            [idxs, jnp.zeros((*idxs.shape[:-1], pad), idxs.dtype)], axis=-1)
    top_vals, pos = jax.lax.top_k(vals, k)
    return top_vals, jnp.take_along_axis(idxs, pos, axis=-1)


def _exact_refine(vectors: Array, queries: Array, cand_idx: Array, k: int,
                  mask: Optional[Array] = None,
                  scales: Optional[Array] = None):
    """Re-score gathered candidates with a direct (q - x)^2 pass, top-k.

    Runs in fp32 regardless of the storage dtype: bf16-stored rows are cast
    up and int8 rows are dequantized with their per-row ``scales``, so the
    refined ordering is exact w.r.t. the stored corpus."""
    rows = vectors[cand_idx].astype(jnp.float32)              # (q, kk, d)
    if scales is not None:
        rows = rows * scales[cand_idx][..., None]
    d2 = jnp.sum((queries[:, None, :] - rows) ** 2, axis=-1)
    if mask is not None:
        d2 = jnp.where(mask[cand_idx], d2, jnp.inf)
    vals, pos = jax.lax.top_k(-d2, k)
    return vals, jnp.take_along_axis(cand_idx, pos, axis=-1)


def _pallas_candidates(index: FlatIndex, queries: Array, kk: int,
                       block_rows: int = 128, block_q: int = 64) -> Array:
    """Candidate ids via the fused Pallas kernel (query padding handled by
    ops; the corpus is scanned in place)."""
    _, idx = ops.score_topk_padded(index.vectors, index.sq_norms, queries, kk,
                                   block_rows=block_rows, block_q=block_q,
                                   scales=index.scales)
    return idx


@partial(jax.jit, static_argnames=("k", "block_rows", "use_pallas"))
def search(index: FlatIndex, queries: Array, k: int, block_rows: int = 0,
           *, use_pallas: bool = False):
    """Top-k by squared-L2 (returned as NEGATIVE distance = score).

    queries: (q, d). Returns (scores (q,k), indices (q,k)).
    ``use_pallas`` routes candidate generation through the fused kernel.
    On the jnp path, ``block_rows`` > 0 streams the corpus in blocks of that
    many rows with a running top-k (bounded memory); 0 scores everything at
    once.
    """
    n = index.size
    k_out = min(k, n)
    kk = min(n, k_out + REFINE_PAD)

    if use_pallas:
        cand = _pallas_candidates(index, queries, kk)
        return _exact_refine(index.vectors, queries, cand, k_out,
                             scales=index.scales)

    q2 = jnp.sum(queries * queries, axis=-1, keepdims=True)

    def score_block(rows: Array, row_sq: Array,
                    row_scale: Optional[Array] = None) -> Array:
        # negative squared distance (higher is better); the per-row int8
        # scale multiplies the matmul OUTPUT column (same formula as the
        # Pallas kernel, so pallas/jnp stay in lockstep)
        dot = queries @ rows.astype(queries.dtype).T
        if row_scale is not None:
            dot = dot * row_scale[None, :]
        return -(q2 - 2.0 * dot + row_sq[None, :])

    if block_rows <= 0 or block_rows >= n:
        scores = score_block(index.vectors, index.sq_norms, index.scales)
        _, cand = jax.lax.top_k(scores, kk)
        return _exact_refine(index.vectors, queries, cand, k_out,
                             scales=index.scales)

    if n % block_rows != 0:
        raise ValueError(f"block_rows={block_rows} must divide n={n}")
    nblk = n // block_rows
    vecs = index.vectors.reshape(nblk, block_rows, index.dim)
    sqs = index.sq_norms.reshape(nblk, block_rows)
    scls = (None if index.scales is None
            else index.scales.reshape(nblk, block_rows))
    kb = min(kk, block_rows)

    def body(carry, blk):
        run_vals, run_idx = carry
        rows, row_sq, row_scale, blk_id = blk
        s = score_block(rows, row_sq, row_scale)
        v, i = jax.lax.top_k(s, kb)
        i = i + blk_id * block_rows
        return merge_topk(run_vals, run_idx, v, i, kk), None

    init_vals = jnp.full((queries.shape[0], kk), -jnp.inf, queries.dtype)
    init_idx = jnp.zeros((queries.shape[0], kk), jnp.int32)
    blk_ids = jnp.arange(nblk)
    if scls is None:
        def body_ns(carry, blk):
            rows, row_sq, blk_id = blk
            return body(carry, (rows, row_sq, None, blk_id))
        (_, cand), _ = jax.lax.scan(
            body_ns, (init_vals, init_idx), (vecs, sqs, blk_ids))
    else:
        (_, cand), _ = jax.lax.scan(
            body, (init_vals, init_idx), (vecs, sqs, scls, blk_ids))
    return _exact_refine(index.vectors, queries, cand, k_out,
                         scales=index.scales)



@partial(jax.jit, static_argnames=("k", "use_pallas"))
def search_masked(index: FlatIndex, queries: Array, k: int, mask: Array,
                  *, use_pallas: bool = False):
    """Exact search restricted to ``mask`` (pre-filtering primitive).

    mask: (n,) bool — True rows are eligible. Ineligible rows score -inf.
    ``use_pallas`` routes candidate generation through the masked variant of
    the fused scan kernel (the mask rides in as a kernel operand).
    """
    n = index.size
    k_out = min(k, n)
    kk = min(n, k_out + REFINE_PAD)
    if use_pallas:
        _, cand = ops.score_topk_padded(
            index.vectors, index.sq_norms, queries, kk, scales=index.scales,
            mask=mask.astype(jnp.float32))
    else:
        q2 = jnp.sum(queries * queries, axis=-1, keepdims=True)
        dot = queries @ index.vectors.astype(queries.dtype).T
        if index.scales is not None:
            dot = dot * index.scales[None, :]
        scores = -(q2 - 2.0 * dot + index.sq_norms[None, :])
        scores = jnp.where(mask[None, :], scores, -jnp.inf)
        _, cand = jax.lax.top_k(scores, kk)
    vals, idx = _exact_refine(index.vectors, queries, cand, k_out, mask=mask,
                              scales=index.scales)
    return jnp.where(jnp.isinf(vals), -jnp.inf, vals), idx


# ---------------------------------------------------------------------------
# Filtered refine: the shared exactness anchor of the filter-algebra plans
# ---------------------------------------------------------------------------
#
# Every physical plan (psi fold / in-kernel mask / routed pruning, meshless
# or sharded) finishes through these primitives, which compute per-row fp32
# squared distances with ONE canonical elementwise expression and break ties
# deterministically by (distance, id). Identical candidate rows therefore
# produce identical bits under every plan and topology — candidate
# generation only has to guarantee the true filtered top-k is IN the
# candidate set, never how it is ordered.

#: id sentinel for dead (ineligible / unfilled) slots while sorting; maps to
#: -1 in the final output. Sorts after every real id at equal key.
DEAD_ID = jnp.iinfo(jnp.int32).max


def filtered_d2(queries: Array, rows: Array) -> Array:
    """Canonical fp32 squared distance: queries (b, d) x rows (b, c, d) or
    (c, d) -> (b, c). Pure elementwise subtract/multiply + minor-axis sum —
    no dot_general — so every plan computes the same bits for the same row.
    """
    if rows.ndim == 2:
        rows = rows[None, :, :]
    diff = queries[:, None, :].astype(jnp.float32) - rows
    return jnp.sum(diff * diff, axis=-1)


def lexsort_topk(d2: Array, ids: Array, k: int):
    """Smallest-k by (d2 asc, id asc) along the last axis; pads with
    (+inf, DEAD_ID) when fewer than ``k`` entries exist."""
    c = d2.shape[-1]
    if c < k:
        pad = k - c
        d2 = jnp.concatenate(
            [d2, jnp.full((*d2.shape[:-1], pad), jnp.inf, d2.dtype)], axis=-1)
        ids = jnp.concatenate(
            [ids, jnp.full((*ids.shape[:-1], pad), DEAD_ID, ids.dtype)],
            axis=-1)
    d2s, idss = jax.lax.sort((d2, ids), dimension=-1, num_keys=2)
    return d2s[..., :k], idss[..., :k]


def finalize_filtered(d2: Array, ids: Array):
    """(d2, ids) -> (scores, ids) in the filtered-result convention:
    scores = -d2, dead slots = (-inf, -1)."""
    dead = jnp.isinf(d2)
    return (jnp.where(dead, -jnp.inf, -d2),
            jnp.where(dead, jnp.int32(-1), ids))


def masked_candidates(index: FlatIndex, queries: Array, kk: int, elig: Array,
                      *, use_pallas: bool = False):
    """Masked-scan candidate generation for the filter algebra's mask plan:
    the (n,) eligibility mask rides into the fused kernel as an operand, so
    ineligible rows score -inf inside the scan. Returns (cand (b, kk) corpus
    ids, valid (b, kk) bool) for ``filtered_refine``."""
    vals, cand = ops.score_topk_padded(
        index.vectors, index.sq_norms, queries, kk, scales=index.scales,
        mask=elig.astype(jnp.float32), use_pallas=use_pallas)
    return jnp.maximum(cand, 0), ~jnp.isneginf(vals)


def filtered_refine(vectors: Array, scales: Optional[Array], queries: Array,
                    cand_idx: Array, cand_valid: Array, elig: Array, k: int):
    """Exact filtered top-k over a candidate set.

    cand_idx: (b, c) corpus ids (valid entries must be duplicate-free);
    cand_valid: (b, c) bool (False = unfilled scan slot); elig: (n,) bool
    row eligibility. Ineligible/invalid candidates get (+inf, DEAD_ID) and
    the survivors sort by (exact fp32 d2, id). Returns (d2 (b, k),
    ids (b, k)) — callers finish with ``finalize_filtered``.
    """
    rows = vectors[cand_idx].astype(jnp.float32)              # (b, c, d)
    if scales is not None:
        rows = rows * scales[cand_idx][..., None]
    d2 = filtered_d2(queries, rows)
    ok = cand_valid & elig[cand_idx]
    d2 = jnp.where(ok, d2, jnp.inf)
    ids = jnp.where(ok, cand_idx.astype(jnp.int32), DEAD_ID)
    return lexsort_topk(d2, ids, k)

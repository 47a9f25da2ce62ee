"""IVF index — k-means coarse quantizer + dual list layouts.

TPU adaptation of FAISS-IVF with two materialisations of the inverted lists:

  * ``lists`` (nlist, max_list) int32 corpus ids, -1 pad — the compact
    id layout used by ``add()``/compaction and for translating slab
    positions back to corpus rows.
  * ``grouped`` (nlist, max_list, d) dense slab of the corpus rows grouped
    by list (plus ``grouped_sq``/``valid``) — the SERVING layout, built once
    at ``build()`` time. Probing a list is then a contiguous slab DMA, which
    is exactly what the scalar-prefetch ``ivf_score`` Pallas kernel wants:
    the probe ids picked by the coarse quantizer index the BlockSpec
    index_map directly, so no per-row gather ever happens on the hot path.

Sub-linear cost: each query scores nprobe/nlist of the corpus.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.clustering import kmeans, assign
from repro.index import quant
from repro.index.flat import row_sq_norms
from repro.index.slab import build_grouped
from repro.kernels import ops

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class IVFIndex:
    vectors: Array     # (n, d) corpus (transformed space)
    sq_norms: Array    # (n,)
    centroids: Array   # (nlist, d)
    lists: Array       # (nlist, max_list) int32 corpus ids, -1 pad
    list_sizes: Array  # (nlist,)
    grouped: Array     # (nlist, max_list, d) corpus grouped by list (serving)
    grouped_sq: Array  # (nlist, max_list)
    valid: Array       # (nlist, max_list) float 0/1 (1 = real row)
    scales: Optional[Array] = None          # (n,) int8 per-row scales
    grouped_scales: Optional[Array] = None  # (nlist, max_list)

    def tree_flatten(self):
        return (self.vectors, self.sq_norms, self.centroids, self.lists,
                self.list_sizes, self.grouped, self.grouped_sq,
                self.valid, self.scales, self.grouped_scales), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def size(self) -> int:
        return self.vectors.shape[0]

    @property
    def nlist(self) -> int:
        return self.centroids.shape[0]

    @property
    def max_list(self) -> int:
        return self.lists.shape[1]

    def search(self, queries: Array, k: int, *, use_pallas: bool = False,
               **opts):
        """SearchBackend protocol entry point."""
        return search(self, queries, k, use_pallas=use_pallas, **opts)

    def slab(self):
        """The serving-layout view of this index (see ``repro.index.slab``):
        what the mesh-sharding and checkpoint layers consume."""
        from repro.index.slab import IVFSlab

        return IVFSlab(centroids=self.centroids, lists=self.lists,
                       grouped=self.grouped, grouped_sq=self.grouped_sq,
                       valid=self.valid, grouped_scales=self.grouped_scales)


# serving-layout materialisation lives with the layout type in index.slab
_grouped_slabs = build_grouped


def build(vectors: Array, nlist: int, rng: Array | None = None,
          iters: int = 15, pad_to_multiple: int = 8,
          storage_dtype=None) -> IVFIndex:
    """Train coarse quantizer and materialise both list layouts (host-side).

    ``storage_dtype`` (bfloat16 or int8) stores the corpus + serving slabs at
    reduced precision (2x / 4x effective HBM bandwidth on the probed scans);
    the quantizer is always trained in fp32 and squared norms are fp32
    computed FROM the stored (cast or dequantized) values, so slab scores
    stay exact for the stored rows. int8 additionally carries per-row scales
    in both layouts (``scales`` row-aligned, ``grouped_scales`` grouped)."""
    vectors = jnp.asarray(vectors, jnp.float32)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    centroids, labels = kmeans(rng, vectors, nlist, iters=iters)
    labels_np = np.asarray(labels)
    buckets = [np.nonzero(labels_np == j)[0] for j in range(nlist)]
    max_list = max(1, max(len(b) for b in buckets))
    if max_list % pad_to_multiple:
        max_list += pad_to_multiple - max_list % pad_to_multiple
    lists = np.full((nlist, max_list), -1, np.int32)
    sizes = np.zeros((nlist,), np.int32)
    for j, b in enumerate(buckets):
        lists[j, : len(b)] = b
        sizes[j] = len(b)
    lists = jnp.asarray(lists)
    scales = grouped_scales = None
    if quant.is_quantized(storage_dtype):
        vectors, scales = quant.quantize_rows(vectors)
        sq_norms = quant.sq_norms_of(vectors, scales)
    else:
        if storage_dtype is not None:
            vectors = vectors.astype(storage_dtype)
        sq_norms = row_sq_norms(vectors)
    grouped, grouped_sq, valid = _grouped_slabs(vectors, sq_norms, lists)
    if scales is not None:
        grouped_scales = _group_scales(scales, lists)
    return IVFIndex(
        vectors=vectors,
        sq_norms=sq_norms,
        centroids=centroids,
        lists=lists,
        list_sizes=jnp.asarray(sizes),
        grouped=grouped,
        grouped_sq=grouped_sq,
        valid=valid,
        scales=scales,
        grouped_scales=grouped_scales,
    )


def _group_scales(scales: Array, lists: Array) -> Array:
    """Group per-row scales by list like ``build_grouped`` groups rows
    (invalid slots get scale 1.0 — they are masked by ``valid`` anyway,
    but a unit scale keeps any dequant of them finite)."""
    safe = jnp.maximum(lists, 0)
    return jnp.where(lists >= 0, scales[safe], 1.0)


@partial(jax.jit, static_argnames=("k", "nprobe", "use_pallas"))
def search(index: IVFIndex, queries: Array, k: int, nprobe: int = 8,
           *, use_pallas: bool = False):
    """Probe the nprobe nearest lists per query; exact scoring inside lists.

    Returns (scores (q,k), indices (q,k)); scores are negative squared L2.
    ``use_pallas`` runs the whole probe step on kernels: the coarse quantizer
    is a small ``ops.score_topk_padded`` call (centroid scoring is just a
    tiny flat search), and the probed lists are deduplicated across the query
    batch and scored probe-major by ``ops.ivf_score_topk_dedup`` so a slab
    shared by many queries is DMA'd from HBM once per batch.
    """
    nprobe = min(nprobe, index.nlist)
    q2 = jnp.sum(queries * queries, axis=-1, keepdims=True)
    c2 = jnp.sum(index.centroids * index.centroids, axis=-1)

    if use_pallas:
        _, probe = ops.score_topk_padded(index.centroids, c2, queries, nprobe)
        uniq, member = ops.dedup_probes(probe.astype(jnp.int32), index.nlist)
        vals, flat_ids = ops.ivf_score_topk_dedup(
            index.grouped, index.grouped_sq, index.valid, uniq, member,
            queries, k, scales=index.grouped_scales)
        cand = index.lists.reshape(-1)[flat_ids]        # -1 on padded slots
        vals = vals - q2                                # back to -||q - x||^2
        idx = jnp.where(jnp.isneginf(vals), 0, jnp.maximum(cand, 0))
        return vals, idx

    cd = -(q2 - 2.0 * queries @ index.centroids.T + c2[None, :])  # (q, nlist)
    _, probe = jax.lax.top_k(cd, nprobe)  # (q, nprobe)

    def one_query(qv, q_sq, probes):
        cand = index.lists[probes].reshape(-1)            # (nprobe*max_list,)
        valid = cand >= 0
        safe = jnp.where(valid, cand, 0)
        rows = index.vectors[safe]                        # (c, d)
        row_sq = index.sq_norms[safe]
        dot = rows.astype(qv.dtype) @ qv
        if index.scales is not None:
            dot = dot * index.scales[safe]
        s = -(q_sq - 2.0 * dot + row_sq)
        s = jnp.where(valid, s, -jnp.inf)
        kk = min(k, s.shape[0])
        v, p = jax.lax.top_k(s, kk)
        idx = safe[p]
        if kk < k:
            v = jnp.pad(v, (0, k - kk), constant_values=-jnp.inf)
            idx = jnp.pad(idx, (0, k - kk))
        return v, idx

    return jax.vmap(one_query)(queries, q2[:, 0], probe)



# ---------------------------------------------------------------------------
# Filter-algebra candidate generation (mask / routed plans)
# ---------------------------------------------------------------------------

def grouped_mask(index: IVFIndex, elig: Array) -> Array:
    """Row eligibility (n,) bool -> the grouped-layout candidate mask
    (nlist, max_list) float 0/1 the dedup kernel streams (pad slots 0)."""
    safe = jnp.maximum(index.lists, 0)
    return (elig[safe] & (index.lists >= 0)).astype(jnp.float32)


def masked_candidates(index: IVFIndex, queries: Array, kk: int, elig: Array,
                      *, use_pallas: bool = False):
    """Exhaustive masked scan over ALL lists — the mask plan's candidate
    generator. Every eligible row competes (uniq = all list ids, full
    member matrix), ineligible rows score -inf in-kernel. Returns
    (cand (b, kk') corpus ids, valid (b, kk') bool) for ``filtered_refine``.
    """
    nlist = index.nlist
    kk = min(kk, nlist * index.max_list)
    uniq = jnp.arange(nlist, dtype=jnp.int32)
    member = jnp.ones((nlist, queries.shape[0]), jnp.float32)
    vals, flat_ids = ops.ivf_score_topk_dedup(
        index.grouped, index.grouped_sq, index.valid, uniq, member, queries,
        kk, scales=index.grouped_scales, mask=grouped_mask(index, elig),
        use_pallas=use_pallas)
    cand = index.lists.reshape(-1)[flat_ids]
    return jnp.maximum(cand, 0), ~jnp.isneginf(vals)


def routed_candidates(index: IVFIndex, queries: Array, kk: int, elig: Array,
                      uniq: Array, n_live, *, use_pallas: bool = False):
    """Masked scan restricted to a routed list set — the routed plan's
    candidate generator: only lists holding at least one eligible row are
    scanned (the rest of the corpus is pruned, never DMA'd).

    uniq: (slots,) int32 list ids, tail slots repeating a live id (the
    pow-2 padding from ``eligible_lists``); n_live: scalar count of live
    slots (data — the slot-bucket SIZE is the only static part, so routed
    predicates share traces per bucket). Returns (cand, valid) like
    ``masked_candidates``; exhaustive over the routed lists' eligible rows.
    """
    b = queries.shape[0]
    slots = uniq.shape[0]
    kk = min(kk, slots * index.max_list)
    member = ((jnp.arange(slots)[:, None] < n_live)
              .astype(jnp.float32) * jnp.ones((1, b), jnp.float32))
    vals, flat_ids = ops.ivf_score_topk_dedup(
        index.grouped, index.grouped_sq, index.valid, uniq, member, queries,
        kk, scales=index.grouped_scales, mask=grouped_mask(index, elig),
        use_pallas=use_pallas)
    cand = index.lists.reshape(-1)[flat_ids]
    return jnp.maximum(cand, 0), ~jnp.isneginf(vals)


def eligible_lists(lists_np: np.ndarray, elig_np: np.ndarray):
    """Host-side routing: which inverted lists hold >= 1 eligible row.

    Returns (uniq (slots,) int32, n_live int) with slots the next power of
    two >= n_live (tail repeats the first live id, masked by ``n_live`` in
    the traced member matrix), or None when no list qualifies (the caller
    short-circuits to an all-empty certified result).
    """
    lists_np = np.asarray(lists_np)
    elig_np = np.asarray(elig_np, bool)
    safe = np.maximum(lists_np, 0)
    has = (elig_np[safe] & (lists_np >= 0)).any(axis=1)
    ids = np.nonzero(has)[0].astype(np.int32)
    n_live = int(ids.shape[0])
    if n_live == 0:
        return None
    slots = 1 << max(0, int(n_live - 1).bit_length())
    uniq = np.full((slots,), ids[0], np.int32)
    uniq[:n_live] = ids
    return uniq, n_live


def add(index: IVFIndex, new_vectors: Array) -> IVFIndex:
    """Incremental insert (host-side rebuild of the padded lists).

    Centroids are kept fixed (standard IVF practice); lists regrow and the
    serving slabs are re-materialised. The serving engine batches adds
    through a delta buffer and calls this on compaction, so the O(n) rebuild
    amortises.
    """
    new_vectors = jnp.asarray(new_vectors, jnp.float32)
    labels = assign(new_vectors, index.centroids)
    if index.scales is not None:
        new_codes, new_scales = quant.quantize_rows(new_vectors)
        all_vecs = jnp.concatenate([index.vectors, new_codes], axis=0)
        all_scales = jnp.concatenate([index.scales, new_scales], axis=0)
    else:
        all_vecs = jnp.concatenate(
            [index.vectors, new_vectors.astype(index.vectors.dtype)], axis=0)
        all_scales = None
    labels_np = np.asarray(labels)
    lists_np = np.asarray(index.lists)
    sizes_np = np.asarray(index.list_sizes).copy()
    nlist, max_list = lists_np.shape
    need = sizes_np.copy()
    for lbl in labels_np:
        need[lbl] += 1
    new_max = max(max_list, int(need.max()))
    if new_max % 8:
        new_max += 8 - new_max % 8
    out = np.full((nlist, new_max), -1, np.int32)
    out[:, :max_list] = lists_np
    base = index.vectors.shape[0]
    for i, lbl in enumerate(labels_np):
        out[lbl, sizes_np[lbl]] = base + i
        sizes_np[lbl] += 1
    lists = jnp.asarray(out)
    if all_scales is not None:
        sq_norms = quant.sq_norms_of(all_vecs, all_scales)
    else:
        sq_norms = jnp.sum(all_vecs.astype(jnp.float32) ** 2, axis=-1)
    grouped, grouped_sq, valid = _grouped_slabs(all_vecs, sq_norms, lists)
    grouped_scales = (None if all_scales is None
                      else _group_scales(all_scales, lists))
    return IVFIndex(
        vectors=all_vecs,
        sq_norms=sq_norms,
        centroids=index.centroids,
        lists=lists,
        list_sizes=jnp.asarray(sizes_np),
        grouped=grouped,
        grouped_sq=grouped_sq,
        valid=valid,
        scales=all_scales,
        grouped_scales=grouped_scales,
    )

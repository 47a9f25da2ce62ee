"""Distributed (multi-device / multi-pod) search via shard_map.

The corpus is row-sharded over one or more mesh axes; every device scores its
shard locally (flat or IVF) and the per-shard top-k candidates are merged with
a tree of all-gathers — one merge stage per mesh axis, so cross-pod traffic is
only the (k x devices-per-axis) candidate sets, never raw scores.

Filter-centric placement (beyond-paper): since psi() already arranges the
corpus into filter clusters, we can shard BY cluster so most queries touch a
few shards; `cluster_sharded_layout` computes that permutation and
`routed_search` masks non-probed shards to skip their matmul.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.clustering import assign
from repro.index import flat as flat_mod

Array = jax.Array


def _local_search(vectors: Array, sq_norms: Array, queries: Array, k: int,
                  row_offset: Array):
    """Exact local top-k with globally valid row ids."""
    q2 = jnp.sum(queries * queries, axis=-1, keepdims=True)
    scores = -(q2 - 2.0 * queries @ vectors.T + sq_norms[None, :])
    vals, idx = jax.lax.top_k(scores, min(k, vectors.shape[0]))
    return vals, idx + row_offset


def linear_shard_index(axes: Sequence[str], sizes: Sequence[int]):
    """This device's linear shard index over the (row-major) product axes.

    The linearization matches how ``PartitionSpec((axes,))`` lays out dim-0
    blocks over the mesh, so ``row // n_local == linear_shard_index`` holds
    for contiguously row-sharded arrays — the ownership convention shared by
    the distributed gather, the tree merge offsets and the shard router.
    Must be called inside a ``shard_map`` body over ``axes``.
    """
    lin = jnp.int32(0)
    stride = 1
    for ax, n_ax in zip(reversed(tuple(axes)), reversed(tuple(sizes))):
        lin = lin + jax.lax.axis_index(ax) * stride
        stride = stride * n_ax
    return lin


def merge_over_axis(vals: Array, idx: Array, axis: str, k: int):
    """All-gather candidate sets over one mesh axis and reduce to top-k.

    The shard-aware merge stage: gathers the (n_ax, q, kl) candidate sets of
    every shard along ``axis`` and runs ``flat.merge_topk`` over the pooled
    columns (one concatenation + one top-k), so the merged output inherits
    merge_topk's padding semantics (-inf fill when k exceeds the pool).
    """
    g_vals = jax.lax.all_gather(vals, axis)  # (n_ax, q, kl)
    g_idx = jax.lax.all_gather(idx, axis)
    n_ax = g_vals.shape[0]
    g_vals = jnp.moveaxis(g_vals, 0, -2).reshape(*vals.shape[:-1], n_ax * vals.shape[-1])
    g_idx = jnp.moveaxis(g_idx, 0, -2).reshape(*idx.shape[:-1], n_ax * idx.shape[-1])
    empty_v = g_vals[..., :0]
    empty_i = g_idx[..., :0]
    return flat_mod.merge_topk(g_vals, g_idx, empty_v, empty_i, k)


# internal name kept for existing call sites
_merge_over_axis = merge_over_axis


def tree_merge_topk(vals: Array, idx: Array, axes: Sequence[str],
                    sizes: Sequence[int], k: int):
    """Hierarchical cross-shard top-k merge: one exact merge stage per mesh
    axis (``sizes`` are the static mesh extents of ``axes``). Intermediate
    stages keep min(k, pool) candidates, so the final (replicated) result
    equals the global top-k over every shard's candidate set — the per-shard
    sets only need to contain their local winners."""
    for ax, n_ax in zip(reversed(tuple(axes)), reversed(tuple(sizes))):
        keep = min(k, n_ax * vals.shape[-1])
        vals, idx = merge_over_axis(vals, idx, ax, keep)
    if vals.shape[-1] < k:
        vals, idx = flat_mod.merge_topk(vals, idx, vals[..., :0],
                                        idx[..., :0], k)
    return vals, idx


def merge_over_axis_rows(vals: Array, idx: Array, rows: Sequence[Array],
                         axis: str, k: int):
    """``merge_over_axis`` that also carries per-candidate PAYLOAD ROWS.

    ``rows`` is a tuple of (..., kl, dim) arrays aligned with the candidate
    axis (e.g. the winners' re-rank vectors and filter values emitted by a
    shard-local scan). The (vals, idx) outputs are computed with exactly the
    same pooled top-k as ``merge_over_axis`` — bit-identical — and every
    rows array is gathered and selected with the same winner positions, so
    the merged candidates arrive WITH their rows and no cross-shard gather
    (mask + psum) is needed afterwards. Pool slots added when ``k`` exceeds
    the pool carry zero rows (matching the -inf / id-0 fill).
    """
    g_vals = jax.lax.all_gather(vals, axis)  # (n_ax, q, kl)
    g_idx = jax.lax.all_gather(idx, axis)
    n_ax = g_vals.shape[0]
    kl = vals.shape[-1]
    total = n_ax * kl
    g_vals = jnp.moveaxis(g_vals, 0, -2).reshape(*vals.shape[:-1], total)
    g_idx = jnp.moveaxis(g_idx, 0, -2).reshape(*idx.shape[:-1], total)
    if k > total:
        pad = k - total
        g_vals = jnp.concatenate(
            [g_vals, jnp.full((*g_vals.shape[:-1], pad), -jnp.inf,
                              g_vals.dtype)], axis=-1)
        g_idx = jnp.concatenate(
            [g_idx, jnp.zeros((*g_idx.shape[:-1], pad), g_idx.dtype)],
            axis=-1)
    top_vals, pos = jax.lax.top_k(g_vals, k)
    top_idx = jnp.take_along_axis(g_idx, pos, axis=-1)
    out_rows = []
    for r in rows:
        g = jax.lax.all_gather(r, axis)      # (n_ax, ..., kl, dim)
        g = jnp.moveaxis(g, 0, -3).reshape(*r.shape[:-2], total, r.shape[-1])
        if k > total:
            g = jnp.concatenate(
                [g, jnp.zeros((*g.shape[:-2], k - total, g.shape[-1]),
                              g.dtype)], axis=-2)
        out_rows.append(jnp.take_along_axis(g, pos[..., None], axis=-2))
    return top_vals, top_idx, tuple(out_rows)


def tree_merge_topk_rows(vals: Array, idx: Array, rows: Sequence[Array],
                         axes: Sequence[str], sizes: Sequence[int], k: int):
    """``tree_merge_topk`` carrying payload rows through every merge stage.

    Same staged reduction (and bit-identical (vals, idx)) as
    ``tree_merge_topk``; the rows ride along via ``merge_over_axis_rows``.
    This is the gather-free alternative to merging ids and then gathering
    rows with a masked psum: the all-gathers here move only (k x fan-in)
    candidate rows per stage, and no all-reduce appears in the trace.
    """
    rows = tuple(rows)
    for ax, n_ax in zip(reversed(tuple(axes)), reversed(tuple(sizes))):
        keep = min(k, n_ax * vals.shape[-1])
        vals, idx, rows = merge_over_axis_rows(vals, idx, rows, ax, keep)
    if vals.shape[-1] < k:
        pad = k - vals.shape[-1]
        vals = jnp.pad(vals, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        idx = jnp.pad(idx, ((0, 0), (0, pad)))
        rows = tuple(jnp.pad(r, ((0, 0), (0, pad), (0, 0))) for r in rows)
    return vals, idx, rows


def sharded_search_fn(mesh: Mesh, shard_axes: Sequence[str], k: int,
                      k_local: int = 0):
    """Build a shard_map'd exact search over a corpus sharded on shard_axes.

    Returns fn(vectors (n,d), sq_norms (n,), queries (q,d)) -> (vals, idx)
    with vectors/sq_norms sharded over rows and queries/output replicated.

    ``k_local`` > 0 truncates per-shard candidate sets before the merge tree
    (candidate-volume /= k/k_local). Statistically safe when k_local well
    exceeds k / n_shards x (merge fan-in): with row-sharded corpora the
    global top-k is spread ~uniformly, so a shard rarely owns more than a
    few winners.
    """
    axes = tuple(shard_axes)
    kl = k_local if k_local and k_local < k else k

    def local_fn(vectors, sq_norms, queries):
        # global row offset of this shard: row-major over the shard axes
        n_local = vectors.shape[0]
        offset = jnp.int32(0)
        stride = n_local
        for ax in reversed(axes):
            offset = offset + jax.lax.axis_index(ax) * stride
            stride = stride * jax.lax.axis_size(ax)
        vals, idx = _local_search(vectors, sq_norms, queries, kl, offset)
        # pad so merges are static even when shards are small
        if vals.shape[-1] < kl:
            pad = kl - vals.shape[-1]
            vals = jnp.pad(vals, ((0, 0), (0, pad)), constant_values=-jnp.inf)
            idx = jnp.pad(idx, ((0, 0), (0, pad)))
        # hierarchical merge: keep k_local until the LAST stage, then k
        for i, ax in enumerate(reversed(axes)):
            keep = k if i == len(axes) - 1 else kl
            vals, idx = _merge_over_axis(vals, idx, ax, keep)
        return vals, idx

    row_spec = P(axes)  # rows sharded over the product of axes
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(row_spec, row_spec, P()),
        out_specs=(P(), P()),
        check_vma=False,
    )


def affinity_group_layout(centers, sizes, n_shards: int,
                          slot_capacity: Optional[int] = None,
                          row_slack: float = 1.3):
    """Shard assignment for groups (psi-clusters / inverted lists) that packs
    NEARBY groups onto the SAME shard, subject to balance caps.

    ``centers``: (ng, d) group centers (numpy/jax, fp32); ``sizes``: (ng,)
    row counts. One region seed per shard is picked with a small k-means over
    the group centers; groups are then placed largest-first onto the nearest
    seed that still has free slot capacity (at most ``slot_capacity`` groups
    per shard) and row headroom (``row_slack`` x the mean shard load); a
    group no shard can take within the row cap falls back to the
    least-loaded shard with a free slot. Returns shard_of_group (ng,) int32.

    This is what makes routed serving skip shards: a query's co-probed
    groups sit in the same region of psi-space, so affinity packing puts
    them on few shards — the pure load-balance packers scatter them and
    every query ends up touching every shard.
    """
    import numpy as np

    from repro.core.clustering import kmeans

    centers = np.asarray(centers, np.float32)
    sizes = np.asarray(sizes, np.int64)
    ng = centers.shape[0]
    if n_shards <= 1:
        return np.zeros((ng,), np.int32)
    if ng <= n_shards:
        return np.arange(ng, dtype=np.int32) % n_shards
    seeds, _ = kmeans(jax.random.PRNGKey(0), jnp.asarray(centers), n_shards,
                      iters=10)
    seeds = np.asarray(seeds)
    d2 = np.sum((centers[:, None, :] - seeds[None]) ** 2, axis=-1)
    cap_rows = int(np.ceil(sizes.sum() / n_shards * row_slack))
    cap_slots = slot_capacity if slot_capacity is not None else ng
    load = np.zeros(n_shards, np.int64)
    used = np.zeros(n_shards, np.int64)
    shard_of = np.zeros(ng, np.int32)
    for g in np.argsort(-sizes, kind="stable"):
        placed = False
        for s in np.argsort(d2[g], kind="stable"):
            if used[s] < cap_slots and load[s] + sizes[g] <= cap_rows:
                shard_of[g] = s
                placed = True
                break
        if not placed:
            free = np.nonzero(used < cap_slots)[0]
            s = free[np.argmin(load[free])]
            shard_of[g] = s
        used[shard_of[g]] += 1
        load[shard_of[g]] += sizes[g]
    return shard_of


def cluster_sharded_layout(vectors: Array, centroids: Array, n_shards: int):
    """Permutation placing whole clusters on shards (filter-centric placement).

    Returns (perm, shard_of_cluster): ``vectors[perm]`` groups rows so that
    shard s holds the contiguous slice [s*n/n_shards, (s+1)*n/n_shards) and
    clusters are packed by CENTER AFFINITY (``affinity_group_layout``:
    nearby psi-clusters co-locate, which is what lets the routed serving
    step skip shards) under a row-load cap, then rebalanced to exact equal
    shard sizes by stealing overflow rows.
    """
    import numpy as np

    labels = np.asarray(assign(vectors, centroids))
    n = len(labels)
    nclusters = centroids.shape[0]
    sizes = np.bincount(labels, minlength=nclusters)
    shard_of_cluster = affinity_group_layout(centroids, sizes, n_shards)
    shard_members: list[list[int]] = [[] for _ in range(n_shards)]
    for c in range(nclusters):
        shard_members[shard_of_cluster[c]].extend(
            np.nonzero(labels == c)[0].tolist())
    # round-robin rebalance to exact equal shard sizes (pad via stealing)
    target = n // n_shards
    overflow: list[int] = []
    for s in range(n_shards):
        while len(shard_members[s]) > target:
            overflow.append(shard_members[s].pop())
    for s in range(n_shards):
        while len(shard_members[s]) < target and overflow:
            shard_members[s].append(overflow.pop())
    perm = np.concatenate([np.asarray(m, np.int64) for m in shard_members])
    return jnp.asarray(perm), jnp.asarray(shard_of_cluster)


def routed_search_fn(mesh: Mesh, shard_axes: Sequence[str], k: int,
                     degraded: bool = False):
    """Like sharded_search_fn but each shard is given a per-query probe mask.

    Per-query routed semantics: a query's candidates come ONLY from shards
    its ``probe_mask`` row selects (unselected shards contribute ``-inf``
    candidate rows). Shards no query in the batch routes to skip their scan
    entirely: the local matmul + top-k runs inside a ``lax.cond`` whose
    predicate is "any query probes me", so an unprobed shard executes the
    zero-work branch instead of a discarded matmul. The serving-engine
    counterpart — router computed in-trace from the slab's placement tables,
    with an exactness bound + dense fallback — is the routed batch step in
    ``repro.serve.sharded``.

    ``degraded=True`` adds one replicated input: an ``alive`` (n_shards,)
    bool mask, ANDed into the cond predicate so a dead shard takes the
    zero-work branch for EVERY query (dead == never-routed) and contributes
    only ``-inf`` rows — shard-loss-tolerant search over the survivors. The
    mask is a traced argument: marking more shards dead never recompiles.
    """
    axes = tuple(shard_axes)
    sizes = tuple(mesh.shape[a] for a in axes)

    def local_fn(vectors, sq_norms, queries, probe_mask, *rest):
        n_local = vectors.shape[0]
        lin = linear_shard_index(axes, sizes)
        offset = lin * n_local
        mine = probe_mask[:, lin]  # (q,)
        pred = jnp.any(mine)
        if degraded:
            alive = rest[0]
            mine = mine & alive[lin]
            pred = pred & alive[lin]
        kl = min(k, n_local)

        def scan(_):
            vals, idx = _local_search(vectors, sq_norms, queries, kl, offset)
            return jnp.where(mine[:, None], vals, -jnp.inf), idx

        def skip(_):
            return (jnp.full((queries.shape[0], kl), -jnp.inf, queries.dtype),
                    jnp.zeros((queries.shape[0], kl), jnp.int32) + offset)

        vals, idx = jax.lax.cond(pred, scan, skip, None)
        if vals.shape[-1] < k:
            pad = k - vals.shape[-1]
            vals = jnp.pad(vals, ((0, 0), (0, pad)), constant_values=-jnp.inf)
            idx = jnp.pad(idx, ((0, 0), (0, pad)))
        for ax in reversed(axes):
            vals, idx = _merge_over_axis(vals, idx, ax, k)
        return vals, idx

    in_specs = (P(axes), P(axes), P(), P())
    if degraded:
        in_specs = in_specs + (P(),)
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(), P()),
        check_vma=False,
    )

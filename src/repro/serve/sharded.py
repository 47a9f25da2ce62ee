"""Mesh-sharded serving: the engine batch step as a ``shard_map`` body.

``ShardedServing`` is the device-mesh counterpart of the single-device
``FCVIEngine`` hot path. The index's serving slab (``repro.index.slab``) is
sharded over the mesh — flat slabs by ROW, IVF slabs by LIST — together with
the normalized re-scoring originals and the engine's delta insert buffer, and
the whole per-batch computation runs as ONE jitted ``shard_map``:

  1. query transform: replicated (identical math on every shard; the fused
     Pallas kernel is preserved inside the shard when ``use_pallas`` is set),
  2. candidate generation: each shard scans only ITS slab block (kernel-backed
     when ``use_pallas``) and emits (score, global id) candidates,
  3. cross-shard merge: the per-axis tree top-k merge
     (``index.distributed.tree_merge_topk`` over ``flat.merge_topk``) reduces
     the per-shard sets to the exact global top-k' — only (k' x shards)
     candidate tuples ever cross the interconnect, never raw score matrices,
  4. re-ranking: candidate rows are fetched from the ROW-SHARDED normalized
     originals with a mask+psum distributed gather (each id is owned by
     exactly one shard; summing one real row with zeros is float-exact), then
     combined-scored exactly as the single-device path,
  5. delta merge: the per-shard delta buffer is searched locally, tree-merged,
     and folded in with the same shard-aware ``merge_topk``.

Parity contract: the sharded step returns results IDENTICAL to the
single-device ``engine._batch_step`` for any mesh shape (including 1 device)
— per-row arithmetic is unchanged, per-shard candidate sets provably contain
every global winner (a shard can hold at most k' of the global top-k', so
per-shard top-min(k', local) + exact tree merge loses nothing), and the
exact-refine / re-rank stages run on the same fp32 values.
``tests/test_sharded_engine.py`` enforces this on a forced 8-device host
mesh, kernels on and off.

Routed serving (``routing="routed"``): with filter-centric placement the
psi-transform makes filtered queries geometrically LOCAL — a query's
candidates concentrate on the few shards holding its nearby psi-clusters —
so the step additionally computes a per-query shard relevance mask IN-TRACE
and shards no query in the batch routes to skip candidate generation
entirely (the local scan runs inside a ``lax.cond``; the skipped branch
emits ``-inf`` candidates without touching the corpus slab):

  * IVF: a probed list is wholly owned by one shard
    (``ShardedIVFSlab.list_to_shard``), so masking shards that own none of a
    query's probed lists is EXACT — routed results equal dense-sharded
    results by construction, always.
  * flat (requires ``placement="cluster"``): the router probes the
    ``router_nprobe`` nearest psi-cluster centers and activates the shards
    holding their rows (``cluster_to_shard``). This can clip the dense
    top-k', so the step also emits a per-query soundness flag from the ball
    bound ||q - x|| >= ||q - mu_c|| - r_c over all clusters with rows on
    non-activated shards: if no clipped row can reach the k'-th routed
    candidate score, routed == dense bit-exactly; otherwise the engine
    re-runs the flagged queries through the dense step (the same sub-batch
    machinery as k' escalation), so end-to-end results stay identical.

The routed step returns two extra outputs — the flag and the (b, n_shards)
route mask — that the engine consumes OFF-trace for the fallback decision
and the router stats counters. ``route_signatures`` exposes the same router
rule host-side so the dispatch layer can sort a batch by shard-group
signature (co-routed queries land in the same padded batch, which is what
lets a shard's ``lax.cond`` actually skip).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import fcvi
from repro.index import flat as flat_mod
from repro.index import pq as pq_mod
from repro.index import slab as slab_mod
from repro.index.distributed import (linear_shard_index, tree_merge_topk,
                                     tree_merge_topk_rows)
from repro.kernels import ops

Array = jax.Array

# safety margin on the routed clipping check: the ball bound is exact in real
# arithmetic, but center distances / radii / refined candidate scores each
# carry ~1e-7-relative fp32 rounding. Scores are squared distances whose
# magnitude scales with the corpus, so the slack combines an absolute floor
# with a relative term (~100x fp32 eps) — conservative both near zero and on
# large-magnitude corpora (a few spurious dense fallbacks, never a missed
# one).
ROUTER_EPS = 1e-3
ROUTER_RTOL = 1e-5


def _gather_rows(local_rows: Array, gids: Array, lin, n_local: int, axes):
    """Distributed gather from a contiguously row-sharded array.

    ``local_rows`` is this shard's (n_local, ...) block of a (ns*n_local,
    ...) array; ``gids`` are global row ids (replicated). Each id is owned by
    exactly one shard: owners contribute the real row, everyone else zeros,
    and the psum reconstructs the gathered rows replicated. Adding zeros is
    float-exact, so the result is bit-identical to a local gather.
    """
    owner = gids // n_local
    mine = owner == lin
    loc = jnp.where(mine, gids % n_local, 0)
    part = jnp.where(mine[..., None], local_rows[loc], 0)
    for ax in axes:
        part = jax.lax.psum(part, ax)
    return part


def _local_flat_topk(vectors: Array, sq_norms: Array, row_ids: Array,
                     queries: Array, kl: int, use_pallas: bool,
                     scales: Optional[Array] = None):
    """Per-shard flat candidate generation with globally valid ids.

    Mirrors ``flat.search`` exactly (matmul-expansion candidate scores, then
    the fp32 exact-refine re-ordering), with padding rows (row_ids == -1,
    +inf squared norms) masked out of the refine so they can never outscore
    real rows. ``scales`` is the int8 storage rung's per-row dequant scale
    block (sharded like the slab; 1.0 on pads). Returns (vals, global ids,
    local slab positions) — the positions let the gather-free step pull the
    winners' re-rank payload rows from the shard-local payload block.
    """
    nl = vectors.shape[0]
    kl = min(kl, nl)
    kk = min(nl, kl + flat_mod.REFINE_PAD)
    if use_pallas:
        _, cand = ops.score_topk_padded(vectors, sq_norms, queries, kk,
                                        scales=scales)
    else:
        q2 = jnp.sum(queries * queries, axis=-1, keepdims=True)
        dot = queries @ vectors.astype(queries.dtype).T
        if scales is not None:
            dot = dot * scales[None, :]
        scores = -(q2 - 2.0 * dot + sq_norms[None, :])
        _, cand = jax.lax.top_k(scores, kk)
    vals, idx = flat_mod._exact_refine(vectors, queries, cand, kl,
                                       mask=row_ids >= 0, scales=scales)
    return vals, row_ids[idx], idx


def _cluster_bounds(q_t: Array, centers: Array, radii: Array):
    """Exact per-(query, cluster) center distances + ball-bound scores.

    Returns (d2 (b, ncl), ub (b, ncl)): ``ub`` is the best score (negative
    squared L2) any row of cluster c could reach for each query, from the
    triangle-inequality ball bound ||q - x|| >= ||q - mu_c|| - r_c. The
    distances are computed with the exact (non-expanded) formula — the bound
    must never be underestimated, so the matmul expansion's cancellation
    error is avoided. Shared by the shard router's clipping check and the
    degraded-mode coverage certificate.
    """
    d2 = jnp.sum(jnp.square(q_t[:, None, :] - centers[None]), axis=-1)
    ub = -jnp.square(jnp.maximum(jnp.sqrt(d2) - radii[None, :], 0.0))
    return d2, ub


def _flat_router(q_t: Array, centers: Array, radii: Array, incidence: Array,
                 router_nprobe: int, d2: Optional[Array] = None,
                 ub: Optional[Array] = None):
    """Per-query shard mask + clipping bound for cluster-placed flat slabs.

    q_t: (b, d) transformed queries; centers (ncl, d), radii (ncl,),
    incidence (ncl, ns) — the slab's routing tables. Probes the
    ``router_nprobe`` nearest psi-clusters per query and activates every
    shard holding rows of a probed cluster. Returns (route_mask (b, ns) bool,
    bound (b,)): ``bound`` is the best ball-bound score any row on a
    NON-activated shard could reach; the step compares it against the
    k'-th routed candidate to decide whether routing may have clipped.
    ``d2``/``ub`` accept precomputed ``_cluster_bounds`` output (the degraded
    step shares them with the coverage certificate).
    """
    ncl = centers.shape[0]
    r = min(router_nprobe, ncl)
    if d2 is None:
        d2, ub = _cluster_bounds(q_t, centers, radii)
    _, probe = jax.lax.top_k(-d2, r)
    probed = jnp.clip(
        jnp.sum(jax.nn.one_hot(probe, ncl, dtype=jnp.float32), axis=1),
        0.0, 1.0)                                            # (b, ncl)
    route_mask = (probed @ incidence) > 0.0                  # (b, ns)
    # clusters with at least one row on a non-activated shard may be clipped;
    # probed clusters never qualify (they activate all their shards)
    inactive = 1.0 - route_mask.astype(jnp.float32)
    clipped = (inactive @ incidence.T) > 0.0                 # (b, ncl)
    has_rows = jnp.sum(incidence, axis=-1) > 0.0             # (ncl,)
    bound = jnp.max(
        jnp.where(clipped & has_rows[None, :], ub, -jnp.inf), axis=-1)
    return route_mask, bound


@dataclasses.dataclass
class ShardedDelta:
    """Per-shard view of the engine's delta insert buffer (row-sharded)."""

    vt: Array       # (nd_pad, d) transformed delta rows, sharded
    sq: Array       # (nd_pad,) squared norms, +inf pads, sharded
    row_ids: Array  # (nd_pad,) delta-local ids, -1 pads, sharded
    vn: Array       # (nd_pad, d) normalized originals, sharded
    fn: Array       # (nd_pad, m) normalized filters, sharded
    nd: int         # real delta rows
    n_local: int    # rows per shard
    sc: Optional[Array] = None  # (nd_pad,) int8 dequant scales, 1.0 pads


class ShardedServing:
    """Sharded slabs + jitted shard_map steps for one (index, mesh) pair.

    Construction shards the serving state once (``slab.shard`` +
    row-sharding the re-rank originals); ``step`` lazily builds and caches
    one jitted shard_map per static (k, k', kd, delta-shape, routed)
    signature — exactly mirroring the jit cache structure of the
    single-device ``_batch_step``. ``routing="routed"`` enables the
    filter-routed step (see module docstring); on the flat backend it
    requires ``placement="cluster"``, and ``router_centers`` optionally pins
    the psi-cluster geometry (e.g. restored from a checkpoint so a restored
    engine routes identically).
    """

    def __init__(self, index, mesh, rules=None, *,
                 placement: str = "contiguous", routing: str = "dense",
                 router_nprobe: int = 0,
                 router_centers: Optional[Array] = None,
                 attrs: Optional[Array] = None):
        from repro.distributed.sharding import AxisRules

        if routing not in ("dense", "routed"):
            raise ValueError(
                f"routing must be 'dense' or 'routed', got {routing!r}")
        self.index = index
        self.mesh = mesh
        self.rules = rules if rules is not None else AxisRules(mesh)
        self.placement = placement
        self.routing = routing
        cfg = index.config
        if cfg.backend == "flat":
            if routing == "routed" and placement != "cluster":
                raise ValueError(
                    "routing='routed' on the flat backend requires "
                    "placement='cluster': the router needs the psi-cluster "
                    "ownership tables of filter-centric placement")
            self.slab = index.backend.slab().shard(
                mesh, self.rules, placement=placement, centers=router_centers,
                attrs=attrs)
        elif cfg.backend == "ivf":
            # "cluster" = filter-centric placement: affinity packing keeps a
            # query's co-probed lists on few shards (routing locality), where
            # plain "balanced" packing scatters them by load alone
            ivf_placement = "affinity" if placement == "cluster" else placement
            self.slab = index.backend.slab().shard(
                mesh, self.rules, placement=ivf_placement,
                list_sizes=index.backend.list_sizes, attrs=attrs)
        elif cfg.backend == "pq":
            if routing == "routed":
                raise ValueError(
                    "routing='routed' is not supported for the PQ backend: "
                    "ADC codes carry no per-shard routing geometry "
                    "(contiguous row placement only)")
            self.slab = index.backend.slab().shard(mesh, self.rules,
                                                   placement=placement)
        else:
            raise NotImplementedError(
                f"mesh-sharded serving supports the flat/ivf/pq backends, "
                f"not {cfg.backend!r}")
        self.axes = self.slab.axes
        self.sizes = tuple(mesh.shape[a] for a in self.axes)
        self.n_shards = slab_mod.axes_size(mesh, self.axes)
        # resolved flat-router probe count: default to ~two shards' worth of
        # psi-clusters — enough coverage that the clipping bound usually
        # certifies (few dense fallbacks) while localized filtered traffic
        # still leaves most shards unprobed
        if cfg.backend == "flat" and self.slab.router_centers is not None:
            ncl = self.slab.router_centers.shape[0]
            self.router_nprobe = (router_nprobe if router_nprobe > 0
                                  else max(1, (2 * ncl) // max(self.n_shards,
                                                               1)))
        else:
            self.router_nprobe = max(router_nprobe, 1)
        # normalized originals, contiguously row-sharded for the distributed
        # re-rank gather (independent of the slab's candidate placement)
        n = index.size
        self.rows_local = -(-n // max(self.n_shards, 1))
        n_pad = self.rows_local * self.n_shards
        self.vectors_n = self._put_rows(
            slab_mod.pad_dim0(index.vectors_n, n_pad, 0))
        self.filters_n = self._put_rows(
            slab_mod.pad_dim0(index.filters_n, n_pad, 0))
        self._steps = {}
        self._fsteps = {}      # filtered (predicate) steps, keyed (k, routed)
        self._payload = None   # gather-free payload slabs (lazy)

    def _put_rows(self, x: Array) -> Array:
        return jax.device_put(x, NamedSharding(self.mesh, P(self.axes)))

    # -- delta ------------------------------------------------------------
    def shard_delta(self, delta) -> ShardedDelta:
        """Shard the engine's device-resident delta buffer over the mesh."""
        nd = delta.vn.shape[0]
        nl = -(-nd // self.n_shards)
        nd_pad = nl * self.n_shards
        ids = jnp.concatenate(
            [jnp.arange(nd, dtype=jnp.int32),
             jnp.full((nd_pad - nd,), -1, jnp.int32)])
        sc = None
        if delta.flat.scales is not None:   # int8 delta: unit-scale pads
            sc = self._put_rows(
                slab_mod.pad_dim0(delta.flat.scales, nd_pad, 1.0))
        return ShardedDelta(
            vt=self._put_rows(
                slab_mod.pad_dim0(delta.flat.vectors, nd_pad, 0)),
            sq=self._put_rows(
                slab_mod.pad_dim0(delta.flat.sq_norms, nd_pad, jnp.inf)),
            row_ids=self._put_rows(ids),
            vn=self._put_rows(slab_mod.pad_dim0(delta.vn, nd_pad, 0)),
            fn=self._put_rows(slab_mod.pad_dim0(delta.fn, nd_pad, 0)),
            nd=nd, n_local=nl, sc=sc,
        )

    # -- dispatch-layer routing -------------------------------------------
    def route_signatures(self, q: np.ndarray, f: np.ndarray) -> np.ndarray:
        """Per-query active-shard bitmasks for dispatch-layer regrouping.

        q: (n, d) fp32 raw queries; f: (n, m) fp32 raw filter targets.
        Returns (n, ceil(n_shards/8)) uint8 packed bits (bit s set = the
        query routes to shard s), computed host-side with the same router
        rule the jitted routed step applies in-trace. Sorting a dispatch
        queue by signature groups co-routed queries into the same padded
        batch, which is what lets a shard's ``lax.cond`` skip fire.
        """
        idx = self.index
        cfg = idx.config
        qn, fqn = idx.transform.normalize(jnp.asarray(q), jnp.asarray(f))
        q_t = np.asarray(idx.transform.apply_normalized(qn, fqn), np.float32)
        n = q_t.shape[0]
        ns = self.n_shards
        mask = np.ones((n, ns), bool)
        # chunked, with the SAME distance formula and top-k tie-breaking as
        # the corresponding in-trace router (flat: exact diff; IVF jnp
        # coarse quantizer: matmul expansion), so the predicted signatures
        # match the step's route mask. The Pallas coarse kernel may break
        # exact centroid ties differently — grouping is best-effort there.
        chunk = 256   # bounds the flat (chunk, ncl, d) diff temporary
        if cfg.backend == "flat" and self.slab.router_centers is not None:
            c = np.asarray(self.slab.router_centers, np.float32)
            inc = np.asarray(self.slab.cluster_to_shard, np.float32)
            r = min(self.router_nprobe, c.shape[0])
            for s in range(0, n, chunk):
                qc = q_t[s:s + chunk]
                d2 = np.sum((qc[:, None, :] - c[None]) ** 2, axis=-1)
                probe = np.asarray(jax.lax.top_k(jnp.asarray(-d2), r)[1])
                probed = np.zeros((qc.shape[0], c.shape[0]), np.float32)
                probed[np.arange(qc.shape[0])[:, None], probe] = 1.0
                mask[s:s + chunk] = (probed @ inc) > 0.0
        elif cfg.backend == "ivf":
            c = np.asarray(self.slab.centroids, np.float32)
            c2 = np.asarray(self.slab.c_sq, np.float32)
            nprobe = min(cfg.nprobe, self.slab.nlist)
            l2s = np.asarray(self.slab.list_to_shard)
            for s in range(0, n, chunk):
                qc = q_t[s:s + chunk]
                q2 = np.sum(qc * qc, axis=-1, keepdims=True)
                cd = -(q2 - 2.0 * qc @ c.T + c2[None, :])
                probe = np.asarray(jax.lax.top_k(jnp.asarray(cd), nprobe)[1])
                m = np.zeros((qc.shape[0], ns), bool)
                m[np.arange(qc.shape[0])[:, None], l2s[probe]] = True
                mask[s:s + chunk] = m
        return np.packbits(mask, axis=1)

    # -- the sharded batch step -------------------------------------------
    def step(self, delta: Optional[ShardedDelta], q: Array, f: Array, *,
             k: int, kp: int, kd: int, routed: bool = False,
             alive: Optional[Array] = None, gather_free: bool = False):
        """One padded batch through the sharded hot path; same contract as
        ``engine._batch_step``: (scores (b, k), ids (b, k), margin (b,)).
        With ``routed=True`` two extra outputs follow: the per-query clipping
        flag (b,) bool (True = routing may have clipped the dense top-k';
        re-run dense) and the route mask (b, n_shards) bool.

        ``alive`` ((n_shards,) bool, or None = all healthy) switches to the
        DEGRADED step variant: shards marked dead take the zero-work
        ``lax.cond`` skip branch (dead == never-routed) and contribute no
        candidates, so results are exactly a search restricted to the
        surviving shards' slab rows; one more output ``uncovered`` (b,) bool
        follows (True = the dead shards could have held a top-k' candidate
        for this query — flat: psi-cluster ball-bound certificate; IVF:
        a probed list is owned by a dead shard; flat without routing tables:
        conservatively every query; PQ likewise). The mask is a TRACED
        argument, so marking further shards dead never retraces, and the
        healthy path's traces are untouched (separate jit-cache key).

        ``gather_free=True`` swaps the re-rank stage: each shard gathers its
        own winners' re-rank rows from its LOCAL payload block and computes
        their combined scores in place; the cross-shard merge then carries
        the finished scores (``tree_merge_topk_rows``) instead of one-hot
        psum-gathering rows after the merge — results stay bit-identical,
        but the step contains NO all-reduce collective.
        """
        degraded = alive is not None
        nld = None if delta is None else delta.n_local
        delta_scaled = delta is not None and delta.sc is not None
        key = (k, kp, kd, nld, routed, degraded, delta_scaled, gather_free)
        fn = self._steps.get(key)
        if fn is None:
            fn = self._steps[key] = self._build_step(
                k, kp, kd, nld, routed, degraded,
                delta_scaled=delta_scaled, gather_free=gather_free)
        args = (self.index.transform,) + self._slab_args(routed, degraded)
        if gather_free:
            args = args + self._rows_payload()
        else:
            args = args + (self.vectors_n, self.filters_n)
        if delta is not None:
            args = args + (delta.vt, delta.sq, delta.row_ids,
                           delta.vn, delta.fn)
            if delta_scaled:
                args = args + (delta.sc,)
        args = args + (q, f)
        if degraded:
            args = args + (jnp.asarray(alive, bool),)
        return fn(*args)

    def _rows_payload(self):
        """Slab-aligned re-rank payloads + replicated row-0 phantom rows for
        the gather-free step (lazy, cached): each shard re-ranks its own
        candidates from its OWN payload block instead of resolving ids
        through the mask+psum distributed gather. Flat: the normalized
        originals permuted into slab row order (contiguous placement aliases
        the row-sharded originals outright); IVF: the originals regrouped
        into this mesh's (slot, max_list, dim) layout; PQ: rows stay in
        corpus order, so the sharded originals ARE the payload. The row-0
        rows substitute unfillable (-inf) merge slots, mirroring the
        id-0 gather convention bit-exactly."""
        if self._payload is not None:
            return self._payload
        idx = self.index
        rep = NamedSharding(self.mesh, P())
        backend = idx.config.backend
        if backend in ("flat", "ivf"):
            # flat: (n_pad,) slab row ids; IVF: (slots, max_list) lists
            ids = np.asarray(self.slab.row_ids if backend == "flat"
                             else self.slab.lists)
            if (self.placement == "contiguous" and backend == "flat"
                    and ids.shape[0] == self.vectors_n.shape[0]):
                pv, pf = self.vectors_n, self.filters_n
            else:
                pv, pf = (slab_mod.put_gathered(self.mesh, self.axes, x, ids,
                                                0.0)
                          for x in (idx.vectors_n, idx.filters_n))
        else:   # pq: contiguous rows — the sharded originals alias directly
            pv, pf = self.vectors_n, self.filters_n
        vn0 = jax.device_put(idx.vectors_n[0], rep)
        fn0 = jax.device_put(idx.filters_n[0], rep)
        self._payload = (pv, pf, vn0, fn0)
        return self._payload

    def _has_flat_router(self) -> bool:
        return (self.index.config.backend == "flat"
                and self.slab.router_centers is not None)

    def slab_row_owner(self) -> np.ndarray:
        """(index.size,) int32 — shard owning each corpus row under the SLAB
        placement (flat: the row's slab block; IVF: its list's shard). This
        is the failure-domain map of degraded serving: a shard's death
        removes exactly the rows it owns here from the candidate space."""
        n = self.index.size
        owner = np.zeros((n,), np.int32)
        backend = self.index.config.backend
        if backend == "flat":
            ids = np.asarray(self.slab.row_ids).reshape(self.n_shards, -1)
            for s in range(self.n_shards):
                block = ids[s]
                owner[block[block >= 0]] = s
        elif backend == "ivf":
            l2s = np.asarray(self.slab.list_to_shard)
            lists = np.asarray(self.index.backend.lists)
            for g in range(lists.shape[0]):
                rows = lists[g]
                owner[rows[rows >= 0]] = l2s[g]
        else:
            # PQ: contiguous row blocks — ownership is pure position
            owner = (np.arange(n) // self.slab.n_local).astype(np.int32)
        return owner

    def _slab_args(self, routed: bool = False, degraded: bool = False):
        s = self.slab
        backend = self.index.config.backend
        if backend == "flat":
            base = (s.vectors, s.sq_norms, s.row_ids)
            # the degraded step needs the routing tables too (coverage
            # certificate), even when serving dense
            if (routed or degraded) and self._has_flat_router():
                base = base + (s.router_centers, s.router_radii,
                               s.cluster_to_shard)
            if s.scales is not None:     # int8 rung: per-row dequant scales
                base = base + (s.scales,)
            return base
        if backend == "ivf":
            base = (s.grouped, s.grouped_sq, s.valid, s.lists, s.centroids,
                    s.c_sq, s.slot_of_list)
            if s.grouped_scales is not None:
                base = base + (s.grouped_scales,)
            return base
        return (s.codes, s.coarse_ids, s.codebooks, s.coarse_centers,
                s.cb_sq, s.coarse_dot)

    def _slab_specs(self, row, routed: bool = False, degraded: bool = False):
        s = self.slab
        backend = self.index.config.backend
        if backend == "flat":
            base = (row, row, row)
            if (routed or degraded) and self._has_flat_router():
                base = base + (P(), P(), P())   # routing tables: replicated
            if s.scales is not None:
                base = base + (row,)
            return base
        if backend == "ivf":
            # grouped layouts are list-sharded; centroid state is replicated
            base = (row, row, row, row, P(), P(), P())
            if s.grouped_scales is not None:
                base = base + (row,)
            return base
        # PQ: per-row codes / coarse ids row-sharded; LUT state replicated
        return (row, row, P(), P(), P(), P())

    def _build_step(self, k: int, kp: int, kd: int, nld: Optional[int],
                    routed: bool, degraded: bool = False,
                    delta_scaled: bool = False, gather_free: bool = False):
        from repro.serve import engine as engine_mod

        cfg = self.index.config
        axes, sizes = self.axes, self.sizes
        ns = self.n_shards
        use_pallas = cfg.use_pallas
        backend = cfg.backend
        rows_local = self.rows_local
        index_size = self.index.size
        has_delta = nld is not None
        has_router = self._has_flat_router()
        router_np = self.router_nprobe
        has_scales = False
        n_local_pq = 0
        if backend == "flat":
            kl = min(kp, self.slab.n_local)
            has_scales = self.slab.scales is not None
        elif backend == "ivf":
            nprobe = min(cfg.nprobe, self.slab.nlist)
            lpp = self.slab.lists_per_shard + 1
            max_list = self.slab.max_list
            kl_ivf = min(kp, nprobe * max_list)
            has_scales = self.slab.grouped_scales is not None
        else:
            n_local_pq = self.slab.n_local
            kl_pq = min(kp, n_local_pq)
            pq_m, pq_ksub = self.slab.codebooks.shape[:2]
            pq_ncoarse = self.slab.coarse_centers.shape[0]

        def flat_scan(slab_args, q_t):
            vectors, sq_norms, row_ids = slab_args[:3]
            sc = slab_args[-1] if has_scales else None
            return _local_flat_topk(vectors, sq_norms, row_ids, q_t, kl,
                                    use_pallas, scales=sc)

        def flat_scan_sc(slab_args, pv_l, pf_l, q_t, qn, fqn):
            # gather-free: re-rank the shard's own candidates against its
            # LOCAL payload block (cheap local gather, no cross-shard
            # mask+psum) and let the merge carry the finished scores
            vals, gids, pos = flat_scan(slab_args, q_t)
            sc = fcvi.combined_score(pv_l[pos], pf_l[pos], qn, fqn, cfg.lam,
                                     use_pallas=use_pallas)
            return vals, gids, sc

        def ivf_probe(slab_args, q_t, q2):
            # coarse quantizer: replicated, identical to the single-device
            # path (centroid scoring is just a tiny flat search)
            c, c2 = slab_args[4], slab_args[5]
            if use_pallas:
                _, probe = ops.score_topk_padded(c, c2, q_t, nprobe)
            else:
                cd = -(q2 - 2.0 * q_t @ c.T + c2[None, :])
                _, probe = jax.lax.top_k(cd, nprobe)
            return probe

        def ivf_local_slots(slab_args, probe, lin):
            slot_of = slab_args[6]
            slot = slot_of[probe]                          # (b, nprobe)
            mine = (slot // lpp) == lin
            # non-local probes go to this shard's all-invalid sentinel slot
            return jnp.where(mine, slot % lpp, lpp - 1)

        def ivf_scan(slab_args, q_t, q2, probe, lin):
            grouped, grouped_sq, valid, lists = slab_args[:4]
            gsc = slab_args[-1] if has_scales else None
            local = ivf_local_slots(slab_args, probe, lin)
            if use_pallas:
                uniq, member = ops.dedup_probes(local.astype(jnp.int32), lpp)
                vals, flat_ids = ops.ivf_score_topk_dedup(
                    grouped, grouped_sq, valid, uniq, member, q_t, kl_ivf,
                    scales=gsc)
                cand = lists.reshape(-1)[flat_ids]         # -1 on pad slots
                return vals - q2, cand

            def one_query(qv, q_sq, slots):
                cand = lists[slots].reshape(-1)            # (nprobe*max_list,)
                ok = cand >= 0
                rows = grouped[slots].reshape(-1, grouped.shape[-1])
                row_sq = grouped_sq[slots].reshape(-1)
                dot = rows.astype(qv.dtype) @ qv
                if gsc is not None:
                    dot = dot * gsc[slots].reshape(-1)
                s = -(q_sq - 2.0 * dot + row_sq)
                s = jnp.where(ok, s, -jnp.inf)
                v, p = jax.lax.top_k(s, kl_ivf)
                return v, jnp.where(ok, cand, -1)[p]

            return jax.vmap(one_query)(q_t, q2[:, 0], local)

        def ivf_scan_sc(slab_args, pv_g, pf_g, q_t, q2, qn, fqn, probe, lin):
            # gather-free IVF scan: candidates' payload rows come from this
            # shard's grouped payload block by probed-slot position (local
            # gathers), are re-ranked here, and only the scores merge
            grouped, grouped_sq, valid, lists = slab_args[:4]
            gsc = slab_args[-1] if has_scales else None
            local = ivf_local_slots(slab_args, probe, lin)
            dv, dm = pv_g.shape[-1], pf_g.shape[-1]
            if use_pallas:
                uniq, member = ops.dedup_probes(local.astype(jnp.int32), lpp)
                vals, flat_ids = ops.ivf_score_topk_dedup(
                    grouped, grouped_sq, valid, uniq, member, q_t, kl_ivf,
                    scales=gsc)
                cand = lists.reshape(-1)[flat_ids]         # -1 on pad slots
                vals = vals - q2
                rv = pv_g.reshape(-1, dv)[flat_ids]
                rf = pf_g.reshape(-1, dm)[flat_ids]
            else:

                def one_query(qv, q_sq, slots):
                    cand = lists[slots].reshape(-1)
                    ok = cand >= 0
                    rows = grouped[slots].reshape(-1, grouped.shape[-1])
                    row_sq = grouped_sq[slots].reshape(-1)
                    dot = rows.astype(qv.dtype) @ qv
                    if gsc is not None:
                        dot = dot * gsc[slots].reshape(-1)
                    s = -(q_sq - 2.0 * dot + row_sq)
                    s = jnp.where(ok, s, -jnp.inf)
                    v, p = jax.lax.top_k(s, kl_ivf)
                    rpv = pv_g[slots].reshape(-1, dv)
                    rpf = pf_g[slots].reshape(-1, dm)
                    return v, jnp.where(ok, cand, -1)[p], rpv[p], rpf[p]

                vals, cand, rv, rf = jax.vmap(one_query)(q_t, q2[:, 0], local)
            sc = fcvi.combined_score(rv, rf, qn, fqn, cfg.lam,
                                     use_pallas=use_pallas)
            return vals, cand, sc

        def pq_scan(slab_args, q_t, lin):
            """Local ADC sweep over this shard's code block; returns
            (vals, local positions). Per-row ADC sums depend only on the
            row's own codes + the replicated LUTs, so local values equal
            the single-device scan's entries bitwise; position-masked pad
            rows (codes 0) score -inf."""
            codes, cids = slab_args[0], slab_args[1]
            pidx = pq_mod.PQIndex(codebooks=slab_args[2], codes=codes,
                                  coarse_centers=slab_args[3],
                                  coarse_ids=cids, cb_sq=slab_args[4],
                                  coarse_dot=slab_args[5])
            luts = pq_mod.compute_luts(pidx, q_t, use_pallas=use_pallas)
            nq = luts.shape[0]
            if use_pallas:
                ccodes = cids[:, None] * pq_ksub + codes
                big = luts.transpose(0, 2, 1, 3).reshape(
                    nq, pq_m, pq_ncoarse * pq_ksub)
                d2 = ops.pq_score_batch(ccodes, big)       # (b, n_local)
            else:
                pos = (cids[:, None] * (pq_m * pq_ksub)
                       + jnp.arange(pq_m)[None, :] * pq_ksub + codes)

                def one_query(lut):
                    return jnp.sum(lut.reshape(-1)[pos], axis=-1)

                d2 = jax.vmap(one_query)(luts)
            rowpos = lin * n_local_pq + jnp.arange(codes.shape[0])
            s = jnp.where((rowpos < index_size)[None, :], -d2, -jnp.inf)
            return jax.lax.top_k(s, kl_pq)

        if backend == "flat":
            n_slab_args = (3 + (3 if (routed or degraded) and has_router
                                else 0) + (1 if has_scales else 0))
        elif backend == "ivf":
            n_slab_args = 7 + (1 if has_scales else 0)
        else:
            n_slab_args = 6

        def body(tfm, *args):
            engine_mod._TRACE_COUNT[0] += 1
            slab_args = args[:n_slab_args]
            rest = args[n_slab_args:]
            alive_v = None
            if degraded:
                alive_v = rest[-1]                 # (ns,) bool, replicated
                rest = rest[:-1]
            vn0 = fn0 = None
            if gather_free:
                # slab-aligned payload blocks + replicated row-0 phantoms
                pv_l, pf_l, vn0, fn0 = rest[:4]
                rest = rest[4:]
            else:
                vn_l, fn_l = rest[:2]
                rest = rest[2:]
            dsc = None
            if has_delta:
                if delta_scaled:
                    dvt, dsq, dids, dvn, dfn, dsc, q, f = rest
                else:
                    dvt, dsq, dids, dvn, dfn, q, f = rest
            else:
                q, f = rest
            lin = linear_shard_index(axes, sizes)
            ok_me = alive_v[lin] if degraded else None   # this shard alive?
            qn, fqn = tfm.normalize(q, f)
            q_t = tfm.apply_normalized(qn, fqn, use_pallas=use_pallas)
            b = q.shape[0]

            route_mask = bound = None
            shard_of = cl_ub = inc = None
            if backend == "flat":
                if (routed or degraded) and has_router:
                    rc, rr, inc = slab_args[3:6]
                    cl_d2, cl_ub = _cluster_bounds(q_t, rc, rr)

                def scan(_):
                    if gather_free:
                        out = flat_scan_sc(slab_args, pv_l, pf_l, q_t,
                                           qn, fqn)
                    else:
                        v, g, _ = flat_scan(slab_args, q_t)
                        out = (v, g)
                    if routed and has_router:
                        # routing masks VALUES only; carried local scores
                        # stay attached and lose the merge as -inf slots
                        out = ((jnp.where(mine_q[:, None], out[0],
                                          -jnp.inf),) + out[1:])
                    return out

                def skip(_):
                    out = (jnp.full((b, kl), -jnp.inf, jnp.float32),
                           jnp.zeros((b, kl), jnp.int32))
                    if gather_free:
                        out = out + (jnp.zeros((b, kl), jnp.float32),)
                    return out

                if routed and has_router:
                    route_mask, bound = _flat_router(q_t, rc, rr, inc,
                                                     router_np, d2=cl_d2,
                                                     ub=cl_ub)
                    mine_q = jnp.take(route_mask, lin, axis=1)   # (b,)
                    pred = jnp.any(mine_q)
                    if degraded:     # dead == never-routed: zero-work branch
                        pred = jnp.logical_and(pred, ok_me)
                    out = jax.lax.cond(pred, scan, skip, None)
                elif degraded:
                    out = jax.lax.cond(ok_me, scan, skip, None)
                    if routed:   # 1-shard mesh: routing is a no-op
                        route_mask = jnp.ones((b, ns), bool)
                else:
                    out = scan(None)
                    if routed:   # 1-shard mesh: routing is a no-op
                        route_mask = jnp.ones((b, ns), bool)
            elif backend == "ivf":
                q2 = jnp.sum(q_t * q_t, axis=-1, keepdims=True)
                probe = ivf_probe(slab_args, q_t, q2)
                if routed or degraded:
                    # a probed list is wholly owned by one shard; the routed
                    # mask is exact, and the degraded coverage certificate
                    # just checks probed-list ownership against the mask
                    shard_of = slab_args[6][probe] // lpp      # (b, nprobe)

                def scan(_):
                    if gather_free:
                        return ivf_scan_sc(slab_args, pv_l, pf_l, q_t, q2,
                                           qn, fqn, probe, lin)
                    return ivf_scan(slab_args, q_t, q2, probe, lin)

                def skip(_):
                    out = (jnp.full((b, kl_ivf), -jnp.inf, jnp.float32),
                           jnp.full((b, kl_ivf), -1, jnp.int32))
                    if gather_free:
                        out = out + (jnp.zeros((b, kl_ivf), jnp.float32),)
                    return out

                if routed:
                    route_mask = jnp.any(
                        shard_of[:, :, None] == jnp.arange(ns)[None, None, :],
                        axis=1)                                # (b, ns)
                    mine_q = jnp.take(route_mask, lin, axis=1)
                    pred = jnp.any(mine_q)
                    if degraded:
                        pred = jnp.logical_and(pred, ok_me)
                    out = jax.lax.cond(pred, scan, skip, None)
                elif degraded:
                    out = jax.lax.cond(ok_me, scan, skip, None)
                else:
                    out = scan(None)
            else:   # pq (routed is rejected at construction)

                def scan(_):
                    vals, p = pq_scan(slab_args, q_t, lin)
                    gids = lin * n_local_pq + p
                    if gather_free:
                        # contiguous ownership: local position p IS the row
                        sc = fcvi.combined_score(pv_l[p], pf_l[p], qn, fqn,
                                                 cfg.lam,
                                                 use_pallas=use_pallas)
                        return vals, gids, sc
                    return vals, gids

                def skip(_):
                    out = (jnp.full((b, kl_pq), -jnp.inf, jnp.float32),
                           jnp.zeros((b, kl_pq), jnp.int32))
                    if gather_free:
                        out = out + (jnp.zeros((b, kl_pq), jnp.float32),)
                    return out

                if degraded:
                    out = jax.lax.cond(ok_me, scan, skip, None)
                else:
                    out = scan(None)

            if gather_free:
                vals, gids, sc = out
                vals, gids, (scc,) = tree_merge_topk_rows(
                    vals, gids, (sc[..., None],), axes, sizes, kp)
            else:
                vals, gids = out
                vals, gids = tree_merge_topk(vals, gids, axes, sizes, kp)
            if routed:
                if backend == "flat" and has_router:
                    # may routing have clipped the dense top-k'? A -inf
                    # k'-th value (routed pool could not even fill k') makes
                    # the slack infinite and always flags, as it must — a
                    # masked shard might have filled it. In degraded mode the
                    # bound still counts dead inactive shards, which only
                    # over-flags: the dense fallback also serves without the
                    # dead shards, so routed == dense-degraded either way.
                    kth = vals[:, -1]
                    tol = ROUTER_EPS + ROUTER_RTOL * jnp.abs(kth)
                    flag = bound >= kth - tol
                else:
                    # IVF routing (and the 1-shard flat no-op) is exact by
                    # construction: masked shards own none of the probed
                    # lists, so even an underfilled pool matches dense
                    flag = jnp.zeros((b,), bool)
            if degraded:
                # coverage certificate vs the HEALTHY corpus: could the dead
                # shards have held a top-k' candidate for this query?
                kth = vals[:, -1]
                if backend == "flat" and has_router:
                    # ball bound over psi-clusters with rows on dead shards,
                    # same tolerance discipline as the router clipping check;
                    # a -inf k'-th value conservatively flags
                    dead_f = 1.0 - alive_v.astype(jnp.float32)
                    dead_cl = (inc @ dead_f) > 0.0             # (ncl,)
                    has_rows = jnp.sum(inc, axis=-1) > 0.0
                    dead_bound = jnp.max(
                        jnp.where((dead_cl & has_rows)[None, :], cl_ub,
                                  -jnp.inf), axis=-1)
                    tol = ROUTER_EPS + ROUTER_RTOL * jnp.abs(kth)
                    uncovered = dead_bound >= kth - tol
                elif backend == "ivf":
                    # exact: the query is affected iff a probed list is
                    # owned by a dead shard
                    uncovered = jnp.any(
                        jnp.logical_not(alive_v[shard_of]), axis=1)
                else:
                    # contiguous flat/PQ placement has no routing geometry:
                    # conservatively flag every query while any shard is dead
                    uncovered = jnp.broadcast_to(
                        jnp.any(jnp.logical_not(alive_v)), (b,))
            # mirror the single-device id convention for unfillable rows
            gids = jnp.where(jnp.isneginf(vals), 0, jnp.maximum(gids, 0))

            if gather_free:
                # -inf merge slots mirror the legacy forced-gid-0 gather:
                # score the replicated corpus-row-0 phantom through the same
                # gather-fed rescore tile shape convention and substitute it
                # where the merge left -inf
                z = jnp.zeros((b, 1), jnp.int32)
                s0 = fcvi.combined_score(vn0[None][z], fn0[None][z], qn, fqn,
                                         cfg.lam, use_pallas=use_pallas)
                score = jnp.where(jnp.isneginf(vals), s0, scc[..., 0])
            else:
                cv = _gather_rows(vn_l, gids, lin, rows_local, axes)
                cf = _gather_rows(fn_l, gids, lin, rows_local, axes)
                score = fcvi.combined_score(cv, cf, qn, fqn, cfg.lam,
                                            use_pallas=use_pallas)
            scores, pos = jax.lax.top_k(score, k)
            ids = jnp.take_along_axis(gids, pos, axis=-1)

            if has_delta:
                kdl = min(kd, nld)
                if gather_free:
                    dvals, dgids, dpos = _local_flat_topk(dvt, dsq, dids,
                                                          q_t, kdl,
                                                          use_pallas,
                                                          scales=dsc)
                    ds = fcvi.combined_score(dvn[dpos], dfn[dpos], qn, fqn,
                                             cfg.lam, use_pallas=use_pallas)
                    dvals, dgids, (dss,) = tree_merge_topk_rows(
                        dvals, dgids, (ds[..., None],), axes, sizes, kd)
                    s = dss[..., 0]
                else:
                    dvals, dgids, _ = _local_flat_topk(dvt, dsq, dids, q_t,
                                                       kdl, use_pallas,
                                                       scales=dsc)
                    dvals, dgids = tree_merge_topk(dvals, dgids, axes,
                                                   sizes, kd)
                    safe = jnp.maximum(dgids, 0)
                    dcv = _gather_rows(dvn, safe, lin, nld, axes)
                    dcf = _gather_rows(dfn, safe, lin, nld, axes)
                    s = fcvi.combined_score(dcv, dcf, qn, fqn, cfg.lam,
                                            use_pallas=use_pallas)
                s = jnp.where(dgids >= 0, s, -jnp.inf)
                safe = jnp.maximum(dgids, 0)
                dv, dp = jax.lax.top_k(s, min(k, kd))
                did = index_size + jnp.take_along_axis(safe, dp, axis=-1)
                scores, ids = flat_mod.merge_topk(scores, ids, dv,
                                                  did.astype(ids.dtype), k)

            margin = scores[:, 0] - scores[:, -1]
            out = (scores, ids, margin)
            if routed:
                out = out + (flag, route_mask)
            if degraded:
                out = out + (uncovered,)
            return out

        row = P(axes)
        specs = (P(),) + self._slab_specs(row, routed, degraded)
        if gather_free:
            specs = specs + (row, row, P(), P())   # payloads + row-0 phantoms
        else:
            specs = specs + (row, row)
        if has_delta:
            specs = specs + (row,) * 5
            if delta_scaled:
                specs = specs + (row,)
        specs = specs + (P(), P())
        if degraded:
            specs = specs + (P(),)     # alive mask: replicated, traced
        n_out = (5 if routed else 3) + (1 if degraded else 0)
        mapped = jax.shard_map(body, mesh=self.mesh, in_specs=specs,
                               out_specs=(P(),) * n_out, check_vma=False)
        return jax.jit(mapped)

    # -- the sharded filtered (predicate) step ----------------------------
    def filtered_step(self, q_t: Array, lo: Array, hi: Array,
                      isin_vals: Array, isin_count: Array, *, k: int,
                      routed: bool = False):
        """Exact predicate-filtered top-k over the sharded slab.

        ``q_t`` is the (b, d) fold-transformed query batch (computed once by
        the engine, replicated in); the four predicate arrays are the
        fixed-shape ``CompiledPredicate`` encoding — pure DATA operands, so
        one trace per (k, routed) signature serves every predicate. Each
        shard evaluates the predicate over its slab-resident RAW attribute
        block (NaN pad/sentinel rows are never eligible), computes the exact
        fp32 squared distances of its ELIGIBLE rows with the same elementwise
        expression as ``flat.filtered_d2``, and emits its local (d2, id)
        top-k under the deterministic (d2 asc, id asc) order; the per-shard
        sets merge by the same two-key sort outside the shard_map. Results
        are bit-identical to the single-device MASK plan.

        ``routed=True`` wraps each shard's scan in a ``lax.cond`` that skips
        the distance work when NO local row is eligible — exact by
        construction (ineligible rows contribute (+inf, DEAD) either way),
        it only changes which code runs. Returns (d2 (b, k), ids (b, k)) in
        the pre-finalize convention (dead slots (+inf, DEAD_ID)) so the
        engine can merge the delta tier in d2-space before
        ``flat.finalize_filtered``.
        """
        if self.slab.attrs is None:
            raise ValueError(
                "filtered_step needs attribute columns on the slab: "
                "construct ShardedServing(..., attrs=<raw (n, m) table>)")
        key = (k, routed)
        fn = self._fsteps.get(key)
        if fn is None:
            fn = self._fsteps[key] = self._build_filtered_step(k, routed)
        return fn(*self._fslab_args(), q_t, lo, hi, isin_vals, isin_count)

    def _fslab_args(self):
        s = self.slab
        if self.index.config.backend == "flat":
            base = (s.vectors, s.row_ids, s.attrs)
            if s.scales is not None:
                base = base + (s.scales,)
            return base
        base = (s.grouped, s.lists, s.attrs)
        if s.grouped_scales is not None:
            base = base + (s.grouped_scales,)
        return base

    def _build_filtered_step(self, k: int, routed: bool):
        from repro.core import filters as filters_mod
        from repro.serve import engine as engine_mod

        backend = self.index.config.backend
        if backend not in ("flat", "ivf"):
            raise ValueError(
                f"filtered serving supports the flat/ivf backends, "
                f"not {backend!r}")
        axes, ns = self.axes, self.n_shards
        has_scales = (self.slab.scales is not None if backend == "flat"
                      else self.slab.grouped_scales is not None)

        def body(*args):
            engine_mod._TRACE_COUNT[0] += 1
            if has_scales:
                vecs, ids_raw, attrs, scales = args[:4]
                rest = args[4:]
            else:
                vecs, ids_raw, attrs = args[:3]
                scales = None
                rest = args[3:]
            q_t, lo, hi, iv, ic = rest
            b = q_t.shape[0]
            if backend == "ivf":
                # flatten the (slot, max_list, ...) grouped layout to rows
                d = vecs.shape[-1]
                vecs = vecs.reshape(-1, d)
                ids_raw = ids_raw.reshape(-1)
                attrs = attrs.reshape(-1, attrs.shape[-1])
                if scales is not None:
                    scales = scales.reshape(-1)
            elig = filters_mod.eval_mask(attrs, lo, hi, iv, ic)
            elig = jnp.logical_and(elig, ids_raw >= 0)
            ids = jnp.where(elig, ids_raw,
                            flat_mod.DEAD_ID).astype(jnp.int32)

            def scan(_):
                rows = vecs.astype(jnp.float32)
                if scales is not None:
                    rows = rows * scales[:, None]
                d2 = flat_mod.filtered_d2(q_t, rows)          # (b, n_local)
                d2 = jnp.where(elig[None, :], d2, jnp.inf)
                return flat_mod.lexsort_topk(
                    d2, jnp.broadcast_to(ids[None, :], d2.shape), k)

            def skip(_):
                return (jnp.full((b, k), jnp.inf, jnp.float32),
                        jnp.full((b, k), flat_mod.DEAD_ID, jnp.int32))

            if routed:
                return jax.lax.cond(jnp.any(elig), scan, skip, None)
            return scan(None)

        row = P(axes)
        n_in = 4 if has_scales else 3
        mapped = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(row,) * n_in + (P(),) * 5,
            out_specs=(P(axes), P(axes)), check_vma=False)

        def step(*args):
            d2, ids = mapped(*args)                  # (ns*b, k) stacked
            b = args[n_in].shape[0]
            d2 = d2.reshape(ns, b, k).transpose(1, 0, 2).reshape(b, ns * k)
            ids = ids.reshape(ns, b, k).transpose(1, 0, 2).reshape(b, ns * k)
            return flat_mod.lexsort_topk(d2, ids, k)

        return jax.jit(step)

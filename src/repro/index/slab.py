"""Serving-layout slabs + device-mesh sharding of the index storage layer.

A *slab* is the dense, padded, DMA-friendly materialisation of an index's
serving data — the thing the query hot path actually streams:

  * ``FlatSlab``  — the (n, d) corpus matrix + squared norms (flat backend).
  * ``IVFSlab``   — the grouped (nlist, max_list, d) inverted-list layout +
    the coarse centroids (IVF backend).
  * ``PQSlab``    — the (n, M) residual-PQ codes + coarse assignments, with
    the tiny LUT terms (codebooks, coarse centers, precomputed cross terms)
    kept replicated (PQ backend).

Storage may be fp32, bf16 or int8 (``FCVIConfig.storage_dtype``); the int8
rung additionally carries per-row dequantisation ``scales`` (flat) /
``grouped_scales`` (IVF) which shard alongside the rows they scale. Pad and
sentinel scale entries are 1.0 (a harmless no-op multiplier).

``build_grouped`` materialises the IVF grouped layout from the compact id
lists (moved here from ``repro.index.ivf`` so the layout construction lives
with the layout type).

Each slab has a ``shard(mesh, rules)`` step producing its device-mesh
counterpart:

  * ``FlatSlab.shard``  — ROW-shards the corpus over the mesh axes that the
    ``AxisRules`` "corpus" entry resolves to. ``placement="cluster"`` reuses
    the filter-centric idea of ``index.distributed.cluster_sharded_layout``:
    rows are permuted so whole psi-clusters land on single shards (the
    transformed corpus clusters BY FILTER, so most filtered queries
    concentrate on few shards); ``row_ids`` carries the slab-row -> corpus-id
    map either way, with ``-1`` marking padding rows.
  * ``IVFSlab.shard``   — LIST-shards the grouped layout ("ivf_lists" rule):
    inverted lists ARE the psi-clusters of the transformed corpus, so whole
    lists are greedily packed onto shards balanced by row count
    (``balanced_list_layout``). Each shard additionally carries one sentinel
    (all-invalid) list slot so non-local probes have a harmless local target.

Padding conventions match the kernel dispatch layer (``repro.kernels.ops``):
pad vectors are zero with ``+inf`` squared norms, so they score ``-inf`` on
the matmul-expansion path and are mask-refinable on the exact path.

The sharded slabs are plain host-side containers (NOT pytrees): they hold the
``jax.device_put``-sharded arrays plus the static layout facts (local sizes,
mesh axes) that the ``shard_map`` serving step closes over.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array


# ---------------------------------------------------------------------------
# Grouped-layout materialisation (the IVF serving layout)
# ---------------------------------------------------------------------------

def build_grouped(vectors: Array, sq_norms: Array, lists: Array):
    """Materialise the dense (nlist, max_list, d) serving slabs from id lists.

    ``lists`` is (nlist, max_list) int32 corpus ids with -1 padding. Returns
    (grouped, grouped_sq, valid) with ``valid`` float 0/1 (1 = real row).
    """
    safe = jnp.maximum(lists, 0)
    return (vectors[safe], sq_norms[safe],
            (lists >= 0).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Mesh helpers
# ---------------------------------------------------------------------------

def resolve_axes(mesh: Mesh, rules, name: str) -> Tuple[str, ...]:
    """Mesh axes a logical axis name shards over, per the AxisRules entry."""
    v = rules.rules.get(name)
    if v is None:
        return ()
    axes = v if isinstance(v, tuple) else (v,)
    return tuple(a for a in axes if a in mesh.axis_names)

def axes_size(mesh: Mesh, axes: Sequence[str]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _put(mesh: Mesh, axes: Tuple[str, ...], x: Array) -> Array:
    """Shard dim 0 of ``x`` over ``axes`` (replicated over other mesh axes)."""
    return jax.device_put(x, NamedSharding(mesh, P(axes)))


@jax.jit
def take_rows(x: Array, idx: Array, fill: Array) -> Array:
    """``x[idx]`` along dim 0 (``idx`` of any rank), ``fill`` where idx < 0."""
    keep = (idx >= 0).reshape(idx.shape + (1,) * (x.ndim - 1))
    return jnp.where(keep, x[jnp.maximum(idx, 0)], fill.astype(x.dtype))


def put_gathered(mesh: Mesh, axes: Tuple[str, ...], x: Array, idx,
                 fill) -> Array:
    """``_put(mesh, axes, take_rows(x, idx, fill))``, built one device's
    block at a time: ``x`` stays where it is and no device ever holds more
    than its own block of the result. Placing a whole permuted slab on the
    device that holds the unsharded index first would need a second copy
    of the corpus there."""
    idx = np.asarray(idx)
    shape = idx.shape + x.shape[1:]
    sharding = NamedSharding(mesh, P(axes))
    fill = jnp.asarray(fill, x.dtype)
    blocks = [jax.device_put(take_rows(x, jnp.asarray(idx[where[0]]), fill),
                             dev)
              for dev, where in
              sharding.addressable_devices_indices_map(shape).items()]
    return jax.make_array_from_single_device_arrays(shape, sharding, blocks)


def pad_dim0(x: Array, to: int, value) -> Array:
    pad = to - x.shape[0]
    if pad <= 0:
        return x
    filler = jnp.full((pad, *x.shape[1:]), value, x.dtype)
    return jnp.concatenate([x, filler], axis=0)


# ---------------------------------------------------------------------------
# Flat slab
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FlatSlab:
    """The flat serving layout: corpus matrix + precomputed squared norms."""

    vectors: Array   # (n, d) fp32 / bf16 / int8 codes
    sq_norms: Array  # (n,)
    scales: Optional[Array] = None  # (n,) fp32 per-row dequant (int8 storage)

    def tree_flatten(self):
        return (self.vectors, self.sq_norms, self.scales), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def size(self) -> int:
        return self.vectors.shape[0]

    def shard(self, mesh: Mesh, rules, *, placement: str = "contiguous",
              centers: Optional[Array] = None,
              rng: Optional[Array] = None,
              attrs: Optional[Array] = None) -> "ShardedFlatSlab":
        """Row-shard this slab over the mesh axes of the "corpus" rule.

        Args: ``mesh`` + an ``AxisRules`` whose "corpus" entry names the mesh
        axes to shard dim 0 over; ``vectors`` may be fp32, bf16 or int8
        codes (the engine's ``storage_dtype`` knob) — sq norms stay fp32
        either way, and int8 storage row-shards its ``scales`` alongside.

        ``placement="contiguous"`` keeps corpus order (bit-compatible with the
        single-device scan); ``"cluster"`` permutes rows so psi-clusters land
        on single shards (filter-centric placement — the transformed corpus
        clusters by filter value, so filtered traffic concentrates per shard).
        ``centers`` optionally fixes the psi-cluster geometry ((ncl, d) fp32,
        e.g. restored from a checkpoint so a restored engine routes
        identically); otherwise a k-means over the stored rows picks
        ``min(4 * n_shards, n)`` centers.

        Cluster placement additionally derives the ROUTING tables consumed by
        the routed serving step (``repro.serve.sharded``): ``router_centers``
        (ncl, d), ``router_radii`` (ncl,) — max distance of a cluster's rows
        to its center, the ball bound used for the exactness check — and the
        ``cluster_to_shard`` incidence (ncl, n_shards) marking every shard
        holding at least one row of each cluster (multi-hot: the load
        balancer may split a cluster's remainder across shards).

        ``attrs`` optionally rides the slab: an (n, m) fp32 RAW attribute
        table, permuted + padded alongside the rows it describes (NaN pad
        rows — NaN compares false under every predicate, so pads are never
        eligible) and sharded the same way, for in-shard predicate
        evaluation by the filtered serving step.
        """
        axes = resolve_axes(mesh, rules, "corpus")
        ns = axes_size(mesh, axes)
        n = self.size
        router_centers = router_radii = cluster_to_shard = None
        if placement == "cluster" and ns > 1:
            from repro.core.clustering import assign, kmeans
            from repro.index.distributed import cluster_sharded_layout

            v32 = self.vectors.astype(jnp.float32)
            if centers is None:
                if rng is None:
                    rng = jax.random.PRNGKey(0)
                centers, _ = kmeans(rng, v32, min(4 * ns, n), iters=5)
            perm, _ = cluster_sharded_layout(v32, centers, ns)
            # the greedy packer balances to exact equal shard loads only when
            # ns divides n; fold any remainder back in corpus order
            if perm.shape[0] < n:
                rest = jnp.setdiff1d(jnp.arange(n), perm, size=n - perm.shape[0])
                perm = jnp.concatenate([perm, rest])
            row_ids = perm.astype(jnp.int32)
            # routing tables, derived from the ACTUAL placement (shard of a
            # row = its slab position // n_local, which also covers rebalanced
            # remainder rows that left their cluster's home shard)
            ncl = centers.shape[0]
            labels = np.asarray(assign(v32, centers))           # corpus order
            c_np = np.asarray(centers, np.float32)
            dist = np.linalg.norm(
                np.asarray(v32, np.float32) - c_np[labels], axis=-1)
            radii = np.zeros((ncl,), np.float32)
            np.maximum.at(radii, labels, dist.astype(np.float32))
            n_local = (n + (-n % ns)) // ns
            perm_np = np.asarray(perm)
            inc = np.zeros((ncl, ns), np.float32)
            inc[labels[perm_np], np.arange(n) // n_local] = 1.0
            router_centers = jnp.asarray(c_np)
            router_radii = jnp.asarray(radii)
            cluster_to_shard = jnp.asarray(inc)
        elif placement == "contiguous" or ns <= 1:
            row_ids = jnp.arange(n, dtype=jnp.int32)
        else:
            raise ValueError(f"unknown placement {placement!r}")
        n_pad = -n % ns
        ids = np.asarray(pad_dim0(row_ids, n + n_pad, -1))
        scales = None
        if self.scales is not None:
            scales = put_gathered(mesh, axes, self.scales, ids, 1.0)
        attrs_sh = None
        if attrs is not None:
            attrs_sh = put_gathered(mesh, axes,
                                    jnp.asarray(attrs, jnp.float32), ids,
                                    jnp.nan)
        return ShardedFlatSlab(
            vectors=put_gathered(mesh, axes, self.vectors, ids, 0),
            sq_norms=put_gathered(mesh, axes, self.sq_norms, ids, jnp.inf),
            row_ids=_put(mesh, axes, jnp.asarray(ids)),
            scales=scales,
            attrs=attrs_sh,
            mesh=mesh, axes=axes, n_real=n,
            n_local=(n + n_pad) // ns, placement=placement,
            router_centers=router_centers, router_radii=router_radii,
            cluster_to_shard=cluster_to_shard,
        )


@dataclasses.dataclass(frozen=True)
class ShardedFlatSlab:
    """Row-sharded flat slab (host-side container, not a pytree).

    The three ``router_*``/``cluster_to_shard`` tables are the routing
    metadata of filter-centric placement; they are only populated for
    ``placement="cluster"`` on a real (>1 shard) mesh and are replicated
    (small: ncl ~ 4 * n_shards).
    """

    vectors: Array        # (n_pad, d) sharded P(axes); zero pad rows
    sq_norms: Array       # (n_pad,) sharded; +inf pad rows
    row_ids: Array        # (n_pad,) sharded int32 corpus ids; -1 pad rows
    mesh: Mesh
    axes: Tuple[str, ...]
    n_real: int
    n_local: int          # rows per shard
    placement: str
    router_centers: Optional[Array] = None   # (ncl, d) fp32 psi-cluster centers
    router_radii: Optional[Array] = None     # (ncl,) fp32 max member distance
    cluster_to_shard: Optional[Array] = None  # (ncl, ns) 0/1 incidence
    scales: Optional[Array] = None  # (n_pad,) sharded fp32; 1.0 pad rows
    attrs: Optional[Array] = None   # (n_pad, m) sharded fp32 RAW attrs;
                                    # NaN pad rows (never predicate-eligible)

    @property
    def n_shards(self) -> int:
        return axes_size(self.mesh, self.axes)


# ---------------------------------------------------------------------------
# IVF slab
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class IVFSlab:
    """The IVF serving layout: coarse centroids + grouped inverted lists."""

    centroids: Array   # (nlist, d)
    lists: Array       # (nlist, max_list) int32 corpus ids, -1 pad
    grouped: Array     # (nlist, max_list, d) fp32 / bf16 / int8 codes
    grouped_sq: Array  # (nlist, max_list)
    valid: Array       # (nlist, max_list) float 0/1
    grouped_scales: Optional[Array] = None  # (nlist, max_list) int8 dequant

    def tree_flatten(self):
        return (self.centroids, self.lists, self.grouped, self.grouped_sq,
                self.valid, self.grouped_scales), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def nlist(self) -> int:
        return self.centroids.shape[0]

    @property
    def max_list(self) -> int:
        return self.lists.shape[1]

    def shard(self, mesh: Mesh, rules, *, placement: str = "balanced",
              list_sizes: Optional[Array] = None,
              attrs: Optional[Array] = None) -> "ShardedIVFSlab":
        """List-shard the grouped layout over the "ivf_lists" rule axes.

        Args: ``mesh`` + an ``AxisRules`` whose "ivf_lists" entry names the
        mesh axes; ``list_sizes`` ((nlist,) int) skips recounting ``valid``.
        The grouped slabs keep their storage dtype (fp32, bf16 or int8 codes
        with ``grouped_scales`` sharded alongside); centroid state stays
        replicated fp32.

        Whole inverted lists (= psi-clusters of the transformed corpus) are
        packed onto shards; ``placement="balanced"`` greedily packs largest
        lists first onto the least-loaded shard (row-count balance, the
        filter-centric analogue of ``cluster_sharded_layout``);
        ``"affinity"`` packs lists with NEARBY centroids onto the same shard
        under balance caps (``distributed.affinity_group_layout`` — the
        placement routed serving wants: a query's co-probed lists share a
        shard, so unprobed shards can skip); ``"contiguous"`` blocks list
        ids in order. Each shard's local block
        carries ``lists_per_shard + 1`` slots — the last is an all-invalid
        sentinel that non-local probes are routed to. The resulting
        ``slot_of_list`` table doubles as the routing table: a probed list's
        owner shard is ``slot_of_list[g] // (lists_per_shard + 1)``
        (``ShardedIVFSlab.list_to_shard``), which the routed serving step
        uses to skip shards owning none of a query's probed lists.

        ``attrs`` optionally rides the slab: an (n, m) fp32 RAW attribute
        table in CORPUS row order, regrouped through ``lists`` into the
        (slot, max_list, m) layout with NaN on pad/sentinel entries (NaN is
        never predicate-eligible) and sharded alongside the rows, for
        in-shard predicate evaluation by the filtered serving step.
        """
        axes = resolve_axes(mesh, rules, "ivf_lists")
        ns = axes_size(mesh, axes)
        nlist, max_list = self.lists.shape
        lp = -(-nlist // ns)              # real list slots per shard
        lpp = lp + 1                      # + sentinel slot
        if list_sizes is None:
            list_sizes = jnp.sum(self.valid > 0.5, axis=-1)
        if placement == "balanced" and ns > 1:
            shard_of, slot_in_shard = balanced_list_layout(
                np.asarray(list_sizes), ns, lp)
        elif placement == "affinity" and ns > 1:
            from repro.index.distributed import affinity_group_layout

            shard_of = affinity_group_layout(
                np.asarray(self.centroids, np.float32),
                np.asarray(list_sizes), ns, slot_capacity=lp)
            slot_in_shard = np.zeros((nlist,), np.int32)
            counts = np.zeros((ns,), np.int32)
            for g in range(nlist):
                slot_in_shard[g] = counts[shard_of[g]]
                counts[shard_of[g]] += 1
        elif placement == "contiguous" or ns <= 1:
            shard_of = np.arange(nlist) // lp
            slot_in_shard = np.arange(nlist) % lp
        else:
            raise ValueError(f"unknown placement {placement!r}")
        slot_of_list = (shard_of * lpp + slot_in_shard).astype(np.int32)

        # source list of each storage slot; -1 = an empty or sentinel slot
        src = np.full((ns * lpp,), -1, np.int32)
        src[slot_of_list] = np.arange(nlist, dtype=np.int32)

        def place(x, fill):
            return put_gathered(mesh, axes, x, src, fill)

        grouped_scales = None
        if self.grouped_scales is not None:
            grouped_scales = place(self.grouped_scales, 1.0)
        attrs_sh = None
        if attrs is not None:
            # (nlist, max_list, m) raw attributes, NaN on pad slots
            ga = take_rows(jnp.asarray(attrs, jnp.float32), self.lists,
                           jnp.float32(jnp.nan))
            attrs_sh = place(ga, jnp.nan)
        return ShardedIVFSlab(
            centroids=self.centroids,
            c_sq=jnp.sum(self.centroids.astype(jnp.float32) ** 2, axis=-1),
            slot_of_list=jnp.asarray(slot_of_list),
            grouped=place(self.grouped, 0),
            grouped_sq=place(self.grouped_sq, jnp.inf),
            valid=place(self.valid, 0),
            lists=place(self.lists, -1),
            mesh=mesh, axes=axes, nlist=nlist, max_list=max_list,
            lists_per_shard=lp, placement=placement,
            grouped_scales=grouped_scales,
            attrs=attrs_sh,
        )


@dataclasses.dataclass(frozen=True)
class ShardedIVFSlab:
    """List-sharded IVF slab (host-side container, not a pytree)."""

    centroids: Array      # (nlist, d) replicated
    c_sq: Array           # (nlist,) replicated
    slot_of_list: Array   # (nlist,) int32 replicated: storage row of list g
    grouped: Array        # (ns*(lp+1), max_list, d) sharded P(axes)
    grouped_sq: Array     # (ns*(lp+1), max_list) sharded; +inf on sentinels
    valid: Array          # (ns*(lp+1), max_list) sharded; 0 on sentinels
    lists: Array          # (ns*(lp+1), max_list) sharded; -1 on sentinels
    mesh: Mesh
    axes: Tuple[str, ...]
    nlist: int
    max_list: int
    lists_per_shard: int  # real slots per shard (local block adds 1 sentinel)
    placement: str
    grouped_scales: Optional[Array] = None  # sharded; 1.0 on sentinels/pads
    attrs: Optional[Array] = None  # (ns*(lp+1), max_list, m) sharded fp32 RAW
                                   # attrs; NaN on sentinels/pads

    @property
    def n_shards(self) -> int:
        return axes_size(self.mesh, self.axes)

    @property
    def list_to_shard(self) -> Array:
        """(nlist,) int32 shard owning each inverted list — every list is
        wholly owned by one shard, so this routing table is exact (the IVF
        analogue of the flat slab's ``cluster_to_shard`` incidence)."""
        return self.slot_of_list // (self.lists_per_shard + 1)


# ---------------------------------------------------------------------------
# PQ slab
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PQSlab:
    """The residual-PQ serving layout: row-shardable codes + replicated LUTs.

    The ADC scan only ever reads ``codes``/``coarse_ids`` per corpus row —
    everything else (codebooks, coarse centers, the precomputed ``cb_sq`` /
    ``coarse_dot`` cross terms) is LUT state a few KB large, consumed whole
    by ``repro.index.pq.compute_luts``. Sharding therefore ROW-splits only
    the per-row arrays and replicates the LUT terms.
    """

    codebooks: Array       # (M, ksub, dsub) replicated
    codes: Array           # (n, M) uint8/int32 — row-shardable
    coarse_centers: Array  # (ncoarse, d) replicated
    coarse_ids: Array      # (n,) int32 — row-shardable
    cb_sq: Array           # (M, ksub) replicated
    coarse_dot: Array      # (ncoarse, M, ksub) replicated

    def tree_flatten(self):
        return (self.codebooks, self.codes, self.coarse_centers,
                self.coarse_ids, self.cb_sq, self.coarse_dot), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def size(self) -> int:
        return self.codes.shape[0]

    def shard(self, mesh: Mesh, rules, *,
              placement: str = "contiguous") -> "ShardedPQSlab":
        """Row-shard the codes over the "corpus" rule axes (contiguous only:
        PQ has no per-row geometry to cluster by — the coarse quantizer
        already IS the cluster structure, and it rides along replicated).
        Pad rows get code 0 / coarse id 0 and are masked by position
        (``row >= n_real``) in the sharded serving step."""
        if placement != "contiguous":
            raise ValueError(
                f"PQ slab only supports contiguous placement, got "
                f"{placement!r}")
        axes = resolve_axes(mesh, rules, "corpus")
        ns = axes_size(mesh, axes)
        n = self.size
        n_pad = -n % ns
        return ShardedPQSlab(
            codebooks=self.codebooks,
            codes=_put(mesh, axes, pad_dim0(self.codes, n + n_pad, 0)),
            coarse_centers=self.coarse_centers,
            coarse_ids=_put(mesh, axes,
                            pad_dim0(self.coarse_ids, n + n_pad, 0)),
            cb_sq=self.cb_sq,
            coarse_dot=self.coarse_dot,
            mesh=mesh, axes=axes, n_real=n,
            n_local=(n + n_pad) // ns, placement=placement,
        )


@dataclasses.dataclass(frozen=True)
class ShardedPQSlab:
    """Row-sharded PQ slab (host-side container, not a pytree).

    Rows stay in corpus order (contiguous placement), so a slab row's corpus
    id is just its global position — no ``row_ids`` indirection needed."""

    codebooks: Array       # replicated
    codes: Array           # (n_pad, M) sharded P(axes); zero pad rows
    coarse_centers: Array  # replicated
    coarse_ids: Array      # (n_pad,) sharded; zero pad rows
    cb_sq: Array           # replicated
    coarse_dot: Array      # replicated
    mesh: Mesh
    axes: Tuple[str, ...]
    n_real: int
    n_local: int           # rows per shard
    placement: str

    @property
    def n_shards(self) -> int:
        return axes_size(self.mesh, self.axes)


def balanced_list_layout(list_sizes: np.ndarray, n_shards: int,
                         capacity: int):
    """Greedy balanced packing of inverted lists onto shards.

    Largest lists first onto the least-loaded shard that still has a free
    slot (each shard holds at most ``capacity`` lists). The filter-centric
    placement step for IVF: lists are whole psi-clusters, so a probe touches
    exactly one shard. Returns (shard_of_list, slot_in_shard) int arrays.
    """
    sizes = np.asarray(list_sizes, np.int64)
    nlist = sizes.shape[0]
    if n_shards * capacity < nlist:
        raise ValueError(
            f"{n_shards} shards x {capacity} slots < {nlist} lists")
    order = np.argsort(-sizes, kind="stable")
    load = np.zeros(n_shards, np.int64)
    used = np.zeros(n_shards, np.int64)
    shard_of = np.zeros(nlist, np.int32)
    slot_in = np.zeros(nlist, np.int32)
    for g in order:
        free = np.nonzero(used < capacity)[0]
        s = free[np.argmin(load[free])]
        shard_of[g] = s
        slot_in[g] = used[s]
        used[s] += 1
        load[s] += sizes[g]
    return shard_of, slot_in

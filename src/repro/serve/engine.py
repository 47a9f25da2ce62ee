"""Batched FCVI serving engine (§4.3 optimizations, production shape).

Implements the paper's serving-side optimizations on top of FCVIIndex:
  * request batching (group queries, amortise index traversal),
  * filter-aware result cache (common filter combinations hit the cache;
    cache keys are quantized once per batch with a single vectorized round),
  * adaptive k' with two-stage escalation (early-termination dual: retrieve
    with a small k', escalate only queries whose top-k margin is ambiguous),
  * delta buffer for inserts + background compaction: new rows live in a
    device-resident delta ``FlatIndex`` (transformed space) between
    compactions,
  * multi-probe execution for range/disjunctive predicates.

The per-batch hot path — normalize + transform the queries, backend candidate
generation, combined-score re-rank, delta search + ``merge_topk``, and the
escalation margin — is ONE ``jax.jit``-compiled function (``_batch_step``)
over statically padded batch shapes: a batch costs a single dispatch, not a
Python re-entry per stage. Cache lookups, stats, and the escalation decision
are host-side bookkeeping OFF the traced path; ``trace_count()`` exposes the
compile counter so tests can pin down per-batch retracing regressions.
Host spans (``repro.serve.spans``) mark each call's layers in any profiler
trace taken around serving: ``fcvi.search`` > ``fcvi.validate``,
``fcvi.cache``, ``fcvi.batch`` > ``fcvi.step``, ``fcvi.escalate``,
``fcvi.fetch``.

When ``FCVIConfig.use_pallas`` is set on the wrapped index, everything inside
the step — the fused query transform, candidate generation, re-scoring, and
the delta merge — runs through the Pallas kernels in ``repro.kernels.ops``.

Mesh-sharded serving: constructing the engine with a ``jax.sharding.Mesh``
(``FCVIEngine(index, cfg, mesh=mesh)``) shards the serving state over the
device mesh and replaces the batch step with the ``shard_map`` step from
``repro.serve.sharded`` — flat slabs row-sharded, IVF slabs list-sharded,
the delta buffer row-sharded, candidates tree-merged per mesh axis. Results
are IDENTICAL to the single-device step for any mesh shape (a 1-device mesh
is the trivial case); ``mesh=None`` (the default) keeps the single-device
``_batch_step``.

Routed serving: ``FCVIEngine(..., mesh=mesh, placement="cluster",
routing="routed")`` turns filter-centric placement into a throughput lever —
the sharded step routes each query to the shards owning its nearby
psi-clusters (flat) or probed inverted lists (IVF) and unrouted shards skip
candidate generation entirely. The dispatch layer sorts each cache-miss
queue by router signature so co-routed queries share batches, and any query
whose routed clipping bound cannot certify exactness is transparently
re-run through the dense step (``stats.router_fallbacks``), keeping routed
results identical to dense results end to end.

Lifecycle: ``engine.save(ckpt_dir)`` checkpoints the full serving state
(transform + backend slab source arrays + re-rank originals + pending delta
rows) through ``repro.checkpoint.ckpt``; ``FCVIEngine.restore(ckpt_dir,
mesh=...)`` rebuilds an engine on ANY target mesh — arrays are loaded
replicated on host and re-laid-out by the sharding step, which is the
elastic-restart path (build on 8 devices, restore and serve on 2).

Degraded serving: a mesh-backed engine carries a ``ShardHealth`` layer
(``repro.serve.health``) — shards marked dead (operator action, heartbeat
timeout, or straggler eviction) are masked out of the sharded step via its
zero-work ``lax.cond`` branch, results stay bit-identical to a search over
the surviving shards' rows, and queries the dead shards could have answered
carry a coverage flag (``stats.last_coverage`` / ``stats.uncovered_queries``)
instead of silently wrong results. Around the jitted step sits an off-trace
resilience envelope: input hardening at the ``search`` boundary (NaN/Inf,
shape, ``k`` vs corpus), bounded retry with exponential backoff on
``TransientShardError``, a per-batch deadline counter, and queue
backpressure (``BackpressureError`` when the cache-miss queue exceeds
``queue_budget``). ``heal()`` turns the elastic restore into recovery:
checkpoint -> re-place the corpus onto the surviving mesh (placement
preserved) -> bit-identity-validated cutover.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt as ckpt_mod
from repro.core import fcvi, theory
from repro.core.baselines import BoxPredicate
from repro.core.fcvi import FCVIConfig, FCVIIndex
from repro.core.filters import Predicate, compile_predicate
from repro.index import flat as flat_mod
from repro.index import ivf as ivf_mod
from repro.kernels import ops
from repro.serve.health import (BackpressureError, ShardHealth,
                                TransientShardError)
from repro.serve.planner import (CANDIDATE_PAD, PLAN_FOLD, PLAN_MASK,
                                 PLAN_ROUTED, PLANS, QueryPlanner,
                                 _pow2_at_least)
from repro.serve.spans import span

# magnitudes beyond this overflow fp32 when squared in the scoring path —
# the input-hardening boundary rejects them as out of support
_SUPPORT_LIMIT = 1e18

# incremented at TRACE time inside _batch_step: stable across steady-state
# batches of the same padded shape, so tests can assert "no silent retracing"
_TRACE_COUNT = [0]


def trace_count() -> int:
    """How many times the jitted engine batch step has been (re)traced."""
    return _TRACE_COUNT[0]


def _subbatch_rows(n: int, b: int) -> int:
    """Rows of the sub-batch that re-runs ``n`` rows of a padded batch of
    ``b``: ``b`` halved while the half still holds them (a power-of-two
    bucket when ``b`` is one), so that each bucket is traced once."""
    while b // 2 >= max(n, 1):
        b //= 2
    return b


@partial(jax.jit, static_argnames=("k", "kp", "kd"))
def _batch_step(index: FCVIIndex, delta_vn, delta_fn, delta_flat, q, f,
                *, k: int, kp: int, kd: int):
    """The whole per-batch hot path as one traced computation.

    transform -> backend candidate generation -> combined-score re-rank ->
    delta search + merge_topk -> escalation margin. ``delta_*`` are None when
    no inserts are pending (a distinct, equally static trace). Returns
    (scores (b,k), ids (b,k), margin (b,)).
    """
    _TRACE_COUNT[0] += 1            # trace-time side effect: counts compiles
    cfg = index.config
    qn, fqn = index.transform.normalize(q, f)
    q_t = index.transform.apply_normalized(qn, fqn, use_pallas=cfg.use_pallas)
    _, cand = fcvi._backend_search(index, q_t, kp)
    scores, ids = fcvi.rescore(index, qn, fqn, cand, k)

    if delta_flat is not None:
        # same over-retrieval bound as the main path (Thm 5.4), so pruning
        # the delta in transformed space never costs more recall than the
        # backend search does; q_t is reused — the fused transform runs once
        nd = delta_vn.shape[0]
        if kd < nd:
            _, dcand = flat_mod.search(delta_flat, q_t, kd,
                                       use_pallas=cfg.use_pallas)
        else:
            dcand = jnp.broadcast_to(jnp.arange(nd)[None, :],
                                     (q.shape[0], nd))
        s = fcvi.combined_score(delta_vn[dcand], delta_fn[dcand], qn, fqn,
                                cfg.lam, use_pallas=cfg.use_pallas)
        dvals, dpos = jax.lax.top_k(s, min(k, kd))
        dids = index.size + jnp.take_along_axis(dcand, dpos, axis=-1)
        scores, ids = flat_mod.merge_topk(scores, ids, dvals,
                                          dids.astype(ids.dtype), k)

    margin = scores[:, 0] - scores[:, -1]
    return scores, ids, margin


# ---------------------------------------------------------------------------
# Predicate-filtered physical plans (general filter algebra, meshless side).
#
# All three plans funnel into the SAME refine convention — canonical fp32
# elementwise d2 (``flat.filtered_d2``) + deterministic (d2 asc, id asc)
# lexsort + dead slots at (+inf, DEAD_ID) — so any plan whose candidate set
# CONTAINS the true eligible top-k produces bit-identical output. Predicate
# values, eligibility masks, and routed list ids enter as DATA operands; the
# only jit keys are (k, kp, use_pallas) plus the pytree structure, so
# steady-state filtered batches never retrace.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("k", "kp", "use_pallas"))
def _filtered_mask_step(backend, q_t, elig, *, k: int, kp: int,
                        use_pallas: bool):
    """MASK plan: in-kernel eligibility-masked scan, then filtered refine.

    ``elig`` is a (n,) bool over corpus rows. Flat backends run the masked
    top-k'' scan (``flat.masked_candidates``); IVF backends run the masked
    EXHAUSTIVE all-lists dedup scan (``ivf.masked_candidates``), so the
    candidate set always contains every eligible row within k'' — exact by
    construction when kp >= min(k, #eligible)."""
    _TRACE_COUNT[0] += 1
    if isinstance(backend, flat_mod.FlatIndex):
        cand, valid = flat_mod.masked_candidates(backend, q_t, kp, elig,
                                                 use_pallas=use_pallas)
        vectors, scales = backend.vectors, backend.scales
    else:
        cand, valid = ivf_mod.masked_candidates(backend, q_t, kp, elig,
                                                use_pallas=use_pallas)
        vectors, scales = backend.vectors, backend.scales
    return flat_mod.filtered_refine(vectors, scales, q_t, cand, valid,
                                    elig, k)


@partial(jax.jit, static_argnames=("k", "kp", "use_pallas"))
def _filtered_fold_step(backend, q_t, elig, *, k: int, kp: int,
                        use_pallas: bool):
    """FOLD plan (flat fp32 only): unmasked scan against the folded query.

    ``q_t`` was transformed against the predicate's RAW-space fold target,
    so eligible rows geometrically cluster near the query (the paper's psi
    contraction). We over-retrieve kp unfiltered candidates, refine over the
    eligible subset, and emit a per-query CERTIFICATE: the result is exact
    when the candidate window held >= k eligible rows, or held every
    eligible row there is. Uncertified rows fall back to the MASK plan
    host-side. Returns (d2, ids, certified)."""
    _TRACE_COUNT[0] += 1
    vals, cand = ops.score_topk_padded(backend.vectors, backend.sq_norms,
                                       q_t, kp, scales=backend.scales,
                                       use_pallas=use_pallas)
    valid = ~jnp.isneginf(vals)
    cand = jnp.maximum(cand, 0)
    d2, ids = flat_mod.filtered_refine(backend.vectors, backend.scales,
                                       q_t, cand, valid, elig, k)
    elig_in = jnp.sum(jnp.where(valid, elig[cand], False), axis=-1)
    n_elig = jnp.sum(elig)
    certified = (elig_in >= k) | (elig_in == n_elig)
    return d2, ids, certified


@partial(jax.jit, static_argnames=("k", "kp", "use_pallas"))
def _filtered_routed_step(backend, q_t, elig, uniq, n_live, *, k: int,
                          kp: int, use_pallas: bool):
    """ROUTED plan (IVF meshless): scan only the lists holding eligible rows.

    ``uniq`` is the pow-2-padded live list-id bucket (pads repeat a live id;
    ``n_live`` masks them via the member operand, both DATA). Exact because
    every eligible row lives in some routed list and the dedup scan inside
    is exhaustive over those lists."""
    _TRACE_COUNT[0] += 1
    cand, valid = ivf_mod.routed_candidates(backend, q_t, kp, elig, uniq,
                                            n_live, use_pallas=use_pallas)
    return flat_mod.filtered_refine(backend.vectors, backend.scales, q_t,
                                    cand, valid, elig, k)


@partial(jax.jit, static_argnames=("k",))
def _filtered_delta_step(delta_flat, q_t, delig, *, k: int):
    """Exact filtered top-k over the delta tier (delta-LOCAL ids).

    ``delig`` is eligibility over the pending raw insert rows. Exhaustive
    elementwise d2 over the (small) delta — same canonical expression as the
    main tiers, so the d2-space merge stays bit-stable. The engine maps the
    returned local ids to ``index.size + j``."""
    _TRACE_COUNT[0] += 1
    rows = delta_flat.vectors.astype(jnp.float32)
    if delta_flat.scales is not None:
        rows = rows * delta_flat.scales[:, None]
    nd = rows.shape[0]
    d2 = flat_mod.filtered_d2(q_t, rows)
    d2 = jnp.where(delig[None, :], d2, jnp.inf)
    ids = jnp.where(delig, jnp.arange(nd, dtype=jnp.int32),
                    flat_mod.DEAD_ID)
    return flat_mod.lexsort_topk(d2, jnp.broadcast_to(ids[None, :], d2.shape),
                                 k)


@dataclasses.dataclass
class EngineConfig:
    """Serving-side knobs (all host-side policy; none change result values
    except ``k``). ``router_nprobe`` only matters for ``routing="routed"``
    flat serving: how many psi-clusters the shard router probes per query
    (0 = auto, ~two shards' worth of clusters; smaller = more shards
    skipped but more dense fallbacks)."""

    k: int = 10
    batch_size: int = 64
    cache_entries: int = 4096
    cache_round: float = 0.05      # filter-key quantization for cache hits
    escalate_margin: float = 0.02  # top-k score margin triggering stage 2
    kprime_escalation: int = 4     # stage-2 k' multiplier
    compact_threshold: int = 2048  # delta rows triggering compaction
    multi_probe_r: int = 4
    router_nprobe: int = 0         # routed flat serving: probed psi-clusters
    # gather-free re-rank (mesh-sharded step only): each shard reads its
    # winners' re-rank rows from its LOCAL payload block and the merge
    # carries finished scores, instead of a mask+psum distributed gather
    # after the merge. Results are bit-identical either way; a meshless
    # engine has one step and ignores the setting
    gather_free: bool = True
    # -- resilience envelope (off-trace; defaults keep behavior unchanged) --
    deadline_s: float = 0.0        # per-batch deadline; 0 disables the check
    max_retries: int = 2           # bounded retry on TransientShardError
    retry_backoff_s: float = 0.05  # base backoff, doubled per retry
    queue_budget: int = 0          # max cache-miss queue; 0 = unlimited
    # straggler-eviction z-threshold for the shard health layer. NOTE the
    # sample-sd z of ONE outlier in a fleet of n is bounded by (n-1)/sqrt(n)
    # (~2.47 for n=8), so small fleets need a threshold below that bound for
    # single-shard stragglers to ever be evictable
    straggler_z: float = 3.0


@dataclasses.dataclass
class EngineStats:
    """Off-trace serving counters. The ``router_*``/``shard*`` fields are
    only advanced by routed sharded engines: ``shard_steps`` counts
    (batch x shard) slots dispatched, ``shards_active`` how many of those
    actually ran candidate generation (the rest took the zero-work branch),
    ``router_fallbacks`` how many queries were re-run dense because the
    routed clipping bound could not certify exactness."""

    queries: int = 0
    cache_hits: int = 0
    escalations: int = 0
    inserts: int = 0
    compactions: int = 0
    # rows the stage-2 escalation sub-batches ran, power-of-two padding
    # included: ``escalations / escalation_rows`` is their useful share
    escalation_rows: int = 0
    routed_batches: int = 0
    router_fallbacks: int = 0
    shards_active: int = 0
    shard_steps: int = 0
    # -- degraded serving / resilience envelope ---------------------------
    degraded_batches: int = 0      # batches served with >= 1 dead shard
    uncovered_queries: int = 0     # queries whose coverage flag was raised
    retries: int = 0               # TransientShardError retries
    deadline_misses: int = 0       # batches exceeding cfg.deadline_s
    backpressure_drops: int = 0    # queries shed by BackpressureError
    straggler_evictions: int = 0   # shards evicted by the health layer
    heals: int = 0                 # validated heal() cutovers
    # -- predicate-filtered serving (filter algebra + planner) -------------
    filtered_queries: int = 0      # queries served through search(filter=)
    plan_fold: int = 0             # queries executed under each physical plan
    plan_mask: int = 0
    plan_routed: int = 0
    filtered_fallbacks: int = 0    # FOLD queries re-run under MASK (uncertified)
    # per-query coverage flags of the LAST search call (True = certified
    # unaffected by dead shards; all-True while healthy)
    last_coverage: Optional[np.ndarray] = None

    @property
    def shard_skip_rate(self) -> float:
        """Fraction of (batch x shard) slots skipped by routing."""
        if not self.shard_steps:
            return 0.0
        return 1.0 - self.shards_active / self.shard_steps

    @property
    def coverage_rate(self) -> float:
        """Fraction of served queries certified unaffected by dead shards."""
        if not self.queries:
            return 1.0
        return 1.0 - self.uncovered_queries / self.queries


@dataclasses.dataclass
class _DeltaBuffer:
    """Device-resident view of the un-compacted inserts."""

    vn: jax.Array        # (nd, d) normalized new vectors
    fn: jax.Array        # (nd, m) normalized new filters
    flat: flat_mod.FlatIndex  # transformed-space index over the delta rows


class FCVIEngine:
    """Batched serving engine over one ``FCVIIndex``.

    Core entry points (all take/return HOST numpy arrays):
      * ``search(queries (n, d) fp32, filters (n, m) fp32)`` ->
        (scores (n, k) fp32, ids (n, k) int64) — ids >= ``index.size`` are
        un-compacted delta rows.
      * ``insert(vectors (n, d), filters (n, m))`` — buffered in the delta
        index until ``compact_threshold`` triggers compaction.
      * ``save(dir)`` / ``FCVIEngine.restore(dir, mesh=...)`` — the elastic
        checkpoint lifecycle (any target mesh).

    Dispatch-changing knobs: the wrapped index's ``FCVIConfig.use_pallas``
    (Pallas kernels vs jnp reference inside the step — identical results)
    and ``storage_dtype`` (bf16 corpus slabs); the constructor's ``mesh``
    (``None`` = single-device jitted step, a ``jax.sharding.Mesh`` = the
    shard_map step from ``repro.serve.sharded``), ``placement``
    ("contiguous" row order vs "cluster" filter-centric packing), and
    ``routing`` ("dense" = every shard scans every batch, "routed" = shards
    irrelevant to a query's psi-clusters/probed lists are masked and skip
    their scan; requires a mesh, and ``placement="cluster"`` for the flat
    backend). All four are pure deployment knobs: results are identical
    across every combination (routed mode re-runs queries dense whenever its
    clipping bound cannot certify exactness).
    """

    def __init__(self, index: FCVIIndex, config: Optional[EngineConfig] = None,
                 *, mesh=None, rules=None, placement: str = "contiguous",
                 routing: str = "dense", router_centers=None,
                 attributes=None, attr_names=None):
        self.index = index
        # default constructed per engine: a shared EngineConfig() default
        # instance would leak mutations across engines
        self.cfg = config if config is not None else EngineConfig()
        self.stats = EngineStats()
        self._cache: "collections.OrderedDict" = collections.OrderedDict()
        self._delta_v: list = []
        self._delta_f: list = []
        self._delta: Optional[_DeltaBuffer] = None
        self._mesh, self._rules, self._placement = mesh, rules, placement
        # predicate-filtered serving state: the RAW attribute table (defaults
        # to the de-normalized filter columns the index was built from), its
        # column names, and the selectivity-aware query planner
        self._init_attrs(attributes, attr_names)
        if routing not in ("dense", "routed"):
            raise ValueError(
                f"routing must be 'dense' or 'routed', got {routing!r}")
        if routing == "routed" and mesh is None:
            raise ValueError("routing='routed' requires a device mesh")
        self._routing = routing
        self._router_centers = router_centers
        self._sharded = None
        self._sharded_delta = None
        # degraded-serving state: health layer (mesh engines only), the
        # alive-mask signature the cache was filled under, the optional
        # fault injector hook, and the heal cutover lock
        self.health: Optional[ShardHealth] = None
        self.fault_injector = None
        self._alive_sig: Optional[bytes] = None
        self._heal_lock = threading.Lock()
        if mesh is not None:
            self._build_sharded()
            self.health = ShardHealth(self._sharded.n_shards,
                                      straggler_z=self.cfg.straggler_z)

    def _init_attrs(self, attributes, attr_names):
        """Set up the predicate-filtered serving state.

        ``attributes`` is the (n, m) RAW attribute table predicates evaluate
        against; when omitted it defaults to the de-normalized filter columns
        (``fcvi.filters_raw``), so ``F.range("f0", ...)`` works out of the
        box on any index. ``attr_names`` names the columns (default
        ``f0..f{m-1}``). The planner's histograms are built here, once."""
        mf = self.index.transform.filt_norm.mean.shape[-1]
        if attributes is None:
            attrs = np.asarray(fcvi.filters_raw(self.index), np.float32)
        else:
            attrs = np.asarray(attributes, np.float32)
            if attrs.shape != (self.index.size, mf):
                # column count must match the filter dimension: the fold
                # plan's representative vector feeds the filter-side psi
                # transform, and delta rows are predicate-checked against
                # their insert filters
                raise ValueError(
                    f"attributes must be (index.size={self.index.size}, "
                    f"m={mf}); got shape {attrs.shape}")
        m = attrs.shape[1]
        if attr_names is None:
            attr_names = tuple(f"f{j}" for j in range(m))
        else:
            attr_names = tuple(attr_names)
            if len(attr_names) != m:
                raise ValueError(
                    f"attr_names has {len(attr_names)} entries for "
                    f"{m} attribute columns")
        self._attrs_np = attrs
        self._attr_names = attr_names
        self._col_means = attrs.mean(axis=0).astype(np.float32)
        self._rebuild_planner()

    def _rebuild_planner(self):
        cfg = self.index.config
        if cfg.backend in ("flat", "ivf"):
            self.planner = QueryPlanner.build(
                self._attrs_np, backend=cfg.backend,
                storage_fp32=cfg.resolved_storage_dtype() is None,
                sharded=self._mesh is not None)
        else:
            self.planner = None  # PQ: no filtered plans

    def _build_sharded(self):
        """(Re)shard the serving state onto the configured mesh."""
        from repro.serve.sharded import ShardedServing

        attrs = (self._attrs_np
                 if self.index.config.backend in ("flat", "ivf") else None)
        self._sharded = ShardedServing(self.index, self._mesh,
                                       rules=self._rules,
                                       placement=self._placement,
                                       routing=self._routing,
                                       router_nprobe=self.cfg.router_nprobe,
                                       router_centers=self._router_centers,
                                       attrs=attrs)
        self._sharded_delta = None

    @property
    def _routed(self) -> bool:
        return self._sharded is not None and self._routing == "routed"

    # -- cache ------------------------------------------------------------
    def _cache_keys(self, queries: np.ndarray,
                    filters: np.ndarray) -> List[bytes]:
        """Quantized keys for the whole batch: one vectorized round."""
        r = self.cfg.cache_round
        qq = np.round(queries / r).astype(np.int32)
        ff = np.round(filters / r).astype(np.int32)
        return [q.tobytes() + b"#" + f.tobytes() for q, f in zip(qq, ff)]

    def _cache_get(self, key: bytes):
        if key in self._cache:
            self._cache.move_to_end(key)
            return self._cache[key]
        return None

    def _cache_put(self, key: bytes, value):
        self._cache[key] = value
        self._cache.move_to_end(key)
        while len(self._cache) > self.cfg.cache_entries:
            self._cache.popitem(last=False)

    # -- input hardening ---------------------------------------------------
    def _validate_inputs(self, queries, filters):
        """Reject malformed/poisoned inputs at the serving boundary with
        clear ``ValueError``s instead of producing garbage top-k: NaN/Inf
        values, dimension mismatches, empty batches, out-of-support filter
        magnitudes (they overflow fp32 when squared), and ``k`` exceeding
        the corpus. Returns the inputs as fp32 numpy arrays."""
        q = np.asarray(queries, np.float32)
        f = np.asarray(filters, np.float32)
        if q.ndim != 2 or f.ndim != 2:
            raise ValueError(
                f"queries/filters must be 2-D (n, dim); got shapes "
                f"{np.shape(queries)} / {np.shape(filters)}")
        if q.shape[0] == 0:
            raise ValueError("empty query batch: queries.shape[0] == 0")
        if q.shape[0] != f.shape[0]:
            raise ValueError(
                f"queries and filters disagree on batch size: "
                f"{q.shape[0]} != {f.shape[0]}")
        d = self.index.transform.vec_norm.mean.shape[-1]
        m = self.index.transform.filt_norm.mean.shape[-1]
        if q.shape[1] != d:
            raise ValueError(
                f"query dimension mismatch: got {q.shape[1]}, index expects "
                f"{d}")
        if f.shape[1] != m:
            raise ValueError(
                f"filter dimension mismatch: got {f.shape[1]}, index "
                f"expects {m}")
        if not np.isfinite(q).all():
            raise ValueError("queries contain NaN/Inf values")
        if not np.isfinite(f).all():
            raise ValueError("filters contain NaN/Inf values")
        amax = max(float(np.abs(q).max()), float(np.abs(f).max()))
        if amax > _SUPPORT_LIMIT:
            raise ValueError(
                f"input magnitude {amax:.3g} out of support (> "
                f"{_SUPPORT_LIMIT:.0e}): values overflow fp32 when squared")
        total = self.index.size + self.delta_size()
        if self.cfg.k > total:
            raise ValueError(
                f"k={self.cfg.k} exceeds corpus size {total}")
        return q, f

    def _alive_for_search(self):
        """Snapshot the health layer for one search call.

        Returns ``None`` while every shard is healthy (the fast path — the
        degraded step variant is never even traced), else the (n_shards,)
        bool alive mask as a device array. The result cache is cleared
        whenever the mask changes (cached results were computed over a
        different surviving-row set), and cache use is suspended entirely
        while degraded — coverage flags are per-result state a plain
        (scores, ids) cache entry cannot carry.
        """
        if self.health is None:
            return None
        self.health.check_failures()
        sig = (self.health.alive_mask().tobytes()
               if self.health.any_dead() else None)
        if sig != self._alive_sig:
            self._cache.clear()
            self._alive_sig = sig
        if sig is None:
            return None
        return jnp.asarray(self.health.alive_mask())

    # -- search -----------------------------------------------------------
    def search(self, queries: np.ndarray, filters: Optional[np.ndarray] = None,
               *, filter: Optional[Predicate] = None,
               plan: Optional[str] = None):
        """queries: (n, d) fp32. Two serving modes, selected by the kwargs:

        * SIMILARITY mode (``filters`` (n, m) fp32, raw, un-normalized):
          the paper's combined-score search. Returns (scores (n, k) fp32,
          ids (n, k) int64); ids >= ``index.size`` refer to un-compacted
          delta inserts. In routed mode the cache-miss queue is first
          sorted by router shard-group signature so co-routed queries
          share a padded batch (and unprobed shards actually skip).
        * PREDICATE mode (``filter=F.range("price", 10, 50) &
          F.isin("region", [...])``): exact top-k by L2 restricted to the
          rows satisfying the predicate (see ``repro.core.filters``). The
          selectivity-aware planner picks the physical plan per query
          batch (``plan`` forces one of "fold" / "mask" / "routed");
          scores are negative squared distances against the fold-
          transformed query. Queries with no eligible row return
          (-inf, -1) rows. This path bypasses the result cache (the
          predicate is not part of the cache key).

        Inputs are validated at this boundary (see ``_validate_inputs``).
        With dead shards the engine serves DEGRADED: results are
        bit-identical to a search over the surviving shards' rows and
        ``stats.last_coverage`` flags the queries the dead shards could have
        affected. Raises ``BackpressureError`` when the cache-miss queue
        exceeds ``cfg.queue_budget`` (> 0).

        Each call opens the host span ``fcvi.search`` (``repro.serve.spans``),
        with ``fcvi.validate``, ``fcvi.cache`` and one ``fcvi.batch`` per
        padded batch inside it in similarity mode."""
        mode = "similarity" if filter is None else "predicate"
        with span("fcvi.search", mode=mode) as sp:
            if filter is not None:
                if filters is not None:
                    raise ValueError(
                        "pass either filters= (similarity mode) or filter= "
                        "(predicate mode), not both")
                return self._search_filtered(queries, filter, sp, plan=plan)
            if filters is None:
                raise TypeError(
                    "search() needs filters= (similarity mode) or filter= "
                    "(predicate mode)")
            if plan is not None:
                raise ValueError(
                    "plan= only applies to predicate mode (filter=)")
            return self._search_similarity(queries, filters, sp)

    def _search_similarity(self, queries, filters, sp):
        """Similarity mode of ``search``; ``sp`` is its ``fcvi.search``
        span."""
        with span("fcvi.validate"):
            queries, filters = self._validate_inputs(queries, filters)
        n = queries.shape[0]
        sp.set_metadata(queries=n)
        k = self.cfg.k
        out_scores = np.zeros((n, k), np.float32)
        out_ids = np.zeros((n, k), np.int64)
        coverage = np.ones((n,), bool)
        alive = self._alive_for_search()
        use_cache = alive is None

        with span("fcvi.cache") as cache_span:
            keys = self._cache_keys(queries, filters)
            todo = []
            for i, key in enumerate(keys):
                hit = self._cache_get(key) if use_cache else None
                if hit is not None:
                    out_scores[i], out_ids[i] = hit
                    self.stats.cache_hits += 1
                else:
                    todo.append(i)
            cache_span.set_metadata(hits=n - len(todo))

        if self.cfg.queue_budget and len(todo) > self.cfg.queue_budget:
            self.stats.backpressure_drops += len(todo)
            raise BackpressureError(
                f"dispatch queue {len(todo)} exceeds queue_budget="
                f"{self.cfg.queue_budget}; shed load and retry")

        if todo and self._routed:
            # dispatch-layer regrouping: bucket the queue by shard-group
            # signature so each padded batch touches as few shards as it can
            sigs = self._sharded.route_signatures(queries[todo], filters[todo])
            order = sorted(range(len(todo)), key=lambda j: sigs[j].tobytes())
            todo = [todo[j] for j in order]

        bs = self.cfg.batch_size
        for s in range(0, len(todo), bs):
            idxs = todo[s:s + bs]
            with span("fcvi.batch", rows=bs, real=len(idxs)):
                pad = bs - len(idxs)
                if pad and self._routed:
                    # pad with the last real query (not zeros): pad rows then
                    # route like an existing query instead of activating
                    # whatever shards the zero vector happens to map to
                    pq, pf = queries[idxs[-1:]], filters[idxs[-1:]]
                    q = np.concatenate([queries[idxs], np.repeat(pq, pad, 0)])
                    f = np.concatenate([filters[idxs], np.repeat(pf, pad, 0)])
                else:
                    q = np.concatenate(
                        [queries[idxs],
                         np.zeros((pad, queries.shape[1]), np.float32)])
                    f = np.concatenate(
                        [filters[idxs],
                         np.zeros((pad, filters.shape[1]), np.float32)])
                qj, fj = jnp.asarray(q), jnp.asarray(f)
                scores, ids, covered = self._dispatch_batch(
                    qj, fj, k, n_real=len(idxs), alive=alive)
                # the host copy waits for the last stage the batch ran
                with span("fcvi.fetch"):
                    scores, ids = np.asarray(scores), np.asarray(ids)
                for j, i in enumerate(idxs):
                    out_scores[i], out_ids[i] = scores[j], ids[j]
                    if covered is not None:
                        coverage[i] = covered[j]
                    if use_cache:
                        self._cache_put(keys[i], (scores[j], ids[j]))

        self.stats.queries += n
        self.stats.uncovered_queries += int((~coverage).sum())
        self.stats.last_coverage = coverage
        return out_scores, out_ids

    # -- predicate-filtered search (filter algebra + planner) --------------
    def _search_filtered(self, queries, pred: Predicate, sp,
                         plan: Optional[str] = None):
        """Exact predicate-filtered top-k (see ``search`` docstring); ``sp``
        is the call's ``fcvi.search`` span.

        The predicate compiles once per call to fixed-shape arrays
        (``repro.core.filters.compile_predicate``); eligibility is evaluated
        host-side over the RAW attribute table and enters the jitted steps
        as a DATA operand, so plan identity + k + the pow-2 batch bucket are
        the only trace keys. All plans score against the SAME fold-
        transformed queries and funnel into the same canonical d2 + lexsort
        + finalize, so forced plans and topologies agree bit-for-bit.
        Pending delta rows are predicate-checked against the filters they
        were inserted with (when a custom ``attributes`` table was supplied,
        inserts must pass filters in that same attribute space)."""
        if self.planner is None:
            raise ValueError(
                "predicate-filtered search needs a flat or ivf backend "
                f"(index backend is {self.index.config.backend!r})")
        q = np.asarray(queries, np.float32)
        if q.ndim != 2 or q.shape[0] == 0:
            raise ValueError(
                f"queries must be a non-empty (n, d) batch; got shape "
                f"{np.shape(queries)}")
        d = self.index.transform.vec_norm.mean.shape[-1]
        if q.shape[1] != d:
            raise ValueError(
                f"query dimension mismatch: got {q.shape[1]}, index expects "
                f"{d}")
        if not np.isfinite(q).all():
            raise ValueError("queries contain NaN/Inf values")
        n, k = q.shape[0], self.cfg.k
        cp = compile_predicate(pred, self._attr_names)
        chosen = plan if plan is not None else self.planner.choose(cp)
        if plan is not None:
            if plan not in PLANS:
                raise ValueError(f"unknown plan {plan!r}; expected one of "
                                 f"{PLANS}")
            if plan == PLAN_FOLD and not self.planner.fold_capable(cp):
                raise ValueError(
                    "plan='fold' needs a flat fp32 backend and a single-"
                    "attribute predicate")
            if plan == PLAN_ROUTED and not self.planner.routed_capable():
                raise ValueError(
                    "plan='routed' needs an IVF backend or a sharded mesh")
        sp.set_metadata(queries=n, plan=chosen)
        self.stats.queries += n
        self.stats.filtered_queries += n
        setattr(self.stats, f"plan_{chosen}",
                getattr(self.stats, f"plan_{chosen}") + n)
        self.stats.last_coverage = np.ones((n,), bool)

        elig_np = cp.eval_np(self._attrs_np)
        delta = self._ensure_delta()
        delig_np = None
        if delta is not None:
            delig_np = cp.eval_np(
                np.concatenate(self._delta_f).astype(np.float32))
        n_elig = int(elig_np.sum())
        nd_elig = 0 if delig_np is None else int(delig_np.sum())
        out_scores = np.full((n, k), -np.inf, np.float32)
        out_ids = np.full((n, k), -1, np.int64)
        if n_elig + nd_elig == 0:
            # zero-match predicate: certified-empty results, not padded
            # id-0 garbage (coverage stays 1.0 — the answer IS empty)
            return out_scores, out_ids

        # every plan scores against the SAME folded queries, computed once:
        # psi folds the predicate's representative RAW filter vector into
        # the query transform (the paper's filter fold)
        fold_raw = cp.fold_target_raw(self._col_means)
        q_t_all = fcvi.fold_queries(self.index, jnp.asarray(q), fold_raw)
        elig_j = jnp.asarray(elig_np)
        delig_j = None if delig_np is None else jnp.asarray(delig_np)

        main_dead = n_elig == 0
        uniq = n_live = None
        if (chosen == PLAN_ROUTED and self._sharded is None
                and not main_dead):
            r = ivf_mod.eligible_lists(np.asarray(self.index.backend.lists),
                                       elig_np)
            assert r is not None  # n_elig > 0 => at least one live list
            uniq, n_live = jnp.asarray(r[0]), jnp.asarray(r[1])
        kp = self.planner.kp_for(chosen, cp, k)
        if self.index.config.backend == "flat":
            kp = min(kp, self.index.size)  # top-k width can't exceed the scan

        bs = self.cfg.batch_size
        for s in range(0, n, bs):
            idxs = np.arange(s, min(s + bs, n))
            nb = min(bs, _pow2_at_least(len(idxs)))
            sel = np.full((nb,), idxs[-1], np.int64)
            sel[: len(idxs)] = idxs
            q_t = q_t_all[jnp.asarray(sel)]
            d2, ids = self._filtered_main(chosen, cp, q_t, elig_j,
                                          uniq, n_live, k=k, kp=kp,
                                          main_dead=main_dead)
            if delta is not None and nd_elig > 0:
                dd2, dids = _filtered_delta_step(delta.flat, q_t, delig_j,
                                                 k=k)
                dids = jnp.where(dids == flat_mod.DEAD_ID, flat_mod.DEAD_ID,
                                 dids + self.index.size)
                d2, ids = flat_mod.lexsort_topk(
                    jnp.concatenate([d2, dd2], axis=-1),
                    jnp.concatenate([ids, dids], axis=-1), k)
            scores, ids = flat_mod.finalize_filtered(d2, ids)
            out_scores[idxs] = np.asarray(scores)[: len(idxs)]
            out_ids[idxs] = np.asarray(ids, np.int64)[: len(idxs)]
        return out_scores, out_ids

    def _filtered_main(self, plan: str, cp, q_t, elig_j, uniq, n_live, *,
                       k: int, kp: int, main_dead: bool):
        """Main-tier (d2, ids) for one padded batch under ``plan``.

        Pre-finalize convention: dead slots are (+inf, DEAD_ID) so the delta
        tier merges in d2-space. Sharded engines run mask/routed through the
        shard_map filtered step; the fold plan is always meshless (its
        certificate needs the global unmasked scan) — documented trade-off,
        the planner only picks it for flat fp32 where the meshless scan is
        cheap."""
        b = q_t.shape[0]
        if main_dead:
            return (jnp.full((b, k), jnp.inf, jnp.float32),
                    jnp.full((b, k), flat_mod.DEAD_ID, jnp.int32))
        if self._sharded is not None and plan in (PLAN_MASK, PLAN_ROUTED):
            lo, hi, iv, ic = cp.as_arrays()
            return self._sharded.filtered_step(
                q_t, lo, hi, iv, ic, k=k, routed=(plan == PLAN_ROUTED))
        backend = self.index.backend
        up = self.index.config.use_pallas
        if plan == PLAN_FOLD:
            d2, ids, cert = _filtered_fold_step(backend, q_t, elig_j,
                                                k=k, kp=kp, use_pallas=up)
            need = ~np.asarray(cert)
            if need.any():
                # uncertified rows re-run under the exhaustive mask plan in
                # a pow-2 sub-batch (same pattern as _dense_subbatch)
                fidx = np.nonzero(need)[0]
                self.stats.filtered_fallbacks += len(fidx)
                sel = np.zeros((_subbatch_rows(len(fidx), b),), np.int64)
                sel[: len(fidx)] = fidx
                kpf = min(k + CANDIDATE_PAD, self.index.size)
                d2f, idsf = _filtered_mask_step(
                    backend, q_t[jnp.asarray(sel)], elig_j,
                    k=k, kp=kpf, use_pallas=up)
                take = jnp.asarray(fidx)
                d2 = d2.at[take].set(d2f[: len(fidx)])
                ids = ids.at[take].set(idsf[: len(fidx)])
            return d2, ids
        if plan == PLAN_MASK:
            return _filtered_mask_step(backend, q_t, elig_j, k=k, kp=kp,
                                       use_pallas=up)
        return _filtered_routed_step(backend, q_t, elig_j, uniq, n_live,
                                     k=k, kp=kp, use_pallas=up)

    def _dispatch_batch(self, q, f, k, n_real: int, alive):
        """One padded batch through the resilience envelope: bounded retry
        with exponential backoff on ``TransientShardError`` (raised by real
        dispatch failures or an attached fault injector), a per-batch
        deadline counter, and the heartbeat feed to the health layer."""
        attempt = 0
        while True:
            t0 = time.perf_counter()
            try:
                if self.fault_injector is not None:
                    self.fault_injector.before_batch()
                out = self._run_batch(q, f, k, n_real=n_real, alive=alive)
            except TransientShardError:
                attempt += 1
                self.stats.retries += 1
                if attempt > self.cfg.max_retries:
                    raise
                time.sleep(self.cfg.retry_backoff_s * (2 ** (attempt - 1)))
                continue
            elapsed = time.perf_counter() - t0
            if self.cfg.deadline_s and elapsed > self.cfg.deadline_s:
                self.stats.deadline_misses += 1
            if self.health is not None:
                if self.fault_injector is not None:
                    times = self.fault_injector.shard_times(
                        self.health.n_shards, elapsed)
                else:
                    # one shard_map dispatch: per-shard timing is not
                    # observable in-process, feed the batch wall time
                    times = [elapsed] * self.health.n_shards
                evicted = self.health.record_batch(times)
                self.stats.straggler_evictions += len(evicted)
            if alive is not None:
                self.stats.degraded_batches += 1
            return out

    def _run_batch(self, q, f, k, n_real: Optional[int] = None, alive=None):
        """One padded batch through the jitted step; escalation decided here
        (host-side bookkeeping), each stage a single compiled dispatch.

        Routed engines run the routed shard_map step first and re-run any
        query whose clipping flag is set through the DENSE step (same k'), so
        routed results always equal dense results end to end; the route mask
        feeds the off-trace router stats. Stage-2 escalation (and the routed
        fallback) runs ONLY the selected queries, gathered into a padded
        power-of-two sub-batch (so trace shapes stay bounded: one cached
        trace per bucket size) and scattered back — with the typical few-
        percent rates this is nearly free instead of re-running the whole
        batch. ``n_real`` caps both to the real rows of a padded batch:
        filler rows have data-dependent margins/flags and must not trigger
        (or count as) re-runs.

        ``alive`` (non-None = degraded mode) flows through EVERY stage —
        the routed step, the dense fallback, and the escalation sub-batch —
        so no stage can resurrect a dead shard's rows. Returns
        (scores, ids, covered): ``covered`` is the per-query coverage flag
        array capped to the real rows (None while healthy).
        """
        cfg = self.index.config
        degraded = alive is not None
        alpha = cfg.resolved_alpha()
        kp = theory.k_prime(k, cfg.lam, alpha, self.index.size, cfg.c)
        delta = self._ensure_delta()
        dvn = dfn = dflat = None
        kd = 0
        if delta is not None:
            nd = delta.vn.shape[0]
            kdp = theory.k_prime(k, cfg.lam, alpha, nd, cfg.c)
            kd = min(nd, max(kdp, 4 * k))
            dvn, dfn, dflat = delta.vn, delta.fn, delta.flat
        nr = q.shape[0] if n_real is None else n_real
        unc = None
        # stage 1 ends when its margins reach the host: stage 2 is decided
        # there, so the chip idles from then until stage 2 is dispatched
        with span("fcvi.step", kp=kp):
            if self._routed:
                out = self._sharded.step(
                    self._sharded_delta_view(dflat), q, f,
                    k=k, kp=kp, kd=kd, routed=True, alive=alive,
                    gather_free=self.cfg.gather_free)
                if degraded:
                    scores, ids, margin, flag, rmask, unc = out
                    unc = np.array(unc)
                else:
                    scores, ids, margin, flag, rmask = out
                rm = np.asarray(rmask)
                self.stats.routed_batches += 1
                self.stats.shard_steps += rm.shape[1]
                self.stats.shards_active += int(rm.any(axis=0).sum())
                need = np.asarray(flag)[:nr]
                if need.any():
                    idxs = np.nonzero(need)[0]
                    self.stats.router_fallbacks += len(idxs)
                    sub = self._dense_subbatch(dvn, dfn, dflat, q, f, idxs,
                                               k=k, kp=kp, kd=kd,
                                               alive=alive)
                    s2, i2, m2 = sub[:3]
                    take = jnp.asarray(idxs)
                    scores = scores.at[take].set(s2)
                    ids = ids.at[take].set(i2)
                    margin = margin.at[take].set(m2)
                    if degraded:
                        # the dense re-run's certificate (vs the dense k'-th
                        # candidate) supersedes the routed one for these rows
                        unc[idxs] = np.asarray(sub[3])
            else:
                out = self._step(dvn, dfn, dflat, q, f, k=k, kp=kp, kd=kd,
                                 alive=alive)
                if degraded:
                    scores, ids, margin, unc = out
                    unc = np.array(unc)
                else:
                    scores, ids, margin = out
            need = np.asarray(margin < self.cfg.escalate_margin)[:nr]
        if need.any():
            idxs = np.nonzero(need)[0]
            kp2 = theory.k_prime(k, cfg.lam, alpha, self.index.size,
                                 cfg.c * self.cfg.kprime_escalation)
            rows = _subbatch_rows(len(idxs), q.shape[0])
            self.stats.escalations += len(idxs)
            self.stats.escalation_rows += rows
            with span("fcvi.escalate", escalated=len(idxs), bucket=rows,
                      kp=kp2):
                sub = self._dense_subbatch(dvn, dfn, dflat, q, f, idxs,
                                           k=k, kp=kp2, kd=kd, alive=alive)
                s2, i2 = sub[:2]
                take = jnp.asarray(idxs)
                scores = scores.at[take].set(s2)
                ids = ids.at[take].set(i2)
                if degraded:
                    unc[idxs] = np.asarray(sub[3])
        covered = None if unc is None else ~unc[:nr]
        return scores, ids, covered

    def _dense_subbatch(self, dvn, dfn, dflat, q, f, idxs, *,
                        k: int, kp: int, kd: int, alive=None):
        """Re-run ``idxs`` (row indices into the padded batch) through the
        dense step in a padded power-of-two sub-batch; pad slots recompute
        query 0. Returns the step's output rows for ``idxs`` (3 outputs, 4
        with a degraded ``alive`` mask)."""
        sel = np.zeros((_subbatch_rows(len(idxs), q.shape[0]),), np.int64)
        sel[: len(idxs)] = idxs
        sel_j = jnp.asarray(sel)
        out = self._step(dvn, dfn, dflat, q[sel_j], f[sel_j],
                         k=k, kp=kp, kd=kd, alive=alive)
        n = len(idxs)
        return tuple(o[:n] for o in out)

    def _sharded_delta_view(self, dflat):
        """Lazily (re)shard the delta buffer for the shard_map steps."""
        if dflat is None:
            return None
        if self._sharded_delta is None:
            self._sharded_delta = self._sharded.shard_delta(self._delta)
        return self._sharded_delta

    def _step(self, dvn, dfn, dflat, q, f, *, k: int, kp: int, kd: int,
              alive=None):
        """Dispatch one padded batch to the single-device jitted step or the
        mesh-sharded DENSE shard_map step (identical results by
        construction; the routed step is dispatched by ``_run_batch``).
        ``cfg.gather_free`` picks the sharded step's re-rank variant."""
        if self._sharded is None:
            return _batch_step(self.index, dvn, dfn, dflat, q, f,
                               k=k, kp=kp, kd=kd)
        return self._sharded.step(self._sharded_delta_view(dflat), q, f,
                                  k=k, kp=kp, kd=kd, alive=alive,
                                  gather_free=self.cfg.gather_free)

    def _staged_query(self, q, f, k):
        """Pre-jit two-stage query WITHOUT the delta merge — kept as the
        faithful legacy baseline for benchmarks/query_path.py."""
        scores, ids = fcvi.query(self.index, q, f, k)
        margin = scores[:, 0] - scores[:, -1]
        need = np.asarray(margin < self.cfg.escalate_margin)
        if need.any():
            self.stats.escalations += int(need.sum())
            cfg = self.index.config
            kp2 = theory.k_prime(k, cfg.lam, cfg.resolved_alpha(),
                                 self.index.size,
                                 cfg.c * self.cfg.kprime_escalation)
            s2, i2 = fcvi.query(self.index, q, f, k, k_prime=kp2)
            sel = jnp.asarray(need)[:, None]
            scores = jnp.where(sel, s2, scores)
            ids = jnp.where(sel, i2, ids)
        return scores, ids

    def search_predicate(self, queries: np.ndarray, pred: BoxPredicate):
        """Range/disjunctive predicate -> multi-probe (§4.3)."""
        probes = np.asarray(pred.probes(self.cfg.multi_probe_r))  # (r, m)
        n = queries.shape[0]
        fp = jnp.broadcast_to(jnp.asarray(probes)[None],
                              (n, *probes.shape))
        return fcvi.multi_probe_query(self.index, jnp.asarray(queries), fp,
                                      self.cfg.k)

    # -- updates ----------------------------------------------------------
    def insert(self, vectors: np.ndarray, filters: np.ndarray):
        self._delta_v.append(np.asarray(vectors, np.float32))
        self._delta_f.append(np.asarray(filters, np.float32))
        self.stats.inserts += len(vectors)
        self._cache.clear()  # results may change
        self._delta = None   # invalidate; rebuilt lazily on the next search
        self._sharded_delta = None
        if sum(len(v) for v in self._delta_v) >= self.cfg.compact_threshold:
            self.compact()

    def delta_size(self) -> int:
        return sum(len(v) for v in self._delta_v)

    def _ensure_delta(self) -> Optional[_DeltaBuffer]:
        """Materialise the device-resident delta buffer on first use after an
        insert (lazy, so back-to-back inserts cost nothing until a query)."""
        if self._delta is None and self._delta_v:
            cfg = self.index.config
            tfm = self.index.transform
            vn = tfm.vec_norm.apply(jnp.asarray(np.concatenate(self._delta_v)))
            fn = tfm.filt_norm.apply(jnp.asarray(np.concatenate(self._delta_f)))
            self._delta = _DeltaBuffer(
                vn=vn, fn=fn,
                flat=flat_mod.build(tfm.apply_normalized(vn, fn),
                                    storage_dtype=cfg.resolved_storage_dtype()))
        return self._delta

    def compact(self):
        if not self._delta_v:
            return
        v = np.concatenate(self._delta_v)
        f = np.concatenate(self._delta_f)
        self.index = fcvi.extend(self.index, jnp.asarray(v), jnp.asarray(f))
        # the compacted rows' attribute values are the filters they were
        # inserted with; refresh the planner's selectivity histograms
        self._attrs_np = np.concatenate([self._attrs_np, f])
        self._col_means = self._attrs_np.mean(axis=0).astype(np.float32)
        self._rebuild_planner()
        self._delta_v, self._delta_f = [], []
        self._delta = None
        self._sharded_delta = None
        self._router_centers = None  # corpus changed: re-derive the router
        if self._sharded is not None:
            self._build_sharded()   # re-shard the grown slabs onto the mesh
        self.stats.compactions += 1

    # -- self-healing ------------------------------------------------------
    def heal(self, ckpt_dir: str, probe_queries=None, probe_filters=None,
             *, step: int = 0, background: bool = False):
        """Recover full coverage after shard loss via elastic re-place.

        checkpoint -> restore the FULL corpus onto a mesh of only the
        surviving devices (placement/routing preserved, so affinity packing
        is re-derived from the same router geometry) -> validate the
        candidate engine bit-identically against a meshless restore of the
        same checkpoint on ``probe_queries``/``probe_filters`` -> cut over
        under the heal lock (swap index/mesh/sharded state, fresh health
        layer, cache cleared). After a successful heal every row is served
        again and coverage returns to 100%.

        Returns True on a validated cutover, False when validation failed
        (the degraded engine keeps serving untouched). ``background=True``
        runs the same flow on a daemon thread and returns it (join it, then
        check ``stats.heals``). Requires a mesh-backed engine with one
        device per shard and at least one surviving device.
        """
        if background:
            t = threading.Thread(
                target=self.heal, args=(ckpt_dir, probe_queries,
                                        probe_filters),
                kwargs={"step": step}, daemon=True)
            t.start()
            return t
        if self._sharded is None or self.health is None:
            raise RuntimeError("heal() requires a mesh-backed engine")
        devices = np.asarray(self._mesh.devices).reshape(-1)
        if self._sharded.n_shards != devices.size:
            raise NotImplementedError(
                "heal() assumes one shard per mesh device")
        alive_idx = np.nonzero(self.health.alive_mask())[0]
        if alive_idx.size == 0:
            raise RuntimeError("heal() needs at least one surviving shard")
        self.save(ckpt_dir, step=step)
        from jax.sharding import Mesh

        shape = (alive_idx.size,) + (1,) * (len(self._mesh.axis_names) - 1)
        new_mesh = Mesh(devices[alive_idx].reshape(shape),
                        self._mesh.axis_names)
        cand = FCVIEngine.restore(ckpt_dir, step=step, config=self.cfg,
                                  mesh=new_mesh, rules=self._rules,
                                  placement=self._placement,
                                  routing=self._routing)
        if probe_queries is not None:
            ref = FCVIEngine.restore(ckpt_dir, step=step, config=self.cfg)
            s_new, i_new = cand.search(probe_queries, probe_filters)
            s_ref, i_ref = ref.search(probe_queries, probe_filters)
            if not (np.array_equal(s_new, s_ref)
                    and np.array_equal(i_new, i_ref)):
                return False
        with self._heal_lock:
            self.index = cand.index
            self._mesh = new_mesh
            self._attrs_np = cand._attrs_np
            self._attr_names = cand._attr_names
            self._col_means = cand._col_means
            self.planner = cand.planner
            self._router_centers = cand._router_centers
            self._sharded = cand._sharded
            self._sharded_delta = cand._sharded_delta
            self._delta_v = cand._delta_v
            self._delta_f = cand._delta_f
            self._delta = cand._delta
            self.health = ShardHealth(self._sharded.n_shards,
                                      straggler_z=self.cfg.straggler_z)
            self._alive_sig = None
            self._cache.clear()
            self.stats.heals += 1
        return True

    # -- checkpoint lifecycle ---------------------------------------------
    def save(self, ckpt_dir: str, step: int = 0, keep: int = 3) -> str:
        """Checkpoint the full serving state (build -> checkpoint -> restore
        -> serve lifecycle).

        Saves the transform + backend source arrays + re-rank originals via
        ``fcvi.index_state`` (derived serving slabs are rebuilt at restore
        time by the slab layer) plus any PENDING delta rows, with the static
        configs — including the serving placement/routing knobs — in the
        manifest metadata. Cluster-placed flat engines also save the router's
        psi-cluster centers ((ncl, d) fp32) so a restored engine derives the
        SAME routing tables (labels, radii, shard incidence) on any target
        mesh instead of re-running k-means. Sharded arrays are gathered to
        host transparently by the checkpoint writer.
        """
        d = self.index.transform.vec_norm.mean.shape[-1]
        m = self.index.transform.filt_norm.mean.shape[-1]
        dv = (np.concatenate(self._delta_v) if self._delta_v
              else np.zeros((0, d), np.float32))
        df = (np.concatenate(self._delta_f) if self._delta_f
              else np.zeros((0, m), np.float32))
        tree = {"index": fcvi.index_state(self.index),
                "delta_v": dv, "delta_f": df,
                "attrs": self._attrs_np}
        if (self._sharded is not None
                and getattr(self._sharded.slab, "router_centers", None)
                is not None):
            tree["router"] = {
                "centers": np.asarray(self._sharded.slab.router_centers)}
        metadata = {
            "fcvi_config": dataclasses.asdict(self.index.config),
            "engine_config": dataclasses.asdict(self.cfg),
            "serving": {"placement": self._placement,
                        "routing": self._routing,
                        "attr_names": list(self._attr_names)},
        }
        return ckpt_mod.save(ckpt_dir, step, tree, metadata=metadata,
                             keep=keep)

    @classmethod
    def restore(cls, ckpt_dir: str, *, step: Optional[int] = None,
                config: Optional[EngineConfig] = None, mesh=None, rules=None,
                placement: Optional[str] = None,
                routing: Optional[str] = None) -> "FCVIEngine":
        """Restore an engine from a checkpoint onto ANY target mesh.

        The elastic-restart path: arrays come back replicated on host, the
        index is rebuilt without re-training (k-means state is part of the
        checkpoint), and — when ``mesh`` is given — the slab layer re-lays
        the serving state out over the TARGET mesh, which may have a
        different shape than the mesh the checkpoint was written from.
        ``placement``/``routing`` default to the values the engine was saved
        with (pass explicitly to override); saved router centers are reused,
        so a routed engine restored onto any mesh routes from the same
        psi-cluster geometry it served with. ``mesh=None`` always serves the
        single-device step (routing needs shards to skip).
        """
        tree, _, metadata = ckpt_mod.load(ckpt_dir, step=step)
        fcfg = FCVIConfig(**metadata["fcvi_config"])
        index = fcvi.index_from_state(fcfg, tree["index"])
        ecfg = (config if config is not None
                else EngineConfig(**metadata["engine_config"]))
        serving = metadata.get("serving", {})
        if placement is None:
            placement = serving.get("placement", "contiguous")
        if routing is None:
            routing = serving.get("routing", "dense")
        if mesh is None:
            routing = "dense"
        centers = None
        if "router" in tree:
            centers = jnp.asarray(tree["router"]["centers"], jnp.float32)
        eng = cls(index, ecfg, mesh=mesh, rules=rules, placement=placement,
                  routing=routing, router_centers=centers,
                  attributes=tree.get("attrs"),
                  attr_names=serving.get("attr_names"))
        if tree["delta_v"].shape[0]:
            eng._delta_v = [np.asarray(tree["delta_v"], np.float32)]
            eng._delta_f = [np.asarray(tree["delta_f"], np.float32)]
            eng.stats.inserts = int(tree["delta_v"].shape[0])
        return eng

"""FCVIIndex — the paper's Algorithm 1 as a composable JAX module.

Offline: fit per-dim normalizers, fit psi (partition / cluster / embedding),
transform the corpus, build ANY backend index (flat / IVF / PQ) over the
transformed vectors, keep the normalized originals for re-scoring.

Online: transform the query with its filter vector, over-retrieve
k' = min(c * k/lambda * 1/alpha^2, N) (Thm 5.4), re-score candidates with
score = lambda*sim(v,q) + (1-lambda)*sim(f,F_q), return top-k.

Kernel-backed dispatch: every backend implements the
``repro.index.SearchBackend`` protocol, and ``FCVIConfig.use_pallas``
threads through the whole query path —

  * the query transform runs as ONE fused kernel
    (``Transform.apply_normalized(..., use_pallas=True)`` ->
    ``ops.fused_transform``) instead of 4+ jnp ops,
  * candidate generation runs the fused Pallas kernels
    (``ops.score_topk_padded`` / ``ops.ivf_score_topk_dedup`` over
    batch-deduplicated probes / ``ops.pq_score_batch``) instead of the
    pure-jnp scans, with the IVF coarse quantizer itself a small
    ``ops.score_topk_padded`` call,
  * re-scoring (``rescore`` and ``multi_probe_query``) runs the fused
    combined-cosine kernel ``ops.rescore``.

``FCVIConfig.storage_dtype`` additionally selects the flat/IVF slab storage
rung: "bfloat16" stores at half width for ~2x effective HBM bandwidth on the
scan-bound paths, "int8" at quarter width with one fp32 dequant scale per
row (``repro.index.quant``). Both keep fp32 accumulation plus the
exact-refine pass, so orderings stay correct.

With ``use_pallas=False`` (the default) the same call graph runs the jnp
reference implementations; the two paths return identical results (see
``tests/test_parity_pallas.py``), so the switch is a pure performance knob
that can be A/B-checked per call site.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import theory
from repro.core.transform import Transform, fit_transform
from repro.index import flat as flat_mod
from repro.index import ivf as ivf_mod
from repro.index import pq as pq_mod
from repro.index.backend import SearchBackend
from repro.kernels import ops
from repro.kernels import ref

Array = jax.Array

BACKENDS = ("flat", "ivf", "pq")


@dataclasses.dataclass(frozen=True)
class FCVIConfig:
    """Static configuration of an FCVI index (hashable; the jit-static aux
    of the ``FCVIIndex`` pytree).

    Semantics-bearing fields: ``alpha`` (filter fold strength — larger
    separates filter groups harder), ``lam`` (combined-score weight),
    ``c`` (k' over-retrieval headroom), ``mode`` (psi variant), ``backend``
    + its shape knobs (``n_clusters``/``nlist``/``nprobe``/``pq_*``).

    Dispatch-changing fields (results stay IDENTICAL, only the executed
    code changes): ``use_pallas`` routes the query path through the Pallas
    kernels in ``repro.kernels.ops`` (False = pure-jnp reference), and
    ``storage_dtype`` selects the corpus slab precision ("float32",
    "bfloat16" or "int8"; reduced storage keeps fp32 norms/accumulation
    plus the exact-refine pass, so top-k ordering is exact w.r.t. stored
    rows — int8 additionally carries one fp32 scale per row).
    """

    alpha: float = 1.0
    lam: float = 0.5            # lambda in [0,1]: 1 => pure vector similarity
    c: float = 4.0              # k' headroom constant (Alg. 1 line 7)
    mode: str = "partition"     # psi variant
    backend: str = "flat"
    n_clusters: int = 16        # cluster mode
    nlist: int = 64             # IVF
    nprobe: int = 8
    pq_m: int = 8               # PQ subspaces
    pq_ksub: int = 256
    pq_coarse: int = 32         # residual-PQ coarse centers
    auto_alpha: bool = False    # alpha = max(1, sqrt((1-lam)/lam)), Thm 5.4
    normalize: bool = True
    use_pallas: bool = False    # route the query path through Pallas kernels
    storage_dtype: str = "float32"  # corpus storage for flat/IVF slabs
                                    # ("bfloat16" halves HBM traffic, "int8"
                                    # quarters it with per-row scales; scores
                                    # accumulate in fp32 and the exact-refine
                                    # pass keeps top-k ordering correct)
    def resolved_alpha(self) -> float:
        if self.auto_alpha:
            return float(theory.optimal_alpha(self.lam))
        return max(1.0, float(self.alpha))

    def resolved_storage_dtype(self):
        """Backend build-time dtype: None means keep the native fp32 (the
        backends' "don't cast" sentinel), else the reduced-precision dtype."""
        if self.storage_dtype not in ("float32", "bfloat16", "int8"):
            raise ValueError(
                f"storage_dtype must be float32, bfloat16 or int8, got "
                f"{self.storage_dtype!r}")
        if self.storage_dtype == "float32":
            return None
        return jnp.dtype(self.storage_dtype)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FCVIIndex:
    config: FCVIConfig          # static
    transform: Transform
    backend: SearchBackend      # FlatIndex | IVFIndex | PQIndex (transformed space)
    vectors_n: Array            # (n, d) normalized originals (for re-scoring)
    filters_n: Array            # (n, m) normalized filters

    def tree_flatten(self):
        return (self.transform, self.backend, self.vectors_n, self.filters_n), self.config

    @classmethod
    def tree_unflatten(cls, config, children):
        return cls(config, *children)

    @property
    def size(self) -> int:
        return self.vectors_n.shape[0]


def cosine_sim(a: Array, b: Array, eps: float = 1e-8) -> Array:
    """cos(a, b) over the last axis, in the re-rank's fixed order
    (``ref.cosine``)."""
    return ref.cosine(a, b, eps)


def build(vectors: Array, filters: Array, config: FCVIConfig,
          rng: Optional[Array] = None) -> FCVIIndex:
    """Offline indexing (Alg. 1 lines 1-5)."""
    if config.backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    alpha = config.resolved_alpha()
    tfm = fit_transform(
        vectors, filters, alpha, config.mode,
        n_clusters=config.n_clusters, rng=rng, normalize=config.normalize,
    )
    vn, fn, transformed = _normalize_and_transform(tfm, vectors, filters)
    backend = build_backend(transformed, config, rng=rng)
    assert isinstance(backend, SearchBackend)
    return FCVIIndex(config=config, transform=tfm, backend=backend,
                     vectors_n=vn, filters_n=fn)


@jax.jit
def _normalize_and_transform(tfm: Transform, vectors: Array, filters: Array):
    """(normalized vectors, normalized filters, transformed corpus) as one
    fused pass: op by op, each (n, d) intermediate would be materialized
    beside the corpus, which is what bounds the corpus a chip can build."""
    vn = tfm.vec_norm.apply(vectors)
    fn = tfm.filt_norm.apply(filters)
    return vn, fn, tfm.apply_normalized(vn, fn)


def build_backend(transformed: Array, config: FCVIConfig,
                  rng: Optional[Array] = None) -> SearchBackend:
    """Build the configured backend over transformed vectors, with the
    configured storage dtype threaded into the flat/IVF slab layouts (PQ
    stores quantized codes already, so the knob does not apply there)."""
    st = config.resolved_storage_dtype()
    if config.backend == "flat":
        return flat_mod.build(transformed, storage_dtype=st)
    if config.backend == "ivf":
        return ivf_mod.build(transformed, nlist=config.nlist, rng=rng,
                             storage_dtype=st)
    return pq_mod.build(transformed, m_subspaces=config.pq_m,
                        ksub=config.pq_ksub, ncoarse=config.pq_coarse,
                        rng=rng)


def _backend_search(index: FCVIIndex, q_t: Array, kp: int):
    cfg = index.config
    if cfg.backend == "ivf":
        return index.backend.search(q_t, kp, use_pallas=cfg.use_pallas,
                                    nprobe=cfg.nprobe)
    return index.backend.search(q_t, kp, use_pallas=cfg.use_pallas)


def _pad_rows(x: Array, mult: int) -> Array:
    pad = -x.shape[0] % mult
    if not pad:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((pad, *x.shape[1:]), x.dtype)], axis=0)


def combined_score(cand_v: Array, cand_f: Array, qn: Array, fqn: Array,
                   lam, *, use_pallas: bool = False) -> Array:
    """score = lam*cos(v, q) + (1-lam)*cos(f, F_q) per candidate.

    cand_v: (b, kp, d); cand_f: (b, kp, m); qn: (b, d); fqn: (b, m).
    With ``use_pallas`` the fused re-ranking kernel computes both cosines and
    the affine combine in one VMEM pass (batch zero-padded to the kernel's
    block multiple; zero rows score 0 and are sliced off).
    """
    if not use_pallas:
        # Bit-stability contract: every serving path (single-device gather,
        # one-hot psum gather, shard-local gather in the gather-free step)
        # must score a candidate to the same bits.  The elementwise mul+sum
        # cosine reduces each row independently, so a candidate's score does
        # not depend on its k-position in the tile — unlike a dot_general
        # contraction, whose CPU lowering handles main-loop vs remainder
        # k-rows differently. Each row sums in one fixed order
        # (``ref.lane_sum``), whatever layout its producer (a gather on one
        # path, an all-reduce on another) leaves it in, and no product is
        # fused into a multiply-add (``ref.rounded``), whatever the batch
        # shape. The Pallas kernel computes the same ``ref.ref_rescore``.
        return ref.ref_rescore(cand_v, cand_f, qn, fqn, lam)
    b = cand_v.shape[0]
    bb = min(8, b)
    s = ops.rescore(_pad_rows(cand_v, bb), _pad_rows(cand_f, bb),
                    _pad_rows(qn, bb), _pad_rows(fqn, bb), lam, block_b=bb)
    return s[:b]


def rescore(index: FCVIIndex, qn: Array, fqn: Array, cand_idx: Array, k: int):
    """Alg. 1 lines 10-16: combined-score re-ranking of candidates.

    qn: (b, d) normalized queries; fqn: (b, m); cand_idx: (b, k').
    Returns (scores (b,k), ids (b,k)).
    """
    cv = index.vectors_n[cand_idx]               # (b, k', d)
    cf = index.filters_n[cand_idx]               # (b, k', m)
    score = combined_score(cv, cf, qn, fqn, index.config.lam,
                           use_pallas=index.config.use_pallas)
    vals, pos = jax.lax.top_k(score, k)
    return vals, jnp.take_along_axis(cand_idx, pos, axis=-1)


@partial(jax.jit, static_argnames=("k", "k_prime"))
def query(index: FCVIIndex, q: Array, f_q: Array, k: int,
          k_prime: Optional[int] = None):
    """Online query processing (Alg. 1 lines 6-16). Batched.

    q: (b, d); f_q: (b, m). Returns (scores (b,k), ids (b,k)).
    """
    cfg = index.config
    kp = k_prime if k_prime is not None else theory.k_prime(
        k, cfg.lam, cfg.resolved_alpha(), index.size, cfg.c)
    qn, fqn = index.transform.normalize(q, f_q)
    q_t = index.transform.apply_normalized(qn, fqn, use_pallas=cfg.use_pallas)
    _, cand = _backend_search(index, q_t, kp)
    return rescore(index, qn, fqn, cand, k)


@partial(jax.jit, static_argnames=("k", "k_prime"))
def multi_probe_query(index: FCVIIndex, q: Array, filter_probes: Array, k: int,
                      k_prime: Optional[int] = None):
    """Range/disjunctive filters (§4.3): probe r representative filter vectors,
    merge + dedup candidates, re-score all, return top-k.

    q: (b, d); filter_probes: (b, r, m) raw filter representatives.
    """
    cfg = index.config
    b, r, m = filter_probes.shape
    kp = k_prime if k_prime is not None else theory.k_prime(
        k, cfg.lam, cfg.resolved_alpha(), index.size, cfg.c)

    qn = index.transform.vec_norm.apply(q)
    fqn = index.transform.filt_norm.apply(filter_probes)       # (b, r, m)
    q_rep = jnp.broadcast_to(qn[:, None, :], (b, r, qn.shape[-1]))
    q_t = index.transform.apply_normalized(q_rep, fqn,
                                           use_pallas=cfg.use_pallas)  # (b, r, d)
    _, cand = _backend_search(index, q_t.reshape(b * r, -1), kp)
    cand = cand.reshape(b, r * kp)
    # dedup: demote duplicate ids so they cannot crowd the candidate set
    sorted_cand = jnp.sort(cand, axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros((b, 1), bool), sorted_cand[:, 1:] == sorted_cand[:, :-1]], axis=-1)
    cv = index.vectors_n[sorted_cand]
    cf = index.filters_n[sorted_cand]
    # filter sim against the NEAREST probe: lam*cos(v,q) is constant across
    # probes, so score = lam*s_v + (1-lam)*max_r s_f_r. The expensive s_v
    # pass over the (b, r*kp, d) candidate tensor runs ONCE (lam=1 makes the
    # fused kernel return pure cos(v,q)); the per-probe passes only touch the
    # small (b, r*kp, m) filter tensor (cf stands in for both kernel operands,
    # which collapses the combine to cos(cf, probe) for any lam).
    s_v = combined_score(cv, cf, qn, fqn[:, 0], 1.0,
                         use_pallas=cfg.use_pallas)
    s_f = combined_score(cf, cf, fqn[:, 0], fqn[:, 0], 0.0,
                         use_pallas=cfg.use_pallas)
    for j in range(1, r):
        s_f = jnp.maximum(
            s_f, combined_score(cf, cf, fqn[:, j], fqn[:, j], 0.0,
                                use_pallas=cfg.use_pallas))
    score = cfg.lam * s_v + (1.0 - cfg.lam) * s_f
    score = jnp.where(dup, -jnp.inf, score)
    vals, pos = jax.lax.top_k(score, k)
    return vals, jnp.take_along_axis(sorted_cand, pos, axis=-1)


# ---------------------------------------------------------------------------
# Predicate (filtered) search support
# ---------------------------------------------------------------------------

def filters_raw(index: FCVIIndex) -> Array:
    """Raw-space attribute table recovered from the stored normalized filters.

    Predicates evaluate over RAW attribute values (``repro.core.filters``);
    an engine built with an explicit ``attributes=`` table uses that, and
    this inverse is the fallback when only the normalized copy exists.
    """
    return index.transform.filt_norm.inverse(index.filters_n)


def fold_queries(index: FCVIIndex, q: Array, fold_raw) -> Array:
    """Transform raw queries against a predicate's raw fold target.

    ``fold_raw`` is the single representative filter point the planner
    derives per predicate (``CompiledPredicate.fold_target_raw``); all of a
    predicate's candidates are scored in this one transformed frame, so
    every physical plan for the predicate ranks identically.
    """
    return index.transform.fold_query(
        q, jnp.asarray(fold_raw, jnp.float32),
        use_pallas=index.config.use_pallas)


# ---------------------------------------------------------------------------
# Ground truth + recall (evaluation oracles)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k",))
def ground_truth_combined(vectors_n: Array, filters_n: Array, qn: Array,
                          fqn: Array, k: int, lam: float):
    """Exact top-k under the paper's combined score (the recall reference)."""
    s_v = cosine_sim(vectors_n[None, :, :], qn[:, None, :])
    s_f = cosine_sim(filters_n[None, :, :], fqn[:, None, :])
    score = lam * s_v + (1.0 - lam) * s_f
    return jax.lax.top_k(score, k)


def recall_at_k(pred_ids: Array, true_ids: Array) -> Array:
    """|pred ∩ true| / k, averaged over the query batch."""
    hits = (pred_ids[:, :, None] == true_ids[:, None, :]).any(-1)
    return jnp.mean(jnp.mean(hits.astype(jnp.float32), axis=-1))


# ---------------------------------------------------------------------------
# Checkpointable state (build -> checkpoint -> restore -> serve lifecycle)
# ---------------------------------------------------------------------------

def index_state(index: FCVIIndex) -> dict:
    """The checkpointable array state of an index, as a nested dict pytree.

    Contains the fitted transform, the re-rank originals and the backend's
    SOURCE arrays only — derived serving layouts (squared norms, the IVF
    grouped slabs, the PQ build-time LUT terms) are rematerialised by
    ``index_from_state``, so checkpoints stay roughly corpus-sized. Paired
    with ``repro.checkpoint.ckpt``: ``ckpt.save(dir, step, index_state(ix))``
    then ``index_from_state(cfg, ckpt.load(dir)[0])``.
    """
    tfm = index.transform
    t = {"alpha": tfm.alpha,
         "vec_mean": tfm.vec_norm.mean, "vec_std": tfm.vec_norm.std,
         "filt_mean": tfm.filt_norm.mean, "filt_std": tfm.filt_norm.std}
    if tfm.centers is not None:
        t["centers"] = tfm.centers
    if tfm.proj is not None:
        t["proj"] = tfm.proj
    cfg = index.config
    b = index.backend
    if cfg.backend == "flat":
        bstate = {"vectors": b.vectors}
        if b.scales is not None:
            bstate["scales"] = b.scales
    elif cfg.backend == "ivf":
        bstate = {"vectors": b.vectors, "centroids": b.centroids,
                  "lists": b.lists, "list_sizes": b.list_sizes}
        if b.scales is not None:
            bstate["scales"] = b.scales
    else:
        bstate = {"codebooks": b.codebooks, "codes": b.codes,
                  "coarse_centers": b.coarse_centers,
                  "coarse_ids": b.coarse_ids}
    return {"transform": t, "backend": bstate,
            "vectors_n": index.vectors_n, "filters_n": index.filters_n}


def index_from_state(config: FCVIConfig, state: dict) -> FCVIIndex:
    """Rebuild an ``FCVIIndex`` from ``index_state`` output (no re-training:
    the fitted normalizers / k-means state come from the checkpoint; only the
    derived serving layouts are rematerialised)."""
    from repro.core.transform import Normalizer

    t = state["transform"]
    tfm = Transform(
        mode=config.mode,
        alpha=jnp.asarray(t["alpha"], jnp.float32),
        vec_norm=Normalizer(mean=jnp.asarray(t["vec_mean"]),
                            std=jnp.asarray(t["vec_std"])),
        filt_norm=Normalizer(mean=jnp.asarray(t["filt_mean"]),
                             std=jnp.asarray(t["filt_std"])),
        centers=jnp.asarray(t["centers"]) if "centers" in t else None,
        proj=jnp.asarray(t["proj"]) if "proj" in t else None,
    )
    from repro.index import quant

    b = state["backend"]
    if config.backend == "flat":
        vectors = jnp.asarray(b["vectors"])
        scales = jnp.asarray(b["scales"]) if "scales" in b else None
        if scales is not None:
            sq_norms = quant.sq_norms_of(vectors, scales)
        else:
            sq_norms = jnp.sum(vectors.astype(jnp.float32) ** 2, axis=-1)
        backend = flat_mod.FlatIndex(vectors=vectors, sq_norms=sq_norms,
                                     scales=scales)
    elif config.backend == "ivf":
        from repro.index.slab import build_grouped

        vectors = jnp.asarray(b["vectors"])
        lists = jnp.asarray(b["lists"])
        scales = jnp.asarray(b["scales"]) if "scales" in b else None
        if scales is not None:
            sq_norms = quant.sq_norms_of(vectors, scales)
            grouped_scales = ivf_mod._group_scales(scales, lists)
        else:
            sq_norms = jnp.sum(vectors.astype(jnp.float32) ** 2, axis=-1)
            grouped_scales = None
        grouped, grouped_sq, valid = build_grouped(vectors, sq_norms, lists)
        backend = ivf_mod.IVFIndex(
            vectors=vectors, sq_norms=sq_norms,
            centroids=jnp.asarray(b["centroids"]), lists=lists,
            list_sizes=jnp.asarray(b["list_sizes"]),
            grouped=grouped, grouped_sq=grouped_sq, valid=valid,
            scales=scales, grouped_scales=grouped_scales)
    else:
        codebooks = jnp.asarray(b["codebooks"])
        coarse_centers = jnp.asarray(b["coarse_centers"])
        ncoarse = coarse_centers.shape[0]
        m, ksub, dsub = codebooks.shape
        centers_sub = coarse_centers.reshape(ncoarse, m, dsub)
        backend = pq_mod.PQIndex(
            codebooks=codebooks, codes=jnp.asarray(b["codes"]),
            coarse_centers=coarse_centers,
            coarse_ids=jnp.asarray(b["coarse_ids"]),
            cb_sq=jnp.sum(codebooks * codebooks, axis=-1),
            coarse_dot=jnp.einsum("cmd,mkd->cmk", centers_sub, codebooks))
    return FCVIIndex(config=config, transform=tfm, backend=backend,
                     vectors_n=jnp.asarray(state["vectors_n"]),
                     filters_n=jnp.asarray(state["filters_n"]))


# ---------------------------------------------------------------------------
# Updates: delta buffer + compaction (production insert path)
# ---------------------------------------------------------------------------

def extend(index: FCVIIndex, new_vectors: Array, new_filters: Array) -> FCVIIndex:
    """Append new rows and rebuild the backend over the transformed corpus.

    Normalizer/centers are kept frozen (same geometry; matches the paper's
    'incremental filter updates' §4.2 — a full refit is a separate offline
    job). The serving engine batches inserts in a delta FlatIndex and calls
    this on compaction.
    """
    tfm = index.transform
    vn_new = tfm.vec_norm.apply(new_vectors)
    fn_new = tfm.filt_norm.apply(new_filters)
    vectors_n = jnp.concatenate([index.vectors_n, vn_new], axis=0)
    filters_n = jnp.concatenate([index.filters_n, fn_new], axis=0)
    transformed = tfm.apply_normalized(vectors_n, filters_n)
    backend = build_backend(transformed, index.config)
    return FCVIIndex(config=index.config, transform=tfm, backend=backend,
                     vectors_n=vectors_n, filters_n=filters_n)

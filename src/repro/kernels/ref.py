"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``ref_*`` function is the semantic ground truth; kernel tests sweep
shapes/dtypes and assert allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def partition_matrix(d: int, m: int, dtype=jnp.float32) -> Array:
    """P in R^{m x d} with P[i, j] = 1 iff j % m == i.

    psi_partition(v, f, a) == v - a * (f @ P): subtracting f from every
    m-segment equals one (m x d) matmul — this turns all three psi variants
    into the same fused kernel (partition: P; embedding: W^T; cluster: P
    applied to substituted centers).
    """
    cols = jnp.arange(d) % m
    return (cols[None, :] == jnp.arange(m)[:, None]).astype(dtype)


def ref_fused_transform(v: Array, f: Array, proj: Array, alpha,
                        mean_v: Array, std_v: Array,
                        mean_f: Array, std_f: Array) -> Array:
    """Fused normalize + psi: ((v-mu)/sd) - alpha * ((f-mu_f)/sd_f) @ proj."""
    vn = (v - mean_v) / std_v
    fn = (f - mean_f) / std_f
    return vn - alpha * (fn @ proj)


def ref_score_topk(corpus: Array, sq_norms: Array, queries: Array, k: int,
                   scales=None, mask=None):
    """Exact negative-squared-L2 top-k: the serving inner loop.

    ``scales`` (n,) is the int8 storage rung's per-row dequant scale; like
    the kernel it multiplies the matmul OUTPUT column (fp32 accumulation).
    ``mask`` (n,) float 0/1 is the filter algebra's candidate mask: rows at
    0 score -inf (their ids collapse to 0 like every other dead slot).
    """
    q2 = jnp.sum(queries * queries, axis=-1, keepdims=True)
    dot = queries @ corpus.astype(queries.dtype).T
    if scales is not None:
        dot = dot * scales[None, :]
    scores = -(q2 - 2.0 * dot + sq_norms[None, :])
    if mask is not None:
        scores = jnp.where(mask[None, :] > 0.5, scores, -jnp.inf)
    vals, ids = jax.lax.top_k(scores, k)
    if mask is not None:
        ids = jnp.where(jnp.isneginf(vals), 0, ids)
    return vals, ids


def rounded(product):
    """A product rounded to fp32 before anything adds to it. The CPU
    backend contracts a multiply and a dependent add into one fused
    multiply-add (a single rounding) in some loop shapes and not in others,
    so an unguarded ``x * y + z`` rounds differently from one batch size or
    program to the next. The select keeps the multiply out of reach; a
    non-finite product becomes NaN, which the cosine yields for it anyway."""
    return jnp.where(jnp.abs(product) < jnp.inf, product, jnp.nan)


def lane_sum(x):
    """Sum over the last axis in one fixed pairwise order: elementwise adds
    of two halves, down to one lane. A width that is not a power of two is
    split into its power-of-two parts (768 = 512 + 256), summed in order, so
    nothing is padded. A plain reduction is free to pick its summation order
    from the operand's layout and its fusion, so the same row could round
    differently in two programs — e.g. when its rows come from an
    all-reduce in a sharded step and from a gather in the meshless one."""
    n = x.shape[-1]
    top = 1 << (n.bit_length() - 1)
    if top != n:
        return lane_sum(x[..., :top]) + lane_sum(x[..., top:])
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x[..., 0]


def cosine(a, b, eps: float = 1e-8, total=lane_sum):
    """cos(a, b) over the last axis (a and b broadcast): each product is
    ``rounded`` and each row is summed by ``total`` (``lane_sum``'s fixed
    order unless a caller passes another reduction). A mul+sum, not a
    dot_general contraction: each row reduces on its own, so a candidate's
    score does not depend on its k-position or the tile width (a
    contraction's CPU lowering treats main-loop vs remainder k-rows
    differently, and the routed path re-scores the same candidate at a
    different k' than the dense path)."""
    num = total(rounded(a * b))
    nn = jnp.sqrt(total(rounded(a * a))) * jnp.sqrt(total(rounded(b * b)))
    return num / (rounded(nn) + eps)


def ref_rescore(cand_v, cand_f, qn, fqn, lam, total=lane_sum):
    """lam * cos(v, q) + (1 - lam) * cos(f, F_q) per candidate (Alg. 1 line
    13): the one definition that the XLA re-rank (``fcvi.combined_score``)
    and the Pallas kernel (``rescore.py``, whose body this is) share. The
    kernel passes the native lane reduction as ``total``
    (``rescore.py`` says why), so its sums may round differently.

    cand_v: (b, kp, d); cand_f: (b, kp, m); qn: (b, d); fqn: (b, m).
    """
    return (rounded(lam * cosine(cand_v, qn[:, None, :], total=total))
            + rounded((1.0 - lam) * cosine(cand_f, fqn[:, None, :],
                                           total=total)))


def ref_ivf_score_topk(grouped: Array, grouped_sq: Array, valid: Array,
                       probes: Array, query: Array, k: int):
    """IVF probed-slab scoring for ONE query.

    grouped: (nlist, max_list, d) corpus grouped by list; valid: (nlist,
    max_list) bool; probes: (nprobe,) list ids. Returns (vals, flat_ids)
    where flat_ids index into grouped.reshape(-1, d).
    """
    slabs = grouped[probes]            # (nprobe, max_list, d)
    sq = grouped_sq[probes]
    ok = valid[probes]
    q2 = jnp.sum(query * query)
    s = -(q2 - 2.0 * slabs @ query + sq)
    s = jnp.where(ok, s, -jnp.inf)
    max_list = grouped.shape[1]
    flat_ids = probes[:, None] * max_list + jnp.arange(max_list)[None, :]
    s = s.reshape(-1)
    vals, pos = jax.lax.top_k(s, k)
    return vals, flat_ids.reshape(-1)[pos]


def ref_ivf_score_topk_batch(grouped: Array, grouped_sq: Array, valid: Array,
                             probes: Array, queries: Array, k: int,
                             scales=None):
    """Batched IVF probed-slab scoring in the KERNEL's score convention.

    probes: (b, nprobe); queries: (b, d). Returns (vals (b, k), flat_ids
    (b, k)) with scores 2<x,q> - ||x||^2 (the ||q||^2 constant dropped, like
    the Pallas kernel) and flat ids into grouped.reshape(-1, d).
    ``scales`` (nlist, max_list): int8 per-row dequant of the dot output.
    """
    max_list = grouped.shape[1]

    def one(probe, query):
        slabs = grouped[probe]                     # (nprobe, max_list, d)
        sq = grouped_sq[probe]
        ok = valid[probe]
        s = 2.0 * (slabs.astype(query.dtype) @ query)
        if scales is not None:
            s = s * scales[probe]
        s = s - sq
        s = jnp.where(ok, s, -jnp.inf)
        flat_ids = probe[:, None] * max_list + jnp.arange(max_list)[None, :]
        vals, pos = jax.lax.top_k(s.reshape(-1), k)
        ids = flat_ids.reshape(-1)[pos]
        return vals, jnp.where(jnp.isneginf(vals), 0, ids)

    return jax.vmap(one)(probes, queries)


def _dedup_scores(grouped, grouped_sq, valid, uniq, member, queries,
                  scales=None, mask=None):
    """Shared (b, s*max_list) masked score matrix + flat id map for the
    dedup oracles (kernel score convention)."""
    max_list = grouped.shape[1]
    slabs = grouped[uniq]                              # (s, max_list, d)
    sq = grouped_sq[uniq]
    ok = valid[uniq]
    if mask is not None:
        ok = ok & (mask[uniq] > 0.5)
    s = 2.0 * jnp.einsum("bd,smd->bsm", queries,
                         slabs.astype(queries.dtype))
    if scales is not None:
        s = s * scales[uniq][None]
    s = s - sq[None]
    keep = ok[None, :, :] & member.T[:, :, None]       # (b, s, max_list)
    s = jnp.where(keep, s, -jnp.inf)
    flat_ids = (uniq[:, None] * max_list
                + jnp.arange(max_list)[None, :]).reshape(-1)
    return s.reshape(s.shape[0], -1), flat_ids


def ref_ivf_score_topk_dedup(grouped: Array, grouped_sq: Array, valid: Array,
                             uniq: Array, member: Array, queries: Array,
                             k: int, scales=None, mask=None):
    """Probe-major deduplicated slab scoring (the dedup kernel's oracle).

    uniq: (s,) unique probed list ids; member: (s, b) bool — query b probed
    list uniq[s]. Same score/id convention as ``ref_ivf_score_topk_batch``.
    ``mask`` (nlist, max_list) float 0/1 is the filter algebra's candidate
    mask, ANDed into ``valid`` slot-wise.
    """
    s, flat_ids = _dedup_scores(grouped, grouped_sq, valid, uniq, member,
                                queries, scales=scales, mask=mask)
    vals, pos = jax.lax.top_k(s, k)
    ids = flat_ids[pos]
    return vals, jnp.where(jnp.isneginf(vals), 0, ids)


def ref_pq_lut_qdot(queries_sub: Array, codebooks: Array) -> Array:
    """PQ LUT q.codebook cross term: (q, M, dsub) x (M, ksub, dsub) ->
    (q, M, ksub), out[i, m, j] = <queries_sub[i, m], codebooks[m, j]>."""
    return jnp.einsum("qmd,mkd->qmk", queries_sub, codebooks)


def ref_pq_score_batch(codes: Array, luts: Array) -> Array:
    """Multi-query ADC: codes (n, M), luts (q, M, ksub) -> scores (q, n)."""
    return jax.vmap(lambda lut: ref_pq_score(codes, lut))(luts)


def ref_pq_score(codes: Array, lut: Array) -> Array:
    """ADC: scores (n,) = sum_m lut[m, codes[n, m]] (squared distances)."""
    n, m = codes.shape
    per = jnp.take_along_axis(lut.T[None, :, :], codes[:, None, :], axis=1)[:, 0, :]
    return jnp.sum(per, axis=-1)

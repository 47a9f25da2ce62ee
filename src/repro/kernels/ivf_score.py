"""Pallas kernel: IVF probed-slab scoring with scalar-prefetched list ids.

The IVF corpus is stored grouped-by-list as a dense (nlist, max_list, d)
slab array (built once at ``IVFIndex.build`` time). The probe ids selected by
the coarse quantizer are passed as a scalar-prefetch operand so the BlockSpec
index_map can route each grid step's DMA directly to the probed slab — the
TPU idiom for data-dependent gathers (the block-table indirection pattern),
replacing the GPU's per-row gather.

The batched variant runs a (batch, nprobe) grid: the probe dimension is the
inner (sequential) axis, so each query's running top-k accumulates across its
probes while the output block revisits the same (1, k) row. Only
nprobe/nlist of the corpus is ever read per query.

The dedup variant inverts the loop to probe-major: the grid walks the UNIQUE
lists probed by any query in the batch, scoring the whole query batch against
each slab with one MXU matmul and masking queries that did not probe it. A
list shared by many queries is DMA'd from HBM exactly once per batch instead
of once per (query, probe) pair — with batch 64 x nprobe 8 over nlist 64 the
slab traffic drops up to 8x, which is the win that matters on the
bandwidth-bound serving path.

TPU layout: the per-slot operands (squared norms, validity, int8 scales)
enter as (nlist, 1, max_list) rows and the membership matrix as (s, 1, b),
so every block's last two dimensions are whole or (8, 128)-aligned. A list
longer than ``PAGE_BUDGET`` bytes of slab is scanned in pages of
``page_rows`` rows (an extra sequential grid axis), which bounds the VMEM a
grid step needs whatever the list-size skew.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_score_topk import (NEG_INF, compiler_params,
                                            merge_topk)

# slab bytes one grid step may DMA before a list is paged
PAGE_BUDGET = 2 * 2**20


def page_rows(max_list: int, row_bytes: int) -> int:
    """Rows per page: all of ``max_list`` when a whole list fits
    ``PAGE_BUDGET``, else the largest multiple of 128 that divides it and
    fits (lists padded to an odd length are scanned whole)."""
    if max_list * row_bytes <= PAGE_BUDGET:
        return max_list
    best = max_list
    for p in range(128, max_list, 128):
        if max_list % p == 0 and p * row_bytes <= PAGE_BUDGET:
            best = p
    return best


def _rows3(x):
    """(a, c) per-slot operand -> (a, 1, c) f32 rows (one (1, c) tile each)."""
    return x.astype(jnp.float32).reshape(x.shape[0], 1, x.shape[1])


def _row_to_col(row):
    """(1, c) -> (c, 1), exactly: each output sums one value with zeros (a
    lane reduction, where a transpose of a one-row tile may not lower)."""
    c = row.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=-1, keepdims=True)


def _batch_kernel(*refs, k: int, max_list: int, has_scale: bool):
    refs = list(refs)
    probes_ref, slab_ref, sq_ref = refs[:3]
    refs = refs[3:]
    sc_ref = refs.pop(0) if has_scale else None
    valid_ref, q_ref, vals_ref, idx_ref = refs
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, NEG_INF)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    slab = slab_ref[0].astype(jnp.float32)               # (max_list, d)
    q = q_ref[0]                                         # (1, d)
    s = 2.0 * jnp.dot(q, slab.T, preferred_element_type=jnp.float32)
    if sc_ref is not None:
        s = s * sc_ref[0]
    s = s - sq_ref[0]                                    # (1, max_list)
    s = jnp.where(valid_ref[0] > 0.5, s, NEG_INF)
    list_id = probes_ref[i, j]
    gids = (list_id * max_list
            + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    new_v, new_i = merge_topk(vals_ref[0], idx_ref[0], s, gids, k)
    vals_ref[0] = new_v
    idx_ref[0] = new_i


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def ivf_score_topk_batch(grouped, grouped_sq, valid, probes, queries, k: int,
                         *, scales=None, interpret: bool):
    """Multi-query probed search over the grouped slab layout.

    grouped: (nlist, max_list, d); grouped_sq: (nlist, max_list);
    valid: (nlist, max_list) float 0/1; probes: (b, nprobe) int32;
    queries: (b, d). Returns (vals (b, k), flat_ids (b, k)) with flat ids
    into grouped.reshape(-1, d). Scores are 2<x,q> - ||x||^2 (monotone in
    negative squared distance — the ||q||^2 constant is dropped).
    ``scales`` (nlist, max_list) routes to the int8 variant (per-row dequant
    of the dot output, fp32 accumulation).
    """
    nlist, max_list, d = grouped.shape
    b, nprobe = probes.shape

    probe_slab = pl.BlockSpec((1, max_list, d),
                              lambda i, j, probes: (probes[i, j], 0, 0))
    probe_row = pl.BlockSpec((1, 1, max_list),
                             lambda i, j, probes: (probes[i, j], 0, 0))
    per_query = lambda w: pl.BlockSpec((1, 1, w),  # noqa: E731
                                       lambda i, j, probes: (i, 0, 0))
    in_specs = [probe_slab, probe_row]
    args = [probes, grouped, _rows3(grouped_sq)]
    if scales is not None:
        in_specs.append(probe_row)
        args.append(_rows3(scales))
    in_specs += [probe_row, per_query(d)]
    args += [_rows3(valid), queries.reshape(b, 1, d)]
    kernel = functools.partial(_batch_kernel, k=k, max_list=max_list,
                               has_scale=scales is not None)
    vmem = 2 * max_list * d * grouped.dtype.itemsize + 16 * (k + max_list) * 4
    vals, idx = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, nprobe), in_specs=in_specs,
            out_specs=(per_query(k), per_query(k))),
        out_shape=(jax.ShapeDtypeStruct((b, 1, k), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, k), jnp.int32)),
        compiler_params=compiler_params(vmem, ("parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return vals[:, 0], idx[:, 0]


def _dedup_kernel(*refs, k: int, max_list: int, page: int, has_scale: bool):
    """Probe-major scan of one (unique list, page) grid cell for the whole
    query batch; queries that did not probe the list score -inf."""
    refs = list(refs)
    uniq_ref, slab_ref, sq_ref = refs[:3]
    refs = refs[3:]
    sc_ref = refs.pop(0) if has_scale else None
    valid_ref, member_ref, q_ref, vals_ref, idx_ref = refs
    s = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when((s == 0) & (p == 0))
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, NEG_INF)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    slab = slab_ref[0].astype(jnp.float32)              # (page, d)
    q = q_ref[...]                                      # (b, d)
    scores = 2.0 * jnp.dot(q, slab.T, preferred_element_type=jnp.float32)
    if sc_ref is not None:
        scores = scores * sc_ref[0]                     # (1, page)
    scores = scores - sq_ref[0]
    mem = _row_to_col(member_ref[0])                     # (b, 1)
    keep = (valid_ref[0] > 0.5) & (mem > 0.5)
    scores = jnp.where(keep, scores, NEG_INF)
    gids = (uniq_ref[s] * max_list + p * page
            + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1))
    new_v, new_i = merge_topk(vals_ref[...], idx_ref[...], scores, gids, k)
    vals_ref[...] = new_v
    idx_ref[...] = new_i


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def ivf_score_topk_dedup(grouped, grouped_sq, valid, uniq, member, queries,
                         k: int, *, scales=None, mask=None,
                         interpret: bool):
    """Probe-major batched slab search over the deduplicated probed lists.

    grouped: (nlist, max_list, d); grouped_sq/valid: (nlist, max_list);
    uniq: (s,) int32 unique probed list ids (tail slots may repeat a filler
    id — they must have an all-zero ``member`` column); member: (s, b) float
    0/1, 1 iff query b probed list uniq[s]; queries: (b, d).

    Returns (vals (b, k), flat_ids (b, k)) in the same convention as
    ``ivf_score_topk_batch``: scores 2<x,q> - ||x||^2, flat ids into
    grouped.reshape(-1, d). Each unique slab is DMA'd once for the whole
    batch (grid is sequential over slots, queries stay VMEM-resident).
    ``scales`` (nlist, max_list) routes to the int8 variant. ``mask``
    (nlist, max_list) float 0/1 is the filter algebra's candidate mask: it
    multiplies into the validity operand the kernel streams, so ineligible
    rows score -inf inside the scan (exact — both operands are 0/1).
    """
    if mask is not None:
        valid = valid * mask
    nlist, max_list, d = grouped.shape
    b = queries.shape[0]
    slots = uniq.shape[0]
    page = page_rows(max_list, d * grouped.dtype.itemsize)

    slab_spec = pl.BlockSpec((1, page, d), lambda s, p, uniq: (uniq[s], p, 0))
    row_spec = pl.BlockSpec((1, 1, page), lambda s, p, uniq: (uniq[s], 0, p))
    in_specs = [slab_spec, row_spec]
    args = [uniq, grouped, _rows3(grouped_sq)]
    if scales is not None:
        in_specs.append(row_spec)
        args.append(_rows3(scales))
    in_specs += [row_spec,
                 pl.BlockSpec((1, 1, b), lambda s, p, uniq: (s, 0, 0)),
                 pl.BlockSpec((b, d), lambda s, p, uniq: (0, 0))]
    args += [_rows3(valid), _rows3(member), queries]
    out_spec = pl.BlockSpec((b, k), lambda s, p, uniq: (0, 0))
    kernel = functools.partial(_dedup_kernel, k=k, max_list=max_list,
                               page=page, has_scale=scales is not None)
    vmem = (2 * page * d * grouped.dtype.itemsize + 2 * b * d * 4
            + 12 * b * (k + page) * 4)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(slots, max_list // page),
            in_specs=in_specs, out_specs=(out_spec, out_spec)),
        out_shape=(jax.ShapeDtypeStruct((b, k), jnp.float32),
                   jax.ShapeDtypeStruct((b, k), jnp.int32)),
        compiler_params=compiler_params(vmem, ("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*args)


def dedup_probes(probes, nlist: int):
    """Compact a (b, nprobe) probe matrix into (uniq, member) for the
    probe-major kernel: uniq (s,) int32 unique list ids (s = min(nlist,
    b*nprobe), tail filled with 0 and masked), member (s, b) float 0/1.

    Pure jnp with static shapes, so it traces into the jitted query step.
    """
    b, nprobe = probes.shape
    slots = min(nlist, b * nprobe)
    flat = jnp.sort(probes.reshape(-1).astype(jnp.int32))
    is_new = jnp.concatenate(
        [jnp.ones((1,), bool), flat[1:] != flat[:-1]])
    pos = jnp.cumsum(is_new) - 1                      # slot of each element
    uniq = jnp.zeros((slots,), jnp.int32).at[pos].set(flat, mode="drop")
    n_uniq = pos[-1] + 1
    slot_live = jnp.arange(slots) < n_uniq
    member = (probes[None, :, :] == uniq[:, None, None]).any(-1)
    member = member & slot_live[:, None]
    return uniq, member.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def ivf_score_topk(grouped, grouped_sq, valid, probes, query, k: int, *,
                   scales=None, interpret: bool):
    """Single-query probed search (batch size 1 of the batched kernel).

    probes: (nprobe,) int32; query: (d,). Returns (vals (k,), flat_ids (k,)).
    """
    vals, idx = ivf_score_topk_batch(
        grouped, grouped_sq, valid, probes[None, :], query[None, :], k,
        scales=scales, interpret=interpret)
    return vals[0], idx[0]

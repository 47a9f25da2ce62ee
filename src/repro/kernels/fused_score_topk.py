"""Pallas kernel: fused distance + running top-k — the serving inner loop.

For each (query-tile, corpus-block) cell the kernel computes the negative
squared-L2 scores with one MXU matmul (||q||^2 - 2 q.x + ||x||^2) and merges
them into a running (value, index) top-k that lives in the output refs across
the sequential corpus-block grid dimension. The corpus is therefore streamed
through VMEM exactly once, and no (q x n) score matrix ever exists in HBM —
the k-selection is fused into the scan.

Top-k selection is a k-step max/mask sweep (max + iota-argmin) in a
``fori_loop`` instead of lax.top_k, so every op lowers to plain TPU vector
reductions and the kernel's code size does not grow with k. The running
top-k and the block's scores stay two separate operands of the sweep (no
lane concatenation at an unaligned offset).

TPU layout: per-row operands (squared norms, int8 scales, the eligibility
mask) are carried lane-major as (1, n) rows and the per-query squared norms
as a (q, 1) column, so every block's last two dimensions tile as (8, 128)
(a 1-D block would not match XLA's HBM tiling of the operand).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEF_BLOCK_ROWS = 128
DEF_BLOCK_Q = 64
NEG_INF = float("-inf")

# scoped-VMEM ceiling a kernel may ask for: v5e has 128 MiB of VMEM per core,
# of which Mosaic grants 16 MiB unless told otherwise
VMEM_CAP = 100 * 2**20


def compiler_params(vmem_bytes: int, semantics):
    """Mosaic parameters for a kernel whose blocks and temporaries need about
    ``vmem_bytes`` of VMEM (raised above Mosaic's 16 MiB default when the
    estimate asks for it, capped at ``VMEM_CAP``)."""
    limit = min(max(32 * 2**20, 2 * vmem_bytes), VMEM_CAP)
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=int(limit))


def merge_topk(run_v, run_i, blk_v, blk_i, k: int):
    """First-occurrence top-k of the concatenation [running | block] along
    the last axis, without materialising the concatenation.

    run_v/run_i: (q, kr); blk_v/blk_i: (q, c). Returns (vals, ids), each
    (q, k), descending by value. Ties go to the lowest position, exactly
    like ``lax.top_k`` over the concatenation.
    """
    q, kr = run_v.shape
    c = blk_v.shape[-1]
    big = kr + c
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (q, kr), 1)
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (q, c), 1) + kr
    slot = jax.lax.broadcasted_iota(jnp.int32, (q, k), 1)

    def body(t, carry):
        cur_r, cur_b, out_v, out_i = carry
        m = jnp.maximum(jnp.max(cur_r, axis=-1, keepdims=True),
                        jnp.max(cur_b, axis=-1, keepdims=True))
        pos = jnp.minimum(
            jnp.min(jnp.where(cur_r == m, iota_r, big), axis=-1,
                    keepdims=True),
            jnp.min(jnp.where(cur_b == m, iota_b, big), axis=-1,
                    keepdims=True))
        sel_r = iota_r == pos
        sel_b = iota_b == pos
        ident = (jnp.sum(jnp.where(sel_r, run_i, 0), axis=-1, keepdims=True)
                 + jnp.sum(jnp.where(sel_b, blk_i, 0), axis=-1,
                           keepdims=True))
        here = slot == t
        return (jnp.where(sel_r, NEG_INF, cur_r),
                jnp.where(sel_b, NEG_INF, cur_b),
                jnp.where(here, m, out_v),
                jnp.where(here, ident, out_i))

    init = (run_v, blk_v, jnp.full((q, k), NEG_INF, jnp.float32),
            jnp.zeros((q, k), jnp.int32))
    _, _, vals, ids = jax.lax.fori_loop(0, k, body, init)
    return vals, ids


def _block_scores(j, x_ref, xsq_ref, scale_ref, mask_ref, q_ref, qsq_ref,
                  block_rows: int):
    """(scores (bq, bn), global row ids (bq, bn)) of one grid cell. ``scale_ref``/``mask_ref`` are None for the plain variants.
    The int8 scale multiplies the matmul OUTPUT column (fp32 accumulation)."""
    x = x_ref[...].astype(jnp.float32)                 # (bn, d)
    q = q_ref[...]                                      # (bq, d)
    scores = 2.0 * jnp.dot(q, x.T, preferred_element_type=jnp.float32)
    if scale_ref is not None:
        scores = scores * scale_ref[...]                # (1, bn)
    scores = scores - xsq_ref[...] - qsq_ref[...]       # (1, bn), (bq, 1)
    if mask_ref is not None:
        scores = jnp.where(mask_ref[...] > 0.5, scores, NEG_INF)
    gids = j * block_rows + jax.lax.broadcasted_iota(jnp.int32, scores.shape,
                                                     1)
    return scores, gids


def _scan_kernel(*refs, k: int, block_rows: int, has_scale: bool,
                 has_mask: bool):
    """Plain / int8-scaled / masked / masked+scaled scan: ineligible rows
    (mask 0) score -inf inside the scan — the filter algebra's in-kernel
    mask plan."""
    refs = list(refs)
    x_ref, xsq_ref = refs.pop(0), refs.pop(0)
    scale_ref = refs.pop(0) if has_scale else None
    mask_ref = refs.pop(0) if has_mask else None
    q_ref, qsq_ref, vals_ref, idx_ref = refs
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, NEG_INF)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    scores, gids = _block_scores(j, x_ref, xsq_ref, scale_ref, mask_ref,
                                 q_ref, qsq_ref, block_rows)
    new_v, new_i = merge_topk(vals_ref[...], idx_ref[...], scores, gids, k)
    vals_ref[...] = new_v
    idx_ref[...] = new_i


def _check_tiling(n, nq, k, block_rows, block_q):
    block_rows = min(block_rows, n)
    block_q = min(block_q, nq)
    if n % block_rows or nq % block_q:
        raise ValueError(
            f"shapes must tile: n={n} %% {block_rows}, q={nq} %% {block_q}")
    if k > n:
        raise ValueError(f"k={k} > corpus size {n}")
    return block_rows, block_q


def _row(x):
    """(n,) per-row operand -> (1, n) lane-major row."""
    return x.astype(jnp.float32).reshape(1, -1)


@functools.partial(jax.jit,
                   static_argnames=("k", "block_rows", "block_q", "interpret"))
def score_topk(corpus, sq_norms, queries, k: int, *, scales=None, mask=None,
               block_rows: int = DEF_BLOCK_ROWS, block_q: int = DEF_BLOCK_Q,
               interpret: bool):
    """corpus: (n, d); sq_norms: (n,); queries: (q, d).

    Returns (scores (q, k), ids (q, k)) — negative squared L2, descending.
    ``scales`` (n,) routes to the int8 kernel variant (per-row dequant of the
    matmul output; scores are exact for the dequantized rows). ``mask`` (n,)
    float 0/1 routes to the filtered variants: rows at 0 score -inf inside
    the scan (the in-kernel candidate-mask plan of the filter algebra).
    """
    n, d = corpus.shape
    nq = queries.shape[0]
    block_rows, block_q = _check_tiling(n, nq, k, block_rows, block_q)
    grid = (nq // block_q, n // block_rows)
    qsq = jnp.sum(queries.astype(jnp.float32) ** 2, axis=-1, keepdims=True)

    row_spec = pl.BlockSpec((block_rows, d), lambda i, j: (j, 0))
    per_row = pl.BlockSpec((1, block_rows), lambda i, j: (0, j))
    in_specs = [row_spec, per_row]
    args = [corpus, _row(sq_norms)]
    for extra in (scales, mask):
        if extra is not None:
            in_specs.append(per_row)
            args.append(_row(extra))
    in_specs += [pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
                 pl.BlockSpec((block_q, 1), lambda i, j: (i, 0))]
    args += [queries, qsq]
    out_spec = pl.BlockSpec((block_q, k), lambda i, j: (i, 0))
    kernel = functools.partial(_scan_kernel, k=k, block_rows=block_rows,
                               has_scale=scales is not None,
                               has_mask=mask is not None)
    tile = block_q * (k + block_rows) * 4
    vmem = (2 * block_rows * d * corpus.dtype.itemsize
            + 2 * block_q * d * 4 + 12 * tile)
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=(out_spec, out_spec),
        out_shape=(jax.ShapeDtypeStruct((nq, k), jnp.float32),
                   jax.ShapeDtypeStruct((nq, k), jnp.int32)),
        compiler_params=compiler_params(vmem, ("parallel", "arbitrary")),
        interpret=interpret,
    )(*args)

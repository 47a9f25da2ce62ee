"""Pallas kernel: fused distance + running top-k — the serving inner loop.

For each (query-tile, corpus-block) cell the kernel computes the negative
squared-L2 scores with one MXU matmul (||q||^2 - 2 q.x + ||x||^2) and merges
them into a running (value, index) top-k that lives in the output refs across
the sequential corpus-block grid dimension. The corpus is therefore streamed
through VMEM exactly once, and no (q x n) score matrix ever exists in HBM —
the k-selection is fused into the scan.

Top-k selection is a threshold-gated merge instead of lax.top_k: the
running top-k stays sorted in a lane-aligned VMEM scratch, and each block
gives up its best remaining score per row only while some row's best beats
that row's running k-th value. The loop's trip count follows the data: a
block no query probed, or one whose scores all fall below every row's k-th
value, costs one pass over it, and no block costs more than k steps. Every
op lowers to plain TPU vector reductions and a one-lane rotate, and the
kernel's code size does not grow with k.

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


def lane_width(k: int) -> int:
    """Lanes of the running top-k scratch: k rounded up to whole vregs."""
    return -(-k // 128) * 128


def init_running(run_v_ref, run_i_ref):
    """Empty running top-k: every slot -inf with id 0 (the fill a row with
    fewer than k finite scores ends with)."""
    run_v_ref[...] = jnp.full_like(run_v_ref, NEG_INF)
    run_i_ref[...] = jnp.zeros_like(run_i_ref)


def merge_topk(run_v_ref, run_i_ref, blk_v, base, k: int):
    """Merge one block of scores into the sorted running top-k held in
    ``run_v_ref``/``run_i_ref``, taking from the block only the scores that
    enter; returns the selection steps taken, 0 to k.

    run_v_ref/run_i_ref: (q, lane_width(k)) VMEM; lanes [0, k) hold the
    running top-k in descending order (ties in order of occurrence), lanes
    past k hold scores that no longer count (none above the k-th). blk_v:
    (q, c) block scores; the id of column j is ``base + j``.

    Each step takes every row's best remaining block score (first column
    on ties) and inserts it where it beats the row's running k-th value,
    after the running entries it ties, by a one-lane shift. A row whose
    best no longer beats its k-th value never will again within the block
    (the k-th value only rises, the block's best only falls), so the loop
    stops when no row's does. The result equals a first-occurrence
    ``lax.top_k`` over everything merged so far, as a k-step max/mask sweep
    over [running | block] gives it.
    """
    q, kw = run_v_ref.shape
    c = blk_v.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, kw), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, c), 1)

    def gate(rv, blk):
        """(best remaining block score, the slot it would take) per row; a
        slot of k or more means it does not enter."""
        m = jnp.max(blk, axis=-1, keepdims=True)
        return m, jnp.sum((rv >= m).astype(jnp.int32), axis=-1,
                          keepdims=True)

    def cond(carry):
        t, _, _, _, _, slot = carry
        live = jnp.max((slot < k).astype(jnp.int32))
        return (t < k) & (live > 0)

    def body(carry):
        t, rv, ri, blk, m, slot = carry
        first = jnp.min(jnp.where(blk == m, col, c), axis=-1, keepdims=True)
        after = lane > slot
        rv = jnp.where(after, pltpu.roll(rv, 1, 1),
                       jnp.where(lane == slot, m, rv))
        ri = jnp.where(after, pltpu.roll(ri, 1, 1),
                       jnp.where(lane == slot, base + first, ri))
        blk = jnp.where(col == first, NEG_INF, blk)
        return (t + 1, rv, ri, blk) + gate(rv, blk)

    run_v = run_v_ref[...]
    init = (jnp.int32(0), run_v, run_i_ref[...], blk_v) + gate(run_v, blk_v)
    steps, run_v, run_i, _, _, _ = jax.lax.while_loop(cond, body, init)
    run_v_ref[...] = run_v
    run_i_ref[...] = run_i
    return steps


def _block_scores(x_ref, xsq_ref, scale_ref, mask_ref, q_ref, qsq_ref):
    """Scores (bq, bn) of one grid cell. ``scale_ref``/``mask_ref`` are None
    for the plain variants. The int8 scale multiplies the matmul OUTPUT
    column (fp32 accumulation)."""
    x = x_ref[...].astype(jnp.float32)                 # (bn, d)
    q = q_ref[...]                                      # (bq, d)
    scores = 2.0 * jnp.dot(q, x.T, preferred_element_type=jnp.float32)
    if scale_ref is not None:
        scores = scores * scale_ref[...]                # (1, bn)
    scores = scores - xsq_ref[...] - qsq_ref[...]       # (1, bn), (bq, 1)
    if mask_ref is not None:
        scores = jnp.where(mask_ref[...] > 0.5, scores, NEG_INF)
    return scores


def _scan_kernel(*refs, k: int, block_rows: int, has_scale: bool,
                 has_mask: bool, n_rows: int | None):
    """Plain / int8-scaled / masked / masked+scaled scan: ineligible rows
    (mask 0) score -inf inside the scan — the filter algebra's in-kernel
    mask plan. ``n_rows`` is the corpus row count when it is no multiple of
    ``block_rows`` (None otherwise): the last block then runs past the
    corpus, and its out-of-range columns score -inf."""
    refs = list(refs)
    x_ref, xsq_ref = refs.pop(0), refs.pop(0)
    scale_ref = refs.pop(0) if has_scale else None
    mask_ref = refs.pop(0) if has_mask else None
    q_ref, qsq_ref, vals_ref, idx_ref, run_v_ref, run_i_ref = refs
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        init_running(run_v_ref, run_i_ref)

    scores = _block_scores(x_ref, xsq_ref, scale_ref, mask_ref, q_ref,
                           qsq_ref)
    if n_rows is not None:
        # the boundary block's rows past n hold unspecified values (NaN
        # included); each score column depends on its own row alone, so
        # masking those columns leaves the real rows' scores as they are
        col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(j * block_rows + col < n_rows, scores, NEG_INF)
    merge_topk(run_v_ref, run_i_ref, scores, j * block_rows, k)

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        vals_ref[...] = run_v_ref[:, :k]
        idx_ref[...] = run_i_ref[:, :k]


def _check_tiling(n, nq, k, block_rows, block_q):
    block_rows = min(block_rows, n)
    block_q = min(block_q, nq)
    if nq % block_q:
        raise ValueError(f"q={nq} queries are no multiple of {block_q}")
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
    ``n`` need not be a multiple of ``block_rows``: the grid's last corpus
    block is then ragged and read in place, with no padded copy.
    ``scales`` (n,) routes to the int8 kernel variant (per-row dequant of the
    matmul output; scores are exact for the dequantized rows). ``mask`` (n,)
    float 0/1 routes to the filtered variants: rows at 0 score -inf inside
    the scan (the in-kernel candidate-mask plan of the filter algebra).
    """
    n, d = corpus.shape
    nq = queries.shape[0]
    block_rows, block_q = _check_tiling(n, nq, k, block_rows, block_q)
    grid = (nq // block_q, pl.cdiv(n, block_rows))
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
                               has_mask=mask is not None,
                               n_rows=n if n % block_rows else None)
    kw = lane_width(k)
    tile = block_q * (kw + block_rows) * 4
    vmem = (2 * block_rows * d * corpus.dtype.itemsize
            + 2 * block_q * d * 4 + 12 * tile)
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=(out_spec, out_spec),
        out_shape=(jax.ShapeDtypeStruct((nq, k), jnp.float32),
                   jax.ShapeDtypeStruct((nq, k), jnp.int32)),
        scratch_shapes=[pltpu.VMEM((block_q, kw), jnp.float32),
                        pltpu.VMEM((block_q, kw), jnp.int32)],
        compiler_params=compiler_params(vmem, ("parallel", "arbitrary")),
        interpret=interpret,
    )(*args)

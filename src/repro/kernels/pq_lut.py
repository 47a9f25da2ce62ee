"""Pallas kernel: PQ ADC scoring — LUT gather-accumulate as one-hot matmuls.

scores[q, n] = sum_m lut[q, m, codes[n, m]]

GPU PQ kernels use per-lane shared-memory gathers; the TPU adaptation turns
each subspace's gather into a (rows x ksub) one-hot times LUT product, which
the MXU executes at full rate and which needs no dynamic addressing. codes
stream through VMEM in row blocks; the LUTs of the whole query batch stay
resident (q x M x ksub floats), and each block's one-hot is built once and
shared by every query.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEF_BATCH_BLOCK_ROWS = 256
DEF_QDOT_BLOCK_Q = 128


def _batch_kernel(codes_ref, lut_ref, out_ref, *, ksub: int):
    """One row block for every query: lut_ref (M, q, ksub) subspace-major,
    out (q, bn). The one-hot matmul runs at HIGHEST precision: it must copy
    fp32 LUT entries, which a single bf16 MXU pass would round."""
    codes = codes_ref[...]            # (bn, M) int32
    bn, m = codes.shape
    hi = jax.lax.Precision.HIGHEST
    iota = jax.lax.broadcasted_iota(jnp.int32, (bn, ksub), 1)
    total = jnp.zeros(out_ref.shape, jnp.float32)
    for j in range(m):                # M is small + static: unrolled
        onehot = (codes[:, j:j + 1] == iota).astype(jnp.float32)
        total = total + jax.lax.dot_general(
            lut_ref[j], onehot, (((1,), (1,)), ((), ())),
            precision=(hi, hi), preferred_element_type=jnp.float32)
    out_ref[...] = total


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def pq_score_batch(codes, luts, *, block_rows: int = DEF_BATCH_BLOCK_ROWS,
                   interpret: bool):
    """Multi-query ADC: codes (n, M) int32, luts (q, M, ksub) -> scores (q, n).

    Grid is over row blocks: the whole batch's LUTs stay resident while code
    blocks stream through VMEM. Rows are zero-padded to a block multiple and
    the pad columns sliced off the result.
    """
    n, m = codes.shape
    q, _, ksub = luts.shape
    block_rows = min(block_rows, n)
    pad = -n % block_rows
    if pad:
        codes = jnp.concatenate(
            [codes, jnp.zeros((pad, m), codes.dtype)], axis=0)
    n_pad = n + pad
    kernel = functools.partial(_batch_kernel, ksub=ksub)
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, m), lambda j: (j, 0)),
            pl.BlockSpec((m, q, ksub), lambda j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((q, block_rows), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((q, n_pad), jnp.float32),
        interpret=interpret,
    )(codes, jnp.transpose(luts, (1, 0, 2)))
    return out[:, :n]


def pq_score(codes, lut, *, block_rows: int = DEF_BATCH_BLOCK_ROWS,
             interpret: bool):
    """Single-LUT ADC: codes (n, M) int32; lut (M, ksub) f32 -> squared
    dists (n,) — a batch of one through ``pq_score_batch``."""
    return pq_score_batch(codes, lut[None], block_rows=block_rows,
                          interpret=interpret)[0]


def _qdot_kernel(q_ref, cb_ref, out_ref):
    q = q_ref[0]                       # (bq, dsub)
    cb = cb_ref[0]                     # (ksub, dsub)
    out_ref[0] = jnp.dot(q, cb.T, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def pq_lut_qdot(queries_sub, codebooks, *, block_q: int = DEF_QDOT_BLOCK_Q,
                interpret: bool):
    """The q . codebook cross term of PQ LUT construction as one fused matmul.

    queries_sub: (q, M, dsub) queries split into subspaces; codebooks:
    (M, ksub, dsub). Returns (q, M, ksub) with out[i, m, j] =
    <queries_sub[i, m], codebooks[m, j]> — the dominant term of
    ``repro.index.pq.compute_luts`` (the residual-norm and build-time terms
    stay jnp). Grid is (subspace, query-block): each subspace's codebook
    stays VMEM-resident while query blocks stream through the MXU. Queries
    are zero-padded to the block multiple and sliced back off.
    """
    q, m, dsub = queries_sub.shape
    ksub = codebooks.shape[1]
    block_q = min(block_q, q)
    pad = -q % block_q
    qs = jnp.transpose(queries_sub, (1, 0, 2))          # (M, q, dsub)
    if pad:
        qs = jnp.concatenate([qs, jnp.zeros((m, pad, dsub), qs.dtype)],
                             axis=1)
    out = pl.pallas_call(
        _qdot_kernel,
        grid=(m, (q + pad) // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, dsub), lambda j, i: (j, i, 0)),
            pl.BlockSpec((1, ksub, dsub), lambda j, i: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, ksub), lambda j, i: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, q + pad, ksub), jnp.float32),
        interpret=interpret,
    )(qs, codebooks)
    return jnp.transpose(out[:, :q], (1, 0, 2))

"""Pallas kernel: fused combined-score re-ranking (Alg. 1 line 13).

score = lam * cos(v_i, q) + (1 - lam) * cos(f_i, F_q)

Both cosine similarities, their norms and the affine combine are fused into
one VMEM pass over the gathered candidate tile, so re-scoring costs one read
of the (kp x d) candidate block instead of four separate elementwise passes.
The body is ``ref.ref_rescore`` itself, so the kernel, its reference and
the XLA re-rank share one definition of the score. Only the row sums
differ: the kernel uses the native lane reduction, not ``ref.lane_sum``'s
fixed halving order: compiled for a TPU v5e, the halvings below 128 lanes
took 3.7 s instead of 0.65 s at k' = 133, and 37 s instead of 4 s at the
escalation k' = 533 (ahead-of-time compiles, b = 64, d = 768), and added
about 19 s to the first Pallas batch of a cold run on a v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_score_topk import compiler_params
from repro.kernels.ref import ref_rescore

DEF_BLOCK_B = 8


def _row_sum(x):
    return jnp.sum(x, axis=-1)


def _kernel(cv_ref, cf_ref, q_ref, fq_ref, lam_ref, out_ref):
    # loads cast up front: bf16 / int8-dequantized candidate tiles are
    # accepted and the norms + dots accumulate in fp32 (no-op for fp32)
    out_ref[...] = ref_rescore(
        cv_ref[...].astype(jnp.float32), cf_ref[...].astype(jnp.float32),
        q_ref[...].astype(jnp.float32), fq_ref[...].astype(jnp.float32),
        lam_ref[0], total=_row_sum).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def rescore(cand_v, cand_f, qn, fqn, lam, *, block_b: int = DEF_BLOCK_B,
            interpret: bool):
    """cand_v: (b, kp, d); cand_f: (b, kp, m); qn: (b, d); fqn: (b, m)."""
    b, kp, d = cand_v.shape
    m = cand_f.shape[-1]
    block_b = min(block_b, b)
    if b % block_b:
        raise ValueError(f"b={b} must be divisible by block_b={block_b}")
    lam_arr = jnp.asarray(lam, jnp.float32).reshape(1)
    grid = (b // block_b,)
    tile = block_b * kp * (d + m) * 4
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, kp, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, kp, m), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((block_b, m), lambda i: (i, 0)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_b, kp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kp), jnp.float32),
        compiler_params=compiler_params(6 * tile, ("parallel",)),
        interpret=interpret,
    )(cand_v, cand_f, qn, fqn, lam_arr)

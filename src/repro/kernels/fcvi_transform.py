"""Pallas kernel: fused per-dim normalize + psi transform.

out = (v - mu_v)/sd_v - alpha * ((f - mu_f)/sd_f) @ P

One pass over the corpus: rows stream through VMEM in (block_rows x d) tiles;
the (m x d) projection P (partition tiling matrix, or learned W^T) and the
normalizer vectors stay resident. The matmul form keeps the filter fold on
the MXU instead of a lane-misaligned reshape (m is typically 2-8, far below
the 128-lane tile, so the reshape formulation would waste the vector unit).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEF_BLOCK_ROWS = 256


def _kernel(v_ref, f_ref, proj_ref, alpha_ref, mv_ref, sv_ref, mf_ref, sf_ref,
            out_ref):
    v = v_ref[...]
    f = f_ref[...]
    alpha = alpha_ref[0]
    vn = (v - mv_ref[...][None, :]) / sv_ref[...][None, :]
    fn = (f - mf_ref[...][None, :]) / sf_ref[...][None, :]
    fold = jnp.dot(fn, proj_ref[...], preferred_element_type=jnp.float32)
    out_ref[...] = (vn - alpha * fold).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_transform(v, f, proj, alpha, mean_v, std_v, mean_f, std_f,
                    *, block_rows: int = DEF_BLOCK_ROWS, interpret: bool):
    """v: (n, d); f: (n, m); proj: (m, d). Returns transformed (n, d)."""
    n, d = v.shape
    m = f.shape[-1]
    block_rows = min(block_rows, n)
    if n % block_rows:
        raise ValueError(f"n={n} must be divisible by block_rows={block_rows}")
    grid = (n // block_rows,)
    alpha_arr = jnp.asarray(alpha, jnp.float32).reshape(1)

    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, m), lambda i: (i, 0)),
            pl.BlockSpec((m, d), lambda i: (0, 0)),
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((m,), lambda i: (0,)),
            pl.BlockSpec((m,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), v.dtype),
        interpret=interpret,
    )(v, f, proj, alpha_arr, mean_v, std_v, mean_f, std_f)

"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

Interpret mode cannot see what the TPU compiler refuses: block shapes that
do not tile as (8, 128), 1-D blocks whose layout differs from XLA's, or
blocks and temporaries that overflow VMEM. Each test lowers one kernel (or
the engine step's scan + re-rank around it) at a deployment width — n = 2^20
rows, d = 768, batch 64, k' = 133 (+ the refine pad), m = 8 filter columns,
IVF nlist = 1024 with 2048-row lists and nprobe = 32 — for one chip of a
described ``v5e:2x2`` topology and checks that the Mosaic kernel is in the
compiled program. Nothing runs and nothing needs a chip.

The topology is described inside a module fixture (never at import), and
the persistent compilation cache is off around the compiles: a compile for
a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import fcvi
from repro.index import flat as flat_mod
from repro.index import ivf as ivf_mod
from repro.kernels import fcvi_transform, fused_score_topk, ivf_score
from repro.kernels import ops, pq_lut, rescore

N, D, B, M = 1 << 20, 768, 64, 8
KP = 133                       # k' of k=10, lam=0.6, c=8 (theory.k_prime)
KK = KP + flat_mod.REFINE_PAD  # what the flat scan kernel is asked for
NLIST, MAX_LIST, NPROBE = 1024, 2048, 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compile_v5e(one_chip, no_compile_cache, monkeypatch):
    """compile_v5e(fn, *shapes) -> compiled text; shapes are (shape, dtype)
    pairs placed on one described chip. The ops layer is steered to the
    compiled (non-interpret) kernels, as it is on a TPU backend."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def go(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text
        return text

    return go


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("variant", ["plain", "masked", "int8"])
def test_flat_scan(compile_v5e, variant):
    dtype = jnp.int8 if variant == "int8" else F32
    extra = {"masked": "mask", "int8": "scales"}.get(variant)

    def fn(corpus, sq, queries, per_row):
        kw = {extra: per_row} if extra else {}
        return fused_score_topk.score_topk(corpus, sq, queries, KK,
                                           interpret=False, **kw)

    compile_v5e(fn, ((N, D), dtype), ((N,), F32), ((B, D), F32), ((N,), F32))


def _rerank(cand, pv, pf, queries):
    """The meshless engine step after the scan: the winners' payload rows
    read by id, combined-scored by the Pallas re-rank kernel."""
    return fcvi.combined_score(pv[cand], pf[cand], queries, pf[:B], 0.6,
                               use_pallas=True)


def test_flat_search_rerank(compile_v5e):
    """Flat candidate generation + re-rank, as the engine step runs them."""
    def fn(corpus, sq, pv, pf, queries):
        index = flat_mod.FlatIndex(vectors=corpus, sq_norms=sq)
        _, cand = flat_mod.search(index, queries, KP, use_pallas=True)
        return _rerank(cand, pv, pf, queries)

    compile_v5e(fn, ((N, D), F32), ((N,), F32), ((N, D), F32), ((N, M), F32),
                ((B, D), F32))


def _ivf_index(grouped, gsq, valid, centroids, lists, vectors, sq):
    return ivf_mod.IVFIndex(
        vectors=vectors, sq_norms=sq, centroids=centroids, lists=lists,
        list_sizes=jnp.zeros((NLIST,), I32), grouped=grouped,
        grouped_sq=gsq, valid=valid)


def test_ivf_dedup(compile_v5e):
    def fn(grouped, gsq, valid, uniq, member, queries):
        return ivf_score.ivf_score_topk_dedup(grouped, gsq, valid, uniq,
                                              member, queries, KP,
                                              interpret=False)

    compile_v5e(fn, ((NLIST, MAX_LIST, D), F32), ((NLIST, MAX_LIST), F32),
                ((NLIST, MAX_LIST), F32), ((NLIST,), I32), ((NLIST, B), F32),
                ((B, D), F32))


def test_ivf_search_rerank(compile_v5e):
    """Coarse quantizer + dedup scan + re-rank: the IVF engine step."""
    def fn(grouped, gsq, valid, centroids, lists, vectors, sq, pv, pf,
           queries):
        index = _ivf_index(grouped, gsq, valid, centroids, lists, vectors, sq)
        _, cand = ivf_mod.search(index, queries, KP, nprobe=NPROBE,
                                 use_pallas=True)
        return _rerank(cand, pv, pf, queries)

    compile_v5e(fn, ((NLIST, MAX_LIST, D), F32), ((NLIST, MAX_LIST), F32),
                ((NLIST, MAX_LIST), F32), ((NLIST, D), F32),
                ((NLIST, MAX_LIST), I32), ((N, D), F32), ((N,), F32),
                ((N, D), F32), ((N, M), F32), ((B, D), F32))


def test_pq_score_batch(compile_v5e):
    def fn(codes, luts):
        return pq_lut.pq_score_batch(codes, luts, interpret=False)

    compile_v5e(fn, ((N, M), I32), ((B, M, 256), F32))


def test_rescore(compile_v5e):
    def fn(cv, cf, qn, fqn):
        return rescore.rescore(cv, cf, qn, fqn, 0.6, interpret=False)

    compile_v5e(fn, ((B, KP, D), F32), ((B, KP, M), F32), ((B, D), F32),
                ((B, M), F32))


def test_fused_transform(compile_v5e):
    def fn(v, f, proj, mv, sv, mf, sf):
        return fcvi_transform.fused_transform(v, f, proj, 1.0, mv, sv, mf, sf,
                                              interpret=False)

    compile_v5e(fn, ((N, D), F32), ((N, M), F32), ((M, D), F32), ((D,), F32),
                ((D,), F32), ((M,), F32), ((M,), F32))

"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

Interpret mode cannot see what the TPU compiler refuses: block shapes that
do not tile as (8, 128), 1-D blocks whose layout differs from XLA's, or
blocks and temporaries that overflow VMEM. Each test lowers one kernel (or
the engine step's scan + re-rank around it) at a deployment width — n = 2^20
rows, d = 768, batch 64, k' = 133 and the escalation's 533 (+ the refine
pad), m = 8 filter columns, IVF nlist = 1024 with 2048-row lists and
nprobe = 32 — for one chip of a described ``v5e:2x2`` topology and checks
that the Mosaic kernel is in the compiled program. The flat scan compiles
also at the benchmark cell's n = 1,000,000, no multiple of its row tile,
where it must read the slab in place. Nothing runs and nothing needs a
chip.

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
# the flat benchmark cell's row count: no multiple of the scan's 128-row
# tile, so the scan's last corpus block is ragged
CELL_N = 1_000_000
KP = 133                       # k' of k=10, lam=0.6, c=8 (theory.k_prime)
# the escalation's k' (c = 8 x kprime_escalation 4): the engine's stage 2
# re-runs a batch's ambiguous queries through the same scan at this k'
KP2 = 533
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
    """compile_v5e(fn, *shapes) -> the compiled program; shapes are
    (shape, dtype) pairs placed on one described chip. The ops layer is
    steered to the compiled (non-interpret) kernels, as it is on a TPU
    backend."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def go(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    return go


F32, I32 = jnp.float32, jnp.int32


def _assert_scans_in_place(compiled, n, gathered_rows=0):
    """The scan reads the (n, D) slab where it lies: no copy of it padded
    to the 128-row tile, and temporaries far below one slab (3.07 GB at the
    cell's n): 64 MiB, plus the fp32 rows a re-rank gathers."""
    padded = n + -n % fused_score_topk.DEF_BLOCK_ROWS
    if padded != n:
        assert f"[{padded},{D}]" not in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 64 * 2**20 + gathered_rows * D * 4


@pytest.mark.parametrize("variant,kp,n", [
    pytest.param("plain", KP, N, id="plain"),
    pytest.param("masked", KP, N, id="masked"),
    pytest.param("int8", KP, N, id="int8"),
    pytest.param("plain", KP2, N, id="plain-kp533"),
    pytest.param("int8", KP2, N, id="int8-kp533"),
    pytest.param("plain", KP, CELL_N, id="cell-plain"),
    pytest.param("plain", KP2, CELL_N, id="cell-plain-kp533"),
])
def test_flat_scan(compile_v5e, variant, kp, n):
    """The flat scan kernel at what the flat search asks of it: k' plus the
    refine pad candidates (141 in stage 1, 541 on escalation), over 2^20
    rows and over the cell's 1,000,000 (a ragged last block)."""
    dtype = jnp.int8 if variant == "int8" else F32
    extra = {"masked": "mask", "int8": "scales"}.get(variant)

    def fn(corpus, sq, queries, per_row):
        kw = {extra: per_row} if extra else {}
        return fused_score_topk.score_topk(corpus, sq, queries,
                                           kp + flat_mod.REFINE_PAD,
                                           interpret=False, **kw)

    compiled = compile_v5e(fn, ((n, D), dtype), ((n,), F32), ((B, D), F32),
                           ((n,), F32))
    _assert_scans_in_place(compiled, n)


def _rerank(cand, pv, pf, queries):
    """The meshless engine step after the scan: the winners' payload rows
    read by id, combined-scored by the Pallas re-rank kernel."""
    return fcvi.combined_score(pv[cand], pf[cand], queries, pf[:B], 0.6,
                               use_pallas=True)


def _compile_flat_search_rerank(compile_v5e, kp, n):
    def fn(corpus, sq, pv, pf, queries):
        index = flat_mod.FlatIndex(vectors=corpus, sq_norms=sq)
        _, cand = flat_mod.search(index, queries, kp, use_pallas=True)
        return _rerank(cand, pv, pf, queries)

    compiled = compile_v5e(fn, ((n, D), F32), ((n,), F32), ((n, D), F32),
                           ((n, M), F32), ((B, D), F32))
    # the exact refine's and the re-rank's gathers of each query's rows
    _assert_scans_in_place(compiled, n,
                           2 * B * (kp + flat_mod.REFINE_PAD))


ROWS = [pytest.param(N, id="2pow20"), pytest.param(CELL_N, id="cell")]


@pytest.mark.parametrize("n", ROWS)
def test_flat_search_rerank(compile_v5e, n):
    """Flat candidate generation + re-rank, as the engine step runs them,
    over 2^20 rows and over the cell's 1,000,000."""
    _compile_flat_search_rerank(compile_v5e, KP, n)


@pytest.mark.parametrize("n", ROWS)
def test_flat_search_rerank_escalation(compile_v5e, n):
    """The same at the escalation's k', on the stage-2 sub-batch."""
    _compile_flat_search_rerank(compile_v5e, KP2, n)


def _ivf_index(grouped, gsq, valid, centroids, lists, vectors, sq):
    return ivf_mod.IVFIndex(
        vectors=vectors, sq_norms=sq, centroids=centroids, lists=lists,
        list_sizes=jnp.zeros((NLIST,), I32), grouped=grouped,
        grouped_sq=gsq, valid=valid)


# the benchmark's IVF deployment: SIFT1M widths and its longest list (one
# page per list), at both k' of the escalating engine
CELL_D, CELL_MAX_LIST = 128, 2592
# one shard of the IVF engine on a 2x2 mesh (serve/sharded.py): its 256
# lists plus the all-invalid sentinel slot every non-local probe goes to
SHARD_SLOTS = NLIST // 4 + 1


@pytest.mark.parametrize("slots,d,max_list,kp,count_steps", [
    pytest.param(NLIST, D, MAX_LIST, KP, False, id="deployment"),
    pytest.param(NLIST, CELL_D, CELL_MAX_LIST, KP, False, id="cell-kp133"),
    pytest.param(NLIST, CELL_D, CELL_MAX_LIST, KP2, False, id="cell-kp533"),
    pytest.param(NLIST, CELL_D, CELL_MAX_LIST, KP, True,
                 id="cell-kp133-steps"),
    pytest.param(NLIST, CELL_D, CELL_MAX_LIST, KP2, True,
                 id="cell-kp533-steps"),
    pytest.param(SHARD_SLOTS, D, MAX_LIST, KP, False, id="shard-kp133"),
    pytest.param(SHARD_SLOTS, D, MAX_LIST, KP2, False, id="shard-kp533"),
])
def test_ivf_dedup(compile_v5e, slots, d, max_list, kp, count_steps):
    def fn(grouped, gsq, valid, uniq, member, queries):
        return ivf_score.ivf_score_topk_dedup(grouped, gsq, valid, uniq,
                                              member, queries, kp,
                                              count_steps=count_steps,
                                              interpret=False)

    compile_v5e(fn, ((slots, max_list, d), F32), ((slots, max_list), F32),
                ((slots, max_list), F32), ((slots,), I32), ((slots, B), F32),
                ((B, d), F32))


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

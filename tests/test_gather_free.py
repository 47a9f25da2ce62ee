"""Gather-free re-rank parity: carried rows/scores vs the HBM/psum gather.

The contract: ``EngineConfig.gather_free`` is a pure EXECUTION knob of the
mesh-sharded step (a meshless engine has one step and ignores it). Under
``shard_map`` each shard gathers its own winners from its LOCAL payload
block, rescores them in place, and the cross-shard merge carries finished
scores instead of psum-gathering rows afterwards. It must return top-k ids
and scores IDENTICAL to the meshless gather-based step and to the psum step
— flat, IVF and PQ, kernels on and off, fp32 and int8 storage, with a live
delta buffer, dense and routed.
The collective-free property itself is pinned by
``tests/test_hlo_analysis.py::test_gather_free_step_has_no_all_reduce``.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FCVIConfig, build
from repro.data.synthetic import CorpusSpec, make_corpus, sample_queries
from repro.launch.mesh import make_mesh
from repro.serve.engine import EngineConfig, FCVIEngine

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_in_subprocess(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


@pytest.fixture(scope="module")
def data():
    spec = CorpusSpec(n=1000, d=64, n_categories=5, n_numeric=3, seed=2)
    corpus = make_corpus(spec)
    q, fq = sample_queries(corpus, 5, seed=3)
    return corpus, np.asarray(q), np.asarray(fq)


def _engine(corpus, backend, use_pallas, gather_free, storage="float32",
            mesh=None, **mesh_kw):
    cfg = FCVIConfig(alpha=1.0, lam=0.6, c=8.0, backend=backend, nlist=16,
                     nprobe=4, pq_m=8, pq_ksub=32, pq_coarse=8,
                     use_pallas=use_pallas, storage_dtype=storage)
    idx = build(jnp.asarray(corpus.vectors), jnp.asarray(corpus.filters), cfg)
    ek = EngineConfig(k=5, batch_size=16, compact_threshold=256,
                      gather_free=gather_free)
    return FCVIEngine(idx, ek, mesh=mesh, **mesh_kw)


def _assert_identical(a, b):
    (s0, i0), (s1, i1) = a, b
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


@pytest.mark.parametrize("backend", ["flat", "ivf"])
@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("storage", ["float32", "int8"])
def test_single_device_gather_free_identity(data, backend, use_pallas,
                                            storage):
    """The gather-free shard_map step on a 1-device mesh must reproduce the
    meshless gather-based step bit-for-bit, kernels on and off, fp32 and
    int8, including through a live delta buffer."""
    corpus, q, fq = data
    mesh = make_mesh((1, 1), ("data", "model"))
    e0 = _engine(corpus, backend, use_pallas, False, storage)
    e1 = _engine(corpus, backend, use_pallas, True, storage, mesh=mesh)
    _assert_identical(e0.search(q, fq), e1.search(q, fq))
    r = np.random.default_rng(0)
    nv = r.normal(size=(20, corpus.spec.d)).astype(np.float32)
    nf = corpus.filters[:20].copy()
    e0.insert(nv, nf)
    e1.insert(nv, nf)
    e0._cache.clear()
    e1._cache.clear()
    _assert_identical(e0.search(q, fq), e1.search(q, fq))


@pytest.mark.parametrize("backend", ["flat", "ivf", "pq"])
def test_one_device_mesh_gather_free_identity(data, backend):
    """A 1-device mesh runs the shard_map gather-free step (local gather +
    carried scores); it must match the meshless gather-based engine."""
    corpus, q, fq = data
    mesh = make_mesh((1, 1), ("data", "model"))
    e0 = _engine(corpus, backend, False, False)
    e1 = _engine(corpus, backend, False, True, mesh=mesh)
    _assert_identical(e0.search(q, fq), e1.search(q, fq))


_PRELUDE = """
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import FCVIConfig, build
    from repro.data.synthetic import CorpusSpec, make_corpus, sample_queries
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import EngineConfig, FCVIEngine

    assert len(jax.devices()) == 8
    spec = CorpusSpec(n=1000, d=64, n_categories=5, n_numeric=3, seed=2)
    corpus = make_corpus(spec)
    q, fq = sample_queries(corpus, 5, seed=3)
    q, fq = np.asarray(q), np.asarray(fq)
    mesh = make_mesh((8, 1), ("data", "model"))

    def engine(backend, use_pallas, gather_free, storage="float32",
               use_mesh=True, **kw):
        cfg = FCVIConfig(alpha=1.0, lam=0.6, c=8.0, backend=backend,
                         nlist=16, nprobe=4, pq_m=8, pq_ksub=32, pq_coarse=8,
                         use_pallas=use_pallas, storage_dtype=storage)
        idx = build(jnp.asarray(corpus.vectors),
                    jnp.asarray(corpus.filters), cfg)
        ek = EngineConfig(k=5, batch_size=16, compact_threshold=256,
                          gather_free=gather_free)
        if use_mesh:
            return FCVIEngine(idx, ek, mesh=mesh, **kw)
        return FCVIEngine(idx, ek, **kw)

    def check(a, b, tag):
        (s0, i0), (s1, i1) = a, b
        assert (np.asarray(i0) == np.asarray(i1)).all(), tag
        assert (np.asarray(s0) == np.asarray(s1)).all(), tag
"""


@pytest.mark.slow
def test_eight_device_gather_free_vs_psum_step():
    """Acceptance: on a forced 8-device mesh the gather-free step (shard-
    local gathers, merge carries scores) is bit-identical to the mask+psum
    step — flat/IVF/PQ, kernels on and off, fp32 and int8, with a delta."""
    run_in_subprocess(_PRELUDE + """
    r = np.random.default_rng(0)
    nv = r.normal(size=(20, spec.d)).astype(np.float32)
    nf = corpus.filters[:20].copy()
    for backend in ("flat", "ivf", "pq"):
        storages = ("float32",) if backend == "pq" else ("float32", "int8")
        for use_pallas in (False, True):
            for storage in storages:
                ref = engine(backend, use_pallas, False, storage,
                             use_mesh=False)
                lg = engine(backend, use_pallas, False, storage)
                gf = engine(backend, use_pallas, True, storage)
                want = ref.search(q, fq)
                tag = (backend, use_pallas, storage)
                check(want, lg.search(q, fq), tag + ("psum",))
                check(want, gf.search(q, fq), tag + ("gather-free",))
                for e in (ref, lg, gf):
                    e.insert(nv, nf)
                    e._cache.clear()
                check(ref.search(q, fq), gf.search(q, fq), tag + ("delta",))
    print("gather-free parity OK")
    """)


@pytest.mark.slow
def test_routed_and_degraded_gather_free():
    """Routing and degraded serving compose with the gather-free step: the
    routed step's extra outputs and the dead-shard skip branches must leave
    results identical to their gather-based counterparts."""
    run_in_subprocess(_PRELUDE + """
    for backend in ("flat", "ivf"):
        pl = "cluster" if backend == "flat" else "contiguous"
        ref = engine(backend, False, False, use_mesh=False)
        gf = engine(backend, False, True, routing="routed", placement=pl)
        check(ref.search(q, fq), gf.search(q, fq), (backend, "routed"))
    for backend in ("flat", "ivf", "pq"):
        lg = engine(backend, False, False)
        gf = engine(backend, False, True)
        for e in (lg, gf):
            e.health.mark_dead([1])
        check(lg.search(q, fq), gf.search(q, fq), (backend, "degraded"))
    print("routed/degraded gather-free OK")
    """)

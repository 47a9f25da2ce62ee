"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import fused_score_topk, ops, ref


def _rand(r, shape, dtype):
    x = r.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("n,d,m", [(256, 64, 4), (512, 128, 8), (128, 96, 3),
                                   (384, 256, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_transform(n, d, m, dtype):
    r = np.random.default_rng(n + m)
    v = _rand(r, (n, d), dtype)
    f = _rand(r, (n, m), dtype)
    P = ref.partition_matrix(d, m)
    mv, sv = jnp.full((d,), 0.2), jnp.full((d,), 1.3)
    mf, sf = jnp.full((m,), -0.1), jnp.full((m,), 0.8)
    got = ops.fused_transform(v, f, P, 2.0, mv, sv, mf, sf, block_rows=128)
    want = ref.ref_fused_transform(v, f, P, 2.0, mv, sv, mf, sf)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def _ints(r, shape, dtype, lo=-2, hi=2):
    """Small-integer data: every score is exact in fp32 (and the rows exact
    in bf16), so equal scores tie exactly and any order of summation agrees."""
    return jnp.asarray(r.integers(lo, hi + 1, size=shape).astype(np.float32),
                       dtype)


@pytest.mark.parametrize("n,d,q,k,case", [
    pytest.param(512, 64, 64, 8, "random", id="512-64-64-8"),
    pytest.param(256, 128, 128, 16, "random", id="256-128-128-16"),
    pytest.param(1024, 32, 64, 32, "random", id="1024-32-64-32"),
    # exact ties within a block and across blocks (every row four times)
    pytest.param(512, 16, 64, 24, "ties", id="ties"),
    # 20 eligible rows for k = 40: -inf fill with id 0
    pytest.param(256, 32, 64, 40, "mask-short", id="mask-short"),
    # k larger than one corpus block
    pytest.param(512, 16, 64, 160, "ints", id="k-over-block"),
])
def test_score_topk(n, d, q, k, case):
    r = np.random.default_rng(n + k)
    if case == "random":
        corpus = _rand(r, (n, d), jnp.float32)
        queries = _rand(r, (q, d), jnp.float32)
        sq = jnp.sum(corpus * corpus, -1)
        v1, i1 = ops.score_topk(corpus, sq, queries, k, block_rows=128,
                                block_q=64)
        v2, i2 = ref.ref_score_topk(corpus, sq, queries, k)
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-4,
                                   atol=1e-4)
        assert (np.asarray(i1) == np.asarray(i2)).mean() > 0.999
        return
    corpus = _ints(r, (n, d), jnp.float32, -1, 1)
    if case == "ties":
        corpus = jnp.tile(corpus[:n // 4], (4, 1))
    queries = _ints(r, (q, d), jnp.float32)
    sq = jnp.sum(corpus * corpus, -1)
    mask = None
    if case == "mask-short":
        keep = r.choice(n, 20, replace=False)
        mask = jnp.zeros((n,), jnp.float32).at[keep].set(1.0)
    v1, i1 = ops.score_topk(corpus, sq, queries, k, mask=mask,
                            block_rows=128, block_q=64)
    v2, i2 = ref.ref_score_topk(corpus, sq, queries, k, mask=mask)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    if case == "mask-short":
        assert np.isneginf(np.asarray(v1)[:, 20:]).all()
        assert (np.asarray(i1)[:, 20:] == 0).all()


@pytest.mark.parametrize("b,kp,d,m", [(8, 32, 64, 4), (16, 64, 128, 8)])
def test_rescore(b, kp, d, m):
    r = np.random.default_rng(b)
    cv = _rand(r, (b, kp, d), jnp.float32)
    cf = _rand(r, (b, kp, m), jnp.float32)
    qn = _rand(r, (b, d), jnp.float32)
    fqn = _rand(r, (b, m), jnp.float32)
    got = ops.rescore(cv, cf, qn, fqn, 0.35, block_b=4)
    want = ref.ref_rescore(cv, cf, qn, fqn, 0.35)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("nlist,maxl,d,nprobe,k",
                         [(8, 64, 64, 3, 8), (16, 128, 32, 5, 16)])
def test_ivf_score_topk(nlist, maxl, d, nprobe, k):
    r = np.random.default_rng(nlist)
    grouped = _rand(r, (nlist, maxl, d), jnp.float32)
    gsq = jnp.sum(grouped * grouped, -1)
    valid = jnp.asarray((r.random((nlist, maxl)) > 0.15).astype(np.float32))
    probes = jnp.asarray(r.choice(nlist, nprobe, replace=False).astype(np.int32))
    qv = _rand(r, (d,), jnp.float32)
    v1, i1 = ops.ivf_score_topk(grouped, gsq, valid, probes, qv, k)
    v2, i2 = ref.ref_ivf_score_topk(grouped, gsq, valid > 0.5, probes, qv, k)
    # kernel drops the ||q||^2 constant: compare shifted
    q2 = float(jnp.sum(qv * qv))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2) + q2,
                               rtol=1e-4, atol=1e-4)
    assert (np.asarray(i1) == np.asarray(i2)).all()


@pytest.mark.parametrize("b,nlist,maxl,d,nprobe,k",
                         [(4, 8, 64, 64, 3, 8), (6, 16, 128, 32, 5, 16)])
def test_ivf_score_topk_batch(b, nlist, maxl, d, nprobe, k):
    """Batched probed-slab kernel vs vmapped oracle (kernel convention)."""
    r = np.random.default_rng(b + nlist)
    grouped = _rand(r, (nlist, maxl, d), jnp.float32)
    gsq = jnp.sum(grouped * grouped, -1)
    valid = jnp.asarray((r.random((nlist, maxl)) > 0.15).astype(np.float32))
    probes = jnp.asarray(np.stack(
        [r.choice(nlist, nprobe, replace=False) for _ in range(b)]
    ).astype(np.int32))
    qs = _rand(r, (b, d), jnp.float32)
    v1, i1 = ops.ivf_score_topk_batch(grouped, gsq, valid, probes, qs, k)
    v2, i2 = ops.ivf_score_topk_batch(grouped, gsq, valid, probes, qs, k,
                                      use_pallas=False)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=1e-4, atol=1e-4)
    assert (np.asarray(i1) == np.asarray(i2)).all()


def _dedup_fixture(case, r, b, nlist, maxl, d, nprobe, dtype):
    """(grouped, gsq, valid, probes, queries, mask) of a small-integer case
    (see ``_ints``); each query's probes in ascending list order, the order
    the dedup kernel walks, so the per-probe kernel meets ties in the same
    order."""
    grouped = _ints(r, (nlist, maxl, d), jnp.float32, -1, 1)
    if case == "ties":      # every list's rows again in another list
        grouped = jnp.concatenate([grouped[:nlist // 2]] * 2)
    grouped = grouped.astype(dtype)
    gsq = jnp.sum(grouped.astype(jnp.float32) ** 2, -1)
    valid = jnp.asarray((r.random((nlist, maxl)) > 0.15).astype(np.float32))
    if case == "filler":    # one shared probe set: most slots are filler
        probes = np.tile(r.choice(nlist, nprobe, replace=False), (b, 1))
    elif case == "sparse":  # disjoint probe sets: one member per slot
        probes = r.permutation(nlist)[:b * nprobe].reshape(b, nprobe)
    else:
        probes = np.stack([r.choice(nlist, nprobe, replace=False)
                           for _ in range(b)])
    probes = jnp.asarray(np.sort(probes, axis=1).astype(np.int32))
    mask = None
    if case == "mask":
        mask = jnp.asarray((r.random((nlist, maxl)) > 0.5).astype(np.float32))
    return grouped, gsq, valid, probes, _ints(r, (b, d), jnp.float32), mask


@pytest.mark.parametrize("b,nlist,maxl,d,nprobe,k,case", [
    pytest.param(4, 8, 64, 64, 3, 8, "random", id="4-8-64-64-3-8"),
    pytest.param(6, 16, 128, 32, 5, 16, "random", id="6-16-128-32-5-16"),
    # exact score ties within a list and across slots (duplicated rows)
    pytest.param(6, 16, 64, 16, 5, 24, "ties", id="ties"),
    # at most 2 x 64 valid rows for k = 160 (k over one page): -inf, id 0
    pytest.param(5, 8, 64, 16, 2, 160, "short", id="short-rows"),
    # 3 live slots of 12, the rest all-zero member filler
    pytest.param(4, 32, 64, 16, 3, 16, "filler", id="filler"),
    # each slot probed by one query of 8
    pytest.param(8, 32, 64, 16, 2, 16, "sparse", id="member-sparse"),
    # a list longer than PAGE_BUDGET, scanned in pages, k over one page
    pytest.param(4, 4, 1536, 1024, 2, 800, "paged", id="paged"),
    # the filter algebra's candidate mask
    pytest.param(6, 16, 64, 16, 5, 24, "mask", id="mask"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ivf_score_topk_dedup(b, nlist, maxl, d, nprobe, k, case, dtype):
    """Probe-major dedup kernel vs its oracle AND the per-probe batch kernel:
    deduplicating shared slabs must not change any result."""
    from repro.kernels.ivf_score import dedup_probes, page_rows

    r = np.random.default_rng(b + nlist)
    if case != "random":
        grouped, gsq, valid, probes, qs, mask = _dedup_fixture(
            case, r, b, nlist, maxl, d, nprobe, dtype)
        uniq, member = dedup_probes(probes, nlist)
        live = np.asarray(member).any(axis=1)
        if case == "filler":
            assert live.sum() == nprobe < live.size
        if case == "sparse":
            assert (np.asarray(member).sum(axis=1) == 1).all()
        if case == "paged":
            assert page_rows(maxl, d * grouped.dtype.itemsize) < k < maxl
        v1, i1 = ops.ivf_score_topk_dedup(grouped, gsq, valid, uniq, member,
                                          qs, k, mask=mask)
        v2, i2 = ops.ivf_score_topk_dedup(grouped, gsq, valid, uniq, member,
                                          qs, k, mask=mask, use_pallas=False)
        vb, ib = ops.ivf_score_topk_batch(
            grouped, gsq, valid if mask is None else valid * mask, probes,
            qs, k)
        for v, i in ((v2, i2), (vb, ib)):
            np.testing.assert_array_equal(np.asarray(v1), np.asarray(v))
            np.testing.assert_array_equal(np.asarray(i1), np.asarray(i))
        if case == "short":
            dead = np.isneginf(np.asarray(v1))
            assert dead.any() and (np.asarray(i1)[dead] == 0).all()
        return
    grouped = _rand(r, (nlist, maxl, d), dtype)
    gsq = jnp.sum(grouped.astype(jnp.float32) ** 2, -1)
    valid = jnp.asarray((r.random((nlist, maxl)) > 0.15).astype(np.float32))
    probes = jnp.asarray(np.stack(
        [r.choice(nlist, nprobe, replace=False) for _ in range(b)]
    ).astype(np.int32))
    qs = _rand(r, (b, d), jnp.float32)
    uniq, member = dedup_probes(probes, nlist)
    assert uniq.shape[0] == min(nlist, b * nprobe)
    v1, i1 = ops.ivf_score_topk_dedup(grouped, gsq, valid, uniq, member, qs, k)
    v2, i2 = ops.ivf_score_topk_dedup(grouped, gsq, valid, uniq, member, qs, k,
                                      use_pallas=False)
    vb, ib = ops.ivf_score_topk_batch(grouped, gsq, valid, probes, qs, k)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=1e-4, atol=1e-4)
    assert (np.asarray(i1) == np.asarray(i2)).all()
    np.testing.assert_allclose(np.asarray(v1), np.asarray(vb),
                               rtol=1e-4, atol=1e-4)
    assert (np.asarray(i1) == np.asarray(ib)).all()


def _gate_steps(blocks, k):
    """The gated merge's step count in NumPy: per block, the most entries
    any row takes from it into its running top-k (first occurrence wins a
    tie, so a later equal score does not enter)."""
    run = np.full((blocks[0].shape[0], k), -np.inf)
    steps = 0
    for blk in blocks:
        both = np.concatenate([run, blk], axis=1)
        top = np.argsort(-both, axis=1, kind="stable")[:, :k]
        steps += int((top >= k).sum(axis=1).max())
        run = np.take_along_axis(both, top, axis=1)
    return steps


@pytest.mark.parametrize("b,nlist,maxl,d,nprobe,k",
                         [(6, 16, 64, 16, 5, 24), (8, 32, 128, 16, 3, 40)])
def test_ivf_dedup_count_steps(b, nlist, maxl, d, nprobe, k):
    """``count_steps`` returns the selection steps of a NumPy model of the
    gate (at most k per slot) and leaves the results as they were."""
    from repro.kernels.ivf_score import dedup_probes

    r = np.random.default_rng(b * nlist)
    grouped, gsq, valid, probes, qs, _ = _dedup_fixture(
        "ints", r, b, nlist, maxl, d, nprobe, jnp.float32)
    uniq, member = dedup_probes(probes, nlist)
    args = (grouped, gsq, valid, uniq, member, qs, k)
    v0, i0 = ops.ivf_score_topk_dedup(*args)
    v1, i1, steps = ops.ivf_score_topk_dedup(*args, count_steps=True)
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))

    g, sq, ok = (np.asarray(a, np.float64) for a in (grouped, gsq, valid))
    qn, mem = np.asarray(qs, np.float64), np.asarray(member)
    blocks = [np.where((ok[u] > 0.5)[None, :] & (mem[s] > 0.5)[:, None],
                       2.0 * qn @ g[u].T - sq[u][None, :], -np.inf)
              for s, u in enumerate(np.asarray(uniq))]
    want = _gate_steps(blocks, k)
    assert int(steps) == want
    assert 0 < want < len(blocks) * k
    with pytest.raises(ValueError):
        ops.ivf_score_topk_dedup(*args, count_steps=True, use_pallas=False)


def _explicit_pad_score_topk(corpus, sq, queries, k, scales, mask):
    """Reference: the kernel on explicitly padded operands. Corpus rows
    zero-padded to the 128-row tile with +inf squared norms, unit scales and
    a zero mask (pad rows score -inf), queries to the 64-row tile; the
    query padding is sliced off."""
    n, d = corpus.shape
    nq = queries.shape[0]
    br, bq = min(128, n), min(64, nq)
    n_pad, q_pad = -n % br, -nq % bq
    corpus = jnp.concatenate([corpus, jnp.zeros((n_pad, d), corpus.dtype)])
    sq = jnp.concatenate([sq, jnp.full((n_pad,), jnp.inf, sq.dtype)])
    if scales is not None:
        scales = jnp.concatenate([scales, jnp.ones((n_pad,), scales.dtype)])
    if mask is not None:
        mask = jnp.concatenate([mask, jnp.zeros((n_pad,), mask.dtype)])
    queries = jnp.concatenate([queries, jnp.zeros((q_pad, d), queries.dtype)])
    vals, idx = fused_score_topk.score_topk(
        corpus, sq, queries, k, scales=scales, mask=mask, block_rows=br,
        block_q=bq, interpret=True)
    return vals[:nq], idx[:nq]


@pytest.mark.parametrize("n,d,nq,k,variant", [
    # below one tile: a single block of all n rows, random data
    pytest.param(100, 32, 5, 7, "random", id="100-below-tile"),
    # 1000 rows: a ragged last block of 104 rows, k' above it
    pytest.param(1000, 32, 64, 141, "plain", id="1000-plain"),
    pytest.param(1000, 32, 64, 141, "masked", id="1000-masked"),
    pytest.param(1000, 32, 64, 141, "int8", id="1000-int8"),
    # 20 eligible rows for k = 40, some in the last block: -inf / id-0 fill
    pytest.param(1000, 32, 64, 40, "mask-short", id="1000-mask-short"),
    # the cell's CPU twin's row count: a last block of 9 rows; 70 queries
    pytest.param(15625, 32, 70, 141, "plain", id="15625-plain"),
    pytest.param(15625, 32, 70, 141, "masked-int8", id="15625-masked-int8"),
])
def test_score_topk_padded_arbitrary_shapes(n, d, nq, k, variant):
    """Padded dispatch: corpus rows and query counts off the tile multiples.
    The corpus is scanned in place with a ragged last block; ids and scores
    equal the explicitly padded scan's bit for bit, and the ids equal the
    plain reference's. Except in the random case, queries are planted on
    the corpus's last rows so the ragged block's ids must surface."""
    r = np.random.default_rng(n + k)
    scales = mask = None
    if variant == "random":
        corpus = rows = _rand(r, (n, d), jnp.float32)
        queries = _rand(r, (nq, d), jnp.float32)
    else:
        corpus = rows = _ints(r, (n, d), jnp.float32, -1, 1)
        if "int8" in variant:
            # power-of-two scales: every dequantized score stays exact
            scales = jnp.asarray(2.0 ** r.integers(-1, 2, size=n), jnp.float32)
            corpus = corpus.astype(jnp.int8)
            rows = corpus.astype(jnp.float32) * scales[:, None]
        planted = n - 1 - np.arange(nq) % 64
        queries = rows[planted]
        if "mask" in variant:
            keep = r.random(n) < 0.5
            if variant == "mask-short":
                keep = np.zeros(n, bool)
                keep[r.choice(n - 64, 10, replace=False)] = True
                keep[n - 10:] = True
            else:
                keep[planted] = True
            mask = jnp.asarray(keep, jnp.float32)
    sq = jnp.sum(rows * rows, -1)
    v1, i1 = ops.score_topk_padded(corpus, sq, queries, k, scales=scales,
                                   mask=mask)
    v0, i0 = _explicit_pad_score_topk(corpus, sq, queries, k, scales, mask)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v0))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    v2, i2 = ref.ref_score_topk(rows if scales is None else corpus, sq,
                                queries, k, scales=scales, mask=mask)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    if variant == "random":
        return
    ids = np.asarray(i1)
    if variant == "mask-short":
        assert np.isneginf(np.asarray(v1)[:, 20:]).all()
        assert (ids[:, 20:] == 0).all()
        assert np.isin(np.arange(n - 10, n), ids).all()
    else:
        assert (ids == planted[:, None]).any(axis=1).all()


@pytest.mark.parametrize("n,M,ksub,q", [(500, 4, 32, 3), (512, 8, 64, 5)])
def test_pq_score_batch(n, M, ksub, q):
    """Multi-query ADC kernel, incl. row counts that need padding."""
    r = np.random.default_rng(n + q)
    codes = jnp.asarray(r.integers(0, ksub, (n, M)).astype(np.int32))
    luts = jnp.asarray(r.random((q, M, ksub)).astype(np.float32))
    got = ops.pq_score_batch(codes, luts, block_rows=128)
    want = ops.pq_score_batch(codes, luts, use_pallas=False)
    assert got.shape == (q, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,M,ksub", [(512, 8, 64), (1024, 16, 256),
                                      (256, 4, 16)])
def test_pq_score(n, M, ksub):
    r = np.random.default_rng(M)
    codes = jnp.asarray(r.integers(0, ksub, (n, M)).astype(np.int32))
    lut = jnp.asarray(r.random((M, ksub)).astype(np.float32))
    got = ops.pq_score(codes, lut, block_rows=128)
    want = ref.ref_pq_score(codes, lut)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_ops_fallback_matches_pallas():
    """use_pallas=False (oracle path) and kernels must agree bit-for-bit-ish."""
    r = np.random.default_rng(9)
    corpus = _rand(r, (256, 64), jnp.float32)
    q = _rand(r, (32, 64), jnp.float32)
    sq = jnp.sum(corpus * corpus, -1)
    v1, i1 = ops.score_topk(corpus, sq, q, 8)
    v2, i2 = ops.score_topk(corpus, sq, q, 8, use_pallas=False)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-4,
                               atol=1e-4)
    assert (np.asarray(i1) == np.asarray(i2)).all()


@pytest.mark.parametrize("q,M,dsub,ksub", [(64, 8, 8, 64), (5, 4, 16, 32),
                                           (130, 2, 8, 16)])
def test_pq_lut_qdot(q, M, dsub, ksub):
    """LUT-construction cross-term kernel vs the einsum oracle (incl. query
    counts that are not a multiple of the kernel's query block)."""
    r = np.random.default_rng(q + ksub)
    qs = _rand(r, (q, M, dsub), jnp.float32)
    cb = _rand(r, (M, ksub, dsub), jnp.float32)
    got = ops.pq_lut_qdot(qs, cb, block_q=64)
    want = ref.ref_pq_lut_qdot(qs, cb)
    assert got.shape == (q, M, ksub)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

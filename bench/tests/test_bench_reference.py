"""The fp64 references against a direct brute force at tiny sizes, and the
bf16 control's code in fp32 agreeing with them."""
import numpy as np
import pytest

from reference import check, plain


def _corpus(n=300, d=16, m=8, seed=0):
    rng = np.random.default_rng(seed)
    v = rng.normal(size=(n, d)).astype(np.float32)
    f = rng.uniform(size=(n, m)).astype(np.float32)
    return v, f


def _std(x):
    x = x.astype(np.float64)
    return x.mean(0), x.std(0) + 1e-6


def test_combined_matches_brute_force():
    v, f = _corpus()
    q, fq = v[:5] + 0.1, f[5:10]
    lam, k = 0.6, 7
    vm, vs = _std(v)
    fm, fs = _std(f)

    def cos(a, b):
        return (a @ b.T) / np.outer(np.linalg.norm(a, axis=1),
                                    np.linalg.norm(b, axis=1))
    s = (lam * cos((q - vm) / vs, (v - vm) / vs)
         + (1 - lam) * cos((fq - fm) / fs, (f - fm) / fs))
    want = np.argsort(-s, axis=1, kind="stable")[:, :k]
    ref = plain.Combined(v, f, lam)
    got_s, got_i = ref.topk(q, fq, k)
    np.testing.assert_array_equal(got_i, want)
    np.testing.assert_allclose(got_s, np.take_along_axis(s, want, 1),
                               rtol=1e-12)
    np.testing.assert_allclose(ref.scores_of(q, fq, want), got_s,
                               rtol=1e-12)


def test_filtered_matches_brute_force():
    v, f = _corpus()
    q = v[:4] + 0.05
    col, lo, hi = 6, 0.2, 0.6
    ref = plain.Filtered(v, f, 1.0, [(col, lo, hi)])
    vm, vs = _std(v)
    fm, fs = _std(f)
    fold = fm.copy()
    fold[col] = 0.5 * (float(np.float32(lo)) + float(np.float32(hi)))

    def psi(x, y):
        return ((x - vm) / vs).reshape(len(x), -1, 8) - ((y - fm) / fs)[:, None]
    rows = psi(v, f).reshape(len(v), -1)
    qt = psi(q, np.broadcast_to(fold, (4, 8))).reshape(4, -1)
    elig = (f[:, col] >= np.float32(lo)) & (f[:, col] <= np.float32(hi))
    d2 = ((qt[:, None, :] - rows[None]) ** 2).sum(-1)
    d2 = np.where(elig[None], d2, np.inf)
    want = np.argsort(d2, axis=1, kind="stable")[:, :5]
    got_d2, got_i, near = ref.topk(q, 5)
    np.testing.assert_array_equal(got_i, want)
    np.testing.assert_allclose(got_d2, np.take_along_axis(d2, want, 1),
                               rtol=1e-9)
    assert not near.any()
    assert set(np.nonzero(ref.elig)[0]) == set(np.nonzero(elig)[0])


def test_filtered_flags_near_ties():
    v, f = _corpus(n=50)
    v[1] = v[0]
    f[1] = f[0]
    f[:2, 6] = 0.5
    ref = plain.Filtered(v, f, 1.0, [(6, 0.0, 1.0)])
    _, ids, near = ref.topk(v[:1], 3)
    assert set(ids[0, :2]) == {0, 1} and near[0, :2].all()


def test_checks_catch_faults():
    v, f = _corpus(n=400)
    q, fq = v[:6] + 0.01, f[:6]
    ref = plain.Combined(v, f, 0.6)
    s, i = ref.topk(q, fq, 5)
    s32 = s.astype(np.float32)
    good = check.similarity(v, f, 0.6, q, fq, i, s32)
    assert good["bad_answers"] == 0 and good["recall_miss"] == 0
    assert good["score_err"] < 1e-6
    alt = i.copy()
    alt[:, 0] = (alt[:, 0] + 1) % 400
    bad = check.similarity(v, f, 0.6, q, fq, alt, s32)
    assert bad["score_err"] > 1e-3 and bad["recall_miss"] > 0
    drop = i.copy()
    drop[3:] = -1
    s_drop = s32.copy()
    s_drop[3:] = -np.inf
    assert check.similarity(v, f, 0.6, q, fq, drop, s_drop)["bad_answers"] == 3


def test_verdict():
    ok, rows = check.verdict({"a": 0.0, "b": 0.5},
                             {"a": {"limit": 0}, "b": {"limit": 1.0}})
    assert ok and [r[0] for r in rows] == ["a", "b"]
    assert not check.verdict({"a": 1.0}, {"a": {"limit": 0}})[0]
    assert not check.verdict({"a": float("nan")}, {"a": {"limit": 1}})[0]
    assert not check.verdict({"c": 0.0}, {})[0]


@pytest.mark.parametrize("mode", ["similarity", "predicate"])
def test_control_code_in_fp32_agrees_with_the_reference(mode):
    import jax.numpy as jnp

    from reference.control import Control

    v, f = _corpus(n=512, d=64)
    cfg = {"k": 5, "batch_size": 8, "fcvi": {"lam": 0.6, "alpha": 1.0}}
    rng = np.random.default_rng(1)
    q = v[:8] + 0.05 * rng.normal(size=(8, 64)).astype(np.float32)
    fq = f[8:16]
    ranges = [(7, 0.0, 0.5)]
    ctl = Control(cfg, {"mode": mode}, jnp.asarray(v), f, ranges,
                  dtype=jnp.float32)
    s, i = ctl.serve(q, fq)
    if mode == "similarity":
        got = check.similarity(v, f, 0.6, q, fq, i, s)
        assert got["recall_miss"] == 0 and got["score_err"] < 1e-5
    else:
        got = check.predicate(v, f, 1.0, ranges, q, i, s)
        assert got["id_mismatch"] == 0 and got["d2_rel_err"] < 1e-5
    assert got["bad_answers"] == 0

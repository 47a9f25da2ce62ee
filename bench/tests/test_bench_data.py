"""Corpus, queries and arrivals repeat per seed and differ across seeds."""
import json
from pathlib import Path

import numpy as np
import pytest

from harness import data

CFG = json.loads((Path(__file__).resolve().parents[1] / "configs" /
                  "cohere768-flat.json").read_text())
BIG = 3_000_000_123        # seeds may exceed 32 bits


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_corpus_repeats_per_seed(seed):
    v1, f1 = data.corpus(dict(CFG, n=512), seed)
    v2, f2 = data.corpus(dict(CFG, n=512), seed)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    assert v1.shape == (512, CFG["d"]) and f1.shape == (512, 8)


def test_corpus_differs_across_seeds():
    a = np.asarray(data.corpus(dict(CFG, n=512), 7)[0])
    for other in (8, BIG, BIG + 1):
        b = data.corpus(dict(CFG, n=512), other)[0]
        assert not np.array_equal(a, np.asarray(b))


@pytest.mark.parametrize("seed", [7, BIG])
def test_fixed_dataset_is_the_same_for_every_seed(seed):
    ivf = json.loads((Path(__file__).resolve().parents[1] / "configs" /
                      "sift1m-ivf.json").read_text())
    small = dict(ivf, n=512)
    a = data.corpus(small, seed)
    b = data.corpus(small, seed + 1)
    c = data.corpus(dict(CFG, n=512, d=ivf["d"]), ivf["corpus_seed"])
    for x, y, z in zip(a, b, c):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        np.testing.assert_array_equal(np.asarray(x), np.asarray(z))
    q1, _ = data.queries(small, *a, seed, 1, 16, 0.5)
    q2, _ = data.queries(small, *b, seed + 1, 1, 16, 0.5)
    assert not np.array_equal(q1, q2)


def test_attributes_shape():
    _, f = data.corpus(dict(CFG, n=4096), 3)
    f = np.asarray(f)
    onehot = f[:, :5]
    assert np.array_equal(onehot.sum(1), np.ones(4096))
    u = f[:, 6:]
    assert u.min() >= 0 and u.max() < 1
    # the 1% predicate of the filter cell keeps about 1% of rows
    keep = ((u[:, 1] >= np.float32(0.99)) & (u[:, 1] <= 1.0)).mean()
    assert 0.004 < keep < 0.02
    assert data.attr_names(CFG) == ("cat0", "cat1", "cat2", "cat3", "cat4",
                                    "num_corr", "num_u0", "num_u1")


def test_queries_repeat_and_streams_differ():
    v, f = data.corpus(dict(CFG, n=512), 5)
    q1, fq1 = data.queries(CFG, v, f, 5, 1, 16, 0.5)
    q2, fq2 = data.queries(CFG, v, f, 5, 1, 16, 0.5)
    q3, _ = data.queries(CFG, v, f, 5, 2, 16, 0.5)
    q4, _ = data.queries(CFG, v, f, 6, 1, 16, 0.5)
    np.testing.assert_array_equal(q1, q2)
    np.testing.assert_array_equal(fq1, fq2)
    assert not np.array_equal(q1, q3) and not np.array_equal(q1, q4)


def test_arrivals_same_count_other_order():
    a = data.arrivals(5, 320, 30)
    b = data.arrivals(BIG, 320, 30)
    assert a.shape == b.shape == (9600,)
    assert np.array_equal(a, data.arrivals(5, 320, 30))
    assert not np.array_equal(a, b)
    assert (np.diff(a) >= 0).all() and a.min() >= 0 and a.max() < 30

"""Seeded corpora and queries, generated on the device with ``jax.random``.

A corpus is a mixture of Gaussians in ``d`` dimensions with ``m`` attribute
columns: one-hot categorical columns (Zipf over their values, correlated
with the vector cluster), one numeric column correlated with the cluster and
uniform numeric columns. The same seed gives the same bits; the shapes come
from the configuration file, never from the seed.

A configuration that names a ``corpus_seed`` holds one fixed dataset, as a
published corpus is one file: every run seed gets the corpus of that seed,
and the run's ``--seed`` draws the queries alone. An index whose layout
follows the data (an IVF's list sizes, and with them the shapes it compiles
and the rows it scans) then does the same work under every run seed.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key from a seed of any size (they may exceed 32 bits) and
    a stream number, so that corpus, queries and warm-up never share bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 62) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def attr_names(cfg: dict) -> tuple:
    a = cfg["attributes"]
    return (tuple(f"cat{j}" for j in range(a["categories"]))
            + ("num_corr",)
            + tuple(f"num_u{j}" for j in range(a["uniform"])))


@partial(jax.jit, static_argnames=("n", "d", "clusters", "categories",
                                   "uniform"))
def _corpus(key, *, n, d, clusters, noise, categories, zipf, corr, uniform):
    ks = jax.random.split(key, 8)
    centers = jax.random.normal(ks[0], (clusters, d), jnp.float32)
    labels = jax.random.randint(ks[1], (n,), 0, clusters)
    vectors = centers[labels] + noise * jax.random.normal(
        ks[2], (n, d), jnp.float32)
    logits = -zipf * jnp.log(jnp.arange(1, categories + 1, dtype=jnp.float32))
    random_cat = jax.random.categorical(ks[3], logits, shape=(n,))
    use_corr = jax.random.uniform(ks[4], (n,)) < corr
    cat = jnp.where(use_corr, labels % categories, random_cat)
    onehot = jax.nn.one_hot(cat, categories, dtype=jnp.float32)
    num_corr = (labels.astype(jnp.float32) / clusters
                + 0.1 * jax.random.normal(ks[5], (n,), jnp.float32))
    num_u = jax.random.uniform(ks[6], (n, uniform), jnp.float32)
    filters = jnp.concatenate([onehot, num_corr[:, None], num_u], axis=1)
    return vectors, filters


def corpus(cfg: dict, seed: int):
    """(vectors (n, d), filters (n, m)) fp32 device arrays of the
    configuration ``cfg``: drawn from its ``corpus_seed`` where it names
    one, else from ``seed``."""
    v, a = cfg["vectors"], cfg["attributes"]
    seed = cfg.get("corpus_seed", seed)
    return _corpus(root_key(seed, 0), n=int(cfg["n"]), d=int(cfg["d"]),
                   clusters=int(v["clusters"]), noise=float(v["noise"]),
                   categories=int(a["categories"]), zipf=float(a["zipf"]),
                   corr=float(a["corr"]), uniform=int(a["uniform"]))


@partial(jax.jit, static_argnames=("count",))
def _queries(key, vectors, filters, *, count, noise):
    k1, k2, k3 = jax.random.split(key, 3)
    n = vectors.shape[0]
    rows = jax.random.randint(k1, (count,), 0, n)
    q = vectors[rows] + noise * jax.random.normal(
        k2, (count, vectors.shape[1]), jnp.float32)
    fq = filters[jax.random.randint(k3, (count,), 0, n)]
    return q, fq


def queries(cfg: dict, vectors, filters, seed: int, stream: int, count: int,
            noise_sigma: float):
    """``count`` queries as host fp32 arrays: corpus rows plus
    ``noise_sigma`` times the corpus noise, and filter targets drawn from
    corpus rows (independently of the query's row)."""
    noise = float(noise_sigma) * float(cfg["vectors"]["noise"])
    q, fq = _queries(root_key(seed, stream), vectors, filters,
                     count=int(count), noise=noise)
    return np.asarray(q), np.asarray(fq)


def arrivals(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Open-loop due times in [0, seconds): exactly round(rate * seconds)
    arrivals, uniform order statistics (a Poisson process conditioned on its
    count), so every seed offers the same amount of work in another order."""
    count = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([int(seed) & (2**64 - 1), 1])
    return np.sort(rng.uniform(0.0, seconds, count))

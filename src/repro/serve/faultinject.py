"""Deterministic fault injection for degraded-serving tests and benchmarks.

Three families of faults, all reproducible (no randomness, no timing races):

  * dispatch faults — ``FaultInjector`` attaches to ``FCVIEngine`` (via
    ``engine.fault_injector``) and (a) raises ``TransientShardError`` for the
    next N batches, exercising the bounded-retry/backoff envelope, and
    (b) feeds SYNTHETIC per-shard step times into the health layer's
    heartbeat (slow shards -> straggler eviction). Synthetic times are the
    only way to drive the straggler detector on a forced host mesh: all
    "shards" share the same cores, so real per-shard timing is neither
    observable in-process nor deterministic.

  * shard loss — not injected here (just ``engine.health.mark_dead``); what
    this module provides is the GROUND TRUTH to check degraded results
    against: ``surviving_reference(engine)`` builds a meshless engine over
    the same corpus with every dead shard's slab rows invalidated in place
    (flat: ``sq_norms=+inf``; IVF: dead lists emptied + grouped slabs
    rebuilt). Invalidating instead of deleting keeps ``index.size`` — and
    therefore the k' over-retrieval and escalation thresholds — IDENTICAL to
    the degraded engine's, so full end-to-end ``engine.search`` results must
    be bit-identical (the tentpole acceptance criterion).

  * state corruption — ``corrupt_checkpoint`` tears/flips/deletes pieces of
    an on-disk checkpoint step to exercise ``ckpt``'s integrity verification
    and newest-intact-step fallback.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from repro.serve.health import TransientShardError


@dataclasses.dataclass
class FaultInjector:
    """Deterministic per-batch fault source for ``FCVIEngine``.

    ``transient_failures``: the next N dispatched batches raise
    ``TransientShardError`` from ``before_batch`` (the engine retries with
    backoff; N <= ``cfg.max_retries`` eventually succeeds, larger N
    propagates). ``slow_shards``: shard -> slowdown factor applied to the
    synthetic heartbeat times (persistently slow shards get straggler-
    evicted by the health layer). ``base_step_time``: the healthy synthetic
    per-shard step time in seconds.
    """

    transient_failures: int = 0
    slow_shards: Dict[int, float] = dataclasses.field(default_factory=dict)
    base_step_time: float = 0.01
    injected: int = 0

    def before_batch(self):
        if self.transient_failures > 0:
            self.transient_failures -= 1
            self.injected += 1
            raise TransientShardError(
                f"injected transient dispatch failure "
                f"({self.transient_failures} left)")

    def shard_times(self, n_shards: int, elapsed: float) -> List[float]:
        return [self.base_step_time * self.slow_shards.get(s, 1.0)
                for s in range(n_shards)]


# ---------------------------------------------------------------------------
# Ground truth for shard loss: the surviving-rows reference engine
# ---------------------------------------------------------------------------

def surviving_row_mask(engine) -> np.ndarray:
    """(index.size,) bool — True for rows whose owning shard is alive.

    Ownership is the SLAB placement (``ShardedServing.slab_row_owner``):
    a shard's death removes exactly its slab block from candidate
    generation; the re-rank originals and the delta buffer are durable.
    """
    owner = engine._sharded.slab_row_owner()
    return engine.health.alive_mask()[owner]


def surviving_reference(engine):
    """A meshless engine whose candidate space is exactly the survivors.

    Same transform, same re-rank originals, same ``index.size`` (dead rows
    are invalidated in place, not removed — keeping k' and escalation
    thresholds identical), same configs, same pending delta rows. Degraded
    ``engine.search`` results must equal this engine's results bit-for-bit.
    """
    from repro.serve.engine import FCVIEngine

    idx = engine.index
    mask = surviving_row_mask(engine)
    b = idx.backend
    if idx.config.backend == "flat":
        # +inf squared norm -> the scoring expansion q.x - 0.5*||x||^2 is
        # -inf, so dead rows can never enter the candidate set
        sq = jnp.where(jnp.asarray(mask), b.sq_norms, jnp.inf)
        backend = dataclasses.replace(b, sq_norms=sq)
    elif idx.config.backend == "ivf":
        l2s = np.asarray(engine._sharded.slab.list_to_shard)
        dead_list = ~engine.health.alive_mask()[l2s]
        lists = np.asarray(b.lists).copy()
        sizes = np.asarray(b.list_sizes).copy()
        lists[dead_list] = -1          # empty the dead shards' lists;
        sizes[dead_list] = 0           # centroids stay (probe selection
                                       # must match the degraded step's)
        # invalid slots score -inf whatever the slab holds there, so the
        # grouped slab is shared with the index rather than rebuilt
        valid = jnp.where(jnp.asarray(dead_list)[:, None], 0.0, b.valid)
        backend = dataclasses.replace(
            b, lists=jnp.asarray(lists), list_sizes=jnp.asarray(sizes),
            valid=valid)
    else:
        raise NotImplementedError(
            f"surviving_reference: backend {idx.config.backend!r}")
    ref_idx = dataclasses.replace(idx, backend=backend)
    ref = FCVIEngine(ref_idx, dataclasses.replace(engine.cfg))
    ref._delta_v = [np.array(v, copy=True) for v in engine._delta_v]
    ref._delta_f = [np.array(f, copy=True) for f in engine._delta_f]
    return ref


# ---------------------------------------------------------------------------
# Checkpoint corruption
# ---------------------------------------------------------------------------

def corrupt_checkpoint(ckpt_dir: str, step: int, mode: str = "truncate"):
    """Deterministically damage one on-disk checkpoint step.

    ``mode``: 'truncate' cuts arrays.npz in half (a torn write);
    'flip' XORs one byte in the middle of arrays.npz (silent bit rot —
    caught by the manifest checksums); 'erase_manifest' makes
    manifest.json unparseable.
    """
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    npz = os.path.join(d, "arrays.npz")
    if mode == "truncate":
        size = os.path.getsize(npz)
        with open(npz, "r+b") as f:
            f.truncate(size // 2)
    elif mode == "flip":
        size = os.path.getsize(npz)
        with open(npz, "r+b") as f:
            f.seek(size // 2)
            byte = f.read(1)
            f.seek(size // 2)
            f.write(bytes([byte[0] ^ 0xFF]))
    elif mode == "erase_manifest":
        with open(os.path.join(d, "manifest.json"), "w") as f:
            f.write("{ torn json")
    else:
        raise ValueError(f"unknown corruption mode {mode!r}")


# ---------------------------------------------------------------------------
# Poisoned inputs (for the input-hardening boundary tests)
# ---------------------------------------------------------------------------

def poisoned_inputs(d: int, m: int) -> list:
    """(name, queries, filters) triples that ``engine.search`` must reject
    with a ``ValueError`` instead of producing garbage top-k."""
    q = np.zeros((2, d), np.float32)
    f = np.zeros((2, m), np.float32)
    qn = q.copy(); qn[0, 0] = np.nan
    qi = q.copy(); qi[1, -1] = np.inf
    fn = f.copy(); fn[0, 0] = np.nan
    fhuge = f.copy(); fhuge[0, 0] = 1e30
    return [
        ("nan_query", qn, f),
        ("inf_query", qi, f),
        ("nan_filter", q, fn),
        ("out_of_support_filter", q, fhuge),
        ("dim_mismatch_query", np.zeros((2, d + 1), np.float32), f),
        ("dim_mismatch_filter", q, np.zeros((2, m + 1), np.float32)),
        ("batch_mismatch", q, np.zeros((3, m), np.float32)),
        ("empty_batch", np.zeros((0, d), np.float32),
         np.zeros((0, m), np.float32)),
        ("not_2d", np.zeros((d,), np.float32), f),
    ]

"""The trace reduction: interval arithmetic, in-span device time and gap
labels on hand-made intervals, and the harness's spans read back from a
trace recorded here."""
import pytest

from harness import tracing


def test_union_merges_overlaps_and_drops_empty():
    got = tracing.union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 8), (6, 9)])
    assert got == [(0, 4), (5, 9)]


@pytest.mark.parametrize("lo,hi,want", [(0, 10, 6), (1, 6, 3), (4, 5, 0),
                                         (3.5, 8.5, 3.5), (9, 20, 0)])
def test_covered(lo, hi, want):
    merged = tracing.union([(0, 2), (3, 4), (5, 8)])
    assert tracing.covered(merged, lo, hi) == pytest.approx(want)


def test_gaps():
    merged = tracing.union([(1, 2), (3, 4)])
    assert tracing.gaps(merged, 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert tracing.gaps(merged, 1.5, 3.5) == [(2, 3)]
    assert tracing.gaps([], 0, 5) == [(0, 5)]


def _hand_trace():
    # window [0, 100); two search calls of 4 and 2 queries, a wait between
    spans = [("window", 0, 100, 0), ("search", 10, 40, 4),
             ("wait", 40, 60, 0), ("search", 60, 90, 2)]
    ops = {0: [(15, 25, "m/fusion.1"), (20, 30, "m/fusion.2"),
               (65, 85, "m/fusion.1"), (95, 99, "m/copy")]}
    return tracing.Trace(ops=ops, spans=spans)


def test_reduce_hand_made():
    r = tracing.reduce(_hand_trace(), top=3)
    ns = 1e-9
    assert r.window_s == pytest.approx(100 * ns)
    assert r.busy_s == pytest.approx((15 + 20 + 4) * ns)
    assert r.search_device_s == pytest.approx(35 * ns)
    assert r.search_host_s == pytest.approx((60 - 35) * ns)
    assert r.search_queries == 6
    assert [q for q, _ in r.calls] == [4, 2]
    assert r.calls[0][1] == pytest.approx(15 * ns)
    assert r.top_ops[0] == ("m/fusion.1", pytest.approx(30 * ns))
    labels = {lab for lab, _ in r.idle_gaps}
    assert labels <= {"search", "wait", "harness"}
    # the longest gap, 30..65, runs from inside a call through the wait
    # into the next call: labelled by the span at its middle (47.5, the wait)
    assert r.idle_gaps[0] == ("wait", pytest.approx(35 * ns))
    # 0..15 (middle 7.5, before the first call), 85..95 (middle 90, where
    # the second call has ended) and 99..100
    assert r.idle_by_label["harness"] == pytest.approx((15 + 10 + 1) * ns)


def test_reduce_needs_window():
    t = tracing.Trace(ops={0: []}, spans=[("search", 0, 1, 1)])
    with pytest.raises(ValueError):
        tracing.reduce(t)


def test_load_reads_the_harness_spans(tmp_path):
    """Record a short trace on this host and read the harness's spans back
    (a host holds no TPU plane, so no device operations are read)."""
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    span = tracing.span_factory(True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("window"):
            for _ in range(3):
                with span("search", queries=5):
                    f(x).block_until_ready()
                with span("wait"):
                    time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    t = tracing.load(str(tmp_path))
    names = [n for n, *_ in t.spans]
    assert names.count("window") == 1 and names.count("search") == 3
    assert names.count("wait") == 3
    assert all(q == 5 for n, _, _, q in t.spans if n == "search")
    r = tracing.reduce(t)
    assert r.search_queries == 15 and r.busy_s == 0
    assert r.window_s > 0.006


def test_require_device_work_fails_loudly():
    tracing.require_device_work(tracing.reduce(_hand_trace()))
    no_ops = tracing.Trace(ops={}, spans=_hand_trace().spans)
    with pytest.raises(ValueError, match="no device operation"):
        tracing.require_device_work(tracing.reduce(no_ops))
    outside = tracing.Trace(ops={0: [(45, 55, "m/fusion.1")]},
                            spans=_hand_trace().spans)
    with pytest.raises(ValueError, match="inside the 'search' spans"):
        tracing.require_device_work(tracing.reduce(outside))
    no_calls = tracing.Trace(ops=_hand_trace().ops,
                             spans=[("window", 0, 100, 0)])
    with pytest.raises(ValueError, match="no 'search' span"):
        tracing.require_device_work(tracing.reduce(no_calls))


def test_reduce_a_trace_recorded_on_a_v5e(tmp_path):
    """One 512-query call of ``sift1m-ivf.bulk`` traced on a TPU v5e
    (``data/ivf-bulk-v5e.xplane.pb.xz``): the chip's plane and its
    ``XLA Ops`` line are found, and the call's device time is the IVF
    dedup kernel's, at k' 133 and at the escalated 533."""
    import lzma
    from pathlib import Path

    packed = Path(__file__).resolve().parent / "data" / \
        "ivf-bulk-v5e.xplane.pb.xz"
    (tmp_path / "t.xplane.pb").write_bytes(lzma.decompress(
        packed.read_bytes()))
    t = tracing.load(str(tmp_path))
    assert sorted(t.ops) == [0] and len(t.ops[0]) > 1000
    r = tracing.reduce(t)
    tracing.require_device_work(r)
    assert r.search_queries == 512 and [q for q, _ in r.calls] == [512]
    assert 0.9 * r.window_s < r.busy_s < r.window_s
    assert r.search_device_s == pytest.approx(r.busy_s, rel=1e-6)
    top = [name for name, _ in r.top_ops[:2]]
    assert all("ivf_score_topk_dedup" in name for name in top)
    assert "f32[64,533]" in top[0] and "f32[64,133]" in top[1]
    assert sum(s for _, s in r.top_ops[:2]) > 0.95 * r.busy_s

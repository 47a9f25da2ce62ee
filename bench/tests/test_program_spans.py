"""The serving engine's own host spans (``fcvi.*``) and their reduction
(``harness/program.py``): the span tree a CPU run of ``FCVIEngine.search``
writes into a profiler trace, the attribution of device time and idle gaps
on hand-made intervals, and the per-layer readers on a trace of one
``sift1m-ivf.bulk`` call recorded on a TPU v5e."""
import lzma
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from harness import program, spec, tracing

DATA = Path(__file__).resolve().parent / "data"
READERS = ("step_device_us", "escalation_device_us", "escalation_idle_us",
           "escalation_pad_share")


# ---------------------------------------------------------------------------
# the span tree of a CPU run
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def corpus():
    import jax.numpy as jnp

    from repro.core import FCVIConfig, build
    from repro.data.synthetic import CorpusSpec, make_corpus, sample_queries

    c = make_corpus(CorpusSpec(n=2048, d=32, n_categories=6, n_numeric=2,
                               seed=3))
    idx = build(jnp.asarray(c.vectors), jnp.asarray(c.filters),
                FCVIConfig(alpha=1.0, lam=0.6, c=8.0))
    q, fq = sample_queries(c, 40, seed=4)
    return idx, np.asarray(q), np.asarray(fq)


def _traced(tmp_path, fn):
    import jax

    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    return out, program.load(str(tmp_path)), tracing.load(str(tmp_path))


def _children(spans, parents, i, name):
    return [j for j, p in enumerate(parents)
            if p == i and spans[j].name == name]


@pytest.mark.parametrize("margin,escalates", [(1e9, True), (-1.0, False)])
def test_search_writes_the_span_tree(corpus, tmp_path, margin, escalates):
    """40 queries in batches of 16: three ``fcvi.batch`` spans (16, 16 and
    8 real rows), each with one ``fcvi.step`` and one ``fcvi.fetch``, and an
    ``fcvi.escalate`` exactly where the batch escalated; the buckets it
    names add up to ``EngineStats.escalation_rows``."""
    from repro.serve.engine import EngineConfig, FCVIEngine

    idx, q, fq = corpus
    eng = FCVIEngine(idx, EngineConfig(k=5, batch_size=16,
                                       escalate_margin=margin))
    eng.search(q[:4], fq[:4])            # compile outside the trace
    eng._cache.clear()
    eng.stats = type(eng.stats)()
    _, spans, trace = _traced(tmp_path, lambda: eng.search(q, fq))
    assert all(sp.name.startswith("fcvi.") for sp in spans)
    assert not any(n.startswith("fcvi.") for n, *_ in trace.spans)
    par = program.parents(spans)
    top = [i for i, p in enumerate(par) if p is None]
    assert [spans[i].name for i in top] == ["fcvi.search"]
    (root,) = top
    assert spans[root].attrs == {"mode": "similarity", "queries": 40}
    for name in ("fcvi.validate", "fcvi.cache"):
        assert len(_children(spans, par, root, name)) == 1
    (cache,) = _children(spans, par, root, "fcvi.cache")
    assert spans[cache].attrs == {"hits": 0}
    batches = _children(spans, par, root, "fcvi.batch")
    assert [spans[b].attrs for b in batches] == [
        {"rows": 16, "real": 16}, {"rows": 16, "real": 16},
        {"rows": 16, "real": 8}]
    buckets = []
    for b in batches:
        assert len(_children(spans, par, b, "fcvi.step")) == 1
        assert len(_children(spans, par, b, "fcvi.fetch")) == 1
        esc = _children(spans, par, b, "fcvi.escalate")
        assert len(esc) == (1 if escalates else 0)
        for e in esc:
            a = spans[e].attrs
            assert a["escalated"] == spans[b].attrs["real"]
            assert a["kp"] > spans[_children(spans, par, b,
                                             "fcvi.step")[0]].attrs["kp"]
            buckets.append(a["bucket"])
    assert sorted(sp.name for sp in spans) == sorted(
        ["fcvi.search", "fcvi.validate", "fcvi.cache"]
        + ["fcvi.batch", "fcvi.step", "fcvi.fetch"] * 3
        + ["fcvi.escalate"] * len(buckets))
    assert buckets == ([16, 16, 8] if escalates else [])
    assert eng.stats.escalations == (40 if escalates else 0)
    assert eng.stats.escalation_rows == sum(buckets)


def test_cache_hits_and_predicate_mode_spans(corpus, tmp_path):
    """A call served from the cache opens no batch; a predicate call opens
    ``fcvi.search`` alone, naming its mode and plan."""
    from repro.core.filters import F
    from repro.serve.engine import EngineConfig, FCVIEngine

    idx, q, fq = corpus
    eng = FCVIEngine(idx, EngineConfig(k=5, batch_size=16))
    eng.search(q[:8], fq[:8])
    pred = F.range("f0", -0.5, 0.5)
    eng.search(q[:4], filter=pred)

    def calls():
        eng.search(q[:8], fq[:8])
        eng.search(q[:4], filter=pred)

    _, spans, _ = _traced(tmp_path, calls)
    names = [sp.name for sp in spans]
    assert names == ["fcvi.search", "fcvi.validate", "fcvi.cache",
                     "fcvi.search"]
    assert spans[2].attrs == {"hits": 8}
    assert spans[3].attrs["mode"] == "predicate"
    assert spans[3].attrs["queries"] == 4
    assert spans[3].attrs["plan"] in ("fold", "mask", "routed")


@pytest.mark.parametrize("n,b,want", [(1, 64, 1), (33, 64, 64), (32, 64, 32),
                                      (5, 64, 8), (0, 64, 1), (7, 48, 12)])
def test_subbatch_rows(n, b, want):
    from repro.serve.engine import _subbatch_rows

    assert _subbatch_rows(n, b) == want


# ---------------------------------------------------------------------------
# attribution on hand-made intervals
# ---------------------------------------------------------------------------

def _hand():
    """One window [0, 200), one harness ``search`` call [10, 190) holding a
    program call with two batches; the chip runs stage 1 and stage 2 of
    each batch, with the idle gap of the margin sync between them, and
    two operations outside the call."""
    spans = [("window", 0, 200, 0), ("search", 10, 190, 8)]
    ops = {0: [(2, 8, "m/other"), (30, 50, "m/step"), (60, 80, "m/esc"),
               (80, 90, "m/esc"), (110, 130, "m/step"), (150, 170, "m/esc"),
               (192, 196, "m/other")]}
    sp = program.Span
    prog = [sp("fcvi.search", 12, 188, {"queries": 8}, "t"),
            sp("fcvi.validate", 14, 18, {}, "t"),
            sp("fcvi.cache", 18, 22, {"hits": 0}, "t"),
            sp("fcvi.batch", 25, 100, {"rows": 4, "real": 4}, "t"),
            sp("fcvi.step", 26, 52, {"kp": 10}, "t"),
            sp("fcvi.escalate", 52, 62, {"escalated": 3, "bucket": 4}, "t"),
            sp("fcvi.fetch", 62, 95, {}, "t"),
            sp("fcvi.batch", 100, 185, {"rows": 4, "real": 4}, "t"),
            sp("fcvi.step", 101, 132, {"kp": 10}, "t"),
            sp("fcvi.escalate", 132, 150, {"escalated": 2, "bucket": 2},
               "t"),
            sp("fcvi.fetch", 150, 180, {}, "t")]
    return tracing.Trace(ops=ops, spans=spans), prog


def test_attribute_hand_made():
    trace, prog = _hand()
    lay = program.attribute(trace, prog, 0, 200)
    ns = 1e-9
    assert set(lay) == {"fcvi.search", "fcvi.validate", "fcvi.cache",
                        "fcvi.batch", "fcvi.step", "fcvi.escalate",
                        "fcvi.fetch"}
    assert lay["fcvi.batch"].count == 2
    assert lay["fcvi.batch"].wall_s == pytest.approx(160 * ns)
    assert lay["fcvi.batch"].device_s == pytest.approx(90 * ns)
    assert lay["fcvi.step"].device_s == pytest.approx(40 * ns)
    assert lay["fcvi.step"].idle_s == pytest.approx(17 * ns)
    assert lay["fcvi.escalate"].device_s == pytest.approx(2 * ns)
    assert lay["fcvi.escalate"].idle_s == pytest.approx(26 * ns)
    # self time: the span less its children
    assert lay["fcvi.search"].self_s == pytest.approx((176 - 8 - 160) * ns)
    assert lay["fcvi.batch"].self_s == pytest.approx(
        (160 - 26 - 10 - 33 - 31 - 18 - 30) * ns)
    assert lay["fcvi.fetch"].self_s == lay["fcvi.fetch"].wall_s
    # a window that holds no program span gives nothing
    assert program.attribute(trace, prog, 190, 200) == {}
    assert program.attribute(trace, [], 0, 200) == {}


def test_idle_gaps_carry_the_program_label():
    """Each gap is labelled at its middle by the harness span and the
    innermost program span open there; with no program span open, by the
    harness span alone."""
    trace, prog = _hand()
    top, by_label = program.idle_gaps(trace, prog, 0, 200, top=3)
    ns = 1e-9
    assert by_label == pytest.approx({
        "harness": (2 + 4) * ns,                # 0..2 and 196..200
        "search>fcvi.cache": 22 * ns,           # 8..30, middle 19
        "search>fcvi.escalate": (10 + 20) * ns,  # 50..60 and 130..150
        "search>fcvi.batch": (20 + 22) * ns,    # 90..110 and 170..192
    })
    assert top == [("search>fcvi.cache", pytest.approx(22 * ns)),
                   ("search>fcvi.batch", pytest.approx(22 * ns)),
                   ("search>fcvi.batch", pytest.approx(20 * ns))]


def test_reduce_is_the_same_with_program_spans():
    """The harness's reduction reads its own spans alone: labels and numbers
    are those it gives without any program span."""
    trace, prog = _hand()
    r = tracing.reduce(trace, top=20)
    labels = {lab for lab, _ in r.idle_gaps}
    assert labels <= {"search", "harness"}
    assert r.search_device_s == pytest.approx(
        program.attribute(trace, prog, 0, 200)["fcvi.batch"].device_s)
    _, by_label = program.idle_gaps(trace, prog, 0, 200)
    plain = {}
    for lab, s in by_label.items():
        plain[lab.split(">")[0]] = plain.get(lab.split(">")[0], 0.0) + s
    assert plain == pytest.approx(r.idle_by_label)


# ---------------------------------------------------------------------------
# a call recorded on a TPU v5e
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def v5e(tmp_path_factory):
    d = tmp_path_factory.mktemp("v5e")
    (d / "t.xplane.pb").write_bytes(lzma.decompress(
        (DATA / "ivf-bulk-spans-v5e.xplane.pb.xz").read_bytes()))
    trace, spans = tracing.load(str(d)), program.load(str(d))
    lo, hi = program.window(trace)
    return trace, spans, lo, hi


def _ctx(trace, spans, lo, hi):
    esc = [sp.attrs for sp in program.within(spans, lo, hi)
           if sp.name == "fcvi.escalate"]
    return SimpleNamespace(
        reduction=tracing.reduce(trace), mode="similarity",
        program=program.attribute(trace, spans, lo, hi),
        counters={"escalations": sum(a["escalated"] for a in esc),
                  "escalation_rows": sum(a["bucket"] for a in esc)})


def test_readers_on_a_v5e_call(v5e):
    """One 512-query call of ``sift1m-ivf.bulk`` with the engine's spans:
    each reader falls in the range the stage split of the benchmark's
    breakdown predicts, and the two stages hold the call's device time."""
    trace, spans, lo, hi = v5e
    ctx = _ctx(trace, spans, lo, hi)
    got = {name: spec.metric_reader(name + ".bulk").read(ctx)
           for name in READERS}
    device_us = spec.metric_reader("device_us.bulk").read(ctx)
    assert ctx.reduction.search_queries == 512
    # what the traced run printed for this call: the spans leave the
    # harness's own reduction as it was, and name the engine's escalations
    assert device_us == pytest.approx(11362.788134765626, rel=1e-12)
    assert spec.metric_reader("host_us.bulk").read(ctx) == \
        pytest.approx(233.61138671875, rel=1e-12)
    assert ctx.counters == {"escalations": 403, "escalation_rows": 512}
    assert [sp.name for sp in spans].count("fcvi.batch") == 8
    assert len(spans) == 3 + 4 * 8
    assert 1900 <= got["step_device_us"] <= 2200
    assert 9100 <= got["escalation_device_us"] <= 9500
    assert 0 <= got["escalation_idle_us"] <= \
        spec.metric_reader("host_us.bulk").read(ctx)
    assert 20 <= got["escalation_pad_share"] <= 23
    assert got["step_device_us"] + got["escalation_device_us"] >= \
        0.99 * device_us


def test_idle_inside_calls_is_labelled_by_the_program(v5e):
    trace, spans, lo, hi = v5e
    _, by_label = program.idle_gaps(trace, spans, lo, hi)
    inside = {lab: s for lab, s in by_label.items()
              if lab.split(">")[0] == "search"}
    labelled = sum(s for lab, s in inside.items() if ">fcvi." in lab)
    assert labelled >= 0.95 * sum(inside.values())


def test_readers_on_hand_made_spans():
    """Per query of the harness's calls: stage 1 inside ``fcvi.step``,
    stage 2 inside ``fcvi.batch`` but outside ``fcvi.step``, the idle time
    inside ``fcvi.escalate``; padding from the counters."""
    trace, prog = _hand()
    ctx = SimpleNamespace(reduction=tracing.reduce(trace), mode="similarity",
                          program=program.attribute(trace, prog, 0, 200),
                          counters={"escalations": 5, "escalation_rows": 6})
    got = {name: spec.metric_reader(name + ".bulk").read(ctx)
           for name in READERS}
    us = 1e-9 * 1e6 / 8        # ns of the hand trace, per query, in us
    assert got == pytest.approx({
        "step_device_us": 40 * us, "escalation_device_us": 50 * us,
        "escalation_idle_us": 26 * us,
        "escalation_pad_share": 100 * (1 - 5 / 6)})
    assert got["step_device_us"] + got["escalation_device_us"] == \
        pytest.approx(spec.metric_reader("device_us.bulk").read(ctx))


def test_readers_give_nothing_without_program_spans():
    """An engine that opens no ``fcvi.`` span, or keeps no
    ``escalation_rows`` counter, leaves the metrics out."""
    trace, _ = _hand()
    ctx = SimpleNamespace(reduction=tracing.reduce(trace), mode="similarity",
                          program=program.attribute(trace, [], 0, 200),
                          counters={"escalations": 3, "queries": 8})
    for name in READERS:
        assert spec.metric_reader(name + ".bulk").read(ctx) is None
    del ctx.program
    for name in READERS:
        assert spec.metric_reader(name + ".bulk").read(ctx) is None

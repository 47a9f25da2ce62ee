"""The ``cohere768-flat-pallas.bulk`` cell: its files as ``BENCHMARK.json``
names them, and its program path on the CPU at a small size.

The cell's configuration is served by ``harness.system.Server`` (the Pallas
flat scan, in interpret mode here) at d = 768 over 15,625 rows, one call of
two batches, with no warm-up: the answers pass the cell's own limits
against the plain fp64 reference, the bf16 control fails them, and the
engine's spans and counters show stage 1 and the escalation re-scan on the
flat path."""
import json
from pathlib import Path

import numpy as np
import pytest

from harness import data, program, spec, system
from reference import check
from reference.control import Control

BENCH = Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELL = "cohere768-flat-pallas.bulk"
# the per-layer metrics of the IVF cell's traffic, which this cell shares
BULK_METRICS = {"idle_share.bulk", "host_us.bulk", "device_us.bulk",
                "scan_roofline.bulk", "escalated_share.bulk"}
# 15,625 rows (the cell's 1,000,000 / 64): like the cell's n, no multiple of
# the scan's 128-row tile, so the scan pads the slab as it does on the chip;
# from about this size on the flat engine's candidate search misses under 5%
# of the exact combined-score top 10 (32 clusters of about 490 rows); two
# batches of 64 queries
ROWS, QUERIES, SEED = 15625, 128, 3150000001


def test_cell_reads_its_files():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "bulk"
    assert {m["name"] for m in cell.end_to_end} == {"qps", "hbm_peak_gb",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == BULK_METRICS
    for m in cell.per_layer:
        assert m["moves"] == "qps"
        assert callable(spec.metric_reader(m["name"]).read)
    assert set(cell.limits) == {"bad_answers", "recall_miss", "score_err"}
    cfg = cell.config
    assert cfg["corpus_seed"] == 1 and cfg["fcvi"]["use_pallas"] is True
    assert cfg["fcvi"]["backend"] == "flat"
    (entry,) = [c for c in SPEC["configs"] if c["name"] == cfg["name"]]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert entry["file"] == f"bench/configs/{cfg['name']}.json"


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One call of ``QUERIES`` queries through the cell's ``Server`` under
    the profiler, with its spans, counters and the corpus."""
    import jax

    cell = spec.load_cell(CELL)
    cfg = dict(cell.config, n=ROWS)
    vectors, filters = data.corpus(cfg, cfg["corpus_seed"])
    filters_host = np.asarray(filters)
    q, fq = data.queries(cfg, vectors, filters, SEED, 1, QUERIES,
                         cell.traffic["query_noise"])
    server = system.Server(cfg, cell.traffic, vectors, filters_host)
    assert server.engine.index.config.use_pallas
    trace_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(trace_dir)):
        scores, ids = server.serve(q, fq)
    return {"cell": cell, "cfg": cfg, "vectors": vectors,
            "filters": filters_host, "q": q, "fq": fq, "scores": scores,
            "ids": ids, "server": server,
            "spans": program.load(str(trace_dir))}


def _numbers(s, scores, ids):
    return check.similarity(np.asarray(s["vectors"]), s["filters"],
                            s["cfg"]["fcvi"]["lam"], s["q"], s["fq"], ids,
                            scores)


def test_engine_passes_the_cell_limits(served):
    ok, rows = check.verdict(_numbers(served, served["scores"],
                                      served["ids"]), served["cell"].limits)
    assert ok, rows


def test_bf16_control_fails_score_err(served):
    ctl = Control(served["cfg"], served["cell"].traffic, served["vectors"],
                  served["filters"])
    scores, ids = ctl.serve(served["q"], served["fq"])
    nums = _numbers(served, scores, ids)
    ok, _ = check.verdict(nums, served["cell"].limits)
    assert not ok
    assert nums["score_err"] > served["cell"].limits["score_err"]["limit"]


def test_stage_one_and_escalation_spans_on_the_flat_path(served):
    """Each ``fcvi.batch`` holds one ``fcvi.step`` at k' 133, and an
    ``fcvi.escalate`` at k' 533 wherever the batch escalated; their
    ``escalated`` attributes add up to ``EngineStats.escalations``."""
    spans = served["spans"]
    par = program.parents(spans)
    batches = [i for i, sp in enumerate(spans) if sp.name == "fcvi.batch"]
    assert len(batches) == QUERIES // served["cfg"]["batch_size"]
    escalated = 0
    for b in batches:
        kids = [spans[j] for j, p in enumerate(par) if p == b]
        steps = [sp for sp in kids if sp.name == "fcvi.step"]
        esc = [sp for sp in kids if sp.name == "fcvi.escalate"]
        assert [sp.attrs["kp"] for sp in steps] == [133]
        assert len(esc) <= 1
        for sp in esc:
            assert sp.attrs["kp"] == 533
            escalated += sp.attrs["escalated"]
    stats = served["server"].engine.stats
    assert escalated > 0
    assert stats.escalations == escalated
    counters = served["server"].counters()
    assert counters["queries"] == QUERIES
    assert counters["escalations"] == escalated

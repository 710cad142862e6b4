"""Whole runs on the CPU at a tiny size: past the look for a chip, the
run builds the fleet, plays its window, and decides ``correct`` against
the reference, which has to come out false when the served path is
broken underneath."""

import pytest

from bench import run as R
from bench.tests.conftest import SEED, tiny


def test_sparse_run_is_correct_and_reports_its_metrics(src_path):
    res = R.run(tiny("bm25-w1-steady"), SEED, 1.5, False)
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] == 12
    assert set(res["metrics"]) == {"p50_ms", "qps", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


def test_traced_hybrid_run_is_correct(src_path):
    res = R.run(tiny("hybrid768-steady", n_docs=1200, rate=6.0), SEED, 1.5,
                True)
    assert res["correct"]
    assert {"fused_mismatch", "dense_rel_err"} <= set(res["checks"])
    # no TPU trace on the CPU: only the counters are read
    assert {"batch_mean", "cold_legs", "window_compiles",
            "tail_p95_ms"} <= set(res["metrics"])
    assert "bm25_roofline" not in res["metrics"]


def _alter_score(app):
    scatter = app.scatter.scatter

    def broken(payload, **kw):
        results, lat, recs = scatter(payload, **kw)
        for r in results[0]["results"]:
            r["scores"] = [s * 1.001 for s in r["scores"]]
        return results, lat, recs
    app.scatter.scatter = broken


def _drop_partition(app):
    scatter = app.scatter.scatter

    def broken(payload, **kw):
        results, lat, recs = scatter(payload, **kw)
        for r in results[1]["results"]:
            r["ids"], r["scores"], r["ext_ids"] = [], [], []
        return results, lat, recs
    app.scatter.scatter = broken


def _alter_doc(app):
    fetch = app.doc_store.batch_get_billed

    def broken(keys):
        raw, s = fetch(keys)
        for v in raw.values():
            v["contents"] = v["contents"][:-1]
        return raw, s
    app.doc_store.batch_get_billed = broken


def _alter_dense(app):
    scatter = app.scatter.scatter

    def broken(payload, **kw):
        results, lat, recs = scatter(payload, **kw)
        for r in results[2]["results"]:
            r["dense"]["scores"] = [s + 1e-3 for s in r["dense"]["scores"]]
        return results, lat, recs
    app.scatter.scatter = broken


@pytest.mark.parametrize("cell,fault", [
    ("bm25-w1-steady", _alter_score),
    ("bm25-w1-steady", _drop_partition),
    ("bm25-w1-steady", _alter_doc),
    ("hybrid768-steady", _alter_dense),
])
def test_a_broken_answer_is_not_correct(cell, fault, src_path):
    res = R.run(tiny(cell, n_docs=1200, rate=6.0), SEED + 1, 1.5, False,
                tamper=fault)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())

"""The commit cell's readers and its tiny twin on the CPU: ``commit_ms`` and
``rollover_h2d_mb`` read the program's ``fleet.commit`` and
``fleet.hydrate`` spans from a profiler trace of a fleet that commits, and
leave their metric out over a trace without them; the tiny ingest cell
compiles nothing in its window and finds no partition cold."""

import json

import jax
import pytest

from bench import run as R
from bench import trace as tr
from bench.tests.conftest import SEED
from bench.tests.test_bench_ingest import ingest_cell


def _fleet(logdir, commits: int) -> dict:
    """``rec["trace"]`` of a tiny fleet serving, with ``commits`` commits,
    as the benchmark's reduction keeps the program's spans."""
    from repro.core.runtime import RuntimeConfig
    from repro.data.corpus import synth_corpus, synth_queries
    from repro.search.searcher import SearchConfig
    from repro.search.service import build_partitioned_search_app

    docs = synth_corpus(140, vocab=300, seed=71)
    app = build_partitioned_search_app(
        docs[:100], 2, runtime_config=RuntimeConfig(),
        search_config=SearchConfig(sim_exec_s=0.002))
    queries = synth_queries(docs, 4, seed=72)
    for q in queries:
        app.query(q, t_arrival=app.runtime.clock + 0.05)
    jax.profiler.start_trace(str(logdir))
    try:
        for c in range(commits):
            app.add_documents(docs[100 + 10 * c: 110 + 10 * c])
            assert app.commit(t_arrival=app.runtime.clock + 0.05).ok
        for q in queries:
            app.query(q, t_arrival=app.runtime.clock + 0.05)
    finally:
        jax.profiler.stop_trace()
    files = sorted(logdir.glob("**/*.xplane.pb"))
    t = tr.load(str(files[-1]))
    return {"fleet": tr.fleet_blocks(t.fleet, lambda s, e: float(e - s))}


def _read(name: str, trace: dict):
    return R.load_reader(name)({"trace": trace})


def test_readers_read_the_commit_spans(src_path, tmp_path):
    trace = _fleet(tmp_path, commits=2)
    fleet = trace["fleet"]
    assert fleet["fleet.commit"]["count"] == 2
    counters = fleet["fleet.commit"]["counters"]
    assert counters["added"] == 20 and counters["published_bytes"] > 0
    for phase in ("apply", "write", "publish", "kv", "prewarm", "gc"):
        assert fleet[f"fleet.commit.{phase}"]["count"] == 2
    commit_ms = _read("commit_ms", trace)
    assert commit_ms == pytest.approx(
        1e3 * fleet["fleet.commit"]["span_s"] / 2)
    h2d = (counters.get("h2d_bytes", 0)
           + fleet["fleet.hydrate"]["counters"]["h2d_bytes"])
    assert h2d > 0
    assert _read("rollover_h2d_mb", trace) == pytest.approx(h2d / 2 / 1e6)


def test_readers_leave_out_a_trace_without_commits(src_path, tmp_path):
    trace = _fleet(tmp_path, commits=0)
    assert "fleet.commit" not in trace["fleet"]
    for name in ("commit_ms", "rollover_h2d_mb"):
        assert _read(name, trace) is None
        assert _read(name, {}) is None
        assert R.load_reader(name)({"trace": None}) is None


def test_tiny_commit_cell_compiles_nothing_and_finds_nothing_cold(src_path):
    res = R.run(ingest_cell(), SEED + 5, 3.0, True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["window_compiles"]["value"] == 0
    assert res["metrics"]["cold_legs"]["value"] == 0
    json.dumps(res)

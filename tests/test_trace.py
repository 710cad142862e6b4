"""Spans of the served path (``repro.core.trace``), read back from a JAX
profiler trace of a tiny partitioned fleet serving a few admission windows.

The contract under test: every ``fleet.`` span appears, nested as the
served path nests (dispatch ⊃ scatter ⊃ leg ⊃ encode, bm25 | dense;
dispatch ⊃ merge, kv, materialize); each device call counts the queries
the gateway batched, the power of two the BM25 call padded them to (the
dense tier makes one call a query, so pads none), the postings a BM25 call
gathered and whether it ran the full rung, and the bytes of the host
arrays it handed over (an array already on the device counts none); a
dense hydration counts its matrix placed on the device once; a window-0
dispatch waited for nothing; and each request keeps the number of the
window it rode.
"""

from collections import Counter
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import trace
from repro.core.partition import FleetSpec, IndexSpec, VectorSpec
from repro.core.runtime import RuntimeConfig
from repro.kernels.dot_topk import padded_rows
from repro.data.corpus import synth_corpus, synth_queries
from repro.search.bm25 import rung_ladder
from repro.search.searcher import DenseSearcher, SearchConfig
from repro.search.service import build_partitioned_search_app

N_PARTS = 2
DIM = 16
CFG = SearchConfig(sim_exec_s=0.002, sim_write_s=0.02)
(T_SMALL, M_SMALL), _ = rung_ladder(CFG.max_terms, CFG.max_blocks)
# windows of 1 (sparse traffic: window 0), 5, 1, 3 requests
GROUPS = (6, 4)


def _spans(logdir: Path) -> list[tuple[int, int, str, dict]]:
    files = sorted(logdir.glob("**/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.extend((int(e.start_ns), int(e.end_ns), e.name, dict(e.stats))
                       for e in line.events
                       if e.name.startswith(trace.PREFIX))
    return sorted(out, key=lambda s: (s[0], -s[1]))


def _inside(inner, outer) -> bool:
    return outer[0] <= inner[0] and inner[1] <= outer[1]


@pytest.fixture(scope="module", params=["sparse", "hybrid"])
def served(request, tmp_path_factory):
    mode = request.param
    docs = synth_corpus(120, vocab=300, seed=61)
    index = (IndexSpec(vector=VectorSpec(dim=DIM)) if mode == "hybrid"
             else IndexSpec())
    app = build_partitioned_search_app(docs, FleetSpec(
        n_parts=N_PARTS, index=index, runtime_config=RuntimeConfig(),
        search_config=CFG))
    queries = synth_queries(docs, sum(GROUPS), seed=62)
    logdir = tmp_path_factory.mktemp(f"trace-{mode}")
    jax.profiler.start_trace(str(logdir))
    try:
        app.warm()
        handles, qi = [], 0
        t = app.runtime.clock + 1.0
        for n in GROUPS:
            # the first arrival finds the trailing rate under sparse_qps
            # and dispatches alone; the rest, 5 ms apart, share a window
            for i in range(n):
                handles.append(app.submit(queries[qi], k=5, mode=mode,
                                          t_arrival=t + 0.005 * i))
                qi += 1
            app.flush()
            t = app.runtime.clock + 10.0
    finally:
        jax.profiler.stop_trace()
    assert all(h.done() and h.response.ok for h in handles)
    return mode, app, handles, _spans(logdir)


def _by_name(spans, name):
    return [s for s in spans if s[2] == trace.PREFIX + name]


def test_every_span_appears_nested_as_the_path_nests(served):
    mode, _, _, spans = served
    call = "dense" if mode == "hybrid" else "bm25"
    names = {s[2] for s in spans}
    want = {"dispatch", "scatter", "leg", "hydrate", "encode", "bm25",
            "backfill", "merge", "kv", "materialize"}
    if mode == "hybrid":
        want.add("dense")
    assert {trace.PREFIX + n for n in want} <= names
    dispatches = _by_name(spans, "dispatch")
    assert len(dispatches) == 4
    for d in dispatches:
        scatters = [s for s in _by_name(spans, "scatter") if _inside(s, d)]
        assert len(scatters) == 1
        legs = [s for s in _by_name(spans, "leg") if _inside(s, d)]
        assert len(legs) == N_PARTS
        assert all(_inside(leg, scatters[0]) for leg in legs)
        assert [leg[3]["partition"] for leg in legs] == list(range(N_PARTS))
        assert all(leg[3]["cold"] == 0 for leg in legs)
        for child in ("encode", "bm25", call):
            inner = [s for s in _by_name(spans, child) if _inside(s, d)]
            assert len(inner) == N_PARTS
            assert all(any(_inside(s, leg) for leg in legs) for s in inner)
        for child in ("merge", "kv", "materialize"):
            inner = [s for s in _by_name(spans, child) if _inside(s, d)]
            assert len(inner) == 1
            assert not _inside(inner[0], scatters[0])


def test_calls_count_batch_padding_and_host_bytes(served):
    mode, app, _, spans = served
    dispatches = _by_name(spans, "dispatch")
    assert [d[3]["requests"] for d in dispatches] == [1, 5, 1, 3]
    for d in dispatches:
        q = d[3]["requests"]
        padded = 1 << max(0, (q - 1).bit_length())
        bm25 = [s for s in _by_name(spans, "bm25") if _inside(s, d)]
        for s in bm25:
            assert (s[3]["queries"], s[3]["padded"]) == (q, padded)
            # queries of at most 3 terms, each one block of 128 postings:
            # the small rung, its T int32 term ids and f32 term weights a
            # query, T·M blocks of B postings gathered
            assert s[3]["h2d_bytes"] == padded * T_SMALL * (4 + 4)
            assert s[3]["gathered"] == padded * T_SMALL * M_SMALL * 128
            assert s[3]["full"] == 0
        if mode == "hybrid":
            dense = [s for s in _by_name(spans, "dense") if _inside(s, d)]
            assert len(dense) == N_PARTS
            for s in dense:
                # one call a query, each handed its f32 query row alone:
                # the partition's matrix already sits on the device
                assert (s[3]["queries"], s[3]["padded"]) == (q, q)
                assert s[3]["h2d_bytes"] == q * DIM * 4


def test_admission_wait_and_the_request_key(served):
    _, _, handles, spans = served
    dispatches = _by_name(spans, "dispatch")
    for d in dispatches:
        a = d[3]
        if a["window_ms"] == 0:
            assert a["requests"] == 1
            assert a["wait_ms_sum"] == 0 and a["wait_ms_max"] == 0
        else:
            assert a["window_ms"] > 0
            assert 0 < a["wait_ms_max"] <= a["wait_ms_sum"]
    # each handle keeps the sequence number of the window it rode
    seqs = [d[3]["dispatch"] for d in dispatches]
    sizes = [d[3]["requests"] for d in dispatches]
    assert [h.dispatch for h in handles] == [
        s for s, n in zip(seqs, sizes) for _ in range(n)]


def test_hydration_counts_state_placed_on_the_device(served):
    mode, app, _, spans = served
    hydrate = _by_name(spans, "hydrate")
    placed = [s[3]["h2d_bytes"] for s in hydrate]
    # each partition's lazy searcher is built once its first terms land,
    # putting its index state on the device
    assert sum(b > 0 for b in placed) >= N_PARTS
    assert all(b >= 0 for b in placed)
    if mode == "hybrid":
        # each partition's dense matrix, its live rows padded with zero
        # rows to the kernel's 1,024-row chunk, is placed once (at warm-up)
        rows = np.diff(app.indexer.part_doc_offsets() + [120])
        matrices = Counter(padded_rows(int(n), CFG.k) * DIM * 4
                           for n in rows)
        assert {b: Counter(placed)[b] for b in matrices} == matrices


@pytest.mark.parametrize("rows_as", ["numpy", "jax"])
def test_dense_counts_only_the_host_arrays_it_hands_over(rows_as, tmp_path):
    rng = np.random.default_rng(63)
    n, q = 40, 3
    vectors = rng.standard_normal((n, DIM)).astype(np.float32)
    if rows_as == "jax":
        vectors = jax.device_put(vectors)
    ds = DenseSearcher(vectors, [str(i) for i in range(n)], np.ones(n, bool),
                       CFG)
    jax.profiler.start_trace(str(tmp_path))
    try:
        hits = ds.search_batch(list(rng.standard_normal((q, DIM))), k=5)
    finally:
        jax.profiler.stop_trace()
    assert [len(h) for h in hits] == [5] * q
    (dense,) = _by_name(_spans(tmp_path), "dense")
    # however the rows were handed to the searcher, it placed them on the
    # device once: a search hands over its f32 query rows alone
    assert (dense[3]["queries"], dense[3]["padded"]) == (q, q)
    assert dense[3]["h2d_bytes"] == q * DIM * 4

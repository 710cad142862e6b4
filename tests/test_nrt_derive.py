"""Generations derived from the one before (Lucene's near-real-time reader):
after every commit each pool's prewarm derives the new generation from the
one it holds — the fixed layout stays on the device, the documents added
since form the tail, deletions become infinite lengths, the shared state
arrives as a delta — and the tests hold it to the answers it owes:

* EQUIVALENCE — after every commit of a sequence (adds, deletes, new
  vocabulary, a tail outgrowing its capacity in documents and in slots, a
  delta merge, a base merge) each partition's derived searcher ranks and
  scores exactly like a from-scratch hydration of the same generation, and
  the fleet's answers equal a float64 BM25 reference over the live
  documents: ids up to near-ties, scores within 1e-6 relative. The
  reference sums in float64 and the fleet in float32 (one rounding an
  impact and one an addition, over at most 4 query terms), so 1e-6 holds
  with room while a float16-sized error (1e-3) would not.
* PINNED — a query admitted under generation g is answered by g after g+1
  is published, and fetches g's passages even when g+1 deleted them.
* O(DELTA) — a commit publishes its state as a delta, no query leg after
  it is cold, and the tail's programs ran before the first commit.
"""

import math
from collections import Counter

import numpy as np
import pytest

from repro.core.refresh import (STATE_DELTA_FILE, generation_version,
                                parse_generation)
from repro.core.runtime import RuntimeConfig
from repro.data.corpus import synth_corpus, synth_queries
from repro.index.builder import K1_DEFAULT, B_DEFAULT, MergePolicy
from repro.index.tokenizer import tokenize
from repro.search import searcher as S
from repro.search.searcher import (SearchConfig, TailSearcher,
                                   hydrate_searcher)
from repro.search.service import build_partitioned_search_app

CFG = SearchConfig(sim_exec_s=0.002, sim_write_s=0.02)
REL = 1e-6


def build_app(docs, n_parts=2, merge_policy=None):
    return build_partitioned_search_app(
        docs, n_parts=n_parts, runtime_config=RuntimeConfig(),
        search_config=CFG, merge_policy=merge_policy)


def reference(live: list, q: str, k: int, depth: int = 30):
    """Float64 BM25 top-``depth`` over the live (ext id, text) documents,
    each query term weighted by its count in the query, ties by position:
    [(ext id, score)]."""
    toks = [tokenize(t) for _, t in live]
    n = len(live)
    avgdl = sum(map(len, toks)) / max(1, n)
    df = Counter(t for d in toks for t in set(d))
    terms = Counter(tokenize(q))
    scores = []
    for i, d in enumerate(toks):
        tf, s = Counter(d), 0.0
        for t, qtf in terms.items():
            if tf[t]:
                idf = math.log(1.0 + (n - df[t] + 0.5) / (df[t] + 0.5))
                s += qtf * idf * tf[t] / (tf[t] + K1_DEFAULT * (
                    1 - B_DEFAULT + B_DEFAULT * len(d) / avgdl))
        if s > 0:
            scores.append((-s, i))
    scores.sort()
    return [(live[i][0], -s) for s, i in scores[:depth]]


def assert_matches_reference(app, queries, k=10):
    live = app.indexer.live_corpus()
    for q in queries:
        r = app.query(q, k=k, t_arrival=app.runtime.clock + 0.05,
                      fetch_docs=False)
        assert r.ok, r.body
        want = reference(live, q, k)
        ref = dict(want)
        got = list(zip(r.body["ext_ids"], r.body["scores"]))
        assert len(got) == min(k, len(want)), q
        for rank, (ext, score) in enumerate(got):
            w_ext, w_score = want[rank]
            assert abs(score - w_score) <= REL * w_score, (q, rank)
            if ext != w_ext:             # a near-tie may swap places
                assert ext in ref, (q, rank)
                assert abs(ref[ext] - w_score) <= REL * w_score, (q, rank)


def assert_derived_equals_whole(app, queries):
    """Every partition's serving entry for the current generation is
    derived, and scores bit for bit like a whole hydration of it."""
    gen = app.indexer.gen
    version = generation_version(gen)
    for asset, group in zip(app.assets, app.fn_groups):
        entries = [e for inst in app.runtime._instances if inst.fn in group
                   for v, e in inst.cache.entries(asset) if v == version]
        assert entries and all(isinstance(e, TailSearcher) for e in entries)
        whole, _ = hydrate_searcher(app.catalog, asset, CFG, version)
        for e in entries:
            got = e.search_batch(queries)
            want = whole.search_batch(queries)
            assert [[(e.doc_ids[i], s) for i, s in hits] for hits in got] \
                == [[(whole.doc_ids[i], s) for i, s in hits]
                    for hits in want]


@pytest.fixture
def small_tail(monkeypatch):
    """A tail that starts at 8 documents and 4 slots a document, so a few
    commits outgrow both."""
    monkeypatch.setattr(S, "TAIL_MIN_DOCS", 8)
    monkeypatch.setattr(S, "TAIL_MIN_SLOTS", 4)


def test_derived_generations_equal_whole_hydration_and_reference(small_tail):
    docs = synth_corpus(420, vocab=500, seed=31)
    init, pool = docs[:200], list(docs[200:])
    app = build_app(init, n_parts=2, merge_policy=MergePolicy(
        max_deltas=2, ratio=0.6, tombstone_ratio=0.12))
    queries = synth_queries(docs, 12, seed=32)
    assert_matches_reference(app, queries)
    ix = app.indexer
    caps, kinds = set(), set()
    for step in range(7):
        batch, pool[:30] = pool[:30], []
        # a document of terms no earlier one has: the vocabulary grows
        batch.append((f"fresh{step}", f"zzq{step}a zzq{step}b zzq{step}a"))
        if step == 4:     # one with more distinct terms than a row holds
            batch.append(("long", " ".join(f"zzw{j}" for j in range(90))))
        assert app.add_documents(batch).ok
        live = ix.live_corpus()
        assert app.delete_documents(
            [live[(step * 17) % len(live)][0],
             live[(step * 31 + 5) % len(live)][0]]).ok
        r = app.commit()
        assert r.ok, r.body
        kinds.update("base" if i in r.body["base_merged"] else "deltas"
                     for i in r.body["merged"])
        if not r.body["base_merged"]:
            assert_derived_equals_whole(app, queries + [f"zzq{step}a"])
        assert_matches_reference(app, queries + [f"zzq{step}a"])
        caps.update(e.state.tail.terms.shape for inst in app.runtime._instances
                    for _, e in inst.cache.entries(app.assets[0])
                    if isinstance(e, TailSearcher))
    assert kinds == {"base", "deltas"}, kinds
    assert len({c[0] for c in caps}) > 1 and len({c[1] for c in caps}) > 1


def test_query_pinned_to_a_generation_is_answered_by_it_after_the_next():
    """A window held open across a commit that deletes the top hit: the
    answer names the generation it was admitted under, ranks that
    generation's documents, and fetches their passages — not None."""
    docs = synth_corpus(160, vocab=300, seed=41)
    app = build_app(docs[:140])
    q = synth_queries(docs, 1, seed=42)[0]
    before = app.indexer.live_corpus()
    t0 = app.runtime.clock + 1.0
    # two arrivals inside one window keep it open until flushed
    h1 = app.submit(q, k=10, t_arrival=t0)
    h2 = app.submit(q, k=10, t_arrival=t0 + 1e-4)
    top = reference(before, q, 10)[0][0]
    assert app.add_documents(docs[140:]).ok
    assert app.delete_documents([top]).ok
    assert app.commit(t_arrival=t0 + 2e-4).ok
    app.flush()
    gen = app.indexer.gen
    for h in (h1, h2):
        body = h.response.body
        assert body["generation"] == gen - 1
        assert body["ext_ids"][0] == top
        passages = dict(before)
        assert [d["contents"] for d in body["docs"]] \
            == [passages[e] for e in body["ext_ids"]]
    after = app.query(q, k=10, t_arrival=app.runtime.clock + 0.05)
    assert after.body["generation"] == gen and top not in after.body["ext_ids"]


def test_commit_publishes_a_delta_state_and_leaves_no_leg_cold():
    docs = synth_corpus(200, vocab=400, seed=51)
    app = build_app(docs[:160])
    queries = synth_queries(docs, 6, seed=52)
    for q in queries:
        app.query(q, k=10, t_arrival=app.runtime.clock + 0.05)
    for step in range(3):
        app.add_documents(docs[160 + 10 * step: 170 + 10 * step])
        assert app.commit(t_arrival=app.runtime.clock + 0.05).ok
        ref = app.indexer._stats_ref
        seg = app.catalog.open_segment(*ref)
        assert STATE_DELTA_FILE in seg.list()
        assert "vocab.json" not in seg.list()
        for q in queries:
            r = app.query(q, k=10, t_arrival=app.runtime.clock + 0.05)
            assert not any(p["cold"] for p in r.body["partitions"])
    # the serving caches keep the generations gc keeps, no older
    for inst in app.runtime._instances:
        gens = {parse_generation(v) for v, _ in
                inst.cache.entries(inst.fn.replace("search", "index"))}
        assert gens <= {app.indexer.gen, app.indexer.gen - 1}


def test_tail_programs_run_before_the_first_commit():
    """The fleet runs the derived state's program in every batch shape it
    serves at set-up, so the first generation a commit derives compiles
    nothing."""
    docs = synth_corpus(150, vocab=300, seed=61)
    app = build_app(docs[:130])
    for q in synth_queries(docs, 3, seed=62):
        app.query(q, k=10, t_arrival=app.runtime.clock + 0.05)
    app.add_documents(docs[130:])
    assert app.commit(t_arrival=app.runtime.clock + 0.05).ok
    derived = [e for inst in app.runtime._instances
               for _, e in inst.cache.entries(inst.fn.replace("search",
                                                              "index"))
               if isinstance(e, TailSearcher)]
    assert derived and all(e.warm_programs() == 0 for e in derived)


def test_merge_policy_plans_tiered_merges():
    pol = MergePolicy(max_deltas=2, ratio=0.5, tombstone_ratio=0.2)
    assert pol.plan(100, 0, 0, 0) is None
    assert pol.plan(100, 30, 3, 0) == "deltas"     # tier long, docs few
    assert pol.plan(100, 60, 1, 0) == "base"       # tier outgrew ratio
    assert pol.plan(100, 0, 0, 30) == "base"       # tombstone debt
    assert MergePolicy(max_deltas=0).plan(100, 5, 1, 0) == "base"


def test_lean_segment_reads_back_as_the_packed_index():
    """A generation's segment (``write_segment(lean=True)``) keeps no
    vocabulary, no eager twins and a sparse term index, and reads back —
    whole or as its lazy layout — as the arrays it was packed from (idf at
    the terms with blocks; a generation scores with its own idf)."""
    from repro.index.builder import (IndexWriter, compute_global_stats,
                                     global_vocab, read_lazy_layout,
                                     read_segment, write_segment)
    docs = synth_corpus(120, vocab=400, seed=81)
    stats = compute_global_stats(docs)
    w = IndexWriter(global_stats=stats, vocab=global_vocab(stats))
    w.add_many(docs[:30])                # a delta: most terms have no blocks
    packed = w.pack()
    d = write_segment(packed, lean=True)
    assert not any(name.endswith(".npy") for name in d.list())
    full = write_segment(packed)
    assert d.open_input("superindex.bin").length() < \
        full.open_input("superindex.bin").length()
    has = packed.term_offsets[1:] > packed.term_offsets[:-1]
    for back in (read_segment(d), read_lazy_layout(d)):
        assert back.vocab == {} and back.meta.doc_ids == packed.meta.doc_ids
        for name in ("term_offsets", "block_docs", "block_tf", "block_max",
                     "doc_len"):
            assert np.array_equal(getattr(back, name), getattr(packed, name))
        assert np.array_equal(back.idf[has], packed.idf[has])
        assert not back.idf[~has].any()

"""Parity suite: every BM25 evaluation path == the exact oracle.

Four paths over ONE corpus and query set, all fed by the single scoring
core in ``search/bm25.py`` and the single packer in ``index/builder.py``:

    dense   Searcher, dense scatter-add accumulator
    sorted  Searcher, sort/segment-sum accumulator
    pruned  Searcher, block-max WAND pruning (pure-JAX ref + fused Pallas
            kernel) — additionally BIT-identical to dense on every path
    mesh    shard_map'd distributed path (1 partition on this host's mesh;
            multi-device geometry is covered in test_distributed)
    fleet   build_partitioned_search_app: N Lambda functions + ScatterGather
            through the Gateway

M·B (max_blocks × block) covers every posting of every query term, so each
path must reproduce the oracle's scores to float tolerance — plus the
distributed-IR invariant that the merged ranking is independent of the
partition count (global idf/avgdl), and scatter-gather's latency model
(max over partitions, not sum).
"""

import jax
import pytest

from repro.data.corpus import synth_corpus, synth_queries
from repro.search.oracle import OracleSearcher
from repro.search.searcher import SearchConfig, Searcher
from repro.search.service import build_partitioned_search_app

K = 10


@pytest.fixture(scope="module")
def corpus():
    # 300 docs / vocab 500: every term's postings fit 64 blocks × 128 lanes
    return synth_corpus(300, vocab=500, seed=21)


@pytest.fixture(scope="module")
def queries(corpus):
    return synth_queries(corpus, 12, seed=23)


@pytest.fixture(scope="module")
def oracle(corpus):
    return OracleSearcher(corpus)


def assert_matches_oracle(got, want, ctx=""):
    """Scores rank-by-rank to float tolerance; ids equal unless score-tied."""
    assert len(got) >= min(len(want), K), (ctx, len(got), len(want))
    for r, ((wd, ws), (gd, gs)) in enumerate(zip(want, got)):
        assert gs == pytest.approx(ws, rel=2e-4), (ctx, r, want[:5], got[:5])
        tied = any(abs(ws - w2) < 1e-5 for d2, w2 in want if d2 != wd)
        assert wd == gd or tied, (ctx, r, want[:8], got[:8])


@pytest.fixture(scope="module")
def packed(corpus):
    from repro.index.builder import IndexWriter
    w = IndexWriter()
    w.add_many(corpus)
    return w.pack()


@pytest.mark.parametrize("accumulator", ["dense", "sorted", "pruned"])
def test_single_node_paths_match_oracle(packed, oracle, queries, accumulator):
    s = Searcher(packed, SearchConfig(max_blocks=64, k=K,
                                      accumulator=accumulator))
    for q in queries:
        assert_matches_oracle(s.search_one(q), oracle.search(q, k=K),
                              ctx=(accumulator, q))


def _bitwise_equal_searches(sa, sb, queries):
    import numpy as np
    for q in queries:
        va, ia = sa.search([q])
        vb, ib = sb.search([q])
        assert np.array_equal(va.view(np.uint32), vb.view(np.uint32)), \
            (q, va, vb)
        assert np.array_equal(ia, ib), (q, ia, ib)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_pruned_bit_identical_to_dense(packed, queries, use_kernel):
    """The pruning invariant, single node: ``accumulator="pruned"`` (pure
    reference AND fused Pallas kernel) returns the exact bits the dense
    scatter-add path returns — pruning may only skip blocks that provably
    cannot enter the top-k, with lax.top_k tie order."""
    dense = Searcher(packed, SearchConfig(max_blocks=64, k=K))
    pruned = Searcher(packed, SearchConfig(max_blocks=64, k=K,
                                           accumulator="pruned",
                                           use_kernel=use_kernel))
    _bitwise_equal_searches(dense, pruned, queries)


def test_pruned_bit_identical_under_truncated_blocks(packed, queries):
    """M smaller than some terms' block counts (the production shape):
    pruning must still be exact w.r.t. dense at the SAME truncation."""
    dense = Searcher(packed, SearchConfig(max_blocks=2, k=K))
    pruned = Searcher(packed, SearchConfig(max_blocks=2, k=K,
                                           accumulator="pruned"))
    _bitwise_equal_searches(dense, pruned, queries)


def test_mesh_path_matches_oracle(corpus, oracle, queries):
    from repro.parallel import compat
    from repro.search.bm25 import encode_queries
    from repro.search.distributed import (build_partitioned_state,
                                          make_dist_search_fn)
    n_parts = 1                      # host pytest process sees one device
    state, cfg, vocab = build_partitioned_state(
        corpus, n_parts, {"k": K, "max_blocks": 64})
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    fn = make_dist_search_fn(cfg, ("data", "model"), mesh=mesh)
    tids, qtf = encode_queries(vocab, queries, max_terms=cfg.max_terms,
                               idf=state["idf"])
    with jax.set_mesh(mesh):
        scores, ids = jax.jit(fn)(
            jax.tree_util.tree_map(jax.numpy.asarray, state), tids, qtf)
    for qi, q in enumerate(queries):
        got = [(int(i), float(v)) for v, i in zip(scores[qi], ids[qi])
               if v > 0]
        assert_matches_oracle(got, oracle.search(q, k=K), ctx=("mesh", q))


def test_mesh_pruned_bit_identical_to_mesh_dense(corpus, oracle, queries):
    """shard_map path with ``accumulator="pruned"``: same bits as the dense
    mesh run, and still oracle-exact."""
    import numpy as np

    from repro.parallel import compat
    from repro.search.bm25 import encode_queries
    from repro.search.distributed import (build_partitioned_state,
                                          make_dist_search_fn)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    out = {}
    for acc in ("dense", "pruned"):
        state, cfg, vocab = build_partitioned_state(
            corpus, 1, {"k": K, "max_blocks": 64, "accumulator": acc})
        fn = make_dist_search_fn(cfg, ("data", "model"), mesh=mesh)
        tids, qtf = encode_queries(vocab, queries, max_terms=cfg.max_terms,
                                   idf=state["idf"])
        with jax.set_mesh(mesh):
            scores, ids = jax.jit(fn)(
                jax.tree_util.tree_map(jax.numpy.asarray, state), tids, qtf)
        out[acc] = (np.asarray(scores), np.asarray(ids))
    assert np.array_equal(out["dense"][0].view(np.uint32),
                          out["pruned"][0].view(np.uint32))
    assert np.array_equal(out["dense"][1], out["pruned"][1])
    for qi, q in enumerate(queries):
        got = [(int(i), float(v)) for v, i in
               zip(out["pruned"][0][qi], out["pruned"][1][qi]) if v > 0]
        assert_matches_oracle(got, oracle.search(q, k=K),
                              ctx=("mesh-pruned", q))


def test_fleet_path_matches_oracle_through_gateway(corpus, oracle, queries):
    app = build_partitioned_search_app(corpus, n_parts=4)
    for q in queries:
        r = app.query(q, k=K)
        assert r.ok, r.body
        got = list(zip(r.body["ids"], r.body["scores"]))
        assert_matches_oracle(got, oracle.search(q, k=K), ctx=("fleet", q))
    # per-partition cold start + hydration recorded in the runtime ledger
    cold = [rec for rec in app.runtime.records if rec.cold]
    assert {rec.fn for rec in cold} == set(app.fn_names)
    assert all(rec.hydrate_s > 0 for rec in cold)
    assert app.runtime.ledger.invocations >= len(queries) * len(app.fn_names)


def test_fleet_batched_queries_match_single(corpus, oracle, queries):
    """A Q>1 micro-batch is ONE invocation per partition, same results."""
    app = build_partitioned_search_app(corpus, n_parts=4)
    n_before = len(app.runtime.records)
    r = app.query(list(queries), k=K, fetch_docs=False)
    assert r.ok, r.body
    assert len(app.runtime.records) - n_before == len(app.fn_names)
    assert len(r.body["results"]) == len(queries)
    for q, res in zip(queries, r.body["results"]):
        got = list(zip(res["ids"], res["scores"]))
        assert_matches_oracle(got, oracle.search(q, k=K), ctx=("batch", q))


def test_fleet_pruned_matches_dense_and_oracle(corpus, oracle, queries):
    """The wired-through flag: ``SearchConfig(accumulator="pruned")`` →
    ``build_partitioned_search_app`` handlers. Results identical to the
    dense fleet (scores bitwise via repr equality on floats) and
    oracle-exact."""
    dense_app = build_partitioned_search_app(corpus, n_parts=4)
    pruned_app = build_partitioned_search_app(
        corpus, n_parts=4,
        search_config=SearchConfig(accumulator="pruned"))
    rd = dense_app.query(list(queries), k=K, fetch_docs=False)
    rp = pruned_app.query(list(queries), k=K, fetch_docs=False)
    assert rd.ok and rp.ok
    for q, res_d, res_p in zip(queries, rd.body["results"],
                               rp.body["results"]):
        assert res_d["ids"] == res_p["ids"], q
        assert res_d["scores"] == res_p["scores"], q   # exact float equality
        assert_matches_oracle(list(zip(res_p["ids"], res_p["scores"])),
                              oracle.search(q, k=K), ctx=("fleet-pruned", q))


def test_global_stats_invariant_across_partition_counts(corpus, queries):
    """idf/avgdl AND the vocab are corpus-global: the merged ranking must
    be bitwise stable under repartitioning (the §3 subtlety the one-core
    build enforces by construction). Includes a query with far more than
    max_terms distinct terms — idf truncation must select the SAME term
    subset in every partition, which only holds with a shared vocab."""
    long_q = " ".join(t for _, text in corpus[:8] for t in text.split()[:6])
    qs = list(queries) + [long_q]
    per_n = {}
    for n in (1, 2, 4):
        app = build_partitioned_search_app(corpus, n_parts=n)
        r = app.query(qs, k=K, fetch_docs=False)
        assert r.ok, r.body
        per_n[n] = [
            (tuple(res["ext_ids"]),
             tuple(round(s, 6) for s in res["scores"]))
            for res in r.body["results"]]
    assert per_n[1] == per_n[2] == per_n[4]


def test_pruned_invariant_across_partition_counts(corpus, queries):
    """Partition-count invariance holds for the pruned path too — and at
    every partition count the pruned fleet returns the dense fleet's
    results (pruning decisions are per-partition, results must not be)."""
    per_n = {}
    for n in (1, 2, 4):
        out = {}
        for acc in ("dense", "pruned"):
            app = build_partitioned_search_app(
                corpus, n_parts=n,
                search_config=SearchConfig(accumulator=acc))
            r = app.query(list(queries), k=K, fetch_docs=False)
            assert r.ok, r.body
            out[acc] = [(tuple(res["ext_ids"]), tuple(res["scores"]))
                        for res in r.body["results"]]
        assert out["dense"] == out["pruned"]      # exact, per count
        per_n[n] = [(ids, tuple(round(s, 6) for s in ss))
                    for ids, ss in out["pruned"]]
    assert per_n[1] == per_n[2] == per_n[4]


def test_scatter_gather_latency_is_max_not_sum(corpus, queries):
    """All partitions fan out at the same arrival instant; end-to-end
    latency is the slowest partition (+merge/fetch), never the sum."""
    app = build_partitioned_search_app(corpus, n_parts=4)
    r = app.query(queries[0], k=K)          # all-cold fan-out
    lats = [p["latency_s"] for p in r.body["partitions"]]
    assert len(lats) == 4 and min(lats) > 0
    # every partition leg saw the same arrival time (un-mutated fleet)
    assert len({rec.t_arrival for rec in app.runtime.records}) == 1
    assert max(lats) <= r.latency_s < sum(lats)
    # warm repeat, straight at the ScatterGather layer: latency == max leg
    # plus the constant gather/merge term (charged on every scatter)
    hits, lat, recs = app.scatter.search(
        {"q": queries[0], "k": K, "fetch_docs": False}, K,
        t_arrival=app.runtime.clock + 1.0)
    assert hits and all(not rec.cold for rec in recs)
    assert lat == pytest.approx(
        max(rec.latency_s for rec in recs) + app.scatter.merge_cost_s)
    assert lat < sum(rec.latency_s for rec in recs)
    assert len({rec.t_arrival for rec in recs}) == 1


@pytest.mark.parametrize("use_kernel", [False, True])
def test_pruned_bit_identical_on_nrt_combined_segments(use_kernel):
    """NRT delta-served generations: ``combine_segments`` zeroes tombstoned
    postings (whole blocks can go dead, tf=0) and recomputes ``block_max``
    under live stats. The pruned path must return the dense path's exact
    bits on the combined index — a zeroed block has block_max 0 and must
    prune away or contribute nothing, never corrupt θ."""
    from repro.index.builder import (IndexWriter, combine_segments,
                                     compute_global_stats, extend_vocab,
                                     global_vocab, update_stats)
    from repro.index.tokenizer import tokenize
    docs = synth_corpus(240, vocab=400, seed=5)
    base_docs, new_docs = docs[:180], docs[180:]
    deleted = {docs[3][0], docs[100][0], docs[200][0]}

    stats = compute_global_stats(base_docs)
    vocab = global_vocab(stats)
    w = IndexWriter(global_stats=stats, vocab=vocab)
    w.add_many(base_docs)
    base = w.pack()
    vocab2 = extend_vocab(vocab, (t for _, txt in new_docs
                                  for t in tokenize(txt)))
    delta = IndexWriter.delta(new_docs, stats, vocab=vocab2)
    live_stats = dict(stats, df=dict(stats["df"]))
    by_id = dict(docs)
    for _, t in new_docs:
        update_stats(live_stats, t, sign=1)
    for e in deleted:
        update_stats(live_stats, by_id[e], sign=-1)
    dead = [i for i, (e, _) in enumerate(base_docs + new_docs)
            if e in deleted]
    combined = combine_segments([base, delta], vocab=vocab2,
                                stats=live_stats, tombstones=dead)

    dense = Searcher(combined, SearchConfig(max_blocks=64, k=K))
    pruned = Searcher(combined, SearchConfig(max_blocks=64, k=K,
                                             accumulator="pruned",
                                             use_kernel=use_kernel))
    _bitwise_equal_searches(dense, pruned, synth_queries(docs, 15, seed=6))


@pytest.mark.parametrize("seed", [101, 202, 303])
def test_property_random_corpora_all_paths_match_oracle(seed):
    """Property-style: random corpora/queries, all four single-node
    evaluation paths (dense, sorted, pruned, pruned+fused-kernel) against
    the exact oracle, and both pruned variants bitwise against dense."""
    import numpy as np
    rng = np.random.default_rng(seed)
    corpus = synth_corpus(int(rng.integers(80, 250)),
                          vocab=int(rng.integers(150, 600)), seed=seed)
    queries = synth_queries(corpus, 8, seed=seed + 1,
                            terms_per_query=int(rng.integers(1, 5)))
    oracle = OracleSearcher(corpus)
    from repro.index.builder import IndexWriter
    w = IndexWriter()
    w.add_many(corpus)
    packed = w.pack()
    variants = {
        "dense": SearchConfig(max_blocks=64, k=K),
        "sorted": SearchConfig(max_blocks=64, k=K, accumulator="sorted"),
        "pruned": SearchConfig(max_blocks=64, k=K, accumulator="pruned"),
        "pruned+kernel": SearchConfig(max_blocks=64, k=K,
                                      accumulator="pruned", use_kernel=True),
    }
    searchers = {name: Searcher(packed, cfg)
                 for name, cfg in variants.items()}
    for q in queries:
        for name, s in searchers.items():
            assert_matches_oracle(s.search_one(q), oracle.search(q, k=K),
                                  ctx=(seed, name, q))
    _bitwise_equal_searches(searchers["dense"], searchers["pruned"], queries)
    _bitwise_equal_searches(searchers["dense"], searchers["pruned+kernel"],
                            queries)


def test_long_query_truncation_keeps_high_idf_terms(corpus, packed):
    """encode_queries sheds the LOWEST-idf terms when a query overflows
    max_terms, so truncated evaluation tracks the full-query ranking."""
    from repro.search.bm25 import encode_queries
    # one long query from many docs' terms
    long_q = " ".join(t for _, text in corpus[:6] for t in text.split()[:8])
    tids, _ = encode_queries(packed.vocab, [long_q], max_terms=8,
                             idf=packed.idf)
    kept = [t for t in tids[0] if t >= 0]
    assert len(kept) == 8
    all_ids = [packed.vocab[t] for t in set(long_q.split())
               if t in packed.vocab]
    dropped = [t for t in all_ids if t not in kept]
    assert dropped, "query should overflow max_terms"
    assert min(packed.idf[kept]) >= max(packed.idf[dropped]) - 1e-6


@pytest.mark.parametrize("accumulator", ["dense", "pruned"])
def test_partial_hydration_bit_identical_under_nrt(accumulator):
    """Lazy partial-hydration views under an NRT generation (base + delta +
    tombstones): with only the QUERY terms' posting blocks hydrated, the
    fused view must rank bit-identically to full hydration — masked blocks
    carry tf=0 and land after the live blocks of their term in
    ``combine_segments``'s impact re-sort, so query terms' rows sit at
    identical positions. Backfill then reproduces the full index
    bit-for-bit."""
    import numpy as np

    from repro.core.object_store import ObjectStore
    from repro.core.refresh import AssetCatalog
    from repro.index.builder import (IndexWriter, combine_segments,
                                     compute_global_stats, extend_vocab,
                                     global_vocab, read_segment, update_stats,
                                     write_segment)
    from repro.index.hydration import LazyIndex, open_partial_segment
    from repro.index.tokenizer import tokenize

    docs = synth_corpus(240, vocab=400, seed=5)
    base_docs, new_docs = docs[:180], docs[180:]
    deleted = {docs[3][0], docs[100][0], docs[200][0]}

    stats = compute_global_stats(base_docs)
    vocab = global_vocab(stats)
    w = IndexWriter(global_stats=stats, vocab=vocab)
    w.add_many(base_docs)
    base = w.pack()
    vocab2 = extend_vocab(vocab, (t for _, txt in new_docs
                                  for t in tokenize(txt)))
    delta = IndexWriter.delta(new_docs, stats, vocab=vocab2)
    live_stats = dict(stats, df=dict(stats["df"]))
    by_id = dict(docs)
    for _, t in new_docs:
        update_stats(live_stats, t, sign=1)
    for e in deleted:
        update_stats(live_stats, by_id[e], sign=-1)
    dead = [i for i, (e, _) in enumerate(base_docs + new_docs)
            if e in deleted]
    combined = combine_segments([base, delta], vocab=vocab2,
                                stats=live_stats, tombstones=dead)

    store = ObjectStore()
    cat = AssetCatalog(store)
    cat.publish_segment("idx", "base", write_segment(base))
    cat.publish_segment("idx", "delta", write_segment(delta))
    lazy = LazyIndex(
        [open_partial_segment(cat.open_segment("idx", "base")),
         open_partial_segment(cat.open_segment("idx", "delta"))],
        vocab=vocab2, stats=live_stats, tombstones=dead)
    assert lazy.state == "partial"

    queries = synth_queries(docs, 15, seed=6)
    lazy.ensure_terms({t for q in queries for t in tokenize(q)})
    cfg = SearchConfig(max_blocks=64, k=K, accumulator=accumulator)
    full_s = Searcher(combined, cfg)
    _bitwise_equal_searches(full_s, Searcher(lazy.packed(), cfg), queries)

    lazy.backfill()
    assert lazy.state == "full"
    for seg, eager in zip(lazy.segments, (base, delta)):
        assert np.array_equal(seg.block_docs, np.asarray(eager.block_docs))
        assert np.array_equal(seg.block_tf, np.asarray(eager.block_tf))
    _bitwise_equal_searches(full_s, Searcher(lazy.packed(), cfg), queries)

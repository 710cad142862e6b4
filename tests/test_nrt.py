"""Near-real-time indexing: delta segments, tombstones, merges, and
zero-downtime generation rollover — the version-consistency harness.

The load-bearing invariants:

* PARITY — any interleaving of add/delete/commit/merge must rank exactly
  like a from-scratch rebuild of the final live corpus (the delta path can
  never drift from the one-segment path). Guaranteed by construction:
  segments store stat-independent postings, idf/avgdl apply at query time
  from the generation manifest's incrementally-maintained live stats.
* CONSISTENCY — no single query ever merges hits from two different index
  generations, across partitions, hedged replica legs, or freshly-scaled
  pools, even when a rollover (or an instance kill) lands mid-scatter.
* ATOMICITY — concurrent generation publishes surface as PublishConflict;
  gc never deletes the serving generation or a segment it references.
"""

import random

import pytest

from repro.core.object_store import ObjectStore
from repro.core.refresh import (AssetCatalog, GenerationManifest,
                                PublishConflict, generation_version)
from repro.core.runtime import RuntimeConfig
from repro.data.corpus import synth_corpus, synth_queries
from repro.index.builder import (IndexWriter, MergePolicy, combine_segments,
                                 compute_global_stats, extend_vocab,
                                 global_vocab, update_stats)
from repro.index.tokenizer import tokenize
from repro.search.oracle import OracleSearcher
from repro.search.searcher import SearchConfig, Searcher
from repro.search.service import build_partitioned_search_app


CFG = SearchConfig(sim_exec_s=0.002, sim_write_s=0.02)


def build_app(docs, n_parts=2, **kw):
    kw.setdefault("runtime_config", RuntimeConfig())
    kw.setdefault("search_config", CFG)
    return build_partitioned_search_app(docs, n_parts=n_parts, **kw)


def oracle_top(corpus, q, k=10):
    oracle = OracleSearcher(corpus)
    return [oracle.doc_ids[i] for i, _ in oracle.search(q, k=k)]


def assert_fleet_matches_oracle(app, queries, k=10):
    """The fleet's merged top-k must equal a from-scratch oracle rebuild of
    the LIVE corpus, in the fleet's own (partition, internal-id) order."""
    corpus = app.indexer.live_corpus()
    for q in queries:
        r = app.query(q, k=k, t_arrival=app.runtime.clock + 0.05,
                      fetch_docs=False)
        assert r.ok, r.body
        assert r.body["ext_ids"] == oracle_top(corpus, q, k), q
        assert len(app.scatter.last_versions) == 1


# -- builder level: the delta segment itself ------------------------------------


def test_delta_plus_combine_equals_rebuild():
    docs = synth_corpus(240, vocab=400, seed=0)
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
    assert delta.meta.n_docs == len(new_docs)

    live_stats = dict(stats, df=dict(stats["df"]))
    by_id = dict(docs)
    for _, t in new_docs:
        update_stats(live_stats, t, sign=1)
    for e in deleted:
        update_stats(live_stats, by_id[e], sign=-1)

    dead_pos = [i for i, (e, _) in enumerate(base_docs + new_docs)
                if e in deleted]                 # tombstones = internal positions
    combined = combine_segments([base, delta], vocab=vocab2,
                                stats=live_stats, tombstones=dead_pos)
    live = [(e, t) for e, t in docs if e not in deleted]
    ref = compute_global_stats(live)
    assert live_stats["n_docs"] == ref["n_docs"]
    assert live_stats["avgdl"] == pytest.approx(ref["avgdl"])
    assert live_stats["df"] == ref["df"]

    s_delta = Searcher(combined, CFG)
    wr = IndexWriter(global_stats=ref, vocab=global_vocab(ref))
    wr.add_many(live)
    s_rebuild = Searcher(wr.pack(), CFG)
    for q in synth_queries(docs, 25, seed=2):
        e1 = [combined.meta.doc_ids[i] for i, _ in s_delta.search_one(q)]
        e2 = [s_rebuild.packed.meta.doc_ids[i]
              for i, _ in s_rebuild.search_one(q)]
        assert e1 == e2 == oracle_top(live, q), q
        assert not set(e1) & deleted


def test_extend_vocab_is_append_only():
    v = {"b": 0, "a": 1}
    v2 = extend_vocab(v, ["c", "a", "aa"])
    assert v2["b"] == 0 and v2["a"] == 1          # existing ids never move
    assert sorted(v2) == ["a", "aa", "b", "c"]
    assert v2["aa"] == 2 and v2["c"] == 3         # new ids appended, sorted
    assert extend_vocab(v2, ["a"]) == v2


def test_merge_policy_tiers():
    pol = MergePolicy(max_deltas=2, ratio=0.5, tombstone_ratio=0.2)
    assert not pol.should_merge(100, 0, 0, 0)           # nothing to do
    assert not pol.should_merge(100, 30, 1, 5)          # small tier, few dead
    assert pol.should_merge(100, 30, 3, 0)              # too many deltas
    assert pol.should_merge(100, 60, 1, 0)              # tier outgrew ratio
    assert pol.should_merge(100, 0, 0, 30)              # tombstone debt
    assert pol.should_merge(0, 1, 1, 0)                 # empty base: any delta


# -- property: random interleavings vs full rebuild ------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_interleaving_parity(seed):
    """Seeded random add/delete/commit/merge interleavings: after every
    commit the fleet must rank exactly like a rebuild of the live corpus."""
    rng = random.Random(seed)
    docs = synth_corpus(160, vocab=300, seed=seed)
    init, pool = docs[:90], list(docs[90:])
    # a tight merge policy so interleavings actually exercise compaction
    app = build_app(init, n_parts=2,
                    merge_policy=MergePolicy(max_deltas=2, ratio=0.4,
                                             tombstone_ratio=0.15))
    queries = synth_queries(docs, 10, seed=seed + 50)
    assert_fleet_matches_oracle(app, queries)

    for _ in range(4):
        n_ops = rng.randint(1, 3)
        for _ in range(n_ops):
            if pool and rng.random() < 0.6:
                take = rng.randint(1, min(12, len(pool)))
                batch, pool[:take] = pool[:take], []
                r = app.add_documents(batch)
                assert r.ok, r.body
            else:
                live = app.indexer.live_corpus()
                victims = rng.sample([e for e, _ in live],
                                     k=min(3, len(live)))
                r = app.delete_documents(victims)
                assert r.ok, r.body
        r = app.commit()
        assert r.ok, r.body
        assert_fleet_matches_oracle(app, queries)

    merges = sum(len(c["merged"]) for c in app.indexer.commits)
    assert merges >= 1, "interleaving never exercised merge compaction"
    # incremental stats never drifted from a from-scratch recount
    ref = compute_global_stats(app.indexer.live_corpus())
    assert app.indexer.stats["n_docs"] == ref["n_docs"]
    assert app.indexer.stats["avgdl"] == pytest.approx(ref["avgdl"])
    assert app.indexer.stats["df"] == ref["df"]


def test_delete_only_commit_and_update_semantics():
    docs = synth_corpus(80, vocab=200, seed=3)
    app = build_app(docs[:60], n_parts=2)
    # delete-only commit: tombstones published, no writer invocation
    victim = docs[0][0]
    app.delete_documents([victim])
    r = app.commit()
    assert r.ok and r.body["writers"] == 0 and r.body["deleted"] == 1
    corpus = app.indexer.live_corpus()
    assert victim not in [e for e, _ in corpus]
    assert_fleet_matches_oracle(app, synth_queries(docs, 6, seed=9))
    # duplicate add refused (update = delete + add + commit)
    with pytest.raises(ValueError):
        app.indexer.stage_add([(docs[1][0], "dup")])
    # deleting a never-committed pending add just unstages it
    app.add_documents(docs[60:62])
    app.delete_documents([docs[60][0]])
    r = app.commit()
    assert r.ok and r.body["indexed"] == 1 and r.body["deleted"] == 0
    assert docs[61][0] in [e for e, _ in app.indexer.live_corpus()]
    assert docs[60][0] not in [e for e, _ in app.indexer.live_corpus()]
    # deleting an unknown id is a no-op, not an error
    r = app.delete_documents(["nope"])
    assert r.ok and r.body["pending_deletes"] == 0
    # a half-bad add batch stages NOTHING (atomic validation)
    with pytest.raises(ValueError):
        app.indexer.stage_add([("brand-new", "x"), (docs[2][0], "dup")])
    assert "brand-new" not in app.indexer._pending_ids
    assert app.commit().body["committed"] is False


def test_update_flow_delete_add_commit():
    """The documented update recipe — delete + add + commit, in ONE batch —
    must work, and repeated updates of the same id must survive landing in
    the partition that tombstoned an older copy (tombstones are internal
    positions, so an old tombstone can never kill the re-added doc)."""
    docs = synth_corpus(60, vocab=150, seed=10)
    app = build_app(docs, n_parts=2)
    queries = synth_queries(docs, 6, seed=19)
    target = docs[2][0]
    for i in range(4):                  # round-robin lands both partitions
        text = f"mede bu dubo variant{i} bu mede"
        app.delete_documents([target])
        app.add_documents([(target, text)])
        r = app.commit()
        assert r.ok, r.body
        live = dict(app.indexer.live_corpus())
        assert live[target] == text     # new copy live, old copies dead
        assert_fleet_matches_oracle(app, queries + ["mede bu"])


# -- fault injection: version consistency under rollover + kills ------------------


def test_rollover_mid_scatter_never_tears_a_query():
    """Force a commit+rollover to land between two scatter legs of one
    query (and kill an instance for good measure): the query must still
    merge hits from ONE generation — the one pinned at dispatch — and the
    next query moves to the new generation."""
    docs = synth_corpus(120, vocab=250, seed=4)
    app = build_app(docs[:100], n_parts=3)
    q = synth_queries(docs, 1, seed=11)[0]
    app.query(q, fetch_docs=False)                      # hydrate gen 1
    gen_before = app.indexer.gen

    app.add_documents(docs[100:])                       # staged, uncommitted
    state = {"armed": True}
    orig_invoke = app.runtime.invoke

    def invoke(fn, payload, **kw):
        result = orig_invoke(fn, payload, **kw)
        if state["armed"] and fn.startswith("search-"):
            state["armed"] = False                      # re-entrancy guard
            app.runtime.kill_instance(fn=app.fn_names[1])
            r = app.commit()                            # rollover mid-scatter
            assert r.ok and r.body["gen"] == gen_before + 1
        return result

    app.runtime.invoke = invoke
    r = app.query(q, k=10, fetch_docs=False)
    assert r.ok
    # every leg answered from the generation pinned BEFORE the rollover
    assert app.scatter.last_versions == [generation_version(gen_before)]
    assert r.body["generation"] == gen_before
    # ...and the very next query serves the new generation, fleet-wide
    r2 = app.query(q, k=10, t_arrival=app.runtime.clock + 0.05,
                   fetch_docs=False)
    assert app.scatter.last_versions == [generation_version(gen_before + 1)]
    assert r2.body["generation"] == gen_before + 1
    assert_fleet_matches_oracle(app, [q])


def test_hedged_legs_share_the_pinned_generation():
    docs = synth_corpus(100, vocab=200, seed=5)
    app = build_app(docs[:80], n_parts=2, replicas=2, hedge=0.01)
    app.warm()
    queries = synth_queries(docs, 6, seed=13)
    for q in queries:                                   # build warm history
        app.query(q, fetch_docs=False,
                  t_arrival=app.runtime.clock + 0.05)
    app.add_documents(docs[80:])
    assert app.commit().ok
    # cold-inject the primary so the hedge actually fires post-rollover
    app.runtime.kill_instance(fn=app.fn_names[0])
    r = app.query(queries[0], fetch_docs=False,
                  t_arrival=app.runtime.clock + 0.05)
    assert r.ok
    assert len(app.scatter.last_versions) == 1          # backup leg included
    assert_fleet_matches_oracle(app, queries)


def test_scale_up_registers_replica_on_current_generation():
    docs = synth_corpus(100, vocab=200, seed=6)
    app = build_app(docs[:80], n_parts=2, autoscale=True)
    app.query(synth_queries(docs, 1, seed=14)[0], fetch_docs=False)
    app.add_documents(docs[80:])
    assert app.commit().ok
    current = app.indexer.gen
    ctl = app.controller
    ctl._scale_up(0, ctl.groups[0], app.runtime.clock + 1.0, "test")
    assert len(app.scatter.groups[0]) == 2
    # the fresh replica's prewarmed pool serves the CURRENT generation;
    # a query touching it must stay single-generation
    for q in synth_queries(docs, 4, seed=15):
        r = app.query(q, k=10, t_arrival=app.runtime.clock + 0.05,
                      fetch_docs=False)
        assert r.ok
        assert app.scatter.last_versions == [generation_version(current)]
    assert_fleet_matches_oracle(app, synth_queries(docs, 4, seed=16))


# -- publish atomicity + gc -------------------------------------------------------


def test_publish_generation_conflict_lost_update():
    """Two writers both base gen 2 on gen 1: the second publish must
    surface PublishConflict, not silently overwrite the winner."""
    store = ObjectStore()
    cat = AssetCatalog(store)
    m1 = GenerationManifest(gen=1, base="g1-base", deltas=[], tombstones=[],
                            stats={"n_docs": 1, "avgdl": 1.0, "df": {}},
                            vocab={})
    cat.publish_generation("idx", m1)
    winner = GenerationManifest(gen=2, base="g1-base", deltas=["g2-a"],
                                tombstones=[], stats=m1.stats, vocab={})
    loser = GenerationManifest(gen=2, base="g1-base", deltas=["g2-b"],
                               tombstones=[], stats=m1.stats, vocab={})
    cat.publish_generation("idx", winner)
    with pytest.raises(PublishConflict):
        cat.publish_generation("idx", loser)
    # the winner's manifest is intact and the loser left no phantom files
    assert cat.current_generation("idx").deltas == ["g2-a"]
    assert cat.read_generation("idx").gen == 2


def test_publish_generation_conflict_torn_race():
    """A manifest swap racing between our read and our conditional put is
    caught by the etag CAS — the torn-publish case."""
    store = ObjectStore()
    cat = AssetCatalog(store)
    m1 = GenerationManifest(gen=1, base="b", deltas=[], tombstones=[],
                            stats={"n_docs": 1, "avgdl": 1.0, "df": {}},
                            vocab={})
    cat.publish_generation("idx", m1)
    real_head = store.head

    def racing_head(key):
        meta = real_head(key)
        if key.endswith("MANIFEST"):
            # another writer flips the manifest AFTER our read
            store.put(key, b'{"current": "gen-000001"}')
        return meta

    store.head = racing_head
    m2 = GenerationManifest(gen=2, base="b", deltas=["d"], tombstones=[],
                            stats=m1.stats, vocab={})
    with pytest.raises(PublishConflict):
        cat.publish_generation("idx", m2)
    store.head = real_head
    # loser cleaned up: gen-000002 left no files behind
    assert not store.list(cat.version_prefix("idx", "gen-000002"))


def test_publish_generation_same_gen_race_spares_winner():
    """Two writers racing the SAME generation number: the loser's cleanup
    must never delete the winner's published files (the generation file is
    create-once, so the loser conflicts before touching anything)."""
    store = ObjectStore()
    cat = AssetCatalog(store)
    m1 = GenerationManifest(gen=1, base="b", deltas=[], tombstones=[],
                            stats={"n_docs": 1, "avgdl": 1.0, "df": {}},
                            vocab={})
    cat.publish_generation("idx", m1)
    winner = GenerationManifest(gen=2, base="b", deltas=["g2-winner"],
                                tombstones=[], stats=m1.stats, vocab={})
    loser = GenerationManifest(gen=2, base="b", deltas=["g2-loser"],
                               tombstones=[], stats=m1.stats, vocab={})
    # interleave: the loser passed the stale-base check (it read gen 1)
    # before the winner's flip landed — simulate by publishing the winner
    # from inside the loser's manifest read
    real_head = store.head

    def racing_head(key):
        meta = real_head(key)
        if key.endswith("MANIFEST"):
            store.head = real_head          # winner publishes, un-raced
            cat.publish_generation("idx", winner)
            store.head = racing_head
        return meta

    store.head = racing_head
    with pytest.raises(PublishConflict):
        cat.publish_generation("idx", loser)
    store.head = real_head
    # the WINNER's generation survives, fully readable, serving its deltas
    assert cat.current_version("idx") == generation_version(2)
    assert cat.read_generation("idx").deltas == ["g2-winner"]


def test_publish_segment_is_create_once():
    """Segments are immutable: re-publishing an existing id conflicts
    instead of silently overwriting bytes a manifest may already serve."""
    from repro.core.directory import RamDirectory
    store = ObjectStore()
    cat = AssetCatalog(store)
    cat.publish_segment("idx", "g000001-base", RamDirectory({"f": b"A"}))
    with pytest.raises(PublishConflict):
        cat.publish_segment("idx", "g000001-base", RamDirectory({"f": b"B"}))
    d = cat.open_segment("idx", "g000001-base")
    assert d.open_input("f").read_all() == b"A"   # original bytes intact


def test_gc_reclaims_merged_away_segments_keeps_serving():
    docs = synth_corpus(90, vocab=200, seed=7)
    app = build_app(docs[:60], n_parts=2,
                    merge_policy=MergePolicy(max_deltas=0))  # merge every commit
    app.add_documents(docs[60:75])
    assert app.commit().ok                               # gen 2: merge
    app.add_documents(docs[75:])
    assert app.commit().ok                               # gen 3: merge again
    cat, store = app.catalog, app.store
    for st in app.indexer.parts:
        asset = st.asset
        # serving + previous generations survive (rollback / pinned queries)
        versions = cat.versions(asset)
        assert cat.current_version(asset) in versions
        assert len(versions) == 2
        # every surviving generation's segments are readable...
        for v in versions:
            for seg in cat.read_generation(asset, v).segments:
                assert store.list(cat.segment_prefix(asset, seg)), (v, seg)
        # ...and the gen-1 base, referenced by nothing alive, is reclaimed
        assert not store.list(cat.segment_prefix(asset, "g000001-base"))
    assert_fleet_matches_oracle(app, synth_queries(docs, 6, seed=17))


def test_failed_commit_rolls_back_and_retries():
    """A commit whose publish conflicts PERSISTENTLY (every in-commit
    rebase-retry loses another race) must exhaust its bounded attempts,
    restore the writer's state — staged batch included — and surface the
    conflict; a later retry must publish a strictly NEWER generation than
    anything the partial flips left behind, instead of wedging on the
    stale-base check. (A TRANSIENT conflict no longer reaches the caller:
    the commit's own retry loop rebases and heals it —
    test_two_writer_race_converges_to_serialized_oracle.)"""
    docs = synth_corpus(90, vocab=200, seed=9)
    app = build_app(docs[:70], n_parts=2)
    ix = app.indexer
    queries = synth_queries(docs, 5, seed=18)
    app.add_documents(docs[70:])
    app.delete_documents([docs[1][0]])
    before = (dict(ix.stats, df=dict(ix.stats["df"])), dict(ix.vocab),
              [list(st.seg_docs) for st in ix.parts])

    # partition 1's CAS loses EVERY attempt: its manifest keeps moving
    # under us (the in-commit retries leave partition 0 further and
    # further ahead — exactly the partial-flip debris the heal must clear)
    real = ix.catalog.publish_generation
    calls = {"n": 0}
    p1_asset = ix.parts[1].asset

    def failing(name, manifest):
        calls["n"] += 1
        if name == p1_asset:
            raise PublishConflict("racing writer won")
        return real(name, manifest)

    ix.catalog.publish_generation = failing
    r = app.commit()
    assert r.status == 502 and "racing writer" in r.body["error"]
    assert calls["n"] >= 4            # bounded attempts actually retried
    ix.catalog.publish_generation = real
    # full rollback: gen, stats, vocab, tiers, and the staged batch
    assert ix.gen == 1
    assert ix.stats == before[0] and ix.vocab == before[1]
    assert [list(st.seg_docs) for st in ix.parts] == before[2]
    assert len(ix.pending_adds) == 20 and len(ix.pending_deletes) == 1
    # queries keep serving the old generation, consistently
    assert_fleet_matches_oracle(app, queries)
    # retry heals past the partial flips: partition 0 is several
    # generations ahead, so the retry publishes one newer still
    heal_gen = ix._published_gen() + 1
    assert heal_gen > 2
    r = app.commit()
    assert r.ok and r.body["gen"] == heal_gen
    assert all(ix.catalog.current_version(st.asset)
               == generation_version(heal_gen) for st in ix.parts)
    assert_fleet_matches_oracle(app, queries)


def test_rollover_prewarms_every_idle_instance():
    """A pool grown to N instances by concurrent traffic must have ALL N
    prewarmed by a commit's rollover — otherwise the un-pinged instances
    hydrate the new generation in-band on their next query, the exact p99
    spike the prewarm exists to prevent."""
    docs = synth_corpus(80, vocab=200, seed=11)
    app = build_app(docs[:60], n_parts=1)
    q1, q2 = synth_queries(docs, 2, seed=20)
    # two queries at ONE arrival instant grow the pool to 2 instances
    t0 = app.runtime.clock + 0.1
    app.query(q1, fetch_docs=False, t_arrival=t0)
    app.query(q2, fetch_docs=False, t_arrival=t0)
    fn = app.fn_names[0]
    assert sum(i.fn == fn for i in app.runtime._instances) == 2
    app.add_documents(docs[60:])
    r = app.commit(t_arrival=app.runtime.clock + 0.1)
    assert r.ok and r.body["pings"] == 2          # one per idle instance
    # concurrent post-rollover queries: BOTH instances serve warm
    t1 = app.runtime.clock + 0.1
    for q in (q1, q2):
        res = app.query(q, fetch_docs=False, t_arrival=t1)
        assert all(not p["cold"] and p["hydrate_s"] == 0
                   for p in res.body["partitions"]), res.body["partitions"]


def test_commit_survives_runtime_straggler_hedge():
    """FaaSRuntime.hedge_after_s re-executes handlers mid-invocation; a
    writer invocation that trips it publishes TWICE. Unique segment ids
    make the re-execution harmless (the loser's segment is an orphan for
    gc), instead of a PublishConflict that wedges every commit."""
    docs = synth_corpus(80, vocab=200, seed=13)
    # writer exec (~0.02 s modeled + per-doc) trips a 1 ms hedge threshold
    app = build_app(docs[:50], n_parts=2,
                    runtime_config=RuntimeConfig(hedge_after_s=0.001))
    queries = synth_queries(docs, 5, seed=21)
    app.add_documents(docs[50:65])
    r = app.commit()
    assert r.ok, r.body
    assert any(rec.write and rec.hedged for rec in app.runtime.records)
    assert_fleet_matches_oracle(app, queries)
    app.add_documents(docs[65:])
    assert app.commit().ok                  # and the next commit too
    assert_fleet_matches_oracle(app, queries)


def test_delete_removes_raw_document_content():
    """An index tombstone alone is cosmetic — the KV record must go too
    (data deletion is the usual reason to delete), except when the same
    commit re-adds the id (update: new content survives). It goes once no
    generation gc keeps can rank it: the commit that deletes it keeps the
    previous generation (a query pinned there still fetches it), the next
    commit retires that generation and the passage with it."""
    docs = synth_corpus(60, vocab=150, seed=12)
    app = build_app(docs[:50], n_parts=2)
    gone, updated = docs[0][0], docs[1][0]
    assert gone in app.doc_store and updated in app.doc_store
    app.delete_documents([gone, updated])
    app.add_documents([(updated, "replacement text body")] + docs[50:55])
    # staged only — content still fetchable until the commit lands
    assert gone in app.doc_store
    assert app.commit().ok
    assert gone in app.doc_store          # generation 1 still ranks it
    assert app.doc_store.get(updated)["contents"] == "replacement text body"
    app.add_documents(docs[55:])
    assert app.commit().ok                # generation 1 retired
    assert gone not in app.doc_store              # content really deleted
    assert app.doc_store.get(updated)["contents"] == "replacement text body"


def test_commit_bills_the_write_line():
    docs = synth_corpus(80, vocab=200, seed=8)
    app = build_app(docs[:60], n_parts=2)
    led = app.runtime.ledger
    assert led.write_invocations == 0                    # bootstrap is offline
    app.add_documents(docs[60:])
    assert app.commit().ok
    assert led.write_invocations == 2                    # one per partition
    assert led.write_dollars > 0
    att = led.attribution()
    assert att["write"] == pytest.approx(led.write_dollars)
    assert sum(att.values()) == pytest.approx(led.compute_dollars)
    # writer invocations are tagged on the record log too
    writes = [r for r in app.runtime.records if r.write]
    assert len(writes) == 2 and all(r.fn.startswith("indexer-") for r in writes)


# -- concurrent multi-writer commits ------------------------------------------


PING = {"q": "", "k": 1, "fetch_docs": False}


def _serialized_twin(base_docs, batches, n_parts=2):
    """One writer committing the batches sequentially — the serialized
    oracle a raced pair of writers must converge to bit-for-bit."""
    app = build_app(base_docs, n_parts=n_parts)
    for adds, dels in batches:
        if dels:
            app.delete_documents(dels)
        if adds:
            app.add_documents(adds)
        r = app.commit()
        assert r.ok, r.body
    return app


def test_two_writer_race_converges_to_serialized_oracle():
    """Seeded sweep: two forked writers stage against the SAME generation
    and commit back to back. The loser must rebase on the winner — adopting
    its documents, live stats/vocab, and round-robin cursor — so the final
    index is bit-identical (placement, stats, merged top-k scores) to one
    writer committing the two batches serially. Without the rebase the
    loser's commit would silently publish a generation missing the
    winner's documents."""
    for seed in (0, 1, 2):
        rng = random.Random(seed)
        docs = synth_corpus(90, vocab=200, seed=40 + seed)
        base, extra = docs[:60], docs[60:]
        cut = rng.randrange(5, len(extra) - 5)
        batch_a, batch_b = extra[:cut], extra[cut:]
        del_a = [base[rng.randrange(len(base))][0]]
        del_b = [base[rng.randrange(len(base))][0]]  # may equal del_a

        racing = build_app(base, n_parts=2)
        a = racing.indexer
        b = a.fork(1)
        # both writers stage BEFORE either commits — the race
        a.stage_delete(del_a)
        a.stage_add(batch_a)
        b.stage_delete(del_b)
        b.stage_add(batch_b)
        ra, _ = a.commit(racing.fn_groups, ping_payload=PING)
        rb, _ = b.commit(racing.fn_groups, ping_payload=PING)
        assert rb["rebased"] == 1 and rb["gen"] == ra["gen"] + 1
        # the app pins queries to ITS writer's generation — the loser's
        # publish is foreign to A until A adopts it
        assert a.sync() is True
        assert a.gen == rb["gen"] and a.live_corpus() == b.live_corpus()

        serial = _serialized_twin(base, [(batch_a, del_a), (batch_b, del_b)])
        six = serial.indexer
        # logical state converged exactly: stats, vocab, placement, cursor
        assert b.stats == six.stats
        assert b.vocab == six.vocab
        assert b._rr == six._rr
        assert b.live_corpus() == six.live_corpus()
        # merged top-k bit-identical to the serialized twin AND the oracle
        queries = synth_queries(docs, 6, seed=70 + seed)
        for q in queries:
            r1 = racing.query(q, k=10, t_arrival=racing.runtime.clock + 0.05,
                              fetch_docs=False)
            r2 = serial.query(q, k=10, t_arrival=serial.runtime.clock + 0.05,
                              fetch_docs=False)
            assert r1.ok and r2.ok
            assert r1.body["ext_ids"] == r2.body["ext_ids"]
            assert r1.body["scores"] == r2.body["scores"]
        assert_fleet_matches_oracle(racing, queries)


def test_publish_conflict_loser_rebases_and_orphans_are_collected():
    """TRUE concurrency: the loser sampled the catalog BEFORE the winner's
    flip landed, so its first attempt targets the winner's generation and
    loses the create-once race — after its delta segments already
    uploaded. The in-commit retry must rebase and republish, and the
    failed attempt's uploads must be unreferenced orphans the
    reference-based gc reclaims."""
    docs = synth_corpus(80, vocab=200, seed=44)
    app = build_app(docs[:60], n_parts=2)
    a = app.indexer
    b = a.fork(1)
    a.stage_add(docs[60:70])
    b.stage_add(docs[70:])
    ra, _ = a.commit(app.fn_groups, ping_payload=PING)

    # freeze B's view of the catalog at the pre-flip instant for ONE
    # commit-loop iteration (what a truly concurrent reader would have seen)
    real_fg, real_pg = b._foreign_gen, b._published_gen
    stale = {"armed": True}

    def stale_fg():
        return None if stale["armed"] else real_fg()

    def stale_pg():
        if stale["armed"]:
            stale["armed"] = False
            return ra["gen"] - 1
        return real_pg()

    b._foreign_gen = stale_fg
    b._published_gen = stale_pg
    published = []
    real_pub = b.catalog.publish_segment

    def recording_pub(name, seg, files):
        published.append((name, seg))
        return real_pub(name, seg, files)

    b.catalog.publish_segment = recording_pub
    rb, _ = b.commit(app.fn_groups, ping_payload=PING)
    b.catalog.publish_segment = real_pub

    assert rb["publish_conflicts"] == 1 and rb["rebased"] == 1
    assert rb["gen"] == ra["gen"] + 1
    # attempt 1 uploaded delta segments AT THE WINNER'S generation before
    # the state segment's create-once check surfaced the conflict
    orphans = [(name, seg) for name, seg in published
               if seg.startswith(f"g{ra['gen']:06d}") and "w1-" in seg]
    assert orphans
    # ...and every one of them is gone: unreferenced by any surviving
    # manifest, swept by the reference-based gc the commit already ran
    for name, seg in orphans:
        assert app.store.list(app.catalog.segment_prefix(name, seg)) == []
    assert a.sync() is True
    queries = synth_queries(docs, 5, seed=46)
    assert_fleet_matches_oracle(app, queries)


def test_sync_adopts_foreign_publish():
    """A stale writer can adopt a racing writer's published state outside
    of a commit; a second sync is a no-op."""
    docs = synth_corpus(70, vocab=200, seed=47)
    app = build_app(docs[:60], n_parts=2)
    a = app.indexer
    b = a.fork(1)
    app.add_documents(docs[60:])
    app.commit()
    assert b.gen == 1
    assert b.sync() is True
    assert b.gen == a.gen
    assert b.live_corpus() == a.live_corpus()
    assert b._rr == a._rr
    assert b.sync() is False


def test_rebase_conflict_on_same_id_is_loud_and_restores(
):
    """Both writers staging an ADD of the same ext id is a real conflict
    (updates = delete + add): the loser's commit must fail loudly with the
    checkpoint restored and the batch still staged, never publish a
    silent duplicate."""
    docs = synth_corpus(70, vocab=200, seed=48)
    app = build_app(docs[:60], n_parts=2)
    a = app.indexer
    b = a.fork(1)
    dup = docs[60]
    a.stage_add([dup])
    b.stage_add([dup, docs[61]])
    a.commit(app.fn_groups, ping_payload=PING)
    with pytest.raises(ValueError, match="rebase conflict"):
        b.commit(app.fn_groups, ping_payload=PING)
    # rollback: still staged, view unchanged, index unharmed
    assert b.gen == 1 and len(b.pending_adds) == 2
    queries = synth_queries(docs, 4, seed=49)
    assert_fleet_matches_oracle(app, queries)

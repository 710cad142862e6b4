"""Hybrid retrieval: the dense-vector tier and its fusion with BM25.

The load-bearing invariants:

* BIT-PARITY — per-partition dense scores (the Pallas ``dot_topk`` path)
  must be uint32-BIT-identical to the full-corpus ``dot_topk_batch_ref``
  oracle, for ANY partition size (the chunk is never shrunk to N) and ANY
  micro-batch width (each query dispatches as its own compiled program, so
  window composition can never perturb a neighbour's bits).
* DELTA PARITY — a dense ranking served from base + delta vector segments
  with tombstones equals a from-scratch rebuild of the live corpus.
* ONE GENERATION — both tiers of a hybrid query answer from the same
  generation; a forged cross-tier skew raises GenerationMismatch; every
  commit (text or not) CAS-flips one manifest per partition.
* FUSION — hybrid top-k is exactly ``rrf_fuse`` over the two tiers'
  merged rankings, reproducible against the two oracles fused the same way.
"""

import jax
import numpy as np
import pytest

from repro.core.partition import FleetSpec, IndexSpec, VectorSpec, rrf_fuse
from repro.core.runtime import RuntimeConfig
from repro.data.corpus import hash_embedder, synth_corpus, synth_queries
from repro.index.builder import (combine_vector_segments, pack_vectors,
                                 read_vector_segment, unpack_vector_superindex,
                                 write_vector_segment)
from repro.index.hydration import LazyVectors, open_partial_vector_segment
from repro.kernels import dot_topk as dot_topk_module
from repro.kernels.dot_topk import padded_rows
from repro.kernels.ops import dot_topk, dot_topk_batch
from repro.kernels.ref import dot_topk_batch_ref
from repro.search.oracle import (DenseOracleSearcher, OracleSearcher,
                                 hybrid_oracle_fuse)
from repro.search.searcher import DenseSearcher, SearchConfig
from repro.search.service import build_partitioned_search_app

CFG = SearchConfig(sim_exec_s=0.002, sim_write_s=0.02)
DIM = 16


def build_app(docs, n_parts=2, *, dtype="float32", cfg=CFG, **kw):
    return build_partitioned_search_app(docs, FleetSpec(
        n_parts=n_parts,
        index=IndexSpec(vector=VectorSpec(dim=DIM, dtype=dtype)),
        runtime_config=RuntimeConfig(), search_config=cfg, **kw))


def bits(xs):
    return [np.float32(x).view(np.uint32) for x in xs]


# -- kernel level: uint32 bit-parity vs the pure-JAX reference -------------------


@pytest.mark.parametrize("N,D,k,Q", [(53, 16, 10, 1), (53, 16, 10, 5),
                                     (136, 16, 10, 7), (1000, 16, 10, 3),
                                     (1091, 16, 10, 8), (4096, 64, 50, 2),
                                     (5, 8, 3, 1)])
def test_dot_topk_batch_bitwise_vs_ref(N, D, k, Q):
    """Kernel vs reference, uint32 score bits — including row counts that
    are NOT multiples of the f32-matvec alignment (53, 1091): the chunk
    padding must make the accumulation shape canonical for any N."""
    rng = np.random.default_rng(N * 7 + D)
    c = rng.standard_normal((N, D)).astype(np.float32)
    q = rng.standard_normal((Q, D)).astype(np.float32)
    gv, gi = dot_topk_batch(q, c, k)
    wv, wi = dot_topk_batch_ref(q, c, k)
    assert (np.asarray(gv).view(np.uint32)
            == np.asarray(wv).view(np.uint32)).all()
    assert (np.asarray(gi) == np.asarray(wi)).all()


@pytest.mark.parametrize("N", [136, 137, 1091])
def test_dot_topk_batch_q_invariant(N):
    """A query's score bits may not depend on how many neighbours shared
    its micro-batch (the windowed-dispatch bit-parity contract): batched
    results row 0 == the Q=1 dispatch, exactly."""
    rng = np.random.default_rng(N)
    c = rng.standard_normal((N, DIM)).astype(np.float32)
    q = rng.standard_normal((8, DIM)).astype(np.float32)
    v1, i1 = dot_topk_batch(q[:1], c, 10)
    for Q in (2, 3, 7, 8):
        vq, iq = dot_topk_batch(q[:Q], c, 10)
        assert (np.asarray(vq)[0].view(np.uint32)
                == np.asarray(v1)[0].view(np.uint32)).all(), Q
        assert (np.asarray(iq)[0] == np.asarray(i1)[0]).all(), Q


def test_partition_bits_match_full_corpus_bits():
    """The fleet argument in one kernel fact: a row scores to the same
    bits whether it sits in a 53-row partition or a 200-row corpus."""
    rng = np.random.default_rng(9)
    c = rng.standard_normal((200, DIM)).astype(np.float32)
    q = rng.standard_normal((1, DIM)).astype(np.float32)
    fv, fi = dot_topk_batch(q, c, 200)
    full = {int(i): np.float32(v).view(np.uint32)
            for v, i in zip(np.asarray(fv)[0], np.asarray(fi)[0])}
    pv, pi = dot_topk_batch(q, c[147:], 53)         # uneven tail partition
    for v, i in zip(np.asarray(pv)[0], np.asarray(pi)[0]):
        assert np.float32(v).view(np.uint32) == full[147 + int(i)]


@pytest.mark.parametrize("N,k", [(5, 10), (53, 10), (1024, 10), (1091, 10),
                                 (2300, 1100)])
def test_dot_topk_on_rows_padded_on_the_host(N, k):
    """Rows padded with zero rows on the host and placed on the device,
    scored with ``n_valid``, give the raw rows' bits and ids — also where
    k > 1,024 makes the chunk k, not 1,024 (2,300 rows → 3,300). k is cut
    to N, as the searcher cuts it."""
    k = min(k, N)
    rng = np.random.default_rng(N + k)
    c = rng.standard_normal((N, DIM)).astype(np.float32)
    q = rng.standard_normal((1, DIM)).astype(np.float32)
    padded = np.zeros((padded_rows(N, k), DIM), np.float32)
    padded[:N] = c
    gv, gi = dot_topk(q[0], jax.device_put(padded), k, n_valid=N)
    rv, ri = dot_topk(q[0], c, k)
    wv, wi = dot_topk_batch_ref(q, c, k)
    for v, i in ((rv, ri), (wv[0], wi[0])):
        assert (np.asarray(gv).view(np.uint32)
                == np.asarray(v).view(np.uint32)).all()
        assert (np.asarray(gi) == np.asarray(i)).all()


@pytest.mark.parametrize("dead", [False, True], ids=["all-live", "tombstoned"])
@pytest.mark.parametrize("Q", [1, 3, 5])
def test_dense_searcher_one_call_a_query_bitwise_vs_ref(Q, dead, monkeypatch):
    """A batch of Q makes exactly Q ``dot_topk`` calls over the matrix the
    searcher placed on the device, and its hits are the reference's over
    the live rows, bit for bit: tombstoned rows are compacted out before
    the zero-row padding."""
    rng = np.random.default_rng(Q)
    n = 70
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    live = np.ones(n, bool)
    if dead:
        live[[0, 3, 4, 41, 69]] = False
    calls = []

    def counted(*args, **kw):
        calls.append(kw.get("n_valid"))
        return dot_topk(*args, **kw)

    ds = DenseSearcher(vecs, [str(i) for i in range(n)], live, CFG)
    monkeypatch.setattr(dot_topk_module, "dot_topk", counted)
    q = rng.standard_normal((Q, DIM)).astype(np.float32)
    hits = ds.search_batch(list(q))
    assert calls == [int(live.sum())] * Q
    wv, wi = dot_topk_batch_ref(q, vecs[live], CFG.k)
    internal = np.flatnonzero(live)
    for qi in range(Q):
        assert [h[0] for h in hits[qi]] == internal[wi[qi]].tolist()
        assert bits([h[1] for h in hits[qi]]) == bits(np.asarray(wv[qi]))


# -- segment level: pack/write/read, quantization, lazy rows --------------------


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_vector_segment_roundtrip(dtype):
    rng = np.random.default_rng(1)
    emb = rng.standard_normal((40, DIM)).astype(np.float32)
    ids = [f"d{i}" for i in range(40)]
    pv = pack_vectors(emb, ids, dtype=dtype)
    d = write_vector_segment(pv)
    back = read_vector_segment(d)
    assert back.meta.doc_ids == ids
    assert back.meta.dtype == dtype
    assert (back.vectors == pv.vectors).all()
    if dtype == "float32":
        assert (back.as_f32() == emb).all()
    else:
        assert pv.vectors.dtype == np.int8
        # symmetric scalar quantization: error bounded by scale/2 per element
        assert np.abs(back.as_f32() - emb).max() <= pv.meta.scale * 0.5 + 1e-7
    # the range-readable twin: superindex header carries the full meta
    meta = unpack_vector_superindex(
        d.open_input("vec_superindex.bin").read_all())
    assert meta.doc_ids == ids and meta.n_docs == 40 and meta.dim == DIM


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_partial_rows_match_eager(dtype):
    rng = np.random.default_rng(2)
    emb = rng.standard_normal((30, DIM)).astype(np.float32)
    pv = pack_vectors(emb, [f"d{i}" for i in range(30)], dtype=dtype)
    d = write_vector_segment(pv)
    part = open_partial_vector_segment(d)
    part.hydrate_rows([(5, 12), (20, 30)])
    assert (part.vectors[5:12] == pv.vectors[5:12]).all()
    assert (part.vectors[20:30] == pv.vectors[20:30]).all()
    assert not part.full
    part.backfill()
    assert part.full and (part.as_f32() == pv.as_f32()).all()


def test_lazy_vectors_pull_only_live_rows():
    """``ensure_live`` hydrates exactly the non-tombstoned rows — dead rows
    never move, and the combined view equals the eager combine."""
    rng = np.random.default_rng(3)
    base = pack_vectors(rng.standard_normal((20, DIM)).astype(np.float32),
                        [f"b{i}" for i in range(20)])
    delta = pack_vectors(rng.standard_normal((7, DIM)).astype(np.float32),
                         [f"x{i}" for i in range(7)])
    tombs = [0, 5, 6, 22]

    def mk(ts):
        return LazyVectors(
            [open_partial_vector_segment(write_vector_segment(p))
             for p in (base, delta)], tombstones=ts)

    lazy, twin = mk(tombs), mk([])
    lazy.ensure_live(), twin.ensure_live()
    vecs, ids, live = lazy.combined()
    evecs, eids, elive = combine_vector_segments([base, delta], tombs)
    assert ids == eids and (live == elive).all()
    assert (vecs[live] == evecs[elive]).all()        # dead rows may stay 0
    # a tombstoned row at a range edge is never ranged in (interior dead
    # rows may ride along when coalescing a small gap is cheaper than a
    # second GET — that is the coalescing model's call, not a leak)
    assert lazy.bytes_read <= twin.bytes_read - DIM * 4


def test_delta_vectors_equal_rebuild():
    """combine(base + deltas, tombstones) == pack of the live corpus: the
    dense tier's delta path can never drift from the one-segment path."""
    rng = np.random.default_rng(4)
    all_emb = rng.standard_normal((25, DIM)).astype(np.float32)
    ids = [f"d{i}" for i in range(25)]
    base = pack_vectors(all_emb[:15], ids[:15])
    d1 = pack_vectors(all_emb[15:20], ids[15:20])
    d2 = pack_vectors(all_emb[20:], ids[20:])
    tombs = [2, 17]
    vecs, got_ids, live = combine_vector_segments([base, d1, d2], tombs)
    keep = [i for i in range(25) if i not in tombs]
    assert [got_ids[i] for i in keep] == [ids[i] for i in keep]
    assert (vecs[live] == all_emb[keep]).all()


# -- fleet level: dense + hybrid vs the oracles ---------------------------------


def fleet_vs_oracles(app, queries, k=10):
    corpus = app.indexer.live_corpus()
    so, do = OracleSearcher(corpus), DenseOracleSearcher(corpus, app.embedder)
    for q in queries:
        s_want = so.search(q, k=app.search_k)
        d_want = do.search(q, k=app.search_k)
        r = app.query(q, k=k, mode="dense",
                      t_arrival=app.runtime.clock + 0.05, fetch_docs=False)
        assert r.body["ext_ids"] == [do.doc_ids[d] for d, _ in d_want[:k]]
        assert bits(r.body["scores"]) == bits([v for _, v in d_want[:k]])
        r = app.query(q, k=k, mode="hybrid",
                      t_arrival=app.runtime.clock + 0.05, fetch_docs=False)
        fused = hybrid_oracle_fuse(s_want, d_want, k)
        assert r.body["ext_ids"] == [so.doc_ids[d] for d, _ in fused]
        assert list(r.body["scores"]) == [v for _, v in fused]
        r = app.query(q, k=k, t_arrival=app.runtime.clock + 0.05,
                      fetch_docs=False)
        assert r.body["ext_ids"] == [so.doc_ids[d]
                                     for d, _ in s_want[:k]]


def test_dense_and_hybrid_match_oracles():
    docs = synth_corpus(150, vocab=300, seed=0)
    app = build_app(docs, n_parts=3)
    fleet_vs_oracles(app, synth_queries(docs, 5, seed=1))


def test_dense_and_hybrid_match_oracles_through_churn():
    """Base + delta + tombstones, across two commits (the second triggers
    whatever merge the policy elects): delta-served dense ranking equals a
    full rebuild, and hybrid fusion stays pinned to the oracle pair."""
    docs = synth_corpus(160, vocab=300, seed=2)
    app = build_app(docs[:120], n_parts=2)
    queries = synth_queries(docs, 4, seed=3)
    fleet_vs_oracles(app, queries[:2])
    app.add_documents(docs[120:140], t_arrival=app.runtime.clock + 0.01)
    app.delete_documents([d for d, _ in docs[0:40:10]],
                         t_arrival=app.runtime.clock + 0.01)
    assert app.commit(t_arrival=app.runtime.clock + 0.01).ok
    fleet_vs_oracles(app, queries)
    app.add_documents(docs[140:], t_arrival=app.runtime.clock + 0.01)
    app.delete_documents([d for d, _ in docs[50:60]],
                         t_arrival=app.runtime.clock + 0.01)
    assert app.commit(t_arrival=app.runtime.clock + 0.01).ok
    fleet_vs_oracles(app, queries)


def test_int8_fleet_matches_oracle_on_dequantized_vectors():
    """The int8 tier scores the DEQUANTIZED representation — the oracle
    must embed the same way to bit-match, so build it over the stored
    codes' f32 view via the fleet's own combine."""
    docs = synth_corpus(90, vocab=200, seed=5)
    app = build_app(docs, n_parts=2, dtype="int8")
    q = synth_queries(docs, 2, seed=6)[0]
    r = app.query(q, k=10, mode="dense",
                  t_arrival=app.runtime.clock + 0.05, fetch_docs=False)
    assert r.ok and len(r.body["ext_ids"]) == 10
    # int8 ranking is close to, but legitimately may differ from, the f32
    # oracle; what must hold exactly is determinism across replays
    r2 = app.query(q, k=10, mode="dense",
                   t_arrival=app.runtime.clock + 0.05, fetch_docs=False)
    assert r.body["ext_ids"] == r2.body["ext_ids"]
    assert bits(r.body["scores"]) == bits(r2.body["scores"])


def test_vector_only_query_and_batched_modes():
    docs = synth_corpus(100, vocab=200, seed=7)
    app = build_app(docs, n_parts=2)
    corpus = app.indexer.live_corpus()
    do = DenseOracleSearcher(corpus, app.embedder)
    qv = [float(x) for x in app.embedder("tail latency")]
    r = app.query(None, k=5, mode="dense", vector=qv,
                  t_arrival=app.runtime.clock + 0.05, fetch_docs=False)
    want = do.search(qv, k=5)
    assert r.body["ext_ids"] == [do.doc_ids[d] for d, _ in want]
    assert bits(r.body["scores"]) == bits([v for _, v in want])
    # a micro-batch of texts through each mode resolves per query
    queries = synth_queries(docs, 3, seed=8)
    for mode in ("dense", "hybrid"):
        r = app.query(queries, k=5, mode=mode,
                      t_arrival=app.runtime.clock + 0.05, fetch_docs=False)
        assert r.ok and len(r.body["results"]) == len(queries)
        for q, res in zip(queries, r.body["results"]):
            one = app.query(q, k=5, mode=mode,
                            t_arrival=app.runtime.clock + 0.05,
                            fetch_docs=False)
            assert res["ext_ids"] == one.body["ext_ids"]
            assert bits(res["scores"]) == bits(one.body["scores"])


def test_windowed_mixed_modes_bitwise_equal_serial():
    """Sparse, dense and hybrid admissions coalescing in ONE gateway window
    must resolve to exactly the serial per-query dispatch — the kernel's
    Q-invariance surfacing at the fleet level."""
    docs = synth_corpus(140, vocab=250, seed=9)
    from repro.core.gateway import WindowPolicy
    from repro.core.partition import GatewaySpec
    app = build_app(docs, n_parts=2, gateway=GatewaySpec(
        window=WindowPolicy(max_window_s=0.08, target_batch=8,
                            sparse_qps=2.0, p99_budget_s=2.0)))
    serial = build_app(docs, n_parts=2)
    queries = synth_queries(docs, 6, seed=10)
    app.warm(), serial.warm()
    t0 = app.runtime.clock + 2.0
    handles = [(q, m, app.submit(q, k=10, mode=m, t_arrival=t0 + i * 0.001,
                                 fetch_docs=False))
               for i, q in enumerate(queries)
               for m in ("sparse", "dense", "hybrid")]
    app.flush()
    for q, m, h in handles:
        want = serial.query(q, k=10, mode=m,
                            t_arrival=serial.runtime.clock + 0.05,
                            fetch_docs=False)
        assert h.response.body["ext_ids"] == want.body["ext_ids"], (q, m)
        assert bits(h.response.body["scores"]) == bits(want.body["scores"])


def test_hybrid_rrf_fusion_is_the_coordinator_rrf():
    """The fused scores ARE rrf_fuse outputs over the two tiers' rankings
    — recomputable from the per-tier responses alone."""
    docs = synth_corpus(80, vocab=150, seed=11)
    app = build_app(docs, n_parts=2)
    q = synth_queries(docs, 1, seed=12)[0]
    rs = app.query(q, k=app.search_k, t_arrival=app.runtime.clock + 0.05,
                   fetch_docs=False)
    rd = app.query(q, k=app.search_k, mode="dense",
                   t_arrival=app.runtime.clock + 0.05, fetch_docs=False)
    rh = app.query(q, k=5, mode="hybrid",
                   t_arrival=app.runtime.clock + 0.05, fetch_docs=False)
    fused = rrf_fuse([list(rs.body["ext_ids"]), list(rd.body["ext_ids"])], 5)
    assert rh.body["ext_ids"] == [d for d, _ in fused]
    assert list(rh.body["scores"]) == [s for _, s in fused]


# -- generations: one manifest flip per commit, no cross-tier skew ---------------


def test_every_commit_flips_every_partition_manifest():
    """A commit routed entirely to one partition still CAS-flips a manifest
    on EVERY partition — the all-or-nothing generation contract the dense
    tier inherits (its vec segments ride the same manifest)."""
    from repro.core.refresh import generation_version
    docs = synth_corpus(60, vocab=150, seed=13)
    app = build_app(docs, n_parts=3)
    gen = app.indexer.gen
    app.add_documents([("zz-one-new-doc", "dense retrieval vector tier")],
                      t_arrival=app.runtime.clock + 0.01)
    assert app.commit(t_arrival=app.runtime.clock + 0.01).ok
    assert app.indexer.gen == gen + 1
    q = synth_queries(docs, 1, seed=14)[0]
    app.query(q, k=5, mode="hybrid", t_arrival=app.runtime.clock + 0.05,
              fetch_docs=False)
    assert app.scatter.last_versions == [generation_version(gen + 1)]
    # the new doc is servable from the dense tier of every generation asset
    r = app.query(None, k=3, mode="dense",
                  vector=[float(x)
                          for x in app.embedder("dense retrieval vector tier")],
                  t_arrival=app.runtime.clock + 0.05, fetch_docs=False)
    assert "zz-one-new-doc" in r.body["ext_ids"]


def test_cross_tier_generation_skew_raises():
    """A leg whose dense tier answered from a different generation than
    the sparse tiers around it must fail the scatter, not fuse."""
    docs = synth_corpus(60, vocab=150, seed=15)
    app = build_app(docs, n_parts=2)
    q = synth_queries(docs, 1, seed=16)[0]
    app.query(q, k=5, mode="hybrid", t_arrival=app.runtime.clock + 0.05,
              fetch_docs=False)
    orig_invoke = app.runtime.invoke
    state = {"armed": True}

    def invoke(fn, payload, **kw):
        result, rec = orig_invoke(fn, payload, **kw)
        if state["armed"] and fn.startswith("search-"):
            state["armed"] = False
            result = dict(result)
            result["vec_version"] = "g999999"       # forged dense tier
        return result, rec

    app.runtime.invoke = invoke
    r = app.query(q, k=5, mode="hybrid", t_arrival=app.runtime.clock + 0.05,
                  fetch_docs=False)
    # the scatter raises GenerationMismatch; the gateway surfaces it as a
    # 502 (the fleet's fault, not the client's) instead of fusing the skew
    assert r.status == 502 and "scatter legs answered from" in r.body["error"]
    assert "g999999" in r.body["error"]


def test_mid_scatter_rollover_pins_both_tiers():
    """A commit landing between two hybrid scatter legs: both tiers of
    every leg answer from the generation pinned at dispatch."""
    from repro.core.refresh import generation_version
    docs = synth_corpus(120, vocab=250, seed=17)
    app = build_app(docs[:100], n_parts=3)
    q = synth_queries(docs, 1, seed=18)[0]
    app.query(q, mode="hybrid", fetch_docs=False)       # hydrate gen 1
    gen_before = app.indexer.gen
    app.add_documents(docs[100:])
    state = {"armed": True}
    orig_invoke = app.runtime.invoke

    def invoke(fn, payload, **kw):
        result = orig_invoke(fn, payload, **kw)
        if state["armed"] and fn.startswith("search-"):
            state["armed"] = False
            r = app.commit()
            assert r.ok and r.body["gen"] == gen_before + 1
        return result

    app.runtime.invoke = invoke
    r = app.query(q, k=10, mode="hybrid", fetch_docs=False)
    assert r.ok
    assert app.scatter.last_versions == [generation_version(gen_before)]
    r2 = app.query(q, k=10, mode="hybrid",
                   t_arrival=app.runtime.clock + 0.05, fetch_docs=False)
    assert r2.ok
    assert app.scatter.last_versions == [generation_version(gen_before + 1)]
    fleet_vs_oracles(app, [q])


def test_sparse_fleet_rejects_dense_modes():
    docs = synth_corpus(40, vocab=100, seed=19)
    app = build_partitioned_search_app(docs, FleetSpec(
        n_parts=2, runtime_config=RuntimeConfig(), search_config=CFG))
    assert app.embedder is None
    q = synth_queries(docs, 1, seed=20)[0]
    r = app.query(q, k=5, mode="dense", fetch_docs=False)
    assert r.status == 400 and "dense" in r.body["error"]
    r = app.query(q, k=5, mode="nonsense", fetch_docs=False)
    assert r.status == 400

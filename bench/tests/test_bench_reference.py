"""The benchmark's reference against the repository's exact oracles, on a
tiny corpus, and the comparison rule that decides ``correct``."""

import numpy as np
import pytest

from bench import reference as ref
from bench.corpus import make_corpus, term_string


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(400, vocab=600, mean_len=20, zipf_a=1.3, seed=5)


def test_bm25_equals_the_oracle_searcher(corpus, src_path):
    from repro.search.oracle import OracleSearcher
    oracle = OracleSearcher(corpus.docs(), k1=0.9, b=0.4)
    rng = np.random.default_rng(3)
    live = np.flatnonzero(~corpus.stop[:600] & (corpus.cf() > 0))
    queries = [rng.choice(live, size=n, replace=False).tolist()
               for n in (1, 2, 3, 4) for _ in range(10)]
    post = ref.build_postings(corpus, {t for q in queries for t in q})
    for q in queries:
        got = ref.bm25_topk(post, q, 10, k1=0.9, b=0.4)
        want = oracle.search(" ".join(term_string(t) for t in q), k=10)
        assert [d for d, _ in got] == [d for d, _ in want]
        assert np.allclose([s for _, s in got], [s for _, s in want],
                           rtol=1e-12, atol=0)


def test_postings_count_documents_not_occurrences(corpus):
    t = int(np.flatnonzero(~corpus.stop & (corpus.cf() > 3))[5])
    post = ref.build_postings(corpus, {t})
    docs, tf = post.lists[t]
    want = [(i, text.split().count(term_string(t)))
            for i, text in enumerate(corpus.texts)
            if term_string(t) in text.split()]
    assert list(zip(docs.tolist(), tf.tolist())) == want
    assert post.df(t) == len(want)


def test_dense_equals_brute_force():
    rng = np.random.default_rng(1)
    vecs = rng.standard_normal((700, 24)).astype(np.float32)
    q = rng.standard_normal(24).astype(np.float32)
    exact = vecs.astype(np.float64) @ q.astype(np.float64)
    order = np.lexsort((np.arange(700), -exact))[:10]
    assert ref.dense_topk(vecs, q, 10) == [(int(i), float(exact[i]))
                                           for i in order]


def test_rrf_equals_the_fleets_fusion(src_path):
    from repro.core.partition import rrf_fuse
    rng = np.random.default_rng(2)
    for _ in range(20):
        a = rng.choice(50, 10, replace=False).tolist()
        b = rng.choice(50, 10, replace=False).tolist()
        assert ref.rrf([a, b], 10) == rrf_fuse([a, b], 10)


def test_compare_rule():
    want = [(1, 10.0), (2, 9.0), (3, 8.999999), (4, 5.0)]
    exact = ref.compare([(1, 10.0), (2, 9.0), (3, 8.999999)], want, 3, 1e-5)
    assert (exact.id_mismatch, exact.rel_err) == (0, 0.0)
    # a near-tie may come in either order
    swapped = ref.compare([(1, 10.0), (3, 9.0), (2, 8.999999)], want, 3, 1e-5)
    assert swapped.id_mismatch == 0
    # a doc that is no near-tie of the rank is wrong
    wrong = ref.compare([(1, 10.0), (4, 9.0), (3, 8.999999)], want, 3, 1e-5)
    assert wrong.id_mismatch == 1
    off = ref.compare([(1, 10.001), (2, 9.0), (3, 8.999999)], want, 3, 1e-5)
    assert off.rel_err == pytest.approx(1e-4)
    assert not off.ok(1e-5) and exact.ok(1e-5)
    short = ref.compare([(1, 10.0)], want, 3, 1e-5)
    assert short.id_mismatch == 2

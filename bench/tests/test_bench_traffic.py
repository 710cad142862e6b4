"""The generator: corpus and schedule are reproducible from the seed, and
every seed gets the same amount of work."""

import dataclasses

import numpy as np

from bench.corpus import draw as make_draw
from bench.corpus import make_corpus, spell, term_string
from bench.tests.conftest import SEED
from bench.traffic import Mix, make_schedule

MIX = Mix(rate_qps=40.0, terms_per_query=[1, 2, 3, 4], max_cf=60,
          mode="sparse", k=10, fetch_docs=True)


def small(seed):
    return make_corpus(2000, vocab=1 << 12, mean_len=30, zipf_a=1.3,
                       seed=seed)


def test_corpus_is_synth_corpus_and_its_analyzed_lengths(src_path):
    from repro.data.corpus import synth_corpus
    from repro.index.tokenizer import tokenize
    draw = spell(*make_draw(1500, vocab=1 << 19, mean_len=60, zipf_a=1.3,
                            seed=SEED), 1 << 19)
    assert draw.docs() == synth_corpus(1500, vocab=1 << 19, seed=SEED)
    c = make_corpus(1500, vocab=1 << 19, mean_len=60, zipf_a=1.3, seed=SEED,
                    n_parts=4)
    assert c.doc_len().tolist() == [len(tokenize(t)) for t in c.texts]
    assert {term_string(int(t)) for t in np.flatnonzero(c.stop)} <= {
        "be", "no", "to"}


def test_seeds_change_the_texts_not_the_index_shapes():
    def shapes(c, n_parts=4):
        """Per partition: the sorted document lengths and the sorted
        document frequencies of its indexed terms."""
        per = -(-c.n_docs // n_parts)
        doc = np.repeat(np.arange(c.n_docs), np.diff(c.starts))
        keep = ~c.stop[c.tids]
        pairs = np.unique(np.stack([doc[keep], c.tids[keep]]), axis=1)
        dl, out = c.doc_len(), []
        for p in range(n_parts):
            mine = pairs[1][pairs[0] // per == p]
            df = np.bincount(mine, minlength=c.vocab)
            out.append((sorted(dl[p * per:(p + 1) * per].tolist()),
                        sorted(df[df > 0].tolist())))
        return out

    a = make_corpus(999, vocab=1 << 12, mean_len=30, zipf_a=1.3, seed=SEED,
                    n_parts=4)
    b = make_corpus(999, vocab=1 << 12, mean_len=30, zipf_a=1.3, seed=3,
                    n_parts=4)
    assert a.texts != b.texts
    assert shapes(a) == shapes(b)


def test_schedule_is_reproducible_from_the_seed():
    c = small(SEED)
    a = make_schedule(MIX, c, 10.0, SEED)
    b = make_schedule(MIX, small(SEED), 10.0, SEED)
    assert np.array_equal(a.due, b.due) and a.queries == b.queries


def test_seeds_change_the_order_not_the_amount_of_work():
    def work(c, s, n_parts=4):
        """Each query's terms as their document frequencies per partition,
        in a canonical order."""
        per = -(-c.n_docs // n_parts)
        doc = np.repeat(np.arange(c.n_docs), np.diff(c.starts))
        d, t = np.unique(np.stack([doc, c.tids]), axis=1)
        df = np.zeros((c.vocab, n_parts), np.int64)
        np.add.at(df, (t, d // per), 1)
        return sorted(sorted(tuple(df[t]) for t in q)
                      for q in s.query_tids)

    seeds = (7, 2**33 + 1)
    cs = [make_corpus(999, vocab=1 << 12, mean_len=30, zipf_a=1.3, seed=x,
                      n_parts=4) for x in seeds]
    a, b = (make_schedule(MIX, c, 10.0, x) for c, x in zip(cs, seeds))
    assert len(a) == len(b) == 400
    assert not np.array_equal(a.due, b.due)
    gaps = lambda s: np.diff(np.append(s.due, 10.0))  # noqa: E731
    assert np.allclose(np.sort(gaps(a)), np.sort(gaps(b)))
    assert gaps(a)[-1] == gaps(a).max() and gaps(b)[-1] == gaps(b).max()
    assert a.due[0] == 0.0 and a.due[-1] < 10.0
    assert a.queries != b.queries
    assert work(cs[0], a) == work(cs[1], b)


def test_queries_obey_the_mix():
    c = small(9)
    s = make_schedule(dataclasses.replace(MIX, rate_qps=20.0), c, 5.0, 9)
    cf = c.cf()
    for text, tids in zip(s.queries, s.query_tids):
        assert len(set(tids)) == len(tids)
        assert all(cf[t] <= MIX.max_cf and not c.stop[t] for t in tids)
        assert text == " ".join(term_string(t) for t in tids)


def test_max_in_span():
    s = make_schedule(MIX, small(3), 10.0, 3)
    brute = max(int(((s.due >= t) & (s.due < t + 0.05)).sum())
                for t in s.due)
    assert s.max_in_span(0.05) == brute


def test_every_seed_warms_shapes_that_hold_its_windows():
    from types import SimpleNamespace

    from bench.run import batch_bound
    policy = SimpleNamespace(max_window_s=0.05, max_batch=64)
    bound = batch_bound(60.0, 51.0, policy)
    c = small(5)
    mix = dataclasses.replace(MIX, rate_qps=60.0)
    for seed in range(12):
        s = make_schedule(mix, c, 51.0, SEED + seed)
        assert s.max_in_span(policy.max_window_s) <= bound
    assert bound < policy.max_batch

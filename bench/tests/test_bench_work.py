"""Work counts come from the traffic and the corpus alone."""

import pytest

from bench import reference as ref
from bench import work
from bench.corpus import make_corpus, term_string


def test_bm25_bytes_count_every_queried_posting_once():
    c = make_corpus(1000, vocab=300, mean_len=15, zipf_a=1.3, seed=4)
    queries = [[20, 31], [45], [20, 60, 77, 90]]
    post = ref.build_postings(c, {t for q in queries for t in q})
    words = [set(t.split()) for t in c.texts]
    per = 250
    want = 0
    for q in queries:
        for t in q:
            for p in range(4):
                want += 9 * sum(term_string(t) in w
                                for w in words[p * per:(p + 1) * per])
        want += 4 * 10 * 8
    assert work.bm25_bytes(post, queries, 4, 10) == want


def test_partition_sizes_are_contiguous_ceilings():
    assert work.partition_sizes(10, 4) == [3, 3, 3, 1]
    assert work.partition_sizes(8, 4) == [2, 2, 2, 2]


def test_dense_work_reads_each_matrix_once_per_batch():
    got = work.dense_work([1, 3], [100, 50], 8, 10)
    assert got == [((100 + 1) * 32 + 80, 2 * 1 * 100 * 8),
                   ((50 + 1) * 32 + 80, 2 * 1 * 50 * 8),
                   ((100 + 3) * 32 + 240, 2 * 3 * 100 * 8),
                   ((50 + 3) * 32 + 240, 2 * 3 * 50 * 8)]


def test_least_time_is_the_larger_bound():
    peak = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    assert work.least_time(2e9, 1e12, peak) == pytest.approx(2.0)
    assert work.least_time(1e6, 3e12, peak) == pytest.approx(3.0)

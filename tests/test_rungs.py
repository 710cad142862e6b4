"""The BM25 step's (T, M) ladder: a call runs the smallest rung that holds
its batch — the columns that hold a term, and the most blocks any of the
batch's terms has in the partition — and answers bit for bit as the full
(max_terms, max_blocks) program does, with a tail and under the pruned
reference too. Every rung of a batch size runs at its first call, so a
later batch that needs another rung compiles nothing.

The index packs 8-posting blocks, so that a corpus of a few hundred
documents holds terms of more than 16 and more than 64 blocks.
"""

import dataclasses
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.corpus import synth_corpus
from repro.index.builder import IndexWriter
from repro.index.tokenizer import tokenize
from repro.search import searcher as S
from repro.search.bm25 import (batch_need, choose_rung, encode_queries,
                               jitted_search_fn, rung_ladder)
from repro.search.searcher import HostTail, SearchConfig, Searcher

K = 10
BLOCK = 8


@pytest.fixture(scope="module")
def corpus():
    return synth_corpus(620, vocab=700, seed=91)


@pytest.fixture(scope="module")
def packed(corpus):
    w = IndexWriter(block=BLOCK)
    w.add_many(corpus[:600])
    return w.pack()


@pytest.fixture(scope="module")
def words(packed):
    """Terms by their block count: ``short`` ones of 2–16 blocks (the
    last of exactly 16), ``long`` ones of more than 16, ``huge`` ones of
    more than 64."""
    to = packed.term_offsets
    n_blk = {t: int(to[i + 1] - to[i]) for t, i in packed.vocab.items()}
    short = sorted((t for t, n in n_blk.items() if 2 <= n < 16),
                   key=lambda t: (-n_blk[t], t))[:24]
    short += [min(t for t, n in n_blk.items() if n == 16)]
    long = sorted(t for t, n in n_blk.items() if 16 < n <= 64)
    huge = sorted(t for t, n in n_blk.items() if n > 64)
    assert len(short) == 25 and long and huge
    return {"short": short, "long": long, "huge": huge}


def batches(w):
    s, L, H = w["short"], w["long"], w["huge"]
    return {
        "short": ([f"{s[0]} {s[1]}", s[2], " ".join(s[3:6]), s[-1]],
                  "small"),
        "five_terms": ([" ".join(s[:5])], "full"),
        "many_blocks": ([L[0]], "full"),
        "past_max_blocks": ([f"{H[0]} {s[0]}"], "full"),
        "idf_cut": ([" ".join(s[:20])], "full"),
        "empty_padded": (["", "zzqnothere qqqnope", s[0]], "small"),
        "all_empty": (["", "zzqnothere"], "small"),
        "mixed": ([s[0], f"{s[1]} {s[2]}", f"{L[0]} {s[3]}", ""], "full"),
    }


def with_tail(searcher: Searcher, packed, extra) -> None:
    """Give ``searcher`` a derived generation's state: ``extra`` documents
    as a tail (new words take ids past the fixed layout, whose idf is
    padded) and two base documents deleted."""
    vocab = dict(packed.vocab)
    rows = []
    for i, (_, text) in enumerate(extra):
        for tok, c in Counter(tokenize(text + " tailword")).items():
            rows.append((i, vocab.setdefault(tok, len(vocab)), c))
    docs, terms, tfs = (np.asarray(c, np.int64) for c in zip(*rows))
    lens = np.asarray([len(tokenize(t)) + 1 for _, t in extra], np.float32)
    tail = HostTail.empty().extend(docs, terms, tfs, lens,
                                   [d for d, _ in extra])
    idf = np.concatenate([packed.idf, np.full(len(vocab) - len(packed.idf),
                                              1.5, np.float32)])
    doc_len = packed.doc_len.copy()
    doc_len[[3, 17]] = np.inf
    searcher.vocab, searcher.host_idf = vocab, idf
    searcher.state = dataclasses.replace(
        searcher.state, idf=jnp.asarray(idf), doc_len=jnp.asarray(doc_len),
        tail=tail.to_device())


def record_rungs(searcher: Searcher) -> list:
    """Wrap the searcher's programs so that each call notes its rung."""
    ran = []

    def wrap(rung, fn):
        def call(*args):
            ran.append(rung)
            return fn(*args)
        return call

    searcher._rungs = {r: wrap(r, fn) for r, fn in searcher._rungs.items()}
    return ran


def needed(searcher: Searcher, queries: list[str], cfg) -> tuple[int, int]:
    """(T, M) the batch needs, counted term by term: distinct terms a
    query keeps (at most ``max_terms``), blocks of each in the layout (a
    term past it has none)."""
    tids, _ = encode_queries(searcher.vocab, queries, max_terms=cfg.max_terms,
                             idf=searcher.host_idf)
    to = searcher.term_offsets
    t = max(int((row >= 0).sum()) for row in tids)
    m = max([int(to[i + 1] - to[i]) for i in tids[tids >= 0]
             if i < len(to) - 1] + [0])
    return t, m


@pytest.mark.parametrize("mode", ["dense", "tail", "pruned"])
@pytest.mark.parametrize("case", ["short", "five_terms", "many_blocks",
                                  "past_max_blocks", "idf_cut",
                                  "empty_padded", "all_empty", "mixed"])
def test_rung_answers_bit_for_bit_as_the_full_program(
        packed, corpus, words, case, mode):
    cfg = SearchConfig(k=K, accumulator="pruned" if mode == "pruned"
                       else "dense")
    s = Searcher(packed, cfg)
    if mode == "tail":
        with_tail(s, packed, corpus[600:])
    queries, label = batches(words)[case]
    if mode == "tail":
        queries = queries + ["tailword", f"{words['short'][0]} tailword"]
    ran = record_rungs(s)
    vals, ids = s.search(queries)

    ladder = rung_ladder(cfg.max_terms, cfg.max_blocks)
    t, m = needed(s, queries, cfg)
    want = next((r for r in ladder if r[0] >= t and r[1] >= m), ladder[-1])
    assert ran[0] == want
    assert want == (ladder[0] if label == "small" else ladder[-1])

    Qp = 1 << max(0, (len(queries) - 1).bit_length())
    tids, qtf = encode_queries(s.vocab, queries + [""] * (Qp - len(queries)),
                               max_terms=cfg.max_terms, idf=s.host_idf)
    full = jitted_search_fn(packed.meta.n_docs, max_terms=cfg.max_terms,
                            max_blocks=cfg.max_blocks, k=K,
                            accumulator=cfg.accumulator, use_kernel=False,
                            use_topk_kernel=False)
    fv, fi = (np.asarray(x)[:len(queries)] for x in full(s.state, tids, qtf))
    assert np.array_equal(vals.view(np.uint32), fv.view(np.uint32))
    assert np.array_equal(ids, fi)
    if case != "all_empty":
        assert (vals > 0).any()


class Compiles:
    """Backend compiles while in the block, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, duration, **_):
        self.n += event == self.EVENT

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


def test_first_call_of_a_batch_size_runs_every_rung_and_the_twins(
        corpus, words):
    # a partition size of its own, so no other test ran these programs
    w = IndexWriter(block=BLOCK)
    w.add_many(corpus[:599])
    packed = w.pack()
    cfg = SearchConfig(k=K)
    s = Searcher(packed, cfg)
    derived = Searcher(packed, cfg)
    with_tail(derived, packed, corpus[600:])
    s.twin = derived.state
    short, mixed = batches(words)["short"][0], batches(words)["mixed"][0]

    small, full = list(s._rungs)
    for queries, rung in ((short[:3], small), (mixed, full)):
        tids, _ = encode_queries(s.vocab, queries, max_terms=cfg.max_terms,
                                 idf=s.host_idf)
        assert choose_rung([small, full],
                           batch_need(tids, s.term_offsets)) == rung

    s.search(short[:3])                 # Qp = 4, the small rung serves
    for fn in s._rungs.values():
        for state in (s.state, s.twin):
            assert (id(fn), 4, S._signature(state)) in S._WARMED
    with Compiles() as c:
        s.search(mixed)                 # Qp = 4, the full rung serves
        derived.search(mixed)
        derived.search(short)
        assert derived.warm_programs() == 0
    assert c.n == 0
    with Compiles() as c:
        s.search(mixed[:2])             # Qp = 2, a shape not yet run
    assert c.n > 0

"""The plain reference: exact BM25 and exact inner products, from the
benchmark's own token ids and vectors, and the rule that compares a served
answer with it.

BM25 is Anserini's variant as the deployment states it (no (k1 + 1)
numerator, tf clamped to 255):

    idf(t)   = ln(1 + (N - df + 0.5) / (df + 0.5))
    score    = sum over query terms of qtf * idf * tf / (tf + k1 * (1 - b + b * dl / avgdl))

Only the posting lists of the terms that the traffic queries are built,
straight from the token ids, so the reference costs seconds. Everything
here is float64 numpy; ``bench/control.py`` computes the same in bfloat16.
"""

from __future__ import annotations

import dataclasses

import numpy as np

TF_CAP = 255
RRF_C = 60.0


@dataclasses.dataclass
class Postings:
    """Posting lists of a set of terms: term id -> (doc ids ascending, tf)."""

    lists: dict[int, tuple[np.ndarray, np.ndarray]]
    dl: np.ndarray            # (N,) analyzed document lengths
    avgdl: float
    n_docs: int

    def df(self, tid: int) -> int:
        return len(self.lists[tid][0])


def build_postings(corpus, tids: set[int]) -> Postings:
    dl = corpus.doc_len()
    want = np.zeros(corpus.vocab, bool)
    want[np.fromiter(tids, np.int64, len(tids))] = True
    pos = np.flatnonzero(want[corpus.tids])
    doc = np.searchsorted(corpus.starts, pos, side="right") - 1
    key = corpus.tids[pos] * corpus.n_docs + doc
    key, tf = np.unique(key, return_counts=True)
    term, doc = np.divmod(key, corpus.n_docs)
    lists = {}
    bounds = np.flatnonzero(np.diff(term)) + 1
    for sl_t, sl_d, sl_f in zip(np.split(term, bounds), np.split(doc, bounds),
                                np.split(tf, bounds)):
        if len(sl_t):
            lists[int(sl_t[0])] = (sl_d, np.minimum(sl_f, TF_CAP))
    for t in tids:
        lists.setdefault(int(t), (np.zeros(0, np.int64), np.zeros(0, np.int64)))
    n = corpus.n_docs
    return Postings(lists=lists, dl=dl, avgdl=float(dl.sum()) / n, n_docs=n)


def bm25_topk(post: Postings, query_tids: list[int], k: int, *, k1: float,
              b: float, depth: int = 0
              ) -> list[tuple[int, float]]:
    """Top ``k + depth`` (doc, score) of one query, ordered by score
    descending then doc ascending. ``depth`` extra ranks let the rule see
    the docs that tie with the last rank."""
    qtf: dict[int, int] = {}
    for t in query_tids:
        qtf[t] = qtf.get(t, 0) + 1
    docs = [post.lists[t][0] for t in qtf]
    if not any(len(d) for d in docs):
        return []
    all_docs = np.concatenate(docs)
    uniq, inv = np.unique(all_docs, return_inverse=True)
    acc = np.zeros(len(uniq))
    at = 0
    for t, c in qtf.items():
        d, tf = post.lists[t]
        df = len(d)
        idf = np.log(1.0 + (post.n_docs - df + 0.5) / (df + 0.5))
        norm = k1 * (1.0 - b + b * post.dl[d] / post.avgdl)
        np.add.at(acc, inv[at: at + df], c * idf * tf / (tf + norm))
        at += df
    order = np.lexsort((uniq, -acc))[: k + depth]
    return [(int(uniq[i]), float(acc[i])) for i in order]


def dense_topk(vectors: np.ndarray, qv: np.ndarray, k: int, *,
               depth: int = 0) -> list[tuple[int, float]]:
    """Top ``k + depth`` (row, inner product) over all rows; ties by row.
    The rows are shortlisted in f32, far deeper than f32 rounding can
    reorder, and the shortlist is scored exactly in f64."""
    rough = vectors @ qv
    cand = np.argpartition(-rough, min(len(rough) - 1, 256))[:257]
    exact = vectors[cand].astype(np.float64) @ qv.astype(np.float64)
    order = np.lexsort((cand, -exact))[: k + depth]
    return [(int(cand[i]), float(exact[i])) for i in order]


def rrf(rankings: list[list[int]], k: int) -> list[tuple[int, float]]:
    """Reciprocal Rank Fusion (Cormack et al. 2009, c = 60): a key scores
    the sum of 1 / (c + rank) over the rankings it is in, tiers added in
    the order given; ties by key."""
    scores: dict[int, float] = {}
    for ranking in rankings:
        for rank, key in enumerate(ranking, start=1):
            scores[key] = scores.get(key, 0.0) + 1.0 / (RRF_C + rank)
    return sorted(scores.items(), key=lambda kv: (-kv[1], kv[0]))[:k]


@dataclasses.dataclass
class Tally:
    """Worst readings of one comparison over many answers."""

    rel_err: float = 0.0        # largest relative score error at a rank
    id_mismatch: int = 0        # ranks whose doc is neither the reference's
                                # nor a near-tie of it
    answers: int = 0

    def ok(self, rtol: float) -> bool:
        return not self.id_mismatch and self.rel_err <= rtol

    def add(self, other: "Tally") -> None:
        self.rel_err = max(self.rel_err, other.rel_err)
        self.id_mismatch += other.id_mismatch
        self.answers += other.answers


def compare(got: list[tuple[int, float]], want: list[tuple[int, float]],
            k: int, tie_rtol: float) -> Tally:
    """One served top-k against the reference's top (k + depth).

    Scores are compared rank for rank. A served doc that is not the
    reference's doc at that rank still counts as right when its reference
    score is within ``tie_rtol`` (relative) of that rank's: a near-tie,
    which f32 arithmetic may order either way."""
    t = Tally(answers=1)
    head = want[:k]
    if len(got) != len(head):
        t.id_mismatch += abs(len(got) - len(head))
    ref_score = dict(want)
    for (d_got, s_got), (d_want, s_want) in zip(got, head):
        denom = abs(s_want) or 1.0
        t.rel_err = max(t.rel_err, abs(s_got - s_want) / denom)
        if d_got != d_want:
            s_ref = ref_score.get(d_got)
            if s_ref is None or abs(s_ref - s_want) > tie_rtol * denom:
                t.id_mismatch += 1
    if len({d for d, _ in got}) != len(got):
        t.id_mismatch += 1
    return t

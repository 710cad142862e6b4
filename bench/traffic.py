"""The one traffic generator: turns a mix file's parameters and the seed
into an open-loop arrival schedule and the query each arrival sends.

A mix file (``bench/traffic/<mix>.json``) holds only numbers and names:

* ``rate_qps``: Poisson arrival rate, fixed in the file (never searched);
* ``terms_per_query``: the query lengths, in equal shares;
* ``max_cf``: a term is eligible only if it occurs at most this often in
  the initial index (so its document frequency is at most this too);
* ``mode``: ``sparse`` or ``hybrid``; ``k`` and ``fetch_docs`` as sent;
* optional writes (absent: the mix writes nothing): ``writes_per_s``, how
  many writes a second are sent; ``docs_per_write``, the documents each
  carries; ``delete_share``, the share of writes that delete documents
  instead of adding them.

Every seed gets the same set of inter-arrival gaps (the exponential
distribution's quantiles at the rate), in an order drawn from the seed but
for the longest, which comes last, so that the window's last arrivals have
the most time to be answered before it closes. Every seed gets the same
queries of the corpus's shape draw (``bench/corpus.py``): drawn once from
a fixed seed, then spelled in the seed's term labels and sent in an order
drawn from the seed. Their terms have the same document frequencies in
every partition whatever the seed, so seeds change which work arrives
when, and the texts, not how much work there is.

Writes (``make_writes``) come from streams of their own, so a mix without
them, or with them, leaves the seed's arrivals and queries as they were.
They are due at evenly spaced times, and which of them delete is fixed by
``delete_share``, the same for every seed. An add carries the next
``docs_per_write`` of the corpus's held-back documents (``n_extra``, the
shape draw's own, spelled in the seed's labels) in a fixed order; a delete
names documents of the initial index: a fixed draw of shape documents,
which the seed's shuffle places at other ids, one partition after another.
So every seed adds and deletes documents of the same shapes in the same
partitions at the same times, and the index every refresh publishes has
the same shapes whatever the seed.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

from bench.corpus import SHAPE_SEED, term_string


@dataclasses.dataclass
class Mix:
    rate_qps: float
    terms_per_query: list[int]
    max_cf: int
    mode: str
    k: int
    fetch_docs: bool
    writes_per_s: float = 0.0
    docs_per_write: int = 1
    delete_share: float = 0.0

    @classmethod
    def load(cls, path: Path) -> "Mix":
        """The mix a file states; an optional field it leaves out takes its
        default, and a key that names no field is not read."""
        raw = json.loads(Path(path).read_text())
        return cls(**{f.name: raw[f.name] for f in dataclasses.fields(cls)
                      if f.name in raw})

    @property
    def writes(self) -> bool:
        return self.writes_per_s > 0

    def n_writes(self, seconds: float) -> int:
        return int(round(self.writes_per_s * seconds)) if self.writes else 0

    def n_deletes(self, seconds: float) -> int:
        return int(np.floor(self.n_writes(seconds) * self.delete_share))

    def n_added_docs(self, seconds: float) -> int:
        """Documents the window's adds carry: the corpus holds them back."""
        return ((self.n_writes(seconds) - self.n_deletes(seconds))
                * self.docs_per_write)


@dataclasses.dataclass
class Schedule:
    due: np.ndarray                 # (n,) seconds after the window opens
    queries: list[str]
    query_tids: list[list[int]]

    def __len__(self) -> int:
        return len(self.queries)

    def max_in_span(self, span_s: float) -> int:
        """Most arrivals due inside any interval [t, t + span_s)."""
        if not len(self.due):
            return 0
        ends = np.searchsorted(self.due, self.due + span_s, side="left")
        return int((ends - np.arange(len(self.due))).max())


@dataclasses.dataclass
class Writes:
    due: np.ndarray                 # (n,) seconds after the window opens
    delete: np.ndarray              # (n,) bool: deletes, else adds
    docs: list[list[int]]           # corpus document ids each write names

    def __len__(self) -> int:
        return len(self.due)


def arrival_times(rate_qps: float, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    n = max(1, int(round(rate_qps * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)      # ascending
    gaps = np.append(rng.permutation(gaps[:-1]), gaps[-1])
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def make_schedule(mix: Mix, corpus, seconds: float, seed: int) -> Schedule:
    """The seed's arrivals and queries. Each query's terms are those of a
    document of the shape draw that ``draw`` picks (fixed), spelled in the
    seed's labels; ``rng`` orders the arrivals and the queries."""
    rng = np.random.default_rng([seed, 0x7AFF1C])
    draw = np.random.default_rng([SHAPE_SEED, 0x7AFF1C])
    due = arrival_times(mix.rate_qps, seconds, rng)
    lengths = np.resize(np.asarray(mix.terms_per_query), len(due))
    eligible = ~corpus.stop & (corpus.cf() <= mix.max_cf)
    n = corpus.n_base
    where = np.empty(n, np.int64)
    where[corpus.shape_doc[:n]] = np.arange(n)
    queries, tids = [], []
    for n_terms in lengths.tolist():
        while True:
            d = int(where[draw.integers(n)])
            toks = corpus.tids[corpus.starts[d]: corpus.starts[d + 1]]
            cand = np.unique(toks[eligible[toks]])
            if len(cand) >= n_terms:
                break
        cand = cand[np.argsort(corpus.shape_tid[cand])]
        pick = draw.choice(cand, size=n_terms, replace=False).tolist()
        tids.append([int(t) for t in pick])
        queries.append(" ".join(term_string(t) for t in pick))
    order = rng.permutation(len(due))
    return Schedule(due=due, queries=[queries[i] for i in order],
                    query_tids=[tids[i] for i in order])


def make_writes(mix: Mix, corpus, seconds: float, n_parts: int) -> Writes:
    """The window's writes (none where the mix has none). The adds carry
    the held-back documents ``n_base ..`` in order; the deletes name
    distinct documents of the initial index, taken from each of the
    ``n_parts`` contiguous partitions in turn."""
    n = mix.n_writes(seconds)
    if not n:
        return Writes(due=np.zeros(0), delete=np.zeros(0, bool), docs=[])
    due = (np.arange(n) + 0.5) * (seconds / n)
    n_del = mix.n_deletes(seconds)
    delete = np.diff(np.floor(np.arange(n + 1) * (n_del / n))) > 0
    if corpus.n_extra != (n - n_del) * mix.docs_per_write:
        raise ValueError(f"the corpus holds back {corpus.n_extra} documents, "
                         f"the mix adds {(n - n_del) * mix.docs_per_write}")
    # the shape documents to delete: a fixed draw, partition by partition
    draw = np.random.default_rng([SHAPE_SEED, 0xDE1E7E])
    n_base, per = corpus.n_base, -(-corpus.n_base // n_parts)
    want = n_del * mix.docs_per_write
    picks = []
    for p in range(n_parts):
        lo, hi = p * per, min(n_base, (p + 1) * per)
        m = want // n_parts + (p < want % n_parts)
        picks.append((lo + draw.choice(hi - lo, size=m, replace=False)
                      ).tolist())
    shape = [x[i] for i in range(max(map(len, picks))) for x in picks
             if i < len(x)]
    where = np.empty(n_base, np.int64)
    where[corpus.shape_doc[:n_base]] = np.arange(n_base)
    gone = where[shape].tolist()
    added = iter(range(n_base, corpus.n_docs))
    docs, at = [], 0
    for d in delete.tolist():
        if d:
            docs.append(gone[at: at + mix.docs_per_write])
            at += mix.docs_per_write
        else:
            docs.append([next(added) for _ in range(mix.docs_per_write)])
    return Writes(due=due, delete=delete, docs=docs)

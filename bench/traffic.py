"""The one traffic generator: turns a mix file's parameters and the seed
into an open-loop arrival schedule and the query each arrival sends.

A mix file (``bench/traffic/<mix>.json``) holds only numbers and names:

* ``rate_qps``: Poisson arrival rate, fixed in the file (never searched);
* ``terms_per_query``: the query lengths, in equal shares;
* ``max_cf``: a term is eligible only if it occurs at most this often in
  the whole corpus (so its document frequency is at most this too);
* ``mode``: ``sparse`` or ``hybrid``; ``k`` and ``fetch_docs`` as sent.

Every seed gets the same set of inter-arrival gaps (the exponential
distribution's quantiles at the rate), in an order drawn from the seed but
for the longest, which comes last, so that the window's last arrivals have
the most time to be answered before it closes. Every seed gets the same
queries of the corpus's shape draw (``bench/corpus.py``): drawn once from
a fixed seed, then spelled in the seed's term labels and sent in an order
drawn from the seed. Their terms have the same document frequencies in
every partition whatever the seed, so seeds change which work arrives
when, and the texts, not how much work there is.
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

    @classmethod
    def load(cls, path: Path) -> "Mix":
        raw = json.loads(Path(path).read_text())
        return cls(**{f.name: raw[f.name] for f in dataclasses.fields(cls)})


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
    n = corpus.n_docs
    where = np.empty(n, np.int64)
    where[corpus.shape_doc] = np.arange(n)
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

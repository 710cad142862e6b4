"""JAX BM25 query evaluation over the packed blocked index.

Fixed-shape, jit-compatible score-at-a-time evaluation:

* gather the first M (impact-ordered) blocks of each of the query's T terms,
* compute per-posting BM25 impacts (optionally through the Pallas kernel),
* accumulate per-document scores, two strategies:
    - ``dense``  : scatter-add into a (Q, n_docs+1) accumulator. Simple,
                   exact, HBM-heavy for big corpora.
    - ``sorted`` : sort the (doc, impact) pairs and segment-sum via the
                   cummax prefix trick — no dense accumulator; memory scales
                   with T·M·B instead of n_docs. TPU-friendly for huge
                   corpora / many concurrent queries.
    - ``pruned`` : block-max WAND — skip whole blocks whose score ceiling
                   (``qtf·block_max`` plus every other term's first-block
                   ceiling) cannot reach a k-th-best lower bound θ taken
                   from the always-scored first blocks. Fused single-pass
                   Pallas kernel (``kernels/bm25_pruned.py``) or a pure-JAX
                   reference with the identical keep mask.
* top-k over accumulated scores.

T and M are the shape of one compiled program. A call runs the smallest
rung of a fixed ladder (:func:`rung_ladder`) that holds its batch's terms
and their blocks (:func:`batch_need`); a smaller rung gathers the same
postings in the same order and leaves out only invalid ones, which add
0.0, so it scores and ranks bit for bit as the full shape does.

A state derived for a later index generation (``SearchState.tail``, dense
accumulator only) scores its base segment as above and the documents its
commits added since — the TAIL — in the same call: a small forward index,
one row of (term, tf) slots a document, matched against the query's terms
and written into the same accumulator after the base's documents. Deleted
documents carry an infinite length, so they score 0 in either part.

All strategies must agree with :class:`repro.search.oracle.OracleSearcher`
whenever M·B covers every posting of every query term (tests enforce this);
``pruned`` must be BIT-identical — it only skips blocks provably unable to
enter the top-k, and ties break exactly like ``lax.top_k``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.index.builder import PackedIndex


TAIL_MIN_DOCS = 1024   # the smallest tail capacity (a power of two)
TAIL_MIN_SLOTS = 128   # the smallest row width: distinct terms a document


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TailState:
    """The documents a generation's commits added since its base segment,
    as a forward index on the device: row d holds document d's distinct
    terms (``terms``, -1 = empty slot) and their tfs. ``doc_len`` is
    infinite for a deleted or empty row, so it scores 0. Both widths are
    powers of two with a floor (``TAIL_MIN_DOCS`` rows, ``TAIL_MIN_SLOTS``
    slots) and only grow, so that a run of generations shares one
    compiled program."""

    terms: jax.Array          # (cap, L) int32
    tf: jax.Array             # (cap, L) uint8
    doc_len: jax.Array        # (cap,) float32

    def tree_flatten(self):
        return (self.terms, self.tf, self.doc_len), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)

    @property
    def cap(self) -> int:
        return self.terms.shape[0]


def pow2_at_least(n: int, floor: int) -> int:
    out = floor
    while out < n:
        out *= 2
    return out


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SearchState:
    """Device-resident index arrays (the hydrated 'warm' state). A state
    derived for a later generation adds a ``tail`` and gives deleted base
    documents an infinite ``doc_len``; one hydrated from a whole segment
    set has no tail and today's shapes."""

    term_offsets: jax.Array   # (V+1,) int32
    block_docs: jax.Array     # (NB, B) int32
    block_tf: jax.Array       # (NB, B) uint8
    block_max: jax.Array      # (NB,) float32 — per-block max impact
    doc_len: jax.Array        # (n_docs+1,) float32
    idf: jax.Array            # (V,) float32
    avgdl: jax.Array          # () float32
    k1: jax.Array             # () float32
    b: jax.Array              # () float32
    n_docs: int               # static
    tail: TailState | None = None

    def tree_flatten(self):
        leaves = (self.term_offsets, self.block_docs, self.block_tf,
                  self.block_max, self.doc_len, self.idf, self.avgdl,
                  self.k1, self.b, self.tail)
        return leaves, self.n_docs

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        *arrays, tail = leaves
        return cls(*arrays, n_docs=aux, tail=tail)

    @property
    def n_slots(self) -> int:
        """Accumulator width: base documents, then the tail's capacity."""
        return self.n_docs + (self.tail.cap if self.tail is not None else 0)

    @classmethod
    def from_packed(cls, idx: PackedIndex) -> "SearchState":
        m = idx.meta
        return cls(
            term_offsets=jnp.asarray(idx.term_offsets),
            block_docs=jnp.asarray(idx.block_docs),
            block_tf=jnp.asarray(idx.block_tf),
            block_max=jnp.asarray(idx.block_max, dtype=jnp.float32),
            doc_len=jnp.asarray(idx.doc_len),
            idf=jnp.asarray(idx.idf),
            avgdl=jnp.float32(m.avgdl),
            k1=jnp.float32(m.k1),
            b=jnp.float32(m.b),
            n_docs=m.n_docs,
        )


def gather_query_blocks(state: SearchState, term_ids: jax.Array, max_blocks: int):
    """Gather (T, M) block indices + validity for one query's terms.

    term_ids: (T,) int32, -1 = pad. Returns docs (T,M,B) i32, tf (T,M,B) u8,
    bmax (T,M) f32 (0 where invalid), valid (T,M,1) bool.
    """
    tid = jnp.maximum(term_ids, 0)
    off = state.term_offsets[tid]                        # (T,)
    n_blk = state.term_offsets[tid + 1] - off            # (T,)
    m = jnp.arange(max_blocks, dtype=jnp.int32)          # (M,)
    blk = off[:, None] + m[None, :]                      # (T, M)
    valid = (m[None, :] < n_blk[:, None]) & (term_ids[:, None] >= 0)
    blk = jnp.where(valid, blk, 0)
    docs = state.block_docs[blk]                         # (T, M, B)
    tf = state.block_tf[blk]                             # (T, M, B)
    bmax = jnp.where(valid, state.block_max[blk], 0.0)   # (T, M)
    return docs, tf, bmax, valid[..., None]


def bm25_impacts(state: SearchState, term_ids: jax.Array, qtf: jax.Array,
                 docs: jax.Array, tf: jax.Array, valid: jax.Array,
                 *, use_kernel: bool = False) -> jax.Array:
    """Per-posting BM25 partial scores. (T,M,B) float32."""
    tid = jnp.maximum(term_ids, 0)
    idf = state.idf[tid] * qtf                            # (T,)
    dl = state.doc_len[jnp.minimum(docs, state.n_docs)]   # (T, M, B)
    if use_kernel:
        from repro.kernels import ops as kops
        imp = kops.bm25_block_scores(
            tf, dl, idf, state.k1, state.b, state.avgdl)
    else:
        tff = tf.astype(jnp.float32)
        denom = tff + state.k1 * (1.0 - state.b + state.b * dl / state.avgdl)
        imp = idf[:, None, None] * tff / denom
    pad = docs >= state.n_docs
    keep = valid & ~pad & (tf > 0)
    if state.tail is not None:           # a derived state: deletes are inf
        keep = keep & jnp.isfinite(dl)
    return jnp.where(keep, imp, 0.0)


def score_dense(state: SearchState, term_ids: jax.Array, qtf: jax.Array,
                *, max_blocks: int, use_kernel: bool = False) -> jax.Array:
    """One query's dense (n_docs,) BM25 scores — THE scoring core.

    gather → impacts → dense scatter-add, shared verbatim by the
    single-node searcher (`make_search_fn`) and the per-partition body of
    the mesh-level distributed path (`search.distributed._local_search`).
    """
    docs, tf, _, valid = gather_query_blocks(state, term_ids, max_blocks)
    docs = docs.astype(jnp.int32)        # block_docs may be uint16 (compact)
    if state.tail is not None:
        return _score_with_tail(state, term_ids, qtf, docs, tf, valid)
    imp = bm25_impacts(state, term_ids, qtf, docs, tf, valid,
                       use_kernel=use_kernel)
    return accumulate_dense(docs, imp, state.n_docs)


def _score_with_tail(state: SearchState, term_ids, qtf, docs, tf, valid):
    """A derived state's dense scores: the base's gathered postings and
    one posting per (query term, tail document) — its tf from matching
    the document's row, 0 where the term is absent — go through ONE
    :func:`bm25_impacts` and ONE scatter-add, tail documents numbered
    after the base's. Every document then gets the same per-posting
    arithmetic, added term after term, as in a whole hydration's packed
    layout, so a derived generation ranks and scores bit for bit like
    one (on a backend whose scatter adds in order, as the CPU's does)."""
    tail = state.tail
    n, cap = state.n_docs, tail.cap
    T = term_ids.shape[0]
    match = ((tail.terms[None, :, :] == term_ids[:, None, None])
             & (term_ids >= 0)[:, None, None])                  # (T, cap, L)
    tail_tf = jnp.sum(jnp.where(match, tail.tf[None].astype(jnp.int32), 0),
                      axis=-1).astype(jnp.uint8)                # (T, cap)
    tail_docs = jnp.broadcast_to(n + jnp.arange(cap, dtype=jnp.int32),
                                 (T, cap))
    docs = jnp.concatenate(
        [jnp.where(docs >= n, n + cap, docs).reshape(T, -1), tail_docs], 1)
    valid = jnp.concatenate(
        [jnp.broadcast_to(valid, tf.shape).reshape(T, -1),
         jnp.broadcast_to((term_ids >= 0)[:, None], (T, cap))], 1)
    tf = jnp.concatenate([tf.reshape(T, -1), tail_tf], 1)
    view = dataclasses.replace(
        state, n_docs=n + cap,
        doc_len=jnp.concatenate([state.doc_len[:n], tail.doc_len,
                                 jnp.ones(1, jnp.float32)]))
    imp = bm25_impacts(view, term_ids, qtf, docs[..., None], tf[..., None],
                       valid[..., None])
    return accumulate_dense(docs, imp, n + cap)


def pruned_keep(docs: jax.Array, imp: jax.Array, ub: jax.Array,
                valid: jax.Array, *, k: int, n_docs: int) -> jax.Array:
    """(T, M) bool keep mask for block-max pruning — the reference twin of
    the mask computed inside ``kernels/bm25_pruned._pruned_kernel``.

    Shares the kernel's θ / bound helpers so reference and kernel can never
    disagree on which blocks are skipped. ``ub`` is (T, M) ``qtf·block_max``
    zeroed where invalid; ``imp`` the full (T,M,B) impacts (only m=0 is
    read); first blocks are kept unconditionally (they seed θ).
    """
    from repro.kernels.bm25_pruned import (PRUNE_SAFETY, block_bounds,
                                           theta_lower_bound)
    T, M, _ = docs.shape
    bound = block_bounds(ub)
    first = jnp.arange(M, dtype=jnp.int32)[None, :] == 0         # (1, M)
    theta = theta_lower_bound(docs[:, 0], imp[:, 0], k, n_docs)
    return valid[..., 0] & (first | (bound * PRUNE_SAFETY >= theta))


def score_pruned(state: SearchState, term_ids: jax.Array, qtf: jax.Array,
                 *, max_blocks: int, k: int, use_kernel: bool = False,
                 use_topk_kernel: bool = False):
    """One query's block-max pruned top-k: (vals (k,), ids (k,) i32,
    touched () i32 = blocks actually scored).

    Requires k ≤ n_docs (``make_search_fn`` clamps). ``use_kernel=True``
    runs the fused Pallas pass (impacts + pruning + streaming top-k, no
    (T,M,B) intermediate and no HBM accumulator); otherwise a pure-JAX
    reference that zeroes skipped blocks' impacts before the dense
    scatter-add — adding 0.0 is a bitwise no-op for the non-negative sums
    here, so both are bit-identical to the dense path for every doc whose
    blocks are all kept, which covers every top-k doc (see the kernel
    module docstring for the losslessness argument).
    """
    docs, tf, bmax, valid = gather_query_blocks(state, term_ids, max_blocks)
    docs = docs.astype(jnp.int32)
    tf = jnp.where(valid, tf, jnp.uint8(0))   # invalid rows alias block 0
    ub = jnp.where(valid[..., 0], qtf[:, None] * bmax, 0.0)      # (T, M)
    if use_kernel:
        from repro.kernels import ops as kops
        tid = jnp.maximum(term_ids, 0)
        idf_q = state.idf[tid] * qtf                              # (T,)
        dl = state.doc_len[jnp.minimum(docs, state.n_docs)]
        return kops.bm25_pruned_topk(
            tf, dl, docs, idf_q, ub, valid[..., 0],
            state.k1, state.b, state.avgdl, k=k, n_docs=state.n_docs)
    imp = bm25_impacts(state, term_ids, qtf, docs, tf, valid)
    keep = pruned_keep(docs, imp, ub, valid, k=k, n_docs=state.n_docs)
    acc = accumulate_dense(docs, jnp.where(keep[..., None], imp, 0.0),
                           state.n_docs)
    if use_topk_kernel:
        from repro.kernels import ops as kops
        vals, ids = kops.topk(acc, k)
    else:
        vals, ids = jax.lax.top_k(acc, k)
    return vals, ids.astype(jnp.int32), jnp.sum(keep).astype(jnp.int32)


# -- accumulation strategies ----------------------------------------------------


def accumulate_dense(docs: jax.Array, impacts: jax.Array, n_docs: int) -> jax.Array:
    """Scatter-add into a dense (n_docs+1,) accumulator; last slot = dump."""
    acc = jnp.zeros(n_docs + 1, dtype=jnp.float32)
    d = jnp.minimum(docs.reshape(-1), n_docs)
    acc = acc.at[d].add(impacts.reshape(-1))
    return acc[:n_docs]


def accumulate_sorted(docs: jax.Array, impacts: jax.Array, n_docs: int,
                      k: int) -> tuple[jax.Array, jax.Array]:
    """Sort-and-segment-sum accumulation, returning top-k directly.

    The cummax prefix trick: after sorting pairs by doc id, the group total
    for the run ending at i is c[i] - p[start(i)] where c = inclusive cumsum
    and p = exclusive cumsum; p at group starts is recovered with a running
    max of p masked to starts (p is nondecreasing, impacts >= 0).
    """
    d = docs.reshape(-1)
    v = impacts.reshape(-1)
    order = jnp.argsort(d)
    d = d[order]
    v = v[order]
    c = jnp.cumsum(v)
    p = c - v                                            # exclusive prefix
    is_start = jnp.concatenate([jnp.ones(1, bool), d[1:] != d[:-1]])
    is_end = jnp.concatenate([d[1:] != d[:-1], jnp.ones(1, bool)])
    start_p = jax.lax.cummax(jnp.where(is_start, p, -jnp.inf))
    totals = jnp.where(is_end & (d < n_docs), c - start_p, -jnp.inf)
    if totals.shape[0] < k:                 # fewer postings than k: pad
        pad = k - totals.shape[0]
        totals = jnp.concatenate([totals, jnp.full(pad, -jnp.inf)])
        d = jnp.concatenate([d, jnp.full(pad, n_docs, d.dtype)])
    vals, pos = jax.lax.top_k(totals, k)
    ids = jnp.where(jnp.isfinite(vals), d[pos], n_docs)
    vals = jnp.where(jnp.isfinite(vals), vals, 0.0)
    return vals, ids.astype(jnp.int32)


# -- end-to-end search fns -------------------------------------------------------


def make_search_fn(n_docs: int, *, max_terms: int, max_blocks: int, k: int,
                   accumulator: str = "dense", use_kernel: bool = False,
                   use_topk_kernel: bool = False):
    """Build the stateless query-evaluation function (the 'Lambda body').

    Returns fn(state, term_ids (Q,T) i32, qtf (Q,T) f32) ->
    (scores (Q,k) f32, ids (Q,k) i32).
    """

    def one_query(state: SearchState, term_ids, qtf):
        if state.tail is not None and accumulator != "dense":
            raise ValueError("a tail is scored by the dense accumulator only")
        if accumulator == "dense":
            acc = score_dense(state, term_ids, qtf, max_blocks=max_blocks,
                              use_kernel=use_kernel)
            kk = min(k, state.n_slots)   # a tiny partition may hold < k docs
            if use_topk_kernel:
                from repro.kernels import ops as kops
                vals, ids = kops.topk(acc, kk)
            else:
                vals, ids = jax.lax.top_k(acc, kk)
            if kk < k:                   # pad to the (Q, k) contract
                vals = jnp.concatenate([vals, jnp.zeros(k - kk, vals.dtype)])
                ids = jnp.concatenate(
                    [ids.astype(jnp.int32),
                     jnp.full(k - kk, state.n_slots, jnp.int32)])
            return vals, ids.astype(jnp.int32)
        elif accumulator == "sorted":
            docs, tf, _, valid = gather_query_blocks(state, term_ids, max_blocks)
            imp = bm25_impacts(state, term_ids, qtf, docs, tf, valid,
                               use_kernel=use_kernel)
            return accumulate_sorted(docs, imp, n_docs, k)
        elif accumulator == "pruned":
            kk = min(k, n_docs)          # θ needs "missing doc = score 0"
            vals, ids, _ = score_pruned(
                state, term_ids, qtf, max_blocks=max_blocks, k=kk,
                use_kernel=use_kernel, use_topk_kernel=use_topk_kernel)
            if kk < k:
                vals = jnp.concatenate([vals, jnp.zeros(k - kk, vals.dtype)])
                ids = jnp.concatenate(
                    [ids, jnp.full(k - kk, n_docs, jnp.int32)])
            return vals, ids
        raise ValueError(f"unknown accumulator {accumulator!r}")

    def search(state: SearchState, term_ids: jax.Array, qtf: jax.Array):
        return jax.vmap(lambda t, w: one_query(state, t, w))(term_ids, qtf)

    return search


# The (T, M) shapes a call may run, smallest first, as caps on max_terms
# and max_blocks; the last rung is the configured caps themselves.
SMALL_RUNG = (4, 16)


def rung_ladder(max_terms: int, max_blocks: int) -> list[tuple[int, int]]:
    """The ladder of (T, M) rungs under the caps: the small rung, cut to
    them, then the full (``max_terms``, ``max_blocks``)."""
    small = (min(SMALL_RUNG[0], max_terms), min(SMALL_RUNG[1], max_blocks))
    full = (max_terms, max_blocks)
    return [small, full] if small != full else [full]


def batch_need(term_ids: np.ndarray, term_offsets: np.ndarray
               ) -> tuple[int, int]:
    """(T, M) a batch of encoded queries needs: the columns that hold a
    term, and the most blocks any of its terms has under ``term_offsets``
    (the partition's, on the host). A term id past them has none, as the
    device's clamped gather reads it."""
    valid = term_ids >= 0
    cols = np.flatnonzero(valid.any(axis=0))
    v = len(term_offsets) - 1
    tid = np.minimum(term_ids[valid], v)
    n_blk = term_offsets[np.minimum(tid + 1, v)] - term_offsets[tid]
    return (int(cols[-1]) + 1 if len(cols) else 0,
            int(n_blk.max(initial=0)))


def choose_rung(ladder: list[tuple[int, int]], need: tuple[int, int]
                ) -> tuple[int, int]:
    """The smallest rung that holds ``need``; else the full one, which
    reads at most ``max_blocks`` blocks a term."""
    t, m = need
    return next((r for r in ladder if r[0] >= t and r[1] >= m), ladder[-1])


_JITTED: dict = {}


def jitted_search_fn(n_docs: int, **kw):
    """``jax.jit(make_search_fn(n_docs, **kw))``, one per configuration in
    the process: every searcher of one partition size — each generation's
    included — calls the same jitted program, so a new generation neither
    traces nor compiles it again."""
    key = (n_docs, tuple(sorted(kw.items())))
    fn = _JITTED.get(key)
    if fn is None:
        fn = _JITTED[key] = jax.jit(make_search_fn(n_docs, **kw))
    return fn


# -- host-side query encoding ------------------------------------------------------


def encode_queries(vocab: dict[str, int], queries: list[str], *,
                   max_terms: int,
                   idf: np.ndarray | None = None,
                   n_terms: int | None = None) -> tuple[np.ndarray, np.ndarray]:
    """Tokenize + map to term ids + qtf weights, padded to (Q, T).

    When a query has more than ``max_terms`` distinct terms, pass ``idf`` to
    keep the highest-idf (most selective) terms — long queries then degrade
    by shedding stopword-ish terms instead of whatever dict order gives.
    ``n_terms`` drops ids at or past it: a generation's view of a vocab
    shared with later generations.
    """
    from collections import Counter

    from repro.index.tokenizer import tokenize

    Q = len(queries)
    tids = np.full((Q, max_terms), -1, dtype=np.int32)
    qtf = np.zeros((Q, max_terms), dtype=np.float32)
    for qi, q in enumerate(queries):
        counts = Counter(tokenize(q))
        items = [(vocab[t], c) for t, c in counts.items() if t in vocab]
        if n_terms is not None:
            items = [(i, c) for i, c in items if i < n_terms]
        if idf is not None and len(items) > max_terms:
            items.sort(key=lambda tc: -float(idf[tc[0]]))
        items = items[:max_terms]
        for j, (tid, c) in enumerate(items):
            tids[qi, j] = tid
            qtf[qi, j] = c
    return tids, qtf

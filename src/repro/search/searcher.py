"""Searcher: hydration + jitted query evaluation + document fetch.

The pieces assemble exactly like Figure 1 of the paper:

    client → Gateway → FaaSRuntime(search handler)
                         ├─ hydrate index   ← ObjectStore (S3)
                         ├─ evaluate query  (stateless JAX fn)
                         └─ fetch raw docs  ← KVStore (DynamoDB)
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from repro.core import trace
from repro.core.cache import HydrationCache, pytree_nbytes
from repro.core.directory import DirectoryError
from repro.core.kvstore import KVStore
from repro.core.object_store import ObjectStore
from repro.core.refresh import (GENERATION_FILE, AssetCatalog,
                                generation_version, parse_generation)
from repro.index.builder import (VECTOR_META_FILE, IndexMeta, PackedIndex,
                                 combine_segments, combine_vector_segments,
                                 read_lazy_layout, read_segment,
                                 read_vector_segment)
from repro.index.hydration import (LazyIndex, LazyVectors, SuperIndexMissing,
                                   open_partial_segment,
                                   open_partial_vector_segment)
from repro.index.tokenizer import tokenize
from repro.kernels.dot_topk import dot_topk_batch, padded_rows
from repro.search.bm25 import (TAIL_MIN_DOCS, TAIL_MIN_SLOTS, SearchState,
                               TailState, batch_need, choose_rung,
                               encode_queries, jitted_search_fn,
                               pow2_at_least, rung_ladder)
from repro.search.query import query_from_payload
from repro.search.structured import (StructuredUnsupported,
                                     evaluate_structured, facet_counts,
                                     structured_topk)


@dataclasses.dataclass
class SearchConfig:
    max_terms: int = 16
    max_blocks: int = 64          # M: impact-ordered truncation per term
    k: int = 10
    accumulator: str = "dense"    # "dense" | "sorted" | "pruned" (block-max)
    use_kernel: bool = False      # Pallas fused BM25 impacts
    use_topk_kernel: bool = False # Pallas streaming top-k
    # device→host transfer + deserialize throughput used to convert index
    # bytes into simulated hydration seconds (on top of store network time)
    hydrate_Bps: float = 2e9
    # Deterministic exec-time model: when set, handlers report
    # sim_exec_s (+ sim_exec_per_query_s per extra batched query) as the
    # request's compute time instead of the measured wall time of the
    # jitted call. Results are still really computed — only the CLOCK is
    # modeled — so CI benchmarks produce machine-independent latencies and
    # ledger charges that a committed regression baseline can be diffed
    # against exactly. Leave None to measure (the paper's claims).
    sim_exec_s: float | None = None
    sim_exec_per_query_s: float = 0.0002
    # Per-1000-docs exec term of the model: evaluation work scales with the
    # partition's document count, so under a SKEWED partitioning a head
    # partition's handler models proportionally longer invocations — the
    # per-partition load heterogeneity B12 autoscales against. Default 0
    # keeps every pre-existing modeled benchmark bit-identical.
    sim_exec_per_kdoc_s: float = 0.0
    # Same idea for the NRT writer path: when set, indexer invocations
    # (delta pack / merge) report sim_write_s + sim_write_per_doc_s × docs
    # as their compute time — a commit's cost and rollover latency then
    # reproduce bit-for-bit in CI. Leave None to measure.
    sim_write_s: float | None = None
    sim_write_per_doc_s: float = 2e-5
    # Lazy (partial) hydration: a cold instance answers its first query from
    # range reads of the superindex + only the queried terms' posting blocks,
    # then backfills the rest OFF the critical path (billed to the ledger's
    # backfill line). Tri-state: None means "resolver's choice" — handlers
    # treat it as eager (bit-identical to the historical default) while
    # fleet assembly (build_partitioned_search_app) flips None→True, the
    # fleet default since PR 8. Pass an explicit bool to pin either mode.
    # Segments published before the lazy layout fall back to full hydration
    # automatically.
    lazy_hydration: bool | None = None


# How many highest-df terms a rollover prewarm ping hydrates on a lazy
# instance (instead of backfilling the whole partition). Head terms cover
# the bulk of query traffic, so the post-rollover cold-read tail shrinks
# while prewarm GET bytes stay a small fraction of the full index.
PREWARM_TOP_TERMS = 64


class DenseTierMissing(Exception):
    """This asset version carries no dense-vector tier."""


# batch shapes each jitted search program (one a rung) has run, and the
# (program, batch, state signature) triples already run: a derived
# generation runs the shapes its programs have seen once, off the query path
_SHAPES_SEEN: dict[int, set] = {}
_WARMED: set = set()


def _signature(state: SearchState) -> tuple:
    leaves, tree = jax.tree_util.tree_flatten(state)
    return tree, tuple((x.shape, str(x.dtype)) for x in leaves)


class Searcher:
    """Holds the hydrated state + compiled search fn for one index version."""

    def __init__(self, packed: PackedIndex, config: SearchConfig | None = None):
        self.config = config or SearchConfig()
        self.packed = packed
        self.state = SearchState.from_packed(packed)
        self.vocab = packed.vocab
        self.n_terms: int | None = None     # every id of ``vocab`` counts
        self.host_idf = packed.idf
        self.doc_ids = packed.meta.doc_ids
        self.n_docs = packed.meta.n_docs
        self.term_offsets = packed.term_offsets   # the device's, on the host
        self._rungs = self._programs(packed.meta.n_docs)
        # the shapes a generation derived from this one will have (set by
        # LazySearcher): run once in every batch shape this one serves, so
        # that a later generation finds its programs compiled at set-up
        self.twin: SearchState | None = None

    def _programs(self, n_docs: int) -> dict:
        """{(T, M): jitted program} for each rung of the ladder, smallest
        first."""
        cfg = self.config
        return {(t, m): jitted_search_fn(
            n_docs, max_terms=t, max_blocks=m, k=cfg.k,
            accumulator=cfg.accumulator, use_kernel=cfg.use_kernel,
            use_topk_kernel=cfg.use_topk_kernel)
            for t, m in rung_ladder(cfg.max_terms, cfg.max_blocks)}

    def search(self, queries: list[str]) -> tuple[np.ndarray, np.ndarray]:
        # Pad the batch to the next power of two: the jitted fn specializes
        # on Q, so micro-batched traffic compiles O(log max_batch) variants
        # instead of one per distinct batch size.
        Q = len(queries)
        Qp = 1 << max(0, (Q - 1).bit_length())
        with trace.span("encode", queries=Q):
            tids, qtf = encode_queries(self.vocab, queries + [""] * (Qp - Q),
                                       max_terms=self.config.max_terms,
                                       idf=self.host_idf,
                                       n_terms=self.n_terms)
            ladder = list(self._rungs)
            t, m = rung = choose_rung(ladder,
                                      batch_need(tids, self.term_offsets))
            fn, tids, qtf = self._rungs[rung], tids[:, :t], qtf[:, :t]
        with trace.span("bm25", queries=Q, padded=Qp,
                        h2d_bytes=trace.host_nbytes(tids, qtf),
                        gathered=Qp * t * m * self.state.block_docs.shape[1],
                        full=int(rung == ladder[-1])):
            vals, ids = fn(self.state, tids, qtf)
            out = np.asarray(vals)[:Q], np.asarray(ids)[:Q]
        _WARMED.add((id(fn), Qp, _signature(self.state)))
        self._warm(self.state, Qp)
        if self.twin is not None:
            self._warm(self.twin, Qp)
        return out

    def _warm(self, state: SearchState, qp: int) -> int:
        """Run ``state`` once in batch shape ``qp`` in each rung whose
        program has not run it; returns the programs run."""
        ran = 0
        sig = _signature(state)
        for (t, _), fn in self._rungs.items():
            _SHAPES_SEEN.setdefault(id(fn), set()).add(qp)
            key = (id(fn), qp, sig)
            if key in _WARMED:
                continue
            tids = np.full((qp, t), -1, np.int32)
            qtf = np.zeros((qp, t), np.float32)
            jax.block_until_ready(fn(state, tids, qtf))
            _WARMED.add(key)
            ran += 1
        return ran

    def search_batch(self, queries: list[str],
                     k: int | None = None) -> list[list[tuple[int, float]]]:
        """Evaluate Q queries in ONE vmapped device call (the micro-batch
        path); returns per-query [(internal_id, score), ...] hit lists."""
        vals, ids = self.search(queries)
        n = self.n_docs
        out = []
        for qi in range(len(queries)):
            hits = [(int(i), float(v)) for v, i in zip(vals[qi], ids[qi])
                    if i < n and v > 0]
            out.append(hits[: (self.config.k if k is None else k)])
        return out

    def search_one(self, query: str, k: int | None = None):
        return self.search_batch([query], k)[0]

    def warm_programs(self) -> int:
        """Run this state once in every rung and batch shape its programs
        have run and this state's shapes have not (a tail that outgrew its
        capacity, say), off the query path. Returns the programs run."""
        seen = set().union(*(_SHAPES_SEEN.get(id(fn), ())
                             for fn in self._rungs.values()))
        return sum(self._warm(self.state, qp) for qp in sorted(seen))


# -- generations derived from an earlier one's hydrated state -------------------


@dataclasses.dataclass
class HostTail:
    """A derived generation's tail on the host, the twin of
    :class:`~repro.search.bm25.TailState`: the ``n_docs`` documents its
    commits added since the fixed layout, one row each. Immutable once
    built: :meth:`extend` and :meth:`kill` return a new tail, so an older
    generation's tail never changes under it."""

    terms: np.ndarray          # (cap, L) int32, -1 = empty slot
    tf: np.ndarray             # (cap, L) uint8
    doc_len: np.ndarray        # (cap,) float32, inf = deleted or empty
    n_docs: int
    doc_ids: list

    @property
    def nbytes(self) -> int:
        return self.terms.nbytes + self.tf.nbytes + self.doc_len.nbytes

    @classmethod
    def empty(cls) -> "HostTail":
        return cls._alloc(TAIL_MIN_DOCS, TAIL_MIN_SLOTS, None)

    @classmethod
    def _alloc(cls, cap: int, slots: int, old: "HostTail | None"
               ) -> "HostTail":
        out = cls(terms=np.full((cap, slots), -1, np.int32),
                  tf=np.zeros((cap, slots), np.uint8),
                  doc_len=np.full(cap, np.inf, np.float32),
                  n_docs=0, doc_ids=[])
        if old is not None:
            n, w = old.n_docs, old.terms.shape[1]
            out.terms[:n, :w], out.tf[:n, :w] = old.terms[:n], old.tf[:n]
            out.doc_len[:n] = old.doc_len[:n]
            out.n_docs, out.doc_ids = n, list(old.doc_ids)
        return out

    def extend(self, docs: np.ndarray, terms: np.ndarray, tfs: np.ndarray,
               doc_len: np.ndarray, doc_ids: list) -> "HostTail":
        """Append documents: postings (``docs[i]``, 0-based among the new
        documents; ``terms[i]``; ``tfs[i]``), their lengths and ids."""
        m = len(doc_ids)
        order = np.argsort(docs, kind="stable")
        docs, terms, tfs = docs[order], terms[order], tfs[order]
        counts = np.bincount(docs, minlength=m)
        first = np.concatenate([[0], np.cumsum(counts)[:-1]])
        slot = np.arange(len(docs)) - first[docs]
        n = self.n_docs + m
        out = self._alloc(
            pow2_at_least(n, self.terms.shape[0]),
            pow2_at_least(int(counts.max(initial=0)), self.terms.shape[1]),
            self)
        out.terms[self.n_docs + docs, slot] = terms
        out.tf[self.n_docs + docs, slot] = np.minimum(tfs, 255)
        out.doc_len[self.n_docs:n] = doc_len
        out.n_docs = n
        out.doc_ids.extend(doc_ids)
        return out

    def kill(self, local: np.ndarray) -> "HostTail":
        """The tail with documents ``local`` deleted (infinite length)."""
        doc_len = self.doc_len.copy()
        doc_len[local] = np.inf
        return dataclasses.replace(self, doc_len=doc_len)

    def to_device(self) -> TailState:
        return TailState(terms=jax.device_put(self.terms),
                         tf=jax.device_put(self.tf),
                         doc_len=jax.device_put(self.doc_len))


def segment_postings(pack: PackedIndex, start: int):
    """The postings of ``pack``'s documents ``start..``: (document, 0-based
    from ``start``; term id; tf), and those documents' lengths and ids."""
    to = pack.term_offsets.astype(np.int64)
    term = np.repeat(np.arange(len(to) - 1), to[1:] - to[:-1])
    B, n = pack.meta.block, pack.meta.n_docs
    docs = pack.block_docs.astype(np.int64).reshape(-1)
    tf = pack.block_tf.reshape(-1)
    keep = (docs >= start) & (docs < n) & (tf > 0)
    return (docs[keep] - start, np.repeat(term, B)[keep],
            tf[keep].astype(np.int64), pack.doc_len[start:n],
            pack.meta.doc_ids[start:])


@dataclasses.dataclass
class Lineage:
    """What a generation's hydrated state hands the next one: the FIXED
    layout (one whole-segment-set hydration: its device arrays, host term
    offsets, doc lengths and ids, its ``n_fixed`` documents), the tail
    grown since, the segments covered (id -> documents, base first) and
    the tombstones applied."""

    base_seg: str
    seg_docs: dict
    n_fixed: int
    fixed: SearchState
    fixed_offsets: np.ndarray
    fixed_doc_len: np.ndarray
    fixed_ids: list
    tail: HostTail
    tail_dev: "TailState | None"
    dead: np.ndarray

    def ids(self, lo: int, hi: int) -> list:
        """External ids of internal positions [lo, hi)."""
        n = self.n_fixed
        out = list(self.fixed_ids[lo:min(hi, n)])
        if hi > n:
            out += self.tail.doc_ids[max(lo, n) - n:hi - n]
        return out


class TailSearcher(Searcher):
    """A generation derived from an earlier one (:func:`derive_searcher`):
    the earlier generation's fixed layout stays on the device as it is,
    and what this generation changed travels as small arrays — its idf
    (shared by every partition, ``GenState.device_idf``), avgdl, the fixed
    layout's doc lengths where it deleted documents, and the tail. Full
    from the first query: nothing backfills."""

    def __init__(self, lineage: Lineage, state: SearchState, gstate,
                 config: SearchConfig, *, gen: int, parent_gen: int,
                 h2d_bytes: int) -> None:
        self.config = config
        self.lineage = lineage
        self.state = state
        self.vocab = gstate.vocab
        self.n_terms = gstate.n_terms
        self.host_idf = gstate.idf()
        self.doc_ids = lineage.fixed_ids + lineage.tail.doc_ids
        self.n_docs = lineage.n_fixed + lineage.tail.n_docs
        self.term_offsets = lineage.fixed_offsets
        self.gen, self.parent_gen = gen, parent_gen
        self.h2d_bytes = h2d_bytes
        self._rungs = self._programs(lineage.n_fixed)
        self.twin = None

    @property
    def nbytes(self) -> int:
        # what this generation added: the rest is its parent's
        return self.h2d_bytes


def _lazy_lineage(parent: "LazySearcher") -> "Lineage | None":
    idx = parent.index
    if idx.segment_ids is None or any(
            s.fields_header is not None for s in idx.segments):
        return None
    if not parent.full:
        parent.backfill()
    searcher = parent.searcher
    packed = searcher.packed
    return Lineage(
        base_seg=idx.segment_ids[0],
        seg_docs={sid: seg.meta.n_docs
                  for sid, seg in zip(idx.segment_ids, idx.segments)},
        n_fixed=packed.meta.n_docs, fixed=searcher.state,
        fixed_offsets=packed.term_offsets, fixed_doc_len=packed.doc_len,
        fixed_ids=packed.meta.doc_ids,
        tail=HostTail.empty(), tail_dev=None,
        dead=np.asarray(sorted(idx.tombstones), np.int64))


def derive_searcher(parent, parent_gen: int, catalog: AssetCatalog,
                    asset: str, version: str, config: SearchConfig
                    ) -> "tuple[TailSearcher, float] | None":
    """Generation ``version`` of ``asset``, derived from ``parent`` — the
    hydrated entry (a :class:`LazySearcher` or a :class:`TailSearcher`) of
    an earlier generation over the same base segment — the way Lucene's
    near-real-time reader reuses every segment it shares with the last:
    reads only the segments ``parent`` has not covered (their documents
    join the tail), applies the new tombstones and takes the generation's
    shared state, which the catalog resolves from its delta. Returns
    (searcher, simulated seconds), or None where this generation cannot be
    derived from ``parent`` (another base, a structured index, another
    accumulator than the dense one, the Pallas impact kernel): the caller
    hydrates it whole.

    Answers equal a whole hydration of the same generation (see
    ``bm25._score_with_tail``) while every term fits ``max_blocks``
    blocks; a longer term keeps the fixed layout's block order, not one
    re-sorted under the new stats, so its truncation may differ."""
    if config.accumulator != "dense" or config.use_kernel:
        return None
    store = catalog.store
    before = store.stats.sim_seconds
    manifest = catalog.read_generation(asset, version)
    if manifest.stats_ref is None:
        return None
    if isinstance(parent, TailSearcher):
        lin = parent.lineage
    else:
        lin = _lazy_lineage(parent)
    if lin is None or manifest.base != lin.base_seg:
        return None
    gstate = catalog.generation_state(manifest.stats_ref)

    covered = lin.n_fixed + lin.tail.n_docs
    tail, seg_docs, off = lin.tail, {}, 0
    for seg in manifest.segments:
        n = lin.seg_docs.get(seg)
        if n is None:
            d = catalog.open_segment(asset, seg)
            pack = None
            try:
                if off < covered:      # a merge of segments parent holds
                    meta = IndexMeta.from_json(
                        d.open_input("meta.json").read_all())
                if off >= covered or off + meta.n_docs > covered:
                    # new documents: the header and payload, nothing else
                    pack = read_lazy_layout(d)
                    meta = pack.meta
            except (ValueError, DirectoryError):
                return None            # format v2, or written before
            n = meta.n_docs
            old = max(0, min(n, covered - off))
            if meta.doc_ids[:old] != lin.ids(off, off + old):
                return None            # not the documents parent holds
            if pack is not None:
                tail = tail.extend(*segment_postings(pack, covered - off))
                covered = off + n
        seg_docs[seg] = n
        off += n
    if off < covered:
        return None                    # fewer documents than parent holds

    dead = np.asarray(manifest.tombstones, np.int64)
    new_dead = np.setdiff1d(dead, lin.dead)
    h2d = 0
    fixed_doc_len = lin.fixed_doc_len
    doc_len_dev = (parent.state.doc_len if isinstance(parent, TailSearcher)
                   else lin.fixed.doc_len)
    gone = new_dead[new_dead < lin.n_fixed]
    if len(gone):
        fixed_doc_len = fixed_doc_len.copy()
        fixed_doc_len[gone] = np.inf
        doc_len_dev = jax.device_put(fixed_doc_len)
        h2d += fixed_doc_len.nbytes
    gone = new_dead[new_dead >= lin.n_fixed] - lin.n_fixed
    if len(gone):
        tail = tail.kill(gone)
    tail_dev = lin.tail_dev
    if tail is not lin.tail or tail_dev is None:
        tail_dev = tail.to_device()
        h2d += tail.nbytes
    idf_dev, placed = gstate.device_idf()
    h2d += placed
    fixed = lin.fixed
    state = SearchState(
        term_offsets=fixed.term_offsets, block_docs=fixed.block_docs,
        block_tf=fixed.block_tf, block_max=fixed.block_max,
        doc_len=doc_len_dev, idf=idf_dev,
        avgdl=jax.device_put(np.float32(gstate.avgdl or 1.0)),
        k1=fixed.k1, b=fixed.b, n_docs=lin.n_fixed, tail=tail_dev)
    lineage = dataclasses.replace(
        lin, seg_docs=seg_docs, fixed_doc_len=fixed_doc_len, tail=tail,
        tail_dev=tail_dev, dead=dead)
    searcher = TailSearcher(
        lineage, state, gstate, config, gen=parse_generation(version),
        parent_gen=parent_gen, h2d_bytes=h2d)
    sim_s = store.stats.sim_seconds - before + h2d / config.hydrate_Bps
    return searcher, sim_s


def hydrate_searcher(catalog: AssetCatalog, asset: str,
                     config: SearchConfig,
                     version: str | None = None) -> tuple[Searcher, float]:
    """Cold-start hydration: resolve manifest, stream segment files through
    the StoreDirectory, unpack, compile. Returns (searcher, simulated_s).

    Two version layouts hydrate through the same call:

    * a PLAIN version directory holding one segment's files (the original
      batch-publish path), read directly; or
    * a GENERATION manifest (NRT): base + ordered delta segments stream in
      and fuse into one PackedIndex (:func:`~repro.index.builder.
      combine_segments`) under the generation's live stats/vocab, with
      tombstones zeroed — so the compiled search fn never knows the index
      was built incrementally.
    """
    store = catalog.store
    before = store.stats.sim_seconds
    version, directory = catalog.open(asset, version)
    if GENERATION_FILE in directory.list():
        manifest = catalog.read_generation(asset, version)
        stats, vocab = catalog.resolve_generation_state(manifest)
        packs = [read_segment(catalog.open_segment(asset, seg))
                 for seg in manifest.segments]
        packed = combine_segments(packs, vocab=vocab, stats=stats,
                                  tombstones=manifest.tombstones)
    else:
        packed = read_segment(directory)
    network_s = store.stats.sim_seconds - before
    deserialize_s = packed.nbytes / config.hydrate_Bps
    return Searcher(packed, config), network_s + deserialize_s


class DenseSearcher:
    """Dense-tier twin of :class:`Searcher`: brute-force inner-product
    top-k over one partition's document embeddings via the fused
    ``dot_topk`` kernel, one single-query call per query of a batch.

    Tombstoned rows are COMPACTED OUT before scoring (dense scores are
    legitimately negative, so masking-by-zero can't express deletion the
    way the sparse tier's tf-zeroing does); live rows keep their relative
    order, so internal-id ascending tie-breaks match a full rebuild.

    The live rows are padded with zero rows to the kernel's chunk on the
    host and placed on the device once, here: a search hands the kernel
    its query rows and nothing of the matrix.
    """

    def __init__(self, vectors: np.ndarray, doc_ids: list[str],
                 live: np.ndarray, config: SearchConfig | None = None):
        self.config = config or SearchConfig()
        self.doc_ids = doc_ids
        self.n_docs = len(doc_ids)
        vecs = np.asarray(vectors, dtype=np.float32)
        live = np.asarray(live, bool)
        self.row_internal = np.flatnonzero(live).astype(np.int32)
        self.n_live = len(self.row_internal)
        self.dim = vecs.shape[1]
        # the kernel's k, which sets its chunk and so the padded layout
        self.k = min(self.config.k, self.n_live)
        rows = np.zeros((padded_rows(self.n_live, self.k), self.dim),
                        np.float32)
        np.compress(live, vecs, axis=0, out=rows[:self.n_live])
        self.rows = jax.device_put(rows)
        # live rows only: the modeled hydration cost and the policy read it
        self.nbytes = self.n_live * self.dim * 4

    def search_batch(self, qvecs, k: int | None = None
                     ) -> list[list[tuple[int, float]]]:
        """Score Q query vectors, one ``dot_topk`` call each; returns
        per-query [(internal_id, score), ...] — same hit-list shape as the
        sparse tier, so the coordinator merges both identically."""
        Q = len(qvecs)
        want = self.config.k if k is None else min(k, self.config.k)
        if Q == 0 or self.n_live == 0:
            return [[] for _ in range(Q)]
        qarr = np.asarray(qvecs, dtype=np.float32).reshape(Q, self.dim)
        with trace.span("dense", queries=Q, padded=Q,
                        h2d_bytes=trace.host_nbytes(qarr)):
            vals, ids = dot_topk_batch(qarr, self.rows, self.k,
                                       n_valid=self.n_live)
        out = []
        for qi in range(Q):
            hits = [(int(self.row_internal[i]), float(v))
                    for v, i in zip(vals[qi], ids[qi])]
            out.append(hits[:want])
        return out


def hydrate_dense_searcher(catalog: AssetCatalog, asset: str,
                           config: SearchConfig,
                           version: str | None = None
                           ) -> tuple[DenseSearcher, float]:
    """Eager dense-tier hydration: stream the generation's vector segments
    (base + deltas), fuse rows in segment order — the SAME internal-id
    space the sparse tier's ``combine_segments`` builds — and flag the
    generation's tombstones dead. Returns (searcher, simulated_s).

    Raises :class:`DenseTierMissing` when the version has no vector tier
    (sparse-only fleets); callers surface that as a bad-request, not a 500.
    """
    store = catalog.store
    before = store.stats.sim_seconds
    version, directory = catalog.open(asset, version)
    if GENERATION_FILE in directory.list():
        manifest = catalog.read_generation(asset, version)
        if manifest.vec_base is None:
            raise DenseTierMissing(asset)
        packs = [read_vector_segment(catalog.open_segment(asset, seg))
                 for seg in manifest.vec_segments]
        vectors, doc_ids, live = combine_vector_segments(
            packs, tombstones=manifest.tombstones)
    else:
        if VECTOR_META_FILE not in directory.list():
            raise DenseTierMissing(asset)
        vectors, doc_ids, live = combine_vector_segments(
            [read_vector_segment(directory)])
    network_s = store.stats.sim_seconds - before
    searcher = DenseSearcher(vectors, doc_ids, live, config)
    return searcher, network_s + searcher.nbytes / config.hydrate_Bps


class LazyDenseSearcher:
    """Cache entry for a lazily-hydrated dense tier.

    Cold start reads each vector segment's compact superindex (one ranged
    GET), then :meth:`ensure_live` range-reads exactly the LIVE row spans —
    tombstoned rows never move, so there is no backfill stage: once the
    live rows are resident the view is complete and queries are
    bit-identical to eager hydration.
    """

    def __init__(self, lazy: LazyVectors, config: SearchConfig,
                 store: ObjectStore) -> None:
        self.lazy = lazy
        self.config = config
        self._store = store
        self._searcher: DenseSearcher | None = None

    @property
    def nbytes(self) -> int:
        return self.lazy.bytes_read

    def ensure_live(self) -> tuple[bool, float]:
        """Hydrate every live row span; (changed, sim_s) priced like
        :meth:`LazySearcher._billed` (network + deserialize of new bytes)."""
        net0 = self._store.stats.sim_seconds
        bytes0 = self.lazy.bytes_read
        changed = self.lazy.ensure_live()
        sim_s = (self._store.stats.sim_seconds - net0
                 + (self.lazy.bytes_read - bytes0) / self.config.hydrate_Bps)
        if changed:
            self._searcher = None
        return changed, sim_s

    @property
    def searcher(self) -> DenseSearcher:
        if self._searcher is None:
            with trace.span("hydrate") as sp:
                vectors, doc_ids, live = self.lazy.combined()
                self._searcher = DenseSearcher(vectors, doc_ids, live,
                                               self.config)
                sp.set_metadata(h2d_bytes=self._searcher.rows.nbytes)
        return self._searcher


def lazy_hydrate_dense_searcher(catalog: AssetCatalog, asset: str,
                                config: SearchConfig,
                                version: str | None = None
                                ) -> tuple[LazyDenseSearcher, float]:
    """Lazy twin of :func:`hydrate_dense_searcher`: superindex-only cold
    read. Raises :class:`DenseTierMissing` when the version carries no
    vector tier, :class:`SuperIndexMissing` for pre-lazy vector segments
    (callers fall back to eager)."""
    store = catalog.store
    before = store.stats.sim_seconds
    version, directory = catalog.open(asset, version)
    if GENERATION_FILE in directory.list():
        manifest = catalog.read_generation(asset, version)
        if manifest.vec_base is None:
            raise DenseTierMissing(asset)
        segments = [open_partial_vector_segment(catalog.open_segment(asset, s))
                    for s in manifest.vec_segments]
        lazy = LazyVectors(segments, tombstones=manifest.tombstones)
    else:
        if VECTOR_META_FILE not in directory.list():
            raise DenseTierMissing(asset)
        lazy = LazyVectors([open_partial_vector_segment(directory)])
    network_s = store.stats.sim_seconds - before
    deserialize_s = lazy.bytes_read / config.hydrate_Bps
    return LazyDenseSearcher(lazy, config, store), network_s + deserialize_s


class LazySearcher:
    """Cache entry for a lazily-hydrated index version.

    Wraps a :class:`~repro.index.hydration.LazyIndex` and lends out a
    compiled :class:`Searcher` over its CURRENT view. The view's arrays are
    full-shape from the first byte (absent terms masked non-live), so every
    rebuild after incremental hydration reuses the same jit specialization;
    results over hydrated terms are bit-identical to full hydration.
    """

    def __init__(self, index: LazyIndex, config: SearchConfig,
                 store: ObjectStore, twin_idf_cap: int | None = None) -> None:
        self.index = index
        self.config = config
        self._store = store           # billing seam: range-read sim seconds
        self._searcher: Searcher | None = None
        # a later generation can be derived from this one: the padded idf
        # length its states will have (None: it cannot)
        self.twin_idf_cap = twin_idf_cap

    @property
    def full(self) -> bool:
        return self.index.state == "full"

    @property
    def nbytes(self) -> int:
        # what the cache's byte budget sees: the bytes actually streamed
        # into this instance so far (grows partial → full via note_backfill)
        return self.index.bytes_read

    def _billed(self, action) -> tuple[bool, float]:
        """Run ``action() -> changed`` and price it: store network seconds
        (range-read first-byte + bandwidth) + deserialize time for the new
        bytes. Invalidates the lent-out Searcher when the view grew."""
        net0 = self._store.stats.sim_seconds
        bytes0 = self.index.bytes_read
        changed = action()
        sim_s = (self._store.stats.sim_seconds - net0
                 + (self.index.bytes_read - bytes0) / self.config.hydrate_Bps)
        if changed:
            self._searcher = None
        return changed, sim_s

    def ensure_queries(self, queries: list[str]) -> tuple[bool, float]:
        """Hydrate the posting blocks every term of ``queries`` names;
        (changed, sim_s). On-critical-path: callers account ``sim_s`` as
        hydration."""
        return self.ensure_terms(
            {t for q in queries for t in tokenize(q)})

    def ensure_terms(self, terms) -> tuple[bool, float]:
        """Hydrate specific terms' posting blocks — the structured path
        hands in its ASTs' term set directly (the same coalesced ranged
        GETs also pull those rows' field/position payload on v2
        segments). Priced exactly like :meth:`ensure_queries`."""
        terms = set(terms)
        return self._billed(lambda: self.index.ensure_terms(terms))

    def ensure_top_terms(self, n: int) -> tuple[bool, float]:
        """Hydrate the ``n`` highest-document-frequency terms' blocks —
        the rollover-prewarm working set. (changed, sim_s), priced like
        :meth:`ensure_queries`."""
        terms = self.index.top_terms(n)
        return self._billed(lambda: self.index.ensure_terms(terms))

    def backfill(self) -> tuple[bool, float]:
        """Upgrade partial → full; (changed, sim_s). Off-critical-path:
        callers account ``sim_s`` as backfill, never latency."""
        return self._billed(self.index.backfill)

    @property
    def searcher(self) -> Searcher:
        if self._searcher is None:
            with trace.span("hydrate") as sp:
                searcher = Searcher(self.index.packed(), self.config)
                nbytes = pytree_nbytes(searcher.state)
                if self.twin_idf_cap is not None:
                    searcher.twin = dataclasses.replace(
                        searcher.state,
                        idf=jax.device_put(
                            np.zeros(self.twin_idf_cap, np.float32)),
                        tail=HostTail.empty().to_device())
                    nbytes += pytree_nbytes(searcher.twin.tail)
                    nbytes += searcher.twin.idf.nbytes
                self._searcher = searcher
                sp.set_metadata(h2d_bytes=nbytes)
        return self._searcher


def lazy_hydrate_searcher(catalog: AssetCatalog, asset: str,
                          config: SearchConfig,
                          version: str | None = None
                          ) -> tuple[LazySearcher, float]:
    """Partial cold-start hydration: ONE ranged GET per segment pulls the
    compact superindex (term extents + block_max + doc lengths + idf); no
    posting payload moves yet. Returns (entry, simulated_s) — the lazy
    replacement for :func:`hydrate_searcher`'s full streaming.

    Raises :class:`~repro.index.hydration.SuperIndexMissing` for segments
    published before the lazy layout; callers fall back to full hydration.
    """
    store = catalog.store
    before = store.stats.sim_seconds
    version, directory = catalog.open(asset, version)
    if GENERATION_FILE in directory.list():
        manifest = catalog.read_generation(asset, version)
        stats, vocab = catalog.resolve_generation_state(manifest)
        segments = [open_partial_segment(catalog.open_segment(asset, seg))
                    for seg in manifest.segments]
        index = LazyIndex(segments, vocab=vocab, stats=stats,
                          tombstones=manifest.tombstones,
                          segment_ids=manifest.segments)
        derivable = (manifest.stats_ref is not None
                     and config.accumulator == "dense"
                     and not config.use_kernel
                     and all(s.fields_header is None for s in segments))
        twin = (catalog.generation_state(manifest.stats_ref).idf_cap
                if derivable else None)
    else:
        index = LazyIndex([open_partial_segment(directory)])
        twin = None
    network_s = store.stats.sim_seconds - before
    deserialize_s = index.bytes_read / config.hydrate_Bps
    return (LazySearcher(index, config, store, twin_idf_cap=twin),
            network_s + deserialize_s)


def make_search_handler(catalog: AssetCatalog, doc_store: KVStore,
                        asset: str = "index",
                        config: SearchConfig | None = None):
    """Build the Lambda handler: (instance_cache, payload) -> (result, exec_s).

    The hydrated Searcher lives in the *instance's* HydrationCache — a warm
    instance skips straight to query evaluation (paper §2).

    Payloads carry either ``q`` (one query → flat result) or ``queries``
    (micro-batch → ``{"results": [...]}``, one vmapped device call for the
    whole batch — how the gateway absorbs concurrent traffic without one
    invocation per query).

    STRUCTURED payloads carry ``sq`` (one AST payload dict) or ``sqs`` (a
    micro-batch of them) instead of text — the coordinator parsed the DSL
    at admission; workers never re-parse. They evaluate host-side over
    the v2 packed arrays (:func:`~repro.search.structured.
    evaluate_structured`, bit-identical across partitioning), honouring
    ``facets`` (per-query facet-field requests, counted over the full
    eligible set) and ``favg`` (the generation's live per-field avgdls).
    Requires a segment published with field/position data — a structured
    payload against a v1 segment raises
    :class:`~repro.search.structured.StructuredUnsupported`.

    ``payload["mode"]`` selects the tier(s): ``"sparse"`` (BM25, the
    default — pre-hybrid payloads are unchanged), ``"dense"`` (embedding
    inner-product via the ``dot_topk`` kernel; query vectors arrive as
    ``qv``/``qvs``, embedded at the coordinator so every replica scores
    identical floats), or ``"hybrid"`` (both tiers evaluated on the SAME
    instance against the SAME pinned generation; dense hit lists ride along
    under ``result["dense"]`` for the coordinator's RRF fusion). Each tier
    hydrates only when a payload needs it — a sparse-only workload never
    touches vector bytes — and dense entries are cached under
    ``version + "+vec"`` so eviction drops both tiers together. Responses
    that served the dense tier stamp ``vec_version`` so the coordinator's
    generation check can refuse cross-tier generation skew.

    ``payload["prewarm_terms"]`` (with optional ``prewarm_dense``) marks a
    rollover-prewarm ping: derive the generation from an earlier one the
    instance holds (:func:`derive_searcher`), or else hydrate the n
    highest-df terms' blocks (and the dense tier's live rows) on a lazy
    instance WITHOUT evaluating a query and WITHOUT triggering backfill.

    ``payload["gen"]`` (an int) PINS the index generation: the handler
    serves exactly that generation, hydrating it if this instance hasn't
    seen it yet (old generations stay readable until gc). The coordinator
    resolves the serving generation ONCE per query and pins every scatter
    leg — primaries, hedged backups, freshly-scaled replicas — so no query
    can ever merge hits across index generations, even when a commit's
    rollover lands mid-scatter. Unpinned payloads resolve the asset
    manifest's current version (the single-function app's path).
    """
    cfg = config or SearchConfig()
    lazy = bool(cfg.lazy_hydration)   # None (resolver's choice) → eager

    def _derive(cache: HydrationCache, version: str):
        """This generation derived from the newest earlier one this
        instance holds, if it can be (:func:`derive_searcher`)."""
        gen = parse_generation(version)
        if gen is None:
            return None
        held = [(g, e) for v, e in cache.entries(asset)
                if (g := parse_generation(v)) is not None and g < gen
                and isinstance(e, (LazySearcher, TailSearcher))]
        if not held:
            return None
        parent_gen, parent = max(held, key=lambda ge: ge[0])
        return derive_searcher(parent, parent_gen, catalog, asset, version,
                               cfg)

    def handler(cache: HydrationCache, payload: dict) -> tuple[dict, float]:
        gen = payload.get("gen")
        version = (generation_version(gen) if gen is not None
                   else catalog.current_version(asset))
        mode = payload.get("mode", "sparse")
        if mode not in ("sparse", "dense", "hybrid"):
            raise ValueError(f"unknown search mode: {mode!r}")

        def _hydrate():
            with trace.span("hydrate") as sp:
                derived = _derive(cache, version) if lazy else None
                if derived is not None:
                    sp.set_metadata(h2d_bytes=derived[0].h2d_bytes)
                    return derived
                if lazy:
                    try:
                        hydrated = lazy_hydrate_searcher(catalog, asset, cfg,
                                                         version)
                    except SuperIndexMissing:
                        pass   # pre-lazy-layout segment: eager fallback
                    else:
                        # its state reaches the device once its searcher
                        # is built (LazySearcher.searcher)
                        sp.set_metadata(h2d_bytes=0)
                        return hydrated
                hydrated = hydrate_searcher(catalog, asset, cfg, version)
                sp.set_metadata(h2d_bytes=pytree_nbytes(hydrated[0].state))
                return hydrated

        def _hydrate_dense():
            # cached under version+"+vec": HydrationCache.invalidate(asset)
            # drops every version of every key for the asset name, so both
            # tiers evict together on rollover/budget pressure
            with trace.span("hydrate") as sp:
                if lazy:
                    try:
                        dentry, sim_s = lazy_hydrate_dense_searcher(
                            catalog, asset, cfg, version)
                        # the live rows ARE the dense working set — pull
                        # them inside the hydration charge (header + live
                        # spans; tombstoned rows never move, so no
                        # backfill stage)
                        _, more = dentry.ensure_live()
                        # its rows reach the device once its searcher is
                        # built (LazyDenseSearcher.searcher)
                        sp.set_metadata(h2d_bytes=0)
                        return dentry, sim_s + more
                    except SuperIndexMissing:
                        pass   # pre-lazy vector segment: eager fallback
                hydrated = hydrate_dense_searcher(catalog, asset, cfg,
                                                  version)
                sp.set_metadata(h2d_bytes=hydrated[0].rows.nbytes)
                return hydrated

        # Rollover prewarm ping: derive the new generation from the one
        # this instance holds where it can (whole, nothing left to load);
        # else warm the head-term working set (and the dense tier when
        # asked) without evaluating a query and without backfilling — hot
        # terms serve warm post-rollover while the cold tail still
        # lazy-loads on demand.
        if "prewarm_terms" in payload:
            entry = cache.get_or_hydrate(asset, version, _hydrate)
            if isinstance(entry, TailSearcher):
                # its programs run once here, and the generations before
                # its parent (gone from the catalog) leave the cache
                entry.warm_programs()
                for v, _ in cache.entries(asset):
                    g = parse_generation(v.split("+", 1)[0])
                    if g is not None and g < entry.parent_gen:
                        cache.invalidate(asset, v)
            elif isinstance(entry, LazySearcher) and not entry.full:
                changed, sim_s = entry.ensure_top_terms(
                    int(payload["prewarm_terms"]))
                if changed:
                    cache.note_hydration(sim_s)
            if payload.get("prewarm_dense"):
                cache.get_or_hydrate(asset, version + "+vec", _hydrate_dense)
            return {"version": version, "prewarmed": True}, 0.0

        need_sparse = mode in ("sparse", "hybrid")
        need_dense = mode in ("dense", "hybrid")
        batched = ("queries" in payload or "qvs" in payload
                   or "sqs" in payload)
        queries = (list(payload["queries"]) if "queries" in payload
                   else [payload["q"]] if "q" in payload else [])
        qvecs = (list(payload["qvs"]) if "qvs" in payload
                 else [payload["qv"]] if "qv" in payload else [])
        # structured (format-v2) queries arrive as admission-parsed AST
        # payloads (sq/sqs) — never re-parsed here — with per-query facet
        # requests and the generation's live field avgdls (favg)
        sq_payloads = (list(payload["sqs"]) if "sqs" in payload
                       else [payload["sq"]] if "sq" in payload else None)
        if sq_payloads is not None and mode != "sparse":
            raise StructuredUnsupported(
                "structured queries are sparse-tier only")
        k = int(payload.get("k", cfg.k))
        n_q = (len(sq_payloads) if sq_payloads is not None
               else len(qvecs) if mode == "dense" else len(queries))
        if need_dense and len(qvecs) != n_q:
            raise ValueError("hybrid query needs one vector per text query")

        t0 = time.perf_counter()
        exec_s = 0.0
        sparse_hits = dense_hits = facets_out = None
        searcher = dsearcher = None
        entry = None
        if need_sparse:
            entry = cache.get_or_hydrate(asset, version, _hydrate)
            if sq_payloads is not None:
                queries_ast = [query_from_payload(d) for d in sq_payloads]
                if isinstance(entry, LazySearcher):
                    # pull exactly the ASTs' term blocks — the same
                    # coalesced ranged GETs bring the v2 field/position
                    # rows along at the wider pitch
                    with trace.span("hydrate", h2d_bytes=0):
                        changed, sim_s = entry.ensure_terms(
                            {t for q in queries_ast for t in q.terms})
                    if changed:
                        cache.note_hydration(sim_s)
                    searcher = entry.searcher
                else:
                    searcher = entry
                packed = getattr(searcher, "packed", None)
                if packed is None or packed.fields is None:
                    raise StructuredUnsupported(
                        "structured query against a v1 segment (publish "
                        "with IndexSpec(structured=True, ...))")
                favg = payload.get("favg") or {}
                facet_req = payload.get("facets") or [[]] * n_q
                n_docs = packed.meta.n_docs
                sparse_hits, facets_out = [], []
                for qi, ast in enumerate(queries_ast):
                    # host-side dense evaluation — ALWAYS, even on pruned
                    # fleets: field/phrase-modified impacts invalidate the
                    # v1 block_max ceilings, so block-max pruning would be
                    # unsound for structured queries
                    scores, eligible = evaluate_structured(
                        packed, ast, field_avgdl=favg)
                    vals, ids = structured_topk(scores, k)
                    sparse_hits.append(
                        [(int(i), float(v)) for v, i in zip(vals, ids)
                         if i < n_docs and v > 0])
                    facets_out.append(
                        {f: facet_counts(packed, eligible, f)
                         for f in facet_req[qi]})
            else:
                if isinstance(entry, LazySearcher):
                    # pull exactly this batch's term blocks — on the
                    # critical path, so it accounts as hydration (a warm
                    # instance whose view already covers the terms pays
                    # nothing here)
                    with trace.span("hydrate", h2d_bytes=0):
                        changed, sim_s = entry.ensure_queries(queries)
                    if changed:
                        cache.note_hydration(sim_s)
                    searcher = entry.searcher
                else:
                    searcher = entry
                sparse_hits = searcher.search_batch(queries, k)
            if cfg.sim_exec_s is not None:
                exec_s += (cfg.sim_exec_s
                           + cfg.sim_exec_per_query_s * (n_q - 1)
                           + cfg.sim_exec_per_kdoc_s
                           * searcher.n_docs / 1000.0)
        if need_dense:
            dentry = cache.get_or_hydrate(asset, version + "+vec",
                                          _hydrate_dense)
            dsearcher = (dentry.searcher
                         if isinstance(dentry, LazyDenseSearcher) else dentry)
            dense_hits = dsearcher.search_batch(qvecs, k)
            if cfg.sim_exec_s is not None:
                # each tier is its own device call, so the model charges
                # the per-invocation base once per tier
                exec_s += (cfg.sim_exec_s
                           + cfg.sim_exec_per_query_s * (n_q - 1)
                           + cfg.sim_exec_per_kdoc_s
                           * dsearcher.n_docs / 1000.0)
        if cfg.sim_exec_s is None:
            exec_s = time.perf_counter() - t0

        primary = sparse_hits if need_sparse else dense_hits
        ext_sparse = searcher.doc_ids if searcher else None
        ext_dense = dsearcher.doc_ids if dsearcher is not None else None
        primary_ext = ext_sparse if need_sparse else ext_dense
        fetch = payload.get("fetch_docs", True)
        # ONE batched KV fetch for the whole micro-batch — the per-query
        # round trip would otherwise eat the batching amortization. Hybrid
        # unions both tiers' hit ids so fused results materialize from one
        # round trip too.
        keys = dict.fromkeys(primary_ext[h[0]]
                             for hits in primary for h in hits)
        if mode == "hybrid":
            keys.update(dict.fromkeys(ext_dense[h[0]]
                                      for hits in dense_hits for h in hits))
        raw, fetch_s = doc_store.batch_get_billed(keys) if fetch else ({}, 0.0)
        exec_s += fetch_s
        results = []
        for qi in range(n_q):
            hits = primary[qi]
            ids = [h[0] for h in hits]
            ext_ids = [primary_ext[i] for i in ids]
            r = {
                "ids": ids,
                "scores": [h[1] for h in hits],
                "ext_ids": ext_ids,
                "docs": [raw.get(e) for e in ext_ids] if raw else [],
            }
            if facets_out is not None:
                # per-partition scatter-add over the FULL eligible match
                # set; the coordinator merges these at gather like top-k
                r["facets"] = facets_out[qi]
            if mode == "hybrid":
                dh = dense_hits[qi]
                r["dense"] = {
                    "ids": [h[0] for h in dh],
                    "scores": [h[1] for h in dh],
                    "ext_ids": [ext_dense[h[0]] for h in dh],
                }
            results.append(r)
        # response is fully computed — NOW backfill partial → full, off the
        # critical path: the runtime bills the cache's backfill delta to its
        # own ledger line and excludes it from this request's latency
        if (need_sparse and isinstance(entry, LazySearcher)
                and not entry.full):
            with trace.span("backfill"):
                _, bf_s = entry.backfill()
                cache.note_backfill(asset, version, bf_s, nbytes=entry.nbytes)

        if batched:
            out = {"version": version, "results": results}
        else:
            out = results[0]
            out["version"] = version
        if need_dense:
            out["vec_version"] = version
        return out, exec_s

    return handler

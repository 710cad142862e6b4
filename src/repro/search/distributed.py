"""Document-partitioned BM25 query evaluation over the device mesh.

Paper §3: "separate Lambda instances are assigned to different partitions of
the document collection. Given the prototype presented here, building out
this design is mostly a matter of software engineering." — here it is, as a
shard_map program: every device owns one document partition's packed index
arrays (leading partition axis sharded over the whole mesh); a query fans
out to all partitions, each evaluates BM25 locally (the SAME scoring core,
``repro.search.bm25.score_dense``, as the single-partition searcher), and
the k·P survivors are all-gathered and merged — the scatter-gather of
repro.core.partition, on-device.

This module contains no BM25 math and no packing code of its own: scoring
lives in ``search/bm25.py``, impact-ordered block packing in
``index/builder.py`` (one ``IndexWriter`` per partition with global stats),
and this file only wires partitions to mesh axes.

idf is GLOBAL (computed over the whole corpus before partitioning), matching
a correctly-built distributed index; doc ids return globally offset.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.partition import local_topk, merge_topk
from repro.parallel import compat
from repro.search.bm25 import SearchState, score_dense, score_pruned


@dataclasses.dataclass(frozen=True)
class DistSearchConfig:
    """Static geometry of the partitioned index (per partition)."""

    n_parts: int             # total partitions = product of mesh axes used
    n_docs_local: int
    n_blocks_local: int      # NB per partition
    vocab: int
    block: int = 128
    max_terms: int = 16
    max_blocks: int = 32     # impact-ordered truncation per term
    k: int = 100
    accumulator: str = "dense"  # "dense" | "pruned" (block-max WAND)
    compact_ids: bool = False   # uint16 partition-local doc ids (perf)
    fused_gather: bool = False  # one all-gather over (data,model) vs two


def abstract_dist_state(cfg: DistSearchConfig) -> dict:
    """ShapeDtypeStruct stand-ins for the partitioned index arrays."""
    Pn, NB, B = cfg.n_parts, cfg.n_blocks_local, cfg.block
    S = jax.ShapeDtypeStruct
    did = jnp.uint16 if cfg.compact_ids else jnp.int32
    assert not cfg.compact_ids or cfg.n_docs_local < 65535, \
        "compact_ids needs n_docs_local < 2^16 - 1"
    return {
        "term_offsets": S((Pn, cfg.vocab + 1), jnp.int32),
        "block_docs": S((Pn, NB, B), did),
        "block_tf": S((Pn, NB, B), jnp.uint8),
        "block_max": S((Pn, NB), jnp.float32),
        "doc_len": S((Pn, cfg.n_docs_local + 1), jnp.float32),
        "idf": S((cfg.vocab,), jnp.float32),
        "params": S((3,), jnp.float32),          # k1, b, avgdl
    }


def dist_state_specs(axes: tuple[str, ...]) -> dict:
    part = axes[0] if len(axes) == 1 else tuple(axes)
    return {
        "term_offsets": P(part, None),
        "block_docs": P(part, None, None),
        "block_tf": P(part, None, None),
        "block_max": P(part, None),
        "doc_len": P(part, None),
        "idf": P(None),
        "params": P(None),
    }


def _local_search(state: dict, term_ids, qtf, cfg: DistSearchConfig,
                  axes: tuple[str, ...]):
    """Per-device body: local BM25 over this partition, merged top-k out.

    The scoring itself is the unified core (`bm25.score_dense`) applied to
    this device's partition slice; only the global-id offset and the
    survivor all-gather are mesh-specific.
    """
    local = SearchState(
        term_offsets=state["term_offsets"][0],     # (V+1,)
        block_docs=state["block_docs"][0],         # (NB, B)
        block_tf=state["block_tf"][0],
        block_max=state["block_max"][0],           # (NB,)
        doc_len=state["doc_len"][0],               # (n_docs_local+1,)
        idf=state["idf"],
        avgdl=state["params"][2],
        k1=state["params"][0],
        b=state["params"][1],
        n_docs=cfg.n_docs_local,
    )
    pid = compat.flat_axis_index(axes)             # flattened partition id
    base = (pid * cfg.n_docs_local).astype(jnp.int32)
    if cfg.accumulator == "pruned":
        # block-max pruned local scoring: top-k comes straight out of
        # score_pruned (lax.top_k over the pruned accumulator — same tie
        # order as local_topk over the dense accumulator, and bit-identical
        # scores since pruning only skips blocks that cannot enter top-k)
        kk = min(cfg.k, cfg.n_docs_local)
        lv, li, _ = jax.vmap(
            lambda t, w: score_pruned(local, t, w,
                                      max_blocks=cfg.max_blocks, k=kk)
        )(term_ids, qtf)                           # (Q, kk) each
        if kk < cfg.k:                             # pad to the (Q, k) merge
            q = lv.shape[0]
            lv = jnp.concatenate(
                [lv, jnp.zeros((q, cfg.k - kk), lv.dtype)], axis=-1)
            li = jnp.concatenate(
                [li, jnp.full((q, cfg.k - kk), cfg.n_docs_local,
                              jnp.int32)], axis=-1)
        li = base + li
    else:
        scores = jax.vmap(
            lambda t, w: score_dense(local, t, w, max_blocks=cfg.max_blocks)
        )(term_ids, qtf)                           # (Q, n_docs_local)
        ids = base + jnp.arange(cfg.n_docs_local, dtype=jnp.int32)
        ids = jnp.broadcast_to(ids[None], scores.shape)
        lv, li = local_topk(scores, ids, cfg.k)
    if cfg.fused_gather:                   # one collective over all axes
        gv = jax.lax.all_gather(lv, axes, axis=-1, tiled=True)
        gi = jax.lax.all_gather(li, axes, axis=-1, tiled=True)
    else:                                  # hierarchical: fast axis first
        gv, gi = lv, li
        for ax in axes:
            gv = jax.lax.all_gather(gv, ax, axis=-1, tiled=True)
            gi = jax.lax.all_gather(gi, ax, axis=-1, tiled=True)
    return merge_topk(gv, gi, cfg.k)


def make_dist_search_fn(cfg: DistSearchConfig,
                        axes: tuple[str, ...] = ("data", "model"),
                        mesh: jax.sharding.Mesh | None = None):
    """Build the shard_map'd global search fn.

    fn(state, term_ids (Q,T) i32, qtf (Q,T) f32) -> (scores (Q,k), ids (Q,k)),
    replicated. Either pass ``mesh`` explicitly, or enter one via
    ``jax.set_mesh``;
    the mesh extent over `axes` must equal cfg.n_parts — one partition per
    device."""
    sspecs = dist_state_specs(axes)
    body = functools.partial(_local_search, cfg=cfg, axes=axes)
    inner = compat.shard_map(
        body, mesh,
        in_specs=(sspecs, P(None, None), P(None, None)),
        out_specs=(P(None, None), P(None, None)),
    )

    def _check_extent(shape: dict) -> None:
        n_dev = 1
        for ax in axes:
            n_dev *= shape[ax]
        if cfg.n_parts != n_dev:
            raise ValueError(
                f"DistSearchConfig.n_parts={cfg.n_parts} must equal the mesh "
                f"extent over {axes} ({n_dev}) — one partition per device")

    def fn(state, term_ids, qtf):
        m = mesh if mesh is not None else jax.sharding.get_abstract_mesh()
        _check_extent(dict(m.shape))
        return inner(state, term_ids, qtf)

    return fn


# -- host-side partitioned build (real arrays, for tests/examples) ----------------


def partition_corpus(docs: list[tuple[str, str]], n_parts: int,
                     weights: "list[float] | None" = None):
    """Contiguous-chunk document partitioning; returns per-partition doc
    lists plus ``per``, the uniform per-partition size (global id =
    part * per + local id — the mesh path's id map).

    ``weights`` skews the split: partition ``p`` receives a share of the
    corpus proportional to ``weights[p]`` (largest-remainder rounding, so
    sizes sum exactly to the corpus). This is how a benchmark builds the
    Zipf-skewed fleet real collections look like — a head partition with
    most of the documents, a long cold tail — while every partition still
    packs against the same global stats. Weighted splits have no uniform
    ``per``; the returned ``per`` is the LARGEST partition (the fleet app
    maps global ids through actual per-partition offsets, never ``per``,
    whenever an indexer is attached — i.e. always)."""
    if weights is None:
        per = -(-len(docs) // n_parts)
        return [docs[p * per: (p + 1) * per] for p in range(n_parts)], per
    if len(weights) != n_parts or any(w < 0 for w in weights) \
            or sum(weights) <= 0:
        raise ValueError(f"need {n_parts} nonnegative weights with a "
                         f"positive sum, got {weights!r}")
    total = float(sum(weights))
    quotas = [len(docs) * w / total for w in weights]
    sizes = [int(q) for q in quotas]
    # largest remainder: hand leftover docs to the most-shortchanged parts
    for p in sorted(range(n_parts), key=lambda p: quotas[p] - sizes[p],
                    reverse=True)[: len(docs) - sum(sizes)]:
        sizes[p] += 1
    parts, at = [], 0
    for n in sizes:
        parts.append(docs[at: at + n])
        at += n
    return parts, max(sizes)


def stack_partitions(packs: list, n_docs_local: int,
                     cfg_hint: dict | None = None) -> tuple[dict, "DistSearchConfig"]:
    """PackedIndex-per-partition → stacked partitioned-state adapter.

    Stacks per-partition :class:`repro.index.builder.PackedIndex` arrays
    (all built against one global vocab + global stats) along a leading
    partition axis, padding each partition's blocks/doc_len to the common
    NB / n_docs_local extents. Padding entries carry tf=0 so the scoring
    core masks them; the packing itself (impact ordering, block layout,
    BM25 constants) has exactly one source of truth: ``IndexWriter.pack``.
    """
    hint = cfg_hint or {}
    V = packs[0].term_offsets.shape[0] - 1
    B = packs[0].meta.block
    m0 = packs[0].meta
    for p in packs[1:]:       # packs must share vocab + global BM25 stats,
        m = p.meta            # or partition 0's idf/params silently win
        if (p.term_offsets.shape[0] - 1 != V or m.block != B
                or (m.k1, m.b, m.avgdl) != (m0.k1, m0.b, m0.avgdl)
                or not np.array_equal(p.idf, packs[0].idf)):
            raise ValueError(
                "heterogeneous partition packs — build every partition with "
                "the same IndexWriter(vocab=global_vocab(stats), "
                "global_stats=stats)")
    NB = max(max(p.meta.n_blocks for p in packs), 1)
    compact = bool(hint.get("compact_ids")) and n_docs_local < 65535
    did = np.uint16 if compact else np.int32

    block_docs = np.stack([
        np.concatenate([
            p.block_docs,
            np.full((NB - p.meta.n_blocks, B), p.meta.n_docs, np.int32)])
        for p in packs]).astype(did)
    block_tf = np.stack([
        np.concatenate([
            p.block_tf, np.zeros((NB - p.meta.n_blocks, B), np.uint8)])
        for p in packs])
    block_max = np.stack([
        np.concatenate([
            np.asarray(p.block_max, np.float32),
            np.zeros(NB - p.meta.n_blocks, np.float32)])
        for p in packs])
    doc_len = np.ones((len(packs), n_docs_local + 1), np.float32)
    for i, p in enumerate(packs):
        doc_len[i, :p.meta.n_docs] = p.doc_len[:p.meta.n_docs]

    meta = packs[0].meta
    state = {
        "term_offsets": np.stack([p.term_offsets for p in packs]),
        "block_docs": block_docs,
        "block_tf": block_tf,
        "block_max": block_max,
        "doc_len": doc_len,
        "idf": packs[0].idf,               # global stats ⇒ identical per part
        "params": np.asarray([meta.k1, meta.b, meta.avgdl], np.float32),
    }
    cfg = DistSearchConfig(
        n_parts=len(packs), n_docs_local=n_docs_local, n_blocks_local=NB,
        vocab=V, block=B, k=hint.get("k", 10),
        accumulator=hint.get("accumulator", "dense"),
        max_terms=hint.get("max_terms", 16),
        max_blocks=hint.get("max_blocks", 32),
        compact_ids=compact,
        fused_gather=bool(hint.get("fused_gather", False)))
    return state, cfg


def build_partitioned_state(docs: list[tuple[str, str]], n_parts: int,
                            cfg_hint: dict | None = None):
    """Build real partitioned arrays (small corpora — tests/examples).

    Per partition: one ``IndexWriter`` packing against the corpus-global
    vocab and ``compute_global_stats`` (idf/avgdl), then
    :func:`stack_partitions` adapts the PackedIndexes to the shard_map
    state layout. Returns (state dict of np arrays, DistSearchConfig,
    vocab)."""
    from repro.index.builder import (IndexWriter, compute_global_stats,
                                     global_vocab)

    hint = cfg_hint or {}
    parts, per = partition_corpus(docs, n_parts)
    gstats = compute_global_stats(docs)
    vocab = global_vocab(gstats)
    packs = []
    for pdocs in parts:
        writer = IndexWriter(
            k1=hint.get("k1", 0.9), b=hint.get("b", 0.4),
            block=hint.get("block", 128),
            global_stats=gstats, vocab=vocab)
        writer.add_many(pdocs)
        packs.append(writer.pack())
    state, cfg = stack_partitions(packs, per, hint)
    return state, cfg, vocab

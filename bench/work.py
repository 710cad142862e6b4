"""The work an exact evaluation of the window's queries requires, counted
from the traffic and the benchmark's own corpus alone (never from the
program), and the least time a chip could do it in.

* BM25 (the jitted ``search`` program of each partition): every queried
  term's postings in that partition are read once, 9 bytes a posting
  (a 4-byte doc id, a 1-byte tf and the doc's 4-byte length), and the k
  hits are written (a 4-byte score and a 4-byte id each).
* Dense tier (the ``dot_topk`` kernel): per dispatched batch of Q queries
  and per partition, the partition's f32 vector matrix is read once and
  the Q query vectors with it; 2·Q·N·D operations.

A kernel's roofline share is this least time over the device time its
program took; the least time is the larger of bytes over the memory
bandwidth and operations over the bf16 peak.
"""

from __future__ import annotations

POSTING_BYTES = 4 + 1 + 4
HIT_BYTES = 4 + 4
F32_BYTES = 4


def partition_sizes(n_docs: int, n_parts: int) -> list[int]:
    """Contiguous partitions of ceil(n_docs / n_parts) docs each."""
    per = -(-n_docs // n_parts)
    return [max(0, min(per, n_docs - p * per)) for p in range(n_parts)]


def bm25_bytes(post, query_tids: list[list[int]], n_parts: int,
               k: int) -> int:
    """A term's postings over all partitions are its document frequency."""
    total = 0
    for q in query_tids:
        total += sum(post.df(t) for t in set(q)) * POSTING_BYTES
        total += n_parts * k * HIT_BYTES
    return total


def dense_work(batch_sizes: list[int], rows: list[int], dim: int,
               k: int) -> list[tuple[int, int]]:
    """(bytes, operations) of each (batch, partition) kernel dispatch."""
    out = []
    for q in batch_sizes:
        for n in rows:
            out.append(((n + q) * dim * F32_BYTES + q * k * HIT_BYTES,
                        2 * q * n * dim))
    return out


def least_time(nbytes: float, ops: float, peak: dict) -> float:
    return max(nbytes / peak["hbm_bytes_per_s"],
               ops / peak["bf16_flops_per_s"])


def window_work(post, query_tids, batch_sizes, config: dict, mix) -> dict:
    out = {"bm25_bytes": bm25_bytes(post, query_tids, config["n_parts"],
                                    mix.k)}
    if mix.mode in ("dense", "hybrid"):
        out["dense"] = dense_work(
            batch_sizes, partition_sizes(post.n_docs, config["n_parts"]),
            config["vector_dim"], mix.k)
    return out

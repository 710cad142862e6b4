"""The benchmark's own data: a synthetic MS MARCO-shaped corpus and its
hash embedding, made from the seed.

The text is what the system under test is given; the token ids, document
lengths and vectors are what the reference scores from. Both come from one
draw, so the reference never reads anything the system made. The draw is
the one ``synth_corpus`` makes (log-normal lengths, then one Zipf draw for
the whole corpus), taken once from the fixed ``SHAPE_SEED``; the run's seed
then relabels its terms and shuffles its documents within each partition.
So every seed gets other texts with the same document lengths and the same
posting-list lengths in every partition: the index the program builds has
the same shapes, and the programs compiled for them are found in the
compile cache, whatever the seed. The strings are assembled with numpy
instead of per-document joins.
"""

from __future__ import annotations

import dataclasses
import zlib

import numpy as np

# the syllables term ids are spelled in (a bijection id -> string)
SYL = ["ba", "be", "bi", "bo", "bu", "da", "de", "di", "do", "du",
       "ka", "ke", "ki", "ko", "ku", "ma", "me", "mi", "mo", "mu",
       "na", "ne", "ni", "no", "nu", "ra", "re", "ri", "ro", "ru",
       "sa", "se", "si", "so", "su", "ta", "te", "ti", "to", "tu"]

# Lucene's classic English stopwords: the analyzer drops these tokens, so
# they count toward neither a document's length nor any posting list
STOPWORDS = frozenset(
    "a an and are as at be but by for if in into is it no not of on or such "
    "that the their then there these they this to was will with".split())


def term_string(tid: int) -> str:
    s = []
    tid += 1
    while tid:
        tid, r = divmod(tid, len(SYL))
        s.append(SYL[r])
    return "".join(s)


def _spelling(vocab: int) -> tuple[np.ndarray, np.ndarray]:
    """(chars (vocab, W) uint8, lengths (vocab,)) of every term's string."""
    digits, n = [], np.arange(vocab, dtype=np.int64) + 1
    while n.any():
        digits.append(np.where(n > 0, n % len(SYL), -1))
        n //= len(SYL)
    width = 2 * len(digits)
    syl = np.frombuffer("".join(SYL).encode(), np.uint8).reshape(-1, 2)
    chars = np.zeros((vocab, width), np.uint8)
    lens = np.zeros(vocab, np.int64)
    for j, d in enumerate(digits):
        live = d >= 0
        chars[live, 2 * j: 2 * j + 2] = syl[d[live]]
        lens += 2 * live
    return chars, lens


def stop_ids(vocab: int) -> np.ndarray:
    """Term ids whose string is a stopword (at most two syllables long)."""
    return np.array([t for t in range(min(vocab, len(SYL) * (len(SYL) + 1)))
                     if term_string(t) in STOPWORDS], np.int64)


@dataclasses.dataclass
class Corpus:
    """Documents as text (for the system) and as token ids (for the
    reference). ``starts[i]:starts[i+1]`` are document i's tokens."""

    texts: list[str]
    tids: np.ndarray          # (n_tokens,) int64 term ids, document order
    starts: np.ndarray        # (n_docs + 1,) int64 token offsets
    vocab: int
    stop: np.ndarray          # (vocab,) bool: dropped by the analyzer
    # the seed's relabelling of the shape draw: document i is the draw's
    # document shape_doc[i], and term id t is the draw's term shape_tid[t]
    shape_doc: np.ndarray | None = None
    shape_tid: np.ndarray | None = None

    @property
    def n_docs(self) -> int:
        return len(self.texts)

    def ext_id(self, i: int) -> str:
        return f"doc{i}"

    def docs(self) -> list[tuple[str, str]]:
        return [(self.ext_id(i), t) for i, t in enumerate(self.texts)]

    def doc_len(self) -> np.ndarray:
        """Analyzed length of every document (stopwords dropped)."""
        kept = (~self.stop[self.tids]).astype(np.int64)
        csum = np.concatenate([[0], np.cumsum(kept)])
        return csum[self.starts[1:]] - csum[self.starts[:-1]]

    def cf(self) -> np.ndarray:
        """Collection frequency of every term id (stopwords included)."""
        return np.bincount(self.tids, minlength=self.vocab)


SHAPE_SEED = 0x5EED    # the draw every seed relabels and shuffles


def draw(n_docs: int, *, vocab: int, mean_len: int, zipf_a: float,
         seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(document lengths, token ids): ``synth_corpus``'s draw."""
    rng = np.random.default_rng(seed)
    lens = np.maximum(4, rng.lognormal(np.log(mean_len), 0.4, n_docs)
                      ).astype(np.int64)
    return lens, rng.zipf(zipf_a, int(lens.sum())) % vocab


def make_corpus(n_docs: int, *, vocab: int, mean_len: int, zipf_a: float,
                seed: int, n_parts: int = 1) -> Corpus:
    """The corpus of ``seed``: the draw of ``SHAPE_SEED`` with its
    non-stopword term ids permuted and its documents permuted within each
    of ``n_parts`` contiguous partitions (the program's partitioning),
    both permutations drawn from ``seed``."""
    lens, tids = draw(n_docs, vocab=vocab, mean_len=mean_len, zipf_a=zipf_a,
                      seed=SHAPE_SEED)
    rng = np.random.default_rng([seed, 0xC0A9])
    stop = np.zeros(vocab, bool)
    stop[stop_ids(vocab)] = True
    label = np.arange(vocab)
    label[~stop] = rng.permutation(label[~stop])
    per = -(-n_docs // n_parts)
    order = np.concatenate([p + rng.permutation(min(per, n_docs - p))
                            for p in range(0, n_docs, per)])
    starts = np.concatenate([[0], np.cumsum(lens)])
    new_lens = lens[order]
    new_starts = np.concatenate([[0], np.cumsum(new_lens)])
    src = (np.repeat(starts[order] - new_starts[:-1], new_lens)
           + np.arange(new_starts[-1]))
    corpus = spell(new_lens, label[tids[src]], vocab)
    corpus.shape_doc = order
    corpus.shape_tid = np.argsort(label)
    return corpus


def spell(lens: np.ndarray, tids: np.ndarray, vocab: int) -> Corpus:
    """The corpus whose document i holds the ``lens[i]`` next token ids."""
    chars, tlen = _spelling(vocab)
    # every token is followed by one separator byte; a document's text is
    # its tokens' span without the last separator
    tok_len = tlen[tids]
    width = chars.shape[1]
    rec = np.full((len(tids), width + 1), ord(" "), np.uint8)
    rec[:, :width] = chars[tids]
    rec[np.arange(len(tids)), tok_len] = ord(" ")
    buf = rec[np.arange(width + 1)[None, :] <= tok_len[:, None]]
    del rec
    tok_start = np.concatenate([[0], np.cumsum(tok_len + 1)])
    big = buf.tobytes().decode("ascii")
    starts = np.concatenate([[0], np.cumsum(lens)])
    cut = tok_start[starts].tolist()
    texts = [big[a: b - 1] for a, b in zip(cut[:-1], cut[1:])]
    stop = np.zeros(vocab, bool)
    stop[stop_ids(vocab)] = True
    return Corpus(texts=texts, tids=tids, starts=starts, vocab=vocab,
                  stop=stop)


def cell_corpus(config: dict, seed: int) -> Corpus:
    """The corpus a deployment's file describes, for one seed."""
    return make_corpus(config["n_docs"], vocab=config["vocab"],
                       mean_len=config["mean_len"], zipf_a=config["zipf_a"],
                       seed=seed, n_parts=config["n_parts"])


def hash_embedding(text: str, dim: int) -> np.ndarray:
    """The deployment's text embedding: a unit f32 Gaussian seeded by the
    CRC32 of the text, the same function for documents and queries."""
    rng = np.random.default_rng(zlib.crc32(text.encode("utf-8")))
    v = rng.standard_normal(dim).astype(np.float32)
    n = float(np.linalg.norm(v))
    return (v / np.float32(n)) if n else v


def embed_all(texts: list[str], dim: int) -> np.ndarray:
    out = np.empty((len(texts), dim), np.float32)
    for i, t in enumerate(texts):
        out[i] = hash_embedding(t, dim)
    return out

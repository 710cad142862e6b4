"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def bm25_block_scores_ref(tf, dl, idf, k1, b, avgdl):
    """tf (T,M,B) uint8, dl (T,M,B) f32, idf (T,) f32 → impacts (T,M,B) f32."""
    tff = tf.astype(jnp.float32)
    denom = tff + k1 * (1.0 - b + b * dl / avgdl)
    return idf[:, None, None] * tff / denom


@functools.partial(jax.jit, static_argnames=("k", "n_docs"))
def bm25_pruned_topk_ref(tf, dl, docs, idf_q, ub, valid, k1, b, avgdl, *,
                         k, n_docs):
    """UNPRUNED oracle for the fused pruned kernel: score every valid block
    densely, then ``lax.top_k``. The kernel must match this bit-for-bit —
    pruning is only allowed to skip blocks that cannot affect the top-k.
    Inputs as in :func:`repro.kernels.bm25_pruned.bm25_pruned_topk`
    (tf pre-zeroed on invalid blocks). ``touched`` is not modeled here.

    jit'd (unlike the allclose oracles above): bit-parity is only
    meaningful compiled-vs-compiled — XLA's elementwise rewrites round
    the BM25 chain differently than eager op-by-op execution.
    """
    # f32 scalars up front: python-float k1/b would make (1 - b) an exact
    # f64 before rounding, a different value than the kernel's f32 params
    k1 = jnp.asarray(k1, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    avgdl = jnp.asarray(avgdl, jnp.float32)
    tff = tf.astype(jnp.float32)
    denom = tff + k1 * (1.0 - b + b * dl / avgdl)
    imp = idf_q[:, None, None] * tff / denom
    imp = jnp.where(docs < n_docs, imp, 0.0)
    acc = jnp.zeros(n_docs + 1, jnp.float32)
    d = jnp.minimum(docs.reshape(-1), n_docs)
    acc = acc.at[d].add(imp.reshape(-1))
    v, i = jax.lax.top_k(acc[:n_docs], k)
    return v, i.astype(jnp.int32)


def topk_ref(scores, k):
    """scores (N,) f32 → (vals (k,), ids (k,) i32), descending."""
    v, i = jax.lax.top_k(scores, k)
    return v, i.astype(jnp.int32)


def dot_topk_ref(query, cands, k):
    """query (D,), cands (N, D) → top-k of cands @ query."""
    scores = cands.astype(jnp.float32) @ query.astype(jnp.float32)
    return topk_ref(scores, k)


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def _dot_topk_one_ref(query, cands, k, *, chunk: int = 1024):
    """Single-query pure-JAX twin of ``dot_topk`` — see batch docstring."""
    N, D = cands.shape
    chunk = max(chunk, k)
    pad = (-N) % chunk
    cp = jnp.pad(cands, ((0, pad), (0, 0))) if pad else cands
    n_chunks = (N + pad) // chunk
    parts = []
    for ci in range(n_chunks):
        c = jax.lax.dynamic_slice_in_dim(cp, ci * chunk, chunk)
        parts.append(jax.lax.dot_general(
            c.astype(jnp.float32), query.astype(jnp.float32)[None, :],
            (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)[:, 0])
    scores = jnp.concatenate(parts)[:N]
    v, i = jax.lax.top_k(scores, k)
    return v, i.astype(jnp.int32)


def dot_topk_batch_ref(queries, cands, k, *, chunk: int = 1024):
    """queries (Q, D), cands (N, D) → (vals (Q, k), ids (Q, k) i32).

    Pure-JAX twin of ``dot_topk_batch`` and the dense tier's
    uint32-bit-parity target. It reproduces the kernel's DOCUMENTED
    reduction structure — per query, per candidate chunk, one
    (chunk, D) × (D,) f32 dot — because f32 dot accumulation is
    shape-dependent on CPU XLA: a fused (N, D) @ (D, Q) matmul (or a
    vmapped matvec, which rebatches into one) reassociates the sum and
    is only an allclose oracle. ``chunk`` must match the kernel call's
    (both default to 1024).

    Like the kernel, ``chunk`` is never shrunk to N — short inputs pad up
    to one full (chunk, D) block, keeping the matvec shape (and its f32
    bit pattern) canonical for any N, so this full-corpus reference bit-
    matches per-partition kernel calls over uneven partition sizes. And
    like the kernel, each query dispatches as its own jit'd single-query
    program (NOT vmap/``lax.map``/one whole-batch jit): XLA's fusion
    around the query axis is context-dependent at the ~1-ulp level when
    N fits one chunk, so only per-program dispatch makes a query's bits
    independent of its batch neighbours."""
    if len(queries) == 0:
        return (jnp.zeros((0, k), jnp.float32),
                jnp.zeros((0, k), jnp.int32))
    out = [_dot_topk_one_ref(q, cands, k, chunk=chunk) for q in queries]
    return (jnp.stack([v for v, _ in out]),
            jnp.stack([i for _, i in out]))


def embedding_bag_ref(table, idx, weights):
    """table (V,D), idx (B,L) i32 (pad<0), weights (B,L) → (B,D) f32 sums."""
    safe = jnp.maximum(idx, 0)
    gathered = table[safe].astype(jnp.float32)            # (B, L, D)
    w = jnp.where(idx >= 0, weights, 0.0).astype(jnp.float32)
    return jnp.einsum("blD,bl->bD", gathered, w)


def mha_attention_ref(q, k, v, *, causal=False, window=None, sm_scale=None,
                      kv_len=None):
    """q (B,Hq,Sq,D), k (B,Hkv,Skv,D), v (B,Hkv,Skv,Dv); Hq % Hkv == 0.

    window: sliding-window size W (key j visible to query i iff
    i - W < j <= i, positions aligned at the sequence end).
    kv_len: number of valid kv positions (rest masked), for decode.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / jnp.sqrt(D)
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, Sq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    # positions: queries occupy the LAST Sq positions of the kv axis
    qpos = jnp.arange(Sq) + (Skv - Sq)
    kpos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    if kv_len is not None:
        mask &= kpos[None, :] < kv_len
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)   # fully-masked rows
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(B, Hq, Sq, Dv).astype(q.dtype)

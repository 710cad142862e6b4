"""Pallas kernel: streaming top-k over a long score vector.

Phase 1 (this kernel): the score vector is tiled into VMEM-sized chunks; each
chunk's local top-k is extracted by k rounds of (max, argmax, mask) — pure
VPU reductions, no sort. Survivors (n_chunks × k) land in HBM.
Phase 2 (XLA): one small ``lax.top_k`` merge over survivors.

Why this shape: ``lax.top_k`` over N=8.8M scores materializes/sorts the whole
vector in HBM; the streaming pass reads each score exactly once (memory-bound
at HBM bandwidth, the roofline floor) and reduces the sort to k·P elements,
P = n_chunks. Used for BM25 dense accumulation and recsys retrieval scoring.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.interpret import resolve_interpret

DEFAULT_CHUNK = 16384    # f32 chunk = 64KB of VMEM


def _local_topk_kernel(scores_ref, vals_ref, ids_ref, *, k: int, chunk: int,
                       n_live: int):
    ci = pl.program_id(0)
    s = scores_ref[...]                                   # (1, chunk)
    base = ci * chunk
    idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, vals_ref.shape, 1)

    def body(i, carry):
        s_cur, vals, ids = carry
        m = jnp.max(s_cur, axis=1, keepdims=True)         # (1, 1)
        # first-occurrence argmax, spelled as max + min so Mosaic lowers it
        am = jnp.min(jnp.where(s_cur == m, idx, chunk), axis=1, keepdims=True)
        vals = jnp.where(lane == i, m, vals)
        # Pad-lane guard: the tail chunk is padded to `chunk` with -inf, so
        # once a round's max is -inf the chunk has no live element left (a
        # padded lane, or a short chunk exhausted by k > live rounds) — emit
        # the sentinel id n_live, never a padded index. A finite max always
        # points at a live lane (< n_live) because only pads carry -inf at
        # entry. Legit -inf inputs get the same "absent" treatment, matching
        # the sorted accumulator's isfinite convention.
        ids = jnp.where(lane == i,
                        jnp.where(m == -jnp.inf, n_live, base + am), ids)
        s_cur = jnp.where(idx == am, -jnp.inf, s_cur)
        return s_cur, vals, ids

    init = (s, jnp.full(vals_ref.shape, -jnp.inf, jnp.float32),
            jnp.full(ids_ref.shape, n_live, jnp.int32))
    _, vals, ids = jax.lax.fori_loop(0, k, body, init)
    vals_ref[...] = vals
    ids_ref[...] = ids


@functools.partial(jax.jit, static_argnames=("k", "chunk", "interpret"))
def topk(scores, k: int, *, chunk: int = DEFAULT_CHUNK,
         interpret: "bool | None" = None):
    """scores (N,) f32 → (vals (k,), ids (k,) i32), descending order.

    Slots past the live elements (k > number of finite scores) return
    (-inf, N) — N is the caller-visible sentinel, the same dump-slot
    convention the search accumulators use.
    """
    interpret = resolve_interpret(interpret)
    (N,) = scores.shape
    chunk = max(chunk, k)   # a chunk must hold at least k survivors
    pad = (-N) % chunk
    if pad:
        scores = jnp.pad(scores, (0, pad), constant_values=-jnp.inf)
    n_chunks = (N + pad) // chunk

    # Each chunk is a (1, chunk) row and each chunk's survivors a
    # (1, kp) lane-aligned row: TPU blocks must tile (8, 128) or span
    # the array's own trailing dims, which (k,) slices of a flat
    # output do not.
    kp = -(-k // 128) * 128
    vals, ids = pl.pallas_call(
        functools.partial(_local_topk_kernel, k=k, chunk=chunk, n_live=N),
        grid=(n_chunks,),
        in_specs=[pl.BlockSpec((None, 1, chunk), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((None, 1, kp), lambda i: (i, 0, 0)),
                   pl.BlockSpec((None, 1, kp), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_chunks, 1, kp), jnp.float32),
                   jax.ShapeDtypeStruct((n_chunks, 1, kp), jnp.int32)],
        interpret=interpret,
    )(scores.reshape(n_chunks, 1, chunk))
    vals = vals[:, 0, :k].reshape(-1)
    ids = ids[:, 0, :k].reshape(-1)

    # phase 2: tiny merge
    mv, mi = jax.lax.top_k(vals, k)
    return mv, ids[mi]

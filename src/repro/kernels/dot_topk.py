"""Pallas kernel: fused candidate scoring + streaming top-k (retrieval).

The recsys ``retrieval_cand`` shape — one query against 10⁶ candidates — is
the paper's query-evaluation problem in dense form. The fusion matters: an
unfused pipeline writes the (N,) score vector to HBM and reads it back for
top-k; fusing the matvec with the local top-k keeps each candidate chunk's
scores in VMEM, so candidate embeddings are read exactly once and *nothing*
per-candidate is ever written back (output is n_chunks·k survivors).

    chunk scores (MXU):  s = C_chunk @ q        (chunk, D) × (D,)
    local top-k  (VPU):  k rounds of max/argmax/mask
    merge (XLA):         lax.top_k over survivors
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.interpret import resolve_interpret


DEFAULT_CHUNK = 1024
# The dense tier serves f32 scores: a default-precision TPU dot rounds the
# operands to bf16 in one MXU pass, which would break the 1e-5 relative
# rule against float64 dots. HIGHEST keeps the scores f32-faithful at the
# cost of several MXU passes per dot. The CPU backend ignores it.
DOT_PRECISION = jax.lax.Precision.HIGHEST


def _dot_topk_kernel(q_ref, c_ref, vals_ref, ids_ref, *, k: int, chunk: int,
                     n_valid: int):
    ci = pl.program_id(0)
    q = q_ref[...].astype(jnp.float32)                     # (1, D)
    c = c_ref[...].astype(jnp.float32)                     # (chunk, D)
    s = jax.lax.dot_general(c, q, (((1,), (1,)), ((), ())),
                            precision=DOT_PRECISION,
                            preferred_element_type=jnp.float32)  # (chunk, 1)
    base = ci * chunk
    idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, vals_ref.shape, 1)
    s = jnp.where(base + idx < n_valid, s, -jnp.inf)       # mask pad rows

    def body(i, carry):
        s_cur, vals, ids = carry
        m = jnp.max(s_cur, axis=0, keepdims=True)          # (1, 1)
        # first-occurrence argmax, spelled as max + min so Mosaic lowers it
        am = jnp.min(jnp.where(s_cur == m, idx, chunk), axis=0, keepdims=True)
        vals = jnp.where(lane == i, m, vals)
        ids = jnp.where(lane == i, base + am, ids)
        return jnp.where(idx == am, -jnp.inf, s_cur), vals, ids

    init = (s, jnp.full(vals_ref.shape, -jnp.inf, jnp.float32),
            jnp.zeros(ids_ref.shape, jnp.int32))
    _, vals, ids = jax.lax.fori_loop(0, k, body, init)
    vals_ref[...] = vals
    ids_ref[...] = ids


def padded_rows(n: int, k: int, chunk: int = DEFAULT_CHUNK) -> int:
    """Rows ``dot_topk`` scores for ``n`` candidates: ``n`` rounded up to
    a multiple of its chunk, ``max(chunk, k)``."""
    chunk = max(chunk, k)
    return -(-n // chunk) * chunk


@functools.partial(jax.jit,
                   static_argnames=("k", "chunk", "n_valid", "interpret"))
def dot_topk(query, cands, k: int, *, chunk: int = DEFAULT_CHUNK,
             n_valid: "int | None" = None, interpret: "bool | None" = None):
    """query (D,), cands (N,D) → (vals (k,), ids (k,) i32).

    ``chunk`` is NEVER shrunk to N: every grid step scores a full
    (chunk, D) block (short inputs pad with masked rows), so the matvec's
    shape — and therefore its f32 accumulation bit pattern, which on CPU
    XLA depends on the row count's alignment — is canonical for any N.
    A 53-row partition and a 207-row full corpus score a shared row to
    IDENTICAL bits, which is what lets a fleet of uneven partitions be
    checked uint32-bitwise against one full-corpus reference.

    ``n_valid`` says that ``cands`` is already padded with zero rows to
    ``padded_rows(n_valid, k, chunk)`` and that its first ``n_valid`` rows
    are the candidates. The program then pads nothing, so a matrix kept on
    the device is read where it lies; the grid, blocks and masked pad rows
    are those of the unpadded call, and so are the result's bits."""
    N, D = cands.shape
    chunk = max(chunk, k)
    interpret = resolve_interpret(interpret)
    if n_valid is None:
        n_valid = N
    elif N != padded_rows(n_valid, k, chunk):
        raise ValueError(f"{N} rows are not {n_valid} candidates padded to "
                         f"a multiple of {chunk}")
    elif interpret:
        # XLA's CPU backend rounds a chunk's dot differently when the pad
        # fuses into it: the interpreter runs the unpadded call's program
        cands = cands[:n_valid]
    pad = padded_rows(n_valid, k, chunk) - cands.shape[0]
    if pad:
        cands = jnp.pad(cands, ((0, pad), (0, 0)))
    n_chunks = cands.shape[0] // chunk
    q2 = query[None, :]

    # each chunk's survivors land in a (1, kp) lane-aligned row: TPU
    # blocks must tile (8, 128) or span the array's own trailing dims
    kp = -(-k // 128) * 128
    vals, ids = pl.pallas_call(
        functools.partial(_dot_topk_kernel, k=k, chunk=chunk,
                          n_valid=n_valid),
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((chunk, D), lambda i: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((None, 1, kp), lambda i: (i, 0, 0)),
                   pl.BlockSpec((None, 1, kp), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_chunks, 1, kp), jnp.float32),
                   jax.ShapeDtypeStruct((n_chunks, 1, kp), jnp.int32)],
        interpret=interpret,
    )(q2, cands)
    vals = vals[:, 0, :k].reshape(-1)
    ids = ids[:, 0, :k].reshape(-1)

    # mask padded candidates (their score is 0·q = 0, could beat negatives)
    valid = ids < n_valid
    vals = jnp.where(valid, vals, -jnp.inf)
    mv, mi = jax.lax.top_k(vals, k)
    return mv, ids[mi]


def dot_topk_batch(queries, cands, k: int, *, chunk: int = DEFAULT_CHUNK,
                   n_valid: "int | None" = None,
                   interpret: "bool | None" = None):
    """queries (Q, D), cands (N, D) → host arrays (vals (Q, k) f32, ids
    (Q, k) i32); ``n_valid`` as for :func:`dot_topk`.

    The fleet's dense micro-batch path. Q-invariant BY CONSTRUCTION: each
    query dispatches as its own single-query ``dot_topk`` executable
    (shape-cached, so all Q dispatches reuse one compiled program), never
    traced together into a batched graph. Any whole-batch program — vmap,
    ``lax.map``, an unrolled loop under one jit — lets XLA fuse across or
    around the query axis, and the (chunk, D) matvec's f32 accumulation
    bits then differ (~1 ulp) from the standalone single-query lowering,
    making a query's scores depend on how many neighbours shared its
    micro-batch window. Per-program dispatch is what lets windowed fleet
    results be checked uint32-bitwise against the one-query-at-a-time
    reference oracle. The results come to the host in one transfer and
    stack there: a device-side stack would compile a program per Q."""
    if len(queries) == 0:
        return np.zeros((0, k), np.float32), np.zeros((0, k), np.int32)
    out = jax.device_get([dot_topk(q, cands, k, chunk=chunk, n_valid=n_valid,
                                   interpret=interpret) for q in queries])
    return np.stack([v for v, _ in out]), np.stack([i for _, i in out])

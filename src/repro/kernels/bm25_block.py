"""Pallas kernel: fused BM25 impact computation over gathered postings blocks.

The query-evaluation hot loop of the paper's system, TPU-adapted: after the
(T, M) impact-ordered blocks of a query's terms are gathered, each posting's
partial score is

    impact = idf_t * tf / (tf + k1 * (1 - b + b * dl / avgdl))

This is elementwise over (T*M, B) with a per-row broadcast of idf — a pure
VPU kernel. Fusing the uint8→f32 dequant, the length-norm, and the idf scale
into one pass avoids materializing three (T,M,B) f32 intermediates in HBM
(XLA usually fuses this too; the kernel makes the tiling explicit and is the
substrate for the fused scatter-accumulate variant).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.interpret import resolve_interpret

DEFAULT_BLOCK_ROWS = 8   # rows of (T*M) per grid step; B=128 lanes fixed


def _bm25_kernel(tf_ref, dl_ref, idf_ref, params_ref, out_ref):
    # Mosaic has no uint8 -> f32 cast; widen through int32 (exact)
    tf = tf_ref[...].astype(jnp.int32).astype(jnp.float32)   # (R, B)
    dl = dl_ref[...]                            # (R, B)
    idf = idf_ref[...]                          # (R, 1)
    k1, b, avgdl = params_ref[0], params_ref[1], params_ref[2]
    denom = tf + k1 * (1.0 - b + b * dl / avgdl)
    out_ref[...] = idf * tf / denom


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def bm25_block_scores(tf, dl, idf, k1, b, avgdl, *,
                      block_rows: int = DEFAULT_BLOCK_ROWS,
                      interpret: "bool | None" = None):
    """tf (T,M,B) uint8, dl (T,M,B) f32, idf (T,) f32 → (T,M,B) f32."""
    interpret = resolve_interpret(interpret)
    T, M, B = tf.shape
    rows = T * M
    tf2 = tf.reshape(rows, B)
    dl2 = dl.reshape(rows, B)
    idf_rows = jnp.repeat(idf.astype(jnp.float32), M)[:, None]  # (rows, 1)
    params = jnp.stack([jnp.asarray(k1, jnp.float32),
                        jnp.asarray(b, jnp.float32),
                        jnp.asarray(avgdl, jnp.float32)])

    R = block_rows
    pad = (-rows) % R
    if pad:
        tf2 = jnp.pad(tf2, ((0, pad), (0, 0)))
        dl2 = jnp.pad(dl2, ((0, pad), (0, 0)), constant_values=1.0)
        idf_rows = jnp.pad(idf_rows, ((0, pad), (0, 0)))
    grid = ((rows + pad) // R,)

    out = pl.pallas_call(
        _bm25_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((R, B), lambda i: (i, 0)),
            pl.BlockSpec((R, B), lambda i: (i, 0)),
            pl.BlockSpec((R, 1), lambda i: (i, 0)),
            pl.BlockSpec((3,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((R, B), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, B), jnp.float32),
        interpret=interpret,
    )(tf2, dl2, idf_rows, params)
    return out[:rows].reshape(T, M, B)

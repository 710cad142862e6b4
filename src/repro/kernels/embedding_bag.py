"""Pallas kernel: EmbeddingBag (weighted gather + bag-sum) for recsys.

JAX has no native EmbeddingBag; this is the TPU-adapted lookup hot path for
the recsys architectures. Each grid step owns a tile of bags; per (bag, slot)
it DMAs one embedding row by dynamic index and accumulates into a VMEM tile:

    out[b] = Σ_l  weight[b,l] · table[idx[b,l]]        (idx < 0 = padding)

Indices/weights ride in SMEM (scalar-addressed); the table stays unblocked
(memory_space=ANY → HBM on real hardware) and rows are fetched by dynamic
ref indexing — the Pallas expression of FBGEMM's TBE row-gather. On a real
TPU deployment the table is additionally row-sharded across devices
(see repro.models.recsys) so each core gathers from its local shard only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.interpret import resolve_interpret


DEFAULT_BLOCK_BAGS = 8


def _embag_kernel(idx_ref, w_ref, table_ref, out_ref, acc_scr, *,
                  bags: int, slots: int):
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def slot_body(t, _):
        b = t // slots
        l = t % slots
        i = idx_ref[b, l]

        @pl.when(i >= 0)
        def _():
            row = table_ref[pl.ds(i, 1), :]                      # (1, D)
            w = w_ref[b, l]
            acc_scr[b, :] = acc_scr[b, :] + row[0].astype(jnp.float32) * w

        return 0

    jax.lax.fori_loop(0, bags * slots, slot_body, 0)
    out_ref[...] = acc_scr[...]


@functools.partial(jax.jit, static_argnames=("block_bags", "interpret"))
def embedding_bag(table, idx, weights, *, block_bags: int = DEFAULT_BLOCK_BAGS,
                  interpret: "bool | None" = None):
    """table (V,D), idx (B,L) i32 (pad<0), weights (B,L) f32 → (B,D) f32."""
    V, D = table.shape
    Bn, L = idx.shape
    bb = min(block_bags, Bn)
    pad = (-Bn) % bb
    if pad:
        idx = jnp.pad(idx, ((0, pad), (0, 0)), constant_values=-1)
        weights = jnp.pad(weights, ((0, pad), (0, 0)))
    grid = ((Bn + pad) // bb,)

    out = pl.pallas_call(
        functools.partial(_embag_kernel, bags=bb, slots=L),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, L), lambda i: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((bb, L), lambda i: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((bb, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bn + pad, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, D), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(idx, weights.astype(jnp.float32), table)
    return out[:Bn]

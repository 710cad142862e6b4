"""Pallas kernel: FlashAttention for TPU (causal / GQA / sliding-window).

Online-softmax tiling (Dao et al. '22, adapted to TPU memory hierarchy):
grid = (batch·kv_heads, q_blocks, kv_blocks) with the kv axis innermost as a
sequential reduction; running max/denominator/accumulator live in VMEM
scratch across kv steps. Q/K/V tiles stream HBM→VMEM per BlockSpec; scores
never touch HBM. MXU does the two matmuls per tile; masking (causal,
sliding-window, kv-length) is applied in-register.

GQA is handled by folding the G = Hq/Hkv query heads of one kv head into the
q-row axis: q tile rows are (g, s) pairs; the row's *sequence* position is
row % Sq (the wrapper guarantees block_q | Sq so a block never straddles g).

Decode (Sq=1, long cache) reuses the same kernel: the G folded rows form the
q tile, causal=False, kv_len masks the unwritten cache tail. Sliding-window
decode masks kpos ≤ qpos − window with qpos = kv_len − 1 via the same
position formula (queries sit at the end of the kv axis).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.interpret import resolve_interpret

NEG_INF = float("-inf")


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  sm_scale: float, causal: bool, window: int | None,
                  q_seq: int, kv_seq: int, kv_len: int | None,
                  block_q: int, block_k: int, n_kv_blocks: int):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                       # (bq, d)
    k = k_ref[0].astype(jnp.float32)                       # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale

    # sequence positions: query rows are (g, s) folded; queries sit at the
    # END of the kv axis (prefill: q_seq == kv_seq; decode: q_seq == 1).
    row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    qpos = row % q_seq + (kv_seq - q_seq)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    if kv_len is not None:
        mask &= kpos < kv_len
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, 0:1]                                 # (bq, 1)
    l_prev = l_scr[:, 0:1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)             # (bq, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)          # (bq, bk)
    alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)                       # (bk, d)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[:, 0:1] = m_new
    l_scr[:, 0:1] = l_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        l = l_scr[:, 0:1]
        o_ref[0, :, :] = jnp.where(l > 0, acc_scr[...] / l, 0.0)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "kv_len", "sm_scale", "block_q",
                     "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = False, window: int | None = None,
                    kv_len: int | None = None, sm_scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: "bool | None" = None):
    """q (B,Hq,Sq,D), k (B,Hkv,Skv,D), v (B,Hkv,Skv,Dv) → (B,Hq,Sq,Dv).

    Hq % Hkv == 0; Dv may differ from D (MLA's v_dim ≠ qk_dim)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    Dv = v.shape[-1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    scale = sm_scale if sm_scale is not None else float(D) ** -0.5

    # fold GQA groups into q rows: (B*Hkv, G*Sq, D)
    qf = q.reshape(B, Hkv, G, Sq, D).reshape(B * Hkv, G * Sq, D)
    kf = k.reshape(B * Hkv, Skv, D)
    vf = v.reshape(B * Hkv, Skv, Dv)

    bq = min(block_q, Sq) if Sq >= 8 else Sq   # block never straddles g
    if Sq % bq:
        bq = Sq
    bk = min(block_k, Skv)
    pad_k = (-Skv) % bk
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
        kv_len = Skv if kv_len is None else kv_len
    rows = G * Sq
    pad_q = (-rows) % bq
    assert pad_q == 0, (rows, bq)
    n_kv_blocks = (Skv + pad_k) // bk
    grid = (B * Hkv, rows // bq, n_kv_blocks)

    kernel = functools.partial(
        _flash_kernel, sm_scale=scale, causal=causal, window=window,
        q_seq=Sq, kv_seq=Skv, kv_len=kv_len, block_q=bq, block_k=bk,
        n_kv_blocks=n_kv_blocks)

    of = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, rows, Dv), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)

    return of.reshape(B, Hkv, G, Sq, Dv).reshape(B, Hq, Sq, Dv).astype(q.dtype)

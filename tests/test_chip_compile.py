"""The served path's kernels compile for a TPU v5e at deployment widths.

The CPU backend runs every Pallas kernel in interpret mode, which accepts
shapes and ops that Mosaic (the TPU kernel compiler) refuses. These tests
hand each kernel, and the jitted BM25 serving function, to the TPU
compiler ahead of time for a described ``v5e:2x2`` topology: no chip is
attached and nothing runs, so they say nothing about results or speed,
only that the chip's compiler accepts the program.

Widths are deployment ones: W1 is MS MARCO passage (8,841,823 passages,
vocabulary 2^19) over four chips, so one chip's share is 2,210,456 docs;
the dense tier scores 768-dim vectors.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.dot_topk import padded_rows
from repro.search.bm25 import (TAIL_MIN_DOCS, TAIL_MIN_SLOTS, SearchState,
                               TailState, make_search_fn, rung_ladder)

W1_CHIP_DOCS = 2_210_456        # 8,841,823 passages / 4 chips
W1_VOCAB = 1 << 19
W1_BLOCKS = 1_300_000           # ~ postings/128 + one tail block per term
Q, T, M, B = 16, 16, 64, 128    # window batch, max_terms, max_blocks, lanes
RUNGS = pytest.mark.parametrize(
    "rung", rung_ladder(T, M), ids=lambda r: f"{r[0]}x{r[1]}")

PRUNED_REFUSAL = (
    "Mosaic refuses bm25_pruned_topk: 'Unsupported cast: uint8 -> float32'; "
    "past that come the argsort in theta_lower_bound, the in-kernel "
    "scatter-add and the whole (n_docs+1) accumulator held in VMEM — a "
    "redesign, not a bring-up fix (ROADMAP S1/S5)")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A described chip's executables are written to the persistent cache
    but cannot be read back; keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def _kernel_compiled(hlo: str) -> bool:
    return "tpu_custom_call" in hlo


def test_dot_topk_compiles(one_chip):
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip)
    hlo = _compile(lambda q, c: ops.dot_topk(q, c, 10, interpret=False),
                   S((768,)), S((100_000, 768)))
    assert _kernel_compiled(hlo)


def test_dot_topk_on_resident_rows_compiles_without_a_pad(one_chip):
    """The served layout: one partition's live rows (69,077 in the hybrid
    benchmark's partitions) placed already padded to the chunk, so the
    program pads no matrix."""
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip)
    n = 69_077
    hlo = _compile(lambda q, c: ops.dot_topk(q, c, 10, n_valid=n,
                                             interpret=False),
                   S((768,)), S((padded_rows(n, 10), 768)))
    assert _kernel_compiled(hlo)
    assert not [ln for ln in hlo.splitlines()
                if " pad(" in ln and "768]" in ln]


def test_topk_compiles(one_chip):
    s = jax.ShapeDtypeStruct((W1_CHIP_DOCS,), jnp.float32, sharding=one_chip)
    hlo = _compile(lambda x: ops.topk(x, 10, interpret=False), s)
    assert _kernel_compiled(hlo)


def test_bm25_block_scores_compiles(one_chip):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    hlo = _compile(
        lambda tf, dl, idf: ops.bm25_block_scores(
            tf, dl, idf, 0.9, 0.4, 60.0, interpret=False),
        S((T, M, B), jnp.uint8), S((T, M, B), jnp.float32),
        S((T,), jnp.float32))
    assert _kernel_compiled(hlo)


@pytest.mark.xfail(strict=True, reason=PRUNED_REFUSAL)
def test_bm25_pruned_topk_compiles(one_chip):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(
        lambda tf, dl, docs, iq, ub, valid: ops.bm25_pruned_topk(
            tf, dl, docs, iq, ub, valid, 0.9, 0.4, 60.0, k=10,
            n_docs=W1_CHIP_DOCS, interpret=False),
        S((T, M, B), jnp.uint8), S((T, M, B), jnp.float32),
        S((T, M, B), jnp.int32), S((T,), jnp.float32),
        S((T, M), jnp.float32), S((T, M), jnp.bool_))


@RUNGS
def test_dense_search_fn_compiles(one_chip, rung):
    """The serving default: pure-XLA gather, scatter-add and top-k, in
    each (T, M) rung a call may run."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    state = SearchState(
        term_offsets=S((W1_VOCAB + 1,), jnp.int32),
        block_docs=S((W1_BLOCKS, B), jnp.int32),
        block_tf=S((W1_BLOCKS, B), jnp.uint8),
        block_max=S((W1_BLOCKS,), jnp.float32),
        doc_len=S((W1_CHIP_DOCS + 1,), jnp.float32),
        idf=S((W1_VOCAB,), jnp.float32),
        avgdl=S((), jnp.float32), k1=S((), jnp.float32), b=S((), jnp.float32),
        n_docs=W1_CHIP_DOCS)
    t, m = rung
    fn = make_search_fn(W1_CHIP_DOCS, max_terms=t, max_blocks=m, k=10,
                        accumulator="dense")
    _compile(fn, state, S((Q, t), jnp.int32), S((Q, t), jnp.float32))


@RUNGS
def test_dense_search_fn_with_a_tail_compiles(one_chip, rung):
    """A generation derived after a commit: the same program over the
    base's arrays plus the tail (a row of (term, tf) slots per added
    document) and an idf padded past the vocabulary."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    tail = TailState(terms=S((TAIL_MIN_DOCS, TAIL_MIN_SLOTS), jnp.int32),
                     tf=S((TAIL_MIN_DOCS, TAIL_MIN_SLOTS), jnp.uint8),
                     doc_len=S((TAIL_MIN_DOCS,), jnp.float32))
    state = SearchState(
        term_offsets=S((W1_VOCAB + 1,), jnp.int32),
        block_docs=S((W1_BLOCKS, B), jnp.int32),
        block_tf=S((W1_BLOCKS, B), jnp.uint8),
        block_max=S((W1_BLOCKS,), jnp.float32),
        doc_len=S((W1_CHIP_DOCS + 1,), jnp.float32),
        idf=S((W1_VOCAB + (1 << 15),), jnp.float32),
        avgdl=S((), jnp.float32), k1=S((), jnp.float32), b=S((), jnp.float32),
        n_docs=W1_CHIP_DOCS, tail=tail)
    t, m = rung
    fn = make_search_fn(W1_CHIP_DOCS, max_terms=t, max_blocks=m, k=10,
                        accumulator="dense")
    _compile(fn, state, S((Q, t), jnp.int32), S((Q, t), jnp.float32))

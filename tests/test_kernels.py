"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracles.

Every kernel is swept over shapes and dtypes and asserted against its
ref.py oracle, per the assignment contract.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    if dtype == jnp.uint8:
        return jax.random.randint(key, shape, 0, 20).astype(jnp.uint8)
    if jnp.issubdtype(dtype, jnp.integer):
        return jax.random.randint(key, shape, 0, 100).astype(dtype)
    return jax.random.normal(key, shape).astype(dtype)


# -- BM25 impact kernel --------------------------------------------------------


@pytest.mark.parametrize("T,M,B", [(1, 1, 128), (4, 8, 128), (16, 3, 128),
                                   (7, 5, 128)])
def test_bm25_block_scores(T, M, B):
    key = jax.random.PRNGKey(T * 100 + M)
    tf = _rand(key, (T, M, B), jnp.uint8)
    dl = jax.random.uniform(key, (T, M, B), minval=1.0, maxval=200.0)
    idf = jax.random.uniform(key, (T,), minval=0.1, maxval=8.0)
    got = ops.bm25_block_scores(tf, dl, idf, 0.9, 0.4, 60.0, interpret=True)
    want = ref.bm25_block_scores_ref(tf, dl, idf, 0.9, 0.4, 60.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("block_rows", [1, 8, 32])
def test_bm25_block_rows_sweep(block_rows):
    key = jax.random.PRNGKey(0)
    tf = _rand(key, (5, 7, 128), jnp.uint8)
    dl = jax.random.uniform(key, (5, 7, 128), minval=1.0, maxval=100.0)
    idf = jax.random.uniform(key, (5,), minval=0.1, maxval=5.0)
    got = ops.bm25_block_scores(tf, dl, idf, 1.2, 0.75, 40.0,
                                block_rows=block_rows, interpret=True)
    want = ref.bm25_block_scores_ref(tf, dl, idf, 1.2, 0.75, 40.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


# -- fused block-max pruned scoring + top-k ---------------------------------------


def _pruned_args(seed, T, M, n_docs, zipf_a=2.0):
    from repro.data.corpus import synth_pruned_blocks
    a = synth_pruned_blocks(seed, n_terms=T, max_blocks=M, n_docs=n_docs,
                            zipf_a=zipf_a)
    return tuple(map(jnp.asarray, a))


_F32 = (jnp.float32(0.9), jnp.float32(0.4), jnp.float32(12.0))


def _assert_bitwise(got, want):
    gv, gi = np.asarray(got[0]), np.asarray(got[1])
    wv, wi = np.asarray(want[0]), np.asarray(want[1])
    assert np.array_equal(gv.view(np.uint32), wv.view(np.uint32)), \
        f"vals not bit-identical: {gv} vs {wv}"
    assert np.array_equal(gi, wi), f"ids differ: {gi} vs {wi}"


@pytest.mark.parametrize("T,M,n_docs,k", [
    (1, 1, 200, 10), (4, 6, 900, 10), (8, 4, 2000, 25), (2, 8, 1024, 5),
    (5, 8, 4000, 50),
])
@pytest.mark.parametrize("zipf_a", [1.3, 4.0])
def test_bm25_pruned_topk_bitwise(T, M, n_docs, k, zipf_a):
    """Pruned fused kernel == UNPRUNED dense ref, bit-for-bit (losslessness)."""
    args = _pruned_args(T * 31 + M, T, M, n_docs, zipf_a)
    gv, gi, _ = ops.bm25_pruned_topk(*args, *_F32, k=k, n_docs=n_docs,
                                     interpret=True)
    want = ref.bm25_pruned_topk_ref(*args, *_F32, k=k, n_docs=n_docs)
    _assert_bitwise((gv, gi), want)


def test_bm25_pruned_actually_prunes():
    """Single-term query over impact-skewed blocks: later blocks' ceilings
    fall below θ from the first block, so touched < valid — the kernel must
    skip work, not just match the oracle — while staying bit-identical."""
    args = _pruned_args(13, 1, 8, 4000, zipf_a=1.3)
    n_valid = int(np.asarray(args[5]).sum())
    gv, gi, touched = ops.bm25_pruned_topk(*args, *_F32, k=10, n_docs=4000,
                                           interpret=True)
    assert 0 < int(touched) < n_valid
    want = ref.bm25_pruned_topk_ref(*args, *_F32, k=10, n_docs=4000)
    _assert_bitwise((gv, gi), want)


def test_bm25_pruned_uniform_ties_and_exact_threshold():
    """Every posting identical → every block's bound EQUALS θ exactly;
    ties at the k boundary must resolve like lax.top_k (lowest ids), and
    the >=-keep rule must not drop the boundary blocks."""
    T, M, B, n_docs, k = 1, 8, 128, 1024, 16
    docs = np.arange(T * M * B, dtype=np.int32).reshape(T, M, B) % n_docs
    tf = np.ones((T, M, B), np.uint8)
    dl = np.full((T, M, B), 12.0, np.float32)    # == avgdl → norm term = 1
    idf_q = np.ones(T, np.float32)
    valid = np.ones((T, M), bool)
    # per-posting impact (f32 math, as the kernel computes it); with a
    # single term, bound(0, m) == ub == the impact == θ for every block
    one = np.float32(1.0) / (np.float32(1.0) + np.float32(0.9))
    ub = np.full((T, M), one, np.float32)    # block_max == the impact
    args = tuple(map(jnp.asarray, (tf, dl, docs, idf_q, ub, valid)))
    gv, gi, touched = ops.bm25_pruned_topk(*args, *_F32, k=k, n_docs=n_docs,
                                           interpret=True)
    want = ref.bm25_pruned_topk_ref(*args, *_F32, k=k, n_docs=n_docs)
    _assert_bitwise((gv, gi), want)
    assert int(touched) == T * M            # equality keeps, never skips


def test_bm25_pruned_tombstone_zeroed_blocks():
    """Blocks whose tf was zeroed (combine_segments tombstones) carry
    block_max 0 and impact 0 — pruned must stay bit-identical."""
    tf, dl, docs, idf_q, ub, valid = map(
        np.asarray, _pruned_args(11, 4, 6, 900, 2.0))
    tf, ub = tf.copy(), ub.copy()
    tf[1, 2] = 0                         # tombstone a mid-impact block
    ub[1, 2] = 0.0
    tf[3, 0] = 0                         # and a FIRST block (θ seed)
    ub[3, 0] = 0.0
    args = tuple(map(jnp.asarray, (tf, dl, docs, idf_q, ub, valid)))
    gv, gi, _ = ops.bm25_pruned_topk(*args, *_F32, k=10, n_docs=900,
                                     interpret=True)
    want = ref.bm25_pruned_topk_ref(*args, *_F32, k=10, n_docs=900)
    _assert_bitwise((gv, gi), want)


def test_bm25_pruned_fewer_postings_than_k():
    """T·B < k in phase 1 → θ must fall back to 0 (prune nothing) rather
    than overestimate from an under-full candidate set."""
    args = _pruned_args(3, 1, 2, 300, 2.0)
    n_valid = int(np.asarray(args[5]).sum())
    gv, gi, touched = ops.bm25_pruned_topk(*args, *_F32, k=200, n_docs=300,
                                           interpret=True)
    want = ref.bm25_pruned_topk_ref(*args, *_F32, k=200, n_docs=300)
    _assert_bitwise((gv, gi), want)
    assert int(touched) == n_valid


# -- streaming top-k ------------------------------------------------------------


@pytest.mark.parametrize("N,k,chunk", [(1000, 10, 256), (16384, 100, 4096),
                                       (777, 5, 128), (128, 128, 128)])
def test_topk(N, k, chunk):
    scores = jax.random.normal(jax.random.PRNGKey(N), (N,))
    gv, gi = ops.topk(scores, k, chunk=chunk, interpret=True)
    wv, wi = ref.topk_ref(scores, k)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv), rtol=1e-6)
    # ids must point at equal scores (ties may reorder)
    np.testing.assert_allclose(np.asarray(scores)[np.asarray(gi)],
                               np.asarray(wv), rtol=1e-6)


def test_topk_with_ties_and_negatives():
    scores = jnp.concatenate([jnp.full(100, -5.0), jnp.full(50, 2.0),
                              jnp.arange(20, dtype=jnp.float32)])
    gv, gi = ops.topk(scores, 30, chunk=64, interpret=True)
    wv, _ = ref.topk_ref(scores, 30)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv), rtol=1e-6)


@pytest.mark.parametrize("N,k,chunk", [(13, 6, 8), (5, 8, 4), (100, 40, 64),
                                       (129, 3, 128)])
def test_topk_pad_never_leaks(N, k, chunk):
    """Short final chunk: a padded lane (or an exhausted chunk when
    k > live elements) must emit the sentinel id N, never a padded index."""
    scores = jax.random.normal(jax.random.PRNGKey(N * 7 + k), (N,))
    gv, gi = ops.topk(scores, k, chunk=chunk, interpret=True)
    gi = np.asarray(gi)
    gv = np.asarray(gv)
    live = min(k, N)
    assert np.all(gi[:live] < N)                  # real hits: real indices
    wv, _ = ref.topk_ref(scores, live)
    np.testing.assert_allclose(gv[:live], np.asarray(wv), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(scores)[gi[:live]], gv[:live],
                               rtol=1e-6)
    if k > N:                                     # k > live: sentinel tail
        assert np.all(gi[N:] == N)
        assert np.all(gv[N:] == -np.inf)


def test_topk_k_exceeds_live_with_neg_inf_inputs():
    """Legit -inf scores count as absent too (the sorted accumulator's
    isfinite convention): with only 3 finite scores and k=6, slots 3+ are
    (-inf, N)."""
    scores = jnp.asarray([-jnp.inf, 2.0, -jnp.inf, 1.0, 3.0, -jnp.inf,
                          -jnp.inf])
    gv, gi = ops.topk(scores, 6, chunk=4, interpret=True)
    np.testing.assert_allclose(np.asarray(gv)[:3], [3.0, 2.0, 1.0])
    assert list(np.asarray(gi)[:3]) == [4, 1, 3]
    assert np.all(np.asarray(gi)[3:] == 7)
    assert np.all(np.asarray(gv)[3:] == -np.inf)


# -- interpret-mode selection -----------------------------------------------------


def test_interpret_defaults_to_backend():
    from repro.kernels.interpret import resolve_interpret
    assert jax.default_backend() == "cpu"     # tests run on the CPU backend
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False  # explicit override wins
    assert resolve_interpret(True) is True


# -- fused dot + top-k (retrieval) ------------------------------------------------


@pytest.mark.parametrize("N,D,k", [(1000, 16, 10), (4096, 64, 100),
                                   (513, 32, 7)])
def test_dot_topk(N, D, k):
    key = jax.random.PRNGKey(N + D)
    q = jax.random.normal(key, (D,))
    c = jax.random.normal(jax.random.fold_in(key, 1), (N, D))
    gv, gi = ops.dot_topk(q, c, k, interpret=True)
    wv, wi = ref.dot_topk_ref(q, c, k)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv), rtol=1e-4,
                               atol=1e-4)
    scores = np.asarray(c) @ np.asarray(q)
    np.testing.assert_allclose(scores[np.asarray(gi)], np.asarray(wv),
                               rtol=1e-4, atol=1e-4)


# -- embedding bag -----------------------------------------------------------------


@pytest.mark.parametrize("V,D,B,L", [(64, 8, 4, 3), (1000, 32, 16, 10),
                                     (50, 128, 7, 5)])
def test_embedding_bag_kernel(V, D, B, L):
    key = jax.random.PRNGKey(V)
    table = jax.random.normal(key, (V, D))
    idx = jax.random.randint(jax.random.fold_in(key, 1), (B, L), -1, V)
    w = jax.random.normal(jax.random.fold_in(key, 2), (B, L))
    got = ops.embedding_bag(table, idx, w, interpret=True)
    want = ref.embedding_bag_ref(table, idx, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


# -- flash attention ---------------------------------------------------------------


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D", [
    (1, 2, 2, 128, 128, 32),       # MHA square
    (2, 4, 2, 128, 128, 64),       # GQA
    (1, 8, 1, 128, 256, 32),       # MQA, longer kv
    (2, 4, 4, 1, 384, 64),         # decode (Sq=1)
])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention(B, Hq, Hkv, Sq, Skv, D, causal):
    if causal and Sq not in (Skv, 1):
        pytest.skip("causal requires aligned positions")
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, Hq, Sq, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, Skv, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, Skv, D))
    got = ops.flash_attention(q, k, v, causal=causal, interpret=True)
    want = ref.mha_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=2e-3)


def test_flash_attention_window():
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 2, 256, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 256, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 256, 32))
    got = ops.flash_attention(q, k, v, causal=True, window=64, interpret=True)
    want = ref.mha_attention_ref(q, k, v, causal=True, window=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=2e-3)


def test_flash_attention_kv_len_mask():
    key = jax.random.PRNGKey(4)
    q = jax.random.normal(key, (2, 2, 1, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 2, 512, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 2, 512, 32))
    got = ops.flash_attention(q, k, v, kv_len=100, interpret=True)
    want = ref.mha_attention_ref(q, k, v, kv_len=100)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=2e-3)


def test_flash_attention_mla_vdim():
    """v head dim ≠ qk head dim (MLA-style)."""
    key = jax.random.PRNGKey(5)
    q = jax.random.normal(key, (1, 4, 128, 48))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 4, 128, 48))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 4, 128, 32))
    got = ops.flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.mha_attention_ref(q, k, v, causal=True)
    assert got.shape == (1, 4, 128, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=2e-3)


def test_flash_vs_chunked_attention():
    """The two attention impls agree (chunked is the model default)."""
    from repro.models.attention import chunked_attention
    key = jax.random.PRNGKey(6)
    q = jax.random.normal(key, (2, 4, 256, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 2, 256, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 2, 256, 32))
    a = ops.flash_attention(q, k, v, causal=True, interpret=True)
    b = chunked_attention(q, k, v, causal=True, block_q=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                               atol=2e-3)

"""Model zoo: transformer variants (decode == forward), MoE dispatch vs
dropless oracle, GNN invariances, recsys forwards, embedding lookup."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.common import init_params
from repro.models.moe import MoEConfig, moe_defs, moe_ffn, moe_ffn_dense_oracle
from repro.models.transformer import (LMConfig, MLAConfig, lm_decode,
                                      lm_forward, lm_loss, lm_param_defs,
                                      lm_prefill)

KEY = jax.random.PRNGKey(0)


def _lm_cfgs():
    return {
        "dense-gqa": LMConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                              n_kv_heads=2, d_ff=128, vocab=256,
                              dtype=jnp.float32),
        "swa-ring": LMConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                             n_kv_heads=2, d_ff=128, vocab=256, window=8,
                             dtype=jnp.float32),
        "gelu-partial-rope": LMConfig(name="t", n_layers=2, d_model=64,
                                      n_heads=4, n_kv_heads=4, d_ff=128,
                                      vocab=256, ffn_act="gelu", rope_pct=0.25,
                                      dtype=jnp.float32),
        "moe": LMConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                        n_kv_heads=4, d_ff=128, vocab=256, dtype=jnp.float32,
                        moe=MoEConfig(n_experts=8, top_k=2, d_model=64,
                                      d_ff=32, capacity_factor=4.0)),
        "mla-moe": LMConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                            n_kv_heads=4, d_ff=128, vocab=256,
                            dtype=jnp.float32,
                            mla=MLAConfig(q_lora=32, kv_lora=16, rope_dim=8,
                                          nope_dim=16, v_dim=16),
                            moe=MoEConfig(n_experts=8, top_k=2, d_model=64,
                                          d_ff=32, n_shared=1,
                                          capacity_factor=4.0)),
    }


@pytest.mark.parametrize("name", list(_lm_cfgs()))
def test_lm_decode_matches_forward(name):
    """Prefill + N decode steps reproduce the full-forward logits."""
    cfg = _lm_cfgs()[name]
    params = init_params(lm_param_defs(cfg), KEY)
    B, S, EXTRA = 2, 12, 3
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + EXTRA), 0,
                              cfg.vocab)
    logits_full, _ = lm_forward(params, toks, cfg)
    pl_logits, cache = lm_prefill(params, toks[:, :S], cfg, max_len=S + EXTRA)
    np.testing.assert_allclose(np.asarray(pl_logits),
                               np.asarray(logits_full[:, S - 1]),
                               rtol=2e-2, atol=2e-2)
    for t in range(EXTRA):
        step_logits, cache = lm_decode(params, cache, toks[:, S + t:S + t + 1],
                                       jnp.int32(S + t), cfg)
        np.testing.assert_allclose(np.asarray(step_logits),
                                   np.asarray(logits_full[:, S + t]),
                                   rtol=5e-2, atol=5e-2)


def test_lm_loss_decreases_with_training():
    cfg = _lm_cfgs()["dense-gqa"]
    from repro.train.optim import OptConfig
    from repro.train.steps import init_train_state, make_train_step
    from repro.data.lm import LMDataConfig, LMTokenStream
    params = init_params(lm_param_defs(cfg), KEY)
    state = init_train_state(params)
    step = jax.jit(make_train_step(lambda p, b: lm_loss(p, b, cfg),
                                   OptConfig(lr=3e-3, warmup_steps=5,
                                             total_steps=60)))
    data = LMTokenStream(LMDataConfig(vocab=cfg.vocab, batch=8, seq=32))
    losses = []
    for i in range(60):
        state, m = step(state, data.batch(i))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.2


def test_swa_masks_beyond_window():
    """A token > window steps back must not influence the current logits."""
    cfg = _lm_cfgs()["swa-ring"]   # window=8
    params = init_params(lm_param_defs(cfg), KEY)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 24), 0, cfg.vocab)
    # flipping token 0 must not change logits at position 20 (>2×window away
    # — with 2 layers the receptive field is 2·(window−1))
    toks2 = toks.at[0, 0].set((toks[0, 0] + 1) % cfg.vocab)
    l1, _ = lm_forward(params, toks, cfg)
    l2, _ = lm_forward(params, toks2, cfg)
    np.testing.assert_allclose(np.asarray(l1[0, 20:]), np.asarray(l2[0, 20:]),
                               rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(l1[0, 1]), np.asarray(l2[0, 1]))


def test_moe_capacity_dispatch_matches_oracle():
    cfg = MoEConfig(n_experts=8, top_k=2, d_model=32, d_ff=16, n_shared=1,
                    capacity_factor=8.0)
    params = init_params(moe_defs(cfg, jnp.float32), KEY)
    x = jax.random.normal(jax.random.PRNGKey(3), (64, 32))
    y, aux = moe_ffn(params, x, cfg)
    y_ref = moe_ffn_dense_oracle(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-5,
                               atol=2e-5)
    assert float(aux) > 0.5          # aux ≈ 1 for near-balanced routing


def test_moe_drops_overflow_tokens():
    cfg = MoEConfig(n_experts=4, top_k=1, d_model=16, d_ff=8,
                    capacity_factor=0.25)
    params = init_params(moe_defs(cfg, jnp.float32), KEY)
    x = jax.random.normal(jax.random.PRNGKey(4), (32, 16))
    y, _ = moe_ffn(params, x, cfg)
    y_ref = moe_ffn_dense_oracle(params, x, cfg)
    # capacity-dropped tokens give zero output rows; oracle doesn't
    dropped = np.all(np.asarray(y) == 0, axis=-1)
    assert dropped.any()
    kept = ~dropped
    np.testing.assert_allclose(np.asarray(y)[kept], np.asarray(y_ref)[kept],
                               rtol=2e-5, atol=2e-5)


def test_gnn_permutation_equivariance():
    """Relabeling nodes permutes outputs correspondingly."""
    from repro.models.gnn import GNNConfig, gnn_forward, gnn_param_defs
    cfg = GNNConfig(name="t", d_feat=6, d_out=4, n_layers=2, d_hidden=16)
    params = init_params(gnn_param_defs(cfg), KEY)
    N, E = 12, 30
    rng = np.random.default_rng(0)
    feat = rng.normal(size=(N, 6)).astype(np.float32)
    src = rng.integers(0, N, E).astype(np.int32)
    dst = rng.integers(0, N, E).astype(np.int32)
    out = gnn_forward(params, {"feat": feat, "src": src, "dst": dst}, cfg)
    perm = rng.permutation(N)
    inv = np.argsort(perm)
    out_p = gnn_forward(params, {"feat": feat[perm],
                                 "src": inv[src].astype(np.int32),
                                 "dst": inv[dst].astype(np.int32)}, cfg)
    np.testing.assert_allclose(np.asarray(out)[perm], np.asarray(out_p),
                               rtol=2e-4, atol=2e-4)


def test_neighbor_sampler_subgraph_valid():
    from repro.data.graphs import NeighborSampler, padded_sizes, synth_graph
    g = synth_graph(500, avg_degree=8, d_feat=5, seed=1)
    sampler = NeighborSampler(g, fanout=(3, 2))
    seeds = np.arange(16)
    sub = sampler.sample(seeds, step=0)
    N_pad, E_pad = padded_sizes(16, (3, 2))
    assert sub["feat"].shape == (N_pad, 5)
    assert sub["src"].shape == (E_pad,)
    real = sub["src"] < N_pad
    # every real edge's dst is a previously-visited node (sampling invariant)
    assert (sub["dst"][real] < sub["n_real_nodes"]).all()
    assert sub["node_mask"].sum() == 16


def test_sharded_lookup_matches_take():
    from repro.models.embedding import sharded_lookup_shardmap
    from repro.parallel import compat
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    table = jax.random.normal(KEY, (64, 8))
    idx = jax.random.randint(jax.random.PRNGKey(5), (16,), 0, 64)
    with jax.set_mesh(mesh):
        got = sharded_lookup_shardmap(mesh, table, idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(table)[idx],
                               rtol=1e-6)


def test_bert4rec_sampled_loss_close_to_full_when_neg_covers_vocab():
    """With negatives = whole vocab, sampled CE ≈ full-softmax CE."""
    from repro.models.recsys import (RecsysConfig, masked_item_loss,
                                     masked_item_loss_sampled,
                                     recsys_param_defs)
    cfg = RecsysConfig(name="t", kind="bert4rec", embed_dim=8, seq_len=6,
                       n_blocks=1, n_heads=2, n_items=30)
    params = init_params(recsys_param_defs(cfg), KEY)
    B = 4
    rng = np.random.default_rng(0)
    seq = rng.integers(0, 30, (B, 6)).astype(np.int32)
    mask_pos = np.tile(np.array([1, 4], np.int32), (B, 1))
    labels = np.take_along_axis(seq, mask_pos, 1)
    masked = seq.copy()
    np.put_along_axis(masked, mask_pos, 31, 1)
    # full-vocab "labels grid" for the dense oracle
    full_labels = np.full((B, 6), -1, np.int32)
    np.put_along_axis(full_labels, mask_pos, labels, 1)
    l_full, _ = masked_item_loss(params, {"seq": masked,
                                          "labels": full_labels}, cfg)
    neg = np.arange(30, dtype=np.int32)
    l_samp, _ = masked_item_loss_sampled(
        params, {"seq": masked, "mask_pos": mask_pos, "labels": labels,
                 "neg_ids": neg}, cfg)
    # sampled set = vocab ∪ {gold} (gold double-counted) → small gap only
    assert abs(float(l_full) - float(l_samp)) < 0.1


def test_recsys_training_learns():
    from repro.data.recsys_data import CTRStream
    from repro.models.recsys import RecsysConfig, recsys_loss, recsys_param_defs
    from repro.train.optim import OptConfig
    from repro.train.steps import init_train_state, make_train_step
    cfg = RecsysConfig(name="t", kind="fm", n_sparse=6, embed_dim=8,
                       rows_per_field=64)
    params = init_params(recsys_param_defs(cfg), KEY)
    state = init_train_state(params)
    step = jax.jit(make_train_step(lambda p, b: recsys_loss(p, b, cfg),
                                   OptConfig(lr=0.05, warmup_steps=5,
                                             total_steps=80,
                                             weight_decay=0.0)))
    data = CTRStream(n_sparse=6, rows_per_field=64, batch=256)
    losses = []
    for i in range(80):
        state, m = step(state, data.batch_at(i))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.02

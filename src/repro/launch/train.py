"""Training driver: config → mesh → sharded jit step → fault-tolerant loop.

The conventional (non-serverless) half of the framework, bridged to the
paper's world by checkpointing into the same ObjectStore the serving fleet
hydrates from (paper §3 batch-rebuild → refresh).

CPU-runnable end to end with reduced/custom configs, e.g.:

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b \
        --preset 100m --steps 300 --batch 16 --seq 256

On a real cluster the same driver runs the full configs on the production
mesh (--mesh prod / prod-multipod).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointConfig, CheckpointManager
from repro.configs import get_arch
from repro.core.object_store import FilesystemBackend, ObjectStore
from repro.data.lm import LMDataConfig, LMTokenStream
from repro.ft.faults import FailureInjector, StragglerMonitor, run_with_restarts
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.common import init_params
from repro.parallel.sharding import tree_named
from repro.train.optim import OptConfig
from repro.train.steps import init_train_state, make_train_step


def _preset_100m(arch_mod, vocab: int = 8192):
    """~100M-param variant of an LM arch family (example driver scale),
    preserving the family's GQA ratio / MoE / MLA structure.

    ~102M params for the dense families; ≈12 s/step on a 1-core CPU host at
    batch 8 × seq 128 — 'a few hundred steps' is a real-accelerator run,
    examples/train_lm.py defaults to a shorter CPU drill."""
    import dataclasses as dc
    cfg = arch_mod.reduced_config()
    ratio = max(1, cfg.n_heads // max(cfg.n_kv_heads, 1))
    return dc.replace(cfg, n_layers=10, d_model=896, n_heads=14,
                      n_kv_heads=max(1, 14 // ratio), d_ff=2048, vocab=vocab)


def build_lm_training(arch: str, preset: str, batch: int, seq: int,
                      steps: int, lr: float):
    mod = get_arch(arch)
    if preset == "100m":
        cfg = _preset_100m(mod)
    elif preset == "reduced":
        cfg = mod.reduced_config()
    elif preset == "full":
        cfg = mod.full_config()
    else:
        raise ValueError(preset)

    from repro.models.transformer import lm_loss, lm_param_defs
    defs = lm_param_defs(cfg)
    opt_cfg = OptConfig(lr=lr, warmup_steps=min(100, steps // 10 + 1),
                        total_steps=steps)
    step_fn = make_train_step(lambda p, b: lm_loss(p, b, cfg), opt_cfg)
    data = LMTokenStream(LMDataConfig(vocab=cfg.vocab, batch=batch, seq=seq))
    return cfg, defs, step_fn, data


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--preset", default="100m",
                    choices=["100m", "reduced", "full"])
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "prod", "prod-multipod"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject failures at these steps (FT drill)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args()

    mod = get_arch(args.arch)
    if mod.FAMILY != "lm":
        raise SystemExit("train driver currently drives LM archs; "
                         "see examples/ for GNN/recsys training")

    cfg, defs, step_fn, data = build_lm_training(
        args.arch, args.preset, args.batch, args.seq, args.steps, args.lr)

    if args.mesh == "host":
        n = len(jax.devices())
        mesh = make_host_mesh((n, 1))
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "prod-multipod")
    rules = mod.rules()
    if "pod" in mesh.axis_names:
        rules = rules.with_pod()

    from repro.configs.cells import train_state_specs
    sspecs = train_state_specs(defs, rules)
    shardings = tree_named(mesh, sspecs)
    bspec = {"tokens": rules.batch_spec(None), "labels": rules.batch_spec(None)}
    bshard = tree_named(mesh, bspec)

    with jax.set_mesh(mesh):
        jstep = jax.jit(step_fn, in_shardings=(shardings, bshard),
                        donate_argnums=(0,))

        store = ObjectStore(FilesystemBackend(args.ckpt_dir))
        ckpt = CheckpointManager(
            store, name=f"{args.arch}-{args.preset}",
            config=CheckpointConfig(every_steps=args.ckpt_every))

        def init_fn():
            params = init_params(defs, jax.random.PRNGKey(0))
            return init_train_state(params)

        state, start = ckpt.restore_or_init(init_fn, shardings=shardings)
        if start:
            print(f"resumed from checkpoint step {start}")

        monitor = StragglerMonitor()
        injector = FailureInjector(fail_at=tuple(args.fail_at))
        history: list[dict] = []
        t_start = time.time()

        def one_step(state, step):
            t0 = time.perf_counter()
            batch = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, s), data.batch(step), bshard)
            state, metrics = jstep(state, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            monitor.record(step, dt)
            if step % args.log_every == 0:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"({dt * 1e3:.0f} ms/step)")
            history.append({"step": step, "loss": loss, "sec": dt})
            return state

        state, stats = run_with_restarts(
            one_step, state, args.steps, ckpt, injector=injector)
        ckpt.save(args.steps, state)
        ckpt.wait()

    wall = time.time() - t_start
    print(f"done: {args.steps} steps in {wall:.1f}s; "
          f"restarts={stats.restarts} steps_lost={stats.steps_lost} "
          f"stragglers={len(monitor.flagged)}")
    first = np.mean([h["loss"] for h in history[:10]])
    last = np.mean([h["loss"] for h in history[-10:]])
    print(f"loss: first10={first:.4f} last10={last:.4f}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump({"history": history, "restarts": stats.restarts,
                       "steps_lost": stats.steps_lost}, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

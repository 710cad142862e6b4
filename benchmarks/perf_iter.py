"""Perf-iteration runner (EXPERIMENTS.md §Perf).

Re-lowers ONE (arch × shape) cell under a named config variant on the
single-pod mesh and records the three roofline terms next to the baseline,
so every hypothesis → change → measure cycle is one command:

    PYTHONPATH=src python -m benchmarks.perf_iter \
        --cell olmoe-1b-7b/train_4k --variant ep

Variants are declared in VARIANTS below (config-field overrides per cell);
results land in benchmarks/results/perf/<cell>__<variant>.json and the
table prints with deltas vs the recorded baseline.
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import dataclasses
import json

import jax

# (cell) -> variant name -> {config field overrides}
VARIANTS: dict[str, dict[str, dict]] = {
    "olmoe-1b-7b/train_4k": {
        "gspmd-baseline": {"moe_impl": "gspmd"},
        "ep": {"moe_impl": "ep"},
        "ep-dots": {"moe_impl": "ep", "remat_policy": "dots_saveable"},
        "ep-noremat": {"moe_impl": "ep", "remat_policy": "none"},
        "ep-bq1024": {"moe_impl": "ep", "attn_block_q": 1024},
        "ep-bq2048": {"moe_impl": "ep", "attn_block_q": 2048},
    },
    "deepseek-v2-236b/train_4k": {
        "ep-baseline": {"moe_impl": "ep"},
        "gspmd": {"moe_impl": "gspmd"},
        "ep-dots": {"moe_impl": "ep", "remat_policy": "dots_saveable"},
    },
    "stablelm-3b/prefill_32k": {
        "baseline": {},
        "bq1024": {"attn_block_q": 1024},
        "bq2048": {"attn_block_q": 2048},
        "noremat": {"remat_policy": "none"},
    },
    "stablelm-3b/train_4k": {
        "baseline": {},
        "dots": {"remat_policy": "dots_saveable"},
        "noremat": {"remat_policy": "none"},
        "bq1024": {"attn_block_q": 1024},
        "bq2048": {"attn_block_q": 2048},
        "bq2048-dots": {"attn_block_q": 2048,
                        "remat_policy": "dots_saveable"},
    },
    "h2o-danube-1.8b/prefill_32k": {
        "baseline": {},
        "bq2048": {"attn_block_q": 2048},
    },
    "graphcast/ogb_products": {
        "baseline": {},
        "dots": {"remat_policy": "dots_saveable"},
        "noremat": {"remat_policy": "none"},
    },
    "starcoder2-3b/prefill_32k": {
        "baseline": {},
        "kv-replicated": {"shard_kv_proj": False},
        "kv-replicated-bq2048": {"shard_kv_proj": False,
                                 "attn_block_q": 2048},
    },
    "starcoder2-3b/train_4k": {
        "baseline": {},
        "kv-replicated": {"shard_kv_proj": False},
    },
    "h2o-danube-1.8b/train_4k": {
        "baseline": {},
        "kv-replicated": {"shard_kv_proj": False},
    },
    "bert4rec/serve_bulk": {
        "baseline": {},
        "sharded-topk": {"sharded_topk": True},
    },
    "anlessini/serve_q64": {
        "baseline": {},
        "compact-ids": {"compact_ids": True},
        "fused-gather": {"fused_gather": True},
        "compact+fused": {"compact_ids": True, "fused_gather": True},
        "compact+fused+m16": {"compact_ids": True, "fused_gather": True,
                              "max_blocks": 16},
        "pruned": {"accumulator": "pruned"},
        "pruned+compact+fused": {"accumulator": "pruned",
                                 "compact_ids": True, "fused_gather": True},
    },
    "anlessini/serve_q1": {
        "baseline": {},
        "compact+fused": {"compact_ids": True, "fused_gather": True},
        "pruned": {"accumulator": "pruned"},
    },
}

PERF_DIR = os.path.join(os.path.dirname(__file__), "results", "perf")


def build_variant_cell(arch: str, shape: str, over: dict):
    """Rebuild one full-config cell with config overrides applied."""
    from repro.configs import get_arch
    from repro.configs.cells import gnn_cells, lm_cells, recsys_cells
    mod = get_arch(arch)
    rules = mod.rules()
    fam = mod.FAMILY
    if fam == "search":
        # late-bound cell: wrap build() to apply config overrides
        cell = mod.cells(rules)[shape]

        def build(mesh):
            import repro.configs.anlessini as an
            from repro.search.distributed import (abstract_dist_state,
                                                  dist_state_specs,
                                                  make_dist_search_fn)
            import jax.numpy as _jnp
            from repro.configs.cells import SDS
            from jax.sharding import PartitionSpec as _P
            axes = tuple(rules.batch) + ("model",)
            n_parts = 1
            for ax in axes:
                n_parts *= mesh.shape[ax]
            cfg = dataclasses.replace(an.full_config(n_parts), **over)
            fn = make_dist_search_fn(cfg, axes, mesh=mesh)
            Q = an.SHAPES[shape]["Q"]
            args = (abstract_dist_state(cfg),
                    SDS((Q, cfg.max_terms), _jnp.int32),
                    SDS((Q, cfg.max_terms), _jnp.float32))
            specs = (dist_state_specs(axes), _P(None, None), _P(None, None))
            return fn, args, specs

        cell.build = build
        return cell
    if fam == "lm":
        cfg = mod.full_config(unroll=True,
                              ep_batch_axes=tuple(rules.batch))
        cfg = dataclasses.replace(cfg, **over)
        return lm_cells(arch, cfg, rules)[shape]
    if fam == "gnn":
        from repro.configs.cells import GNN_SHAPES
        cfg = mod.full_config(d_feat=GNN_SHAPES[shape]["d_feat"], unroll=True)
        cfg = dataclasses.replace(cfg, **over)
        return gnn_cells(arch, cfg, rules)[shape]
    if fam == "recsys":
        cfg = dataclasses.replace(mod.full_config(unroll=True), **over)
        return recsys_cells(arch, cfg, rules)[shape]
    raise ValueError(fam)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default=None)
    ap.add_argument("--variant", default=None,
                    help="one variant (default: all declared for the cell)")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    if not args.cell:
        ap.error("--cell is required")

    from repro.launch.dryrun import run_cell
    from repro.launch.mesh import make_production_mesh

    arch, shape = args.cell.split("/")
    variants = VARIANTS.get(args.cell, {"baseline": {}})
    if args.variant:
        variants = {args.variant: variants[args.variant]}

    mesh = make_production_mesh()
    os.makedirs(PERF_DIR, exist_ok=True)
    rows = []
    for vname, over in variants.items():
        cell = build_variant_cell(arch, shape, over)
        name = f"{args.cell}@{vname}"
        rec = run_cell(name, cell, mesh, "pod1_16x16", PERF_DIR,
                       force=args.force)
        rows.append((vname, rec))

    print(f"\n{'variant':18s} {'flops/dev':>11s} {'bytes/dev':>11s} "
          f"{'coll B/dev':>11s} {'temp GiB':>9s} {'compile s':>9s}")
    for vname, rec in rows:
        if not rec.get("ok"):
            print(f"{vname:18s} FAIL {rec.get('error', '')[:70]}")
            continue
        pd = rec["per_device"]
        print(f"{vname:18s} {pd['flops']:11.3e} {pd['bytes_accessed']:11.3e} "
              f"{rec['collectives']['total_bytes']:11.3e} "
              f"{pd['temp_bytes'] / 2**30:9.2f} {rec['compile_s']:9.1f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

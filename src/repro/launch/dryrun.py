import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape) cell
on the production meshes, and dump memory/cost/collective analysis.

The two lines above MUST stay the first two statements of this module —
jax locks the device count on first init, and the dry-run needs 512
placeholder CPU devices to build the (pod=2, data=16, model=16) mesh.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                 # all cells, both meshes
    PYTHONPATH=src python -m repro.launch.dryrun --arch fm --shape train_batch
    PYTHONPATH=src python -m repro.launch.dryrun --multi-pod     # 2×16×16 only
    PYTHONPATH=src python -m repro.launch.dryrun --force         # ignore cache

Per cell it writes benchmarks/results/dryrun/<mesh>/<arch>__<shape>.json with
per-device FLOPs, bytes, peak memory, and collective-bytes-by-op parsed from
the post-SPMD optimized HLO — the inputs to the roofline analysis
(benchmarks/roofline.py, EXPERIMENTS.md §Roofline).
"""

import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import all_cells, build_cells
from repro.launch.mesh import make_production_mesh

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device collective bytes by op, from post-partitioning HLO.

    Convention: bytes = output-shape bytes; all-reduce counted twice
    (ring = send+recv of ~the full payload each way)."""
    out: dict[str, float] = {}
    counts: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape, op = m.group(1), m.group(2)
        b = _shape_bytes(shape)
        if op == "all-reduce":
            b *= 2
        out[op] = out.get(op, 0) + b
        counts[op] = counts.get(op, 0) + 1
    return {"bytes_by_op": out, "counts": counts,
            "total_bytes": float(sum(out.values()))}


def run_cell(name: str, cell, mesh, mesh_name: str, out_dir: str,
             *, force: bool = False, verbose: bool = True) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, name.replace("/", "__") + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            rec = json.load(f)
        if rec.get("ok") or rec.get("skip"):
            if verbose:
                print(f"[cache] {mesh_name} {name}: "
                      f"{'skip' if rec.get('skip') else 'ok'}")
            return rec

    if cell.skip:
        rec = {"cell": name, "mesh": mesh_name, "skip": True,
               "note": cell.note}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        if verbose:
            print(f"[skip ] {mesh_name} {name}: {cell.note[:80]}")
        return rec

    t0 = time.time()
    try:
        if hasattr(cell, "build"):                 # late-bound (anlessini)
            fn, args, specs = cell.build(mesh)
        else:
            fn, args, specs = cell.fn, cell.args, cell.in_specs
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=shardings,
                             donate_argnums=cell.donate)
            lowered = jitted.lower(*args)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        rec = {
            "cell": name, "mesh": mesh_name, "ok": True,
            "kind": cell.kind,
            "compile_s": round(time.time() - t0, 2),
            "per_device": {
                "flops": float(cost.get("flops", 0.0)),
                "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "peak_bytes": int(mem.peak_memory_in_bytes),
            },
            "collectives": coll,
            "hlo_bytes": len(hlo),
        }
        if verbose:
            pd = rec["per_device"]
            print(f"[ok   ] {mesh_name} {name}: "
                  f"flops/dev={pd['flops']:.3g} "
                  f"bytes/dev={pd['bytes_accessed']:.3g} "
                  f"peak={pd['peak_bytes'] / 2**30:.2f}GiB "
                  f"coll={coll['total_bytes']:.3g}B "
                  f"({rec['compile_s']}s)")
    except Exception as e:
        rec = {"cell": name, "mesh": mesh_name, "ok": False,
               "error": f"{type(e).__name__}: {e}",
               "compile_s": round(time.time() - t0, 2)}
        if verbose:
            print(f"[FAIL ] {mesh_name} {name}: {rec['error'][:160]}")
            traceback.print_exc(limit=4)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape name")
    ap.add_argument("--multi-pod", action="store_true",
                    help="only the 2×16×16 mesh")
    ap.add_argument("--single-pod", action="store_true",
                    help="only the 16×16 mesh")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--reduced", action="store_true", help="debug: tiny configs")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    meshes = []
    if not args.multi_pod:
        meshes.append(("pod1_16x16", False))
    if not args.single_pod:
        meshes.append(("pod2_2x16x16", True))

    base_out = args.out or os.path.normpath(RESULTS_DIR)
    n_fail = 0
    for mesh_name, multi_pod in meshes:
        mesh = make_production_mesh(multi_pod=multi_pod)
        if args.arch:
            cells = {f"{args.arch}/{k}": v for k, v in build_cells(
                args.arch, multi_pod=multi_pod, reduced=args.reduced).items()}
        else:
            cells = all_cells(multi_pod=multi_pod, reduced=args.reduced)
        if args.shape:
            cells = {k: v for k, v in cells.items()
                     if k.endswith("/" + args.shape)}
        out_dir = os.path.join(base_out, mesh_name)
        for name, cell in cells.items():
            rec = run_cell(name, cell, mesh, mesh_name, out_dir,
                           force=args.force)
            if not (rec.get("ok") or rec.get("skip")):
                n_fail += 1
    print(f"\ndry-run complete; failures: {n_fail}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())

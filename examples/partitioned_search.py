"""Document-partitioned search two ways (paper §3's scale-out path):

1. FLEET-LEVEL: ``build_partitioned_search_app`` — one Lambda function +
   one published segment per partition (packed with GLOBAL idf/avgdl by
   the one true packer, ``IndexWriter``), ``/search`` routed through the
   Gateway → ScatterGather → merge. All partitions fan out at the same
   arrival instant, so latency is max-over-partitions; a list of queries
   micro-batches as ONE invocation per partition (Q>1 through the same
   vmapped scoring fn).
2. MESH-LEVEL: the same partitioning as a single shard_map program over a
   device mesh — each device owns a partition and runs the same scoring
   core (``bm25.score_dense``), global top-k via all-gather-merge. On this
   CPU container the mesh is 1×1 (set
   XLA_FLAGS=--xla_force_host_platform_device_count=4 to see 4 real
   partitions); on the production mesh it is 16×16.

Both must agree with the exact BM25 oracle — and with each other, because
scoring and packing each have exactly one implementation.

    PYTHONPATH=src python examples/partitioned_search.py
"""

import jax
from jax.sharding import NamedSharding

from repro.core.partition import FleetSpec, ReplicationSpec
from repro.data.corpus import synth_corpus, synth_queries
from repro.parallel import compat
from repro.search.bm25 import encode_queries
from repro.search.distributed import (build_partitioned_state,
                                      dist_state_specs, make_dist_search_fn)
from repro.search.oracle import OracleSearcher
from repro.search.service import build_partitioned_search_app

N_PARTS = 4
docs = synth_corpus(2_000, vocab=3_000, seed=0)
queries = synth_queries(docs, 5, seed=1)
oracle = OracleSearcher(docs)

# -- 1. fleet-level scatter-gather ------------------------------------------------
print(f"== fleet-level: {N_PARTS} Lambda functions, scatter-gather ==")
app = build_partitioned_search_app(docs, FleetSpec(n_parts=N_PARTS))

for q in queries:
    r = app.query(q, k=10)
    got = r.body["ids"]                      # already globalized by the app
    want = [d for d, _ in oracle.search(q, k=10)]
    ok = got[:3] == want[:3]
    cold = sum(p["cold"] for p in r.body["partitions"])
    print(f"  '{q[:28]:30s}' lat={r.latency_s * 1e3:7.1f} ms top3 "
          f"{'==' if ok else '!='} oracle  ({cold}/{N_PARTS} cold)")

# micro-batch: all 5 queries in ONE invocation per partition
r = app.query(queries, k=10, t_arrival=app.runtime.clock + 1)
n_ok = sum(res["ids"][:3] == [d for d, _ in oracle.search(q, k=3)]
           for q, res in zip(queries, r.body["results"]))
print(f"  batch Q={len(queries)}: {len(r.body['partitions'])} invocations, "
      f"lat={r.latency_s * 1e3:.1f} ms, {n_ok}/{len(queries)} top3 == oracle")
print(f"  fleet={app.runtime.fleet_size}, warm={app.runtime.warm_fraction():.0%}, "
      f"cost=${app.runtime.ledger.total_dollars:.6f}")

# -- 1b. replicated partitions + hedged scatter legs ------------------------------
# Each segment is served by TWO independent instance pools; when a primary
# projects a cold start (we kill its instance), the scatter leg fires a
# backup on the replica at the same arrival instant and the warm pool wins —
# the tail flattens, the ledger shows the hedging tax, results stay
# bit-identical (same PackedIndex behind every replica).
print(f"\n== replicated: {N_PARTS} partitions x 2 replicas, hedged legs ==")
from repro.core.partition import HedgePolicy  # noqa: E402

happ = build_partitioned_search_app(docs, FleetSpec(
    n_parts=N_PARTS, replication=ReplicationSpec(replicas=2, hedge=HedgePolicy())))
happ.warm()
for q in queries:                                 # warm traffic → policy history
    happ.query(q, k=10, t_arrival=happ.runtime.clock + 0.05, fetch_docs=False)
for q in queries:
    happ.runtime.kill_instance(fn=happ.fn_names[0])   # partition 0 goes cold
    r = happ.query(q, k=10, t_arrival=happ.runtime.clock + 0.05,
                   fetch_docs=False)
    hedged = [p["fn"] for p in r.body["partitions"] if p["hedged"]]
    ok = r.body["ids"][:3] == [d for d, _ in oracle.search(q, k=10)][:3]
    print(f"  '{q[:28]:30s}' lat={r.latency_s * 1e3:7.1f} ms top3 "
          f"{'==' if ok else '!='} oracle  hedged={hedged or '-'}")
led = happ.runtime.ledger
print(f"  hedge tax: ${led.hedge_dollars:.8f} of ${led.total_dollars:.6f} "
      f"({led.hedge_invocations} backup legs)")

# -- 2. mesh-level shard_map ---------------------------------------------------------
n_dev = len(jax.devices())
shape = {1: (1, 1), 2: (2, 1), 4: (2, 2), 8: (4, 2)}.get(n_dev, (1, 1))
n_mesh_parts = shape[0] * shape[1]        # one partition per device
print(f"\n== mesh-level: shard_map over {shape} device mesh "
      f"({n_mesh_parts} partitions) ==")
state, cfg, vocab = build_partitioned_state(docs, n_mesh_parts,
                                            {"k": 10, "max_blocks": 64})
mesh = compat.make_mesh(shape, ("data", "model"))
fn = make_dist_search_fn(cfg, ("data", "model"), mesh=mesh)
tids, qtf = encode_queries(vocab, queries, max_terms=cfg.max_terms,
                           idf=state["idf"])
# each device holds its own partition: place the stacked state with the
# path's NamedShardings instead of copying it whole onto device 0
specs = dist_state_specs(("data", "model"))
placed = {name: jax.device_put(arr, NamedSharding(mesh, specs[name]))
          for name, arr in state.items()}
scores, ids = jax.jit(fn)(placed, tids, qtf)

for qi, q in enumerate(queries):
    want = [d for d, _ in oracle.search(q, k=10)]
    got = [int(i) for v, i in zip(scores[qi], ids[qi]) if v > 0]
    ok = got[:3] == want[:3]
    print(f"  '{q[:28]:30s}' top3 {'==' if ok else '!='} oracle "
          f"({[round(float(v), 2) for v in scores[qi][:3]]})")

print("\nboth realizations run the SAME scoring core (bm25.score_dense) over "
      "the SAME packing (IndexWriter): per-partition BM25 + k-survivor merge "
      "— paper §3, now actually 'a matter of software engineering'.")

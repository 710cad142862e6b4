#!/usr/bin/env python3
"""Chip smoke test: the served search path, end to end, on a TPU.

    python chip_smoke.py                 # one chip: sparse fleet + hybrid fleet
    python chip_smoke.py --docs 1000000  # a smaller sparse corpus
    python chip_smoke.py --chips 4       # four chips: the mesh shard_map path only

One chip (the default) runs two phases through the normal entry points
(FleetSpec -> Gateway -> ScatterGather -> search handler -> device ->
merge -> KV fetch), the short one first:

* sparse: a deployment shaped like MS MARCO passage (Bajaj et al.,
  arXiv:1611.09268; 8,841,823 passages) at one chip's share of a
  four-chip layout (2,210,456 docs), served by a 4-partition fleet with
  the default SearchConfig. Queries go through ``app.query`` and then, as
  one burst, through ``app.submit`` + ``flush`` (the windowed batch path).
  Every top-10 is checked against an exact float64 BM25 reference computed
  from the raw text, sharing no code with the packed index.
* hybrid: a smaller fleet with a 768-dim dense tier, so the compiled
  ``dot_topk`` Pallas kernel serves; dense top-10s are checked against
  exact float64 dot products under the same rule, and their ids must
  equal ``DenseOracleSearcher``'s (whose XLA matvec need not round like
  the Mosaic kernel's on a TPU, so its score bits are only reported);
  hybrid results must equal ``hybrid_oracle_fuse`` exactly, both over the
  two oracles' rankings and over the two rankings the fleet served.

``--chips 4`` runs only the multi-device path: the same corpus split four
ways over a (2, 2) mesh, the stacked state placed with its NamedShardings,
checked against the same reference.

With no TPU the script exits 2 before building anything; any failed check
exits 1. The last line of stdout, on success only, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import statistics
import sys
import time
from collections import Counter
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

W1_PASSAGES = 8_841_823          # MS MARCO passage collection
W1_CHIP_DOCS = 2_210_456         # one chip's share of a four-chip layout
W1_VOCAB = 1 << 19               # configs/anlessini.py's vocabulary
K = 10
MAX_BLOCKS, BLOCK = 64, 128      # SearchConfig.max_blocks x lanes per block
DF_CAP = MAX_BLOCKS * BLOCK      # longer posting lists are truncated (R1)
K1, B = 0.9, 0.4                 # BM25 constants the index packs with
TF_CAP = 255                     # tf is stored as uint8
RTOL = 1e-5                      # f32 on the device vs float64 here
N_CANDIDATES = 6000              # candidate queries drawn per corpus
N_QUERIES = 16                   # sparse queries per path (query, submit)
DENSE_DIM = 768
DENSE_DOCS, DENSE_QUERIES = 50_000, 8

RULE = (f"parity rule: every returned score within {RTOL:g} relative of the "
        f"float64 reference score at its rank; ids equal rank for rank, "
        f"except where the returned doc's reference score is within "
        f"{RTOL:g} relative of that rank's (a near-tie)")
ORACLE_RULE = ("oracle rule: dense ids equal DenseOracleSearcher's rank for "
               "rank; hybrid ids and fused scores equal hybrid_oracle_fuse "
               "over the OracleSearcher and DenseOracleSearcher rankings, "
               "exactly")


def log(msg: str) -> None:
    print(msg, flush=True)


# -- the exact reference -----------------------------------------------------


class Reference:
    """Float64 BM25 over the raw text, for a fixed set of terms.

    One pass over the corpus records every document's length and, for the
    given terms only, document frequencies and (doc, tf) postings. Postings
    are kept for terms whose df stays within ``DF_CAP`` only; longer lists
    are never queried, because the index truncates them."""

    def __init__(self, docs, terms: set[str]):
        from repro.index.tokenizer import tokenize
        self.ext_ids = [d for d, _ in docs]
        self.n = len(docs)
        self.dl = np.zeros(self.n, np.float64)
        self.df = dict.fromkeys(terms, 0)
        self.postings: dict[str, list[tuple[int, int]]] = {t: [] for t in terms}
        for i, (_, text) in enumerate(docs):
            toks = tokenize(text)
            self.dl[i] = len(toks)
            for t in terms.intersection(toks):
                self.df[t] += 1
                if self.df[t] <= DF_CAP:
                    self.postings[t].append((i, toks.count(t)))
        self.avgdl = float(self.dl.sum()) / self.n

    def scores(self, query: str) -> dict[int, float]:
        from repro.index.tokenizer import tokenize
        out: dict[int, float] = {}
        for t, qtf in Counter(tokenize(query)).items():
            df = self.df[t]
            idf = math.log(1.0 + (self.n - df + 0.5) / (df + 0.5))
            for d, tf in self.postings[t]:
                tf = min(tf, TF_CAP)
                norm = K1 * (1.0 - B + B * self.dl[d] / self.avgdl)
                out[d] = out.get(d, 0.0) + qtf * idf * tf / (tf + norm)
        return out


def candidate_queries(docs, seed: int) -> list[str]:
    """Queries of 1-3 distinct terms drawn from random documents."""
    from repro.index.tokenizer import tokenize
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < N_CANDIDATES:
        toks = sorted(set(tokenize(docs[int(rng.integers(len(docs)))][1])))
        n = min(1 + len(out) % 3, len(toks))
        if n:
            pick = rng.choice(len(toks), size=n, replace=False)
            out.append(" ".join(toks[int(j)] for j in pick))
    return out


def select_queries(docs, n_queries: int, seed: int):
    """(queries, reference): the first ``n_queries`` candidates whose every
    term has df <= DF_CAP, and the reference built over their terms."""
    from repro.index.tokenizer import tokenize
    cands = candidate_queries(docs, seed)
    terms = {t for q in cands for t in tokenize(q)}
    ref = Reference(docs, terms)
    chosen, examined = [], 0
    for q in cands:
        if len(chosen) == n_queries:
            break
        examined += 1
        if all(ref.df[t] <= DF_CAP for t in tokenize(q)):
            chosen.append(q)
    if len(chosen) < n_queries:
        raise RuntimeError(f"only {len(chosen)} of {N_CANDIDATES} candidate "
                           f"queries have every term's df <= {DF_CAP}")
    log(f"queries: {len(chosen)} chosen of {examined} candidates examined; "
        f"{examined - len(chosen)} excluded for a term with df > {DF_CAP} "
        f"(= max_blocks {MAX_BLOCKS} x block {BLOCK}, longer lists are "
        f"truncated)")
    return chosen, ref


def compare(tag: str, query: str, got_ext: list[str], got_scores: list[float],
            scores: dict[int, float], ext_ids: list[str]) -> list[str]:
    """Check one returned top-K against exact reference ``scores`` (doc
    index -> score) under RULE; returns the failures (empty when it holds)
    and logs one line."""
    want = sorted(scores.items(), key=lambda kv: (-kv[1], kv[0]))[:K]
    errs, ties, worst = [], 0, 0.0
    if len(got_ext) != len(want):
        errs.append(f"{len(got_ext)} hits, reference has {len(want)}")
    if len(set(got_ext)) != len(got_ext):
        errs.append("duplicate ids")
    index = {ext_ids[d]: d for d, _ in want}
    for r, ((d_want, s_want), e_got, s_got) in enumerate(
            zip(want, got_ext, got_scores)):
        tol = RTOL * abs(s_want)
        worst = max(worst, abs(s_got - s_want) / abs(s_want))
        if abs(s_got - s_want) > tol:
            errs.append(f"rank {r}: score {s_got!r} vs reference {s_want!r}")
        if e_got != ext_ids[d_want]:
            d_got = index.get(e_got, int(e_got[3:]))    # ext ids are doc{i}
            if abs(scores.get(d_got, 0.0) - s_want) > tol:
                errs.append(f"rank {r}: {e_got} where the reference has "
                            f"{ext_ids[d_want]}")
            else:
                ties += 1
    verdict = "==" if not errs else "!="
    log(f"  {tag} {query!r}: top-{len(got_ext)} {verdict} reference "
        f"(near-tie swaps {ties}, max rel err {worst:.3g})")
    for e in errs:
        log(f"    FAIL {e}")
    return errs


# -- timing and device accounting ----------------------------------------------


class CompileLog:
    """Backend compile count and seconds, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax
        self.n, self.s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1
            self.s += duration


def device_bytes() -> str:
    import jax
    live = sum(a.nbytes for a in jax.live_arrays())
    stats = jax.devices()[0].memory_stats() or {}
    in_use = stats.get("bytes_in_use")
    return (f"live jax arrays {live / 1e9:.3f} GB; device 0 bytes_in_use "
            f"{'n/a' if in_use is None else f'{in_use / 1e9:.3f} GB'}")


def next_arrival(t: float, resp) -> float:
    """An arrival after every instance the response used is free again, so
    the next query lands on the warm pool instead of provisioning one."""
    backfill = max((p["backfill_s"] for p in resp.body["partitions"]),
                   default=0.0)
    return t + resp.latency_s + backfill + 0.01


def check_response(resp, app, errs: list[str], tag: str) -> bool:
    if resp.status != 200:
        errs.append(f"{tag}: status {resp.status} {resp.body}")
        log(f"  FAIL {tag}: status {resp.status} {resp.body}")
        return False
    if app.scatter.last_degraded:
        errs.append(f"{tag}: degraded, partitions {app.scatter.last_degraded}")
        log(f"  FAIL {tag}: degraded {app.scatter.last_degraded}")
        return False
    return True


# -- phases ---------------------------------------------------------------------


def run_sparse(n_docs: int, n_queries: int, seed: int, clog: CompileLog
               ) -> list[str]:
    """The main path: a 4-partition BM25 fleet at the W1 chip share."""
    from repro.core.gateway import WindowPolicy
    from repro.core.partition import FleetSpec, GatewaySpec
    from repro.data.corpus import synth_corpus
    from repro.search.service import build_partitioned_search_app

    log(f"== sparse: MS MARCO passage shape ({W1_PASSAGES:,} passages), "
        f"{n_docs:,} docs = one chip's share, 4-partition fleet ==")
    t0 = time.perf_counter()
    docs = synth_corpus(n_docs, vocab=W1_VOCAB, seed=seed)
    log(f"corpus: {len(docs):,} docs, vocab {W1_VOCAB:,}, "
        f"{time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    queries, ref = select_queries(docs, 2 * n_queries, seed + 1)
    log(f"reference pass: {time.perf_counter() - t0:.1f} s, avgdl "
        f"{ref.avgdl:.4f}")
    log(RULE)
    # the burst must coalesce into one window: hold the window open for
    # any arrival rate and ignore the cold queries' p99
    spec = FleetSpec(n_parts=4, gateway=GatewaySpec(
        window=WindowPolicy(sparse_qps=0.0, p99_budget_s=None)))
    t0 = time.perf_counter()
    app = build_partitioned_search_app(docs, spec)
    log(f"build: {time.perf_counter() - t0:.1f} s, 4 partitions of "
        f"{app.n_docs_local:,} docs")

    errs: list[str] = []
    t, walls = 0.0, []
    c0 = (clog.n, clog.s)
    for i, q in enumerate(queries[:n_queries]):
        w0 = time.perf_counter()
        resp = app.query(q, k=K, t_arrival=t)
        walls.append(time.perf_counter() - w0)
        if check_response(resp, app, errs, f"query {i}"):
            errs += compare(f"query {i:2d}", q, resp.body["ext_ids"],
                            resp.body["scores"], ref.scores(q), ref.ext_ids)
            t = next_arrival(t, resp)
    log(f"query path: first (cold: hydrate + compile) {walls[0]:.2f} s, "
        f"median of the rest {statistics.median(walls[1:]):.4f} s wall; "
        f"{clog.n - c0[0]} compiles, {clog.s - c0[1]:.1f} s compiling")

    burst = queries[n_queries:]
    c0 = (clog.n, clog.s)
    w0 = time.perf_counter()
    handles = [app.submit(q, k=K, t_arrival=t + 0.001 * j)
               for j, q in enumerate(burst)]
    app.flush()
    wall = time.perf_counter() - w0
    ws = app.gateway.window_stats("GET", "/search")
    first = handles[0].response
    legs = first.body["partitions"] if first.status == 200 else []
    log(f"windowed batch path: {len(burst)} submits in "
        f"{ws['batches']} window(s) (sizes {ws['mean_batch']:.1f} mean), "
        f"{wall:.2f} s wall; {clog.n - c0[0]} compiles, "
        f"{clog.s - c0[1]:.1f} s compiling; cold partition legs "
        f"{sum(p['cold'] for p in legs)} of {len(legs)}")
    for j, (q, h) in enumerate(zip(burst, handles)):
        resp = h.response
        if check_response(resp, app, errs, f"submit {j}"):
            errs += compare(f"submit {j:2d}", q, resp.body["ext_ids"],
                            resp.body["scores"], ref.scores(q), ref.ext_ids)
    log(f"device: {device_bytes()}")
    log(f"compiles so far: {clog.n}, {clog.s:.1f} s")
    return errs


def bits(xs) -> list[int]:
    return np.asarray(xs, np.float32).view(np.uint32).tolist()


def run_dense(n_docs: int, n_queries: int, seed: int) -> list[str]:
    """A hybrid fleet, so the compiled dot_topk kernel serves the dense tier."""
    from repro.core.partition import FleetSpec, IndexSpec, VectorSpec
    from repro.data.corpus import synth_corpus
    from repro.search.oracle import (DenseOracleSearcher, OracleSearcher,
                                     hybrid_oracle_fuse)
    from repro.search.service import build_partitioned_search_app

    log(f"== hybrid: {n_docs:,} docs, {DENSE_DIM}-dim vectors, "
        f"4-partition fleet, mode=dense and mode=hybrid ==")
    docs = synth_corpus(n_docs, vocab=W1_VOCAB, seed=seed)
    queries, ref = select_queries(docs, n_queries, seed + 1)
    t0 = time.perf_counter()
    app = build_partitioned_search_app(docs, FleetSpec(
        n_parts=4, index=IndexSpec(vector=VectorSpec(dim=DENSE_DIM))))
    log(f"build: {time.perf_counter() - t0:.1f} s")
    log(RULE)
    log(ORACLE_RULE)
    live = app.indexer.live_corpus()
    oracle = DenseOracleSearcher(live, app.embedder)
    sparse_oracle = OracleSearcher(live)

    vectors = oracle.vectors.astype(np.float64)
    errs: list[str] = []
    t, n_bits = 0.0, 0
    for i, q in enumerate(queries):
        sparse = app.query(q, k=K, t_arrival=t, fetch_docs=False)
        if not check_response(sparse, app, errs, f"sparse {i}"):
            continue
        errs += compare(f"sparse {i}", q, sparse.body["ext_ids"],
                        sparse.body["scores"], ref.scores(q), ref.ext_ids)
        t = next_arrival(t, sparse)

        dense = app.query(q, k=K, mode="dense", t_arrival=t, fetch_docs=False)
        if not check_response(dense, app, errs, f"dense {i}"):
            continue
        t = next_arrival(t, dense)
        # the exact float64 dots decide near-ties, as for BM25
        exact = vectors @ np.asarray(app.embedder(q), np.float64)
        top = np.argsort(-exact, kind="stable")[:4 * K]
        errs += compare(f"dense  {i}", q, dense.body["ext_ids"],
                        dense.body["scores"],
                        {int(d): float(exact[d]) for d in top}, oracle.doc_ids)
        want = oracle.search(q, k=app.search_k)
        got, exp = bits(dense.body["scores"]), bits([v for _, v in want[:K]])
        same_ids = dense.body["ext_ids"] == [oracle.doc_ids[d]
                                             for d, _ in want[:K]]
        n_bits += same_ids and got == exp
        log(f"    vs DenseOracleSearcher: ids {'==' if same_ids else '!='}, "
            f"scores " + ("bit-identical" if got == exp else
                          "differ by up to " + str(max(
                              abs(a - b) for a, b in zip(got, exp)))
                          + " ulp"))
        if not same_ids:
            errs.append(f"dense {i}: ids differ from DenseOracleSearcher")

        hybrid = app.query(q, k=K, mode="hybrid", t_arrival=t,
                           fetch_docs=False)
        if not check_response(hybrid, app, errs, f"hybrid {i}"):
            continue
        t = next_arrival(t, hybrid)
        # fuse the two oracles' rankings, and the two rankings the fleet
        # served (each just checked), the way the coordinator does; every
        # hit is a global doc index
        for src, fused in (
                ("the oracles'", hybrid_oracle_fuse(
                    sparse_oracle.search(q, k=app.search_k), want, K)),
                ("the served", hybrid_oracle_fuse(
                    list(zip(sparse.body["ids"], sparse.body["scores"])),
                    list(zip(dense.body["ids"], dense.body["scores"])), K))):
            ok = (hybrid.body["ext_ids"]
                  == [oracle.doc_ids[d] for d, _ in fused]
                  and list(hybrid.body["scores"]) == [v for _, v in fused])
            log(f"  hybrid {i}: fused top-{len(fused)} "
                f"{'==' if ok else '!='} hybrid_oracle_fuse over {src} "
                f"rankings")
            if not ok:
                errs.append(f"hybrid {i}: not equal to hybrid_oracle_fuse "
                            f"over {src} rankings")
    log(f"dense tier: {n_bits} of {len(queries)} queries uint32-identical "
        f"to DenseOracleSearcher (ids and score bits)")
    log(f"device: {device_bytes()}")
    return errs


def dense_kernel_compiled(n_rows: int) -> list[str]:
    """The served ``dot_topk`` (``interpret`` left to the backend) lowers
    to a Mosaic kernel here, not to the interpreter."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.dot_topk import dot_topk
    from repro.kernels.interpret import resolve_interpret
    hlo = dot_topk.lower(
        jax.ShapeDtypeStruct((DENSE_DIM,), jnp.float32),
        jax.ShapeDtypeStruct((n_rows, DENSE_DIM), jnp.float32), K
    ).compile().as_text()
    ok = not resolve_interpret(None) and "tpu_custom_call" in hlo
    log(f"dense tier kernel: dot_topk at ({n_rows:,}, {DENSE_DIM}) "
        f"{'compiles to a Mosaic kernel' if ok else 'is NOT a Mosaic kernel'}"
        f" (interpret resolves to {resolve_interpret(None)})")
    return [] if ok else ["dot_topk does not compile to a Mosaic kernel"]


def run_mesh(n_docs: int, n_queries: int, seed: int) -> list[str]:
    """Four chips: the mesh shard_map path over the same corpus."""
    import jax
    from jax.sharding import NamedSharding

    from repro.data.corpus import synth_corpus
    from repro.parallel import compat
    from repro.search.bm25 import encode_queries
    from repro.search.distributed import (build_partitioned_state,
                                          dist_state_specs,
                                          make_dist_search_fn)

    axes = ("data", "model")
    log(f"== mesh: {n_docs:,} docs split four ways over a (2, 2) mesh ==")
    t0 = time.perf_counter()
    docs = synth_corpus(n_docs, vocab=W1_VOCAB, seed=seed)
    log(f"corpus: {len(docs):,} docs, {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    queries, ref = select_queries(docs, n_queries, seed + 1)
    log(f"reference pass: {time.perf_counter() - t0:.1f} s")
    log(RULE)
    t0 = time.perf_counter()
    state, cfg, vocab = build_partitioned_state(
        docs, 4, {"k": K, "max_blocks": MAX_BLOCKS})
    log(f"build: {time.perf_counter() - t0:.1f} s, 4 partitions of "
        f"{cfg.n_docs_local:,} docs, {cfg.n_blocks_local:,} blocks each")
    mesh = compat.make_mesh((2, 2), axes)
    specs = dist_state_specs(axes)
    t0 = time.perf_counter()
    placed = {name: jax.device_put(arr, NamedSharding(mesh, specs[name]))
              for name, arr in state.items()}
    jax.block_until_ready(placed)
    per_dev = {d.id: 0 for d in mesh.devices.flat}
    for arr in placed.values():
        for shard in arr.addressable_shards:
            per_dev[shard.device.id] += shard.data.nbytes
    log(f"placed: {time.perf_counter() - t0:.1f} s, bytes per device "
        f"{sorted(per_dev.items())}")
    fn = jax.jit(make_dist_search_fn(cfg, axes, mesh=mesh))
    tids, qtf = encode_queries(vocab, queries, max_terms=cfg.max_terms,
                               idf=state["idf"])
    t0 = time.perf_counter()
    vals, ids = jax.block_until_ready(fn(placed, tids, qtf))
    log(f"first call (compile + run): {time.perf_counter() - t0:.2f} s")
    t0 = time.perf_counter()
    vals, ids = jax.block_until_ready(fn(placed, tids, qtf))
    log(f"second call ({len(queries)} queries): "
        f"{time.perf_counter() - t0:.4f} s")
    vals, ids = np.asarray(vals), np.asarray(ids)
    errs: list[str] = []
    for qi, q in enumerate(queries):
        keep = vals[qi] > 0
        errs += compare(f"mesh {qi:2d}", q,
                        [ref.ext_ids[int(d)] for d in ids[qi][keep]],
                        [float(v) for v in vals[qi][keep]], ref.scores(q),
                        ref.ext_ids)
    return errs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--docs", type=int, default=W1_CHIP_DOCS)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {enable_compile_cache()}")

    t0 = time.perf_counter()
    if args.chips == 4:
        errs = run_mesh(args.docs, 2 * N_QUERIES, args.seed)
    else:
        clog = CompileLog()
        errs = run_dense(DENSE_DOCS, DENSE_QUERIES, args.seed + 7)
        errs += dense_kernel_compiled(DENSE_DOCS // 4)
        errs += run_sparse(args.docs, N_QUERIES, args.seed, clog)
    log(f"total: {time.perf_counter() - t0:.1f} s, {len(errs)} failure(s)")
    if errs:
        for e in errs[:20]:
            print(f"chip_smoke: FAIL {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

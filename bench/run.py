#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chip, and print one result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a deployment (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<mix>.json``). From the seed the run makes the corpus,
the queries and the arrival schedule; it builds the fleet through the
program's own entry point, hydrates every partition, warms the batch shapes
the schedule can form, and then plays the schedule open-loop on the wall
clock through ``PartitionedSearchApp.submit`` / ``flush``: each arrival is
submitted with its due time as ``t_arrival`` once that time has passed,
the loop plays the admission-window timer by calling ``flush(now)``, and a
request's latency runs from its due time to the moment its handle
resolves. The program's own (virtual-clock) latencies are never read.

After the window closes the run compares every answer with the
benchmark's own reference (``bench/reference.py``) and prints, as the last
line of stdout, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics, each read by ``bench/metrics/<metric>.py``), ``device``,
``breakdown`` (traced runs) and, last, ``checks``: every number compared,
with its limit. The same checks are the last lines of stderr.

It exits 2, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for, or when the chip is missing from ``bench/peaks.json``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import reference as ref  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench import work  # noqa: E402
from bench.corpus import cell_corpus, embed_all, hash_embedding  # noqa: E402
from bench.traffic import Mix, make_schedule  # noqa: E402

TRACE_DIR = BENCH / ".trace"     # gitignored; emptied after every traced run
DRAIN_S = 30.0                   # how long past the window's close answers may come
POLL_S = 0.001                   # the window timer's resolution
TIE_DEPTH = 50                   # reference ranks past k the near-tie rule may see
HOST_SPANS = ("bench.submit", "bench.flush")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def clock() -> float:
    return time.perf_counter() - T_PROCESS


# -- the cell -------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: Mix
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    mix = Mix.load(root / "bench" / "traffic" / f"{w['traffic']}.json")

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=[m for m in spec["end_to_end"] if mine(m)],
                per_layer=[m for m in spec["per_layer"] if mine(m)])


def quiet_tpu_logs() -> None:
    """libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes
    nothing outside its checkout and its own HOME and TMPDIR."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def find_chip(chips: int) -> tuple[object, dict] | None:
    """The first TPU and its peaks, or None (with the reason on stderr)."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"bench: JAX found no TPU (platform {dev.platform!r}); "
            f"nothing was run")
        return None
    if len(devices) < chips:
        log(f"bench: the cell needs {chips} chips, JAX sees {len(devices)}")
        return None
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if dev.device_kind not in peaks:
        log(f"bench: no peaks for device kind {dev.device_kind!r} in "
            f"bench/peaks.json")
        return None
    return dev, peaks[dev.device_kind]


class CompileLog:
    """Backend compiles, from JAX's monitoring events: every program JAX
    loads for the backend (``loads``) less those the persistent cache
    served (``hits``)."""

    LOAD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax
        self.loads = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_load)
        jax.monitoring.register_event_listener(self._on_hit)

    def _on_load(self, event: str, duration: float, **_) -> None:
        self.loads += event == self.LOAD

    def _on_hit(self, event: str, **_) -> None:
        self.hits += event == self.HIT

    @property
    def n(self) -> int:
        return self.loads - self.hits


# -- the system under test --------------------------------------------------------


def build_app(config: dict, docs):
    """The deployment through the program's own assembly entry point, with
    the gateway's default admission window; returns (app, window policy)."""
    from repro.core.gateway import WindowPolicy
    from repro.core.partition import (FleetSpec, GatewaySpec, IndexSpec,
                                      VectorSpec)
    from repro.core.runtime import RuntimeConfig
    from repro.search.searcher import SearchConfig
    from repro.search.service import build_partitioned_search_app

    vector = (VectorSpec(dim=config["vector_dim"],
                         dtype=config["vector_dtype"])
              if config.get("vector_dim") else None)
    scfg = SearchConfig(k=config["k"], max_blocks=config["max_blocks"],
                        max_terms=config["max_terms"],
                        lazy_hydration=config["lazy_hydration"])
    policy = WindowPolicy()
    spec = FleetSpec(
        n_parts=config["n_parts"],
        gateway=GatewaySpec(window=policy),
        index=IndexSpec(vector=vector),
        runtime_config=RuntimeConfig(max_instances=config["max_instances"]),
        search_config=scfg)
    return build_partitioned_search_app(docs, spec), policy


class Recorder:
    """Wraps the app's scatter (handlers + device) and KV fetch: counts
    the dispatches' sizes and cold legs, keeps each query's per-partition
    answers (the hybrid check reads the tiers the coordinator fused), and
    with tracing on marks both calls as host spans."""

    def __init__(self, app, *, spans: bool) -> None:
        self.sizes: list[int] = []
        self.times: list[float] = []
        self.cold_legs = 0
        self.legs: dict[str, list] = {}
        self.on = False
        scatter, fetch = app.scatter.scatter, app.doc_store.batch_get_billed
        span = _span if spans else _no_span

        def rec_scatter(payload, **kw):
            with span("bench.scatter"):
                results, lat, records = scatter(payload, **kw)
            if self.on:
                qs = payload.get("queries") or payload.get("qvs") or []
                self.sizes.append(len(qs))
                self.times.append(clock())
                self.cold_legs += sum(bool(r.cold) for r in records)
                for qi, q in enumerate(payload.get("queries") or ()):
                    self.legs[q] = [r["results"][qi] for r in results]
            return results, lat, records

        def rec_fetch(keys):
            with span("bench.kv"):
                return fetch(keys)

        app.scatter.scatter = rec_scatter
        app.doc_store.batch_get_billed = rec_fetch


def log_segments(win, times: list[float], sizes: list[int],
                 step_s: float = 5.0) -> None:
    """Latency and batching by due time, one stderr line per ``step_s``:
    where in the window a tail came from."""
    lat = win.done - win.due
    t_rel = win.due - win.t_open
    times = np.asarray(times) - win.t_open
    sizes = np.asarray(sizes)
    for a in np.arange(0.0, win.t_close - win.t_open, step_s):
        sel = (t_rel >= a) & (t_rel < a + step_s)
        if not sel.any():
            continue
        v = np.sort(np.where(np.isfinite(lat[sel]), lat[sel], np.inf))
        b = sizes[(times >= a) & (times < a + step_s)]
        log(f"bench: due {a:5.1f}-{a + step_s:5.1f} s: {int(sel.sum())} "
            f"requests, p50 {v[len(v) // 2] * 1e3:.1f} ms, "
            f"p95 {percentile(v, 0.95) * 1e3:.1f} ms, "
            f"max {v[-1] * 1e3:.1f} ms; {len(b)} dispatches, "
            f"mean batch {b.mean() if len(b) else 0:.2f}")


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _no_span(name: str):
    return contextlib.nullcontext()


def batch_bound(rate_qps: float, seconds: float, policy) -> int:
    """The most queries a window of the cell's traffic holds, but for a
    chance under 1e-4 a run: arrivals come at ``rate_qps``, a window holds
    those of at most ``max_window_s``, and at most ``max_batch``. It
    depends on the mix alone, so every seed warms the same shapes."""
    mean = rate_qps * policy.max_window_s
    windows = max(1.0, rate_qps * seconds)
    n, term = 0, math.exp(-mean)
    tail = 1.0                      # P(N >= n) for N ~ Poisson(mean)
    while n < policy.max_batch and windows * tail >= 1e-4:
        tail -= term
        n += 1
        term *= mean / n
    return max(1, n)


def pow2_shapes(n: int) -> list[int]:
    out, q = [], 1
    while q < 2 * n:
        out.append(q)
        q *= 2
    return out


def wait_idle(app) -> None:
    """Until every instance is idle on the runtime's clock, so that a
    window opens on the warm pools."""
    while any(app.runtime.pool_busy(fn, clock()) for g in app.fn_groups
              for fn in g):
        time.sleep(0.05)


def set_up(cfg: dict, mix: Mix, corpus, scheds: list, seconds: float):
    """Build the fleet, hydrate every partition, and warm each batch shape
    the schedules can form (``batch_bound``, or more where a schedule
    holds more); a partition pads its batch to a power of two."""
    t = clock()
    app, policy = build_app(cfg, corpus.docs())
    log(f"bench: fleet built: {clock() - t:.1f} s")
    t = clock()
    app.warm(t_arrival=clock())
    log(f"bench: every partition hydrated: {clock() - t:.1f} s")
    t = clock()
    q_max = max(max(batch_bound(len(s) / seconds, seconds, policy),
                    min(policy.max_batch, s.max_in_span(policy.max_window_s)))
                for s in scheds)
    queries = scheds[-1].queries
    shapes = pow2_shapes(q_max)
    for q in shapes:
        app.query([queries[i % len(queries)] for i in range(q)], k=mix.k,
                  mode=mix.mode, fetch_docs=mix.fetch_docs,
                  t_arrival=clock())
    wait_idle(app)
    log(f"bench: batch shapes {shapes} warmed: {clock() - t:.1f} s")
    # the gateway clamps its window by the route's trailing p99 over the
    # last p99_window requests, where the first call of each shape above
    # still stands: warm requests push it out, so that the window opens
    # on the route's steady state and not on set-up's slow calls
    t = clock()
    for i in range(policy.p99_window):
        app.query(queries[i % len(queries)], k=mix.k, mode=mix.mode,
                  fetch_docs=mix.fetch_docs, t_arrival=clock())
    wait_idle(app)
    log(f"bench: {policy.p99_window} warm requests: {clock() - t:.1f} s")
    return app


# -- the window ---------------------------------------------------------------------


@dataclasses.dataclass
class Window:
    due: np.ndarray            # absolute due times on clock()
    done: np.ndarray           # when each handle resolved (nan: never)
    late: np.ndarray           # submit time - due time
    responses: list
    t_open: float
    t_close: float


def run_window(app, sched, mix: Mix, t_open: float, seconds: float,
               span) -> Window:
    n = len(sched)
    due = t_open + sched.due
    done = np.full(n, np.nan)
    late = np.full(n, np.nan)
    handles: list = [None] * n
    pending: list[int] = []
    t_close = t_open + seconds

    def collect() -> None:
        if not pending:
            return
        now = clock()
        keep = []
        for j in pending:
            if handles[j].done():
                done[j] = now
            else:
                keep.append(j)
        pending[:] = keep

    i = 0
    with span("bench.window"):
        while True:
            now = clock()
            if i < n and due[i] <= now:
                with span("bench.submit"):
                    while i < n and due[i] <= clock():
                        late[i] = clock() - due[i]
                        handles[i] = app.submit(
                            sched.queries[i], k=mix.k, mode=mix.mode,
                            fetch_docs=mix.fetch_docs, t_arrival=float(due[i]))
                        pending.append(i)
                        i += 1
                        collect()
                continue
            if pending:
                with span("bench.flush"):
                    app.flush(now)
                collect()
            if i == n and not pending:
                break
            if now > t_close + DRAIN_S:
                break
            nxt = due[i] if i < n else now + POLL_S
            with span("bench.wait"):
                time.sleep(max(0.0, min(nxt - clock(), POLL_S)))
    responses = [h.response if h is not None and h.done() else None
                 for h in handles]
    return Window(due=due, done=done, late=late, responses=responses,
                  t_open=t_open, t_close=t_close)


# -- the comparison -------------------------------------------------------------------


def merge_tier(per_part: list[dict], per: int, k: int
               ) -> list[tuple[int, float]]:
    """Per-partition (local id, score) lists -> global top-k, ties by
    (partition, local id): the coordinator's documented merge rule."""
    hits = [(-float(s), p, int(d)) for p, r in enumerate(per_part)
            for d, s in zip(r["ids"], r["scores"])]
    hits.sort()
    return [(p * per + d, -s) for s, p, d in hits[:k]]


def check(win: Window, sched, corpus, post, config: dict, mix: Mix,
          legs: dict) -> tuple[dict, np.ndarray]:
    """Every answer of the window against the reference: the numbers
    compared, each with its limit, and which requests were answered
    correctly."""
    limits = config["limits"]
    k, tie = mix.k, limits["score_rel_err"]
    hybrid = mix.mode == "hybrid"
    per = -(-corpus.n_docs // config["n_parts"])
    if hybrid:
        vectors = embed_all(corpus.texts, config["vector_dim"])
    sparse, dense = ref.Tally(), ref.Tally()
    failed = docs_bad = fused_bad = 0
    good = np.zeros(len(win.responses), bool)
    for j, resp in enumerate(win.responses):
        if resp is None or resp.status != 200:
            failed += 1
            continue
        body = resp.body
        got = list(zip(body["ids"], body["scores"]))
        want_s = ref.bm25_topk(post, sched.query_tids[j], k, k1=config["k1"],
                               b=config["b"], depth=TIE_DEPTH)
        bad_docs = 0
        if mix.fetch_docs:
            for gid, ext, doc in zip(body["ids"], body["ext_ids"],
                                     body["docs"]):
                bad_docs += (ext != corpus.ext_id(gid) or doc is None
                             or doc.get("id") != ext
                             or doc.get("contents") != corpus.texts[gid])
        docs_bad += bad_docs
        if hybrid:
            q = sched.queries[j]
            per_part = legs.get(q)
            if per_part is None:
                fused_bad += 1
                continue
            served_s = merge_tier(per_part, per, k)
            served_d = merge_tier([r["dense"] for r in per_part], per, k)
            ts = ref.compare(served_s, want_s, k, tie)
            want_d = ref.dense_topk(vectors, hash_embedding(
                q, config["vector_dim"]), k, depth=TIE_DEPTH)
            td = ref.compare(served_d, want_d, k, tie)
            fused = ref.rrf([[d for d, _ in served_s],
                             [d for d, _ in served_d]], k)
            fused_ok = got == fused
            fused_bad += not fused_ok
            sparse.add(ts)
            dense.add(td)
            good[j] = (fused_ok and not bad_docs
                       and ts.ok(limits["score_rel_err"])
                       and td.ok(limits["dense_rel_err"]))
        else:
            ts = ref.compare(got, want_s, k, tie)
            sparse.add(ts)
            good[j] = not bad_docs and ts.ok(limits["score_rel_err"])
    checks = {"failed": (failed, limits["failed"]),
              "id_mismatch": (sparse.id_mismatch, limits["id_mismatch"]),
              "score_rel_err": (sparse.rel_err, limits["score_rel_err"])}
    if hybrid:
        checks["dense_id_mismatch"] = (dense.id_mismatch,
                                       limits["dense_id_mismatch"])
        checks["dense_rel_err"] = (dense.rel_err, limits["dense_rel_err"])
        checks["fused_mismatch"] = (fused_bad, limits["fused_mismatch"])
    if mix.fetch_docs:
        checks["doc_mismatch"] = (docs_bad, limits["doc_mismatch"])
    return ({name: {"value": v, "limit": lim}
             for name, (v, lim) in checks.items()}, good)


# -- metrics ----------------------------------------------------------------------------


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest rank; a failed or unfinished request reads as infinite."""
    v = np.sort(values)
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- one run ------------------------------------------------------------------------------


def run(cell: Cell, seed: int, seconds: float, trace: bool, *, dev=None,
        peak: dict | None = None, tamper=None) -> dict:
    """Set up, play the window, check, and return the result line. With
    ``dev`` None the run is on whatever JAX has (the CPU in tests);
    ``tamper(app)`` breaks the system under test before the window."""
    import jax

    clog = CompileLog()
    cfg, mix = cell.config, cell.mix
    seed = int(seed) % (1 << 63)
    log(f"bench: {cell.name} seed {seed} seconds {seconds} trace {int(trace)}")

    t = clock()
    corpus = cell_corpus(cfg, seed)
    sched = make_schedule(mix, corpus, seconds, seed)
    log(f"bench: corpus {corpus.n_docs} docs, {len(corpus.tids)} tokens, "
        f"{len(sched)} arrivals: {clock() - t:.1f} s")
    app = set_up(cfg, mix, corpus, [sched], seconds)
    log(f"bench: programs loaded in set-up: {clog.loads}, compiled "
        f"{clog.n}, from the persistent cache {clog.hits}")
    if tamper is not None:
        tamper(app)
    recorder = Recorder(app, spans=trace)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    c0 = clog.n
    t_open = clock() + 0.01
    setup_s = t_open
    recorder.on = True
    win = run_window(app, sched, mix, t_open, seconds,
                     _span if trace else _no_span)
    recorder.on = False
    compiles = clog.n - c0
    if trace:
        t = clock()
        jax.profiler.stop_trace()
        log(f"bench: trace written: {clock() - t:.1f} s")
    stats = app.gateway.window_stats("GET", "/search")
    mem = (dev.memory_stats() or {}) if dev is not None else {}
    legs = recorder.legs
    del app
    gc.collect()

    t = clock()
    post = ref.build_postings(
        corpus, {tid for q in sched.query_tids for tid in q})
    checks, good = check(win, sched, corpus, post, cfg, mix, legs)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    log(f"bench: reference check of {len(sched)} answers: "
        f"{clock() - t:.1f} s")

    lat = win.done - win.due
    ok = np.array([r is not None and r.status == 200
                   for r in win.responses])
    lat = np.where(ok & np.isfinite(lat), lat, np.inf)
    n_failed = int((~ok).sum())
    # the rate takes every answer of the window's arrivals, over the time
    # until the last of them came (the window's length at least)
    answered = np.isfinite(win.done)
    window_s = (max(win.t_close, float(win.done[answered].max()))
                if answered.any() else win.t_close) - win.t_open
    log(f"bench: generator lateness p50 {np.nanmedian(win.late) * 1e3:.3f} "
        f"ms, max {np.nanmax(win.late) * 1e3:.3f} ms; {stats['batches']} "
        f"windows, mean batch {stats['mean_batch']:.3f}; window compiles "
        f"{compiles}; cold legs {recorder.cold_legs}")
    log_segments(win, recorder.times, recorder.sizes)

    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    result = {"correct": bool(correct), "attempted": len(sched),
              "failed": n_failed}
    if not trace:
        values = {
            "p50_ms": percentile(lat, 0.50) * 1e3,
            "qps": int(good.sum()) / window_s,
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    else:
        t = clock()
        files = sorted(TRACE_DIR.glob("**/*.xplane.pb"))
        red = tr.reduce(tr.load(str(files[-1])), host_spans=HOST_SPANS) \
            if files else {}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        log(f"bench: trace reduced: {clock() - t:.1f} s")
        rec = {
            "queries": len(sched),
            "latency_ms": (lat * 1e3).tolist(),
            "batch_mean": stats["mean_batch"] if stats["batches"] else None,
            "cold_legs": recorder.cold_legs,
            "window_compiles": compiles,
            "trace": red,
            "peak": peak or {},
            "work": work.window_work(post, sched.query_tids,
                                     recorder.sizes, cfg, mix),
        }
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = tr.breakdown(red)
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r}) "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    quiet_tpu_logs()
    cell = load_cell(args.workload)
    chip = find_chip(cell.chips)
    if chip is None:
        return 2
    dev, peak = chip
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    # every program of the run goes to the persistent cache, however fast
    # it compiled, so that only a checkout's first run compiles
    log(f"bench: compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = run(cell, args.seed, args.seconds, bool(args.trace), dev=dev,
                 peak=peak)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

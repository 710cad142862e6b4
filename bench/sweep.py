#!/usr/bin/env python3
"""Find a cell's knee once: one set-up, then open-loop windows at rising
rates, each reported with its latency percentiles and whether a backlog grew.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 10,20,40

The knee is the highest rate whose p95 stays within the paper's
interactive limit, 300 ms, and whose answers all arrive within a
second of the window's close (no growing backlog), although the host
stands still for ``STALL_S`` in the middle of every window, as a shared
host now and then does: the p95 of the window's first two fifths, before
the stall, and of its last fifth, after it, both have to stay within the
limit, so that the rate survives the burst that follows a stall. The steady mix file
then carries four fifths of it as a fixed number; the benchmark itself
never searches for a rate. Prints one JSON line per rate and exits 2 with
no TPU, like ``run.py``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

from bench import run as R  # noqa: E402
from bench.corpus import cell_corpus  # noqa: E402
from bench.traffic import make_schedule  # noqa: E402

BACKLOG_S = 1.0
LIMIT_MS = 300.0
STALL_S = 3.0


def stall_once(app, at: float) -> None:
    """The host stands still for ``STALL_S`` once the clock passes ``at``."""
    hit = []

    def stalled(call):
        def inner(*a, **kw):
            if not hit and R.clock() >= at:
                hit.append(1)
                time.sleep(STALL_S)
            return call(*a, **kw)
        return inner
    app.submit, app.flush = stalled(app.submit), stalled(app.flush)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    R.quiet_tpu_logs()
    cell = R.load_cell(args.workload)
    if R.find_chip(cell.chips) is None:
        return 2
    sys.path.insert(0, str(R.ROOT / "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cfg = cell.config
    rates = [float(r) for r in args.rates.split(",")]
    t = R.clock()
    corpus = cell_corpus(cfg, args.seed)
    scheds = [make_schedule(dataclasses.replace(cell.mix, rate_qps=r),
                            corpus, args.seconds, args.seed) for r in rates]
    R.log(f"sweep: corpus and schedules {R.clock() - t:.1f} s")
    app = R.set_up(cfg, cell.mix, corpus, scheds, args.seconds)
    R.log(f"sweep: set-up ends at {R.clock():.1f} s")
    clog = R.CompileLog()
    for rate, sched in zip(rates, scheds):
        R.wait_idle(app)
        c0 = clog.n
        st = app.gateway.window_stats("GET", "/search")
        b0, q0 = st["batches"], st["batches"] * st["mean_batch"]
        mix = dataclasses.replace(cell.mix, rate_qps=rate)
        t_open = R.clock() + 0.01
        stall_once(app, t_open + args.seconds / 2)
        win = R.run_window(app, sched, mix, t_open, args.seconds,
                           R._no_span)
        del app.submit, app.flush
        lat = win.done - win.due
        ok = np.array([r is not None and r.status == 200
                       for r in win.responses])
        lat = np.where(ok & np.isfinite(lat), lat, np.inf)
        drain = float(np.nanmax(np.where(np.isfinite(win.done), win.done,
                                         np.nan)) - win.t_close)
        p95 = R.percentile(lat, 0.95) * 1e3
        first = R.percentile(lat[sched.due < 0.4 * args.seconds], 0.95) * 1e3
        last = R.percentile(lat[sched.due >= 0.8 * args.seconds], 0.95) * 1e3
        st = app.gateway.window_stats("GET", "/search")
        row = {"rate_qps": rate, "requests": len(sched),
               "p50_ms": R.percentile(lat, 0.50) * 1e3, "p95_ms": p95,
               "p99_ms": R.percentile(lat, 0.99) * 1e3,
               "p95_first_two_fifths_ms": first, "p95_last_fifth_ms": last,
               "mean_batch": (st["batches"] * st["mean_batch"] - q0)
               / max(1, st["batches"] - b0),
               "failed": int((~ok).sum()), "drain_s": drain,
               "late_max_ms": float(np.nanmax(win.late)) * 1e3,
               "compiles": clog.n - c0,
               "sustained": bool(max(first, last) <= LIMIT_MS
                                 and drain < BACKLOG_S and ok.all())}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

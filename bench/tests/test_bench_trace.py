"""The trace reduction, on a trace recorded on a TPU v5e (a hybrid fleet
of 4 partitions serving three windows of queries)."""

from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).parent / "data" / "hybrid_window.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    t = tr.load(DATA)
    lo = min(s for s, _, _ in t.spans)
    hi = max(e for _, e, _ in t.spans)
    t.spans = sorted(t.spans + [(lo, hi, tr.WINDOW_SPAN)])
    return t, lo, hi


def sweep_busy(intervals, lo, hi):
    """Covered length by counting overlaps at sorted endpoints."""
    edges = sorted([(max(s, lo), 1) for s, e, _ in intervals if e > s]
                   + [(min(e, hi), -1) for s, e, _ in intervals if e > s])
    covered, depth, last = 0, 0, None
    for t, d in edges:
        if depth > 0 and last is not None:
            covered += max(0, t - last)
        depth += d
        last = t
    return covered


def test_reads_device_ops_programs_and_host_spans(recorded):
    t, _, _ = recorded
    assert list(t.ops) == ["/device:TPU:0"]
    assert len(t.ops["/device:TPU:0"]) == 2820
    names = {tr.module_base(n) for _, _, n in t.modules["/device:TPU:0"]}
    assert {"jit_search", "jit_dot_topk"} <= names
    assert {n for _, _, n in t.spans} == {
        "bench.submit", "bench.flush", "bench.sparse", tr.WINDOW_SPAN}


def test_busy_is_the_union_of_op_intervals(recorded):
    t, lo, hi = recorded
    red = tr.reduce(t)
    want = sweep_busy(t.ops["/device:TPU:0"], lo, hi)
    assert red["busy_s"] == pytest.approx(want / 1e9, rel=1e-12)
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert 0 < red["busy_s"] < red["window_s"]


def test_program_time_sums_its_executions(recorded):
    t, lo, hi = recorded
    red = tr.reduce(t)
    for prog in ("jit_search", "jit_dot_topk"):
        want = sum(e - s for s, e, n in t.modules["/device:TPU:0"]
                   if tr.module_base(n) == prog and s >= lo and e <= hi)
        assert red["module_s"][prog] == pytest.approx(want / 1e9, rel=1e-12)


def test_idle_gaps_fill_the_window_and_are_named_by_spans(recorded):
    t, _, _ = recorded
    red = tr.reduce(t)
    assert sum(red["gap_s"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)
    assert set(red["gap_s"]) <= {"bench.submit", "bench.flush",
                                 "bench.sparse", "host:untraced"}
    b = tr.breakdown(red)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].startswith("jit_search:")


def test_innermost_span_names_each_point():
    spans = [(0, 100, "a"), (10, 20, "b"), (12, 14, "c"), (30, 40, "d")]
    assert tr.innermost(spans, [5, 13, 15, 35, 50, 200]) == [
        "a", "c", "b", "d", "a", "host:untraced"]


def test_host_time_is_span_time_less_device_busy():
    t = tr.Trace(ops={"/device:TPU:0": [(20, 50, "%fusion.1 = f32[] x"),
                                       (152, 170, "%fusion.2 = f32[] y")]},
                 modules={"/device:TPU:0": [(20, 50, "jit_a(1)"),
                                           (152, 170, "jit_b(2)")]},
                 spans=[(0, 200, tr.WINDOW_SPAN), (0, 100, "bench.flush"),
                        (140, 160, "bench.submit")])
    red = tr.reduce(t, host_spans=("bench.flush", "bench.submit"))
    assert red["host_s"] == pytest.approx(((100 - 30) + (20 - 8)) / 1e9)
    assert red["busy_s"] == pytest.approx(48 / 1e9)
    assert red["module_s"] == pytest.approx({"jit_a": 30e-9, "jit_b": 18e-9})
    assert red["op_s"] == pytest.approx({"jit_a:fusion.1": 30e-9,
                                         "jit_b:fusion.2": 18e-9})
    assert red["gap_s"] == pytest.approx({"bench.flush": 20e-9,
                                          "host:untraced": 132e-9})


def test_no_window_span_reads_nothing(recorded):
    t, _, _ = recorded
    bare = tr.Trace(ops=t.ops, modules=t.modules,
                    spans=[s for s in t.spans if s[2] != tr.WINDOW_SPAN])
    assert tr.reduce(bare) == {}


def test_busy_prefix_sum_matches_interval_overlap():
    merged = [(10, 20), (30, 35), (50, 90)]
    busy = tr.Busy(merged)
    for lo in range(0, 100, 3):
        for hi in range(lo, 100, 7):
            want = sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)
            assert busy.within(lo, hi) == want


# sha256 of json.dumps(reduce(...), sort_keys=True) of the recorded trace
# (host spans bench.submit and bench.flush), as the reduction gave it
# before it kept the program's spans
PARENT_REDUCTION = \
    "f0513171a0bf9fb05a90c75779dd2e52e01a132a980d59b50ae593128054184f"


def test_recorded_trace_reduces_as_it_did(recorded):
    import hashlib
    import json
    t, _, _ = recorded
    red = tr.reduce(t, host_spans=("bench.submit", "bench.flush"))
    assert "fleet" not in red and not t.fleet
    assert hashlib.sha256(json.dumps(red, sort_keys=True).encode()
                          ).hexdigest() == PARENT_REDUCTION


def test_fleet_spans_get_a_block_each():
    t = tr.Trace(
        ops={"/device:TPU:0": [(30, 40, "%fusion.1 = f32[] x"),
                               (70, 90, "%fusion.2 = f32[] y")]},
        modules={"/device:TPU:0": [(30, 40, "jit_a(1)"),
                                   (70, 90, "jit_a(1)")]},
        spans=[(0, 200, tr.WINDOW_SPAN), (10, 100, "bench.flush")],
        fleet=[(20, 50, "fleet.bm25", {"queries": 3, "padded": 4,
                                       "h2d_bytes": 512}),
               (60, 95, "fleet.bm25", {"queries": 2, "padded": 2,
                                       "h2d_bytes": 256}),
               (12, 98, "fleet.dispatch", {"requests": 5, "wait_ms_sum": 1.5,
                                           "wait_ms_max": 0.75}),
               (110, 120, "fleet.dispatch", {"requests": 1,
                                             "wait_ms_sum": 2.0,
                                             "wait_ms_max": 2.0}),
               (150, 250, "fleet.kv", {"keys": 10})])     # past the window
    red = tr.reduce(t, host_spans=("bench.flush",))
    bm25 = red["fleet"]["fleet.bm25"]
    assert bm25["count"] == 2
    assert bm25["span_s"] == pytest.approx(65e-9)
    assert bm25["host_s"] == pytest.approx((30 - 10 + 35 - 20) * 1e-9)
    assert bm25["counters"] == {"queries": 5, "padded": 6, "h2d_bytes": 768}
    d = red["fleet"]["fleet.dispatch"]
    assert d["count"] == 2
    assert d["counters"] == pytest.approx(
        {"requests": 6, "wait_ms_sum": 3.5, "wait_ms_max": 2.0})
    assert d["host_s"] == pytest.approx((86 - 30 + 10) * 1e-9)
    assert set(red["fleet"]) == {"fleet.bm25", "fleet.dispatch"}
    # the bench spans alone name the gaps and count as spans
    assert set(red["gap_s"]) == {"bench.flush", "host:untraced"}
    assert red["spans"] == 1
    assert red["host_s"] == pytest.approx((90 - 30) * 1e-9)


def test_load_keeps_the_programs_spans_and_counters(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("fleet.bm25", queries=3, padded=4):
            jnp.ones(3).sum().block_until_ready()
        with jax.profiler.TraceAnnotation("fleet.hydrate") as sp:
            sp.set_metadata(h2d_bytes=12345678901)
        with jax.profiler.TraceAnnotation("other.span"):
            pass
    jax.profiler.stop_trace()
    t = tr.load(next(tmp_path.glob("**/*.xplane.pb")))
    assert [(n, c) for _, _, n, c in t.fleet] == [
        ("fleet.bm25", {"queries": 3, "padded": 4}),
        ("fleet.hydrate", {"h2d_bytes": 12345678901})]
    assert [n for _, _, n in t.spans] == [tr.WINDOW_SPAN]

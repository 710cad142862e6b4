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

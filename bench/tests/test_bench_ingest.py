"""Writes and refreshes in the window, on the CPU at a tiny size: the
run plays a mix's adds and deletes, commits every ``refresh_s``, and holds
each answer to the generation it names; a fleet whose refresh is broken
underneath is refused by the number named for the fault."""

import copy
import dataclasses
import json

import numpy as np
import pytest

from bench import run as R
from bench.corpus import cell_corpus
from bench.tests.conftest import SEED
from bench.traffic import Mix, make_schedule, make_writes

DATA = R.BENCH / "tests" / "data"


def ingest_cell() -> R.Cell:
    spec = json.loads((R.ROOT / "BENCHMARK.json").read_text())
    return R.make_cell("ingest-tiny", 1,
                       json.loads((DATA / "ingest-tiny.json").read_text()),
                       Mix.load(DATA / "ingest-tiny-mix.json"), spec)


@pytest.fixture
def windows(monkeypatch):
    """Every Window the run plays."""
    seen = []
    play = R.run_window

    def spy(*a, **kw):
        seen.append(play(*a, **kw))
        return seen[-1]
    monkeypatch.setattr(R, "run_window", spy)
    return seen


def test_ingest_run_is_correct_across_generations(src_path, windows):
    res = R.run(ingest_cell(), SEED, 2.5, False)
    assert res["correct"], res["checks"]
    assert {"stale", "commit_failed"} <= set(res["checks"])
    win = windows[0]
    published = [c for c in win.commits if c.committed]
    assert len(published) >= 2
    assert sum(c.body["indexed"] for c in published) > 0
    assert sum(c.body["deleted"] for c in published) > 0
    gens = {r.body["generation"] for r in win.responses}
    assert len(gens) >= 3
    assert win.write_sent.all() and win.write_ok.all()


def _pin_stays(app):
    """Commits publish, but the serving generation never moves."""
    ix = app.indexer
    commit = ix.commit

    def broken(fn_groups, **kw):
        gen = ix.gen
        out = commit(fn_groups, **kw)
        ix.gen = gen
        return out
    ix.commit = broken


def _previous_stats(app):
    """Each generation is scored with the stats of the one before it."""
    cat = app.catalog
    publish = cat.publish_generation_state
    prev = [copy.deepcopy(app.indexer.stats)]

    def broken(name, gen, stats, vocab, writer=None):
        old, prev[0] = prev[0], copy.deepcopy(stats)
        return publish(name, gen, old, vocab, writer)
    cat.publish_generation_state = broken


def _commit_fails(app):
    def broken(fn_groups, **kw):
        raise RuntimeError("the writer's publish failed")
    app.indexer.commit = broken


@pytest.mark.parametrize("fault,numbers", [
    (_pin_stays, ("stale",)),
    (_previous_stats, ("id_mismatch", "score_rel_err")),
    (_commit_fails, ("commit_failed",)),
])
def test_a_broken_refresh_is_not_correct(fault, numbers, src_path):
    res = R.run(ingest_cell(), SEED + 2, 2.5, False, tamper=fault)
    assert res["correct"] is False
    assert any(res["checks"][n]["value"] > res["checks"][n]["limit"]
               for n in numbers), res["checks"]


def test_a_hybrid_mix_may_not_write():
    cell = R.load_cell("hybrid768-steady")
    mix = dataclasses.replace(cell.mix, writes_per_s=1.0)
    with pytest.raises(SystemExit, match="only sparse mixes may write"):
        R.make_cell("hybrid-writes", 1, cell.config, mix, {
            "end_to_end": [], "per_layer": []})


def test_generations_follow_the_acknowledged_writes():
    cell = ingest_cell()
    mix, cfg = cell.mix, cell.config
    corpus = cell_corpus(cfg, SEED, n_extra=mix.n_added_docs(2.5))
    writes = make_writes(mix, corpus, 2.5, cfg["n_parts"])
    ok = np.ones(len(writes), bool)
    ok[1] = False                        # refused: it is in no generation
    sent = np.ones(len(writes), bool)
    sent[-1] = ok[-1] = False            # never sent: no failure of its own

    def commit(before, gen, committed=True):
        return R.Commit(due=0.0, start=0.0, done=0.0, writes_before=before,
                        status=200, committed=committed, gen=gen, body={},
                        compiles=(0, 0))
    win = R.Window(due=np.zeros(0), done=np.zeros(0), late=np.zeros(0),
                   responses=[], t_open=0.0, t_close=2.5, writes=writes,
                   write_sent=sent, write_ok=ok, commits=[commit(3, 2), commit(3, None, False),
                                         commit(8, 3)])
    live = R.generations(win, corpus)
    assert sorted(live) == [1, 2, 3]
    assert live[1].sum() == corpus.n_base
    acked = np.flatnonzero(ok)
    for gen, upto in ((2, 3), (3, 8)):
        want = np.arange(corpus.n_docs) < corpus.n_base
        for w in acked[:upto]:
            want[writes.docs[w]] = not writes.delete[w]
        assert np.array_equal(live[gen], want)
    assert not live[3][writes.docs[1]].any()
    _, failed = R.refresh_checks(win)
    assert failed == 1                     # the refused write
    win.commits[1] = commit(5, None, False)    # staged writes not published
    assert R.refresh_checks(win)[1] == 2


def test_every_seed_writes_the_same_shapes():
    cell = ingest_cell()
    mix, cfg = cell.mix, cell.config
    n_parts, per = cfg["n_parts"], -(-cfg["n_docs"] // cfg["n_parts"])

    def shapes(seed):
        c = cell_corpus(cfg, seed, n_extra=mix.n_added_docs(10.0))
        w = make_writes(mix, c, 10.0, n_parts)
        dl = c.doc_len()
        return c, w, [(bool(d), [(i // per if d else -1, int(dl[i]))
                                 for i in ids])
                      for d, ids in zip(w.delete, w.docs)]

    (a, wa, sa), (b, wb, sb) = shapes(SEED), shapes(7)
    assert sa == sb
    assert np.array_equal(wa.due, wb.due)
    assert [i for d, ids in zip(wa.delete, wa.docs) if d for i in ids] != \
        [i for d, ids in zip(wb.delete, wb.docs) if d for i in ids]
    gone = [i for d, ids in zip(wa.delete, wa.docs) if d for i in ids]
    assert len(set(gone)) == len(gone) and max(gone) < a.n_base
    added = [i for d, ids in zip(wa.delete, wa.docs) if not d for i in ids]
    assert added == list(range(a.n_base, a.n_docs))
    assert a.texts[a.n_base:] != b.texts[b.n_base:]


def test_writes_leave_the_seeds_arrivals_queries_and_index_alone():
    cell = ingest_cell()
    cfg, mix = cell.config, cell.mix
    quiet = Mix(**{f.name: getattr(mix, f.name)
                   for f in dataclasses.fields(Mix)
                   if f.name in ("rate_qps", "terms_per_query", "max_cf",
                                 "mode", "k", "fetch_docs")})
    assert not quiet.writes and not make_writes(quiet, None, 5.0, 4).docs
    a = cell_corpus(cfg, SEED)
    b = cell_corpus(cfg, SEED, n_extra=mix.n_added_docs(5.0))
    assert b.texts[: b.n_base] == a.texts and b.n_extra > 0
    assert np.array_equal(b.tids[: b.starts[b.n_base]], a.tids)
    assert np.array_equal(a.cf(), b.cf())
    sa, sb = make_schedule(quiet, a, 5.0, SEED), make_schedule(mix, b, 5.0, SEED)
    assert np.array_equal(sa.due, sb.due) and sa.queries == sb.queries

#!/usr/bin/env python3
"""The control of the comparison behind ``correct``: the reference itself,
put in the program's place and computed one precision lower (bfloat16 for
the deployments' float32), has to come out as not correct.

    python bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 30]

For each seed it makes the cell's corpus and the window's requests exactly
as ``run.py`` does, answers every request with the reference in bfloat16
(jax.numpy, on the chip when there is one), feeds those answers through
``run.check`` and prints one JSON line: the numbers compared, each with its
limit, and whether the control was (rightly) refused. A fleet is never
built, so a seed costs the corpus and the answers alone.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

from bench import reference as ref  # noqa: E402
from bench import run as R  # noqa: E402
from bench.corpus import cell_corpus, embed_all, hash_embedding  # noqa: E402
from bench.traffic import make_schedule  # noqa: E402


def control_dtype():
    import jax.numpy as jnp
    return jnp.bfloat16


class _Answer:
    status = 200

    def __init__(self, body: dict) -> None:
        self.body = body


def bm25_control(post, queries_tids, k: int, *, k1: float, b: float,
                 dtype) -> list[list[tuple[int, float]]]:
    """Every query's BM25 top-k with all arithmetic in ``dtype``: the
    impacts of all queried postings in one device pass, each query's sum
    over its terms accumulated in ``dtype`` too."""
    import jax
    import jax.numpy as jnp

    terms = sorted(post.lists)
    start = dict(zip(terms, np.cumsum([0] + [post.df(t) for t in terms])))
    docs = np.concatenate([post.lists[t][0] for t in terms])
    tf = np.concatenate([post.lists[t][1] for t in terms])
    idf = np.concatenate([np.full(post.df(t), np.log(
        1.0 + (post.n_docs - post.df(t) + 0.5) / (post.df(t) + 0.5)))
        for t in terms])

    def impacts(tf, dl, idf):
        c = lambda x: jnp.asarray(x, dtype)  # noqa: E731
        norm = c(k1) * (c(1.0 - b) + c(b) * dl / c(post.avgdl))
        return idf * tf / (tf + norm)

    imp = np.asarray(jax.jit(impacts)(
        jnp.asarray(tf, dtype), jnp.asarray(post.dl[docs], dtype),
        jnp.asarray(idf, dtype)))
    out = []
    for q in queries_tids:
        uniq = np.unique(np.concatenate(
            [post.lists[t][0] for t in set(q)]))
        acc = np.zeros(len(uniq), imp.dtype)
        for t in set(q):
            sl = slice(start[t], start[t] + post.df(t))
            idx = np.searchsorted(uniq, docs[sl])
            acc[idx] = acc[idx] + imp[sl] * imp.dtype.type(q.count(t))
        score = acc.astype(np.float64)
        order = np.lexsort((uniq, -score))[:k]
        out.append([(int(uniq[i]), float(score[i])) for i in order])
    return out


def dense_control(vectors: np.ndarray, qvs: np.ndarray, k: int,
                  dtype) -> list[list[tuple[int, float]]]:
    """Every query's inner-product top-k with bfloat16 operands (f32
    accumulation), all queries in one device matmul."""
    import jax
    import jax.numpy as jnp
    s = np.asarray(jax.lax.dot(
        jnp.asarray(qvs, dtype), jnp.asarray(vectors, dtype).T,
        preferred_element_type=jnp.float32)).astype(np.float64)
    out = []
    for row in s:
        cand = np.argpartition(-row, min(len(row) - 1, 256))[:257]
        order = np.lexsort((cand, -row[cand]))[:k]
        out.append([(int(cand[i]), float(row[cand[i]])) for i in order])
    return out


def control_window(cell, corpus, sched, dtype):
    """The reference in ``dtype`` answering every request of the window,
    shaped like the served answers (and, for hybrid, like the per-tier
    legs the check reads)."""
    cfg, mix = cell.config, cell.mix
    post = ref.build_postings(corpus, {t for q in sched.query_tids for t in q})
    sparse = bm25_control(post, sched.query_tids, mix.k, k1=cfg["k1"],
                          b=cfg["b"], dtype=dtype)
    hybrid = mix.mode == "hybrid"
    if hybrid:
        dim = cfg["vector_dim"]
        dense = dense_control(
            embed_all(corpus.texts, dim),
            np.stack([hash_embedding(q, dim) for q in sched.queries]),
            mix.k, dtype)
    responses, legs = [], {}
    for j, q in enumerate(sched.queries):
        hits = sparse[j]
        if hybrid:
            legs[q] = [{"ids": [d for d, _ in hits],
                        "scores": [s for _, s in hits],
                        "dense": {"ids": [d for d, _ in dense[j]],
                                  "scores": [s for _, s in dense[j]]}}]
            hits = ref.rrf([[d for d, _ in hits], [d for d, _ in dense[j]]],
                           mix.k)
        responses.append(_Answer({
            "ids": [d for d, _ in hits], "scores": [s for _, s in hits],
            "ext_ids": [corpus.ext_id(d) for d, _ in hits],
            "docs": [{"id": corpus.ext_id(d), "contents": corpus.texts[d]}
                     for d, _ in hits]}))
    win = R.Window(due=sched.due, done=sched.due, late=sched.due * 0,
                   responses=responses, t_open=0.0, t_close=0.0)
    return win, post, legs


def run_control(cell, seed: int, seconds: float, dtype=None) -> dict:
    dtype = dtype or control_dtype()
    cfg = cell.config
    corpus = cell_corpus(cfg, seed)
    sched = make_schedule(cell.mix, corpus, seconds, seed)
    win, post, legs = control_window(cell, corpus, sched, dtype)
    checks, _ = R.check(win, sched, corpus, post, cfg, cell.mix, legs)
    return {"seed": seed, "dtype": np.dtype(dtype).name, "answers": len(sched),
            "refused": any(c["value"] > c["limit"] for c in checks.values()),
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    R.quiet_tpu_logs()
    cell = R.load_cell(args.workload)
    seconds = args.seconds or json.loads(
        (R.ROOT / "BENCHMARK.json").read_text())["run_seconds"]
    import jax
    R.log(f"control: {args.workload} on {jax.devices()[0].device_kind}")
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(run_control(cell, seed, seconds)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

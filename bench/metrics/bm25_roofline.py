"""Device BM25 step (search/bm25.py make_search_fn, the jitted ``search``
program): the least time the window's queries need (bench/work.py, every
queried term's postings in every partition read once, the k hits written)
over the device time of that program, in %."""

from bench.work import least_time


def read(rec):
    t = rec["trace"]
    s = t.get("module_s", {}).get("jit_search") if t else None
    if not s:
        return None
    return 100.0 * least_time(rec["work"]["bm25_bytes"], 0, rec["peak"]) / s

"""Hydration (index/hydration.py, LazySearcher): partition legs of the
window's dispatches that the runtime served cold (a new instance or a
re-hydration). Warm cells read 0."""


def read(rec):
    return rec["cold_legs"]

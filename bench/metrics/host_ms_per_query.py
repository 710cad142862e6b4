"""Coordinator and handlers on the host (search/service.py,
search/searcher.py, core/kvstore.py): the time of the benchmark's submit
and flush calls less the device-busy time inside them, per query of the
traced window."""


def read(rec):
    t = rec["trace"]
    if not t or not rec["queries"]:
        return None
    return 1e3 * t["host_s"] / rec["queries"]

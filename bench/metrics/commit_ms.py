"""NRT commit and rollover (search/service.py FleetIndexer.commit, the
program's span ``fleet.commit``): the mean wall time of a refresh in the
traced window, from the call until every pool holds the new generation,
in ms. None where the program has no such span."""


def read(rec):
    b = ((rec.get("trace") or {}).get("fleet") or {}).get("fleet.commit")
    if not b or not b["count"]:
        return None
    return 1e3 * b["span_s"] / b["count"]

"""Request path on the host clock: the 95th percentile (nearest rank) of
the latency of every request of the traced window, from its due time to
its response. It is no end-to-end metric because the host stands still
now and then for up to some seconds, and a stall of a few seconds holds
up more than a twentieth of a window's requests."""

import math


def read(rec):
    lat = sorted(rec["latency_ms"])
    if not lat:
        return None
    v = lat[max(0, math.ceil(0.95 * len(lat)) - 1)]
    return v if math.isfinite(v) else None

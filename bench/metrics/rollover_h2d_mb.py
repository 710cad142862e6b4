"""NRT commit and rollover: the bytes commits and their prewarm
hydrations placed on the device (``h2d_bytes`` of the program's spans
``fleet.commit`` and ``fleet.hydrate``), per commit of the traced window,
in MB (10^6 bytes). None where the program has no ``fleet.commit`` span."""


def read(rec):
    fleet = (rec.get("trace") or {}).get("fleet") or {}
    commits = fleet.get("fleet.commit")
    if not commits or not commits["count"]:
        return None
    placed = sum(fleet.get(name, {}).get("counters", {}).get("h2d_bytes", 0)
                 for name in ("fleet.commit", "fleet.hydrate"))
    return placed / commits["count"] / 1e6

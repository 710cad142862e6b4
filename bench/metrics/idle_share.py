"""Device: the share of the traced window in which no operation ran on the
chip, 100 * (1 - busy / window), busy being the union of the XLA ops'
intervals."""


def read(rec):
    t = rec["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

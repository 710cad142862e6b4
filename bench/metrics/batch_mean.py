"""Gateway admission window (core/gateway.py): queries per flushed window,
from the gateway's own window_stats over the measured window."""


def read(rec):
    return rec["batch_mean"]

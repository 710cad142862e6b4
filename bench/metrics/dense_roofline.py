"""Dense tier kernel (kernels/dot_topk.py): the least time of the window's
dense dispatches (bench/work.py: each partition's vector matrix read once
per batch, or 2*Q*N*D operations at the bf16 peak, whichever is longer)
over the device time of the ``dot_topk`` program, in %."""

from bench.work import least_time


def read(rec):
    t = rec["trace"]
    s = t.get("module_s", {}).get("jit_dot_topk") if t else None
    work = rec["work"].get("dense")
    if not s or not work:
        return None
    least = sum(least_time(b, ops, rec["peak"]) for b, ops in work)
    return 100.0 * least / s

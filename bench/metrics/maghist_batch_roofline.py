"""Roofline share of the candidate report's magnitude histogram: the least
time of one pass over the round's (N, d) float32 gradients, once per round,
over the device time of the ``maghist_batch`` kernel."""

KERNEL = "maghist_batch"


def read(ctx):
    t = ctx["trace"]
    if not t or not t["op_s"].get(KERNEL):
        return None
    w = ctx["work"]
    least = w.least_seconds(*w.maghist_least(ctx["n"], ctx["d"]), ctx["peak"])
    return 100.0 * ctx["rounds"] * least / t["op_s"][KERNEL]

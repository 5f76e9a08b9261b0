"""Share of the traced window that the engine's round loop spent blocked on the every-M
recluster (the engine's ``recluster_wait_s`` counter, accrued in the
window). Nothing to read where the window holds no recluster."""


def read(ctx):
    t = ctx["trace"]
    rounds_per_recluster = ctx["protocol"]["M"]
    if not t or ctx["rounds"] < rounds_per_recluster:
        return None
    return 100.0 * ctx["recluster_wait_s"] / t["window_s"]

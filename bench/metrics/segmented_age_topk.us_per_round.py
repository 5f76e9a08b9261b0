"""Device time of the segmented age top-k selection kernel per round. The
kernel is latency-bound (k passes over the candidates), so its time says
more than a roofline would."""

KERNEL = "segmented_age_topk"


def read(ctx):
    t = ctx["trace"]
    if not t or not t["op_s"].get(KERNEL) or not ctx["rounds"]:
        return None
    return 1e6 * t["op_s"][KERNEL] / ctx["rounds"]

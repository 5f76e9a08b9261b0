"""Roofline share of the sparse aggregation: the least time of reading the
round's N*k (index, value) pairs and writing d dense sums, once per round,
over the device time of the ``sparse_aggregate`` kernel."""

KERNEL = "sparse_aggregate"


def read(ctx):
    t = ctx["trace"]
    if not t or not t["op_s"].get(KERNEL):
        return None
    w = ctx["work"]
    nk = ctx["n"] * ctx["protocol"]["k"]
    least = w.least_seconds(*w.sparse_aggregate_least(nk, ctx["d"]),
                            ctx["peak"])
    return 100.0 * ctx["rounds"] * least / t["op_s"][KERNEL]

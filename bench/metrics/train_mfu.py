"""Share of the chips' bf16 peak that the clients' training FLOPs fill over
the traced window: the forward and backward FLOPs of every sample trained
(``work.train_flops_per_sample``, no recompute counted) over window x chips
x peak. Eval, the protocol and host stops count as time, not work."""


def read(ctx):
    t = ctx["trace"]
    if not t or not ctx["samples"]:
        return None
    flops = ctx["work"].train_flops_per_sample(ctx["config"]) * ctx["samples"]
    return 100.0 * flops / (t["window_s"] * t["devices"]
                            * ctx["peak"]["bf16_flops_per_s"])

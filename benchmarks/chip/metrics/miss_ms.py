"""miss_ms: the window's seconds over the plan-miss multiplies it
completed, each from a new pattern to C's values ready on the device
(host clock)."""


def read(ctx):
    w = ctx.window
    if w.loop != "churn":
        return None
    return w.window_s / len(w.latencies) * 1e3

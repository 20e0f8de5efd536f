"""plan_build_ms.churn: mean of the benchmark's span around
``cached_plan`` and the plan's stream build, over the window's misses
(host clock): pattern fingerprints and the host symbolic phase."""


def read(ctx):
    w = ctx.window
    if w.loop != "churn" or w.plan_s is None:
        return None
    return float(w.plan_s.mean()) * 1e3

"""host_ms_per_call.replay: mean over the window's calls of the call's
span minus the device busy time inside it: the host's own time per
plan-hit multiply (operand checks, value upload, dispatch, the wait for
the result)."""


def read(ctx):
    w, t = ctx.window, ctx.trace
    if w.loop != "replay" or t is None or t.busy_s <= 0:
        return None
    spans = t.span_s("bench.call")
    if len(spans) == 0:
        return None
    return float((spans - t.busy_in("bench.call")).mean()) * 1e3

"""replay_device_ms: device busy time in the traced window (the union of
its operations' intervals) over the multiplies in it."""


def read(ctx):
    w, t = ctx.window, ctx.trace
    if w.loop != "replay" or t is None or t.busy_s <= 0:
        return None
    return t.busy_s / len(w.latencies) * 1e3

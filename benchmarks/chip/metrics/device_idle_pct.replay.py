"""device_idle_pct.replay: the share of the traced window in which no
operation ran on the device, in the replay cells."""


def read(ctx):
    t = ctx.trace
    if ctx.window.loop != "replay" or t is None or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)

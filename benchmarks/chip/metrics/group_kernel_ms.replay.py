"""group_kernel_ms.replay: device time of the per-group SPA and SPARS
``pallas_call``s per call in the traced window (``pallas_spans.kernel_s``)."""

import pallas_spans


def read(ctx):
    w, t = ctx.window, ctx.trace
    if w.loop != "replay" or t is None or t.busy_s <= 0:
        return None
    s = pallas_spans.kernel_s(t, "spa") + pallas_spans.kernel_s(t, "spars")
    return s / len(w.latencies) * 1e3 if s > 0 else None

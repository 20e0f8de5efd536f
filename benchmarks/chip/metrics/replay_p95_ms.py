"""replay_p95_ms: 95th percentile of the latency of every multiply in the
window, from the call to C's values ready on the device (host clock)."""

import numpy as np


def read(ctx):
    w = ctx.window
    if w.loop != "replay":
        return None
    return float(np.percentile(w.latencies, 95)) * 1e3

"""compile_ms.churn: JAX's compile pipeline per miss in the window.

The sum of JAX's ``jax.monitoring`` durations of tracing, lowering and
the backend compile step over the window, over the misses.  Every
executable is in the persistent compilation cache by then, so the backend
step is the executable's load.
"""


def read(ctx):
    w = ctx.window
    if w.loop != "churn":
        return None
    return w.compile_s / len(w.latencies) * 1e3

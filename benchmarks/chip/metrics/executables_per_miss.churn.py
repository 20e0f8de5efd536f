"""executables_per_miss.churn: executables compiled or loaded in the
window, over the misses.

A count of JAX's ``jax.monitoring`` backend-compile events, each the last
step of getting one executable.  Every executable is in the persistent
compilation cache by then, so each event is a load.  It reads 1 while
every new pattern needs an executable of its own; a program that shares
executables between patterns (shape buckets) reads less.
"""


def read(ctx):
    w = ctx.window
    if w.loop != "churn":
        return None
    return w.executables / len(w.latencies)

"""replay_roofline: the share of its roofline that the replay reaches.

The least time of every multiply in the traced window (``work.py``'s
bytes and flops at ``peaks.json``'s peaks; the bytes bound it), summed,
over the device's busy time in the window.  The work comes from the
operands and the reference's C, never from the program's stream.
"""


def read(ctx):
    w, t = ctx.window, ctx.trace
    if w.loop != "replay" or t is None or ctx.least_time_s is None \
            or t.busy_s <= 0:
        return None
    return 100.0 * float(ctx.least_time_s.sum()) / t.busy_s

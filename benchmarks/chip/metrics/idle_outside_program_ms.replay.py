"""idle_outside_program_ms.replay: device idle time in the traced window
while no ``spgemm.`` span of the program is open, per call: the caller's
wait for C and its own loop."""

import program_spans


def read(ctx):
    s = program_spans.window_spans(ctx, "replay")
    if s is None or len(s.get("spgemm.execute")) == 0:
        return None
    return s.idle_ns(s.outside()) * 1e-6 / s.calls

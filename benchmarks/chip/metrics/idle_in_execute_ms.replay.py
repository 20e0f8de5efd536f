"""idle_in_execute_ms.replay: device idle time inside the program's
``spgemm.execute`` spans, per call in the traced window: the device
waiting while the host checks operands, transfers values and
dispatches."""

import program_spans


def read(ctx):
    s = program_spans.window_spans(ctx, "replay")
    if s is None or len(s.get("spgemm.execute")) == 0:
        return None
    return s.idle_ns(s.get("spgemm.execute")) * 1e-6 / s.calls

"""dispatch_ms.replay: the program's ``spgemm.dispatch`` span per call in
the traced window: the call of the plan's compiled function (host to
device transfer of the values, enqueue)."""

import program_spans


def read(ctx):
    return program_spans.per_call_ms(ctx, "replay", "spgemm.dispatch")

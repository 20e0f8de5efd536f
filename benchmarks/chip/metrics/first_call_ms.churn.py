"""first_call_ms.churn: the program's ``spgemm.first_call`` spans summed
per miss in the traced window: the first call of a plan's jitted function
(trace, lowering, executable load, first dispatch)."""

import program_spans


def read(ctx):
    return program_spans.per_call_ms(ctx, "churn", "spgemm.first_call")

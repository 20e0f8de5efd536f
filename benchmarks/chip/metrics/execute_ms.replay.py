"""execute_ms.replay: the program's ``spgemm.execute`` span per call in
the traced window: ``SpgemmPlan.execute`` from the call to its return,
which comes before the device finishes (operand checks, value transfer,
dispatch)."""

import program_spans


def read(ctx):
    return program_spans.per_call_ms(ctx, "replay", "spgemm.execute")

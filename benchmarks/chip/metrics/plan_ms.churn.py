"""plan_ms.churn: the program's ``spgemm.plan`` span per miss in the
traced window: ``cached_plan`` whole, its key's fingerprints and the plan
build."""

import program_spans


def read(ctx):
    return program_spans.per_call_ms(ctx, "churn", "spgemm.plan")

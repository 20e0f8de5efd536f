"""symbolic_ms.churn: the program's ``spgemm.symbolic`` spans summed per
miss in the traced window: the host symbolic phase that builds the
product stream (``fast.build_product_stream``)."""

import program_spans


def read(ctx):
    return program_spans.per_call_ms(ctx, "churn", "spgemm.symbolic")

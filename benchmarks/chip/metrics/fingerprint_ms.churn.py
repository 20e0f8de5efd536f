"""fingerprint_ms.churn: the program's ``spgemm.fingerprint`` spans summed
per miss in the traced window: the pattern hashes of the cache key and of
the plan's patterns."""

import program_spans


def read(ctx):
    return program_spans.per_call_ms(ctx, "churn", "spgemm.fingerprint")

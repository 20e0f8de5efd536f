"""device_lift_ms.churn: the program's ``spgemm.device_lift`` spans summed
per miss in the traced window: the stream's segment ids and the upload
of its three index arrays to the device."""

import program_spans


def read(ctx):
    return program_spans.per_call_ms(ctx, "churn", "spgemm.device_lift")

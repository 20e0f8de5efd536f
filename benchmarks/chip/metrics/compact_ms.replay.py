"""compact_ms.replay: the program's ``spgemm.pallas.compact`` spans summed
per call in the traced window: the device gather's dispatch and the drop
of C's exact zeros (device compaction), or the host compaction of each
dense tile into C's columns (``CSCBuilder``) and the final build."""

import pallas_spans


def read(ctx):
    return pallas_spans.per_call_ms(ctx, "spgemm.pallas.compact")

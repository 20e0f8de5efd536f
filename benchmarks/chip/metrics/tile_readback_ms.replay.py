"""tile_readback_ms.replay: the program's ``spgemm.pallas.readback`` spans
summed per call in the traced window: the device-to-host copy of C's
values (device compaction) or of each launch's dense tile (host
compaction)."""

import pallas_spans


def read(ctx):
    return pallas_spans.per_call_ms(ctx, "spgemm.pallas.readback")

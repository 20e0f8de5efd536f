"""spa_roofline.replay: the SPA kernel's share of its roofline in the
traced window: the least time of the products its launches computed
(each call's least time from ``work.py`` and ``peaks.json``, split by
the launches' ``products``) over its device time.  ``None`` where the
window had no SPA launch (``pallas_spans.roofline_pct``)."""

import pallas_spans


def read(ctx):
    return pallas_spans.roofline_pct(ctx, "spa")

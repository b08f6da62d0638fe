"""Time in ``kv.resort`` (the in-memory KV's ordered key list extended
and re-sorted after puts) a live SUBSCRIBE routed (``sub.route``)."""
from . import ratio
from .totals import totals


def read(ctx):
    t = totals(ctx)
    resort, sub = t.get("kv.resort"), t.get("sub.route")
    if not resort or not sub:
        return None
    return ratio(resort[1], sub[0], 1e3)

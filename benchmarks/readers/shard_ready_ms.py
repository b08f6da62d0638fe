"""Per-shard dispatch -> ready time of a mesh step: total of the span
``device.shard_ready`` (one a dispatched shard a batch) over its count."""
from . import ratio
from .totals import totals


def read(ctx):
    t = totals(ctx).get("device.shard_ready")
    if not t:
        return None
    return ratio(t[1], t[0], 1e3)

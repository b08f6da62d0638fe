"""Mean ``sub.route`` span: SUBSCRIBE parsed -> SUBACK queued, from inside
the broker (the consensus write, the KV put, the matcher patch)."""
from . import ratio
from .totals import totals


def read(ctx):
    sub = totals(ctx).get("sub.route")
    if not sub:
        return None
    return ratio(sub[1], sub[0], 1e3)

"""Mean ``unsub.route`` span: UNSUBSCRIBE parsed -> UNSUBACK queued, from
inside the broker (the consensus write, the KV delete and its re-sort, the
matcher patch)."""
from . import ratio
from .totals import totals


def read(ctx):
    unsub = totals(ctx).get("unsub.route")
    if not unsub:
        return None
    return ratio(unsub[1], unsub[0], 1e3)

"""Mean ``sub.retained`` span: one SUBSCRIBE's retained delivery inside
``sub.route``, from the retain service's match to the last matched message
handed to the session's send path or queued behind a full window."""
from . import ratio
from .totals import totals


def read(ctx):
    sub = totals(ctx).get("sub.retained")
    if not sub:
        return None
    return ratio(sub[1], sub[0], 1e3)

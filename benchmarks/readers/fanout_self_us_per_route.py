"""The fan-out's own time a route: the ``deliver.fanout`` span less the
sub-broker calls inside it (``deliver.call``), over the routes handed
over (``deliver.routes``): election, grouping, packs, results read back."""
from . import ratio
from .totals import totals


def read(ctx):
    t = totals(ctx)
    fan, call = t.get("deliver.fanout"), t.get("deliver.call")
    routes = t.get("deliver.routes")
    if not fan or not call or not routes:
        return None
    return ratio(fan[1] - call[1], routes[0], 1e6)

"""Mean ``retain.scan`` span: one retained scan batch on SUBSCRIBE, from the
serve's call (the filter-keyed cache probe) to its rows filled: the walk,
its escalation and expansion for the filters the cache missed."""
from . import ratio
from .totals import totals


def read(ctx):
    scan = totals(ctx).get("retain.scan")
    if not scan:
        return None
    return ratio(scan[1], scan[0], 1e3)

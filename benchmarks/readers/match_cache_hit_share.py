"""Rows answered by the matcher's result cache (``models/matchcache.py``,
scope ``matcher``), of all the rows that reached the matcher in the window:
counters ``match.cache.hits`` / ``match.cache.lookups``."""
from . import ratio
from .totals import totals


def read(ctx):
    t = totals(ctx)
    lookups = t.get("match.cache.lookups")
    if not lookups:
        return None
    return ratio(t.get("match.cache.hits", (0, 0.0))[0], lookups[0], 100.0)

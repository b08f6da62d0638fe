"""Filters handed to the retained scan plane that a walk served, in
percent: 1 - ``retain.scan.cache_hits`` / ``retain.scan.queries`` (the
rest the filter-keyed scan cache answered)."""
from . import ratio
from .totals import totals


def read(ctx):
    t = totals(ctx)
    queries = t.get("retain.scan.queries")
    if not queries:
        return None
    hits = t.get("retain.scan.cache_hits", (0, 0.0))[0]
    return ratio(queries[0] - hits, queries[0], 100.0)

"""Publishes answered by the dist service's pub-side match cache, of all
it looked up in the window."""
from . import delta, ratio


def read(ctx):
    hits = delta(ctx, "pubcache.hits")
    return ratio(hits, hits + delta(ctx, "pubcache.misses"), 100.0)

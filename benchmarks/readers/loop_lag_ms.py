"""Mean lateness of the serving loop's fixed 20 ms heartbeat (``loop.lag``):
how long the one thread was held by something else when a beat was due."""
from . import ratio
from .totals import totals


def read(ctx):
    lag = totals(ctx).get("loop.lag")
    if not lag:
        return None
    return ratio(lag[1], lag[0], 1e3)

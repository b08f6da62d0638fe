"""Median SUBSCRIBE sent -> SUBACK received of the window's live churn
(load generator's clock): the raft -> patch path under load."""
from . import percentile


def read(ctx):
    return percentile(ctx["report"]["subscribe_ms"], 50)

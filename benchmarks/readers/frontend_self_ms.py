"""The frontend's own time a publish: the ``pub.ingest`` span (PUBLISH
parsed -> dist call returned and acked) less the ``dist.pub`` span inside
it (queue wait + match + fan-out)."""
from . import ratio
from .totals import totals


def read(ctx):
    t = totals(ctx)
    ingest, dist = t.get("pub.ingest"), t.get("dist.pub")
    if not ingest or not dist:
        return None
    return ratio(ingest[1] - dist[1], ingest[0], 1e3)

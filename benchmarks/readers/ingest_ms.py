"""Mean of the session's ``ingest`` stage: PUBLISH parsed -> dist call
returned (it spans the queue wait, the match and the fan-out)."""
from . import delta, ratio


def read(ctx):
    return ratio(delta(ctx, "stage.ingest.sum_s"),
                 delta(ctx, "stage.ingest.n"), 1e3)

"""Mean wait in the dist service's publish batcher (``queue_wait``)."""
from . import delta, ratio


def read(ctx):
    return ratio(delta(ctx, "stage.queue_wait.sum_s"),
                 delta(ctx, "stage.queue_wait.n"), 1e3)

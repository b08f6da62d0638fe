"""The dist service's ``deliver`` stage (fan-out of one publish to its
sub-brokers) per route delivery completed in the window."""
from . import delta, ratio


def read(ctx):
    return ratio(delta(ctx, "stage.deliver.sum_s"),
                 ctx["route_deliveries"], 1e6)

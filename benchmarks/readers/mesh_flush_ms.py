"""Seconds a per-shard patch flush of a mesh takes (the program's stage
``mesh.flush``, which the matcher also sums as its patch device time):
the scatters into one shard's slice of the stacked tables, or the
restack. ``None`` off a mesh."""
from . import delta, ratio


def read(ctx):
    if "mesh.rows_each" not in ctx["after"]:
        return None
    return ratio(delta(ctx, "patch.device_s"), delta(ctx, "patch.flushes"),
                 1e3)

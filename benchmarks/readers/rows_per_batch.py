"""Mean topics walked per device batch (its padded size is printed on an
earlier line)."""
from . import ratio


def read(ctx):
    return ratio(ctx["batches"]["rows"], ctx["batches"]["n"])

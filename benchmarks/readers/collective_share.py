"""Device time of the operations that cross chips (the ring's
``collective-permute``, an ``all-reduce``) over the device time of all
operations, summed over the planes of the traced window, in percent.
``None`` on one plane, or where no collective ran."""


def read(ctx):
    tr = ctx.get("trace")
    each = (tr or {}).get("busy_each") or []
    if len(each) < 2 or sum(each) <= 0 or not tr.get("collective_s"):
        return None
    return 100.0 * tr["collective_s"] / sum(each)

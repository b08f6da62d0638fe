"""Busiest device plane's busy seconds over the mean of all planes in
the traced window: 1 is an even mesh. ``None`` on one plane."""


def read(ctx):
    tr = ctx.get("trace")
    each = (tr or {}).get("busy_each") or []
    if len(each) < 2 or sum(each) <= 0:
        return None
    return max(each) * len(each) / sum(each)

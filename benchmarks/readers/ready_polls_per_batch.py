"""``is_ready`` polls that found the walk unfinished (``ready.polls``,
counted in ``wait_ready``) a device batch dispatched."""
from . import ratio
from .totals import totals


def read(ctx):
    t = totals(ctx)
    polls, disp = t.get("ready.polls"), t.get("device.dispatch")
    if polls is None or not disp:
        return None
    return ratio(polls[0], disp[0])

"""Retained filter rows the exact host oracle (``match_filter_host``)
answered, in percent of the rows any path answered: ``retain.rows.oracle``
over ``retain.rows.device`` + ``.native`` + ``.oracle``."""
from . import ratio
from .totals import totals

PATHS = ("retain.rows.device", "retain.rows.native", "retain.rows.oracle")


def read(ctx):
    t = totals(ctx)
    rows = [t.get(name, (0, 0.0))[0] for name in PATHS]
    if not sum(rows):
        return None
    return ratio(rows[2], sum(rows), 100.0)

"""Mean ``deliver.group`` span, microseconds a publish's fan-out: the
program's ``_group_targets``, which is the ELECTION of one member a
matching group (``_elect``: the balancer's scan of the members for
``$share``, a BLAKE2b rendezvous hash a member for ``$oshare``), the
persistent byte cap, and the grouping of the targets into sub-broker calls
(a kept plan joined with the elected members). The program has no span for
the election alone yet (PERF.md section 7)."""
from . import ratio
from .totals import totals


def read(ctx):
    group = totals(ctx).get("deliver.group")
    if not group:
        return None
    return ratio(group[1], group[0], 1e6)

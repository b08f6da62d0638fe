"""Host planning + device scatter time per patch flush in the window
(the live SUBSCRIBE / UNSUBSCRIBE churn folded into the resident tables)."""
from . import delta, ratio


def read(ctx):
    return ratio(delta(ctx, "patch.host_s") + delta(ctx, "patch.device_s"),
                 delta(ctx, "patch.flushes"), 1e3)

"""Mean host time per device batch: tokenize + dispatch + fetch + expand
of the profiler's BatchRecords (``ready_s``, the wait for the device,
left out)."""
from . import ratio


def read(ctx):
    b = ctx["batches"]
    s = b["sums"]
    return ratio(s["tokenize_s"] + s["dispatch_s"] + s["fetch_s"]
                 + s["expand_s"], b["n"], 1e3)

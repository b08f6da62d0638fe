"""p95 of (actually sent - due): a starved generator must not read as a
fast broker. Closed loops send when the last reply came, so read ~0."""
from . import percentile


def read(ctx):
    return percentile(ctx["report"]["gen_late_ms"], 95)

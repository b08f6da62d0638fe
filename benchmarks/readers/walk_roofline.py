"""Least time for the traced window's walks over the summed device time
of the walk and expand programs. Bound: HBM bandwidth (``roofline.py``)."""
import roofline
import trace_reduce

# jitted names as the v5e trace shows them (PR 28): ``_walk_routes_fn``,
# ``_expand_routes_fn``; a later walk keeps a name that starts alike. The
# mesh's step and expand are the shard_map'd ``local_step`` / ``local_expand``
# (PR 38); their device time is summed over every chip's plane.
PROGRAMS = ("_walk_routes", "walk_routes", "_expand_routes", "expand_routes",
            "local_step", "local_expand")


def read(ctx):
    tr, ref = ctx.get("trace"), ctx.get("reference")
    if not tr or not ref or not ref.get("traced_topics"):
        return None
    seconds = trace_reduce.program_seconds(tr, PROGRAMS)
    if seconds <= 0:
        return None
    need = roofline.walk_bytes(
        ref["visited_per_topic"] * ref["traced_topics"],
        ref["matched_per_topic"] * ref["traced_topics"],
        ref["traced_topics"], ctx["device"]["record_bytes"])
    return roofline.roofline_share(need, seconds, ctx["peaks"])

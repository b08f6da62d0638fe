"""Least time for the traced retained walks over the device time of the
retained walk programs, in percent. Bound: HBM bandwidth (``roofline.py``).

The least bytes a walked filter row needs: one edge-table bucket a level of
its filter (a level is one hashed probe of one bucket; ``+`` and ``#``
read less) and one emitted range (a start and a count, int32). Rows are
those of the traced walks: the programs' calls in the trace times the rows
a walk took in the counted window (filters that missed the scan cache over
``retain.scan.walks``); levels are the mean of the window's SUBSCRIBE-lane
filters. The bucket's bytes are read off the resident retained edge table.
"""
import roofline
import trace_reduce

from .totals import totals

# jitted names as the v5e trace shows them: ``retained_walk_ext`` (the
# extras-aware walk every scan dispatches) and its plain twin
PROGRAMS = ("retained_walk",)
RANGE_BYTES = 8


def _bucket_bytes():
    try:
        from bifromq_tpu.obs import OBS
        planes = list(getattr(OBS, "_retained_planes", ()))
    except ImportError:
        return None
    for plane in planes:
        dev = getattr(plane.index, "_device_tables", None)
        if dev is not None:
            edge = dev.edge_tab
            return edge.shape[1] * edge.shape[2] * edge.dtype.itemsize
    return None


def _mean_levels(report):
    ops = report.get("retained", {}).get("ops", ())
    levels = [len(op[5].split("/")) for op in ops if op[6]]
    return sum(levels) / len(levels) if levels else None


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx.get("peaks"):
        return None
    progs = [v for p, v in tr["programs"].items()
             if any(p.startswith(n) for n in PROGRAMS)]
    seconds = trace_reduce.program_seconds(tr, PROGRAMS)
    calls = sum(v["calls"] for v in progs)
    t = totals(ctx)
    walks = t.get("retain.scan.walks", (0, 0.0))[0]
    queries = t.get("retain.scan.queries", (0, 0.0))[0]
    hits = t.get("retain.scan.cache_hits", (0, 0.0))[0]
    levels = _mean_levels(ctx["report"])
    bucket = _bucket_bytes()
    if seconds <= 0 or not walks or levels is None or not bucket:
        return None
    rows = calls * (queries - hits) / walks
    need = rows * (levels * bucket + RANGE_BYTES)
    return roofline.roofline_share(need, seconds, ctx["peaks"])

"""The program's window totals over the counted window: the one reader
helper that imports the program.

``TRACER.totals`` keeps per-name running sums ``(n, total_s)`` of every
span closed and every counter recorded, in one-second slices for minutes,
so the window can be taken after the run: ``between(before, after)`` over
the two ``t_ns`` stamps of ``sut.counters``. A program without window
totals (a parent commit) gives ``{}``, and every reader then ``None``.
"""
import json


def totals(ctx) -> dict:
    """``{name: (n, total_s)}``; read once a run and kept in ``ctx``."""
    if "totals" not in ctx:
        try:
            from bifromq_tpu.trace import TRACER
            t0, t1 = ctx["before"]["t_ns"], ctx["after"]["t_ns"]
            ctx["totals"] = TRACER.totals.between(t0, t1)
            peaks = TRACER.totals.peaks(t0, t1)
        except (ImportError, AttributeError, KeyError):
            ctx["totals"] = peaks = {}
        if ctx["totals"]:       # the whole table, once, for the books
            print("[bench] window totals (n, total_s, max_s): " + json.dumps(
                {k: [n, round(s, 6), round(peaks.get(k, 0.0), 6)]
                 for k, (n, s) in sorted(ctx["totals"].items())}),
                flush=True)
    return ctx["totals"]

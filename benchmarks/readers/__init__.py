"""One small reader per layer metric: ``read(ctx) -> number or None``.

``ctx`` holds what a traced run gathered: ``report`` (the load
generator's), ``before`` / ``after`` (program counters at the window's
two ends, see ``sut.counters``), ``batches`` (the profiler's batch
records summed over the window), ``trace`` (``trace_reduce``'s output or
``None``), ``reference`` (work the plain reference counted), ``peaks``,
``device`` and ``seconds``. A reader that finds nothing to read returns
``None`` and the metric is left out of the line — never 0.
"""


def delta(ctx: dict, key: str) -> float:
    return ctx["after"].get(key, 0) - ctx["before"].get(key, 0)


def ratio(num: float, den: float, scale: float = 1.0):
    return scale * num / den if den else None


def percentile(values, q: float):
    """Nearest rank on the sorted values (q in 0..100)."""
    if not values:
        return None
    s = sorted(values)
    k = max(0, min(len(s) - 1, int(round(q / 100.0 * len(s) + 0.5)) - 1))
    return s[k]

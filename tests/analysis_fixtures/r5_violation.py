"""R5 fixture: unregistered stage + cache-field typo + a boundary name
with no row in the registry."""
from bifromq_tpu import trace
from bifromq_tpu.utils.metrics import MATCH_CACHE, STAGES


def bad_stage(dt):
    # R5: not in KNOWN_STAGES — would open an orphan histogram
    STAGES.record("devcie.dispatch", dt)


def bad_cache_field():
    # R5: typo'd field not in MatchCacheMetrics._FIELDS
    MATCH_CACHE.inc("matcher", "hist", 1)


def bad_boundary():
    # R5: a span and a counter opened under names trace/names.py lacks
    with trace.span("deliver.fanuot"):
        trace.count("redy.polls", 1)

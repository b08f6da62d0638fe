"""R5 clean twin: registered names only."""
from bifromq_tpu import trace
from bifromq_tpu.utils.metrics import MATCH_CACHE, STAGES


def good_stage(dt):
    STAGES.record("device.dispatch", dt)


def good_cache_field():
    MATCH_CACHE.inc("matcher", "hits", 1)


def good_boundary():
    with trace.span("deliver.fanout"):
        trace.count("ready.polls", 1)

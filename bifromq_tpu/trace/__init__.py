"""Distributed tracing & hot-path profiling (ISSUE 2): an HLC-stamped
flight recorder for publish→match→deliver.

Usage at an instrumentation site::

    from .. import trace
    with trace.span("match.device", tenant=tenant_id, n=len(queries)):
        ...

A span always times its boundary (``time.monotonic_ns``) and feeds the
name's window totals (``TRACER.totals``), stage histogram and tenant
window as ``trace/names.py`` registers them; it materializes a ``Span``
into the ring only when sampling says so (per-tenant probabilistic via
``TRACER.sampler``, always-on-slow via ``TRACER.slow_ms``, env knobs
``BIFROMQ_TRACE_SAMPLE`` / ``BIFROMQ_TRACE_SLOW_MS``). The RPC fabric
carries contexts across processes; the API server serves the rings at
``/trace`` and ``/trace/slow``.
"""

from .names import BOUNDARIES, KNOWN_STAGES, Boundary
from .recorder import SpanRing
from .sampler import TenantSampler
from .span import Span, SpanContext, decode_ctx, new_id
from .totals import WindowTotals
from .tracer import (LINK_CAP, TRACER, Tracer, activate, count, current_ctx,
                     extract, inject, open_batch, record_finished, span)

__all__ = [
    "BOUNDARIES", "Boundary", "KNOWN_STAGES", "LINK_CAP", "TRACER", "Tracer",
    "Span", "SpanContext", "SpanRing", "TenantSampler", "WindowTotals",
    "activate", "count", "current_ctx", "decode_ctx", "extract", "inject",
    "new_id", "open_batch", "record_finished", "span",
]

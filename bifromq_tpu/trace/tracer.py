"""The one recorder on the publish path: every layer boundary timed once,
on one clock, feeding every reader.

Design constraints:

- **One timing per boundary.** ``span(name)`` is the only place a
  boundary is timed. Sampled or not, a span stamps its start and end with
  ``time.monotonic_ns()`` (the load generator's clock), adds ``(1,
  duration)`` to its name's window totals (``TRACER.totals``, one-second
  slices kept for minutes) and feeds the cumulative stage histogram and
  the tenant window that ``trace/names.py`` registers for the name — all
  from ``Tracer._feed``. A span whose registered body never yields to the
  event loop also opens a ``jax.profiler.TraceAnnotation`` of the same
  bare name, so it lands on the ``/host:CPU`` plane of a profiler trace,
  on the device trace's clock.
- **What "off" is.** With sampling off (the default) that always-on part
  is the whole cost: two clock reads, one slot object, one slice add, one
  annotation enter/exit. No ``Span``, no ids, nothing in the ring.
- **Sampling decides at the ROOT.** A root span (no active context) draws
  a trace id and asks the per-tenant sampler once; the verdict propagates
  to every child (in-process via the contextvar, cross-process via the
  wire context), so traces are never fragmented by independent
  re-sampling. Unsampled roots still install a not-sampled context so
  descendants don't try to become roots themselves. Only a sampled trace
  materializes ``Span``s (ids, HLC stamps for causal order across
  processes, and the same monotonic stamps) into the ring.
- **Slow outliers are always captured** (when ``slow_ms`` is set): an
  unsampled root that crosses the threshold lands in the slow ring. Child
  detail is absent for such traces (the decision is only knowable at the
  end); probabilistically sampled traces that turn out slow land in BOTH
  rings.
- **Causal order across processes** comes from the HLC handshake: contexts
  carry the sender's stamp, ``decode_ctx`` merges it, so remote child spans
  start at a strictly larger HLC than their parent's start.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import sys
import time
from typing import Dict, Iterator, List, Optional

from ..utils import env as _env
from ..utils.hlc import HLC
from .names import BOUNDARIES as _ROWS
from .recorder import SpanRing
from .sampler import TenantSampler
from .span import Span, SpanContext, decode_ctx, new_id
from .totals import NS as _NS
from .totals import WindowTotals

_now_ns = time.monotonic_ns

_CTX: contextvars.ContextVar[Optional[SpanContext]] = contextvars.ContextVar(
    "bifromq_trace_ctx", default=None)


def current_ctx() -> Optional[SpanContext]:
    return _CTX.get()


@contextlib.contextmanager
def activate(ctx: Optional[SpanContext]) -> Iterator[None]:
    """Install ``ctx`` as the active trace context for the block. Always
    sets (a None CLEARS a stale inherited context — batch-emit tasks and
    server connection loops must not leak a previous request's trace)."""
    token = _CTX.set(ctx)
    try:
        yield
    finally:
        _CTX.reset(token)


# the id of the batch this task is serving (``open_batch``): stamped on
# every sampled span below it as the ``batch_id`` tag
_BATCH: contextvars.ContextVar[int] = contextvars.ContextVar(
    "bifromq_trace_batch", default=0)
_batch_ids = itertools.count(1)


def open_batch() -> int:
    """Draw a process-unique batch id and make it this task's: a batcher
    calls it once per emitted batch, in the task that serves the batch."""
    bid = next(_batch_ids)
    _BATCH.set(bid)
    return bid


_ANNOTATE = None        # jax.profiler.TraceAnnotation, once JAX is seen


def _annotation_cls():
    """``jax.profiler.TraceAnnotation`` once this process has imported
    JAX (a process that has not cannot be under its profiler either);
    nothing where JAX is absent. Never imports JAX itself."""
    global _ANNOTATE
    if _ANNOTATE is None and "jax" in sys.modules:
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATE = TraceAnnotation
        except Exception:  # noqa: BLE001 — a JAX without the profiler
            _ANNOTATE = False
    return _ANNOTATE or None


_SINKS = None


def _sinks():
    """(STAGES, OBS): the cumulative stage histograms and the tenant
    windows a boundary's exit feeds. Resolved on first use: ``utils.
    metrics`` imports this package's registry, not the other way."""
    global _SINKS
    if _SINKS is None:
        from ..obs import OBS
        from ..utils.metrics import STAGES
        _SINKS = (STAGES, OBS)
    return _SINKS


# a span links at most this many extra callers (ISSUE 5 satellite: one
# pathological batch must not bloat a ring slot). THE bound — the batcher
# collects against it too.
LINK_CAP = 16


class _Sampled:
    """The recording half of a sampled span: installs its context on
    enter, materializes a ``Span`` into the tracer's ring on exit."""

    __slots__ = ("ctx", "parent_id", "links", "start_hlc", "_token",
                 "_ring_mark")
    sampled = True

    def __init__(self, trace_id: int, parent_id: int, tenant: str) -> None:
        self.ctx = SpanContext(trace_id, new_id(), True, tenant)
        self.parent_id = parent_id
        self.links: tuple = ()

    def enter(self, tracer: "Tracer") -> None:
        self._token = _CTX.set(self.ctx)
        # remember the ring write-counter (slow capture armed only): a
        # slow finish then scans just the spans recorded during its own
        # lifetime — its local descendants by construction — not the
        # whole ring. Tracked for EVERY span, not only process-local
        # roots: the server half of a cross-process trace has a remote
        # parent id, and its slow spans must drag their children too.
        self._ring_mark = (tracer.ring._written
                           if tracer.slow_ms is not None else None)
        self.start_hlc = HLC.INST.get()

    def exit(self, tracer: "Tracer", slot: "_Boundary", exc_type) -> None:
        _CTX.reset(self._token)
        if exc_type is not None:
            slot.tags.setdefault("error", exc_type.__name__)
        batch_id = _BATCH.get()
        if batch_id:
            slot.tags.setdefault("batch_id", batch_id)
        tracer._finish(Span(
            name=slot.name, trace_id=self.ctx.trace_id,
            span_id=self.ctx.span_id, parent_id=self.parent_id,
            tenant=self.ctx.tenant, service=tracer.service,
            start_hlc=self.start_hlc, end_hlc=HLC.INST.get(),
            duration_ms=(slot.end_ns - slot.start_ns) / 1e6,
            status="error" if exc_type is not None else "ok",
            tags=slot.tags, links=self.links,
            start_ns=slot.start_ns, end_ns=slot.end_ns),
            ring_mark=self._ring_mark)


class _UnsampledRoot:
    """Root that lost the sampling draw: blocks descendants (installs a
    not-sampled context) and, when a slow threshold is armed, lands in
    the slow ring if it crosses the threshold."""

    __slots__ = ("ctx", "start_hlc", "_token")
    sampled = False
    links = ()

    def __init__(self, trace_id: int, tenant: str) -> None:
        self.ctx = SpanContext(trace_id, 0, False, tenant)

    def enter(self, tracer: "Tracer") -> None:
        self._token = _CTX.set(self.ctx)
        self.start_hlc = HLC.INST.get()

    def exit(self, tracer: "Tracer", slot: "_Boundary", exc_type) -> None:
        _CTX.reset(self._token)
        duration_ms = (slot.end_ns - slot.start_ns) / 1e6
        slow = tracer.slow_ms
        if slow is not None and duration_ms >= slow:
            slot.tags["slow_only"] = True
            tracer.slow_ring.record(Span(
                name=slot.name, trace_id=self.ctx.trace_id,
                span_id=new_id(), parent_id=0, tenant=self.ctx.tenant,
                service=tracer.service, start_hlc=self.start_hlc,
                end_hlc=HLC.INST.get(), duration_ms=duration_ms,
                status="error" if exc_type is not None else "ok",
                tags=slot.tags, start_ns=slot.start_ns,
                end_ns=slot.end_ns))


class _Boundary:
    """One timing of one boundary. Always: two ``monotonic_ns`` stamps,
    the name's window totals, its stage histogram and tenant window (as
    the registry row says). Only under a sampled trace: a ``Span`` in the
    ring. After exit ``duration_s`` is the one measurement every sink
    got."""

    __slots__ = ("_tracer", "name", "_row", "tenant", "tags", "start_ns",
                 "end_ns", "_rec", "_ann", "shares", "waited_ns")

    def __init__(self, tracer: "Tracer", name: str, row, tenant, tags,
                 rec) -> None:
        self._tracer = tracer
        self.name = name
        self._row = row
        self.tenant = tenant
        self.tags = tags
        self._rec = rec
        self.shares = None
        self.waited_ns = 0

    @property
    def sampled(self) -> bool:
        rec = self._rec
        return rec is not None and rec.sampled

    @property
    def ctx(self) -> Optional[SpanContext]:
        rec = self._rec
        return rec.ctx if rec is not None else None

    @property
    def duration_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def set_tag(self, key: str, value) -> None:
        if self._rec is not None:
            self.tags[key] = value

    def set_links(self, links) -> None:
        """Record additional sampled callers as (trace_id, span_id) span
        links (bounded): the batch-emit multi-parent satellite."""
        rec = self._rec
        if rec is not None and rec.sampled:
            rec.links = tuple(links)[:LINK_CAP]

    def charge(self, *, shares=None, waited_s: float = 0.0) -> None:
        """Say how the exit feeds the stage and the tenant window:
        ``shares`` ({tenant: weight}) splits the window feed over the
        tenants of a mixed batch; ``waited_s`` is waiting inside the
        span that is queue time and not this stage's cost."""
        self.shares = shares
        self.waited_ns = int(waited_s * 1e9)

    def __enter__(self) -> "_Boundary":
        rec = self._rec
        if rec is not None:
            rec.enter(self._tracer)
        self.start_ns = _now_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        end = self.end_ns = _now_ns()
        dur = end - self.start_ns
        tracer = self._tracer
        # WindowTotals.add(name, 1, dur, end), inlined: this exit runs
        # some fifty times a publish, and a call costs what the adds do
        totals = tracer.totals
        sec = end // _NS
        slot = totals._cur if sec == totals._cur_sec else totals._open(sec)
        cell = slot.get(self.name)
        if cell is None:
            slot[self.name] = [1, dur, dur]
        else:
            cell[0] += 1
            cell[1] += dur
            if dur > cell[2]:
                cell[2] = dur
        row = self._row
        if row is not None and row.feeds:
            tracer._feed_sinks(row, dur, self.tenant, self.shares,
                               self.waited_ns)
        rec = self._rec
        if rec is not None:
            rec.exit(tracer, self, exc_type)
        return False


class _SyncBoundary(_Boundary):
    """A boundary whose body never yields to the event loop: it also
    opens a profiler annotation of the same bare name (no keyword
    metadata, so names group), which lands on the ``/host:CPU`` plane of
    a profiler trace, on the device trace's clock. The annotation is the
    outer of the two: it opens before the span's start stamp and closes
    after the span has fed its sinks."""

    __slots__ = ()

    def __enter__(self) -> "_Boundary":
        cls = _ANNOTATE or _annotation_cls()
        if cls is not None:
            ann = self._ann = cls(self.name)
            ann.__enter__()
        else:
            self._ann = None
        return _Boundary.__enter__(self)

    def __exit__(self, exc_type, exc, tb) -> bool:
        _Boundary.__exit__(self, exc_type, exc, tb)
        ann = self._ann
        if ann is not None:
            ann.__exit__(exc_type, exc, tb)
        return False


class Tracer:
    def __init__(self, *, service: str = "bifromq",
                 sampler: Optional[TenantSampler] = None,
                 capacity: int = 4096, slow_capacity: int = 512,
                 slow_ms: Optional[float] = None) -> None:
        self.service = service
        self.sampler = sampler or TenantSampler()
        self.ring = SpanRing(capacity)
        self.slow_ring = SpanRing(slow_capacity)
        self.slow_ms = slow_ms
        self.totals = WindowTotals()

    # ---------------- hot path ---------------------------------------------

    @property
    def enabled(self) -> bool:
        return self.sampler.active or self.slow_ms is not None

    def span(self, name: str, *, tenant: Optional[str] = None, **tags):
        """Open a boundary as a context manager: timed whatever the
        sampling says. Under an active context it is that trace's child;
        otherwise a root that runs the sampling draw."""
        parent = _CTX.get()
        rec = None
        if parent is not None:
            if parent.sampled:
                rec = _Sampled(parent.trace_id, parent.span_id,
                               tenant or parent.tenant)
        elif self.sampler.active or self.slow_ms is not None:
            trace_id = new_id()
            if self.sampler.sample(tenant or "-", trace_id):
                rec = _Sampled(trace_id, 0, tenant or "-")
            else:
                rec = _UnsampledRoot(trace_id, tenant or "-")
        row = _ROWS.get(name)
        if row is not None and row.sync:
            return _SyncBoundary(self, name, row, tenant, tags, rec)
        return _Boundary(self, name, row, tenant, tags, rec)

    def count(self, name: str, k: int = 1) -> None:
        """A counter recorded at a boundary: ``k`` more of ``name`` in
        the current slice of the window totals."""
        self.totals.add(name, k, 0)

    def _feed_sinks(self, row, dur_ns: int, tenant, shares=None,
                    waited_ns: int = 0, stage: Optional[str] = None) -> None:
        """THE one function a closed boundary's measurement reaches the
        cumulative stage histogram and the tenant window through, as the
        registry row names them (the window totals took it already)."""
        seconds = (dur_ns - waited_ns) * 1e-9
        stages, obs = _SINKS or _sinks()
        if stage is None and not row.by_hand:
            stage = row.stage
        if stage is not None:
            stages.record(stage, seconds)
        window = row.window
        if window is not None:
            if shares is not None:
                for t, w in shares.items():
                    obs.record_latency(t, window, seconds * w)
            elif tenant is not None:
                obs.record_latency(tenant, window, seconds)
                if window == "queue_wait":
                    obs.record_queue_wait(tenant, seconds)

    def record_finished(self, name: str, ctx: Optional[SpanContext], *,
                        start_ns: int, end_ns: int, start_hlc: int = 0,
                        tenant: Optional[str] = None,
                        tags: Optional[Dict] = None,
                        stage: Optional[str] = None) -> None:
        """Close a boundary that was timed by two stamps and not by a
        ``with`` block (deferred spans: the batcher learns the batch
        shape only at emit time; a heartbeat's lateness has no body).
        Feeds the same sinks as a span's exit; materializes a ``Span``
        only under a sampled ``ctx``. ``stage`` overrides the row's
        histogram (a batcher built for another stage)."""
        self.totals.add(name, 1, end_ns - start_ns, end_ns)
        row = _ROWS.get(name)
        if row is not None and row.feeds:
            self._feed_sinks(row, end_ns - start_ns, tenant, stage=stage)
        if ctx is None or not ctx.sampled:
            return
        self._finish(Span(
            name=name, trace_id=ctx.trace_id, span_id=new_id(),
            parent_id=ctx.span_id, tenant=tenant or ctx.tenant,
            service=self.service, start_hlc=start_hlc,
            end_hlc=HLC.INST.get(),
            duration_ms=(end_ns - start_ns) / 1e6,
            status="ok", tags=tags or {}, start_ns=start_ns,
            end_ns=end_ns))

    # a slow ROOT drags at most this many of its children into the slow
    # ring (ISSUE 3 satellite: /trace/slow returns the full slow trace,
    # not just the root; bounded so one pathological fan-out can't flush
    # the whole slow ring)
    SLOW_CHILD_CAP = 32

    def _finish(self, span: Span, ring_mark: Optional[int] = None) -> None:
        self.ring.record(span)
        if self.slow_ms is not None and span.duration_ms >= self.slow_ms:
            self.slow_ring.record(span)
            if ring_mark is not None or span.parent_id == 0:
                self._capture_slow_children(span, ring_mark)

    def _capture_slow_children(self, slow: Span,
                               ring_mark: Optional[int]) -> None:
        """Copy a slow span's sampled local descendants from the main
        ring into the slow ring (children finish before their parent, so
        they are already recorded). Runs for any slow live span — local
        roots AND spans whose parent lives in another process (the server
        half of a cross-process trace). Children that were individually
        slow are skipped — their own ``_finish`` already placed them.
        ``ring_mark`` (the ring write-counter at span enter) bounds the
        scan to spans recorded during the slow span's own lifetime, so
        the cost tracks the trace's size, not the ring's. A fast span
        under several nested slow ancestors may be copied more than once
        — harmless for a ring, and the exporter dedupes by span id."""
        if ring_mark is not None:
            candidates, _, _ = self.ring.since(ring_mark)
        else:               # deferred spans carry no mark: full scan
            candidates = self.ring.spans()
        copied = 0
        for s in candidates:
            if copied >= self.SLOW_CHILD_CAP:
                break
            if (s.trace_id == slow.trace_id and s.span_id != slow.span_id
                    and s.duration_ms < self.slow_ms):
                self.slow_ring.record(s)
                copied += 1

    # ---------------- wire propagation -------------------------------------

    def inject(self) -> Optional[bytes]:
        """Serialize the active context (with a fresh HLC stamp) for the
        RPC request header; None when there is nothing to propagate."""
        ctx = _CTX.get()
        if ctx is None or ctx.trace_id == 0:
            return None
        return ctx.encode()

    @staticmethod
    def extract(blob: bytes) -> Optional[SpanContext]:
        return decode_ctx(blob)

    # ---------------- export / admin ---------------------------------------

    def export(self, *, trace_id: Optional[str] = None,
               tenant: Optional[str] = None, limit: int = 1000,
               slow: bool = False) -> List[dict]:
        """JSON-able spans, causally ordered by start HLC. ``trace_id`` is
        the 16-hex-char export form."""
        if limit <= 0:
            return []
        ring = self.slow_ring if slow else self.ring
        want_tid = int(trace_id, 16) if trace_id else None
        out = []
        for s in ring.spans():
            if want_tid is not None and s.trace_id != want_tid:
                continue
            if tenant is not None and s.tenant != tenant:
                continue
            out.append(s)
        out.sort(key=lambda s: s.start_hlc)
        return [s.to_dict() for s in out[-limit:]]

    def reset(self) -> None:
        self.ring.clear()
        self.slow_ring.clear()
        self.totals.clear()


# process-global tracer: sampling defaults off (spans time their boundary
# and record nothing else) unless configured by env, the /trace admin API, or code. The BIFROMQ_TRACE_*
# knobs are deliberately read ONCE at import (documented discipline
# since ISSUE 2; runtime reconfig goes through PUT /trace or TRACER
# attributes) — graftcheck R3 carries suppressions for these three.
TRACER = Tracer(
    service=_env.env_str("BIFROMQ_TRACE_SERVICE", "bifromq"),
    sampler=TenantSampler(
        _env.env_opt_float("BIFROMQ_TRACE_SAMPLE") or 0.0),
    slow_ms=_env.env_opt_float("BIFROMQ_TRACE_SLOW_MS"))


span = TRACER.span      # the hot path: no forwarding frame


def inject() -> Optional[bytes]:
    return TRACER.inject()


def extract(blob: bytes) -> Optional[SpanContext]:
    return decode_ctx(blob)


def count(name: str, k: int = 1) -> None:
    TRACER.count(name, k)


def record_finished(name: str, ctx: Optional[SpanContext], *,
                    start_ns: int, end_ns: int, start_hlc: int = 0,
                    tenant: Optional[str] = None,
                    tags: Optional[Dict] = None,
                    stage: Optional[str] = None) -> None:
    TRACER.record_finished(name, ctx, start_ns=start_ns, end_ns=end_ns,
                           start_hlc=start_hlc, tenant=tenant, tags=tags,
                           stage=stage)

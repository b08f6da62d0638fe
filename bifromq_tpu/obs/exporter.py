"""Push telemetry export (ISSUE 3, part 3 — closes PR 2's pull-only gap).

Batched JSON-lines records shipped to a file or HTTP sink by a background
asyncio task:

- **metric snapshots** — the windowed per-tenant SLO state, device gauges,
  process stage histograms and fabric counters, one record per flush tick;
- **spans** — incremental drains of the tracer's slow ring (always) and
  sampled ring (optional), via ``SpanRing.since`` cursors, so every slow
  trace reaches the sink even though /trace stays pull-able.

Discipline mirrors the delivery plane: the queue is **bounded** (overflow
increments ``dropped`` and evicts the oldest — telemetry may lag, memory
may not grow), flush failures retry with the resilience fabric's
``RetryPolicy`` (full-jitter backoff), and a batch that exhausts its
retries is counted dropped rather than wedging the loop.
"""

from __future__ import annotations

import asyncio
import json
import time
from collections import deque
from typing import Callable, Dict, List, Optional
from urllib.parse import urlsplit

from ..resilience.policy import RetryPolicy

EXPORT_RETRY = RetryPolicy(max_attempts=3, base_delay=0.05, max_delay=1.0)

# telemetry record schema generation (ISSUE 5 satellite): bumped when the
# line format changes shape, so federated sinks can route per version
SCHEMA_VERSION = "bifromq-tpu.telemetry/1"

# ---------------------------------------------------------------------------
# OTLP-JSON framing (ISSUE 8 satellite: BIFROMQ_OBS_FORMAT=otlp|jsonl)
#
# The jsonl mode ships our native records; otlp mode re-frames each flush
# batch into OpenTelemetry protocol JSON envelopes — spans into
# resourceSpans, metric snapshots flattened into resourceMetrics gauges,
# anything else into resourceLogs — so a stock OTLP collector ingests the
# exporter's stream without a custom shim. The resource envelope
# (node_id / cluster_id / schema_version) maps onto OTLP resource
# attributes; scripts/otlp_schema.json pins the emitted shape.
# ---------------------------------------------------------------------------

_OTLP_SCOPE = {"name": "bifromq_tpu", "version": SCHEMA_VERSION}
_OTLP_METRIC_CAP = 512      # flattened gauges per metrics record


def _otlp_resource(resource: Optional[Dict]) -> dict:
    from ..trace.span import otlp_attributes
    attrs = {f"bifromq.{k}": v for k, v in (resource or {}).items()}
    attrs.setdefault("service.name", "bifromq_tpu")
    return {"attributes": otlp_attributes(attrs)}


def _flatten_numeric(prefix: str, obj, out: List[tuple]) -> None:
    if len(out) >= _OTLP_METRIC_CAP:
        return
    if isinstance(obj, bool):
        return
    if isinstance(obj, (int, float)):
        out.append((prefix, float(obj)))
    elif isinstance(obj, dict):
        for k, v in obj.items():
            _flatten_numeric(f"{prefix}.{k}" if prefix else str(k), v, out)


def _otlp_metrics(rec: dict, ts: float) -> List[dict]:
    ns = str(int(ts * 1e9))
    leaves: List[tuple] = []
    for k, v in rec.items():
        if k in ("type", "ts", "resource"):
            continue
        _flatten_numeric(k, v, leaves)
    return [{"name": name,
             "gauge": {"dataPoints": [{"asDouble": val,
                                       "timeUnixNano": ns}]}}
            for name, val in leaves]


def otlp_frame(records: List[Dict],
               resource: Optional[Dict]) -> List[str]:
    """Frame one flush batch as OTLP-JSON lines: one resourceSpans
    envelope for the spans, one resourceMetrics for the metric
    snapshots, one resourceLogs for everything else."""
    from ..trace.span import otlp_attributes, otlp_span_from_dict
    res = _otlp_resource(resource)
    spans, metrics, logs = [], [], []
    for rec in records:
        kind = rec.get("type")
        if kind == "span":
            spans.append(otlp_span_from_dict(rec))
        elif kind == "metrics":
            metrics.extend(_otlp_metrics(rec, rec.get("ts", 0.0)))
        else:
            logs.append({
                "timeUnixNano": str(int(rec.get("ts", 0.0) * 1e9)),
                "body": {"stringValue": json.dumps(
                    {k: v for k, v in rec.items() if k != "resource"},
                    default=str)},
                "attributes": otlp_attributes(
                    {"type": kind or "record"}),
            })
    lines = []
    if spans:
        lines.append(json.dumps({"resourceSpans": [{
            "resource": res,
            "scopeSpans": [{"scope": _OTLP_SCOPE, "spans": spans}],
        }]}, default=str))
    if metrics:
        lines.append(json.dumps({"resourceMetrics": [{
            "resource": res,
            "scopeMetrics": [{"scope": _OTLP_SCOPE, "metrics": metrics}],
        }]}, default=str))
    if logs:
        lines.append(json.dumps({"resourceLogs": [{
            "resource": res,
            "scopeLogs": [{"scope": _OTLP_SCOPE, "logRecords": logs}],
        }]}, default=str))
    return lines


class FileSink:
    """Append JSON lines to a local file (fsync-free: the OS page cache is
    durable enough for telemetry)."""

    def __init__(self, path: str) -> None:
        self.path = path

    def _write(self, blob: str) -> None:
        with open(self.path, "a", encoding="utf-8") as f:
            f.write(blob)

    async def ship(self, lines: List[str]) -> None:
        # off-loop: a slow/network filesystem must not stall the broker's
        # event loop (the same loop serving publishes) for the write
        await asyncio.get_running_loop().run_in_executor(
            None, self._write, "\n".join(lines) + "\n")

    def describe(self) -> str:
        return f"file:{self.path}"


class HTTPSink:
    """POST the batch as an ``application/x-ndjson`` body over a raw
    asyncio connection (dependency-free, same discipline as the API
    server's HTTP/1.1 plumbing). Any non-2xx status raises so the
    exporter's retry policy takes over."""

    def __init__(self, url: str, timeout_s: float = 5.0) -> None:
        u = urlsplit(url)
        if u.scheme != "http" or not u.hostname:
            raise ValueError(f"unsupported telemetry sink url {url!r}")
        self.host = u.hostname
        self.port = u.port or 80
        # keep the query string: auth-in-query (?token=...) is the common
        # telemetry-collector pattern
        self.path = (u.path or "/") + (f"?{u.query}" if u.query else "")
        self.timeout_s = timeout_s
        self.url = url

    async def ship(self, lines: List[str]) -> None:
        body = ("\n".join(lines) + "\n").encode()
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection(self.host, self.port), self.timeout_s)
        try:
            writer.write(
                f"POST {self.path} HTTP/1.1\r\nhost: {self.host}\r\n"
                f"content-type: application/x-ndjson\r\n"
                f"content-length: {len(body)}\r\n"
                f"connection: close\r\n\r\n".encode() + body)
            await asyncio.wait_for(writer.drain(), self.timeout_s)
            status_line = await asyncio.wait_for(reader.readline(),
                                                self.timeout_s)
            parts = status_line.split()
            if len(parts) < 2 or not parts[1].startswith(b"2"):
                raise ConnectionError(
                    f"telemetry sink rejected batch: {status_line!r}")
        finally:
            try:
                writer.close()
            except Exception:  # noqa: BLE001
                pass

    def describe(self) -> str:
        return f"http:{self.url}"


class TelemetryExporter:
    def __init__(self, sink, *, interval_s: float = 2.0,
                 queue_cap: int = 2048, batch_max: int = 256,
                 snapshot_fn: Optional[Callable[[], dict]] = None,
                 export_sampled: bool = False,
                 retry: RetryPolicy = EXPORT_RETRY,
                 resource: Optional[Dict] = None,
                 framing: str = "jsonl",
                 clock: Callable[[], float] = time.time) -> None:
        if framing not in ("jsonl", "otlp"):
            raise ValueError(f"unknown telemetry framing {framing!r}")
        self.sink = sink
        # ISSUE 8 satellite: jsonl ships native records; otlp re-frames
        # each flush batch into OTLP-JSON envelopes (see otlp_frame)
        self.framing = framing
        self.interval_s = interval_s
        self.queue_cap = queue_cap
        self.batch_max = batch_max
        self.snapshot_fn = snapshot_fn
        self.export_sampled = export_sampled
        self.retry = retry
        # resource envelope (ISSUE 5 satellite): node/cluster identity +
        # schema version stamped on every record, so a federated sink
        # ingesting many brokers' lines can attribute each one
        self.resource = resource
        self._clock = clock
        self._queue: deque = deque()
        self._task: Optional[asyncio.Task] = None
        self._wake: Optional[asyncio.Event] = None
        # counters surfaced under /metrics "obs"
        self.enqueued = 0
        self.shipped = 0
        self.dropped = 0          # queue overflow + retry-exhausted batches
        self.ship_failures = 0    # individual failed ship attempts
        self.batches = 0
        # incremental ring cursors (slow ring always; main ring optional)
        self._slow_cursor = 0
        self._ring_cursor = 0
        # ISSUE 20: SLO burn/recovery journal cursor — events ship in
        # both framings (otlp re-frames them as resourceLogs)
        self._slo_cursor = -1
        # span ids already enqueued: a slow span lives in BOTH rings (and
        # a slow root's dragged-in children reach the slow ring a tick
        # after the sampled drain saw them) — dedupe so consumers never
        # double-count a span. Bounded FIFO.
        self._seen_ids: set = set()
        self._seen_fifo: deque = deque()
        self.SEEN_CAP = 8192

    # ---------------- producers --------------------------------------------

    def enqueue(self, record: Dict) -> None:
        """Bounded enqueue: past the cap the OLDEST record is evicted (the
        newest telemetry is the one an operator is paging through)."""
        if self.resource is not None:
            record.setdefault("resource", self.resource)
        if len(self._queue) >= self.queue_cap:
            self._queue.popleft()
            self.dropped += 1
        self._queue.append(record)
        self.enqueued += 1

    def _collect(self) -> None:
        """One flush tick's worth of records: a metric snapshot + any new
        spans since the last drain."""
        now = self._clock()
        if self.snapshot_fn is not None:
            try:
                snap = self.snapshot_fn()
            except Exception:  # noqa: BLE001 — telemetry must not raise
                snap = None
            if snap:
                self.enqueue({"type": "metrics", "ts": now, **snap})
        from .. import trace
        self._slow_cursor = self._drain(trace.TRACER.slow_ring,
                                        self._slow_cursor, now)
        if self.export_sampled:
            self._ring_cursor = self._drain(trace.TRACER.ring,
                                            self._ring_cursor, now)
        try:
            from .burnrate import SLO_EVENTS
            evs, self._slo_cursor = SLO_EVENTS.since(self._slo_cursor)
            for e in evs:
                self.enqueue({"type": "slo_event", "ts": now, **e})
        except Exception:  # noqa: BLE001 — telemetry must not raise
            pass

    def _drain(self, ring, cursor: int, now: float) -> int:
        """Incrementally drain one span ring into the queue; returns the
        advanced cursor. The slow ring also holds FAST children dragged
        in by a slow root — ``slow`` is flagged per-span from its own
        duration so consumers alerting on slow==true don't count context
        spans as SLO violations; ``_first_sighting`` dedupes spans that
        live in both rings."""
        from .. import trace
        spans, cursor, missed = ring.since(cursor)
        self.dropped += missed
        slow_ms = trace.TRACER.slow_ms
        for s in spans:
            if not self._first_sighting(s.span_id):
                continue
            self.enqueue({"type": "span", "ts": now,
                          "slow": (slow_ms is not None
                                   and s.duration_ms >= slow_ms),
                          **s.to_dict()})
        return cursor

    def _first_sighting(self, span_id: int) -> bool:
        if span_id in self._seen_ids:
            return False
        self._seen_ids.add(span_id)
        self._seen_fifo.append(span_id)
        if len(self._seen_fifo) > self.SEEN_CAP:
            self._seen_ids.discard(self._seen_fifo.popleft())
        return True

    # ---------------- flush loop -------------------------------------------

    async def _flush_once(self) -> None:
        self._collect()
        while self._queue:
            batch = []
            while self._queue and len(batch) < self.batch_max:
                batch.append(self._queue.popleft())
            if self.framing == "otlp":
                lines = otlp_frame(batch, self.resource)
            else:
                lines = [json.dumps(r, default=str) for r in batch]
            attempt = 0
            try:
                while True:
                    try:
                        await self.sink.ship(lines)
                        self.shipped += len(batch)
                        self.batches += 1
                        break
                    except Exception:  # noqa: BLE001 — sink down: back off
                        self.ship_failures += 1
                        attempt += 1
                        if not self.retry.should_retry(attempt):
                            self.dropped += len(batch)
                            return  # sink is down — try again next tick
                        await asyncio.sleep(self.retry.backoff(attempt))
            except asyncio.CancelledError:
                # cancelled mid-ship (e.g. stop()'s 5s grace expired):
                # the de-queued batch must still be ACCOUNTED — silent
                # loss would break the drop-counter contract
                self.dropped += len(batch)
                raise

    async def _run(self) -> None:
        while True:
            try:
                await asyncio.wait_for(self._wake.wait(), self.interval_s)
            except asyncio.TimeoutError:
                pass
            if self._wake.is_set():     # stop requested: final flush below
                return
            try:
                await self._flush_once()
            except Exception:  # noqa: BLE001 — the loop must survive
                import logging
                logging.getLogger(__name__).exception("telemetry flush")

    def start(self) -> None:
        if self._task is not None:
            return
        self._wake = asyncio.Event()
        self._task = asyncio.get_running_loop().create_task(
            self._run(), name="obs-exporter")

    async def stop(self, final_flush: bool = True) -> None:
        task, self._task = self._task, None
        if task is None:
            return
        self._wake.set()
        try:
            await asyncio.wait_for(task, 5.0)
        except asyncio.TimeoutError:
            task.cancel()
        except asyncio.CancelledError:
            # shutdown itself was cancelled: don't keep flushing into a
            # possibly-dead sink — propagate after killing the loop task
            task.cancel()
            raise
        if final_flush:
            try:
                await self._flush_once()
            except Exception:  # noqa: BLE001
                pass

    def snapshot(self) -> dict:
        return {"sink": self.sink.describe(),
                "framing": self.framing,
                "resource": self.resource,
                "interval_s": self.interval_s,
                "queue_depth": len(self._queue),
                "queue_cap": self.queue_cap,
                "enqueued": self.enqueued,
                "shipped": self.shipped,
                "batches": self.batches,
                "dropped": self.dropped,
                "ship_failures": self.ship_failures}

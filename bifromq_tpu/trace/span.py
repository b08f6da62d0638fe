"""Span + trace-context primitives for the flight recorder.

A trace is a tree of spans sharing one 64-bit ``trace_id``; every span is
stamped with the process HLC (``utils/hlc.py``) at start and end, so spans
from DIFFERENT processes order causally as long as the trace context (which
carries the sender's HLC stamp) rode the wire: the receiver merges the
stamp via ``HLC.update`` before opening its own spans, making every remote
child's ``start_hlc`` strictly greater than its parent's.

``SpanContext`` is the tiny propagation unit held in a contextvar and
serialized into the RPC fabric's request header (25 bytes: trace id, span
id, flags, HLC stamp — see ``codec``/``decode`` below).
"""

from __future__ import annotations

import os
import random
import struct
from dataclasses import dataclass, field
from typing import Dict, Optional

from ..utils.hlc import HLC

_PID = os.getpid()

# 25-byte wire form: u64 trace_id ‖ u64 span_id ‖ u8 flags ‖ u64 hlc
CTX_WIRE = struct.Struct(">QQBQ")
FLAG_SAMPLED = 0x01


def new_id() -> int:
    """Non-zero random 64-bit id (0 is the 'absent' sentinel)."""
    return random.getrandbits(64) | 1


@dataclass
class SpanContext:
    """What propagates: identity + the sampling decision. ``tenant`` rides
    along in-process so child spans inherit attribution; it is NOT sent on
    the wire (the remote side re-derives it from its own payloads)."""

    __slots__ = ("trace_id", "span_id", "sampled", "tenant")

    trace_id: int
    span_id: int
    sampled: bool
    tenant: str

    def encode(self) -> bytes:
        return CTX_WIRE.pack(self.trace_id, self.span_id,
                             FLAG_SAMPLED if self.sampled else 0,
                             HLC.INST.get())


# a remote stamp may only pull the local clock forward by this much: an
# unbounded merge would let ONE hostile/corrupted frame poison the clock
# (and, via re-stamped outgoing contexts, the whole cluster) forever
MAX_CLOCK_DRIFT_MS = 60_000


def decode_ctx(blob: bytes) -> Optional["SpanContext"]:
    """Decode a wire context and MERGE its HLC stamp into the local clock
    (the causal-ordering handshake). Returns None on a short/garbled blob
    — tracing must never fail a request. Stamps further than
    ``MAX_CLOCK_DRIFT_MS`` ahead of local wall time are NOT merged (the
    context still extracts; only causal ordering for that trace degrades)."""
    if len(blob) < CTX_WIRE.size:
        return None
    trace_id, span_id, flags, stamp = CTX_WIRE.unpack_from(blob)
    if trace_id == 0:
        return None
    import time as _time
    if HLC.physical(stamp) <= int(_time.time() * 1000) + MAX_CLOCK_DRIFT_MS:
        HLC.INST.update(stamp)
    return SpanContext(trace_id, span_id, bool(flags & FLAG_SAMPLED), "-")


@dataclass
class Span:
    """One finished timing record (spans are materialized at CLOSE time;
    open spans live only as context managers)."""

    name: str
    trace_id: int
    span_id: int
    parent_id: int
    tenant: str
    service: str
    start_hlc: int
    end_hlc: int
    duration_ms: float
    status: str = "ok"           # ok | error
    tags: Dict[str, object] = field(default_factory=dict)
    # multi-parent causality (ISSUE 5 satellite): a batch-emit span
    # parents under ONE representative caller but links every other
    # sampled caller's (trace_id, span_id) — OpenTelemetry span-link
    # semantics, bounded by the recorder
    links: tuple = ()
    # the same boundary on CLOCK_MONOTONIC (``time.monotonic_ns``): the
    # clock of the window totals, of the load generator and, through one
    # annotated span seen in a kept profiler trace, of the device trace
    start_ns: int = 0
    end_ns: int = 0

    def to_dict(self) -> dict:
        out = {
            "name": self.name,
            "trace_id": f"{self.trace_id:016x}",
            "span_id": f"{self.span_id:016x}",
            "parent_id": (f"{self.parent_id:016x}"
                          if self.parent_id else ""),
            "tenant": self.tenant,
            "service": self.service,
            "pid": _PID,
            "start_hlc": self.start_hlc,
            "end_hlc": self.end_hlc,
            "start_ms": HLC.physical(self.start_hlc),
            "start_ns": self.start_ns,
            "end_ns": self.end_ns,
            "duration_ms": round(self.duration_ms, 4),
            "status": self.status,
            # wire-bytes tag values (ISSUE 12 byte-plane pub path) decode
            # at this cold export boundary so /trace and the exporter
            # stay JSON-clean
            "tags": {k: (v.decode("utf-8", "replace")
                         if isinstance(v, bytes) else v)
                     for k, v in self.tags.items()},
        }
        if self.links:
            out["links"] = [{"trace_id": f"{t:016x}",
                             "span_id": f"{s:016x}"}
                            for t, s in self.links]
        return out


def otlp_attributes(pairs: Dict[str, object]) -> list:
    """Flat key/value dict → OTLP attribute list (typed value union)."""
    out = []
    for k, v in pairs.items():
        if isinstance(v, bool):
            val = {"boolValue": v}
        elif isinstance(v, int):
            val = {"intValue": str(v)}      # OTLP-JSON encodes i64 as str
        elif isinstance(v, float):
            val = {"doubleValue": v}
        else:
            val = {"stringValue": str(v)}
        out.append({"key": str(k), "value": val})
    return out


def otlp_span_from_dict(rec: dict) -> dict:
    """One exporter span record (``Span.to_dict`` + envelope fields) →
    an OTLP-JSON span (ISSUE 8 satellite: ``BIFROMQ_OBS_FORMAT=otlp``).

    Our ids are 64-bit; OTLP trace ids are 128-bit, so the trace id is
    left-padded with zeros (a legal, collision-preserving embedding).
    Timestamps come from the HLC's physical milliseconds."""
    start_ns = int(rec.get("start_ms", 0)) * 1_000_000
    end_ns = start_ns + int(float(rec.get("duration_ms", 0.0)) * 1e6)
    attrs = {"service": rec.get("service", ""),
             "tenant": rec.get("tenant", ""),
             "pid": rec.get("pid", 0),
             "hlc.start": rec.get("start_hlc", 0),
             "hlc.end": rec.get("end_hlc", 0)}
    if "slow" in rec:
        attrs["slow"] = bool(rec["slow"])
    for k, v in (rec.get("tags") or {}).items():
        attrs[f"tag.{k}"] = v
    out = {
        "traceId": rec.get("trace_id", "").rjust(32, "0"),
        "spanId": rec.get("span_id", ""),
        "name": rec.get("name", ""),
        "kind": 1,                          # SPAN_KIND_INTERNAL
        "startTimeUnixNano": str(start_ns),
        "endTimeUnixNano": str(end_ns),
        "attributes": otlp_attributes(attrs),
        "status": {"code": 2 if rec.get("status") == "error" else 1},
    }
    if rec.get("parent_id"):
        out["parentSpanId"] = rec["parent_id"]
    if rec.get("links"):
        out["links"] = [{"traceId": ln["trace_id"].rjust(32, "0"),
                         "spanId": ln["span_id"]}
                        for ln in rec["links"]]
    return out

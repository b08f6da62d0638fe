"""Always-on continuous profiler for the device match path (ISSUE 8
tentpole, part 2).

PR 6's async pipeline left "where do the microseconds go between
dispatch and fetch" answerable only by an offline bench run. This module keeps the answer live, at a cost the pipelined
path cannot feel (<2% — the recording site is a handful of attribute
increments plus one ring store, the ``SpanRing`` discipline: GIL-atomic
enough for telemetry, no locks, no allocation beyond the record):

- **Per-batch stage decomposition.** Every device batch (sync or async)
  records its tokenize / dispatch / ready / fetch / expand seconds
  (ISSUE 11 split the byte-plane prep out of dispatch) plus batch
  geometry (queries vs padded rows) and the kernel that served it.
  These are host-clock stage times; device-side kernel time comes from
  a profiler trace, not from here.
- **Efficiency counters.** Padding waste (pow2 pad rows that walk for
  nothing), in-batch dedup savings and cache-hit bypasses (rows that
  never reached the device), batcher emit occupancy, and degraded
  serves by reason.
- **Compile-event ledger.** Every base install is attributable: what
  triggered it (first_base / threshold / forced / refresh), how long the
  compile ran, the table salt, node count, table bytes, and whether it
  bumped the match-cache generation — so a
  rebuild storm reads as a sequence of causes, not a mystery latency
  cliff.

Records drain into the bounded segment store (``obs.segstore``) via
``since()`` cursors for post-hoc analysis after a TPU session ends.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Callable, Dict, List, Optional

from ..trace.recorder import SpanRing


class BatchRecord:
    """One device batch's profile. Plain slots — built once per batch on
    the serving path, so no dataclass/dict overhead."""

    __slots__ = ("ts", "n_queries", "batch", "kernel", "path",
                 "tokenize_s", "dispatch_s", "ready_s", "fetch_s",
                 "expand_s", "dev_expand_s", "degraded")

    def __init__(self, ts, n_queries, batch, kernel, path, tokenize_s,
                 dispatch_s, ready_s, fetch_s, expand_s, degraded,
                 dev_expand_s=0.0) -> None:
        self.ts = ts
        self.n_queries = n_queries
        self.batch = batch
        self.kernel = kernel
        self.path = path
        self.tokenize_s = tokenize_s
        self.dispatch_s = dispatch_s
        self.ready_s = ready_s
        self.fetch_s = fetch_s
        self.expand_s = expand_s
        # ISSUE 19: the DEVICE expansion stage (fan-out pairing +
        # peer bucketing enqueue) — distinct from expand_s, which is the
        # host's stage-3 leg (escalation + overlay + route assembly;
        # with device expansion on, the residual last hop)
        self.dev_expand_s = dev_expand_s
        self.degraded = degraded

    def to_dict(self) -> dict:
        return {"ts": round(self.ts, 3), "n_queries": self.n_queries,
                "batch": self.batch, "kernel": self.kernel,
                "path": self.path,
                "tokenize_ms": round(self.tokenize_s * 1e3, 4),
                "dispatch_ms": round(self.dispatch_s * 1e3, 4),
                "ready_ms": round(self.ready_s * 1e3, 4),
                "fetch_ms": round(self.fetch_s * 1e3, 4),
                "expand_ms": round(self.expand_s * 1e3, 4),
                "dev_expand_ms": round(self.dev_expand_s * 1e3, 4),
                "degraded": self.degraded}


class CompileLedger:
    """Bounded ledger of base-install events (ISSUE 8: rebuild storms
    must be attributable). Appended from the matcher's install path —
    once per compile, so a deque with a lock-free append is plenty.

    ISSUE 9: the ledger also carries the PATCH stream — every coalesced
    device patch flush records its trigger (``rows`` scatter vs a
    ``node``/``edge`` reshape re-upload), how many mutations it folded,
    rows touched and host→device bytes shipped — so subscription churn
    reads as a sequence of narrow updates next to the (now rare)
    compiles, not as silence."""

    CAP = 256

    def __init__(self, clock: Callable[[], float] = time.time) -> None:
        self._clock = clock
        self._events: deque = deque(maxlen=self.CAP)
        self.total = 0
        self.total_compile_s = 0.0
        self.generation_bumps = 0
        self._patch_events: deque = deque(maxlen=self.CAP)
        self.patch_flushes = 0
        self.patch_mutations = 0
        self.patch_rows = 0
        self.patch_bytes = 0
        self.patch_total_s = 0.0

    def record(self, *, reason: str, duration_s: float, salt,
               n_nodes: int, table_bytes: int,
               generation_bumped: bool, kind: str = "single") -> None:
        self.total += 1
        self.total_compile_s += duration_s
        if generation_bumped:
            self.generation_bumps += 1
        self._events.append({
            "ts": round(self._clock(), 3),
            "reason": reason,
            "compile_s": round(duration_s, 4),
            "salt": salt,
            "n_nodes": n_nodes,
            "table_bytes": table_bytes,
            "generation_bumped": generation_bumped,
            "kind": kind,
        })

    def record_patch(self, *, reason: str, mutations: int, rows: int,
                     bytes_shipped: int, duration_s: float) -> None:
        self.patch_flushes += 1
        self.patch_mutations += mutations
        self.patch_rows += rows
        self.patch_bytes += bytes_shipped
        self.patch_total_s += duration_s
        self._patch_events.append({
            "ts": round(self._clock(), 3),
            "reason": reason,
            "mutations": mutations,
            "rows": rows,
            "bytes": bytes_shipped,
            "apply_ms": round(duration_s * 1e3, 4),
        })

    def events(self, limit: int = 0) -> List[dict]:
        evs = list(self._events)
        return evs[-limit:] if limit > 0 else evs

    def patch_events(self, limit: int = 0) -> List[dict]:
        evs = list(self._patch_events)
        return evs[-limit:] if limit > 0 else evs

    def snapshot(self, limit: int = 16) -> dict:
        return {"total": self.total,
                "total_compile_s": round(self.total_compile_s, 3),
                "generation_bumps": self.generation_bumps,
                "events": self.events(limit),
                "patch": {
                    "flushes": self.patch_flushes,
                    "mutations": self.patch_mutations,
                    "rows": self.patch_rows,
                    "bytes": self.patch_bytes,
                    "total_apply_s": round(self.patch_total_s, 4),
                    "events": self.patch_events(limit),
                }}

    def reset(self) -> None:
        self._events.clear()
        self.total = 0
        self.total_compile_s = 0.0
        self.generation_bumps = 0
        self._patch_events.clear()
        self.patch_flushes = 0
        self.patch_mutations = 0
        self.patch_rows = 0
        self.patch_bytes = 0
        self.patch_total_s = 0.0


def _pctl(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1) + 0.5))
    return sorted_vals[idx]


class ContinuousProfiler:
    RING_CAP = 2048

    def __init__(self, clock: Callable[[], float] = time.time) -> None:
        self._clock = clock
        # the tracer's fixed-slot ring is record-type-agnostic — reuse
        # it (record/spans/since cursor math in ONE place) rather than
        # re-deriving the wrap/missed arithmetic here
        self._ring = SpanRing(self.RING_CAP)
        self.ledger = CompileLedger(clock=clock)
        # counters (monotonic; plain int adds on the hot path)
        self.batches_total = 0
        self.queries_total = 0
        self.padded_rows_total = 0
        self.cache_hits_total = 0
        self.dedup_saved_total = 0
        self.frontend_queries_total = 0
        self.degraded_total: Dict[str, int] = {}
        self.emits_total = 0
        self.emit_calls_total = 0
        self.emit_cap_total = 0
        self.emit_depth_total = 0

    # ---------------- hot-path recording (the <2% budget) ------------------

    def record_batch(self, *, n_queries: int, batch: int, kernel: str,
                     dispatch_s: float, tokenize_s: float = 0.0,
                     ready_s: float = 0.0,
                     fetch_s: float = 0.0, expand_s: float = 0.0,
                     dev_expand_s: float = 0.0,
                     path: str = "async",
                     degraded: Optional[str] = None) -> None:
        self.batches_total += 1
        self.queries_total += n_queries
        self.padded_rows_total += max(0, batch - n_queries)
        if degraded is not None:
            self.degraded_total[degraded] = \
                self.degraded_total.get(degraded, 0) + 1
        self._ring.record(BatchRecord(
            self._clock(), n_queries, batch, kernel, path, tokenize_s,
            dispatch_s, ready_s, fetch_s, expand_s, degraded,
            dev_expand_s=dev_expand_s))

    def record_frontend(self, n_queries: int, hits: int,
                        dedup_saved: int) -> None:
        """Cache-plane bypasses: rows that never reached the device."""
        self.frontend_queries_total += n_queries
        self.cache_hits_total += hits
        self.dedup_saved_total += dedup_saved

    def record_emit(self, batch_size: int, cap: int, depth: int) -> None:
        """Batcher emit occupancy (scheduler side of padding waste: a
        batch far under its adaptive cap pads more downstream) plus the
        queue depth observed at emit (the saturation signal _adapt
        keys on)."""
        self.emits_total += 1
        self.emit_calls_total += batch_size
        self.emit_cap_total += cap
        self.emit_depth_total += depth

    # ---------------- snapshots --------------------------------------------

    def records(self, limit: int = 0) -> List[BatchRecord]:
        out = self._ring.spans()        # oldest first (generic ring)
        return out[-limit:] if limit > 0 else out

    def since(self, cursor: int):
        """Records after write-counter ``cursor`` (oldest first), the new
        cursor, and how many were overwritten unread — the segment
        store's incremental drain (``SpanRing.since``'s contract,
        verbatim, because it IS that implementation)."""
        return self._ring.since(cursor)

    def split_snapshot(self) -> dict:
        """Host-clock stage p50/p99 over the retained ring, plus which
        kernels served. Never touches the device."""
        recs = self.records()
        out: Dict[str, object] = {"window_batches": len(recs)}
        for stage in ("tokenize_s", "dispatch_s", "ready_s", "fetch_s",
                      "expand_s", "dev_expand_s"):
            vals = sorted(getattr(r, stage) for r in recs)
            key = stage[:-2]
            out[f"{key}_ms_p50"] = round(_pctl(vals, 0.50) * 1e3, 4)
            out[f"{key}_ms_p99"] = round(_pctl(vals, 0.99) * 1e3, 4)
        kernels: Dict[str, int] = {}
        for r in recs:
            kernels[r.kernel] = kernels.get(r.kernel, 0) + 1
        out["kernels"] = kernels
        return out

    def snapshot(self, *, brief: bool = False) -> dict:
        walked = self.queries_total
        padded = self.padded_rows_total
        fe = self.frontend_queries_total
        out = {
            "batches": self.batches_total,
            "queries": walked,
            "padding_waste_ratio": round(
                padded / max(1, walked + padded), 4),
            "cache_bypass_rate": round(
                self.cache_hits_total / max(1, fe), 4),
            "dedup_saved": self.dedup_saved_total,
            "degraded": dict(self.degraded_total),
            "split": self.split_snapshot(),
            "compile_ledger": self.ledger.snapshot(
                limit=4 if brief else 16),
        }
        if not brief:
            out["emit"] = {
                "batches": self.emits_total,
                "avg_batch": round(self.emit_calls_total
                                   / max(1, self.emits_total), 2),
                "avg_cap": round(self.emit_cap_total
                                 / max(1, self.emits_total), 2),
                "avg_depth_at_emit": round(self.emit_depth_total
                                           / max(1, self.emits_total),
                                           2),
            }
            out["recent"] = [r.to_dict() for r in self.records(8)]
        return out

    def reset(self) -> None:
        self._ring.clear()
        self.ledger.reset()
        self.batches_total = 0
        self.queries_total = 0
        self.padded_rows_total = 0
        self.cache_hits_total = 0
        self.dedup_saved_total = 0
        self.frontend_queries_total = 0
        self.degraded_total = {}
        self.emits_total = 0
        self.emit_calls_total = 0
        self.emit_cap_total = 0
        self.emit_depth_total = 0

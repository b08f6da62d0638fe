"""End-to-end delivery latency plane (ISSUE 20, tentpole part 1).

Every *delivered* message records its publish→socket-write latency here:
the HLC stamp written at ingest (``mqtt/session`` PUBLISH handling) is
read back at the delivery write and the physical-ms delta lands in a
per-(tenant, qos, path) windowed log2 histogram. Full population — no
sampling — so the distribution can back an SLO; the per-record cost is
a handful of dict probes plus one slice-ring increment (the profiler's
ring discipline, bounded <20µs and test-enforced).

Delivery **paths** attribute where the message came from:

- ``local_fanout`` — same-process fan-out (the default);
- ``remote``       — arrived over a deliverer RPC hop (cross-process
  deltas are meaningful because HLC merges on the ``request3`` header);
- ``inbox_replay`` — persistent-session inbox drain;
- ``retained``     — retained-message replay on SUBSCRIBE;
- ``shared_sub``   — shared-subscription group delivery.

The path rides :data:`DELIVERY_PATH` (a contextvar set by the remote
deliverer entry point and the inbox drain; retained/shared-sub are
decided at the send site itself).

Messages that are *not* delivered — expiries, QoS0 discards to
unwritable channels, oversize drops, receive-maximum drops, shed
publishes, inbox overflow — are counted as **SLO violations** alongside,
keyed by reason, so the burn-rate engine sees the success ratio, not
just the latency of the survivors.

Negative deltas (physical clock skew between the publishing and the
delivering process that HLC's counter bits cannot mask) are clamped to
0 at record time and counted in ``skew_clamped`` instead of silently
polluting the low buckets.

Also here:

- :class:`ShardCompletionBoard` — per-shard dispatch→ready timing rows
  for the mesh step (tentpole part 3): a hung device is *named* with its
  shard index, recent ready-latency history feeds per-shard deadline
  hints while a breaker is half-open.
- degraded-attribution map — the mesh/matcher timeout path marks which
  shard/device is degrading deliveries; ``GET /slo`` surfaces it next
  to the latency distribution it explains.
- write-buffer watermark watch — bounded per-connection time above
  ``SEND_BUFFER_HIGH_WATER`` backing the ``SLOW_CONSUMER`` event.

Layering: like the rest of ``obs`` this module must NOT import
``utils.metrics`` (that module imports ``obs`` at import time).
"""

from __future__ import annotations

import contextvars
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from ..utils.hlc import HLC
from .window import WindowedCounter, WindowedLog2Histogram

# the delivery-path attribution a record site inherits when it does not
# decide the path itself (remote RPC entry + inbox drain set it around
# their deliver calls; plain local fan-out leaves the default)
DELIVERY_PATH: contextvars.ContextVar[str] = contextvars.ContextVar(
    "bifromq_delivery_path", default="local_fanout")

PATHS = ("local_fanout", "remote", "inbox_replay", "retained",
         "shared_sub")

# violation reasons (dict keys in snapshots; bounded by construction)
VIOLATIONS = ("expired", "discard", "oversize", "recv_max", "shed",
              "deliver_error", "inbox_overflow")


class _TenantE2E:
    """One tenant's live e2e state: per-(qos, path) latency histograms
    plus per-reason violation windows."""

    __slots__ = ("hists", "violations", "viol_total", "_mk_hist",
                 "_mk_counter")

    def __init__(self, mk_counter, mk_hist) -> None:
        self.hists: Dict[Tuple[int, str], WindowedLog2Histogram] = {}
        self.violations: Dict[str, WindowedCounter] = {}
        self.viol_total = mk_counter()
        self._mk_hist = mk_hist
        self._mk_counter = mk_counter

    def hist(self, qos: int, path: str) -> WindowedLog2Histogram:
        key = (qos, path)
        h = self.hists.get(key)
        if h is None:
            h = self.hists.setdefault(key, self._mk_hist())
        return h

    def violation(self, reason: str) -> WindowedCounter:
        c = self.violations.get(reason)
        if c is None:
            c = self.violations.setdefault(reason, self._mk_counter())
        return c


class E2EPlane:
    """The windowed publish→deliver registry. Same threading contract as
    ``TenantSLO``: locked registration, GIL-atomic recording."""

    def __init__(self, *, window_s: float = 10.0, n_slices: int = 5,
                 max_tenants: int = 512,
                 clock: Callable[[], float] = time.monotonic,
                 wall_ms: Callable[[], float] = None) -> None:
        self.window_s = float(window_s)
        self.n_slices = int(n_slices)
        self.max_tenants = int(max_tenants)
        self._clock = clock
        # wall-clock ms source for the HLC delta (injectable so tests can
        # pin both ends of the subtraction)
        self._wall_ms = wall_ms or (lambda: time.time() * 1000.0)
        self._tenants: Dict[str, _TenantE2E] = {}
        self._lock = threading.Lock()
        # satellite: negative publish→deliver deltas clamped at record
        self.skew_clamped = 0
        # degraded attribution: component name -> {"reason", "since"}
        self._degraded: Dict[str, dict] = {}
        # write-buffer watermark watch: conn key -> monotonic ts the
        # buffer went above high water (bounded FIFO like tenants)
        self._over_since: Dict[str, float] = {}
        self.slow_consumer_events = 0

    def _mk_counter(self) -> WindowedCounter:
        return WindowedCounter(self.window_s, self.n_slices, self._clock)

    def _mk_hist(self) -> WindowedLog2Histogram:
        return WindowedLog2Histogram(self.window_s, self.n_slices,
                                     self._clock)

    def _windows(self, tenant: str) -> _TenantE2E:
        w = self._tenants.get(tenant)
        if w is None:
            with self._lock:
                w = self._tenants.get(tenant)
                if w is None:
                    if len(self._tenants) >= self.max_tenants:
                        self._tenants.pop(next(iter(self._tenants)))
                    w = _TenantE2E(self._mk_counter, self._mk_hist)
                    self._tenants[tenant] = w
        return w

    # ---------------- recording (hot path) ---------------------------------

    def record(self, tenant: str, qos: int, path: str,
               publish_hlc: int) -> float:
        """Fold one delivered message; returns the (clamped) latency in
        seconds. Called at the socket-write site for EVERY delivery."""
        delta_ms = self._wall_ms() - HLC.INST.physical(publish_hlc)
        if delta_ms < 0:
            # HLC merging bounds the *logical* order, not the physical
            # skew between hosts — clamp and count instead of polluting
            # the low buckets with wrapped garbage
            self.skew_clamped += 1
            delta_ms = 0.0
        seconds = delta_ms / 1000.0
        self._windows(tenant).hist(qos, path).record(seconds)
        return seconds

    def record_violation(self, tenant: str, qos: int, reason: str) -> None:
        """A message that should have been delivered was not (expiry,
        discard, drop, shed, overflow) — the SLO denominator still grows
        and the burn engine sees the failure."""
        w = self._windows(tenant)
        w.viol_total.add(1.0)
        w.violation(reason).add(1.0)

    # ---------------- degraded attribution (tentpole part 3) ----------------

    def set_degraded(self, name: str, reason: str) -> None:
        """Name a component (``mesh:shard2``, device tag…) currently
        degrading deliveries. Bounded; re-marking refreshes the reason
        but keeps the original ``since``."""
        with self._lock:
            cur = self._degraded.get(name)
            if cur is not None:
                cur["reason"] = reason
                return
            if len(self._degraded) >= 64:
                self._degraded.pop(next(iter(self._degraded)))
            self._degraded[name] = {"reason": reason,
                                    "since": round(time.time(), 3)}

    def clear_degraded(self, name: str) -> None:
        with self._lock:
            self._degraded.pop(name, None)

    def degraded(self) -> Dict[str, dict]:
        with self._lock:
            return {k: dict(v) for k, v in self._degraded.items()}

    # ---------------- write-buffer watermark watch (satellite) --------------

    def note_watermark(self, key: str, above: bool) -> float:
        """Track one connection's continuous time above the send-buffer
        high water mark. Returns the current seconds-above (0.0 once the
        buffer drains below). Cardinality is bounded: only connections
        currently above hold an entry."""
        now = self._clock()
        since = self._over_since.get(key)
        if above:
            if since is None:
                if len(self._over_since) >= 1024:
                    with self._lock:
                        if len(self._over_since) >= 1024:
                            self._over_since.pop(
                                next(iter(self._over_since)))
                self._over_since[key] = now
                return 0.0
            return now - since
        if since is not None:
            self._over_since.pop(key, None)
        return 0.0

    def drop_watermark(self, key: str) -> None:
        """Connection closed — forget its watermark state."""
        self._over_since.pop(key, None)

    def watermark_gauges(self) -> dict:
        now = self._clock()
        over = list(self._over_since.values())
        return {"over_high_water": len(over),
                "max_over_s": round(max((now - s for s in over),
                                        default=0.0), 3),
                "slow_consumer_events": self.slow_consumer_events}

    # ---------------- snapshots --------------------------------------------

    def snapshot_tenant(self, tenant: str) -> dict:
        w = self._tenants.get(tenant)
        if w is None:
            return {}
        paths: Dict[str, dict] = {}
        for (qos, path), h in list(w.hists.items()):
            s = h.snapshot()        # ONE merge per histogram
            if s["count"]:
                paths.setdefault(path, {})[f"qos{qos}"] = s
        violations = {}
        for reason, c in list(w.violations.items()):
            t = c.total()
            if t:
                violations[reason] = t
        out: dict = {}
        if paths:
            out["paths"] = paths
        if violations or w.viol_total.total():
            out["violations"] = violations
            out["violations_total"] = w.viol_total.total()
        return out

    def snapshot(self) -> dict:
        tenants = {}
        for tenant in list(self._tenants):
            s = self.snapshot_tenant(tenant)
            if s:
                tenants[tenant] = s
        return {"window_s": self.window_s,
                "tenants": tenants,
                "skew_clamped": self.skew_clamped,
                "degraded": self.degraded(),
                "write_buffer": self.watermark_gauges()}

    def qos_rollup(self) -> dict:
        """Per-qos p50/p99 + violation totals across every tenant/path,
        in one compact shape."""
        from .window import N_BUCKETS, percentile_ms_from
        merged: Dict[int, List[int]] = {}
        violations = 0.0
        for w in list(self._tenants.values()):
            for (qos, _path), h in list(w.hists.items()):
                b = h.merged()
                acc = merged.setdefault(qos, [0] * N_BUCKETS)
                for i in range(N_BUCKETS):
                    acc[i] += b[i]
            violations += w.viol_total.total()
        out = {}
        for qos, b in sorted(merged.items()):
            out[f"qos{qos}"] = {"count": sum(b),
                                "p50_ms": percentile_ms_from(b, 50),
                                "p99_ms": percentile_ms_from(b, 99)}
        out["violations"] = violations
        out["skew_clamped"] = self.skew_clamped
        return out

    def reset(self) -> None:
        with self._lock:
            self._tenants.clear()
            self._degraded.clear()
            self._over_since.clear()
            self.skew_clamped = 0
            self.slow_consumer_events = 0


class _ShardRow:
    """One shard's recent completion history."""

    __slots__ = ("ready_s", "last_ready_s", "timeouts", "hung",
                 "hung_since", "hung_reason")

    def __init__(self) -> None:
        self.ready_s: List[float] = []
        self.last_ready_s = 0.0
        self.timeouts = 0
        self.hung = False
        self.hung_since: Optional[float] = None
        self.hung_reason = ""


class ShardCompletionBoard:
    """Per-shard dispatch→ready completion attribution for the mesh step
    (ISSUE 20 tentpole part 3; closes the ROADMAP replication/retained
    follow-up (d)).

    The mesh matcher's await leg reports one row per dispatched shard —
    ``note_ready`` when the shard's leaves became ready, ``note_hung``
    when its deadline lapsed — so the ``/mesh`` surface names *which*
    device stalled the collective step instead of a step-wide anonymous
    timeout. Recent ready rows feed :meth:`deadline_hint`: while a shard
    breaker is half-open its canary probes run against a deadline scaled
    to the shard's own recent completion latency, not the global knob.
    """

    HISTORY = 32

    def __init__(self) -> None:
        self._rows: Dict[int, _ShardRow] = {}
        self._lock = threading.Lock()

    def _row(self, shard: int) -> _ShardRow:
        r = self._rows.get(shard)
        if r is None:
            with self._lock:
                r = self._rows.setdefault(shard, _ShardRow())
        return r

    def note_ready(self, shard: int, dt_s: float) -> None:
        r = self._row(shard)
        r.last_ready_s = dt_s
        r.ready_s.append(dt_s)
        if len(r.ready_s) > self.HISTORY:
            del r.ready_s[: len(r.ready_s) - self.HISTORY]
        if r.hung:
            r.hung = False
            r.hung_since = None
            r.hung_reason = ""

    def note_hung(self, shard: int, reason: str = "deadline") -> None:
        r = self._row(shard)
        r.timeouts += 1
        if not r.hung:
            r.hung = True
            r.hung_since = round(time.time(), 3)
        r.hung_reason = reason

    def note_recovered(self, shard: int) -> None:
        r = self._rows.get(shard)
        if r is not None and r.hung:
            r.hung = False
            r.hung_since = None
            r.hung_reason = ""

    def hung_shards(self) -> List[int]:
        return sorted(s for s, r in self._rows.items() if r.hung)

    def deadline_hint(self, shard: int, default_s: Optional[float]
                      ) -> Optional[float]:
        """A per-shard deadline for half-open canary probes: ~4× the
        shard's worst recent ready latency, floored at 50ms, never above
        the configured default. With no history (or no default) the
        default stands — a hint must only ever tighten."""
        r = self._rows.get(shard)
        if r is None or len(r.ready_s) < 4 or default_s is None:
            return default_s
        hint = max(0.05, 4.0 * max(r.ready_s))
        return min(default_s, hint)

    def snapshot(self) -> dict:
        shards = {}
        for s, r in sorted(self._rows.items()):
            row = {"last_ready_ms": round(r.last_ready_s * 1000.0, 3),
                   "timeouts": r.timeouts,
                   "hung": r.hung}
            if r.ready_s:
                row["recent_max_ms"] = round(max(r.ready_s) * 1000.0, 3)
                row["recent_n"] = len(r.ready_s)
            if r.hung:
                row["hung_since"] = r.hung_since
                row["reason"] = r.hung_reason
            shards[str(s)] = row
        return {"shards": shards, "hung": self.hung_shards()}

    def reset(self) -> None:
        with self._lock:
            self._rows.clear()

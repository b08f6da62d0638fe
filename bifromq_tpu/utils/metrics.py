"""Per-tenant metrics (≈ bifromq-metrics ITenantMeter/TenantMeter).

The reference meters every tenant-visible flow through micrometer
(TenantMetric enum: MqttQoS0IngressBytes, MqttPersistentFanOutBytes, …).
Here: a dependency-free registry of per-(tenant, metric) counters and
gauges with a JSON-able snapshot (served by the API server's /metrics).
An event-collector adapter turns the plugin event stream into meters, so
services need no direct metrics coupling.
"""

from __future__ import annotations

import enum
import threading
import time
import weakref
from collections import defaultdict
from typing import Callable, Dict, List, Tuple

from ..obs import OBS
from ..obs import window as _window
from ..plugin.events import Event, EventType, IEventCollector


class LatencyHistogram:
    """Fixed log2-bucketed latency histogram (ISSUE 2): bucket *i* counts
    samples whose microsecond value has bit_length ``i`` (i.e. the
    [2^(i-1), 2^i) range), topping out around 2 minutes. Recording is one
    list-index increment — GIL-atomic, no lock on the hot path; percentile
    extraction returns the bucket's upper edge (conservative). The bucket
    math is shared with the windowed twin (``obs.window``) — one place
    owns the discipline."""

    N_BUCKETS = _window.N_BUCKETS

    def __init__(self) -> None:
        self._buckets: List[int] = [0] * self.N_BUCKETS

    def record(self, seconds: float) -> None:
        self._buckets[_window.bucket_index(seconds)] += 1

    @property
    def count(self) -> int:
        return sum(self._buckets)

    def percentile_ms(self, p: float) -> float:
        """Upper edge (ms) of the bucket containing the p-th percentile."""
        return _window.percentile_ms_from(self._buckets, p)

    def snapshot(self) -> Dict[str, float]:
        return {"count": self.count,
                "p50_ms": self.percentile_ms(50),
                "p99_ms": self.percentile_ms(99)}

    def reset(self) -> None:
        self._buckets = [0] * self.N_BUCKETS


class StageLatencies:
    """Named per-stage histograms for the publish→match→deliver hot path
    (queue_wait / device / rpc / deliver / ingest + ad-hoc stages). Always
    on — recording is cheap enough to run untraced — so ``/metrics`` and
    the benchmark's readers get stage breakdowns without sampling."""

    def __init__(self) -> None:
        self._hists: Dict[str, LatencyHistogram] = {}

    def hist(self, stage: str) -> LatencyHistogram:
        h = self._hists.get(stage)
        if h is None:
            h = self._hists.setdefault(stage, LatencyHistogram())
        return h

    def record(self, stage: str, seconds: float) -> None:
        self.hist(stage).record(seconds)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        return {name: h.snapshot() for name, h in self._hists.items()
                if h.count}

    def reset(self) -> None:
        for h in self._hists.values():
            h.reset()


# the process-global stage-latency registry the hot path reports into
STAGES = StageLatencies()

# ISSUE 10 (graftcheck R5): the registered stage-name set. Stage
# histograms are stringly-typed — a typo'd name at a record site would
# silently open an orphan series nobody dashboards — so every literal
# fed to STAGES.record / Batcher(stage=...) / OBS.record_latency must be
# a stage that the ONE registry of boundary names (``trace/names.py``)
# gives some row, and every registered stage must be emitted somewhere
# (the analyzer checks both directions).
from ..trace.names import KNOWN_STAGES  # noqa: E402,F401


class TenantMetric(enum.Enum):
    CONNECTIONS = "connections"
    CONNECT_COUNT = "connect_count"
    DISCONNECT_COUNT = "disconnect_count"
    KICKED = "kicked"
    PUB_RECEIVED = "pub_received"
    DELIVERED = "delivered"
    DELIVER_ERRORS = "deliver_errors"
    QOS_DROPPED = "qos_dropped"
    SUB_COUNT = "sub_count"
    UNSUB_COUNT = "unsub_count"
    FANOUT_THROTTLED = "fanout_throttled"
    RETAINED = "retained"
    RETAIN_CLEARED = "retain_cleared"
    WILL_DISTED = "will_disted"
    INBOX_OVERFLOW = "inbox_overflow"
    # ISSUE 7: QoS0 publishes shed under device overload (tenant-fair)
    MATCH_SHED = "match_shed_total"


class FabricMetric(enum.Enum):
    """Process-wide (tenant-agnostic) resilience counters: the RPC fabric's
    retry/breaker/fault/degradation observability (ISSUE 1)."""

    RPC_RETRIES = "rpc_retries_total"
    RPC_FAILOVERS = "rpc_failovers_total"
    RPC_DEADLINE_EXPIRED = "rpc_deadline_expired_total"
    BREAKER_OPENED = "breaker_open_total"
    BREAKER_HALF_OPEN = "breaker_half_open_total"
    BREAKER_CLOSED = "breaker_closed_total"
    FAULTS_INJECTED = "faults_injected_total"
    MATCH_DEGRADED = "match_degraded_total"
    LEADER_REDIRECTS = "leader_redirects_total"
    # ISSUE 7: device-fault resilience plane
    DEVICE_TIMEOUT = "device_timeout_total"
    MATCH_SHED = "match_shed_total"
    # a jit warm-up (serving walk, mesh step, patch scatter) raised: the
    # first serve then compiles lazily — or fails the same way
    WARMUP_FAILED = "device_warmup_failed_total"


def warmup_failed(what: str) -> None:
    """Count and log a jit warm-up that raised — call from the ``except``
    block. The first serve of that shape then compiles lazily (or fails
    the same way, visibly); a swallowed warm-up failure used to surface
    only as every batch quietly degrading to the host oracle."""
    import logging
    FABRIC.inc(FabricMetric.WARMUP_FAILED)
    logging.getLogger(__name__).exception("%s warm-up failed", what)


class FabricMetrics:
    """Global counter registry for fabric-level metrics (per-tenant flows
    stay in ``MetricsRegistry``). Thread-safe: breakers/retries fire from
    RPC tasks while compaction threads may report too."""

    def __init__(self) -> None:
        self._counters: Dict[str, int] = defaultdict(int)
        self._lock = threading.Lock()
        # live breaker registries (weakly held: test-scoped ServiceRegistry
        # instances must not pin their breakers forever) — feeds the
        # per-endpoint state gauges in the /metrics "fabric" section
        self._breaker_sets: "weakref.WeakSet" = weakref.WeakSet()

    def register_breakers(self, breaker_registry) -> None:
        """Expose a BreakerRegistry's live per-endpoint state through
        ``breaker_snapshot`` (ISSUE 2 satellite: breaker state next to the
        monotonic retry/failover totals so traces correlate)."""
        self._breaker_sets.add(breaker_registry)

    # WeakSet iteration order is arbitrary: when two registries track the
    # SAME endpoint, keep the operator-conservative (worst) state rather
    # than whichever registry happened to iterate last
    _BREAKER_SEVERITY = {"closed": 0, "half_open": 1, "open": 2}

    def breaker_snapshot(self) -> Dict[str, dict]:
        merged: Dict[str, dict] = {}
        for reg in list(self._breaker_sets):
            try:
                snap = reg.snapshot()
            except Exception:  # noqa: BLE001 — telemetry must not raise
                continue
            for ep, state in snap.items():
                prev = merged.get(ep)
                if prev is None or (
                        self._BREAKER_SEVERITY.get(state.get("state"), 0)
                        > self._BREAKER_SEVERITY.get(prev.get("state"), 0)):
                    merged[ep] = state
        return merged

    def inc(self, metric: FabricMetric, n: int = 1) -> None:
        with self._lock:
            self._counters[metric.value] += n

    def get(self, metric: FabricMetric) -> int:
        return self._counters.get(metric.value, 0)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()


# the process-global instance the resilience fabric reports into
FABRIC = FabricMetrics()


class MatchCacheMetrics:
    """Process-global counters for the match-result cache plane (ISSUE 4):
    hits/misses/evictions/epoch-bumps per scope (``"matcher"`` = the
    per-range TpuMatcher caches, ``"pub"`` = the dist service's frontend
    cache) plus the in-batch dedup tally. Served under ``/metrics``
    ``"match_cache"``. Thread-safe: range matchers may serve from coproc
    appliers while the pub cache runs on the loop."""

    _FIELDS = ("hits", "misses", "evictions", "epoch_bumps")

    def __init__(self) -> None:
        self._scopes: Dict[str, Dict[str, int]] = {}
        self._lock = threading.Lock()
        self.dedup_walked = 0      # unique rows actually dispatched
        self.dedup_saved = 0       # duplicate rows served by fan-out

    def inc(self, scope: str, field: str, n: int = 1) -> None:
        with self._lock:
            s = self._scopes.setdefault(scope, dict.fromkeys(self._FIELDS, 0))
            s[field] += n

    def record_dedup(self, walked: int, saved: int) -> None:
        with self._lock:
            self.dedup_walked += walked
            self.dedup_saved += saved

    def snapshot(self) -> dict:
        with self._lock:
            out = {}
            for scope, s in self._scopes.items():
                lookups = s["hits"] + s["misses"]
                out[scope] = dict(s)
                out[scope]["hit_rate"] = (round(s["hits"] / lookups, 4)
                                          if lookups else 0.0)
            rows = self.dedup_walked + self.dedup_saved
            out["dedup"] = {
                "walked": self.dedup_walked,
                "saved": self.dedup_saved,
                "ratio": round(self.dedup_saved / rows, 4) if rows else 0.0,
            }
        return out

    def reset(self) -> None:
        with self._lock:
            self._scopes.clear()
            self.dedup_walked = 0
            self.dedup_saved = 0


# the process-global instance every TenantMatchCache reports into
MATCH_CACHE = MatchCacheMetrics()


class ReplicationMetrics:
    """Process-global counters for the patch-delta replication fabric
    (ISSUE 12): records emitted/applied, stream anchors (compaction
    re-anchors), bounded resyncs, gaps (consumer fell off the ring /
    epoch moved), reorder-buffer parks and exact invalidations applied.
    Served under ``/metrics`` ``"replication"`` and ``GET
    /replication``. Thread-safe: leaders append from apply streams while
    standbys/pullers run on the loop."""

    # NOTE: not named _FIELDS — graftcheck R5 pins that name to the
    # MATCH_CACHE field registry when parsing this module's AST
    _COUNTERS = ("records", "applied", "invalidations", "anchors",
                 "resyncs", "gaps", "reorders",
                 # ISSUE 18: parity-audit mismatches caught by a standby
                 "parity_divergence_total")

    def __init__(self) -> None:
        self._counts: Dict[str, int] = dict.fromkeys(self._COUNTERS, 0)
        self._lock = threading.Lock()

    def inc(self, field: str, n: int = 1) -> None:
        with self._lock:
            self._counts[field] = self._counts.get(field, 0) + n

    def get(self, field: str) -> int:
        with self._lock:
            return self._counts.get(field, 0)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def reset(self) -> None:
        with self._lock:
            self._counts = dict.fromkeys(self._COUNTERS, 0)


# the process-global instance the replication fabric reports into
REPLICATION = ReplicationMetrics()


class MetricsRegistry:
    def __init__(self) -> None:
        self._counters: Dict[Tuple[str, str], int] = defaultdict(int)
        self._gauges: Dict[Tuple[str, str], Callable[[], float]] = {}
        self._lock = threading.Lock()
        self.started_at = time.time()

    def inc(self, tenant_id: str, metric: TenantMetric, n: int = 1) -> None:
        with self._lock:
            self._counters[(tenant_id, metric.value)] += n

    def gauge(self, tenant_id: str, name: str,
              fn: Callable[[], float]) -> None:
        with self._lock:
            self._gauges[(tenant_id, name)] = fn

    def get(self, tenant_id: str, metric: TenantMetric) -> int:
        return self._counters.get((tenant_id, metric.value), 0)

    def tenant_counters(self, tenant: str) -> Dict[str, float]:
        """One tenant's counters + evaluated gauges (the lean
        ``GET /metrics?tenant=`` scrape and ``/tenants/<id>`` detail)."""
        with self._lock:
            counters = {n: float(v) for (t, n), v in self._counters.items()
                        if t == tenant}
            gauges = {n: fn for (t, n), fn in self._gauges.items()
                      if t == tenant}
        for n, fn in gauges.items():
            try:
                counters[n] = fn()
            except Exception:  # noqa: BLE001
                pass
        return counters

    def snapshot(self, tenant: str = None) -> dict:
        """The registry's part of the /metrics payload: per-tenant
        counters/gauges plus the process fabric/stage sections. With
        ``tenant`` set (ISSUE 3 satellite: ``GET /metrics?tenant=<id>``)
        only that tenant ships. The API server composes the higher-level
        "device"/"obs"/"slo" sections on top — this module stays below
        the obs hub in the layering."""
        if tenant is not None:
            return {"uptime_s": round(time.time() - self.started_at, 1),
                    "tenants": {tenant: self.tenant_counters(tenant)}}
        # copy the raw maps under the lock, assemble OUTSIDE it: gauge
        # callables must never run while holding the lock every metered
        # event's inc() takes — a wedged gauge would otherwise block the
        # publish path behind a telemetry scrape
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
        per_tenant: Dict[str, Dict[str, float]] = defaultdict(dict)
        for (t, name), v in counters.items():
            per_tenant[t][name] = v
        for (t, name), fn in gauges.items():
            try:
                per_tenant[t][name] = fn()
            except Exception:  # noqa: BLE001
                pass
        fabric = FABRIC.snapshot()
        breakers = FABRIC.breaker_snapshot()
        if breakers:
            fabric["breakers"] = breakers
        out = {"uptime_s": round(time.time() - self.started_at, 1),
               "tenants": dict(per_tenant),
               "fabric": fabric,
               "stages": STAGES.snapshot(),
               "match_cache": MATCH_CACHE.snapshot(),
               # ISSUE 12: delta-stream emit/apply/resync counters
               "replication": REPLICATION.snapshot()}
        # ISSUE 7: per-tenant shed counters (match_shed_total{tenant}) —
        # only shipped once something actually shed, so the happy-path
        # payload doesn't grow. Lazy import: resilience ← utils.metrics
        # would otherwise close a cycle through obs.exporter.
        from ..resilience.device import SHEDDER
        if SHEDDER.shed_total:
            out["shed"] = SHEDDER.snapshot()
        return out


_EVENT_TO_METRIC = {
    EventType.CLIENT_CONNECTED: TenantMetric.CONNECT_COUNT,
    EventType.CLIENT_DISCONNECTED: TenantMetric.DISCONNECT_COUNT,
    EventType.KICKED: TenantMetric.KICKED,
    EventType.PUB_RECEIVED: TenantMetric.PUB_RECEIVED,
    EventType.DELIVERED: TenantMetric.DELIVERED,
    EventType.DELIVER_ERROR: TenantMetric.DELIVER_ERRORS,
    EventType.QOS0_DROPPED: TenantMetric.QOS_DROPPED,
    EventType.QOS1_DROPPED: TenantMetric.QOS_DROPPED,
    EventType.QOS2_DROPPED: TenantMetric.QOS_DROPPED,
    EventType.SUB_ACKED: TenantMetric.SUB_COUNT,
    EventType.UNSUB_ACKED: TenantMetric.UNSUB_COUNT,
    EventType.PERSISTENT_FANOUT_THROTTLED: TenantMetric.FANOUT_THROTTLED,
    EventType.PERSISTENT_FANOUT_BYTES_THROTTLED:
        TenantMetric.FANOUT_THROTTLED,
    EventType.GROUP_FANOUT_THROTTLED: TenantMetric.FANOUT_THROTTLED,
    EventType.MSG_RETAINED: TenantMetric.RETAINED,
    EventType.RETAIN_MSG_CLEARED: TenantMetric.RETAIN_CLEARED,
    EventType.WILL_DISTED: TenantMetric.WILL_DISTED,
    EventType.OVERFLOWED: TenantMetric.INBOX_OVERFLOW,
    EventType.SHED_QOS0: TenantMetric.MATCH_SHED,
}


# the error-classed subset feeding the windowed RED "E" (ISSUE 3).
# SHED_QOS0 counts as an error on purpose: a shed IS a drop, and charging
# it to the shedded tenant's error rate keeps the noisy flag sticky while
# that tenant is being shed — mild hysteresis, not a bug (ISSUE 7).
_ERROR_METRICS = frozenset({
    TenantMetric.DELIVER_ERRORS,
    TenantMetric.QOS_DROPPED,
    TenantMetric.INBOX_OVERFLOW,
    TenantMetric.MATCH_SHED,
})


class MeteringEventCollector(IEventCollector):
    """Event-collector decorator: meters events (monotonic registry +
    windowed SLO layer), then forwards downstream."""

    def __init__(self, registry: MetricsRegistry,
                 downstream: IEventCollector = None) -> None:
        self.registry = registry
        self.downstream = downstream
        # SLO wiring (ISSUE 3): offender events ride this same collector
        # chain, and exporter snapshots can include the registry counters
        OBS.bind_events(self)
        OBS.bind_registry(registry)

    def report(self, event: Event) -> None:
        metric = _EVENT_TO_METRIC.get(event.type)
        if metric is not None:
            tenant = event.tenant_id or "-"
            self.registry.inc(tenant, metric)
            OBS.record_flow(tenant)
            if metric in _ERROR_METRICS:
                OBS.record_error(tenant)
        if self.downstream is not None:
            self.downstream.report(event)

    # decorator transparency: code that inspects a collecting tail
    # (``broker.events.events`` / ``.of(...)``) keeps working when the
    # metering layer wraps the default CollectingEventCollector
    @property
    def events(self):
        return getattr(self.downstream, "events", [])

    def of(self, etype) -> list:
        of_fn = getattr(self.downstream, "of", None)
        return of_fn(etype) if of_fn is not None else []

"""Tenant SLO observability (ISSUE 3): windowed RED metrics, noisy-neighbor
detection, device-pipeline gauges, and push telemetry export.

The process-global ``OBS`` hub is the single attachment point:

- hot-path sites call ``OBS.record_*`` (one ``enabled`` check when the
  window layer is off — same no-op discipline as the tracer);
- ``MeteringEventCollector`` forwards every metered tenant flow/error;
- the API server serves ``GET /tenants`` (+ per-tenant detail) from the
  detector and folds ``OBS.device.snapshot()`` into ``/metrics``;
- the broker starts/stops the push exporter from env knobs
  (``BIFROMQ_OBS_EXPORT`` file path or ``BIFROMQ_OBS_EXPORT_URL`` HTTP
  sink, ``BIFROMQ_OBS_EXPORT_INTERVAL_S``, ``BIFROMQ_OBS_EXPORT_CAP``,
  ``BIFROMQ_OBS_EXPORT_SAMPLED=1`` to also ship sampled spans).

``BIFROMQ_OBS_WINDOWS=0`` disables the window layer entirely (records
become a single attribute check); the detector then reports nothing.

Env knobs are read ONCE when the hub is constructed at import (the same
discipline as ``trace.TRACER``'s ``BIFROMQ_TRACE_*``); everything is
reconfigurable at runtime through ``PUT /obs`` or the hub's attributes.

Layering: ``utils.metrics`` imports this package (feeding flows/errors
and sharing the log2 bucket math in ``window``); nothing in ``obs`` may
import ``utils.metrics`` — that would close an import cycle.
"""

from __future__ import annotations

import os
import time
import weakref
from typing import Callable, Optional

from ..utils.env import (env_bool as _env_bool, env_float as _env_float,
                         env_str as _env_str)
from .burnrate import BurnRateEngine
from .device import DeviceGauges
from .e2e import E2EPlane, ShardCompletionBoard
from .exporter import FileSink, HTTPSink, TelemetryExporter
from .neighbor import NoisyNeighborDetector
from .profiler import CompileLedger, ContinuousProfiler
from .segstore import SegmentStore
from .slo import TenantSLO
from .window import WindowedCounter, WindowedLog2Histogram


class ObsHub:
    def __init__(self, *, clock: Callable[[], float] = time.monotonic,
                 window_s: Optional[float] = None) -> None:
        self.enabled = _env_bool("BIFROMQ_OBS_WINDOWS", True)
        ws = window_s or _env_float("BIFROMQ_OBS_WINDOW_S", 10.0)
        if ws <= 0:
            # a bad telemetry knob must never crash the publish hot path
            # (TenantSLO would raise on the first record)
            import logging
            logging.getLogger(__name__).error(
                "BIFROMQ_OBS_WINDOW_S=%r invalid; using 10.0", ws)
            ws = 10.0
        self.windows = TenantSLO(window_s=ws, clock=clock)
        self.detector = NoisyNeighborDetector(
            self.windows,
            slow_p99_ms=_env_float("BIFROMQ_OBS_SLO_MS", 1000.0),
            clock=clock)
        self.device = DeviceGauges(clock=clock)
        # ISSUE 20: full-population publish→deliver latency plane +
        # multi-window burn-rate SLO engine riding the same clock
        self.e2e = E2EPlane(window_s=ws, clock=clock)
        self.burnrate = BurnRateEngine(clock=clock)
        # ISSUE 8: always-on continuous profiler (per-batch stage split,
        # padding/dedup/cache efficiency, compile-event ledger) — wall
        # clock, not the hub's monotonic: its records persist across
        # process restarts and must be comparable post-hoc
        self.profiler = ContinuousProfiler()
        # ISSUE 8: bounded segment-file store for post-hoc analysis
        # (armed by start_persistence from env knobs)
        self.store: Optional[SegmentStore] = None
        self._store_refs = 0
        self._store_prof_cursor = 0
        self._store_slow_cursor = 0
        self._store_ledger_cursor = 0
        # ISSUE 18: delta-plane event journal drain (lag transitions,
        # parity audits, autoscaler decisions) into the same store
        self._store_repl_cursor = -1
        # ISSUE 20: SLO burn/recovery journal drain
        self._store_slo_cursor = -1
        self.exporter: Optional[TelemetryExporter] = None
        self._exporter_refs = 0
        self._registry_ref = None       # weakref to a MetricsRegistry
        # throttler-advisory background refresh (ISSUE 4 satellite): when
        # armed, the detector's flag set refreshes on this tick instead of
        # lazily on the connect/publish guard path
        self._advisory_task = None
        self._advisory_refs = 0
        self._advisory_interval = float("inf")
        # extra callbacks run on each advisory tick (ISSUE 5: the cluster
        # view refreshes its gossiped health digest here)
        self._tick_hooks: list = []
        # node identity for federated sinks (ISSUE 5 satellite): stamped
        # into every exporter record's resource envelope; the starter
        # overrides from the cluster config
        self.node_id = _env_str("BIFROMQ_NODE_ID") or f"pid-{os.getpid()}"
        self.cluster_id = _env_str("BIFROMQ_CLUSTER_ID")

    # ---------------- hot-path recording -----------------------------------

    def record_flow(self, tenant: str, n: float = 1.0) -> None:
        if self.enabled:
            self.windows.record_flow(tenant, n)

    def record_error(self, tenant: str, n: float = 1.0) -> None:
        if self.enabled:
            self.windows.record_error(tenant, n)

    def record_fanout(self, tenant: str, n: float) -> None:
        if self.enabled:
            self.windows.record_fanout(tenant, n)

    def record_queue_wait(self, tenant: str, seconds: float) -> None:
        if self.enabled:
            self.windows.record_queue_wait(tenant, seconds)

    def record_latency(self, tenant: str, stage: str,
                       seconds: float) -> None:
        if self.enabled:
            self.windows.record_latency(tenant, stage, seconds)

    def record_match_cache(self, tenant: str, hits: int,
                           misses: int) -> None:
        """Match-result cache lookups (ISSUE 4): feeds the per-tenant hit
        rate in ``GET /tenants``."""
        if self.enabled and (hits or misses):
            self.windows.record_match_cache(tenant, hits, misses)

    def record_delivery(self, tenant: str, qos: int, path: str,
                        publish_hlc: int) -> None:
        """ISSUE 20: one delivered message's publish-HLC→socket-write
        latency — full population, every delivery site calls this."""
        if self.enabled:
            seconds = self.e2e.record(tenant, qos, path, publish_hlc)
            # a retained replay's "latency" is the retained message's AGE
            # (publish may predate the SUBSCRIBE by hours) — it counts
            # toward delivery success but never as a latency-target miss
            self.burnrate.observe(
                tenant, 0.0 if path == "retained" else seconds)

    def record_delivery_violation(self, tenant: str, qos: int,
                                  reason: str) -> None:
        """ISSUE 20: a delivery that failed (expiry/discard/drop/shed/
        overflow) — counted against the tenant's SLO budget."""
        if self.enabled:
            self.e2e.record_violation(tenant, qos, reason)
            self.burnrate.observe_violation(tenant)

    # ---------------- wiring ------------------------------------------------

    def bind_events(self, collector) -> None:
        """Give the detector an event outlet (NOISY_TENANT/SLOW_TENANT)
        and the burn engine its SLO_BURN/SLO_RECOVERED outlet. Called by
        MeteringEventCollector so offender events ride the same stream
        operators already collect."""
        self.detector.events = collector
        self.burnrate.events = collector

    def register_pub_cache(self, cache) -> None:
        """ISSUE 12: the dist service registers its pub-side match cache
        so the gossip digest can ship the node's hot (tenant, topic) key
        set — a failover target pre-warms against it before taking
        traffic. Weakly held: a torn-down service must not pin its cache."""
        self._pub_cache_ref = weakref.ref(cache)

    def pub_cache(self):
        ref = getattr(self, "_pub_cache_ref", None)
        return ref() if ref is not None else None

    # ---------------- retained & session plane (ISSUE 13) -------------------

    def register_retained_plane(self, plane) -> None:
        """Weakly track a retained scan plane so ``/metrics`` can serve
        a "retained" section (scans/degradations/cache efficiency per
        range replica) without pinning torn-down services."""
        if not hasattr(self, "_retained_planes"):
            self._retained_planes = weakref.WeakSet()
        self._retained_planes.add(plane)

    def register_drain_governor(self, gov) -> None:
        if not hasattr(self, "_drain_governors"):
            self._drain_governors = weakref.WeakSet()
        self._drain_governors.add(gov)

    def drain_pressure(self) -> float:
        """Worst drain-governor occupancy on this node — (active +
        waiting) / capacity; >1.0 means reconnects are queueing. Gossiped
        in the health digest (ISSUE 15 satellite) so a clustered
        reconnect storm sheds toward quieter peers."""
        worst = 0.0
        for g in list(getattr(self, "_drain_governors", ()) or ()):
            try:
                worst = max(worst, g.pressure())
            except Exception:  # noqa: BLE001 — telemetry must not raise
                continue
        return round(worst, 3)

    def retained_snapshot(self) -> dict:
        """The ``/metrics`` "retained" section: every live scan plane's
        serve/degrade/cache counters + every drain governor's admission
        state (best-effort; introspection must never raise)."""
        planes = []
        for p in list(getattr(self, "_retained_planes", ()) or ()):
            try:
                planes.append(p.snapshot())
            except Exception:  # noqa: BLE001
                continue
        drains = []
        for g in list(getattr(self, "_drain_governors", ()) or ()):
            try:
                drains.append(g.snapshot())
            except Exception:  # noqa: BLE001
                continue
        return {"scan_planes": planes, "drain_governors": drains}

    def mesh_snapshot(self) -> list:
        """The ``/metrics`` "mesh" section + the digest ``mesh`` field:
        every live mesh matcher's shard-load rows, skew, map version and
        in-flight migrations (ISSUE 17; introspection must never raise).
        Single-chip matchers (no ``mesh_status``) are skipped."""
        out = []
        for m in self.device.matchers():
            status = getattr(m, "mesh_status", None)
            if status is None:
                continue
            try:
                s = status()
            except Exception:  # noqa: BLE001 — telemetry must not raise
                continue
            if s.get("n_shards", 0) > 1 or s.get("shard_load"):
                out.append(s)
        return out

    def bind_registry(self, registry) -> None:
        """Weakly remember the metrics registry so exporter snapshots can
        include the monotonic per-tenant counters."""
        self._registry_ref = weakref.ref(registry)

    def is_noisy(self, tenant: str) -> bool:
        """Throttler advisory: is this tenant currently flagged noisy?"""
        return self.enabled and self.detector.is_noisy(tenant)

    def is_burning(self, tenant: str) -> bool:
        """Shedder advisory (ISSUE 20): is this tenant's SLO budget
        burning? A set probe — evaluation happens on the advisory tick."""
        return self.enabled and self.burnrate.is_burning(tenant)

    def set_identity(self, node_id: Optional[str] = None,
                     cluster_id: Optional[str] = None) -> None:
        """Pin the node/cluster identity federated sinks attribute by."""
        if node_id:
            self.node_id = node_id
        if cluster_id is not None:
            self.cluster_id = cluster_id

    def resource_envelope(self) -> dict:
        """The per-record attribution envelope (ISSUE 5 satellite)."""
        from .exporter import SCHEMA_VERSION
        return {"node_id": self.node_id,
                "cluster_id": self.cluster_id,
                "schema_version": SCHEMA_VERSION}

    def on_advisory_tick(self, cb: Callable[[], None]) -> None:
        """Run ``cb`` on every advisory tick (after the detector refresh).
        Idempotent per callback."""
        if cb not in self._tick_hooks:
            self._tick_hooks.append(cb)

    def remove_advisory_hook(self, cb: Callable[[], None]) -> None:
        try:
            self._tick_hooks.remove(cb)
        except ValueError:
            pass

    # ---------------- snapshots --------------------------------------------

    def tenants_snapshot(self, top_k: int = 10, emit: bool = True) -> dict:
        rows = (self.detector.evaluate(top_k=top_k, emit=emit)
                if self.enabled else [])
        return {"window_s": self.windows.window_s,
                "enabled": self.enabled,
                "top_k": top_k,
                "tenants": rows}

    def device_snapshot(self, *, memory: bool = True) -> dict:
        return self.device.snapshot(memory=memory)

    def obs_snapshot(self) -> dict:
        out = {"windows_enabled": self.enabled}
        if self.exporter is not None:
            out["exporter"] = self.exporter.snapshot()
        if self.store is not None:
            out["store"] = self.store.snapshot()
        return out

    def profile_snapshot(self, *, brief: bool = False) -> dict:
        """The ``GET /profile`` payload (ISSUE 8): host-clock stage
        split, padding/dedup/cache efficiency, compile ledger, store
        state. Never touches the device."""
        out = self.profiler.snapshot(brief=brief)
        if self.store is not None and not brief:
            out["store"] = self.store.snapshot()
        return out

    def _export_snapshot(self) -> dict:
        """One exporter 'metrics' record: windowed SLO + device + the
        bound registry's monotonic counters (when still alive)."""
        out = {"slo": self.windows.snapshot() if self.enabled else {},
               "device": self.device_snapshot(memory=False)}
        if self.enabled:
            # ISSUE 20: e2e latency distributions + burn-rate state ride
            # every exporter metrics record in both framings
            out["e2e"] = self.e2e.snapshot()
            out["slo_burn"] = self.burnrate.snapshot()
        reg = self._registry_ref() if self._registry_ref else None
        if reg is not None:
            try:
                # the registry snapshot is counters/fabric/stages only
                # (composition of device/obs sections lives in the API
                # server) — the flush loop never runs the jax memory probe
                out["registry"] = reg.snapshot()
            except Exception:  # noqa: BLE001 — telemetry must not raise
                pass
        return out

    # ---------------- exporter lifecycle -----------------------------------

    def exporter_from_env(self) -> Optional[TelemetryExporter]:
        path = _env_str("BIFROMQ_OBS_EXPORT")
        url = _env_str("BIFROMQ_OBS_EXPORT_URL")
        if not path and not url:
            return None
        framing = _env_str("BIFROMQ_OBS_FORMAT", "jsonl").lower()
        if framing not in ("jsonl", "otlp"):
            import logging
            logging.getLogger(__name__).error(
                "BIFROMQ_OBS_FORMAT=%r unknown; using jsonl", framing)
            framing = "jsonl"
        try:
            sink = HTTPSink(url) if url else FileSink(path)
        except ValueError as e:
            # a bad telemetry knob must not abort broker startup
            import logging
            logging.getLogger(__name__).error(
                "telemetry export disabled: %s", e)
            return None
        return TelemetryExporter(
            sink,
            interval_s=_env_float("BIFROMQ_OBS_EXPORT_INTERVAL_S", 2.0),
            queue_cap=int(_env_float("BIFROMQ_OBS_EXPORT_CAP", 2048)),
            export_sampled=_env_bool("BIFROMQ_OBS_EXPORT_SAMPLED", False),
            snapshot_fn=self._export_snapshot,
            resource=self.resource_envelope(),
            framing=framing)

    def start_exporter(self,
                       exporter: Optional[TelemetryExporter] = None) -> bool:
        """Refcounted start (several brokers may share the process-global
        hub in tests): the first caller creates/starts, later callers just
        bump the count. Returns whether a ref was ACQUIRED — a caller
        whose start was a no-op (no sink configured at the time) must not
        release someone else's ref at stop."""
        if self.exporter is None:
            exporter = exporter or self.exporter_from_env()
            if exporter is None:
                return False
            self.exporter = exporter
            self.exporter.start()
        self._exporter_refs += 1
        return True

    async def stop_exporter(self) -> None:
        if self.exporter is None:
            return
        self._exporter_refs -= 1
        if self._exporter_refs <= 0:
            exp, self.exporter = self.exporter, None
            self._exporter_refs = 0
            await exp.stop()

    # ---------------- segment-store persistence (ISSUE 8) ------------------

    def store_from_env(self) -> Optional[SegmentStore]:
        """Build the segment store from env knobs: ``BIFROMQ_OBS_STORE``
        (directory; empty = disabled), ``BIFROMQ_OBS_STORE_SEGMENT_BYTES``
        and ``BIFROMQ_OBS_STORE_SEGMENTS`` (retention)."""
        path = _env_str("BIFROMQ_OBS_STORE")
        if not path:
            return None
        try:
            return SegmentStore(
                path,
                max_segment_bytes=int(_env_float(
                    "BIFROMQ_OBS_STORE_SEGMENT_BYTES", float(1 << 20))),
                max_segments=int(_env_float(
                    "BIFROMQ_OBS_STORE_SEGMENTS", 8.0)))
        except (ValueError, OSError) as e:
            # a bad persistence knob must not abort broker startup
            import logging
            logging.getLogger(__name__).error(
                "telemetry store disabled: %s", e)
            return None

    def start_persistence(self,
                          store: Optional[SegmentStore] = None) -> bool:
        """Refcounted start (same contract as the exporter): the first
        caller creates the store and hooks the flush onto the advisory
        tick; returns whether a ref was acquired."""
        if self.store is None:
            store = store or self.store_from_env()
            if store is None:
                return False
            self.store = store
            self.on_advisory_tick(self.persist_now)
        self._store_refs += 1
        return True

    def stop_persistence(self, final_flush: bool = True) -> None:
        if self.store is None:
            return
        self._store_refs -= 1
        if self._store_refs > 0:
            return
        self._store_refs = 0
        self.remove_advisory_hook(self.persist_now)
        if final_flush:
            try:
                self.persist_now()
            except Exception:  # noqa: BLE001
                pass
        self.store = None

    def persist_now(self) -> int:
        """Flush everything new — profiler batch records, compile-ledger
        events, slow spans — into the segment store as typed records.
        Incremental via cursors (the same ``since`` discipline as the
        push exporter's ring drains); returns records written."""
        store = self.store
        if store is None:
            return 0
        out = []
        recs, self._store_prof_cursor, _ = \
            self.profiler.since(self._store_prof_cursor)
        for r in recs:
            out.append({"type": "profile", **r.to_dict()})
        events = self.profiler.ledger.events()
        n_new = self.profiler.ledger.total - self._store_ledger_cursor
        for e in (events[-min(n_new, len(events)):] if n_new > 0 else []):
            out.append({"type": "compile", **e})
        self._store_ledger_cursor = self.profiler.ledger.total
        from .. import trace
        spans, self._store_slow_cursor, _ = \
            trace.TRACER.slow_ring.since(self._store_slow_cursor)
        for s in spans:
            out.append({"type": "span", **s.to_dict()})
        # ISSUE 18: lag-stale transitions, gaps/resyncs, parity audits
        # and autoscaler decisions — the post-hoc reader reconstructs
        # WHY the delta plane resynced or the mesh scaled
        from .lag import REPL_EVENTS
        evs, self._store_repl_cursor = \
            REPL_EVENTS.since(self._store_repl_cursor)
        for e in evs:
            out.append({"type": "repl_event", **e})
        # ISSUE 20: SLO burn/recovery transitions — the post-hoc reader
        # lines budget burns up against the profile/span records
        from .burnrate import SLO_EVENTS
        sevs, self._store_slo_cursor = \
            SLO_EVENTS.since(self._store_slo_cursor)
        for e in sevs:
            out.append({"type": "slo_event", **e})
        if out:
            # one summary record per flush stamps the aggregate view the
            # post-hoc reader anchors on
            out.append({"type": "profile_summary",
                        "resource": self.resource_envelope(),
                        **self.profiler.snapshot(brief=True)})
        return store.append_many(out)

    # ---------------- throttler-advisory tick (ISSUE 4 satellite) ----------

    def start_advisory_tick(self,
                            interval_s: Optional[float] = None) -> None:
        """Refcounted background flag refresh: arming a
        ``SLOAdvisedResourceThrottler`` on a max-tenant deployment must not
        pay a full detector evaluation on the publish/connect guard path —
        the tick evaluates off-path and ``is_noisy`` becomes a set probe.

        Re-arming with a SHORTER interval restarts the shared task at the
        faster cadence (ISSUE 5: the cluster view's digest refresh must
        honor ``BIFROMQ_CLUSTER_OBS_INTERVAL_S`` even when the broker
        armed the tick first for the throttler advisory)."""
        import asyncio

        self._advisory_refs += 1
        if self._advisory_task is not None:
            if interval_s is not None and interval_s < self._advisory_interval:
                task, self._advisory_task = self._advisory_task, None
                task.cancel()
            else:
                return
        interval = interval_s or self.detector.advisory_ttl_s
        self._advisory_interval = interval
        self.detector.tick_armed = True

        async def loop() -> None:
            while True:
                await asyncio.sleep(interval)
                try:
                    # evaluate even with the window layer disabled: the
                    # decayed (or empty) windows then CLEAR stale noisy
                    # flags instead of freezing them — ObsHub.is_noisy
                    # short-circuits on enabled, but the flag set must
                    # not go stale for a later re-enable
                    self.detector.evaluate(emit=False)
                except Exception:  # noqa: BLE001 — telemetry must not die
                    import logging
                    logging.getLogger(__name__).exception("advisory tick")
                try:
                    # ISSUE 20: burn-rate transitions fire off-path here
                    # (same decay argument: windows must keep clearing)
                    self.burnrate.evaluate()
                except Exception:  # noqa: BLE001 — telemetry must not die
                    import logging
                    logging.getLogger(__name__).exception("burn evaluate")
                for cb in list(self._tick_hooks):
                    try:
                        cb()
                    except Exception:  # noqa: BLE001
                        import logging
                        logging.getLogger(__name__).exception(
                            "advisory tick hook")

        self._advisory_task = asyncio.get_event_loop().create_task(loop())

    async def stop_advisory_tick(self) -> None:
        if self._advisory_task is None:
            return
        self._advisory_refs -= 1
        if self._advisory_refs > 0:
            return
        task, self._advisory_task = self._advisory_task, None
        self._advisory_refs = 0
        self._advisory_interval = float("inf")
        self.detector.tick_armed = False
        task.cancel()
        try:
            await task
        except BaseException:  # noqa: BLE001 — cancellation
            pass

    def reset(self) -> None:
        """Test isolation: drop all windows/flags/gauges (exporter and
        advisory tick left to their owners)."""
        self.windows.reset()
        self.detector.reset()
        self.device.reset()
        self.profiler.reset()
        self._store_prof_cursor = 0
        self._store_slow_cursor = 0
        self._store_ledger_cursor = 0
        self._store_repl_cursor = -1
        self._store_slo_cursor = -1
        from .lag import LAG, REPL_EVENTS
        LAG.reset()
        REPL_EVENTS.reset()
        self.e2e.reset()
        self.burnrate.reset()
        from .burnrate import SLO_EVENTS
        SLO_EVENTS.reset()


# the process-global hub every instrumentation site reports into
OBS = ObsHub()

from .campaign import CampaignMonitor  # noqa: E402 — needs OBS defined

__all__ = [
    "OBS", "ObsHub", "TenantSLO", "NoisyNeighborDetector", "DeviceGauges",
    "TelemetryExporter", "FileSink", "HTTPSink", "WindowedCounter",
    "WindowedLog2Histogram", "ContinuousProfiler", "CompileLedger",
    "SegmentStore", "CampaignMonitor", "E2EPlane", "BurnRateEngine",
    "ShardCompletionBoard",
]

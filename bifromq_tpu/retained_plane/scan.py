"""Device-side retained wildcard scans on SUBSCRIBE, served through the
shared resilience machinery (ISSUE 13 tentpole part 2).

``RetainedScanPlane`` wraps one replica's :class:`RetainedIndex` with
the same serving discipline the forward matcher earned over PRs 6–11:

- the extras-aware walk dispatches through a bounded
  :class:`~bifromq_tpu.models.pipeline.DispatchRing` (scan N+1 preps
  while scan N walks; ring gauges feed ``queue_pressure``),
- readiness is awaited under the ISSUE 7 watchdog — a hung device
  RECLAIMS the slot (orphaned result arrays quarantined) and degrades
  THIS scan to the exact host oracle (``match_filter_host``),
- a per-plane device circuit breaker (shared board — ``/metrics``
  ``fabric.breakers``, gossip digest demotion) opens on repeated
  timeouts/errors: open means scans skip dispatch entirely; half-open
  admits ONE canary scan that re-closes only on oracle parity,
- results memoize in a filter-keyed :class:`RetainedScanCache` whose
  evictions are EXACT, fed per-mutation by the retained delta hooks,
- every batch lands a ``retain.scan`` span (the serve's call -> rows
  filled) + stage sample, ``retain.scan.queries`` / ``.cache_hits``, and the
  per-tenant latency/fanout feed ``TenantSLO`` (the ISSUE 13 satellite
  bugfix: retained scans used to bypass the RED windows entirely).
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .. import trace
from ..utils.env import env_bool
from ..utils.metrics import STAGES
from .cache import RetainedScanCache

log = logging.getLogger(__name__)


def scan_async_enabled() -> bool:
    """Kill-switch for the async retained scan plane
    (``BIFROMQ_RETAIN_SCAN_ASYNC=0`` serves scans synchronously —
    still cached, still SLO-fed, no ring/watchdog overlap)."""
    return env_bool("BIFROMQ_RETAIN_SCAN_ASYNC", True)


def scan_cache_enabled() -> bool:
    """Kill-switch for the filter-keyed scan result cache
    (``BIFROMQ_RETAIN_SCAN_CACHE=0``)."""
    return env_bool("BIFROMQ_RETAIN_SCAN_CACHE", True)


class RetainedScanPlane:
    """One replica's retained-scan serving plane.

    ``index_fn`` indirects to the live :class:`RetainedIndex` — the
    hosting coproc REPLACES its index on reset-from-KV, and a plane
    pinning the old object would serve a dead world.
    """

    def __init__(self, index_fn: Callable, *, device=None,
                 cache: Optional[RetainedScanCache] = None) -> None:
        self._index_fn = index_fn
        self.device = device
        self._ring = None
        from ..resilience.device import (DEVICE_BREAKERS,
                                         device_breaker_enabled)
        self.device_breaker = (DEVICE_BREAKERS.create()
                               if device_breaker_enabled() else None)
        self.cache = cache if cache is not None else (
            RetainedScanCache() if scan_cache_enabled() else None)
        self.scans_total = 0
        self.degraded_total: Dict[str, int] = {}
        from ..obs import OBS
        OBS.register_retained_plane(self)   # /metrics "retained" section

    @property
    def index(self):
        return self._index_fn()

    def _pipeline_ring(self):
        if self._ring is None:
            from ..models.pipeline import DispatchRing
            self._ring = DispatchRing()
            from ..obs import OBS
            OBS.device.register_ring(self._ring)
        return self._ring

    # ---------------- serving entry points ----------------------------------

    def scan_batch_sync(self, queries: Sequence[Tuple[str, Sequence[str]]],
                        limit: Optional[int] = None) -> List[List[str]]:
        """The non-async leg (no event loop / kill-switch): same cache,
        spans, SLO feeds — minus the ring overlap and the watchdog."""
        return self._serve(queries, limit, self._device_serve_sync)

    async def scan_batch(self, queries: Sequence[Tuple[str, Sequence[str]]],
                         limit: Optional[int] = None) -> List[List[str]]:
        """Pipelined serving path: the device walk dispatches through
        the bounded ring and is awaited on READINESS under the watchdog;
        breaker-open / timeout / device-error serve the exact oracle."""
        if not scan_async_enabled():
            return self.scan_batch_sync(queries, limit)
        out = self._serve(queries, limit, None)
        if isinstance(out, list):
            return out
        miss_queries, fill = out
        rows, reason = await self._device_serve_async(miss_queries, limit)
        return fill(rows, reason)

    def _serve(self, queries, limit, device_leg):
        """Shared front-end: cache probe + span/stage/SLO accounting.
        With ``device_leg`` None (the async caller), returns a
        ``(miss_queries, fill)`` continuation tuple instead of
        blocking (a plain list means the serve completed)."""
        if not queries:
            return []
        t0, t0_ns = time.perf_counter(), time.monotonic_ns()
        ctx = trace.current_ctx()
        self.scans_total += len(queries)
        cache = self.cache
        out: List[Optional[List[str]]] = [None] * len(queries)
        miss_rows: List[int] = []
        tokens: Dict[str, object] = {}
        for qi, (tenant, levels) in enumerate(queries):
            key = tuple(levels)
            hit = cache.get(tenant, key, limit) if cache is not None \
                else None
            if hit is not None:
                out[qi] = list(hit)
            else:
                miss_rows.append(qi)
                if cache is not None and tenant not in tokens:
                    tokens[tenant] = cache.token(tenant)
        miss_queries = [queries[qi] for qi in miss_rows]
        trace.count("retain.scan.queries", len(queries))
        if len(miss_rows) < len(queries):
            trace.count("retain.scan.cache_hits",
                        len(queries) - len(miss_rows))
        front_s = time.perf_counter() - t0
        miss_set = set(miss_rows)

        def fill(rows, reason):
            for qi, row in zip(miss_rows, rows):
                out[qi] = row
                if cache is not None and reason is None:
                    tenant, levels = queries[qi]
                    cache.put(tenant, tuple(levels), limit, row,
                              tokens[tenant])
            dt = time.perf_counter() - t0
            STAGES.record("retain.scan", dt)
            tags = {"n_queries": len(queries), "misses": len(miss_rows),
                    "limit": limit}
            if reason is not None:
                self.degraded_total[reason] = \
                    self.degraded_total.get(reason, 0) + 1
                tags["degraded"] = reason
            trace.record_finished("retain.scan", ctx, start_ns=t0_ns,
                                  end_ns=time.monotonic_ns(), tags=tags)
            # ISSUE 13 satellite bugfix: retained scans feed the tenant
            # RED windows like deliver.fanout does — latency per scanned
            # tenant, achieved retained fan-out into the fanout share.
            # Attribution is per-QUERY cost: a cache hit records the
            # front-probe time, not the batch's device-leg wall (these
            # windows feed the noisy detector, which also gates drain
            # admission — a quiet tenant co-batched with a heavy one
            # must not inherit its latency)
            from ..obs import OBS
            for qi, (tenant, _lv) in enumerate(queries):
                OBS.record_latency(tenant, "retain.scan",
                                   dt if qi in miss_set else front_s)
                OBS.record_fanout(tenant, len(out[qi] or ()))
            return [row if row is not None else [] for row in out]

        if device_leg is None:
            if not miss_queries:
                return fill([], None)
            return miss_queries, fill
        rows, reason = (device_leg(miss_queries, limit)
                        if miss_queries else ([], None))
        return fill(rows, reason)

    # ---------------- device legs -------------------------------------------

    def _oracle_rows(self, queries, limit) -> List[List[str]]:
        idx = self.index
        out = []
        for tenant, levels in queries:
            trie = idx.tries.get(tenant)
            out.append(match_filter_host_safe(trie, levels, limit))
        return out

    def _canary_parity(self, queries, rows, limit) -> Tuple[bool, list]:
        """Half-open success bar: the canary scan's device rows must be
        an exact (limit-aware) subset of the unbounded host oracle — a
        device returning plausible-but-wrong topics after a fault must
        NOT re-close the breaker."""
        full = self._oracle_rows(queries, None)
        ok = True
        for row, want in zip(rows, full):
            wset = set(want)
            bound = len(want) if limit is None else min(limit, len(want))
            if len(row) != bound or not set(row) <= wset:
                ok = False
                break
        if limit is None:
            return ok, full
        return ok, [w[:limit] for w in full]

    def _device_serve_sync(self, queries, limit):
        verdict = self._admit()
        if verdict == "rejected":
            return self._degrade(queries, limit, "breaker")
        try:
            idx = self.index
            prep = idx.prepare_scan(queries)
            prep, res = idx.dispatch_scan(prep)
            return self._settle(queries, limit, idx, prep, res,
                                verdict=verdict)
        except Exception as e:  # noqa: BLE001 — degrade, don't fail
            if self.device_breaker is not None:
                self.device_breaker.record_failure(repr(e))
            return self._degrade(queries, limit, "device_error", e)

    def _admit(self) -> str:
        br = self.device_breaker
        return br.admit() if br is not None else "ok"

    async def _device_serve_async(self, queries, limit):
        from ..resilience.device import DeviceTimeoutError
        verdict = self._admit()
        if verdict == "rejected":
            return self._degrade(queries, limit, "breaker")
        ring = self._pipeline_ring()
        settled = False
        try:
            idx = self.index
            idx.serving_ring = ring     # ring-less flushers must see us
            prep = idx.prepare_scan(queries)
            await ring.acquire()
            try:
                prep, res = idx.dispatch_scan(prep, ring=ring, own_slots=1)
                ring.start_fetch(res)
                try:
                    await ring.wait_ready(res)
                except DeviceTimeoutError:
                    ring.reclaim(res)
                    raise
                except BaseException:
                    # cancelled mid-wait: the arrays may still be in
                    # flight — park them like a timeout does
                    ring.quarantine.add(res)
                    raise
            finally:
                ring.release()
            rows, reason = self._settle(queries, limit, idx, prep, res,
                                        verdict=verdict)
            settled = True
            return rows, reason
        except DeviceTimeoutError as e:
            from ..utils.metrics import FABRIC, FabricMetric
            FABRIC.inc(FabricMetric.DEVICE_TIMEOUT)
            if self.device_breaker is not None:
                self.device_breaker.record_failure(repr(e))
                settled = True
            return self._degrade(queries, limit, "timeout")
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — degrade, don't fail
            if self.device_breaker is not None:
                self.device_breaker.record_failure(repr(e))
                settled = True
            return self._degrade(queries, limit, "device_error", e)
        finally:
            if self.device_breaker is not None and verdict == "canary" \
                    and not settled:
                # cancelled mid-probe with no verdict: the half-open
                # budget must not leak or the breaker wedges refusing
                self.device_breaker.release_probe()

    def _settle(self, queries, limit, idx, prep, res, *, verdict):
        """Fetch + expand, then the breaker bookkeeping (canary scans
        re-close only on oracle parity)."""
        if verdict == "rejected":
            return self._degrade(queries, limit, "breaker")
        rows = idx.expand_scan(prep, idx.fetch_scan(res), limit=limit)
        br = self.device_breaker
        if br is not None:
            if verdict == "canary":
                ok, oracle_rows = self._canary_parity(queries, rows, limit)
                if not ok:
                    br.record_failure("canary row parity")
                    return self._degrade(queries, limit, "canary_parity",
                                         rows_override=oracle_rows)
                br.record_success()
            elif br.state == "closed":
                # pre-trip straggler guard (same as the forward matcher)
                br.record_success()
        return rows, None

    def _degrade(self, queries, limit, reason, exc=None,
                 rows_override=None):
        if exc is not None:
            log.warning("retained scan failed; serving host oracle: %r",
                        exc)
        from ..utils.metrics import FABRIC, FabricMetric
        FABRIC.inc(FabricMetric.MATCH_DEGRADED, len(queries))
        trace.count("retain.rows.oracle", len(queries))
        rows = (rows_override if rows_override is not None
                else self._oracle_rows(queries, limit))
        return rows, reason

    def snapshot(self) -> dict:
        out = {"scans_total": self.scans_total,
               "degraded": dict(self.degraded_total)}
        if self.cache is not None:
            out["cache"] = self.cache.snapshot()
        if self.device_breaker is not None:
            out["breaker"] = self.device_breaker.state
        return out


def match_filter_host_safe(trie, levels, limit) -> List[str]:
    from ..models.retained import match_filter_host
    if trie is None:
        return []
    return match_filter_host(trie, list(levels), limit=limit)

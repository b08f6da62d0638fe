"""Device-pipeline gauges (ISSUE 3, part 4).

As the match hot path moves onto the accelerator, the broker's visibility
has to follow it below the Python line: XLA recompiles (each one stalls
serving for seconds), the dispatch queue in front of the device (the
batcher's backlog is the first thing to grow when the device slows), and
device memory watermarks. Producers register weakly — a test-scoped
matcher or scheduler must not be pinned by telemetry — and the snapshot
is assembled on demand for ``/metrics`` ``"device"``.

jax is only touched inside a guarded, TTL-cached probe: the gauges must
stay readable (reporting zeros / unavailability) when the device is
unreachable — that is exactly when an operator is looking at them.
"""

from __future__ import annotations

import time
import weakref
from typing import Callable, Dict, Optional


class DeviceGauges:
    MEM_PROBE_TTL_S = 5.0

    def __init__(self, clock: Callable[[], float] = time.monotonic) -> None:
        self._clock = clock
        self._matchers: "weakref.WeakSet" = weakref.WeakSet()
        self._schedulers: "weakref.WeakSet" = weakref.WeakSet()
        self._rings: "weakref.WeakSet" = weakref.WeakSet()
        self._mem_cache: Optional[dict] = None
        self._mem_at = -1e18
        self._mem_peak_bytes = 0

    def register_matcher(self, matcher) -> None:
        """Track a TpuMatcher's compile count/time (weakly held)."""
        self._matchers.add(matcher)

    def matchers(self) -> list:
        """Live registered matchers (ISSUE 8: the capacity model walks
        their installed bases for byte accounting)."""
        return list(self._matchers)

    def register_scheduler(self, scheduler) -> None:
        """Track a BatchCallScheduler's live queue depth (weakly held)."""
        self._schedulers.add(scheduler)

    def register_ring(self, ring) -> None:
        """Track a DispatchRing's in-flight occupancy (ISSUE 6: the async
        pipeline's half of the dispatch-queue picture — batches PAST the
        batcher queue but not yet fetched; weakly held). The adaptive
        shaping signals themselves live at the sources (Batcher._adapt's
        depth-at-emit, DispatchRing.effective_floor); this surface is
        observability only."""
        self._rings.add(ring)

    @property
    def peak_memory_bytes(self) -> int:
        """High-water device memory (ISSUE 5): last probed peak, readable
        without triggering a fresh jax probe — the gossip digest refreshes
        every second and must never block on the device."""
        return self._mem_peak_bytes

    # ---------------- probes ------------------------------------------------

    def _compile_stats(self) -> Dict[str, float]:
        count = 0
        total_s = 0.0
        for m in list(self._matchers):
            count += getattr(m, "compile_count", 0)
            total_s += getattr(m, "compile_time_s", 0.0)
        return {"compile_count": count,
                "compile_time_s": round(total_s, 3)}

    def _dispatch_stats(self) -> Dict[str, float]:
        depth = inflight = batchers = 0
        cap = 0
        for sched in list(self._schedulers):
            for b in list(getattr(sched, "_batchers", {}).values()):
                batchers += 1
                depth += len(getattr(b, "_queue", ()))
                inflight += getattr(b, "_inflight", 0)
                cap = max(cap, getattr(b, "_cap", 0))
        ring_inflight = ring_waiting = ring_peak = ring_depth = 0
        ring_timeouts = ring_quarantined = 0
        for ring in list(self._rings):
            ring_inflight += getattr(ring, "in_flight", 0)
            ring_waiting += getattr(ring, "waiting", 0)
            ring_peak = max(ring_peak, getattr(ring, "peak_inflight", 0))
            ring_depth = max(ring_depth, getattr(ring, "depth", 0))
            # ISSUE 7: watchdog reclaims + quarantined orphan buffers
            ring_timeouts += getattr(ring, "timeouts_total", 0)
            q = getattr(ring, "quarantine", None)
            if q is not None:
                ring_quarantined += len(q)
        return {"dispatch_queue_depth": depth,
                "batches_in_flight": inflight,
                "batchers": batchers,
                "max_batch_cap": cap,
                # ISSUE 6: device-side pipeline occupancy (the ring holds
                # batches already dispatched to the device, distinct from
                # the batcher queue waiting in front of it)
                "ring_in_flight": ring_inflight,
                "ring_waiting": ring_waiting,
                "ring_peak_in_flight": ring_peak,
                "ring_depth": ring_depth,
                "ring_timeouts_total": ring_timeouts,
                "ring_quarantined": ring_quarantined}

    # ---------------- overload signals (ISSUE 7) ----------------------------

    def queue_pressure(self) -> float:
        """Dispatch-ring pressure for the load shedder: the worst ring's
        (in-flight + parked waiters) / depth. 0 = idle, 1.0 = a full but
        healthy pipeline, > 1 = dispatches parked behind the ring. Pure
        attribute reads — safe on the publish hot path."""
        worst = 0.0
        for ring in list(self._rings):
            depth = getattr(ring, "depth", 0) or 1
            occ = (getattr(ring, "in_flight", 0)
                   + getattr(ring, "waiting", 0)) / depth
            if occ > worst:
                worst = occ
        return worst

    def dispatch_queue_depth(self) -> int:
        """Live batcher backlog (calls enqueued, not yet emitted) summed
        across registered schedulers — the second overload signal, read
        without the memory probe."""
        depth = 0
        for sched in list(self._schedulers):
            for b in list(getattr(sched, "_batchers", {}).values()):
                depth += len(getattr(b, "_queue", ()))
        return depth

    def memory_stats(self) -> dict:
        """Public guarded memory probe (ISSUE 8: the capacity planner's
        HBM-limit source) — TTL-cached, never triggers backend init."""
        return self._memory_stats()

    def _memory_stats(self) -> dict:
        now = self._clock()
        if (self._mem_cache is not None
                and now - self._mem_at < self.MEM_PROBE_TTL_S):
            return self._mem_cache
        out: dict = {"available": False}
        try:
            # NEVER trigger backend init from a telemetry scrape (a
            # process whose matcher lives elsewhere must not claim the
            # chip): only read a backend a registered matcher already
            # created by installing a base.
            import sys
            if "jax" not in sys.modules or not any(
                    getattr(m, "_device_trie", None) is not None
                    for m in list(self._matchers)):
                raise LookupError("no matcher has device tables yet")
            import jax
            devs = jax.local_devices()
            per_dev = []
            for d in devs:
                try:
                    ms = d.memory_stats()
                except Exception:  # noqa: BLE001 — CPU backends lack this
                    ms = None
                if ms:
                    in_use = int(ms.get("bytes_in_use", 0))
                    self._mem_peak_bytes = max(self._mem_peak_bytes,
                                               int(ms.get(
                                                   "peak_bytes_in_use",
                                                   in_use)))
                    per_dev.append({
                        "platform": d.platform,
                        "bytes_in_use": in_use,
                        "peak_bytes_in_use": int(ms.get("peak_bytes_in_use",
                                                        in_use)),
                        "bytes_limit": int(ms.get("bytes_limit", 0)),
                    })
            out = {"available": bool(per_dev),
                   "n_devices": len(devs),
                   "platform": devs[0].platform if devs else "none",
                   "peak_bytes_in_use": self._mem_peak_bytes,
                   "devices": per_dev}
        except Exception as e:  # noqa: BLE001 — device down / jax absent
            out = {"available": False,
                   "error": f"{type(e).__name__}: {e}"[:120]}
        self._mem_cache = out
        self._mem_at = now
        return out

    def snapshot(self, *, memory: bool = True) -> dict:
        """The ``/metrics`` ``"device"`` section. ``memory=False`` skips
        the jax probe (hot scrape loops)."""
        out = {**self._compile_stats(), **self._dispatch_stats()}
        if memory:
            out["memory"] = self._memory_stats()
        return out

    def reset(self) -> None:
        self._matchers = weakref.WeakSet()
        self._schedulers = weakref.WeakSet()
        self._rings = weakref.WeakSet()
        self._mem_cache = None
        self._mem_at = -1e18
        self._mem_peak_bytes = 0

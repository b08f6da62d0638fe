"""Device-fault resilience plane (ISSUE 7 tentpole).

PR 6 moved the serving hot path onto the accelerator; this module makes
the accelerator a survivable dependency instead of a single point of
hang. One failure taxonomy threads through the pipeline, matcher,
worker, scheduler, and obs layers:

- **timeout** — ``DispatchRing.wait_ready`` gains a watchdog deadline
  (``BIFROMQ_DEVICE_DEADLINE_S``, default derived from the live
  dispatch-stage p99 via ``utils.metrics.STAGES``) raising
  :class:`DeviceTimeoutError`; the timed-out slot is reclaimed and its
  orphaned result arrays are parked in a :class:`BufferQuarantine`
  until the device actually finishes with them (donated buffers must
  never be reused mid-flight), while the batch re-routes to the host
  oracle.
- **breaker** — every ``TpuMatcher`` carries a per-device circuit
  breaker (the PR 1 ``resilience/breaker.py`` state machine, fed by
  device timeouts/errors). Open ⇒ matches skip dispatch entirely and
  serve the exact host-oracle degraded path; half-open ⇒ a single
  canary batch probes the device and re-closes only on row-parity
  success. The :class:`DeviceBreakerBoard` joins the breakers to the
  ``/metrics`` ``fabric.breakers`` section and the PR 5 gossip health
  digest so peers demote a device-sick node before routing to it.
- **shed** — when ring pressure (``obs.device.queue_pressure()``) plus
  batcher queue depth exceed a bound, QoS0 publishes shed with
  per-tenant fairness: noisy tenants (PR 3 detector) shed first, and
  only a deeper overload sheds everyone. QoS1 never sheds — it
  backpressures through the bounded :class:`IngestGate` instead of
  queueing without bound.
- **drain** — shutdown/compaction waits bounded for in-flight ring
  slots (``BIFROMQ_DRAIN_TIMEOUT_S``) then gives up cleanly.

Layering: this module may be imported by ``models``/``mqtt``/``dist``;
it must not import ``obs`` or ``utils.metrics`` at module level (the
exporter already imports ``resilience`` — all hub access is lazy, the
same discipline as ``breaker._meter``).
"""

from __future__ import annotations

import asyncio
import threading
import time
import weakref
from collections import deque
from typing import Callable, Deque, Dict, List, Optional

from ..utils.env import env_bool, env_float as _env_float, env_opt_str
from .breaker import CircuitBreaker

#: severity order shared with utils.metrics.FabricMetrics
_SEVERITY = {"closed": 0, "half_open": 1, "open": 2}


class DeviceTimeoutError(Exception):
    """A device dispatch failed to become ready within the watchdog
    deadline: the accelerator is hung or saturated past its budget. Carries the deadline so degraded-path telemetry can
    say how long we waited."""

    def __init__(self, deadline_s: float, detail: str = "") -> None:
        super().__init__(
            f"device not ready within {deadline_s:.3f}s{detail}")
        self.deadline_s = deadline_s


# watchdog bounds: the derived deadline never drops below the floor (a
# cold STAGES histogram or a sub-ms CPU walk must not turn scheduler
# jitter into timeouts) and never exceeds the ceiling (a pathological
# p99 sample must not disarm the watchdog)
DEADLINE_FLOOR_S = 0.25
DEADLINE_CEIL_S = 30.0
DEADLINE_COLD_S = 5.0
#: headroom multiplier over the observed dispatch-stage p99
DEADLINE_P99_FACTOR = 32.0


def _pinned_deadline(env: str) -> Optional[float]:
    """Resolve an explicit deadline pin from ``env``.

    Returns ``(found, value)`` folded into one optional: ``None`` when
    the knob is unset or malformed (callers fall through to their
    derived default), the float otherwise — ``0``/negative disarm
    (``-0.0``... any non-positive), positive pins CLAMP into
    [``DEADLINE_FLOOR_S``, ``DEADLINE_CEIL_S``]. Before ISSUE 16 a
    positive pin passed through unclamped, so ``=0.001`` turned
    scheduler jitter into timeouts and ``=9999`` silently disarmed the
    watchdog; now a nonsensical knob degrades to the nearest sane bound.
    """
    raw = env_opt_str(env)
    if raw is None:
        return None
    try:
        v = float(raw)
    except ValueError:
        return None    # malformed pin ("2s") ⇒ the adaptive derivation,
    if v <= 0:         # same unset-garbage fallback as utils.env helpers
        return float("-inf")   # sentinel: explicit disarm
    return min(DEADLINE_CEIL_S, max(DEADLINE_FLOOR_S, v))


def device_deadline_s() -> Optional[float]:
    """The watchdog deadline for one device batch.

    ``BIFROMQ_DEVICE_DEADLINE_S`` pins it explicitly (``0`` or negative
    disarms the watchdog entirely; positive values clamp to
    [``DEADLINE_FLOOR_S``, ``DEADLINE_CEIL_S``]). Unset, it derives
    from the live dispatch-stage p99 in ``STAGES`` (``device.dispatch``
    + ``device.ready``) with generous headroom, clamped the same way;
    before any sample exists the cold-start default applies. The
    derivation is two ≤64 bucket walks — cheap enough per batch, and it
    tracks the deployment (small batches time out in sub-second, large
    ones get seconds).
    """
    pinned = _pinned_deadline("BIFROMQ_DEVICE_DEADLINE_S")
    if pinned is not None:
        return None if pinned == float("-inf") else pinned
    from ..utils.metrics import STAGES
    p99_ms = 0.0
    n = 0
    for stage in ("device.dispatch", "device.ready"):
        h = STAGES.hist(stage)
        if h.count:
            n += h.count
            p99_ms += h.percentile_ms(99)
    if n == 0:
        return DEADLINE_COLD_S
    derived = (p99_ms / 1000.0) * DEADLINE_P99_FACTOR
    return min(DEADLINE_CEIL_S, max(DEADLINE_FLOOR_S, derived))


def shard_deadline_s() -> Optional[float]:
    """Per-shard watchdog deadline for ISSUE 16 split mesh dispatch.

    When the mesh step splits into per-fault-domain groups, each group
    waits under ITS OWN deadline so a hang is attributed to the
    offending shard instead of timing out the whole step.
    ``BIFROMQ_SHARD_DEADLINE_S`` pins it (same disarm/clamp contract as
    the device knob); unset, it inherits :func:`device_deadline_s` —
    one group is just a smaller device batch.
    """
    pinned = _pinned_deadline("BIFROMQ_SHARD_DEADLINE_S")
    if pinned is not None:
        return None if pinned == float("-inf") else pinned
    return device_deadline_s()


# ---------------------------------------------------------------------------
# quarantine: orphaned in-flight buffers parked until actually ready
# ---------------------------------------------------------------------------

class BufferQuarantine:
    """Holds the result arrays of timed-out dispatches alive until the
    device reports them ready.

    A timed-out slot's arrays may alias DONATED probe buffers the device
    is still writing: dropping the last reference (or handing the pages
    back to the allocator) mid-flight is use-after-free by another name.
    Parking the whole result object here keeps the buffers pinned;
    ``sweep()`` (called on ring release — O(1) when empty) frees entries
    whose leaves all report ready. A hard age cap bounds the worst case
    of a permanently wedged device: after ``max_age_s`` the entry is
    dropped anyway (at that point the backend is being torn down, not
    raced) and ``expired_total`` records the leak-or-free gamble.
    """

    def __init__(self, max_age_s: float = 300.0,
                 clock: Callable[[], float] = time.monotonic) -> None:
        self.max_age_s = max_age_s
        self._clock = clock
        self._entries: List[tuple] = []    # (res, quarantined_at, tag)
        self._lock = threading.Lock()
        self.quarantined_total = 0
        self.released_total = 0
        self.expired_total = 0
        # ISSUE 15: per-tag lifetime counts (the mesh tags reclaimed
        # batches with the implicated shard, e.g. "mesh:shard3")
        self.quarantined_by_tag: Dict[str, int] = {}

    def __len__(self) -> int:
        return len(self._entries)

    def add(self, res, tag: Optional[str] = None) -> None:
        with self._lock:
            self._entries.append((res, self._clock(), tag))
            self.quarantined_total += 1
            if tag:
                self.quarantined_by_tag[tag] = \
                    self.quarantined_by_tag.get(tag, 0) + 1

    @staticmethod
    def _ready(res) -> bool:
        try:
            for leaf in (res.start, res.count, res.overflow):
                is_ready = getattr(leaf, "is_ready", None)
                if is_ready is not None and not is_ready():
                    return False
        except Exception:  # noqa: BLE001 — a deleted/poisoned buffer is
            return True    # no longer in flight; safe to let go
        return True

    def sweep(self) -> int:
        """Drop every entry whose buffers are ready (or too old to keep
        gambling on). Returns how many were released."""
        if not self._entries:
            return 0
        now = self._clock()
        kept: List[tuple] = []
        freed = 0
        with self._lock:
            for res, at, tag in self._entries:
                if self._ready(res):
                    freed += 1
                    self.released_total += 1
                elif now - at >= self.max_age_s:
                    freed += 1
                    self.expired_total += 1
                else:
                    kept.append((res, at, tag))
            self._entries = kept
        return freed

    def snapshot(self) -> dict:
        out = {"held": len(self._entries),
               "quarantined_total": self.quarantined_total,
               "released_total": self.released_total,
               "expired_total": self.expired_total}
        if self.quarantined_by_tag:
            out["by_tag"] = dict(self.quarantined_by_tag)
        return out


# ---------------------------------------------------------------------------
# device circuit breakers (per matcher), joined to /metrics + gossip
# ---------------------------------------------------------------------------

def device_breaker_enabled() -> bool:
    return env_bool("BIFROMQ_DEVICE_BREAKER", True)


class DeviceBreakerBoard:
    """Process-global registry of per-matcher device breakers.

    Shaped like ``BreakerRegistry`` so ``FabricMetrics.breaker_snapshot``
    (the ``/metrics`` ``fabric.breakers`` section) can merge it, and so
    the cluster digest can gossip the worst state. Matchers are weakly
    held (a test-scoped matcher must not be pinned by telemetry);
    labels are stable per matcher lifetime."""

    def __init__(self) -> None:
        self._breakers: "weakref.WeakValueDictionary[str, CircuitBreaker]" \
            = weakref.WeakValueDictionary()
        self._seq = 0
        self._registered = False

    def create(self, *, failure_threshold: Optional[int] = None,
               recovery_time: Optional[float] = None,
               clock: Callable[[], float] = time.monotonic,
               label: Optional[str] = None) -> CircuitBreaker:
        if failure_threshold is None:
            failure_threshold = int(
                _env_float("BIFROMQ_DEVICE_BREAKER_THRESHOLD", 3))
        if recovery_time is None:
            recovery_time = _env_float(
                "BIFROMQ_DEVICE_BREAKER_RECOVERY_S", 5.0)
        br = CircuitBreaker(failure_threshold=max(1, failure_threshold),
                            recovery_time=recovery_time, clock=clock)
        self._seq += 1
        # ISSUE 15: labeled breakers (the mesh's per-shard fault domains)
        # keep the shard id in the board key so /metrics and the gossip
        # digest can report per-shard state, not just the worst
        key = f"device:{self._seq}" + (f":{label}" if label else "")
        self._breakers[key] = br
        if not self._registered:
            # lazy: utils.metrics imports obs which imports the exporter
            # which imports resilience — registering at import would
            # close the cycle
            from ..utils.metrics import FABRIC
            FABRIC.register_breakers(self)
            self._registered = True
        return br

    def snapshot(self) -> Dict[str, dict]:
        """Non-closed device breakers only: closed is the default, and
        the happy-path ``/metrics`` payload must not grow a row per
        matcher ever constructed."""
        return {label: b.snapshot()
                for label, b in list(self._breakers.items())
                if b.state != "closed"}

    def states(self, include_closed: bool = False) -> Dict[str, str]:
        return {label: b.state
                for label, b in list(self._breakers.items())
                if include_closed or b.state != "closed"}

    def worst_state(self) -> str:
        worst = "closed"
        for b in list(self._breakers.values()):
            s = b.state
            if _SEVERITY.get(s, 0) > _SEVERITY.get(worst, 0):
                worst = s
        return worst


# the process-global board every TpuMatcher's breaker registers into
DEVICE_BREAKERS = DeviceBreakerBoard()


# ---------------------------------------------------------------------------
# fair load shedding under device overload
# ---------------------------------------------------------------------------

class LoadShedder:
    """QoS0 shedding keyed on device-pipeline pressure, tenant-fair.

    The overload score combines the dispatch ring's occupancy pressure
    (``obs.device.queue_pressure()``: (in-flight + parked waiters) /
    ring depth, so a merely-full pipelining ring scores 1.0) with the
    batcher backlog normalized by ``BIFROMQ_SHED_QUEUE_DEPTH``. Two
    thresholds give the fairness ladder:

    - score ≥ ``level1`` (``BIFROMQ_SHED_PRESSURE``, default 1.5):
      shed QoS0 publishes of tenants the PR 3 detector flags NOISY —
      the tenants filling the pipeline pay first;
    - score ≥ 2×``level1``: shed every QoS0 publish — at-most-once
      traffic is the only legal loss under saturation.

    QoS1/2 are never shed here; they backpressure through the
    :class:`IngestGate`. The score is TTL-cached (5 ms) so the per-
    publish cost under load is one clock compare, and exactly zero
    publishes shed while the score stays under the bound — the tier-2
    chaos gate asserts the counters stay silent outside injected
    overload."""

    SCORE_TTL_S = 0.005

    def __init__(self, clock: Callable[[], float] = time.monotonic) -> None:
        # knobs resolve lazily at first use, NOT at construction: the
        # process-global SHEDDER is built when this module is first
        # imported, which is typically BEFORE the embedding broker (or a
        # monkeypatching test) has set its BIFROMQ_* env — every sibling
        # knob in this plane (deadline, drain, breaker) is read at use
        # time and these must not silently differ. Tests that assign
        # level1/queue_depth_bound directly stay pinned.
        self.level1: Optional[float] = None
        self.queue_depth_bound: Optional[float] = None
        self._clock = clock
        self._score = 0.0
        self._score_at = -1e18
        self._lock = threading.Lock()
        self._shed: Dict[str, int] = {}
        self.shed_total = 0

    # -- signal ------------------------------------------------------------

    def _resolve_knobs(self) -> None:
        if self.level1 is None:
            self.level1 = _env_float("BIFROMQ_SHED_PRESSURE", 1.5)
        if self.queue_depth_bound is None:
            self.queue_depth_bound = max(
                1.0, _env_float("BIFROMQ_SHED_QUEUE_DEPTH", 4096.0))

    def overload_score(self) -> float:
        now = self._clock()
        if now - self._score_at < self.SCORE_TTL_S:
            return self._score
        self._resolve_knobs()
        from ..obs import OBS
        score = (OBS.device.queue_pressure()
                 + OBS.device.dispatch_queue_depth()
                 / self.queue_depth_bound)
        self._score = score
        self._score_at = now
        return score

    # -- decision ----------------------------------------------------------

    def should_shed(self, tenant: str, qos: int = 0) -> bool:
        if qos != 0:
            return False
        score = self.overload_score()     # always resolves the knobs
        if score < self.level1:
            return False
        if score < 2 * self.level1:
            from ..obs import OBS
            # ISSUE 20 advisory feed: between level1 and 2×level1 only
            # tenants flagged noisy OR already burning their SLO budget
            # shed — a burning tenant's QoS0 loss is already priced into
            # its budget, so the spend lands where the SLO is lost
            if not (OBS.is_noisy(tenant) or OBS.is_burning(tenant)):
                return False
        self._record(tenant)
        return True

    def _record(self, tenant: str) -> None:
        with self._lock:
            self._shed[tenant] = self._shed.get(tenant, 0) + 1
            self.shed_total += 1
        from ..utils.metrics import FABRIC, FabricMetric
        FABRIC.inc(FabricMetric.MATCH_SHED)

    # -- observability ------------------------------------------------------

    def snapshot(self) -> dict:
        """``/metrics`` ``"shed"`` section: ``match_shed_total`` per
        tenant plus the live overload score and thresholds."""
        self._resolve_knobs()
        with self._lock:
            per_tenant = dict(self._shed)
        return {"match_shed_total": per_tenant,
                "shed_total": self.shed_total,
                "level1": self.level1,
                "queue_depth_bound": self.queue_depth_bound}

    def reset(self) -> None:
        with self._lock:
            self._shed.clear()
            self.shed_total = 0
        self._score = 0.0
        self._score_at = -1e18


SHEDDER = LoadShedder()


# ---------------------------------------------------------------------------
# bounded-slot admission: the shared primitive under the dispatch ring
# and the QoS>0 ingest gate
# ---------------------------------------------------------------------------

class BoundedSlots:
    """Loop-agnostic bounded in-flight admission.

    No asyncio primitive is bound at construction: waiters are plain
    futures created on whichever loop runs the caller, so one instance
    can serve sessions and matchers across loops (and tests can drive it
    with hand-built loops). Cancellation hygiene: a parked waiter
    withdraws itself (a cancelled future is ``done()``, so it must be
    REMOVED — a stale entry would overcount ``waiting``); a waiter that
    was already granted a wake but dies before using it passes the wake
    on so the slot isn't lost. ``DispatchRing`` (models/pipeline.py) and
    :class:`IngestGate` both ride this — the admission machinery must
    not fork into subtly divergent copies."""

    def __init__(self, capacity: int) -> None:
        self.capacity = max(1, capacity)
        self._inflight = 0
        self._waiters: Deque[asyncio.Future] = deque()
        self.peak_inflight = 0
        self.waited_total = 0

    @property
    def in_flight(self) -> int:
        return self._inflight

    @property
    def waiting(self) -> int:
        return len(self._waiters)

    async def acquire(self) -> None:
        while self._inflight >= self.capacity:
            fut = asyncio.get_running_loop().create_future()
            self._waiters.append(fut)
            self.waited_total += 1
            try:
                await fut
            except BaseException:
                if fut in self._waiters:
                    self._waiters.remove(fut)
                elif fut.done() and not fut.cancelled():
                    self._wake_one()
                raise
        self._inflight += 1
        self.peak_inflight = max(self.peak_inflight, self._inflight)

    def try_acquire(self) -> bool:
        """Take a slot if one is free, without parking (the caller keeps
        its own line: the dispatch ring's prep tickets)."""
        if self._inflight >= self.capacity:
            return False
        self._inflight += 1
        self.peak_inflight = max(self.peak_inflight, self._inflight)
        return True

    def _wake_one(self) -> None:
        while self._waiters:
            fut = self._waiters.popleft()
            if not fut.done():
                fut.set_result(None)
                break

    def release(self) -> None:
        self._inflight = max(0, self._inflight - 1)
        self._wake_one()


# ---------------------------------------------------------------------------
# bounded QoS>0 ingest (backpressure instead of unbounded queueing)
# ---------------------------------------------------------------------------

class IngestGate(BoundedSlots):
    """Bounded in-flight QoS>0 publish admissions.

    Under device overload the batcher queue must not absorb unbounded
    at-least-once work: sessions acquiring past the bound PARK (their
    read loop stalls, TCP backpressures the publisher) instead of
    enqueueing — the loss-free counterpart of QoS0 shedding."""

    def __init__(self, capacity: Optional[int] = None) -> None:
        # like LoadShedder's knobs, the env capacity resolves at first
        # acquire, not at module import — the global INGEST_GATE exists
        # before the broker (or a test) sets BIFROMQ_QOS1_INFLIGHT
        self._lazy_env = capacity is None
        super().__init__(capacity if capacity is not None else 1)

    def _resolve_env(self) -> None:
        if self._lazy_env:
            self._lazy_env = False
            self.capacity = max(
                1, int(_env_float("BIFROMQ_QOS1_INFLIGHT", 1024.0)))

    async def acquire(self) -> None:
        self._resolve_env()
        await super().acquire()

    def snapshot(self) -> dict:
        self._resolve_env()
        return {"in_flight": self._inflight, "waiting": len(self._waiters),
                "capacity": self.capacity,
                "peak_in_flight": self.peak_inflight,
                "waited_total": self.waited_total}


INGEST_GATE = IngestGate()


def drain_timeout_s() -> float:
    """Bounded wait for in-flight device slots on shutdown/compaction."""
    return _env_float("BIFROMQ_DRAIN_TIMEOUT_S", 2.0)

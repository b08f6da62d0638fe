"""Device-fault resilience plane tests (ISSUE 7): watchdog deadlines with
slot reclaim + donated-buffer quarantine, the per-device circuit breaker
(open serves the host oracle with zero dispatches, half-open canary
re-closes only on oracle row parity), the device-side fault-injector
taxonomy (hang / error / slow / flaky_ready), tenant-fair QoS0 shedding
under overload, the bounded QoS>0 ingest gate, and graceful drain.

Everything is deterministic: device readiness is driven by gated leaves
(the test_pipeline pattern), clocks are injectable, and overload is a
registered fake ring — no wall-clock sleeps beyond bounded waits.
"""

import asyncio

import numpy as np
import pytest

from bifromq_tpu.models.matcher import TpuMatcher
from bifromq_tpu.models.oracle import Route
from bifromq_tpu.models.pipeline import DispatchRing
from bifromq_tpu.resilience.device import (BufferQuarantine,
                                           DeviceTimeoutError, IngestGate,
                                           LoadShedder, device_deadline_s)
from bifromq_tpu.resilience.faults import get_injector
from bifromq_tpu.types import RouteMatcher

pytestmark = [pytest.mark.asyncio, pytest.mark.chaos]


def mk_route(topic_filter: str, receiver: str, incarnation: int = 0):
    return Route(matcher=RouteMatcher.from_topic_filter(topic_filter),
                 broker_id=0, receiver_id=receiver, deliverer_key="d0",
                 incarnation=incarnation)


def mk_matcher(match_cache=False):
    m = TpuMatcher(max_levels=8, k_states=8, auto_compact=False,
                   match_cache=match_cache)
    m.add_route("T", mk_route("a/b", "r1"))
    m.add_route("T", mk_route("a/+", "r2"))
    m.refresh()
    return m


def _ids(res):
    return sorted(r.receiver_id for r in res.normal)


class _Gate:
    def __init__(self) -> None:
        self.open = False


class _GatedLeaf:
    """numpy-backed stand-in for a jax result buffer whose readiness the
    test controls (the device is 'still walking' until the gate opens)."""

    def __init__(self, arr, gate: _Gate) -> None:
        self._arr = np.asarray(arr)
        self._gate = gate
        self.reads = 0

    def is_ready(self) -> bool:
        return self._gate.open

    def copy_to_host_async(self) -> None:
        pass

    def __array__(self, dtype=None):
        self.reads += 1
        assert self._gate.open, \
            "buffer materialized before is_ready — use-after-donate hazard"
        return (self._arr if dtype is None
                else self._arr.astype(dtype, copy=False))


def _gate_matcher(m: TpuMatcher, gate: _Gate):
    from bifromq_tpu.ops.match import RouteIntervals
    real = m._walk_primary

    def gated(probes, ct, *, donate):
        res, kernel = real(probes, ct, donate=donate)
        return RouteIntervals(
            start=_GatedLeaf(res.start, gate),
            count=_GatedLeaf(res.count, gate),
            n_routes=_GatedLeaf(res.n_routes, gate),
            overflow=_GatedLeaf(res.overflow, gate)), kernel

    m._walk_primary = gated


@pytest.fixture(autouse=True)
def _clean_injector():
    get_injector().reset()
    yield
    get_injector().reset()


# ---------------- watchdog: deadline, reclaim, quarantine -------------------


class TestWatchdog:
    async def test_timeout_reclaims_slot_and_serves_oracle(self, monkeypatch):
        monkeypatch.setenv("BIFROMQ_DEVICE_DEADLINE_S", "0.05")
        m = mk_matcher()
        gate = _Gate()
        _gate_matcher(m, gate)
        res = await m.match_batch_async([("T", ["a", "b"])], batch=16)
        # served — exactly, from the host oracle — despite the hung device
        assert _ids(res[0]) == ["r1", "r2"]
        ring = m._ring
        assert ring.timeouts_total == 1
        assert ring.in_flight == 0, "timed-out slot must be reclaimed"
        # the orphaned result arrays are quarantined, NOT dropped: the
        # device may still be writing buffers that alias donated probes
        assert len(ring.quarantine) == 1
        assert m.device_breaker.snapshot()["failures"] == 1

    async def test_quarantined_buffers_released_only_when_ready(
            self, monkeypatch):
        monkeypatch.setenv("BIFROMQ_DEVICE_DEADLINE_S", "0.05")
        m = mk_matcher()
        gate = _Gate()
        _gate_matcher(m, gate)
        await m.match_batch_async([("T", ["a", "b"])], batch=16)
        q = m._ring.quarantine
        assert len(q) == 1
        # still in flight: a sweep must NOT free it
        q.sweep()
        assert len(q) == 1 and q.released_total == 0
        # ...and no host materialization ever touched the buffers
        (res_obj, _at, _tag) = q._entries[0]
        assert res_obj.start.reads == 0
        # the device finally finishes: the next sweep lets go
        gate.open = True
        q.sweep()
        assert len(q) == 0 and q.released_total == 1

    async def test_ring_stays_live_after_timeout(self, monkeypatch):
        """The deadlock shape from the issue: a wedged dispatch must not
        pin a bounded ring slot — later batches still serve (via device
        once the fault clears)."""
        monkeypatch.setenv("BIFROMQ_DEVICE_DEADLINE_S", "0.05")
        m = mk_matcher()
        gate = _Gate()
        _gate_matcher(m, gate)
        m._pipeline_ring().depth = 1        # one slot: wedging it = deadlock
        await m.match_batch_async([("T", ["a", "b"])], batch=16)
        assert m._ring.timeouts_total == 1
        gate.open = True                    # device recovers
        res = await m.match_batch_async([("T", ["a", "c"])], batch=16)
        assert _ids(res[0]) == ["r2"]
        assert m._ring.in_flight == 0

    def test_deadline_env_pin_and_disarm(self, monkeypatch):
        monkeypatch.setenv("BIFROMQ_DEVICE_DEADLINE_S", "1.25")
        assert device_deadline_s() == 1.25
        monkeypatch.setenv("BIFROMQ_DEVICE_DEADLINE_S", "0")
        assert device_deadline_s() is None      # watchdog disarmed
        monkeypatch.setenv("BIFROMQ_DEVICE_DEADLINE_S", "-3")
        assert device_deadline_s() is None

    def test_deadline_env_clamps_nonsense(self, monkeypatch):
        """ISSUE 16 satellite: a nonsensical pin degrades to the nearest
        sane bound instead of weaponizing scheduler jitter (=0.001) or
        silently disarming the watchdog (=9999); malformed values fall
        through to the adaptive derivation."""
        from bifromq_tpu.resilience.device import (DEADLINE_CEIL_S,
                                                   DEADLINE_FLOOR_S,
                                                   shard_deadline_s)
        monkeypatch.setenv("BIFROMQ_DEVICE_DEADLINE_S", "0.001")
        assert device_deadline_s() == DEADLINE_FLOOR_S
        monkeypatch.setenv("BIFROMQ_DEVICE_DEADLINE_S", "9999")
        assert device_deadline_s() == DEADLINE_CEIL_S
        monkeypatch.setenv("BIFROMQ_DEVICE_DEADLINE_S", "2s")
        derived = device_deadline_s()       # malformed ⇒ derived, clamped
        assert derived is not None
        assert DEADLINE_FLOOR_S <= derived <= DEADLINE_CEIL_S
        # the per-shard knob has the same clamp/disarm contract...
        monkeypatch.setenv("BIFROMQ_SHARD_DEADLINE_S", "0.001")
        assert shard_deadline_s() == DEADLINE_FLOOR_S
        monkeypatch.setenv("BIFROMQ_SHARD_DEADLINE_S", "1e9")
        assert shard_deadline_s() == DEADLINE_CEIL_S
        monkeypatch.setenv("BIFROMQ_SHARD_DEADLINE_S", "-1")
        assert shard_deadline_s() is None
        # ...and unset it inherits the device deadline
        monkeypatch.delenv("BIFROMQ_SHARD_DEADLINE_S")
        monkeypatch.setenv("BIFROMQ_DEVICE_DEADLINE_S", "2.5")
        assert shard_deadline_s() == 2.5

    async def test_wait_ready_no_deadline_never_raises(self):
        gate = _Gate()
        leaf = _GatedLeaf(np.zeros(1), gate)

        class R:
            start = count = overflow = leaf
        task = asyncio.ensure_future(
            DispatchRing.wait_ready(R(), poll_s=0.001, deadline_s=None))
        for _ in range(30):
            await asyncio.sleep(0)
        assert not task.done()
        gate.open = True
        await asyncio.wait_for(task, 2)

    @pytest.mark.parametrize("frozen_s, expect_timeout", [
        (10.0, False),   # the host froze past the deadline: not the device's fault
        (0.0, True),     # a live loop and a device that never answers
    ])
    async def test_wait_ready_deadline_runs_on_observed_time(
            self, monkeypatch, frozen_s, expect_timeout):
        """PR 32: a 4 s stall of the serving thread read ``device_timeout``
        6 on the chip. The first poll after a freeze finds the walk
        unfinished (the runtime thaws with the loop); it must get its
        polls, and a hang under a live loop must still time out."""
        from bifromq_tpu.models import pipeline
        now = [100.0]

        class Clock:
            monotonic = staticmethod(lambda: now[0])
        monkeypatch.setattr(pipeline, "time", Clock)
        gate = _Gate()
        polls = [0]

        class Leaf(_GatedLeaf):
            def is_ready(self):
                polls[0] += 1
                if polls[0] == 1:
                    now[0] += frozen_s       # the freeze, between two polls
                elif frozen_s and polls[0] == 3:
                    gate.open = True         # ready two polls after the thaw
                else:
                    now[0] += 0.01
                return gate.open
        leaf = Leaf(np.zeros(1), gate)

        class R:
            start = count = overflow = None

            @staticmethod
            def ready_leaves():
                return [leaf]
        wait = DispatchRing.wait_ready(R(), deadline_s=0.25)
        if expect_timeout:
            with pytest.raises(DeviceTimeoutError):
                await asyncio.wait_for(wait, 5)
            # 0.01 s a poll under a 0.25 s deadline: at the deadline, not
            # four polls in (the cap bounds a GAP, not the deadline)
            assert 20 <= polls[0] <= 30
        else:
            await asyncio.wait_for(wait, 5)
            assert polls[0] == 3


class TestQuarantine:
    def test_expiry_bounds_a_permanently_wedged_device(self):
        t = [0.0]
        q = BufferQuarantine(max_age_s=10.0, clock=lambda: t[0])
        gate = _Gate()
        leaf = _GatedLeaf(np.zeros(1), gate)

        class R:
            start = count = overflow = leaf
        q.add(R())
        t[0] = 5.0
        q.sweep()
        assert len(q) == 1
        t[0] = 11.0
        q.sweep()
        assert len(q) == 0 and q.expired_total == 1

    async def test_cancelled_wait_quarantines_inflight_buffers(
            self, monkeypatch):
        """A task cancelled while parked in ``wait_ready`` must park its
        in-flight (possibly donated-aliasing) result arrays in quarantine
        exactly like a timeout does — dropping the last reference while
        the device may still be writing is the use-after-donate the
        quarantine exists to prevent. No timeout is counted (the device
        did nothing wrong), and the buffers free once actually ready."""
        monkeypatch.setenv("BIFROMQ_DEVICE_DEADLINE_S", "30")
        m = mk_matcher()
        gate = _Gate()
        _gate_matcher(m, gate)
        task = asyncio.ensure_future(
            m.match_batch_async([("T", ["a", "b"])], batch=16))
        for _ in range(60):                 # into the readiness wait
            await asyncio.sleep(0)
        assert m._ring.in_flight == 1
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        assert m._ring.in_flight == 0       # slot released...
        assert len(m._ring.quarantine) == 1  # ...buffers parked, not lost
        assert m._ring.timeouts_total == 0
        gate.open = True                    # device finishes with them
        m._ring.quarantine.sweep()
        assert len(m._ring.quarantine) == 0


# ---------------- device circuit breaker ------------------------------------


class TestDeviceBreaker:
    async def test_consecutive_timeouts_open_breaker_then_skip_dispatch(
            self, monkeypatch):
        monkeypatch.setenv("BIFROMQ_DEVICE_DEADLINE_S", "0.05")
        m = mk_matcher()
        gate = _Gate()
        _gate_matcher(m, gate)
        thr = m.device_breaker.failure_threshold
        for _ in range(thr):
            res = await m.match_batch_async([("T", ["a", "b"])], batch=16)
            assert _ids(res[0]) == ["r1", "r2"]     # every serve exact
        assert m.device_breaker.state == "open"
        d0 = m._ring.dispatched_total
        res = await m.match_batch_async([("T", ["a", "b"])], batch=16)
        assert _ids(res[0]) == ["r1", "r2"]
        assert m._ring.dispatched_total == d0, \
            "open breaker must skip the device entirely"

    async def test_half_open_canary_recloses_on_row_parity(self):
        t = [0.0]
        m = mk_matcher()
        from bifromq_tpu.resilience.breaker import CircuitBreaker
        m.device_breaker = CircuitBreaker(failure_threshold=1,
                                          recovery_time=5.0,
                                          clock=lambda: t[0])
        m.device_breaker.force_open()
        d0 = m._pipeline_ring().dispatched_total
        res = await m.match_batch_async([("T", ["a", "b"])], batch=16)
        assert _ids(res[0]) == ["r1", "r2"]
        assert m._ring.dispatched_total == d0      # open: no dispatch
        t[0] = 6.0                                  # recovery window passed
        res = await m.match_batch_async([("T", ["a", "b"])], batch=16)
        assert _ids(res[0]) == ["r1", "r2"]
        assert m._ring.dispatched_total == d0 + 1   # the canary probe
        assert m.device_breaker.state == "closed"
        # device serving resumed for good
        res = await m.match_batch_async([("T", ["a", "x"])], batch=16)
        assert _ids(res[0]) == ["r2"]
        assert m._ring.dispatched_total == d0 + 2

    async def test_canary_parity_failure_reopens_and_serves_oracle(self):
        t = [0.0]
        m = mk_matcher()
        from bifromq_tpu.resilience.breaker import CircuitBreaker
        m.device_breaker = CircuitBreaker(failure_threshold=1,
                                          recovery_time=5.0,
                                          clock=lambda: t[0])
        m.device_breaker.force_open()
        t[0] = 6.0
        # the recovered 'device' returns plausible-but-WRONG rows
        from bifromq_tpu.models.oracle import MatchedRoutes
        real = m._expand_walk

        def corrupt(fl, overflow, starts_a, counts_a, mpf, mgf):
            rows = real(fl, overflow, starts_a, counts_a, mpf, mgf)
            return [MatchedRoutes() for _ in rows]      # drops every route
        m._expand_walk = corrupt
        res = await m.match_batch_async([("T", ["a", "b"])], batch=16)
        # the caller still gets the EXACT rows (oracle), and the breaker
        # refuses to re-close on a device that lies
        assert _ids(res[0]) == ["r1", "r2"]
        assert m.device_breaker.state == "open"

    def test_sync_path_breaker_open_serves_oracle(self):
        m = mk_matcher()
        m.device_breaker.force_open()
        res = m.match_batch([("T", ["a", "b"])])
        assert _ids(res[0]) == ["r1", "r2"]

    async def test_breaker_joins_fabric_metrics_and_board(self, monkeypatch):
        import gc
        from bifromq_tpu.resilience.device import DEVICE_BREAKERS
        from bifromq_tpu.utils.metrics import FABRIC
        gc.collect()    # flush earlier tests' gated matchers (ref cycles)
        m = mk_matcher()
        assert DEVICE_BREAKERS.worst_state() == "closed"
        m.device_breaker.force_open()
        assert DEVICE_BREAKERS.worst_state() == "open"
        snap = FABRIC.breaker_snapshot()
        assert any(k.startswith("device:") and v["state"] == "open"
                   for k, v in snap.items())
        # a STALE success (admitted before the trip, landing after it)
        # must NOT re-close an OPEN breaker — that would bypass the
        # recovery window and the canary parity bar
        m.device_breaker.record_success()
        assert DEVICE_BREAKERS.worst_state() == "open"
        # the legitimate path: recovery window elapses -> half-open
        # canary admission -> its success closes
        m.device_breaker._opened_at -= (
            m.device_breaker.recovery_time + 1.0)
        assert m.device_breaker.admit() == "canary"
        m.device_breaker.record_success()
        # closed breakers stay OUT of the snapshot (absent means healthy):
        # the happy-path /metrics payload must not grow a row per matcher
        assert not any(k.startswith("device:")
                       for k in DEVICE_BREAKERS.snapshot())


# ---------------- device-side fault injector ---------------------------------


class TestDeviceFaultInjector:
    async def test_error_rule_at_dispatch_degrades_async(self):
        m = mk_matcher()
        get_injector().add_rule(service="tpu-device", method="dispatch",
                                action="error", max_hits=1)
        stats = {}
        res = await m.match_batch_async([("T", ["a", "b"])], stats=stats)
        assert _ids(res[0]) == ["r1", "r2"]
        assert stats["degraded"] == "device_error"
        assert m.device_breaker.snapshot()["failures"] == 1
        # rule exhausted: the device serves again
        stats = {}
        res = await m.match_batch_async([("T", ["a", "x"])], stats=stats)
        assert _ids(res[0]) == ["r2"] and "degraded" not in stats

    def test_error_rule_at_dispatch_propagates_sync(self):
        from bifromq_tpu.resilience.faults import InjectedFault
        m = mk_matcher()
        get_injector().add_rule(service="tpu-device", method="dispatch",
                                action="error", max_hits=1)
        with pytest.raises(InjectedFault):
            m.match_batch([("T", ["a", "b"])])
        # ...but the breaker saw it
        assert m.device_breaker.snapshot()["failures"] == 1

    async def test_error_rule_at_fetch_degrades_async(self):
        m = mk_matcher()
        get_injector().add_rule(service="tpu-device", method="fetch",
                                action="error", max_hits=1)
        stats = {}
        res = await m.match_batch_async([("T", ["a", "b"])], stats=stats)
        assert _ids(res[0]) == ["r1", "r2"]
        assert stats["degraded"] == "device_error"

    async def test_hang_rule_times_out_then_clearing_recovers(
            self, monkeypatch):
        monkeypatch.setenv("BIFROMQ_DEVICE_DEADLINE_S", "0.05")
        m = mk_matcher()
        inj = get_injector()
        inj.add_rule(service="tpu-device", method="dispatch", action="hang")
        stats = {}
        res = await m.match_batch_async([("T", ["a", "b"])], stats=stats)
        assert _ids(res[0]) == ["r1", "r2"]
        assert stats["degraded"] == "timeout"
        assert m._ring.timeouts_total == 1
        inj.reset()     # un-wedge the device
        m._ring.quarantine.sweep()      # buffers were really ready
        assert len(m._ring.quarantine) == 0
        stats = {}
        res = await m.match_batch_async([("T", ["a", "x"])], stats=stats)
        assert _ids(res[0]) == ["r2"] and "degraded" not in stats

    async def test_slow_rule_delays_but_completes(self):
        import time as _time
        m = mk_matcher()
        get_injector().add_rule(service="tpu-device", method="dispatch",
                                action="slow", delay=0.08, max_hits=1)
        t0 = _time.monotonic()
        res = await m.match_batch_async([("T", ["a", "b"])], batch=16)
        assert _ids(res[0]) == ["r1", "r2"]
        assert _time.monotonic() - t0 >= 0.08
        assert m._ring.timeouts_total == 0

    def test_sync_path_does_not_consume_readiness_rules(self):
        """The sync leg's fetch is a blocking synchronize with no
        readiness poll to thread a fault into: a hang/slow/flaky_ready
        rule must stay ARMED (hit budget and injection counters
        untouched) for the watchdogged async path instead of being
        silently consumed with nothing injected."""
        m = mk_matcher()
        inj = get_injector()
        inj.add_rule(service="tpu-device", method="dispatch",
                     action="hang", max_hits=1)
        rule = inj.rules[0]
        res = m.match_batch([("T", ["a", "b"])])
        assert _ids(res[0]) == ["r1", "r2"]      # sync serve unaffected
        assert rule.hits == 0                    # rule still armed
        assert inj.injected_total == 0

    async def test_flaky_ready_rule_completes(self):
        m = mk_matcher()
        get_injector().add_rule(service="tpu-device", method="dispatch",
                                action="flaky_ready", probability=1.0,
                                max_hits=1)
        rule = get_injector().rules[0]
        # probability=1 would lie forever: cap the lying by removing the
        # rule from a side task once the batch is in its readiness wait
        task = asyncio.ensure_future(
            m.match_batch_async([("T", ["a", "b"])], batch=16))
        for _ in range(20):
            await asyncio.sleep(0)
        get_injector().remove_rule(rule)
        res = await asyncio.wait_for(task, 5)
        assert _ids(res[0]) == ["r1", "r2"]


# ---------------- fair load shedding -----------------------------------------


class _FakeRing:
    def __init__(self, in_flight=0, waiting=0, depth=2):
        self.in_flight = in_flight
        self.waiting = waiting
        self.depth = depth
        self.peak_inflight = in_flight
        self.timeouts_total = 0


class TestLoadShedding:
    def _shedder(self, clock):
        s = LoadShedder(clock=clock)
        s.level1 = 1.5
        s.queue_depth_bound = 100.0
        return s

    def test_env_knobs_resolve_at_first_use(self, monkeypatch):
        """Knobs set AFTER construction (the process-global SHEDDER is
        built at module import, before the broker sets BIFROMQ_*) must
        still apply; explicit attribute assignment stays pinned."""
        s = LoadShedder(clock=lambda: 0.0)  # built before the env knobs
        monkeypatch.setenv("BIFROMQ_SHED_PRESSURE", "0.25")
        monkeypatch.setenv("BIFROMQ_SHED_QUEUE_DEPTH", "10")
        snap = s.snapshot()
        assert snap["level1"] == 0.25
        assert snap["queue_depth_bound"] == 10.0

    def _overload(self, monkeypatch, pressure, depth=0):
        from bifromq_tpu.obs import OBS
        monkeypatch.setattr(OBS.device, "queue_pressure", lambda: pressure)
        monkeypatch.setattr(OBS.device, "dispatch_queue_depth",
                            lambda: depth)

    def test_no_shed_below_bound(self, monkeypatch):
        t = [0.0]
        s = self._shedder(lambda: t[0])
        self._overload(monkeypatch, 1.0)        # full-but-healthy pipeline
        assert not s.should_shed("any")
        assert s.shed_total == 0

    def test_level1_sheds_noisy_tenants_first(self, monkeypatch):
        from bifromq_tpu.obs import OBS
        t = [0.0]
        s = self._shedder(lambda: t[0])
        self._overload(monkeypatch, 2.0)        # level1 ≤ score < 2·level1
        monkeypatch.setattr(OBS, "is_noisy",
                            lambda tenant: tenant == "noisy")
        for i in range(10):
            t[0] += 0.01                        # step past the score TTL
            assert s.should_shed("noisy")
            assert not s.should_shed("quiet")
        snap = s.snapshot()
        # tenant-fair: the noisy tenant sheds STRICTLY more than the
        # quiet one in the same window (the acceptance shape)
        assert snap["match_shed_total"].get("noisy", 0) == 10
        assert snap["match_shed_total"].get("quiet", 0) == 0

    def test_level2_sheds_everyone(self, monkeypatch):
        from bifromq_tpu.obs import OBS
        t = [0.0]
        s = self._shedder(lambda: t[0])
        self._overload(monkeypatch, 4.0)        # ≥ 2·level1
        monkeypatch.setattr(OBS, "is_noisy", lambda tenant: False)
        assert s.should_shed("quiet")

    def test_qos1_never_sheds(self, monkeypatch):
        t = [0.0]
        s = self._shedder(lambda: t[0])
        self._overload(monkeypatch, 100.0)
        assert not s.should_shed("any", qos=1)
        assert not s.should_shed("any", qos=2)

    def test_score_combines_ring_pressure_and_batcher_depth(
            self, monkeypatch):
        t = [0.0]
        s = self._shedder(lambda: t[0])
        self._overload(monkeypatch, 0.9, depth=100)     # 0.9 + 1.0 = 1.9
        from bifromq_tpu.obs import OBS
        monkeypatch.setattr(OBS, "is_noisy", lambda tenant: True)
        assert s.should_shed("noisy")

    def test_queue_pressure_gauge_reads_rings(self):
        from bifromq_tpu.obs import OBS
        ring = _FakeRing(in_flight=2, waiting=2, depth=2)
        OBS.device.register_ring(ring)
        try:
            assert OBS.device.queue_pressure() >= 2.0
        finally:
            OBS.device._rings.discard(ring)


class TestSessionShedWiring:
    async def test_shed_qos0_event_and_qos1_survives(self, monkeypatch):
        """e2e through a real broker: under forced overload QoS0
        publishes shed (SHED_QOS0 event, no delivery) while a QoS1
        publish on the same topic still delivers — zero QoS1 loss."""
        from bifromq_tpu import resilience
        from bifromq_tpu.mqtt.broker import MQTTBroker
        from bifromq_tpu.mqtt.client import MQTTClient
        from bifromq_tpu.plugin.events import (CollectingEventCollector,
                                               EventType)

        class AlwaysShed:
            def should_shed(self, tenant, qos=0):
                return qos == 0
        monkeypatch.setattr(resilience.device, "SHEDDER", AlwaysShed())
        ev = CollectingEventCollector()
        broker = MQTTBroker(host="127.0.0.1", port=0, events=ev)
        await broker.start()
        try:
            sub = MQTTClient("127.0.0.1", broker.port, client_id="s",
                             protocol_level=5)
            await sub.connect()
            await sub.subscribe("shed/t", qos=1)
            p = MQTTClient("127.0.0.1", broker.port, client_id="p",
                           protocol_level=5)
            await p.connect()
            await p.publish("shed/t", b"q0", qos=0)
            await p.publish("shed/t", b"q1", qos=1)
            msg = await asyncio.wait_for(sub.messages.get(), 5)
            assert msg.payload == b"q1"      # QoS1 delivered, QoS0 shed
            assert sub.messages.qsize() == 0
            shed = ev.of(EventType.SHED_QOS0)
            assert shed and shed[0].meta["topic"] == "shed/t"
            await sub.disconnect()
            await p.disconnect()
        finally:
            await broker.stop()


# ---------------- bounded QoS>0 ingest gate ----------------------------------


class TestIngestGate:
    async def test_bounds_and_backpressure(self):
        g = IngestGate(capacity=2)
        await g.acquire()
        await g.acquire()
        third = asyncio.ensure_future(g.acquire())
        await asyncio.sleep(0)
        assert not third.done() and g.waiting == 1
        g.release()
        await asyncio.sleep(0)
        assert third.done()
        assert g.peak_inflight == 2
        g.release()
        g.release()
        assert g.in_flight == 0

    async def test_env_capacity_resolves_at_first_use(self, monkeypatch):
        """The env knob must apply to a gate constructed BEFORE the env
        was set (the process-global INGEST_GATE exists at module import,
        long before the broker sets BIFROMQ_*)."""
        g = IngestGate()                    # built before the env knob
        monkeypatch.setenv("BIFROMQ_QOS1_INFLIGHT", "2")
        await g.acquire()
        await g.acquire()
        assert g.capacity == 2 and g.in_flight == 2
        g.release()
        g.release()

    async def test_cancelled_waiter_withdraws(self):
        g = IngestGate(capacity=1)
        await g.acquire()
        parked = asyncio.ensure_future(g.acquire())
        await asyncio.sleep(0)
        assert g.waiting == 1
        parked.cancel()
        await asyncio.sleep(0)
        assert g.waiting == 0
        g.release()
        await g.acquire()       # slot still cycles
        g.release()


# ---------------- graceful drain ---------------------------------------------


class TestDrain:
    async def test_drain_waits_bounded_then_gives_up(self, monkeypatch):
        monkeypatch.setenv("BIFROMQ_DEVICE_DEADLINE_S", "30")
        m = mk_matcher()
        gate = _Gate()
        _gate_matcher(m, gate)
        task = asyncio.ensure_future(
            m.match_batch_async([("T", ["a", "b"])], batch=16))
        for _ in range(10):
            await asyncio.sleep(0)
        assert m._ring.in_flight == 1
        assert not await m.drain_device(timeout_s=0.05)     # bounded
        gate.open = True
        await asyncio.wait_for(task, 5)
        assert await m.drain_device(timeout_s=1.0)

    async def test_drain_noop_without_ring(self):
        m = mk_matcher()
        assert await m.drain_device(timeout_s=0.01)

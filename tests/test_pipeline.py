"""Device-pipeline tests (ISSUE 6): queue-depth-adaptive batch sizing
(fake clock), in-flight overlap through the dispatch ring, donation
safety, and the _InFlight snapshot discipline under mid-flight mutations
and compaction swaps."""

import asyncio
import heapq

import numpy as np
import pytest

from bifromq_tpu.models.matcher import TpuMatcher
from bifromq_tpu.models.oracle import Route
from bifromq_tpu.models.pipeline import (BASE_FLOOR, MIN_FLOOR,
                                         PIPELINE_DEPTH, DispatchRing)
from bifromq_tpu.scheduler.batcher import Batcher
from bifromq_tpu.types import RouteMatcher


def mk_route(topic_filter: str, receiver: str, incarnation: int = 0):
    return Route(matcher=RouteMatcher.from_topic_filter(topic_filter),
                 broker_id=0, receiver_id=receiver, deliverer_key="d0",
                 incarnation=incarnation)


class FakeClock:
    def __init__(self) -> None:
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class SimClock(FakeClock):
    """Fake time that several tasks share: ``sleep`` parks its caller
    until the clock reaches the due time; ``run`` lets every task that
    can run at the current instant run, then steps to the next due time
    (batches of a depth-2 pipeline overlap as they do on a real loop)."""

    def __init__(self) -> None:
        super().__init__()
        self._due = []
        self._seq = 0

    def sleep(self, dt: float) -> "asyncio.Future":
        fut = asyncio.get_running_loop().create_future()
        self._seq += 1      # equal due times fire in the order they parked
        heapq.heappush(self._due, (self.t + dt, self._seq, fut))
        return fut

    async def run(self, *coros):
        tasks = [asyncio.ensure_future(c) for c in coros]
        while True:
            for _ in range(16):     # a wake-up chain is 4 turns long
                await asyncio.sleep(0)
            if all(t.done() for t in tasks):
                return [t.result() for t in tasks]
            self.t, _, fut = heapq.heappop(self._due)
            fut.set_result(None)


def sim_batcher(clk: SimClock, *, fixed: float = 0.0, per_call: float = 0.0,
                staged: bool = True, **kw):
    """A batcher on ``clk`` whose batches take ``fixed`` + ``per_call`` a
    call, as the pub scheduler builds its own (5 ms budget, depth 2, at
    most 16 calls) unless ``kw`` says otherwise; ``sizes`` collects the
    batches it emitted. Un-staged it records no enqueue time: the
    parent's rule."""
    sizes = []

    async def process(calls):
        sizes.append(len(calls))
        await clk.sleep(fixed + per_call * len(calls))
        return list(calls)

    kw.setdefault("max_burst_latency", 0.005)
    kw.setdefault("pipeline_depth", 2)
    kw.setdefault("max_batch_size", 16)
    b = Batcher(process, stage="queue_wait" if staged else None,
                clock=clk, **kw)
    return b, sizes


async def closed_loop(clk: SimClock, b: Batcher, lanes: int, each: int,
                      think: float = 0.0):
    """``lanes`` submitters, each sending its next call ``think`` (the
    client's turn-around) after the last returned; returns the cap each
    saw as a call returned."""
    caps = []

    async def lane(i):
        for _ in range(each):
            await b.submit(i)
            caps.append(b.batch_cap)
            if think:
                await clk.sleep(think)

    await clk.run(*[lane(i) for i in range(lanes)])
    return caps


# ---------------- adaptive batch sizing (fake clock) ------------------------


class TestAdaptiveSizing:
    async def test_deep_queue_grows_cap(self):
        clk = FakeClock()

        async def fast(calls):
            clk.advance(0.001)      # well under the budget
            return list(calls)

        b = Batcher(fast, max_burst_latency=0.5, pipeline_depth=1,
                    clock=clk)
        for _ in range(6):
            futs = [b.submit(i) for i in range(b.batch_cap * 2)]
            await asyncio.gather(*futs)
        assert b.batch_cap > Batcher.IDLE_CAP

    async def test_shallow_queue_emits_small_batches(self):
        clk = FakeClock()
        sizes = []

        async def fast(calls):
            sizes.append(len(calls))
            clk.advance(0.001)
            return list(calls)

        b = Batcher(fast, max_burst_latency=0.5, pipeline_depth=2,
                    clock=clk)
        # trickle: one call at a time, each fully drained — every batch
        # must emit immediately at size 1, never padded/held to the cap
        for i in range(10):
            await b.submit(i)
        assert sizes == [1] * 10
        assert b.batch_cap == Batcher.IDLE_CAP    # never grew

    async def test_cap_decays_after_burst_drains(self):
        clk = FakeClock()

        async def fast(calls):
            clk.advance(0.001)
            return list(calls)

        b = Batcher(fast, max_burst_latency=0.5, pipeline_depth=1,
                    clock=clk)
        # burst: saturate until the cap grows well past idle
        for _ in range(6):
            futs = [b.submit(i) for i in range(b.batch_cap * 2)]
            await asyncio.gather(*futs)
        grown = b.batch_cap
        assert grown > Batcher.IDLE_CAP
        # trickle: the depth EMA decays, the cap halves back toward idle
        for i in range(80):
            await b.submit(i)
        assert b.batch_cap == Batcher.IDLE_CAP < grown

    async def test_shallow_decay_opt_out_keeps_grown_cap(self):
        # coalescer shape (the worker's consensus-mutation batcher):
        # batches are pure throughput, so the cap must survive each
        # burst's drain tail instead of re-growing from idle every burst
        clk = FakeClock()

        async def fast(calls):
            clk.advance(0.001)
            return list(calls)

        b = Batcher(fast, max_burst_latency=0.5, pipeline_depth=1,
                    shallow_decay=False, clock=clk)
        for _ in range(6):
            futs = [b.submit(i) for i in range(b.batch_cap * 2)]
            await asyncio.gather(*futs)
        grown = b.batch_cap
        assert grown > Batcher.IDLE_CAP
        for i in range(80):
            await b.submit(i)
        assert b.batch_cap == grown          # no decay
        # the latency-overrun guard still applies to opted-out batchers
        # (a cost that grows with the batch)
        async def slow(calls):
            clk.advance(0.01 * len(calls))
            return list(calls)

        b._process = slow
        futs = [b.submit(i) for i in range(grown)]
        await asyncio.gather(*futs)
        assert b.batch_cap < grown

    async def test_latency_overrun_still_halves(self):
        # (b) the guard's own case: 1 ms a call, bursts that leave
        # nobody queued behind them: over budget, and the calls waited
        # (one call's millisecond) under a quarter of their batch's run
        clk = SimClock()
        b, sizes = sim_batcher(clk, per_call=0.001, pipeline_depth=1)
        assert b.batch_cap == 16

        async def bursts():
            for _ in range(4):
                # the first leaves alone and at once, the rest as ONE
                # batch of a whole cap when it returns
                await asyncio.gather(*[b.submit(i)
                                       for i in range(b.batch_cap + 1)])

        await clk.run(bursts())
        assert sizes == [1, 16, 1, 8, 1, 4, 1, 4]
        assert b.batch_cap == 4         # 4 ms a batch: inside the budget

    async def test_fixed_cost_overrun_does_not_collapse(self):
        # (a) THE COLLAPSE: a 9 ms round trip whatever the batch, a 5 ms
        # budget, 64 closed-loop lanes behind depth 2. Halving cannot
        # shorten such a batch: the parent's rule ends at one call a
        # batch, for good
        clk = SimClock()
        b, sizes = sim_batcher(clk, fixed=0.009)
        caps = await closed_loop(clk, b, lanes=64, each=40)
        settled = caps[len(caps) // 4:]
        assert b.batch_cap >= 8 and min(settled) >= 8
        assert sum(sizes) == 64 * 40 and max(sizes) <= 16
        took = clk.t

        clk = SimClock()
        parent, _ = sim_batcher(clk, fixed=0.009, staged=False)
        await closed_loop(clk, parent, lanes=64, each=40)
        assert parent.batch_cap == 1
        assert b.batches_emitted * 4 <= parent.batches_emitted
        assert took * 4 <= clk.t        # and the same calls in a quarter

    async def test_open_trickle_keeps_single_call_batches(self):
        # (c) wildcard_1m.fanout_r25's shape: a 47 ms batch against a
        # 5 ms budget, arrivals one at a time on an empty queue: nobody
        # waits, so the guard takes the cap to 1 and nothing takes it
        # back; no call is held for company
        clk = SimClock()
        b, sizes = sim_batcher(clk, fixed=0.047)
        done_at = []

        async def trickle():
            for i in range(12):
                t0 = clk.t
                await b.submit(i)
                done_at.append(clk.t - t0)
                await clk.sleep(0.1)

        await clk.run(trickle())
        assert sizes == [1] * 12
        assert done_at == pytest.approx([0.047] * 12)
        assert b.batch_cap == 1

    async def test_collapsed_cap_recovers_when_queue_deepens(self):
        # (d) a cap the trickle took to 1 comes back once calls wait
        # longer than their batches run; the parent's never does
        for staged in (True, False):
            clk = SimClock()
            b, sizes = sim_batcher(clk, fixed=0.009, staged=staged)

            async def trickle():
                for i in range(8):
                    await b.submit(i)

            await clk.run(trickle())
            assert b.batch_cap == 1
            await closed_loop(clk, b, lanes=64, each=20)
            if staged:
                assert b.batch_cap >= 8
                assert sizes[-12:-2] == [16] * 10   # the tail drains
            else:
                assert b.batch_cap == 1 and set(sizes) == {1}

    @pytest.mark.parametrize("max_batch", [4, 16])
    async def test_cap_never_passes_max_batch_size(self, max_batch):
        # (e) one device batch is all a pub batch may hand the matcher:
        # a fresh batcher's first burst of 64 leaves in batches of at
        # most max_batch_size (IDLE_CAP clamps to it), and no depth of
        # queue grows the cap past it
        clk = SimClock()
        b, sizes = sim_batcher(clk, fixed=0.009, max_batch_size=max_batch)
        assert b.batch_cap == max_batch < Batcher.IDLE_CAP

        async def burst():
            await asyncio.gather(*[b.submit(i) for i in range(64)])

        await clk.run(burst())
        assert sum(sizes) == 64 and max(sizes) <= max_batch
        caps = await closed_loop(clk, b, lanes=64, each=20)
        assert max(caps) <= max_batch and max(sizes) == max_batch

    async def test_unstaged_batcher_keeps_the_unconditional_guard(self):
        # (f) the worker's mutation coalescer records no enqueue time:
        # it is never starved, and an overrun halves its cap whatever
        # the queue holds, as at the parent
        clk = SimClock()
        b, sizes = sim_batcher(clk, fixed=0.009, staged=False,
                               max_batch_size=8192, shallow_decay=False)
        assert b.batch_cap == Batcher.IDLE_CAP

        async def burst():
            await asyncio.gather(*[b.submit(i) for i in range(400)])

        await clk.run(burst())
        # 1, 1 (the two free slots), then a half less as each returns
        assert sizes[:8] == [1, 1, 32, 16, 8, 4, 2, 1]
        assert b.batch_cap == 1 and b._wait.value == 0.0

    @pytest.mark.parametrize("lanes,think,cap", [
        (12, 0.001, 4), (16, 0.002, 4), (24, 0.001, 16), (64, 0.003, 16)])
    async def test_cap_holds_between_the_two_thresholds(self, lanes, think,
                                                        cap):
        # halving asks for a wait under a quarter of the run, doubling
        # for a wait over the whole of it: a closed loop sits between
        # the two at some cap and stays there (with one threshold the
        # cap flips every few batches, and the EMAs' lag takes it to 1)
        clk = SimClock()
        b, _ = sim_batcher(clk, fixed=0.009)
        caps = await closed_loop(clk, b, lanes, each=80, think=think)
        # past the start, and before the lanes run out one by one
        assert set(caps[len(caps) // 2:len(caps) * 3 // 4]) == {cap}

    async def test_staged_batches_count_calls_and_cap_moves(self):
        import time

        from bifromq_tpu import trace

        def totals():
            got = trace.TRACER.totals.between(0, time.monotonic_ns() + 10**9)
            return [got.get(n, (0, 0.0))[0] for n in
                    ("batch.calls", "batch.cap_grow", "batch.cap_shrink",
                     "batch.emitted")]

        before = totals()
        clk = SimClock()
        b, sizes = sim_batcher(clk, fixed=0.009)
        await closed_loop(clk, b, lanes=64, each=10)
        calls, grew, shrank, batches = (a - b4 for a, b4
                                        in zip(totals(), before))
        assert calls == 640 and batches == b.batches_emitted == len(sizes)
        # 16 -> 8 -> 4 while the first calls had not waited, then back
        assert shrank == grew == 2 and b.batch_cap == 16
        # an un-staged batcher counts nothing
        before = totals()
        b, sizes = sim_batcher(SimClock(), fixed=0.009, staged=False)
        await closed_loop(b._clock, b, lanes=8, each=4)
        assert totals() == before

    async def test_queue_depth_property(self):
        started = asyncio.Event()
        release = asyncio.Event()

        async def block(calls):
            started.set()
            await release.wait()
            return list(calls)

        b = Batcher(block, pipeline_depth=1)
        futs = [b.submit(i) for i in range(5)]
        await started.wait()
        # one in flight (the first emitted immediately), four queued
        assert b.queue_depth == 4
        release.set()
        await asyncio.gather(*futs)
        assert b.queue_depth == 0


# ---------------- dispatch ring -------------------------------------------


class TestDispatchRing:
    async def test_ring_bounds_inflight_and_tracks_peak(self):
        ring = DispatchRing(depth=2)
        await ring.acquire()
        await ring.acquire()
        assert ring.in_flight == 2
        third = asyncio.ensure_future(ring.acquire())
        await asyncio.sleep(0)
        assert not third.done()         # parked: ring is full
        assert ring.waiting == 1
        ring.release()
        await asyncio.sleep(0)
        assert third.done()
        assert ring.peak_inflight == 2
        ring.release()
        ring.release()

    async def test_cancelled_waiter_withdraws_from_queue(self):
        """A parked waiter that gets cancelled must not linger in the
        waiter deque — a stale entry overcounts ring.waiting and pins
        effective_floor at the throughput floor on an idle broker."""
        ring = DispatchRing(depth=1, min_floor=8)
        await ring.acquire()
        parked = asyncio.ensure_future(ring.acquire())
        await asyncio.sleep(0)
        assert ring.waiting == 1
        parked.cancel()
        await asyncio.sleep(0)
        assert ring.waiting == 0
        assert ring.effective_floor() == 8      # idle again: latency floor
        # the slot still cycles: release + re-acquire works
        ring.release()
        await ring.acquire()
        ring.release()

    async def test_effective_floor_shallow_vs_busy(self):
        ring = DispatchRing(depth=3, min_floor=8)
        await ring.acquire()
        assert ring.effective_floor() == 8      # alone in flight: latency
        await ring.acquire()
        assert ring.effective_floor() == 16     # concurrency: throughput
        ring.release()
        ring.release()


def _pub_scheduler_depth():
    from bifromq_tpu.dist.service import DistService
    from bifromq_tpu.plugin.events import CollectingEventCollector
    from bifromq_tpu.plugin.settings import DefaultSettingProvider
    from bifromq_tpu.plugin.subbroker import SubBrokerRegistry
    svc = DistService(SubBrokerRegistry(), CollectingEventCollector(),
                      DefaultSettingProvider(), worker=object())
    return svc._pub_scheduler.batcher("T")._depth


def _capacity_ring_depth():
    from bifromq_tpu.obs.capacity import inflight_bytes
    return inflight_bytes(BASE_FLOOR)["ring_depth"]


@pytest.mark.parametrize("read, want", [
    (lambda: DispatchRing().depth, PIPELINE_DEPTH),
    (_pub_scheduler_depth, PIPELINE_DEPTH),
    (_capacity_ring_depth, PIPELINE_DEPTH),
    (lambda: DispatchRing().min_floor, MIN_FLOOR),
    (lambda: DispatchRing().base_floor, BASE_FLOOR),
], ids=["ring_depth", "pub_scheduler_depth", "capacity_ring_depth",
        "idle_pad", "busy_pad"])
def test_every_reader_of_a_pipeline_size_reads_the_constant(read, want):
    """The ring, the pub scheduler and the capacity model each hold the
    pipeline's sizes: one number each, from ``models/pipeline.py``."""
    assert read() == want


# ---------------- matcher async pipeline -----------------------------------


class _Gate:
    def __init__(self) -> None:
        self.open = False


class _GatedLeaf:
    """numpy-backed stand-in for a jax result buffer whose readiness the
    test controls (CPU completes too fast to observe real overlap)."""

    def __init__(self, arr, gate: _Gate) -> None:
        self._arr = np.asarray(arr)
        self._gate = gate

    def is_ready(self) -> bool:
        return self._gate.open

    def copy_to_host_async(self) -> None:
        pass

    def __array__(self, dtype=None):
        return (self._arr if dtype is None
                else self._arr.astype(dtype, copy=False))


def _gate_matcher(m: TpuMatcher, gate: _Gate):
    """Wrap the primary walk so its results report not-ready until the
    gate opens — the device is 'still walking'."""
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


@pytest.fixture(scope="module")
def matcher():
    m = TpuMatcher(max_levels=8, k_states=8, auto_compact=False,
                   match_cache=True)
    m.add_route("T", mk_route("a/b", "r1"))
    m.add_route("T", mk_route("a/+", "r2"))
    m.add_route("T", mk_route("x/#", "r3"))
    m.add_route("T", mk_route("deep/q/w", "r4"))
    m.refresh()
    return m


def _ids(res):
    return sorted(r.receiver_id for r in res.normal)


class TestMatcherAsync:
    async def test_async_parity_with_sync(self, matcher):
        qs = [("T", ["a", "b"]), ("T", ["x", "y", "z"]),
              ("T", ["deep", "q", "w"]), ("T", ["nomatch"])]
        sync = matcher.match_batch(qs)
        matcher.match_cache.clear()
        got = await matcher.match_batch_async(qs)
        for a, b in zip(got, sync):
            assert _ids(a) == _ids(b)

    async def test_two_batches_in_flight_concurrently(self):
        m = TpuMatcher(max_levels=8, k_states=8, auto_compact=False,
                       match_cache=False)
        m.add_route("T", mk_route("a/b", "r1"))
        m.refresh()
        gate = _Gate()
        _gate_matcher(m, gate)
        t1 = asyncio.ensure_future(
            m.match_batch_async([("T", ["a", "b"])], batch=16))
        t2 = asyncio.ensure_future(
            m.match_batch_async([("T", ["a", "c"])], batch=16))
        # let both tasks run to their readiness await
        for _ in range(10):
            await asyncio.sleep(0)
        ring = m._ring
        assert ring.in_flight >= 2, \
            "batch N+1 must dispatch while batch N is still walking"
        gate.open = True
        r1, r2 = await asyncio.gather(t1, t2)
        assert _ids(r1[0]) == ["r1"]
        assert _ids(r2[0]) == []
        assert ring.peak_inflight >= 2

    async def test_ring_depth_bounds_inflight(self):
        m = TpuMatcher(max_levels=8, k_states=8, auto_compact=False,
                       match_cache=False)
        m.add_route("T", mk_route("a/b", "r1"))
        m.refresh()
        gate = _Gate()
        _gate_matcher(m, gate)
        m._pipeline_ring().depth = 2
        tasks = [asyncio.ensure_future(
            m.match_batch_async([("T", ["a", str(i)])], batch=16))
            for i in range(5)]
        for _ in range(10):
            await asyncio.sleep(0)
        assert m._ring.in_flight == 2
        # ISSUE 11: the 3 excess callers park behind TWO gates now —
        # prep tickets (depth+1, held for the whole slot tenure) bound
        # uploaded probe batches, so exactly ONE caller preps ahead and
        # parks at the slot gate; the other 2 wait un-uploaded in the
        # line for a ticket
        assert m._ring.waiting == 1
        assert m._ring.prepping == 3        # 2 in flight + 1 prep-ahead
        assert m._ring.parked == 2
        gate.open = True
        await asyncio.gather(*tasks)
        assert m._ring.in_flight == 0
        assert m._ring.prepping == 0

    async def test_mutation_mid_flight_defeats_cache_store(self):
        m = TpuMatcher(max_levels=8, k_states=8, auto_compact=False,
                       match_cache=True)
        m.add_route("T", mk_route("a/b", "r1"))
        m.refresh()
        gate = _Gate()
        _gate_matcher(m, gate)
        task = asyncio.ensure_future(
            m.match_batch_async([("T", ["a", "b"])], batch=16))
        for _ in range(10):
            await asyncio.sleep(0)
        # a second subscriber lands WHILE the walk is in flight
        m.add_route("T", mk_route("a/b", "r9"))
        gate.open = True
        await task
        # the in-flight result must not have been stamped into the cache:
        # the next (sync) match sees the new route
        res = m.match_batch([("T", ["a", "b"])])
        assert _ids(res[0]) == ["r1", "r9"]

    async def test_compaction_swap_mid_flight_keeps_overlay(self):
        """_InFlight snapshot discipline: a blocking compaction swapping
        the base between dispatch and fetch must not lose overlay routes
        the old-base expansion needs."""
        m = TpuMatcher(max_levels=8, k_states=8, auto_compact=False,
                       match_cache=False)
        m.add_route("T", mk_route("a/b", "r1"))
        m.refresh()
        m.add_route("T", mk_route("a/+", "r2"))     # overlay-only route
        gate = _Gate()
        _gate_matcher(m, gate)
        task = asyncio.ensure_future(
            m.match_batch_async([("T", ["a", "b"])], batch=16))
        for _ in range(10):
            await asyncio.sleep(0)
        m.refresh()     # folds r2 into a fresh base, clears the overlay
        gate.open = True
        res = await task
        assert _ids(res[0]) == ["r1", "r2"]

    async def test_unknown_keyword_is_refused(self, matcher):
        with pytest.raises(TypeError):
            await matcher.match_batch_async([("T", ["a", "b"])],
                                            compaction="scatter")


# ---------------- one device walk for callers in line ----------------------


def _fleet(n_tenants: int) -> TpuMatcher:
    m = TpuMatcher(max_levels=8, k_states=8, auto_compact=False,
                   match_cache=False)
    for i in range(n_tenants):
        m.add_route(f"T{i}", mk_route("a/+", f"r{i}a"))
        m.add_route(f"T{i}", mk_route(f"a/{i}", f"r{i}b"))
        m.add_route(f"T{i}", mk_route("x/#", f"r{i}c"))
    m.refresh()
    return m


class _Hold:
    """Keeps a ring busy: every slot and every prep ticket taken, so
    callers that enter wait in line until ``free``."""

    def __init__(self, ring: DispatchRing) -> None:
        self.ring = ring
        self.tickets = []

    async def take(self) -> "_Hold":
        from bifromq_tpu.models.pipeline import Merged
        for _ in range(self.ring.depth):
            await self.ring.acquire()
        while self.ring._prep.try_acquire():
            self.tickets.append(Merged())
        return self

    def free(self) -> None:
        for _ in range(self.ring.depth):
            self.ring.release()
        for held in self.tickets:
            self.ring.release_prep(held)


async def _turns(n: int = 10) -> None:
    for _ in range(n):
        await asyncio.sleep(0)


def _new_records(n0: int):
    from bifromq_tpu.obs import OBS
    n = OBS.profiler.batches_total - n0
    return OBS.profiler.records()[-n:] if n else []


def _batches_total() -> int:
    from bifromq_tpu.obs import OBS
    return OBS.profiler.batches_total


def _exact(m: TpuMatcher, queries, rows) -> bool:
    want = m.match_from_tries(queries)
    return len(rows) == len(want) and all(
        _ids(a) == _ids(b) for a, b in zip(rows, want))


@pytest.fixture
def injector():
    from bifromq_tpu.resilience.faults import get_injector
    get_injector().reset(seed=7)
    yield get_injector()
    get_injector().reset()


class TestMergeAtAdmission:
    async def test_callers_in_line_share_one_batch(self):
        async with asyncio.timeout(30):
            m = _fleet(6)
            ring = m._pipeline_ring()
            ring.depth = 1
            hold = await _Hold(ring).take()
            # N callers of N tenants, one or two rows each
            qs = [[(f"T{i}", ["a", str(i)])] + ([(f"T{i}", ["x", "y"])]
                                               if i % 2 else [])
                  for i in range(6)]
            tasks = [asyncio.ensure_future(m.match_batch_async(q))
                     for q in qs]
            await _turns()
            assert ring.parked == 6 and ring.in_flight == 1
            n0 = _batches_total()
            hold.free()
            got = await asyncio.gather(*tasks)
            recs = _new_records(n0)
            assert [r.n_queries for r in recs] == [sum(map(len, qs))]
            assert recs[0].kernel != "oracle" and recs[0].batch == 16
            for q, rows in zip(qs, got):    # its own rows, nobody else's
                assert _exact(m, q, rows)
            assert ring.in_flight == ring.prepping == ring.parked == 0

    async def test_lone_caller_on_idle_ring_leaves_at_once(self):
        async with asyncio.timeout(30):
            m = _fleet(1)
            gate = _Gate()
            gate.open = True        # the walk reads ready at first poll
            _gate_matcher(m, gate)
            await m.match_batch_async([("T0", ["a", "0"])])     # compile
            loop = asyncio.get_running_loop()
            timers = []
            real_call_at = loop.call_at     # call_later goes through it

            def call_at(when, *a, **kw):
                timers.append(when)
                return real_call_at(when, *a, **kw)
            loop.call_at = call_at
            n0 = _batches_total()
            try:
                stats = {}
                rows = await m.match_batch_async([("T0", ["a", "q"])],
                                                 stats=stats)
            finally:
                del loop.call_at
            assert timers == []     # no timed wait was ever scheduled
            assert _ids(rows[0]) == ["r0a"]
            recs = _new_records(n0)
            assert [(r.n_queries, r.batch) for r in recs] == [(1, 8)]
            assert stats["batch_share"] == 1.0

    async def test_forty_in_line_leave_as_16_16_8_in_entry_order(self):
        async with asyncio.timeout(30):
            m = _fleet(4)
            ring = m._pipeline_ring()
            ring.depth = 1
            hold = await _Hold(ring).take()
            qs = [[(f"T{i % 4}", ["a", str(i)])] for i in range(40)]
            done = []
            tasks = []
            for i, q in enumerate(qs):
                t = asyncio.ensure_future(m.match_batch_async(q))
                t.add_done_callback(lambda _t, i=i: done.append(i))
                tasks.append(t)
            await _turns()
            assert ring.parked == 40
            n0 = _batches_total()
            hold.free()
            got = await asyncio.gather(*tasks)
            assert [r.n_queries for r in _new_records(n0)] == [16, 16, 8]
            assert done == list(range(40))
            for q, rows in zip(qs, got):
                assert _exact(m, q, rows)

    async def test_stall_at_the_ring_is_late_not_degraded(self):
        """Time in line starts no deadline: callers whose match deadline
        runs out WHILE they wait for the ring are served by the device,
        as in the parent (the check is on entry, in the worker)."""
        from bifromq_tpu.dist.worker import DistWorker
        from bifromq_tpu.resilience.policy import deadline_scope
        from bifromq_tpu.utils.metrics import FABRIC, FabricMetric
        async with asyncio.timeout(40):
            w = DistWorker()
            await w.start()
            try:
                for i in range(4):
                    await w.add_route(f"T{i}", mk_route("s/+", f"r{i}"))
                kw = dict(max_persistent_fanout=100, max_group_fanout=100)
                await w.match_batch([("T0", "s/warm")], **kw)   # compile
                ring = w.matcher._pipeline_ring()
                hold = await _Hold(ring).take()
                base = (FABRIC.get(FabricMetric.MATCH_DEGRADED),
                        FABRIC.get(FabricMetric.DEVICE_TIMEOUT))
                n0 = _batches_total()

                async def one(i):
                    with deadline_scope(0.05):
                        return await w.match_batch([(f"T{i}", f"s/{i}")],
                                                   **kw)
                tasks = [asyncio.ensure_future(one(i)) for i in range(4)]
                await asyncio.sleep(0.3)
                assert ring.parked == 4
                hold.free()
                got = await asyncio.gather(*tasks)
                for i, rows in enumerate(got):
                    assert _ids(rows[0]) == [f"r{i}"]
                assert (FABRIC.get(FabricMetric.MATCH_DEGRADED),
                        FABRIC.get(FabricMetric.DEVICE_TIMEOUT)) == base
                recs = _new_records(n0)
                # four rows, and still the throughput pad: callers that
                # share a batch are concurrency, however idle the ring
                assert [(r.n_queries, r.batch) for r in recs] == [(4, 16)]
                assert recs[0].kernel != "oracle"
            finally:
                await w.stop()

    async def test_fault_on_merged_batch_is_one_fault(self, injector):
        from bifromq_tpu.resilience.breaker import CircuitBreaker
        from bifromq_tpu.utils.metrics import FABRIC, FabricMetric
        async with asyncio.timeout(30):
            m = _fleet(5)
            clock = [0.0]
            br = m.device_breaker = CircuitBreaker(
                failure_threshold=3, recovery_time=5.0,
                clock=lambda: clock[0])
            seen = {"failures": 0, "admits": []}
            real_fail, real_admit = br.record_failure, br.admit

            def record_failure(*a, **kw):
                seen["failures"] += 1
                return real_fail(*a, **kw)

            def admit():
                seen["admits"].append(real_admit())
                return seen["admits"][-1]
            br.record_failure, br.admit = record_failure, admit
            ring = m._pipeline_ring()
            ring.depth = 1

            async def merged_round(n):
                hold = await _Hold(ring).take()
                qs = [[(f"T{i}", ["a", str(i)])] for i in range(n)]
                stats = [{} for _ in qs]
                tasks = [asyncio.ensure_future(
                    m.match_batch_async(q, stats=st))
                    for q, st in zip(qs, stats)]
                await _turns()
                assert ring.parked == n
                n0, d0 = _batches_total(), ring.dispatched_total
                hold.free()
                got = await asyncio.gather(*tasks)
                for q, rows in zip(qs, got):
                    assert _exact(m, q, rows)
                return (stats, _new_records(n0),
                        ring.dispatched_total - d0)

            # a device error: every caller served by the oracle, the rows
            # counted once, the breaker fed once
            injector.add_rule(service="tpu-device", method="dispatch",
                              action="error", max_hits=1)
            base = FABRIC.get(FabricMetric.MATCH_DEGRADED)
            stats, recs, dispatched = await merged_round(5)
            assert [st["degraded"] for st in stats] == ["device_error"] * 5
            assert FABRIC.get(FabricMetric.MATCH_DEGRADED) == base + 5
            assert seen == {"failures": 1, "admits": ["ok"]}
            assert dispatched == 1
            assert [(r.kernel, r.n_queries) for r in recs] == [("oracle", 5)]
            # half-open: the merged batch is ONE canary
            br.force_open()
            clock[0] = 6.0
            seen["admits"].clear()
            stats, recs, dispatched = await merged_round(4)
            assert seen["admits"] == ["canary"] and dispatched == 1
            assert br.state == "closed"
            assert all("degraded" not in st for st in stats)
            assert [r.n_queries for r in recs] == [4]
            assert FABRIC.get(FabricMetric.MATCH_DEGRADED) == base + 5

    async def test_cancelled_callers_harm_nobody(self):
        async with asyncio.timeout(30):
            m = _fleet(4)
            gate = _Gate()
            _gate_matcher(m, gate)
            ring = m._pipeline_ring()
            ring.depth = 1
            hold = await _Hold(ring).take()
            qs = [[(f"T{i}", ["a", str(i)])] for i in range(4)]
            tasks = [asyncio.ensure_future(m.match_batch_async(q))
                     for q in qs]
            await _turns()
            assert ring.parked == 4
            tasks[1].cancel()               # cancelled while in line
            await _turns()
            assert ring.parked == 3
            n0 = _batches_total()
            hold.free()
            await _turns()
            assert ring.in_flight == 1 and ring.parked == 0
            tasks[0].cancel()               # cancelled after dispatch
            await _turns()
            assert ring.in_flight == 1      # the shared walk goes on
            gate.open = True
            r2, r3 = await asyncio.gather(tasks[2], tasks[3])
            assert _exact(m, qs[2], r2) and _exact(m, qs[3], r3)
            assert [r.n_queries for r in _new_records(n0)] == [3]
            assert tasks[0].cancelled() and tasks[1].cancelled()
            await _turns()
            assert ring.in_flight == ring.prepping == ring.parked == 0
            assert len(ring.quarantine) == 0
            # nobody left to serve: the walk is given up, its arrays
            # quarantined like any cancelled in-flight batch
            gate.open = False
            last = asyncio.ensure_future(m.match_batch_async(qs[0]))
            await _turns()
            assert ring.in_flight == 1
            last.cancel()
            await _turns()
            assert last.cancelled()
            assert ring.in_flight == ring.prepping == ring.parked == 0
            assert len(ring.quarantine) == 1
            gate.open = True
            assert _exact(m, qs[3], await m.match_batch_async(qs[3]))

    async def test_shares_of_one_batch_sum_to_one(self):
        from bifromq_tpu.dist.worker import DistWorker
        from bifromq_tpu.utils.metrics import STAGES
        async with asyncio.timeout(40):
            w = DistWorker()
            await w.start()
            try:
                for i in range(3):
                    await w.add_route(f"T{i}", mk_route("s/+", f"r{i}"))
                kw = dict(max_persistent_fanout=100, max_group_fanout=100)
                await w.match_batch([("T0", "s/warm")], **kw)   # compile
                shares = []
                real = DistWorker._tenant_shares

                def tenant_shares(sub, of_batch=1.0):
                    shares.append(real(sub, of_batch))
                    return shares[-1]
                w._tenant_shares = tenant_shares
                hold = await _Hold(w.matcher._pipeline_ring()).take()
                # 1 + 2 + 1 rows of three tenants, T0 twice
                subs = [[("T0", "s/a")], [("T1", "s/b"), ("T1", "s/c")],
                        [("T0", "s/d")]]
                count0 = STAGES.snapshot().get("device", {}).get("count", 0)
                n0 = _batches_total()
                tasks = [asyncio.ensure_future(w.match_batch(q, **kw))
                         for q in subs]
                await _turns()
                hold.free()
                await asyncio.gather(*tasks)
                assert [r.n_queries for r in _new_records(n0)] == [4]
                assert shares == [{"T0": 0.25}, {"T1": 0.5}, {"T0": 0.25}]
                assert STAGES.snapshot()["device"]["count"] == count0 + 3
            finally:
                await w.stop()


class TestDonationSafety:
    def test_donated_probes_are_consumed_and_results_match(self):
        """walk_routes_donated must produce identical results while
        actually consuming the probe buffers (use-after-donate raises)."""
        from bifromq_tpu.models.automaton import compile_tries, tokenize
        from bifromq_tpu.ops.match import (DeviceTrie, Probes, walk_routes,
                                           walk_routes_donated)
        m = TpuMatcher(max_levels=8, k_states=8, auto_compact=False,
                       match_cache=False)
        m.add_route("T", mk_route("a/b", "r1"))
        m.add_route("T", mk_route("a/+", "r2"))
        ct = compile_tries(m.tries, max_levels=8)
        dev = DeviceTrie.from_compiled(ct)
        tok = tokenize([["a", "b"], ["a", "z"]], [ct.root_of("T")] * 2,
                       max_levels=ct.max_levels, salt=ct.salt, batch=16)
        kw = dict(probe_len=ct.probe_len, k_states=8, max_intervals=16,
                  esc_k=0)
        base = walk_routes(dev, Probes.from_tokenized(tok), **kw)
        p = Probes.from_tokenized(tok)
        got = walk_routes_donated(dev, p, **kw)
        assert (np.asarray(got.count) == np.asarray(base.count)).all()
        assert (np.asarray(got.start) == np.asarray(base.start)).all()
        # after donation the buffer is in one of exactly two SAFE states:
        # deleted (XLA aliased it — reading raises) or intact (XLA
        # declined the alias for shape reasons and left it alone); silent
        # corruption would surface as a parity failure above
        try:
            h1 = np.asarray(p.tok_h1)
        except RuntimeError:
            pass    # consumed, as the donated-jit contract promises
        else:
            assert (h1 == tok.tok_h1).all()

    async def test_pipelined_serving_never_reuses_donated_buffers(self):
        """End-to-end: repeated donated dispatches through the async path
        stay correct — any use-after-donate inside the pipeline would
        raise 'Array has been deleted'."""
        m = TpuMatcher(max_levels=8, k_states=8, auto_compact=False,
                       match_cache=False)
        m.add_route("T", mk_route("a/b", "r1"))
        m.add_route("T", mk_route("a/+", "r2"))
        m.refresh()
        for _ in range(4):
            res = await m.match_batch_async(
                [("T", ["a", "b"]), ("T", ["a", "q"])])
            assert _ids(res[0]) == ["r1", "r2"]
            assert _ids(res[1]) == ["r2"]


class TestGauges:
    def test_device_snapshot_reports_ring(self):
        from bifromq_tpu.obs import OBS
        m = TpuMatcher(max_levels=8, k_states=8, auto_compact=False,
                       match_cache=False)
        ring = m._pipeline_ring()
        snap = OBS.device.snapshot(memory=False)
        assert snap["ring_depth"] >= ring.depth
        assert "ring_in_flight" in snap and "ring_waiting" in snap

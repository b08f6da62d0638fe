"""Adaptive batcher tests (≈ base-scheduler BatcherTest behaviors)."""

import asyncio

import pytest

from bifromq_tpu.scheduler.batcher import BatchCallScheduler, Batcher


class TestBatcher:
    async def test_results_in_order(self):
        async def process(calls):
            return [c * 2 for c in calls]

        b = Batcher(process)
        futs = [b.submit(i) for i in range(100)]
        results = await asyncio.gather(*futs)
        assert results == [i * 2 for i in range(100)]

    async def test_batching_happens(self):
        sizes = []

        async def process(calls):
            sizes.append(len(calls))
            await asyncio.sleep(0.001)
            return list(calls)

        b = Batcher(process, pipeline_depth=1)
        futs = [b.submit(i) for i in range(50)]
        await asyncio.gather(*futs)
        # pipeline depth 1: first batch emits immediately; the rest coalesce
        assert len(sizes) < 50
        assert sum(sizes) == 50

    async def test_pipeline_depth_respected(self):
        inflight = 0
        peak = 0

        async def process(calls):
            nonlocal inflight, peak
            inflight += 1
            peak = max(peak, inflight)
            await asyncio.sleep(0.002)
            inflight -= 1
            return list(calls)

        b = Batcher(process, pipeline_depth=2, max_batch_size=4)
        futs = [b.submit(i) for i in range(64)]
        await asyncio.gather(*futs)
        assert peak <= 2

    async def test_cap_shrinks_on_overrun(self):
        # the guard's own case: a cost that grows with the batch, and
        # bursts that leave nobody queued behind them (a fixed cost under
        # a deep queue is test_pipeline's TestAdaptiveSizing)
        # on the batcher's own clock: under a loaded machine a wall-clock
        # 0.5 ms sleep stretches until the calls HAVE waited long enough
        now = [0.0]

        async def slow(calls):
            now[0] += 0.0005 * len(calls)
            await asyncio.sleep(0)
            return list(calls)

        b = Batcher(slow, max_burst_latency=0.001, pipeline_depth=1,
                    stage="queue_wait", clock=lambda: now[0])
        start_cap = b.batch_cap
        for _ in range(3):
            # the first leaves alone and at once, the rest as ONE batch
            # when it returns: they waited 0.5 ms and run cap x 0.5 ms
            await asyncio.gather(*[b.submit(i)
                                   for i in range(b.batch_cap + 1)])
        assert b.batch_cap < start_cap

    async def test_cap_grows_when_fast(self):
        async def fast(calls):
            return list(calls)

        b = Batcher(fast, max_burst_latency=0.5, pipeline_depth=1)
        for _ in range(20):
            futs = [b.submit(i) for i in range(b.batch_cap * 2)]
            await asyncio.gather(*futs)
        assert b.batch_cap > 64

    async def test_failure_fails_batch(self):
        async def boom(calls):
            raise RuntimeError("nope")

        b = Batcher(boom)
        fut = b.submit(1)
        with pytest.raises(RuntimeError):
            await fut


class TestScheduler:
    async def test_per_key_isolation(self):
        seen = {}

        def factory(key):
            async def process(calls):
                seen.setdefault(key, []).extend(calls)
                return list(calls)
            return process

        s = BatchCallScheduler(factory)
        await asyncio.gather(s.submit("a", 1), s.submit("b", 2),
                             s.submit("a", 3))
        assert sorted(seen["a"]) == [1, 3]
        assert seen["b"] == [2]

"""Adaptive batching framework (≈ reference base-scheduler).

The reference funnels every data-path RPC through
``BatchCallScheduler``/``Batcher`` (base-scheduler .../Batcher.java:46):
calls are grouped by a batcher key, queued, and emitted as batches whose size
adapts to a moving-average latency budget (``maxBurstLatency``), with a
bounded pipeline of in-flight batches (trigger():186, batchAndEmit():201).

Here the same contract drives the TPU match plane: PUBLISH topics accumulate
per tenant-shard and are emitted as fixed-shape device batches; the latency
budget maps to device step cadence. Implemented on asyncio instead of
CompletableFuture chains — single-threaded, so no locks.
"""

from __future__ import annotations

import asyncio
import time
from typing import (Awaitable, Callable, Dict, Generic, Hashable, List,
                    Optional, Sequence, Tuple, TypeVar)

from .. import trace
from ..obs import OBS
from ..utils.hlc import HLC

CallT = TypeVar("CallT")
ResultT = TypeVar("ResultT")

# process_batch(calls) -> results, one per call, same order
BatchFn = Callable[[Sequence[CallT]], Awaitable[Sequence[ResultT]]]


class EMA:
    """Exponential moving average (≈ base-scheduler EMALong)."""

    def __init__(self, alpha: float = 0.2, init: float = 0.0) -> None:
        self.alpha = alpha
        self.value = init

    def update(self, sample: float) -> float:
        self.value = (1 - self.alpha) * self.value + self.alpha * sample
        return self.value


class Batcher(Generic[CallT, ResultT]):
    """One batching pipeline (≈ Batcher.java:46).

    - bounded in-flight pipeline (``pipeline_depth``)
    - a batch is what is queued when a pipeline slot frees, up to an
      adaptive cap; nobody is held for a batch to fill (no timer)
    - the cap (``_adapt``) follows what the batcher itself observes:
      STARVED (a staged batcher's calls waited longer in the queue than
      their batch ran, both smoothed) and SATURATED (the queue held a
      full cap at emit) doubles it toward ``max_batch_size`` whatever
      the budget says: a batch whose cost is a fixed round trip is not
      shortened by halving it, only its queue is lengthened. An OVERRUN
      (a batch longer than ``max_burst_latency``) whose calls waited
      under a quarter of its run halves it: the ``maxBurstLatency``
      guard, for a cost that grows with the batch on a shallow queue.
      Between the two the cap holds: a closed loop settles where the
      wait is about the run, and one threshold there would flip the cap
      every few batches. Within the budget the cap grows while the
      queue stays saturated and decays back toward the idle cap while
      it runs shallow, so after a burst drains the next trickle is not
      measured against a stale burst-sized cap.
    - an un-staged batcher records no enqueue time, is never starved,
      and so keeps the overrun guard unconditionally.
    """

    #: cap a freshly-built (or drained-idle) batcher starts from
    IDLE_CAP = 64

    def __init__(self, process_batch: BatchFn, *,
                 pipeline_depth: int = 2,
                 max_burst_latency: float = 0.010, max_batch_size: int = 8192,
                 min_batch_size: int = 1,
                 stage: Optional[str] = None,
                 obs_key: Optional[str] = None,
                 shallow_decay: bool = True,
                 clock: Callable[[], float] = time.monotonic) -> None:
        self._process = process_batch
        self._depth = pipeline_depth
        self._budget = max_burst_latency
        self._max_cap = max_batch_size
        self._idle_cap = min(max(min_batch_size, self.IDLE_CAP),
                             max_batch_size)
        self._cap = self._idle_cap
        self._min_cap = min_batch_size
        # injectable time source (fake-clock adaptive-sizing tests drive
        # the latency/depth signals deterministically)
        self._clock = clock
        # ISSUE 2: a named stage turns on enqueue→emit queue-wait
        # attribution — one deferred "batch.queue_wait" boundary per
        # call, closed AT EMIT TIME on the batcher's clock (seconds of
        # CLOCK_MONOTONIC): its exit feeds the ``stage`` histogram and,
        # for sampled calls, a span stamped with batch size + the
        # adaptive cap
        self._stage = stage
        # ISSUE 3: when the batcher key IS a tenant (the pub scheduler),
        # queue-wait also lands in that tenant's SLO window — the
        # noisy-neighbor detector's share-of-queue-wait signal
        self._obs_key = obs_key
        # queue entries: (call, fut, enqueue_clock, trace_ctx, start_hlc)
        self._queue: List[Tuple[CallT, asyncio.Future, float,
                                Optional[object], int]] = []
        self._inflight = 0
        self._latency = EMA(init=0.0)
        # mean enqueue→emit wait of a batch's calls, smoothed like the
        # batch time it is compared with (staged batchers only)
        self._wait = EMA(init=0.0)
        # queue depth observed at emit (EMA smooths one-batch spikes so a
        # single burst doesn't whipsaw the cap)
        self._depth_ema = EMA(alpha=0.3, init=0.0)
        # shallow-queue decay exists for time-to-first-result on SERVING
        # batchers; coalescers whose batches are purely throughput (the
        # worker's consensus-mutation batcher: one raft propose per
        # batch) opt out, or each bursty drain tail would shrink the cap
        # and the next burst would re-grow from idle in many small,
        # per-batch-expensive proposes
        self._shallow_decay = shallow_decay
        # strong refs: the loop only weakly references tasks, and a collected
        # batch task would strand every future in that batch
        self._tasks: set = set()
        self.batches_emitted = 0
        self.calls_submitted = 0
        self.last_activity = time.monotonic()

    def submit(self, call: CallT) -> "asyncio.Future[ResultT]":
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        if self._stage is not None:
            tctx = trace.current_ctx()
            shlc = 0
            if tctx is not None and tctx.sampled:
                shlc = HLC.INST.get()
            else:
                tctx = None
            self._queue.append((call, fut, self._clock(), tctx,
                                shlc))
        else:
            # un-staged batchers (e.g. the worker's mutation coalescer)
            # skip the timing capture entirely — zero added hot-path cost
            self._queue.append((call, fut, 0.0, None, 0))
        self.calls_submitted += 1
        self.last_activity = time.monotonic()
        self._trigger()
        return fut

    @property
    def idle(self) -> bool:
        return not self._queue and self._inflight == 0

    @property
    def batch_cap(self) -> int:
        return self._cap

    @property
    def avg_latency(self) -> float:
        return self._latency.value

    @property
    def queue_depth(self) -> int:
        """Calls enqueued but not yet emitted (the obs/device.py
        dispatch-queue gauge reads this via ``_queue``)."""
        return len(self._queue)

    def _trigger(self) -> None:
        while self._queue and self._inflight < self._depth:
            # depth BEFORE slicing: the saturation signal _adapt keys on
            # is "how much work was waiting when this batch emitted"
            depth_at_emit = len(self._queue)
            batch = self._queue[:self._cap]
            del self._queue[:len(batch)]
            self._inflight += 1
            self.batches_emitted += 1
            task = asyncio.get_running_loop().create_task(
                self._run(batch, depth_at_emit))
            self._tasks.add(task)
            task.add_done_callback(self._tasks.discard)

    async def _run(self, batch: List[Tuple], depth_at_emit: int = 0) -> None:
        calls = [b[0] for b in batch]
        start = self._clock()
        rep_ctx = None
        links: List[Tuple[int, int]] = []
        waited = 0.0
        if self._stage is not None:
            # enqueue→emit queue-wait per call, stamped at EMIT time with
            # the batch shape the adaptive cap produced
            start_ns = int(start * 1e9)
            # one id per emitted batch, carried by every sampled span
            # opened under this task (the device spans included), so a
            # sampled publish joins its batch whoever parents the batch
            batch_id = trace.open_batch()
            tags = None
            for _, _, enq, tctx, shlc in batch:
                waited += start - enq
                if tctx is not None:
                    if rep_ctx is None:
                        rep_ctx = tctx
                    elif len(links) < trace.LINK_CAP:
                        # every LATER sampled caller becomes a span link
                        # on the batch-emit span below, so its trace still
                        # reaches the device work it shared
                        links.append((tctx.trace_id, tctx.span_id))
                    tags = {"batch_size": len(batch), "cap": self._cap,
                            "stage": self._stage, "batch_id": batch_id}
                trace.record_finished(
                    "batch.queue_wait", tctx, start_ns=int(enq * 1e9),
                    end_ns=start_ns, start_hlc=shlc, tenant=self._obs_key,
                    tags=tags, stage=self._stage)
            trace.count("batch.emitted")
            trace.count("batch.calls", len(batch))
        try:
            if self._stage is not None:
                # a batch aggregates many callers' traces; run the
                # processing under the FIRST sampled caller's context as
                # the representative parent (and clear any stale context
                # this task inherited from whichever submit() spawned it).
                # With MORE than one sampled caller, a "batch.emit" span
                # records the others as links (multi-parent causality —
                # the single-caller common case pays nothing extra).
                with trace.activate(rep_ctx):
                    if links:
                        sp = trace.span("batch.emit",
                                        batch_size=len(batch),
                                        cap=self._cap, stage=self._stage)
                        sp.set_links(links)
                        with sp:
                            results = await self._process(calls)
                    else:
                        results = await self._process(calls)
            else:
                results = await self._process(calls)
            elapsed = self._clock() - start
            self._adapt(len(calls), elapsed, depth_at_emit,
                        waited / len(batch))
            if self._stage is not None:
                # ISSUE 8: emit occupancy for the continuous profiler
                # (the scheduler-side half of padding waste: a batch far
                # under its adaptive cap pads more downstream) — three
                # int adds, serving batchers only
                OBS.profiler.record_emit(len(calls), self._cap,
                                         depth_at_emit)
            for b, res in zip(batch, results):
                fut = b[1]
                if not fut.done():
                    fut.set_result(res)
        except Exception as e:  # noqa: BLE001 — batch failure fails all calls
            for b in batch:
                fut = b[1]
                if not fut.done():
                    fut.set_exception(e)
        finally:
            self._inflight -= 1
            self._trigger()

    def _adapt(self, batch_size: int, elapsed: float,
               depth_at_emit: int = 0, waited: float = 0.0) -> None:
        """The cap after one batch, from its run time ``elapsed``, the
        queue depth when it emitted and its calls' mean queue wait
        ``waited`` (0 for an un-staged batcher):

        - starved and saturated ⇒ double, whatever the budget says: the
          batch is too SMALL, its calls waited longer than it ran and a
          full cap more was waiting behind them;
        - overrun, and the calls waited under a quarter of the run ⇒
          halve (the ``maxBurstLatency`` guard: a shallow queue, a cost
          that grows with the batch); an overrun whose calls waited
          longer holds: halving would move their time from the batch
          into the queue;
        - saturated within budget ⇒ double toward the throughput-optimal
          cap;
        - shallow (smoothed depth under a quarter cap) ⇒ decay halfway
          toward the idle cap, so the cap tracks the LIVE queue instead
          of whatever the last burst grew it to.
        """
        self._latency.update(elapsed)
        self._depth_ema.update(depth_at_emit)
        self._wait.update(waited)
        cap = self._cap
        starved = self._wait.value > self._latency.value
        saturated = depth_at_emit >= cap
        if starved and saturated:
            cap = min(self._max_cap, cap * 2)
        elif elapsed > self._budget:
            if self._wait.value < self._latency.value / 4:
                cap = max(self._min_cap, cap // 2)
        elif saturated and self._latency.value < self._budget / 2:
            cap = min(self._max_cap, cap * 2)
        elif (self._shallow_decay
                and self._depth_ema.value < cap / 4
                and cap > self._idle_cap):
            cap = max(self._idle_cap, cap // 2)
        if cap != self._cap and self._stage is not None:
            if cap > self._cap:
                trace.count("batch.cap_grow")
            else:
                trace.count("batch.cap_shrink")
        self._cap = cap


class BatchCallScheduler(Generic[CallT, ResultT]):
    """Routes calls to per-key Batchers (≈ BatchCallScheduler.java:48).

    Batchers are created lazily per key and reaped when idle (the reference
    expires them after inactivity; here reaping happens opportunistically).
    """

    def __init__(self, process_batch_for_key: Callable[
            [Hashable], BatchFn], *, pipeline_depth: int = 2,
            max_burst_latency: float = 0.010,
            max_batch_size: int = 8192,
            stage: Optional[str] = None,
            obs_tenant_key: bool = False,
            shallow_decay: bool = True) -> None:
        self._factory = process_batch_for_key
        self._depth = pipeline_depth
        self._budget = max_burst_latency
        self._max_batch = max_batch_size
        self._stage = stage
        # ISSUE 3: EXPLICIT opt-in that this scheduler's batcher keys are
        # tenant ids (the pub scheduler) — never inferred from ``stage``,
        # so a future staged scheduler keyed by range/shard can't leak
        # bogus rows into the tenant SLO registry
        self._obs_tenant_key = obs_tenant_key
        self._shallow_decay = shallow_decay
        self._batchers: Dict[Hashable, Batcher] = {}
        self.calls_seen = 0
        if stage is not None:
            # a staged scheduler fronts the device pipeline — expose its
            # live queue depth through the "device" gauges
            OBS.device.register_scheduler(self)

    def batcher(self, key: Hashable) -> Batcher:
        b = self._batchers.get(key)
        if b is None:
            b = Batcher(self._factory(key), pipeline_depth=self._depth,
                        max_burst_latency=self._budget,
                        max_batch_size=self._max_batch,
                        stage=self._stage,
                        obs_key=str(key) if self._obs_tenant_key
                        else None,
                        shallow_decay=self._shallow_decay)
            self._batchers[key] = b
        return b

    IDLE_REAP_SECS = 30.0

    def submit(self, key: Hashable, call: CallT) -> "asyncio.Future[ResultT]":
        fut = self.batcher(key).submit(call)
        # opportunistic reaping (the reference expires batchers after
        # inactivity): retired keys — e.g. merged-away ranges — must not
        # pin their Batcher state forever
        if len(self._batchers) > 8 and (self.calls_seen % 256) == 0:
            now = time.monotonic()
            for k in [k for k, b in self._batchers.items()
                      if k != key and b.idle
                      and now - b.last_activity > self.IDLE_REAP_SECS]:
                del self._batchers[k]
        self.calls_seen += 1
        return fut

"""Async device-dispatch ring (ISSUE 6 tentpole part 1).

On the sync serving path every publish pays `batcher queue → pow2 pad →
device dispatch → BLOCKING device_get` with nothing overlapped. This
module is the overlap plane:

- a **dispatch ring** bounds the number of in-flight device batches
  (``PIPELINE_DEPTH``: 2, double-buffered): batch N+1 tokenizes and
  enqueues on device while batch N is still walking, because the await
  happens on *readiness*, not inside dispatch;
- results come back via **fetch-on-ready**: the dispatch starts a
  ``copy_to_host_async`` immediately, the serving coroutine polls
  ``jax.Array.is_ready`` (yielding the event loop between polls — other
  batches dispatch in those gaps) and only then pays the final host copy;
- the ring's occupancy is the **queue-depth signal** for adaptive batch
  shaping: an idle ring means a shallow dispatch queue, so the pow2 pad
  floor drops to ``MIN_FLOOR`` (8) to cut time-to-first-result; a busy
  ring keeps the throughput floor (``BASE_FLOOR``, 16).

- callers that wait at admission **share one device batch**: whoever is
  in line when a prep ticket frees leaves together, up to the busy-ring
  pad floor, so a batch grows only out of waiting that already happens
  (no timer, no knob; a lone caller on an idle ring leaves at once).

The ring deliberately has NO asyncio primitives bound at construction
(no Semaphore/Event): matchers outlive event loops in tests and
multi-loop processes, so waiters are plain per-call futures created on
whatever loop is running the dispatch.
"""

from __future__ import annotations

import asyncio
import contextvars
import time
from collections import deque
from typing import Deque, List, Optional

from .. import trace
from ..resilience.device import (BoundedSlots, BufferQuarantine,
                                 DeviceTimeoutError, device_deadline_s)


#: rows of the throughput pad: the widest device batch the serving path
#: warms (``TpuMatcher._warm``), the most rows callers share at the ring
#: (``DispatchRing.board``) and the most calls one pub batch hands the
#: matcher (``dist/service.py``). A wider batch is a new XLA shape class.
BASE_FLOOR = 16

#: rows of the latency pad: what a lone caller on an idle ring is padded
#: to. Each floor is one more XLA shape class to warm, so there are two.
MIN_FLOOR = 8

#: device batches in flight at once (double-buffered): the ring's slots,
#: the pub batcher's batches, and what the capacity model multiplies by.
PIPELINE_DEPTH = 2


class Caller:
    """One caller of the async device leg, from entry until its rows are
    in: what it asked for, when and in which context it entered, and the
    future its rows arrive on. ``serve`` is the matcher's coroutine that
    runs ONE device batch for a :class:`Merged` group."""

    __slots__ = ("queries", "caps", "batch", "serve", "entered", "ctx",
                 "fut", "merged")

    def __init__(self, queries, caps, batch, serve) -> None:
        self.queries = queries
        self.caps = caps            # (max_persistent_fanout, max_group_fanout)
        self.batch = batch          # an explicit pad size never merges
        self.serve = serve
        self.entered = time.monotonic()
        self.ctx = contextvars.copy_context()
        self.fut = asyncio.get_running_loop().create_future()
        self.merged: Optional[Merged] = None


class Merged:
    """The callers one device batch serves, in entry order (they board
    at the first step of ``task``, which runs it), whether the batch
    still holds its prep ticket, and the two times its callers' queue
    time is read from: ``admitted``, the instant it won its slot (or
    stopped trying), and ``tokenize_s``, the prep done inside that
    wait."""

    __slots__ = ("callers", "task", "ticket", "admitted", "tokenize_s")

    def __init__(self) -> None:
        self.callers: List[Caller] = []
        self.task: Optional[asyncio.Task] = None
        self.ticket = True
        self.admitted = 0.0
        self.tokenize_s = 0.0


class DispatchRing(BoundedSlots):
    """Bounded in-flight dispatch slots + the queue-depth signal.

    One per TpuMatcher (created lazily on the first async match). The
    gauge surface (obs/device.py) reads ``in_flight`` / ``waiters`` /
    ``depth`` weakly; ``effective_floor`` feeds the adaptive pow2 pad.
    Slot admission (bound, parked-waiter futures, cancellation hygiene)
    is the shared :class:`~bifromq_tpu.resilience.device.BoundedSlots`
    machinery — the same core that gates QoS>0 ingest.
    """

    def __init__(self, depth: int = PIPELINE_DEPTH,
                 min_floor: int = MIN_FLOOR,
                 base_floor: int = BASE_FLOOR) -> None:
        super().__init__(depth)
        self.min_floor = min_floor
        self.base_floor = base_floor
        # observability (tests assert overlap through these)
        self.dispatched_total = 0
        # ISSUE 7: timed-out slots park their orphaned result arrays here
        # until the device actually finishes with them — a reclaimed slot
        # must never let donated buffers be reused mid-flight
        self.quarantine = BufferQuarantine()
        self.timeouts_total = 0
        # ISSUE 11: stage-1 prep (tokenize + probe upload) runs BEFORE
        # ring admission for overlap, so prep tickets — not ring slots —
        # bound the probe batches resident on device. A ticket is held
        # for the whole prep + slot tenure (released WITH the slot), so
        # prepped + in-flight batches together never exceed depth + 1:
        # with the ring full, exactly ONE batch can hold an uploaded-
        # but-undispatched probe set, which is the "+1 prep-ahead" the
        # capacity model counts (obs/capacity.inflight_bytes). Without
        # the gate, K parked callers would each hold an upload the
        # model never saw.
        self._prep = BoundedSlots(self.capacity + 1)
        # callers waiting for a prep ticket, in entry order: whoever is
        # here when the next batch boards leaves with it (board)
        self._line: Deque[Caller] = deque()
        # the batch that holds a ticket and has not boarded yet
        self._boarding: Optional[Merged] = None

    # ---------------- slot management --------------------------------------

    @property
    def depth(self) -> int:
        return self.capacity

    @depth.setter
    def depth(self, v: int) -> None:
        self.capacity = v
        self._prep.capacity = max(1, v + 1)

    def enter(self, caller: Caller) -> None:
        """Put a caller in line for a prep ticket. With a ticket free and
        nobody ahead it leaves at the loop's next turn, alone or with
        whoever entered in between; else with whoever is in line when
        its turn comes. Its rows (or the batch's exception) arrive on
        ``caller.fut``."""
        self._line.append(caller)
        self._pump()

    def leave(self, caller: Caller) -> None:
        """A caller gave up (cancelled). Still in line: it just goes.
        Already part of a batch: the shared walk goes on for the others
        and is cancelled only when nobody is left to serve."""
        merged = caller.merged
        if merged is None:
            self._line.remove(caller)
        elif all(c.fut.done() for c in merged.callers):
            merged.task.cancel()

    def release_prep(self, merged: Merged) -> None:
        """Give the batch's prep ticket back (with its slot, or when the
        leg dies; once), and let the line move."""
        if merged.ticket:
            merged.ticket = False
            self._prep.release()
            self._pump()

    def _pump(self) -> None:
        """Start a batch for the head of the line if a prep ticket is
        free. It boards at its task's first step and not here, so that
        whoever enters during that turn of the loop rides along; one
        batch boards at a time."""
        if (self._line and self._boarding is None
                and self._prep.try_acquire()):
            head = self._line[0]
            merged = self._boarding = Merged()
            merged.task = asyncio.get_running_loop().create_task(
                head.serve(self, merged), context=head.ctx)
            merged.task.add_done_callback(
                lambda _t, m=merged: self._retire(m))

    def board(self, merged: Merged) -> List[Caller]:
        """The first step of a batch's task: the head of the line and,
        in entry order, every caller behind it that fits: same fan-out
        caps, no pad size of its own, and ``base_floor`` rows in all —
        the pad a busy ring gives a one-row batch anyway, so a merged
        batch runs the programs already compiled. Stops at the first
        that does not fit: nobody is overtaken."""
        self._boarding = None
        line, callers = self._line, merged.callers
        if line:
            head = line.popleft()
            callers.append(head)
            rows = len(head.queries)
            while line and head.batch is None:
                nxt = line[0]
                rows += len(nxt.queries)
                if (nxt.batch is not None or nxt.caps != head.caps
                        or rows > self.base_floor):
                    break
                callers.append(line.popleft())
            for c in callers:
                c.merged = merged
            self._pump()        # those that did not fit: the next ticket
        return callers

    def _retire(self, merged: Merged) -> None:
        # the batch's task is done, however it ended (a leg that got as
        # far as its slot gave the ticket back with it; a task cancelled
        # before its first step never boarded): no ticket and no caller
        # may hang on it
        if self._boarding is merged:
            self._boarding = None
        self.release_prep(merged)
        for c in merged.callers:
            if not c.fut.done():
                c.fut.cancel()

    @property
    def prepping(self) -> int:
        return self._prep.in_flight

    @property
    def parked(self) -> int:
        """Callers in line for a prep ticket."""
        return len(self._line)

    async def acquire(self) -> None:
        await super().acquire()
        self.dispatched_total += 1

    def release(self) -> None:
        super().release()
        # opportunistic quarantine sweep: O(1) when nothing is parked
        if len(self.quarantine):
            self.quarantine.sweep()

    def reclaim(self, res, tag: Optional[str] = None) -> None:
        """A slot timed out: park its (possibly donated-aliasing) result
        arrays in quarantine until the device reports them ready. The
        caller releases the slot itself — the ring stays bounded AND
        live, instead of one stuck dispatch wedging a slot forever.
        ``tag`` attributes the parked batch (ISSUE 15: the mesh tags the
        implicated shard)."""
        self.timeouts_total += 1
        self.quarantine.add(res, tag=tag)

    async def wait_idle(self, timeout_s: float = 2.0,
                        poll_s: float = 0.002) -> bool:
        """Graceful drain (ISSUE 7): wait bounded for every in-flight
        slot to retire. Returns False on timeout — the caller proceeds
        with shutdown/compaction anyway (in-flight coroutines release
        their slots when cancelled)."""
        deadline = time.monotonic() + timeout_s
        while self._inflight > 0:
            if time.monotonic() >= deadline:
                return False
            await asyncio.sleep(poll_s)
        return True

    # ---------------- adaptive pad floor ------------------------------------

    def effective_floor(self, *, pre_acquire: bool = False) -> int:
        """Shallow queue (nothing else in flight, nobody parked) ⇒ the
        small latency floor; any concurrency ⇒ the throughput floor.

        ONE definition for both call shapes: post-acquire (the default;
        ``in_flight`` counts this dispatch too, so <=1 is the
        idle-broker single-publish shape) and ``pre_acquire`` (ISSUE 11:
        stage-1 prep chooses the pad floor BEFORE a slot is held, where
        the same idle state reads ==0).
        """
        own = 0 if pre_acquire else 1
        if self._inflight <= own and not self._waiters:
            return self.min_floor
        return self.base_floor

    def planned_floor(self) -> int:
        """The pre-admission floor the async prep leg uses."""
        return self.effective_floor(pre_acquire=True)

    # ---------------- fetch-on-ready ----------------------------------------

    @staticmethod
    def start_fetch(res) -> None:
        """Kick the device→host copy without blocking (fetch-on-ready
        half 1); ``np.asarray`` later finds the bytes already local.
        Only the leaves ``_fetch_walk`` actually reads — ``n_routes`` is
        derivable from ``count`` and never fetched, so copying it would
        be one wasted D2H transfer per batch.
        ISSUE 19 device-expand results name their own fetch set
        (``ready_leaves``): the compact pair buffers, never the grids."""
        ready = getattr(res, "ready_leaves", None)
        leaves = ready() if ready is not None \
            else (res.start, res.count, res.overflow)
        for leaf in leaves:
            copy_async = getattr(leaf, "copy_to_host_async", None)
            if copy_async is not None:
                try:
                    copy_async()
                except Exception:  # noqa: BLE001 — backend-optional fast path
                    return

    @staticmethod
    async def wait_ready(res, poll_s: float = 0.0005,
                         spin_polls: int = 50,
                         deadline_s: Optional[float] = None,
                         fault=None) -> None:
        """Yield the event loop until every result leaf is ready (half 2).

        ``is_ready`` is a PJRT-buffer query, not a sync: other coroutines
        (the NEXT batch's tokenize + dispatch) run between polls. Backends
        whose arrays lack ``is_ready`` fall through to the blocking fetch
        the caller performs next — still correct, just unoverlapped.

        Two-phase poll: the first ``spin_polls`` misses use ``sleep(0)``
        — a bare loop yield costing microseconds, which sub-millisecond
        CPU walks finish within (a timed sleep would quantize to the
        loop's ~1ms timer and tax every fast batch) — then back off to
        ``poll_s`` timed sleeps for long completions (large batches),
        where spinning would burn a core for nothing.

        ISSUE 7 watchdog: past ``deadline_s`` (default derived from the
        dispatch-stage p99, env ``BIFROMQ_DEVICE_DEADLINE_S``) a
        :class:`DeviceTimeoutError` fires so one hung dispatch cannot
        wedge a ring slot forever. The deadline check is one monotonic
        read per poll — the sub-ms spin phase stays spin (no timed sleep
        is ever added to it). The deadline runs on OBSERVED time: a gap
        between two polls counts for at most a quarter of it, so a host
        that was frozen (the serving thread blocked, the machine stolen)
        does not charge its own absence to the device — the walk gets
        its polls after the thaw, and a device that hangs under a live
        loop still times out at ``deadline_s``. ``fault`` is a fired
        device FaultRule (models/matcher threads it from the dispatch
        hook): ``hang``
        withholds readiness while the rule stays installed, ``slow``
        withholds it for the rule's delay, ``flaky_ready`` makes each
        poll lie with the rule's probability.
        """
        if deadline_s is None:
            deadline_s = device_deadline_s()
        t0 = last = time.monotonic()
        observed = 0.0
        ready = getattr(res, "ready_leaves", None)
        leaves = list(ready()) if ready is not None \
            else [res.start, res.count, res.overflow]
        polls = 0
        injector = None
        if fault is not None:
            from ..resilience.faults import get_injector
            injector = get_injector()
        try:
            while True:
                faulted = False
                if fault is not None:
                    if fault.action == "hang":
                        faulted = injector.rule_active(fault)
                    elif fault.action == "slow":
                        faulted = time.monotonic() - t0 < fault.delay
                    elif fault.action == "flaky_ready":
                        # the documented contract is delayed-never-
                        # denied: clamp the per-poll lie below 1.0 so a
                        # rule with the default probability (1.0) stays
                        # a flake, not a hang (hang is its own action)
                        faulted = (injector.rule_active(fault)
                                   and injector.rng.random()
                                   < min(fault.probability, 0.95))
                if not faulted:
                    try:
                        if all(leaf.is_ready() for leaf in leaves):
                            return
                    except AttributeError:
                        return
                if deadline_s is not None:
                    now = time.monotonic()
                    observed += min(now - last, deadline_s / 4)
                    last = now
                    if observed >= deadline_s:
                        raise DeviceTimeoutError(deadline_s)
                await asyncio.sleep(0 if polls < spin_polls else poll_s)
                polls += 1
        finally:
            # polls that found the walk unfinished, and how many of them
            # slept (ROADMAP S6: the poll was suspected and never counted)
            trace.count("ready.polls", polls)
            if polls > spin_polls:
                trace.count("ready.sleeps", polls - spin_polls)

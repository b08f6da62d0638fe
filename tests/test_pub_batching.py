"""The pub scheduler as ``DistService`` builds it: one tenant's queued
publishes leave together, at most one warmed device batch at a time, and
each publish keeps its own result and its place in its publisher's
order."""

import asyncio
import time

from bifromq_tpu import trace
from bifromq_tpu.dist.service import DistService
from bifromq_tpu.models.oracle import MatchedRoutes, Route
from bifromq_tpu.models.pipeline import BASE_FLOOR, DispatchRing
from bifromq_tpu.plugin.events import CollectingEventCollector
from bifromq_tpu.plugin.settings import DefaultSettingProvider
from bifromq_tpu.plugin.subbroker import (DeliveryResult, ISubBroker,
                                          SubBrokerRegistry)
from bifromq_tpu.types import ClientInfo, Message, QoS, RouteMatcher
from bifromq_tpu.utils.metrics import MATCH_CACHE


class StubWorker:
    """A dist worker whose match is a fixed 10 ms round trip whatever the
    rows (the shape of a device batch): topic ``t/<k>`` has one receiver
    when k is odd and none when it is even."""

    def __init__(self) -> None:
        self.calls = []

    async def start(self) -> None:
        pass

    async def stop(self) -> None:
        pass

    async def match_batch(self, queries, *, max_persistent_fanout,
                          max_group_fanout):
        self.calls.append([t for _, t in queries])
        await asyncio.sleep(0.010)
        out = []
        for _, topic in queries:
            k = int(topic.split("/")[1])
            out.append(MatchedRoutes(normal=[Route(
                matcher=RouteMatcher.from_topic_filter(topic), broker_id=7,
                receiver_id=f"r{k}", deliverer_key="d0")] if k % 2 else []))
        return out


class RecordingSubBroker(ISubBroker):
    id = 7

    def __init__(self) -> None:
        self.delivered = []     # (publisher, message id, receiver)

    async def deliver(self, tenant_id, deliverer_key, packs):
        res = {}
        for dp in packs:
            for pmp in dp.message_pack.packs:
                for msg in pmp.messages:
                    for mi in dp.match_infos:
                        self.delivered.append((pmp.publisher.meta()["p"],
                                               msg.message_id,
                                               mi.receiver_id))
            for mi in dp.match_infos:
                res[mi] = DeliveryResult.OK
        return res


def counters():
    got = trace.TRACER.totals.between(0, time.monotonic_ns() + 10**9)
    pub = MATCH_CACHE.snapshot().get("pub", {})
    return {**{n: got.get(n, (0, 0.0))[0] for n in
               ("match.no_route", "batch.calls", "batch.emitted",
                "batch.queue_wait")},
            "hits": pub.get("hits", 0), "misses": pub.get("misses", 0)}


def test_pub_batch_is_one_warmed_device_batch():
    # the width is the ring's own constant, not a second 16
    svc = DistService(SubBrokerRegistry(), CollectingEventCollector(),
                      DefaultSettingProvider(), worker=StubWorker())
    b = svc._pub_scheduler.batcher("T")
    assert b._max_cap == b.batch_cap == BASE_FLOOR
    assert DispatchRing(depth=2).base_floor == BASE_FLOOR


async def test_64_concurrent_publishes_of_one_tenant():
    worker, sub = StubWorker(), RecordingSubBroker()
    brokers = SubBrokerRegistry()
    brokers.register(sub)
    svc = DistService(brokers, CollectingEventCollector(),
                      DefaultSettingProvider(), worker=worker)
    await svc.start()
    try:
        before = counters()
        # 64 publishes at once: 8 publishers x 8 messages over 40 topics
        # (24 of the publishes repeat a topic: in-batch dedupe first,
        # pub-cache hits once the first batches stored theirs)
        topics = [f"t/{(i * 7) % 40}" for i in range(64)]
        pubs = [ClientInfo(tenant_id="T", type="test",
                           metadata=(("p", str(p)),)) for p in range(8)]
        results = await asyncio.gather(*[
            svc.pub(pubs[i % 8], topics[i],
                    Message(message_id=i, pub_qos=QoS.AT_LEAST_ONCE,
                            payload=b"x", timestamp=0))
            for i in range(64)])
        got = {k: v - before[k] for k, v in counters().items()}
    finally:
        await svc.stop()
    routed = [int(t.split("/")[1]) % 2 == 1 for t in topics]
    # every PubResult is its own publish's
    assert [r.ok for r in results] == [True] * 64
    assert [r.fanout for r in results] == [int(x) for x in routed]
    # the worker saw at most one device batch of topics a call, each
    # topic once a call; the first two calls are the two that found a
    # free slot and left alone, the rest left together
    assert worker.calls and max(len(c) for c in worker.calls) <= BASE_FLOOR
    assert all(len(set(c)) == len(c) for c in worker.calls)
    assert [len(c) for c in worker.calls[:2]] == [1, 1]
    # the two lone batches overran with nobody waiting and halved the
    # cap twice; the queue's wait then brought it back (the parent's
    # rule ends this burst one publish at a time, in 30 calls and more)
    assert len(worker.calls) <= 12
    assert svc._pub_scheduler.batcher("T").batch_cap == BASE_FLOOR
    # fan-out in submit order, publisher by publisher and overall
    ids = [mid for _, mid, _ in sub.delivered]
    assert ids == [i for i in range(64) if routed[i]]
    for p in range(8):
        mine = [mid for who, mid, _ in sub.delivered if who == str(p)]
        assert mine == sorted(mine) and all(m % 8 == p for m in mine)
    assert all(rcv == f"r{topics[mid].split('/')[1]}"
               for _, mid, rcv in sub.delivered)
    # the books: every publish counted once at each boundary
    assert got["batch.calls"] == got["batch.queue_wait"] == 64
    assert got["batch.emitted"] == svc._pub_scheduler.batcher(
        "T").batches_emitted
    assert got["hits"] + got["misses"] == 64
    assert got["misses"] >= sum(len(c) for c in worker.calls) >= 40
    assert got["match.no_route"] == routed.count(False)

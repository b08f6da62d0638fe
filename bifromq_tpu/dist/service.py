"""Distribution service: route table + TPU match + fan-out delivery.

Re-expression of the reference's dist stack (bifromq-dist-server
DistService → dist-worker DistWorkerCoProc → bifromq-deliverer
MessageDeliverer). There is ONE route table and it lives on the replicated
KV range hosted by ``DistWorker`` (≈ DistWorkerCoProc.java:105 — "the route
table *is* the KV"):

- ``match``/``unmatch`` are RW coproc calls through consensus
  (≈ batchAddRoute:304 / batchRemoveRoute:415, incl. incarnation guards).
- ``pub`` funnels through a per-tenant adaptive Batcher (≈ PubCallScheduler →
  BatchDistServerCall) that emits device match batches served from the
  worker replica's derived TpuMatcher.
- Fan-out: shared-group member election (ordered share = rendezvous hash on
  topic, unordered = least delivery count — ≈ DeliverExecutorGroup's cached
  ordered pick; its state kept while a group's membership stands:
  ``GroupFanoutBalancer``), then delivery batched per (tenant, sub-broker,
  deliverer key) (≈ MessageDeliverer/BatchDeliveryCall.java:53) with
  NO_SUB/NO_RECEIVER results feeding route cleanup.
"""

from __future__ import annotations

import hashlib
import random
from collections import OrderedDict, defaultdict
from dataclasses import dataclass
from itertools import chain
from operator import attrgetter
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..models.matcher import TpuMatcher
from ..models.oracle import (PERSISTENT_SUB_BROKER_ID, MatchedRoutes,
                             Route)
from ..plugin.events import Event, EventType, IEventCollector
from ..plugin.settings import ISettingProvider, Setting
from ..plugin.subbroker import (DeliveryPack, DeliveryResult, ISubBroker,
                                SubBrokerRegistry)
from .. import trace
from ..scheduler.batcher import BatchCallScheduler
from ..types import (ClientInfo, MatchInfo, Message, PublisherMessagePack,
                     RouteMatcher, RouteMatcherType, TopicMessagePack)
from ..obs import OBS
from ..utils import topic as topic_util


@dataclass
class PubCall:
    publisher: ClientInfo
    topic: str
    message: Message


@dataclass
class PubResult:
    ok: bool
    fanout: int = 0
    error: str = ""


_U64 = np.uint64
_S30, _S27, _S31 = _U64(30), _U64(27), _U64(31)
_M1, _M2 = _U64(0xBF58476D1CE4E5B9), _U64(0x94D049BB133111EB)


class _Kept:
    """What the election keeps of one group while its membership stands."""
    __slots__ = ("members", "pending", "rounds", "values")

    def __init__(self) -> None:
        self.members: Tuple[Route, ...] = ()  # what the rest was built from
        self.pending: List[Route] = []  # $share: members still at the minimum
        self.rounds = 0                 # $share: that minimum (refills so far)
        self.values = None              # $oshare: one uint64 a member


def _member_value(r: Route) -> int:
    h = hashlib.blake2b(f"{r.receiver_id}|{r.deliverer_key}".encode(),
                        digest_size=8).digest()
    return int.from_bytes(h, "little")


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer over a uint64 array, in place."""
    x ^= x >> _S30
    x *= _M1
    x ^= x >> _S27
    x *= _M2
    x ^= x >> _S31
    return x


class GroupFanoutBalancer:
    """Shared-group election: exactly ONE member of a matching group a
    publish, chosen among the members it is handed (the group as
    subscribed at that moment). The same code on every leg.

    ``$share`` (:meth:`pick`): least delivery count, ties at random by
    the service rng. Every member's count is the group's minimum or one
    more (spread <= 1, where a uniform draw gives balls-into-bins skew:
    a burst of a few hundred publishes lands 2-3x the mean on one
    member). A first-seen member enters AT the minimum: it takes a fair
    share at once and is not flooded until a lifetime count caught up.
    ``$oshare`` (:meth:`pick_ordered`): rendezvous. A member's score for
    a topic is a pure function of (``receiver_id``, ``deliverer_key``,
    topic) with no per-process seed (BLAKE2b halves, a fixed 64-bit
    mixer; never ``hash()``): two services holding one membership elect
    the same member, one topic goes to one member while the membership
    stands, a join moves only the topics the joiner wins and a leave
    only the leaver's.

    State is kept per (tenant, group filter) for as long as a MEMBERSHIP
    stands, and a membership is known by identity: the patcher swaps a
    group's whole ``members`` tuple at a join or leave and the matcher
    hands that tuple on uncopied, so ``members is`` the kept one means
    nobody joined or left. Then ``$share`` draws one of the members
    still at the minimum (``pending``: a swap-remove at ``randrange``,
    refilled with the whole membership when it empties) in O(1), and
    ``$oshare`` hashes the topic once and mixes it over the members'
    kept halves. Any other object (a new membership, or a leg that
    builds a fresh list a call: overlay, multi-range union, a remote
    worker's reply, the host oracle) re-syncs ON that election, before
    it draws, in O(members): ``pending`` keeps who was pending and still
    is a member, takes in every first-seen member, drops the departed;
    the halves are hashed anew. Nothing is answered from a membership
    other than the one handed in.

    Bounded by ``max_groups`` entries (a list of at most ``len(members)``
    pointers or as many uint64, and one reference, each); past it the
    coldest entry goes, one at a time. A dropped group starts a new
    round at its next election: a balancing hint lost, never a wrong
    delivery.
    """

    def __init__(self, rng: random.Random, max_groups: int = 65536) -> None:
        self._rng = rng
        self.max_groups = max_groups
        # (tenant, filter) -> kept state, coldest first
        self._kept: "OrderedDict[Tuple[str, str], _Kept]" = OrderedDict()
        # since the last drain(): elections answered from kept state,
        # that re-synced it, that found none (a group's first, or its
        # first since it was dropped), and the members the last two scanned
        self._tally = [0, 0, 0, 0]

    def _entry(self, tenant_id: str, mqtt_filter: str) -> _Kept:
        key = (tenant_id, mqtt_filter)
        e = self._kept.get(key)
        if e is None:
            e = self._kept[key] = _Kept()
            if len(self._kept) > self.max_groups:
                self._kept.popitem(last=False)
        else:
            self._kept.move_to_end(key)
        return e

    def _renew(self, e: _Kept, members) -> None:
        """``e`` was just rebuilt from ``members``. A list can change
        under its identity: a copy is kept of it, which no later call
        ``is``, so a leg that hands lists re-syncs every time."""
        self._tally[1 if e.members else 2] += 1
        self._tally[3] += len(members)
        e.members = members if type(members) is tuple else tuple(members)

    def pick(self, tenant_id: str, mqtt_filter: str, members) -> Route:
        e = self._entry(tenant_id, mqtt_filter)
        if members is e.members:
            self._tally[0] += 1
        else:
            served = ({r.receiver_url for r in e.members}
                      - {r.receiver_url for r in e.pending})
            e.pending = ([r for r in members if r.receiver_url not in served]
                         if served else list(members))
            self._renew(e, members)
        pending = e.pending
        if not pending:
            # everyone stands one above the old minimum: the next round
            pending.extend(members)
            e.rounds += 1
        i = self._rng.randrange(len(pending))
        elected = pending[i]
        last = pending.pop()
        if i < len(pending):
            pending[i] = last
        return elected

    def pick_ordered(self, tenant_id: str, mqtt_filter: str, members,
                     topic: str) -> Route:
        e = self._entry(tenant_id, mqtt_filter)
        if members is e.members:
            self._tally[0] += 1
        else:
            e.values = np.fromiter(map(_member_value, members), _U64,
                                   len(members))
            self._renew(e, members)
        t = hashlib.blake2b(topic.encode(), digest_size=8).digest()
        scores = _mix64(e.values ^ _U64(int.from_bytes(t, "little")))
        return members[int(scores.argmax())]

    def drain(self) -> Tuple[int, int, int, int]:
        """(kept, re-synced, first, members scanned) since the last
        call."""
        out = tuple(self._tally)
        self._tally = [0, 0, 0, 0]
        return out

    def spread(self, tenant_id: str, mqtt_filter: str) -> dict:
        """Per-group balance introspection (the fairness tests read
        it): the counts the kept state stands for."""
        e = self._kept.get((tenant_id, mqtt_filter))
        if e is None or e.values is not None:
            return {"members": 0, "max": 0, "min": 0}
        n, waiting = len(e.members), len(e.pending)
        lo = e.rounds if waiting else e.rounds + 1
        return {"members": n, "min": lo, "max": lo + (0 < waiting < n)}


_MATCH_INFO = attrgetter("match_info")


def _match_infos(routes) -> Tuple[MatchInfo, ...]:
    """Each route's own ``MatchInfo``; those built here, on a route's
    first delivery, are booked."""
    built = Route.match_info.built
    infos = tuple(map(_MATCH_INFO, routes))
    if Route.match_info.built != built:
        trace.count("deliver.match_info.built",
                    Route.match_info.built - built)
    return infos


def _by_call(routes) -> Dict[Tuple[int, str], List[Route]]:
    """``routes`` per (broker, deliverer key) ≈ BatchDeliveryCall
    grouping: calls in order of first appearance, routes in their own
    order within a call."""
    by: Dict[Tuple[int, str], List[Route]] = defaultdict(list)
    for r in routes:
        by[r.broker_id, r.deliverer_key].append(r)
    return by


class _FanoutPlan:
    """One target set as its sub-broker calls: ``match_infos`` holds every
    route's in call order and ``calls`` is one ``(broker_id,
    deliverer_key, start, end)`` a call into it.

    Flat and small on purpose: a plan is kept beside every cached match
    result, and whatever it holds of objects, and of pointers to objects,
    every whole-heap collection walks. The routes in call order are needed
    only where a call's results are not all ``OK`` and are grouped again
    from ``source`` there."""
    __slots__ = ("source", "match_infos", "calls", "n_persistent",
                 "_routes")

    def __init__(self, source: List[Route], by_infos=None) -> None:
        if by_infos is None:
            by = _by_call(source)
            self.match_infos = _match_infos(chain.from_iterable(by.values()))
        else:       # call key -> match infos, from ``joined``
            by = by_infos
            self.match_infos = tuple(chain.from_iterable(by.values()))
        self.source = source
        self._routes: Optional[List[Route]] = None
        self.calls: List[Tuple[int, str, int, int]] = []
        start = self.n_persistent = 0
        for (broker_id, dkey), members in by.items():
            end = start + len(members)
            self.calls.append((broker_id, dkey, start, end))
            if broker_id == PERSISTENT_SUB_BROKER_ID:
                self.n_persistent += end - start
            start = end

    def joined(self, extra: List[Route]) -> "_FanoutPlan":
        """A new plan of ``source + extra``: each extra route at the end
        of its call, new calls last. This plan stays as it is."""
        by = {(b, d): list(self.match_infos[s:e])
              for b, d, s, e in self.calls}
        for r, mi in zip(extra, _match_infos(extra)):
            by.setdefault((r.broker_id, r.deliverer_key), []).append(mi)
        return _FanoutPlan(self.source + extra, by)

    def routes(self, start: int, end: int) -> List[Route]:
        if self._routes is None:
            self._routes = list(chain.from_iterable(
                _by_call(self.source).values()))
        return self._routes[start:end]


class DistService:
    def __init__(self, sub_brokers: SubBrokerRegistry,
                 event_collector: IEventCollector,
                 setting_provider: ISettingProvider, *,
                 worker=None,
                 max_burst_latency: float = 0.005,
                 rng_seed: Optional[int] = None) -> None:
        self.sub_brokers = sub_brokers
        self.events = event_collector
        self.settings = setting_provider
        if worker is None:
            from .worker import DistWorker
            worker = DistWorker()
        self.worker = worker
        # degradation surface (ISSUE 1): a local worker's host-oracle
        # fallback reports MATCH_DEGRADED through the event stream (the
        # remote worker meters in its own process)
        if hasattr(worker, "on_degraded"):
            worker.on_degraded = self._on_match_degraded
        # cross-broker delivery plane (clustered frontends): set by the
        # starter — registry resolving mqtt-deliverer:{server_id} + this
        # node's own server id (local keys skip the hop)
        self.deliverer_registry = None
        self.server_id = ""
        self._rng = random.Random(rng_seed)
        # ISSUE 13: unordered-$share election balances on per-member
        # delivery counts instead of uniform random (ordered share keeps
        # the stateless rendezvous pick — its contract is stability)
        self.group_balancer = GroupFanoutBalancer(self._rng)
        # pub-side match cache (ISSUE 4: the shared TenantMatchCache, ≈
        # SubscriptionCache/TenantRouteCache.java:65): matched routes per
        # (tenant, topic) with filter-aware invalidation. The TTL bounds
        # staleness from mutations applied on OTHER nodes when the worker
        # is remote; with a local worker the coproc apply-stream hook
        # below makes invalidation exact (replayed mutations included).
        from ..models.matchcache import TenantMatchCache
        self._match_cache = TenantMatchCache(
            scope="pub", ttl_s=self._MATCH_CACHE_TTL_DEFAULT,
            max_topics_per_tenant=self.MATCH_CACHE_MAX,
            max_entries=self.MATCH_CACHE_MAX)   # same TOTAL bound as the
        # hand-rolled predecessor: TTL expiry is lazy, the bound is the
        # memory wall
        if hasattr(worker, "on_route_mutation"):
            worker.on_route_mutation = self._on_route_mutation
        # ISSUE 12: a REMOTE worker has no local apply stream — the
        # exact-invalidation puller (armed in start()) replaces the TTL
        # wait with per-mutation evictions carried on the delta stream
        self._inval_puller = None
        # ISSUE 12 satellite: the pub cache's hot (tenant, topic) key set
        # rides the PR 5 gossip digest so a failover target pre-warms
        # before taking traffic
        OBS.register_pub_cache(self._match_cache)
        # a pub batch hands the matcher at most one warmed device batch:
        # 17 unique topics would pad to 32 rows, a shape nothing warms,
        # and compile on the serving path
        from ..models.pipeline import BASE_FLOOR, PIPELINE_DEPTH
        self._pub_scheduler: BatchCallScheduler[PubCall, PubResult] = \
            BatchCallScheduler(lambda tenant: self._make_pub_batch(tenant),
                               pipeline_depth=PIPELINE_DEPTH,
                               max_burst_latency=max_burst_latency,
                               max_batch_size=BASE_FLOOR,
                               stage="queue_wait",
                               obs_tenant_key=True)

    @property
    def matcher(self) -> TpuMatcher:
        """This replica's derived matcher (introspection/metrics only —
        mutations MUST go through match/unmatch so they ride consensus)."""
        return self.worker.matcher

    async def start(self) -> None:
        await self.worker.start()
        # ISSUE 12: exact invalidation for the remote-worker deployment —
        # evictions arrive on the delta stream within one RTT; the TTL
        # stays only as the backstop for stream loss
        from ..utils.env import env_bool
        if (self._inval_puller is None
                and not hasattr(self.worker, "on_route_mutation")
                and getattr(self.worker, "registry", None) is not None
                and env_bool("BIFROMQ_REPL_INVAL", True)):
            from ..replication.standby import InvalidationPuller
            self._inval_puller = InvalidationPuller(
                self.worker.registry, self._on_route_mutation,
                service=getattr(self.worker, "service", "dist-worker"))
            await self._inval_puller.start()
        from ..utils.sysprops import SysProp, get
        interval = get(SysProp.DIST_GC_INTERVAL_SECONDS)
        if interval and interval > 0:
            import asyncio

            async def loop():
                while True:
                    await asyncio.sleep(interval)
                    try:
                        await self.gc_sweep()
                    except Exception:  # noqa: BLE001
                        import logging
                        logging.getLogger(__name__).exception("dist gc")
            self._gc_task = asyncio.create_task(loop())

    async def stop(self) -> None:
        task = getattr(self, "_gc_task", None)
        if task is not None:
            task.cancel()
            self._gc_task = None
        if self._inval_puller is not None:
            await self._inval_puller.stop()
            self._inval_puller = None
        await self.worker.stop()

    async def gc_sweep(self) -> int:
        """Periodic dead-route sweep (≈ DistWorkerCoProc.gc:554 +
        SubscriptionCleaner): every stored route is checked against its
        sub-broker's checkSubscriptions; routes whose receiver no longer
        holds the subscription are removed through consensus."""
        if not hasattr(self.worker, "_iter_all_routes"):
            # remote worker: the sweep must run in the worker process (it
            # owns the keyspace); the frontend has nothing to scan
            return 0
        # batch checks per (broker, tenant) — the ISubBroker SPI is batched
        # exactly for this (≈ SubscriptionCleaner batching)
        groups: Dict[Tuple[int, str], List[Route]] = {}
        for tenant_id, route in self.worker._iter_all_routes():
            if self.sub_brokers.has(route.broker_id):
                groups.setdefault((route.broker_id, tenant_id),
                                  []).append(route)
        removed = 0
        for (broker_id, tenant_id), routes in groups.items():
            broker = self.sub_brokers.get(broker_id)
            mis = [r.match_info for r in routes]
            try:
                alive = await broker.check_subscriptions(tenant_id, mis)
            except Exception:  # noqa: BLE001
                continue
            for r, ok in zip(routes, alive):
                if not ok:
                    await self.worker.remove_route(
                        tenant_id, r.matcher, r.receiver_url, r.incarnation)
                    self._match_cache.invalidate(tenant_id,
                                                 r.matcher.filter_levels)
                    removed += 1
        return removed

    # ---------------- route mutations (≈ batchAddRoute/batchRemoveRoute) ---

    async def match(self, tenant_id: str, matcher: RouteMatcher,
                    broker_id: int, receiver_id: str, deliverer_key: str,
                    incarnation: int = 0) -> bool:
        route = Route(matcher=matcher, broker_id=broker_id,
                      receiver_id=receiver_id, deliverer_key=deliverer_key,
                      incarnation=incarnation)
        with trace.span("sub.dist", tenant=tenant_id):
            try:
                out = await self.worker.add_route(tenant_id, route)
            except Exception:  # noqa: BLE001 — consensus/transport failure
                self.events.report(Event(EventType.MATCH_ERROR, tenant_id,
                                         {"filter":
                                          matcher.mqtt_topic_filter}))
                raise
            ok = out in ("ok", "exists")
            if ok:
                # filter-aware (ISSUE 4): an exact filter evicts one topic
                # key, a wildcard bumps the tenant epoch
                self._match_cache.invalidate(tenant_id,
                                             matcher.filter_levels)
        self.events.report(Event(
            EventType.MATCHED if ok else EventType.MATCH_ERROR, tenant_id,
            {"filter": matcher.mqtt_topic_filter}
            | ({} if ok else {"reason": out})))
        return ok

    async def unmatch(self, tenant_id: str, matcher: RouteMatcher,
                      broker_id: int, receiver_id: str, deliverer_key: str,
                      incarnation: int = 0) -> bool:
        try:
            out = await self.worker.remove_route(
                tenant_id, matcher, (broker_id, receiver_id, deliverer_key),
                incarnation)
        except Exception:  # noqa: BLE001
            self.events.report(Event(EventType.UNMATCH_ERROR, tenant_id,
                                     {"filter":
                                      matcher.mqtt_topic_filter}))
            raise
        ok = out == "ok"
        if ok:
            self._match_cache.invalidate(tenant_id, matcher.filter_levels)
        self.events.report(Event(
            EventType.UNMATCHED if ok else EventType.UNMATCH_ERROR,
            tenant_id, {"filter": matcher.mqtt_topic_filter}
            | ({} if ok else {"reason": out})))
        return ok

    # ---------------- publish path -----------------------------------------

    async def pub(self, publisher: ClientInfo, topic: str,
                  message: Message) -> PubResult:
        call = PubCall(publisher=publisher, topic=topic, message=message)
        return await self._pub_scheduler.submit(publisher.tenant_id, call)

    # pub-side match cache knobs (see __init__): the TTL bounds staleness
    # from mutations made on OTHER nodes, the reference's refresh window
    _MATCH_CACHE_TTL_DEFAULT = 1.0
    MATCH_CACHE_MAX = 8192

    @property
    def MATCH_CACHE_TTL(self) -> float:
        return self._match_cache.ttl_s

    @MATCH_CACHE_TTL.setter
    def MATCH_CACHE_TTL(self, value: float) -> None:
        # a runtime knob, not a constructor snapshot: tests/operators set
        # it on a live service (chaos suite pins 0.0 so every publish
        # exercises the fabric)
        self._match_cache.ttl_s = value

    def _on_route_mutation(self, tenant_id, filter_levels) -> None:
        """Apply-stream invalidation (ISSUE 4): fires for every route
        mutation the local worker's coprocs apply — including mutations
        REPLAYED from raft peers that never passed through this service's
        match/unmatch — keeping the pub cache filter-aware-fresh without
        waiting out the TTL."""
        if tenant_id is None:
            self._match_cache.bump_all()
        else:
            self._match_cache.invalidate(tenant_id, filter_levels)

    def _make_pub_batch(self, tenant_id: str):
        async def process(calls: Sequence[PubCall]) -> List[PubResult]:
            mpf = self.settings.provide(
                Setting.MaxPersistentFanout, tenant_id)
            if mpf is None:
                mpf = Setting.MaxPersistentFanout.default
            mgf = self.settings.provide(Setting.MaxGroupFanout, tenant_id)
            if mgf is None:
                mgf = Setting.MaxGroupFanout.default
            caps = (mpf, mgf)
            matched: List[Optional[MatchedRoutes]] = []
            miss_topics: List[str] = []     # deduped (hot-topic bursts
            miss_pos: Dict[str, int] = {}   # must not fan into N queries)
            n_miss_calls = 0
            for qi, c in enumerate(calls):
                m = self._match_cache.get(tenant_id, c.topic, caps)
                matched.append(m)
                if m is None:
                    n_miss_calls += 1
                    if c.topic not in miss_pos:
                        miss_pos[c.topic] = len(miss_topics)
                        miss_topics.append(c.topic)
            OBS.record_match_cache(tenant_id, len(calls) - n_miss_calls,
                                   n_miss_calls)
            # global section totals: one locked inc per pub batch
            from ..utils.metrics import MATCH_CACHE
            MATCH_CACHE.inc("pub", "hits", len(calls) - n_miss_calls)
            MATCH_CACHE.inc("pub", "misses", n_miss_calls)
            if miss_topics:
                # snapshot BEFORE the (awaited) match: a mutation landing
                # mid-flight must make the stored entry instantly stale
                token = self._match_cache.token(tenant_id)
                try:
                    fresh = await self._match_missing(
                        tenant_id, miss_topics, mpf, mgf)
                except Exception:  # noqa: BLE001 — match backend failure
                    # ≈ DistError event + failed PubResults (caller acks
                    # the client with an error / QoS0 drops)
                    self.events.report(Event(
                        EventType.DIST_ERROR, tenant_id,
                        {"topics": len(miss_topics)}))
                    raise
                for t, m in zip(miss_topics, fresh):
                    self._match_cache.put(tenant_id, t, caps, m, token)
                for qi, c in enumerate(calls):
                    if matched[qi] is None:
                        matched[qi] = fresh[miss_pos[c.topic]]
            results: List[PubResult] = []
            trace.count("match.no_route", sum(
                1 for m in matched if not m.normal and not m.groups))
            for call, m in zip(calls, matched):
                fanout = await self._fan_out(tenant_id, call, m)
                results.append(PubResult(ok=True, fanout=fanout))
                if fanout:
                    # ≈ Disted event (dist call accepted + fanned out)
                    self.events.report(Event(
                        EventType.DISTED, tenant_id,
                        {"topic": topic_util.to_str(call.topic),
                         "fanout": fanout}))
            return results
        return process

    # match-path deadline budget (ISSUE 1): caps every RPC hop to a
    # remote worker (per-attempt timeout + retries) and gates the local
    # device walk at each range's dispatch boundary — an exhausted budget
    # degrades to the host oracle instead of failing the publish. (An
    # in-flight device call is not preempted; only remote hops carry a
    # hard per-attempt timeout.)
    MATCH_DEADLINE_S = 5.0

    def _on_match_degraded(self, n_queries: int, reason: str) -> None:
        self.events.report(Event(EventType.MATCH_DEGRADED, "-",
                                 {"queries": n_queries,
                                  "reason": reason}))

    async def _match_missing(self, tenant_id, miss_topics, mpf, mgf):
        from ..resilience.policy import deadline_scope
        with deadline_scope(self.MATCH_DEADLINE_S):
            # caps arrive pre-resolved (they are also the cache key dims).
            # ISSUE 11 byte plane: raw topic STRINGS flow to the matcher,
            # which packs one contiguous byte buffer per batch — no
            # per-topic parse/list materialization on the publish path;
            # levels appear only on the matcher's rare fallback legs.
            return await self.worker.match_batch(
                [(tenant_id, t) for t in miss_topics],
                max_persistent_fanout=mpf, max_group_fanout=mgf)

    async def _fan_out(self, tenant_id: str, call: PubCall,
                       matched: MatchedRoutes) -> int:
        """Span-wrapped fan-out (ISSUE 2): one "deliver.fanout" span per
        publish with the achieved fan-out; its exit feeds the "deliver"
        stage histogram and the tenant's window either way."""
        fanout = 0
        # ISSUE 12 byte plane: wire-bytes topics decode ONCE here, at the
        # delivery boundary — the match path upstream never did
        topic_s = topic_util.to_str(call.topic)
        try:
            with trace.span("deliver.fanout", tenant=tenant_id,
                            topic=topic_s) as sp:
                fanout = await self._fan_out_inner(tenant_id, call, matched,
                                                   topic_s)
                sp.set_tag("fanout", fanout)
                return fanout
        finally:
            # ISSUE 3: the achieved fan-out feeds the tenant's SLO window
            # (fan-out share is the detector's first signal)
            OBS.record_fanout(tenant_id, fanout)

    async def _fan_out_inner(self, tenant_id: str, call: PubCall,
                             matched: MatchedRoutes,
                             topic_s: str) -> int:
        """group -> per (broker, deliverer key): one call, its results
        settled at once. The grouping and each call are boundaries of
        their own (the calls are the finest grain timed, never one span
        per route); what is left of ``deliver.fanout`` is the fan-out's
        own time: results read back. No object is built per route: a
        route's ``MatchInfo`` lives on the route, and a call whose
        results are all ``OK`` is settled by counting them."""
        with trace.span("deliver.group"):
            pack, plan = self._group_targets(tenant_id, call, matched,
                                             topic_s)
        if plan is None:
            return 0
        fanout = n_routes = 0
        # cross-broker delivery (≈ mqtt-broker-client deliver RPC): a
        # deliverer key owned by ANOTHER server makes one RPC hop to that
        # broker node, whose local sub-brokers finish it
        remote = self.deliverer_registry is not None and self.server_id
        ok = DeliveryResult.OK
        for broker_id, dkey, start, end in plan.calls:
            n_routes += end - start
            match_infos = plan.match_infos[start:end]
            owner = None
            if remote:
                from .deliverer import server_of
                owner = server_of(dkey)
            try:
                if owner and owner != self.server_id:
                    from .deliverer import remote_deliver
                    with trace.span("deliver.call"):
                        res = await remote_deliver(
                            self.deliverer_registry, owner, tenant_id,
                            broker_id, dkey, pack, match_infos)
                elif not self.sub_brokers.has(broker_id):
                    continue
                else:
                    broker = self.sub_brokers.get(broker_id)
                    dp = DeliveryPack(message_pack=pack,
                                      match_infos=match_infos)
                    with trace.span("deliver.call"):
                        res = await broker.deliver(tenant_id, dkey, [dp])
            except Exception as e:  # noqa: BLE001
                self.events.report(Event(EventType.DELIVER_ERROR,
                                         tenant_id, {"error": repr(e)}))
                OBS.record_delivery_violation(tenant_id, 0,
                                              "deliver_error")
                continue
            # a result missing from the reply reads None: an ERROR,
            # neither counted nor reaped
            outcomes = list(map(res.get, match_infos))
            n_ok = outcomes.count(ok)
            fanout += n_ok
            if n_ok == len(outcomes):
                continue
            trace.count("deliver.settle.slow")
            for route, outcome in zip(plan.routes(start, end), outcomes):
                if outcome in (DeliveryResult.NO_SUB,
                               DeliveryResult.NO_RECEIVER):
                    # dead route cleanup (≈ BatchDeliveryCall NO_SUB handling)
                    await self.worker.remove_route(
                        tenant_id, route.matcher, route.receiver_url,
                        route.incarnation)
                    self._match_cache.invalidate(
                        tenant_id, route.matcher.filter_levels)
        trace.count("deliver.routes", n_routes)
        return fanout

    def _group_targets(self, tenant_id: str, call: PubCall,
                       matched: MatchedRoutes, topic_s: str):
        """Election, byte cap and grouping: the message pack and the
        plan of the sub-broker calls (``None``, ``None`` with nobody to
        deliver to). Never yields to the loop.

        The plan of ``matched.normal`` is kept on ``matched`` and reused
        while ``normal`` is still the very list it was built from
        (whoever REASSIGNS ``normal`` gets a new plan); elected group
        members and the byte cap are applied per publish, beside it."""
        if matched.max_persistent_fanout_exceeded:
            self.events.report(Event(EventType.PERSISTENT_FANOUT_THROTTLED,
                                     tenant_id, {"topic": topic_s}))
        if matched.max_group_fanout_exceeded:
            self.events.report(Event(EventType.GROUP_FANOUT_THROTTLED,
                                     tenant_id, {"topic": topic_s}))
        normal = matched.normal
        if not normal and not matched.groups:
            return None, None
        plan = matched.fanout_plan
        if (plan is not None and plan.source is normal
                and len(plan.match_infos) == len(normal)):
            trace.count("deliver.plan.reused")
        else:
            plan = matched.fanout_plan = _FanoutPlan(normal)
            trace.count("deliver.plan.built")
        n_persistent = plan.n_persistent
        elected: List[Route] = []
        for mqtt_filter, members in matched.groups.items():
            member = self._elect(tenant_id, mqtt_filter, members, topic_s)
            if member is not None:
                elected.append(member)
                if member.broker_id == PERSISTENT_SUB_BROKER_ID:
                    n_persistent += 1
        if matched.groups:
            kept, resynced, first, scanned = self.group_balancer.drain()
            if kept:
                trace.count("share.elect.kept", kept)
            if resynced:
                trace.count("share.elect.resync", resynced)
            if first:
                trace.count("share.elect.first", first)
            if resynced or first:
                trace.count("share.elect.scanned", scanned)
        # byte-based persistent fan-out cap (≈ MaxPersistentFanoutBytes in
        # DeliverExecutorGroup.java:132), applied over the FULL target set
        # (normal + elected shared-group members — an elected persistent
        # member consumes budget too); transient receivers are untouched
        max_pf_bytes = self.settings.provide(
            Setting.MaxPersistentFanoutBytes, tenant_id)
        if max_pf_bytes is None:
            max_pf_bytes = Setting.MaxPersistentFanoutBytes.default
        payload_len = len(call.message.payload)
        if payload_len and n_persistent * payload_len > max_pf_bytes:
            allowed = int(max_pf_bytes // payload_len)
            kept: List[Route] = []
            used = 0
            for r in chain(normal, elected):
                if r.broker_id != PERSISTENT_SUB_BROKER_ID:
                    kept.append(r)
                elif used < allowed:
                    kept.append(r)
                    used += 1
            plan = _FanoutPlan(kept)
            self.events.report(Event(
                EventType.PERSISTENT_FANOUT_BYTES_THROTTLED, tenant_id,
                {"topic": topic_s, "allowed": allowed}))
        elif elected:
            plan = plan.joined(elected)
        if not plan.calls:
            return None, None
        pack = TopicMessagePack(
            topic=topic_s,
            packs=(PublisherMessagePack(publisher=call.publisher,
                                        messages=(call.message,)),))
        return pack, plan

    def _elect(self, tenant_id: str, mqtt_filter: str,
               members: Sequence[Route], topic: str) -> Optional[Route]:
        """Shared-group member election (≈ DeliverExecutorGroup): one of
        ``members``, by the rules :class:`GroupFanoutBalancer` states
        (ordered share: rendezvous over (member, topic); unordered:
        least delivery count). ``members`` is read, never changed."""
        if not members:
            return None
        if members[0].matcher.type is RouteMatcherType.ORDERED_SHARE:
            return self.group_balancer.pick_ordered(
                tenant_id, mqtt_filter, members, topic)
        return self.group_balancer.pick(tenant_id, mqtt_filter, members)

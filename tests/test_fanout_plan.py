"""The fan-out as ``DistService`` runs it since a route keeps its own
``MatchInfo`` and group key: against a recording sub-broker, the same
calls, the same match infos a call and the same fan-out count as the plain
per-route logic it replaced, which this file keeps as its reference."""

import pickle
import time

import pytest

from bifromq_tpu import trace
from bifromq_tpu.dist import deliverer
from bifromq_tpu.dist.service import DistService, PubCall
from bifromq_tpu.models.oracle import (PERSISTENT_SUB_BROKER_ID,
                                       MatchedRoutes, Route,
                                       SubscriptionTrie)
from bifromq_tpu.plugin.events import CollectingEventCollector, EventType
from bifromq_tpu.plugin.settings import DefaultSettingProvider, Setting
from bifromq_tpu.plugin.subbroker import (DeliveryResult, ISubBroker,
                                          SubBrokerRegistry)
from bifromq_tpu.types import (ClientInfo, MatchInfo, Message, QoS,
                               RouteMatcher, TopicMessagePack)

TENANT = "T"
OK, NO_SUB, NO_RECEIVER, ERROR = (DeliveryResult.OK, DeliveryResult.NO_SUB,
                                  DeliveryResult.NO_RECEIVER,
                                  DeliveryResult.ERROR)
COUNTERS = ("deliver.routes", "deliver.match_info.built",
            "deliver.plan.built", "deliver.plan.reused",
            "deliver.settle.slow")


class RecordingWorker:
    def __init__(self) -> None:
        self.removed = []       # (filter, receiver_url, incarnation)

    async def start(self) -> None:
        pass

    async def stop(self) -> None:
        pass

    async def remove_route(self, tenant_id, matcher, receiver_url,
                           incarnation):
        self.removed.append((matcher.mqtt_topic_filter, receiver_url,
                             incarnation))
        return "ok"

    def _iter_all_routes(self):
        return iter(self.stored)


class ScriptedSubBroker(ISubBroker):
    """Records every call; answers ``OK`` unless the script says otherwise
    for a receiver: a ``DeliveryResult``, ``"missing"`` (no key in the
    reply) or, for a deliverer key, ``"raise"``."""

    def __init__(self, broker_id: int, script=None) -> None:
        self.id = broker_id
        self.script = script or {}
        self.calls = []         # (deliverer key, topic, match infos)
        self.checked = []

    async def deliver(self, tenant_id, deliverer_key, packs):
        out = {}
        for dp in packs:
            self.calls.append((deliverer_key, dp.message_pack.topic,
                               dp.match_infos))
            if self.script.get(deliverer_key) == "raise":
                raise RuntimeError("sub-broker down")
            for mi in dp.match_infos:
                answer = self.script.get(mi.receiver_id, OK)
                if answer != "missing":
                    out[mi] = answer
        return out

    async def check_subscriptions(self, tenant_id, match_infos):
        self.checked.extend(match_infos)
        return [self.script.get(mi.receiver_id, OK) == OK
                for mi in match_infos]


def route(i: int, *, broker: int = 7, flt: str = None, inc: int = 0,
          dkeys: int = 4) -> Route:
    return Route(matcher=RouteMatcher.from_topic_filter(flt or f"a/{i}/#"),
                 broker_id=broker, receiver_id=f"r{i}",
                 deliverer_key=f"d{i % dkeys}", incarnation=inc)


def service(script=None, overrides=None):
    brokers = SubBrokerRegistry()
    subs = {b: ScriptedSubBroker(b, script)
            for b in (7, PERSISTENT_SUB_BROKER_ID)}
    for sub in subs.values():
        brokers.register(sub)
    events = CollectingEventCollector()
    svc = DistService(brokers, events,
                      DefaultSettingProvider({TENANT: overrides or {}}),
                      worker=RecordingWorker())
    return svc, subs, events


def call(payload: bytes = b"x" * 8, topic: str = "a/b") -> PubCall:
    return PubCall(publisher=ClientInfo(tenant_id=TENANT), topic=topic,
                   message=Message(message_id=1, pub_qos=QoS.AT_LEAST_ONCE,
                                   payload=payload, timestamp=0))


def counters() -> dict:
    got = trace.TRACER.totals.between(0, time.monotonic_ns() + 10**9)
    return {n: got.get(n, (0, 0.0))[0] for n in COUNTERS}


def counted(before: dict) -> dict:
    return {n: v - before[n] for n, v in counters().items()}


def plain_fan_out(targets, script, registered=(7, PERSISTENT_SUB_BROKER_ID)):
    """The per-route logic the fan-out had: group by a fresh key tuple,
    a fresh ``MatchInfo`` a route, every result looked up and compared.
    Returns (calls, fan-out, removed) for ``targets`` in their order."""
    by = {}
    for r in targets:
        by.setdefault((r.broker_id, r.deliverer_key), []).append(r)
    calls, fanout, removed = [], 0, []
    for (broker_id, dkey), routes in by.items():
        if broker_id not in registered:
            continue
        infos = tuple(MatchInfo(matcher=r.matcher, receiver_id=r.receiver_id,
                                incarnation=r.incarnation) for r in routes)
        calls.append((broker_id, dkey, infos))
        if script.get(dkey) == "raise":
            continue
        for r in routes:
            outcome = script.get(r.receiver_id, OK)
            if outcome == "missing":
                outcome = ERROR
            if outcome == OK:
                fanout += 1
            elif outcome in (NO_SUB, NO_RECEIVER):
                removed.append((r.matcher.mqtt_topic_filter, r.receiver_url,
                                r.incarnation))
    return calls, fanout, removed


def calls_of(subs) -> list:
    """Every recorded call as (broker id, deliverer key, match infos), in
    the order made (each broker records its own; a publish's calls to one
    broker keep their order, which is what the grouping fixes)."""
    return [(b, dkey, infos) for b, sub in subs.items()
            for dkey, _topic, infos in sub.calls]


def by_broker(calls) -> dict:
    out = {}
    for b, dkey, infos in calls:
        out.setdefault(b, []).append((dkey, infos))
    return out


RESULT_CASES = {
    "all_ok": {},
    "no_sub_and_no_receiver_mid_group": {"r5": NO_SUB, "r9": NO_RECEIVER},
    "missing_key_is_an_error": {"r6": "missing"},
    "explicit_error": {"r2": ERROR},
    "sub_broker_raises": {"d1": "raise"},
    "raise_beside_a_dead_route": {"d2": "raise", "r7": NO_SUB},
}


@pytest.mark.parametrize("case", sorted(RESULT_CASES))
async def test_results_settle_like_the_plain_logic(case):
    script = RESULT_CASES[case]
    svc, subs, events = service(script)
    normal = [route(i) for i in range(16)]
    want_calls, want_fanout, want_removed = plain_fan_out(normal, script)
    before = counters()
    invalidated = []
    svc._match_cache.invalidate = lambda t, levels: invalidated.append(levels)
    fanout = await svc._fan_out(TENANT, call(), MatchedRoutes(normal=normal))
    got = counted(before)
    assert fanout == want_fanout
    assert calls_of(subs) == want_calls
    assert all(topic == "a/b" for _d, topic, _i in subs[7].calls)
    # exactly the dead routes are reaped and invalidated, in route order
    assert svc.worker.removed == want_removed
    by_filter = {r.matcher.mqtt_topic_filter: r for r in normal}
    assert invalidated == [by_filter[f].matcher.filter_levels
                           for f, _u, _i in want_removed]
    raised = [d for d, v in script.items() if v == "raise"]
    assert len(events.of(EventType.DELIVER_ERROR)) == len(raised)
    # the books: every route handed over is counted, raised call or not;
    # only a call with something other than OK takes the per-route loop
    assert got["deliver.routes"] == len(normal)
    assert got["deliver.match_info.built"] == len(normal)
    slow = {r.deliverer_key for r in normal
            if script.get(r.receiver_id, OK) != OK
            and script.get(r.deliverer_key) != "raise"}
    assert got["deliver.settle.slow"] == len(slow)
    assert (got["deliver.plan.built"], got["deliver.plan.reused"]) == (1, 0)


async def test_unregistered_broker_is_skipped_not_counted():
    svc, subs, _ = service()
    normal = [route(0), route(1, broker=9), route(2)]
    fanout = await svc._fan_out(TENANT, call(), MatchedRoutes(normal=normal))
    want_calls, want_fanout, _ = plain_fan_out(normal, {})
    assert fanout == want_fanout == 2
    assert calls_of(subs) == want_calls


@pytest.mark.parametrize("kind", ["$oshare", "$share"])
async def test_shared_groups_are_elected_every_publish(kind):
    svc, subs, _ = service()
    normal = [route(i) for i in range(8)]
    flt = f"{kind}/g/a/#"
    # member 20 rides d0, which normal routes use too; 21 and 22 bring
    # deliverer keys of their own
    members = [route(20, flt=flt), route(21, flt=flt, dkeys=64),
               route(22, flt=flt, dkeys=64)]
    matched = MatchedRoutes(normal=normal, groups={flt: members})
    kept = None
    elected = []
    for n in range(6):
        topic = f"a/t{n}"
        for sub in subs.values():
            sub.calls.clear()
        if kind == "$oshare":
            want = svc._elect(TENANT, flt, members, topic)   # stateless
        fanout = await svc._fan_out(TENANT, call(topic=topic), matched)
        assert fanout == len(normal) + 1
        got = calls_of(subs)
        infos = [mi for _b, _d, mis in got for mi in mis]
        picked = [mi for mi in infos if mi.matcher.is_shared]
        assert len(picked) == 1
        member = next(m for m in members
                      if m.receiver_id == picked[0].receiver_id)
        if kind == "$oshare":
            assert member is want
        elected.append(member.receiver_id)
        # the same calls as the plain logic over normal + the elected one
        assert got == plain_fan_out(normal + [member], {})[0]
        # the plan kept on ``matched`` holds the normal routes alone and
        # is the one object the first publish built
        plan = matched.fanout_plan
        if kept is None:
            kept = (plan, list(plan.calls), plan.match_infos)
        assert (plan, list(plan.calls), plan.match_infos) == kept
        assert plan.source is normal
        assert len(plan.match_infos) == len(normal)
        assert not any(mi.matcher.is_shared for mi in plan.match_infos)
    if kind == "$share":
        # least-outstanding election: six publishes, three members, two each
        assert sorted(elected.count(m.receiver_id) for m in members) \
            == [2, 2, 2]
    else:
        # rendezvous hash per topic: stable for a topic, not one member
        # for all six topics
        assert len(set(elected)) > 1


@pytest.mark.parametrize("feed", ["fresh", "kept"])
@pytest.mark.parametrize("kind", ["$oshare", "$share"])
async def test_elections_count_what_they_cost(kind, feed):
    """Counted beside ``deliver.group``: while the match hands on the
    slot's own ``members`` tuple, the group's first election scans the
    members and 699 are answered from what it kept; a leg that builds a
    list a publish re-syncs, and pays the scan, every time. One delivery a publish either way."""
    svc, subs, _ = service()
    flt = f"{kind}/g/a/#"
    members = tuple(route(20 + i, flt=flt, dkeys=64) for i in range(7))
    names = ("share.elect.kept", "share.elect.resync", "share.elect.first",
             "share.elect.scanned")

    def read():
        got = trace.TRACER.totals.between(0, time.monotonic_ns() + 10**9)
        return [got.get(n, (0, 0.0))[0] for n in names]

    before = read()
    won = dict.fromkeys(members, 0)
    for n in range(700):
        groups = {flt: members if feed == "kept" else list(members)}
        fanout = await svc._fan_out(TENANT, call(topic=f"a/t{n % 70}"),
                                    MatchedRoutes(groups=groups))
        assert fanout == 1
    for _d, _t, infos in subs[7].calls:
        (mi,) = infos
        won[next(m for m in members if m.match_info is mi)] += 1
    assert sum(won.values()) == 700
    if kind == "$share":
        assert set(won.values()) == {100}
    assert [b - a for a, b in zip(before, read())] == {
        "kept": [699, 0, 1, 7], "fresh": [0, 699, 1, 4900]}[feed]


async def test_dead_elected_member_is_reaped_beside_a_dead_normal_route():
    script = {"r20": NO_SUB, "r4": NO_RECEIVER}
    svc, subs, _ = service(script)
    normal = [route(i) for i in range(8)]
    flt = "$oshare/g/a/#"
    member = route(20, flt=flt)         # rides d0 with r0 and r4
    matched = MatchedRoutes(normal=normal, groups={flt: [member]})
    for _ in range(2):                  # the second finds the kept plan
        svc.worker.removed.clear()
        subs[7].calls.clear()
        want_calls, want_fanout, want_removed = plain_fan_out(
            normal + [member], script)
        assert await svc._fan_out(TENANT, call(), matched) == want_fanout == 7
        assert calls_of(subs) == want_calls
        assert svc.worker.removed == want_removed
        assert [f for f, _u, _i in want_removed] == ["a/4/#", flt]


@pytest.mark.parametrize("payload_len, allowed", [(8, 3), (16, 1), (64, 0)])
async def test_byte_cap_trims_persistent_routes_in_order(payload_len,
                                                         allowed):
    svc, subs, events = service(
        overrides={Setting.MaxPersistentFanoutBytes: 31})
    flt = "$oshare/g/a/#"
    members = [route(30, broker=PERSISTENT_SUB_BROKER_ID, flt=flt)]
    normal = [route(i, broker=PERSISTENT_SUB_BROKER_ID if i % 2 else 7)
              for i in range(10)]
    matched = MatchedRoutes(normal=normal, groups={flt: members},
                            persistent_fanout=5)
    # the cap runs over normal + elected, transient receivers untouched
    kept, used = [], 0
    for r in normal + members:
        if r.broker_id != PERSISTENT_SUB_BROKER_ID:
            kept.append(r)
        elif used < allowed:
            kept.append(r)
            used += 1
    for _ in range(2):      # the second publish finds a plan and trims again
        for sub in subs.values():
            sub.calls.clear()
        fanout = await svc._fan_out(TENANT, call(b"p" * payload_len),
                                    matched)
        want_calls, want_fanout, _ = plain_fan_out(kept, {})
        assert fanout == want_fanout == 5 + allowed
        assert by_broker(calls_of(subs)) == by_broker(want_calls)
    throttled = events.of(EventType.PERSISTENT_FANOUT_BYTES_THROTTLED)
    assert [e.meta["allowed"] for e in throttled] == [allowed, allowed]
    # a payload the cap lets through delivers to all of them again
    for sub in subs.values():
        sub.calls.clear()
    assert await svc._fan_out(TENANT, call(b"p"), matched) == 11
    assert len(events.of(EventType.PERSISTENT_FANOUT_BYTES_THROTTLED)) == 2


async def test_throttle_events_keep_their_order():
    svc, _subs, events = service(
        overrides={Setting.MaxPersistentFanoutBytes: 1})
    matched = MatchedRoutes(
        normal=[route(1, broker=PERSISTENT_SUB_BROKER_ID), route(2)],
        max_persistent_fanout_exceeded=True, max_group_fanout_exceeded=True)
    assert await svc._fan_out(TENANT, call(b"pp"), matched) == 1
    assert [e.type for e in events.events] == [
        EventType.PERSISTENT_FANOUT_THROTTLED,
        EventType.GROUP_FANOUT_THROTTLED,
        EventType.PERSISTENT_FANOUT_BYTES_THROTTLED]


async def test_same_matched_three_times_reuses_its_plan():
    svc, subs, _ = service()
    normal = [route(i) for i in range(12)]
    matched = MatchedRoutes(normal=normal)
    want_calls, want_fanout, _ = plain_fan_out(normal, {})
    before = counters()
    seen = []
    for _ in range(3):
        subs[7].calls.clear()
        assert await svc._fan_out(TENANT, call(), matched) == want_fanout
        assert calls_of(subs) == want_calls
        seen.append([infos for _d, _t, infos in subs[7].calls])
    got = counted(before)
    assert (got["deliver.plan.built"], got["deliver.plan.reused"]) == (1, 2)
    assert got["deliver.match_info.built"] == len(normal)
    assert got["deliver.routes"] == 3 * len(normal)
    # not only equal: the very MatchInfo objects, call by call
    for later in seen[1:]:
        assert all(a is b for mis, again in zip(seen[0], later)
                   for a, b in zip(mis, again))
    # a fresh MatchedRoutes over the same Route objects groups anew but
    # builds no MatchInfo
    before = counters()
    subs[7].calls.clear()
    await svc._fan_out(TENANT, call(), MatchedRoutes(normal=list(normal)))
    got = counted(before)
    assert (got["deliver.plan.built"], got["deliver.plan.reused"]) == (1, 0)
    assert got["deliver.match_info.built"] == 0
    assert all(a is b for mis, again in zip(
        seen[0], [i for _d, _t, i in subs[7].calls])
        for a, b in zip(mis, again))


def _truncate(m):      # benchmarks/sut.py ``_truncate64``, at 4
    m.normal = m.normal[:4]


def _drop_one(m):      # benchmarks/sut.py ``_drop_one``
    m.normal = m.normal[:-1]


def _append_in_place(m):
    m.normal.append(route(99))


@pytest.mark.parametrize("alter", [_truncate, _drop_one, _append_in_place])
async def test_normal_changed_between_publishes_rebuilds_the_plan(alter):
    svc, subs, _ = service()
    matched = MatchedRoutes(normal=[route(i) for i in range(12)])
    assert await svc._fan_out(TENANT, call(), matched) == 12
    alter(matched)
    subs[7].calls.clear()
    before = counters()
    want_calls, want_fanout, _ = plain_fan_out(matched.normal, {})
    assert await svc._fan_out(TENANT, call(), matched) == want_fanout
    assert want_fanout == len(matched.normal) != 12
    assert calls_of(subs) == want_calls
    got = counted(before)
    assert (got["deliver.plan.built"], got["deliver.plan.reused"]) == (1, 0)


async def test_no_route_publish_builds_nothing():
    svc, subs, _ = service()
    before = counters()
    assert await svc._fan_out(TENANT, call(), MatchedRoutes()) == 0
    assert counted(before) == dict.fromkeys(COUNTERS, 0)
    assert calls_of(subs) == []


def test_cached_and_wire_decoded_match_info_are_one_key():
    r = route(3, flt="$share/g/a/+/c", inc=7)
    cached = r.match_info
    assert cached is r.match_info
    pack = TopicMessagePack(topic="a/b/c", packs=())
    _t, _b, _d, _p, (wire,) = deliverer.decode_deliver(
        deliverer.encode_deliver(TENANT, 7, "d3", pack, [cached]))
    assert wire is not cached and wire.matcher is not cached.matcher
    assert wire == cached and hash(wire) == hash(cached)
    assert hash(cached) == hash((cached.matcher, cached.receiver_id,
                                 cached.incarnation))
    assert {cached: OK}[wire] is OK and {wire: OK}.get(cached) is OK
    other = MatchInfo(matcher=cached.matcher, receiver_id="r3",
                      incarnation=8)
    assert other != cached and {cached: OK}.get(other) is None
    # the kept hash is this process's own: it does not travel
    assert "_hash" in cached.__dict__
    assert "_hash" not in cached.__getstate__()
    clone = pickle.loads(pickle.dumps(cached))
    assert "_hash" not in clone.__dict__
    assert clone == cached and hash(clone) == hash(cached)


def test_route_equality_ignores_what_it_keeps():
    a, b = route(5), route(5)
    assert a.match_info is not b.match_info and a.match_info == b.match_info
    assert a == b and hash(a) == hash(b)
    assert "match_info" not in repr(a)


def test_kept_match_info_materialises_no_dict_on_the_route():
    import gc

    def has_dict(obj) -> bool:      # without asking for ``__dict__``
        return any(type(x) is dict for x in gc.get_referents(obj))
    r = route(6)
    if has_dict(r):
        pytest.skip("this interpreter keeps no inline attribute values")
    mi = r.match_info
    assert r.match_info is mi and not has_dict(r)


async def test_route_readded_under_new_incarnation_gets_its_own():
    svc, subs, _ = service()
    trie = SubscriptionTrie()
    trie.add(route(1, flt="a/#", inc=1))
    first = trie.match(["a", "b"])
    assert await svc._fan_out(TENANT, call(), first) == 1
    old = first.normal[0]
    trie.add(route(1, flt="a/#", inc=2))
    second = trie.match(["a", "b"])
    new = second.normal[0]
    assert new is not old and "match_info" not in new.__dict__
    assert await svc._fan_out(TENANT, call(), second) == 1
    (_d1, _t1, (mi_old,)), (_d2, _t2, (mi_new,)) = subs[7].calls
    assert (mi_old.incarnation, mi_new.incarnation) == (1, 2)
    assert mi_old is old.match_info and mi_new is new.match_info
    assert mi_old != mi_new


async def test_gc_sweep_checks_under_the_routes_own_match_info():
    script = {"r2": NO_SUB}
    svc, subs, _ = service(script)
    routes = [route(i) for i in range(4)]
    svc.worker.stored = [(TENANT, r) for r in routes]
    assert await svc._fan_out(TENANT, call(),
                              MatchedRoutes(normal=routes[:2])) == 2
    assert await svc.gc_sweep() == 1
    assert all(a is r.match_info for a, r in zip(subs[7].checked, routes))
    assert svc.worker.removed == [("a/2/#", routes[2].receiver_url, 0)]


@pytest.mark.parametrize("name", COUNTERS)
def test_counters_are_registered_boundaries(name):
    from bifromq_tpu.trace.names import BOUNDARIES
    row = BOUNDARIES[name]
    assert row.kind == "counter"
    with open(__file__.rsplit("/tests/", 1)[0] + "/README.md") as f:
        assert f"| `{name}` | `{row.where}` | counter |" in f.read()

"""Retained & session serving plane tests (ISSUE 13).

Randomized parity suite for the patched retained columns — patched
index ≡ post-compaction rebuild ≡ host ``match_filter_host`` oracle over
adversarial topics ($SYS roots, '#'/'+' folds, expiry races, arena
growth) — plus the async scan plane (ring/breaker/watchdog/cache with
exact invalidation), drain-storm tenant fairness, $share balanced
election, the multi-range standby supervisor, and the mixed-workload
generator.
"""

import asyncio
import random

import pytest

from bifromq_tpu.models.retained import RetainedIndex, match_filter_host
from bifromq_tpu.retained_plane import (DrainGovernor,
                                        RetainedScanPlane)
from bifromq_tpu.utils import topic as t
from bifromq_tpu.utils.metrics import STAGES


def brute_force(topics, filter_levels):
    return sorted(topic for topic in topics
                  if t.matches(t.parse(topic), list(filter_levels)))


ALPHABET = ["a", "b", "c", "", "x1", "$s", "dev", "ação"]


def rand_topic(rng, depth=(1, 5)):
    n = rng.randint(*depth)
    return "/".join(rng.choice(ALPHABET) for _ in range(n))


def rand_filters(rng, k):
    out = []
    for _ in range(k):
        n = rng.randint(1, 5)
        lv = []
        for i in range(n):
            roll = rng.random()
            if roll < 0.28:
                lv.append("+")
            elif roll < 0.38 and i == n - 1:
                lv.append("#")
            else:
                lv.append(rng.choice(ALPHABET))
        out.append(lv)
    out += [["#"], ["+"], ["$s", "#"], ["$s", "+"], ["+", "+"],
            ["+", "#"]]
    return out


def build_index(topics, tenant="T", **kw):
    idx = RetainedIndex(**kw)
    for topic in topics:
        idx.add_topic(tenant, t.parse(topic), topic)
    idx.refresh()
    return idx


def assert_parity(idx, filters, tenant="T", rebuilt_from=None):
    """patched ≡ host oracle (and optionally ≡ a fresh rebuild)."""
    got = idx.match_batch([(tenant, f) for f in filters])
    fresh = None
    if rebuilt_from is not None:
        fresh = build_index(sorted(rebuilt_from), tenant=tenant,
                            patched=False)
        fresh_rows = fresh.match_batch([(tenant, f) for f in filters])
    trie = idx.tries.get(tenant)
    for i, f in enumerate(filters):
        want = sorted(match_filter_host(trie, f)) if trie else []
        assert sorted(got[i]) == want, (f, sorted(got[i]), want)
        if fresh is not None:
            assert sorted(fresh_rows[i]) == want, ("rebuild", f)


class TestPatchedRetainedParity:
    def test_flood_parity_randomized(self):
        rng = random.Random(11)
        live = set()
        while len(live) < 150:
            live.add(rand_topic(rng))
        idx = build_index(sorted(live), k_states=16)
        assert hasattr(idx._compiled, "retained_add")
        rebuilds0 = idx.rebuilds
        for i in range(500):
            roll = rng.random()
            if roll < 0.5:
                topic = rand_topic(rng)
                if rng.random() < 0.4:
                    topic += f"/d{i}"      # fresh device leaf
                if topic not in live:
                    idx.add_topic("T", t.parse(topic), topic)
                    live.add(topic)
            elif roll < 0.8 and live:
                topic = rng.choice(sorted(live))
                idx.remove_topic("T", t.parse(topic), topic)
                live.discard(topic)
            elif live:
                # re-SET of a live topic: payload replace, index no-op
                topic = rng.choice(sorted(live))
                idx.add_topic("T", t.parse(topic), topic)
            if i % 125 == 60:
                assert_parity(idx, rand_filters(rng, 60),
                              rebuilt_from=live)
        assert_parity(idx, rand_filters(rng, 80), rebuilt_from=live)
        assert idx.rebuilds == rebuilds0, "flood triggered a full rebuild"
        assert idx.patch_fallbacks == 0

    def test_sys_root_rules_on_patched_topics(self):
        idx = build_index(["a/b"])
        rebuilds0 = idx.rebuilds
        for topic in ["$SYS/health", "$SYS/x/y", "$stat", "c/$d", "c/e"]:
            idx.add_topic("T", t.parse(topic), topic)
        live = ["a/b", "$SYS/health", "$SYS/x/y", "$stat", "c/$d", "c/e"]
        for f in [["#"], ["+"], ["$SYS", "#"], ["$SYS", "+"],
                  ["+", "+"], ["c", "+"], ["$stat"], ["+", "$d"]]:
            got = sorted(idx.match("T", f))
            assert got == brute_force(live, f), f
        assert idx.rebuilds == rebuilds0

    def test_expiry_race_resurrection(self):
        """set → clear (expiry) → re-set of the SAME topic must
        resurrect the tombstone in place — zero arena growth."""
        idx = build_index(["a/b", "a/c"])
        base = idx._compiled
        slots0 = len(base.matchings)
        assert idx.remove_topic("T", ["a", "b"], "a/b")
        assert base.dead_slots == 1
        assert sorted(idx.match("T", ["a", "+"])) == ["a/c"]
        assert idx.add_topic("T", ["a", "b"], "a/b")
        assert base.dead_slots == 0
        assert len(base.matchings) == slots0     # resurrected, not appended
        assert sorted(idx.match("T", ["a", "+"])) == ["a/b", "a/c"]
        # patch-era slot: same cycle on a brand-new topic
        idx.add_topic("T", ["a", "d"], "a/d")
        idx.remove_topic("T", ["a", "d"], "a/d")
        idx.add_topic("T", ["a", "d"], "a/d")
        assert sorted(idx.match("T", ["a", "#"])) == \
            ["a/b", "a/c", "a/d"]

    def test_arena_growth_parity(self):
        """A flood against a tiny base forces node-arena growth, edge
        regrow and child/extra list regrows — parity must survive every
        reshape."""
        rng = random.Random(3)
        idx = build_index(["seed/x"], k_states=16)
        base = idx._compiled
        live = {"seed/x"}
        for i in range(400):
            topic = f"f{i % 37}/s{i % 11}/d{i}"
            idx.add_topic("T", t.parse(topic), topic)
            live.add(topic)
        assert base.node_grows >= 1
        assert idx.rebuilds == 0
        assert_parity(idx, rand_filters(rng, 40)
                      + [["f3", "+", "#"], ["+", "s4", "#"]],
                      rebuilt_from=live)

    def test_compaction_folds_and_stays_exact(self):
        rng = random.Random(5)
        topics = [f"a/b/t{i}" for i in range(120)]
        idx = build_index(topics)
        rebuilds0 = idx.rebuilds
        for topic in topics[:90]:
            idx.remove_topic("T", t.parse(topic), topic)
        # fragmentation crossed the ratio: the next refresh compacts
        assert idx.frag_pending()
        idx.refresh()
        assert idx.compactions == 1 and idx.rebuilds == rebuilds0
        assert idx._compiled.pristine
        assert_parity(idx, rand_filters(rng, 30) + [["a", "b", "#"]],
                      rebuilt_from=topics[90:])

    def test_new_tenant_via_patch(self):
        idx = build_index(["a/b"], tenant="T")
        rebuilds0 = idx.rebuilds
        idx.add_topic("U", ["u", "v"], "u/v")
        idx.add_topic("U", ["$SYS", "s"], "$SYS/s")
        assert sorted(idx.match("U", ["#"])) == ["u/v"]
        assert sorted(idx.match("U", ["$SYS", "#"])) == ["$SYS/s"]
        assert idx.match("T", ["u", "v"]) == []
        assert idx.rebuilds == rebuilds0

    def test_limit_scan_bounded_with_tombstones(self):
        topics = [f"x/t{i:03d}" for i in range(50)]
        idx = build_index(topics)
        for topic in topics[::2]:
            idx.remove_topic("T", t.parse(topic), topic)
        live = set(topics[1::2])
        got = idx.match("T", ["x", "#"], limit=7)
        assert len(got) == 7 and set(got) <= live
        got = idx.match("T", ["x", "+"], limit=1000)
        assert sorted(got) == sorted(live)

    def test_kill_switch_restores_rebuild_path(self):
        idx = build_index(["a/b"], patched=False)
        assert not hasattr(idx._compiled, "retained_add")
        idx.add_topic("T", ["a", "c"], "a/c")
        assert idx._dirty
        assert sorted(idx.match("T", ["a", "+"])) == ["a/b", "a/c"]
        assert idx.rebuilds == 1

    def test_remove_last_topic_of_tenant(self):
        idx = build_index(["only/one"])
        assert idx.remove_topic("T", ["only", "one"], "only/one")
        assert "T" not in idx.tries
        assert idx.match("T", ["#"]) == []
        # overflow/host fallback row for a tenant gone from authority
        assert idx.match("T", ["+"] * 3) == []


pytestmark_async = pytest.mark.asyncio


class TestScanPlane:
    def _index(self, n=60, seed=2):
        rng = random.Random(seed)
        topics = set()
        while len(topics) < n:
            topics.add(rand_topic(rng))
        return build_index(sorted(topics)), sorted(topics)

    @pytest.mark.asyncio
    async def test_async_scan_parity_and_cache(self):
        idx, topics = self._index()
        plane = RetainedScanPlane(lambda: idx)
        rng = random.Random(7)
        filters = rand_filters(rng, 30)
        queries = [("T", f) for f in filters]
        rows = await plane.scan_batch(queries)
        for f, row in zip(filters, rows):
            assert sorted(row) == brute_force(topics, f), f
        hits0 = plane.cache.hits
        rows2 = await plane.scan_batch(queries)
        assert plane.cache.hits - hits0 == len(queries)
        assert [sorted(r) for r in rows2] == [sorted(r) for r in rows]

    @pytest.mark.asyncio
    async def test_exact_invalidation_on_mutation(self):
        idx, _ = self._index()
        plane = RetainedScanPlane(lambda: idx)
        idx.delta_hooks.append(plane.cache.on_delta)
        q_hit = [("T", ["zz", "+"])]
        q_other = [("T", ["yy", "#"])]
        await plane.scan_batch(q_hit)
        await plane.scan_batch(q_other)
        # a mutation matching zz/+ evicts ONLY that key
        idx.add_topic("T", ["zz", "new"], "zz/new")
        m0 = plane.cache.misses
        rows = await plane.scan_batch(q_hit)
        assert plane.cache.misses == m0 + 1      # evicted → re-scanned
        assert rows[0] == ["zz/new"]
        h0 = plane.cache.hits
        await plane.scan_batch(q_other)          # untouched filter: hit
        assert plane.cache.hits == h0 + 1

    @pytest.mark.asyncio
    async def test_store_raced_by_mutation_is_refused(self):
        idx, _ = self._index()
        plane = RetainedScanPlane(lambda: idx)
        idx.delta_hooks.append(plane.cache.on_delta)
        cache = plane.cache
        token = cache.token("T")
        idx.add_topic("T", ["race", "x"], "race/x")   # bumps the seq
        cache.put("T", ("race", "+"), None, ["stale"], token)
        assert cache.get("T", ("race", "+"), None) is None

    @pytest.mark.asyncio
    async def test_watchdog_timeout_degrades_to_oracle(self, monkeypatch):
        from bifromq_tpu.resilience.device import DeviceTimeoutError
        idx, topics = self._index()
        plane = RetainedScanPlane(lambda: idx)
        ring = plane._pipeline_ring()

        async def hang(res, **kw):
            raise DeviceTimeoutError(0.01)
        monkeypatch.setattr(ring, "wait_ready", hang)
        filters = [["+"], ["a", "#"]]
        rows = await plane.scan_batch([("T", f) for f in filters])
        for f, row in zip(filters, rows):
            assert sorted(row) == brute_force(topics, f), f
        assert plane.degraded_total.get("timeout") == 1
        assert ring.timeouts_total == 1
        if plane.device_breaker is not None:
            assert plane.device_breaker._failures >= 1

    @pytest.mark.asyncio
    async def test_breaker_open_skips_dispatch(self):
        idx, topics = self._index()
        plane = RetainedScanPlane(lambda: idx)
        br = plane.device_breaker
        if br is None:
            pytest.skip("device breaker disabled in env")
        for _ in range(10):
            br.record_failure("boom")
        assert br.state == "open"
        called = {"n": 0}
        orig = idx.dispatch_scan

        def counting(*a, **kw):
            called["n"] += 1
            return orig(*a, **kw)
        idx.dispatch_scan = counting
        rows = await plane.scan_batch([("T", ["#"])])
        assert called["n"] == 0
        assert sorted(rows[0]) == brute_force(topics, ["#"])
        assert plane.degraded_total.get("breaker", 0) >= 1

    @pytest.mark.asyncio
    async def test_service_scans_feed_slo_and_delta_log(self):
        from bifromq_tpu.obs import OBS
        from bifromq_tpu.plugin.events import CollectingEventCollector
        from bifromq_tpu.retain.service import RetainService
        from bifromq_tpu.types import ClientInfo, Message, QoS
        svc = RetainService(CollectingEventCollector())
        pub = ClientInfo(tenant_id="tenantX")
        msg = Message(message_id=1, payload=b"p",
                      pub_qos=QoS.AT_LEAST_ONCE, timestamp=0,
                      expiry_seconds=0xFFFFFFFF)
        assert await svc.retain(pub, "dev/1/temp", msg)
        hist0 = STAGES.snapshot().get("retain.scan", {}).get("count", 0)
        res = await svc.match("tenantX", ["dev", "+", "temp"], 10)
        assert [topic for topic, _m in res] == ["dev/1/temp"]
        assert STAGES.snapshot()["retain.scan"]["count"] > hist0
        # per-tenant RED window carries the scan stage (satellite bugfix)
        raw = OBS.windows.raw_snapshot().get("tenantX", {})
        assert "retain.scan" in raw.get("stages", raw.get("latency", {})) \
            or any("retain.scan" in str(k) for k in raw)
        # the retained delta stream recorded the mutation
        from bifromq_tpu.replication import status_report
        hubs = status_report()["hubs"]
        retained = [h for h in hubs if h.get("role") == "retained-hub"]
        assert retained and any(r["head_seq"] >= 1
                                for h in retained
                                for r in h["ranges"])
        coproc = next(iter(svc.kvstore.coprocs.values()))
        assert coproc.scan_plane is not None
        # the /metrics "retained" section sees the live plane
        snap = OBS.retained_snapshot()
        assert any(p.get("scans_total", 0) >= 1
                   for p in snap["scan_planes"])
        await svc.stop()

    async def test_retained_standby_promotes_without_kv_rebuild(self):
        """ISSUE 16 leg 2 at the service layer: a standby spawned off
        the live RetainService tracks retains through the delta log,
        and PROMOTING it serves wildcard scans straight off the
        replicated arenas — one resync ever, no KV replay."""
        from bifromq_tpu.plugin.events import CollectingEventCollector
        from bifromq_tpu.retain.service import RetainService
        from bifromq_tpu.types import ClientInfo, Message, QoS
        svc = RetainService(CollectingEventCollector())
        pub = ClientInfo(tenant_id="tenX")
        msg = Message(message_id=1, payload=b"p",
                      pub_qos=QoS.AT_LEAST_ONCE, timestamp=0,
                      expiry_seconds=0xFFFFFFFF)
        for topic in ("dev/1/temp", "dev/2/temp", "site/a/hum"):
            assert await svc.retain(pub, topic, msg)
        sb = svc.retained_standby()
        await sb.sync_once()
        assert sb.attached and sb.resyncs == 1
        # a post-attach retain rides the op stream, not a resync
        assert await svc.retain(pub, "dev/3/temp", msg)
        await sb.sync_once()
        assert sb.applied >= 1 and sb.resyncs == 1
        idx = sb.promote()
        assert sb.promote() is idx
        rows = idx.match_batch([("tenX", ["dev", "+", "temp"])])[0]
        assert sorted(rows) == ["dev/1/temp", "dev/2/temp",
                                "dev/3/temp"]
        await svc.stop()


class TestDrainGovernor:
    @pytest.mark.asyncio
    async def test_tenant_fairness_under_herd(self):
        gov = DrainGovernor(slots=4, per_tenant=2,
                            noisy_fn=lambda tenant: False)
        peak = {}
        active = {}
        order = []

        async def drain(tenant, i):
            async with gov.slot(tenant):
                active[tenant] = active.get(tenant, 0) + 1
                peak[tenant] = max(peak.get(tenant, 0), active[tenant])
                await asyncio.sleep(0.002)
                active[tenant] -= 1
                order.append(tenant)

        herd = [drain("A", i) for i in range(40)]
        quiet = [drain("B", i) for i in range(3)]
        await asyncio.gather(*herd, *quiet)
        # per-tenant cap respected: the herd never held more than 2 slots
        assert peak["A"] <= 2 and peak["B"] <= 2
        # fairness: B's three drains all completed inside the first
        # fraction of the storm instead of queuing behind A's herd
        assert all(tenant == "B" for tenant in order
                   if tenant == "B")
        b_done = max(i for i, tenant in enumerate(order) if tenant == "B")
        assert b_done < len(order) // 2
        assert gov.admitted_total == 43

    @pytest.mark.asyncio
    async def test_cancellation_releases_slots(self):
        gov = DrainGovernor(slots=1, per_tenant=1,
                            noisy_fn=lambda tenant: False)
        entered = asyncio.Event()

        async def holder():
            async with gov.slot("A"):
                entered.set()
                await asyncio.sleep(10)

        async def waiter():
            async with gov.slot("A"):
                pass

        h = asyncio.ensure_future(holder())
        await entered.wait()
        w = asyncio.ensure_future(waiter())
        await asyncio.sleep(0.01)
        w.cancel()
        with pytest.raises(asyncio.CancelledError):
            await w
        h.cancel()
        try:
            await h
        except asyncio.CancelledError:
            pass
        # both slots free again
        async with gov.slot("A"):
            pass
        assert gov._global.in_flight == 0

    @pytest.mark.asyncio
    async def test_reconnect_drain_is_governed_and_staged(self):
        """Broker-level: an offline backlog drained at reconnect passes
        the governor and lands an inbox.drain stage sample."""
        from bifromq_tpu.mqtt.broker import MQTTBroker
        from bifromq_tpu.mqtt.client import MQTTClient
        b = MQTTBroker(port=0)
        await b.start()
        try:
            c = MQTTClient(port=b.port, client_id="drain1",
                           clean_start=False)
            await c.connect()
            await c.subscribe("alerts/#", qos=1)
            await c.disconnect()
            p = MQTTClient(port=b.port, client_id="pub")
            await p.connect()
            for i in range(4):
                await p.publish("alerts/x", f"m{i}".encode(), qos=1)
            await p.disconnect()
            admitted0 = b.inbox.drain_governor.admitted_total
            hist0 = STAGES.snapshot().get("inbox.drain",
                                          {}).get("count", 0)
            c2 = MQTTClient(port=b.port, client_id="drain1",
                            clean_start=False)
            await c2.connect()
            got = [await c2.recv() for _ in range(4)]
            assert [m.payload for m in got] == [b"m0", b"m1", b"m2", b"m3"]
            await c2.disconnect()
            assert b.inbox.drain_governor.admitted_total > admitted0
            assert STAGES.snapshot()["inbox.drain"]["count"] > hist0
        finally:
            b.inbox.close()
            await b.stop()


# the two ways a group's members reach the election: the matcher's own
# tuple, the same object until somebody joins or leaves, and the fresh
# list a call that the overlay, the multi-range union, a remote worker's
# reply and the host oracle build
FEEDS = {"kept": lambda members: members, "fresh": list}
feeds = pytest.mark.parametrize("feed", sorted(FEEDS))


class TestGroupBalancer:
    def _members(self, n, kind="$share", first=0, dkey="d"):
        from bifromq_tpu.models.oracle import Route
        from bifromq_tpu.types import RouteMatcher
        matcher = RouteMatcher.from_topic_filter(f"{kind}/g/t/#")
        return tuple(Route(matcher=matcher, broker_id=0,
                           receiver_id=f"w{i}", deliverer_key=dkey)
                     for i in range(first, first + n))

    def _balancer(self, **kw):
        from bifromq_tpu.dist.service import GroupFanoutBalancer
        return GroupFanoutBalancer(random.Random(0), **kw)

    def test_balanced_spread_is_tight(self):
        bal = self._balancer()
        members = list(self._members(7))
        counts = {}
        for _ in range(700):
            r = bal.pick("T", "$share/g/t/#", members)
            counts[r.receiver_id] = counts.get(r.receiver_id, 0) + 1
        assert max(counts.values()) - min(counts.values()) <= 1
        sp = bal.spread("T", "$share/g/t/#")
        assert sp["members"] == 7 and sp["max"] - sp["min"] <= 1

    @feeds
    @pytest.mark.parametrize("n", [7, 50])
    def test_spread_and_what_each_feed_costs(self, n, feed):
        """Spread <= 1 at EVERY election, not only at the end; the kept
        membership is scanned once, the fresh lists every time."""
        bal = self._balancer()
        members = self._members(n)
        counts = dict.fromkeys(members, 0)
        for _ in range(700):
            counts[bal.pick("T", "f", FEEDS[feed](members))] += 1
            assert max(counts.values()) - min(counts.values()) <= 1
            sp = bal.spread("T", "f")
            assert (sp["members"], sp["min"], sp["max"]) == (
                n, min(counts.values()), max(counts.values()))
        # (kept, re-synced, first, members scanned)
        assert bal.drain() == {"kept": (699, 0, 1, n),
                               "fresh": (0, 699, 1, 700 * n)}[feed]
        assert bal.drain() == (0, 0, 0, 0)

    def test_membership_churn_seeds_newcomer_fairly(self):
        """A first-seen member seeds at the group MIN: it takes a fair
        share immediately but is NOT flooded with 100% of traffic until
        its lifetime count catches up (the cold-consumer inversion)."""
        bal = self._balancer()
        members = list(self._members(4))
        for _ in range(400):
            bal.pick("T", "f", members)
        grown = members + list(self._members(5)[4:])
        picks = [bal.pick("T", "f", grown).receiver_id
                 for _ in range(50)]
        newcomer = picks.count("w4")
        # fair share of 50 picks over 5 members is 10 — the newcomer
        # joins the min tie (gets some) without monopolizing the group
        assert 1 <= newcomer <= 25, newcomer
        sp = bal.spread("T", "f")
        assert sp["max"] - sp["min"] <= 1

    @feeds
    @pytest.mark.parametrize("before", [0, 2, 400, 403])
    def test_a_joiner_enters_at_the_minimum(self, before, feed):
        """Whatever the round stands at when the membership is swapped:
        the joiner is neither starved nor flooded, and the balance of
        the members that stayed is carried over the swap."""
        bal = self._balancer()
        members = self._members(4)
        counts = dict.fromkeys(self._members(5), 0)
        for _ in range(before):
            counts[bal.pick("T", "f", FEEDS[feed](members))] += 1
        grown = self._members(5)        # a new tuple, as the patcher's
        joiner = grown[4]
        for _ in range(50):
            counts[bal.pick("T", "f", FEEDS[feed](grown))] += 1
        assert 9 <= counts[joiner] <= 11        # 1-25 asked; it is 10 +- 1
        stayed = [counts[r] for r in grown[:4]]
        assert max(stayed) - min(stayed) <= 1
        sp = bal.spread("T", "f")
        assert sp["members"] == 5 and sp["max"] - sp["min"] <= 1

    @feeds
    def test_a_leaver_is_never_elected_again(self, feed):
        bal = self._balancer()
        members = self._members(7)
        counts = dict.fromkeys(members, 0)
        for _ in range(3):
            counts[bal.pick("T", "f", FEEDS[feed](members))] += 1
        # somebody still waiting for this round's delivery leaves
        leaver = next(r for r in members if not counts[r])
        left = tuple(r for r in members if r is not leaver)
        for _ in range(600):
            counts[bal.pick("T", "f", FEEDS[feed](left))] += 1
        assert counts.pop(leaver) == 0
        assert max(counts.values()) - min(counts.values()) <= 1
        assert bal.spread("T", "f")["members"] == 6

    @feeds
    def test_every_member_replaced_at_once(self, feed):
        bal = self._balancer()
        old = self._members(5)
        for _ in range(3):
            bal.pick("T", "f", FEEDS[feed](old))
        new = self._members(5, first=5)
        got = [bal.pick("T", "f", FEEDS[feed](new)) for _ in range(10)]
        assert set(got[:5]) == set(got[5:]) == set(new)

    @feeds
    @pytest.mark.parametrize("kind", ["$share", "$oshare"])
    def test_a_group_of_one(self, kind, feed):
        bal = self._balancer()
        only = self._members(1, kind)
        for n in range(5):
            got = (bal.pick("T", "f", FEEDS[feed](only))
                   if kind == "$share" else
                   bal.pick_ordered("T", "f", FEEDS[feed](only), f"t/{n}"))
            assert got is only[0]

    def test_bounded_group_table(self):
        """``max_groups`` entries at most; past it the COLDEST entry goes,
        one at a time (it was the oldest half, 4,096 dicts in one turn)."""
        bal = self._balancer(max_groups=8)
        members = self._members(2)
        firsts = []
        for i in range(40):
            firsts.append(bal.pick("T", f"f{i}", members))
            assert len(bal._kept) == min(i + 1, 8)
        assert [f for _t, f in bal._kept] == [f"f{i}" for i in range(32, 40)]
        # an election warms its group: f32 is no longer the coldest
        second = bal.pick("T", "f32", members)
        bal.pick_ordered("T", "o0", self._members(2, "$oshare"), "t")
        assert [f for _t, f in bal._kept] == (
            [f"f{i}" for i in range(34, 40)] + ["f32", "o0"])
        # and had kept its balance: the second of its round is the other
        assert second is not firsts[32]
        # f33 was dropped: a new round, not a wrong delivery
        assert bal.pick("T", "f33", members) in members
        # 40 groups' first elections, o0's, and f33's second "first"
        assert bal.drain() == (1, 0, 42, 84)

    # ---- $oshare: rendezvous over (member, topic) ---------------------

    TOPICS = [f"t/s{i % 49}/d{i}/telemetry" for i in range(1000)]

    def _winners(self, bal, members, topics=TOPICS, feed="kept"):
        return [bal.pick_ordered("T", "$oshare/g/t/#", FEEDS[feed](members),
                                 topic).receiver_id for topic in topics]

    def test_ordered_is_stable_per_topic_on_every_feed(self):
        bal = self._balancer()
        members = self._members(50, "$oshare")
        first = self._winners(bal, members)
        assert bal.drain() == (999, 0, 1, 50)
        assert self._winners(bal, members) == first
        assert bal.drain() == (1000, 0, 0, 0)
        # a leg that builds fresh lists, and another service with the
        # members in another order, elect the same member a topic
        assert self._winners(bal, members, feed="fresh") == first
        assert bal.drain() == (0, 1000, 0, 50000)
        assert self._winners(self._balancer(), members[::-1]) == first
        assert len(set(first)) > 25

    def test_ordered_is_the_same_in_another_process(self):
        """No per-process seed in the score (never ``hash()``): a process
        started under another ``PYTHONHASHSEED`` elects the same."""
        import json
        import os
        import subprocess
        import sys
        code = (
            "import json, random, sys\n"
            "sys.path[:0] = json.loads(sys.argv[1])\n"
            "from tests.test_retained_plane import TestGroupBalancer as T\n"
            "t = T()\n"
            "print(json.dumps(t._winners(t._balancer(), "
            "t._members(50, '$oshare'), T.TOPICS[:200])))\n")
        want = self._winners(self._balancer(), self._members(50, "$oshare"),
                             self.TOPICS[:200])
        for seed in ("1", "4242"):
            env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu")
            out = subprocess.run(
                [sys.executable, "-c", code, json.dumps(sys.path)],
                env=env, capture_output=True, text=True, timeout=120)
            assert out.returncode == 0, out.stderr[-2000:]
            assert json.loads(out.stdout.splitlines()[-1]) == want

    def test_ordered_is_uniform_over_the_members(self):
        bal = self._balancer()
        members = self._members(50, "$oshare")
        n = 20000
        got = self._winners(bal, members,
                            [f"t/s{i % 49}/d{i}/telemetry" for i in range(n)])
        mean = n / 50
        sigma = (n * (1 / 50) * (1 - 1 / 50)) ** 0.5
        counts = [got.count(r.receiver_id) for r in members]
        assert max(abs(c - mean) for c in counts) <= 3 * sigma, counts

    def test_ordered_join_and_leave_move_the_least(self):
        bal = self._balancer()
        members = self._members(50, "$oshare")
        before = self._winners(bal, members)
        grown = self._members(51, "$oshare")
        after = self._winners(bal, grown)
        moved = [(b, a) for b, a in zip(before, after) if a != b]
        assert moved and all(a == "w50" for _b, a in moved)
        assert len(moved) <= 3 * len(before) // 51
        # w7 leaves: only its topics move, each to a member that stayed
        left = tuple(r for r in grown if r.receiver_id != "w7")
        last = self._winners(bal, left)
        moved = [(a, z) for a, z in zip(after, last) if z != a]
        assert "w7" in after and "w7" not in last
        assert moved and all(a == "w7" for a, _z in moved)
        assert bal.drain() == (2997, 2, 1, 151)


class TestStandbySupervisor:
    class _FakeStandby:
        def __init__(self, rid):
            self.rid = rid
            self.started = False
            self.stopped = False
            self.attached = True

        async def start(self):
            self.started = True

        async def stop(self):
            self.stopped = True

        def promote(self):
            return f"matcher-{self.rid}"

        def lag(self):
            return 0

    @pytest.mark.asyncio
    async def test_spawns_follows_splits_and_retires(self):
        from bifromq_tpu.replication.standby import StandbySupervisor
        ranges = {"live": ["r1", "r2"]}

        async def ranges_fn():
            return ranges["live"]

        made = []

        def factory(rid):
            sb = self._FakeStandby(rid)
            made.append(sb)
            return sb

        sup = StandbySupervisor(ranges_fn=ranges_fn,
                                standby_factory=factory)
        await sup.poll_once()
        assert sorted(sup.standbys) == ["r1", "r2"]
        assert all(sb.started for sb in made)
        # a split lands a new range id on the next poll
        ranges["live"] = ["r1", "r2", "r2a"]
        await sup.poll_once()
        assert sorted(sup.standbys) == ["r1", "r2", "r2a"]
        assert sup.spawned == 3
        # a merged/decommissioned range retires its applier
        ranges["live"] = ["r1", "r2a"]
        await sup.poll_once()
        assert sorted(sup.standbys) == ["r1", "r2a"]
        assert sup.retired == 1
        assert made[1].stopped
        promoted = sup.promote_all()
        assert promoted == {"r1": "matcher-r1", "r2a": "matcher-r2a"}
        st = sup.status()
        assert st["role"] == "standby-supervisor" and st["polls"] == 3
        await sup.stop()

    @pytest.mark.asyncio
    async def test_supervisor_tracks_live_worker_over_rpc(self):
        """End to end over the real fabric: the supervisor reads
        repl_status, spawns a REAL per-range WarmStandby, and the
        applier reaches delta parity with the leader."""
        from bifromq_tpu.dist.remote import (SERVICE, DistWorkerRPCService,
                                             RemoteDistWorker)
        from bifromq_tpu.dist.worker import DistWorker
        from bifromq_tpu.replication.standby import StandbySupervisor
        from bifromq_tpu.rpc.fabric import RPCServer, ServiceRegistry
        from bifromq_tpu.models.oracle import Route
        from bifromq_tpu.types import RouteMatcher

        def rt(tf, i):
            return Route(matcher=RouteMatcher.from_topic_filter(tf),
                         broker_id=0, receiver_id=f"r{i}",
                         deliverer_key="d0")

        worker = DistWorker(node_id="w0")
        await worker.start()
        server = RPCServer(host="127.0.0.1", port=0)
        DistWorkerRPCService(worker).register(server)
        await server.start()
        reg = ServiceRegistry()
        reg.announce(SERVICE, f"127.0.0.1:{server.port}")
        sup = StandbySupervisor(reg)
        try:
            for i in range(8):
                remote = RemoteDistWorker(reg)
                assert (await remote.add_route(
                    "T", rt(f"x/{i}/y", i))) in ("ok", "exists")
            await sup.poll_once()
            assert len(sup.standbys) >= 1
            for sb in sup.standbys.values():
                for _ in range(200):
                    await asyncio.sleep(0.05)
                    if sb.attached and sb.lag() == 0:
                        break
                assert sb.attached
            matchers = sup.promote_all()
            assert len(matchers) == len(sup.standbys)
            got = next(iter(matchers.values())).match_batch(
                [("T", f"x/{i}/y") for i in range(8)])
            assert all(len(m.normal) == 1 for m in got)
        finally:
            await sup.stop()
            await server.stop()
            await worker.stop()


class TestMixedWorkloadPlan:
    def test_deterministic_and_shaped(self):
        from bifromq_tpu import workloads
        a = workloads.config_mixed(3000, seed=9, retained_ops=300,
                                   scan_filters=40, churn_ops=50,
                                   drain_sessions=40, retained_base=256)
        b = workloads.config_mixed(3000, seed=9, retained_ops=300,
                                   scan_filters=40, churn_ops=50,
                                   drain_sessions=40, retained_base=256)
        assert a["qos_mix"] == b["qos_mix"]
        assert a["retained_flood"] == b["retained_flood"]
        assert a["drain_plan"] == b["drain_plan"]
        assert len(a["retained_flood"]) == 300
        assert sum(a["qos_mix"].values()) == a["n_clients"]
        # QoS mix is a real mix
        assert all(a["qos_mix"][q] > 0 for q in (0, 1, 2))
        # the drain plan is herd-shaped (tenant0 dominates)
        herd = sum(1 for tenant, _i, _b in a["drain_plan"]
                   if tenant == "tenant0")
        assert herd >= len(a["drain_plan"]) * 0.7
        # share members present in the route table
        from bifromq_tpu.types import RouteMatcherType
        some_share = any(
            r.matcher.type != RouteMatcherType.NORMAL
            for trie in a["subscriptions"].values()
            for node_routes in [trie]
            for r in [] )
        # (structural check via matcher counts instead)
        n_share = 0
        for trie in a["subscriptions"].values():
            root = trie._root
            stack = [root]
            while stack:
                n = stack.pop()
                n_share += len(n.groups)
                stack.extend(n.children.values())
            if n_share:
                break
        assert n_share > 0

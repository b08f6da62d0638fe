"""The point-to-point deployment (``benchmarks/generators/device_fleet.py``,
cell ``device_command_1m.p2p_sat``) at a size a test can hold: 2,000 devices
x 5 exact filters, 200 roaming devices.

- the broker path (a started ``Standalone``: ``DistWorker`` + ``TpuMatcher``
  on the CPU backend) against the benchmark's plain reference on the
  generator's own draws;
- a roaming device coming and going: an EMPTY result cached by both caches
  is evicted by the exact filter's SUBSCRIBE, one key at a time;
- the generator's contract;
- the five boundary names this deployment brought.
"""

import asyncio
import sys
import time

import pytest

from bifromq_tpu import trace
from bifromq_tpu.models.oracle import UNCAPPED_FANOUT
from bifromq_tpu.trace import names
from rehearsal_broker import BENCH, rehearsal_broker, rehearsal_config

if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference  # noqa: E402
from generators import device_fleet  # noqa: E402

NEW_NAMES = ("unsub.route", "match.cache.lookups", "match.cache.hits",
             "match.cache.evict_exact", "match.no_route", "patch.regrow")


def small_cfg(table_seed: int = 0) -> dict:
    return rehearsal_config(
        "rehearsal_p2p_20k", devices=2000, sites=40, roaming_devices=200,
        subscriptions=10000, table_seed=table_seed,
        topic_population={"draws": 1024, "seed": 1})


def totals_since(t0_ns: int) -> dict:
    return trace.TRACER.totals.between(t0_ns, time.monotonic_ns()
                                       + 2_000_000_000)


class TestBrokerAgainstReference:
    @pytest.mark.parametrize("table_seed", [0, 1, 20260928])
    async def test_population_receiver_sets(self, table_seed):
        """Every population topic through ``DistWorker.match_batch``: the
        receiver set the plain reference gives, and nobody on a roaming
        device's topic."""
        cfg = small_cfg(table_seed)
        table = reference.Table()
        for tenant, levels, rid, dkey in device_fleet.subscriptions(cfg):
            table.add(tenant, levels, (rid, dkey))
        roaming = {"/".join(f) for f in device_fleet.roaming_filters(cfg)}
        population = device_fleet.topic_population(cfg)
        async with rehearsal_broker(cfg) as (node, _matcher, tenant, _t):
            worker = node.broker.dist.worker
            got = []
            for lo in range(0, len(population), 64):
                got += await worker.match_batch(
                    [(tenant, t) for t in population[lo:lo + 64]],
                    max_persistent_fanout=UNCAPPED_FANOUT,
                    max_group_fanout=UNCAPPED_FANOUT)
        n_roaming = 0
        for topic, m in zip(population, got):
            want = sorted(table.match(tenant, topic))
            have = sorted((r.receiver_id, r.deliverer_key) for r in m.normal)
            assert have == want, topic
            assert not m.groups
            if topic in roaming:
                n_roaming += 1
                assert have == []
            else:
                assert len(have) == 1
        assert n_roaming == len(population) // 10


class TestRoamingDeviceComesAndGoes:
    @pytest.mark.parametrize("sub_qos", [0, 1])
    async def test_cached_empty_result_is_evicted_by_key(self, sub_qos):
        """A command to a device that is offline caches an EMPTY result
        in the pub cache and in the matcher's. The device's SUBSCRIBE must
        evict exactly that key from both (no epoch bump), its UNSUBSCRIBE
        again: delivered once after the SUBACK, not after the UNSUBACK."""
        from bifromq_tpu.mqtt.client import MQTTClient
        cfg = small_cfg()
        topic = "/".join(device_fleet.roaming_filters(cfg)[3])
        other = "/".join(device_fleet.roaming_filters(cfg)[4])
        async with rehearsal_broker(cfg) as (node, matcher, tenant, _t):
            dist = node.broker.dist
            dist.MATCH_CACHE_TTL = 3600.0     # only an eviction can help
            caches = (dist._match_cache, matcher.match_cache)
            pub = MQTTClient("127.0.0.1", node.broker.port, client_id="be",
                             username=f"{tenant}/backend")
            dev = MQTTClient("127.0.0.1", node.broker.port, client_id="dv",
                             username=f"{tenant}/device")
            await pub.connect()
            await dev.connect()
            # slices are whole seconds: the case before this one may have
            # ended inside the same second
            trace.TRACER.totals.clear()
            t0 = time.monotonic_ns()
            for t in (topic, other):           # nobody there: acked, cached
                await pub.publish(t, b"offline", qos=1)
            assert all(len(c) == 2 for c in caches)
            bumps = [c.epoch_bumps for c in caches]
            no_route = totals_since(t0)["match.no_route"][0]
            assert no_route == 2

            await dev.subscribe(topic, qos=sub_qos)
            assert all(len(c) == 1 for c in caches), "sibling key kept"
            await pub.publish(topic, b"online", qos=1)
            got = await dev.recv(timeout=5.0)
            assert (got.topic, got.payload) == (topic, b"online")

            await dev.unsubscribe(topic)
            await pub.publish(topic, b"gone", qos=1)
            await pub.publish(other, b"fence", qos=1)
            with pytest.raises(asyncio.TimeoutError):
                await dev.recv(timeout=0.3)
            totals = totals_since(t0)
            await pub.disconnect()
            await dev.disconnect()
        assert [c.epoch_bumps for c in caches] == bumps
        # SUBSCRIBE: the empty result out of both caches; UNSUBSCRIBE: the
        # one-receiver result out of both
        assert totals["match.cache.evict_exact"][0] == 4
        # "gone", and the fence on the sibling's still-cached empty result
        assert totals["match.no_route"][0] == no_route + 2
        assert totals["unsub.route"][0] == 1
        assert totals["sub.route"][0] == 1


class TestGenerator:
    CFG = small_cfg()

    @pytest.mark.parametrize("what", [
        "pure_in_table_seed", "row_count", "no_wildcard", "one_receiver",
        "roaming_absent", "population", "source_hands_out_once"])
    def test_contract(self, what):
        cfg = self.CFG
        rows = list(device_fleet.subscriptions(cfg))
        filters = {r[1] for r in rows}
        roaming = device_fleet.roaming_filters(cfg)
        if what == "pure_in_table_seed":
            assert rows == list(device_fleet.subscriptions(dict(cfg)))
            assert rows != list(device_fleet.subscriptions(
                dict(cfg, table_seed=1)))
        elif what == "row_count":
            assert len(rows) == cfg["devices"] * cfg["filters_per_device"]
            assert device_fleet.tenant_sizes(cfg) == [("tenant0", len(rows))]
            sizes = device_fleet.site_sizes(cfg["sites"], cfg["devices"])
            assert sum(sizes) == cfg["devices"] and min(sizes) >= 1
            assert sizes == sorted(sizes, reverse=True)
        elif what == "no_wildcard":
            for levels in list(filters) + roaming:
                assert len(levels) == 5
                assert "+" not in levels and "#" not in levels
        elif what == "one_receiver":
            assert len(filters) == len(rows)        # no filter twice
            assert len({r[2] for r in rows}) == cfg["devices"]
            assert {r[3] for r in rows} == {
                f"d{i}" for i in range(cfg["deliverer_keys"])}
        elif what == "roaming_absent":
            assert len(roaming) == len(set(roaming)) \
                == cfg["roaming_devices"]
            assert not filters & set(roaming)
        elif what == "population":
            pop = device_fleet.topic_population(cfg)
            assert len(pop) == cfg["topic_population"]["draws"]
            assert pop == device_fleet.topic_population(dict(cfg))
            own = {"/".join(f) for f in filters}
            away = {"/".join(f) for f in roaming}
            for i, topic in enumerate(pop):
                assert topic in (away if i % 10 == 9 else own), (i, topic)
            for topic in device_fleet.stress_topics(cfg):
                assert topic not in own and topic not in away
        elif what == "source_hands_out_once":
            import random
            src = device_fleet.FilterSource(cfg)
            rng = random.Random(5)
            drawn = [src.draw(rng, wildcard_only=bool(i % 2))
                     for i in range(cfg["roaming_devices"])]
            assert len(set(drawn)) == len(drawn)
            assert set(drawn) == {"/".join(f) for f in roaming}
            with pytest.raises(ValueError):
                src.draw(rng)


class TestNewBoundaryNames:
    @pytest.mark.parametrize("name", NEW_NAMES)
    def test_registered(self, name):
        row = names.BOUNDARIES[name]
        assert row.kind == ("span" if name == "unsub.route" else "counter")
        assert f"`{name}`" in names.readme_table()

    @pytest.mark.parametrize("name", NEW_NAMES)
    async def test_fires(self, name):
        """Each name is recorded by the code path its row names."""
        from bifromq_tpu.mqtt.client import MQTTClient
        cfg = small_cfg()
        topic = "/".join(device_fleet.roaming_filters(cfg)[7])
        t0 = time.monotonic_ns()
        async with rehearsal_broker(cfg) as (node, _m, tenant, _t):
            # pub cache off: a repeat is the MATCHER's cache's to answer
            node.broker.dist.MATCH_CACHE_TTL = 0.0
            c = MQTTClient("127.0.0.1", node.broker.port, client_id="nm",
                           username=f"{tenant}/nm")
            await c.connect()
            await c.publish(topic, b"x", qos=1)       # lookups, no_route
            await c.subscribe(topic, qos=1)           # evict_exact
            await c.publish(topic, b"y", qos=1)       # flush: patch.regrow
            await c.publish(topic, b"z", qos=1)       # a hit
            await c.unsubscribe(topic)                # unsub.route
            await c.disconnect()
        totals = totals_since(t0)
        assert name in totals, sorted(totals)
        n = totals[name][0]
        if name == "patch.regrow":
            assert n == 0         # a reading of none, not an absence
        elif name == "match.cache.lookups":
            assert n >= totals["match.cache.hits"][0] >= 0
        else:
            assert n >= 1


class TestChurnFlushShapes:
    @pytest.mark.parametrize("coalesced", [1, 3, 17, 80])
    def test_a_flush_never_meets_a_new_scatter_shape(self, coalesced):
        """However many exact-filter mutations share one device flush,
        the scatters it issues are of the one ``_PATCH_CHUNK`` shape the
        first flush compiled: nothing is traced on the serving path
        (``compiles_in_window`` of the cell). One such op dirties 4 node
        rows and 3 edge buckets; 80 of them fill several chunks."""
        import jax
        import sut
        from bifromq_tpu.models.matcher import TpuMatcher
        from bifromq_tpu.models.oracle import Route
        from bifromq_tpu.ops import match as om
        from bifromq_tpu.types import RouteMatcher, RouteMatcherType
        cfg = small_cfg()
        tries, _n = sut.build_tries(device_fleet.subscriptions(cfg))
        m = TpuMatcher.from_tries(tries, device=jax.devices()[0])
        if not m._patching_enabled():
            pytest.skip("patch plane disabled in this environment")
        filters = device_fleet.roaming_filters(cfg)

        def churn(lo, n):
            for levels in filters[lo:lo + n]:
                m.add_route("tenant0", Route(
                    matcher=RouteMatcher(
                        type=RouteMatcherType.NORMAL, filter_levels=levels,
                        mqtt_topic_filter="/".join(levels)),
                    broker_id=0, receiver_id="/".join(levels),
                    deliverer_key="k"))
            got = m.match_batch([("tenant0", list(filters[lo]))])
            assert [r.receiver_id for r in got[0].normal] \
                == ["/".join(filters[lo])]

        def programs():
            return (om._scatter_rows._cache_size()
                    + om._scatter_rows_donated._cache_size())
        churn(0, 1)                     # the first flush compiles the shape
        before, flushes = programs(), m.patch_flushes
        churn(1, coalesced)
        assert m.patch_flushes == flushes + 1
        assert programs() == before

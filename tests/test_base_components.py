"""Base infrastructure components: sysprops, env/mem-pressure, hookloader,
MDC logger, AsyncRunner/retry/rendezvous, dist GC sweep, cross-node
session dict, client balancer redirect, connection admission."""

import asyncio
import logging
import os

import pytest

from bifromq_tpu.mqtt.broker import MQTTBroker
from bifromq_tpu.mqtt.client import MQTTClient, MQTTClientError
from bifromq_tpu.utils import sysprops
from bifromq_tpu.utils.async_util import (AsyncRunner, RendezvousHash,
                                          async_retry)
from bifromq_tpu.utils.env import EnvProvider, MemUsage
from bifromq_tpu.utils.hookloader import load_hook, load_optional
from bifromq_tpu.utils.logger import mdc_logger

pytestmark = pytest.mark.asyncio


class TestSysProps:
    def test_default_env_override_precedence(self):
        p = sysprops.SysProp.DIST_MATCH_PARALLELISM
        sysprops.override(p, None)
        assert sysprops.get(p) == 4
        os.environ["BIFROMQ_DIST_MATCH_PARALLELISM"] = "9"
        sysprops._cache.pop(p, None)
        try:
            assert sysprops.get(p) == 9
            sysprops.override(p, 2)
            assert sysprops.get(p) == 2
        finally:
            del os.environ["BIFROMQ_DIST_MATCH_PARALLELISM"]
            sysprops.override(p, None)
            sysprops._cache.pop(p, None)

    def test_bad_value_falls_back_to_default(self):
        p = sysprops.SysProp.MATCH_WALK_WIDTH
        os.environ["BIFROMQ_MATCH_WALK_WIDTH"] = "not-a-number"
        sysprops._cache.pop(p, None)
        try:
            assert sysprops.get(p) == 16
        finally:
            del os.environ["BIFROMQ_MATCH_WALK_WIDTH"]
            sysprops._cache.pop(p, None)


class TestEnv:
    def test_mem_usage_probe(self):
        m = MemUsage(budget_bytes=1 << 40, sample_interval=0)
        assert 0 <= m.usage() < 0.01
        assert not m.under_pressure()
        tiny = MemUsage(budget_bytes=1, sample_interval=0)
        assert tiny.under_pressure()

    def test_env_provider_named_executor(self):
        env = EnvProvider()
        pool = env.executor("test-pool", max_workers=1)
        assert pool is env.executor("test-pool")
        name = pool.submit(lambda: __import__("threading")
                           .current_thread().name).result()
        assert name.startswith("test-pool")
        env.shutdown()


class TestHookLoader:
    def test_load_and_cache(self):
        h1 = load_hook("bifromq_tpu.plugin.auth:AllowAllAuthProvider")
        h2 = load_hook("bifromq_tpu.plugin.auth:AllowAllAuthProvider")
        assert h1 is h2

    def test_type_check_and_optional_fallback(self):
        from bifromq_tpu.plugin.throttler import IResourceThrottler
        with pytest.raises(TypeError):
            load_hook("bifromq_tpu.plugin.auth:AuthData", IResourceThrottler)
        sentinel = object()
        assert load_optional("no.such.module:X", default=sentinel) is sentinel
        assert load_optional(None, default=sentinel) is sentinel


class TestMDCLogger:
    def test_context_tags_prefix(self, caplog):
        log = mdc_logger("t.mdc", storeId="s1").with_context(rangeId="r7")
        with caplog.at_level(logging.INFO, logger="t.mdc"):
            log.info("applied %d", 3)
        assert "[rangeId=r7 storeId=s1] applied 3" in caplog.text


class TestAsyncUtil:
    async def test_async_runner_fifo(self):
        runner = AsyncRunner()
        seen = []

        async def job(i, delay):
            await asyncio.sleep(delay)
            seen.append(i)
            return i

        futs = [runner.submit(lambda i=i, d=0.02 - i * 0.005: job(i, d))
                for i in range(4)]
        results = await asyncio.gather(*futs)
        assert results == [0, 1, 2, 3]
        assert seen == [0, 1, 2, 3]  # strict FIFO despite inverse delays

    async def test_async_retry_backoff(self):
        attempts = []

        async def flaky():
            attempts.append(1)
            if len(attempts) < 3:
                raise ValueError("try again")
            return "done"

        out = await async_retry(flaky, retries=4, base_delay=0.001)
        assert out == "done" and len(attempts) == 3
        with pytest.raises(ValueError):
            await async_retry(flaky_always, retries=1, base_delay=0.001)

    async def test_rendezvous_stability(self):
        rh = RendezvousHash(["a", "b", "c"])
        before = {f"k{i}": rh.pick(f"k{i}") for i in range(100)}
        rh.remove("b")
        moved = sum(1 for k, v in before.items()
                    if v != "b" and rh.pick(k) != v)
        assert moved == 0  # only keys on the removed node move
        assert len(rh.ranked("k1", 2)) == 2


async def flaky_always():
    raise ValueError("always")


class TestDistGC:
    async def test_gc_sweep_removes_dead_routes(self):
        broker = MQTTBroker(host="127.0.0.1", port=0)
        await broker.start()
        try:
            c = MQTTClient("127.0.0.1", broker.port, client_id="gc1")
            await c.connect()
            await c.subscribe("gc/+", qos=0)
            assert len(list(broker.dist.worker.space.iterate())) == 1
            # simulate a dead receiver: session vanishes without unroute
            broker.local_sessions._by_id.clear()
            removed = await broker.dist.gc_sweep()
            assert removed == 1
            assert len(list(broker.dist.worker.space.iterate())) == 0
            await c.disconnect()
        finally:
            await broker.stop()


class TestSessionDict:
    async def test_cluster_wide_kick_and_exist(self):
        from bifromq_tpu.rpc.fabric import RPCServer, ServiceRegistry
        from bifromq_tpu.sessiondict import (SessionDictClient,
                                             SessionDictRPCService)
        from bifromq_tpu.sessiondict.service import SERVICE

        reg = ServiceRegistry()
        brokers, servers = [], []
        for _ in range(2):
            b = MQTTBroker(host="127.0.0.1", port=0)
            await b.start()
            srv = RPCServer()
            SessionDictRPCService(b).register(srv)
            await srv.start()
            reg.announce(SERVICE, srv.address)
            b.session_dict = SessionDictClient(reg,
                                              self_address=srv.address)
            brokers.append(b)
            servers.append(srv)
        try:
            c1 = MQTTClient("127.0.0.1", brokers[0].port, client_id="dup",
                            protocol_level=5)
            await c1.connect()
            sd = brokers[1].session_dict
            assert await sd.exist("DevOnly", ["dup", "ghost"]) == [True,
                                                                   False]
            # same client id connects to broker B: A's session is kicked
            c2 = MQTTClient("127.0.0.1", brokers[1].port, client_id="dup",
                            protocol_level=5)
            await c2.connect()
            await asyncio.wait_for(c1.closed.wait(), 5)
            assert brokers[0].session_registry.get("DevOnly", "dup") is None
            assert brokers[1].session_registry.get("DevOnly",
                                                   "dup") is not None
            await c2.disconnect()
        finally:
            for b in brokers:
                await b.stop()
            for s in servers:
                await s.stop()


class TestSessionDictOnBehalf:
    async def test_cross_node_sub_unsub_and_inbox_state(self):
        """Sub/unsub/inboxState on behalf of a session hosted on ANOTHER
        broker (≈ SessionDictService.proto:38-40): the dict fans the call
        out and the hosting broker's live session applies it."""
        from bifromq_tpu.rpc.fabric import RPCServer, ServiceRegistry
        from bifromq_tpu.sessiondict import (SessionDictClient,
                                             SessionDictRPCService)
        from bifromq_tpu.sessiondict.service import SERVICE

        reg = ServiceRegistry()
        brokers, servers = [], []
        for _ in range(2):
            b = MQTTBroker(host="127.0.0.1", port=0)
            await b.start()
            srv = RPCServer()
            SessionDictRPCService(b).register(srv)
            await srv.start()
            reg.announce(SERVICE, srv.address)
            b.session_dict = SessionDictClient(reg,
                                              self_address=srv.address)
            brokers.append(b)
            servers.append(srv)
        try:
            c = MQTTClient("127.0.0.1", brokers[0].port, client_id="ob",
                           protocol_level=5)
            await c.connect()
            # call through broker B's dict — session lives on broker A
            sd = brokers[1].session_dict
            assert await sd.sub("DevOnly", "ob", "ob/+", 1) == "ok"
            assert await sd.sub("DevOnly", "ob", "ob/+", 1) == "exists"
            state = await sd.inbox_state("DevOnly", "ob")
            assert state is not None
            assert state["subscriptions"]["ob/+"]["qos"] == 1
            # traffic published on broker A reaches the on-behalf sub
            p = MQTTClient("127.0.0.1", brokers[0].port, client_id="obp")
            await p.connect()
            await p.publish("ob/x", b"cross", qos=1)
            msg = await asyncio.wait_for(c.messages.get(), 5)
            assert msg.payload == b"cross"
            assert await sd.unsub("DevOnly", "ob", "ob/+") == "ok"
            assert await sd.unsub("DevOnly", "ob", "ob/+") == "no_sub"
            assert await sd.sub("DevOnly", "ghost", "g/+", 0) \
                == "no_session"
            assert await sd.inbox_state("DevOnly", "ghost") is None
            await p.disconnect()
            await c.disconnect()
        finally:
            for b in brokers:
                await b.stop()
            for s in servers:
                await s.stop()


class TestClientBalancer:
    async def test_redirect_on_connect(self):
        from bifromq_tpu.plugin.balancer import (IClientBalancer,
                                                 RedirectType,
                                                 ServerRedirection)

        class MoveAll(IClientBalancer):
            def need_redirect(self, client):
                return ServerRedirection(RedirectType.TEMPORARY,
                                         "other:1883")

        broker = MQTTBroker(host="127.0.0.1", port=0, balancer=MoveAll())
        await broker.start()
        try:
            c = MQTTClient("127.0.0.1", broker.port, client_id="r",
                           protocol_level=5)
            with pytest.raises(MQTTClientError, match="156"):
                await c.connect()
            from bifromq_tpu.mqtt.protocol import PropertyId
            assert c.connack.properties[
                PropertyId.SERVER_REFERENCE] == "other:1883"
        finally:
            await broker.stop()


class TestAdmission:
    async def test_mem_pressure_rejects_connections(self):
        broker = MQTTBroker(host="127.0.0.1", port=0,
                            mem_usage=MemUsage(budget_bytes=1,
                                               sample_interval=0))
        await broker.start()
        try:
            c = MQTTClient("127.0.0.1", broker.port, client_id="x")
            with pytest.raises(Exception):
                await c.connect(timeout=2)
        finally:
            await broker.stop()


class TestClusteredStarter:
    async def test_two_standalone_nodes_cluster_wide_kick(self):
        from bifromq_tpu.starter import Standalone

        n1 = Standalone({"mqtt": {"host": "127.0.0.1", "tcp": {"port": 0}},
                         "cluster": {"node_id": "sn1", "port": 0}})
        await n1.start()
        n2 = Standalone({
            "mqtt": {"host": "127.0.0.1", "tcp": {"port": 0}},
            "cluster": {"node_id": "sn2", "port": 0,
                        "seeds": [f"127.0.0.1:{n1.agent_host.port}"]}})
        await n2.start()
        try:
            # wait for gossip to spread the session-dict endpoints
            for _ in range(200):
                if (n1.broker.session_dict.registry.endpoints(
                        "session-dict")
                        and len(n2.broker.session_dict.registry.endpoints(
                            "session-dict")) >= 2):
                    break
                await asyncio.sleep(0.02)
            c1 = MQTTClient("127.0.0.1", n1.broker.port, client_id="one",
                            protocol_level=5)
            await c1.connect()
            c2 = MQTTClient("127.0.0.1", n2.broker.port, client_id="one",
                            protocol_level=5)
            await c2.connect()
            await asyncio.wait_for(c1.closed.wait(), 5)
            assert n1.broker.session_registry.get("DevOnly", "one") is None
            await c2.disconnect()
        finally:
            await n2.stop()
            await n1.stop()


class TestClusteredDistPlane:
    async def test_frontends_share_worker_with_cross_broker_delivery(self):
        """Full clustered topology from YAML alone: worker node W hosts
        the route table; frontends A and B run dist.mode=remote; a
        subscriber on A receives a publish made on B — match on W,
        delivery via the cross-broker deliverer RPC hop to A
        (≈ mqtt-frontend -> dist-worker -> mqtt-broker-client deliver)."""
        from bifromq_tpu.starter import Standalone

        w = Standalone({"mqtt": {"host": "127.0.0.1", "tcp": {"port": 0}},
                        "dist": {"mode": "worker"},
                        "cluster": {"node_id": "w", "port": 0}})
        await w.start()
        seeds = [f"127.0.0.1:{w.agent_host.port}"]
        fa = Standalone({"mqtt": {"host": "127.0.0.1", "tcp": {"port": 0}},
                         "dist": {"mode": "remote"},
                         "cluster": {"node_id": "fa", "port": 0,
                                     "seeds": seeds}})
        fb = Standalone({"mqtt": {"host": "127.0.0.1", "tcp": {"port": 0}},
                         "dist": {"mode": "remote"},
                         "cluster": {"node_id": "fb", "port": 0,
                                     "seeds": seeds}})
        await fa.start()
        await fb.start()
        try:
            # wait for gossip: frontends must see the worker AND each
            # other's deliverer endpoints
            from bifromq_tpu.dist.deliverer import SERVICE_PREFIX
            from bifromq_tpu.dist.remote import SERVICE as DW

            def ready():
                reg_a = fa.broker.dist.deliverer_registry
                reg_b = fb.broker.dist.deliverer_registry
                return (reg_a.endpoints(DW) and reg_b.endpoints(DW)
                        and reg_b.endpoints(
                            f"{SERVICE_PREFIX}:"
                            f"{fa.broker.server_id}"))
            for _ in range(400):
                if ready():
                    break
                await asyncio.sleep(0.02)
            assert ready()

            sub = MQTTClient("127.0.0.1", fa.broker.port, client_id="xa")
            await sub.connect()
            await sub.subscribe("xnode/+", qos=1)
            pub = MQTTClient("127.0.0.1", fb.broker.port, client_id="xb")
            await pub.connect()
            await pub.publish("xnode/t", b"crossed-brokers", qos=1)
            msg = await asyncio.wait_for(sub.messages.get(), 10)
            assert msg.payload == b"crossed-brokers"
            await sub.disconnect()

            # persistent session on A: a publish on B must persist into
            # A's inbox STORE (server-prefixed inbox deliverer key) and
            # reach the session when it reconnects to A
            ps = MQTTClient("127.0.0.1", fa.broker.port, client_id="px",
                            clean_start=False)
            await ps.connect()
            await ps.subscribe("xinbox/+", qos=1)
            await ps.disconnect()
            await pub.publish("xinbox/t", b"stored-on-A", qos=1)
            await asyncio.sleep(0.3)
            ps2 = MQTTClient("127.0.0.1", fa.broker.port, client_id="px",
                             clean_start=False)
            await ps2.connect()
            msg = await asyncio.wait_for(ps2.messages.get(), 10)
            assert msg.payload == b"stored-on-A"
            await ps2.disconnect()
            await pub.disconnect()
        finally:
            await fb.stop()
            await fa.stop()
            await w.stop()


class TestElasticityFromYAML:
    async def test_split_threshold_via_starter_config(self):
        """Route-table elasticity configured purely in YAML: enough
        subscriptions trip the key-count split balancer."""
        from bifromq_tpu.starter import Standalone

        node = Standalone({
            "mqtt": {"host": "127.0.0.1", "tcp": {"port": 0}},
            "dist": {"split_threshold": 60}})
        await node.start()
        try:
            worker = node.broker.dist.worker
            assert worker.balance_controller is not None
            c = MQTTClient("127.0.0.1", node.broker.port, client_id="ey")
            await c.connect()
            for i in range(100):
                await c.subscribe(f"ey/{i:03d}/+", qos=0)
            ok = False
            for _ in range(100):
                if len(worker.store.ranges) >= 2:
                    ok = True
                    break
                await asyncio.sleep(0.1)
            assert ok, worker.store.describe()
            # routing still exact across the split
            await c.publish("ey/042/x", b"post-split", qos=1)
            msg = await asyncio.wait_for(c.messages.get(), 10)
            assert msg.payload == b"post-split"
            await c.disconnect()
        finally:
            await node.stop()


class TestSortedBytesMap:
    """The in-memory KV's key order is merged on demand (PR 27: an insort
    per put made 1M-route bulk loads quadratic) — ordered reads must still
    see every interleaving of puts and deletes exactly sorted."""

    def test_random_interleaving_matches_sorted_dict(self):
        import random
        from bifromq_tpu.kv.engine import _SortedBytesMap
        rng = random.Random(5)
        m, ref = _SortedBytesMap(), {}
        for step in range(4000):
            k = b"k%04d" % rng.randrange(600)
            op = rng.random()
            if op < 0.6:
                m.put(k, b"v%d" % step)
                ref[k] = b"v%d" % step
            elif op < 0.8:
                m.delete(k)
                ref.pop(k, None)
            elif op < 0.85:
                lo, hi = sorted((b"k%04d" % rng.randrange(600),
                                 b"k%04d" % rng.randrange(600)))
                m.delete_range(lo, hi)
                for d in [x for x in ref if lo <= x < hi]:
                    del ref[d]
            else:
                lo = b"k%04d" % rng.randrange(600)
                assert list(m.scan(lo, None)) == sorted(
                    (x, v) for x, v in ref.items() if x >= lo)
        assert list(m.scan(None, None)) == sorted(ref.items())
        assert list(m.scan(None, None, reverse=True)) == sorted(
            ref.items(), reverse=True)
        assert len(m) == len(ref)

    @pytest.mark.parametrize("tail", [1, 64, 65, 300])
    def test_short_and_long_tails_merge_alike(self, tail):
        """A pending tail is placed key by key up to ``INSORT_MAX`` and
        re-sorted with the list beyond it (a live UNSUBSCRIBE at 1M keys is
        the first, a bulk load the second): same order either way."""
        import random
        from bifromq_tpu.kv.engine import _SortedBytesMap
        assert _SortedBytesMap.INSORT_MAX == 64
        rng = random.Random(tail)
        m = _SortedBytesMap()
        base = [b"%08d" % rng.randrange(10**8) for _ in range(2000)]
        for k in base:
            m.put(k, b"b")
        m.delete(base[0])                       # merges the bulk tail
        new = [b"%08d" % rng.randrange(10**8) for _ in range(tail)]
        for k in new:
            m.put(k, b"n")
        assert len(m._pending) == len(set(new) - set(base[1:]))
        m.delete(base[1])                       # merges this tail
        want = sorted((set(base) | set(new)) - {base[0], base[1]})
        assert m._keys == want and not m._pending
        assert [k for k, _ in m.scan(None, None)] == want

    def test_bulk_puts_defer_the_sort_and_copy_sees_them(self):
        from bifromq_tpu.kv.engine import _SortedBytesMap
        m = _SortedBytesMap()
        for i in reversed(range(5000)):
            m.put(b"%06d" % i, b"")
        assert len(m._pending) == 5000 and not m._keys     # O(1) puts
        c = m.copy()
        assert [k for k, _ in c.scan(None, None)] == [
            b"%06d" % i for i in range(5000)]
        assert not m._pending                              # merged once

"""Retained messages on SUBSCRIBE: the session's retained backlog and the
native walker's answer for '+'-overflow rows over a tombstoned index.

- A transient session keeps a SUBSCRIBE's matched retained messages that
  found the send window full and sends them, in order, as PUBACKs free
  packet ids; an UNSUBSCRIBE of the filter discards what is still queued.
- ``RetainedIndex.expand_scan`` answers a flagged '+' row with the native
  walker whenever no patch-era extra exists, tombstones or not; with an
  extra the row falls to the host oracle, and each path counts its rows.
"""

import asyncio
import collections
import random

import pytest

from bifromq_tpu.models import retained as retained_mod
from bifromq_tpu.models.retained import RetainedIndex, match_filter_host
from bifromq_tpu.mqtt.broker import MQTTBroker
from bifromq_tpu.mqtt.client import MQTTClient
from bifromq_tpu.mqtt import packets as pk
from bifromq_tpu.mqtt.protocol import PropertyId
from bifromq_tpu.trace import TRACER
from bifromq_tpu.trace.names import BOUNDARIES
from bifromq_tpu.utils import topic as topic_util

try:
    from bifromq_tpu.models.native_retained import load_lib
    load_lib()
    HAVE_NATIVE = True
except Exception:  # noqa: BLE001 — no toolchain
    HAVE_NATIVE = False

LIMIT = 10          # the program's default RetainMessageMatchLimit
WINDOW = 8          # the program's default MinSendPerSec, the window floor


@pytest.fixture
def counts(monkeypatch):
    """Every ``trace.count`` of the test, by name."""
    seen = collections.Counter()
    real = TRACER.count

    def count(name, k=1):
        seen[name] += k
        real(name, k)
    monkeypatch.setattr(TRACER, "count", count)
    return seen


class HoldingClient(MQTTClient):
    """A client that PUBACKs a QoS 1 PUBLISH only when told to."""

    def __init__(self, *a, **kw) -> None:
        super().__init__(*a, **kw)
        self.held = []
        self.hold = False

    async def _on_packet(self, p) -> None:
        if self.hold and isinstance(p, pk.Publish) and p.qos == 1:
            self.held.append(p.packet_id)
            await self.messages.put(p)
            return
        await super()._on_packet(p)

    async def ack_held(self) -> None:
        for pid in self.held:
            await self._send(pk.PubAck(packet_id=pid))
        self.held.clear()


async def _seed(broker, prefix: str, n: int) -> None:
    p = MQTTClient(port=broker.port, client_id=f"{prefix}-pub")
    await p.connect()
    for i in range(n):
        await p.publish(f"{prefix}/d{i:02d}", f"v{i}".encode(), qos=1,
                        retain=True)
    await p.disconnect()


async def _subscriber(broker, client_id: str, cls=MQTTClient):
    """A v5 session whose QoS 1 window is the program's floor."""
    c = cls(port=broker.port, client_id=client_id, protocol_level=5,
            properties={PropertyId.RECEIVE_MAXIMUM: WINDOW})
    await c.connect()
    session = next(s for s in broker.session_registry._owners.values()
                   if s.client_id == client_id)
    return c, session


async def _drain(c, timeout: float = 0.4) -> list:
    got = []
    while True:
        try:
            got.append(await c.recv(timeout=timeout))
        except asyncio.TimeoutError:
            return got


@pytest.mark.asyncio
class TestRetainedBacklog:
    @pytest.mark.parametrize("n", [3, WINDOW, WINDOW + 1, LIMIT, 12, 30])
    async def test_qos1_subscribe_is_handed_min_limit_n_in_order(
            self, n, counts):
        broker = MQTTBroker(port=0)
        await broker.start()
        try:
            prefix = f"win{n}"
            await _seed(broker, prefix, n)
            c, session = await _subscriber(broker, f"{prefix}-sub")
            await c.subscribe(f"{prefix}/+", qos=1)
            got = await _drain(c)
            want = [t for t, _m in await broker.retain_service.match(
                session.client_info.tenant_id, [prefix, "+"], LIMIT)]
            assert len(want) == min(LIMIT, n)
            assert [m.topic for m in got] == want
            assert all(m.retain and m.qos == 1 for m in got)
            assert counts["retain.deliver.deferred"] == max(
                0, min(LIMIT, n) - WINDOW)
            assert not session._retained_backlog
            await c.disconnect()
        finally:
            await broker.stop()

    @pytest.mark.parametrize("unsubscribe, more", [(True, 0),
                                                   (False, LIMIT - WINDOW)])
    async def test_unsubscribe_discards_the_queued_messages(
            self, unsubscribe, more, counts):
        broker = MQTTBroker(port=0)
        await broker.start()
        try:
            await _seed(broker, "uq", 12)
            c, session = await _subscriber(broker, "uq-sub", HoldingClient)
            c.hold = True
            await c.subscribe("uq/+", qos=1)
            first = await _drain(c)
            assert len(first) == WINDOW
            assert len(session._retained_backlog) == LIMIT - WINDOW
            assert counts["retain.deliver.deferred"] == LIMIT - WINDOW
            if unsubscribe:
                await c.unsubscribe("uq/+")
                assert not session._retained_backlog
            c.hold = False
            await c.ack_held()
            rest = await _drain(c)
            assert len(rest) == more
            assert len({m.topic for m in first + rest}) == WINDOW + more
            assert not session._retained_backlog
            await c.disconnect()
        finally:
            await broker.stop()


# ---------------------------------------------------------------- walker

SITES, DEVICES = 3, 40          # 40 children under one '+': past 32 states
ATTRS = ("$state", "$name", "temperature", "battery", "rssi")


def _homie(rng: random.Random, tenants=("t0", "t1")):
    """Seeded Homie topics; a few devices miss a few attributes."""
    out = []
    for tenant in tenants:
        for s in range(SITES):
            for d in range(DEVICES):
                for a in ATTRS:
                    if rng.random() < 0.95:
                        out.append((tenant, f"homie/s{s}/d{d}/{a}"))
    return out


def _matches(flt: str, topic: str) -> bool:
    fl, tl = flt.split("/"), topic.split("/")
    for i, f in enumerate(fl):
        if f == "#":
            return True
        if i >= len(tl) or (f != "+" and f != tl[i]):
            return False
    return len(fl) == len(tl)


def _index(topics) -> RetainedIndex:
    idx = RetainedIndex()
    for tenant, topic in topics:
        idx.add_topic(tenant, topic_util.parse(topic), topic)
    idx.refresh()
    return idx


def _churn(idx, rng: random.Random, live: set, steps: int) -> None:
    """CLEARs, re-SETs of cleared topics and SETs of live ones, all on
    seeded topics: tombstones come and go, no patch-era slot appears."""
    cleared = []
    for _ in range(steps):
        r = rng.random()
        if r < 0.5 and live:
            tenant, topic = rng.choice(sorted(live))
            assert idx.remove_topic(tenant, topic_util.parse(topic), topic)
            live.discard((tenant, topic))
            cleared.append((tenant, topic))
        elif r < 0.75 and cleared:
            tenant, topic = cleared.pop(rng.randrange(len(cleared)))
            idx.add_topic(tenant, topic_util.parse(topic), topic)
            live.add((tenant, topic))
        elif live:
            tenant, topic = rng.choice(sorted(live))
            idx.add_topic(tenant, topic_util.parse(topic), topic)


def _site_attr_rows(rng: random.Random):
    return [(f"t{rng.randrange(2)}",
             ["homie", f"s{rng.randrange(SITES)}", "+", rng.choice(ATTRS)])
            for _ in range(12)]


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
class TestNativeWalkerOverTombstones:
    @pytest.mark.parametrize("seed, steps, limit", [
        (1, 60, LIMIT), (2, 150, LIMIT), (3, 90, 3), (4, 120, None)])
    def test_plus_rows_answered_natively_and_exactly(
            self, seed, steps, limit, counts, monkeypatch):
        rng = random.Random(seed)
        topics = _homie(rng)
        live = set(topics)
        idx = _index(topics)
        _churn(idx, rng, live, steps)
        base = idx.refresh()
        assert base.dead_slots > 0 and base.extra_live == 0

        def no_oracle(*a, **kw):
            raise AssertionError("a '+' row went to the host oracle")
        monkeypatch.setattr(retained_mod, "match_filter_host", no_oracle)
        rows = _site_attr_rows(rng)
        got = idx.match_batch(rows, limit=limit)
        for (tenant, levels), topics_got in zip(rows, got):
            flt = "/".join(levels)
            want = {t for tn, t in live if tn == tenant and _matches(flt, t)}
            bound = len(want) if limit is None else min(limit, len(want))
            assert len(topics_got) == len(set(topics_got)) == bound, flt
            assert set(topics_got) <= want, flt
        assert counts["retain.rows.native"] == len(rows)
        assert counts["retain.rows.oracle"] == 0
        assert counts["retain.rows.device"] == 0
        assert counts["retain.scan.walks"] == 1

    def test_a_patch_era_extra_sends_the_row_to_the_oracle(self, counts):
        rng = random.Random(5)
        topics = _homie(rng, tenants=("t0",))
        live = set(topics)
        idx = _index(topics)
        _churn(idx, rng, live, 40)
        new = "homie/s0/d99/$state"      # a device the seed did not have
        idx.add_topic("t0", topic_util.parse(new), new)
        live.add(("t0", new))
        assert idx.refresh().extra_live > 0
        rows = [("t0", ["homie", "s0", "+", "$state"]),
                ("t0", ["homie", "s1", "d3", "#"])]
        got = idx.match_batch(rows, limit=None)
        want = sorted(t for _tn, t in live if _matches("homie/s0/+/$state",
                                                      t))
        assert sorted(got[0]) == want and new in got[0]
        trie = idx.tries["t0"]
        assert sorted(got[1]) == sorted(match_filter_host(
            trie, ["homie", "s1", "d3", "#"]))
        assert counts["retain.rows.oracle"] == 1
        assert counts["retain.rows.native"] == 0
        assert counts["retain.rows.device"] == 1


@pytest.mark.parametrize("name, kind", [
    ("sub.retained", "span"), ("retain.scan", "span"),
    ("retain.deliver.deferred", "counter"),
    ("retain.scan.queries", "counter"), ("retain.scan.cache_hits", "counter"),
    ("retain.scan.walks", "counter"), ("retain.rows.device", "counter"),
    ("retain.rows.native", "counter"), ("retain.rows.oracle", "counter")])
def test_retained_boundaries_are_registered(name, kind):
    assert BOUNDARIES[name].kind == kind

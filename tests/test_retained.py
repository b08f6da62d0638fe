"""Retained-message index + service tests.

Device retained-walk parity against a brute-force per-topic matcher
(utils.topic.matches with roles swapped) and the host fallback; service
semantics per [MQTT-3.3.1-*] (empty-payload delete, expiry, quotas).
Mirrors reference RetainStoreCoProc/RetainMatcher tests.
"""

import random

import pytest

from bifromq_tpu.models.retained import RetainedIndex, match_filter_host
from bifromq_tpu.plugin.events import CollectingEventCollector, EventType
from bifromq_tpu.plugin.throttler import IResourceThrottler, TenantResourceType
from bifromq_tpu.retain.service import RetainService
from bifromq_tpu.types import ClientInfo, Message, QoS
from bifromq_tpu.utils import topic as t


def brute_force(topics, filter_levels):
    """Ground truth: a filter matches a stored topic iff topic_util.matches."""
    return sorted(topic for topic in topics
                  if t.matches(t.parse(topic), list(filter_levels)))


class TestRetainedIndex:
    def build(self, topics, tenant="T", **kw):
        idx = RetainedIndex(**kw)
        for topic in topics:
            idx.add_topic(tenant, t.parse(topic), topic)
        return idx

    @pytest.mark.parametrize("tf", [
        "a/b", "a/+", "a/#", "#", "+", "+/+", "+/b", "a/b/#", "x",
        "$SYS/#", "$SYS/+", "+/health", "a/+/c",
    ])
    def test_parity_small(self, tf):
        topics = ["a/b", "a/c", "a/b/c", "b/b", "x", "$SYS/health",
                  "$SYS/x/y", "a", "c/d/e"]
        idx = self.build(topics)
        got = sorted(idx.match("T", t.parse(tf)))
        expect = brute_force(topics, t.parse(tf))
        assert got == expect, tf
        # host fallback agrees too
        host = sorted(match_filter_host(idx.tries["T"], t.parse(tf)))
        assert host == expect, tf

    def test_random_parity(self):
        rng = random.Random(5)
        alphabet = ["a", "b", "c", "", "x1", "$s"]
        topics = set()
        while len(topics) < 300:
            n = rng.randint(1, 5)
            topics.add("/".join(rng.choice(alphabet) for _ in range(n)))
        topics = sorted(topics)
        idx = self.build(topics, k_states=16)

        filters = []
        for _ in range(150):
            n = rng.randint(1, 5)
            levels = []
            for i in range(n):
                roll = rng.random()
                if roll < 0.25:
                    levels.append("+")
                elif roll < 0.35 and i == n - 1:
                    levels.append("#")
                else:
                    levels.append(rng.choice(alphabet))
            filters.append(levels)
        results = idx.match_batch([("T", f) for f in filters])
        for f, got in zip(filters, results):
            assert sorted(got) == brute_force(topics, f), f

    def test_plus_overflow_falls_back(self):
        # root has 40 children > k_states=8 → '+' overflows → host fallback
        topics = [f"t{i}/x" for i in range(40)]
        idx = self.build(topics, k_states=8)
        got = sorted(idx.match("T", ["+", "x"]))
        assert got == sorted(topics)

    def test_plus_overflow_escalates_on_device(self, monkeypatch):
        """40 children > k_states=8 but < esc_k=64: the second device pass
        rescues the row; the Python oracle must never run (on a 1M-topic
        trie a single '#'-tailed oracle walk costs seconds)."""
        from bifromq_tpu.models import retained as mod
        topics = [f"t{i}/x" for i in range(40)]
        idx = self.build(topics, k_states=8)

        def boom(*a, **k):
            raise AssertionError("host oracle used despite escalation")
        monkeypatch.setattr(mod, "match_filter_host", boom)
        got = sorted(idx.match("T", ["+", "x"]))
        assert got == sorted(topics)
        # beyond even esc_k: the oracle IS the correct last resort
        monkeypatch.undo()
        many = [f"m{i}" for i in range(300)]     # 300 roots > 8*8 esc_k=64
        idx2 = self.build(many, k_states=8)
        assert sorted(idx2.match("T", ["+"])) == sorted(many)

    def test_remove(self):
        idx = self.build(["a/b", "a/c"])
        idx.remove_topic("T", ["a", "b"], "a/b")
        assert idx.match("T", ["a", "+"]) == ["a/c"]

    def test_unknown_tenant(self):
        idx = self.build(["a"])
        assert idx.match("nobody", ["a"]) == []

    def test_multi_tenant(self):
        idx = RetainedIndex()
        idx.add_topic("t1", ["a"], "a")
        idx.add_topic("t2", ["a"], "a")
        idx.remove_topic("t1", ["a"], "a")
        assert idx.match("t1", ["a"]) == []
        assert idx.match("t2", ["a"]) == ["a"]


@pytest.mark.parametrize("seed", [0, 1])
def test_bucket_lookup_matches_edge_lookup(seed):
    """The retained walk's direct bucket gather answers every key as the
    publish walk's [NB, P*4] view does: hits, absent keys (some of them
    hashing into a FULL bucket), near misses (a stored key with one
    column changed, in the stored key's bucket), and the dead states'
    ``act.clip(0)`` lanes, which probe node 0 with the level's hashes."""
    import jax.numpy as jnp
    import numpy as np
    from bifromq_tpu.models import automaton as A
    from bifromq_tpu.ops import match as M
    from bifromq_tpu.ops import retained as R

    nb, p = 256, 16
    rng = np.random.default_rng(seed)
    mask = np.uint32(nb - 1)

    def bucket(keys):
        return (A._mix_u32(keys[:, 0], keys[:, 1], keys[:, 2])
                & mask).astype(np.int64)

    cand = np.concatenate([
        rng.integers(0, 64, (20_000, 1)),
        rng.integers(-2 ** 31, 2 ** 31, (20_000, 2))], axis=1).astype(
            np.int32)
    cand = np.unique(cand, axis=0)
    cand = cand[rng.permutation(len(cand))]
    per = np.zeros(nb, dtype=np.int64)
    edges = {}
    for key, b1 in zip(cand, bucket(cand)):
        if per[b1] < (p if b1 == 0 else 6):    # bucket 0 fills up
            per[b1] += 1
            edges[tuple(int(v) for v in key)] = len(edges) + 1
    tab = A._build_edge_table([k + (c,) for k, c in edges.items()], p,
                              min_cap=nb)
    assert tab.shape == (nb, p, 4)
    assert (tab[0, :, 0] != A._EMPTY).all()    # one full bucket
    stored = np.array(list(edges), dtype=np.int32)
    absent = cand[~np.isin(cand.view([("", cand.dtype)] * 3),
                           stored.view([("", stored.dtype)] * 3)).ravel()]
    assert (bucket(absent) == 0).any()
    hits = stored[rng.choice(len(stored), 256)]
    missing = absent[rng.choice(len(absent), 256)]
    dead = np.concatenate([np.zeros((64, 1), np.int32),
                           hits[:64, 1:]], axis=1)       # clip(0) lanes
    node0 = stored[stored[:, 0] == 0][:64]
    # near misses: a stored key with ONE column changed, in the same bucket
    near = []
    for col in range(3):
        base = np.repeat(stored[:32], 2048, axis=0)
        lo, hi = (0, 64) if col == 0 else (-2 ** 31, 2 ** 31)
        base[:, col] = rng.integers(lo, hi, len(base))
        same = (bucket(base) == np.repeat(bucket(stored[:32]), 2048)) \
            & (base[:, col] != np.repeat(stored[:32, col], 2048))
        near.append(base[same][:32])
    near = np.concatenate(near)
    assert not any(tuple(int(v) for v in k) in edges for k in near)
    keys = np.concatenate([hits, missing, dead, node0, near])
    keys = keys[rng.permutation(len(keys))]
    keys = keys[: len(keys) // 32 * 32].reshape(-1, 32, 3)   # [B,K,3]
    node, h1, h2 = (jnp.asarray(keys[..., i]) for i in range(3))
    dev = jnp.asarray(tab)
    got = np.asarray(R._bucket_lookup(dev, node, h1, h2))
    want = np.asarray(M._edge_lookup(dev, p, node, h1, h2))
    truth = np.array([edges.get(tuple(int(v) for v in k), -1)
                      for k in keys.reshape(-1, 3)]).reshape(got.shape)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, truth)
    assert (got > 0).sum() >= 200 and (got == -1).sum() >= 200


def mk_msg(payload=b"x", expiry=0xFFFFFFFF):
    return Message(message_id=0, pub_qos=QoS.AT_MOST_ONCE, payload=payload,
                   timestamp=0, expiry_seconds=expiry, is_retain=True)


PUB = ClientInfo(tenant_id="T")


class TestRetainService:
    async def test_retain_and_match(self):
        svc = RetainService(CollectingEventCollector())
        await svc.retain(PUB, "a/b", mk_msg(b"v1"))
        hits = await svc.match("T", ["a", "+"], limit=10)
        assert [(h[0], h[1].payload) for h in hits] == [("a/b", b"v1")]

    async def test_replace(self):
        svc = RetainService(CollectingEventCollector())
        await svc.retain(PUB, "a", mk_msg(b"v1"))
        await svc.retain(PUB, "a", mk_msg(b"v2"))
        hits = await svc.match("T", ["a"], limit=10)
        assert hits[0][1].payload == b"v2"
        assert svc.topic_count("T") == 1

    async def test_empty_payload_clears(self):
        ev = CollectingEventCollector()
        svc = RetainService(ev)
        await svc.retain(PUB, "a", mk_msg(b"v1"))
        await svc.retain(PUB, "a", mk_msg(b""))
        assert await svc.match("T", ["a"], limit=10) == []
        assert ev.of(EventType.RETAIN_MSG_CLEARED)

    async def test_limit(self):
        svc = RetainService(CollectingEventCollector())
        for i in range(20):
            await svc.retain(PUB, f"l/{i}", mk_msg())
        hits = await svc.match("T", ["l", "+"], limit=5)
        assert len(hits) == 5

    async def test_expiry(self):
        now = [1000.0]
        svc = RetainService(CollectingEventCollector(), clock=lambda: now[0])
        await svc.retain(PUB, "exp", mk_msg(expiry=10))
        await svc.retain(PUB, "keep", mk_msg())
        assert len(await svc.match("T", ["#"], limit=10)) == 2
        now[0] = 1011.0
        hits = await svc.match("T", ["#"], limit=10)
        assert [h[0] for h in hits] == ["keep"]
        assert svc.topic_count("T") == 1  # lazily expired

    async def test_gc(self):
        now = [0.0]
        svc = RetainService(CollectingEventCollector(), clock=lambda: now[0])
        for i in range(5):
            await svc.retain(PUB, f"g/{i}", mk_msg(expiry=5))
        now[0] = 100.0
        assert await svc.gc() == 5
        assert svc.topic_count("T") == 0

    async def test_quota(self):
        class OneTopicOnly(IResourceThrottler):
            def has_resource(self, tenant_id, rtype):
                if rtype == TenantResourceType.TOTAL_RETAIN_TOPICS:
                    return svc.topic_count(tenant_id) < 1
                return True

        ev = CollectingEventCollector()
        svc = RetainService(ev, throttler=OneTopicOnly())
        assert await svc.retain(PUB, "one", mk_msg())
        assert not await svc.retain(PUB, "two", mk_msg())
        assert await svc.retain(PUB, "one", mk_msg(b"update"))  # replace ok
        assert ev.of(EventType.MSG_RETAINED_ERROR)


class TestRetainReplicatedDurability:
    async def test_retained_messages_survive_restart(self):
        from bifromq_tpu.kv.engine import InMemKVEngine
        engine = InMemKVEngine()
        svc = RetainService(CollectingEventCollector(), engine=engine)
        await svc.retain(PUB, "keep/a", mk_msg(b"v1"))
        await svc.retain(PUB, "keep/b", mk_msg(b"v2"))
        await svc.retain(PUB, "keep/a", mk_msg(b""))  # clear one
        await svc.stop()
        # restart over the same engine: derived index rebuilds from KV
        svc2 = RetainService(CollectingEventCollector(), engine=engine)
        hits = await svc2.match("T", ["keep", "+"], limit=10)
        assert [(t, m.payload) for t, m in hits] == [("keep/b", b"v2")]
        assert svc2.topic_count("T") == 1

"""Sharded-mesh serving plane at patch speed (ISSUE 15).

Randomized mesh-vs-single-chip-vs-oracle parity under churn patches
interleaved with ASYNC mesh matches, per-shard fault domains (breaker
open/canary recovery, one hung shard degrading only its own rows),
mid-flight compaction snapshot discipline, mesh base replication (v2
compressed codec, per-shard arena parity on a warm standby), and the
replicated-hot-tenant dedup in the /cluster/capacity logical-subs rollup.
Runs on the conftest-forced 8-device CPU mesh.
"""

import asyncio
import random
import types

import numpy as np
import pytest

from bifromq_tpu.models.matcher import TpuMatcher
from bifromq_tpu.models.oracle import Route, SubscriptionTrie
from bifromq_tpu.parallel.sharded import MeshMatcher, make_mesh
from bifromq_tpu.replication import records as R
from bifromq_tpu.replication.standby import WarmStandby
from bifromq_tpu.replication.stream import DeltaLog
from bifromq_tpu.types import RouteMatcher

pytestmark = pytest.mark.asyncio


def rt(f, i, broker=0):
    return Route(matcher=RouteMatcher.from_topic_filter(f),
                 broker_id=broker, receiver_id=f"rcv{i}",
                 deliverer_key=f"d{i}", incarnation=0)


def canon(m):
    return (sorted((r.matcher.mqtt_topic_filter, r.receiver_url)
                   for r in m.normal),
            {f: sorted(r.receiver_url for r in ms)
             for f, ms in m.groups.items()})


TENANTS = [f"ten{i}" for i in range(10)]
FILTERS = ["a/b", "a/+", "a/#", "+/b", "x/y/z", "a/b/c", "#",
           "s/0/t", "s/1/t", "deep/w/x/y"]
TOPICS = ["a/b", "a/c", "a/b/c", "x/y/z", "s/0/t", "s/1/t", "q",
          "deep/w/x/y"]


def _mesh(r=2, s=4):
    return make_mesh(r, s)


def seed_matchers(mesh, n=50, seed=3, replicate=None, **kw):
    """A MeshMatcher, a same-population single-chip TpuMatcher, and the
    oracle tries — the three-way parity fixture."""
    rng = random.Random(seed)
    mm = MeshMatcher(mesh=mesh, max_levels=8, k_states=16,
                     auto_compact=False, match_cache=False,
                     replicate=replicate, **kw)
    sc = TpuMatcher(max_levels=8, k_states=16, auto_compact=False,
                    match_cache=False)
    oracle = {}
    for i in range(n):
        t = rng.choice(TENANTS)
        r = rt(rng.choice(FILTERS), i)
        mm.add_route(t, r)
        sc.add_route(t, r)
        oracle.setdefault(t, SubscriptionTrie()).add(r)
    mm.refresh()
    sc.refresh()
    return mm, sc, oracle


class TestMeshChurnAsyncParity:
    async def test_randomized_mesh_vs_single_vs_oracle(self):
        """Churn patches interleaved with async mesh matches: at every
        step mesh ≡ single-chip ≡ oracle, with ZERO rebuilds on either
        side: both still serve the base they were seeded with (a
        match-cache generation moves only when a base is installed).
        Read off these two matchers: the process-wide compile ledger
        also counts what matchers of earlier tests do on their
        background threads."""
        mm, sc, oracle = seed_matchers(_mesh())
        base_mm, base_sc = mm._base_ct, sc._base_ct
        c_mm, c_sc = mm.compile_count, sc.compile_count
        rng = random.Random(17)
        for step in range(120):
            t = rng.choice(TENANTS)
            if rng.random() < 0.55:
                r = rt(rng.choice(FILTERS), 1000 + step)
                mm.add_route(t, r)
                sc.add_route(t, r)
                oracle.setdefault(t, SubscriptionTrie()).add(r)
            else:
                f = rng.choice(FILTERS)
                url = (0, f"rcv{rng.randrange(50)}",
                       f"d{rng.randrange(50)}")
                mt = RouteMatcher.from_topic_filter(f)
                mm.remove_route(t, mt, url)
                sc.remove_route(t, mt, url)
                if t in oracle:
                    oracle[t].remove(mt, url, 0)
            if step % 6 == 0:
                qs = [(t2, topic) for t2 in TENANTS for topic in TOPICS]
                got_m = await mm.match_batch_async(qs)
                got_s = sc.match_batch(qs)
                for (t2, topic), gm, gs in zip(qs, got_m, got_s):
                    want = (canon(oracle[t2].match(topic.split("/")))
                            if t2 in oracle else ([], {}))
                    assert canon(gm) == want, (step, t2, topic)
                    assert canon(gs) == want, (step, t2, topic)
        assert mm.compile_count == c_mm, "mesh churn must not rebuild"
        assert sc.compile_count == c_sc
        assert mm.overlay_size == 0 and mm.patch_count > 0
        assert mm._base_ct is base_mm and sc._base_ct is base_sc

    async def test_replicated_hot_tenant_serves_and_mutates(self):
        """A replicated tenant's queries fan over the whole grid and its
        mutations patch EVERY shard copy — results stay exact."""
        mesh = _mesh(1, 8)
        mm = MeshMatcher(mesh=mesh, max_levels=8, k_states=16,
                         auto_compact=False, match_cache=False,
                         replicate={"hot"})
        oracle = SubscriptionTrie()
        for i in range(20):
            r = rt(f"h/{i}/+", i)
            mm.add_route("hot", r)
            oracle.add(r)
        mm.refresh()
        tables = mm._base_ct
        assert tables.shards_of("hot") == list(range(8))
        for sh in range(8):
            assert tables.compiled[sh].root_of("hot") >= 0
        c0 = mm.compile_count
        r = rt("h/99/+", 99)
        mm.add_route("hot", r)
        oracle.add(r)
        qs = [("hot", f"h/{i}/x") for i in list(range(20)) + [99]] * 4
        got = await mm.match_batch_async(qs)
        for (t, topic), g in zip(qs, got):
            assert canon(g) == canon(oracle.match(topic.split("/"))), topic
        assert mm.compile_count == c0
        # every shard's copy took the patch (no shard serves stale rows)
        for sh in range(8):
            assert any(x.receiver_url == (0, "rcv99", "d99")
                       for x in tables.compiled[sh].matchings
                       if not isinstance(x, tuple)
                       and hasattr(x, "receiver_url")), sh


class TestMeshMergeAtAdmission:
    async def test_two_callers_in_line_take_one_step(self):
        """The merge at ring admission sits above the prepare / dispatch
        / ready hooks the mesh overrides: two callers waiting for the
        ring leave as ONE mesh step, each with exactly its own rows."""
        from bifromq_tpu.models.pipeline import Merged
        from bifromq_tpu.obs import OBS
        async with asyncio.timeout(40):
            mm, _sc, oracle = seed_matchers(_mesh())
            await mm.match_batch_async([(TENANTS[0], "a/b")])   # compile
            ring = mm._pipeline_ring()
            # keep the ring busy: every slot and every prep ticket held
            for _ in range(ring.depth):
                await ring.acquire()
            tickets = []
            while ring._prep.try_acquire():
                tickets.append(Merged())
            # 9 + 7 rows: together the 16 a merged batch may hold
            qs = [[(t, topic) for t in TENANTS[:3] for topic in TOPICS[:3]],
                  [(t, TOPICS[3]) for t in TENANTS[3:]]]
            tasks = [asyncio.ensure_future(mm.match_batch_async(q))
                     for q in qs]
            for _ in range(10):
                await asyncio.sleep(0)
            assert ring.parked == 2
            n0, d0 = OBS.profiler.batches_total, ring.dispatched_total
            for _ in range(ring.depth):
                ring.release()
            for held in tickets:
                ring.release_prep(held)
            got = await asyncio.gather(*tasks)
            assert ring.dispatched_total == d0 + 1
            recs = OBS.profiler.records()[-(OBS.profiler.batches_total
                                            - n0):]
            assert [(r.kernel, r.n_queries) for r in recs] == [("mesh", 16)]
            for q, rows in zip(qs, got):
                assert len(rows) == len(q)
                for (t, topic), g in zip(q, rows):
                    want = (canon(oracle[t].match(topic.split("/")))
                            if t in oracle else ([], {}))
                    assert canon(g) == want, (t, topic)
            assert ring.in_flight == ring.prepping == ring.parked == 0


class TestShardFaultDomains:
    async def test_hung_shard_degrades_only_its_rows(self, monkeypatch):
        """A hang injected on ONE shard's device: the watchdog reclaims
        (shard-tagged quarantine), ONLY that shard's breaker opens, its
        rows serve exactly from the host oracle, healthy shards keep
        serving on device, and the half-open canary re-closes on row
        parity."""
        monkeypatch.setenv("BIFROMQ_DEVICE_DEADLINE_S", "0.3")
        from bifromq_tpu.resilience.faults import get_injector
        mesh = _mesh(1, 8)
        m = MeshMatcher(mesh=mesh, max_levels=8, k_states=16,
                        match_cache=False, auto_compact=False)
        oracle = {}
        tens = [f"t{i}" for i in range(24)]
        for i, t in enumerate(tens):
            r = rt(f"a/{i}/+", i)
            m.add_route(t, r)
            oracle.setdefault(t, SubscriptionTrie()).add(r)
        m.refresh()
        sick = m._base_ct.shard_of("t0")
        inj = get_injector()
        rule = inj.add_rule(service="tpu-device",
                            method=f"mesh:shard{sick}", action="hang",
                            side="device")
        qs = [(t, f"a/{i}/x") for i, t in enumerate(tens)]
        try:
            for _ in range(4):   # breaker threshold 3 + one open serve
                got = await m.match_batch_async(qs)
                for (t, topic), g in zip(qs, got):
                    assert canon(g) == canon(
                        oracle[t].match(topic.split("/"))), (t, topic)
            states = [br.state for br in m.shard_breakers]
            assert states[sick] == "open", states
            assert all(s == "closed" for i, s in enumerate(states)
                       if i != sick), states
            q = m._ring.quarantine.snapshot()
            assert q["by_tag"] == {f"mesh:shard{sick}": 3}
        finally:
            inj.remove_rule(rule)
        # open shard excluded pre-dispatch: healthy shards stay on
        # device with no further timeouts, rows all exact
        t0 = m._ring.timeouts_total
        got = await m.match_batch_async(qs)
        assert m._ring.timeouts_total == t0
        for (t, topic), g in zip(qs, got):
            assert canon(g) == canon(oracle[t].match(topic.split("/")))
        # canary recovery on row parity
        m.shard_breakers[sick].recovery_time = 0.0
        await m.match_batch_async(qs)
        assert m.shard_breakers[sick].state == "closed"
        # quarantined arrays eventually released (rule removed ⇒ ready)
        m._ring.quarantine.sweep()
        assert len(m._ring.quarantine) == 0

    async def test_canary_parity_failure_reopens(self):
        """A half-open shard whose device rows mismatch the oracle must
        NOT re-close — and the caller still gets the oracle rows."""
        mesh = _mesh(1, 4)
        m = MeshMatcher(mesh=mesh, max_levels=8, k_states=16,
                        match_cache=False, auto_compact=False)
        oracle = {}
        tens = [f"t{i}" for i in range(8)]
        for i, t in enumerate(tens):
            r = rt(f"a/{i}/+", i)
            m.add_route(t, r)
            oracle.setdefault(t, SubscriptionTrie()).add(r)
        m.refresh()
        sick = m._base_ct.shard_of("t0")
        br = m.shard_breakers[sick]
        for _ in range(3):
            br.record_failure("test trip")
        assert br.state == "open"
        br.recovery_time = 0.0
        # poison the sick shard's serving arena (NOT the authoritative
        # tries): tombstone a live slot behind the oracle's back, so the
        # device-leg expansion drops a route the oracle still has — the
        # exact wrong-rows shape the canary parity bar exists to catch
        pt = m._base_ct.compiled[sick]
        tt = next(t for t in tens if m._base_ct.shard_of(t) == sick)
        k = tens.index(tt)
        nid = pt._descend(pt.tenant_root[tt], ["a", str(k), "+"],
                          create=False)
        from bifromq_tpu.models.automaton import NODE_RSTART
        from bifromq_tpu.models.automaton import CompiledTrie as _CT
        pt._kind[int(pt.node_tab[nid, NODE_RSTART])] = _CT.SLOT_DEAD
        opens0 = br.open_count
        qs = [(t, f"a/{i}/x") for i, t in enumerate(tens)]
        got = await m.match_batch_async(qs)
        for (t, topic), g in zip(qs, got):
            assert canon(g) == canon(oracle[t].match(topic.split("/")))
        # the failed parity RE-TRIPPED the breaker (recovery_time=0 lets
        # the lazy state read advance straight back to half_open, so
        # assert the trip itself, and that it never closed)
        assert br.open_count == opens0 + 1, "wrong canary rows must retrip"
        assert br.state != "closed"


class TestMidFlightSnapshots:
    async def test_compaction_swap_mid_flight_keeps_overlay(
            self, monkeypatch):
        """Snapshot discipline: a batch dispatched against base A (with
        overlay content, kill-switch path) expands exactly even when a
        forced compaction installs base B before the expansion runs."""
        monkeypatch.setenv("BIFROMQ_MESH_PATCH", "0")
        mesh = _mesh(1, 4)
        m = MeshMatcher(mesh=mesh, max_levels=8, k_states=16,
                        auto_compact=False, match_cache=False)
        oracle = {}
        for i in range(12):
            t = TENANTS[i % 4]
            r = rt(f"a/{i}/+", i)
            m.add_route(t, r)
            oracle.setdefault(t, SubscriptionTrie()).add(r)
        m.refresh()
        # overlay-resident mutations (patching killed)
        for i in range(12, 18):
            t = TENANTS[i % 4]
            r = rt(f"a/{i}/+", i)
            m.add_route(t, r)
            oracle.setdefault(t, SubscriptionTrie()).add(r)
        assert m.overlay_size > 0
        qs = [(TENANTS[i % 4], f"a/{i}/x") for i in range(18)]
        prep = m._prepare_probes(qs)
        fl = m._dispatch_prepared(prep)
        # compaction folds the overlay into a NEW base and clears the
        # live overlay dicts — the in-flight snapshot must keep serving
        # the dispatch-time dict objects
        m._maybe_compact(force=True)
        m.drain()
        assert m._base_ct is not fl.ct
        overflow, starts_a, counts_a = m._fetch_walk(fl.res)
        got = m._expand_walk(fl, overflow, starts_a, counts_a,
                             1 << 30, 1 << 30)
        for (t, topic), g in zip(qs, got):
            assert canon(g) == canon(oracle[t].match(topic.split("/")))

    async def test_patch_flush_mid_flight_keeps_expansion_exact(self):
        """In-place patches landing between dispatch and expand: the
        tombstone suppresses exactly, relocated slots stay readable (the
        garbage-not-dead arena contract)."""
        mesh = _mesh(1, 4)
        m = MeshMatcher(mesh=mesh, max_levels=8, k_states=16,
                        auto_compact=False, match_cache=False)
        t = "ten0"
        oracle = SubscriptionTrie()
        for i in range(10):
            r = rt(f"a/{i}/+", i)
            m.add_route(t, r)
            oracle.add(r)
        m.refresh()
        qs = [(t, f"a/{i}/x") for i in range(10)]
        prep = m._prepare_probes(qs)
        fl = m._dispatch_prepared(prep)
        # mutate + flush while the batch is in flight. The arena
        # contract (PatchableTrie docstring): a TOMBSTONE suppresses the
        # route for the in-flight expansion too (like the old overlay
        # tombstones), while an ADD that relocates a node's slots leaves
        # the old copies live — the pre-patch interval expands to the
        # PRE-patch route set.
        mt = RouteMatcher.from_topic_filter("a/3/+")
        m.remove_route(t, mt, (0, "rcv3", "d3"))
        oracle.remove(mt, (0, "rcv3", "d3"), 0)
        m.add_route(t, rt("a/4/+", 44))
        m._flush_patches()
        overflow, starts_a, counts_a = m._fetch_walk(fl.res)
        got = m._expand_walk(fl, overflow, starts_a, counts_a,
                             1 << 30, 1 << 30)
        for (tt, topic), g in zip(qs, got):
            # oracle WITHOUT the new a/4 route == pre-patch set minus
            # the tombstone — exactly what the in-flight batch must see
            assert canon(g) == canon(oracle.match(topic.split("/"))), topic
        # a FRESH batch sees the add too
        oracle.add(rt("a/4/+", 44))
        got2 = m.match_batch([(t, "a/4/x")])
        assert canon(got2[0]) == canon(oracle.match(["a", "4", "x"]))


class TestMeshRestack:
    async def test_node_growth_restacks_without_rebuild(self):
        """Patching past a shard's node-arena capacity restacks the
        device tables at the new common shape — a full re-upload,
        never a trie recompile — and serving stays exact."""
        mesh = _mesh(1, 4)
        m = MeshMatcher(mesh=mesh, max_levels=8, k_states=16,
                        auto_compact=False, match_cache=False)
        t = "growth"
        oracle = SubscriptionTrie()
        r0 = rt("seed/x", 0)
        m.add_route(t, r0)
        oracle.add(r0)
        m.refresh()
        c0 = m.compile_count
        cap0 = m._base_ct.node_tab.shape[1]
        for i in range(cap0 + 64):      # forces ≥1 arena doubling
            r = rt(f"g/{i}/leaf/+", i)
            m.add_route(t, r)
            oracle.add(r)
        got = await m.match_batch_async(
            [(t, f"g/{i}/leaf/x") for i in range(0, cap0 + 64, 9)])
        for (tt, topic), g in zip(
                [(t, f"g/{i}/leaf/x") for i in range(0, cap0 + 64, 9)],
                got):
            assert canon(g) == canon(oracle.match(topic.split("/"))), topic
        assert m.compile_count == c0, "growth must restack, not rebuild"
        assert m._base_ct.node_tab.shape[1] > cap0
        assert m._base_ct.compiled[
            m._base_ct.shard_of(t)].node_grows >= 1


class TestMeshReplication:
    def _leader(self, mesh, replicate=None):
        leader = MeshMatcher(mesh=mesh, max_levels=8, k_states=16,
                             auto_compact=False, match_cache=False,
                             replicate=replicate)
        log = DeltaLog("n0", "r0")
        leader.on_delta = lambda t, f, op, plan, fb: log.append(
            tenant=t, filter_levels=f, op=op, plan=plan, fallback=fb)
        leader.on_rebase = lambda salt, reason: log.anchor(salt, reason)
        rng = random.Random(5)
        for i in range(40):
            leader.add_route(rng.choice(TENANTS), rt(rng.choice(FILTERS),
                                                     i))
        leader.add_route("ten1", rt("$share/g/sh/x", 902))
        leader.add_route("ten1", rt("$share/g/sh/x", 903))
        leader.refresh()
        return leader, log

    def _attach(self, leader, log, mesh):
        snap = R.decode_base(R.encode_base_snapshot(
            R.capture_mesh_base(leader._base_ct, leader.tries)))
        assert isinstance(snap, R.MeshBaseSnapshot)
        sb = WarmStandby(matcher=MeshMatcher(
            mesh=mesh, max_levels=8, k_states=16, auto_compact=False,
            match_cache=False))
        sb.range_id = "r0"
        sb._install(snap, log.cursor())
        return sb

    @staticmethod
    def _assert_shard_parity(leader, sb):
        a, b = leader._base_ct, sb.matcher._base_ct
        assert a.n_shards == b.n_shards
        for sh in range(a.n_shards):
            pa, pb = a.compiled[sh], b.compiled[sh]
            assert np.array_equal(pa.node_tab, pb.node_tab), sh
            assert np.array_equal(pa.edge_tab, pb.edge_tab), sh
            assert np.array_equal(pa.slot_kind, pb.slot_kind), sh
            assert pa.n_live == pb.n_live
            assert pa.tenant_root == pb.tenant_root
            assert len(pa.matchings) == len(pb.matchings)

    async def test_mesh_standby_delta_parity(self):
        """Mesh base ships per-shard arenas; op-only records re-run the
        same deterministic patches on the replica — ARENA parity per
        shard, zero rebuilds, exact match parity, after a 150-op churn."""
        mesh = _mesh(1, 4)
        leader, log = self._leader(mesh)
        sb = self._attach(leader, log, mesh)
        self._assert_shard_parity(leader, sb)
        rebuilds0 = sb.matcher.compile_count
        rng = random.Random(11)
        cursor = log.cursor()
        n = 0
        while n < 150:
            t = rng.choice(TENANTS)
            if rng.random() < 0.6:
                if leader.add_route(t, rt(f"c/{rng.randint(0, 30)}/x",
                                          2000 + n)):
                    n += 1
            else:
                f = f"c/{rng.randint(0, 30)}/x"
                urls = [x.receiver_url
                        for tr in leader.tries.values()
                        for x in tr.match(f.split("/")).normal]
                if urls and leader.remove_route(
                        t, RouteMatcher.from_topic_filter(f), urls[0]):
                    n += 1
        status, recs = log.since(*cursor)
        assert status == "ok" and len(recs) >= 150
        wired = [R.decode_record(rec.encoded())[0] for rec in recs]
        assert sb.offer(wired)
        assert sb.matcher.compile_count == rebuilds0
        self._assert_shard_parity(leader, sb)
        topics = TOPICS + [f"c/{i}/x" for i in range(31)]
        qs = [(t, topic) for t in TENANTS for topic in topics]
        got = sb.matcher.match_batch(qs)
        want = leader.match_from_tries(qs)
        for (t, topic), g, w in zip(qs, got, want):
            assert canon(g) == canon(w), (t, topic)

    async def test_mesh_standby_replicated_tenant(self):
        """Replicated-hot-tenant mutations fan to every shard on BOTH
        sides (routing metadata rides the base snapshot)."""
        mesh = _mesh(1, 4)
        leader, log = self._leader(mesh, replicate={"hot"})
        for i in range(6):
            leader.add_route("hot", rt(f"h/{i}/+", 700 + i))
        sb = self._attach(leader, log, mesh)
        assert sb.matcher._base_ct.replicated == frozenset({"hot"})
        cursor = log.cursor()
        leader.add_route("hot", rt("h/99/+", 799))
        status, recs = log.since(*cursor)
        assert status == "ok"
        assert sb.offer([R.decode_record(r.encoded())[0] for r in recs])
        self._assert_shard_parity(leader, sb)

    async def test_base_codec_version_rejected_cleanly(self):
        with pytest.raises(ValueError, match="codec version"):
            R.decode_base(bytes([1, 0]) + b"garbage")
        with pytest.raises(ValueError, match="codec version"):
            R.decode_base(b"")

    async def test_base_codec_compresses(self):
        """v2 frames are zlib-compressed: materially smaller than the
        raw body for a real arena set."""
        m = TpuMatcher(auto_compact=False, match_cache=False)
        for i in range(200):
            m.add_route("T", rt(f"s/{i}/t", i))
        m.refresh()
        snap = R.capture_base(m._base_ct, m.tries)
        wire = R.encode_base_snapshot(snap)
        import struct
        (raw_len,) = struct.unpack_from(">Q", wire, 2)
        assert len(wire) < raw_len / 2, (len(wire), raw_len)
        back = R.decode_base(wire)
        assert np.array_equal(back.node_tab, snap.node_tab)
        assert back.routes.keys() == snap.routes.keys()


class TestClusterCapacityDedup:
    async def test_replicated_tenant_counts_once_in_logical_subs(self):
        """/cluster/capacity rollup: a tenant replicated into every
        shard still counts its subscriptions ONCE (logical vs physical),
        while the physical per-shard bytes carry all S copies."""
        from bifromq_tpu.obs.capacity import digest_capacity
        mesh = _mesh(1, 4)
        m = MeshMatcher(mesh=mesh, max_levels=8, k_states=16,
                        auto_compact=False, match_cache=False,
                        replicate={"hot"})
        for i in range(10):
            m.add_route("hot", rt(f"h/{i}/+", i))
        m.add_route("cold", rt("c/x", 100))
        m.refresh()
        hub = types.SimpleNamespace(device=types.SimpleNamespace(
            matchers=lambda: [m], peak_memory_bytes=0))
        cap = digest_capacity(hub)
        assert cap["logical_subs"] == 11      # not 10*4 + 1
        # physical: every shard's arena really holds the hot tenant
        for sh in range(4):
            assert m._base_ct.compiled[sh].root_of("hot") >= 0


class TestDrainShedToPeers:
    async def test_saturated_governor_sheds_toward_quieter_peers(self):
        from bifromq_tpu.retained_plane.drain import DrainGovernor
        gov = DrainGovernor(slots=2, per_tenant=2)
        assert not gov.should_shed_reconnect()    # unwired: never sheds
        gov.peer_pressure_fn = lambda: {"n2": 0.0, "n3": 0.25}
        assert not gov.should_shed_reconnect()    # idle: admit locally
        async with gov.slot("a"):
            async with gov.slot("b"):
                assert gov.pressure() >= 1.0
                assert gov.should_shed_reconnect()
                assert gov.shed_to_peers_total == 1
                # cluster-wide saturation: nowhere better to go
                gov.peer_pressure_fn = lambda: {"n2": 1.0, "n3": 2.0}
                assert not gov.should_shed_reconnect()
                # gossip failure degrades to admit, not to a crash
                def boom():
                    raise RuntimeError("gossip down")
                gov.peer_pressure_fn = boom
                assert not gov.should_shed_reconnect()
        assert gov.pressure() == 0.0
        assert "shed_to_peers_total" in gov.snapshot()

    async def test_drain_pressure_rides_the_digest(self):
        from bifromq_tpu.obs import OBS
        from bifromq_tpu.obs.clusterview import ClusterView
        from bifromq_tpu.retained_plane.drain import DrainGovernor
        gov = DrainGovernor(slots=4)
        assert OBS.drain_pressure() >= 0.0
        async with gov.slot("t"):
            assert OBS.drain_pressure() >= 0.25

        class _Host:
            members = {}

            def agent_members(self, aid):
                return {"n2": {"addr": "a2", "api": 0,
                               "digest": {"hlc": 1,
                                          "drain_pressure": 0.75}}}

        view = ClusterView("n1", _Host(), hub=OBS)
        assert view.peer_drain_pressures() == {"n2": 0.75}
        # the local digest carries the field too
        assert "drain_pressure" in view.build_digest()

"""Elastic mesh (ISSUE 17): live tenant migration, online rebalancing,
mesh autoscaling — and the satellite planes that ride along.

Covers the full migration ladder under randomized churn (pre-move ≡
dual-serve ≡ post-cutover ≡ oracle parity, zero trie rebuilds, zero
match-cache generation bumps), dual-serve mutations folding into BOTH
arenas, the abort ladder (open target breaker → clean return to
source-only serving, partial target rows tombstoned), standby replay of
the migration op stream to per-shard ARENA parity, mid-migration base
snapshots, mesh grow/shrink, the migration-op/base-trailer codec, the
skew-driven rebalancer with its capacity veto, device-tokenized
retained FILTER probes (host-reference bit parity + the prepare_scan
wiring), and the ``GET /mesh`` / ``GET /mesh/rebalance`` surfaces.
Runs on the conftest-forced 8-device CPU mesh.
"""

import asyncio
import json
import random

import numpy as np
import pytest

from bifromq_tpu.models.automaton import CompiledTrie
from bifromq_tpu.models.oracle import Route
from bifromq_tpu.parallel import reshard
from bifromq_tpu.parallel.reshard import (MeshRebalancer, MigrationAborted,
                                          ShardLoadModel, TenantMigration)
from bifromq_tpu.parallel.sharded import MeshMatcher, make_mesh
from bifromq_tpu.replication import records as R
from bifromq_tpu.replication.standby import WarmStandby
from bifromq_tpu.replication.stream import DeltaLog
from bifromq_tpu.types import RouteMatcher

TENANTS = [f"t{i}" for i in range(12)]
FILTERS = ["a/b", "a/+", "s/#", "c/1/x", "live/+/topic", "d/e/f",
           "$share/g/sh/x"]
TOPICS = ["a/b", "s/3/x", "c/1/x", "live/new/topic", "sh/x", "d/e/f",
          "q/none"]


def rt(f, i, broker=0):
    return Route(matcher=RouteMatcher.from_topic_filter(f),
                 broker_id=broker, receiver_id=f"rcv{i}",
                 deliverer_key=f"d{i}", incarnation=0)


def canon(m):
    return (sorted((r.matcher.mqtt_topic_filter, r.receiver_url)
                   for r in m.normal),
            {f: sorted(r.receiver_url for r in ms)
             for f, ms in m.groups.items()})


def build(seed=7, *, match_cache=False, replicate=None, log=True,
          n_routes=70):
    m = MeshMatcher(mesh=make_mesh(1, 4), max_levels=8, k_states=16,
                    auto_compact=False, match_cache=match_cache,
                    replicate=replicate)
    dlog = None
    if log:
        dlog = DeltaLog("n0", "r0")
        m.on_delta = lambda t, f, op, plan, fb: dlog.append(
            tenant=t, filter_levels=f, op=op, plan=plan, fallback=fb)
        m.on_rebase = lambda salt, reason: dlog.anchor(salt, reason)
    rng = random.Random(seed)
    for i in range(n_routes):
        m.add_route(rng.choice(TENANTS), rt(rng.choice(FILTERS), i))
    m.refresh()
    return m, dlog


def assert_parity(m, label=""):
    qs = [(t, topic) for t in TENANTS for topic in TOPICS]
    got = m.match_batch(qs)
    want = m.match_from_tries(qs)
    for q, g, w in zip(qs, got, want):
        assert canon(g) == canon(w), (label, q)


def live_slots(pt) -> int:
    n = len(pt.matchings)
    return n - int(np.sum(np.asarray(pt.slot_kind[:n])
                          == CompiledTrie.SLOT_DEAD))


def assert_shard_parity(leader, sb):
    a, b = leader._base_ct, sb.matcher._base_ct
    assert a.n_shards == b.n_shards
    for sh in range(a.n_shards):
        pa, pb = a.compiled[sh], b.compiled[sh]
        assert np.array_equal(pa.node_tab, pb.node_tab), sh
        assert np.array_equal(pa.edge_tab, pb.edge_tab), sh
        assert np.array_equal(pa.slot_kind, pb.slot_kind), sh
        assert pa.n_live == pb.n_live, sh
        assert pa.tenant_root == pb.tenant_root, sh


# ---------------- migration ladder ------------------------------------------


class TestMigrationLadder:
    def test_triple_parity_under_churn(self):
        """The acceptance gate: a live move with randomized churn DURING
        the copy stream — exact oracle parity at every phase (pre-move,
        each copy chunk, the dual-serve window incl. a mid-window
        mutation, post-cutover, post-tombstone), zero trie rebuilds,
        zero match-cache generation bumps."""
        m, _ = build(match_cache=True, log=False)
        victim = "t0"
        src = m._base_ct.shard_of(victim)
        dst = (src + 1) % 4
        rebuilds0 = m.compile_count
        gen0 = m.match_cache._gen
        assert_parity(m, "pre-move")

        mig = m.migrate_tenant(victim, src, dst, run=False)
        rng = random.Random(31)
        seq = 0
        while mig.state == "copying":
            more = mig.step(4)
            # churn mid-stream: adds and removes, some on the victim
            t = rng.choice([victim, rng.choice(TENANTS)])
            m.add_route(t, rt(f"churn/{seq}/x", 5000 + seq))
            seq += 1
            if rng.random() < 0.4:
                urls = [r.receiver_url for tr in (m.tries.get(t),)
                        if tr is not None
                        for r in tr.match(["a", "b"]).normal]
                if urls:
                    m.remove_route(t, RouteMatcher.from_topic_filter("a/b"),
                                   urls[0])
            assert_parity(m, f"copy-{seq}")
            if more:
                break
        assert mig.state == "ready"
        # dual-serve window: both shards serve the victim
        assert m._base_ct.shards_of(victim) == [src, dst]
        assert_parity(m, "dual-serve")
        m.add_route(victim, rt("dual/serve/add", 9001))
        assert_parity(m, "dual-serve+mutation")

        mig.cutover()
        assert m._base_ct.shards_of(victim) == [dst]
        assert_parity(m, "post-cutover")
        assert mig.finish()
        assert_parity(m, "post-tombstone")

        assert m.compile_count == rebuilds0          # zero rebuilds
        assert m.match_cache._gen == gen0            # zero gen bumps
        assert m._base_ct.migrating in (None, {})
        assert m._pins.get(victim) == dst

    def test_dual_serve_mutations_fold_into_both_shards(self):
        m, _ = build(log=False)
        victim = "t1"
        src = m._base_ct.shard_of(victim)
        dst = (src + 2) % 4
        mig = m.migrate_tenant(victim, src, dst, run=False)
        while not mig.step(8):
            pass
        assert mig.state == "ready"
        src_live = live_slots(m._base_ct.compiled[src])
        dst_live = live_slots(m._base_ct.compiled[dst])
        m.add_route(victim, rt("both/arenas", 9100))
        assert live_slots(m._base_ct.compiled[src]) == src_live + 1
        assert live_slots(m._base_ct.compiled[dst]) == dst_live + 1
        # and an rm mid-window kills the slot in BOTH arenas
        m.remove_route(victim, RouteMatcher.from_topic_filter("both/arenas"),
                       rt("both/arenas", 9100).receiver_url)
        assert live_slots(m._base_ct.compiled[src]) == src_live
        assert live_slots(m._base_ct.compiled[dst]) == dst_live
        assert_parity(m, "dual-fold")

    def test_abort_restores_source_only_and_is_retryable(self):
        from bifromq_tpu.resilience.breaker import CircuitBreaker
        m, _ = build(log=False)
        victim = "t2"
        src = m._base_ct.shard_of(victim)
        dst = (src + 1) % 4
        dst_live0 = live_slots(m._base_ct.compiled[dst])
        m.shard_breakers[dst] = CircuitBreaker(failure_threshold=1,
                                               recovery_time=3600.0)
        mig = m.migrate_tenant(victim, src, dst, run=False)
        assert len(mig.pending) > 1, "victim must need >1 copy chunk"
        mig.step(1)          # partial copy only — stay mid-stream
        m.shard_breakers[dst].record_failure("forced")
        with pytest.raises(MigrationAborted):
            mig.step(1)
        assert mig.state == "aborted"
        assert not m._base_ct.migrating
        assert m._base_ct.shards_of(victim) == [src]
        # every partially-copied target row is tombstoned
        assert live_slots(m._base_ct.compiled[dst]) == dst_live0
        assert_parity(m, "post-abort")
        # the aborted move is retryable once the target heals
        m.shard_breakers[dst] = CircuitBreaker()
        mig2 = m.migrate_tenant(victim, src, dst, run=False)
        mig2.run()
        assert mig2.state == "done"
        assert m._base_ct.shards_of(victim) == [dst]
        assert_parity(m, "post-retry")

    def test_stale_pending_copy_not_resurrected(self):
        """A route removed (cleanly, in both arenas) while still QUEUED
        in the copy stream must not be re-added to the target by its
        stale pending entry — the ghost-route hazard."""
        m, _ = build(log=False)
        victim = "t3"
        # give the victim a known route that sorts late in the stream
        ghost = rt("zz/ghost", 9200)
        m.add_route(victim, ghost)
        src = m._base_ct.shard_of(victim)
        dst = (src + 3) % 4
        mig = m.migrate_tenant(victim, src, dst, run=False)
        mig.step(1)          # partial: ghost still pending
        assert m.remove_route(victim,
                              RouteMatcher.from_topic_filter("zz/ghost"),
                              ghost.receiver_url)
        while not mig.step(8):
            pass
        mig.cutover()
        assert mig.finish()
        assert_parity(m, "post-move")
        got = m.match_batch([(victim, "zz/ghost")])[0]
        assert not any(r.receiver_id == "rcv9200" for r in got.normal)

    def test_guards(self):
        m, _ = build(replicate={"t4"}, log=False)
        src = m._base_ct.shard_of("t5")
        with pytest.raises(ValueError):
            m.migrate_tenant("t4", m._base_ct.shard_of("t4"),
                             (m._base_ct.shard_of("t4") + 1) % 4)
        with pytest.raises(ValueError):
            m.migrate_tenant("t5", src, src)          # dst == src
        with pytest.raises(ValueError):
            m.migrate_tenant("t5", src, 99)           # dst out of range
        mig = m.migrate_tenant("t5", src, (src + 1) % 4, run=False)
        with pytest.raises(RuntimeError):
            m.migrate_tenant("t6", m._base_ct.shard_of("t6"),
                             (m._base_ct.shard_of("t6") + 1) % 4)
        with pytest.raises(RuntimeError):
            m.replicate_tenant("t6")
        # compaction defers while a migration is in flight
        assert m._maybe_compact() is None
        mig.run()
        assert mig.state == "done"


# ---------------- standby replay --------------------------------------------


class TestStandbyReplay:
    def _attach(self, leader, log):
        snap = R.decode_base(R.encode_base_snapshot(
            R.capture_mesh_base(leader._base_ct, leader.tries)))
        assert isinstance(snap, R.MeshBaseSnapshot)
        sb = WarmStandby(matcher=MeshMatcher(
            mesh=make_mesh(1, 4), max_levels=8, k_states=16,
            auto_compact=False, match_cache=False))
        sb.range_id = "r0"
        sb._install(snap, log.cursor())
        return sb

    def _offer_since(self, log, sb, cursor):
        status, recs = log.since(*cursor)
        assert status == "ok"
        assert sb.offer([R.decode_record(r.encoded())[0] for r in recs])

    def test_full_ladder_arena_parity(self):
        """The standby replays begin/copy/ready/cutover/tombstone ops
        interleaved with churn to BYTE-identical per-shard arenas, and
        lands on the same shard map (pins + map_version)."""
        m, log = build()
        sb = self._attach(m, log)
        assert_shard_parity(m, sb)
        cursor = log.cursor()
        victim = "t6"
        src = m._base_ct.shard_of(victim)
        dst = (src + 1) % 4
        mig = m.migrate_tenant(victim, src, dst, run=False)
        rng = random.Random(13)
        i = 0
        while mig.state == "copying":
            mig.step(3)
            m.add_route(rng.choice(TENANTS), rt(f"sb/{i}", 6000 + i))
            i += 1
        mig.cutover()
        assert mig.finish()
        m.add_route(victim, rt("post/cutover", 6999))
        self._offer_since(log, sb, cursor)
        assert_shard_parity(m, sb)
        assert sb.matcher._pins.get(victim) == dst
        assert sb.matcher._base_ct.shards_of(victim) == [dst]
        assert sb.matcher._base_ct.map_version == m._base_ct.map_version
        assert_parity(sb.matcher, "standby")

    def test_mid_migration_snapshot_attach(self):
        """A standby attaching FROM a snapshot captured mid-copy (the
        dual-fold state rides the base trailer) replays the REST of the
        ladder to arena parity."""
        m, log = build(seed=9)
        victim = "t7"
        src = m._base_ct.shard_of(victim)
        dst = (src + 2) % 4
        mig = m.migrate_tenant(victim, src, dst, run=False)
        mig.step(2)
        m.add_route(victim, rt("mid/attach", 7001))   # dual-folds
        sb = self._attach(m, log)                     # mid-migration!
        assert victim in (sb.matcher._base_ct.migrating or {})
        assert sb.matcher._base_ct.shards_of(victim) == [src, dst]
        cursor = log.cursor()
        while not mig.step(4):
            pass
        mig.cutover()
        assert mig.finish()
        self._offer_since(log, sb, cursor)
        assert_shard_parity(m, sb)
        assert sb.matcher._base_ct.shards_of(victim) == [dst]
        assert_parity(sb.matcher, "standby-mid-attach")

    def test_abort_replays_cleanly(self):
        from bifromq_tpu.resilience.breaker import CircuitBreaker
        m, log = build(seed=11)
        sb = self._attach(m, log)
        cursor = log.cursor()
        victim = "t8"
        src = m._base_ct.shard_of(victim)
        dst = (src + 1) % 4
        m.shard_breakers[dst] = CircuitBreaker(failure_threshold=1,
                                               recovery_time=3600.0)
        mig = m.migrate_tenant(victim, src, dst, run=False)
        assert len(mig.pending) > 1, "victim must need >1 copy chunk"
        mig.step(1)          # partial copy only — stay mid-stream
        m.shard_breakers[dst].record_failure("forced")
        with pytest.raises(MigrationAborted):
            mig.step(1)
        self._offer_since(log, sb, cursor)
        assert_shard_parity(m, sb)
        assert not (sb.matcher._base_ct.migrating or {})


# ---------------- resize ----------------------------------------------------


class TestResize:
    def test_grow_preserves_placement_and_parity(self):
        m, _ = build(log=False)
        rebuilds0 = m.compile_count
        homes = {t: m._base_ct.shard_of(t) for t in TENANTS
                 if t in m.tries}
        m.resize_mesh(8)
        assert m.n_shards == 8
        assert m.compile_count == rebuilds0
        # every tenant pinned to its pre-grow shard: placement is stable
        for t, sh in homes.items():
            assert m._base_ct.shards_of(t) == [sh], t
        assert_parity(m, "post-grow")
        # the freed shards accept a migration
        victim = next(iter(homes))
        dst = next(sh for sh in range(8)
                   if sh not in set(homes.values()))
        m.migrate_tenant(victim, homes[victim], dst)
        assert m._base_ct.shards_of(victim) == [dst]
        assert_parity(m, "post-grow-migrate")

    def test_shrink_drains_evacuees(self):
        m, _ = build(log=False)
        rebuilds0 = m.compile_count
        m.resize_mesh(2)
        assert m.n_shards == 2
        assert m.compile_count == rebuilds0
        for t in TENANTS:
            if t in m.tries:
                (sh,) = m._base_ct.shards_of(t)
                assert sh < 2, (t, sh)
        assert_parity(m, "post-shrink")

    def test_resize_guards(self):
        m, _ = build(log=False)
        with pytest.raises(ValueError):
            m.resize_mesh(0)
        src = m._base_ct.shard_of("t0")
        mig = m.migrate_tenant("t0", src, (src + 1) % 4, run=False)
        with pytest.raises(RuntimeError):
            m.resize_mesh(8)
        mig.run()
        assert mig.state == "done"


# ---------------- codec -----------------------------------------------------


class TestCodec:
    def test_migration_op_round_trip(self):
        route = rt("a/+", 1)
        grp = rt("$share/g/sh/x", 2)
        ops = [("mig_begin", "ten", 1, 3),
               ("mig_copy", "ten", 3, route),
               ("mig_copy", "ten", 3, grp),
               ("mig_ready", "ten"),
               ("mig_cutover", "ten", 1, 3),
               ("mig_abort", "ten", 1, 3),
               ("mig_tombstone", "ten", 1)]
        for op in ops:
            buf = R.encode_op(op)
            back = R.decode_op(buf)
            assert back[0] == op[0] and back[1] == op[1], op
            if op[0] == "mig_copy":
                assert back[2] == op[2]
                assert back[3].receiver_url == op[3].receiver_url
            else:
                assert tuple(int(x) for x in back[2:]) \
                    == tuple(int(x) for x in op[2:]), op
        with pytest.raises(ValueError):
            R.encode_op(("mig_not_a_thing", "ten"))

    def test_mesh_snapshot_trailer_round_trip(self):
        m, _ = build(seed=5)
        victim = "t9"
        src = m._base_ct.shard_of(victim)
        mig = m.migrate_tenant(victim, src, (src + 1) % 4, run=False)
        mig.step(2)
        snap = R.decode_base(R.encode_base_snapshot(
            R.capture_mesh_base(m._base_ct, m.tries)))
        assert snap.map_version == m._base_ct.map_version
        assert victim in snap.migrating
        st = snap.to_migrating()[victim]
        live = m._base_ct.migrating[victim]
        assert (st.src, st.dst, st.ready) == (live.src, live.dst,
                                              live.ready)
        assert sorted(st.copied) == sorted(live.copied)
        mig.run()
        # no migration → empty trailer, map_version still rides
        snap2 = R.decode_base(R.encode_base_snapshot(
            R.capture_mesh_base(m._base_ct, m.tries)))
        assert snap2.migrating == {}
        assert snap2.map_version == m._base_ct.map_version


# ---------------- rebalancer ------------------------------------------------


def _skewed_mesh():
    """One whale tenant (many routes + all the query heat) on one shard:
    the load model must rank its shard hot and the rebalancer must move
    it somewhere colder."""
    m = MeshMatcher(mesh=make_mesh(1, 4), max_levels=8, k_states=16,
                    auto_compact=False, match_cache=False)
    whale = "whale0"
    for i in range(160):
        m.add_route(whale, rt(f"w/{i}/x", i))
    for j, t in enumerate(TENANTS[:4]):
        m.add_route(t, rt(f"cold/{j}", 800 + j))
    m.refresh()
    m.query_heat[whale] = 4096
    return m, whale


class TestRebalancer:
    def test_load_model_rows(self):
        m, whale = _skewed_mesh()
        model = ShardLoadModel()
        rows = model.rows(m)
        assert len(rows) == 4
        hot = max(rows, key=lambda r: r["score"])
        assert hot["shard"] == m._base_ct.shard_of(whale)
        assert hot["heat"] >= 4096
        assert model.skew(rows) > 1.0
        for r in rows:
            assert set(r) >= {"shard", "padded_bytes", "real_bytes",
                              "logical_subs", "tenants", "heat",
                              "queue_pressure", "breaker", "score"}

    def test_plan_moves_whale_hot_to_cold(self):
        m, whale = _skewed_mesh()
        reb = MeshRebalancer(m, max_skew=1.2, min_heat=64)
        decision = reb.plan()
        assert decision is not None
        assert decision["tenant"] == whale
        assert decision["src"] == m._base_ct.shard_of(whale)
        assert decision["dst"] != decision["src"]
        assert m.mesh_rebalancer is reb

    def test_noisy_ranking_first(self):
        m, whale = _skewed_mesh()
        # a flagged-noisy tenant on the hot shard outranks the whale
        hot = m._base_ct.shard_of(whale)
        noisy = next(t for t in (f"n{i}" for i in range(64))
                     if __import__("bifromq_tpu.parallel.sharded",
                                   fromlist=["tenant_shard"])
                     .tenant_shard(t, 4) == hot)
        m.add_route(noisy, rt("noise/maker", 901))
        m.refresh()
        reb = MeshRebalancer(m, max_skew=1.2, min_heat=64)
        decision = reb.plan(noisy=[noisy])
        assert decision is not None and decision["tenant"] == noisy

    def test_capacity_veto(self):
        m, whale = _skewed_mesh()
        reb = MeshRebalancer(m, max_skew=1.2, min_heat=64)
        reb.planner = type("Veto", (), {
            "fits": lambda self, *a, **k: {"hbm": {"fits": False}}})()
        assert reb.plan() is None
        assert reb.decisions
        assert whale in reb.decisions[-1]["vetoed"]

    def test_step_executes_and_improves_skew(self):
        m, whale = _skewed_mesh()
        reb = MeshRebalancer(m, max_skew=1.2, min_heat=64)
        rebuilds0 = m.compile_count
        decision = reb.step()
        assert decision is not None
        assert decision["outcome"] == "done"
        assert decision["skew_after"] < decision["skew"]
        assert m.compile_count == rebuilds0
        assert m._base_ct.shards_of(whale) == [decision["dst"]]
        assert_parity(m, "post-rebalance")
        # balanced now (under this threshold) → no further move
        reb.max_skew = decision["skew_after"] + 0.5
        assert reb.step() is None

    def test_balanced_mesh_plans_nothing(self):
        m, _ = build(log=False)
        reb = MeshRebalancer(m, max_skew=50.0, min_heat=0)
        assert reb.plan() is None

    def test_mesh_status_surface(self):
        m, whale = _skewed_mesh()
        s = m.mesh_status()
        assert s["n_shards"] == 4 and len(s["shard_load"]) == 4
        assert s["skew"] >= 1.0 and s["map_version"] == 0
        src = m._base_ct.shard_of(whale)
        mig = m.migrate_tenant(whale, src, (src + 1) % 4, run=False)
        mig.step(2)
        s = m.mesh_status()
        assert whale in s["migrating"]
        assert s["migrating"][whale]["copied"] > 0
        mig.abort("test over")


# ---------------- device-tokenized retained filter probes -------------------


class TestDeviceFilterTokenize:
    def _filters(self):
        rng = random.Random(2)
        filters = []
        for _ in range(200):
            depth = rng.randint(1, 6)
            lv = []
            for d in range(depth):
                r = rng.random()
                if r < 0.2:
                    lv.append("+")
                elif r < 0.28 and d == depth - 1:
                    lv.append("#")
                else:
                    lv.append(f"l{rng.randint(0, 9)}")
            filters.append(lv)
        filters += [[], ["x"] * 20, ["em/bed"], ["+"], ["#"],
                    ["a" * 200]]
        return filters

    def test_bit_parity_with_host_reference(self):
        from bifromq_tpu.models.automaton import tokenize_filters
        from bifromq_tpu.ops.tokenize import device_tokenize_filters
        filters = self._filters()
        roots = list(range(len(filters)))
        ref = tokenize_filters(filters, roots, max_levels=8,
                               salt=987654321, batch=256)
        mir, pr = device_tokenize_filters(filters, roots, max_levels=8,
                                          salt=987654321, batch=256)
        sup = np.asarray(mir.lengths) != -1
        assert sup.sum() > 150
        assert np.array_equal(np.asarray(mir.lengths)[sup],
                              ref.lengths[sup])
        assert np.array_equal(np.asarray(pr.tok_h1)[sup],
                              ref.tok_h1[sup])
        assert np.array_equal(np.asarray(pr.tok_h2)[sup],
                              ref.tok_h2[sup])
        assert np.array_equal(np.asarray(pr.tok_kind)[sup],
                              ref.tok_kind[sup])
        # zero-on-wildcard contract
        kd = np.asarray(pr.tok_kind)
        assert not np.asarray(pr.tok_h1)[kd != 0].any()
        assert not np.asarray(pr.tok_h2)[kd != 0].any()

    def test_fallback_rows_marked_padding(self):
        from bifromq_tpu.ops.tokenize import device_tokenize_filters
        filters = [["ok", "row"], ["x"] * 20, ["em/bed"], [],
                   ["a" * 200]]
        mir, _ = device_tokenize_filters(filters, [0] * 5, max_levels=8,
                                         salt=1, batch=8)
        L = np.asarray(mir.lengths)
        assert L[0] == 2          # supported
        assert L[1] == -1         # too deep → host fallback
        assert L[2] == -1         # embedded delimiter → host fallback
        assert L[3] == 0          # empty filter: zero levels, no lanes
        assert L[4] == -1         # level over one BLAKE2b block

    def test_prepare_scan_rides_device_path(self, monkeypatch):
        from bifromq_tpu.models.retained import (RetainedIndex,
                                                 match_filter_host)
        from bifromq_tpu.utils import topic as tp
        monkeypatch.setenv("BIFROMQ_DEVICE_TOKENIZE", "1")
        idx = RetainedIndex()
        rng = random.Random(4)
        for i in range(60):
            topic = f"dev/{rng.randint(0, 9)}/s{rng.randint(0, 5)}"
            idx.add_topic(f"ten{i % 3}", tp.parse(topic), topic)
        scans = [("ten0", ["dev", "+", "s1"]), ("ten1", ["#"]),
                 ("ten2", ["dev", "3", "#"]), ("ten0", ["dev", "+", "+"]),
                 ("ten1", ["nope", "x"])]
        got = idx.match_batch(scans)
        for (tenant, f), rows in zip(scans, got):
            trie = idx.tries.get(tenant)
            want = sorted(match_filter_host(trie, f)) if trie else []
            assert sorted(rows) == want, (tenant, f)
            assert len(rows) == len(set(rows))


# ---------------- /mesh + /mesh/rebalance -----------------------------------


@pytest.mark.asyncio
class TestMeshEndpoints:
    async def _http(self, port, method, path):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(f"{method} {path} HTTP/1.1\r\nhost: x\r\n"
                     f"content-length: 0\r\nconnection: close\r\n\r\n"
                     .encode())
        await writer.drain()
        raw = await reader.read(1 << 20)
        writer.close()
        head, _, payload = raw.partition(b"\r\n\r\n")
        return int(head.split(b" ")[1]), json.loads(payload)

    async def test_mesh_surfaces(self):
        from bifromq_tpu.apiserver import APIServer
        from bifromq_tpu.mqtt.broker import MQTTBroker
        from bifromq_tpu.utils.metrics import MetricsRegistry
        m, whale = _skewed_mesh()          # registers with OBS.device
        reb = MeshRebalancer(m, max_skew=1.2, min_heat=64)
        reb.plan()
        broker = MQTTBroker(port=0)
        await broker.start()
        api = APIServer(broker, port=0, metrics=MetricsRegistry())
        await api.start()
        try:
            status, out = await self._http(api.port, "GET", "/mesh")
            assert status == 200
            mine = [s for s in out["meshes"] if s["n_shards"] == 4
                    and any(r["heat"] >= 4096 for r in s["shard_load"])]
            assert mine, out
            assert mine[0]["skew"] > 1.0

            status, out = await self._http(api.port, "GET",
                                           "/mesh/rebalance")
            assert status == 200
            # the endpoint lists every live (weakly-registered) mesh's
            # rebalancer — other suites' not-yet-collected matchers may
            # precede ours, so select by the decision we just planned
            # instead of by position
            rebs = [r for r in out["rebalancers"]
                    if any(d.get("tenant") == whale
                           for d in r["decisions"])]
            assert rebs, out
            assert rebs[0]["decisions"][-1]["tenant"] == whale

            status, out = await self._http(api.port, "GET", "/metrics")
            assert status == 200
            assert "mesh" in out
            assert any(s["n_shards"] == 4
                       for s in out["mesh"]["shard_load"])
        finally:
            await api.stop()
            broker.inbox.close()
            await broker.stop()

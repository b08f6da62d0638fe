"""Incremental automaton patching tests (ISSUE 9 tentpole).

The contract under test: every mutation folds into the LIVE base arenas
as an in-place delta patch (append-only nodes/edges, tombstoned route
slots, narrow device updates) with

- zero full rebuilds and zero match-cache generation bumps under steady
  churn,
- row-identical results to the ``SubscriptionTrie`` oracle at every
  interleaving point (randomized gate), and again after a forced
  compaction folds the patched arenas into a fresh tight base,
- in-flight-batch safety: a patch landing between dispatch and fetch
  never corrupts the in-flight expansion (relocated slots stay
  live-readable; tombstones suppress like the old overlay did),
- tombstone-walk correctness across '#'/'+'/'$share' filters, including
  the parent-folded '#'-child columns the walk reads.
"""

import asyncio
import random

from bifromq_tpu.models.automaton import PatchableTrie
from bifromq_tpu.models.matcher import TpuMatcher
from bifromq_tpu.models.oracle import Route
from bifromq_tpu.obs import OBS
from bifromq_tpu.types import RouteMatcher


def mk_route(tf: str, rid: str, inc: int = 0, broker: int = 0) -> Route:
    return Route(matcher=RouteMatcher.from_topic_filter(tf),
                 broker_id=broker, receiver_id=rid, deliverer_key="d0",
                 incarnation=inc)


def canon(m):
    return (sorted((r.matcher.mqtt_topic_filter, r.receiver_url)
                   for r in m.normal),
            {f: sorted(r.receiver_url for r in ms)
             for f, ms in m.groups.items()})


def assert_oracle_parity(m, queries, ctx=""):
    got = m.match_batch(queries)
    want = m.match_from_tries(queries)
    for q, a, b in zip(queries, got, want):
        assert canon(a) == canon(b), f"{ctx}: {q} -> {canon(a)} != {canon(b)}"


FILTERS = ["a/b", "a/+", "a/#", "+/b", "x/y/z", "a/b/c", "#",
           "deep/1/2/3/4", "$share/g1/a/b", "$share/g1/a/+",
           "$oshare/g2/a/b", "lit/p", "lit/q"]
TOPICS = [["a", "b"], ["a", "c"], ["a", "b", "c"], ["x", "y", "z"],
          ["deep", "1", "2", "3", "4"], ["lit", "p"], ["q"],
          ["a", "b", "c", "d"]]


class TestPatchBasics:
    def test_mutations_patch_in_place_no_recompile(self):
        m = TpuMatcher(max_levels=8, k_states=16, auto_compact=False)
        m.add_route("T", mk_route("a/b", "r1"))
        m.refresh()
        assert isinstance(m._base_ct, PatchableTrie)
        c0 = m.compile_count
        m.add_route("T", mk_route("a/+", "r2"))
        m.add_route("T", mk_route("a/#", "r3"))
        assert m.overlay_size == 0          # patched, not overlaid
        assert m.patch_count == 2
        res = m.match_batch([("T", ["a", "b"])])[0]
        assert sorted(r.receiver_id for r in res.normal) == \
            ["r1", "r2", "r3"]
        assert m.compile_count == c0, "the serving path recompiled"

    def test_tombstone_remove_zero_device_traffic(self):
        m = TpuMatcher(max_levels=8, auto_compact=False)
        m.add_route("T", mk_route("a/b", "r1"))
        m.add_route("T", mk_route("a/b", "r2"))
        m.refresh()
        m.match_batch([("T", ["a", "b"])])      # flush any install dirt
        flushes0 = m.patch_flushes
        m.remove_route("T", RouteMatcher.from_topic_filter("a/b"),
                       (0, "r1", "d0"))
        res = m.match_batch([("T", ["a", "b"])])[0]
        assert [r.receiver_id for r in res.normal] == ["r2"]
        # a tombstone is host-only: intervals untouched, no device flush
        assert m.patch_flushes == flushes0
        assert m._base_ct.dead_slots == 1

    def test_incarnation_upsert_replaces_slot_in_place(self):
        m = TpuMatcher(max_levels=8, auto_compact=False)
        m.add_route("T", mk_route("a/b", "r1", inc=1))
        m.refresh()
        slots0 = len(m._base_ct.matchings)
        m.add_route("T", mk_route("a/b", "r1", inc=5))
        assert len(m._base_ct.matchings) == slots0   # no new slot
        res = m.match_batch([("T", ["a", "b"])])[0]
        assert [r.incarnation for r in res.normal] == [5]
        # stale re-add stays a no-op
        assert not m.add_route("T", mk_route("a/b", "r1", inc=3))

    def test_new_tenant_patched_into_base(self):
        m = TpuMatcher(max_levels=8, auto_compact=False)
        m.add_route("T1", mk_route("a/b", "r1"))
        m.refresh()
        m.add_route("T2", mk_route("a/+", "r2"))
        assert m._base_ct.root_of("T2") >= 0, "tenant root not patched in"
        res = m.match_batch([("T2", ["a", "b"])])[0]
        assert [r.receiver_id for r in res.normal] == ["r2"]
        assert m.match_batch([("zz", ["a", "b"])])[0].all_routes() == []

    def test_group_member_churn_swaps_slot_object(self):
        m = TpuMatcher(max_levels=8, auto_compact=False)
        m.add_route("T", mk_route("$share/g/a/b", "r1"))
        m.refresh()
        m.match_batch([("T", ["a", "b"])])
        flushes0 = m.patch_flushes
        m.add_route("T", mk_route("$share/g/a/b", "r2"))
        m.remove_route("T", RouteMatcher.from_topic_filter("$share/g/a/b"),
                       (0, "r1", "d0"))
        res = m.match_batch([("T", ["a", "b"])])[0]
        assert sorted(r.receiver_id
                      for r in res.groups["$share/g/a/b"]) == ["r2"]
        # member churn on an existing group slot is a host object swap
        assert m.patch_flushes == flushes0
        # last member out tombstones the slot
        m.remove_route("T", RouteMatcher.from_topic_filter("$share/g/a/b"),
                       (0, "r2", "d0"))
        assert m.match_batch([("T", ["a", "b"])])[0].all_routes() == []

    def test_refresh_skips_rebuild_when_fully_patched(self):
        m = TpuMatcher(max_levels=8, auto_compact=False)
        m.add_route("T", mk_route("a/b", "r1"))
        m.refresh()
        c0 = m.compile_count
        for i in range(20):
            m.add_route("T", mk_route(f"s/{i}/+", f"r{i}"))
        m.refresh()                      # quiesce: shadow sync, no compile
        assert m.compile_count == c0
        assert m.overlay_size == 0
        # and the shadow actually absorbed the ops: a forced compaction
        # from it reproduces the same results
        m._maybe_compact(force=True)
        m.drain()
        assert m.compile_count == c0 + 1
        assert_oracle_parity(m, [("T", t) for t in TOPICS],
                             "post-forced-compaction")

    def test_kill_switch_restores_overlay_path(self, monkeypatch):
        monkeypatch.setenv("BIFROMQ_PATCH", "0")
        m = TpuMatcher(max_levels=8, auto_compact=False)
        m.add_route("T", mk_route("a/b", "r1"))
        m.refresh()
        assert not isinstance(m._base_ct, PatchableTrie)
        m.add_route("T", mk_route("a/+", "r2"))
        assert m.overlay_size == 1          # classic overlay serving
        res = m.match_batch([("T", ["a", "b"])])[0]
        assert sorted(r.receiver_id for r in res.normal) == ["r1", "r2"]


class TestTombstoneWalks:
    """Tombstone correctness through every wildcard path the walk takes —
    incl. the '#'-child (rcount, rstart) folded into the PARENT record,
    which the patcher must re-fold on every interval change."""

    def test_hash_child_added_post_base_folds_into_parent(self):
        m = TpuMatcher(max_levels=8, auto_compact=False)
        m.add_route("T", mk_route("a/b", "r1"))
        m.refresh()
        # '#': matched via the parent's NODE_HRCOUNT/HRSTART columns only
        m.add_route("T", mk_route("a/#", "rh"))
        for topic in (["a"], ["a", "b"], ["a", "b", "c"]):
            res = m.match_batch([("T", topic)])[0]
            assert "rh" in [r.receiver_id for r in res.normal], topic
        m.remove_route("T", RouteMatcher.from_topic_filter("a/#"),
                       (0, "rh", "d0"))
        for topic in (["a"], ["a", "b"], ["a", "b", "c"]):
            res = m.match_batch([("T", topic)])[0]
            assert "rh" not in [r.receiver_id for r in res.normal], topic

    def test_root_hash_and_plus_churn(self):
        m = TpuMatcher(max_levels=8, auto_compact=False)
        m.add_route("T", mk_route("x/y", "seed"))
        m.refresh()
        m.add_route("T", mk_route("#", "rall"))
        m.add_route("T", mk_route("+/y", "rpy"))
        assert_oracle_parity(m, [("T", t) for t in TOPICS], "add")
        m.remove_route("T", RouteMatcher.from_topic_filter("#"),
                       (0, "rall", "d0"))
        m.remove_route("T", RouteMatcher.from_topic_filter("+/y"),
                       (0, "rpy", "d0"))
        assert_oracle_parity(m, [("T", t) for t in TOPICS], "remove")
        # $-topics keep the [MQTT-4.7.2-1] rule through patched roots
        m.add_route("T", mk_route("#", "rall2"))
        m.add_route("T", mk_route("$sys/health", "rsys"))
        assert_oracle_parity(
            m, [("T", ["$sys", "health"]), ("T", ["q"])], "sys")

    def test_group_membership_is_one_object_until_patched(self):
        """The election knows a standing membership by identity: the
        match hands on the slot's own ``members`` tuple, whatever the
        topic, and a join or leave swaps in another."""
        m = TpuMatcher(max_levels=8, auto_compact=False)
        m.add_route("T", mk_route("base", "r0"))
        m.refresh()
        flt = "$share/g/s/+"
        m.add_route("T", mk_route(flt, "ra"))
        m.add_route("T", mk_route(flt, "rb"))
        queries = [("T", ["s", "1"]), ("T", ["s", "2"])]

        def memberships():
            got = [res.groups[flt] for res in m.match_batch(queries)]
            assert got[0] is got[1] and type(got[0]) is tuple
            return got[0]

        first = memberships()
        assert memberships() is first
        m.add_route("T", mk_route(flt, "rc"))               # patch_add
        joined = memberships()
        assert joined is not first and memberships() is joined
        assert sorted(r.receiver_id for r in joined) == ["ra", "rb", "rc"]
        m.remove_route("T", RouteMatcher.from_topic_filter(flt),
                       mk_route(flt, "ra").receiver_url)    # patch_remove
        left = memberships()
        assert left is not joined and memberships() is left
        assert sorted(r.receiver_id for r in left) == ["rb", "rc"]
        assert m.overlay_size == 0 and m.compile_count == 1
        assert_oracle_parity(m, queries, "membership identity")

    def test_share_filter_tombstones(self):
        m = TpuMatcher(max_levels=8, auto_compact=False)
        m.add_route("T", mk_route("s/1", "seed"))
        m.refresh()
        m.add_route("T", mk_route("$share/g/s/+", "ra"))
        m.add_route("T", mk_route("$oshare/g/s/+", "rb"))
        assert_oracle_parity(m, [("T", ["s", "1"])], "share add")
        m.remove_route("T", RouteMatcher.from_topic_filter("$share/g/s/+"),
                       (0, "ra", "d0"))
        res = m.match_batch([("T", ["s", "1"])])[0]
        assert list(res.groups) == ["$oshare/g/s/+"]
        assert_oracle_parity(m, [("T", ["s", "1"])], "share remove")


class TestRandomizedChurnParity:
    def test_interleaved_churn_triple_parity(self):
        """THE acceptance gate: randomized mutation/query interleaving —
        patched automaton vs the SubscriptionTrie oracle at every probe
        point, zero rebuilds, zero generation bumps; then a forced
        compaction folds the arenas and the fresh base must agree again
        (patched ≡ oracle ≡ post-compaction base)."""
        m = TpuMatcher(max_levels=8, k_states=16, auto_compact=False,
                       match_cache=True)
        rng = random.Random(23)
        for i in range(60):
            m.add_route(f"T{i % 3}",
                        mk_route(FILTERS[i % len(FILTERS)], f"r{i}", inc=i))
        m.refresh()
        c0 = m.compile_count
        gen0 = m.match_cache._gen
        live = {}
        for step in range(400):
            tenant = f"T{rng.randrange(3)}"
            tf = rng.choice(FILTERS)
            rid = f"r{rng.randrange(80)}"
            if rng.random() < 0.55:
                m.add_route(tenant, mk_route(tf, rid, inc=step))
                live[(tenant, tf, rid)] = step
            else:
                m.remove_route(tenant, RouteMatcher.from_topic_filter(tf),
                               (0, rid, "d0"), incarnation=step)
                live.pop((tenant, tf, rid), None)
            if step % 20 == 0:
                queries = [(f"T{rng.randrange(3)}", rng.choice(TOPICS))
                           for _ in range(8)]
                assert_oracle_parity(m, queries, f"step {step}")
        assert m.compile_count == c0, "steady churn rebuilt the base"
        assert m.match_cache._gen == gen0, "generation bumped under churn"
        assert m.overlay_size == 0
        # fold the patched arenas into a fresh tight base and re-verify
        m._maybe_compact(force=True)
        m.drain()
        assert isinstance(m._base_ct, PatchableTrie)
        assert m._base_ct.dead_slots == 0       # compaction reclaimed
        assert m.match_cache._gen == gen0, "pure compaction bumped gen"
        queries = [(f"T{t}", topic) for t in range(3) for topic in TOPICS]
        assert_oracle_parity(m, queries, "post-compaction")

    def test_churn_with_background_compaction_threshold(self, monkeypatch):
        """Remove-heavy churn crossing the tombstone threshold compacts in
        the BACKGROUND (reason=frag) and serving stays exact throughout."""
        monkeypatch.setenv("BIFROMQ_PATCH_FRAG_RATIO", "0.1")
        monkeypatch.setenv("BIFROMQ_PATCH_FRAG_FLOOR", "16")
        OBS.profiler.ledger.reset()
        m = TpuMatcher(max_levels=8, k_states=16, auto_compact=True,
                       compact_threshold=10_000, match_cache=True)
        for i in range(120):
            m.add_route("T", mk_route(f"s/{i}/+", f"r{i}"))
        m.refresh()
        gen0 = m.match_cache._gen
        rng = random.Random(5)
        for step in range(300):
            i = rng.randrange(160)
            if rng.random() < 0.5:
                m.add_route("T", mk_route(f"s/{i}/+", f"r{i}", inc=step))
            else:
                m.remove_route("T",
                               RouteMatcher.from_topic_filter(f"s/{i}/+"),
                               (0, f"r{i}", "d0"), incarnation=step)
            if step % 13 == 0:
                i = rng.randrange(160)
                assert_oracle_parity(m, [("T", ["s", str(i), "leaf"])],
                                     f"step {step}")
        m.drain()
        assert m.compile_count >= 2, "frag compaction never ran"
        reasons = [e["reason"] for e in OBS.profiler.ledger.events()]
        assert "frag" in reasons, reasons
        assert m.match_cache._gen == gen0, \
            "fragmentation compaction must not bump the generation"
        assert_oracle_parity(m, [("T", ["s", str(i), "leaf"])
                                 for i in range(0, 160, 11)], "post")


class TestArenaGrowth:
    def test_node_arena_growth_keeps_serving_exact(self):
        m = TpuMatcher(max_levels=8, auto_compact=False)
        m.add_route("T", mk_route("seed/1", "r0"))
        m.refresh()
        cap0 = m._base_ct.node_tab.shape[0]
        i = 0
        while m._base_ct.node_grows == 0 and i < 4 * cap0:
            m.add_route("T", mk_route(f"grow/{i}/x", f"g{i}"))
            i += 1
        assert m._base_ct.node_grows >= 1, "arena never grew"
        assert m._base_ct.node_tab.shape[0] > cap0
        # growth re-ships + re-traces; results stay exact
        assert_oracle_parity(
            m, [("T", ["grow", str(j), "x"]) for j in range(0, i, 7)]
            + [("T", ["seed", "1"])], "post-growth")

    def test_edge_table_regrow_on_bucket_overflow(self):
        m = TpuMatcher(max_levels=8, auto_compact=False)
        m.add_route("T", mk_route("seed/1", "r0"))
        m.refresh()
        nb0 = m._base_ct.edge_tab.shape[0]
        # a tiny base builds 8 buckets x 16 entries; a few hundred literal
        # edges must overflow one and force the vectorized regrow
        i = 0
        while m._base_ct.edge_regrows == 0 and i < 2000:
            m.add_route("T", mk_route(f"lit{i}", f"l{i}"))
            i += 1
        assert m._base_ct.edge_regrows >= 1, "edge table never regrew"
        assert m._base_ct.edge_tab.shape[0] > nb0
        assert_oracle_parity(
            m, [("T", [f"lit{j}"]) for j in range(0, i, 17)]
            + [("T", ["seed", "1"])], "post-regrow")


class TestWalkReadsPatchedArenas:
    def test_narrow_flush_visible_on_next_launch_without_rebuild(self):
        """A narrow flush is visible on the next launch with no rebuild,
        and tombstones die in the shared host expansion."""
        m = TpuMatcher(max_levels=6, k_states=8, auto_compact=False)
        m.add_route("T", mk_route("a/b", "r1"))
        m.refresh()
        m.add_route("T", mk_route("a/+", "r2"))
        m.add_route("T", mk_route("a/#", "r3"))
        res = m.match_batch([("T", ["a", "b"])])[0]
        assert sorted(r.receiver_id for r in res.normal) == \
            ["r1", "r2", "r3"]
        m.remove_route("T", RouteMatcher.from_topic_filter("a/+"),
                       (0, "r2", "d0"))
        res = m.match_batch([("T", ["a", "b"])])[0]
        assert sorted(r.receiver_id for r in res.normal) == ["r1", "r3"]
        assert m.compile_count == 1 and m.patch_count == 3


class TestInFlightSafety:
    async def test_relocation_mid_flight_keeps_dispatch_snapshot(self):
        """A patch that RELOCATES a node's slot interval while a batch is
        between dispatch and fetch: the in-flight expansion still reads
        the pre-patch interval, whose old slot copies must stay live —
        the route set at dispatch time, exactly."""
        from tests.test_pipeline import _Gate, _gate_matcher
        m = TpuMatcher(max_levels=8, k_states=8, auto_compact=False,
                       match_cache=False)
        m.add_route("T", mk_route("a/b", "r1"))
        m.add_route("T", mk_route("a/c", "r2"))  # pins r1's interval mid-arena
        m.refresh()
        assert isinstance(m._base_ct, PatchableTrie)
        gate = _Gate()
        _gate_matcher(m, gate)
        task = asyncio.ensure_future(
            m.match_batch_async([("T", ["a", "b"])], batch=16))
        for _ in range(10):
            await asyncio.sleep(0)
        # lands mid-flight: a/b's interval is NOT at the tail -> relocate
        m.add_route("T", mk_route("a/b", "r9"))
        assert m._base_ct.relocations == 1
        gate.open = True
        res = await task
        assert [r.receiver_id for r in res[0].normal] == ["r1"], \
            "in-flight expansion lost the pre-patch route set"
        # and the NEXT dispatch serves the patched interval
        res = m.match_batch([("T", ["a", "b"])])[0]
        assert sorted(r.receiver_id for r in res.normal) == ["r1", "r9"]

    async def test_tombstone_mid_flight_suppresses_like_overlay(self):
        from tests.test_pipeline import _Gate, _gate_matcher
        m = TpuMatcher(max_levels=8, k_states=8, auto_compact=False,
                       match_cache=False)
        m.add_route("T", mk_route("a/b", "r1"))
        m.add_route("T", mk_route("a/+", "r2"))
        m.refresh()
        gate = _Gate()
        _gate_matcher(m, gate)
        task = asyncio.ensure_future(
            m.match_batch_async([("T", ["a", "b"])], batch=16))
        for _ in range(10):
            await asyncio.sleep(0)
        m.remove_route("T", RouteMatcher.from_topic_filter("a/+"),
                       (0, "r2", "d0"))
        gate.open = True
        res = await task
        # the established tombstone semantic: a remove landing mid-flight
        # suppresses the route in the concurrent expansion
        assert [r.receiver_id for r in res[0].normal] == ["r1"]


class TestFailureRecovery:
    def test_failed_flush_restores_dirty_as_full_reupload(self, monkeypatch):
        """A device flush that raises mid-update (tunnel hiccup, OOM)
        must not lose the drained patches: the dirty state is restored
        as a full re-upload and the next dispatch rebuilds the device
        tables from the authoritative host arenas."""
        from bifromq_tpu.ops import match as match_ops
        m = TpuMatcher(max_levels=8, auto_compact=False)
        m.add_route("T", mk_route("a/b", "r1"))
        m.refresh()
        m.match_batch([("T", ["a", "b"])])
        m.add_route("T", mk_route("a/+", "r2"))
        real = match_ops._patch_device_trie
        boom = {"n": 0}

        def flaky(*a, **kw):
            if boom["n"] == 0:
                boom["n"] += 1
                raise RuntimeError("injected flush failure")
            return real(*a, **kw)
        monkeypatch.setattr(match_ops, "_patch_device_trie", flaky)
        try:
            m.match_batch([("T", ["a", "b"])])
        except RuntimeError:
            pass    # sync path propagates (worker's degradation boundary)
        # the drained rows were NOT lost: full re-upload is pending
        assert m._base_ct.dirty
        assert {"node", "edge"} <= m._base_ct._full
        res = m.match_batch([("T", ["a", "b"])])[0]
        assert sorted(r.receiver_id for r in res.normal) == ["r1", "r2"]

    def test_patch_era_hash_collision_falls_back_to_overlay(self):
        """A same-parent 64-bit level-hash collision among patch-inserted
        edges must never descend into the wrong child: the op falls back
        to the overlay (exact serving) instead."""
        from bifromq_tpu.models.automaton import level_hash
        m = TpuMatcher(max_levels=8, auto_compact=False)
        m.add_route("T", mk_route("seed/x", "r0"))
        m.refresh()
        m.add_route("T", mk_route("edge/one", "r1"))     # patch-era edge
        base = m._base_ct
        # simulate the astronomically-unlikely collision: rewrite the
        # recorded level string of 'one' under its parent so the next
        # descend of 'one' sees a conflicting claimant for its (h1, h2)
        root = base.tenant_root["T"]
        h1, h2 = level_hash("edge", base.salt)
        edge_nid = base._edge_child(root, h1, h2)
        k1, k2 = level_hash("one", base.salt)
        base._edge_level[(edge_nid, k1, k2)] = "SOMETHING-ELSE"
        fb0 = m.patch_fallbacks
        m.add_route("T", mk_route("edge/one", "r2"))
        assert m.patch_fallbacks == fb0 + 1
        assert m.overlay_size == 1          # served exactly via overlay
        res = m.match_batch([("T", ["edge", "one"])])[0]
        assert sorted(r.receiver_id for r in res.normal) == ["r1", "r2"]


class TestObservability:
    def test_patch_ledger_and_capacity_report(self):
        OBS.profiler.ledger.reset()
        m = TpuMatcher(max_levels=8, auto_compact=False)
        m.add_route("T", mk_route("a/b", "r1"))
        m.refresh()
        m.add_route("T", mk_route("a/+", "r2"))
        m.match_batch([("T", ["a", "b"])])          # forces the flush
        led = OBS.profiler.ledger.snapshot()["patch"]
        assert led["flushes"] >= 1
        assert led["rows"] >= 1
        assert led["bytes"] > 0
        ev = led["events"][-1]
        assert ev["reason"] in ("rows", "node", "edge", "node+edge")
        assert ev["mutations"] >= 1 and ev["apply_ms"] >= 0
        # capacity plane: headroom + tombstone accounting rides measure()
        from bifromq_tpu.obs.capacity import measure
        m.remove_route("T", RouteMatcher.from_topic_filter("a/b"),
                       (0, "r1", "d0"))
        rep = measure(m)
        assert rep["installed"] and "patch" in rep
        assert rep["patch"]["dead_slots"] == 1
        assert 0.0 < rep["patch"]["node_headroom_ratio"] < 1.0
        assert rep["patched_mutations"] == m.patch_count
        # parity stays exact for the padded arenas (model == device)
        assert rep["parity_error"] == 0.0

    def test_patchable_base_capacity_parity_after_growth(self):
        from bifromq_tpu.obs.capacity import measure
        m = TpuMatcher(max_levels=8, auto_compact=False)
        m.add_route("T", mk_route("a/b", "r1"))
        m.refresh()
        i = 0
        while m._base_ct.node_grows == 0 and i < 500:
            m.add_route("T", mk_route(f"g/{i}/x", f"g{i}"))
            i += 1
        m.match_batch([("T", ["a", "b"])])          # flush the growth
        rep = measure(m)
        assert rep["parity_error"] == 0.0, rep

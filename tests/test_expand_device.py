"""Device fan-out expansion parity (ISSUE 19).

The device expansion stage (ops.match.expand_pairs + _bucket_pairs) must be
byte-identical to the host expander (ops.match.expand_intervals) on every
row it claims to serve — overflow rows, buffer-truncated rows and empty
batches included — and the peer bucketing must be an exact stable
regrouping of those pairs (oracle: bucket_pairs_host, numpy stable sort).
On top of the raw surfaces, the serving paths (single-chip TpuMatcher and
the 8-device CPU mesh, including a mid-migration dual-serve shard map)
must produce identical MatchedRoutes with ``BIFROMQ_DEVICE_EXPAND`` on
and off.
"""

import random

import numpy as np
import pytest

from bifromq_tpu.models.matcher import TpuMatcher, _HostPairs
from bifromq_tpu.models.oracle import Route
from bifromq_tpu.ops.match import (
    N_SENTINEL_BUCKETS, bucket_pairs_host, expand_intervals, expand_pairs,
    _bucket_pairs,
)
from bifromq_tpu.types import RouteMatcher


def rt(f, i, srv=None):
    key = f"{srv}|d{i}" if srv else f"d{i}"
    return Route(matcher=RouteMatcher.from_topic_filter(f), broker_id=0,
                 receiver_id=f"rcv{i}", deliverer_key=key, incarnation=0)


def canon(m):
    return (sorted((r.matcher.mqtt_topic_filter, r.receiver_url)
                   for r in m.normal),
            {f: sorted(r.receiver_url for r in ms)
             for f, ms in m.groups.items()})


def random_grid(rng, b, a, *, max_start=500, max_count=6, p_empty=0.3):
    starts = rng.integers(0, max_start, size=(b, a)).astype(np.int32)
    counts = rng.integers(1, max_count + 1, size=(b, a)).astype(np.int32)
    counts[rng.random((b, a)) < p_empty] = 0
    return starts, counts


def assert_pair_parity(starts, counts, cap):
    """Device pairs == host expander, row-for-row, on non-trunc rows."""
    slots, rows, offs, n_pairs, trunc = (
        np.asarray(x) for x in expand_pairs(starts, counts, cap=cap))
    h_slots, h_offs = expand_intervals(starts, counts)
    total = int(h_offs[-1])
    assert int(n_pairs) == min(total, cap)
    assert np.array_equal(offs.astype(np.int64), h_offs)
    assert np.array_equal(trunc, h_offs[1:] > cap)
    live = min(total, cap)
    assert np.array_equal(slots[:live], h_slots[:live])
    assert np.all(slots[live:] == -1)
    # rows mirror the host's np.repeat row ownership
    h_rows = np.repeat(np.arange(starts.shape[0]),
                       np.diff(h_offs)).astype(np.int32)
    assert np.array_equal(rows[:live], h_rows[:live])
    for i in range(starts.shape[0]):
        if not trunc[i]:
            lo, hi = int(offs[i]), int(offs[i + 1])
            assert np.array_equal(slots[lo:hi], h_slots[h_offs[i]:h_offs[i + 1]])


class TestExpandPairsParity:
    @pytest.mark.parametrize("shape", [(1, 1), (4, 8), (16, 32), (64, 4)])
    def test_random_grids(self, shape):
        rng = np.random.default_rng(7)
        b, a = shape
        for _ in range(5):
            starts, counts = random_grid(rng, b, a)
            assert_pair_parity(starts, counts, cap=b * a * 8)

    def test_empty_batch(self):
        starts = np.zeros((8, 4), np.int32)
        counts = np.zeros((8, 4), np.int32)
        assert_pair_parity(starts, counts, cap=64)

    def test_exact_cap_and_truncation(self):
        rng = np.random.default_rng(11)
        starts, counts = random_grid(rng, 16, 8, p_empty=0.0)
        total = int(counts.sum())
        # exact fit, one-short (truncates the tail), and tiny cap
        for cap in (total, total - 1, 8):
            assert_pair_parity(starts, counts, cap=cap)

    def test_escalation_width_grids(self):
        # the escalation re-walk emits WIDER grids (4x interval budget):
        # the raw surface must expand those identically too
        rng = np.random.default_rng(13)
        starts, counts = random_grid(rng, 8, 128, max_count=3)
        assert_pair_parity(starts, counts, cap=8 * 128 * 4)


class TestNonPow2LaneWidth:
    """Lane counts that are not a power of two take the ``lane // a``
    row recovery instead of the shift — same contract, same oracle."""

    @pytest.mark.parametrize("shape", [(4, 6), (32, 12)])
    def test_parity_wide_and_tiny_cap(self, shape):
        rng = np.random.default_rng(23)
        b, a = shape
        starts, counts = random_grid(rng, b, a)
        assert_pair_parity(starts, counts, cap=b * a * 8)
        assert_pair_parity(starts, counts, cap=17)

    def test_empty(self):
        z = np.zeros((4, 3), np.int32)
        assert_pair_parity(z, z, cap=16)


class TestBucketParity:
    @pytest.mark.parametrize("n_peers", [0, 1, 3, 20])
    def test_bucket_parity(self, n_peers):
        # n_peers=20 exercises the stable-argsort path (> 16 buckets),
        # the rest the unrolled counting sort; slot ids past the table
        # must land in UNKNOWN, -1 pads in the trailing PAD bucket
        rng = np.random.default_rng(n_peers)
        cap, n_slot = 256, 40
        slots = rng.integers(-1, n_slot + 10, size=cap).astype(np.int32)
        rows = rng.integers(0, 8, size=cap).astype(np.int32)
        slot_peer = rng.integers(0, n_peers + 1, size=n_slot).astype(np.int32)
        d_slots, d_rows, d_offs = (np.asarray(x) for x in _bucket_pairs(
            slots, rows, slot_peer, n_peers))
        h_slots, h_rows, h_offs = bucket_pairs_host(
            slots, rows, slot_peer, n_peers)
        assert np.array_equal(d_offs, h_offs)
        assert d_offs.shape == (n_peers + N_SENTINEL_BUCKETS + 1,)
        live = int(h_offs[-2])    # everything before the PAD bucket
        assert np.array_equal(d_slots[:live], h_slots[:live])
        assert np.array_equal(d_rows[:live], h_rows[:live])

    def test_empty_table(self):
        slots = np.array([3, -1, 7, -1], np.int32)
        rows = np.array([0, 0, 1, 0], np.int32)
        empty = np.zeros((0,), np.int32)
        d_slots, d_rows, d_offs = (np.asarray(x) for x in _bucket_pairs(
            slots, rows, empty, 0))
        h_slots, h_rows, h_offs = bucket_pairs_host(slots, rows, empty, 0)
        assert np.array_equal(d_offs, h_offs)
        assert np.array_equal(d_slots[:2], h_slots[:2])


FILTERS = ["a/b", "a/+", "s/#", "c/1/x", "live/+/topic", "d/e/f",
           "$share/g/sh/x", "+/+", "fan/+/+"]
TOPICS = ["a/b", "s/3/x", "c/1/x", "live/new/topic", "sh/x", "d/e/f",
          "fan/1/2", "q/none"]
TENANTS = [f"t{i}" for i in range(6)]


def _loaded_matcher(**kw):
    m = TpuMatcher(max_levels=8, k_states=16, auto_compact=False, **kw)
    rng = random.Random(5)
    for i in range(120):
        m.add_route(rng.choice(TENANTS), rt(rng.choice(FILTERS), i,
                                            srv=f"srv{i % 3}"))
    m.refresh()
    return m


def _queries(n=48, seed=9):
    rng = random.Random(seed)
    return [(rng.choice(TENANTS), rng.choice(TOPICS)) for _ in range(n)]


class TestServingParity:
    def test_device_vs_host_expand(self, monkeypatch):
        qs = _queries()
        monkeypatch.setenv("BIFROMQ_DEVICE_EXPAND", "1")
        dev = _loaded_matcher().match_batch(qs)
        monkeypatch.setenv("BIFROMQ_DEVICE_EXPAND", "0")
        host = _loaded_matcher().match_batch(qs)
        for q, a, b in zip(qs, dev, host):
            assert canon(a) == canon(b), q

    def test_truncation_path(self, monkeypatch):
        # CAP=1 starves the pair buffer: nearly every row re-expands on
        # host from the lazily fetched grids — results must not change
        qs = _queries()
        monkeypatch.setenv("BIFROMQ_DEVICE_EXPAND", "1")
        monkeypatch.setenv("BIFROMQ_EXPAND_CAP", "1")
        m = _loaded_matcher()
        dev = m.match_batch(qs)
        assert m.last_expanded is not None
        pairs, _ = m.last_expanded
        assert pairs.trunc.any(), "CAP=1 must truncate this workload"
        monkeypatch.setenv("BIFROMQ_DEVICE_EXPAND", "0")
        host = _loaded_matcher().match_batch(qs)
        for q, a, b in zip(qs, dev, host):
            assert canon(a) == canon(b), q

    def test_escalation_overflow_rows(self, monkeypatch):
        # max_intervals=1 forces walk overflow -> the escalation re-walk
        # (host expander by design) while healthy rows stay device-served
        qs = _queries()
        monkeypatch.setenv("BIFROMQ_DEVICE_EXPAND", "1")
        dev = _loaded_matcher(max_intervals=1).match_batch(qs)
        monkeypatch.setenv("BIFROMQ_DEVICE_EXPAND", "0")
        host = _loaded_matcher(max_intervals=1).match_batch(qs)
        for q, a, b in zip(qs, dev, host):
            assert canon(a) == canon(b), q

    def test_bucket_views_cover_pairs(self, monkeypatch):
        # the delivery surface: per-peer views must be a stable exact
        # regrouping of the batch's expanded pairs
        from bifromq_tpu.dist.deliverer import bucket_views
        monkeypatch.setenv("BIFROMQ_DEVICE_EXPAND", "1")
        m = _loaded_matcher()
        m.match_batch(_queries())
        pairs, tab = m.last_expanded
        assert isinstance(pairs, _HostPairs) and tab is not None
        views = bucket_views(pairs.peer_slots, pairs.peer_rows,
                             pairs.peer_offsets, tab.peers)
        n_live = int(pairs.n_pairs)
        got = sorted((int(s), int(r)) for _, vs, vr in views
                     for s, r in zip(vs, vr))
        want = sorted((int(s), int(r)) for s, r in
                      zip(pairs.slots[:n_live], pairs.rows[:n_live]))
        assert got == want
        for sid, _, _ in views:
            assert sid == "" or sid in tab.peers


class TestMeshParity:
    @pytest.fixture()
    def mesh_pair(self):
        import jax
        from bifromq_tpu.parallel.sharded import MeshMatcher, make_mesh
        assert len(jax.devices()) >= 8
        def build():
            m = MeshMatcher(mesh=make_mesh(1, 4), max_levels=8,
                            k_states=16, auto_compact=False,
                            match_cache=False)
            rng = random.Random(3)
            for i in range(90):
                m.add_route(rng.choice(TENANTS),
                            rt(rng.choice(FILTERS), i, srv=f"srv{i % 3}"))
            m.refresh()
            return m
        return build

    def test_mesh_device_vs_host(self, mesh_pair, monkeypatch):
        qs = _queries()
        monkeypatch.setenv("BIFROMQ_DEVICE_EXPAND", "1")
        m = mesh_pair()
        dev = m.match_batch(qs)
        pairs, tab = m.last_expanded
        totals = np.asarray(pairs.res.peer_totals)
        # the right_permute ring's global ledger == the live pair count
        assert int(totals[:-1].sum()) == int(np.asarray(pairs.n_pairs).sum())
        monkeypatch.setenv("BIFROMQ_DEVICE_EXPAND", "0")
        host = mesh_pair().match_batch(qs)
        for q, a, b in zip(qs, dev, host):
            assert canon(a) == canon(b), q

    def test_mid_migration_dual_serve(self, mesh_pair, monkeypatch):
        # a tenant serving from BOTH shards (dual-serve window held open
        # mid-copy) must expand identically on device and host
        qs = _queries()
        monkeypatch.setenv("BIFROMQ_DEVICE_EXPAND", "1")
        outs = {}
        for mode in ("1", "0"):
            monkeypatch.setenv("BIFROMQ_DEVICE_EXPAND", mode)
            m = mesh_pair()
            victim = "t1"
            src = m._base_ct.shard_of(victim)
            dst = (src + 2) % 4
            mig = m.migrate_tenant(victim, src, dst, run=False)
            while not mig.step(4):
                pass
            assert mig.state == "ready"       # dual-serve window open
            outs[mode] = m.match_batch(qs)
            oracle = m.match_from_tries(qs)
            for q, a, b in zip(qs, outs[mode], oracle):
                assert canon(a) == canon(b), (mode, q)
        for q, a, b in zip(qs, outs["1"], outs["0"]):
            assert canon(a) == canon(b), q

"""Device capacity model & planner (ISSUE 8): model-vs-live byte parity
on the CPU backend, planner calibration round trips, the HBM
verdict reproducing the serving gate's comparison without a dispatch,
mesh per-shard accounting, and the federated capacity surfaces."""

import numpy as np
import pytest

from bifromq_tpu.models.matcher import TpuMatcher
from bifromq_tpu.models.oracle import Route
from bifromq_tpu.obs import OBS, ObsHub
from bifromq_tpu.obs import capacity as cap
from bifromq_tpu.types import RouteMatcher


def mk_route(tf: str, rid: str) -> Route:
    return Route(matcher=RouteMatcher.from_topic_filter(tf), broker_id=0,
                 receiver_id=rid, deliverer_key="d")


def build_matcher(n: int = 300, tenant: str = "T") -> TpuMatcher:
    m = TpuMatcher(auto_compact=False)
    for i in range(n):
        m.add_route(tenant, mk_route(f"cap/{i}/+", f"r{i}"))
    m.refresh()
    return m


class TestExactAccounting:
    def test_model_matches_live_device_bytes_exactly(self):
        """The acceptance bar is <10%; the shape math makes it exact —
        the model derives from the same layout the upload path uses."""
        m = build_matcher(300)
        rep = cap.measure(m)
        assert rep["installed"]
        assert rep["kind"] == "single"
        assert rep["measured_device_bytes"] > 0
        assert rep["parity_error"] == 0.0
        assert rep["predicted"]["total"] == rep["measured_device_bytes"]

    def test_arena_bytes_sum_into_prediction(self):
        m = build_matcher(64)
        ct = m._base_ct
        arenas = ct.arena_bytes()
        pred = cap.compiled_trie_device_bytes(ct)
        for k, v in arenas.items():
            assert pred[k] == v
        assert pred["total"] == (sum(arenas.values()) + pred["count_tab"]
                                 + pred["route_tab"])

    def test_uninstalled_matcher_reports_not_installed(self):
        m = TpuMatcher(auto_compact=False)
        assert cap.measure(m) == {"installed": False}

    def test_probe_and_result_bytes(self):
        # [B, L+1] int32 ×2 + [B] int32 ×2 + [B] bool
        assert cap.probe_bytes(16, max_levels=16) == \
            16 * (2 * 17 * 4 + 2 * 4 + 1)
        assert cap.result_bytes(16, max_intervals=32) == \
            16 * (2 * 32 * 4 + 4 + 1)

    def test_inflight_donation_aliases(self):
        aliased = cap.inflight_bytes(16, ring_depth=2)
        assert aliased["per_slot"] == max(aliased["probe_bytes"],
                                         aliased["result_bytes"])
        # ISSUE 11: + one prep-ahead probe batch (the ring's prep
        # tickets bound stage-1 uploads to depth + 1)
        assert aliased["total"] == \
            aliased["per_slot"] * 2 + aliased["probe_bytes"]


class TestPlanner:
    def test_calibrated_prediction_is_exact_for_same_workload(self):
        n = 400
        m = build_matcher(n)
        planner = cap.CapacityPlanner().calibrate(m._base_ct, n)
        pred = planner.predict_tables(n)
        live = cap.compiled_trie_device_bytes(m._base_ct)
        # the acceptance criterion's 10% bar, met exactly by calibration
        assert abs(pred["total"] - live["total"]) / live["total"] < 0.10
        assert pred["edge_tab"] == \
            int(m._base_ct.edge_tab.size) * 4

    def test_fits_1m_subs_against_v5e_hbm(self):
        """The 1M-sub verdict from the model alone (nothing built or
        dispatched): tables + in-flight buffers + the compile-time
        double fit one v5e chip's 16 GB."""
        verdict = cap.CapacityPlanner().fits(
            1_000_000, hbm_limit_bytes=16 << 30)
        assert verdict["hbm"]["fits"] is True
        assert verdict["per_device_peak_bytes"] == (
            2 * verdict["tables"]["total"] + verdict["inflight"]["total"])
        assert verdict["tables"]["total"] > 100 << 20

    def test_fits_mesh_divides_the_tables(self):
        single = cap.CapacityPlanner().fits(1_000_000)
        mesh = cap.CapacityPlanner().fits(1_000_000, mesh=4)
        assert mesh["mesh"] == {"replicas": 1, "shards": 4}
        assert mesh["per_device_bytes"] < single["per_device_bytes"]

    def test_walk_bytes_agree_with_uploaded_tables(self):
        """The model's edge+route byte count equals what the serving
        walk actually gathers from on the uploaded DeviceTrie."""
        m = build_matcher(200)
        dev = m._device_trie
        assert cap.walk_bytes_from_compiled(m._base_ct) == \
            int(dev.edge_tab.nbytes) + int(dev.route_tab.nbytes)

    def test_hbm_headroom_math(self):
        verdict = cap.CapacityPlanner().fits(
            1000, hbm_limit_bytes=1 << 30)
        hbm = verdict["hbm"]
        assert hbm["limit_bytes"] == 1 << 30
        assert hbm["headroom_bytes"] == \
            (1 << 30) - verdict["per_device_peak_bytes"]
        assert hbm["fits"] is True
        tiny = cap.CapacityPlanner().fits(1_000_000,
                                          hbm_limit_bytes=1 << 20)
        assert tiny["hbm"]["fits"] is False

    def test_sharding_shrinks_per_device_tables(self):
        planner = cap.CapacityPlanner()
        one = planner.fits(1_000_000)
        four = planner.fits(1_000_000, mesh=(1, 4))
        assert four["tables"]["total"] < one["tables"]["total"]
        assert four["mesh"] == {"replicas": 1, "shards": 4}
        # mesh placement ships no node/count tables
        assert four["tables"]["node_tab"] == 0


class TestMeshAccounting:
    def test_sharded_tables_device_bytes(self):
        from bifromq_tpu.models.oracle import SubscriptionTrie
        from bifromq_tpu.parallel.sharded import build_sharded
        tries = {}
        for t in ("a", "b", "c", "d"):
            trie = SubscriptionTrie()
            for i in range(40):
                trie.add(mk_route(f"{t}/x/{i}", f"r{i}"))
            tries[t] = trie
        tables = build_sharded(tries, 2)
        acc = tables.device_bytes()
        assert acc["n_shards"] == 2
        expected = (tables.edge_tab.nbytes + tables.child_list.nbytes
                    + tables.route_tab.nbytes)
        assert acc["total"]["total"] == expected
        assert len(acc["per_shard"]) == 2
        for row in acc["per_shard"]:
            assert row["padded_bytes"] == expected // 2
            assert 0 < row["real_bytes"] <= row["padded_bytes"]
        assert 0.0 <= acc["pad_waste_ratio"] < 1.0

    def test_per_shard_bytes_within_the_planner_prediction(self):
        """No shard's stacked tables outgrow ``CapacityPlanner.fits``'s
        per-shard figure when the planner is fitted to the busiest shard
        (at scale only the slow ``tests/test_mesh_scale.py`` holds it).
        Host tables as compiled: at this size the patch plane's pow2
        headroom alone is 0.2-2% over the figure."""
        from bifromq_tpu import workloads
        from bifromq_tpu.parallel.sharded import build_sharded
        n_shards = 4
        tries = workloads.config_multi_tenant(n_tenants=32,
                                              total_subs=6000, seed=0)
        tables = build_sharded(tries, n_shards)
        worst = max(p["padded_bytes"]
                    for p in tables.device_bytes()["per_shard"])
        slots_ref = max(ct.n_slots for ct in tables.compiled)
        e_max = max(
            int(np.count_nonzero(ct.edge_tab.reshape(-1, 4)[:, 0] >= 0))
            for ct in tables.compiled)
        planner = cap.CapacityPlanner(
            nodes_per_sub=max(ct.node_tab.shape[0]
                              for ct in tables.compiled) / slots_ref,
            edges_per_sub=e_max / slots_ref, slots_per_sub=1.0,
            edge_load=e_max / (tables.edge_tab.shape[1]
                               * tables.probe_len))
        predicted = planner.fits(
            slots_ref * n_shards, mesh=(1, n_shards),
            probe_len=tables.probe_len)["tables"]["total"]
        assert 0 < worst <= predicted, (worst, predicted)

    def test_mesh_matcher_measure(self):
        import jax
        from bifromq_tpu.parallel.sharded import MeshMatcher, make_mesh
        mesh = make_mesh(1, 2, devices=jax.devices()[:2])
        m = MeshMatcher(mesh=mesh, auto_compact=False)
        for i in range(50):
            m.add_route("T", mk_route(f"m/{i}", f"r{i}"))
        m.refresh()
        rep = cap.measure(m)
        assert rep["installed"] and rep["kind"] == "mesh"
        assert rep["parity_error"] == 0.0


class TestReportSurfaces:
    def test_capacity_report_covers_registered_matchers(self):
        OBS.device.reset()
        m = build_matcher(128)
        rep = cap.capacity_report(n_subs=500)
        assert rep["table_bytes"] >= \
            cap.measure(m)["measured_device_bytes"]
        assert rep["parity_error"] == 0.0
        assert "hbm" in rep["fits"]
        assert rep["planner"]["calibrated_from"] is not None

    def test_digest_capacity_is_cheap_and_compact(self):
        hub = ObsHub()
        m = build_matcher(64)
        hub.device.register_matcher(m)
        d = cap.digest_capacity(hub)
        assert d["table_bytes"] == \
            cap.measure(m)["measured_device_bytes"]
        assert "vmem_fits" not in d

    def test_hbm_env_override(self, monkeypatch):
        monkeypatch.setenv("BIFROMQ_HBM_BYTES", str(1 << 31))
        assert cap._live_hbm_limit() == 1 << 31

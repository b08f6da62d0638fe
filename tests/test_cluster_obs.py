"""Cluster observability plane (ISSUE 5): digest publish/decode over real
gossip, stale-digest expiry under a fake clock, bucket-wise federation math
verified against a single combined histogram, health-aware rendezvous pick,
per-tenant detector overrides, batch-emit span links, and the exporter's
resource envelope."""

import asyncio
import json
import time

import pytest

from bifromq_tpu import trace
from bifromq_tpu.cluster.membership import AgentHost
from bifromq_tpu.obs import ObsHub
from bifromq_tpu.obs.clusterview import (AGENT_ID, SERVICE,
                                         ClusterObsRPCService, ClusterView,
                                         derive_red_row, merge_tenant_raws)
from bifromq_tpu.obs.slo import TenantSLO
from bifromq_tpu.rpc.fabric import RPCServer, ServiceRegistry
from bifromq_tpu.utils.hlc import HLC

pytestmark = pytest.mark.asyncio


async def wait_for(cond, timeout=8.0):
    deadline = asyncio.get_running_loop().time() + timeout
    while asyncio.get_running_loop().time() < deadline:
        if cond():
            return
        await asyncio.sleep(0.05)
    raise AssertionError("condition not reached")


class FakeHost:
    """Minimal AgentHost stand-in: just the agent-metadata surface the
    ClusterView consumes (real-gossip coverage lives in the tests that
    spin actual AgentHosts)."""

    def __init__(self, node_id="me"):
        self.node_id = node_id
        self.agent_meta = {}        # node_id -> meta dict
        self.members = {}
        self._listeners = []

    def agent_members(self, agent_id):
        return dict(self.agent_meta)

    def host_agent(self, agent_id, meta=None):
        self.agent_meta[self.node_id] = meta or {}

    def stop_agent(self, agent_id):
        self.agent_meta.pop(self.node_id, None)

    def on_change(self, cb):
        self._listeners.append(cb)


def _peer_digest(**over):
    d = {"v": 1, "hlc": HLC.INST.get(), "breakers": {},
         "device": {"dispatch_queue_depth": 0, "batches_in_flight": 0,
                    "compile_count": 0, "mem_peak_bytes": 0},
         "match_cache_hit_rate": 0.0, "noisy": []}
    d.update(over)
    return d


def _fresh_hub(clock=None):
    kw = {"clock": clock} if clock is not None else {}
    hub = ObsHub(**kw)
    hub.enabled = True
    return hub


class TestDigest:
    async def test_digest_builds_all_fields(self):
        hub = _fresh_hub()
        hub.windows.record_flow("loud", 30)
        hub.windows.record_fanout("loud", 50)
        reg = ServiceRegistry()
        reg.breakers.for_endpoint("10.0.0.9:1").force_open()
        view = ClusterView("n1", FakeHost("n1"), hub=hub, registry=reg,
                           rpc_address="127.0.0.1:7777")
        d = view.build_digest()
        assert d["breakers"] == {"10.0.0.9:1": "open"}
        assert "dispatch_queue_depth" in d["device"]
        assert "mem_peak_bytes" in d["device"]
        assert "match_cache_hit_rate" in d
        assert d["noisy"] and d["noisy"][0]["tenant"] == "loud"
        assert HLC.physical(d["hlc"]) > 0
        # compact: closed breakers are ABSENT, not listed
        reg.breakers.for_endpoint("10.0.0.8:1")  # stays closed
        assert "10.0.0.8:1" not in view.build_digest()["breakers"]

    async def test_digest_publish_decode_over_real_gossip(self):
        """A digest published into agent metadata on one host arrives,
        intact, in a peer's ClusterView over real loopback UDP gossip."""
        a = AgentHost("ha")
        await a.start()
        b = AgentHost("hb", seeds=[("127.0.0.1", a.port)])
        await b.start()
        try:
            hub = _fresh_hub()
            reg = ServiceRegistry()
            reg.breakers.for_endpoint("127.0.0.1:9999").force_open()
            view_a = ClusterView("ha", a, hub=hub, registry=reg,
                                 rpc_address="127.0.0.1:5001", api_port=81)
            view_a.refresh()
            view_b = ClusterView("hb", b, hub=_fresh_hub())
            await wait_for(lambda: "ha" in view_b.peers())
            p = view_b.peers()["ha"]
            assert p["addr"] == "127.0.0.1:5001"
            assert p["api"] == 81
            assert not p["stale"]
            assert p["age_s"] < 5.0
            assert p["digest"]["breakers"] == {"127.0.0.1:9999": "open"}
            # ...and the peer's pick-demotion set reflects it
            view_b._recompute()
            assert view_b.suspect("127.0.0.1:9999")
            # the full member table carries the digest + age
            table = view_b.cluster_table()
            assert table["ha"]["alive"] and not table["ha"]["stale"]
            assert table["ha"]["digest"]["breakers"]
        finally:
            await a.stop()
            await b.stop()

    async def test_stale_digest_expiry_fake_clock(self):
        """A digest ages out deterministically: past ``stale_after_s`` it
        is flagged stale and stops feeding the unhealthy set (a dead
        node's last report says nothing about NOW)."""
        t0 = time.time()
        now = [t0]
        host = FakeHost("me")
        host.agent_meta["peer"] = {
            "addr": "127.0.0.1:6000",
            "digest": _peer_digest(breakers={"127.0.0.1:6001": "open"})}
        view = ClusterView("me", host, hub=_fresh_hub(),
                           stale_after_s=5.0, clock=lambda: now[0])
        view._recompute()
        assert not view.peers()["peer"]["stale"]
        assert view.suspect("127.0.0.1:6001")
        now[0] = t0 + 60.0                      # the peer went silent
        assert view.peers()["peer"]["stale"]
        view._recompute()
        assert not view.suspect("127.0.0.1:6001")
        # age is receipt-based: a CHANGED stamp resets it even though the
        # peer's wall clock may be skewed arbitrarily from ours
        host.agent_meta["peer"]["digest"] = _peer_digest(
            breakers={"127.0.0.1:6001": "open"})
        p = view.peers()["peer"]
        assert p["age_s"] == 0.0 and not p["stale"]
        view._recompute()
        assert view.suspect("127.0.0.1:6001")
        # a digest with no stamp at all is stale by definition
        host.agent_meta["peer"]["digest"] = {}
        assert view.peers()["peer"]["stale"]


class TestFederationMath:
    def test_bucketwise_merge_matches_single_combined_histogram(self):
        """Merging N nodes' raw windows bucket-wise must be EXACTLY what
        one histogram would report had it observed every sample."""
        t = [1000.0]
        clock = lambda: t[0]                          # noqa: E731
        node_a = TenantSLO(window_s=10.0, clock=clock)
        node_b = TenantSLO(window_s=10.0, clock=clock)
        combined = TenantSLO(window_s=10.0, clock=clock)
        samples_a = [0.001, 0.004, 0.016, 0.064, 0.256]
        samples_b = [0.002, 0.008, 0.032, 0.128, 0.512, 2.048]
        for s in samples_a:
            node_a.record_latency("T", "ingest", s)
            combined.record_latency("T", "ingest", s)
            node_a.record_flow("T")
            combined.record_flow("T")
        for s in samples_b:
            node_b.record_latency("T", "ingest", s)
            combined.record_latency("T", "ingest", s)
            node_b.record_flow("T")
            combined.record_flow("T")
        node_b.record_error("T", 3)
        combined.record_error("T", 3)
        merged = merge_tenant_raws([node_a.raw_snapshot(),
                                    node_b.raw_snapshot()])
        row = derive_red_row(merged["T"], 10.0)
        ref = combined.snapshot_tenant("T")
        assert row["rate_per_s"] == ref["rate_per_s"]
        assert row["errors_per_s"] == ref["errors_per_s"]
        assert row["error_rate"] == ref["error_rate"]
        assert row["stages"]["ingest"] == ref["stages"]["ingest"]
        # and the raw buckets themselves add exactly
        raw_c = combined.raw_snapshot()["T"]["stages"]["ingest"]
        assert merged["T"]["stages"]["ingest"] == raw_c

    def test_merge_disjoint_tenants_is_union(self):
        merged = merge_tenant_raws([
            {"a": {"flows": 1, "stages": {}}},
            {"b": {"flows": 2, "stages": {}}},
            {"a": {"flows": 4, "stages": {}}},
        ])
        assert merged["a"]["flows"] == 5 and merged["b"]["flows"] == 2


class TestHealthAwarePick:
    EPS = ["127.0.0.1:9001", "127.0.0.1:9002", "127.0.0.1:9003"]

    def _registry(self):
        reg = ServiceRegistry()
        for ep in self.EPS:
            reg.announce("svc", ep)
        return reg

    async def test_gossiped_open_breaker_demotes_endpoint(self):
        """The acceptance shape: an endpoint some OTHER node's breaker
        holds open is never picked — with zero local failures observed."""
        reg = self._registry()
        host = FakeHost("me")
        host.agent_meta["peer"] = {
            "addr": "127.0.0.1:8000",
            "digest": _peer_digest(breakers={self.EPS[1]: "open"})}
        view = ClusterView("me", host, hub=_fresh_hub())
        view._recompute()
        # sanity: without remote health, some key routes to the endpoint
        assert any(reg.pick("svc", f"k{i}") == self.EPS[1]
                   for i in range(64))
        reg.remote_health = view
        picks = {reg.pick("svc", f"k{i}") for i in range(64)}
        assert self.EPS[1] not in picks
        assert picks <= set(self.EPS)
        # local breakers never tripped — the demotion was pure gossip
        assert reg.breakers.states(include_closed=False) == {}

    async def test_deep_dispatch_queue_browns_out_node(self):
        reg = self._registry()
        host = FakeHost("me")
        host.agent_meta["worker"] = {
            "addr": self.EPS[2],
            "digest": _peer_digest(
                device={"dispatch_queue_depth": 999999,
                        "batches_in_flight": 2, "compile_count": 1,
                        "mem_peak_bytes": 0})}
        view = ClusterView("me", host, hub=_fresh_hub(),
                           queue_depth_threshold=4096)
        view._recompute()
        reg.remote_health = view
        assert view.suspect(self.EPS[2])
        assert all(reg.pick("svc", f"k{i}") != self.EPS[2]
                   for i in range(64))

    async def test_all_flagged_falls_back_to_available(self):
        """Gossip rumors must never blackhole the whole service: with
        every endpoint flagged, pick degrades to the available tier."""
        reg = self._registry()
        host = FakeHost("me")
        host.agent_meta["peer"] = {
            "addr": "127.0.0.1:8000",
            "digest": _peer_digest(
                breakers={ep: "open" for ep in self.EPS})}
        view = ClusterView("me", host, hub=_fresh_hub())
        view._recompute()
        reg.remote_health = view
        assert reg.pick("svc", "k") in self.EPS

    async def test_own_endpoint_never_self_flagged(self):
        host = FakeHost("me")
        host.agent_meta["peer"] = {
            "addr": "127.0.0.1:8000",
            "digest": _peer_digest(breakers={"127.0.0.1:5555": "open"})}
        view = ClusterView("me", host, hub=_fresh_hub(),
                           rpc_address="127.0.0.1:5555")
        view._recompute()
        assert not view.suspect("127.0.0.1:5555")

    async def test_suspect_errors_never_break_pick(self):
        reg = self._registry()

        class Broken:
            def suspect(self, ep):
                raise RuntimeError("telemetry bug")
        reg.remote_health = Broken()
        assert reg.pick("svc", "k") in self.EPS


class TestFederatedViews:
    async def test_federated_tenants_merges_remote_node(self):
        """Two in-process 'nodes' with SEPARATE hubs: the federated view
        served from A includes B's tenants, fetched over the fabric."""
        hub_a, hub_b = _fresh_hub(), _fresh_hub()
        hub_a.windows.record_flow("only-a", 20)
        hub_b.windows.record_flow("only-b", 10)
        hub_b.windows.record_latency("only-b", "ingest", 0.004)
        hub_a.windows.record_flow("shared", 5)
        hub_b.windows.record_flow("shared", 7)
        server = RPCServer()
        host = FakeHost("A")
        view_b = ClusterView("B", FakeHost("B"), hub=hub_b)
        ClusterObsRPCService(view_b).register(server)
        await server.start()
        try:
            host.agent_meta["B"] = {"addr": server.address,
                                    "digest": _peer_digest()}
            view_a = ClusterView("A", host, hub=hub_a,
                                 registry=ServiceRegistry())
            out = await view_a.federated_tenants()
            assert out["nodes"] == {"A": "local", "B": "ok"}
            rows = out["tenants"]
            assert set(rows) == {"only-a", "only-b", "shared"}
            assert rows["shared"]["rate_per_s"] == round(12 / 10.0, 3)
            assert rows["only-b"]["stages"]["ingest"]["count"] == 1
        finally:
            await server.stop()

    async def test_federated_tenants_rescales_mismatched_window(self):
        """A peer on a different BIFROMQ_OBS_WINDOW_S must not inflate
        merged rates: its scalar totals rescale to the coordinator's
        window before the merge."""
        hub_a = _fresh_hub()
        hub_b = ObsHub(window_s=30.0)
        hub_b.enabled = True
        hub_b.windows.record_flow("t", 30)      # 1.0 flow/s over B's 30s
        server = RPCServer()
        view_b = ClusterView("B", FakeHost("B"), hub=hub_b)
        ClusterObsRPCService(view_b).register(server)
        await server.start()
        try:
            host = FakeHost("A")
            host.agent_meta["B"] = {"addr": server.address,
                                    "digest": _peer_digest()}
            view_a = ClusterView("A", host, hub=hub_a,
                                 registry=ServiceRegistry())
            out = await view_a.federated_tenants()
            assert out["nodes"]["B"].startswith("ok (window_s=30")
            # NOT 30/10 = 3.0: B's totals were rescaled, not re-divided
            assert out["tenants"]["t"]["rate_per_s"] == 1.0
        finally:
            await server.stop()

    async def test_federated_tenants_degrades_on_dead_peer(self):
        hub_a = _fresh_hub()
        hub_a.windows.record_flow("local-t", 3)
        host = FakeHost("A")
        host.agent_meta["dead"] = {"addr": "127.0.0.1:1",
                                   "digest": _peer_digest()}
        view_a = ClusterView("A", host, hub=hub_a,
                             registry=ServiceRegistry())
        out = await view_a.federated_tenants(timeout_s=0.5)
        assert out["nodes"]["dead"].startswith("error")
        assert "local-t" in out["tenants"]

    async def test_federated_trace_collects_remote_spans(self):
        trace.TRACER.reset()
        trace.TRACER.sampler.default_rate = 1.0
        try:
            with trace.span("pub.ingest", tenant="t") as root:
                tid = f"{root.ctx.trace_id:016x}"
            server = RPCServer()
            view_b = ClusterView("B", FakeHost("B"), hub=_fresh_hub())
            ClusterObsRPCService(view_b).register(server)
            await server.start()
            try:
                host = FakeHost("A")
                host.agent_meta["B"] = {"addr": server.address,
                                        "digest": _peer_digest()}
                view_a = ClusterView("A", host, hub=_fresh_hub(),
                                     registry=ServiceRegistry())
                out = await view_a.federated_trace(tid)
                assert out["nodes"]["B"] == "ok"
                assert [s["name"] for s in out["spans"]] == ["pub.ingest"]
                # HLC-ordered output (single node here, still sorted)
                hlcs = [s["start_hlc"] for s in out["spans"]]
                assert hlcs == sorted(hlcs)
            finally:
                await server.stop()
        finally:
            trace.TRACER.sampler.default_rate = 0.0
            trace.TRACER.reset()


class TestTenantOverrides:
    def _slo_with_traffic(self, clock):
        slo = TenantSLO(window_s=10.0, clock=clock)
        for tenant in ("a", "b"):
            for _ in range(20):
                slo.record_flow(tenant)
                slo.record_latency(tenant, "ingest", 0.050)
        return slo

    def test_per_tenant_slow_threshold(self):
        from bifromq_tpu.obs.neighbor import NoisyNeighborDetector
        t = [1000.0]
        slo = self._slo_with_traffic(lambda: t[0])
        det = NoisyNeighborDetector(slo, slow_p99_ms=1000.0,
                                    clock=lambda: t[0])
        rows = {r["tenant"]: r for r in det.evaluate(emit=False)}
        assert "slow" not in rows["a"]["flags"]
        det.configure_tenant("a", slow_p99_ms=10.0)
        rows = {r["tenant"]: r for r in det.evaluate(emit=False)}
        assert "slow" in rows["a"]["flags"]
        assert "slow" not in rows["b"]["flags"]
        det.clear_tenant("a")
        rows = {r["tenant"]: r for r in det.evaluate(emit=False)}
        assert "slow" not in rows["a"]["flags"]

    def test_weights_and_threshold_overrides(self):
        from bifromq_tpu.obs.neighbor import NoisyNeighborDetector
        t = [1000.0]
        slo = TenantSLO(window_s=10.0, clock=lambda: t[0])
        # two tenants, one dominating fan-out
        for _ in range(20):
            slo.record_flow("big")
            slo.record_flow("small")
        slo.record_fanout("big", 900)
        slo.record_fanout("small", 100)
        det = NoisyNeighborDetector(slo, noisy_threshold=0.5,
                                    clock=lambda: t[0])
        rows = {r["tenant"]: r for r in det.evaluate(emit=False)}
        assert "noisy" not in rows["big"]["flags"]   # 0.4*0.9 < 0.5
        # weight fan-out fully: big crosses, small does not
        det.w_fanout, det.w_queue_wait, det.w_errors = 1.0, 0.0, 0.0
        rows = {r["tenant"]: r for r in det.evaluate(emit=False)}
        assert "noisy" in rows["big"]["flags"]
        assert "noisy" not in rows["small"]["flags"]
        # per-tenant threshold raise whitelists the by-design fan-out
        det.configure_tenant("big", noisy_threshold=0.95)
        rows = {r["tenant"]: r for r in det.evaluate(emit=False)}
        assert "noisy" not in rows["big"]["flags"]
        assert det.config_snapshot()["tenant_overrides"]["big"] == {
            "noisy_threshold": 0.95}

    def test_unknown_knob_rejected(self):
        from bifromq_tpu.obs.neighbor import NoisyNeighborDetector
        det = NoisyNeighborDetector(TenantSLO())
        with pytest.raises(ValueError):
            det.configure_tenant("t", bogus_knob=1.0)


class TestBatchLinks:
    async def test_batch_emit_links_every_sampled_caller(self):
        """ISSUE 5 satellite (closes the PR-2 follow-up): a batch holding
        several sampled callers records a batch.emit span linking every
        caller beyond the representative parent."""
        from bifromq_tpu.scheduler.batcher import Batcher
        trace.TRACER.reset()
        trace.TRACER.sampler.default_rate = 1.0
        gate = asyncio.Event()

        async def process(calls):
            await gate.wait()
            return list(calls)

        b = Batcher(process, pipeline_depth=1, stage="device")
        roots = []
        try:
            with trace.span("r0", tenant="t"):
                f0 = b.submit("c0")          # occupies the pipeline
            for name in ("r1", "r2", "r3"):
                with trace.span(name, tenant="t") as sp:
                    roots.append(sp.ctx)
                    b.submit(name)
            gate.set()
            await asyncio.wait_for(f0, 5)
            await asyncio.sleep(0.05)        # drain the second batch
            spans = trace.TRACER.export(limit=1000)
            emits = [s for s in spans if s["name"] == "batch.emit"]
            assert emits, [s["name"] for s in spans]
            emit = emits[-1]
            # parented under r1 (the representative), linking r2 + r3
            assert emit["trace_id"] == f"{roots[0].trace_id:016x}"
            linked = {l["trace_id"] for l in emit["links"]}
            assert linked == {f"{roots[1].trace_id:016x}",
                              f"{roots[2].trace_id:016x}"}
        finally:
            trace.TRACER.sampler.default_rate = 0.0
            trace.TRACER.reset()


class TestResourceEnvelope:
    async def test_exporter_stamps_resource_on_every_record(self):
        from bifromq_tpu.obs.exporter import (SCHEMA_VERSION, FileSink,
                                              TelemetryExporter)
        res = {"node_id": "n7", "cluster_id": "c1",
               "schema_version": SCHEMA_VERSION}
        exp = TelemetryExporter(FileSink("/dev/null"), resource=res,
                                snapshot_fn=lambda: {"x": 1})
        exp._collect()
        assert exp._queue, "no record collected"
        assert all(r["resource"] == res for r in exp._queue)
        assert exp.snapshot()["resource"] == res

    async def test_hub_envelope_defaults(self):
        hub = _fresh_hub()
        env = hub.resource_envelope()
        assert env["node_id"] and "schema_version" in env
        hub.set_identity(node_id="node-x", cluster_id="prod")
        env = hub.resource_envelope()
        assert env["node_id"] == "node-x" and env["cluster_id"] == "prod"


class TestClusterObsRPC:
    async def test_digest_method_serves_fresh_digest(self):
        hub = _fresh_hub()
        hub.windows.record_flow("t", 5)
        server = RPCServer()
        view = ClusterView("N", FakeHost("N"), hub=hub,
                           registry=ServiceRegistry())
        ClusterObsRPCService(view).register(server)
        await server.start()
        try:
            reg = ServiceRegistry()
            out = await reg.client_for(server.address).call(
                SERVICE, "digest", b"")
            got = json.loads(out)
            assert got["node"] == "N"
            assert "hlc" in got["digest"]
            await reg.close()
        finally:
            await server.stop()

    async def test_agent_id_constant(self):
        # the gossip agent id is wire surface: peers key on it
        assert AGENT_ID == "obs"


class TestDemotionHysteresis:
    """ISSUE 7 satellite: an endpoint flapping between healthy and
    suspect within the cooldown window stays demoted — the pick tier
    must not oscillate with a sawtoothing health signal."""

    EP = "127.0.0.1:6001"

    def _view(self, clock, hysteresis_s=5.0):
        host = FakeHost("me")
        host.agent_meta["peer"] = {
            "addr": "127.0.0.1:8000",
            "digest": _peer_digest(breakers={self.EP: "open"})}
        view = ClusterView("me", host, hub=_fresh_hub(),
                           hysteresis_s=hysteresis_s, clock=clock)
        return host, view

    def test_flapping_endpoint_stays_demoted_until_cooldown(self):
        t = [1000.0]
        host, view = self._view(lambda: t[0])
        view._recompute()
        assert view.suspect(self.EP)
        # the breaker half-opens: the digest stops naming the endpoint,
        # but inside the cooldown the demotion is sticky
        host.agent_meta["peer"]["digest"] = _peer_digest()
        t[0] += 1.0
        view._recompute()
        assert view.suspect(self.EP)
        # it flaps bad again — the cooldown clock RESTARTS
        host.agent_meta["peer"]["digest"] = _peer_digest(
            breakers={self.EP: "open"})
        t[0] += 1.0
        view._recompute()
        host.agent_meta["peer"]["digest"] = _peer_digest()
        t[0] += 4.0                 # 4s healthy < 5s cooldown
        view._recompute()
        assert view.suspect(self.EP)
        # a FULL cooldown of consecutive health finally clears it
        t[0] += 5.1
        view._recompute()
        assert not view.suspect(self.EP)

    def test_steady_healthy_endpoint_never_demoted(self):
        t = [1000.0]
        host = FakeHost("me")
        host.agent_meta["peer"] = {"addr": "127.0.0.1:8000",
                                   "digest": _peer_digest()}
        view = ClusterView("me", host, hub=_fresh_hub(),
                           hysteresis_s=5.0, clock=lambda: t[0])
        for _ in range(5):
            t[0] += 1.0
            view._recompute()
            assert not view.suspect(self.EP)

    def test_device_breaker_open_demotes_node(self):
        """ISSUE 7: a node gossiping a non-closed DEVICE breaker (it is
        serving, but oracle-degraded) is demoted like a browned-out
        node — peers with a healthy accelerator rank first."""
        t = [1000.0]
        host = FakeHost("me")
        host.agent_meta["worker"] = {
            "addr": "127.0.0.1:9100",
            "digest": _peer_digest(
                device={"dispatch_queue_depth": 0,
                        "batches_in_flight": 0, "compile_count": 0,
                        "mem_peak_bytes": 0, "breaker": "open"})}
        view = ClusterView("me", host, hub=_fresh_hub(),
                           clock=lambda: t[0])
        view._recompute()
        assert view.suspect("127.0.0.1:9100")


class TestTraceGapAnnotation:
    """ISSUE 7 satellite: a wrapped SpanRing must not silently serve a
    partial trace — /cluster/trace/<id> annotates the gap."""

    def _span(self, name, tid, sid, parent, hlc):
        from bifromq_tpu.trace.span import Span
        return Span(name=name, trace_id=tid, span_id=sid,
                    parent_id=parent, tenant="t", service="svc",
                    start_hlc=hlc, end_hlc=hlc + 1, duration_ms=1.0)

    async def test_wrapped_ring_annotates_dropped_spans(self):
        from bifromq_tpu.trace.recorder import SpanRing
        tr = trace.TRACER
        old_ring = tr.ring
        tr.ring = SpanRing(4)
        try:
            tid = 0xABC123
            # an early span of the trace...
            tr.ring.record(self._span("pub.ingest", tid, 0x1, 0, 10))
            # ...rolls off under unrelated traffic...
            for i in range(6):
                tr.ring.record(self._span("noise", 0x999, 0x100 + i, 0,
                                          20 + i))
            # ...before a late child (parented under it) is recorded
            tr.ring.record(self._span("deliver.fanout", tid, 0x2, 0x1, 40))
            view = ClusterView("A", FakeHost("A"), hub=_fresh_hub())
            out = await view.federated_trace(f"{tid:016x}")
            assert [s["name"] for s in out["spans"]] == ["deliver.fanout"]
            assert out["spans_dropped"] == 1
            assert out["complete"] is False
            assert "A" in out["rings_wrapped"]
        finally:
            tr.ring = old_ring

    async def test_old_wrap_does_not_flag_recent_complete_trace(self):
        """The wrap signal is per-trace: a ring that wrapped under OLD
        unrelated traffic must not brand a fully-captured recent trace
        incomplete (the lifetime ``dropped`` counter is monotonic — the
        annotation keys on the wrap horizon instead)."""
        from bifromq_tpu.trace.recorder import SpanRing
        tr = trace.TRACER
        old_ring = tr.ring
        tr.ring = SpanRing(4)
        try:
            # unrelated history rolls the ring over...
            for i in range(8):
                tr.ring.record(self._span("noise", 0x999, 0x100 + i, 0,
                                          10 + i))
            # ...long before a complete parent+child trace is recorded
            tid = 0x5EC0FD
            tr.ring.record(self._span("pub.ingest", tid, 0x1, 0, 100))
            tr.ring.record(self._span("deliver.fanout", tid, 0x2, 0x1,
                                      110))
            view = ClusterView("A", FakeHost("A"), hub=_fresh_hub())
            out = await view.federated_trace(f"{tid:016x}")
            assert out["count"] == 2
            assert out["spans_dropped"] == 0
            assert out["complete"] is True
            assert out["rings_wrapped"] == []
        finally:
            tr.ring = old_ring

    async def test_unwrapped_ring_reports_complete(self):
        from bifromq_tpu.trace.recorder import SpanRing
        tr = trace.TRACER
        old_ring = tr.ring
        tr.ring = SpanRing(16)
        try:
            tid = 0xDEF456
            tr.ring.record(self._span("pub.ingest", tid, 0x1, 0, 10))
            tr.ring.record(self._span("deliver.fanout", tid, 0x2, 0x1, 20))
            view = ClusterView("A", FakeHost("A"), hub=_fresh_hub())
            out = await view.federated_trace(f"{tid:016x}")
            assert out["count"] == 2
            assert out["spans_dropped"] == 0
            assert out["complete"] is True
            assert out["rings_wrapped"] == []
        finally:
            tr.ring = old_ring


class TestDigestDeltaEncoding:
    """ISSUE 8 satellite: a full digest every ``full_every`` ticks,
    deltas (changed top-level fields only, computed vs the last FULL)
    in between; the consumer reconstructs and falls back on a gap."""

    def _view(self, host=None, **kw):
        kw.setdefault("hub", _fresh_hub())
        return ClusterView("me", host or FakeHost("me"),
                           rpc_address="127.0.0.1:7000", api_port=8080,
                           **kw)

    async def test_publisher_alternates_full_and_delta(self):
        host = FakeHost("me")
        view = self._view(host, full_every=3)
        view.refresh()                          # tick 1: full
        meta1 = host.agent_meta["me"]
        assert "digest" in meta1 and "digest_delta" not in meta1
        view.refresh()                          # tick 2: delta
        meta2 = host.agent_meta["me"]
        assert "digest" not in meta2
        assert meta2["base_seq"] == meta1["seq"]
        # a steady node's delta carries only the always-changing HLC
        # stamp (and any genuinely changed section), not the whole digest
        assert "hlc" in meta2["digest_delta"]
        assert set(meta2["digest_delta"]) < set(view.build_digest())
        view.refresh()                          # tick 3
        view.refresh()                          # tick 4: full again
        assert "digest" in host.agent_meta["me"]

    async def test_consumer_applies_delta_onto_cached_full(self):
        host = FakeHost("me")
        view = self._view(host)
        full = _peer_digest(match_cache_hit_rate=0.5)
        host.agent_meta["peer"] = {"addr": "127.0.0.1:6000",
                                   "seq": 7, "digest": full}
        assert view.peers()["peer"]["digest"][
            "match_cache_hit_rate"] == 0.5
        host.agent_meta["peer"] = {
            "addr": "127.0.0.1:6000", "seq": 8, "base_seq": 7,
            "digest_delta": {"hlc": HLC.INST.get(),
                             "match_cache_hit_rate": 0.9}}
        d = view.peers()["peer"]["digest"]
        assert d["match_cache_hit_rate"] == 0.9
        assert d["breakers"] == full["breakers"]    # carried from full
        assert view.digest_deltas_applied == 1
        assert view.digest_gaps == 0

    async def test_gap_applies_delta_best_effort_and_stays_fresh(self):
        """A delta whose base full we never saw (last-writer-wins gossip
        overwrote it before we sampled): the delta's absolute values
        still apply best-effort onto the last view — an alive, gossiping
        peer must not age out as stale because one full was missed — the
        gap is counted, and the next full resyncs exactly."""
        host = FakeHost("me")
        view = self._view(host)
        full = _peer_digest(match_cache_hit_rate=0.5)
        host.agent_meta["peer"] = {"addr": "127.0.0.1:6000",
                                   "seq": 7, "digest": full}
        view.peers()
        fresh_hlc = HLC.INST.get()
        host.agent_meta["peer"] = {
            "addr": "127.0.0.1:6000", "seq": 12, "base_seq": 10,
            "digest_delta": {"hlc": fresh_hlc,
                             "match_cache_hit_rate": 0.9}}
        p = view.peers()["peer"]
        assert p["digest"]["match_cache_hit_rate"] == 0.9
        assert p["digest"]["breakers"] == full["breakers"]
        # freshness advanced: the delta's hlc landed, so digest_age_s
        # reset — the peer does NOT drift toward stale through the gap
        assert p["digest"]["hlc"] == fresh_hlc and p["age_s"] == 0.0
        assert view.digest_gaps >= 1
        # the next full resyncs the chain (deltas chain off it again)
        host.agent_meta["peer"] = {
            "addr": "127.0.0.1:6000", "seq": 13,
            "digest": _peer_digest(match_cache_hit_rate=0.7)}
        assert view.peers()["peer"]["digest"][
            "match_cache_hit_rate"] == 0.7
        host.agent_meta["peer"] = {
            "addr": "127.0.0.1:6000", "seq": 14, "base_seq": 13,
            "digest_delta": {"hlc": HLC.INST.get()}}
        assert view.peers()["peer"]["digest"][
            "match_cache_hit_rate"] == 0.7
        assert view.digest_deltas_applied >= 1

    async def test_delta_roundtrip_over_publish_decode(self):
        """Publisher and consumer compose: a second view decoding the
        publisher's own metadata sees the same digest the publisher
        built, across full AND delta ticks."""
        host = FakeHost("me")
        view = self._view(host, full_every=4)
        consumer = ClusterView("other", host, hub=_fresh_hub())
        for _ in range(5):
            view.refresh()
            got = consumer.peers()["me"]["digest"]
            assert got.get("v") == 1
            assert "device" in got and "breakers" in got

    async def test_legacy_full_only_meta_still_decodes(self):
        host = FakeHost("me")
        view = self._view(host)
        host.agent_meta["old"] = {"addr": "127.0.0.1:6000",
                                  "digest": _peer_digest()}
        assert view.peers()["old"]["digest"]["v"] == 1


class TestWeightedDemotion:
    """ISSUE 8 satellite: per-signal scores accumulate per endpoint and
    demote at the threshold — two sub-threshold signals combine where
    either alone would not; every legacy single-signal verdict holds."""

    def _view(self, host, **kw):
        t0 = time.time()
        now = [t0]
        kw.setdefault("hub", _fresh_hub())
        view = ClusterView("me", host, clock=lambda: now[0],
                           queue_depth_threshold=1000,
                           hysteresis_s=5.0, **kw)
        return view, now

    def _meta(self, addr, *, breakers=None, depth=0, device_breaker=None):
        dev = {"dispatch_queue_depth": depth, "batches_in_flight": 0,
               "compile_count": 0, "mem_peak_bytes": 0}
        if device_breaker:
            dev["breaker"] = device_breaker
        return {"addr": addr,
                "digest": _peer_digest(breakers=breakers or {},
                                       device=dev)}

    async def test_single_full_signals_still_demote(self):
        host = FakeHost("me")
        host.agent_meta["p1"] = self._meta(
            "127.0.0.1:1", breakers={"127.0.0.1:9": "open"})
        host.agent_meta["p2"] = self._meta("127.0.0.1:2", depth=1000)
        host.agent_meta["p3"] = self._meta("127.0.0.1:3",
                                           device_breaker="half_open")
        view, _ = self._view(host)
        view._recompute()
        assert view.suspect("127.0.0.1:9")      # peer breaker open
        assert view.suspect("127.0.0.1:2")      # queue at threshold
        assert view.suspect("127.0.0.1:3")      # device breaker

    async def test_subthreshold_signals_alone_do_not_demote(self):
        host = FakeHost("me")
        # queue at 60% of brown-out depth: score 0.6 < 1.0
        host.agent_meta["p1"] = self._meta("127.0.0.1:2", depth=600)
        # a half-open PEER breaker alone: 0.5 < 1.0
        host.agent_meta["p2"] = self._meta(
            "127.0.0.1:1", breakers={"127.0.0.1:9": "half_open"})
        view, _ = self._view(host)
        view._recompute()
        assert not view.suspect("127.0.0.1:2")
        assert not view.suspect("127.0.0.1:9")
        assert view.demotion_scores["127.0.0.1:2"] == 0.6
        assert view.demotion_scores["127.0.0.1:9"] == 0.5

    async def test_combined_subthreshold_signals_demote(self):
        host = FakeHost("me")
        # the same endpoint accumulates: half-open peer breaker (0.5)
        # + 60%-deep queue (0.6) = 1.1 ≥ 1.0
        host.agent_meta["p1"] = self._meta(
            "127.0.0.1:1", breakers={"127.0.0.1:2": "half_open"})
        host.agent_meta["p2"] = self._meta("127.0.0.1:2", depth=600)
        view, _ = self._view(host)
        view._recompute()
        assert view.demotion_scores["127.0.0.1:2"] == 1.1
        assert view.suspect("127.0.0.1:2")

    async def test_weights_configurable(self):
        host = FakeHost("me")
        host.agent_meta["p1"] = self._meta(
            "127.0.0.1:1", breakers={"127.0.0.1:9": "open"})
        view, _ = self._view(
            host, demotion_weights={"peer_breaker_open": 0.4})
        view._recompute()
        assert not view.suspect("127.0.0.1:9")  # 0.4 < threshold 1.0

    async def test_queue_score_saturates_at_2x(self):
        host = FakeHost("me")
        host.agent_meta["p1"] = self._meta("127.0.0.1:2", depth=10**9)
        view, _ = self._view(host)
        view._recompute()
        assert view.demotion_scores["127.0.0.1:2"] == 2.0

    async def test_hysteresis_with_fake_clock(self):
        """Weighted demotion composes with the ISSUE 7 hysteresis: the
        endpoint stays demoted a full cooldown after its last bad
        observation, then clears."""
        host = FakeHost("me")
        host.agent_meta["p1"] = self._meta("127.0.0.1:2", depth=1000)
        view, now = self._view(host)
        view._recompute()
        assert view.suspect("127.0.0.1:2")
        # signal clears, but the cooldown holds the demotion
        host.agent_meta["p1"] = self._meta("127.0.0.1:2", depth=0)
        now[0] += 2.0
        view._recompute()
        assert view.suspect("127.0.0.1:2")
        now[0] += 10.0                          # past hysteresis_s=5
        view._recompute()
        assert not view.suspect("127.0.0.1:2")


class TestClusterCapacity:
    async def test_digest_carries_capacity_field(self):
        from bifromq_tpu.models.matcher import TpuMatcher
        from bifromq_tpu.models.oracle import Route
        from bifromq_tpu.types import RouteMatcher
        hub = _fresh_hub()
        m = TpuMatcher(auto_compact=False)
        m.add_route("T", Route(
            matcher=RouteMatcher.from_topic_filter("cap/x"),
            broker_id=0, receiver_id="r", deliverer_key="d"))
        m.refresh()
        hub.device.register_matcher(m)
        view = ClusterView("me", FakeHost("me"), hub=hub)
        digest = view.build_digest()
        assert digest["capacity"]["table_bytes"] > 0

    async def test_capacity_table_federates_from_digests(self):
        host = FakeHost("me")
        host.agent_meta["peer"] = {
            "addr": "127.0.0.1:6000",
            "digest": _peer_digest(
                capacity={"table_bytes": 12345,
                          "mem_peak_bytes": 777})}
        view = ClusterView("me", host, hub=_fresh_hub())
        table = view.capacity_table()
        assert table["nodes"]["me"]["self"] is True
        peer_row = table["nodes"]["peer"]
        assert peer_row["capacity"]["table_bytes"] == 12345
        assert not peer_row["stale"]
        local_tb = table["nodes"]["me"]["capacity"]["table_bytes"]
        assert table["total_table_bytes"] == local_tb + 12345
        assert table["max_mem_peak_bytes"] >= 777

    async def test_logical_subs_rollup_dedups_by_fingerprint(self):
        """ISSUE 9 satellite (PR 8 follow-up): physical table bytes sum
        per node (that's what HBM holds), but LOGICAL subscriptions dedup
        by the gossiped subscription-set fingerprint — two replicas of
        one route table count once; a disjoint shard counts on top."""
        host = FakeHost("me")
        host.agent_meta["rep1"] = {
            "addr": "127.0.0.1:6001",
            "digest": _peer_digest(capacity={
                "table_bytes": 100, "logical_subs": 40,
                "subs_fp": "aaaa"})}
        host.agent_meta["rep2"] = {
            "addr": "127.0.0.1:6002",
            "digest": _peer_digest(capacity={
                "table_bytes": 100, "logical_subs": 40,
                "subs_fp": "aaaa"})}
        host.agent_meta["shardx"] = {
            "addr": "127.0.0.1:6003",
            "digest": _peer_digest(capacity={
                "table_bytes": 50, "logical_subs": 7,
                "subs_fp": "bbbb"})}
        view = ClusterView("me", host, hub=_fresh_hub())
        table = view.capacity_table()
        ls = table["logical_subs"]
        assert ls["sum"] == 40 + 40 + 7          # naive per-node census
        assert ls["dedup"] == 40 + 7             # replicas counted once
        # physical bytes stay per-node (replicas DO occupy HBM twice)
        assert table["total_table_bytes"] >= 100 + 100 + 50

    async def test_local_digest_reports_logical_subs(self):
        from bifromq_tpu.models.matcher import TpuMatcher
        from bifromq_tpu.models.oracle import Route
        from bifromq_tpu.types import RouteMatcher
        hub = _fresh_hub()
        m = TpuMatcher(auto_compact=False)
        for i in range(3):
            m.add_route("T", Route(
                matcher=RouteMatcher.from_topic_filter(f"cap/{i}"),
                broker_id=0, receiver_id=f"r{i}", deliverer_key="d"))
        m.refresh()
        hub.device.register_matcher(m)
        from bifromq_tpu.obs.capacity import digest_capacity
        cap = digest_capacity(hub)
        assert cap["logical_subs"] == 3
        assert len(cap["subs_fp"]) == 16
        # the fingerprint tracks the census: a removal changes it
        fp0 = cap["subs_fp"]
        m.remove_route("T", RouteMatcher.from_topic_filter("cap/0"),
                       (0, "r0", "d"))
        assert digest_capacity(hub)["subs_fp"] != fp0

    async def test_stale_peer_excluded_from_totals(self):
        t0 = time.time()
        now = [t0]
        host = FakeHost("me")
        host.agent_meta["peer"] = {
            "addr": "127.0.0.1:6000",
            "digest": _peer_digest(capacity={"table_bytes": 999})}
        view = ClusterView("me", host, hub=_fresh_hub(),
                           stale_after_s=5.0, clock=lambda: now[0])
        view.peers()
        now[0] = t0 + 60.0
        table = view.capacity_table()
        assert table["nodes"]["peer"]["stale"]
        assert table["total_table_bytes"] == \
            table["nodes"]["me"]["capacity"]["table_bytes"]

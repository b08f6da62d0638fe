"""API server + metrics integration tests (≈ bifromq-apiserver handler
tests): a real broker + real HTTP over loopback."""

import asyncio
import json

import pytest

from bifromq_tpu.apiserver import APIServer
from bifromq_tpu.mqtt.broker import MQTTBroker
from bifromq_tpu.mqtt.client import MQTTClient
from bifromq_tpu.plugin.events import CollectingEventCollector
from bifromq_tpu.utils.metrics import (MeteringEventCollector, MetricsRegistry,
                                       TenantMetric)

pytestmark = pytest.mark.asyncio


async def http(port, method, path, body=b""):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(
        f"{method} {path} HTTP/1.1\r\nhost: x\r\n"
        f"content-length: {len(body)}\r\nconnection: close\r\n\r\n".encode()
        + body)
    await writer.drain()
    raw = await reader.read(65536)
    writer.close()
    head, _, payload = raw.partition(b"\r\n\r\n")
    status = int(head.split(b" ")[1])
    return status, json.loads(payload)


@pytest.fixture
async def stack():
    registry = MetricsRegistry()
    events = MeteringEventCollector(registry, CollectingEventCollector())
    broker = MQTTBroker(port=0, events=events)
    await broker.start()
    api = APIServer(broker, port=0, metrics=registry)
    await api.start()
    yield broker, api, registry
    await api.stop()
    broker.inbox.close()
    await broker.stop()


class TestAPI:
    async def test_pub_reaches_subscriber(self, stack):
        broker, api, _ = stack
        sub = MQTTClient(port=broker.port, client_id="s1")
        await sub.connect()
        await sub.subscribe("api/t")
        status, out = await http(api.port, "PUT",
                                 "/pub?tenant_id=DevOnly&topic=api/t&qos=1",
                                 b"hello-from-http")
        assert status == 200 and out["fanout"] == 1
        msg = await sub.recv()
        assert msg.payload == b"hello-from-http"
        await sub.disconnect()

    async def test_pub_invalid_topic(self, stack):
        _, api, _ = stack
        status, out = await http(api.port, "PUT", "/pub?topic=bad/%2B/x")
        # '+' decoded into the topic -> invalid
        assert status == 400

    async def test_kill(self, stack):
        broker, api, _ = stack
        c = MQTTClient(port=broker.port, client_id="victim")
        await c.connect()
        status, out = await http(api.port, "DELETE",
                                 "/kill?tenant_id=DevOnly&client_id=victim")
        assert status == 200
        await asyncio.wait_for(c.closed.wait(), 5)
        status, _ = await http(api.port, "DELETE",
                               "/kill?tenant_id=DevOnly&client_id=victim")
        assert status == 404

    async def test_sub_unsub_on_behalf(self, stack):
        broker, api, _ = stack
        # persistent session exists offline
        c = MQTTClient(port=broker.port, client_id="dev9", clean_start=False)
        await c.connect()
        await c.disconnect()
        status, out = await http(
            api.port, "PUT",
            "/sub?tenant_id=DevOnly&client_id=dev9&topic_filter=a/%23&qos=1")
        assert status == 200 and out["result"] == "ok"
        # publish lands in the inbox even though the client is offline
        await http(api.port, "PUT", "/pub?topic=a/b&qos=1", b"queued")
        f = broker.inbox.store.fetch("DevOnly", "dev9")
        assert len(f.buffer) == 1
        status, out = await http(
            api.port, "DELETE",
            "/unsub?tenant_id=DevOnly&client_id=dev9&topic_filter=a/%23")
        assert status == 200 and out["removed"]

    async def test_sub_on_behalf_live_session(self, stack):
        """A LIVE (transient) session gets the on-behalf subscription
        through its own session object (≈ SessionDictService.sub): messages
        flow to the connected client immediately, and /inbox-state exposes
        the live subscription set."""
        broker, api, _ = stack
        c = MQTTClient(port=broker.port, client_id="live1")
        await c.connect()
        status, out = await http(
            api.port, "PUT",
            "/sub?tenant_id=DevOnly&client_id=live1"
            "&topic_filter=lv/%23&qos=1")
        assert status == 200 and out["result"] == "ok" and out["live"]
        # the live session now receives matching traffic
        status, _ = await http(api.port, "PUT", "/pub?topic=lv/x&qos=1",
                               b"to-live")
        assert status == 200
        msg = await c.recv()
        assert msg.payload == b"to-live"
        # duplicate sub with same qos reports exists
        status, out = await http(
            api.port, "PUT",
            "/sub?tenant_id=DevOnly&client_id=live1"
            "&topic_filter=lv/%23&qos=1")
        assert status == 200 and out["result"] == "exists"
        # inbox-state surfaces the subscription
        status, state = await http(
            api.port, "GET",
            "/inbox-state?tenant_id=DevOnly&client_id=live1")
        assert status == 200
        assert state["subscriptions"]["lv/#"]["qos"] == 1
        # unsub on behalf detaches it
        status, out = await http(
            api.port, "DELETE",
            "/unsub?tenant_id=DevOnly&client_id=live1&topic_filter=lv/%23")
        assert status == 200 and out["result"] == "ok" and out["live"]
        status, _ = await http(
            api.port, "DELETE",
            "/unsub?tenant_id=DevOnly&client_id=live1&topic_filter=lv/%23")
        assert status == 404
        await c.disconnect()
        status, _ = await http(
            api.port, "GET",
            "/inbox-state?tenant_id=DevOnly&client_id=live1")
        assert status == 404

    async def test_session_expire_and_listing(self, stack):
        broker, api, _ = stack
        c = MQTTClient(port=broker.port, client_id="listme",
                       clean_start=False)
        await c.connect()
        status, out = await http(api.port, "GET",
                                 "/sessions?tenant_id=DevOnly")
        assert "listme" in out["online"] and "listme" in out["persistent"]
        await c.disconnect()
        status, out = await http(
            api.port, "DELETE",
            "/session?tenant_id=DevOnly&client_id=listme")
        assert status == 200 and out["deleted"]

    async def test_retain_and_listing(self, stack):
        broker, api, _ = stack
        status, out = await http(api.port, "PUT",
                                 "/retain?tenant_id=DevOnly&topic=r/t",
                                 b"val")
        assert status == 200 and out["retained"]
        status, out = await http(api.port, "GET",
                                 "/retained?tenant_id=DevOnly")
        assert out["topics"] == ["r/t"]
        # empty body clears
        await http(api.port, "PUT", "/retain?tenant_id=DevOnly&topic=r/t")
        status, out = await http(api.port, "GET",
                                 "/retained?tenant_id=DevOnly")
        assert out["count"] == 0

    async def test_routes_listing(self, stack):
        broker, api, _ = stack
        c = MQTTClient(port=broker.port, client_id="router")
        await c.connect()
        await c.subscribe("x/+")
        status, out = await http(api.port, "GET", "/routes?tenant_id=DevOnly")
        assert out["count"] == 1 and out["routes"][0]["filter"] == "x/+"
        await c.disconnect()

    async def test_metrics_endpoint(self, stack):
        broker, api, registry = stack
        c = MQTTClient(port=broker.port, client_id="m1")
        await c.connect()
        await c.subscribe("mt/t")
        await c.publish("mt/t", b"x", qos=1)
        await c.recv()
        await c.disconnect()
        status, out = await http(api.port, "GET", "/metrics")
        t = out["tenants"]["DevOnly"]
        assert t["connect_count"] >= 1
        assert t["pub_received"] >= 1
        assert t["delivered"] >= 1
        assert registry.get("DevOnly", TenantMetric.PUB_RECEIVED) >= 1

    async def test_metrics_build_info_graftcheck(self, stack):
        # ISSUE 10: /metrics stamps the analyzer's checked-in last-run
        # state (rule count, suppression count, hash) so drift between
        # nodes is visible on a live scrape
        _, api, _ = stack
        status, out = await http(api.port, "GET", "/metrics")
        assert status == 200
        g = out["build_info"]["graftcheck"]
        assert g["stamp"] == "ok"
        # served VERBATIM from the checked-in stamp — compare against
        # the file, not literal counts, so a legitimate rule-set change
        # plus --write-stamp doesn't break an unrelated HTTP test
        import json as _json
        from bifromq_tpu.analysis import STAMP_PATH
        with open(STAMP_PATH, encoding="utf-8") as f:
            stamp = _json.load(f)
        for k in ("rules", "suppressions", "unsuppressed", "hash"):
            assert g[k] == stamp[k]
        assert len(g["hash"]) == 16

    async def test_replication_endpoint(self, stack):
        # ISSUE 12: per-range stream heads + replication counters; the
        # broker's local dist-worker hosts at least one range's DeltaLog
        broker, api, _ = stack
        c = MQTTClient(port=broker.port, client_id="repl1")
        await c.connect()
        await c.subscribe("repl/t")     # one route mutation → one record
        status, out = await http(api.port, "GET", "/replication")
        assert status == 200
        assert "counters" in out and "hubs" in out
        hubs = out["hubs"]
        assert hubs and any(h["ranges"] for h in hubs)
        rng = next(h["ranges"][0] for h in hubs if h["ranges"])
        assert {"range", "epoch", "head_seq"} <= set(rng)
        status, metrics = await http(api.port, "GET", "/metrics")
        assert "replication" in metrics
        assert metrics["replication"]["records"] >= 1
        await c.disconnect()

    async def test_unknown_route(self, stack):
        _, api, _ = stack
        status, _ = await http(api.port, "GET", "/nope")
        assert status == 404

    async def test_cluster_standalone(self, stack):
        _, api, _ = stack
        status, out = await http(api.port, "GET", "/cluster")
        assert out["mode"] == "standalone"

    async def test_bad_qos_param_returns_400(self, stack):
        _, api, _ = stack
        status, out = await http(api.port, "PUT",
                                 "/pub?topic=t&qos=abc", b"x")
        assert status == 400
        status, out = await http(api.port, "PUT", "/pub?topic=t&qos=7", b"x")
        assert status == 400


class TestAdminEndpoints:
    """Balancer enable/disable/state + traffic directives (≈ the reference
    apiserver's balancer and traffic-rules handler families)."""

    async def test_balancer_state_and_toggle(self):
        # elasticity knobs configured → dist, inbox AND retain stores run
        # balance controllers the admin API can inspect and toggle
        broker = MQTTBroker(port=0,
                            dist_worker_kwargs={"split_threshold": 100},
                            inbox_split_threshold=500,
                            retain_split_threshold=500)
        await broker.start()
        api = APIServer(broker, port=0)
        await api.start()
        try:
            status, state = await http(api.port, "GET", "/balancer")
            assert status == 200
            assert set(state) == {"dist", "inbox", "retain"}
            assert state["dist"]["enabled"] is True
            assert "RangeSplitBalancer" in state["dist"]["balancers"]
            assert state["inbox"]["enabled"] and state["retain"]["enabled"]

            status, out = await http(api.port, "PUT",
                                     "/balancer?enable=false")
            assert status == 200 and "dist" in out["stores"]
            ctl = broker.dist.worker.balance_controller
            assert ctl.enabled is False
            assert await ctl.run_once() == 0   # disabled loop is a no-op
            status, state = await http(api.port, "GET", "/balancer")
            assert state["dist"]["enabled"] is False
            await http(api.port, "PUT", "/balancer?enable=true")
            assert ctl.enabled is True

            status, _ = await http(api.port, "PUT",
                                   "/balancer?enable=false&store=nope")
            assert status == 404
        finally:
            await api.stop()
            broker.inbox.close()
            await broker.stop()

    async def test_traffic_endpoints_standalone_404(self, stack):
        _, api, _ = stack
        status, _ = await http(api.port, "GET", "/traffic")
        assert status == 404

    async def test_traffic_set_get_unset_with_registry(self):
        from bifromq_tpu.rpc.fabric import ServiceRegistry
        broker = MQTTBroker(port=0)
        await broker.start()
        reg = ServiceRegistry()
        api = APIServer(broker, port=0, registry=reg)
        await api.start()
        try:
            body = json.dumps({"groupA": 2, "groupB": 1}).encode()
            status, _ = await http(
                api.port, "PUT", "/traffic?service=dist&tenant_prefix=acme",
                body)
            assert status == 200
            status, rules = await http(api.port, "GET", "/traffic")
            assert status == 200
            assert rules == {"dist": {"acme": {"groupA": 2, "groupB": 1}}}
            status, _ = await http(
                api.port, "DELETE",
                "/traffic?service=dist&tenant_prefix=acme")
            assert status == 200
            _, rules = await http(api.port, "GET", "/traffic")
            assert rules == {"dist": {}}
        finally:
            await api.stop()
            broker.inbox.close()
            await broker.stop()


class TestTraceEndpoints:
    """Flight-recorder surface (ISSUE 2): /trace, /trace/slow, and the
    runtime sampling knobs, plus stage histograms in /metrics."""

    async def test_trace_knobs_and_span_export(self, stack):
        from bifromq_tpu import trace

        broker, api, _ = stack
        trace.TRACER.reset()
        try:
            # arm sampling at runtime through the API
            status, out = await http(api.port, "PUT", "/trace?rate=1.0")
            assert status == 200
            assert out["sampling"]["default_rate"] == 1.0

            sub = MQTTClient(port=broker.port, client_id="tr1")
            await sub.connect()
            await sub.subscribe("trc/t")
            status, out = await http(
                api.port, "PUT", "/pub?tenant_id=DevOnly&topic=trc/t&qos=1",
                b"x")
            assert status == 200 and out["fanout"] == 1
            await sub.recv()
            await sub.disconnect()

            status, out = await http(api.port, "GET",
                                     "/trace?tenant_id=DevOnly&limit=100")
            assert status == 200
            names = {s["name"] for s in out["spans"]}
            assert {"match.device", "deliver.fanout"} <= names, names
            # filter by trace id round-trips
            tid = out["spans"][0]["trace_id"]
            status, one = await http(api.port, "GET",
                                     f"/trace?trace_id={tid}")
            assert status == 200
            assert all(s["trace_id"] == tid for s in one["spans"])

            # slow ring via knob: everything beyond 0.0001ms is "slow"
            status, _ = await http(api.port, "PUT", "/trace?slow_ms=0.0001")
            assert status == 200
            status, out = await http(
                api.port, "PUT", "/pub?tenant_id=DevOnly&topic=trc/t&qos=0",
                b"y")
            assert status == 200
            status, slow = await http(api.port, "GET", "/trace/slow")
            assert status == 200 and slow["count"] >= 1
            # disarm
            status, out = await http(api.port, "PUT",
                                     "/trace?rate=0&slow_ms=0")
            assert status == 200
            assert out["sampling"]["default_rate"] == 0.0
            assert out["slow_ms"] is None
        finally:
            trace.TRACER.sampler.default_rate = 0.0
            trace.TRACER.slow_ms = None
            trace.TRACER.reset()

    async def test_metrics_stage_breakdown(self, stack):
        broker, api, _ = stack
        sub = MQTTClient(port=broker.port, client_id="st1")
        await sub.connect()
        await sub.subscribe("stg/t")
        status, _ = await http(api.port, "PUT",
                               "/pub?tenant_id=DevOnly&topic=stg/t&qos=1",
                               b"z")
        assert status == 200
        await sub.recv()
        await sub.disconnect()
        status, snap = await http(api.port, "GET", "/metrics")
        assert status == 200
        stages = snap["stages"]
        for stage in ("queue_wait", "device", "deliver"):
            assert stages.get(stage, {}).get("count", 0) >= 1, stages
            assert "p50_ms" in stages[stage] and "p99_ms" in stages[stage]


async def http_with_headers(port, method, path, body=b""):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(
        f"{method} {path} HTTP/1.1\r\nhost: x\r\n"
        f"content-length: {len(body)}\r\nconnection: close\r\n\r\n".encode()
        + body)
    await writer.drain()
    raw = await reader.read(262144)
    writer.close()
    head, _, payload = raw.partition(b"\r\n\r\n")
    lines = head.decode().split("\r\n")
    status = int(lines[0].split(" ")[1])
    headers = {}
    for ln in lines[1:]:
        k, _, v = ln.partition(":")
        headers[k.strip().lower()] = v.strip()
    return status, json.loads(payload), headers


class TestCapacityProfileAPI:
    """ISSUE 8: the capacity & continuous-profiling plane end to end
    over real HTTP."""

    async def test_capacity_reports_parity_and_planner(self, stack):
        broker, api, _ = stack
        sub = MQTTClient(port=broker.port, client_id="cap1")
        await sub.connect()
        await sub.subscribe("cap/+")
        # a publish forces a match → an installed base to account
        status, _ = await http(api.port, "PUT",
                               "/pub?tenant_id=DevOnly&topic=cap/x",
                               b"x")
        assert status == 200
        status, out = await http(api.port, "GET", "/capacity")
        assert status == 200
        assert out["table_bytes"] > 0
        # acceptance: planner-vs-live parity within 10% on CPU
        assert out["parity_error"] < 0.10
        assert any(r.get("installed") for r in out["matchers"])
        await sub.disconnect()

    async def test_capacity_fits_verdict_without_dispatch(self, stack):
        _, api, _ = stack
        status, out = await http(api.port, "GET",
                                 "/capacity?n_subs=1000000")
        assert status == 200
        # judged from the model alone (nothing was built or dispatched)
        assert out["fits"]["tables"]["total"] > 100 << 20
        assert out["fits"]["mesh"]["shards"] == 1
        status, out = await http(api.port, "GET",
                                 "/capacity?n_subs=1000&shards=4")
        assert out["fits"]["mesh"]["shards"] == 4

    async def test_profile_serves_split_and_ledger(self, stack):
        broker, api, _ = stack
        sub = MQTTClient(port=broker.port, client_id="prof1")
        await sub.connect()
        await sub.subscribe("prof/+")
        await http(api.port, "PUT",
                   "/pub?tenant_id=DevOnly&topic=prof/x", b"x")
        status, out = await http(api.port, "GET", "/profile")
        assert status == 200
        assert out["batches"] >= 1
        assert "dispatch_ms_p50" in out["split"]
        assert "ready_ms_p50" in out["split"]
        assert out["compile_ledger"]["total"] >= 1
        ev = out["compile_ledger"]["events"][-1]
        assert {"reason", "compile_s", "salt", "table_bytes",
                "n_nodes"} <= set(ev)
        await sub.disconnect()

    async def test_cluster_capacity_standalone(self, stack):
        _, api, _ = stack
        status, out = await http(api.port, "GET", "/cluster/capacity")
        assert status == 200
        assert len(out["nodes"]) == 1
        (row,) = out["nodes"].values()
        assert row["self"] is True and row["stale"] is False

    async def test_cluster_tenants_cached_with_max_age_header(self, stack):
        broker, api, _ = stack
        status, out1, hdr = await http_with_headers(
            api.port, "GET", "/cluster/tenants")
        assert status == 200
        assert hdr["cache-control"].startswith("max-age=")
        assert float(hdr["age"]) == 0.0
        assert out1["cache"]["age_s"] == 0.0
        # second hit inside the TTL serves the cache (age advances)
        status, out2, hdr2 = await http_with_headers(
            api.port, "GET", "/cluster/tenants")
        assert out2["cache"]["age_s"] >= 0.0
        assert out2["tenants"] == out1["tenants"]
        # ?max_age_s=0 forces a refresh
        status, out3, hdr3 = await http_with_headers(
            api.port, "GET", "/cluster/tenants?max_age_s=0")
        assert out3["cache"]["age_s"] == 0.0

    async def test_cluster_tenants_top_k_filters_cached_rows(self, stack):
        broker, api, registry = stack
        from bifromq_tpu.obs import OBS
        OBS.record_flow("hot", 50)
        OBS.record_flow("warm", 5)
        status, out, _ = await http_with_headers(
            api.port, "GET", "/cluster/tenants?max_age_s=0")
        n_all = len(out["tenants"])
        if n_all >= 2:
            status, out1, _ = await http_with_headers(
                api.port, "GET", "/cluster/tenants?top_k=1")
            assert len(out1["tenants"]) == 1


class TestDeltaPlaneEndpoints:
    """ISSUE 18 surfaces: the lag plane, the migration ladder and the
    autoscaler decision ring over real HTTP."""

    async def test_replication_lag_endpoint(self, stack):
        from bifromq_tpu.obs.lag import LAG, REPL_EVENTS
        _, api, _ = stack
        LAG.reset()
        REPL_EVENTS.reset()
        try:
            LAG.observe("n0", "r0", 0.25)
            LAG.note_gap("n0", "r0")
            status, out = await http(api.port, "GET", "/replication/lag")
            assert status == 200
            assert out["stale"] == 0
            (s,) = out["streams"]
            assert s["origin"] == "n0" and s["range"] == "r0"
            assert s["lag_s"] == 0.25 and s["gaps"] == 1
            kinds = [e["kind"] for e in out["events"]]
            assert "gap" in kinds
            status, out = await http(api.port, "GET",
                                     "/replication/lag?events=0")
            assert status == 200 and out["events"] == []
        finally:
            LAG.reset()
            REPL_EVENTS.reset()

    async def test_mesh_migrations_404_on_single_chip(self, stack):
        _, api, _ = stack
        status, _ = await http(api.port, "GET", "/mesh/migrations")
        assert status == 404

    async def test_mesh_autoscaler_404_without_scaler(self, stack):
        _, api, _ = stack
        status, _ = await http(api.port, "GET", "/mesh/autoscaler")
        assert status == 404

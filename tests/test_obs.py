"""Tenant SLO observability tests (ISSUE 3): windowed RED decay under a
fake clock, metering-collector + gauge coverage, noisy-neighbor ranking,
the throttler advisory, push telemetry export (bounded queue, retries,
drop counters), slow-trace child capture, and the /tenants + /metrics
API surface end-to-end through a real broker."""

import asyncio
import json
import os

import pytest

from bifromq_tpu import trace
from bifromq_tpu.obs import (OBS, FileSink, NoisyNeighborDetector,
                             TelemetryExporter, TenantSLO, WindowedCounter,
                             WindowedLog2Histogram)
from bifromq_tpu.plugin.events import (CollectingEventCollector, Event,
                                       EventType)
from bifromq_tpu.plugin.throttler import (SLOAdvisedResourceThrottler,
                                          TenantResourceType)
from bifromq_tpu.utils.metrics import (MeteringEventCollector,
                                       MetricsRegistry, TenantMetric)


@pytest.fixture(autouse=True)
def _clean_obs():
    OBS.reset()
    OBS.enabled = True
    yield
    OBS.reset()
    OBS.enabled = True
    OBS.detector.events = None


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# windowed primitives: decay determinism under a fake clock
# ---------------------------------------------------------------------------

class TestWindowed:
    def test_histogram_decays_deterministically(self):
        clk = FakeClock()
        h = WindowedLog2Histogram(window_s=10.0, n_slices=5, clock=clk)
        h.record(0.001)
        h.record(0.004)
        assert h.count == 2
        # still inside the window
        clk.t = 9.9
        assert h.count == 2
        # the recording slice (epoch 0, [0,2)) expires once the window
        # slides past it: at t=12.1 live epochs are 2..6
        clk.t = 12.1
        assert h.count == 0
        # records land in the CURRENT slice after decay
        h.record(0.002)
        assert h.count == 1
        clk.t = 30.0
        assert h.count == 0

    def test_histogram_partial_decay_is_slice_granular(self):
        clk = FakeClock()
        h = WindowedLog2Histogram(window_s=10.0, n_slices=5, clock=clk)
        h.record(0.001)            # slice epoch 0
        clk.t = 4.0
        h.record(0.001)            # slice epoch 2
        clk.t = 11.0               # live epochs 1..5: first record expired
        assert h.count == 1
        clk.t = 15.0               # live epochs 3..7: second gone too
        assert h.count == 0

    def test_histogram_percentiles_merge_slices(self):
        clk = FakeClock()
        h = WindowedLog2Histogram(window_s=10.0, n_slices=5, clock=clk)
        for _ in range(95):
            h.record(0.001)        # ~1ms
        clk.t = 4.0
        for _ in range(5):
            h.record(1.0)          # 1s outliers in a later slice
        snap = h.snapshot()
        assert snap["count"] == 100
        assert snap["p50_ms"] <= 2.1
        assert snap["p99_ms"] >= 500.0

    def test_counter_rate_and_reuse_of_slots(self):
        clk = FakeClock()
        c = WindowedCounter(window_s=10.0, n_slices=5, clock=clk)
        c.add(5.0)
        assert c.total() == 5.0
        assert c.rate() == 0.5
        # wrap far enough that the same slot index is reused for a new
        # epoch: the old value must be zeroed, not accumulated
        clk.t = 20.0               # epoch 10 ≡ slot 0 again
        c.add(1.0)
        assert c.total() == 1.0

    def test_same_verdict_regardless_of_observation_order(self):
        # decay is a pure function of the clock: observing (or not
        # observing) intermediate states must not change the outcome
        clk1, clk2 = FakeClock(), FakeClock()
        a = WindowedCounter(window_s=10.0, n_slices=5, clock=clk1)
        b = WindowedCounter(window_s=10.0, n_slices=5, clock=clk2)
        a.add(3.0)
        b.add(3.0)
        for t in (3.0, 6.0, 9.0, 11.5):
            clk1.t = t
            a.total()              # poke a at every step
        clk2.t = 11.5              # b jumps straight there
        assert a.total() == b.total()


# ---------------------------------------------------------------------------
# metering collector + registry gauges (ISSUE 3 satellite: untested before)
# ---------------------------------------------------------------------------

class TestMeteringEventCollector:
    def test_meters_and_forwards_downstream(self):
        reg = MetricsRegistry()
        tail = CollectingEventCollector()
        col = MeteringEventCollector(reg, tail)
        col.report(Event(EventType.PUB_RECEIVED, "acme", {"topic": "t"}))
        col.report(Event(EventType.DELIVERED, "acme", {}))
        col.report(Event(EventType.DELIVER_ERROR, "acme", {}))
        # unmapped event types pass through without metering
        col.report(Event(EventType.PING_REQ, "acme", {}))
        assert reg.get("acme", TenantMetric.PUB_RECEIVED) == 1
        assert reg.get("acme", TenantMetric.DELIVERED) == 1
        assert reg.get("acme", TenantMetric.DELIVER_ERRORS) == 1
        assert len(tail.events) == 4

    def test_blank_tenant_buckets_under_dash(self):
        reg = MetricsRegistry()
        col = MeteringEventCollector(reg)
        col.report(Event(EventType.PUB_RECEIVED, "", {}))
        assert reg.get("-", TenantMetric.PUB_RECEIVED) == 1

    def test_feeds_slo_windows_and_errors(self):
        reg = MetricsRegistry()
        col = MeteringEventCollector(reg)
        for _ in range(10):
            col.report(Event(EventType.PUB_RECEIVED, "acme", {}))
        col.report(Event(EventType.QOS0_DROPPED, "acme", {}))
        snap = OBS.windows.snapshot_tenant("acme")
        assert snap["rate_per_s"] > 0
        assert snap["errors_per_s"] > 0
        assert 0 < snap["error_rate"] < 0.2

    def test_disabled_windows_record_nothing(self):
        OBS.enabled = False
        reg = MetricsRegistry()
        col = MeteringEventCollector(reg)
        col.report(Event(EventType.PUB_RECEIVED, "ghost", {}))
        OBS.record_latency("ghost", "ingest", 0.1)
        OBS.record_fanout("ghost", 5)
        assert "ghost" not in OBS.windows.tenants()
        # monotonic counters still meter
        assert reg.get("ghost", TenantMetric.PUB_RECEIVED) == 1


class TestRegistryGauges:
    def test_gauge_appears_in_snapshot(self):
        reg = MetricsRegistry()
        reg.gauge("acme", "inflight", lambda: 7.0)
        snap = reg.snapshot()
        assert snap["tenants"]["acme"]["inflight"] == 7.0

    def test_raising_gauge_is_skipped_not_fatal(self):
        reg = MetricsRegistry()
        reg.gauge("acme", "bad", lambda: 1 / 0)
        reg.gauge("acme", "good", lambda: 3.0)
        snap = reg.snapshot()
        assert snap["tenants"]["acme"]["good"] == 3.0
        assert "bad" not in snap["tenants"]["acme"]

    def test_gauge_rebind_overwrites(self):
        reg = MetricsRegistry()
        reg.gauge("acme", "depth", lambda: 1.0)
        reg.gauge("acme", "depth", lambda: 2.0)
        assert reg.snapshot()["tenants"]["acme"]["depth"] == 2.0

    def test_tenant_filtered_snapshot(self):
        reg = MetricsRegistry()
        reg.inc("a", TenantMetric.PUB_RECEIVED, 3)
        reg.inc("b", TenantMetric.PUB_RECEIVED, 9)
        reg.gauge("a", "g", lambda: 1.0)
        snap = reg.snapshot(tenant="a")
        assert set(snap["tenants"]) == {"a"}
        assert snap["tenants"]["a"]["pub_received"] == 3
        assert snap["tenants"]["a"]["g"] == 1.0
        # the lean scrape skips fabric/stages
        assert "fabric" not in snap
        assert reg.tenant_counters("a") == {"pub_received": 3.0, "g": 1.0}
        # the registry stays BELOW the obs hub: device/obs sections are
        # composed by the API server, never here
        full = reg.snapshot()
        assert set(full["tenants"]) == {"a", "b"}
        assert "fabric" in full and "stages" in full
        assert "device" not in full and "obs" not in full


# ---------------------------------------------------------------------------
# noisy-neighbor detector
# ---------------------------------------------------------------------------

def _drive(slo, tenant, *, flows=0, fanout=0.0, wait=0.0, errors=0,
           ingest_ms=None):
    for _ in range(flows):
        slo.record_flow(tenant)
    if fanout:
        slo.record_fanout(tenant, fanout)
    if wait:
        slo.record_queue_wait(tenant, wait)
    for _ in range(errors):
        slo.record_error(tenant)
    if ingest_ms is not None:
        slo.record_latency(tenant, "ingest", ingest_ms / 1e3)


class TestDetector:
    def test_hot_tenant_ranks_first_and_is_flagged(self):
        clk = FakeClock()
        slo = TenantSLO(window_s=10, clock=clk)
        det = NoisyNeighborDetector(slo, clock=clk)
        _drive(slo, "hot", flows=100, fanout=900.0, wait=3.0)
        _drive(slo, "quiet", flows=20, fanout=10.0, wait=0.05)
        rows = det.evaluate()
        assert [r["tenant"] for r in rows[:2]] == ["hot", "quiet"]
        assert "noisy" in rows[0]["flags"]
        assert rows[1]["flags"] == []
        assert det.is_noisy("hot") and not det.is_noisy("quiet")

    def test_single_tenant_is_never_noisy(self):
        clk = FakeClock()
        slo = TenantSLO(window_s=10, clock=clk)
        det = NoisyNeighborDetector(slo, clock=clk)
        _drive(slo, "only", flows=1000, fanout=9999.0, wait=10.0)
        rows = det.evaluate()
        assert rows[0]["flags"] == []   # share 1.0 of a 1-tenant broker

    def test_idle_tenant_not_flagged_despite_share(self):
        clk = FakeClock()
        slo = TenantSLO(window_s=10, clock=clk)
        det = NoisyNeighborDetector(slo, min_rate_per_s=1.0, clock=clk)
        _drive(slo, "a", flows=2, fanout=5.0)       # 0.2 flows/s — idle
        _drive(slo, "b", flows=3, fanout=1.0)
        for r in det.evaluate():
            assert "noisy" not in r["flags"]

    def test_slow_flag_from_windowed_ingest_p99(self):
        clk = FakeClock()
        slo = TenantSLO(window_s=10, clock=clk)
        det = NoisyNeighborDetector(slo, slow_p99_ms=100.0, clock=clk)
        _drive(slo, "slowpoke", flows=50, ingest_ms=900.0)
        _drive(slo, "ok", flows=50, ingest_ms=1.0)
        rows = {r["tenant"]: r for r in det.evaluate()}
        assert "slow" in rows["slowpoke"]["flags"]
        assert "slow" not in rows["ok"]["flags"]

    def test_events_emitted_with_cooldown(self):
        clk = FakeClock()
        slo = TenantSLO(window_s=10, clock=clk)
        det = NoisyNeighborDetector(slo, event_cooldown_s=30.0, clock=clk)
        sink = CollectingEventCollector()
        det.events = sink
        _drive(slo, "hot", flows=100, fanout=900.0, wait=3.0)
        _drive(slo, "quiet", flows=20, fanout=1.0)
        det.evaluate()
        det.evaluate()              # inside cooldown: no duplicate
        assert len(sink.of(EventType.NOISY_TENANT)) == 1
        clk.t += 31.0
        _drive(slo, "hot", flows=100, fanout=900.0, wait=3.0)
        _drive(slo, "quiet", flows=20, fanout=1.0)
        det.evaluate()
        assert len(sink.of(EventType.NOISY_TENANT)) == 2

    def test_score_tenant_matches_ranked_row_without_cache_clobber(self):
        clk = FakeClock()
        slo = TenantSLO(window_s=10, clock=clk)
        det = NoisyNeighborDetector(slo, clock=clk)
        _drive(slo, "hot", flows=100, fanout=900.0, wait=3.0)
        _drive(slo, "quiet", flows=20, fanout=10.0, wait=0.05)
        ranked = {r["tenant"]: r for r in det.evaluate(emit=False)}
        flags_at = det._flags_at
        assert det.score_tenant("hot") == ranked["hot"]
        assert det.score_tenant("quiet") == ranked["quiet"]
        assert det.score_tenant("nobody") is None
        # the single-tenant path must not refresh the advisory cache
        assert det._flags_at == flags_at

    def test_cooldown_map_stays_bounded(self):
        clk = FakeClock()
        slo = TenantSLO(window_s=10, max_tenants=4096, clock=clk)
        det = NoisyNeighborDetector(slo, event_cooldown_s=30.0, clock=clk)
        det.events = sink = CollectingEventCollector()
        for i in range(1500):
            det._last_emit[(f"old{i}", "noisy")] = clk.t
        clk.t += 31.0               # everything above is past cooldown
        _drive(slo, "hot", flows=100, fanout=900.0, wait=3.0)
        _drive(slo, "quiet", flows=20, fanout=1.0)
        det.evaluate()
        assert len(sink.of(EventType.NOISY_TENANT)) == 1
        assert len(det._last_emit) <= 1024

    def test_flags_decay_with_the_window(self):
        clk = FakeClock()
        slo = TenantSLO(window_s=10, clock=clk)
        det = NoisyNeighborDetector(slo, clock=clk)
        _drive(slo, "hot", flows=100, fanout=900.0, wait=3.0)
        _drive(slo, "quiet", flows=20, fanout=1.0)
        det.evaluate()
        assert det.is_noisy("hot")
        clk.t = 25.0                # window slid past everything
        assert not det.is_noisy("hot")   # advisory TTL forces re-eval


class TestThrottlerAdvisory:
    def test_advisory_counts_enforce_denies(self):
        clk = FakeClock()
        OBS.windows = TenantSLO(window_s=10, clock=clk)
        OBS.detector = NoisyNeighborDetector(OBS.windows, clock=clk)
        _drive(OBS.windows, "hot", flows=100, fanout=900.0, wait=3.0)
        _drive(OBS.windows, "quiet", flows=20, fanout=1.0)
        OBS.detector.evaluate(emit=False)

        advisory = SLOAdvisedResourceThrottler()
        rt = TenantResourceType.TOTAL_INGRESS_BYTES_PER_SECOND
        assert advisory.has_resource("hot", rt)        # advisory only
        assert advisory.advised_denials == 1
        assert advisory.has_resource("quiet", rt)
        assert advisory.advised_denials == 1

        enforcing = SLOAdvisedResourceThrottler(enforce=True)
        assert not enforcing.has_resource("hot", rt)
        # non-rate resources are never advisory-denied
        assert enforcing.has_resource(
            "hot", TenantResourceType.TOTAL_CONNECTIONS)
        assert enforcing.has_resource("quiet", rt)


# ---------------------------------------------------------------------------
# push telemetry exporter
# ---------------------------------------------------------------------------

pytestmark_async = pytest.mark.asyncio


class _FlakySink:
    def __init__(self, fail_times=0):
        self.fail_times = fail_times
        self.batches = []

    async def ship(self, lines):
        if self.fail_times > 0:
            self.fail_times -= 1
            raise ConnectionError("sink down")
        self.batches.append(list(lines))

    def describe(self):
        return "flaky:"


@pytest.mark.asyncio
class TestExporter:
    async def test_file_sink_ships_metrics_and_slow_spans(self, tmp_path):
        path = tmp_path / "telemetry.jsonl"
        tracer_slow, trace.TRACER.slow_ms = trace.TRACER.slow_ms, 0.0001
        trace.TRACER.reset()
        try:
            OBS.record_latency("acme", "ingest", 0.005)
            with trace.span("pub.ingest", tenant="acme"):
                await asyncio.sleep(0.002)
            exp = TelemetryExporter(FileSink(str(path)), interval_s=60,
                                    snapshot_fn=OBS._export_snapshot)
            await exp._flush_once()
        finally:
            trace.TRACER.slow_ms = tracer_slow
            trace.TRACER.reset()
        lines = [json.loads(ln) for ln in
                 path.read_text().strip().splitlines()]
        kinds = [r["type"] for r in lines]
        assert "metrics" in kinds and "span" in kinds
        metric = next(r for r in lines if r["type"] == "metrics")
        assert "acme" in metric["slo"]
        span = next(r for r in lines if r["type"] == "span")
        assert span["slow"] and span["name"] == "pub.ingest"
        assert exp.shipped == len(lines) and exp.dropped == 0

    async def test_fast_child_of_slow_root_not_flagged_slow(self, tmp_path):
        path = tmp_path / "children.jsonl"
        tracer_slow, trace.TRACER.slow_ms = trace.TRACER.slow_ms, 50.0
        trace.TRACER.reset()
        trace.TRACER.sampler.default_rate = 1.0
        try:
            with trace.span("root", tenant="acme") as root:
                with trace.span("fastchild"):
                    pass
                root.start_ns -= 10**9        # root crosses the threshold
            exp = TelemetryExporter(FileSink(str(path)), interval_s=60)
            await exp._flush_once()
        finally:
            trace.TRACER.sampler.default_rate = 0.0
            trace.TRACER.slow_ms = tracer_slow
            trace.TRACER.reset()
        by_name = {r["name"]: r for r in
                   (json.loads(ln) for ln in
                    path.read_text().strip().splitlines())
                   if r["type"] == "span"}
        assert by_name["root"]["slow"] is True
        # dragged-in context span ships, but not as an SLO violation
        assert by_name["fastchild"]["slow"] is False

    async def test_sampled_export_never_double_ships_slow_spans(
            self, tmp_path):
        path = tmp_path / "dedupe.jsonl"
        tracer_slow, trace.TRACER.slow_ms = trace.TRACER.slow_ms, 50.0
        trace.TRACER.reset()
        trace.TRACER.sampler.default_rate = 1.0
        try:
            exp = TelemetryExporter(FileSink(str(path)), interval_s=60,
                                    export_sampled=True)
            with trace.span("root", tenant="acme") as root:
                with trace.span("child"):
                    pass            # fast child: sampled ring this tick
            await exp._flush_once()
            with trace.span("root2", tenant="acme") as root:
                with trace.span("child2"):
                    pass
                root.start_ns -= 10**9     # slow root: lands in BOTH rings
            await exp._flush_once()
        finally:
            trace.TRACER.sampler.default_rate = 0.0
            trace.TRACER.slow_ms = tracer_slow
            trace.TRACER.reset()
        spans = [json.loads(ln) for ln in
                 path.read_text().strip().splitlines()
                 if json.loads(ln)["type"] == "span"]
        ids = [s["span_id"] for s in spans]
        assert len(ids) == len(set(ids)), ids
        names = sorted(s["name"] for s in spans)
        assert names == ["child", "child2", "root", "root2"]
        slow_flags = {s["name"]: s["slow"] for s in spans}
        assert slow_flags["root2"] is True
        assert slow_flags["child2"] is False

    async def test_export_snapshot_registry_skips_device_probe(self):
        reg = MetricsRegistry()
        MeteringEventCollector(reg)         # binds registry to OBS
        snap = OBS._export_snapshot()
        assert "device" in snap             # probe-free top-level section
        assert "memory" not in snap["device"]
        # the embedded registry must not re-run device/obs sections
        assert "device" not in snap["registry"]
        assert "obs" not in snap["registry"]

    async def test_exporter_refcount_unbalanced_stop_is_safe(self,
                                                             tmp_path):
        # a caller whose start was a no-op must not release another
        # owner's ref
        assert OBS.start_exporter() is False    # no sink configured
        exp = TelemetryExporter(FileSink(str(tmp_path / "r.jsonl")),
                                interval_s=60)
        assert OBS.start_exporter(exp) is True
        await OBS.stop_exporter()               # balanced: stops
        assert OBS.exporter is None

    async def test_queue_is_bounded_with_drop_counter(self):
        sink = _FlakySink()
        exp = TelemetryExporter(sink, interval_s=60, queue_cap=8)
        for i in range(20):
            exp.enqueue({"i": i})
        assert len(exp._queue) == 8
        assert exp.dropped == 12
        await exp._flush_once()
        # survivors are the NEWEST records
        shipped = [json.loads(ln)["i"] for b in sink.batches for ln in b]
        assert shipped == list(range(12, 20))

    async def test_retry_then_success(self):
        sink = _FlakySink(fail_times=2)
        exp = TelemetryExporter(sink, interval_s=60)
        exp.enqueue({"x": 1})
        await exp._flush_once()
        assert exp.shipped == 1
        assert exp.ship_failures == 2

    async def test_retry_exhaustion_drops_batch_not_loop(self):
        sink = _FlakySink(fail_times=99)
        exp = TelemetryExporter(sink, interval_s=60)
        exp.enqueue({"x": 1})
        await exp._flush_once()
        assert exp.shipped == 0
        assert exp.dropped == 1
        # sink recovers: the next tick ships fresh records
        sink.fail_times = 0
        exp.enqueue({"x": 2})
        await exp._flush_once()
        assert exp.shipped == 1

    async def test_http_sink_posts_ndjson(self):
        from bifromq_tpu.obs import HTTPSink
        got = []

        async def serve(reader, writer):
            head = b""
            while b"\r\n\r\n" not in head:
                head += await reader.read(4096)
            head, _, body = head.partition(b"\r\n\r\n")
            n = int([ln for ln in head.split(b"\r\n")
                     if ln.lower().startswith(b"content-length")]
                    [0].split(b":")[1])
            while len(body) < n:
                body += await reader.read(4096)
            got.append(body)
            writer.write(b"HTTP/1.1 204 No Content\r\n"
                         b"content-length: 0\r\n\r\n")
            await writer.drain()
            writer.close()

        server = await asyncio.start_server(serve, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        try:
            sink = HTTPSink(f"http://127.0.0.1:{port}/telemetry")
            exp = TelemetryExporter(sink, interval_s=60)
            exp.enqueue({"a": 1})
            exp.enqueue({"b": 2})
            await exp._flush_once()
        finally:
            server.close()
            await server.wait_closed()
        assert exp.shipped == 2 and exp.dropped == 0
        lines = [json.loads(ln) for ln in
                 got[0].decode().strip().splitlines()]
        assert lines == [{"a": 1}, {"b": 2}]

    async def test_http_sink_rejection_counts_failure(self):
        from bifromq_tpu.obs import HTTPSink

        async def serve(reader, writer):
            await reader.read(4096)
            writer.write(b"HTTP/1.1 500 Nope\r\ncontent-length: 0\r\n\r\n")
            await writer.drain()
            writer.close()

        server = await asyncio.start_server(serve, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        try:
            exp = TelemetryExporter(
                HTTPSink(f"http://127.0.0.1:{port}/t"), interval_s=60)
            exp.enqueue({"a": 1})
            await exp._flush_once()
        finally:
            server.close()
            await server.wait_closed()
        assert exp.shipped == 0
        assert exp.ship_failures >= 1 and exp.dropped == 1

    def test_http_sink_rejects_bad_url(self):
        from bifromq_tpu.obs import HTTPSink
        with pytest.raises(ValueError):
            HTTPSink("ftp://x/y")

    def test_http_sink_keeps_query_string(self):
        from bifromq_tpu.obs import HTTPSink
        sink = HTTPSink("http://h:9009/ingest?token=abc")
        assert sink.path == "/ingest?token=abc"

    async def test_start_stop_background_task(self, tmp_path):
        path = tmp_path / "bg.jsonl"
        exp = TelemetryExporter(FileSink(str(path)), interval_s=0.05,
                                snapshot_fn=lambda: {"slo": {}})
        exp.start()
        await asyncio.sleep(0.2)
        await exp.stop()
        lines = path.read_text().strip().splitlines()
        assert len(lines) >= 1
        assert all(json.loads(ln)["type"] == "metrics" for ln in lines)


# ---------------------------------------------------------------------------
# slow-ring child capture (PR 2 follow-up fix)
# ---------------------------------------------------------------------------

class TestSlowTraceChildren:
    def test_slow_root_drags_children_into_slow_ring(self):
        from bifromq_tpu.trace import Tracer, TenantSampler
        tr = Tracer(sampler=TenantSampler(1.0), slow_ms=50.0)
        with tr.span("root", tenant="t") as root:
            for i in range(3):
                with tr.span(f"child{i}"):
                    pass                    # fast children
            root.start_ns -= 10**9                 # root crossed the threshold
        slow = tr.export(slow=True, limit=100)
        names = {s["name"] for s in slow}
        assert names == {"root", "child0", "child1", "child2"}
        tid = next(s["trace_id"] for s in slow if s["name"] == "root")
        assert all(s["trace_id"] == tid for s in slow)

    def test_child_capture_is_bounded(self):
        from bifromq_tpu.trace import Tracer, TenantSampler
        tr = Tracer(sampler=TenantSampler(1.0), slow_ms=50.0)
        with tr.span("root", tenant="t") as root:
            for i in range(100):
                with tr.span(f"c{i}"):
                    pass
            root.start_ns -= 10**9
        slow = tr.export(slow=True, limit=1000)
        # root + at most SLOW_CHILD_CAP children
        assert 2 <= len(slow) <= Tracer.SLOW_CHILD_CAP + 1

    def test_individually_slow_child_not_duplicated(self):
        from bifromq_tpu.trace import Tracer, TenantSampler
        tr = Tracer(sampler=TenantSampler(1.0), slow_ms=50.0)
        with tr.span("root", tenant="t") as root:
            with tr.span("slowchild") as c:
                c.start_ns -= 10**9                # child itself slow
            root.start_ns -= 10**9
        slow = tr.export(slow=True, limit=100)
        assert [s["name"] for s in slow].count("slowchild") == 1

    def test_remote_parented_slow_span_drags_children(self):
        """The server half of a cross-process trace: its top span's
        parent id is a REMOTE span id (never 0), and its slow spans must
        still pull their local children into the slow ring."""
        from bifromq_tpu.trace import (SpanContext, Tracer, TenantSampler,
                                       activate)
        tr = Tracer(sampler=TenantSampler(1.0), slow_ms=50.0)
        wire_ctx = SpanContext(trace_id=0xABC, span_id=0x999,
                               sampled=True, tenant="t")
        with activate(wire_ctx):
            with tr.span("rpc.server") as server:
                with tr.span("match.device"):
                    pass
                server.start_ns -= 10**9   # the server span is the slow one
        slow = tr.export(slow=True, limit=100)
        names = {s["name"] for s in slow}
        assert names == {"rpc.server", "match.device"}, names

    def test_fast_root_leaves_slow_ring_empty(self):
        from bifromq_tpu.trace import Tracer, TenantSampler
        tr = Tracer(sampler=TenantSampler(1.0), slow_ms=50.0)
        with tr.span("root", tenant="t"):
            with tr.span("child"):
                pass
        assert tr.export(slow=True) == []

    def test_ring_since_cursor(self):
        from bifromq_tpu.trace import SpanRing
        from bifromq_tpu.trace.span import Span

        def mk(i):
            return Span(name=f"s{i}", trace_id=1, span_id=i + 1,
                        parent_id=0, tenant="-", service="t",
                        start_hlc=i, end_hlc=i, duration_ms=1.0)
        ring = SpanRing(capacity=4)
        cur = 0
        for i in range(3):
            ring.record(mk(i))
        spans, cur, missed = ring.since(cur)
        assert [s.name for s in spans] == ["s0", "s1", "s2"]
        assert missed == 0
        spans, cur, missed = ring.since(cur)
        assert spans == [] and missed == 0
        # overflow the ring: 6 more spans into capacity 4 → 2 missed
        for i in range(3, 9):
            ring.record(mk(i))
        spans, cur, missed = ring.since(cur)
        assert missed == 2
        assert [s.name for s in spans] == ["s5", "s6", "s7", "s8"]


# ---------------------------------------------------------------------------
# end-to-end: /tenants ranking + /metrics tenant filter through a broker
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
class TestObsAPI:
    async def _http(self, port, method, path, body=b""):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(
            f"{method} {path} HTTP/1.1\r\nhost: x\r\n"
            f"content-length: {len(body)}\r\n"
            f"connection: close\r\n\r\n".encode() + body)
        await writer.drain()
        raw = await reader.read(262144)
        writer.close()
        head, _, payload = raw.partition(b"\r\n\r\n")
        return int(head.split(b" ")[1]), json.loads(payload)

    @pytest.fixture
    async def stack(self):
        from bifromq_tpu.apiserver import APIServer
        from bifromq_tpu.mqtt.broker import MQTTBroker
        registry = MetricsRegistry()
        events = MeteringEventCollector(registry,
                                        CollectingEventCollector())
        broker = MQTTBroker(port=0, events=events)
        await broker.start()
        api = APIServer(broker, port=0, metrics=registry)
        await api.start()
        yield broker, api, events
        await api.stop()
        broker.inbox.close()
        await broker.stop()

    async def test_hot_tenant_tops_ranking(self, stack):
        from bifromq_tpu.mqtt.client import MQTTClient
        broker, api, events = stack
        subs = []
        for tenant, n_subs in (("hot", 4), ("quiet", 1)):
            for i in range(n_subs):
                c = MQTTClient(port=broker.port,
                               client_id=f"{tenant}-s{i}",
                               username=f"{tenant}/u{i}")
                await c.connect()
                await c.subscribe("load/t")
                subs.append(c)
        hot = MQTTClient(port=broker.port, client_id="hot-pub",
                         username="hot/pub")
        quiet = MQTTClient(port=broker.port, client_id="quiet-pub",
                           username="quiet/pub")
        await hot.connect()
        await quiet.connect()
        for _ in range(40):
            await hot.publish("load/t", b"x", qos=1)
        for _ in range(2):
            await quiet.publish("load/t", b"x", qos=1)
        status, out = await self._http(api.port, "GET", "/tenants")
        assert status == 200
        ranked = [r["tenant"] for r in out["tenants"]]
        assert "hot" in ranked and "quiet" in ranked
        assert ranked.index("hot") < ranked.index("quiet")
        hot_row = out["tenants"][ranked.index("hot")]
        assert hot_row["fanout_share"] > 0.5
        assert hot_row["stages"].get("ingest", {}).get("count", 0) > 0

        # per-tenant detail endpoint
        status, detail = await self._http(api.port, "GET", "/tenants/hot")
        assert status == 200
        assert detail["tenant"] == "hot"
        assert detail["counters"]["pub_received"] >= 40
        assert detail["slo"]["rate_per_s"] > 0
        status, _ = await self._http(api.port, "GET", "/tenants/nobody")
        assert status == 404

        for c in subs + [hot, quiet]:
            await c.disconnect()

    async def test_metrics_tenant_filter(self, stack):
        from bifromq_tpu.mqtt.client import MQTTClient
        broker, api, _ = stack
        a = MQTTClient(port=broker.port, client_id="a1", username="ta/u")
        b = MQTTClient(port=broker.port, client_id="b1", username="tb/u")
        await a.connect()
        await b.connect()
        await a.publish("x/t", b"p", qos=1)
        await b.publish("x/t", b"p", qos=1)
        status, one = await self._http(api.port, "GET",
                                       "/metrics?tenant=ta")
        assert status == 200
        assert set(one["tenants"]) == {"ta"}
        assert one["tenants"]["ta"]["pub_received"] >= 1
        assert "fabric" not in one
        status, full = await self._http(api.port, "GET", "/metrics")
        assert {"ta", "tb"} <= set(full["tenants"])
        assert "device" in full
        assert "dispatch_queue_depth" in full["device"]
        await a.disconnect()
        await b.disconnect()

    async def test_obs_knobs(self, stack):
        _, api, _ = stack
        status, out = await self._http(api.port, "GET", "/obs")
        assert status == 200 and out["windows_enabled"] is True
        status, out = await self._http(
            api.port, "PUT", "/obs?windows=0&slow_p99_ms=250")
        assert status == 200
        assert out["windows_enabled"] is False
        assert out["slow_p99_ms"] == 250.0
        status, out = await self._http(api.port, "GET", "/tenants")
        assert out["enabled"] is False and out["tenants"] == []
        status, _ = await self._http(api.port, "PUT", "/obs?windows=nope")
        assert status == 400
        await self._http(api.port, "PUT", "/obs?windows=1")

    async def test_exporter_file_sink_through_broker(self, stack, tmp_path,
                                                     monkeypatch):
        """The env-configured exporter ships at least one metrics record
        for traffic driven through a live broker."""
        from bifromq_tpu.mqtt.client import MQTTClient
        broker, api, _ = stack
        path = tmp_path / "exp.jsonl"
        exp = TelemetryExporter(FileSink(str(path)), interval_s=60,
                                snapshot_fn=OBS._export_snapshot)
        c = MQTTClient(port=broker.port, client_id="e1", username="exp/u")
        await c.connect()
        await c.publish("e/t", b"z", qos=1)
        await c.disconnect()
        await exp._flush_once()
        lines = [json.loads(ln) for ln in
                 path.read_text().strip().splitlines()]
        metric = next(r for r in lines if r["type"] == "metrics")
        assert "exp" in metric["slo"]
        assert "registry" in metric     # bound by MeteringEventCollector
        assert metric["registry"]["tenants"]["exp"]["pub_received"] >= 1

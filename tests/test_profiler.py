"""Continuous profiler, compile-event ledger, segment-file persistence
and OTLP framing (ISSUE 8): per-batch stage records from both serve
paths, ledger attribution across forced/threshold compactions, the <2%
overhead bound on the recording site, store rotation / retention /
restart survival, and OTLP-JSON envelope shape."""

import asyncio
import json
import time

import pytest

from bifromq_tpu import trace
from bifromq_tpu.models.matcher import TpuMatcher
from bifromq_tpu.models.oracle import Route
from bifromq_tpu.obs import OBS, FileSink, ObsHub, TelemetryExporter
from bifromq_tpu.obs.profiler import CompileLedger, ContinuousProfiler
from bifromq_tpu.obs.segstore import SegmentStore
from bifromq_tpu.types import RouteMatcher

pytestmark = pytest.mark.asyncio


def mk_route(tf: str, rid: str) -> Route:
    return Route(matcher=RouteMatcher.from_topic_filter(tf), broker_id=0,
                 receiver_id=rid, deliverer_key="d")


class TestProfilerCore:
    def test_batch_record_aggregation(self):
        p = ContinuousProfiler()
        p.record_batch(n_queries=3, batch=16, kernel="lax",
                       dispatch_s=0.001, ready_s=0.002, fetch_s=0.003)
        p.record_batch(n_queries=8, batch=16, kernel="lax_donated",
                       dispatch_s=0.002, path="sync")
        assert p.batches_total == 2
        assert p.queries_total == 11
        assert p.padded_rows_total == (16 - 3) + (16 - 8)
        snap = p.snapshot()
        assert snap["padding_waste_ratio"] == pytest.approx(
            21 / (11 + 21), abs=1e-3)
        assert snap["split"]["kernels"] == {"lax": 1, "lax_donated": 1}
        assert snap["split"]["dispatch_ms_p50"] > 0

    def test_frontend_and_degraded_counters(self):
        p = ContinuousProfiler()
        p.record_frontend(10, hits=7, dedup_saved=2)
        p.record_batch(n_queries=1, batch=1, kernel="oracle",
                       dispatch_s=0.0, degraded="timeout")
        snap = p.snapshot()
        assert snap["cache_bypass_rate"] == pytest.approx(0.7)
        assert snap["dedup_saved"] == 2
        assert snap["degraded"] == {"timeout": 1}

    def test_ring_bounded_and_since_cursor(self):
        p = ContinuousProfiler()
        for i in range(p.RING_CAP + 50):
            p.record_batch(n_queries=1, batch=1, kernel="lax",
                           dispatch_s=0.0)
        assert len(p.records()) == p.RING_CAP
        recs, cursor, missed = p.since(0)
        assert cursor == p.RING_CAP + 50
        assert missed == 50
        assert len(recs) == p.RING_CAP
        recs2, cursor2, missed2 = p.since(cursor)
        assert recs2 == [] and missed2 == 0 and cursor2 == cursor

    def test_recording_overhead_bound(self):
        """The ISSUE's <2% bound on the pipelined path: at the measured
        CPU pipeline p99 of ~3.8ms/batch, 2% is 76µs. The recording
        site must stay well under that — assert a generous 20µs mean
        over 10k records (it is attribute math + one list store)."""
        p = ContinuousProfiler()
        n = 10_000
        t0 = time.perf_counter()
        for _ in range(n):
            p.record_batch(n_queries=8, batch=16, kernel="lax",
                           dispatch_s=0.001, ready_s=0.001,
                           fetch_s=0.001, expand_s=0.001)
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 20e-6, f"record_batch cost {per_call*1e6:.1f}µs"

    def test_snapshot_and_reset(self):
        p = ContinuousProfiler()
        p.record_batch(n_queries=1, batch=2, kernel="lax", dispatch_s=0.0)
        p.ledger.record(reason="refresh", duration_s=0.1, salt=0,
                        n_nodes=10, table_bytes=100,
                        generation_bumped=False)
        p.reset()
        snap = p.snapshot()
        assert snap["batches"] == 0
        assert snap["compile_ledger"]["total"] == 0


class TestHostOnlySurfaces:
    """The profiler and the memory gauge read host state only: a scrape
    must never be the thing that initializes (and claims) the device."""

    def test_split_snapshot_is_host_stage_times_only(self):
        p = ContinuousProfiler()
        p.record_batch(n_queries=4, batch=16, kernel="lax",
                       dispatch_s=0.001, ready_s=0.004, fetch_s=0.002)
        snap = p.split_snapshot()
        assert snap["window_batches"] == 1
        assert snap["ready_ms_p50"] == pytest.approx(4.0)
        assert snap["fetch_ms_p50"] == pytest.approx(2.0)
        assert not any("rtt" in k or "kernel_ms" in k for k in snap)

    def test_memory_stats_unavailable_until_a_base_is_installed(self):
        from bifromq_tpu.obs.device import DeviceGauges
        g = DeviceGauges()
        assert g.memory_stats()["available"] is False
        m = TpuMatcher(match_cache=False)
        g.register_matcher(m)
        assert g.memory_stats()["available"] is False   # no tables yet

    def test_warmup_failure_is_counted_not_swallowed(self, monkeypatch):
        from bifromq_tpu.ops import match as om
        from bifromq_tpu.utils.metrics import FABRIC, FabricMetric

        def boom(*a, **kw):
            raise RuntimeError("compiler refused the walk")
        monkeypatch.setattr(om, "walk_routes", boom)
        before = FABRIC.get(FabricMetric.WARMUP_FAILED)
        m = TpuMatcher(match_cache=False)
        m.add_route("T", mk_route("w/+", "r0"))
        m.refresh()
        assert FABRIC.get(FabricMetric.WARMUP_FAILED) == before + 1


class TestMatcherIntegration:
    def _matcher(self, n=60, **kw) -> TpuMatcher:
        m = TpuMatcher(auto_compact=False, **kw)
        for i in range(n):
            m.add_route("T", mk_route(f"p/{i}/+", f"r{i}"))
        m.refresh()
        return m

    def test_sync_path_records_profile(self):
        OBS.profiler.reset()
        m = self._matcher()
        m.match_batch([("T", ["p", "3", "x"]), ("T", ["p", "4", "y"])])
        recs = OBS.profiler.records()
        assert recs, "sync match must record a batch profile"
        last = recs[-1]
        assert last.path == "sync"
        assert last.kernel in ("lax", "lax_donated")
        assert last.n_queries == 2 and last.batch >= 2
        assert last.dispatch_s > 0 and last.fetch_s > 0

    async def test_async_path_records_ready_stage_and_cache_bypass(self):
        OBS.profiler.reset()
        m = self._matcher()
        q = [("T", ["p", "7", "x"])]
        await m.match_batch_async(q)
        await m.match_batch_async(q)        # cache hit: no device batch
        recs = [r for r in OBS.profiler.records() if r.path == "async"]
        assert len(recs) == 1, "the repeat must bypass the device"
        assert recs[0].ready_s >= 0 and recs[0].fetch_s > 0
        snap = OBS.profiler.snapshot()
        assert snap["cache_bypass_rate"] > 0

    def test_compile_ledger_attribution_across_forced_compaction(
            self, monkeypatch):
        """first_base → threshold → forced, each with duration, salt,
        table bytes and the VMEM verdict — rebuild storms must read as
        a sequence of causes. Pinned to the overlay path (ISSUE 9: with
        patching on, mutations fold into the base and the overlay
        threshold never fires — patched churn is ledgered as `patch`
        events instead, tests/test_patch.py)."""
        monkeypatch.setenv("BIFROMQ_PATCH", "0")
        OBS.profiler.reset()
        m = TpuMatcher(auto_compact=True, compact_threshold=8)
        m.add_route("T", mk_route("a/0", "r0"))     # first_base (bg)
        m.drain()
        for i in range(1, 12):                      # crosses threshold=8
            m.add_route("T", mk_route(f"a/{i}", f"r{i}"))
        m.drain()
        m._maybe_compact(force=True)                # forced recompile
        m.drain()
        events = OBS.profiler.ledger.events()
        reasons = [e["reason"] for e in events]
        assert reasons[0] == "first_base"
        assert "threshold" in reasons
        assert reasons[-1] == "forced"
        for e in events:
            assert e["compile_s"] >= 0
            assert e["table_bytes"] > 0
            assert e["kind"] == "single"
        # pure same-salt compactions never bump the generation
        assert OBS.profiler.ledger.generation_bumps == 1

    def test_refresh_reason_and_mesh_kind(self):
        import jax
        from bifromq_tpu.parallel.sharded import MeshMatcher, make_mesh
        OBS.profiler.reset()
        mesh = make_mesh(1, 2, devices=jax.devices()[:2])
        m = MeshMatcher(mesh=mesh, auto_compact=False)
        m.add_route("T", mk_route("m/1", "r1"))
        m.refresh()
        ev = OBS.profiler.ledger.events()[-1]
        assert ev["kind"] == "mesh"
        assert ev["table_bytes"] > 0
        assert m.compile_time_s > 0     # mesh now accounts compile time


class TestSegmentStore:
    def test_rotation_and_retention(self, tmp_path):
        st = SegmentStore(str(tmp_path), max_segment_bytes=200,
                          max_segments=3)
        for i in range(60):
            st.append({"type": "profile", "i": i, "pad": "x" * 40})
        snap = st.snapshot()
        assert snap["segments"] <= 3
        assert snap["rotations"] > 0
        assert snap["segments_dropped"] > 0
        assert snap["bytes"] <= 3 * (200 + 4096)    # one record of slack
        # the OLDEST records were dropped, the newest survive
        recs = st.read()
        assert recs[-1]["i"] == 59
        assert recs[0]["i"] > 0

    def test_restart_survives_and_continues_numbering(self, tmp_path):
        st = SegmentStore(str(tmp_path), max_segment_bytes=100,
                          max_segments=4)
        for i in range(10):
            st.append({"type": "profile", "i": i})
        seq = st.snapshot()["active_seq"]
        # process restart: a fresh store on the same directory
        st2 = SegmentStore(str(tmp_path), max_segment_bytes=100,
                           max_segments=4)
        assert st2.snapshot()["active_seq"] == seq
        prev = st2.read()
        assert prev and prev[-1]["i"] == 9
        st2.append({"type": "profile", "i": 10})
        assert st2.read()[-1]["i"] == 10
        # retention enforced across the restart boundary too
        assert st2.snapshot()["segments"] <= 4

    def test_torn_line_skipped(self, tmp_path):
        st = SegmentStore(str(tmp_path))
        st.append({"type": "profile", "i": 1})
        with open(st._active_path(), "a") as f:
            f.write('{"type": "profile", "i"')    # crash mid-write
        st2 = SegmentStore(str(tmp_path))
        assert [r["i"] for r in st2.read()] == [1]

    def test_hub_persist_now_writes_typed_records(self, tmp_path):
        hub = ObsHub()
        hub.profiler.record_batch(n_queries=2, batch=4, kernel="lax",
                                  dispatch_s=0.001)
        hub.profiler.ledger.record(
            reason="refresh", duration_s=0.2, salt=0, n_nodes=5,
            table_bytes=123, generation_bumped=True)
        assert hub.start_persistence(SegmentStore(str(tmp_path)))
        n = hub.persist_now()
        assert n > 0
        types = {r["type"] for r in hub.store.read()}
        assert {"profile", "compile", "profile_summary"} <= types
        # incremental: nothing new → nothing written
        assert hub.persist_now() == 0
        hub.stop_persistence(final_flush=False)

    def test_hub_persists_delta_plane_events(self, tmp_path):
        """ISSUE 18: lag transitions, resyncs and autoscaler decisions
        drain into the segment store via the journal's cursor — same
        incremental contract as the profiler rings."""
        from bifromq_tpu.obs.lag import LAG, REPL_EVENTS
        LAG.reset()
        REPL_EVENTS.reset()
        hub = ObsHub()
        try:
            LAG.observe("n0", "r0", 99.0)       # → lag_stale event
            LAG.note_resync("n0", "r0")
            REPL_EVENTS.append("autoscale_decision", action="grow",
                               acted=True)
            assert hub.start_persistence(SegmentStore(str(tmp_path)))
            assert hub.persist_now() > 0
            kinds = [r["kind"] for r in hub.store.read()
                     if r["type"] == "repl_event"]
            assert kinds == ["lag_stale", "resync", "autoscale_decision"]
            # idempotent across flushes: the cursor advanced
            hub.persist_now()
            again = [r for r in hub.store.read()
                     if r["type"] == "repl_event"]
            assert len(again) == 3
            hub.stop_persistence(final_flush=False)
        finally:
            LAG.reset()
            REPL_EVENTS.reset()


class TestOTLPFraming:
    async def test_otlp_envelopes_validate_shape(self, tmp_path):
        path = tmp_path / "otlp.jsonl"
        tracer_slow, trace.TRACER.slow_ms = trace.TRACER.slow_ms, 0.0001
        trace.TRACER.reset()
        try:
            with trace.span("pub.ingest", tenant="acme"):
                await asyncio.sleep(0.002)
            exp = TelemetryExporter(
                FileSink(str(path)), interval_s=60,
                snapshot_fn=lambda: {"device": {"compile_count": 2}},
                resource={"node_id": "n1", "cluster_id": "c1",
                          "schema_version": "s1"},
                framing="otlp")
            exp.enqueue({"type": "profile", "ts": time.time(),
                         "batches": 3})
            await exp._flush_once()
        finally:
            trace.TRACER.slow_ms = tracer_slow
            trace.TRACER.reset()
        lines = [json.loads(ln) for ln in
                 path.read_text().strip().splitlines()]
        by_kind = {next(iter(ln)): ln for ln in lines}
        assert {"resourceSpans", "resourceMetrics",
                "resourceLogs"} <= set(by_kind)
        rs = by_kind["resourceSpans"]["resourceSpans"][0]
        attrs = {a["key"]: a["value"] for a in
                 rs["resource"]["attributes"]}
        assert attrs["bifromq.node_id"] == {"stringValue": "n1"}
        span = rs["scopeSpans"][0]["spans"][0]
        assert len(span["traceId"]) == 32
        assert len(span["spanId"]) == 16
        assert span["name"] == "pub.ingest"
        assert int(span["endTimeUnixNano"]) >= \
            int(span["startTimeUnixNano"])
        metric = by_kind["resourceMetrics"]["resourceMetrics"][0][
            "scopeMetrics"][0]["metrics"][0]
        assert metric["name"] == "device.compile_count"
        assert metric["gauge"]["dataPoints"][0]["asDouble"] == 2.0
        logrec = by_kind["resourceLogs"]["resourceLogs"][0][
            "scopeLogs"][0]["logRecords"][0]
        assert json.loads(logrec["body"]["stringValue"])["batches"] == 3

    async def test_jsonl_framing_unchanged(self, tmp_path):
        path = tmp_path / "native.jsonl"
        exp = TelemetryExporter(FileSink(str(path)), interval_s=60)
        exp.enqueue({"type": "profile", "ts": 1.0, "batches": 1})
        await exp._flush_once()
        rec = json.loads(path.read_text().strip())
        assert rec["type"] == "profile"

    def test_bad_framing_rejected(self):
        with pytest.raises(ValueError):
            TelemetryExporter(FileSink("/tmp/x"), framing="xml")

    def test_exporter_from_env_reads_format(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BIFROMQ_OBS_EXPORT",
                           str(tmp_path / "e.jsonl"))
        monkeypatch.setenv("BIFROMQ_OBS_FORMAT", "otlp")
        hub = ObsHub()
        exp = hub.exporter_from_env()
        assert exp.framing == "otlp"
        monkeypatch.setenv("BIFROMQ_OBS_FORMAT", "bogus")
        assert hub.exporter_from_env().framing == "jsonl"


# ---------------------------------------------------------------------------
# PR 29: the BatchRecord and the profiler's trace read the spans
# ---------------------------------------------------------------------------

class TestBoundaryRecords:
    async def test_batch_record_durations_equal_the_spans(self):
        """One publish on the rehearsal table: the profiler's BatchRecord
        holds the spans' own durations, not a second pair of clock
        reads."""
        from bifromq_tpu.mqtt.client import MQTTClient
        from rehearsal_broker import rehearsal_broker
        async with rehearsal_broker() as (node, _matcher, tenant, topics):
            p = MQTTClient("127.0.0.1", node.broker.port, client_id="bp",
                           username=f"{tenant}/pub")
            await p.connect()
            await p.publish(topics[0], b"warm" * 4, qos=1)
            trace.TRACER.reset()
            _recs, cursor, _missed = OBS.profiler.since(0)
            trace.TRACER.sampler.default_rate = 1.0
            try:
                await p.publish(topics[1], b"timed" * 4, qos=1)
            finally:
                trace.TRACER.sampler.default_rate = 0.0
            await p.disconnect()
            recs, _cursor, _missed = OBS.profiler.since(cursor)
        spans = {s.name: s for s in trace.TRACER.ring.spans()}
        trace.TRACER.reset()
        assert len(recs) == 1, [r.to_dict() for r in recs]
        rec = recs[0]

        def seconds(name):
            s = spans[name]
            return (s.end_ns - s.start_ns) * 1e-9
        assert rec.tokenize_s == seconds("device.tokenize")
        assert rec.dispatch_s == seconds("device.dispatch")
        assert rec.ready_s == seconds("device.ready")
        assert rec.fetch_s == seconds("device.fetch")
        assert rec.expand_s == seconds("match.expand")
        if "device.expand" in spans:
            assert rec.dev_expand_s == seconds("device.expand")
        # the fetch holds its wait
        assert seconds("device.fetch.wait") <= rec.fetch_s
        assert abs(rec.ts - time.time()) < 60       # wall clock, as read

    async def test_cpu_profiler_trace_holds_the_boundary_names(self,
                                                               tmp_path):
        """A ``jax.profiler.trace`` of one publish (CPU): the program's
        own annotations sit on a ``/host:`` plane, under the spans' bare
        names. Its own time limit, inside the harness's."""
        import glob
        import os
        import jax
        from jax.profiler import ProfileData
        from bifromq_tpu.mqtt.client import MQTTClient
        from rehearsal_broker import rehearsal_broker

        async def traced_publish():
            async with rehearsal_broker() as (node, _m, tenant, topics):
                p = MQTTClient("127.0.0.1", node.broker.port,
                               client_id="tp", username=f"{tenant}/pub")
                await p.connect()
                await p.publish(topics[0], b"warm" * 4, qos=1)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0    # the program names itself
                jax.profiler.start_trace(str(tmp_path),
                                         profiler_options=opts)
                try:
                    await p.publish(topics[1], b"seen" * 4, qos=1)
                finally:
                    jax.profiler.stop_trace()
                await p.disconnect()
        await asyncio.wait_for(traced_publish(), 50)
        (path,) = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        names = set()
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    names.update(e.name for e in line.events)
        # every synchronous boundary of a publish, by its bare name
        want = {"mqtt.decode", "device.tokenize", "device.dispatch",
                "device.fetch", "device.fetch.wait", "match.expand",
                "deliver.group"}
        assert want <= names, want - names
        # spans whose bodies await are not annotated as a whole
        assert not {"pub.ingest", "dist.pub", "device.ready"} & names

"""Flight-recorder unit + integration tests (ISSUE 2): sampler determinism,
ring-buffer wraparound, disabled-path no-op overhead, wire propagation, the
stage histograms, and a single-process publish traced end-to-end through
the broker hot path."""

import asyncio
import time

import pytest

from bifromq_tpu import trace
from bifromq_tpu.trace import (SpanContext, SpanRing, TenantSampler,
                               Tracer, decode_ctx)
from bifromq_tpu.trace.span import Span
from bifromq_tpu.utils.hlc import HLC
from bifromq_tpu.utils.metrics import STAGES, LatencyHistogram


def _mk_span(i, trace_id=0xABC, tenant="-"):
    return Span(name=f"s{i}", trace_id=trace_id, span_id=i + 1,
                parent_id=0, tenant=tenant, service="t",
                start_hlc=i, end_hlc=i + 1, duration_ms=1.0)


class TestSampler:
    def test_deterministic_per_trace_id(self):
        s = TenantSampler(0.5)
        ids = [trace.new_id() for _ in range(512)]
        first = [s.sample("-", t) for t in ids]
        again = [s.sample("-", t) for t in ids]
        assert first == again
        # roughly half sampled (loose: 512 draws at p=.5)
        frac = sum(first) / len(first)
        assert 0.3 < frac < 0.7

    def test_edge_rates(self):
        s = TenantSampler(0.0)
        ids = [trace.new_id() for _ in range(64)]
        assert not any(s.sample("-", t) for t in ids)
        s.default_rate = 1.0
        assert all(s.sample("-", t) for t in ids)

    def test_per_tenant_overrides(self):
        s = TenantSampler(0.0)
        s.set_rate("hot", 1.0)
        assert s.active
        t = trace.new_id()
        assert s.sample("hot", t)
        assert not s.sample("cold", t)
        s.clear_rate("hot")
        assert not s.active
        assert not s.sample("hot", t)


class TestRing:
    def test_wraparound_keeps_newest_in_order(self):
        ring = SpanRing(4)
        for i in range(6):
            ring.record(_mk_span(i))
        assert len(ring) == 4
        assert ring.dropped == 2
        assert [s.name for s in ring.spans()] == ["s2", "s3", "s4", "s5"]

    def test_below_capacity(self):
        ring = SpanRing(8)
        for i in range(3):
            ring.record(_mk_span(i))
        assert [s.name for s in ring.spans()] == ["s0", "s1", "s2"]
        assert ring.dropped == 0
        ring.clear()
        assert len(ring) == 0


class TestDisabledOverhead:
    """Tier-1-safe smoke for the acceptance criterion: with sampling off,
    spans time their boundary and build nothing else."""

    def test_disabled_span_is_shared_noop(self):
        t = Tracer()     # default: rate 0, no slow threshold
        assert not t.enabled
        for sp in (t.span("pub.ingest", tenant="x"), t.span("anything")):
            assert not sp.sampled and sp.ctx is None
            with sp:
                pass
        assert len(t.ring) == 0

    def test_disabled_overhead_negligible(self):
        t = Tracer()
        n = 50_000
        t0 = time.perf_counter()
        for _ in range(n):
            with t.span("hot", tenant="x", k=1):
                pass
        elapsed = time.perf_counter() - t0
        # intentionally generous (CI-safe): ~40µs/span budget vs the
        # sub-µs reality — catches accidental allocation/recording on
        # the disabled path, not scheduler noise
        assert elapsed < 2.0, f"disabled span too slow: {elapsed:.3f}s"

    def test_unsampled_root_blocks_children_from_rooting(self):
        t = Tracer()
        t.sampler.default_rate = 1e-18      # enabled, ~never samples
        with t.span("root", tenant="x"):
            child = t.span("child")
            assert not child.sampled and child.ctx is None
        assert len(t.ring) == 0


class TestSpans:
    def test_parent_child_share_trace_and_order_by_hlc(self):
        t = Tracer(service="test")
        t.sampler.default_rate = 1.0
        with t.span("root", tenant="acme", k="v") as root:
            with t.span("child"):
                pass
        spans = {s.name: s for s in t.ring.spans()}
        assert set(spans) == {"root", "child"}
        assert spans["child"].trace_id == spans["root"].trace_id
        assert spans["child"].parent_id == spans["root"].span_id
        assert spans["child"].start_hlc > spans["root"].start_hlc
        assert spans["child"].end_hlc < spans["root"].end_hlc
        assert spans["root"].tenant == "acme"
        assert spans["child"].tenant == "acme"      # inherited
        assert spans["root"].tags == {"k": "v"}
        assert root.ctx.trace_id == spans["root"].trace_id

    def test_error_status(self):
        t = Tracer()
        t.sampler.default_rate = 1.0
        with pytest.raises(ValueError):
            with t.span("boom"):
                raise ValueError("x")
        (s,) = t.ring.spans()
        assert s.status == "error"
        assert s.tags["error"] == "ValueError"

    def test_slow_ring_captures_unsampled_outliers(self):
        t = Tracer(slow_ms=5.0)
        assert t.enabled                    # slow-watch arms the tracer
        with t.span("fast", tenant="x"):
            pass
        with t.span("slow", tenant="x"):
            time.sleep(0.02)
        assert len(t.ring) == 0             # nothing probabilistically sampled
        names = [s.name for s in t.slow_ring.spans()]
        assert names == ["slow"]
        assert t.slow_ring.spans()[0].tags.get("slow_only") is True

    def test_sampled_slow_span_lands_in_both_rings(self):
        t = Tracer(slow_ms=1.0)
        t.sampler.default_rate = 1.0
        with t.span("slowish", tenant="x"):
            time.sleep(0.005)
        assert [s.name for s in t.ring.spans()] == ["slowish"]
        assert [s.name for s in t.slow_ring.spans()] == ["slowish"]

    def test_export_filters_and_orders(self):
        t = Tracer()
        t.sampler.default_rate = 1.0
        with t.span("a", tenant="t1"):
            pass
        with t.span("b", tenant="t2"):
            pass
        out = t.export(tenant="t1")
        assert [s["name"] for s in out] == ["a"]
        tid = out[0]["trace_id"]
        assert t.export(trace_id=tid)[0]["name"] == "a"
        hlcs = [s["start_hlc"] for s in t.export()]
        assert hlcs == sorted(hlcs)


class TestWirePropagation:
    def test_inject_extract_roundtrip_merges_hlc(self):
        t = Tracer()
        t.sampler.default_rate = 1.0
        with t.span("root", tenant="x") as root:
            blob = t.inject()
            assert blob is not None
            before = HLC.INST.get()
            ctx = decode_ctx(blob)
            assert ctx is not None
            assert ctx.trace_id == root.ctx.trace_id
            assert ctx.span_id == root.ctx.span_id
            assert ctx.sampled
            # the merge advanced the clock past the carried stamp
            assert HLC.INST.get() > before

    def test_extract_garbage_is_none(self):
        assert decode_ctx(b"") is None
        assert decode_ctx(b"\x00" * 10) is None
        assert decode_ctx(b"\x00" * 25) is None     # zero trace id

    def test_hostile_future_stamp_does_not_poison_clock(self):
        """A remote stamp beyond the drift bound must NOT be merged: one
        hostile frame would otherwise wedge the process clock (and, via
        re-stamped outgoing contexts, the cluster) at ~year 10889."""
        import struct as _s
        evil = _s.pack(">QQBQ", 7, 8, 1, (1 << 64) - 1)
        before = HLC.INST.get()
        ctx = decode_ctx(evil)
        assert ctx is not None and ctx.trace_id == 7  # context still works
        after = HLC.INST.get()
        # clock advanced normally (monotone), not to the poisoned stamp
        assert before < after < (1 << 63)

    def test_activate_installs_and_clears(self):
        ctx = SpanContext(123, 456, True, "t")
        with trace.activate(ctx):
            assert trace.current_ctx() is ctx
            with trace.activate(None):      # explicit CLEAR
                assert trace.current_ctx() is None
            assert trace.current_ctx() is ctx
        assert trace.current_ctx() is None


class TestHistograms:
    def test_log_buckets_and_percentiles(self):
        h = LatencyHistogram()
        for _ in range(98):
            h.record(0.001)     # 1 ms
        h.record(1.0)           # two 1 s outliers: p99 lands among them
        h.record(1.0)
        snap = h.snapshot()
        assert snap["count"] == 100
        assert 0.5 <= snap["p50_ms"] <= 3.0
        assert snap["p99_ms"] >= 500.0
        h.reset()
        assert h.snapshot()["count"] == 0

    def test_stage_registry_snapshot(self):
        STAGES.reset()
        STAGES.record("unit_test_stage", 0.002)
        snap = STAGES.snapshot()
        assert snap["unit_test_stage"]["count"] == 1
        assert snap["unit_test_stage"]["p50_ms"] > 0


@pytest.mark.asyncio
class TestBrokerHotPathTrace:
    """A sampled PUBLISH through a real (single-process) broker produces
    one trace covering ingest → batch queue-wait → device match → deliver,
    with queue-wait and device time as separate spans."""

    async def test_publish_trace_spans(self):
        from bifromq_tpu.mqtt.broker import MQTTBroker
        from bifromq_tpu.mqtt.client import MQTTClient

        trace.TRACER.reset()
        trace.TRACER.sampler.default_rate = 1.0
        try:
            broker = MQTTBroker(host="127.0.0.1", port=0)
            await broker.start()
            try:
                sub = MQTTClient("127.0.0.1", broker.port, client_id="ts")
                await sub.connect()
                await sub.subscribe("tr/+/x", qos=1)
                p = MQTTClient("127.0.0.1", broker.port, client_id="tp")
                await p.connect()
                await p.publish("tr/a/x", b"traced", qos=1)
                msg = await asyncio.wait_for(sub.messages.get(), 10)
                assert msg.payload == b"traced"
                await sub.disconnect()
                await p.disconnect()
            finally:
                await broker.stop()
        finally:
            trace.TRACER.sampler.default_rate = 0.0

        spans = trace.TRACER.export(limit=1000)
        ingest = [s for s in spans if s["name"] == "pub.ingest"
                  and s["tags"].get("topic") == "tr/a/x"]
        assert ingest, f"no ingest root span in {[s['name'] for s in spans]}"
        tid = ingest[0]["trace_id"]
        mine = [s for s in spans if s["trace_id"] == tid]
        names = {s["name"] for s in mine}
        # queue-wait and device time reported as SEPARATE spans
        assert {"pub.ingest", "batch.queue_wait", "match.device",
                "deliver.fanout"} <= names, names
        assert len(mine) >= 5
        # causal HLC order: every child starts after the root
        root_hlc = ingest[0]["start_hlc"]
        for s in mine:
            if s["name"] != "pub.ingest":
                assert s["start_hlc"] > root_hlc, s
        # batch shape captured at emit time
        qw = next(s for s in mine if s["name"] == "batch.queue_wait")
        assert qw["tags"]["batch_size"] >= 1
        assert qw["tags"]["cap"] >= 1
        # stage histograms populated alongside the spans
        snap = STAGES.snapshot()
        for stage in ("ingest", "queue_wait", "device", "deliver"):
            assert snap.get(stage, {}).get("count", 0) >= 1, (stage, snap)


# ---------------------------------------------------------------------------
# PR 29: one recorder on the publish path
# ---------------------------------------------------------------------------

class TestWindowTotals:
    """Window totals under a fake clock: a reader that was not there when
    a window opened takes it afterwards, over whole one-second slices."""

    def _totals(self, now):
        from bifromq_tpu.trace import WindowTotals
        return WindowTotals(clock=lambda: now[0])

    def test_between_over_slice_edges(self):
        now = [10 * 10**9]
        t = self._totals(now)
        t.add("a", 1, 5_000_000, 10 * 10**9 + 1)          # second 10
        t.add("a", 1, 7_000_000, 11 * 10**9 - 1)          # still second 10
        t.add("a", 1, 11_000_000, 11 * 10**9)             # second 11
        t.add("a", 1, 13_000_000, 12 * 10**9 + 5)         # second 12
        now[0] = 13 * 10**9
        # whole slices: from the one holding t0 up to, not including,
        # the one holding t1
        assert t.between(10 * 10**9, 11 * 10**9) == {"a": (2, 0.012)}
        assert t.between(10 * 10**9 + 999, 12 * 10**9 + 1)["a"] == \
            (3, pytest.approx(0.023))
        assert t.between(11 * 10**9, 13 * 10**9)["a"] == \
            (2, pytest.approx(0.024))
        assert t.between(12 * 10**9, 12 * 10**9 + 999_999_999) == {}
        assert t.peaks(10 * 10**9, 13 * 10**9) == {"a": 0.013}

    def test_window_readable_for_240s_then_expires(self):
        now = [100 * 10**9]
        t = self._totals(now)
        t.add("a", 1, 1_000_000)
        assert t.KEEP_S >= 240
        now[0] = (100 + 240) * 10**9
        assert t.between(100 * 10**9, 101 * 10**9) == {"a": (1, 0.001)}
        # past KEEP_S the slice is gone, overwritten or not
        now[0] = (100 + t.KEEP_S) * 10**9
        assert t.between(100 * 10**9, 101 * 10**9) == {}
        t.add("b", 1, 2_000_000)        # same slot, another second
        assert t.between(100 * 10**9, (101 + t.KEEP_S) * 10**9) == \
            {"b": (1, 0.002)}

    def test_counter_and_span_share_a_slice(self):
        now = [50 * 10**9 + 7]
        t = Tracer()
        t.totals.clock = lambda: now[0]
        t.count("ready.polls", 3)
        t.count("ready.polls", 0)
        t.totals.add("device.ready", 1, 4_000_000)
        now[0] += 10**9
        got = t.totals.between(50 * 10**9, 51 * 10**9)
        assert got == {"ready.polls": (3, 0.0), "device.ready": (1, 0.004)}

    def test_span_feeds_totals_on_the_monotonic_clock(self):
        t = Tracer()
        t0 = time.monotonic_ns()
        with t.span("unit.totals") as sp:
            time.sleep(0.002)
        assert t0 <= sp.start_ns <= sp.end_ns <= time.monotonic_ns()
        got = t.totals.between(t0, time.monotonic_ns() + 10**9)
        n, total_s = got["unit.totals"]
        assert n == 1 and total_s == pytest.approx(sp.duration_s)
        assert total_s >= 0.002


class TestRegistry:
    def test_known_stages_derive_from_the_one_table(self):
        from bifromq_tpu.trace import BOUNDARIES, KNOWN_STAGES
        from bifromq_tpu.utils import metrics
        assert metrics.KNOWN_STAGES is KNOWN_STAGES
        for stage in ("ingest", "queue_wait", "device", "deliver",
                      "device.dispatch", "device.ready"):
            assert stage in KNOWN_STAGES
        assert BOUNDARIES["pub.ingest"].stage == "ingest"
        assert BOUNDARIES["deliver.group"].sync
        assert not BOUNDARIES["pub.ingest"].sync    # its body awaits

    def test_readme_table_is_generated_from_the_registry(self):
        import os
        from bifromq_tpu.trace import names
        readme = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "README.md")
        with open(readme, encoding="utf-8") as f:
            text = f.read()
        assert names.readme_table() in text
        assert names.replace_table(text) == text


class _Summing(LatencyHistogram):
    """The twin ``benchmarks/sut.py`` installs: its own ``sum_s`` / ``n``,
    then the histogram's record."""

    def __init__(self):
        super().__init__()
        self.sum_s = 0.0
        self.n = 0

    def record(self, seconds):
        self.sum_s += seconds
        self.n += 1
        super().record(seconds)


class TestOneTimingPerBoundary:
    async def test_fanout_reaches_stage_histogram_exactly_once(self):
        from bifromq_tpu.mqtt.broker import MQTTBroker
        from bifromq_tpu.mqtt.client import MQTTClient
        old = dict(STAGES._hists)
        for stage in ("ingest", "queue_wait", "device", "deliver"):
            STAGES._hists[stage] = _Summing()
        n_pubs = 7
        trace.TRACER.totals.clear()     # slices are whole seconds
        t0 = time.monotonic_ns()
        try:
            broker = MQTTBroker(host="127.0.0.1", port=0)
            await broker.start()
            try:
                sub = MQTTClient("127.0.0.1", broker.port, client_id="os")
                await sub.connect()
                await sub.subscribe("once/#", qos=1)
                p = MQTTClient("127.0.0.1", broker.port, client_id="op")
                await p.connect()
                for i in range(n_pubs):
                    await p.publish(f"once/{i}", b"x", qos=1)
                for _ in range(n_pubs):
                    await asyncio.wait_for(sub.messages.get(), 10)
                await sub.disconnect()
                await p.disconnect()
            finally:
                await broker.stop()
            twins = {s: STAGES._hists[s] for s in
                     ("ingest", "queue_wait", "device", "deliver")}
        finally:
            STAGES._hists.clear()
            STAGES._hists.update(old)
        # exactly once an event: the twin counted N, and so did the
        # histogram under it (a doubled feed would read 2N)
        for stage in ("ingest", "queue_wait", "deliver"):
            assert twins[stage].n == n_pubs, (stage, twins[stage].n)
            assert twins[stage].count == n_pubs
        assert 1 <= twins["device"].n <= n_pubs       # pub cache may hit
        got = trace.TRACER.totals.between(t0, time.monotonic_ns() + 10**9)
        assert got["deliver.fanout"][0] == n_pubs
        assert got["deliver.fanout"][1] == pytest.approx(
            twins["deliver"].sum_s)
        assert got["pub.ingest"][1] == pytest.approx(twins["ingest"].sum_s)
        assert got["deliver.routes"][0] == n_pubs     # one subscriber each
        # its one route's MatchInfo was built on the first delivery and
        # reused by the six after; seven topics, seven grouped plans
        assert got["deliver.match_info.built"][0] == 1
        assert got["deliver.plan.built"][0] == n_pubs
        assert "deliver.plan.reused" not in got
        assert "deliver.settle.slow" not in got
        assert got["loop.lag"][0] >= 1

    def test_sampling_off_builds_no_span(self, monkeypatch):
        from bifromq_tpu.trace import tracer as tracer_mod

        def boom(*a, **kw):
            raise AssertionError("a Span was built with sampling off")
        monkeypatch.setattr(tracer_mod, "Span", boom)
        t = Tracer()
        with t.span("pub.ingest", tenant="x", topic="a/b"):
            with t.span("deliver.group"):
                pass
            t.record_finished("batch.queue_wait", trace.current_ctx(),
                              start_ns=1, end_ns=2)
        assert len(t.ring) == 0 and len(t.slow_ring) == 0
        assert trace.current_ctx() is None


PUBLISH_BOUNDARIES = {
    "pub.ingest", "dist.pub", "batch.queue_wait", "match.device",
    "device.acquire", "device.tokenize", "device.dispatch", "device.ready",
    "device.fetch", "device.fetch.wait", "match.expand", "deliver.fanout",
    "deliver.group", "deliver.call", "pub.ack"}


class TestRehearsalPublishTrace:
    async def test_one_publish_yields_one_trace_of_every_boundary(self):
        """Sampling 1.0, one QoS 1 publish through the broker on the
        benchmark's 20,000-row rehearsal table: one span of each boundary
        name under one trace id, children inside their parents on the
        monotonic stamps."""
        from bifromq_tpu.mqtt.client import MQTTClient
        from rehearsal_broker import rehearsal_broker
        async with rehearsal_broker() as (node, _matcher, tenant, topics):
            p = MQTTClient("127.0.0.1", node.broker.port, client_id="rp",
                           username=f"{tenant}/pub")
            await p.connect()
            await p.publish(topics[0], b"warm" * 4, qos=1)   # compiles
            trace.TRACER.reset()
            trace.TRACER.sampler.default_rate = 1.0
            try:
                await p.publish(topics[1], b"traced" * 4, qos=1)
            finally:
                trace.TRACER.sampler.default_rate = 0.0
            await p.disconnect()
        spans = trace.TRACER.ring.spans()
        trace.TRACER.reset()
        roots = [s for s in spans if s.name == "pub.ingest"]
        assert len(roots) == 1, [s.name for s in spans]
        mine = [s for s in spans if s.trace_id == roots[0].trace_id]
        names = [s.name for s in mine]
        assert PUBLISH_BOUNDARIES <= set(names), \
            PUBLISH_BOUNDARIES - set(names)
        for once in PUBLISH_BOUNDARIES - {"deliver.call"}:
            assert names.count(once) == 1, (once, names.count(once))
        by_id = {s.span_id: s for s in mine}
        for s in mine:
            assert 0 < s.start_ns <= s.end_ns
            if s.parent_id:
                parent = by_id[s.parent_id]
                assert parent.start_ns <= s.start_ns, (parent.name, s.name)
                assert s.end_ns <= parent.end_ns, (parent.name, s.name)
        # the socket read that carried the PUBLISH is a boundary too, a
        # root of its own (it ends before the session sees the packet)
        assert any(s.name == "mqtt.decode" for s in spans)
        # one batch id joins the publish's queue wait to its device spans
        ids = {s.tags.get("batch_id") for s in mine
               if s.name in ("batch.queue_wait", "device.dispatch",
                             "device.fetch", "deliver.fanout")}
        assert len(ids) == 1 and None not in ids, ids

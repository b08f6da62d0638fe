"""ISSUE 11 byte-plane parity & integration suite.

The ingest byte plane has four tokenizer legs — the per-row Python
reference, the vectorized numpy BLAKE2b, the native C++ tokenizer, and
the device hash program (jit'd XLA) — and they must be
BIT-EXACT with ``automaton.level_hash`` over adversarial topics:
multi-byte UTF-8, empty levels / separator runs, ``$share``/``$SYS``
roots, max-levels truncation, >1-block levels. Plus the serving
integration: raw-string queries through the matcher, the byte-keyed
TokenCache, escalation sub-batches from a device-tokenized mirror, the
sync-leg watchdog (PR 7 carry-over), the transfer-guard run proving the
byte plane makes only declared h2d transfers, and the operational
planner calibrate.
"""

import asyncio
import random

import numpy as np
import pytest

from bifromq_tpu.models import bytetok
from bifromq_tpu.models.automaton import TokenCache, level_hash, tokenize
from bifromq_tpu.models.bytetok import TopicBytes
from bifromq_tpu.models.matcher import TpuMatcher
from bifromq_tpu.models.oracle import Route
from bifromq_tpu.types import RouteMatcher
from bifromq_tpu.utils import topic as topic_util


def _adversarial_topics(rng: random.Random, n: int = 200):
    """Random topics biased toward the nasty shapes."""
    segs = ["a", "bb", "sensor", "température", "日本語", "датчик", "",
            "x" * 40, "d" * 127, "long" * 50, "$SYS", "$share", "0"]
    fixed = ["", "/", "//", "a//b", "///", "trailing/", "/leading",
             "$SYS/health/cpu", "$share/g/t", "a/" * 20 + "tail",
             "é" * 64, "x" * 129, "y" * 300 + "/z"]
    out = list(fixed)
    for _ in range(n - len(fixed)):
        depth = rng.randint(1, 20)
        out.append("/".join(rng.choice(segs) for _ in range(depth)))
    return out


class TestHashParity:
    @pytest.mark.parametrize("salt", [0, 1, 7, 987654321])
    def test_numpy_vectorized_blake2b_bit_exact(self, salt):
        rng = random.Random(salt)
        topics = _adversarial_topics(rng)
        roots = list(range(len(topics)))
        py = tokenize(topics, roots, max_levels=16, salt=salt,
                      native=False)
        tb = TopicBytes.from_topics(topics)
        h1, h2, ln, rv, sm = bytetok.tokenize_bytes(
            tb, roots, max_levels=16, salt=salt)
        np.testing.assert_array_equal(py.tok_h1, h1)
        np.testing.assert_array_equal(py.tok_h2, h2)
        np.testing.assert_array_equal(py.lengths, ln)
        np.testing.assert_array_equal(py.roots, rv)
        np.testing.assert_array_equal(py.sys_mask, sm)

    def test_native_consumes_topic_bytes(self):
        try:
            from bifromq_tpu.models.native_tok import load_lib
            load_lib()
        except Exception:
            pytest.skip("native tokenizer unavailable (no compiler)")
        rng = random.Random(5)
        topics = _adversarial_topics(rng)
        roots = list(range(len(topics)))
        tb = TopicBytes.from_topics(topics)
        py = tokenize(topics, roots, max_levels=16, salt=5, native=False)
        nat = tokenize(tb, roots, max_levels=16, salt=5, native=True)
        np.testing.assert_array_equal(py.tok_h1, nat.tok_h1)
        np.testing.assert_array_equal(py.tok_h2, nat.tok_h2)
        np.testing.assert_array_equal(py.lengths, nat.lengths)
        np.testing.assert_array_equal(py.sys_mask, nat.sys_mask)

    @pytest.mark.parametrize("salt", [11, 0x5EEDBEEF])
    def test_device_kernel_bit_exact_on_supported_rows(self, salt):
        from bifromq_tpu.ops.tokenize import device_tokenize
        rng = random.Random(11)
        topics = _adversarial_topics(rng, n=96)
        roots = list(range(len(topics)))
        n = len(topics)
        tb = TopicBytes.from_topics(topics)
        py = tokenize(topics, roots, max_levels=16, salt=salt,
                      native=False)
        mirror, probes = device_tokenize(tb, roots, max_levels=16,
                                         salt=salt)
        sup = mirror.lengths[:n] >= 0
        dh1 = np.asarray(probes.tok_h1)[:n]
        dh2 = np.asarray(probes.tok_h2)[:n]
        np.testing.assert_array_equal(dh1[sup], py.tok_h1[sup])
        np.testing.assert_array_equal(dh2[sup], py.tok_h2[sup])
        np.testing.assert_array_equal(
            np.asarray(probes.lengths)[:n][sup], py.lengths[sup])
        np.testing.assert_array_equal(
            np.asarray(probes.sys_mask)[:n][sup], py.sys_mask[sup])
        # the unsupported set is exactly the declared contract: too
        # deep (host also pads), too many bytes, or a >128B level
        from bifromq_tpu.ops.tokenize import tok_max_bytes
        for i in np.nonzero(~sup)[0]:
            enc = topics[i].encode("utf-8")
            assert (py.lengths[i] < 0 or len(enc) > tok_max_bytes()
                    or max(len(s.encode("utf-8"))
                           for s in topic_util.parse(topics[i])) > 128)

    def test_ragged_batch_padding_rows_hash_to_zero(self):
        # a batch padded past its topics must hash every real row and
        # leave the padding rows (nlv == -1) all-zero
        from bifromq_tpu.ops import tokenize as dtok
        topics = [f"a/b/{i}" for i in range(259)]
        roots = [0] * len(topics)
        tb = TopicBytes.from_topics(topics)
        py = tokenize(topics, roots, max_levels=16, salt=2, native=False)
        mirror, dev = dtok.device_tokenize(tb, roots, max_levels=16,
                                           salt=2, batch=512)
        h1, h2 = np.asarray(dev.tok_h1), np.asarray(dev.tok_h2)
        np.testing.assert_array_equal(h1[:259], py.tok_h1)
        np.testing.assert_array_equal(h2[:259], py.tok_h2)
        assert not h1[259:].any() and not h2[259:].any()
        assert (mirror.lengths[259:] == -1).all()

    def test_multiblock_level_hashlib_leg(self):
        # levels > 128 bytes exercise the multi-block hashlib fallback
        # of the numpy leg; parity against level_hash directly
        lvl = "z" * 500
        h1, h2 = bytetok.hash_levels(
            np.frombuffer(lvl.encode(), np.uint8),
            np.array([0], np.int64), np.array([500], np.int64), salt=9)
        assert (int(h1[0]), int(h2[0])) == level_hash(lvl, 9)


class TestTopicBytes:
    def test_pack_round_trip_str_bytes_levels(self):
        topics = ["a/b", "", "é/ü", "x/y/z"]
        tb_s = TopicBytes.from_topics(topics)
        tb_b = TopicBytes.from_topics([t.encode() for t in topics])
        tb_l = TopicBytes.from_topics([t.split("/") for t in topics])
        for tb in (tb_s, tb_b, tb_l):
            assert [tb.row_str(i) for i in range(4)] == topics
        np.testing.assert_array_equal(tb_s.data, tb_b.data)
        np.testing.assert_array_equal(tb_s.offsets, tb_l.offsets)

    def test_pack_nul_fallback(self):
        # a topic containing NUL (invalid MQTT, but the pack must not
        # corrupt) falls back to the per-row loop and stays exact
        topics = ["a/b", "bad\x00topic", "c"]
        tb = TopicBytes.from_topics(topics)
        assert [tb.row_str(i) for i in range(3)] == topics

    def test_select_is_row_subset(self):
        topics = [f"t/{i}/x" for i in range(10)]
        tb = TopicBytes.from_topics(topics)
        sub = tb.select([7, 2, 9])
        assert [sub.row_str(i) for i in range(3)] == \
            [topics[7], topics[2], topics[9]]

    def test_token_cache_keys_on_byte_slices(self):
        cache = TokenCache()
        topics = ["a/b", "c/d", "a/b"]
        tb = TopicBytes.from_topics(topics)
        t1 = tokenize(tb, [0, 1, 2], max_levels=8, salt=0, cache=cache)
        # in-batch duplicates probe before the miss fill lands (same
        # contract as the str-keyed path): 3 probes, 0 hits, then fill
        assert cache.misses == 3 and cache.hits == 0
        t2 = tokenize(TopicBytes.from_topics(["a/b"]), [5], max_levels=8,
                      salt=0, cache=cache)
        assert cache.hits == 1          # repeat probe, zero re-hash
        np.testing.assert_array_equal(t1.tok_h1[0], t2.tok_h1[0])
        assert t2.roots[0] == 5         # roots are per-batch, not cached


def _route(filt, url="r1"):
    return Route(matcher=RouteMatcher.from_topic_filter(filt),
                 broker_id=0, receiver_id=url, deliverer_key="d0",
                 incarnation=1)


def _canon(rows):
    return [(sorted((r.matcher.mqtt_topic_filter, r.receiver_url)
                    for r in m.normal),
             {f: sorted(r.receiver_url for r in ms)
              for f, ms in m.groups.items()}) for m in rows]


class TestMatcherByteQueries:
    def _matcher(self, **kw):
        m = TpuMatcher(auto_compact=False, **kw)
        for i in range(8):
            m.add_route("tenant", _route(f"s/{i}/t"))
        m.add_route("tenant", _route("s/+/t", url="wild"))
        m.add_route("tenant", _route("deep/#", url="hash"))
        m.refresh()
        return m

    def test_string_queries_equal_level_queries(self):
        m = self._matcher()
        qs = [("tenant", "s/3/t"), ("tenant", "deep/a/b"),
              ("tenant", "none")]
        ql = [(t, topic_util.parse(x)) for t, x in qs]
        assert _canon(m.match_batch(qs)) == _canon(m.match_batch(ql)) \
            == _canon(m.match_from_tries(qs))

    def test_wire_bytes_queries_equal_str_queries(self):
        """Wire ``bytes`` topics flow end-to-end: the byte plane packs
        them directly AND every fallback/overlay leg decodes them to
        level strings (review fix: _parse_levels(b"a/b") must not yield
        int levels)."""
        m = self._matcher()
        qs_b = [("tenant", b"s/3/t"), ("tenant", b"deep/a/b"),
                ("tenant", "a/" * 20 + "too-deep")]  # oracle-leg row
        qs_s = [(t, x.decode() if isinstance(x, bytes) else x)
                for t, x in qs_b]
        assert _canon(m.match_batch(qs_b)) == _canon(m.match_batch(qs_s))
        assert _canon(m.match_from_tries(qs_b)) == \
            _canon(m.match_from_tries(qs_s))

    def test_device_tokenize_serving_parity(self, monkeypatch):
        monkeypatch.setenv("BIFROMQ_DEVICE_TOKENIZE", "1")
        m = self._matcher()
        qs = [("tenant", "s/1/t"), ("tenant", "s/9/t"),
              ("tenant", "deep/x")]
        assert _canon(m.match_batch(qs)) == _canon(m.match_from_tries(qs))

        async def run():
            return await m.match_batch_async(qs)
        assert _canon(asyncio.get_event_loop().run_until_complete(run())) \
            == _canon(m.match_from_tries(qs))

    def test_device_tokenize_unsupported_row_takes_oracle(self,
                                                          monkeypatch):
        monkeypatch.setenv("BIFROMQ_DEVICE_TOKENIZE", "1")
        m = self._matcher()
        long_topic = "s/" + "x" * 300 + "/t"     # level > one block
        qs = [("tenant", long_topic), ("tenant", "s/2/t")]
        assert _canon(m.match_batch(qs)) == _canon(m.match_from_tries(qs))

    def test_escalation_sub_batch_from_device_mirror(self, monkeypatch):
        # force tiny state budget so a wildcard fanout overflows and the
        # escalation re-walk runs against a device-tokenized mirror
        monkeypatch.setenv("BIFROMQ_DEVICE_TOKENIZE", "1")
        m = TpuMatcher(auto_compact=False, k_states=2,
                       max_intervals=2)
        for i in range(12):
            m.add_route("tenant", _route(f"f/{i}/+/x", url=f"u{i}"))
            m.add_route("tenant", _route(f"f/{i}/y/#", url=f"h{i}"))
        m.add_route("tenant", _route("f/+/y/x", url="wide"))
        m.add_route("tenant", _route("#", url="root"))
        m.refresh()
        qs = [("tenant", f"f/{i}/y/x") for i in range(12)]
        assert _canon(m.match_batch(qs)) == _canon(m.match_from_tries(qs))

    def test_tokenize_stage_recorded(self):
        from bifromq_tpu.obs import OBS
        m = self._matcher()
        b0 = OBS.profiler.batches_total
        m.match_batch([("tenant", "s/0/t")])
        recs = OBS.profiler.records()
        n_new = OBS.profiler.batches_total - b0
        assert n_new > 0      # [-0:] would select the WHOLE ring
        new = recs[-n_new:]
        assert any(r.tokenize_s > 0 for r in new)
        assert "tokenize_ms" in new[-1].to_dict()
        assert "tokenize_ms_p50" in OBS.profiler.split_snapshot()


class TestSyncWatchdog:
    def test_sync_fetch_timeout_degrades_to_oracle(self, monkeypatch):
        """ISSUE 11 satellite (PR 7 carry-over): a never-ready result on
        the SYNC leg must degrade to the exact oracle within the
        deadline instead of blocking forever."""
        from bifromq_tpu.utils.metrics import FABRIC, FabricMetric
        # match_cache FALSE: a cache hit would serve the repeat query
        # without ever dispatching
        m = TpuMatcher(auto_compact=False, match_cache=False)
        m.add_route("tenant", _route("a/b"))
        m.refresh()
        qs = [("tenant", "a/b")]
        m.match_batch(qs)                   # warm real path

        class NeverReady:
            def is_ready(self):
                return False

        class FakeRes:
            start = NeverReady()
            count = NeverReady()
            overflow = NeverReady()

        real_dispatch = m._dispatch_prepared

        def hung_dispatch(prep, **kw):
            fl = real_dispatch(prep, **kw)
            fl.res = FakeRes()
            return fl
        monkeypatch.setattr(m, "_dispatch_prepared", hung_dispatch)
        monkeypatch.setenv("BIFROMQ_DEVICE_DEADLINE_S", "0.05")
        t0 = FABRIC.get(FabricMetric.DEVICE_TIMEOUT)
        stats = {}
        rows = m.match_batch(qs, stats=stats)
        assert stats.get("degraded") == "timeout"
        assert FABRIC.get(FabricMetric.DEVICE_TIMEOUT) == t0 + 1
        assert _canon(rows) == _canon(m.match_from_tries(qs))

    def test_sync_fetch_normal_path_unaffected(self):
        m = TpuMatcher(auto_compact=False)
        m.add_route("tenant", _route("a/+"))
        m.refresh()
        qs = [("tenant", "a/z")]
        assert _canon(m.match_batch(qs)) == _canon(m.match_from_tries(qs))


class TestTransferGuard:
    def test_byte_plane_declared_transfers_only(self, monkeypatch,
                                                no_implicit_transfers):
        """The device-tokenize serving path ships ONLY declared bytes:
        packed rows, boundary grids, h0 lanes, lengths/roots/sys — all
        explicit device_put — then walks. Any implicit transfer
        raises."""
        from bifromq_tpu.analysis import sanitize
        sanitize.assert_guard_arms()
        monkeypatch.setenv("BIFROMQ_DEVICE_TOKENIZE", "1")
        m = TpuMatcher(auto_compact=False)
        for i in range(8):
            m.add_route("tenant", _route(f"s/{i}/t"))
        m.refresh()
        warm = [("tenant", "s/0/t")]
        m.match_batch(warm)                 # compiles, unguarded
        queries = [("tenant", "s/3/t"), ("tenant", "q/r")]
        with no_implicit_transfers():
            rows = m.match_batch(queries)
        assert _canon(rows) == _canon(m.match_from_tries(queries))


class TestValidationParity:
    def test_is_valid_topic_matches_reference_loop(self):
        """The C-speed rewrite must be semantics-identical to the old
        per-char loop (re-implemented here as the oracle)."""
        def ref(topic, mll=40, ml=16, mlen=255):
            if not topic or len(topic) > mlen:
                return False
            if topic.startswith("$oshare/") or topic.startswith("$share/"):
                return False
            level_len, level = 0, 1
            for ch in topic:
                if ch == "/":
                    level += 1
                    if level > ml or level_len > mll:
                        return False
                    level_len = 0
                else:
                    if ch in ("\x00", "+", "#"):
                        return False
                    level_len += 1
            return level_len <= mll
        rng = random.Random(3)
        cases = _adversarial_topics(rng) + [
            "a" * 41, ("a/" * 16) + "b", "x/+/y", "#", "ok/topic",
            "a" * 40, "a/" * 15 + "b"]
        for t in cases:
            assert topic_util.is_valid_topic(t) == ref(t), t


class TestCalibrate:
    def test_calibrate_report_from_live_base(self):
        from bifromq_tpu.obs.capacity import calibrate_report
        m = TpuMatcher(auto_compact=False)
        for i in range(200):
            m.add_route("cal-tenant", _route(f"cal/{i}/+", url=f"r{i}"))
        m.refresh()
        rep = calibrate_report(n_subs=100_000)
        assert rep["calibrated"]
        assert rep["n_subs_live"] >= 200
        assert rep["after"]["calibrated_from"].startswith("live:")
        assert set(rep["delta"]) == {"nodes_per_sub", "edges_per_sub",
                                     "slots_per_sub", "edge_load"}
        pb = rep["predicted_table_bytes"]
        assert pb["n_subs"] == 100_000 and pb["after"] > 0

    def test_capacity_report_calibrate_flag(self):
        from bifromq_tpu.obs.capacity import capacity_report
        out = capacity_report(n_subs=50_000, calibrate=True)
        assert "calibrate" in out
        if out["calibrate"].get("calibrated"):
            assert "fits" in out

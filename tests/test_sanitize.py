"""Transfer-guard sanitizer harness (ISSUE 10): the matcher hot path —
sync, async, and patched-churn — must make only *declared* transfers
(`device_put` probe upload, the `_fetch_walk` readback) once warm.
Anything implicit (a numpy array slipping un-put into a jit'd walk, a
patch flush shipping host rows implicitly — the bug this PR fixed in
`_patch_device_trie`) raises under `jax.transfer_guard("disallow")`.

Runs on `JAX_PLATFORMS=cpu` (conftest forces it): the CPU guard catches
implicit host-to-device transfers, which is exactly the accidental-
upload class; d2h on CPU is zero-copy and exempt either way.
"""

import pytest

from bifromq_tpu.analysis import sanitize
from bifromq_tpu.models.matcher import TpuMatcher
from bifromq_tpu.models.oracle import Route
from bifromq_tpu.types import RouteMatcher


def _route(filt: str, url: str = "r1") -> Route:
    return Route(matcher=RouteMatcher.from_topic_filter(filt),
                 broker_id=0, receiver_id=url, deliverer_key="d0",
                 incarnation=1)


def _mk_matcher(n: int = 8, **kw) -> TpuMatcher:
    m = TpuMatcher(auto_compact=False, **kw)
    for i in range(n):
        m.add_route("tenant", _route(f"s/{i}/t"))
    m.add_route("tenant", _route("s/+/t", url="wild"))
    m.refresh()
    return m


def _canon(rows):
    return [sorted((r.matcher.mqtt_topic_filter, r.receiver_url)
                   for r in m.normal) for m in rows]


class TestGuardArms:
    def test_guard_rejects_implicit_h2d(self):
        # would raise TransferGuardUnavailable on a jax where the
        # sanitizer is vacuous — that must FAIL, not skip
        sanitize.assert_guard_arms()


class TestSyncPath:
    def test_sync_match_transfer_silent(self, no_implicit_transfers):
        m = _mk_matcher()
        warm = [("tenant", ["s", "0", "t"])]
        m.match_batch(warm)                       # compiles, unguarded
        queries = [("tenant", ["s", "3", "t"]), ("tenant", ["x", "y"])]
        with no_implicit_transfers():
            rows = m.match_batch(queries)
        assert _canon(rows) == _canon(m.match_from_tries(queries))


class TestAsyncPath:
    @pytest.mark.asyncio
    async def test_async_match_transfer_silent(self, no_implicit_transfers):
        m = _mk_matcher()
        warm = [("tenant", ["s", "0", "t"])]
        await m.match_batch_async(warm)           # compiles, unguarded
        queries = [("tenant", ["s", "5", "t"])]
        with no_implicit_transfers():
            rows = await m.match_batch_async(queries)
        assert _canon(rows) == _canon(m.match_from_tries(queries))
        assert m._ring is not None and m._ring.dispatched_total >= 2


class TestPatchedChurn:
    def test_patch_flush_transfer_silent(self, no_implicit_transfers):
        m = _mk_matcher()
        if not m._patching_enabled():
            pytest.skip("patch plane disabled in this environment")
        # one unguarded churn cycle compiles the flush scatters (they
        # are also pre-warmed at install — see test below)
        m.add_route("tenant", _route("warm/up"))
        m.match_batch([("tenant", ["warm", "up"])])
        flushes_before = m.patch_flushes
        with no_implicit_transfers():
            m.add_route("tenant", _route("churn/a"))
            m.add_route("tenant", _route("churn/+", url="wild2"))
            queries = [("tenant", ["churn", "a"])]
            rows = m.match_batch(queries)
        assert m.patch_flushes > flushes_before, \
            "churn did not exercise the patch-flush path"
        assert m.compile_count == 1, "churn must not trigger a rebuild"
        assert _canon(rows) == _canon(m.match_from_tries(queries))

    def test_patch_scatter_prewarmed_at_install(self, monkeypatch):
        """ISSUE 10 satellite (ROADMAP PR 9 follow-up (c)): the install-
        time warm covers the flush's scatter shape classes, so the first
        churn flush hits compiled code. Proven via jit cache stats: after
        refresh(), the first flush adds no scatter cache misses.

        The warm arms only for serving-scale arenas (WARM_SCATTER_MIN_
        ROWS) after a cold-start grace delay — both lowered here so a
        test-sized base exercises the full path deterministically. The
        warm's own completion registry is asserted (not just global jit
        cache counts, which a sibling test's flush on an equal shape
        class could satisfy vacuously), and this matcher uses a route
        count no other test in this file builds, so the no-re-trace
        check stays meaningful under the full suite too."""
        from bifromq_tpu.ops import match as om
        from bifromq_tpu.ops.match import (_WARMED_SCATTER_KEYS,
                                           _scatter_rows,
                                           _scatter_rows_donated)
        monkeypatch.setattr(om, "WARM_SCATTER_MIN_ROWS", 0)
        monkeypatch.setenv("BIFROMQ_SCATTER_WARM_DELAY_S", "0")
        keys_before = len(_WARMED_SCATTER_KEYS)
        m = _mk_matcher(n=61)
        if not m._patching_enabled():
            pytest.skip("patch plane disabled in this environment")
        # the warm runs on a background thread (install must not block
        # on it); the test joins to assert the steady state
        t = m._scatter_warm_thread
        assert t is not None, "install did not arm the scatter warm"
        t.join(timeout=30)
        assert len(_WARMED_SCATTER_KEYS) > keys_before, \
            "warm thread did not claim its shape class"
        hits0 = _scatter_rows._cache_size() \
            + _scatter_rows_donated._cache_size()
        assert hits0 >= 2, "install-time warm compiled no scatters"
        m.add_route("tenant", _route("first/churn"))
        m.match_batch([("tenant", ["first", "churn"])])
        hits1 = _scatter_rows._cache_size() \
            + _scatter_rows_donated._cache_size()
        assert hits1 == hits0, \
            f"first flush re-traced the scatter ({hits0} -> {hits1})"

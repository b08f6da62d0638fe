"""Retained-topic index: host authority + compiled trie for filter probes.

Host-side counterpart of ops.retained (the reference's RetainTopicIndex,
bifromq-retain .../store/index/RetainTopicIndex.java:35, rebuilt from KV on
reset — here rebuilt/compiled from the authoritative per-tenant topic maps).
The oracle-grade fallback ``match_filter_host`` mirrors RetainMatcher.java:36
semantics plus the [MQTT-4.7.2-1] root-'$' rule.

ISSUE 13: the index is PATCHED, not rebuilt, on the mutation path —
RETAIN set/clear/expire fold into the live
:class:`~bifromq_tpu.retained_plane.patched.RetainedPatchableTrie`
arenas as in-place row writes (tombstones, resurrections, extras-plane
appends, child-run maintenance) shipped to device as narrow scatters;
``compile_tries`` survives only for the first build, reset-from-KV, and
fragmentation-triggered compaction. The scan side is staged
(prepare → dispatch → fetch → expand) so the async serving plane
(retained_plane/scan.py) can thread the shared dispatch-ring/breaker/
watchdog machinery between the stages exactly like the forward matcher.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import trace
from ..types import RouteMatcher, RouteMatcherType
from ..utils import topic as topic_util
from ..utils.env import env_bool
from .automaton import (CompiledTrie, PatchFallback, _next_pow2,
                        compile_tries, tokenize_filters)
from .oracle import Route, SubscriptionTrie, _TrieNode


def retained_patch_enabled() -> bool:
    """Kill-switch for the in-place retained patch plane
    (``BIFROMQ_RETAIN_PATCH=0`` restores the rebuild-on-mutation path)."""
    from .automaton import patch_enabled
    return patch_enabled() and env_bool("BIFROMQ_RETAIN_PATCH", True)


def _topic_route(topic_levels: Sequence[str], topic_str: str) -> Route:
    """A retained topic stored as a wildcard-free 'route'; receiver == topic."""
    return Route(
        matcher=RouteMatcher(type=RouteMatcherType.NORMAL,
                             filter_levels=tuple(topic_levels),
                             mqtt_topic_filter=topic_str),
        broker_id=0, receiver_id=topic_str, deliverer_key="")


def match_filter_host(trie: SubscriptionTrie,
                      filter_levels: Sequence[str],
                      limit: Optional[int] = None) -> List[str]:
    """Exact filter-over-topic-trie match (host fallback & test oracle).

    ``limit`` makes the walk scan-bounded (early exit once ``limit``
    topics are collected — the RetainMessageMatchLimit contract): the
    production serving path always passes it. DEPTH-first traversal so a
    bounded lookup costs ~O(limit × depth) even for '+'-heavy filters
    over a million-topic trie — the level-synchronous frontier expansion
    paid the whole '+' fan-out before emitting anything (measured ~10ms
    per fallback at limit=10 on a 200K-topic trie; DFS is ~free).
    """
    out: List[str] = []
    cap = limit if limit is not None else (1 << 62)
    if cap <= 0:
        return out
    n_levels = len(filter_levels)

    class _Full(Exception):
        pass

    def emit(r) -> None:
        out.append(r.receiver_id)
        if len(out) >= cap:
            raise _Full()

    def collect_subtree(node: _TrieNode, skip_sys: bool) -> None:
        for r in node.routes.values():
            emit(r)
        for level, child in node.children.items():
            if skip_sys and level.startswith(topic_util.SYS_PREFIX):
                continue
            collect_subtree(child, False)

    def walk(node: _TrieNode, i: int) -> None:
        if i == n_levels:
            for r in node.routes.values():
                emit(r)
            return
        lvl = filter_levels[i]
        at_root = i == 0
        if lvl == topic_util.MULTI_WILDCARD:
            collect_subtree(node, skip_sys=at_root)
        elif lvl == topic_util.SINGLE_WILDCARD:
            for name, child in node.children.items():
                if at_root and name.startswith(topic_util.SYS_PREFIX):
                    continue
                walk(child, i + 1)
        else:
            child = node.children.get(lvl)
            if child is not None:
                walk(child, i + 1)

    try:
        walk(trie._root, 0)
    except _Full:
        pass
    return out


class _ScanPrep:
    """Stage-0 output of the retained scan pipeline: tokenized +
    uploaded filter probes plus the host mirrors the expansion needs.
    ``ct``/``recv`` are the SNAPSHOT the walk dispatched against — the
    matcher's _InFlight discipline: a compaction swapping the compiled
    base mid-flight (the async leg genuinely awaits between dispatch
    and expand) must not let old slot ids index a renumbered world."""

    __slots__ = ("queries", "probes", "roots", "lengths", "batch", "ct",
                 "recv")

    def __init__(self, **kw) -> None:
        for k, v in kw.items():
            setattr(self, k, v)


class RetainedIndex:
    """Per-tenant retained-topic tries + compiled automaton for device probes.

    Mirrors TpuMatcher's serving contract; query side takes wildcard
    FILTERS (ops.retained walk) instead of topics. ISSUE 13: mutations
    fold into the live arenas in place (``rebuilds`` stays 0 under a
    retained flood; ``compactions`` counts the fragmentation-triggered
    folds which are the only compiles after the first build).
    """

    def __init__(self, *, max_levels: int = 16, k_states: int = 32,
                 probe_len: int = 16, device=None,
                 patched: Optional[bool] = None) -> None:
        self.max_levels = max_levels
        self.k_states = k_states
        self.probe_len = probe_len
        self.device = device
        self.tries: Dict[str, SubscriptionTrie] = {}
        self._compiled: Optional[CompiledTrie] = None
        self._device_tables = None
        self._dirty = True
        self._patched = (retained_patch_enabled() if patched is None
                         else patched)
        # observability: full compiles split by trigger — a retained
        # flood must keep `rebuilds` at ZERO (ISSUE 13 acceptance);
        # compaction is the fragmentation fallback
        self.rebuilds = 0
        self.compactions = 0
        self.compile_time_s = 0.0
        self.patch_fallbacks = 0
        self.patch_flushes = 0
        # exact-invalidation consumers (scan cache, retained delta log):
        # fired per applied mutation with (tenant, levels, op) where op
        # is "set" | "del"; a full rebuild does NOT fire (results are
        # content-identical across renumbering)
        self.delta_hooks: List = []
        # slot -> retained topic string, capacity-padded object ndarray
        # so slot ranges expand with one vectorized fancy-index (the
        # per-slot loop measured ~90 filters/s on the c4 bench)
        self._receiver_arr = np.empty(0, dtype=object)
        # the scan plane pins its dispatch ring here so EVERY flush —
        # including ring-less callers like the coproc's RO wire query —
        # sees the in-flight scans before deciding to donate
        self.serving_ring = None

    # ---------------- mutation side (patch-first, ISSUE 13) -----------------

    def _emit_delta(self, tenant_id: str, levels, op: str) -> None:
        for cb in list(self.delta_hooks):
            try:
                cb(tenant_id, tuple(levels), op)
            except Exception:  # noqa: BLE001 — observers must not break
                import logging
                logging.getLogger(__name__).exception("retained delta hook")

    def _patch_base(self):
        """The live patchable base, or None when patching cannot serve
        this mutation (no base yet / kill-switch / pending rebuild)."""
        if not self._patched or self._dirty or self._compiled is None:
            return None
        from ..retained_plane.patched import RetainedPatchableTrie
        ct = self._compiled
        return ct if isinstance(ct, RetainedPatchableTrie) else None

    def add_topic(self, tenant_id: str, topic_levels: Sequence[str],
                  topic_str: str) -> bool:
        trie = self.tries.setdefault(tenant_id, SubscriptionTrie())
        route = _topic_route(topic_levels, topic_str)
        added = trie.add(route)
        if added:  # payload replacement leaves the index unchanged
            base = self._patch_base()
            if base is not None:
                try:
                    action, slot = base.retained_add(
                        tenant_id, list(topic_levels), route)
                    if action == "add":
                        self._recv_set(slot, topic_str)
                except PatchFallback:
                    # patch-era hash collision (astronomically rare):
                    # never guess — the rebuild re-salts
                    self.patch_fallbacks += 1
                    self._dirty = True
            else:
                self._dirty = True
            self._emit_delta(tenant_id, topic_levels, "set")
        return added

    def remove_topic(self, tenant_id: str, topic_levels: Sequence[str],
                     topic_str: str) -> bool:
        trie = self.tries.get(tenant_id)
        if trie is None:
            return False
        r = _topic_route(topic_levels, topic_str)
        removed = trie.remove(r.matcher, r.receiver_url)
        if removed:
            if len(trie) == 0:
                del self.tries[tenant_id]
            base = self._patch_base()
            if base is not None:
                try:
                    if not base.retained_remove(tenant_id,
                                                list(topic_levels)):
                        # index/authority drift — rebuild, never serve wrong
                        self.patch_fallbacks += 1
                        self._dirty = True
                except PatchFallback:
                    self.patch_fallbacks += 1
                    self._dirty = True
            else:
                self._dirty = True
            self._emit_delta(tenant_id, topic_levels, "del")
        return removed

    def topic_count(self, tenant_id: str) -> int:
        trie = self.tries.get(tenant_id)
        return len(trie) if trie is not None else 0

    # ---------------- compile / compaction ----------------------------------

    def _recv_set(self, slot: int, topic_str: str) -> None:
        if slot >= self._receiver_arr.shape[0]:
            arr = np.empty(_next_pow2(slot + 1, floor=64), dtype=object)
            arr[:self._receiver_arr.shape[0]] = self._receiver_arr
            self._receiver_arr = arr
        self._receiver_arr[slot] = topic_str

    def frag_pending(self) -> bool:
        base = self._patch_base()
        return base is not None and base.frag_pending()

    def refresh(self) -> CompiledTrie:
        if self._compiled is None:
            reason = "first"
        elif self._dirty:
            reason = "rebuild"
        elif self.frag_pending():
            # fragmentation compaction: the ONLY compile a patched index
            # runs after its first build (tombstone/garbage reclaim)
            reason = "compact"
        else:
            return self._compiled
        t0 = time.perf_counter()
        ct = compile_tries(self.tries, max_levels=self.max_levels,
                           probe_len=self.probe_len)
        if self._patched:
            from ..retained_plane.patched import RetainedPatchableTrie
            ct = RetainedPatchableTrie(ct)
        self._compiled = ct
        from ..ops.retained import RetainedDeviceTables
        self._device_tables = RetainedDeviceTables.from_trie(
            ct, device=self.device)
        arr = np.empty(_next_pow2(max(len(ct.matchings), 1), floor=64),
                       dtype=object)
        for i, m in enumerate(ct.matchings):
            arr[i] = m.receiver_id
        self._receiver_arr = arr
        self._dirty = False
        self.compile_time_s += time.perf_counter() - t0
        if reason == "rebuild":
            self.rebuilds += 1
        elif reason == "compact":
            self.compactions += 1
        return self._compiled

    def flush_device(self, *, ring=None, own_slots: int = 0) -> None:
        """Ship pending host patches to device as narrow scatters —
        coalesced, at most one flush per dispatch. Donation only when no
        in-flight scan can still read the old tables (same proof the
        forward matcher uses: the caller's own not-yet-dispatched slot
        plus an empty quarantine)."""
        base = self._patch_base()
        if base is None or not base.dirty or self._device_tables is None:
            return
        from ..ops.retained import patch_retained_tables
        if ring is None:
            # a ring-less caller (sync path, RO query) must still honor
            # the plane's in-flight scans — donating tables a parked
            # async walk is reading is the exact use-after-donate the
            # quarantine discipline exists to prevent
            ring = self.serving_ring
            own_slots = 0
        donate = ring is None or (ring.in_flight <= own_slots
                                  and not len(ring.quarantine))
        dev, _stats = patch_retained_tables(
            self._device_tables, base, device=self.device, donate=donate)
        self._device_tables = dev
        self.patch_flushes += 1

    # ---------------- staged scan pipeline (ISSUE 13) -----------------------

    def prepare_scan(self, queries: Sequence[Tuple[str, Sequence[str]]],
                     *, batch: Optional[int] = None) -> _ScanPrep:
        """Stage 0: tokenize (tenant, filter_levels) pairs into device
        filter probes. The ONE probe-construction definition — the sync
        path, the async plane and the benchmark all use it."""
        from ..ops.retained import FilterProbes
        from .matcher import _pow2_batch

        ct = self.refresh()
        if batch is None:
            batch = _pow2_batch(len(queries))
        roots = [ct.root_of(t) for t, _ in queries]
        filters = [f for _, f in queries]
        # ISSUE 17 satellite: the filter-probe twin of the publish-side
        # byte plane — raw filter bytes ship to device, the BLAKE2b
        # kernel hashes the literal lanes there, wildcard lanes ride the
        # kind grid. Same gate and fallback contract as device_tokenize:
        # rows the kernel can't hash are padding (-1) and fall back.
        from ..ops.tokenize import (device_tokenize_enabled,
                                    device_tokenize_filters)
        if device_tokenize_enabled():
            mirror, probes = device_tokenize_filters(
                filters, roots, max_levels=ct.max_levels, salt=ct.salt,
                batch=batch, device=self.device)
            return _ScanPrep(queries=list(queries), probes=probes,
                             roots=np.asarray(roots, dtype=np.int64),
                             lengths=mirror.lengths, batch=batch, ct=ct)
        tok = tokenize_filters(filters, roots,
                               max_levels=ct.max_levels, salt=ct.salt,
                               batch=batch)
        return _ScanPrep(queries=list(queries),
                         probes=FilterProbes.from_tokenized(
                             tok, device=self.device),
                         roots=np.asarray(roots, dtype=np.int64),
                         lengths=tok.lengths, batch=batch, ct=ct)

    def device_probes(self, queries: Sequence[Tuple[str, Sequence[str]]],
                      *, batch: Optional[int] = None):
        """Back-compat probe constructor: (probes, roots, lengths)."""
        prep = self.prepare_scan(queries, batch=batch)
        return prep.probes, list(prep.roots), prep.lengths

    def dispatch_scan(self, prep: _ScanPrep, *,
                      k_states: Optional[int] = None,
                      ring=None, own_slots: int = 0):
        """Stage 1: flush pending patches, enqueue the extras-aware walk.
        Returns ``(prep, RetainedScanResult)`` — the result is ENQUEUED,
        not synchronized, and ``prep`` may be a re-prep: a compaction
        swap landing between prep and dispatch (the async leg awaits
        ring admission in the gap) renumbers roots/salt, so the probes
        re-tokenize against the installed base."""
        from ..ops.retained import retained_walk_ext
        if self._compiled is not prep.ct:
            prep = self.prepare_scan(prep.queries, batch=prep.batch)
        self.flush_device(ring=ring, own_slots=own_slots)
        # snapshot the slot→topic mirror AT dispatch: later growth
        # reallocates the array, and a later compaction renumbers slots
        # entirely — emitted ids must expand against THIS world
        prep.recv = self._receiver_arr
        res = retained_walk_ext(self._device_tables, prep.probes,
                                k_states=k_states or self.k_states)
        trace.count("retain.scan.walks")
        return prep, res

    @staticmethod
    def fetch_scan(res):
        """Stage 2: the one true synchronization — writable host copies
        (escalation clears rescued rows in place)."""
        return (np.asarray(res.start), np.asarray(res.count),
                np.array(res.overflow))

    def walk_device(self, probes, *, k_states: Optional[int] = None):
        """Dispatch the retained walk on the current compiled tables
        (back-compat surface: returns (base ranges, overflow))."""
        from ..ops.retained import retained_walk_ext
        self.refresh()
        self.flush_device()
        res = retained_walk_ext(self._device_tables, probes,
                                k_states=k_states or self.k_states)
        return res.start, res.overflow

    # ---------------- expansion (stage 3) -----------------------------------

    def expand_scan(self, prep: _ScanPrep, fetched,
                    limit: Optional[int] = None) -> List[List[str]]:
        """ranges → retained topic strings: native/host escalation for
        overflow rows, extras-plane resolution, dead-slot filtering, and
        scan-bounded ``limit`` trimming — all against host mirrors."""
        queries = prep.queries
        nq = len(queries)
        base_r, ext_r, overflow = fetched
        base_r = base_r[:nq]
        ext_r = ext_r[:nq]
        overflow = np.array(overflow[:nq])    # writable: escalation clears
        lengths = np.asarray(prep.lengths)[:nq]
        roots_a = prep.roots[:nq]
        # the dispatch-time snapshot, NOT the live index: a compaction
        # landing mid-flight must not renumber under this expansion
        ct = prep.ct
        recv = getattr(prep, "recv", None)
        if recv is None:
            recv = self._receiver_arr
        from ..retained_plane.patched import RetainedPatchableTrie
        base = ct if isinstance(ct, RetainedPatchableTrie) else None
        kind_arr = ct.slot_kind if (base is not None
                                    and base.dead_slots) else None
        # a base with tombstones: expand up to ``cap`` slots a row, drop
        # the dead ones, trim back to ``limit``
        cap = None if limit is None else limit + (
            base.expansion_budget() if kind_arr is not None else 0)

        # native escalation: '+'-exploded rows resolve via the C++ DFS
        # over the same compiled tables. The walker reads the base's
        # subtree ranges, exhaustive while no patch-era slot exists
        # (tombstones only mark base slots dead, filtered below); with
        # patch-era extras the rows go to the exact Python oracle until
        # the next compaction
        native_map: Dict[int, tuple] = {}
        esc = np.nonzero(overflow & (lengths >= 0) & (roots_a >= 0))[0]
        if esc.size and (base is None or base.extra_live == 0):
            try:
                from .native_retained import match_rows_native
                sub_tok = tokenize_filters(
                    [list(queries[i][1]) for i in esc],
                    [int(roots_a[i]) for i in esc],
                    max_levels=ct.max_levels, salt=ct.salt)
                rr, rn, rovf = match_rows_native(
                    ct, sub_tok.tok_h1, sub_tok.tok_h2, sub_tok.tok_kind,
                    sub_tok.lengths, sub_tok.roots, limit=cap)
                for j, qi in enumerate(esc):
                    if not rovf[j]:
                        n = int(rn[j])
                        s0 = rr[j, :n, 0].astype(np.int64)
                        c0 = np.maximum(rr[j, :n, 1], 0).astype(np.int64)
                        if cap is not None and n:
                            cum = np.cumsum(c0)
                            c0 = np.clip(cap - (cum - c0), 0, c0)
                        native_map[int(qi)] = (s0, c0)
                        overflow[qi] = False
            except Exception:  # noqa: BLE001 — no compiler / load failure:
                pass    # rows stay on the (exact) oracle path

        starts = base_r[..., 0].astype(np.int64)
        counts = np.maximum(base_r[..., 1], 0).astype(np.int64)
        estarts = ext_r[..., 0].astype(np.int64)
        ecounts = np.maximum(ext_r[..., 1], 0).astype(np.int64)
        host_rows = overflow | (lengths < 0)
        row_mask = host_rows | (roots_a < 0)
        counts[row_mask] = 0
        ecounts[row_mask] = 0
        for qi in native_map:
            counts[qi] = 0      # grid contributes nothing for native rows
            ecounts[qi] = 0
        if cap is not None:
            # clip the CONCATENATED base+extras counts so expansion stops
            # at the cap (scan-bounded like RetainMessageMatchLimit)
            all_c = np.concatenate([counts, ecounts], axis=1)
            cum = np.cumsum(all_c, axis=1)
            all_c = np.clip(cap - (cum - all_c), 0, all_c)
            counts = all_c[:, :counts.shape[1]]
            ecounts = all_c[:, counts.shape[1]:]

        def _ragged(st, ct_):
            fc = ct_.ravel()
            total = int(fc.sum())
            if not total:
                return (np.empty(0, dtype=np.int64),
                        np.zeros(nq + 1, dtype=np.int64))
            offs = np.cumsum(fc) - fc
            flat = (np.arange(total, dtype=np.int64)
                    - np.repeat(offs, fc) + np.repeat(st.ravel(), fc))
            row_offs = np.concatenate(
                [np.zeros(1, np.int64), np.cumsum(ct_.sum(axis=1))])
            return flat, row_offs

        bslots, boffs = _ragged(starts, counts)
        eidx, eoffs = _ragged(estarts, ecounts)
        if eidx.size:
            extra_host = base.extra_list
            eslots = extra_host[eidx].astype(np.int64)
        else:
            eslots = eidx

        def _live(row):
            if kind_arr is not None and row.size:
                row = row[kind_arr[row] != CompiledTrie.SLOT_DEAD]
            if limit is not None and row.size > limit:
                row = row[:limit]
            return list(recv[row]) if row.size else []

        out: List[List[str]] = []
        n_oracle = 0
        for qi, (tenant_id, levels) in enumerate(queries):
            if roots_a[qi] < 0:
                out.append([])
                continue
            if qi in native_map:
                s0, c0 = native_map[qi]
                tot = int(c0.sum())
                o = np.cumsum(c0) - c0
                out.append(_live(np.arange(tot, dtype=np.int64)
                                 - np.repeat(o, c0) + np.repeat(s0, c0)))
                continue
            if host_rows[qi]:
                n_oracle += 1
                trie = self.tries.get(tenant_id)
                out.append(match_filter_host(trie, list(levels),
                                             limit=limit)
                           if trie is not None else [])
                continue
            out.append(_live(np.concatenate(
                [bslots[boffs[qi]:boffs[qi + 1]],
                 eslots[eoffs[qi]:eoffs[qi + 1]]])))
        # which path answered each row: the device walk, the native
        # walker, the host oracle
        trace.count("retain.rows.device", nq - len(native_map) - n_oracle)
        if native_map:
            trace.count("retain.rows.native", len(native_map))
        if n_oracle:
            trace.count("retain.rows.oracle", n_oracle)
        return out

    # ---------------- sync entry points -------------------------------------

    def match_batch(self, queries: Sequence[Tuple[str, Sequence[str]]],
                    *, batch: Optional[int] = None,
                    limit: Optional[int] = None) -> List[List[str]]:
        """(tenant, filter_levels) pairs → matched retained topic strings.

        ``limit`` bounds expansion per query (scan-bounded like the
        reference's RetainMessageMatchLimit): expired entries filtered by
        the caller may reduce the final result below the limit.
        """
        if not queries:
            return []
        prep = self.prepare_scan(queries, batch=batch)
        prep, res = self.dispatch_scan(prep)
        return self.expand_scan(prep, self.fetch_scan(res), limit=limit)

    def match(self, tenant_id: str, filter_levels: Sequence[str],
              limit: Optional[int] = None) -> List[str]:
        return self.match_batch([(tenant_id, filter_levels)], limit=limit)[0]

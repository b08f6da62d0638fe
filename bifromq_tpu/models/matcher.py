"""TpuMatcher: the full match plane — compile, walk on device, expand on host.

This is the component that stands in for the reference's
``SubscriptionCache`` → ``TenantRouteCache`` → ``TenantRouteMatcher`` pipeline
(bifromq-dist-worker .../cache/SubscriptionCache.java:59,
TenantRouteCache.java:65, TenantRouteMatcher.java:68): authoritative
subscription state lives in host-side per-tenant tries (fed by route
mutations); a compiled automaton snapshot serves batched match queries on
device; topics that exceed the fixed-shape walk (active-state overflow,
over-deep topics) fall back to the host oracle, mirroring the bounded-probe
fallback contract of the reference matcher.

Mutation → visibility (the TenantRouteCache.java:100-160 refresh-on-mutation
contract, re-designed for an immutable compiled automaton):

- Every mutation applies to the authoritative tries instantly (exact
  incarnation guards) and lands in a small **delta overlay** — per-tenant
  delta tries for adds plus a tombstone set for removes/supersedes — so it
  is visible to the *next* match call without recompiling anything.
- Serving walks the **base** compiled automaton (double-buffered device
  tables) and corrects the expansion with the overlay: tombstoned base
  matchings are suppressed, delta-trie matches are merged in, then fan-out
  caps apply to the merged set.
- A background **compaction** folds the overlay into a new base: the
  mutation log replays onto a shadow copy of the tries (so the compile
  reads a frozen snapshot while serving keeps mutating), the shadow
  compiles off-thread, and the serving thread swaps in the new tables and
  rebuilds the (now tiny) overlay from the log suffix. Staleness of the
  base is bounded by compile time; correctness never depends on it.
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from .. import trace
from ..utils import topic as topic_util
from .automaton import (
    CompiledTrie, GroupMatching, Matching, PatchableTrie, PatchFallback,
    compile_tries, patch_enabled, tokenize,
)
from .oracle import (
    PERSISTENT_SUB_BROKER_ID, UNCAPPED_FANOUT, MatchedRoutes, Route,
    SubscriptionTrie,
)


def _pow2_batch(n: int, floor: int = 16) -> int:
    """Snap a batch size up to a power of two: every distinct batch shape
    costs an XLA compile, so live traffic must reuse a small set of
    shapes."""
    b = floor
    while b < n:
        b *= 2
    return b


def _parse_levels(levels) -> List[str]:
    """Queries carry the raw topic — str or wire ``bytes`` (ISSUE 11
    byte plane: the serving path ships bytes to the tokenizer and only
    the rare fallback/overlay paths materialize level lists) — or a
    pre-parsed level sequence; normalize to a level-string list at the
    point of use."""
    if isinstance(levels, bytes):
        levels = levels.decode("utf-8")
    if isinstance(levels, str):
        return topic_util.parse(levels)
    return list(levels)


def _query_key(levels):
    """Cache/dedup key of a query's topic half: the raw string (or wire
    bytes) is its own key (no re-join, no tuple build); level lists
    keep the tuple form."""
    if isinstance(levels, (str, bytes)):
        return levels
    return tuple(levels)

# tombstone key: (full mqtt topic filter incl. any share prefix, receiver_url)
_TombKey = Tuple[str, Tuple[int, str, str]]


class _Prepared:
    """Stage-1 output (ISSUE 11): a tokenized + uploaded probe batch,
    built BEFORE ring admission so batch N+1's prep overlaps batch N's
    walk. Holds the base snapshot it tokenized against — the dispatch
    half re-preps iff a compaction swapped the base in the gap (roots
    and salt are per-snapshot)."""

    __slots__ = ("queries", "ct", "tok", "probes", "roots", "batch",
                 "tokenize_s")

    def __init__(self, **kw) -> None:
        for k, v in kw.items():
            setattr(self, k, v)


class _InFlight:
    """Captured dispatch state for one device batch (ISSUE 6 pipeline).

    The expansion step (sync or async-on-ready) must run against the
    SNAPSHOT the walk dispatched on — the base tables and the overlay
    dict *objects* captured here — never re-read ``self._base_ct``: a
    background compaction swapping mid-flight replaces the overlay dicts
    with the (empty) log-suffix rebuild, and expanding old-base slots
    with the new overlay would drop every mutation the compaction folded.
    Holding the old dict objects keeps them alive and still-mutating
    (pre-swap mutations land in them in place), which is exactly the
    state the old base needs.
    """

    __slots__ = ("queries", "ct", "dev", "tok", "roots", "res", "tomb",
                 "delta", "batch", "kernel", "fault", "dispatch_s",
                 "tokenize_s", "dev_expand_s", "peer_tab")

    def __init__(self, **kw) -> None:
        self.fault = None   # fired device FaultRule (ISSUE 7 chaos hook)
        self.dispatch_s = 0.0  # dispatch-stage seconds (ISSUE 8 profiler)
        self.tokenize_s = 0.0  # stage-1 prep seconds (ISSUE 11 profiler)
        self.dev_expand_s = 0.0  # device-expand enqueue (ISSUE 19)
        self.peer_tab = None     # PeerTable the expansion bucketed against
        for k, v in kw.items():
            setattr(self, k, v)


class _HostPairs:
    """Host view of one device-expanded batch (ISSUE 19): the compact
    (slot, row) pair buffers + peer buckets ``_fetch_walk`` read back,
    plus the in-flight result object for the lazy grid fetch that only
    buffer-truncated rows need."""

    __slots__ = ("slots", "rows", "row_offsets", "n_pairs", "trunc",
                 "peer_slots", "peer_rows", "peer_offsets", "res")

    def __init__(self, **kw) -> None:
        for k, v in kw.items():
            setattr(self, k, v)


def apply_log_op(tries: Dict[str, SubscriptionTrie], op: Tuple) -> None:
    """Apply ONE matcher log op to a tries dict — THE single definition
    of the op → trie semantics, shared by the shadow replay and the
    replication standby's authoritative-trie upkeep (ISSUE 12): the two
    sides must never drift, or standby host-oracle parity silently
    breaks."""
    if op[0] == "add":
        _, tenant, route = op
        tries.setdefault(tenant, SubscriptionTrie()).add(route)
    elif op[0] == "rm":
        _, tenant, matcher, url, inc = op
        trie = tries.get(tenant)
        if trie is not None:
            trie.remove(matcher, url, inc)
            if len(trie) == 0:
                del tries[tenant]


def _safe_hook(cb, what: str, *args) -> None:
    """Fire an optional observer hook without letting it poison the
    mutation/install path (ISSUE 12: delta/rebase emit chains)."""
    if cb is None:
        return
    try:
        cb(*args)
    except Exception:  # noqa: BLE001 — observers must not break serving
        logging.getLogger(__name__).exception("%s hook failed", what)


class TpuMatcher:
    def __init__(self, *, max_levels: int = 16, k_states: int = 32,
                 probe_len: int = 16, device=None,
                 auto_compact: bool = True,
                 compact_threshold: int = 2048,
                 max_intervals: int = 32,
                 match_cache: bool = True) -> None:
        self.max_levels = max_levels
        self.k_states = k_states
        self.probe_len = probe_len
        self.max_intervals = max_intervals
        self.device = device
        self.auto_compact = auto_compact
        self.compact_threshold = compact_threshold
        # authoritative state (exact guards; host fallback matches)
        self.tries: Dict[str, SubscriptionTrie] = {}
        # serving snapshot (double-buffered: swapped atomically, old tables
        # stay alive for in-flight dispatches)
        self._base_ct: Optional[CompiledTrie] = None
        self._device_trie = None
        # overlay since the base snapshot
        self._delta: Dict[str, SubscriptionTrie] = {}
        self._tomb: Dict[str, Set[_TombKey]] = {}
        self._overlay_n = 0
        # per-topic token-row cache (topics repeat — the reference's
        # TenantRouteCache bet); survives recompiles, cleared on salt change
        from .automaton import TokenCache
        self._tok_cache = TokenCache()
        # ISSUE 4 tentpole: match-RESULT cache plane in front of the device
        # walk — a repeated (tenant, topic) is a dict probe, not a
        # dispatch. Filter-aware invalidation lives in add/remove_route;
        # base rebuilds bump the generation (_install_base).
        from .matchcache import TenantMatchCache
        self.match_cache = (TenantMatchCache(scope="matcher")
                            if match_cache else None)
        # ISSUE 6: async dispatch ring (lazy — sync-only deployments never
        # pay for it); see models/pipeline.py for its sizes
        self._ring = None
        # ISSUE 7: per-device circuit breaker fed by device timeouts and
        # errors — open serves the exact host-oracle degraded path with
        # no dispatch at all, half-open admits ONE canary batch that
        # re-closes only on row parity with the oracle. Registered on
        # the process-global board so /metrics "fabric.breakers" and the
        # gossip health digest see it.
        from ..resilience.device import (DEVICE_BREAKERS,
                                         device_breaker_enabled)
        self.device_breaker = (DEVICE_BREAKERS.create()
                               if device_breaker_enabled() else None)
        # ISSUE 12 replication emit hooks (armed by DistWorkerCoProc):
        # on_delta(tenant, filter_levels, op, plan, fallback) fires per
        # applied mutation with the captured PatchPlan (None when the op
        # went to the overlay); on_rebase(salt, reason) fires on every
        # COMPILED base install — arenas renumbered, the delta stream
        # must re-anchor. _replaying suppresses emission while a replay
        # (log suffix / reset-from-KV rebuild) re-applies ops that were
        # already streamed (or are covered by an anchor).
        self.on_delta = None
        self.on_rebase = None
        self._replaying = False
        # mutation log since the shadow copy last synced; shadow is the
        # frozen snapshot source for off-thread compiles
        self._log: List[Tuple] = []
        self._shadow: Dict[str, SubscriptionTrie] = {}
        self._swap_lock = threading.Lock()
        self._pending_swap = None   # set by the compact thread
        self._compact_done = False
        self._compact_thread: Optional[threading.Thread] = None
        # ISSUE 10: background patch-scatter warm (joinable by tests)
        self._scatter_warm_thread: Optional[threading.Thread] = None
        self.compile_count = 0      # full compiles (observability/tests)
        self.compile_time_s = 0.0   # cumulative wall time in compiles
        # ISSUE 19 device fan-out: slot→delivery-peer table cache, keyed
        # on base-snapshot identity (rebuilt per compile, NEVER per patch
        # flush — slots patched in after the build land in the UNKNOWN
        # bucket and get exact host grouping, so staleness is a fast-path
        # miss, not a correctness risk). last_expanded is the observability
        # surface for the most recent device-bucketed batch (bench/tests).
        self._peer_cache: Optional[Tuple] = None
        self.last_expanded = None
        # ISSUE 9 patch-plane accounting (mutations folded into the base
        # in place vs ops that fell back to the overlay)
        self.patch_count = 0        # mutations applied as in-place patches
        self.patch_fallbacks = 0    # ops the patcher refused (overlay'd)
        self.patch_flushes = 0      # device patch-update rounds
        self.patch_host_s = 0.0     # cumulative host plan+arena time
        self.patch_device_s = 0.0   # cumulative device update time
        # ISSUE 8 compile-event ledger: what triggered the build the
        # NEXT _install_base lands (first_base / threshold / forced /
        # refresh), and how long that compile ran
        self._compile_reason = "first_base"
        self._last_compile_s = 0.0
        # ISSUE 3: compile count/time surface under /metrics "device"
        from ..obs import OBS
        OBS.device.register_matcher(self)

    def clone_empty(self) -> "TpuMatcher":
        """A fresh matcher with the same configuration — the reset-from-KV
        rebuild target (subclasses override to preserve their plumbing)."""
        return TpuMatcher(max_levels=self.max_levels, k_states=self.k_states,
                          probe_len=self.probe_len, device=self.device,
                          auto_compact=self.auto_compact,
                          compact_threshold=self.compact_threshold,
                          max_intervals=self.max_intervals,
                          match_cache=self.match_cache is not None)

    @classmethod
    def from_tries(cls, tries: Dict[str, SubscriptionTrie],
                   **kwargs) -> "TpuMatcher":
        """Seed a matcher from pre-built tries WITHOUT replaying every
        route through the mutation log/overlay (benchmark + tier-2 gate bulk
        loads). The trie objects are SHARED between authoritative and
        shadow state: later add/remove_route traffic stays correct (the
        shadow replay re-applies each op idempotently), but the compile
        thread then reads live tries — serve-only or serially-mutating
        workloads only."""
        m = cls(**kwargs)
        m.tries = tries
        m._shadow = tries
        m.refresh()
        return m

    # ---------------- mutation side (≈ batchAddRoute/batchRemoveRoute) -----

    def add_route(self, tenant_id: str, route: Route) -> bool:
        trie = self.tries.setdefault(tenant_id, SubscriptionTrie())
        created, effective = trie.add_effective(route)
        if not effective:  # stale-incarnation upsert: nothing changed
            return False
        op = ("add", tenant_id, route)
        self._log.append(op)
        plan, fallback = self._fold_op(op)
        if self.match_cache is not None:
            # filter-aware (ISSUE 4): exact filters evict one topic key,
            # wildcard filters bump the tenant epoch
            self.match_cache.invalidate(tenant_id,
                                        route.matcher.filter_levels)
        self._emit_delta(tenant_id, route.matcher.filter_levels, op,
                         plan, fallback)
        self._maybe_compact()
        return created

    def remove_route(self, tenant_id: str, matcher, receiver_url,
                     incarnation: int = 0) -> bool:
        trie = self.tries.get(tenant_id)
        if trie is None:
            return False
        removed = trie.remove(matcher, receiver_url, incarnation)
        if not removed:
            return False
        if len(trie) == 0:
            del self.tries[tenant_id]
        op = ("rm", tenant_id, matcher, receiver_url, incarnation)
        self._log.append(op)
        plan, fallback = self._fold_op(op)
        if self.match_cache is not None:
            self.match_cache.invalidate(tenant_id, matcher.filter_levels)
        self._emit_delta(tenant_id, matcher.filter_levels, op, plan,
                         fallback)
        self._maybe_compact()
        return True

    # ---------------- incremental patching (ISSUE 9 tentpole) --------------

    def _fold_op(self, op: Tuple):
        """Patch-first fold of one log op, with PatchPlan capture when a
        delta subscriber is armed (ISSUE 12): the physical write set the
        leader just executed is EXACTLY what a byte-identical replica
        applies — no second descent, no hashing. Returns
        ``(plan, fallback)``; a declined op records into the overlay and
        ships op-only (a fallback may still carry a PARTIAL plan: nodes
        allocated before the patcher refused stay in the arena as
        garbage, and the replica mirrors them to keep byte parity)."""
        base = self._base_ct
        record = (self.on_delta is not None and not self._replaying
                  and isinstance(base, PatchableTrie))
        if record:
            base.begin_plan()
        try:
            ok = self._try_patch(op)
        finally:
            plan = base.take_plan() if record else None
        if not ok:
            # no patchable base (or the op fell back): serve it from the
            # delta overlay until the next compaction folds it in
            self._overlay_record(op)
        if plan is not None and plan.empty and not ok:
            plan = None
        return plan, not ok

    def _emit_delta(self, tenant_id, filter_levels, op, plan,
                    fallback) -> None:
        if not self._replaying:
            _safe_hook(self.on_delta, "delta emit", tenant_id,
                       filter_levels, op, plan, fallback)

    def _patching_enabled(self) -> bool:
        return patch_enabled()

    def _group_members(self, tenant_id: str, matcher) -> dict:
        """The authoritative surviving member set for a shared-group op —
        the patcher replaces the whole GroupMatching slot with it (group
        member churn is a pure host-side object swap, zero device
        traffic)."""
        trie = self.tries.get(tenant_id)
        node = trie._root if trie is not None else None
        for level in matcher.filter_levels:
            if node is None:
                return {}
            node = node.children.get(level)
        if node is None:
            return {}
        gkey = (int(matcher.type), matcher.group or "")
        return dict(node.groups.get(gkey, {}))

    def _patch_targets(self, tenant_id: str) -> list:
        """The PatchableTrie arena(s) a mutation for this tenant folds
        into — the single-chip base itself; the mesh subclass routes to
        the tenant's shard(s) (every shard for a replicated hot tenant).
        Empty when there is nothing to patch (no base yet, kill-switch,
        non-patchable compile target)."""
        base = self._base_ct
        if base is None or not isinstance(base, PatchableTrie) \
                or not self._patching_enabled():
            return []
        return [base]

    def _try_patch(self, op: Tuple) -> bool:
        """Fold one log op straight into the installed base arenas.

        Returns False when there is nothing to patch (no base yet, env
        kill-switch) or the patcher declined (``PatchFallback``) — the
        caller then records the op into the overlay, exactly the
        pre-patching serving path. A multi-target fold (replicated mesh
        tenant) that declines mid-way is safe: the patch methods are
        find-or-append idempotent and the overlay record supersedes the
        partially-patched copies exactly like a base copy.
        """
        targets = self._patch_targets(op[1])
        if not targets:
            return False
        with trace.span("patch.host") as sp:
            ok = self._fold_into(op, targets)
        if ok:
            self.patch_count += 1
            self.patch_host_s += sp.duration_s
        else:
            self.patch_fallbacks += 1
        return ok

    def _fold_into(self, op: Tuple, targets) -> bool:
        from ..types import RouteMatcherType
        try:
            if op[0] == "add":
                _, tenant_id, route = op
                gm = None
                if route.matcher.type != RouteMatcherType.NORMAL:
                    gm = self._group_members(tenant_id, route.matcher)
                for base in targets:
                    base.patch_add(tenant_id, route, group_members=gm)
            else:
                _, tenant_id, matcher, url, _inc = op
                gm = None
                if matcher.type != RouteMatcherType.NORMAL:
                    gm = self._group_members(tenant_id, matcher)
                for base in targets:
                    base.patch_remove(tenant_id, matcher, url,
                                      group_members=gm)
        except PatchFallback:
            return False
        return True

    def _flush_patches(self, own_slots: int = 0) -> None:
        """Ship accumulated host patches to device as narrow scatter
        updates (coalesced: at most one flush per dispatch, however many
        mutations landed since). Functional update by default — the old
        tables stay alive for in-flight dispatches; when nothing else is
        in flight the tables are DONATED so XLA updates them in place
        with no table copy at all. ``own_slots`` is the ring slots the
        CALLER itself holds (the async leg acquires before dispatching,
        so its own not-yet-dispatched slot is counted in ``in_flight``
        but provably isn't a reader of the old tables yet)."""
        base = self._base_ct
        if not isinstance(base, PatchableTrie) or not base.dirty \
                or self._device_trie is None:
            return
        from ..ops.match import patch_device_trie
        ring = self._ring
        # donation exclusivity rides the matcher's single-serving-thread
        # contract (the same one the overlay dicts and _apply_pending_swap
        # already assume): only the serving thread flushes, always BEFORE
        # its own dispatch, and the sync/async legs both synchronize their
        # walks (incl. the escalation re-walk) without yielding between
        # slot release and expansion — so in_flight<=own_slots plus an
        # empty quarantine (timed-out/cancelled walks still reading the
        # tables park their arrays there) proves no device reader of the
        # old tables exists. Mutation-side callers never flush.
        donate = ring is None or (ring.in_flight <= own_slots
                                  and not len(ring.quarantine))
        with trace.span("patch.flush") as sp:
            dev, stats = patch_device_trie(self._device_trie, base,
                                           device=self.device,
                                           donate=donate)
        self._device_trie = dev
        dt = sp.duration_s
        self.patch_flushes += 1
        self.patch_device_s += dt
        # ISSUE 9: every flush lands in the compile ledger's patch stream
        # (reason / mutations coalesced / rows touched / bytes shipped) so
        # churn reads as narrow updates, not invisible work
        from ..obs import OBS
        OBS.profiler.ledger.record_patch(
            reason="+".join(stats["full"]) if stats["full"] else "rows",
            mutations=stats["ops"], rows=stats["rows"],
            bytes_shipped=stats["bytes"], duration_s=dt)
        if stats["reshaped"]:
            # arena growth / edge regrow changed a table shape: the walk
            # re-traces. The triggering batch inherently pays its own
            # shape's compile, but the OTHER warm shapes (pipeline
            # floors) compile on a background thread — same off-thread
            # warming a compaction install gets from the compile thread.
            # Against throwaway zero tables of the same shapes: the jit
            # cache keys on avals, and the live tables may be consumed
            # by the next donated flush while this thread still runs.
            import jax
            import jax.numpy as jnp
            avals = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), dev)
            device = self.device

            def warm():
                self._warm_walk(base, jax.tree_util.tree_map(
                    lambda a: jax.device_put(
                        jnp.zeros(a.shape, a.dtype), device), avals))
            threading.Thread(target=warm, name="tpu-matcher-warm",
                             daemon=True).start()

    def _overlay_record(self, op: Tuple) -> None:
        """Fold one log op into the serving overlay (delta tries + tombstones).

        The single definition of the overlay semantics: an add supersedes any
        base copy (tombstone) and supplies the live version via the delta
        trie; a remove tombstones the base copy and retracts any delta copy.
        """
        if op[0] == "add":
            _, tenant, route = op
            self._delta.setdefault(tenant, SubscriptionTrie()).add(route)
            self._tomb.setdefault(tenant, set()).add(
                (route.matcher.mqtt_topic_filter, route.receiver_url))
        else:
            _, tenant, matcher, url, inc = op
            d = self._delta.get(tenant)
            if d is not None:
                d.remove(matcher, url, inc)
            self._tomb.setdefault(tenant, set()).add(
                (matcher.mqtt_topic_filter, url))
        self._overlay_n += 1

    # ---------------- compilation / compaction -----------------------------

    @property
    def overlay_size(self) -> int:
        return self._overlay_n

    def _replay_log_into_shadow(self) -> None:
        for op in self._log:
            apply_log_op(self._shadow, op)
        self._log.clear()

    def _compile_shadow(self) -> Tuple[CompiledTrie, object]:
        import time as _time
        t0 = _time.perf_counter()
        self.compile_count += 1
        ct = compile_tries(self._shadow, max_levels=self.max_levels,
                           probe_len=self.probe_len)
        if self._patching_enabled():
            # ISSUE 9: pad the arenas with pow2 growth headroom so the
            # serving base accepts in-place patches without reshaping
            # (the padded shape is what jit compiles against)
            ct = PatchableTrie(ct)
        from ..ops.match import DeviceTrie  # deferred: keeps jax optional
        dev = DeviceTrie.from_compiled(ct, device=self.device)
        self._warm_walk(ct, dev)
        self._last_compile_s = _time.perf_counter() - t0
        self.compile_time_s += self._last_compile_s
        return ct, dev

    def _warm_walk(self, ct: CompiledTrie, dev) -> None:
        """Pre-compile the serving walk for this table's shapes at the
        smallest serving batches: 16 (the _pow2_batch floor) and, once
        the async ring has served, the shallow-queue latency floor too —
        the idle-broker single-publish shape must not pay a first-use
        compile on the serving path.

        XLA re-compiles whenever the table SHAPES change, and an
        uncompiled walk on the serving path delays the first match by
        seconds — enough to expire a short-MESSAGE_EXPIRY will that fired
        right before it. Warming here (mutation-triggered background
        compile path) keeps the publish path jit-warm."""
        from ..ops.match import Probes, walk_routes, walk_routes_donated
        from .pipeline import BASE_FLOOR, MIN_FLOOR
        kw = dict(probe_len=ct.probe_len, k_states=self.k_states,
                  max_intervals=self.max_intervals)
        # warm exactly the (batch, walk) pairs _walk_primary will
        # select: the sync floor always; once the async ring has
        # actually served (self._ring exists), ALSO the shallow-queue
        # latency floor and the busy-ring throughput floor on the
        # pipeline's donated walk — a live pipeline must stay
        # jit-warm across recompiles, but sync-only deployments (and the
        # test suite) never pay for shapes they don't serve. The very
        # first shallow publish of a process compiles its floor lazily
        # instead.
        def sync_fn(d, p):
            return walk_routes(d, p, esc_k=0, **kw)

        def pipe_fn(d, p):
            return walk_routes_donated(d, p, esc_k=0, **kw)
        warm = [(BASE_FLOOR, sync_fn)]
        if self._ring is not None:
            warm += [(BASE_FLOOR, pipe_fn),
                     (MIN_FLOOR, pipe_fn)]
        try:
            for b, fn in warm:
                tok = tokenize([["warm"]], [-1], max_levels=ct.max_levels,
                               salt=ct.salt, batch=b)
                res = fn(dev, Probes.from_tokenized(tok,
                                                    device=self.device))
                np.asarray(res.overflow)
        except Exception:  # noqa: BLE001 — the first serve compiles lazily
            from ..utils.metrics import warmup_failed
            warmup_failed("serving-walk jit")
            return
        # ISSUE 10 satellite (ROADMAP PR 9 follow-up (c)): pre-warm the
        # patch-scatter jits too, so the FIRST churn flush stops paying
        # its one-off trace on the serving path. On a DELAYED background
        # thread: the walk warm gates first serving and must stay inline,
        # but churn starts long after install — ~0.6s of scatter traces
        # competing with a cold process's first serves (workers hold 1s
        # RPC deadlines across them) would cost more than they save, so
        # the warm waits out the cold-start window first. Deduped per
        # shape class inside warm_patch_scatter, so multi-range workers
        # compile each class once.
        from ..ops import match as _om
        if isinstance(ct, PatchableTrie) \
                and ct.node_tab.shape[0] >= _om.WARM_SCATTER_MIN_ROWS:
            from ..utils.env import env_float
            # capture ONLY shape classes + device: closing over self
            # would pin the matcher (and its device breaker on the
            # process-global board) for the whole delay window, and
            # holding the live tables would race a donated flush
            # consuming them mid-delay
            device = self.device
            shapes = _om.scatter_warm_shapes(dev)
            scatter_warm_fn = _om.warm_patch_scatter

            def _warm_scatters():
                try:
                    time.sleep(max(0.0, env_float(
                        "BIFROMQ_SCATTER_WARM_DELAY_S", 1.0)))
                    scatter_warm_fn(shapes, device=device)
                except Exception:  # noqa: BLE001 — first flush traces
                    from ..utils.metrics import warmup_failed
                    warmup_failed("patch-scatter")
            t = threading.Thread(target=_warm_scatters,
                                 name="tpu-matcher-warm-scatter",
                                 daemon=True)
            self._scatter_warm_thread = t
            t.start()

    def refresh(self) -> CompiledTrie:
        """Blocking quiesce: every pending mutation lands in the base.

        ISSUE 9: when the base is patchable and every pending log op was
        already folded in as a patch (the overlay is empty), quiesce is
        just a shadow sync + device flush — NO rebuild. The full compile
        survives for cold start, overlay-resident ops, and mesh bases.
        """
        self.drain()
        if self._base_ct is None:
            self._compile_reason = "first_base"
            self._replay_log_into_shadow()
            ct, dev = self._compile_shadow()
            self._install_base(ct, dev)
        elif self._log:
            if self._overlay_n == 0 and self._base_patchable():
                # base already exact (patch-first path): sync the shadow
                # so the next compaction replays from the right snapshot
                self._replay_log_into_shadow()
            else:
                self._compile_reason = "refresh"
                self._replay_log_into_shadow()
                ct, dev = self._compile_shadow()
                self._install_base(ct, dev)
        self._flush_patches()
        return self._base_ct

    def _base_patchable(self) -> bool:
        """Is the INSTALLED base exact under the patch-first path (so a
        quiesce needs no rebuild)? The mesh subclass answers for its
        per-shard arenas."""
        return isinstance(self._base_ct, PatchableTrie)

    @staticmethod
    def _base_salt(ct) -> object:
        """Salt fingerprint of a base snapshot — works for the single-chip
        CompiledTrie and the mesh's ShardedTables (per-shard salts)."""
        salt = getattr(ct, "salt", None)
        if salt is not None:
            return salt
        shards = getattr(ct, "compiled", None)
        if shards is not None:
            return tuple(getattr(s, "salt", None) for s in shards)
        return None

    def _install_base(self, ct: CompiledTrie, dev) -> None:
        prev = self._base_ct
        self._base_ct = ct
        self._device_trie = dev
        # mutations not in this base = the log suffix. ISSUE 9: fold them
        # in as PATCHES on the fresh arenas (the patch methods are
        # find-or-append idempotent, so replaying an op that raced the
        # compile snapshot is safe); only ops the patcher declines land
        # in the overlay. Dirty rows flush on the next dispatch.
        self._delta = {}
        self._tomb = {}
        self._overlay_n = 0
        for op in self._log:
            if not self._try_patch(op):
                self._overlay_record(op)
        # ISSUE 6 satellite (PR-4 follow-up): a PURE compaction — folding
        # the overlay into a new base with the SAME salt — produces an
        # automaton equivalent to base ⊕ overlay, so every cached result
        # stays exact: mutations already invalidated their keys when they
        # were applied (add/remove_route), and in-flight puts racing a
        # mutation are defeated by the per-tenant seq. Only a SALT change
        # (hash-collision recompile) or the first install still bumps the
        # global generation; reset-from-KV rebuilds through clone_empty
        # (fresh cache) and never reaches here.
        bumped = False
        if self.match_cache is not None:
            if prev is None or self._base_salt(prev) != self._base_salt(ct):
                self.match_cache.bump_all()
                bumped = True
        self._ledger_record(ct, bumped)
        # ISSUE 12: a compiled install renumbers the arenas (even a pure
        # same-salt compaction re-runs the DFS) — the delta stream must
        # re-anchor so replicas resync instead of scattering stale rows
        _safe_hook(self.on_rebase, "rebase", self._base_salt(ct),
                   self._compile_reason)

    def _ledger_record(self, ct, bumped: bool) -> None:
        """ISSUE 8: stamp this install into the compile-event ledger so
        rebuild storms are attributable — trigger reason, compile wall
        time, salt, table bytes, and whether the
        match-cache generation was bumped. The byte derivation
        lives in one place (obs.capacity.record_compile_event — bench
        builds stamp through it too)."""
        from ..obs.capacity import record_compile_event
        record_compile_event(ct, reason=self._compile_reason,
                             duration_s=self._last_compile_s,
                             salt=self._base_salt(ct),
                             generation_bumped=bumped)

    def _patch_frag_pending(self) -> bool:
        """ISSUE 9 compaction trigger: dead+garbage slots crossed the
        tombstone threshold. Steady patching churn below it (and ANY
        volume of pure adds, which never fragment) compacts never."""
        base = self._base_ct
        return isinstance(base, PatchableTrie) and base.frag_pending()

    def _maybe_compact(self, force: bool = False) -> None:
        # trigger on the FIRST mutation too (base is None): the first base
        # builds in the background so the first publish finds trie tables
        # AND the walk jit already warm, instead of paying both compiles
        # inline (the reference's refresh-on-mutation contract,
        # TenantRouteCache.java:100). ``force`` recompiles regardless of
        # overlay size (shard re-placement: new pins need a new build).
        # ISSUE 9: with patch-first mutations the overlay stays empty and
        # the threshold trigger goes quiet; compaction becomes the
        # FRAGMENTATION fallback (tombstone/garbage ratio) instead of the
        # every-2048-mutations rebuild.
        frag = self.auto_compact and self._patch_frag_pending()
        if (self._compact_thread is not None
                or (not force and not frag
                    and (not self.auto_compact
                         or (self._base_ct is not None
                             and self._overlay_n < self.compact_threshold)))):
            self._apply_pending_swap()
            return
        # ledger attribution (ISSUE 8): why this build is happening
        if self._base_ct is None:
            self._compile_reason = "first_base"
        elif force:
            self._compile_reason = "forced"
        elif self._overlay_n >= self.compact_threshold:
            self._compile_reason = "threshold"
        else:
            self._compile_reason = "frag"
        # snapshot: fold the log into the shadow NOW (serving thread, cheap —
        # O(log)); the compile thread then reads only the frozen shadow
        self._replay_log_into_shadow()

        def work():
            try:
                result = self._compile_shadow()
            except Exception:  # noqa: BLE001 — must not wedge compaction
                import logging
                logging.getLogger(__name__).exception(
                    "background compaction failed; will retry")
                result = None
            with self._swap_lock:
                self._pending_swap = result
                self._compact_done = True

        self._compact_done = False
        t = threading.Thread(target=work, name="tpu-matcher-compact",
                             daemon=True)
        self._compact_thread = t
        t.start()

    def _apply_pending_swap(self) -> None:
        with self._swap_lock:
            pending, self._pending_swap = self._pending_swap, None
            done = self._compact_done
        if pending is not None:
            self._install_base(*pending)
        if done:
            # thread finished (successfully or not): allow the next compact
            self._compact_thread = None
            self._compact_done = False

    def drain(self) -> None:
        """Wait for any in-flight compaction and apply its result."""
        t = self._compact_thread
        if t is not None:
            t.join()
        self._apply_pending_swap()

    @property
    def compiled(self) -> CompiledTrie:
        return self.refresh()

    @property
    def device_trie(self):
        self.refresh()
        return self._device_trie

    # ---------------- query side (≈ SubscriptionCache.get) -----------------

    def match_batch(self, queries: Sequence[Tuple[str, Sequence[str]]],
                    *, max_persistent_fanout: int = UNCAPPED_FANOUT,
                    max_group_fanout: int = UNCAPPED_FANOUT,
                    batch: Optional[int] = None,
                    stats: Optional[dict] = None,
                    **device_kw) -> List[MatchedRoutes]:
        """The cache-plane front-end (ISSUE 4, ≈ SubscriptionCache.get →
        TenantRouteCache): per-query cache probe, then in-batch dedup so N
        identical (tenant, topic) rows walk ONCE — only the unique misses
        reach ``_match_batch_device``, so hits also shrink the padded
        device batch. Cached/fanned-out results are shared objects and
        must be treated read-only by callers (the established contract of
        the dist pub cache)."""
        if not queries:
            return []
        cache = self.match_cache
        if cache is None:
            return self._match_batch_device(
                queries, max_persistent_fanout=max_persistent_fanout,
                max_group_fanout=max_group_fanout, batch=batch,
                stats=stats, **device_kw)
        # fold any finished background compaction in BEFORE probing: its
        # generation bump must land before this batch's token snapshots,
        # not mid-walk (which would refuse every put of the batch)
        self._apply_pending_swap()
        caps = (max_persistent_fanout, max_group_fanout)
        out, uniq, uniq_queries, miss_rows, tokens = \
            self._frontend_probe(queries, caps)
        if uniq_queries:
            res = self._match_batch_device(
                uniq_queries, max_persistent_fanout=max_persistent_fanout,
                max_group_fanout=max_group_fanout, batch=batch,
                stats=stats, **device_kw)
            self._frontend_fill(out, res, uniq, miss_rows, tokens, caps)
        self._frontend_metrics(len(queries), uniq_queries, miss_rows)
        return out

    def _frontend_probe(self, queries, caps):
        """Cache probe + in-batch dedup (the ISSUE 4 front-end, shared by
        the sync and async serving paths): returns (out, uniq, uniq_queries,
        miss_rows, tokens) where ``out`` holds the hits and ``tokens`` the
        pre-match invalidation snapshots — taken BEFORE any walk is issued,
        so a mutation landing mid-match (the async path genuinely awaits
        across the event loop) defeats the store."""
        cache = self.match_cache
        out: List[Optional[MatchedRoutes]] = [None] * len(queries)
        uniq: Dict[Tuple[str, Tuple[str, ...]], int] = {}
        uniq_queries: List[Tuple[str, Sequence[str]]] = []
        miss_rows: List[Tuple[int, int]] = []   # (query idx, unique pos)
        for qi, (tenant_id, levels) in enumerate(queries):
            key = _query_key(levels)
            m = cache.get(tenant_id, key, caps)
            if m is not None:
                out[qi] = m
                continue
            uk = (tenant_id, key)
            pos = uniq.get(uk)
            if pos is None:
                pos = uniq[uk] = len(uniq_queries)
                uniq_queries.append((tenant_id, levels))
            miss_rows.append((qi, pos))
        tokens = ({t: cache.token(t) for t in {q[0] for q in uniq_queries}}
                  if uniq_queries else {})
        return out, uniq, uniq_queries, miss_rows, tokens

    def _frontend_fill(self, out, res, uniq, miss_rows, tokens, caps):
        cache = self.match_cache
        for (tenant_id, key), pos in uniq.items():
            cache.put(tenant_id, key, caps, res[pos], tokens[tenant_id])
        for qi, pos in miss_rows:
            out[qi] = res[pos]

    def _frontend_metrics(self, n_queries, uniq_queries, miss_rows):
        # global section totals: ONE locked inc per batch, not per row.
        # Per-tenant OBS hit rates are fed by the PUB plane alone
        # (dist/service.py) — recording both planes into one window made
        # the /tenants number interpretable as neither.
        from ..utils.metrics import MATCH_CACHE
        MATCH_CACHE.inc(self.match_cache.scope, "hits",
                        n_queries - len(miss_rows))
        MATCH_CACHE.inc(self.match_cache.scope, "misses", len(miss_rows))
        trace.count("match.cache.lookups", n_queries)
        trace.count("match.cache.hits", n_queries - len(miss_rows))
        if uniq_queries:
            MATCH_CACHE.record_dedup(len(uniq_queries),
                                     len(miss_rows) - len(uniq_queries))
        # ISSUE 8: the profiler's cache-bypass / dedup-savings counters
        # (rows that never reached the device) — three int adds
        from ..obs import OBS
        OBS.profiler.record_frontend(
            n_queries, n_queries - len(miss_rows),
            len(miss_rows) - len(uniq_queries))

    # ---------------- async device pipeline (ISSUE 6 tentpole) -------------

    def _pipeline_ring(self):
        if self._ring is None:
            from .pipeline import DispatchRing
            self._ring = DispatchRing()
            from ..obs import OBS
            OBS.device.register_ring(self._ring)
        return self._ring

    async def drain_device(self, timeout_s: Optional[float] = None) -> bool:
        """Graceful drain (ISSUE 7): wait bounded for in-flight device
        batches to retire, then sweep the quarantine. Shutdown and
        compaction call this so a slot mid-walk finishes (or is given up
        on) instead of being torn down under the device. Returns whether
        the ring actually went idle."""
        ring = self._ring
        if ring is None:
            return True
        from ..resilience.device import drain_timeout_s
        if timeout_s is None:
            timeout_s = drain_timeout_s()
        idle = await ring.wait_idle(timeout_s)
        ring.quarantine.sweep()
        return idle

    async def match_batch_async(self, queries, *,
                                max_persistent_fanout: int = UNCAPPED_FANOUT,
                                max_group_fanout: int = UNCAPPED_FANOUT,
                                batch: Optional[int] = None,
                                stats: Optional[dict] = None
                                ) -> List[MatchedRoutes]:
        """Pipelined serving path: same results as ``match_batch``, but
        the device walk is dispatched through the bounded in-flight ring
        and awaited on READINESS — batch N+1 tokenizes and enqueues while
        batch N is still walking, and the event loop keeps serving between
        readiness polls instead of blocking inside ``device_get``.

        ``stats`` (optional dict) receives ``acquire_s``: the
        ring-acquire wait inside this call (the ``device.acquire``
        span's time less the prep it overlaps). A caller attributing
        device cost (the dist worker's ``match.device`` span and its
        per-tenant SLO shares) takes it off its own span, which under an
        overlapped pipeline also counts that wait — so THIS batch's
        match cost is cache probe + dispatch+ready+fetch + host
        expansion and cache fill, the same work the sync path's wall
        clock covers: what the "device" stage histograms measure.
        ``stats["batch_share"]`` is this call's share of the rows of the
        device batch that served it (1.0 alone; callers waiting at ring
        admission leave as one batch). ``stats["degraded"]``
        carries the reason when the batch was served from the host
        oracle (ISSUE 7: breaker open, watchdog timeout, device error)
        so the worker can emit MATCH_DEGRADED events without a raising
        boundary.
        """
        if not queries:
            return []
        caps = (max_persistent_fanout, max_group_fanout)
        cache = self.match_cache
        if cache is not None:
            self._apply_pending_swap()
            out, uniq, uniq_queries, miss_rows, tokens = \
                self._frontend_probe(queries, caps)
        else:
            out = [None] * len(queries)
            uniq_queries = list(queries)
        if uniq_queries:
            res, degraded, acquire_s, share = \
                await self._device_serve_async(
                    uniq_queries, batch, max_persistent_fanout,
                    max_group_fanout)
            if cache is not None:
                self._frontend_fill(out, res, uniq, miss_rows, tokens,
                                    caps)
            else:
                out = res
            if stats is not None:
                # the ring-acquire wait is queue time under a saturated
                # pipeline, not match cost — the caller's span leaves it
                # out of the "device" stage and the per-tenant
                # attribution feeding the noisy detector
                stats["acquire_s"] = acquire_s
                # this caller's rows over the device batch's: callers
                # that shared one walk split its cost between them
                stats["batch_share"] = share
                if degraded is not None:
                    stats["degraded"] = degraded
        if cache is not None:
            self._frontend_metrics(len(queries), uniq_queries, miss_rows)
        return out

    async def _device_serve_async(self, uniq_queries, batch,
                                  max_persistent_fanout, max_group_fanout):
        """One caller's way through ring admission: it waits in line for
        a prep ticket and leaves with whoever waits there with it, as ONE
        device batch (``_serve_merged``), then takes its own rows.

        Returns ``(results, degraded_reason, acquire_s, share)``:
        ``degraded_reason`` as ``_serve_batch`` gives it; ``acquire_s``
        is this caller's wait from entry to the batch's slot admission
        (queue time, which the caller subtracts from its device-time
        accounting; it starts no deadline and no watchdog); ``share`` is
        its rows over the batch's. Cancelled while in line it just
        leaves; cancelled after its batch left, its rows are dropped and
        the walk goes on for the others."""
        from .pipeline import Caller
        ring = self._pipeline_ring()
        me = Caller(uniq_queries,
                    (max_persistent_fanout, max_group_fanout), batch,
                    self._serve_merged)
        ring.enter(me)
        try:
            return await me.fut
        except asyncio.CancelledError:
            ring.leave(me)
            raise

    async def _serve_merged(self, ring, merged) -> None:
        """ONE device batch over the callers' rows, concatenated in entry
        order; each caller's future gets its own slice, resolved in entry
        order. The batch is one unit above the device too: one breaker
        admission and settle, one ``BatchRecord``, one quarantine entry,
        and on any fault every caller in it is served by the oracle."""
        callers = ring.board(merged)
        if not callers:         # everybody in line gave up meanwhile
            return
        queries = (callers[0].queries if len(callers) == 1
                   else [q for c in callers for q in c.queries])
        merged.admitted = time.monotonic()
        try:
            rows, reason = await self._serve_batch(
                ring, merged, queries, callers[0].batch, *callers[0].caps)
        except Exception as e:  # noqa: BLE001 — the callers' to see
            for c in callers:
                if not c.fut.done():
                    c.fut.set_exception(e)
            return
        off = 0
        for c in callers:
            n = len(c.queries)
            if not c.fut.done():
                c.fut.set_result((
                    rows[off:off + n], reason,
                    max(0.0, merged.admitted - c.entered
                        - merged.tokenize_s),
                    n / len(queries)))
            off += n

    async def _serve_batch(self, ring, merged, uniq_queries, batch,
                           max_persistent_fanout, max_group_fanout):
        """The failure-bounded device leg of the async path (ISSUE 7).

        Returns ``(results, degraded_reason)`` —
        ``degraded_reason`` is None when the device served, else one of
        ``breaker`` (circuit open: dispatch skipped entirely), ``timeout``
        (watchdog fired: the ring slot was reclaimed, the orphaned arrays
        quarantined), or ``device_error`` (dispatch/fetch raised).
        Every degraded serve comes from
        ``match_from_tries`` — the authoritative host oracle, exact by
        construction — so the publish path NEVER fails on a sick device;
        it just loses the accelerator speedup until the canary re-closes
        the breaker."""
        from ..resilience.device import DeviceTimeoutError
        from ..utils.metrics import FABRIC, FabricMetric
        br = self.device_breaker
        verdict = br.admit() if br is not None else "ok"
        reason = None
        oracle_rows = None
        if verdict == "rejected":
            reason = "breaker"
        else:
            settled = False
            try:
                res = await self._device_leg_async(
                    ring, merged, uniq_queries, batch,
                    max_persistent_fanout, max_group_fanout)
                if br is not None:
                    if verdict == "canary":
                        ok, oracle_rows = self._canary_parity(
                            uniq_queries, res, max_persistent_fanout,
                            max_group_fanout)
                        if ok:
                            br.record_success()
                        else:
                            br.record_failure("canary row parity")
                            reason = "canary_parity"
                    elif br.state == "closed":
                        # an "ok"-admitted batch completing while the
                        # breaker is no longer closed is a pre-trip
                        # STRAGGLER: its success must not close the
                        # circuit past the canary parity bar (not even
                        # indirectly, by landing while a canary is out)
                        br.record_success()
                settled = True
                if reason is None:
                    return res, None
            except DeviceTimeoutError as e:
                FABRIC.inc(FabricMetric.DEVICE_TIMEOUT)
                if br is not None:
                    br.record_failure(repr(e))
                    settled = True
                reason = "timeout"
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001 — degrade, don't fail
                if br is not None:
                    br.record_failure(repr(e))
                    settled = True
                logging.getLogger(__name__).warning(
                    "device match failed; serving host oracle: %r", e)
                reason = "device_error"
            finally:
                if br is not None and verdict == "canary" and not settled:
                    # cancelled mid-probe with no verdict: the half-open
                    # budget must not leak or the breaker wedges refusing
                    br.release_probe()
        FABRIC.inc(FabricMetric.MATCH_DEGRADED, len(uniq_queries))
        from ..obs import OBS
        OBS.profiler.record_batch(
            n_queries=len(uniq_queries), batch=len(uniq_queries),
            kernel="oracle", dispatch_s=0.0, path="async",
            degraded=reason)
        with trace.span("match.degraded", reason=reason,
                        n_queries=len(uniq_queries)):
            if oracle_rows is None:
                # parity failures already walked the oracle — reuse it
                oracle_rows = self.match_from_tries(
                    uniq_queries,
                    max_persistent_fanout=max_persistent_fanout,
                    max_group_fanout=max_group_fanout)
            return oracle_rows, reason

    async def _device_leg_async(self, ring, merged, uniq_queries, batch,
                                max_persistent_fanout, max_group_fanout):
        """prepare → dispatch → fetch-on-ready → expand through the
        bounded ring, once a device batch, with the ISSUE 7 watchdog
        armed on the readiness wait. A timeout
        RECLAIMS the slot: the ring releases it immediately (the next
        batch keeps flowing) and the orphaned result arrays — which may
        alias donated probe buffers the device is still writing — go to
        quarantine until actually ready. ``merged.admitted`` (the end
        of its callers' queue time) and ``merged.tokenize_s`` are set
        here, and stand even when the leg later raises."""
        from ..resilience.device import DeviceTimeoutError
        # ISSUE 11 overlap: stage-1 prep (tokenize + probe upload) runs
        # BEFORE slot admission — batch N+1 tokenizes while batch N is
        # still walking, and a full ring stalls only the enqueue, not
        # the byte plane. The batch holds a prep TICKET (depth + 1 of
        # them, taken when it left the line): callers beyond one
        # prep-ahead batch wait un-uploaded, keeping the capacity
        # model's in-flight byte accounting honest. The dispatch half
        # re-preps iff a compaction swapped the base during the
        # admission wait.
        with trace.span("device.acquire"):
            if batch is None:
                # queue-depth-adaptive pow2 floor: idle ring ⇒ small
                # pad to cut time-to-first-result, busy ring ⇒ the
                # throughput floor. Read before slot admission
                # (planned_floor = the pre-acquire twin). Callers that
                # share this batch ARE concurrency, whatever the ring
                # holds: simultaneous publishes meet the throughput
                # shape whether they merge or overlap, so what warms it
                # does not hang on how their arrivals fall.
                floor = (ring.base_floor if len(merged.callers) > 1
                         else ring.planned_floor())
                batch = _pow2_batch(len(uniq_queries), floor=floor)
            prep = self._prepare_probes(uniq_queries, batch)
            merged.tokenize_s = prep.tokenize_s
            await ring.acquire()
        merged.admitted = time.monotonic()
        trace.count("match.merged_calls", len(merged.callers))
        try:
            fl = self._dispatch_prepared(prep, donate=True,
                                         watchdogged=True)
            ring.start_fetch(fl.res)
            try:
                with trace.span("device.ready", batch=fl.batch,
                                kernel=fl.kernel) as ready:
                    await self._await_ready(ring, fl)
            except DeviceTimeoutError:
                ring.reclaim(fl.res,
                             tag=getattr(fl, "quarantine_tag", None))
                # ISSUE 15: let the subclass attribute the timeout
                # (the mesh feeds the implicated SHARD's breaker)
                self._note_device_timeout(fl)
                # ISSUE 20: the e2e plane's degraded map names the
                # component stalling deliveries (the mesh hook above
                # already named individual shards; this covers the
                # single-chip matcher)
                from ..obs import OBS
                OBS.e2e.set_degraded(
                    getattr(fl, "quarantine_tag", None) or "device",
                    "device_timeout")
                raise
            except BaseException:
                # cancelled mid-wait (caller timeout, client
                # disconnect): the arrays may still be in flight and
                # may alias donated probe buffers — park them like a
                # timeout does, minus the timeout accounting, or
                # dropping the last reference here would be the
                # exact use-after-donate the quarantine exists to
                # prevent
                ring.quarantine.add(fl.res,
                                    tag=getattr(fl, "quarantine_tag",
                                                None))
                raise
            # a step that completes clears the single-chip degraded
            # mark (per-shard marks clear on their own ready rows)
            from ..obs import OBS as _obs
            _obs.e2e.clear_degraded("device")
        finally:
            # the ticket is held for the WHOLE slot tenure and goes back
            # with the slot: tickets bound prepped + in-flight batches
            # together at depth+1, so at most ONE uploaded-but-
            # undispatched probe set exists when the ring is full — the
            # exact +1 the capacity model counts
            ring.release()
            ring.release_prep(merged)
        with trace.span("device.fetch") as fetch:
            overflow, starts_a, counts_a = self._fetch_walk(fl.res)
        with trace.span("match.expand") as expand:
            out = self._expand_walk(fl, overflow, starts_a, counts_a,
                                    max_persistent_fanout,
                                    max_group_fanout)
        # ISSUE 8: the continuous profiler's per-batch stage record,
        # built from the spans' own durations — attribute increments +
        # one ring store, nothing else
        from ..obs import OBS
        OBS.profiler.record_batch(
            n_queries=len(fl.queries), batch=fl.batch, kernel=fl.kernel,
            tokenize_s=fl.tokenize_s, dispatch_s=fl.dispatch_s,
            ready_s=ready.duration_s, fetch_s=fetch.duration_s,
            expand_s=expand.duration_s,
            dev_expand_s=fl.dev_expand_s, path="async")
        return out

    async def _await_ready(self, ring, fl) -> None:
        """Readiness-wait hook (ISSUE 16): one watchdogged wait over the
        whole in-flight batch. The mesh overrides this for SPLIT
        dispatches — per-fault-domain groups each wait under their own
        per-shard deadline so a hang indicts one device, not the step."""
        await ring.wait_ready(fl.res, fault=fl.fault)

    def _note_device_timeout(self, fl) -> None:
        """Subclass hook (ISSUE 15): attribute a watchdog timeout of one
        in-flight batch — the mesh feeds the implicated shard breaker(s)
        and settles outstanding canary probes. The single-chip matcher's
        own breaker is fed by the caller, so this is a no-op here."""

    def _canary_parity(self, queries, device_rows,
                       max_persistent_fanout, max_group_fanout):
        """Half-open success bar: the canary batch's device rows must be
        row-identical to the host oracle (receivers + groups per row) —
        a device that returns plausible-but-wrong rows after a fault must
        NOT re-close the breaker. Returns ``(ok, oracle_rows)`` so a
        failed parity check can serve the already-computed oracle rows
        instead of walking the host tries a second time."""
        oracle = self.match_from_tries(
            queries, max_persistent_fanout=max_persistent_fanout,
            max_group_fanout=max_group_fanout)

        def canon(m):
            return (sorted((r.matcher.mqtt_topic_filter, r.receiver_url)
                           for r in m.normal),
                    {f: sorted(r.receiver_url for r in ms)
                     for f, ms in m.groups.items()})
        return all(canon(d) == canon(o)
                   for d, o in zip(device_rows, oracle)), oracle

    def _match_batch_device(self, queries: Sequence[Tuple[str,
                                                          Sequence[str]]],
                            *, max_persistent_fanout: int = UNCAPPED_FANOUT,
                            max_group_fanout: int = UNCAPPED_FANOUT,
                            batch: Optional[int] = None,
                            stats: Optional[dict] = None
                            ) -> List[MatchedRoutes]:
        """Match (tenant_id, topic_levels) pairs; returns per-query routes.

        Exact at every instant: base walk ⊕ overlay ⊖ tombstones equals a
        match against the authoritative tries.

        The device emits matched-slot INTERVALS (ops.match.walk_routes, the
        compressed MatchedRoutes form); the host expands all rows with one
        vectorized ragged-arange (ops.match.expand_intervals) — never a
        per-slot Python loop (the c4 92-filters/s failure mode, VERDICT
        r4 #2). This sync entry is dispatch+fetch+expand back to back; the
        async pipeline (match_batch_async) runs the same three stages with
        an is_ready await between dispatch and fetch.

        ISSUE 7: the device breaker gates this sync leg too — open
        serves the host oracle with no dispatch, a device error feeds
        the breaker and then PROPAGATES (the worker's degradation
        boundary owns the sync fallback), and a half-open admission
        holds the canary batch to oracle row parity.

        ISSUE 11 (the PR 7 carry-over): the fetch is no longer a
        blocking synchronize the watchdog cannot preempt — it waits on
        the same ``is_ready`` short-poll the async leg uses, honoring
        ``BIFROMQ_DEVICE_DEADLINE_S``, and a truly hung device degrades
        THIS caller to the exact host oracle (breaker fed, MATCH_DEGRADED
        counted) instead of wedging it forever.
        """
        if not queries:
            return []
        from ..resilience.device import DeviceTimeoutError
        br = self.device_breaker
        verdict = br.admit() if br is not None else "ok"
        if verdict == "rejected":
            from ..utils.metrics import FABRIC, FabricMetric
            FABRIC.inc(FabricMetric.MATCH_DEGRADED, len(queries))
            if stats is not None:
                # the sync serve has no raising boundary here — the
                # worker's MATCH_DEGRADED event outlet keys on this
                stats["degraded"] = "breaker"
            with trace.span("match.degraded", reason="breaker",
                            n_queries=len(queries)):
                return self.match_from_tries(
                    queries, max_persistent_fanout=max_persistent_fanout,
                    max_group_fanout=max_group_fanout)
        try:
            fl = self._dispatch_device(queries, batch)
            with trace.span("device.fetch") as fetch:
                self._await_ready_sync(fl.res)
                overflow, starts_a, counts_a = self._fetch_walk(fl.res)
            with trace.span("match.expand") as expand:
                out = self._expand_walk(fl, overflow, starts_a, counts_a,
                                        max_persistent_fanout,
                                        max_group_fanout)
            from ..obs import OBS
            OBS.profiler.record_batch(
                n_queries=len(fl.queries), batch=fl.batch,
                kernel=fl.kernel, tokenize_s=fl.tokenize_s,
                dispatch_s=fl.dispatch_s,
                fetch_s=fetch.duration_s, expand_s=expand.duration_s,
                dev_expand_s=fl.dev_expand_s, path="sync")
        except DeviceTimeoutError as e:
            # the watchdog fired on the SYNC leg: reclaimed slot
            # semantics without a ring — the orphaned (non-donated)
            # result arrays are dropped to the backend, the breaker is
            # fed, and this caller serves the exact host oracle
            from ..obs import OBS
            from ..utils.metrics import FABRIC, FabricMetric
            FABRIC.inc(FabricMetric.DEVICE_TIMEOUT)
            FABRIC.inc(FabricMetric.MATCH_DEGRADED, len(queries))
            if br is not None:
                br.record_failure(repr(e))
            self._note_device_timeout(fl)
            if stats is not None:
                stats["degraded"] = "timeout"
            OBS.profiler.record_batch(
                n_queries=len(queries), batch=len(queries),
                kernel="oracle", dispatch_s=0.0, path="sync",
                degraded="timeout")
            with trace.span("match.degraded", reason="timeout",
                            n_queries=len(queries)):
                return self.match_from_tries(
                    queries, max_persistent_fanout=max_persistent_fanout,
                    max_group_fanout=max_group_fanout)
        except BaseException as e:
            if br is not None:
                if isinstance(e, Exception):
                    br.record_failure(repr(e))
                elif verdict == "canary":
                    br.release_probe()
            raise
        if br is not None:
            if verdict == "canary":
                ok, oracle_rows = self._canary_parity(
                    queries, out, max_persistent_fanout, max_group_fanout)
                if not ok:
                    br.record_failure("canary row parity")
                    from ..utils.metrics import FABRIC, FabricMetric
                    FABRIC.inc(FabricMetric.MATCH_DEGRADED, len(queries))
                    if stats is not None:
                        stats["degraded"] = "canary_parity"
                    with trace.span("match.degraded",
                                    reason="canary_parity",
                                    n_queries=len(queries)):
                        return oracle_rows
                br.record_success()
            elif br.state == "closed":
                # pre-trip straggler guard, same as the async leg
                br.record_success()
        return out

    def _prepare_probes(self, queries, batch: Optional[int] = None,
                        ) -> _Prepared:
        """Stage 0 (ISSUE 11, the ``tokenize`` stage): byte-plane topic
        prep + probe upload, SEPARATE from the walk enqueue so the async
        leg runs it before ring admission — batch N+1 tokenizes while
        batch N is still walking — and the profiler attributes prep
        apart from dispatch.

        String/bytes topic rows (the serving call sites hand raw topics
        now) pack into ONE contiguous ``TopicBytes`` buffer; with
        ``BIFROMQ_DEVICE_TOKENIZE`` on, the raw bytes ship to the device
        hash program and only bytes cross to the device. Pre-parsed level
        lists (legacy callers, tests) keep the token-cache host path.
        """
        from ..ops.match import Probes
        self._apply_pending_swap()
        if self._base_ct is None:
            self.refresh()
        ct = self._base_ct
        if batch is None:
            batch = _pow2_batch(len(queries))
        roots = [ct.root_of(t) for t, _ in queries]
        with trace.span("device.tokenize", batch=batch,
                        queries=len(queries)) as sp:
            topics = [levels for _, levels in queries]
            byte_rows = all(isinstance(t, (str, bytes)) for t in topics)
            tok = probes = None
            if byte_rows:
                from ..models.bytetok import TopicBytes
                from ..ops.tokenize import (device_tokenize,
                                            device_tokenize_enabled)
                tb = TopicBytes.from_topics(topics)
                if device_tokenize_enabled():
                    tok, probes = device_tokenize(
                        tb, roots, max_levels=ct.max_levels,
                        salt=ct.salt, batch=batch, device=self.device)
                else:
                    tok = tokenize(tb, roots, max_levels=ct.max_levels,
                                   salt=ct.salt, batch=batch,
                                   cache=self._tok_cache)
            else:
                tok = tokenize(topics, roots, max_levels=ct.max_levels,
                               salt=ct.salt, batch=batch,
                               cache=self._tok_cache)
            if probes is None:
                probes = Probes.from_tokenized(tok, device=self.device)
        return _Prepared(queries=list(queries), ct=ct, tok=tok,
                         probes=probes, roots=roots, batch=batch,
                         tokenize_s=sp.duration_s)

    def _dispatch_device(self, queries, batch: Optional[int] = None, *,
                         donate: bool = False,
                         watchdogged: bool = False) -> _InFlight:
        """Stage 0+1 back to back (the sync leg; the async leg preps
        before ring admission and calls ``_dispatch_prepared`` itself)."""
        return self._dispatch_prepared(self._prepare_probes(queries, batch),
                                       donate=donate,
                                       watchdogged=watchdogged)

    def _dispatch_prepared(self, prep: _Prepared, *, donate: bool = False,
                           watchdogged: bool = False) -> _InFlight:
        """Stage 1: enqueue the device walk for a prepared probe batch.

        Returns as soon as the walk is ENQUEUED (dispatch is
        asynchronous; only a readback or an ``is_ready`` poll observes
        completion). ``donate=True`` routes through
        the donated jit so XLA reuses the probe buffers for the results
        (the pipeline's in-flight memory bound); callers must then treat
        the device probes as consumed — everything downstream here reads
        only the HOST token mirror.
        """
        from ..resilience.faults import get_injector
        # ISSUE 7 device-fault hook: error rules raise here; readiness-
        # shaping rules (hang/slow/flaky_ready) ride the _InFlight into
        # wait_ready — but ONLY the watchdogged async leg has a readiness
        # poll to thread them into. The sync leg's fetch now short-polls
        # too (ISSUE 11), but hang/slow injection stays an async-leg
        # surface. One attribute check when the injector is disabled.
        if watchdogged:
            fault = get_injector().device_rule("dispatch")
        else:
            get_injector().check_raise("device", "tpu-device", "dispatch")
            fault = None
        if self._base_ct is not prep.ct:
            # a compaction swap landed between prep and dispatch (the
            # async leg awaits ring admission in the gap): roots/salt are
            # per-snapshot, so re-prep against the installed base —
            # rare enough that the re-tokenize is noise
            prep = self._prepare_probes(prep.queries, prep.batch)
        # ISSUE 9: ship any host patches accumulated since the last
        # dispatch (one coalesced narrow update, so this batch walks the
        # post-mutation tables). watchdogged == the async leg, which
        # already holds its own (not-yet-dispatched) ring slot.
        self._flush_patches(own_slots=1 if watchdogged else 0)
        ct, tok, roots, batch = prep.ct, prep.tok, prep.roots, prep.batch
        # esc_k=0: escalation stays a SEPARATE lazily-compiled dispatch
        # (_expand_walk) — fusing it into this jit would compile the
        # high-K escalation walk on the first serving query, doubling
        # cold-start latency for a pass that almost never runs
        with trace.span("device.dispatch", batch=batch,
                        queries=len(prep.queries)) as sp:
            res, kernel = self._walk_primary(prep.probes, ct,
                                             donate=donate)
            sp.set_tag("kernel", kernel)
        # ISSUE 6: the `device.sync` stage of the sync era is replaced by
        # the dispatch/ready/fetch split in the always-on stage
        # histograms (/metrics "stages" + the bench breakdown), each fed
        # by its span's exit
        dispatch_s = sp.duration_s
        # ISSUE 19: the second device stage — fan-out expansion + peer
        # bucketing enqueued right behind the walk, so the host fetch
        # reads pre-bucketed (slot, row) pairs instead of interval grids
        dev_expand_s = 0.0
        peer_tab = None
        from ..ops.match import device_expand_enabled
        import jax
        # real device arrays only: tests (and degraded backends) hand
        # duck-typed result leaves the expansion jit cannot consume —
        # those batches keep the host expander
        if device_expand_enabled() and isinstance(res.start, jax.Array):
            from ..ops.match import expand_cap_lanes, expand_routes
            with trace.span("device.expand", batch=batch) as sp:
                peer_tab, slot_peer = self._peer_table(ct)
                res = expand_routes(
                    res, slot_peer, cap=batch * expand_cap_lanes(),
                    n_peers=peer_tab.n_peers)
            dev_expand_s = sp.duration_s
        return _InFlight(queries=prep.queries, ct=ct,
                         dev=self._device_trie, tok=tok, roots=roots,
                         res=res, tomb=self._tomb, delta=self._delta,
                         batch=batch, kernel=kernel, fault=fault,
                         dispatch_s=dispatch_s,
                         tokenize_s=prep.tokenize_s,
                         dev_expand_s=dev_expand_s, peer_tab=peer_tab)

    def _walk_primary(self, probes, ct, *, donate: bool):
        """The primary serving walk — donated variant when the pipeline
        asked for it."""
        dev = self._device_trie
        from ..ops.match import walk_routes, walk_routes_donated
        fn = walk_routes_donated if donate else walk_routes
        return fn(dev, probes, probe_len=ct.probe_len,
                  k_states=self.k_states,
                  max_intervals=self.max_intervals,
                  esc_k=0), ("lax_donated" if donate else "lax")

    def _peer_table(self, ct):
        """The slot→delivery-peer table for this base snapshot, host +
        device halves, cached on snapshot identity (see __init__ note on
        why patch flushes must NOT invalidate it)."""
        cached = self._peer_cache
        if cached is not None and cached[0] is ct:
            return cached[1], cached[2]
        import jax
        from ..dist.deliverer import build_peer_table
        tab = build_peer_table(ct.matchings_arr)
        dev_tab = jax.device_put(tab.slot_peer, self.device)
        self._peer_cache = (ct, tab, dev_tab)
        return tab, dev_tab

    @staticmethod
    def _await_ready_sync(res, deadline_s: Optional[float] = None,
                          spin_polls: int = 50,
                          poll_s: float = 0.0005) -> None:
        """ISSUE 11 (PR 7 carry-over): the sync leg's pre-fetch
        readiness wait — the same two-phase ``is_ready`` short-poll the
        async watchdog uses (spin for sub-ms completions, timed sleeps
        for long ones), minus the event loop. Past the
        ``BIFROMQ_DEVICE_DEADLINE_S`` deadline a
        :class:`DeviceTimeoutError` fires so a hung device degrades the
        SYNC caller to the oracle instead of wedging it inside an
        uninterruptible PJRT synchronize. Backends whose arrays lack
        ``is_ready`` fall through to the blocking fetch — still correct,
        just unpreemptable (the pre-ISSUE-11 behavior)."""
        from ..resilience.device import DeviceTimeoutError, \
            device_deadline_s
        if deadline_s is None:
            deadline_s = device_deadline_s()
        ready = getattr(res, "ready_leaves", None)
        leaves = ready() if ready is not None \
            else (res.start, res.count, res.overflow)
        t0 = time.monotonic()
        polls = 0
        while True:
            try:
                if all(leaf.is_ready() for leaf in leaves):
                    return
            except AttributeError:
                return
            if (deadline_s is not None
                    and time.monotonic() - t0 >= deadline_s):
                raise DeviceTimeoutError(deadline_s)
            if polls >= spin_polls:
                time.sleep(poll_s)
            polls += 1

    @staticmethod
    def _fetch_walk(res):
        """Stage 2: the one true synchronization — writable host copies
        (escalation patches rescued rows in place; a bare asarray view of
        a jax buffer is read-only). ISSUE 7: the fetch-side device-fault
        hook fires here (error rules only — a readback can crash, it
        cannot hang-inject).

        ISSUE 19 device-expand batches read the COMPACT pair buffers —
        the interval grids stay on device (escalation/truncation rows
        fetch them lazily via _fetch_escalation_grids on the slow path).
        Returns (overflow, _HostPairs, None) in that mode; the legacy
        (overflow, starts, counts) grids otherwise."""
        from ..resilience.faults import get_injector
        get_injector().check_raise("device", "tpu-device", "fetch")
        # the wait for the device apart from the copy: after a readiness
        # poll said "ready" this is nothing; where the poll slept past
        # the completion, or the sync leg fell through, it is the rest
        # of the device's time
        with trace.span("device.fetch.wait"):
            ready = getattr(res, "ready_leaves", None)
            for leaf in (ready() if ready is not None
                         else (res.start, res.count, res.overflow)):
                if hasattr(leaf, "block_until_ready"):  # not duck-typed
                    leaf.block_until_ready()
        overflow = np.array(res.overflow)
        if hasattr(res, "slots"):
            pairs = _HostPairs(
                slots=np.asarray(res.slots), rows=np.asarray(res.rows),
                row_offsets=np.asarray(res.row_offsets),
                n_pairs=int(np.asarray(res.n_pairs)),
                trunc=np.asarray(res.trunc),
                peer_slots=np.asarray(res.peer_slots),
                peer_rows=np.asarray(res.peer_rows),
                peer_offsets=np.asarray(res.peer_offsets), res=res)
            return overflow, pairs, None
        starts_a = np.array(res.start)
        counts_a = np.array(res.count)
        return overflow, starts_a, counts_a

    @staticmethod
    def _fetch_escalation_grids(res):
        """Slow-path grid readback: with device expansion on, only
        buffer-truncated rows ever need the interval grids on host — a
        deliberate synchronization OFF the serving fast path."""
        return np.asarray(res.start), np.asarray(res.count)

    def _expand_walk(self, fl: _InFlight, overflow, starts_a, counts_a,
                     max_persistent_fanout: int,
                     max_group_fanout: int) -> List[MatchedRoutes]:
        """Stage 3: escalation + interval expansion + overlay correction,
        all against the _InFlight SNAPSHOT (see _InFlight docstring)."""
        from ..ops.match import Probes, expand_intervals, walk_routes
        queries, ct, tok, roots = fl.queries, fl.ct, fl.tok, fl.roots
        # host-triggered escalation: rows whose active set (or interval
        # budget) overflowed re-walk in one compacted sub-batch at a
        # higher state budget AND a wider interval budget (a separate
        # dispatch, so its lane width is free to differ — the host merges
        # by slot arrays) — only rows that overflow even that fall
        # through to the host oracle
        esc_k = min(4 * self.k_states, 128)
        # never narrower than the base budget (a narrower re-walk is
        # guaranteed-futile for interval overflows)
        esc_a = max(min(4 * self.max_intervals, 256), self.max_intervals)
        esc_slots = {}
        ovf_rows = np.nonzero(overflow[:len(queries)]
                              & (tok.lengths[:len(queries)] >= 0))[0]
        if len(ovf_rows) and (esc_k > self.k_states
                              or esc_a > self.max_intervals):
            eb = _pow2_batch(len(ovf_rows))
            # ISSUE 11: sub_batch is polymorphic — host-tokenized
            # batches slice their rows; device-tokenized mirrors (whose
            # hash lanes never came back to host) re-tokenize just the
            # overflow rows
            sub = Probes.from_tokenized(tok.sub_batch(ovf_rows, eb),
                                        device=self.device)
            res2 = walk_routes(fl.dev, sub,
                               probe_len=ct.probe_len, k_states=esc_k,
                               max_intervals=esc_a, esc_k=0)
            o2 = np.asarray(res2.overflow)
            slots2, offs2 = expand_intervals(res2.start, res2.count)
            for j, qi in enumerate(ovf_rows):
                if not o2[j]:
                    esc_slots[int(qi)] = slots2[offs2[j]:offs2[j + 1]]
                    overflow[qi] = False
        # ISSUE 19: device-expanded batches hand the pairs pre-computed;
        # only buffer-truncated rows re-expand on host from the (lazily
        # fetched) interval grids — exact, just not pre-bucketed
        pairs = starts_a if isinstance(starts_a, _HostPairs) else None
        trunc_slots = trunc_offs = None
        trunc_map: dict = {}
        if pairs is not None:
            slots, offs = pairs.slots, pairs.row_offsets
            need = np.nonzero(pairs.trunc[:len(queries)]
                              & ~overflow[:len(queries)])[0]
            if len(need):
                g_s, g_c = self._fetch_escalation_grids(pairs.res)
                trunc_slots, trunc_offs = expand_intervals(
                    g_s[need], g_c[need])
                trunc_map = {int(qi): j for j, qi in enumerate(need)}
            self.last_expanded = (pairs, fl.peer_tab)
        else:
            slots, offs = expand_intervals(starts_a, counts_a)
        out: List[MatchedRoutes] = []
        for qi, (tenant_id, levels) in enumerate(queries):
            tomb = fl.tomb.get(tenant_id)
            delta = fl.delta.get(tenant_id)
            if roots[qi] < 0:
                # tenant absent from the base snapshot: all its routes (if
                # any) are newer than the base — serve from authoritative
                out.append(self.match_from_tries(
                    [(tenant_id, levels)],
                    max_persistent_fanout=max_persistent_fanout,
                    max_group_fanout=max_group_fanout)[0])
                continue
            if overflow[qi] or tok.lengths[qi] < 0:
                # even the device escalation overflowed (or the topic
                # is too deep for the walk shape): host oracle re-match
                out.append(self.match_from_tries(
                    [(tenant_id, levels)],
                    max_persistent_fanout=max_persistent_fanout,
                    max_group_fanout=max_group_fanout)[0])
                continue
            if qi in esc_slots:
                row = esc_slots[qi]
            elif qi in trunc_map:
                j = trunc_map[qi]
                row = trunc_slots[trunc_offs[j]:trunc_offs[j + 1]]
            else:
                row = slots[offs[qi]:offs[qi + 1]]
            if not tomb and delta is None:
                # fast path: no overlay for this tenant
                out.append(self._routes_from_slots(
                    ct, row, max_persistent_fanout, max_group_fanout))
                continue
            out.append(self._expand_with_overlay(
                ct, row, tomb or (), delta, _parse_levels(levels),
                max_persistent_fanout, max_group_fanout))
        return out

    def match(self, tenant_id: str, topic: str, **kwargs) -> MatchedRoutes:
        # ISSUE 11: the raw topic string flows through — the byte plane
        # tokenizes it; levels materialize only on fallback paths
        return self.match_batch([(tenant_id, topic)], **kwargs)[0]

    def match_from_tries(self, queries: Sequence[Tuple[str, Sequence[str]]],
                         *, max_persistent_fanout: int = UNCAPPED_FANOUT,
                         max_group_fanout: int = UNCAPPED_FANOUT
                         ) -> List[MatchedRoutes]:
        """Match straight from the authoritative host tries — the ONE
        exact-oracle fallback surface, shared by the walk's overflow path
        and the dist worker's fault/deadline degradation path (keeping
        their semantics identical by construction)."""
        out: List[MatchedRoutes] = []
        for tenant_id, levels in queries:
            trie = self.tries.get(tenant_id)
            out.append(trie.match(
                _parse_levels(levels),
                max_persistent_fanout=max_persistent_fanout,
                max_group_fanout=max_group_fanout)
                if trie is not None else MatchedRoutes())
        return out

    @staticmethod
    def _routes_from_slots(ct: CompiledTrie, row: np.ndarray,
                           max_persistent_fanout: int,
                           max_group_fanout: int) -> MatchedRoutes:
        """Slot ids → MatchedRoutes, caps applied vectorized.

        Same cap semantics as _expand (MatchedRoutes.java:38 rules) but all
        per-slot work is numpy: kind masks + cumsum ranks instead of a
        Python loop over slots. Group filters are unique per topic (one
        GroupMatching slot per (node, filter)), so a rank cutoff equals the
        reference's distinct-filter cap.
        """
        out = MatchedRoutes()
        if row.size == 0:
            return out
        kinds = ct.slot_kind[row]
        # ISSUE 9: tombstoned slots ride the interval until compaction
        # reclaims them — the walk emits them, this is where they die
        dead = kinds == CompiledTrie.SLOT_DEAD
        if dead.any():
            row, kinds = row[~dead], kinds[~dead]
            if row.size == 0:
                return out
        pers_mask = kinds == CompiledTrie.SLOT_PERSISTENT
        if (max_persistent_fanout != UNCAPPED_FANOUT
                and int(pers_mask.sum()) > max_persistent_fanout):
            out.max_persistent_fanout_exceeded = True
            drop = pers_mask & (np.cumsum(pers_mask)
                                > max_persistent_fanout)
            row, kinds, pers_mask = (row[~drop], kinds[~drop],
                                     pers_mask[~drop])
        out.persistent_fanout = int(pers_mask.sum())
        grp_mask = kinds == CompiledTrie.SLOT_GROUP
        arr = ct.matchings_arr
        if grp_mask.any():
            grp_slots = row[grp_mask]
            if (max_group_fanout != UNCAPPED_FANOUT
                    and grp_slots.size > max_group_fanout):
                out.max_group_fanout_exceeded = True
                grp_slots = grp_slots[:max_group_fanout]
            for m in arr[grp_slots]:
                # the slot's own tuple, uncopied: the patcher swaps it
                # whole at a join or leave, so its identity tells the
                # election that the membership stands (nobody mutates it)
                out.groups[m.mqtt_topic_filter] = m.members
            out.normal = arr[row[~grp_mask]].tolist()
        else:
            out.normal = arr[row].tolist()
        return out

    def _expand_with_overlay(self, ct: CompiledTrie, slots: np.ndarray,
                             tomb, delta: Optional[SubscriptionTrie],
                             levels: List[str],
                             max_persistent_fanout: int,
                             max_group_fanout: int) -> MatchedRoutes:
        """Base expansion ⊖ tombstones ⊕ delta matches, then caps.

        ``slots`` are matched slot ids from the interval walk (single-chip
        and mesh paths both expand intervals before calling)."""
        normal: List[Route] = []
        groups: Dict[str, List[Route]] = {}
        kind_arr = ct.slot_kind
        for slot in (int(s) for s in slots):
            if kind_arr[slot] == CompiledTrie.SLOT_DEAD:
                continue    # ISSUE 9: patch-tombstoned base slot
            m: Matching = ct.matchings[slot]
            if isinstance(m, GroupMatching):
                members = [r for r in m.members
                           if (m.mqtt_topic_filter, r.receiver_url)
                           not in tomb]
                if members:
                    groups[m.mqtt_topic_filter] = members
            else:
                if (m.matcher.mqtt_topic_filter, m.receiver_url) not in tomb:
                    normal.append(m)
        if delta is not None:
            dm = delta.match(levels)
            normal.extend(dm.normal)
            for f, members in dm.groups.items():
                groups.setdefault(f, []).extend(members)
        # caps over the merged set (MatchedRoutes.java:38 rules)
        out = MatchedRoutes()
        for r in normal:
            if r.broker_id == PERSISTENT_SUB_BROKER_ID:
                if out.persistent_fanout >= max_persistent_fanout:
                    out.max_persistent_fanout_exceeded = True
                    continue
                out.persistent_fanout += 1
            out.normal.append(r)
        for f, members in groups.items():
            if len(out.groups) >= max_group_fanout:
                out.max_group_fanout_exceeded = True
                continue
            out.groups[f] = members
        return out

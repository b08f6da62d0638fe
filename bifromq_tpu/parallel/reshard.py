"""Elastic mesh (ISSUE 17): live tenant migration, online shard
rebalancing and mesh grow/shrink — with ZERO trie rebuilds and ZERO
match-cache generation bumps.

PR 15 froze tenant→shard placement at build time; under Zipf-skewed
multi-tenant traffic one shard saturates while the rest idle. This
module moves a live tenant between shards using only machinery the repo
already has:

- the tenant's arena rows stream to the target shard as **migration
  ops** riding the PR 12 delta hub (``DeltaRecord`` with a ``mig_*`` log
  op), replayed through the target ``PatchableTrie``'s find-or-append
  patch path — byte-deterministic by construction, so mesh standbys
  replaying the same op stream keep arena byte parity;
- during the copy the tenant serves from BOTH shards (the dual-serve
  window): ``ShardedTables.shards_of`` reports ``[src, dst]`` so
  mutations fold into both arenas, and once the copy cursor catches up
  (``mig_ready``) queries take either grid slot exactly like hot-tenant
  replication;
- cutover is one shard-map write (``pins[tenant] = dst`` +
  ``map_version`` bump) — no rebuild, no cache bump (the result set is
  identical from either shard);
- the source rows are tombstoned (``SLOT_DEAD``) once no batch is in
  flight, and the existing frag-compaction reclaims them.

The **abort ladder**: a target-shard breaker leaving "closed" mid-copy
(hang/timeout chaos), or any error in the copy loop, aborts back to
source-only serving — the partial target rows are killed via the
``MigrationState.copied`` ledger (exactly the slots this migration
created, ghost-route-proof even across repeated attempts), the shard map
never saw the tenant move, and nothing was lost or duplicated because
the source arena was never touched before cutover.

``resize_mesh`` grows/shrinks the shard axis of a live mesh: every
tenant is first pinned to its current shard (hash placement moves with
``n_shards``; pins don't), new shards join as empty patchable arenas at
the common edge capacity, evacuating shards drain tenant-by-tenant
through the same migration path, and the jax ``Mesh``/``NamedSharding``
plumbing is re-placed — never a recompile.

Env knobs: ``BIFROMQ_RESHARD_CHUNK`` (routes per copy step),
``BIFROMQ_RESHARD_MAX_SKEW`` (rebalancer trigger),
``BIFROMQ_RESHARD_MIN_HEAT`` (minimum hot-shard heat).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .. import trace
from ..models.automaton import PatchFallback, PatchableTrie, compile_tries
from ..types import RouteMatcher, RouteMatcherType
from ..utils.env import env_float, env_int
from ..utils.metrics import STAGES

RouteKey = Tuple[str, Tuple[int, str, str]]


def reshard_chunk() -> int:
    """Routes streamed per migration step (``BIFROMQ_RESHARD_CHUNK``) —
    the dual-fold/copy interleave granularity, and therefore the bound
    on how long one step holds the serving thread."""
    return max(1, env_int("BIFROMQ_RESHARD_CHUNK", 64))


def reshard_max_skew() -> float:
    """Shard skew (max/mean load score) above which the rebalancer plans
    a move (``BIFROMQ_RESHARD_MAX_SKEW``)."""
    return max(1.0, env_float("BIFROMQ_RESHARD_MAX_SKEW", 1.5))


def reshard_min_heat() -> int:
    """Minimum hot-shard query heat before a migration is worth the
    dual-serve window (``BIFROMQ_RESHARD_MIN_HEAT``)."""
    return max(0, env_int("BIFROMQ_RESHARD_MIN_HEAT", 64))


def _route_key(route) -> RouteKey:
    return (route.matcher.mqtt_topic_filter, route.receiver_url)


def canonical_routes(trie) -> list:
    """The tenant's routes in canonical (topic filter, receiver_url)
    order — the ONE iteration order for copy streams and tombstone
    sweeps, so leader and standby touch arena slots identically."""
    if trie is None:
        return []
    return sorted(trie.routes(), key=_route_key)


def _route_live(trie, route) -> bool:
    """Is this exact route still in the authoritative trie? The copy
    cursor consults this before emitting, so a route removed while it
    waited in the pending list is never resurrected on the target."""
    if trie is None:
        return False
    node = trie._root
    for level in route.matcher.filter_levels:
        node = node.children.get(level)
        if node is None:
            return False
    if route.matcher.type == RouteMatcherType.NORMAL:
        return route.receiver_url in node.routes
    g = node.groups.get((int(route.matcher.type), route.matcher.group or ""))
    return bool(g) and route.receiver_url in g


def is_migration_op(op: Tuple) -> bool:
    """Migration control ops share the delta hub with route mutations
    but never enter the matcher's logical log — they move rows, not
    routes."""
    return bool(op) and isinstance(op[0], str) and op[0].startswith("mig_")


class MigrationAborted(RuntimeError):
    """The migration fell back to source-only serving (target breaker
    opened mid-stream, copy error, or an explicit abort)."""


@dataclass
class MigrationState:
    """Per-tenant migration bookkeeping carried ON the serving snapshot
    (``ShardedTables.migrating``) so routing, mutation fan-out and the
    base-snapshot codec all read one source of truth.

    ``copied`` ledgers every route folded into the TARGET arena on this
    migration's behalf (copy stream + dual-fold adds; dual-fold removes
    retract). An abort kills exactly these slots — never a pre-existing
    row — so repeated migrate/abort cycles against the same target can
    not leave ghost routes.
    """
    tenant: str
    src: int
    dst: int
    ready: bool = False
    copied: Dict[RouteKey, object] = field(default_factory=dict)

    def digest(self) -> dict:
        return {"src": self.src, "dst": self.dst, "ready": self.ready,
                "copied": len(self.copied)}


# ---------------------------------------------------------------------------
# the ONE migration-op → mesh-state definition
# ---------------------------------------------------------------------------
#
# Op tuples (encoded by replication.records alongside add/rm):
#
#   ("mig_begin",     tenant, src, dst)   — open the dual-fold window
#   ("mig_copy",      tenant, dst, route) — fold one route into dst
#   ("mig_ready",     tenant)             — copy caught up: dual-SERVE
#   ("mig_cutover",   tenant, src, dst)   — shard map flips to dst
#   ("mig_abort",     tenant, src, dst)   — kill the copied ledger in dst
#   ("mig_tombstone", tenant, src)        — kill the moved rows in src

def apply_migration_op(matcher, op: Tuple) -> None:
    """Apply one migration op to a mesh matcher's serving state — the
    single definition the leader applies before emitting and mesh
    standbys replay verbatim. Both sides go through the same idempotent
    ``PatchableTrie`` patch calls at the same op-stream positions
    (group membership resolved from the authoritative trie, which the
    surrounding add/rm stream keeps identical), so arenas stay
    byte-identical. The match-cache is NEVER touched: migration moves
    rows between shards, the logical result set is unchanged."""
    base = matcher._base_ct
    if base is None or not hasattr(base, "compiled"):
        raise RuntimeError("migration ops require an installed mesh base")
    kind, tenant = op[0], op[1]
    mig = getattr(base, "migrating", None)
    if kind == "mig_begin":
        _, _, src, dst = op
        if mig is None:
            mig = base.migrating = {}
        if tenant in mig:
            raise RuntimeError(f"tenant {tenant!r} is already migrating")
        mig[tenant] = MigrationState(tenant=tenant, src=int(src),
                                     dst=int(dst))
        base.map_version += 1
    elif kind == "mig_copy":
        _, _, dst, route = op
        st = (mig or {}).get(tenant)
        if st is None:
            return  # copy raced an abort off the map: nothing to fold
        gm = None
        if route.matcher.type != RouteMatcherType.NORMAL:
            gm = matcher._group_members(tenant, route.matcher)
        pt = base.compiled[int(dst)]
        try:
            pt.patch_add(tenant, route, group_members=gm)
        except PatchFallback:
            # deterministic skip (e.g. an emptied group): both sides see
            # the same authoritative state, so both skip the same op
            matcher.patch_fallbacks += 1
            return
        st.copied[_route_key(route)] = route
        base.sync_edge_caps()
    elif kind == "mig_ready":
        st = (mig or {}).get(tenant)
        if st is not None and not st.ready:
            st.ready = True
            base.map_version += 1
    elif kind == "mig_cutover":
        _, _, src, dst = op
        st = (mig or {}).pop(tenant, None)
        if st is None:
            raise RuntimeError(f"cutover without a migration for {tenant!r}")
        pins = dict(base.pins or {})
        pins[tenant] = int(dst)
        base.pins = pins
        matcher._pins[tenant] = int(dst)
        base.map_version += 1
    elif kind == "mig_abort":
        _, _, src, dst = op
        st = (mig or {}).pop(tenant, None)
        if st is None:
            return
        pt = base.compiled[int(dst)]
        for key in sorted(st.copied):
            route = st.copied[key]
            try:
                pt.patch_remove(tenant, route.matcher, route.receiver_url)
            except PatchFallback:
                pass  # group slot died with its first member — same both sides
        base.map_version += 1
    elif kind == "mig_tombstone":
        _, _, src = op
        pt = base.compiled[int(src)]
        for route in canonical_routes(matcher.tries.get(tenant)):
            try:
                pt.patch_remove(tenant, route.matcher, route.receiver_url)
            except PatchFallback:
                pass
        # overlay-resident removes left live-but-masked rows in the
        # source arena (the rm fell back before it could kill the slot):
        # sweep those too so frag-compaction reclaims everything
        for tf, url in sorted(matcher._tomb.get(tenant, ())):
            try:
                pt.patch_remove(tenant, RouteMatcher.from_topic_filter(tf),
                                url)
            except PatchFallback:
                pass
        base.map_version += 1
    else:
        raise ValueError(f"unknown migration op {kind!r}")


def emit_migration_op(matcher, op: Tuple) -> None:
    """Apply locally, then ship on the delta hub (same ordered path as
    route mutations — standbys replay copy ops interleaved with the
    dual-fold add/rm stream in the exact leader order)."""
    apply_migration_op(matcher, op)
    matcher._emit_delta(op[1], (), op, None, False)


# ---------------------------------------------------------------------------
# migration observability (ISSUE 18 leg 3)
# ---------------------------------------------------------------------------

#: completed/aborted migrations kept per matcher for GET /mesh/migrations
MIGRATION_HISTORY_CAP = 32


def _inflight(matcher) -> Dict[str, "TenantMigration"]:
    mp = getattr(matcher, "migrations_inflight", None)
    if mp is None:
        mp = matcher.migrations_inflight = {}
    return mp


def _history(matcher) -> List[dict]:
    hist = getattr(matcher, "migration_history", None)
    if hist is None:
        hist = matcher.migration_history = []
    return hist


def migration_digest(matcher) -> dict:
    """Compact ``mesh.migrations`` digest field: active copy progress +
    completed/aborted tallies from the bounded history ring."""
    hist = getattr(matcher, "migration_history", None) or []
    active = [mig.progress()
              for mig in (getattr(matcher, "migrations_inflight", None)
                          or {}).values()]
    return {
        "active": len(active),
        "pct": (round(min(p["pct"] for p in active), 1)
                if active else 100.0),
        "completed": sum(1 for h in hist if h["outcome"] == "done"),
        "aborted": sum(1 for h in hist if h["outcome"] == "aborted"),
    }


# ---------------------------------------------------------------------------
# migration driver
# ---------------------------------------------------------------------------

class TenantMigration:
    """Drives ONE live tenant move: ``start`` → ``step``* → ``cutover``
    → ``finish``; ``abort`` at any pre-cutover point returns cleanly to
    source-only serving. ``run`` drives the whole ladder synchronously
    (the rebalancer's mode); services interleave ``step`` with serving.

    The driver is leader-side only — standbys see the emitted op stream,
    never this object."""

    def __init__(self, matcher, tenant_id: str, dst: int, *,
                 src: Optional[int] = None) -> None:
        base = matcher._base_ct
        if base is None or not hasattr(base, "compiled"):
            raise ValueError("migration requires an installed mesh base")
        if not base.patchable or not matcher._patching_enabled():
            raise ValueError("migration requires the per-shard patch plane "
                             "(BIFROMQ_MESH_PATCH)")
        if not 0 <= dst < base.n_shards:
            raise ValueError(f"target shard {dst} out of range")
        if base.replicated and tenant_id in base.replicated:
            raise ValueError("replicated tenants live on every shard "
                             "already — nothing to migrate")
        if tenant_id in (base.migrating or {}):
            raise ValueError(f"tenant {tenant_id!r} is already migrating")
        home = base.shard_of(tenant_id)
        if src is None:
            src = home
        elif src != home:
            raise ValueError(f"tenant {tenant_id!r} lives on shard {home}, "
                             f"not {src}")
        if dst == src:
            raise ValueError("source and target shard are the same")
        self.matcher = matcher
        self.tenant = tenant_id
        self.src = int(src)
        self.dst = int(dst)
        # the copy cursor's worklist: a point-in-time canonical snapshot;
        # routes removed while queued are filtered at emission, routes
        # added later dual-fold into both shards directly
        self.pending: List[object] = canonical_routes(
            matcher.tries.get(tenant_id))
        self._cursor = 0
        self.copied_n = 0
        self.state = "init"   # init→copying→ready→cutover→done | aborted
        self.abort_reason = ""
        # ISSUE 18 leg 3: per-rung wall timestamps + copy-stream volume
        # for GET /mesh/migrations, the mesh.migrations digest field and
        # the abort-attribution history record
        self.rung_at: Dict[str, float] = {}
        self.chunks = 0
        self.bytes_copied = 0

    # -------------- observability (ISSUE 18 leg 3) --------------------------

    def _stamp(self, rung: str) -> None:
        self.rung_at[rung] = time.monotonic()

    def dual_serve_s(self) -> Optional[float]:
        """Duration the tenant served from BOTH shards (ready→cutover;
        still-open windows measure up to now)."""
        t_ready = self.rung_at.get("ready")
        if t_ready is None:
            return None
        t_end = self.rung_at.get("cutover")
        return max(0.0, (t_end if t_end is not None
                         else time.monotonic()) - t_ready)

    def progress(self) -> dict:
        total = len(self.pending)
        dual = self.dual_serve_s()
        return {
            "tenant": self.tenant, "src": self.src, "dst": self.dst,
            "state": self.state,
            "rows": self.copied_n, "total": total,
            "pct": round(100.0 * min(self._cursor, total)
                         / max(1, total), 1),
            "chunks": self.chunks, "bytes": self.bytes_copied,
            "dual_serve_s": None if dual is None else round(dual, 6),
            "abort_reason": self.abort_reason,
        }

    def _retire(self, outcome: str) -> None:
        """Move this migration from the in-flight map into the bounded
        per-matcher history ring, with full rung/volume attribution."""
        _inflight(self.matcher).pop(self.tenant, None)
        t0 = self.rung_at.get("begin")
        durations = {}
        if t0 is not None:
            for rung, at in self.rung_at.items():
                durations[rung] = round(at - t0, 6)
        dual = self.dual_serve_s()
        hist = _history(self.matcher)
        hist.append({
            "tenant": self.tenant, "src": self.src, "dst": self.dst,
            "outcome": outcome, "abort_reason": self.abort_reason,
            "rows": self.copied_n, "total": len(self.pending),
            "chunks": self.chunks, "bytes": self.bytes_copied,
            "rung_s": durations,
            "dual_serve_s": None if dual is None else round(dual, 6),
        })
        del hist[:-MIGRATION_HISTORY_CAP]

    # -------------- abort ladder -------------------------------------------

    def _dst_breaker(self) -> str:
        brs = getattr(self.matcher, "shard_breakers", None)
        br = brs[self.dst] if brs and self.dst < len(brs) else None
        return "closed" if br is None else br.state

    def _check_target(self) -> None:
        state = self._dst_breaker()
        if state != "closed":
            self.abort(f"target shard {self.dst} breaker {state}")
            raise MigrationAborted(self.abort_reason)

    def abort(self, reason: str = "") -> None:
        """Back to source-only serving: the copied ledger is killed in
        the target arena, the shard map never changed, the source arena
        was never touched — zero lost, zero duplicated routes."""
        if self.state in ("cutover", "done"):
            raise RuntimeError("cannot abort after cutover")
        if self.state == "aborted":
            return
        self.abort_reason = reason or "aborted"
        if self.state in ("copying", "ready"):
            emit_migration_op(self.matcher, ("mig_abort", self.tenant,
                                             self.src, self.dst))
        self.state = "aborted"
        self._stamp("abort")
        self._retire("aborted")

    # -------------- the ladder ---------------------------------------------

    def start(self) -> "TenantMigration":
        if self.state != "init":
            raise RuntimeError(f"start() in state {self.state!r}")
        if self.matcher._compact_thread is not None:
            raise RuntimeError("compaction in flight — retry after the swap")
        inflight = self.matcher._base_ct.migrating or {}
        if inflight:
            # one live move at a time keeps the dual-serve window (and
            # the standby's replay surface) bounded and attributable
            raise RuntimeError(f"migration of {sorted(inflight)} in "
                               f"flight — one live move at a time")
        self._check_migratable_base()
        with trace.span("mesh.migrate.begin", tenant=self.tenant,
                        src=self.src, dst=self.dst):
            emit_migration_op(self.matcher, ("mig_begin", self.tenant,
                                             self.src, self.dst))
        self.state = "copying"
        self._stamp("begin")
        _inflight(self.matcher)[self.tenant] = self
        return self

    def _check_migratable_base(self) -> None:
        base = self.matcher._base_ct
        if base.shard_of(self.tenant) != self.src:
            raise RuntimeError("base swapped under the migration")

    def step(self, n: Optional[int] = None) -> bool:
        """Stream up to ``n`` (default ``BIFROMQ_RESHARD_CHUNK``) routes
        to the target; returns True once the copy cursor caught up and
        the dual-SERVE window opened (``mig_ready`` emitted). Aborts —
        raising :class:`MigrationAborted` — when the target shard's
        breaker left "closed"."""
        if self.state == "ready":
            return True
        if self.state != "copying":
            raise RuntimeError(f"step() in state {self.state!r}")
        self._check_target()
        from ..replication.records import encode_op
        chunk = reshard_chunk() if n is None else max(1, n)
        trie = self.matcher.tries.get(self.tenant)
        emitted = 0
        with trace.span("mesh.migrate", tenant=self.tenant,
                        src=self.src, dst=self.dst), \
                trace.span("mesh.migrate.copy", tenant=self.tenant,
                           chunk=self.chunks):
            try:
                while self._cursor < len(self.pending) and emitted < chunk:
                    route = self.pending[self._cursor]
                    self._cursor += 1
                    if not _route_live(trie, route):
                        continue
                    op = ("mig_copy", self.tenant, self.dst, route)
                    emit_migration_op(self.matcher, op)
                    emitted += 1
                    self.copied_n += 1
                    self.bytes_copied += len(encode_op(op))
            except MigrationAborted:
                raise
            except Exception as e:  # noqa: BLE001 — abort, never half-copy
                self.abort(f"copy error: {e!r}")
                raise MigrationAborted(self.abort_reason) from e
        self.chunks += 1
        if self._cursor >= len(self.pending):
            with trace.span("mesh.migrate.ready", tenant=self.tenant,
                            rows=self.copied_n):
                emit_migration_op(self.matcher, ("mig_ready", self.tenant))
            self.state = "ready"
            self._stamp("ready")
            return True
        return False

    def cutover(self) -> "TenantMigration":
        """Atomic shard-map flip: pins[tenant]=dst + map_version bump.
        No rebuild, no cache bump — the result set is identical from
        either shard, which the dual-serve window just proved."""
        if self.state != "ready":
            raise RuntimeError(f"cutover() in state {self.state!r}")
        self._check_target()
        with trace.span("mesh.migrate.cutover", tenant=self.tenant,
                        src=self.src, dst=self.dst):
            emit_migration_op(self.matcher, ("mig_cutover", self.tenant,
                                             self.src, self.dst))
        self.state = "cutover"
        self._stamp("cutover")
        return self

    def finish(self) -> bool:
        """Tombstone the moved source rows once NO batch is in flight
        (in-flight expansions read the live arenas through their
        ``_MeshInFlight`` snapshot — killing slots under them would drop
        routes). Returns False while the ring is busy; retry later —
        serving is already correct, this is reclamation."""
        if self.state == "done":
            return True
        if self.state != "cutover":
            raise RuntimeError(f"finish() in state {self.state!r}")
        ring = self.matcher._ring
        if ring is not None and ring.in_flight > 0:
            return False
        with trace.span("mesh.migrate.tombstone", tenant=self.tenant,
                        src=self.src):
            emit_migration_op(self.matcher, ("mig_tombstone", self.tenant,
                                             self.src))
        self.state = "done"
        self._stamp("tombstone")
        self._retire("done")
        return True

    def run(self) -> "TenantMigration":
        if self.state == "init":
            self.start()
        while not self.step():
            pass
        self.cutover()
        self.finish()
        return self


# ---------------------------------------------------------------------------
# skew detection
# ---------------------------------------------------------------------------

class ShardLoadModel:
    """Per-shard load rows from the signals already in the gossip digest
    — arena bytes (``ShardedTables.device_bytes``), logical subs, tenant
    count, query heat, queue pressure, breaker state — plus one scalar
    ``score`` per shard (byte fraction and heat fraction, equally
    weighted) and a ``skew`` = max(score)/mean(score). Operators
    (``/metrics`` → ``mesh.shard_load``, ClusterView digest) and the
    rebalancer read the SAME rows."""

    def __init__(self, *, bytes_weight: float = 0.5,
                 heat_weight: float = 0.5) -> None:
        self.bytes_weight = bytes_weight
        self.heat_weight = heat_weight

    def rows(self, matcher) -> List[dict]:
        base = matcher._base_ct
        if base is None or not hasattr(base, "compiled"):
            return []
        s = base.n_shards
        per_shard = base.device_bytes()["per_shard"]
        subs = [0] * s
        tenants = [0] * s
        heat = [0] * s
        for tenant_id, trie in matcher.tries.items():
            n = len(trie)
            shards = base.shards_of(tenant_id)
            h = matcher.query_heat.get(tenant_id, 0) // max(1, len(shards))
            for sh in shards:
                subs[sh] += n
                tenants[sh] += 1
                heat[sh] += h
        try:
            from ..obs import OBS
            pressure = float(OBS.device.queue_pressure())
        except Exception:  # noqa: BLE001 — telemetry must not raise
            pressure = 0.0
        total_heat = max(1, sum(heat))
        total_bytes = max(1, sum(int(row["real_bytes"]) for row in per_shard))
        brs = getattr(matcher, "shard_breakers", None) or []
        out = []
        for sh in range(s):
            row = per_shard[sh]
            bytes_frac = int(row["real_bytes"]) / total_bytes
            heat_frac = heat[sh] / total_heat
            br = brs[sh] if sh < len(brs) else None
            out.append({
                "shard": sh,
                "padded_bytes": int(row["padded_bytes"]),
                "real_bytes": int(row["real_bytes"]),
                "logical_subs": subs[sh],
                "tenants": tenants[sh],
                "heat": heat[sh],
                # per-shard attribution of the global ring pressure by
                # heat share — a proxy until rings are per-shard
                "queue_pressure": round(pressure * heat_frac, 6),
                "breaker": "closed" if br is None else br.state,
                "score": round(self.bytes_weight * bytes_frac
                               + self.heat_weight * heat_frac, 6),
            })
        return out

    @staticmethod
    def skew(rows: List[dict]) -> float:
        if not rows:
            return 1.0
        scores = [row["score"] for row in rows]
        mean = sum(scores) / len(scores)
        return round(max(scores) / mean, 4) if mean > 0 else 1.0


# ---------------------------------------------------------------------------
# rebalancer
# ---------------------------------------------------------------------------

class MeshRebalancer:
    """Observe→plan→migrate controller: when shard skew crosses
    ``BIFROMQ_RESHARD_MAX_SKEW``, move ONE tenant from the hottest shard
    to the coldest via live migration (never a recompile). Candidate
    order: the PR 3 noisy-tenant ranking first (the detector already
    names who is burning the shard), then by query heat. The PR 8
    ``CapacityPlanner.fits`` vetoes any move that would overflow the
    target shard's HBM. Decisions (including vetoes and aborts) are kept
    for ``GET /mesh/rebalance`` and the gossip digest."""

    MAX_DECISIONS = 32

    def __init__(self, matcher, *, planner=None,
                 max_skew: Optional[float] = None,
                 min_heat: Optional[int] = None) -> None:
        self.matcher = matcher
        if planner is None:
            from ..obs.capacity import CapacityPlanner
            planner = CapacityPlanner()
        self.planner = planner
        self.model = ShardLoadModel()
        self.max_skew = max_skew
        self.min_heat = min_heat
        self.decisions: List[dict] = []
        matcher.mesh_rebalancer = self

    def _record(self, decision: dict) -> dict:
        self.decisions.append(decision)
        del self.decisions[:-self.MAX_DECISIONS]
        return decision

    def plan(self, noisy: Optional[List[str]] = None) -> Optional[dict]:
        """One planning round: returns the move decision (not yet
        executed) or None when balanced / blocked."""
        m = self.matcher
        base = m._base_ct
        if base is None or not hasattr(base, "compiled") \
                or base.n_shards < 2:
            return None
        if base.migrating:
            return None   # one migration at a time — convergence > thrash
        rows = self.model.rows(m)
        skew = self.model.skew(rows)
        max_skew = self.max_skew if self.max_skew is not None \
            else reshard_max_skew()
        min_heat = self.min_heat if self.min_heat is not None \
            else reshard_min_heat()
        hot = max(rows, key=lambda row: row["score"])
        cold = min(rows, key=lambda row: row["score"])
        if skew <= max_skew or hot["shard"] == cold["shard"]:
            return None
        if hot["heat"] < min_heat:
            return None
        movable = [t for t in m.tries
                   if base.shard_of(t) == hot["shard"]
                   and not (base.replicated and t in base.replicated)]
        ranked = [t for t in (noisy or []) if t in movable]
        ranked += sorted((t for t in movable if t not in ranked),
                         key=lambda t: -m.query_heat.get(t, 0))
        vetoed = []
        for tenant in ranked:
            projected = cold["logical_subs"] + len(m.tries[tenant])
            verdict = self.planner.fits(
                projected, mesh=(m.n_replicas, m.n_shards),
                max_levels=m.max_levels, probe_len=m.probe_len)
            if verdict["hbm"]["fits"] is False:
                vetoed.append(tenant)
                continue
            return self._record({
                "tenant": tenant, "src": hot["shard"], "dst": cold["shard"],
                "skew": skew, "max_skew": max_skew,
                "hot_score": hot["score"], "cold_score": cold["score"],
                "vetoed": vetoed,
                "reason": (f"shard {hot['shard']} score {hot['score']} vs "
                           f"mesh skew {skew} > {max_skew}")})
        if vetoed:
            self._record({"tenant": None, "skew": skew,
                          "vetoed": vetoed,
                          "reason": "every candidate vetoed by capacity"})
        return None

    def step(self, noisy: Optional[List[str]] = None) -> Optional[dict]:
        """One controller round: plan, then drive the migration to
        cutover synchronously. Abort outcomes are recorded, never
        raised — the next round replans."""
        decision = self.plan(noisy)
        if decision is None or decision.get("tenant") is None:
            return None
        try:
            mig = TenantMigration(self.matcher, decision["tenant"],
                                  decision["dst"],
                                  src=decision["src"]).run()
            decision["outcome"] = mig.state
            decision["copied"] = mig.copied_n
        except MigrationAborted as e:
            decision["outcome"] = f"aborted: {e}"
        except (RuntimeError, ValueError) as e:
            decision["outcome"] = f"blocked: {e}"
        rows = self.model.rows(self.matcher)
        decision["skew_after"] = self.model.skew(rows)
        return decision


# ---------------------------------------------------------------------------
# mesh grow / shrink
# ---------------------------------------------------------------------------

def resize_mesh(matcher, n_shards: int) -> None:
    """Grow or shrink the shard axis of a LIVE mesh with zero rebuilds.

    Both directions first pin every tenant to its current shard (hash
    placement is a function of ``n_shards``; pins are not). Growing
    appends empty ``PatchableTrie`` arenas at the common edge capacity
    and stocks them with the replicated hot tenants; shrinking drains
    each evacuating shard tenant-by-tenant through the live-migration
    path into the least-loaded survivor. Finally the jax Mesh /
    NamedSharding / step-trace plumbing is re-placed and the delta
    stream re-anchors (standbys resync the resized base).

    Requires: idle dispatch ring, no active migrations, no compaction in
    flight — resize is a control-plane action between batches."""
    base = matcher._base_ct
    if base is None or not hasattr(base, "compiled"):
        raise ValueError("resize requires an installed mesh base")
    if not base.patchable or not matcher._patching_enabled():
        raise ValueError("resize requires the per-shard patch plane")
    if n_shards < 1:
        raise ValueError("n_shards must be >= 1")
    if base.migrating:
        raise RuntimeError("migrations in flight — finish or abort first")
    if matcher._compact_thread is not None:
        raise RuntimeError("compaction in flight — retry after the swap")
    ring = matcher._ring
    if ring is not None and ring.in_flight > 0:
        raise RuntimeError("dispatch ring busy — resize between batches")
    old = base.n_shards
    if n_shards == old:
        return
    t0 = time.perf_counter()
    pins = dict(base.pins or {})
    for tenant_id in sorted(matcher.tries):
        if base.replicated and tenant_id in base.replicated:
            continue
        sh = base.shard_of(tenant_id)
        pins[tenant_id] = sh
        matcher._pins[tenant_id] = sh
    base.pins = pins
    if n_shards > old:
        cap = max(pt.edge_tab.shape[0] for pt in base.compiled)
        for _ in range(old, n_shards):
            ct = compile_tries({}, max_levels=base.max_levels,
                               probe_len=base.probe_len, min_edge_cap=cap)
            base.compiled.append(PatchableTrie(ct))
        base.n_shards = n_shards
        # replicated hot tenants live on EVERY shard: stock the new ones
        # through the same canonical-order patch path
        for tenant_id in sorted(base.replicated or ()):
            routes = canonical_routes(matcher.tries.get(tenant_id))
            for sh in range(old, n_shards):
                pt = base.compiled[sh]
                for route in routes:
                    gm = None
                    if route.matcher.type != RouteMatcherType.NORMAL:
                        gm = matcher._group_members(tenant_id, route.matcher)
                    try:
                        pt.patch_add(tenant_id, route, group_members=gm)
                    except PatchFallback:
                        matcher.patch_fallbacks += 1
        base.sync_edge_caps()
    else:
        # drain evacuating shards through the live-migration ladder
        survivor_subs = [0] * n_shards
        for tenant_id, trie in matcher.tries.items():
            sh = base.shard_of(tenant_id)
            if sh < n_shards:
                survivor_subs[sh] += len(trie)
        for sh in range(n_shards, old):
            evacuees = sorted(
                t for t in matcher.tries
                if base.shard_of(t) == sh
                and not (base.replicated and t in base.replicated))
            for tenant_id in evacuees:
                dst = min(range(n_shards), key=lambda i: survivor_subs[i])
                TenantMigration(matcher, tenant_id, dst, src=sh).run()
                survivor_subs[dst] += len(matcher.tries[tenant_id])
        del base.compiled[n_shards:]
        base.n_shards = n_shards
        # replicated tenants simply lose their evacuated copies
    base.map_version += 1
    matcher._rebuild_mesh_plumbing(n_shards)
    STAGES.record("mesh.migrate", time.perf_counter() - t0)
    # a resize changes the stacked shard-axis shape: standbys must
    # resync the resized base rather than scatter into the old one
    from ..models.matcher import _safe_hook
    _safe_hook(matcher.on_rebase, "rebase", matcher._base_salt(base),
               "resize_mesh")

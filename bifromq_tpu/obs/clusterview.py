"""Cluster observability plane (ISSUE 5 tentpole).

PRs 2–4 built rich per-node surfaces (``/metrics``, ``/tenants``,
``/trace``) that answer only for the local process. This module federates
them into one cluster-wide plane riding the broker's OWN gossip — no
external middleware, the same discipline as upstream BifroMQ:

- **Health digests.** Every node publishes a compact digest — non-closed
  breaker states per endpoint, device gauges (dispatch queue depth,
  compile count, memory watermark), match-cache hit rate, top-3 noisy
  tenants, an HLC stamp — into its gossip agent metadata
  (``AgentHost.host_agent("obs", ...)``), refreshed on the ObsHub
  advisory tick. Digests age out: a killed node's last digest goes
  *stale* in the table instead of lying forever.
- **Health-aware routing.** ``ClusterView.suspect(endpoint)`` answers
  from the gossiped digests: an endpoint some OTHER node's breaker holds
  open, or a node self-reporting a deep dispatch queue, is demoted by
  ``ServiceRegistry.pick`` *before* any local failure is observed —
  closing the PR-1 "breaker state is per-process" follow-up.
- **Federated views.** ``ClusterObsRPCService`` serves each node's raw
  tenant windows and span rings on the RPC fabric; ``federated_tenants``
  scatter-gathers them under a PR-1 deadline budget and merges per-tenant
  RED **bucket-wise** (log2 histograms add exactly), and
  ``federated_trace`` assembles a full cross-process trace ordered by the
  HLC stamps PR 2 already records.

Layering: this module lives in ``obs`` and therefore must not import
``utils.metrics`` at module level (``utils.metrics`` imports the obs
package); the match-cache scrape happens lazily inside ``build_digest``.
"""

from __future__ import annotations

import asyncio
import json
import logging
import time
from typing import Callable, Dict, List, Optional

from ..utils.env import env_float as _env_float
from ..utils.hlc import HLC
from .window import N_BUCKETS, percentile_ms_from

log = logging.getLogger(__name__)

# gossip agent carrying the digests (one per node, LWW by incarnation)
AGENT_ID = "obs"
# RPC fabric service for the scatter-gather plane
SERVICE = "cluster-obs"
DIGEST_VERSION = 1


# ---------------------------------------------------------------------------
# bucket-wise RED merge (the federation math, unit-testable on its own)
# ---------------------------------------------------------------------------

_RAW_SCALARS = ("flows", "errors", "fanout", "queue_wait_s",
                "cache_hits", "cache_misses")


def merge_tenant_raws(raws: List[Dict[str, dict]]) -> Dict[str, dict]:
    """Merge several nodes' raw per-tenant window exports
    (``TenantSLO.raw_snapshot``) into one: scalar windows add, per-stage
    log2 histograms add **bucket-wise** — mathematically identical to one
    histogram having observed every node's samples."""
    out: Dict[str, dict] = {}
    for raw in raws:
        for tenant, r in (raw or {}).items():
            dst = out.get(tenant)
            if dst is None:
                dst = out[tenant] = {k: 0.0 for k in _RAW_SCALARS}
                dst["stages"] = {}
            for k in _RAW_SCALARS:
                dst[k] += float(r.get(k, 0.0))
            for stage, buckets in (r.get("stages") or {}).items():
                cur = dst["stages"].get(stage)
                if cur is None:
                    dst["stages"][stage] = list(buckets)[:N_BUCKETS]
                else:
                    for i, c in enumerate(buckets[:N_BUCKETS]):
                        cur[i] += c
    return out


def derive_red_row(raw: dict, window_s: float) -> dict:
    """Raw merged windows → the same derived RED row shape
    ``TenantSLO.snapshot_tenant`` serves locally (rates, error rate,
    cache hit rate, per-stage count/p50/p99)."""
    flows = raw.get("flows", 0.0)
    errors = raw.get("errors", 0.0)
    hits = raw.get("cache_hits", 0.0)
    lookups = hits + raw.get("cache_misses", 0.0)
    stages = {}
    for stage, buckets in (raw.get("stages") or {}).items():
        count = sum(buckets)
        if count:
            stages[stage] = {"count": count,
                             "p50_ms": percentile_ms_from(buckets, 50),
                             "p99_ms": percentile_ms_from(buckets, 99)}
    return {
        "rate_per_s": round(flows / window_s, 3),
        "errors_per_s": round(errors / window_s, 3),
        "error_rate": round(errors / flows, 4) if flows else 0.0,
        "fanout_per_s": round(raw.get("fanout", 0.0) / window_s, 3),
        "queue_wait_s": round(raw.get("queue_wait_s", 0.0), 6),
        "match_cache_hit_rate": (round(hits / lookups, 4)
                                 if lookups else 0.0),
        "stages": stages,
    }


# ---------------------------------------------------------------------------
# the per-node view
# ---------------------------------------------------------------------------

class ClusterView:
    """One node's participation in the cluster observability plane.

    Publishes this node's digest, decodes peers', and keeps a cached
    unhealthy-endpoint set ``ServiceRegistry.pick`` probes per request
    (set membership only — the hot path never walks gossip state)."""

    def __init__(self, node_id: str, agent_host, *, hub=None,
                 registry=None, rpc_address: str = "", api_port: int = 0,
                 stale_after_s: Optional[float] = None,
                 queue_depth_threshold: Optional[float] = None,
                 interval_s: Optional[float] = None,
                 hysteresis_s: Optional[float] = None,
                 full_every: Optional[int] = None,
                 demotion_weights: Optional[Dict[str, float]] = None,
                 demote_threshold: float = 1.0,
                 clock: Callable[[], float] = time.time) -> None:
        from . import OBS
        self.node_id = node_id
        self.agent_host = agent_host
        self.hub = hub if hub is not None else OBS
        self.registry = registry          # rpc.fabric.ServiceRegistry
        self.rpc_address = rpc_address
        self.api_port = api_port
        # a digest older than this is display-only: it neither demotes
        # nor clears endpoints (the node may be dead — its last report
        # says nothing about NOW)
        self.stale_after_s = (stale_after_s if stale_after_s is not None
                              else _env_float("BIFROMQ_CLUSTER_OBS_STALE_S",
                                              10.0))
        # a node self-reporting a dispatch queue at/after this depth is
        # browned out: its endpoints demote fleet-wide
        self.queue_depth_threshold = (
            queue_depth_threshold if queue_depth_threshold is not None
            else _env_float("BIFROMQ_CLUSTER_OBS_QUEUE_DEPTH", 4096.0))
        self.interval_s = (interval_s if interval_s is not None
                           else _env_float("BIFROMQ_CLUSTER_OBS_INTERVAL_S",
                                           1.0))
        # ISSUE 7 satellite: demotion hysteresis — an endpoint stays
        # demoted until it has looked healthy for a full cooldown window
        # since its LAST bad observation, so a node flapping between
        # healthy and suspect (a breaker oscillating open/half-open, a
        # queue sawtoothing around the threshold) cannot oscillate the
        # routing tier with it
        self.hysteresis_s = (hysteresis_s if hysteresis_s is not None
                             else _env_float(
                                 "BIFROMQ_CLUSTER_OBS_HYSTERESIS_S", 5.0))
        self._last_bad: Dict[str, float] = {}
        self._clock = clock
        self._unhealthy: frozenset = frozenset()
        # ISSUE 8 satellite — digest delta encoding: between full
        # snapshots (every ``full_every`` ticks) only the fields that
        # CHANGED since the last full are gossiped. Deltas are computed
        # against the last FULL (not the previous tick), so a consumer
        # that missed intermediate publishes (gossip metadata is
        # last-writer-wins, not a stream) can still apply any delta
        # directly onto its cached full snapshot.
        self.full_every = (full_every if full_every is not None
                           else max(1, int(_env_float(
                               "BIFROMQ_CLUSTER_OBS_FULL_EVERY", 10.0))))
        self._pub_seq = 0
        self._full_seq = 0
        self._last_full: Optional[dict] = None
        # consumer side: node -> (full_seq, full digest) and the live
        # reconstructed view (full ⊕ applied delta)
        self._digest_full: Dict[str, tuple] = {}
        self._digest_view: Dict[str, dict] = {}
        self.digest_deltas_applied = 0
        self.digest_gaps = 0
        # ISSUE 8 satellite — per-signal demotion weighting: signals
        # accumulate a score per endpoint instead of boolean-OR'ing, so
        # two sub-threshold signals (a half-open peer breaker + a
        # climbing-but-not-deep queue) can demote together while either
        # alone does not. Defaults reproduce the legacy single-signal
        # verdicts exactly (each full-strength signal alone reaches the
        # threshold).
        self.demote_threshold = demote_threshold
        self.demotion_weights = {
            "peer_breaker_open": 1.0,
            "peer_breaker_half": 0.5,
            "queue_depth": 1.0,          # × min(2, depth/threshold)
            "device_breaker_open": 1.0,
            "device_breaker_half": 1.0,
            **(demotion_weights or {}),
        }
        self.demotion_scores: Dict[str, float] = {}
        # node_id -> (last digest HLC stamp seen, local receipt time):
        # digest age is measured from when WE saw the stamp change, so
        # staleness is immune to inter-node wall-clock skew (a peer 15s
        # behind must not look permanently stale, nor a dead fast-clock
        # peer permanently fresh)
        self._digest_seen: Dict[str, tuple] = {}
        self._started = False

    # ---------------- digest (publisher side) -------------------------------

    def build_digest(self) -> dict:
        """This node's compact health digest. Kept small on purpose: it
        piggybacks on UDP gossip packets alongside up to 7 other member
        records."""
        hub = self.hub
        device = hub.device.snapshot(memory=False)
        digest = {
            "v": DIGEST_VERSION,
            "hlc": HLC.INST.get(),
            "breakers": self._breaker_states(),
            "device": {
                "dispatch_queue_depth": device.get("dispatch_queue_depth",
                                                   0),
                "batches_in_flight": device.get("batches_in_flight", 0),
                "compile_count": device.get("compile_count", 0),
                "mem_peak_bytes": hub.device.peak_memory_bytes,
                # ISSUE 7: worst local DEVICE breaker state — peers
                # demote a device-sick node (serving oracle-degraded)
                # before routing to it; "closed" is omitted to keep the
                # UDP payload small
                **self._device_breaker_field(),
            },
            "match_cache_hit_rate": self._match_cache_hit_rate(),
            "noisy": [{"tenant": r["tenant"], "score": r["score"],
                       "flags": r["flags"]}
                      for r in self._noisy_rows()[:3]],
            # ISSUE 8: compact capacity accounting rides the digest so
            # GET /cluster/capacity federates with no extra RPC plane
            "capacity": self._capacity_field(),
            # ISSUE 12: this node's hot (tenant, topic) working set — a
            # failover target pre-warms its match cache against the
            # cluster's union of these BEFORE taking traffic
            "hot_topics": self._hot_topics(),
            # ISSUE 15 satellite (ROADMAP retained follow-up (d)): this
            # node's reconnect-drain occupancy — a clustered reconnect
            # storm sheds herd drains toward peers reporting less
            "drain_pressure": self._drain_pressure(),
        }
        # ISSUE 17: compact mesh shard-load skew — peers (and /cluster)
        # see a lopsided mesh before its hot shard trips a breaker;
        # omitted on single-chip nodes to keep the UDP payload small
        mesh = self._mesh_field()
        if mesh:
            digest["mesh"] = mesh
        # ISSUE 18: compact replication-lag summary — a peer whose
        # standby is stale is a bad failover target, and /cluster shows
        # apply lag cluster-wide with no extra RPC plane; omitted when
        # this node consumes no delta streams
        repl = self._replication_field()
        if repl:
            digest["replication"] = repl
        # ISSUE 20: compact burn summary — which tenants burn their SLO
        # budget on this node, and the worst burner; /cluster/slo
        # federates these with no extra RPC plane. Omitted while no
        # tenant burns to keep the UDP payload small.
        slo = self._slo_field()
        if slo.get("burning") or slo.get("worst"):
            digest["slo"] = slo
        return digest

    @staticmethod
    def _mesh_field() -> dict:
        try:
            from . import OBS
            meshes = OBS.mesh_snapshot()
            if not meshes:
                return {}
            s = meshes[0]     # one mesh matcher per node in practice
            return {"skew": round(float(s.get("skew", 1.0)), 3),
                    "map_version": s.get("map_version", 0),
                    "migrating": len(s.get("migrating", {})),
                    "shard_load": [round(float(r.get("score", 0.0)), 3)
                                   for r in s.get("shard_load", [])],
                    # ISSUE 18: live-migration ladder progress rides the
                    # same field — peers see a dual-serve window open
                    "migrations": s.get("migrations", {})}
        except Exception:  # noqa: BLE001 — telemetry must not raise
            return {}

    @staticmethod
    def _replication_field() -> dict:
        try:
            from .lag import LAG
            return LAG.summary()
        except Exception:  # noqa: BLE001 — telemetry must not raise
            return {}

    @staticmethod
    def _slo_field() -> dict:
        try:
            from . import OBS
            return OBS.burnrate.summary()
        except Exception:  # noqa: BLE001 — telemetry must not raise
            return {}

    def _drain_pressure(self) -> float:
        try:
            return self.hub.drain_pressure()
        except Exception:  # noqa: BLE001 — telemetry must not raise
            return 0.0

    def peer_drain_pressures(self) -> Dict[str, float]:
        """Fresh peers' gossiped drain-governor occupancy (ISSUE 15
        satellite): what the local DrainGovernor consults before
        admitting a herd drain — a saturated broker with quieter peers
        sheds the reconnect so the client lands elsewhere."""
        out: Dict[str, float] = {}
        for node, p in self.peers().items():
            if p["stale"]:
                continue
            dp = (p["digest"] or {}).get("drain_pressure")
            if dp is not None:
                out[node] = float(dp)
        return out

    def _hot_topics(self) -> list:
        try:
            cache = self.hub.pub_cache()
            return cache.hot_keys(16) if cache is not None else []
        except Exception:  # noqa: BLE001 — telemetry must not raise
            return []

    def _capacity_field(self) -> dict:
        try:
            from .capacity import digest_capacity
            return digest_capacity(self.hub)
        except Exception:  # noqa: BLE001 — telemetry must not raise
            return {}

    def _noisy_rows(self) -> list:
        """Ranked rows for the digest: reuse the advisory tick's fresh
        evaluation when available (the tick just ran one; a second full
        scoring pass per second is pure waste on a max-tenant node)."""
        if not self.hub.enabled:
            return []
        rows = self.hub.detector.recent_rows(self.interval_s)
        if rows is None:
            rows = self.hub.detector.evaluate(top_k=3, emit=False)
        return rows

    def _breaker_states(self) -> Dict[str, str]:
        """Non-closed breaker states per endpoint (closed is the default
        — absent means healthy, keeping the gossip payload compact)."""
        if self.registry is None:
            return {}
        try:
            return self.registry.breakers.states(include_closed=False)
        except Exception:  # noqa: BLE001 — telemetry must not raise
            return {}

    @staticmethod
    def _device_breaker_field() -> Dict[str, object]:
        try:
            from ..resilience.device import DEVICE_BREAKERS
            worst = DEVICE_BREAKERS.worst_state()
            if worst == "closed":
                return {}
            out: Dict[str, object] = {"breaker": worst}
            # ISSUE 15: per-SHARD breaker state rides the digest so peers
            # (and /cluster) can see exactly which fault domain of a mesh
            # node is sick — closed shards are omitted (compact UDP)
            shards = {label.rpartition(":")[2]: state
                      for label, state in DEVICE_BREAKERS.states().items()
                      if ":shard" in label}
            if shards:
                out["shard_breakers"] = shards
            return out
        except Exception:  # noqa: BLE001 — telemetry must not raise
            return {}

    @staticmethod
    def _match_cache_hit_rate() -> float:
        # lazy: utils.metrics imports the obs package (layering note in
        # the module docstring)
        try:
            from ..utils.metrics import MATCH_CACHE
            snap = MATCH_CACHE.snapshot()
            hits = misses = 0
            for scope, s in snap.items():
                if scope == "dedup":
                    continue
                hits += s.get("hits", 0)
                misses += s.get("misses", 0)
            return round(hits / (hits + misses), 4) if hits + misses \
                else 0.0
        except Exception:  # noqa: BLE001
            return 0.0

    def refresh(self) -> None:
        """Publish a fresh digest (full or delta — see ``_publish_meta``)
        into the gossip agent metadata (bumping the member incarnation so
        peers merge it) and recompute the unhealthy set from what peers
        have gossiped back."""
        try:
            self.agent_host.host_agent(AGENT_ID, self._publish_meta())
        except Exception:  # noqa: BLE001 — telemetry must not raise
            log.exception("digest publish failed")
        self._recompute()

    def _publish_meta(self) -> dict:
        """Delta-encoded digest publication (ISSUE 8 satellite): a full
        snapshot every ``full_every`` ticks, otherwise only the top-level
        fields that changed since the last full (the HLC stamp always
        changes — it is the freshness signal — but a steady node's
        breakers/device/noisy/capacity sections stop riding every UDP
        gossip packet)."""
        digest = self.build_digest()
        self._pub_seq += 1
        meta = {"addr": self.rpc_address, "api": self.api_port,
                "seq": self._pub_seq}
        if (self._last_full is None or self.full_every <= 1
                or self._pub_seq - self._full_seq >= self.full_every):
            meta["digest"] = digest
            self._last_full = digest
            self._full_seq = self._pub_seq
        else:
            meta["digest_delta"] = {
                k: v for k, v in digest.items()
                if self._last_full.get(k) != v}
            meta["base_seq"] = self._full_seq
        return meta

    def _decode_digest(self, node: str, meta: Optional[dict]) -> dict:
        """Reconstruct a peer's digest from full-or-delta metadata.
        A delta applies only when we hold its base full snapshot; on a
        gap (we joined after the base was published, or the base was
        overwritten before we gossiped it in) the last good view keeps
        serving — it ages out naturally via ``digest_age_s`` if the gap
        persists — and the next full snapshot repairs the chain."""
        meta = meta or {}
        full = meta.get("digest")
        if full is not None:
            if meta.get("seq") is not None:
                self._digest_full[node] = (meta["seq"], full)
            self._digest_view[node] = full
            return full
        delta = meta.get("digest_delta")
        if delta is not None:
            cached = self._digest_full.get(node)
            if cached is not None and cached[0] == meta.get("base_seq"):
                view = {**cached[1], **delta}
                self._digest_view[node] = view
                self.digest_deltas_applied += 1
                return view
            # GAP: we never saw this delta's base full (gossip metadata
            # is last-writer-wins — the one tick holding the full can be
            # overwritten before we sample it). The delta's VALUES are
            # still current-absolute (it lists fields that differ from
            # the publisher's last full), so apply it best-effort onto
            # whatever view we hold: freshness (the hlc field, always in
            # the delta) keeps advancing — an alive, gossiping peer must
            # not age out as stale just because we missed one full —
            # while any field that changed since OUR base but matches
            # THEIR base stays ≤ one full cycle behind, until the next
            # full snapshot resyncs the chain exactly.
            self.digest_gaps += 1
            prev = self._digest_view.get(node)
            if prev is not None:
                view = {**prev, **delta}
                self._digest_view[node] = view
                return view
            return {}
        return {}

    # ---------------- peers (consumer side) ----------------------------------

    def digest_age_s(self, node: str,
                     digest: Optional[dict]) -> Optional[float]:
        """Seconds since this node's digest last CHANGED, measured on the
        LOCAL clock at receipt: a fresh HLC stamp resets the age. Skew
        between node wall clocks cannot fake freshness or staleness —
        only a peer actually going silent ages out."""
        if not digest or "hlc" not in digest:
            self._digest_seen.pop(node, None)
            return None
        now = self._clock()
        seen = self._digest_seen.get(node)
        if seen is None or seen[0] != digest["hlc"]:
            self._digest_seen[node] = (digest["hlc"], now)
            return 0.0
        return max(0.0, now - seen[1])

    def peers(self, include_self: bool = False) -> Dict[str, dict]:
        """node_id → {addr, api, digest, age_s, stale} for every ALIVE
        node hosting the obs agent."""
        out = {}
        members = self.agent_host.agent_members(AGENT_ID)
        for node, meta in members.items():
            if node == self.node_id and not include_self:
                continue
            digest = self._decode_digest(node, meta)
            age = self.digest_age_s(node, digest)
            out[node] = {
                "addr": (meta or {}).get("addr", ""),
                "api": (meta or {}).get("api", 0),
                "digest": digest,
                "age_s": age,
                "stale": age is None or age > self.stale_after_s,
            }
        # receipt entries for departed members must not pin forever
        for node in [n for n in self._digest_seen if n not in members]:
            del self._digest_seen[node]
        for cache in (self._digest_full, self._digest_view):
            for node in [n for n in cache if n not in members]:
                del cache[node]
        return out

    def cluster_table(self) -> Dict[str, dict]:
        """The merged node table behind ``GET /cluster``: every known
        member (any status) with its digest, digest age, and liveness."""
        peers = self.peers(include_self=True)
        out = {}
        for m in self.agent_host.members.values():
            row = {"status": m.status,
                   "alive": m.status == "alive",
                   "agents": sorted(m.agents)}
            p = peers.get(m.node_id)
            if p is not None:
                row.update(addr=p["addr"], api=p["api"],
                           digest=p["digest"],
                           digest_age_s=(round(p["age_s"], 3)
                                         if p["age_s"] is not None
                                         else None),
                           stale=p["stale"])
            out[m.node_id] = row
        return out

    # ---------------- health-aware routing -----------------------------------

    def _recompute(self) -> None:
        """Rebuild the cached unhealthy-endpoint set from fresh peer
        digests. Called on the advisory tick and on gossip membership
        change — never from ``suspect`` (the pick hot path)."""
        try:
            # ISSUE 8 satellite — per-signal weighted scoring: each
            # signal contributes its weight to the endpoint's score and
            # the endpoint demotes at ``demote_threshold``, instead of
            # any single signal boolean-OR'ing it out. Defaults keep
            # every legacy verdict (each full-strength signal alone
            # crosses the threshold) while letting sub-threshold signals
            # combine: a half-open peer breaker (0.5) plus a queue at
            # 60% of the brown-out depth (0.6) now demotes.
            w = self.demotion_weights
            scores: Dict[str, float] = {}

            def bump(ep: str, amount: float) -> None:
                if ep and amount > 0:
                    scores[ep] = scores.get(ep, 0.0) + amount

            for node, p in self.peers().items():
                if p["stale"]:
                    continue
                digest = p["digest"]
                # another node's circuit to an endpoint: OPEN is a full
                # vote, HALF_OPEN (still probing) a partial one
                for ep, state in (digest.get("breakers") or {}).items():
                    if state == "open":
                        bump(ep, w["peer_breaker_open"])
                    elif state == "half_open":
                        bump(ep, w["peer_breaker_half"])
                # the node itself reports a browning-out device pipeline:
                # queue depth scores proportionally (capped at 2× so one
                # signal saturates instead of dwarfing the rest), and
                # (ISSUE 7) a non-closed DEVICE breaker means the node
                # serves oracle-degraded — healthy accelerators first
                dev = digest.get("device") or {}
                if p["addr"]:
                    depth = dev.get("dispatch_queue_depth", 0)
                    if depth > 0 and self.queue_depth_threshold > 0:
                        bump(p["addr"], w["queue_depth"] * min(
                            2.0, depth / self.queue_depth_threshold))
                    db = dev.get("breaker")
                    if db == "open":
                        bump(p["addr"], w["device_breaker_open"])
                    elif db == "half_open":
                        bump(p["addr"], w["device_breaker_half"])
            # never let gossip rumors blackhole OUR OWN endpoint for the
            # local picker: local breakers already own that verdict
            scores.pop(self.rpc_address, None)
            self.demotion_scores = {ep: round(s, 3)
                                    for ep, s in scores.items()}
            bad = {ep for ep, s in scores.items()
                   if s >= self.demote_threshold}
            # ISSUE 7 satellite — demotion hysteresis: an endpoint leaves
            # the unhealthy set only after a full cooldown of CONSECUTIVE
            # healthy observations; any bad sighting restarts the clock,
            # so a flapping endpoint stays demoted instead of oscillating
            # the pick tier
            now = self._clock()
            for ep in bad:
                self._last_bad[ep] = now
            sticky = set()
            for ep, at in list(self._last_bad.items()):
                if now - at < self.hysteresis_s:
                    sticky.add(ep)
                else:       # cooled off: forget it (bounds the map too)
                    del self._last_bad[ep]
            bad |= sticky
        except Exception:  # noqa: BLE001 — telemetry must not raise
            return
        self._unhealthy = frozenset(bad)

    def suspect(self, endpoint: str) -> bool:
        """Hot-path probe for ``ServiceRegistry.pick``: is this endpoint
        flagged unhealthy by gossiped remote state? Pure set membership."""
        return endpoint in self._unhealthy

    def unhealthy_endpoints(self) -> List[str]:
        return sorted(self._unhealthy)

    # ---------------- federation (scatter-gather) ----------------------------

    async def _scatter(self, method: str, payload: dict,
                       timeout_s: float) -> Dict[str, dict]:
        """Call ``cluster-obs/<method>`` on every fresh peer under one
        deadline budget; per-node failures degrade to error rows instead
        of failing the whole view (an operator debugging a sick node
        needs the healthy ones' answer MORE)."""
        from ..resilience.policy import deadline_scope
        if self.registry is None:
            return {}
        peers = {n: p for n, p in self.peers().items()
                 if p["addr"] and not p["stale"]}

        async def one(addr: str):
            out = await self.registry.client_for(addr).call(
                SERVICE, method, json.dumps(payload).encode(),
                timeout=timeout_s)
            return json.loads(out)

        results: Dict[str, dict] = {}
        with deadline_scope(timeout_s):
            done = await asyncio.gather(
                *(one(p["addr"]) for p in peers.values()),
                return_exceptions=True)
        for node, res in zip(peers, done):
            if isinstance(res, BaseException):
                results[node] = {"error": repr(res)}
            else:
                results[node] = res
        return results

    async def federated_tenants(self, timeout_s: float = 2.0,
                                top_k: int = 0) -> dict:
        """``GET /cluster/tenants``: per-tenant RED merged across every
        node (bucket-wise histogram merge), plus per-node fetch status.

        A peer running a different ``BIFROMQ_OBS_WINDOW_S`` has its
        scalar totals rescaled to the coordinator's window before the
        merge, so the derived rates stay true; its histogram BUCKETS
        merge raw (quantiles are window-agnostic, only the absolute
        stage counts then span mixed windows)."""
        hub = self.hub
        window_s = hub.windows.window_s
        local_raw = hub.windows.raw_snapshot() if hub.enabled else {}
        raws = [local_raw]
        nodes = {self.node_id: "local"}
        for node, res in (await self._scatter(
                "tenants", {}, timeout_s)).items():
            if "error" in res:
                nodes[node] = f"error: {res['error']}"
                continue
            nodes[node] = "ok"
            raw = res.get("tenants") or {}
            peer_w = float(res.get("window_s") or window_s)
            if peer_w > 0 and peer_w != window_s:
                scale = window_s / peer_w
                raw = {t: {**r, **{k: r.get(k, 0.0) * scale
                                   for k in _RAW_SCALARS}}
                       for t, r in raw.items()}
                nodes[node] = f"ok (window_s={peer_w:g}, rescaled)"
            raws.append(raw)
        merged = merge_tenant_raws(raws)
        rows = {t: derive_red_row(r, window_s) for t, r in merged.items()}
        if top_k > 0:
            keep = sorted(rows, key=lambda t: -rows[t]["rate_per_s"])[:top_k]
            rows = {t: rows[t] for t in keep}
        return {"window_s": window_s, "nodes": nodes, "tenants": rows}

    async def federated_trace(self, trace_id: str,
                              timeout_s: float = 2.0) -> dict:
        """``GET /cluster/trace/<id>``: assemble the full cross-process
        trace — every peer's span rings queried for the id, spans merged
        with the local ring's and ordered by the causal HLC stamps.

        ISSUE 7 satellite: when a contributing ring has WRAPPED (its
        oldest spans overwritten), the assembled trace may be missing
        spans that once existed. The response annotates the gap instead
        of silently returning a partial trace — and the wrap signal is
        PER-TRACE, not the ring's lifetime drop counter (which would
        brand every trace incomplete forever after one wrap on a
        long-running node): a ring counts as wrapped *for this trace*
        only when the trace shows a visible tear (a returned span
        references a parent absent from the assembly) or the trace's
        earliest known span starts at-or-before the ring's wrap horizon
        (the ``end_hlc`` of its oldest surviving span — everything
        overwritten ended before that, so only a trace overlapping the
        horizon can have lost leaf spans). ``spans_dropped`` counts the
        dangling parent ids, ``complete`` goes false whenever any ring
        wrapped over this trace's window, and ``rings_wrapped`` names
        the nodes. A fully-captured recent trace on a long-wrapped ring
        reports complete; without any wrapped ring, missing parents are
        attributed to slow-only captures / peer errors, not drops."""
        from .. import trace as tr
        spans = [dict(s, node=self.node_id)
                 for s in tr.TRACER.export(trace_id=trace_id, limit=1000)]
        # slow-only captures live in the slow ring exclusively
        seen = {s["span_id"] for s in spans}
        for s in tr.TRACER.export(trace_id=trace_id, limit=1000, slow=True):
            if s["span_id"] not in seen:
                spans.append(dict(s, node=self.node_id))
                seen.add(s["span_id"])
        horizons: Dict[str, int] = {}
        local_hz = tr.TRACER.ring.wrap_horizon()
        if local_hz is not None:
            horizons[self.node_id] = local_hz
        nodes = {self.node_id: "local"}
        peer_errors = False
        for node, res in (await self._scatter(
                "trace_spans", {"trace_id": trace_id},
                timeout_s)).items():
            if "error" in res:
                nodes[node] = f"error: {res['error']}"
                peer_errors = True
                continue
            nodes[node] = "ok"
            if res.get("wrap_horizon") is not None:
                horizons[res.get("node", node)] = res["wrap_horizon"]
            for s in res.get("spans") or []:
                if s.get("span_id") not in seen:
                    spans.append(dict(s, node=res.get("node", node)))
                    seen.add(s.get("span_id"))
        spans.sort(key=lambda s: s.get("start_hlc", 0))
        # the visible tears: parents referenced but absent everywhere.
        # A peer that ERRORED is the more plausible owner of a dangling
        # parent than some node's ancient wrap — with an error in the
        # response (already visible in ``nodes``) the tears are not
        # attributed to wraps at all.
        missing = {s.get("parent_id") for s in spans
                   if s.get("parent_id")
                   and s.get("parent_id") not in seen} \
            if not peer_errors else set()
        trace_min = min((s.get("start_hlc", 0) for s in spans),
                        default=None)
        wrapped = [node for node, hz in horizons.items()
                   if missing
                   or (trace_min is not None and trace_min <= hz)]
        dropped = len(missing) if wrapped else 0
        return {"trace_id": trace_id,
                "count": len(spans),
                "nodes": nodes,
                "processes": len({s.get("node") for s in spans}),
                "spans_dropped": dropped,
                "complete": not wrapped,
                "rings_wrapped": wrapped,
                "spans": spans}

    def capacity_table(self) -> dict:
        """``GET /cluster/capacity`` (ISSUE 8): per-node device capacity
        federated from the gossiped digests — automaton table bytes,
        memory watermarks — plus cluster totals.
        Pure digest reads: no scatter-gather RPC, a dead node's row just
        goes stale with its digest."""
        from .capacity import digest_capacity
        rows: Dict[str, dict] = {}
        local = digest_capacity(self.hub)
        rows[self.node_id] = {"capacity": local, "stale": False,
                              "self": True}
        total = int(local.get("table_bytes", 0))
        peak = int(local.get("mem_peak_bytes", 0))
        # ISSUE 9 satellite (PR 8 follow-up): logical-subscription rollup.
        # Physical table bytes sum per node (that IS what HBM holds, incl.
        # replicas); logical subs dedup by the gossiped subscription-set
        # fingerprint — nodes carrying an identical (tenant, count) census
        # hold replicas of one logical route table and count ONCE. Nodes
        # without a fingerprint (older digests, empty tables) count
        # individually — no dedup evidence, no dedup.
        logical_sum = 0
        fp_groups: Dict[str, int] = {}
        for node, p in self.peers().items():
            cap = (p["digest"] or {}).get("capacity") or {}
            rows[node] = {"capacity": cap, "stale": p["stale"]}
            if not p["stale"]:
                total += int(cap.get("table_bytes", 0))
                peak = max(peak, int(cap.get("mem_peak_bytes", 0)))
        for node, row in rows.items():
            if row.get("stale"):
                continue
            cap = row["capacity"]
            ls = int(cap.get("logical_subs", 0))
            logical_sum += ls
            if ls <= 0:
                # empty tables (or pre-rollup digests) form no replica
                # group — matches the apiserver single-node fallback
                continue
            key = cap.get("subs_fp") or f"node:{node}"
            fp_groups[key] = max(fp_groups.get(key, 0), ls)
        return {"nodes": rows,
                "total_table_bytes": total,
                "max_mem_peak_bytes": peak,
                "logical_subs": {
                    "sum": logical_sum,
                    "dedup": sum(fp_groups.values()),
                    "replica_groups": len(fp_groups),
                }}

    # ---------------- lifecycle ----------------------------------------------

    def start(self) -> None:
        """Publish the first digest and ride the ObsHub advisory tick for
        refreshes (refcounted — shares the tick with the throttler
        advisory)."""
        if self._started:
            return
        self._started = True
        self.refresh()
        self.agent_host.on_change(self._recompute)
        self.hub.on_advisory_tick(self.refresh)
        self.hub.start_advisory_tick(self.interval_s)

    async def stop(self) -> None:
        if not self._started:
            return
        self._started = False
        self.hub.remove_advisory_hook(self.refresh)
        await self.hub.stop_advisory_tick()
        remove = getattr(self.agent_host, "remove_on_change", None)
        if remove is not None:
            remove(self._recompute)
        try:
            self.agent_host.stop_agent(AGENT_ID)
        except Exception:  # noqa: BLE001 — host may already be stopped
            pass


# ---------------------------------------------------------------------------
# the RPC service every node serves (the scatter-gather's far end)
# ---------------------------------------------------------------------------

class ClusterObsRPCService:
    """Serves this node's raw tenant windows and span rings to peers."""

    def __init__(self, view: ClusterView) -> None:
        self.view = view

    def register(self, server) -> None:
        server.register(SERVICE, {
            "tenants": self._tenants,
            "trace_spans": self._trace_spans,
            "digest": self._digest,
        })

    async def _tenants(self, payload: bytes, okey: str) -> bytes:
        hub = self.view.hub
        return json.dumps({
            "node": self.view.node_id,
            "window_s": hub.windows.window_s,
            "tenants": hub.windows.raw_snapshot() if hub.enabled else {},
        }).encode()

    async def _trace_spans(self, payload: bytes, okey: str) -> bytes:
        from .. import trace as tr
        try:
            args = json.loads(payload.decode() or "{}")
        except ValueError:
            args = {}
        tid = args.get("trace_id")
        limit = int(args.get("limit", 1000))
        spans = tr.TRACER.export(trace_id=tid, limit=limit)
        seen = {s["span_id"] for s in spans}
        for s in tr.TRACER.export(trace_id=tid, limit=limit, slow=True):
            if s["span_id"] not in seen:
                spans.append(s)
                seen.add(s["span_id"])
        return json.dumps({"node": self.view.node_id,
                           # ISSUE 7: how far back does surviving ring
                           # history reach? (None = never wrapped; the
                           # coordinator's per-trace gap annotation keys
                           # on it, not on the lifetime drop counter)
                           "wrap_horizon": tr.TRACER.ring.wrap_horizon(),
                           "spans": spans}).encode()

    async def _digest(self, payload: bytes, okey: str) -> bytes:
        return json.dumps({"node": self.view.node_id,
                           "digest": self.view.build_digest()}).encode()

"""Tenant-sharded, replica-parallel match plane over a jax.sharding.Mesh.

This is the TPU-native analog of the reference's two scale-out axes for the
route table (SURVEY.md §2.8):

- KV **range partitioning** across dist-worker stores → here: tenants are
  hashed onto ``n_shards`` automaton shards; each mesh column holds one
  shard's tables in its HBM (sharded over the ``shard`` mesh axis).
- **Raft replication** for read scaling (replica-spread queries,
  BatchDistServerCall.replicaSelect:245) → here: every shard's tables are
  replicated over the ``replica`` mesh axis and probe batches are split
  across replicas. HOT tenants additionally replicate across the SHARD
  axis (``MeshMatcher.replicate_tenant``): their queries fan to the
  least-loaded slot of the whole grid instead of one home shard.

The per-device program is the same fixed-shape walk as single-chip
(ops.match.walk); cross-device communication is a single ``psum`` merging
the global fan-out count on device before the one host readback — probes
are routed host-side to their tenant's shard, so the match itself needs
no collective, exactly like the reference where a topic's query goes to
the one range replica that owns the tenant's key span.

ISSUE 15 makes this a first-class serving plane:

- **Per-shard patching** — every shard's automaton is a
  :class:`~bifromq_tpu.models.automaton.PatchableTrie`; route mutations
  fold into the owning shard's arenas in place and flush as NARROW
  per-shard ``idx+rows`` scatters into the stacked device tables
  (donated when the dispatch ring is idle). A churn storm at mesh scale
  runs zero rebuilds and zero match-cache generation bumps; only an
  arena reshape (node growth / edge regrow, pow2-amortized) restacks.
- **Async serving** — the mesh leg rides the shared dispatch-ring/
  watchdog/profiler machinery (prep-before-admission, fetch-on-ready,
  tokenize/dispatch/ready/fetch stages stamped per mesh step).
- **Per-shard fault domains** — one device breaker per shard on the
  shared board: an open shard's rows serve from the host oracle while
  healthy shards stay on device; half-open re-closes on canary row
  parity; watchdog reclaims quarantine shard-tagged.
"""

from __future__ import annotations

import asyncio
import functools
import hashlib
import time
from dataclasses import dataclass
from typing import Dict, FrozenSet, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import trace
from ..models.automaton import (
    NODE_COLS, CompiledTrie, PatchableTrie, _build_edge_table,
    compile_tries, tokenize,
)
from ..models.matcher import TpuMatcher, _HostPairs, _parse_levels, \
    _pow2_batch
from ..models.oracle import UNCAPPED_FANOUT, MatchedRoutes, SubscriptionTrie
from ..ops.match import (
    RT_COLS, DeviceTrie, Probes, _bucket_pairs, _expand_pairs,
    _patch_chunks, _route_walk, device_expand_enabled, expand_cap_lanes,
    expand_intervals, route_cols_from_node_tab,
)
from ..obs import OBS
from ..obs.e2e import ShardCompletionBoard
from ..utils.env import env_bool
from ..utils.hlc import HLC
from ..utils.metrics import STAGES

REPLICA_AXIS = "replica"
SHARD_AXIS = "shard"


def mesh_patch_enabled() -> bool:
    """Kill-switch for the per-shard patch plane (``BIFROMQ_MESH_PATCH=0``
    restores the overlay+compaction mutation path on the mesh)."""
    return env_bool("BIFROMQ_MESH_PATCH", True)


def tenant_shard(tenant_id: str, n_shards: int) -> int:
    """Stable tenant → shard assignment (≈ range ownership by tenant prefix)."""
    d = hashlib.blake2b(tenant_id.encode("utf-8"), digest_size=4).digest()
    return int.from_bytes(d, "little") % n_shards


@dataclass
class ShardedTables:
    """Per-shard compiled automata padded/stacked for mesh placement.

    ``pins`` is the tenant→shard OVERRIDE map this build was compiled
    with (load-driven re-placement, SURVEY §2.8 placement row): routing
    MUST consult the snapshot's own pins — a pin applied after this build
    only takes effect when the recompiled tables swap in, so queries
    always route to the shard that actually holds the tenant.
    ``replicated`` names the hot tenants compiled into EVERY shard
    (query fan-out balancing); ``compiled`` holds per-shard
    :class:`PatchableTrie` arenas once :meth:`make_patchable` ran.
    """
    node_tab: np.ndarray    # [S, N, NODE_COLS]
    edge_tab: np.ndarray    # [S, T, 4]
    child_list: np.ndarray  # [S, E]
    compiled: List[CompiledTrie]   # per-shard (for salt, matchings, roots)
    n_shards: int
    probe_len: int
    max_levels: int
    pins: Optional[Dict[str, int]] = None
    route_tab: Optional[np.ndarray] = None   # [S, N, RT_COLS]
    replicated: Optional[FrozenSet[str]] = None
    # ISSUE 17 elastic mesh: in-flight live migrations keyed by tenant
    # (reshard.MigrationState) and the shard-map version — every
    # routing-affecting transition (begin/ready/cutover/abort/resize)
    # bumps it, so operators and tests can watch the map move without
    # diffing pin dicts
    migrating: Optional[Dict[str, object]] = None
    map_version: int = 0

    def shard_of(self, tenant_id: str) -> int:
        """The tenant's HOME shard (hash placement unless pinned).
        Replicated tenants report their home shard too — callers that
        care about every copy use :meth:`shards_of`."""
        if self.pins:
            pin = self.pins.get(tenant_id)
            # same range guard as build_sharded: an out-of-range pin fell
            # back to hash placement at build time, so routing must too
            if pin is not None and 0 <= pin < self.n_shards:
                return pin
        return tenant_shard(tenant_id, self.n_shards)

    def shards_of(self, tenant_id: str) -> List[int]:
        """Every shard holding this tenant's automaton (all shards for a
        replicated hot tenant) — the mutation fan-out set."""
        if self.replicated and tenant_id in self.replicated:
            return list(range(self.n_shards))
        st = (self.migrating or {}).get(tenant_id)
        if st is not None:
            # dual-fold window (ISSUE 17): mutations land on BOTH the
            # source and the copy-in-progress target until cutover
            return [st.src, st.dst]
        return [self.shard_of(tenant_id)]

    def root_of(self, tenant_id: str) -> int:
        return self.compiled[self.shard_of(tenant_id)].root_of(tenant_id)

    def device_bytes(self) -> Dict[str, object]:
        """Per-shard HBM accounting (ISSUE 8): exact bytes of the stacks
        ``MeshMatcher._compile_shadow`` actually uploads (node_tab never
        ships), each shard's padded slice next to its real rows — the
        capacity plane the multi-chip ROADMAP item lands against."""
        from ..obs.capacity import sharded_tables_device_bytes
        return sharded_tables_device_bytes(self)

    # ------------- per-shard patchable arenas (ISSUE 15) -------------------

    @property
    def patchable(self) -> bool:
        return all(isinstance(ct, PatchableTrie) for ct in self.compiled)

    def make_patchable(self) -> "ShardedTables":
        """Wrap every shard in a :class:`PatchableTrie` arena and restack
        — the one-time conversion after a compile (in-place mutations
        then never rebuild). build_sharded already forced one common
        edge bucket count; node caps stay per-shard (pow2 + headroom)
        and the stacks pad to the max."""
        self.compiled = [ct if isinstance(ct, PatchableTrie)
                         else PatchableTrie(ct) for ct in self.compiled]
        self.restack()
        return self

    def sync_edge_caps(self) -> bool:
        """Regrow every shard's edge table to the COMMON bucket count
        (the device-side mixing mask reads one shared shape). Called on
        the MUTATION path right after a patch op — never from the flush
        — so cap changes are a pure function of the op stream: a replica
        applying the same ops regrows at the same op with the same live
        entry set, keeping arenas byte-identical (``_build_edge_table``
        is deterministic in (live set, cap)). Returns True when any
        shard regrew."""
        if not self.patchable:
            return False
        edge_cap = max(pt.edge_tab.shape[0] for pt in self.compiled)
        changed = False
        while True:
            for pt in self.compiled:
                if pt.edge_tab.shape[0] < edge_cap:
                    entries = pt.edge_tab.reshape(-1, 4)
                    live = entries[entries[:, 0] >= 0]
                    pt.edge_tab = _build_edge_table(
                        live, self.probe_len, min_cap=edge_cap)
                    pt._full.add("edge")
                    pt._dirty_edges.clear()
                    changed = True
            new_cap = max(pt.edge_tab.shape[0] for pt in self.compiled)
            if new_cap == edge_cap:
                break
            edge_cap = new_cap
        return changed

    def restack(self) -> None:
        """Rebuild the stacked host arrays from the (possibly patched)
        per-shard arenas — the full-re-upload half of a mesh reshape.
        Pure STACKING: per-shard arena shapes are never touched here
        (node caps are op-driven; edge caps sync on the mutation path),
        so replica arenas stay byte-identical to the leader's regardless
        of flush cadence. Drains every shard's dirty set: the fresh
        stacks subsume it."""
        assert len({pt.edge_tab.shape[0] for pt in self.compiled}) == 1, \
            "edge caps must be common (sync_edge_caps on the mutation path)"
        s = self.n_shards
        n_max = max(ct.node_tab.shape[0] for ct in self.compiled)
        cap = max(ct.edge_tab.shape[0] for ct in self.compiled)
        e_max = max(ct.child_list.shape[0] for ct in self.compiled)
        node_tab = np.full((s, n_max, NODE_COLS), -1, dtype=np.int32)
        edge_tab = np.full((s, cap, self.probe_len, 4), -1, dtype=np.int32)
        child_list = np.full((s, e_max), -1, dtype=np.int32)
        route_tab = np.zeros((s, n_max, RT_COLS), dtype=np.int32)
        for i, ct in enumerate(self.compiled):
            n = ct.node_tab.shape[0]
            node_tab[i, :n] = ct.node_tab
            edge_tab[i] = ct.edge_tab
            child_list[i, :ct.child_list.shape[0]] = ct.child_list
            route_tab[i, :n] = route_cols_from_node_tab(ct.node_tab)
            if isinstance(ct, PatchableTrie):
                ct.drain_dirty()
        self.node_tab = node_tab
        self.edge_tab = edge_tab
        self.child_list = child_list
        self.route_tab = route_tab

    @classmethod
    def from_patchable(cls, pts: List[PatchableTrie], *, probe_len: int,
                       max_levels: int, pins: Optional[Dict[str, int]] = None,
                       replicated=None, migrating=None,
                       map_version: int = 0) -> "ShardedTables":
        """Reassemble a mesh base from SHIPPED per-shard arenas (ISSUE 15
        mesh replication: a standby installs the leader's exact shard
        arenas — no DFS, no compile — then tracks the op stream).
        ``migrating``/``map_version`` carry a leader's in-flight
        migrations (ISSUE 17) so a standby joining mid-copy replays the
        remaining migration ops against identical state."""
        s = len(pts)
        self = cls(node_tab=np.zeros((s, 1, NODE_COLS), np.int32),
                   edge_tab=np.zeros((s, 1, probe_len, 4), np.int32),
                   child_list=np.zeros((s, 1), np.int32),
                   compiled=list(pts), n_shards=s, probe_len=probe_len,
                   max_levels=max_levels,
                   pins=dict(pins) if pins else None,
                   route_tab=None,
                   replicated=(frozenset(replicated)
                               if replicated else None),
                   migrating=dict(migrating) if migrating else None,
                   map_version=int(map_version))
        self.restack()
        return self


def build_sharded(tries: Dict[str, SubscriptionTrie], n_shards: int, *,
                  max_levels: int = 16, probe_len: int = 16,
                  pins: Optional[Dict[str, int]] = None,
                  replicate: Optional[Set[str]] = None) -> ShardedTables:
    """Compile each tenant shard with a common edge-table capacity.

    All shards share one edge-table size (power of two) so the device-side
    mixing mask is identical; node/child arrays are -1-padded to the max.
    Tenants in ``replicate`` (hot tenants) compile into EVERY shard.
    """
    by_shard: List[Dict[str, SubscriptionTrie]] = [dict() for _ in range(n_shards)]
    for tenant_id, trie in tries.items():
        if replicate and tenant_id in replicate:
            for d in by_shard:
                d[tenant_id] = trie
            continue
        sh = (pins or {}).get(tenant_id)
        if sh is None or not (0 <= sh < n_shards):
            sh = tenant_shard(tenant_id, n_shards)
        by_shard[sh][tenant_id] = trie

    compiled = [compile_tries(s, max_levels=max_levels, probe_len=probe_len)
                for s in by_shard]
    # common bucket count: the mixing mask must be identical across shards
    cap = max(ct.edge_tab.shape[0] for ct in compiled)
    # re-sync: rebuilding one shard at `cap` can itself overflow a bucket
    # and grow past it; iterate until all bucket counts agree.
    while True:
        compiled = [
            ct if ct.edge_tab.shape[0] == cap else compile_tries(
                by_shard[i], max_levels=max_levels, probe_len=probe_len,
                min_edge_cap=cap)
            for i, ct in enumerate(compiled)
        ]
        new_cap = max(ct.edge_tab.shape[0] for ct in compiled)
        if new_cap == cap:
            break
        cap = new_cap

    n_max = max(ct.node_tab.shape[0] for ct in compiled)
    e_max = max(ct.child_list.shape[0] for ct in compiled)
    node_tab = np.full((n_shards, n_max, NODE_COLS), -1, dtype=np.int32)
    edge_tab = np.full((n_shards, cap, probe_len, 4), -1, dtype=np.int32)
    child_list = np.full((n_shards, e_max), -1, dtype=np.int32)
    route_tab = np.zeros((n_shards, n_max, RT_COLS), dtype=np.int32)
    for s, ct in enumerate(compiled):
        n = ct.node_tab.shape[0]
        node_tab[s, :n] = ct.node_tab
        edge_tab[s] = ct.edge_tab
        child_list[s, :ct.child_list.shape[0]] = ct.child_list
        route_tab[s, :n] = route_cols_from_node_tab(ct.node_tab)
    return ShardedTables(node_tab=node_tab, edge_tab=edge_tab,
                         child_list=child_list, compiled=compiled,
                         n_shards=n_shards, probe_len=probe_len,
                         max_levels=max_levels,
                         pins=dict(pins) if pins else None,
                         route_tab=route_tab,
                         replicated=(frozenset(replicate)
                                     if replicate else None))


def make_mesh(n_replicas: int, n_shards: int,
              devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    assert len(devices) >= n_replicas * n_shards, (
        f"need {n_replicas * n_shards} devices, have {len(devices)}")
    grid = np.array(devices[:n_replicas * n_shards]).reshape(
        n_replicas, n_shards)
    return Mesh(grid, (REPLICA_AXIS, SHARD_AXIS))


_STEP_CACHE: Dict[Tuple, object] = {}


def make_match_step(mesh: Mesh, *, probe_len: int, k_states: int = 32,
                    max_intervals: int = 32, merge_total: bool = True):
    """Build (or reuse) the jitted multi-device match step — memoized per
    (mesh, probe_len, k_states, max_intervals): clone_empty()/reset and
    per-range matchers must share one compiled program, not re-trace
    identical closures at ~seconds each.

    Inputs:  tables sharded [S, ...] over SHARD_AXIS (replicated over
             REPLICA_AXIS); probes [R, S, B, ...] split over both axes.
    Outputs: per-topic matched-slot INTERVALS [R, S, B, A] × (start,
             count) — the same compressed MatchedRoutes the single-chip
             walk_routes emits — plus per-topic totals, overflow, and
             (with ``merge_total``) a globally psum'd matched-route count
             (the cross-shard fan-out MERGE happens on device; the host
             reads one scalar). Cross-device traffic is exactly that one
             psum: probes are shard-routed host-side, so the match itself
             needs no collective. ISSUE 19: the device-expand serving
             path drops the psum (``merge_total=False``) — its merge is
             the expand step's per-peer right_permute ring instead.
    """
    key = (mesh, probe_len, k_states, max_intervals, merge_total)
    cached = _STEP_CACHE.get(key)
    if cached is not None:
        return cached

    def local_step(edge_tab, child_list, route_tab,
                   tok_h1, tok_h2, lengths, roots, sys_mask):
        # the interval walk reads ONLY route_tab + edge_tab (+ child_list
        # for shape plumbing) — the 48B/row full node table never ships
        # to the mesh (route_tab stands in for the unused node_tab slot)
        trie = DeviceTrie(route_tab[0], edge_tab[0], child_list[0],
                          None, route_tab[0])
        probes = Probes(tok_h1[0, 0], tok_h2[0, 0], lengths[0, 0],
                        roots[0, 0], sys_mask[0, 0])
        ivl_s, ivl_c, n_routes, overflow = _route_walk(
            trie, probes, probe_len, k_states, "sort", max_intervals)
        expand = lambda x: x[None, None]
        outs = (expand(ivl_s), expand(ivl_c), expand(n_routes),
                expand(overflow))
        if not merge_total:
            return outs
        total = jax.lax.psum(n_routes.sum(), (REPLICA_AXIS, SHARD_AXIS))
        return outs + (total,)

    table_spec = P(SHARD_AXIS)
    probe_spec = P(REPLICA_AXIS, SHARD_AXIS)
    out_specs = (probe_spec, probe_spec, probe_spec, probe_spec)
    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(table_spec, table_spec, table_spec,
                  probe_spec, probe_spec, probe_spec, probe_spec, probe_spec),
        out_specs=out_specs + (P(),) if merge_total else out_specs,
        # the walk's loop carries start as replicated constants and become
        # device-varying after the first level; skip the vma consistency check
        check_vma=False,
    )
    step = jax.jit(sharded)
    _STEP_CACHE[key] = step
    return step


def _ring_allreduce(x, axis_name: str, size: int):
    """Right-rotate ring allreduce over one mesh axis: ``size - 1``
    single-neighbor hops, each adding the predecessor's running block.
    This is the ISSUE 19 merge — per-peer delivery counts cross the
    interconnect as neighbor permutes (``jax.lax.ppermute``, which XLA
    lowers to collective-permute on the chip interconnect), never as an
    all-to-host psum."""
    if size <= 1:
        return x
    perm = [(i, (i + 1) % size) for i in range(size)]
    acc = x
    buf = x
    for _ in range(size - 1):
        buf = jax.lax.ppermute(buf, axis_name, perm)
        acc = acc + buf
    return acc


def make_expand_step(mesh: Mesh, *, cap: int, n_peers: int):
    """The mesh's second device stage (ISSUE 19): per-shard ragged
    expansion of the walk's interval grids into dense (slot, row) pairs +
    stable per-peer bucketing, with the global per-peer totals merged by
    a right_permute ring (shard axis, then replica axis) instead of the
    psum the walk step used to carry.

    Inputs:  ivl_s/ivl_c [R, S, B, A] + overflow [R, S, B] (the walk's
             outputs, still device-resident) and slot_peer [S, n_cap]
             sharded over SHARD_AXIS (each shard buckets against its own
             arena's table; ids come from the PINNED shared peer list so
             bucket b means the same broker on every device).
    Outputs: per-shard compact buffers — slots/rows [R, S, cap],
             row_offsets [R, S, B+1], n_pairs [R, S], trunc [R, S, B],
             peer_slots/peer_rows [R, S, cap], peer_offsets
             [R, S, n_peers+3] — plus the ring-merged per-peer totals
             [n_peers+2] (pad bucket excluded from meaning, kept for
             shape). The host reads buffers that are already grouped by
             delivery target; nothing here ever round-trips the full
             interval grids.
    """
    key = (mesh, "expand", cap, n_peers)
    cached = _STEP_CACHE.get(key)
    if cached is not None:
        return cached
    r = mesh.shape[REPLICA_AXIS]
    s = mesh.shape[SHARD_AXIS]

    def local_expand(ivl_s, ivl_c, overflow, slot_peer):
        ivl_s, ivl_c, ovf = ivl_s[0, 0], ivl_c[0, 0], overflow[0, 0]
        # walk-overflow rows spend no buffer: their grids are junk and
        # the host oracle re-matches them regardless (same zeroing as
        # the single-chip expand_routes)
        serve_c = jnp.where(ovf[:, None], 0, ivl_c)
        slots, rows, row_offsets, n_pairs, trunc = _expand_pairs(
            ivl_s, serve_c, cap)
        if n_peers == 0:
            # no named peers: live pairs are a contiguous prefix (all
            # UNKNOWN) with pad trailing, so the counting sort is the
            # identity — same scatter-free shortcut as the single-chip
            # _expand_routes_fn
            peer_slots, peer_rows = slots, rows
            peer_offsets = jnp.stack(
                [jnp.zeros((), jnp.int32), n_pairs,
                 jnp.full((), cap, jnp.int32)])
        else:
            peer_slots, peer_rows, peer_offsets = _bucket_pairs(
                slots, rows, slot_peer[0], n_peers)
        counts = peer_offsets[1:] - peer_offsets[:-1]
        totals = _ring_allreduce(counts, SHARD_AXIS, s)
        totals = _ring_allreduce(totals, REPLICA_AXIS, r)
        expand = lambda x: x[None, None]
        return (expand(slots), expand(rows), expand(row_offsets),
                expand(n_pairs), expand(trunc), expand(peer_slots),
                expand(peer_rows), expand(peer_offsets), totals)

    table_spec = P(SHARD_AXIS)
    probe_spec = P(REPLICA_AXIS, SHARD_AXIS)
    sharded = jax.shard_map(
        local_expand, mesh=mesh,
        in_specs=(probe_spec, probe_spec, probe_spec, table_spec),
        out_specs=(probe_spec,) * 8 + (P(),),
        check_vma=False,
    )
    step = jax.jit(sharded)
    _STEP_CACHE[key] = step
    return step


# --------------- narrow per-shard device scatters (ISSUE 15) ---------------
#
# The single-chip patch flush ships idx+rows into flat tables
# (ops.match.patch_device_trie); the mesh flush ships the SAME narrow
# updates into one shard's slice of the stacked tables. ``shard`` is
# static (one trace per shard id per shape class — S is small) so the
# update lowers as a local dynamic-update on the owning mesh column.
# Donated variants update in place when the dispatch ring proves no
# in-flight reader of the old tables exists (the matcher's
# single-serving-thread contract, models/matcher._flush_patches).

@functools.partial(jax.jit, static_argnames=("shard",))
def _shard_scatter(tab, idx, vals, *, shard: int):
    return tab.at[shard, idx].set(vals)


@functools.partial(jax.jit, static_argnames=("shard",), donate_argnums=(0,))
def _shard_scatter_donated(tab, idx, vals, *, shard: int):
    return tab.at[shard, idx].set(vals)


@functools.partial(jax.jit, static_argnames=("shard",))
def _shard_slice_set(tab, vals, *, shard: int):
    return tab.at[shard].set(vals)


@functools.partial(jax.jit, static_argnames=("shard",), donate_argnums=(0,))
def _shard_slice_set_donated(tab, vals, *, shard: int):
    return tab.at[shard].set(vals)


# ---------------------- mesh serving plumbing (ISSUE 15) -------------------


@dataclass
class _MeshResult:
    """The mesh step's in-flight result leaves, shaped like the
    single-chip :class:`~bifromq_tpu.ops.match.RouteIntervals` surface the
    ring/watchdog/quarantine machinery reads (``start``/``count``/
    ``overflow`` — ``is_ready``/``copy_to_host_async`` probe these)."""
    start: object     # [R, S, B, A] int32
    count: object     # [R, S, B, A] int32
    overflow: object  # [R, S, B] bool


class _MeshExpanded:
    """The mesh twin of :class:`~bifromq_tpu.ops.match.ExpandedRoutes`
    (ISSUE 19): the walk's interval grids stay device-resident for the
    escalation slow path, while the serving fetch reads only the compact
    per-shard pair buffers + peer buckets. ``peer_totals`` is the
    ring-merged global per-peer delivery ledger — the replacement for
    the walk step's all-reduce psum scalar."""

    __slots__ = ("start", "count", "overflow", "slots", "rows",
                 "row_offsets", "n_pairs", "trunc", "peer_slots",
                 "peer_rows", "peer_offsets", "peer_totals")

    def __init__(self, **kw) -> None:
        for k, v in kw.items():
            setattr(self, k, v)

    def ready_leaves(self):
        """What the dispatch ring kicks/polls (see ExpandedRoutes): the
        compact buffers, never the [R, S, B, A] grids."""
        return (self.slots, self.rows, self.row_offsets, self.n_pairs,
                self.trunc, self.peer_slots, self.peer_rows,
                self.peer_offsets, self.peer_totals, self.overflow)


class _MeshPeerTable:
    """The pinned shared delivery-peer id space of one base snapshot:
    every shard buckets against its OWN arena's slot→peer row, but ids
    index this one ``peers`` list, so bucket b is the same broker on
    every device and per-peer totals are summable across the mesh."""

    __slots__ = ("peers", "tables")

    def __init__(self, peers, tables) -> None:
        self.peers = list(peers)
        self.tables = tables     # per-shard dist.deliverer.PeerTable

    @property
    def n_peers(self) -> int:
        return len(self.peers)


class _MultiLeaf:
    """One logical result leaf spanning every group of a split dispatch,
    quacking like a jax array for exactly the two probes the shared
    machinery makes: ``is_ready`` (ring watchdog + quarantine sweep) and
    ``copy_to_host_async`` (fetch-on-ready kick)."""

    __slots__ = ("_leaves",)

    def __init__(self, leaves) -> None:
        self._leaves = list(leaves)

    def is_ready(self) -> bool:
        for leaf in self._leaves:
            ready = getattr(leaf, "is_ready", None)
            if ready is not None and not ready():
                return False
        return True

    def copy_to_host_async(self) -> None:
        for leaf in self._leaves:
            kick = getattr(leaf, "copy_to_host_async", None)
            if kick is not None:
                try:
                    kick()
                except Exception:  # noqa: BLE001 — backend-optional
                    pass


class _SplitGroup:
    """One fault-domain group of a split mesh dispatch: the healthy-shard
    collective, or a single half-open canary shard probing alone. A group
    that times out flips ``failed`` — its rows re-route to the host
    oracle while sibling groups' results still serve."""

    __slots__ = ("shards", "res", "fault", "tag", "failed")

    def __init__(self, shards, res, fault, tag) -> None:
        self.shards = list(shards)
        self.res = res
        self.fault = fault
        self.tag = tag
        self.failed = False


class _SplitMeshResult:
    """Composite in-flight result of a SPLIT mesh step (ISSUE 16).

    Presents the ``start``/``count``/``overflow`` leaf surface the
    ring/watchdog/quarantine machinery expects (as :class:`_MultiLeaf`
    aggregates), while ``MeshMatcher._await_ready`` waits each group
    under its OWN per-shard deadline and ``_fetch_walk`` reassembles the
    full [R, S, B, …] grid from the surviving groups."""

    __slots__ = ("groups", "shape")

    def __init__(self, groups: List[_SplitGroup],
                 shape: Tuple[int, int, int, int]) -> None:
        self.groups = groups
        self.shape = shape    # full-grid (r, s, b, max_intervals)

    @property
    def start(self) -> _MultiLeaf:
        return _MultiLeaf(g.res.start for g in self.groups)

    @property
    def count(self) -> _MultiLeaf:
        return _MultiLeaf(g.res.count for g in self.groups)

    @property
    def overflow(self) -> _MultiLeaf:
        return _MultiLeaf(g.res.overflow for g in self.groups)


class _CanaryTokens:
    """Outstanding half-open canary probes for one in-flight mesh batch.

    A canary admission reserves the breaker's single probe slot; the
    verdict lands in ``_expand_walk`` (row parity) or the timeout path.
    A batch abandoned BEFORE a verdict (device error, cancellation, a
    re-prep discarding the prepared batch) must hand the slot back or
    the breaker wedges half-open refusing forever — the finalizer
    releases whatever was never settled."""

    __slots__ = ("pending",)

    def __init__(self) -> None:
        self.pending: Dict[int, object] = {}    # shard -> breaker

    def settle(self, shard: int) -> None:
        self.pending.pop(shard, None)

    def __del__(self):
        for br in self.pending.values():
            try:
                br.release_probe()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass


class _MeshPrepared:
    """Stage-1 output of the mesh leg: shard-routed, tokenized and
    uploaded probe grids, built BEFORE ring admission (ISSUE 11 overlap
    contract) with per-shard breaker admission already applied.

    ISSUE 16: when any shard breaker is not closed, ``split`` is set and
    ``grids`` stays ``None`` — the full-mesh upload is skipped because
    the step will dispatch as per-fault-domain GROUPS over sub-mesh
    slices (``grids_np`` keeps the host grids for per-group slicing)."""

    __slots__ = ("queries", "ct", "batch", "b", "slots", "grids",
                 "grids_np", "split", "lengths_np", "oracle_qis",
                 "canaries", "dispatch_shards", "tokenize_s")

    def __init__(self, **kw) -> None:
        for k, v in kw.items():
            setattr(self, k, v)


class _MeshInFlight:
    """Captured dispatch state for one mesh batch — the mesh twin of
    models.matcher._InFlight: expansion must run against THIS snapshot
    (tables object + overlay dict objects), never re-read the live
    matcher, or a mid-flight compaction swap drops overlay routes."""

    __slots__ = ("queries", "ct", "dev", "res", "tomb", "delta", "batch",
                 "b", "slots", "lengths_np", "oracle_qis", "canaries",
                 "dispatch_shards", "kernel", "fault", "fault_shards",
                 "dispatch_s", "tokenize_s", "quarantine_tag",
                 "dev_expand_s", "peer_tab")

    def __init__(self, **kw) -> None:
        self.fault = None
        self.fault_shards = {}
        self.dispatch_s = 0.0
        self.tokenize_s = 0.0
        self.quarantine_tag = "mesh"
        self.dev_expand_s = 0.0  # device-expand enqueue (ISSUE 19)
        self.peer_tab = None     # _MeshPeerTable the buckets index
        for k, v in kw.items():
            setattr(self, k, v)


@dataclass(frozen=True)
class ShardMoveCommand:
    """One balancer decision: re-pin a tenant's automaton shard (the
    TPU-shard analog of the reference's balancer→command pattern,
    KVStoreBalanceController.java:85)."""
    tenant_id: str
    from_shard: int
    to_shard: int
    reason: str


class ShardPlacementBalancer:
    """Heat-driven tenant→shard re-placement (closes SURVEY §2.8's
    placement row for the TPU plane).

    Observes per-tenant query heat (MeshMatcher.query_heat — the same
    role kv/load.py's KVLoadRecorder plays for KV ranges) and, when the
    hottest shard carries more than ``imbalance_factor`` × the coldest
    shard's heat, emits ONE command moving that shard's hottest tenant to
    the coldest shard. One move per round, like the KV balancers: each
    recompile is a placement epoch, and convergence beats thrash.
    """

    def __init__(self, *, imbalance_factor: float = 2.0,
                 min_heat: int = 64) -> None:
        self.imbalance_factor = imbalance_factor
        self.min_heat = min_heat

    def balance(self, heat: Dict[str, int], tables: ShardedTables
                ) -> Optional[ShardMoveCommand]:
        s = tables.n_shards
        shard_heat = [0] * s
        by_shard: List[List[Tuple[int, str]]] = [[] for _ in range(s)]
        for tenant_id, h in heat.items():
            if tables.replicated and tenant_id in tables.replicated:
                continue    # replicated tenants spread by construction
            sh = tables.shard_of(tenant_id)
            shard_heat[sh] += h
            by_shard[sh].append((h, tenant_id))
        hot = max(range(s), key=lambda i: shard_heat[i])
        cold = min(range(s), key=lambda i: shard_heat[i])
        if shard_heat[hot] < self.min_heat:
            return None
        if shard_heat[hot] <= self.imbalance_factor * max(1,
                                                          shard_heat[cold]):
            return None
        # move the hottest tenant whose relocation actually improves the
        # max: new cold-shard heat must stay below the current hot-shard
        # heat (moving a shard's ONLY tenant to a busier target is a loss)
        by_shard[hot].sort(reverse=True)
        for h, tenant_id in by_shard[hot]:
            if shard_heat[cold] + h < shard_heat[hot]:
                return ShardMoveCommand(
                    tenant_id=tenant_id, from_shard=hot, to_shard=cold,
                    reason=f"shard {hot} heat {shard_heat[hot]} > "
                           f"{self.imbalance_factor}x shard {cold} "
                           f"heat {shard_heat[cold]}")
        return None


class MeshMatcher(TpuMatcher):
    """The multi-device match plane with TpuMatcher's full mutation
    machinery — per-shard in-place patching first, delta overlay as the
    fallback, background shadow-compile compaction — and the SAME staged
    serving path (prepare → dispatch → ready → fetch → expand) as the
    single-chip matcher, so the async dispatch ring, watchdog, quarantine
    and profiler drive the mesh leg unchanged. A MeshMatcher drops into
    every TpuMatcher seat (DistWorkerCoProc, DistWorker) and serves live
    add_route/remove_route traffic."""

    def __init__(self, tries: Optional[Dict[str, SubscriptionTrie]] = None,
                 mesh: Optional[Mesh] = None, *,
                 max_levels: int = 16, probe_len: int = 16,
                 k_states: int = 32, auto_compact: bool = True,
                 compact_threshold: int = 2048,
                 match_cache: bool = True,
                 replicate: Optional[Set[str]] = None) -> None:
        assert mesh is not None, "MeshMatcher requires a mesh"
        super().__init__(max_levels=max_levels, k_states=k_states,
                         probe_len=probe_len, auto_compact=auto_compact,
                         compact_threshold=compact_threshold,
                         match_cache=match_cache)
        self.mesh = mesh
        self.n_replicas = mesh.shape[REPLICA_AXIS]
        self.n_shards = mesh.shape[SHARD_AXIS]
        self._step = make_match_step(mesh, probe_len=probe_len,
                                     k_states=k_states)
        # ISSUE 19: the device-expand serving path walks WITHOUT the
        # scalar psum — its cross-mesh merge is the expand step's
        # per-peer right_permute ring (jit is lazy; only the path that
        # actually serves ever compiles)
        self._step_walk_only = make_match_step(
            mesh, probe_len=probe_len, k_states=k_states,
            merge_total=False)
        self._table_sharding = NamedSharding(mesh, P(SHARD_AXIS))
        self._probe_sharding = NamedSharding(mesh, P(REPLICA_AXIS,
                                                     SHARD_AXIS))
        self._repl_sharding = NamedSharding(mesh, P())
        # ISSUE 15 fault domains: ONE breaker per shard on the shared
        # board replaces the single matcher-level device breaker — an
        # open shard's rows degrade to the host oracle while healthy
        # shards keep serving on device; the board joins them to
        # /metrics fabric.breakers + the gossip digest per label
        from ..resilience.device import (DEVICE_BREAKERS,
                                         device_breaker_enabled)
        self.device_breaker = None
        self.shard_breakers = [
            DEVICE_BREAKERS.create(label=f"shard{sh}")
            if device_breaker_enabled() else None
            for sh in range(self.n_shards)]
        # load-driven shard re-placement (SURVEY §2.8 placement): desired
        # tenant→shard pins; the serving snapshot routes by ITS OWN pin
        # copy until a recompile swaps the new assignment in
        self._pins: Dict[str, int] = {}
        # ISSUE 20: per-shard dispatch→ready completion rows — a hung
        # device is NAMED in /mesh and the e2e degraded attribution, and
        # recent ready history feeds half-open canary deadline hints
        self.completion = ShardCompletionBoard()
        # ISSUE 16 split dispatch: sub-mesh + group-table caches keyed on
        # the shard column set (one trace / one upload per healthy-mask
        # class, invalidated by compile epoch + flush count)
        self._sub_meshes: Dict[Tuple[int, ...], Mesh] = {}
        self._split_tables: Dict[Tuple[int, ...], tuple] = {}
        # hot tenants compiled into EVERY shard (ISSUE 15): queries fan
        # to the least-loaded grid slot; mutations fan to all shards
        self._replicas: Set[str] = set(replicate or ())
        self.query_heat: Dict[str, int] = {}
        self.shard_balancer = ShardPlacementBalancer()
        if tries:
            # seed path: write straight into authoritative + shadow state
            # and compile one base — building a full overlay that the
            # first refresh immediately discards would be wasted work
            for tenant_id, trie in tries.items():
                for route in trie.routes():
                    self.tries.setdefault(
                        tenant_id, SubscriptionTrie()).add(route)
                    self._shadow.setdefault(
                        tenant_id, SubscriptionTrie()).add(route)
            self._install_base(*self._compile_shadow())

    def clone_empty(self) -> "MeshMatcher":
        return MeshMatcher(mesh=self.mesh, max_levels=self.max_levels,
                           probe_len=self.probe_len, k_states=self.k_states,
                           auto_compact=self.auto_compact,
                           compact_threshold=self.compact_threshold,
                           match_cache=self.match_cache is not None,
                           replicate=set(self._replicas))

    # ---------------- compile target: sharded tables on the mesh -----------

    def _compile_shadow(self) -> Tuple[ShardedTables, tuple]:
        import time as _time
        t0 = _time.perf_counter()
        self.compile_count += 1
        tables = build_sharded(self._shadow, self.n_shards,
                               max_levels=self.max_levels,
                               probe_len=self.probe_len,
                               pins=dict(self._pins),
                               replicate=set(self._replicas))
        if self._patching_enabled():
            # ISSUE 15: per-shard patchable arenas at common capacities —
            # the padded stacked shape is what the mesh step jits against
            tables.make_patchable()
        # node_tab intentionally NOT uploaded: the interval step never
        # gathers from it (route_tab carries every column the walk reads)
        dev = (jax.device_put(tables.edge_tab, self._table_sharding),
               jax.device_put(tables.child_list, self._table_sharding),
               jax.device_put(tables.route_tab, self._table_sharding))
        # warm the step at the small-grid shape so the first serve after
        # an install (this runs on the compile thread) pays no trace
        self._warm_step(dev)
        # ISSUE 8: the mesh plane feeds the same compile accounting
        # (time + ledger attribution via _install_base) as single-chip
        self._last_compile_s = _time.perf_counter() - t0
        self.compile_time_s += self._last_compile_s
        return tables, dev

    def _warm_step(self, dev, b: int = 16) -> None:
        try:
            r, s = self.n_replicas, self.n_shards
            width = self.max_levels + 1
            z = np.zeros((r, s, b, width), dtype=np.int32)
            lengths = np.full((r, s, b), -1, dtype=np.int32)
            roots = np.full((r, s, b), -1, dtype=np.int32)
            sysm = np.zeros((r, s, b), dtype=bool)
            out = self._step(dev[0], dev[1], dev[2], z, z, lengths, roots,
                             sysm)
            out[4].block_until_ready()
        except Exception:  # noqa: BLE001 — the first serve compiles lazily
            from ..utils.metrics import warmup_failed
            warmup_failed("mesh step jit")

    # ---------------- per-shard patch plane (ISSUE 15 tentpole) ------------

    def _patching_enabled(self) -> bool:
        return super()._patching_enabled() and mesh_patch_enabled()

    def _base_patchable(self) -> bool:
        base = self._base_ct
        return isinstance(base, ShardedTables) and base.patchable

    def _patch_targets(self, tenant_id: str) -> list:
        base = self._base_ct
        if not isinstance(base, ShardedTables) \
                or not self._patching_enabled():
            return []
        pts = [base.compiled[sh] for sh in base.shards_of(tenant_id)]
        if not all(isinstance(pt, PatchableTrie) for pt in pts):
            return []
        return pts

    def _patch_frag_pending(self) -> bool:
        base = self._base_ct
        return isinstance(base, ShardedTables) and any(
            isinstance(pt, PatchableTrie) and pt.frag_pending()
            for pt in base.compiled)

    def _try_patch(self, op) -> bool:
        ok = super()._try_patch(op)
        if ok:
            # edge-cap sync ON THE MUTATION PATH (not the flush): an
            # organic bucket regrow on one shard regrows the rest to the
            # new common mask at the SAME op position — a replica
            # applying the same op stream regrows at the same point with
            # the same live sets, keeping arenas byte-identical
            base = self._base_ct
            if isinstance(base, ShardedTables):
                base.sync_edge_caps()
                # ISSUE 17 dual-fold ledger: a mutation folded into a
                # migrating tenant's TARGET arena joins (add) or leaves
                # (rm) the copied ledger, so an abort kills exactly the
                # rows this migration created — leader and standby run
                # this same hook at the same op position
                st = (base.migrating or {}).get(op[1])
                if st is not None:
                    if op[0] == "add":
                        route = op[2]
                        st.copied[(route.matcher.mqtt_topic_filter,
                                   route.receiver_url)] = route
                    elif op[0] == "rm":
                        st.copied.pop((op[2].mqtt_topic_filter, op[3]),
                                      None)
        return ok

    def _flush_patches(self, own_slots: int = 0) -> None:
        """Ship every dirty shard's host patches as NARROW per-shard
        scatters into the stacked device tables (one coalesced flush per
        dispatch, donated in place when nothing else is in flight — the
        same exclusivity proof as the single-chip flush). An arena
        reshape (node growth / edge regrow on any shard) RESTACKS at the
        new common capacities and re-uploads — pow2-amortized, never a
        recompile."""
        base = self._base_ct
        if not isinstance(base, ShardedTables) or self._device_trie is None:
            return
        dirty = [(sh, pt) for sh, pt in enumerate(base.compiled)
                 if isinstance(pt, PatchableTrie) and pt.dirty]
        if not dirty:
            return
        ring = self._ring
        donate = ring is None or (ring.in_flight <= own_slots
                                  and not len(ring.quarantine))
        t0 = time.perf_counter()
        dev_edge, dev_child, dev_route = self._device_trie
        node_dim = int(dev_route.shape[1])
        edge_shape = tuple(dev_edge.shape[1:])
        restack = any(pt.node_tab.shape[0] > node_dim
                      or tuple(pt.edge_tab.shape) != edge_shape
                      for _, pt in dirty)
        ops_total = rows_total = bytes_total = 0
        full_tags = set()
        drained: List[Tuple[PatchableTrie, int]] = []
        put = functools.partial(jax.device_put, device=self._repl_sharding)
        scatter = _shard_scatter_donated if donate else _shard_scatter
        slice_set = _shard_slice_set_donated if donate else _shard_slice_set
        try:
            if restack:
                for _, pt in dirty:
                    ops = pt.drain_dirty()[3]
                    drained.append((pt, ops))
                    ops_total += ops
                base.restack()
                dev_edge = jax.device_put(base.edge_tab,
                                          self._table_sharding)
                dev_route = jax.device_put(base.route_tab,
                                           self._table_sharding)
                rows_total = int(base.route_tab.shape[0]
                                 * base.route_tab.shape[1])
                bytes_total = int(base.edge_tab.nbytes
                                  + base.route_tab.nbytes)
                full_tags.add("restack")
            else:
                for sh, pt in dirty:
                    full, nodes, edges, ops = pt.drain_dirty()
                    drained.append((pt, ops))
                    ops_total += ops
                    if "node" in full:
                        from ..models.automaton import pad_rows
                        rows = pad_rows(
                            route_cols_from_node_tab(pt.node_tab),
                            node_dim)
                        dev_route = slice_set(dev_route, put(rows),
                                              shard=sh)
                        rows_total += int(rows.shape[0])
                        bytes_total += int(rows.nbytes)
                        full_tags.add(f"s{sh}:node")
                    elif nodes.size:
                        for idx_np in _patch_chunks(nodes.astype(np.int32)):
                            rows_np = route_cols_from_node_tab(
                                pt.node_tab[idx_np])
                            dev_route = scatter(dev_route, put(idx_np),
                                                put(rows_np), shard=sh)
                            bytes_total += int(idx_np.nbytes
                                               + rows_np.nbytes)
                        rows_total += int(nodes.size)
                    if "edge" in full:
                        dev_edge = slice_set(dev_edge, put(pt.edge_tab),
                                             shard=sh)
                        rows_total += int(pt.edge_tab.shape[0])
                        bytes_total += int(pt.edge_tab.nbytes)
                        full_tags.add(f"s{sh}:edge")
                    elif edges.size:
                        for idx_np in _patch_chunks(edges.astype(np.int32)):
                            rows_np = pt.edge_tab[idx_np]
                            dev_edge = scatter(dev_edge, put(idx_np),
                                               put(rows_np), shard=sh)
                            bytes_total += int(idx_np.nbytes
                                               + rows_np.nbytes)
                        rows_total += int(edges.size)
        except BaseException:
            # a flush that dies mid-update must not lose the drained row
            # ids (donation may even have consumed a table): mark every
            # drained shard for full re-upload from its host arenas
            for pt, ops in drained:
                pt.restore_dirty(ops)
            raise
        self._device_trie = (dev_edge, dev_child, dev_route)
        dt = time.perf_counter() - t0
        self.patch_flushes += 1
        self.patch_device_s += dt
        STAGES.record("mesh.flush", dt)
        from ..obs import OBS
        OBS.profiler.ledger.record_patch(
            reason="+".join(sorted(full_tags)) if full_tags else "rows",
            mutations=ops_total, rows=rows_total,
            bytes_shipped=bytes_total, duration_s=dt)

    # ---------------- load-driven shard re-placement ------------------------

    def pin_tenant(self, tenant_id: str, shard: int) -> None:
        """Pin a tenant's automaton to a shard; takes effect when the next
        recompiled snapshot swaps in (serving stays exact throughout —
        the installed snapshot keeps routing by its own assignment)."""
        assert 0 <= shard < self.n_shards
        self._pins[tenant_id] = shard

    def replicate_tenant(self, tenant_id: str) -> None:
        """Mark a hot tenant for replication across EVERY shard (ISSUE 15:
        query fan-out spreads over the whole grid; mutations fan to all
        copies). Takes effect when the next recompiled snapshot swaps in."""
        base = self._base_ct
        if isinstance(base, ShardedTables) and base.migrating:
            # replication lands via a forced recompile, and recompiles
            # defer while a migration owns the shard map — raising is
            # honest where silent no-op would lose the request
            raise RuntimeError(
                f"migration of {sorted(base.migrating)} in flight — "
                "finish or abort before replicating")
        if tenant_id not in self._replicas:
            self._replicas.add(tenant_id)
            self._maybe_compact(force=True)

    def rebalance_step(self) -> Optional[ShardMoveCommand]:
        """One balancer round (≈ KVStoreBalanceController.java:85's
        observe→command→apply loop for TPU shards): consult the heat
        profile, apply at most one move, kick a background recompile,
        and decay the heat window.

        This is the RECOMPILE re-placement path (pre-ISSUE 17, kept for
        the quiesce/bench baseline); :meth:`migrate_tenant` /
        :class:`~bifromq_tpu.parallel.reshard.MeshRebalancer` move live
        tenants with zero rebuilds."""
        # defer while a compaction is in flight: the compile thread reads
        # the frozen shadow, and replaying the log (or re-pinning) under
        # it would race; the heat profile persists, so the next round
        # re-evaluates after the swap
        if self._base_ct is None or self._compact_thread is not None:
            self._apply_pending_swap()
            return None
        if isinstance(self._base_ct, ShardedTables) \
                and self._base_ct.migrating:
            return None   # live migrations own the shard map right now
        cmd = self.shard_balancer.balance(self.query_heat, self._base_ct)
        if cmd is not None:
            self.pin_tenant(cmd.tenant_id, cmd.to_shard)
            # fold pending mutations + new pins into a fresh shadow build
            # on the compaction thread (_maybe_compact replays the log
            # itself, safely, before spawning); serving swaps atomically
            self._maybe_compact(force=True)
        # exponential decay: old heat fades, the window tracks current load
        self.query_heat = {t: h // 2 for t, h in self.query_heat.items()
                           if h // 2 > 0}
        return cmd

    # ---------------- elastic mesh (ISSUE 17 tentpole) ----------------------

    def _maybe_compact(self, force: bool = False) -> None:
        # a rebuild mid-migration would compile from the shadow (which
        # places the tenant by pins — still the SOURCE shard) and
        # destroy the migration's dual-fold state: defer until every
        # migration cut over or aborted; the trigger condition persists
        base = self._base_ct
        if isinstance(base, ShardedTables) and base.migrating:
            self._apply_pending_swap()
            return
        super()._maybe_compact(force)

    def migrate_tenant(self, tenant_id: str, src: Optional[int] = None,
                       dst: Optional[int] = None, *, run: bool = True):
        """Live-migrate a tenant between shards with zero rebuilds
        (ISSUE 17): streams the tenant's arena rows to ``dst`` as delta
        records through the target's patch path, dual-serves during the
        copy, then atomically cuts the shard map over. ``run=False``
        returns the started :class:`~bifromq_tpu.parallel.reshard.
        TenantMigration` for step-wise driving (services interleave
        ``step()`` with serving); ``run=True`` drives the whole ladder
        synchronously."""
        from .reshard import TenantMigration
        if dst is None:
            src, dst = None, src
        if dst is None:
            raise ValueError("migrate_tenant needs a target shard")
        mig = TenantMigration(self, tenant_id, int(dst), src=src)
        return mig.run() if run else mig.start()

    def resize_mesh(self, n_shards: int) -> None:
        """Grow or shrink the mesh's shard axis live (ISSUE 17): pin
        tenants where they are, add empty arenas / drain evacuees via
        live migration, re-place the jax mesh plumbing. Zero rebuilds."""
        from .reshard import resize_mesh
        resize_mesh(self, n_shards)

    def _rebuild_mesh_plumbing(self, n_shards: int) -> None:
        """Re-place everything derived from the shard count after a
        resize: jax Mesh + shardings + step trace + per-shard breakers +
        split caches, then a full restack/re-upload of the stacked
        tables (the pjit/NamedSharding re-placement leg — the arenas
        themselves never recompile)."""
        from ..resilience.device import (DEVICE_BREAKERS,
                                         device_breaker_enabled)
        self.mesh = make_mesh(self.n_replicas, n_shards)
        self.n_shards = n_shards
        self._step = make_match_step(self.mesh, probe_len=self.probe_len,
                                     k_states=self.k_states)
        self._step_walk_only = make_match_step(
            self.mesh, probe_len=self.probe_len, k_states=self.k_states,
            merge_total=False)
        # the peer table's stacked [S, n_cap] layout is shard-count
        # derived: a resize must rebuild it (snapshot identity alone
        # would serve a stale-shaped device table to the new mesh)
        self._peer_cache = None
        self._table_sharding = NamedSharding(self.mesh, P(SHARD_AXIS))
        self._probe_sharding = NamedSharding(self.mesh, P(REPLICA_AXIS,
                                                          SHARD_AXIS))
        self._repl_sharding = NamedSharding(self.mesh, P())
        self.shard_breakers = [
            DEVICE_BREAKERS.create(label=f"shard{sh}")
            if device_breaker_enabled() else None
            for sh in range(n_shards)]
        self._sub_meshes.clear()
        self._split_tables.clear()
        base = self._base_ct
        if isinstance(base, ShardedTables) and self._device_trie is not None:
            base.sync_edge_caps()
            base.restack()
            dev = (jax.device_put(base.edge_tab, self._table_sharding),
                   jax.device_put(base.child_list, self._table_sharding),
                   jax.device_put(base.route_tab, self._table_sharding))
            self._device_trie = dev
            self._warm_step(dev)

    def mesh_status(self) -> dict:
        """The ``GET /mesh`` / ``mesh.shard_load`` surface: shard map
        version, per-shard load rows (the same numbers the rebalancer
        scores), in-flight migrations, pins and replicas."""
        from .reshard import ShardLoadModel, migration_digest
        base = self._base_ct
        if not isinstance(base, ShardedTables):
            return {"n_replicas": self.n_replicas, "n_shards": self.n_shards,
                    "map_version": 0, "shard_load": [], "skew": 1.0,
                    "migrating": {}, "migrations": migration_digest(self),
                    "pins": {}, "replicated": [],
                    "completion": self.completion.snapshot()}
        model = ShardLoadModel()
        rows = model.rows(self)
        return {"n_replicas": self.n_replicas,
                "n_shards": base.n_shards,
                "map_version": base.map_version,
                "shard_load": rows,
                "skew": model.skew(rows),
                "migrating": {t: st.digest()
                              for t, st in (base.migrating or {}).items()},
                # ISSUE 18 leg 3: ladder progress + completed/aborted
                # tallies (the mesh.migrations digest subfield)
                "migrations": migration_digest(self),
                "pins": dict(base.pins or {}),
                "replicated": sorted(base.replicated or ()),
                # ISSUE 20: per-shard dispatch→ready rows + hung naming
                "completion": self.completion.snapshot()}

    # ---------------- staged serving path (ISSUE 15 tentpole) --------------
    #
    # The mesh leg implements the SAME prepare/dispatch/expand stage
    # contract as the single-chip matcher, so TpuMatcher's sync entry
    # (_match_batch_device) and async entry (_device_leg_async — ring
    # admission, watchdogged readiness, fetch-on-ready, quarantine,
    # profiler stamping) drive it without a mesh-specific serve loop.

    def _route_slots(self, queries, tables: ShardedTables
                     ) -> List[List[int]]:
        """Route each query to its (replica, shard) slot: home-shard
        queries round-robin across replicas; replicated hot tenants take
        the least-loaded slot of the WHOLE grid."""
        r, s = self.n_replicas, self.n_shards
        slots: List[List[int]] = [[] for _ in range(r * s)]
        replicated = tables.replicated or frozenset()
        migrating = tables.migrating or {}
        for qi, (tenant_id, _) in enumerate(queries):
            self.query_heat[tenant_id] = \
                self.query_heat.get(tenant_id, 0) + 1
            if tenant_id in replicated:
                slot = min(range(r * s), key=lambda j: len(slots[j]))
            else:
                st = migrating.get(tenant_id)
                if st is not None and st.ready:
                    # dual-SERVE window (ISSUE 17): the copy caught up,
                    # so either shard answers exactly — take the
                    # least-loaded of the tenant's two homes, like a
                    # two-shard slice of hot-tenant replication
                    slot = min((j * s + sh for j in range(r)
                                for sh in (st.src, st.dst)),
                               key=lambda j: len(slots[j]))
                else:
                    sh = tables.shard_of(tenant_id)
                    slot = min((j * s + sh for j in range(r)),
                               key=lambda j: len(slots[j]))
            slots[slot].append(qi)
        return slots

    def _prepare_probes(self, queries, batch: Optional[int] = None
                        ) -> _MeshPrepared:
        """Stage 0: shard-route + per-shard breaker admission + tokenize
        + probe-grid upload, BEFORE ring admission (the async leg preps
        batch N+1 while batch N walks). ``batch`` from the generic entry
        is a whole-batch hint; the mesh pads PER DEVICE from the busiest
        slot's occupancy (honoring the ring's adaptive floor)."""
        self._apply_pending_swap()
        if self._base_ct is None:
            self.refresh()
        tables: ShardedTables = self._base_ct
        r, s = self.n_replicas, self.n_shards
        slots = self._route_slots(queries, tables)
        # per-shard fault domain: an OPEN shard's rows never dispatch —
        # they serve from the exact host oracle while healthy shards
        # stay on device; HALF-OPEN admits this batch's rows as the
        # canary, re-closed only on row parity in _expand_walk
        oracle_qis: List[int] = []
        canaries = _CanaryTokens()
        for sh in range(s):
            br = self.shard_breakers[sh]
            if br is None or not any(slots[j * s + sh] for j in range(r)):
                continue
            verdict = br.admit()
            if verdict == "rejected":
                for j in range(r):
                    oracle_qis.extend(slots[j * s + sh])
                    slots[j * s + sh] = []
            elif verdict == "canary":
                canaries.pending[sh] = br
        # ISSUE 16 split trigger: a not-closed breaker ANYWHERE on the
        # board means the full-mesh collective would still synchronize
        # with the sick device (the psum spans every mesh slot, even
        # row-less ones) — so the step dispatches as per-fault-domain
        # groups over sub-mesh slices instead. Half-open canary shards
        # probe in their OWN group: they never rejoin the collective
        # until row parity re-closes them.
        split = bool(canaries.pending) or any(
            br is not None and br.state != "closed"
            for br in self.shard_breakers)
        floor = self._ring.planned_floor() if self._ring is not None else 16
        need = max([len(x) for x in slots] + [1])
        b = _pow2_batch(need, floor=floor)
        width = tables.max_levels + 1
        tok_h1 = np.zeros((r, s, b, width), dtype=np.int32)
        tok_h2 = np.zeros((r, s, b, width), dtype=np.int32)
        lengths = np.full((r, s, b), -1, dtype=np.int32)
        roots = np.full((r, s, b), -1, dtype=np.int32)
        sys_mask = np.zeros((r, s, b), dtype=bool)
        salts = {ct.salt for ct in tables.compiled}
        cache = self._tok_cache if len(salts) == 1 else None
        with trace.span("device.tokenize", batch=r * s * b,
                        queries=len(queries)) as sp:
            for rep in range(r):
                for sh in range(s):
                    idxs = slots[rep * s + sh]
                    if not idxs:
                        continue
                    ct = tables.compiled[sh]
                    topics = [queries[qi][1] for qi in idxs]
                    qroots = [ct.root_of(queries[qi][0]) for qi in idxs]
                    tk = tokenize(topics, qroots, max_levels=ct.max_levels,
                                  salt=ct.salt, batch=b, cache=cache)
                    tok_h1[rep, sh] = tk.tok_h1
                    tok_h2[rep, sh] = tk.tok_h2
                    lengths[rep, sh] = tk.lengths
                    roots[rep, sh] = tk.roots
                    sys_mask[rep, sh] = tk.sys_mask
            # prep-before-admission upload: the grids land on the mesh
            # NOW, so ring-parked callers hold uploaded probes bounded by
            # the prep tickets exactly like the single-chip leg. Split
            # mode defers the upload: each group device_puts only ITS
            # sub-mesh slice at dispatch, so no probe bytes ever target a
            # quarantined device.
            grids = None if split else tuple(
                jax.device_put(a, self._probe_sharding)
                for a in (tok_h1, tok_h2, lengths, roots, sys_mask))
        tokenize_s = sp.duration_s
        dispatch_shards = sorted({
            sh for sh in range(s)
            if any(slots[j * s + sh] for j in range(r))})
        return _MeshPrepared(queries=list(queries), ct=tables, batch=r * s * b,
                             b=b, slots=slots, grids=grids,
                             grids_np=(tok_h1, tok_h2, lengths, roots,
                                       sys_mask),
                             split=split, lengths_np=lengths,
                             oracle_qis=oracle_qis, canaries=canaries,
                             dispatch_shards=dispatch_shards,
                             tokenize_s=tokenize_s)

    def _dispatch_prepared(self, prep: _MeshPrepared, *,
                           donate: bool = False,
                           watchdogged: bool = False) -> _MeshInFlight:
        """Stage 1: flush per-shard patches, enqueue the mesh step.
        Returns on ENQUEUE — readiness is awaited by the caller (the
        watchdogged async ring or the sync short-poll)."""
        from ..resilience.faults import get_injector
        inj = get_injector()
        fault = None
        fault_shards: Dict[int, object] = {}
        if watchdogged:
            fault = inj.device_rule("dispatch")
        else:
            inj.check_raise("device", "tpu-device", "dispatch")
        # per-shard chaos (ISSUE 15): rules target method "mesh:shard<k>"
        # so a test can hang ONE shard's device; the fired rule both
        # shapes readiness (threaded into wait_ready) and attributes the
        # resulting timeout to that shard's breaker alone
        for sh in prep.dispatch_shards:
            try:
                rule = inj.device_rule(f"mesh:shard{sh}")
            except BaseException:
                br = self.shard_breakers[sh]
                if br is not None:
                    br.record_failure(f"injected error shard{sh}")
                    prep.canaries.settle(sh)
                raise
            if rule is not None:
                fault_shards[sh] = rule
                if fault is None:
                    fault = rule
        if self._base_ct is not prep.ct:
            # a compaction swap landed between prep and dispatch (the
            # async leg awaits ring admission in the gap): roots/salts
            # are per-snapshot, so re-prep against the installed base
            prep = self._prepare_probes(prep.queries)
        # ship any host patches accumulated since the last dispatch (one
        # coalesced narrow update per shard, so this batch walks the
        # post-mutation tables). watchdogged == the async leg, which
        # already holds its own (not-yet-dispatched) ring slot.
        self._flush_patches(own_slots=1 if watchdogged else 0)
        if prep.split:
            # ISSUE 16: a not-closed shard breaker splits the step into
            # per-fault-domain groups so the collective never
            # synchronizes with the quarantined device
            return self._dispatch_split(prep, fault, fault_shards)
        dev_edge, dev_child, dev_route = self._device_trie
        use_expand = device_expand_enabled()
        with trace.span("device.dispatch", batch=prep.batch,
                        queries=len(prep.queries)) as sp:
            if use_expand:
                ivl_s, ivl_c, _n_routes, overflow = self._step_walk_only(
                    dev_edge, dev_child, dev_route, *prep.grids)
            else:
                ivl_s, ivl_c, _n_routes, overflow, _total = self._step(
                    dev_edge, dev_child, dev_route, *prep.grids)
            sp.set_tag("kernel", "mesh")
        dispatch_s = sp.duration_s
        res = _MeshResult(start=ivl_s, count=ivl_c, overflow=overflow)
        dev_expand_s = 0.0
        peer_tab = None
        if use_expand:
            # ISSUE 19: the second device stage — per-shard fan-out
            # expansion + peer bucketing, cross-mesh totals merged by the
            # right_permute ring; the fetch then reads compact buffers
            # that are already grouped by delivery broker
            with trace.span("device.expand", batch=prep.batch) as sp:
                peer_tab, slot_peer = self._mesh_peer_table(prep.ct)
                step = make_expand_step(
                    self.mesh, cap=prep.b * expand_cap_lanes(),
                    n_peers=peer_tab.n_peers)
                (slots, rows, row_offsets, n_pairs, trunc, peer_slots,
                 peer_rows, peer_offsets, peer_totals) = step(
                    ivl_s, ivl_c, overflow, slot_peer)
                res = _MeshExpanded(
                    start=ivl_s, count=ivl_c, overflow=overflow,
                    slots=slots, rows=rows, row_offsets=row_offsets,
                    n_pairs=n_pairs, trunc=trunc, peer_slots=peer_slots,
                    peer_rows=peer_rows, peer_offsets=peer_offsets,
                    peer_totals=peer_totals)
            dev_expand_s = sp.duration_s
        tag = "mesh"
        if fault_shards:
            tag = "mesh:" + ",".join(f"shard{sh}"
                                     for sh in sorted(fault_shards))
        return _MeshInFlight(
            queries=prep.queries, ct=prep.ct, dev=self._device_trie,
            res=res, dev_expand_s=dev_expand_s, peer_tab=peer_tab,
            tomb=self._tomb, delta=self._delta, batch=prep.batch,
            b=prep.b, slots=prep.slots, lengths_np=prep.lengths_np,
            oracle_qis=prep.oracle_qis, canaries=prep.canaries,
            dispatch_shards=prep.dispatch_shards, kernel="mesh",
            fault=fault, fault_shards=fault_shards,
            dispatch_s=dispatch_s, tokenize_s=prep.tokenize_s,
            quarantine_tag=tag)

    def _mesh_peer_table(self, tables: ShardedTables):
        """The per-shard slot→delivery-peer tables of one base snapshot,
        stacked + device_put over SHARD_AXIS, with the peer-id space
        PINNED to the union of every shard's deliverer servers (sorted)
        so bucket ids line up across devices. Cached on base-snapshot
        identity only — patch flushes must NOT invalidate it (a stale
        slot lands in UNKNOWN, a fast-path miss, not a correctness
        risk; see models/matcher.TpuMatcher.__init__)."""
        cached = self._peer_cache
        if cached is not None and cached[0] is tables:
            return cached[1], cached[2]
        from ..dist.deliverer import build_peer_table, server_of
        arenas = [ct.matchings_arr for ct in tables.compiled]
        keys: Set[str] = set()
        for arr in arenas:
            for m in arr:
                dkey = getattr(m, "deliverer_key", None)
                if isinstance(dkey, str):
                    sid = server_of(dkey)
                    if sid:
                        keys.add(sid)
        peers = sorted(keys)
        tabs = [build_peer_table(arr, peers=peers) for arr in arenas]
        n_cap = max([t.slot_peer.shape[0] for t in tabs] + [1])
        # pad rows read UNKNOWN (= n_peers): a slot id past a shard's
        # arena can only come from post-table patches — host fallback
        stacked = np.full((self.n_shards, n_cap), len(peers), np.int32)
        for sh, t in enumerate(tabs):
            stacked[sh, :t.slot_peer.shape[0]] = t.slot_peer
        tab = _MeshPeerTable(peers, tabs)
        dev = jax.device_put(stacked, self._table_sharding)
        self._peer_cache = (tables, tab, dev)
        return tab, dev

    # ------------- split mesh dispatch (ISSUE 16 tentpole leg 1) -----------

    def _sub_mesh(self, cols: Tuple[int, ...]) -> Mesh:
        """The surviving mesh slice for one fault-domain group: the same
        replica rows over only the group's shard columns. Cached per
        column set so ``make_match_step``'s (mesh, …) memo key is stable
        — one trace per healthy-mask class, not per batch."""
        cached = self._sub_meshes.get(cols)
        if cached is None:
            cached = Mesh(self.mesh.devices[:, list(cols)],
                          (REPLICA_AXIS, SHARD_AXIS))
            self._sub_meshes[cols] = cached
        return cached

    def _group_tables(self, tables: ShardedTables, cols: Tuple[int, ...]):
        """Stack the group's per-shard HOST arenas onto its sub-mesh.

        Built from ``tables.compiled[sh]`` (the authoritative arenas),
        NOT the full-mesh host stacks — those go stale after narrow
        per-shard device flushes. Cached per (column set, base identity,
        compile epoch, flush count): a mutation bumps ``patch_flushes``
        via the pre-dispatch flush, so the cache never serves pre-
        mutation rows. Edge caps are common across shards by the
        ``sync_edge_caps`` invariant, so no edge padding happens here
        (padding would change the device-side mixing mask)."""
        ver = (id(tables), self.compile_count, self.patch_flushes)
        cached = self._split_tables.get(cols)
        if cached is not None and cached[0] == ver:
            return cached[1]
        sub = [tables.compiled[sh] for sh in cols]
        g = len(sub)
        cap = sub[0].edge_tab.shape[0]
        n_max = max(ct.node_tab.shape[0] for ct in sub)
        e_max = max(ct.child_list.shape[0] for ct in sub)
        edge_tab = np.full((g, cap, tables.probe_len, 4), -1,
                           dtype=np.int32)
        child_list = np.full((g, e_max), -1, dtype=np.int32)
        route_tab = np.zeros((g, n_max, RT_COLS), dtype=np.int32)
        for i, ct in enumerate(sub):
            edge_tab[i] = ct.edge_tab
            child_list[i, :ct.child_list.shape[0]] = ct.child_list
            route_tab[i, :ct.node_tab.shape[0]] = \
                route_cols_from_node_tab(ct.node_tab)
        sharding = NamedSharding(self._sub_mesh(cols), P(SHARD_AXIS))
        dev = (jax.device_put(edge_tab, sharding),
               jax.device_put(child_list, sharding),
               jax.device_put(route_tab, sharding))
        self._split_tables[cols] = (ver, dev)
        return dev

    def _dispatch_split(self, prep: _MeshPrepared, fault,
                        fault_shards: Dict[int, object]) -> _MeshInFlight:
        """Dispatch the step as per-fault-domain GROUPS: one collective
        over every closed shard (psum spans only the surviving slice) +
        one single-shard group per half-open canary — a canary probes
        alone and rejoins the collective only after row parity re-closes
        its breaker. Each group gets its own result leaves, chaos rule
        and quarantine tag, so ``_await_ready`` can time out ONE group
        (attributing the hang to its shards) while siblings' results
        still serve from device."""
        tables: ShardedTables = prep.ct
        r, s, b = self.n_replicas, self.n_shards, prep.b
        closed = tuple(sh for sh in prep.dispatch_shards
                       if sh not in prep.canaries.pending)
        group_cols: List[Tuple[int, ...]] = \
            ([closed] if closed else []) + \
            [(sh,) for sh in sorted(prep.canaries.pending)
             if sh in prep.dispatch_shards]
        groups: List[_SplitGroup] = []
        with trace.span("device.dispatch", batch=prep.batch,
                        queries=len(prep.queries)) as sp:
            for cols in group_cols:
                sub_mesh = self._sub_mesh(cols)
                step = make_match_step(sub_mesh, probe_len=self.probe_len,
                                       k_states=self.k_states)
                dev = self._group_tables(tables, cols)
                psharding = NamedSharding(sub_mesh, P(REPLICA_AXIS,
                                                      SHARD_AXIS))
                idx = list(cols)
                grids = tuple(
                    jax.device_put(np.ascontiguousarray(a[:, idx]),
                                   psharding)
                    for a in prep.grids_np)
                ivl_s, ivl_c, _n_routes, overflow, _total = \
                    step(*dev, *grids)
                gf = next((fault_shards[sh] for sh in cols
                           if sh in fault_shards), fault)
                tag = "mesh:" + ",".join(f"shard{sh}" for sh in cols)
                groups.append(_SplitGroup(
                    cols, _MeshResult(start=ivl_s, count=ivl_c,
                                      overflow=overflow), gf, tag))
            sp.set_tag("kernel", "mesh_split")
        dispatch_s = sp.duration_s
        tag = "mesh"
        if fault_shards:
            tag = "mesh:" + ",".join(f"shard{sh}"
                                     for sh in sorted(fault_shards))
        return _MeshInFlight(
            queries=prep.queries, ct=prep.ct, dev=self._device_trie,
            res=_SplitMeshResult(groups, (r, s, b)),
            tomb=self._tomb, delta=self._delta, batch=prep.batch,
            b=prep.b, slots=prep.slots, lengths_np=prep.lengths_np,
            oracle_qis=prep.oracle_qis, canaries=prep.canaries,
            dispatch_shards=prep.dispatch_shards, kernel="mesh_split",
            fault=fault, fault_shards=fault_shards,
            dispatch_s=dispatch_s, tokenize_s=prep.tokenize_s,
            quarantine_tag=tag)

    def _note_shard_ready(self, sh: int, t0_ns: int,
                          start_hlc: int = 0) -> None:
        """One completion row (ISSUE 20): per-shard dispatch→ready timing
        (from ``t0_ns``, a ``monotonic_ns`` stamp, to now) into the stage
        histogram + the board (deferred span like the batcher's
        queue-wait — duration is only known at readiness); a
        previously-hung shard that serves again clears its degraded
        attribution."""
        now_ns = time.monotonic_ns()
        trace.record_finished("device.shard_ready", trace.current_ctx(),
                              start_ns=t0_ns, end_ns=now_ns,
                              start_hlc=start_hlc, tags={"shard": sh})
        self.completion.note_ready(sh, (now_ns - t0_ns) * 1e-9)
        OBS.e2e.clear_degraded(f"mesh:shard{sh}")

    async def _await_ready_shards(self, ring, fl) -> None:
        """Non-split readiness with PER-SHARD completion attribution
        (ISSUE 20 tentpole part 3): every dispatched shard polls the
        same collective leaves under its OWN chaos-rule view, so the
        board gets one dispatch→ready row per shard and a timeout NAMES
        the hung shard(s) instead of raising an anonymous step-wide
        error. The collective still completes (or times out) as one
        step — attribution costs concurrent polls, never extra syncs."""
        from ..resilience.device import (DeviceTimeoutError,
                                         device_deadline_s)
        shards = list(fl.dispatch_shards or ())
        if len(shards) <= 1:
            t0, shlc = time.monotonic_ns(), HLC.INST.get()
            await ring.wait_ready(fl.res, fault=fl.fault)
            for sh in shards:
                self._note_shard_ready(sh, t0, shlc)
            return
        deadline = device_deadline_s()
        t0, shlc = time.monotonic_ns(), HLC.INST.get()
        hung: List[int] = []

        async def wait_shard(sh: int) -> None:
            try:
                await ring.wait_ready(
                    fl.res, deadline_s=deadline,
                    fault=fl.fault_shards.get(sh, fl.fault))
                self._note_shard_ready(sh, t0, shlc)
            except DeviceTimeoutError:
                hung.append(sh)
        await asyncio.gather(*(wait_shard(sh) for sh in shards))
        if hung:
            for sh in sorted(hung):
                self.completion.note_hung(sh, "deadline")
                OBS.e2e.set_degraded(f"mesh:shard{sh}", "device_timeout")
            raise DeviceTimeoutError(
                deadline or 0.0,
                " (shard%s)" % ",".join(str(sh) for sh in sorted(hung)))

    async def _await_ready(self, ring, fl) -> None:
        """Per-group readiness waits under PER-SHARD deadlines (ISSUE 16):
        a hung group is indicted alone — its leaves go to quarantine
        shard-tagged, its breakers open, its rows re-route to the host
        oracle — while every surviving group's device results serve.
        Only an all-groups hang escalates to the whole-step
        DeviceTimeoutError the base leg already handles."""
        res = fl.res
        if not isinstance(res, _SplitMeshResult):
            await self._await_ready_shards(ring, fl)
            return
        if not res.groups:
            return
        from ..resilience.device import (DeviceTimeoutError,
                                         shard_deadline_s)
        deadline = shard_deadline_s()
        t0, shlc = time.monotonic_ns(), HLC.INST.get()

        async def wait_group(g: _SplitGroup) -> None:
            # ISSUE 20: a half-open canary probes alone under a deadline
            # scaled to ITS OWN recent completion history (never looser
            # than the configured shard deadline)
            gd = deadline
            if len(g.shards) == 1 and g.shards[0] in fl.canaries.pending:
                gd = self.completion.deadline_hint(g.shards[0], deadline)
            try:
                await ring.wait_ready(g.res, deadline_s=gd,
                                      fault=g.fault)
                for sh in g.shards:
                    self._note_shard_ready(sh, t0, shlc)
            except DeviceTimeoutError:
                g.failed = True
        await asyncio.gather(*(wait_group(g) for g in res.groups))
        failed = [g for g in res.groups if g.failed]
        if not failed:
            return
        if len(failed) == len(res.groups):
            # no surviving device evidence: whole-step timeout semantics
            # (the caller reclaims the composite, _note_device_timeout
            # attributes every dispatched shard)
            raise DeviceTimeoutError(deadline or 0.0,
                                     " (all shard groups)")
        from ..utils.metrics import FABRIC, FabricMetric
        s = self.n_shards
        for g in failed:
            ring.reclaim(g.res, tag=g.tag)
            FABRIC.inc(FabricMetric.DEVICE_TIMEOUT)
            # blame the shard(s) whose chaos rule shaped the hang when
            # one fired; a collective-group stall with no finer evidence
            # indicts every member
            blame = [sh for sh in g.shards
                     if sh in fl.fault_shards] or list(g.shards)
            for sh in blame:
                br = self.shard_breakers[sh]
                if br is not None:
                    br.record_failure("shard group timeout")
                    fl.canaries.settle(sh)
                # ISSUE 20: the hung shard is NAMED on the completion
                # board and in the e2e plane's degraded attribution
                self.completion.note_hung(sh, "group timeout")
                OBS.e2e.set_degraded(f"mesh:shard{sh}", "shard_group_timeout")
            for sh in g.shards:
                for rep in range(self.n_replicas):
                    fl.oracle_qis.extend(fl.slots[rep * s + sh])

    @staticmethod
    def _fetch_walk(res):
        if isinstance(res, _MeshExpanded):
            # ISSUE 19 fast path: compact per-shard pair buffers only —
            # the [R, S, B, A] interval grids stay on device (truncated
            # rows fetch them lazily via _fetch_escalation_grids)
            from ..resilience.faults import get_injector
            get_injector().check_raise("device", "tpu-device", "fetch")
            overflow = np.array(res.overflow)
            pairs = _HostPairs(
                slots=np.asarray(res.slots), rows=np.asarray(res.rows),
                row_offsets=np.asarray(res.row_offsets),
                n_pairs=np.asarray(res.n_pairs),
                trunc=np.asarray(res.trunc),
                peer_slots=np.asarray(res.peer_slots),
                peer_rows=np.asarray(res.peer_rows),
                peer_offsets=np.asarray(res.peer_offsets), res=res)
            return overflow, pairs, None
        if not isinstance(res, _SplitMeshResult):
            return TpuMatcher._fetch_walk(res)
        from ..resilience.faults import get_injector
        get_injector().check_raise("device", "tpu-device", "fetch")
        r, s, b = res.shape
        live = []
        a = 1
        for g in res.groups:
            if g.failed:
                continue    # never synchronize with a hung group's leaves
            gs = np.array(g.res.start)
            live.append((g, gs, np.array(g.res.count),
                         np.array(g.res.overflow)))
            a = max(a, gs.shape[-1])
        starts = np.zeros((r, s, b, a), dtype=np.int32)
        counts = np.zeros((r, s, b, a), dtype=np.int32)
        overflow = np.zeros((r, s, b), dtype=bool)
        for g, gs, gc, go in live:
            for i, sh in enumerate(g.shards):
                starts[:, sh, :, :gs.shape[-1]] = gs[:, i]
                counts[:, sh, :, :gc.shape[-1]] = gc[:, i]
                overflow[:, sh] = go[:, i]
        # failed/absent shards stay all-zero: their rows are already in
        # oracle_qis, so _expand_walk overwrites them with exact rows
        return overflow, starts, counts

    def _note_device_timeout(self, fl) -> None:
        """Watchdog attribution (ISSUE 15): a timed-out mesh step feeds
        the breaker(s) of the shard(s) whose chaos rule shaped the hang
        when one fired — else every dispatched shard (a whole-mesh stall
        has no finer evidence). Subsequent batches then exclude exactly
        the opened shards while the rest keep serving on device."""
        shards = sorted(getattr(fl, "fault_shards", {}) or ()) \
            or list(getattr(fl, "dispatch_shards", ()) or ())
        for sh in shards:
            br = self.shard_breakers[sh]
            if br is not None:
                br.record_failure("mesh step timeout")
                fl.canaries.settle(sh)
            # ISSUE 20: name the implicated shard(s) on the completion
            # board (idempotent when _await_ready already did)
            self.completion.note_hung(sh, "mesh step timeout")
            OBS.e2e.set_degraded(f"mesh:shard{sh}", "device_timeout")
        # canary shards not implicated got no verdict: hand the probe
        # slot back so the breaker can re-probe on the next batch
        for sh, br in list(fl.canaries.pending.items()):
            br.release_probe()
            fl.canaries.settle(sh)

    @staticmethod
    def _canon_routes(m: MatchedRoutes):
        return (sorted((r.matcher.mqtt_topic_filter, r.receiver_url)
                       for r in m.normal),
                {f: sorted(r.receiver_url for r in ms)
                 for f, ms in m.groups.items()})

    def _expand_walk(self, fl: _MeshInFlight, overflow, starts_a, counts_a,
                     max_persistent_fanout: int,
                     max_group_fanout: int) -> List[MatchedRoutes]:
        """Stage 3: one vectorized interval expansion for the whole
        [R,S,B] grid + overlay correction against the _MeshInFlight
        SNAPSHOT, canary parity settlement, and exact host-oracle serving
        for breaker-excluded / unknown-tenant / overflowed rows."""
        tables: ShardedTables = fl.ct
        r, s, b = overflow.shape
        # ISSUE 19: device-expanded batches hand the pairs pre-computed
        # per shard; only buffer-truncated rows re-expand on host from
        # the lazily fetched interval grids (exact, just not bucketed)
        pairs = starts_a if isinstance(starts_a, _HostPairs) else None
        g_s = g_c = None
        if pairs is None:
            a = starts_a.shape[-1]
            flat_slots, flat_offs = expand_intervals(
                starts_a.reshape(-1, a), counts_a.reshape(-1, a))
        out: List[Optional[MatchedRoutes]] = [None] * len(fl.queries)
        oracle_qis: Set[int] = set(fl.oracle_qis)
        canary_rows: Dict[int, List[int]] = {}
        for rep in range(r):
            for sh in range(s):
                ct = tables.compiled[sh]
                for bi, qi in enumerate(fl.slots[rep * s + sh]):
                    tenant_id, levels = fl.queries[qi]
                    if ct.root_of(tenant_id) < 0 \
                            or overflow[rep, sh, bi] \
                            or fl.lengths_np[rep, sh, bi] < 0:
                        # tenant newer than the base / active-set or
                        # interval overflow / topic too deep: exact
                        # host fallback (not a fault-domain degradation)
                        oracle_qis.add(qi)
                        continue
                    if pairs is None:
                        row_i = (rep * s + sh) * b + bi
                        row = flat_slots[
                            flat_offs[row_i]:flat_offs[row_i + 1]]
                    elif pairs.trunc[rep, sh, bi]:
                        if g_s is None:
                            g_s, g_c = TpuMatcher._fetch_escalation_grids(
                                pairs.res)
                        row, _ = expand_intervals(
                            g_s[rep, sh, bi:bi + 1],
                            g_c[rep, sh, bi:bi + 1])
                    else:
                        offs = pairs.row_offsets[rep, sh]
                        row = pairs.slots[rep, sh][offs[bi]:offs[bi + 1]]
                    tomb = fl.tomb.get(tenant_id)
                    delta = fl.delta.get(tenant_id)
                    if not tomb and delta is None:
                        out[qi] = self._routes_from_slots(
                            ct, row, max_persistent_fanout,
                            max_group_fanout)
                    else:
                        out[qi] = self._expand_with_overlay(
                            ct, row, tomb or (), delta,
                            _parse_levels(levels),
                            max_persistent_fanout, max_group_fanout)
                    if sh in fl.canaries.pending:
                        canary_rows.setdefault(sh, []).append(qi)
        if pairs is not None:
            # the delivery-plane surface (deliverer.bucket_views reads
            # the per-shard buckets through this; bench reads totals)
            self.last_expanded = (pairs, fl.peer_tab)
        # half-open settlement: a canary shard re-closes ONLY when its
        # device rows are row-identical to the host oracle; wrong rows
        # reopen the breaker and the oracle rows serve instead
        for sh, br in list(fl.canaries.pending.items()):
            qis = canary_rows.get(sh)
            if not qis:
                # every row of the canary shard fell to the oracle —
                # no device evidence either way: release the probe
                br.release_probe()
                fl.canaries.settle(sh)
                continue
            oracle = self.match_from_tries(
                [fl.queries[qi] for qi in qis],
                max_persistent_fanout=max_persistent_fanout,
                max_group_fanout=max_group_fanout)
            if all(self._canon_routes(out[qi]) == self._canon_routes(om)
                   for qi, om in zip(qis, oracle)):
                br.record_success()
            else:
                br.record_failure("canary row parity")
                for qi, om in zip(qis, oracle):
                    out[qi] = om
            fl.canaries.settle(sh)
        if oracle_qis:
            qlist = sorted(oracle_qis)
            rows = self.match_from_tries(
                [fl.queries[qi] for qi in qlist],
                max_persistent_fanout=max_persistent_fanout,
                max_group_fanout=max_group_fanout)
            for qi, m in zip(qlist, rows):
                out[qi] = m
            degraded = len(fl.oracle_qis)
            if degraded:
                # ONLY breaker-excluded rows are a degradation; the
                # overflow/unknown-tenant fallback is normal serving
                from ..utils.metrics import FABRIC, FabricMetric
                FABRIC.inc(FabricMetric.MATCH_DEGRADED, degraded)
                with trace.span("match.degraded", reason="shard_breaker",
                                n_queries=degraded):
                    pass
        return out

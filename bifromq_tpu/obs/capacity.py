"""Device capacity model & placement planner (ISSUE 8 tentpole, part 1).

The two headline ROADMAP items — real-TPU validation of the async
pipeline and the 10M-sub sharded matcher — are capacity questions before
they are performance questions: "will this tenant population's automaton
tables fit in HBM on this shard" is answered today by dispatching and
watching for OOMs.
Tailwind (PAPERS.md) argues accelerator systems need a first-class
capacity/placement model instead; TrieJax's relational formulation makes
trie footprints exactly computable from arena shapes. This module is
that model:

- **Exact accounting** of everything the matcher puts on device, derived
  from the same shape math the upload paths use (``DeviceTrie.
  from_compiled``, ``MeshMatcher._compile_shadow``): level-packed
  node/edge arenas, the narrow count/route column tables, per-shard mesh
  slices (padded exactly as ``build_sharded`` pads them), probe/result
  buffers × dispatch-ring depth, and the transient compile-time double
  (old + new base both alive across a background compaction swap).
- **A planner** (``CapacityPlanner.fits``) that predicts table bytes for
  a subscription count that has never been built, from per-subscription
  coefficients — calibrated from any live ``CompiledTrie`` or defaulting
  to the repo's measured 1M-wildcard-sub build — and renders the HBM
  headroom verdict.
- **Validation**: ``measure()`` reads the actually-uploaded device
  arrays, so ``GET /capacity`` can report model-vs-live parity (the
  tier-2 gate requires <10% error; the shape math makes it exact).

Layering: this module lives in ``obs`` but describes ``models``/
``parallel`` objects — every models import is deferred inside a function
so the obs package stays importable without jax, and no import cycle
forms (models.matcher imports the obs package at module level).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from ..utils.env import env_int as _env_int

_I32 = 4            # every automaton table is int32
_EDGE_ENTRY_I32 = 4  # edge_tab entries are (node, h1, h2, child)


def _next_pow2(n: int, floor: int = 1) -> int:
    p = max(1, floor)
    while p < n:
        p *= 2
    return p




# ---------------------------------------------------------------------------
# exact accounting from compiled/placed objects
# ---------------------------------------------------------------------------

def compiled_trie_device_bytes(ct) -> Dict[str, int]:
    """Byte-exact footprint of one single-chip base snapshot as
    ``DeviceTrie.from_compiled`` places it: the full node arena, the
    bucketed edge hash table, the CSR child list, and the narrow
    count/route column tables derived at upload time."""
    from ..ops.match import CT_COLS, RT_COLS
    n = int(ct.node_tab.shape[0])
    out = dict(ct.arena_bytes())
    out["count_tab"] = n * CT_COLS * _I32
    out["route_tab"] = n * RT_COLS * _I32
    out["total"] = sum(out.values())
    return out


def walk_bytes_from_compiled(ct) -> int:
    """edge_tab + route_tab bytes of this base — the two tables the
    interval walk gathers from — computed host-side from shapes."""
    from ..ops.match import RT_COLS
    return (int(ct.edge_tab.size) + int(ct.node_tab.shape[0]) * RT_COLS) \
        * _I32


def sharded_tables_device_bytes(tables) -> Dict[str, object]:
    """Byte-exact footprint of a mesh base (``ShardedTables``) as
    ``MeshMatcher._compile_shadow`` places it: edge/child/route stacks
    sharded over the mesh — node_tab is intentionally NOT uploaded
    (route_tab carries every column the interval walk reads). Per-shard
    slices are the stacked (padded) rows divided by S, which is exactly
    what each shard's HBM holds."""
    s = int(tables.n_shards)
    total = {
        "edge_tab": int(tables.edge_tab.size) * _I32,
        "child_list": int(tables.child_list.size) * _I32,
        "route_tab": (int(tables.route_tab.size) * _I32
                      if tables.route_tab is not None else 0),
    }
    total["total"] = sum(total.values())
    per_shard = []
    for i, ct in enumerate(tables.compiled):
        # the shard's REAL rows vs its padded slice: padding waste is the
        # price of one common mesh shape (build_sharded pads to the max)
        real = walk_bytes_from_compiled(ct) \
            + int(ct.child_list.shape[0]) * _I32
        per_shard.append({
            "shard": i,
            "padded_bytes": total["total"] // s,
            "real_bytes": real,
            "n_nodes": int(ct.node_tab.shape[0]),
            "n_slots": ct.n_slots,
        })
    return {"n_shards": s, "total": total, "per_shard": per_shard,
            "pad_waste_ratio": round(
                1.0 - (sum(p["real_bytes"] for p in per_shard)
                       / max(1, total["total"])), 4)}


def probe_bytes(batch: int, max_levels: int = 16) -> int:
    """One uploaded probe batch (``Probes``): two [B, L+1] token-hash
    lanes, [B] lengths + roots, [B] bool sys mask."""
    width = max_levels + 1
    return batch * (2 * width * _I32 + 2 * _I32 + 1)


def result_bytes(batch: int, max_intervals: int = 32) -> int:
    """One walk result (``RouteIntervals``): [B, A] start + count,
    [B] n_routes, [B] bool overflow."""
    return batch * (2 * max_intervals * _I32 + _I32 + 1)


def inflight_bytes(batch: int, *, max_levels: int = 16,
                   max_intervals: int = 32,
                   ring_depth: Optional[int] = None) -> Dict[str, int]:
    """Device bytes pinned by the async dispatch ring: ``ring_depth``
    in-flight slots, each holding a probe batch and its result arrays,
    plus ONE prep-ahead probe batch (ISSUE 11: stage-1 prep uploads
    before ring admission; the ring's prep tickets bound it to depth+1,
    so exactly one extra probe set can be resident). The ring's walk
    donates its probe buffers and XLA may alias the results into them,
    so a slot costs max(probes, results), not the sum."""
    if ring_depth is None:
        from ..models.pipeline import PIPELINE_DEPTH
        ring_depth = PIPELINE_DEPTH
    pb = probe_bytes(batch, max_levels)
    rb = result_bytes(batch, max_intervals)
    per_slot = max(pb, rb)
    return {"ring_depth": int(ring_depth), "batch": int(batch),
            "probe_bytes": pb, "result_bytes": rb, "per_slot": per_slot,
            "prep_ahead_bytes": pb,
            "total": per_slot * int(ring_depth) + pb}


def measure(matcher) -> Dict[str, object]:
    """Model-vs-live parity for one matcher's INSTALLED base: predicted
    bytes from the host-side shape math next to the bytes of the jax
    arrays actually resident on device. Single-chip and mesh bases both
    supported; an uninstalled matcher reports ``installed: False``."""
    base = getattr(matcher, "_base_ct", None)
    dev = getattr(matcher, "_device_trie", None)
    if base is None or dev is None:
        return {"installed": False}

    def arr_bytes(a) -> int:
        return int(a.size) * a.dtype.itemsize if a is not None else 0

    if hasattr(base, "compiled"):            # mesh ShardedTables
        predicted = sharded_tables_device_bytes(base)
        measured = sum(arr_bytes(a) for a in dev)
        predicted_total = predicted["total"]["total"]
        kind = "mesh"
    else:                                    # single-chip CompiledTrie
        predicted = compiled_trie_device_bytes(base)
        measured = sum(arr_bytes(a) for a in (
            dev.node_tab, dev.edge_tab, dev.child_list,
            dev.count_tab, dev.route_tab))
        predicted_total = predicted["total"]
        kind = "single"
    err = (abs(measured - predicted_total) / measured) if measured else 0.0
    out = {
        "installed": True,
        "kind": kind,
        "predicted": predicted,
        "measured_device_bytes": measured,
        "parity_error": round(err, 6),
        "overlay_routes": getattr(matcher, "overlay_size", 0),
    }
    # ISSUE 9: arena headroom + tombstone/fragmentation accounting for
    # patchable bases — the numbers the patch-vs-compact decision reads
    if hasattr(base, "patch_stats"):
        out["patch"] = base.patch_stats()
        out["patch_fallbacks"] = getattr(matcher, "patch_fallbacks", 0)
        out["patched_mutations"] = getattr(matcher, "patch_count", 0)
    elif kind == "mesh" and any(hasattr(c, "patch_stats")
                                for c in base.compiled):
        # ISSUE 15: per-shard arena accounting for the patched mesh base
        out["patch"] = {"shards": [
            c.patch_stats() if hasattr(c, "patch_stats") else None
            for c in base.compiled]}
        out["patch_fallbacks"] = getattr(matcher, "patch_fallbacks", 0)
        out["patched_mutations"] = getattr(matcher, "patch_count", 0)
    ring = getattr(matcher, "_ring", None)
    if ring is not None:
        out["inflight"] = inflight_bytes(
            getattr(ring, "base_floor", 16),
            max_levels=matcher.max_levels,
            max_intervals=getattr(matcher, "max_intervals", 32),
            ring_depth=ring.depth)
    return out


# ---------------------------------------------------------------------------
# the planner: predict footprints that have never been built
# ---------------------------------------------------------------------------

@dataclass
class CapacityPlanner:
    """Per-subscription footprint coefficients → byte predictions.

    Defaults are calibrated from the repo's measured 1M-wildcard-sub
    build (ROADMAP: ~1.6M automaton nodes, ~67MB edge table =
    2^18 buckets × probe_len 16 × 4 × int32): ~1.6 trie nodes and ~1.6
    literal edges per subscription, hash buckets grown until no bucket
    overflows at ~0.4 entry load. ``calibrate`` replaces them with exact
    ratios from any live ``CompiledTrie`` so same-workload predictions
    are shape-exact.
    """

    nodes_per_sub: float = 1.6
    edges_per_sub: float = 1.6
    slots_per_sub: float = 1.0
    edge_load: float = 0.4       # valid entries / table entry capacity
    calibrated_from: Optional[str] = None

    def calibrate(self, ct, n_subs: int) -> "CapacityPlanner":
        """Fit the coefficients to a live base snapshot compiled from
        ``n_subs`` subscriptions (returns self for chaining)."""
        import numpy as np
        if n_subs <= 0:
            raise ValueError("n_subs must be positive")
        n = int(ct.node_tab.shape[0])
        entries = int(ct.edge_tab.size) // _EDGE_ENTRY_I32
        valid = int(np.count_nonzero(
            np.asarray(ct.edge_tab).reshape(-1, _EDGE_ENTRY_I32)[:, 0] >= 0))
        self.nodes_per_sub = n / n_subs
        self.edges_per_sub = valid / n_subs
        self.slots_per_sub = max(1, ct.n_slots) / n_subs
        self.edge_load = valid / entries if entries else self.edge_load
        self.calibrated_from = f"live:{n_subs}"
        return self

    def predict_tables(self, n_subs: int, *, probe_len: int = 16,
                       n_shards: int = 1,
                       mesh_placed: bool = False) -> Dict[str, int]:
        """Predicted per-device table bytes for ``n_subs`` subscriptions
        spread evenly over ``n_shards`` shards. ``mesh_placed`` models
        the mesh upload (no node_tab / count_tab on device) vs the
        single-chip upload (all five tables)."""
        from ..models.automaton import NODE_COLS
        from ..ops.match import CT_COLS, RT_COLS
        per_shard_subs = max(1, math.ceil(n_subs / max(1, n_shards)))
        n = max(1, math.ceil(per_shard_subs * self.nodes_per_sub))
        edges = max(1, math.ceil(per_shard_subs * self.edges_per_sub))
        # the builder grows the bucket table (power-of-two bucket counts,
        # min_edge_cap=8) until no bucket overflows; the calibrated load
        # factor folds that growth into one ratio
        buckets = _next_pow2(
            math.ceil(edges / (self.edge_load * probe_len)), floor=8)
        out = {
            "n_nodes": n,
            "n_edges": edges,
            "edge_buckets": buckets,
            "edge_tab": buckets * probe_len * _EDGE_ENTRY_I32 * _I32,
            "child_list": edges * _I32,
            "route_tab": n * RT_COLS * _I32,
        }
        if mesh_placed:
            out["node_tab"] = 0
            out["count_tab"] = 0
        else:
            out["node_tab"] = n * NODE_COLS * _I32
            out["count_tab"] = n * CT_COLS * _I32
        out["total"] = (out["edge_tab"] + out["child_list"]
                        + out["route_tab"] + out["node_tab"]
                        + out["count_tab"])
        return out

    def fits(self, n_subs: int, mesh: Optional[object] = None,
             *, batch: int = 16,
             max_levels: int = 16, probe_len: int = 16,
             max_intervals: int = 32, ring_depth: Optional[int] = None,
             hbm_limit_bytes: Optional[int] = None) -> Dict[str, object]:
        """The planner verdict: would ``n_subs`` subscriptions fit this
        device (or each shard of ``mesh``) — WITHOUT building or
        dispatching anything.

        ``mesh`` is ``None`` (single chip), an ``int`` shard count, or a
        ``(replicas, shards)`` tuple / ``jax.sharding.Mesh``. The HBM
        verdict compares predicted resident bytes — tables + the
        dispatch ring's in-flight buffers + the transient compile-time
        double (old and new base both alive across a compaction swap) —
        against ``hbm_limit_bytes`` (default: the live device's
        ``memory_stats`` limit when probeable, else the
        ``BIFROMQ_HBM_BYTES`` env knob, else unknown).
        """
        n_shards = 1
        n_replicas = 1
        if mesh is not None:
            if isinstance(mesh, int):
                n_shards = mesh
            elif isinstance(mesh, (tuple, list)):
                n_replicas, n_shards = int(mesh[0]), int(mesh[1])
            else:                       # jax Mesh
                from ..parallel.sharded import REPLICA_AXIS, SHARD_AXIS
                n_replicas = int(mesh.shape[REPLICA_AXIS])
                n_shards = int(mesh.shape[SHARD_AXIS])
        tables = self.predict_tables(n_subs, probe_len=probe_len,
                                     n_shards=n_shards,
                                     mesh_placed=n_shards > 1)
        flight = inflight_bytes(batch, max_levels=max_levels,
                                max_intervals=max_intervals,
                                ring_depth=ring_depth)
        # a background compaction holds TWO bases alive across the swap
        # (in-flight dispatches pin the old tables) — plan for the peak
        transient = tables["total"]
        per_device = tables["total"] + flight["total"]
        peak = per_device + transient
        if hbm_limit_bytes is None:
            hbm_limit_bytes = _live_hbm_limit()
        headroom = (hbm_limit_bytes - peak
                    if hbm_limit_bytes is not None else None)
        return {
            "n_subs": n_subs,
            "mesh": {"replicas": n_replicas, "shards": n_shards},
            "tables": tables,
            "inflight": flight,
            "compile_transient_bytes": transient,
            "per_device_bytes": per_device,
            "per_device_peak_bytes": peak,
            "hbm": {
                "limit_bytes": hbm_limit_bytes,
                "headroom_bytes": headroom,
                "fits": (headroom >= 0 if headroom is not None else None),
            },
        }

    def snapshot(self) -> dict:
        return {"nodes_per_sub": round(self.nodes_per_sub, 4),
                "edges_per_sub": round(self.edges_per_sub, 4),
                "slots_per_sub": round(self.slots_per_sub, 4),
                "edge_load": round(self.edge_load, 4),
                "calibrated_from": self.calibrated_from}


def _live_hbm_limit() -> Optional[int]:
    """The live device's HBM byte limit: the env override first, then
    the guarded memory probe (never triggers backend init — same
    discipline as ``DeviceGauges._memory_stats``)."""
    env = _env_int("BIFROMQ_HBM_BYTES", 0)
    if env > 0:
        return env
    from . import OBS
    ms = OBS.device.memory_stats()
    if ms.get("available"):
        limits = [d.get("bytes_limit", 0) for d in ms.get("devices", ())]
        limits = [x for x in limits if x > 0]
        if limits:
            return min(limits)
    return None


# ---------------------------------------------------------------------------
# report surfaces (GET /capacity, the gossip digest, bench records)
# ---------------------------------------------------------------------------

def default_planner(matchers: Sequence = ()) -> CapacityPlanner:
    """A planner calibrated from the largest installed single-chip base
    among ``matchers`` (n_subs approximated by slot count — every
    subscription contributes ≥1 matching slot), else the 1M-sub
    defaults."""
    planner = CapacityPlanner()
    best = None
    for m in matchers:
        base = getattr(m, "_base_ct", None)
        if base is None or hasattr(base, "compiled"):
            continue
        if best is None or base.n_slots > best.n_slots:
            best = base
    if best is not None and best.n_slots >= 64:
        # small bases calibrate to noise (fixed pow2 floors dominate);
        # keep the defaults below that
        planner.calibrate(best, best.n_slots)
    return planner


def calibrate_report(*, n_subs: Optional[int] = None,
                     matchers: Optional[Sequence] = None,
                     before: Optional[CapacityPlanner] = None
                     ) -> Dict[str, object]:
    """Operational ``calibrate`` (ISSUE 11 satellite, ROADMAP sharding
    follow-up (c)): re-fit the planner's per-subscription coefficients
    from the live base using the TRUE logical subscription count (one
    per live route in the authoritative tries — the slot-count proxy
    ``default_planner`` uses overcounts group slots and tombstones), and
    report old-vs-new coefficient deltas plus the predicted-bytes shift
    at a target population. Served by ``GET /capacity?calibrate=1``;
    ``scripts/calibrate_capacity.sh`` is the one-liner.
    ``matchers``/``before`` let ``capacity_report`` hand over its
    already-computed scan instead of walking every base twice."""
    if matchers is None:
        from . import OBS
        matchers = OBS.device.matchers()
    if before is None:
        before = default_planner(matchers)
    best = best_m = None
    for m in matchers:
        base = getattr(m, "_base_ct", None)
        if base is None or hasattr(base, "compiled"):
            continue
        if best is None or base.n_slots > best.n_slots:
            best, best_m = base, m
    if best is None:
        return {"calibrated": False,
                "reason": "no installed single-chip base"}
    live_subs = sum(len(t) for t in
                    (getattr(best_m, "tries", None) or {}).values())
    if live_subs <= 0:
        live_subs = max(1, best.n_slots)
    after = CapacityPlanner().calibrate(best, live_subs)
    fields = ("nodes_per_sub", "edges_per_sub", "slots_per_sub",
              "edge_load")
    target = n_subs or live_subs
    return {
        "calibrated": True,
        "n_subs_live": live_subs,
        "before": before.snapshot(),
        "after": after.snapshot(),
        "delta": {k: round(getattr(after, k) - getattr(before, k), 4)
                  for k in fields},
        "predicted_table_bytes": {
            "n_subs": target,
            "before": before.predict_tables(target)["total"],
            "after": after.predict_tables(target)["total"],
        },
    }


def capacity_report(*, n_subs: Optional[int] = None,
                    mesh: Optional[object] = None,
                    memory: bool = True,
                    calibrate: bool = False) -> Dict[str, object]:
    """The ``GET /capacity`` payload: model-vs-live parity for every
    registered matcher, the guarded HBM stats, the planner coefficients,
    and (when ``n_subs`` is given) a full ``fits`` verdict. With
    ``calibrate`` the response also carries the live re-fit + deltas
    (and the ``fits`` verdict uses the re-fit coefficients)."""
    from . import OBS
    matchers = OBS.device.matchers()
    rows = [measure(m) for m in matchers]
    planner = default_planner(matchers)
    out: Dict[str, object] = {
        "matchers": rows,
        "planner": planner.snapshot(),
        "table_bytes": sum(r.get("measured_device_bytes", 0) for r in rows),
    }
    if calibrate:
        cal = calibrate_report(n_subs=n_subs, matchers=matchers,
                               before=planner)
        out["calibrate"] = cal
        if cal.get("calibrated"):
            planner = CapacityPlanner(**{
                k: cal["after"][k] for k in
                ("nodes_per_sub", "edges_per_sub", "slots_per_sub",
                 "edge_load")})
            planner.calibrated_from = cal["after"]["calibrated_from"]
    installed = [r for r in rows if r.get("installed")]
    if installed:
        out["parity_error"] = max(r["parity_error"] for r in installed)
    if memory:
        out["hbm"] = OBS.device.memory_stats()
        out["hbm_limit_bytes"] = _live_hbm_limit()
    if n_subs is not None:
        out["fits"] = planner.fits(n_subs, mesh=mesh)
    return out


def record_compile_event(base, *, reason: str, duration_s: float,
                         salt=None,
                         generation_bumped: bool = False) -> None:
    """Stamp one base build into the process compile ledger — the ONE
    site deriving a ledger event's table bytes
    from a compiled base (single-chip or mesh). Matcher installs and
    bench builds both route here, so their records cannot diverge.
    Best-effort: accounting must never fail a build."""
    from . import OBS
    try:
        if hasattr(base, "compiled"):        # mesh ShardedTables
            tb = sharded_tables_device_bytes(base)["total"]["total"]
            n_nodes = sum(int(c.node_tab.shape[0])
                          for c in base.compiled)
            kind = "mesh"
            if salt is None:
                salt = tuple(getattr(c, "salt", None)
                             for c in base.compiled)
        else:                                # single-chip CompiledTrie
            tb = compiled_trie_device_bytes(base)["total"]
            n_nodes = base.n_nodes
            kind = "single"
            if salt is None:
                salt = base.salt
        OBS.profiler.ledger.record(
            reason=reason, duration_s=duration_s, salt=salt,
            n_nodes=n_nodes, table_bytes=tb,
            generation_bumped=generation_bumped, kind=kind)
    except Exception:  # noqa: BLE001 — telemetry must not raise
        pass


def digest_capacity(hub) -> Dict[str, object]:
    """The compact capacity field gossiped in the health digest (ISSUE 8:
    ``GET /cluster/capacity`` federates these — no extra RPC plane).
    Host-side shape math + cached watermarks only: the digest refresh
    must never block on the device."""
    table_bytes = 0
    logical: List[Tuple[str, int]] = []
    for m in hub.device.matchers():
        # ISSUE 9 satellite (PR 8 follow-up): dedup-aware LOGICAL
        # subscription count next to the physical table bytes — counted
        # from the authoritative tries (one entry per live subscription,
        # regardless of arena padding/tombstones), fingerprinted so the
        # cluster rollup can count replicated tables once
        for tenant_id, trie in (getattr(m, "tries", None) or {}).items():
            logical.append((tenant_id, len(trie)))
        base = getattr(m, "_base_ct", None)
        if base is None:
            continue
        try:
            if hasattr(base, "compiled"):
                table_bytes += sharded_tables_device_bytes(
                    base)["total"]["total"]
            else:
                table_bytes += compiled_trie_device_bytes(base)["total"]
        except Exception:  # noqa: BLE001 — telemetry must not raise
            continue
    out: Dict[str, object] = {"table_bytes": table_bytes,
                              "mem_peak_bytes": hub.device.peak_memory_bytes}
    out["logical_subs"] = sum(c for _, c in logical)
    if logical:
        import hashlib
        h = hashlib.blake2b(digest_size=8)
        for tenant_id, c in sorted(logical):
            h.update(f"{tenant_id}:{c};".encode("utf-8"))
        out["subs_fp"] = h.hexdigest()
    limit = _env_int("BIFROMQ_HBM_BYTES", 0)
    if limit > 0:
        out["hbm_limit_bytes"] = limit
    return out

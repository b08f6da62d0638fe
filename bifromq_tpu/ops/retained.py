"""Retained-message lookup kernel: wildcard filters probe a topic trie.

The roles-swapped twin of ops.match (BASELINE.json: "retain-store's
retained-message wildcard lookup reuses the same compiled-trie kernel"):
the automaton stores *concrete retained topics*; probes are SUBSCRIBE
*filters* that may contain '+'/'#'. Reference behavior:
bifromq-retain .../store/RetainStoreCoProc.batchMatch with
RetainTopicIndex.java:35 + RetainMatcher.java:36 semantics.

One program, :func:`retained_walk_ext`. Per probe level:
- literal  → the same single-choice bucket and hit compare as
             ops.match._edge_lookup, gathered straight from the resident
             [NB, P, 4] edge table (:func:`_bucket_lookup`)
- '+'      → expand to ALL literal children of every active node (a CSR
             range read + cumsum-partitioned compaction; overflow → host)
- '#'      → terminal: every active node's whole DFS subtree matches; with
             pre-order numbering a subtree's matching slots are ONE
             contiguous range, so the device emits (start, count) pairs —
             no per-descendant work at all.

[MQTT-4.7.2-1]: a root-level '+'/'#' must not reach '$'-prefixed first
levels. The compiler sorts sys children first (automaton.py), so the walk
just skips a prefix of the child range / slot range when i == 0.

Output is slot *ranges* (not node ids): [B, K, 2] (start, count), since '#'
can accept whole subtrees. The host expands slots → retained messages.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.automaton import (
    EXT_COLS, EXT_COUNT, EXT_OWN, EXT_START, KIND_HASH, KIND_LIT,
    KIND_PLUS, NODE_CCOUNT, NODE_CSTART, NODE_RCOUNT, NODE_RSTART,
    NODE_SUB_RCOUNT, NODE_SYS_CCOUNT, NODE_SYS_SLOTS, TokenizedFilters,
)
from .match import _mix_u32


def _bucket_lookup(edge_tab: jax.Array, node: jax.Array, h1: jax.Array,
                   h2: jax.Array) -> jax.Array:
    """Exact literal-child lookup; node/h1/h2 are [B,K]; returns child or -1.

    The answer of ops.match._edge_lookup (same bucket, same compare), but
    the bucket rows are gathered from the [NB, P, 4] table as it lies in
    HBM. Viewing it as [NB, P*4] first makes XLA re-lay-out the whole
    table (bucket index minor on the chip) on every loop step: 6.2 of a
    7.6 ms walk at 1,048,576 buckets on v5e.
    """
    mask = jnp.uint32(edge_tab.shape[0] - 1)
    b1 = (_mix_u32(node, h1, h2) & mask).astype(jnp.int32)
    rows = edge_tab[b1]                                      # [B,K,P,4]
    hit = ((rows[..., 0] == node[..., None])
           & (rows[..., 1] == h1[..., None])
           & (rows[..., 2] == h2[..., None]))
    return jnp.max(jnp.where(hit, rows[..., 3], -1), axis=-1)


@jax.tree_util.register_pytree_node_class
@dataclass
class FilterProbes:
    tok_h1: jax.Array
    tok_h2: jax.Array
    tok_kind: jax.Array
    lengths: jax.Array
    roots: jax.Array

    def tree_flatten(self):
        return (self.tok_h1, self.tok_h2, self.tok_kind, self.lengths,
                self.roots), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @staticmethod
    def from_tokenized(t: TokenizedFilters, device=None) -> "FilterProbes":
        put = functools.partial(jax.device_put, device=device)
        return FilterProbes(put(t.tok_h1), put(t.tok_h2), put(t.tok_kind),
                            put(t.lengths), put(t.roots))


# ---------------- patched retained tables & extras-aware walk (ISSUE 13) ----
#
# A RetainedPatchableTrie keeps the compile-time pre-order subtree ranges
# frozen and parks patch-era topic slots in a per-node EXTRAS plane
# (retained_plane/patched.py): ext_tab[node] = (start, count, own_idx, pad)
# into an extra_list of slot ids. The extras-aware walk gathers one more
# 16B row per active state and emits a SECOND (start, count) pair per
# lane — '#' emits the node's extras run, the final level emits the
# node's own patch slot — so patched serving pays one extra gather, not
# a rebuild. Base ranges and extras are disjoint by construction; dead
# slots in either are host-filtered exactly like the forward matcher's
# tombstones.

@jax.tree_util.register_pytree_node_class
@dataclass
class RetainedDeviceTables:
    """Device-resident retained automaton: the compiled tables + the
    extras plane (zero-sized/empty for a pristine compiled index, so the
    one jit serves both)."""
    node_tab: jax.Array     # [N, NODE_COLS] int32
    edge_tab: jax.Array     # [NB, P, 4] int32
    child_list: jax.Array   # [C] int32
    ext_tab: jax.Array      # [N, EXT_COLS] int32
    extra_list: jax.Array   # [E] int32 (slot ids; -1 slack)

    def tree_flatten(self):
        return (self.node_tab, self.edge_tab, self.child_list,
                self.ext_tab, self.extra_list), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @staticmethod
    def from_trie(ct, device=None) -> "RetainedDeviceTables":
        put = functools.partial(jax.device_put, device=device)
        ext = getattr(ct, "ext_tab", None)
        if ext is None:
            ext = np.zeros((ct.node_tab.shape[0], EXT_COLS),
                           dtype=np.int32)
            ext[:, EXT_OWN] = -1
        extra = getattr(ct, "extra_list", None)
        if extra is None:
            extra = np.full(1, -1, dtype=np.int32)
        return RetainedDeviceTables(
            node_tab=put(np.ascontiguousarray(ct.node_tab)),
            edge_tab=put(np.ascontiguousarray(ct.edge_tab)),
            child_list=put(np.ascontiguousarray(ct.child_list)),
            ext_tab=put(np.ascontiguousarray(ext)),
            extra_list=put(np.ascontiguousarray(extra)))


@jax.tree_util.register_pytree_node_class
@dataclass
class RetainedScanResult:
    """One retained scan batch in flight. Field names follow the
    DispatchRing fetch contract (``start``/``count``/``overflow`` are
    the leaves ``start_fetch``/``wait_ready`` poll): ``start`` holds the
    BASE slot ranges [B, K, 2], ``count`` the EXTRAS index ranges
    [B, K, 2] (into ``extra_list``), ``overflow`` the per-row escape
    flag."""
    start: jax.Array
    count: jax.Array
    overflow: jax.Array

    def tree_flatten(self):
        return (self.start, self.count, self.overflow), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@functools.partial(jax.jit, static_argnames=("k_states",))
def retained_walk_ext(tables: RetainedDeviceTables, probes: FilterProbes,
                      *, k_states: int = 32) -> RetainedScanResult:
    """The retained walk over the compiled tables and their extras plane.

    Returns base slot ranges, extras index ranges (resolved through
    ``extra_list`` host-side) and the overflow flags, all [B, K, ...].
    Ranges with count <= 0 are empty; padding probes produce no ranges.
    Beside the base ranges, each active state gathers one 16B ext row
    and emits a second (start, count) pair.
    """
    b, width = probes.tok_h1.shape
    max_levels = width - 1
    k = k_states
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]

    act0 = jnp.full((b, k), -1, dtype=jnp.int32)
    act0 = act0.at[:, 0].set(jnp.where(probes.lengths >= 0, probes.roots, -1))
    ranges0 = jnp.zeros((b, k, 2), dtype=jnp.int32)
    ext0 = jnp.zeros((b, k, 2), dtype=jnp.int32)
    overflow0 = jnp.zeros((b,), dtype=bool)

    def body(i, carry):
        act, ranges, ext_ranges, overflow = carry
        valid = act >= 0                                     # [B,K]
        stepping = (i < probes.lengths)[:, None]
        node_rec = tables.node_tab[act.clip(0)]              # [B,K,12]
        ext_rec = tables.ext_tab[act.clip(0)]                # [B,K,4]
        kind = jax.lax.dynamic_index_in_dim(probes.tok_kind, i, axis=1)
        at_root = i == 0

        # ---- '#': base subtree range + the node's extras run --------------
        is_hash = stepping & (kind == KIND_HASH)
        sys_skip = jnp.where(at_root, node_rec[..., NODE_RCOUNT]
                             + node_rec[..., NODE_SYS_SLOTS], 0)
        h_start = node_rec[..., NODE_RSTART] + sys_skip
        h_count = node_rec[..., NODE_SUB_RCOUNT] - sys_skip
        hash_ranges = jnp.stack([h_start, jnp.where(valid, h_count, 0)],
                                axis=-1)
        ranges = jnp.where((is_hash & valid)[..., None], hash_ranges, ranges)
        # extras need no root '$' skip: sys-rooted topics never enter the
        # tenant root's run (the patcher applies [MQTT-4.7.2-1] at insert)
        hash_ext = jnp.stack(
            [ext_rec[..., EXT_START],
             jnp.where(valid, ext_rec[..., EXT_COUNT], 0)], axis=-1)
        ext_ranges = jnp.where((is_hash & valid)[..., None], hash_ext,
                               ext_ranges)

        # ---- final level consumed: base own slots + own patch slot --------
        is_final = (i == probes.lengths)[:, None]
        own = jnp.stack([node_rec[..., NODE_RSTART],
                         jnp.where(valid, node_rec[..., NODE_RCOUNT], 0)],
                        axis=-1)
        ranges = jnp.where((is_final & valid)[..., None], own, ranges)
        own_idx = ext_rec[..., EXT_OWN]
        own_ext = jnp.stack(
            [own_idx.clip(0),
             jnp.where(valid & (own_idx >= 0), 1, 0)], axis=-1)
        ext_ranges = jnp.where((is_final & valid)[..., None], own_ext,
                               ext_ranges)

        # ---- successors -----------------------------------------------------
        live = stepping & (kind != KIND_HASH) & valid
        h1 = jnp.broadcast_to(
            jax.lax.dynamic_index_in_dim(probes.tok_h1, i, axis=1), (b, k))
        h2 = jnp.broadcast_to(
            jax.lax.dynamic_index_in_dim(probes.tok_h2, i, axis=1), (b, k))
        exact = _bucket_lookup(tables.edge_tab, act.clip(0), h1, h2)
        exact = jnp.where(live & (kind == KIND_LIT), exact, -1)

        sys_cskip = jnp.where(at_root, node_rec[..., NODE_SYS_CCOUNT], 0)
        c_start = node_rec[..., NODE_CSTART] + sys_cskip
        c_count = jnp.where(live & (kind == KIND_PLUS),
                            node_rec[..., NODE_CCOUNT] - sys_cskip, 0)
        offsets = jnp.cumsum(c_count, axis=1)
        total = offsets[:, -1]
        overflow = overflow | (total > k)
        slot_ids = jnp.arange(k, dtype=jnp.int32)[None, :]
        src = jnp.sum(offsets[:, None, :] <= slot_ids[..., None], axis=-1)
        src_c = src.clip(0, k - 1)
        base = jnp.take_along_axis(offsets, src_c, axis=1) \
            - jnp.take_along_axis(c_count, src_c, axis=1)
        within = slot_ids - base
        list_idx = (jnp.take_along_axis(c_start, src_c, axis=1) + within)
        plus_kids = tables.child_list[
            list_idx.clip(0, tables.child_list.shape[0] - 1)]
        plus_kids = jnp.where(slot_ids < total[:, None], plus_kids, -1)

        is_plus_row = kind == KIND_PLUS
        cand = jnp.where(is_plus_row, plus_kids, exact)
        cvalid = cand >= 0
        pos = jnp.cumsum(cvalid, axis=1) - 1
        pos = jnp.where(cvalid & (pos < k), pos, 2 * k)
        new_act = jnp.full((b, k), -1, dtype=jnp.int32)
        new_act = new_act.at[rows, pos].set(cand, mode="drop")
        return new_act, ranges, ext_ranges, overflow

    upper = jnp.clip(jnp.max(probes.lengths, initial=-1) + 1, 0,
                     max_levels + 1)
    act, ranges, ext_ranges, overflow = jax.lax.fori_loop(
        0, upper, body, (act0, ranges0, ext0, overflow0))
    return RetainedScanResult(start=ranges, count=ext_ranges,
                              overflow=overflow)


# ---------------- device-side retained patch flush (ISSUE 13) ---------------

def patch_retained_tables(dev: RetainedDeviceTables, rt, *, device=None,
                          donate: bool = False
                          ) -> Tuple[RetainedDeviceTables, dict]:
    """Ship a RetainedPatchableTrie's pending dirty set to device as
    narrow scatters (idx + values only), mirroring
    :func:`ops.match.patch_device_trie` for the five retained tables.
    Reshaped tables (arena growth / edge regrow) re-put whole; the
    caller re-warms the walk then. A failed flush restores full-upload
    dirt (the host arenas stay authoritative; nothing is lost)."""
    full, node_rows, edge_rows, ext_rows, child_idx, extra_idx, ops = \
        rt.drain_dirty_retained()
    try:
        return _patch_retained(dev, rt, full, node_rows, edge_rows,
                               ext_rows, child_idx, extra_idx, ops,
                               device=device, donate=donate)
    except BaseException:
        rt.restore_dirty(ops)
        raise


def _patch_retained(dev, rt, full, node_rows, edge_rows, ext_rows,
                    child_idx, extra_idx, ops, *, device, donate):
    from .match import _patch_chunks, _scatter_rows, _scatter_rows_donated
    put = functools.partial(jax.device_put, device=device)
    scatter = _scatter_rows_donated if donate else _scatter_rows
    stats = {"rows": 0, "bytes": 0, "ops": ops, "reshaped": False,
             "full": sorted(full), "donated": bool(donate)}

    def _table(name, host, dev_tab, rows):
        nonlocal stats
        if name in full:
            stats["reshaped"] |= tuple(host.shape) != tuple(dev_tab.shape)
            stats["rows"] += int(host.shape[0])
            stats["bytes"] += int(host.nbytes)
            return put(host)
        if rows.size:
            stats["rows"] += int(rows.size)
            for idx_np in _patch_chunks(rows.astype(np.int32)):
                vals_np = host[idx_np]
                stats["bytes"] += int(idx_np.nbytes) + int(vals_np.nbytes)
                dev_tab = scatter(dev_tab, put(idx_np), put(vals_np))
        return dev_tab

    node_tab = _table("node", rt.node_tab, dev.node_tab, node_rows)
    edge_tab = _table("edge", rt.edge_tab, dev.edge_tab, edge_rows)
    child_list = _table("child", rt.child_list, dev.child_list, child_idx)
    ext_tab = _table("ext", rt.ext_tab, dev.ext_tab, ext_rows)
    extra_list = _table("extra", rt.extra_list, dev.extra_list, extra_idx)
    return RetainedDeviceTables(
        node_tab=node_tab, edge_tab=edge_tab, child_list=child_list,
        ext_tab=ext_tab, extra_list=extra_list), stats

"""Fixed-shape NFA trie walk over the compiled automaton (the hot kernel).

This replaces the reference's per-PUBLISH iterator join
(bifromq-dist-worker .../cache/TenantRouteMatcher.java:68 +
.../trie/TopicFilterIterator.java:38) with a batched, fully static walk:

- B topics × K active NFA states advance one topic level per step
  (``lax.fori_loop`` over max_levels+1 static iterations — XLA-friendly, no
  data-dependent control flow).
- Literal-edge lookup = ONE contiguous bucket-row gather of the
  single-choice hash table (TPU gather cost is per-index, not per-byte).
- '+' / '#' transitions = one packed node-record gather per step; the '#'
  child's route count is folded into the parent record (NODE_HRCOUNT) so
  counting costs no extra gather.
- Successor compaction to K slots: per-row descending sort via a static
  bitonic compare-exchange network (_bitonic_desc — XLA's generic sort
  lowering measured 10x slower); a mask+cumsum+scatter alternative is
  selectable for on-hardware A/B (``compaction="scatter"``).
- Topics whose active set would exceed K set an overflow flag and are
  re-walked on device at higher K in a fused escalation pass
  (walk_count_only); only rows that exceed even that fall back to the host
  oracle — the same bounded-work-then-fallback contract the reference's
  20-probe seek heuristic embodies (TenantRouteMatcher.java:129-136).

Outputs are accepting *node ids*; route expansion to delivery targets happens
host-side (models.automaton matchings), while fan-out counting stays on device
for benchmarks (route_count gather + sum).
"""

from __future__ import annotations

import functools
import os
import threading
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.automaton import (
    NODE_HASH, NODE_PLUS, NODE_RCOUNT, CompiledTrie, TokenizedTopics,
)


# narrow count-walk table layout (see DeviceTrie.count_tab). CT_PLUS MUST
# stay at column 0: _advance reads its node-record argument at NODE_PLUS=0,
# and the count walk passes count_tab records straight through it.
CT_PLUS = 0
CT_HRCOUNT = 1
CT_RCOUNT = 2
CT_COLS = 4      # padded to a power of two for clean gather tiling

# route-materializing walk table (DeviceTrie.route_tab): the five columns the
# interval-emitting walk reads — plus-child (column 0, the _advance layout
# contract), the '#'-child's folded (count, start) and the node's own
# (count, start) — padded to 8 columns (32B rows; narrower than the 48B full
# record, wider than the 16B count row because it emits slot intervals).
RT_PLUS = 0
RT_HRCOUNT = 1
RT_RCOUNT = 2
RT_HRSTART = 3
RT_RSTART = 4
RT_COLS = 8


def route_cols_from_node_tab(node_tab: np.ndarray) -> np.ndarray:
    """Extract the RT_* route-walk columns from a full node table (or any
    row slice of one) — the ONE construction site for the layout
    (single-chip DeviceTrie, the mesh's per-shard stacking, and the
    ISSUE 9 patch flush all use it)."""
    from ..models.automaton import (
        NODE_HRCOUNT, NODE_HRSTART, NODE_RSTART,
    )
    route_cols = np.zeros((node_tab.shape[0], RT_COLS), dtype=np.int32)
    route_cols[:, RT_PLUS] = node_tab[:, NODE_PLUS]
    route_cols[:, RT_HRCOUNT] = node_tab[:, NODE_HRCOUNT]
    route_cols[:, RT_RCOUNT] = node_tab[:, NODE_RCOUNT]
    route_cols[:, RT_HRSTART] = node_tab[:, NODE_HRSTART]
    route_cols[:, RT_RSTART] = node_tab[:, NODE_RSTART]
    return route_cols


def count_cols_from_node_tab(node_tab: np.ndarray) -> np.ndarray:
    """Extract the CT_* count-walk columns (same one-construction-site
    contract as route_cols_from_node_tab; shared by the upload path and
    the patch flush)."""
    from ..models.automaton import NODE_HRCOUNT
    count_cols = np.zeros((node_tab.shape[0], CT_COLS), dtype=np.int32)
    count_cols[:, CT_PLUS] = node_tab[:, NODE_PLUS]
    count_cols[:, CT_HRCOUNT] = node_tab[:, NODE_HRCOUNT]
    count_cols[:, CT_RCOUNT] = node_tab[:, NODE_RCOUNT]
    return count_cols


@jax.tree_util.register_pytree_node_class
@dataclass
class DeviceTrie:
    """Compiled automaton tables resident on device."""
    node_tab: jax.Array   # [N, NODE_COLS] int32
    edge_tab: jax.Array   # [T, 4] int32
    child_list: jax.Array  # [E] int32
    # [N, CT_COLS] int32 — just the columns the count walk touches
    # (plus-child, folded '#'-route count, final-route count): the full
    # node record is 12 cols = 48B/row, of which the fan-out-count walk
    # reads 3; gathering the narrow row cuts per-step node bytes 3x.
    # Optional: paths that only run the full walk() (e.g. the shard_map
    # mesh step) may leave it None; walk_count_only requires it.
    count_tab: "jax.Array | None" = None
    # [N, RT_COLS] int32 — the interval-emitting walk's columns; optional
    # for the same reason (walk_routes requires it).
    route_tab: "jax.Array | None" = None

    def tree_flatten(self):
        return (self.node_tab, self.edge_tab, self.child_list,
                self.count_tab, self.route_tab), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @staticmethod
    def from_compiled(ct: CompiledTrie, device=None) -> "DeviceTrie":
        put = functools.partial(jax.device_put, device=device)
        return DeviceTrie(
            node_tab=put(ct.node_tab),
            edge_tab=put(ct.edge_tab),
            child_list=put(ct.child_list),
            count_tab=put(count_cols_from_node_tab(ct.node_tab)),
            route_tab=put(route_cols_from_node_tab(ct.node_tab)),
        )


@jax.tree_util.register_pytree_node_class
@dataclass
class Probes:
    """Device-side tokenized topic batch (see automaton.TokenizedTopics)."""
    tok_h1: jax.Array    # [B, L+1] int32
    tok_h2: jax.Array    # [B, L+1] int32
    lengths: jax.Array   # [B] int32
    roots: jax.Array     # [B] int32
    sys_mask: jax.Array  # [B] bool

    def tree_flatten(self):
        return (self.tok_h1, self.tok_h2, self.lengths, self.roots,
                self.sys_mask), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @staticmethod
    def from_tokenized(t: TokenizedTopics, device=None) -> "Probes":
        put = functools.partial(jax.device_put, device=device)
        return Probes(put(t.tok_h1), put(t.tok_h2), put(t.lengths),
                      put(t.roots), put(t.sys_mask))


@jax.tree_util.register_pytree_node_class
@dataclass
class WalkResult:
    """Accepting node ids, -1-padded; fixed shape for a [B] probe batch."""
    hash_acc: jax.Array   # [B, L+1, K] '#'-child accepts per consumed-level count
    final_acc: jax.Array  # [B, K] nodes active after consuming all levels
    overflow: jax.Array   # [B] bool — active-set overflow; host must re-match

    def tree_flatten(self):
        return (self.hash_acc, self.final_acc, self.overflow), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _mix_u32(node: jax.Array, h1: jax.Array, h2: jax.Array) -> jax.Array:
    """MUST stay in sync with models.automaton._mix_u32."""
    x = node.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
    x = x ^ (h1.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B))
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (h2.astype(jnp.uint32) * jnp.uint32(0x27D4EB2F))
    x = x ^ (x >> jnp.uint32(13))
    return x


def _edge_lookup(edge_tab: jax.Array, probe_len: int, node: jax.Array,
                 h1: jax.Array, h2: jax.Array) -> jax.Array:
    """Exact literal-child lookup; node/h1/h2 are [B,K]; returns child or -1.

    The edge table is single-choice bucketed ([NB, P, 4],
    automaton._build_edge_table): every key lives in bucket mix1(key), so
    the lookup is exactly ONE contiguous bucket-row gather. Gather cost is
    dominated by the per-index fetch, but row BYTES matter too: the r3
    probe_len sweep on v5e measured 241K topics/s @ P=32 (512B rows),
    300K @ P=16, 262K @ P=8 (table bytes double each halving; P=8's 256MB
    table loses more to cache pressure than the narrower row wins) — so
    the compiler default is probe_len=16.
    """
    nb = edge_tab.shape[0]
    mask = jnp.uint32(nb - 1)
    flat = edge_tab.reshape(nb, probe_len * 4)
    b1 = (_mix_u32(node, h1, h2) & mask).astype(jnp.int32)
    rows = flat[b1].reshape(node.shape + (probe_len, 4))  # [B,K,P,4]
    hit = ((rows[..., 0] == node[..., None])
           & (rows[..., 1] == h1[..., None])
           & (rows[..., 2] == h2[..., None]))
    return jnp.max(jnp.where(hit, rows[..., 3], -1), axis=-1)


def _bitonic_desc(x: jax.Array) -> jax.Array:
    """Descending sort along axis 1 as a static compare-exchange network.

    XLA's generic variadic-sort lowering measured ~3.9ms/step on v5e for
    [8192, 32] int32; this network is nothing but static lane permutations
    and min/max, which the Mosaic/XLA backend turns into cheap vector
    shuffles. Non-power-of-two widths (e.g. k_states=6 -> 12 candidate
    lanes) are padded with INT32_MIN, which sorts past every real value
    including the -1 empty marker; the caller's [:, :k] slice never sees
    the pad lanes."""
    orig = x.shape[1]
    n = 1 << (orig - 1).bit_length()
    if n != orig:
        pad = jnp.full((x.shape[0], n - orig), jnp.iinfo(jnp.int32).min,
                       dtype=x.dtype)
        x = jnp.concatenate([x, pad], axis=1)
    # the lane^step exchange is a REGULAR blocked swap, so it lowers as
    # reshape + a static reversed slice (vector shuffles, no gather), and
    # the direction mask is elementwise on an iota — lane < (lane^step)
    # iff lane's step-bit is 0 — which XLA constant-folds.
    b = x.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    stage = 2
    while stage <= n:
        step = stage // 2
        while step >= 1:
            y = x.reshape(b, n // (2 * step), 2, step)[:, :, ::-1, :] \
                 .reshape(b, n)
            take_max = ((lane & stage) == 0) == ((lane & step) == 0)
            x = jnp.where(take_max, jnp.maximum(x, y), jnp.minimum(x, y))
            step //= 2
        stage *= 2
    return x


def _advance(trie: DeviceTrie, probes: Probes, probe_len: int, b: int,
             k: int, i, act, valid, allow_wc, node_rec,
             compaction: str = "sort"):
    """One NFA step: literal + '+' successors, compacted to K slots.

    Shared by walk() and walk_count_only() so the successor semantics have
    exactly one definition. ``act`` may be narrower than K ([B, cap] for
    the progressively-widening prefix steps — after s steps at most 2^s
    states are active, so early steps gather far fewer indices); when the
    2*cap candidates still fit in K, no compaction happens and overflow is
    statically impossible. Returns (new_act [B, min(2*cap, K)],
    overflowed [B]).

    ``compaction`` picks the compaction strategy:
    - "sort": per-row descending sort of 2K lanes via a static bitonic
      compare-exchange network (vectorizes on the TPU VPU).
    - "scatter": mask + cumsum + one scatter per row — fewer total ops
      but the scatter can serialize on some backends.
    """
    cap = act.shape[1]
    stepping = (i < probes.lengths)[:, None]
    h1 = jnp.broadcast_to(
        jax.lax.dynamic_index_in_dim(probes.tok_h1, i, axis=1), (b, cap))
    h2 = jnp.broadcast_to(
        jax.lax.dynamic_index_in_dim(probes.tok_h2, i, axis=1), (b, cap))
    exact = _edge_lookup(trie.edge_tab, probe_len, act.clip(0), h1, h2)
    exact = jnp.where(stepping & valid, exact, -1)
    plus = jnp.where(stepping & valid & allow_wc,
                     node_rec[..., NODE_PLUS], -1)
    cand = jnp.concatenate([exact, plus], axis=1)        # [B,2*cap]
    if 2 * cap <= k:
        return cand, jnp.zeros((b,), dtype=bool)
    overflowed = (cand >= 0).sum(axis=1) > k
    if compaction == "scatter":
        live = cand >= 0
        # deterministic compaction: position = exclusive cumsum of live
        # lanes; dead lanes and overflow (pos >= k) fall to mode="drop" —
        # no duplicate indices, so the first K live candidates in lane
        # order always win
        pos = jnp.cumsum(live.astype(jnp.int32), axis=1) - 1
        pos = jnp.where(live, pos, 2 * k)      # out of range = dropped
        new_act = jnp.full((b, k), -1, jnp.int32)
        rows = jnp.broadcast_to(jnp.arange(b)[:, None], cand.shape)
        new_act = new_act.at[rows, pos].set(cand, mode="drop")
    else:
        # per-row SORT: the active set is a set — order is immaterial
        new_act = _bitonic_desc(cand)[:, :k]
    return new_act, overflowed


@functools.partial(jax.jit,
                   static_argnames=("probe_len", "k_states", "compaction"))
def walk(trie: DeviceTrie, probes: Probes, *, probe_len: int,
         k_states: int = 32, compaction: str = "sort") -> WalkResult:
    """Run the NFA walk for a batch of topics. See module docstring."""
    b, width = probes.tok_h1.shape
    max_levels = width - 1
    k = k_states

    def pad_k(x):
        cap = x.shape[1]
        if cap == k:
            return x
        return jnp.concatenate(
            [x, jnp.full((b, k - cap), -1, jnp.int32)], axis=1)

    def step(i, act, hash_acc, final_acc, overflow):
        in_range = (i <= probes.lengths)[:, None]           # [B,1]
        valid = (act >= 0) & in_range                       # [B,cap]
        # [MQTT-4.7.2-1]: block the root's wildcard children for '$'-topics
        allow_wc = jnp.logical_not(probes.sys_mask & (i == 0))[:, None]
        node_rec = trie.node_tab[act.clip(0)]               # [B,cap,NODE_COLS]

        # 1. '#'-child accepts: match regardless of remaining levels
        hc = jnp.where(valid & allow_wc, node_rec[..., NODE_HASH], -1)
        hash_acc = jax.lax.dynamic_update_slice_in_dim(
            hash_acc, pad_k(hc)[:, None, :], i, axis=1)

        # 2. final accepts once the whole topic is consumed
        is_final = (i == probes.lengths)[:, None]
        final_acc = jnp.where(is_final, pad_k(jnp.where(valid, act, -1)),
                              final_acc)

        # 3. successors for topics that still have levels left
        new_act, overflowed = _advance(trie, probes, probe_len, b, k, i,
                                       act, valid, allow_wc, node_rec,
                                       compaction)
        return new_act, hash_acc, final_acc, overflow | overflowed

    hash_acc = jnp.full((b, max_levels + 1, k), -1, dtype=jnp.int32)
    final_acc = jnp.full((b, k), -1, dtype=jnp.int32)
    overflow = jnp.zeros((b,), dtype=bool)
    # progressively-widening unrolled prefix (see _count_walk): at most 2^s
    # states live after s steps, so early steps run with narrow lanes.
    act = jnp.where(probes.lengths >= 0, probes.roots, -1)[:, None]
    i = 0
    while act.shape[1] < k and i < width:
        act, hash_acc, final_acc, overflow = step(
            jnp.int32(i), act, hash_acc, final_acc, overflow)
        i += 1
    if i < width:
        def body(j, carry):
            return step(j, *carry)
        # dynamic trip count: stop at the longest topic actually in the
        # batch (lowered to a while loop; short batches' tail costs nothing)
        upper = jnp.clip(jnp.max(probes.lengths, initial=-1) + 1, i, width)
        act, hash_acc, final_acc, overflow = jax.lax.fori_loop(
            i, upper, body, (act, hash_acc, final_acc, overflow))
    return WalkResult(hash_acc=hash_acc, final_acc=final_acc,
                      overflow=overflow)


@jax.jit
def count_routes(trie: DeviceTrie, result: WalkResult) -> jax.Array:
    """Per-topic matched-slot count (normal routes + group matchings). [B]"""
    def node_count(nodes):  # [...,] -> [...]
        cnt = trie.node_tab[nodes.clip(0), NODE_RCOUNT]
        return jnp.where(nodes >= 0, cnt, 0)

    b = result.final_acc.shape[0]
    hash_cnt = node_count(result.hash_acc).reshape(b, -1).sum(axis=1)
    final_cnt = node_count(result.final_acc).sum(axis=1)
    return hash_cnt + final_cnt


@functools.partial(jax.jit,
                   static_argnames=("probe_len", "k_states", "compaction"))
def walk_and_count(trie: DeviceTrie, probes: Probes, *, probe_len: int,
                   k_states: int = 32, compaction: str = "sort"
                   ) -> Tuple[WalkResult, jax.Array]:
    """Fused walk + per-topic fan-out count."""
    res = walk(trie, probes, probe_len=probe_len, k_states=k_states,
               compaction=compaction)
    return res, count_routes(trie, res)


def _count_walk(trie: DeviceTrie, probes: Probes, probe_len: int,
                k_states: int, compaction: str
                ) -> Tuple[jax.Array, jax.Array]:
    """Count-only walk body (shared by the primary and escalation passes):
    accumulates per-topic matched-slot counts in the loop and never
    materializes the accept tensors — the cheapest full-match measurement
    (and the shape a pure fan-out-counting service would use).

    '#'-accept counting reads the CT_HRCOUNT column (the hash child's
    route count folded into the parent record at compile time) — on v5e the
    separate hash-child gather was ~half the whole walk's time.
    Returns ([B] counts, [B] overflow)."""
    b, width = probes.tok_h1.shape
    k = k_states

    def step(i, act, cnt, overflow):
        in_range = (i <= probes.lengths)[:, None]
        valid = (act >= 0) & in_range
        allow_wc = jnp.logical_not(probes.sys_mask & (i == 0))[:, None]
        # narrow gather: count_tab carries exactly the 3 columns this walk
        # reads, with the plus-child at column 0 so the record can be
        # handed to _advance unchanged (layout contract at CT_PLUS)
        node_rec = trie.count_tab[act.clip(0)]
        hc_cnt = jnp.where(valid & allow_wc, node_rec[..., CT_HRCOUNT], 0)
        cnt = cnt + hc_cnt.sum(axis=1, dtype=jnp.int32)
        is_final = (i == probes.lengths)[:, None]
        fin_cnt = jnp.where(is_final & valid, node_rec[..., CT_RCOUNT], 0)
        cnt = cnt + fin_cnt.sum(axis=1, dtype=jnp.int32)
        new_act, overflowed = _advance(trie, probes, probe_len, b, k, i,
                                       act, valid, allow_wc, node_rec,
                                       compaction)
        return new_act, cnt, overflow | overflowed

    # progressively-widening unrolled prefix: after s steps at most 2^s
    # states can be active, so early steps run with 1, 2, 4, ... lanes —
    # gathers are the whole walk cost (~14.5ns/index on v5e) and this
    # nearly halves the total index count (112 -> 63 per topic at K=16).
    # Steps past a topic's length are per-row no-ops, so running the
    # prefix unconditionally is semantics-preserving.
    act = jnp.where(probes.lengths >= 0, probes.roots, -1)[:, None]
    cnt = jnp.zeros((b,), dtype=jnp.int32)
    overflow = jnp.zeros((b,), dtype=bool)
    i = 0
    while act.shape[1] < k and i < width:
        act, cnt, overflow = step(jnp.int32(i), act, cnt, overflow)
        i += 1
    if i < width:
        def body(j, carry):
            return step(j, *carry)
        upper = jnp.clip(jnp.max(probes.lengths, initial=-1) + 1, i, width)
        act, cnt, overflow = jax.lax.fori_loop(i, upper, body,
                                               (act, cnt, overflow))
    return cnt, overflow


@functools.partial(jax.jit,
                   static_argnames=("probe_len", "k_states", "compaction",
                                    "esc_k", "esc_rows"))
def walk_count_only(trie: DeviceTrie, probes: Probes, *, probe_len: int,
                    k_states: int = 32, compaction: str = "sort",
                    esc_k=None, esc_rows=None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Count-only walk + fused on-device overflow escalation.

    Overflowed topics (active set > k_states) are re-walked ON DEVICE in the
    same jit call: up to ``esc_rows`` overflow rows (default b/64, min 64)
    are compacted into a small sub-batch and run at ``esc_k`` states
    (default 2*k_states, capped at 128). Only rows that overflow even at
    esc_k — or beyond the esc_rows budget — report overflow to the host
    fallback. This replaces a ~360 topics/s host-oracle penalty with a
    small second device pass (measured free at [128 rows, 32 states]
    against an [8192, 16] primary on v5e) that lax.cond skips entirely
    when nothing overflowed.

    Returns ([B] counts, [B] overflow)."""
    b = probes.tok_h1.shape[0]
    cnt, overflow = _count_walk(trie, probes, probe_len, k_states, compaction)
    if esc_k is None:
        esc_k = min(2 * k_states, 128)
    if not esc_k or esc_k <= k_states:
        return cnt, overflow
    if esc_rows is None:
        esc_rows = max(64, b // 64)
    e = min(esc_rows, b)

    def escalate(args):
        cnt, overflow = args
        n_found = overflow.sum(dtype=jnp.int32)
        idx = jnp.nonzero(overflow, size=e, fill_value=0)[0]
        sel = jnp.arange(e) < n_found
        sub = Probes(
            tok_h1=probes.tok_h1[idx],
            tok_h2=probes.tok_h2[idx],
            lengths=jnp.where(sel, probes.lengths[idx], -1),
            roots=probes.roots[idx],
            sys_mask=probes.sys_mask[idx],
        )
        cnt2, ovf2 = _count_walk(trie, sub, probe_len, esc_k, compaction)
        success = sel & jnp.logical_not(ovf2)
        # duplicate pad indices (fill 0) make plain scatter-set racy;
        # max-combining is order-independent: pads contribute 0/False
        succ_full = jnp.zeros(b, jnp.int32).at[idx].max(
            success.astype(jnp.int32)).astype(bool)
        cnt2_full = jnp.zeros_like(cnt).at[idx].max(
            jnp.where(success, cnt2, 0))
        return (jnp.where(succ_full, cnt2_full, cnt),
                overflow & jnp.logical_not(succ_full))

    return jax.lax.cond(overflow.any(), escalate, lambda a: a,
                        (cnt, overflow))


# ------------------- route-materializing (interval) walk --------------------

@jax.tree_util.register_pytree_node_class
@dataclass
class RouteIntervals:
    """Per-topic matched slot set in compressed fixed shape.

    Each accepting node owns a CONTIGUOUS matching-slot interval
    [route_start, route_start + route_count) (automaton DFS pre-order), so
    the full matched route set of a topic is exactly a small list of
    (start, count) pairs — the fan-out lives in the counts, not the lanes.
    This is the device-side analog of the reference's materialized
    ``MatchedRoutes`` (.../worker/cache/MatchedRoutes.java:38): the host
    turns intervals into slot ids with one vectorized ragged-arange
    (automaton matchings[slot] are the route objects), never a per-slot
    Python loop.
    """
    start: jax.Array     # [B, A] int32 — interval starts (0 where unused)
    count: jax.Array     # [B, A] int32 — interval lengths (0 where unused)
    n_routes: jax.Array  # [B] int32 — total matched slots per topic
    overflow: jax.Array  # [B] bool — state overflow OR interval overflow;
    #                       the row's intervals are unusable, host re-matches

    def tree_flatten(self):
        return (self.start, self.count, self.n_routes, self.overflow), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _route_walk(trie: DeviceTrie, probes: Probes, probe_len: int,
                k_states: int, compaction: str, max_intervals: int
                ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Interval-emitting walk body (shared by primary + escalation passes).

    Mirrors _count_walk, but instead of summing matched-slot counts it
    EMITS each accepting node's slot interval: '#'-child accepts read the
    folded (RT_HRSTART, RT_HRCOUNT) columns of the already-gathered parent
    record, final accepts read (RT_RSTART, RT_RCOUNT) — no gathers beyond
    what the count walk pays. Emissions land in a dense [B, width, 2K]
    buffer via contiguous dynamic_update_slice writes; ONE cumsum+scatter
    compaction at the end packs live intervals into [B, A] lanes.

    Returns (ivl_start [B, A], ivl_count [B, A], n_routes [B], overflow [B]).
    """
    b, width = probes.tok_h1.shape
    k = k_states

    def pad_k(x, fill=0):
        cap = x.shape[1]
        if cap == k:
            return x
        return jnp.concatenate(
            [x, jnp.full((b, k - cap), fill, x.dtype)], axis=1)

    def step(i, act, em_s, em_c, overflow):
        in_range = (i <= probes.lengths)[:, None]
        valid = (act >= 0) & in_range
        allow_wc = jnp.logical_not(probes.sys_mask & (i == 0))[:, None]
        node_rec = trie.route_tab[act.clip(0)]
        hc_cnt = jnp.where(valid & allow_wc, node_rec[..., RT_HRCOUNT], 0)
        hc_start = node_rec[..., RT_HRSTART]
        is_final = (i == probes.lengths)[:, None]
        fin_cnt = jnp.where(is_final & valid, node_rec[..., RT_RCOUNT], 0)
        fin_start = node_rec[..., RT_RSTART]
        em_row_c = jnp.concatenate([pad_k(hc_cnt), pad_k(fin_cnt)], axis=1)
        em_row_s = jnp.concatenate([pad_k(hc_start), pad_k(fin_start)],
                                   axis=1)
        em_s = jax.lax.dynamic_update_slice_in_dim(
            em_s, em_row_s[:, None, :], i, axis=1)
        em_c = jax.lax.dynamic_update_slice_in_dim(
            em_c, em_row_c[:, None, :], i, axis=1)
        new_act, overflowed = _advance(trie, probes, probe_len, b, k, i,
                                       act, valid, allow_wc, node_rec,
                                       compaction)
        return new_act, em_s, em_c, overflow | overflowed

    # the named scopes land in every op's metadata: a device trace then
    # splits the walk's time into its step loop, its emit (compaction)
    # and, in ``_walk_routes_fn``, its escalation
    with jax.named_scope("walk.steps"):
        em_s = jnp.zeros((b, width, 2 * k), dtype=jnp.int32)
        em_c = jnp.zeros((b, width, 2 * k), dtype=jnp.int32)
        overflow = jnp.zeros((b,), dtype=bool)
        act = jnp.where(probes.lengths >= 0, probes.roots, -1)[:, None]
        i = 0
        while act.shape[1] < k and i < width:
            act, em_s, em_c, overflow = step(jnp.int32(i), act, em_s, em_c,
                                             overflow)
            i += 1
        if i < width:
            def body(j, carry):
                return step(j, *carry)
            upper = jnp.clip(jnp.max(probes.lengths, initial=-1) + 1, i,
                             width)
            act, em_s, em_c, overflow = jax.lax.fori_loop(
                i, upper, body, (act, em_s, em_c, overflow))

    # ---- single compaction pass: dense emissions -> [B, A] interval lanes
    with jax.named_scope("walk.emit"):
        a = max_intervals
        flat_c = em_c.reshape(b, -1)
        flat_s = em_s.reshape(b, -1)
        keep = flat_c > 0
        n_ivl = keep.sum(axis=1, dtype=jnp.int32)
        n_routes = flat_c.sum(axis=1, dtype=jnp.int32)
        pos = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1
        pos = jnp.where(keep, pos, a)      # a == out of range -> dropped
        rows = jnp.broadcast_to(jnp.arange(b)[:, None], flat_c.shape)
        ivl_s = jnp.zeros((b, a), jnp.int32).at[rows, pos].set(
            flat_s, mode="drop")
        ivl_c = jnp.zeros((b, a), jnp.int32).at[rows, pos].set(
            flat_c, mode="drop")
        return ivl_s, ivl_c, n_routes, overflow | (n_ivl > a)


def _walk_routes_fn(trie: DeviceTrie, probes: Probes, *, probe_len: int,
                    k_states: int = 32, compaction: str = "sort",
                    max_intervals: int = 32, esc_k=None, esc_rows=None
                    ) -> RouteIntervals:
    """Interval walk + fused on-device overflow escalation.

    Same escalation contract as walk_count_only: overflowed rows (active
    states > k_states, or > max_intervals live intervals) re-walk in one
    compacted sub-batch at esc_k states; only rows that overflow even then
    report overflow to the host fallback.
    """
    b = probes.tok_h1.shape[0]
    ivl_s, ivl_c, n_routes, overflow = _route_walk(
        trie, probes, probe_len, k_states, compaction, max_intervals)
    if esc_k is None:
        esc_k = min(2 * k_states, 128)
    if not esc_k or esc_k <= k_states:
        return RouteIntervals(ivl_s, ivl_c, n_routes, overflow)
    if esc_rows is None:
        esc_rows = max(64, b // 64)
    e = min(esc_rows, b)

    def escalate(args):
        ivl_s, ivl_c, n_routes, overflow = args
        n_found = overflow.sum(dtype=jnp.int32)
        idx = jnp.nonzero(overflow, size=e, fill_value=0)[0]
        sel = jnp.arange(e) < n_found
        sub = Probes(
            tok_h1=probes.tok_h1[idx],
            tok_h2=probes.tok_h2[idx],
            lengths=jnp.where(sel, probes.lengths[idx], -1),
            roots=probes.roots[idx],
            sys_mask=probes.sys_mask[idx],
        )
        s2, c2, nr2, ovf2 = _route_walk(trie, sub, probe_len, esc_k,
                                        compaction, max_intervals)
        success = sel & jnp.logical_not(ovf2)
        # duplicate pad indices (fill 0) make plain scatter-set racy;
        # max-combining is order-independent: pads contribute all-zeros
        # (starts/counts are >= 0), real rows write their values
        succ_full = jnp.zeros(b, jnp.int32).at[idx].max(
            success.astype(jnp.int32)).astype(bool)
        s2_full = jnp.zeros_like(ivl_s).at[idx].max(
            jnp.where(success[:, None], s2, 0))
        c2_full = jnp.zeros_like(ivl_c).at[idx].max(
            jnp.where(success[:, None], c2, 0))
        nr2_full = jnp.zeros_like(n_routes).at[idx].max(
            jnp.where(success, nr2, 0))
        return (jnp.where(succ_full[:, None], s2_full, ivl_s),
                jnp.where(succ_full[:, None], c2_full, ivl_c),
                jnp.where(succ_full, nr2_full, n_routes),
                overflow & jnp.logical_not(succ_full))

    with jax.named_scope("walk.escalate"):
        out = jax.lax.cond(overflow.any(), escalate, lambda a: a,
                           (ivl_s, ivl_c, n_routes, overflow))
    return RouteIntervals(*out)


_WALK_ROUTES_STATICS = ("probe_len", "k_states", "compaction",
                        "max_intervals", "esc_k", "esc_rows")

walk_routes = functools.partial(
    jax.jit, static_argnames=_WALK_ROUTES_STATICS)(_walk_routes_fn)

# ISSUE 6 tentpole: the dispatch ring's variant DONATES the probe buffers
# (arg 1) — the backend frees (or reuses) their device memory as soon as
# the walk consumes them, so a depth-N in-flight pipeline holds N result
# buffers, not N probe + N result. Callers must treat the Probes object
# as CONSUMED after the call (re-reading a donated jax buffer raises
# "Array has been deleted"); the matcher's escalation/readback paths only
# ever touch the HOST TokenizedTopics copy, never the donated device
# arrays.
_walk_routes_donated_jit = functools.partial(
    jax.jit, static_argnames=_WALK_ROUTES_STATICS,
    donate_argnums=(1,))(_walk_routes_fn)


def walk_routes_donated(trie, probes, **kw):
    import warnings
    with warnings.catch_warnings():
        # probe shapes ([B, W] tokens) rarely tile onto the result shapes
        # ([B, A] intervals), so XLA reports the donation as "not usable"
        # for aliasing — the EARLY FREE is the point here, and the hint
        # would fire on every new shape class in live serving
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return _walk_routes_donated_jit(trie, probes, **kw)


# ------------------- device-side patch application (ISSUE 9) ---------------
#
# A host patch plan (models.automaton.PatchableTrie) ships to device as
# NARROW row scatters — idx + row values only, never a whole-table
# re-upload — unless the arena reshaped (node growth / edge regrow), which
# re-puts just the reshaped table. The update is FUNCTIONAL by default
# (`tab.at[idx].set` returns a new array; the old one stays alive for any
# in-flight dispatch pinning it — the same double-buffer discipline as a
# compaction swap); with ``donate=True`` XLA aliases the update in place
# (O(rows) device work, no table copy), which is only legal when the
# caller proves no in-flight batch references the old tables.
#
# Every scatter carries exactly ``_PATCH_CHUNK`` rows: a table has ONE
# scatter shape (per donation variant), compiled by the install-time warm,
# so no flush — however many mutations it coalesces — traces a new program
# on the serving path. One exact-filter op dirties 4 node rows and 3 edge
# buckets; a pow2-snapped vector met a new class (and a 0.1-0.4 s compile
# per table) the first time three of them shared a flush.

_PATCH_CHUNK = 64


@jax.jit
def _scatter_rows(tab, idx, vals):
    with jax.named_scope("patch.scatter"):
        return tab.at[idx].set(vals)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows_donated(tab, idx, vals):
    with jax.named_scope("patch.scatter"):
        return tab.at[idx].set(vals)


def _patch_chunks(idx: np.ndarray):
    """A dirty-row index vector in pieces of exactly ``_PATCH_CHUNK``
    rows, the last one filled by repeating its last index — duplicate
    indices write identical values, so the result is deterministic."""
    for lo in range(0, idx.shape[0], _PATCH_CHUNK):
        part = idx[lo:lo + _PATCH_CHUNK]
        if part.shape[0] < _PATCH_CHUNK:
            part = np.concatenate([part, np.full(
                _PATCH_CHUNK - part.shape[0], part[-1], part.dtype)])
        yield part


def patch_device_trie(dev: DeviceTrie, pt, *, device=None,
                      donate: bool = False):
    """Apply a PatchableTrie's pending dirty set to the device tables.

    Returns ``(new DeviceTrie, stats)`` with ``stats`` carrying the rows
    touched, host→device bytes shipped, the mutation count drained, and
    whether any table reshaped (the caller re-warms the walk jit then).
    """
    full, node_rows, edge_rows, ops = pt.drain_dirty()
    try:
        return _patch_device_trie(dev, pt, full, node_rows, edge_rows,
                                  ops, device=device, donate=donate)
    except BaseException:
        # the drained rows must not be lost (a donated partial update may
        # even have consumed a table): fall back to full re-upload dirt
        pt.restore_dirty(ops)
        raise


def _patch_device_trie(dev, pt, full, node_rows, edge_rows, ops, *,
                       device, donate):
    put = functools.partial(jax.device_put, device=device)
    scatter = _scatter_rows_donated if donate else _scatter_rows
    stats = {"rows": 0, "bytes": 0, "ops": ops, "reshaped": False,
             "full": sorted(full), "donated": bool(donate)}
    node_tab, count_tab, route_tab = (dev.node_tab, dev.count_tab,
                                      dev.route_tab)
    edge_tab = dev.edge_tab
    if "node" in full:
        stats["reshaped"] |= tuple(pt.node_tab.shape) \
            != tuple(dev.node_tab.shape)
        node_tab = put(pt.node_tab)
        count_tab = put(count_cols_from_node_tab(pt.node_tab))
        route_tab = put(route_cols_from_node_tab(pt.node_tab))
        stats["rows"] += int(pt.node_tab.shape[0])
        stats["bytes"] += int(pt.node_tab.nbytes) \
            + pt.node_tab.shape[0] * (CT_COLS + RT_COLS) * 4
    elif node_rows.size:
        # idx/rows device_put EXPLICITLY (ISSUE 10): passing host numpy
        # into the jit'd scatter was an IMPLICIT h2d transfer per flush —
        # legal but invisible; the transfer-guard sanitizer now proves
        # the steady-churn path makes only declared transfers
        for idx_np in _patch_chunks(node_rows.astype(np.int32)):
            rows_np = pt.node_tab[idx_np]
            idx = put(idx_np)
            node_tab = scatter(node_tab, idx, put(rows_np))
            count_tab = scatter(count_tab, idx,
                                put(count_cols_from_node_tab(rows_np)))
            route_tab = scatter(route_tab, idx,
                                put(route_cols_from_node_tab(rows_np)))
            stats["bytes"] += int(idx_np.nbytes) * 3 + int(rows_np.nbytes) \
                + idx_np.shape[0] * (CT_COLS + RT_COLS) * 4
        stats["rows"] += int(node_rows.size)
    if "edge" in full:
        stats["reshaped"] |= tuple(pt.edge_tab.shape) \
            != tuple(dev.edge_tab.shape)
        edge_tab = put(pt.edge_tab)
        stats["rows"] += int(pt.edge_tab.shape[0])
        stats["bytes"] += int(pt.edge_tab.nbytes)
    elif edge_rows.size:
        for idx_np in _patch_chunks(edge_rows.astype(np.int32)):
            rows_np = pt.edge_tab[idx_np]
            edge_tab = scatter(edge_tab, put(idx_np), put(rows_np))
            stats["bytes"] += int(idx_np.nbytes) + int(rows_np.nbytes)
        stats["rows"] += int(edge_rows.size)
    return DeviceTrie(node_tab=node_tab, edge_tab=edge_tab,
                      child_list=dev.child_list, count_tab=count_tab,
                      route_tab=route_tab), stats


# shape classes already warmed this process: the scatter jit cache is
# process-global, so re-warming an identical (table shapes, device)
# class — e.g. one per range-matcher install on a multi-range worker —
# is pure wasted compile CPU. The claim must be atomic: same-delay warm
# threads wake together, and a GIL switch between check and add would
# let several pay the traces.
_WARMED_SCATTER_KEYS: set = set()
_WARM_CLAIM_LOCK = threading.Lock()

# node-arena floor below which the install-time warm is skipped: tiny
# bases (unit tests, cold single-tenant workers) trace their scatters
# in low tens of ms — background warm threads would cost more in
# cold-start CPU contention than the first flush saves. Serving-scale
# arenas (the ~100ms-per-trace class the warm exists for) clear this
# easily: 20k subs already builds ~30k nodes.
WARM_SCATTER_MIN_ROWS = 4096


def scatter_warm_shapes(dev: DeviceTrie) -> tuple:
    """The (shape, dtype) classes a patch flush of ``dev`` would
    scatter into — extracted while the tables are provably alive, so
    the delayed warm thread never has to touch (or pin) live device
    arrays that a donated flush may consume in the meantime."""
    return tuple((tuple(t.shape), np.dtype(t.dtype).name)
                 for t in (dev.node_tab, dev.count_tab, dev.route_tab,
                           dev.edge_tab) if t is not None)


def warm_patch_scatter(shapes: tuple, *, device=None,
                       donated: bool = True) -> None:
    """Pre-compile the patch-flush scatters (ISSUE 10 satellite,
    ROADMAP PR 9 follow-up (c)).

    The first churn flush otherwise pays a ~100ms one-off XLA trace per
    table shape — on the serving path, inside
    ``_dispatch_device``. ``shapes`` is ``scatter_warm_shapes(dev)``;
    warming compiles the ``_PATCH_CHUNK``-row scatter (the only shape a
    flush uses, see ``_patch_chunks``) per
    class, functional AND donated variants — both against throwaway
    device zeros tables (the jit cache keys on avals, not identity, and
    a live table captured across the warm delay could already be
    donated-consumed by an early flush). Deduped per shape class per
    process, key CLAIMED before compiling so concurrently-waking warm
    threads (multi-range installs share the default delay) don't
    duplicate the traces and full-table device allocations; the matcher
    runs this on a DELAYED background thread so a cold process's first
    serves never compete with it (see ``TpuMatcher._warm_walk``).
    """
    import jax.numpy as jnp
    key = (shapes, donated, str(device))
    with _WARM_CLAIM_LOCK:
        if key in _WARMED_SCATTER_KEYS:
            return
        _WARMED_SCATTER_KEYS.add(key)
    idx = jax.device_put(np.zeros(_PATCH_CHUNK, np.int32),
                         device=device)
    for shape, dtype in shapes:
        try:
            rows = jax.device_put(
                np.zeros((_PATCH_CHUNK,) + tuple(shape[1:]), dtype),
                device=device)
            dummy = jax.device_put(jnp.zeros(shape, dtype),
                                   device=device)
            _scatter_rows(dummy, idx, rows)
            if donated:
                dummy = jax.device_put(jnp.zeros(shape, dtype),
                                       device=device)
                _scatter_rows_donated(dummy, idx, rows)
        except Exception:  # noqa: BLE001 — one failed class must not
            # abort the rest; the first flush of that class traces lazily
            from ..utils.metrics import warmup_failed
            warmup_failed(f"patch-scatter {shape} {dtype}")


def _expand_lib():
    import ctypes

    from ..utils.nativelib import compile_and_load
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "native",
        "expand.cpp")
    lib = compile_and_load(src, os.path.join(os.path.dirname(src),
                                             "libexpand.so"))
    if not getattr(lib, "_ex_typed", False):
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.expand_grid.restype = ctypes.c_int64
        lib.expand_grid.argtypes = [i32p, ctypes.c_int64, ctypes.c_int64,
                                    i32p, i64p]
        lib._ex_typed = True
    return lib


def expand_intervals(ivl_start: np.ndarray, ivl_count: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side interval -> slot-id expansion.

    Returns (slots, row_offsets): row i's matched slot ids are
    ``slots[row_offsets[i]:row_offsets[i+1]]``. Native C++ sequential
    stores when the toolchain exists (memory-bandwidth-bound, ~15x the
    numpy repeat/arange chain on a 144M-slot batch); numpy fallback
    otherwise. No per-slot Python loop either way (the reference's
    per-route append, TenantRouteMatcher.java:96, is the shape this
    replaces; the c4 92-filters/s collapse was the Python version of it).
    """
    ivl_start = np.asarray(ivl_start)
    ivl_count = np.maximum(np.asarray(ivl_count), 0)
    counts64 = ivl_count.astype(np.int64, copy=False)
    row_counts = (counts64.sum(axis=1) if counts64.ndim == 2
                  else counts64.sum(keepdims=True))
    row_offsets = np.concatenate([np.zeros(1, np.int64),
                                  np.cumsum(row_counts)])
    total = int(row_offsets[-1])
    if 0 < total <= np.iinfo(np.int32).max:
        try:
            import ctypes
            lib = _expand_lib()
            grid = np.ascontiguousarray(
                np.stack([ivl_start, ivl_count], axis=-1), dtype=np.int32)
            rows = grid.shape[0] if grid.ndim == 3 else 1
            lanes = grid.reshape(rows, -1, 2).shape[1]
            out = np.empty(total, np.int32)
            row_totals = np.empty(rows, np.int64)
            w = lib.expand_grid(
                grid.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                ctypes.c_int64(rows), ctypes.c_int64(lanes),
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                row_totals.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_int64)))
            assert w == total, (w, total)   # counts/grid must agree
            return out, row_offsets
        except (RuntimeError, AttributeError):
            pass    # no compiler / stale incompatible .so: numpy below
    flat_s = ivl_start.ravel().astype(np.int64)
    flat_c = counts64.ravel()
    ends = np.cumsum(flat_c)
    inner = np.arange(total, dtype=np.int64) - np.repeat(ends - flat_c,
                                                         flat_c)
    # int32 like the native path: callers must see ONE dtype regardless
    # of toolchain availability (slot ids are device int32 by construction)
    slots = (np.repeat(flat_s, flat_c) + inner).astype(np.int32)
    return slots, row_offsets


# ------------------ device-side fan-out (ISSUE 19) --------------------------
#
# expand_intervals above is the host wall this section removes: the walk's
# [B, A] interval grids become dense (slot, topic-row) pairs ON DEVICE via a
# ragged arange (one scatter marks each live lane's first output position, a
# running max recovers the lane per position — O(cap), no per-element binary
# search), then one stable counting sort groups the pairs by delivery peer so
# the host receives pre-bucketed grids and keeps only the last-hop MQTT
# encode. The raw surface (expand_pairs) is byte-compatible with
# expand_intervals' row-major order; bucketing ships as a SEPARATELY ordered
# view (peer_slots/peer_rows/peer_offsets), never as a reordering of the
# parity surface.

# sentinel buckets appended after the n_peers real peers: slots whose
# delivery target the compile-time peer table cannot name (group matchings
# spanning servers, slots patched in after the table was built) land in
# UNKNOWN and get the exact host server_of() grouping; PAD holds the
# expansion buffer's dead lanes so live buckets stay contiguous in front.
PEER_UNKNOWN = 0   # bucket id = n_peers + PEER_UNKNOWN
PEER_PAD = 1       # bucket id = n_peers + PEER_PAD
N_SENTINEL_BUCKETS = 2


def device_expand_mode() -> str:
    """``BIFROMQ_DEVICE_EXPAND``: ``0`` host expansion (PR-18 behavior),
    ``1``/``auto`` (default) device expansion on."""
    from ..utils.env import env_str
    mode = env_str("BIFROMQ_DEVICE_EXPAND", "auto").strip().lower()
    return mode if mode in ("0", "1", "auto") else "auto"


def device_expand_enabled() -> bool:
    return device_expand_mode() != "0"


def expand_cap_lanes() -> int:
    """``BIFROMQ_EXPAND_CAP``: per-row pair budget of the device expansion
    buffer (batch capacity = B x this). Rows whose fan-out pushes the batch
    past the buffer are flagged ``trunc`` and re-expand on host from the
    interval grids — exact, just not pre-bucketed."""
    from ..utils.env import env_int
    return max(1, env_int("BIFROMQ_EXPAND_CAP", 64))


@jax.tree_util.register_pytree_node_class
@dataclass
class ExpandedRoutes:
    """Device-expanded, peer-bucketed fan-out of one walk batch.

    Carries the full :class:`RouteIntervals` surface (``start``/``count``/
    ``n_routes``/``overflow`` — the escalation re-walk and the host
    fallback read those unchanged) plus the expansion:

    - ``slots``/``rows``: dense (matching-slot, probe-row) pairs in the
      host expander's row-major order, ``-1`` past ``n_pairs``. Walk-
      overflow rows spend no buffer (they re-match on host anyway).
    - ``row_offsets``: row i's pairs live at ``[ro[i], ro[i+1])`` —
      valid wherever ``trunc[i]`` is False.
    - ``trunc``: the row's pairs did not fit the buffer; the host
      re-expands that row from ``start``/``count``.
    - ``peer_slots``/``peer_rows``/``peer_offsets``: the same pairs
      stably grouped by delivery peer (bucket ``n_peers`` = unknown
      target, ``n_peers + 1`` = dead padding), row-major inside each
      bucket.
    """
    start: jax.Array         # [B, A] int32
    count: jax.Array         # [B, A] int32
    n_routes: jax.Array      # [B] int32
    overflow: jax.Array      # [B] bool — walk overflow (host re-match)
    slots: jax.Array         # [CAP] int32
    rows: jax.Array          # [CAP] int32
    row_offsets: jax.Array   # [B+1] int32
    n_pairs: jax.Array       # [] int32
    trunc: jax.Array         # [B] bool — expansion buffer overflow
    peer_slots: jax.Array    # [CAP] int32
    peer_rows: jax.Array     # [CAP] int32
    peer_offsets: jax.Array  # [n_peers+3] int32

    def tree_flatten(self):
        return ((self.start, self.count, self.n_routes, self.overflow,
                 self.slots, self.rows, self.row_offsets, self.n_pairs,
                 self.trunc, self.peer_slots, self.peer_rows,
                 self.peer_offsets), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def ready_leaves(self):
        """The leaves the dispatch ring kicks/polls: the compact pair
        buffers the fetch reads every batch. The interval grids are NOT
        here — they only cross to host on the escalation slow path."""
        return (self.slots, self.rows, self.row_offsets, self.n_pairs,
                self.trunc, self.peer_slots, self.peer_rows,
                self.peer_offsets, self.overflow, self.n_routes)


def _expand_pairs(ivl_s: jax.Array, ivl_c: jax.Array, cap: int):
    """Ragged-arange expansion of [B, A] interval grids into dense pairs.

    Returns (slots [cap], rows [cap], row_offsets [B+1], n_pairs [],
    trunc [B]) in exactly ``expand_intervals``' row-major order, ``-1``
    past ``n_pairs``.
    """
    b, a = ivl_s.shape
    n = b * a
    flat_c = jnp.maximum(ivl_c.reshape(n), 0)
    flat_s = ivl_s.reshape(n)
    ends = jnp.cumsum(flat_c, dtype=jnp.int32)       # [n] lane end offsets
    lane_lo = ends - flat_c
    total = ends[-1]
    row_offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), ends.reshape(b, a)[:, -1]])
    trunc = row_offsets[1:] > cap
    # Each output position's owning lane, recovered by one scatter-add +
    # one cumsum: lane i's pairs start at lane_lo[i], so adding 1 at
    # every lane_lo[i] (i >= 1) and prefix-summing counts how many lane
    # boundaries precede each position — i.e. the lane index. Runs of
    # empty lanes share a boundary position and their +1s telescope to
    # the correct jump, always landing on the live lane that owns the
    # position. (A cummax over scatter-max marks computes the same thing
    # but the cap-sized cummax measures ~13 ns/elem on the single-core
    # XLA-CPU backend vs ~8 ns/elem for cumsum — at c2 fan-out caps that
    # difference alone is ~0.5 s per batch.)
    marks = jnp.zeros((cap,), jnp.int32).at[lane_lo[1:]].add(
        1, mode="drop")
    lane_c = jnp.cumsum(marks, dtype=jnp.int32)
    j = jnp.arange(cap, dtype=jnp.int32)
    valid = j < total
    # slot = flat_s[lane] + (j - lane_lo[lane]) refactored to ONE gather
    # from a precombined [n] table: the cap-sized gathers are the stage's
    # hot loop and XLA cannot fuse two of them (folding the pair halved
    # the measured single-core stage time at c2 fan-out)
    comb = flat_s - lane_lo
    slots = jnp.where(valid, comb[lane_c] + j, -1)
    if a & (a - 1) == 0:    # lane // a as a shift: a is a pow2 lane count
        row_of = jax.lax.shift_right_logical(lane_c, a.bit_length() - 1)
    else:
        row_of = lane_c // a
    rows = jnp.where(valid, row_of, -1)
    return slots, rows, row_offsets, jnp.minimum(total, cap), trunc


def _bucket_pairs(slots: jax.Array, rows: jax.Array, slot_peer: jax.Array,
                  n_peers: int):
    """Stable counting sort of expanded pairs by delivery peer.

    ``slot_peer``: [n_slot_cap] int32, peer id in [0, n_peers) or
    ``n_peers`` for unknown. Pairs keep expansion (row-major) order inside
    each bucket; pad pairs (slot == -1) sort to the final bucket; slots
    beyond the table (patched in after the peer table was built) go to the
    unknown bucket. For wide peer sets a stable argsort replaces the
    unrolled counting scan.
    """
    cap = slots.shape[0]
    n_slot = slot_peer.shape[0]
    unknown = n_peers + PEER_UNKNOWN
    pad = n_peers + PEER_PAD
    if n_slot == 0:     # empty arena: nothing to bucket beyond live/pad
        peer = jnp.where(slots < 0, pad, unknown)
    else:
        in_tab = (slots >= 0) & (slots < n_slot)
        peer = jnp.where(
            slots < 0, pad,
            jnp.where(in_tab, slot_peer[slots.clip(0, n_slot - 1)],
                      unknown))
    p_tot = n_peers + N_SENTINEL_BUCKETS
    counts = jnp.zeros((p_tot,), jnp.int32).at[peer].add(
        1, mode="drop")
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)])
    if p_tot <= 16:
        rank = jnp.zeros((cap,), jnp.int32)
        for p in range(p_tot):
            m = peer == p
            rank = rank + jnp.where(m, jnp.cumsum(m.astype(jnp.int32)) - 1,
                                    0)
        dst = starts[peer] + rank
        peer_slots = jnp.zeros((cap,), jnp.int32).at[dst].set(slots,
                                                              mode="drop")
        peer_rows = jnp.zeros((cap,), jnp.int32).at[dst].set(rows,
                                                             mode="drop")
    else:
        order = jnp.argsort(peer)   # lax.sort is stable
        peer_slots = slots[order]
        peer_rows = rows[order]
    return peer_slots, peer_rows, starts


@functools.partial(jax.jit, static_argnames=("cap",))
def expand_pairs(ivl_start: jax.Array, ivl_count: jax.Array, *, cap: int):
    """Raw device twin of :func:`expand_intervals` (the parity surface):
    expands whatever the grids say, overflow rows included, no bucketing.
    Returns (slots [cap], rows [cap], row_offsets [B+1], n_pairs, trunc)."""
    return _expand_pairs(ivl_start, ivl_count, cap)


@functools.partial(jax.jit, static_argnames=("cap", "n_peers"))
def _expand_routes_fn(ivl_s, ivl_c, overflow, slot_peer, *,
                      cap: int, n_peers: int):
    with jax.named_scope("expand.pairs"):
        serve_c = jnp.where(overflow[:, None], 0, ivl_c)
        slots, rows, row_offsets, n_pairs, trunc = _expand_pairs(
            ivl_s, serve_c, cap)
    if n_peers == 0:
        # structurally bucketed already: with no named peers every live
        # pair lands in UNKNOWN, and _expand_pairs emits live pairs as a
        # contiguous prefix with the pad lanes trailing — the stable
        # counting sort is the identity. Skipping it skips two cap-sized
        # scatters, which run ~8M updates/s on the single-core XLA-CPU
        # backend and would otherwise dominate the whole stage. The peer
        # views are aliased OUTSIDE the jit (None here): a jit that
        # returns the same buffer twice pays a real cap-sized copy per
        # duplicate on the CPU backend.
        peer_slots = peer_rows = None
        peer_offsets = jnp.stack(
            [jnp.zeros((), jnp.int32), n_pairs,
             jnp.full((), cap, jnp.int32)])
    else:
        with jax.named_scope("expand.bucket"):
            peer_slots, peer_rows, peer_offsets = _bucket_pairs(
                slots, rows, slot_peer, n_peers)
    return (slots, rows, row_offsets, n_pairs, trunc, peer_slots,
            peer_rows, peer_offsets)


def expand_routes(ivl: RouteIntervals, slot_peer, *, cap: int,
                  n_peers: int) -> ExpandedRoutes:
    """The serving expansion stage: walk intervals -> peer-bucketed pairs.

    Walk-overflow rows spend no buffer (their grids are junk and the host
    re-matches them regardless); their raw counts stay visible in
    ``.count`` for the escalation leg.
    """
    (slots, rows, row_offsets, n_pairs, trunc, peer_slots, peer_rows,
     peer_offsets) = _expand_routes_fn(
        ivl.start, ivl.count, ivl.overflow, slot_peer,
        cap=cap, n_peers=n_peers)
    if peer_slots is None:      # n_peers == 0: alias, don't copy
        peer_slots, peer_rows = slots, rows
    # the interval grids ride along from the caller's arrays — routing
    # them through the jit would copy [B, A] buffers for nothing
    return ExpandedRoutes(ivl.start, ivl.count, ivl.n_routes,
                          ivl.overflow, slots, rows, row_offsets, n_pairs,
                          trunc, peer_slots, peer_rows, peer_offsets)


def bucket_pairs_host(slots: np.ndarray, rows: np.ndarray,
                      slot_peer: np.ndarray, n_peers: int):
    """Host reference of :func:`_bucket_pairs` (parity oracle): same
    bucket layout, numpy stable argsort."""
    slots = np.asarray(slots)
    rows = np.asarray(rows)
    slot_peer = np.asarray(slot_peer)
    n_slot = slot_peer.shape[0]
    unknown = n_peers + PEER_UNKNOWN
    pad = n_peers + PEER_PAD
    if n_slot == 0:
        peer = np.where(slots < 0, pad, unknown).astype(np.int32)
    else:
        in_tab = (slots >= 0) & (slots < n_slot)
        peer = np.where(
            slots < 0, pad,
            np.where(in_tab, slot_peer[np.clip(slots, 0, n_slot - 1)],
                     unknown)).astype(np.int32)
    p_tot = n_peers + N_SENTINEL_BUCKETS
    counts = np.bincount(peer, minlength=p_tot).astype(np.int32)
    starts = np.concatenate([np.zeros(1, np.int32),
                             np.cumsum(counts, dtype=np.int32)])
    order = np.argsort(peer, kind="stable")
    return slots[order], rows[order], starts

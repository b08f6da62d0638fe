"""Device-side byte-level topic hashing (ISSUE 11 tentpole, device half).

The host byte plane (``models/bytetok.py``) already removes per-row
Python from topic prep; this module removes the HASH from the host
entirely: the raw topic bytes ship to device as one ``[B, MAX_BYTES]``
uint8 block plus the per-lane level boundaries (tiny int32 grids), and a
jit'd program computes the ``Probes`` h1/h2 token lanes on device — at
serving scale only bytes cross the host↔device link, the
"accelerator-side trie matching from raw token streams" move of
"Vectorizing the Trie" (PAPERS.md).

The kernel is BLAKE2b (RFC 7693) with digest_size=8 and the automaton
salt, **bit-exact** with ``automaton.level_hash`` (the randomized parity
suite enforces it). TPUs have no uint64, so the 64-bit state runs as
uint32 (lo, hi) lane pairs — add-with-carry, xor, and rotations composed
from 32-bit shifts. One final-block compression per level (a level
longer than one 128-byte block is unsupported by construction — the
host marks such rows padding and they take the exact oracle fallback,
the same bounded-work contract as the walk's overflow rows).

One lowering: the plain jit'd XLA program (``_hash_lanes_lax``). The
Pallas twin that used to sit beside it was refused by the v5e compiler
(Mosaic has no lowering for its per-lane byte gather) and was deleted in
PR 27; ``tests/test_tpu_compile.py`` keeps this program compiling for
the chip.

Deployment gate (``device_tokenize_enabled``): ``BIFROMQ_DEVICE_TOKENIZE``
``0``/``off`` kills the path, ``1``/``on`` forces it on every backend,
unset/``auto`` enables it only on a TPU backend — on CPU the native C++
tokenizer is the faster host.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import bytetok
from ..models.automaton import TokenizedTopics
from ..utils.env import env_int, env_str

_EMPTY = -1
_LEVEL_BLOCK = bytetok.MAX_SINGLE_BLOCK_LEVEL   # 128: one BLAKE2b block

# the IV split into uint32 (lo, hi) lanes once at import — the traced
# kernel body must not coerce device-typed scalars (graftcheck R1)
_IV_LO = (bytetok.BLAKE2B_IV & np.uint64(0xFFFFFFFF)).astype(np.uint32)
_IV_HI = (bytetok.BLAKE2B_IV >> np.uint64(32)).astype(np.uint32)

def _mode() -> str:
    v = env_str("BIFROMQ_DEVICE_TOKENIZE", "auto").lower()
    if v in ("0", "off", "false"):
        return "off"
    if v in ("1", "on", "true"):
        return "on"
    return "auto"


def device_tokenize_enabled() -> bool:
    """Should publish-side prep hash on device? Read per-batch (one env
    read) so tests and operators can flip the knob on a live process."""
    mode = _mode()
    if mode == "off":
        return False
    if mode == "on":
        return True
    return jax.default_backend() == "tpu"


def tok_max_bytes() -> int:
    """Per-topic byte budget of the device path (``BIFROMQ_TOK_MAX_BYTES``,
    default 256 — MQTT spec allows 64KB but real topics are tens of
    bytes; longer rows take the host path via the padding contract)."""
    return max(_LEVEL_BLOCK, env_int("BIFROMQ_TOK_MAX_BYTES", 256))


# ------------------- 64-bit-as-uint32-pairs BLAKE2b ------------------------

def _add64(alo, ahi, blo, bhi):
    lo = alo + blo
    carry = (lo < alo).astype(jnp.uint32)
    return lo, ahi + bhi + carry


def _rotr64(lo, hi, n: int):
    if n == 32:
        return hi, lo
    if n < 32:
        return ((lo >> n) | (hi << (32 - n)),
                (hi >> n) | (lo << (32 - n)))
    m = n - 32
    return ((hi >> m) | (lo << (32 - m)),
            (lo >> m) | (hi << (32 - m)))


def _hash_lanes(rows, starts, lens, nlv, h0lo, h0hi):
    """The shared kernel math: one final-block BLAKE2b-8 per (row, lane).

    ``rows`` [B, MB] uint8 raw topic bytes; ``starts``/``lens`` [B, W]
    int32 level boundaries (relative to the row); ``nlv`` [B, 1] int32
    level counts (-1 for padding rows); ``h0lo``/``h0hi`` [1, 8] uint32
    salt-folded initial state. Returns (h1, h2) [B, W] int32 with lanes
    past a row's level count zeroed — the exact ``TokenizedTopics``
    contract."""
    b, mb = rows.shape
    w = starts.shape[1]
    # the named scopes land in every op's metadata: a device trace then
    # splits the program's time by phase (gather / rounds / mask)
    with jax.named_scope("tokenize.gather"):
        # gather each lane's level bytes into a [B, W, 128] block (on
        # device — the host ships only the packed rows + tiny grids)
        iota = jax.lax.broadcasted_iota(jnp.int32, (b, w, _LEVEL_BLOCK), 2)
        gidx = jnp.clip(starts[:, :, None] + iota, 0, mb - 1)
        byte = rows[jnp.arange(b)[:, None, None], gidx].astype(jnp.uint32)
        byte = jnp.where(iota < lens[:, :, None], byte, jnp.uint32(0))
        # 16 message words as (lo, hi) uint32 pairs, little-endian
        wb = byte.reshape(b, w, 16, 8)
        m = []
        for i in range(16):
            lo = (wb[..., i, 0] | (wb[..., i, 1] << 8)
                  | (wb[..., i, 2] << 16) | (wb[..., i, 3] << 24))
            hi = (wb[..., i, 4] | (wb[..., i, 5] << 8)
                  | (wb[..., i, 6] << 16) | (wb[..., i, 7] << 24))
            m.append((lo, hi))
    iv_lo = [jnp.uint32(v) for v in _IV_LO]
    iv_hi = [jnp.uint32(v) for v in _IV_HI]
    shape = (b, w)
    def full(x):
        return jnp.broadcast_to(x, shape)
    v0 = [(full(h0lo[0, i]), full(h0hi[0, i])) for i in range(8)]
    v0 += [(full(iv_lo[i]), full(iv_hi[i])) for i in range(8)]
    t = lens.astype(jnp.uint32)                     # t0 (levels ≤ 128B)
    v0[12] = (v0[12][0] ^ t, v0[12][1])
    v0[14] = (~v0[14][0], ~v0[14][1])               # final-block flag
    m_lo = jnp.stack([x[0] for x in m])             # [16, B, W]
    m_hi = jnp.stack([x[1] for x in m])
    sigma = jnp.asarray(bytetok.BLAKE2B_SIGMA, jnp.int32)

    # the 12 rounds run as a ROLLED loop (one compiled round body, the
    # message schedule gathered per round): fully unrolled, the ~1,300
    # dependent elementwise ops defeat XLA's fusion pass — the CPU
    # backend grew past 40 GB without finishing the compile
    def one_round(r, carry):
        v_lo, v_hi = carry
        order = sigma[r]
        mr_lo, mr_hi = m_lo[order], m_hi[order]
        v = [(v_lo[i], v_hi[i]) for i in range(16)]

        def g(a, bb, c, d, k):
            x, y = (mr_lo[k], mr_hi[k]), (mr_lo[k + 1], mr_hi[k + 1])
            v[a] = _add64(*_add64(*v[a], *v[bb]), *x)
            v[d] = _rotr64(v[d][0] ^ v[a][0], v[d][1] ^ v[a][1], 32)
            v[c] = _add64(*v[c], *v[d])
            v[bb] = _rotr64(v[bb][0] ^ v[c][0], v[bb][1] ^ v[c][1], 24)
            v[a] = _add64(*_add64(*v[a], *v[bb]), *y)
            v[d] = _rotr64(v[d][0] ^ v[a][0], v[d][1] ^ v[a][1], 16)
            v[c] = _add64(*v[c], *v[d])
            v[bb] = _rotr64(v[bb][0] ^ v[c][0], v[bb][1] ^ v[c][1], 63)

        g(0, 4, 8, 12, 0)
        g(1, 5, 9, 13, 2)
        g(2, 6, 10, 14, 4)
        g(3, 7, 11, 15, 6)
        g(0, 5, 10, 15, 8)
        g(1, 6, 11, 12, 10)
        g(2, 7, 8, 13, 12)
        g(3, 4, 9, 14, 14)
        return (jnp.stack([x[0] for x in v]), jnp.stack([x[1] for x in v]))

    with jax.named_scope("tokenize.rounds"):
        v_lo, v_hi = jax.lax.fori_loop(
            0, len(bytetok.BLAKE2B_SIGMA), one_round,
            (jnp.stack([x[0] for x in v0]), jnp.stack([x[1] for x in v0])))

    with jax.named_scope("tokenize.mask"):
        out_lo = full(h0lo[0, 0]) ^ v_lo[0] ^ v_lo[8]
        out_hi = full(h0hi[0, 0]) ^ v_hi[0] ^ v_hi[8]
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        active = lane < nlv      # nlv == -1 padding rows mask everything
        h1 = jnp.where(active, out_lo.astype(jnp.int32), 0)
        h2 = jnp.where(active, out_hi.astype(jnp.int32), 0)
    return h1, h2


_hash_lanes_lax = jax.jit(_hash_lanes)


def hash_topics_device(rows, starts, lens, nlv, salt: int, *,
                       device=None):
    """Upload the packed byte batch and hash every level on device.

    All transfers are explicit ``device_put`` (the transfer-guard
    sanitizer proves the byte plane ships only declared bytes). Returns
    (h1, h2) device arrays [B, W] int32."""
    h0 = bytetok.blake2b8_h0(salt)
    h0lo = (h0 & np.uint64(0xFFFFFFFF)).astype(np.uint32).reshape(1, 8)
    h0hi = (h0 >> np.uint64(32)).astype(np.uint32).reshape(1, 8)
    put = functools.partial(jax.device_put, device=device)
    return _hash_lanes_lax(put(rows), put(starts), put(lens), put(nlv),
                           put(h0lo), put(h0hi))


class DeviceTokenized:
    """Host mirror of a device-tokenized probe batch.

    The hash lanes live ONLY on device (that is the point); the host
    keeps the cheap vectorized structure — lengths / roots / sys flags —
    plus the raw bytes, so the expansion stage never reads the device
    token arrays back. The rare paths that need host token rows (the
    escalation re-walk) re-tokenize just their rows via ``sub_batch``.
    """

    __slots__ = ("lengths", "roots", "sys_mask", "_tb", "_salt",
                 "_max_levels")

    def __init__(self, lengths, roots, sys_mask, tb, salt, max_levels):
        self.lengths = lengths
        self.roots = roots
        self.sys_mask = sys_mask
        self._tb = tb
        self._salt = salt
        self._max_levels = max_levels

    @property
    def batch(self) -> int:
        return self.lengths.shape[0]

    def sub_batch(self, rows: np.ndarray, batch: int) -> TokenizedTopics:
        """Host token rows for a row subset (escalation re-walk): the
        selected topics re-tokenize host-side — a few rows through the
        native path, paid only on the rare overflow escalation."""
        from ..models.automaton import tokenize
        rows = np.asarray(rows, dtype=np.int64)
        sub_tb = self._tb.select(rows)
        return tokenize(sub_tb, [int(r) for r in self.roots[rows]],
                        max_levels=self._max_levels, salt=self._salt,
                        batch=batch)


class DeviceTokenizedFilters:
    """Host mirror of a device-tokenized FILTER probe batch (ISSUE 17
    satellite): the retained scan plane needs the host lengths / roots /
    kind grid for planning and fallback accounting, but the literal-lane
    hashes live only on device — same split as :class:`DeviceTokenized`.
    """

    __slots__ = ("lengths", "roots", "kinds")

    def __init__(self, lengths, roots, kinds):
        self.lengths = lengths
        self.roots = roots
        self.kinds = kinds

    @property
    def batch(self) -> int:
        return self.lengths.shape[0]


def device_tokenize_filters(filters, roots: Sequence[int], *,
                            max_levels: int, salt: int,
                            batch: Optional[int] = None, device=None):
    """Device-side retained FILTER tokenization (ISSUE 17 satellite).

    Mirrors :func:`device_tokenize`: the host does the cheap vectorized
    structure work — pack the joined filter bytes, scan level
    boundaries, classify the single-byte ``'+'``/``'#'`` wildcard lanes
    into ``KIND_PLUS``/``KIND_HASH`` — and the BLAKE2b program hashes the
    lanes on device. Wildcard lanes are post-masked to ``h1 == h2 == 0``
    (the exact ``TokenizedFilters`` contract: only ``KIND_LIT`` lanes
    carry hashes; the retained walk branches on the kind grid).

    Rows the kernel cannot hash — deeper than ``max_levels``, longer
    than ``tok_max_bytes()``, a level over one BLAKE2b block, or a level
    embedding the topic delimiter (re-split hazard; impossible from
    ``parse()`` but this is a public API) — are marked padding (length
    ``-1``) and take the caller's exact host fallback. Empty filters
    record length 0 with no lanes, matching the reference loop.

    Returns ``(host_mirror, FilterProbes)``.
    """
    from ..utils import topic as topic_util
    from .retained import FilterProbes
    from ..models.automaton import KIND_HASH, KIND_LIT, KIND_PLUS
    n = len(filters)
    b = batch or n
    assert b >= n
    width = max_levels + 1
    max_bytes = tok_max_bytes()
    tb = bytetok.TopicBytes.from_topics(
        [topic_util.DELIMITER.join(f) for f in filters])
    st = bytetok.topic_structure(tb)
    byte_lens = tb.byte_lens.astype(np.int64)
    n_ref = np.fromiter((len(f) for f in filters), dtype=np.int64,
                        count=n)
    empty_rows = n_ref == 0
    resplit = (st.n_levels != n_ref) & ~empty_rows
    ok = ((st.n_levels <= max_levels) & (byte_lens <= max_bytes)
          & (st.max_lvl_len <= _LEVEL_BLOCK) & ~empty_rows & ~resplit)
    lengths = np.full(b, _EMPTY, dtype=np.int32)
    rootv = np.full(b, _EMPTY, dtype=np.int32)
    roots_a = np.fromiter(roots, dtype=np.int32, count=n)
    lengths[:n][ok] = st.n_levels[ok]
    rootv[:n][ok] = roots_a[ok]
    lengths[:n][empty_rows] = 0
    rootv[:n][empty_rows] = roots_a[empty_rows]
    rows = np.zeros((b, max_bytes), dtype=np.uint8)
    row_of = np.repeat(np.arange(n, dtype=np.int64), byte_lens)
    pos = bytetok._intra_row_positions(byte_lens)
    keep = ok[row_of]
    rows[row_of[keep], pos[keep]] = tb.data[keep]
    starts = np.zeros((b, width), dtype=np.int32)
    lens_g = np.zeros((b, width), dtype=np.int32)
    kinds = np.zeros((b, width), dtype=np.int32)
    sel = ok[st.lvl_row]
    # wildcard lanes are exactly the single-byte '+'/'#' levels
    one = st.lvl_len == 1
    b0 = np.zeros(st.lvl_len.shape[0], dtype=np.uint8)
    oidx = np.nonzero(one)[0]
    b0[oidx] = tb.data[st.lvl_start[oidx]]
    kind_lvl = np.zeros(st.lvl_len.shape[0], dtype=np.int32)
    kind_lvl[one & (b0 == ord(topic_util.SINGLE_WILDCARD))] = KIND_PLUS
    kind_lvl[one & (b0 == ord(topic_util.MULTI_WILDCARD))] = KIND_HASH
    row_off = tb.offsets.astype(np.int64)[:-1]
    starts[st.lvl_row[sel], st.lvl_idx[sel]] = \
        (st.lvl_start[sel] - row_off[st.lvl_row[sel]]).astype(np.int32)
    lens_g[st.lvl_row[sel], st.lvl_idx[sel]] = \
        st.lvl_len[sel].astype(np.int32)
    kinds[st.lvl_row[sel], st.lvl_idx[sel]] = kind_lvl[sel]
    nlv = lengths.reshape(b, 1)
    h1, h2 = hash_topics_device(rows, starts, lens_g, nlv, salt,
                                device=device)
    put = functools.partial(jax.device_put, device=device)
    kd = put(kinds)
    # zero-on-wildcard contract: inactive lanes are already zero (the
    # kernel's active mask) and carry kind 0 == KIND_LIT, so this mask
    # only strips the wildcard lanes' dummy hashes
    lit = kd == KIND_LIT
    h1 = jnp.where(lit, h1, 0)
    h2 = jnp.where(lit, h2, 0)
    probes = FilterProbes(tok_h1=h1, tok_h2=h2, tok_kind=kd,
                          lengths=put(lengths), roots=put(rootv))
    mirror = DeviceTokenizedFilters(lengths=lengths, roots=rootv,
                                    kinds=kinds)
    return mirror, probes


def device_tokenize(tb, roots: Sequence[int], *, max_levels: int,
                    salt: int, batch: Optional[int] = None,
                    device=None) -> Tuple[DeviceTokenized, "object"]:
    """The byte-plane device prep: pack + structure on host (vectorized
    numpy), hash on device. Returns ``(host_mirror, Probes)``.

    Rows the kernel cannot hash — longer than ``tok_max_bytes()``, a
    level over one BLAKE2b block, or deeper than ``max_levels`` — are
    marked padding (length -1) and take the caller's exact host
    fallback, the same bounded-work-then-fallback contract as the
    walk's overflow rows.
    """
    from .match import Probes
    n = len(tb)
    b = batch or n
    assert b >= n
    width = max_levels + 1
    max_bytes = tok_max_bytes()
    st = bytetok.topic_structure(tb)
    byte_lens = tb.byte_lens.astype(np.int64)
    ok = ((st.n_levels <= max_levels) & (byte_lens <= max_bytes)
          & (st.max_lvl_len <= _LEVEL_BLOCK))
    lengths = np.full(b, _EMPTY, dtype=np.int32)
    rootv = np.full(b, _EMPTY, dtype=np.int32)
    sys_mask = np.zeros(b, dtype=bool)
    lengths[:n][ok] = st.n_levels[ok]
    rootv[:n][ok] = np.fromiter(roots, dtype=np.int32, count=n)[ok]
    sys_mask[:n][ok] = st.sys_mask[ok]
    # pack supported rows into the fixed [B, MB] block + boundary grids
    rows = np.zeros((b, max_bytes), dtype=np.uint8)
    row_of = np.repeat(np.arange(n, dtype=np.int64), byte_lens)
    pos = bytetok._intra_row_positions(byte_lens)
    keep = ok[row_of]
    rows[row_of[keep], pos[keep]] = tb.data[keep]
    starts = np.zeros((b, width), dtype=np.int32)
    lens_g = np.zeros((b, width), dtype=np.int32)
    sel = ok[st.lvl_row]
    row_off = tb.offsets.astype(np.int64)[:-1]
    starts[st.lvl_row[sel], st.lvl_idx[sel]] = \
        (st.lvl_start[sel] - row_off[st.lvl_row[sel]]).astype(np.int32)
    lens_g[st.lvl_row[sel], st.lvl_idx[sel]] = \
        st.lvl_len[sel].astype(np.int32)
    nlv = lengths.reshape(b, 1)
    h1, h2 = hash_topics_device(rows, starts, lens_g, nlv, salt,
                                device=device)
    put = functools.partial(jax.device_put, device=device)
    probes = Probes(tok_h1=h1, tok_h2=h2, lengths=put(lengths),
                    roots=put(rootv), sys_mask=put(sys_mask))
    mirror = DeviceTokenized(lengths=lengths, roots=rootv,
                             sys_mask=sys_mask, tb=tb, salt=salt,
                             max_levels=max_levels)
    return mirror, probes

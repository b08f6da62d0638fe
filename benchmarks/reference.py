"""The plain reference: MQTT topic-filter matching by the book.

Imports nothing of the program and takes nothing the program made. Two
forms of the same semantics ([MQTT-4.7.1], [MQTT-4.7.2-1], [MQTT-4.8.2]):

- ``filter_matches`` is the definition, level by level;
- ``Table`` answers "which rows match this topic" by enumerating the
  topic's generalisations (every level kept or replaced by ``+``, every
  prefix closed with ``#``) and looking each up: a filter matches a topic
  exactly when it is one of them. ``selfcheck.py`` holds the two against
  each other and against hand-written cases.

``$share/<group>/<filter>`` and ``$oshare/...``: the filter behind the
prefix matches as usual and ONE member of each matching group receives.
A group is its whole filter string: ``$share/g/a/+`` and ``$oshare/g/a/+``
are two groups, and so are ``$share/g/a/+`` and ``$share/g/a/#``.
``Table.add`` files a row whose levels begin with a share prefix under
its group; ``Table.match`` gives the plain rows, ``Table.match_groups``
every matching group with its member rows.

``RetainedTable`` is the retained-message side ([MQTT-3.3.1-5..11]): the
topics retained per tenant, each with its versions and the instants around
which each began and ended, and which of them a SUBSCRIBE between two
instants must and may be handed.
"""

from __future__ import annotations

from itertools import product
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

PLUS, HASH = "+", "#"
SHARE_PREFIXES = ("$share", "$oshare")


def split_filter(topic_filter: str) -> Tuple[Optional[str], Tuple[str, ...]]:
    """``(group, levels)``; group is ``None`` for a plain filter and
    ``"<prefix>/<name>"`` for a shared one."""
    levels = topic_filter.split("/")
    if levels[0] in SHARE_PREFIXES and len(levels) >= 3:
        return f"{levels[0]}/{levels[1]}", tuple(levels[2:])
    return None, tuple(levels)


def filter_matches(filter_levels: Sequence[str],
                   topic_levels: Sequence[str]) -> bool:
    """``+`` is exactly one level, ``#`` any number of trailing levels
    (none included); neither matches a first level that starts with ``$``."""
    if topic_levels and topic_levels[0].startswith("$") \
            and filter_levels and filter_levels[0] in (PLUS, HASH):
        return False
    nf, nt = len(filter_levels), len(topic_levels)
    for i, f in enumerate(filter_levels):
        if f == HASH:
            return i == nf - 1
        if i >= nt:
            return False
        if f != PLUS and f != topic_levels[i]:
            return False
    return nf == nt


def generalisations(topic_levels: Sequence[str]) -> Iterator[Tuple[str, ...]]:
    """Every filter that matches the topic, once each."""
    n = len(topic_levels)
    sys_first = n > 0 and topic_levels[0].startswith("$")
    options = [(lv,) if (i == 0 and sys_first) else (lv, PLUS)
               for i, lv in enumerate(topic_levels)]
    for k in range(n + 1):
        if k == 0 and sys_first:
            continue
        for head in product(*options[:k]):
            yield head + (HASH,)
    yield from product(*options)


def is_shared(levels: Sequence[str]) -> bool:
    """Do these filter levels begin with a share prefix and a group?"""
    return len(levels) >= 3 and levels[0] in SHARE_PREFIXES


class Table:
    """Rows per tenant, keyed by filter levels; a shared row (its levels
    begin ``$share`` / ``$oshare``, group name) by the levels behind the
    prefix, under its group."""

    def __init__(self) -> None:
        self._rows: Dict[str, Dict[Tuple[str, ...], List[tuple]]] = {}
        # tenant -> levels behind the prefix -> "<prefix>/<name>" -> rows
        self._groups: Dict[str, Dict[Tuple[str, ...],
                                     Dict[str, List[tuple]]]] = {}
        self.n_groups = 0

    def add(self, tenant: str, levels: Tuple[str, ...], row: tuple) -> None:
        if is_shared(levels):
            group, rest = split_filter("/".join(levels))
            members = self._groups.setdefault(tenant, {}).setdefault(
                rest, {}).setdefault(group, [])
            self.n_groups += not members
            members.append(row)
            return
        self._rows.setdefault(tenant, {}).setdefault(levels, []).append(row)

    def members(self, tenant: str, group: str,
                levels: Tuple[str, ...]) -> List[tuple]:
        """The member rows of one group (``"<prefix>/<name>"``, levels)."""
        return self._groups.get(tenant, {}).get(levels, {}).get(group, [])

    def match_groups(self, tenant: str,
                     topic: str) -> List[Tuple[str, List[tuple]]]:
        """Every shared group whose filter matches ``topic``: (the group's
        whole filter string, its member rows)."""
        groups = self._groups.get(tenant)
        if not groups:
            return []
        out = []
        for g in generalisations(topic.split("/")):
            for group, members in groups.get(g, {}).items():
                out.append((f"{group}/{'/'.join(g)}", members))
        return out

    def match(self, tenant: str, topic: str) -> List[tuple]:
        """All rows whose filter matches ``topic`` (one per matching row)."""
        rows = self._rows.get(tenant)
        if not rows:
            return []
        out: List[tuple] = []
        for g in generalisations(topic.split("/")):
            hit = rows.get(g)
            if hit:
                out.extend(hit)
        return out


NEVER = 1 << 62      # an instant past every reading of the monotonic clock


class RetainedTable:
    """Retained messages by the book, per tenant: topic -> versions.

    A topic is seeded with version 0 retained since minus infinity. A SET
    of version ``k`` sent at ``sent`` and acknowledged at ``ack`` began at
    some instant of ``[sent, ack]`` and ended the version before it there;
    a CLEAR (an empty payload) ends the topic's version the same way. A
    version is kept as ``[k, began_lo, began_hi, ended_lo, ended_hi]`` on
    the load generator's clock, an open end at ``NEVER``; an event never
    acknowledged may have taken effect at any later instant.

    For a SUBSCRIBE sent at ``s`` and acknowledged at ``a``: a topic is a
    MUST when some version of it was retained throughout ``[s, a]``, a MAY
    when one was retained at some instant of ``[s, a]``; a version handed
    to it has to have been current at some instant of ``[s, a]``. Which
    matching topics are handed, up to the limit, is the program's choice:
    the comparison holds the count and the set, not the order.

    ``match`` walks a trie of plain dicts by the book's rules
    (``filter_matches``: ``+`` one level, ``#`` any trailing levels and
    the parent, no leading wildcard into a ``$`` topic) and is cached by
    filter."""

    def __init__(self) -> None:
        self._roots: Dict[str, dict] = {}
        self.topics: List[Tuple[str, str]] = []      # topic id -> (tenant, topic)
        self.tid_of: Dict[Tuple[str, str], int] = {}
        self._history: Dict[int, List[list]] = {}    # topic id -> versions
        self._sent: Dict[int, List[tuple]] = {}      # topic id -> (version, sent, ack)
        self._cache: Dict[tuple, Tuple[int, ...]] = {}

    def add(self, tenant: str, topic: str) -> int:
        """A seeded topic (version 0, retained since minus infinity); its
        topic id is the order of the calls."""
        tid = len(self.topics)
        self.topics.append((tenant, topic))
        self.tid_of[(tenant, topic)] = tid
        node = self._roots.setdefault(tenant, {})
        for lv in topic.split("/"):
            node = node.setdefault(lv, {})
        node[None] = tid
        return tid

    def apply(self, tid: int, version: int, sent: int, ack: int) -> None:
        """One SET (``version`` >= 1) or CLEAR (``version`` < 0) of a topic,
        in the order the topic's events were sent; ``ack`` 0: never."""
        ack = ack or NEVER
        self._sent.setdefault(tid, []).append((version, sent, ack))
        hist = self._history.setdefault(tid, [[0, -NEVER, -NEVER, NEVER,
                                               NEVER]])
        last = hist[-1]
        if last[3] == NEVER:            # the standing version ends here
            last[3], last[4] = sent, ack
        if version >= 0:
            hist.append([version, sent, ack, NEVER, NEVER])

    def match(self, tenant: str, filter_levels: Sequence[str]) -> Tuple[int, ...]:
        """The topic ids ever retained under ``tenant`` that the filter
        matches."""
        key = (tenant, tuple(filter_levels))
        hit = self._cache.get(key)
        if hit is None:
            out: List[int] = []
            root = self._roots.get(tenant)
            if root is not None:
                _walk(root, list(filter_levels), 0, out)
            hit = self._cache[key] = tuple(out)
        return hit

    def must_may(self, tid: int, s: int, a: int) -> Tuple[bool, bool]:
        hist = self._history.get(tid)
        if hist is None:
            return True, True
        must = any(v[2] <= s and a < v[3] for v in hist)
        may = any(v[1] <= a and s < v[4] for v in hist)
        return must, may

    def current_in(self, tid: int, version: int, s: int, a: int) -> bool:
        """Was ``version`` of the topic current at some instant of [s, a]?"""
        for v in self._history.get(tid, ([0, -NEVER, -NEVER, NEVER, NEVER],)):
            if v[0] == version:
                return v[1] <= a and s < v[4]
        return False

    def history(self, tid: int) -> List[list]:
        """The topic's versions, ``[k, began_lo, began_hi, ended_lo,
        ended_hi]`` each (the seed's alone where nothing was sent)."""
        return self._history.get(tid, [[0, -NEVER, -NEVER, NEVER, NEVER]])

    def in_flight(self, tid: int, version: int, lo: int, hi: int) -> bool:
        """Was a SET of ``version`` (a CLEAR: -1) of the topic between its
        send and its PUBACK at some instant of [lo, hi]?"""
        return any(v == version and sent <= hi and lo <= ack
                   for v, sent, ack in self._sent.get(tid, ()))


def _walk(node: dict, levels: List[str], i: int, out: List[int]) -> None:
    if i == len(levels):
        if None in node:
            out.append(node[None])
        return
    lv, at_root = levels[i], i == 0
    if lv == HASH:
        _subtree(node, out, at_root)
    elif lv == PLUS:
        for name, child in node.items():
            if name is not None and not (at_root and name.startswith("$")):
                _walk(child, levels, i + 1, out)
    else:
        child = node.get(lv)
        if child is not None:
            _walk(child, levels, i + 1, out)


def _subtree(node: dict, out: List[int], skip_sys: bool) -> None:
    for name, child in node.items():
        if name is None:
            out.append(child)
        elif not (skip_sys and name.startswith("$")):
            _subtree(child, out, False)


def truncated(rows: List[tuple], cap: int) -> List[tuple]:
    """THE CONTROL. A device row holds ``cap`` matches; the program
    re-expands a fuller row on the host. Leaving that out (the step a
    later PR would be tempted by) answers with the first ``cap`` rows:
    fast, approximate, and no longer what the configuration guarantees."""
    return rows[:cap]

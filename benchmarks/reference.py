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


def truncated(rows: List[tuple], cap: int) -> List[tuple]:
    """THE CONTROL. A device row holds ``cap`` matches; the program
    re-expands a fuller row on the host. Leaving that out (the step a
    later PR would be tempted by) answers with the first ``cap`` rows:
    fast, approximate, and no longer what the configuration guarantees."""
    return rows[:cap]

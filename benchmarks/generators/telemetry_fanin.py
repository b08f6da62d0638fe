"""Telemetry fan-in to shared-subscription consumers: every row of the
table is a member of a ``$share`` / ``$oshare`` group.

The deployment generator behind ``configs/telemetry_fanin.json``. A tenant
is an IoT application whose gateways publish every device's telemetry on
the device's own topic

    app/s<site>/d<device>/telemetry

and whose back-end services consume it as shared groups of workers, so
that ONE worker of each service sees a record:

- a site has ``site_groups`` (ingest, rules, archive as ``$share``; audit
  as ``$oshare``) on ``app/s<site>/+/telemetry``;
- the tenant has ``tenant_groups`` (metrics, alerts, billing as ``$share``;
  journal as ``$oshare``) on ``app/+/+/telemetry``;

each of ``members`` workers. A publish of a seeded site matches one group
of every kind and is delivered to one member of each. Tenants are equal.

A row is ``(tenant id, filter levels, receiver id, deliverer key)`` and a
shared row's levels keep the prefix: ``("$share", "<group>", "app", ...)``
(``sut.build_tries`` makes the program's matcher from them as a SUBSCRIBE
would; ``reference.Table`` files them by ``split_filter``). Every member
has a receiver id of its own.

It imports nothing of the program. A generator module is found by the
``generator`` key of a configuration file and offers ``tenant_sizes(cfg)``,
``subscriptions(cfg)``, ``topic_population(cfg)``, ``stress_topics(cfg)``
and ``FilterSource(cfg)``.
"""

from __future__ import annotations

import random
from typing import Iterator, List, Tuple

APP, LEAF = "app", "telemetry"


def _groups(cfg: dict, key: str) -> List[Tuple[str, str]]:
    """``[(prefix, group name), ...]`` of ``cfg[key]``, as written there
    (``"$share/ingest"``)."""
    return [tuple(g.split("/", 1)) for g in cfg[key]]


def rows_per_tenant(cfg: dict) -> int:
    m = int(cfg["members"])
    return (int(cfg["sites"]) * len(cfg["site_groups"])
            + len(cfg["tenant_groups"])) * m


def tenant_sizes(cfg: dict) -> List[Tuple[str, int]]:
    n = rows_per_tenant(cfg)
    return [(f"tenant{t}", n) for t in range(int(cfg["tenants"]))]


def site_filter(prefix: str, group: str, site: int) -> Tuple[str, ...]:
    return (prefix, group, APP, f"s{site}", "+", LEAF)


def subscriptions(cfg: dict) -> Iterator[Tuple[str, Tuple[str, ...], str, str]]:
    """Every row of the deployment's table: (tenant id, filter levels with
    the share prefix, receiver id, deliverer key). Nothing is drawn: the
    table is the same whatever ``table_seed``."""
    m, n_keys = int(cfg["members"]), int(cfg["deliverer_keys"])
    site_groups = _groups(cfg, "site_groups")
    tenant_groups = _groups(cfg, "tenant_groups")
    for t in range(int(cfg["tenants"])):
        tenant, i = f"tenant{t}", 0
        for site in range(int(cfg["sites"])):
            for prefix, group in site_groups:
                levels = site_filter(prefix, group, site)
                for _ in range(m):
                    yield tenant, levels, f"t{t}w{i}", f"d{i % n_keys}"
                    i += 1
        for prefix, group in tenant_groups:
            levels = (prefix, group, APP, "+", "+", LEAF)
            for _ in range(m):
                yield tenant, levels, f"t{t}w{i}", f"d{i % n_keys}"
                i += 1


def topic_population(cfg: dict) -> List[str]:
    """``draws`` distinct device topics: sites in turn, devices a site in
    order (topic ``i`` is device ``i // sites`` of site ``i % sites``)."""
    n_sites = int(cfg["sites"])
    return [f"{APP}/s{i % n_sites}/d{i // n_sites}/{LEAF}"
            for i in range(int(cfg["topic_population"]["draws"]))]


def stress_topics(cfg: dict) -> List[str]:
    """For the warm-up only: a site nobody consumes (the tenant-wide
    groups alone match) and a leaf no filter ends in (nothing matches)."""
    return [f"{APP}/snone/d0/{LEAF}", f"{APP}/s0/d0/other"]


class FilterSource:
    """Filters for live subscribers, settle rounds and churn.

    A plain draw is a dashboard on one site, ``app/s<site>/#``. A churn
    draw (``wildcard_only``) is alternately such a dashboard and a
    MEMBERSHIP of one of the seeded site groups
    (``$share/ingest/app/s<site>/+/telemetry``): the live session joins the
    group with its SUBSCRIBE and leaves it with its UNSUBSCRIBE."""

    def __init__(self, cfg: dict) -> None:
        self.n_sites = int(cfg["sites"])
        self.site_groups = _groups(cfg, "site_groups")
        self.churn_draws = 0

    def draw(self, rng: random.Random, wildcard_only: bool = False) -> str:
        site = rng.randrange(self.n_sites)
        if wildcard_only:
            self.churn_draws += 1
            if self.churn_draws % 2 == 0:
                prefix, group = self.site_groups[
                    rng.randrange(len(self.site_groups))]
                return "/".join(site_filter(prefix, group, site))
        return f"{APP}/s{site}/#"

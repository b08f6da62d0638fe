"""Subscription tables and publish topics over one Zipf-skewed topic tree.

The deployment generator behind ``configs/wildcard_1m.json`` and
``configs/tenant_fleet_1k.json``. Copied from the program's
``bifromq_tpu/workloads.py`` (``config_wildcard``, ``config_multi_tenant``,
``gen_filter_levels``, ``probe_topics``): it draws from ``random.Random``
in the same order, so the same seed gives the same table the bring-up
smoke loaded. It imports nothing of the program: rows are plain tuples.

A generator module is found by the ``generator`` key of a configuration
file and offers ``tenant_sizes(cfg)``, ``subscriptions(cfg)``,
``topic_population(cfg)``, ``stress_topics(cfg)`` and ``FilterSource(cfg)``.
"""

from __future__ import annotations

import random
from typing import Iterator, List, Tuple

PLUS, HASH = "+", "#"


def level_names(n: int) -> Tuple[List[str], List[float]]:
    """Level names ``l0..l<n-1>`` with CUMULATIVE Zipf(1) weights."""
    names = [f"l{i}" for i in range(n)]
    acc, cum = 0.0, []
    for i in range(n):
        acc += 1.0 / (i + 1)
        cum.append(acc)
    return names, cum


def gen_filter(rng: random.Random, names, cum, *, max_depth: int,
               p_plus: float, p_hash: float) -> List[str]:
    depth = rng.randint(1, max_depth)
    levels = rng.choices(names, cum_weights=cum, k=depth)
    for j in range(depth):
        if rng.random() < p_plus:
            levels[j] = PLUS
    if rng.random() < p_hash:
        levels.append(HASH)
    return levels


def gen_topic(rng: random.Random, names, cum, *, max_depth: int) -> List[str]:
    depth = rng.randint(1, max_depth)
    return rng.choices(names, cum_weights=cum, k=depth)


def tenant_sizes(cfg: dict) -> List[Tuple[str, int]]:
    """(tenant id, subscriptions) in rank order, without generating rows."""
    total = int(cfg["subscriptions"])
    n = int(cfg["tenants"])
    if n == 1:
        return [("tenant0", total)]
    w = [1.0 / (i + 1) for i in range(n)]
    wsum = sum(w)
    return [(f"tenant{t}", max(1, int(total * w[t] / wsum)))
            for t in range(n)]


def subscriptions(cfg: dict) -> Iterator[Tuple[str, Tuple[str, ...], str, str]]:
    """Every row of the deployment's table, from ``cfg['table_seed']``:
    (tenant id, filter levels, receiver id, deliverer key)."""
    rng = random.Random(int(cfg["table_seed"]))
    names, cum = level_names(int(cfg["level_names"]))
    kw = dict(max_depth=int(cfg["max_depth"]), p_plus=float(cfg["p_plus"]),
              p_hash=float(cfg["p_hash"]))
    n_keys = int(cfg["deliverer_keys"])
    one = int(cfg["tenants"]) == 1
    for t, (tenant, n) in enumerate(tenant_sizes(cfg)):
        for i in range(n):
            levels = tuple(gen_filter(rng, names, cum, **kw))
            if one:
                # config_wildcard draws its persistent-session coin here;
                # the draw is kept so the stream stays the smoke's
                rng.random()
                rid = f"r{i}"
            else:
                rid = f"t{t}r{i}"
            yield tenant, levels, rid, f"d{i % n_keys}"


def topic_population(cfg: dict) -> List[str]:
    """The deployment's publish topics: ``draws`` topics from the same
    tree (duplicates kept: a Zipf tree repeats its short topics)."""
    pop = cfg["topic_population"]
    rng = random.Random(int(pop["seed"]))
    names, cum = level_names(int(cfg["level_names"]))
    return ["/".join(gen_topic(rng, names, cum,
                               max_depth=int(cfg["max_depth"])))
            for _ in range(int(pop["draws"]))]


def stress_topics(cfg: dict) -> List[str]:
    """Topics that take the matcher's rare paths, for the warm-up only:
    the deepest topics of the most popular level name. In a large table
    every {name, +} pattern above them exists, so the walk's active set
    passes its 32 states and the escalation walk (a program of its own,
    compiled on first use) runs before the window and not inside it."""
    names, _cum = level_names(int(cfg["level_names"]))
    d = int(cfg["max_depth"])
    return ["/".join([names[0]] * k) for k in (d, d - 1) if k > 0]


class FilterSource:
    """Filters for live subscribers and churn, drawn from a caller's rng."""

    def __init__(self, cfg: dict) -> None:
        self.names, self.cum = level_names(int(cfg["level_names"]))
        self.kw = dict(max_depth=int(cfg["max_depth"]),
                       p_plus=float(cfg["p_plus"]),
                       p_hash=float(cfg["p_hash"]))

    def draw(self, rng: random.Random, wildcard_only: bool = False) -> str:
        while True:
            levels = gen_filter(rng, self.names, self.cum, **self.kw)
            if not wildcard_only or PLUS in levels or HASH in levels:
                return "/".join(levels)

"""Workload generators for the BASELINE.md measurement configs.

The five configs (BASELINE.json `configs`):
1. 1 tenant, 10K exact-topic subscriptions
2. 1 tenant, 1M wildcard subscriptions, Zipf-skewed topic tree
3. 1K tenants × 10K subs each, $share fan-out
4. retained: 5M retained topics, wildcard SUBSCRIBE probes
5. 10K tenants, 10M total subs, tenant-sharded across the mesh

Generation is deterministic per seed. Filters are built directly as
RouteMatcher tuples (bypassing string validation) for speed at the 10M scale.
"""

from __future__ import annotations

import random
from typing import Dict, List, Optional, Sequence, Tuple

from .models.oracle import Route, SubscriptionTrie
from .types import RouteMatcher, RouteMatcherType
from .utils import topic as topic_util


def _zipf_levels(n_levels: int) -> Tuple[List[str], List[float]]:
    """Returns (names, CUMULATIVE weights) — cumulative so random.choices
    skips its per-call accumulate pass (it dominates 10M-scale generation)."""
    names = [f"l{i}" for i in range(n_levels)]
    acc, cum = 0.0, []
    for i in range(n_levels):
        acc += 1.0 / (i + 1)
        cum.append(acc)
    return names, cum


def _mk_matcher(levels: Sequence[str], share_group: str = "",
                ordered: bool = False) -> RouteMatcher:
    if share_group:
        prefix = topic_util.ORDERED_SHARE if ordered else topic_util.UNORDERED_SHARE
        tf = f"{prefix}/{share_group}/" + "/".join(levels)
        return RouteMatcher(
            type=(RouteMatcherType.ORDERED_SHARE if ordered
                  else RouteMatcherType.UNORDERED_SHARE),
            filter_levels=tuple(levels), mqtt_topic_filter=tf,
            group=share_group)
    return RouteMatcher(type=RouteMatcherType.NORMAL,
                        filter_levels=tuple(levels),
                        mqtt_topic_filter="/".join(levels))


def gen_filter_levels(rng: random.Random, names: List[str],
                      weights: List[float], *, max_depth: int = 6,
                      p_plus: float = 0.15, p_hash: float = 0.1) -> List[str]:
    depth = rng.randint(1, max_depth)
    levels = rng.choices(names, cum_weights=weights, k=depth)
    for j in range(depth):
        if rng.random() < p_plus:
            levels[j] = topic_util.SINGLE_WILDCARD
    if rng.random() < p_hash:
        levels.append(topic_util.MULTI_WILDCARD)
    return levels


def gen_topic_levels(rng: random.Random, names: List[str],
                     weights: List[float], *, max_depth: int = 6) -> List[str]:
    depth = rng.randint(1, max_depth)
    return rng.choices(names, cum_weights=weights, k=depth)


def config_exact(n_subs: int = 10_000, *, seed: int = 0,
                 persistent_ratio: float = 0.0) -> Dict[str, SubscriptionTrie]:
    """Config 1: one tenant, exact-topic subscriptions."""
    rng = random.Random(seed)
    names, weights = _zipf_levels(max(64, n_subs // 100))
    trie = SubscriptionTrie()
    for i in range(n_subs):
        levels = gen_topic_levels(rng, names, weights)
        broker = 1 if rng.random() < persistent_ratio else 0
        trie.add(Route(matcher=_mk_matcher(levels), broker_id=broker,
                       receiver_id=f"r{i}", deliverer_key=f"d{i % 64}"))
    return {"tenant0": trie}


def config_wildcard(n_subs: int = 1_000_000, *, seed: int = 0,
                    n_level_names: int = 1000, max_depth: int = 6,
                    persistent_ratio: float = 0.1
                    ) -> Dict[str, SubscriptionTrie]:
    """Config 2: one tenant, wildcard-heavy Zipf subscriptions."""
    rng = random.Random(seed)
    names, weights = _zipf_levels(n_level_names)
    trie = SubscriptionTrie()
    for i in range(n_subs):
        levels = gen_filter_levels(rng, names, weights, max_depth=max_depth)
        broker = 1 if rng.random() < persistent_ratio else 0
        trie.add(Route(matcher=_mk_matcher(levels), broker_id=broker,
                       receiver_id=f"r{i}", deliverer_key=f"d{i % 64}"))
    return {"tenant0": trie}


def config_shared(n_tenants: int = 1000, subs_per_tenant: int = 10_000, *,
                  seed: int = 0, n_groups: int = 16
                  ) -> Dict[str, SubscriptionTrie]:
    """Config 3: many tenants, $share shared-subscription fan-out."""
    rng = random.Random(seed)
    names, weights = _zipf_levels(500)
    out: Dict[str, SubscriptionTrie] = {}
    for t in range(n_tenants):
        trie = SubscriptionTrie()
        for i in range(subs_per_tenant):
            levels = gen_filter_levels(rng, names, weights, p_plus=0.05,
                                       p_hash=0.05)
            group = f"g{rng.randrange(n_groups)}"
            ordered = rng.random() < 0.3
            trie.add(Route(matcher=_mk_matcher(levels, group, ordered),
                           broker_id=0, receiver_id=f"t{t}m{i}",
                           deliverer_key=f"d{i % 64}"))
        out[f"tenant{t}"] = trie
    return out


def config_multi_tenant(n_tenants: int = 10_000, total_subs: int = 10_000_000,
                        *, seed: int = 0) -> Dict[str, SubscriptionTrie]:
    """Config 5: tenant-sharded: Zipf tenant sizes summing to total_subs."""
    rng = random.Random(seed)
    names, weights = _zipf_levels(1000)
    tenant_weights = [1.0 / (i + 1) for i in range(n_tenants)]
    wsum = sum(tenant_weights)
    out: Dict[str, SubscriptionTrie] = {}
    for t in range(n_tenants):
        n = max(1, int(total_subs * tenant_weights[t] / wsum))
        trie = SubscriptionTrie()
        for i in range(n):
            levels = gen_filter_levels(rng, names, weights)
            trie.add(Route(matcher=_mk_matcher(levels), broker_id=0,
                           receiver_id=f"t{t}r{i}", deliverer_key=f"d{i % 64}"))
        out[f"tenant{t}"] = trie
    return out


def config_retained(n_topics: int = 5_000_000, *, seed: int = 0,
                    n_level_names: int = 1000, max_depth: int = 6
                    ) -> Dict[str, List[List[str]]]:
    """Config 4: retained-message store — concrete topics per tenant.

    The retained path stores *topics* (not filters) and probes with wildcard
    FILTERS (roles-swapped walk, models/retained.py); returns unique topic
    level-lists for one tenant.
    """
    rng = random.Random(seed)
    names, weights = _zipf_levels(n_level_names)
    seen = set()
    topics: List[List[str]] = []
    for i in range(n_topics):
        levels = gen_topic_levels(rng, names, weights, max_depth=max_depth)
        if tuple(levels) in seen:
            # disambiguate with a device-id tail (realistic retained-topic
            # shape: per-device leaves under shared prefixes); may exceed
            # max_depth by one level
            levels = levels + [f"d{i}"]
        seen.add(tuple(levels))
        topics.append(levels)
    return {"tenant0": topics}


def probe_filters(n: int, *, seed: int = 2, n_level_names: int = 1000,
                  max_depth: int = 6) -> List[List[str]]:
    """Wildcard SUBSCRIBE filters probing the retained store (config 4)."""
    rng = random.Random(seed)
    names, weights = _zipf_levels(n_level_names)
    return [gen_filter_levels(rng, names, weights, max_depth=max_depth)
            for _ in range(n)]


def probe_topics(n: int, *, seed: int = 1, n_level_names: int = 1000,
                 max_depth: int = 6) -> List[List[str]]:
    """Concrete PUBLISH topics drawn from the same Zipf tree."""
    rng = random.Random(seed)
    names, weights = _zipf_levels(n_level_names)
    return [gen_topic_levels(rng, names, weights, max_depth=max_depth)
            for _ in range(n)]


# ---------------------- topic-diversity generator (ISSUE 11) ----------------
#
# The paper benchmarks its broker against tenant populations whose TOPIC
# SHAPES differ wildly — short flat telemetry channels, deep per-device
# vehicle paths, i18n retail catalogs, $SYS operational streams — while
# `probe_topics` emits uniform `l<i>/l<j>/...` strings whose levels are
# 2-5 ASCII bytes. Tokenizer cost is byte- and level-count-shaped, so the
# ingest bench (config 9) must measure on realistic strings, not
# `bench/a/b`. Profiles mix level counts, level byte lengths, multi-byte
# UTF-8 density, numeric device-id leaves, and the '$'-root class.

TENANT_TOPIC_PROFILES: dict = {
    # flat sensor telemetry: shallow, short ASCII levels, numeric leaf
    "telemetry": dict(weight=0.40, depth=(3, 6), seg_len=(3, 10),
                      unicode_p=0.0, numeric_leaf_p=0.8, sys_p=0.0),
    # fleet/vehicle: deep paths, mid-size levels, uuid-ish leaves
    "fleet": dict(weight=0.25, depth=(6, 12), seg_len=(6, 18),
                  unicode_p=0.02, numeric_leaf_p=0.5, sys_p=0.0),
    # retail/i18n: shallow but multi-byte-UTF-8-heavy long levels
    "retail_i18n": dict(weight=0.20, depth=(2, 5), seg_len=(4, 24),
                        unicode_p=0.6, numeric_leaf_p=0.1, sys_p=0.0),
    # operational $SYS streams (exercises the sys-root walk rule)
    "sysmon": dict(weight=0.05, depth=(2, 4), seg_len=(4, 12),
                   unicode_p=0.0, numeric_leaf_p=0.0, sys_p=1.0),
    # adversarial edge: empty levels / separator runs / deep shapes
    "edge": dict(weight=0.10, depth=(1, 15), seg_len=(0, 8),
                 unicode_p=0.1, numeric_leaf_p=0.2, sys_p=0.0),
}

_UNICODE_SEGS = ["日本語", "センサー", "größe", "müller", "caféteria",
                 "датчик", "température", "aßßen", "चैनल", "중계기"]
_ASCII = "abcdefghijklmnopqrstuvwxyz"


def diverse_topics(n: int, *, seed: int = 0,
                   profiles: dict = None) -> List[str]:
    """``n`` topic STRINGS drawn from the tenant profiles above (byte
    plane: the serving path ships strings/bytes, so the generator does
    too). Deterministic per seed; used by the ingest tier-2 gate."""
    rng = random.Random(seed)
    profs = profiles or TENANT_TOPIC_PROFILES
    names = list(profs)
    cum: List[float] = []
    acc = 0.0
    for p in names:
        acc += profs[p]["weight"]
        cum.append(acc)
    out: List[str] = []
    for _ in range(n):
        p = profs[rng.choices(names, cum_weights=cum, k=1)[0]]
        depth = rng.randint(*p["depth"])
        levels: List[str] = []
        for j in range(depth):
            lo, hi = p["seg_len"]
            seg_len = rng.randint(lo, hi)
            if seg_len == 0:
                levels.append("")       # empty level / separator run
            elif rng.random() < p["unicode_p"]:
                levels.append(rng.choice(_UNICODE_SEGS))
            else:
                levels.append("".join(rng.choice(_ASCII)
                                      for _ in range(seg_len)))
        if p["sys_p"] and rng.random() < p["sys_p"]:
            levels.insert(0, "$SYS")
        if levels and rng.random() < p["numeric_leaf_p"]:
            levels.append(f"d{rng.randrange(1 << 20)}")
        out.append("/".join(levels) if levels else "x")
    return out


# ---------------------- mixed million-client workload (ISSUE 13) ------------
#
# Configs 1-5 each exercise ONE plane in isolation; real broker
# populations are a MIX — transient and persistent sessions, QoS spread,
# $share worker pools, retained floods, churny connections, reconnect
# drain storms — and the SLO / noisy-neighbor / shed / cache planes only
# mean anything under that diversity. `config_mixed` generates one
# deterministic plan covering all of it.

def config_mixed(n_clients: int = 1_000_000, *, seed: int = 0,
                 n_tenants: int = 100, persistent_ratio: float = 0.3,
                 share_ratio: float = 0.1, n_groups: int = 16,
                 retained_base: Optional[int] = None,
                 retained_ops: int = 10_000,
                 scan_filters: int = 512,
                 churn_ops: int = 2_048,
                 drain_sessions: int = 256,
                 publishes: int = 4_096) -> dict:
    """One deterministic mixed-workload plan for ``n_clients`` clients.

    Returns a dict of per-plane inputs:

    - ``subscriptions``: per-tenant SubscriptionTrie route table (one
      filter per client; Zipf tenant sizes, ~``persistent_ratio``
      persistent receivers, ~``share_ratio`` $share group members)
    - ``qos_mix``: per-client QoS histogram {0,1,2} (0.7/0.25/0.05)
    - ``retained_seed`` / ``retained_flood``: the retained store's base
      topic population and the SET/CLEAR flood ops (≥ ``retained_ops``,
      re-SET/CLEAR mix with per-device leaf diversity)
    - ``scan_filters``: wildcard SUBSCRIBE filters probing the retained
      store (per tenant)
    - ``publishes``: (tenant, topic, qos) publish stream over the same
      Zipf tree
    - ``session_churn``: ("sub"|"unsub", tenant, filter levels,
      receiver) connect/disconnect route churn
    - ``drain_plan``: (tenant, inbox_id, backlog) reconnect-storm
      population — one HERD tenant holding most sessions plus quiet
      tenants, the shape tenant-fairness must survive
    """
    rng = random.Random(seed)
    names, weights = _zipf_levels(1000)
    tenant_w = [1.0 / (i + 1) for i in range(n_tenants)]
    wsum = sum(tenant_w)
    tenants = [f"tenant{i}" for i in range(n_tenants)]

    subs: Dict[str, SubscriptionTrie] = {}
    qos_mix = {0: 0, 1: 0, 2: 0}
    client = 0
    for ti, tenant in enumerate(tenants):
        n = max(1, int(n_clients * tenant_w[ti] / wsum))
        trie = SubscriptionTrie()
        for i in range(n):
            roll = rng.random()
            qos = 0 if roll < 0.70 else (1 if roll < 0.95 else 2)
            qos_mix[qos] += 1
            levels = gen_filter_levels(rng, names, weights, p_plus=0.10,
                                       p_hash=0.05)
            share = rng.random() < share_ratio
            group = f"g{rng.randrange(n_groups)}" if share else ""
            broker = 1 if (not share
                           and rng.random() < persistent_ratio) else 0
            trie.add(Route(
                matcher=_mk_matcher(levels, group, share
                                    and rng.random() < 0.3),
                broker_id=broker, receiver_id=f"c{client}",
                deliverer_key=f"d{client % 64}"))
            client += 1
        subs[tenant] = trie

    # retained plane: base population + flood (device-leaf diversity,
    # re-SET/CLEAR mix, a '$SYS' slice for the root rules)
    if retained_base is None:
        retained_base = max(1024, n_clients // 10)
    seen = set()
    retained_seed: List[Tuple[str, List[str]]] = []
    for i in range(retained_base):
        tenant = tenants[rng.randrange(n_tenants)]
        levels = gen_topic_levels(rng, names, weights)
        if rng.random() < 0.02:
            levels = ["$SYS"] + levels
        if (tenant, tuple(levels)) in seen:
            levels = levels + [f"d{i}"]
        seen.add((tenant, tuple(levels)))
        retained_seed.append((tenant, levels))
    flood: List[Tuple[str, str, List[str]]] = []
    live = list(retained_seed)
    for i in range(retained_ops):
        roll = rng.random()
        if roll < 0.55 or not live:
            tenant = tenants[rng.randrange(n_tenants)]
            levels = gen_topic_levels(rng, names, weights) + [f"f{i}"]
            flood.append(("set", tenant, levels))
            live.append((tenant, levels))
        elif roll < 0.85:
            tenant, levels = live.pop(rng.randrange(len(live)))
            flood.append(("clear", tenant, levels))
        else:   # re-SET of a live topic (payload replace, index no-op)
            tenant, levels = live[rng.randrange(len(live))]
            flood.append(("set", tenant, levels))

    filters = [(tenants[rng.randrange(n_tenants)],
                gen_filter_levels(rng, names, weights))
               for _ in range(scan_filters)]

    pubs = []
    for _ in range(publishes):
        roll = rng.random()
        qos = 0 if roll < 0.70 else (1 if roll < 0.95 else 2)
        pubs.append((tenants[rng.randrange(n_tenants)],
                     "/".join(gen_topic_levels(rng, names, weights)), qos))

    churn = []
    for i in range(churn_ops):
        tenant = tenants[rng.randrange(n_tenants)]
        levels = gen_filter_levels(rng, names, weights)
        churn.append(("sub", tenant, levels, f"churn{i}"))
        if rng.random() < 0.5:
            churn.append(("unsub", tenant, levels, f"churn{i}"))

    # drain storm: tenant0 reconnects a HERD, the tail tenants a handful
    drain_plan = []
    herd = max(1, int(drain_sessions * 0.8))
    for i in range(herd):
        drain_plan.append(("tenant0", f"inbox-h{i}",
                           rng.randint(32, 128)))
    rest = drain_sessions - herd
    for i in range(rest):
        tenant = tenants[1 + rng.randrange(max(1, n_tenants - 1))]
        drain_plan.append((tenant, f"inbox-q{i}", rng.randint(8, 32)))

    return {"tenants": tenants, "subscriptions": subs,
            "qos_mix": qos_mix, "n_clients": client,
            "retained_seed": retained_seed, "retained_flood": flood,
            "scan_filters": filters, "publishes": pubs,
            "session_churn": churn, "drain_plan": drain_plan,
            "n_groups": n_groups, "seed": seed}

"""Last-known device state as retained messages, after the Homie
convention (homieiot.github.io): every attribute of every device is a
retained message, and an application that starts, or opens a view, learns
the state by SUBSCRIBING and being handed the retained messages that match.

The deployment generator behind ``configs/rehearsal_retained_20k.json``.
A tenant has ``sites`` sites of ``devices`` devices each; device ``d`` of
site ``s`` keeps one retained topic an attribute of ``attributes``

    homie/s<s>/d<d>/<attribute>

(Homie's device attributes and one node's properties on one level).
Topic id ``i`` is the ``i``-th row of ``retained(cfg)``: tenants, sites,
devices, attributes, in that order.

A SUBSCRIBE draws one of four shapes (``retained.filter_mix``): one
attribute of one device (an exact topic); a device's whole state
``homie/s<s>/d<d>/#``; one attribute across a site ``homie/s<s>/+/<a>``
(``devices`` children under one ``+``: more than the walk's 32 states, so
the device flags the row and the host escalates it); a whole site
``homie/s<s>/#``, which ``RetainMessageMatchLimit`` cuts.

The same devices also raise alerts, ordinary publishes on
``alerts/s<s>/d<d>`` that the route table holds consumers for:
``device_consumers`` receivers a device and ``site_consumers`` on
``alerts/s<s>/+`` a site. The two namespaces are disjoint: a state
SUBSCRIBE never matches an alert, and the table never matches a retained
SET.

It imports nothing of the program. A generator module offers
``tenant_sizes(cfg)``, ``subscriptions(cfg)``, ``topic_population(cfg)``,
``stress_topics(cfg)`` and ``FilterSource(cfg)``; one that seeds retained
messages also ``retained(cfg)``, ``retained_count(cfg)``,
``retained_row(cfg, i)``, ``retained_stress_filters(cfg)`` and
``FilterSource.retained_topic(rng)``.
"""

from __future__ import annotations

import bisect
import random
from itertools import accumulate
from typing import Iterator, List, Tuple

STATE, ALERTS = "homie", "alerts"
SHAPES = ("exact", "device", "site_attr", "site")


def _cum(weights) -> List[float]:
    return list(accumulate(weights))


def _zipf_cum(n: int) -> List[float]:
    return _cum(1.0 / (i + 1) for i in range(n))


def _dims(cfg: dict) -> Tuple[int, int, int, int]:
    return (int(cfg["tenants"]), int(cfg["sites"]), int(cfg["devices"]),
            len(cfg["attributes"]))


def rows_per_tenant(cfg: dict) -> int:
    _t, sites, devices, _a = _dims(cfg)
    return sites * (devices * int(cfg["device_consumers"])
                    + int(cfg["site_consumers"]))


def tenant_sizes(cfg: dict) -> List[Tuple[str, int]]:
    n = rows_per_tenant(cfg)
    return [(f"tenant{t}", n) for t in range(int(cfg["tenants"]))]


def subscriptions(cfg: dict) -> Iterator[Tuple[str, Tuple[str, ...], str, str]]:
    """The route table: (tenant id, filter levels, receiver id, deliverer
    key). Nothing is drawn: the table is the same whatever ``table_seed``."""
    n_tenants, sites, devices, _a = _dims(cfg)
    per_dev, per_site = int(cfg["device_consumers"]), int(cfg["site_consumers"])
    n_keys = int(cfg["deliverer_keys"])
    for t in range(n_tenants):
        tenant, i = f"tenant{t}", 0
        for s in range(sites):
            for _ in range(per_site):
                yield tenant, (ALERTS, f"s{s}", "+"), f"t{t}r{i}", f"d{i % n_keys}"
                i += 1
            for d in range(devices):
                for _ in range(per_dev):
                    yield (tenant, (ALERTS, f"s{s}", f"d{d}"), f"t{t}r{i}",
                           f"d{i % n_keys}")
                    i += 1


def topic_population(cfg: dict) -> List[str]:
    """``draws`` alert topics: devices in an order fixed by the
    population's seed, wrapping."""
    _t, sites, devices, _a = _dims(cfg)
    pop = cfg["topic_population"]
    order = list(range(sites * devices))
    random.Random(int(pop["seed"])).shuffle(order)
    return [f"{ALERTS}/s{k // devices}/d{k % devices}"
            for k in (order[i % len(order)] for i in range(int(pop["draws"])))]


def stress_topics(cfg: dict) -> List[str]:
    """For the warm-up only: a site nobody consumes (the taps alone) and a
    device nobody consumes (the site's consumers alone)."""
    return [f"{ALERTS}/snone/d0", f"{ALERTS}/s0/dnone"]


# ---------------------------------------------------------- retained state

def retained_count(cfg: dict) -> int:
    n_tenants, sites, devices, attrs = _dims(cfg)
    return n_tenants * sites * devices * attrs


def retained_row(cfg: dict, i: int) -> Tuple[str, str, int]:
    """(tenant id, topic, payload bytes) of topic id ``i``."""
    _t, sites, devices, attrs = _dims(cfg)
    rest, a = divmod(i, attrs)
    rest, d = divmod(rest, devices)
    t, s = divmod(rest, sites)
    return (f"tenant{t}", f"{STATE}/s{s}/d{d}/{cfg['attributes'][a]}",
            int(cfg["retained"]["payload_bytes"]))


def retained(cfg: dict) -> Iterator[Tuple[str, str, int]]:
    """Every seeded retained message, by topic id: (tenant id, topic,
    payload bytes)."""
    n_tenants, sites, devices, _a = _dims(cfg)
    size = int(cfg["retained"]["payload_bytes"])
    for t in range(n_tenants):
        tenant = f"tenant{t}"
        for s in range(sites):
            for d in range(devices):
                head = f"{STATE}/s{s}/d{d}/"
                for attr in cfg["attributes"]:
                    yield tenant, head + attr, size


def retained_stress_filters(cfg: dict) -> List[Tuple[str, str]]:
    """For the set-up's warm-up of the retained scans only: one filter of
    each shape, and one that matches nothing."""
    attr = cfg["attributes"][0]
    return [("tenant0", f"{STATE}/s0/d0/{attr}"), ("tenant0", f"{STATE}/s0/d0/#"),
            ("tenant0", f"{STATE}/s0/+/{attr}"), ("tenant0", f"{STATE}/s0/#"),
            ("tenant0", f"{STATE}/snone/#")]


class FilterSource:
    """Filters for live subscribers, settle rounds, churn and SUBSCRIBE lanes.

    A plain draw is one device's alerts ``alerts/s<s>/d<d>``, a churn draw
    (``wildcard_only``) a site's ``alerts/s<s>/+``; site and device uniform.
    A ``retained`` draw is a state SUBSCRIBE of a shape drawn by
    ``retained.filter_mix``, its site and device uniform or, with ``zipf``,
    Zipf(1) over their numbers, the attribute uniform.

    A retained SET's topic (``retained_topic``) is drawn as a ``zipf``
    state SUBSCRIBE draws its site and device: the devices that report
    most are the ones watched."""

    def __init__(self, cfg: dict) -> None:
        self.tenants, self.sites, self.devices, _a = _dims(cfg)
        self.attrs = list(cfg["attributes"])
        mix = cfg.get("retained", {}).get("filter_mix", {})
        self.shape_cum = _cum(float(mix.get(k, 0)) for k in SHAPES)
        self.site_cum = _zipf_cum(self.sites)
        self.device_cum = _zipf_cum(self.devices)

    @staticmethod
    def _pick(rng: random.Random, n: int, cum) -> int:
        if cum is None:
            return rng.randrange(n)
        return bisect.bisect_left(cum, rng.random() * cum[-1])

    def draw(self, rng: random.Random, wildcard_only: bool = False,
             retained: bool = False, zipf: bool = False) -> str:
        site = self._pick(rng, self.sites, self.site_cum if zipf else None)
        device = self._pick(rng, self.devices, self.device_cum if zipf else None)
        if not retained:
            if wildcard_only:
                return f"{ALERTS}/s{site}/+"
            return f"{ALERTS}/s{site}/d{device}"
        shape = SHAPES[self._pick(rng, len(SHAPES), self.shape_cum)]
        attr = self.attrs[rng.randrange(len(self.attrs))]
        return {"exact": f"{STATE}/s{site}/d{device}/{attr}",
                "device": f"{STATE}/s{site}/d{device}/#",
                "site_attr": f"{STATE}/s{site}/+/{attr}",
                "site": f"{STATE}/s{site}/#"}[shape]

    def retained_topic(self, rng: random.Random) -> int:
        """A SET's topic id (``retained_row``'s order): tenant and attribute
        uniform, site and device Zipf(1) over their numbers."""
        t = rng.randrange(self.tenants)
        site = self._pick(rng, self.sites, self.site_cum)
        device = self._pick(rng, self.devices, self.device_cum)
        attr = rng.randrange(len(self.attrs))
        return ((t * self.sites + site) * self.devices + device) \
            * len(self.attrs) + attr

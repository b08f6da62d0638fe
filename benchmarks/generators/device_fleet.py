"""A device fleet, point to point: every device owns a small sub-tree of
exact topics and is the one receiver of each.

The deployment generator behind ``configs/device_command_1m.json``. The
topic scheme follows the AWS IoT device-shadow namespace (a handful of
reserved topics a thing): device ``j`` of site ``k`` subscribes to

    fleet/s<k>/d<j>/cmd/req        fleet/s<k>/d<j>/shadow/delta
    fleet/s<k>/d<j>/cmd/cancel     fleet/s<k>/d<j>/shadow/accepted
    fleet/s<k>/d<j>/ota/notify

so the table holds no ``+`` and no ``#``, every filter has depth 5, and a
publish has one receiver or none. Sites have Zipf(1) sizes. Beside the
seeded devices there is a ROAMING set: devices with ids after the seeded
ones, spread over the sites by the same Zipf, that are NOT in the table.
Their ``cmd/req`` filters are what live subscribers, the settle rounds and
the churn subscribe to, and every tenth publish topic is one of theirs: a
command to a device that may not be there.

It imports nothing of the program: rows are plain tuples. A generator
module is found by the ``generator`` key of a configuration file and offers
``tenant_sizes(cfg)``, ``subscriptions(cfg)``, ``topic_population(cfg)``,
``stress_topics(cfg)`` and ``FilterSource(cfg)``.
"""

from __future__ import annotations

import bisect
import random
from itertools import accumulate
from typing import Iterator, List, Tuple

TENANT = "tenant0"
CHANNELS = (("cmd", "req"), ("cmd", "cancel"), ("shadow", "delta"),
            ("shadow", "accepted"), ("ota", "notify"))
ROAMING_EVERY = 10      # every tenth rank of the population is roaming


def zipf_cum(n: int) -> List[float]:
    """CUMULATIVE Zipf(1) weights of ranks 0..n-1."""
    return list(accumulate(1.0 / (i + 1) for i in range(n)))


def site_sizes(n_sites: int, n_devices: int) -> List[int]:
    """Zipf(1) shares of ``n_devices``, rounded down, the remainder handed
    out one each from the largest site on: the sizes sum to ``n_devices``."""
    cum = zipf_cum(n_sites)
    sizes = [int(n_devices / ((k + 1) * cum[-1])) for k in range(n_sites)]
    for k in range(n_devices - sum(sizes)):
        sizes[k % n_sites] += 1
    return sizes


def levels_of(site: int, device: int, channel: int) -> Tuple[str, ...]:
    kind, verb = CHANNELS[channel]
    return ("fleet", f"s{site}", f"d{device}", kind, verb)


def seeded_sites(cfg: dict) -> List[int]:
    """Site of every seeded device id: the ids are dealt to the sites in
    an order shuffled by ``table_seed``."""
    n = int(cfg["devices"])
    ids = list(range(n))
    random.Random(int(cfg["table_seed"])).shuffle(ids)
    site_of = [0] * n
    at = 0
    for k, size in enumerate(site_sizes(int(cfg["sites"]), n)):
        for j in ids[at:at + size]:
            site_of[j] = k
        at += size
    return site_of


def roaming_filters(cfg: dict) -> List[Tuple[str, ...]]:
    """The ``cmd/req`` filter of every roaming device, by rank. Rank ``r``
    has id ``devices + r`` and a site drawn by the sites' Zipf."""
    rng = random.Random(f"{int(cfg['table_seed'])}:roaming")
    cum = zipf_cum(int(cfg["sites"]))
    first = int(cfg["devices"])
    return [levels_of(bisect.bisect_left(cum, rng.random() * cum[-1]),
                      first + r, 0)
            for r in range(int(cfg["roaming_devices"]))]


def tenant_sizes(cfg: dict) -> List[Tuple[str, int]]:
    return [(TENANT, int(cfg["devices"]) * int(cfg["filters_per_device"]))]


def subscriptions(cfg: dict) -> Iterator[Tuple[str, Tuple[str, ...], str, str]]:
    """Every row of the deployment's table, from ``cfg['table_seed']``:
    (tenant id, filter levels, receiver id, deliverer key). One receiver a
    device, ``filters_per_device`` rows each."""
    per = int(cfg["filters_per_device"])
    n_keys = int(cfg["deliverer_keys"])
    for j, site in enumerate(seeded_sites(cfg)):
        rid, dkey = f"dev{j}", f"d{j % n_keys}"
        for c in range(per):
            yield TENANT, levels_of(site, j, c), rid, dkey


def topic_population(cfg: dict) -> List[str]:
    """``draws`` publish topics in popularity order (the mixes draw Zipf(1)
    over the rank). Every tenth rank is the ``cmd/req`` topic of the roaming
    device of rank ``i // 10`` (wrapping past the last one); every other
    rank is a seeded device's topic, the devices in an order fixed by the
    population's seed and the channel ``i mod 5``."""
    pop = cfg["topic_population"]
    per = int(cfg["filters_per_device"])
    site_of = seeded_sites(cfg)
    order = list(range(len(site_of)))
    random.Random(int(pop["seed"])).shuffle(order)
    roaming = roaming_filters(cfg)
    out, m = [], 0
    for i in range(int(pop["draws"])):
        if i % ROAMING_EVERY == ROAMING_EVERY - 1:
            levels = roaming[(i // ROAMING_EVERY) % len(roaming)]
        else:
            j = order[m % len(order)]
            levels = levels_of(site_of[j], j, i % per)
            m += 1
        out.append("/".join(levels))
    return out


def stress_topics(cfg: dict) -> List[str]:
    """For the warm-up only: a topic under the largest site that no device
    owns (the walk leaves the trie at the widest node), and one under a
    site that does not exist."""
    return ["fleet/s0/dnone/cmd/req", "fleet/snone/dnone/cmd/req"]


class FilterSource:
    """Filters for live subscribers, settle rounds and churn: roaming
    devices' ``cmd/req`` filters, a device drawn by Zipf(1) over its rank
    from the caller's rng. One source hands out each roaming device at most
    once, so no topic ever has two receivers."""

    def __init__(self, cfg: dict) -> None:
        self.filters = roaming_filters(cfg)
        self.cum = zipf_cum(len(self.filters))
        self.taken = set()

    def draw(self, rng: random.Random, wildcard_only: bool = False) -> str:
        """``wildcard_only`` is accepted and ignored: this deployment has
        no wildcard filter to draw."""
        if len(self.taken) >= len(self.filters):
            raise ValueError("every roaming device has been handed out")
        while True:
            r = bisect.bisect_left(self.cum, rng.random() * self.cum[-1])
            if r not in self.taken:
                self.taken.add(r)
                return "/".join(self.filters[r])

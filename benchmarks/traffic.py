"""The one traffic generator: a mix's data file -> a run's plan.

A plan is a pure function of (configuration file, traffic file, --seed,
--seconds). The AMOUNT of work is fixed by the files: the multiset of
(tenant, topic) draws, of inter-arrival gaps and of live filters comes
from the mix's own ``shape_seed``, as do the churn's instants; ``--seed``
decides their ORDER (inside blocks of ``order_block``), the QoS phase, the
churn's filters, and which publishes are sampled for the full comparison. So runs on different seeds do the same
work in another order (the builder's contract asks for exactly this), and
the same seed gives the same plan.

Keys of a traffic file (all data, no code per mix):
  loop            "open" (Poisson arrivals at rate_per_s, latency from the
                  DUE time) or "closed" (lanes with one QoS 1 publish in
                  flight each)
  rate_per_s      open loop only: offered publishes per second
  publishers      closed: lanes; open: connections of a one-tenant pool
  qos             cycle of publish QoS levels
  tenant_draw     "by_size" (tenant drawn in proportion to its table)
  topic_draw      "uniform" or "zipf" over the configuration's population
  payload_bytes   PUBLISH payload (16 bytes of it are seq and due time)
  live            {"generated": n filters from the configuration's
                  generator on n connections, "taps": one "#" subscriber
                  for each of the first k tenants by size, "qos": cycle}
  churn_per_s     live SUBSCRIBEs + UNSUBSCRIBEs per second (alternating)
  warmup_seconds  unmeasured traffic of the same mix before the window
  warm_bursts     simultaneous-publish bursts sent first, one per size
  settle          {"round": n, "min_rounds": a, "max_rounds": b}: before the
                  warm-up, rounds of n live SUBSCRIBEs + UNSUBSCRIBEs of
                  fresh filters, repeated while the resident tables still
                  change shape (a table that has just been built grows on
                  its first hundred patches, and the walk re-traces; a
                  broker that has been up has that behind it)
  sample_stride   one publish in this many is compared receiver by receiver
  draws           closed loop: length of the (tenant, topic) cycle
  order_block     the seed permutes publishes (and gaps) inside blocks of
                  this many
  shape_seed      seed of the fixed multisets

Retained on subscribe (only with a configuration whose ``retained``
section seeds retained messages; a mix without these keys plans as before):
  resub           {"lanes": n, "qos": cycle, "filter_draw": "uniform" |
                  "zipf", "pool": p}: n SUBSCRIBE lanes on connections of
                  their own, each of one tenant (drawn by size) with p state
                  filters of its own from the generator's
                  ``FilterSource.draw(rng, retained=True)``; a lane walks its
                  filters round, SUBSCRIBE -> SUBACK -> UNSUBSCRIBE ->
                  UNSUBACK, one operation in flight
  retain_set_per_s   retained PUBLISHes (RETAIN bit, QoS 1) a second on the
                  seeded topics, from a publisher pool of their own
  retain_clear_share the share of them with an empty payload (a CLEAR)
The lanes' filters and the SET topics are fixed multisets (a SET's topic
drawn by the generator's ``FilterSource.retained_topic``); ``--seed``
orders them, as it orders the publishes.
"""

from __future__ import annotations

import bisect
import importlib
import json
import math
import os
import random
import struct
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))

# A retained message's payload begins (RETAINED_MARK | topic id, version):
# the first word is above every seq the load generator numbers its
# publishes with (window seqs count from 0, warm-up ones from 1 << 62), so
# no reader takes it for a publish of its own. Version 0 is the seed's.
RETAINED_MARK = 1 << 63
_HEADER = struct.Struct(">Qq")


def retained_payload(tid: int, version: int, nbytes: int) -> bytes:
    head = _HEADER.pack(RETAINED_MARK | tid, version)
    return head + b"r" * max(0, nbytes - len(head))


def retained_header(payload: bytes):
    """(topic id, version) of a retained message's payload, else ``None``."""
    if len(payload) < _HEADER.size:
        return None
    word, version = _HEADER.unpack_from(payload)
    if not word & RETAINED_MARK:
        return None
    return word & ~RETAINED_MARK, version


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(workload: str, bench_file: str = "") -> dict:
    """BENCHMARK.json's entry for ``workload`` with its two data files.
    ``bench_file`` (tests, rehearsals) names another file of that form."""
    path = bench_file or os.path.join(os.path.dirname(HERE), "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in {path}")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    root = os.path.dirname(HERE)
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    return {"cell": cell, "bench": bench, "config": cfg,
            "traffic": load_json("traffic", cell["traffic"] + ".json")}


def generator_of(cfg: dict):
    return importlib.import_module(f"generators.{cfg['generator']}")


def _cum(weights) -> List[float]:
    acc, out = 0.0, []
    for w in weights:
        acc += w
        out.append(acc)
    return out


def _draws(rng: random.Random, n: int, tenant_cum, n_topics: int,
           topic_cum) -> List[tuple]:
    out = []
    for _ in range(n):
        t = bisect.bisect_left(tenant_cum, rng.random() * tenant_cum[-1])
        if topic_cum is None:
            k = rng.randrange(n_topics)
        else:
            k = bisect.bisect_left(topic_cum, rng.random() * topic_cum[-1])
        out.append((t, k))
    return out


def _shuffle_blocks(rng: random.Random, items: list, block: int) -> None:
    for lo in range(0, len(items), block):
        part = items[lo:lo + block]
        rng.shuffle(part)
        items[lo:lo + block] = part


def build_plan(cfg: dict, traffic: dict, seed: int, seconds: float) -> dict:
    gen = generator_of(cfg)
    sizes = gen.tenant_sizes(cfg)
    tenants = [t for t, _n in sizes]
    population = gen.topic_population(cfg)
    tenant_cum = _cum(n for _t, n in sizes)
    topic_cum = (_cum(1.0 / (i + 1) for i in range(len(population)))
                 if traffic["topic_draw"] == "zipf" else None)
    fixed = random.Random(int(traffic["shape_seed"]))
    order = random.Random(int(seed))
    qos_cycle = [int(q) for q in traffic["qos"]]
    qos_phase = order.randrange(len(qos_cycle))

    # ---- live subscribers (fixed multiset; taps first)
    live = traffic["live"]
    live_qos = [int(q) for q in live["qos"]]
    subs = []            # (tenant index, filter, qos)
    for t in range(min(int(live["taps"]), len(tenants))):
        subs.append((t, "#", live_qos[t % len(live_qos)]))
    source = gen.FilterSource(cfg)
    for i in range(int(live["generated"])):
        t = bisect.bisect_left(tenant_cum, fixed.random() * tenant_cum[-1])
        subs.append((t, source.draw(fixed), live_qos[i % len(live_qos)]))
    n_taps = min(int(live["taps"]), len(tenants))

    # ---- warm-up traffic (fixed; never measured)
    warm_n = max(1, int(round(float(traffic["warmup_seconds"])
                              * float(traffic.get("rate_per_s", 0) or 0))))
    if traffic["loop"] == "closed":
        warm_n = int(traffic["publishers"]) * 4
    warm = _draws(fixed, warm_n, tenant_cum, len(population), topic_cum)
    bursts = [_draws(fixed, int(b), tenant_cum, len(population), topic_cum)
              for b in traffic["warm_bursts"]]

    # ---- the window's publishes. The seed reorders them inside blocks of
    # ``order_block``: a closed loop gets as far through its cycle as the
    # system lets it, and an open loop's queue remembers the last second,
    # so with whole-sequence shuffles the seed would change WHICH publishes
    # a window holds and which heavy ones meet; inside blocks it changes
    # the order and leaves the work alone.
    block = int(traffic["order_block"])
    stress = [(t, topic) for topic in gen.stress_topics(cfg)
              for t in range(min(8, len(tenants)))]
    plan = {"tenants": tenants, "population": population, "subs": subs,
            "stress": stress,
            "n_taps": n_taps, "warm": warm, "bursts": bursts,
            "qos_cycle": qos_cycle, "qos_phase": qos_phase,
            "loop": traffic["loop"], "seconds": float(seconds),
            "payload_bytes": int(traffic["payload_bytes"]),
            "publishers": int(traffic["publishers"]),
            "warmup_seconds": float(traffic["warmup_seconds"])}
    if traffic["loop"] == "open":
        n = max(1, int(round(float(traffic["rate_per_s"]) * seconds)))
        draws = _draws(fixed, n, tenant_cum, len(population), topic_cum)
        gaps = [fixed.expovariate(1.0) for _ in range(n)]
        _shuffle_blocks(order, draws, block)
        _shuffle_blocks(order, gaps, block)
        scale = seconds / sum(gaps)        # every arrival inside the window
        due, acc = [], 0.0
        for g in gaps:
            due.append(acc)                # the first is due at 0
            acc += g * scale
        plan["arrivals"] = [(due[i], draws[i][0], draws[i][1]) for i in range(n)]
    else:
        draws = _draws(fixed, int(traffic["draws"]), tenant_cum,
                       len(population), topic_cum)
        _shuffle_blocks(order, draws, block)
        plan["cycle"] = draws

    # ---- connection pools: a session serves its publishes one at a time,
    # so a tenant gets as many connections as its share of the lanes needs
    lanes = int(traffic["publishers"])
    total = tenant_cum[-1]
    if len(tenants) == 1:
        pools = [lanes]
    else:
        used = {d[0] for d in draws} | {d[0] for d in warm}
        used |= {d[0] for b in bursts for d in b} | {t for t, _ in stress}
        pools = [max(1, math.ceil(2.0 * lanes * n / total)) if t in used
                 else 0 for t, (_tenant, n) in enumerate(sizes)]
    plan["pools"] = pools

    # ---- churn: alternating SUBSCRIBE / UNSUBSCRIBE on the generated
    # subscribers' connections; wildcard filters from the seed, instants
    # fixed (a SUBSCRIBE holds the broker's one thread for tens of ms, so
    # where it falls among the arrivals is part of the work)
    per_s = float(traffic["churn_per_s"])
    churn = []
    n_gen = int(live["generated"])
    if per_s > 0 and n_gen:
        slot = 1.0 / per_s
        pre = int(traffic.get("pre_churn", 4))
        k = 0
        for j in range(pre):               # before the window: targets for
            churn.append((-1.0, "sub", n_taps + (k % n_gen),   # the unsubs
                          source.draw(order, wildcard_only=True)))
            k += 1
        n_slots = int(seconds / slot)
        for j in range(n_slots):
            at = (j + fixed.random() * 0.8) * slot     # same for every seed
            if j % 2 == 0:
                churn.append((at, "sub", n_taps + (k % n_gen),
                              source.draw(order, wildcard_only=True)))
                k += 1
            else:
                churn.append((at, "unsub", -1, ""))   # oldest churn filter
    plan["churn"] = churn
    settle = traffic.get("settle", {"round": 0, "min_rounds": 0,
                                    "max_rounds": 0})
    plan["settle"] = settle
    plan["settle_filters"] = [
        source.draw(fixed, wildcard_only=False)
        for _ in range(int(settle["round"]) * int(settle["max_rounds"]))]
    stride = int(traffic["sample_stride"])
    plan["sample"] = (stride, order.randrange(stride))
    if "resub" in traffic:
        plan["resub"] = _resub_lanes(gen, cfg, traffic, fixed, order,
                                     tenant_cum)
    if float(traffic.get("retain_set_per_s", 0)) > 0:
        plan["retain_sets"] = _retain_sets(gen, cfg, traffic, fixed, order,
                                           tenants, seconds)
    return plan


def _resub_lanes(gen, cfg, traffic, fixed, order, tenant_cum) -> dict:
    """Each lane's tenant and its filters, in the order ``--seed`` gives."""
    rs = traffic["resub"]
    source = gen.FilterSource(cfg)
    zipf = rs.get("filter_draw", "uniform") == "zipf"
    lanes = []
    for _k in range(int(rs["lanes"])):
        t = bisect.bisect_left(tenant_cum, fixed.random() * tenant_cum[-1])
        pool = [source.draw(fixed, retained=True, zipf=zipf)
                for _ in range(int(rs["pool"]))]
        _shuffle_blocks(order, pool, int(traffic["order_block"]))
        lanes.append((t, pool))
    return {"lanes": lanes, "qos": [int(q) for q in rs["qos"]]}


def _retain_sets(gen, cfg, traffic, fixed, order, tenants, seconds) -> list:
    """Retained SET / CLEAR events ``(at, topic id, tenant index, topic,
    payload bytes, kind)``: instants and kinds fixed by ``shape_seed``, the
    topics a fixed multiset that ``--seed`` orders. Three events at ``at``
    -1 go before the window (a SET, then a CLEAR and a SET of one topic):
    they warm the path and leave the seed's topic set as it was."""
    per_s = float(traffic["retain_set_per_s"])
    clear_share = float(traffic.get("retain_clear_share", 0))
    source = gen.FilterSource(cfg)
    n = int(seconds * per_s)
    ats = [(j + fixed.random() * 0.8) / per_s for j in range(n)]
    kinds = ["clear" if fixed.random() < clear_share else "set"
             for _ in range(n)]
    tids = [source.retained_topic(fixed) for _ in range(n)]
    order.shuffle(tids)
    a, b = source.retained_topic(fixed), source.retained_topic(fixed)
    events = [(-1.0, a, "set"), (-1.0, b, "clear"), (-1.0, b, "set")]
    events += list(zip(ats, tids, kinds))
    index = {t: i for i, t in enumerate(tenants)}
    out = []
    for at, tid, kind in events:
        tenant, topic, nbytes = gen.retained_row(cfg, tid)
        out.append((at, tid, index[tenant], topic, nbytes, kind))
    return out


def fingerprint(plan: dict) -> str:
    """A digest of everything a plan holds (selfcheck: purity in --seed)."""
    import hashlib
    keys = ("subs", "warm", "bursts", "qos_phase", "pools", "churn",
            "sample", "arrivals", "cycle", "settle_filters")
    held = {k: plan.get(k) for k in keys}
    for k in ("resub", "retain_sets"):     # only where the mix has them
        if k in plan:
            held[k] = plan[k]
    blob = json.dumps(held, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()

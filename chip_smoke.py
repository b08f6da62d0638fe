#!/usr/bin/env python3
"""Publish -> match -> deliver on a real TPU, through the entry points a
user calls. The quickest proof that the broker still starts on the chip.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --mesh4    # four chips: the tenant-sharded mesh

One chip: starts ``bifromq_tpu.starter.Standalone`` in-process (MQTT
listener -> DistService -> DistWorker -> TpuMatcher -> deliverer), loads
1,000,000 wildcard subscriptions for one tenant (BASELINE config 2,
seeded) through the dist worker's route-mutation path, connects real MQTT
clients over loopback TCP (live SUBSCRIBEs that ride the patch path, a few
thousand QoS 0 / QoS 1 PUBLISHes from several publishers, one retained
message picked up by a late wildcard SUBSCRIBE) and compares the
delivered (subscriber, topic, QoS) sets with ``models/oracle.py``.

The 1M subscriptions stand for a receiver fleet this process does not
host, so their routes point at a recording sub-broker (the ISubBroker
plug-in seat, id 7) instead of the transient-session broker — a route to
a session that does not exist would be reaped on first delivery.

It FAILS (non-zero exit, no result line) when JAX finds no TPU, when any
batch was served by the host oracle instead of the device, when a device
watchdog fired, when a jit warm-up raised, when the resident tables are
not on the TPU, or when a delivered set differs from the oracle's.

Last stdout line on success:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import dataclasses
import json
import sys
import time

SEED = 0
N_SUBS = 1_000_000            # config 2; never below 100,000
N_TOPICS = 512                # distinct publish topics (seeded, Zipf)
N_PUBLISHES = 2_048           # across N_PUBLISHERS, QoS 0 and 1 alternating
N_PUBLISHERS = 4
MESH_TENANTS = 1_000          # config 5 cut to one four-chip host
MESH_SUBS = 1_000_000
MESH_PUB_TENANTS = 6
MESH_PUBLISHES = 384
FLEET_BROKER_ID = 7
LOAD_CHUNK = 20_000
LOAD_BUDGET_S = 700.0         # of the driver's 1,200 s: cut the size, never
MIN_SUBS = 100_000            # below MIN_SUBS, rather than run out of time
LIVE_KEY = "live"             # deliverer key of live clients in the oracle


def say(msg: str) -> None:
    print(f"[smoke +{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


_T0 = time.perf_counter()


class SmokeFailure(Exception):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --------------------------------------------------------------------------
# device + build surface
# --------------------------------------------------------------------------

def claim_devices(n: int):
    """The platform assertion — before anything else touches the broker.
    No CPU continuation: a run that finds no chip fails here."""
    import jax
    from bifromq_tpu.utils.jaxenv import setup_compile_cache
    cache_dir = setup_compile_cache()
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX backend is {jax.default_backend()!r}, "
              "not 'tpu' — no chip, no run", file=sys.stderr)
        sys.exit(2)
    devices = jax.devices()
    if len(devices) != n or any(d.platform != "tpu" for d in devices):
        print(f"chip_smoke: need exactly {n} tpu device(s), found "
              f"{[str(d) for d in devices]}", file=sys.stderr)
        sys.exit(2)
    say(f"devices: {n} x {devices[0].device_kind}; compile cache: "
        f"{cache_dir}")
    return devices


class CacheCounter:
    """Counts JAX persistent-compile-cache hits and misses."""

    def __init__(self) -> None:
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def native_report() -> None:
    """Build/load every native library now and NAME the outcome — a
    library that fails to build must not be silently replaced by its
    Python twin."""
    from bifromq_tpu.kv import native as kv_native
    from bifromq_tpu.models import native_retained, native_tok
    from bifromq_tpu.ops import match as ops_match
    from bifromq_tpu.utils import nativelib
    for load in (native_tok.load_lib, native_retained.load_lib,
                 kv_native.load_lib, ops_match._expand_lib):
        try:
            load()
        except RuntimeError as e:
            say(f"native build/load failed: {e}")
    status = nativelib.status()
    say(f"native libraries: {json.dumps(status, sort_keys=True)}")
    require(len(status) == 4 and set(status.values()) == {"loaded"},
            f"native libraries not all loaded: {status}")


# --------------------------------------------------------------------------
# the recording receiver fleet
# --------------------------------------------------------------------------

class FleetSubBroker:
    """Sub-broker plug-in standing for the receiver fleet the synthetic
    subscriptions belong to. Per (tenant, topic, deliverer): the receiver
    set it was asked to deliver to, and how often per QoS."""

    id = FLEET_BROKER_ID

    def __init__(self) -> None:
        self.sets = {}
        self.calls = collections.Counter()
        self.set_changes = 0
        self.deliveries = 0

    async def deliver(self, tenant_id, deliverer_key, packs):
        from bifromq_tpu.plugin.subbroker import DeliveryResult
        out = {}
        for dp in packs:
            topic = dp.message_pack.topic
            rcv = frozenset(mi.receiver_id for mi in dp.match_infos)
            key = (tenant_id, topic, deliverer_key)
            if self.sets.setdefault(key, rcv) != rcv:
                self.set_changes += 1
            for pmp in dp.message_pack.packs:
                for msg in pmp.messages:
                    self.calls[key + (int(msg.pub_qos),)] += 1
                    self.deliveries += len(rcv)
            out.update(dict.fromkeys(dp.match_infos, DeliveryResult.OK))
        return out

    async def check_subscriptions(self, tenant_id, match_infos):
        return [True] * len(match_infos)


async def load_fleet(worker, tries, budget_s: float = LOAD_BUDGET_S) -> int:
    """Every workload route through the dist worker's batched add-route
    path: ``LOAD_CHUNK`` encoded ops per coproc call = one raft entry,
    applied by ``DistWorkerCoProc.mutate`` -> ``matcher.add_route`` (the
    path ``tests/test_batch_mutations.py`` exercises). One ``add_route``
    coroutine per route rides 7-op scheduler windows and a raft log
    snapshot every few hundred entries — quadratic at 1M routes."""
    from bifromq_tpu.kv import schema
    from bifromq_tpu.dist.worker import (decode_batch_reply,
                                         encode_add_route, encode_batch)
    n = 0
    ops = []
    first_key = None
    t_end = time.monotonic() + budget_s

    async def flush():
        rid = worker.store.router.find_by_key(first_key)
        out = await worker.store.ranges[rid].mutate_coproc(
            encode_batch(ops))
        bad = collections.Counter(decode_batch_reply(out))
        bad.pop(b"ok", None)
        require(not bad, f"add_route statuses: {bad}")

    for tenant_id, trie in tries.items():
        for route in trie.routes():
            route = dataclasses.replace(route, broker_id=FLEET_BROKER_ID)
            if first_key is None:
                first_key = schema.route_key(tenant_id, route.matcher,
                                             route.receiver_url)
            ops.append(encode_add_route(tenant_id, route))
            n += 1
            if len(ops) >= LOAD_CHUNK:
                await flush()
                ops = []
                if time.monotonic() > t_end:
                    return n      # the caller states the cut
    if ops:
        await flush()
    return n


def fleet_expectation(tries, publishes):
    """What the oracle says the fleet must have been handed."""
    from bifromq_tpu.utils import topic as topic_util
    sets, calls = {}, collections.Counter()
    matched = {}
    for tenant_id, topic, qos in publishes:
        m = matched.get((tenant_id, topic))
        if m is None:
            m = tries[tenant_id].match(topic_util.parse(topic))
            matched[(tenant_id, topic)] = m
        by_key = collections.defaultdict(set)
        for r in m.normal:
            if r.deliverer_key != LIVE_KEY:
                by_key[r.deliverer_key].add(r.receiver_id)
        for dkey, rcv in by_key.items():
            sets[(tenant_id, topic, dkey)] = frozenset(rcv)
            calls[(tenant_id, topic, dkey, qos)] += 1
    return sets, calls, matched


# --------------------------------------------------------------------------
# verdicts shared by both modes
# --------------------------------------------------------------------------

def device_verdict(matcher, platform: str, n_devices: int) -> dict:
    """The conditions under which the chip was absent or bypassed.
    Returns the device batches counted by kernel."""
    import jax
    from bifromq_tpu.obs import OBS
    from bifromq_tpu.utils.metrics import FABRIC, FabricMetric
    recs = OBS.profiler.records()
    kernels = collections.Counter(r.kernel for r in recs)
    degraded = FABRIC.get(FabricMetric.MATCH_DEGRADED)
    timeouts = FABRIC.get(FabricMetric.DEVICE_TIMEOUT)
    warm_failed = FABRIC.get(FabricMetric.WARMUP_FAILED)
    say(f"device batches by kernel: {dict(kernels)}; queries walked: "
        f"{OBS.profiler.queries_total}; match_degraded={degraded} "
        f"device_timeout={timeouts} warmup_failed={warm_failed} "
        f"profiler_degraded={OBS.profiler.degraded_total}")
    from bifromq_tpu.ops.match import device_expand_enabled
    from bifromq_tpu.ops.tokenize import device_tokenize_enabled
    from bifromq_tpu.models.matcher import TpuMatcher
    # the mesh leg overrides topic prep and always tokenizes on the host
    dev_tok = (device_tokenize_enabled() and type(matcher)._prepare_probes
               is TpuMatcher._prepare_probes)
    say("served by: tokenizer="
        + ("device (_hash_lanes_lax)" if dev_tok else "host (native)")
        + "; walk=" + "/".join(sorted(kernels)) + "; expander="
        + ("device (_expand_pairs + _bucket_pairs)"
           if device_expand_enabled() else "host (native)"))
    require(degraded == 0, f"MATCH_DEGRADED = {degraded}")
    require(timeouts == 0, f"DEVICE_TIMEOUT = {timeouts}")
    require(warm_failed == 0, f"jit warm-up raised {warm_failed} time(s)")
    require(kernels.get("oracle", 0) == 0,
            f"{kernels['oracle']} batch(es) served by the host oracle")
    require(sum(kernels.values()) > 0, "no batch ran on the device path")
    dev = matcher._device_trie
    leaves = [a for a in jax.tree_util.tree_leaves(dev) if a is not None]
    on = set()
    for a in leaves:
        on |= set(a.devices())
    require(all(d.platform == platform for d in on),
            f"resident tables on {[str(d) for d in on]}, not {platform}")
    require(len(on) == n_devices,
            f"tables on {len(on)} device(s), expected {n_devices}")
    resident = sum(int(a.nbytes) for a in leaves)
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    say(f"resident table bytes: {resident:,} on "
        f"{sorted(str(d) for d in on)}; device memory in use/peak: "
        f"{[(s.get('bytes_in_use'), s.get('peak_bytes_in_use')) for s in stats]}")
    return dict(kernels)


def fleet_verdict(fleet, want_sets, want_calls) -> None:
    say(f"fleet: {len(fleet.sets):,} (topic, deliverer) sets, "
        f"{sum(fleet.calls.values()):,} deliver calls, "
        f"{fleet.deliveries:,} route deliveries")
    require(fleet.set_changes == 0,
            f"{fleet.set_changes} repeat publishes reached a different set")
    require(fleet.sets == want_sets, "fleet receiver sets != oracle: "
            f"{len(set(fleet.sets) ^ set(want_sets))} keys differ, "
            f"{sum(1 for k in fleet.sets if fleet.sets[k] != want_sets.get(k))}"
            " sets differ")
    require(fleet.calls == want_calls, "fleet delivery counts != oracle")


async def drain_clients(clients, want_total: int, timeout: float = 60.0):
    """Wait until the live clients hold what the oracle expects (or the
    timeout passes), then a grace window for anything unexpected."""
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        if sum(c.messages.qsize() for c in clients) >= want_total:
            break
        await asyncio.sleep(0.05)
    await asyncio.sleep(0.5)
    got = collections.Counter()
    for c in clients:
        while not c.messages.empty():
            p = c.messages.get_nowait()
            got[(c.client_id, p.topic, int(p.qos), bool(p.retain))] += 1
    return got


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------

async def run_single(n_subs: int, platform: str, *,
                     n_topics: int = N_TOPICS,
                     n_publishes: int = N_PUBLISHES) -> None:
    from bifromq_tpu import workloads
    from bifromq_tpu.models.oracle import Route
    from bifromq_tpu.mqtt.client import MQTTClient
    from bifromq_tpu.starter import Standalone
    from bifromq_tpu.types import RouteMatcher
    from bifromq_tpu.utils import topic as topic_util

    tenant = "tenant0"
    say(f"subscriptions: {n_subs:,} wildcard (config 2, seed {SEED})")
    t0 = time.perf_counter()
    tries = workloads.config_wildcard(n_subs, seed=SEED)
    oracle = tries[tenant]
    topics = sorted(set("/".join(t) for t in workloads.probe_topics(
        4 * n_topics, seed=SEED + 1)))[:n_topics]
    say(f"workload generated in {time.perf_counter() - t0:.1f}s "
        f"({len(topics)} distinct publish topics)")

    node = Standalone({"mqtt": {"tcp": {"port": 0}}})
    await node.start()
    try:
        broker = node.broker
        worker = broker.dist.worker
        fleet = FleetSubBroker()
        broker.sub_brokers.register(fleet)
        port = broker.port
        say(f"broker up on 127.0.0.1:{port}")

        t0 = time.perf_counter()
        n = await load_fleet(worker, tries)
        load_s = time.perf_counter() - t0
        matcher = worker.matcher
        say(f"loaded {n:,} routes via the worker's batched add-route path in {load_s:.1f}s "
            f"({n / load_s:,.0f}/s); patched={matcher.patch_count:,} "
            f"patch_fallbacks={matcher.patch_fallbacks} "
            f"full compiles={matcher.compile_count}")
        require(len(matcher.tries[tenant]) == n, f"matcher holds "
                f"{len(matcher.tries[tenant])} of {n} loaded routes")
        if n < n_subs:
            # the load budget ran out on this host: the size actually
            # resident is what the rest of the run (and the oracle) uses
            say(f"SIZE CUT: {n:,} of {n_subs:,} subscriptions loaded in "
                f"the {LOAD_BUDGET_S:.0f}s load budget")
            require(n >= MIN_SUBS, f"only {n} subscriptions loaded")
            from bifromq_tpu.models.oracle import SubscriptionTrie
            oracle = SubscriptionTrie()
            for i, route in enumerate(tries[tenant].routes()):
                if i >= n:
                    break
                oracle.add(route)
        mu = broker.mem_usage
        say(f"host memory: rss {mu.rss_bytes() >> 20:,} MiB of budget "
            f"{mu.budget_bytes >> 20:,} MiB (usage {mu.usage():.3f}, "
            f"reject above {mu.high_watermark})")

        def client(cid):
            return MQTTClient(port=port, client_id=cid,
                              username=f"{tenant}/{cid}")

        # ---- first publish: table upload + walk/expand compile + warm-up
        pubs = [client(f"pub{i}") for i in range(N_PUBLISHERS)]
        for p in pubs:
            await p.connect()
        t0 = time.perf_counter()
        rc = await pubs[0].publish(topics[0], b"warm", qos=1, timeout=900)
        require(rc == 0, f"first PUBACK reason {rc}")
        say(f"first publish (flush {matcher.patch_flushes} patch round(s) + "
            f"compile + walk + deliver): {time.perf_counter() - t0:.1f}s")
        first = [(tenant, topics[0], 1)]

        # ---- live SUBSCRIBEs through the patch path
        deep = [t for t in topics if t.count("/") >= 2]
        t_exact, t_plus, t_hash, t_share = deep[0], deep[1], deep[2], deep[3]
        f_plus = t_plus.rsplit("/", 1)[0] + "/+"
        f_hash = t_hash.split("/", 1)[0] + "/#"
        f_share = t_share.split("/", 1)[0] + "/#"
        live = {"subExact": (t_exact, 1), "subPlus": (f_plus, 0),
                "subHash": (f_hash, 1)}
        share_members = ["subShareA", "subShareB"]
        subs = {cid: client(cid) for cid in [*live, *share_members]}
        compiles_before = matcher.compile_count
        patched_before = matcher.patch_count
        for cid, c in subs.items():
            await c.connect()
        for cid, (flt, qos) in live.items():
            ack = await subs[cid].subscribe(flt, qos=qos)
            require(ack.reason_codes[0] == qos, f"SUBACK {ack.reason_codes}")
            oracle.add(Route(matcher=RouteMatcher.from_topic_filter(flt),
                             broker_id=0, receiver_id=cid,
                             deliverer_key=LIVE_KEY))
        for cid in share_members:
            await subs[cid].subscribe(f"$share/g1/{f_share}", qos=1)
        say(f"live subscriptions: {live} + $share/g1/{f_share} x2; "
            f"patched +{matcher.patch_count - patched_before}, full "
            f"compiles +{matcher.compile_count - compiles_before}")
        require(matcher.patch_count > patched_before,
                "live SUBSCRIBEs did not ride the patch path")
        require(matcher.compile_count == compiles_before,
                "a live SUBSCRIBE triggered a full rebuild")

        # ---- retained message, then the late wildcard subscriber
        rc = await pubs[1].publish("smoke/retained/x", b"kept", qos=1,
                                   retain=True, timeout=60)
        require(rc == 0, f"retained PUBACK reason {rc}")
        late = client("subLate")
        await late.connect()
        await late.subscribe("smoke/retained/#", qos=1)
        got = await late.recv(timeout=30)
        require((got.topic, got.payload, got.retain) ==
                ("smoke/retained/x", b"kept", True),
                f"late subscriber got {got.topic!r} retain={got.retain}")
        say("retained message delivered to the late wildcard subscriber")
        await late.disconnect()

        # ---- the publish mix
        plan = [(topics[i % len(topics)], i % 2) for i in range(n_publishes)]
        plan += [(t, 1) for t in (t_exact, t_plus, t_hash, t_share)]

        async def publisher(k):
            for topic, qos in plan[k::N_PUBLISHERS]:
                rc = await pubs[k].publish(topic, b"x" * 64, qos=qos,
                                           timeout=120)
                require(rc in (None, 0), f"PUBACK reason {rc}")
            # QoS 1 fence: everything this publisher sent is processed
            await pubs[k].publish(f"smoke/fence/{k}", b"", qos=1,
                                  timeout=120)
        t0 = time.perf_counter()
        await asyncio.gather(*(publisher(k) for k in range(N_PUBLISHERS)))
        pub_s = time.perf_counter() - t0
        say(f"{len(plan):,} PUBLISHes (QoS 0/1) from {N_PUBLISHERS} "
            f"publishers in {pub_s:.1f}s")

        # ---- compare with the oracle (outside any timing)
        publishes = first + [(tenant, "smoke/retained/x", 1)] + [
            (tenant, t, q) for t, q in plan] + [
            (tenant, f"smoke/fence/{k}", 1) for k in range(N_PUBLISHERS)]
        want_sets, want_calls, matched = fleet_expectation(
            {tenant: oracle}, publishes)
        want_live = collections.Counter()
        want_share = 0
        share_levels = topic_util.parse(f_share)
        for _tenant, topic, qos in publishes[1:]:
            for r in matched[(tenant, topic)].normal:
                if r.deliverer_key == LIVE_KEY:
                    sub_qos = live[r.receiver_id][1]
                    want_live[(r.receiver_id, topic, min(qos, sub_qos),
                               False)] += 1
            if topic_util.matches(topic_util.parse(topic), share_levels):
                want_share += 1
        got = await drain_clients(list(subs.values()),
                                  sum(want_live.values()) + want_share)
        got_live = collections.Counter(
            {k: v for k, v in got.items() if k[0] in live})
        got_share = collections.Counter(
            {k: v for k, v in got.items() if k[0] in share_members})
        say(f"live clients received {sum(got_live.values()):,} "
            f"(oracle {sum(want_live.values()):,}); $share group received "
            f"{sum(got_share.values()):,} (oracle {want_share:,}) split "
            f"{[sum(v for k, v in got_share.items() if k[0] == m) for m in share_members]}")
        require(got_live == want_live, "live (subscriber, topic, QoS) "
                f"sets != oracle: {(got_live - want_live) + (want_live - got_live)}")
        require(sum(got_share.values()) == want_share,
                "$share group deliveries != one per matching publish")
        fleet_verdict(fleet, want_sets, want_calls)
        say(f"delivered sets equal the oracle's on {len(matched)} topics")

        device_verdict(matcher, platform, 1)
        for c in [*pubs, *subs.values()]:
            await c.disconnect()
    finally:
        await node.stop()


# --------------------------------------------------------------------------
# four chips: the tenant-sharded mesh and what it is compared with
# --------------------------------------------------------------------------

def _canon(m):
    return (sorted((r.matcher.mqtt_topic_filter, r.receiver_id)
                   for r in m.normal),
            {f: sorted(r.receiver_id for r in ms)
             for f, ms in m.groups.items()})


async def run_mesh(n_subs: int, n_tenants: int, platform: str,
                   n_devices: int, *,
                   n_publishes: int = MESH_PUBLISHES) -> None:
    import jax
    import numpy as np
    from bifromq_tpu import workloads
    from bifromq_tpu.models.matcher import TpuMatcher
    from bifromq_tpu.mqtt.client import MQTTClient
    from bifromq_tpu.parallel.sharded import MeshMatcher
    from bifromq_tpu.starter import Standalone

    say(f"subscriptions: ~{n_subs:,} over {n_tenants:,} tenants (config 5 "
        f"cut from 10M/10K to one {n_devices}-chip host, seed {SEED})")
    t0 = time.perf_counter()
    tries = workloads.config_multi_tenant(n_tenants, n_subs, seed=SEED)
    total = sum(len(t) for t in tries.values())
    topics = sorted(set("/".join(t) for t in workloads.probe_topics(
        256, seed=SEED + 1)))[:64]
    say(f"workload generated in {time.perf_counter() - t0:.1f}s "
        f"({total:,} subscriptions)")

    node = Standalone({"mqtt": {"tcp": {"port": 0}}, "dist": {"mesh": True}})
    await node.start()
    try:
        broker = node.broker
        worker = broker.dist.worker
        fleet = FleetSubBroker()
        broker.sub_brokers.register(fleet)
        matcher = worker.matcher
        require(isinstance(matcher, MeshMatcher)
                and matcher.n_shards == n_devices,
                f"worker matcher is {type(matcher).__name__}")
        t0 = time.perf_counter()
        n = await load_fleet(worker, tries)
        load_s = time.perf_counter() - t0
        say(f"loaded {n:,} routes via the worker's batched add-route path in {load_s:.1f}s; "
            f"patched={matcher.patch_count:,} "
            f"patch_fallbacks={matcher.patch_fallbacks} "
            f"full compiles={matcher.compile_count}")

        # the whale, two mid tenants, the tail — one publisher each
        names = sorted(tries, key=lambda t: -len(tries[t]))
        step = max(1, len(names) // MESH_PUB_TENANTS)
        pub_tenants = names[::step][:MESH_PUB_TENANTS]
        pubs = {}
        for t in pub_tenants:
            pubs[t] = MQTTClient(port=broker.port, client_id=f"pub-{t}",
                                 username=f"{t}/pub")
            await pubs[t].connect()
        t0 = time.perf_counter()
        rc = await pubs[pub_tenants[0]].publish(topics[0], b"warm", qos=1,
                                                timeout=900)
        require(rc == 0, f"first PUBACK reason {rc}")
        say("first publish (table upload + mesh step compile + deliver): "
            f"{time.perf_counter() - t0:.1f}s")
        plan = {t: [(topics[(i + j) % len(topics)], i % 2)
                    for i in range(n_publishes // len(pub_tenants))]
                for j, t in enumerate(pub_tenants)}

        async def publisher(t):
            for topic, qos in plan[t]:
                rc = await pubs[t].publish(topic, b"x" * 64, qos=qos,
                                           timeout=120)
                require(rc in (None, 0), f"PUBACK reason {rc}")
            await pubs[t].publish("smoke/fence", b"", qos=1, timeout=120)
        t0 = time.perf_counter()
        await asyncio.gather(*(publisher(t) for t in pub_tenants))
        n_sent = sum(len(v) for v in plan.values())
        say(f"{n_sent} PUBLISHes from {len(pub_tenants)} tenants in "
            f"{time.perf_counter() - t0:.1f}s")

        publishes = [(pub_tenants[0], topics[0], 1)] + [
            (t, topic, qos) for t in pub_tenants for topic, qos in plan[t]
        ] + [(t, "smoke/fence", 1) for t in pub_tenants]
        want_sets, want_calls, matched = fleet_expectation(tries, publishes)
        fleet_verdict(fleet, want_sets, want_calls)
        say(f"delivered sets equal the oracle's on {len(matched)} "
            "(tenant, topic) pairs")

        # ---- against the one-chip matcher on the same data
        queries = sorted(matched)
        one = TpuMatcher.from_tries(
            {t: tries[t] for t in pub_tenants}, device=jax.devices()[0],
            match_cache=False)
        a = one.match_batch(queries)
        b = matcher.match_batch(queries)
        diff = sum(_canon(x) != _canon(y) for x, y in zip(a, b))
        say(f"mesh vs one-chip matcher on {len(queries)} queries: "
            f"{diff} rows differ")
        require(diff == 0, "mesh rows != one-chip rows")

        # ---- every shard's tables on a different device
        dev_tabs = matcher._device_trie
        for name, arr in zip(("edge_tab", "child_list", "route_tab"),
                             dev_tabs):
            placed = {s.index[0].start: s.device
                      for s in arr.addressable_shards}
            require(len(placed) == n_devices
                    and len(set(placed.values())) == n_devices,
                    f"{name} shards on {placed}")
        say(f"each of {n_devices} shards' tables on its own device: "
            f"{sorted(str(d) for d in set(placed.values()))}")

        # ---- the ring merge ran: device totals == host sum over shards
        pairs, _peer_tab = matcher.last_expanded
        totals = np.asarray(pairs.res.peer_totals)
        offs = np.asarray(pairs.peer_offsets)
        host = (offs[..., 1:] - offs[..., :-1]).sum(axis=(0, 1))
        require(np.array_equal(totals, host),
                f"ring-merged peer totals {totals} != host sum {host}")
        say(f"ring all-reduce ran: per-peer totals {totals.tolist()} equal "
            "the host sum over shards")

        kernels = device_verdict(matcher, platform, n_devices)
        require(kernels.get("mesh", 0) > 0,
                f"no mesh step recorded: {kernels}")
        for c in pubs.values():
            await c.disconnect()
    finally:
        await node.stop()


# --------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh4", action="store_true",
                    help="four chips: only the tenant-sharded mesh path "
                         "and what it is compared with")
    args = ap.parse_args()
    n_devices = 4 if args.mesh4 else 1
    devices = claim_devices(n_devices)
    cache = CacheCounter()
    platform = devices[0].platform
    try:
        native_report()
        if args.mesh4:
            asyncio.run(run_mesh(MESH_SUBS, MESH_TENANTS, platform,
                                 n_devices))
        else:
            asyncio.run(run_single(N_SUBS, platform))
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    say(f"compile cache: {cache.hits} hit(s), {cache.misses} miss(es) "
        f"({'warm' if cache.hits and not cache.misses else 'cold or partly cold'})")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

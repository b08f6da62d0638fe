"""The system under test, as the benchmark holds it: everything that
imports the program (``bifromq_tpu``) or JAX lives here.

From the program it takes the entry point (``starter.Standalone``), the
plug-in seats (``ISubBroker``, ``ISettingProvider``), its counters
(``STAGES``, ``FABRIC``, the profiler's ``BatchRecord``s and patch
ledger) and the names of its jitted programs. Copied from
``chip_smoke.py``: ``claim_devices``, the compile-cache counter, the
fleet stand-in's seat and ``device_verdict``'s conditions.
"""

from __future__ import annotations

import os
import struct
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(1, ROOT)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import traffic  # noqa: E402  (beside this file; no program)
from reference import is_shared  # noqa: E402

FLEET_BROKER_ID = 7
WARM_FLAG = 1 << 62
HEADER = struct.Struct(">Qq")
MASK = (1 << 64) - 1


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - log.t0:7.1f}s] {msg}", flush=True)


log.t0 = time.monotonic()


# ------------------------------------------------------------------ device

def claim_devices(n: int, rehearse_cpu: bool = False):
    """The platform assertion, before anything else touches the broker.
    No chip -> exit 2 and no result line. ``rehearse_cpu`` is the
    builder's rehearsal: it reports platform "cpu" and no device metric."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bifromq_tpu.utils.jaxenv import setup_compile_cache
    cache_dir = setup_compile_cache()
    devices = jax.devices()
    if not rehearse_cpu:
        if jax.default_backend() != "tpu" or len(devices) < n \
                or any(d.platform != "tpu" for d in devices):
            print(f"benchmark: need {n} tpu device(s), JAX found "
                  f"{[str(d) for d in devices]} — no chip, no run",
                  file=sys.stderr)
            sys.exit(2)
    log(f"devices: {len(devices)} x {devices[0].device_kind}; "
        f"compile cache: {cache_dir}")
    return devices


class CompileCounter:
    """Persistent-cache hits and misses, and every backend compile with
    its time, so that compiles inside the window can be counted."""

    def __init__(self) -> None:
        import jax
        self.hits = self.misses = 0
        self.compiles = []           # (monotonic_ns at its end, name, s)
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_dur(self, name: str, secs: float, **kw) -> None:
        # fires for every program JAX builds or fetches from the
        # persistent cache: either way a shape the process had not met
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.monotonic_ns(),
                                  str(kw.get("fun_name")), secs))

    def between(self, t0_ns: int, t1_ns: int) -> list:
        return [(n, round(s, 3)) for t, n, s in self.compiles
                if t0_ns <= t < t1_ns]


# ------------------------------------------------------------ plug-in seats

Settings = None      # the class the starter's plug-in loader finds: "sut:Settings"


def install_settings(values: dict):
    """ISettingProvider seat: the configuration file's ``settings``, by
    ``Setting`` name. Returns the class now bound to ``sut:Settings``."""
    global Settings
    from bifromq_tpu.plugin.settings import ISettingProvider
    values = dict(values)

    class _Settings(ISettingProvider):
        def provide(self, setting, tenant_id):
            return values.get(setting.name)
    Settings = _Settings
    return _Settings


class FleetStandIn:
    """The receiver fleet the table's subscriptions belong to, on the
    ISubBroker seat. Cheap inside the window: per deliver call a count and
    an order-independent digest of receiver ids under the publish's seq;
    full receiver sets only for the sampled publishes.

    Where the table holds shared groups (``shared = True``, set by
    ``run.py`` from the rows) a delivery made under a ``$share`` /
    ``$oshare`` matcher is an ELECTION's, the program's choice: it is kept
    out of the count and the digest and recorded as it came, (group filter,
    receiver id, deliverer key) under the publish's seq, for
    ``run.group_verdict`` to hold to "exactly one member a matching group".
    A table without groups pays one test a pack for this."""

    id = FLEET_BROKER_ID

    def __init__(self) -> None:
        from bifromq_tpu.plugin.subbroker import DeliveryResult
        self._ok = DeliveryResult.OK
        self.t0 = self.t1 = 0
        self.stride, self.offset = 1 << 62, 0
        self.shared = False          # the table holds shared groups
        self.reset()
        self.annotate = None         # TraceAnnotation factory while tracing

    def reset(self) -> None:
        self.count = {}              # seq -> route deliveries
        self.digest = {}             # seq -> sum of hash(receiver id)
        self.qos = {}                # seq -> set of pub qos seen
        self.sets = {}               # sampled seq -> {dkey: [receiver ids]}
        self.elected = {}            # seq -> [(group filter, rid, dkey)]
        self.member_load = {}        # (tenant, group filter) -> {rid: n}
        self.calls = 0
        self.in_window = 0
        self.total = 0
        self.spent_s = 0.0

    def arm(self, t0: int, t1: int, stride: int, offset: int) -> None:
        self.reset()
        self.t0, self.t1, self.stride, self.offset = t0, t1, stride, offset

    async def deliver(self, tenant_id, deliverer_key, packs):
        t_in = time.perf_counter()
        span = self.annotate("fleet.deliver") if self.annotate else None
        if span is not None:
            span.__enter__()
        now = time.monotonic_ns()
        out = {}
        for dp in packs:
            infos = dp.match_infos
            n = n_plain = len(infos)
            rids = [mi.receiver_id for mi in infos]
            if self.shared:          # the one test a table without groups pays
                plain = self._keep_elected(tenant_id, deliverer_key, dp, now)
                if plain is not None:
                    rids, n_plain = plain, len(plain)
            dig = sum(map(hash, rids))
            for pmp in dp.message_pack.packs:
                for msg in pmp.messages:
                    self.total += n
                    if self.t0 <= now < self.t1:
                        self.in_window += n
                    payload = msg.payload
                    if len(payload) < HEADER.size:
                        continue
                    seq = HEADER.unpack_from(payload)[0]
                    if seq >= WARM_FLAG:
                        continue
                    self.count[seq] = self.count.get(seq, 0) + n_plain
                    self.digest[seq] = (self.digest.get(seq, 0) + dig) & MASK
                    self.qos.setdefault(seq, set()).add(int(msg.pub_qos))
                    if seq % self.stride == self.offset:
                        self.sets.setdefault(seq, {}).setdefault(
                            (tenant_id, deliverer_key), []).extend(rids)
            out.update(dict.fromkeys(infos, self._ok))
        self.calls += 1
        if span is not None:
            span.__exit__(None, None, None)
        self.spent_s += time.perf_counter() - t_in
        return out

    def _keep_elected(self, tenant_id, deliverer_key, dp, now):
        """A pack's deliveries made under a shared matcher, recorded under
        each of its window publishes; returns the receiver ids of the
        pack's PLAIN routes, or ``None`` where it holds no elected one."""
        infos = dp.match_infos
        group = [(mi.matcher.mqtt_topic_filter, mi.receiver_id, deliverer_key)
                 for mi in infos if mi.matcher.type]
        if not group:
            return None
        in_window = self.t0 <= now < self.t1
        for pmp in dp.message_pack.packs:
            for msg in pmp.messages:
                payload = msg.payload
                if len(payload) < HEADER.size:
                    continue
                seq = HEADER.unpack_from(payload)[0]
                if seq >= WARM_FLAG:
                    continue
                self.elected.setdefault(seq, []).extend(group)
                if in_window:
                    for flt, rid, _dkey in group:
                        load = self.member_load.setdefault(
                            (tenant_id, flt), {})
                        load[rid] = load.get(rid, 0) + 1
        return [mi.receiver_id for mi in infos if not mi.matcher.type]

    async def check_subscriptions(self, tenant_id, match_infos):
        return [True] * len(match_infos)


# ------------------------------------------------------------ build + seed

def build_tries(rows):
    """The generated rows as the program's input types. Returns the tries
    and the row count. ``rows`` yields (tenant, levels, receiver, dkey).
    A row whose levels begin ``$share`` / ``$oshare``, group name, is a
    member of that group: its matcher is the one the program makes of the
    filter string at a SUBSCRIBE (type, group and the levels behind the
    prefix). Every other row is a NORMAL route, as before."""
    from bifromq_tpu.models.oracle import Route, SubscriptionTrie
    from bifromq_tpu.types import RouteMatcher, RouteMatcherType
    normal = RouteMatcherType.NORMAL
    tries, n = {}, 0
    for tenant, levels, rid, dkey in rows:
        trie = tries.get(tenant)
        if trie is None:
            trie = tries[tenant] = SubscriptionTrie()
        if is_shared(levels):
            matcher = RouteMatcher.from_topic_filter("/".join(levels))
        else:
            matcher = RouteMatcher(type=normal, filter_levels=levels,
                                   mqtt_topic_filter="/".join(levels))
        trie.add(Route(matcher=matcher, broker_id=FLEET_BROKER_ID,
                       receiver_id=rid, deliverer_key=dkey))
        n += 1
    return tries, n


def seed_worker(worker, tries) -> dict:
    """Give the started worker the state a restarted one holds: the
    route keyspace in its range's KV space, and the matcher derived from
    it, built in bulk by ``from_tries``. (``matcher_factory``
    cannot carry it: the range's ``reset`` on open replaces whatever
    matcher the factory made with ``clone_empty()``.)

    What is seeded is what the broker STARTED: where the co-processor's
    matcher runs on a mesh (``"dist": {"mesh": true}`` in the
    configuration's ``broker``), a matcher of that class on that very
    mesh with the started one's parameters; otherwise one chip's
    ``TpuMatcher``. Read off the started object, not off a key."""
    import jax
    from bifromq_tpu.kv import schema
    from bifromq_tpu.models.matcher import TpuMatcher
    (rid, coproc), = worker.store.coprocs.items()
    started = coproc.matcher
    mesh = getattr(started, "mesh", None)
    t0 = time.perf_counter()
    if mesh is None:
        matcher = TpuMatcher.from_tries(tries, device=jax.devices()[0])
    else:
        matcher = type(started).from_tries(
            tries, mesh=mesh, max_levels=started.max_levels,
            probe_len=started.probe_len, k_states=started.k_states,
            auto_compact=started.auto_compact,
            compact_threshold=started.compact_threshold,
            match_cache=started.match_cache is not None,
            replicate=set(started._replicas))
    t_build = time.perf_counter() - t0
    coproc.matcher = matcher
    coproc._wire_repl_hooks()
    t0 = time.perf_counter()
    space = worker.store.ranges[rid].space
    w = space.writer()
    value = schema.route_value(0)
    for tenant_id, trie in tries.items():
        for route in trie.routes():
            w.put(schema.route_key(tenant_id, route.matcher,
                                   route.receiver_url), value)
    w.done()
    coproc._fact_reader = space
    coproc._fact_dirty = True
    return {"from_tries_s": t_build, "kv_fill_s": time.perf_counter() - t0,
            "matcher": matcher}


def seed_retained(broker, rows) -> dict:
    """Give the started retain service the state a restarted one holds:
    every row ``(tenant, topic, payload bytes)`` a retained message in its
    range's KV space, in the program's own value encoding
    (``retain.coproc.enc_retained`` of ``schema.encode_message``), then the
    co-processor's own ``reset`` (rebuild-from-KV: values and
    ``RetainedIndex``), then the index's first build and device put
    (``refresh``). No raft proposal a topic. A row's payload is
    ``traffic.retained_payload(topic id, 0, bytes)``, its topic id its
    place in ``rows``."""
    import jax
    from bifromq_tpu.kv import schema
    from bifromq_tpu.retain.coproc import dec_retained, enc_retained
    from bifromq_tpu.types import ClientInfo, Message, QoS
    store = broker.retain_service.kvstore
    (rid, coproc), = store.coprocs.items()
    space = store.ranges[rid].space
    frames = {}             # (tenant, bytes) -> (value head, value tail)

    def frame(tenant, nbytes):
        # the encoding of two payloads of one length differs only in the
        # payload's own bytes: what is before and after them is the frame
        enc = [enc_retained(schema.encode_message(Message(
            message_id=0, pub_qos=QoS(1), payload=fill * nbytes, timestamp=0,
            is_retain=True)), ClientInfo(tenant_id=tenant), None)
            for fill in (b"\x00", b"\xff")]
        at = next(i for i, (x, y) in enumerate(zip(*enc)) if x != y)
        return enc[0][:at], enc[0][at + nbytes:]
    t0 = time.perf_counter()
    w = space.writer()
    for tid, (tenant, topic, nbytes) in enumerate(rows):
        head_tail = frames.get((tenant, nbytes))
        if head_tail is None:
            head_tail = frames[(tenant, nbytes)] = frame(tenant, nbytes)
        w.put(schema.retain_key(tenant, topic), head_tail[0]
              + traffic.retained_payload(tid, 0, nbytes) + head_tail[1])
    w.done()
    t_fill = time.perf_counter() - t0
    t0 = time.perf_counter()
    coproc.reset(space)
    t_reset = time.perf_counter() - t0
    t0 = time.perf_counter()
    index = coproc.index
    index.refresh()
    jax.block_until_ready(index._device_tables)
    t_build = time.perf_counter() - t0
    # the value a scan decodes is the one seeded: checked on the first row
    tenant, topic, nbytes = rows[0]
    _exp, pub, msg = dec_retained(coproc.values[tenant][topic])
    if pub.tenant_id != tenant or bytes(msg.payload) != \
            traffic.retained_payload(0, 0, nbytes) or not msg.is_retain:
        raise RuntimeError(f"seeded retained value of {topic!r} decodes "
                           f"as {msg!r}")
    return {"kv_fill_s": t_fill, "reset_s": t_reset, "build_put_s": t_build,
            "topics": sum(len(v) for v in coproc.values.values())}


async def warm_retained_scans(broker, filters, limit: int) -> list:
    """Each set-up filter once through the public
    ``RetainService.match_batch``, one at a time (a SUBSCRIBE scans one
    filter: batch 16): the device walk, the probe tokenizer and, for a
    ``+`` row past the walk's states, the native escalation, all built
    before the window. Returns the hits a filter."""
    from bifromq_tpu.utils import topic as topic_util
    service = broker.retain_service
    out = []
    for tenant, flt in filters:
        hits = await service.match_batch(
            [(tenant, topic_util.parse(flt))], limit)
        out.append(len(hits[0]))
    return out


def retained_device_state(broker, platform: str) -> dict:
    """Bytes of the retain ranges' device tables and where they lie."""
    import jax
    on, nbytes = set(), 0
    for coproc in broker.retain_service.kvstore.coprocs.values():
        dev = coproc.index._device_tables
        for a in jax.tree_util.tree_leaves(dev):
            on |= set(a.devices())
            nbytes += int(a.nbytes)
    return {"bytes": nbytes, "on": sorted(str(d) for d in on),
            "all_on_platform": bool(on) and all(d.platform == platform
                                                for d in on)}


def warm_patch_programs(matcher) -> int:
    """A mesh builds one scatter program a shard, a table and a donation
    mode (``parallel/sharded.py``: ``shard`` is a static argument), each on
    its first use, and warms none of them itself; the live churn would
    meet up to 16 compiles inside the window. Run each once now, as the
    flush does, on row 0 of every shard with the row's own content: the
    tables come out as they went in. Nothing to do on one chip, whose
    matcher warms its own. Only with nothing in flight (it donates)."""
    shards = _shards(matcher._base_ct)
    if shards is None:
        return 0
    import functools

    import jax
    import numpy as np
    from bifromq_tpu.ops.match import _PATCH_CHUNK, route_cols_from_node_tab
    from bifromq_tpu.parallel import sharded
    put = functools.partial(jax.device_put, device=matcher._repl_sharding)
    rows_np = np.zeros(_PATCH_CHUNK, np.int32)
    edge, child, route = matcher._device_trie
    for sh, pt in enumerate(shards):
        idx = put(rows_np)
        vals = put(route_cols_from_node_tab(pt.node_tab[rows_np]))
        sharded._shard_scatter(route, idx, vals, shard=sh)   # a copy, dropped
        route = sharded._shard_scatter_donated(route, idx, vals, shard=sh)
        vals = put(pt.edge_tab[rows_np])
        sharded._shard_scatter(edge, idx, vals, shard=sh)
        edge = sharded._shard_scatter_donated(edge, idx, vals, shard=sh)
    jax.block_until_ready((edge, route))
    matcher._device_trie = (edge, child, route)
    return 4 * len(shards)


# -------------------------------------------------------------- counters

def install_stage_sums():
    """The program's stage histograms are log2 buckets with no sum. Put a
    summing twin in each named slot, so that a mean is exact."""
    from bifromq_tpu.utils.metrics import STAGES, LatencyHistogram

    class Summing(LatencyHistogram):
        def __init__(self) -> None:
            super().__init__()
            self.sum_s = 0.0
            self.n = 0

        def record(self, seconds: float) -> None:
            self.sum_s += seconds
            self.n += 1
            super().record(seconds)
    for stage in ("ingest", "queue_wait", "device", "deliver"):
        STAGES._hists[stage] = Summing()
    return STAGES


def counters(matcher, stand_in) -> dict:
    """One reading of every program counter the layer metrics use."""
    from bifromq_tpu.obs import OBS
    from bifromq_tpu.utils.metrics import FABRIC, MATCH_CACHE, STAGES, \
        FabricMetric
    prof = OBS.profiler
    out = {"t_ns": time.monotonic_ns()}
    for stage, h in STAGES._hists.items():
        if hasattr(h, "sum_s"):
            out[f"stage.{stage}.sum_s"] = h.sum_s
            out[f"stage.{stage}.n"] = h.n
    mc = MATCH_CACHE.snapshot()
    pub = mc.get("pub", {}) if isinstance(mc, dict) else {}
    out["pubcache.hits"] = pub.get("hits", 0)
    out["pubcache.misses"] = pub.get("misses", 0)
    out["frontend.queries"] = prof.frontend_queries_total
    out["frontend.hits"] = prof.cache_hits_total
    out["batches"] = prof.batches_total
    out["queries"] = prof.queries_total
    out["padded_rows"] = prof.padded_rows_total
    out["patch.count"] = matcher.patch_count
    out["patch.fallbacks"] = matcher.patch_fallbacks
    out["patch.flushes"] = matcher.patch_flushes
    out["patch.host_s"] = matcher.patch_host_s
    out["patch.device_s"] = matcher.patch_device_s
    out["compile_count"] = matcher.compile_count
    out["match_degraded"] = FABRIC.get(FabricMetric.MATCH_DEGRADED)
    out["device_timeout"] = FABRIC.get(FabricMetric.DEVICE_TIMEOUT)
    out["warmup_failed"] = FABRIC.get(FabricMetric.WARMUP_FAILED)
    out["fleet.total"] = stand_in.total
    out["fleet.calls"] = stand_in.calls
    out["fleet.spent_s"] = stand_in.spent_s
    shards = _shards(matcher._base_ct)
    if shards is not None:       # a mesh: rows routed to each shard so far
        rows = [0] * len(shards)
        shard_of = matcher._base_ct.shard_of
        for tenant_id, n in matcher.query_heat.items():
            rows[shard_of(tenant_id)] += n
        out["mesh.rows_each"] = rows
    return out


class BatchDrain:
    """Drains the profiler's ring (2,048 records) into sums by kernel
    while the window runs, so that no batch is lost to the wrap."""

    FIELDS = ("tokenize_s", "dispatch_s", "ready_s", "fetch_s", "expand_s",
              "dev_expand_s")

    def __init__(self) -> None:
        from bifromq_tpu.obs import OBS
        self.prof = OBS.profiler
        _recs, self.cursor, _missed = self.prof.since(0)
        self.reset()

    def reset(self) -> None:
        self.kernels = {}
        self.sums = dict.fromkeys(self.FIELDS, 0.0)
        self.n = self.rows = self.padded = self.missed = 0
        self.stamps = []             # (wall ts, n_queries) per batch

    def drain(self) -> None:
        recs, self.cursor, missed = self.prof.since(self.cursor)
        self.missed += missed
        for r in recs:
            self.n += 1
            self.rows += r.n_queries
            self.padded += r.batch
            self.kernels[r.kernel] = self.kernels.get(r.kernel, 0) + 1
            for f in self.FIELDS:
                self.sums[f] += getattr(r, f)
            self.stamps.append((r.ts, r.n_queries))


MESH_TABLES = ("edge_tab", "child_list", "route_tab")


def device_state(matcher, platform: str) -> dict:
    """Where the resident tables are, and their record widths. One chip
    holds a ``DeviceTrie``; a mesh holds ``MESH_TABLES`` stacked, the
    leading axis the shard. ``resident_bytes`` is the fullest device's;
    ``each_on`` the fewest devices any one table lies on."""
    import jax
    dev = matcher._device_trie
    stacked = not hasattr(dev, "edge_tab")
    named = dict(zip(MESH_TABLES, dev)) if stacked else \
        {n: getattr(dev, n, None) for n in MESH_TABLES + ("node_tab",)}
    leaves = [a for a in jax.tree_util.tree_leaves(dev) if a is not None]
    on, per_device = set(), {}
    for a in leaves:
        on |= set(a.devices())
        for sh in a.addressable_shards:
            per_device[sh.device] = per_device.get(sh.device, 0) \
                + int(sh.data.nbytes)
    widths = {}
    for name in ("edge_tab", "child_list", "route_tab", "node_tab"):
        a = named.get(name)
        if a is not None and getattr(a, "ndim", 0) >= 1:
            row = int(a.dtype.itemsize)
            for d in a.shape[2 if stacked else 1:]:
                row *= int(d)
            widths[name] = row
    return {"resident_bytes": max(per_device.values(), default=0),
            "bytes_each": [per_device[d] for d in
                           sorted(per_device, key=lambda d: d.id)],
            "on": sorted(str(d) for d in on),
            "all_on_platform": all(d.platform == platform for d in on),
            "n_devices": len(on),
            "each_on": min((len(a.devices()) for a in leaves), default=0),
            "record_bytes": widths}


def _shards(base):
    """The per-shard host arenas of a mesh's base, else ``None``."""
    return getattr(base, "compiled", None)


def table_shapes(matcher) -> tuple:
    """Shapes of the host arenas the next flush ships: a change means the
    device tables reshape and the walk re-traces. On a mesh, the shapes
    the shards' arenas stack to (every shard padded to the largest)."""
    base = matcher._base_ct
    names = ("node_tab", "edge_tab", "child_list")
    shards = _shards(base)
    if shards is None:
        return tuple(tuple(getattr(base, n).shape) for n in names)
    return tuple((len(shards),) + tuple(
        max(dims) for dims in zip(*(getattr(pt, n).shape for pt in shards)))
        for n in names)


def table_fill(matcher) -> dict:
    base = matcher._base_ct
    keys = ("n_live", "child_used", "edge_regrows", "node_grows")
    shards = _shards(base)
    if shards is None:
        return {k: int(getattr(base, k, -1)) for k in keys}
    return {k: [int(getattr(pt, k, -1)) for pt in shards] for k in keys}


def host_rss_bytes() -> dict:
    """This process's resident set now (``VmRSS``) and at its highest
    (``VmHWM``), from ``/proc/self/status``; ``{}`` where there is none."""
    out = {}
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith(("VmRSS:", "VmHWM:")):
                    out[line[:5]] = int(line.split()[1]) * 1024
    except OSError:
        pass
    return out


def retained_scans(broker) -> list:
    """A snapshot a range of the SUBSCRIBE-side retained scan plane: scans,
    those it served from the host oracle by reason, its breaker, its
    filter-keyed result cache (hits are scans no walk served). A degraded
    scan counts under ``match_degraded`` like a degraded match; this says
    which it was. ``[]`` where the program has no such plane."""
    service = getattr(broker, "retain_service", None)
    out = []
    for coproc in getattr(getattr(service, "kvstore", None), "coprocs",
                          {}).values():
        plane = getattr(coproc, "scan_plane", None)
        if plane is not None:
            snap = plane.snapshot()
            out.append({k: snap[k] for k in ("scans_total", "degraded",
                                             "breaker", "cache") if k in snap})
    return out


def retained_walked(snapshot: list) -> int:
    """Scans the planes of a ``retained_scans`` snapshot served by a walk:
    every scan less those the filter-keyed cache answered."""
    return sum(p["scans_total"] - p.get("cache", {}).get("hits", 0)
               for p in snapshot)


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks)) if peaks else 0


# ------------------------------------------------- controls and faults

def _wrap_retained(broker, alter) -> None:
    """Alter what the retain service answers a SUBSCRIBE (the seat the
    session calls, ``RetainService.match``)."""
    service = broker.retain_service
    inner = service.match

    async def match(tenant_id, filter_levels, limit):
        return alter(await inner(tenant_id, filter_levels, limit))
    service.match = match


def _seed_version(hits):
    # every hit in the version the seed gave it (a later SET not served)
    import dataclasses
    out = []
    for topic, msg in hits:
        head = traffic.retained_header(bytes(msg.payload))
        if head is not None:
            msg = dataclasses.replace(msg, payload=traffic.retained_payload(
                head[0], 0, len(msg.payload)))
        out.append((topic, msg))
    return out


def _wrap_match(worker, alter) -> None:
    inner = worker.match_batch

    async def match_batch(queries, **kw):
        out = await inner(queries, **kw)
        for m in out:
            alter(m)
        return out
    worker.match_batch = match_batch


def _truncate64(m) -> None:
    # the device's rows hold 64 matches; a fuller row is re-expanded on
    # the host. The answer WITHOUT that step is the first 64.
    if len(m.normal) > 64:
        m.normal = m.normal[:64]


def _drop_one(m) -> None:
    if m.normal:
        m.normal = m.normal[:-1]


def _share_all(m) -> None:
    # no election: every member of a matching group is delivered to (what
    # a fan-out that treats a group slot as plain routes would do)
    for members in m.groups.values():
        m.normal = m.normal + list(members)
    m.groups = {}


def _share_none(m) -> None:
    # the group slots left out of the match: nobody of a group receives
    m.groups = {}


# name -> fn(broker): put a broken guarantee in the matcher's (or the
# retain service's) place. The benchmark's own runs never use them;
# ``--control`` and the tests do.
CONTROLS = {
    "truncate64": lambda broker: _wrap_match(broker.dist.worker, _truncate64),
    "drop_one": lambda broker: _wrap_match(broker.dist.worker, _drop_one),
    "share_all": lambda broker: _wrap_match(broker.dist.worker, _share_all),
    "share_none": lambda broker: _wrap_match(broker.dist.worker, _share_none),
    # each SUBSCRIBE's last retained message withheld
    "retained_drop_one": lambda broker: _wrap_retained(
        broker, lambda hits: hits[:-1]),
    "retained_stale": lambda broker: _wrap_retained(broker, _seed_version),
}

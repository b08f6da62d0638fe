#!/usr/bin/env python3
"""One run of one cell: a started broker, a load generator of its own,
a measured window, the comparison, one JSON line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process alone touches JAX. It starts ``starter.Standalone`` (MQTT
listener -> DistService -> DistWorker -> TpuMatcher -> deliverer), gives
the worker the configuration's subscription table, starts
``loadgen.py`` as a child (real MQTT over loopback TCP), and reads the
program's counters at the window's two ends. Set-up is everything from
process start to the first measured publish's due time.

Builder's options (not used by the driver): ``--rehearse-cpu`` skips the
look for a chip (the line then says platform "cpu" and carries no device
metric); ``--sweep r1,r2,..`` and ``--seeds s1,s2,..`` run several
windows on one set-up and print a line for each (``--trace 0`` only);
``--control <name>`` puts a broken guarantee in the matcher's place (see
``sut.CONTROLS``).

The comparison: plain routes by count and digest a publish
(``fleet_verdict``); deliveries a shared group ELECTED, which are the
program's choice, by the guarantee "exactly one member a matching group",
summed over the stand-in and the live sessions (``group_verdict``; only in
cells whose table or traffic holds a ``$share`` / ``$oshare`` group);
retained messages handed to a SUBSCRIBE, by count, set and version
(``retained_verdict``; only in cells whose configuration seeds them).
"""

from __future__ import annotations

import time

T_START_NS = time.monotonic_ns()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import bisect  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import reference  # noqa: E402
import sut  # noqa: E402  (imports the program and JAX only inside its functions)
import traffic as traffic_mod  # noqa: E402
from readers import percentile  # noqa: E402
from sut import MASK, log  # noqa: E402

OUT_DIR = os.path.join(HERE, ".out")
log.t0 = T_START_NS / 1e9
#: the device watchdog's deadline every cell runs under (see ``main``)
DEADLINE_ENV, DEADLINE_S = "BIFROMQ_DEVICE_DEADLINE_S", "5"


# ---------------------------------------------------------------- the child

class LoadGen:
    def __init__(self, proc) -> None:
        self.proc = proc

    @classmethod
    async def start(cls, port, workload, seconds, windows,
                    bench_file) -> "LoadGen":
        proc = await asyncio.create_subprocess_exec(
            sys.executable, os.path.join(HERE, "loadgen.py"),
            "--port", str(port), "--workload", workload,
            "--seconds", str(seconds), "--windows", json.dumps(windows),
            "--bench-file", bench_file, stdin=asyncio.subprocess.PIPE,
            stdout=asyncio.subprocess.PIPE, limit=1 << 30)
        return cls(proc)

    async def event(self, kind: str, timeout: float) -> dict:
        """The child's next line, which has to be of ``kind``."""
        line = await asyncio.wait_for(self.proc.stdout.readline(), timeout)
        if not line:
            rc = await self.proc.wait()
            raise RuntimeError(f"load generator ended (exit {rc})")
        ev = json.loads(line)
        if ev.get("event") != kind:
            raise RuntimeError(f"load generator said {ev.get('event')!r}, "
                               f"not {kind!r}")
        return ev

    async def say(self, word: str) -> None:
        self.proc.stdin.write(word.encode() + b"\n")
        await self.proc.stdin.drain()

    async def stop(self) -> None:
        if self.proc.returncode is None:
            try:
                await asyncio.wait_for(self.proc.wait(), 20)
            except asyncio.TimeoutError:
                self.proc.kill()
                await self.proc.wait()


class Warnings(logging.Handler):
    """The program's warnings (a degraded match names its exception only
    there), kept for the run's log: the first few, and a count."""

    def __init__(self, keep: int = 8) -> None:
        super().__init__(logging.WARNING)
        self.keep, self.n, self.first = keep, 0, []

    def emit(self, record) -> None:
        self.n += 1
        if len(self.first) < self.keep:
            self.first.append(f"{record.name}: {record.getMessage()}"[:600])


# ------------------------------------------------------------ the reference

class FleetReference:
    """What the plain reference says the fleet must be handed, worked out
    after the window from the generated rows alone."""

    def __init__(self, rows, with_prefixes: bool) -> None:
        self.table = reference.Table()
        self.prefixes = {} if with_prefixes else None
        for tenant, levels, rid, dkey in rows:
            self.table.add(tenant, levels, (rid, dkey))
            if with_prefixes:
                if reference.is_shared(levels):     # the trie holds what is
                    levels = levels[2:]             # behind the prefix
                seen = self.prefixes.setdefault(tenant, set())
                for k in range(len(levels) + 1):
                    seen.add(levels[:k])
        self.shared = self.table.n_groups > 0
        self._cache = {}
        self._groups = {}

    def groups(self, tenant: str, topic: str) -> dict:
        """Every seeded group that matches: {group filter: its members as a
        set of (receiver id, deliverer key)}."""
        key = (tenant, topic)
        hit = self._groups.get(key)
        if hit is None:
            hit = self._groups[key] = {
                flt: frozenset(members) for flt, members
                in self.table.match_groups(tenant, topic)}
        return hit

    def expect(self, tenant: str, topic: str):
        key = (tenant, topic)
        hit = self._cache.get(key)
        if hit is None:
            rows = self.table.match(tenant, topic)
            hit = (len(rows), sum(hash(r[0]) for r in rows) & MASK, rows)
            self._cache[key] = hit
        return hit

    def visited(self, tenant: str, topic: str) -> int:
        """Trie nodes a plain walk visits: the existing filter prefixes
        among the topic's generalised prefixes."""
        seen = self.prefixes.get(tenant, ())
        levels = topic.split("/")
        n, frontier = 0, [()]
        for lv in levels:
            nxt = []
            for p in frontier:
                for step in (lv, reference.PLUS):
                    q = p + (step,)
                    if q in seen:
                        nxt.append(q)
            n += len(frontier)
            frontier = nxt
        return n + len(frontier)


def live_groups(report) -> dict:
    """The load generator's half of "one member a group": for every
    (publish, group) a live session's shared subscription may have been
    elected for, ``[lo, hi, firm, who, band]``: the fewest and the most
    live deliveries that can be the group's (a client's receipts beyond
    its plain subscriptions' musts, less what its in-flight plain ones may
    explain), whether a live member certainly stood, the clients that
    certainly got one, and whether a live member was in flight around its
    SUBACK or UNSUBACK."""
    out = {}
    for client, seq, got, must, may_plain, groups in \
            report.get("shared", {}).get("receipts", ()):
        extra = max(0, got - must)
        hi = min(len(groups), extra)
        lo = min(len(groups), max(0, extra - may_plain))
        for flt, firm in groups:
            e = out.setdefault((seq, flt), [0, 0, False, [], False])
            e[0] += max(0, lo - (len(groups) - 1))
            e[1] += min(1, hi)
            e[2] = e[2] or firm
            e[4] = e[4] or not firm
            if lo == len(groups):
                e[3].append(client)
    return out


def membership_bands(report, tenants) -> dict:
    """(tenant, group filter) -> the (request, ack) bands in which a live
    session joined or left the group, in time order."""
    bands = {}
    for _c, t, flt, sub_req, suback, unsub_req, unsuback in \
            report.get("shared", {}).get("subs", ()):
        b = bands.setdefault((tenants[t], flt), [])
        b.append((sub_req, suback or 1 << 62))
        if unsub_req:
            b.append((unsub_req, unsuback or 1 << 62))
    for b in bands.values():
        b.sort()
    return bands


def group_verdict(stand_in, report, plan, ref: FleetReference) -> dict:
    """Elected deliveries against the plain reference: for every window
    publish and every group the reference (seeded members) or the load
    generator (live members) says matches, EXACTLY ONE delivery over both
    processes, the stand-in's to a member of that group under the member's
    own deliverer key; none under any other group. A publish in flight
    around a live member's SUBACK or UNSUBACK may have elected that member
    and fallen with it (the configuration's fourth guarantee: either way):
    counted, not a fault. ``$oshare``: between two membership changes of a
    group, one topic's publishes go to one member."""
    tenants, pop = plan["tenants"], plan["population"]
    pubs = report["publishes"]
    live = live_groups(report)
    live_at = {}                        # seq -> the groups live members hold
    for seq, flt in live:
        live_at.setdefault(seq, []).append(flt)
    bands = membership_bands(report, tenants)
    done_ns = report.get("shared", {}).get("done_ns")
    missing = surplus = foreign = lost_qos0 = live_elected = matched = 0
    fell_in_flight = 0
    first_bad = None
    ordered = {}      # (tenant, filter, topic, epoch) -> the members elected
    for seq, t, k, qos, _conn, _due, sent, ack in pubs:
        tenant, topic = tenants[t], pop[k]
        want = ref.groups(tenant, topic)
        got = {}
        for flt, rid, dkey in stand_in.elected.get(seq, ()):
            got.setdefault(flt, []).append((rid, dkey))
        for flt in list(want) + [f for f in live_at.get(seq, ())
                                 if f not in want]:
            members = want.get(flt, ())
            picks = got.pop(flt, ())
            lo, hi, firm, who, band = live.get((seq, flt),
                                               (0, 0, False, (), False))
            matched += bool(members or firm)
            bad = sum(1 for p in picks if p not in members)
            foreign += bad
            n = len(picks)
            live_elected += 1 if lo and not n else 0
            if n + hi < 1 and (members or firm):
                if band:
                    fell_in_flight += 1
                    continue
                if qos == 0:
                    lost_qos0 += 1      # at most once: a loss, not a fault
                    continue
                missing += 1
                bad = True
            elif n + lo > 1:
                surplus += 1
                bad = True
            if bad and first_bad is None:
                first_bad = (seq, tenant, topic, flt, list(picks), lo, hi)
            if flt.startswith("$oshare/") and n + lo == 1 and hi == lo:
                epoch = 0
                done = (done_ns[seq] if done_ns else ack) or 1 << 62
                for req, acked in bands.get((tenant, flt), ()):
                    if acked < sent:
                        epoch += 1
                    elif req <= done:
                        epoch = None    # in flight around a join or a leave
                        break
                    else:
                        break
                if epoch is not None:
                    ordered.setdefault((tenant, flt, topic, epoch),
                                       set()).add(picks[0] if n else who[0])
        for flt, picks in got.items():      # under a group that does not match
            foreign += len(picks)
            if first_bad is None:
                first_bad = (seq, tenant, topic, flt, picks, 0, 0)
    foreign += sum(len(v) for seq, v in stand_in.elected.items()
                   if seq >= len(pubs))
    split = sum(1 for who in ordered.values() if len(who) > 1)
    subs = report.get("shared", {}).get("subs", ())
    t0, t1 = report["t0_ns"], report["t1_ns"]
    return {"group_missing": missing, "group_surplus": surplus,
            "group_foreign": foreign, "oshare_split": split,
            "group_lost_qos0": lost_qos0, "group_matched": matched,
            "live_elected": live_elected, "ordered_keys": len(ordered),
            "fell_in_flight": fell_in_flight,
            "joins_in_window": sum(1 for s in subs if t0 <= s[4] < t1),
            "leaves_in_window": sum(1 for s in subs if t0 <= s[6] < t1),
            "first_bad": first_bad}


SKEW_MIN_DELIVERIES = 100


def share_skew(stand_in, ref: FleetReference) -> dict:
    """Per kind of group (``$share``, ``$oshare``), over the groups the
    stand-in was handed at least ``SKEW_MIN_DELIVERIES`` deliveries for
    inside the window: the largest member's deliveries over the group's
    mean a SEEDED member (1.0 is even), the worst group's; and how many
    groups that was. The stand-in's own record."""
    out = {}
    for (tenant, flt), load in stand_in.member_load.items():
        total = sum(load.values())
        group, rest = reference.split_filter(flt)
        size = len(ref.table.members(tenant, group, rest))
        if total < SKEW_MIN_DELIVERIES or not size:
            continue
        kind = out.setdefault(flt.split("/", 1)[0], {"max": 0.0, "groups": 0})
        kind["groups"] += 1
        kind["max"] = max(kind["max"], max(load.values()) * size / total)
    return out


RETAINED_NUMBERS = ("retained_missing", "retained_surplus", "retained_foreign",
                    "retained_stale", "retained_flag")


def retained_table(rows) -> reference.RetainedTable:
    table = reference.RetainedTable()
    for tenant, topic, _nbytes in rows:
        table.add(tenant, topic)
    return table


def retained_verdict(report, plan, table: reference.RetainedTable,
                     limit: int) -> dict:
    """Retained deliveries on the SUBSCRIBE lanes against the plain
    reference. Every PUBLISH a lane receives belongs to the lane's
    operation whose SUBSCRIBE -> UNSUBACK holds its receipt; a window
    operation (SUBSCRIBE sent at ``s`` inside the window, SUBACKed at
    ``a``) with MUST / MAY the topics its filter matches that were retained
    throughout / at some instant of ``[s, a]``:

    - ``retained_missing``: fewer than min(limit, |MUST|) distinct topics
      of MAY handed with the RETAIN bit;
    - ``retained_surplus``: more than min(limit, |MAY|) topics of MAY
      handed, or a topic twice; also a lane receipt outside every
      operation of its lane;
    - ``retained_foreign``: a delivery of a topic outside MAY (a filter
      that does not match, another tenant's topic, a topic never
      retained), or whose name is not its topic id's;
    - ``retained_stale``: a version not current at some instant of [s, a];
    - ``retained_flag``: a retained message handed without the RETAIN bit.
      A delivery without it of a SET or CLEAR sent before the UNSUBACK of
      a lane's SUBSCRIBE that matches it is that event's live forward
      ([MQTT-3.3.1-9], [MQTT-3.10.4-3]): either way, no fault, and not a
      retained delivery.

    Only window operations are judged; ``table`` holds every SET / CLEAR
    so far."""
    ret = report["retained"]
    tenants = plan["tenants"]
    lane_tenant = [t for t, _pool in plan.get("resub", {}).get("lanes", ())]
    mark = traffic_mod.RETAINED_MARK
    ops = {}                                # lane -> its operations by s
    for op in ret["ops"]:
        ops.setdefault(op[0], []).append(op)
    starts = {k: [op[1] for op in v] for k, v in ops.items()}
    out = dict.fromkeys(RETAINED_NUMBERS, 0)
    got = {}                                # id(op) -> its retained receipts
    either = 0
    first_bad = None
    for rc in ret["received"]:
        lane, topic, word, version, retain, _q, t_ns = rc
        tenant = tenants[lane_tenant[lane]]
        if word >= 0 and word & mark:
            tid = word & ~mark
        else:           # a CLEAR's empty payload names no topic id
            tid = table.tid_of.get((tenant, topic), -1)
        i = bisect.bisect_right(starts.get(lane, ()), t_ns) - 1
        op = ops[lane][i] if i >= 0 else None
        if retain:
            if op is None or (op[4] and t_ns > op[4]):
                out["retained_surplus"] += 1    # outside every SUBSCRIBE
                first_bad = first_bad or (lane, tenant, "orphan", rc)
            else:
                got.setdefault(id(op), []).append((tid, topic, version))
            continue
        if op is None or not op[6]:
            continue
        # without the RETAIN bit: the live forward of a SET or CLEAR sent
        # before an UNSUBACK of this lane whose filter matches it, or a
        # retained message that lost its flag
        if tid >= 0 and any(
                o[1] <= t_ns and tid in table.match(tenant, o[5].split("/"))
                and table.in_flight(tid, version, o[1], o[4] or reference.NEVER)
                for o in ops[lane][:i + 1]):
            either += 1
        else:
            out["retained_flag" if tid >= 0 else "retained_foreign"] += 1
            first_bad = first_bad or (lane, tenant, "no RETAIN bit", rc)
    subs = handed = must_total = 0
    for lane, lane_ops in ops.items():
        tenant = tenants[lane_tenant[lane]]
        for op in lane_ops:
            _k, s, a, _ureq, _uack, flt, window, qos = op
            if not window:
                continue
            a = a or reference.NEVER
            must, may = set(), set()
            for tid in table.match(tenant, flt.split("/")):
                m, y = table.must_may(tid, s, a)
                if m:
                    must.add(tid)
                if y:
                    may.add(tid)
            must_total += len(must)
            receipts = got.get(id(op), ())
            good, dup, bad = set(), False, []
            for tid, topic, version in receipts:
                if tid < 0 or tid >= len(table.topics) \
                        or table.topics[tid] != (tenant, topic) \
                        or tid not in may:
                    out["retained_foreign"] += 1
                    bad.append(("foreign", topic, version))
                elif tid in good:
                    dup = True
                else:
                    good.add(tid)
                    if not table.current_in(tid, version, s, a):
                        out["retained_stale"] += 1
                        bad.append(("stale", topic, version))
            handed += len(receipts)
            subs += bool(receipts)
            if len(good) < min(limit, len(must)):
                out["retained_missing"] += 1
                bad.append(("missing", qos, len(good), min(limit, len(must)),
                            [(table.topics[t][1], table.history(t))
                             for t in sorted(must - good)[:3]]))
            if dup or len(good) > min(limit, len(may)):
                out["retained_surplus"] += 1
                bad.append(("surplus", len(good), min(limit, len(may))))
            if bad and first_bad is None:
                first_bad = (lane, tenant, flt, s, a, bad[:4])
    n_ops = sum(1 for v in ops.values() for op in v if op[6])
    out.update({"retained_subs": subs, "window_subscribes": n_ops,
                "handed": handed, "live_either": either,
                "must_per_subscribe": must_total / max(1, n_ops),
                "events": len(ret["events"]), "first_bad": first_bad})
    return out


def fleet_verdict(stand_in, report, plan, ref: FleetReference) -> dict:
    tenants, pop = plan["tenants"], plan["population"]
    pubs = report["publishes"]
    mismatch = lost_qos0 = qos_wrong = set_mismatch = 0
    matched_total = 0
    first_bad = None
    for seq, t, k, qos, _conn, _due, _sent, _ack in pubs:
        want_n, want_d, _rows = ref.expect(tenants[t], pop[k])
        matched_total += want_n
        got_n = stand_in.count.get(seq, 0)
        if got_n == 0 and want_n and qos == 0:
            lost_qos0 += 1
            continue
        if got_n != want_n or (want_n and stand_in.digest.get(seq) != want_d):
            mismatch += 1
            if first_bad is None:
                first_bad = (seq, tenants[t], pop[k], got_n, want_n)
        if (want_n or seq in stand_in.elected) \
                and stand_in.qos.get(seq) != {qos}:
            qos_wrong += 1
    for seq, got in stand_in.sets.items():
        if seq >= len(pubs):
            continue
        _s, t, k = pubs[seq][:3]
        want = {}
        for rid, dkey in ref.expect(tenants[t], pop[k])[2]:
            want.setdefault((tenants[t], dkey), []).append(rid)
        if {a: sorted(b) for a, b in got.items() if b} != \
                {a: sorted(b) for a, b in want.items()}:
            if not (pubs[seq][3] == 0 and not got):
                set_mismatch += 1
    foreign = sum(1 for seq in stand_in.count if seq >= len(pubs))
    return {"fleet_mismatch": mismatch, "fleet_set_mismatch": set_mismatch,
            "fleet_qos_wrong": qos_wrong, "fleet_unknown_seq": foreign,
            "fleet_lost_qos0": lost_qos0, "sampled_sets": len(stand_in.sets),
            "matched_total": matched_total, "first_bad": first_bad}


# ------------------------------------------------------------------ one run

async def run(args, cell) -> list:
    cfg = cell["config"]
    chips = int(cell["cell"]["chips"])
    devices = sut.claim_devices(chips, rehearse_cpu=args.rehearse_cpu)
    platform = devices[0].platform
    compiles = sut.CompileCounter()
    sut.install_stage_sums()
    warnings = Warnings()
    logging.getLogger().addHandler(warnings)

    # ---- the table, from the configuration's own seed
    freeze = bool(cfg.get("runtime", {}).get("gc_freeze_after_setup"))
    if freeze:
        gc.disable()
    t0 = time.perf_counter()
    gen = traffic_mod.generator_of(cfg)
    rows = list(gen.subscriptions(cfg))
    tries, n_rows = sut.build_tries(rows)
    n_shared = sum(1 for row in rows if reference.is_shared(row[1]))
    log(f"table: {n_rows:,} subscriptions over {len(tries):,} tenant(s) "
        f"generated in {time.perf_counter() - t0:.1f}s"
        + (f"; {n_shared:,} of them members of shared groups"
           if n_shared else ""))

    # ---- the broker, through the entry point a user starts
    from bifromq_tpu.starter import Standalone
    settings_cls = sut.install_settings(cfg.get("settings", {}))
    node_cfg = json.loads(json.dumps(cfg["broker"]))
    node_cfg.setdefault("plugins", {})["settings"] = "sut:Settings"
    node = Standalone(node_cfg)
    await node.start()
    gen_proc = None
    try:
        broker = node.broker
        if not isinstance(broker.settings, settings_cls):
            raise RuntimeError("the settings seat was not taken")
        stand_in = sut.FleetStandIn()
        stand_in.shared = n_shared > 0
        broker.sub_brokers.register(stand_in)
        worker = broker.dist.worker
        seeded = sut.seed_worker(worker, tries)
        matcher = seeded["matcher"]
        log(f"worker seeded: from_tries {seeded['from_tries_s']:.1f}s "
            f"(compile_count {matcher.compile_count}), KV fill "
            f"{seeded['kv_fill_s']:.1f}s; cache {compiles.hits} hit(s) / "
            f"{compiles.misses} miss(es)")
        warmed = sut.warm_patch_programs(matcher)
        if warmed:
            log(f"mesh: {warmed} per-shard patch programs warmed; tables "
                f"{sut.table_shapes(matcher)}, fill {sut.table_fill(matcher)}")
        seat = {}
        if "retained" in cfg:
            seat = await seat_retained(cfg, gen, broker, platform, compiles)
        if freeze:
            gc.freeze()
            gc.enable()
        if args.control:
            sut.CONTROLS[args.control](broker)
            log(f"CONTROL in place: {args.control}")
        mu = broker.mem_usage
        log(f"device watchdog deadline: {DEADLINE_ENV}="
            f"{os.environ.get(DEADLINE_ENV)}")
        log(f"host rss {mu.rss_bytes() >> 20:,} MiB of budget "
            f"{mu.budget_bytes >> 20:,} MiB")

        windows = [[s, r] for r in (args.sweep or [None])
                   for s in (args.seeds or [args.seed])]
        gen_proc = await LoadGen.start(broker.port, args.workload,
                                       args.seconds, windows,
                                       args.bench_file)
        ev = await gen_proc.event("subscribed", 600)
        log(f"load generator connected: {ev['connections']} connections, "
            f"live subscribers SUBACKed; patched {matcher.patch_count}")

        # ---- settle: churn until the tables have stopped changing shape
        st = cell["traffic"].get("settle", {})
        shapes, grown = sut.table_shapes(matcher), 0
        while True:
            ev = await gen_proc.event("settled", 600)
            now = sut.table_shapes(matcher)
            changed, shapes = now != shapes, now
            grown += changed
            if ev["round"] < 0 or (ev["round"] >= int(st.get("min_rounds", 0))
                                   and not changed):
                await gen_proc.say("go")
                break
            await gen_proc.say("more")
        log(f"settled after {ev['round']} churn round(s): table shapes "
            f"{shapes} changed in {grown} of them, fill "
            f"{sut.table_fill(matcher)}; patched "
            f"{matcher.patch_count}, fallbacks {matcher.patch_fallbacks}")

        results, shared = [], {"retained": seat}
        drain = sut.BatchDrain()
        for seed, rate in windows:
            tr = dict(cell["traffic"])
            if rate is not None:
                tr["rate_per_s"] = rate
            plan = traffic_mod.build_plan(cfg, tr, seed, args.seconds)
            res = await one_window(args, cell, plan, gen_proc, stand_in,
                                   matcher, drain, compiles, rows, platform,
                                   devices, shared)
            res["seed"], res["rate"] = seed, rate
            results.append(res)
        log(f"retained scan plane (SUBSCRIBE side): "
            f"{sut.retained_scans(broker)}")
    finally:
        if gen_proc is not None:
            await gen_proc.stop()
        await node.stop()
    log(f"compile cache: {compiles.hits} hit(s), {compiles.misses} miss(es)")
    for msg in warnings.first:
        log(f"program warning (of {warnings.n}): {msg}")
    return results


async def seat_retained(cfg, gen, broker, platform, compiles) -> dict:
    """The configuration's retained messages, seeded and warmed; what the
    windows need of them."""
    t0 = time.perf_counter()
    rows = list(gen.retained(cfg))
    t_rows = time.perf_counter() - t0
    seeded = sut.seed_retained(broker, rows)
    limit = int(cfg["settings"]["RetainMessageMatchLimit"])
    t0 = time.perf_counter()
    hits = await sut.warm_retained_scans(
        broker, gen.retained_stress_filters(cfg), limit)
    state = sut.retained_device_state(broker, platform)
    log(f"retained seeded: {seeded['topics']:,} topics (rows "
        f"{t_rows:.1f}s, KV fill {seeded['kv_fill_s']:.1f}s, reset "
        f"{seeded['reset_s']:.1f}s, index build + device put "
        f"{seeded['build_put_s']:.1f}s); {state['bytes']:,} B on "
        f"{state['on']}; scans warmed in {time.perf_counter() - t0:.1f}s, "
        f"hits {hits}; cache {compiles.hits} hit(s) / {compiles.misses} "
        f"miss(es)")
    return {"broker": broker, "rows": rows, "limit": limit, "table": None}


async def one_window(args, cell, plan, gen_proc, stand_in, matcher, drain,
                     compiles, rows, platform, devices, shared) -> dict:
    import jax
    loop = asyncio.get_running_loop()
    ev = await gen_proc.event("window", 900)
    t0_ns, t1_ns = ev["t0_ns"], ev["t1_ns"]
    stand_in.arm(t0_ns, t1_ns, *plan["sample"])
    snaps = {}

    def at(t_ns, fn):
        loop.call_later(max(0.0, (t_ns - time.monotonic_ns()) / 1e9), fn)

    seat = shared["retained"]

    def open_window():
        drain.drain()
        drain.reset()
        snaps["before"] = sut.counters(matcher, stand_in)
        if seat:
            snaps["scans_before"] = sut.retained_scans(seat["broker"])

    def close_window():
        if seat:
            snaps["scans_after"] = sut.retained_scans(seat["broker"])
        drain.drain()
        snaps["batches"] = {"n": drain.n, "rows": drain.rows,
                            "padded": drain.padded, "missed": drain.missed,
                            "kernels": dict(drain.kernels),
                            "sums": dict(drain.sums)}
        snaps["after"] = sut.counters(matcher, stand_in)
    # A traced run traces the window's last seconds and takes the program
    # counters over the part BEFORE the profiler started: the profiler's
    # Python tracer slows the host by half, and that must not be read as
    # the program's own cost.
    tracing = bool(args.trace) and platform != "cpu"
    span = min(4.0, max(0.5, args.seconds - 2.0)) if tracing else 0.0
    t_trace_ns = t1_ns - int((span + 0.5) * 1e9)
    at(t0_ns, open_window)
    at(t_trace_ns if tracing else t1_ns, close_window)

    async def tick():       # past the counters' close too: the traced
        while time.monotonic_ns() < t1_ns:      # batches' stamps are wanted
            await asyncio.sleep(0.5)
            if "before" in snaps:
                drain.drain()
    ticker = asyncio.ensure_future(tick())

    trace_info = {}
    if tracing:
        trace_dir = os.path.join(OUT_DIR, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)

        async def traced():
            await asyncio.sleep(max(0.0, (t_trace_ns - time.monotonic_ns())
                                    / 1e9 + 0.05))
            await loop.run_in_executor(None, jax.profiler.start_trace,
                                       trace_dir)
            stand_in.annotate = jax.profiler.TraceAnnotation
            a, wall_a = time.monotonic(), time.time()
            await asyncio.sleep(span)
            b = time.monotonic()
            stand_in.annotate = None
            await loop.run_in_executor(None, jax.profiler.stop_trace)
            trace_info.update(dir=trace_dir, window_s=b - a, wall_a=wall_a,
                              wall_b=wall_a + (b - a))
        tracer = asyncio.ensure_future(traced())
    else:
        tracer = None

    report = await gen_proc.event("report", args.seconds + 600)
    await ticker
    if tracer is not None:
        await tracer
    drain.drain()
    after_drain = sut.counters(matcher, stand_in)
    peak = sut.memory_peak_bytes()
    state = sut.device_state(matcher, platform)

    # ---- the comparison (after the window; not part of set-up)
    t_cmp = time.perf_counter()
    if "ref" not in shared:
        shared["ref"] = FleetReference(rows, with_prefixes=bool(args.trace))
    ref = shared["ref"]
    fleet = fleet_verdict(stand_in, report, plan, ref)
    # elections: only where the table or the traffic holds a shared group
    groups = (group_verdict(stand_in, report, plan, ref)
              if ref.shared or "shared" in report else None)
    skew = share_skew(stand_in, ref) if groups is not None else None
    retained = None
    if seat:
        if seat["table"] is None:
            seat["table"] = retained_table(seat["rows"])
        table = seat["table"]
        for tid, version, sent, ack in report.get("retained", {}).get(
                "events", ()):
            table.apply(tid, version, sent, ack)
        retained = retained_verdict(report, plan, table, seat["limit"]) \
            if "retained" in report else None
        rstate = sut.retained_device_state(seat["broker"], platform)
    kernels = snaps["batches"]["kernels"]
    in_window_compiles = compiles.between(t0_ns, t1_ns)
    pubs = report["publishes"]
    checks = [  # (name, value, limit): every one an exact comparison
        ("fleet_mismatch", fleet["fleet_mismatch"], 0),
        ("fleet_set_mismatch", fleet["fleet_set_mismatch"], 0),
        ("fleet_qos_wrong", fleet["fleet_qos_wrong"], 0),
        ("fleet_unknown_seq", fleet["fleet_unknown_seq"], 0),
        ("live_missing", report["live_missing"], 0),
        ("live_unexpected", report["live_unexpected"], 0),
        ("live_surplus", report["live_surplus"], 0),
        ("order_violations", report["order_violations"], 0),
        ("qos_violations", report["qos_violations"], 0),
        ("unacked_qos1", report["unacked_qos1"], 0),
        ("loadgen_errors", report["n_errors"], 0),
        ("oracle_batches", kernels.get("oracle", 0), 0),
        ("match_degraded", after_drain["match_degraded"], 0),
        ("device_timeout", after_drain["device_timeout"], 0),
        ("warmup_failed", after_drain["warmup_failed"], 0),
        ("compiles_in_window", len(in_window_compiles), 0),
        ("full_rebuilds_in_window", snaps["after"]["compile_count"]
         - snaps["before"]["compile_count"], 0),
        ("tables_off_device", 0 if state["all_on_platform"]
         and state["n_devices"] == state["each_on"]
         == int(cell["cell"]["chips"]) else 1, 0),
    ]
    floors = [  # (name, value, at least)
        ("device_batches", snaps["batches"]["n"], 1),
        ("publishes", len(pubs), 1),
        ("live_samples", len(report["latencies_ms"]), 1),
        ("sampled_sets", fleet["sampled_sets"], 1),
    ]
    if state["n_devices"] > 1:      # a mesh: its own step served, all of it
        floors.append(("mesh_batches", kernels.get("mesh", 0),
                       max(1, snaps["batches"]["n"])))
    if groups is not None:
        checks += [(n, groups[n], 0) for n in (
            "group_missing", "group_surplus", "group_foreign",
            "oshare_split")]
        if ref.shared:              # seeded groups: elections were held
            floors.append(("group_matched", groups["group_matched"], 1))
    if seat:    # the scan planes served every scan, and walked some inside
        checks.append(("retained_degraded", sum(
            n for p in snaps["scans_after"] for n in p["degraded"].values()),
            0))
        checks.append(("retained_tables_off_device",
                       0 if rstate["all_on_platform"] else 1, 0))
        walks = sut.retained_walked(snaps["scans_after"]) \
            - sut.retained_walked(snaps["scans_before"])
        floors.append(("retained_walks", walks, 1))
        if retained is not None:
            checks += [(n, retained[n], 0) for n in RETAINED_NUMBERS]
            if "resub" in plan:     # the lanes ran
                floors.append(("retained_subs", retained["retained_subs"], 1))
    correct = all(v <= lim for _n, v, lim in checks) and \
        all(v >= lim for _n, v, lim in floors)
    compared = {n: [v, lim] for n, v, lim in checks}
    compared.update({n: [v, f">={lim}"] for n, v, lim in floors})
    cmp_s = time.perf_counter() - t_cmp

    # ---- end-to-end numbers, all from the load generator's clock
    seconds = (t1_ns - t0_ns) / 1e9
    counted_s = (snaps["after"]["t_ns"] - snaps["before"]["t_ns"]) / 1e9
    lat = report["latencies_ms"]
    route_deliveries = stand_in.in_window + report["live_in_window"]
    if "retained" in report:        # retained deliveries on the lanes
        route_deliveries += report["retained"]["in_window"]
    e2e = {
        "deliver_p50_ms": percentile(lat, 50),
        "deliver_p95_ms": percentile(lat, 95),
        "delivered_per_s": route_deliveries / seconds,
        "subscribe_p50_ms": (statistics.median(report["subscribe_ms"])
                             if report["subscribe_ms"] else None),
        "setup_s": (t0_ns - T_START_NS) / 1e9,
    }
    failed = (fleet["fleet_lost_qos0"] + report["live_lost_qos0"]
              + report["unacked_qos1"] + report["live_missing"]
              + (groups["group_lost_qos0"] if groups else 0))
    b = snaps["batches"]
    fan_out = fleet["matched_total"] + (groups["group_matched"] if groups
                                        else 0)
    log(f"window {seconds:.1f}s: {len(pubs):,} "
        f"publishes ({len(pubs) / seconds:,.1f}/s), {stand_in.in_window:,} "
        f"fleet + {report['live_in_window']:,} live route deliveries in "
        f"window, {len(lat):,} latency samples; p50 "
        f"{e2e['deliver_p50_ms']:.2f} p95 {e2e['deliver_p95_ms']:.2f} p99 "
        f"{percentile(lat, 99):.2f} max {max(lat):.2f} ms; gen_late p95 "
        f"{percentile(report['gen_late_ms'], 95):.3f} ms; churn "
        f"{report['churn_subs']} sub / {report['churn_unsubs']} unsub, "
        f"subscribe p50 {e2e['subscribe_p50_ms']} ms")
    log(f"counters over {counted_s:.1f}s: device batches {b['n']:,} by kernel {b['kernels']}, rows "
        f"{b['rows']:,} padded to {b['padded']:,} (ring missed "
        f"{b['missed']}); pub cache hits/misses "
        f"{snaps['after']['pubcache.hits'] - snaps['before']['pubcache.hits']}"
        f"/{snaps['after']['pubcache.misses'] - snaps['before']['pubcache.misses']}"
        f"; patches {snaps['after']['patch.count'] - snaps['before']['patch.count']}"
        f" in {snaps['after']['patch.flushes'] - snaps['before']['patch.flushes']}"
        f" flushes, fallbacks {snaps['after']['patch.fallbacks']}; stand-in "
        f"{stand_in.spent_s / max(1, stand_in.total) * 1e6:.3f} us/route over "
        f"{stand_in.total:,} routes; mean fan-out "
        f"{fan_out / max(1, len(pubs)):,.1f}; comparison "
        f"{cmp_s:.1f}s; failed {failed} (lost QoS 0: fleet "
        f"{fleet['fleet_lost_qos0']}, live {report['live_lost_qos0']})")
    log(f"resident tables {state['bytes_each']} B on {state['on']}, "
        f"row bytes {state['record_bytes']}; peak device memory {peak:,} B; "
        f"connections {report['connections']}")
    log(f"tables now: shapes {sut.table_shapes(matcher)}, fill "
        f"{sut.table_fill(matcher)}; host rss {sut.host_rss_bytes()}")
    if "mesh.rows_each" in snaps["after"]:
        rows = [a - b for a, b in zip(snaps["after"]["mesh.rows_each"],
                                      snaps["before"]["mesh.rows_each"])]
        log(f"mesh: rows walked a shard in the counted window {rows}; "
            f"completion {matcher.completion.snapshot()}")
    if in_window_compiles:
        log(f"compiled INSIDE the window: {in_window_compiles}")
    log(f"programs built or fetched so far: "
        f"{[(n, s) for _t, n, s in compiles.compiles]}")
    if fleet["first_bad"]:
        log(f"first fleet mismatch (seq, tenant, topic, got, want): "
            f"{fleet['first_bad']}")
    if groups is not None:
        log(f"shared groups: {groups['group_matched']:,} (publish, group) "
            f"elections held, {groups['live_elected']} of them won by a live "
            f"session; live joins / leaves of groups acked inside the window "
            f"{groups['joins_in_window']} / {groups['leaves_in_window']}; "
            f"$oshare (group, topic, epoch) keys held to one member "
            f"{groups['ordered_keys']:,}; lost QoS 0 "
            f"{groups['group_lost_qos0']}, fell with a member in flight "
            f"{groups['fell_in_flight']}; largest member over the group's "
            f"mean, groups of >= {SKEW_MIN_DELIVERIES} deliveries: {skew}")
        if groups["first_bad"]:
            log(f"first group fault (seq, tenant, topic, group, stand-in "
                f"picks, live lo, live hi): {groups['first_bad']}")
    if seat:
        log(f"retained scan planes: before {snaps['scans_before']}, after "
            f"{snaps['scans_after']}; tables {rstate['bytes']:,} B on "
            f"{rstate['on']}")
    if retained is not None:
        log(f"retained: {retained['window_subscribes']:,} window SUBSCRIBEs, "
            f"{retained['retained_subs']:,} of them handed retained "
            f"messages, {retained['handed']:,} in all ("
            f"{report['retained']['in_window']:,} inside the window); MUST "
            f"{retained['must_per_subscribe']:.1f} a SUBSCRIBE; live "
            f"forwards of a SET (either way) {retained['live_either']}; "
            f"SET / CLEAR events this window {retained['events']}; scans "
            f"walked (not the scan cache's) {walks:,}, a share of the window "
            f"SUBSCRIBEs {walks / max(1, retained['window_subscribes']):.4f}")
        if retained["first_bad"]:
            log(f"first retained fault (lane, tenant, filter, s, a, faults): "
                f"{retained['first_bad']}")
    for e in report["errors"]:
        log(f"load generator error: {e}")
    for e in report.get("notes", ()):
        log(f"load generator: {e}")

    res = {"correct": correct, "attempted": len(pubs), "failed": failed,
           "e2e": e2e, "compared": compared, "peak": peak,
           "report": report, "snaps": snaps, "state": state}

    # ---- per-layer numbers (a traced run's)
    if args.trace:
        reduced = None
        if trace_info:
            import trace_reduce
            path = trace_reduce.find_xplane(trace_info["dir"])
            if path:
                reduced = trace_reduce.reduce_trace(path,
                                                    trace_info["window_s"])
                log(f"trace {os.path.getsize(path):,} B: busy "
                    f"{reduced['busy_s']:.4f}s of {reduced['window_s']:.3f}s; "
                    f"programs {json.dumps(reduced['programs'])}")
                if args.keep_trace:
                    os.makedirs(args.keep_trace, exist_ok=True)
                    shutil.copy(path, args.keep_trace)
            shutil.rmtree(trace_info["dir"], ignore_errors=True)
        tenants, pop = plan["tenants"], plan["population"]
        uniq = {(tenants[p[1]], pop[p[2]]) for p in pubs}
        n_uniq = max(1, len(uniq))
        ref_work = {
            "visited_per_topic": sum(ref.visited(t, k) for t, k in uniq) / n_uniq,
            # a matching group is ONE result slot, whatever its members
            "matched_per_topic": sum(ref.expect(t, k)[0] + len(ref.groups(t, k))
                                     for t, k in uniq) / n_uniq,
            "traced_topics": (sum(n for ts, n in drain.stamps
                                  if trace_info["wall_a"] <= ts
                                  <= trace_info["wall_b"])
                              if trace_info else 0)}
        import roofline
        ctx = {"report": report, "before": snaps["before"],
               "after": snaps["after"], "batches": b, "trace": reduced,
               "reference": ref_work, "device": state, "seconds": counted_s,
               "share_skew": skew,
               "route_deliveries": (snaps["after"]["fleet.total"]
                                    - snaps["before"]["fleet.total"]),
               "peaks": (roofline.peaks_for(devices[0].device_kind)
                         if platform != "cpu" else None)}
        layer = {}
        for m in cell["bench"]["per_layer"]:
            if args.workload not in m.get("workloads", [args.workload]):
                continue
            spec = traffic_mod.load_json("layer_metrics", m["name"] + ".json")
            reader = importlib.import_module(f"readers.{spec['reader']}")
            value = reader.read(ctx)
            if value is not None:
                layer[m["name"]] = {"value": value, "unit": m["unit"]}
        res["layer"] = layer
        res["reduced"] = reduced
        log(f"reference work per walked topic: {ref_work}")
    return res


# --------------------------------------------------------------- the line

def result_line(args, cell, res, devices) -> dict:
    bench = cell["bench"]
    if args.trace:
        metrics = res["layer"]
    else:
        metrics = {}
        for m in bench["end_to_end"]:
            if args.workload in m.get("workloads", [args.workload]):
                v = res["e2e"].get(m["name"])
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": res["peak"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace and res.get("reduced"):
        device["busy_s"] = res["reduced"]["busy_s"]
        device["window_s"] = res["reduced"]["window_s"]
        line["breakdown"] = {"device_ops": res["reduced"]["device_ops"],
                             "idle_gaps": res["reduced"]["idle_gaps"]}
    line["compared"] = res["compared"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--sweep", type=lambda s: [float(x) for x in s.split(",")])
    ap.add_argument("--seeds", type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--control", default="")
    ap.add_argument("--keep-trace", default="")
    ap.add_argument("--bench-file", default="")
    args = ap.parse_args(argv)
    if args.trace and len(args.seeds or args.sweep or ()) > 1:
        # a traced window's comparison waits for the profiler to stop while
        # the generator is already into its next window, whose publishes
        # then land in counts not yet re-armed (my chip run, PR 38)
        ap.error("--seeds / --sweep with several windows need --trace 0")
    cell = traffic_mod.load_cell(args.workload, args.bench_file)
    if not os.path.isdir(os.path.join(os.path.dirname(HERE), "bifromq_tpu")):
        print("benchmark: the program (bifromq_tpu/) is not in this "
              "directory — nothing to measure", file=sys.stderr)
        return 3
    os.makedirs(OUT_DIR, exist_ok=True)
    # The program's device watchdog serves a batch from the host oracle once
    # it has waited 32 x the p99 of dispatch + ready, at least 0.25 s: in a
    # cell of small batches on an idle loop that is a quarter of a second,
    # which one host stall or the profiler's start and stop can spend. A
    # batch that is late for that is late, not wrong, and the chip still did
    # its work. Every cell waits the program's own cold-start deadline, 5 s,
    # so that only a device that hangs (or raises) fails the chip clause.
    os.environ[DEADLINE_ENV] = DEADLINE_S
    results = asyncio.run(run(args, cell))
    import jax
    devices = jax.devices()
    if len(results) > 1:
        for res in results:
            summarise(res)
    res = results[-1]
    line = result_line(args, cell, res, devices)
    for name, (value, limit) in line["compared"].items():
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def summarise(res) -> None:
    """Sweep / many-seed lines: what a builder reads off one set-up."""
    rep = res["report"]
    # ack lateness of the QoS 1 publishes, first quarter against last:
    # a backlog that grows through the window shows here
    lat = sorted((p[5], (p[7] - p[5]) / 1e6) for p in rep["publishes"]
                 if p[3] == 1 and p[7])
    q = max(1, len(lat) // 4)
    first = statistics.median(v for _d, v in lat[:q]) if lat else 0
    last = statistics.median(v for _d, v in lat[-q:]) if lat else 0
    bad = {k: v for k, v in res["compared"].items()
           if isinstance(v[1], int) and v[0] > v[1]}
    print(json.dumps({
        "seed": res["seed"], "rate": res["rate"], "correct": res["correct"],
        "publishes": res["attempted"], "failed": res["failed"],
        "p50_ms": res["e2e"]["deliver_p50_ms"],
        "p95_ms": res["e2e"]["deliver_p95_ms"],
        "delivered_per_s": res["e2e"]["delivered_per_s"],
        "subscribe_p50_ms": res["e2e"]["subscribe_p50_ms"],
        "ack_after_due_first_quarter_ms": first,
        "ack_after_due_last_quarter_ms": last, "over_limit": bad}), flush=True)


if __name__ == "__main__":
    sys.exit(main())

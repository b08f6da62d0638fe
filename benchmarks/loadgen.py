#!/usr/bin/env python3
"""The load generator: a process of its own, real MQTT over loopback TCP.

Never imports JAX or the program. Started by ``run.py`` with the broker's
port; speaks JSON lines on its standard output:

    {"event": "window", "t0_ns": ..., "t1_ns": ...}   before any traffic
    {"event": "report", ...}                          after the drain

All times are ``time.monotonic_ns()`` (CLOCK_MONOTONIC, shared with the
broker process on the one machine). Open-loop latency is taken from the
time a publish was DUE, not from when it was sent.

A mix with ``resub`` / ``retain_set_per_s`` (``traffic.py``) adds SUBSCRIBE
lanes and a retained SET / CLEAR stream, each on connections of their own,
and ships what they saw under the report's ``retained`` key; ``run.py``
holds it to the plain reference (``reference.RetainedTable``).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import struct
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import mqttlite  # noqa: E402
import reference  # noqa: E402
import traffic as traffic_mod  # noqa: E402

WARM_FLAG = 1 << 62          # seq of a publish that is not the window's
HEADER = struct.Struct(">Qq")  # seq, due_ns
DRAIN_S = 60.0
SUBACK_S = 10.0              # a lane's SUBACK / UNSUBACK later than this: an error


def emit(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj, separators=(",", ":")) + "\n")
    sys.stdout.flush()


def now_ns() -> int:
    return time.monotonic_ns()


class Run:
    def __init__(self, port: int, plan: dict) -> None:
        self.port = port
        self.plan = plan
        self.tenants = plan["tenants"]
        self.topics = [t.encode() for t in plan["population"]]
        self.stress = [(t, len(self.topics) + i) for i, (t, _topic)
                       in enumerate(plan["stress"])]
        self.topics += [topic.encode() for _t, topic in plan["stress"]]
        self.pad = b"x" * max(0, plan["payload_bytes"] - HEADER.size)
        self.sub_clients = []
        self.pools = {}              # tenant index -> [Client]
        self.conn_index = {}         # Client -> publisher connection number
        self.received = []           # (sub client idx, seq, qos, t_ns)
        self.pubs = []               # window publishes, by seq
        self.subscriptions = []      # dicts, setup + churn
        self.churn_active = []       # FIFO of churn subscription dicts
        self.warm_seq = WARM_FLAG
        self.t0 = self.t1 = 0
        self.errors = []
        self.notes = []              # the first missing deliveries, for the log
        # retained on subscribe (only where the plan has lanes or SETs)
        self.lanes = []              # RetainClient a SUBSCRIBE lane
        self.resub_ops = []          # [lane, s, suback, unsub_req, unsuback, filter, window, qos]
        self.resub_received = []     # [lane, topic, word, version, retain, qos, t_ns]
        self.retain_pubs = {}        # tenant index -> RetainClient
        self.retain_events = []      # [topic id, version (-1: CLEAR), sent, ack]
        self.retain_version = {}     # topic id -> last version sent
        self.retain_pending = {}     # topic id -> its last event's task

    # ---------------- connections -------------------------------------
    def _on_publish(self, client, topic, payload, qos, t_ns) -> None:
        if len(payload) >= HEADER.size:
            seq, _due = HEADER.unpack_from(payload)
            if seq < WARM_FLAG:
                self.received.append((client.index, seq, qos, t_ns))

    async def _gather_limited(self, coros, limit: int = 128):
        sem = asyncio.Semaphore(limit)

        async def one(c):
            async with sem:
                return await c
        return await asyncio.gather(*(one(c) for c in coros))

    async def connect(self) -> None:
        async def sub_client(i, t):
            c = await mqttlite.Client.open(
                self.port, f"sub{i}", f"{self.tenants[t]}/sub{i}",
                self._on_publish)
            c.index = i
            return c

        async def pub_client(t, j):
            return await mqttlite.Client.open(
                self.port, f"pub{t}x{j}", f"{self.tenants[t]}/pub{t}x{j}")
        self.sub_clients = await self._gather_limited(
            sub_client(i, t) for i, (t, _f, _q) in enumerate(self.plan["subs"]))
        jobs = [(t, j) for t, n in enumerate(self.plan["pools"])
                for j in range(n)]
        conns = await self._gather_limited(pub_client(t, j) for t, j in jobs)
        for k, ((t, _j), c) in enumerate(zip(jobs, conns)):
            self.pools.setdefault(t, []).append(c)
            self.conn_index[c] = k
        if "resub" in self.plan or "retain_sets" in self.plan:
            await self._connect_retained()

    async def _connect_retained(self) -> None:
        async def lane(k, t):
            c = await mqttlite.RetainClient.open(
                self.port, f"resub{k}", f"{self.tenants[t]}/resub{k}",
                self._on_lane_publish)
            c.index = k
            return c
        lanes = self.plan.get("resub", {}).get("lanes", ())
        self.lanes = await self._gather_limited(
            lane(k, t) for k, (t, _pool) in enumerate(lanes))
        for t in sorted({e[2] for e in self.plan.get("retain_sets", ())}):
            self.retain_pubs[t] = await mqttlite.RetainClient.open(
                self.port, f"rset{t}", f"{self.tenants[t]}/rset{t}")

    def _on_lane_publish(self, client, topic, payload, qos, t_ns,
                         retain) -> None:
        word = version = -1
        if len(payload) >= HEADER.size:
            word, version = HEADER.unpack_from(payload)
        self.resub_received.append([client.index, topic.decode(), word,
                                    version, retain, qos, t_ns])

    async def _subscribe(self, idx: int, flt: str, qos: int) -> dict:
        t, _f, _q = self.plan["subs"][idx]
        group, levels = reference.split_filter(flt)
        rec = {"client": idx, "tenant": t, "filter": flt, "levels": levels,
               "group": group, "qos": qos, "sub_req": now_ns(),
               "suback": 0, "unsub_req": 0, "unsuback": 0}
        rc = await self.sub_clients[idx].subscribe(flt, qos)
        rec["suback"] = now_ns()
        if rc != qos:
            self.errors.append(f"SUBACK {rc} for {flt!r} (asked {qos})")
        self.subscriptions.append(rec)
        return rec

    async def subscribe_live(self) -> None:
        await self._gather_limited(
            self._subscribe(i, f, q)
            for i, (_t, f, q) in enumerate(self.plan["subs"]))
        if self.lanes:
            await self._gather_limited(
                self._lane_touch(k, pool) for k, (_t, pool)
                in enumerate(self.plan["resub"]["lanes"]))

    async def _lane_touch(self, k: int, pool: list) -> None:
        """Every filter of the lane once before the window, as a member of
        a shared group: the route's trie nodes then exist, so a SUBSCRIBE
        in the window grows no table (and its slot, once made, is revived
        on the lane's later visits), while the retain service, which hands
        a shared subscription nothing [MQTT-4.8.2], caches no scan of it."""
        for flt in dict.fromkeys(pool):
            if not await self._lane_op(k, f"$share/warm/{flt}", 0, False):
                return

    async def _lane_op(self, k: int, flt: str, qos: int, window: bool) -> bool:
        """SUBSCRIBE, SUBACK, UNSUBSCRIBE, UNSUBACK on lane ``k``."""
        c = self.lanes[k]
        rec = [k, now_ns(), 0, 0, 0, flt, window, qos]
        self.resub_ops.append(rec)
        try:
            rc = await c.subscribe(flt, qos, timeout=SUBACK_S)
            rec[2] = now_ns()
            if rc != qos:
                self.errors.append(f"lane {k}: SUBACK {rc} for {flt!r} "
                                   f"(asked {qos})")
            rec[3] = now_ns()
            await c.unsubscribe(flt, timeout=SUBACK_S)
            rec[4] = now_ns()
        except (asyncio.TimeoutError, ConnectionError) as e:
            self.errors.append(f"lane {k} {flt!r}: {e!r}")
            return False
        return True

    async def resub_lanes(self) -> None:
        """Each lane walks its filters round until the window closes."""
        rs = self.plan["resub"]
        qos = rs["qos"]

        async def lane(k: int, pool: list) -> None:
            i = 0
            while now_ns() < self.t1:
                flt = pool[i % len(pool)]
                ok = await self._lane_op(k, flt, qos[(i + k) % len(qos)],
                                         now_ns() >= self.t0)
                if not ok:
                    return
                i += 1
        await asyncio.gather(*(lane(k, pool) for k, (_t, pool)
                               in enumerate(rs["lanes"])))

    # ---------------- retained SET / CLEAR ----------------------------
    def _retain_event(self, tid: int, tenant: int, topic: str, nbytes: int,
                      kind: str):
        """One SET or CLEAR, sent once the topic's previous one is acked
        (so a topic's versions take effect in the order they are sent)."""
        task = asyncio.ensure_future(self._retain_send(
            tid, tenant, topic, nbytes, kind, self.retain_pending.get(tid)))
        self.retain_pending[tid] = task
        return task

    async def _retain_send(self, tid, tenant, topic, nbytes, kind,
                           prev) -> None:
        if prev is not None:
            await prev
        if kind == "set":
            version = self.retain_version.get(tid, 0) + 1
            self.retain_version[tid] = version
            payload = traffic_mod.retained_payload(tid, version, nbytes)
        else:
            version, payload = -1, b""
        rec = [tid, version, now_ns(), 0]
        self.retain_events.append(rec)
        fut = self.retain_pubs[tenant].publish_retained(topic.encode(),
                                                        payload)
        try:
            rec[3] = await asyncio.wait_for(fut, 120)
        except (asyncio.TimeoutError, ConnectionError) as e:
            self.errors.append(f"retained {kind} {topic}: {e!r}")

    async def retain_warm(self) -> None:
        for at, tid, t, topic, nbytes, kind in self.plan["retain_sets"]:
            if at < 0:
                await self._retain_event(tid, t, topic, nbytes, kind)

    async def retain_stream(self) -> None:
        for at, tid, t, topic, nbytes, kind in self.plan["retain_sets"]:
            if at < 0:
                continue
            await self._sleep_until(self.t0 + int(at * 1e9))
            if now_ns() >= self.t1:
                return
            self._retain_event(tid, t, topic, nbytes, kind)

    # ---------------- publishing --------------------------------------
    def _conn(self, tenant: int):
        pool = self.pools[tenant]
        best = pool[0]
        for c in pool:
            if c.inflight < best.inflight:
                best = c
        return best

    def _send(self, tenant: int, topic: int, qos: int, due_ns: int,
              window: bool):
        """One PUBLISH now. Returns (record or None, PUBACK future or None)."""
        conn = self._conn(tenant)
        if window:
            seq = len(self.pubs)
        else:
            seq = self.warm_seq
            self.warm_seq += 1
        sent = now_ns()
        fut = conn.publish(self.topics[topic],
                           HEADER.pack(seq, due_ns) + self.pad, qos)
        rec = None
        if window:
            rec = [seq, tenant, topic, qos, self.conn_index[conn], due_ns,
                   sent, 0]
            self.pubs.append(rec)
            if fut is not None:
                fut.add_done_callback(lambda f, r=rec: self._acked(f, r))
        elif fut is not None:
            fut.add_done_callback(lambda f: f.exception())
        return rec, fut

    def _acked(self, fut, rec) -> None:
        if fut.cancelled() or fut.exception() is not None:
            return
        rec[7] = fut.result()

    def _qos(self, i: int) -> int:
        cyc = self.plan["qos_cycle"]
        return cyc[(i + self.plan["qos_phase"]) % len(cyc)]

    async def bursts(self) -> None:
        for t, k in self.stress:          # one at a time: rare paths first
            await asyncio.wait_for(self._send(t, k, 1, now_ns(), False)[1],
                                   600)
        for burst in self.plan["bursts"]:
            futs = [self._send(t, k, 1, now_ns(), False)[1] for t, k in burst]
            await asyncio.wait_for(asyncio.gather(*futs), 600)

    async def _sleep_until(self, t_ns: int) -> None:
        d = (t_ns - now_ns()) / 1e9
        if d > 0:
            await asyncio.sleep(d)

    async def open_loop(self) -> None:
        warm = self.plan["warm"]
        w_ns = int(self.plan["warmup_seconds"] * 1e9)
        start = self.t0 - w_ns - 500_000_000
        for i, (t, k) in enumerate(warm):
            due = start + i * w_ns // len(warm)
            await self._sleep_until(due)
            self._send(t, k, self._qos(i), due, False)
        arrivals = self.plan["arrivals"]
        i, n = 0, len(arrivals)
        while i < n:
            due = self.t0 + int(arrivals[i][0] * 1e9)
            await self._sleep_until(due)
            now = now_ns()
            while i < n:                   # everything that is due by now
                due = self.t0 + int(arrivals[i][0] * 1e9)
                if due > now:
                    break
                self._send(arrivals[i][1], arrivals[i][2], self._qos(i),
                           due, True)
                i += 1

    async def closed_loop(self) -> None:
        cycle = self.plan["cycle"]
        lanes = self.plan["publishers"]

        async def lane(k: int) -> None:
            i = k
            while True:
                now = now_ns()
                if now >= self.t1:
                    return
                t, topic = cycle[i % len(cycle)]
                i += lanes
                _rec, fut = self._send(t, topic, 1, now, now >= self.t0)
                try:
                    await asyncio.wait_for(fut, 120)
                except (asyncio.TimeoutError, ConnectionError) as e:
                    self.errors.append(f"lane {k}: {e!r}")
                    return
        await asyncio.gather(*(lane(k) for k in range(lanes)))

    # ---------------- churn -------------------------------------------
    async def churn(self) -> None:
        for at, kind, idx, flt in self.plan["churn"]:
            if at < 0:
                continue
            await self._sleep_until(self.t0 + int(at * 1e9))
            if now_ns() >= self.t1:
                return
            try:
                if kind == "sub":
                    rec = await self._subscribe(idx, flt, len(
                        self.subscriptions) % 2)
                    rec["churn"] = True
                    self.churn_active.append(rec)
                elif self.churn_active:
                    rec = self.churn_active.pop(0)
                    rec["unsub_req"] = now_ns()
                    await self.sub_clients[rec["client"]].unsubscribe(
                        rec["filter"])
                    rec["unsuback"] = now_ns()
            except (asyncio.TimeoutError, ConnectionError) as e:
                self.errors.append(f"churn {kind}: {e!r}")

    async def settle(self) -> None:
        """Rounds of SUBSCRIBE + UNSUBSCRIBE of fresh filters, for as long
        as ``run.py`` (which sees the tables' shapes) answers "more"."""
        st = self.plan["settle"]
        n, filters = int(st["round"]), self.plan["settle_filters"]
        n_gen = len(self.plan["subs"]) - self.plan["n_taps"]
        loop = asyncio.get_running_loop()
        for r in range(int(st["max_rounds"]) if n and n_gen else 0):
            batch = filters[r * n:(r + 1) * n]

            async def one(k, flt):
                idx = self.plan["n_taps"] + k % n_gen
                if flt == self.plan["subs"][idx][1]:
                    return      # not fresh: the client's own filter, which
                c = self.sub_clients[idx]    # the UNSUBSCRIBE would end
                await c.subscribe(flt, 0)
                await c.unsubscribe(flt)
            await self._gather_limited(one(k, f) for k, f in enumerate(batch))
            emit({"event": "settled", "round": r + 1})
            answer = await loop.run_in_executor(None, sys.stdin.readline)
            if answer.strip() != "more":
                return
        emit({"event": "settled", "round": -1})
        await loop.run_in_executor(None, sys.stdin.readline)

    async def pre_churn(self) -> None:
        for at, kind, idx, flt in self.plan["churn"]:
            if at < 0 and kind == "sub":
                rec = await self._subscribe(idx, flt, 1)
                self.churn_active.append(rec)

    # ---------------- the comparison on the live side -----------------
    def _done_times(self) -> None:
        """A QoS 0 publish is done when a later QoS 1 publish of its
        connection is acked (a session serves its publishes in order)."""
        last = {}
        for rec in reversed(self.pubs):
            conn = rec[4]
            if rec[3] == 1 and rec[7]:
                last[conn] = rec[7]
            rec.append(rec[7] if rec[3] == 1 else last.get(conn, 0))

    def lifetimes(self) -> list:
        """One record a LIFETIME of a (client, filter) subscription. A
        SUBSCRIBE of a filter the client already holds replaces that
        subscription ([MQTT-3.8.4-3]: it goes on, perhaps at another QoS)
        and the first UNSUBSCRIBE ends it, whichever record asked: the
        churn may draw a client's own filter. ``grants`` holds every
        (request, SUBACK, QoS) of the lifetime."""
        events = []
        for s in self.subscriptions:
            events.append((s["sub_req"], 0, s))
            if s["unsub_req"]:
                events.append((s["unsub_req"], 1, s))
        events.sort(key=lambda e: e[:2])
        held, out = {}, []
        for _t, kind, s in events:
            key = (s["client"], s["filter"])
            cur = held.get(key)
            if kind == 0 and cur is None:
                cur = held[key] = dict(s, unsub_req=0, unsuback=0, grants=[])
                out.append(cur)
            if kind == 0:
                cur["grants"].append((s["sub_req"], s["suback"], s["qos"]))
            elif cur is not None:
                cur["unsub_req"], cur["unsuback"] = (s["unsub_req"],
                                                     s["unsuback"])
                del held[key]
        return out

    @staticmethod
    def _granted(grants: list, qos: int, sent: int, done: int) -> set:
        """The QoS a delivery of this publish may come at: the grant that
        stood, or either one around a replacing SUBSCRIBE."""
        if len(grants) == 1:
            return {min(qos, grants[0][2])}
        out = set()
        for i, (req, _ack, q) in enumerate(grants):
            nxt = grants[i + 1] if i + 1 < len(grants) else None
            if (done and done < req) or (nxt and nxt[1] and sent > nxt[1]):
                continue
            out.add(min(qos, q))
        return out

    def expectations(self, fence_ns: dict):
        """Per (client, seq): [must, may, QoS set, groups]: counts of
        matching live subscriptions by the guarantees of the configuration.
        A SHARED subscription (``$share`` / ``$oshare``) is never a must:
        its group elects one member a publish, so it may bring 0 or 1
        (counted under ``may``; never after its UNSUBACK was followed by
        the send, never before its request) and is listed under ``groups``
        as (group filter, firm), firm where it certainly stood."""
        by_tenant = {}
        for s in self.lifetimes():
            by_tenant.setdefault(s["tenant"], []).append(s)
        levels_of = [t.decode().split("/") for t in self.topics]
        expect = {}
        for rec in self.pubs:
            seq, tenant, topic, qos, conn, _due, sent, _ack, done = rec
            done = rec[8] = done or fence_ns.get(conn, 0)
            for s in by_tenant.get(tenant, ()):
                if not reference.filter_matches(s["levels"], levels_of[topic]):
                    continue
                if sent > s["suback"] and (not s["unsub_req"] or (
                        done and done < s["unsub_req"])):
                    kind = 0          # must
                elif (done and done < s["sub_req"]) or (
                        s["unsuback"] and sent > s["unsuback"]):
                    continue          # must not
                else:
                    kind = 1          # in flight around the (un)subscribe
                e = expect.setdefault((s["client"], seq), [0, 0, set(), []])
                if s["group"] is None:
                    e[kind] += 1
                else:
                    e[1] += 1
                    e[3].append([s["filter"], kind == 0])
                e[2] |= self._granted(s["grants"], qos, sent, done)
        return expect

    async def finish(self) -> dict:
        # every window publish acked (QoS 1) and a fence behind each
        # connection, then wait for what the reference says is still due
        t_end = time.monotonic() + DRAIN_S
        pending = [c for pool in self.pools.values() for c in pool]
        while any(c.inflight for c in pending) and time.monotonic() < t_end:
            await asyncio.sleep(0.02)
        used = {rec[4] for rec in self.pubs}
        conn_of = {k: c for c, k in self.conn_index.items()}
        fence_ns = {}

        async def fence(k):
            fut = conn_of[k].publish(b"bench/fence", HEADER.pack(
                self.warm_seq, 0) + self.pad, 1)
            try:
                fence_ns[k] = await asyncio.wait_for(
                    fut, max(1.0, t_end - time.monotonic()))
            except (asyncio.TimeoutError, ConnectionError) as e:
                self.errors.append(f"fence {k}: {e!r}")
        await self._gather_limited(fence(k) for k in sorted(used))
        if self.retain_pending:
            await asyncio.wait(list(self.retain_pending.values()),
                               timeout=max(1.0, t_end - time.monotonic()))
        self._done_times()
        expect = self.expectations(fence_ns)
        must_total = sum(1 for e in expect.values() if e[0])
        while time.monotonic() < t_end:
            got = {(c, s) for c, s, _q, _t in self.received}
            if sum(1 for key, e in expect.items()
                   if e[0] and key in got) >= must_total:
                break
            await asyncio.sleep(0.05)
        await asyncio.sleep(0.3)        # room for what should NOT come
        drain_end = now_ns()
        return self.verdict(expect, drain_end)

    def _describe_missing(self, key) -> str:
        client, seq = key
        rec = self.pubs[seq]
        subs = [(s["filter"], s["sub_req"] - self.t0, s["suback"] - self.t0,
                 s["unsub_req"] and s["unsub_req"] - self.t0,
                 s["unsuback"] and s["unsuback"] - self.t0)
                for s in self.subscriptions if s["client"] == client]
        return (f"missing: client {client} seq {seq} topic "
                f"{self.topics[rec[2]].decode()} sent {rec[6] - self.t0} "
                f"done {rec[8] - self.t0}; its subscriptions (filter, "
                f"sub_req, suback, unsub_req, unsuback; ns from t0): {subs}")

    def verdict(self, expect: dict, drain_end: int) -> dict:
        pubs = self.pubs
        # Latency samples are the deliveries to the TAPS: one "#" subscriber
        # per tenant, so every publish is sampled exactly once whatever the
        # seed. The generated and the churn subscribers' deliveries are
        # compared like the rest but give no sample: their filters match
        # the heavy topics more often, and the churn ones change with the
        # seed, which moved the median by a quarter between seeds.
        n_taps = self.plan["n_taps"]
        counts, lat = {}, []
        order_viol = qos_viol = 0
        last_seq = {}
        in_window = 0
        for client, seq, qos, t_ns in self.received:
            if seq >= len(pubs):
                continue
            key = (client, seq)
            counts[key] = counts.get(key, 0) + 1
            rec = pubs[seq]
            if client < n_taps:
                lat.append((t_ns - rec[5]) / 1e6)
            if self.t0 <= t_ns < self.t1:
                in_window += 1
            e = expect.get(key)
            if e is not None and qos not in e[2]:
                qos_viol += 1
            okey = (client, rec[4], rec[2], qos)   # MQTT order: per topic,
            if last_seq.get(okey, -1) > seq:       # publisher and QoS
                order_viol += 1
            last_seq[okey] = max(seq, last_seq.get(okey, -1))
        missing = unexpected = surplus = 0
        lost_qos0 = 0
        receipts = []         # what the shared subscriptions may have got
        for key, (must, may, _q, groups) in expect.items():
            got = counts.get(key, 0)
            if groups:
                receipts.append([key[0], key[1], got, must,
                                 may - len(groups), groups])
            if must and not got:
                if pubs[key[1]][3] == 0:
                    lost_qos0 += 1        # at most once: a loss, not a fault
                else:
                    missing += 1
                    if len(self.notes) < 8:
                        self.notes.append(self._describe_missing(key))
                if key[0] < n_taps:
                    lat.append((drain_end - pubs[key[1]][5]) / 1e6)
            if got > must + may:
                surplus += 1
        for key in counts:
            if key not in expect:
                unexpected += 1
        unacked = sum(1 for r in pubs if r[3] == 1 and not r[7])
        late = [(r[6] - r[5]) / 1e6 for r in pubs]
        sub_ms = [(s["suback"] - s["sub_req"]) / 1e6
                  for s in self.subscriptions
                  if s.get("churn") and s["suback"]]
        report = {
            "event": "report", "t0_ns": self.t0, "t1_ns": self.t1,
            "publishes": [r[:8] for r in pubs],
            "latencies_ms": lat, "gen_late_ms": late,
            "subscribe_ms": sub_ms, "live_in_window": in_window,
            "live_received": len(self.received),
            "live_expected_must": sum(1 for e in expect.values() if e[0]),
            "live_missing": missing, "live_unexpected": unexpected,
            "live_surplus": surplus, "live_lost_qos0": lost_qos0,
            "order_violations": order_viol, "qos_violations": qos_viol,
            "unacked_qos1": unacked, "errors": self.errors[:20],
            "notes": self.notes,
            "n_errors": len(self.errors),
            "connections": (len(self.sub_clients) + len(self.conn_index)
                            + len(self.lanes) + len(self.retain_pubs)),
            "churn_subs": len(sub_ms),
            "churn_unsubs": sum(1 for s in self.subscriptions
                                if s["unsuback"]),
        }
        if self.lanes or self.retain_pubs:
            report["retained"] = self._retained_report()
        shared = [s for s in self.lifetimes() if s["group"] is not None]
        if shared:            # the live half of "one member a group": run.py
            report["shared"] = {    # closes the sum with the stand-in's half
                "subs": [[s["client"], s["tenant"], s["filter"], s["sub_req"],
                          s["suback"], s["unsub_req"], s["unsuback"]]
                         for s in shared],
                "receipts": receipts,
                "done_ns": [r[8] for r in pubs]}
        return report

    def _retained_report(self) -> dict:
        """What the lanes and the SET stream saw, for ``run.py``: every
        lane operation, every PUBLISH a lane received, every SET / CLEAR
        with its send and PUBACK instants; ``in_window``: the retained
        deliveries (RETAIN bit) received on a lane inside the window."""
        return {"ops": self.resub_ops, "received": self.resub_received,
                "events": self.retain_events,
                "in_window": sum(1 for r in self.resub_received
                                 if r[4] and self.t0 <= r[6] < self.t1)}

    # ---------------- one window --------------------------------------
    async def window(self) -> dict:
        plan = self.plan
        await self.pre_churn()
        if "retain_sets" in plan:
            await self.retain_warm()
        await self.bursts()
        lead = plan["warmup_seconds"] + (0.5 if plan["loop"] == "open" else 0)
        self.t0 = now_ns() + int((lead + 0.2) * 1e9)
        self.t1 = self.t0 + int(plan["seconds"] * 1e9)
        emit({"event": "window", "t0_ns": self.t0, "t1_ns": self.t1})
        publishing = self.open_loop() if plan["loop"] == "open" \
            else self.closed_loop()
        jobs = [publishing, self.churn()]
        if self.lanes:
            jobs.append(self.resub_lanes())
        if "retain_sets" in plan:
            jobs.append(self.retain_stream())
        await asyncio.gather(*jobs)
        await self._sleep_until(self.t1)
        return await self.finish()

    def reset(self, plan: dict) -> None:
        """A further window on the same connections (sweep, many seeds)."""
        self.plan = plan
        self.received, self.pubs, self.notes = [], [], []
        self.resub_ops, self.resub_received, self.retain_events = [], [], []
        self.subscriptions = [s for s in self.subscriptions
                              if not s["unsuback"]]

    def close(self) -> None:
        for c in self.sub_clients:
            c.close()
        for pool in self.pools.values():
            for c in pool:
                c.close()
        for c in self.lanes + list(self.retain_pubs.values()):
            c.close()


async def amain(args) -> None:
    cell = traffic_mod.load_cell(args.workload, args.bench_file)
    windows = json.loads(args.windows)     # [[seed, rate or null], ...]

    def plan_for(seed, rate):
        tr = dict(cell["traffic"])
        if rate is not None:
            tr["rate_per_s"] = rate
        return traffic_mod.build_plan(cell["config"], tr, seed, args.seconds)
    run = Run(args.port, plan_for(*windows[0]))
    try:
        await run.connect()
        await run.subscribe_live()
        emit({"event": "subscribed",
              "connections": len(run.sub_clients) + len(run.conn_index)})
        await run.settle()
        for i, (seed, rate) in enumerate(windows):
            if i:
                run.reset(plan_for(seed, rate))
            report = await run.window()
            report["seed"], report["rate"] = seed, rate
            emit(report)
    finally:
        run.close()
        await asyncio.sleep(0.2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--windows", required=True)
    ap.add_argument("--bench-file", default="")
    asyncio.run(amain(ap.parse_args()))


if __name__ == "__main__":
    main()

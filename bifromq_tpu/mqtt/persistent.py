"""Persistent MQTT session handler (≈ MQTTPersistentSessionHandler).

Reference behavior (bifromq-mqtt .../MQTTPersistentSessionHandler.java):
subscriptions and undelivered messages live in the inbox store (sub-broker
id 1); while the session is online an inbox fetch loop (reference
inboxReader.fetch, :387) drains the qos0 + send-buffer queues into the
connection; PUBACK/PUBCOMP commit the send-buffer (consume():518, commit
scheduler); on disconnect the inbox detaches and expires on its own clock.
"""

from __future__ import annotations

import asyncio
import time
from typing import Dict, Optional, Set

from .. import trace
from ..inbox.service import InboxService
from ..obs.e2e import DELIVERY_PATH
from ..inbox.store import LWT
from ..plugin.events import Event, EventType
from ..types import Message, QoS, TopicFilterOption
from ..utils.hlc import HLC
from . import packets as pk
from .protocol import PROTOCOL_MQTT5, ReasonCode
from .session import BLOCKED, Session, Subscription


class PersistentSession(Session):
    def __init__(self, *, inbox: InboxService, expiry_seconds: int,
                 **kwargs) -> None:
        super().__init__(**kwargs)
        self.inbox = inbox
        self.expiry_seconds = expiry_seconds
        self.inbox_id = self.client_id
        self.session_present = False
        self._fetch_wake = asyncio.Event()
        self._fetch_task: Optional[asyncio.Task] = None
        self._qos0_cursor: Optional[int] = None
        self._buf_cursor: Optional[int] = None
        # outbound packet id -> send-buffer seq (for commit on ack)
        self._pid_to_seq: Dict[int, int] = {}
        self._acked_seqs: Set[int] = set()
        self._commit_tasks: Set[asyncio.Task] = set()
        self._committed_seq = -1

    # ---------------- lifecycle -------------------------------------------

    async def start(self) -> None:
        tenant = self.client_info.tenant_id
        lwt = None
        if self.will is not None:
            from .session import will_delay_seconds, will_to_message
            lwt = LWT(topic=self.will.topic,
                      delay_seconds=will_delay_seconds(
                          self.will, self.protocol_level),
                      message=will_to_message(self.will,
                                              self.protocol_level))
        try:
            meta, present = await self.inbox.attach(
                tenant, self.inbox_id, clean_start=self.clean_start,
                expiry_seconds=self.expiry_seconds,
                client_meta=self.client_info.metadata, lwt=lwt)
        except Exception as e:  # noqa: BLE001 — inbox store unavailable
            # ≈ InboxTransientError close event: the persistent session
            # cannot come up without its inbox; drop the connection and
            # unwind via the quiet sentinel (the outage is already
            # event-reported — no "connection crashed" stack spam)
            from .session import SessionStartAborted
            self.events.report(Event(
                EventType.INBOX_TRANSIENT_ERROR, tenant,
                {"client_id": self.client_id}))
            self.closed = True
            await self.conn.close_transport()
            raise SessionStartAborted(str(e)) from e
        self.session_present = present
        if present:
            # restore subscription state (routes already exist in dist)
            for tf, opt in meta.filters.items():
                from ..types import RouteMatcher
                self.subscriptions[tf] = Subscription(
                    matcher=RouteMatcher.from_topic_filter(tf),
                    qos=int(opt.qos), no_local=opt.no_local,
                    retain_as_published=opt.retain_as_published,
                    retain_handling=opt.retain_handling, sub_id=opt.sub_id)
        self._committed_seq = meta.buffer_start_seq - 1
        self.local_registry.register(self)
        await self.session_registry.register(self)
        await self._global_kick()
        self.inbox.register_fetcher(tenant, self.inbox_id,
                                    self._fetch_wake.set)
        self._fetch_task = asyncio.get_running_loop().create_task(
            self._fetch_loop())
        self._fetch_wake.set()  # drain messages accumulated while offline

    async def close(self, fire_will: bool) -> None:
        if self.closed:
            return
        self.closed = True
        tenant = self.client_info.tenant_id
        if self._fetch_task is not None:
            self._fetch_task.cancel()
        self.inbox.unregister_fetcher(tenant, self.inbox_id)
        self.session_registry.unregister(self)
        self.local_registry.unregister(self)
        if self._kicked_replaced:
            # the new owner took over the inbox; nothing to detach
            pass
        elif fire_will and self.will is not None \
                and not self._will_suppressed:
            from .session import will_delay_seconds
            delay = min(will_delay_seconds(self.will, self.protocol_level),
                        self._will_delay_cap())
            if delay > 0:
                # MQTT5 Will Delay, DURABLE: the inbox store already holds
                # the LWT (attach carried it with delay_seconds) — let the
                # inbox service fire it server-side at detached_at +
                # min(delay, expiry). An in-memory timer here would lose
                # the will if the broker crashed inside the window
                # (ADVICE r3 finding 1; reference InboxStoreCoProc LWT)
                await self.inbox.detach(tenant, self.inbox_id,
                                        fire_lwt_on_expiry=True)
            else:
                # immediate fire, then let the inbox expire without
                # double-firing the LWT
                await self._fire_or_schedule_will()
                await self.inbox.detach(tenant, self.inbox_id,
                                        fire_lwt_on_expiry=False)
        elif self.expiry_seconds <= 0:
            # session expiry 0: state dies with the connection (v5 semantics)
            await self.inbox.delete(tenant, self.inbox_id)
        else:
            await self.inbox.detach(tenant, self.inbox_id,
                                    fire_lwt_on_expiry=False)
        await self.conn.close_transport()
        self.events.report(Event(EventType.CLIENT_DISCONNECTED, tenant,
                                 {"client_id": self.client_id}))

    _kicked_replaced = False

    def _will_delay_cap(self) -> int:
        # the session survives the connection for expiry_seconds — the
        # will may defer up to that window [MQTT-3.1.3.2-2]
        return max(0, int(self.expiry_seconds))

    async def kick(self) -> None:
        self._kicked_replaced = True
        await super().kick()

    # ---------------- subscriptions ----------------------------------------

    async def _subscribe_one(self, req: pk.SubscriptionRequest,
                             sub_id: Optional[int]) -> int:
        code = await super()._subscribe_one(req, sub_id)
        if code >= 0x80:
            return code
        sub = self.subscriptions[req.topic_filter]
        res = await self.inbox.sub(
            self.client_info.tenant_id, self.inbox_id, req.topic_filter,
            TopicFilterOption(qos=QoS(sub.qos), no_local=sub.no_local,
                              retain_as_published=sub.retain_as_published,
                              retain_handling=sub.retain_handling,
                              sub_id=sub.sub_id))
        if res == "exceeds_limit":
            del self.subscriptions[req.topic_filter]
            return (ReasonCode.QUOTA_EXCEEDED
                    if self.protocol_level >= PROTOCOL_MQTT5 else 0x80)
        return code

    @property
    def _NORMAL_SUB_RESOURCE(self):
        from ..plugin.throttler import TenantResourceType
        return TenantResourceType.TOTAL_PERSISTENT_SUBSCRIPTIONS

    async def _route(self, sub: Subscription) -> None:
        pass  # inbox.sub (in _subscribe_one) registers the inbox route

    async def _unroute(self, sub: Subscription) -> None:
        # persistent routes belong to the inbox; remove via the inbox so
        # store metadata and dist stay consistent
        await self.inbox.unsub(self.client_info.tenant_id, self.inbox_id,
                               sub.matcher.mqtt_topic_filter)

    # ---------------- inbox fetch loop (≈ inboxReader.fetch) ---------------

    _drop_on_recv_max = False  # pause the fetch loop, never drop QoS>0

    async def _fetch_loop(self) -> None:
        tenant = self.client_info.tenant_id
        catchup = True
        try:
            while not self.closed:
                await self._fetch_wake.wait()
                self._fetch_wake.clear()
                if catchup:
                    # ISSUE 13: the CATCH-UP drain (offline backlog at
                    # reconnect) is admission-governed and measured —
                    # a mass-reconnect storm stays tenant-fair and the
                    # drain cost lands in the `inbox.drain` stage and
                    # the tenant's SLO windows. Steady-state wakes
                    # (live traffic) bypass the governor.
                    catchup = False
                    governor = getattr(self.inbox, "drain_governor", None)
                    with trace.span("inbox.drain", tenant=tenant,
                                    inbox=self.inbox_id) as sp:
                        if governor is not None:
                            async with governor.slot(tenant):
                                fetched = await self._drain_pages(tenant)
                        else:
                            fetched = await self._drain_pages(tenant)
                        sp.set_tag("fetched", fetched or 0)
                    if fetched is None:
                        return      # inbox gone (kicked/deleted)
                else:
                    if await self._drain_pages(tenant) is None:
                        return      # inbox gone (kicked/deleted)
        except asyncio.CancelledError:
            pass

    async def _drain_pages(self, tenant: str) -> Optional[int]:
        """Drain inbox pages until empty/blocked; returns messages
        pushed, or None when the inbox is gone (the fetch loop exits) —
        the one page-pump definition, catch-up and steady-state wakes
        share it."""
        drained = 0
        while not self.closed:
            budget = self._client_recv_max - len(self._pid_to_seq)
            fetched = self.inbox.store.fetch(
                tenant, self.inbox_id, max_fetch=100,
                qos0_after=self._qos0_cursor,
                buffer_after=self._buf_cursor,
                max_buffer=max(0, budget))
            if fetched is None:
                return None     # inbox deleted/taken over: stop fetching
            if fetched.qos0 or fetched.buffer:
                # ≈ MsgFetched (inbox fetcher drained a page)
                self.events.report(Event(
                    EventType.MSG_FETCHED, tenant,
                    {"count": len(fetched.qos0)
                     + len(fetched.buffer)}))
            if not fetched.qos0 and not fetched.buffer:
                if budget <= 0 and self._pid_to_seq \
                        and not self._stall_reported:
                    # window full — but only a genuine backlog is a
                    # stall (fetch(max_buffer=0) can't tell "empty"
                    # from "window-gated"; a 1-message probe can,
                    # and fetch never advances cursors)
                    probe = self.inbox.store.fetch(
                        tenant, self.inbox_id, max_fetch=1,
                        qos0_after=self._qos0_cursor,
                        buffer_after=self._buf_cursor, max_buffer=1)
                    if probe is not None and probe.buffer:
                        self._report_stalled()
                break  # drained (or window full): wait for a wake
            for seq, topic, msg in fetched.qos0:
                self._qos0_cursor = seq
                await self._push(topic, msg)
                drained += 1
            if fetched.qos0:
                # qos0 committed on send (reference: commit after push)
                await self.inbox.store.commit(tenant, self.inbox_id,
                                              qos0_up_to=self._qos0_cursor)
            blocked = False
            for seq, topic, msg in fetched.buffer:
                if not await self._push(topic, msg, buffer_seq=seq):
                    blocked = True
                    break  # retry this seq after acks free the window
                self._buf_cursor = seq
                drained += 1
            if blocked:
                self._report_stalled()
                break  # _commit_acked wakes us
        return drained

    async def _push(self, topic: str, msg: Message,
                    buffer_seq: Optional[int] = None) -> bool:
        """Send one inbox message via the shared send path (properties,
        retain-as-published, receive-maximum all handled there). Returns
        False when the send window is exhausted (caller must not advance)."""
        sub = self._matching_sub(topic)
        if sub is None:
            # subscription changed since enqueue; honor the stored QoS
            sub = Subscription(matcher=None, qos=int(msg.pub_qos))
        # ISSUE 20: the e2e plane attributes this delivery to the inbox
        # drain, not the live fan-out (the HLC delta still measures the
        # true publish→deliver latency the subscriber experienced)
        token = DELIVERY_PATH.set("inbox_replay")
        try:
            result = await self._send_publish(topic, msg, sub,
                                              retained=msg.is_retained)
        finally:
            DELIVERY_PATH.reset(token)
        if result is BLOCKED:
            return False
        if buffer_seq is not None:
            if isinstance(result, int):
                self._pid_to_seq[result] = buffer_seq
            else:
                # sub got downgraded to qos0: nothing will ack; commit now
                self._commit_seq_direct(buffer_seq)
        return True

    def _matching_sub(self, topic: str) -> Optional[Subscription]:
        from ..utils import topic as topic_util
        levels = topic_util.parse(topic)
        for tf, sub in self.subscriptions.items():
            if topic_util.matches(levels, list(sub.matcher.filter_levels)):
                return sub
        return None

    # ---------------- ack handling → commit --------------------------------

    def _commit_seq_direct(self, seq: int) -> None:
        self._acked_seqs.add(seq)
        self._advance_commit()

    _stall_reported = False

    def _report_stalled(self) -> None:
        """Once per stall transition (≈ SubStalled.java), not per wake —
        the flag clears when an ack frees window budget."""
        if self._stall_reported:
            return
        self._stall_reported = True
        self.events.report(Event(
            EventType.SUB_STALLED, self.client_info.tenant_id,
            {"client_id": self.client_id,
             "inflight": len(self._pid_to_seq)}))

    def _commit_acked(self, pid: int) -> None:
        # ANY ack frees send-window budget (direct retained deliveries
        # included), so the stall transition resets before the inbox-seq
        # check can early-return
        self._stall_reported = False
        seq = self._pid_to_seq.pop(pid, None)
        if seq is None:
            return
        self._acked_seqs.add(seq)
        self._advance_commit()
        self._fetch_wake.set()  # freed in-flight budget

    def _advance_commit(self) -> None:
        up_to = self._committed_seq
        while up_to + 1 in self._acked_seqs:
            up_to += 1
            self._acked_seqs.discard(up_to)
        if up_to != self._committed_seq:
            self._committed_seq = up_to
            # fire-and-forget: commits are monotonic and idempotent (a
            # smaller up_to applying late is a no-op), so ack handling
            # stays synchronous while the trim rides consensus; hold a
            # strong reference and surface failures (GC'd or silently
            # failed tasks would un-trim acked messages)
            task = asyncio.ensure_future(self.inbox.store.commit(
                self.client_info.tenant_id, self.inbox_id,
                buffer_up_to=up_to))
            self._commit_tasks.add(task)

            def _done(t):
                self._commit_tasks.discard(t)
                if not t.cancelled() and t.exception() is not None:
                    import logging
                    logging.getLogger(__name__).warning(
                        "inbox commit failed: %r", t.exception())
            task.add_done_callback(_done)

    def _on_puback(self, pid: int) -> None:
        super()._on_puback(pid)
        self._commit_acked(pid)
        # any ack (inbox or direct retained delivery) frees send-window
        # budget — always wake the fetch loop
        self._fetch_wake.set()

    def _on_pubcomp(self, pid: int) -> None:
        super()._on_pubcomp(pid)
        self._commit_acked(pid)
        self._fetch_wake.set()

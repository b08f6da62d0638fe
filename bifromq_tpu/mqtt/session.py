"""MQTT session logic: transient sessions, registries, local delivery.

Re-expression of the reference session stack (bifromq-mqtt
.../handler/MQTTSessionHandler.java 1868 LoC + MQTTTransientSessionHandler,
protocol variance from IMQTTProtocolHelper v3/v5): one asyncio ``Session``
class parameterized by protocol level, since the version differences —
reason codes, properties, topic aliases — live in the codec layer here.

Delivery path: the dist plane fans out to ``TransientSubBroker`` (sub-broker
id 0, ≈ mqtt-broker-client + LocalDistService.dist:97) which resolves
receiver ids in the ``LocalSessionRegistry`` and pushes into sessions.
SessionRegistry kicks the previous owner on re-register
(≈ session-dict SessionRegistry.java:72-86).
"""

from __future__ import annotations

import asyncio
import functools
import logging
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Sequence, Set, Tuple

from ..dist.service import DistService
from ..plugin.auth import IAuthProvider, MQTTAction
from ..plugin.events import Event, EventType, IEventCollector
from ..plugin.settings import Setting, TenantSettings
from ..plugin.subbroker import (DeliveryPack, DeliveryResult, ISubBroker,
                                TRANSIENT_SUB_BROKER_ID)
from .. import trace
from ..types import ClientInfo, MatchInfo, Message, QoS, RouteMatcher
from ..utils import topic as topic_util
from ..utils.hlc import HLC
from ..obs import OBS
from ..obs.e2e import DELIVERY_PATH
from ..utils.env import env_float
from . import packets as pk
from .protocol import (PROTOCOL_MQTT5, PropertyId, ReasonCode,
                       CONNACK_ACCEPTED)


@dataclass
class Subscription:
    matcher: RouteMatcher
    qos: int
    no_local: bool = False
    retain_as_published: bool = False
    retain_handling: int = 0
    sub_id: Optional[int] = None


class LocalSessionRegistry:
    """receiver_id (session id) → live session (≈ LocalSessionRegistry)."""

    def __init__(self) -> None:
        self._by_id: Dict[str, "Session"] = {}

    def register(self, session: "Session") -> None:
        self._by_id[session.session_id] = session

    def unregister(self, session: "Session") -> None:
        self._by_id.pop(session.session_id, None)

    def get(self, session_id: str) -> Optional["Session"]:
        return self._by_id.get(session_id)

    def __len__(self) -> int:
        return len(self._by_id)


class SessionRegistry:
    """(tenant, client_id) → session, kicking the previous owner on conflict
    (≈ session-dict server SessionRegistry.java:72-86)."""

    def __init__(self, events: IEventCollector) -> None:
        self._owners: Dict[Tuple[str, str], "Session"] = {}
        self._events = events
        # MQTT5 Will Delay [MQTT-3.1.3.2.2]: pending delayed wills keyed by
        # session slot, value = (task, fire callback). Registry-owned so a
        # reconnect DISCARDS the pending will, a re-schedule replaces it
        # (no double fire), and broker shutdown flushes them (the window
        # ends with the server). The fire callback must capture plain refs
        # (dist/events/will fields), never the Session object.
        self._pending_wills: Dict[Tuple[str, str], Tuple] = {}

    async def register(self, session: "Session") -> None:
        key = (session.client_info.tenant_id, session.client_id)
        pending = self._pending_wills.pop(key, None)
        if pending is not None:
            task, fire, state = pending
            if state["firing"]:
                # the delay expired concurrently and fire() is already in
                # flight (e.g. past dist.pub, before retain/event):
                # cancelling mid-fire and then re-firing would DOUBLE-
                # publish — let the in-flight fire finish instead (the
                # will belongs to the old session's end either way)
                try:
                    await asyncio.shield(task)
                except Exception:  # noqa: BLE001 — run() reports its own
                    pass
            else:
                task.cancel()
                if session.clean_start:
                    # a clean-start reconnect ENDS the old session — per
                    # [MQTT-3.1.3.2-2] the will fires at session end, it is
                    # not silently discarded (only a resuming reconnect
                    # suppresses it)
                    try:
                        await fire()
                    except Exception:  # noqa: BLE001
                        self._events.report(Event(
                            EventType.WILL_DIST_ERROR, key[0],
                            {"client_id": key[1]}))
        prev = self._owners.get(key)
        self._owners[key] = session
        if prev is not None and prev is not session:
            self._events.report(Event(
                EventType.KICKED, session.client_info.tenant_id,
                {"client_id": session.client_id}))
            await prev.kick()

    def unregister(self, session: "Session") -> None:
        key = (session.client_info.tenant_id, session.client_id)
        if self._owners.get(key) is session:
            del self._owners[key]

    def get(self, tenant_id: str, client_id: str) -> Optional["Session"]:
        return self._owners.get((tenant_id, client_id))

    def client_ids(self, tenant_id: str) -> List[str]:
        """Connected client ids for a tenant (introspection)."""
        return [cid for (t, cid) in self._owners if t == tenant_id]

    def schedule_will(self, tenant_id: str, client_id: str,
                      delay_s: float, fire) -> None:
        """Arm (or re-arm) the delayed will for a session slot; ``fire``
        is an async callable holding no Session reference."""
        key = (tenant_id, client_id)
        old = self._pending_wills.pop(key, None)
        if old is not None and not old[2]["firing"]:
            old[0].cancel()
        state = {"firing": False}

        async def run():
            try:
                await asyncio.sleep(delay_s)
                # point of no return: from here a cancel() cannot prevent
                # the publish — register()/flush await us instead of
                # re-firing (the cancel-then-refire double-publish race)
                state["firing"] = True
                try:
                    await fire()
                except Exception:  # noqa: BLE001 — a lost will must be
                    # plugin-visible, like the inbox LWT path
                    self._events.report(Event(
                        EventType.WILL_DIST_ERROR, tenant_id,
                        {"client_id": client_id}))
            finally:
                if self._pending_wills.get(key, (None,))[0] is task:
                    del self._pending_wills[key]

        task = asyncio.get_running_loop().create_task(run())
        self._pending_wills[key] = (task, fire, state)

    async def flush_pending_wills(self, should_fire) -> None:
        """Broker shutdown: the delay window ends with the server — fire
        each armed will now unless ``should_fire(tenant_id)`` says the
        tenant suppresses shutdown LWTs (NoLWTWhenServerShuttingDown)."""
        pending = list(self._pending_wills.items())
        self._pending_wills.clear()
        for (tenant_id, client_id), (task, fire, state) in pending:
            if state["firing"]:
                # fire() already in flight: await it, never re-fire
                try:
                    await asyncio.shield(task)
                except Exception:  # noqa: BLE001 — run() reports its own
                    pass
                continue
            task.cancel()
            try:
                # a throwing settings plugin must not abort shutdown; fall
                # back to the setting's CONFIGURED default
                # (NoLWTWhenServerShuttingDown defaults to True — suppress;
                # both here and in the reference, Setting.java) instead of
                # inverting it
                from ..plugin.settings import _DEFAULTS, Setting
                fire_it = not _DEFAULTS[Setting.NoLWTWhenServerShuttingDown]
                try:
                    fire_it = should_fire(tenant_id)
                except Exception:  # noqa: BLE001
                    log.exception("settings plugin failed during shutdown")
                if fire_it:
                    await fire()
            except Exception:  # noqa: BLE001
                self._events.report(Event(
                    EventType.WILL_DIST_ERROR, tenant_id,
                    {"client_id": client_id}))

    def close(self) -> None:
        """Cancel every pending delayed will (broker shutdown)."""
        for t, _fire, _state in self._pending_wills.values():
            t.cancel()
        self._pending_wills.clear()


class TransientSubBroker(ISubBroker):
    """Sub-broker id 0: delivery into local transient sessions."""

    id = TRANSIENT_SUB_BROKER_ID

    def __init__(self, registry: LocalSessionRegistry) -> None:
        self.registry = registry

    async def deliver(self, tenant_id: str, deliverer_key: str,
                      packs: Sequence[DeliveryPack]
                      ) -> Dict[MatchInfo, DeliveryResult]:
        out: Dict[MatchInfo, DeliveryResult] = {}
        with trace.span("deliver.transient", tenant=tenant_id,
                        deliverer_key=deliverer_key) as sp:
            for pack in packs:
                for mi in pack.match_infos:
                    session = self.registry.get(mi.receiver_id)
                    if session is None or session.closed:
                        out[mi] = DeliveryResult.NO_RECEIVER
                        continue
                    ok = await session.deliver(pack.message_pack, mi)
                    out[mi] = (DeliveryResult.OK if ok
                               else DeliveryResult.NO_SUB)
            sp.set_tag("receivers", len(out))
        return out

    async def check_subscriptions(self, tenant_id: str,
                                  match_infos: Sequence[MatchInfo]
                                  ) -> List[bool]:
        out = []
        for mi in match_infos:
            s = self.registry.get(mi.receiver_id)
            out.append(bool(
                s is not None and not s.closed
                and mi.matcher.mqtt_topic_filter in s.subscriptions))
        return out


class SessionStartAborted(Exception):
    """Session.start() failed after already reporting its own event and
    closing the transport — callers must unwind quietly (no crash log)."""


class _PacketIdAllocator:
    def __init__(self) -> None:
        self._next = 1
        self._in_use: Set[int] = set()

    def alloc(self) -> Optional[int]:
        for _ in range(65535):
            pid = self._next
            self._next = pid % 65535 + 1
            if pid not in self._in_use:
                self._in_use.add(pid)
                return pid
        return None

    def release(self, pid: int) -> None:
        self._in_use.discard(pid)


@dataclass
class _OutboundQoS:
    packet_id: int
    publish: pk.Publish
    phase: int  # 1 = awaiting PUBACK/PUBREC, 2 = awaiting PUBCOMP
    sent_at: float = 0.0  # monotonic send time (ack-latency pacing)


# _send_publish result: the send was gated by receive-maximum / packet-id
# exhaustion. Transient sessions drop (and report) a live message;
# persistent sessions stop fetching and retry after acks free the window;
# a SUBSCRIBE's retained messages wait in the session's retained backlog.
BLOCKED = object()

log = logging.getLogger(__name__)


def will_to_message(will: pk.Will, protocol_level: int) -> Message:
    """The ONE will→Message definition (transient fire, delayed fire, and
    the persistent LWT all share it, so v5 will properties cannot diverge
    between paths)."""
    wp = (will.properties or {}) if protocol_level >= PROTOCOL_MQTT5 else {}
    return Message(
        message_id=0, pub_qos=QoS(will.qos), payload=will.payload,
        timestamp=HLC.INST.get(), is_retain=will.retain,
        expiry_seconds=wp.get(PropertyId.MESSAGE_EXPIRY_INTERVAL,
                              0xFFFFFFFF),
        user_properties=tuple(wp.get(PropertyId.USER_PROPERTY) or ()),
        content_type=wp.get(PropertyId.CONTENT_TYPE, ""),
        response_topic=wp.get(PropertyId.RESPONSE_TOPIC, ""),
        correlation_data=wp.get(PropertyId.CORRELATION_DATA, b""),
        payload_format_indicator=int(
            wp.get(PropertyId.PAYLOAD_FORMAT_INDICATOR, 0)))


def will_delay_seconds(will: Optional[pk.Will], protocol_level: int) -> int:
    if will is None or protocol_level < PROTOCOL_MQTT5:
        return 0
    return int((will.properties or {}).get(
        PropertyId.WILL_DELAY_INTERVAL, 0))


async def fire_will(*, will: pk.Will, client_info: ClientInfo,
                    dist, retain_service, events: IEventCollector,
                    protocol_level: int = PROTOCOL_MQTT5,
                    msg: Optional[Message] = None) -> None:
    """Publish a will (shared by immediate and delayed paths; holds only
    the refs it needs — never a Session). When ``msg`` is omitted it is
    built HERE, at fire time — a will's MESSAGE_EXPIRY_INTERVAL starts
    when the will is published, so stamping it at arm time would burn the
    delay window out of the expiry."""
    if msg is None:
        msg = will_to_message(will, protocol_level)
    await dist.pub(client_info, will.topic, msg)
    if will.retain and retain_service is not None:
        await retain_service.retain(client_info, will.topic, msg)
    events.report(Event(EventType.WILL_DISTED, client_info.tenant_id,
                        {"topic": will.topic}))


class Session:
    """One connected MQTT session (transient)."""

    def __init__(self, *, conn, client_id: str, client_info: ClientInfo,
                 protocol_level: int, clean_start: bool, keep_alive: int,
                 will: Optional[pk.Will], settings: TenantSettings,
                 dist: DistService, auth: IAuthProvider,
                 events: IEventCollector,
                 local_registry: LocalSessionRegistry,
                 session_registry: SessionRegistry,
                 connect_props: Optional[dict] = None,
                 retain_service=None, throttler=None,
                 auth_method: Optional[str] = None,
                 user_props_customizer=None) -> None:
        self.conn = conn
        self.client_id = client_id
        self.client_info = client_info
        self.protocol_level = protocol_level
        self.clean_start = clean_start
        self.keep_alive = keep_alive
        self.will = will
        self.settings = settings
        self.dist = dist
        self.auth = auth
        self.events = events
        self.local_registry = local_registry
        self.session_registry = session_registry
        self.retain_service = retain_service
        from ..plugin.throttler import AllowAllResourceThrottler
        self.throttler = throttler or AllowAllResourceThrottler()
        self.auth_method = auth_method  # enhanced-auth method (MQTT5)
        self._reauth_pending = False
        self.connect_props = connect_props or {}
        # ≈ IUserPropsCustomizer SPI (mqtt-server-spi): stamps extra user
        # properties at the inbound and outbound edges
        from ..plugin.userprops import NoopUserPropsCustomizer
        self.user_props_customizer = (user_props_customizer
                                      or NoopUserPropsCustomizer())

        self.session_id = uuid.uuid4().hex
        self.subscriptions: Dict[str, Subscription] = {}
        self.closed = False
        self._will_suppressed = False
        self._pid_alloc = _PacketIdAllocator()
        self._outbound: Dict[int, _OutboundQoS] = {}
        # retained messages a SUBSCRIBE matched that found the send window
        # full: sent, in order, as PUBACKs / PUBCOMPs free packet ids
        self._retained_backlog: Deque[Tuple[str, Message, Subscription]] = \
            deque()
        self._inbound_qos2: Set[int] = set()
        self._recv_topic_alias: Dict[int, str] = {}
        # per-session publish-rate token bucket (≈ ExceedPubRate guard,
        # MsgPubPerSec tenant setting)
        from ..utils.ratelimit import TokenBucket
        self._pub_bucket = TokenBucket(
            float(self.settings[Setting.MsgPubPerSec] or 0))
        self.last_active = time.monotonic()
        # client's receive maximum (v5) ceiling + latency-AIMD pacing
        # floor (MinSendPerSec) — ≈ AdaptiveReceiveQuota at
        # MQTTSessionHandler.java:373
        self._client_recv_max = int(
            self.connect_props.get(PropertyId.RECEIVE_MAXIMUM, 65535)
            if protocol_level >= PROTOCOL_MQTT5 else 65535)
        from .quota import AdaptiveReceiveQuota
        self._recv_quota = AdaptiveReceiveQuota(
            int(self.settings[Setting.MinSendPerSec] or 1),
            self._client_recv_max)
        # outbound topic aliasing (v5, ≈ SenderTopicAliasManager): the
        # client's TopicAliasMaximum caps how many topics we may alias
        # on the way OUT; repeated topics then ship a 2-byte alias
        # instead of the full string
        self._send_alias_max = int(
            self.connect_props.get(PropertyId.TOPIC_ALIAS_MAXIMUM, 0)
            if protocol_level >= PROTOCOL_MQTT5 else 0)
        self._send_alias: Dict[str, int] = {}
        # client's Maximum Packet Size (v5): outbound packets beyond it
        # are dropped, never sent [MQTT-3.1.2-25]
        self._client_max_packet = int(
            self.connect_props.get(PropertyId.MAXIMUM_PACKET_SIZE, 0)
            if protocol_level >= PROTOCOL_MQTT5 else 0)

    # ---------------- lifecycle -------------------------------------------

    async def start(self) -> None:
        self.local_registry.register(self)
        await self.session_registry.register(self)
        await self._global_kick()
        self.events.report(Event(
            EventType.MQTT_SESSION_START, self.client_info.tenant_id,
            {"client_id": self.client_info.meta().get("clientId", "")}))

    async def _global_kick(self) -> None:
        """Cluster-wide single-owner kick via the session-dict service
        (≈ cross-node SessionRegistry semantics)."""
        sd = getattr(getattr(self.conn, "broker", None), "session_dict",
                     None)
        if sd is not None:
            await sd.kick_everywhere(self.client_info.tenant_id,
                                     self.client_id)

    async def kick(self) -> None:
        """Another session took over this (tenant, client_id)."""
        self._will_suppressed = True
        # server-initiated disconnect: reported for EVERY protocol level
        # (only the DISCONNECT packet itself is MQTT5-only)
        self.events.report(Event(EventType.BY_SERVER,
                                 self.client_info.tenant_id,
                                 {"client_id": self.client_id,
                                  "reason": "kicked"}))
        if self.protocol_level >= PROTOCOL_MQTT5:
            await self.conn.send(pk.Disconnect(
                reason_code=ReasonCode.SESSION_TAKEN_OVER))
        await self.close(fire_will=False)

    async def close(self, fire_will: bool) -> None:
        if self.closed:
            return
        self.closed = True
        self.session_registry.unregister(self)
        self.local_registry.unregister(self)
        OBS.e2e.drop_watermark(self.session_id)
        self._retained_backlog.clear()
        for tf, sub in list(self.subscriptions.items()):
            await self._unroute(sub)
        self.subscriptions.clear()
        if fire_will and self.will is not None and not self._will_suppressed:
            await self._fire_or_schedule_will()
        await self.conn.close_transport()
        # after cleanup: a throwing event-collector plugin must not be
        # able to abort teardown (closed is already True — no retry)
        self.events.report(Event(
            EventType.MQTT_SESSION_STOP, self.client_info.tenant_id,
            {"client_id": self.client_info.meta().get("clientId", "")}))
        self.events.report(Event(EventType.CLIENT_DISCONNECTED,
                                 self.client_info.tenant_id,
                                 {"client_id": self.client_id}))

    # Will Delay only defers when session state OUTLIVES the connection
    # [MQTT-3.1.3.2-2]: the will fires at min(delay, session end), and a
    # transient session ends the instant the network connection drops —
    # PersistentSession overrides this with its expiry window.
    def _will_delay_cap(self) -> int:
        return 0

    async def _fire_or_schedule_will(self) -> None:
        """Immediate fire, or — MQTT5 Will Delay [MQTT-3.1.3.2-2] — arm the
        registry-owned pending will: a reconnect into this
        (tenant, client_id) slot discards it, re-arming replaces it, and
        broker shutdown flushes it. The callback captures plain refs,
        never the Session."""
        delay = min(will_delay_seconds(self.will, self.protocol_level),
                    self._will_delay_cap())
        if delay > 0:
            self.session_registry.schedule_will(
                self.client_info.tenant_id, self.client_id, delay,
                functools.partial(
                    fire_will, will=self.will,
                    protocol_level=self.protocol_level,
                    client_info=self.client_info, dist=self.dist,
                    retain_service=self.retain_service,
                    events=self.events))
        else:
            await self._fire_will()

    async def _fire_will(self) -> None:
        will = self.will
        await fire_will(
            will=will, msg=will_to_message(will, self.protocol_level),
            client_info=self.client_info, dist=self.dist,
            retain_service=self.retain_service, events=self.events)

    # ---------------- inbound packet handling ------------------------------

    async def handle(self, packet) -> None:
        self.last_active = time.monotonic()
        if isinstance(packet, pk.Publish):
            await self._on_publish(packet)
        elif isinstance(packet, pk.PubAck):
            self._on_puback(packet.packet_id)
            if self._retained_backlog:
                await self._drain_retained()
        elif isinstance(packet, pk.PubRec):
            await self._on_pubrec(packet.packet_id)
        elif isinstance(packet, pk.PubRel):
            await self._on_pubrel(packet.packet_id)
        elif isinstance(packet, pk.PubComp):
            self._on_pubcomp(packet.packet_id)
            if self._retained_backlog:
                await self._drain_retained()
        elif isinstance(packet, pk.Subscribe):
            await self._on_subscribe(packet)
        elif isinstance(packet, pk.Unsubscribe):
            await self._on_unsubscribe(packet)
        elif isinstance(packet, pk.PingReq):
            self.events.report(Event(EventType.PING_REQ,
                                     self.client_info.tenant_id, {}))
            await self.conn.send(pk.PingResp())
        elif isinstance(packet, pk.Disconnect):
            self.events.report(Event(EventType.BY_CLIENT,
                                     self.client_info.tenant_id,
                                     {"client_id": self.client_id}))
            if (self.protocol_level >= PROTOCOL_MQTT5
                    and packet.reason_code ==
                    ReasonCode.DISCONNECT_WITH_WILL):
                await self.close(fire_will=True)
            else:
                self._will_suppressed = True
                await self.close(fire_will=False)
        elif isinstance(packet, pk.Auth):
            await self._on_auth(packet)
        else:
            await self.conn.protocol_error(f"unexpected {type(packet).__name__}")

    def _sub_resource(self, tf: str):
        from ..plugin.throttler import TenantResourceType
        if topic_util.is_shared_subscription(tf):
            return TenantResourceType.TOTAL_SHARED_SUBSCRIPTIONS
        return self._NORMAL_SUB_RESOURCE

    # persistent sessions override with TOTAL_PERSISTENT_SUBSCRIPTIONS
    @property
    def _NORMAL_SUB_RESOURCE(self):
        from ..plugin.throttler import TenantResourceType
        return TenantResourceType.TOTAL_TRANSIENT_SUBSCRIPTIONS

    # -------- MQTT5 enhanced re-auth (≈ ReAuthenticator.java) --------------

    async def _on_auth(self, a: pk.Auth) -> None:
        from ..plugin.auth import ExtAuthData

        if self.protocol_level < PROTOCOL_MQTT5 or self.auth_method is None:
            await self.conn.protocol_error("unexpected AUTH")
            return
        props = a.properties or {}
        method = props.get(PropertyId.AUTHENTICATION_METHOD)
        if method != self.auth_method:
            # [MQTT-4.12.0-5] method must not change mid-connection
            await self.conn.protocol_error(
                "auth method changed", ReasonCode.BAD_AUTHENTICATION_METHOD)
            return
        if a.reason_code == ReasonCode.REAUTHENTICATE:
            self._reauth_pending = True
        elif not self._reauth_pending:
            await self.conn.protocol_error("unexpected AUTH")
            return
        res = await self.auth.extended_auth(ExtAuthData(
            client_id=self.client_id, method=method,
            data=props.get(PropertyId.AUTHENTICATION_DATA, b""),
            is_reauth=True))
        if res.kind == "fail":
            # ≈ ReAuthFailed close event
            self.events.report(Event(EventType.RE_AUTH_FAILED,
                                     self.client_info.tenant_id,
                                     {"reason": res.reason}))
            await self.conn.protocol_error("re-authentication failed",
                                           ReasonCode.NOT_AUTHORIZED)
            return
        out_props = {PropertyId.AUTHENTICATION_METHOD: method}
        if res.data:
            out_props[PropertyId.AUTHENTICATION_DATA] = res.data
        if res.kind == "continue":
            await self.conn.send(pk.Auth(
                reason_code=ReasonCode.CONTINUE_AUTHENTICATION,
                properties=out_props))
            return
        self._reauth_pending = False
        await self.conn.send(pk.Auth(reason_code=ReasonCode.SUCCESS,
                                     properties=out_props))

    # -------- PUBLISH ingress (≈ MQTTSessionHandler.handleQoS{0,1,2}Pub) ---

    async def _on_publish(self, p: pk.Publish) -> None:
        topic = await self._resolve_topic_alias(p)
        if topic is None:
            return  # error already sent by _resolve_topic_alias
        ts = self.settings
        from ..utils import sysprops as sp
        bad_utf8 = (sp.get(sp.SysProp.SANITY_CHECK_MQTT_UTF8)
                    and not topic_util.is_well_formed_utf8(topic))
        if bad_utf8 or not topic_util.is_valid_topic(
                topic, ts[Setting.MaxTopicLevelLength],
                ts[Setting.MaxTopicLevels], ts[Setting.MaxTopicLength]):
            # bad UTF-8 → MalformedTopic; structural violation (wildcard/
            # empty/too long) → InvalidTopic (distinct reference events)
            self.events.report(Event(
                EventType.MALFORMED_TOPIC if bad_utf8
                else EventType.INVALID_TOPIC,
                self.client_info.tenant_id,
                {"topic": topic_util.to_str(topic)}))
            await self.conn.protocol_error(
                "invalid topic", ReasonCode.TOPIC_NAME_INVALID)
            return
        # ISSUE 12 byte plane: ``topic`` may be raw wire bytes (server
        # ingress keeps them for the match path — byte cache keys, zero
        # re-encode in TopicBytes); text boundaries (events, SPI plugins,
        # span tags, retain) share THIS one decode
        topic_s = topic_util.to_str(topic)
        if p.qos > ts[Setting.MaximumQoS]:
            await self.conn.protocol_error(
                "QoS not supported", ReasonCode.QOS_NOT_SUPPORTED)
            return
        if len(p.payload) > ts[Setting.MaxUserPayloadBytes]:
            await self.conn.protocol_error(
                "payload too large", ReasonCode.PACKET_TOO_LARGE)
            return
        # QoS2 DUP retransmits of an in-flight packet are not new
        # publishes — they must never drain the rate bucket
        is_qos2_dup = p.qos == 2 and p.packet_id in self._inbound_qos2
        if self._pub_bucket.rate > 0 and not is_qos2_dup \
                and not self._pub_bucket.try_take():
            # the reference treats sustained over-rate publishing as a
            # session-fatal violation (ExceedPubRate → disconnect)
            self.events.report(Event(
                EventType.EXCEED_PUB_RATE,
                self.client_info.tenant_id,
                {"client_id": self.client_id,
                 "limit": self._pub_bucket.rate}))
            await self.conn.disconnect_with(
                ReasonCode.MESSAGE_RATE_TOO_HIGH
                if self.protocol_level >= PROTOCOL_MQTT5 else 0)
            return
        from ..plugin.throttler import TenantResourceType
        if not self.throttler.has_resource(
                self.client_info.tenant_id,
                TenantResourceType.TOTAL_INGRESS_BYTES_PER_SECOND):
            self.events.report(Event(EventType.OUT_OF_TENANT_RESOURCE,
                                     self.client_info.tenant_id,
                                     {"topic": topic_s,
                                      "resource": "ingress_bytes"}))
            if p.qos == 1:
                await self.conn.send(pk.PubAck(
                    packet_id=p.packet_id,
                    reason_code=ReasonCode.QUOTA_EXCEEDED))
            elif p.qos == 2:
                await self.conn.send(pk.PubRec(
                    packet_id=p.packet_id,
                    reason_code=ReasonCode.QUOTA_EXCEEDED))
            return
        allowed = await self._check_permission(MQTTAction.PUB, topic_s)
        if not allowed:
            self.events.report(Event(EventType.PUB_ACTION_DISALLOW,
                                     self.client_info.tenant_id,
                                     {"topic": topic_s}))
            if self.protocol_level < PROTOCOL_MQTT5 and p.qos > 0:
                # MQTT3 acks cannot convey an error: the reference closes
                # the channel instead (NoPubPermission close event)
                self.events.report(Event(EventType.NO_PUB_PERMISSION,
                                         self.client_info.tenant_id,
                                         {"topic": topic_s}))
                await self.conn.disconnect_with(0)
            elif p.qos == 1:
                await self.conn.send(pk.PubAck(
                    packet_id=p.packet_id,
                    reason_code=ReasonCode.NOT_AUTHORIZED))
            elif p.qos == 2:
                await self.conn.send(pk.PubRec(
                    packet_id=p.packet_id,
                    reason_code=ReasonCode.NOT_AUTHORIZED))
            elif self.protocol_level >= PROTOCOL_MQTT5:
                await self.conn.disconnect_with(ReasonCode.NOT_AUTHORIZED)
            return
        if p.qos == 2:
            if p.packet_id in self._inbound_qos2:
                # duplicate delivery of an unreleased QoS2 publish
                await self.conn.send(pk.PubRec(packet_id=p.packet_id))
                return
            if len(self._inbound_qos2) >= ts[Setting.ReceivingMaximum]:
                # client exceeded the server's advertised Receive Maximum
                # [MQTT-3.3.4-9] (≈ ExceedReceivingLimit close event)
                self.events.report(Event(
                    EventType.EXCEED_RECEIVING_LIMIT,
                    self.client_info.tenant_id,
                    {"limit": ts[Setting.ReceivingMaximum]}))
                await self.conn.disconnect_with(
                    ReasonCode.RECEIVE_MAXIMUM_EXCEEDED
                    if self.protocol_level >= PROTOCOL_MQTT5 else 0)
                return
            self._inbound_qos2.add(p.packet_id)
            self.events.report(Event(EventType.QOS2_RECEIVED,
                                     self.client_info.tenant_id,
                                     {"packet_id": p.packet_id}))

        expiry = 0xFFFFFFFF
        uprops: tuple = ()
        ctype, rtopic, cdata, pfi = "", "", b"", 0
        if self.protocol_level >= PROTOCOL_MQTT5 and p.properties:
            pp = p.properties
            expiry = pp.get(PropertyId.MESSAGE_EXPIRY_INTERVAL, 0xFFFFFFFF)
            # request/response + content metadata travel end-to-end
            # [MQTT-3.3.2-15..20] (≈ the reference's Message proto fields)
            uprops = tuple(pp.get(PropertyId.USER_PROPERTY) or ())
            ctype = pp.get(PropertyId.CONTENT_TYPE, "")
            rtopic = pp.get(PropertyId.RESPONSE_TOPIC, "")
            cdata = pp.get(PropertyId.CORRELATION_DATA, b"")
            pfi = int(pp.get(PropertyId.PAYLOAD_FORMAT_INDICATOR, 0))
        hlc_now = HLC.INST.get()
        try:
            extra = tuple(self.user_props_customizer.inbound(
                topic_s, p.qos, p.payload, self.client_info, hlc_now))
        except Exception:  # noqa: BLE001 — SPI failure must not drop the pub
            log.exception("user-props customizer inbound failed")
            extra = ()
        msg = Message(message_id=p.packet_id or 0, pub_qos=QoS(p.qos),
                      payload=p.payload, timestamp=hlc_now,
                      expiry_seconds=expiry, is_retain=p.retain,
                      user_properties=uprops + extra, content_type=ctype,
                      response_topic=rtopic, correlation_data=cdata,
                      payload_format_indicator=pfi)
        self.events.report(Event(EventType.PUB_RECEIVED,
                                 self.client_info.tenant_id,
                                 {"topic": topic_s, "qos": p.qos}))
        # ISSUE 2: the publish→match→deliver ROOT span — the per-tenant
        # sampling draw for the whole distributed trace happens here; its
        # exit feeds the "ingest" stage histogram and the tenant's
        # windowed RED duration (the /tenants "is this tenant slow NOW"
        # signal) regardless of sampling (trace/names.py)
        with trace.span("pub.ingest", tenant=self.client_info.tenant_id,
                        topic=topic_s, qos=p.qos):
            await self._ingest_publish(p, topic, msg, topic_s=topic_s)

    async def _ingest_publish(self, p: pk.Publish, topic,
                              msg: Message, topic_s: str = None) -> None:
        """Retain + dist + ack — the traced tail of ``_on_publish``.

        ISSUE 7 overload discipline: under device-pipeline overload
        (ring pressure + batcher backlog past the shed bound) QoS0
        publishes are SHED — tenant-fair, noisy tenants first — before
        they cost a match; at-most-once loss is the contract. QoS>0 is
        never shed: it backpressures through the bounded ingest gate
        instead (the session's read loop parks, TCP pushes back on the
        publisher) so at-least-once work cannot queue without bound.
        """
        from ..resilience.device import INGEST_GATE, SHEDDER
        if topic_s is None:
            topic_s = topic_util.to_str(topic)
        ts = self.settings
        if p.retain and self.retain_service is not None:
            if ts[Setting.RetainEnabled]:
                # retained state lands BEFORE any shed decision: the shed
                # contract covers at-most-once DELIVERY, not the durable
                # retain-store write (dropping it would leave stale
                # retained payloads long after the overload clears), and
                # the write costs no device match
                await self.retain_service.retain(self.client_info, topic_s,
                                                 msg)
        if p.qos == 0 and SHEDDER.should_shed(self.client_info.tenant_id):
            self.events.report(Event(
                EventType.SHED_QOS0, self.client_info.tenant_id,
                {"topic": topic_s, "reason": "overload"}))
            # ISSUE 20: a shed publish is messages NOT delivered — the
            # tenant's SLO budget pays for it
            OBS.record_delivery_violation(self.client_info.tenant_id, 0,
                                          "shed")
            return
        try:
            if p.qos > 0:
                await INGEST_GATE.acquire()
                try:
                    with trace.span("dist.pub"):
                        result = await self.dist.pub(self.client_info,
                                                     topic, msg)
                finally:
                    INGEST_GATE.release()
            else:
                with trace.span("dist.pub"):
                    result = await self.dist.pub(self.client_info, topic,
                                                 msg)
        except Exception:  # noqa: BLE001 — dist backend failure
            log.exception("dist.pub failed")
            # ≈ QoS{0,1,2}DistError events; QoS1/2 get an error ack so the
            # client can retry, QoS0 is silently lost (at-most-once)
            self.events.report(Event(
                (EventType.QOS0_DIST_ERROR, EventType.QOS1_DIST_ERROR,
                 EventType.QOS2_DIST_ERROR)[p.qos],
                self.client_info.tenant_id, {"topic": topic_s}))
            if p.qos == 2:
                # forget the undistributed publish on EVERY version —
                # otherwise a v3 retry hits the duplicate guard, gets a
                # bare PUBREC, and the message is silently lost
                self._inbound_qos2.discard(p.packet_id)
            if self.protocol_level >= PROTOCOL_MQTT5:
                if p.qos == 1:
                    await self.conn.send(pk.PubAck(
                        packet_id=p.packet_id,
                        reason_code=ReasonCode.UNSPECIFIED_ERROR))
                elif p.qos == 2:
                    await self.conn.send(pk.PubRec(
                        packet_id=p.packet_id,
                        reason_code=ReasonCode.UNSPECIFIED_ERROR))
            return
        if p.qos == 0:
            return
        rc = (ReasonCode.SUCCESS if result.fanout > 0
              else ReasonCode.NO_MATCHING_SUBSCRIBERS)
        ack = pk.PubAck if p.qos == 1 else pk.PubRec
        with trace.span("pub.ack"):
            await self.conn.send(ack(
                packet_id=p.packet_id,
                reason_code=(rc if self.protocol_level >= PROTOCOL_MQTT5
                             else 0)))

    async def _resolve_topic_alias(self, p: pk.Publish) -> Optional[str]:
        """MQTT5 inbound topic alias (≈ v5/ReceiverTopicAliasManager).

        Returns the effective topic, or None after sending the error.
        """
        alias = (p.properties or {}).get(PropertyId.TOPIC_ALIAS) \
            if self.protocol_level >= PROTOCOL_MQTT5 else None
        if alias is None:
            if not p.topic:
                await self.conn.protocol_error(
                    "empty topic", ReasonCode.TOPIC_NAME_INVALID)
                return None
            return p.topic
        max_alias = self.settings[Setting.MaxTopicAlias]
        if alias == 0 or alias > max_alias:
            await self.conn.disconnect_with(ReasonCode.TOPIC_ALIAS_INVALID)
            return None
        if p.topic:
            self._recv_topic_alias[alias] = p.topic
            return p.topic
        topic = self._recv_topic_alias.get(alias)
        if topic is None:
            await self.conn.disconnect_with(ReasonCode.PROTOCOL_ERROR)
        return topic

    async def _on_pubrel(self, packet_id: int) -> None:
        self._inbound_qos2.discard(packet_id)
        await self.conn.send(pk.PubComp(packet_id=packet_id))

    async def _check_permission(self, action, topic: str) -> bool:
        """Exception-isolated permission check (≈ the reference's
        auth-provider helper wrapper): a throwing plugin DENIES (fail
        closed) and surfaces ACCESS_CONTROL_ERROR instead of crashing the
        session."""
        try:
            return await self.auth.check_permission(
                self.client_info, action, topic)
        except Exception:  # noqa: BLE001
            log.exception("auth plugin check_permission failed")
            self.events.report(Event(
                EventType.ACCESS_CONTROL_ERROR,
                self.client_info.tenant_id,
                {"action": getattr(action, "value", str(action)),
                 "topic": topic}))
            return False

    # -------- SUBSCRIBE/UNSUBSCRIBE (≈ MQTTSessionHandler.doSubscribe) -----

    async def _on_subscribe(self, s: pk.Subscribe) -> None:
        ts = self.settings
        v5 = self.protocol_level >= PROTOCOL_MQTT5
        if len(s.subscriptions) > ts[Setting.MaxTopicFiltersPerSub]:
            self.events.report(Event(EventType.TOO_LARGE_SUBSCRIPTION,
                                     self.client_info.tenant_id,
                                     {"count": len(s.subscriptions)}))
            await self.conn.protocol_error(
                "too many filters", ReasonCode.QUOTA_EXCEEDED)
            return
        sub_id = None
        if v5 and s.properties:
            sids = s.properties.get(PropertyId.SUBSCRIPTION_IDENTIFIER)
            if sids:
                if not ts[Setting.SubscriptionIdentifierEnabled]:
                    await self.conn.protocol_error(
                        "sub id disabled",
                        ReasonCode.SUBSCRIPTION_IDENTIFIERS_NOT_SUPPORTED)
                    return
                sub_id = sids[0]
        with trace.span("sub.route", tenant=self.client_info.tenant_id,
                        filters=len(s.subscriptions)):
            codes: List[int] = []
            for req in s.subscriptions:
                codes.append(await self._subscribe_one(req, sub_id))
            await self.conn.send(pk.SubAck(packet_id=s.packet_id,
                                           reason_codes=codes))
        self.events.report(Event(EventType.SUB_ACKED,
                                 self.client_info.tenant_id,
                                 {"filters": [r.topic_filter
                                              for r in s.subscriptions]}))

    async def _subscribe_one(self, req: pk.SubscriptionRequest,
                             sub_id: Optional[int]) -> int:
        ts = self.settings
        v5 = self.protocol_level >= PROTOCOL_MQTT5
        tf = req.topic_filter
        from ..utils import sysprops as sp
        tf_bad_utf8 = (sp.get(sp.SysProp.SANITY_CHECK_MQTT_UTF8)
                       and not topic_util.is_well_formed_utf8(tf))
        if tf_bad_utf8 or not topic_util.is_valid_topic_filter(
                tf, ts[Setting.MaxTopicLevelLength],
                ts[Setting.MaxTopicLevels], ts[Setting.MaxTopicLength]):
            # bad UTF-8 → MalformedTopicFilter; structural violation
            # (misplaced wildcard etc.) → InvalidTopicFilter
            self.events.report(Event(
                EventType.MALFORMED_TOPIC_FILTER if tf_bad_utf8
                else EventType.INVALID_TOPIC_FILTER,
                self.client_info.tenant_id, {"filter": tf}))
            return (ReasonCode.TOPIC_FILTER_INVALID if v5 else 0x80)
        if (topic_util.is_wildcard_topic_filter(tf)
                and not ts[Setting.WildcardSubscriptionEnabled]):
            self.events.report(Event(EventType.WILDCARD_SUB_UNSUPPORTED,
                                     self.client_info.tenant_id,
                                     {"filter": tf}))
            return (ReasonCode.WILDCARD_SUBSCRIPTIONS_NOT_SUPPORTED
                    if v5 else 0x80)
        if topic_util.is_shared_subscription(tf):
            if not ts[Setting.SharedSubscriptionEnabled]:
                self.events.report(Event(
                    EventType.SHARED_SUB_UNSUPPORTED,
                    self.client_info.tenant_id, {"filter": tf}))
                return (ReasonCode.SHARED_SUBSCRIPTIONS_NOT_SUPPORTED
                        if v5 else 0x80)
            if v5 and req.no_local:
                # [MQTT-3.8.3-4] shared subscription must not set no-local
                return ReasonCode.PROTOCOL_ERROR
        if len(self.subscriptions) >= ts[Setting.MaxTopicFiltersPerInbox] \
                and tf not in self.subscriptions:
            return ReasonCode.QUOTA_EXCEEDED if v5 else 0x80
        if not self.throttler.has_resource(self.client_info.tenant_id,
                                           self._sub_resource(tf)):
            self.events.report(Event(EventType.OUT_OF_TENANT_RESOURCE,
                                     self.client_info.tenant_id,
                                     {"filter": tf, "resource": "sub"}))
            return ReasonCode.QUOTA_EXCEEDED if v5 else 0x80
        allowed = await self._check_permission(MQTTAction.SUB, tf)
        if not allowed:
            self.events.report(Event(EventType.SUB_ACTION_DISALLOW,
                                     self.client_info.tenant_id,
                                     {"filter": tf}))
            return ReasonCode.NOT_AUTHORIZED if v5 else 0x80
        granted = min(req.qos, ts[Setting.MaximumQoS])
        matcher = RouteMatcher.from_topic_filter(tf)
        old = self.subscriptions.get(tf)
        sub = Subscription(matcher=matcher, qos=granted,
                           no_local=req.no_local,
                           retain_as_published=req.retain_as_published,
                           retain_handling=req.retain_handling,
                           sub_id=sub_id)
        self.subscriptions[tf] = sub
        await self._route(sub)
        # retained delivery (≈ retainClient.match on SUBSCRIBE)
        if (self.retain_service is not None and ts[Setting.RetainEnabled]
                and not topic_util.is_shared_subscription(tf)
                and (req.retain_handling == 0
                     or (req.retain_handling == 1 and old is None))):
            await self._deliver_retained(sub)
        return granted

    async def _deliver_retained(self, sub: Subscription) -> None:
        with trace.span("sub.retained", tenant=self.client_info.tenant_id):
            limit = self.settings[Setting.RetainMessageMatchLimit]
            try:
                matches = await self.retain_service.match(
                    self.client_info.tenant_id,
                    list(sub.matcher.filter_levels), limit)
            except Exception:  # noqa: BLE001 — retain backend failure
                log.exception("retain match failed")
                # ≈ MatchRetainError: the SUBSCRIBE itself stays granted
                self.events.report(Event(
                    EventType.MATCH_RETAIN_ERROR, self.client_info.tenant_id,
                    {"filter": sub.matcher.mqtt_topic_filter}))
                return
            if matches:
                self.events.report(Event(
                    EventType.RETAIN_MSG_MATCHED, self.client_info.tenant_id,
                    {"filter": sub.matcher.mqtt_topic_filter,
                     "count": len(matches)}))
            # what an earlier SUBSCRIBE of this filter left queued is void
            self._discard_retained(sub.matcher.mqtt_topic_filter)
            backlog = self._retained_backlog
            for topic, msg in matches:
                # behind a backlog, or on a full window: wait in line
                if not backlog and await self._send_publish(
                        topic, msg, sub, retained=True) is not BLOCKED:
                    continue
                backlog.append((topic, msg, sub))
                trace.count("retain.deliver.deferred")

    async def _drain_retained(self) -> None:
        """Send the retained backlog, in order, while the window has room;
        a subscription that ended (or was made anew) meanwhile takes its
        queued messages with it."""
        backlog = self._retained_backlog
        while backlog and not self.closed:
            topic, msg, sub = entry = backlog.popleft()
            if self.subscriptions.get(sub.matcher.mqtt_topic_filter) \
                    is not sub:
                continue
            if await self._send_publish(topic, msg, sub,
                                        retained=True) is BLOCKED:
                backlog.appendleft(entry)
                return

    def _discard_retained(self, tf: str) -> None:
        """Drop what is still queued for the subscription of ``tf``."""
        backlog = self._retained_backlog
        kept = [e for e in backlog if e[2].matcher.mqtt_topic_filter != tf]
        if len(kept) != len(backlog):
            backlog.clear()
            backlog.extend(kept)

    # ------- on-behalf management surface (≈ SessionDictService sub/unsub/
    # inboxState, SessionDictService.proto:38-40) -----------------------------

    async def admin_sub(self, tf: str, qos: int) -> str:
        """Subscribe on behalf of this live session (admin/API initiated).
        Returns a SubReply.Result name (lower-case)."""
        prior = self.subscriptions.get(tf)
        if prior is not None and int(prior.qos) == int(qos):
            return "exists"
        req = pk.SubscriptionRequest(topic_filter=tf, qos=qos)
        # _subscribe_one runs the full SUBSCRIBE pipeline including
        # retained delivery under its own guards — nothing extra here
        code = await self._subscribe_one(req, None)
        if code < 0x80:
            return "ok"
        return {
            ReasonCode.QUOTA_EXCEEDED: "exceed_limit",
            ReasonCode.NOT_AUTHORIZED: "not_authorized",
            ReasonCode.TOPIC_FILTER_INVALID: "topic_filter_invalid",
            ReasonCode.WILDCARD_SUBSCRIPTIONS_NOT_SUPPORTED:
                "wildcard_not_supported",
            ReasonCode.SHARED_SUBSCRIPTIONS_NOT_SUPPORTED:
                "shared_subscription_not_supported",
        }.get(code, "error")

    async def admin_unsub(self, tf: str) -> str:
        """Unsubscribe on behalf of this live session. Returns an
        UnsubReply.Result name (lower-case)."""
        if not await self._check_permission(MQTTAction.UNSUB, tf):
            self.events.report(Event(
                EventType.UNSUB_ACTION_DISALLOW,
                self.client_info.tenant_id, {"filter": tf}))
            return "not_authorized"
        sub = self.subscriptions.pop(tf, None)
        if sub is None:
            return "no_sub"
        self._discard_retained(tf)
        await self._unroute(sub)
        return "ok"

    def inbox_state(self) -> dict:
        """Live-session state for the management API (≈ the transient
        InboxState reply of SessionDictService.inboxState)."""
        return {
            "client_id": self.client_id,
            "session_id": self.session_id,
            "subscriptions": {
                tf: {"qos": int(s.qos), "no_local": bool(s.no_local),
                     "retain_as_published": bool(s.retain_as_published),
                     "retain_handling": int(s.retain_handling)}
                for tf, s in self.subscriptions.items()},
            "inflight": len(self._outbound),
            "inbound_qos2": len(self._inbound_qos2),
        }

    async def _on_unsubscribe(self, u: pk.Unsubscribe) -> None:
        v5 = self.protocol_level >= PROTOCOL_MQTT5
        ts = self.settings
        if len(u.topic_filters) > ts[Setting.MaxTopicFiltersPerSub]:
            self.events.report(Event(EventType.TOO_LARGE_UNSUBSCRIPTION,
                                     self.client_info.tenant_id,
                                     {"count": len(u.topic_filters)}))
            await self.conn.protocol_error(
                "too many filters", ReasonCode.QUOTA_EXCEEDED)
            return
        with trace.span("unsub.route", tenant=self.client_info.tenant_id,
                        filters=len(u.topic_filters)):
            codes: List[int] = []
            for tf in u.topic_filters:
                codes.append(await self._unsubscribe_one(tf, v5))
            await self.conn.send(pk.UnsubAck(packet_id=u.packet_id,
                                             reason_codes=codes))
        self.events.report(Event(EventType.UNSUB_ACKED,
                                 self.client_info.tenant_id,
                                 {"filters": u.topic_filters}))

    async def _unsubscribe_one(self, tf: str, v5: bool) -> int:
        # unsub permission check (≈ MQTTSessionHandler checkAndUnsub →
        # UnsubActionDisallow event)
        if not await self._check_permission(MQTTAction.UNSUB, tf):
            self.events.report(Event(
                EventType.UNSUB_ACTION_DISALLOW,
                self.client_info.tenant_id, {"filter": tf}))
            return ReasonCode.NOT_AUTHORIZED if v5 else 0x80
        sub = self.subscriptions.pop(tf, None)
        if sub is None:
            return ReasonCode.NO_SUBSCRIPTION_EXISTED if v5 else 0
        self._discard_retained(tf)
        await self._unroute(sub)
        return ReasonCode.SUCCESS

    async def _route(self, sub: Subscription) -> None:
        """Register the dist route for a new subscription (a consensus write
        on the route table); persistent sessions override (their routes
        target the inbox sub-broker).

        Non-shared transient subs ride the LocalTopicRouter: one SHARED
        route per (server, filter, bucket) with local re-fan-out
        (≈ LocalTopicRouter.java:36) — shared subs keep per-session routes
        because group election must see individual receivers."""
        tf = sub.matcher.mqtt_topic_filter
        router = self._local_router()
        if router is not None and not topic_util.is_shared_subscription(tf):
            if await router.add_local_sub(self.client_info.tenant_id, tf,
                                          self.session_id):
                return
        await self.dist.match(self.client_info.tenant_id, sub.matcher,
                              TRANSIENT_SUB_BROKER_ID, self.session_id,
                              self._deliverer_key())

    async def _unroute(self, sub: Subscription) -> None:
        tf = sub.matcher.mqtt_topic_filter
        router = self._local_router()
        if router is not None and await router.remove_local_sub(
                self.client_info.tenant_id, tf, self.session_id):
            return
        await self.dist.unmatch(self.client_info.tenant_id, sub.matcher,
                                TRANSIENT_SUB_BROKER_ID, self.session_id,
                                self._deliverer_key())

    def _local_router(self):
        broker = getattr(self.conn, "broker", None)
        router = getattr(broker, "local_router", None)
        return router if (router is not None
                          and router.dist is not None) else None

    def _deliverer_key(self) -> str:
        # one deliverer group per session bucket (≈ DeliverersPerMqttServer),
        # prefixed by the broker-instance id so crash sweeps are scoped
        sid = getattr(getattr(self.conn, "broker", None), "server_id", "")
        return f"{sid}|d{hash(self.session_id) % 16}"

    # ---------------- outbound delivery ------------------------------------

    async def deliver(self, pack, match_info: MatchInfo) -> bool:
        """Called by TransientSubBroker; returns False if sub is gone."""
        sub = self.subscriptions.get(match_info.matcher.mqtt_topic_filter)
        if sub is None or self.closed:
            return False
        for pub_pack in pack.packs:
            for msg in pub_pack.messages:
                if sub.no_local and (pub_pack.publisher.meta().get("sessionId")
                                     == self.session_id):
                    continue
                await self._send_publish(pack.topic, msg, sub,
                                         publisher=pub_pack.publisher)
        return True

    def _outbound_alias(self, topic: str):
        """(topic-to-send, extra props): first use of a topic registers an
        alias (full topic + alias property); later uses send the alias
        with an EMPTY topic [MQTT-3.3.2-12]. No eviction — the alias
        space is first-come (the reference's LRU matters only when
        distinct topics exceed the client's cap; beyond it we simply
        stop aliasing)."""
        if not self._send_alias_max:
            return topic, None
        alias = self._send_alias.get(topic)
        if alias is not None:
            return "", {PropertyId.TOPIC_ALIAS: alias}
        if len(self._send_alias) < self._send_alias_max:
            alias = len(self._send_alias) + 1
            self._send_alias[topic] = alias
            return topic, {PropertyId.TOPIC_ALIAS: alias}
        return topic, None

    # transient semantics: a full receive window DROPS QoS>0 messages;
    # persistent sessions override this to pause their fetch loop instead
    _drop_on_recv_max = True

    # outbound socket-buffer bytes beyond which QoS0 pushes are discarded
    # rather than awaited (slow-consumer isolation)
    SEND_BUFFER_HIGH_WATER = 512 * 1024

    # one SLOW_CONSUMER event per continuous above-water episode
    _slow_over_flagged = False

    def _watch_write_buffer(self) -> int:
        """Write-buffer watermark watch (ISSUE 20 satellite): returns
        the outbound buffer size while tracking this connection's
        continuous time above ``SEND_BUFFER_HIGH_WATER``; crossing
        ``BIFROMQ_SLOW_CONSUMER_S`` emits one ``SLOW_CONSUMER`` event
        per episode (cardinality bounded in the e2e plane)."""
        transport = getattr(self.conn.writer, "transport", None)
        if transport is None:
            return 0
        size = transport.get_write_buffer_size()
        over_s = OBS.e2e.note_watermark(
            self.session_id, size > self.SEND_BUFFER_HIGH_WATER)
        if over_s <= 0.0:
            self._slow_over_flagged = False
        elif (not self._slow_over_flagged
              and over_s >= env_float("BIFROMQ_SLOW_CONSUMER_S", 1.0)):
            self._slow_over_flagged = True
            OBS.e2e.slow_consumer_events += 1
            self.events.report(Event(
                EventType.SLOW_CONSUMER, self.client_info.tenant_id,
                {"client_id": self.client_id, "buffer_bytes": size,
                 "over_s": round(over_s, 3)}))
        return size

    async def _send_publish(self, topic: str, msg: Message,
                            sub: Subscription, retained: bool = False,
                            publisher=None):
        """Returns None (sent as qos0), the packet id (sent qos>0), or
        ``BLOCKED`` (receive-maximum / packet-id window exhausted).
        ``publisher`` is the originating ClientInfo when the caller knows
        it (live fan-out); None on retained/inbox replay."""
        qos = min(int(msg.pub_qos), sub.qos)
        # ISSUE 20: delivery-path attribution for the e2e plane. The
        # contextvar carries what only the entry point knows (remote RPC
        # hop, inbox replay); retained/shared-sub are decided right here.
        e2e_path = DELIVERY_PATH.get()
        if e2e_path == "local_fanout":
            if retained:
                e2e_path = "retained"
            elif sub.matcher is not None and sub.matcher.is_shared:
                e2e_path = "shared_sub"
        tenant = self.client_info.tenant_id
        remaining_expiry = None
        if msg.expiry_seconds != 0xFFFFFFFF:
            # [MQTT-3.3.2-5]: drop once the expiry interval has elapsed;
            # [MQTT-3.3.2-6]: forward the REMAINING interval to receivers
            elapsed_s = max(0, HLC.INST.physical(HLC.INST.get())
                            - HLC.INST.physical(msg.timestamp)) / 1000.0
            remaining_expiry = msg.expiry_seconds - elapsed_s
            if remaining_expiry <= 0:
                self.events.report(Event(
                    EventType.QOS0_DROPPED if qos == 0 else
                    (EventType.QOS1_DROPPED if qos == 1
                     else EventType.QOS2_DROPPED),
                    self.client_info.tenant_id,
                    {"topic": topic, "reason": "message_expired"}))
                OBS.record_delivery_violation(tenant, qos, "expired")
                return None
        retain_flag = (retained if not sub.retain_as_published
                       else (msg.is_retain or retained))
        # ≈ IUserPropsCustomizer.outbound — extra props stamped at the push
        # edge, counted against Maximum Packet Size like any other property.
        # v3 subscribers carry no properties on the wire: skip the SPI call
        # entirely on their (hot) push path
        out_extra = ()
        if self.protocol_level >= PROTOCOL_MQTT5:
            try:
                out_extra = tuple(self.user_props_customizer.outbound(
                    topic, msg, publisher,
                    sub.matcher.mqtt_topic_filter if sub.matcher else "",
                    self.client_info, HLC.INST.get()))
            except Exception:  # noqa: BLE001 — SPI failure ≠ dropped push
                log.exception("user-props customizer outbound failed")
                out_extra = ()
        props = None
        if self.protocol_level >= PROTOCOL_MQTT5:
            props = {}
            if remaining_expiry is not None:
                props[PropertyId.MESSAGE_EXPIRY_INTERVAL] = max(
                    1, int(remaining_expiry))
            if sub.sub_id is not None:
                props[PropertyId.SUBSCRIPTION_IDENTIFIER] = [sub.sub_id]
            if msg.user_properties or out_extra:
                props[PropertyId.USER_PROPERTY] = (
                    list(msg.user_properties) + list(out_extra))
            if msg.content_type:
                props[PropertyId.CONTENT_TYPE] = msg.content_type
            if msg.response_topic:
                props[PropertyId.RESPONSE_TOPIC] = msg.response_topic
            if msg.correlation_data:
                props[PropertyId.CORRELATION_DATA] = msg.correlation_data
            if msg.payload_format_indicator:
                props[PropertyId.PAYLOAD_FORMAT_INDICATOR] = \
                    msg.payload_format_indicator
            if not props:
                props = None
        # [MQTT-3.1.2-25]: never send a packet beyond the client's announced
        # Maximum Packet Size — drop it and record the event (≈
        # OversizePacketDropped.java). The probe encodes the full topic plus
        # a margin for a possible TOPIC_ALIAS property (the registration
        # send carries BOTH the topic and the alias, so it can only be
        # larger); packets nowhere near the cap skip the probe encode.
        props_est = 0
        if props:
            # forwarded properties are unbounded (user props, correlation
            # data...) — they must count toward the skip heuristic. String
            # lengths are CHARS; count 4 bytes each (UTF-8 worst case) so
            # non-ASCII content can only make the estimate conservative —
            # a too-low estimate would skip the exact probe and let an
            # oversize packet through.
            # per-property wire overhead: a user property costs an id byte
            # plus TWO 2-byte length prefixes (5B/pair beyond the chars),
            # string/bytes properties an id byte plus one prefix (3B) —
            # count 8 per property so hundreds of tiny properties cannot
            # erode the fixed margin below
            props_est = sum(
                8 + 4 * (len(k) + len(v)) for k, v in (
                    props.get(PropertyId.USER_PROPERTY) or ())) \
                + (8 + 4 * len(msg.content_type) if msg.content_type else 0) \
                + (8 + 4 * len(msg.response_topic)
                   if msg.response_topic else 0) \
                + (8 + len(msg.correlation_data)
                   if msg.correlation_data else 0)
        if self._client_max_packet and (
                len(msg.payload) + 4 * len(topic) + props_est + 512
                >= self._client_max_packet):
            from .codec import encode as _encode
            probe = pk.Publish(topic=topic, payload=msg.payload, qos=qos,
                               retain=retain_flag,
                               packet_id=1 if qos else None,
                               properties=props)
            alias_margin = 8 if self._send_alias_max else 0
            if len(_encode(probe, self.protocol_level)) + alias_margin \
                    > self._client_max_packet:
                self.events.report(Event(
                    EventType.OVERSIZE_PACKET_DROPPED,
                    self.client_info.tenant_id,
                    {"topic": topic, "limit": self._client_max_packet}))
                OBS.record_delivery_violation(tenant, qos, "oversize")
                return None

        def aliased(base_props):
            # resolved at SEND time only: a blocked publish must not
            # consume an alias the client never learns. ``topic`` (the
            # original) stays intact for event reporting.
            wire_topic, alias_props = self._outbound_alias(topic)
            if alias_props:
                out = dict(base_props or {})
                out.update(alias_props)
                return wire_topic, out
            return wire_topic, base_props

        if qos == 0:
            # unwritable channel → DROP the QoS0 push instead of awaiting
            # drain: one slow consumer must never stall the fan-out loop
            # for its siblings (≈ MQTTTransientSessionHandler's
            # channel-writability drop + Discard event)
            if self._watch_write_buffer() > self.SEND_BUFFER_HIGH_WATER:
                self.events.report(Event(
                    EventType.DISCARD, self.client_info.tenant_id,
                    {"topic": topic, "client_id": self.client_id,
                     "reason": "channel_unwritable"}))
                OBS.record_delivery_violation(tenant, 0, "discard")
                return None
            wire_topic, wprops = aliased(props)
            await self.conn.send(pk.Publish(topic=wire_topic,
                                            payload=msg.payload,
                                            qos=0, retain=retain_flag,
                                            properties=wprops))
            self.events.report(Event(EventType.QOS0_PUSHED,
                                     self.client_info.tenant_id,
                                     {"topic": topic}))
            self.events.report(Event(EventType.DELIVERED,
                                     self.client_info.tenant_id,
                                     {"topic": topic, "qos": 0}))
            # ISSUE 20: full-population publish→socket-write latency
            OBS.record_delivery(tenant, 0, e2e_path, msg.timestamp)
            return None
        pid = None
        if self._recv_quota.has_room(len(self._outbound)):
            pid = self._pid_alloc.alloc()
        if pid is None:
            # a retained message is not dropped: _deliver_retained queues it
            if self._drop_on_recv_max and not retained:
                dropped = (EventType.QOS1_DROPPED if qos == 1
                           else EventType.QOS2_DROPPED)
                self.events.report(Event(dropped,
                                         self.client_info.tenant_id,
                                         {"topic": topic,
                                          "reason": "recv_max"}))
                OBS.record_delivery_violation(tenant, qos, "recv_max")
            return BLOCKED
        self._watch_write_buffer()
        wire_topic, wprops = aliased(props)
        publish = pk.Publish(topic=wire_topic, payload=msg.payload, qos=qos,
                             retain=retain_flag, packet_id=pid,
                             properties=wprops)
        self._outbound[pid] = _OutboundQoS(packet_id=pid, publish=publish,
                                           phase=1,
                                           sent_at=time.monotonic())
        try:
            await self.conn.send(publish)
        except (ConnectionError, OSError) as e:
            # ≈ QoS1PushError / QoS2PushError: the write failed; the
            # in-flight record stays for redelivery on reconnect
            self.events.report(Event(
                EventType.QOS1_PUSH_ERROR if qos == 1
                else EventType.QOS2_PUSH_ERROR,
                self.client_info.tenant_id,
                {"topic": topic, "detail": type(e).__name__}))
            return pid
        self.events.report(Event(
            EventType.QOS1_PUSHED if qos == 1 else EventType.QOS2_PUSHED,
            self.client_info.tenant_id, {"topic": topic}))
        self.events.report(Event(EventType.DELIVERED,
                                 self.client_info.tenant_id,
                                 {"topic": topic, "qos": qos}))
        # ISSUE 20: full-population publish→socket-write latency
        OBS.record_delivery(tenant, qos, e2e_path, msg.timestamp)
        return pid

    def _on_puback(self, pid: int) -> None:
        st = self._outbound.pop(pid, None)
        if st is None:
            self.events.report(Event(EventType.PUB_ACK_DROPPED,
                                     self.client_info.tenant_id,
                                     {"packet_id": pid}))
            return
        self._pid_alloc.release(pid)
        if st.sent_at:
            self._recv_quota.on_ack(time.monotonic() - st.sent_at)
        if st.publish.qos == 1:
            self.events.report(Event(EventType.QOS1_CONFIRMED,
                                     self.client_info.tenant_id,
                                     {"packet_id": pid}))
        self.events.report(Event(EventType.PUB_ACKED,
                                 self.client_info.tenant_id,
                                 {"packet_id": pid}))

    async def _on_pubrec(self, pid: int) -> None:
        st = self._outbound.get(pid)
        if st is None or st.publish.qos != 2:
            self.events.report(Event(EventType.PUB_REC_DROPPED,
                                     self.client_info.tenant_id,
                                     {"packet_id": pid}))
            await self.conn.send(pk.PubRel(packet_id=pid))
            return
        if st.phase != 2:       # retransmitted PUBREC: report once
            if st.sent_at:
                self._recv_quota.on_ack(time.monotonic() - st.sent_at)
            self.events.report(Event(EventType.PUB_RECED,
                                     self.client_info.tenant_id,
                                     {"packet_id": pid}))
        st.phase = 2
        await self.conn.send(pk.PubRel(packet_id=pid))

    def _on_pubcomp(self, pid: int) -> None:
        st = self._outbound.pop(pid, None)
        if st is not None:
            self._pid_alloc.release(pid)
            if st.publish.qos == 2:
                self.events.report(Event(EventType.QOS2_CONFIRMED,
                                         self.client_info.tenant_id,
                                         {"packet_id": pid}))

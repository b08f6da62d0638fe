"""Asyncio MQTT broker frontend (≈ bifromq-mqtt MQTTBroker + handler pipeline).

Connection lifecycle mirrors the reference Netty pipeline
(MQTTBroker.java:177-240 → MQTTPreludeHandler.java:58 → MQTT{3,5}ConnectHandler
→ session handler swap): wait for CONNECT with a timeout, authenticate via the
plugin, resolve tenant settings, register the session (kicking any previous
owner), then dispatch packets into the session until close. Keep-alive
enforcement closes connections silent for 1.5× the negotiated interval.
"""

from __future__ import annotations

import asyncio
import logging
import time
import uuid
from typing import Optional

from .. import trace
from ..dist.service import DistService
from ..plugin.auth import (AllowAllAuthProvider, AuthData, IAuthProvider,
                           MQTTAction)
from ..plugin.events import (CollectingEventCollector, Event, EventType,
                             IEventCollector)
from ..plugin.settings import (DefaultSettingProvider, ISettingProvider,
                               Setting, TenantSettings)
from ..plugin.subbroker import SubBrokerRegistry
from ..types import ClientInfo
from ..utils import topic as topic_util
from . import packets as pk
from .codec import StreamDecoder, encode, topic_bytes_enabled
from .protocol import (CONNACK_ACCEPTED, CONNACK_REFUSED_IDENTIFIER_REJECTED,
                       CONNACK_REFUSED_NOT_AUTHORIZED,
                       CONNACK_REFUSED_SERVER_UNAVAILABLE, PROTOCOL_MQTT5,
                       MalformedPacket, PropertyId, ReasonCode)
from .session import (LocalSessionRegistry, Session, SessionRegistry,
                      SessionStartAborted, TransientSubBroker)

log = logging.getLogger("bifromq_tpu.mqtt")

CONNECT_TIMEOUT = 10.0  # ≈ MQTTPreludeHandler timeout


def _lift_write_buffer_limit(writer: asyncio.StreamWriter) -> None:
    """Raise the transport's pause threshold ABOVE the session's QoS0
    discard watermark: drain() must never block the fan-out loop before
    the slow-consumer discard check can fire. Derived (2x) from the one
    constant so the two can't drift apart."""
    try:
        writer.transport.set_write_buffer_limits(
            high=2 * Session.SEND_BUFFER_HIGH_WATER)
    except (AttributeError, RuntimeError):
        pass


class Connection:
    """One client transport; owns the write side and the decode loop."""

    def __init__(self, broker: "MQTTBroker", reader: asyncio.StreamReader,
                 writer: asyncio.StreamWriter,
                 peer_addr=None) -> None:
        self.broker = broker
        self.reader = reader
        self.writer = writer
        # ISSUE 12: server ingress keeps PUBLISH topics as wire bytes
        self.decoder = StreamDecoder(raw_pub_topic=topic_bytes_enabled())
        self.session: Optional[Session] = None
        self.protocol_level = 4
        self._closed = False
        self._pending_packets: list = []
        # the REAL client address: the proxy-protocol stage overrides the
        # socket peername when a load balancer fronts the listener
        self.peer_addr = (peer_addr if peer_addr is not None
                          else writer.get_extra_info("peername"))

    # ------------- write side ---------------------------------------------

    async def send(self, packet) -> None:
        if self._closed:
            return
        try:
            self.writer.write(encode(packet, self.protocol_level))
            await self.writer.drain()
        except (ConnectionError, RuntimeError):
            self._closed = True

    async def protocol_error(self, msg: str,
                             reason: int = ReasonCode.PROTOCOL_ERROR) -> None:
        log.debug("protocol error: %s", msg)
        tenant = (self.session.client_info.tenant_id
                  if self.session is not None else "")
        self.broker.events.report(Event(EventType.PROTOCOL_VIOLATION,
                                        tenant, {"detail": msg}))
        await self.disconnect_with(reason)

    async def disconnect_with(self, reason: int) -> None:
        if self.protocol_level >= PROTOCOL_MQTT5:
            await self.send(pk.Disconnect(reason_code=reason))
        if self.session is not None:
            await self.session.close(fire_will=True)
        else:
            await self.close_transport()

    async def close_transport(self) -> None:
        if not self._closed:
            self._closed = True
            try:
                self.writer.close()
            except Exception:  # noqa: BLE001
                pass

    # ------------- read loop ----------------------------------------------

    async def run(self) -> None:
        try:
            await self._prelude()
            if self.session is None:
                return
            while not self._closed and not self.session.closed:
                timeout = None
                if self.session.keep_alive:
                    timeout = self.session.keep_alive * 1.5
                try:
                    data = await asyncio.wait_for(self.reader.read(65536),
                                                  timeout=timeout)
                except asyncio.TimeoutError:
                    self.broker.events.report(Event(
                        EventType.IDLE,
                        self.session.client_info.tenant_id,
                        {"client_id": self.session.client_id}))
                    self.broker.events.report(Event(
                        EventType.CLIENT_DISCONNECTED,
                        self.session.client_info.tenant_id,
                        {"reason": "keepalive_timeout"}))
                    await self.session.close(fire_will=True)
                    return
                if not data:
                    await self.session.close(fire_will=True)
                    return
                with trace.span("mqtt.decode"):
                    packets = self.decoder.feed(data)
                for packet in packets:
                    if isinstance(packet, pk.Connect):
                        await self.protocol_error("duplicate CONNECT")
                        return
                    await self.session.handle(packet)
                    if self.session.closed:
                        # e.g. DISCONNECT followed by more packets in the
                        # same TCP chunk: drop the remainder
                        return
        except MalformedPacket as e:
            if self.session is not None:
                # undecodable packet mid-session (≈ BadPacket close event)
                self.broker.events.report(Event(
                    EventType.BAD_PACKET,
                    self.session.client_info.tenant_id,
                    {"detail": str(e)}))
                await self.disconnect_with(e.reason)
            else:
                self.broker.events.report(Event(
                    EventType.CHANNEL_ERROR, "", {"detail": str(e)}))
                await self.close_transport()
        except (ConnectionError, asyncio.IncompleteReadError) as e:
            if self.session is not None:
                self.broker.events.report(Event(
                    EventType.CLIENT_CHANNEL_ERROR,
                    self.session.client_info.tenant_id,
                    {"detail": type(e).__name__}))
                await self.session.close(fire_will=True)
            else:
                self.broker.events.report(Event(
                    EventType.CHANNEL_ERROR, "",
                    {"detail": type(e).__name__}))
        except SessionStartAborted:
            # session reported its own close event (e.g.
            # INBOX_TRANSIENT_ERROR) and shut the transport — unwind quietly
            pass
        except Exception:  # noqa: BLE001
            log.exception("connection crashed")
            if self.session is not None:
                await self.session.close(fire_will=True)
            await self.close_transport()
        finally:
            await self.close_transport()

    async def _prelude(self) -> None:
        """Wait for the first packet; it must be CONNECT (prelude handler)."""
        buf_packets = []
        try:
            while not buf_packets:
                data = await asyncio.wait_for(self.reader.read(65536),
                                              timeout=CONNECT_TIMEOUT)
                if not data:
                    await self.close_transport()
                    return
                buf_packets = self.decoder.feed(data)
        except asyncio.TimeoutError:
            # no CONNECT within the prelude window (≈ ConnectTimeout)
            self.broker.events.report(Event(EventType.CONNECT_TIMEOUT,
                                            "", {}))
            await self.close_transport()
            return
        except MalformedPacket as e:
            self.broker.events.report(Event(
                EventType.CHANNEL_ERROR, "", {"detail": str(e)}))
            await self.close_transport()
            return
        first = buf_packets[0]
        if not isinstance(first, pk.Connect):
            # first packet must be CONNECT (≈ ProtocolError close event)
            self.broker.events.report(Event(
                EventType.PROTOCOL_ERROR, "",
                {"detail": "first packet not CONNECT"}))
            await self.close_transport()
            return
        self.protocol_level = first.protocol_level
        # packets pipelined behind CONNECT are visible to the enhanced-auth
        # exchange (_next_packet) and flushed to the session afterwards
        self._pending_packets = buf_packets[1:]
        await self._on_connect(first)
        if self.session is not None:
            while self._pending_packets:
                await self.session.handle(self._pending_packets.pop(0))
                if self.session.closed:
                    return

    async def _next_packet(self, timeout: float = 10.0):
        """Read the next single packet during a pre-CONNACK exchange."""
        if self._pending_packets:
            return self._pending_packets.pop(0)
        deadline = asyncio.get_event_loop().time() + timeout
        while True:
            remain = deadline - asyncio.get_event_loop().time()
            if remain <= 0:
                return None
            try:
                data = await asyncio.wait_for(self.reader.read(65536),
                                              remain)
            except asyncio.TimeoutError:
                return None
            if not data:
                return None
            pkts = self.decoder.feed(data)
            if pkts:
                self._pending_packets = pkts[1:]
                return pkts[0]

    async def _extended_auth_exchange(self, c: pk.Connect, method: str):
        """MQTT5 enhanced auth: run the provider's AUTH challenge loop
        before CONNACK; returns an AuthResult or None (closed)."""
        from ..plugin.auth import AuthResult, ExtAuthData

        broker = self.broker
        peer = str(self.peer_addr)
        step = ExtAuthData(
            client_id=c.client_id, method=method,
            data=(c.properties or {}).get(PropertyId.AUTHENTICATION_DATA,
                                          b""),
            remote_addr=peer)
        for _ in range(8):  # bounded exchange rounds
            res = await broker.auth.extended_auth(step)
            if res.kind == "fail":
                # method-unsupported vs credential failure carry distinct
                # MQTT5 reason codes ([MQTT-4.12])
                rc = (ReasonCode.BAD_AUTHENTICATION_METHOD if res.bad_method
                      else ReasonCode.NOT_AUTHORIZED)
                await self.send(pk.Connack(reason_code=rc))
                broker.events.report(Event(EventType.CONNECT_REJECTED, "",
                                           {"reason": res.reason}))
                await self.close_transport()
                return None
            if res.kind == "success":
                self.auth_method = method
                # CONNACK must echo the method (+ any final server proof)
                self.auth_success_data = res.data
                return AuthResult.success(res.tenant_id, res.user_id)
            props = {PropertyId.AUTHENTICATION_METHOD: method}
            if res.data:
                props[PropertyId.AUTHENTICATION_DATA] = res.data
            await self.send(pk.Auth(
                reason_code=ReasonCode.CONTINUE_AUTHENTICATION,
                properties=props))
            reply = await self._next_packet()
            if isinstance(reply, pk.Disconnect):
                # client aborted the exchange with DISCONNECT [MQTT-4.12.4]
                broker.events.report(Event(
                    EventType.ENHANCED_AUTH_ABORT_BY_CLIENT, "",
                    {"client_id": c.client_id, "method": method}))
                await self.close_transport()
                return None
            if not isinstance(reply, pk.Auth) or (reply.properties or {}).get(
                    PropertyId.AUTHENTICATION_METHOD) != method:
                await self.close_transport()
                return None
            step = ExtAuthData(
                client_id=c.client_id, method=method,
                data=(reply.properties or {}).get(
                    PropertyId.AUTHENTICATION_DATA, b""),
                remote_addr=peer)
        await self.close_transport()
        return None

    async def _on_connect(self, c: pk.Connect) -> None:
        broker = self.broker
        v5 = c.protocol_level >= PROTOCOL_MQTT5
        peer = self.peer_addr
        if (v5 and c.properties
                and c.properties.get(PropertyId.MAXIMUM_PACKET_SIZE) == 0):
            # MQTT5 3.1.2.11.4: a zero Maximum Packet Size is a Protocol
            # Error — it must not be read as "no limit"
            broker.events.report(Event(EventType.PROTOCOL_VIOLATION, "",
                                       {"reason": "max_packet_size_0"}))
            await self.send(pk.Connack(
                reason_code=ReasonCode.PROTOCOL_ERROR))
            await self.close_transport()
            return
        auth_method = None
        if v5 and c.properties:
            auth_method = c.properties.get(PropertyId.AUTHENTICATION_METHOD)
        if auth_method is not None:
            # MQTT5 enhanced auth: AUTH-packet exchange before CONNACK
            # (≈ MQTT5ConnectHandler + ReAuthenticator SPI flow)
            auth_result = await self._extended_auth_exchange(c, auth_method)
            if auth_result is None:
                return  # exchange failed; connection already closed
        else:
            try:
                auth_result = await broker.auth.auth(AuthData(
                    client_id=c.client_id, protocol_level=c.protocol_level,
                    username=c.username, password=c.password,
                    remote_addr=str(peer)))
            except Exception:  # noqa: BLE001 — plugin failure ≠ crash
                log.exception("auth provider failed")
                broker.events.report(Event(EventType.AUTH_ERROR, "",
                                           {"client_id": c.client_id}))
                rc = (ReasonCode.UNSPECIFIED_ERROR if v5
                      else CONNACK_REFUSED_NOT_AUTHORIZED)
                await self.send(pk.Connack(reason_code=rc))
                await self.close_transport()
                return
        if not auth_result.ok:
            rc = (ReasonCode.NOT_AUTHORIZED if v5
                  else CONNACK_REFUSED_NOT_AUTHORIZED)
            await self.send(pk.Connack(reason_code=rc))
            # ≈ UnauthenticatedClient vs NotAuthorizedClient close events
            # (reject code from the auth provider, Reject.Code analog)
            etype = (EventType.NOT_AUTHORIZED_CLIENT
                     if getattr(auth_result, "code", "") == "not_authorized"
                     else EventType.UNAUTHENTICATED_CLIENT)
            broker.events.report(Event(etype, "",
                                       {"reason": auth_result.reason}))
            broker.events.report(Event(EventType.CONNECT_REJECTED, "",
                                       {"reason": auth_result.reason}))
            await self.close_transport()
            return

        tenant_id = auth_result.tenant_id
        # TotalConnections quota (≈ MQTTConnectHandler.java:134-146)
        from ..plugin.throttler import TenantResourceType
        if not broker.throttler.has_resource(
                tenant_id, TenantResourceType.TOTAL_CONNECTIONS):
            rc = ReasonCode.QUOTA_EXCEEDED if v5 else 3
            await self.send(pk.Connack(reason_code=rc))
            broker.events.report(Event(
                EventType.OUT_OF_TENANT_RESOURCE, tenant_id,
                {"resource": "total_connections"}))
            # the channel-close reason twin (≈ ResourceThrottled)
            broker.events.report(Event(
                EventType.RESOURCE_THROTTLED, tenant_id,
                {"resource": "total_connections"}))
            await self.close_transport()
            return
        redirect = broker.balancer.need_redirect(ClientInfo(
            tenant_id=tenant_id, type="MQTT",
            metadata=(("clientId", c.client_id),)))
        if redirect is not None:
            # server redirection (≈ IClientBalancer → MQTT5 Server Reference)
            broker.events.report(Event(
                EventType.SERVER_REDIRECTED, tenant_id,
                {"server_reference": redirect.server_reference}))
            from ..plugin.balancer import RedirectType
            if v5:
                rc = (ReasonCode.SERVER_MOVED
                      if redirect.type == RedirectType.MOVE
                      else ReasonCode.USE_ANOTHER_SERVER)
                props = ({PropertyId.SERVER_REFERENCE:
                          redirect.server_reference}
                         if redirect.server_reference else None)
                await self.send(pk.Connack(reason_code=rc,
                                           properties=props))
            else:
                await self.send(pk.Connack(reason_code=3))
            await self.close_transport()
            return
        settings = TenantSettings.resolve(broker.settings, tenant_id)
        enabled = {3: Setting.MQTT3Enabled, 4: Setting.MQTT4Enabled,
                   5: Setting.MQTT5Enabled}[c.protocol_level]
        if not settings[enabled]:
            broker.events.report(Event(
                EventType.UNACCEPTED_PROTOCOL_VER, tenant_id,
                {"ver": c.protocol_level}))
            rc = (ReasonCode.UNSUPPORTED_PROTOCOL_VERSION if v5 else 1)
            await self.send(pk.Connack(reason_code=rc))
            await self.close_transport()
            return

        client_id = c.client_id
        assigned = None
        # length + UTF-8 sanity guards (≈ MaxMqtt3/5ClientIdLength,
        # SanityCheckMqttUtf8String sysprops)
        from ..utils import sysprops as sp
        max_cid = sp.get(sp.SysProp.MAX_MQTT5_CLIENT_ID_LENGTH if v5
                         else sp.SysProp.MAX_MQTT3_CLIENT_ID_LENGTH)
        bad_utf8 = (sp.get(sp.SysProp.SANITY_CHECK_MQTT_UTF8)
                    and not topic_util.is_well_formed_utf8(client_id))
        if len(client_id.encode()) > max_cid or bad_utf8:
            # length → IdentifierRejected; malformed UTF-8 →
            # MalformedClientIdentifier (distinct reference close events)
            broker.events.report(Event(
                EventType.MALFORMED_CLIENT_IDENTIFIER if bad_utf8
                else EventType.IDENTIFIER_REJECTED, tenant_id,
                {"length": len(client_id),
                 "reason": "malformed" if bad_utf8 else "too_long"}))
            await self.send(pk.Connack(reason_code=(
                ReasonCode.CLIENT_IDENTIFIER_NOT_VALID if v5
                else CONNACK_REFUSED_IDENTIFIER_REJECTED)))
            await self.close_transport()
            return
        if not client_id:
            if not c.clean_start and not v5:
                broker.events.report(Event(
                    EventType.IDENTIFIER_REJECTED, tenant_id, {}))
                await self.send(pk.Connack(
                    reason_code=CONNACK_REFUSED_IDENTIFIER_REJECTED))
                await self.close_transport()
                return
            client_id = assigned = uuid.uuid4().hex

        client_info = ClientInfo(
            tenant_id=tenant_id, type="MQTT",
            metadata=tuple(sorted({
                "clientId": client_id,
                "userId": auth_result.user_id,
                "ver": str(c.protocol_level),
                **auth_result.attrs,
            }.items())))

        if (c.username is not None
                and sp.get(sp.SysProp.SANITY_CHECK_MQTT_UTF8)
                and not topic_util.is_well_formed_utf8(c.username)):
            broker.events.report(Event(
                EventType.MALFORMED_USERNAME, tenant_id, {}))
            await self.send(pk.Connack(reason_code=(
                ReasonCode.MALFORMED_PACKET if v5
                else CONNACK_REFUSED_NOT_AUTHORIZED)))
            await self.close_transport()
            return
        if (c.will is not None
                and (not topic_util.is_valid_topic(
                        c.will.topic, settings[Setting.MaxTopicLevelLength],
                        settings[Setting.MaxTopicLevels],
                        settings[Setting.MaxTopicLength])
                     or (sp.get(sp.SysProp.SANITY_CHECK_MQTT_UTF8)
                         and not topic_util.is_well_formed_utf8(
                             c.will.topic)))):
            broker.events.report(Event(
                EventType.MALFORMED_WILL_TOPIC, tenant_id,
                {"topic": c.will.topic}))
            await self.send(pk.Connack(reason_code=(
                ReasonCode.TOPIC_NAME_INVALID if v5
                else CONNACK_REFUSED_NOT_AUTHORIZED)))
            await self.close_transport()
            return
        if (c.will is not None and len(c.will.payload)
                > settings[Setting.MaxLastWillBytes]):
            broker.events.report(Event(
                EventType.OVERSIZE_WILL_REJECTED, tenant_id,
                {"bytes": len(c.will.payload)}))
            await self.send(pk.Connack(reason_code=(
                ReasonCode.PACKET_TOO_LARGE if v5
                else CONNACK_REFUSED_NOT_AUTHORIZED)))
            await self.close_transport()
            return

        keep_alive = c.keep_alive
        min_ka = settings[Setting.MinKeepAliveSeconds]
        server_keep_alive = None
        if keep_alive and keep_alive < min_ka:
            keep_alive = min_ka
            server_keep_alive = min_ka

        # persistent vs transient (≈ setupTransient/PersistentSessionHandler,
        # MQTTConnectHandler.java:166-200): v5 uses the session-expiry
        # property; v3/v4 use cleanSession=false; ForceTransient overrides.
        session_expiry = 0
        if v5:
            session_expiry = int((c.properties or {}).get(
                PropertyId.SESSION_EXPIRY_INTERVAL, 0))
        elif not c.clean_start:
            session_expiry = settings[Setting.MaxSessionExpirySeconds]
        requested_expiry = session_expiry
        if session_expiry:
            session_expiry = max(session_expiry,
                                 settings[Setting.MinSessionExpirySeconds])
        session_expiry = min(session_expiry,
                             settings[Setting.MaxSessionExpirySeconds])
        persistent = session_expiry > 0 and not settings[
            Setting.ForceTransient]
        if (not persistent and v5 and not c.clean_start
                and not settings[Setting.ForceTransient]
                and broker.inbox.store.exists(tenant_id, client_id)):
            # [MQTT-3.1.2-5]: Clean Start 0 resumes existing session state
            # even with session-expiry 0 — the session then ends at
            # network disconnect (expiry 0 deletes on close)
            persistent = True
        if persistent and broker.inbox.store.exists(tenant_id, client_id):
            # ISSUE 15 satellite (ROADMAP retained (d)): a RESUMING
            # persistent session triggers a catch-up drain — under a
            # clustered reconnect storm, a broker whose drain pool is
            # saturated while peers gossip quieter pressure refuses the
            # reconnect so the client's retry lands on a quieter peer
            governor = getattr(broker.inbox, "drain_governor", None)
            if governor is not None and governor.should_shed_reconnect():
                broker.events.report(Event(
                    EventType.SERVER_BUSY, tenant_id,
                    {"reason": "drain_shed",
                     "clientId": client_id}))
                await self.send(pk.Connack(reason_code=(
                    ReasonCode.SERVER_BUSY if v5
                    else CONNACK_REFUSED_SERVER_UNAVAILABLE)))
                await self.close_transport()
                return

        common = dict(
            conn=self, client_id=client_id, client_info=ClientInfo(
                tenant_id=tenant_id, type="MQTT",
                metadata=client_info.metadata + (("sessionId", ""),)),
            protocol_level=c.protocol_level, clean_start=c.clean_start,
            keep_alive=keep_alive, will=c.will, settings=settings,
            dist=broker.dist, auth=broker.auth, events=broker.events,
            local_registry=broker.local_sessions,
            session_registry=broker.session_registry,
            connect_props=c.properties,
            retain_service=broker.retain_service,
            throttler=broker.throttler,
            auth_method=getattr(self, "auth_method", None),
            user_props_customizer=broker.user_props_customizer)
        if persistent:
            from .persistent import PersistentSession
            session = PersistentSession(inbox=broker.inbox,
                                        expiry_seconds=session_expiry,
                                        **common)
        else:
            # clean-start semantics: a transient connect discards any
            # existing persistent state for this client id (inbox + routes)
            await broker.inbox.delete(tenant_id, client_id)
            session = Session(**common)
        # bake the session id into publisher identity (no_local support)
        session.client_info = ClientInfo(
            tenant_id=tenant_id, type="MQTT",
            metadata=client_info.metadata + (
                ("sessionId", session.session_id),))
        self.session = session
        await session.start()

        props = None
        if v5:
            props = {
                PropertyId.TOPIC_ALIAS_MAXIMUM:
                    settings[Setting.MaxTopicAlias],
                PropertyId.SHARED_SUBSCRIPTION_AVAILABLE:
                    1 if settings[Setting.SharedSubscriptionEnabled] else 0,
                PropertyId.WILDCARD_SUBSCRIPTION_AVAILABLE:
                    1 if settings[Setting.WildcardSubscriptionEnabled] else 0,
                PropertyId.RETAIN_AVAILABLE:
                    1 if settings[Setting.RetainEnabled] else 0,
                PropertyId.MAXIMUM_QOS: settings[Setting.MaximumQoS],
                PropertyId.RECEIVE_MAXIMUM:
                    settings[Setting.ReceivingMaximum],
            }
            if assigned:
                props[PropertyId.ASSIGNED_CLIENT_IDENTIFIER] = assigned
            if session_expiry != requested_expiry:
                # [MQTT-3.2.2.3.2]: a server using a different Session
                # Expiry Interval MUST advertise it in the CONNACK
                props[PropertyId.SESSION_EXPIRY_INTERVAL] = session_expiry
            if server_keep_alive is not None:
                props[PropertyId.SERVER_KEEP_ALIVE] = server_keep_alive
            if getattr(self, "auth_method", None) is not None:
                # [MQTT-4.12]: CONNACK echoes the method (+ final proof)
                props[PropertyId.AUTHENTICATION_METHOD] = self.auth_method
                if getattr(self, "auth_success_data", b""):
                    props[PropertyId.AUTHENTICATION_DATA] = \
                        self.auth_success_data
        session_present = bool(getattr(session, "session_present", False)
                               and not c.clean_start)
        await self.send(pk.Connack(session_present=session_present,
                                   reason_code=CONNACK_ACCEPTED,
                                   properties=props))
        broker.events.report(Event(EventType.CLIENT_CONNECTED, tenant_id,
                                   {"client_id": client_id}))


class MQTTBroker:
    """The broker process: listeners + shared services (≈ StandaloneStarter
    wiring for the mqtt-server role, SURVEY.md §3.1)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 1883, *,
                 auth: Optional[IAuthProvider] = None,
                 settings: Optional[ISettingProvider] = None,
                 events: Optional[IEventCollector] = None,
                 dist: Optional[DistService] = None,
                 retain_service=None, inbox_engine=None,
                 dist_worker_kwargs=None,
                 inbox_split_threshold: Optional[int] = None,
                 retain_split_threshold: Optional[int] = None,
                 ssl_context=None, throttler=None,
                 balancer=None, session_dict=None, mem_usage=None,
                 tls_port: Optional[int] = None, tls_ssl_context=None,
                 ws_port: Optional[int] = None,
                 ws_path: str = "/mqtt", ws_ssl_context=None,
                 proxy_protocol: bool = False,
                 user_props_customizer=None) -> None:
        self.host = host
        self.port = port
        # PROXY-protocol stage on the plain-TCP listener (a fronting LB
        # prepends the real client address; ≈ HAProxyMessageDecoder +
        # ClientAddr channel attribute, MQTTBroker.java:177-240)
        self.proxy_protocol = proxy_protocol
        self.ssl_context = ssl_context  # TLS listener (≈ 8883/netty-tcnative)
        self.tls_port = tls_port        # additional TLS listener (8883)
        self.tls_ssl_context = tls_ssl_context
        self.ws_port = ws_port          # WS listener (≈ MqttOverWSHandler)
        self.ws_path = ws_path
        self.ws_ssl_context = ws_ssl_context
        # stable broker-instance id: scopes this broker's transient routes in
        # the shared route table (deliverer-key prefix), so a startup sweep
        # can purge ITS stale routes without touching other frontends'
        self.server_id = uuid.uuid4().hex[:8]
        if inbox_engine is not None:
            meta_space = inbox_engine.create_space("broker_meta")
            sid = meta_space.get_metadata(b"server_id")
            if sid is None:
                meta_space.put_metadata(b"server_id",
                                        self.server_id.encode())
            else:
                self.server_id = sid.decode()
        self.auth = auth or AllowAllAuthProvider()
        from ..plugin.throttler import AllowAllResourceThrottler
        self.throttler = throttler or AllowAllResourceThrottler()
        from ..plugin.balancer import NoRedirectBalancer
        self.balancer = balancer or NoRedirectBalancer()
        # cross-node session dict client (cluster-wide kick); None = local
        self.session_dict = session_dict
        from ..utils.env import MemUsage
        from ..utils.sysprops import SysProp, get
        self.mem_usage = mem_usage or MemUsage(
            high_watermark=get(SysProp.INGRESS_SLOWDOWN_MEM_USAGE))
        # token bucket for connection-rate limiting
        # (≈ ConnectionRateLimitHandler)
        from ..utils.ratelimit import TokenBucket
        self._conn_bucket = TokenBucket(get(SysProp.MAX_CONN_PER_SECOND))
        self.settings = settings or DefaultSettingProvider()
        self.events = events or CollectingEventCollector()
        # ≈ IUserPropsCustomizerFactory SPI (mqtt-server-spi)
        from ..plugin.userprops import NoopUserPropsCustomizer
        self.user_props_customizer = (user_props_customizer
                                      or NoopUserPropsCustomizer())
        self.local_sessions = LocalSessionRegistry()
        self.session_registry = SessionRegistry(self.events)
        self.sub_brokers = SubBrokerRegistry()
        self.sub_brokers.register(TransientSubBroker(self.local_sessions))
        # one shared route per (server, filter, bucket) for transient subs
        # (≈ LocalTopicRouter.java:36); dist is attached below
        from .localrouter import LocalTopicRouter
        self.local_router = LocalTopicRouter(self.server_id,
                                             self.local_sessions,
                                             dist_getter=lambda: self.dist)
        self.sub_brokers.register(self.local_router)
        if dist is None:
            # ONE route table, on the replicated KV (DistWorkerCoProc.java:105)
            # — durable when an engine is provided, so routes survive restart
            # through the dist keyspace itself (coproc reset-from-KV)
            from ..dist.worker import DistWorker
            engine = None
            raft_store_factory = None
            if inbox_engine is not None:
                engine = inbox_engine
                # raft hard state/log on per-range spaces of the same
                # durable engine (≈ the reference's separate WALable engine)
                from ..raft.store import KVRaftStateStore

                def raft_store_factory(rid, _eng=inbox_engine):
                    return KVRaftStateStore(
                        _eng.create_space(f"raft_{rid}"))
            dist = DistService(self.sub_brokers, self.events, self.settings,
                               worker=DistWorker(
                                   engine=engine,
                                   raft_store_factory=raft_store_factory,
                                   **(dist_worker_kwargs or {})))
        self.dist = dist
        if retain_service is None:
            from ..retain.service import RetainService
            # share the durable engine so retained messages survive restart
            retain_service = RetainService(
                self.events, engine=inbox_engine,
                split_threshold=retain_split_threshold)
        elif retain_split_threshold is not None:
            # dropping the knob silently would let an operator believe
            # splits are enabled (same contract as the starter's dist check)
            raise ValueError("retain_split_threshold has no effect with a "
                             "caller-supplied retain_service; configure the "
                             "service directly")
        self.retain_service = retain_service
        from ..inbox.service import InboxService, InboxSubBroker
        self.inbox = InboxService(self.dist, self.events, self.settings,
                                  engine=inbox_engine,
                                  server_id=self.server_id,
                                  split_threshold=inbox_split_threshold)
        self.sub_brokers.register(InboxSubBroker(self.inbox))
        self._server: Optional[asyncio.AbstractServer] = None
        self._tls_server: Optional[asyncio.AbstractServer] = None
        self._ws_server: Optional[asyncio.AbstractServer] = None

    async def start(self) -> None:
        await self.dist.start()
        # unclean-shutdown sweep: transient-session routes in a durable route
        # keyspace point at sessions that no longer exist — purge before
        # serving (the reference's dist GC role, DistWorkerCoProc.gc:554)
        from ..plugin.subbroker import TRANSIENT_SUB_BROKER_ID
        from .localrouter import LOCAL_ROUTER_SUB_BROKER_ID
        purged = await self.dist.worker.purge_broker_routes(
            TRANSIENT_SUB_BROKER_ID, deliverer_prefix=self.server_id + "|")
        purged += await self.dist.worker.purge_broker_routes(
            LOCAL_ROUTER_SUB_BROKER_ID,
            deliverer_prefix=self.server_id + "|")
        if purged:
            log.info("purged %d stale transient routes", purged)
        await self.inbox.start()
        if hasattr(self.retain_service, "start"):
            await self.retain_service.start()
        recovered = await self.inbox.recover()
        if recovered:
            log.info("recovered %d persistent sessions from storage",
                     recovered)
        self._server = await asyncio.start_server(
            self._on_client, self.host, self.port, ssl=self.ssl_context)
        addr = self._server.sockets[0].getsockname()
        self.port = addr[1]
        log.info("mqtt broker listening on %s:%s", *addr[:2])
        if self.tls_port is not None:
            self._tls_server = await asyncio.start_server(
                self._on_client, self.host, self.tls_port,
                ssl=self.tls_ssl_context)
            self.tls_port = self._tls_server.sockets[0].getsockname()[1]
            log.info("mqtts listening on %s:%s", self.host, self.tls_port)
        if self.ws_port is not None:
            self._ws_server = await asyncio.start_server(
                self._on_ws_client, self.host, self.ws_port,
                ssl=self.ws_ssl_context)
            self.ws_port = self._ws_server.sockets[0].getsockname()[1]
            log.info("mqtt-over-ws listening on %s:%s%s", self.host,
                     self.ws_port, self.ws_path)
        from ..utils.sysprops import SysProp, get
        self._redirect_task = asyncio.get_running_loop().create_task(
            self._redirect_sweep(
                get(SysProp.CLIENT_REDIRECT_CHECK_INTERVAL_SECONDS)))
        self._heartbeat_task = asyncio.get_running_loop().create_task(
            self._loop_heartbeat())
        # push telemetry export (ISSUE 3): refcounted on the process-global
        # hub; a no-op unless a sink is configured (BIFROMQ_OBS_EXPORT /
        # BIFROMQ_OBS_EXPORT_URL). Only a broker that actually acquired a
        # ref releases one at stop.
        from ..obs import OBS
        self._obs_exporter_ref = OBS.start_exporter()
        # ISSUE 8: segment-file persistence of profile records, compile
        # ledger events and slow spans (BIFROMQ_OBS_STORE directory);
        # flushes ride the advisory tick, so arming persistence also
        # arms the tick
        self._obs_store_ref = OBS.start_persistence()
        if self._obs_store_ref:
            OBS.start_advisory_tick()
        # ISSUE 4 satellite: an armed SLO-advised throttler gets its flag
        # set refreshed on a background tick, so the connect/publish guard
        # path (has_resource) never pays a detector evaluation
        from ..plugin.throttler import SLOAdvisedResourceThrottler
        self._obs_tick_ref = False
        t = self.throttler
        while t is not None:
            if isinstance(t, SLOAdvisedResourceThrottler):
                OBS.start_advisory_tick()
                self._obs_tick_ref = True
                break
            t = getattr(t, "delegate", None)

    async def _redirect_sweep(self, interval: float) -> None:
        """Periodic IClientBalancer re-check on LIVE sessions (≈ the
        reference's ClientRedirectCheckIntervalSeconds loop): a balancer
        that starts redirecting (drain, rebalance) moves already-connected
        clients, not just new CONNECTs."""
        from ..plugin.balancer import RedirectType
        while True:
            await asyncio.sleep(interval)
            for sid in list(self.local_sessions._by_id):
                # a throwing plugin (balancer OR event collector) or a
                # failing close must cost one session's sweep, never the
                # sweep task itself
                try:
                    session = self.local_sessions.get(sid)
                    if session is None or session.closed:
                        continue
                    redirect = self.balancer.need_redirect(
                        session.client_info)
                    if redirect is None:
                        continue
                    self.events.report(Event(
                        EventType.SERVER_REDIRECTED,
                        session.client_info.tenant_id,
                        {"client_id": session.client_id,
                         "server_reference": redirect.server_reference}))
                    if session.protocol_level >= PROTOCOL_MQTT5:
                        rc = (ReasonCode.SERVER_MOVED
                              if redirect.type == RedirectType.MOVE
                              else ReasonCode.USE_ANOTHER_SERVER)
                        props = ({PropertyId.SERVER_REFERENCE:
                                  redirect.server_reference}
                                 if redirect.server_reference else None)
                        # a slow consumer's paused transport must not
                        # wedge the whole sweep in drain()
                        try:
                            await asyncio.wait_for(
                                session.conn.send(pk.Disconnect(
                                    reason_code=rc, properties=props)),
                                5.0)
                        except asyncio.TimeoutError:
                            pass
                    # per MQTT5 only a client DISCONNECT 0x00 removes the
                    # will, and the reference's onRedirect farewell keeps
                    # the LWT — close via normal teardown so the will
                    # fires (or arms its delay) like any server-initiated
                    # disconnect (ADVICE r3: a forced _will_suppressed
                    # silently dropped transient wills on admin moves)
                    await session.close(fire_will=True)
                except asyncio.CancelledError:
                    raise
                except Exception:  # noqa: BLE001
                    log.exception("redirect sweep failed for one session")

    HEARTBEAT_S = 0.020

    async def _loop_heartbeat(self) -> None:
        """The serving loop's fixed heartbeat: how late each beat fires is
        how long the loop's one thread was held by something else
        (`loop.lag`: n, total and the slice's max in the window totals)."""
        period_ns = int(self.HEARTBEAT_S * 1e9)
        due = time.monotonic_ns() + period_ns
        while True:
            await asyncio.sleep((due - time.monotonic_ns()) / 1e9)
            now = time.monotonic_ns()
            trace.record_finished("loop.lag", None, start_ns=min(due, now),
                                  end_ns=now)
            due = max(due + period_ns, now)

    async def stop(self) -> None:
        for name in ("_redirect_task", "_heartbeat_task"):
            if getattr(self, name, None) is not None:
                getattr(self, name).cancel()
        if self._server is not None:
            self._server.close()
        if self._tls_server is not None:
            self._tls_server.close()
        if self._ws_server is not None:
            self._ws_server.close()
        # close lingering sessions: wait_closed() (py3.12+) blocks until every
        # client handler returns, so orphaned connections must be torn down
        for sid in list(self.local_sessions._by_id):
            session = self.local_sessions.get(sid)
            if session is not None:
                no_lwt = session.settings[
                    Setting.NoLWTWhenServerShuttingDown]
                if no_lwt:
                    session._will_suppressed = True
                await session.close(fire_will=not no_lwt)
        if self._server is not None:
            try:
                await asyncio.wait_for(self._server.wait_closed(), 5)
            except asyncio.TimeoutError:
                pass
        # the delay window ends with the server: fire armed wills now
        # (unless the tenant suppresses shutdown LWTs), then cancel — a
        # task surviving stop() would fire into a stopped dist
        await self.inbox.flush_pending_lwts(
            lambda tenant: not TenantSettings.resolve(
                self.settings, tenant)[Setting.NoLWTWhenServerShuttingDown])
        await self.session_registry.flush_pending_wills(
            lambda tenant: not TenantSettings.resolve(
                self.settings, tenant)[Setting.NoLWTWhenServerShuttingDown])
        self.session_registry.close()
        await self.inbox.stop()
        if hasattr(self.retain_service, "stop"):
            await self.retain_service.stop()
        await self.dist.stop()
        if getattr(self, "_obs_exporter_ref", False):
            self._obs_exporter_ref = False
            from ..obs import OBS
            await OBS.stop_exporter()
        if getattr(self, "_obs_store_ref", False):
            self._obs_store_ref = False
            from ..obs import OBS
            OBS.stop_persistence()
            await OBS.stop_advisory_tick()
        if getattr(self, "_obs_tick_ref", False):
            self._obs_tick_ref = False
            from ..obs import OBS
            await OBS.stop_advisory_tick()

    def _admit_connection(self) -> Optional[EventType]:
        """Frontend admission stage (≈ ConnectionRateLimitHandler +
        ConditionalRejectHandler): token-bucket connection rate + process
        memory pressure. Returns the rejection event type, or None."""
        if not self._conn_bucket.try_take():
            return EventType.CONNECTION_RATE_EXCEEDED
        if self.mem_usage.under_pressure():
            return EventType.SERVER_BUSY
        return None

    def _reject(self, writer, reason: EventType) -> None:
        self.events.report(Event(reason, "", {}))
        writer.close()

    async def _on_client(self, reader: asyncio.StreamReader,
                         writer: asyncio.StreamWriter) -> None:
        rejected = self._admit_connection()
        if rejected is not None:
            self._reject(writer, rejected)
            return
        _lift_write_buffer_limit(writer)
        peer_addr = None
        # PROXY headers only exist on the plain-TCP listener: a TLS
        # connection's first plaintext bytes are MQTT (the LB's header
        # would have to precede the TLS handshake, which asyncio already
        # completed before this callback)
        if (self.proxy_protocol
                and writer.get_extra_info("ssl_object") is None):
            from .proxyproto import read_proxy_header
            try:
                peer_addr = await asyncio.wait_for(
                    read_proxy_header(reader), CONNECT_TIMEOUT)
            except Exception:  # noqa: BLE001 — malformed/missing header
                self._reject(writer, EventType.PROTOCOL_VIOLATION)
                return
        conn = Connection(self, reader, writer, peer_addr=peer_addr)
        await conn.run()

    async def _on_ws_client(self, reader: asyncio.StreamReader,
                            writer: asyncio.StreamWriter) -> None:
        rejected = self._admit_connection()
        if rejected is not None:
            self._reject(writer, rejected)
            return
        from . import ws
        if not await ws.server_handshake(reader, writer, self.ws_path):
            writer.close()
            return
        _lift_write_buffer_limit(writer)
        stream = ws.server_stream(reader, writer)
        conn = Connection(self, stream, stream)
        await conn.run()

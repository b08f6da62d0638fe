"""A small MQTT 3.1.1 client for the load generator: CONNECT, SUBSCRIBE,
UNSUBSCRIBE, PUBLISH at QoS 0/1 both ways, PUBACK. Its own codec, so the
broker's wire format is checked by code the broker did not write.

One ``asyncio.Protocol`` per connection; received PUBLISHes go to a plain
callback with the receive time taken as the bytes come off the socket.
``RetainClient`` also sends PUBLISHes with the RETAIN bit and hands the
bit of every PUBLISH it receives to its callback.
"""

from __future__ import annotations

import asyncio
import struct
import time
from typing import Callable, Dict, Optional

CONNECT, CONNACK, PUBLISH, PUBACK = 1, 2, 3, 4
SUBSCRIBE, SUBACK, UNSUBSCRIBE, UNSUBACK = 8, 9, 10, 11
DISCONNECT = 14


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n % 128
        n //= 128
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _str(s: bytes) -> bytes:
    return struct.pack(">H", len(s)) + s


def _packet(kind: int, flags: int, body: bytes) -> bytes:
    return bytes([(kind << 4) | flags]) + _varint(len(body)) + body


def connect_packet(client_id: str, username: str) -> bytes:
    body = (_str(b"MQTT") + bytes([4, 0x80 | 0x02]) + struct.pack(">H", 0)
            + _str(client_id.encode()) + _str(username.encode()))
    return _packet(CONNECT, 0, body)


def publish_packet(topic: bytes, payload: bytes, qos: int, pid: int) -> bytes:
    body = _str(topic) + (struct.pack(">H", pid) if qos else b"") + payload
    return _packet(PUBLISH, qos << 1, body)


class Client(asyncio.Protocol):
    def __init__(self, client_id: str, username: str,
                 on_publish: Optional[Callable] = None) -> None:
        self.client_id = client_id
        self.username = username
        self.on_publish = on_publish      # fn(client, topic, payload, qos, t_ns)
        self.transport = None
        self._buf = bytearray()
        self._waiters: Dict[tuple, asyncio.Future] = {}
        self._pid = 0
        self.closed = False
        self.inflight = 0                 # QoS 1 publishes not yet acked

    # ---- connection
    @classmethod
    async def open(cls, port: int, client_id: str, username: str,
                   on_publish=None, timeout: float = 30.0) -> "Client":
        loop = asyncio.get_running_loop()
        self = cls(client_id, username, on_publish)
        await loop.create_connection(lambda: self, "127.0.0.1", port)
        fut = self._expect(CONNACK, 0)
        self.transport.write(connect_packet(client_id, username))
        rc = await asyncio.wait_for(fut, timeout)
        if rc != 0:
            raise ConnectionError(f"CONNACK {rc} for {client_id}")
        return self

    def connection_made(self, transport) -> None:
        self.transport = transport
        sock = transport.get_extra_info("socket")
        if sock is not None:
            import socket
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def connection_lost(self, exc) -> None:
        self.closed = True
        for fut in self._waiters.values():
            if not fut.done():
                fut.set_exception(ConnectionError(
                    f"{self.client_id}: connection lost ({exc})"))
        self._waiters.clear()

    def close(self) -> None:
        if self.transport is not None and not self.closed:
            self.transport.write(_packet(DISCONNECT, 0, b""))
            self.transport.close()

    # ---- requests
    def _next_pid(self) -> int:
        self._pid = self._pid % 65535 + 1
        return self._pid

    def _expect(self, kind: int, pid: int) -> asyncio.Future:
        fut = asyncio.get_running_loop().create_future()
        self._waiters[(kind, pid)] = fut
        return fut

    async def subscribe(self, topic_filter: str, qos: int,
                        timeout: float = 60.0) -> int:
        pid = self._next_pid()
        fut = self._expect(SUBACK, pid)
        self.transport.write(_packet(
            SUBSCRIBE, 2, struct.pack(">H", pid)
            + _str(topic_filter.encode()) + bytes([qos])))
        return await asyncio.wait_for(fut, timeout)

    async def unsubscribe(self, topic_filter: str,
                          timeout: float = 60.0) -> None:
        pid = self._next_pid()
        fut = self._expect(UNSUBACK, pid)
        self.transport.write(_packet(
            UNSUBSCRIBE, 2, struct.pack(">H", pid)
            + _str(topic_filter.encode())))
        await asyncio.wait_for(fut, timeout)

    def publish(self, topic: bytes, payload: bytes,
                qos: int) -> Optional[asyncio.Future]:
        """Writes the PUBLISH now; for QoS 1 returns the PUBACK's future
        (resolved with the receive time in ns)."""
        if qos == 0:
            self.transport.write(publish_packet(topic, payload, 0, 0))
            return None
        pid = self._next_pid()
        fut = self._expect(PUBACK, pid)
        self.inflight += 1
        self.transport.write(publish_packet(topic, payload, 1, pid))
        return fut

    # ---- receive
    def data_received(self, data: bytes) -> None:
        now = time.monotonic_ns()
        buf = self._buf
        buf += data
        pos, n = 0, len(buf)
        while n - pos >= 2:
            first = buf[pos]
            length, shift, i = 0, 0, pos + 1
            while True:
                if i >= n:
                    length = -1
                    break
                b = buf[i]
                i += 1
                length |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
            if length < 0 or n - i < length:
                break
            self._on_packet(first, bytes(buf[i:i + length]), now)
            pos = i + length
        if pos:
            del buf[:pos]

    def _resolve(self, key: tuple, value) -> None:
        fut = self._waiters.pop(key, None)
        if fut is not None and not fut.done():
            fut.set_result(value)

    def _on_packet(self, first: int, body: bytes, now: int) -> None:
        kind = first >> 4
        if kind == PUBLISH:
            qos = (first >> 1) & 3
            tlen = (body[0] << 8) | body[1]
            topic = body[2:2 + tlen]
            pos = 2 + tlen
            if qos:
                self.transport.write(_packet(PUBACK, 0, body[pos:pos + 2]))
                pos += 2
            if self.on_publish is not None:
                self.on_publish(self, topic, body[pos:], qos, now)
        elif kind == PUBACK:
            self.inflight -= 1
            self._resolve((PUBACK, (body[0] << 8) | body[1]), now)
        elif kind == SUBACK:
            self._resolve((SUBACK, (body[0] << 8) | body[1]), body[2])
        elif kind == UNSUBACK:
            self._resolve((UNSUBACK, (body[0] << 8) | body[1]), None)
        elif kind == CONNACK:
            self._resolve((CONNACK, 0), body[1])


class RetainClient(Client):
    """A client that speaks the RETAIN bit: ``publish_retained`` sends a
    QoS 1 PUBLISH with it set ([MQTT-3.3.1-5]; an empty payload clears the
    topic), and ``on_publish`` is called as fn(client, topic, payload, qos,
    t_ns, retain)."""

    def publish_retained(self, topic: bytes, payload: bytes) -> asyncio.Future:
        pid = self._next_pid()
        fut = self._expect(PUBACK, pid)
        self.inflight += 1
        body = _str(topic) + struct.pack(">H", pid) + payload
        self.transport.write(_packet(PUBLISH, (1 << 1) | 1, body))
        return fut

    def _on_packet(self, first: int, body: bytes, now: int) -> None:
        if first >> 4 != PUBLISH:
            super()._on_packet(first, body, now)
            return
        qos = (first >> 1) & 3
        tlen = (body[0] << 8) | body[1]
        topic = body[2:2 + tlen]
        pos = 2 + tlen
        if qos:
            self.transport.write(_packet(PUBACK, 0, body[pos:pos + 2]))
            pos += 2
        if self.on_publish is not None:
            self.on_publish(self, topic, body[pos:], qos, now, first & 1)

"""RPC fabric: multiplexed length-prefixed RPC over asyncio TCP.

Re-expression of base-rpc (SURVEY.md §2.4) without gRPC (not in the image):

- ``RPCServer`` binds one port and hosts many named services
  (≈ RPCServer.java: one server, many BluePrints). A service is a map of
  method name → async handler(payload: bytes, headers) -> bytes.
- ``RPCClient`` multiplexes concurrent calls over one connection with
  correlation ids; calls carrying an ``order_key`` execute in FIFO order
  per key on the server (≈ orderKey-pinned ManagedRequestPipeline /
  ResponsePipeline semantics: one ordered stream per key).
- ``ServiceRegistry`` is the traffic-governor analog: servers announce
  ``(service, address)`` into a gossip agent's metadata
  (≈ RPCServiceAnnouncer publishing ServerEndpoint into the traffic
  governor ORMap CRDT, RPCServiceTrafficService.java:30); clients pick a
  server by rendezvous hash over a tenant key (≈ HRWRouter tenant-aware
  load balancing).

Wire format (all big-endian):
  frame   := u32 length ‖ body
  request := 0x01 ‖ u64 id ‖ len16 service ‖ len16 method ‖ len16 order_key
             ‖ payload
  request2:= 0x03 ‖ u64 id ‖ len16 service ‖ len16 method ‖ len16 order_key
             ‖ u32 deadline_ms ‖ payload       (deadline header, ISSUE 1 —
             the remaining call budget, ≈ gRPC's grpc-timeout; 0 = none)
  request3:= 0x04 ‖ u64 id ‖ len16 service ‖ len16 method ‖ len16 order_key
             ‖ u32 deadline_ms ‖ u8 trace_len ‖ trace_ctx ‖ payload
             (request2 header family extended with a trace context,
             ISSUE 2: trace id ‖ parent span id ‖ sampled flag ‖ sender
             HLC stamp — the receiver merges the stamp so cross-process
             spans order causally)
  reply   := 0x02 ‖ u64 id ‖ u8 status ‖ payload      (status 0 = OK)

Resilience (ISSUE 1): transport failures surface as ``RPCTransportError``
and timeouts as ``RPCTimeoutError`` (both ``RPCError``), call outcomes
feed per-endpoint circuit breakers so ``ServiceRegistry.pick`` routes
around open circuits, and the process-global ``resilience.faults``
injector hooks both ends of the frame path for chaos tests.
"""

from __future__ import annotations

import asyncio
import hashlib
import logging
import struct
import time
from typing import Awaitable, Callable, Dict, List, Optional, Tuple

from .. import trace as _trace
from ..resilience import faults as _faults
from ..resilience import policy as _policy

log = logging.getLogger(__name__)

_REQ = 0x01
_REP = 0x02
_REQ2 = 0x03
_REQ3 = 0x04

Handler = Callable[[bytes, str], Awaitable[bytes]]


class RPCError(Exception):
    """Base of the fabric's error taxonomy (also: handler-raised errors
    reflected back over the wire as status-1 replies)."""


class RPCTransportError(RPCError):
    """The connection failed (dial, write, or mid-call loss). The request
    may or may not have executed server-side — only idempotent methods
    auto-retry (``resilience.policy.is_idempotent``)."""


class RPCTimeoutError(RPCTransportError):
    """The per-call timeout or the propagated deadline budget expired."""


class RPCCircuitOpenError(RPCTransportError):
    """Refused pre-send by an OPEN circuit (or an exhausted half-open
    probe budget): the request was never transmitted, so there is ZERO
    execution ambiguity — even non-idempotent calls may safely fail over
    to another endpoint."""


def _len16(b: bytes) -> bytes:
    return struct.pack(">H", len(b)) + b


def _read16(buf: bytes, pos: int) -> Tuple[bytes, int]:
    n = struct.unpack_from(">H", buf, pos)[0]
    pos += 2
    return buf[pos:pos + n], pos + n


async def _read_frame(reader: asyncio.StreamReader) -> bytes:
    hdr = await reader.readexactly(4)
    (n,) = struct.unpack(">I", hdr)
    return await reader.readexactly(n)


def _write_frame(writer: asyncio.StreamWriter, body: bytes) -> None:
    writer.write(struct.pack(">I", len(body)) + body)


class _OrderedRunner:
    """Per-order-key FIFO execution (≈ base-util AsyncRunner: a serialized
    async task queue; the reference pins one response pipeline per key)."""

    IDLE_RETIRE_S = 30.0

    def __init__(self) -> None:
        self._queues: Dict[str, asyncio.Queue] = {}
        self._tasks: Dict[str, asyncio.Task] = {}

    def submit(self, key: str, coro_fn) -> None:
        q = self._queues.get(key)
        if q is None:
            q = self._queues[key] = asyncio.Queue()
            self._tasks[key] = asyncio.create_task(self._drain(key, q))
        q.put_nowait(coro_fn)

    async def _drain(self, key: str, q: asyncio.Queue) -> None:
        while True:
            try:
                coro_fn = await asyncio.wait_for(q.get(),
                                                 timeout=self.IDLE_RETIRE_S)
            except asyncio.TimeoutError:
                # idle: retire ATOMICALLY — deregister FIRST, then re-check
                # the queue. A submit() that raced the wait_for timeout
                # (its enqueue landed between the timeout firing and this
                # block — incl. the pre-3.12 wait_for lost-wakeup window)
                # left the queue non-empty: re-register and keep draining
                # instead of abandoning its item. submit() itself is
                # synchronous on the event loop, so it can never observe
                # the deregistered-but-nonempty intermediate state.
                if self._queues.get(key) is q:
                    del self._queues[key]
                    self._tasks.pop(key, None)
                if q.empty():
                    return
                self._queues[key] = q
                self._tasks[key] = asyncio.current_task()
                continue
            try:
                await coro_fn()
            except Exception:  # noqa: BLE001
                log.exception("ordered task failed (key=%s)", key)

    def close(self) -> None:
        for t in self._tasks.values():
            t.cancel()
        self._queues.clear()
        self._tasks.clear()


# process-local server table: calls addressed to a server in THIS process
# bypass TCP entirely (≈ the reference's in-proc RPC bypass, where client
# and server stubs short-circuit inside one JVM)
_LOCAL_SERVERS: Dict[str, "RPCServer"] = {}


class RPCServer:
    """One listener hosting many services.

    ``ssl_context`` (server-side) enables TLS on the listener — the
    counterpart of the reference's SSL-capable RPC servers."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0, *,
                 ssl_context=None) -> None:
        self.host = host
        self.port = port
        self.ssl_context = ssl_context
        self._services: Dict[str, Dict[str, Handler]] = {}
        self._server: Optional[asyncio.AbstractServer] = None
        self._conn_tasks: set = set()
        # unordered handler tasks, strongly held server-wide: a bare
        # ensure_future is only weakly referenced (GC could collect it
        # mid-flight, silently dropping the reply). They run to
        # COMPLETION even if their connection dies — wire-path parity
        # with the local bypass's shielded dispatch (a cancelled mutate
        # could be half-applied) — and are cancelled only by stop().
        self._handler_tasks: set = set()
        self._local_runner: Optional[_OrderedRunner] = None

    def register(self, service: str, methods: Dict[str, Handler]) -> None:
        self._services.setdefault(service, {}).update(methods)

    async def start(self) -> None:
        self._server = await asyncio.start_server(self._on_conn, self.host,
                                                  self.port,
                                                  ssl=self.ssl_context)
        self.port = self._server.sockets[0].getsockname()[1]
        _LOCAL_SERVERS[self.address] = self

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    async def stop(self) -> None:
        _LOCAL_SERVERS.pop(self.address, None)
        if self._server is not None:
            self._server.close()
        if self._local_runner is not None:
            self._local_runner.close()
            self._local_runner = None
        for t in list(self._conn_tasks):
            t.cancel()
        # stop == crash semantics for in-flight handlers (raft/kv
        # invariants must tolerate that anyway); cancelling here keeps
        # them from dying as destroyed-pending tasks at loop teardown
        for t in list(self._handler_tasks):
            t.cancel()

    async def dispatch_local(self, service: str, method: str,
                             payload: bytes, order_key: str) -> bytes:
        """In-proc bypass entry: same semantics as the wire path —
        handler errors surface as RPCError, and calls sharing an
        order_key execute FIFO through the same runner machinery."""
        handler = self._services.get(service, {}).get(method)
        if handler is None:
            raise RPCError("no such method")
        # capture the CALLER's deadline + trace context: the ordered path
        # below runs the handler in the _OrderedRunner drain task, whose
        # context would otherwise silently drop the budget (and trace)
        # the wire path re-arms
        deadline = _policy.current_deadline()
        tctx = _trace.current_ctx()

        async def run() -> bytes:
            try:
                with _policy.absolute_deadline(deadline), \
                        _trace.activate(tctx):
                    return await handler(payload, order_key)
            except Exception as e:  # noqa: BLE001 — wire-path parity
                raise RPCError(repr(e)) from e

        if not order_key:
            return await run()
        if self._local_runner is None:
            self._local_runner = _OrderedRunner()
        fut: asyncio.Future = asyncio.get_running_loop().create_future()

        async def ordered() -> None:
            try:
                res = await run()
                if not fut.done():      # caller may have been cancelled
                    fut.set_result(res)
            except BaseException as e:  # noqa: BLE001
                if not fut.done():
                    fut.set_exception(e)
        self._local_runner.submit(order_key, ordered)
        return await fut

    async def _on_conn(self, reader: asyncio.StreamReader,
                       writer: asyncio.StreamWriter) -> None:
        task = asyncio.current_task()
        self._conn_tasks.add(task)
        runner = _OrderedRunner()
        send_lock = asyncio.Lock()
        try:
            while True:
                body = await _read_frame(reader)
                # hostile/truncated frames (port scanners, bad peers) drop
                # the connection without an unhandled-traceback path
                if not body or body[0] not in (_REQ, _REQ2, _REQ3):
                    if not body:
                        break
                    continue
                try:
                    (rid,) = struct.unpack_from(">Q", body, 1)
                    service_b, pos = _read16(body, 9)
                    method_b, pos = _read16(body, pos)
                    okey_b, pos = _read16(body, pos)
                    deadline = None
                    tctx = None
                    if body[0] in (_REQ2, _REQ3):
                        # deadline header: remaining budget in ms (0 = none)
                        (ms,) = struct.unpack_from(">I", body, pos)
                        pos += 4
                        if ms:
                            deadline = time.monotonic() + ms / 1000.0
                    if body[0] == _REQ3:
                        # trace context (ISSUE 2): decode merges the
                        # sender's HLC stamp into the local clock. A
                        # trace_len overrunning the frame is a malformed
                        # frame — drop the connection like any other
                        # garbled header, never run the handler on a
                        # truncated payload
                        tlen = body[pos]
                        pos += 1
                        if pos + tlen > len(body):
                            break
                        tctx = _trace.extract(body[pos:pos + tlen])
                        pos += tlen
                    service = service_b.decode()
                    method = method_b.decode()
                    okey = okey_b.decode()
                except (struct.error, IndexError, UnicodeDecodeError):
                    break
                payload = body[pos:]
                fault = _faults.get_injector().decide("server", service,
                                                      method)
                if fault is not None:
                    if fault.action == "drop":
                        continue        # request vanishes: caller times out
                    if fault.action == "disconnect":
                        break
                handler = self._services.get(service, {}).get(method)

                async def run(rid=rid, handler=handler, payload=payload,
                              okey=okey, deadline=deadline, fault=fault,
                              tctx=tctx, service=service, method=method):
                    if fault is not None and fault.action == "delay":
                        await asyncio.sleep(fault.delay)
                    if fault is not None and fault.action == "error":
                        status, out = 1, b"injected fault"
                    elif handler is None:
                        status, out = 1, b"no such method"
                    else:
                        try:
                            # re-arm the caller's budget so handler-issued
                            # downstream RPCs inherit the shrunken deadline,
                            # and the caller's trace context so handler
                            # spans join the distributed trace (activate
                            # also CLEARS any context leaked from a prior
                            # request on this connection task)
                            with _policy.absolute_deadline(deadline), \
                                    _trace.activate(tctx), \
                                    _trace.span("rpc.server",
                                                service=service,
                                                method=method):
                                out = await handler(payload, okey)
                            status = 0
                        except Exception as e:  # noqa: BLE001
                            status, out = 1, repr(e).encode()
                    if fault is not None and fault.action == "corrupt":
                        out = _faults.get_injector().corrupt(out)
                    try:
                        async with send_lock:
                            _write_frame(writer, bytes([_REP])
                                         + struct.pack(">Q", rid)
                                         + bytes([status]) + out)
                            await writer.drain()
                    except (ConnectionError, OSError, RuntimeError):
                        # the caller is gone (died/disconnected mid-call):
                        # its reply has nowhere to go — never let a
                        # detached handler task die with an unretrieved
                        # exception over it (RuntimeError: write() on a
                        # transport closed by connection teardown)
                        pass

                if okey:
                    runner.submit(okey, run)
                else:
                    t = asyncio.ensure_future(run())
                    self._handler_tasks.add(t)
                    t.add_done_callback(self._handler_tasks.discard)
        except (asyncio.IncompleteReadError, ConnectionError,
                asyncio.CancelledError):
            pass
        finally:
            runner.close()
            writer.close()
            self._conn_tasks.discard(task)


class RPCClient:
    """Multiplexed client for one server address; reconnects lazily.
    Calls addressed to a server living in THIS process short-circuit
    through ``dispatch_local`` (no sockets). ``ssl_context`` dials TLS."""

    def __init__(self, host: str, port: int, *, ssl_context=None,
                 local_bypass: bool = True, breaker=None) -> None:
        self.host = host
        self.port = port
        self.ssl_context = ssl_context
        self.local_bypass = local_bypass
        # optional resilience.breaker.CircuitBreaker fed by wire-path call
        # outcomes (a status-1 handler error is a SUCCESSFUL round trip)
        self.breaker = breaker
        self._writer: Optional[asyncio.StreamWriter] = None
        self._reader_task: Optional[asyncio.Task] = None
        self._pending: Dict[int, asyncio.Future] = {}
        self._next_id = 0
        self._conn_lock = asyncio.Lock()

    @classmethod
    def from_address(cls, address: str) -> "RPCClient":
        host, port = address.rsplit(":", 1)
        return cls(host, int(port))

    async def _ensure_conn(self) -> asyncio.StreamWriter:
        async with self._conn_lock:
            if self._writer is not None and not self._writer.is_closing():
                return self._writer
            try:
                reader, writer = await asyncio.open_connection(
                    self.host, self.port, ssl=self.ssl_context)
            except (ConnectionError, OSError) as e:
                raise RPCTransportError(f"dial {self.host}:{self.port} "
                                        f"failed: {e!r}") from e
            # per-connection pending map: a dead connection's cleanup must
            # only fail ITS calls, never a successor connection's
            self._writer = writer
            self._pending = {}
            self._reader_task = asyncio.create_task(
                self._read_loop(reader, writer, self._pending))
            return writer

    async def _read_loop(self, reader: asyncio.StreamReader,
                         writer: asyncio.StreamWriter,
                         pending: Dict[int, asyncio.Future]) -> None:
        try:
            while True:
                body = await _read_frame(reader)
                if not body or body[0] != _REP:
                    if not body:
                        break
                    continue
                (rid,) = struct.unpack_from(">Q", body, 1)
                status = body[9]
                payload = body[10:]
                fut = pending.pop(rid, None)
                if fut is not None and not fut.done():
                    if status == 0:
                        fut.set_result(payload)
                    else:
                        # errors="replace": a corrupted error reply (chaos
                        # injection, hostile peer) must not kill the read
                        # loop with a UnicodeDecodeError
                        fut.set_exception(RPCError(
                            payload.decode(errors="replace")))
        except (asyncio.IncompleteReadError, ConnectionError,
                asyncio.CancelledError):
            pass
        finally:
            for fut in pending.values():
                if not fut.done():
                    fut.set_exception(RPCTransportError("connection lost"))
            pending.clear()
            writer.close()
            if self._writer is writer:
                self._writer = None

    def _effective_timeout(self, timeout: float) -> Tuple[float, bool]:
        """Per-call timeout capped by the propagated deadline budget; an
        exhausted budget fails fast (metered) instead of dispatching.
        Returns (timeout, budget_capped) — when the budget is the binding
        constraint, a resulting timeout says nothing about endpoint
        health and must not feed the breaker."""
        rem = _policy.remaining_budget()
        if rem is None:
            return timeout, False
        if rem <= 0.0:
            from ..utils.metrics import FABRIC, FabricMetric
            FABRIC.inc(FabricMetric.RPC_DEADLINE_EXPIRED)
            raise RPCTimeoutError("deadline budget exhausted")
        return min(timeout, rem), rem < timeout

    async def call(self, service: str, method: str, payload: bytes, *,
                   order_key: str = "", timeout: float = 30.0,
                   trace_tags: Optional[dict] = None) -> bytes:
        """Span-wrapped call (ISSUE 2): every attempt gets an "rpc.attempt"
        span tagged with endpoint + breaker state (``trace_tags`` lets
        ``call_resilient`` stamp attempt/failover counts); its exit feeds
        the "rpc" stage histogram whether or not the trace is sampled."""
        sp = _trace.span("rpc.attempt", service=service, method=method,
                         endpoint=f"{self.host}:{self.port}",
                         **(trace_tags or {}))
        if self.breaker is not None:
            sp.set_tag("breaker", self.breaker.state)
        with sp:
            return await self._call(service, method, payload, order_key,
                                    timeout)

    async def _call(self, service: str, method: str, payload: bytes,
                    order_key: str, timeout: float) -> bytes:
        timeout, budget_capped = self._effective_timeout(timeout)
        if self.local_bypass:
            local = _LOCAL_SERVERS.get(f"{self.host}:{self.port}")
            if (local is not None and local._server is not None
                    and local._server.is_serving()):
                # in-proc bypass: no sockets, no codec. The handler runs
                # as a DETACHED task shielded from the client timeout —
                # on the wire path a timed-out call still completes
                # server-side, and the bypass must not diverge (a
                # cancelled mutate could be half-applied)
                task = asyncio.ensure_future(local.dispatch_local(
                    service, method, payload, order_key))
                try:
                    return await asyncio.wait_for(asyncio.shield(task),
                                                  timeout)
                except asyncio.TimeoutError as e:
                    raise RPCTimeoutError(
                        f"{service}/{method} timed out after "
                        f"{timeout:.3f}s (local)") from e
        if self.breaker is not None and not self.breaker.allow():
            # OPEN circuit (or half-open probe budget exhausted): fail fast
            # without dialing — and without recording a new failure, a
            # refused admission is not a fresh outcome. The distinct type
            # tells retrying callers the request was NEVER sent (safe to
            # fail over even for non-idempotent methods).
            raise RPCCircuitOpenError(
                f"circuit open for {self.host}:{self.port}")
        fault = _faults.get_injector().decide("client", service, method)
        if fault is not None and fault.action == "error":
            self._record(False, "injected fault")
            raise RPCTransportError("injected fault")
        try:
            out = await self._call_wire(service, method, payload,
                                        order_key, timeout, fault)
        except RPCTimeoutError as e:
            # a timeout whose clock was the CALLER's nearly-spent budget
            # says nothing about endpoint health: release the admission
            # without a verdict instead of tripping a healthy breaker
            if budget_capped:
                if self.breaker is not None:
                    self.breaker.release_probe()
            else:
                self._record(False, repr(e))
            raise
        except RPCTransportError as e:
            # breaker food: transport failures only
            self._record(False, repr(e))
            raise
        except RPCError:
            # a reflected handler error is a SUCCESSFUL round trip — the
            # endpoint is alive. Recording success here also releases a
            # HALF_OPEN probe slot (a handler-error probe must close the
            # circuit, not strand it half-open forever)
            self._record(True)
            raise
        except BaseException:
            # cancellation (or any non-RPC failure) mid-call: no verdict
            # on endpoint health, but a charged HALF_OPEN probe slot must
            # be returned or the breaker wedges half-open forever
            if self.breaker is not None:
                self.breaker.release_probe()
            raise
        self._record(True)
        return out

    def _record(self, ok: bool, error: Optional[str] = None) -> None:
        if self.breaker is not None:
            if ok:
                self.breaker.record_success()
            else:
                self.breaker.record_failure(error)

    async def _call_wire(self, service: str, method: str, payload: bytes,
                         order_key: str, timeout: float, fault) -> bytes:
        writer = await self._ensure_conn()
        if fault is not None:
            if fault.action == "delay":
                # injected latency counts AGAINST the per-call timeout,
                # exactly like real network delay would
                await asyncio.sleep(fault.delay)
                timeout -= fault.delay
                if timeout <= 0:
                    raise RPCTimeoutError(
                        f"{service}/{method} timed out under injected "
                        f"{fault.delay:.3f}s delay")
            elif fault.action == "corrupt":
                payload = _faults.get_injector().corrupt(payload)
            elif fault.action == "disconnect":
                writer.close()
                raise RPCTransportError("injected disconnect")
        pending = self._pending
        self._next_id += 1
        rid = self._next_id
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        pending[rid] = fut
        rem = _policy.remaining_budget()
        tblob = _trace.inject()
        hdr = (struct.pack(">Q", rid) + _len16(service.encode())
               + _len16(method.encode()) + _len16(order_key.encode()))
        if tblob is not None:
            # request3: deadline budget (0 = none) + trace context, so the
            # server joins the distributed trace in causal HLC order
            body = (bytes([_REQ3]) + hdr
                    + struct.pack(">I", 0 if rem is None
                                  else max(1, int(rem * 1000)))
                    + bytes([len(tblob)]) + tblob + payload)
        elif rem is not None:
            # request2: stamp the remaining budget so the server (and its
            # downstream calls) inherit the shrunken deadline
            body = (bytes([_REQ2]) + hdr
                    + struct.pack(">I", max(1, int(rem * 1000)))
                    + payload)
        else:
            body = bytes([_REQ]) + hdr + payload
        if fault is not None and fault.action == "drop":
            # the request frame vanishes on the wire: the reply future can
            # only time out (exactly what a blackholed network does)
            pass
        else:
            try:
                _write_frame(writer, body)
                await writer.drain()
            except (ConnectionError, OSError) as e:
                pending.pop(rid, None)
                raise RPCTransportError(f"send failed: {e!r}") from e
        try:
            return await asyncio.wait_for(fut, timeout)
        except asyncio.TimeoutError as e:
            raise RPCTimeoutError(f"{service}/{method} timed out after "
                                  f"{timeout:.3f}s") from e
        finally:
            # a timed-out call must not leak its correlation entry
            pending.pop(rid, None)

    async def close(self) -> None:
        if self._reader_task is not None:
            self._reader_task.cancel()
        if self._writer is not None:
            self._writer.close()
            self._writer = None


class ServiceRegistry:
    """Service discovery (traffic governor analog, three backends):

    - **CRDT** (the reference way, RPCServiceTrafficService.java:30): each
      server announces ``(service → address)`` into a replicated ORMap
      ("traffic" uri) on a CRDTStore; anti-entropy spreads it.
    - **gossip agents**: announce into agent ``rpc:<service>`` metadata.
    - **static**: explicit addresses (tests / config files).

    Clients rendezvous-hash a tenant key over the union of live endpoints
    (HRWRouter semantics)."""

    TRAFFIC_URI = "traffic"
    DIRECTIVE_URI = "traffic-directive"

    def __init__(self, agent_host=None, crdt_store=None, *,
                 local_bypass: bool = True,
                 client_ssl_context=None, breakers=None) -> None:
        from ..resilience.breaker import BreakerRegistry
        self.agent_host = agent_host
        self.crdt_store = crdt_store
        self.local_bypass = local_bypass        # in-proc short-circuit
        self.client_ssl_context = client_ssl_context  # TLS dialing
        # per-endpoint circuit breakers: pick() routes around open
        # circuits; clients created here feed them with call outcomes
        self.breakers = (breakers if breakers is not None
                         else BreakerRegistry())
        # live breaker state shows up in the /metrics "fabric" section
        # (weakly held — a test-scoped registry dies with its owner)
        from ..utils.metrics import FABRIC as _FABRIC
        _FABRIC.register_breakers(self.breakers)
        # gossiped remote health (ISSUE 5): an object with
        # ``suspect(endpoint) -> bool`` (obs.clusterview.ClusterView) —
        # pick() demotes endpoints the CLUSTER says are unhealthy (a
        # peer's open breaker, a self-reported deep dispatch queue)
        # before any local failure is observed
        self.remote_health = None
        self._static: Dict[str, List[str]] = {}
        self._clients: Dict[str, RPCClient] = {}
        # traffic governor state (≈ IRPCServiceTrafficGovernor.java:29):
        # address -> server-group tag, and per-service tenant-prefix
        # directives mapping group -> weight
        self._groups: Dict[str, str] = {}
        self._directives: Dict[str, Dict[str, Dict[str, int]]] = {}

    # -- server side --------------------------------------------------------

    def announce(self, service: str, address: str,
                 group: str = "") -> None:
        """Announce an endpoint, optionally tagged with a server GROUP
        (the traffic governor's unit of weighted tenant assignment)."""
        element = f"{address}|{group}" if group else address
        if self.crdt_store is not None:
            self.crdt_store.set_add(self.TRAFFIC_URI, service, element)
        if self.agent_host is not None:
            self.agent_host.host_agent(f"rpc:{service}",
                                       {"address": address,
                                        "group": group})
        self._static.setdefault(service, []).append(address)
        if group:
            self._groups[address] = group

    # -- traffic directives (≈ setTrafficDirective) -------------------------

    def set_traffic_directive(self, service: str, tenant_prefix: str,
                              group_weights: Dict[str, int]) -> None:
        """Route tenants matching ``tenant_prefix`` across server groups
        by weight (weight 0 = drain). The LONGEST matching prefix wins;
        tenants matching no directive spread over all endpoints."""
        self._directives.setdefault(service, {})[tenant_prefix] = \
            dict(group_weights)
        getattr(self, "_directive_cache", {}).pop(service, None)
        if self.crdt_store is not None:
            import json as _json
            key = f"{service}/{tenant_prefix}"
            for el in self.crdt_store.elements(self.DIRECTIVE_URI, key):
                self.crdt_store.set_remove(self.DIRECTIVE_URI, key, el)
            self.crdt_store.set_add(self.DIRECTIVE_URI, key,
                                    _json.dumps(group_weights,
                                                sort_keys=True))

    def traffic_directives(self) -> Dict[str, Dict[str, Dict[str, int]]]:
        """Snapshot of service → tenant_prefix → {group: weight} (the
        apiserver's GET /traffic introspection)."""
        return {svc: {pfx: dict(gw) for pfx, gw in rules.items()}
                for svc, rules in self._directives.items()}

    def unset_traffic_directive(self, service: str,
                                tenant_prefix: str) -> None:
        self._directives.get(service, {}).pop(tenant_prefix, None)
        getattr(self, "_directive_cache", {}).pop(service, None)
        if self.crdt_store is not None:
            self.crdt_store.remove_key(self.DIRECTIVE_URI,
                                       f"{service}/{tenant_prefix}")

    _DIRECTIVE_CACHE_TTL = 1.0

    def _directive_for(self, service: str,
                       key: str) -> Optional[Dict[str, int]]:
        import time as _time
        cached = getattr(self, "_directive_cache", None)
        if cached is None:
            cached = self._directive_cache = {}
        hit = cached.get(service)
        if hit is not None and hit[0] > _time.monotonic():
            directives = hit[1]
        else:
            directives = dict(self._directives.get(service, {}))
            if self.crdt_store is not None:
                import json as _json
                prefix = f"{service}/"
                for k in self.crdt_store.keys(self.DIRECTIVE_URI):
                    if k.startswith(prefix):
                        for el in self.crdt_store.elements(
                                self.DIRECTIVE_URI, k):
                            try:
                                directives.setdefault(k[len(prefix):],
                                                      _json.loads(el))
                            except ValueError:
                                continue
            # bounded staleness beats O(directives) JSON parsing on every
            # routed message (pick() is the per-request hot path)
            cached[service] = (_time.monotonic()
                               + self._DIRECTIVE_CACHE_TTL, directives)
        best = None
        for pfx in directives:
            if key.startswith(pfx) and (best is None
                                        or len(pfx) > len(best)):
                best = pfx
        return directives[best] if best is not None else None

    def withdraw(self, service: str, address: str) -> None:
        if self.crdt_store is not None:
            # grouped endpoints are stored as "address|group": remove every
            # element whose address part matches
            for el in list(self.crdt_store.elements(self.TRAFFIC_URI,
                                                    service)):
                if el == address or el.startswith(address + "|"):
                    self.crdt_store.set_remove(self.TRAFFIC_URI, service,
                                               el)
        if self.agent_host is not None:
            self.agent_host.stop_agent(f"rpc:{service}")
        if address in self._static.get(service, []):
            self._static[service].remove(address)

    # -- client side --------------------------------------------------------

    def endpoints(self, service: str) -> List[str]:
        out = []
        if self.crdt_store is not None:
            for el in self.crdt_store.elements(self.TRAFFIC_URI, service):
                addr, _, group = el.partition("|")
                if group:
                    self._groups[addr] = group
                if addr not in out:
                    out.append(addr)
        if self.agent_host is not None:
            for _node, meta in self.agent_host.agent_members(
                    f"rpc:{service}").items():
                addr = (meta or {}).get("address")
                if addr and addr not in out:
                    out.append(addr)
                    if (meta or {}).get("group"):
                        self._groups[addr] = meta["group"]
        for addr in self._static.get(service, []):
            if addr not in out:
                out.append(addr)
        return sorted(out)

    def pick(self, service: str, key: str,
             exclude: Optional[set] = None) -> Optional[str]:
        """Weighted rendezvous hash (≈ HRWRouter with traffic-governor
        directives): the longest tenant-prefix directive scales each
        endpoint's score by its group weight; weight-0 groups drain.

        Endpoints whose circuit breaker is OPEN are skipped, so the hash
        falls over to the next-ranked live server (ISSUE 1 failover);
        ``exclude`` additionally masks endpoints a retrying caller already
        failed against THIS call. Candidate tiers degrade gracefully:
        (1) locally available AND clear of gossiped remote health flags
        (ISSUE 5: a peer's open breaker or a node's self-reported deep
        dispatch queue demotes it here, before any local failure),
        (2) breaker-available and not excluded, (3) breaker-available —
        a retry that has failed against EVERY endpoint must prefer a
        live-looking one over a known-open circuit, (4) everything
        (total outage stays no worse than before breakers existed)."""
        eps = self.endpoints(service)
        if not eps:
            return None
        available = [ep for ep in eps if self.breakers.available(ep)]
        healthy = available
        rh = self.remote_health
        if rh is not None:
            try:
                healthy = [ep for ep in available if not rh.suspect(ep)]
            except Exception:  # noqa: BLE001 — advisory only: routing
                healthy = available  # must survive a telemetry bug
        if exclude:
            tier1 = [ep for ep in healthy if ep not in exclude]
            tier2 = [ep for ep in available if ep not in exclude]
        else:
            tier1, tier2 = healthy, available
        eps = tier1 or tier2 or available or eps
        directive = self._directive_for(service, key)
        if directive is not None:
            weighted = [ep for ep in eps
                        if directive.get(self._groups.get(ep, ""), 0) > 0]
            if weighted:
                def wscore(ep: str) -> float:
                    w = directive.get(self._groups.get(ep, ""), 0)
                    h = hashlib.blake2b(f"{ep}|{key}".encode(),
                                        digest_size=8).digest()
                    # weighted rendezvous: u^(1/w) ordering via -w/ln(u).
                    # Map the top 52 hash bits into (0,1) EXCLUSIVE with
                    # representable float endpoints — a u that rounds to
                    # exactly 0.0 or 1.0 would crash log for that
                    # (endpoint, tenant) pair deterministically forever
                    import math
                    u = ((int.from_bytes(h, "big") >> 12) + 1) \
                        / float((1 << 52) + 2)
                    return -w / math.log(u)
                return max(weighted, key=wscore)

        def score(ep: str) -> int:
            h = hashlib.blake2b(f"{ep}|{key}".encode(),
                                digest_size=8).digest()
            return int.from_bytes(h, "big")
        return max(eps, key=score)

    def client_for(self, addr: str) -> RPCClient:
        c = self._clients.get(addr)
        if c is None:
            host, port = addr.rsplit(":", 1)
            c = self._clients[addr] = RPCClient(
                host, int(port), ssl_context=self.client_ssl_context,
                local_bypass=self.local_bypass,
                breaker=self.breakers.for_endpoint(addr))
        return c

    async def call_resilient(self, service: str, key: str, method: str,
                             payload: bytes, *, order_key: str = "",
                             timeout: float = 30.0, policy=None,
                             idempotent: Optional[bool] = None,
                             rng=None) -> bytes:
        """Pick → call with retry + endpoint failover (the fabric's
        bounded-work-then-fallback discipline, ISSUE 1 tentpole).

        Each attempt rendezvous-picks over the live (breaker-closed)
        endpoint set, excluding endpoints that already failed THIS call;
        transport failures on idempotent methods back off (exponential +
        full jitter) and fail over; non-idempotent methods fail fast —
        the request may have executed server-side and the caller owns
        that ambiguity. Handler errors (plain RPCError) never retry: the
        server answered. Retries/failovers are metered."""
        from ..resilience.policy import (DEFAULT_RETRY_POLICY,
                                         is_idempotent)
        from ..utils.metrics import FABRIC, FabricMetric
        if policy is None:
            policy = DEFAULT_RETRY_POLICY
        if idempotent is None:
            idempotent = is_idempotent(service, method)
        tried_and_failed: set = set()
        attempt = 0
        last_failed: Optional[str] = None
        while True:
            attempt += 1
            addr = self.pick(service, key, exclude=tried_and_failed)
            if addr is None:
                raise RPCTransportError(
                    f"no endpoints for service {service}")
            failed_over = last_failed is not None and addr != last_failed
            if failed_over:
                FABRIC.inc(FabricMetric.RPC_FAILOVERS)
            try:
                return await self.client_for(addr).call(
                    service, method, payload, order_key=order_key,
                    timeout=timeout,
                    trace_tags={"attempt": attempt,
                                "failed_over": failed_over})
            except RPCTransportError as e:
                tried_and_failed.add(addr)
                last_failed = addr
                # a circuit-open refusal was NEVER sent: zero execution
                # ambiguity, so even non-idempotent methods fail over
                retryable = (idempotent
                             or isinstance(e, RPCCircuitOpenError))
                if not retryable or not policy.should_retry(attempt):
                    raise
                FABRIC.inc(FabricMetric.RPC_RETRIES)
                log.debug("retrying %s/%s after %r (attempt %d)",
                          service, method, e, attempt)
                await asyncio.sleep(policy.backoff(attempt, rng))

    async def close(self) -> None:
        for c in self._clients.values():
            await c.close()
        self._clients.clear()

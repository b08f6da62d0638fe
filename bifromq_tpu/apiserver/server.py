"""HTTP management API (≈ bifromq-apiserver).

Reference endpoints (apiserver/http/handler/*: PubHandler.java:62 et al.):
pub / sub / unsub / kill / expire-sessions / retain ops + cluster
introspection. Here a dependency-free asyncio HTTP/1.1 server exposing:

  PUT  /pub?tenant_id=&topic=&qos=&retain=     body = payload
  PUT  /sub?tenant_id=&client_id=&topic_filter=&qos=
  DELETE /unsub?tenant_id=&client_id=&topic_filter=
  DELETE /kill?tenant_id=&client_id=
  DELETE /session?tenant_id=&client_id=         (expire/delete inbox)
  PUT  /retain?tenant_id=&topic=                body = payload ('' clears)
  GET  /cluster                                  (gossip membership, if any)
  GET  /sessions?tenant_id=
  GET  /routes?tenant_id=
  GET  /retained?tenant_id=
  GET  /metrics

Headers (tenant_id etc.) are also accepted in the reference style
(`x-tenant-id`, `x-client-id`...); query params win.
"""

from __future__ import annotations

import asyncio
import json
import logging
import time
from typing import Dict, Optional, Tuple
from urllib.parse import parse_qs, urlsplit

from ..mqtt.broker import MQTTBroker
from ..types import ClientInfo, Message, QoS
from ..utils import topic as topic_util
from ..utils.env import env_float as _env_float
from ..utils.hlc import HLC

log = logging.getLogger("bifromq_tpu.api")


class APIServer:
    def __init__(self, broker: MQTTBroker, host: str = "127.0.0.1",
                 port: int = 0, *, cluster=None, metrics=None,
                 registry=None, clusterview=None) -> None:
        self.broker = broker
        self.host = host
        self.port = port
        self.cluster = cluster
        self.metrics = metrics
        self.registry = registry    # rpc.fabric.ServiceRegistry (clustered)
        self.clusterview = clusterview  # obs.clusterview.ClusterView
        self._server: Optional[asyncio.AbstractServer] = None
        # ISSUE 8 satellite: periodic merged /cluster/tenants cache —
        # (monotonic stamp, full merged payload); served with max-age /
        # age headers instead of scatter-gathering per request
        self._tenants_cache: Optional[Tuple[float, dict]] = None

    async def start(self) -> None:
        self._server = await asyncio.start_server(self._on_client, self.host,
                                                  self.port)
        self.port = self._server.sockets[0].getsockname()[1]

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()

    # ---------------- http plumbing ----------------------------------------

    async def _on_client(self, reader: asyncio.StreamReader,
                         writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                request = await self._read_request(reader)
                if request is None:
                    break
                method, path, headers, body = request
                result = await self._route(method, path, headers, body)
                # handlers return (status, payload) or, when they carry
                # response headers (ISSUE 8: the tenants cache's max-age
                # / age pair), (status, payload, extra_headers)
                if len(result) == 3:
                    status, payload, extra = result
                else:
                    status, payload = result
                    extra = {}
                data = json.dumps(payload).encode() + b"\n"
                reason = {200: "OK", 400: "Bad Request", 404: "Not Found",
                          429: "Too Many Requests",
                          500: "Internal Server Error"}.get(status, "Status")
                head = (f"HTTP/1.1 {status} {reason}\r\n"
                        f"content-type: application/json\r\n")
                for k, v in extra.items():
                    head += f"{k}: {v}\r\n"
                writer.write(
                    (head + f"content-length: {len(data)}\r\n\r\n").encode()
                    + data)
                await writer.drain()
                if headers.get("connection", "").lower() == "close":
                    break
        except (ConnectionError, asyncio.IncompleteReadError, ValueError):
            pass
        finally:
            try:
                writer.close()
            except Exception:  # noqa: BLE001
                pass

    async def _read_request(self, reader: asyncio.StreamReader):
        line = await reader.readline()
        if not line:
            return None
        try:
            method, path, _ = line.decode().split(" ", 2)
        except ValueError:
            return None
        headers: Dict[str, str] = {}
        while True:
            h = await reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            k, _, v = h.decode().partition(":")
            headers[k.strip().lower()] = v.strip()
        body = b""
        n = int(headers.get("content-length", "0") or 0)
        if n:
            body = await reader.readexactly(n)
        return method.upper(), path, headers, body

    # ---------------- routing ----------------------------------------------

    async def _route(self, method: str, path: str, headers: Dict[str, str],
                     body: bytes) -> Tuple[int, object]:
        url = urlsplit(path)
        q = {k: v[0] for k, v in parse_qs(url.query).items()}

        def arg(name: str, default: Optional[str] = None) -> Optional[str]:
            return q.get(name, headers.get(f"x-{name.replace('_', '-')}",
                                           default))

        route = (method, url.path)
        try:
            if route == ("PUT", "/pub"):
                return await self._pub(arg, body)
            if route == ("PUT", "/sub"):
                return await self._sub(arg)
            if route == ("DELETE", "/unsub"):
                return await self._unsub(arg)
            if route == ("DELETE", "/kill"):
                return await self._kill(arg)
            if route == ("DELETE", "/session"):
                return await self._expire_session(arg)
            if route == ("PUT", "/retain"):
                return await self._retain(arg, body)
            if route == ("GET", "/cluster"):
                return self._cluster_info()
            if route == ("GET", "/cluster/tenants"):
                return await self._cluster_tenants(arg)
            if route == ("GET", "/cluster/capacity"):
                return self._cluster_capacity()
            if route == ("GET", "/cluster/slo"):
                return self._cluster_slo()
            if route == ("GET", "/capacity"):
                return self._capacity_get(arg)
            if route == ("GET", "/replication"):
                # ISSUE 12: delta-stream status — per-range heads on the
                # hosting worker, standby cursors/lag, puller cursors
                from .. import replication
                return 200, replication.status_report()
            if route == ("GET", "/profile"):
                return self._profile_get(arg)
            if method == "GET" and url.path.startswith("/cluster/trace/"):
                return await self._cluster_trace(
                    url.path[len("/cluster/trace/"):], arg)
            if route == ("GET", "/cluster/route"):
                return self._cluster_route(arg)
            if route == ("GET", "/sessions"):
                return self._sessions(arg)
            if route == ("GET", "/inbox-state"):
                return await self._inbox_state(arg)
            if route == ("GET", "/routes"):
                return self._routes(arg)
            if route == ("GET", "/retained"):
                return self._retained(arg)
            if route == ("GET", "/mesh"):
                return self._mesh_get()
            if route == ("GET", "/mesh/rebalance"):
                return self._mesh_rebalance(arg)
            if route == ("GET", "/mesh/migrations"):
                return self._mesh_migrations()
            if route == ("GET", "/mesh/autoscaler"):
                return self._mesh_autoscaler(arg)
            if route == ("GET", "/replication/lag"):
                return self._replication_lag(arg)
            if route == ("GET", "/slo"):
                return self._slo_get(arg)
            if route == ("GET", "/metrics"):
                return self._metrics_get(arg)
            if route == ("GET", "/tenants"):
                return self._tenants_ranked(arg)
            if method == "GET" and url.path.startswith("/tenants/"):
                from urllib.parse import unquote
                return self._tenant_detail(
                    unquote(url.path[len("/tenants/"):]))
            if route == ("GET", "/obs"):
                return self._obs_state()
            if route == ("PUT", "/obs"):
                return self._obs_config(arg)
            if route == ("GET", "/trace"):
                return self._trace_get(arg, slow=False)
            if route == ("GET", "/trace/slow"):
                return self._trace_get(arg, slow=True)
            if route == ("PUT", "/trace"):
                return self._trace_config(arg)
            if route == ("GET", "/ranges"):
                return self._ranges()
            if route == ("GET", "/balancer"):
                return self._balancer_state()
            if route == ("PUT", "/balancer"):
                return self._balancer_toggle(arg)
            if route == ("PUT", "/balancer-rules"):
                return self._balancer_rules_set(arg, body)
            if route == ("GET", "/traffic"):
                return self._traffic_get()
            if route == ("PUT", "/traffic"):
                return self._traffic_set(arg, body)
            if route == ("DELETE", "/traffic"):
                return self._traffic_unset(arg)
            return 404, {"error": f"no route {method} {url.path}"}
        except KeyError as e:
            return 400, {"error": f"missing parameter {e}"}
        except ValueError as e:
            return 400, {"error": f"bad parameter: {e}"}
        except Exception as e:  # noqa: BLE001 — a handler bug must surface
            log.exception("api handler failed: %s %s", method, url.path)
            return 500, {"error": repr(e)}

    # ---------------- handlers ---------------------------------------------

    async def _pub(self, arg, body: bytes) -> Tuple[int, object]:
        tenant = arg("tenant_id") or "DevOnly"
        topic = arg("topic")
        if not topic or not topic_util.is_valid_topic(topic):
            return 400, {"error": "invalid topic"}
        qos = int(arg("qos", "0"))
        msg = Message(message_id=0, pub_qos=QoS(qos), payload=body,
                      timestamp=HLC.INST.get(),
                      is_retain=arg("retain", "false") == "true")
        publisher = ClientInfo(tenant_id=tenant, type="API")
        if msg.is_retain and self.broker.retain_service is not None:
            await self.broker.retain_service.retain(publisher, topic, msg)
        result = await self.broker.dist.pub(publisher, topic, msg)
        return 200, {"fanout": result.fanout}

    async def _sub(self, arg) -> Tuple[int, object]:
        """Sub-on-behalf (≈ SessionDictService.sub): a LIVE session gets
        the subscription through its own session object (permission checks,
        retained delivery, route registration all apply); only an OFFLINE
        persistent session falls back to the direct inbox write."""
        tenant = arg("tenant_id") or "DevOnly"
        client_id = arg("client_id")
        tf = arg("topic_filter")
        if not client_id or not tf:
            return 400, {"error": "client_id and topic_filter required"}
        if not topic_util.is_valid_topic_filter(tf):
            return 400, {"error": "invalid topic filter"}
        qos = int(arg("qos", "0"))
        res = await self._live_on_behalf("sub", tenant, client_id, tf, qos)
        if res is not None and res != "no_session":
            code = 200 if res in ("ok", "exists") else 403
            return code, {"result": res, "live": True}
        from ..types import TopicFilterOption
        res = await self.broker.inbox.sub(tenant, client_id, tf,
                                    TopicFilterOption(qos=QoS(qos)))
        if res == "no_inbox":
            return 404, {"error": "no such session (live or persistent)"}
        return 200, {"result": res}

    async def _unsub(self, arg) -> Tuple[int, object]:
        tenant = arg("tenant_id") or "DevOnly"
        client_id = arg("client_id")
        tf = arg("topic_filter")
        if not client_id or not tf:
            return 400, {"error": "client_id and topic_filter required"}
        res = await self._live_on_behalf("unsub", tenant, client_id, tf)
        if res is not None and res != "no_session":
            code = 200 if res == "ok" else (404 if res == "no_sub" else 403)
            return code, {"result": res, "live": True}
        removed = await self.broker.inbox.unsub(tenant, client_id, tf)
        return (200 if removed else 404), {"removed": removed}

    async def _live_on_behalf(self, op: str, tenant: str, client_id: str,
                              tf: str, qos: int = 0):
        """Try the live session: local registry first, then the cluster
        session dict. Returns a result name or None/no_session."""
        session = self.broker.session_registry.get(tenant, client_id)
        if session is not None and not session.closed:
            if op == "sub":
                return await session.admin_sub(tf, qos)
            return await session.admin_unsub(tf)
        sd = getattr(self.broker, "session_dict", None)
        if sd is not None:
            try:
                if op == "sub":
                    return await sd.sub(tenant, client_id, tf, qos)
                return await sd.unsub(tenant, client_id, tf)
            except Exception:  # noqa: BLE001 — dict unavailable: fall back
                return None
        return None

    async def _inbox_state(self, arg) -> Tuple[int, object]:
        """Live-session state (≈ SessionDictService.inboxState)."""
        tenant = arg("tenant_id") or "DevOnly"
        client_id = arg("client_id")
        if not client_id:
            return 400, {"error": "client_id required"}
        session = self.broker.session_registry.get(tenant, client_id)
        if session is not None and not session.closed:
            return 200, session.inbox_state()
        sd = getattr(self.broker, "session_dict", None)
        if sd is not None:
            try:
                state = await sd.inbox_state(tenant, client_id)
            except Exception:  # noqa: BLE001
                state = None
            if state is not None:
                return 200, state
        return 404, {"error": "no live session"}

    async def _kill(self, arg) -> Tuple[int, object]:
        tenant = arg("tenant_id") or "DevOnly"
        client_id = arg("client_id")
        session = self.broker.session_registry.get(tenant, client_id or "")
        if session is None:
            return 404, {"error": "not connected"}
        await session.kick()
        return 200, {"killed": client_id}

    async def _expire_session(self, arg) -> Tuple[int, object]:
        tenant = arg("tenant_id") or "DevOnly"
        client_id = arg("client_id")
        existed = self.broker.inbox.store.exists(tenant, client_id or "")
        await self.broker.inbox.delete(tenant, client_id or "")
        return (200 if existed else 404), {"deleted": existed}

    async def _retain(self, arg, body: bytes) -> Tuple[int, object]:
        tenant = arg("tenant_id") or "DevOnly"
        topic = arg("topic")
        if not topic or not topic_util.is_valid_topic(topic):
            return 400, {"error": "invalid topic"}
        msg = Message(message_id=0, pub_qos=QoS.AT_MOST_ONCE, payload=body,
                      timestamp=HLC.INST.get(), is_retain=True)
        ok = await self.broker.retain_service.retain(
            ClientInfo(tenant_id=tenant, type="API"), topic, msg)
        return (200 if ok else 429), {"retained": ok and bool(body)}

    # -- flight recorder (ISSUE 2: /trace, /trace/slow + sampling knobs) ----

    def _trace_get(self, arg, slow: bool) -> Tuple[int, object]:
        from .. import trace as tr
        spans = tr.TRACER.export(trace_id=arg("trace_id"),
                                 tenant=arg("tenant_id"),
                                 limit=int(arg("limit", "256")),
                                 slow=slow)
        return 200, {"count": len(spans),
                     "dropped": (tr.TRACER.slow_ring if slow
                                 else tr.TRACER.ring).dropped,
                     "sampling": tr.TRACER.sampler.snapshot(),
                     "slow_ms": tr.TRACER.slow_ms,
                     "spans": spans}

    def _trace_config(self, arg) -> Tuple[int, object]:
        """Runtime sampling knobs: ``rate`` (0..1, per-tenant when
        ``tenant_id`` is given, else the process default) and ``slow_ms``
        (0 disarms the always-on-slow capture)."""
        from .. import trace as tr
        # parse EVERYTHING before applying anything: a 400 on a bad knob
        # must not leave sampling half-reconfigured
        rate = arg("rate")
        r = float(rate) if rate is not None else None
        slow = arg("slow_ms")
        v = float(slow) if slow is not None else None
        if r is not None:
            tenant = arg("tenant_id")
            if tenant:
                tr.TRACER.sampler.set_rate(tenant, r)
            else:
                tr.TRACER.sampler.default_rate = r
        if v is not None:
            tr.TRACER.slow_ms = v if v > 0 else None
        return 200, {"sampling": tr.TRACER.sampler.snapshot(),
                     "slow_ms": tr.TRACER.slow_ms}

    # -- tenant SLO surface (ISSUE 3: /tenants, /tenants/<id>, /obs) --------

    def _metrics_get(self, arg) -> Tuple[int, object]:
        """/metrics: the registry snapshot composed with the obs-layer
        sections (composition lives HERE so utils.metrics stays below the
        obs hub). ``?tenant=<id>`` is the lean per-tenant scrape — that
        tenant's counters + SLO window, no fabric/stages/device payload."""
        from ..obs import OBS
        if self.metrics is None:
            return 200, {}
        tenant = arg("tenant")
        snap = self.metrics.snapshot(tenant=tenant)
        if tenant is not None:
            snap["slo"] = ({tenant: OBS.windows.snapshot_tenant(tenant)}
                           if OBS.enabled else {})
        else:
            snap["device"] = OBS.device_snapshot()
            snap["obs"] = OBS.obs_snapshot()
            # ISSUE 13: retained scan planes + drain governors (absent
            # key when neither exists — lean default scrape)
            retained = OBS.retained_snapshot()
            if retained["scan_planes"] or retained["drain_governors"]:
                snap["retained"] = retained
            # ISSUE 17: mesh shard-load rows + in-flight migrations
            # (absent key on single-chip nodes — lean default scrape)
            mesh = OBS.mesh_snapshot()
            if mesh:
                snap["mesh"] = {"shard_load": mesh}
            # ISSUE 10: graftcheck build-info (rule count, suppression
            # count, last-run hash) — two live nodes disagreeing on the
            # hash are running different code or different suppressions
            from ..analysis import build_info
            snap["build_info"] = {"graftcheck": build_info()}
        return 200, snap

    def _mesh_get(self) -> Tuple[int, object]:
        """/mesh: every live mesh matcher's shard map — per-shard load
        rows (bytes / logical subs / heat / queue pressure / breaker),
        skew, map version, in-flight migrations, pins and replicas
        (ISSUE 17). 404 on a single-chip node: there is no shard map."""
        from ..obs import OBS
        meshes = OBS.mesh_snapshot()
        if not meshes:
            return 404, {"error": "no mesh matcher on this node"}
        return 200, {"meshes": meshes}

    def _mesh_rebalance(self, arg) -> Tuple[int, object]:
        """/mesh/rebalance: the rebalancer's decision log — executed
        moves (tenant/src/dst, skew before/after, capacity vetoes) and
        the live skew it would act on next. Read-only: driving a
        migration is a control-plane call, not a scrape side effect."""
        from ..obs import OBS
        top_k = int(arg("top_k", "10"))
        if top_k < 0:
            return 400, {"error": f"top_k={top_k} (must be >= 0)"}
        out = []
        for m in OBS.device.matchers():
            status = getattr(m, "mesh_status", None)
            if status is None:
                continue
            try:
                s = status()
            except Exception:  # noqa: BLE001 — telemetry must not raise
                continue
            reb = getattr(m, "mesh_rebalancer", None)
            out.append({
                "skew": s.get("skew"),
                "map_version": s.get("map_version"),
                "migrating": s.get("migrating", {}),
                "decisions": (list(reb.decisions)[-top_k:]
                              if reb is not None else []),
            })
        if not out:
            return 404, {"error": "no mesh matcher on this node"}
        return 200, {"rebalancers": out}

    def _mesh_migrations(self) -> Tuple[int, object]:
        """/mesh/migrations: the live-migration ladder, rung by rung —
        per in-flight migration the copy-stream progress (chunks, rows,
        bytes, %), the dual-serve-window duration and the current rung;
        per retired migration the per-rung timings and the abort
        attribution (ISSUE 18). 404 on a single-chip node."""
        from ..obs import OBS
        from ..parallel.reshard import migration_digest
        out = []
        for m in OBS.device.matchers():
            if getattr(m, "mesh_status", None) is None:
                continue
            active = [mig.progress() for mig in
                      getattr(m, "migrations_inflight", {}).values()]
            out.append({
                "digest": migration_digest(m),
                "active": active,
                "history": list(getattr(m, "migration_history", [])),
            })
        if not out:
            return 404, {"error": "no mesh matcher on this node"}
        return 200, {"migrations": out}

    def _mesh_autoscaler(self, arg) -> Tuple[int, object]:
        """/mesh/autoscaler: the unattended scaling loop's knobs and its
        bounded decision ring — every grow/rebalance/shrink/veto with
        the exact signal snapshot it acted on (ISSUE 18 provenance:
        'why did the mesh grow at 3am' is answerable from one GET)."""
        from ..obs import OBS
        top_k = int(arg("top_k", "32"))
        if top_k < 0:
            return 400, {"error": f"top_k={top_k} (must be >= 0)"}
        out = []
        for m in OBS.device.matchers():
            scaler = getattr(m, "mesh_autoscaler", None)
            if scaler is None:
                continue
            st = scaler.status()
            st["decisions"] = st["decisions"][-top_k:]
            out.append(st)
        if not out:
            return 404, {"error": "no autoscaler on this node"}
        return 200, {"autoscalers": out}

    def _replication_lag(self, arg) -> Tuple[int, object]:
        """/replication/lag: the ISSUE 18 lag plane — per (origin,
        range) stream the windowed apply-lag histogram, throughput,
        reorder occupancy, resync/gap counters and the stale flag, plus
        the recent delta-plane event journal."""
        from ..obs.lag import LAG, REPL_EVENTS
        top_k = int(arg("events", "64"))
        if top_k < 0:
            return 400, {"error": f"events={top_k} (must be >= 0)"}
        snap = LAG.snapshot()
        snap["events"] = REPL_EVENTS.tail(top_k)
        return 200, snap

    def _slo_get(self, arg) -> Tuple[int, object]:
        """``GET /slo``: the ISSUE 20 delivery-SLO plane — per-tenant
        multi-window burn-rate state (objectives, fast/slow burns, the
        burning set), the full-population publish→deliver latency
        histograms per (tenant, qos, path) with violation counters and
        degraded attribution, plus the recent SLO_BURN / SLO_RECOVERED
        journal (``?events=`` caps the tail)."""
        from ..obs import OBS
        from ..obs.burnrate import SLO_EVENTS
        top_k = int(arg("events", "64"))
        if top_k < 0:
            return 400, {"error": f"events={top_k} (must be >= 0)"}
        return 200, {"burn": OBS.burnrate.snapshot(),
                     "e2e": OBS.e2e.snapshot(),
                     "events": SLO_EVENTS.tail(top_k)}

    def _tenants_ranked(self, arg) -> Tuple[int, object]:
        """Live noisy-neighbor ranking over the windowed RED state: top-K
        tenants by blended contention score, flags included. Evaluation
        also refreshes the throttler advisory and emits NOISY_TENANT /
        SLOW_TENANT events (cooldown-limited)."""
        from ..obs import OBS
        top_k = int(arg("top_k", "10"))
        if top_k < 0:
            return 400, {"error": f"top_k={top_k} (must be >= 0)"}
        return 200, OBS.tenants_snapshot(top_k=top_k)

    def _tenant_detail(self, tenant: str) -> Tuple[int, object]:
        """One tenant's full SLO state: windowed RED + per-stage windows,
        the ranked row (score/shares/flags), and the monotonic counters."""
        from ..obs import OBS
        if not tenant:
            return 400, {"error": "tenant id required"}
        windows = OBS.windows.snapshot_tenant(tenant)
        row = OBS.detector.score_tenant(tenant) if OBS.enabled else None
        counters = {}
        if self.metrics is not None:
            counters = self.metrics.tenant_counters(tenant)
        # ISSUE 20: burn-rate state + e2e delivery latency ride the view
        burn = OBS.burnrate.snapshot_tenant(tenant)
        e2e = OBS.e2e.snapshot_tenant(tenant)
        if not windows and not counters and not burn and not e2e:
            return 404, {"error": f"no SLO state for tenant {tenant!r}"}
        return 200, {"tenant": tenant,
                     "window_s": OBS.windows.window_s,
                     "slo": windows,
                     "rank": row,
                     "counters": counters,
                     "burn": burn,
                     "e2e": e2e}

    def _obs_state(self) -> Tuple[int, object]:
        from ..obs import OBS
        return 200, {**OBS.obs_snapshot(),
                     "window_s": OBS.windows.window_s,
                     "noisy_threshold": OBS.detector.noisy_threshold,
                     "slow_p99_ms": OBS.detector.slow_p99_ms,
                     "detector": OBS.detector.config_snapshot(),
                     # ISSUE 20: the burn engine's live config rides the
                     # same state view PUT /obs returns
                     "slo": OBS.burnrate.snapshot()}

    def _obs_config(self, arg) -> Tuple[int, object]:
        """Runtime SLO knobs: ``windows`` (0/1 toggles the window layer),
        ``noisy_threshold``, ``slow_p99_ms``, blend weights (``w_fanout``
        / ``w_queue_wait`` / ``w_errors``). With ``tenant_id`` set the
        threshold/weight knobs install a per-tenant override instead
        (ISSUE 5 satellite; ``clear=1`` drops that tenant's overrides).
        ISSUE 20 adds the burn-rate engine's knobs: process-wide
        ``slo_fast_window_s`` / ``slo_slow_window_s`` /
        ``slo_burn_threshold`` / ``slo_cooldown_s`` / ``slo_p99_ms`` /
        ``slo_success``; with ``tenant_id`` set, ``slo_p99_ms`` /
        ``slo_success`` install a per-tenant objective instead.
        Parse everything before applying anything (same contract as
        PUT /trace)."""
        from ..obs import OBS
        det = OBS.detector
        raw_windows = arg("windows")
        windows = None
        if raw_windows is not None:
            low = raw_windows.lower()
            if low in ("1", "true", "on"):
                windows = True
            elif low in ("0", "false", "off"):
                windows = False
            else:
                return 400, {"error": f"windows={raw_windows!r}"}
        knobs = {}
        for name in sorted(det.TENANT_KNOBS):
            raw = arg(name)
            if raw is not None:
                knobs[name] = float(raw)      # ValueError → 400 upstream
        slo = {}
        for qname, kname in (("slo_fast_window_s", "fast_window_s"),
                             ("slo_slow_window_s", "slow_window_s"),
                             ("slo_burn_threshold", "burn_threshold"),
                             ("slo_cooldown_s", "cooldown_s"),
                             ("slo_p99_ms", "p99_ms"),
                             ("slo_success", "success")):
            raw = arg(qname)
            if raw is not None:
                slo[kname] = float(raw)       # ValueError → 400 upstream
        tenant = arg("tenant_id")
        if tenant and any(k not in ("p99_ms", "success") for k in slo):
            return 400, {"error": "per-tenant SLO overrides accept only "
                                  "slo_p99_ms / slo_success"}
        if windows is not None:       # process-wide regardless of tenant
            OBS.enabled = windows
        if tenant:
            # clear-then-set: ?clear=1&slow_p99_ms=150 drops the old
            # override and installs the new knob, never discards it
            if arg("clear") in ("1", "true"):
                det.clear_tenant(tenant)
                OBS.burnrate.clear_tenant(tenant)
            if knobs:
                det.configure_tenant(tenant, **knobs)
            if slo:
                OBS.burnrate.configure_tenant(tenant, **slo)
        else:
            # process-wide defaults: noisy_threshold / slow_p99_ms / w_*
            for name, v in knobs.items():
                setattr(det, name, v)
            if slo:
                OBS.burnrate.configure(**slo)
        return self._obs_state()

    def _cluster_info(self) -> Tuple[int, object]:
        """``GET /cluster``: the merged node table (ISSUE 5) — liveness,
        gossiped health digest + its age, and hosted agents per member.
        Falls back to the plain membership table when no cluster view is
        wired (and to standalone when there is no cluster at all)."""
        if self.cluster is None:
            return 200, {"mode": "standalone"}
        if self.clusterview is not None:
            return 200, {"mode": "cluster",
                         "self": self.clusterview.node_id,
                         "unhealthy_endpoints":
                             self.clusterview.unhealthy_endpoints(),
                         "members": self.clusterview.cluster_table()}
        return 200, {
            "mode": "cluster",
            "members": {m.node_id: {"status": m.status,
                                    "agents": sorted(m.agents)}
                        for m in self.cluster.members.values()},
        }

    async def _cluster_tenants(self, arg) -> Tuple:
        """``GET /cluster/tenants``: per-tenant RED merged across every
        node (scatter-gather under a deadline budget; log2 histograms
        merged bucket-wise). Standalone/unwired nodes degrade to a
        local-only view with the same shape.

        ISSUE 8 satellite: the merged view is CACHED — a scrape loop or
        dashboard polling every second no longer fans an RPC out to
        every node per request. The full (top_k=0) merge is cached for
        ``BIFROMQ_CLUSTER_TENANTS_TTL_S`` (request override:
        ``?max_age_s=``, 0 forces a refresh); top_k filtering applies
        per request on the cached rows, and the response carries
        ``cache-control: max-age`` + ``age`` headers so consumers can
        see exactly how fresh the merge is."""
        top_k = int(arg("top_k", "0"))
        timeout_s = float(arg("timeout_s", "2.0"))
        ttl = float(arg("max_age_s", "") or _env_float(
            "BIFROMQ_CLUSTER_TENANTS_TTL_S", 2.0))
        now = time.monotonic()
        cached = self._tenants_cache
        if cached is not None and ttl > 0 and now - cached[0] < ttl:
            age = now - cached[0]
            out = cached[1]
        else:
            out = await self._cluster_tenants_fetch(timeout_s)
            self._tenants_cache = (now, out)
            age = 0.0
        payload = dict(out)
        rows = payload.get("tenants") or {}
        if top_k > 0:       # filter per request; the cache stays full
            keep = sorted(rows,
                          key=lambda t: -rows[t]["rate_per_s"])[:top_k]
            payload["tenants"] = {t: rows[t] for t in keep}
        payload["cache"] = {"age_s": round(age, 3), "max_age_s": ttl}
        return 200, payload, {"cache-control": f"max-age={ttl:g}",
                              "age": f"{age:.3f}"}

    async def _cluster_tenants_fetch(self, timeout_s: float) -> dict:
        """One full (unfiltered) merge — the cache's fill path."""
        if self.clusterview is not None:
            return await self.clusterview.federated_tenants(
                timeout_s=timeout_s, top_k=0)
        from ..obs import OBS
        from ..obs.clusterview import derive_red_row, merge_tenant_raws
        merged = merge_tenant_raws(
            [OBS.windows.raw_snapshot() if OBS.enabled else {}])
        rows = {t: derive_red_row(r, OBS.windows.window_s)
                for t, r in merged.items()}
        return {"window_s": OBS.windows.window_s,
                "nodes": {OBS.node_id: "local"},
                "tenants": rows}

    # -- capacity & profiling plane (ISSUE 8) -------------------------------

    def _capacity_get(self, arg) -> Tuple[int, object]:
        """``GET /capacity``: model-vs-live byte parity for every
        registered matcher, guarded HBM stats, planner coefficients;
        ``?n_subs=`` (+ optional ``shards=``) adds a full ``fits``
        verdict (HBM headroom), computed without dispatching
        anything. ``?calibrate=1`` (ISSUE 11
        satellite, ROADMAP sharding follow-up (c)) re-fits the per-sub
        coefficients from the live base with its true logical sub count
        and reports old-vs-new deltas; the ``fits`` verdict then uses
        the re-fit planner."""
        from ..obs.capacity import capacity_report
        kw = {}
        n_subs = arg("n_subs")
        if n_subs is not None:
            kw["n_subs"] = int(n_subs)
        shards = arg("shards")
        if shards is not None:
            kw["mesh"] = int(shards)
        if arg("calibrate", "0") in ("1", "true"):
            kw["calibrate"] = True
        return 200, capacity_report(
            memory=arg("memory", "1") != "0", **kw)

    def _profile_get(self, arg) -> Tuple[int, object]:
        """``GET /profile``: the continuous profiler's live snapshot —
        host-clock tokenize/dispatch/ready/fetch/expand split, padding
        waste, dedup savings, cache bypasses, the compile-event ledger,
        and segment-store state."""
        from ..obs import OBS
        return 200, OBS.profile_snapshot(
            brief=arg("brief", "0") in ("1", "true"))

    def _cluster_capacity(self) -> Tuple[int, object]:
        """``GET /cluster/capacity``: per-node capacity federated from
        the gossiped health digests (no scatter-gather RPC)."""
        if self.clusterview is not None:
            return 200, self.clusterview.capacity_table()
        from ..obs import OBS
        from ..obs.capacity import digest_capacity
        local = digest_capacity(OBS)
        ls = int(local.get("logical_subs", 0))
        return 200, {"nodes": {OBS.node_id: {"capacity": local,
                                             "stale": False,
                                             "self": True}},
                     "total_table_bytes": local.get("table_bytes", 0),
                     "max_mem_peak_bytes": local.get("mem_peak_bytes", 0),
                     "logical_subs": {"sum": ls, "dedup": ls,
                                      "replica_groups": 1 if ls else 0}}

    def _cluster_slo(self) -> Tuple[int, object]:
        """``GET /cluster/slo``: per-node burn summaries federated from
        the gossiped health digests (no scatter-gather RPC) — which
        tenants are burning anywhere in the cluster, and the worst
        burner per node."""
        from ..obs import OBS
        local = OBS.burnrate.summary()
        nodes = {OBS.node_id: {"slo": local, "stale": False,
                               "self": True}}
        if self.clusterview is not None:
            for node, p in self.clusterview.peers().items():
                nodes[node] = {"slo": (p["digest"] or {}).get("slo", {}),
                               "stale": p["stale"]}
        burning = sorted({t for n in nodes.values()
                          for t in (n["slo"] or {}).get("burning", [])})
        return 200, {"nodes": nodes, "burning": burning}

    async def _cluster_trace(self, trace_id: str, arg) -> Tuple[int, object]:
        """``GET /cluster/trace/<id>``: the full cross-process trace,
        every peer's span rings queried and the union ordered by HLC."""
        if not trace_id:
            return 400, {"error": "trace id required"}
        timeout_s = float(arg("timeout_s", "2.0"))
        if self.clusterview is not None:
            return 200, await self.clusterview.federated_trace(
                trace_id, timeout_s=timeout_s)
        from .. import trace as tr
        from ..obs import OBS
        spans = tr.TRACER.export(trace_id=trace_id, limit=1000)
        return 200, {"trace_id": trace_id, "count": len(spans),
                     "nodes": {OBS.node_id: "local"},
                     "processes": 1 if spans else 0,
                     "spans": [dict(s, node=OBS.node_id) for s in spans]}

    def _cluster_route(self, arg) -> Tuple[int, object]:
        """``GET /cluster/route?service=&key=``: where would this tenant
        key route right now? Operator introspection for the health-aware
        rendezvous pick (and the tier-2 cluster gate's probe)."""
        if self.registry is None:
            return 404, {"error": "no service registry (standalone mode)"}
        service = arg("service")
        if not service:
            return 400, {"error": "missing parameter 'service'"}
        key = arg("key") or ""
        rh = self.registry.remote_health
        return 200, {
            "service": service,
            "key": key,
            "endpoint": self.registry.pick(service, key),
            "endpoints": self.registry.endpoints(service),
            "unhealthy": (rh.unhealthy_endpoints()
                          if rh is not None
                          and hasattr(rh, "unhealthy_endpoints") else []),
        }

    def _sessions(self, arg) -> Tuple[int, object]:
        tenant = arg("tenant_id") or "DevOnly"
        online = self.broker.session_registry.client_ids(tenant)
        persistent = [i for t, i, m in self.broker.inbox.store.all_inboxes()
                      if t == tenant]
        return 200, {"online": sorted(online),
                     "persistent": sorted(persistent)}

    def _ranges(self) -> Tuple[int, object]:
        """Per-range observability (≈ KVRangeMetricManager): key counts,
        raft health, and the load profile feeding the split hinters —
        for the dist, inbox, and retain stores."""
        from ..kv.metrics import range_stats

        out = {}
        worker_store = getattr(self.broker.dist.worker, "store", None)
        if worker_store is not None:
            out["dist"] = range_stats(worker_store)
        inbox_store = getattr(self.broker.inbox, "kvstore", None)
        if inbox_store is not None:
            out["inbox"] = range_stats(inbox_store)
        retain_store = getattr(self.broker.retain_service, "kvstore", None)
        if retain_store is not None:
            out["retain"] = range_stats(retain_store)
        return 200, out

    # -- balancer admin (≈ apiserver balancer enable/disable/state handlers)

    def _controllers(self) -> Dict[str, object]:
        out: Dict[str, object] = {}
        ctl = getattr(getattr(self.broker.dist, "worker", None),
                      "balance_controller", None)
        if ctl is not None:
            out["dist"] = ctl
        for name, svc in (("inbox", self.broker.inbox),
                          ("retain", self.broker.retain_service)):
            c = getattr(svc, "balance_controller", None)
            if c is not None:
                out[name] = c
        return out

    def _balancer_state(self) -> Tuple[int, object]:
        return 200, {name: c.state()
                     for name, c in self._controllers().items()}

    def _balancer_rules_set(self, arg, body: bytes) -> Tuple[int, object]:
        """Install declarative placement rules on a store's controller
        (≈ KVStoreBalanceController.updateLoadRules via the reference's
        PUT LoadRules admin API). Body: the rule JSON document."""
        try:
            rules = json.loads(body.decode() or "{}")
        except ValueError:
            return 400, {"error": "body must be a JSON rule document"}
        target = arg("store")      # omit = all rule-capable controllers
        hit = []
        for name, c in self._controllers().items():
            if target in (None, name):
                if not hasattr(c, "set_rules"):
                    if target == name:
                        return 400, {"error":
                                     f"controller {name!r} takes no rules"}
                    continue
                err = c.set_rules(rules)
                if err is not None:
                    return 400, {"error": err}
                hit.append(name)
        if not hit:
            return 404, {"error": f"no rule-capable controller {target!r}"}
        return 200, {"rules": rules, "stores": hit}

    def _balancer_toggle(self, arg) -> Tuple[int, object]:
        raw = (arg("enable") or "true").lower()
        if raw in ("1", "true", "yes", "on"):
            enable = True
        elif raw in ("0", "false", "no", "off"):
            enable = False
        else:
            # a typo must not silently disable elasticity cluster-wide
            return 400, {"error": f"enable={raw!r} (use true|false)"}
        target = arg("store")      # omit = all
        hit = []
        for name, c in self._controllers().items():
            if target in (None, name):
                c.enabled = enable
                hit.append(name)
        if not hit:
            return 404, {"error": f"no balance controller {target!r}"}
        return 200, {"enabled": enable, "stores": hit}

    # -- traffic directives (≈ apiserver traffic-rules handlers over the
    #    RPC traffic governor)

    def _traffic_get(self) -> Tuple[int, object]:
        if self.registry is None:
            return 404, {"error": "no service registry (standalone mode)"}
        return 200, self.registry.traffic_directives()

    def _traffic_set(self, arg, body: bytes) -> Tuple[int, object]:
        if self.registry is None:
            return 404, {"error": "no service registry (standalone mode)"}
        service = arg("service")
        if not service:
            return 400, {"error": "missing parameter 'service'"}
        groups = json.loads(body or b"{}")
        # a bad weight stored here would TypeError inside every routed RPC
        # for matching tenants — reject at the admin boundary instead
        if (not isinstance(groups, dict)
                or not all(isinstance(w, int) and not isinstance(w, bool)
                           and w >= 0 for w in groups.values())):
            return 400, {"error": "body must be {server_group: weight>=0}"}
        self.registry.set_traffic_directive(
            service, arg("tenant_prefix") or "", groups)
        return 200, {"ok": True}

    def _traffic_unset(self, arg) -> Tuple[int, object]:
        if self.registry is None:
            return 404, {"error": "no service registry (standalone mode)"}
        service = arg("service")
        if not service:
            return 400, {"error": "missing parameter 'service'"}
        self.registry.unset_traffic_directive(
            service, arg("tenant_prefix") or "")
        return 200, {"ok": True}

    def _routes(self, arg) -> Tuple[int, object]:
        tenant = arg("tenant_id") or "DevOnly"
        trie = self.broker.dist.matcher.tries.get(tenant)
        routes = []
        if trie is not None:
            for r in trie.routes():
                routes.append({"filter": r.matcher.mqtt_topic_filter,
                               "broker": r.broker_id,
                               "receiver": r.receiver_id})
        return 200, {"count": len(routes), "routes": routes[:1000]}

    def _retained(self, arg) -> Tuple[int, object]:
        tenant = arg("tenant_id") or "DevOnly"
        svc = self.broker.retain_service
        topics = svc.topics(tenant) if svc else []
        return 200, {"count": len(topics), "topics": topics[:1000]}

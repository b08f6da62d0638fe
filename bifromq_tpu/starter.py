"""Standalone boot assembly (≈ build-bifromq-starter StandaloneStarter).

``python -m bifromq_tpu --config conf.yml`` parses the YAML config tree,
consolidates defaults (≈ StandaloneConfigConsolidator), assembles the
enabled services (mqtt listeners incl. TLS/WS, API server, durable engine,
cluster membership), and runs until SIGINT — the role of
StandaloneStarter.java:87 + ServiceBootstrapper.java:39.

Config shape (all keys optional):

    mqtt:
      host: 0.0.0.0
      tcp: {port: 1883}
      tls: {port: 8883, cert: server.pem, key: server.key}
      ws:  {port: 8080, path: /mqtt}
    api: {port: 9090}
    data_dir: /var/lib/bifromq-tpu       # durable engine when set
    cluster:
      node_id: node1
      port: 7946
      seeds: ["10.0.0.1:7946"]
    dist:
      split_threshold: 100000            # route-table elasticity knobs
      load_split_threshold: 50000        # (per-range keys / load rate;
      merge_threshold: 1000              #  omit to disable a balancer)
      mesh: true                         # tenant-shard the route table over
                                         # ALL local devices (MeshMatcher);
                                         # default: one device (TpuMatcher)
    inbox:
      split_threshold: 100000            # inbox-keyspace range split
    retain:
      split_threshold: 100000            # retain-keyspace range split
      mode: local | worker | remote      # clustered dist-plane role:
        # local  = in-process worker (default; standalone)
        # worker = host the route table here AND serve it on the RPC
        #          fabric (announced over gossip, ≈ a dist-worker node)
        # remote = frontend-only: the dist plane lives on worker nodes
        #          discovered via gossip (≈ mqtt-frontend role)
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import signal
import ssl as ssl_mod
from typing import Optional

log = logging.getLogger("bifromq_tpu.starter")


def load_config(path: Optional[str]) -> dict:
    if not path:
        return {}
    import yaml
    with open(path) as f:
        return yaml.safe_load(f) or {}


def _tls_context(cfg: dict):
    ctx = ssl_mod.SSLContext(ssl_mod.PROTOCOL_TLS_SERVER)
    ctx.load_cert_chain(cfg["cert"], cfg.get("key"))
    return ctx


class Standalone:
    """Assembled standalone broker node."""

    def __init__(self, config: dict) -> None:
        self.config = config
        self.broker = None
        self.api = None
        self.agent_host = None
        self.rpc_server = None
        self.metrics_registry = None
        self.clusterview = None
        self._isolated_hosts = []

    @staticmethod
    def _apply_obs_config(ocfg: dict) -> None:
        """YAML ``obs:`` section → detector knobs (ISSUE 5 satellite):
        process defaults, blend weights, and per-tenant SLO overrides.

            obs:
              noisy_threshold: 0.5
              slow_p99_ms: 1000
              weights: {fanout: 0.4, queue_wait: 0.4, errors: 0.2}
              tenants:
                latency-sensitive-tenant: {slow_p99_ms: 150}
              slo:                       # ISSUE 20: burn-rate objectives
                p99_ms: 250
                success: 0.999
                fast_window_s: 60
                slow_window_s: 300
                burn_threshold: 2.0
                cooldown_s: 30
                tenants:
                  paying-tenant: {p99_ms: 100, success: 0.9999}
        """
        from .obs import OBS
        det = OBS.detector
        if "noisy_threshold" in ocfg:
            det.noisy_threshold = float(ocfg["noisy_threshold"])
        if "slow_p99_ms" in ocfg:
            det.slow_p99_ms = float(ocfg["slow_p99_ms"])
        weights = ocfg.get("weights") or {}
        for key, attr in (("fanout", "w_fanout"),
                          ("queue_wait", "w_queue_wait"),
                          ("errors", "w_errors")):
            if key in weights:
                setattr(det, attr, float(weights[key]))
        for tenant, knobs in (ocfg.get("tenants") or {}).items():
            det.configure_tenant(str(tenant),
                                 **{k: float(v)
                                    for k, v in (knobs or {}).items()})
        slo = ocfg.get("slo") or {}
        if slo:
            defaults = {k: float(slo[k])
                        for k in ("p99_ms", "success", "fast_window_s",
                                  "slow_window_s", "burn_threshold",
                                  "cooldown_s") if k in slo}
            if defaults:
                OBS.burnrate.configure(**defaults)
            for tenant, knobs in (slo.get("tenants") or {}).items():
                OBS.burnrate.configure_tenant(
                    str(tenant), **{k: float(v)
                                    for k, v in (knobs or {}).items()})

    @staticmethod
    def _load_plugins(pcfg: dict) -> dict:
        """YAML ``plugins:`` section → MQTTBroker plugin kwargs.

        Each entry is ``name: module:Class`` or
        ``name: {path: module:Class, isolated: true}`` (≈ the reference
        starter naming plugin FQCNs in config, BifroMQPluginManager).
        ``isolated: true`` runs the plugin out-of-process
        (plugin/isolated.py) — supported for settings / events /
        user_props; latency-critical SPIs load in-process.
        """
        from .plugin.auth import IAuthProvider
        from .plugin.balancer import IClientBalancer
        from .plugin.events import IEventCollector
        from .plugin.settings import ISettingProvider
        from .plugin.throttler import IResourceThrottler
        from .plugin.userprops import IUserPropsCustomizer
        from .utils.hookloader import load_optional

        kinds = {
            "auth": ("auth", IAuthProvider, None),
            "settings": ("settings", ISettingProvider,
                         "IsolatedSettingProvider"),
            "events": ("events", IEventCollector,
                       "IsolatedEventCollector"),
            "throttler": ("throttler", IResourceThrottler, None),
            "balancer": ("balancer", IClientBalancer, None),
            # user_props runs per-message: isolation's pipe round-trip
            # does not belong on that path — in-process only
            "user_props": ("user_props_customizer", IUserPropsCustomizer,
                           None),
        }
        out = {}
        try:
            for name, spec in (pcfg or {}).items():
                if name not in kinds:
                    raise ValueError(f"unknown plugin kind {name!r} "
                                     f"(one of {sorted(kinds)})")
                kwarg, iface, iso_cls = kinds[name]
                if isinstance(spec, str):
                    spec = {"path": spec}
                path = spec["path"]
                if spec.get("isolated"):
                    if iso_cls is None:
                        raise ValueError(
                            f"plugin kind {name!r} cannot be isolated "
                            "(latency-critical SPI; loads in-process)")
                    from .plugin import isolated as iso
                    if name == "events":
                        # keep an in-process mirror fed: the broker's own
                        # introspection reads the local collector
                        from .plugin.events import CollectingEventCollector
                        out[kwarg] = iso.IsolatedEventCollector(
                            path, mirror=CollectingEventCollector())
                    else:
                        out[kwarg] = getattr(iso, iso_cls)(path)
                else:
                    obj = load_optional(path, iface)
                    if obj is not None:
                        out[kwarg] = obj
        except Exception:
            # a later entry failing must not orphan already-spawned
            # children of earlier entries
            for v in out.values():
                if hasattr(v, "host"):
                    v.host.close()
            raise
        return out

    async def start(self) -> None:
        from .mqtt.broker import MQTTBroker

        cfg = self.config
        mqtt_cfg = cfg.get("mqtt", {})
        host = mqtt_cfg.get("host", "127.0.0.1")
        if cfg.get("obs"):
            # detector knobs + per-tenant SLO overrides: applied before
            # the broker starts so the exporter/detector see them from
            # the first record
            self._apply_obs_config(cfg["obs"])
        engine = None
        if cfg.get("data_dir"):
            from .kv.native import NativeKVEngine
            engine = NativeKVEngine(cfg["data_dir"])

        cluster_cfg = cfg.get("cluster")
        registry = None
        if cluster_cfg:
            from .cluster.membership import AgentHost
            from .rpc.fabric import ServiceRegistry
            seeds = []
            for s in cluster_cfg.get("seeds", []):
                h, p = str(s).rsplit(":", 1)
                seeds.append((h, int(p)))
            # optional TLS on the TCP large-payload plane:
            #   cluster: {tls: {cert: c.pem, key: k.pem, verify: false}}
            tls_srv = tls_cli = None
            tls_cfg = cluster_cfg.get("tls")
            if tls_cfg:
                tls_srv = _tls_context(tls_cfg)
                tls_cli = ssl_mod.SSLContext(ssl_mod.PROTOCOL_TLS_CLIENT)
                if tls_cfg.get("verify", False):
                    # trust store: explicit CA if given, else the cluster's
                    # own cert (self-signed deployments), else system CAs.
                    # check_hostname stays off — peers dial by gossip IP.
                    tls_cli.check_hostname = False
                    ca = tls_cfg.get("ca") or tls_cfg.get("cert")
                    if ca:
                        tls_cli.load_verify_locations(ca)
                    else:
                        tls_cli.load_default_certs()
                else:
                    tls_cli.check_hostname = False
                    tls_cli.verify_mode = ssl_mod.CERT_NONE
            # optional SWIM timing overrides (ISSUE 5):
            #   cluster: {probe_timeout_s: 0.5, suspect_timeout_s: 3.0, …}
            timing = {k: float(cluster_cfg[k]) for k in
                      ("probe_interval_s", "probe_timeout_s",
                       "suspect_timeout_s", "dead_reap_s")
                      if k in cluster_cfg}
            self.agent_host = AgentHost(
                cluster_cfg.get("node_id", "node"),
                host=host, port=int(cluster_cfg.get("port", 0)),
                seeds=seeds, tls_server_ctx=tls_srv, tls_client_ctx=tls_cli,
                **timing)
            await self.agent_host.start()
            registry = ServiceRegistry(agent_host=self.agent_host)
            # identity for the telemetry resource envelope (ISSUE 5
            # satellite) — pinned before the broker starts the exporter
            from .obs import OBS
            OBS.set_identity(
                node_id=self.agent_host.node_id,
                cluster_id=str(cluster_cfg.get("cluster_id", "") or ""))

        # dist-plane role (clustered deployments): a "remote" frontend's
        # route table lives on "worker" nodes discovered over gossip —
        # the reference's mqtt-frontend → dist-worker split in YAML
        dist_cfg = cfg.get("dist", {})
        dist_mode = dist_cfg.get("mode", "local")
        if dist_mode not in ("local", "worker", "remote"):
            raise ValueError(f"unknown dist.mode {dist_mode!r} "
                             "(local | worker | remote)")
        if dist_mode in ("worker", "remote") and registry is None:
            # silently degrading to local would strand every remote
            # frontend with 'no endpoints for dist-worker'
            raise ValueError(f"dist.mode={dist_mode} requires a cluster "
                             "section (discovery rides gossip)")
        elastic = {k: dist_cfg[k] for k in
                   ("split_threshold", "load_split_threshold",
                    "merge_threshold") if k in dist_cfg}
        if dist_cfg.get("mesh"):
            # one shard per local device, no replicas: each device's HBM
            # holds its tenants' tables (BASELINE config 5's layout)
            import jax
            from .parallel.sharded import MeshMatcher, make_mesh
            mesh = make_mesh(1, len(jax.devices()))
            elastic["matcher_factory"] = lambda: MeshMatcher(mesh=mesh)
        dist = None
        if dist_mode == "remote":
            from .dist.remote import RemoteDistWorker
            from .dist.service import DistService
            from .plugin.events import CollectingEventCollector
            from .plugin.settings import DefaultSettingProvider
            from .plugin.subbroker import SubBrokerRegistry
            sub_brokers = SubBrokerRegistry()
            dist = DistService(sub_brokers, CollectingEventCollector(),
                               DefaultSettingProvider(),
                               worker=RemoteDistWorker(registry))

        if dist is not None and elastic:
            # the route table lives on worker NODES in remote mode; the
            # knobs belong in THEIR config — dropping them silently would
            # let an operator believe splits are enabled
            raise ValueError("dist elasticity knobs have no effect with "
                             "dist.mode=remote; set them on the worker "
                             "nodes instead")

        tcp = mqtt_cfg.get("tcp", {"port": 1883})
        tls = mqtt_cfg.get("tls")
        ws = mqtt_cfg.get("ws")
        inbox_cfg = cfg.get("inbox", {})
        retain_cfg = cfg.get("retain", {})
        plug = self._load_plugins(cfg.get("plugins", {}))
        # register spawned children for cleanup IMMEDIATELY: a failing
        # broker.start() below must not orphan plugin processes
        self._isolated_hosts = [
            v.host for v in plug.values() if hasattr(v, "host")]
        # meter EVERY tenant-visible flow (ISSUE 3): the metering collector
        # wraps whatever event collector the operator plugged in, feeding
        # the per-tenant registry the API server serves at /metrics and
        # the windowed SLO layer behind /tenants — without it a starter
        # deployment scraped empty tenant counters
        from .plugin.events import CollectingEventCollector
        from .utils.metrics import MeteringEventCollector, MetricsRegistry
        self.metrics_registry = MetricsRegistry()
        plug["events"] = MeteringEventCollector(
            self.metrics_registry,
            plug.get("events") or CollectingEventCollector())
        self.broker = MQTTBroker(
            **plug,
            host=host, port=int(tcp.get("port", 1883)),
            inbox_engine=engine, dist=dist,
            dist_worker_kwargs=elastic or None,
            inbox_split_threshold=(
                int(inbox_cfg["split_threshold"])
                if "split_threshold" in inbox_cfg else None),
            retain_split_threshold=(
                int(retain_cfg["split_threshold"])
                if "split_threshold" in retain_cfg else None),
            tls_port=(int(tls.get("port", 8883)) if tls else None),
            tls_ssl_context=(_tls_context(tls) if tls else None),
            ws_port=(int(ws["port"]) if ws else None),
            ws_path=(ws.get("path", "/mqtt") if ws else "/mqtt"),
            proxy_protocol=bool(tcp.get("proxy_protocol", False)))
        if dist is not None:
            # the remote dist plane delivers into THIS broker's sub-brokers
            dist.sub_brokers = self.broker.sub_brokers
            dist.events = self.broker.events
            dist.settings = self.broker.settings
        await self.broker.start()

        if self.agent_host is not None:
            # clustered: expose the session-dict service on the RPC fabric
            # and discover peers over gossip, so (tenant, client) stays
            # single-owner cluster-wide
            from .rpc.fabric import RPCServer
            from .sessiondict import (SessionDictClient,
                                      SessionDictRPCService)
            from .sessiondict.service import SERVICE as _SD
            self.rpc_server = RPCServer(host=host)
            SessionDictRPCService(self.broker).register(self.rpc_server)
            if dist_mode == "worker":
                # serve THIS node's route table to remote frontends
                from .dist.remote import DistWorkerRPCService
                DistWorkerRPCService(self.broker.dist.worker).register(
                    self.rpc_server)
            # cross-broker delivery: every clustered broker serves its
            # local sessions to the fleet (≈ mqtt-broker-client deliver)
            from .dist.deliverer import SERVICE_PREFIX as _DP
            from .dist.deliverer import DelivererRPCService
            DelivererRPCService(self.broker.sub_brokers,
                                self.broker.server_id).register(
                self.rpc_server)
            await self.rpc_server.start()
            registry.announce(_SD, self.rpc_server.address)
            if dist_mode == "worker":
                from .dist.remote import SERVICE as _DW
                registry.announce(_DW, self.rpc_server.address)
            registry.announce(f"{_DP}:{self.broker.server_id}",
                              self.rpc_server.address)
            self.broker.dist.deliverer_registry = registry
            self.broker.dist.server_id = self.broker.server_id
            self.broker.session_dict = SessionDictClient(
                registry, self_address=self.rpc_server.address)
            # cluster observability plane (ISSUE 5): publish this node's
            # health digest over gossip, serve the scatter-gather RPC
            # surface, and let pick() consult gossiped remote health
            from .obs.clusterview import (ClusterObsRPCService,
                                          ClusterView)
            self.clusterview = ClusterView(
                self.agent_host.node_id, self.agent_host,
                registry=registry, rpc_address=self.rpc_server.address)
            ClusterObsRPCService(self.clusterview).register(
                self.rpc_server)
            registry.remote_health = self.clusterview
            # ISSUE 15 satellite (ROADMAP retained (d)): the reconnect
            # drain governor consults peers' gossiped drain pressure
            # before admitting a herd drain — a saturated broker sheds
            # the reconnect toward quieter peers
            gov = getattr(self.broker.inbox, "drain_governor", None)
            if gov is not None:
                gov.peer_pressure_fn = self.clusterview.peer_drain_pressures

        api_cfg = cfg.get("api")
        if api_cfg:
            from .apiserver.server import APIServer
            self.api = APIServer(self.broker,
                                 metrics=self.metrics_registry,
                                 host=host,
                                 port=int(api_cfg.get("port", 9090)),
                                 registry=registry,
                                 cluster=self.agent_host,
                                 clusterview=self.clusterview)
            await self.api.start()
        if self.clusterview is not None:
            if self.api is not None:
                self.clusterview.api_port = self.api.port
            self.clusterview.start()
        log.info("standalone up: mqtt=%s:%s%s%s", host, self.broker.port,
                 f" ws={self.broker.ws_port}" if ws else "",
                 f" api={self.api.port}" if self.api else "")

    async def stop(self) -> None:
        if self.clusterview is not None:
            await self.clusterview.stop()
        if self.api is not None:
            await self.api.stop()
        if self.rpc_server is not None:
            await self.rpc_server.stop()
        if (self.broker is not None
                and getattr(self.broker, "session_dict", None) is not None):
            await self.broker.session_dict.registry.close()
        if self.broker is not None:
            await self.broker.stop()
        if self.agent_host is not None:
            await self.agent_host.stop()
        for host in self._isolated_hosts:
            host.close()


async def run(config: dict) -> None:
    node = Standalone(config)
    try:
        await node.start()
    except BaseException:
        # half-started node: release listeners + isolated plugin children
        await node.stop()
        raise
    stop_ev = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop_ev.set)
        except NotImplementedError:
            pass
    try:
        await stop_ev.wait()
    finally:
        await node.stop()


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="bifromq_tpu",
                                description="TPU-native MQTT broker")
    p.add_argument("--config", "-c", default=None, help="YAML config path")
    args = p.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s %(message)s")
    asyncio.run(run(load_config(args.config)))

"""Standalone dist-worker process: ``python -m bifromq_tpu.dist.worker_main``.

Hosts the route-table range + TPU matcher behind the RPC fabric — the
dist-worker role of the reference's multi-process deployment
(DistWorker.java:48 on a BaseKVStoreServer, reached via gRPC). The
mqtt-frontend process connects with ``dist.remote.RemoteDistWorker``.

Prints ``READY <port>`` on stdout once serving.
"""

from __future__ import annotations

import argparse
import asyncio
import sys

from ..utils.jaxenv import setup_compile_cache


def _prewarm_serving_jit() -> None:
    """Compile the serving walk before READY is advertised.

    A cold worker's FIRST match pays the full walk jit compile — seconds
    on a small CPU container — against the frontend's 1s per-attempt
    match deadline (remote.RemoteDistWorker.call_timeout), so the first
    publish after boot times out and burns its retry budget on a worker
    that is healthy but cold. The scratch table below never touches
    worker state; its pow2-padded arena shapes coincide with small
    serving tables, so the compile it triggers is the one first serves
    would otherwise hit. Best-effort: a warm failure must not keep the
    worker from serving (the first match just runs cold, as before)."""
    try:
        from ..models.matcher import TpuMatcher
        from ..models.oracle import Route
        from ..types import RouteMatcher
        m = TpuMatcher(auto_compact=False, match_cache=False)
        m.add_route("_warm", Route(
            matcher=RouteMatcher.from_topic_filter("w/+/x"), broker_id=0,
            receiver_id="r0", deliverer_key="d0", incarnation=1))
        m.refresh()
        m.match_batch([("_warm", "w/a/x")])
    except Exception:  # noqa: BLE001 — the first match then runs cold
        import logging
        logging.getLogger(__name__).exception("serving-jit pre-warm failed")


async def serve(args) -> None:
    from .. import trace
    from ..kv.native import NativeKVEngine
    from ..raft.store import KVRaftStateStore
    from ..rpc.fabric import RPCServer
    from .remote import DistWorkerRPCService
    from .worker import DistWorker

    # attribute this process's spans (exported via the "trace_spans"
    # method / the owning node's /trace) to the worker role
    from ..utils.env import env_opt_str
    if env_opt_str("BIFROMQ_TRACE_SERVICE") is None:
        trace.TRACER.service = f"dist-worker:{args.node_id}"

    engine = None
    raft_store_factory = None
    if args.data_dir:
        engine = NativeKVEngine(args.data_dir)

        def raft_store_factory(rid, _eng=engine):
            return KVRaftStateStore(_eng.create_space(f"raft_{rid}"))
    worker = DistWorker(node_id=args.node_id, engine=engine,
                        raft_store_factory=raft_store_factory)
    await worker.start()
    _prewarm_serving_jit()
    server = RPCServer(host=args.host, port=args.port)
    DistWorkerRPCService(worker).register(server)
    await server.start()
    print(f"READY {server.port}", flush=True)
    try:
        await asyncio.Event().wait()
    finally:
        await server.stop()
        await worker.stop()


def main(argv=None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--node-id", default="worker0")
    p.add_argument("--data-dir", default="")
    args = p.parse_args(argv)
    setup_compile_cache()
    try:
        asyncio.run(serve(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()

"""Standalone KV store process.

≈ the reference's standalone store server deployment (base-kv-store-server
hosted inside a bifromq-starter process): one ``KVRangeStore`` + raft
``StoreMessenger`` + RPC facade, addressed by static peer configuration.

    python -m bifromq_tpu.kv.store_main --node s1 --port 7001 \
        --peers s1=127.0.0.1:7001,s2=127.0.0.1:7002,s3=127.0.0.1:7003 \
        [--coproc echo|dist] [--data-dir /path]

Prints ``READY <port>`` on stdout once serving. With ``--data-dir`` the
store and raft state are durable (native C++ engine) and a restarted
process resumes from its WAL; without it a restart rejoins empty and
catches up via the leader's snapshot dump session.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys

from ..utils.jaxenv import setup_compile_cache


def _coproc_factory(kind: str):
    if kind == "dist":
        from ..dist.worker import DistWorkerCoProc

        def make(range_id: str):
            return DistWorkerCoProc()
        return make

    if kind == "inbox":
        from ..inbox.coproc import InboxStoreCoProc
        from ..plugin.events import IEventCollector

        class _NoEvents(IEventCollector):
            def report(self, event):
                pass
        return lambda range_id: InboxStoreCoProc(_NoEvents())

    if kind == "retain":
        from ..retain.coproc import RetainCoProc
        return lambda range_id: RetainCoProc()

    from .range import IKVRangeCoProc

    class _EchoCoProc(IKVRangeCoProc):
        boundary = (b"", None)

        def query(self, input_data, reader):
            return reader.get(input_data) or b""

        def mutate(self, input_data, reader, writer):
            k, v = input_data.split(b"=", 1)
            writer.put(k, v)
            return b"ok:" + k

        def reset(self, reader):
            pass

    return lambda range_id: _EchoCoProc()


async def amain(args) -> None:
    from ..rpc.fabric import RPCServer, ServiceRegistry
    from .engine import InMemKVEngine
    from .messenger import StoreMessenger
    from .meta import BaseKVStoreServer, MetaService
    from .store import KVRangeStore

    peers = dict(p.split("=", 1) for p in args.peers.split(",") if p)
    registry = ServiceRegistry()
    meta = MetaService()
    messenger = StoreMessenger(args.node, registry)
    for node, addr in peers.items():
        registry.announce(f"{messenger.service}:{node}", addr)

    if args.data_dir:
        from .native import NativeKVEngine
        from ..raft.store import KVRaftStateStore
        engine = NativeKVEngine(args.data_dir)
        raft_store_factory = (
            lambda rid: KVRaftStateStore(
                engine.create_space(f"raft_{rid}")))
    else:
        engine = InMemKVEngine()
        raft_store_factory = None

    store = KVRangeStore(args.node, messenger, engine,
                         _coproc_factory(args.coproc),
                         member_nodes=sorted(peers),
                         raft_store_factory=raft_store_factory)
    store.open()
    server = BaseKVStoreServer(store, messenger,
                               RPCServer(port=args.port), registry, meta,
                               tick_interval=args.tick_interval)
    await server.start()
    print(f"READY {server.server.port}", flush=True)
    await asyncio.Event().wait()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--node", required=True)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--peers", required=True,
                    help="node=host:port,... (must include --node)")
    ap.add_argument("--coproc", default="echo",
                    choices=["echo", "dist", "inbox", "retain"])
    ap.add_argument("--data-dir", default="")
    ap.add_argument("--tick-interval", type=float, default=0.02)
    args = ap.parse_args(argv)
    setup_compile_cache()
    try:
        asyncio.run(amain(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    sys.exit(main())

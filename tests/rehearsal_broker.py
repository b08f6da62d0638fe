"""A started broker on the benchmark's 20,000-row rehearsal table, seeded
the way ``benchmarks/run.py`` seeds a cell (``from_tries`` + a bulk KV
fill, the fleet stand-in on its ISubBroker seat), for tests that need one
real publish through every layer. Not a test module."""

import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


def _harness():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import sut
    import traffic
    return sut, traffic


def rehearsal_config(name: str = "rehearsal_20k", **changes) -> dict:
    """A configuration file of the benchmark, with keys replaced."""
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return dict(json.load(f), **changes)


@contextlib.asynccontextmanager
async def rehearsal_broker(cfg: dict = None):
    """Yields ``(node, matcher, tenant, topics)``: ``topics`` are publish
    topics of the configuration's own population that match rows."""
    sut, traffic = _harness()
    from bifromq_tpu.starter import Standalone
    cfg = cfg or rehearsal_config()
    gen = traffic.generator_of(cfg)
    rows = list(gen.subscriptions(cfg))
    tries, _n = sut.build_tries(rows)
    sut.install_settings(cfg.get("settings", {}))
    node_cfg = json.loads(json.dumps(cfg["broker"]))
    node_cfg.setdefault("plugins", {})["settings"] = "sut:Settings"
    node = Standalone(node_cfg)
    await node.start()
    try:
        node.broker.sub_brokers.register(sut.FleetStandIn())
        matcher = sut.seed_worker(node.broker.dist.worker, tries)["matcher"]
        tenant = rows[0][0]
        trie = tries[tenant]
        topics = [t for t in gen.topic_population(cfg)
                  if trie.match(t.split("/")).normal]
        yield node, matcher, tenant, topics
    finally:
        await node.stop()

#!/usr/bin/env python3
"""What ``sut.seed_worker`` seats, for the tests (CPU, forced host
devices): start the broker of a configuration, seed it with a small
``zipf_tree`` table, and print one JSON line with the seated matcher's
class and shards, ``sut.device_state`` / ``table_shapes`` / ``table_fill``
/ ``warm_patch_programs``, and how many of 200 seeded (tenant, topic)
pairs its rows differ from the plain reference's on.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 benchmarks/tests/mesh_seat_check.py <0|1: dist.mesh>
"""

import asyncio
import json
import os
import random
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import reference  # noqa: E402
import sut  # noqa: E402
import traffic  # noqa: E402


async def main(mesh: bool) -> dict:
    cfg = traffic.load_json("configs", "rehearsal_mesh_20k.json")
    cfg["subscriptions"] = 6000
    broker_cfg = dict(cfg["broker"])
    if not mesh:
        broker_cfg.pop("dist")
    gen = traffic.generator_of(cfg)
    rows = list(gen.subscriptions(cfg))
    tries, _n = sut.build_tries(rows)
    table = reference.Table()
    for tenant, levels, rid, dkey in rows:
        table.add(tenant, levels, (rid, dkey))
    from bifromq_tpu.starter import Standalone
    node = Standalone(broker_cfg)
    await node.start()
    try:
        worker = node.broker.dist.worker
        started = type(worker.matcher).__name__
        matcher = sut.seed_worker(worker, tries)["matcher"]
        before = sut.device_state(matcher, "cpu")
        warmed = sut.warm_patch_programs(matcher)
        rng = random.Random(20261004)
        tenants = [t for t, _n in gen.tenant_sizes(cfg)]
        pop = gen.topic_population(cfg)
        queries = [(rng.choice(tenants), rng.choice(pop)) for _ in range(200)]
        got = matcher.match_batch(queries)
        differ = sum(
            sorted((r.receiver_id, r.deliverer_key) for r in m.normal)
            != sorted(table.match(t, topic))
            for (t, topic), m in zip(queries, got))
        matched = sum(len(m.normal) for m in got)
        return {"started": started, "seated": type(matcher).__name__,
                "seat_is_workers": worker.matcher is matcher,
                "n_shards": getattr(matcher, "n_shards", 0),
                "state": before, "state_after_warm": sut.device_state(
                    matcher, "cpu"), "warmed": warmed,
                "shapes": sut.table_shapes(matcher),
                "fill": sut.table_fill(matcher),
                "counter_keys": sorted(sut.counters(
                    matcher, sut.FleetStandIn())),
                "differ": differ, "matched": matched}
    finally:
        await node.stop()


if __name__ == "__main__":
    print(json.dumps(asyncio.run(main(sys.argv[1] == "1"))), flush=True)
    os._exit(0)     # a matcher's warm-up thread may still run: do not wait

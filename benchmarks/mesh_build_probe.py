#!/usr/bin/env python3
"""Builder's probe (not a cell; the driver never runs it): what seeding a
mesh costs at a size, before a configuration is cut to it.

    python3 benchmarks/mesh_build_probe.py --tenants 4000 --subscriptions 4000000
        [--config tenant_fleet_4k] [--ctor] [--trace] [--rehearse-cpu]

Draws the configuration's table at the given size, builds a matcher on a
mesh of every local chip the way ``sut.seed_worker`` does (``from_tries``;
``--ctor``: the constructor's seed path, which copies every route into
two tries), and prints one JSON line: seconds of each step, host RSS,
each chip's bytes and peak, the stacked tables' shapes and each shard's
rows. ``--trace``: also profiles a few batches and prints the names of
the programs and of the collective operations as the device trace shows
them (what ``readers/walk_roofline.py`` and ``trace_reduce.COLLECTIVE``
match on).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import sut  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="tenant_fleet_4k")
    ap.add_argument("--tenants", type=int, required=True)
    ap.add_argument("--subscriptions", type=int, required=True)
    ap.add_argument("--ctor", action="store_true")
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    cfg = traffic.load_json("configs", args.config + ".json")
    cfg["tenants"], cfg["subscriptions"] = args.tenants, args.subscriptions
    t_start = time.perf_counter()
    import gc
    gc.disable()                    # as run.py builds (gc_freeze_after_setup)
    devices = sut.claim_devices(1, rehearse_cpu=args.rehearse_cpu)
    import jax
    from bifromq_tpu.parallel.sharded import MeshMatcher, make_mesh
    out = {"tenants": args.tenants, "subscriptions": args.subscriptions,
           "path": "ctor" if args.ctor else "from_tries",
           "devices": len(devices), "kind": devices[0].device_kind,
           "import_s": time.perf_counter() - t_start}
    t0 = time.perf_counter()
    gen = traffic.generator_of(cfg)
    rows = list(gen.subscriptions(cfg))
    out["rows_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    tries, n = sut.build_tries(rows)
    out["tries_s"], out["rows"] = time.perf_counter() - t0, n
    out["rss_before_build"] = sut.host_rss_bytes()
    mesh = make_mesh(1, len(devices))
    t0 = time.perf_counter()
    matcher = MeshMatcher(tries, mesh=mesh) if args.ctor \
        else MeshMatcher.from_tries(tries, mesh=mesh)
    jax.block_until_ready(matcher._device_trie)
    out["build_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    out["warmed"] = sut.warm_patch_programs(matcher)
    out["warm_patch_s"] = time.perf_counter() - t0
    out["rss_after_build"] = sut.host_rss_bytes()
    state = sut.device_state(matcher, devices[0].platform)
    out["bytes_each"], out["on"] = state["bytes_each"], state["on"]
    out["record_bytes"] = state["record_bytes"]
    out["device_shapes"] = [list(a.shape) for a in matcher._device_trie]
    out["table_shapes"] = sut.table_shapes(matcher)
    out["fill"] = sut.table_fill(matcher)
    out["rows_each"] = [sum(len(tries[t]) for t in tries
                            if matcher._base_ct.shard_of(t) == sh)
                        for sh in range(matcher.n_shards)]
    out["peak_each"] = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                        for d in jax.local_devices()]
    out["in_use_each"] = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                          for d in jax.local_devices()]
    # a few batches, by size: the first compiles the step and the expand
    rng = random.Random(1)
    sizes = gen.tenant_sizes(cfg)
    pop = gen.topic_population(cfg)
    batches = [[(rng.choice(sizes[:64])[0], rng.choice(pop))
                for _ in range(b)] for b in (1, 4, 16, 16, 16, 16)]
    t0 = time.perf_counter()
    matched = sum(len(m.normal) for m in matcher.match_batch(batches[0]))
    out["first_batch_s"] = time.perf_counter() - t0
    if args.trace and devices[0].platform != "cpu":
        trace_dir = os.path.join(HERE, ".out", "probe_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        for b in batches[1:3]:
            matcher.match_batch(b)
        jax.profiler.start_trace(trace_dir)
        a = time.monotonic()
        for b in batches[3:]:
            matched += sum(len(m.normal) for m in matcher.match_batch(b))
        window = time.monotonic() - a
        jax.profiler.stop_trace()
        red = trace_reduce.reduce_trace(trace_reduce.find_xplane(trace_dir),
                                        window)
        shutil.rmtree(trace_dir, ignore_errors=True)
        out["trace"] = {k: red[k] for k in ("busy_each", "collective_s",
                                            "programs", "device_ops")}
    out["matched"] = matched
    out["rss_end"] = sut.host_rss_bytes()
    out["total_s"] = time.perf_counter() - t_start
    print(json.dumps(out), flush=True)
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())

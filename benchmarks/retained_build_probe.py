#!/usr/bin/env python3
"""A probe run by hand, not a cell: what seeding retained messages costs
at a size, before a configuration is cut to it.

    python3 benchmarks/retained_build_probe.py --topics 5000000
        [--config rehearsal_retained_20k] [--rehearse-cpu]

Scales the configuration's ``sites`` to the asked number of retained
topics (the other sizes and the shape stay), seeds a bare
``RetainService`` the way ``sut.seed_retained`` seeds a started one (KV
fill, the co-processor's ``reset``, the index's first build and device
put), builds the plain reference, and walks one batch of 64 filters of
each shape on the device. Prints one JSON line: seconds of each step,
host RSS, the retained tables' bytes and the chip's peak, and for each
shape:

- ``host_wall_s``: host clock from dispatch to the fetched result, median
  of three after a first that compiles (it holds the flush, the launch and
  the copy back, not the device's time alone);
- ``device``: one more batch under the profiler (written under
  ``--trace-dir``): the device's busy seconds in it, and each program's
  device seconds and calls, read from the trace;
- ``expand_s``: host expansion, median of three;
- ``flagged_share``: the rows the walk flagged (``+`` past its states);
- ``served``: of the traced batch's rows, how many the native walker and
  how many the exact host oracle answered, counted at the two calls.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import random
import statistics
import sys
import time
import types

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import reference  # noqa: E402
import sut  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

SHAPES = ("exact", "device", "site_attr", "site")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="rehearsal_retained_20k")
    ap.add_argument("--topics", type=int, required=True)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--trace-dir",
                    default=os.path.join(HERE, ".out", "retained_probe_trace"))
    args = ap.parse_args()
    t_start = time.perf_counter()
    gc.disable()                    # as run.py builds (gc_freeze_after_setup)
    cfg = traffic.load_json("configs", args.config + ".json")
    per_site = int(cfg["tenants"]) * int(cfg["devices"]) * len(cfg["attributes"])
    cfg["sites"] = max(1, args.topics // per_site)
    devices = sut.claim_devices(1, rehearse_cpu=args.rehearse_cpu)
    import jax
    from bifromq_tpu.plugin.events import IEventCollector
    from bifromq_tpu.retain.service import RetainService
    from bifromq_tpu.utils import topic as topic_util
    gen = traffic.generator_of(cfg)
    out = {"topics": gen.retained_count(cfg), "sites": cfg["sites"],
           "kind": devices[0].device_kind,
           "import_s": time.perf_counter() - t_start}
    t0 = time.perf_counter()
    rows = list(gen.retained(cfg))
    out["rows_s"] = time.perf_counter() - t0
    service = RetainService(IEventCollector())
    seeded = sut.seed_retained(types.SimpleNamespace(retain_service=service),
                               rows)
    out.update({k: seeded[k] for k in ("kv_fill_s", "reset_s", "build_put_s")})
    out["rss_after_seed"] = sut.host_rss_bytes()
    state = sut.retained_device_state(
        types.SimpleNamespace(retain_service=service), devices[0].platform)
    out["device_bytes"], out["on"] = state["bytes"], state["on"]
    (coproc,) = service.kvstore.coprocs.values()
    index = coproc.index
    ct = index._compiled
    out["shapes"] = {n: list(getattr(ct, n).shape)
                     for n in ("node_tab", "edge_tab", "child_list")}
    # the plain reference a run's comparison builds from the same rows
    t0 = time.perf_counter()
    table = reference.RetainedTable()
    for tenant, topic, _n in rows:
        table.add(tenant, topic)
    out["reference_s"] = time.perf_counter() - t0
    out["rss_after_reference"] = sut.host_rss_bytes()
    limit = int(cfg["settings"]["RetainMessageMatchLimit"])
    rng = random.Random(1)
    served = count_served()
    scans = {}
    for shape in SHAPES:
        one = dict(cfg, retained=dict(cfg["retained"],
                                      filter_mix={shape: 1.0}))
        source = gen.FilterSource(one)
        queries = [(f"tenant{rng.randrange(int(cfg['tenants']))}",
                    topic_util.parse(source.draw(rng, retained=True)))
                   for _ in range(args.batch)]
        walls, expands = [], []
        for _rep in range(4):
            prep = index.prepare_scan(queries)
            t0 = time.perf_counter()
            prep, res = index.dispatch_scan(prep)
            fetched = index.fetch_scan(res)
            walls.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            got = index.expand_scan(prep, fetched, limit=limit)
            expands.append(time.perf_counter() - t0)
        flagged = int(fetched[2][:len(queries)].sum())
        trace_dir = os.path.join(args.trace_dir, f"{args.topics}-{shape}")
        t0 = time.perf_counter()
        jax.profiler.start_trace(trace_dir)
        prep = index.prepare_scan(queries)
        prep, res = index.dispatch_scan(prep)
        fetched = index.fetch_scan(res)
        jax.profiler.stop_trace()
        window_s = time.perf_counter() - t0
        served.update(native=0, host_oracle=0)
        index.expand_scan(prep, fetched, limit=limit)
        path = trace_reduce.find_xplane(trace_dir)
        reduced = path and trace_reduce.reduce_trace(path, window_s)
        scans[shape] = {
            "first_s": walls[0], "host_wall_s": statistics.median(walls[1:]),
            "device": reduced and {"busy_s": reduced["busy_s"],
                                   "programs": reduced["programs"]},
            "expand_s": statistics.median(expands[1:]),
            "flagged_share": flagged / len(queries),
            "served": dict(served, device=len(queries) - served["native"]
                           - served["host_oracle"]),
            "hits": sum(len(g) for g in got),
            "want": sum(min(limit, len(table.match(t, lv)))
                        for t, lv in queries)}
    out["scans"] = scans
    out["peak_bytes"] = sut.memory_peak_bytes()
    out["rss_end"] = sut.host_rss_bytes()
    out["total_s"] = time.perf_counter() - t_start
    jax.block_until_ready(index._device_tables)
    print(json.dumps(out), flush=True)
    os._exit(0)


def count_served() -> dict:
    """Counts the rows the native walker answers (the rows it returns
    without overflow) and those the exact host oracle answers (one call a
    row), by wrapping the two functions where ``expand_scan`` looks them
    up."""
    from bifromq_tpu.models import native_retained, retained
    served = {"native": 0, "host_oracle": 0}
    native, host = native_retained.match_rows_native, retained.match_filter_host

    def native_counted(*a, **kw):
        out = native(*a, **kw)
        served["native"] += int(np.count_nonzero(~out[2]))
        return out

    def host_counted(*a, **kw):
        served["host_oracle"] += 1
        return host(*a, **kw)
    native_retained.match_rows_native = native_counted
    retained.match_filter_host = host_counted
    return served


if __name__ == "__main__":
    sys.exit(main())

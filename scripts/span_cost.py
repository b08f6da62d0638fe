#!/usr/bin/env python3
"""ns a span of each kind, from a micro-loop (PR 29).

What every boundary on the publish path pays whether or not its trace is
sampled, on the machine this runs on. Imports JAX (so that the profiler
annotation is live, as in a serving process) but never touches a device.

    python3 scripts/span_cost.py [n]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402,F401  (the annotation exists only beside JAX)

from bifromq_tpu import trace  # noqa: E402


def per_call_ns(fn, n: int) -> float:
    fn()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter_ns()
        for _ in range(n):
            fn()
        best = min(best, (time.perf_counter_ns() - t0) / n)
    return best


def main(n: int) -> dict:
    span = trace.span

    def plain():
        with span("deliver.call"):
            pass

    def annotated():
        with span("deliver.group"):
            pass

    def stage_fed():
        with span("device.dispatch", batch=16, queries=1):
            pass

    def stage_and_window():
        with span("deliver.fanout", tenant="t0", topic="a/b"):
            pass

    def counter():
        trace.count("ready.polls", 3)

    def deferred():
        trace.record_finished("loop.lag", None, start_ns=1, end_ns=2)

    def clocks():
        time.monotonic_ns()
        time.monotonic_ns()

    def sampled_root_and_child():
        with span("pub.ingest", tenant="t0", topic="a/b", qos=1):
            with span("deliver.call"):
                pass

    out = {"two_clock_reads": per_call_ns(clocks, n),
           "plain": per_call_ns(plain, n),
           "annotated": per_call_ns(annotated, n),
           "annotated_stage": per_call_ns(stage_fed, n),
           "stage_and_window": per_call_ns(stage_and_window, n),
           "counter": per_call_ns(counter, n),
           "deferred": per_call_ns(deferred, n),
           "off_root_and_child": per_call_ns(sampled_root_and_child, n)}
    trace.TRACER.sampler.default_rate = 1.0
    try:
        out["sampled_root_and_child"] = per_call_ns(
            sampled_root_and_child, n)
    finally:
        trace.TRACER.sampler.default_rate = 0.0
        trace.TRACER.reset()
    return out


if __name__ == "__main__":
    res = main(int(sys.argv[1]) if len(sys.argv) > 1 else 200_000)
    print(json.dumps({k: round(v, 1) for k, v in res.items()}))

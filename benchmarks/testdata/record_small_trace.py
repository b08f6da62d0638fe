#!/usr/bin/env python3
"""How ``small_trace.xplane.pb`` was recorded (one v5e chip, PR 28):

    python3 benchmarks/testdata/record_small_trace.py <out dir>

Two small jitted programs, a host span around them and a sleep between,
traced with the Python tracer off so that the file stays small. Writes
the trace and, beside it, what ``trace_reduce`` read from it at the time:
``selfcheck.py`` holds the reduction to those numbers.
"""

import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import trace_reduce


@jax.jit
def walk_demo(x):
    return (x @ x).sum(axis=0)


@jax.jit
def expand_demo(x):
    return jnp.cumsum(x, axis=1)


def main(out: str) -> None:
    if jax.default_backend() != "tpu":
        raise SystemExit("record on the chip: a CPU trace has no device plane")
    x = jnp.ones((1024, 1024), jnp.float32)
    walk_demo(x).block_until_ready()
    expand_demo(x).block_until_ready()
    tmp = os.path.join(out, "tmp_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    a = time.monotonic()
    with jax.profiler.TraceAnnotation("demo.host_span"):
        for _ in range(3):
            walk_demo(x).block_until_ready()
        time.sleep(0.02)
        for _ in range(2):
            expand_demo(x).block_until_ready()
    window_s = time.monotonic() - a
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    dst = os.path.join(out, "small_trace.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    red = trace_reduce.reduce_trace(dst, window_s)
    expected = {"window_s": window_s, "busy_s": red["busy_s"],
                "idle_share": 100.0 * (1 - red["busy_s"] / window_s),
                "programs": {p: v["seconds"]
                             for p, v in red["programs"].items()},
                "calls": {p: v["calls"] for p, v in red["programs"].items()},
                "idle_gaps": red["idle_gaps"][:3],
                "device": jax.devices()[0].device_kind}
    with open(os.path.join(out, "small_trace.expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
    print(os.path.getsize(dst), json.dumps(expected))


if __name__ == "__main__":
    main(sys.argv[1])

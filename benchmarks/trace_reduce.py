"""From a profiler trace (``*.xplane.pb``) to busy time, per-program
device time and idle gaps. Needs nothing but JAX's ``ProfileData``.

What the TPU's trace looks like (read by hand on a v5e trace, PR 28):
one plane per chip named ``/device:TPU:<n>``; on it a line ``XLA
Modules`` with one event per executed program, named
``jit_<function>(<fingerprint>)``, and a line ``XLA Ops`` with one event
per HLO operation inside them. Host threads are lines of the plane
``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans land there, on the
same clock.

Busy is the union of the intervals of the ``XLA Ops`` line (of ``XLA
Modules`` where a plane has no ops line), per device plane, averaged over
the device planes (``busy_each`` keeps each plane's). ``collective_s`` is
the summed device time, over all planes, of the operations that cross
chips. A reader that finds no device plane returns ``None``: a CPU trace
never yields a device number.
"""

from __future__ import annotations

import glob
import os
import re
from typing import List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
_FINGERPRINT = re.compile(r"\(\d+\)$")
# HLO names of the operations that cross chips, as an ops line shows them
COLLECTIVE = re.compile(r"^%?(collective-permute|all-reduce|all-gather|"
                        r"all-to-all|reduce-scatter)")


def program_name(event_name: str) -> str:
    """``jit_walk_routes_donated(1234)`` -> ``walk_routes_donated``."""
    name = _FINGERPRINT.sub("", event_name)
    return name[4:] if name.startswith("jit_") else name


def union_ns(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def gaps_ns(intervals: List[Tuple[float, float]], lo: float,
            hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi] that no interval covers."""
    out, end = [], lo
    for s, e in sorted(intervals):
        if s > end:
            out.append((end, min(s, hi)))
        end = max(end, e)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(s, e) for s, e in out if e > s]


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def reduce_trace(path: str, window_s: float) -> Optional[dict]:
    """``window_s`` is the traced window's length on the host's clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_planes, host_lines = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            host_lines.extend(plane.lines)
    if not device_planes:
        return None
    busy_each, programs, ops = [], {}, {}
    all_busy: List[Tuple[float, float]] = []
    for plane in device_planes:
        lines = {ln.name: ln for ln in plane.lines}
        op_line = next((lines[n] for n in OPS_LINES if n in lines), None)
        mod_line = next((lines[n] for n in MODULE_LINES if n in lines), None)
        busy_src = op_line or mod_line
        if busy_src is None:
            continue
        ev = _events(busy_src)
        busy_each.append(union_ns([(s, e) for _n, s, e in ev]) / 1e9)
        all_busy.extend((s, e) for _n, s, e in ev)
        if op_line is not None:
            for n, s, e in ev:
                ops[n] = ops.get(n, 0.0) + (e - s) / 1e9
        if mod_line is not None:
            for n, s, e in _events(mod_line):
                p = program_name(n)
                c = programs.setdefault(p, [0.0, 0])
                c[0] += (e - s) / 1e9
                c[1] += 1
    if not busy_each:
        return None
    busy_s = sum(busy_each) / len(busy_each)
    # idle gaps of the first device, named by the host span that covers most
    lo = min(s for s, _e in all_busy)
    hi = max(e for _s, e in all_busy)
    gaps = sorted(gaps_ns(all_busy, lo, hi), key=lambda g: g[0] - g[1])[:10]
    host = [ev for ln in host_lines for ev in _events(ln)
            if ev[2] - ev[1] > 0]
    named = []
    for s, e in gaps:
        best, best_cover = "host:unattributed", 0.0
        for n, hs, he in host:
            cover = min(e, he) - max(s, hs)
            # the innermost span that still covers most of the gap
            if cover > 0.5 * (e - s) and (best_cover == 0.0
                                          or he - hs < best_cover):
                best, best_cover = n, he - hs
        named.append([best, (e - s) / 1e9])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_ops = [(n[:120], s) for n, s in top_ops]
    return {"busy_s": busy_s, "window_s": window_s,
            "device_planes": len(busy_each), "busy_each": busy_each,
            "collective_s": sum(s for n, s in ops.items()
                                if COLLECTIVE.match(n)),
            "programs": {p: {"seconds": c[0], "calls": c[1]}
                         for p, c in programs.items()},
            "device_ops": [[n, s] for n, s in top_ops],
            "idle_gaps": named,
            "span_s": (hi - lo) / 1e9}


def program_seconds(reduced: dict, names) -> float:
    """Summed device time of the programs whose name starts with one of
    ``names`` (a jitted function's name is stable; its fingerprint not)."""
    return sum(v["seconds"] for p, v in reduced["programs"].items()
               if any(p.startswith(n) for n in names))

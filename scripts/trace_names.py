#!/usr/bin/env python3
"""What the program wrote into a kept profiler trace (PR 29).

    python3 scripts/trace_names.py <dir or *.xplane.pb>

Prints one JSON object: ``host_annotations`` (the registered boundary
names found on ``/host:`` planes, with counts and summed seconds: the
program's own ``TraceAnnotation``s, on the device trace's clock),
``scope_names_in_file`` (how often each ``jax.named_scope`` name occurs in
the file's bytes: the op metadata of the HLO modules the trace carries;
``ProfileData`` does not expose it event by event) and, for
reading a sampled span's monotonic stamps against the trace's clock,
``first_annotation_ns`` (the earliest annotation's start on the trace's
clock, by name).
"""

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCOPES = ("walk.steps", "walk.emit", "walk.escalate", "expand.pairs",
          "expand.bucket", "tokenize.gather", "tokenize.rounds",
          "tokenize.mask", "patch.scatter")


def find(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise SystemExit(f"no *.xplane.pb under {path}")
    return files[-1]


def main(path: str) -> dict:
    from jax.profiler import ProfileData
    from bifromq_tpu.trace import BOUNDARIES
    data = ProfileData.from_file(find(path))
    host, first = {}, {}
    planes = []
    for plane in data.planes:
        planes.append(plane.name)
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in BOUNDARIES:
                    c = host.setdefault(ev.name, [0, 0.0])
                    c[0] += 1
                    c[1] += ev.duration_ns / 1e9
                    if ev.name not in first or ev.start_ns < first[ev.name]:
                        first[ev.name] = ev.start_ns
    # the scopes live in the op metadata of the HLO the trace carries
    # (``jit(_walk_routes_fn)/walk.steps/...``)
    with open(find(path), "rb") as f:
        raw = f.read()
    in_bytes = {s: raw.count(s.encode()) for s in SCOPES}
    return {"planes": planes, "scope_names_in_file": in_bytes,
            "host_annotations": {k: [n, round(s, 6)]
                                 for k, (n, s) in sorted(host.items())},
            "first_annotation_ns": first}


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1])))

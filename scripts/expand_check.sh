#!/usr/bin/env bash
# Tier-2 device fan-out gate (ISSUE 19): the second device stage —
# interval expansion + per-peer bucketing — asserting the contract:
#   1. the full parity suite (device expansion ≡ host expand_intervals
#      + numpy stable-argsort bucketing, overflow/trunc/empty/migration
#      cases included),
#   2. a ~100K-route microbench: device fused expand+bucket beats the
#      pre-change host shape (grid readback + C++/numpy expansion +
#      per-route python delivery grouping) by >= the bar,
#   3. serving attribution + A/B: BIFROMQ_DEVICE_EXPAND=1 serves
#      byte-identical MatchedRoutes to =0, batches carry a dev_expand
#      stage in the profiler split and the device.expand histogram.
# Runs on CPU (JAX_PLATFORMS=cpu), hard timeout like the other gates.
set -o pipefail

cd "$(dirname "$0")/.."

echo "== 1. expansion/bucketing parity suite =="
timeout -k 10 "${EXPAND_CHECK_TIMEOUT:-420}" \
    env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_expand_device.py -q -p no:cacheprovider \
    || exit 1

echo "== 2. microbench + 3. serving A/B =="
timeout -k 10 "${EXPAND_CHECK_TIMEOUT:-420}" \
    env JAX_PLATFORMS=cpu \
    python - <<'EOF'
import os, time

import numpy as np
import jax

from bifromq_tpu.models.matcher import TpuMatcher
from bifromq_tpu.models.oracle import Route
from bifromq_tpu.obs import OBS
from bifromq_tpu.ops.match import (RouteIntervals, bucket_pairs_host,
                                   expand_intervals, expand_routes)
from bifromq_tpu.types import RouteMatcher
from bifromq_tpu.utils.metrics import STAGES

SPEEDUP_MIN = float(os.environ.get("EXPAND_CHECK_SPEEDUP", "1.5"))

# ---- 2. ~100K-route microbench: device stage vs pre-change host shape
B, A = 1024, 16
rng = np.random.default_rng(11)
counts = rng.poisson(6, size=(B, A)).astype(np.int32)
starts = rng.integers(0, 200_000, size=(B, A)).astype(np.int32)
total = int(counts.sum())
cap = max(65536, -(-int(total * 2) // 65536) * 65536)
ivl = RouteIntervals(
    start=jax.device_put(starts), count=jax.device_put(counts),
    n_routes=jax.device_put(counts.sum(axis=1)),
    overflow=jax.device_put(np.zeros(B, bool)))
slot_peer = jax.device_put(np.zeros(0, np.int32))   # single-server arena

er = expand_routes(ivl, slot_peer, cap=cap, n_peers=0)   # jit warmup
np.asarray(er.peer_offsets)

def best_of(fn, reps=7):
    best = float("inf")
    for _ in range(reps):
        s = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - s)
    return best

def device_leg():
    er = expand_routes(ivl, slot_peer, cap=cap, n_peers=0)
    np.asarray(er.peer_slots); np.asarray(er.peer_rows)
    np.asarray(er.row_offsets); np.asarray(er.trunc)

def host_leg():
    # the pre-ISSUE-19 serving shape: full grid readback, host
    # expansion, per-route python delivery grouping
    gs = np.asarray(ivl.start); gc = np.asarray(ivl.count)
    slots, offs = expand_intervals(gs, np.maximum(gc, 0))
    by_peer = {}
    for sl in slots.tolist():
        by_peer.setdefault(0, []).append(sl)

dev_s, host_s = best_of(device_leg), best_of(host_leg)
speedup = host_s / max(1e-9, dev_s)
print(f"microbench: {total:,} routes — device {dev_s*1e3:.1f}ms, "
      f"host {host_s*1e3:.1f}ms -> {speedup:.1f}x (bar {SPEEDUP_MIN}x)")
assert speedup >= SPEEDUP_MIN, \
    f"device expand only {speedup:.2f}x the host path"

# untimed: the non-identity bucket path stays byte-exact vs the oracle
sp = rng.integers(0, 3, 200_000).astype(np.int32)
er = expand_routes(ivl, jax.device_put(sp), cap=cap, n_peers=3)
h_slots, h_offs = expand_intervals(starts, np.maximum(counts, 0))
h_rows = np.repeat(np.arange(B, dtype=np.int32), np.diff(h_offs))
hps, hpr, hpo = bucket_pairs_host(h_slots, h_rows, sp, 3)
live = int(np.asarray(er.peer_offsets)[4])
assert live == int(hpo[4]), "live-pair count drift"
assert np.array_equal(np.asarray(er.peer_slots)[:live], hps[:live])
assert np.array_equal(np.asarray(er.peer_rows)[:live], hpr[:live])
print(f"bucket parity: {live:,} pairs across 3 peers + sentinels OK")

# ---- 3. serving A/B + stage attribution ------------------------------
def mk(tf, rid):
    return Route(matcher=RouteMatcher.from_topic_filter(tf), broker_id=0,
                 receiver_id=rid, deliverer_key="d0", incarnation=1)

# match_cache=False (not None, which means "default"): the ISSUE-4
# front-end would serve the second leg's identical queries from cache
# and the device stage would never run
m = TpuMatcher(auto_compact=False, match_cache=False)
for i in range(256):
    m.add_route("tenant0", mk(f"dev/{i}/+", f"r{i}"))
    m.add_route("tenant0", mk(f"dev/{i}/#", f"w{i}"))
m.refresh()
queries = [("tenant0", f"dev/{i % 256}/x") for i in range(64)]

def canon(results):
    return [sorted((x.matcher.mqtt_topic_filter, x.receiver_url)
                   for x in r.normal) for r in results]

prev = os.environ.get("BIFROMQ_DEVICE_EXPAND")
try:
    os.environ["BIFROMQ_DEVICE_EXPAND"] = "0"
    legacy = canon(m.match_batch(queries))
    os.environ["BIFROMQ_DEVICE_EXPAND"] = "1"
    b0 = OBS.profiler.batches_total
    device = canon(m.match_batch(queries))
finally:
    if prev is None:
        os.environ.pop("BIFROMQ_DEVICE_EXPAND", None)
    else:
        os.environ["BIFROMQ_DEVICE_EXPAND"] = prev
assert legacy == device, "MatchedRoutes drift between expand modes"
assert m.last_expanded is not None, "device leg served without buckets"
n_new = OBS.profiler.batches_total - b0
recs = OBS.profiler.records()[-n_new:] if n_new else []
assert recs and any(r.dev_expand_s > 0 for r in recs), \
    "no dev_expand attribution on the device-expand batch"
assert "device.expand" in STAGES.snapshot(), \
    "device.expand stage histogram empty"
split = OBS.profiler.split_snapshot()
assert "dev_expand_ms_p50" in split, split.keys()
print(f"serving A/B: {len(queries)} topics byte-identical across modes; "
      f"dev_expand stage attributed on {len(recs)} batch(es)")
print("EXPAND CHECK PASSED")
EOF
rc=$?
if [ $rc -ne 0 ]; then
    echo "EXPAND CHECK FAILED (rc=$rc)" >&2
fi
exit $rc

#!/usr/bin/env bash
# Tier-2 ingest byte-plane gate (ISSUE 11): publish-side topic prep on
# the topic-diversity corpus, asserting the byte-plane contract:
#   1. batched byte-plane prep (TopicBytes pack + native/numpy tokenize)
#      is >=10x the per-message python-loop path at batch >= 1024,
#   2. EXACT three-way parity — python loop ≡ vectorized numpy ≡ native
#      C++ ≡ device kernel (interpret on CPU) — on adversarial topics,
#   3. the profiler split attributes a `tokenize` stage on every device
#      batch served through the matcher (sync and async legs).
# Runs on CPU (JAX_PLATFORMS=cpu), hard timeout like the other gates.
set -o pipefail

cd "$(dirname "$0")/.."

timeout -k 10 "${INGEST_CHECK_TIMEOUT:-420}" \
    env JAX_PLATFORMS=cpu \
    python - <<'EOF'
import asyncio, os, time

import numpy as np

from bifromq_tpu import workloads
from bifromq_tpu.models import bytetok
from bifromq_tpu.models.automaton import tokenize
from bifromq_tpu.models.bytetok import TopicBytes
from bifromq_tpu.models.matcher import TpuMatcher
from bifromq_tpu.models.oracle import Route
from bifromq_tpu.obs import OBS
from bifromq_tpu.types import RouteMatcher

BATCH = int(os.environ.get("INGEST_CHECK_BATCH", "2048"))
SPEEDUP_MIN = float(os.environ.get("INGEST_CHECK_SPEEDUP", "10"))
assert BATCH >= 1024, "the gate bar is defined at batch >= 1024"

corpus = workloads.diverse_topics(BATCH * 4, seed=7)
batches = [corpus[i * BATCH:(i + 1) * BATCH] for i in range(4)]
roots = [0] * BATCH

# ---- 1. throughput: byte plane vs per-message python loop -------------
# best-of-N: the byte plane's MT hash halves under a busy sibling core
# on a 2-core CI box (the single-threaded python baseline doesn't), so
# a transient background load would fail the ratio spuriously; more
# reps + a settle pause let at least one rep run uncontended
time.sleep(float(os.environ.get("INGEST_CHECK_SETTLE_S", "2")))

def timed(fn, legs, reps=5):
    fn(0)
    best = 0.0
    for _ in range(reps):
        s = time.perf_counter()
        for it in range(legs):
            fn(it)
        best = max(best, BATCH * legs / (time.perf_counter() - s))
    return best

def py_leg(it):
    for t in batches[it % 4]:
        tokenize([t], roots[:1], max_levels=16, salt=0, native=False)

py_rate = timed(py_leg, legs=1, reps=2)
byte_rate = timed(lambda it: tokenize(
    TopicBytes.from_topics(batches[it % 4]), roots, max_levels=16,
    salt=0), legs=8)
speedup = byte_rate / max(1e-9, py_rate)
print(f"prep: python-loop {py_rate:,.0f}/s, byte-plane "
      f"{byte_rate:,.0f}/s -> {speedup:.1f}x (bar {SPEEDUP_MIN}x)")
assert speedup >= SPEEDUP_MIN, \
    f"byte-plane prep only {speedup:.1f}x the python loop"

# ---- 2. exact multi-way parity on adversarial topics ------------------
adversarial = corpus[:512] + [
    "", "/", "//", "a//b", "$SYS/health", "$share/g/dev/1",
    "héllo/wörld/日本語", "x" * 200 + "/" + "y" * 300,
    "a/" * 20 + "deep", "trailing/", "/leading",
]
n = len(adversarial)
tb = TopicBytes.from_topics(adversarial)
rts = list(range(n))
py = tokenize(adversarial, rts, max_levels=16, salt=3, native=False)
nat = tokenize(tb, rts, max_levels=16, salt=3)
h1, h2, ln, rv, sm = bytetok.tokenize_bytes(tb, rts, max_levels=16,
                                            salt=3)
for name, a, b in (("native.h1", py.tok_h1, nat.tok_h1),
                   ("native.h2", py.tok_h2, nat.tok_h2),
                   ("native.len", py.lengths, nat.lengths),
                   ("numpy.h1", py.tok_h1, h1),
                   ("numpy.h2", py.tok_h2, h2),
                   ("numpy.len", py.lengths, ln),
                   ("numpy.sys", py.sys_mask, sm)):
    assert np.array_equal(a, b), f"parity break: {name}"
from bifromq_tpu.ops.tokenize import device_tokenize
mirror, probes = device_tokenize(tb, rts, max_levels=16, salt=3)
sup = mirror.lengths[:n] >= 0
dh1 = np.asarray(probes.tok_h1)[:n]
dh2 = np.asarray(probes.tok_h2)[:n]
assert np.array_equal(dh1[sup], py.tok_h1[:n][sup]), "device h1 parity"
assert np.array_equal(dh2[sup], py.tok_h2[:n][sup]), "device h2 parity"
assert sup.sum() >= n - 2, "device path rejected too many rows"
print(f"parity: python ≡ native ≡ numpy ≡ device "
      f"({int(sup.sum())}/{n} device-supported rows)")

# ---- 3. tokenize stage attributed on every device batch ---------------
def mk(tf, rid):
    return Route(matcher=RouteMatcher.from_topic_filter(tf), broker_id=0,
                 receiver_id=rid, deliverer_key="d0", incarnation=1)

m = TpuMatcher(auto_compact=False, match_cache=None)
for i in range(64):
    m.add_route("tenant0", mk(f"dev/{i}/+", f"r{i}"))
m.refresh()
b0 = OBS.profiler.batches_total
m.match_batch([("tenant0", f"dev/{i}/x") for i in range(32)])

async def run():
    for i in range(4):
        await m.match_batch_async(
            [("tenant0", f"dev/{j}/y{i}") for j in range(16)])
asyncio.run(run())
n_new = OBS.profiler.batches_total - b0
assert n_new > 0, "no device batches recorded in the gate window"
recs = OBS.profiler.records()[-n_new:]
assert recs, "no device batches recorded"
assert all(r.tokenize_s > 0 for r in recs if r.kernel != "oracle"), \
    "a device batch lacked tokenize attribution"
split = OBS.profiler.split_snapshot()
assert "tokenize_ms_p50" in split, split.keys()
from bifromq_tpu.utils.metrics import STAGES
assert "tokenize" in STAGES.snapshot(), "tokenize stage histogram empty"
print(f"profiler: tokenize stage on all {len(recs)} device batches "
      f"(p50 {split['tokenize_ms_p50']}ms)")
print("INGEST CHECK PASSED")
EOF
rc=$?
if [ $rc -ne 0 ]; then
    echo "INGEST CHECK FAILED (rc=$rc)" >&2
fi
exit $rc

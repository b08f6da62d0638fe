#!/usr/bin/env bash
# Tier-2 device-chaos gate (ISSUE 7): inject hang + error + slow device
# faults under load and assert the broker's device-fault resilience plane
# holds:
#   1. with a PERMANENT device-hang fault injected, serving never
#      deadlocks — every match returns exact (host-oracle) rows within
#      the watchdog deadline budget,
#   2. the device circuit breaker opens within its failure threshold of
#      batches, after which dispatches stop entirely,
#   3. clearing the fault restores device serving via the half-open
#      canary probe — verified by `kernel=lax|lax_donated` span
#      tags returning on device.dispatch spans,
#   4. QoS0 shedding fires ONLY under injected overload and is
#      tenant-fair (the noisy tenant sheds strictly more than the quiet
#      tenant in the same window); the bounded QoS>0 ingest gate
#      backpressures without ever dropping (zero QoS1 loss).
# Runs on CPU (JAX_PLATFORMS=cpu) under a hard timeout like the other
# gates, plus the chaos-marked unit suite for this plane.
set -o pipefail

cd "$(dirname "$0")/.."

timeout -k 10 "${CHAOS_DEVICE_TIMEOUT:-300}" \
    env JAX_PLATFORMS=cpu python -m pytest tests/test_device_chaos.py \
    -q -m chaos -p no:cacheprovider -p no:xdist -p no:randomly || exit $?

timeout -k 10 "${CHAOS_DEVICE_TIMEOUT:-300}" \
    env JAX_PLATFORMS=cpu BIFROMQ_DEVICE_DEADLINE_S=0.3 \
    python - <<'EOF'
import asyncio, time

from bifromq_tpu import trace
from bifromq_tpu.models.matcher import TpuMatcher
from bifromq_tpu.models.oracle import Route
from bifromq_tpu.resilience.device import LoadShedder, IngestGate
from bifromq_tpu.resilience.faults import get_injector
from bifromq_tpu.types import RouteMatcher
from bifromq_tpu.utils.metrics import FABRIC, FabricMetric


def mk(tf, r):
    return Route(matcher=RouteMatcher.from_topic_filter(tf), broker_id=0,
                 receiver_id=r, deliverer_key="d0")


m = TpuMatcher(max_levels=8, k_states=8, auto_compact=False,
               match_cache=False)
m.add_route("T", mk("a/b", "r1"))
m.add_route("T", mk("a/+", "r2"))
m.refresh()
m.device_breaker.recovery_time = 0.2
thr = m.device_breaker.failure_threshold
inj = get_injector()


async def serve(topic):
    res = await m.match_batch_async([("T", topic)])
    return sorted(r.receiver_id for r in res[0].normal)


async def main():
    # ---- 1+2: permanent hang → no deadlock, breaker opens -----------------
    inj.add_rule(service="tpu-device", method="dispatch", action="hang")
    t0 = time.monotonic()
    for i in range(thr + 2):
        assert await serve(["a", "b"]) == ["r1", "r2"], "wrong rows"
    wall = time.monotonic() - t0
    budget = 0.3 * (thr + 2) + 2.0
    assert wall < budget, f"hang serving took {wall:.1f}s > {budget:.1f}s"
    assert m.device_breaker.state == "open", m.device_breaker.state
    d_open = m._ring.dispatched_total
    assert await serve(["a", "b"]) == ["r1", "r2"]
    assert m._ring.dispatched_total == d_open, "open breaker dispatched"
    assert m._ring.timeouts_total >= thr
    print(f"hang gate ok: {thr + 2} batches in {wall:.2f}s, breaker open "
          f"after {m._ring.timeouts_total} timeouts, dispatch stopped")

    # ---- error + slow faults also degrade exactly -------------------------
    inj.reset()
    m.device_breaker.force_close()          # re-arm a closed breaker
    inj.add_rule(service="tpu-device", method="dispatch", action="error",
                 max_hits=1)
    assert await serve(["a", "b"]) == ["r1", "r2"]
    inj.add_rule(service="tpu-device", method="dispatch", action="slow",
                 delay=0.05, max_hits=1)
    assert await serve(["a", "b"]) == ["r1", "r2"]
    print("error + slow fault gate ok (exact rows either way)")

    # ---- 3: canary recovery, kernel tags return ---------------------------
    m.device_breaker.force_open()
    await asyncio.sleep(0.25)               # recovery window
    trace.TRACER.reset()
    trace.TRACER.sampler.default_rate = 1.0
    try:
        assert await serve(["a", "b"]) == ["r1", "r2"]   # the canary
        assert m.device_breaker.state == "closed", "canary did not close"
        assert await serve(["a", "x"]) == ["r2"]
        kernels = {s["tags"].get("kernel")
                   for s in trace.TRACER.export(limit=100)
                   if s["name"] == "device.dispatch"}
        assert kernels & {"lax", "lax_donated"}, kernels
    finally:
        trace.TRACER.sampler.default_rate = 0.0
        trace.TRACER.reset()
    print(f"canary recovery ok: breaker closed, kernel tags {kernels}")

    # ---- 4: shed only under injected overload, tenant-fair ----------------
    clk = [0.0]
    shed = LoadShedder(clock=lambda: clk[0])
    pressure = [0.0]
    import bifromq_tpu.obs as obs_pkg
    real_qp = obs_pkg.OBS.device.queue_pressure
    real_dd = obs_pkg.OBS.device.dispatch_queue_depth
    real_noisy = obs_pkg.OBS.is_noisy
    obs_pkg.OBS.device.queue_pressure = lambda: pressure[0]
    obs_pkg.OBS.device.dispatch_queue_depth = lambda: 0
    obs_pkg.OBS.is_noisy = lambda tenant: tenant == "noisy"
    try:
        for _ in range(50):                 # healthy: nothing sheds
            clk[0] += 0.01
            assert not shed.should_shed("noisy")
            assert not shed.should_shed("quiet")
        assert shed.shed_total == 0, "shed outside injected overload"
        pressure[0] = 2.0                   # injected overload (level 1)
        for _ in range(50):
            clk[0] += 0.01
            shed.should_shed("noisy")
            shed.should_shed("quiet")
        snap = shed.snapshot()["match_shed_total"]
        assert snap.get("noisy", 0) > snap.get("quiet", 0), snap
        assert snap.get("quiet", 0) == 0, snap
    finally:
        obs_pkg.OBS.device.queue_pressure = real_qp
        obs_pkg.OBS.device.dispatch_queue_depth = real_dd
        obs_pkg.OBS.is_noisy = real_noisy
    print(f"shed gate ok: silent when healthy, tenant-fair under "
          f"overload {snap}")

    # ---- zero QoS1 loss: the gate parks, it never drops -------------------
    gate = IngestGate(capacity=4)
    delivered = []

    async def one(i):
        await gate.acquire()
        try:
            await asyncio.sleep(0.001)
            delivered.append(i)
        finally:
            gate.release()

    await asyncio.gather(*(one(i) for i in range(64)))
    assert len(delivered) == 64, "QoS1 admission lost work"
    assert gate.peak_inflight <= 4
    print(f"qos1 gate ok: 64/64 delivered, peak in-flight "
          f"{gate.peak_inflight} (bounded)")


asyncio.run(main())
print("DEVICE CHAOS GATE PASS")
EOF
rc=$?
if [ $rc -ne 0 ]; then
    echo "chaos_device: FAIL (rc=$rc)" >&2
    exit $rc
fi
echo "chaos_device: PASS"

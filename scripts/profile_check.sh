#!/usr/bin/env bash
# Tier-2 capacity & continuous-profiling gate (ISSUE 8). Asserts:
#   1. the capacity model's predicted device bytes match the live jax
#      buffer bytes within 10% (CPU backend — the acceptance bar),
#   2. the planner's fits() renders the 1M-sub HBM verdict WITHOUT
#      dispatching anything,
#   3. a pipelined serving run leaves a live profiler ledger (stage
#      split, padding waste, compile events) and bench.py stamps the
#      same snapshot into its record (code-path probed directly),
#   4. the segment store survives a simulated process restart with
#      retention enforced,
#   5. BIFROMQ_OBS_FORMAT=otlp output validates against the checked-in
#      scripts/otlp_schema.json.
# Runs on CPU (JAX_PLATFORMS=cpu), hard timeout like the sibling gates.
set -o pipefail

cd "$(dirname "$0")/.."

STORE_DIR="$(mktemp -d /tmp/profile_check_XXXX)"
trap 'rm -rf "$STORE_DIR"' EXIT

timeout -k 10 "${PROFILE_CHECK_TIMEOUT:-300}" \
    env JAX_PLATFORMS=cpu \
        BIFROMQ_OBS_STORE="$STORE_DIR/segs" \
        BIFROMQ_OBS_STORE_SEGMENT_BYTES=4096 \
        BIFROMQ_OBS_STORE_SEGMENTS=4 \
    python - <<'EOF'
import asyncio, json, os, sys, time

def check(cond, msg):
    assert cond, msg
    print(f"OK {msg}")

async def main():
    from bifromq_tpu.models.matcher import TpuMatcher
    from bifromq_tpu.models.oracle import Route
    from bifromq_tpu.obs import OBS, SegmentStore
    from bifromq_tpu.obs import capacity as cap
    from bifromq_tpu.types import RouteMatcher

    def mk(tf, rid):
        return Route(matcher=RouteMatcher.from_topic_filter(tf),
                     broker_id=0, receiver_id=rid, deliverer_key="d")

    # ---- 1. model-vs-live parity --------------------------------------
    m = TpuMatcher(auto_compact=False)
    for i in range(500):
        m.add_route("T", mk(f"gate/{i}/+", f"r{i}"))
    m.refresh()
    rep = cap.measure(m)
    check(rep["installed"] and rep["parity_error"] < 0.10,
          f"capacity parity {rep['parity_error']:.4f} < 10% "
          f"({rep['measured_device_bytes']} bytes live)")

    # ---- 2. the 1M-sub HBM verdict, no dispatch -----------------------
    verdict = cap.default_planner([m]).fits(
        1_000_000, hbm_limit_bytes=16 << 30)
    check(verdict["hbm"]["fits"] is True,
          f"planner 1M-sub verdict: peak "
          f"{verdict['per_device_peak_bytes']>>20}MB fits 16GB HBM")

    # ---- 3. pipelined serving fills the profiler + bench stamps it ----
    for i in range(40):
        await m.match_batch_async([("T", ["gate", str(i % 7), "x"])])
    prof = OBS.profiler.snapshot(brief=True)
    check(prof["batches"] >= 1
          and "dispatch_ms_p50" in prof["split"]
          and "ready_ms_p50" in prof["split"],
          f"profiler split live ({prof['split']['window_batches']} "
          f"batches)")
    check(prof["compile_ledger"]["total"] >= 1
          and prof["compile_ledger"]["events"],
          f"compile ledger attributed "
          f"({prof['compile_ledger']['total']} events, last reason="
          f"{prof['compile_ledger']['events'][-1]['reason']})")
    check(prof["cache_bypass_rate"] > 0,
          f"cache bypasses profiled (rate="
          f"{prof['cache_bypass_rate']})")
    # the bench stamps THIS snapshot into every record — probe the same
    # code path bench.py runs (a full bench is a different gate's job)
    src = open("bench.py").read()
    check('record["profile"]' in src and 'record["capacity"]' in src,
          "bench.py stamps profile + capacity snapshots")

    # ---- 4. segment store: restart survival + retention ---------------
    check(OBS.start_persistence(), "segment store armed from env")
    for _ in range(30):                   # force rotations past 4 segs
        OBS.profiler.record_batch(n_queries=4, batch=16, kernel="lax",
                                  dispatch_s=0.001, ready_s=0.002,
                                  fetch_s=0.001)
        OBS.persist_now()
    snap1 = OBS.store.snapshot()
    OBS.stop_persistence(final_flush=False)
    st2 = SegmentStore(os.environ["BIFROMQ_OBS_STORE"],
                       max_segment_bytes=4096, max_segments=4)
    snap2 = st2.snapshot()
    recs = st2.read()
    check(recs and snap2["segments"] <= 4
          and snap2["active_seq"] == snap1["active_seq"],
          f"store survives restart ({len(recs)} records, "
          f"{snap2['segments']} segments retained, "
          f"{snap1['segments_dropped']} dropped)")
    kinds = {r.get("type") for r in recs}
    check("profile" in kinds and "profile_summary" in kinds,
          f"store record types {sorted(k for k in kinds if k)}")

    # ---- 5. OTLP output validates against the checked-in schema -------
    from bifromq_tpu import trace
    from bifromq_tpu.obs import FileSink, TelemetryExporter
    otlp_path = os.path.join(os.path.dirname(
        os.environ["BIFROMQ_OBS_STORE"]), "otlp.jsonl")
    old_slow, trace.TRACER.slow_ms = trace.TRACER.slow_ms, 0.0001
    try:
        with trace.span("pub.ingest", tenant="gate"):
            time.sleep(0.002)
        exp = TelemetryExporter(
            FileSink(otlp_path), interval_s=60, framing="otlp",
            snapshot_fn=lambda: OBS.profiler.snapshot(brief=True),
            resource=OBS.resource_envelope())
        exp.enqueue({"type": "profile", "ts": time.time(),
                     **OBS.profiler.snapshot(brief=True)})
        await exp._flush_once()
    finally:
        trace.TRACER.slow_ms = old_slow

    schema = json.load(open("scripts/otlp_schema.json"))

    def validate(obj, sch, path="$"):
        """Subset JSON-Schema validator: type, required, properties,
        items, minItems, oneOf."""
        if "oneOf" in sch:
            errs = []
            for i, branch in enumerate(sch["oneOf"]):
                try:
                    validate(obj, branch, f"{path}<{i}>")
                    return
                except AssertionError as e:
                    errs.append(str(e))
            raise AssertionError(f"{path}: no oneOf branch matched: "
                                 + " | ".join(errs))
        t = sch.get("type")
        if t:
            pytype = {"object": dict, "array": list, "string": str,
                      "number": (int, float), "boolean": bool}[t]
            assert isinstance(obj, pytype), f"{path}: not {t}"
        for req in sch.get("required", ()):
            assert req in obj, f"{path}: missing {req!r}"
        for k, sub in sch.get("properties", {}).items():
            if isinstance(obj, dict) and k in obj:
                validate(obj[k], sub, f"{path}.{k}")
        if "items" in sch and isinstance(obj, list):
            assert len(obj) >= sch.get("minItems", 0), \
                f"{path}: fewer than minItems"
            for i, el in enumerate(obj):
                validate(el, sch["items"], f"{path}[{i}]")

    lines = [ln for ln in open(otlp_path).read().splitlines() if ln]
    assert lines, "otlp exporter wrote nothing"
    kinds = set()
    for ln in lines:
        obj = json.loads(ln)
        validate(obj, schema)
        kinds |= set(obj.keys())
    check({"resourceSpans", "resourceMetrics", "resourceLogs"} <= kinds,
          f"{len(lines)} OTLP lines validate against "
          f"scripts/otlp_schema.json ({sorted(kinds)})")

asyncio.run(main())
print("profile_check PASSED")
EOF
rc=$?
if [ $rc -eq 124 ] || [ $rc -eq 137 ]; then
    echo "profile check TIMED OUT (rc=$rc)" >&2
fi
exit $rc

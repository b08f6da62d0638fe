#!/usr/bin/env python
"""Route-match throughput benchmarks for the five BASELINE.md configs.

The device kernel under test is the TPU re-design of the reference hot loop
(bifromq-dist-worker .../cache/TenantRouteMatcher.java:68 joined with
.../trie/TopicFilterIterator.java:38): level-packed automaton + fixed-shape
NFA walk (ops/match.py), retained-mode roles-swapped walk (ops/retained.py),
host tokenization in C++ (native/tokenizer.cpp).

Prints ONE JSON line on stdout — the headline config-2 number:
  {"metric": ..., "value": N, "unit": "routes/s", "vs_baseline": N/BASELINE}
All five configs' numbers go to stderr in the extras dict.

HEADLINE METRIC (VERDICT r4 #1): end-to-end MATCHED ROUTES per second —
tokenize + device interval walk + readback + vectorized expansion to
materialized per-topic route-slot arrays. The divisor is the MEASURED stock
baseline (bench_results/stock_baseline.json: native/stockmatch.cpp, the
faithful C++ port of the reference TenantRouteMatcher.matchAll cache-miss
loop, cross-checked vs the oracle). Comparison basis: KERNEL-vs-KERNEL,
cache-off, 1-core stock — the stock side omits the reference's
TenantRouteCache layer and its DistMatchParallelism workers; both sides
materialize per-topic route-entry vectors and neither does delivery I/O.
If stock_baseline.json is absent vs_baseline is null ("not measured").

NO DEVICE, NO RECORD: the device is initialised in this process (one
process per chip); if that fails the bench exits non-zero. It never prints
a stored result.

The committed throughput is end-to-end device serving rate: pipelined
dispatch, host-fallback cost for overflowed topics folded in at the
measured oracle rate.

MATCH-RESULT CACHE (ISSUE 4): ``--match-cache=on|off`` (or env
BIFROMQ_MATCH_CACHE) A/Bs the TenantMatchCache plane; config "6" runs the
dedicated repeated-vs-unique-topic A/B through TpuMatcher.match_batch and
the broker config prints hit rate + dedup ratio next to the stage
breakdown.

DEVICE PIPELINE (ISSUE 6): config "7" A/Bs the sync blocking serve
against the async double-buffered dispatch ring (BENCH_PIPE_SUBS caps
its sub count, BENCH_PIPE_SMALL sets the shallow-queue batch;
BIFROMQ_PIPELINE_DEPTH steers the pipeline
itself) and reports batch p50/p99 per leg + the dispatch/ready/fetch
stage split. Every run is stamped with platform, device_kind and
device_count.

SUBSCRIPTION CHURN (ISSUE 9): config "8" runs sustained subscribe/
unsubscribe against a full-size base interleaved with publishes,
measuring single-mutation patch-apply latency (host plan + narrow device
scatter) against the full-rebuild cost, match p99 during churn, the
zero-rebuild/zero-generation-bump window, and exact oracle parity after
the storm (BENCH_CHURN_SUBS / BENCH_CHURN_OPS; persists
bench_results/churn_last.json and stamps record["churn"]).

INGEST BYTE PLANE (ISSUE 11): config "9" A/Bs publish-side topic prep —
per-message python loop vs the contiguous-byte-buffer plane (native C++
/ vectorized numpy) vs the device-side Pallas hash kernel — on the
topic-diversity corpus, checks exact three-way parity, and verifies the
profiler attributes a `tokenize` stage on every device batch
(BENCH_TOK_SUBS sizes its base; every record stamps a "tokenize"
section when config 9 ran).

MIXED MILLION-CLIENT WORKLOAD (ISSUE 13): config "10" executes one
deterministic `workloads.config_mixed` plan — Zipf tenants, QoS mix,
$share worker pools, a >=10k-op retained SET/CLEAR flood against the
PATCHED RetainedIndex (acceptance: ZERO full rebuilds, device scans
byte-identical to the host oracle before/during/after), async wildcard
scans through the retain.scan plane (cache hit-rate on the repeat
pass), publish matching under concurrent session churn, balanced-vs-
random $share election spread, a governed reconnect drain storm
(tenant fairness: the quiet tenants' mean admission wait must not sit
behind tenant0's herd), and the SLO top-k snapshot (BENCH_MIX_CLIENTS
default 100_000 — set 1_000_000 for the paper-scale record;
BENCH_MIX_RETAIN_OPS default 10_000). Stamps record["mixed"].

SHARDED MESH (ISSUE 15): config "11" serves BENCH_MESH_SUBS logical
subscriptions from a BENCH_MESH_REPLICAS x BENCH_MESH_SHARDS device
mesh (CPU: XLA_FLAGS=--xla_force_host_platform_device_count=8) with a
replicated hot tenant, checks per-shard bytes against the
CapacityPlanner.fits prediction, and runs a BENCH_MESH_CHURN_OPS churn
storm through the per-shard patch plane (acceptance: zero rebuilds,
zero generation bumps, exact oracle parity). Stamps record["mesh"].

ELASTIC MESH (ISSUE 17): config "12" live-migrates the Zipf whale
tenant off its hot shard through the begin/copy/ready/cutover/
tombstone ladder while async match batches serve THROUGH the
dual-serve window — migration wall-clock vs the full mesh rebuild,
match p99 during the window, skew before/after, zero rebuilds, zero
generation bumps, exact oracle parity. Stamps record["reshard"].

Env knobs: BENCH_CONFIGS ("1,2,3,4,5" default; "2" = headline only;
"6" = match-cache A/B; "7" = pipeline A/B; "8" = churn/patch;
"9" = ingest byte-plane A/B; "10" = mixed million-client workload;
"11" = sharded mesh serving; "12" = live migration vs mesh rebuild
(BENCH_RESHARD_SUBS 200000, BENCH_RESHARD_SHARDS 8,
BENCH_RESHARD_REPLICAS 1, BENCH_RESHARD_TENANTS 64,
BENCH_RESHARD_CHUNK 256);
BENCH_CACHE_HOT_TOPICS sizes config 6's Zipf pool),
BENCH_SUBS (config-2 subs, default 1_000_000), BENCH_BATCH (16384),
BENCH_ITERS (30), BENCH_K (16), BENCH_SEED (0), BENCH_RETAINED (1_000_000),
BENCH_COMPACTION (sort|scatter), BENCH_INTERVALS (64, route-walk lanes),
BENCH_ROUTES (1 = measure the e2e matched-routes path; 0 = count-only),
BENCH_LATENCY (0; 1 = small-batch latency frontier sweep, B in
BENCH_LATENCY_B default "256,1024,4096"),
BENCH_SHARED_TENANTS (1000), BENCH_SHARED_SUBS (1000), BENCH_MT_TENANTS
(10_000), BENCH_MT_SUBS (1_000_000).
"""

import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))
STOCK_BASELINE_PATH = os.path.join(_REPO, "bench_results",
                                   "stock_baseline.json")


def load_stock_baseline():
    """Measured stock rates from the C++ proxy run (``bench_stock.py``).

    Returns (topics_rate, routes_rate, basis_str), or ``(None, None,
    reason)`` when no measured baseline is on disk — ``vs_baseline`` is
    then reported as null, never divided by an assumed rate. The c2
    rates are the stock side's BEST cells (B16384 has the higher
    matched_routes/s; the comparison hands the stock side its best
    operating point per metric).
    """
    try:
        with open(STOCK_BASELINE_PATH) as f:
            sb = json.load(f)
        cells = sb["c2_wildcard_1000000"]["cells"]
        topics = max(c["topics_per_s"] for c in cells.values())
        routes = max(c["matched_routes_per_s"] for c in cells.values())
        return topics, routes, (
            "measured stockmatch.cpp (kernel-vs-kernel, cache-off, 1-core"
            " stock; best stock cell per metric)")
    except (OSError, KeyError, ValueError):
        return None, None, "not measured (stock_baseline.json missing)"


def _vs(rate, stock):
    return round(rate / stock, 3) if stock else None

# --match-cache=on|off A/B flag (ISSUE 4): mapped onto the env knob the
# matcher reads (BIFROMQ_MATCH_CACHE) so every plane in this process —
# TpuMatcher, MeshMatcher, the broker's dist service — follows the mode
for _arg in list(sys.argv[1:]):
    if _arg.startswith("--match-cache="):
        _mode = _arg.split("=", 1)[1].lower()
        if _mode not in ("on", "off"):
            raise SystemExit(f"--match-cache={_mode!r} (use on|off)")
        os.environ["BIFROMQ_MATCH_CACHE"] = "1" if _mode == "on" else "0"
        sys.argv.remove(_arg)

CONFIGS = os.environ.get("BENCH_CONFIGS", "1,2,3,4,5").split(",")
N_SUBS = int(os.environ.get("BENCH_SUBS", "1000000"))
BATCH = int(os.environ.get("BENCH_BATCH", "16384"))
ITERS = int(os.environ.get("BENCH_ITERS", "30"))
K_STATES = int(os.environ.get("BENCH_K", "16"))
SEED = int(os.environ.get("BENCH_SEED", "0"))
N_RETAINED = int(os.environ.get("BENCH_RETAINED", "1000000"))
SHARED_TENANTS = int(os.environ.get("BENCH_SHARED_TENANTS", "1000"))
SHARED_SUBS = int(os.environ.get("BENCH_SHARED_SUBS", "1000"))
MT_TENANTS = int(os.environ.get("BENCH_MT_TENANTS", "10000"))
MT_SUBS = int(os.environ.get("BENCH_MT_SUBS", "1000000"))
# 64 lanes: the c2@1M interval-count distribution measured p99=37 with
# 0.024% overflow at A=64 vs 2.2% at A=32 — and every overflow row costs
# a ~360 topics/s host-oracle re-match, so lane bytes are the cheaper coin
INTERVALS = int(os.environ.get("BENCH_INTERVALS", "64"))
ROUTES_MODE = os.environ.get("BENCH_ROUTES", "1") != "0"
LATENCY_MODE = os.environ.get("BENCH_LATENCY", "0") == "1"
EXPAND_AB_MODE = os.environ.get("BENCH_EXPAND_AB", "1") != "0"


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def _compile(tries, *, name, max_levels=16):
    from bifromq_tpu.models.automaton import compile_tries
    from bifromq_tpu.ops.match import DeviceTrie

    t0 = time.time()
    ct = compile_tries(tries, max_levels=max_levels)
    t1 = time.time()
    log(f"[{name}] compiled: nodes={ct.n_nodes} slots={ct.n_slots} "
        f"({t1 - t0:.1f}s)")
    # ISSUE 8: bench builds bypass TpuMatcher, so stamp the compile into
    # the ledger here — the record's compile_ledger must attribute the
    # build that produced the headline table, not come back empty on
    # direct-walk configs (shared derivation with the matcher installs)
    from bifromq_tpu.obs.capacity import record_compile_event
    record_compile_event(ct, reason=f"bench:{name}", duration_s=t1 - t0)
    return ct, DeviceTrie.from_compiled(ct), t1 - t0


def _measure_match(tries, probe_fn, *, name, k_states=K_STATES,
                   iters=ITERS, batch=BATCH, max_levels=16,
                   compiled=None):
    """Compile `tries` (or reuse ``compiled``), probe with batches from
    probe_fn(i) -> queries.

    Returns dict of measured numbers. probe_fn yields (levels_list, tenant)
    pairs resolved against the compiled roots.
    """
    import jax

    from bifromq_tpu.models.automaton import tokenize
    from bifromq_tpu.ops.match import Probes, walk_count_only

    if compiled is None:
        ct, dev, compile_s = _compile(tries, name=name,
                                      max_levels=max_levels)
    else:
        ct, dev, compile_s = compiled
    t0 = time.time()
    t1 = t0 + compile_s

    n_batches = 4
    probe_sets = []
    all_queries = []
    toks = []
    t2 = time.time()
    for i in range(n_batches):
        queries = probe_fn(i, batch)
        all_queries.append(queries)
        toks.append(tokenize([q[0] for q in queries],
                             [ct.root_of(q[1]) for q in queries],
                             max_levels=ct.max_levels, salt=ct.salt,
                             batch=batch))
    t3 = time.time()
    # tokenize-only rate: device_put is timed apart so upload time does
    # not blend into the tokenizer number
    tok_rate = batch * n_batches / (t3 - t2)
    probe_sets = [Probes.from_tokenized(t) for t in toks]
    # Read back a slice of EVERY array of every set so no in-flight
    # upload bleeds into the warmup number.
    for p in probe_sets:
        for a in (p.tok_h1, p.tok_h2, p.lengths, p.roots, p.sys_mask):
            np.asarray(a[:1])
    t4u = time.time()
    upload_s = t4u - t3

    compaction = os.environ.get("BENCH_COMPACTION", "sort")
    if compaction not in ("sort", "scatter"):
        raise ValueError(f"BENCH_COMPACTION={compaction!r} "
                         "(must be sort|scatter)")
    run = lambda p: walk_count_only(dev, p, probe_len=ct.probe_len,
                                    k_states=k_states,
                                    compaction=compaction)

    for p in probe_sets:
        np.asarray(run(p)[0])  # true sync per set (see note above)
    t4 = time.time()
    log(f"[{name}] warmup+jit {t4 - t4u:.1f}s; probe upload {upload_s:.1f}s; "
        f"host tokenize {tok_rate:,.0f} topics/s")

    # ---- pipelined throughput: one readback at the end --------------------
    # fire-and-forget dispatch, sync once on the LAST call's output: a
    # per-iteration readback or a loop-carried accumulator would
    # serialize dispatch.
    s = time.perf_counter()
    for it in range(iters - 1):
        run(probe_sets[it % n_batches])
    cnt_last, ovf_last = run(probe_sets[(iters - 1) % n_batches])
    np.asarray(cnt_last)
    elapsed = time.perf_counter() - s
    device_rate = batch * iters / elapsed

    # exact totals, untimed: the timed loop cycles these same probe sets,
    # so per-set counts scaled by occurrence count reproduce it exactly
    uses = [(iters + n_batches - 1 - i) // n_batches for i in range(n_batches)]
    total_routes = 0.0
    total_ovf = 0
    ovf_masks = []
    for bi, p in enumerate(probe_sets):
        cnt, ovf = run(p)
        ovf_masks.append(np.asarray(ovf))
        total_routes += float(np.asarray(cnt, dtype=np.float64).sum()) * uses[bi]
        total_ovf += int(ovf_masks[-1].sum()) * uses[bi]

    # ---- host-fallback cost for overflowed topics -------------------------
    # overflowed topics re-match on the host oracle; fold that cost in,
    # sampling overflow rows across ALL probe sets (overflow may cluster)
    ovf_frac = total_ovf / (batch * iters)
    oracle_rate = None
    eff_rate = device_rate
    if total_ovf:
        samples = []
        for bi in range(n_batches):
            for qi in np.nonzero(ovf_masks[bi])[0][:32]:
                samples.append(all_queries[bi][qi])
        s = time.perf_counter()
        for levels, t in samples:
            trie = tries.get(t)
            if trie is not None:
                trie.match(list(levels))
        host_t = time.perf_counter() - s
        if samples:
            oracle_rate = len(samples) / host_t
            # effective: device pipeline + host oracle work in parallel
            # threads would overlap; be conservative and ADD the time
            host_total = (batch * iters * ovf_frac) / oracle_rate
            eff_rate = batch * iters / (elapsed + host_total)

    # ---- sync latency -----------------------------------------------------
    lat = []
    for it in range(min(iters, 8)):
        p = probe_sets[it % n_batches]
        s = time.perf_counter()
        cnt, ovf = run(p)
        np.asarray(cnt)
        lat.append(time.perf_counter() - s)
    lat = np.array(lat)
    out = {
        "topics_per_s": round(eff_rate, 1),
        "device_topics_per_s": round(device_rate, 1),
        "routes_per_s": round(total_routes / elapsed, 1),
        "overflow_frac": round(ovf_frac, 5),
        "oracle_fallback_topics_per_s": (round(oracle_rate, 1)
                                         if oracle_rate else None),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
        "host_tokenize_topics_per_s": round(tok_rate, 1),
        "probe_upload_s": round(upload_s, 2),
        "compile_s": round(t1 - t0, 1),
        "batch": batch,
        "k_states": k_states,
    }
    log(f"[{name}] {json.dumps(out)}")
    return out


def _measure_routes(tries, probe_fn, *, name, compiled,
                    k_states=None, iters=None, batch=None,
                    max_intervals=None):
    """End-to-end matched-routes measurement (the honest headline).

    Pipelined interval-walk dispatch with double-buffered readback: while
    the device walks iteration i+1, the host reads back and expands
    iteration i's intervals into materialized per-topic route-slot arrays
    (ops.match.expand_intervals) — the same per-topic route-entry vectors
    the stock proxy materializes. Tokenize cost is folded in SERIALLY
    (conservative: real serving overlaps the multicore C++ tokenizer with
    device compute).
    """
    from bifromq_tpu.models.automaton import TokenCache, tokenize
    from bifromq_tpu.ops.match import (Probes, expand_intervals,
                                       walk_routes)
    k_states = k_states or K_STATES
    iters = iters or ITERS
    batch = batch or BATCH
    max_intervals = max_intervals or INTERVALS
    tok_cache = (TokenCache()
                 if os.environ.get("BENCH_TOK_CACHE", "1") != "0" else None)

    ct, dev, compile_s = compiled
    n_batches = 4
    all_queries = [probe_fn(i, batch) for i in range(n_batches)]
    t2 = time.time()
    toks = [tokenize([q[0] for q in queries],
                     [ct.root_of(q[1]) for q in queries],
                     max_levels=ct.max_levels, salt=ct.salt, batch=batch,
                     cache=tok_cache)
            for queries in all_queries]
    t3 = time.time()
    tok_rate = batch * n_batches / (t3 - t2)  # COLD (first-touch) rate
    probe_sets = [Probes.from_tokenized(t) for t in toks]
    for p in probe_sets:
        for a in (p.tok_h1, p.tok_h2, p.lengths, p.roots, p.sys_mask):
            np.asarray(a[:1])  # true upload sync (see _measure_match note)
    compaction = os.environ.get("BENCH_COMPACTION", "sort")
    run = lambda p: walk_routes(dev, p, probe_len=ct.probe_len,
                                k_states=k_states,
                                max_intervals=max_intervals,
                                compaction=compaction)

    def process(r):
        s_np = np.asarray(r.start)
        c_np = np.asarray(r.count)
        ovf = np.asarray(r.overflow)
        slots, offs = expand_intervals(s_np, c_np)
        return slots.size, int(ovf.sum()), slots, offs

    t4u = time.time()
    for p in probe_sets:
        process(run(p))  # warmup + jit + readback-path warmup
    log(f"[{name}] routes-walk warmup+jit {time.time() - t4u:.1f}s; "
        f"host tokenize {tok_rate:,.0f} topics/s")

    # ---- pipelined e2e: dispatch iter i+1, then read back + expand iter i
    s = time.perf_counter()
    prev = None
    total_routes = 0
    total_ovf = 0
    for it in range(iters):
        h = run(probe_sets[it % n_batches])
        if prev is not None:
            nr, no, _, _ = process(prev)
            total_routes += nr
            total_ovf += no
        prev = h
    nr, no, _, _ = process(prev)
    total_routes += nr
    total_ovf += no
    elapsed = time.perf_counter() - s
    pipe_topics = batch * iters / elapsed
    pipe_routes = total_routes / elapsed

    # ---- host-oracle fold for rows even escalation couldn't fit ----------
    ovf_frac = total_ovf / (batch * iters)
    eff_elapsed = elapsed
    oracle_rate = None
    if total_ovf:
        from bifromq_tpu.models.automaton import tokenize as _tk  # noqa
        r0 = run(probe_sets[0])
        ovf_mask = np.asarray(r0.overflow)
        samples = [all_queries[0][qi]
                   for qi in np.nonzero(ovf_mask)[0][:32]]
        if samples:
            s0 = time.perf_counter()
            for levels, t in samples:
                trie = tries.get(t)
                if trie is not None:
                    trie.match(list(levels))
            oracle_rate = len(samples) / (time.perf_counter() - s0)
            eff_elapsed += (batch * iters * ovf_frac) / oracle_rate

    # ---- conservative serial tokenize fold -------------------------------
    tok_s = batch * iters / tok_rate
    e2e_topics = batch * iters / (eff_elapsed + tok_s)
    e2e_routes = total_routes / (eff_elapsed + tok_s)

    # ---- host↔device round trip vs walk+readback ------------------------
    # a tiny scalar round trip isolates the transport cost; walk_read
    # minus it approximates the walk's own time (an estimate — device
    # truth comes from a profiler trace, ROADMAP S3)
    import jax
    rtts = []
    for _ in range(8):
        s0 = time.perf_counter()
        np.asarray(jax.device_put(np.zeros(1, np.int32)))
        rtts.append(time.perf_counter() - s0)
    rtt_ms = float(np.percentile(rtts, 50)) * 1e3

    # ---- sync latency: tokenize + upload + walk + readback + expand ------
    lat = []
    phases = {"tok_ms": [], "upload_ms": [], "walk_read_ms": [],
              "expand_ms": []}
    for it in range(min(iters, 8)):
        queries = all_queries[it % n_batches]
        s0 = time.perf_counter()
        tk = tokenize([q[0] for q in queries],
                      [ct.root_of(q[1]) for q in queries],
                      max_levels=ct.max_levels, salt=ct.salt, batch=batch,
                      cache=tok_cache)
        s1 = time.perf_counter()
        p = Probes.from_tokenized(tk)
        np.asarray(p.tok_h1[:1])
        s2 = time.perf_counter()
        r = run(p)
        s_np = np.asarray(r.start)
        c_np = np.asarray(r.count)
        s3 = time.perf_counter()
        expand_intervals(s_np, c_np)
        s4 = time.perf_counter()
        lat.append(s4 - s0)
        phases["tok_ms"].append((s1 - s0) * 1e3)
        phases["upload_ms"].append((s2 - s1) * 1e3)
        phases["walk_read_ms"].append((s3 - s2) * 1e3)
        phases["expand_ms"].append((s4 - s3) * 1e3)
    lat = np.array(lat)
    out = {
        "e2e_topics_per_s": round(e2e_topics, 1),
        "e2e_matched_routes_per_s": round(e2e_routes, 1),
        "pipeline_topics_per_s": round(pipe_topics, 1),
        "pipeline_matched_routes_per_s": round(pipe_routes, 1),
        "routes_per_topic": round(total_routes / (batch * iters), 2),
        "overflow_frac": round(ovf_frac, 5),
        "oracle_fallback_topics_per_s": (round(oracle_rate, 1)
                                         if oracle_rate else None),
        "host_tokenize_topics_per_s": round(tok_rate, 1),
        "host_tokenize_warm_topics_per_s": round(
            batch / (float(np.percentile(phases["tok_ms"], 50)) / 1e3), 1),
        "tok_cache_hit_rate": (round(tok_cache.hits / max(
            1, tok_cache.hits + tok_cache.misses), 3)
            if tok_cache is not None else None),
        "e2e_p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
        "e2e_p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
        "phase_ms_p50": {k: round(float(np.percentile(v, 50)), 2)
                         for k, v in phases.items()},
        "device_rtt_ms_p50": round(rtt_ms, 3),
        "device_kernel_ms_p50": round(max(0.0, float(np.percentile(
            phases["walk_read_ms"], 50)) - rtt_ms), 2),
        "batch": batch,
        "k_states": k_states,
        "max_intervals": max_intervals,
        "compile_s": round(compile_s, 1),
    }
    log(f"[{name}] routes-e2e {json.dumps(out)}")
    return out


def _measure_expand_ab(tries, probe_fn, *, name, compiled,
                       k_states=None, iters=None, batch=None,
                       max_intervals=None):
    """Device-vs-host fan-out A/B (ISSUE 19 headline): end-to-end
    matched-routes/s over walk + expansion + per-peer bucketing, tokenize
    excluded (identical on every leg). Three legs, same probe sets, same
    walk kernel:

    - ``host``: the pre-ISSUE-19 serving shape — read back the full
      [B, A] interval grids, ``expand_intervals`` on host, then the
      per-route ``setdefault(...).append`` delivery grouping the dist
      service does (dist/service.py BatchDeliveryCall grouping), its rate
      measured on a bounded pair sample and extrapolated (the loop at
      full c2 fan-out is minutes per batch — the very wall this A/B
      documents).
    - ``host_vectorized``: strongest host contender — same expansion,
      then ``bucket_pairs_host`` (numpy stable-argsort grouping). Not
      what the pre-change code did, reported so the headline is not a
      strawman ratio.
    - ``device``: fused ``expand_routes`` (ragged-arange expansion +
      counting-sort bucketing on device); the host reads back only the
      compact pre-bucketed pair buffers. ``trunc`` rows re-expand on
      host from the grids — the exact serving cold path.

    The expansion cap is sized from the warmup batches' MEASURED fan-out
    (1.25x margin, 64k-rounded — NOT pow2, and NOT batch x
    BIFROMQ_EXPAND_CAP: device expansion is O(cap) whatever the live
    pair count, so an oversized buffer charges the device leg for lanes
    the workload never fills).
    """
    import jax

    from bifromq_tpu.dist.deliverer import build_peer_table
    from bifromq_tpu.models.automaton import tokenize
    from bifromq_tpu.ops.match import (Probes, bucket_pairs_host,
                                       expand_intervals, expand_routes,
                                       walk_routes)
    k_states = k_states or K_STATES
    iters = int(os.environ.get("BENCH_EXPAND_AB_ITERS",
                               str(min(iters or ITERS, 6))))
    # B=4096 at c2 fan-out is the measured sweet spot for the full-route
    # walk: walk_routes (unlike the headline's walk_count_only) scales
    # superlinearly with batch (measured ~36 us/topic at 4096 vs ~116 at
    # 8192), and the expand stage is linear in cap through ~90M lanes
    # with a ~2.5x per-pair cliff above (multi-GB working set on the
    # single-core backend). Batch is a tuning knob, not part of the A/B
    # contract: every leg serves the same batches either way.
    batch = int(os.environ.get("BENCH_EXPAND_AB_BATCH",
                               str(min(batch or BATCH, 4096))))
    max_intervals = max_intervals or INTERVALS

    ct, dev, _ = compiled
    tab = build_peer_table(ct.matchings_arr)
    n_peers = tab.n_peers
    dev_slot_peer = jax.device_put(tab.slot_peer)

    n_batches = 2
    all_queries = [probe_fn(i, batch) for i in range(n_batches)]
    toks = [tokenize([q[0] for q in queries],
                     [ct.root_of(q[1]) for q in queries],
                     max_levels=ct.max_levels, salt=ct.salt, batch=batch)
            for queries in all_queries]
    probe_sets = [Probes.from_tokenized(t) for t in toks]
    for p in probe_sets:
        for a in (p.tok_h1, p.tok_h2, p.lengths, p.roots, p.sys_mask):
            np.asarray(a[:1])  # true upload sync (see _measure_match)
    compaction = os.environ.get("BENCH_COMPACTION", "sort")
    run = lambda p: walk_routes(dev, p, probe_len=ct.probe_len,
                                k_states=k_states,
                                max_intervals=max_intervals,
                                compaction=compaction)

    # ---- warmup + cap sizing from measured fan-out -----------------------
    t0 = time.perf_counter()
    max_pairs = 1
    grids = []
    for p in probe_sets:
        r = run(p)
        c_np = np.asarray(r.count).copy()
        c_np[np.asarray(r.overflow)] = 0
        np.maximum(c_np, 0, out=c_np)
        grids.append((np.asarray(r.start), c_np))
        max_pairs = max(max_pairs, int(c_np.sum(dtype=np.int64)))
    cap = max(65536, -(-int(max_pairs * 1.25) // 65536) * 65536)
    er = expand_routes(run(probe_sets[0]), dev_slot_peer, cap=cap,
                       n_peers=n_peers)
    np.asarray(er.peer_offsets)  # jit + readback-path warmup
    log(f"[{name}] expand-ab warmup {time.perf_counter() - t0:.1f}s; "
        f"max_pairs={max_pairs} cap={cap} n_peers={n_peers}")

    def host_expand(gs, gc):
        slots, offs = expand_intervals(gs, gc)
        rows = np.repeat(np.arange(offs.size - 1, dtype=np.int32),
                         np.diff(offs))
        return slots, rows, offs

    # ---- one-shot bucket parity check (warmup batch, untimed) ------------
    gs0, gc0 = grids[0]
    h_slots, h_rows, _ = host_expand(gs0, gc0)
    hps, hpr, hpo = bucket_pairs_host(h_slots, h_rows, tab.slot_peer,
                                      n_peers)
    live = int(np.asarray(er.peer_offsets)[n_peers + 1])
    parity = (not np.asarray(er.trunc).any()
              and live == int(hpo[n_peers + 1])
              and np.array_equal(np.asarray(er.peer_slots)[:live],
                                 hps[:live])
              and np.array_equal(np.asarray(er.peer_rows)[:live],
                                 hpr[:live]))
    if not parity:
        log(f"[{name}] expand-ab WARNING: device/host bucket MISMATCH")

    # ---- device leg ------------------------------------------------------
    ab_debug = os.environ.get("BENCH_EXPAND_AB_DEBUG", "0") != "0"
    dev_lat = []
    dev_routes = 0
    trunc_rows = 0
    for it in range(iters):
        s0 = time.perf_counter()
        r = run(probe_sets[it % n_batches])
        if ab_debug:
            jax.block_until_ready(r.count)
            t_walk = time.perf_counter() - s0
        er = expand_routes(r, dev_slot_peer, cap=cap, n_peers=n_peers)
        if ab_debug:
            jax.block_until_ready(er.peer_slots)
            t_expand = time.perf_counter() - s0 - t_walk
        # the delivery surface serving reads: pre-bucketed pairs + the
        # per-topic offsets + the escalation flags
        ps = np.asarray(er.peer_slots)
        pr = np.asarray(er.peer_rows)
        po = np.asarray(er.peer_offsets)
        ro = np.asarray(er.row_offsets)
        n_live = int(np.asarray(er.n_pairs))
        tr = np.asarray(er.trunc)
        np.asarray(er.overflow)
        if tr.any():
            # cold path: trunc rows re-expand from the grids, exactly
            # like serving's escalation fetch
            first = int(np.argmax(tr))
            n_live = int(ro[first])
            g_s = np.asarray(er.start)
            g_c = np.maximum(np.asarray(er.count), 0)
            g_c[~tr] = 0
            esc_slots, _ = expand_intervals(g_s, g_c)
            n_live += esc_slots.size
            trunc_rows += int(tr.sum())
        dev_routes += n_live
        dev_lat.append(time.perf_counter() - s0)
        if ab_debug:
            log(f"[{name}] expand-ab dbg it{it}: walk {t_walk * 1e3:.0f}ms"
                f" expand {t_expand * 1e3:.0f}ms"
                f" readback {(dev_lat[-1] - t_walk - t_expand) * 1e3:.0f}ms")
    dev_elapsed = float(np.sum(dev_lat))
    del ps, pr, po

    # ---- host leg: walk + grid readback + expand (timed), python
    # delivery grouping folded from a sampled rate ------------------------
    host_lat = []
    host_routes = 0
    for it in range(iters):
        s0 = time.perf_counter()
        r = run(probe_sets[it % n_batches])
        gs = np.asarray(r.start)
        gc = np.asarray(r.count).copy()
        gc[np.asarray(r.overflow)] = 0
        np.maximum(gc, 0, out=gc)
        slots, rows, offs = host_expand(gs, gc)
        host_routes += slots.size
        host_lat.append(time.perf_counter() - s0)
    host_expand_elapsed = float(np.sum(host_lat))
    # per-route python grouping rate, sampled (generously: peer ids are
    # pre-gathered vectorized; the dist service hashes a (broker, str)
    # tuple per route on top of this)
    n_slot = tab.slot_peer.shape[0]
    sample = min(h_slots.size, 2_000_000)
    if sample:
        peer_of = (tab.slot_peer[np.clip(h_slots[:sample], 0, n_slot - 1)]
                   if n_slot else np.zeros(sample, np.int32)).tolist()
        sl_list = h_slots[:sample].tolist()
        s0 = time.perf_counter()
        by_peer = {}
        for pe, sl in zip(peer_of, sl_list):
            by_peer.setdefault(pe, []).append(sl)
        py_rate = sample / (time.perf_counter() - s0)
        del by_peer, peer_of, sl_list
    else:
        py_rate = float("inf")
    host_elapsed = host_expand_elapsed + host_routes / py_rate

    # ---- host vectorized leg --------------------------------------------
    viters = max(2, iters // 2)
    vec_lat = []
    vec_routes = 0
    for it in range(viters):
        s0 = time.perf_counter()
        r = run(probe_sets[it % n_batches])
        gs = np.asarray(r.start)
        gc = np.asarray(r.count).copy()
        gc[np.asarray(r.overflow)] = 0
        np.maximum(gc, 0, out=gc)
        slots, rows, offs = host_expand(gs, gc)
        bucket_pairs_host(slots, rows, tab.slot_peer, n_peers)
        vec_routes += slots.size
        vec_lat.append(time.perf_counter() - s0)
    vec_elapsed = float(np.sum(vec_lat))

    dev_rate = dev_routes / dev_elapsed
    host_rate = host_routes / host_elapsed
    vec_rate = vec_routes / vec_elapsed
    out = {
        "device_matched_routes_per_s": round(dev_rate, 1),
        "host_matched_routes_per_s": round(host_rate, 1),
        "host_vectorized_matched_routes_per_s": round(vec_rate, 1),
        "speedup_vs_host": round(dev_rate / host_rate, 2),
        "speedup_vs_host_vectorized": round(dev_rate / vec_rate, 2),
        "routes_per_topic": round(dev_routes / (batch * iters), 2),
        "device_ms_p50": round(
            float(np.percentile(dev_lat, 50)) * 1e3, 1),
        "host_expand_ms_p50": round(
            float(np.percentile(host_lat, 50)) * 1e3, 1),
        "host_python_group_pairs_per_s": (round(py_rate, 1)
                                          if sample else None),
        "bucket_parity": parity,
        "cap": cap,
        "cap_fill": round(max_pairs / cap, 3),
        "trunc_row_frac": round(trunc_rows / (batch * iters), 6),
        "n_peers": n_peers,
        "batch": batch,
        "iters": iters,
        "k_states": k_states,
        "max_intervals": max_intervals,
        "basis": ("walk + expand + per-peer bucketing, tokenize excluded"
                  " (identical all legs); host grouping rate sampled at"
                  f" {sample} pairs then extrapolated"),
    }
    log(f"[{name}] expand-ab {json.dumps(out)}")
    return out


def _latency_frontier(tries, probe_fn, *, name, compiled,
                      k_states=None):
    """Small-batch latency mode (VERDICT r4 #4): per-batch sync p50/p99
    and topics/s across B ∈ BENCH_LATENCY_B, count walk + route walk, with
    a phase breakdown to root-cause the latency floor (dispatch vs
    transfer vs walk)."""
    from bifromq_tpu.models.automaton import tokenize
    from bifromq_tpu.ops.match import (Probes, expand_intervals,
                                       walk_count_only, walk_routes)
    k_states = k_states or K_STATES
    ct, dev, _ = compiled
    sweep_b = [int(x) for x in os.environ.get(
        "BENCH_LATENCY_B", "256,1024,4096").split(",") if x]
    compaction = os.environ.get("BENCH_COMPACTION", "sort")
    grid = {}
    for b in sweep_b:
        queries = probe_fn(0, b)
        tok = tokenize([q[0] for q in queries],
                       [ct.root_of(q[1]) for q in queries],
                       max_levels=ct.max_levels, salt=ct.salt, batch=b)
        p = Probes.from_tokenized(tok)
        np.asarray(p.tok_h1[:1])
        runs = {
            "count": lambda: walk_count_only(
                dev, p, probe_len=ct.probe_len, k_states=k_states,
                compaction=compaction),
            "routes": lambda: walk_routes(
                dev, p, probe_len=ct.probe_len, k_states=k_states,
                max_intervals=INTERVALS, compaction=compaction),
        }
        cell = {}
        for kind, fn in runs.items():
            fn()  # jit warmup
            np.asarray(fn()[0] if kind == "count" else fn().start)
            lat, disp = [], []
            for _ in range(20):
                s0 = time.perf_counter()
                r = fn()
                s1 = time.perf_counter()
                if kind == "count":
                    np.asarray(r[0])
                else:
                    s_np = np.asarray(r.start)
                    c_np = np.asarray(r.count)
                    expand_intervals(s_np, c_np)
                lat.append(time.perf_counter() - s0)
                disp.append(s1 - s0)
            lat = np.array(lat)
            cell[kind] = {
                "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
                "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
                "dispatch_p50_ms": round(
                    float(np.percentile(disp, 50)) * 1e3, 2),
                "topics_per_s": round(b / float(np.percentile(lat, 50)), 1),
            }
        grid[f"B{b}"] = cell
        log(f"[{name}] latency B={b}: {json.dumps(cell)}")
    return grid


def _run_modes(tries, probe, *, name, compiled, out, **kw):
    """Shared per-config mode fan-out: e2e routes + expand A/B + latency
    frontier."""
    if ROUTES_MODE:
        out["routes"] = _measure_routes(tries, probe, name=name,
                                        compiled=compiled, **kw)
    if EXPAND_AB_MODE:
        out["expand_ab"] = _measure_expand_ab(tries, probe, name=name,
                                              compiled=compiled, **kw)
    if LATENCY_MODE:
        out["latency"] = _latency_frontier(
            tries, probe, name=name, compiled=compiled,
            k_states=kw.get("k_states"))
    return out


def bench_config1():
    from bifromq_tpu import workloads
    tries = workloads.config_exact(10_000, seed=SEED)
    topics = workloads.probe_topics(BATCH * 4, seed=SEED + 1,
                                    n_level_names=max(64, 10_000 // 100))

    def probe(i, batch):
        return [(t, "tenant0") for t in topics[i * batch:(i + 1) * batch]]
    name = "c1_exact_10K"
    compiled = _compile(tries, name=name)
    out = _measure_match(tries, probe, name=name, compiled=compiled)
    return _run_modes(tries, probe, name=name, compiled=compiled, out=out)


def bench_config2():
    from bifromq_tpu import workloads
    tries = workloads.config_wildcard(N_SUBS, seed=SEED)
    name = f"c2_wildcard_{N_SUBS}"
    if os.environ.get("BENCH_SWEEP"):
        sweep_b = [int(x) for x in os.environ.get(
            "BENCH_SWEEP_B", "8192,16384,32768").split(",") if x]
        sweep_k = [int(x) for x in os.environ.get(
            "BENCH_SWEEP_K", "8,16").split(",") if x]
        # one compile, a (batch × k_states) grid of measurements; the best
        # cell becomes the headline (VERDICT-r3 sweep: B∈{8192,32768} ×
        # K∈{8,16} on the sort-compaction kernel)
        compiled = _compile(tries, name=name)
        best, grid = None, {}
        for b in sweep_b:
            topics = workloads.probe_topics(b * 4, seed=SEED + 1)

            def probe(i, batch, topics=topics):
                return [(t, "tenant0")
                        for t in topics[i * batch:(i + 1) * batch]]
            for k in sweep_k:
                r = _measure_match(tries, probe,
                                   name=f"{name}_B{b}_K{k}",
                                   batch=b, k_states=k, compiled=compiled)
                grid[f"B{b}_K{k}"] = r
                if best is None or r["topics_per_s"] > best["topics_per_s"]:
                    best = r
        log(f"[{name}] sweep grid: {json.dumps(grid)}")
        log(f"[{name}] best cell: B={best['batch']} K={best['k_states']}")
        bb, bk = best["batch"], best["k_states"]
        btopics = workloads.probe_topics(bb * 4, seed=SEED + 1)

        def bprobe(i, batch, topics=btopics):
            return [(t, "tenant0") for t in topics[i * batch:(i + 1) * batch]]
        return _run_modes(tries, bprobe, name=name, compiled=compiled,
                          out=best, k_states=bk, batch=bb)

    topics = workloads.probe_topics(BATCH * 4, seed=SEED + 1)

    def probe(i, batch):
        return [(t, "tenant0") for t in topics[i * batch:(i + 1) * batch]]
    compiled = _compile(tries, name=name)
    out = _measure_match(tries, probe, name=name, compiled=compiled)
    return _run_modes(tries, probe, name=name, compiled=compiled, out=out)


def bench_config3():
    from bifromq_tpu import workloads
    tries = workloads.config_shared(SHARED_TENANTS, SHARED_SUBS, seed=SEED)
    topics = workloads.probe_topics(BATCH * 4, seed=SEED + 1,
                                    n_level_names=500)
    tenants = sorted(tries)

    def probe(i, batch):
        ts = topics[i * batch:(i + 1) * batch]
        return [(t, tenants[(i * batch + j) % len(tenants)])
                for j, t in enumerate(ts)]
    name = f"c3_shared_{SHARED_TENANTS}x{SHARED_SUBS}"
    compiled = _compile(tries, name=name)
    out = _measure_match(tries, probe, name=name, compiled=compiled)
    return _run_modes(tries, probe, name=name, compiled=compiled, out=out)


def bench_config4():
    """Retained path: concrete-topic trie probed by wildcard filters."""
    import jax

    from bifromq_tpu import workloads
    from bifromq_tpu.models.retained import RetainedIndex

    t0 = time.time()
    topics = workloads.config_retained(N_RETAINED, seed=SEED)["tenant0"]
    idx = RetainedIndex(max_levels=18, k_states=K_STATES)
    for levels in topics:
        idx.add_topic("tenant0", levels, "/".join(levels))
    ct = idx.refresh()
    t1 = time.time()
    log(f"[c4_retained_{N_RETAINED}] built+compiled {t1 - t0:.1f}s "
        f"nodes={ct.n_nodes}")

    filters = workloads.probe_filters(BATCH * 4, seed=SEED + 2)
    batches = [[("tenant0", f) for f in filters[i * BATCH:(i + 1) * BATCH]]
               for i in range(4)]
    # ---- device-only walk rate (pipelined, like _measure_match) -----------
    probe_sets = [idx.device_probes(batches[i], batch=BATCH)[0]
                  for i in range(4)]
    run = idx.walk_device
    for p in probe_sets:
        np.asarray(run(p)[0])  # true sync (block_until_ready is a no-op)
    dev_iters = ITERS
    s = time.perf_counter()
    for it in range(dev_iters - 1):
        run(probe_sets[it % 4])
    r_last, _ = run(probe_sets[(dev_iters - 1) % 4])
    np.asarray(r_last)
    dev_rate = BATCH * dev_iters / (time.perf_counter() - s)

    # ---- end-to-end (device walk + host range expansion, sync per call) ---
    # production semantics FIRST: every serving lookup passes
    # RetainMessageMatchLimit (default 10, retain/service.py), which also
    # scan-bounds the host fallback for '+'-exploded filters; the
    # unlimited full-enumeration rate is the stress number
    res = idx.match_batch(batches[0], batch=BATCH, limit=10)  # warmup
    iters = max(4, ITERS // 4)
    s = time.perf_counter()
    matched_lim = 0
    for it in range(iters):
        res = idx.match_batch(batches[it % 4], batch=BATCH, limit=10)
        matched_lim += sum(len(r) for r in res)
    lim_elapsed = time.perf_counter() - s

    res = idx.match_batch(batches[0], batch=BATCH)  # warmup (unlimited)
    s = time.perf_counter()
    matched = 0
    for it in range(iters):
        res = idx.match_batch(batches[it % 4], batch=BATCH)
        matched += sum(len(r) for r in res)
    elapsed = time.perf_counter() - s
    out = {
        "filters_per_s_limit10": round(BATCH * iters / lim_elapsed, 1),
        "matched_retained_per_s_limit10": round(matched_lim / lim_elapsed,
                                                1),
        "filters_per_s": round(BATCH * iters / elapsed, 1),
        "device_filters_per_s": round(dev_rate, 1),
        "matched_retained_per_s": round(matched / elapsed, 1),
        "n_retained": N_RETAINED,
        "compile_s": round(t1 - t0, 1),
    }
    log(f"[c4_retained_{N_RETAINED}] {json.dumps(out)}")
    return out


def bench_config5():
    import random

    from bifromq_tpu import workloads
    tries = workloads.config_multi_tenant(MT_TENANTS, MT_SUBS, seed=SEED)
    topics = workloads.probe_topics(BATCH * 4, seed=SEED + 1)
    tenants = sorted(tries)
    # Zipf tenant traffic: heavier tenants see proportionally more queries
    rng = random.Random(SEED + 3)
    cum = []
    acc = 0.0
    for i in range(len(tenants)):
        acc += 1.0 / (i + 1)
        cum.append(acc)
    tenant_seq = rng.choices(tenants, cum_weights=cum, k=BATCH * 4)

    def probe(i, batch):
        ts = topics[i * batch:(i + 1) * batch]
        return [(t, tenant_seq[i * batch + j]) for j, t in enumerate(ts)]
    name = f"c5_multitenant_{MT_TENANTS}x{MT_SUBS}"
    compiled = _compile(tries, name=name)
    out = _measure_match(tries, probe, name=name, compiled=compiled)
    return _run_modes(tries, probe, name=name, compiled=compiled, out=out)


def bench_config6():
    """Match-result cache A/B (ISSUE 4): the full TpuMatcher.match_batch
    serving plane — cache probe + in-batch dedup + device walk + host
    expansion — on (a) a Zipf repeated-topic workload (the dominant MQTT
    pattern: the acceptance bar is cache-on ≥2× cache-off) and (b) a
    unique-topic workload (the miss path: probe/dedup overhead must stay
    in the noise). Prints hit rate + dedup ratio per mode."""
    import random as _random

    from bifromq_tpu import workloads
    from bifromq_tpu.models.matcher import TpuMatcher
    from bifromq_tpu.utils.metrics import MATCH_CACHE

    tries = workloads.config_wildcard(N_SUBS, seed=SEED)
    batch = min(BATCH, 4096)
    iters = max(8, ITERS // 2)
    n_batches = 4
    hot = int(os.environ.get("BENCH_CACHE_HOT_TOPICS", "512"))
    pool = workloads.probe_topics(hot, seed=SEED + 1)
    rng = _random.Random(SEED + 7)
    cum, acc = [], 0.0
    for i in range(hot):
        acc += 1.0 / (i + 1)
        cum.append(acc)
    zipf_sets = [[("tenant0", pool[j]) for j in rng.choices(
        range(hot), cum_weights=cum, k=batch)] for _ in range(n_batches)]
    # TRULY unique topics (probe_topics draws Zipf names and repeats):
    # duplicates would hand the cache-on leg in-batch dedup wins the
    # cache-off leg can't have, biasing the miss-path comparison
    seen = set()
    uniq_topics = []
    gen = 2
    while len(uniq_topics) < batch * n_batches:
        for t in workloads.probe_topics(batch * n_batches, seed=SEED + gen):
            k = tuple(t)
            if k not in seen:
                seen.add(k)
                uniq_topics.append(t)
        gen += 1
    uniq_sets = [[("tenant0", t)
                  for t in uniq_topics[i * batch:(i + 1) * batch]]
                 for i in range(n_batches)]
    name = f"c6_match_cache_{N_SUBS}"
    out = {}
    for mode in ("off", "on"):
        MATCH_CACHE.reset()
        m = TpuMatcher.from_tries(tries, match_cache=(mode == "on"),
                                  auto_compact=False)
        cell = {}
        for wl, sets in (("repeated", zipf_sets), ("unique", uniq_sets)):
            if m.match_cache is not None:
                m.match_cache.clear()
            # warm a FULL cycle: every probe set's miss pattern gets its
            # device shapes jit-compiled (the pow2-snapped miss sub-batch
            # is a new shape class the off path never sees), and the
            # repeated workload's cache reaches steady state — the regime
            # the acceptance bar speaks about
            for ws in sets:
                m.match_batch(ws)
            h0 = m.match_cache.counts() if m.match_cache else (0, 0)
            lat = []
            s = time.perf_counter()
            for it in range(iters):
                if wl == "unique" and m.match_cache is not None:
                    # keep "unique" honest across cycles: every timed
                    # iteration is a pure miss pass (probe + dedup + put
                    # overhead on top of the full device walk)
                    m.match_cache.clear()
                s0 = time.perf_counter()
                m.match_batch(sets[it % n_batches])
                lat.append(time.perf_counter() - s0)
            elapsed = time.perf_counter() - s
            lat = np.array(lat)
            cell[wl] = {
                "topics_per_s": round(batch * iters / elapsed, 1),
                "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
                "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
            }
            if m.match_cache is not None:
                h1 = m.match_cache.counts()
                lookups = (h1[0] - h0[0]) + (h1[1] - h0[1])
                cell[wl]["hit_rate"] = round(
                    (h1[0] - h0[0]) / lookups, 4) if lookups else 0.0
        if m.match_cache is not None:
            cell["cache"] = m.match_cache.snapshot()
            cell["dedup"] = MATCH_CACHE.snapshot()["dedup"]
        out[mode] = cell
        log(f"[{name}] cache={mode}: {json.dumps(cell)}")
    on, off = out.get("on"), out.get("off")
    if on and off:
        out["repeated_speedup"] = round(
            on["repeated"]["topics_per_s"]
            / max(1e-9, off["repeated"]["topics_per_s"]), 2)
        out["unique_p99_ratio"] = round(
            on["unique"]["p99_ms"] / max(1e-9, off["unique"]["p99_ms"]), 2)
        log(f"[{name}] repeated speedup {out['repeated_speedup']}x, "
            f"unique p99 ratio {out['unique_p99_ratio']}")
    return out


def bench_config7():
    """Device-pipeline A/B (ISSUE 6): per-batch serving latency through
    the full TpuMatcher plane.

    - **sync leg** — the BENCH_r01 shape: every batch is a blocking
      full-size `match_batch` round trip (queue → pow2 pad → dispatch →
      device_get), so every topic's latency is the whole batch's.
    - **pipelined leg** — the same topic stream as SMALL adaptive batches
      (the shallow-queue floor the ring emits) through
      `match_batch_async`: `pipeline_depth` workers keep the ring full,
      dispatch overlaps fetch, and per-batch latency is what a publish
      actually waits.

    Prints both legs' topics/s + batch p50/p99 and the p99 speedup (the
    acceptance bar is ≥10×), plus the dispatch/ready/fetch stage
    histograms that replace the old blocking `device.sync` stage.
    """
    import asyncio

    from bifromq_tpu import workloads
    from bifromq_tpu.models.matcher import TpuMatcher
    from bifromq_tpu.models.pipeline import pipeline_depth
    from bifromq_tpu.utils.metrics import STAGES

    n_subs = min(N_SUBS, int(os.environ.get("BENCH_PIPE_SUBS", "200000")))
    tries = workloads.config_wildcard(n_subs, seed=SEED)
    big = min(BATCH, 4096)
    iters = max(8, ITERS // 2)
    try:
        small = int(os.environ.get("BENCH_PIPE_SMALL", "16"))
    except ValueError:
        small = 16
    # clamp to [1, big]: small > big would compute an empty pipelined
    # workload (n_small = 0 → sm[0] IndexError), small < 1 divides by zero
    small = max(1, min(small, big))
    topics = workloads.probe_topics(big * 4, seed=SEED + 1)
    name = f"c7_pipeline_{n_subs}"
    m = TpuMatcher.from_tries(tries, match_cache=False,
                              auto_compact=False)

    batches = [[("tenant0", t) for t in topics[i * big:(i + 1) * big]]
               for i in range(4)]
    # ---- sync leg ---------------------------------------------------------
    m.match_batch(batches[0])   # warm the big-batch shape
    lat = []
    s = time.perf_counter()
    for it in range(iters):
        s0 = time.perf_counter()
        m.match_batch(batches[it % 4])
        lat.append(time.perf_counter() - s0)
    sync_elapsed = time.perf_counter() - s
    lat = np.array(lat)
    sync = {
        "batch": big,
        "topics_per_s": round(big * iters / sync_elapsed, 1),
        "batch_p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
        "batch_p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
    }
    log(f"[{name}] sync: {json.dumps(sync)}")

    # ---- pipelined leg ----------------------------------------------------
    n_small = max(1, min(big * iters // small, 2048))
    sm = [[("tenant0", topics[(j * small + k) % len(topics)])
           for k in range(small)] for j in range(n_small)]
    STAGES.reset()

    async def run_pipe():
        lats = []
        nxt = {"i": 0}
        peak = {"v": 0}

        async def worker():
            while nxt["i"] < len(sm):
                b = sm[nxt["i"]]
                nxt["i"] += 1
                s0 = time.perf_counter()
                await m.match_batch_async(b, batch=None)
                lats.append(time.perf_counter() - s0)
                ring = m._ring
                if ring is not None:
                    peak["v"] = max(peak["v"], ring.peak_inflight)

        # warm the small shapes before timing
        await m.match_batch_async(sm[0])
        s = time.perf_counter()
        workers = [asyncio.ensure_future(worker())
                   for _ in range(pipeline_depth())]
        await asyncio.gather(*workers)
        return lats, time.perf_counter() - s, peak["v"]

    lats, pipe_elapsed, peak_inflight = asyncio.run(run_pipe())
    lats = np.array(lats)
    pipe = {
        "batch": small,
        "topics_per_s": round(small * len(sm) / pipe_elapsed, 1),
        "batch_p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 2),
        "batch_p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 2),
        "peak_in_flight": peak_inflight,
        "ring_depth": pipeline_depth(),
    }
    log(f"[{name}] pipelined: {json.dumps(pipe)}")
    stages = {k: v for k, v in STAGES.snapshot().items()
              if k.startswith("device")}
    out = {
        "sync": sync,
        "pipelined": pipe,
        "batch_p99_speedup": round(
            sync["batch_p99_ms"] / max(1e-9, pipe["batch_p99_ms"]), 2),
        "stage_latency_ms": stages,
    }
    log(f"[{name}] p99 speedup {out['batch_p99_speedup']}x; "
        f"stages: {json.dumps(stages)}")
    return out


def bench_config8():
    """Subscription-churn config (ISSUE 9): sustained subscribe /
    unsubscribe at rate against a full-size base, interleaved with
    publishes — measuring single-mutation patch-apply latency (host plan
    + narrow device update, ``_flush_patches`` forced per op so every
    sample is one mutation end-to-end) and match p99 DURING churn, next
    to the full-rebuild cost the same mutation used to amortize.

    The acceptance bar: patch apply ≥100× faster than the full rebuild
    at 1M subs on CPU; steady churn below the tombstone threshold does
    ZERO full rebuilds and ZERO match-cache generation bumps; results
    stay row-identical to the host oracle. The cell persists to
    bench_results/churn_last.json so the measurement survives the run.
    """
    from bifromq_tpu import workloads
    from bifromq_tpu.models.matcher import TpuMatcher
    from bifromq_tpu.models.oracle import Route
    from bifromq_tpu.obs import OBS
    from bifromq_tpu.types import RouteMatcher

    n_subs = int(os.environ.get("BENCH_CHURN_SUBS", str(N_SUBS)))
    n_ops = int(os.environ.get("BENCH_CHURN_OPS", "256"))
    name = f"c8_churn_{n_subs}"

    def mk(tf, rid, inc=0):
        return Route(matcher=RouteMatcher.from_topic_filter(tf),
                     broker_id=0, receiver_id=rid, deliverer_key="d0",
                     incarnation=inc)

    t0 = time.perf_counter()
    tries = workloads.config_wildcard(n_subs, seed=SEED)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    m = TpuMatcher.from_tries(tries, match_cache=False)
    install_s = time.perf_counter() - t0
    # the cost every compact_threshold'th mutation used to pay: the full
    # compile + device upload + walk warm of this exact population
    rebuild_s = m._last_compile_s
    log(f"[{name}] base: build {build_s:.1f}s, compile+install "
        f"{rebuild_s:.1f}s, patchable={type(m._base_ct).__name__}")
    ledger = OBS.profiler.ledger
    compiles0 = m.compile_count
    bumps0 = ledger.generation_bumps

    batch = 64
    topics = workloads.probe_topics(batch * 8, seed=SEED + 1)
    mb = [[("tenant0", t) for t in topics[i * batch:(i + 1) * batch]]
          for i in range(8)]
    # warm the match shapes AND the patch-scatter jit outside the timing —
    # every probe batch once, so the lazily-compiled escalation walk (an
    # overflow row's first dispatch pays its XLA compile) lands in warmup,
    # not in the churn-window p99
    for wb in mb:
        m.match_batch(wb)
    m.add_route("tenant0", mk("bench/churn/warm/+", "w0"))
    m._flush_patches()
    m.match_batch(mb[0])

    patch_lat, unsub_lat, match_lat = [], [], []
    added = []
    for i in range(n_ops):
        tf = f"bench/churn/{i}/+"
        s0 = time.perf_counter()
        m.add_route("tenant0", mk(tf, f"c{i}", inc=1))
        m._flush_patches()
        patch_lat.append(time.perf_counter() - s0)
        added.append((tf, f"c{i}"))
        if i % 8 == 4:
            s0 = time.perf_counter()
            m.match_batch(mb[(i // 8) % 8])
            match_lat.append(time.perf_counter() - s0)
    for i, (tf, rid) in enumerate(added[:n_ops // 2]):
        s0 = time.perf_counter()
        m.remove_route("tenant0", RouteMatcher.from_topic_filter(tf),
                       (0, rid, "d0"), incarnation=1)
        m._flush_patches()
        unsub_lat.append(time.perf_counter() - s0)

    # oracle parity after the storm: device serving vs authoritative tries
    probe = [("tenant0", t) for t in topics[:256]]
    probe += [("tenant0", ["bench", "churn", str(i), "x"])
              for i in range(0, n_ops, 7)]
    got = m.match_batch(probe)
    want = m.match_from_tries(probe)

    def canon(r):
        return (sorted((x.matcher.mqtt_topic_filter, x.receiver_url)
                       for x in r.normal),
                {f: sorted(x.receiver_url for x in ms)
                 for f, ms in r.groups.items()})
    parity = all(canon(a) == canon(b) for a, b in zip(got, want))

    patch_lat = np.array(patch_lat)
    # degenerate BENCH_CHURN_OPS (<8) can leave the sampled legs empty;
    # report zeros instead of crashing the whole bench run
    unsub_lat = np.array(unsub_lat) if unsub_lat else np.zeros(1)
    match_lat = np.array(match_lat) if match_lat else np.zeros(1)
    p99 = float(np.percentile(patch_lat, 99))
    out = {
        "n_subs": n_subs,
        "churn_ops": n_ops,
        "build_s": round(build_s, 1),
        "full_rebuild_s": round(rebuild_s, 2),
        "patch_apply_ms": {
            "p50": round(float(np.percentile(patch_lat, 50)) * 1e3, 3),
            "p99": round(p99 * 1e3, 3),
            "mean": round(float(patch_lat.mean()) * 1e3, 3),
        },
        "unsubscribe_ms": {
            "p50": round(float(np.percentile(unsub_lat, 50)) * 1e3, 3),
            "p99": round(float(np.percentile(unsub_lat, 99)) * 1e3, 3),
        },
        "patch_vs_rebuild_speedup": round(rebuild_s / max(1e-9, p99), 1),
        "match_p50_ms_during_churn": round(
            float(np.percentile(match_lat, 50)) * 1e3, 2),
        "match_p99_ms_during_churn": round(
            float(np.percentile(match_lat, 99)) * 1e3, 2),
        "match_batch": batch,
        "full_rebuilds_in_window": m.compile_count - compiles0,
        "generation_bumps_in_window": ledger.generation_bumps - bumps0,
        "oracle_parity": parity,
        "patch": m._base_ct.patch_stats()
        if hasattr(m._base_ct, "patch_stats") else None,
        "patch_ledger": {
            "flushes": ledger.patch_flushes,
            "mutations": ledger.patch_mutations,
            "rows": ledger.patch_rows,
            "bytes": ledger.patch_bytes,
        },
        "install_s": round(install_s, 1),
    }
    log(f"[{name}] {json.dumps(out)}")
    try:
        path = os.path.join(_REPO, "bench_results", "churn_last.json")
        # same guard as last_good: a down-scaled smoke run must never
        # clobber the full-population churn record
        keep = True
        try:
            with open(path) as f:
                if n_subs < json.load(f).get("n_subs", 0):
                    keep = False
        except (OSError, ValueError):
            pass
        if keep:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w") as f:
                json.dump(dict(out, measured_at=time.strftime(
                    "%Y-%m-%dT%H:%M:%S")), f, indent=1)
    except OSError as e:  # noqa: BLE001 — persistence is best-effort
        log(f"churn record write failed: {e}")
    return out


def bench_config9():
    """Ingest byte-plane A/B (ISSUE 11): publish-side topic prep measured
    on the topic-diversity corpus (realistic level-count / byte-length /
    unicode mix, not `bench/a/b`) across the three tokenizer paths —

    - **python** — the per-message loop (split + per-level hashlib), the
      r01 138K-topics/s wall;
    - **native** — the byte plane: one contiguous TopicBytes pack + the
      C++ tokenizer (numpy-vectorized BLAKE2b as the no-toolchain leg);
    - **device** — raw bytes shipped to the jit'd device hash program
      (its rate is reported, not gated).

    The acceptance bar: byte-plane prep ≥10× the python loop at batch
    ≥1024, exact three-way parity, and the matcher-integrated leg must
    attribute a `tokenize` stage on every device batch in the profiler
    split. Stamps record["tokenize"].
    """
    import asyncio

    from bifromq_tpu import workloads
    from bifromq_tpu.models import bytetok
    from bifromq_tpu.models.automaton import tokenize
    from bifromq_tpu.models.bytetok import TopicBytes
    from bifromq_tpu.models.matcher import TpuMatcher

    n_subs = min(N_SUBS, int(os.environ.get("BENCH_TOK_SUBS", "50000")))
    batch = max(1024, min(BATCH, 4096))
    iters = max(8, ITERS // 2)
    name = f"c9_ingest_{n_subs}"
    tries = workloads.config_wildcard(n_subs, seed=SEED)
    m = TpuMatcher.from_tries(tries, match_cache=False, auto_compact=False)
    ct = m._base_ct
    corpus = workloads.diverse_topics(batch * 4, seed=SEED + 11)
    batches = [corpus[i * batch:(i + 1) * batch] for i in range(4)]
    roots = [ct.root_of("tenant0")] * batch

    def timed(fn, legs=iters):
        fn(0)   # warm (jit / native lib load / cache shape)
        s = time.perf_counter()
        for it in range(legs):
            fn(it)
        return batch * legs / (time.perf_counter() - s)

    # --- per-message python loop (the r01 wall: one tokenize per
    # publish, split + per-level hashlib — the pre-batching shape) -----
    def py_leg(it):
        for t in batches[it % 4]:
            tokenize([t], roots[:1], max_levels=ct.max_levels,
                     salt=ct.salt, native=False)
    py_rate = timed(py_leg, legs=2)
    # batched python loop (one call per batch, still per-row inside):
    # reported for transparency, not the A/B baseline
    py_batched = timed(lambda it: tokenize(
        batches[it % 4], roots, max_levels=ct.max_levels, salt=ct.salt,
        native=False), legs=max(4, iters // 4))
    # --- byte plane, native C++ (pack cost included — honest) -------------
    nat_rate = timed(lambda it: tokenize(
        TopicBytes.from_topics(batches[it % 4]), roots,
        max_levels=ct.max_levels, salt=ct.salt))
    # --- byte plane, vectorized numpy (no-toolchain fallback) -------------
    np_rate = timed(lambda it: bytetok.tokenize_bytes(
        TopicBytes.from_topics(batches[it % 4]), roots,
        max_levels=ct.max_levels, salt=ct.salt))
    # --- device hash program ---------------------------------------------
    from bifromq_tpu.ops.tokenize import device_tokenize

    def dev_leg(it):
        _, p = device_tokenize(TopicBytes.from_topics(batches[it % 4]),
                               roots, max_levels=ct.max_levels,
                               salt=ct.salt, batch=batch)
        np.asarray(p.tok_h1)
    dev_rate = timed(dev_leg, legs=max(4, iters // 4))

    # --- three-way parity on one batch ------------------------------------
    tb0 = TopicBytes.from_topics(batches[0])
    py = tokenize(batches[0], roots, max_levels=ct.max_levels,
                  salt=ct.salt, native=False)
    nat = tokenize(tb0, roots, max_levels=ct.max_levels, salt=ct.salt)
    h1, h2, ln, _, sm = bytetok.tokenize_bytes(
        tb0, roots, max_levels=ct.max_levels, salt=ct.salt)
    mirror, probes = device_tokenize(tb0, roots, max_levels=ct.max_levels,
                                     salt=ct.salt, batch=batch)
    sup = mirror.lengths >= 0
    parity = (np.array_equal(py.tok_h1, nat.tok_h1)
              and np.array_equal(py.tok_h1, h1)
              and np.array_equal(py.tok_h2, h2)
              and np.array_equal(py.lengths, ln)
              and np.array_equal(py.sys_mask, sm)
              and np.array_equal(np.asarray(probes.tok_h1)[sup],
                                 py.tok_h1[sup]))

    # --- matcher-integrated leg: tokenize stage on every device batch -----
    from bifromq_tpu.obs import OBS
    prev = os.environ.get("BIFROMQ_DEVICE_TOKENIZE")
    os.environ["BIFROMQ_DEVICE_TOKENIZE"] = "1"
    try:
        rec0 = OBS.profiler.batches_total

        async def run():
            for i in range(8):
                sub = [("tenant0", t)
                       for t in batches[i % 4][:256]]
                await m.match_batch_async(sub, batch=256)
        asyncio.run(run())
    finally:
        if prev is None:
            os.environ.pop("BIFROMQ_DEVICE_TOKENIZE", None)
        else:
            os.environ["BIFROMQ_DEVICE_TOKENIZE"] = prev
    n_new = OBS.profiler.batches_total - rec0
    # n_new == 0 must yield an EMPTY window, not the whole ring ([-0:]):
    # stale records from earlier configs would let the tokenize-stage
    # verdict pass vacuously on exactly the regression it exists to catch
    recs = OBS.profiler.records()[-n_new:] if n_new else []
    dev_batches = [r for r in recs if r.kernel != "oracle"]
    tokenized_all = bool(dev_batches) and all(
        r.tokenize_s > 0 for r in dev_batches)
    split = OBS.profiler.split_snapshot()

    out = {
        "batch": batch,
        "corpus": "diverse_topics",
        "python_topics_per_s": round(py_rate, 1),
        "python_batched_topics_per_s": round(py_batched, 1),
        "native_topics_per_s": round(nat_rate, 1),
        "numpy_topics_per_s": round(np_rate, 1),
        "device_topics_per_s": round(dev_rate, 1),
        "speedup_native_vs_python": round(nat_rate / max(1e-9, py_rate),
                                          2),
        "speedup_numpy_vs_python": round(np_rate / max(1e-9, py_rate), 2),
        "three_way_parity": parity,
        "device_supported_frac": round(float(sup.mean()), 4),
        "tokenize_stage_on_every_device_batch": tokenized_all,
        "profiler_tokenize_ms_p50": split.get("tokenize_ms_p50"),
    }
    log(f"[{name}] {json.dumps(out)}")
    return out


def bench_config10():
    """Mixed million-client workload (ISSUE 13 tentpole part 4): every
    serving plane measured under one realistic population instead of
    isolation — see the module docstring for the leg list. The retained
    flood leg IS the acceptance gate shape: >=10k SET/CLEAR mutations
    against the patched index with zero full rebuilds and exact scan
    parity before, during and after the storm."""
    import asyncio
    import random as _random
    from collections import Counter

    from bifromq_tpu import workloads
    from bifromq_tpu.dist.service import GroupFanoutBalancer
    from bifromq_tpu.models.matcher import TpuMatcher
    from bifromq_tpu.models.retained import RetainedIndex, match_filter_host
    from bifromq_tpu.obs import OBS
    from bifromq_tpu.retained_plane import DrainGovernor, RetainedScanPlane
    from bifromq_tpu.types import RouteMatcher, RouteMatcherType
    from bifromq_tpu.models.oracle import Route

    n_clients = int(os.environ.get("BENCH_MIX_CLIENTS", "100000"))
    retained_ops = int(os.environ.get("BENCH_MIX_RETAIN_OPS", "10000"))
    name = f"c10_mixed_{n_clients}"
    t0 = time.perf_counter()
    plan = workloads.config_mixed(n_clients, seed=SEED,
                                  retained_ops=retained_ops)
    gen_s = time.perf_counter() - t0
    log(f"[{name}] plan: {plan['n_clients']} clients, qos {plan['qos_mix']}, "
        f"{len(plan['retained_seed'])} retained base, "
        f"{len(plan['retained_flood'])} flood ops ({gen_s:.1f}s)")

    # ---- leg 1: route table (transient + persistent + $share) -------------
    t0 = time.perf_counter()
    m = TpuMatcher.from_tries(plan["subscriptions"], match_cache=True)
    build_s = time.perf_counter() - t0

    # ---- leg 2: retained flood against the PATCHED index ------------------
    idx = RetainedIndex(k_states=K_STATES)
    t0 = time.perf_counter()
    for tenant, levels in plan["retained_seed"]:
        idx.add_topic(tenant, levels, "/".join(levels))
    ct = idx.refresh()
    retained_compile_s = time.perf_counter() - t0
    plane = RetainedScanPlane(lambda: idx)
    rebuilds0, compactions0 = idx.rebuilds, idx.compactions

    sample = plan["scan_filters"][:32]

    def parity_sample():
        got = idx.match_batch(sample)
        for (tenant, f), g in zip(sample, got):
            trie = idx.tries.get(tenant)
            want = sorted(match_filter_host(trie, list(f))) if trie else []
            if sorted(g) != want:
                return False
        return True

    parity_before = parity_sample()
    flood = plan["retained_flood"]
    scan_lat_during = []
    t0 = time.perf_counter()
    for i, (op, tenant, levels) in enumerate(flood):
        if op == "set":
            idx.add_topic(tenant, levels, "/".join(levels))
        else:
            idx.remove_topic(tenant, levels, "/".join(levels))
        if i % 1024 == 512:
            s0 = time.perf_counter()
            idx.match_batch(sample[:8], limit=10)
            scan_lat_during.append(time.perf_counter() - s0)
    flood_s = time.perf_counter() - t0
    parity_during = parity_sample()
    zero_rebuilds = idx.rebuilds == rebuilds0
    parity_after = parity_sample()

    # ---- leg 3: async wildcard scans through the retain.scan plane --------
    batches = [plan["scan_filters"][i:i + 64]
               for i in range(0, len(plan["scan_filters"]), 64)]

    async def scan_all():
        lats = []
        for b in batches:
            s0 = time.perf_counter()
            await plane.scan_batch(b, limit=10)
            lats.append(time.perf_counter() - s0)
        return lats

    asyncio.run(scan_all())        # warm (jit + cache fill probes)
    scan_lats = asyncio.run(scan_all())
    cache0 = dict(plane.cache.snapshot()) if plane.cache else {}
    repeat_lats = asyncio.run(scan_all())   # repeat pass: cache hits
    cache1 = dict(plane.cache.snapshot()) if plane.cache else {}
    rpt_hits = cache1.get("hits", 0) - cache0.get("hits", 0)
    rpt_miss = cache1.get("misses", 0) - cache0.get("misses", 0)

    # ---- leg 4: publish matching under concurrent session churn -----------
    pub_batches = [[(t, topic) for t, topic, _q in plan["publishes"][i:i + 64]]
                   for i in range(0, min(len(plan["publishes"]), 1024), 64)]
    for b in pub_batches:
        m.match_batch(b)           # warm
    churn = plan["session_churn"]
    match_lat, churn_lat = [], []
    ci = 0
    t0 = time.perf_counter()
    for bi, b in enumerate(pub_batches * 4):
        for _ in range(4):
            if ci < len(churn):
                op, tenant, levels, rid = churn[ci]
                ci += 1
                mt = RouteMatcher(type=RouteMatcherType.NORMAL,
                                  filter_levels=tuple(levels),
                                  mqtt_topic_filter="/".join(levels))
                s0 = time.perf_counter()
                if op == "sub":
                    m.add_route(tenant, Route(matcher=mt, broker_id=0,
                                              receiver_id=rid,
                                              deliverer_key="d0"))
                else:
                    m.remove_route(tenant, mt, (0, rid, "d0"))
                m._flush_patches()
                churn_lat.append(time.perf_counter() - s0)
        s0 = time.perf_counter()
        m.match_batch(b)
        match_lat.append(time.perf_counter() - s0)
    mix_s = time.perf_counter() - t0

    # ---- leg 5: $share election balance (balanced vs random) --------------
    members = [Route(matcher=RouteMatcher(
                        type=RouteMatcherType.UNORDERED_SHARE,
                        filter_levels=("t", "#"),
                        mqtt_topic_filter="$share/g/t/#", group="g"),
                     broker_id=0, receiver_id=f"w{i}",
                     deliverer_key="d0") for i in range(16)]
    bal = GroupFanoutBalancer(_random.Random(SEED))
    for _ in range(4096):
        bal.pick("T", "$share/g/t/#", members)
    bspread = bal.spread("T", "$share/g/t/#")
    rng = _random.Random(SEED)
    rcounts = Counter(members[rng.randrange(16)].receiver_id
                      for _ in range(4096))

    # ---- leg 6: governed reconnect drain storm ----------------------------
    async def drain_storm():
        gov = DrainGovernor(slots=16, per_tenant=4,
                            noisy_fn=lambda t: False)
        waits = {}

        async def one(tenant, _inbox, backlog):
            s0 = time.perf_counter()
            async with gov.slot(tenant):
                await asyncio.sleep(backlog * 2e-5)  # simulated page pump
            waits.setdefault(tenant, []).append(time.perf_counter() - s0)

        await asyncio.gather(*(one(*d) for d in plan["drain_plan"]))
        herd = waits.pop("tenant0", [0.0])
        quiet = [w for ws in waits.values() for w in ws] or [0.0]
        return {
            "herd_sessions": len(herd),
            "quiet_sessions": len(quiet),
            "herd_mean_ms": round(1e3 * sum(herd) / len(herd), 2),
            "quiet_mean_ms": round(1e3 * sum(quiet) / len(quiet), 2),
            "tenant_fair": (sum(quiet) / len(quiet))
            <= (sum(herd) / len(herd)) * 1.5 + 0.005,
            "governor": gov.snapshot(),
        }

    drain = asyncio.run(drain_storm())

    def pct(xs, q):
        return round(float(np.percentile(np.array(xs or [0.0]), q)) * 1e3, 3)

    out = {
        "n_clients": plan["n_clients"],
        "qos_mix": plan["qos_mix"],
        "plan_gen_s": round(gen_s, 1),
        "route_table_build_s": round(build_s, 1),
        "retained": {
            "base_topics": len(plan["retained_seed"]),
            "flood_ops": len(flood),
            "compile_s": round(retained_compile_s, 1),
            "flood_ops_per_s": round(len(flood) / max(1e-9, flood_s), 1),
            "full_rebuilds_in_flood": idx.rebuilds - rebuilds0,
            "compactions_in_flood": idx.compactions - compactions0,
            "zero_rebuilds": zero_rebuilds,
            "patch_fallbacks": idx.patch_fallbacks,
            "scan_parity_before_during_after": [
                parity_before, parity_during, parity_after],
            "scan_p99_ms_during_flood": pct(scan_lat_during, 99),
            "patch": (idx._compiled.patch_stats()
                      if hasattr(idx._compiled, "patch_stats") else None),
        },
        "scan": {
            "filters": len(plan["scan_filters"]),
            "batch_p50_ms": pct(scan_lats, 50),
            "batch_p99_ms": pct(scan_lats, 99),
            "repeat_batch_p50_ms": pct(repeat_lats, 50),
            "repeat_hit_rate": round(
                rpt_hits / max(1, rpt_hits + rpt_miss), 3),
            "degraded": dict(plane.degraded_total),
        },
        "publish_mix": {
            "match_p50_ms": pct(match_lat, 50),
            "match_p99_ms": pct(match_lat, 99),
            "churn_patch_p99_ms": pct(churn_lat, 99),
            "churn_ops": ci,
            "wall_s": round(mix_s, 1),
            "matcher_rebuilds": m.compile_count,
        },
        "share_balance": {
            "members": 16, "elections": 4096,
            "balanced_spread": bspread["max"] - bspread["min"],
            "random_spread": max(rcounts.values()) - min(rcounts.values()),
        },
        "drain_storm": drain,
        "slo_top5": [
            {"tenant": r.get("tenant"), "score": r.get("score")}
            for r in OBS.tenants_snapshot(top_k=5,
                                          emit=False)["tenants"]],
    }
    log(f"[{name}] {json.dumps(out)}")
    return out


def bench_config11():
    """Sharded-mesh serving config (ISSUE 15): the multi-chip matcher as
    a first-class serving plane on the (emulated or real) device mesh —

    - builds BENCH_MESH_SUBS logical subscriptions across
      BENCH_MESH_SHARDS shards (BENCH_MESH_REPLICAS replica rows; on CPU
      run under XLA_FLAGS=--xla_force_host_platform_device_count=8) with
      one HOT TENANT replicated into every shard,
    - asserts per-shard ``ShardedTables.device_bytes()`` stays ≤ the
      ``CapacityPlanner.fits`` per-shard prediction (the ISSUE 9
      multichip gate, at serving scale),
    - measures async mesh match p50/p99 through the shared dispatch
      ring, per-shard patch-apply p99 under an interleaved
      BENCH_MESH_CHURN_OPS churn storm (acceptance: ZERO full rebuilds,
      ZERO match-cache generation bumps, ≥100× cheaper than the mesh
      rebuild, exact oracle parity after the storm), and the
      replicated-hot-tenant fan-out spread over the grid.

    Stamps record["mesh"].
    """
    import asyncio

    from bifromq_tpu import workloads
    from bifromq_tpu.models.oracle import Route
    from bifromq_tpu.obs import OBS
    from bifromq_tpu.obs.capacity import CapacityPlanner
    from bifromq_tpu.parallel.sharded import MeshMatcher, make_mesh
    from bifromq_tpu.types import RouteMatcher

    import jax

    n_subs = int(os.environ.get("BENCH_MESH_SUBS", "200000"))
    n_shards = int(os.environ.get("BENCH_MESH_SHARDS", "8"))
    n_replicas = int(os.environ.get("BENCH_MESH_REPLICAS", "1"))
    churn_ops = int(os.environ.get("BENCH_MESH_CHURN_OPS", "400"))
    need = n_shards * n_replicas
    if len(jax.devices()) < need:
        log(f"[c11_mesh] SKIP: {need} devices needed, "
            f"{len(jax.devices())} present (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} on CPU)")
        return {"skipped": True, "devices": len(jax.devices())}
    name = f"c11_mesh_{n_subs}x{n_replicas}r{n_shards}s"
    mesh = make_mesh(n_replicas, n_shards)

    def mk(tf, rid, inc=0):
        return Route(matcher=RouteMatcher.from_topic_filter(tf),
                     broker_id=0, receiver_id=rid, deliverer_key="d0",
                     incarnation=inc)

    t0 = time.perf_counter()
    tries = workloads.config_multi_tenant(
        n_tenants=max(n_shards * 4,
                      int(os.environ.get("BENCH_MESH_TENANTS", "64"))),
        total_subs=n_subs, seed=SEED)
    # hot tenant to replicate across every shard: a mid-rank tenant —
    # big enough to matter, small enough that S physical copies don't
    # dominate the per-shard byte budget (tenant0 under Zipf is ~20%)
    hot = sorted(tries, key=lambda t: -len(tries[t]))[
        min(7, len(tries) - 1)]
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    m = MeshMatcher.from_tries(tries, mesh=mesh, match_cache=False,
                               replicate={hot})
    install_s = time.perf_counter() - t0
    rebuild_s = m._last_compile_s
    tables = m._base_ct
    logical = sum(len(t) for t in tries.values())
    log(f"[{name}] base: gen {build_s:.1f}s, compile+install "
        f"{install_s:.1f}s (mesh rebuild {rebuild_s:.1f}s), "
        f"logical_subs={logical} hot={hot} ({len(tries[hot])} subs "
        f"replicated x{n_shards})")

    # --- capacity: per-shard padded bytes vs the planner prediction ----
    db = tables.device_bytes()
    worst = max(p["padded_bytes"] for p in db["per_shard"])
    slots_ref = max(1, max(ct.n_slots for ct in tables.compiled))
    n_max = max(ct.node_tab.shape[0] for ct in tables.compiled)
    e_max = max(1, max(
        int(np.count_nonzero(ct.edge_tab.reshape(-1, 4)[:, 0] >= 0))
        for ct in tables.compiled))
    buckets = tables.edge_tab.shape[1]
    planner = CapacityPlanner(
        nodes_per_sub=n_max / slots_ref, edges_per_sub=e_max / slots_ref,
        slots_per_sub=1.0,
        edge_load=e_max / (buckets * tables.probe_len),
        calibrated_from=f"c11:{slots_ref}subs/shard")
    fits = planner.fits(slots_ref * n_shards, mesh=(n_replicas, n_shards),
                        probe_len=tables.probe_len)
    predicted = fits["tables"]["total"]
    cap_ok = worst <= predicted

    # --- serving: async mesh match latency through the dispatch ring ---
    ledger = OBS.profiler.ledger
    compiles0, bumps0 = m.compile_count, ledger.generation_bumps
    tenants = sorted(tries)
    topics = workloads.probe_topics(1024, seed=SEED + 1)
    batch = 256
    rng = np.random.default_rng(SEED)

    def probe_batch(i, tenant=None):
        rows = topics[(i * batch) % 512:(i * batch) % 512 + batch]
        ts = ([tenant] * batch if tenant else
              [tenants[int(j)] for j in rng.integers(0, len(tenants),
                                                     batch)])
        return list(zip(ts, rows))

    async def serve():
        match_lat, hot_lat, patch_lat = [], [], []
        for wb in range(2):     # warm the grid shapes + scatter jits
            await m.match_batch_async(probe_batch(wb))
        # the hot-tenant batch concentrates rows into fewer slots → a
        # different pow2 grid shape; warm it too or its first serve
        # pays the XLA trace inside the measured window
        await m.match_batch_async(probe_batch(0, tenant=hot))
        m.add_route(hot, mk("bench/mesh/warm/+", "w0"))
        m._flush_patches()
        added = []
        for i in range(churn_ops):
            tf = f"bench/mesh/{i}/+"
            tenant = tenants[i % len(tenants)]
            s0 = time.perf_counter()
            m.add_route(tenant, mk(tf, f"c{i}", inc=1))
            m._flush_patches()
            patch_lat.append(time.perf_counter() - s0)
            added.append((tenant, tf, f"c{i}"))
            if i % 8 == 4:
                s0 = time.perf_counter()
                await m.match_batch_async(probe_batch(i))
                match_lat.append(time.perf_counter() - s0)
            if i % 16 == 8:
                s0 = time.perf_counter()
                await m.match_batch_async(probe_batch(i, tenant=hot))
                hot_lat.append(time.perf_counter() - s0)
        for tenant, tf, rid in added[:churn_ops // 2]:
            s0 = time.perf_counter()
            m.remove_route(tenant, RouteMatcher.from_topic_filter(tf),
                           (0, rid, "d0"), incarnation=1)
            m._flush_patches()
            patch_lat.append(time.perf_counter() - s0)
        return match_lat, hot_lat, patch_lat

    match_lat, hot_lat, patch_lat = asyncio.run(serve())

    # --- oracle parity after the storm ---------------------------------
    probe = probe_batch(3)[:128]
    probe += [(tenants[i % len(tenants)], f"bench/mesh/{i}/x")
              for i in range(0, churn_ops, 7)]
    got = m.match_batch(probe)
    want = m.match_from_tries(probe)

    def canon(r):
        return (sorted((x.matcher.mqtt_topic_filter, x.receiver_url)
                       for x in r.normal),
                {f: sorted(x.receiver_url for x in ms)
                 for f, ms in r.groups.items()})
    parity = all(canon(a) == canon(b) for a, b in zip(got, want))

    # --- expand A/B: device-bucketed serve vs host-expansion serve -----
    # (ISSUE 19) same pre-generated batches through the full serving path
    # under BIFROMQ_DEVICE_EXPAND=0 (legacy psum merge + host expansion)
    # vs =1 (walk-only step + device expand step returning per-peer
    # buckets, no full-grid host merge). The common MatchedRoutes
    # materialization dilutes the ratio — the undiluted kernel-level A/B
    # is config 2's expand_ab record.
    expand_ab = None
    if EXPAND_AB_MODE:
        ab_iters = int(os.environ.get("BENCH_MESH_AB_ITERS", "8"))
        ab_batches = [probe_batch(100 + i) for i in range(ab_iters)]
        prev_mode = os.environ.get("BIFROMQ_DEVICE_EXPAND")

        def _serve_leg(mode):
            os.environ["BIFROMQ_DEVICE_EXPAND"] = mode
            m.match_batch(ab_batches[0])   # warm this mode's traces
            n = 0
            s0 = time.perf_counter()
            for b in ab_batches:
                for r in m.match_batch(b):
                    n += len(r.normal) + sum(len(ms) for ms
                                             in r.groups.values())
            return n, time.perf_counter() - s0

        try:
            host_n, host_s = _serve_leg("0")
            dev_n, dev_s = _serve_leg("1")
        finally:
            if prev_mode is None:
                os.environ.pop("BIFROMQ_DEVICE_EXPAND", None)
            else:
                os.environ["BIFROMQ_DEVICE_EXPAND"] = prev_mode
        expand_ab = {
            "device_matched_routes_per_s": round(dev_n / dev_s, 1),
            "host_matched_routes_per_s": round(host_n / host_s, 1),
            "speedup": round((dev_n / dev_s)
                             / max(1e-9, host_n / host_s), 2),
            "route_count_parity": host_n == dev_n,
            "device_peer_buckets": m.last_expanded is not None,
            "iters": ab_iters,
            "batch": batch,
            "basis": ("full mesh serve incl host MatchedRoutes"
                      " materialization (common to both legs)"),
        }
        log(f"[{name}] expand-ab {json.dumps(expand_ab)}")

    def pct(xs, q):
        return round(float(np.percentile(np.array(xs or [0.0]), q)) * 1e3,
                     3)
    patch_p99 = pct(patch_lat, 99)
    out = {
        "n_subs": n_subs,
        "logical_subs": logical,
        "mesh": {"replicas": n_replicas, "shards": n_shards},
        "build_s": round(build_s, 1),
        "mesh_rebuild_s": round(rebuild_s, 2),
        "capacity": {
            "worst_shard_padded_bytes": worst,
            "predicted_per_shard_bytes": predicted,
            "per_shard_under_prediction": cap_ok,
            "pad_waste_ratio": db["pad_waste_ratio"],
            "per_shard": db["per_shard"],
        },
        "match_ms": {"batch": batch, "p50": pct(match_lat, 50),
                     "p99": pct(match_lat, 99)},
        "hot_tenant_fanout_ms": {"tenant": hot, "p50": pct(hot_lat, 50),
                                 "p99": pct(hot_lat, 99)},
        "patch_apply_ms": {"p50": pct(patch_lat, 50), "p99": patch_p99},
        "patch_vs_rebuild_speedup": round(
            rebuild_s / max(1e-9, patch_p99 / 1e3), 1),
        "churn_ops": len(patch_lat),
        "full_rebuilds_in_window": m.compile_count - compiles0,
        "generation_bumps_in_window": ledger.generation_bumps - bumps0,
        "oracle_parity": parity,
        "expand_ab": expand_ab,
        "patch_flushes": m.patch_flushes,
        "patch_fallbacks": m.patch_fallbacks,
        "shard_breakers": [br.state if br else None
                           for br in m.shard_breakers],
    }
    log(f"[{name}] {json.dumps(out)}")
    return out


def bench_config12():
    """Config 12 — c12_reshard (ISSUE 17): live tenant migration vs the
    full mesh rebuild. A Zipf-skewed population on a replicas x shards
    mesh; the whale tenant live-migrates off its hot shard through the
    begin/copy/ready/cutover/tombstone ladder while async match batches
    keep serving THROUGH the dual-serve window. Reports migration
    wall-clock vs the mesh rebuild (the zero-rebuild dividend), match
    p50/p99 during the window, skew before/after, and the zero-rebuild /
    zero-generation-bump acceptance bits. Stamps record["reshard"]."""
    import asyncio

    from bifromq_tpu import workloads
    from bifromq_tpu.models.oracle import Route
    from bifromq_tpu.obs import OBS
    from bifromq_tpu.parallel.reshard import ShardLoadModel
    from bifromq_tpu.parallel.sharded import MeshMatcher, make_mesh
    from bifromq_tpu.types import RouteMatcher

    import jax

    n_subs = int(os.environ.get("BENCH_RESHARD_SUBS", "200000"))
    n_shards = int(os.environ.get("BENCH_RESHARD_SHARDS", "8"))
    n_replicas = int(os.environ.get("BENCH_RESHARD_REPLICAS", "1"))
    chunk = int(os.environ.get("BENCH_RESHARD_CHUNK", "256"))
    need = n_shards * n_replicas
    if len(jax.devices()) < need:
        log(f"[c12_reshard] SKIP: {need} devices needed, "
            f"{len(jax.devices())} present (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} on CPU)")
        return {"skipped": True, "devices": len(jax.devices())}
    name = f"c12_reshard_{n_subs}x{n_replicas}r{n_shards}s"
    mesh = make_mesh(n_replicas, n_shards)

    def mk(tf, rid):
        return Route(matcher=RouteMatcher.from_topic_filter(tf),
                     broker_id=0, receiver_id=rid, deliverer_key="d0",
                     incarnation=0)

    tries = workloads.config_multi_tenant(
        n_tenants=max(n_shards * 4,
                      int(os.environ.get("BENCH_RESHARD_TENANTS", "64"))),
        total_subs=n_subs, seed=SEED)
    whale = max(tries, key=lambda t: len(tries[t]))
    t0 = time.perf_counter()
    m = MeshMatcher.from_tries(tries, mesh=mesh, match_cache=False)
    install_s = time.perf_counter() - t0
    rebuild_s = m._last_compile_s
    m.query_heat[whale] = 65536
    tables = m._base_ct
    tenants = sorted(tries)
    logical = sum(len(t) for t in tries.values())
    src = tables.shard_of(whale)
    per_shard = [0] * n_shards
    for t in tenants:
        per_shard[tables.shard_of(t)] += len(tries[t])
    dst = min((s for s in range(n_shards) if s != src),
              key=lambda s: per_shard[s])
    log(f"[{name}] base: compile+install {install_s:.1f}s (mesh rebuild "
        f"{rebuild_s:.1f}s), logical_subs={logical}, whale={whale} "
        f"({len(tries[whale])} subs) shard{src} -> shard{dst}")

    model = ShardLoadModel()
    skew0 = model.skew(model.rows(m))
    topics = workloads.probe_topics(1024, seed=SEED + 1)
    batch = 256
    rng = np.random.default_rng(SEED)

    def probe_batch(i):
        rows = topics[(i * batch) % 512:(i * batch) % 512 + batch]
        return [(tenants[int(j)], t) for j, t in
                zip(rng.integers(0, len(tenants), batch), rows)]

    ledger = OBS.profiler.ledger
    compiles0, bumps0 = m.compile_count, ledger.generation_bumps

    async def migrate_and_serve():
        for wb in range(2):      # warm grid shapes outside the window
            await m.match_batch_async(probe_batch(wb))
        window_lat = []
        t0 = time.perf_counter()
        mig = m.migrate_tenant(whale, src, dst, run=False)
        i = 0
        while mig.state == "copying":
            done = mig.step(chunk)
            s0 = time.perf_counter()
            await m.match_batch_async(probe_batch(i))
            window_lat.append(time.perf_counter() - s0)
            i += 1
            if done:
                break
        # dual-serve window: both shards answer for the whale
        s0 = time.perf_counter()
        await m.match_batch_async(probe_batch(i))
        window_lat.append(time.perf_counter() - s0)
        mig.cutover()
        while not mig.finish():
            await asyncio.sleep(0)
        migrate_s = time.perf_counter() - t0
        return mig, migrate_s, window_lat

    mig, migrate_s, window_lat = asyncio.run(migrate_and_serve())
    # the ladder's own cost: the window wall-clock minus the serving
    # batches deliberately interleaved into it (those are the point of a
    # LIVE migration, but they are serving time, not migration time)
    ladder_s = max(1e-9, migrate_s - sum(window_lat))
    skew1 = model.skew(model.rows(m))

    probe = probe_batch(5)[:192]
    got = m.match_batch(probe)
    want = m.match_from_tries(probe)

    def canon(r):
        return (sorted((x.matcher.mqtt_topic_filter, x.receiver_url)
                       for x in r.normal),
                {f: sorted(x.receiver_url for x in ms)
                 for f, ms in r.groups.items()})
    parity = all(canon(a) == canon(b) for a, b in zip(got, want))

    def pct(xs, q):
        return round(float(np.percentile(np.array(xs or [0.0]), q)) * 1e3,
                     3)
    out = {
        "n_subs": n_subs,
        "logical_subs": logical,
        "mesh": {"replicas": n_replicas, "shards": n_shards},
        "mesh_rebuild_s": round(rebuild_s, 2),
        "whale": {"tenant": whale, "subs": len(tries[whale]),
                  "src": src, "dst": dst},
        "migrate_s": round(migrate_s, 3),
        "migrate_ladder_s": round(ladder_s, 3),
        "migrated_routes": mig.copied_n,
        "migrate_vs_rebuild_speedup": round(rebuild_s / ladder_s, 1),
        "match_during_window_ms": {"batch": batch,
                                   "p50": pct(window_lat, 50),
                                   "p99": pct(window_lat, 99)},
        "skew": {"before": round(skew0, 3), "after": round(skew1, 3)},
        "full_rebuilds_in_window": m.compile_count - compiles0,
        "generation_bumps_in_window": ledger.generation_bumps - bumps0,
        "oracle_parity": parity,
        "patch_fallbacks": m.patch_fallbacks,
        "map_version": tables.map_version,
    }
    log(f"[{name}] {json.dumps(out)}")
    return out


def bench_broker():
    """End-to-end MQTT broker throughput over loopback TCP: QoS0/QoS1
    publish → dist match (device matcher) → local fan-out → delivery.
    The BROKER-plane number (supplement to the match-kernel configs);
    enable with "b" in BENCH_CONFIGS."""
    import asyncio

    from bifromq_tpu.mqtt.broker import MQTTBroker
    from bifromq_tpu.mqtt.client import MQTTClient

    n_subs = int(os.environ.get("BENCH_BROKER_SUBS", "20"))
    n_msgs = int(os.environ.get("BENCH_BROKER_MSGS", "2000"))
    n_pubs = max(1, int(os.environ.get("BENCH_BROKER_PUBS", "4")))

    from bifromq_tpu.plugin.settings import DefaultSettingProvider, Setting

    class BenchSettings(DefaultSettingProvider):
        """Raise the per-session publish-rate guard (MsgPubPerSec defaults
        to 200/s — the throughput bench would trip ExceedPubRate)."""

        def provide(self, setting, tenant_id):
            if setting is Setting.MsgPubPerSec:
                return 100_000_000
            return super().provide(setting, tenant_id)

    # per-stage latency breakdown (ISSUE 2): the hot path feeds the
    # always-on stage histograms (ingest / queue_wait / device / deliver,
    # + rpc in clustered mode) whether or not span sampling is enabled —
    # reset here so the breakdown covers exactly this run
    from bifromq_tpu.utils.metrics import MATCH_CACHE, STAGES
    STAGES.reset()
    MATCH_CACHE.reset()
    # ISSUE 20: e2e delivery-latency plane — reset so the per-qos
    # publish->deliver rollup stamped below covers exactly this run
    from bifromq_tpu.obs import OBS
    OBS.e2e.reset()

    async def run():
        broker = MQTTBroker(host="127.0.0.1", port=0,
                            settings=BenchSettings())
        await broker.start()
        subs = []
        for i in range(n_subs):
            c = MQTTClient("127.0.0.1", broker.port, client_id=f"bs{i}")
            await c.connect()
            await c.subscribe(f"bench/{i}/t", qos=0)
            subs.append(c)
        pubs = []
        for i in range(n_pubs):
            p = MQTTClient("127.0.0.1", broker.port, client_id=f"bp{i}")
            await p.connect()
            pubs.append(p)
        pub = pubs[0]
        # QoS0 ingest: n_pubs concurrent publishers fire n_msgs total,
        # one matching subscriber each
        per_pub = n_msgs // n_pubs

        async def fire(p, base):
            for i in range(per_pub):
                await p.publish(f"bench/{(base + i) % n_subs}/t", b"x",
                                qos=0)
        t0 = time.perf_counter()
        await asyncio.gather(*[fire(p, j * per_pub)
                               for j, p in enumerate(pubs)])
        sent = per_pub * n_pubs
        # barrier: all deliveries drained
        got = 0
        deadline = asyncio.get_event_loop().time() + 60
        while got < sent and asyncio.get_event_loop().time() < deadline:
            pending = sum(s.messages.qsize() for s in subs)
            if pending >= sent:
                got = pending
                break
            await asyncio.sleep(0.01)
        qos0_dt = time.perf_counter() - t0
        delivered = sum(s.messages.qsize() for s in subs)
        # QoS1 round-trips (ack-gated, serial per publisher)
        t0 = time.perf_counter()
        for i in range(min(n_msgs, 500)):
            await pub.publish(f"bench/{i % n_subs}/t", b"x", qos=1)
        qos1_dt = time.perf_counter() - t0
        for c in subs + pubs:
            await c.disconnect()
        await broker.stop()
        return {
            # honest rate: only messages that actually ARRIVED count
            "qos0_pub_to_deliver_msgs_per_s": round(delivered / qos0_dt, 1),
            "qos0_delivered": delivered,
            "qos0_published": sent,
            "qos1_acked_pubs_per_s": round(min(n_msgs, 500) / qos1_dt, 1),
            "subscribers": n_subs,
            "publishers": n_pubs,
        }

    out = asyncio.run(run())
    out["stage_latency_ms"] = STAGES.snapshot()
    # ISSUE 4: hit rate + dedup ratio next to the stage breakdown — how
    # much of the publish path the match-result cache actually absorbed
    out["match_cache"] = MATCH_CACHE.snapshot()
    # ISSUE 20: per-qos e2e snapshot (p50/p99 publish->deliver + SLO
    # violations) rides the bench record next to the stage breakdown
    out["e2e"] = OBS.e2e.qos_rollup()
    log(f"[broker_e2e] {json.dumps(out)}")
    return out


def main():
    from bifromq_tpu.utils.jaxenv import setup_compile_cache
    setup_compile_cache()
    # one process per chip: the device is initialised HERE, in the only
    # process that will use it. No device, no run — a failed init raises
    # and the bench exits non-zero; no stored record is ever printed.
    import jax
    log(f"devices: {jax.devices()}")
    results = {}
    if "1" in CONFIGS:
        results["c1"] = bench_config1()
    headline = None
    if "2" in CONFIGS:
        results["c2"] = bench_config2()
        headline = results["c2"]
    if "3" in CONFIGS:
        results["c3"] = bench_config3()
    if "4" in CONFIGS:
        results["c4"] = bench_config4()
    if "5" in CONFIGS:
        results["c5"] = bench_config5()
    if "6" in CONFIGS:
        results["c6"] = bench_config6()
    if "7" in CONFIGS:
        results["c7"] = bench_config7()
    if "8" in CONFIGS:
        results["c8"] = bench_config8()
    if "9" in CONFIGS:
        results["c9"] = bench_config9()
    if "10" in CONFIGS:
        results["c10"] = bench_config10()
    if "11" in CONFIGS:
        results["c11"] = bench_config11()
    if "12" in CONFIGS:
        results["c12"] = bench_config12()
    if "b" in CONFIGS:
        results["broker"] = bench_broker()

    log(f"extras: {json.dumps(results)}")
    stock_topics, stock_routes, basis = load_stock_baseline()
    record = None
    if headline is not None and "routes" in headline:
        # THE honest headline (VERDICT r4 #1): e2e matched routes/s vs the
        # measured stock matched-routes rate, identical c2 workload
        r = headline["routes"]
        value = r["e2e_matched_routes_per_s"]
        record = {
            "metric": f"e2e_matched_routes@{N_SUBS}_wildcard_subs",
            "value": value,
            "unit": "routes/s",
            "vs_baseline": _vs(value, stock_routes),
            "baseline_basis": basis,
            "stock_matched_routes_per_s": stock_routes,
            "e2e_topics_per_s": r["e2e_topics_per_s"],
            "vs_stock_topics": _vs(r["e2e_topics_per_s"], stock_topics),
            "e2e_p50_ms": r["e2e_p50_ms"],
            "e2e_p99_ms": r["e2e_p99_ms"],
        }
    elif headline is not None:
        value = headline["topics_per_s"]
        record = {
            "metric": f"device_match_throughput@{N_SUBS}_wildcard_subs",
            "value": value,
            "unit": "topics/s",
            "vs_baseline": _vs(value, stock_topics),
            "baseline_basis": basis,
        }
    else:
        # no config-2 run: fall back to any config with a comparable rate
        for key, r in results.items():
            if "topics_per_s" in r:
                record = {
                    "metric": f"device_match_throughput_{key}",
                    "value": r["topics_per_s"],
                    "unit": "topics/s",
                    "vs_baseline": _vs(r["topics_per_s"], stock_topics),
                    "baseline_basis": basis,
                }
                break
        else:
            if "c4" in results:
                r = results["c4"]
                record = {
                    "metric": "retained_match_throughput_c4",
                    "value": r.get("filters_per_s", 0.0),
                    "unit": "filters/s",
                    "vs_baseline": _vs(r.get("filters_per_s", 0.0),
                                       stock_topics),
                    "baseline_basis": basis,
                }
            else:
                r = results.get("broker", {})
                record = {
                    "metric": "broker_e2e_qos0",
                    "value": r.get("qos0_pub_to_deliver_msgs_per_s", 0.0),
                    "unit": "msgs/s",
                    "vs_baseline": 0.0,
                    "baseline_basis": "broker-plane loopback (no stock "
                                      "broker in image)",
                }
    record["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    record["platform"] = jax.devices()[0].platform
    record["device_kind"] = jax.devices()[0].device_kind
    record["device_count"] = len(jax.devices())
    record["n_subs"] = N_SUBS
    # pipeline A/B next to the headline (ISSUE 6): the dispatch/ready/
    # fetch stage split + the sync-vs-pipelined batch-latency comparison
    if "c7" in results:
        record["pipeline"] = {
            "batch_p99_speedup": results["c7"]["batch_p99_speedup"],
            "sync_batch_p99_ms": results["c7"]["sync"]["batch_p99_ms"],
            "pipelined_batch_p99_ms":
                results["c7"]["pipelined"]["batch_p99_ms"],
            "stage_latency_ms": results["c7"]["stage_latency_ms"],
        }
    # churn cell next to the headline (ISSUE 9): patch-apply latency vs
    # the full rebuild, zero-rebuild/zero-bump window, oracle parity
    if "c8" in results:
        c8 = results["c8"]
        record["churn"] = {
            "n_subs": c8["n_subs"],
            "full_rebuild_s": c8["full_rebuild_s"],
            "patch_apply_ms": c8["patch_apply_ms"],
            "patch_vs_rebuild_speedup": c8["patch_vs_rebuild_speedup"],
            "match_p99_ms_during_churn": c8["match_p99_ms_during_churn"],
            "full_rebuilds_in_window": c8["full_rebuilds_in_window"],
            "generation_bumps_in_window":
                c8["generation_bumps_in_window"],
            "oracle_parity": c8["oracle_parity"],
        }
    # ingest byte-plane cell next to the headline (ISSUE 11): the
    # three-way prep A/B + parity verdict and the profiler's tokenize
    # attribution — every record carries the tokenize story
    if "c9" in results:
        c9 = results["c9"]
        record["tokenize"] = {
            "python_topics_per_s": c9["python_topics_per_s"],
            "native_topics_per_s": c9["native_topics_per_s"],
            "device_topics_per_s": c9["device_topics_per_s"],
            "speedup_native_vs_python": c9["speedup_native_vs_python"],
            "three_way_parity": c9["three_way_parity"],
            "tokenize_stage_on_every_device_batch":
                c9["tokenize_stage_on_every_device_batch"],
        }
    # mixed-workload breakdown next to the headline (ISSUE 13): the
    # retained-flood zero-rebuild verdict, scan parity/latency, drain
    # fairness and share balance under the realistic population
    if "c10" in results:
        c10 = results["c10"]
        record["mixed"] = {
            "n_clients": c10["n_clients"],
            "retained": {k: c10["retained"][k] for k in (
                "flood_ops", "flood_ops_per_s", "full_rebuilds_in_flood",
                "compactions_in_flood", "zero_rebuilds",
                "scan_parity_before_during_after")},
            "scan": c10["scan"],
            "publish_mix": c10["publish_mix"],
            "share_balance": c10["share_balance"],
            "drain_tenant_fair": c10["drain_storm"]["tenant_fair"],
        }
    # sharded-mesh cell next to the headline (ISSUE 15): mesh match
    # latency, per-shard patch p99 under the churn storm, shard count,
    # per-shard bytes vs the planner prediction
    if "c11" in results and not results["c11"].get("skipped"):
        c11 = results["c11"]
        record["mesh"] = {
            "logical_subs": c11["logical_subs"],
            "shards": c11["mesh"]["shards"],
            "replicas": c11["mesh"]["replicas"],
            "match_p50_ms": c11["match_ms"]["p50"],
            "match_p99_ms": c11["match_ms"]["p99"],
            "patch_p99_ms": c11["patch_apply_ms"]["p99"],
            "patch_vs_rebuild_speedup": c11["patch_vs_rebuild_speedup"],
            "full_rebuilds_in_window": c11["full_rebuilds_in_window"],
            "generation_bumps_in_window":
                c11["generation_bumps_in_window"],
            "oracle_parity": c11["oracle_parity"],
            "per_shard_bytes": [p["padded_bytes"] for p in
                                c11["capacity"]["per_shard"]],
            "per_shard_under_prediction":
                c11["capacity"]["per_shard_under_prediction"],
            "hot_tenant_fanout_p99_ms":
                c11["hot_tenant_fanout_ms"]["p99"],
        }
    # elastic-mesh cell (ISSUE 17): live-migration wall-clock vs the
    # full mesh rebuild, match p99 THROUGH the dual-serve window, skew
    # before/after — the zero-rebuild dividend as a standing number
    if "c12" in results and not results["c12"].get("skipped"):
        c12 = results["c12"]
        record["reshard"] = {
            "logical_subs": c12["logical_subs"],
            "shards": c12["mesh"]["shards"],
            "whale_subs": c12["whale"]["subs"],
            "migrate_s": c12["migrate_s"],
            "migrate_ladder_s": c12["migrate_ladder_s"],
            "mesh_rebuild_s": c12["mesh_rebuild_s"],
            "migrate_vs_rebuild_speedup":
                c12["migrate_vs_rebuild_speedup"],
            "match_window_p99_ms": c12["match_during_window_ms"]["p99"],
            "skew_before": c12["skew"]["before"],
            "skew_after": c12["skew"]["after"],
            "full_rebuilds_in_window": c12["full_rebuilds_in_window"],
            "generation_bumps_in_window":
                c12["generation_bumps_in_window"],
            "oracle_parity": c12["oracle_parity"],
        }
    # per-stage p50/p99 next to the headline (ISSUE 2): where the broker
    # plane actually spends its time (queue-wait vs device vs deliver)
    stage = results.get("broker", {}).get("stage_latency_ms")
    if stage:
        record["stage_latency_ms"] = stage
    # match-cache disposition next to the stage breakdown (ISSUE 4)
    mc = results.get("broker", {}).get("match_cache")
    if mc:
        record["match_cache"] = mc
    # device-pipeline gauges next to the headline (ISSUE 3): XLA compile
    # count/time, dispatch queue depth, device memory watermarks — the
    # same "device" section /metrics serves
    try:
        from bifromq_tpu.obs import OBS
        record["device"] = OBS.device_snapshot()
        log(f"device gauges: {json.dumps(record['device'])}")
    except Exception as e:  # noqa: BLE001 — gauges must not fail the bench
        log(f"device gauges unavailable: {e!r}")
    # continuous-profiler snapshot on every record (ISSUE 8): the
    # host stage split, padding waste / dedup / cache-bypass efficiency
    # and the compile-event ledger — the same data GET /profile serves,
    # so trajectory records stay analyzable post-hoc
    try:
        from bifromq_tpu.obs import OBS
        record["profile"] = OBS.profiler.snapshot(brief=True)
        log(f"profile: {json.dumps(record['profile'])}")
    except Exception as e:  # noqa: BLE001 — must not fail the bench
        log(f"profile snapshot unavailable: {e!r}")
    # capacity accounting next to it (ISSUE 8): model-vs-live parity for
    # every registered matcher + the planner's verdict for the HEADLINE
    # subscription count on this device
    try:
        from bifromq_tpu.obs.capacity import capacity_report
        record["capacity"] = capacity_report(n_subs=N_SUBS)
        cap = record["capacity"]
        log(f"capacity: table_bytes={cap.get('table_bytes')} "
            f"parity_error={cap.get('parity_error')} "
            f"hbm={json.dumps(cap.get('fits', {}).get('hbm'))}")
    except Exception as e:  # noqa: BLE001 — must not fail the bench
        log(f"capacity report unavailable: {e!r}")
    # persist the profile into the segment store when one is configured
    # (BIFROMQ_OBS_STORE): post-hoc analysis survives the TPU session
    try:
        from bifromq_tpu.obs import OBS
        if OBS.start_persistence():
            OBS.persist_now()
            OBS.stop_persistence(final_flush=False)
    except Exception as e:  # noqa: BLE001
        log(f"profile persistence failed: {e!r}")
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()

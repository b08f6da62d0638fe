#!/usr/bin/env python
"""North-star scale probe (VERDICT r4 #3): build + compile the full-scale
configs HOST-SIDE and record whether the compiled tables fit v5e HBM.

Pure host work — no jax import, no device needed.
Emits chiprun_out/r5_scale_probe.json and saves the packed arrays to
/tmp/scale_tables_<cfg>.npz so a later device run (scale_device_run.py)
can upload without rebuilding (the 10M-sub Python trie build is the slow
part).

Usage: python scripts/scale_probe.py [c5|c4|c2_10m ...]
"""

import json
import os
import resource
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES = 16 * 2 ** 30   # v5e: 16 GiB per chip


def _rss_gb():
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def _compile_and_record(name, rec, tries, *, max_levels):
    """Shared compile→measure→save block (one definition: the HBM
    accounting and npz key set cannot drift between configs)."""
    from bifromq_tpu.models.automaton import compile_tries
    t0 = time.time()
    ct = compile_tries(tries, max_levels=max_levels)
    rec["compile_s"] = round(time.time() - t0, 1)
    rec["n_nodes"] = int(ct.n_nodes)
    rec["n_slots"] = int(ct.n_slots)
    n = ct.node_tab.shape[0]
    tb = {
        "node_tab": int(ct.node_tab.nbytes),
        "edge_tab": int(ct.edge_tab.nbytes),
        "child_list": int(ct.child_list.nbytes),
        # device-side derived tables (ops.match.DeviceTrie.from_compiled):
        # CT_COLS=4 and RT_COLS=8 int32 columns per node
        "count_tab": n * 4 * 4,
        "route_tab": n * 8 * 4,
    }
    tb["total"] = sum(tb.values())
    rec["tables_bytes"] = tb
    rec["fits_hbm_v5e"] = tb["total"] < HBM_BYTES
    rec["hbm_frac"] = round(tb["total"] / HBM_BYTES, 4)
    rec["peak_rss_gb"] = round(_rss_gb(), 1)
    np.savez(f"/tmp/scale_tables_{name}.npz", node_tab=ct.node_tab,
             edge_tab=ct.edge_tab, child_list=ct.child_list,
             salt=np.int64(ct.salt), probe_len=np.int64(ct.probe_len),
             max_levels=np.int64(ct.max_levels))
    with open(f"/tmp/scale_roots_{name}.json", "w") as f:
        json.dump(ct.tenant_root, f)
    return rec


def probe_c5(total_subs=10_000_000, n_tenants=10_000):
    from bifromq_tpu import workloads
    rec = {"config": "c5_multitenant", "n_subs": total_subs,
           "n_tenants": n_tenants}
    t0 = time.time()
    tries = workloads.config_multi_tenant(n_tenants, total_subs, seed=0)
    rec["build_s"] = round(time.time() - t0, 1)
    print(f"[c5] tries built in {rec['build_s']}s rss={_rss_gb():.1f}GB",
          flush=True)
    return _compile_and_record("c5", rec, tries, max_levels=16)


def probe_c4(n_topics=5_000_000):
    from bifromq_tpu import workloads
    from bifromq_tpu.models.oracle import SubscriptionTrie
    from bifromq_tpu.models.retained import _topic_route
    rec = {"config": "c4_retained", "n_retained": n_topics}
    t0 = time.time()
    topics = workloads.config_retained(n_topics, seed=0)["tenant0"]
    trie = SubscriptionTrie()
    for levels in topics:
        trie.add(_topic_route(levels, "/".join(levels)))
    rec["build_s"] = round(time.time() - t0, 1)
    print(f"[c4] trie built in {rec['build_s']}s rss={_rss_gb():.1f}GB",
          flush=True)
    return _compile_and_record("c4", rec, {"tenant0": trie}, max_levels=18)


def probe_c2_10m(n_subs=10_000_000):
    from bifromq_tpu import workloads
    rec = {"config": "c2_wildcard", "n_subs": n_subs}
    t0 = time.time()
    tries = workloads.config_wildcard(n_subs, seed=0)
    rec["build_s"] = round(time.time() - t0, 1)
    print(f"[c2@10M] tries built in {rec['build_s']}s rss={_rss_gb():.1f}GB",
          flush=True)
    return _compile_and_record("c2_10m", rec, tries, max_levels=16)


def main():
    which = sys.argv[1:] or ["c5", "c4"]
    out_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out", "r5_scale_probe.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    results = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)
    for name in which:
        fn = {"c5": probe_c5, "c4": probe_c4, "c2_10m": probe_c2_10m}[name]
        rec = fn()
        rec["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%S")
        results[name] = rec
        print(f"[{name}] {json.dumps(rec)}", flush=True)
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)
    print(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()

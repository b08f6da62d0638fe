"""Mesh scale proof (ISSUE 15 tentpole part 4, slow tier).

Builds MESH_SCALE_SUBS logical subscriptions (default 2M here; the full
10M acceptance run is ``MESH_SCALE_SUBS=10000000``) across the 8-way
host mesh, asserts
per-shard ``device_bytes()`` stays under the ``CapacityPlanner.fits``
per-shard prediction, and serves + patches through the async plane with
zero rebuilds.
"""

import asyncio
import os

import numpy as np
import pytest

from bifromq_tpu import workloads
from bifromq_tpu.models.oracle import Route
from bifromq_tpu.obs.capacity import CapacityPlanner
from bifromq_tpu.parallel.sharded import MeshMatcher, make_mesh
from bifromq_tpu.types import RouteMatcher

pytestmark = [pytest.mark.slow, pytest.mark.asyncio]


def mk(tf, rid):
    return Route(matcher=RouteMatcher.from_topic_filter(tf), broker_id=0,
                 receiver_id=rid, deliverer_key="d0", incarnation=1)


async def _run(n_subs: int, n_shards: int = 8):
    mesh = make_mesh(1, n_shards)
    tries = workloads.config_multi_tenant(n_tenants=64, total_subs=n_subs,
                                          seed=0)
    logical = sum(len(t) for t in tries.values())
    m = MeshMatcher.from_tries(tries, mesh=mesh, match_cache=False)
    tables = m._base_ct

    # per-shard bytes <= the planner's per-shard prediction
    db = tables.device_bytes()
    worst = max(p["padded_bytes"] for p in db["per_shard"])
    slots_ref = max(1, max(ct.n_slots for ct in tables.compiled))
    e_max = max(1, max(
        int(np.count_nonzero(ct.edge_tab.reshape(-1, 4)[:, 0] >= 0))
        for ct in tables.compiled))
    planner = CapacityPlanner(
        nodes_per_sub=max(ct.node_tab.shape[0]
                          for ct in tables.compiled) / slots_ref,
        edges_per_sub=e_max / slots_ref, slots_per_sub=1.0,
        edge_load=e_max / (tables.edge_tab.shape[1] * tables.probe_len))
    predicted = planner.fits(slots_ref * n_shards, mesh=(1, n_shards),
                             probe_len=tables.probe_len)["tables"]["total"]
    assert worst <= predicted, (worst, predicted)

    # serve + patch at scale: async batches, zero rebuilds under churn
    tenants = sorted(tries)
    topics = workloads.probe_topics(512, seed=1)
    qs = [(tenants[i % len(tenants)], t) for i, t in enumerate(topics[:256])]
    await m.match_batch_async(qs)
    c0 = m.compile_count
    for i in range(64):
        m.add_route(tenants[i % len(tenants)], mk(f"scale/{i}/+", f"c{i}"))
        m._flush_patches()
    got = await m.match_batch_async(qs[:64])
    want = m.match_from_tries(qs[:64])

    def canon(r):
        return sorted((x.matcher.mqtt_topic_filter, x.receiver_url)
                      for x in r.normal)
    assert all(canon(a) == canon(b) for a, b in zip(got, want))
    assert m.compile_count == c0
    return logical, worst, predicted


async def test_mesh_scale_under_planner_prediction():
    n = int(os.environ.get("MESH_SCALE_SUBS", "2000000"))
    logical, worst, predicted = await _run(n)
    assert logical >= n * 0.99

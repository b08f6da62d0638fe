"""The harness on a mesh, without a chip (PR 38).

``rehearsal_mesh_20k.rehearsal_closed`` is ``tenant_fleet_4k.zipf_sat_mesh4``
at 20,000 rows over 12 tenants: a broker started with ``dist.mesh`` on
four forced host devices (``--xla_force_host_platform_device_count=4``),
seeded by ``sut.seed_worker`` with a four-shard ``MeshMatcher``, driven
through the same entry, window, drain and comparison as a chip run.

- a sound run is correct, the mesh's own step serves every batch, the
  tables lie on four devices, and a traced run prints the mesh's metrics;
- both controls come out not correct;
- ``seed_worker`` seats what the broker started, one chip or mesh, and
  either's rows equal the plain reference's on 200 seeded pairs;
- the readers the mesh cell adds, on synthetic inputs; and the recorded
  one-plane trace still reduces to the busy time written down with it.

    python3 -m pytest benchmarks/tests/test_mesh_rehearsal.py -q   (about 90 s, CPU)
"""

import importlib
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

BENCH = os.path.join(HERE, "rehearsal_mesh_bench.json")
CELL = "rehearsal_mesh_20k.rehearsal_closed"
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           XLA_FLAGS="--xla_force_host_platform_device_count=4")
MESH_METRICS = ("shard_ready_ms.tput", "mesh_flush_ms.tput",
                "fullest_shard_row_share.tput")


def run_cell(*extra: str):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "4",
         "--rehearse-cpu", "--bench-file", BENCH, *extra],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


def over_limit(line: dict) -> dict:
    # (a 4 s window on the CPU may catch a step compiling for a new pad
    # size; every other comparison has to hold)
    return {k: v for k, v in line["compared"].items()
            if isinstance(v[1], int) and v[0] > v[1]
            and k != "compiles_in_window"}


def test_sound_traced_run_on_four_devices():
    line, stdout = run_cell("--trace", "1")
    assert not over_limit(line), over_limit(line)
    c = line["compared"]
    assert c["tables_off_device"] == [0, 0] and c["oracle_batches"] == [0, 0]
    assert c["mesh_batches"][0] == c["device_batches"][0] > 0
    kernels = re.search(r"by kernel (\{[^}]*\})", stdout).group(1)
    assert "'mesh'" in kernels and "oracle" not in kernels, kernels
    assert line["device"]["count"] == 4
    assert "16 per-shard patch programs warmed" in stdout
    assert "_shard_scatter" not in str(re.findall(
        r"compiled INSIDE the window: (.*)", stdout))
    for name in MESH_METRICS:
        assert line["metrics"][name]["value"] > 0, sorted(line["metrics"])
    assert 25.0 <= line["metrics"][MESH_METRICS[2]]["value"] <= 100.0
    # a CPU run never yields a device number
    assert not any(k.startswith(("walk_roofline", "device_idle",
                                 "shard_busy", "collective"))
                   for k in line["metrics"])


@pytest.mark.parametrize("control", ["truncate64", "drop_one"])
def test_broken_guarantee_is_not_correct_on_a_mesh(control):
    line, _ = run_cell("--trace", "0", "--control", control)
    assert line["correct"] is False
    assert line["compared"]["fleet_mismatch"][0] > 0


# ------------------------------------------------ what seed_worker seats

@pytest.fixture(scope="module", params=[0, 1], ids=["one_chip", "mesh"])
def seat(request):
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "mesh_seat_check.py"),
         str(request.param)],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return request.param, json.loads(out.stdout.strip().splitlines()[-1])


def test_seed_worker_seats_what_the_broker_started(seat):
    mesh, got = seat
    want = "MeshMatcher" if mesh else "TpuMatcher"
    assert got["started"] == got["seated"] == want
    assert got["seat_is_workers"] and got["n_shards"] == (4 if mesh else 0)


def test_seated_matchers_rows_equal_the_references(seat):
    _mesh, got = seat
    assert got["differ"] == 0 and got["matched"] > 200


def test_device_state_and_table_shapes(seat):
    mesh, got = seat
    n = 4 if mesh else 1
    st = got["state"]
    assert st["n_devices"] == st["each_on"] == len(st["bytes_each"]) == n
    assert st["resident_bytes"] == max(st["bytes_each"]) > 0
    # row widths are the same whatever holds the tables
    assert st["record_bytes"]["edge_tab"] == 256
    assert st["record_bytes"]["route_tab"] == 32
    assert ("node_tab" in st["record_bytes"]) is (not mesh)
    # the warmed programs left the tables where and as wide as they were
    assert got["state_after_warm"] == st
    assert got["warmed"] == (16 if mesh else 0)
    node, edge, child = got["shapes"]
    if mesh:
        assert node[0] == edge[0] == child[0] == 4 and len(edge) == 4
        assert all(len(v) == 4 for v in got["fill"].values())
        assert "mesh.rows_each" in got["counter_keys"]
    else:
        assert len(node) == 2 and len(edge) == 3 and len(child) == 1
        assert all(isinstance(v, int) for v in got["fill"].values())
        assert "mesh.rows_each" not in got["counter_keys"]


# ------------------------------------------------------- the new readers

def reader(name):
    return importlib.import_module(f"readers.{name}")


MESH_CTX = {
    "totals": {"device.shard_ready": (200, 0.5)},
    "before": {"patch.device_s": 1.0, "patch.flushes": 10,
               "mesh.rows_each": [100, 100, 100, 100]},
    "after": {"patch.device_s": 1.3, "patch.flushes": 110,
              "mesh.rows_each": [500, 300, 200, 200]},
    "trace": {"busy_each": [0.4, 0.2, 0.1, 0.1], "collective_s": 0.04,
              "busy_s": 0.2, "window_s": 4.0},
}
ONE_CHIP_CTX = {
    "totals": {}, "before": {"patch.device_s": 1.0, "patch.flushes": 10},
    "after": {"patch.device_s": 1.3, "patch.flushes": 110},
    "trace": {"busy_each": [0.4], "collective_s": 0.0, "busy_s": 0.4,
              "window_s": 4.0},
}


@pytest.mark.parametrize("name,want", [
    ("shard_ready_ms", 2.5),              # 0.5 s / 200
    ("mesh_flush_ms", 3.0),               # 0.3 s / 100 flushes
    ("shard_busy_skew", 2.0),             # 0.4 / mean 0.2
    ("collective_share", 5.0),            # 0.04 / 0.8
    ("fullest_shard_row_share", 50.0),    # 400 of 800 rows
])
def test_mesh_reader(name, want):
    assert reader(name).read(dict(MESH_CTX)) == pytest.approx(want)
    # one chip has nothing for them to read: left out, never 0
    assert reader(name).read(dict(ONE_CHIP_CTX)) is None


def test_recorded_one_plane_trace_reads_as_it_did():
    import trace_reduce
    with open(os.path.join(BENCH_DIR, "testdata",
                           "small_trace.expected.json")) as f:
        want = json.load(f)
    got = trace_reduce.reduce_trace(
        os.path.join(BENCH_DIR, "testdata", "small_trace.xplane.pb"),
        want["window_s"])
    assert got["busy_s"] == pytest.approx(want["busy_s"], abs=1e-12)
    assert got["busy_each"] == [got["busy_s"]] and got["device_planes"] == 1
    assert got["collective_s"] == 0
    assert not trace_reduce.COLLECTIVE.match("fusion.12")
    for op in ("collective-permute.3", "collective-permute-start.1",
               "all-reduce.7", "%all-gather.2", "all-to-all", "reduce-scatter.1"):
        assert trace_reduce.COLLECTIVE.match(op), op


def test_every_metric_file_of_the_mesh_cell_has_its_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cell = "tenant_fleet_4k.zipf_sat_mesh4"
    assert any(w["name"] == cell and w["chips"] == 4
               for w in bench["workloads"])
    for name in ("shard_ready_ms", "mesh_flush_ms", "shard_busy_skew",
                 "collective_share", "fullest_shard_row_share"):
        with open(os.path.join(BENCH_DIR, "layer_metrics",
                               f"{name}.tput.json")) as f:
            spec = json.load(f)
        assert spec.pop("reader") == name
        assert spec == entries[f"{name}.tput"]
        assert spec["workloads"] == [cell]
    # every .tput metric the one-chip fleet cell reports, the mesh cell too
    for m in bench["per_layer"]:
        if "tenant_fleet_1k.zipf_sat" in m.get("workloads", ()):
            assert cell in m["workloads"], m["name"]

"""The seven readers of the program's window totals (PR 29).

Each returns ``None`` where the totals hold no sample of what it reads
(a parent commit has no totals at all) and the right number on synthetic
ones; and a CPU rehearsal with ``--trace 1`` prints every one of them.

    python3 -m pytest benchmarks/tests/test_span_readers.py -q   (about 25 s, CPU)
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

BENCH = os.path.join(HERE, "rehearsal_spans_bench.json")

# name -> (n, total_s): a 10 s window of 100 publishes, 50 device batches
# and 4 live SUBSCRIBEs
SYNTHETIC = {
    "pub.ingest": (100, 2.0), "dist.pub": (100, 1.5),
    "loop.lag": (500, 0.25),
    "device.dispatch": (50, 0.05), "device.ready": (50, 0.15),
    "device.fetch.wait": (50, 0.05), "ready.polls": (120, 0.0),
    "sub.route": (4, 0.2), "kv.resort": (6, 0.1),
    "deliver.fanout": (100, 1.0), "deliver.call": (6400, 0.6),
    "deliver.routes": (200_000, 0.0),
}
EXPECTED = {
    "frontend_self_ms": 5.0,            # (2.0 - 1.5) s / 100
    "loop_lag_ms": 0.5,                 # 0.25 s / 500
    "device_wait_ms": 4.0,              # (0.15 + 0.05) s / 50
    "ready_polls_per_batch": 2.4,       # 120 / 50
    "sub_apply_ms": 50.0,               # 0.2 s / 4
    "kv_resort_ms": 25.0,               # 0.1 s / 4 SUBSCRIBEs
    "fanout_self_us_per_route": 2.0,    # (1.0 - 0.6) s / 200,000
}


def reader(name):
    return importlib.import_module(f"readers.{name}")


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_synthetic_totals(name):
    assert reader(name).read({"totals": dict(SYNTHETIC)}) == \
        pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_returns_none(name):
    assert reader(name).read({"totals": {}}) is None
    # a program with no window totals (the parent): the helper gives {}
    assert reader(name).read({"before": {}, "after": {}}) is None


@pytest.mark.parametrize("missing,name", [
    ("dist.pub", "frontend_self_ms"), ("device.dispatch", "device_wait_ms"),
    ("device.dispatch", "ready_polls_per_batch"),
    ("sub.route", "kv_resort_ms"), ("kv.resort", "kv_resort_ms"),
    ("deliver.call", "fanout_self_us_per_route"),
    ("deliver.routes", "fanout_self_us_per_route")])
def test_reader_with_one_name_missing_returns_none(missing, name):
    totals = {k: v for k, v in SYNTHETIC.items() if k != missing}
    assert reader(name).read({"totals": totals}) is None


def test_every_new_metric_has_its_file_and_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        for spelling, moves in (("lat", "deliver_p50_ms"),
                                ("tput", "delivered_per_s")):
            with open(os.path.join(BENCH_DIR, "layer_metrics",
                                   f"{name}.{spelling}.json")) as f:
                spec = json.load(f)
            entry = dict(entries[f"{name}.{spelling}"])
            assert spec.pop("reader") == name
            # a later PR that adds a cell lists it in BENCHMARK.json and
            # may not edit the metric's own file: the file names the
            # cells the metric came with, the entry those and the later
            assert set(spec.pop("workloads")) <= set(entry.pop("workloads"))
            assert spec == entry and entry["moves"] == moves


@pytest.mark.parametrize("workload,spelling", [
    ("rehearsal_20k.rehearsal_open", "lat"),
    ("rehearsal_20k.rehearsal_closed", "tput")])
def test_cpu_rehearsal_prints_every_new_metric(workload, spelling):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"),
         "--workload", workload, "--seed", "2147483659", "--seconds", "4",
         "--trace", "1", "--rehearse-cpu", "--bench-file", BENCH],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    # (a 4 s window on the CPU may catch a patch scatter compiling for a
    # new pad size; every other comparison has to hold)
    over = {k: v for k, v in line["compared"].items()
            if isinstance(v[1], int) and v[0] > v[1]
            and k != "compiles_in_window"}
    assert not over, over
    for name in EXPECTED:
        # every new name a CPU run can have: all seven read the host
        value = line["metrics"][f"{name}.{spelling}"]["value"]
        assert value is not None and value >= 0, (name, value)
    # the earlier host metrics are still there, and not doubled: the
    # deliver stage's mean per route stays within the fan-out's own span
    assert line["metrics"][f"deliver_us_per_route.{spelling}"]["value"] > \
        line["metrics"][f"fanout_self_us_per_route.{spelling}"]["value"] > 0
    assert "[bench] window totals" in out.stdout

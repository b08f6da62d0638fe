"""The point-to-point cell's shape without a chip, and which control bites.

``rehearsal_p2p_20k.rehearsal_p2p`` is ``device_command_1m.p2p_sat`` at
4,000 devices x 5 exact filters (``--rehearse-cpu``). Fan-out is 1 (the
device) + 1 (the tap), so ``truncate64`` has nothing to cut and comes out
CORRECT here: the control that bites on this deployment is ``drop_one``,
which takes the publish's last route, the device's or the tap's.

    python3 -m pytest benchmarks/tests/test_p2p_rehearsal.py -q   (about 60 s, CPU)
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(HERE, "rehearsal_p2p_bench.json")
CELL = "rehearsal_p2p_20k.rehearsal_p2p"
NEW_METRICS = ("match_cache_hit_share.tput", "unsub_apply_ms.tput")


def run_cell(*extra: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "4",
         "--rehearse-cpu", "--bench-file", BENCH, *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_traced_run_prints_the_new_metrics():
    line = run_cell("--trace", "1")
    assert line["correct"] is True, line["compared"]
    for name in NEW_METRICS:
        assert name in line["metrics"], sorted(line["metrics"])
    assert 0.0 <= line["metrics"][NEW_METRICS[0]]["value"] <= 100.0


@pytest.mark.parametrize("control,bites", [("drop_one", True),
                                           ("truncate64", False)])
def test_which_control_bites(control, bites):
    line = run_cell("--trace", "0", "--control", control)
    assert line["correct"] is (not bites), line["compared"]
    if bites:
        c = line["compared"]
        assert c["fleet_mismatch"][0] + c["live_missing"][0] > 0

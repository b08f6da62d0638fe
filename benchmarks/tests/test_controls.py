"""The comparison that decides ``correct`` has to be able to fail.

Each case skips the harness's look for a chip (``--rehearse-cpu``) and
drives the rest of a run at a size a test can hold (20,000 rows), through
the same entry, window, drain and comparison as a chip run:

- a sound run comes out correct;
- the CONTROL (``truncate64``: a row's matches cut at the device's 64
  slots, the host re-expansion left out) comes out not correct;
- an answer altered where it is produced (``drop_one``: the matcher's
  result loses one route) comes out not correct.

Of the faults the builder's contract lists, a broker on one chip can have
only the last: it has no training state, no batch mean and no exchange
between chips.

    python3 -m pytest benchmarks/tests -q        (about 50 s, CPU)
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "tests", "rehearsal_bench.json")


def run_cell(workload: str, *extra: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", workload, "--seed", "2147483659", "--seconds", "3",
         "--trace", "0", "--rehearse-cpu", "--bench-file", BENCH, *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["rehearsal_20k.rehearsal_open",
                                      "rehearsal_20k.rehearsal_closed"])
def test_sound_run_is_correct(workload):
    line = run_cell(workload)
    assert line["correct"] is True, line["compared"]
    assert line["device"]["platform"] == "cpu"
    assert not any(k.startswith(("walk_roofline", "device_idle"))
                   for k in line["metrics"])


@pytest.mark.parametrize("control", ["truncate64", "drop_one"])
def test_broken_guarantee_is_not_correct(control):
    line = run_cell("rehearsal_20k.rehearsal_open", "--control", control)
    assert line["correct"] is False
    assert line["compared"]["fleet_mismatch"][0] > 0


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "rehearsal_20k.rehearsal_open", "--seed", "1",
         "--seconds", "1", "--trace", "0", "--bench-file", BENCH],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert not out.stdout.strip().endswith("}")

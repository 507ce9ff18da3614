"""The check that decides ``correct``, at a size a test run can hold.

The control (the plain reference one precision lower, in the program's
place) has to fail a cell's limits, and a run whose timed path is broken
underneath has to come out ``correct: false``, once for each fault the
cell can have. Each faulty run is a process of its own, on CPU devices,
with the harness's look for a chip skipped.
"""
import json
import os
import subprocess
import sys

import pytest

import calibrate
from tests import tiny

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**33 + 17

FAULTY_RUN = """
import json, sys
sys.path.insert(0, {bench!r})
import run
from harness import faults
from tests import tiny
faults.plant({fault!r})
r = run.run_cell(tiny.cell({workload!r}), {seed}, 1.0, False, require_tpu=False)
print(json.dumps({{"correct": r["correct"], "checks": r["checks"]}}))
"""

CELL_FAULTS = [
    ("sleipner-serve-ensemble", None),
    ("sleipner-serve-ensemble", "answer_altered"),
    ("sleipner-serve-realizations", "answer_altered"),
    ("sleipner-train", None),
    ("sleipner-train", "state_unchanged"),
]


@pytest.mark.parametrize("workload,fault", CELL_FAULTS)
def test_fault_makes_run_incorrect(workload, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = FAULTY_RUN.format(bench=BENCH_DIR, fault=fault, workload=workload, seed=SEED)
    if fault is None:
        code = code.replace("faults.plant(None)", "")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600, cwd=BENCH_DIR)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is (fault is None), result


@pytest.mark.parametrize("workload", ["sleipner-serve-ensemble",
                                      "sleipner-serve-realizations", "sleipner-train"])
def test_control_fails(workload):
    """The sound run is judged correct and the control (the reference at
    three bf16 passes, in the program's place) is not, by the verdict that
    decides a run's ``correct`` and under the limits set on the chip."""
    cell = tiny.cell(workload)
    r = calibrate.readings(cell, SEED, 1.0, control=True, require_tpu=False)
    assert r["sound_correct"] is True, r
    assert r["control_correct"] is False, r

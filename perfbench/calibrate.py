"""Readings that the correctness limits are set from (not part of a run).

    python3 perfbench/calibrate.py --workload NAME --seeds 1,2,3 \
        --seconds S [--control-seeds 4,5,6] [--fault NAME]

In one process, for each of ``--seeds``: the cell's set-up and a short
window at its own load, then the numbers its check compares (the sound
readings). For each of ``--control-seeds`` the same, and then the control:
the plain reference put in the program's place and computed one precision
below what the configuration states (``high``, three bf16 passes, for
float32 at ``highest``), judged by the same comparison, limits and verdict
that decide a run's ``correct``. One JSON line per seed: the numbers
compared, ``sound_correct`` and ``control_correct``, and what a driver
reports beside them (the serve drivers: each gap at each rollout step).
With ``--fault``, a fault of ``harness/faults.py`` is planted in the
program first: its "sound" readings are then the fault's readings.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402  (sets the import paths)
from harness import cell as cell_lib  # noqa: E402


def readings(cell, seed: int, seconds: float, control: bool, require_tpu=True) -> dict:
    devs = run.devices_for(cell.chips, require_tpu)
    driver = cell.driver().Driver(cell, cell.model(), devs, seed, run.Spans())
    driver.setup()
    driver.window(seconds)
    driver.release()
    gc.collect()
    out = {"seed": seed}
    runs = [("sound", None)]
    if control:
        runs.append(("control", driver.control_outputs()))
    for name, got in runs:
        checks = driver.check(got)
        out[name] = {k: v["value"] for k, v in checks.items()}
        out[f"{name}_correct"] = run.verdict(checks)
        out.update({f"{name}_{k}": v for k, v in getattr(driver, "detail", {}).items()})
    del driver
    gc.collect()
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    if args.fault:
        from harness import faults

        faults.plant(args.fault)
    cell = cell_lib.load(args.workload)
    for s in filter(None, args.seeds.split(",")):
        print(json.dumps(readings(cell, int(s), args.seconds, False)), flush=True)
    for s in filter(None, args.control_seeds.split(",")):
        print(json.dumps(readings(cell, int(s), args.seconds, True)), flush=True)


if __name__ == "__main__":
    main()
